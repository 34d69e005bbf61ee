"""The passes round the grouped matmuls, as Pallas TPU kernels that touch
only the rows in use.

``moe_gmm.py`` multiplies the tiles in use of a buffer sized for the worst
case. What moves rows into that buffer and out of it again, and the gate
between the two products, were XLA passes on static shapes and paid for the
whole buffer; these kernels follow the same ``n_tiles`` (a pass in row order)
or the assignments whose expert is held (a pass in token order), and at a
full buffer visit everything, as the XLA passes did.

**Rows one at a time.** Mosaic slices a tiled array along its rows only in
whole tiles of 8 (16 for bf16), so a single row of ``(N, C)`` cannot be the
source or the target of a DMA. A row that has to be fetched alone is
therefore kept in a second layout, a *slab*: ``(N * S, W)`` ``uint32``, the
row's ``C`` values cut into chunks of ``W`` lanes, one chunk a sublane (two
for a 16-bit dtype: chunk ``2s`` in the low half of sublane ``s``'s words,
chunk ``2s + 1`` in the high half). A row is then ``S`` sublanes, whole
aligned tiles of 8, ``C * itemsize`` bytes, and the values come back bit for
bit. ``W`` is 128, the one width Mosaic's strided loads take, where a row has
a multiple of 1,024 words (``C = 2,048`` in bf16: ``S = 8``); a narrower row
takes ``S = 8`` and runs in interpret mode, which has no tiling.

- ``moe_rows_pack``: ``(N, C)`` to its slab, tiles in use only.
- ``moe_rows_gather``: row order. ``out[r] = scale[r] * rows[idx[r]]`` for
  the leading ``tile_valid`` rows of each tile in use, zero in the tile's
  padding rows (the grouped matmuls' contract), nothing past ``n_tiles``.
- ``moe_rows_combine``: token order. ``out[t] = sum_s w[t, s] *
  rows[dest[t, s]]`` over the ``held[t]`` leading slots of a token, in fp32.
- ``moe_rows_dot``: token order. ``out[t, s] = rows[dest[t, s]] . d[t]``,
  the gradient of the weights above.
  Both work on the fetched rows as they lie, a token's ``S`` sublanes at a
  time for eight tokens, and only as many slots as one of the eight fills;
  what is dense a token (the weights, ``d``) comes laid out the same way.
- ``moe_rows_gate`` / ``moe_rows_gate_bwd``: ``silu(gate) * up`` in fp32 over
  the tiles in use, and its gradient.

Rows past ``n_tiles * tile_rows`` of any output are left as they come, as
``moe_gmm``'s are: no kernel here or there reads them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_for

__all__ = ["pack", "gather", "combine", "dot", "gate", "TOKEN_TILE"]

TOKEN_TILE = 128      # tokens a step of a token-order kernel takes
_VMEM_LIMIT = 64 * 2**20
_HIGH = 0xFFFF0000
#: XLA lays a 1-D int32 operand out in tiles of 1,024, and Mosaic takes a
#: block of it into SMEM only at that size
_SMEM_BLOCK = 1024


def _slab_shape(C: int, dtype):
    """``(S, W)``: sublanes a row's slab has (whole tiles of 8) and words in
    each; ``W`` is 128, the only width Mosaic's strided loads take, wherever
    the row is wide enough (1,024 words)."""
    per_word = 4 // jnp.dtype(dtype).itemsize
    if per_word not in (1, 2) or C % (8 * per_word):
        raise ValueError(f"a slab takes rows of 16- or 32-bit values, a multiple "
                         f"of {8 * per_word} wide; got {C} of {jnp.dtype(dtype)}")
    words = C // per_word
    S = words // 128 if words % 1024 == 0 else 8
    return S, words // S


def _smem_block(n: int, unit: int) -> int:
    return _SMEM_BLOCK if n % _SMEM_BLOCK == 0 and _SMEM_BLOCK % unit == 0 else unit


def _parts(words, dtype):
    """The values a slab's words hold, in fp32: one array for a 32-bit
    dtype, two for a 16-bit one (the low halves, the high halves)."""
    if jnp.dtype(dtype).itemsize == 4:
        return (jax.lax.bitcast_convert_type(words, dtype).astype(jnp.float32),)
    # a 16-bit float widened to fp32 is its own bits over sixteen zeros
    return (jax.lax.bitcast_convert_type(words << 16, jnp.float32),
            jax.lax.bitcast_convert_type(words & jnp.uint32(_HIGH), jnp.float32))


def _words(tile, s: int, W: int):
    """Sublane ``s`` of the slab of each row of ``tile (n, C)``: ``(n, W)``."""
    if tile.dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(tile[:, s * W:(s + 1) * W], jnp.uint32)
    lo, hi = (jax.lax.bitcast_convert_type(
        tile[:, c * W:(c + 1) * W].astype(jnp.float32), jnp.uint32)
        for c in (2 * s, 2 * s + 1))
    return (lo >> 16) | (hi & jnp.uint32(_HIGH))


def _values(buf_ref, n: int, s: int, S: int, dtype):
    """Back from ``_words``, in fp32: sublane ``s`` of the first ``n`` rows of
    the slab in ``buf_ref``, ``(n, W)`` or, 16-bit, ``(n, 2 W)``; with it the
    first column they are of."""
    parts = _parts(buf_ref[pl.ds(s, n, stride=S), :], dtype)
    return jnp.concatenate(parts, axis=1), len(parts) * s * parts[0].shape[1]


def _params(interpret, *semantics):
    return {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT)}


def _tile(i, nt):                 # a tile out of use names the last in use
    return jnp.minimum(i, nt[0] - 1)


# --- (N, C) to its slab -------------------------------------------------------

def _pack_kernel(nt_ref, a_ref, out_ref, *, S):
    @pl.when(pl.program_id(0) < nt_ref[0])
    def _tile_in_use():
        n = a_ref.shape[0]
        for s in range(S):
            out_ref[pl.ds(s, n, stride=S), :] = _words(a_ref[...], s, out_ref.shape[1])


def pack(a, n_tiles=None, tile_rows: int = 256):
    """The slab ``(N * S, W)`` of ``a (N, C)``; with ``n_tiles (1,)`` only of
    the leading tiles of ``tile_rows`` rows in use."""
    tm = min(tile_rows, a.shape[0])
    if n_tiles is None:
        n_tiles = jnp.full((1,), a.shape[0] // tm, jnp.int32)
    return _pack(a, n_tiles, tm, _interpret_for(a))


# The kernels that cost something to trace are jitted functions of their own:
# a custom VJP traces its primal and its forward rule both, and calls of one
# shape (the combine of the forward pass and of the dispatch's gradient) share
# the trace.

@functools.partial(jax.jit, static_argnums=(2, 3))
def _pack(a, n_tiles, tm, interpret):
    N, C = a.shape
    S, W = _slab_shape(C, a.dtype)
    return pl.pallas_call(
        functools.partial(_pack_kernel, S=S),
        name="moe_rows_pack",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(N // tm,),
            in_specs=[pl.BlockSpec((tm, C), lambda i, nt: (_tile(i, nt), 0))],
            out_specs=pl.BlockSpec((tm * S, W), lambda i, nt: (_tile(i, nt), 0))),
        out_shape=jax.ShapeDtypeStruct((N * S, W), jnp.uint32),
        interpret=interpret, **_params(interpret, "parallel"),
    )(n_tiles, a)


# --- row order: fetch the row each row of a tile names -------------------------

def _fetch(slab_ref, buf_ref, sem, row, slot, S):
    """Start the copy of slab row ``row`` into slot ``slot`` of ``buf_ref``."""
    pltpu.make_async_copy(
        slab_ref.at[pl.ds(pl.multiple_of(row * S, S), S)],
        buf_ref.at[pl.ds(pl.multiple_of(slot * S, S), S)], sem).start()


def _await(buf_ref, sem, copies, S):
    """Wait for ``copies`` row copies into ``buf_ref`` on ``sem``. A DMA
    semaphore counts what has arrived, and a wait takes off what its own
    copy would bring, so one wait stands for a power of two of rows: a dozen
    waits at most where a wait a row costs as much as starting it did."""
    rows = 1
    while rows <= buf_ref.shape[0] // S:
        @pl.when((copies & rows) != 0)
        def _these(rows=rows):
            some = buf_ref.at[pl.ds(0, rows * S)]
            pltpu.make_async_copy(some, some, sem).wait()
        rows *= 2


def _gather_kernel(nt_ref, valid_ref, idx_ref, slab_ref, *rest, dtype, scaled):
    scale_ref = rest[0] if scaled else None
    out_ref, buf_ref, sem = rest[-3:]
    i = pl.program_id(0)

    @pl.when(i < nt_ref[0])
    def _tile_in_use():
        tm = out_ref.shape[0]
        S = buf_ref.shape[0] // tm
        n = valid_ref[i]
        base = (i * tm) % idx_ref.shape[0]       # the tile within its SMEM block

        def start(r, c):
            _fetch(slab_ref, buf_ref, sem, idx_ref[base + r], r, S)
            return c
        jax.lax.fori_loop(0, n, start, 0)
        _await(buf_ref, sem, n, S)
        # what the buffer held before is under the padding rows: select, not scale
        live = jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0) < n
        for s in range(S):
            v, col = _values(buf_ref, tm, s, S, dtype)
            if scaled:
                v = v * scale_ref[...]
            out_ref[:, col:col + v.shape[1]] = jnp.where(live, v, 0).astype(out_ref.dtype)


def gather(slab, like, idx, tile_valid, n_tiles, tile_rows: int, scale=None):
    """``out (R, C)``: row ``r`` is row ``idx[r]`` of the array ``slab`` was
    packed from (times ``scale[r]``, ``(R, 1)`` fp32), for the leading
    ``tile_valid[i]`` rows of tile ``i < n_tiles``; the other rows of a tile
    in use are zero. ``like = (C, dtype)`` of the packed array's rows, and of
    ``out``'s; ``idx`` is read under the valid rows only."""
    return _gather(slab, idx, tile_valid, n_tiles, scale, (like[0], jnp.dtype(like[1])),
                   tile_rows, _interpret_for(slab))


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _gather(slab, idx, tile_valid, n_tiles, scale, like, tm, interpret):
    (C, dtype), R = like, idx.shape[0]
    S, W = _slab_shape(C, dtype)
    sb = _smem_block(R, tm)
    in_specs = [pl.BlockSpec((sb,), lambda i, nt, tv: (_tile(i, nt) * tm // sb,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY)]
    args = [idx, slab]
    if scale is not None:
        in_specs.append(pl.BlockSpec((tm, 1), lambda i, nt, tv: (_tile(i, nt), 0)))
        args.append(scale)
    return pl.pallas_call(
        functools.partial(_gather_kernel, dtype=dtype, scaled=scale is not None),
        name="moe_rows_gather",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(R // tm,), in_specs=in_specs,
            out_specs=pl.BlockSpec((tm, C), lambda i, nt, tv: (_tile(i, nt), 0)),
            scratch_shapes=[pltpu.VMEM((tm * S, W), jnp.uint32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((R, C), dtype),
        interpret=interpret, **_params(interpret, "arbitrary"),
    )(n_tiles, tile_valid, *args)


# --- token order: fetch the rows of the assignments held ------------------------

def _chunk(tt: int, dtype) -> int:
    """Tokens a step of the loop inside a tile of ``tt`` takes: whole tiles
    of the rows they become (16 of a 16-bit dtype, 8 of a 32-bit one)."""
    return next(n for n in (32 // jnp.dtype(dtype).itemsize, 8, 1) if tt % n == 0)


def _fetch_held(dest_ref, held_ref, slab_ref, buf_ref, sem, first, tt, k, S):
    """Fetch ``rows[dest[t, s]]`` for ``s < held[t]`` into slot ``s * tt + t``
    of ``buf_ref``, for the tile's ``tt`` tokens from ``first`` of the block."""
    together = 8 if tt % 8 == 0 else 1      # tokens a step: the scalar core's loop costs a token as much as a copy

    def tokens(g, copies):
        for t in range(together):
            t = g * together + t
            n = held_ref[first + t]

            def start(s, c, t=t):
                _fetch(slab_ref, buf_ref, sem, dest_ref[(first + t) * k + s], s * tt + t, S)
                return c
            jax.lax.fori_loop(0, n, start, 0)
            copies = copies + n
        return copies
    _await(buf_ref, sem, jax.lax.fori_loop(0, tt // together, tokens, jnp.int32(0)), S)


def _by_chunk(held_ref, first, tt, k, S, dtype, slot, before=None, after=None):
    """The tile's tokens a chunk at a time, in the slab's own layout (a token
    is ``S`` sublanes): ``slot(s, at, rows)`` for each slot ``s`` that some
    token of the chunk fills, ``at`` the chunk's first sublane of a slot's
    ``tt * S`` and ``rows`` how many it has."""
    ct = _chunk(tt, dtype)
    rows = ct * S

    def chunk(c, carry):
        at = pl.multiple_of(c * rows, rows)
        most = held_ref[first + c * ct]
        for j in range(1, ct):
            most = jnp.maximum(most, held_ref[first + c * ct + j])
        if before is not None:
            before(at, rows)
        for s in range(k):
            pl.when(s < most)(functools.partial(slot, s, at, rows))
        if after is not None:
            after(at, rows)
        return carry
    jax.lax.fori_loop(0, tt // ct, chunk, 0)


def _combine_kernel(dest_ref, held_ref, slab_ref, w_ref, n_ref, out_ref,
                    buf_ref, sum_ref, sem, *, dtype, k, S):
    tt = out_ref.shape[0]
    first = (pl.program_id(0) * tt) % held_ref.shape[0]   # the tile within its SMEM block
    _fetch_held(dest_ref, held_ref, slab_ref, buf_ref, sem, first, tt, k, S)
    parts = 4 // jnp.dtype(dtype).itemsize      # of a word, each with its own sums

    def before(at, rows):
        sum_ref[...] = jnp.zeros_like(sum_ref)

    def slot(s, at, rows):
        here = pl.ds(at, rows)
        live, w = n_ref[here, :] > s, w_ref[here, s:s + 1]
        for p, part in enumerate(_parts(buf_ref[pl.ds(s * tt * S + at, rows), :], dtype)):
            sum_ref[p * rows:(p + 1) * rows, :] += jnp.where(live, part * w, 0)

    def after(at, rows):                         # the sums' slab back to rows
        tokens = pl.ds(pl.multiple_of(at // S, rows // S), rows // S)
        W = sum_ref.shape[1]
        for sub in range(S):
            for p in range(parts):
                col = (parts * sub + p) * W
                out_ref[tokens, col:col + W] = sum_ref[
                    pl.ds(p * rows + sub, rows // S, stride=S), :].astype(out_ref.dtype)
    _by_chunk(held_ref, first, tt, k, S, dtype, slot, before, after)


def _dot_kernel(dest_ref, held_ref, slab_ref, d_ref, n_ref, out_ref,
                buf_ref, sem, *, dtype, k, S):
    tt = out_ref.shape[0] // S
    first = (pl.program_id(0) * tt) % held_ref.shape[0]
    _fetch_held(dest_ref, held_ref, slab_ref, buf_ref, sem, first, tt, k, S)

    def before(at, rows):
        out_ref[pl.ds(at, rows), :] = jnp.zeros((rows, k), jnp.float32)

    def slot(s, at, rows):
        here = pl.ds(at, rows)
        prod = [v * d for v, d in zip(
            _parts(buf_ref[pl.ds(s * tt * S + at, rows), :], dtype), _parts(d_ref[here, :], dtype))]
        prod = jnp.where(n_ref[here, :] > s, prod[0] if len(prod) == 1 else prod[0] + prod[1], 0)
        out_ref[here, s:s + 1] = jnp.sum(prod, axis=1, keepdims=True)
    _by_chunk(held_ref, first, tt, k, S, dtype, slot, before)


@functools.partial(jax.jit, static_argnums=(0, 1, 3, 7, 8, 9))
def _token_order(kernel, name, slab, like, dest, held, dense, out, sums, interpret):
    """A token-order kernel over tiles of ``TOKEN_TILE`` tokens: the indices
    in SMEM by block, the slab left in HBM, ``dense (T * S, n)`` and each
    token's count (a row a sublane of its slab, as ``dense`` is) by tile;
    ``out = (rows a token, columns, dtype)``; with ``sums`` fp32 room for a
    chunk's sums."""
    (C, dtype), (T, k) = like, dest.shape
    tt = min(TOKEN_TILE, T)
    S, W = _slab_shape(C, dtype)
    sb = _smem_block(T, tt)
    per, cols, out_dtype = out
    scratch = [pltpu.VMEM((k * tt * S, W), jnp.uint32)]
    if sums:
        scratch.append(pltpu.VMEM((4 // dtype.itemsize * _chunk(tt, dtype) * S, W), jnp.float32))
    return pl.pallas_call(
        functools.partial(kernel, dtype=dtype, k=k, S=S),
        name=name, grid=(T // tt,),
        in_specs=[pl.BlockSpec((sb * k,), lambda i: (i * tt // sb,), memory_space=pltpu.SMEM),
                  pl.BlockSpec((sb,), lambda i: (i * tt // sb,), memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec((tt * S, dense.shape[1]), lambda i: (i, 0)),
                  pl.BlockSpec((tt * S, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tt * per, cols), lambda i: (i, 0)),
        scratch_shapes=scratch + [pltpu.SemaphoreType.DMA(())],
        out_shape=jax.ShapeDtypeStruct((T * per, cols), out_dtype),
        interpret=interpret, **_params(interpret, "arbitrary"),
    )(dest.reshape(-1), held, slab, dense, jnp.repeat(held, S)[:, None])


def combine(slab, like, dest, held, weight):
    """``out (T, C)``: ``sum_s weight[t, s] * rows[dest[t, s]]`` over ``s <
    held[t]``, in fp32; ``dest``/``weight (T, k)``, a token's held assignments
    in its leading slots, ``rows`` what ``slab`` was packed from, ``like =
    (C, dtype)`` of them and of ``out``."""
    like = (like[0], jnp.dtype(like[1]))
    S, _ = _slab_shape(*like)
    return _token_order(_combine_kernel, "moe_rows_combine", slab, like, dest, held,
                        jnp.repeat(weight, S, axis=0), (1,) + like, True,
                        _interpret_for(slab))


def dot(slab, like, dest, held, d_slab):
    """``out (T, k)`` fp32: ``rows[dest[t, s]] . d[t]`` for ``s < held[t]``,
    zero in the other slots; ``d_slab`` the slab of ``d (T, C)``."""
    like = (like[0], jnp.dtype(like[1]))
    (T, k), (S, _) = dest.shape, _slab_shape(*like)
    out = _token_order(_dot_kernel, "moe_rows_dot", slab, like, dest, held, d_slab,
                       (S, k, jnp.dtype(jnp.float32)), False, _interpret_for(slab))
    return out.reshape(T, S, k).sum(1)      # a token's sublanes, summed


# --- the gate between the two grouped matmuls ----------------------------------

def _gate_kernel(nt_ref, h_ref, out_ref):
    @pl.when(pl.program_id(0) < nt_ref[0])
    def _tile_in_use():
        F = out_ref.shape[1]
        g, u = (h_ref[:, a:a + F].astype(jnp.float32) for a in (0, F))
        out_ref[...] = (jax.nn.silu(g) * u).astype(out_ref.dtype)


def _gate_bwd_kernel(nt_ref, h_ref, d_ref, out_ref):
    @pl.when(pl.program_id(0) < nt_ref[0])
    def _tile_in_use():
        F = d_ref.shape[1]
        g, u = (h_ref[:, a:a + F].astype(jnp.float32) for a in (0, F))
        d = d_ref[...].astype(jnp.float32)
        sig = jax.nn.sigmoid(g)
        out_ref[:, :F] = (d * u * sig * (1 + g * (1 - sig))).astype(out_ref.dtype)
        out_ref[:, F:] = (d * g * sig).astype(out_ref.dtype)


def _by_tile(kernel, name, n_tiles, tm, out_cols, *arrays):
    R, interpret = arrays[0].shape[0], _interpret_for(arrays[0])
    return pl.pallas_call(
        kernel, name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R // tm,),
            in_specs=[pl.BlockSpec((tm, a.shape[1]), lambda i, nt: (_tile(i, nt), 0))
                      for a in arrays],
            out_specs=pl.BlockSpec((tm, out_cols), lambda i, nt: (_tile(i, nt), 0))),
        out_shape=jax.ShapeDtypeStruct((R, out_cols), arrays[0].dtype),
        interpret=interpret, **_params(interpret, "parallel"),
    )(n_tiles, *arrays)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def gate(h, n_tiles, tile_rows: int):
    """``silu(h[:, :F]) * h[:, F:]`` of ``h (R, 2F)`` in fp32, cast once, over
    the ``n_tiles`` tiles in use; zero rows give zero rows."""
    return _by_tile(_gate_kernel, "moe_rows_gate", n_tiles, tile_rows,
                    h.shape[1] // 2, h)


def _gate_fwd(h, n_tiles, tile_rows):
    return gate(h, n_tiles, tile_rows), (h, n_tiles)


def _gate_bwd(tile_rows, res, d):
    h, n_tiles = res
    return _by_tile(_gate_bwd_kernel, "moe_rows_gate_bwd", n_tiles, tile_rows,
                    h.shape[1], h, d), None


gate.defvjp(_gate_fwd, _gate_bwd)
