"""The gated short convolution as a Pallas TPU kernel pair: one pass over its
operands forward, one backward.

The op (``ops.nn.short_conv_gate``) on ``bcx (B, L, 3C)``, the three
``C``-wide parts ``Bg | Cg | x`` of one projection, and ``w (C, K)``, one
weight a channel and a tap::

    s[t] = Bg[t] * x[t]
    c[t] = sum_k w[:, k] * s[t - (K - 1) + k]        (s zero before the row)
    y[t] = Cg[t] * c[t]

is pure memory traffic (a dozen multiply-adds for each four values moved),
and XLA's way to say it, a grouped convolution over a transposed copy with
the gates as separate passes, moves every value several times. Here the
forward kernel (``short_conv_fwd``) reads ``bcx`` once and writes ``y``
once; the backward kernel (``short_conv_bwd``) reads ``bcx`` and ``dy`` once
and writes ``d bcx`` once, and rebuilds ``s`` and ``c`` on the way: nothing
but the op's inputs is kept between the two. ``d w`` is summed over rows in
fp32, in one output block that stays in VMEM for the whole grid.

**Layout.** Channels on lanes, rows on sublanes; a grid step takes a tile of
``tile_rows`` rows of one sequence at its full ``3C`` width (the three parts
are lane ranges of one block, so ``d bcx`` leaves as one array and nothing
is concatenated). A tap reaches ``K - 1`` rows back, and in the backward
pass as many rows forward: a tile's neighbours come as *halo* blocks of
``ROWS`` rows, the tile before (``s``) and the tile after (``dy * Cg``),
fetched beside the tile and ignored at a sequence's ends. Inside a step the
tile is walked ``ROWS`` rows and ``LANES`` channels at a time, so that every
intermediate is a few vector registers of fp32; a shift by ``d`` rows is one
select between two neighbouring chunks and one sublane rotation.

bf16 or fp32 in and out, fp32 inside. ``L`` is padded up to whole tiles
(zero rows after a row's end change nothing before it, and their gradient
is dropped); ``C`` must be a multiple of 128 on the chip. In interpret mode
(off the TPU: the tests) any size runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_for

__all__ = ["forward", "backward", "supported", "TILE_ROWS", "ROWS"]

TILE_ROWS = 512       # rows a grid step takes
ROWS = 16             # rows a step of the loop inside takes: one bf16 tile; a tap reaches no further
LANES = 256           # channels a step of the loop inside takes
_TAPS = 8             # the taps' block: (8, C) fp32, taps on sublanes
_VMEM_LIMIT = 100 * 2**20


def supported(bcx, w) -> bool:
    """Do the kernels take this call on the chip: ``C`` whole lane tiles,
    at most 8 taps (the taps' block), bf16 or fp32?"""
    C, K = w.shape
    return (bcx.ndim == 3 and bcx.shape[-1] == 3 * C and C % 128 == 0 and 1 <= K <= _TAPS
            and bcx.dtype in (jnp.bfloat16, jnp.float32))


def _lanes(C: int) -> int:
    return next((n for n in (LANES, 128) if C % n == 0), C)


def _shift(before, here, d: int, row):
    """``out[i] = here[i - d]``, from ``before`` (the chunk of rows above)
    where ``i < d``: one select and one rotation down by ``d`` rows."""
    R = here.shape[0]
    return pltpu.roll(jnp.where(row >= R - d, before, here), d, 0)


def _unshift(here, after, d: int, row):
    """``out[i] = here[i + d]``, from ``after`` (the chunk of rows below)
    where ``i + d`` passes the chunk."""
    R = here.shape[0]
    return pltpu.roll(jnp.where(row < d, after, here), R - d, 0)


def _part(ref, rows, part: int, c0: int, n: int, C: int):
    """Rows ``rows`` of one ``C``-wide part of a ``(1, ., 3C)`` block, the
    ``n`` channels from ``c0``, in fp32."""
    return ref[0, rows, pl.ds(part * C + c0, n)].astype(jnp.float32)


def _taps(w_ref, K, c0, n):
    return [w_ref[k:k + 1, pl.ds(c0, n)] for k in range(K)]


def _fwd_kernel(bcx_ref, before_ref, w_ref, out_ref, *, K, C):
    tl, n = out_ref.shape[1], _lanes(C)
    first = pl.program_id(1) == 0
    row = jax.lax.broadcasted_iota(jnp.int32, (ROWS, n), 0)
    halo = pl.ds(0, ROWS)
    for c0 in range(0, C, n):
        w = _taps(w_ref, K, c0, n)

        def chunk(j, s_before, c0=c0, w=w):
            rows = pl.ds(pl.multiple_of(j * ROWS, ROWS), ROWS)
            s = _part(bcx_ref, rows, 0, c0, n, C) * _part(bcx_ref, rows, 2, c0, n, C)
            c = w[K - 1] * s
            for k in range(K - 1):
                c += w[k] * _shift(s_before, s, K - 1 - k, row)
            out_ref[0, rows, pl.ds(c0, n)] = (
                _part(bcx_ref, rows, 1, c0, n, C) * c).astype(out_ref.dtype)
            return s
        s0 = _part(before_ref, halo, 0, c0, n, C) * _part(before_ref, halo, 2, c0, n, C)
        jax.lax.fori_loop(0, tl // ROWS, chunk, jnp.where(first, 0.0, s0))


def _bwd_kernel(bcx_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref,
                dbcx_ref, dw_ref, *, K, C):
    tl, n = dy_ref.shape[1], _lanes(C)
    i = pl.program_id(1)
    first, last = i == 0, i == pl.num_programs(1) - 1
    row = jax.lax.broadcasted_iota(jnp.int32, (ROWS, n), 0)
    halo = pl.ds(0, ROWS)

    @pl.when(jnp.logical_and(pl.program_id(0) == 0, first))
    def _start():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def dc_of(gates_ref, d_ref, rows, c0):      # dy * Cg: the gradient of c
        return (d_ref[0, rows, pl.ds(c0, n)].astype(jnp.float32)
                * _part(gates_ref, rows, 1, c0, n, C))

    for c0 in range(0, C, n):
        w = _taps(w_ref, K, c0, n)

        def chunk(rows, dc_after, carry, c0=c0, w=w):
            """One chunk of rows: ``carry`` holds ``s`` of the chunk above,
            this chunk's ``dc`` and the sums of ``d w``'s taps."""
            s_before, dc, sums = carry
            b, x = _part(bcx_ref, rows, 0, c0, n, C), _part(bcx_ref, rows, 2, c0, n, C)
            s = b * x
            taps = [_shift(s_before, s, K - 1 - k, row) for k in range(K - 1)] + [s]
            c = w[K - 1] * s
            ds = w[K - 1] * dc
            for k in range(K - 1):
                c += w[k] * taps[k]
                ds += w[k] * _unshift(dc, dc_after, K - 1 - k, row)
            dy = dy_ref[0, rows, pl.ds(c0, n)].astype(jnp.float32)
            for part, value in ((0, ds * x), (1, dy * c), (2, ds * b)):
                dbcx_ref[0, rows, pl.ds(part * C + c0, n)] = value.astype(dbcx_ref.dtype)
            return s, dc_after, tuple(a + dc * t for a, t in zip(sums, taps))

        def inner(j, carry):
            below = pl.ds(pl.multiple_of((j + 1) * ROWS, ROWS), ROWS)
            return chunk(pl.ds(pl.multiple_of(j * ROWS, ROWS), ROWS),
                         dc_of(bcx_ref, dy_ref, below, c0), carry)

        s0 = _part(before_ref, halo, 0, c0, n, C) * _part(before_ref, halo, 2, c0, n, C)
        carry = (jnp.where(first, 0.0, s0), dc_of(bcx_ref, dy_ref, halo, c0),
                 tuple(jnp.zeros((ROWS, n), jnp.float32) for _ in range(K)))
        carry = jax.lax.fori_loop(0, tl // ROWS - 1, inner, carry)
        dc_end = jnp.where(last, 0.0, dc_of(after_ref, dy_after_ref, halo, c0))
        sums = chunk(pl.ds(tl - ROWS, ROWS), dc_end, carry)[2]
        for k in range(K):
            dw_ref[k:k + 1, pl.ds(c0, n)] += sums[k].sum(0, keepdims=True)


def _tile(L: int, tile_rows: int) -> int:
    """Rows a grid step takes: ``tile_rows``, or the whole of a shorter row
    on whole chunks."""
    return min(tile_rows, -(-L // ROWS) * ROWS)


def _padded(a, tl: int):
    pad = -a.shape[1] % tl
    return jnp.pad(a, ((0, 0), (0, pad), (0, 0))) if pad else a


def _taps_block(w):
    """``w (C, K)`` as the kernels read it: ``(8, C)`` fp32, a tap a sublane."""
    return jnp.pad(w.astype(jnp.float32).T, ((0, _TAPS - w.shape[1]), (0, 0)))


def _params(interpret):
    return {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)}


def forward(bcx, w, tile_rows: int = TILE_ROWS):
    """``y (B, L, C)`` of the module docstring."""
    return _forward(bcx, w, tile_rows, _interpret_for(bcx))


def backward(bcx, w, dy, tile_rows: int = TILE_ROWS):
    """``(d bcx (B, L, 3C), d w (C, K))`` from the op's inputs and ``dy``."""
    return _backward(bcx, w, dy, tile_rows, _interpret_for(bcx))


# jitted functions of their own, as the row kernels are (``moe_rows.py``): a
# model's convolution layers share one trace of each

@functools.partial(jax.jit, static_argnums=(2, 3))
def _forward(bcx, w, tile_rows, interpret):
    (B, L, _), (C, K) = bcx.shape, w.shape
    tl = _tile(L, tile_rows)
    bcx = _padded(bcx, tl)
    per = tl // ROWS
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, K=K, C=C),
        name="short_conv_fwd",
        grid=(B, bcx.shape[1] // tl),
        in_specs=[pl.BlockSpec((1, tl, 3 * C), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, ROWS, 3 * C), lambda b, i: (b, jnp.maximum(i * per - 1, 0), 0)),
                  pl.BlockSpec((_TAPS, C), lambda b, i: (0, 0))],
        out_specs=pl.BlockSpec((1, tl, C), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, bcx.shape[1], C), bcx.dtype),
        interpret=interpret, **_params(interpret),
    )(bcx, bcx, _taps_block(w))
    return out[:, :L]


@functools.partial(jax.jit, static_argnums=(3, 4))
def _backward(bcx, w, dy, tile_rows, interpret):
    (B, L, _), (C, K) = bcx.shape, w.shape
    tl = _tile(L, tile_rows)
    bcx, dy = _padded(bcx, tl), _padded(dy, tl)
    per, chunks = tl // ROWS, bcx.shape[1] // ROWS

    def tile(width):
        return pl.BlockSpec((1, tl, width), lambda b, i: (b, i, 0))

    def after(width):
        return pl.BlockSpec((1, ROWS, width),
                            lambda b, i: (b, jnp.minimum((i + 1) * per, chunks - 1), 0))
    dbcx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, K=K, C=C),
        name="short_conv_bwd",
        grid=(B, bcx.shape[1] // tl),
        in_specs=[tile(3 * C),
                  pl.BlockSpec((1, ROWS, 3 * C), lambda b, i: (b, jnp.maximum(i * per - 1, 0), 0)),
                  after(3 * C), tile(C), after(C),
                  pl.BlockSpec((_TAPS, C), lambda b, i: (0, 0))],
        out_specs=[tile(3 * C), pl.BlockSpec((_TAPS, C), lambda b, i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct((_TAPS, C), jnp.float32)],
        interpret=interpret, **_params(interpret),
    )(bcx, bcx, bcx, dy, dy, _taps_block(w))
    return dbcx[:, :L], dw[:K].T.astype(w.dtype)
