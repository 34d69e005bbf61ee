"""A Mamba-2 mixer's causal depthwise convolution with bias and SiLU as a
Pallas TPU kernel pair: one pass over its operands forward, one backward.

The op (``ops.nn.causal_conv1d``) on ``x (B, L, C)``, ``w (C, K)`` and
``b (C,)``::

    p[t] = sum_k w[:, k] * x[t - (K - 1) + k] + b        (x zero before the row)
    y[t] = silu(p[t])

is pure memory traffic, and XLA's way to say it (a padded fp32 copy of ``x``
and ``K`` slices of it shifted along the rows, differentiated by jax into as
many passes again) moves every value several times. Here the forward kernel
(``causal_conv_fwd``) reads ``x`` once and writes ``y`` once; the backward
kernel (``causal_conv_bwd``) reads ``x`` and ``dy`` once and writes ``dx``
once, and rebuilds ``p`` on the way, since SiLU's derivative needs it:
nothing but the op's inputs is kept between the two. ``d w`` and ``d b`` are
summed over rows in fp32, in output blocks that stay in VMEM for the whole
grid.

**The parts.** ``y`` may leave as lane ranges of its own (``widths``, which
sum to ``C``): a Mamba-2 mixer splits the convolution's output into the
scan's ``x | B | C`` at once, and a kernel that reads them (``ssd.py``) would
otherwise be handed copies of three slices. ``dy`` comes back the same way,
as one array a part; ``x`` and ``dx`` are whole.

**Layout.** ``short_conv.py``'s: channels on lanes, rows on sublanes; a grid
step takes a tile of ``tile_rows`` rows of one sequence at its full width,
with halo blocks of ``ROWS`` rows from the tile before (``x``, forward and
backward) and the tile after (``x`` and ``dy``, backward: a tap reaches
``K - 1`` rows forward there), ignored at a sequence's ends. Inside a step
the tile is walked ``ROWS`` rows and 256 (or 128) channels of one part at
a time, every intermediate a few vector registers of fp32, and a shift by
``d`` rows is one select and one sublane rotation.

bf16 or fp32 in and out, fp32 inside, one cast back, as the plain form
computes. ``L`` is padded up to whole tiles (zero rows after a row's end
change nothing before it, and their gradient is zero); on the chip every part
must be whole lane tiles. In interpret mode (off the TPU: the tests) any size
runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _interpret_for
from .short_conv import (ROWS, TILE_ROWS, _TAPS, _lanes, _padded, _params, _shift, _taps,
                         _taps_block, _tile, _unshift)

__all__ = ["forward", "backward", "supported", "widths_of"]


def widths_of(C: int, split=()) -> tuple:
    """The parts' widths where ``jnp.split(y, split, axis=-1)`` would cut
    ``C`` channels."""
    edges = (0, *split, C)
    return tuple(b - a for a, b in zip(edges, edges[1:]))


def supported(x, w, split=(), start: int = 0) -> bool:
    """Do the kernels take this call on the chip: ``start`` and every part
    whole lane tiles, at most 8 taps (the taps' block), bf16 or fp32?"""
    C, K = w.shape
    return (x.ndim == 3 and start % 128 == 0 and 0 <= start <= x.shape[-1] - C
            and 1 <= K <= _TAPS and x.dtype in (jnp.bfloat16, jnp.float32)
            and all(n > 0 and n % 128 == 0 for n in widths_of(C, split)))


def _chunks(widths):
    """``(part, lane in the part, lane in C, lanes)`` of each lane chunk the
    kernels walk: none crosses a part's edge."""
    out, c = [], 0
    for p, width in enumerate(widths):
        n = _lanes(width)
        out += [(p, o, c + o, n) for o in range(0, width, n)]
        c += width
    return out


def _rows(ref, rows, c0: int, n: int):
    return ref[0, rows, pl.ds(c0, n)].astype(jnp.float32)


def _pre(x_before, x, w, bias, row):
    """``p`` of a chunk of rows from its ``x`` and the chunk's above."""
    K = len(w)
    p = w[K - 1] * x
    for k in range(K - 1):
        p += w[k] * _shift(x_before, x, K - 1 - k, row)
    return p + bias


def _sigmoid(p):
    return 1.0 / (1.0 + jnp.exp(-p))


def _fwd_kernel(x_ref, before_ref, w_ref, b_ref, *out_refs, K, widths):
    tl = x_ref.shape[1]
    first = pl.program_id(1) == 0
    halo = pl.ds(0, ROWS)
    for p, o, c0, n in _chunks(widths):
        w, bias = _taps(w_ref, K, c0, n), b_ref[:, pl.ds(c0, n)]
        row = jax.lax.broadcasted_iota(jnp.int32, (ROWS, n), 0)
        out_ref = out_refs[p]

        def chunk(j, x_before, c0=c0, o=o, n=n, w=w, bias=bias, row=row, out_ref=out_ref):
            rows = pl.ds(pl.multiple_of(j * ROWS, ROWS), ROWS)
            x = _rows(x_ref, rows, c0, n)
            pre = _pre(x_before, x, w, bias, row)
            out_ref[0, rows, pl.ds(o, n)] = (pre * _sigmoid(pre)).astype(out_ref.dtype)
            return x
        x0 = _rows(before_ref, halo, c0, n)
        jax.lax.fori_loop(0, tl // ROWS, chunk, jnp.where(first, 0.0, x0))


def _bwd_kernel(x_ref, before_ref, after_ref, *refs, K, widths):
    parts = len(widths)
    dy_refs, dy_after_refs = refs[:parts], refs[parts:2 * parts]
    w_ref, b_ref, dx_ref, dw_ref, db_ref = refs[2 * parts:]
    tl = x_ref.shape[1]
    i = pl.program_id(1)
    first, last = i == 0, i == pl.num_programs(1) - 1
    halo = pl.ds(0, ROWS)

    @pl.when(jnp.logical_and(pl.program_id(0) == 0, first))
    def _start():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    for p, o, c0, n in _chunks(widths):
        w, bias = _taps(w_ref, K, c0, n), b_ref[:, pl.ds(c0, n)]
        row = jax.lax.broadcasted_iota(jnp.int32, (ROWS, n), 0)

        def dpre(x_before, x, dy, w=w, bias=bias, row=row):
            """The gradient of ``p``: ``dy silu'(p)``, ``p`` rebuilt."""
            pre = _pre(x_before, x, w, bias, row)
            s = _sigmoid(pre)
            return dy * s * (1.0 + pre * (1.0 - s))

        def chunk(rows, x_before, x, x_after, dp, dy_after, sums, c0=c0, n=n, w=w, row=row,
                  dpre=dpre):
            """One chunk of rows: ``dx`` from this chunk's ``dp`` and the
            next one's, and the sums of ``d w``'s taps and ``d b``."""
            dp_after = dpre(x, x_after, dy_after)
            dx = w[K - 1] * dp
            for k in range(K - 1):
                dx += w[k] * _unshift(dp, dp_after, K - 1 - k, row)
            dx_ref[0, rows, pl.ds(c0, n)] = dx.astype(dx_ref.dtype)
            taps = [_shift(x_before, x, K - 1 - k, row) for k in range(K - 1)] + [x]
            return dp_after, tuple(a + dp * t for a, t in zip(sums, taps)) + (sums[K] + dp,)

        def inner(j, carry, c0=c0, o=o, n=n, p=p, chunk=chunk):
            x_before, x, dp, sums = carry
            below = pl.ds(pl.multiple_of((j + 1) * ROWS, ROWS), ROWS)
            x_after = _rows(x_ref, below, c0, n)
            dp_after, sums = chunk(pl.ds(pl.multiple_of(j * ROWS, ROWS), ROWS), x_before, x,
                                   x_after, dp, _rows(dy_refs[p], below, o, n), sums)
            return x, x_after, dp_after, sums

        x_before = jnp.where(first, 0.0, _rows(before_ref, halo, c0, n))
        x = _rows(x_ref, halo, c0, n)
        carry = (x_before, x, dpre(x_before, x, _rows(dy_refs[p], halo, o, n)),
                 tuple(jnp.zeros((ROWS, n), jnp.float32) for _ in range(K + 1)))
        x_before, x, dp, sums = jax.lax.fori_loop(0, tl // ROWS - 1, inner, carry)
        dy_end = jnp.where(last, 0.0, _rows(dy_after_refs[p], halo, o, n))
        sums = chunk(pl.ds(tl - ROWS, ROWS), x_before, x, _rows(after_ref, halo, c0, n), dp,
                     dy_end, sums)[1]
        for k in range(K):
            dw_ref[k:k + 1, pl.ds(c0, n)] += sums[k].sum(0, keepdims=True)
        db_ref[:, pl.ds(c0, n)] += sums[K].sum(0, keepdims=True)


def forward(x, w, b, split=(), start: int = 0, tile_rows: int = TILE_ROWS):
    """``silu(conv(x) + b)`` as a tuple of its parts, cut where
    ``jnp.split(., split, axis=-1)`` would cut it; the convolution reads the
    ``C`` channels of ``x`` from ``start``."""
    return _forward(x, w, b, widths_of(w.shape[0], split), start, tile_rows, _interpret_for(x))


def backward(x, w, b, dys, start: int = 0, tile_rows: int = TILE_ROWS):
    """``(dx (B, L, C), d w (C, K), d b (C,))`` from the op's inputs and
    ``dys``, the output's gradient a part; ``dx`` of the ``C`` channels of
    ``x`` from ``start``."""
    return _backward(x, w, b, tuple(dys), start, tile_rows, _interpret_for(x))


def _bias_row(b):
    return b.astype(jnp.float32).reshape(1, -1)


def _window(rows: int, C: int, start: int, first_row):
    """A block of ``x``: ``rows`` rows from ``first_row(i)`` and the ``C``
    channels from ``start``, by element offsets, so that ``x`` may be a
    wider array (the in-projection's whole output) read in place."""
    return pl.BlockSpec((pl.Element(1), pl.Element(rows), pl.Element(C)),
                        lambda b, i: (b, pl.multiple_of(first_row(i), ROWS), start))


def _tiles(tl: int):
    return lambda width: pl.BlockSpec((1, tl, width), lambda b, i: (b, i, 0))


# jitted functions of their own, as short_conv.py's are: a model's Mamba
# layers share one trace of each

@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _forward(x, w, b, widths, start, tile_rows, interpret):
    (B, L, _), (C, K) = x.shape, w.shape
    tl = _tile(L, tile_rows)
    x = _padded(x, tl)
    tile = _tiles(tl)
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, K=K, widths=widths),
        name="causal_conv_fwd",
        grid=(B, x.shape[1] // tl),
        in_specs=[_window(tl, C, start, lambda i: i * tl),
                  _window(ROWS, C, start, lambda i: jnp.maximum(i * tl - ROWS, 0)),
                  pl.BlockSpec((_TAPS, C), lambda b, i: (0, 0)),
                  pl.BlockSpec((1, C), lambda b, i: (0, 0))],
        out_specs=[tile(n) for n in widths],
        out_shape=[jax.ShapeDtypeStruct((B, x.shape[1], n), x.dtype) for n in widths],
        interpret=interpret, **_params(interpret),
    )(x, x, _taps_block(w), _bias_row(b))
    return tuple(out[:, :L] for out in outs)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _backward(x, w, b, dys, start, tile_rows, interpret):
    (B, L, _), (C, K) = x.shape, w.shape
    widths = tuple(dy.shape[-1] for dy in dys)
    tl = _tile(L, tile_rows)
    x, dys = _padded(x, tl), [_padded(dy, tl) for dy in dys]
    per, chunks = tl // ROWS, x.shape[1] // ROWS
    tile = _tiles(tl)

    def after(width):
        return pl.BlockSpec((1, ROWS, width),
                            lambda b, i: (b, jnp.minimum((i + 1) * per, chunks - 1), 0))
    dx, dw, db = pl.pallas_call(
        functools.partial(_bwd_kernel, K=K, widths=widths),
        name="causal_conv_bwd",
        grid=(B, x.shape[1] // tl),
        in_specs=[_window(tl, C, start, lambda i: i * tl),
                  _window(ROWS, C, start, lambda i: jnp.maximum(i * tl - ROWS, 0)),
                  _window(ROWS, C, start, lambda i: jnp.minimum((i + 1) * tl, x.shape[1] - ROWS)),
                  *[tile(n) for n in widths], *[after(n) for n in widths],
                  pl.BlockSpec((_TAPS, C), lambda b, i: (0, 0)),
                  pl.BlockSpec((1, C), lambda b, i: (0, 0))],
        out_specs=[tile(C), pl.BlockSpec((_TAPS, C), lambda b, i: (0, 0)),
                   pl.BlockSpec((1, C), lambda b, i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, x.shape[1], C), x.dtype),
                   jax.ShapeDtypeStruct((_TAPS, C), jnp.float32),
                   jax.ShapeDtypeStruct((1, C), jnp.float32)],
        interpret=interpret, **_params(interpret),
    )(x, x, x, *dys, *dys, _taps_block(w), _bias_row(b))
    return dx[:, :L], dw[:K].T.astype(w.dtype), db[0].astype(b.dtype)
