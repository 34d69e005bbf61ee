"""What lies between a q or k projection and the flash kernels, as a Pallas
TPU kernel pair: per-head RMSNorm, rotary and the head-major write in one
pass over the projection's result, and one pass back.

The op (``ops.nn.qk_norm_rope``) on ``x (B, L, H*D)``, a projection's
result, ``gamma (D,)`` and, where the layer has rotary, the tables ``cos``
and ``sin (B, L, D)`` of its positions (``sin`` with the sign of the
``rotate_half`` convention: minus on the first ``D/2`` lanes)::

    n[b, t, h] = x[b, t, h] * rsqrt(mean(x[b, t, h]^2) + eps) * gamma
    y[b, h, t] = n * cos[b, t] + roll(n, D/2) * sin[b, t]

XLA's way to say it (``rms_norm``, ``rotary``, ``transpose``) lifts the
bf16 projection to fp32 twice, splits the lanes in halves and concatenates
them, and writes fp32 arrays of q's size between its fusions, forward and
backward. Here the forward kernel (``qk_prologue_fwd``) reads ``x`` once
and writes ``y (B, H, L, D)`` once, in the layout the flash kernels read;
the backward kernel (``qk_prologue_bwd``) reads ``x`` and ``dy`` once and
writes ``dx`` once, rebuilding the statistic on the way: nothing but the
op's inputs is kept between the two. ``d gamma`` is summed over rows and
heads in fp32, in one output block that stays in VMEM for the whole grid.

**Layout.** A head's ``D`` values on lanes, rows on sublanes; a grid step
takes ``tile_rows`` rows of one sequence at the full ``H*D`` width and
writes block ``(b, :, tile, :)`` of the head-major result. Inside a step a
loop takes the heads in turn, ``HEADS_UNROLLED`` to a step (a head is a lane
range of the input block and a plane of the output block; its ``(tile_rows,
D)`` values in fp32 are the compiler's to keep in registers or spill). The
rotation by half a head is one product with a 0/1 matrix on the MXU
(``_half_swap``). What the loop's shape costs (v5e, PR 35): a step of it
drains the vector unit's pipeline, 0.19 us, so ``8,192 / rows x 32 / heads``
steps of ``rows`` rows and ``heads`` heads cost 0.4 ms at 2,048 steps and
nothing that shows under 512; all 32 heads unrolled is as fast and takes
0.16 s more to trace, a kernel and a trace of it.

bf16 in and out; the statistic, the scale and the rotation in fp32. The
value is rounded to bf16 where the plain form rounds it, between the norm
and the rotation (and its gradient there on the way back), so the two agree
to one rounding of the result. ``D`` must be whole lane tiles and ``L``
whole row tiles on the chip. In interpret mode (off the TPU: the tests) any
``D`` and any ``L`` of whole tiles runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_for

__all__ = ["forward", "backward", "supported", "TILE_ROWS"]

TILE_ROWS = 512       # rows a grid step takes
HEADS_UNROLLED = 2    # heads a step of the loop over heads takes: room for the scheduler, a short trace
_SUBLANES = 16        # a bf16 tile's rows: a row tile is whole ones
_MAX_WIDTH = 8192     # H*D: a step's blocks stay under 8 MB each
_VMEM_LIMIT = 100 * 2**20


def supported(x, heads: int) -> bool:
    """Do the kernels take this call on the chip: bf16, a head whole lane
    tiles, the sequence whole row tiles?"""
    if x.ndim != 3 or x.dtype != jnp.bfloat16 or x.shape[-1] % heads:
        return False
    L, width = x.shape[1:]
    tl = min(TILE_ROWS, L)
    return ((width // heads) % 128 == 0 and width <= _MAX_WIDTH
            and L % tl == 0 and tl % _SUBLANES == 0)


def _inv_rms(x, eps):
    return jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _half_swap(D: int, dtype):
    """The ``(D, D)`` 0/1 matrix that swaps a head's two halves: ``v @ it``
    is ``roll(v, D/2)``, exactly where ``v`` is of ``dtype`` (one product by
    1.0 a lane). The rotation goes through the MXU, which has nothing else
    to do here: as a lane rotation (``pltpu.roll``) it waited behind the
    statistic's lane reduction, head after head, and cost three times the
    rest of the kernel (v5e, PR 35)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (D, D), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (D, D), 1)
    return (jnp.abs(row - col) == D // 2).astype(dtype)


def _rotated(v, swap, cos, sin):
    """``v * cos + roll(v, D/2) * sin`` in fp32, of a value ``v`` that is
    exact in ``swap``'s dtype."""
    return (v.astype(jnp.float32) * cos
            + jnp.dot(v, swap, preferred_element_type=jnp.float32) * sin)


def _head_lanes(h, D):
    return pl.ds(pl.multiple_of(h * D, D), D)


def _over_heads(heads, head, carry):
    """``head(h, carry)`` for every head in turn: a loop whose step takes
    ``HEADS_UNROLLED`` heads (as many as divide ``heads``), so the trace
    holds that many copies of the body whatever the head count."""
    n = next(n for n in range(min(HEADS_UNROLLED, heads), 0, -1) if heads % n == 0)

    def group(i, carry):
        for u in range(n):
            carry = head(i * n + u, carry)
        return carry
    return jax.lax.fori_loop(0, heads // n, group, carry)


def _fwd_kernel(x_ref, g_ref, *refs, heads, eps):
    *table, out_ref = refs
    D = out_ref.shape[-1]
    g = g_ref[...]
    if table:
        cos, sin, swap = table[0][0], table[1][0], _half_swap(D, out_ref.dtype)

    def head(h, carry):
        x = x_ref[0, :, _head_lanes(h, D)].astype(jnp.float32)
        y = x * _inv_rms(x, eps) * g
        if table:       # rounded where the plain form hands a bf16 value from the norm to the rotation
            y = _rotated(y.astype(out_ref.dtype), swap, cos, sin)
        out_ref[0, h] = y.astype(out_ref.dtype)
        return carry
    _over_heads(heads, head, 0)


def _bwd_kernel(x_ref, g_ref, dy_ref, *refs, heads, eps):
    *table, dx_ref, dg_ref = refs
    tl, D = dy_ref.shape[2:]
    g = g_ref[...]
    if table:               # the rotation's transpose: roll(sin, D/2) = -sin
        cos, sin, swap = table[0][0], -table[1][0], _half_swap(D, dy_ref.dtype)

    @pl.when(jnp.logical_and(pl.program_id(0) == 0, pl.program_id(1) == 0))
    def _start():
        dg_ref[...] = jnp.zeros_like(dg_ref)

    def head(h, dg):
        lanes = _head_lanes(h, D)
        x = x_ref[0, :, lanes].astype(jnp.float32)
        dn = dy_ref[0, h]
        if table:           # rounded where the plain form rounds the bf16 value's gradient
            dn = _rotated(dn, swap, cos, sin).astype(dy_ref.dtype)
        dn = dn.astype(jnp.float32)
        r = _inv_rms(x, eps)
        xr = x * r
        gd = g * dn
        dx = r * (gd - xr * jnp.mean(gd * xr, axis=-1, keepdims=True))
        dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
        return dg + dn * xr
    dg = _over_heads(heads, head, jnp.zeros((tl, D), jnp.float32))
    dg_ref[0:1, :] += dg.sum(0, keepdims=True)


def _params(interpret):
    return {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT)}


def forward(x, gamma, table, eps: float, heads: int, tile_rows: int = TILE_ROWS):
    """``y (B, H, L, D)`` of the module docstring; ``table`` the pair
    ``(cos, sin)``, or ``None`` where the layer has no rotary."""
    return _forward(x, gamma, table, eps, heads, tile_rows, _interpret_for(x))


def backward(x, gamma, table, dy, eps: float, heads: int, tile_rows: int = TILE_ROWS):
    """``(dx (B, L, H*D), d gamma (D,))`` from the op's inputs and ``dy``."""
    return _backward(x, gamma, table, dy, eps, heads, tile_rows, _interpret_for(x))


def _specs(B, L, heads, D, tile_rows, table):
    """Grid and block specs: the projection's tile, ``gamma``, the tables'
    tiles (none without rotary) and the head-major tile."""
    tl = min(tile_rows, L)
    if L % tl:
        raise ValueError(f"qk_prologue: {L} rows are not whole tiles of {tl}")
    flat = pl.BlockSpec((1, tl, heads * D), lambda b, i: (b, i, 0))
    gamma = pl.BlockSpec((1, D), lambda b, i: (0, 0))
    tables = [pl.BlockSpec((1, tl, D), lambda b, i: (b, i, 0))] * (2 if table else 0)
    major = pl.BlockSpec((1, heads, tl, D), lambda b, i: (b, 0, i, 0))
    return (B, L // tl), flat, gamma, tables, major


# jitted functions of their own, as the row kernels are (``moe_rows.py``): a
# model's attention layers share one trace of each kind of call

@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _forward(x, gamma, table, eps, heads, tile_rows, interpret):
    B, L, width = x.shape
    D = width // heads
    grid, flat, g, tables, major = _specs(B, L, heads, D, tile_rows, table)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, eps=eps),
        name="qk_prologue_fwd",
        grid=grid,
        in_specs=[flat, g, *tables],
        out_specs=major,
        out_shape=jax.ShapeDtypeStruct((B, heads, L, D), x.dtype),
        interpret=interpret, **_params(interpret),
    )(x, gamma.astype(jnp.float32).reshape(1, D), *(table or ()))


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _backward(x, gamma, table, dy, eps, heads, tile_rows, interpret):
    B, L, width = x.shape
    D = width // heads
    grid, flat, g, tables, major = _specs(B, L, heads, D, tile_rows, table)
    dx, dg = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, eps=eps),
        name="qk_prologue_bwd",
        grid=grid,
        in_specs=[flat, g, major, *tables],
        out_specs=[flat, pl.BlockSpec((8, D), lambda b, i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((8, D), jnp.float32)],
        interpret=interpret, **_params(interpret),
    )(x, gamma.astype(jnp.float32).reshape(1, D), dy, *(table or ()))
    return dx, dg[0].astype(gamma.dtype)
