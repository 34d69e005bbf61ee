"""The chunked state-space scan of a Mamba-2 mixer (the "SSD" form) as a
Pallas TPU kernel pair: ``ssd_fwd`` forward, ``ssd_bwd`` backward.

The op (``ops.ssm.ssd_scan``), per sequence and head ``h``, with ``x_t (P,)``,
``dt_t`` and ``A`` scalars of the head and ``B_t``, ``C_t (N,)`` shared by all
heads (one group)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        (S (P, N), zero before the row)
    y_t = S_t C_t

taken ``Q`` rows (a chunk) at a time. With ``cs`` the cumulative sum of
``dt A`` inside a chunk (fp32, computed before the kernels), ``Xd = dt * x``
and ``T`` the state entering the chunk stored transposed, ``(N, P)``::

    G      = C B^T                                   (Q, Q), once a chunk for all heads
    M      = G * exp(cs_t - cs_s)  [s <= t]          (Q, Q), a head
    Y      = M Xd + exp(cs)[:, None] * (C T) + D x
    T_next = exp(cs_Q) T + B^T (exp(cs_Q - cs)[:, None] * Xd)

**Layout.** The grid is ``(sequences, chunks, head blocks)``, the head
blocks innermost: ``B`` and ``C`` of a chunk are fetched once and ``G`` is
built once for every head, and each head block's state ``T`` (``HEADS``
heads side by side on lanes, ``(N, HEADS * P)`` fp32) stays in VMEM from one
chunk to the next, the chunk axis running in order (all axes
``arbitrary``). ``x``, ``y``, ``dy`` and ``dx`` are read and written as
``(Q, HEADS * P)`` blocks of the ``(B, L, H * P)`` arrays the projections
give and take, and ``dt`` and ``cs`` as lane-dense rows ``(HEADS, Q)`` of
``(B, H, L)`` arrays, turned into columns by a transpose in VMEM: nothing of
a 64-lane head layout or of a one-lane column exists in HBM. Matmul
operands are bf16 with fp32 accumulation; the decay's sums, ``exp`` and the
state are fp32. The forward also writes the state entering each chunk,
``(B, L / Q, N, H * P)`` fp32, which the backward pass reads.

The backward kernel walks the chunks in reverse and carries the state's
gradient ``dT`` in VMEM as the forward carries the state::

    dM   = dY Xd^T;   dXd = M^T dY + w * (B dT_next);   w = exp(cs_Q - cs)
    dG  += dM * exp(cs_t - cs_s)   over the heads; then dC += dG B, dB += dG^T C
    dC  += (e dY) T^T;  dB += (w Xd) dT_next^T;        e = exp(cs)
    dT   = exp(cs_Q) dT_next + C^T (e dY)
    dcs  = rowsum(dM * M) - colsum(dM * M) + e rowsum(dY * (C T)) - w rowsum(Xd * (B dT_next))
           + [t = Q] (sum_s w rowsum(Xd * (B dT_next)) + exp(cs_Q) sum(T * dT_next))

``dcs`` leaves as rows, and becomes ``d dt`` and ``d A`` by a reverse
cumulative sum, outside.

``L`` must be a whole number of chunks, ``H`` of ``HEADS``; ``x``, ``B`` and
``C`` bf16. In interpret mode (off the TPU: the tests) any ``L``, ``P`` and
``N`` run, and ``H`` is still a whole number of ``HEADS``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret_for

__all__ = ["forward", "backward", "supported", "HEADS"]

HEADS = 8             # heads a grid step takes, side by side on lanes
_NEG = -1e30
_VMEM_LIMIT = 100 * 2**20
_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b


def supported(x, B, chunk: int) -> bool:
    """Do the kernels take this call on the chip: bf16, one group of
    ``B``/``C``, whole chunks of whole row tiles, whole head blocks of whole
    lane tiles?"""
    if x.ndim != 4 or B.ndim != 4:
        return False
    _, L, H, P = x.shape
    G, N = B.shape[2:]
    return (x.dtype == jnp.bfloat16 and B.dtype == jnp.bfloat16 and G == 1
            and chunk % 128 == 0 and L % chunk == 0 and H % HEADS == 0
            and (HEADS * P) % 128 == 0 and N % 128 == 0)


def _layouts(dt, A, chunk: int):
    """``dt (B, L, H)`` and ``A (H,)`` as the kernels read them, a head a
    row: ``dt`` and ``cs``, the cumulative sum of ``dt A`` inside each chunk
    (fp32), both ``(B, H, L)``."""
    Bt, L, H = dt.shape
    dt = dt.astype(jnp.float32).transpose(0, 2, 1)
    cs = jnp.cumsum((dt * A.astype(jnp.float32)[:, None]).reshape(Bt, H, L // chunk, chunk),
                    axis=-1).reshape(Bt, H, L)
    return dt, cs


def _at(row, lane: int):
    """``row (1, Q)``'s value at ``lane``, ``(1, 1)``: a masked lane sum."""
    at = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1) == lane
    return jnp.sum(jnp.where(at, row, 0.0), axis=1, keepdims=True)


def _decay(cs_col, cs_row, causal):
    return jnp.exp(jnp.where(causal, cs_col - cs_row, _NEG))


def _causal(Q):
    return (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))


class _Heads:
    """A head block's per-head scalars of one chunk, as rows ``(heads, Q)``
    and, transposed in VMEM, as columns ``(Q, heads)``."""

    def __init__(self, dt_ref, cs_ref):
        self.dt_rows, self.cs_rows = dt_ref[0], cs_ref[0]
        self.dt_cols, self.cs_cols = self.dt_rows.T, self.cs_rows.T

    def head(self, k):
        """Head ``k``: ``(dt, cs)`` as columns ``(Q, 1)``, ``cs`` as a row
        ``(1, Q)``, and ``cs`` at the chunk's last row, ``(1, 1)``."""
        row = self.cs_rows[k:k + 1, :]
        return (self.dt_cols[:, k:k + 1], self.cs_cols[:, k:k + 1], row,
                _at(row, row.shape[1] - 1))


def _fwd_kernel(x_ref, dt_ref, cs_ref, b_ref, c_ref, d_ref,
                y_ref, st_out_ref, g_ref, st_ref, ct_ref, *, P, heads):
    c, g = pl.program_id(1), pl.program_id(2)
    Q = x_ref.shape[1]
    bf16, f32 = jnp.bfloat16, jnp.float32

    @pl.when(g == 0)
    def _gram():
        g_ref[...] = jax.lax.dot_general(c_ref[0], b_ref[0], _NT, preferred_element_type=f32)

    @pl.when(c == 0)
    def _start():
        st_ref[g] = jnp.zeros(st_ref.shape[1:], f32)

    st = st_ref[g]                                       # (N, heads P): the state entering
    st_out_ref[0, 0] = st
    ct_ref[...] = jnp.dot(c_ref[0], st.astype(bf16), preferred_element_type=f32)
    causal = _causal(Q)
    hs = _Heads(dt_ref, cs_ref)
    for k in range(heads):
        lanes = pl.ds(k * P, P)
        dt, cs_col, cs_row, last = hs.head(k)
        x = x_ref[0, :, lanes].astype(f32)
        xd = x * dt
        m = (g_ref[...] * _decay(cs_col, cs_row, causal)).astype(bf16)
        y = (jnp.dot(m, xd.astype(bf16), preferred_element_type=f32)
             + jnp.exp(cs_col) * ct_ref[:, lanes] + d_ref[:, lanes] * x)
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        wx = (xd * jnp.exp(last - cs_col)).astype(bf16)
        st_ref[g, :, lanes] = (jnp.exp(last) * st_ref[g, :, lanes] + jax.lax.dot_general(
            b_ref[0], wx, _TN, preferred_element_type=f32))


def _bwd_kernel(x_ref, dt_ref, cs_ref, b_ref, c_ref, d_ref, dy_ref, st_in_ref,
                dx_ref, ddt_ref, dcs_ref, db_ref, dc_ref, dd_ref,
                g_ref, dg_ref, dst_ref, dtn_ref, ct_ref, bd_ref, ed_ref, wx_ref,
                dcs_col_ref, ddt_col_ref, *, P, heads):
    c, g = pl.program_id(1), pl.program_id(2)
    Q = x_ref.shape[1]
    bf16, f32 = jnp.bfloat16, jnp.float32
    Cb, Bb = c_ref[0], b_ref[0]

    @pl.when(g == 0)
    def _gram():
        g_ref[...] = jax.lax.dot_general(Cb, Bb, _NT, preferred_element_type=f32)
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_ref[0] = jnp.zeros(db_ref.shape[1:], f32)
        dc_ref[0] = jnp.zeros(dc_ref.shape[1:], f32)

    @pl.when(c == 0)
    def _start():
        dst_ref[g] = jnp.zeros(dst_ref.shape[1:], f32)

    st = st_in_ref[0, 0]                                 # (N, heads P): the state entering
    dtn_ref[...] = dst_ref[g]                            # the gradient of the state leaving
    ct_ref[...] = jnp.dot(Cb, st.astype(bf16), preferred_element_type=f32)
    bd_ref[...] = jnp.dot(Bb, dtn_ref[...].astype(bf16), preferred_element_type=f32)
    causal = _causal(Q)
    at_last = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
    hs = _Heads(dt_ref, cs_ref)
    dcs_rows = []
    for k in range(heads):
        lanes = pl.ds(k * P, P)
        dt, cs_col, cs_row, last = hs.head(k)
        x = x_ref[0, :, lanes].astype(f32)
        dy = dy_ref[0, :, lanes].astype(f32)
        xd = x * dt
        decay = _decay(cs_col, cs_row, causal)
        m = g_ref[...] * decay
        dm = jax.lax.dot_general(dy.astype(bf16), xd.astype(bf16), _NT,
                                 preferred_element_type=f32)
        dxd = jax.lax.dot_general(m.astype(bf16), dy.astype(bf16), _TN,
                                  preferred_element_type=f32)
        dg_ref[...] += dm * decay
        r = dm * m
        e = jnp.exp(cs_col)
        w = jnp.exp(last - cs_col)
        bd = bd_ref[:, lanes]
        dxd = dxd + w * bd
        dw = w * jnp.sum(xd * bd, axis=1, keepdims=True)            # (Q, 1)
        d_last = (jnp.sum(dw, axis=0, keepdims=True) + jnp.exp(last) * jnp.sum(
            jnp.sum(st_in_ref[0, 0, :, lanes] * dtn_ref[:, lanes], axis=1, keepdims=True),
            axis=0, keepdims=True))
        dcs_col_ref[:, k:k + 1] = (jnp.sum(r, axis=1, keepdims=True)
                                   + e * jnp.sum(dy * ct_ref[:, lanes], axis=1, keepdims=True)
                                   - dw + jnp.where(at_last, d_last, 0.0))
        dcs_rows.append(-jnp.sum(r, axis=0, keepdims=True))
        dx_ref[0, :, lanes] = (dxd * dt + d_ref[:, lanes] * dy).astype(dx_ref.dtype)
        ddt_col_ref[:, k:k + 1] = jnp.sum(dxd * x, axis=1, keepdims=True)
        dd_ref[0, 0, :, lanes] = jnp.sum(dy * x, axis=0, keepdims=True)
        ed_ref[:, lanes] = e * dy
        wx_ref[:, lanes] = w * xd
        dst_ref[g, :, lanes] = jnp.exp(last) * dtn_ref[:, lanes]
    dcs_ref[0] = jnp.concatenate(dcs_rows, axis=0) + dcs_col_ref[...].T
    ddt_ref[0] = ddt_col_ref[...].T
    ed = ed_ref[...].astype(bf16)
    dst_ref[g] += jax.lax.dot_general(Cb, ed, _TN, preferred_element_type=f32)
    dc_ref[0] += jax.lax.dot_general(ed, st.astype(bf16), _NT, preferred_element_type=f32)
    db_ref[0] += jax.lax.dot_general(wx_ref[...].astype(bf16), dtn_ref[...].astype(bf16),
                                     _NT, preferred_element_type=f32)

    @pl.when(g == pl.num_programs(2) - 1)
    def _gram_grad():
        dgb = dg_ref[...].astype(bf16)
        dc_ref[0] += jnp.dot(dgb, Bb, preferred_element_type=f32)
        db_ref[0] += jax.lax.dot_general(dgb, Cb, _TN, preferred_element_type=f32)


def _params(interpret):
    return {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)}


def forward(x, dt, A, B, C, D, chunk: int):
    """``(y (B, L, H * P), states)``: ``y`` of the module docstring with
    ``D x`` added, from ``x (B, L, H * P)``, and the state entering each
    chunk, ``(B, L / chunk, N, H P)`` fp32, for :func:`backward`."""
    return _forward(x, dt, A, B, C, D, chunk, _interpret_for(x))


def backward(x, dt, A, B, C, D, states, dy, chunk: int):
    """``(dx, d dt, d A, d B, d C, d D)`` from the op's inputs (``x``,
    ``dy (B, L, H * P)``), the chunk states :func:`forward` wrote and
    ``dy``."""
    return _backward(x, dt, A, B, C, D, states, dy, chunk, _interpret_for(x))


def _lane_row(D, P):
    """``D (H,)`` as one fp32 row of ``H P`` lanes, each head's value over
    its ``P`` lanes."""
    return jnp.repeat(D.astype(jnp.float32), P)[None, :]


# jitted functions of their own, as the short convolution's are: a model's
# state-space layers share one trace of each

@functools.partial(jax.jit, static_argnums=(6, 7))
def _forward(x, dt, A, B, C, D, chunk, interpret):
    Bt, L, HP = x.shape
    H, N = dt.shape[-1], B.shape[-1]
    P, hb = HP // H, HEADS
    nc, ng = L // chunk, H // hb
    dt_rows, cs_rows = _layouts(dt, A, chunk)
    rows = pl.BlockSpec((1, chunk, hb * P), lambda b, c, g: (b, c, g))
    heads = pl.BlockSpec((1, hb, chunk), lambda b, c, g: (b, g, c))
    shared = pl.BlockSpec((1, chunk, N), lambda b, c, g: (b, c, 0))
    y, states = pl.pallas_call(
        functools.partial(_fwd_kernel, P=P, heads=hb),
        name="ssd_fwd",
        grid=(Bt, nc, ng),
        in_specs=[rows, heads, heads, shared, shared,
                  pl.BlockSpec((1, hb * P), lambda b, c, g: (0, g))],
        out_specs=[rows, pl.BlockSpec((1, 1, N, hb * P), lambda b, c, g: (b, c, 0, g))],
        out_shape=[jax.ShapeDtypeStruct((Bt, L, HP), x.dtype),
                   jax.ShapeDtypeStruct((Bt, nc, N, HP), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((ng, N, hb * P), jnp.float32),
                        pltpu.VMEM((chunk, hb * P), jnp.float32)],
        interpret=interpret, **_params(interpret),
    )(x, dt_rows, cs_rows, B[:, :, 0], C[:, :, 0], _lane_row(D, P))
    return y, states


@functools.partial(jax.jit, static_argnums=(8, 9))
def _backward(x, dt, A, B, C, D, states, dy, chunk, interpret):
    Bt, L, HP = x.shape
    H, N = dt.shape[-1], B.shape[-1]
    P, hb = HP // H, HEADS
    nc, ng = L // chunk, H // hb
    dt_rows, cs_rows = _layouts(dt, A, chunk)

    def rev(c):
        return nc - 1 - c
    rows = pl.BlockSpec((1, chunk, hb * P), lambda b, c, g: (b, rev(c), g))
    heads = pl.BlockSpec((1, hb, chunk), lambda b, c, g: (b, g, rev(c)))
    shared = pl.BlockSpec((1, chunk, N), lambda b, c, g: (b, rev(c), 0))
    f32 = jnp.float32
    dx, ddt, dcs, db, dc, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, P=P, heads=hb),
        name="ssd_bwd",
        grid=(Bt, nc, ng),
        in_specs=[rows, heads, heads, shared, shared,
                  pl.BlockSpec((1, hb * P), lambda b, c, g: (0, g)), rows,
                  pl.BlockSpec((1, 1, N, hb * P), lambda b, c, g: (b, rev(c), 0, g))],
        out_specs=[rows, heads, heads, shared, shared,
                   pl.BlockSpec((1, 1, 1, hb * P), lambda b, c, g: (b, rev(c), 0, g))],
        out_shape=[jax.ShapeDtypeStruct((Bt, L, HP), x.dtype),
                   jax.ShapeDtypeStruct((Bt, H, L), f32), jax.ShapeDtypeStruct((Bt, H, L), f32),
                   jax.ShapeDtypeStruct((Bt, L, N), f32), jax.ShapeDtypeStruct((Bt, L, N), f32),
                   jax.ShapeDtypeStruct((Bt, nc, 1, HP), f32)],
        scratch_shapes=[pltpu.VMEM((chunk, chunk), f32), pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((ng, N, hb * P), f32), pltpu.VMEM((N, hb * P), f32)]
        + [pltpu.VMEM((chunk, hb * P), f32)] * 4 + [pltpu.VMEM((chunk, hb), f32)] * 2,
        interpret=interpret, **_params(interpret),
    )(x, dt_rows, cs_rows, B[:, :, 0], C[:, :, 0], _lane_row(D, P), dy, states)
    # cs is a cumulative sum of dt A inside each chunk: its transpose sums
    # from the chunk's end back
    da = jnp.flip(jnp.cumsum(jnp.flip(dcs.reshape(Bt, H, nc, chunk), -1), axis=-1), -1)
    da = da.reshape(Bt, H, L)
    d_dt = (ddt + da * A.astype(f32)[:, None]).transpose(0, 2, 1)
    d_A = jnp.sum(da * dt_rows, axis=(0, 2))
    d_D = dd.sum(axis=(0, 1, 2)).reshape(H, P).sum(-1)
    return (dx, d_dt.astype(dt.dtype), d_A.astype(A.dtype), db[:, :, None].astype(B.dtype),
            dc[:, :, None].astype(C.dtype), d_D.astype(D.dtype))
