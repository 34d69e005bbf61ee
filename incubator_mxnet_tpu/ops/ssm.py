"""State-space sequence mixing: the chunked scan of a Mamba-2 layer (no
reference counterpart).

``ssd_scan`` computes, per sequence and head ``h``, the linear recurrence::

    S_t = exp(dt_t,h A_h) S_{t-1} + dt_t,h x_t,h B_t^T     (S (P, N), zero before the row)
    y_t,h = S_t C_t + D_h x_t,h

with ``B_t``, ``C_t`` shared by the heads of a group. It is computed a chunk
of ``Q`` positions at a time (the "SSD" form): inside a chunk a masked
``(C B^T * decay) (dt x)`` product on the MXU, across chunks the state
carried in order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .registry import register_op

__all__ = ["ssd_scan", "ssd_scan_plain"]


def _group_of_heads(v, H):
    """``v (B, L, G, N)`` repeated to one slice a head, ``(B, L, H, N)``."""
    return jnp.repeat(v, H // v.shape[2], axis=2)


def ssd_scan_plain(x, dt, A, B, C, D, chunk: int = 256):
    """:func:`ssd_scan` as the chunked algorithm in plain ``jax.numpy``,
    differentiated by jax itself: what XLA makes of the op (it holds a
    ``(B, L / Q, H, Q, Q)`` fp32 decay matrix), what a call the kernels do
    not take traces, and the oracle the kernels are tested against. Sums,
    ``exp`` and the state in fp32. A ragged ``L`` is padded with ``dt = 0``
    rows, which leave the state as it was."""
    Bt, L, H, P = x.shape
    f32 = jnp.float32
    pad = -L % chunk
    if pad:
        x, dt, B, C = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, B, C))
    n = x.shape[1] // chunk

    def chunks(v):
        return v.reshape(Bt, n, chunk, *v.shape[2:])
    xd = chunks(x.astype(f32) * dt.astype(f32)[..., None])              # (B, n, Q, H, P)
    Bh, Ch = (chunks(_group_of_heads(v.astype(f32), H)) for v in (B, C))  # (B, n, Q, H, N)
    cs = jnp.cumsum(chunks(dt.astype(f32) * A.astype(f32)), axis=2)     # (B, n, Q, H)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]                   # (B, n, Q, Q, H)
    decay = jnp.exp(jnp.where(causal[None, None, :, :, None], seg, -jnp.inf))
    scores = jnp.einsum("bcthn,bcshn->bctsh", Ch, Bh) * decay
    y = jnp.einsum("bctsh,bcshp->bcthp", scores, xd)
    last = cs[:, :, -1:, :]                                             # (B, n, 1, H)
    local = jnp.einsum("bcsh,bcshn,bcshp->bchpn", jnp.exp(last - cs), Bh, xd)

    def carry(state, c):                      # the state entering chunk c, then leaving it
        decay_c, local_c = c
        return jnp.exp(decay_c)[..., None, None] * state + local_c, state
    _, entering = jax.lax.scan(
        carry, jnp.zeros((Bt, H, P, B.shape[-1]), f32),
        (jnp.moveaxis(last[:, :, 0], 1, 0), jnp.moveaxis(local, 1, 0)))
    y = y + jnp.einsum("bcthn,cbhpn->bcthp", Ch, entering) * jnp.exp(cs)[..., None]
    y = y.reshape(Bt, n * chunk, H, P)[:, :L] + D.astype(f32)[:, None] * x[:, :L].astype(f32)
    return y.astype(x.dtype)


def _kernels(x, B, chunk):
    """The Pallas kernel pair (its module) on a TPU where the call allows,
    ``None`` for the plain form elsewhere: by platform, dtype and shape, as
    ``dot_product_attention`` chooses its flash kernels."""
    from .pallas import ssd
    return ssd if not ssd._interpret_for(x) and ssd.supported(x, B, chunk) else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def ssd_fused(x, dt, A, B, C, D, chunk):
    """:func:`ssd_scan` on the kernel pair, whatever the platform (interpret
    mode off the TPU), with the op's own backward: nothing but the inputs
    and the chunk states are held for it. The kernels read and write ``x``,
    ``y``, ``dy`` and ``dx`` as the ``(B, L, H * P)`` arrays the model's
    projections give and take: no 64-lane head layout exists in HBM."""
    return _ssd_fused_fwd(x, dt, A, B, C, D, chunk)[0]


def _ssd_fused_fwd(x, dt, A, B, C, D, chunk):
    from .pallas import ssd
    Bt, L, H, P = x.shape
    y, states = ssd.forward(x.reshape(Bt, L, H * P), dt, A, B, C, D, chunk)
    return y.reshape(x.shape), (x, dt, A, B, C, D, states)


def _ssd_fused_bwd(chunk, res, dy):
    from .pallas import ssd
    x, dt, A, B, C, D, states = res
    Bt, L, H, P = x.shape
    dx, *rest = ssd.backward(x.reshape(Bt, L, H * P), dt, A, B, C, D, states,
                             dy.reshape(Bt, L, H * P), chunk)
    return (dx.reshape(x.shape), *rest)


ssd_fused.defvjp(_ssd_fused_fwd, _ssd_fused_bwd)


@register_op()
def ssd_scan(x, dt, A, B, C, D, chunk: int = 256, **_):
    """The chunked state-space scan of a Mamba-2 mixer (module docstring):
    ``x (B, L, H, P)``, ``dt (B, L, H)`` fp32 after its softplus, ``A (H,)``
    (negative), ``B``/``C (B, L, G, N)`` with ``H`` a multiple of ``G``,
    ``D (H,)``; returns ``y (B, L, H, P)`` in ``x``'s dtype.

    The call decides by what it can observe: bf16 with one group, ``L`` whole
    chunks and whole head blocks on a TPU is one Pallas kernel each way
    (``ops/pallas/ssd.py``: ``ssd_fwd``, ``ssd_bwd``) behind a backward rule
    of the op's own, the state carried in VMEM in fp32 from chunk to chunk;
    anything else traces :func:`ssd_scan_plain`. The gauge
    ``mxtpu_ssd_fused{kernel=}`` says which way the last call of that head
    count, head size and state size went."""
    from ..telemetry import metrics
    H, P, N = x.shape[2], x.shape[3], B.shape[3]
    kernels = _kernels(x, B, chunk)
    metrics.gauge("mxtpu_ssd_fused", "1 where the SSD scan's kernel pair took the last "
                  "call of this head count and size, 0 where the plain form did",
                  kernel=f"ssd_h{H}_p{P}_n{N}").set(int(kernels is not None))
    with jax.named_scope("ssd_scan"):
        if kernels is None:
            return ssd_scan_plain(x, dt, A, B, C, D, chunk)
        return ssd_fused(x, dt, A, B, C, D, chunk)
