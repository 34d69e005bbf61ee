"""Fused attention ops — the TPU counterpart of the contrib transformer ops.

Reference parity: ``src/operator/contrib/transformer.cc / .cu`` —
``_contrib_interleaved_matmul_selfatt_qk``,
``_contrib_interleaved_matmul_selfatt_valatt``,
``_contrib_interleaved_matmul_encdec_qk``,
``_contrib_interleaved_matmul_encdec_valatt`` — the fused interleaved
multi-head-attention matmuls GluonNLP's BERT uses (SURVEY §2.4, §5.7), plus
``SoftmaxWithLength`` masking (``src/operator/nn/softmax.cc``).

TPU-native design: instead of hand-scheduled cuBLAS strided-batch GEMMs, the
headline primitive is :func:`dot_product_attention` — a single fused
(scores → mask → softmax → context) computation. On TPU backends it lowers to
a blockwise **flash attention** (never materializing the L×L matrix in HBM,
see ``ops/pallas/flash_attention.py``); elsewhere XLA fuses the jnp graph.
The interleaved_* ops are kept with reference semantics (layouts included)
so ported GluonNLP model code runs unchanged.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op

__all__ = [
    "dot_product_attention",
    "checkpoint_layer",
    "interleaved_matmul_selfatt_qk",
    "interleaved_matmul_selfatt_valatt",
    "interleaved_matmul_encdec_qk",
    "interleaved_matmul_encdec_valatt",
]

_NEG = -1e30


def _mask_bias(mask, dtype):
    """Boolean/0-1 mask -> additive bias (0 keep, -inf drop)."""
    return jnp.where(mask.astype(bool), jnp.zeros((), dtype), jnp.full((), _NEG, dtype))


def _maybe_ring(query, key, value, mask, causal, scale):
    """Lower to ring attention when an active mesh shards sequence over sp.

    Conditions: tracing (inside a compiled step), sp>1, self-attention
    (Lq == Lk, divisible over sp), and a key-padding-style mask (or none).
    Returns None to fall through to the single-shard paths.
    """
    from ..parallel.mesh import current_active_mesh
    mesh = current_active_mesh()
    if mesh is None or mesh.shape.get("sp", 1) <= 1:
        return None
    if not isinstance(query, jax.core.Tracer):
        return None
    if query.ndim != 4 or key.shape != value.shape \
            or key.shape[1] != query.shape[1]:      # grouped K/V: no ring path
        return None
    B, H, Lq, D = query.shape
    Lk = key.shape[2]
    sp = mesh.shape["sp"]
    if Lq != Lk or Lq % sp:
        return None
    dp = mesh.shape.get("dp", 1)
    tp = mesh.shape.get("tp", 1)
    if B % max(dp, 1) or H % max(tp, 1):
        return None
    key_mask = None
    if mask is not None:
        from .pallas.flash_attention import _as_key_mask
        key_mask = _as_key_mask(mask, B, H, Lq, Lk)
        if key_mask is None:
            return None                     # dense masks stay on XLA path
        if key_mask.shape[1] % sp:
            return None
    from functools import partial
    from ..parallel.collectives import shard_map
    from ..parallel.ring import ring_attention
    from jax.sharding import PartitionSpec as P
    bspec = "dp" if dp > 1 else None
    hspec = "tp" if tp > 1 else None
    spec = P(bspec, hspec, "sp", None)
    if key_mask is None:
        fn = shard_map(
            partial(ring_attention, key_mask=None, axis="sp",
                    causal=causal, scale=scale),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        return fn(query, key, value)
    mspec = P(bspec, "sp")
    fn = shard_map(
        partial(ring_attention, axis="sp", causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec, mspec), out_specs=spec)
    return fn(query, key, value, key_mask)


def _flash_on_mesh(query, key, value, mask, causal, scale, window, shared=None):
    """The Pallas flash kernel, run per shard where a mesh is active.

    GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"), so inside a step compiled over a multi-device mesh the
    kernel runs under a full-manual ``shard_map``: batch over ``dp`` and
    heads over ``tp`` where they divide (attention is independent along
    both), replicated over every other axis. Outside a trace, or on a
    one-device mesh, it is the plain kernel call.
    """
    from functools import partial
    from ..parallel.mesh import current_active_mesh
    from .pallas.flash_attention import _as_key_mask, flash_attention
    attn = partial(flash_attention, causal=causal, scale=scale,
                   window=window)
    mesh = current_active_mesh()
    if mesh is None or mesh.devices.size == 1 \
            or not isinstance(query, jax.core.Tracer):
        return attn(query, key, value, mask=mask, shared=shared)
    from jax.sharding import PartitionSpec as P
    from ..parallel.collectives import shard_map
    B, H, Lq, _ = query.shape
    dp, tp = mesh.shape.get("dp", 1), mesh.shape.get("tp", 1)
    bspec = "dp" if dp > 1 and B % dp == 0 else None
    # heads split over tp only where the K/V heads divide too (grouped K/V:
    # a shard then holds whole groups); the shared pair's one key head does
    # not divide, so such a call keeps its heads together
    hspec = "tp" if tp > 1 and H % tp == 0 and key.shape[1] % tp == 0 \
        and shared is None else None
    spec = P(bspec, hspec, None, None)
    key_mask = None
    if mask is not None:
        key_mask = _as_key_mask(mask, B, H, Lq, key.shape[2])
        if key_mask is None:
            return attn(query, key, value, mask=mask)   # raises: not a key mask
    # the optional operands ride as one pytree: None has no leaves and
    # needs no spec
    return shard_map(
        lambda q, k, v, m, s: attn(q, k, v, mask=m, shared=s), mesh=mesh,
        in_specs=(spec, spec, spec, None if key_mask is None else P(bspec, None),
                  None if shared is None else (spec, spec)),
        out_specs=spec)(query, key, value, key_mask, shared)


@functools.cache
def _keep_flash_result():
    """The one policy object every recomputed layer shares: jax keys its
    caches of a split, transposed or lowered sub-jaxpr by the policy too,
    so a policy made anew for each layer would have the layers' common
    jitted parts (the routed half, the kernels) traced and lowered once a
    layer and not once."""
    from .pallas.flash_attention import REMAT_KEEP
    return jax.checkpoint_policies.save_only_these_names(*REMAT_KEEP)


def checkpoint_layer(body):
    """``jax.checkpoint`` as the models' ``remat=True`` means it: the layer
    is rebuilt in the backward pass from its input, all but the flash
    kernel's output and log-sum-exp, which are held (``REMAT_KEEP``). They
    are the step's dearest values to rebuild and among the cheapest to hold:
    at 32 heads of 128 and 8,192 tokens, 68 MB a layer against a second run
    of the forward kernel. Where attention went the XLA way the names are
    not in the trace, the policy keeps nothing, and this is a bare
    ``jax.checkpoint``."""
    return jax.checkpoint(body, policy=_keep_flash_result())


@register_op()
def dot_product_attention(query, key, value, mask=None, causal=False,
                          scale=None, impl="auto", window=None, shared=None,
                          **_):
    """Fused scaled-dot-product attention.

    Shapes: ``query (B, H, Lq, D)``, ``key/value (B, Hkv, Lk, D)`` with
    ``H`` a multiple of ``Hkv`` (query head ``h`` reads K/V head
    ``h // (H // Hkv)``; ``Hkv = H`` is plain multi-head attention),
    ``mask`` broadcastable to ``(B, H, Lq, Lk)`` (1 = attend). Returns
    ``(B, H, Lq, D)``.

    ``impl``: "auto" picks the Pallas flash kernel on TPU when shapes allow,
    else the XLA-fused jnp path; "xla" / "flash" force one (env override:
    MXTPU_ATTN_IMPL).

    ``window`` (with ``causal=True``): causal sliding-window attention over
    the ``window`` most recent keys — O(L·window) on the flash path (dead
    tiles skipped), a banded mask on the XLA path.

    ``shared=(q_s, k_s)``: a second score term over one key head that all
    query heads read, ``q_s (B, H, Lq, Ds)`` against ``k_s (B, 1, Lk, Ds)``
    (latent attention's rotary part): the score is ``(q . k + q_s . k_s) *
    scale``, ``scale`` by default ``(D + Ds) ** -0.5``. The flash path reads
    the one key through its block index; nothing is repeated or
    concatenated in HBM. Ring attention does not carry it.
    """
    import os
    impl = os.environ.get("MXTPU_ATTN_IMPL", impl)
    width = query.shape[-1] + (0 if shared is None else shared[0].shape[-1])
    scale = (width ** -0.5) if scale is None else scale
    if shared is not None and impl == "ring":
        raise ValueError(
            "impl='ring' does not support shared= (the hops carry one key "
            "per head); use impl='auto'/'flash'")
    if window is not None:
        window = int(window)
        if not causal:
            raise ValueError("window= requires causal=True")
        if window <= 0:
            raise ValueError(f"window must be positive, got {window}")
        if impl == "ring":
            raise ValueError(
                "impl='ring' does not support window= (the band does not "
                "decompose over ring hops); use impl='auto'/'flash'")
    # Sequence parallelism: when tracing under a mesh with sp>1 (ShardedTrainer
    # binds it via parallel.mesh.active_mesh), lower to ring attention — K/V
    # shards rotate over the sp axis, the per-hop block attention is the
    # Pallas flash kernel. See parallel/ring.py. (A sliding window stays on
    # the local paths: the band doesn't decompose over ring hops.)
    if impl in ("auto", "ring") and window is None and shared is None:
        ring_out = _maybe_ring(query, key, value, mask, causal, scale)
        if ring_out is not None:
            return ring_out
    if impl in ("auto", "flash"):
        # no catch here: a kernel that fails to import or lower must be
        # seen, not replaced by the XLA path in silence
        from .pallas.flash_attention import flash_supported
        if impl == "flash" or flash_supported(query, key, value, mask, shared):
            return _flash_on_mesh(query, key, value, mask, causal, scale,
                                  window, shared)
    acc = jnp.float32
    if key.shape[1] != query.shape[1]:
        # grouped K/V heads: this dense path repeats them (the flash kernel
        # reads K/V head h // group through its block index instead)
        group = query.shape[1] // key.shape[1]
        key = jnp.repeat(key, group, axis=1)
        value = jnp.repeat(value, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", query, key, preferred_element_type=acc)
    if shared is not None:
        s = s + jnp.einsum("bhqd,bkd->bhqk", shared[0], shared[1][:, 0],
                           preferred_element_type=acc)
    s = s * scale
    if mask is not None:
        s = s + _mask_bias(mask, acc)
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((lq, lk), bool), k=lk - lq)
        if window is not None:
            cm = jnp.logical_and(
                cm, jnp.triu(jnp.ones((lq, lk), bool),
                             k=lk - lq - int(window) + 1))
        s = jnp.where(cm, s, jnp.full((), _NEG, acc))
    p = jax.nn.softmax(s, axis=-1).astype(query.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, value,
                      preferred_element_type=acc).astype(query.dtype)


# ---------------------------------------------------------------------------
# Reference-layout interleaved ops. Layout contract (from the reference op
# docs): self-attention input is the fused QKV projection output with shape
# (seq, batch, heads*3*head_dim), interleaved per head as [q, k, v]; the
# qk output is (batch*heads, seq, seq) with q pre-scaled by 1/sqrt(head_dim).
# ---------------------------------------------------------------------------

def _split_selfatt(qkv, heads):
    L, B, C3 = qkv.shape
    d = C3 // (3 * heads)
    x = qkv.reshape(L, B, heads, 3, d)
    # -> (B, heads, L, d)
    q = jnp.transpose(x[:, :, :, 0, :], (1, 2, 0, 3))
    k = jnp.transpose(x[:, :, :, 1, :], (1, 2, 0, 3))
    v = jnp.transpose(x[:, :, :, 2, :], (1, 2, 0, 3))
    return q, k, v, d


@register_op(aliases=("_contrib_interleaved_matmul_selfatt_qk",))
def interleaved_matmul_selfatt_qk(queries_keys_values, heads=1, **_):
    q, k, _, d = _split_selfatt(queries_keys_values, heads)
    s = jnp.einsum("bhqd,bhkd->bhqk", q * (d ** -0.5), k,
                   preferred_element_type=jnp.float32)
    B, H, L, _ = q.shape
    return s.astype(queries_keys_values.dtype).reshape(B * H, L, L)


@register_op(aliases=("_contrib_interleaved_matmul_selfatt_valatt",))
def interleaved_matmul_selfatt_valatt(queries_keys_values, attention, heads=1, **_):
    _, _, v, d = _split_selfatt(queries_keys_values, heads)
    B, H, L, _ = v.shape
    att = attention.reshape(B, H, L, L)
    out = jnp.einsum("bhqk,bhkd->bhqd", att, v,
                     preferred_element_type=jnp.float32)
    # -> (L, B, H*d)
    return jnp.transpose(out, (2, 0, 1, 3)).reshape(L, B, H * d).astype(
        queries_keys_values.dtype)


def _split_kv(kv, heads):
    L, B, C2 = kv.shape
    d = C2 // (2 * heads)
    x = kv.reshape(L, B, heads, 2, d)
    k = jnp.transpose(x[:, :, :, 0, :], (1, 2, 0, 3))
    v = jnp.transpose(x[:, :, :, 1, :], (1, 2, 0, 3))
    return k, v, d


@register_op(aliases=("_contrib_interleaved_matmul_encdec_qk",))
def interleaved_matmul_encdec_qk(queries, keys_values, heads=1, **_):
    Lq, B, C = queries.shape
    d = C // heads
    q = jnp.transpose(queries.reshape(Lq, B, heads, d), (1, 2, 0, 3))
    k, _, _ = _split_kv(keys_values, heads)
    s = jnp.einsum("bhqd,bhkd->bhqk", q * (d ** -0.5), k,
                   preferred_element_type=jnp.float32)
    Lk = k.shape[2]
    return s.astype(queries.dtype).reshape(B * heads, Lq, Lk)


@register_op(aliases=("_contrib_interleaved_matmul_encdec_valatt",))
def interleaved_matmul_encdec_valatt(keys_values, attention, heads=1, **_):
    k, v, d = _split_kv(keys_values, heads)
    B, H, Lk, _ = v.shape
    Lq = attention.shape[1]
    att = attention.reshape(B, H, Lq, Lk)
    out = jnp.einsum("bhqk,bhkd->bhqd", att, v,
                     preferred_element_type=jnp.float32)
    return jnp.transpose(out, (2, 0, 1, 3)).reshape(Lq, B, H * d).astype(
        keys_values.dtype)
