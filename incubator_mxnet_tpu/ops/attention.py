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
    "projected_attention",
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


def _per_shard(attn, operands, mask, heads, lengths, head_counts=()):
    """``attn(*operands, mask=mask)``, run per shard where a mesh is active.

    GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"), so inside a step compiled over a multi-device mesh the
    kernels run under a full-manual ``shard_map``: every operand's first
    dimension, the batch, over ``dp`` where it divides, the second over
    ``tp`` where every count of ``head_counts`` divides (head-major
    operands; attention is independent along both), replicated over every
    other axis; the mask as a key mask, ``(B, Lk)``. ``operands`` may hold
    None and pairs. Outside a trace, or on a one-device mesh, it is the
    plain call.
    """
    from ..parallel.mesh import current_active_mesh
    from .pallas.flash_attention import _as_key_mask
    mesh = current_active_mesh()
    if mesh is None or mesh.devices.size == 1 \
            or not isinstance(operands[0], jax.core.Tracer):
        return attn(*operands, mask=mask)
    from jax.sharding import PartitionSpec as P
    from ..parallel.collectives import shard_map
    B = operands[0].shape[0]
    dp, tp = mesh.shape.get("dp", 1), mesh.shape.get("tp", 1)
    bspec = "dp" if dp > 1 and B % dp == 0 else None
    hspec = "tp" if tp > 1 and head_counts and all(n % tp == 0 for n in head_counts) \
        else None
    key_mask = None
    if mask is not None:
        key_mask = _as_key_mask(mask, B, heads, *lengths)
        if key_mask is None:
            return attn(*operands, mask=mask)       # raises: not a key mask

    def spec(x):
        return P(bspec, hspec, *(None,) * (x.ndim - 2))
    # a None operand has no leaves and needs no spec
    return shard_map(
        lambda *args: attn(*args[:-1], mask=args[-1]), mesh=mesh,
        in_specs=(*jax.tree.map(spec, operands),
                  None if key_mask is None else P(bspec, None)),
        out_specs=spec(operands[0]))(*operands, key_mask)


def _flash_on_mesh(query, key, value, mask, causal, scale, window, shared=None):
    """The head-major Pallas flash kernels, per shard where a mesh is
    active (:func:`_per_shard`). Heads are split over ``tp`` only where the
    K/V heads divide too (grouped K/V: a shard then holds whole groups);
    the shared pair's one key head does not divide, so such a call keeps
    its heads together."""
    from .pallas.flash_attention import flash_attention

    def attn(q, k, v, s, mask):
        return flash_attention(q, k, v, mask=mask, causal=causal, scale=scale,
                               window=window, shared=s)
    H = query.shape[1]
    return _per_shard(attn, (query, key, value, shared), mask, H,
                      (query.shape[2], key.shape[2]),
                      () if shared is not None else (H, key.shape[1]))


def _lanes_taken(query, key_value, heads, mask) -> bool:
    """Do the flash kernels take this call in the projections' own layout?
    Where they would take its head-major form (``dot_product_attention``'s
    impl and the kernels' switch, on a TPU), the shapes have a lane layout
    (``flash_attention._lane_layout``), and a mesh the call runs under
    shards no heads (``tp``) and no sequence (``sp``, the ring path): by
    what the call can observe."""
    import os
    from ..parallel.mesh import current_active_mesh
    from .pallas import flash_attention
    if os.environ.get("MXTPU_ATTN_IMPL", "auto") not in ("auto", "flash") \
            or os.environ.get("MXTPU_FLASH_ATTENTION", "1") == "0" \
            or flash_attention._interpret_for(query):
        return False
    mesh = current_active_mesh()
    if mesh is not None and isinstance(query, jax.core.Tracer) and (
            mesh.shape.get("tp", 1) > 1 or mesh.shape.get("sp", 1) > 1):
        return False
    return flash_attention._lane_layout(query, key_value, heads, mask) is not None


@register_op()
def projected_attention(query, *operands, heads=1, cross=False, causal=False,
                        scale=None, **_):
    """Multi-head attention over the projections as they are.
    Self-attention: ``query (B, L, 3C)``, the fused q, k, v projection,
    ``C = heads * D``. Cross-attention (``cross=True``): ``query (B, Lq,
    C)`` and, first of ``operands``, ``(B, Lk, 2C)``, the k, v projection.
    A mask, as :func:`dot_product_attention` takes it, comes last in
    ``operands``. Returns ``(B, Lq, C)``, what the output projection reads.

    On a TPU the flash kernels read q, k and v as 128-lane column blocks of
    these arrays (one head a block at D = 128, two at D = 64) and write the
    result, and in the backward pass the gradient, in the same layout
    (``flash_attention.flash_attention_lanes``): no head-major transpose in
    HBM either way. Taken where the call allows it: head size 32, 64 or
    128, ``C`` whole lane blocks, lengths the tiles divide, a key mask or
    none, no ``tp`` or ``sp`` in the mesh; inside a step compiled over a
    mesh, per shard of the batch (``dp``). Elsewhere the heads are split
    out, ``(B, H, L, D)``, for :func:`dot_product_attention` and its result
    is transposed back. The gauge ``mxtpu_flash_lane_layout{kernel=}`` says
    which way the last call of that head count and size went."""
    from functools import partial
    from ..telemetry import metrics
    from .pallas.flash_attention import flash_attention_lanes
    if cross and not operands:
        raise ValueError("projected_attention(cross=True) needs the k, v projection")
    key_value = operands[0] if cross else None
    mask = operands[-1] if len(operands) > int(cross) else None
    B, Lq = query.shape[:2]
    C = query.shape[-1] // (1 if cross else 3)
    taken = _lanes_taken(query, key_value, heads, mask)
    metrics.gauge("mxtpu_flash_lane_layout", "1 where the flash kernels took the last call "
                  "of this head count and size in the projections' own layout, 0 where "
                  "its heads were split out for them", kernel=f"flash_h{heads}_d{C // heads}"
                  ).set(int(taken))
    if taken:
        Lk = (key_value if cross else query).shape[1]
        return _per_shard(partial(flash_attention_lanes, heads=heads, causal=causal, scale=scale),
                          (query, key_value), mask, heads, (Lq, Lk))

    def split(x, n):
        # (B, L, n*C) -> n tensors of (B, H, L, D)
        return [p.reshape(B, x.shape[1], heads, C // heads).transpose(0, 2, 1, 3)
                for p in jnp.split(x, n, axis=2)]
    q, k, v = split(query, 1) + split(key_value, 2) if cross else split(query, 3)
    out = dot_product_attention(q, k, v, mask, causal=causal, scale=scale)
    return out.transpose(0, 2, 1, 3).reshape(B, Lq, C)


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
