"""Blockwise (flash) attention as a Pallas TPU kernel, with custom VJP.

Reference counterpart: the fused interleaved-MHA contrib ops
(``src/operator/contrib/transformer.cu``) — which still materialize the
(B·H, L, L) score matrix in HBM. This kernel never does: scores live one
(BQ, BK) tile at a time in VMEM with the online-softmax recurrence, so memory
is O(L·D) instead of O(L²) (SURVEY §5.7 calls this the required
capability-parity-plus deliverable).

TPU mapping (the parts that set the MFU):

- All matmuls run on the MXU in the *input* dtype (bf16 in training) with
  fp32 accumulation (``preferred_element_type``); probabilities are cast
  back to bf16 before the PV dot. fp32 operands would run the MXU at a
  fraction of peak.
- K/V are **streamed from HBM one (BK, D) block per grid step** — the grid's
  innermost "arbitrary" dimension — with softmax state (m, l, acc) carried
  in VMEM scratch across steps. Pallas double-buffers the HBM→VMEM copies
  automatically, so there is no whole-sequence VMEM residency and no cap on
  L (the old design held all of K/V per (b,h) in VMEM and capped L at 4k).
- ``dimension_semantics``: (batch·head, q-block) grid dims are "parallel";
  the k-block dim is "arbitrary" (carries the softmax recurrence).
- Fully-masked causal tiles are skipped with ``pl.when`` (≈2× on causal).

Longer-than-memory sequences go through ring attention over the ``sp`` mesh
axis (``parallel/ring.py``), which calls back into this kernel's ``_fwd``
per K/V hop and merges the per-hop (o, lse) pairs; ``dot_product_attention``
routes there automatically when the active mesh has sp>1.

Grouped-query attention: ``k``/``v`` may carry fewer heads than ``q``
(``H = Hkv * group``); query head ``h`` reads K/V head ``h // group`` through
the block index, nothing is repeated in HBM, and the dkv kernel adds up the
group's query heads. Plain multi-head attention is group 1 and compiles to
the programs it always did.

A second pair of operands (``shared=(q_s, k_s)``, latent attention's rotary
part): ``q_s (B, H, Lq, Ds)`` against ``k_s (B, 1, Lk, Ds)``, one key head
that every query head reads. The score is ``(q . k + q_s . k_s) * scale``;
``k_s`` is fetched with the block index ``b // H`` as a grouped K/V head is,
so neither a ``(B, H, Lk, D + Ds)`` key nor ``H`` copies of ``k_s`` exist in
HBM, forward or backward. ``dk_s`` is the sum over the heads, which the dkv
kernel streams one after another past an fp32 accumulator. Such calls are
named ``flash_*_mla``.

Masking: ``causal`` and/or a key-padding mask of shape (B, Lk) (1 = valid).
The generic (B, H, Lq, Lk) mask case falls back to the XLA path in
``ops/attention.py`` — loading an L² mask would defeat the point.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM

__all__ = ["flash_attention", "flash_supported", "REMAT_KEEP"]

#: the names the forward rules give the kernel's output and log-sum-exp
#: (``jax.ad_checkpoint.checkpoint_name``): a ``jax.checkpoint`` whose policy
#: saves them holds the two and does not run the forward kernel again in the
#: backward pass (``ops.attention.checkpoint_layer``). Outside a checkpoint
#: the naming is the identity.
REMAT_KEEP = ("flash_out", "flash_lse")

_NEG = -1e30


def _interpret_for(x) -> bool:
    """Run the kernel in interpreter mode? Concrete arrays: only where
    they live off the TPU; tracers: only where the backend this trace is
    compiled for (the process default backend) is not the TPU. So on a
    TPU host a jitted step or an array on the chip always compiles the
    kernel with Mosaic, whatever ``impl`` asked for."""
    if isinstance(x, jax.core.Tracer):
        return jax.default_backend() != "tpu"
    return next(iter(x.devices())).platform != "tpu"


def flash_supported(q, k, v, mask=None, shared=None) -> bool:
    """Shape/backend gate used by dot_product_attention(impl='auto')."""
    if os.environ.get("MXTPU_FLASH_ATTENTION", "1") == "0":
        return False
    if _interpret_for(q):
        return False
    if q.ndim != 4 or k.shape != v.shape or _kv_group(q, k) is None:
        return False
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    if D % 8 or D > 256:
        return False
    if shared is not None and not _shared_ok(q, k, *shared):
        return False
    if Lq % _bq(Lq) or Lk % _bk(Lk):
        return False
    if mask is not None and _as_key_mask(mask, B, H, Lq, Lk) is None:
        return False
    return True


def _kv_group(q, k):
    """Query heads per K/V head (grouped-query attention), or None where
    the shapes are no such grouping: ``q (B, H, Lq, D)`` against ``k, v
    (B, Hkv, Lk, D)`` with ``H = Hkv * group``; query head ``h`` reads K/V
    head ``h // group``. Plain multi-head attention is group 1."""
    if k.ndim != 4 or k.shape[0] != q.shape[0] or k.shape[3] != q.shape[3]:
        return None
    H, Hkv = q.shape[1], k.shape[1]
    return H // Hkv if Hkv and H % Hkv == 0 else None


def _shared_ok(q, k, q_s, k_s) -> bool:
    """Is ``(q_s, k_s)`` a second score term for ``q`` against ``k``:
    ``q_s (B, H, Lq, Ds)``, ``k_s (B, 1, Lk, Ds)``, and a key head a query
    head in the first pair (group 1: the dkv kernel's stream over the heads
    is then the shared key's alone)?"""
    B, H, Lq, _ = q.shape
    Ds = q_s.shape[-1]
    return (q_s.shape == (B, H, Lq, Ds) and k_s.shape == (B, 1, k.shape[2], Ds)
            and k.shape[1] == H and Ds % 8 == 0 and Ds <= 256)


def _auto_block(length: int) -> int:
    """Default tile rows for one grid dimension: 512 or 256 when they divide
    ``length``, else one whole block for sublane-aligned (length % 8 == 0)
    short sequences (unaligned ones only with MXTPU_FLASH_UNALIGNED=1),
    else 512 (which won't divide — the caller then routes to the XLA path
    via ``flash_supported``).

    Measured 2026-07-30 on a v5e under an earlier installation (BASELINE.md;
    not re-measured since): BERT-base, L=512, D=64, (BQ, BK)=(512, 512) ran
    the step at 40.9ms vs 45.5ms for (256, 512) and a pathological 1066ms for
    (128, 512) — bigger tiles amortize the grid/recurrence overhead and keep
    the MXU busier, and VMEM comfortably holds a 512-row block up to D=256.
    Tiles below 256 rows are never chosen automatically (the 128-row config
    is the measured-pathological regime; env overrides remain available).
    """
    for cand in (512, 256):
        if cand <= length and length % cand == 0:
            return cand
    if length <= 1024 and (
            length % 8 == 0
            or os.environ.get("MXTPU_FLASH_UNALIGNED", "0") == "1"):
        # One whole block; VMEM holds it up to D=256. Sublane-unaligned
        # (length % 8 != 0) block shapes are where Mosaic lowering failures
        # and perf cliffs live, so they stay env-gated until a hardware run
        # validates them (MXTPU_FLASH_UNALIGNED=1).
        return length
    return 512  # not handled: caller falls back to XLA via flash_supported


def _bq(lq: int) -> int:
    env = os.environ.get("MXTPU_FLASH_BQ")
    if env:
        return min(int(env), lq)
    return _auto_block(lq)


def _bk(lk: int) -> int:
    env = os.environ.get("MXTPU_FLASH_BK")
    if env:
        return min(int(env), lk)
    return _auto_block(lk)


#: grid semantics of all three kernels for Mosaic: (batch·head, fixed
#: block) are parallel, the streamed block carries the recurrence
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _as_key_mask(mask, B, H, Lq, Lk):
    """Reduce a broadcastable mask to (B, Lk) key-padding form, else None."""
    if mask is None:
        return None
    if mask.ndim == 2 and mask.shape == (B, Lk):
        return mask
    if mask.ndim == 4 and mask.shape[1] == 1 and mask.shape[2] == 1 \
            and mask.shape[0] in (1, B) and mask.shape[3] == Lk:
        m = mask[:, 0, 0, :]
        return jnp.broadcast_to(m, (B, Lk))
    return None


def _causal_live(iq, jk, bq, bk, causal_off, window=None):
    """Does q-block iq intersect any unmasked position of k-block jk?
    (bottom-right aligned causal: col <= row + causal_off; with a sliding
    window additionally col > row + causal_off - window). Dead tiles are
    skipped entirely — a window turns the O(L²) tile grid into O(L·W)."""
    first_row = iq * bq
    first_col = jk * bk
    live = first_col <= first_row + (bq - 1) + causal_off
    if window is not None:
        last_col = first_col + bk - 1
        live = jnp.logical_and(
            live, last_col > first_row + causal_off - window)
    return live


def _band(rows, cols, causal_off, window):
    """The in-tile visibility mask for causal (+ optional window)."""
    live = cols <= rows + causal_off
    if window is not None:
        live = jnp.logical_and(live, cols > rows + causal_off - window)
    return live


def _live_k(i, bq, bk, nk, causal_off, window):
    """``j -> j`` held inside the k-blocks that q-block ``i`` can see: a dead
    tile then names the block of the nearest live one, and Pallas, which
    copies a block only when its index changes, fetches nothing for it. The
    tile skipping saves the arithmetic; this saves the HBM traffic."""
    hi = jnp.clip((i * bq + bq - 1 + causal_off) // bk, 0, nk - 1)
    lo = 0 if window is None else jnp.clip(
        (i * bq + causal_off - window + 1) // bk, 0, nk - 1)
    return lambda j: jnp.clip(j, lo, hi)


def _live_q(j, bq, bk, nq, causal_off, window):
    """The same for the q-blocks that can see k-block ``j`` (dkv kernel)."""
    lo = jnp.clip(-((bq - 1 + causal_off - j * bk) // bq), 0, nq - 1)
    hi = nq - 1 if window is None else jnp.clip(
        (j * bk + bk - 2 - causal_off + window) // bq, 0, nq - 1)
    return lambda i: jnp.clip(i, lo, hi)


def _kernel_name(base: str, window, shared=None) -> str:
    """Windowed calls are named apart, so a trace separates a model's
    sliding layers from its full ones; so are calls with the shared second
    pair (latent attention)."""
    return (base + ("" if window is None else "_win")
            + ("" if shared is None else "_mla"))


def _optional_inputs(kernel, fixed: int, present: tuple):
    """Pallas hands a kernel its refs by position. ``kernel`` takes
    ``fixed`` inputs, then one ref for each entry of ``present``, then
    outputs and scratch: the absent ones are passed as ``None``."""
    def call(*refs, **kw):
        refs = list(refs)
        given = iter(refs[fixed:fixed + sum(present)])
        optional = [next(given) if p else None for p in present]
        return kernel(*refs[:fixed], *optional, *refs[fixed + sum(present):], **kw)
    return call


def _scores(qb, kb, qs_ref, ks_ref):
    """``q k^T`` of one tile in fp32, plus the shared pair's product where
    the call has one."""
    s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if qs_ref is not None:
        s = s + jax.lax.dot_general(qs_ref[0], ks_ref[0], (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    return s


# ---------------------------------------------------------------------------
# forward: grid (B·H, nq, nk) — K/V streamed block-by-block, state in scratch
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, qs_ref, ks_ref, mask_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, causal_off,
                window=None):
    bq, d = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[1]
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _step():
        q = q_ref[0]                       # input dtype (bf16 in training)
        kb = k_ref[0]
        # MXU dot in input dtype, fp32 accumulate; scale applied in fp32
        s = _scores(q, kb, qs_ref, ks_ref) * scale
        if mask_ref is not None:
            mb = mask_ref[0, 0]
            s = jnp.where(mb[None, :].astype(bool), s, _NEG)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + jk * bk
            s = jnp.where(_band(rows, cols, causal_off, window), s, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:  # skip tiles fully outside the (banded) diagonal
        pl.when(_causal_live(iq, jk, bq, bk, causal_off, window))(_step)
    else:
        _step()

    @pl.when(jk == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)  # fully-masked rows → output 0
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l))[:, 0]


def _scratch(bq, d):
    return [pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32)]


def _fwd(q, k, v, key_mask, causal, scale, window=None, shared=None):
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    G = _kv_group(q, k)                 # query heads per K/V head
    bq, bk = _bq(Lq), _bk(Lk)
    BH = B * H
    nk = Lk // bk
    q3 = q.reshape(BH, Lq, D)
    k3 = k.reshape(BH // G, Lk, D)
    v3 = v.reshape(BH // G, Lk, D)
    grid = (BH, Lq // bq, nk)
    if causal:      # dead tiles fetch nothing (see _live_k)
        def jj(i, j):
            return _live_k(i, bq, bk, nk, Lk - Lq, window)(j)
    else:
        def jj(i, j):
            return j
    kv_spec = pl.BlockSpec(
        (1, bk, D), (lambda b, i, j: (b, jj(i, j), 0)) if G == 1 else
        (lambda b, i, j: (b // G, jj(i, j), 0)), memory_space=_VMEM)
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0), memory_space=_VMEM),
        kv_spec, kv_spec,
    ]
    args = [q3, k3, v3]
    if shared is not None:
        # the one key head all H query heads read: block b // H, as a
        # grouped K/V head is read through b // G
        Ds = shared[0].shape[-1]
        in_specs += [
            pl.BlockSpec((1, bq, Ds), lambda b, i, j: (b, i, 0), memory_space=_VMEM),
            pl.BlockSpec((1, bk, Ds), lambda b, i, j: (b // H, jj(i, j), 0),
                         memory_space=_VMEM)]
        args += [shared[0].reshape(BH, Lq, Ds), shared[1].reshape(B, Lk, Ds)]
    if key_mask is not None:
        # (B, 1, Lk): TPU block shapes need the trailing two dims to be
        # tile-divisible or whole, so the mask rides with a singleton row.
        in_specs.append(pl.BlockSpec(
            (1, 1, bk), lambda b, i, j: (b // H, 0, jj(i, j)),
            memory_space=_VMEM))
        args.append(key_mask.astype(jnp.int32).reshape(key_mask.shape[0], 1, Lk))
    kern = functools.partial(
        _optional_inputs(_fwd_kernel, 3, (shared is not None, shared is not None,
                                          key_mask is not None)),
        scale=scale, causal=causal, causal_off=Lk - Lq, window=window)
    interpret = _interpret_for(q3)
    kwargs = {} if interpret else {"compiler_params": _COMPILER_PARAMS}
    o, lse = pl.pallas_call(
        kern,
        name=_kernel_name("flash_fwd", window, shared),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i),
                         memory_space=_VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Lq, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, Lq), jnp.float32),
        ],
        scratch_shapes=_scratch(bq, D),
        interpret=interpret,
        **kwargs,
    )(*args)
    return o.reshape(B, H, Lq, D), lse.reshape(B, H, Lq)


# ---------------------------------------------------------------------------
# backward: dkv kernel (grid B·H, nk, nq) + dq kernel (grid B·H, nq, nk);
# delta = rowsum(do * o) precomputed with plain jnp.
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    qs_ref, ks_ref, mask_ref, dk_ref, dv_ref, *more, scale,
                    causal, causal_off, window=None, group=1):
    if qs_ref is None:
        dk_acc, dv_acc = more
    else:
        dks_ref, dk_acc, dv_acc, dks_acc = more
    bk, d = k_ref.shape[1], k_ref.shape[2]
    bq = q_ref.shape[1]
    jk = pl.program_id(1)
    # the streamed dimension runs over the q-blocks of every query head of
    # this K/V head's group, one head after another: dk and dv add them up
    step = pl.program_id(2)
    last = pl.num_programs(2) - 1
    iq = step if group == 1 else step % (pl.num_programs(2) // group)
    # with the shared pair the group is the shared key's (all the heads of a
    # row): k and v are a head's own, so dk and dv start and end with each
    # head's q-blocks, and only dk_s adds up the whole stream
    per_head = qs_ref is not None

    @pl.when(iq == 0 if per_head else step == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if qs_ref is not None:
        @pl.when(step == 0)
        def _init_shared():
            dks_acc[...] = jnp.zeros_like(dks_acc)

    def _step():
        kb = k_ref[0]
        vb = v_ref[0]
        qb = q_ref[0]
        dob = do_ref[0]
        lseb = lse_ref[0, 0]
        deltab = delta_ref[0, 0]
        s = _scores(qb, kb, qs_ref, ks_ref) * scale
        if mask_ref is not None:
            # broadcast, then convert, as the forward does: converting the
            # (bk,) row to bool first lowers to a relayout of every tile
            # that tripled both backward kernels on bf16 operands (v5e, PR
            # 26: dkv 2.47 -> 0.78 ms, dq 1.58 -> 0.51 ms a call at
            # (384, 512, 64); gradients bit-identical)
            s = jnp.where(mask_ref[0, 0][None, :].astype(bool), s, _NEG)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + jk * bk
            s = jnp.where(_band(rows, cols, causal_off, window), s, _NEG)
        # masked entries: exp(s - lse) can overflow for fully-masked rows
        # (lse floors at m + log eps); they carry no gradient — zero them.
        p = jnp.where(s > _NEG * 0.5, jnp.exp(s - lseb[:, None]), 0.0)
        pb = p.astype(dob.dtype)
        dv_acc[...] += jax.lax.dot_general(
            pb, dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - deltab[:, None]) * scale).astype(qb.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if qs_ref is not None:
            dks_acc[...] += jax.lax.dot_general(
                ds, qs_ref[0], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if causal:
        pl.when(_causal_live(iq, jk, bq, bk, causal_off, window))(_step)
    else:
        _step()

    @pl.when(iq == pl.num_programs(2) // group - 1 if per_head else step == last)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    if qs_ref is not None:
        @pl.when(step == last)
        def _finish_shared():
            dks_ref[0] = dks_acc[...].astype(dks_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   qs_ref, ks_ref, mask_ref, dq_ref, *more, scale, causal,
                   causal_off, window=None):
    if qs_ref is None:
        dq_acc, = more
    else:
        dqs_ref, dq_acc, dqs_acc = more
    bq, d = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[1]
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        if qs_ref is not None:
            dqs_acc[...] = jnp.zeros_like(dqs_acc)

    def _step():
        qb = q_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        dob = do_ref[0]
        lseb = lse_ref[0, 0]
        deltab = delta_ref[0, 0]
        s = _scores(qb, kb, qs_ref, ks_ref) * scale
        if mask_ref is not None:
            s = jnp.where(mask_ref[0, 0][None, :].astype(bool), s, _NEG)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + jk * bk
            s = jnp.where(_band(rows, cols, causal_off, window), s, _NEG)
        p = jnp.where(s > _NEG * 0.5, jnp.exp(s - lseb[:, None]), 0.0)
        dp = jax.lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - deltab[:, None]) * scale).astype(kb.dtype)
        dq_acc[...] += jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if qs_ref is not None:
            dqs_acc[...] += jax.lax.dot_general(
                ds, ks_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if causal:
        pl.when(_causal_live(iq, jk, bq, bk, causal_off, window))(_step)
    else:
        _step()

    @pl.when(jk == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)
        if qs_ref is not None:
            dqs_ref[0] = dqs_acc[...].astype(dqs_ref.dtype)


def _bwd(q, k, v, key_mask, causal, scale, o, lse, do, dlse=None,
         window=None, shared=None):
    """``(dq, dk, dv)``, and with ``shared`` a fourth: ``(dq_s, dk_s)``."""
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    G = _kv_group(q, k)                 # query heads per K/V head
    # query heads that one row of the dkv grid streams: the K/V head's
    # group, or with the shared pair every head of the batch row
    S = G if shared is None else H
    bq, bk = _bq(Lq), _bk(Lk)
    BH, BHkv = B * H, B * H // G
    nq, nk = Lq // bq, Lk // bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        # The lse output's cotangent enters the score gradient as
        # ds += p * dlse — algebraically a shift of delta, so the same
        # backward kernels serve the (o, lse) block-attention entry used by
        # ring attention.
        delta = delta - dlse.astype(jnp.float32)
    q3 = q.reshape(BH, Lq, D)
    k3, v3 = k.reshape(BHkv, Lk, D), v.reshape(BHkv, Lk, D)
    do3 = do.reshape(BH, Lq, D)
    lse3 = lse.reshape(BH, 1, Lq)
    delta3 = delta.reshape(BH, 1, Lq)
    interpret = _interpret_for(q3)
    kwargs = {} if interpret else {"compiler_params": _COMPILER_PARAMS}
    off = Lk - Lq
    args = [q3, k3, v3, do3, lse3, delta3]
    if shared is not None:
        Ds = shared[0].shape[-1]
        args += [shared[0].reshape(BH, Lq, Ds), shared[1].reshape(B, Lk, Ds)]
    if key_mask is not None:
        args.append(key_mask.astype(jnp.int32).reshape(-1, 1, Lk))
    present = (shared is not None, shared is not None, key_mask is not None)

    # ---- dk/dv: fixed k-block (parallel), stream q-blocks (arbitrary);
    # grid over K/V heads, the stream covering the group's query heads
    if S == 1:
        def qh(b, t):                   # the query head of step t
            return b
    else:
        def qh(b, t):
            return b * S + t // nq
    if causal:      # dead tiles fetch nothing (see _live_k)
        def ii(j, t):
            return _live_q(j, bq, bk, nq, off, window)(t if S == 1 else t % nq)
    elif S == 1:
        def ii(j, t):
            return t
    else:
        def ii(j, t):
            return t % nq

    def q_spec(width):
        return pl.BlockSpec((1, bq, width), lambda b, j, t: (qh(b, t), ii(j, t), 0),
                            memory_space=_VMEM)
    row_spec = pl.BlockSpec((1, 1, bq), lambda b, j, t: (qh(b, t), 0, ii(j, t)),
                            memory_space=_VMEM)
    # a K/V head's block is the grid row's own; with the shared pair it is
    # the streamed head's, and the grid row's own block is the shared key's
    kv_spec = pl.BlockSpec(
        (1, bk, D), (lambda b, j, t: (b, j, 0)) if shared is None else
        (lambda b, j, t: (qh(b, t), j, 0)), memory_space=_VMEM)
    dkv_specs = [q_spec(D), kv_spec, kv_spec, q_spec(D), row_spec, row_spec]
    out_specs = [kv_spec, kv_spec]
    out_shape = [jax.ShapeDtypeStruct((BHkv, Lk, D), k.dtype),
                 jax.ShapeDtypeStruct((BHkv, Lk, D), v.dtype)]
    scratch = [pltpu.VMEM((bk, D), jnp.float32), pltpu.VMEM((bk, D), jnp.float32)]
    if shared is not None:
        ks_spec = pl.BlockSpec((1, bk, Ds), lambda b, j, t: (b, j, 0),
                               memory_space=_VMEM)
        dkv_specs += [q_spec(Ds), ks_spec]
        out_specs.append(ks_spec)
        out_shape.append(jax.ShapeDtypeStruct((B, Lk, Ds), shared[1].dtype))
        scratch.append(pltpu.VMEM((bk, Ds), jnp.float32))
    if key_mask is not None:
        dkv_specs.append(pl.BlockSpec((1, 1, bk),
                                      lambda b, j, t: (b // (H // S), 0, j),
                                      memory_space=_VMEM))
    dkv_kern = functools.partial(
        _optional_inputs(_bwd_dkv_kernel, 6, present),
        scale=scale, causal=causal, causal_off=off, window=window,
        **({} if S == 1 else {"group": S}))
    dk, dv, *dks = pl.pallas_call(
        dkv_kern,
        name=_kernel_name("flash_bwd_dkv", window, shared),
        grid=(BH // S, nk, S * nq),
        in_specs=dkv_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        **kwargs,
    )(*args)

    # ---- dq: fixed q-block (parallel), stream k-blocks (arbitrary)
    if causal:
        def jj(i, j):
            return _live_k(i, bq, bk, nk, off, window)(j)
    else:
        def jj(i, j):
            return j

    def q_spec(width):
        return pl.BlockSpec((1, bq, width), lambda b, i, j: (b, i, 0),
                            memory_space=_VMEM)
    row_spec = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i),
                            memory_space=_VMEM)
    kv_spec = pl.BlockSpec(
        (1, bk, D), (lambda b, i, j: (b, jj(i, j), 0)) if G == 1 else
        (lambda b, i, j: (b // G, jj(i, j), 0)), memory_space=_VMEM)
    dq_specs = [q_spec(D), kv_spec, kv_spec, q_spec(D), row_spec, row_spec]
    out_specs, out_shape = [q_spec(D)], [jax.ShapeDtypeStruct((BH, Lq, D), q.dtype)]
    scratch = [pltpu.VMEM((bq, D), jnp.float32)]
    if shared is not None:
        dq_specs += [q_spec(Ds),
                     pl.BlockSpec((1, bk, Ds), lambda b, i, j: (b // H, jj(i, j), 0),
                                  memory_space=_VMEM)]
        out_specs.append(q_spec(Ds))
        out_shape.append(jax.ShapeDtypeStruct((BH, Lq, Ds), shared[0].dtype))
        scratch.append(pltpu.VMEM((bq, Ds), jnp.float32))
    if key_mask is not None:
        dq_specs.append(pl.BlockSpec((1, 1, bk),
                                     lambda b, i, j: (b // H, 0, jj(i, j)),
                                     memory_space=_VMEM))
    dq_kern = functools.partial(
        _optional_inputs(_bwd_dq_kernel, 6, present),
        scale=scale, causal=causal, causal_off=off, window=window)
    dq, *dqs = pl.pallas_call(
        dq_kern,
        name=_kernel_name("flash_bwd_dq", window, shared),
        grid=(BH, nq, nk),
        in_specs=dq_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        **kwargs,
    )(*args)
    grads = (dq.reshape(B, H, Lq, D), dk.reshape(B, H // G, Lk, D),
             dv.reshape(B, H // G, Lk, D))
    if shared is None:
        return grads
    return grads + ((dqs[0].reshape(B, H, Lq, Ds), dks[0].reshape(B, 1, Lk, Ds)),)


# ---------------------------------------------------------------------------
# public entry with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash(q, k, v, key_mask, shared, causal, scale, window=None):
    o, _ = _fwd(q, k, v, key_mask, causal, scale, window, shared)
    return o


def _named(o, lse):
    return checkpoint_name(o, REMAT_KEEP[0]), checkpoint_name(lse, REMAT_KEEP[1])


def _flash_fwd(q, k, v, key_mask, shared, causal, scale, window=None):
    o, lse = _named(*_fwd(q, k, v, key_mask, causal, scale, window, shared))
    return o, (q, k, v, key_mask, shared, o, lse)


def _flash_bwd(causal, scale, window, res, do):
    q, k, v, key_mask, shared, o, lse = res
    dq, dk, dv, *dshared = _bwd(q, k, v, key_mask, causal, scale, o, lse, do,
                                window=window, shared=shared)
    return dq, dk, dv, None, (dshared[0] if dshared else None)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# block-attention entry for ring attention: returns (o, lse), differentiable
# in both outputs (the lse cotangent folds into delta — see _bwd).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def flash_block(q, k, v, key_mask, causal, scale):
    """One K/V block's attention returning ``(o, lse)`` — the unit ring
    attention merges per hop. Same mask/shape contract as flash_attention."""
    return _fwd(q, k, v, key_mask, causal, scale)


def _flash_block_fwd(q, k, v, key_mask, causal, scale):
    o, lse = _named(*_fwd(q, k, v, key_mask, causal, scale))
    return (o, lse), (q, k, v, key_mask, o, lse)


def _flash_block_bwd(causal, scale, res, cts):
    do, dlse = cts
    q, k, v, key_mask, o, lse = res
    dq, dk, dv = _bwd(q, k, v, key_mask, causal, scale, o, lse,
                      do.astype(q.dtype), dlse)
    return dq, dk, dv, None


flash_block.defvjp(_flash_block_fwd, _flash_block_bwd)


def flash_attention(q, k, v, mask=None, causal: bool = False,
                    scale: Optional[float] = None,
                    window: Optional[int] = None, shared=None):
    """Blockwise attention, O(L·D) memory. See module docstring for the
    supported mask forms; unsupported ones should be routed to the XLA path
    by the caller (dot_product_attention does this via flash_supported).

    ``window`` (requires ``causal=True``): causal sliding-window attention —
    position i attends to the ``window`` most recent keys only. Tiles fully
    outside the band are skipped, so compute is O(L·window) not O(L²): the
    Mistral-style long-context recipe, native to the tile grid.

    ``shared=(q_s, k_s)``: a second score term, ``q_s (B, H, Lq, Ds)``
    against the one key head ``k_s (B, 1, Lk, Ds)`` that every query head
    reads (module docstring); the default scale is then ``(D + Ds) ** -0.5``."""
    if shared is not None:
        shared = tuple(shared)
        if not _shared_ok(q, k, *shared):
            raise ValueError(
                "shared=(q_s, k_s) needs q_s (B, H, Lq, Ds), k_s (B, 1, Lk, Ds) "
                "and as many key heads as query heads; got q "
                f"{q.shape}, k {k.shape}, q_s {shared[0].shape}, k_s {shared[1].shape}")
    width = q.shape[-1] + (0 if shared is None else shared[0].shape[-1])
    scale = (width ** -0.5) if scale is None else float(scale)
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    if window is not None:
        window = int(window)
        if window <= 0:
            raise ValueError(f"window must be positive, got {window}")
        if not causal:
            raise ValueError("window= requires causal=True (the sliding "
                             "window is defined over the causal band)")
    if Lq % _bq(Lq) or Lk % _bk(Lk):
        raise ValueError(
            f"flash_attention needs Lq/Lk divisible by the block size "
            f"({_bq(Lq)}/{_bk(Lk)}); got Lq={Lq}, Lk={Lk} — pad the "
            "sequence or use the XLA path (dot_product_attention impl='xla')")
    key_mask = _as_key_mask(mask, B, H, Lq, Lk)
    if mask is not None and key_mask is None:
        raise ValueError("flash_attention supports key-padding masks "
                         "(B, Lk) / (B,1,1,Lk); use the XLA path otherwise")
    return _flash(q, k, v, key_mask, shared, causal, scale, window)
