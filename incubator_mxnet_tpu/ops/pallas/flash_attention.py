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
- A non-causal call runs the rectangular grid ``(batch·head, fixed block,
  streamed block)``: the first two "parallel", the streamed one "arbitrary"
  (it carries the softmax recurrence).
- A causal call (with or without ``window``) **walks a list of its live
  tiles**. What the call can see when it is traced (``Lq``, ``Lk``, the
  tile sizes, the window, and for dkv the heads a grid row streams) fixes
  which tiles the band leaves live and in what order the rectangle would
  visit them; ``_tile_schedule`` builds that list once a shape, in numpy,
  and the kernel takes it as scalar prefetch (as ``moe_gmm`` and
  ``moe_rows`` take theirs). The grid is ``(batch·head, steps)``: no step
  exists for a dead tile (47% of the rectangle for a full causal call at
  L = 8,192 and 512-row tiles, 73% with a window of 2,048), every index map
  reads its block from the tables, and a step's ``kind`` says whether it is
  the first or last of its fixed block (scratch zeroed, result written) and
  whether the band cuts the tile or leaves it **whole**. Only a cut tile
  builds the in-tile mask (two iotas, compares, a select; in the backward
  kernels a second select on hidden pairs); a whole tile runs the same
  body without it. Same tiles, same order, same arithmetic on every visible
  pair as the rectangular walk. Gauges ``mxtpu_flash_tiles_live_share`` and
  ``mxtpu_flash_tiles_cut_share`` ``{kernel=}`` say how far it engages.

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

Two layouts, one set of kernel bodies. **Head-major** (``flash_attention``):
``q (B, H, Lq, D)``, ``k, v (B, Hkv, Lk, D)``, a block a head; what
``dot_product_attention`` hands over, and every call of the decoders, whose
prologues write that layout (grouped K/V, windows, the shared pair and the
ring's hops are this layout's alone). **Lane blocks**
(``flash_attention_lanes``, behind ``ops.attention.projected_attention``):
the projections' own arrays, the fused ``(B, L, 3C)`` q, k, v of
self-attention or ``(B, Lq, C)`` with ``(B, Lk, 2C)`` of cross-attention,
read as 128-lane column blocks through the index maps, one head a block at
D = 128 and two at D = 64 (a grid row is a batch row and a lane block; a
head's scores see its own lanes, the others zeroed, at the MXU cost a
64-wide contraction pays anyway, and each head keeps its own softmax state).
``o`` is written as ``(B, Lq, C)``, what the output projection reads; the
dkv kernel writes k's and v's gradient blocks by DMA into one buffer shaped
like their array, and the dq kernel q's into the same buffer where q came
from it too, so the fused projection's gradient is one array. No
head-major transpose exists in HBM either way: BERT's encoder
(``models/transformer.py`` ``MultiHeadAttention``) hands its projections to
``projected_attention``, which takes this layout where the call allows it.
The kernels keep their names.

Masking: ``causal`` and/or a key-padding mask of shape (B, Lk) (1 = valid).
The generic (B, H, Lq, Lk) mask case falls back to the XLA path in
``ops/attention.py`` — loading an L² mask would defeat the point.
"""
from __future__ import annotations

import collections
import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM

__all__ = ["flash_attention", "flash_attention_lanes", "flash_supported", "REMAT_KEEP"]

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

    Why no smaller tile is chosen: a grid step has a fixed cost beside its
    tile's work (v5e, PR 32's chip runs, PERF.md section 6: an empty step of
    the causal kernels took 0.06-0.25 us and a 512 x 512 tile 1.4-2.0 us), so
    bigger tiles amortize it, and VMEM comfortably holds a 512-row block up
    to D=256. One 128-row tile a (row, head) is the measured bad case
    (`bert_base_pretrain.phase1_l128`: the kernels take longer than at
    L=512 for a quarter of the pairs). The figures this docstring carried
    until PR 32 (a BERT-base step at three tilings) dated from 2026-07-30
    and an earlier installation and were never measured again; tile shape
    is ROADMAP S1 (e). Env overrides remain available.
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


#: grid semantics for Mosaic. The rectangular grid of a non-causal call:
#: (batch·head, fixed block) are parallel, the streamed block carries the
#: recurrence. A causal call walks its schedule in one dimension, fixed
#: blocks one after another, so that dimension is "arbitrary".
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))
_SCHEDULED_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


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


def _band(rows, cols, causal_off, window):
    """The in-tile visibility mask for causal (+ optional window)."""
    live = cols <= rows + causal_off
    if window is not None:
        live = jnp.logical_and(live, cols > rows + causal_off - window)
    return live


#: what one step of a causal call's schedule is, as bits of its ``kind``:
#: the first / last step of its fixed block (scratch zeroed / written out),
#: and a tile the band leaves whole (every pair visible) or cuts (some
#: hidden). A step with neither is the one a fixed block gets that sees
#: nothing, so that its zeros are written. Where several heads stream past a
#: fixed block (dkv), first / last are a head's and open / close the block's.
_FIRST, _LAST, _WHOLE, _CUT, _OPEN, _CLOSE = 1, 2, 4, 8, 16, 32

_Schedule = collections.namedtuple(
    "_Schedule", "tables live_share cut_share")


@functools.lru_cache(maxsize=None)
def _tile_schedule(Lq, Lk, bq, bk, window=None, heads=None) -> _Schedule:
    """The tiles a causal call visits, in the order it visits them (bottom-
    right aligned: col <= row + Lk - Lq; with a sliding window additionally
    col > row + Lk - Lq - window). Dead tiles are no step at all: a window
    turns the O(L²) tile grid into O(L·W) steps.

    ``tables`` are int32 vectors with an entry a step. ``heads=None``
    (forward, dq): ``(q-block, k-block, kind)``, k-blocks streamed past each
    q-block. ``heads=S`` (dkv): ``(k-block, q-block, kind, head)``, for each
    k-block the q-blocks of head 0, then of head 1, ... of the ``S`` query
    heads a grid row streams. ``live_share`` is live tiles over the
    rectangle's, ``cut_share`` cut tiles over live ones. Built once a shape:
    every layer of a model hands its kernels the same constants."""
    nq, nk, off = Lq // bq, Lk // bk, Lk - Lq
    row0, col0 = np.arange(nq)[:, None] * bq, np.arange(nk)[None, :] * bk
    # col - row over a tile takes every integer of [lo, hi]
    lo, hi = col0 - (row0 + bq - 1), col0 + (bk - 1) - row0
    live, whole = lo <= off, hi <= off
    if window is not None:
        live, whole = live & (hi > off - window), whole & (lo > off - window)
    kinds = np.where(whole, _WHOLE, np.where(live, _CUT, 0))
    steps = []                          # [fixed, streamed, kind, head]
    for fixed, kind in enumerate(kinds if heads is None else kinds.T):
        seen = np.flatnonzero(kind)
        if not seen.size:               # names a block that is fetched already
            seen = [steps[-1][1] if steps else 0]
        opened = len(steps)
        for head in range(heads or 1):
            steps += [[fixed, s, kind[s], head] for s in seen]
            steps[-len(seen)][2] |= _FIRST
            steps[-1][2] |= _LAST
        steps[opened][2] |= _OPEN
        steps[-1][2] |= _CLOSE
    tables = np.asarray(steps, np.int32).T[:3 if heads is None else 4]
    return _Schedule(tuple(np.ascontiguousarray(t) for t in tables), live.mean(),
                     (live & ~whole).sum() / live.sum())


def _schedule(name, causal, *key):
    """The schedule of a causal call of the kernel ``name`` (None for a
    non-causal one, whose every tile is live and whole: the rectangular grid
    is its schedule), with the two gauges that say how far it engages."""
    if not causal:
        return None
    from ...telemetry import metrics
    sched = _tile_schedule(*key)
    for what, share, text in (
            ("live", sched.live_share, "Tiles a causal flash kernel visits over "
             "the rectangular grid's, last call traced"),
            ("cut", sched.cut_share, "Visited tiles of a causal flash kernel "
             "that keep the band mask, last call traced")):
        metrics.gauge(f"mxtpu_flash_tiles_{what}_share", text, kernel=name).set(share)
    return sched.tables


def _kernel_name(base: str, window, shared=None) -> str:
    """Windowed calls are named apart, so a trace separates a model's
    sliding layers from its full ones; so are calls with the shared second
    pair (latent attention)."""
    return (base + ("" if window is None else "_win")
            + ("" if shared is None else "_mla"))


def _optional_inputs(kernel, tables: int, fixed: int, present: tuple, unused: int = 0):
    """Pallas hands a kernel its refs by position: the ``tables`` of a
    schedule first (scalar prefetch), which ``kernel`` takes as one tuple
    (``None`` where there are none), then ``fixed`` inputs, then one ref for
    each entry of ``present``, then ``unused`` inputs the kernel does not
    see (a buffer its output is aliased to), then outputs and scratch: the
    absent ones are passed as ``None``."""
    def call(*refs, **kw):
        sched, refs = refs[:tables] or None, list(refs[tables:])
        given = iter(refs[fixed:fixed + sum(present)])
        optional = [next(given) if p else None for p in present]
        return kernel(sched, *refs[:fixed], *optional,
                      *refs[fixed + sum(present) + unused:], **kw)
    return call


def _walk(sched, group=1):
    """Where a grid step is: ``(fixed block, streamed block, (first, last,
    open, close), run)``. With a schedule it is what the tables say of step
    ``program_id(1)``, and ``run(step)`` runs ``step(cut)`` under the tile's
    kind: without the band on a whole tile, with it on a cut one, not at all
    where the fixed block sees nothing. Without one the grid is the
    rectangle ``(row, fixed, streamed)``, the stream running ``group`` times
    over the blocks, and every tile is whole."""
    if sched is None:
        fixed, step, n = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
        if group == 1:
            streamed, ends = step, (step == 0, step == n - 1) * 2
        else:
            streamed = step % (n // group)
            ends = (streamed == 0, streamed == n // group - 1, step == 0, step == n - 1)
        return fixed, streamed, ends, lambda body: body(False)
    t = pl.program_id(1)
    kind = sched[2][t]

    def run(body):
        pl.when(kind & _WHOLE != 0)(functools.partial(body, False))
        pl.when(kind & _CUT != 0)(functools.partial(body, True))
    return (sched[0][t], sched[1][t],
            tuple(kind & bit != 0 for bit in (_FIRST, _LAST, _OPEN, _CLOSE)), run)


def _scores(qb, kb, qs_ref, ks_ref, mask_ref, scale, band):
    """The scaled scores of one tile in fp32: ``q k^T``, plus the shared
    pair's product where the call has one, with ``_NEG`` where the key mask
    or ``band = (iq, jk, causal_off, window)``, given for a tile the band
    cuts, hides the pair."""
    s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if qs_ref is not None:
        s = s + jax.lax.dot_general(qs_ref[0], ks_ref[0], (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    s = s * scale                       # MXU dot in input dtype, scale in fp32
    if mask_ref is not None:
        # broadcast, then convert: converting the (bk,) row to bool first
        # lowers to a relayout of every tile that tripled both backward
        # kernels on bf16 operands (v5e, PR 26: dkv 2.47 -> 0.78 ms, dq
        # 1.58 -> 0.51 ms a call at (384, 512, 64); gradients bit-identical)
        s = jnp.where(mask_ref[0, 0][None, :].astype(bool), s, _NEG)
    if band is not None:
        iq, jk, causal_off, window = band
        bq, bk = s.shape
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
        cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + jk * bk
        s = jnp.where(_band(rows, cols, causal_off, window), s, _NEG)
    return s


def _probs(s, lseb, hidden: bool):
    """``exp(s - lse)`` of a backward tile. Where a pair can be hidden,
    ``exp`` can overflow for fully-masked rows (lse floors at m + log eps);
    hidden pairs carry no gradient: zero them."""
    if not hidden:
        return jnp.exp(s - lseb[:, None])
    return jnp.where(s > _NEG * 0.5, jnp.exp(s - lseb[:, None]), 0.0)


def _lanes_of(x, h, heads):
    """Where head ``h`` of the ``heads`` a lane block holds sits in ``x``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return lane // (x.shape[-1] // heads) == h


def _head(x, h, heads):
    """Head ``h`` of a block of ``heads``, the other heads' lanes zeroed: a
    product that contracts over the lanes then sees that head alone, at the
    MXU's cost of a 128-wide contraction, which a 64-wide one pays too. A
    block of one head is returned as it is."""
    return x if heads == 1 else jnp.where(_lanes_of(x, h, heads), x, 0)


def _merge(parts):
    """One block from a full-width value a head: each head's lanes from its
    own. One head's value is the block."""
    out = parts[0]
    for h, part in enumerate(parts[1:], 1):
        out = jnp.where(_lanes_of(part, h, len(parts)), part, out)
    return out


# ---------------------------------------------------------------------------
# forward: K/V streamed block by block past each q-block, state in scratch.
# Grid (B·H, nq, nk), or for a causal call (B·H, steps of the schedule)
# ---------------------------------------------------------------------------

def _fwd_kernel(sched, q_ref, k_ref, v_ref, qs_ref, ks_ref, mask_ref, o_ref,
                lse_ref, *scratch, scale, causal_off, window=None, heads=1):
    # ``heads`` heads a block (the lane layout at D < 128), each with its
    # own softmax state: (acc, m, l) a head
    iq, jk, (first, last, _, _), run = _walk(sched)
    state = [scratch[3 * h:3 * h + 3] for h in range(heads)]

    @pl.when(first)
    def _init():
        for acc_ref, m_ref, l_ref in state:
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, _NEG)
            l_ref[...] = jnp.zeros_like(l_ref)

    def _step(cut):
        for h, (acc_ref, m_ref, l_ref) in enumerate(state):
            s = _scores(_head(q_ref[0], h, heads), k_ref[0], qs_ref, ks_ref, mask_ref,
                        scale, (iq, jk, causal_off, window) if cut else None)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            # a head's p against the whole block of v: its own lanes are
            # its output, the others' are dropped by _merge
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

    run(_step)

    @pl.when(last)
    def _finish():
        # fully-masked rows → output 0
        ls = [jnp.maximum(l_ref[...], 1e-30) for _, _, l_ref in state]
        o_ref[0] = _merge([acc_ref[...] / l for (acc_ref, _, _), l in zip(state, ls)]
                          ).astype(o_ref.dtype)
        for h, ((_, m_ref, _), l) in enumerate(zip(state, ls)):
            lse_ref[h, 0] = (m_ref[...] + jnp.log(l))[:, 0]


def _scratch(bq, d):
    return [pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32)]


def _pallas(kernel, name, sched, rectangle, interpret, in_specs, out_specs,
            out_shape, scratch, args, in_order=False, aliases=None):
    """One ``pallas_call``: over the grid ``rectangle``, or where the call
    has a schedule over ``(rows, its steps)`` with the tables prefetched, so
    that every index map reads its block from them. ``in_order``: every
    dimension "arbitrary" (a kernel that waits for one step's DMA in a
    later one); ``aliases``: ``input_output_aliases``."""
    kwargs = {} if interpret else {
        "compiler_params": _COMPILER_PARAMS if sched is None else _SCHEDULED_PARAMS}
    if in_order and not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * (3 if sched is None else 2))
    if aliases:
        kwargs["input_output_aliases"] = aliases
    if sched is None:
        return pl.pallas_call(
            kernel, name=name, grid=rectangle, in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
            interpret=interpret, **kwargs)(*args)
    return pl.pallas_call(
        kernel, name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(sched), grid=(rectangle[0], len(sched[0])),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape, interpret=interpret, **kwargs)(*sched, *args)


def _spec(place, shape, block):
    """A block of ``shape`` at ``block(b, h, i, j)``: grid row, query head,
    q-block and k-block, which ``place`` reads off the grid's indices (and a
    schedule's tables)."""
    return pl.BlockSpec(shape, lambda *g: block(*place(*g)), memory_space=_VMEM)


def _q_block(b, h, i, j):
    return h, i, 0


def _q_row(b, h, i, j):
    return h, 0, i


def _kv_block(G):
    """The K/V block a query head reads: its own, or its group's."""
    if G == 1:
        return lambda b, h, i, j: (h, j, 0)
    return lambda b, h, i, j: (h // G, j, 0)


def _place_qk(sched):
    """``place`` of a grid whose rows are query heads and whose fixed block
    is the q-block (forward, dq)."""
    if sched is None:
        return lambda b, i, j: (b, b, i, j)
    return lambda b, t, qi, kj, kind: (b, b, qi[t], kj[t])


def _fwd(q, k, v, key_mask, causal, scale, window=None, shared=None):
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    G = _kv_group(q, k)                 # query heads per K/V head
    bq, bk = _bq(Lq), _bk(Lk)
    BH = B * H
    name = _kernel_name("flash_fwd", window, shared)
    sched = _schedule(name, causal, Lq, Lk, bq, bk, window)
    spec = functools.partial(_spec, _place_qk(sched))
    q3 = q.reshape(BH, Lq, D)
    k3 = k.reshape(BH // G, Lk, D)
    v3 = v.reshape(BH // G, Lk, D)
    kv_spec = spec((1, bk, D), _kv_block(G))
    in_specs = [spec((1, bq, D), _q_block), kv_spec, kv_spec]
    args = [q3, k3, v3]
    if shared is not None:
        # the one key head all H query heads read: block h // H, as a
        # grouped K/V head is read through h // G
        Ds = shared[0].shape[-1]
        in_specs += [spec((1, bq, Ds), _q_block),
                     spec((1, bk, Ds), lambda b, h, i, j: (h // H, j, 0))]
        args += [shared[0].reshape(BH, Lq, Ds), shared[1].reshape(B, Lk, Ds)]
    if key_mask is not None:
        # (B, 1, Lk): TPU block shapes need the trailing two dims to be
        # tile-divisible or whole, so the mask rides with a singleton row.
        in_specs.append(spec((1, 1, bk), lambda b, h, i, j: (h // H, 0, j)))
        args.append(key_mask.astype(jnp.int32).reshape(key_mask.shape[0], 1, Lk))
    kern = functools.partial(
        _optional_inputs(_fwd_kernel, len(sched or ()), 3,
                         (shared is not None, shared is not None,
                          key_mask is not None)),
        scale=scale, causal_off=Lk - Lq, window=window)
    o, lse = _pallas(
        kern, name, sched, (BH, Lq // bq, Lk // bk), _interpret_for(q3), in_specs,
        [spec((1, bq, D), _q_block), spec((1, 1, bq), _q_row)],
        [jax.ShapeDtypeStruct((BH, Lq, D), q.dtype),
         jax.ShapeDtypeStruct((BH, 1, Lq), jnp.float32)],
        _scratch(bq, D), args)
    return o.reshape(B, H, Lq, D), lse.reshape(B, H, Lq)


# ---------------------------------------------------------------------------
# backward: the dkv kernel streams q-blocks past each k-block (grid B·H / S,
# nk, S·nq), the dq kernel k-blocks past each q-block (grid B·H, nq, nk);
# a causal call walks its schedules. delta = rowsum(do * o) precomputed with
# plain jnp.
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(sched, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    qs_ref, ks_ref, mask_ref, *refs, scale, causal_off,
                    window=None, group=1, heads=1, lanes=None):
    # ``lanes`` (the lane layout): dk and dv leave by DMA, not as blocks of
    # two outputs, because both are lane blocks of one buffer (section
    # "lane layout" below); refs are then that buffer, the accumulators, the
    # two blocks' staging buffers and their semaphores
    dks_ref = dks_acc = None
    if lanes is not None:
        out_ref, *accs, dk_buf, dv_buf, sem = refs
    elif qs_ref is None:
        dk_ref, dv_ref, *accs = refs
    else:
        dk_ref, dv_ref, dks_ref, *accs, dks_acc = refs
    accs = [accs[2 * h:2 * h + 2] for h in range(heads)]
    # the streamed dimension runs over the q-blocks of every query head of
    # this K/V head's group, one head after another: dk and dv add them up.
    # With the shared pair the group is the shared key's (all the heads of a
    # row): k and v are a head's own, so dk and dv start and end with each
    # head's q-blocks, and only dk_s adds up the whole stream
    jk, iq, (first, last, opened, closed), run = _walk(sched, group)
    if qs_ref is None:
        first, last = opened, closed
    if lanes is not None:               # read outside any pl.when: interpret mode
        grid_row, grid_rows = pl.program_id(0), pl.num_programs(0)

    @pl.when(first)
    def _init():
        for dk_acc, dv_acc in accs:
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

    if qs_ref is not None:
        @pl.when(opened)
        def _init_shared():
            dks_acc[...] = jnp.zeros_like(dks_acc)

    def _step(cut):
        kb = k_ref[0]
        vb = v_ref[0]
        qb = q_ref[0]
        dob = do_ref[0]
        for h, (dk_acc, dv_acc) in enumerate(accs):
            lseb = lse_ref[h, 0]
            deltab = delta_ref[h, 0]
            s = _scores(qb, _head(kb, h, heads), qs_ref, ks_ref, mask_ref, scale,
                        (iq, jk, causal_off, window) if cut else None)
            p = _probs(s, lseb, cut or mask_ref is not None)
            pb = p.astype(dob.dtype)
            dv_acc[...] += jax.lax.dot_general(
                pb, dob, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(dob, _head(vb, h, heads), (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - deltab[:, None]) * scale).astype(qb.dtype)
            dk_acc[...] += jax.lax.dot_general(
                ds, qb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if qs_ref is not None:
                dks_acc[...] += jax.lax.dot_general(
                    ds, qs_ref[0], (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

    run(_step)

    @pl.when(last)
    def _finish():
        if lanes is None:
            dk_ref[0] = _merge([dk_acc[...] for dk_acc, _ in accs]).astype(dk_ref.dtype)
            dv_ref[0] = _merge([dv_acc[...] for _, dv_acc in accs]).astype(dv_ref.dtype)
            return
        _write_lanes(out_ref, sem, (grid_row, grid_rows, jk), lanes, (
            (dk_buf, _merge([dk_acc[...] for dk_acc, _ in accs])),
            (dv_buf, _merge([dv_acc[...] for _, dv_acc in accs]))))

    if qs_ref is not None:
        @pl.when(closed)
        def _finish_shared():
            dks_ref[0] = dks_acc[...].astype(dks_ref.dtype)


def _write_lanes(out_ref, sem, where, lanes, blocks):
    """Write a fixed k-block's results into lane blocks of ``out_ref``
    (HBM): each ``(staging buffer, value)`` of ``blocks`` is stored and
    copied to block column ``col + p`` (``lanes = (P, cols, nk)``; ``where =
    (g, grid rows, jk)``: grid row ``g`` is batch row ``g // P``, lane block
    ``p = g % P``), rows ``jk``.
    A copy is waited for when the next block's is about to reuse its
    buffer, so it runs under that block's work; the grid's last block waits
    for its own. The grid runs in order (every dimension "arbitrary"): a
    chain that ended at each grid row would let the rows run in parallel
    on a chip with two TensorCores, but a row holds one k-block wherever
    ``bk`` is the whole length (BERT's L = 512 and 128), so every copy
    would be waited for where it starts."""
    (P, cols, nk), (g, grid_rows, jk) = lanes, where
    bk, W = blocks[0][0].shape
    rows = pl.ds(pl.multiple_of(jk * bk, bk), bk)
    copies = [pltpu.make_async_copy(
        buf, out_ref.at[g // P, rows, pl.ds(pl.multiple_of((col + g % P) * W, W), W)],
        sem.at[i]) for i, ((buf, _), col) in enumerate(zip(blocks, cols))]

    @pl.when((g > 0) | (jk > 0))
    def _previous():
        for copy in copies:
            copy.wait()

    for buf, value in blocks:
        buf[...] = value.astype(buf.dtype)
    for copy in copies:
        copy.start()

    @pl.when((g == grid_rows - 1) & (jk == nk - 1))
    def _own():
        for copy in copies:
            copy.wait()


def _bwd_dq_kernel(sched, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   qs_ref, ks_ref, mask_ref, dq_ref, *more, scale,
                   causal_off, window=None, heads=1):
    if qs_ref is None:
        dq_accs = more                  # one a head of the block
    else:
        dqs_ref, *dq_accs, dqs_acc = more
    iq, jk, (first, last, _, _), run = _walk(sched)

    @pl.when(first)
    def _init():
        for dq_acc in dq_accs:
            dq_acc[...] = jnp.zeros_like(dq_acc)
        if qs_ref is not None:
            dqs_acc[...] = jnp.zeros_like(dqs_acc)

    def _step(cut):
        qb = q_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        dob = do_ref[0]
        for h, dq_acc in enumerate(dq_accs):
            lseb = lse_ref[h, 0]
            deltab = delta_ref[h, 0]
            s = _scores(_head(qb, h, heads), kb, qs_ref, ks_ref, mask_ref, scale,
                        (iq, jk, causal_off, window) if cut else None)
            p = _probs(s, lseb, cut or mask_ref is not None)
            dp = jax.lax.dot_general(_head(dob, h, heads), vb, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - deltab[:, None]) * scale).astype(kb.dtype)
            dq_acc[...] += jax.lax.dot_general(
                ds, kb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if qs_ref is not None:
                dqs_acc[...] += jax.lax.dot_general(
                    ds, ks_ref[0], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

    run(_step)

    @pl.when(last)
    def _finish():
        dq_ref[0] = _merge([dq_acc[...] for dq_acc in dq_accs]).astype(dq_ref.dtype)
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
    interpret = _interpret_for(q3)
    args = [q3, k.reshape(BHkv, Lk, D), v.reshape(BHkv, Lk, D),
            do.reshape(BH, Lq, D), lse.reshape(BH, 1, Lq), delta.reshape(BH, 1, Lq)]
    Ds = None
    if shared is not None:
        Ds = shared[0].shape[-1]
        args += [shared[0].reshape(BH, Lq, Ds), shared[1].reshape(B, Lk, Ds)]
    if key_mask is not None:
        args.append(key_mask.astype(jnp.int32).reshape(-1, 1, Lk))
    present = (shared is not None, shared is not None, key_mask is not None)
    params = dict(scale=scale, causal_off=Lk - Lq, window=window)

    # ---- dk/dv: fixed k-block, stream q-blocks; grid rows are K/V heads
    # (batch rows with the shared pair), the stream covering their S query
    # heads one after another
    name = _kernel_name("flash_bwd_dkv", window, shared)
    sched = _schedule(name, causal, Lq, Lk, bq, bk, window, S)
    if sched is None:
        def place(b, j, t):
            return (b, b, t, j) if S == 1 else (b, b * S + t // nq, t % nq, j)
    else:
        def place(b, t, kj, qi, kind, head):
            return b, (b if S == 1 else b * S + head[t]), qi[t], kj[t]
    spec = functools.partial(_spec, place)
    # a K/V head's block is the grid row's own; with the shared pair it is
    # the streamed head's, and the grid row's own block is the shared key's
    kv_spec = spec((1, bk, D), (lambda b, h, i, j: (b, j, 0)) if shared is None
                   else (lambda b, h, i, j: (h, j, 0)))
    in_specs = [spec((1, bq, D), _q_block), kv_spec, kv_spec,
                spec((1, bq, D), _q_block), spec((1, 1, bq), _q_row),
                spec((1, 1, bq), _q_row)]
    out_specs = [kv_spec, kv_spec]
    out_shape = [jax.ShapeDtypeStruct((BHkv, Lk, D), k.dtype),
                 jax.ShapeDtypeStruct((BHkv, Lk, D), v.dtype)]
    scratch = [pltpu.VMEM((bk, D), jnp.float32), pltpu.VMEM((bk, D), jnp.float32)]
    if shared is not None:
        ks_spec = spec((1, bk, Ds), lambda b, h, i, j: (b, j, 0))
        in_specs += [spec((1, bq, Ds), _q_block), ks_spec]
        out_specs.append(ks_spec)
        out_shape.append(jax.ShapeDtypeStruct((B, Lk, Ds), shared[1].dtype))
        scratch.append(pltpu.VMEM((bk, Ds), jnp.float32))
    if key_mask is not None:
        in_specs.append(spec((1, 1, bk), lambda b, h, i, j: (b // (H // S), 0, j)))
    dk, dv, *dks = _pallas(
        functools.partial(
            _optional_inputs(_bwd_dkv_kernel, len(sched or ()), 6, present),
            **params, **({} if S == 1 else {"group": S})),
        name, sched, (BH // S, nk, S * nq), interpret, in_specs, out_specs,
        out_shape, scratch, args)

    # ---- dq: fixed q-block, stream k-blocks; grid rows are query heads
    name = _kernel_name("flash_bwd_dq", window, shared)
    sched = _schedule(name, causal, Lq, Lk, bq, bk, window)
    spec = functools.partial(_spec, _place_qk(sched))
    kv_spec = spec((1, bk, D), _kv_block(G))
    in_specs = [spec((1, bq, D), _q_block), kv_spec, kv_spec,
                spec((1, bq, D), _q_block), spec((1, 1, bq), _q_row),
                spec((1, 1, bq), _q_row)]
    out_specs = [spec((1, bq, D), _q_block)]
    out_shape = [jax.ShapeDtypeStruct((BH, Lq, D), q.dtype)]
    scratch = [pltpu.VMEM((bq, D), jnp.float32)]
    if shared is not None:
        in_specs += [spec((1, bq, Ds), _q_block),
                     spec((1, bk, Ds), lambda b, h, i, j: (h // H, j, 0))]
        out_specs.append(spec((1, bq, Ds), _q_block))
        out_shape.append(jax.ShapeDtypeStruct((BH, Lq, Ds), shared[0].dtype))
        scratch.append(pltpu.VMEM((bq, Ds), jnp.float32))
    if key_mask is not None:
        in_specs.append(spec((1, 1, bk), lambda b, h, i, j: (h // H, 0, j)))
    dq, *dqs = _pallas(
        functools.partial(
            _optional_inputs(_bwd_dq_kernel, len(sched or ()), 6, present), **params),
        name, sched, (BH, nq, nk), interpret, in_specs, out_specs, out_shape,
        scratch, args)
    grads = (dq.reshape(B, H, Lq, D), dk.reshape(B, H // G, Lk, D),
             dv.reshape(B, H // G, Lk, D))
    if shared is None:
        return grads
    return grads + ((dqs[0].reshape(B, H, Lq, Ds), dks[0].reshape(B, 1, Lk, Ds)),)


# ---------------------------------------------------------------------------
# lane layout: the same kernels over the projections' own (B, L, n·H·D)
# arrays, read and written as 128-lane column blocks of ``128 // D`` heads.
# A grid row is (batch row, lane block): g = b·P + p with P = H·D / 128.
# ---------------------------------------------------------------------------

_LANES = 128

#: ``heads`` in all, ``per_block`` heads a 128-lane block, ``blocks`` = P
#: blocks a projection, ``cols``: the block columns where q, k and v start in
#: the arrays they are read from; the tiles ``bq``, ``bk`` and ``interpret``
#: as the call found them (static arguments of the jitted call below)
_Lanes = collections.namedtuple("_Lanes", "heads per_block blocks cols bq bk interpret")


def _lane_layout(x_q, x_kv, heads, mask=None) -> Optional[_Lanes]:
    """The lane layout of a call, or None where it has none: self-attention
    over one fused ``(B, L, 3C)`` projection (``x_kv`` None: q, k and v are
    its thirds), or cross-attention over ``(B, Lq, C)`` and ``(B, Lk, 2C)``
    (k, v), with ``C = heads * D``, ``D`` one of 32, 64, 128 (at most four
    heads a block, each an unrolled body with its own accumulators in VMEM),
    ``C`` whole blocks, lengths the tiles divide, a key mask or none."""
    if x_q.ndim != 3 or heads <= 0:
        return None
    B, Lq, width = x_q.shape
    if width % (3 if x_kv is None else 1):
        return None
    C = width // (3 if x_kv is None else 1)
    D = C // heads
    if C % heads or D not in (32, 64, 128) or C % _LANES:
        return None
    if x_kv is not None and (x_kv.ndim != 3 or x_kv.shape[0] != B
                             or x_kv.shape[2] != 2 * C or x_kv.dtype != x_q.dtype):
        return None
    Lk = (x_q if x_kv is None else x_kv).shape[1]
    bq, bk = _bq(Lq), _bk(Lk)
    if Lq % bq or Lk % bk:
        return None
    if mask is not None and _as_key_mask(mask, B, heads, Lq, Lk) is None:
        return None
    P = C // _LANES
    return _Lanes(heads, _LANES // D, P, (0, P, 2 * P) if x_kv is None else (0, 0, P),
                  bq, bk, _interpret_for(x_q))


def _lane_q(P, col):
    """A q-block's lane block: batch row, row block, column ``col + p``."""
    return lambda g, h, i, j: (g // P, i, col + g % P)


def _lane_k(P, col):
    return lambda g, h, i, j: (g // P, j, col + g % P)


def _lane_mask(P):
    return lambda g, h, i, j: (g // P, 0, j)


def _fwd_lanes(x_q, x_kv, key_mask, lay, causal, scale):
    """``o (B, Lq, C)`` and ``lse (B·H, 1, Lq)`` (a block's heads' rows
    together, as a grid row reads them)."""
    B, Lq, _ = x_q.shape
    kv = x_q if x_kv is None else x_kv
    Lk = kv.shape[1]
    H, n, P, (cq, ck, cv), bq, bk, interpret = lay
    name = "flash_fwd"
    sched = _schedule(name, causal, Lq, Lk, bq, bk)
    spec = functools.partial(_spec, _place_qk(sched))
    in_specs = [spec((1, bq, _LANES), _lane_q(P, cq)), spec((1, bk, _LANES), _lane_k(P, ck)),
                spec((1, bk, _LANES), _lane_k(P, cv))]
    args = [x_q, kv, kv]
    if key_mask is not None:
        in_specs.append(spec((1, 1, bk), _lane_mask(P)))
        args.append(key_mask.astype(jnp.int32).reshape(B, 1, Lk))
    kern = functools.partial(
        _optional_inputs(_fwd_kernel, len(sched or ()), 3, (False, False, key_mask is not None)),
        scale=scale, causal_off=Lk - Lq, heads=n)
    return _pallas(
        kern, name, sched, (B * P, Lq // bq, Lk // bk), interpret, in_specs,
        [spec((1, bq, _LANES), _lane_q(P, 0)), spec((n, 1, bq), _q_row)],
        [jax.ShapeDtypeStruct((B, Lq, P * _LANES), x_q.dtype),
         jax.ShapeDtypeStruct((B * H, 1, Lq), jnp.float32)],
        _scratch(bq, _LANES) * n, args)


def _bwd_lanes(x_q, x_kv, key_mask, lay, causal, scale, o, lse, do):
    """The gradients of ``x_q`` and ``x_kv`` in their own layout. The dkv
    kernel writes k's and v's lane blocks of one buffer shaped like the
    array they were read from; with self-attention the dq kernel then
    writes q's blocks into that same buffer (``input_output_aliases``), so
    the fused projection's gradient is one array and no concatenation."""
    B, Lq, _ = x_q.shape
    kv = x_q if x_kv is None else x_kv
    Lk = kv.shape[1]
    H, n, P, (cq, ck, cv), bq, bk, interpret = lay
    nq, nk = Lq // bq, Lk // bk
    # delta = rowsum(do * o) a head. Summed by a product with the 0/1 matrix
    # of which head a lane is: a reshape of the lanes into (H, D) is a
    # relayout copy on a TPU (v5e compile: five times the estimated cycles)
    D = _LANES // n
    heads_of = (np.arange(H * D)[:, None] // D == np.arange(H)[None, :]).astype(np.float32)
    delta = jnp.einsum("blc,ch->bhl", do.astype(jnp.float32) * o.astype(jnp.float32),
                       heads_of, precision=jax.lax.Precision.HIGHEST).reshape(B * H, 1, Lq)
    args = [x_q, kv, kv, do, lse, delta]
    present = (False, False, key_mask is not None)
    if key_mask is not None:
        args.append(key_mask.astype(jnp.int32).reshape(B, 1, Lk))
    params = dict(scale=scale, causal_off=Lk - Lq, heads=n)

    def operands(spec):
        specs = [spec((1, bq, _LANES), _lane_q(P, cq)), spec((1, bk, _LANES), _lane_k(P, ck)),
                 spec((1, bk, _LANES), _lane_k(P, cv)), spec((1, bq, _LANES), _lane_q(P, 0)),
                 spec((n, 1, bq), _q_row), spec((n, 1, bq), _q_row)]
        return specs + ([spec((1, 1, bk), _lane_mask(P))] if key_mask is not None else [])

    # ---- dk/dv: fixed k-block, stream q-blocks, out by DMA (_write_lanes)
    name = "flash_bwd_dkv"
    sched = _schedule(name, causal, Lq, Lk, bq, bk, None, 1)
    if sched is None:
        def place(b, j, t):
            return b, b, t, j
    else:
        def place(b, t, kj, qi, kind, head):
            return b, b, qi[t], kj[t]
    dkv, = _pallas(
        functools.partial(_optional_inputs(_bwd_dkv_kernel, len(sched or ()), 6, present),
                          **params, lanes=(P, (ck, cv), nk)),
        name, sched, (B * P, nk, nq), interpret, operands(functools.partial(_spec, place)),
        [pl.BlockSpec(memory_space=pl.ANY)], [jax.ShapeDtypeStruct(kv.shape, kv.dtype)],
        [pltpu.VMEM((bk, _LANES), jnp.float32)] * (2 * n)
        + [pltpu.VMEM((bk, _LANES), kv.dtype)] * 2 + [pltpu.SemaphoreType.DMA((2,))],
        args, in_order=True)

    # ---- dq: fixed q-block, stream k-blocks; into dkv's buffer where q
    # came from the same array
    name = "flash_bwd_dq"
    sched = _schedule(name, causal, Lq, Lk, bq, bk)
    spec = functools.partial(_spec, _place_qk(sched))
    in_specs, aliases = operands(spec), None
    if x_kv is None:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        aliases = {len(sched or ()) + len(args): 0}
        args = args + [dkv]
    dq, = _pallas(
        functools.partial(_optional_inputs(_bwd_dq_kernel, len(sched or ()), 6, present,
                                           unused=int(x_kv is None)), **params),
        name, sched, (B * P, nq, nk), interpret, in_specs,
        [spec((1, bq, _LANES), _lane_q(P, cq))], [jax.ShapeDtypeStruct(x_q.shape, x_q.dtype)],
        [pltpu.VMEM((bq, _LANES), jnp.float32)] * n, args, aliases=aliases)
    return (dq, None) if x_kv is None else (dq, dkv)


#: Both directions jitted and inlined: the layers of a model, which make the
#: same call, then share one trace and one lowering of each kernel (jax caches
#: both by the traced jaxpr) and the program's text still holds every call.
#: Traced and lowered anew a layer, the kernels cost BERT-base's step some
#: 6 s of set-up (v5e: warm `first_call_s` 19.4 against the parent's 13.0 s)
_fwd_lanes_once = jax.jit(_fwd_lanes, static_argnums=(3, 4, 5), inline=True)
_bwd_lanes_once = jax.jit(_bwd_lanes, static_argnums=(3, 4, 5), inline=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_lanes(x_q, x_kv, key_mask, lay, causal, scale):
    return _fwd_lanes_once(x_q, x_kv, key_mask, lay, causal, scale)[0]


def _flash_lanes_fwd(x_q, x_kv, key_mask, lay, causal, scale):
    o, lse = _named(*_fwd_lanes_once(x_q, x_kv, key_mask, lay, causal, scale))
    return o, (x_q, x_kv, key_mask, o, lse)


def _flash_lanes_bwd(lay, causal, scale, res, do):
    x_q, x_kv, key_mask, o, lse = res
    return (*_bwd_lanes_once(x_q, x_kv, key_mask, lay, causal, scale, o, lse,
                             do.astype(o.dtype)), None)


_flash_lanes.defvjp(_flash_lanes_fwd, _flash_lanes_bwd)


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
    outside the band are no grid step, so compute is O(L·window) not O(L²):
    the Mistral-style long-context recipe, native to the tile schedule.

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


def flash_attention_lanes(x_q, x_kv=None, heads: int = 1, mask=None, causal: bool = False,
                          scale: Optional[float] = None):
    """Blockwise attention read from, and written to, the projections' own
    layout: ``x_q (B, L, 3C)``, the fused q, k, v projection, with ``x_kv``
    None (self-attention); or ``x_q (B, Lq, C)`` and ``x_kv (B, Lk, 2C)``,
    the k, v projection (cross-attention). ``C = heads * D``; q, k and v are
    the thirds (halves) of their array, head ``h`` lanes ``h·D`` to
    ``(h+1)·D`` of each. Returns ``(B, Lq, C)``, what the output projection
    reads; the gradient of ``x_q`` (and ``x_kv``) comes back in the same
    layout. What :func:`flash_attention` computes on the head-major
    transposes of these arrays, without the transposes (module docstring).
    Raises where :func:`_lane_layout` finds no lane layout."""
    lay = _lane_layout(x_q, x_kv, heads, mask)
    if lay is None:
        raise ValueError(
            f"no lane layout for x_q {x_q.shape}, x_kv {None if x_kv is None else x_kv.shape}, "
            f"{heads} heads: needs (B, L, 3C) or (B, Lq, C) with (B, Lk, 2C), head size "
            "32, 64 or 128, C whole 128-lane blocks, lengths the tiles divide and a key mask "
            "or none; use flash_attention on head-major arrays")
    D = x_q.shape[-1] // (3 if x_kv is None else 1) // heads
    scale = (D ** -0.5) if scale is None else float(scale)
    B, Lq = x_q.shape[:2]
    Lk = (x_q if x_kv is None else x_kv).shape[1]
    return _flash_lanes(x_q, x_kv, _as_key_mask(mask, B, heads, Lq, Lk), lay, causal, scale)
