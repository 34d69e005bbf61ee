"""DecodeEngine — the prefill/decode split over a paged KV-cache.

Autoregressive generation on a jit-cache runtime has exactly two graphs
worth compiling (PyGraph's capture-once/replay-cheaply argument):

- **prefill**: encode the prompt and precompute the per-layer
  cross-attention K/V — prompt lengths are ragged, so this is a
  :class:`~..compiled.CompiledModel` bucketed over ``(batch, src)``;
- **decode**: one fixed-shape single-token step
  (:func:`~...models.nmt.nmt_paged_step`) that reads/writes cache pages
  in-place (the pool arrays are donated), AOT-lowered ONCE at
  ``warmup()`` — generation length never appears in any shape, so
  ragged generation lengths cannot recompile anything, by construction.

The KV pool's size is not a tunable: ``capacity_report()`` traces the
decode graph at two pool sizes, reads the fixed and per-page peak live
bytes off the PR 12 liveness model (``analysis.hlo.cost.peak_live_bytes``,
donation-aware), and prices the static "sequences that fit in
``MXTPU_HBM_BUDGET``" number; the runtime :class:`~.blocks.BlockPool` is
built from the same numbers, so the static capacity and the actual
admission limit cannot drift apart. ``check_budget()`` re-runs the
MX709-family memory gate over the real (capacity-sized) graphs.

Env knobs: ``MXTPU_DECODE_MAX_BATCH``, ``MXTPU_DECODE_BLOCK_SIZE``,
``MXTPU_DECODE_MAX_TOKENS`` (see docs/env_vars.md).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as onp

from ...base import MXNetError
from ...lockcheck import make_rlock
from ...util import getenv, hbm_budget_bytes
from ...telemetry import compile_log
from ..buckets import BucketTable
from ..compiled import CompiledModel
from .blocks import (BlockPool, blocks_per_sequence, block_bytes,
                     price_capacity)

__all__ = ["DecodeEngine", "PrefillEntry", "DECODE_SITE"]

#: compile-ledger site of the AOT decode step (prefill buckets ride the
#: regular ``serve.compiled`` site)
DECODE_SITE = "serve.decode"


class PrefillEntry:
    """HybridBlock entry the prefill CompiledModel wraps: encoder forward
    plus every decoder layer's cross-attention K/V projection, packed
    into one ``(B, Ls, num_layers * 2 * units)`` tensor (a single output
    keeps the bucket-padding slice trivial)."""

    def __new__(cls, model):
        from ...gluon.block import HybridBlock

        class _Entry(HybridBlock):
            def __init__(self, m, **kw):
                super().__init__(**kw)
                self._m = m      # Block.__setattr__ registers the child

            def hybrid_forward(self, F, src, src_valid_length):
                m = self._m
                B, L = src.shape[0], src.shape[1]
                mask = m._src_mask(F, src_valid_length, B, L)
                mem = m.encoder(m.src_embed(src), mask)
                kvs = [layer.cross_attn.kv_proj(mem)
                       for layer in m.decoder.layers]
                return F.concat(*kvs, dim=2) if len(kvs) > 1 else kvs[0]

        return _Entry(model, prefix="prefill_")


class DecodeEngine:
    """Paged-KV-cache generation engine for one :class:`NMTModel` replica.

    ``prompt_table`` must declare ``batch`` and ``src`` axes; decode-side
    shapes are fixed by ``max_batch`` (concurrent rows), ``block_size``
    (tokens per cache page) and ``max_target_len`` (generation cap =
    pages per sequence × block_size). ``warmup()`` AOT-compiles every
    prefill bucket plus the single decode executable; after it,
    ``telemetry.compile_log.assert_zero_post_warmup()`` is an invariant
    across arbitrarily ragged prompt/generation lengths.
    """

    def __init__(self, model, prompt_table: BucketTable, *,
                 max_batch: Optional[int] = None,
                 block_size: Optional[int] = None,
                 max_target_len: Optional[int] = None,
                 hbm_budget: Optional[int] = None,
                 bos_id: int = 1, eos_id: int = 2):
        import jax

        if not {"batch", "src"} <= set(prompt_table.axes):
            raise MXNetError("DecodeEngine prompt_table needs 'batch' and "
                             f"'src' axes, got {sorted(prompt_table.axes)}")
        self._model = model
        self._table = prompt_table
        self.max_batch = int(max_batch or getenv("MXTPU_DECODE_MAX_BATCH"))
        self.block_size = int(block_size
                              or getenv("MXTPU_DECODE_BLOCK_SIZE"))
        self.max_target_len = int(max_target_len
                                  or getenv("MXTPU_DECODE_MAX_TOKENS"))
        self.bos_id, self.eos_id = int(bos_id), int(eos_id)
        if self.max_target_len > model.decoder._max_length:
            raise MXNetError(
                f"max_target_len {self.max_target_len} exceeds the "
                f"model's position table ({model.decoder._max_length})")
        self._budget = hbm_budget if hbm_budget is not None \
            else hbm_budget_bytes()
        self._lock = make_rlock("DecodeEngine._lock")

        from ...models.nmt import incremental_decode_params
        self._extract_params = lambda: incremental_decode_params(model)
        try:
            params = self._extract_params()
        except Exception:
            # a freshly-initialize()d gluon model defers parameter
            # creation to its first forward — run one tiny full pass so
            # the decoder-side params exist before extraction
            from ... import autograd
            from ...ndarray import array as _force_nd
            lo_s0 = int(prompt_table.axes["src"][0])
            src0 = _force_nd(onp.full((1, lo_s0), self.bos_id), dtype="int32")
            tgt0 = _force_nd(onp.full((1, 1), self.bos_id), dtype="int32")
            with autograd.predict_mode():
                model(src0, tgt0)
            params = self._extract_params()
        self._treedef = jax.tree_util.tree_structure(params)
        self._param_leaves = jax.tree_util.tree_leaves(params)
        self.num_layers = len(params["layers"])
        self.units = int(params["embed"].shape[1])
        self.vocab = int(params["proj_w"].shape[0])
        self.num_heads = model.decoder.layers[0].self_attn._num_heads
        self.max_src = int(prompt_table.axes["src"][1])
        self._dtype = params["embed"].dtype

        # -- prefill: bucketed CompiledModel over (batch, src) -------------
        from ...ndarray import array as _nd_array
        lo_b = prompt_table.axes["batch"][0]
        lo_s = prompt_table.axes["src"][0]
        # NDArray example args: the warm-up call must take the block's
        # eager (ndarray-F) path, not the symbolic compose path
        ex_src = _nd_array(onp.zeros((lo_b, lo_s)), dtype="int32")
        ex_vl = _nd_array(onp.full((lo_b,), float(lo_s)), dtype="float32")
        self.prefill = CompiledModel(
            PrefillEntry(model), prompt_table,
            input_axes=[{0: "batch", 1: "src"}, {0: "batch"}],
            example_args=(ex_src, ex_vl), donate=False)

        # -- decode: one flat fixed-shape step, AOT-compiled at warmup -----
        self._flat_step = self._make_flat_step()
        # the donating jit is the TPU-semantics graph: capacity pricing and
        # the MX709 gate read its donation-aware liveness
        self._jit_step = jax.jit(self._flat_step, donate_argnums=(0, 1))
        self._exe = None

        # -- capacity: priced off the liveness model, pool sized from it ---
        self.capacity = self.capacity_report()
        nb = self.capacity["num_blocks"]
        bps = self.capacity["blocks_per_seq"]
        self.pool = BlockPool(nb, self.block_size, bps,
                              max_sequences=self.capacity["max_sequences"])
        self._warmed = False
        self.steps = 0

        import jax.numpy as jnp
        B, NL, U = self.max_batch, self.num_layers, self.units
        self._pool_k = jnp.zeros((nb, NL, self.block_size, U), self._dtype)
        self._pool_v = jnp.zeros_like(self._pool_k)
        self._cross = jnp.zeros((NL, B, self.max_src, 2 * U), self._dtype)
        self._tables = onp.zeros((B, bps), "int32")
        self._valid = onp.zeros((B,), "float32")

    # -- graph construction ------------------------------------------------

    def _make_flat_step(self):
        import jax
        import jax.numpy as jnp
        from ...models.nmt import nmt_paged_step

        H, bs, max_src, treedef = (self.num_heads, self.block_size,
                                   self.max_src, self._treedef)

        def flat_step(pool_k, pool_v, tables, positions, tokens, cross_kv,
                      valid, *param_leaves):
            params = jax.tree_util.tree_unflatten(treedef,
                                                  list(param_leaves))
            mem_mask = jnp.arange(max_src)[None, :] < valid[:, None]
            return nmt_paged_step(params, H, bs, pool_k, pool_v, tables,
                                  positions, tokens, cross_kv, mem_mask)

        return flat_step

    def _step_avals(self, num_blocks: Optional[int] = None):
        import jax
        import jax.numpy as jnp
        nb = num_blocks if num_blocks is not None \
            else self.capacity["num_blocks"]
        B, NL, U = self.max_batch, self.num_layers, self.units
        bps = blocks_per_sequence(self.max_target_len, self.block_size)
        sds = lambda s, d: jax.ShapeDtypeStruct(s, jnp.dtype(d))
        pool = sds((nb, NL, self.block_size, U), self._dtype)
        return (pool, pool, sds((B, bps), "int32"), sds((B,), "int32"),
                sds((B,), "int32"), sds((NL, B, self.max_src, 2 * U),
                                        self._dtype),
                sds((B,), "float32"),
                *[sds(tuple(l.shape), l.dtype) for l in self._param_leaves])

    def _traced_step(self, num_blocks: int):
        """One TracedGraph of the decode step at ``num_blocks`` pool pages
        — the liveness-model view capacity pricing reads."""
        from ...analysis.hlo.trace import trace_entry
        res = trace_entry(self._jit_step,
                          sample_args=[tuple(self._step_avals(num_blocks))])
        g = res.graphs[0]
        g.entry = "DecodeEngine.step"
        g.expected = True
        n_state, n_in = 2, 5
        g.roles = (["state"] * n_state + ["input"] * n_in
                   + ["param"] * len(self._param_leaves))
        return g

    # -- capacity ----------------------------------------------------------

    def capacity_report(self) -> Dict[str, int]:
        """Price the static capacity: trace the decode graph at two pool
        sizes, read fixed vs per-page peak live bytes off the liveness
        scan, divide into ``MXTPU_HBM_BUDGET``. Deterministic — the
        serve_bench gate asserts this equals the runtime pool's
        admission limit."""
        from ...analysis.hlo.cost import peak_live_bytes
        bps = blocks_per_sequence(self.max_target_len, self.block_size)
        if self._budget is None:
            rep = price_capacity(hbm_budget=None, fixed_bytes=0,
                                 per_block_bytes=1,
                                 max_target_len=self.max_target_len,
                                 block_size=self.block_size,
                                 max_batch=self.max_batch)
        else:
            p2 = peak_live_bytes(self._traced_step(2))
            p3 = peak_live_bytes(self._traced_step(3))
            per_block = max(1, p3 - p2)
            analytic = block_bytes(self.num_layers, self.units,
                                   self.block_size,
                                   onp.dtype(self._dtype).itemsize)
            per_block = max(per_block, analytic)
            fixed = max(0, p2 - 2 * per_block)
            rep = price_capacity(hbm_budget=self._budget, fixed_bytes=fixed,
                                 per_block_bytes=per_block,
                                 max_target_len=self.max_target_len,
                                 block_size=self.block_size,
                                 max_batch=self.max_batch)
            rep["fixed_bytes"] = fixed
            rep["per_block_bytes"] = per_block
            rep["hbm_budget"] = int(self._budget)
        if rep["max_sequences"] < 1:
            raise MXNetError(
                "MXTPU_HBM_BUDGET too small for even one decode sequence: "
                f"{rep} — shrink the model, block_size, or max_target_len")
        return rep

    def trace(self, max_graphs: int = 8):
        """TraceResult over BOTH graph families (every prefill bucket plus
        the capacity-sized decode step) — what ``analysis.hlo.verify``
        dispatches to, giving the MX706/MX709 passes decode coverage."""
        from ...analysis.hlo.trace import trace_entry
        res = trace_entry(self.prefill, max_graphs=max_graphs)
        res.graphs.append(self._traced_step(self.capacity["num_blocks"]))
        return res

    def check_budget(self):
        """MX709-family gate over the real (capacity-sized) graphs."""
        from ...analysis import hlo as _hlo
        return _hlo.verify_trace(self.trace(),
                                 hbm_budget_bytes=self._budget)

    # -- lifecycle ---------------------------------------------------------

    def warmup(self) -> dict:
        """AOT-compile every prefill bucket and the decode executable.
        After this, zero post-warmup compiles is an invariant."""
        t0 = time.monotonic()
        pre = self.prefill.warmup()
        # holding the engine lock across the AOT compile is the warmup
        # CONTRACT (same as CompiledModel.warmup): run_step callers block
        # until the executable exists instead of racing a half-installed one
        with self._lock:  # mxlint: disable=MX803
            if self._exe is None:
                import jax
                t1 = time.monotonic()
                # donation is a TPU-backend capability; CPU (tests) runs
                # the same graph without it — same contract as
                # CompiledModel's donate="auto"
                jit = self._jit_step if jax.default_backend() != "cpu" \
                    else jax.jit(self._flat_step)
                with compile_log.at(DECODE_SITE):
                    self._exe = jit.lower(*self._step_avals()).compile()
                compile_log.note(
                    DECODE_SITE,
                    (("pool", tuple(self._pool_k.shape)),
                     ("batch", self.max_batch)),
                    wall_ms=(time.monotonic() - t1) * 1e3, warmup=True)
            compile_log.mark_warmed(DECODE_SITE)
            self._warmed = True
        return {"prefill": pre, "decode_compiled": 1,
                "capacity": dict(self.capacity),
                "seconds": time.monotonic() - t0}

    def refresh_params(self) -> None:
        """Re-extract decoder params after a weight sync (same shapes —
        the AOT executable is reused, no recompile)."""
        import jax
        with self._lock:
            self._param_leaves = jax.tree_util.tree_leaves(
                self._extract_params())
        self.prefill.refresh_params()

    # -- serving operations (called by DecodeBatcher at token boundaries) --

    def prefill_request(self, src_tokens, valid_len: Optional[int] = None
                        ) -> Tuple[onp.ndarray, int]:
        """Run the bucketed prefill for ONE prompt; returns the packed
        cross-KV row ``(NL, max_src, 2U)`` (padded to max_src) and the
        prompt's valid length."""
        src = onp.asarray(src_tokens, "int32").reshape(1, -1)
        lp = int(valid_len if valid_len is not None else src.shape[1])
        out = self.prefill.predict(src, onp.asarray([float(lp)], "float32"))
        packed = onp.asarray(getattr(out, "_data", out))[0]   # (Ls, NL*2U)
        NL, U = self.num_layers, self.units
        row = onp.zeros((NL, self.max_src, 2 * U), packed.dtype)
        ls = min(packed.shape[0], self.max_src)
        row[:, :ls] = packed[:ls].reshape(ls, NL, 2 * U).transpose(1, 0, 2)
        return row, lp

    def bind_row(self, row: int, cross_row: onp.ndarray,
                 valid_len: int) -> None:
        """Install an admitted sequence's cross-KV into batch row ``row``
        (an eager in-place-style update, not a recompile)."""
        import jax.numpy as jnp
        with self._lock:
            self._cross = self._cross.at[:, row].set(
                jnp.asarray(cross_row, self._dtype))
            self._valid[row] = float(valid_len)

    def clear_row(self, row: int) -> None:
        with self._lock:
            self._tables[row] = 0
            self._valid[row] = 0.0

    def set_row_table(self, row: int, table: Sequence[int]) -> None:
        with self._lock:
            self._tables[row] = 0
            self._tables[row, :len(table)] = onp.asarray(table, "int32")

    def run_step(self, positions: onp.ndarray, tokens: onp.ndarray
                 ) -> onp.ndarray:
        """One fixed-shape decode step over the whole batch; returns
        logits ``(max_batch, vocab)``. Rows not bound to a sequence must
        point at the scratch page (table row 0) — their logits are
        garbage and ignored by the batcher."""
        import jax.numpy as jnp
        # the un-warmed first step pays the compile under the lock by the
        # same warmup contract — steady-state steps never compile
        with self._lock:  # mxlint: disable=MX803
            if self._exe is None:
                self.warmup()
            logits, self._pool_k, self._pool_v = self._exe(
                self._pool_k, self._pool_v,
                jnp.asarray(self._tables), jnp.asarray(positions, "int32"),
                jnp.asarray(tokens, "int32"), self._cross,
                jnp.asarray(self._valid), *self._param_leaves)
            self.steps += 1
        return onp.asarray(logits)

    def reset_cache(self) -> None:
        """Drop all cache contents (e.g. after a chaos replica death) —
        pages are zeroed host-side state only; no recompile."""
        import jax.numpy as jnp
        with self._lock:
            self._pool_k = jnp.zeros_like(self._pool_k)
            self._pool_v = jnp.zeros_like(self._pool_v)
            self._tables[:] = 0
            self._valid[:] = 0.0

    def stats(self) -> dict:
        return {"prefill": dict(self.prefill.stats),
                "decode_steps": self.steps,
                "capacity": dict(self.capacity),
                "pool": self.pool.snapshot(),
                "warmed": self._warmed}
