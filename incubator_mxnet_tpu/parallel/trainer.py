"""ShardedTrainer — one compiled SPMD training step over the mesh.

Reference counterpart: the whole inner loop of SURVEY §3.2 fused into one XLA
executable. What the reference runs as four separate engine phases —
``CachedOp::Forward``, ``Imperative::Backward``, kvstore push/pull
(``KVStoreNCCL`` all-reduce), and per-parameter optimizer ops
(``src/operator/optimizer_op.cc``) — is here a single pjit-compiled pure
function ``(params, opt_state, batch) -> (loss, params', opt_state')`` with
*explicit* ``PartitionSpec`` in/out resources: every parameter, optimizer
shard and batch argument carries its :class:`~jax.sharding.NamedSharding`
into ``jax.jit`` (the pjit formulation), so gradient exchange lowers to XLA
all-reduce over the mesh axes and — under ZeRO-1 — the optimizer update
executes cross-replica sharded (reduce-scatter into the ``dp``-partitioned
update, all-gather of the new weights; Xu et al. 2020, arXiv 2004.13336).
Parameter donation gives the in-place-update memory behavior of
``FMutateInputs``.

This compiled step is THE default execution path whenever a mesh is
configured. The reference's per-parameter kvstore push/pull loop survives
only as a *named fallback* for the async parameter-server scenario: setting
``MXTPU_KVSTORE_FALLBACK=1`` routes :meth:`ShardedTrainer.step` through a
host-side per-parameter exchange over a kvstore backend (``dist_async``
keeps its reconnect/exactly-once-resend semantics untouched) — every other
configuration runs ONE compiled call with zero per-parameter host work.

Whole-step capture finishes the job: the guard's finite verdict and the
LR-schedule position are computed INSIDE that one donated graph (loss/grad-norm/ok come back as
pinned replicated outputs; the rollback decision stays on host), so a
guarded, LR-scheduled step is still exactly one jitted graph + one host
sync per step. Builds consult the on-disk autotune cache
(``MXTPU_AUTOTUNE_DIR`` — winners banked by ``benchmark/autotune.py``
per (model, mesh_shape, chip)) and overlay the winning env knobs for
exactly the first-trace scope.

Usage::

    mesh = parallel.make_mesh(dp=2, tp=4)
    trainer = parallel.ShardedTrainer(net, loss_fn, 'adamw',
                                      {'learning_rate': 1e-4}, mesh=mesh,
                                      rules=bert_sharding_rules())
    loss = trainer.step(data, label)       # compiled after first call
"""
from __future__ import annotations

import os
import re
import weakref
from contextlib import ExitStack as _ExitStack
from contextlib import nullcontext as _nullcontext
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as onp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..base import MXNetError
from ..context import current_context
from ..ndarray import NDArray
from .. import autograd
from .. import profiler as _prof
from .. import optimizer as opt_mod
from .. import random as random_mod
from ..gluon import _trace
from ..gluon.block import _TRACING
from .mesh import default_mesh
from .sharding import ShardingRules, data_sharding

P = PartitionSpec

__all__ = ["ShardedTrainer"]


def _natural_key(name: str):
    """Sort key comparing digit runs as numbers: ``dense9_`` < ``dense10_``."""
    return [int(p) if p.isdigit() else p for p in re.split(r"(\d+)", name)]


class _StepProgram:
    """How ``compile_log.program_text("trainer.step")`` reads the compiled
    step back: the jitted step lowered again from its arguments' shapes
    and shardings, under the step's mesh and tuned overlay, so that jax's
    caches hand back the executable that ran. It holds the trainer weakly
    (the jitted step holds the block) until :meth:`pin` captures what the
    lowering needs; ``ShardedTrainer.step`` pins it when a profiler trace
    records the step, so a trace can be read after the trainer is gone."""

    __slots__ = ("_trainer", "_batch", "_pinned")

    def __init__(self, trainer, batch_vals):
        self._trainer = weakref.ref(trainer)
        self._batch = tuple(_spec(v) for v in batch_vals)
        self._pinned = None

    def _capture(self):
        tr = self._trainer()
        if tr is None:
            return None
        state = jax.tree.map(_spec, (tr._param_vals, tr._opt_states, tr._base_key,
                                     tr._lr_dev, tr._t_dev))
        return tr._step_fn, state + self._batch, tr._mesh, tr._tuned

    def pin(self) -> None:
        if self._pinned is None:
            self._pinned = self._capture()

    def __call__(self) -> Optional[str]:
        parts = self._pinned or self._capture()
        if parts is None:
            return None
        step_fn, args, mesh, tuned = parts
        from .. import autotune as _autotune
        from .mesh import active_mesh
        with active_mesh(mesh), (_autotune.applied(tuned) if tuned else _nullcontext()):
            lowered = step_fn.lower(*args)
        return lowered.compile().as_text()


def _spec(a):
    """``a``'s shape, dtype, sharding and weak type, without its data (a
    typed key has no weak type)."""
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding,
                                weak_type=getattr(a, "weak_type", False))


class ShardedTrainer:
    """Drives a HybridBlock's training SPMD over a named mesh.

    Unlike :class:`~incubator_mxnet_tpu.gluon.trainer.Trainer` (which mirrors
    the reference's kvstore push/pull step), this owns the parameters as a
    sharded pytree and updates them functionally each step — the TPU-idiomatic
    formulation. ``sync_to_block()`` writes the current values back into the
    gluon Parameters (for save_parameters / evaluation on one chip).
    """

    def __init__(self, block, loss_fn: Callable, optimizer,
                 optimizer_params: Optional[dict] = None,
                 mesh: Optional[Mesh] = None,
                 rules: Optional[ShardingRules] = None,
                 n_labels: int = 1, seq_axis: Optional[int] = None,
                 donate: bool = True, zero1: Optional[bool] = None,
                 kvstore=None, guard=None, watchdog=None,
                 autotune_key: Optional[str] = None,
                 numerics=None):
        self._block = block
        self._loss_fn = loss_fn
        self._optimizer = opt_mod.create(
            optimizer, **(optimizer_params or {}))
        self._mesh = mesh if mesh is not None else default_mesh()
        self._rules = rules if rules is not None else ShardingRules()
        self._n_labels = n_labels
        self._seq_axis = seq_axis
        self._donate = donate
        #: ZeRO-1 / cross-replica weight-update sharding (Xu et al. 2020,
        #: arxiv 2004.13336): optimizer states (moments + fp32 masters)
        #: additionally partition over the ``dp`` axis, so XLA
        #: reduce-scatters gradients into the sharded update and
        #: all-gathers the new weights — per-chip optimizer memory drops by
        #: the dp degree while the numerics are unchanged. Default (None):
        #: on whenever the mesh has a real ``dp`` axis — the compiled
        #: cross-replica-sharded weight update IS the default path.
        self._zero1 = (self._mesh.shape.get("dp", 1) > 1
                       if zero1 is None else bool(zero1))
        #: named fallback backend for the async-PS scenario: the
        #: per-parameter host push/pull loop, active only under
        #: MXTPU_KVSTORE_FALLBACK=1 (``kvstore`` names/carries the store —
        #: 'dist_async' keeps its retry/exactly-once client semantics).
        self._kvstore_req = kvstore
        self._kv = None              # resolved lazily on first fallback step
        self._grad_fn = None         # compiled fwd+bwd (fallback path)
        self._step_ndims = None      # batch ranks the built step was pinned to
        self._step_n_data = None     # data-arg count of the built step
        #: staged-recompile cutover flag (:meth:`retune`): the ledger
        #: site the NEXT dispatch's compile is banked under — never
        #: ``trainer.step``, so that site's zero-post-warmup contract
        #: survives a director-driven rebuild
        self._retune_site: Optional[str] = None
        self.last_path: Optional[str] = None
        #: whole-step capture: the guard's finite verdict and the
        #: LR-schedule position are computed INSIDE the one donated pjit
        #: step — loss/grad-norm/ok come back as pinned replicated
        #: outputs, so a guarded, scheduled step runs exactly ONE jitted
        #: graph with one host sync
        self._lr_fold = False        # schedule folded into the step graph
        #: jitted-executable invocations the last step() made (the
        #: compiled step: 1; the kvstore fallback with a guard: 2, its
        #: fwd+bwd and the separate finite check)
        self.last_step_graphs = 0
        #: autotune-cache key (benchmark/autotune.py winners); default =
        #: the block's class name lowercased — drivers pass the family
        #: name ("bert") so the banked winner and the build agree
        self._autotune_key = (autotune_key
                              or type(block).__name__.lower())
        self._tuned = None           # consult result, resolved at build
        self.autotune_entry: Optional[Dict[str, Any]] = None
        #: in-graph numerics telemetry (telemetry.numerics): an explicit
        #: NumericsConfig, or None = resolve MXTPU_NUMERICS at build
        #: time. When enabled the step graph returns per-site stat
        #: vectors (param:/grad:/act: sites) as extra pinned replicated
        #: outputs of the SAME jitted graph — still exactly one
        #: executable per step — which the host syncs (folded into the
        #: guard's existing device read) every cfg.every steps.
        self._numerics_req = numerics
        self._numerics_cfg = None    # resolved at build (env or explicit)
        self._params = None          # sorted List[Parameter]
        self._param_vals = None      # tuple of sharded jax arrays
        self._opt_states = None      # tuple of per-param state tuples
        self._param_shardings = None  # per-param NamedSharding (post-init)
        self._state_shardings = None  # per-param tuple of NamedShardings
        self._step_fn = None
        self._program = None          # _StepProgram of the step that runs
        self._info: Dict[str, Any] = {}
        self._t = 0
        self._t_dev = None           # device-resident step counter
        self._base_key = None        # device-resident RNG base key
        self._lr_val = None          # python lr the cached device lr mirrors
        self._lr_dev = None
        #: mx.fault wiring (all optional): a StepGuard syncs loss/grad-norm
        #: each step and applies its policy (warn / skip_and_rollback /
        #: halt); a Watchdog flags steps that blow the wall-clock deadline.
        self._guard = guard
        self._watchdog = watchdog
        self._snapshot = None        # (t, param copies, opt-state copies)
        self.last_grad_norm: Optional[float] = None
        self.last_loss: Optional[float] = None
        #: batch (shape, dtype) signatures the compiled step has seen —
        #: a NEW signature after the first is a silent re-trace inside
        #: one jit entry, recorded in the telemetry compile ledger
        self._step_sigs: set = set()
        # registry handles resolved once, not per step (registry lock)
        from ..telemetry import metrics as _tmetrics
        self._m_steps = _tmetrics.counter("mxtpu_train_steps_total",
                                          "Training steps attempted")
        self._m_step_ms = _tmetrics.histogram(
            "mxtpu_train_step_ms", "Training step wall time (ms)")
        self._m_gnorm = _tmetrics.gauge(
            "mxtpu_train_grad_norm",
            "Global gradient norm (guarded steps)")
        self._m_rollbacks = _tmetrics.counter(
            "mxtpu_train_rollbacks_total", "Guarded steps rolled back")
        # Work in the mesh's device context: wrapping step outputs/batches in
        # the *default* (cpu) Context would force sync device→host round
        # trips every step.
        from ..context import context_for_device
        self._ctx = context_for_device(self._mesh.devices.flat[0])
        #: the step's replicated placement (scalars, RNG key, loss)
        self._repl = NamedSharding(self._mesh, P())

    # ------------------------------------------------------------------
    @property
    def mesh(self) -> Mesh:
        return self._mesh

    @property
    def num_update(self) -> int:
        return self._t

    def _init_state(self, data_args: Sequence[NDArray], warm_ctx) -> None:
        """Warm up the block eagerly (finishes deferred init) in the context
        the parameters live on, then shard every parameter and optimizer
        state onto the mesh by rule."""
        blk = self._block
        with autograd.pause(train_mode=True):
            _TRACING.flag = True
            try:
                blk.forward(*data_args)
            finally:
                _TRACING.flag = False
        items = sorted(blk.collect_params().items())
        self._params = [p for _, p in items]
        opt = self._optimizer
        opt.idx2name = {i: name for i, (name, _) in enumerate(items)}
        # Optimizer state arrays share the weight's layout when same-shaped
        # (momentum / adam moments / fp32 master weights); anything else is
        # replicated. Weights are copied before placement: device_put of an
        # already-matching array shares the buffer, and step-time donation
        # would otherwise delete the gluon Parameter's live data.
        vals, states = [], []
        self._param_shardings, self._state_shardings = [], []
        for i, (name, p) in enumerate(items):
            v = p.data(warm_ctx)._data
            sh = self._rules.sharding_for(name, self._mesh, tuple(v.shape))
            vals.append(jax.device_put(jnp.copy(v), sh))
            self._param_shardings.append(sh)
            placed, st_shs = [], []
            for s in opt.create_state_multi_precision(i, p.data(warm_ctx)):
                st_sh = self._state_sharding(name, tuple(v.shape),
                                             tuple(s.shape))
                placed.append(jax.device_put(s, st_sh))
                st_shs.append(st_sh)
            states.append(tuple(placed))
            self._state_shardings.append(tuple(st_shs))
        self._param_vals = tuple(vals)
        self._opt_states = tuple(states)
        # attribute this trainer's resident state on the device-memory
        # ledger (weak provider: a collected trainer drops off silently)
        from ..telemetry import memory as _memory
        self._mem_unregister = _memory.register_site(
            "trainer.step", self._resident_bytes)

    def _resident_bytes(self) -> int:
        """Device bytes this trainer pins between steps (parameters +
        optimizer states) — the ``trainer.step`` site of the
        ``telemetry.memory`` ledger."""
        total = 0
        for leaf in jax.tree_util.tree_leaves(
                (self._param_vals or (), self._opt_states or ())):
            total += int(getattr(leaf, "nbytes", 0) or 0)
        return total

    def _state_sharding(self, name, wshape, sshape) -> NamedSharding:
        """ONE policy for optimizer-state placement (used by init and
        restore): weight-shaped states follow the weight's rule spec — plus
        the zero1 dp-partition when enabled — everything else replicates."""
        spec = (self._rules.spec_for(name, wshape, self._mesh)
                if sshape == wshape else P())
        if self._zero1 and sshape == wshape:
            spec = self._zero1_spec(spec, sshape)
        return NamedSharding(self._mesh, spec)

    def _zero1_spec(self, spec, shape):
        """Extend a weight's PartitionSpec with a ``dp`` factor on the first
        axis that has room — the optimizer-state layout of ZeRO stage 1."""
        dp = self._mesh.shape.get("dp", 1)
        if dp == 1:
            return spec
        entries = list(tuple(spec)) + [None] * (len(shape) - len(tuple(spec)))
        for e in entries:
            used = e if isinstance(e, tuple) else ((e,) if e else ())
            if "dp" in used:
                return P(*entries)      # already dp-partitioned by rule
        for ax in range(len(shape)):
            e = entries[ax]
            used = tuple(e) if isinstance(e, tuple) else ((e,) if e else ())
            cur = 1
            for a in used:
                cur *= self._mesh.shape[a]
            if shape[ax] % (cur * dp) == 0:
                entries[ax] = used + ("dp",)
                return P(*entries)
        return spec                     # nothing divisible: stay replicated

    # ------------------------------------------------------------------
    def _per_param_hparams(self):
        """(lr_mults, wds, mp) — the per-parameter hyperparameter vectors
        shared by the compiled pjit step and the kvstore-fallback update,
        so the two paths can never apply different schedules."""
        opt, params = self._optimizer, self._params
        lr_mults = [opt._get_lr(i) / max(opt.learning_rate, 1e-30)
                    for i in range(len(params))]
        wds = [opt._get_wd(i) for i in range(len(params))]
        # Mixed precision: state[0] is the fp32 master weight (reference:
        # Optimizer.update_multi_precision master branch).
        mp = [bool(opt.multi_precision
                   and self._param_vals[i].dtype in (jnp.float16, jnp.bfloat16)
                   and self._opt_states[i]
                   and self._opt_states[i][0].dtype == jnp.float32
                   and self._opt_states[i][0].shape == self._param_vals[i].shape)
              for i in range(len(params))]
        return lr_mults, wds, mp

    def step_shardings(self, batch_ndims: Sequence[int]):
        """The explicit pjit resource contract of the compiled step:
        ``(in_shardings, out_shardings)`` NamedSharding pytrees matching
        ``step(param_vals, opt_states, key, lr, t, *batch)`` →
        ``(loss, gnorm, new_vals, new_states, effects, t+1, ok[, stats])``
        (``ok`` is the in-graph guard verdict; ``stats`` — the per-site
        numerics pytree — only when numerics telemetry is enabled for
        this build). Scalars and the RNG key
        replicate; parameters/optimizer shards carry their rule (+ zero1
        ``dp``) layouts in AND out, so the optimizer update is compiled
        cross-replica sharded and the next call sees identical
        placements (no silent re-trace); batch arguments take the
        batch-over-``dp`` / seq-over-``sp`` data sharding."""
        repl = self._repl
        batch_sh = tuple(
            data_sharding(self._mesh, batch_axis=0, seq_axis=self._seq_axis,
                          ndim=nd) for nd in batch_ndims)
        params_sh = tuple(self._param_shardings)
        states_sh = tuple(tuple(s) for s in self._state_shardings)
        in_shardings = (params_sh, states_sh, repl, repl, repl) + batch_sh
        # effects (aux state: batchnorm running stats) replicate — a repl
        # prefix broadcasts over that subtree whatever its arity
        # the last slot is the guard verdict: a pinned replicated scalar,
        # read back in the SAME host sync as loss/grad-norm
        out_shardings = (repl, repl, params_sh, states_sh, repl, repl, repl)
        if self._numerics_cfg is not None and self._numerics_cfg.enabled:
            # numerics stats: a dict subtree of small replicated vectors
            # — one repl prefix broadcasts over it whatever its arity
            out_shardings = out_shardings + (repl,)
        return in_shardings, out_shardings

    def _make_loss_grads(self, n_data: int) -> Callable:
        """``(param_vals, key, t, *batch) -> (loss, gnorm, grads, effects,
        taps)`` — the fwd+bwd half of the step, shared verbatim by the
        compiled pjit step and the kvstore-fallback path so their
        gradients are the same function of the same inputs. ``taps`` is
        the tuple of in-graph activation stats collected from
        ``numerics.tap()`` sites during the forward trace (site names
        recorded in ``info['tap_sites']``); empty when numerics is off
        — tap stat tracers belong to the inner differentiated trace, so
        like the aux effects they MUST ride out through ``has_aux``."""
        blk, params = self._block, self._params
        loss_fn, ctx, info = self._loss_fn, self._ctx, self._info
        num_cfg = self._numerics_cfg
        num_on = num_cfg is not None and num_cfg.enabled

        def loss_grads(param_vals, key, t, *batch_vals):
            # Per-step randomness is derived ON DEVICE from one resident base
            # key — the host passes the same array every step, so there is no
            # eager key-split or host→device key transfer in the loop (about
            # 7 ms/step in BASELINE.md's 2026-07-30 trace).
            key = jax.random.fold_in(key, t)

            def loss_of(pvals):
                from ..telemetry import numerics as _numerics
                proxies = {id(p): NDArray(v, ctx=ctx)
                           for p, v in zip(params, pvals)}
                ins = [NDArray(v, ctx=ctx) for v in batch_vals]
                col_ctx = (_numerics.collecting(num_cfg) if num_on
                           else _nullcontext())
                _TRACING.flag = True
                try:
                    with autograd.pause(train_mode=True), \
                            random_mod.trace_rng(key), \
                            col_ctx as col, \
                            _trace.TraceScope(proxies) as scope:
                        out = blk.forward(*ins[:n_data])
                        loss = loss_fn(out, *ins[n_data:])
                finally:
                    _TRACING.flag = False
                lv = loss._data if isinstance(loss, NDArray) else loss
                info["effects"] = list(scope.effect_keys)
                info["tap_sites"] = list(col.names) if num_on else []
                taps = tuple(col.values) if num_on else ()
                return jnp.mean(lv), (tuple(scope.effect_values), taps)

            (loss, (effects, taps)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(param_vals)
            # Global grad norm, fused into the step (fp32 accumulation so a
            # bf16 overflow can't hide): one scalar out, consumed by the
            # fault.StepGuard finite/limit check and exposed as
            # trainer.last_grad_norm.
            gnorm = jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32))) for g in grads))
            return loss, gnorm, grads, effects, taps

        return loss_grads

    def _resolve_numerics(self):
        """Resolve the numerics config ONCE per trainer (explicit ctor
        config wins; else the env) — build-time, like the autotune
        consult, so flipping MXTPU_NUMERICS mid-run cannot silently
        re-trace a compiled step."""
        if self._numerics_cfg is None:
            from ..telemetry import numerics as _numerics
            self._numerics_cfg = (self._numerics_req
                                  if self._numerics_req is not None
                                  else _numerics.config())
        return self._numerics_cfg

    def _build_step(self, n_data: int, batch_ndims: Sequence[int]) -> Callable:
        opt = self._optimizer
        param_shardings = self._param_shardings
        state_shardings = self._state_shardings
        lr_mults, wds, mp = self._per_param_hparams()
        num_cfg = self._resolve_numerics()
        num_on = num_cfg.enabled
        param_names = [name for name, _ in
                       sorted(self._block.collect_params().items())]
        # local alias, NOT self: the jitted step closure must never
        # capture the trainer — that cycle would keep dead trainers
        # (and their weak memory-ledger providers) alive past refcount
        step_info = self._info
        loss_grads = self._make_loss_grads(n_data)
        # LR-schedule position folded into the graph (whole-step capture):
        # with a traceable scheduler the per-step LR is a function of the
        # device-resident update counter — no host schedule eval, no
        # per-step host→device LR transfer. ``lr`` stays an input scalar
        # carrying the base LR (so an explicit set_learning_rate still
        # rescales without a re-trace); the schedule position is t-1,
        # this step's optimizer.num_update.
        sched = getattr(opt, "lr_scheduler", None)
        fold_lr = sched is not None and hasattr(sched, "jax_lr")
        self._lr_fold = fold_lr
        base_lr = (float(getattr(sched, "base_lr", 0.0) or 0.0)
                   if fold_lr else None)

        def step(param_vals, opt_states, key, lr, t, *batch_vals):
            if fold_lr:
                # scale by lr/baked_base: the lr input tracks the
                # scheduler's live base_lr (_refresh_scalars), so a
                # mid-run base override rescales the folded schedule
                # without a re-trace; at the baked base the factor is 1
                scale = (lr / jnp.float32(base_lr)) if base_lr else 1.0
                lr = sched.jax_lr(t - 1) * scale
            loss, gnorm, grads, effects, taps = loss_grads(
                param_vals, key, t, *batch_vals)
            stats = None
            if num_on:
                # per-site tensor stats, computed IN-GRAPH (a handful of
                # fused reductions) and returned as extra pinned
                # replicated outputs of this same executable — never a
                # host callback (the MX603/MX701 anti-pattern)
                from ..telemetry import numerics as _numerics
                stats = {}
                for name, w, g in zip(param_names, param_vals, grads):
                    s = f"param:{name}"
                    if num_cfg.wants(s):
                        stats[s] = _numerics.graph_stats(w, num_cfg)
                    s = f"grad:{name}"
                    if num_cfg.wants(s):
                        stats[s] = _numerics.graph_stats(g, num_cfg)
                for site, val in zip(step_info.get("tap_sites", ()),
                                     taps):
                    stats[site] = val
            constrain = jax.lax.with_sharding_constraint
            new_vals, new_states = [], []
            # one scope round the update: the device's operations carry it in
            # their op_name, so a trace reads the update's time by name
            with jax.named_scope("optimizer_update"):
                for i, (w, g, s) in enumerate(zip(param_vals, grads, opt_states)):
                    if mp[i]:
                        nm, ns = opt.step(s[0], g.astype(jnp.float32), tuple(s[1:]),
                                          lr * lr_mults[i], wds[i], t)
                        nv = nm.astype(w.dtype)
                        nst = (nm,) + tuple(ns)
                    else:
                        nw, ns = opt.step(w, g.astype(w.dtype), s,
                                          lr * lr_mults[i], wds[i], t)
                        nv = nw.astype(w.dtype)
                        nst = tuple(ns)
                    # Pin layouts so step outputs keep the step-input shardings:
                    # under zero1 the update math runs dp-sharded (XLA
                    # reduce-scatters the grads into it) and ONLY the new weight
                    # is gathered back to the rule layout — and the next call
                    # sees identical input shardings (no silent recompile).
                    nv = constrain(nv, param_shardings[i])
                    nst = tuple(constrain(a, sh)
                                for a, sh in zip(nst, state_shardings[i]))
                    new_vals.append(nv)
                    new_states.append(nst)
            # the guard's finite check, captured in-graph: one fused
            # reduction instead of a separate jitted call — the rollback
            # DECISION stays on host (_apply_guard)
            ok = jnp.logical_and(jnp.isfinite(loss).all(),
                                 jnp.isfinite(gnorm))
            out = (loss, gnorm, tuple(new_vals), tuple(new_states),
                   effects, t + 1, ok)
            if num_on:
                out = out + (stats,)
            return out

        # The explicit pjit contract: named in/out resources + donation.
        # With out_shardings pinned, XLA's SPMD partitioner OWNS the
        # gradient exchange (all-reduce over dp — reduce-scatter +
        # all-gather under zero1) and the donated param/state buffers are
        # updated in place: zero per-parameter host work on the hot path.
        in_shardings, out_shardings = self.step_shardings(batch_ndims)
        donate = (0, 1, 4) if self._donate else ()
        return jax.jit(step, in_shardings=in_shardings,
                       out_shardings=out_shardings, donate_argnums=donate)

    # ------------------------------------------------------------------
    # named fallback: the per-parameter kvstore push/pull loop (async-PS)
    # ------------------------------------------------------------------
    @staticmethod
    def kv_fallback_active() -> bool:
        """True when MXTPU_KVSTORE_FALLBACK=1 routes the step through the
        host-side per-parameter kvstore exchange (the async parameter-
        server scenario). Explicit opt-in: every other configuration runs
        the compiled pjit step. Read straight off the environment — this
        sits on the hot step path, where an import + catalog lookup per
        step is measurable dispatch tax (profiler-gated at >=95%
        instrumented); the catalog entry lives in util.ENV_VARS."""
        return os.environ.get("MXTPU_KVSTORE_FALLBACK", "0") == "1"

    def _resolve_kvstore(self):
        if self._kv is None:
            if self._kvstore_req is None or isinstance(self._kvstore_req, str):
                from .. import kvstore as kv_mod
                self._kv = kv_mod.create(self._kvstore_req or "device")
            else:
                self._kv = self._kvstore_req    # explicit store object
            for i, v in enumerate(self._param_vals):
                self._kv.init(i, NDArray(jax.device_get(v)))
        return self._kv

    def _kv_step(self, vals, n_data: int):
        """One fallback step: compiled fwd+bwd, then a PER-PARAMETER
        Python push/pull loop through the kvstore (host round trip per
        key — exactly the dispatch tax the pjit path removes), then the
        eager optimizer update. The kvstore client's semantics ride along
        untouched: a ``dist_async`` store keeps its reconnect, bounded
        retry and versioned exactly-once resend behavior per key."""
        if self._grad_fn is None:
            self._resolve_numerics()
            self._grad_fn = jax.jit(self._make_loss_grads(n_data))
        kv = self._resolve_kvstore()
        # taps are discarded on this path: numerics decimation/recording
        # belongs to the compiled pjit step (the fallback is the legacy
        # per-parameter host loop — it was never capture-clean)
        loss, gnorm, grads, effects, _taps = self._grad_fn(
            self._param_vals, self._base_key, self._t_dev, *vals)
        lr_mults, wds, mp = self._per_param_hparams()
        opt = self._optimizer
        # the whole update runs host-side: every operand comes off the
        # mesh (the per-parameter device→host sync IS this path's cost).
        # The LR comes straight off the host schedule — the device mirror
        # may hold only the base LR when a pjit build folded the schedule
        t = jnp.asarray(jax.device_get(self._t_dev))
        lr = jnp.asarray(float(self._optimizer.learning_rate), jnp.float32)
        new_vals, new_states = [], []
        for i, (wm, g, sm) in enumerate(zip(self._param_vals, grads,
                                            self._opt_states)):
            # the reference Trainer.step shape: push grad i, pull the
            # merged value back — one host round trip per parameter
            merged = kv.pushpull(i, NDArray(jax.device_get(g)))
            gm = jnp.asarray(merged._data)
            w = jnp.asarray(jax.device_get(wm))
            s = tuple(jnp.asarray(jax.device_get(a)) for a in sm)
            if mp[i]:
                nm, ns = opt.step(s[0], gm.astype(jnp.float32), tuple(s[1:]),
                                  lr * lr_mults[i], wds[i], t)
                nv = nm.astype(w.dtype)
                nst = (nm,) + tuple(ns)
            else:
                nw, ns = opt.step(w, gm.astype(w.dtype), s,
                                  lr * lr_mults[i], wds[i], t)
                nv = nw.astype(w.dtype)
                nst = tuple(ns)
            new_vals.append(jax.device_put(nv, self._param_shardings[i]))
            new_states.append(tuple(
                jax.device_put(a, sh)
                for a, sh in zip(nst, self._state_shardings[i])))
        self._param_vals = tuple(new_vals)
        self._opt_states = tuple(new_states)
        self._t_dev = self._t_dev + 1
        return loss, gnorm, effects

    # ------------------------------------------------------------------
    def _ensure_built(self, n_data: int, ndims: Tuple[int, ...]) -> None:
        """(Re)build the pjit step for these batch ranks, consulting the
        autotune cache ONCE per trainer first (``MXTPU_AUTOTUNE_DIR``):
        a banked winner's env knobs overlay the first trace, so the
        tuned configuration is applied per build, not per shell."""
        if self._step_fn is not None and ndims == self._step_ndims:
            return
        if self._tuned is None:
            from .. import autotune as _autotune
            self._tuned = _autotune.consult(
                "trainer.step", self._autotune_key, mesh=self._mesh) or {}
            self.autotune_entry = self._tuned or None
        self._step_fn = self._build_step(n_data, ndims)
        self._step_ndims = ndims
        self._step_n_data = n_data

    def _refresh_scalars(self, next_t: int) -> None:
        """Materialize the device-resident step scalars. With the LR
        schedule folded into the graph the LR input is the base LR set
        ONCE — no per-step host schedule eval or transfer; otherwise the
        host mirror refreshes whenever the schedule moved."""
        # Placed with the step's own replicated NamedSharding: the jit
        # entry keys its trace on the argument types, which carry the
        # mesh, and `t` comes back from every step as a mesh-typed
        # output. A scalar made without the mesh would make step 2 a
        # different signature: a silent second trace and compile, made
        # outside the autotune overlay.
        repl = self._repl
        if self._lr_fold:
            # the lr input carries the scheduler's CURRENT base LR; the
            # step graph computes jax_lr(t) * (lr / baked_base), so a
            # live `sched.base_lr = x` rescales the folded schedule on
            # the next step without a re-trace (at the baked base the
            # factor is exactly 1)
            sched = self._optimizer.lr_scheduler
            base = float(getattr(sched, "base_lr", 0.0) or 0.0)
            if self._lr_dev is None or self._lr_val != base:
                self._lr_val = base
                self._lr_dev = jax.device_put(
                    jnp.asarray(base, jnp.float32), repl)
        elif self._lr_dev is None \
                or self._lr_val != self._optimizer.learning_rate:
            self._lr_val = self._optimizer.learning_rate
            self._lr_dev = jax.device_put(
                jnp.asarray(self._lr_val, jnp.float32), repl)
        if self._t_dev is None:
            self._t_dev = jax.device_put(
                jnp.asarray(next_t, jnp.int32), repl)
        if self._base_key is None:
            self._base_key = jax.device_put(
                random_mod.next_key(self._ctx), repl)

    def prepare(self, *batch) -> None:
        """Build everything :meth:`step` needs WITHOUT dispatching (no
        XLA compile): eager parameter init, sharding resolution, the
        autotune consult, the pjit step function, and the device-resident
        scalars. After ``prepare()`` the full fwd+bwd+optimizer graph is
        traceable offline (``analysis.hlo`` / ``benchmark.autotune``
        price it through ``jax.make_jaxpr``) before any step has run —
        the autotuner's trace-only scoring path."""
        n_data = len(batch) - self._n_labels
        if n_data < 1:
            raise MXNetError("prepare() needs at least one data argument")
        if self._params is None:
            warm_ctx = current_context()
            warm = [a if isinstance(a, NDArray) else NDArray(a, ctx=warm_ctx)
                    for a in batch[:n_data]]
            with _prof.Scope("trainer.init_state"):
                self._init_state(warm, warm_ctx)
        vals = self.place(*batch)
        self._ensure_built(n_data, tuple(v.ndim for v in vals))
        self._refresh_scalars(self._t + 1)

    def retune(self, entry: Optional[Dict[str, Any]] = None,
               site: str = "director.recompile") -> None:
        """Stage a recompile cutover (the flight director's
        ``compute_bound`` remediation): swap the tuned config and rebuild
        the pjit step entry NOW — no dispatch, no XLA compile yet (pjit
        traces lazily), so the running step is never interrupted. The
        NEXT :meth:`step` traces the fresh entry under the new config's
        env overlay and pays exactly one compile, which is banked on the
        compile ledger under ``site`` — never ``trainer.step``, so that
        site's ``assert_zero_post_warmup`` contract still holds across
        the cutover. Safe mid-run: parameters, optimizer state, the step
        counter, and the seen-signature set are all untouched.

        ``entry`` is an autotune-cache entry (``{"config": {"env": ...},
        ...}``); ``{}`` clears the tuned overlay, ``None`` keeps the
        current one (rebuild only — still a guaranteed fresh compile)."""
        if self._step_fn is None or self._step_ndims is None:
            raise MXNetError("retune() before the first build — run "
                             "prepare() or step() first")
        if self.kv_fallback_active():
            raise MXNetError("retune() stages a pjit rebuild; the "
                             "kvstore-fallback path has no pjit step")
        if entry is not None:
            self._tuned = dict(entry)
            self.autotune_entry = self._tuned or None
        from .. import autotune as _autotune
        tune_ctx = (_autotune.applied(self._tuned) if self._tuned
                    else _nullcontext())
        with tune_ctx:
            self._step_fn = self._build_step(self._step_n_data,
                                             self._step_ndims)
        self._retune_site = site

    # ------------------------------------------------------------------
    def step_trace_args(self, *batch):
        """Live argument tuple matching the jitted step's signature, for
        offline inspection (``mx.analysis.hlo`` traces the full
        fwd+bwd+optimizer graph without executing it). Requires a built
        step function — one completed :meth:`step`, or a compile-free
        :meth:`prepare`."""
        if self._step_fn is None or self._base_key is None:
            raise MXNetError("step_trace_args() needs a built step "
                             "function: run one step() (or prepare()) "
                             "first")
        vals = self.place(*batch)
        return (self._param_vals, self._opt_states, self._base_key,
                self._lr_dev, self._t_dev) + tuple(vals)

    # ------------------------------------------------------------------
    def place(self, *batch):
        """Place batch arrays onto the mesh with the data sharding (batch
        over ``dp``, sequence over ``sp``). One hop host→mesh; arrays already
        resident with a matching sharding pass through for free — call this
        from the input pipeline to overlap transfer with compute."""
        vals = []
        for a in batch:
            if isinstance(a, NDArray):
                v = a._data
            elif isinstance(a, jax.Array):
                v = a
            else:
                v = onp.asarray(a)
            sh = data_sharding(self._mesh, batch_axis=0,
                               seq_axis=self._seq_axis, ndim=v.ndim)
            vals.append(jax.device_put(v, sh))
        return tuple(vals)

    def step(self, *batch) -> NDArray:
        """Run one training step on a global batch; returns the mean loss.

        ``batch`` = data arguments then ``n_labels`` label arguments, as
        NDArrays or numpy/jax arrays (placed with batch-over-``dp``,
        seq-over-``sp`` sharding).
        """
        n_data = len(batch) - self._n_labels
        if n_data < 1:
            raise MXNetError("step() needs at least one data argument")
        from ..fault import inject as _inject
        from ..telemetry import compile_log as _clog
        from ..telemetry import events as _tele
        from ..telemetry import trace as _trace
        from .mesh import active_mesh
        attempted = self._t + 1      # event id even if a rollback resets _t
        # Dispatch: a configured mesh runs the ONE compiled pjit step
        # (explicit in/out PartitionSpecs, donated buffers) — the default
        # path. The per-parameter kvstore loop survives only behind the
        # MXTPU_KVSTORE_FALLBACK=1 opt-in (async-PS scenario).
        fallback = self.kv_fallback_active()
        # one root span per step: the kvstore-fallback push/pull hops,
        # guard verdicts, chaos draws, and the profiler's step frame all
        # stitch under it — the training twin of the router's
        # per-request tree (head sampling decides per step). Inside it,
        # one live "step" frame with its segments: in the profiler's ring
        # (the raw material of profiler.step_report()'s host-gap
        # attribution) and, under an XProf trace, in the host plane on
        # the clock of the device's operations
        with _tele.step_scope(attempted), \
                _trace.span("train.step", step=attempted,
                            path="kvstore_fallback" if fallback
                            else "pjit"), \
                _ExitStack() as live:
            frame = live.enter_context(_prof.Frame("step", step=attempted))
            # XProf's step view groups host and device work by this number
            live.enter_context(jax.profiler.StepTraceAnnotation(
                "train", step_num=attempted))
            # elastic step-boundary hooks: poll() surfaces any host loss
            # the lease watchdog detected since the last step (one
            # lock-free list read when the pod is healthy — never I/O on
            # the hot path), and note_step drives the seeded
            # host_kill/host_stall chaos knobs
            from . import elastic as _elastic
            _elastic.poll()
            _inject.note_step(attempted)
            if _inject.active() is not None:
                # the poisoned batch belongs to the step about to run
                batch = self._chaos_batch(batch, n_data)
            if self._params is None:
                # Eager warmup runs wherever the parameters were
                # initialized (current context), NOT on the mesh.
                warm_ctx = current_context()
                warm = [a if isinstance(a, NDArray)
                        else NDArray(a, ctx=warm_ctx)
                        for a in batch[:n_data]]
                with _prof.Scope("trainer.init_state"):
                    self._init_state(warm, warm_ctx)
            with _prof.Scope("step.place", step=attempted) as placed:
                vals = self.place(*batch)
            if not fallback:
                # the jit entry's batch in_shardings are rank-pinned; a
                # batch of NEW ranks rebuilds the entry (a fresh compile,
                # noted in the ledger via its new signature — the same
                # cost the re-trace paid before shardings were explicit)
                self._ensure_built(n_data, tuple(v.ndim for v in vals))
            if self._guard is not None:
                self._maybe_snapshot()
            self._t = attempted
            self._refresh_scalars(self._t)
            # a new batch (shape, dtype) signature re-traces inside the
            # jit entry — the classic silent recompile; the ledger makes
            # it visible
            sig = tuple((tuple(v.shape), str(v.dtype)) for v in vals)
            new_sig = sig not in self._step_sigs
            first_sig = not self._step_sigs
            wd = self._watchdog
            with wd.watch(step=self._t, block=self._block) if wd is not None \
                    else _nullcontext():
                _inject.maybe_delay("slow_step")
                # chaos leak site: retains device arrays so the memory
                # ledger's leak watchdog is deterministically testable
                _inject.maybe_leak("trainer.step")
                self.last_step_graphs = 1       # the step executable
                ok = None
                # a NEW signature is about to trace: overlay the autotune
                # winner's env knobs for exactly that trace (user-set env
                # always wins; see autotune.applied). A staged retune()
                # cutover re-traces a FRESH pjit entry at a seen
                # signature — same overlay rule applies
                retuned_now = self._retune_site is not None and not fallback
                if (new_sig or retuned_now) and not fallback and self._tuned:
                    from .. import autotune as _autotune
                    tune_ctx = _autotune.applied(self._tuned)
                else:
                    tune_ctx = _nullcontext()
                # a RESOURCE_EXHAUSTED out of dispatch (or the guard's
                # device sync below) writes ONE OOM flight bundle with
                # the memory ledger + static peaks, then re-raises
                from ..telemetry import memory as _memory
                with _prof.Scope("step.dispatch", step=attempted) as sent, \
                        _memory.oom_guard("trainer.step", step=attempted), \
                        active_mesh(self._mesh), tune_ctx, \
                        _clog.at(self._retune_site if retuned_now
                                 else "trainer.step"):
                    # the mesh is bound during (first-call) tracing so
                    # mesh-aware ops lower to mesh collectives — e.g.
                    # attention → ring over sp; what jax traces, lowers
                    # or compiles in here is this site's
                    # (compile_log.phase_seconds)
                    stats_dev = None
                    num_cfg = self._numerics_cfg
                    num_on = (not fallback and num_cfg is not None
                              and num_cfg.enabled)
                    if fallback:
                        loss, gnorm, effects = self._kv_step(vals, n_data)
                    else:
                        out = self._step_fn(self._param_vals,
                                            self._opt_states,
                                            self._base_key, self._lr_dev,
                                            self._t_dev, *vals)
                        if num_on:
                            stats_dev = out[-1]
                            out = out[:-1]
                        (loss, gnorm, self._param_vals,
                         self._opt_states, effects, self._t_dev,
                         ok) = out
                self.last_path = "kvstore_fallback" if fallback else "pjit"
                dispatch_ms = sent.dur_ms
                from ..telemetry import collective_ledger as _cledger
                if new_sig:
                    self._step_sigs.add(sig)
                    _clog.note("trainer.step", sig, wall_ms=dispatch_ms,
                               warmup=first_sig)
                    # bank this build's collective-schedule fingerprint
                    # (one re-trace, no XLA compile; ledger off = one env
                    # read) — a post-warmup rebank in a multi-process run
                    # crosschecks immediately: the one-host-recompiled
                    # divergence onset
                    if _cledger.enabled() and not fallback:
                        _cledger.bank_trainer(self, vals)
                if retuned_now:
                    # the staged cutover's one compile: seen signature,
                    # fresh pjit entry — banked under the staging site
                    # (director.recompile), never trainer.step, so the
                    # step site's zero-post-warmup contract survives
                    _clog.note(self._retune_site, sig,
                               wall_ms=dispatch_ms, warmup=None)
                    self._retune_site = None
                if (new_sig or retuned_now) and not fallback:
                    # how to read the step that now runs back, kept, not
                    # run: compile_log.program_text("trainer.step")
                    self._program = _StepProgram(self, vals)
                    _clog.keep_program("trainer.step", self._program)
                if self._program is not None \
                        and jax.profiler.TraceAnnotation.is_enabled():
                    # a profiler trace records this step: the program it
                    # saw outlives the trainer, to read the trace against
                    self._program.pin()
                # the dispatch ring: what this pod member actually ran,
                # in order — the flight bundle's cross-host diff surface
                _cledger.note_dispatch("trainer.step", sig)
                # numerics decimation: the host SYNCS the stat outputs
                # only every cfg.every steps (first step included), and
                # the read rides the guard's existing single device
                # sync — stats never add a host round trip of their own
                read_stats = (num_on and stats_dev is not None
                              and (attempted - 1) % num_cfg.every == 0)
                # the guard's loss/grad-norm device_get is the one point
                # the host provably blocks on the device inside the step:
                # a span of its own on guarded runs only
                with _prof.Scope("step.device_wait", step=attempted) \
                        if self._guard is not None \
                        else _nullcontext() as synced, \
                        _memory.oom_guard("trainer.step", step=attempted):
                    rolled_back = (self._guard is not None
                                   and self._apply_guard(
                                       loss, gnorm, ok,
                                       stats_dev=(stats_dev if read_stats
                                                  else None),
                                       step=attempted))
                    if read_stats and self._guard is None:
                        # unguarded loop: the decimated read is the only
                        # sync this step performs
                        from ..telemetry import numerics as _numerics
                        _numerics.record("trainer.step", attempted,
                                         jax.device_get(stats_dev),
                                         num_cfg)
            # the frame ends here, before the step's events go out: they
            # and the spans come from one reading and cannot disagree
            live.close()
            wall_ms = frame.dur_ms
            fields = {"wall_ms": round(wall_ms, 3),
                      "place_ms": round(placed.dur_ms, 3),
                      "dispatch_ms": round(dispatch_ms, 3),
                      "path": self.last_path,
                      "graphs": self.last_step_graphs}
            if self._guard is not None:
                # guard runs synced loss/grad-norm to host — free to report
                fields.update(loss=self.last_loss,
                              grad_norm=self.last_grad_norm,
                              rolled_back=rolled_back,
                              device_wait_ms=round(synced.dur_ms, 3))
            _tele.emit("train.step", step=attempted, **fields)
            # the goodput ledger folds the SAME timings into the run's
            # wall-clock attribution vector (compute/collective via the
            # guard's sync, one-off compile, host remainder; a rollback
            # reclassifies the discarded since-snapshot steps as waste)
            from ..telemetry import goodput as _goodput
            if _goodput.enabled():
                _goodput.note_step(
                    step=attempted, wall_ms=wall_ms,
                    device_wait_ms=(synced.dur_ms
                                    if self._guard is not None else 0.0),
                    compile_ms=(dispatch_ms if (new_sig or retuned_now)
                                else 0.0),
                    rolled_back=rolled_back,
                    rollback_to=(self._t if rolled_back else None))
        self._m_steps.inc()
        self._m_step_ms.observe(wall_ms)
        if self._guard is not None and self.last_grad_norm is not None:
            self._m_gnorm.set(self.last_grad_norm)
        self._optimizer.num_update = self._t
        if not rolled_back:
            # aux effects (batchnorm running stats etc.) of a rolled-back
            # step are part of the bad step — dropping them keeps the
            # restored state internally consistent
            for (p, ectx), val in zip(self._info.get("effects", ()),
                                      effects):
                p._deposit_aux(val._data if isinstance(val, NDArray)
                               else val,
                               ectx if ectx is not None else self._ctx)
        return NDArray(loss, ctx=self._ctx)

    # ------------------------------------------------------------------
    # fault tolerance (mx.fault wiring)
    # ------------------------------------------------------------------
    @staticmethod
    def _chaos_batch(batch, n_data: int):
        """Chaos hook: when the active monkey draws ``nan_batch``, the first
        float data argument is replaced with NaNs — the realistic NaN-step
        signature (propagates to loss and every grad through the unmodified
        compiled graph). The ``grad_blowup`` / ``activation_drift`` knobs
        apply the monkey's seeded per-site scale ramp to the float data
        arguments instead: activations and gradients grow monotonically
        step over step — the slow divergence trajectory the numerics
        drift watchdog must flag BEFORE anything goes non-finite (the
        ramp eventually overflows f32 and the classic guard trips, so
        one chaos run exercises the whole warn → drift → non-finite
        escalation ladder)."""
        from ..fault import inject as _inject
        scale = (_inject.scale_ramp("grad_blowup")
                 * _inject.scale_ramp("activation_drift"))
        nan = _inject.should("nan_batch")
        if not nan and scale == 1.0:
            return batch
        out = list(batch)
        poisoned = False
        for i in range(n_data):
            a = out[i]
            v = a.asnumpy() if isinstance(a, NDArray) else onp.asarray(a)
            if v.dtype.kind != "f":
                continue
            if nan and not poisoned:
                out[i] = _inject.poison(v)
                poisoned = True
            elif scale != 1.0:
                out[i] = (v * scale).astype(v.dtype, copy=False)
        return tuple(out)

    def _maybe_snapshot(self) -> None:
        """Refresh the rollback snapshot (device-side copies — step-time
        donation consumes the live buffers, so rollback needs its own)."""
        g = self._guard
        if self._snapshot is not None \
                and self._t - self._snapshot[0] < g.snapshot_every:
            return
        self._snapshot = (self._t, self._copy_state(self._param_vals),
                          self._copy_state(self._opt_states))

    @staticmethod
    def _copy_state(tree):
        return jax.tree.map(lambda a: a.copy(), tree)

    def _apply_guard(self, loss, gnorm, ok=None, stats_dev=None,
                     step=None) -> bool:
        """Returns True when the step was rolled back. ``ok`` is the
        compiled step's in-graph finite verdict — everything comes back
        in ONE host sync (``stats_dev``, the decimated numerics outputs,
        joins that same sync when due). Without ``ok`` (the kvstore
        fallback) the finite check is a SEPARATE jitted reduction, one
        more graph on this step's dispatch count.

        Escalation ordering: a real non-finite/limit verdict always
        wins; otherwise a sustained ``numerics.drift`` verdict (under
        ``MXTPU_NUMERICS_DRIFT=rollback``) feeds the SAME guard policy
        — so the drift watchdog can skip-and-rollback a diverging run
        steps before it ever goes non-finite, and ``halt``/
        ``max_consecutive`` precedence is unchanged."""
        g = self._guard
        stats_host = None
        if ok is None:
            from ..fault.guards import all_finite
            self.last_step_graphs += 1
            finite = all_finite(loss, gnorm)
            if stats_dev is not None:
                lf, gn, stats_host = jax.device_get((loss, gnorm,
                                                     stats_dev))
                lf, gn = float(lf), float(gn)
            else:
                lf = float(jax.device_get(loss))
                gn = float(jax.device_get(gnorm))
        elif stats_dev is not None:
            lf, gn, okv, stats_host = jax.device_get(
                (loss, gnorm, ok, stats_dev))
            lf, gn, finite = float(lf), float(gn), bool(okv)
        else:
            lf, gn, okv = jax.device_get((loss, gnorm, ok))
            lf, gn, finite = float(lf), float(gn), bool(okv)
        self.last_grad_norm = gn
        self.last_loss = lf
        drift = []
        if stats_host is not None:
            from ..telemetry import numerics as _numerics
            drift = _numerics.record("trainer.step", step, stats_host,
                                     self._numerics_cfg)
        reason = g.is_bad(finite, gn)
        if reason is None and drift \
                and self._numerics_cfg.drift_action == "rollback":
            # the drift watchdog armed the guard: escalate BEFORE any
            # non-finite exists, through the guard's own policy ladder
            v = drift[0]
            reason = (f"numerics drift at {v['site']} "
                      f"({v['reason']})")
        if reason is None:
            g.good_step()
            return False
        action = g.decide(self._t, reason,
                          detail=f"loss={lf:g}, grad_norm={gn:g}")
        if action == "rollback":
            self._m_rollbacks.inc()
            snap_t, pvals, states = self._snapshot
            # restore COPIES — the snapshot must survive further rollbacks
            # until the next good-step refresh
            self._param_vals = self._copy_state(pvals)
            self._opt_states = self._copy_state(states)
            self._t = snap_t
            self._t_dev = None
            self._optimizer.num_update = snap_t
            return True
        return False

    @property
    def guard(self):
        return self._guard

    @property
    def watchdog(self):
        return self._watchdog

    # ------------------------------------------------------------------
    def sync_to_block(self) -> None:
        """Write current sharded values back into the gluon Parameters."""
        if self._params is None:
            return
        for p, v in zip(self._params, self._param_vals):
            p.set_data(NDArray(jax.device_get(v), ctx=self._ctx))

    def release_block(self) -> None:
        """Free the gluon Parameters' own device arrays. From its first step
        on the trainer's copies are the weights, and the block's are a
        second set the device holds for nothing: 1.4 GB of bf16 at 705 M
        parameters, where the step's temporaries need the room.
        ``sync_to_block()`` gives the block current values back."""
        for p in self._params or ():
            for arr in (p._data or {}).values():
                if isinstance(arr._data, jax.Array) and not arr._data.is_deleted():
                    arr._data.delete()

    def save_states(self, fname: str, backend: str = "pickle") -> None:
        """Checkpoint parameters + optimizer state + step counter.

        ``backend='pickle'`` (default: one host-side file, reference
        Trainer.save_states shape) or ``'orbax'`` (a DIRECTORY written by
        orbax/TensorStore — each shard saved from its own device without a
        full host gather, the multi-controller-safe path SURVEY §5.4's TPU
        mapping prescribes). Opt-in, so existing extension-less paths keep
        producing a single pickle file; ``load_states`` auto-detects either.
        """
        if backend == "orbax":
            self._save_states_orbax(fname)
            return
        if backend != "pickle":
            raise MXNetError(f"unknown checkpoint backend {backend!r}")
        import pickle
        state = {
            "t": self._t,
            "opt_states": jax.device_get(self._opt_states),
            "param_vals": jax.device_get(self._param_vals),
        }
        tmp = f"{fname}.tmp-{os.getpid()}"
        try:
            with open(tmp, "wb") as f:
                pickle.dump(state, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, fname)  # never leave a truncated checkpoint
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def _ckpt_tree(self):
        return {"param_vals": list(self._param_vals),
                "opt_states": [list(s) for s in self._opt_states]}

    def _save_states_orbax(self, path: str) -> None:
        try:
            import orbax.checkpoint as ocp
        except ImportError as e:
            raise MXNetError(
                "backend='orbax' needs the orbax-checkpoint package") from e
        path = os.path.abspath(path)
        with ocp.Checkpointer(ocp.CompositeCheckpointHandler()) as ckptr:
            ckptr.save(
                path,
                args=ocp.args.Composite(
                    state=ocp.args.PyTreeSave(self._ckpt_tree()),
                    meta=ocp.args.JsonSave({"t": self._t})),
                force=True)

    def load_states(self, fname: str, backend: str = "auto") -> None:
        if self._params is None:
            raise MXNetError("call step() once (or _init_state) before "
                             "load_states so the parameter set exists")
        if backend == "auto":
            backend = "orbax" if os.path.isdir(fname) else "pickle"
        if backend == "orbax":
            self._load_states_orbax(fname)
            return
        if backend != "pickle":
            raise MXNetError(f"unknown checkpoint backend {backend!r}")
        import pickle
        with open(fname, "rb") as f:
            state = pickle.load(f)
        self._t = state["t"]
        self._t_dev = None  # re-materialized from self._t on next step
        items = sorted(self._block.collect_params().items())
        vals, states = [], []
        for i, ((name, p), v, st) in enumerate(
                zip(items, state["param_vals"], state["opt_states"])):
            # Restore onto the EXACT live placements (guaranteed present:
            # load_states requires an initialized trainer, and _init_state
            # always records them) — keeps the traced step signature, incl.
            # the zero1 dp-partition of optimizer states.
            vals.append(jax.device_put(jnp.asarray(v),
                                       self._param_shardings[i]))
            states.append(tuple(
                jax.device_put(jnp.asarray(s), ssh)
                for s, ssh in zip(st, self._state_shardings[i])))
        self._param_vals, self._opt_states = tuple(vals), tuple(states)

    # ------------------------------------------------------------------
    # resumable checkpoints (mx.fault.checkpoint — SURVEY §5.4 + ISSUE 2)
    # ------------------------------------------------------------------
    _CKPT_FORMAT = 1

    def save_checkpoint(self, root: str, keep: Optional[int] = 3,
                        data_state: Optional[dict] = None) -> str:
        """Write one atomic, versioned checkpoint directory under ``root``
        covering EVERYTHING a bit-identical resume needs: parameters,
        optimizer state (incl. ZeRO-1 shards — gathered to host, resharded
        on load), the step counter, the LR-schedule position, and the RNG
        base key. Returns the checkpoint directory; retention keeps the
        newest ``keep`` steps. ``data_state`` (an
        ``io.PrefetchIter.shard_state()`` dict) rides in the meta so an
        elastic restore can resume the data stream under a new host count
        with no sample overlap. Call it from the training loop::

            if trainer.num_update % 500 == 0:
                trainer.save_checkpoint("ckpts/",
                                        data_state=it.shard_state())
        """
        if self._params is None:
            raise MXNetError("nothing to checkpoint: run step() at least "
                             "once so the parameter state exists")
        from ..fault import checkpoint as ckpt
        items = sorted(self._block.collect_params().items())
        arrays: Dict[str, Any] = {}
        for i, (name, _) in enumerate(items):
            arrays[f"param:{i:04d}"] = jax.device_get(self._param_vals[i])
            for j, s in enumerate(self._opt_states[i]):
                arrays[f"opt:{i:04d}:{j}"] = jax.device_get(s)
        if self._base_key is not None:
            arrays["rng:base_key"] = jax.device_get(
                jax.random.key_data(self._base_key))
        meta = {
            "trainer": "ShardedTrainer", "format": self._CKPT_FORMAT,
            "t": self._t,
            "num_update": self._optimizer.num_update,
            "lr": float(self._optimizer.learning_rate),
            "zero1": self._zero1,
            "optimizer": type(self._optimizer).__name__,
            "rng_impl": random_mod._impl(),
            "param_names": [name for name, _ in items],
            "opt_state_sizes": [len(s) for s in self._opt_states],
        }
        if data_state is not None:
            meta["data_state"] = dict(data_state)
        from . import elastic as _elastic
        idx, count = _elastic.membership()
        meta["elastic"] = {"generation": _elastic.generation(),
                           "process_count": count}
        return ckpt.save_checkpoint(root, arrays, meta, step=self._t,
                                    keep=keep)

    def restore_checkpoint(self, root: str,
                           step: Optional[int] = None) -> int:
        """Restore from the newest verified checkpoint under ``root`` (or
        an explicit ``step``), placing every array DIRECTLY onto its live
        mesh sharding (load → reshard; the zero1 dp-partition of optimizer
        states included). Requires an initialized trainer (one ``step()``
        — its state is fully overwritten). Returns the restored step."""
        if self._params is None:
            raise MXNetError("call step() once before restore_checkpoint "
                             "so the parameter set and shardings exist")
        from ..fault import checkpoint as ckpt
        if step is None:
            arrays, meta, step = ckpt.load_latest(root)
        else:
            arrays, meta, step = ckpt.load_checkpoint(root, step)
        if meta.get("trainer") != "ShardedTrainer" \
                or meta.get("format") != self._CKPT_FORMAT:
            raise MXNetError(
                f"checkpoint step {step} was not written by "
                f"ShardedTrainer.save_checkpoint (meta: {meta.get('trainer')!r}"
                f" format {meta.get('format')!r})")
        items = sorted(self._block.collect_params().items())
        names = [name for name, _ in items]
        saved_names = meta.get("param_names", [])
        if len(saved_names) != len(names):
            raise MXNetError(
                "checkpoint parameter set does not match this block: "
                f"saved {len(saved_names)} parameters, live {len(names)}")
        src = list(range(len(names)))    # live position -> saved position
        if saved_names != names:
            # auto-incremented gluon prefixes differ across same-process
            # instances; shapes/dtypes below are the binding contract.
            # Both lists are sorted as strings, and a counter that gains
            # a digit (dense9_ -> dense10_) sorts elsewhere, so pair the
            # two by natural order, which follows creation order.
            import warnings
            warnings.warn(f"checkpoint parameter names differ from the live "
                          f"block ({saved_names[:2]}... vs {names[:2]}...); "
                          "restoring by position")
            idx = range(len(names))
            for li, si in zip(
                    sorted(idx, key=lambda i: _natural_key(names[i])),
                    sorted(idx, key=lambda i: _natural_key(saved_names[i]))):
                src[li] = si
        vals, states = [], []
        for i in range(len(items)):
            try:
                v = arrays[f"param:{src[i]:04d}"]
                st = [arrays[f"opt:{src[i]:04d}:{j}"]
                      for j in range(meta["opt_state_sizes"][src[i]])]
            except KeyError as e:
                raise MXNetError(f"checkpoint step {step} is missing "
                                 f"array {e}") from e
            live = self._param_vals[i]
            if tuple(v.shape) != tuple(live.shape) \
                    or jnp.asarray(v).dtype != live.dtype:
                raise MXNetError(
                    f"checkpoint array for parameter {names[i]!r} is "
                    f"{v.dtype}{tuple(v.shape)}, live parameter is "
                    f"{live.dtype}{tuple(live.shape)}")
            vals.append(jax.device_put(jnp.asarray(v),
                                       self._param_shardings[i]))
            states.append(tuple(
                jax.device_put(jnp.asarray(s), ssh)
                for s, ssh in zip(st, self._state_shardings[i])))
        self._param_vals, self._opt_states = tuple(vals), tuple(states)
        self._t = int(meta["t"])
        self._t_dev = None           # re-materialized from _t on next step
        self._optimizer.num_update = int(meta["num_update"])
        if "rng:base_key" in arrays:
            # placed as _refresh_scalars places it: same argument type,
            # so the restored trainer's next step is not a new signature
            self._base_key = jax.device_put(
                jax.random.wrap_key_data(
                    jnp.asarray(arrays["rng:base_key"]),
                    impl=meta.get("rng_impl") or random_mod._impl()),
                self._repl)
        self._snapshot = None        # stale rollback state from before
        # banked for elastic.recover: the data-shard boundary + the saving
        # membership live in the meta, not in any trainer array
        self.last_restore_meta = dict(meta)
        return step

    def _load_states_orbax(self, path: str) -> None:
        """Restore each array DIRECTLY onto its mesh sharding (TensorStore
        reads only this process's shards — no host-side full gather)."""
        try:
            import orbax.checkpoint as ocp
        except ImportError as e:
            raise MXNetError(
                "this checkpoint is an orbax directory; the orbax-checkpoint "
                "package is required to restore it") from e
        path = os.path.abspath(path)
        # restore targets: abstract arrays carrying the CURRENT shardings
        tpl = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
            self._ckpt_tree())
        with ocp.Checkpointer(ocp.CompositeCheckpointHandler()) as ckptr:
            restored = ckptr.restore(
                path,
                args=ocp.args.Composite(
                    state=ocp.args.PyTreeRestore(
                        tpl, restore_args=jax.tree.map(
                            lambda s: ocp.ArrayRestoreArgs(sharding=s.sharding),
                            tpl)),
                    meta=ocp.args.JsonRestore()))
        state = restored["state"]
        self._t = int(restored["meta"]["t"])
        self._t_dev = None
        self._param_vals = tuple(state["param_vals"])
        self._opt_states = tuple(tuple(s) for s in state["opt_states"])
