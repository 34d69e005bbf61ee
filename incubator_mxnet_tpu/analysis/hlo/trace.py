"""Entry-point tracing: any model entry → jaxpr + lowered StableHLO.

The compiled graph — not the Python source — determines what the TPU
actually executes (fusion, transfers, donation, baked constants), so the
MX7xx passes inspect :class:`TracedGraph` records produced here rather
than ASTs. One tracer per entry-point family:

- a live :class:`~incubator_mxnet_tpu.gluon.block.HybridBlock` (traced
  through the same inference pure function ``export()`` serializes);
- a :class:`~incubator_mxnet_tpu.serve.CompiledModel` (one graph per
  bucket assignment, donation intent included);
- a cold-loaded :class:`~incubator_mxnet_tpu.gluon.block.SymbolBlock`
  artifact (per baked signature, via ``jax.export`` round-trip);
- a :class:`~incubator_mxnet_tpu.parallel.ShardedTrainer` step (the full
  fwd+bwd+optimizer jaxpr, donation flags read off the jitted entry);
- any plain callable + sample args.

Tracing never triggers an XLA *compile* — ``jax.make_jaxpr`` only runs
the Python trace, and the StableHLO text is lowered lazily on demand —
so the passes are safe to run at serve staging time and in CI. One
exception, same contract as ``CompiledModel(example_args=...)``: a
HybridBlock that has never recorded a forward is hybridized and given
ONE eager warmup call with the first ``sample_args`` site (finishing
deferred parameter init and recording the call signature; the first call
of a fresh hybridized block runs eagerly, outside the jit cache).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as onp

from ...base import MXNetError
from ..diagnostics import Diagnostic, Report

__all__ = ["TracedGraph", "TraceResult", "trace_entry", "walk_eqns"]


@dataclass
class TracedGraph:
    """One lowered call site: the (unwrapped) closed jaxpr plus the
    calling-convention metadata the MX7xx passes need.

    ``arg_names``/``roles`` align with ``closed.jaxpr.invars``; roles are
    ``"rng_key" | "input" | "param" | "state" | "other"``. ``donated`` is
    per-invar donation intent (``None`` = donation not applicable/unknown,
    e.g. a bare block — the donation pass skips those). ``signature`` is
    the (shape, dtype) tuple of the ``input``-role invars — the static
    twin of the telemetry compile-ledger key. ``expected`` records whether
    this signature was declared up front (a bucket assignment / exported
    signature); ``False`` means an unbucketed call site reached the model
    and is reported as an error-severity MX706. The in-tree compiled
    tracer diagnoses its own overflow samples directly, so ``False`` is
    primarily the contract for custom tracers that hand-build
    TracedGraphs for :func:`~..passes.run_hlo_passes`.
    """

    entry: str
    site: str
    closed: Any                      # jax ClosedJaxpr
    arg_names: List[str]
    roles: List[str]
    kind: str = "infer"              # "infer" | "train"
    donated: Optional[Tuple[bool, ...]] = None
    signature: tuple = ()
    expected: Optional[bool] = None
    #: named mesh axis sizes the graph was traced under (``None`` = no
    #: mesh context) — lets the cost model price collectives
    mesh_axes: Optional[Dict[str, int]] = None
    #: per-invar PartitionSpec (``None`` entries = unknown/replicated),
    #: aligned with ``closed.jaxpr.invars`` — the SPMD resource contract
    #: the cost model derives implied gradient-exchange collectives from
    in_specs: Optional[List] = None
    _lower: Optional[Callable[[], str]] = None

    def hlo_text(self) -> str:
        """Lowered StableHLO text (lazy — only the first call pays the
        lowering; the text is memoized)."""
        if self._lower is None:
            raise MXNetError(f"{self.entry}[{self.site}] was built without "
                             "a lowering hook; construct the TracedGraph "
                             "with _lower=<zero-arg callable returning the "
                             "StableHLO text> to make hlo_text() available")
        if getattr(self, "_hlo_cache", None) is None:
            self._hlo_cache = self._lower()
        return self._hlo_cache

    @property
    def label(self) -> str:
        return f"{self.entry}[{self.site}]"


@dataclass
class TraceResult:
    graphs: List[TracedGraph] = field(default_factory=list)
    #: notes about coverage limits (surfaced via Report.skipped)
    skipped: List[str] = field(default_factory=list)
    #: diagnostics raised by tracing itself (e.g. bucket overflow)
    diags: List[Diagnostic] = field(default_factory=list)


def walk_eqns(jaxpr):
    """Yield every eqn in a (open) jaxpr, recursing into sub-jaxprs held
    in eqn params (jit / scan / cond bodies) — duck-typed so it works
    across jax versions."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _jaxprs_in(v):
                yield from walk_eqns(sub)


def _jaxprs_in(v):
    """Open jaxprs held in an eqn-param value. ClosedJaxpr is checked
    FIRST: it also exposes ``.eqns`` (delegated), but only the open
    ``.jaxpr`` carries ``.invars``."""
    if hasattr(v, "jaxpr") and hasattr(v, "consts"):     # ClosedJaxpr
        yield v.jaxpr
    elif hasattr(v, "eqns"):                    # open Jaxpr
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _jaxprs_in(x)


def _unwrap_jit(closed, fn):
    """make_jaxpr over a jitted callable yields one wrapping ``jit`` eqn
    carrying the donation intent; return (inner ClosedJaxpr,
    donated_invars). A plain callable has no wrapper: (closed, None).
    A jitted ``fn`` (it has ``.lower``) that does not unwrap is an error
    — donation would be silently invisible to MX704/MX708 and to
    ``peak_live_bytes``."""
    jaxpr = closed.jaxpr
    if len(jaxpr.eqns) == 1 and jaxpr.eqns[0].primitive.name == "jit":
        eqn = jaxpr.eqns[0]
        inner = eqn.params["jaxpr"]
        if len(inner.jaxpr.invars) == len(jaxpr.invars):
            return inner, tuple(eqn.params["donated_invars"])
    if hasattr(fn, "lower"):
        raise MXNetError(
            "analysis.hlo: the jitted entry "
            f"{getattr(fn, '__name__', type(fn).__name__)!r} did not trace "
            "to one wrapping 'jit' eqn (got "
            f"{[e.primitive.name for e in jaxpr.eqns][:4]}) — the jaxpr "
            "spelling of the installed jax changed; repair _unwrap_jit")
    return closed, None


def _aval_of(a) -> Tuple[tuple, str]:
    from ...ndarray import NDArray
    if isinstance(a, NDArray):
        return tuple(a.shape), str(a._data.dtype)
    arr = onp.asarray(a) if not hasattr(a, "dtype") else a
    return tuple(getattr(arr, "shape", ())), str(arr.dtype)


def _sds(shape, dtype):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def _sites_of(sample_args):
    """Normalize the ``sample_args`` argument: one tuple of arrays = one
    call site; a list of tuples = several call sites."""
    if sample_args is None:
        return []
    if isinstance(sample_args, list):
        return [tuple(s) if isinstance(s, (list, tuple)) else (s,)
                for s in sample_args]
    if isinstance(sample_args, tuple):
        return [sample_args]
    return [(sample_args,)]


def _sig_str(sig) -> str:
    return ",".join(f"{'x'.join(map(str, s))}:{d}" for s, d in sig)


# ---------------------------------------------------------------------------
# per-entry tracers
# ---------------------------------------------------------------------------

def _trace_block(block, sample_args, max_graphs: int) -> TraceResult:
    """Trace a live HybridBlock through the same inference-mode pure
    function ``export()``/``CompiledModel`` use. Each sample-args set is
    one call site; the recorded ``_last_sig`` is the fallback site."""
    import jax

    from ... import random as random_mod

    res = TraceResult()
    sites = _sites_of(sample_args)
    if getattr(block, "_last_sig", None) is None:
        if not sites:
            raise MXNetError(
                "analysis.hlo needs a traced graph: call hybridize() and "
                "run one forward, or pass sample_args")
        if not block._active:
            block.hybridize()
        block(*sites[0])      # establish skeleton / parameter set
    skeleton, n_in, rec_avals, ctx = block._last_sig
    params = getattr(block, "_cached_params", [])
    name_by_id = {id(p): k for k, p in
                  block._collect_params_with_prefix().items()}
    pnames = [name_by_id.get(id(p), f"param:{i}")
              for i, p in enumerate(params)]
    impl = random_mod._impl()
    key_data = jax.random.key_data(jax.random.key(0, impl=impl))
    entry = type(block).__name__

    site_sigs = []
    for i, site in enumerate(sites):
        arrs = [a for a in site]
        if len(arrs) != n_in:
            raise MXNetError(f"sample_args[{i}] has {len(arrs)} arrays but "
                             f"the model takes {n_in}")
        site_sigs.append(("site%d" % i, [_aval_of(a) for a in arrs]))
    if not site_sigs:
        site_sigs = [("recorded", [(tuple(s), str(d)) for s, d in rec_avals])]
    if len(site_sigs) > max_graphs:
        res.skipped.append(
            f"hlo: traced {max_graphs}/{len(site_sigs)} call sites of "
            f"{entry}")
        site_sigs = site_sigs[:max_graphs]

    for site, sig in site_sigs:
        pure, _meta = block._make_pure_infer(skeleton, n_in, ctx)
        avals = [_sds(key_data.shape, key_data.dtype)]
        avals += [_sds(s, d) for s, d in sig]
        avals += [_sds(tuple(p.shape), p.dtype) for p in params]
        closed = jax.make_jaxpr(pure)(*avals)
        closed, donated = _unwrap_jit(closed, pure)
        res.graphs.append(TracedGraph(
            entry=entry, site=site, closed=closed,
            arg_names=(["rng_key"] + [f"input:{i}" for i in range(n_in)]
                       + pnames),
            roles=(["rng_key"] + ["input"] * n_in + ["param"] * len(params)),
            donated=donated,
            signature=tuple((tuple(s), str(d)) for s, d in sig),
            # lazy lowering hook, invoked at most once per graph
            _lower=(lambda p=pure, av=tuple(avals):
                    jax.jit(p).lower(*av).as_text())))  # mxlint: disable=MX501
    return res


def _trace_compiled(cm, sample_args, max_graphs: int) -> TraceResult:
    """One graph per bucket assignment of a CompiledModel (all marked
    ``expected``), plus one per sample-args call site checked against the
    bucket table — a sample that overflows the table is the unbucketed-
    shape bug, reported as an MX706 diagnostic right here."""
    import jax

    from ...serve.buckets import BucketOverflow

    res = TraceResult()
    entry = type(cm._block).__name__
    n_in = cm._n_in
    if cm._mode == "artifact":
        fns = None
        donated = None
    else:
        fns = cm._pure
        req = getattr(cm, "_donate_requested", "auto")
        donated = None if req is None else (
            (False,) + (req in ("auto", True),) * n_in
            + (False,) * len(cm._pvals))

    assignments = list(cm._table.assignments())
    # EVERY bucket signature is "declared" even when tracing is capped —
    # a sample landing in an untraced-but-declared bucket must not be
    # reported as unbucketed (MX706)
    declared = {tuple(cm.signature_for(a)) for a in assignments}
    if len(assignments) > max_graphs:
        res.skipped.append(
            f"hlo: traced {max_graphs}/{len(assignments)} bucket "
            f"signatures of {entry}")
        assignments = assignments[:max_graphs]

    def one(site, sig, expected):
        avals = [_sds(cm._key_data.shape, cm._key_data.dtype)]
        avals += [_sds(s, d) for s, d in sig]
        avals += [_sds(p.shape, p.dtype) for p in cm._pvals]
        if cm._mode == "artifact":
            ins = [_sds(s, d) for s, d in sig]
            fn = cm._block._sig_for(ins)["exported"].call
        else:
            fn = fns
        closed = jax.make_jaxpr(fn)(*avals)
        closed, unwrapped_donated = _unwrap_jit(closed, fn)
        res.graphs.append(TracedGraph(
            entry=entry, site=site, closed=closed,
            arg_names=(["rng_key"] + [f"input:{i}" for i in range(n_in)]
                       + [f"param:{i}" for i in range(len(cm._pvals))]),
            roles=(["rng_key"] + ["input"] * n_in
                   + ["param"] * len(cm._pvals)),
            donated=donated if donated is not None else unwrapped_donated,
            signature=tuple((tuple(s), str(d)) for s, d in sig),
            expected=expected,
            # lazy lowering hook, invoked at most once per graph
            _lower=(lambda f=fn, av=tuple(avals):
                    jax.jit(f).lower(*av).as_text())))  # mxlint: disable=MX501

    seen = set()
    for assignment in assignments:
        sig = cm.signature_for(assignment)
        key = tuple(sig)
        if key in seen:
            continue
        seen.add(key)
        site = ",".join(f"{k}={v}" for k, v in sorted(assignment.items()))
        one(site, sig, expected=True)

    for i, sample in enumerate(_sites_of(sample_args)):
        arrays = [onp.asarray(a) if not hasattr(a, "shape") else a
                  for a in sample]
        try:
            sizes = cm._sizes_of([onp.asarray(getattr(a, "_data", a))
                                  for a in arrays])
            assignment = cm._table.assignment(sizes)
        except BucketOverflow as e:
            res.diags.append(Diagnostic(
                "MX706", f"call site sample[{i}] does not fit the bucket "
                f"table ({e}) — this request shape reaches the model "
                "unbucketed and costs a fresh XLA compile per novel shape",
                node=f"{entry}[sample{i}]", pass_name="hlo_signature",
                severity="error"))
            continue
        sig = cm.signature_for(assignment)
        if tuple(sig) not in seen:
            seen.add(tuple(sig))
            one(f"sample{i}", sig, expected=tuple(sig) in declared)
    return res


def _trace_artifact(block, sample_args, max_graphs: int) -> TraceResult:
    """Every signature baked into an exported SymbolBlock artifact."""
    import jax

    res = TraceResult()
    entry = block._arch.get("block", "SymbolBlock") if block._arch \
        else "SymbolBlock"
    sigs = block._sigs
    if len(sigs) > max_graphs:
        res.skipped.append(f"hlo: traced {max_graphs}/{len(sigs)} artifact "
                           f"signatures of {entry}")
        sigs = sigs[:max_graphs]
    arch = block._arch
    order = list(arch.get("param_order", []))
    key = arch["key"]
    for i, ent in enumerate(sigs):
        sig = [(tuple(s), d) for s, d in ent["in_avals"]]
        fn = ent["exported"].call
        avals = [_sds(tuple(key["shape"]), key["dtype"])]
        avals += [_sds(s, d) for s, d in sig]
        avals += [_sds(tuple(block._param_arrays[n].shape),
                       block._param_arrays[n]._data.dtype) for n in order]
        closed = jax.make_jaxpr(fn)(*avals)
        closed, _don = _unwrap_jit(closed, fn)
        res.graphs.append(TracedGraph(
            entry=entry, site=f"sig{i}:{_sig_str(sig)}", closed=closed,
            arg_names=(["rng_key"]
                       + [f"input:{j}" for j in range(len(sig))] + order),
            roles=(["rng_key"] + ["input"] * len(sig)
                   + ["param"] * len(order)),
            donated=None,
            signature=tuple(sig), expected=True,
            # lazy lowering hook, invoked at most once per graph
            _lower=(lambda f=fn, av=tuple(avals):
                    jax.jit(f).lower(*av).as_text())))  # mxlint: disable=MX501
    return res


def _trace_trainer(trainer, sample_args) -> TraceResult:
    """The full sharded training step (fwd + bwd + optimizer + collectives)
    — the graph the telemetry compile ledger sees at ``trainer.step``."""
    import jax

    from ...parallel.mesh import active_mesh

    res = TraceResult()
    sites = _sites_of(sample_args)
    if not sites:
        raise MXNetError("analysis.hlo over a ShardedTrainer needs "
                         "sample_args=(one training batch)")
    args = trainer.step_trace_args(*sites[0])
    param_vals, opt_states, key, lr, t = args[:5]
    batch_vals = args[5:]
    names, roles, specs = [], [], []
    pnames = [p.name for p in trainer._params]
    param_shardings = list(trainer._param_shardings or [])
    state_shardings = [sh for tup in (trainer._state_shardings or [])
                       for sh in tup]
    for i, _ in enumerate(jax.tree_util.tree_leaves(tuple(param_vals))):
        names.append(pnames[i] if i < len(pnames) else f"param:{i}")
        roles.append("param")
        specs.append(param_shardings[i].spec
                     if i < len(param_shardings) else None)
    for i, _ in enumerate(jax.tree_util.tree_leaves(tuple(opt_states))):
        names.append(f"opt:{i}")
        roles.append("state")
        specs.append(state_shardings[i].spec
                     if i < len(state_shardings) else None)
    for n, r in [("rng_key", "rng_key"), ("lr", "other"), ("t", "other")]:
        names.append(n)
        roles.append(r)
        specs.append(None)
    for i, v in enumerate(batch_vals):
        names.append(f"input:{i}")
        roles.append("input")
        specs.append(getattr(getattr(v, "sharding", None), "spec", None))
    with active_mesh(trainer._mesh):
        closed = jax.make_jaxpr(trainer._step_fn)(*args)
    closed, donated = _unwrap_jit(closed, trainer._step_fn)
    if len(names) != len(closed.jaxpr.invars):
        # flattening mismatch (exotic optimizer state): degrade gracefully
        names = [f"arg:{i}" for i in range(len(closed.jaxpr.invars))]
        roles = ["other"] * len(names)
        specs = None
    res.graphs.append(TracedGraph(
        entry=type(trainer._block).__name__ + ".step", site="step",
        closed=closed, arg_names=names, roles=roles, kind="train",
        donated=donated,
        signature=tuple(_aval_of(v) for v in batch_vals),
        mesh_axes=dict(trainer._mesh.shape),
        in_specs=specs,
        _lower=(lambda fn=trainer._step_fn, av=args, m=trainer._mesh:
                _lower_in_mesh(fn, av, m))))
    return res


def _lower_in_mesh(fn, args, mesh):
    from ...parallel.mesh import active_mesh
    with active_mesh(mesh):
        return fn.lower(*args).as_text()


def _trace_callable(fn, sample_args, entry=None) -> TraceResult:
    import jax

    from ...parallel.mesh import current_active_mesh

    res = TraceResult()
    sites = _sites_of(sample_args)
    if not sites:
        raise MXNetError("analysis.hlo over a plain callable needs "
                         "sample_args")
    name = entry or getattr(fn, "__name__", type(fn).__name__)
    # tracing inside `with active_mesh(mesh):` gives the cost model the
    # axis sizes it needs to price explicit (shard_map) collectives
    mesh = current_active_mesh()
    mesh_axes = dict(mesh.shape) if mesh is not None else None
    for i, site in enumerate(sites):
        avals = [_sds(*_aval_of(a)) for a in site]
        closed = jax.make_jaxpr(fn)(*avals)
        closed, donated = _unwrap_jit(closed, fn)
        n = len(closed.jaxpr.invars)
        res.graphs.append(TracedGraph(
            entry=name, site=f"site{i}", closed=closed,
            arg_names=[f"input:{j}" for j in range(n)],
            roles=["input"] * n, donated=donated,
            signature=tuple(_aval_of(a) for a in site),
            mesh_axes=mesh_axes,
            # lazy lowering hook, invoked at most once per graph
            _lower=(lambda f=fn, av=tuple(avals):
                    jax.jit(f).lower(*av).as_text())))  # mxlint: disable=MX501
    return res


def trace_entry(model, sample_args=None, max_graphs: int = 8) -> TraceResult:
    """Dispatch one model entry point to its tracer. Accepts a
    CompiledModel, ShardedTrainer, SymbolBlock artifact, HybridBlock, or
    plain callable (+ ``sample_args``)."""
    from ...gluon.block import HybridBlock, SymbolBlock
    from ...serve.compiled import CompiledModel
    from ...serve.decode.engine import DecodeEngine
    try:
        from ...parallel.trainer import ShardedTrainer
    except Exception:                                    # pragma: no cover
        ShardedTrainer = ()
    if isinstance(model, DecodeEngine):
        # both graph families: every prefill bucket + the capacity-sized
        # decode step (the engine owns the assembly)
        return model.trace(max_graphs=max_graphs)
    if isinstance(model, CompiledModel):
        return _trace_compiled(model, sample_args, max_graphs)
    if ShardedTrainer and isinstance(model, ShardedTrainer):
        return _trace_trainer(model, sample_args)
    if isinstance(model, SymbolBlock):
        return _trace_artifact(model, sample_args, max_graphs)
    if isinstance(model, HybridBlock):
        return _trace_block(model, sample_args, max_graphs)
    if callable(model):
        return _trace_callable(model, sample_args)
    raise MXNetError(
        f"analysis.hlo cannot trace {type(model).__name__}; pass a "
        "HybridBlock, CompiledModel, SymbolBlock, ShardedTrainer, or a "
        "callable with sample_args")
