"""Analytic cost model over traced compiled graphs — the device-blind
perf proxy.

A device is not always at hand (no hardware in CI, a budgeted chip),
but the *compiled graph* is always available: ``trace.py`` lowers any
entry point to a jaxpr without an XLA compile. This module walks that
jaxpr and prices it — FLOPs (dot/conv from dimension numbers, everything
else per output element), transcendental element counts, parameter /
input / output / activation bytes, and fusion statistics (maximal
def-use-connected groups of elementwise ops — the metric "Operator
Fusion in XLA" (arXiv 2301.13062) shows tracks realized performance).
Every count is a deterministic function of the traced graph, so two runs
of the same code produce byte-identical tables — the property the
autotuner's deterministic election and the MX709 budget gate rely on.

Entry points::

    rep = mx.analysis.hlo.cost(model, sample_args)   # CostReport
    rep.model_flops_per_step()                       # derived headline
    print(rep.text_table())                          # mxlint --hlo --cost

The same numbers surface as an informational MX707 diagnostic per graph
when the ``hlo_cost`` pass runs with ``cost=True``
(``mx.analysis.hlo.verify(model, sample_args, cost=True)``) — opt-in so
staging gates stay signal-only by default.

Accounting rules (documented limits, all deterministic):

- ``scan`` bodies multiply execution metrics (FLOPs/transcendentals/
  activation bytes) by the trip count; ``while`` bodies count once (trip
  count unknowable statically — noted per graph); ``cond`` prices its
  costliest branch.
- fusion statistics are compile-time metrics: counted once per (sub-)
  jaxpr, never multiplied by trip counts.
- unknown primitives price one FLOP per output element and are tallied
  in ``unknown_eqns`` so a drifting jax version is visible, not silent.

Memory: :func:`peak_live_bytes` is a donation-aware last-use liveness
scan over the same jaxpr — args + consts + the maximal simultaneously-
live eqn outputs — the deterministic static twin of the runtime
``telemetry.memory`` ledger. It feeds the ``peak`` column of ``mxlint
--cost``, the autotune memory-feasibility constraint, and the MX709 ``hlo_memory`` pass that
errors when a graph (or a whole bucket ladder,
:func:`ladder_peak_bytes`) exceeds ``MXTPU_HBM_BUDGET``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as onp

from ..diagnostics import Diagnostic
from .trace import TracedGraph, trace_entry

__all__ = ["GraphCost", "CostReport", "graph_cost", "cost_table", "cost",
           "peak_live_bytes", "ladder_peak_bytes", "hbm_budget_bytes"]


# -- primitive taxonomy ------------------------------------------------------
#: one transcendental evaluation per output element (counted separately —
#: TPUs run these on the slower special-function path)
_TRANSCENDENTAL = frozenset({
    "exp", "exp2", "log", "log2", "log1p", "expm1", "tanh", "sin", "cos",
    "tan", "asin", "acos", "atan", "atan2", "sinh", "cosh", "asinh",
    "acosh", "atanh", "erf", "erfc", "erf_inv", "logistic", "pow",
    "rsqrt", "sqrt", "cbrt", "digamma", "lgamma", "igamma", "igammac",
})

#: one FLOP per output element
_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "rem", "max", "min", "neg", "abs",
    "sign", "floor", "ceil", "round", "clamp", "select_n", "and", "or",
    "xor", "not", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "eq", "ne", "ge", "gt", "le", "lt",
    "nextafter", "is_finite", "square", "reciprocal", "integer_pow",
    "add_any", "real", "imag", "conj", "complex", "population_count",
    "clz", "random_bits",
})

#: one FLOP per *input* element (a reduction reads everything once)
_REDUCE = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
    "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
    "reduce_window_sum", "reduce_window_max", "reduce_window_min",
})

#: zero FLOPs — data movement / relabeling XLA lowers to copies or elides
_MOVEMENT = frozenset({
    "reshape", "broadcast_in_dim", "transpose", "slice", "dynamic_slice",
    "dynamic_update_slice", "concatenate", "pad", "rev", "gather",
    "scatter", "scatter-add", "scatter_add", "squeeze", "expand_dims",
    "iota", "convert_element_type", "bitcast_convert_type",
    "stop_gradient", "split", "sort", "top_k", "copy", "device_put",
    "random_seed", "random_wrap", "random_fold_in", "random_unwrap",
    "reduce_precision", "sharding_constraint", "broadcast",
})

#: eqns XLA's fusion pass can merge with their producers/consumers; a
#: def-use-connected group of these lowers to ~one fused kernel
_FUSIBLE = (_TRANSCENDENTAL | _ELEMENTWISE
            | frozenset({"broadcast_in_dim", "convert_element_type",
                         "reshape", "iota", "copy", "reduce_precision"}))

#: explicit collective primitives (shard_map / pmap regions) → verb.
#: Priced per device with the standard ring-algorithm byte counts.
_COLLECTIVE_VERBS = {
    "psum": "all_reduce", "pmax": "all_reduce", "pmin": "all_reduce",
    "all_gather": "all_gather",
    "psum_scatter": "reduce_scatter", "reduce_scatter": "reduce_scatter",
    "all_to_all": "all_to_all",
    "ppermute": "ppermute", "collective_permute": "ppermute",
}


def _collective_axes(eqn) -> tuple:
    """Named mesh axes a collective eqn reduces/gathers over."""
    ax = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(ax, (str, int)):
        ax = (ax,)
    return tuple(a for a in ax if isinstance(a, str))


def _spec_axes(spec) -> frozenset:
    """Mesh axis names a PartitionSpec partitions over."""
    if spec is None:
        return frozenset()
    axes = set()
    for e in tuple(spec):
        if e is None:
            continue
        for a in ((e,) if isinstance(e, str) else tuple(e)):
            axes.add(a)
    return frozenset(axes)


def _is_literal(v) -> bool:
    return type(v).__name__ == "Literal"


def _elems(aval) -> int:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 1
    return int(onp.prod(shape, dtype=onp.int64)) if len(shape) else 1


def _nbytes(aval) -> int:
    try:
        d = onp.dtype(aval.dtype)
    except (TypeError, AttributeError):
        return 0                      # extended dtypes (PRNG keys)
    return _elems(aval) * d.itemsize


@dataclass
class GraphCost:
    """One traced graph priced. ``flops`` is per executed call — for a
    ``kind == "train"`` graph that IS the model-FLOPs-per-step."""

    entry: str
    site: str
    kind: str = "infer"
    flops: float = 0.0
    matmul_flops: float = 0.0        # dot_general + conv share of flops
    transcendentals: int = 0         # transcendental element evaluations
    param_bytes: int = 0
    input_bytes: int = 0
    output_bytes: int = 0
    #: every eqn output's bytes (trip-multiplied) — a memory-TRAFFIC
    #: proxy, NOT residency: values that die immediately still count.
    #: Residency is :attr:`peak_live_bytes` (the liveness scan).
    activation_bytes: int = 0
    #: deterministic peak live device bytes over one executed call:
    #: non-donated args + consts resident for the whole call, plus the
    #: maximal simultaneously-live set of eqn outputs under a
    #: last-use liveness scan (donated inputs die at their last use —
    #: the donation credit). An upper-bound residency model: XLA's
    #: buffer-assignment reuse can only come in under it.
    peak_live_bytes: int = 0
    eqns: int = 0
    fusible_eqns: int = 0
    fusion_groups: int = 0           # def-use components of fusible eqns
    fusion_candidates: int = 0       # groups of >= 2 eqns (real fusions)
    unknown_eqns: int = 0
    #: collective verb → executed count: explicit shard_map/pmap prims in
    #: the jaxpr PLUS, for a mesh-configured train graph, the implied SPMD
    #: gradient exchange (all-reduce over ``dp``; reduce-scatter +
    #: all-gather under ZeRO-1) derived from the in-resource specs
    collective_ops: Dict[str, int] = field(default_factory=dict)
    #: per-device communication bytes per executed call (ring-algorithm
    #: accounting: all-reduce 2(N-1)/N·B, gather/scatter (N-1)/N·B)
    comm_bytes: float = 0.0
    notes: List[str] = field(default_factory=list)

    @property
    def label(self) -> str:
        return f"{self.entry}[{self.site}]"

    @property
    def bytes_per_step(self) -> int:
        """Memory-TRAFFIC floor per call: params + inputs + outputs —
        bytes the call must at minimum move through HBM, not bytes it
        must simultaneously hold. Residency (what OOMs a chip) is
        :attr:`peak_live_bytes`; ``activation_bytes`` is likewise a
        traffic proxy (every eqn output, even values that die
        immediately)."""
        return self.param_bytes + self.input_bytes + self.output_bytes

    def to_dict(self) -> dict:
        return {
            "entry": self.entry, "site": self.site, "kind": self.kind,
            "flops": float(self.flops),
            "matmul_flops": float(self.matmul_flops),
            "transcendentals": int(self.transcendentals),
            "param_bytes": int(self.param_bytes),
            "input_bytes": int(self.input_bytes),
            "output_bytes": int(self.output_bytes),
            "activation_bytes": int(self.activation_bytes),
            "peak_live_bytes": int(self.peak_live_bytes),
            "bytes_per_step": int(self.bytes_per_step),
            "eqns": int(self.eqns),
            "fusible_eqns": int(self.fusible_eqns),
            "fusion_groups": int(self.fusion_groups),
            "fusion_candidates": int(self.fusion_candidates),
            "unknown_eqns": int(self.unknown_eqns),
            "collective_ops": {k: int(v)
                               for k, v in sorted(self.collective_ops.items())},
            "comm_bytes": int(self.comm_bytes),
            "notes": list(self.notes),
        }


# -- jaxpr walk --------------------------------------------------------------

def _sub_jaxprs(eqn):
    from .trace import _jaxprs_in
    for v in eqn.params.values():
        yield from _jaxprs_in(v)


def _fusion_stats(jaxpr):
    """(fusible_eqns, fusion_groups, fusion_candidates) at ONE jaxpr
    level: union-find over fusible eqns connected by def-use edges."""
    fusible = [i for i, e in enumerate(jaxpr.eqns)
               if e.primitive.name in _FUSIBLE]
    if not fusible:
        return 0, 0, 0
    idx = set(fusible)
    parent = {i: i for i in fusible}

    def find(i):
        while parent[i] != i:
            parent[i] = parent[parent[i]]
            i = parent[i]
        return i

    producer = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for o in eqn.outvars:
            producer[o] = i
    for i in fusible:
        for v in jaxpr.eqns[i].invars:
            if _is_literal(v):
                continue
            j = producer.get(v)
            if j is not None and j in idx:
                parent[find(i)] = find(j)
    sizes: Dict[int, int] = {}
    for i in fusible:
        r = find(i)
        sizes[r] = sizes.get(r, 0) + 1
    groups = len(sizes)
    candidates = sum(1 for s in sizes.values() if s >= 2)
    return len(fusible), groups, candidates


def _axis_prod(axes: tuple, mesh_axes: Optional[Dict[str, int]]) -> int:
    """Product of the named axis sizes, 0 when any size is unknown."""
    n = 1
    for a in axes:
        size = (mesh_axes or {}).get(a)
        if not size:
            return 0
        n *= size
    return n


def _comm_into(verb: str, nbytes: float, n: int, count: float,
               acc: dict) -> None:
    """Accumulate one collective: ring-algorithm per-device bytes —
    all-reduce moves 2(N-1)/N·B, gather/scatter-family (N-1)/N·B,
    ppermute B. Unknown axis size (n=0) prices the full payload."""
    factor = (n - 1) / n if n > 1 else (0.0 if n == 1 else 1.0)
    if verb == "all_reduce":
        factor *= 2.0
    if verb == "ppermute":
        factor = 1.0
    acc["collectives"][verb] = acc["collectives"].get(verb, 0) + count
    acc["comm_bytes"] += factor * nbytes * count


def _eqn_into(eqn, mul: float, acc: dict,
              mesh_axes: Optional[Dict[str, int]] = None) -> None:
    name = eqn.primitive.name
    out_elems = sum(_elems(o.aval) for o in eqn.outvars
                    if hasattr(o, "aval"))
    out_bytes = sum(_nbytes(o.aval) for o in eqn.outvars
                    if hasattr(o, "aval"))
    if name in _COLLECTIVE_VERBS:
        verb = _COLLECTIVE_VERBS[name]
        n = _axis_prod(_collective_axes(eqn), mesh_axes)
        payload = out_bytes
        if verb == "reduce_scatter":      # input is the full array
            payload = sum(_nbytes(v.aval) for v in eqn.invars
                          if not _is_literal(v) and hasattr(v, "aval"))
        _comm_into(verb, payload, n, mul, acc)
        acc["activation_bytes"] += out_bytes * mul
        acc["eqns"] += 1
        return
    flops = 0.0
    if name == "dot_general":
        (lc, _rc), _batch = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval
        contract = 1
        for d in lc:
            contract *= int(lhs.shape[d])
        flops = 2.0 * out_elems * contract
        acc["matmul_flops"] += flops * mul
    elif name == "conv_general_dilated":
        dn = eqn.params["dimension_numbers"]
        rhs = eqn.invars[1].aval
        rhs_spec = dn.rhs_spec          # (out_ch, in_ch/groups, *spatial)
        in_ch = int(rhs.shape[rhs_spec[1]])
        ksp = 1
        for d in rhs_spec[2:]:
            ksp *= int(rhs.shape[d])
        flops = 2.0 * out_elems * in_ch * ksp
        acc["matmul_flops"] += flops * mul
    elif name in _TRANSCENDENTAL:
        flops = float(out_elems)
        acc["transcendentals"] += int(out_elems * mul)
    elif name in _ELEMENTWISE:
        flops = float(out_elems)
    elif name in _REDUCE:
        ins = [v for v in eqn.invars
               if not _is_literal(v) and hasattr(v, "aval")]
        flops = float(_elems(ins[0].aval)) if ins else float(out_elems)
    elif name in _MOVEMENT:
        flops = 0.0
    else:
        flops = float(out_elems)
        acc["unknown_eqns"] += 1
    acc["flops"] += flops * mul
    acc["activation_bytes"] += out_bytes * mul
    acc["eqns"] += 1


def _closed_to_open(j):
    return j.jaxpr if hasattr(j, "jaxpr") and hasattr(j, "consts") else j


def _walk_jaxpr(jaxpr, mul: float, acc: dict,
                mesh_axes: Optional[Dict[str, int]] = None) -> None:
    fus = _fusion_stats(jaxpr)
    acc["fusible_eqns"] += fus[0]
    acc["fusion_groups"] += fus[1]
    acc["fusion_candidates"] += fus[2]
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "scan":
            length = int(eqn.params.get("length", 1))
            _walk_jaxpr(_closed_to_open(eqn.params["jaxpr"]),
                        mul * max(length, 1), acc, mesh_axes)
            continue
        if name == "while":
            _walk_jaxpr(_closed_to_open(eqn.params["body_jaxpr"]), mul, acc,
                        mesh_axes)
            _walk_jaxpr(_closed_to_open(eqn.params["cond_jaxpr"]), mul, acc,
                        mesh_axes)
            note = "while body priced for one trip (count unknowable)"
            if note not in acc["notes"]:
                acc["notes"].append(note)
            continue
        if name == "cond":
            branches = eqn.params.get("branches", ())
            best = None
            for b in branches:
                sub = _fresh_acc()
                _walk_jaxpr(_closed_to_open(b), mul, sub, mesh_axes)
                if best is None or sub["flops"] > best["flops"]:
                    best = sub
            if best is not None:
                for k, v in best.items():
                    if k == "notes":
                        acc["notes"].extend(n for n in v
                                            if n not in acc["notes"])
                    elif k == "collectives":
                        for verb, c in v.items():
                            acc[k][verb] = acc[k].get(verb, 0) + c
                    else:
                        acc[k] += v
            continue
        subs = list(_sub_jaxprs(eqn))
        if subs:                      # pjit / remat / custom_*_call bodies
            for s in subs:
                _walk_jaxpr(s, mul, acc, mesh_axes)
            continue
        _eqn_into(eqn, mul, acc, mesh_axes)


def _fresh_acc() -> dict:
    return {"flops": 0.0, "matmul_flops": 0.0, "transcendentals": 0,
            "activation_bytes": 0, "eqns": 0, "fusible_eqns": 0,
            "fusion_groups": 0, "fusion_candidates": 0, "unknown_eqns": 0,
            "collectives": {}, "comm_bytes": 0.0, "notes": []}


def _implied_spmd_comm(g: TracedGraph, acc: dict) -> None:
    """Price the gradient exchange XLA's SPMD partitioner inserts at
    compile time (invisible in the jaxpr): for a train graph on a mesh
    with a real ``dp`` axis, every ``dp``-replicated parameter's gradient
    is all-reduced over ``dp`` — or, when its optimizer states are
    ``dp``-partitioned (ZeRO-1), reduce-scattered into the sharded update
    with the new weight all-gathered back. Both move the same
    2(N-1)/N·B bytes; only the verb split differs. Deterministic: a pure
    function of the in-resource specs and the mesh axis sizes."""
    dp = (g.mesh_axes or {}).get("dp", 1)
    if g.kind != "train" or dp <= 1 or not g.in_specs:
        return
    zero1 = any(r == "state" and "dp" in _spec_axes(s)
                for r, s in zip(g.roles, g.in_specs))
    jaxpr = g.closed.jaxpr
    priced = 0
    for v, role, spec in zip(jaxpr.invars, g.roles, g.in_specs):
        if role != "param" or "dp" in _spec_axes(spec):
            continue                  # dp-sharded params exchange no grad
        b = _nbytes(v.aval)
        if not b:
            continue
        priced += 1
        if zero1:
            _comm_into("reduce_scatter", b, dp, 1.0, acc)
            _comm_into("all_gather", b, dp, 1.0, acc)
        else:
            _comm_into("all_reduce", b, dp, 1.0, acc)
    if priced:
        acc["notes"].append(
            f"implied SPMD gradient exchange priced for {priced} "
            f"parameter(s) over dp={dp}"
            + (" (zero1: reduce-scatter + all-gather)" if zero1 else
               " (all-reduce)"))


# -- liveness: peak resident device bytes ------------------------------------

def _donated_mask(g: TracedGraph) -> tuple:
    n = len(g.closed.jaxpr.invars)
    d = g.donated or ()
    return tuple(bool(d[i]) if i < len(d) else False for i in range(n))


def _inner_extra(eqn) -> int:
    """Transient scratch an eqn's sub-jaxprs (pjit/remat/scan/cond
    bodies) need beyond the eqn's own operands: the sub-graph's peak
    minus its invar bytes (those alias buffers already live in the
    enclosing frame). Counted once — residency is a max, never a sum
    over trips — so a scan body's scratch is NOT trip-multiplied."""
    extra = 0
    for sub in _sub_jaxprs(eqn):
        in_b = sum(_nbytes(v.aval) for v in sub.invars
                   if hasattr(v, "aval"))
        extra = max(extra, max(0, _open_jaxpr_peak(sub, ()) - in_b))
    return extra


def _open_jaxpr_peak(jaxpr, donated: tuple) -> int:
    """Last-use liveness scan over one (open) jaxpr, in bytes.

    Residency model, a deterministic pure function of the jaxpr:

    - non-donated invars are resident for the WHOLE call (the caller
      retains those buffers) — so are constvars (trace-time constants
      XLA materializes on device);
    - donated invars die after their last use (the donation credit —
      XLA may alias the buffer into an output);
    - each eqn's outputs are allocated while its inputs are still live
      (an executing kernel holds both), then freed after their own last
      use; jaxpr outvars live to the end of the call;
    - an eqn with sub-jaxprs additionally holds the sub-graph's
      transient scratch (:func:`_inner_extra`) while it runs.
    """
    n_eqns = len(jaxpr.eqns)
    last_use: Dict = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not _is_literal(v):
                last_use[v] = i
    for v in jaxpr.outvars:
        if not _is_literal(v):
            last_use[v] = n_eqns          # outputs survive the call
    fixed = sum(_nbytes(v.aval) for v in getattr(jaxpr, "constvars", ())
                if hasattr(v, "aval"))
    live: Dict = {}                       # var -> bytes, dies at last use
    for i, v in enumerate(jaxpr.invars):
        b = _nbytes(v.aval) if hasattr(v, "aval") else 0
        if i < len(donated) and donated[i]:
            live[v] = b
        else:
            fixed += b
    live_b = sum(live.values())
    peak = fixed + live_b
    for i, eqn in enumerate(jaxpr.eqns):
        out_b = sum(_nbytes(o.aval) for o in eqn.outvars
                    if hasattr(o, "aval"))
        peak = max(peak, fixed + live_b + out_b + _inner_extra(eqn))
        for o in eqn.outvars:
            if last_use.get(o, -1) > i:   # value someone later reads
                b = _nbytes(o.aval) if hasattr(o, "aval") else 0
                live[o] = b
                live_b += b
        for v in eqn.invars:
            if not _is_literal(v) and last_use.get(v) == i and v in live:
                live_b -= live.pop(v)
    return int(peak)


def peak_live_bytes(g: TracedGraph) -> int:
    """Deterministic peak live device bytes of one traced graph —
    args + consts + the maximal simultaneously-live eqn outputs under a
    donation-aware last-use liveness scan. Zero XLA compiles; same
    graph → same number, the property the MX709 budget gate relies
    on."""
    return _open_jaxpr_peak(g.closed.jaxpr, _donated_mask(g))


def _graph_param_bytes(g: TracedGraph) -> int:
    return sum(_nbytes(v.aval)
               for v, role in zip(g.closed.jaxpr.invars, g.roles)
               if role in ("param", "state") and hasattr(v, "aval"))


def _ladder_from_pairs(pairs) -> int:
    """THE ladder accounting, over ``(param_bytes, peak_bytes)`` pairs:
    parameters counted once (max — weights are shared across bucket
    executables), every graph's non-parameter residency summed. Shared
    by :func:`ladder_peak_bytes` (TracedGraphs) and
    :meth:`CostReport.ladder_peak_bytes` (priced rows) so the staging
    preflight and the banked proxy can never disagree."""
    pairs = list(pairs)
    if not pairs:
        return 0
    params = max(pb for pb, _ in pairs)
    rest = sum(max(0, peak - pb) for pb, peak in pairs)
    return int(params + rest)


def ladder_peak_bytes(graphs: List[TracedGraph]) -> int:
    """Conservative resident footprint of a whole bucket LADDER (one
    entry's graphs held on device at once): the parameter/state set
    counted ONCE (weights are shared across bucket executables) plus
    every bucket's non-parameter residency summed — each warmed bucket
    retains its own donated request buffers, outputs, and executable
    scratch. This is the number the serve staging preflight checks
    against ``MXTPU_HBM_BUDGET``: buckets execute one at a time, but
    they stay RESIDENT together."""
    return _ladder_from_pairs((_graph_param_bytes(g), peak_live_bytes(g))
                              for g in graphs)


def hbm_budget_bytes() -> Optional[int]:
    """``MXTPU_HBM_BUDGET`` in bytes, or ``None`` when unset — a
    re-export of :func:`~...util.hbm_budget_bytes` (the ONE budget read
    every gate shares) at the analysis surface."""
    from ...util import hbm_budget_bytes as _budget
    return _budget()


def _fmt_mib(n: int) -> str:
    return f"{n / 2**20:.1f} MiB"


def graph_cost(g: TracedGraph) -> GraphCost:
    """Price one :class:`~.trace.TracedGraph` — THE cost function every
    surface (``analysis.hlo.cost``, the MX707 pass, ``mxlint --cost``,
    the autotuner) shares, so they can never disagree."""
    jaxpr = g.closed.jaxpr
    acc = _fresh_acc()
    _walk_jaxpr(jaxpr, 1.0, acc, g.mesh_axes)
    _implied_spmd_comm(g, acc)
    param_bytes = input_bytes = 0
    for v, role in zip(jaxpr.invars, g.roles):
        if role in ("param", "state"):
            param_bytes += _nbytes(v.aval)
        elif role == "input":
            input_bytes += _nbytes(v.aval)
    output_bytes = sum(_nbytes(o.aval) for o in jaxpr.outvars
                       if hasattr(o, "aval"))
    return GraphCost(
        entry=g.entry, site=g.site, kind=g.kind,
        flops=acc["flops"], matmul_flops=acc["matmul_flops"],
        transcendentals=acc["transcendentals"],
        param_bytes=param_bytes, input_bytes=input_bytes,
        output_bytes=output_bytes,
        activation_bytes=int(acc["activation_bytes"]),
        peak_live_bytes=peak_live_bytes(g),
        eqns=acc["eqns"], fusible_eqns=acc["fusible_eqns"],
        fusion_groups=acc["fusion_groups"],
        fusion_candidates=acc["fusion_candidates"],
        unknown_eqns=acc["unknown_eqns"],
        collective_ops={k: int(round(v))
                        for k, v in sorted(acc["collectives"].items())},
        comm_bytes=float(acc["comm_bytes"]),
        notes=acc["notes"])


def cost_table(graphs: List[TracedGraph]) -> List[GraphCost]:
    return [graph_cost(g) for g in graphs]


@dataclass
class CostReport:
    """Cost rows for every traced graph of one entry, plus the derived
    headline metrics."""

    rows: List[GraphCost] = field(default_factory=list)
    skipped: List[str] = field(default_factory=list)

    @property
    def head(self) -> Optional[GraphCost]:
        """The costliest graph — for a bucketed serving model the largest
        bucket, for a trainer the step graph."""
        return max(self.rows, key=lambda r: r.flops) if self.rows else None

    def model_flops_per_step(self) -> float:
        """Derived model-FLOPs-per-step: the costliest graph's FLOPs (one
        executed step/call runs exactly one bucket's executable)."""
        return float(self.head.flops) if self.rows else 0.0

    def bytes_per_step(self) -> int:
        return int(self.head.bytes_per_step) if self.rows else 0

    def peak_live_bytes(self) -> int:
        """Deterministic peak live device bytes: the WORST graph's peak
        (one executed step/call runs one executable, so the largest
        bucket / the step graph sets the high-water mark)."""
        return max((int(r.peak_live_bytes) for r in self.rows), default=0)

    def ladder_peak_bytes(self) -> int:
        """Conservative whole-ladder resident footprint — the SAME
        :func:`_ladder_from_pairs` accounting as the module-level
        :func:`ladder_peak_bytes`, derived from the priced rows so
        callers holding only a CostReport need not re-trace."""
        return _ladder_from_pairs((r.param_bytes, r.peak_live_bytes)
                                  for r in self.rows)

    def comm_bytes_per_step(self) -> int:
        """Per-device collective communication bytes of the costliest
        graph (explicit collective prims + implied SPMD gradient
        exchange) — 0 for a single-device graph."""
        return int(self.head.comm_bytes) if self.rows else 0

    def collective_ops_per_step(self) -> int:
        return (sum(self.head.collective_ops.values())
                if self.rows else 0)

    def to_dict(self) -> dict:
        return {"rows": [r.to_dict() for r in self.rows],
                "model_flops_per_step": self.model_flops_per_step(),
                "bytes_per_step": self.bytes_per_step(),
                "peak_live_bytes": self.peak_live_bytes(),
                "ladder_peak_bytes": self.ladder_peak_bytes(),
                "comm_bytes_per_step": self.comm_bytes_per_step(),
                "collective_ops_per_step": self.collective_ops_per_step(),
                "skipped": list(self.skipped)}

    def text_table(self) -> str:
        """Aligned human table (``mxlint --hlo <t> --cost``)."""
        hdr = (f"{'graph':<40} {'kind':<6} {'MFLOP':>10} {'mm%':>5} "
               f"{'trans':>8} {'par KiB':>9} {'act KiB':>9} "
               f"{'peak KiB':>9} "
               f"{'io KiB':>9} {'comm KiB':>9} {'coll':>4} {'eqns':>5} "
               f"{'fus':>4} {'grp':>4} {'cand':>4}")
        lines = [hdr, "-" * len(hdr)]
        for r in self.rows:
            mm = 100.0 * r.matmul_flops / r.flops if r.flops else 0.0
            io_kib = (r.input_bytes + r.output_bytes) >> 10
            lines.append(
                f"{r.label:<40} {r.kind:<6} {r.flops / 1e6:>10.3f} "
                f"{mm:>5.1f} {r.transcendentals:>8} "
                f"{r.param_bytes >> 10:>9} {r.activation_bytes >> 10:>9} "
                f"{r.peak_live_bytes >> 10:>9} "
                f"{io_kib:>9} {int(r.comm_bytes) >> 10:>9} "
                f"{sum(r.collective_ops.values()):>4} "
                f"{r.eqns:>5} {r.fusible_eqns:>4} "
                f"{r.fusion_groups:>4} {r.fusion_candidates:>4}")
        if self.rows:
            lines.append(
                f"model_flops_per_step={self.model_flops_per_step():.6g} "
                f"bytes_per_step={self.bytes_per_step()} "
                f"peak_live_bytes={self.peak_live_bytes()} "
                f"ladder_peak_bytes={self.ladder_peak_bytes()} "
                f"comm_bytes_per_step={self.comm_bytes_per_step()}")
        for s in self.skipped:
            lines.append(f"note: skipped {s}")
        return "\n".join(lines)


def cost(model, sample_args=None, max_graphs: int = 8) -> CostReport:
    """Trace ``model`` (same dispatch as :func:`~..verify`: CompiledModel
    buckets, SymbolBlock signatures, ShardedTrainer step, HybridBlock,
    plain callable) and price every traced graph. Never XLA-compiles."""
    result = trace_entry(model, sample_args, max_graphs=max_graphs)
    return CostReport(rows=cost_table(result.graphs),
                      skipped=list(result.skipped))


# -- the informational MX707 pass -------------------------------------------

def _register():
    from .passes import register_hlo_pass

    @register_hlo_pass("hlo_cost",
                       describe="per-graph cost table (FLOPs, bytes, "
                                "transcendentals, fusion groups) as "
                                "informational MX707 rows — opt-in via "
                                "cost=True")
    def hlo_cost(ctx) -> None:
        """Informational per-graph cost rows (MX707). Opt-in: runs only
        when the pass context carries ``cost=True``
        (``verify(model, args, cost=True)`` / ``mxlint --hlo --cost``),
        so staging gates stay signal-only by default."""
        if not ctx.opt("cost", False):
            return
        for g in ctx.graphs:
            c = graph_cost(g)
            coll = (f", {int(c.comm_bytes) >> 10} KiB comm over "
                    f"{sum(c.collective_ops.values())} collective(s) "
                    f"({', '.join(f'{k}x{v}' for k, v in sorted(c.collective_ops.items()))})"
                    if c.collective_ops else "")
            ctx.diag(
                "MX707",
                f"cost: {c.flops:.6g} FLOPs ({c.matmul_flops:.6g} matmul), "
                f"{c.transcendentals} transcendental elems, "
                f"{c.param_bytes >> 10} KiB params, "
                f"{c.activation_bytes >> 10} KiB activations, "
                f"{c.peak_live_bytes >> 10} KiB peak live, "
                f"{c.input_bytes + c.output_bytes >> 10} KiB in+out, "
                f"{c.eqns} eqns, {c.fusible_eqns} fusible in "
                f"{c.fusion_groups} group(s) "
                f"({c.fusion_candidates} multi-op){coll}", g, severity="info")

    @register_hlo_pass("hlo_memory",
                       describe="peak live device memory exceeds "
                                "MXTPU_HBM_BUDGET (donation-aware jaxpr "
                                "liveness scan; whole bucket ladders "
                                "checked too), MX709")
    def hlo_memory(ctx) -> None:
        """The memory budget gate (MX709): each graph's deterministic
        ``peak_live_bytes`` — and each entry's summed bucket-ladder
        residency — must fit ``MXTPU_HBM_BUDGET`` (or the explicit
        ``hbm_budget_bytes`` pass option). Silent when no budget is
        configured, so un-budgeted runs and the clean fixtures see zero
        findings; with a budget set it is error severity and aborts
        serve staging exactly like MX701/MX705."""
        budget = ctx.opt("hbm_budget_bytes", None)
        if budget is None:
            budget = hbm_budget_bytes()
        if not budget:
            return
        by_entry: Dict[str, list] = {}
        for g in ctx.graphs:
            peak = peak_live_bytes(g)
            by_entry.setdefault(g.entry, []).append((g, peak))
            if peak > budget:
                ctx.diag(
                    "MX709",
                    f"peak live device memory {_fmt_mib(peak)} exceeds "
                    f"the HBM budget {_fmt_mib(int(budget))} "
                    f"(MXTPU_HBM_BUDGET): this graph cannot fit on the "
                    "chip — shrink the batch/bucket geometry, enable "
                    "remat, or raise the budget", g, severity="error")
        for entry, rows in by_entry.items():
            if len(rows) < 2 or any(p > budget for _, p in rows):
                continue          # per-graph findings already tell the story
            ladder = _ladder_from_pairs(          # peaks already scanned
                (_graph_param_bytes(g), p) for g, p in rows)
            if ladder > budget:
                ctx.diag(
                    "MX709",
                    f"bucket ladder holds {_fmt_mib(ladder)} resident "
                    f"across {len(rows)} warmed bucket(s) — over the "
                    f"HBM budget {_fmt_mib(int(budget))} even though "
                    "every bucket fits alone (weights counted once, "
                    "per-bucket buffers summed): trim the bucket table "
                    "or raise the budget",
                    node=f"{entry}[ladder]", severity="error")


_register()
