"""MX71x — dtype-flow verification of quantized compiled graphs.

``quantization.quantize_net``/``quantize_model`` swap float layers for
int8 twins, but the property that matters — *the compute the TPU runs is
actually int8* — only exists in the compiled graph. Source-level checks
cannot see a ``jnp.matmul`` that silently promoted its int8 operand back
to f32, or a calibration range that lowered to an online ``reduce_max``
instead of a baked constant. This pass family walks the traced jaxpr
propagating a per-var compute-dtype lattice with quantize/dequantize
boundary detection (a quantize boundary is a ``convert_element_type`` to
int8; a dequantize boundary is an integer→float convert) and proves the
declared-int8 regions hold:

==========  =============================================================
``MX710``   informational quantized-region summary (boundaries, int8
            matmuls, bytes saved vs churned) — opt-in via ``quant=True``
``MX711``   silent f32 promotion inside a declared-int8 region: an int8
            tensor is widened back to float and feeds a float matmul
``MX712``   quantize boundary with no calibration provenance: the range
            is an online min/max reduction over the data itself
``MX713``   q/dq pairing hazard: re-quantization with no intervening
            compute (double quantization / scale-mismatch round trip)
``MX714``   additive reduction accumulating in int8 (must widen)
``MX715``   boundary churn: q/dq convert traffic exceeds the f32 bytes
            the int8 compute saves (priced via ``analysis.hlo.cost``)
==========  =============================================================

Detection runs over a *flattened* view of each graph: transparent call
primitives (``jit`` — every ``jnp.clip``/``jnp.round`` helper lowers to
one — plus custom-derivative wrappers) are inlined with var
substitution, so dataflow walks cross them; control-flow bodies
(scan/while/cond) stay separate scopes, analyzed independently.

Every detection is a deterministic pure function of the jaxpr, so the
pass is safe at ``ModelRegistry`` staging time: an un-calibrated or
silently-promoted quantized version is rejected before its first device
step while the active version keeps serving. Float graphs have no
quantize boundaries and produce zero findings — the pass costs one jaxpr
walk on the f32 zoo and never fires there.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as onp

from .passes import _is_literal, _np_dtype, register_hlo_pass
from .trace import TracedGraph, _jaxprs_in

__all__ = ["quant_graph_stats", "QuantGraphStats"]

#: matmul-shaped compute — the eqns a declared-int8 region exists to feed
_MATMUL_PRIMS = frozenset({"dot_general", "conv_general_dilated"})

#: additive reductions whose accumulator width is the accuracy hazard
#: MX714 guards (max/min are order statistics — int8-safe)
_ACCUM_REDUCE_PRIMS = frozenset({
    "reduce_sum", "cumsum", "cumlogsumexp", "reduce_window_sum",
    "add_any", "reduce_prod", "cumprod",
})

#: the elementwise chain a quantize op lowers to between the f32 data and
#: the int8 convert (scale-divide, round, clamp) — followed backwards by
#: the MX712 provenance walk to separate the data path from the scale path
_Q_CHAIN_PRIMS = frozenset({
    "div", "mul", "add", "sub", "max", "min", "clamp", "round",
    "nextafter", "convert_element_type", "reshape", "broadcast_in_dim",
})

#: call-shaped primitives inlined by the flattener — one sub-jaxpr,
#: invars/outvars align one-to-one with the sub-jaxpr's
_TRANSPARENT_CALLS = frozenset({
    "jit", "closed_call", "call", "remat2", "custom_jvp_call",
    "custom_vjp_call",
})


def _dt(v):
    aval = getattr(v, "aval", None)
    return _np_dtype(aval.dtype) if hasattr(aval, "dtype") else None


def _is_int8(d) -> bool:
    return d is not None and d.kind in ("i", "u") and d.itemsize == 1


def _is_int(d) -> bool:
    return d is not None and d.kind in ("i", "u")


def _is_float(d) -> bool:
    return d is not None and d.kind in ("f", "c")


def _nbytes_var(v) -> int:
    from .cost import _nbytes
    aval = getattr(v, "aval", None)
    return _nbytes(aval) if aval is not None else 0


def _shape_elems(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    if not shape:
        return 1
    return int(onp.prod(shape, dtype=onp.int64))


# ---------------------------------------------------------------------------
# flattened dataflow view
# ---------------------------------------------------------------------------

class _FlatEqn:
    """One equation of the flattened graph: call boundaries dissolved,
    invars substituted back to their producing scope's vars."""
    __slots__ = ("name", "invars", "outvars", "params")

    def __init__(self, name, invars, outvars, params):
        self.name = name
        self.invars = invars
        self.outvars = outvars
        self.params = params


class _PVar:
    """Per-inline-instance proxy for an equation output. jax caches and
    reuses sub-jaxpr objects (two ``jnp.clip`` calls share one jaxpr),
    so the original outvars are NOT unique across inline instances —
    every flattened equation gets fresh proxies carrying the aval."""
    __slots__ = ("aval",)

    def __init__(self, aval):
        self.aval = aval


def _resolve(v, env):
    while not _is_literal(v) and v in env:
        v = env[v]
    return v


def _flatten_into(jaxpr, env, out: List[_FlatEqn], scopes: List) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        subs = [s for val in eqn.params.values() for s in _jaxprs_in(val)]
        if name in _TRANSPARENT_CALLS and len(subs) == 1 \
                and len(subs[0].invars) == len(eqn.invars):
            sub = subs[0]
            for sv, ov in zip(sub.invars, eqn.invars):
                env[sv] = ov if _is_literal(ov) else _resolve(ov, env)
            _flatten_into(sub, env, out, scopes)
            for sv, ov in zip(sub.outvars, eqn.outvars):
                env[ov] = sv if _is_literal(sv) else _resolve(sv, env)
            continue
        ivs = [v if _is_literal(v) else _resolve(v, env)
               for v in eqn.invars]
        ovs = []
        for o in eqn.outvars:
            p = _PVar(getattr(o, "aval", None))
            env[o] = p
            ovs.append(p)
        out.append(_FlatEqn(name, ivs, ovs, eqn.params))
        scopes.extend(subs)     # opaque control-flow bodies: own scopes


def _flat_scopes(jaxpr):
    """Yield one flattened equation list per dataflow scope: the top
    level (with transparent calls inlined), then each control-flow body
    reachable from it, recursively. Vars never cross scopes."""
    pending = [jaxpr]
    while pending:
        j = pending.pop(0)
        out: List[_FlatEqn] = []
        _flatten_into(j, {}, out, pending)
        yield out


def _producer_map(eqns: List[_FlatEqn]) -> Dict:
    prod = {}
    for eqn in eqns:
        for o in eqn.outvars:
            prod[o] = eqn
    return prod


def _is_q_convert(eqn) -> bool:
    """A quantize boundary: convert_element_type float → int8, tensor
    shaped (scalar converts are range/bound arithmetic, not data)."""
    return (eqn.name == "convert_element_type"
            and _is_int8(_dt(eqn.outvars[0]))
            and _is_float(_dt(eqn.invars[0]))
            and _shape_elems(eqn.outvars[0]) > 1)


def _is_dq_convert(eqn) -> bool:
    """A dequantize boundary: convert_element_type integer → float,
    tensor shaped."""
    return (eqn.name == "convert_element_type"
            and _is_float(_dt(eqn.outvars[0]))
            and _is_int(_dt(eqn.invars[0]))
            and _shape_elems(eqn.outvars[0]) > 1)


def _int_matmul_operands(eqn) -> List:
    if eqn.name not in _MATMUL_PRIMS:
        return []
    ops = [v for v in eqn.invars[:2] if _is_int8(_dt(v))]
    return ops if ops else []


class QuantGraphStats:
    """Boundary census of one traced graph (every dataflow scope):
    quantize/dequantize converts, int8 matmuls, and the byte economics
    MX715 gates on — all via the same ``_nbytes`` element-width pricing
    ``analysis.hlo.cost`` uses, so the churn verdict and the banked
    proxy can never disagree."""

    def __init__(self):
        self.q_converts: List[_FlatEqn] = []
        self.dq_converts: List[_FlatEqn] = []
        self.int_matmuls: List[_FlatEqn] = []
        self.wasted_boundaries: List[_FlatEqn] = []  # not matmul-adjacent
        self.saved_bytes: int = 0
        self.churn_bytes: int = 0

    @property
    def quantized(self) -> bool:
        return bool(self.q_converts or self.int_matmuls)


def _scope_stats(eqns: List[_FlatEqn], prod, stats: QuantGraphStats):
    q_here, dq_here, mm_here = [], [], []
    for eqn in eqns:
        if _is_q_convert(eqn):
            q_here.append(eqn)
        elif _is_dq_convert(eqn):
            dq_here.append(eqn)
        ops = _int_matmul_operands(eqn)
        if ops:
            mm_here.append(eqn)
            stats.saved_bytes += 3 * sum(_nbytes_var(v) for v in ops)
    stats.q_converts += q_here
    stats.dq_converts += dq_here
    stats.int_matmuls += mm_here
    if not (q_here or dq_here):
        return
    # integer-typed dataflow closure around the int8 matmuls: backward
    # from their int8 operands, forward from their outputs — a boundary
    # convert outside that closure moves bytes for no int8 compute
    useful = set()
    back = [v for e in mm_here for v in _int_matmul_operands(e)]
    seen = set()
    while back:
        v = back.pop()
        if _is_literal(v) or id(v) in seen:
            continue
        seen.add(id(v))
        e = prod.get(v)
        if e is None:
            continue
        if _is_q_convert(e):
            useful.add(id(e))
            continue                              # float side: stop
        if all(_is_int(_dt(o)) for o in e.outvars):
            back.extend(iv for iv in e.invars if not _is_literal(iv))
    consumers: Dict = {}
    for eqn in eqns:
        for iv in eqn.invars:
            if not _is_literal(iv):
                consumers.setdefault(id(iv), []).append(eqn)
    fwd = [o for e in mm_here for o in e.outvars]
    seen_f = set()
    while fwd:
        v = fwd.pop()
        if id(v) in seen_f:
            continue
        seen_f.add(id(v))
        for e in consumers.get(id(v), ()):
            if _is_dq_convert(e):
                useful.add(id(e))
                continue                          # float side: stop
            if all(_is_int(_dt(o)) for o in e.outvars):
                fwd.extend(e.outvars)
    for eqn in q_here + dq_here:
        if id(eqn) in useful:
            continue
        stats.wasted_boundaries.append(eqn)
        stats.churn_bytes += (_nbytes_var(eqn.invars[0])
                              + _nbytes_var(eqn.outvars[0]))


def quant_graph_stats(g: TracedGraph) -> QuantGraphStats:
    """Census the quantization boundaries of one traced graph.

    ``saved_bytes``: 3× the int8 operand bytes of every int8 matmul/conv
    (the same operands at f32 would be 4× the width — weights and
    activations stream from HBM at a quarter the traffic).
    ``churn_bytes``: in+out bytes of every q/dq boundary convert NOT
    connected to an int8 matmul through an integer-typed dataflow chain —
    a quantize round trip that feeds no int8 compute moves bytes for
    nothing. A clean quantized layer (q → int8 dot → dq) contributes to
    ``saved_bytes`` only.
    """
    stats = QuantGraphStats()
    for eqns in _flat_scopes(g.closed.jaxpr):
        _scope_stats(eqns, _producer_map(eqns), stats)
    return stats


# ---------------------------------------------------------------------------
# per-detection walks (each over one flattened scope)
# ---------------------------------------------------------------------------

def _silent_promotions(eqns: List[_FlatEqn]) -> List[Tuple]:
    """MX711: int8 values widened back to float that reach a float-typed
    matmul/conv. Taint starts at int8→float converts, propagates through
    float-typed non-matmul eqns, and dies at any convert to a non-float
    dtype — so a bias re-encode (int8 → f32 → int32) or a legitimate
    dequantize→re-quantize between layers never taints the next layer's
    int8 dot."""
    tainted: set = set()
    hits = []
    for eqn in eqns:
        if eqn.name in _MATMUL_PRIMS:
            out_d = _dt(eqn.outvars[0])
            if _is_float(out_d) and any(
                    not _is_literal(v) and id(v) in tainted
                    for v in eqn.invars[:2]):
                hits.append((eqn, out_d))
            continue                 # matmul output is fresh, not tainted
        if (eqn.name == "convert_element_type"
                and _is_int8(_dt(eqn.invars[0]))
                and _is_float(_dt(eqn.outvars[0]))):
            tainted.add(id(eqn.outvars[0]))
            continue
        if any(not _is_literal(v) and id(v) in tainted
               for v in eqn.invars):
            for o in eqn.outvars:
                if _is_float(_dt(o)):
                    tainted.add(id(o))
    return hits


def _online_range_boundaries(eqns: List[_FlatEqn], prod) -> List:
    """MX712: quantize boundaries whose scale derives from a min/max
    reduction over the tensor being quantized (the ``quantize_v2`` online
    branch) instead of a baked calibrated constant. The walk follows the
    quantize lowering chain backwards from the int8 convert, splitting
    each step into the (non-scalar) data path and the (scalar) scale
    operands — seeing through the broadcast jnp inserts around a scalar
    scale — then closes over the scale operands' ancestry looking for a
    reduction over non-scalar input."""
    def _scalar_root(v, depth=4):
        if _is_literal(v):
            return None
        if _shape_elems(v) <= 1:
            return v
        e = prod.get(v)
        if (depth > 0 and e is not None and e.name in
                ("broadcast_in_dim", "reshape", "convert_element_type")):
            return _scalar_root(e.invars[0], depth - 1)
        return None

    hits = []
    for eqn in eqns:
        if not _is_q_convert(eqn):
            continue
        scale_roots: List = []
        frontier = [eqn.invars[0]]
        for _ in range(16):
            if not frontier:
                break
            v = frontier.pop()
            if _is_literal(v):
                continue
            e = prod.get(v)
            if e is None or e.name not in _Q_CHAIN_PRIMS:
                continue
            data = []
            for iv in e.invars:
                if _is_literal(iv):
                    continue
                root = _scalar_root(iv)
                if root is not None:
                    scale_roots.append(root)
                else:
                    data.append(iv)
            frontier += data[:1]
        walk = list(scale_roots)
        seen = set()
        online = False
        while walk and not online:
            v = walk.pop()
            if _is_literal(v) or id(v) in seen:
                continue
            seen.add(id(v))
            e = prod.get(v)
            if e is None:
                continue
            if (e.name in ("reduce_max", "reduce_min", "reduce_sum")
                    and any(_shape_elems(iv) > 1 for iv in e.invars
                            if not _is_literal(iv))):
                online = True
                break
            walk.extend(iv for iv in e.invars if not _is_literal(iv))
        if online:
            hits.append(eqn)
    return hits


def _requantize_pairs(eqns: List[_FlatEqn], prod) -> List:
    """MX713: a quantize boundary whose backward slice — followed through
    boundary converts and elementwise/movement glue but stopped at any
    matmul/conv/reduction (real compute) — contains another quantize
    boundary: the tensor went q→dq→q with nothing computed in between,
    i.e. double quantization / a redundant round trip whose two scales
    can silently disagree."""
    stop = _MATMUL_PRIMS | _ACCUM_REDUCE_PRIMS | frozenset(
        {"reduce_max", "reduce_min", "reduce_window_max",
         "reduce_window_min"})
    hits = []
    for eqn in eqns:
        if not _is_q_convert(eqn):
            continue
        seen = set()
        walk = [v for v in eqn.invars if not _is_literal(v)]
        found = None
        for _ in range(256):
            if not walk or found is not None:
                break
            v = walk.pop()
            if id(v) in seen:
                continue
            seen.add(id(v))
            e = prod.get(v)
            if e is None or e.name in stop:
                continue
            if _is_q_convert(e):
                found = e
                continue
            walk.extend(iv for iv in e.invars if not _is_literal(iv))
        if found is not None:
            hits.append((eqn, found))
    return hits


def _narrow_accumulations(eqns: List[_FlatEqn]) -> List:
    """MX714: additive reductions whose accumulator dtype is int8."""
    return [eqn for eqn in eqns
            if eqn.name in _ACCUM_REDUCE_PRIMS
            and _is_int8(_dt(eqn.outvars[0]))]


# ---------------------------------------------------------------------------
# the registered pass
# ---------------------------------------------------------------------------

@register_hlo_pass("hlo_quant",
                   describe="dtype-flow precision propagation over "
                            "quantized graphs: silent f32 promotion, "
                            "calibration provenance, q/dq pairing, int8 "
                            "accumulation, boundary churn, MX710-MX715")
def hlo_quant(ctx) -> None:
    """The MX71x family. Auto-detecting: a graph with no quantize
    boundary and no int8 matmul is skipped after one census walk, so the
    f32 zoo and every existing caller see zero findings at default
    options. ``quant=True`` (``verify(..., quant=True)``, the
    ``ModelRegistry`` staging gate) additionally emits the MX710
    informational region summary for quantized graphs."""
    emit_summary = bool(ctx.opt("quant", False))
    for g in ctx.graphs:
        scopes = list(_flat_scopes(g.closed.jaxpr))
        stats = QuantGraphStats()
        prods = [_producer_map(eqns) for eqns in scopes]
        for eqns, prod in zip(scopes, prods):
            _scope_stats(eqns, prod, stats)
        if not stats.quantized:
            continue
        n711 = n712 = n713 = 0
        for eqns, prod in zip(scopes, prods):
            for eqn, out_d in _silent_promotions(eqns)[:3]:
                n711 += 1
                ctx.diag(
                    "MX711",
                    f"'{eqn.name}' runs at {out_d.name} on an operand "
                    "that was quantized to int8 and silently widened "
                    "back to float: the matmul the int8 region exists "
                    "to feed left the MXU int8 path — keep the operand "
                    "int8 into the dot (preferred_element_type=int32) "
                    "and dequantize the accumulator instead", g,
                    op=eqn.name, severity="error")
            for eqn in _online_range_boundaries(eqns, prod)[:3]:
                n712 += 1
                ctx.diag(
                    "MX712",
                    "quantize boundary computes its range online "
                    "(min/max reduction over the data being quantized): "
                    "no calibration provenance backs the scale — every "
                    "step re-derives a different range and an outlier "
                    "batch silently reshapes the encoding; lower a "
                    "calibrated Observer range instead "
                    "(quantization.quantize_model)", g,
                    op=eqn.name, severity="error")
            for eqn, _prev in _requantize_pairs(eqns, prod)[:3]:
                n713 += 1
                ctx.diag(
                    "MX713",
                    "tensor is quantized twice with no intervening "
                    "compute (quantize → dequantize → quantize): the two "
                    "boundaries' scales can silently disagree and each "
                    "round trip loses precision — quantize once and keep "
                    "the int8 value", g, op=eqn.name, severity="error")
            for eqn in _narrow_accumulations(eqns)[:3]:
                ctx.diag(
                    "MX714",
                    f"'{eqn.name}' accumulates in int8: an 8-bit "
                    "accumulator overflows after ~2 terms at full scale "
                    "— softmax/normalization/mean reductions over "
                    "quantized values must widen to int32 or float "
                    "before reducing", g, op=eqn.name, severity="warning")
        if stats.churn_bytes > stats.saved_bytes:
            ctx.diag(
                "MX715",
                f"quantization boundary churn: {stats.churn_bytes} bytes "
                f"of q/dq convert traffic not adjacent to any int8 "
                f"matmul vs {stats.saved_bytes} bytes saved by "
                f"{len(stats.int_matmuls)} int8 matmul(s) — the "
                "quantized build moves more bytes than it saves "
                "(an anti-optimization): drop the unused boundaries or "
                "quantize the compute they were meant to feed", g,
                severity="warning")
        if emit_summary:
            ctx.diag(
                "MX710",
                f"quantized region summary: {len(stats.q_converts)} "
                f"quantize boundary(ies), {len(stats.dq_converts)} "
                f"dequantize boundary(ies), {len(stats.int_matmuls)} "
                f"int8 matmul(s); ~{stats.saved_bytes} bytes/step saved "
                f"vs {stats.churn_bytes} bytes boundary churn"
                + (f"; {n711 + n712 + n713} precision-flow error(s)"
                   if n711 + n712 + n713 else ""), g, severity="info")
