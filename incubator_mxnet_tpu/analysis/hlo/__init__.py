"""``mx.analysis.hlo`` — compiled-graph inspection passes (MX7xx).

mxlint (MX2xx–MX6xx) sees Python ASTs; the telemetry compile ledger sees
recompiles only after they burn device wall-time. This layer closes the
gap: it traces any model entry point to the artifact the TPU actually
runs — a jaxpr plus (lazily) lowered StableHLO — and inspects it *before
the first device step*. Entry points: a live ``HybridBlock``, a
``serve.CompiledModel`` (per bucket), a ``SymbolBlock`` export artifact
(per baked signature), a ``parallel.ShardedTrainer`` step, or any plain
callable with sample args.

Programmatic entry point (called by ``serve.ModelRegistry.load`` and
``benchmark/serve_bench.py`` at staging time)::

    report = mx.analysis.hlo.verify(model, sample_args)
    report.raise_if_errors()

The same traced graphs feed the device-blind cost model
(:mod:`~.cost`): ``mx.analysis.hlo.cost(model, sample_args)`` prices
FLOPs / bytes / transcendentals / fusion groups per graph: counts from
a CPU trace that rank graphs, never device metrics. ``verify(...,
cost=True)`` surfaces the table as informational MX707 diagnostics.

CLI::

    python -m tools.mxlint --hlo all --format=json
    python -m tools.mxlint --hlo bert_encoder
    python -m tools.mxlint --hlo my_pkg.my_mod:factory
    python -m tools.mxlint --hlo bert --cost

Pass registry (the compiled-graph sibling of ``analysis/passes.py``):
``HLO_PASSES``, extendable with :func:`register_hlo_pass`.
"""
from __future__ import annotations

from typing import Optional, Sequence

from ..diagnostics import Report
from .passes import (  # noqa: F401
    HLO_PASSES, HloPassContext, list_hlo_passes, register_hlo_pass,
    run_hlo_passes,
)
from .trace import (  # noqa: F401
    TracedGraph, TraceResult, trace_entry, walk_eqns,
)
from .cost import (  # noqa: F401  (importing registers hlo_cost/hlo_memory)
    CostReport, GraphCost, cost, cost_table, graph_cost, hbm_budget_bytes,
    ladder_peak_bytes, peak_live_bytes,
)
from .quant import (  # noqa: F401  (importing registers hlo_quant)
    QuantGraphStats, quant_graph_stats,
)

__all__ = ["verify", "verify_trace", "trace_entry", "TracedGraph",
           "TraceResult", "HLO_PASSES", "register_hlo_pass",
           "list_hlo_passes", "run_hlo_passes", "walk_eqns",
           "cost", "cost_table", "graph_cost", "CostReport", "GraphCost",
           "peak_live_bytes", "ladder_peak_bytes", "hbm_budget_bytes",
           "quant_graph_stats", "QuantGraphStats"]


def verify_trace(result: TraceResult, *,
                 passes: Optional[Sequence[str]] = None,
                 const_limit_bytes: int = 1 << 20,
                 donation_min_bytes: int = 1 << 16,
                 hbm_budget_bytes: Optional[int] = None,
                 cost: bool = False,
                 quant: bool = False) -> Report:
    """Run the MX7xx passes over an already-traced entry and fold in the
    tracer's own diagnostics/coverage notes — the shared second half of
    :func:`verify`, exposed so a caller that needs the
    :class:`~.trace.TraceResult` for something else (``mxlint --cost``
    prices the same graphs) traces exactly once."""
    report = run_hlo_passes(result.graphs, names=passes,
                            const_limit_bytes=const_limit_bytes,
                            donation_min_bytes=donation_min_bytes,
                            hbm_budget_bytes=hbm_budget_bytes,
                            cost=cost, quant=quant)
    for d in result.diags:
        report.add(d)
    report.skipped.extend(result.skipped)
    return report


def verify(model, sample_args=None, *,
           passes: Optional[Sequence[str]] = None,
           max_graphs: int = 8,
           const_limit_bytes: int = 1 << 20,
           donation_min_bytes: int = 1 << 16,
           hbm_budget_bytes: Optional[int] = None,
           cost: bool = False,
           quant: bool = False) -> Report:
    """Trace ``model`` (every bucket/signature/call site, capped at
    ``max_graphs``) and run the registered MX7xx passes; returns the
    merged :class:`~..diagnostics.Report`.

    ``sample_args``: one tuple of arrays (one call site) or a list of
    tuples (several call sites — MX706 compares their lowered
    signatures). Optional for entries that carry their own signatures
    (a hybridized block with a recorded forward, a CompiledModel's
    bucket table, an export artifact). A block that has never run a
    forward is warmed with one eager call on the first sample site —
    the same signature-establishing contract as
    ``CompiledModel(example_args=...)`` — which mutates the block
    (hybridize + deferred parameter init).

    ``cost=True`` additionally runs the informational ``hlo_cost`` pass,
    appending one MX707 info row per graph (the
    :func:`~.cost.graph_cost` table in diagnostic form).

    ``hbm_budget_bytes`` overrides the ``MXTPU_HBM_BUDGET`` env read of
    the MX709 memory pass (``None`` = read the env; unset env = the
    pass is silent).

    ``quant=True`` additionally emits the MX710 informational
    quantized-region summary per quantized graph. The MX711–MX715
    precision-flow checks themselves are always on — they fire only on
    graphs that actually contain quantize boundaries or int8 matmuls, so
    float models are unaffected. ``serve.ModelRegistry`` stages every
    version with ``quant=True``: an un-calibrated or silently-promoted
    int8 build is rejected before its first device step while the active
    version keeps serving.
    """
    return verify_trace(trace_entry(model, sample_args,
                                    max_graphs=max_graphs),
                        passes=passes, const_limit_bytes=const_limit_bytes,
                        donation_min_bytes=donation_min_bytes,
                        hbm_budget_bytes=hbm_budget_bytes, cost=cost,
                        quant=quant)
