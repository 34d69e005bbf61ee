#!/usr/bin/env python
"""mxlint — static analysis CLI over models, examples, symbol JSON, and
compiled graphs.

Reference counterpart: the graph sanity MXNet ran implicitly inside
``nnvm::Graph`` passes, surfaced the way modern stacks do it (TVM's pass
infra, clang-tidy): one command, stable diagnostic codes, non-zero exit on
findings::

    python -m tools.mxlint                       # models + examples (default)
    python -m tools.mxlint path/to/file.py dir/  # AST tracer-leak lint (MX2xx)
    python -m tools.mxlint net-symbol.json       # graph passes (MX0xx/MX1xx)
    python -m tools.mxlint layout.json           # sharding table (MX3xx)
    python -m tools.mxlint incubator_mxnet_tpu.models.bert   # dotted module
    python -m tools.mxlint --hlo all             # MX7xx over models.SERVE_SPECS
    python -m tools.mxlint --hlo bert_encoder    # one serving family
    python -m tools.mxlint --hlo pkg.mod:factory # custom entry point
    python -m tools.mxlint --hlo bert --cost     # + per-graph cost table
    python -m tools.mxlint --concurrency         # MX8xx over the package
    python -m tools.mxlint --concurrency dir/    # ... or given targets
    python -m tools.mxlint --distributed         # MX9xx over the package
    python -m tools.mxlint --distributed dir/    # ... or given targets
    python -m tools.mxlint --format=json ...     # one JSON finding per line

Python targets get the pure-AST JAX-pitfall lint (no import of the linted
code); ``.json`` targets are loaded as Symbols and run through the
``graph_verify`` + ``infer_shapes`` passes (shape pass auto-skips when the
graph needs input shapes) — unless the file is a sharding table (a top-level
``"mesh"`` key: ``{"mesh": {axis: size}, "rules": [[pattern, [axes...]]],
"params": {name: [shape]}}``), which runs the sharding-consistency pass
instead.

``--hlo`` targets trace the *compiled* graph (jaxpr/StableHLO) and run the
MX7xx passes: a serving-family name from ``models.SERVE_SPECS``, ``all``
(every family), or ``module:factory`` where the zero-arg factory returns a
traceable entry (HybridBlock / CompiledModel / SymbolBlock / callable) or a
``(entry, sample_args)`` tuple.

``--concurrency`` runs the MX8xx race/deadlock passes
(``mx.analysis.concurrency``) over the given Python targets — default:
the installed ``incubator_mxnet_tpu`` package — as ONE merged model, so
the MX802 lock-acquisition graph spans every module. It replaces the
per-file AST families for those targets (the two lint modes answer
different questions; run both commands to get both).

``--distributed`` runs the MX9xx SPMD-divergence passes
(``mx.analysis.distributed``) over the given Python targets — default:
the installed ``incubator_mxnet_tpu`` package. MX901–MX904 are source
passes (host-conditional collectives, unelected writes, import-frozen
world sizes, cross-host RNG); MX905 (cross-bucket collective-schedule
divergence) runs with the compiled-graph passes under ``--hlo``.

``--format=json`` emits one finding per line
(``{"file", "line", "node", "code", "severity", "message", "pass",
"op"}``) on stdout — CI annotates from it instead of grepping — with the
summary on stderr. ``file``/``line`` are filled only for path-shaped
provenance; graph findings (MX0xx/MX7xx) carry their location in
``node``. Exit status: 0 clean, 1 error diagnostics (``--strict``:
warnings too), 2 bad invocation.
"""
from __future__ import annotations

import argparse
import importlib.util
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
# Same as tools/gen_docs.py: linting traces graphs and never needs the chip,
# which one process at a time may hold — pin the cpu before any backend
# starts, so a lint run beside a training job cannot take it.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

DEFAULT_TARGETS = ("incubator_mxnet_tpu/models", "examples")


def _resolve_module(name: str):
    """Dotted module name -> file or package directory to lint."""
    try:
        spec = importlib.util.find_spec(name)
    except (ImportError, ModuleNotFoundError, ValueError):
        return None
    if spec is None:
        return None
    if spec.submodule_search_locations:
        return list(spec.submodule_search_locations)[0]
    return spec.origin


class _TableMesh:
    """Axis-name/size view of a mesh declaration — the sharding pass only
    consults ``axis_names`` and ``shape``, so a layout file can be linted
    without claiming real devices."""

    def __init__(self, axes):
        self.axis_names = tuple(axes)
        self.shape = dict(axes)


def _lint_sharding_json(path: str, payload: dict, analysis):
    from jax.sharding import PartitionSpec

    def _entry(e):
        return tuple(e) if isinstance(e, list) else e

    rules = [(pat, PartitionSpec(*[_entry(e) for e in spec]))
             for pat, spec in payload.get("rules", ())]
    try:
        from incubator_mxnet_tpu.parallel.sharding import ShardingRules
        table = ShardingRules(rules)
    except Exception as e:  # unparseable regex etc.
        report = analysis.Report()
        report.add(analysis.Diagnostic(
            "MX301", f"sharding table failed to load: "
            f"{type(e).__name__}: {e}", node=path, pass_name="sharding"))
        return report
    params = {k: tuple(v) for k, v in payload.get("params", {}).items()}
    return analysis.check_sharding(table, _TableMesh(payload["mesh"]),
                                   params or None)


def _lint_json(path: str, analysis):
    import json

    try:
        with open(path) as f:
            payload = json.load(f)
        is_table = isinstance(payload, dict) and "mesh" in payload
    except Exception as e:
        report = analysis.Report()
        report.add(analysis.Diagnostic(
            "MX007", f"not valid JSON: {type(e).__name__}: {e}",
            node=path, pass_name="graph_verify"))
        return report
    if is_table:
        return _lint_sharding_json(path, payload, analysis)
    from incubator_mxnet_tpu import symbol as S
    try:
        sym = S._symbol_from_payload(payload)
    except Exception as e:
        report = analysis.Report()
        report.add(analysis.Diagnostic(
            "MX007", f"symbol JSON failed to load: {type(e).__name__}: {e}",
            node=path, pass_name="graph_verify"))
        return report
    return analysis.verify(sym, passes=["graph_verify", "infer_shapes"])


class _HloTargetError(Exception):
    """Bad ``--hlo`` invocation (unknown family, unloadable factory) —
    distinct from exceptions raised INSIDE a user's factory, which
    propagate with their own traceback."""


def _hlo_expand(targets, quantized=False):
    """``--hlo`` target list → [(label, entry, sample_args)]; families
    come from models.SERVE_SPECS, ``all`` expands to every family,
    ``module:factory`` is imported and called. ``quantized=True``
    resolves families through ``models.quantized_smoke`` instead (the
    calibrated int8 zoo; ``all`` expands to ``models.QUANT_FAMILIES``)."""
    import importlib

    from incubator_mxnet_tpu import models

    out = []
    names = []
    for t in targets:
        if t == "all":
            names.extend(sorted(models.QUANT_FAMILIES if quantized
                                else models.SERVE_SPECS))
        else:
            names.append(t)
    for name in names:
        if ":" in name:
            mod_name, attr = name.rsplit(":", 1)
            try:
                factory = getattr(importlib.import_module(mod_name), attr)
            except (ImportError, AttributeError) as e:
                raise _HloTargetError(
                    f"cannot load --hlo factory {name!r}: "
                    f"{type(e).__name__}: {e}") from e
            made = factory()     # user code: its errors traceback as-is
            entry, sample = made if isinstance(made, tuple) else (made, None)
            out.append((name, entry, sample))
        elif name in models.SERVE_SPECS:
            if quantized and name not in models.QUANT_FAMILIES:
                raise _HloTargetError(
                    f"--hlo target {name!r} has no quantizable layers "
                    f"(quantized zoo: {sorted(models.QUANT_FAMILIES)})")
            try:
                smoke = (models.quantized_smoke(name) if quantized
                         else models.hlo_smoke(name))
                out.append((name + ("_int8" if quantized else ""),
                            smoke["compiled"], None))
            except KeyError as e:
                # hlo_smoke's own "no smoke model" KeyError means a
                # family was added to SERVE_SPECS without a smoke
                # branch — invocation-level drift. Any OTHER KeyError
                # is a real bug inside model construction: let it
                # traceback.
                if not (e.args and str(e.args[0]).startswith(
                        "no hlo smoke model")):
                    raise
                raise _HloTargetError(
                    f"--hlo target {name!r}: {e.args[0]}") from e
        else:
            raise _HloTargetError(
                f"--hlo target {name!r} is neither a serving family "
                f"({sorted(models.SERVE_SPECS)}), 'all', nor a "
                "module:factory")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="mxlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("targets", nargs="*",
                    help="*.py files, directories, *-symbol.json files, or "
                         "dotted module names (default: in-tree models + "
                         "examples)")
    ap.add_argument("--hlo", action="append", default=[], metavar="TARGET",
                    help="compiled-graph MX7xx passes over a serving "
                         "family from models.SERVE_SPECS, 'all', or "
                         "module:factory (repeatable)")
    ap.add_argument("--concurrency", action="store_true",
                    help="run the MX8xx race/deadlock passes "
                         "(mx.analysis.concurrency) over the Python "
                         "targets as one whole-package lock graph "
                         "(default target: the installed package)")
    ap.add_argument("--distributed", action="store_true",
                    help="run the MX9xx SPMD-divergence passes "
                         "(mx.analysis.distributed) over the Python "
                         "targets (default target: the installed "
                         "package); combine with --hlo for the MX905 "
                         "cross-bucket collective-schedule pass")
    ap.add_argument("--cost", action="store_true",
                    help="with --hlo: also print the per-graph cost table "
                         "(analysis.hlo.cost — FLOPs, bytes, "
                         "transcendentals, fusion groups; --format=json "
                         "emits one {\"kind\": \"cost\", ...} object per "
                         "graph) and run the informational MX707 pass")
    ap.add_argument("--quantized", action="store_true",
                    help="with --hlo: lint the calibrated int8 zoo instead "
                         "of the float one — families resolve through "
                         "models.quantized_smoke ('all' expands to "
                         "models.QUANT_FAMILIES) and the MX71x pass emits "
                         "its per-region MX710 quantization summaries")
    ap.add_argument("--format", choices=("text", "json"), default="text",
                    help="finding output: human text (default) or one "
                         "JSON object per line (summary on stderr)")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="suppress per-diagnostic text lines, print "
                         "summary only (--format=json findings always "
                         "stream)")
    ap.add_argument("--strict", action="store_true",
                    help="exit non-zero on warnings too (perf hazards like "
                         "MX201/MX302 gate the build)")
    args = ap.parse_args(argv)

    if args.cost and not args.hlo:
        print("mxlint: --cost needs at least one --hlo target "
              "(the cost table prices compiled graphs)", file=sys.stderr)
        return 2
    if args.quantized and not args.hlo:
        print("mxlint: --quantized needs at least one --hlo target "
              "(the quantized zoo is a compiled-graph surface)",
              file=sys.stderr)
        return 2

    import incubator_mxnet_tpu.analysis as analysis

    targets = args.targets
    if (args.concurrency or args.distributed) and not targets:
        targets = [os.path.join(REPO, "incubator_mxnet_tpu")]
    elif not targets and not args.hlo:
        targets = [os.path.join(REPO, t) for t in DEFAULT_TARGETS]
    py_targets, json_targets = [], []
    for t in targets:
        if t.endswith(".json"):
            if not os.path.exists(t):
                print(f"mxlint: no such file: {t}", file=sys.stderr)
                return 2
            json_targets.append(t)
        elif t.endswith(".py") or os.path.isdir(t):
            if not os.path.exists(t):
                print(f"mxlint: no such path: {t}", file=sys.stderr)
                return 2
            py_targets.append(t)
        else:
            resolved = _resolve_module(t)
            if resolved is None:
                print(f"mxlint: cannot resolve target {t!r} (not a path, "
                      "not an importable module)", file=sys.stderr)
                return 2
            py_targets.append(resolved)

    report = analysis.Report()
    if py_targets:
        if args.concurrency:
            # MX8xx wants ONE merged model over every target (the lock
            # graph is whole-package), not a per-file walk
            report.extend(analysis.concurrency.lint_paths(py_targets))
        if args.distributed:
            report.extend(analysis.distributed.lint_paths(py_targets))
        if not args.concurrency and not args.distributed:
            report.extend(analysis.lint_paths(py_targets))
    for jt in json_targets:
        report.extend(_lint_json(jt, analysis))

    n_hlo = 0
    cost_rows = []          # (target label, GraphCost) for --cost output
    if args.hlo:
        from incubator_mxnet_tpu.base import MXNetError
        try:
            hlo_targets = _hlo_expand(args.hlo, quantized=args.quantized)
        except _HloTargetError as e:
            print(f"mxlint: {e}", file=sys.stderr)
            return 2
        for label, entry, sample in hlo_targets:
            n_hlo += 1
            try:
                # one trace per target: the MX7xx passes and the cost
                # table price the SAME TracedGraph records, so the
                # diagnostics and the cost rows can never disagree
                traced = analysis.hlo.trace_entry(entry, sample)
                report.extend(analysis.hlo.verify_trace(
                    traced, cost=args.cost, quant=args.quantized))
                if args.cost:
                    cost_rows.extend(
                        (label, c) for c in
                        analysis.hlo.cost_table(traced.graphs))
            except MXNetError as e:
                # an untraceable factory product is a bad invocation, not
                # a finding — keep exit 2 distinct from exit 1
                print(f"mxlint: --hlo target {label!r} is not traceable: "
                      f"{e}", file=sys.stderr)
                return 2

    if cost_rows:
        if args.format == "json":
            import json as _json
            for label, c in cost_rows:
                row = c.to_dict()
                # the graph's infer/train kind must not mask the record
                # discriminator CI switches on
                row["graph_kind"] = row.pop("kind")
                print(_json.dumps({"kind": "cost", "target": label, **row}))
        else:
            from incubator_mxnet_tpu.analysis.hlo import CostReport
            by_target = {}
            for label, c in cost_rows:
                by_target.setdefault(label, []).append(c)
            for label, rows in by_target.items():
                print(f"== cost: {label} ==")
                print(CostReport(rows=rows).text_table())

    # json mode always streams its findings: -q only silences the human
    # text path, never the machine contract CI consumes
    if not args.quiet or args.format == "json":
        for d in report:
            if args.format == "json":
                import json as _json
                print(_json.dumps(d.as_dict()))
            else:
                print(d)
        if not args.quiet:
            for s in report.skipped:
                print(f"note: skipped {s}", file=sys.stderr)
    n_err, n_warn = len(report.errors), len(report.warnings)
    summary = (f"mxlint: {n_err} error(s), {n_warn} warning(s) across "
               f"{len(py_targets) + len(json_targets) + n_hlo} target(s)")
    print(summary, file=sys.stderr if args.format == "json" else sys.stdout)
    return 1 if (report.errors or (args.strict and report.warnings)) else 0


if __name__ == "__main__":
    sys.exit(main())
