#!/usr/bin/env python
"""Generate the API reference under docs/api/ from live introspection.

Reference counterpart: ``docs/python_docs/`` + the sphinx autodoc pipeline
(the reference renders its site from the same op registry this framework
keeps in ``ops/registry.py``). Here one command rewrites the markdown so the
docs can never drift from the code:

    python tools/gen_docs.py [--check]

``--check`` exits non-zero if the committed files are stale (CI hook).
"""
from __future__ import annotations

import argparse
import inspect
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
# Introspection never needs the chip, which one process at a time may hold:
# pin the cpu before any backend starts (same as tests/conftest).
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def _first_line(obj) -> str:
    # classes: own docstring only (cls.__doc__ doesn't walk the MRO, unlike
    # inspect.getdoc) — an inherited "pure function of its inputs" line says
    # nothing about the subclass
    doc = (obj.__doc__ if inspect.isclass(obj) else
           inspect.getdoc(obj)) or ""
    line = doc.split("\n", 1)[0].strip()
    return line.replace("|", "\\|")


def gen_ops() -> str:
    from incubator_mxnet_tpu.ops.registry import OPS

    by_module = {}
    seen = set()
    for name, opdef in sorted(OPS.items()):
        if id(opdef) in seen:
            continue
        seen.add(id(opdef))
        mod = opdef.module.rsplit(".", 1)[-1]
        by_module.setdefault(mod, []).append(opdef)

    out = ["# Operator reference", "",
           "Generated by `tools/gen_docs.py` from the live op registry "
           "(`ops/registry.py`) — run it after adding ops.", ""]
    total = len(seen)
    out.append(f"{total} unique operators across {len(by_module)} modules. "
               "Aliases (e.g. `_contrib_*`) route to the same entry.")
    for mod in sorted(by_module):
        out += ["", f"## ops.{mod}", "",
                "| Op | Aliases | Summary |", "|---|---|---|"]
        for opdef in sorted(by_module[mod], key=lambda o: o.name.lower()):
            aliases = ", ".join(f"`{a}`" for a in opdef.aliases) or "—"
            summary = _first_line(opdef.fn)  # already pipe-escaped
            out.append(f"| `{opdef.name}` | {aliases} | {summary} |")
        schemas = [o for o in by_module[mod] if o.schema is not None]
        for opdef in sorted(schemas, key=lambda o: o.name.lower()):
            out += ["", f"### `{opdef.name}` parameters", "```"]
            out.append(opdef.schema.doc().strip())
            out.append("```")
    return "\n".join(out) + "\n"


def _public_classes(mod, base=None):
    res = []
    for name in getattr(mod, "__all__", dir(mod)):
        if name.startswith("_"):
            continue
        obj = getattr(mod, name, None)
        if inspect.isclass(obj) and (base is None or issubclass(obj, base)):
            res.append((name, obj))
    return res


def gen_python_api() -> str:
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, initializer, lr_scheduler, metric
    from incubator_mxnet_tpu import optimizer as opt_mod
    from incubator_mxnet_tpu.gluon import loss as loss_mod, nn, rnn

    sections = [
        ("gluon.nn layers", nn, gluon.Block),
        ("gluon.rnn layers & cells", rnn, gluon.Block),
        ("gluon.loss", loss_mod, loss_mod.Loss),
        ("Optimizers", opt_mod, opt_mod.Optimizer),
        ("Metrics", metric, metric.EvalMetric),
        ("Initializers", initializer, initializer.Initializer),
        ("LR schedulers", lr_scheduler, lr_scheduler.LRScheduler),
    ]
    out = ["# Python API surface", "",
           "Generated by `tools/gen_docs.py` from live introspection.", ""]
    for title, mod, base in sections:
        rows = _public_classes(mod, base)
        out += [f"## {title}", "", "| Class | Summary |", "|---|---|"]
        for name, cls in rows:
            out.append(f"| `{name}` | {_first_line(cls)} |")
        out.append("")
    # top-level modules
    out += ["## Top-level modules", "", "| Module | Role |", "|---|---|"]
    for name in ["nd", "sym", "np", "autograd", "gluon", "module", "io",
                 "image", "kvstore", "parallel", "optimizer", "metric",
                 "initializer", "lr_scheduler", "random", "profiler",
                 "quantization", "onnx", "amp", "fault", "serve",
                 "telemetry", "recordio", "contrib", "runtime",
                 "test_utils", "visualization"]:
        obj = getattr(mx, name, None)
        if obj is None:
            continue
        out.append(f"| `mx.{name}` | {_first_line(obj)} |")
    return "\n".join(out) + "\n"


def gen_analysis() -> str:
    import incubator_mxnet_tpu.analysis as analysis
    from incubator_mxnet_tpu.analysis.diagnostics import (CODES,
                                                          DEFAULT_SEVERITY)

    out = ["# Static analysis (`mx.analysis` / `mxlint`)", "",
           "Generated by `tools/gen_docs.py` from the live pass registries "
           "(`analysis/passes.py`, `analysis/hlo/passes.py`) and the "
           "diagnostic table (`analysis/diagnostics.py`).", "",
           "Run the passes programmatically:", "", "```python",
           "report = mx.analysis.verify(sym, shapes={\"data\": (32, 784)})",
           "report.raise_if_errors()",
           "report = mx.analysis.hlo.verify(model, sample_args)  "
           "# compiled graph",
           "```", "",
           "or from the CLI (exit 0 clean, 1 diagnostics, 2 bad "
           "invocation):", "", "```",
           "python -m tools.mxlint                    # models + examples",
           "python -m tools.mxlint net-symbol.json    # graph passes",
           "python -m tools.mxlint layout.json        # sharding table",
           "python -m tools.mxlint my_blocks.py dir/  # AST tracer lint",
           "python -m tools.mxlint --hlo all          # compiled-graph "
           "MX7xx over models.SERVE_SPECS",
           "python -m tools.mxlint --hlo all --quantized  # calibrated "
           "int8 twins (models.QUANT_FAMILIES) + MX71x dtype-flow",
           "python -m tools.mxlint --concurrency      # MX8xx "
           "race/deadlock passes over the package",
           "python -m tools.mxlint --distributed      # MX9xx "
           "SPMD-divergence passes over the package",
           "python -m tools.mxlint --format=json ...  # one JSON finding "
           "per line",
           "```", "",
           "Source lints honor inline suppressions: "
           "`# mxlint: disable=MX204` on the flagged line, "
           "`# mxlint: disable-file=MX501` anywhere in the file.", "",
           "## Registered passes (Symbol graphs)", "",
           "| Pass | Checks |", "|---|---|"]
    for p in analysis.PASSES.values():
        desc = p.describe.replace("|", "\\|")
        out.append(f"| `{p.name}` | {desc} |")
    out += ["",
            "## Registered passes (compiled graphs, `mx.analysis.hlo`)", "",
            "Traced from any entry point (HybridBlock, "
            "`serve.CompiledModel` buckets, `SymbolBlock` export "
            "artifacts, `parallel.ShardedTrainer.step`) to jaxpr + "
            "StableHLO, then inspected *before the first device step*. "
            "`serve.ModelRegistry.load` and `benchmark/serve_bench.py` "
            "run them at staging time; the CI `hlo-lint` job covers "
            "every `models.SERVE_SPECS` family.", "",
            "| Pass | Checks |", "|---|---|"]
    for p in analysis.hlo.HLO_PASSES.values():
        desc = p.describe.replace("|", "\\|")
        out.append(f"| `{p.name}` | {desc} |")
    out += ["",
            "## Registered passes (concurrency, "
            "`mx.analysis.concurrency`)", "",
            "The MX8xx race/deadlock family over the package's own "
            "threaded tier (serve batcher/server, async kvstore, "
            "telemetry bus, watchdog, chaos injector). Whole-package by "
            "design: every module's `with`-regions and cross-module "
            "calls merge into ONE lock-acquisition graph before MX802's "
            "cycle detection. The runtime twin is "
            "`incubator_mxnet_tpu.lockcheck` (`MXTPU_LOCKCHECK=1`): "
            "tracked locks record real acquisition order, publish "
            "`concurrency.inversion` telemetry events, bound inverted "
            "acquires so a genuine deadlock fails instead of hanging, "
            "and cross-check the static graph by lock name "
            "(`concurrency.crosscheck()`). The CI `concurrency-lint` "
            "job runs `mxlint --concurrency --strict` plus a "
            "lockcheck-enabled chaos smoke gated on zero inversion "
            "events.", "",
            "| Pass | Checks |", "|---|---|"]
    for name, desc in analysis.concurrency.CONCURRENCY_PASSES.items():
        out.append(f"| `{name}` | {desc.replace('|', chr(92) + '|')} |")
    out += ["",
            "## Registered passes (distributed, "
            "`mx.analysis.distributed`)", "",
            "The MX9xx SPMD-divergence family over the multi-host tier. "
            "The multi-controller JAX model assumes *every process runs "
            "the same program*; when the assumption breaks, nothing "
            "crashes — one host takes a divergent branch and the rest "
            "of the pod blocks inside a collective forever. MX901–MX904 "
            "are source passes; `hlo_collective_schedule` (MX905) runs "
            "in the `analysis.hlo` registry over traced graphs and "
            "compares the collective verb/axis sequence across buckets "
            "of one entry. The runtime twin is "
            "`telemetry.collective_ledger` "
            "(`MXTPU_COLLECTIVE_LEDGER=1`): every pjit step/bucket "
            "build banks a fingerprint of its collective schedule, and "
            "`crosscheck()` exchanges the digests across processes at "
            "`dist.initialize()` and on post-warmup recompiles — a "
            "mismatch writes one flight bundle and raises instead of "
            "wedging the pod (the MX802↔lockcheck analogue one layer "
            "up). The CI `distributed-lint` job runs "
            "`mxlint --distributed --strict` plus the 2-process "
            "`tools/collective_smoke.py` crosscheck drill, clean and "
            "under the seeded `collective_divergence` chaos knob.", "",
            "| Pass | Code | Checks |", "|---|---|---|"]
    for name, code in analysis.distributed.DIST_PASSES.items():
        out.append(f"| `{name}` | `{code}` | "
                   f"{CODES[code].replace('|', chr(92) + '|')} |")
    out += ["",
            "The AST tracer lint (`analysis/tracer_lint.py`) and the "
            "runtime recompile accounting (`analysis/recompile.py`) run "
            "outside the Symbol pass pipeline: the lint needs source "
            "files, the accounting needs a live jit cache.", "",
            "## Diagnostic codes", "",
            "Codes are stable and append-only — CI and tooling grep for "
            "them. The default severity column is the single source of "
            "truth (`analysis/diagnostics.py:DEFAULT_SEVERITY`, audited "
            "by tests); a pass may override per finding.", "",
            "| Code | Default severity | Meaning |", "|---|---|---|"]
    for code in sorted(CODES):
        out.append(f"| `{code}` | {DEFAULT_SEVERITY[code]} | "
                   f"{CODES[code]} |")
    return "\n".join(out) + "\n"


def gen_fault() -> str:
    import inspect as _inspect

    import incubator_mxnet_tpu.fault as fault
    from incubator_mxnet_tpu.fault import (checkpoint, guards, inject,
                                           retry, watchdog)

    out = ["# Fault tolerance (`mx.fault`)", "",
           "Generated by `tools/gen_docs.py` from live introspection of "
           "the `incubator_mxnet_tpu.fault` subsystem.", "",
           "Typical wiring:", "", "```python",
           "guard = mx.fault.StepGuard(policy=\"skip_and_rollback\")",
           "trainer = mx.parallel.ShardedTrainer(net, loss_fn, \"adamw\","
           " ...,",
           "                                     guard=guard,",
           "                                     watchdog=mx.fault."
           "Watchdog(30.0))",
           "for step, batch in enumerate(batches):",
           "    trainer.step(*batch)",
           "    if step % 500 == 0:",
           "        trainer.save_checkpoint(\"ckpts/\", keep=3)",
           "# after a crash: resumes bit-identically (params, optimizer,",
           "# step counter, LR position, RNG base key)",
           "trainer.restore_checkpoint(\"ckpts/\")", "```", ""]
    for title, mod in [("Checkpointing (`fault.checkpoint`)", checkpoint),
                       ("Step guards (`fault.guards`)", guards),
                       ("Watchdog (`fault.watchdog`)", watchdog),
                       ("Retry policy (`fault.retry`)", retry),
                       ("Chaos injection (`fault.inject`)", inject)]:
        out += [f"## {title}", "",
                (mod.__doc__ or "").split("\n", 1)[0], "",
                "| Name | Summary |", "|---|---|"]
        for name in mod.__all__:
            obj = getattr(mod, name, None)
            if obj is None or not (_inspect.isclass(obj)
                                   or _inspect.isfunction(obj)):
                continue
            out.append(f"| `{name}` | {_first_line(obj)} |")
        out.append("")
    out += ["## Environment knobs", "",
            "| Variable | Default | Effect |", "|---|---|---|",
            "| `MXNET_KVSTORE_TIMEOUT` | `60` | Per-socket-op timeout "
            "(seconds) of the `dist_async` client. |",
            "| `MXNET_KVSTORE_RETRIES` | `5` | Reconnect/resend attempts "
            "after a first failure. |",
            "| `MXNET_KVSTORE_RETRY_DELAY` | `0.05` | Base backoff seconds "
            "(doubles per attempt, capped at 2s). |",
            "| `MXTPU_CHAOS` | unset | Seeded chaos spec, e.g. "
            "`seed=7,nan_prob=0.01,kv_drop=0.1,crash=nd.save`. |", "",
            "The `mxlint` MX401 diagnostic (warning severity) flags "
            "training scripts that build a trainer and run a step loop "
            "without ever checkpointing.", ""]
    return "\n".join(out)


def gen_serve() -> str:
    import inspect as _inspect

    import incubator_mxnet_tpu.serve as serve
    from incubator_mxnet_tpu.serve import (artifact_cache, batcher, buckets,
                                           compiled, metrics, registry,
                                           replica, router, server)
    from incubator_mxnet_tpu.serve.decode import (batcher as decode_batcher,
                                                  blocks as decode_blocks,
                                                  engine as decode_engine)
    from incubator_mxnet_tpu.serve.decode import \
        metrics as decode_metrics

    out = ["# Serving (`mx.serve`)", "",
           "Generated by `tools/gen_docs.py` from live introspection of "
           "the `incubator_mxnet_tpu.serve` subsystem.", "",
           "Typical wiring:", "", "```python",
           "table = mx.serve.BucketTable({\"batch\": (1, 32), "
           "\"seq\": (16, 128)})",
           "spec = mx.models.serve_spec(\"bert_encoder\")",
           "model = mx.serve.CompiledModel(net, table, "
           "spec[\"input_axes\"],",
           "                               output_axes="
           "spec[\"output_axes\"],",
           "                               pad_values="
           "spec[\"pad_values\"])",
           "model.warmup()      # AOT-compiles every shape bucket",
           "batcher = mx.serve.DynamicBatcher(model).start()",
           "out = batcher.submit(ids, token_types, valid_length)"
           ".result()",
           "assert model.cache_info()[\"post_warmup_compiles\"] == 0",
           "```", "",
           "Cold start from an exported artifact (no Python model code):",
           "", "```python",
           "mx.serve.export_for_serving(net, \"deploy/bert\", table, "
           "spec[\"input_axes\"])",
           "reg = mx.serve.ModelRegistry()",
           "reg.load(\"bert\", table=table, input_axes="
           "spec[\"input_axes\"],",
           "         artifacts=\"deploy/bert\", ckpt_root=\"ckpts/\")"
           "  # newest verified weights",
           "srv = mx.serve.Server(reg).start()   # JSON-lines TCP on "
           "srv.port", "```", ""]
    out += ["High availability — N replicas behind the failover router, "
            "prewarmed from the on-disk artifact cache:", "", "```python",
            "cache = mx.serve.ArtifactCache(\"deploy/cache\")",
            "def loader(rep):",
            "    rep.load(\"bert\", table=table, "
            "input_axes=spec[\"input_axes\"],",
            "             factory=build_bert, cache=cache)  "
            "# hit = no retrace",
            "router = mx.serve.Router([mx.serve.Replica(f\"r{i}\", loader)",
            "                          for i in range(3)]).start()",
            "out = router.call(\"bert\", ids, token_types, valid_length)",
            "router.start_weight_sync(\"bert\", \"ckpts/\", "
            "interval_s=300)", "```", ""]
    out += ["Autoregressive decode serving — paged KV-cache + continuous "
            "batching (`serve.decode`):", "", "```python",
            "table = mx.serve.BucketTable({\"batch\": (1, 1), "
            "\"src\": (4, 64)})",
            "engine = mx.serve.DecodeEngine(nmt, table, max_batch=8,",
            "                               hbm_budget=mx.util.getenv"
            "(\"MXTPU_HBM_BUDGET\"))",
            "engine.warmup()       # bucketed prefill + ONE AOT decode "
            "step",
            "batcher = mx.serve.DecodeBatcher(engine).start()",
            "stream = batcher.submit(prompt_ids, max_new_tokens=32)",
            "for tok in iter(lambda: stream.next_token(timeout=5), None):",
            "    ...               # tokens stream at decode-step "
            "granularity", "```", ""]
    for title, mod in [("Shape buckets (`serve.buckets`)", buckets),
                       ("Compiled inference (`serve.compiled`)", compiled),
                       ("Dynamic batching (`serve.batcher`)", batcher),
                       ("Model registry (`serve.registry`)", registry),
                       ("Front ends (`serve.server`)", server),
                       ("Replicas (`serve.replica`)", replica),
                       ("Failover routing (`serve.router`)", router),
                       ("AOT prewarm cache (`serve.artifact_cache`)",
                        artifact_cache),
                       ("Observability (`serve.metrics`)", metrics),
                       ("Paged KV-cache (`serve.decode.blocks`)",
                        decode_blocks),
                       ("Prefill/decode engine (`serve.decode.engine`)",
                        decode_engine),
                       ("Continuous batching (`serve.decode.batcher`)",
                        decode_batcher),
                       ("Decode observability (`serve.decode.metrics`)",
                        decode_metrics)]:
        out += [f"## {title}", "",
                (mod.__doc__ or "").split("\n", 1)[0], "",
                "| Name | Summary |", "|---|---|"]
        for name in mod.__all__:
            obj = getattr(mod, name, None)
            if obj is None or not (_inspect.isclass(obj)
                                   or _inspect.isfunction(obj)):
                continue
            out.append(f"| `{name}` | {_first_line(obj)} |")
        out.append("")
    # the env table renders from the single catalog (util.ENV_VARS) so it
    # cannot drift from the code's getenv defaults
    from incubator_mxnet_tpu.util import ENV_VARS
    out += ["## Environment knobs", "",
            "| Variable | Default | Effect |", "|---|---|---|"]
    for name in sorted(ENV_VARS):
        if name.startswith(("MXTPU_SERVE_", "MXTPU_DECODE_")):
            default, desc = ENV_VARS[name]
            out.append(f"| `{name}` | `{default}` | "
                       f"{desc.replace('|', chr(92) + '|')} |")
    out += ["",
            "The `mxlint` MX5xx family (warning severity) flags serving "
            "code that compiles per request (MX501) or feeds raw "
            "unbucketed request shapes to a jitted callable (MX502).", ""]
    return "\n".join(out)


def gen_telemetry() -> str:
    import inspect as _inspect

    import incubator_mxnet_tpu.telemetry as telemetry
    from incubator_mxnet_tpu.telemetry import (collective_ledger,
                                               compile_log, director, events,
                                               export, flight, goodput,
                                               memory, metrics, numerics,
                                               slo, trace)

    out = ["# Observability (`mx.telemetry`)", "",
           "Generated by `tools/gen_docs.py` from live introspection of "
           "the `incubator_mxnet_tpu.telemetry` subsystem.", "",
           "Typical wiring (the runtime publishes automatically; user "
           "code reads):", "", "```python",
           "sink = mx.telemetry.install_jsonl(\"events.jsonl\")  # or "
           "MXTPU_TELEMETRY_JSONL",
           "trainer.step(x, y)          # train.step / guard / compile "
           "events",
           "batcher.submit(request)     # serve.admit ... serve.reply "
           "events",
           "",
           "mx.telemetry.snapshot()     # \"what is this job doing right "
           "now\"",
           "mx.telemetry.prometheus_text()        # the metrics scrape",
           "mx.telemetry.compile_log.assert_zero_post_warmup()",
           "open(\"trace.json\", \"w\").write(mx.telemetry.chrome_trace())",
           "```", "",
           "Event kinds the wired runtime publishes:", "",
           "| Kind | Publisher | Correlation |", "|---|---|---|",
           "| `train.step` | `parallel.ShardedTrainer.step` | step id |",
           "| `guard` | `fault.StepGuard.decide` | step id |",
           "| `watchdog` | `fault.Watchdog` deadline fire | step id |",
           "| `chaos` | `fault.inject` fired draws / crashes | step id |",
           "| `kvstore` | `dist_async` client push/pull/retry/error | "
           "step id |",
           "| `serve.admit` / `serve.reject` | `DynamicBatcher.submit` | "
           "request id |",
           "| `serve.batch` / `serve.execute` | batcher flush | request "
           "ids |",
           "| `serve.reply` | per-request completion | request id |",
           "| `serve.load` | `ModelRegistry.load` | — |",
           "| `compile` | the compile ledger (every jit cache) | step id "
           "|",
           "| `amp.loss_scale` | `amp.LossScaler.update_scale` | step id "
           "|",
           "| `router.failover` / `router.shed` / `router.hedge` | "
           "`serve.Router` request path | request id + trace |",
           "| `slo.burn` | `SLOMonitor.evaluate` multi-window breach / "
           "recovery | — |",
           "| `flight.dump` | `telemetry.flight` post-mortem written | "
           "trigger context |",
           "| `memory.leak` | `telemetry.memory` leak watchdog "
           "(monotonic residency growth over a full sample window) | — |",
           "| `memory.oom` | `telemetry.memory` OOM guard "
           "(RESOURCE_EXHAUSTED at `trainer.step` / `serve.compiled`) | "
           "site + step |",
           "| `serve.memory` | `ModelRegistry.load` staging memory "
           "preflight (ladder peak vs `MXTPU_HBM_BUDGET`) | — |",
           "| `numerics.step` | `telemetry.numerics.record` — one "
           "decimated in-graph stats sample (per-site rings/gauges "
           "updated alongside) | step / request count |",
           "| `numerics.drift` | the numerics drift watchdog: "
           "monotonic rms growth or finite-fraction decay over the "
           "recorded window (damped; fires BEFORE non-finite) | step + "
           "site |",
           "| `goodput.window` | `telemetry.goodput` — one closed "
           "attribution window: per-category ms vector, measured vs "
           "roofline MFU, unattributed share | step |",
           "| `director.decision` | `telemetry.director` — one "
           "flight-director decision: trigger window + divergence, "
           "candidate table, chosen action, hysteresis state "
           "(severity `warning` when an action was applied, `info` "
           "for audited no-action/hold) | window id |",
           "| `router.policy` | `serve.Router.set_overload_policy` — "
           "an overload-policy cutover (shed depth / hedge budget), "
           "previous values included | — |",
           "| `checkpoint.save` | `fault.checkpoint.save_checkpoint` "
           "wall + path (also a `checkpoint` goodput note) | step |",
           "| `collective.bank` | `telemetry.collective_ledger` — one "
           "collective-schedule fingerprint banked per new step/bucket "
           "build (`MXTPU_COLLECTIVE_LEDGER=1`) | site + signature |",
           "| `collective.crosscheck` / `collective.mismatch` | the "
           "cross-process fingerprint exchange at `dist.initialize()` "
           "and post-warmup recompiles — a mismatch also writes one "
           "flight bundle and raises | tag |",
           "",
           "Every event additionally carries the active distributed-"
           "trace context (`trace_id`/`span_id`) whenever one is "
           "active — sampled or not, since sampling gates span "
           "RECORDING, not id propagation; an unsampled event's ids "
           "correlate log streams but resolve to no recorded spans — "
           "see `telemetry.trace` below.", ""]
    for title, mod in [("Event bus (`telemetry.events`)", events),
                       ("Metrics registry (`telemetry.metrics`)", metrics),
                       ("Compile ledger (`telemetry.compile_log`)",
                        compile_log),
                       ("Collective-schedule ledger "
                        "(`telemetry.collective_ledger`)",
                        collective_ledger),
                       ("Sinks (`telemetry.export`)", export),
                       ("Distributed tracing (`telemetry.trace`)", trace),
                       ("Flight recorder (`telemetry.flight`)", flight),
                       ("Device-memory ledger (`telemetry.memory`)",
                        memory),
                       ("In-graph numerics (`telemetry.numerics`)",
                        numerics),
                       ("Goodput ledger (`telemetry.goodput`)", goodput),
                       ("SLO burn-rate monitor (`telemetry.slo`)", slo),
                       ("Flight director (`telemetry.director`)",
                        director)]:
        out += [f"## {title}", "",
                (mod.__doc__ or "").split("\n", 1)[0], "",
                "| Name | Summary |", "|---|---|"]
        for name in mod.__all__:
            obj = getattr(mod, name, None)
            if obj is None or not (_inspect.isclass(obj)
                                   or _inspect.isfunction(obj)):
                continue
            out.append(f"| `{name}` | {_first_line(obj)} |")
        out.append("")
    from incubator_mxnet_tpu.util import ENV_VARS
    out += ["## Environment knobs", "",
            "| Variable | Default | Effect |", "|---|---|---|"]
    for name in sorted(ENV_VARS):
        if name.startswith(("MXTPU_TELEMETRY", "MXTPU_TRACE",
                            "MXTPU_FLIGHT", "MXTPU_SLO",
                            "MXTPU_HBM_BUDGET", "MXTPU_MEMORY",
                            "MXTPU_NUMERICS", "MXTPU_COLLECTIVE",
                            "MXTPU_GOODPUT", "MXTPU_DIRECTOR")):
            default, desc = ENV_VARS[name]
            out.append(f"| `{name}` | `{default}` | "
                       f"{desc.replace('|', chr(92) + '|')} |")
    out += ["",
            "The `mxlint` MX601 diagnostic (warning severity) flags "
            "training loops and serving entry points that hand-roll "
            "wall-clock timing/counters instead of publishing through "
            "`mx.telemetry` — those measurements never reach the bus, "
            "the scrape, or the JSONL stream; MX602 flags request-path "
            "`emit(...)` calls outside any request/step correlation "
            "scope (uncorrelated telemetry that can never stitch into a "
            "trace); MX603 flags tensor statistics routed through host "
            "callbacks inside a jitted function — the whole-step-"
            "capture-breaking anti-pattern `telemetry.numerics` exists "
            "to replace (stats as pinned in-graph outputs, decimated "
            "host-side). The CI `telemetry-smoke` job replays the serving "
            "bench's event stream through `tools/telemetry_check.py` "
            "(strict JSON + zero post-warmup compile events), and the "
            "`trace-smoke` job re-validates the exported span stream "
            "with `--require-rooted-traces` (every sampled request one "
            "rooted tree, zero orphan spans). See "
            "`docs/observability.md` for the end-to-end runbook.", ""]
    return "\n".join(out)


def gen_profiler() -> str:
    import inspect as _inspect

    import incubator_mxnet_tpu.profiler as profiler

    out = ["# Profiler (`mx.profiler`)", "",
           "Generated by `tools/gen_docs.py` from live introspection of "
           "`incubator_mxnet_tpu/profiler.py`.", "",
           (profiler.__doc__ or "").split("\n", 1)[0], "",
           "Typical wiring:", "", "```python",
           "mx.profiler.set_config(filename=\"profile.json\")",
           "mx.profiler.set_state(\"run\")        # XProf device trace",
           "with mx.profiler.Scope(\"data_prep\"):   # nests under the",
           "    with mx.profiler.Scope(\"decode\"):  # enclosing scope",
           "        ...",
           "trainer.step(x, y)    # records one 'step' frame with",
           "                      # place/dispatch/device_wait segments",
           "mx.profiler.step_report()            # host-gap attribution",
           "mx.profiler.dump()    # atomic chrome-trace JSON at filename",
           "```", "",
           "## Step attribution (`step_report`)", "",
           "Every `ShardedTrainer.step` records one `step` frame; every "
           "`serve.CompiledModel.predict` records one `serve.predict` "
           "frame. `step_report(frame=...)` splits each frame into named "
           "segments and attributes the remainder to `python`, so 100% "
           "of frame wall time is accounted for:", "",
           "| Segment | Meaning |", "|---|---|",
           "| `place` | host→device input placement (`trainer.place`) |",
           "| `dispatch` | the jitted step call (enqueue on TPU; may "
           "include compute on CPU backends) |",
           "| `device_wait` | host blocked on device results (the "
           "guard's loss/grad-norm sync) |",
           "| `serve.pad` / `serve.compute` / `serve.unpad` | serving "
           "stages under `serve.predict` |",
           "| `python` | un-instrumented host framework time (the "
           "frame total minus all children) |", "",
           "`host_gap_ms_*` = frame time minus device-side segments "
           "(`device_wait`, `serve.compute`) — the host dispatch tax "
           "ROADMAP open item 2 drives toward zero. On the chip the "
           "benchmark reads the same spans from the device trace "
           "(`host_step_ms.train`, `step_*_ms.train`); "
           "`telemetry.snapshot()` embeds both reports.",
           "", "## Kernels and scopes in a device trace", "",
           "What the program names so that a trace of the chip reads by "
           "module (the table of all of them, with the benchmark metric "
           "that reads each, is `docs/observability.md` section 9). Pallas "
           "kernels carry their `pl.pallas_call(name=...)` in the HLO "
           "instruction's name: `flash_fwd`, `flash_bwd_dkv`, "
           "`flash_bwd_dq`; with `_win` where the call has a `window=`; "
           "with `_mla` where it has `shared=(q_s, k_s)`, the second score "
           "term whose one key head all query heads read (latent "
           "attention: `flash_fwd_mla`, `flash_bwd_dkv_mla`, "
           "`flash_bwd_dq_mla`, read by `mla_attn_ms.train` and "
           "`mla_attn_roofline.train`); the same three names where BERT's "
           "attention reads its projections as they are "
           "(`ops.projected_attention`, the gauge "
           "`mxtpu_flash_lane_layout{kernel=}` says whether a call took "
           "it); `moe_gmm`, `moe_tgmm`; "
           "`moe_rows_*`; `short_conv_fwd`, `short_conv_bwd` (the gated "
           "short convolution `ops.nn.short_conv_gate`, one pass over its "
           "operands each way, read by `short_conv_ms.train` and "
           "`short_conv_roofline.train`); `qk_prologue_fwd`, `qk_prologue_bwd` "
           "(`ops.nn.qk_norm_rope`: per-head RMSNorm, rotary and the "
           "head-major write of a q or k projection in one pass each way; "
           "the gauge `mxtpu_qk_prologue_fused{kernel=}` says whether a "
           "call took them); `ssd_fwd`, `ssd_bwd` (the chunked state-space "
           "scan `ops.ssm.ssd_scan` of a Mamba-2 mixer, the state carried "
           "in VMEM from chunk to chunk, read by `ssd_scan_ms.train` and "
           "`ssd_scan_roofline.train`; the gauge `mxtpu_ssd_fused{kernel=}` "
           "says whether a call took them); `causal_conv_fwd`, "
           "`causal_conv_bwd` (the causal convolution with bias and SiLU "
           "`ops.nn.causal_conv1d` of a Mamba-2 mixer, one pass over its "
           "operands each way, read through the scope `mamba_conv` by "
           "`mamba_conv_ms.train` and `mamba_conv_roofline.train`; the gauge "
           "`mxtpu_causal_conv_fused{kernel=}` says whether a call took "
           "them). `jax.named_scope`s sit in every operation's "
           "`op_name`: `afmoe_attention`, `moe_router`, `moe_dispatch`, "
           "`moe_experts`, `moe_combine`, `lm_head`, and in "
           "`models/deepseek_v3.py`'s attention block `mla_q` (the query "
           "projection and its split), `mla_latent` (the down-projection, "
           "the latent's norm), `mla_kv_up` (the up-projection to keys and "
           "values) and `mla_attention` (rotary on the 64-wide parts, the "
           "heads' layout, the kernel); in `models/lfm2_moe.py` "
           "`lfm2_mixer_conv` and `lfm2_mixer_attn` round the two kinds of "
           "token mixer, and `short_conv` round the fused op inside the first; "
           "`qk_prologue` round the fused q/k prologue where its kernels run; "
           "in `models/granite_hybrid.py` `mamba_mixer` round the whole "
           "Mamba-2 mixer (read by `mamba_mixer_ms.train`), `mamba_conv` and "
           "`mamba_gated_norm` round its convolution and its gated norm, and "
           "`ssd_scan` (`ops/ssm.py`) round the scan; "
           "`layer_norm` and `rms_norm` (`ops/nn.py`) round the two norms; "
           "`optimizer_update` (`ShardedTrainer`) round the per-parameter "
           "update. jax adds `transpose(jvp(...))` for the backward pass and "
           "`rematted_computation` for a recomputed layer's forward. A trace "
           "names an operation by its HLO instruction only, so the benchmark "
           "joins it to the compiled step's text, which "
           "`telemetry.compile_log.program_text(\"trainer.step\")` hands out "
           "on request (`chipbench/op_scopes.py`: `optimizer_update_ms.train`, "
           "`norm_ms.train`, `recompute_ms.train`, `unscoped_share.train`, and "
           "for the collectives the compiler puts in, `collective_ms.train` "
           "and `collective_exposed_ms.train`).",
           "", "## API", "", "| Name | Summary |", "|---|---|"]
    for name in profiler.__all__:
        obj = getattr(profiler, name, None)
        if obj is None or not (_inspect.isclass(obj)
                               or _inspect.isfunction(obj)):
            continue
        out.append(f"| `{name}` | {_first_line(obj)} |")
    out += ["",
            "Spans and markers are bounded (8192 samples per name, a "
            "4096-entry raw ring), so a long-lived server never grows "
            "without limit; aggregate counters keep counting past the "
            "caps. All span timestamps derive from one perf_counter "
            "clock anchored to the wall clock at import, so nested spans "
            "provably nest in the merged chrome trace "
            "(`mx.telemetry.chrome_trace`).", ""]
    return "\n".join(out)


def gen_parallel() -> str:
    import inspect as _inspect

    from incubator_mxnet_tpu.parallel import (collectives, dist, mesh,
                                              sharding, trainer)

    out = ["# Parallel execution (`mx.parallel`)", "",
           "Generated by `tools/gen_docs.py` from live introspection of "
           "the `incubator_mxnet_tpu.parallel` subsystem.", "",
           "## Compiled mesh execution — the default path", "",
           (trainer.ShardedTrainer.__doc__ or "").split("\n", 1)[0], "",
           "```python",
           "mesh = mx.parallel.make_mesh(dp=2, tp=4)",
           "trainer = mx.parallel.ShardedTrainer(net, loss_fn, \"adamw\","
           " {\"learning_rate\": 1e-4},",
           "                                     mesh=mesh, "
           "rules=models.bert_sharding_rules())",
           "loss = trainer.step(data, label)   # ONE pjit call: fwd+bwd+"
           "all-reduce+sharded update",
           "```", "",
           "With a mesh configured, `step()` runs one compiled pjit call "
           "with explicit `PartitionSpec` in/out resources and donated "
           "parameter/optimizer buffers: gradient exchange lowers to XLA "
           "all-reduce over the mesh axes, and the optimizer update "
           "executes cross-replica sharded over the ZeRO-1 `dp` shards "
           "(on by default when `dp > 1`; `zero1=False` opts out). The "
           "per-parameter kvstore push/pull loop survives only behind "
           "`MXTPU_KVSTORE_FALLBACK=1` (the async-PS scenario — the "
           "`dist_async` client's retry/exactly-once semantics ride "
           "along untouched). The MX708 `hlo_mesh_step` pass and the CI "
           "`multichip-smoke` job gate the contract.", ""]
    for title, mod in [
            ("Trainer (`parallel.trainer`)", trainer),
            ("Meshes (`parallel.mesh`)", mesh),
            ("Sharding rules (`parallel.sharding`)", sharding),
            ("Collective verbs (`parallel.collectives`)", collectives),
            ("Multi-host runtime (`parallel.dist`)", dist)]:
        names = list(getattr(mod, "__all__", []) or [])
        if mod is mesh:
            names = ["MeshConfig", "make_mesh", "local_mesh",
                     "default_mesh", "set_default_mesh", "active_mesh"]
        if mod is dist:
            names = ["initialize", "finalize", "process_count",
                     "process_index"]
        out += [f"## {title}", "",
                (mod.__doc__ or "").split("\n", 1)[0], "",
                "| Name | Summary |", "|---|---|"]
        for name in names:
            obj = getattr(mod, name, None)
            if obj is None or not (_inspect.isclass(obj)
                                   or _inspect.isfunction(obj)):
                continue
            out.append(f"| `{name}` | {_first_line(obj)} |")
        out.append("")
    out += ["## ShardedTrainer surface", "",
            "| Method | Summary |", "|---|---|"]
    from incubator_mxnet_tpu.parallel.trainer import ShardedTrainer
    for name, obj in sorted(vars(ShardedTrainer).items()):
        if name.startswith("_") or not callable(obj):
            continue
        out.append(f"| `{name}` | {_first_line(obj)} |")
    out += ["",
            "## Fallback matrix", "",
            "| Configuration | Execution path |", "|---|---|",
            "| mesh configured (any axis, incl. 1 device) | ONE compiled "
            "pjit step: explicit in/out `NamedSharding`s, "
            "`donate_argnums=(params, opt_states, t)` |",
            "| mesh with `dp > 1` (default `zero1=None`) | same, with the "
            "optimizer update cross-replica sharded (reduce-scatter grads "
            "→ `dp`-partitioned update → all-gather new weights) |",
            "| `MXTPU_KVSTORE_FALLBACK=1` | per-parameter host push/pull "
            "loop over the `kvstore=` backend (default `'device'`; "
            "`'dist_async'` keeps reconnect + versioned exactly-once "
            "resend) |",
            "| `gluon.Trainer` + kvstore | one batched push/pull per "
            "`allreduce_grads` call — a single compiled collective for "
            "the whole key set; per-key loop only for `dist_async` or "
            "under the fallback flag |", ""]
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="verify committed docs match the code")
    args = ap.parse_args(argv)

    targets = {
        os.path.join(REPO, "docs", "api", "operators.md"): gen_ops,
        os.path.join(REPO, "docs", "api", "python_api.md"): gen_python_api,
        os.path.join(REPO, "docs", "api", "analysis.md"): gen_analysis,
        os.path.join(REPO, "docs", "api", "fault.md"): gen_fault,
        os.path.join(REPO, "docs", "api", "serve.md"): gen_serve,
        os.path.join(REPO, "docs", "api", "telemetry.md"): gen_telemetry,
        os.path.join(REPO, "docs", "api", "profiler.md"): gen_profiler,
        os.path.join(REPO, "docs", "api", "parallel.md"): gen_parallel,
    }
    os.makedirs(os.path.join(REPO, "docs", "api"), exist_ok=True)
    stale = []
    for path, gen in targets.items():
        content = gen()
        if args.check:
            on_disk = open(path).read() if os.path.exists(path) else ""
            if on_disk != content:
                stale.append(path)
            continue
        with open(path, "w") as f:
            f.write(content)
        print(f"wrote {os.path.relpath(path, REPO)} "
              f"({len(content.splitlines())} lines)")
    if stale:
        print("STALE:", *stale, "\nrun: python tools/gen_docs.py")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
