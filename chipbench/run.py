#!/usr/bin/env python3
"""chipbench: one run of one cell.

    python3 chipbench/run.py --workload W --seed N --seconds S --trace 0|1 [--rehearse]

A new process that builds the system from ``--seed``, checks it against the
plain reference, warms up the cell's own shapes, measures for ``--seconds``
and prints one JSON object as the last line of stdout. ``--trace 0`` gives
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics and a
``breakdown``. Everything about a cell is data: the workload file names its
configuration and traffic kind, the configuration names its family, and
``BENCHMARK.json`` lists the cell's metrics; this file names none of them.
"""
import time
_T0 = time.perf_counter()          # set-up is counted from here

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)


class CompileCounter:
    """Persistent-cache traffic since the last ``take()``: how many compiles
    asked the cache, and how many it answered."""

    def __init__(self):
        import jax
        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self) -> dict:
        out = {"compile_requests": self.requests, "cache_hits": self.hits}
        self.requests = self.hits = 0
        return out


def use_compile_cache(jax) -> str:
    """JAX's persistent cache at a fixed place, set before the first compile:
    where ``JAX_COMPILATION_CACHE_DIR`` says (jax reads it itself), else
    ``<checkout>/.jax_cache``. Every program is kept, however quick its
    compile, so that a later run of the cell compiles nothing."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_metric(name: str):
    """The reader of one per-layer metric: ``layer_metrics/<name>.py``."""
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(bench: dict, group: str, cell: str) -> list:
    return [m for m in bench[group] if cell in m.get("workloads", [cell])]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any platform; always exits 3")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="also copy the raw trace of a --trace 1 run there, for a look by hand")
    args = ap.parse_args(argv)

    bench = load_json("BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
    workload = load_json("chipbench", "workloads", args.workload + ".json")
    cfg = load_json(next(c["file"] for c in bench["configs"] if c["name"] == cell["config"]))
    traffic = dict(workload["traffic"])

    import jax
    devices = jax.devices()            # first act on JAX: what is there?
    on_chip = devices[0].platform == "tpu"
    if not on_chip and not args.rehearse:
        raise SystemExit(f"chipbench needs a TPU and found platform {devices[0].platform!r} "
                         f"({devices[0].device_kind}); there is no fallback "
                         "(--rehearse runs tiny sizes anywhere and exits 3)")
    if len(devices) < cell["chips"]:
        raise SystemExit(f"cell {args.workload} needs {cell['chips']} chips, "
                         f"JAX found {len(devices)}")
    devices = devices[:cell["chips"]]
    cache_dir = use_compile_cache(jax)
    counter = CompileCounter()

    family = importlib.import_module("chipbench.families." + cfg["family"])
    kind = importlib.import_module("chipbench.traffic." + traffic["kind"])
    if args.rehearse:        # the tiny sizes of the data files' own rehearse blocks
        cfg = {**cfg, **cfg["rehearse"]}
        traffic.update(traffic["rehearse"])
    from chipbench import tracered
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if args.trace else None
    tracer = tracered.Tracer(trace_dir) if args.trace else None
    try:
        out = kind.run(family, cfg, traffic, devices, args.seed, args.seconds,
                       tracer, on_chip, counter)
        trace = tracered.load(trace_dir) if args.trace else None
        if args.keep_trace and trace_dir:
            shutil.copytree(trace_dir, args.keep_trace, dirs_exist_ok=True)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    setup_s = out["window_start"] - _T0
    stats = devices[0].memory_stats() or {}
    samples = dict(out["samples"], device_kind=devices[0].device_kind)
    # PjRt's peak counts live buffers only and misses a program's temporaries
    # (PR 21, seen again in PR 23); the traffic kind reports what its compiled
    # program holds by the compiler's own memory_analysis()
    peak = max(int(stats.get("peak_bytes_in_use", 0)), int(samples.get("program_bytes", 0)))
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}

    values = {}
    if args.trace:
        device.update(busy_s=trace.busy_s, window_s=trace.window_s)
        for m in metrics_of(bench, "per_layer", args.workload):
            v = load_metric(m["name"]).compute(samples, trace)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = dict(out["end_to_end"], setup_s=setup_s)
        for m in metrics_of(bench, "end_to_end", args.workload):
            values[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    correct = all(c["ok"] for c in out["checks"].values())
    print(json.dumps({"workload": args.workload, "seed": args.seed, "cache_dir": cache_dir,
                      "setup_cache": samples.get("setup_cache"), "window_s": out["window_s"],
                      "pjrt_memory": {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")},
                      "jax": jax.__version__, "phases": out.get("phases"), "checks": out["checks"],
                      "end_to_end": dict(out["end_to_end"], setup_s=setup_s)}), flush=True)
    line = {"correct": bool(correct), "attempted": out["attempted"], "failed": out["failed"],
            "metrics": values, "device": device}
    if args.trace:
        line["breakdown"] = trace.breakdown()
    print(json.dumps(line), flush=True)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
