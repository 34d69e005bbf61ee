#!/usr/bin/env python
"""Config sweep over the headline BERT bench (bench.py) on real hardware.

DEPRECATION NOTE: the hand-listed variant set has moved — this script's
VARIANTS now derive from ``benchmark/autotune.py``'s declared search
space (:func:`autotune.bench_variants`), the one source of truth for the
tunable dimensions. For device-blind search over the FULL space (scored
by the HLO cost model, winners banked into the autotune cache that
trainer and serve consult), use ``python -m benchmark.autotune``; keep
this script for validating banked winners on real hardware — each
variant still runs ``python bench.py`` in its own subprocess (its own
device client; the persistent compile cache is shared through
``bench.use_compile_cache``) so a hung or crashed config can't poison the
rest of the sweep. The variants run one after another: a chip belongs to
one process at a time. Results append to
``benchmark/sweep_results.jsonl`` and print as a table.

    python benchmark/bert_sweep.py             # the derived hardware sweep
    python benchmark/bert_sweep.py --quick     # default config only (smoke)
    python benchmark/bert_sweep.py --trace DIR # + profiler trace of default

Reference counterpart: ``benchmark/opperf`` does per-op timing; this is the
whole-step equivalent for the north-star workload (BASELINE.md protocol).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# One process for each chip: this parent must never touch JAX, or it holds
# the chip and every ``bench.py`` child fails or hangs at device init. It
# imports only ``benchmark/autotune.py``, whose ``import jax`` lines are all
# inside functions that a sweep does not call — keep it so.
try:                              # package import (python -m benchmark...)
    from . import autotune as _autotune
except ImportError:               # direct script run
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import autotune as _autotune

# Derived from the autotuner's search-space declaration (the BASELINE.md
# round-3 prepared sweep: batch/remat rescan under the adaptive flash
# tiles, the BK=256 variant, the one-hot embedding-gradient path).
VARIANTS = _autotune.bench_variants()


def run_variant(name, env_delta, timeout=1200, trace=None):
    env = dict(os.environ, MXTPU_BENCH_TIMEOUT=str(timeout - 60), **env_delta)
    if trace:
        env["MXTPU_BENCH_TRACE"] = trace
    try:
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")], env=env,
            capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"variant": name, "error": f"timeout {timeout}s"}
    for line in reversed(out.stdout.strip().splitlines()):
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        # bench.py's contract is one JSON *object* with these keys; anything
        # else (a stray numeric debug line, a partial record) is not a result
        if isinstance(rec, dict) and "value" in rec and "extra" in rec:
            rec["variant"] = name
            rec["env"] = env_delta
            return rec
    return {"variant": name, "error": (out.stderr or out.stdout)[-400:],
            "returncode": out.returncode}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="default config only")
    ap.add_argument("--trace", default=None,
                    help="capture a profiler trace of the default config "
                         "into this directory")
    ap.add_argument("--only", default=None,
                    help="comma-separated variant names to run")
    args = ap.parse_args(argv)

    variants = VARIANTS[:1] if args.quick else VARIANTS
    if args.only:
        keep = set(args.only.split(","))
        unknown = keep - {v[0] for v in VARIANTS}
        if unknown:
            ap.error(f"unknown variant(s) {sorted(unknown)}; "
                     f"available: {[v[0] for v in VARIANTS]}")
        variants = [v for v in variants if v[0] in keep]
        if not variants:
            ap.error("--only selected nothing from the active set "
                     "(--quick keeps only the first variant)")

    results = []
    out_path = os.path.join(REPO, "benchmark", "sweep_results.jsonl")
    for name, delta in variants:
        trace = args.trace if (args.trace and name == "default-B8") else None
        rec = run_variant(name, delta, trace=trace)
        results.append(rec)
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        extra = rec.get("extra", {})
        if "error" in rec:
            print(f"{name:24s} ERROR {rec['error'][:120]}")
        else:
            print(f"{name:24s} step {extra.get('step_ms'):>8} ms   "
                  f"MFU {extra.get('mfu')}   {rec.get('value')} tok/s")
    ok = [r for r in results if "error" not in r]
    if ok:
        best = max(ok, key=lambda r: r["extra"]["mfu"])
        print(f"\nbest: {best['variant']}  MFU {best['extra']['mfu']}  "
              f"(env {best['env']})")
    return results


if __name__ == "__main__":
    main()
