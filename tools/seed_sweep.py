#!/usr/bin/env python
"""Convergence-gate flake sweep.

Runs every convergence-gated test under N different MXNET_TEST_SEED values.
For the example gates it runs the example ``main()`` in a driver subprocess
with EXACTLY the arguments the test uses and records the metric value, so
the artifact (benchmark/seed_sweep.jsonl) carries per-seed metrics, the
worst-case margin to the gate threshold, and the cross-seed spread; the
test_train gates (which do not expose a metric) record pass/fail only.

The reference mechanism this hardens is tests/python/unittest/common.py
``with_seed()``: tests must hold under arbitrary seeds, not just lucky
ones. De-flake criterion: all seeds pass AND worst-margin >= 2x the
cross-seed spread (max - min of the metric).

    python tools/seed_sweep.py                 # 20 seeds, all gates
    python tools/seed_sweep.py --seeds 5 --gates mnist
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# One process for each chip: this parent never imports jax, and every child
# it starts (the _DRIVER source below, the pytest gates) pins the CPU.

# Metric gates: (key, example file, argv, threshold, direction).
# argv mirrors tests/test_example_*.py — keep in sync with the test files.
METRIC_GATES = [
    ("mnist", "train_mnist.py",
     ["--num-epochs", "3", "--num-synthetic", "600", "--lr", "0.05"],
     0.9, "higher"),
    ("image_classification", "image_classification.py",
     ["--model", "mobilenet0.25", "--epochs", "2", "--classes", "4",
      "--batch-size", "16"], 0.5, "higher"),
    ("bert_pretraining", "bert_pretraining.py",
     ["--model", "bert_2_128_2", "--steps", "6", "--batch-size", "4",
      "--seq-len", "64"], 20.0, "lower"),
    ("machine_translation", "machine_translation.py",
     ["--task", "copy", "--steps", "300", "--seq-len", "5", "--vocab", "12",
      "--lr", "0.002", "--batch-size", "32"], 0.8, "higher"),
    # threshold 12: r5 sweep measured 6.66..8.27 over 20 seeds (spread
    # 1.61); 12 gives margin >= 2x spread, untrained baseline is ~50
    ("word_language_model", "word_language_model.py",
     ["--steps", "40", "--epochs", "2"], 12.0, "lower"),
    # dcgan returns moment stats; the driver reduces them to the worst
    # normalized distance (must stay < 1.0 to pass both test bounds).
    # 300 steps: at 150 the r5 sweep measured worst 0.88 / spread 0.33
    # (margin < 2x spread); at 300 the worst seed converges to 0.17
    ("dcgan", "dcgan.py", ["--steps", "300"], 1.0, "lower"),
    ("ssd", "train_ssd.py", ["--steps", "150"], 0.8, "higher"),
    # 400 steps + threshold 0.25: the r5 20-seed sweep measured 0.75..1.0
    # (spread 0.25); 0.25 keeps margin >= 2x that spread while staying >3x
    # the untrained baseline (~0.08)
    ("frcnn", "train_frcnn.py", ["--steps", "400"], 0.25, "higher"),
]

# pytest-only gates (no exposed metric)
PYTEST_GATES = [
    "tests/test_train.py::test_lenet_gluon_converges_digits",
    "tests/test_train.py::test_mlp_module_fit_digits",
]

_DRIVER = r"""
import os
# Pin to the virtual CPU mesh BEFORE any device touch (same ordering as
# tests/conftest.py): these gates are convergence checks, and on a TPU host
# a child that reached for the chip would fight every other child for it
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
# with_seed() parity (tests/python/unittest/common.py seeds np + mx + py):
# examples seed mx.random from MXNET_TEST_SEED themselves, but data-order
# randomness (NDArrayIter shuffle) draws from the numpy/python GLOBAL
# streams, which are OS-entropy seeded per process — unseeded, the same
# gate seed gives different batch orders run to run (observed: mnist
# 1.0 vs 0.77 on identical invocations). tests/conftest.py already does
# this for the pytest gates; this driver is the other harness.
import random as _pyrandom
import numpy as _np
_sweep_seed = int(os.environ.get("MXNET_TEST_SEED", "0"))
_np.random.seed(_sweep_seed % 2**32)
_pyrandom.seed(_sweep_seed)
import importlib.util, json, sys
path, argv = sys.argv[1], json.loads(sys.argv[2])
spec = importlib.util.spec_from_file_location("sweep_target", path)
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
m = mod.main(argv)
if isinstance(m, dict):   # dcgan stats -> worst normalized moment distance
    m = max(abs(m["fake_mean"] - m["real_mean"]) / 0.3,
            abs(m["fake_std"] - m["real_std"]) / 0.4)
print("SWEEP_METRIC", float(m))
"""


def _run_metric_gate(example, argv, seed, timeout):
    env = dict(os.environ, MXNET_TEST_SEED=str(seed))
    try:
        r = subprocess.run(
            [sys.executable, "-c", _DRIVER,
             os.path.join(REPO, "examples", example), json.dumps(argv)],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=timeout)
    except subprocess.TimeoutExpired:
        return None, "timeout"
    for line in reversed(r.stdout.strip().splitlines()):
        if line.startswith("SWEEP_METRIC "):
            return float(line.split()[1]), None
    return None, (r.stderr or r.stdout)[-300:]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=20)
    ap.add_argument("--gates", default=None,
                    help="comma-separated gate-name substrings to keep")
    ap.add_argument("--timeout", type=int, default=900)
    args = ap.parse_args(argv)

    keys = args.gates.split(",") if args.gates else None

    def keep(name):
        return keys is None or any(k in name for k in keys)

    # deterministic, arbitrary-looking seed list (avoid Python hash salt)
    seeds = [(1103515245 * (i + 1) + 12345) % (2**31)
             for i in range(args.seeds)]

    out_path = os.path.join(REPO, "benchmark", "seed_sweep.jsonl")
    flaky = []

    for key, example, gate_argv, thresh, direction in METRIC_GATES:
        if not keep(key):
            continue
        vals, fails = [], []
        for seed in seeds:
            v, err = _run_metric_gate(example, gate_argv, seed, args.timeout)
            ok = v is not None and \
                (v > thresh if direction == "higher" else v < thresh)
            if not ok:
                fails.append({"seed": seed, "value": v, "err": err})
            if v is not None:
                vals.append(v)
            print(f"{key:24s} seed {seed:>10d} metric "
                  f"{v if v is not None else 'ERR'} "
                  f"{'ok' if ok else 'FAIL'}", flush=True)
        spread = (max(vals) - min(vals)) if vals else None
        worst = (min(vals) if direction == "higher" else max(vals)) \
            if vals else None
        margin = None
        if worst is not None:
            margin = (worst - thresh) if direction == "higher" \
                else (thresh - worst)
        rec = {"gate": key, "seeds": len(seeds), "threshold": thresh,
               "direction": direction, "values": vals,
               "worst": worst, "margin": margin, "spread": spread,
               "deflaked": (not fails and margin is not None
                            and spread is not None
                            and (spread == 0 or margin >= 2 * spread)),
               "failed": fails}
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(f"{key}: worst={worst} margin={margin} spread={spread} "
              f"deflaked={rec['deflaked']}", flush=True)
        if fails:
            flaky.append(rec)

    for gate in PYTEST_GATES:
        if not keep(gate):
            continue
        fails = []
        for seed in seeds:
            env = dict(os.environ, MXNET_TEST_SEED=str(seed))
            try:
                r = subprocess.run(
                    [sys.executable, "-m", "pytest", gate, "-q", "-x"],
                    cwd=REPO, env=env, capture_output=True, text=True,
                    timeout=args.timeout)
                ok = r.returncode == 0
            except subprocess.TimeoutExpired:
                ok = False
            if not ok:
                fails.append(seed)
            print(f"{gate.split('::')[1]:40s} seed {seed:>10d} "
                  f"{'ok' if ok else 'FAIL'}", flush=True)
        rec = {"gate": gate, "seeds": len(seeds), "failed_seeds": fails}
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        if fails:
            flaky.append(rec)

    print()
    if flaky:
        for rec in flaky:
            print(f"FLAKY: {rec['gate']}: {rec.get('failed') or rec.get('failed_seeds')}")
        return 1
    print("all gates green over", len(seeds), "seeds")
    return 0


if __name__ == "__main__":
    sys.exit(main())
