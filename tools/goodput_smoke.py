#!/usr/bin/env python
"""Goodput-ledger smoke — the CI gate for ISSUE 15.

Runs two short training phases with the goodput ledger on and asserts
the whole contract end to end:

1. **sums_to_wall** — over a steady guarded train loop fed by a
   ``PrefetchIter``, the attribution vector accounts for the run's
   wall-clock: over-attribution stays within 5% of the measured wall
   and ``unattributed < 10%`` (the ledger's honesty gates);
2. **one_graph_per_step** / **ledger_clean** — with the ledger ON the
   fused step still runs exactly ONE jitted executable and the compile
   ledger stays clean post-warmup (goodput is host-side bookkeeping:
   the compiled graphs are untouched);
3. **mfu_reconciled** — ``price()`` installs the cost-model roofline
   and the report carries measured vs predicted MFU plus their
   divergence (the "why is MFU stuck" number);
4. **input_bound_classified** — a second phase under the seeded
   ``slow_input`` chaos knob must classify as ``input_bound`` with
   ``input_wait`` the dominant bucket — starvation attribution proven
   end to end;
5. **window_events** — ``goodput.window`` events landed on the bus
   (the stream is then independently validated by telemetry_check);
6. **perf_history** — ``tools/perf_history.py`` renders the banked
   trajectory from the repo artifacts (BASELINE.md's dated sweep
   tables; the checkout keeps no per-round records): the
   0.3789-MFU best config is reproduced and no regression flags.

Prints one JSON line of gates; exit 0 = all green, 1 = any gate red.

    MXTPU_TELEMETRY_JSONL=events.jsonl python tools/goodput_smoke.py
"""
from __future__ import annotations

import json
import os
import sys


def _setup_env() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["MXTPU_GOODPUT"] = "1"
    os.environ["MXTPU_GOODPUT_WINDOW"] = "8"


def _build(mx, gluon, parallel, fault, jax):
    mx.random.seed(11)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(32, activation="relu", in_units=16),
            gluon.nn.Dense(8, in_units=32))
    net.initialize(mx.init.Xavier())
    return parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.05},
        mesh=parallel.make_mesh(devices=jax.devices()[:1]),
        guard=fault.StepGuard(policy="warn"))


def _run_phase(mx, gluon, parallel, fault, jax, mio, goodput, onp,
               steps: int, chaos=None):
    """One instrumented train phase over a PrefetchIter; returns the
    (trainer, report) pair. ``begin()`` anchors AFTER warmup so the
    one-off compile wall does not swamp the tiny steady-state phase."""
    tr = _build(mx, gluon, parallel, fault, jax)
    rng = onp.random.RandomState(0)
    x = rng.randn(16 * (steps + 2), 16).astype("float32")
    y = rng.randint(0, 8, (16 * (steps + 2),)).astype("float32")
    tr.step(x[:16], y[:16]).asnumpy()       # init + compile (pre-begin)
    goodput.price(tr, sample_args=(x[:16], y[:16]))
    it = mio.PrefetchIter(
        mio.NDArrayIter(x, y, batch_size=16, last_batch_handle="discard"),
        place=lambda b: tr.place(*(b.data + b.label)), depth=1)
    goodput.begin()
    ctx = chaos if chaos is not None else _null()
    with ctx:
        for i, placed in enumerate(it):
            tr.step(*placed)
            if i + 1 >= steps:
                break
    report = goodput.report()
    it.close()
    return tr, report


class _null:
    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


def main() -> int:
    _setup_env()
    import numpy as onp

    import incubator_mxnet_tpu as mx
    import jax
    from incubator_mxnet_tpu import fault, gluon, parallel, telemetry
    from incubator_mxnet_tpu import io as mio
    from incubator_mxnet_tpu.telemetry import compile_log, goodput

    gates = {}

    # -- phase 1: steady guarded loop — the accounting gates -------------
    tr, rep = _run_phase(mx, gluon, parallel, fault, jax, mio, goodput,
                         onp, steps=24)
    wall = rep["wall_ms"] or 1.0
    gates["steps"] = rep["steps"]
    gates["unattributed_pct"] = rep["unattributed_pct"]
    gates["sums_to_wall"] = rep["attributed_ms"] <= wall * 1.05
    gates["unattributed_lt_10"] = rep["unattributed_pct"] < 10.0
    gates["one_graph_per_step"] = tr.last_step_graphs == 1
    n_ledger = len(compile_log.records("trainer.step"))
    compile_log.mark_warmed("trainer.step")
    try:
        compile_log.assert_zero_post_warmup("trainer.step")
        gates["ledger_clean"] = n_ledger == 1
    except AssertionError:
        gates["ledger_clean"] = False
    mfu = rep.get("mfu") or {}
    gates["measured_mfu"] = mfu.get("measured_mfu")
    gates["predicted_mfu"] = mfu.get("predicted_mfu")
    gates["mfu_reconciled"] = bool(
        mfu.get("measured_mfu") is not None
        and mfu.get("predicted_mfu") is not None
        and mfu.get("divergence_pct") is not None)
    gates["window_events"] = len(telemetry.get_events("goodput.window"))
    gates["windows_emitted"] = gates["window_events"] >= 1

    # -- phase 2: seeded input starvation — attribution proves out -------
    goodput.reset()
    os.environ["MXTPU_GOODPUT"] = "1"       # reset cleared overrides only
    chaos = fault.inject.chaos(seed=7, slow_input=1.0, delay_s=0.02)
    _, rep2 = _run_phase(mx, gluon, parallel, fault, jax, mio, goodput,
                         onp, steps=10, chaos=chaos)
    gates["input_share_pct"] = \
        rep2["categories"]["input_wait"]["share_pct"]
    gates["input_bound_classified"] = \
        rep2["classification"] == "input_bound"

    # -- the banked trajectory renders ------------------------------------
    from tools import perf_history
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    hist = perf_history.collect(root)
    best = hist.get("best_banked") or {}
    rendered = perf_history.render(hist)
    gates["history_best_mfu"] = best.get("mfu")
    gates["perf_history"] = bool(
        best.get("mfu") == 0.3789
        and not hist["regressions"]
        and "0.3789" in rendered)

    ok = all(gates[k] for k in
             ("sums_to_wall", "unattributed_lt_10", "one_graph_per_step",
              "ledger_clean", "mfu_reconciled", "windows_emitted",
              "input_bound_classified", "perf_history"))
    gates["ok"] = ok
    print(json.dumps(gates, sort_keys=True))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
