#!/usr/bin/env python
"""Numerics-observability smoke — the CI gate for ISSUE 14.

Runs a short chaos training run with the ``grad_blowup`` ramp armed and
``MXTPU_NUMERICS=summary`` on the fused trainer, then asserts the whole
contract end to end:

1. **drift_before_guard** — the first ``numerics.drift`` warning is
   emitted strictly BEFORE the guard's first non-finite verdict (the
   watchdog sees the divergence trajectory, not the corpse);
2. **one_graph_per_step** — with stats enabled the fused step still
   runs exactly ONE jitted executable (``trainer.last_step_graphs``)
   and the compile ledger records exactly one ``trainer.step`` entry
   (``assert_zero_post_warmup`` after marking warmed);
3. **hlo_clean** — ``analysis.hlo.verify`` over the instrumented step
   graph: MX704/MX708 stay clean with stats on;
4. **bundle_renders_drift** — the guard-halt flight bundle carries a
   ``numerics`` section whose ring history PREDATES the trip, and
   ``tools/postmortem.py`` renders it;
5. **calibration_roundtrip** — a second short ``hist``-mode run exports
   a calibration table a ``quantization.Observer`` round-trips
   byte-for-byte.

Prints one JSON line of gates; exit 0 = all green, 1 = any gate red.
With ``MXTPU_NUMERICS`` unset (the default) the traced graphs are those
of an uninstrumented build (``tests/test_numerics.py::
test_trainer_off_path_unchanged``).

    MXTPU_TELEMETRY_JSONL=events.jsonl python tools/numerics_smoke.py
"""
# mxlint: disable-file=MX401 — a throwaway chaos smoke whose run is
# SUPPOSED to die (the guard halt IS the gate); checkpointing it would
# only slow the CI job down
from __future__ import annotations

import json
import os
import sys
import tempfile
import warnings


def _setup_env(flight_dir: str) -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    prev = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in prev:
        os.environ["XLA_FLAGS"] = (
            prev + " --xla_force_host_platform_device_count=8").strip()
    os.environ["MXTPU_NUMERICS"] = "summary"
    os.environ["MXTPU_NUMERICS_EVERY"] = "1"
    os.environ["MXTPU_FLIGHT_DIR"] = flight_dir


def _build_trainer(mx, gluon, parallel, fault, prefix: str):
    mx.random.seed(11)
    net = gluon.nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(gluon.nn.Dense(32, activation="relu", in_units=16),
                gluon.nn.Dense(8, in_units=32))
    net.initialize(mx.init.Xavier())
    guard = fault.StepGuard(policy="halt")
    return parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.05}, mesh=parallel.make_mesh(dp=4, tp=2),
        guard=guard)


def main() -> int:
    flight_dir = tempfile.mkdtemp(prefix="numerics-smoke-flight-")
    _setup_env(flight_dir)
    import numpy as onp

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import fault, gluon, parallel, telemetry
    from incubator_mxnet_tpu.analysis import hlo
    from incubator_mxnet_tpu.telemetry import compile_log, flight, numerics

    gates = {}
    rng = onp.random.RandomState(0)
    x = rng.randn(16, 16).astype("float32")
    y = rng.randint(0, 8, (16,)).astype("float32")

    # -- phase 1: summary mode under grad_blowup chaos -------------------
    tr = _build_trainer(mx, gluon, parallel, fault, "numsmoke_")
    halted = False
    with fault.inject.chaos(seed=7, grad_blowup=1.0, blowup_factor=16.0), \
            warnings.catch_warnings():
        warnings.simplefilter("ignore")
        try:
            for _ in range(120):
                tr.step(x, y)
        except fault.NonFiniteError:
            halted = True
    drift = telemetry.get_events("numerics.drift")
    guard_evs = telemetry.get_events("guard")
    gates["halted"] = halted
    gates["drift_events"] = len(drift)
    gates["drift_before_guard"] = bool(
        drift and guard_evs and drift[0].seq < guard_evs[0].seq)
    gates["one_graph_per_step"] = tr.last_step_graphs == 1
    n_ledger = len(compile_log.records("trainer.step"))
    compile_log.mark_warmed("trainer.step")
    try:
        compile_log.assert_zero_post_warmup("trainer.step")
        ledger_clean = n_ledger == 1
    except AssertionError:
        ledger_clean = False
    gates["ledger_one_compile"] = ledger_clean

    # MX704/MX708 clean with stats enabled (the instrumented graph)
    rep = hlo.verify(tr, sample_args=(x, y))
    bad = [d.code for d in rep.diagnostics
           if d.code in ("MX704", "MX708") and d.severity == "error"]
    gates["hlo_clean"] = rep.ok and not bad

    # -- the bundle carries the drift trajectory and renders -------------
    bundles = flight.list_bundles(flight_dir)
    gates["bundle_written"] = bool(bundles)
    renders = False
    predates = False
    if bundles:
        doc = flight.load(bundles[-1])
        num = doc.get("numerics") or {}
        sites = num.get("sites") or {}
        trip_step = tr.num_update
        predates = any(
            len(recs) >= 2 and recs[0].get("step") is not None
            and recs[0]["step"] < trip_step
            for recs in sites.values())
        from tools import postmortem
        renders = postmortem.main([bundles[-1]]) == 0
        rendered = postmortem.render(doc)
        renders = renders and "numerics" in rendered
    gates["bundle_renders_drift"] = bool(renders and predates)

    # -- phase 2: hist mode -> calibration -> Observer round-trip --------
    # numerics-only reset: a full telemetry.reset() would reinstall the
    # JSONL sink, truncating phase 1's drift/guard evidence out of the
    # stream telemetry_check validates
    numerics.reset()
    os.environ["MXTPU_NUMERICS"] = "hist"
    tr2 = _build_trainer(mx, gluon, parallel, fault, "numsmokeh_")
    for _ in range(6):
        tr2.step(x, y)
    table = numerics.calibration_table()
    from incubator_mxnet_tpu import quantization
    obs = quantization.Observer(table)
    gates["calibration_sites"] = len(table)
    gates["calibration_roundtrip"] = bool(table) \
        and obs.to_table() == table \
        and all(hi > 0 for _, hi in obs.ranges().values())

    ok = all(gates[k] for k in
             ("halted", "drift_before_guard", "one_graph_per_step",
              "ledger_one_compile", "hlo_clean", "bundle_written",
              "bundle_renders_drift", "calibration_roundtrip"))
    gates["ok"] = ok
    print(json.dumps(gates, sort_keys=True))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
