"""opperf microbenchmark suite sanity (reference: benchmark/opperf/)."""
import json
import subprocess
import sys
import os

import incubator_mxnet_tpu  # noqa: F401  (repo on path)
from benchmark.opperf import run, run_performance_test, op_configs

import numpy as onp


def test_run_subset():
    rows = run(["broadcast_add", "sqrt"], iters=2)
    assert len(rows) == 2
    for r in rows:
        assert "error" not in r, r
        assert r["fwd_ms"] > 0
        assert r["bwd_ms"] > 0
        assert "gflops" in r


class _ShapesOnly:
    """Stands in for the builders' ``RandomState``: what they draw has the
    asked shape and dtype and no element of its own (a zero-strided view).
    Drawn for real, the operands are over a billion normals through one
    generator (``LayerNorm`` alone is 64x512x32768): five minutes, to
    check names and a ``dict``."""

    class _Drawn:
        def __init__(self, shape):
            self.shape = tuple(shape)

        def astype(self, dtype):
            return onp.broadcast_to(onp.zeros((), dtype), self.shape)

    def randn(self, *shape):
        return self._Drawn(shape)

    def randint(self, low, high, size):
        return self._Drawn(size)


def test_every_config_entry_is_well_formed(monkeypatch):
    import benchmark.opperf as opperf
    monkeypatch.setattr(opperf, "_rng", _ShapesOnly)
    cfg = op_configs()
    from incubator_mxnet_tpu.ops.registry import OPS
    for name, cases in cfg.items():
        assert name in OPS, f"config references unregistered op {name}"
        for case, builder, flops in cases:
            args, kwargs = builder()
            assert isinstance(kwargs, dict)


def test_run_performance_test_api():
    row = run_performance_test(
        "sqrt", {"data": onp.abs(onp.random.randn(64, 64)).astype("float32")},
        iters=2)
    assert row["op"] == "sqrt" and row["fwd_ms"] > 0


def test_unknown_op_reports_error_row():
    rows = run(["definitely_not_an_op"], iters=1)
    assert rows[0]["error"] == "no benchmark config"


def test_rows_flow_through_telemetry_jsonl(tmp_path):
    # the satellite contract: opperf results ride the telemetry JSONL
    # stream, validated by the same checker as the serve bench
    from incubator_mxnet_tpu import telemetry
    from tools.telemetry_check import check_stream

    telemetry.reset()
    path = tmp_path / "opperf_events.jsonl"
    telemetry.install_jsonl(str(path))
    try:
        rows = run(["sqrt"], iters=1)
        assert rows and "error" not in rows[0]
        evs = telemetry.get_events("opperf.result")
        assert evs and evs[-1].fields["op"] == "sqrt"
        assert evs[-1].fields["fwd_ms"] > 0
    finally:
        telemetry.reset()          # closes + unsubscribes the sink
    problems = check_stream(path.read_text().splitlines(), name=str(path))
    assert problems == [], problems
