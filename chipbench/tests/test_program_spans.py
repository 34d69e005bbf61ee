"""The readers of the program's own spans, kernel names and compile phases,
each on a trace or a ring written by hand (CPU, under a second)."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import run, tracered  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
KERNELS = ("flash_fwd_ms.train", "flash_dkv_ms.train", "flash_dq_ms.train")
NEW = KERNELS + ("step_place_ms.train", "step_dispatch_ms.train", "step_self_ms.train",
                 "init_state_s.setup", "step_trace_lower_s.setup", "step_cache_read_s.setup")
EMPTY = tracered.Trace({}, [])


def compute(name, samples=None, trace=EMPTY):
    return run.load_metric(name).compute(samples or {}, trace)


def test_each_reader_is_declared_as_it_describes_itself():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        mod, m = run.load_metric(name), declared[name]
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == (m["layer"], m["unit"], m["moves"]), name
        assert m["better"] == "lower" and set(m["workloads"]) <= {w["name"] for w in BENCH["workloads"]}


def test_kernels_by_name_add_up_to_the_custom_call_total():
    # as the v5e's compiler names them (tests/test_tpu_compile.py), two layers, two steps
    ops = [("jvp_flash_fwd_.1 custom-call tpu_custom_call (bf16[384,512,64], f32[384,1,512])", 0.0, 1.0),
           ("jvp_flash_fwd_.2 custom-call tpu_custom_call (bf16[384,512,64], f32[384,1,512])", 1.0, 2.0),
           ("transpose_jvp_flash_bwd_dkv__.1 custom-call tpu_custom_call (bf16[384,512,64], bf16[384,512,64])", 2.0, 5.0),
           ("transpose_jvp_flash_bwd_dq__.1 custom-call tpu_custom_call bf16[384,512,64]", 5.0, 7.0),
           # reads a kernel's result, is none
           ("fusion.7 fusion (f32[32,512], f32[8])", 7.0, 8.0)]
    trace = tracered.Trace({"/device:TPU:0": ops}, [("bench.step", 0.0, 0.1), ("bench.step", 4.0, 4.1)])
    got = [compute(k, trace=trace) for k in KERNELS]
    assert got == pytest.approx([1000.0, 1500.0, 1000.0])                  # ms a step
    assert sum(got) == pytest.approx(trace.seconds_matching(tracered.CUSTOM_CALL) / 2 * 1e3)
    # a program that names no kernel (the parent's trace) gives nothing, and no error
    unnamed = tracered.Trace({"/device:TPU:0": [
        ("transpose_jvp___.25 custom-call tpu_custom_call (f32[384,512,64], f32[384,512,64])", 0.0, 1.0)]},
        [("bench.step", 0.0, 0.1)])
    assert [compute(k, trace=unnamed) for k in KERNELS] == [None, None, None]
    assert [compute(k) for k in KERNELS] == [None, None, None]
    assert [compute(k, trace=None) for k in KERNELS] == [None, None, None]


def test_host_span_medians_on_the_trace_clock():
    host = [("bench.step", 0.0, 0.010), ("step.place", 0.001, 0.003), ("step.dispatch", 0.004, 0.009),
            ("bench.step", 1.0, 1.010), ("step.place", 1.001, 1.002), ("step.dispatch", 1.004, 1.007),
            ("bench.step", 2.0, 2.010), ("step.place", 2.001, 2.007), ("step.dispatch", 2.008, 2.009)]
    trace = tracered.Trace({"/device:TPU:0": [("fusion.1 fusion f32[8]", 0.5, 2.5)]}, host)
    assert compute("step_place_ms.train", trace=trace) == pytest.approx(2.0)
    assert compute("step_dispatch_ms.train", trace=trace) == pytest.approx(3.0)
    for name in ("step_place_ms.train", "step_dispatch_ms.train"):
        assert compute(name) is None and compute(name, trace=None) is None


def test_step_self_time_is_the_frame_less_its_children(monkeypatch):
    from incubator_mxnet_tpu import profiler
    S = profiler.SpanRecord

    def step(i, wall, place, dispatch):
        return [S("step.place", "scope", 0.0, place, "step", 1, i, None),
                S("step.dispatch", "scope", 0.0, dispatch, "step", 1, i, None),
                S("step", "frame", 0.0, wall, None, 0, i, None)]

    ring = (step(1, 900.0, 1.0, 800.0)            # warm-up: not of the window
            + step(2, 7.0, 2.0, 4.0) + step(3, 9.0, 2.0, 4.0) + step(4, 6.5, 2.0, 4.0)
            + [S("io.wait", "scope", 0.0, 50.0, None, 0, 4, None)])
    monkeypatch.setattr(profiler, "recent_spans", lambda: ring)
    assert compute("step_self_ms.train", {"steps": 3}) == pytest.approx(1.0)     # of 1.0, 3.0, 0.5
    assert compute("step_self_ms.train", {"steps": 0}) is None
    monkeypatch.setattr(profiler, "recent_spans", lambda: [])
    assert compute("step_self_ms.train", {"steps": 3}) is None


def test_init_state_span(monkeypatch):
    from incubator_mxnet_tpu import profiler
    monkeypatch.setattr(profiler, "span_records", lambda: {
        "trainer.init_state": {"kind": "scope", "count": 1, "total_ms": 2500.0},
        "step.place": {"kind": "scope", "count": 4, "total_ms": 9.0}})
    assert compute("init_state_s.setup") == pytest.approx(2.5)
    monkeypatch.setattr(profiler, "span_records", lambda: {})
    assert compute("init_state_s.setup") is None


def test_compile_phases_of_the_step_site(monkeypatch):
    from incubator_mxnet_tpu.telemetry import compile_log
    asked = []

    def phase_seconds(site=None):
        asked.append(site)
        return {"trace_s": 4.0, "lower_s": 1.5, "backend_compile_s": 0.75,
                "cache_retrieval_s": 0.25, "events": 900}

    monkeypatch.setattr(compile_log, "phase_seconds", phase_seconds, raising=False)
    assert compute("step_trace_lower_s.setup") == pytest.approx(5.5)
    assert compute("step_cache_read_s.setup") == pytest.approx(1.0)
    assert asked == ["trainer.step", "trainer.step"]
    # nothing compiled at the site, or a program that keeps no such account (the parent)
    monkeypatch.setattr(compile_log, "phase_seconds", lambda site=None: dict.fromkeys(
        ("trace_s", "lower_s", "backend_compile_s", "cache_retrieval_s"), 0.0) | {"events": 0})
    assert compute("step_trace_lower_s.setup") is None and compute("step_cache_read_s.setup") is None
    monkeypatch.delattr(compile_log, "phase_seconds")
    assert compute("step_trace_lower_s.setup") is None and compute("step_cache_read_s.setup") is None
