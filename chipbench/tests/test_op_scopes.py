"""The readers of device time by the program's scopes and of the collectives,
on events and HLO text written by hand (CPU, a few seconds)."""
import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import op_scopes, run, tracered  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
SCOPED = ("optimizer_update_ms.train", "norm_ms.train", "recompute_ms.train",
          "unscoped_share.train")
NEW = SCOPED + ("collective_ms.train", "collective_exposed_ms.train")

#: a compiled module as ``compiled.as_text()`` prints one, cut to what the join reads
TEXT = """HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.9 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/optimizer_update/mul"}
}

%all-reduce-scatter (input: bf16[768,768]) -> bf16[192,768] {
  %input = bf16[768,768]{1,0:T(8,128)(2,1)} parameter(0)
  %all-reduce.3 = bf16[768,768]{1,0:T(8,128)(2,1)} all-reduce(%input), channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%add.1
  ROOT %dynamic-slice.1 = bf16[192,768]{1,0} dynamic-slice(%all-reduce.3, %c), dynamic_slice_sizes={192,768}
}

%fused_gather (param_0.1: bf16[192,768]) -> bf16[768,768] {
  %param_0.1 = bf16[192,768]{1,0} parameter(0)
  ROOT %all-gather.4 = bf16[768,768]{1,0} all-gather(%param_0.1), dimensions={0}
}

ENTRY %main.7 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0:T(256)} parameter(0)
  %fusion.1 = f32[8]{0:T(256)} fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/optimizer_update/mul" stack_frame_id=3}
  %fusion.2 = (f32[8192]{0}, bf16[8192,2048]{1,0}) fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp(afmoe_attention))/checkpoint/rematted_computation/rms_norm/mul"}
  %fusion.3 = bf16[8192,2048]{1,0} fusion(%a), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(step)/jvp(rms_norm_x)/mul"}
  %copy.4 = f32[8]{0} copy(%a)
  %fusion.5 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp(layer_norm))/reduce_sum"}
  %all-gather-start.6 = (bf16[768,768]{1,0}, bf16[3072,768]{1,0}) all-gather-start(%a), channel_id=1, metadata={op_name="jit(step)/optimizer_update/convert_element_type"}
  %all-gather-done.6 = bf16[3072,768]{1,0} all-gather-done(%all-gather-start.6), metadata={op_name="jit(step)/optimizer_update/convert_element_type"}
  %fusion.10 = bf16[192,768]{1,0:T(8,128)(2,1)S(1)} fusion(%a), kind=kCustom, calls=%all-reduce-scatter, metadata={op_name="jit(step)/transpose(jvp())/dot_general"}
  %async-collective-start = (bf16[192,768]{1,0}, bf16[768,768]{1,0}) fusion(%a), kind=kCustom, calls=%fused_gather
  %async-collective-done = bf16[768,768]{1,0} fusion(%async-collective-start), kind=kCustom, calls=%fused_gather
  %all-reduce-done.11 = f32[8]{0} all-reduce-done(%a)
  %copy-start.3 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%a)
  ROOT %reduce-scatter.8 = f32[192]{0} reduce-scatter(%a), channel_id=2, dimensions={0}
}
"""


def make_trace(device, steps=1):
    return tracered.Trace(device, [("bench.step", 0.0, 0.001)] * steps)


def compute(name, trace, samples=None):
    return run.load_metric(name).compute(samples or {}, trace)


def test_each_reader_is_declared_as_it_describes_itself():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        mod, m = run.load_metric(name), declared[name]
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == (m["layer"], m["unit"], m["moves"]), name
        assert m["source"] == "device_trace" and m["better"] == "lower"
        assert set(m["workloads"]) <= set(CELLS)
    assert declared["collective_ms.train"]["workloads"] == ["bert_base_pretrain.dp4"]


def test_the_module_text_parses_to_instruction_opcode_and_op_name():
    names = op_scopes.parse(TEXT)
    assert names["fusion.1"] == ("fusion", "jit(step)/optimizer_update/mul", False)
    assert names["multiply.9"].opcode == "multiply"              # a fusion's body is read too
    assert names["copy.4"] == ("copy", "", False)                # no metadata, no scope
    assert names["reduce-scatter.8"] == ("reduce-scatter", "", True)   # ROOT dropped
    assert names["all-gather-start.6"].collective and names["all-reduce-done.11"].collective
    # a fusion that calls a collective is one, whatever its name
    assert names["fusion.10"].collective
    assert names["async-collective-start"].collective and names["async-collective-done"].collective
    assert not names["copy-start.3"].collective and not names["fusion.5"].collective
    assert "HloModule" not in " ".join(names) and "fused_computation" not in names


@pytest.mark.parametrize("op_name, words", [
    ("jit(step)/transpose(jvp(afmoe_attention))/checkpoint/rematted_computation/rms_norm/mul",
     {"rms_norm", "rematted_computation", "afmoe_attention", "checkpoint", "transpose", "jvp"}),
    ("jit(step)/jvp(rms_norm_x)/mul", {"rms_norm_x"}),
    ("jit(step)/optimizer_update/mul", {"optimizer_update"}),
    ("", set()),
])
def test_scope_words_are_bounded_by_slash_and_parentheses(op_name, words):
    got = op_scopes.segments(op_name)
    assert words <= got
    assert "rms_norm" not in got or "rms_norm" in words          # rms_norm_x is not rms_norm


def test_nested_scopes_a_name_that_must_not_match_and_an_unnamed_fusion():
    # one chip, two traced steps; every event joins the map, copy.4 with no op_name
    trace = make_trace({"/device:TPU:0": [
        ("fusion.1 fusion f32[8]", 0.000, 0.010),                  # optimizer_update
        ("fusion.2 fusion (f32[8192], bf16[8192,2048])", 0.010, 0.016),  # recomputed rms_norm
        ("fusion.3 fusion bf16[8192,2048]", 0.016, 0.020),          # rms_norm_x: no norm
        ("copy.4 copy f32[8]", 0.020, 0.030),                       # no op_name
        ("fusion.5 fusion f32[8]", 0.030, 0.032),                   # layer_norm backward
    ]}, steps=2)
    trace.op_names = op_scopes.parse(TEXT)
    assert compute("optimizer_update_ms.train", trace) == pytest.approx(5.0)     # 10 ms / 2 steps
    assert compute("norm_ms.train", trace) == pytest.approx((6 + 2) / 2)
    assert compute("recompute_ms.train", trace) == pytest.approx(3.0)
    # fusion.3 (4 ms) and copy.4 (10 ms) of 32 ms busy hold no scope of the tuple
    assert compute("unscoped_share.train", trace) == pytest.approx(100 * 14 / 32)
    for name in SCOPED[:3]:
        assert compute(name, trace) <= compute("device_step_ms.train", trace)


def test_an_event_that_does_not_join_holds_no_scope_and_too_few_joins_give_none():
    ops = [("fusion.1 fusion f32[8]", 0.0, 0.6),
           ("fusion.1 copy f32[8]", 0.6, 0.8),                      # same name, other opcode
           ("fusion.99 fusion f32[8]", 0.8, 1.0)]                   # not in the program
    trace = make_trace({"/device:TPU:0": ops})
    trace.op_names = op_scopes.parse(TEXT)
    assert compute("optimizer_update_ms.train", trace) == pytest.approx(600.0)
    assert compute("unscoped_share.train", trace) == pytest.approx(40.0)
    # under half the busy time joins: the text is not the program that ran
    trace = make_trace({"/device:TPU:0": [ops[0][:1] + (0.0, 0.4), ops[2][:1] + (0.4, 1.0)]})
    trace.op_names = op_scopes.parse(TEXT)
    assert all(compute(name, trace) is None for name in SCOPED)


def test_no_map_gives_none_and_no_error(monkeypatch):
    from incubator_mxnet_tpu.telemetry import compile_log
    device = {"/device:TPU:0": [("fusion.1 fusion f32[8]", 0.0, 1.0)]}
    # the program keeps nothing at the site
    monkeypatch.setattr(compile_log, "program_text", lambda site: None, raising=False)
    assert all(compute(name, make_trace(device)) is None for name in SCOPED)
    # a program that cannot hand out its text (the commit before the scopes)
    monkeypatch.delattr(compile_log, "program_text")
    assert all(compute(name, make_trace(device)) is None for name in SCOPED)
    # nothing traced, or no trace: the program is not asked
    asked = []
    monkeypatch.setattr(compile_log, "program_text", lambda site: asked.append(site), raising=False)
    for trace in (None, tracered.Trace({}, []), tracered.Trace({}, [("bench.step", 0, 1)])):
        assert all(compute(name, trace) is None for name in NEW)
    assert asked == []


def test_the_program_is_asked_once_a_trace_at_the_step_site(monkeypatch):
    from incubator_mxnet_tpu.telemetry import compile_log
    asked = []

    def program_text(site):
        asked.append(site)
        return TEXT

    monkeypatch.setattr(compile_log, "program_text", program_text, raising=False)
    trace = make_trace({"/device:TPU:0": [("fusion.1 fusion f32[8]", 0.0, 0.5),
                                         ("fusion.5 fusion f32[8]", 0.5, 1.0)]})
    got = [compute(name, trace) for name in SCOPED]
    assert got == [pytest.approx(500.0), pytest.approx(500.0), None, pytest.approx(0.0)]
    assert asked == ["trainer.step"]


def test_collective_start_done_pairs_and_exposed_time_on_two_chips():
    # chip 0: an asynchronous all-gather's start (1-1.2 ms) and done
    # (4.5-5 ms) with compute between them, a synchronous reduce-scatter
    # 6-7 ms beside nothing; chip 1: an all-gather whose done runs under
    # compute, and a reduce-scatter fusion 6-8 ms half under compute. The
    # span between a start and its done is not collective time. Two steps.
    ms = 1e-3
    chip0 = [("async-collective-start fusion (bf16[192,768], bf16[768,768])", 1 * ms, 1.2 * ms),
             ("fusion.1 fusion f32[8]", 2 * ms, 4 * ms),
             ("async-collective-done fusion bf16[768,768]", 4.5 * ms, 5 * ms),
             ("reduce-scatter.8 reduce-scatter f32[192]", 6 * ms, 7 * ms)]
    chip1 = [("all-gather-start.6 all-gather-start (bf16[768,768], bf16[3072,768])", 1 * ms, 1.1 * ms),
             ("fusion.1 fusion f32[8]", 1.1 * ms, 3 * ms),
             ("all-gather-done.6 all-gather-done bf16[3072,768]", 2.9 * ms, 3 * ms),
             ("fusion.10 fusion bf16[192,768]", 6 * ms, 8 * ms),          # the reduce-scatter fusion
             ("fusion.5 fusion f32[8]", 7 * ms, 9 * ms),
             ("copy-start.3 copy-start (f32[8], f32[8], u32[])", 9 * ms, 9.5 * ms)]   # no collective
    trace = make_trace({"/device:TPU:0": chip0, "/device:TPU:1": chip1}, steps=2)
    trace.op_names = op_scopes.parse(TEXT)
    total, exposed = op_scopes.collective_seconds(trace)
    # chip 0: 0.2 + 0.5 + 1 = 1.7 ms, all of it exposed; chip 1: 0.1 + 0.1 + 2 = 2.2 ms,
    # exposed the start (0.1) and 6-7 of the fusion (1.0)
    assert total == pytest.approx(1.95 * ms) and exposed == pytest.approx(1.4 * ms)
    assert compute("collective_ms.train", trace) == pytest.approx(1.95 / 2)
    assert compute("collective_exposed_ms.train", trace) == pytest.approx(1.4 / 2)
    assert compute("collective_exposed_ms.train", trace) <= compute("collective_ms.train", trace)
    # a -done alone counts for its own event
    alone = make_trace({"/device:TPU:0": [("all-reduce-done.11 all-reduce-done f32[8]", 0.0, 2 * ms)]})
    alone.op_names = op_scopes.parse(TEXT)
    assert op_scopes.collective_seconds(alone) == pytest.approx((2 * ms, 2 * ms))
    # one chip with no collective: nothing to say
    quiet = make_trace({"/device:TPU:0": [("fusion.1 fusion f32[8]", 0.0, 1.0)]})
    quiet.op_names = op_scopes.parse(TEXT)
    assert op_scopes.collective_seconds(quiet) is None
    assert compute("collective_ms.train", quiet) is None
    # without the compiled text a reduce-scatter fusion is a fusion like any other
    trace.op_names = None
    assert compute("collective_ms.train", trace) is None


@pytest.mark.parametrize("cell", CELLS)
def test_existing_readers_read_the_same_with_and_without_the_map(cell):
    """Every per-layer metric the cell had before the scope readers reads
    the same on one trace with and without the op_name map beside it."""
    wl = json.load(open(os.path.join(ROOT, "chipbench/workloads", cell + ".json")))
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    cfg = json.load(open(os.path.join(ROOT, next(c["file"] for c in BENCH["configs"]
                                                  if c["name"] == entry["config"]))))
    traffic = wl["traffic"]
    family = importlib.import_module("chipbench.families." + cfg["family"])
    samples = {"first_call_s": 12.0, "setup_cache": {"compile_requests": 9, "cache_hits": 7},
               "host_step_ms_p50": 5.5, "steps": 20, "chips": entry["chips"],
               "tokens_per_step": traffic["batch"] * traffic["seq_len"],
               "flops_per_token": family.flops_per_token(cfg, traffic),
               "attention": family.attention_roofline_inputs(cfg, traffic),
               "program_bytes": 9e9, "device_kind": "TPU v5 lite"}
    ms = 1e-3
    kernels = ["jvp_flash_fwd_.1", "transpose_jvp_flash_bwd_dkv__.2", "flash_bwd_dq_.3",
               "checkpoint_flash_fwd_win.4", "flash_bwd_dkv_win.5", "flash_fwd_mla.6",
               "moe_gmm.7", "moe_tgmm.8", "moe_rows_gather.9", "short_conv_fwd.10"]
    ops = [(k + " custom-call tpu_custom_call bf16[8,8]", i * ms, (i + 0.2) * ms)
           for i, k in enumerate(kernels)]
    ops += [("fusion.1 fusion f32[8]", 10 * ms, 12 * ms), ("copy.4 copy f32[8]", 12 * ms, 13 * ms),
            ("all-gather-start.6 all-gather-start bf16[8]", 13 * ms, 13.5 * ms),
            ("fusion.5 fusion f32[8]", 14 * ms, 15 * ms)]
    host = [("bench.step", 0.0, 0.5 * ms), ("step.place", 0.1 * ms, 0.2 * ms),
            ("step.dispatch", 0.2 * ms, 0.4 * ms), ("bench.step", 8 * ms, 8.5 * ms)]
    device = {f"/device:TPU:{i}": list(ops) for i in range(entry["chips"])}
    plain, mapped = tracered.Trace(device, host), tracered.Trace(device, host)
    mapped.op_names = op_scopes.parse(TEXT)
    new = [compute(m["name"], mapped, samples) for m in run.metrics_of(BENCH, "per_layer", cell)
           if m["name"] in NEW]
    assert any(v is not None for v in new)
    for m in run.metrics_of(BENCH, "per_layer", cell):
        if m["name"] not in NEW:
            assert compute(m["name"], mapped, samples) == compute(m["name"], plain, samples), m["name"]
    assert mapped.breakdown() == plain.breakdown()
