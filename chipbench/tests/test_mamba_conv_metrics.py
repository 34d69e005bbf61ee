"""The readers of the Mamba-2 mixer's causal convolution in the Granite cell:
``mamba_conv_ms.train`` (device time under the scope ``mamba_conv``) and
``mamba_conv_roofline.train`` (the HBM roof's least time over it), on events
written by hand; their count against a count by hand at the cell's shapes;
their declarations by membership (CPU; ``pytest chipbench/tests``)."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import op_scopes, run, tracered  # noqa: E402
from chipbench.families import granite_hybrid  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CFG = json.load(open(os.path.join(ROOT, "chipbench/configs/granite4_h_micro_train.json")))
WORKLOAD = json.load(open(os.path.join(
    ROOT, "chipbench/workloads/granite4_h_micro_train.packed8k.json")))
CELL = "granite4_h_micro_train.packed8k"
NAMES = ("mamba_conv_ms.train", "mamba_conv_roofline.train")
SAMPLES = {"device_kind": "TPU v5 lite",
           "attention": granite_hybrid.attention_roofline_inputs(CFG, WORKLOAD["traffic"])}
#: instruction -> op_name, as the compiled step's text gives them: the kernels
#: (the change) and XLA's fusions (the commit before it) under the scope, a
#: recomputed layer's forward, and the mixer's other work outside it
OP_NAMES = {
    "causal_conv_fwd.1": "jit(step)/jvp(mamba_mixer)/mamba_conv/jit(_forward)/causal_conv_fwd",
    "causal_conv_fwd.2": ("jit(step)/transpose(jvp(checkpoint))/rematted_computation/"
                          "mamba_mixer/mamba_conv/jit(_forward)/causal_conv_fwd"),
    "causal_conv_bwd.3": ("jit(step)/transpose(jvp(mamba_mixer))/mamba_conv/jit(_backward)/"
                          "causal_conv_bwd"),
    "fusion.4": "jit(step)/jvp(mamba_mixer)/mamba_conv/convert_element_type",
    "fusion.5": "jit(step)/jvp(mamba_mixer)/dot_general",
    "ssd_fwd.6": "jit(step)/jvp(mamba_mixer)/ssd_scan/jit(_forward)/ssd_fwd",
    "fusion.7": "jit(step)/jvp(mamba_mixer)/mamba_conv_like/mul",   # another word
}


def _trace(events, steps=2):
    """``events`` as ``(instruction, ms)`` one after another on one chip,
    joined to :data:`OP_NAMES` as the readers join a traced step."""
    device, t = {"/device:TPU:0": []}, 0.0
    for name, ms in events:
        device["/device:TPU:0"].append((f"{name} custom-call bf16[1,8192,4096]", t, t + ms * 1e-3))
        t += ms * 1e-3
    trace = tracered.Trace(device, [("bench.step", i * 0.5, i * 0.5 + 0.4) for i in range(steps)])
    trace.op_names = {name: op_scopes.Op("custom-call", op_name, False)
                      for name, op_name in OP_NAMES.items()}
    return trace


def _read(name, trace, samples=SAMPLES):
    return run.load_metric(name).compute(samples, trace)


def test_the_scope_reader_sums_the_scopes_operations_a_step():
    """Forward, recomputed forward, backward and an XLA fusion under
    ``mamba_conv``: 1 + 1 + 2 + 4 ms over two steps; the projection, the
    scan and a scope that only begins with the word are not counted."""
    trace = _trace([("causal_conv_fwd.1", 1.0), ("causal_conv_fwd.2", 1.0),
                    ("causal_conv_bwd.3", 2.0), ("fusion.4", 4.0), ("fusion.5", 30.0),
                    ("ssd_fwd.6", 6.0), ("fusion.7", 9.0)])
    assert _read("mamba_conv_ms.train", trace) == pytest.approx(4.0)


def test_the_roofline_is_the_hand_count_at_the_cells_shapes():
    """Nine layers of one row of 8,192 tokens over 4,352 channels (64 heads
    of 64, B and C of 128), five bf16 values a (token, channel): 3.21 GB,
    3.92 ms at 819 GB/s. At the 45.73 ms a step XLA's fusions took, 8.6%."""
    assert SAMPLES["attention"]["ssd"]["layers"] == 9
    nbytes = 9 * 8192 * (64 * 64 + 2 * 128) * 5 * 2
    assert round(nbytes / 1e9, 2) == 3.21 and round(nbytes / 819e9 * 1e3, 2) == 3.92
    mod = run.load_metric("mamba_conv_roofline.train")
    assert mod.step_bytes(**SAMPLES["attention"]["ssd"]) == nbytes
    parent = _trace([("fusion.4", 2 * 45.73)])
    assert _read("mamba_conv_roofline.train", parent) == pytest.approx(
        100 * nbytes / 819e9 / 45.73e-3)
    assert round(_read("mamba_conv_roofline.train", parent), 1) == 8.6
    change = _trace([("causal_conv_fwd.1", 4.0), ("causal_conv_fwd.2", 4.0),
                     ("causal_conv_bwd.3", 8.0)])
    assert _read("mamba_conv_roofline.train", change) == pytest.approx(
        100 * nbytes / 819e9 / 8e-3)


def test_both_readers_find_nothing_where_the_scope_is_absent():
    """Another family's step, a trace with no map, no trace or no ``ssd``
    shapes among the samples: None, never an error."""
    other = _trace([("fusion.5", 3.0), ("ssd_fwd.6", 3.0), ("fusion.7", 3.0)])
    conv = _trace([("causal_conv_bwd.3", 3.0)])
    unmapped = _trace([("causal_conv_bwd.3", 3.0)])
    unmapped.op_names = None
    lfm2 = {"device_kind": "TPU v5 lite", "attention": dict(batch=4, seq_len=8192, short_conv={})}
    for name in NAMES:
        assert _read(name, other) is None and _read(name, None) is None, name
        assert _read(name, unmapped) is None, name
        assert _read(name, tracered.Trace({}, [])) is None, name
    assert _read("mamba_conv_roofline.train", conv, lfm2) is None
    assert _read("mamba_conv_roofline.train", conv, {}) is None


def test_the_metrics_are_declared_for_the_granite_cell():
    """By membership: a later PR may append a cell."""
    for name, unit, better in (("mamba_conv_ms.train", "ms", "lower"),
                               ("mamba_conv_roofline.train", "%", "higher")):
        declared = next(m for m in BENCH["per_layer"] if m["name"] == name)
        mod = run.load_metric(name)
        assert CELL in declared["workloads"] and declared["source"] == "device_trace"
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
            declared["layer"], declared["unit"], declared["moves"]) == (
            "kernels", unit, "train_tokens_per_s_per_chip")
        assert declared["better"] == better
