"""``moe_rows_ms.train`` on events written by hand: it reads the row kernels
by name, apart from the grouped matmuls, and gives ``None`` for a program
that has none (``pytest chipbench/tests``)."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import run, tracered  # noqa: E402

KERNEL = " custom-call tpu_custom_call (bf16[69632,2048])"


def _trace(names, steps=2):
    device = {"/device:TPU:0": [(n, i * 1e-3, i * 1e-3 + ms * 1e-3) for i, (n, ms) in enumerate(names)]}
    host = [("bench.step", i * 0.5, i * 0.5 + 0.4) for i in range(steps)]
    return tracered.Trace(device, host)


def test_the_row_kernels_are_read_apart_from_the_grouped_matmuls():
    trace = _trace([("checkpoint_moe_rows_pack.3" + KERNEL, 1.0),
                    ("checkpoint_moe_rows_gather.4" + KERNEL, 2.0),
                    ("jvp_moe_rows_gate.2" + KERNEL, 0.5),
                    ("transpose_jvp_moe_rows_gate_bwd.6" + KERNEL, 0.5),
                    ("moe_rows_combine.8" + KERNEL, 3.0), ("moe_rows_dot.9" + KERNEL, 1.0),
                    ("moe_gmm.11" + KERNEL, 20.0), ("transpose_moe_tgmm.4" + KERNEL, 10.0),
                    ("fusion.5 fusion bf16[69632,2048]", 50.0)])
    rows, gmm = (run.load_metric(n).compute({}, trace)
                 for n in ("moe_rows_ms.train", "moe_gmm_ms.train"))
    assert rows == pytest.approx(4.0) and gmm == pytest.approx(15.0)


def test_a_program_without_the_row_kernels_reads_nothing():
    reader = run.load_metric("moe_rows_ms.train").compute
    assert reader({}, _trace([("moe_gmm.11" + KERNEL, 20.0),
                              ("fusion.5 fusion bf16[65536,2048]", 50.0)])) is None
    assert reader({}, None) is None


def test_the_metric_is_declared_for_the_trinity_cell():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(m for m in bench["per_layer"] if m["name"] == "moe_rows_ms.train")
    assert entry == {"name": "moe_rows_ms.train", "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": "router",
                     "moves": "train_tokens_per_s_per_chip",
                     "workloads": ["trinity_mini_train.packed8k"]}
    reader = run.load_metric("moe_rows_ms.train")
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == (entry["layer"], entry["unit"], entry["moves"])
