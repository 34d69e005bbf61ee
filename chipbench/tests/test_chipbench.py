"""The benchmark's own tests: ``pytest chipbench/tests`` (CPU, about a minute).

Tier-1 collects ``tests/`` only; these guard the yardstick itself.
"""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import flops, tracered  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="session")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("jax_cache"))


def run_cell(root, cache_dir, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=cache_dir,
               PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, os.path.join(root, "chipbench", "run.py"), *args],
                       capture_output=True, text=True, env=env, cwd=root, timeout=300)
    lines = p.stdout.strip().splitlines()
    return p, (json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None)


def test_tracered_reduction_by_hand():
    # two lines of one chip overlap in [1.0, 1.5]; gap [2, 3] lies in a host
    # span, gap [3.5, 4] in none; the window opens with the host span at 0.5
    trace = tracered.Trace(
        {"/device:TPU:0": [("fusion.1 fusion f32[8]", 1.0, 1.5),
                           ("flash.2 custom-call tpu_custom_call f32[8]", 1.0, 2.0),
                           ("fusion.4 fusion f32[8]", 3.0, 3.5), ("copy.3 copy f32[8]", 4.0, 4.5)]},
        [("bench.step", 0.5, 0.9), ("serve.pad", 1.9, 3.1), ("bench.step", 4.1, 4.2)])
    assert trace.window_s == pytest.approx(4.0)                 # 0.5 .. 4.5
    assert trace.busy_s == pytest.approx(1.0 + 0.5 + 0.5)       # the overlap counts once
    assert trace.idle_share == pytest.approx(0.5)
    assert trace.by_name() == pytest.approx(
        {"fusion.1 fusion f32[8]": 0.5, "flash.2 custom-call tpu_custom_call f32[8]": 1.0,
         "fusion.4 fusion f32[8]": 0.5, "copy.3 copy f32[8]": 0.5})
    assert trace.seconds_matching(tracered.CUSTOM_CALL) == pytest.approx(1.0)
    assert trace.span_count("bench.step") == 2
    assert trace.breakdown()["device_ops"][0] == ["fusion fusion f32[8]", pytest.approx(1.0)]  # instances added
    gaps = trace.gaps()
    assert [g[0] for g in gaps] == ["serve.pad", "bench.step", "none"]
    assert [g[1] for g in gaps] == pytest.approx([1.0, 0.5, 0.5])
    assert tracered.Trace({}, []).idle_share is None            # nothing traced, nothing claimed
    # an event's name is its whole HLO instruction; a fusion that reads a kernel's result is no kernel
    kernel = ('%transpose_jvp___.51 = (f32[256,512,64]{2,1,0:T(8,128)}, f32[256,512,64]{2,1,0:T(8,128)}) '
              'custom-call(f32[256,512,64]{2,1,0:T(8,128)} %bitcast.1, s32[16,1,512]{2,1,0:T(1,128)S(1)} '
              '%copy-done.8), custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    fusion = '%fusion.7 = (f32[32,512]{1,0:T(8,128)S(1)}, f32[8]{0}) fusion(f32[8]{0} %custom-call.5), kind=kLoop'
    assert tracered.short_name(kernel) == ("transpose_jvp___.51 custom-call tpu_custom_call "
                                          "(f32[256,512,64], f32[256,512,64])")
    assert tracered.short_name(fusion) == "fusion.7 fusion (f32[32,512], f32[8])"
    assert re.search(tracered.CUSTOM_CALL, tracered.short_name(kernel))
    assert not re.search(tracered.CUSTOM_CALL, tracered.short_name(fusion))


def test_flops_term_by_term():
    cfg = json.load(open(os.path.join(ROOT, "chipbench/configs/bert_base_pretrain.json")))
    terms = flops.bert_forward_flops_per_token(cfg, 512, 77)
    per_layer = 2 * (4 * 768 * 768 + 2 * 768 * 3072) + 4 * 512 * 768
    assert per_layer == 15_728_640
    assert terms["layers"] == 12 * per_layer == 188_743_680           # "189 forward"
    assert terms["mlm"] == pytest.approx(2 * (768 * 768 + 768 * 30522) * 77 / 512)
    assert terms["mlm"] == pytest.approx(7.23e6, rel=1e-3)            # "7 in the MLM head"
    assert terms["heads"] == pytest.approx(2 * (768 * 768 + 2 * 768) / 512)
    total = flops.bert_train_flops_per_token(cfg, 512, 77)
    assert total == pytest.approx(587.9e6, rel=1e-3)                  # about 588 MFLOP/token
    # bench.py's 6 * n_params count (716 MFLOP/token) is 22% above it
    assert 716e6 / total == pytest.approx(1.22, abs=0.01)
    ops, nbytes = flops.attention_step_flops_bytes(32, 12, 512, 64, 12)
    assert ops == 12 * 3.5 * 4 * 32 * 12 * 512 * 512 * 64
    assert nbytes == 12 * 8 * 32 * 12 * 512 * 64 * 2
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_seconds(ops, nbytes, peak) == (pytest.approx(ops / 197e12), "compute")


def test_train_batches_come_from_the_seed():
    from chipbench.families import bert
    cfg, traffic = {"vocab_size": 30522}, {"seq_len": 512, "masked": 77}
    a = bert.train_batches(cfg, traffic, 3_000_000_001, 3, 4)
    b = bert.train_batches(cfg, traffic, 3_000_000_001, 3, 4)
    c = bert.train_batches(cfg, traffic, 3_000_000_002, 3, 4)
    assert all(np.array_equal(x, y) for p, q in zip(a, b) for x, y in zip(p, q))
    assert not np.array_equal(a[0][0], c[0][0])
    ids, tt, vl, pos, lab, w, nsp = a[0]
    assert ids.shape == tt.shape == (4, 512) and pos.shape == lab.shape == w.shape == (4, 77)
    assert ids.min() >= 0 and ids.max() < 30522 and (vl == 512).all()
    assert all(len(set(row)) == 77 for row in pos) and pos.min() >= 0 and pos.max() < 512


def test_reference_agrees_with_the_system_at_a_tiny_size():
    import jax
    from chipbench.families import bert
    cfg = json.load(open(os.path.join(ROOT, "chipbench/configs/bert_base_pretrain.json")))
    cfg = {**cfg, **cfg["rehearse"]}                              # bert_2_128_2, vocab 1,000
    system = bert.build_train(cfg, jax.devices()[:1], seed=11)
    batch = bert.train_batches(cfg, {"seq_len": 128, "masked": 19}, 12, 1, 2)[0]
    batch[2][1] = 96
    batch[3][1] %= 96
    got = system.reference_check(batch)
    assert got["ok"], got
    assert got["seq_rel_err"] < bert.SEQ_TOL and got["loss_rel_err"] < bert.LOSS_RTOL
    assert abs(got["loss_reference"] - np.log(1000) - np.log(2)) < 0.5   # random weights: ln V + ln 2


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contract_line_and_exits_3(cell, cache_dir):
    p, line = run_cell(ROOT, cache_dir, "--workload", cell, "--seed", "3000000001",
                       "--seconds", "1", "--trace", "0", "--rehearse")
    assert p.returncode == 3, p.stderr[-2000:]
    assert set(line) == LINE_KEYS and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    want = {m["name"] for m in BENCH["end_to_end"] if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want and all(v["value"] > 0 for v in line["metrics"].values())


def test_refuses_a_cpu_without_rehearse(cache_dir):
    p, line = run_cell(ROOT, cache_dir, "--workload", CELLS[0], "--seed", "1",
                       "--seconds", "1", "--trace", "0")
    assert p.returncode not in (0, 3) and line is None
    assert "needs a TPU" in p.stderr


def test_a_cell_and_a_metric_are_added_as_files_only(tmp_path, cache_dir):
    """A copy of the benchmark gains one workload file, one per-layer reader
    and their ``BENCHMARK.json`` entries; no file that was there is edited."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {f: open(os.path.join(d, f), "rb").read()
              for d, _, fs in os.walk(os.path.join(root, "chipbench")) for f in fs}
    wl = json.load(open(os.path.join(root, "chipbench/workloads", CELLS[0] + ".json")))
    wl["name"] = "bert_base_pretrain.added"
    json.dump(wl, open(os.path.join(root, "chipbench/workloads/bert_base_pretrain.added.json"), "w"))
    with open(os.path.join(root, "chipbench/layer_metrics/steps_done.train.py"), "w") as f:
        f.write('LAYER, UNIT, MOVES = "step driver (host)", "count", '
                '"train_tokens_per_s_per_chip"\n\n\n'
                'def compute(samples, trace):\n    return float(samples["steps"])\n')
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": wl["name"], "config": wl["config"],
                               "traffic": "added", "chips": 1, "why": "data-only proof"})
    bench["per_layer"].append({"name": "steps_done.train", "unit": "count", "better": "higher",
                               "source": "program_counter", "layer": "step driver (host)",
                               "moves": "train_tokens_per_s_per_chip",
                               "workloads": [wl["name"]]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    p, line = run_cell(root, cache_dir, "--workload", wl["name"], "--seed", "5",
                       "--seconds", "1", "--trace", "1", "--rehearse")
    assert p.returncode == 3, p.stderr[-2000:]
    assert line["correct"] and line["metrics"]["steps_done.train"]["value"] == line["attempted"]
    assert "host_step_ms.train" in line["metrics"] and "breakdown" in line
    after = {f: open(os.path.join(d, f), "rb").read()
             for d, _, fs in os.walk(os.path.join(root, "chipbench")) for f in fs
             if "__pycache__" not in d}
    assert all(after[f] == v for f, v in before.items())
