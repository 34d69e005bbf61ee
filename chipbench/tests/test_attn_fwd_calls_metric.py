"""``attn_fwd_calls.train`` on events written by hand: it counts the flash
forward kernels of a step, windowed and full, under whatever prefix the
transformations gave them, and no backward kernel; a program without them,
as the CPU rehearsal is, reads nothing (``pytest chipbench/tests``)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import run, tracered  # noqa: E402

NAME, CELL = "attn_fwd_calls.train", "trinity_mini_train.packed8k"
KERNEL = " custom-call tpu_custom_call (bf16[32,8192,128], f32[32,1,8192])"


def _trace(names, steps=2, planes=1):
    events = [(n, i * 1e-3, i * 1e-3 + 5e-4) for i, n in enumerate(names)]
    host = [("bench.step", i * 0.5, i * 0.5 + 0.4) for i in range(steps)]
    return tracered.Trace({f"/device:TPU:{p}": events for p in range(planes)}, host)


def test_forward_kernels_are_counted_and_backward_ones_are_not():
    reader = run.load_metric(NAME).compute
    names = ["jvp_flash_fwd_win.3" + KERNEL, "checkpoint_flash_fwd.7" + KERNEL,
             "flash_bwd_dq_win.2" + KERNEL, "transpose_jvp_flash_bwd_dkv__.5" + KERNEL,
             "fusion.5 fusion bf16[32,8192,128]", "flash_fwd_like.1 fusion bf16[8,128]"]
    assert reader({}, _trace(names, steps=1)) == 2
    # a count a step, and of one device where the step runs on four
    assert reader({}, _trace(names * 2, steps=2, planes=4)) == 2
    assert reader({}, _trace(names + names[:1], steps=2)) == 1.5


def test_a_program_without_the_kernel_reads_nothing():
    reader = run.load_metric(NAME).compute
    assert reader({}, _trace(["flash_bwd_dq.2" + KERNEL, "fusion.5 fusion bf16[8,128]"])) is None
    assert reader({}, _trace(["jvp_flash_fwd_.3" + KERNEL], steps=0)) is None
    assert reader({}, tracered.Trace({}, [])) is None and reader({}, None) is None


def test_the_metric_is_declared_for_the_trinity_cell():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert bench["per_layer"][-1] == {
        "name": NAME, "unit": "count", "better": "lower", "source": "device_trace",
        "layer": "compiled step", "moves": "train_tokens_per_s_per_chip", "workloads": [CELL]}
    reader = run.load_metric(NAME)
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == ("compiled step", "count",
                                                         "train_tokens_per_s_per_chip")


def test_the_traced_rehearsal_reads_a_count_or_nothing(tmp_path):
    """The Trinity cell's traced rehearsal (CPU: attention goes the XLA way
    and there is no device plane) ends as every rehearsal does, with the
    metric left out of its line or a count in it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=ROOT)
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
                        "--workload", CELL, "--seed", "3000000007", "--seconds", "1",
                        "--trace", "1", "--rehearse"],
                       capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode == 3, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    # the traced path ran to its line (``correct`` is not asked for: the
    # falling-loss check of a one-second window fails beside busy workers)
    assert "host_step_ms.train" in line["metrics"] and "breakdown" in line
    got = line["metrics"].get(NAME)
    assert got is None or (got["unit"] == "count" and got["value"] == pytest.approx(round(got["value"])))
