"""The AFMoE family's share of the yardstick: its operation counts, its
readers on events written by hand, and its reference against the system at
the rehearsal size (CPU; ``pytest chipbench/tests``)."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import flops_afmoe, run, tracered  # noqa: E402

CFG = json.load(open(os.path.join(ROOT, "chipbench/configs/trinity_mini_train.json")))
KERNEL = " custom-call tpu_custom_call (bf16[64,8192,128])"


def test_flops_term_by_term():
    """ISSUE 27's arithmetic: 159 / 110 / 147 / 102 MFLOP a token forward in
    the dense layer, a sliding MoE layer, the full MoE layer and the head."""
    assert flops_afmoe.mean_keys_per_query(8192, 2048) == 1792.125
    assert flops_afmoe.mean_keys_per_query(8192) == 4096.5
    t = flops_afmoe.forward_flops_per_token(CFG, 8192)
    proj, pair = t["attn_proj"] / 5, 4 * 32 * 128
    sliding, full = proj + pair * 1792.125, proj + pair * 4096.5
    moe = (t["router"] + t["shared"] + t["routed"]) / 4
    assert round((sliding + t["dense_ffn"]) / 1e6) == 159
    assert round((sliding + moe) / 1e6) == 110 and round((full + moe) / 1e6) == 147
    assert round(t["head"] / 1e6) == 102
    assert t["routed"] == t["shared"]          # 8 x 16/128 = one expert a token, in expectation
    assert round(flops_afmoe.train_flops_per_token(CFG, 8192) / 1e9, 2) == 2.21


def test_attention_and_grouped_matmul_counts():
    ops, nbytes = flops_afmoe.attention_step_flops_bytes(2, 8192, 32, 4, 128, [2048, None])
    pairs = 2 * 8192 * (1792.125 + 4096.5)
    assert ops == 3.5 * 4 * 32 * 128 * pairs
    assert nbytes == 2 * 4 * (32 + 4) * 128 * 2 * 8192 * 2
    # a window at least as long as the row is the full causal triangle
    assert flops_afmoe.mean_keys_per_query(512, 2048) == flops_afmoe.mean_keys_per_query(512)
    ops, nbytes = flops_afmoe.grouped_matmul_step_flops_bytes([1000, 0], 16, 2048, 1024)
    assert ops == 18.0 * 1000 * 2048 * 1024           # no row, no operation
    assert nbytes > 2 * 3 * 3 * 16 * 2048 * 1024 * 2   # the weights cross three times a layer


def _trace(names, steps=2):
    device = {"/device:TPU:0": [(n, i * 1e-3, i * 1e-3 + ms * 1e-3) for i, (n, ms) in enumerate(names)]}
    host = [("bench.step", i * 0.5, i * 0.5 + 0.4) for i in range(steps)]
    return tracered.Trace(device, host)


def test_readers_tell_windowed_full_and_grouped_kernels_apart():
    trace = _trace([("checkpoint_flash_fwd_win.7" + KERNEL, 4.0),
                    ("transpose_jvp_flash_bwd_dkv_win__.3" + KERNEL, 6.0),
                    ("jvp_flash_fwd_.2" + KERNEL, 3.0),
                    ("transpose_jvp_flash_bwd_dq__.9" + KERNEL, 5.0),
                    ("moe_gmm.11" + KERNEL, 2.0), ("transpose_moe_tgmm.4" + KERNEL, 1.0),
                    ("fusion.5 fusion bf16[2,8192,2048]", 50.0)])
    read = lambda name: run.load_metric(name).compute  # noqa: E731
    assert read("attn_window_ms.train")({}, trace) == pytest.approx(5.0)
    assert read("attn_full_ms.train")({}, trace) == pytest.approx(4.0)
    assert read("moe_gmm_ms.train")({}, trace) == pytest.approx(1.5)
    inputs = dict(batch=2, seq_len=8192, heads=32, kv_heads=4, head_dim=128, windows=[2048, None])
    samples = {"device_kind": "TPU v5 lite", "attention": inputs}
    least_ms = flops_afmoe.attention_step_flops_bytes(**inputs)[0] / 197e12 * 1e3
    assert read("attn_roofline.train")(samples, trace) == pytest.approx(100 * least_ms / 9.0)


def test_routing_readers_count_the_traced_steps_own_rows(monkeypatch):
    """Two traced steps after three warm-up steps and three of the window:
    the readers take steps 6 and 7 of what the steps themselves counted."""
    from chipbench import flops, peaks
    from chipbench.families import afmoe
    rows = [np.full((2, 4), 100.0 * i) for i in range(6)]
    rows += [np.array([[30.0, 10, 10, 10], [20, 20, 20, 20]]),
             np.array([[10.0, 10, 10, 10], [40, 0, 0, 0]])]
    monkeypatch.setattr(afmoe, "STEP_ROWS", rows)
    trace = _trace([("moe_gmm.11" + KERNEL, 2.0), ("transpose_moe_tgmm.4" + KERNEL, 1.0)])
    read = lambda name: run.load_metric(name).compute  # noqa: E731
    samples = {"device_kind": "TPU v5 lite",
               "attention": {"moe": dict(groups=4, hidden=64, ffn=32, layers=2)}}
    # layer 0: (2 + 1) / 2, layer 1: (1 + 4) / 2
    assert read("moe_expert_load_max_over_mean.train")(samples, trace) == pytest.approx(2.5)
    ops, nbytes = flops_afmoe.grouped_matmul_step_flops_bytes([50.0, 60.0], 4, 64, 32)
    least_s = flops.roofline_seconds(ops, nbytes, peaks.peak("TPU v5 lite"))[0]
    assert read("moe_gmm_roofline.train")(samples, trace) == pytest.approx(100 * least_s / 1.5e-3)
    monkeypatch.setattr(afmoe, "STEP_ROWS", rows[:7])       # a step short: nothing to read
    assert read("moe_gmm_roofline.train")(samples, trace) is None


def test_readers_find_nothing_in_a_program_without_the_kernels():
    """The parent commit's step, or BERT's: no such kernel, span or count
    gives None, never an error."""
    trace = _trace([("fusion.5 fusion bf16[32,512,768]", 50.0)])
    bert = {"device_kind": "TPU v5 lite",
            "attention": dict(batch=32, heads=12, seq_len=512, head_dim=64, layers=12)}
    for name in ("attn_window_ms.train", "attn_full_ms.train", "attn_roofline.train",
                 "moe_gmm_ms.train", "moe_gmm_roofline.train",
                 "moe_expert_load_max_over_mean.train"):
        assert run.load_metric(name).compute(bert, trace) is None, name
        assert run.load_metric(name).compute(bert, None) is None, name


def test_batches_and_reference_at_the_rehearsal_size():
    import jax
    from chipbench.families import afmoe
    cfg = {**CFG, **CFG["rehearse"]}
    traffic = {"seq_len": 64, "batch": 2}
    a = afmoe.train_batches(cfg, traffic, 7, 2, 2)
    b = afmoe.train_batches(cfg, traffic, 7, 2, 2)
    assert all((x == y).all() for p, q in zip(a, b) for x, y in zip(p, q))
    ids, pos, vl, lab = a[0]
    assert ids.shape == lab.shape == pos.shape == (2, 64) and (vl == 64).all()
    assert (ids[:, 1:] == lab[:, :-1]).all() and ids.max() < cfg["vocab_size"]
    system = afmoe.build_train(cfg, jax.devices()[:1], seed=11)
    batch = afmoe.train_batches(cfg, traffic, 12, 1, 2)[0]
    batch[2][1] = 48
    batch[3][1] %= 48
    got = system.reference_check(batch)
    assert got["ok"] and got["assignments_dropped"] == 0, got
    assert abs(got["loss_reference"] - np.log(cfg["vocab_size"])) < 0.5
    need = afmoe.attention_roofline_inputs(cfg, traffic)
    assert need["windows"] == [32, 32, 32, 32, None] and need["moe"]["layers"] == 4
