"""The ``deepseek_v3`` family's share of the yardstick (Kanana-2-30B-A3B): its
operation counts term by term, its two readers on events written by hand,
and its batches and reference against the system at the rehearsal size (CPU;
``pytest chipbench/tests``)."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import flops_deepseek_v3, run, tracered  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CFG = json.load(open(os.path.join(ROOT, "chipbench/configs/kanana2_30b_a3b_train.json")))
CELL = "kanana2_30b_a3b_train.packed8k"
KERNEL = " custom-call tpu_custom_call (bf16[32,8192,128], f32[32,1,8192])"
SHAPES = dict(batch=1, seq_len=8192, heads=32, nope_dim=128, rope_dim=64, v_dim=128, layers=6)


def test_flops_term_by_term():
    """ISSUE 31's arithmetic: 212.1 / 163.1 / 65.7 MFLOP a token forward in
    the dense layer, a MoE layer and the head; 1,093 in all, 46% of it
    attention pairs and 75% of it MLA; 3.28 GFLOP a token trained."""
    t = flops_deepseek_v3.forward_flops_per_token(CFG, 8192)
    proj, pairs = t["attn_proj"] / 6, t["attn_pairs"] / 6
    assert proj == 2 * (2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048) == 52_690_944
    assert pairs == 2 * 32 * (192 + 128) * 4096.5
    moe = (t["router"] + t["shared"] + t["routed"]) / 5
    assert round((proj + pairs + t["dense_ffn"]) / 1e6, 1) == 212.1
    assert round((proj + pairs + moe) / 1e6, 1) == 163.1 and round(moe / 1e6, 1) == 26.5
    assert round(t["head"] / 1e6, 1) == 65.7
    assert t["shared"] / 5 == 6 * 2048 * 768 * 2            # two shared experts, every token
    assert t["routed"] / 5 == 6 * 2048 * 768 * 6 * 16 / 128    # top 6, an eighth of them held
    total = sum(t.values())
    assert round(total / 1e6) == 1093
    assert round(t["attn_pairs"] / total, 2) == 0.46
    assert round((t["attn_pairs"] + t["attn_proj"]) / total, 2) == 0.75
    assert round(flops_deepseek_v3.train_flops_per_token(CFG, 8192) / 1e9, 2) == 3.28
    assert flops_deepseek_v3.moe_layers(CFG) == 5
    assert flops_deepseek_v3.moe_layers(dict(CFG, num_hidden_layers=48)) == 47


def test_the_kernels_operations_and_bytes():
    """A (query, key, head) pair costs 2 (192 + 128) forward and 2 (3 x 192 +
    2 x 128) backward, 2,304 in all; the shared key's bytes count once."""
    ops, nbytes = flops_deepseek_v3.mla_attention_step_flops_bytes(**SHAPES)
    assert ops == 6 * 2304 * 32 * 4096.5 * 8192
    assert round(ops / 1e12, 2) == 14.85 and round(ops / 197e12 * 1e3, 1) == 75.4
    per_token = 32 * (2 * 192 + 6 * 128) + 2 * 64
    assert nbytes == 6 * per_token * 8192 * 2
    # the compute roof sets it by far: the bytes would take under 5 ms
    assert nbytes / 819e9 < 0.005 < ops / 197e12


def _trace(names, steps=2):
    device = {"/device:TPU:0": [(n, i * 1e-3, i * 1e-3 + ms * 1e-3) for i, (n, ms) in enumerate(names)]}
    host = [("bench.step", i * 0.5, i * 0.5 + 0.4) for i in range(steps)]
    return tracered.Trace(device, host)


def test_readers_read_the_mla_kernels_and_no_other_flash_kernel():
    trace = _trace([("checkpoint_flash_fwd_mla.7" + KERNEL, 4.0),
                    ("transpose_jvp_flash_bwd_dkv_mla__.3" + KERNEL, 6.0),
                    ("transpose_jvp_flash_bwd_dq_mla.9" + KERNEL, 5.0),
                    ("jvp_flash_fwd_.2" + KERNEL, 30.0),                    # a full call
                    ("checkpoint_flash_fwd_win.4" + KERNEL, 50.0),          # a windowed one
                    ("transpose_jvp_flash_bwd_dq_win__.5" + KERNEL, 70.0),
                    ("flash_fwd_mla_like.1 fusion bf16[8,128]", 90.0),      # no kernel
                    ("moe_gmm.11" + KERNEL, 2.0)])
    read = lambda name: run.load_metric(name).compute  # noqa: E731
    assert read("mla_attn_ms.train")({}, trace) == pytest.approx(7.5)
    samples = {"device_kind": "TPU v5 lite", "attention": dict(SHAPES, moe={})}
    least_ms = flops_deepseek_v3.mla_attention_step_flops_bytes(**SHAPES)[0] / 197e12 * 1e3
    assert read("mla_attn_roofline.train")(samples, trace) == pytest.approx(100 * least_ms / 7.5)
    # and the reverse: the windowed kernels' reader takes no _mla name, and
    # the forward counter takes the _mla forward for what it is
    assert read("attn_window_ms.train")({}, trace) == pytest.approx(60.0)
    assert read("attn_fwd_calls.train")({}, trace) == pytest.approx(1.5)


def test_readers_find_nothing_in_a_program_without_the_kernels():
    """The parent commit's step, Trinity's or BERT's: no ``_mla`` kernel, or
    another family's shapes, gives None and never an error."""
    trinity = {"device_kind": "TPU v5 lite",
               "attention": dict(batch=1, seq_len=8192, heads=32, kv_heads=4, head_dim=128,
                                 windows=[2048, None])}
    flash = _trace([("jvp_flash_fwd_.2" + KERNEL, 3.0), ("flash_bwd_dq_win.2" + KERNEL, 3.0)])
    mla = _trace([("flash_fwd_mla.2" + KERNEL, 3.0)])
    for name in ("mla_attn_ms.train", "mla_attn_roofline.train"):
        reader = run.load_metric(name).compute
        assert reader(trinity, flash) is None and reader({}, None) is None, name
        assert reader(trinity, tracered.Trace({}, [])) is None, name
    assert run.load_metric("mla_attn_roofline.train").compute(trinity, mla) is None


def test_the_metrics_and_the_cell_are_declared():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["config"] == "kanana2_30b_a3b_train" and cell["chips"] == 1
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["source"] == CFG["source"] and entry["file"].endswith("kanana2_30b_a3b_train.json")
    assert entry["reduced"] == CFG["reduced"] == ["num_hidden_layers", "experts_held", "vocab_size"]
    for name, unit in (("mla_attn_ms.train", "ms"), ("mla_attn_roofline.train", "%")):
        declared = next(m for m in BENCH["per_layer"] if m["name"] == name)
        reader = run.load_metric(name)
        assert declared["workloads"] == [CELL] and declared["source"] == "device_trace"
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (declared["layer"], unit, declared["moves"])
    # the accepted metrics that read what this cell's program has list it,
    # each still listing the cells it had
    listed = {m["name"]: m["workloads"] for m in BENCH["per_layer"] if "workloads" in m}
    for name in ("attn_fwd_calls.train", "moe_gmm_ms.train", "moe_gmm_roofline.train",
                 "moe_rows_ms.train", "moe_expert_load_max_over_mean.train",
                 "step_place_ms.train", "step_trace_lower_s.setup"):
        assert "trinity_mini_train.packed8k" in listed[name] and CELL in listed[name], name
    for name in ("attn_full_ms.train", "attn_window_ms.train", "attn_roofline.train",
                 "flash_fwd_ms.train", "flash_roofline.train"):
        assert CELL not in listed[name], name      # their patterns or counts are other kernels'


def test_the_configuration_keeps_the_published_widths():
    published = dict(hidden_size=2048, num_attention_heads=32, kv_lora_rank=512, q_lora_rank=None,
                     qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                     intermediate_size=6144, moe_intermediate_size=768, n_routed_experts=128,
                     num_experts_per_tok=6, n_shared_experts=2, routed_scaling_factor=2.448,
                     first_k_dense_replace=1, rope_theta=1000000, rms_norm_eps=1e-06,
                     n_group=1, topk_group=1, norm_topk_prob=True, rope_interleave=True)
    assert {k: CFG[k] for k in published} == published
    assert (CFG["num_hidden_layers"], CFG["experts_held"], CFG["vocab_size"]) == (6, 16, 16032)
    assert CFG["published"] == dict(num_hidden_layers=48, n_routed_experts=128, vocab_size=128256)
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"]
    assert "eight chips share each layer" in CFG["deployment"]
    assert set(CFG["reduced"]) <= set(CFG["assumed"])


def test_batches_and_reference_at_the_rehearsal_size():
    import jax
    from chipbench.families import afmoe, deepseek_v3
    cfg = {**CFG, **CFG["rehearse"]}
    traffic = {"seq_len": 64, "batch": 2}
    a = deepseek_v3.train_batches(cfg, traffic, 7, 2, 2)
    b = deepseek_v3.train_batches(cfg, traffic, 7, 2, 2)
    assert all((x == y).all() for p, q in zip(a, b) for x, y in zip(p, q))
    ids, pos, vl, lab = a[0]
    assert ids.shape == lab.shape == pos.shape == (2, 64) and (vl == 64).all()
    assert (ids[:, 1:] == lab[:, :-1]).all() and ids.max() < cfg["vocab_size"]
    system = deepseek_v3.build_train(cfg, jax.devices()[:1], seed=11)
    batch = deepseek_v3.train_batches(cfg, traffic, 12, 1, 2)[0]
    batch[2][1] = 48
    batch[3][1] %= 48
    readings = system.reference_readings(batch)
    got = deepseek_v3.compare(readings)
    assert got["ok"] and got["assignments_dropped"] == 0, got
    assert len(got["route_agree_share"]) == 2 and min(got["route_agree_share"]) == 1.0
    assert abs(got["loss_reference"] - np.log(cfg["vocab_size"])) < 0.5
    # the control the limits are set against (on the chip, at the published
    # widths): the reference with every matmul operand rounded to fp8, read
    # against the reference proper, is thousands of times further off than
    # the float32 system at this size
    low = system.reference_readings(batch, operands="float8_e4m3fn")
    off = deepseek_v3.compare(readings, dict(hidden=low["hidden"], loss=low["loss"], idx=low["idx"]))
    assert off["hidden_rms_err"] > 1e-2 > 1e3 * got["hidden_rms_err"], (off, got)
    assert min(off["route_agree_share"]) < 1.0
    need = deepseek_v3.attention_roofline_inputs(cfg, traffic)
    assert need["rope_dim"] == 8 and need["layers"] == 3 and need["moe"]["layers"] == 2
    assert need["moe"]["groups"] == 4 and "windows" not in need
    # one list of step rows for both families: the routing readers read it
    system.step(batch)
    assert deepseek_v3.STEP_ROWS is afmoe.STEP_ROWS and afmoe.STEP_ROWS[-1].shape == (2, 4)
