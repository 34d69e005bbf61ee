"""The ``lfm2_moe`` family's share of the yardstick (LFM2-8B-A1B): its
operation and byte counts against counts by hand, its two readers on events
written by hand, its declarations by membership (a later PR may append a
cell to any list), and its batches and reference against the system at the
rehearsal size (CPU; ``pytest chipbench/tests``)."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import flops_lfm2_moe, run, tracered  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CFG = json.load(open(os.path.join(ROOT, "chipbench/configs/lfm2_8b_a1b_train.json")))
WORKLOAD = json.load(open(os.path.join(
    ROOT, "chipbench/workloads/lfm2_8b_a1b_train.packed8k_b4.json")))
CELL = "lfm2_8b_a1b_train.packed8k_b4"
KERNEL = " custom-call tpu_custom_call (bf16[4,8192,2048])"
SHAPES = dict(batch=4, seq_len=8192, channels=2048, taps=3, layers=4)


def test_flops_term_by_term_at_the_published_widths():
    """ISSUE 34's arithmetic: 432 MFLOP a token forward, of which the one
    attention layer's pairs are 33.6 (under a tenth), the routed experts a
    fifth, and the projections, the dense FFN and the head the rest."""
    t = flops_lfm2_moe.forward_flops_per_token(CFG, 8192)
    assert flops_lfm2_moe.layer_counts(CFG) == {"conv": 4, "attn": 1, "dense": 1, "moe": 4}
    assert t["conv_proj"] == 4 * 2 * 2048 * (3 * 2048 + 2048) == 134_217_728
    assert t["attn_proj"] == 2 * 2048 * (2 * 32 * 64 + 2 * 8 * 64) == 20_971_520
    assert t["attn_pairs"] == 4 * 32 * 64 * 4096.5
    assert t["dense_ffn"] == 6 * 2048 * 7168
    assert t["router"] == 4 * 2 * 2048 * 32
    assert t["routed"] == 4 * 6 * 2048 * 1792 * 4 * 8 / 32     # top 4, a quarter of them held
    assert t["head"] == 2 * 2048 * 16384
    total = sum(t.values())
    assert round(total / 1e6, 1) == 432.5 and "shared" not in t
    assert round(t["attn_pairs"] / total, 3) == 0.078 and round(t["routed"] / total, 2) == 0.20
    assert round(flops_lfm2_moe.train_flops_per_token(CFG, 8192) / 1e9, 3) == 1.298


def test_flops_by_hand_at_the_rehearsal_sizes():
    """C=64, 4 heads over 2 K/V heads of 16, conv / attention / conv with one
    dense layer of 128 and two MoE layers of 8 experts of 32, top 2, 4 held,
    vocabulary 512, L=64."""
    cfg = {**CFG, **CFG["rehearse"]}
    t = flops_lfm2_moe.forward_flops_per_token(cfg, 64)
    assert t == {"conv_proj": 2 * (2 * 64 * 192 + 2 * 64 * 64),
                 "attn_proj": 2 * 64 * 64 * 2 + 2 * 64 * 32 * 2,
                 "attn_pairs": 4 * 4 * 16 * 32.5,
                 "dense_ffn": 3 * 2 * 64 * 128,
                 "router": 2 * 2 * 64 * 8,
                 "routed": 2 * 3 * 2 * 64 * 32 * 2 * 4 / 8,
                 "head": 2 * 64 * 512}
    assert flops_lfm2_moe.train_flops_per_token(cfg, 64) == 3 * sum(t.values())
    ops, nbytes = flops_lfm2_moe.short_conv_step_flops_bytes(2, 64, 64, 3, 2, bytes_per_el=4)
    assert nbytes == 2 * (2 * 64 * 64) * (4 + 7) * 4        # 4 values forward, 7 backward, fp32
    assert ops == 2 * (2 * 64 * 64) * ((2 * 3 + 2) + (6 * 3 + 5))


def test_the_kernels_bytes_set_the_roof():
    ops, nbytes = flops_lfm2_moe.short_conv_step_flops_bytes(**SHAPES)
    assert nbytes == 4 * 32768 * 2048 * 11 * 2 and round(nbytes / 1e9, 2) == 5.91
    assert round(nbytes / 819e9 * 1e3, 2) == 7.21 and ops / 197e12 < 1e-4 < nbytes / 819e9


def _trace(names, steps=2):
    device = {"/device:TPU:0": [(n, i * 1e-3, i * 1e-3 + ms * 1e-3) for i, (n, ms) in enumerate(names)]}
    host = [("bench.step", i * 0.5, i * 0.5 + 0.4) for i in range(steps)]
    return tracered.Trace(device, host)


def test_readers_read_the_short_conv_kernels_and_a_known_time_gives_a_known_share():
    trace = _trace([("checkpoint_short_conv_fwd.7" + KERNEL, 4.0),
                    ("jvp_short_conv_fwd.3" + KERNEL, 4.0),
                    ("transpose_jvp_short_conv_bwd.9" + KERNEL, 12.0),
                    ("short_conv_fwd_like.1 fusion bf16[8,128]", 90.0),      # no kernel
                    ("jvp_flash_fwd_.2" + KERNEL, 30.0),
                    ("moe_gmm.11" + KERNEL, 2.0)])
    read = lambda name: run.load_metric(name).compute  # noqa: E731
    assert read("short_conv_ms.train")({}, trace) == pytest.approx(10.0)
    samples = {"device_kind": "TPU v5 lite", "attention": {"short_conv": SHAPES}}
    least_ms = 4 * 32768 * 2048 * 22 / 819e9 * 1e3
    assert read("short_conv_roofline.train")(samples, trace) == pytest.approx(100 * least_ms / 10.0)
    # the accepted readers this cell is listed under read its one causal call
    # and nothing of the new kernels
    assert read("attn_full_ms.train")({}, trace) == pytest.approx(15.0)
    assert read("attn_fwd_calls.train")({}, trace) == pytest.approx(0.5)
    assert read("moe_gmm_ms.train")({}, trace) == pytest.approx(1.0)
    assert read("moe_rows_ms.train")({}, trace) is None


def test_readers_find_nothing_in_a_program_without_the_kernels():
    """The parent commit's step, or another family's: no ``short_conv``
    kernel event, or no such shapes among the samples, gives None and never
    an error."""
    trinity = {"device_kind": "TPU v5 lite",
               "attention": dict(batch=1, seq_len=8192, heads=32, kv_heads=4, head_dim=128,
                                 windows=[2048, None], moe={})}
    flash = _trace([("jvp_flash_fwd_.2" + KERNEL, 3.0), ("moe_gmm.2" + KERNEL, 3.0)])
    conv = _trace([("short_conv_fwd.2" + KERNEL, 3.0)])
    for name in ("short_conv_ms.train", "short_conv_roofline.train"):
        reader = run.load_metric(name).compute
        assert reader(trinity, flash) is None and reader({}, None) is None, name
        assert reader(trinity, tracered.Trace({}, [])) is None, name
    assert run.load_metric("short_conv_roofline.train").compute(trinity, conv) is None
    assert run.load_metric("short_conv_roofline.train").compute({}, conv) is None


def test_the_metrics_and_the_cell_are_declared():
    """By membership: each list may have grown since."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["config"] == "lfm2_8b_a1b_train" and cell["chips"] == 1
    assert cell["traffic"] == "packed8k_b4" and len(cell["why"]) <= 200
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["source"] == CFG["source"] and entry["file"].endswith("lfm2_8b_a1b_train.json")
    assert entry["reduced"] == CFG["reduced"] and len(entry["why"]) <= 200
    for name, unit in (("short_conv_ms.train", "ms"), ("short_conv_roofline.train", "%")):
        declared = next(m for m in BENCH["per_layer"] if m["name"] == name)
        reader = run.load_metric(name)
        assert CELL in declared["workloads"] and declared["source"] == "device_trace"
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (declared["layer"], unit, declared["moves"])
    listed = {m["name"]: m["workloads"] for m in BENCH["per_layer"] if "workloads" in m}
    for name in ("step_place_ms.train", "step_dispatch_ms.train", "step_self_ms.train",
                 "init_state_s.setup", "step_trace_lower_s.setup", "step_cache_read_s.setup",
                 "moe_gmm_ms.train", "moe_gmm_roofline.train", "moe_rows_ms.train",
                 "moe_expert_load_max_over_mean.train", "attn_fwd_calls.train",
                 "attn_full_ms.train"):
        assert CELL in listed[name] and "trinity_mini_train.packed8k" in listed[name], name
    for name in ("attn_window_ms.train", "attn_roofline.train", "mla_attn_ms.train",
                 "mla_attn_roofline.train", "flash_fwd_ms.train", "flash_roofline.train"):
        assert CELL not in listed[name], name      # their patterns or counts are other kernels'
    # the metrics without a list are every training cell's, this one's too
    unlisted = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert {"mfu.train", "device_step_ms.train", "host_step_ms.train",
            "device_idle_share.train", "first_call_s.setup",
            "compile_cache_misses.setup"} <= unlisted


def test_the_configuration_keeps_the_published_widths():
    published = dict(hidden_size=2048, num_attention_heads=32, num_key_value_heads=8,
                     intermediate_size=7168, moe_intermediate_size=1792, num_experts=32,
                     num_experts_per_tok=4, conv_L_cache=3, conv_bias=False, norm_eps=1e-05,
                     norm_topk_prob=True, rope_theta=1000000, routed_scaling_factor=1,
                     use_expert_bias=True, max_position_embeddings=128000, model_type="lfm2_moe")
    assert {k: CFG[k] for k in published} == published
    assert CFG["reduced"] == ["num_hidden_layers", "num_dense_layers", "layer_types",
                              "experts_held", "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["num_dense_layers"], CFG["experts_held"],
            CFG["vocab_size"]) == (5, 1, 8, 16384)
    assert CFG["layer_types"] == ["conv", "full_attention", "conv", "conv", "conv"]
    pub = CFG["published"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"], pub["num_experts"],
            pub["vocab_size"]) == (24, 2, 32, 65536)
    assert len(pub["layer_types"]) == 24 and pub["layer_types"].count("full_attention") == 6
    # the layers kept are published layer 0 and the whole period 2..5
    assert CFG["layer_types"] == [pub["layer_types"][0]] + pub["layer_types"][2:6]
    assert CFG["vocab_size"] * 4 == pub["vocab_size"] and CFG["experts_held"] * 4 == pub["num_experts"]
    assert "4 chips share each layer" in CFG["deployment"]
    assert set(CFG["reduced"]) <= set(CFG["assumed"])
    assert {"head_dim", "tie_word_embeddings", "rope", "expert_bias", "dtype", "optimizer",
            "initializer_range", "remat", "documents"} <= set(CFG["assumed"])


def test_the_cells_traffic_is_the_issues():
    traffic = WORKLOAD["traffic"]
    assert (traffic["kind"], traffic["seq_len"], traffic["pool"], traffic["in_flight"],
            traffic["trace_steps"]) == ("train_steps", 8192, 8, 2, 5)
    assert traffic["batch"] in (4, 2, 1) and WORKLOAD["chips"] == 1
    # the rows a held expert sees a step, as the why says them
    rows = traffic["batch"] * 8192 * CFG["num_experts_per_tok"] // CFG["num_experts"]
    assert f"{rows:,} rows" in WORKLOAD["why"] and "batch" in WORKLOAD["assumed"]


def test_batches_and_reference_at_the_rehearsal_size():
    import jax
    from chipbench.families import afmoe, lfm2_moe
    cfg = {**CFG, **CFG["rehearse"]}
    traffic = {"seq_len": 64, "batch": 2}
    a = lfm2_moe.train_batches(cfg, traffic, 7, 2, 2)
    b = lfm2_moe.train_batches(cfg, traffic, 7, 2, 2)
    assert all((x == y).all() for p, q in zip(a, b) for x, y in zip(p, q))
    ids, pos, vl, lab = a[0]
    assert ids.shape == lab.shape == pos.shape == (2, 64) and (vl == 64).all()
    assert (ids[:, 1:] == lab[:, :-1]).all() and ids.max() < cfg["vocab_size"]
    system = lfm2_moe.build_train(cfg, jax.devices()[:1], seed=11)
    batch = lfm2_moe.train_batches(cfg, traffic, 12, 1, 2)[0]
    batch[2][1] = 48
    batch[3][1] %= 48
    names = lfm2_moe.grad_tensors(cfg)
    assert {"layer2_conv_weight", "layer2_moe_router_weight", "layer2_moe_experts_w13",
            "layer1_attn_q_weight", "embed_weight"} <= set(names)
    readings = system.reference_readings(batch)
    got = lfm2_moe.compare(readings)
    assert got["ok"] and got["assignments_dropped"] == 0, got
    assert len(got["route_agree_share"]) == 2 and min(got["route_agree_share"]) == 1.0
    assert set(got["grad_rms_err"]) == set(names) and max(got["grad_rms_err"].values()) < 1e-5
    assert got["logits_rms_err"] < 1e-5 and got["loss_rel_err"] < 1e-6
    assert abs(got["loss_reference"] - np.log(cfg["vocab_size"])) < 0.5
    # the control the limits are set against (on the chip, at the published
    # widths): the reference with every matmul operand rounded to fp8, read
    # against the reference proper, is thousands of times further off than
    # the float32 system at this size, in the gradients too
    low = system.reference_readings(batch, operands="float8_e4m3fn")
    off = lfm2_moe.compare(readings, {k: low[k] for k in ("hidden", "logits", "loss", "grads", "idx")})
    assert off["hidden_rms_err"] > 1e-2 > 1e3 * got["hidden_rms_err"], (off, got)
    assert min(off["grad_rms_err"].values()) > 1e-2 > 1e3 * max(got["grad_rms_err"].values())
    need = lfm2_moe.attention_roofline_inputs(cfg, traffic)
    assert need["moe"] == dict(groups=4, hidden=64, ffn=32, layers=2)
    assert need["short_conv"] == dict(batch=2, seq_len=64, channels=64, taps=3, layers=2)
    assert "windows" not in need and "rope_dim" not in need
    # one list of step rows for the decoder families: the routing readers read it
    system.step(batch)
    assert lfm2_moe.STEP_ROWS is afmoe.STEP_ROWS and afmoe.STEP_ROWS[-1].shape == (2, 4)
