"""The ``granite_hybrid`` family's share of the yardstick (Granite-4.0-H-Micro):
its operation and byte counts against counts by hand, its readers on events
written by hand, its declarations by membership (a later PR may append a
cell to any list), the configuration against the published one, and the
cell's checks at the rehearsal size: batches, reference against the system,
the fp8 control, the worst-chunk reading (CPU; ``pytest chipbench/tests``)."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import flops_granite_hybrid as flops_gh, run, tracered  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CFG = json.load(open(os.path.join(ROOT, "chipbench/configs/granite4_h_micro_train.json")))
WORKLOAD = json.load(open(os.path.join(
    ROOT, "chipbench/workloads/granite4_h_micro_train.packed8k.json")))
CELL = "granite4_h_micro_train.packed8k"
KERNEL = " custom-call tpu_custom_call (bf16[1,8192,4096])"
SHAPES = dict(batch=1, seq_len=8192, heads=64, head_dim=64, groups=1, state=128, chunk=256,
              layers=9)


def test_flops_term_by_term_at_the_published_widths():
    """The arithmetic at the published widths: 1.61 GFLOP a token forward, 4.82 forward and
    backward; the nine Mamba layers carry 87% of it, the scan itself 3.18
    MFLOP a token and layer (26 GFLOP a layer at 8,192 tokens)."""
    t = flops_gh.forward_flops_per_token(CFG, 8192)
    assert flops_gh.layer_counts(CFG) == {"mamba": 9, "attn": 1}
    assert t["mamba_proj"] == 9 * 2 * 2048 * (8512 + 4096) == 464_781_312
    assert flops_gh.scan_flops_per_token(64, 64, 1, 128, 256)[0] == (
        2 * 128 * 128.5 + 64 * 2 * 64 * 128.5 + 64 * 4 * 128 * 64) == 3_182_720
    assert t["scan"] == 9 * 3_182_720
    assert t["attn_proj"] == 2 * 2048 * (2 * 32 * 64 + 2 * 8 * 64) == 20_971_520
    assert t["attn_pairs"] == 4 * 32 * 64 * 4096.5
    assert t["mlp"] == 10 * 6 * 2048 * 8192
    assert t["head"] == 2 * 2048 * 12544
    total = sum(t.values())
    assert total == 1_605_969_024
    mamba = t["mamba_proj"] + t["scan"] + 9 * 6 * 2048 * 8192
    assert round(mamba / total, 2) == 0.87
    assert round(flops_gh.train_flops_per_token(CFG, 8192) / 1e9, 3) == 4.818
    assert round(8192 * 3_182_720 / 1e9, 1) == 26.1


def test_flops_and_bytes_by_hand_at_a_small_shape():
    """4 heads of 32, one group, a state of 16, chunks of 8: a chunk's
    causal pairs are 8 x 9 / 2, 4.5 a token."""
    fwd, bwd = flops_gh.scan_flops_per_token(4, 32, 1, 16, 8)
    assert fwd == 2 * 16 * 4.5 + 4 * 2 * 32 * 4.5 + 4 * 4 * 16 * 32 == 9488
    assert bwd == 4 * 4 * 32 * 4.5 + 4 * 16 * 4.5 + 4 * 8 * 16 * 32 == 18976
    f_bytes, b_bytes = flops_gh.scan_bytes_per_token(4, 32, 1, 16)
    assert f_bytes == 2 * 128 * 2 + 4 * 4 + 2 * 16 * 2 == 592          # x, y; dt fp32; B, C
    assert b_bytes == 3 * 128 * 2 + 2 * 4 * 4 + 4 * 16 * 2 == 928      # x, dy, dx; dt, d dt; ...
    ops, nbytes = flops_gh.ssd_step_flops_bytes(2, 64, 4, 32, 1, 16, 8, layers=3)
    assert ops == 3 * 2 * 64 * (9488 + 18976) and nbytes == 3 * 2 * 64 * (592 + 928)
    cfg = {**CFG, **CFG["rehearse"]}
    t = flops_gh.forward_flops_per_token(cfg, 32)
    assert t == {"mamba_proj": 2 * 2 * 64 * (3 * 128 + 2 * 16 + 4), "scan": 2 * 9488,
                 "attn_proj": 2 * 64 * (2 * 64 + 2 * 32), "attn_pairs": 4 * 4 * 16 * 16.5,
                 "mlp": 3 * 6 * 64 * 128, "head": 2 * 64 * 512}


def test_the_scans_least_time_at_the_cells_shape():
    """A Mamba layer's scan forward and backward: 78.2 GFLOP and 354 MB at
    8,192 tokens, so the HBM roof sets its least time, 0.43 ms a layer."""
    ops, nbytes = flops_gh.ssd_step_flops_bytes(**SHAPES)
    assert round(ops / 9 / 1e9, 1) == 78.2 and round(nbytes / 9 / 1e6, 1) == 354.4
    assert nbytes / 819e9 > ops / 197e12
    assert round(nbytes / 819e9 * 1e3, 2) == 3.89


def _trace(names, steps=2):
    device = {"/device:TPU:0": [(n, i * 1e-3, i * 1e-3 + ms * 1e-3) for i, (n, ms) in enumerate(names)]}
    host = [("bench.step", i * 0.5, i * 0.5 + 0.4) for i in range(steps)]
    return tracered.Trace(device, host)


def test_readers_read_the_ssd_kernels_and_a_known_time_gives_a_known_share():
    trace = _trace([("checkpoint_ssd_fwd.7" + KERNEL, 6.0),
                    ("jvp_ssd_fwd.3" + KERNEL, 6.0),
                    ("transpose_jvp_ssd_bwd.9" + KERNEL, 18.0),
                    ("ssd_like.1 fusion bf16[8,128]", 90.0),          # no kernel
                    ("jvp_flash_fwd_.2" + KERNEL, 30.0),
                    ("short_conv_fwd.11" + KERNEL, 2.0)])
    read = lambda name: run.load_metric(name).compute  # noqa: E731
    from chipbench.families import granite_hybrid
    assert read("ssd_scan_ms.train")({}, trace) == pytest.approx(15.0)
    samples = {"device_kind": "TPU v5 lite",
               "attention": granite_hybrid.attention_roofline_inputs(CFG, WORKLOAD["traffic"])}
    assert samples["attention"]["ssd"] == SHAPES
    least_ms = 9 * 8192 * (17152 + 26112) / 819e9 * 1e3
    assert read("ssd_scan_roofline.train")(samples, trace) == pytest.approx(100 * least_ms / 15.0)
    # the accepted readers this cell is listed under read its one causal call
    # and nothing of the new kernels: one full causal layer of 32 query heads
    # over 8 K/V heads of 64, 3.5 x 4 x 32 x 64 x 4,096.5 x 8,192 operations
    # (the compute roof sets its least time, 4.88 ms)
    assert read("attn_full_ms.train")({}, trace) == pytest.approx(15.0)
    ops = 3.5 * 4 * 32 * 64 * 4096.5 * 8192
    assert round(ops / 197e12 * 1e3, 2) == 4.88 and ops / 197e12 > 4 * 40 * 64 * 8192 * 2 / 819e9
    assert read("attn_roofline.train")(samples, trace) == pytest.approx(
        100 * ops / 197e12 * 1e3 / 15.0)
    assert read("attn_fwd_calls.train")({}, trace) == pytest.approx(0.5)
    assert read("short_conv_ms.train")({}, trace) == pytest.approx(1.0)


def test_readers_find_nothing_in_a_program_without_the_kernels():
    """The parent commit's step, or another family's: no ``ssd_`` kernel
    event, no such shapes among the samples, or no scope map gives None
    and never an error."""
    lfm2 = {"device_kind": "TPU v5 lite",
            "attention": dict(batch=4, seq_len=8192, short_conv={}, moe={})}
    flash = _trace([("jvp_flash_fwd_.2" + KERNEL, 3.0), ("short_conv_fwd.2" + KERNEL, 3.0)])
    scan = _trace([("ssd_fwd.2" + KERNEL, 3.0)])
    for name in ("ssd_scan_ms.train", "ssd_scan_roofline.train", "mamba_mixer_ms.train"):
        reader = run.load_metric(name).compute
        assert reader(lfm2, flash) is None and reader({}, None) is None, name
        assert reader(lfm2, tracered.Trace({}, [])) is None, name
    assert run.load_metric("ssd_scan_roofline.train").compute(lfm2, scan) is None
    assert run.load_metric("ssd_scan_roofline.train").compute({}, scan) is None


def test_the_mixer_scope_reader_sums_the_scopes_operations(monkeypatch):
    from chipbench import op_scopes
    trace = _trace([("fusion.1 fusion bf16[8192,8512]", 4.0), ("ssd_fwd.2" + KERNEL, 6.0),
                    ("fusion.3 fusion bf16[8192,8192]", 10.0)])
    names = {"fusion.1": "jit(step)/jvp(mamba_mixer)/dot_general",
             "ssd_fwd.2": "jit(step)/jvp(mamba_mixer)/ssd_scan/pallas_call",
             "fusion.3": "jit(step)/jvp(ffn)/dot_general"}
    rows = {dev: [(None, op_scopes.segments(names[n.split()[0]]), s, e) for n, s, e in evs]
            for dev, evs in trace.device.items()}
    monkeypatch.setattr(op_scopes, "_joined", lambda t: rows)
    assert run.load_metric("mamba_mixer_ms.train").compute({}, trace) == pytest.approx(5.0)


def test_the_metrics_and_the_cell_are_declared():
    """By membership: each list may have grown since."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["config"] == "granite4_h_micro_train" and cell["chips"] == 1
    assert cell["traffic"] == "packed8k" and len(cell["why"]) <= 200
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["source"] == CFG["source"] and entry["file"].endswith("granite4_h_micro_train.json")
    assert entry["reduced"] == CFG["reduced"] and len(entry["why"]) <= 200
    for name, unit, layer in (("ssd_scan_ms.train", "ms", "kernels"),
                              ("ssd_scan_roofline.train", "%", "kernels"),
                              ("mamba_mixer_ms.train", "ms", "compiled step")):
        declared = next(m for m in BENCH["per_layer"] if m["name"] == name)
        reader = run.load_metric(name)
        assert declared["workloads"] == [CELL] and declared["source"] == "device_trace"
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (layer, unit, declared["moves"])
        assert declared["layer"] == layer
    listed = {m["name"]: m["workloads"] for m in BENCH["per_layer"] if "workloads" in m}
    for name in ("step_place_ms.train", "step_dispatch_ms.train", "step_self_ms.train",
                 "init_state_s.setup", "step_trace_lower_s.setup", "step_cache_read_s.setup",
                 "optimizer_update_ms.train", "norm_ms.train", "recompute_ms.train",
                 "unscoped_share.train", "attn_full_ms.train", "attn_fwd_calls.train",
                 "attn_roofline.train"):
        assert CELL in listed[name], name
    for name in ("moe_gmm_ms.train", "moe_gmm_roofline.train", "moe_rows_ms.train",
                 "moe_expert_load_max_over_mean.train", "flash_fwd_ms.train",
                 "flash_roofline.train", "attn_window_ms.train", "short_conv_ms.train"):
        assert CELL not in listed[name], name      # no such kernel or router here


def test_the_configuration_keeps_the_published_widths():
    """Every number of the published config under its own key; only the
    depth, the layers' kinds with it, and the vocabulary are cut."""
    published = dict(hidden_size=2048, intermediate_size=8192, shared_intermediate_size=8192,
                     num_attention_heads=32, num_key_value_heads=8, attention_multiplier=0.015625,
                     embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8,
                     rms_norm_eps=1e-05, position_embedding_type="nope", tie_word_embeddings=True,
                     mamba_chunk_size=256, mamba_conv_bias=True, mamba_d_conv=4, mamba_d_head=64,
                     mamba_d_state=128, mamba_expand=2, mamba_n_groups=1, mamba_n_heads=64,
                     mamba_proj_bias=False, num_local_experts=0, num_experts_per_tok=0,
                     model_type="granitemoehybrid", max_position_embeddings=131072)
    assert {k: CFG[k] for k in published} == published
    assert CFG["reduced"] == ["num_hidden_layers", "layer_types", "vocab_size"]
    pub = CFG["published"]
    assert (CFG["num_hidden_layers"], CFG["vocab_size"]) == (10, 12544)
    assert (pub["num_hidden_layers"], pub["vocab_size"]) == (40, 100352)
    assert CFG["vocab_size"] * 8 == pub["vocab_size"]
    assert CFG["layer_types"] == pub["layer_types"][:10] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert pub["layer_types"].count("attention") == 4 and len(pub["layer_types"]) == 40
    assert "four pipeline stages" in CFG["deployment"] and "8 chips" in CFG["deployment"]
    assert set(CFG["reduced"]) <= set(CFG["assumed"])
    assert {"initializers", "time_step_limit", "documents", "dtype", "optimizer", "remat",
            "parameters"} <= set(CFG["assumed"])
    reh = CFG["rehearse"]
    assert reh["mamba_chunk_size"] == 8 and reh["dtype"] == "float32"
    assert WORKLOAD["traffic"]["rehearse"]["seq_len"] >= 3 * reh["mamba_chunk_size"]


def test_the_cells_traffic():
    traffic = WORKLOAD["traffic"]
    assert (traffic["kind"], traffic["seq_len"], traffic["pool"], traffic["in_flight"],
            traffic["trace_steps"], traffic["batch"]) == ("train_steps", 8192, 8, 2, 5, 1)
    assert WORKLOAD["chips"] == 1 and "batch" in WORKLOAD["assumed"]
    assert "87%" in WORKLOAD["why"] and "9 of its 10" in WORKLOAD["why"]


def test_batches_and_reference_at_the_rehearsal_size():
    import jax
    from chipbench.families import granite_hybrid
    cfg = {**CFG, **CFG["rehearse"]}
    traffic = {"seq_len": 32, "batch": 2}
    a = granite_hybrid.train_batches(cfg, traffic, 7, 2, 2)
    ids, pos, vl, lab = a[0]
    assert ids.shape == lab.shape == pos.shape == (2, 32) and (vl == 32).all()
    assert (ids[:, 1:] == lab[:, :-1]).all() and ids.max() < cfg["vocab_size"]
    system = granite_hybrid.build_train(cfg, jax.devices()[:1], seed=11)
    batch = granite_hybrid.train_batches(cfg, traffic, 12, 1, 2)[0]
    batch[2][1] = 24
    batch[3][1] %= 24
    names = granite_hybrid.grad_tensors(cfg)
    assert {"layer0_mamba_A_log", "layer0_mamba_dt_bias", "layer0_mamba_D",
            "layer0_mamba_conv_weight", "layer0_mamba_in_proj_weight",
            "layer0_mamba_out_proj_weight", "layer0_mamba_norm_gamma", "layer1_attn_q_weight",
            "layer0_ffn_gate_weight", "embed_weight"} == set(names)
    # the fp8 control before the reading that frees the block
    low = system.reference_part(batch, operands="float8_e4m3fn")
    readings = system.reference_readings(batch)
    got = granite_hybrid.compare(readings)
    assert got["ok"], got
    assert set(got["grad_rms_err"]) == set(names) and max(got["grad_rms_err"].values()) < 1e-5
    assert got["hidden_rms_err"] < 1e-5 and got["logits_rms_err"] < 1e-5
    assert got["loss_rel_err"] < 1e-6
    assert abs(got["loss_reference"] - np.log(cfg["vocab_size"])) < 0.5
    # the control the limits are set against (on the chip, at the published
    # widths): the reference with every matmul operand rounded to fp8, read
    # against the reference proper, is thousands of times further off than
    # the float32 system at this size, in the gradients too
    off = granite_hybrid.compare(readings, {k: low[k] for k in ("hidden", "logits", "loss",
                                                                 "grads", "update")})
    assert off["hidden_rms_err"] > 1e-2 > 1e3 * got["hidden_rms_err"], (off, got)
    assert min(off["grad_rms_err"].values()) > 1e-3 > 1e3 * max(got["grad_rms_err"].values())
    # the first update: the trainer's real step against the reference's
    # AdamW step from its own gradients; fp32 here, so near zero, where the
    # fp8 control's gradients turn some elements the other way
    assert system.trainer.num_update == 1 and set(got["update_rms_err"]) == set(names)
    assert max(got["update_rms_err"].values()) < 1e-2
    assert max(off["update_rms_err"].values()) > 10 * max(got["update_rms_err"].values())
    # a state the step left unchanged reads 1 and fails the check
    still = dict(readings["system"],
                 update={n: np.zeros_like(u) for n, u in readings["system"]["update"].items()})
    unchanged = granite_hybrid.compare(readings, still)
    assert not unchanged["ok"] and set(unchanged["update_rms_err"].values()) == {1.0}
    need = granite_hybrid.attention_roofline_inputs(cfg, traffic)
    assert need["ssd"] == dict(batch=2, seq_len=32, layers=2, heads=4, head_dim=32, groups=1,
                               state=16, chunk=8)
    system.step(batch)


def test_the_worst_chunk_decides_the_hidden_reading():
    """An error confined to one 256-position chunk of one row reads as that
    chunk's error, not as the row's average, and a padded chunk is skipped."""
    from chipbench.families import granite_hybrid
    rng = np.random.default_rng(0)
    want = rng.normal(size=(2, 1024, 8))
    got = want.copy()
    got[1, 512:768] *= 1.2                                    # one chunk 20% off
    keep = np.ones((2, 1024), bool)
    keep[1, 768:] = False
    per = granite_hybrid.chunk_errors(got, want, keep)
    assert per.shape == (2, 4) and np.isnan(per[1, 3]) and per[1, 2] == pytest.approx(0.2)
    assert np.nanmax(np.delete(per.ravel(), 6)) == 0.0
    row = granite_hybrid._rms_err(got[1][keep[1]], want[1][keep[1]])
    assert row < 0.13 < 0.2                                   # the row's average hides half of it
