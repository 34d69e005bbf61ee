"""The LFM2-MoE-style decoder (LFM2-8B-A1B's block) against its plain
reference, at a tiny size: 4 query heads over 2 K/V heads of 16, a dense
convolution layer, then an attention and a convolution layer of 8 experts
top 2 with 4 held and no shared expert. Also what it brought to the shared
code: the fused gated short convolution with its kernel pair (interpret
mode) against the plain form, ``AfmoeMoE(num_shared=0)``, a recomputed layer
without an attention kernel, and the four chips' shares of an expert layer
against the uncut layer."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import models, parallel
from incubator_mxnet_tpu.models.afmoe import AfmoeMoE
from incubator_mxnet_tpu.ops import nn as ops_nn
from incubator_mxnet_tpu.ops.pallas import short_conv

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.reference import lfm2_moe as reference  # noqa: E402

CFG = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
           layer_types=["conv", "full_attention", "conv"], conv_L_cache=3, conv_bias=False,
           rope_theta=1000000, norm_eps=1e-5, intermediate_size=128, num_dense_layers=1,
           moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
           norm_topk_prob=True, routed_scaling_factor=1, use_expert_bias=True,
           vocab_size=96, experts_held=4, expert_first=2, moe_tile_rows=8)
B, L = 2, 32


def _batch(seed=0):
    rng = onp.random.default_rng(seed)
    seq = rng.integers(0, CFG["vocab_size"], (B, L + 1)).astype("int32")
    return (seq[:, :L], onp.tile(onp.arange(L, dtype="int32"), (B, 1)),
            onp.array([L, L * 3 // 4], "float32"), seq[:, 1:])


def _net(seed=3, cfg=CFG, **kwargs):
    mx.random.seed(seed)
    net = models.get_lfm2_moe(cfg, **kwargs)
    net.initialize(mx.init.Normal(0.05))
    return net


def _params(net):
    return {k[len(net.prefix):]: p.data()._data for k, p in net.collect_params().items()}


def _trainer(net, rate=1.0):
    return parallel.ShardedTrainer(
        net, models.afmoe_lm_loss, "sgd", dict(learning_rate=rate),
        mesh=parallel.make_mesh(devices=jax.devices()[:1]), n_labels=1)


@pytest.fixture(scope="module")
def system_and_reference():
    """One forward of the program and of the reference on the same seeded
    weights, one row padded, and the parameter gradients of both: the
    program's through the trainer's compiled step (SGD at rate 1: gradient =
    old - new weight), every layer recomputed in the backward pass."""
    net = _net(remat=True)
    ids, pos, vl, lab = _batch()
    logits, valid = net(*(mx.nd.array(a, dtype=a.dtype) for a in (ids, pos, vl)))
    loss = models.afmoe_lm_loss((logits, valid), mx.nd.array(lab, dtype="int32"))
    params = _params(net)
    r_loss, r_out, r_grads = jax.jit(
        lambda p: reference.loss_and_grads(p, CFG, ids, pos, vl, lab, tuple(p)))(params)
    trainer = _trainer(net)
    step_loss = float(trainer.step(ids, pos, vl, lab).asnumpy())
    trainer.sync_to_block()
    grads = {k: params[k] - v for k, v in _params(net).items()}
    return dict(logits=onp.asarray(logits.asnumpy()), loss=float(loss.asnumpy()),
                step_loss=step_loss, grads=grads, r_out=r_out, r_loss=float(r_loss),
                r_logits=onp.asarray(reference.logits(params, r_out["hidden"])),
                r_grads=r_grads, valid=onp.asarray(valid.asnumpy()).astype(bool))


def test_logits_match_the_reference(system_and_reference):
    """float32 against float32 at ``highest``: what is left is the order of
    the sums (the kernels' tiles, the routed half's sorted rows)."""
    s = system_and_reference
    keep = s["valid"]
    onp.testing.assert_allclose(s["logits"][keep], s["r_logits"][keep], rtol=2e-4, atol=2e-5)
    assert len(s["r_out"]["routes"]) == 2          # one dense layer, two MoE layers


def test_loss_matches_the_reference(system_and_reference):
    s = system_and_reference
    assert s["loss"] == pytest.approx(s["r_loss"], rel=1e-5)
    assert s["step_loss"] == pytest.approx(s["r_loss"], rel=1e-5)   # the compiled step's own
    assert abs(s["r_loss"] - onp.log(CFG["vocab_size"])) < 0.5     # random weights: ln V


def test_parameter_gradients_match_the_reference(system_and_reference):
    """Every parameter kind, through the trainer's compiled and recomputed
    step: the embedding (tied: the gather's and the head's gradient in one
    array), the three matrices and the taps of a convolution mixer, the
    attention block with its per-head norms, the router and the experts.
    Relative 5e-3 with a floor of 2e-3 of the gradient's largest entry: the
    step's gradients pass through float32 sums in another order than
    ``jax.grad`` of the reference, and entries near zero carry no digits."""
    s = system_and_reference
    assert set(s["grads"]) == set(s["r_grads"])
    assert {"embed_weight", "layer0_conv_in_proj_weight", "layer0_conv_weight",
            "layer2_conv_out_proj_weight", "layer1_attn_q_norm_gamma", "layer1_attn_k_weight",
            "layer1_moe_router_weight", "layer2_moe_experts_w13", "layer0_ffn_up_weight",
            "norm_gamma"} <= set(s["grads"])
    assert not any("lm_head" in k or "shared" in k for k in s["grads"])
    for name, want in s["r_grads"].items():
        want, got = onp.asarray(want), onp.asarray(s["grads"][name])
        if name.endswith(("expert_bias", "expert_rows")):   # buffers outside the gradient
            assert not got.any() and not want.any(), name
            continue
        assert onp.abs(want).max() > 0, name
        onp.testing.assert_allclose(got, want, rtol=5e-3, atol=2e-6 + 2e-3 * onp.abs(want).max(),
                                    err_msg=name)


@pytest.fixture(scope="module")
def three_steps():
    """Three compiled steps from the same weights, every layer recomputed in
    the backward pass and every layer held: ``{remat: readings}``."""
    out = {}
    for remat in (True, False):
        net = _net(remat=remat)
        trainer, batch = _trainer(net, rate=0.1), _batch()
        losses = [float(trainer.step(*batch).asnumpy()) for _ in range(3)]
        rows = [onp.asarray(r) for r in net.expert_rows()]
        trainer.sync_to_block()
        out[remat] = dict(losses=losses, rows=rows, params=_params(net), path=trainer.last_path,
                          traces=trainer._step_fn._cache_size())
    return out


@pytest.mark.parametrize("remat", [True, False], ids=["recomputed", "held"])
def test_trains_in_one_compiled_step_and_recomputation_changes_nothing(
        system_and_reference, three_steps, remat):
    """A stack with convolution layers, whose recomputed layers have no
    attention kernel's result to keep: the same losses and the same
    parameters after three steps either way, and each MoE layer's
    ``expert_rows`` written by the compiled step."""
    mine, other = three_steps[remat], three_steps[not remat]
    assert mine["path"] == "pjit" and mine["traces"] == 1
    losses = mine["losses"]
    assert onp.isfinite(losses).all() and losses[2] < losses[1] < losses[0]
    assert losses[0] == pytest.approx(system_and_reference["step_loss"], rel=1e-6)
    assert len(mine["rows"]) == 2 and all(r.shape == (4,) and r.sum() > 0 for r in mine["rows"])
    assert losses == pytest.approx(other["losses"], rel=1e-6)
    for name, value in mine["params"].items():
        onp.testing.assert_allclose(value, other["params"][name], rtol=1e-5, atol=1e-7,
                                    err_msg=name)


def test_a_configuration_the_block_cannot_run_is_refused_by_name():
    for key, value in (("conv_bias", True), ("layer_types", ["conv", "sliding_attention", "conv"]),
                       ("hidden_size", 66)):
        with pytest.raises(ValueError, match=key):
            models.get_lfm2_moe(dict(CFG, **{key: value}))


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The guide's share test: chips holding experts [0, 2), [2, 4), [4, 6)
    and [6, 8) each compute their part of one MoE layer's result for the
    same tokens through the program's own layer (the router whole on each);
    with no shared expert nothing is counted twice, and the four parts add
    up to what the uncut reference gives for the whole layer."""
    rng = onp.random.default_rng(1)
    x = rng.standard_normal((B, L, 64)).astype("float32")
    whole = dict(router_weight=rng.standard_normal((8, 64)).astype("float32") * 0.3,
                 expert_bias=onp.zeros((8,), "float32"),
                 experts_w13=rng.standard_normal((8, 64, 64)).astype("float32") * 0.1,
                 experts_w2=rng.standard_normal((8, 64, 32)).astype("float32") * 0.1)
    cfg = dict(CFG, experts_held=8, expert_first=0)
    want, (idx, _gap) = reference.moe({"m_" + k: v for k, v in whole.items()}, "m_", cfg,
                                      jnp.asarray(x).reshape(B * L, 64), (0, 8))
    total, rows = 0.0, []
    for first in (0, 2, 4, 6):
        layer = AfmoeMoE(64, 32, 8, 2, (first, 2), num_shared=0, route_norm_eps=1e-6,
                         tile_rows=8, prefix=f"share{first}_")
        layer.initialize()
        for name, value in whole.items():
            held = value[first:first + 2] if name.startswith("experts_") else value
            getattr(layer, name).set_data(mx.nd.array(held))
        out, got_rows = layer(mx.nd.array(x))
        total = total + onp.asarray(out.asnumpy())
        rows.append(onp.asarray(got_rows.asnumpy()))
    onp.testing.assert_allclose(total.reshape(B * L, 64), want, rtol=2e-5, atol=2e-6)
    # every assignment lands on exactly one chip
    assert onp.concatenate(rows).tolist() == onp.bincount(
        onp.asarray(idx).reshape(-1), minlength=8).tolist()


def test_moe_without_a_shared_expert_builds_none_and_adds_none():
    layer = AfmoeMoE(64, 32, 8, 2, (0, 8), num_shared=0, tile_rows=8, prefix="bare_")
    assert layer.shared is None
    assert not any("shared" in name for name in layer.collect_params())
    with_one = AfmoeMoE(64, 32, 8, 2, (0, 8), num_shared=1, tile_rows=8, prefix="one_")
    assert sorted(n[len("one_"):] for n in with_one.collect_params() if "shared" in n) == [
        "shared_down_weight", "shared_gate_weight", "shared_up_weight"]


# --- the gated short convolution ----------------------------------------------------

def _conv_operands(shape, taps, dtype=jnp.float32):
    B_, L_, C_ = shape
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    return (jax.random.normal(keys[0], (B_, L_, 3 * C_), jnp.float32).astype(dtype),
            (jax.random.normal(keys[1], (C_, taps), jnp.float32) * 0.5).astype(dtype),
            jax.random.normal(keys[2], (B_, L_, C_), jnp.float32).astype(dtype))


def test_short_conv_gate_is_the_equation():
    """``Cg * conv(Bg * x)`` by hand on a few numbers: the taps reach back,
    ``w[:, K - 1]`` weighs the position itself, and nothing precedes a row."""
    bcx = jnp.asarray([[[1., 2., 3.], [2., 1., 1.], [3., 1., 2.], [1., 1., 5.]]])   # C = 1
    w = jnp.asarray([[0.5, 2.0, 1.0]])
    s = [1 * 3, 2 * 1, 3 * 2, 1 * 5]
    want = [2 * (1.0 * s[0]), 1 * (2.0 * s[0] + 1.0 * s[1]),
            1 * (0.5 * s[0] + 2.0 * s[1] + 1.0 * s[2]), 1 * (0.5 * s[1] + 2.0 * s[2] + 1.0 * s[3])]
    for f in (ops_nn.short_conv_gate_plain, ops_nn.short_conv_gate):
        onp.testing.assert_allclose(f(bcx, w)[0, :, 0], want, rtol=1e-6)


@pytest.mark.parametrize("shape,taps,tile", [((2, 40, 64), 3, 16), ((1, 100, 128), 4, 32),
                                             ((2, 16, 64), 3, 512), ((1, 48, 384), 2, 16)],
                         ids=["L40_tile16", "L100_tile32_K4", "one_tile", "L48_C384_K2"])
def test_short_conv_kernels_against_the_plain_form(shape, taps, tile):
    """The kernel pair in interpret mode against the plain form and
    ``jax.grad`` of it: rows that are no multiple of the tile (40 in tiles
    of 16, 100 in tiles of 32), several tiles a row so that the halo crosses
    tile borders both ways, one tile, and other tap counts."""
    bcx, w, dy = _conv_operands(shape, taps)
    want = ops_nn.short_conv_gate_plain(bcx, w)
    want_b, want_w = jax.vjp(ops_nn.short_conv_gate_plain, bcx, w)[1](dy)
    onp.testing.assert_allclose(short_conv.forward(bcx, w, tile), want, rtol=1e-5, atol=1e-5)
    got_b, got_w = short_conv.backward(bcx, w, dy, tile)
    onp.testing.assert_allclose(got_b, want_b, rtol=1e-5, atol=1e-5)
    onp.testing.assert_allclose(got_w, want_w, rtol=1e-4, atol=1e-4)
    assert got_b.shape == bcx.shape and got_w.shape == w.shape


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_short_conv_gate_and_its_backward_against_jax_grad_of_the_plain_form(dtype, tol):
    """The fused op's own backward rule and, called directly, the
    kernels', in both dtypes; bf16 in and out with fp32 inside leaves one
    rounding of each result."""
    bcx, w, dy = _conv_operands((2, 40, 64), 3, dtype)
    f32 = lambda t: onp.asarray(t, "float32")  # noqa: E731

    def total(f):
        return lambda bcx, w: (f(bcx, w).astype(jnp.float32) * dy.astype(jnp.float32)).sum()
    want = jax.grad(total(ops_nn.short_conv_gate_plain), (0, 1))(bcx, w)
    for got in (jax.grad(total(ops_nn.short_conv_gate), (0, 1))(bcx, w),
                short_conv.backward(bcx, w, dy, 16)):
        for a, b in zip(got, want):
            assert a.dtype == b.dtype == dtype
            onp.testing.assert_allclose(f32(a), f32(b), rtol=5 * tol,
                                        atol=tol * max(1.0, float(onp.abs(f32(b)).max())))
    onp.testing.assert_allclose(f32(ops_nn.short_conv_gate(bcx, w)),
                                f32(ops_nn.short_conv_gate_plain(bcx, w)), rtol=tol, atol=tol)


def test_short_conv_kernels_take_the_chips_shapes_and_refuse_others(monkeypatch):
    bcx, w, _ = _conv_operands((1, 32, 128), 3, jnp.bfloat16)
    assert short_conv.supported(bcx, w)
    assert not short_conv.supported(bcx[..., :3 * 64], w[:64])          # not whole lane tiles
    assert not short_conv.supported(bcx, jnp.zeros((128, 9), jnp.bfloat16))   # too many taps
    assert not short_conv.supported(bcx.astype(jnp.float16), w)
    # off the TPU the op takes the plain form; as on the chip, the kernels
    assert not ops_nn._short_conv_kernels(bcx, w)
    monkeypatch.setattr(short_conv, "_interpret_for", lambda x: False)
    assert ops_nn._short_conv_kernels(bcx, w)
    assert not ops_nn._short_conv_kernels(bcx[..., :3 * 64], w[:64])
