"""The DeepSeek-V3-style decoder (Kanana-2-30B-A3B's block) against its plain
reference, at a tiny size: 4 heads of 16 + 8 against values of 16 over a
32-wide latent, one dense layer and two MoE layers of 8 experts top 2 with 4
held and two shared experts. Also what it brought to the flash kernels: the
second pair of operands whose one key head every query head reads, in
interpret mode against the XLA path."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import models, parallel
from incubator_mxnet_tpu.models.afmoe import rotary
from incubator_mxnet_tpu.ops.attention import dot_product_attention
from incubator_mxnet_tpu.ops.pallas import flash_attention as fa

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.reference import deepseek_v3 as reference  # noqa: E402

CFG = dict(hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
           qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
           q_lora_rank=None, rope_theta=1000000, rope_scaling=None, rms_norm_eps=1e-6,
           intermediate_size=128, first_k_dense_replace=1, moe_layer_freq=1,
           moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
           n_shared_experts=2, norm_topk_prob=True, routed_scaling_factor=2.448,
           n_group=1, topk_group=1, vocab_size=96,
           experts_held=4, expert_first=2, moe_tile_rows=8)
B, L = 2, 32


def _batch(seed=0):
    rng = onp.random.default_rng(seed)
    seq = rng.integers(0, CFG["vocab_size"], (B, L + 1)).astype("int32")
    return (seq[:, :L], onp.tile(onp.arange(L, dtype="int32"), (B, 1)),
            onp.array([L, L * 3 // 4], "float32"), seq[:, 1:])


def _net(seed=3, **kwargs):
    mx.random.seed(seed)
    net = models.get_deepseek_v3(CFG, **kwargs)
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

    def ref_loss(p):
        out = reference.forward(p, CFG, ids, pos, vl)
        return reference.lm_loss(out["logits"], out["valid"], lab), out

    (r_loss, r_out), r_grads = jax.jit(jax.value_and_grad(ref_loss, has_aux=True))(params)
    trainer = _trainer(net)
    step_loss = float(trainer.step(ids, pos, vl, lab).asnumpy())
    trainer.sync_to_block()
    grads = {k: params[k] - v for k, v in _params(net).items()}
    return dict(logits=onp.asarray(logits.asnumpy()), loss=float(loss.asnumpy()),
                step_loss=step_loss, grads=grads, trainer=trainer, r_out=r_out,
                r_loss=float(r_loss), r_grads=r_grads,
                valid=onp.asarray(valid.asnumpy()).astype(bool))


def test_logits_match_the_reference(system_and_reference):
    """float32 against float32 at ``highest``: what is left is the order of
    the sums (the kernels' tiles, the routed half's sorted rows)."""
    s = system_and_reference
    keep = s["valid"]
    onp.testing.assert_allclose(s["logits"][keep], onp.asarray(s["r_out"]["logits"])[keep],
                                rtol=2e-4, atol=2e-5)
    assert len(s["r_out"]["routes"]) == 2          # one dense layer, two MoE layers


def test_loss_matches_the_reference(system_and_reference):
    s = system_and_reference
    assert s["loss"] == pytest.approx(s["r_loss"], rel=1e-5)
    assert s["step_loss"] == pytest.approx(s["r_loss"], rel=1e-5)   # the compiled step's own
    assert abs(s["r_loss"] - onp.log(CFG["vocab_size"])) < 0.5     # random weights: ln V


def test_parameter_gradients_match_the_reference(system_and_reference):
    """Every parameter, through the trainer's compiled and recomputed step.
    Relative 5e-3 with a floor of 2e-3 of the gradient's largest entry: the
    step's gradients pass through float32 sums in another order than
    ``jax.grad`` of the reference, and entries near zero carry no digits."""
    s = system_and_reference
    assert set(s["grads"]) == set(s["r_grads"])
    assert {"layer1_attn_kv_a_weight", "layer1_attn_kv_norm_gamma", "layer1_attn_kv_b_weight",
            "layer2_moe_shared_gate_weight", "layer0_ffn_up_weight"} <= set(s["grads"])
    for name, want in s["r_grads"].items():
        want, got = onp.asarray(want), onp.asarray(s["grads"][name])
        if name.endswith(("expert_bias", "expert_rows")):   # buffers outside the gradient
            assert not got.any() and not want.any(), name
            continue
        assert onp.abs(want).max() > 0, name
        onp.testing.assert_allclose(got, want, rtol=5e-3, atol=2e-6 + 2e-3 * onp.abs(want).max(),
                                    err_msg=name)


@pytest.mark.parametrize("remat", [True, False], ids=["recomputed", "held"])
def test_trains_in_one_compiled_step_and_recomputation_changes_no_loss(
        system_and_reference, remat):
    trainer, batch = _trainer(_net(remat=remat), rate=0.1), _batch()
    losses = [float(trainer.step(*batch).asnumpy()) for _ in range(3)]
    assert trainer.last_path == "pjit" and trainer._step_fn._cache_size() == 1
    assert onp.isfinite(losses).all() and losses[2] < losses[1] < losses[0]
    # the same first loss either way, and the fixture's recomputed step's
    assert losses[0] == pytest.approx(system_and_reference["step_loss"], rel=1e-6)


def test_a_configuration_the_block_cannot_run_is_refused_by_name():
    for key, value in (("q_lora_rank", 1536), ("rope_scaling", {"type": "yarn"}), ("n_group", 8)):
        with pytest.raises(ValueError, match=key):
            models.get_deepseek_v3(dict(CFG, **{key: value}))


# --- the flash kernels' second pair ------------------------------------------------

@pytest.fixture
def tiles(monkeypatch, request):
    bq, bk = request.param
    monkeypatch.setenv("MXTPU_FLASH_BQ", str(bq))
    monkeypatch.setenv("MXTPU_FLASH_BK", str(bk))


def _mla_operands(dtype=jnp.float32, B=2, H=4, L=64, D=16, Ds=8):
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    shapes = [(B, H, L, D)] * 3 + [(B, H, L, Ds), (B, 1, L, Ds), (B, H, L, D)]
    return [jax.random.normal(k, s, dtype) for k, s in zip(keys, shapes)]


@pytest.mark.parametrize("tiles", [(16, 16), (16, 32), (32, 16)], indirect=True,
                         ids=["q16_k16", "q16_k32", "q32_k16"])
@pytest.mark.parametrize("causal,masked", [(True, True), (True, False), (False, True)],
                         ids=["causal_masked", "causal", "masked"])
def test_flash_shared_pair_against_the_xla_path(tiles, causal, masked):
    """Values and all five gradients (``dq``, ``dk``, ``dv``, ``dq_s`` and
    ``dk_s``, the last a sum over the heads made inside the dkv kernel), over
    tiles small enough that a row crosses several key blocks and the
    diagonal."""
    q, k, v, q_s, k_s, cot = _mla_operands()
    mask = (jnp.arange(64)[None, :] < jnp.array([64, 40])[:, None])[:, None, None, :]

    def run(impl):
        def f(q, k, v, q_s, k_s):
            o = dot_product_attention(q, k, v, mask=mask if masked else None, causal=causal,
                                      impl=impl, shared=(q_s, k_s))
            return (o * cot).sum(), o
        return jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4), has_aux=True)(q, k, v, q_s, k_s)

    (_, want), want_grads = run("xla")
    (_, got), got_grads = run("flash")
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for name, a, b in zip(("dq", "dk", "dv", "dq_s", "dk_s"), got_grads, want_grads):
        assert a.shape == b.shape and float(jnp.abs(b).max()) > 0, name
        onp.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)


def test_the_shared_pair_is_the_concatenated_key_without_building_it():
    """``(q . k + q_s . k_s) * scale`` is the score of the 24-wide query
    against a key that repeats ``k_s`` in every head; the default scale is
    that width's."""
    q, k, v, q_s, k_s, _ = _mla_operands()
    wide_q = jnp.concatenate([q, q_s], -1)
    wide_k = jnp.concatenate([k, jnp.broadcast_to(k_s, q_s.shape)], -1)
    s = jnp.einsum("bhqd,bhkd->bhqk", wide_q, wide_k) * 24 ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), s, -1e30)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    for impl in ("xla", "flash"):
        got = dot_product_attention(q, k, v, causal=True, impl=impl, shared=(q_s, k_s))
        onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5, err_msg=impl)


def test_flash_supported_names_and_refusals_know_the_shared_pair(monkeypatch):
    monkeypatch.setattr(fa, "_interpret_for", lambda x: False)      # as on the chip
    q, k, v, q_s, k_s, _ = _mla_operands(jnp.bfloat16, L=512, D=128, Ds=64)
    assert fa.flash_supported(q, k, v, shared=(q_s, k_s))
    assert not fa.flash_supported(q, k, v, shared=(q_s, jnp.repeat(k_s, 4, 1)))   # a key a head
    assert not fa.flash_supported(q, k[:, :2], v[:, :2], shared=(q_s, k_s))       # grouped K/V
    assert not fa.flash_supported(q, k, v, shared=(q_s[..., :60], k_s[..., :60]))
    assert fa._kernel_name("flash_fwd", None, (q_s, k_s)) == "flash_fwd_mla"
    assert fa._kernel_name("flash_bwd_dkv", None, (q_s, k_s)) == "flash_bwd_dkv_mla"
    assert fa._kernel_name("flash_bwd_dq", None) == "flash_bwd_dq"
    with pytest.raises(ValueError, match="shared="):
        fa.flash_attention(q, k, v, shared=(q_s, jnp.repeat(k_s, 4, 1)))
    with pytest.raises(ValueError, match="ring.*shared="):
        dot_product_attention(q, k, v, impl="ring", shared=(q_s, k_s))


def test_interleaved_rotary_is_a_rotation_by_relative_position():
    """Pairs ``(2i, 2i + 1)``: norms are kept, position 0 is the identity,
    the product of two rotated vectors depends on the distance alone, and
    the pairs are not the half-split convention's."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 2, 8))
    y = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 2, 8))
    at = lambda t, m: rotary(t, jnp.full((1, 1), m), 1e6, interleaved=True)  # noqa: E731
    onp.testing.assert_allclose(at(x, 0), x, atol=1e-7)
    onp.testing.assert_allclose(jnp.linalg.norm(at(x, 37), axis=-1),
                                jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    dots = [float((at(x, m) * at(y, n)).sum()) for m, n in ((5, 2), (103, 100), (3, 0))]
    assert dots[0] == pytest.approx(dots[1], rel=1e-4) == pytest.approx(dots[2], rel=1e-4)
    assert float((at(x, 5) * at(y, 2)).sum()) != pytest.approx(float((at(x, 5) * at(y, 3)).sum()))
    # the first pair turns by the position itself (frequency 1)
    first = at(jnp.zeros((1, 1, 1, 8)).at[..., 0].set(1.0), 1)[0, 0, 0]
    onp.testing.assert_allclose(first[:2], [onp.cos(1.0), onp.sin(1.0)], rtol=1e-6)
    assert not onp.allclose(at(x, 5), rotary(x, jnp.full((1, 1), 5), 1e6))
