"""The Granite-4.0-H-style hybrid decoder against its plain reference, at a
tiny size: two Mamba-2 layers of 4 heads of 32 (state 16, chunks of 8)
around an attention layer of 4 query heads over 2 K/V heads of 16, an MLP of
96 in every layer. Also what it brought: the chunked state-space scan
(``ops.ssm.ssd_scan``: the plain chunked form, and the kernel pair in
interpret mode) against the recurrence one position at a time, with a
state that lives across chunks; the causal convolution with bias and SiLU
and the gated norm against plain forms; each of the model's multipliers
shown to matter."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import models, parallel
from incubator_mxnet_tpu.models.granite_hybrid import GraniteAttention, GraniteMamba
from incubator_mxnet_tpu.ops import nn as ops_nn, ssm
from incubator_mxnet_tpu.ops.pallas import causal_conv, ssd

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.reference import granite_hybrid as reference  # noqa: E402

CFG = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
           attention_multiplier=0.0625, embedding_multiplier=12, residual_multiplier=0.22,
           logits_scaling=8, rms_norm_eps=1e-5, shared_intermediate_size=96,
           mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16, mamba_n_groups=1,
           mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=8,
           layer_types=["mamba", "attention", "mamba"], vocab_size=80,
           tie_word_embeddings=True, position_embedding_type="nope", num_local_experts=0)
B, L = 2, 32


def recurrence(x, dt, A, Bm, Cm, D):
    """The state updated one position at a time, from zero, in float32."""
    with jax.default_matmul_precision("highest"):
        return reference.recurrence(x, dt, A, Bm, Cm, D)


def _scan_inputs(seed, Bt=2, L=40, H=4, P=8, G=1, N=16, dtype=jnp.float32):
    """Inputs as the Mamba-2 initialisers draw ``A`` and ``dt``: a decay of
    ``exp(dt A)`` a position with ``dt A`` from 1e-3 to 1.6, so that a
    state outlives a chunk of 8."""
    rng = onp.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(Bt, L, H, P)), dtype)
    dt = jnp.asarray(onp.exp(rng.uniform(onp.log(1e-3), onp.log(1e-1), (Bt, L, H))), jnp.float32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, H), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(Bt, L, G, N)) * N ** -0.5, dtype)
    Cm = jnp.asarray(rng.normal(size=(Bt, L, G, N)) * N ** -0.5, dtype)
    D = jnp.asarray(rng.normal(size=H), jnp.float32)
    return x, dt, A, Bm, Cm, D


def _rel(got, want):
    got, want = onp.asarray(got, "float64"), onp.asarray(want, "float64")
    return float(onp.sqrt(((got - want) ** 2).sum() / ((want ** 2).sum() + 1e-300)))


def _grads(scan, args, cot):
    return jax.grad(lambda *a: jnp.sum(scan(*a).astype(jnp.float32) * cot),
                    argnums=range(6))(*args)


def test_the_state_outlives_a_chunk_in_these_inputs():
    """Cut the state at every chunk of 8 and the recurrence's result moves
    by far more than any tolerance below: the inter-chunk path is tested.
    (``D x`` left out: it reads no state.)"""
    *args, D = _scan_inputs(0)
    args = (*args, jnp.zeros_like(D))          # the state's part alone
    whole = recurrence(*args)
    cut = jnp.concatenate([recurrence(*(a[:, i:i + 8] if a.ndim > 1 else a for a in args))
                           for i in range(0, 40, 8)], axis=1)
    assert _rel(cut, whole) > 0.1


@pytest.mark.parametrize("chunk,L,H,G", [(8, 40, 4, 1), (16, 48, 4, 1), (8, 37, 4, 1),
                                         (32, 64, 4, 2)],
                         ids=["chunk8", "chunk16", "ragged_L", "two_groups"])
def test_ssd_scan_plain_is_the_recurrence(chunk, L, H, G):
    """The plain chunked form, forward and the gradients of all six inputs,
    against the recurrence to float32 rounding."""
    args = _scan_inputs(1, L=L, H=H, G=G)
    cot = jnp.asarray(onp.random.default_rng(2).normal(size=args[0].shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        assert _rel(ssm.ssd_scan_plain(*args, chunk), recurrence(*args)) < 1e-5
        got = _grads(lambda *a: ssm.ssd_scan_plain(*a, chunk), args, cot)
        want = _grads(recurrence, args, cot)
    for name, g, w in zip(("x", "dt", "A", "B", "C", "D"), got, want):
        assert _rel(g, w) < 1e-4, name


@pytest.mark.parametrize("chunk,H", [(8, 8), (16, 16)], ids=["chunk8_h8", "chunk16_h16_two_blocks"])
def test_ssd_kernels_are_the_recurrence(chunk, H):
    """The kernel pair in interpret mode, behind the op's own backward rule,
    forward and the gradients of all six inputs, against the recurrence:
    the kernels round their matmul operands to bf16, so a few tenths of a
    per cent. At 16 heads the grid has two head blocks, each with a state of
    its own."""
    args = _scan_inputs(3, L=48, H=H)
    cot = jnp.asarray(onp.random.default_rng(4).normal(size=args[0].shape), jnp.float32)
    assert _rel(ssm.ssd_fused(*args, chunk), recurrence(*args)) < 1e-2
    got = _grads(lambda *a: ssm.ssd_fused(*a, chunk), args, cot)
    want = _grads(recurrence, args, cot)
    for name, g, w in zip(("x", "dt", "A", "B", "C", "D"), got, want):
        assert _rel(g, w) < 1e-2, name


def test_ssd_kernels_in_bf16_against_the_plain_form():
    """bf16 in and out, as the model calls them: the kernels' result and
    gradients within bf16 roundings of the plain form's."""
    args = _scan_inputs(5, L=32, H=8, dtype=jnp.bfloat16)
    cot = jnp.asarray(onp.random.default_rng(6).normal(size=args[0].shape), jnp.float32)
    assert ssm.ssd_fused(*args, 8).dtype == jnp.bfloat16
    assert _rel(ssm.ssd_fused(*args, 8), ssm.ssd_scan_plain(*args, 8)) < 2e-2
    got = _grads(lambda *a: ssm.ssd_fused(*a, 8), args, cot)
    want = _grads(lambda *a: ssm.ssd_scan_plain(*a, 8), args, cot)
    for name, g, w in zip(("x", "dt", "A", "B", "C", "D"), got, want):
        assert g.dtype == w.dtype and _rel(g, w) < 2e-2, name


def test_the_kernels_take_the_chips_shapes_and_refuse_others(monkeypatch):
    """By what the call can observe: bf16, one group, whole chunks of whole
    row tiles, whole head blocks; off the TPU never (the gauge says so)."""
    from incubator_mxnet_tpu.telemetry import metrics

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)
    x, bc = sds((1, 8192, 64, 64)), sds((1, 8192, 1, 128))
    assert ssd.supported(x, bc, 256)
    assert not ssd.supported(sds((1, 8192, 64, 64), jnp.float32), bc, 256)
    assert not ssd.supported(x, sds((1, 8192, 2, 128)), 256)              # two groups
    assert not ssd.supported(sds((1, 8000, 64, 64)), sds((1, 8000, 1, 128)), 256)
    assert not ssd.supported(sds((1, 8192, 12, 64)), bc, 256)             # no whole head block
    assert not ssd.supported(x, bc, 100)
    args = _scan_inputs(7, L=16, H=8, dtype=jnp.bfloat16)
    ssm.ssd_scan(*args, 8)
    gauge = metrics.gauge("mxtpu_ssd_fused", kernel="ssd_h8_p8_n16")
    assert gauge.value == 0                                  # the CPU traced the plain form
    monkeypatch.setattr(ssd, "_interpret_for", lambda x: False)
    monkeypatch.setattr(ssd, "supported", lambda *a: True)
    monkeypatch.setattr(ssm, "ssd_fused", lambda *a: "kernels")
    assert ssm.ssd_scan(*args, 8) == "kernels" and gauge.value == 1


@pytest.mark.parametrize("conv", [ops_nn.causal_conv1d,
                                  lambda x, w, b: ops_nn.causal_conv_fused(x, w, b, (), 0)],
                         ids=["plain", "kernels"])
def test_causal_conv1d_is_the_plain_convolution(conv):
    rng = onp.random.default_rng(8)
    x = rng.normal(size=(2, 12, 6)).astype("float32")
    w, b = rng.normal(size=(6, 4)).astype("float32"), rng.normal(size=6).astype("float32")
    want = onp.zeros_like(x)
    for t in range(12):
        for k in range(4):
            s = t - 3 + k
            if s >= 0:
                want[:, t] += w[:, k] * x[:, s]
    want = want + b
    want = want / (1 + onp.exp(-want))                       # silu
    got = conv(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b))
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # and through the gradient: a position's output reads no later position
    g = jax.grad(lambda x: conv(x, jnp.asarray(w), jnp.asarray(b))[:, 5].sum())(
        jnp.asarray(x))
    assert float(jnp.abs(g[:, 6:]).max()) == 0.0 and float(jnp.abs(g[:, 2:6]).min()) > 0.0


def _conv_operands(shape, taps, dtype, width=None, seed=10):
    """``x (B, L, width)``, taps ``(C, K)`` and a bias, drawn as the Mamba-2
    initialisers draw the taps (``U[+-1/sqrt(K)]``)."""
    B_, L_, C_ = shape
    rng = onp.random.default_rng(seed)
    bound = taps ** -0.5
    return (jnp.asarray(rng.normal(size=(B_, L_, width or C_)), dtype),
            jnp.asarray(rng.uniform(-bound, bound, (C_, taps)), dtype),
            jnp.asarray(rng.uniform(-bound, bound, C_), dtype))


def _close(got, want, tol):
    f32 = lambda t: onp.asarray(t, "float32")  # noqa: E731
    assert got.shape == want.shape and got.dtype == want.dtype
    onp.testing.assert_allclose(f32(got), f32(want), rtol=5 * tol,
                                atol=tol * max(1.0, float(onp.abs(f32(want)).max())))


# (B, L, C), taps, split, start, width, tile: rows that are no whole number of
# tiles (40 in tiles of 16, 100 in tiles of 32) so that the halo crosses tile
# borders both ways, the output cut into parts as a Mamba-2 mixer cuts it, and
# the input read as a window of a wider array at a lane-tile offset
_CONV_CASES = [((2, 40, 256), 4, (), 0, None, 16), ((2, 100, 384), 2, (256, 320), 0, None, 32),
               ((2, 40, 384), 4, (256, 320), 128, 640, 16), ((2, 48, 256), 2, (128,), 0, None, 512)]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape,taps,split,start,width,tile", _CONV_CASES,
                         ids=["L40_C256_K4", "L100_C384_K2_parts", "window_C384_K4_parts",
                              "one_tile_C256_K2"])
def test_causal_conv_kernels_against_the_plain_form(shape, taps, split, start, width, tile,
                                                    dtype, tol):
    """The kernel pair in interpret mode against the plain form and
    ``jax.vjp`` of it: ``y`` a part at a time, ``dx``, ``d w``, ``d b``;
    bf16 in and out with fp32 inside leaves one rounding of each result."""
    x, w, b = _conv_operands(shape, taps, dtype, width)
    want = ops_nn.causal_conv1d_plain(x, w, b, split, start)
    want = want if split else (want,)
    dys = tuple(jnp.asarray(onp.random.default_rng(11).normal(size=y.shape), dtype)
                for y in want)
    got = causal_conv.forward(x, w, b, split, start, tile)
    assert len(got) == len(want)
    for g, y in zip(got, want):
        _close(g, y, tol)
    _, vjp = jax.vjp(lambda *a: ops_nn.causal_conv1d_plain(*a, split, start), x, w, b)
    want_x, want_w, want_b = vjp(dys if split else dys[0])
    got_x, got_w, got_b = causal_conv.backward(x, w, b, dys, start, tile)
    _close(got_x, want_x[..., start:start + shape[2]], tol)
    _close(got_w, want_w, tol)
    _close(got_b, want_b, tol)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_causal_conv_op_and_its_backward_against_jax_grad_of_the_plain_form(dtype, tol):
    """The fused op through its own backward rule, as the mixer calls it:
    the input a window of the projection's wider output, the output in
    three parts; the gradient of the whole input is zero outside the
    window."""
    x, w, b = _conv_operands((2, 40, 384), 4, dtype, width=640)
    cots = [jnp.asarray(onp.random.default_rng(12).normal(size=(2, 40, n)), jnp.float32)
            for n in (256, 64, 64)]

    def total(f):
        return lambda *a: sum((y.astype(jnp.float32) * c).sum()
                              for y, c in zip(f(*a, (256, 320), 128), cots))
    want = jax.grad(total(ops_nn.causal_conv1d_plain), (0, 1, 2))(x, w, b)
    got = jax.grad(total(ops_nn.causal_conv_fused), (0, 1, 2))(x, w, b)
    for g, y in zip(got, want):
        _close(g, y, tol)
    assert float(jnp.abs(got[0][..., :128]).max()) == float(jnp.abs(got[0][..., 512:]).max()) == 0


def test_the_conv_kernels_take_the_chips_shapes_and_refuse_others(monkeypatch):
    """By what the call can observe: bf16 or fp32, whole lane tiles, at most
    8 taps; off the TPU never (the gauge says so)."""
    from incubator_mxnet_tpu.telemetry import metrics

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)
    x, w = sds((1, 8192, 4352)), sds((4352, 4))
    assert causal_conv.supported(x, w) and causal_conv.supported(x, w, (4096, 4224))
    assert causal_conv.supported(sds((1, 8192, 8512)), w, (4096, 4224), 4096)  # in place
    assert causal_conv.supported(sds((1, 8192, 4352), jnp.float32), w)
    assert not causal_conv.supported(sds((1, 8192, 4352), jnp.float16), w)
    assert not causal_conv.supported(sds((1, 8192, 4300)), sds((4300, 4)))   # not lane tiles
    assert not causal_conv.supported(x, sds((4352, 9)))                     # too many taps
    assert not causal_conv.supported(x, w, (4000,))                         # a part not whole
    assert not causal_conv.supported(sds((1, 8192, 8512)), w, (), 4000)     # a window not whole
    assert not causal_conv.supported(sds((1, 8192, 8512)), w, (), 4224)     # past the end
    x, w, b = _conv_operands((1, 16, 128), 4, jnp.bfloat16)
    ops_nn.causal_conv1d(x, w, b)
    gauge = metrics.gauge("mxtpu_causal_conv_fused", kernel="causal_conv_c128_k4")
    assert gauge.value == 0                                  # the CPU traced the plain form
    monkeypatch.setattr(causal_conv, "_interpret_for", lambda x: False)
    monkeypatch.setattr(ops_nn, "causal_conv_fused", lambda *a: "kernels")
    assert ops_nn.causal_conv1d(x, w, b) == "kernels" and gauge.value == 1
    w9 = jnp.zeros((128, 9), jnp.bfloat16)
    assert ops_nn.causal_conv1d(x, w9, b).shape == x.shape             # refused: the plain form
    assert metrics.gauge("mxtpu_causal_conv_fused", kernel="causal_conv_c128_k9").value == 0


def test_rms_norm_gated_is_the_plain_form():
    rng = onp.random.default_rng(9)
    y, z = rng.normal(size=(2, 5, 16)), rng.normal(size=(2, 5, 16))
    gamma = rng.normal(size=16)
    g = y * z / (1 + onp.exp(-z))
    want = g / onp.sqrt((g ** 2).mean(-1, keepdims=True) + 1e-5) * gamma
    got = ops_nn.rms_norm_gated(jnp.asarray(y, jnp.float32), jnp.asarray(z, jnp.float32),
                                jnp.asarray(gamma, jnp.float32), eps=1e-5)
    onp.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _batch(seed=0):
    rng = onp.random.default_rng(seed)
    seq = rng.integers(0, CFG["vocab_size"], (B, L + 1)).astype("int32")
    return (seq[:, :L], onp.tile(onp.arange(L, dtype="int32"), (B, 1)),
            onp.array([L, L * 3 // 4], "float32"), seq[:, 1:])


@pytest.fixture(scope="module")
def net_and_params():
    mx.random.seed(0)
    net = models.get_granite_hybrid(CFG)
    net.initialize(mx.init.Normal(0.02))
    params = {k[len(net.prefix):]: p.data()._data for k, p in net.collect_params().items()}
    return net, params


def _keep():
    return onp.arange(L)[None, :] < _batch()[2][:, None]


def test_layer_types_build_their_mixers_in_order(net_and_params):
    net, _ = net_and_params
    kinds = [type(layer.mixer) for layer in net.layers]
    assert kinds == [GraniteMamba, GraniteAttention, GraniteMamba]
    with pytest.raises(ValueError, match="layer_types"):
        models.get_granite_hybrid({**CFG, "layer_types": ["mamba", "conv"]})
    with pytest.raises(ValueError, match="nope"):
        models.get_granite_hybrid({**CFG, "position_embedding_type": "rope"})


def test_the_initialisers_are_the_mamba2_familys(net_and_params):
    _, p = net_and_params
    for i in (0, 2):
        a = onp.exp(onp.asarray(p[f"layer{i}_mamba_A_log"]))
        dt = onp.log1p(onp.exp(onp.asarray(p[f"layer{i}_mamba_dt_bias"])))
        assert 1.0 <= a.min() and a.max() <= 16.0
        assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
        assert (onp.asarray(p[f"layer{i}_mamba_D"]) == 1).all()
        for name in ("conv_weight", "conv_bias"):
            w = onp.asarray(p[f"layer{i}_mamba_{name}"])
            assert onp.abs(w).max() <= 0.5 and w.std() > 0.2      # U[-1/2, 1/2], not N(0, 0.02)
    assert onp.asarray(p["layer0_mamba_in_proj_weight"]).std() == pytest.approx(0.02, rel=0.1)


def test_logits_and_loss_match_the_reference(net_and_params):
    net, params = net_and_params
    ids, pos, vl, lab = _batch()
    args = [mx.nd.array(a, dtype=a.dtype) for a in (ids, pos, vl, lab)]
    logits, valid = net(*args[:3])
    out = reference.forward(params, CFG, ids, pos, vl)
    want = reference.logits(params, CFG, out["hidden"])
    keep = _keep()
    assert _rel(onp.asarray(logits.asnumpy())[keep], onp.asarray(want)[keep]) < 1e-5
    loss = float(models.afmoe_lm_loss((logits, valid), args[3]).asnumpy())
    r_loss = float(reference.lm_loss(params, CFG, out["hidden"], out["valid"], lab, block=16))
    assert abs(loss - r_loss) < 1e-5 * abs(r_loss)


@pytest.mark.parametrize("key", ["embedding_multiplier", "residual_multiplier",
                                 "logits_scaling"],
                         ids=["embedding_12", "residual_0.22", "logits_8"])
def test_each_multiplier_matters(net_and_params, key):
    """The reference with one multiplier dropped (set to 1) is far from the
    model, which matches the reference that has it to float32 rounding: a
    model that lost it would fail the match."""
    net, params = net_and_params
    ids, pos, vl, _ = _batch()
    logits = onp.asarray(net(*[mx.nd.array(a, dtype=a.dtype) for a in (ids, pos, vl)])[0].asnumpy())
    cfg = {**CFG, key: 1.0}
    out = reference.forward(params, cfg, ids, pos, vl)
    other = onp.asarray(reference.logits(params, cfg, out["hidden"]))
    keep = _keep()
    assert _rel(logits[keep], other[keep]) > 1e-2


def test_the_attention_scale_is_the_multiplier_and_not_the_head_size():
    """Causal attention with no positions at ``attention_multiplier`` (1/16
    here, 1/64 as published): the block against a plain softmax at that
    scale, and far from one at ``head_dim ** -0.5``, with weights large
    enough that the scale moves the softmax."""
    mx.random.seed(1)
    block = GraniteAttention(64, 4, 2, 0.0625, prefix="attn_")
    block.initialize(mx.init.Normal(0.5))
    x = onp.random.default_rng(10).normal(size=(2, 16, 64)).astype("float32")
    keep = onp.ones((2, 16), "float32")
    got = onp.asarray(block(mx.nd.array(x), None, mx.nd.array(keep)).asnumpy())
    w = {n[len("attn_"):]: onp.asarray(p.data().asnumpy(), "float64")
         for n, p in block.collect_params().items()}

    def plain(scale):
        q = (x @ w["q_weight"].T).reshape(2, 16, 4, 16)
        k = onp.repeat((x @ w["k_weight"].T).reshape(2, 16, 2, 16), 2, axis=2)
        v = onp.repeat((x @ w["v_weight"].T).reshape(2, 16, 2, 16), 2, axis=2)
        s = onp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        s = onp.where(onp.tril(onp.ones((16, 16), bool)), s, -onp.inf)
        p = onp.exp(s - s.max(-1, keepdims=True))
        o = onp.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True), v)
        return o.reshape(2, 16, 64) @ w["o_weight"].T
    assert _rel(got, plain(0.0625)) < 1e-5
    assert _rel(got, plain(16 ** -0.5)) > 1e-1


def test_gradients_and_one_compiled_step_match_the_reference(net_and_params):
    """The trainer's forward-backward half against the reference's
    gradients, every parameter of a Mamba layer and the attention layer's
    query among them; then one compiled step with recomputation on changes
    nothing in the half's loss."""
    _, params = net_and_params
    ids, pos, vl, lab = _batch(1)
    mx.random.seed(0)
    net = models.get_granite_hybrid(CFG, remat=True)
    net.initialize(mx.init.Normal(0.02))
    net.collect_params().setattr("grad_req", "null")
    tr = parallel.ShardedTrainer(net, models.afmoe_lm_loss, "adamw", {"learning_rate": 1e-3},
                                 mesh=parallel.make_mesh(devices=jax.devices()[:1]), n_labels=1)
    batch = (ids, pos, vl, lab)
    tr.prepare(*batch)
    names = sorted(k[len(net.prefix):] for k in net.collect_params())
    half = tr._make_loss_grads(3)
    loss, _norm, grads, _e, _t = jax.jit(half)(tr._param_vals, tr._base_key, tr._t_dev,
                                               *tr.place(*batch))
    wrt = [n for n in names if n.startswith(("layer0_mamba_", "layer1_attn_q"))]
    r_loss, _out, r_grads = reference.loss_and_grads(params, CFG, ids, pos, vl, lab, wrt)
    assert abs(float(loss) - float(r_loss)) < 1e-5 * abs(float(r_loss))
    for n in wrt:
        assert _rel(grads[names.index(n)], r_grads[n]) < 1e-4, n
    first = float(tr.step(*batch).asnumpy())
    assert abs(first - float(loss)) < 1e-5 * abs(first)
    assert float(tr.step(*batch).asnumpy()) < first
