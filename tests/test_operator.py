"""Operator numerics vs numpy golden (reference: tests/python/unittest/test_operator.py)."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu.test_utils import (
    assert_almost_equal, check_numeric_gradient, rand_ndarray,
)


def test_unary_ops():
    x_np = onp.random.uniform(0.5, 2.0, (3, 4)).astype(onp.float32)
    x = nd.array(x_np)
    for name, ref in [
        ("exp", onp.exp), ("log", onp.log), ("sqrt", onp.sqrt),
        ("square", onp.square), ("abs", onp.abs), ("sign", onp.sign),
        ("sin", onp.sin), ("cos", onp.cos), ("tanh", onp.tanh),
        ("floor", onp.floor), ("ceil", onp.ceil),
    ]:
        assert_almost_equal(getattr(nd, name)(x), ref(x_np), rtol=1e-5, atol=1e-5)


def test_activation_ops():
    x_np = onp.random.uniform(-3, 3, (5, 5)).astype(onp.float32)
    x = nd.array(x_np)
    assert_almost_equal(nd.relu(x), onp.maximum(x_np, 0))
    assert_almost_equal(nd.sigmoid(x), 1 / (1 + onp.exp(-x_np)), rtol=1e-5)
    assert_almost_equal(nd.Activation(x, act_type="tanh"), onp.tanh(x_np), rtol=1e-5)
    assert_almost_equal(nd.LeakyReLU(x, act_type="leaky", slope=0.1),
                        onp.where(x_np >= 0, x_np, 0.1 * x_np), rtol=1e-5)
    # elu / selu / gelu sanity
    for t in ("elu", "selu", "gelu"):
        out = nd.LeakyReLU(x, act_type=t)
        assert out.shape == x.shape


def test_reductions():
    x_np = onp.random.uniform(-1, 1, (2, 3, 4)).astype(onp.float32)
    x = nd.array(x_np)
    assert_almost_equal(nd.sum(x), x_np.sum(), rtol=1e-5)
    assert_almost_equal(nd.sum(x, axis=1), x_np.sum(axis=1), rtol=1e-5)
    assert_almost_equal(nd.mean(x, axis=(0, 2)), x_np.mean(axis=(0, 2)), rtol=1e-5)
    assert_almost_equal(nd.max(x, axis=2), x_np.max(axis=2))
    assert_almost_equal(nd.min(x), x_np.min())
    assert_almost_equal(nd.prod(x, axis=0), x_np.prod(axis=0), rtol=1e-5)
    assert_almost_equal(nd.norm(x), onp.sqrt((x_np ** 2).sum()), rtol=1e-5)
    assert_almost_equal(nd.sum(x, axis=1, exclude=True), x_np.sum(axis=(0, 2)), rtol=1e-5)


def test_argmax_argmin():
    x_np = onp.random.uniform(-1, 1, (3, 7)).astype(onp.float32)
    x = nd.array(x_np)
    assert_almost_equal(nd.argmax(x, axis=1), x_np.argmax(axis=1).astype(onp.float32))
    assert_almost_equal(nd.argmin(x, axis=0), x_np.argmin(axis=0).astype(onp.float32))


def test_dot():
    a_np = onp.random.normal(size=(3, 4)).astype(onp.float32)
    b_np = onp.random.normal(size=(4, 5)).astype(onp.float32)
    assert_almost_equal(nd.dot(nd.array(a_np), nd.array(b_np)), a_np @ b_np, rtol=1e-4)
    # transpose flags
    assert_almost_equal(
        nd.dot(nd.array(a_np), nd.array(b_np.T), transpose_b=True), a_np @ b_np, rtol=1e-4)
    assert_almost_equal(
        nd.dot(nd.array(a_np.T), nd.array(b_np), transpose_a=True), a_np @ b_np, rtol=1e-4)
    # ND dot: contract last axis of lhs with first of rhs
    c_np = onp.random.normal(size=(2, 3, 4)).astype(onp.float32)
    d_np = onp.random.normal(size=(4, 6)).astype(onp.float32)
    assert_almost_equal(nd.dot(nd.array(c_np), nd.array(d_np)),
                        onp.tensordot(c_np, d_np, axes=(2, 0)), rtol=1e-4)


def test_batch_dot():
    a_np = onp.random.normal(size=(5, 3, 4)).astype(onp.float32)
    b_np = onp.random.normal(size=(5, 4, 2)).astype(onp.float32)
    assert_almost_equal(nd.batch_dot(nd.array(a_np), nd.array(b_np)),
                        onp.matmul(a_np, b_np), rtol=1e-4)
    assert_almost_equal(
        nd.batch_dot(nd.array(a_np), nd.array(onp.swapaxes(b_np, 1, 2)), transpose_b=True),
        onp.matmul(a_np, b_np), rtol=1e-4)


def test_take_pick_gather():
    x_np = onp.random.normal(size=(4, 5)).astype(onp.float32)
    x = nd.array(x_np)
    idx = nd.array(onp.array([0, 3], dtype=onp.int32))
    assert_almost_equal(nd.take(x, idx, axis=0), x_np[[0, 3]])
    pick_idx = nd.array(onp.array([1, 0, 2, 4], dtype=onp.int32))
    assert_almost_equal(nd.pick(x, pick_idx, axis=1),
                        x_np[onp.arange(4), [1, 0, 2, 4]])
    gnd_idx = nd.array(onp.array([[0, 1], [1, 2]], dtype=onp.int32))
    assert_almost_equal(nd.gather_nd(x, gnd_idx), x_np[[0, 1], [1, 2]])


def test_one_hot_embedding():
    idx = nd.array(onp.array([0, 2, 1], dtype=onp.int32))
    oh = nd.one_hot(idx, depth=4)
    ref = onp.eye(4, dtype=onp.float32)[[0, 2, 1]]
    assert_almost_equal(oh, ref)
    w_np = onp.random.normal(size=(10, 6)).astype(onp.float32)
    emb = nd.Embedding(idx, nd.array(w_np), input_dim=10, output_dim=6)
    assert_almost_equal(emb, w_np[[0, 2, 1]])


def test_embedding_onehot_grad_matches_scatter():
    """MXTPU_EMBED_ONEHOT_GRAD=1 swaps the scatter-add weight gradient for a
    one-hot MXU matmul — values must be identical (incl. repeated indices)."""
    import os
    import jax
    import jax.numpy as jnp

    idx = jnp.array([[0, 2, 2, 5], [1, 1, 9, 0]], jnp.int32)
    w = jnp.asarray(onp.random.normal(size=(10, 6)).astype(onp.float32))
    ct = jnp.asarray(onp.random.normal(size=(2, 4, 6)).astype(onp.float32))
    from incubator_mxnet_tpu.ops import tensor as T

    def loss(weight, use_onehot):
        os.environ["MXTPU_EMBED_ONEHOT_GRAD"] = "1" if use_onehot else "0"
        try:
            return (T.embedding(idx, weight) * ct).sum()
        finally:
            os.environ.pop("MXTPU_EMBED_ONEHOT_GRAD", None)

    g_scatter = jax.grad(lambda w: loss(w, False))(w)
    g_onehot = jax.grad(lambda w: loss(w, True))(w)
    assert_almost_equal(g_onehot, g_scatter, rtol=1e-6, atol=1e-6)


def test_softmax_family():
    x_np = onp.random.normal(size=(3, 6)).astype(onp.float32)
    x = nd.array(x_np)
    e = onp.exp(x_np - x_np.max(axis=-1, keepdims=True))
    ref = e / e.sum(axis=-1, keepdims=True)
    assert_almost_equal(nd.softmax(x), ref, rtol=1e-5)
    assert_almost_equal(nd.log_softmax(x), onp.log(ref), rtol=1e-4)
    # softmax with length masking (SoftmaxWithLength parity)
    length = nd.array(onp.array([2, 4, 6], dtype=onp.int32))
    out = nd.softmax(x, length, axis=-1, use_length=True).asnumpy()
    assert out[0, 2:].sum() == pytest.approx(0.0, abs=1e-6)
    assert out[0, :2].sum() == pytest.approx(1.0, rel=1e-5)


def test_topk_sort():
    x_np = onp.random.permutation(24).reshape(4, 6).astype(onp.float32)
    x = nd.array(x_np)
    vals = nd.topk(x, k=3, ret_typ="value")
    ref = -onp.sort(-x_np, axis=-1)[:, :3]
    assert_almost_equal(vals, ref)
    both = nd.topk(x, k=2, ret_typ="both")
    assert len(both) == 2
    asc = nd.topk(x, k=2, ret_typ="value", is_ascend=True)
    assert_almost_equal(asc, onp.sort(x_np, axis=-1)[:, :2])
    assert_almost_equal(nd.sort(x, is_ascend=False), -onp.sort(-x_np, axis=-1))
    assert_almost_equal(nd.argsort(x, is_ascend=True),
                        onp.argsort(x_np, axis=-1).astype(onp.float32))


def test_elementwise_broadcast_binary():
    a_np = onp.random.normal(size=(2, 1, 4)).astype(onp.float32)
    b_np = onp.random.normal(size=(1, 3, 4)).astype(onp.float32)
    a, b = nd.array(a_np), nd.array(b_np)
    assert_almost_equal(nd.broadcast_add(a, b), a_np + b_np, rtol=1e-5)
    assert_almost_equal(nd.broadcast_mul(a, b), a_np * b_np, rtol=1e-5)
    assert_almost_equal(nd.maximum(a, b), onp.maximum(a_np, b_np))
    assert_almost_equal(nd.minimum(a, b), onp.minimum(a_np, b_np))


def test_where_clip():
    x_np = onp.random.normal(size=(3, 3)).astype(onp.float32)
    x = nd.array(x_np)
    assert_almost_equal(nd.clip(x, a_min=-0.5, a_max=0.5), onp.clip(x_np, -0.5, 0.5))
    cond = nd.array((x_np > 0).astype(onp.float32))
    assert_almost_equal(nd.where(cond, x, -x), onp.where(x_np > 0, x_np, -x_np))


def test_convolution_shapes_and_numerics():
    # 3x3 conv vs explicit correlation
    x_np = onp.random.normal(size=(2, 3, 8, 8)).astype(onp.float32)
    w_np = onp.random.normal(size=(5, 3, 3, 3)).astype(onp.float32)
    b_np = onp.random.normal(size=(5,)).astype(onp.float32)
    out = nd.Convolution(nd.array(x_np), nd.array(w_np), nd.array(b_np),
                         kernel=(3, 3), num_filter=5, stride=(1, 1), pad=(1, 1))
    assert out.shape == (2, 5, 8, 8)
    # golden via scipy-style direct computation at one position
    patch = x_np[0, :, 0:3, 0:3]
    expect = (patch * w_np[1]).sum() + b_np[1]
    assert out.asnumpy()[0, 1, 1, 1] == pytest.approx(expect, rel=1e-4)
    # stride-2, no pad
    out2 = nd.Convolution(nd.array(x_np), nd.array(w_np), nd.array(b_np),
                          kernel=(3, 3), num_filter=5, stride=(2, 2), pad=(0, 0))
    assert out2.shape == (2, 5, 3, 3)
    # grouped conv
    wg = onp.random.normal(size=(6, 1, 3, 3)).astype(onp.float32)
    outg = nd.Convolution(nd.array(x_np[:, :3]), nd.array(wg[:3]), None, kernel=(3, 3),
                          num_filter=3, num_group=3, pad=(1, 1), no_bias=True)
    assert outg.shape == (2, 3, 8, 8)


def test_pooling():
    x_np = onp.random.normal(size=(1, 2, 6, 6)).astype(onp.float32)
    x = nd.array(x_np)
    mp = nd.Pooling(x, kernel=(2, 2), stride=(2, 2), pool_type="max")
    assert mp.shape == (1, 2, 3, 3)
    assert mp.asnumpy()[0, 0, 0, 0] == x_np[0, 0, :2, :2].max()
    ap = nd.Pooling(x, kernel=(2, 2), stride=(2, 2), pool_type="avg")
    assert ap.asnumpy()[0, 1, 1, 1] == pytest.approx(x_np[0, 1, 2:4, 2:4].mean(), rel=1e-5)
    gp = nd.Pooling(x, pool_type="avg", global_pool=True)
    assert gp.shape == (1, 2, 1, 1)
    assert gp.asnumpy()[0, 0, 0, 0] == pytest.approx(x_np[0, 0].mean(), rel=1e-5)


def test_fully_connected():
    x_np = onp.random.normal(size=(4, 3, 2)).astype(onp.float32)
    w_np = onp.random.normal(size=(7, 6)).astype(onp.float32)
    b_np = onp.random.normal(size=(7,)).astype(onp.float32)
    out = nd.FullyConnected(nd.array(x_np), nd.array(w_np), nd.array(b_np), num_hidden=7)
    ref = x_np.reshape(4, 6) @ w_np.T + b_np
    assert_almost_equal(out, ref, rtol=1e-4)
    # flatten=False
    out2 = nd.FullyConnected(nd.array(x_np), nd.array(onp.random.normal(size=(7, 2)).astype(onp.float32)),
                             None, num_hidden=7, no_bias=True, flatten=False)
    assert out2.shape == (4, 3, 7)


def test_batchnorm_layernorm():
    x_np = onp.random.normal(size=(4, 3, 5, 5)).astype(onp.float32)
    gamma = onp.random.uniform(0.5, 1.5, 3).astype(onp.float32)
    beta = onp.random.normal(size=3).astype(onp.float32)
    mean = x_np.mean(axis=(0, 2, 3))
    var = x_np.var(axis=(0, 2, 3))
    out, m, v = nd.BatchNorm(nd.array(x_np), nd.array(gamma), nd.array(beta),
                             nd.array(mean), nd.array(var), fix_gamma=False, training=True)
    ref = (x_np - mean.reshape(1, 3, 1, 1)) / onp.sqrt(var.reshape(1, 3, 1, 1) + 1e-5)
    ref = ref * gamma.reshape(1, 3, 1, 1) + beta.reshape(1, 3, 1, 1)
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-4)

    x2 = onp.random.normal(size=(2, 5, 8)).astype(onp.float32)
    g2 = onp.ones(8, onp.float32)
    b2 = onp.zeros(8, onp.float32)
    ln = nd.LayerNorm(nd.array(x2), nd.array(g2), nd.array(b2), axis=-1)
    ref2 = (x2 - x2.mean(-1, keepdims=True)) / onp.sqrt(x2.var(-1, keepdims=True) + 1e-5)
    assert_almost_equal(ln, ref2, rtol=1e-4, atol=1e-4)


def _layer_norm_formerly(x, g, b, eps=1e-5):
    """``ops.nn.layer_norm`` as it stood before PR 26: the normalised value
    cast to the input dtype, then scaled and shifted in whatever dtype the
    product promotes to. Kept as the float32 reference."""
    x32 = x.astype(jnp.float32)
    m = jnp.mean(x32, axis=-1, keepdims=True)
    v = jnp.var(x32, axis=-1, keepdims=True)
    out = ((x32 - m) * jax.lax.rsqrt(v + eps)).astype(x.dtype)
    return out * g.reshape(1, 1, -1) + b.reshape(1, 1, -1), m[..., 0], v[..., 0]


def _rms_norm_formerly(x, g, b, eps=1e-6):
    x32 = x.astype(jnp.float32)
    v = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(v + eps)).astype(x.dtype) * g, None, None


_NORMS = {
    "layer_norm": (lambda x, g, b, **kw: nd.LayerNorm(x, g, b, axis=-1, **kw),
                   _layer_norm_formerly),
    "rms_norm": (lambda x, g, b, **kw: nd.RMSNorm(x, g, axis=-1),
                 _rms_norm_formerly),
}


@pytest.mark.parametrize("norm,case", [
    (n, c) for n in sorted(_NORMS)
    for c in ("bf16_stays_bf16", "bf16_parameters", "float32_bit_identical",
              "statistics_fp32")
    if (n, c) != ("rms_norm", "statistics_fp32")])   # it returns none
def test_norm_output_dtype_follows_input(norm, case):
    """A norm's output has its input's dtype whatever its parameters' dtype
    (the layers keep gamma/beta float32 in a bf16 net): statistics, scale
    and shift in float32, one cast at the end."""
    op, formerly = _NORMS[norm]
    rng = onp.random.RandomState(7)
    x = rng.normal(size=(2, 5, 64)).astype("float32") * 3 + 1
    g = nd.array(rng.uniform(0.5, 1.5, 64).astype("float32"))
    b = nd.array(rng.normal(size=64).astype("float32"))
    if case == "bf16_stays_bf16":
        xb = nd.array(x).astype("bfloat16")
        out = op(xb, g, b)
        assert out.dtype == jnp.bfloat16
        # the all-float32 result on the same (bf16-valued) input, rounded once
        want = op(xb.astype("float32"), g, b).asnumpy()
        got = out.astype("float32").asnumpy()
        assert onp.all(onp.abs(got - want) <= 2.0 ** -8 * onp.abs(want))
    elif case == "bf16_parameters":
        # parameters of the data's own dtype (a net cast whole) change nothing
        gb, bb = g.astype("bfloat16"), b.astype("bfloat16")
        xb = nd.array(x).astype("bfloat16")
        assert op(xb, gb, bb).dtype == jnp.bfloat16
        out = op(nd.array(x), gb, bb)
        assert out.dtype == onp.float32
        want, _, _ = formerly(jnp.asarray(x), gb._data, bb._data)
        onp.testing.assert_array_equal(out.asnumpy(), onp.asarray(want))
    elif case == "float32_bit_identical":
        out = op(nd.array(x), g, b)
        assert out.dtype == onp.float32
        want, _, _ = formerly(jnp.asarray(x), g._data, b._data)
        onp.testing.assert_array_equal(out.asnumpy(), onp.asarray(want))
    else:
        xb = nd.array(x).astype("bfloat16")
        out, m, v = op(xb, g, b, output_mean_var=True)
        _, want_m, want_v = formerly(xb._data, g._data, b._data)
        assert out.dtype == jnp.bfloat16
        assert m.dtype == onp.float32 and v.dtype == onp.float32
        onp.testing.assert_array_equal(m.asnumpy(), onp.asarray(want_m))
        onp.testing.assert_array_equal(v.asnumpy(), onp.asarray(want_v))


def test_sequence_ops():
    # (T=4, B=2, C=3)
    x_np = onp.random.normal(size=(4, 2, 3)).astype(onp.float32)
    x = nd.array(x_np)
    slen = nd.array(onp.array([2, 4], dtype=onp.float32))
    masked = nd.SequenceMask(x, slen, use_sequence_length=True, value=-1.0)
    m = masked.asnumpy()
    assert (m[2:, 0] == -1.0).all() and (m[:, 1] == x_np[:, 1]).all()
    last = nd.SequenceLast(x, slen, use_sequence_length=True)
    assert_almost_equal(last, onp.stack([x_np[1, 0], x_np[3, 1]]))
    rev = nd.SequenceReverse(x, slen, use_sequence_length=True)
    assert_almost_equal(rev.asnumpy()[0, 0], x_np[1, 0])
    assert_almost_equal(rev.asnumpy()[0, 1], x_np[3, 1])


def test_rnn_op_shapes():
    T, N, C, H = 5, 2, 4, 6
    x = nd.array(onp.random.normal(size=(T, N, C)).astype(onp.float32))
    from incubator_mxnet_tpu.ops.nn import rnn_param_size
    for mode, nstates in [("lstm", 2), ("gru", 1), ("rnn_tanh", 1)]:
        psize = rnn_param_size(mode, 1, C, H, False)
        params = nd.array(onp.random.normal(scale=0.1, size=(psize,)).astype(onp.float32))
        h0 = nd.zeros((1, N, H))
        if mode == "lstm":
            out = nd.RNN(x, params, h0, nd.zeros((1, N, H)), state_size=H,
                         num_layers=1, mode=mode, state_outputs=True)
            assert out[0].shape == (T, N, H) and out[1].shape == (1, N, H) and out[2].shape == (1, N, H)
        else:
            out = nd.RNN(x, params, h0, state_size=H, num_layers=1, mode=mode)
            assert out.shape == (T, N, H)
    # bidirectional
    psize = rnn_param_size("lstm", 2, C, H, True)
    params = nd.array(onp.random.normal(scale=0.1, size=(psize,)).astype(onp.float32))
    out = nd.RNN(x, params, nd.zeros((4, N, H)), nd.zeros((4, N, H)), state_size=H,
                 num_layers=2, mode="lstm", bidirectional=True)
    assert out.shape == (T, N, 2 * H)


def test_dropout_modes():
    import incubator_mxnet_tpu.random as rng
    x = nd.ones((100, 100))
    out_eval = nd.Dropout(x, p=0.5, training=False)
    assert_almost_equal(out_eval, onp.ones((100, 100)))
    key = rng.next_key(x.context)
    out_train = nd.Dropout(x, p=0.5, training=True, key=key)
    frac = (out_train.asnumpy() == 0).mean()
    assert 0.4 < frac < 0.6


def test_linalg_ops():
    a_np = onp.random.normal(size=(3, 4)).astype(onp.float32)
    b_np = onp.random.normal(size=(4, 5)).astype(onp.float32)
    assert_almost_equal(nd.linalg_gemm2(nd.array(a_np), nd.array(b_np)), a_np @ b_np, rtol=1e-4)
    spd = onp.eye(4, dtype=onp.float32) * 3 + 0.1
    L = nd.linalg_potrf(nd.array(spd))
    assert_almost_equal(nd.batch_dot(L.expand_dims(0), L.expand_dims(0), transpose_b=True)[0],
                        spd, rtol=1e-4)


def test_pad_tile_repeat_flip():
    x_np = onp.arange(6, dtype=onp.float32).reshape(2, 3)
    x = nd.array(x_np)
    p = nd.pad(x.reshape((1, 1, 2, 3)), mode="constant",
               pad_width=(0, 0, 0, 0, 1, 1, 2, 2), constant_value=9.0)
    assert p.shape == (1, 1, 4, 7)
    assert p.asnumpy()[0, 0, 0, 0] == 9.0
    assert_almost_equal(nd.tile(x, reps=(2, 1)), onp.tile(x_np, (2, 1)))
    assert_almost_equal(nd.repeat(x, repeats=2, axis=1), onp.repeat(x_np, 2, 1))
    assert_almost_equal(nd.reverse(x, axis=1), x_np[:, ::-1])


def test_scalar_ops_on_int():
    x = nd.array(onp.array([5, 7], dtype=onp.int32))
    assert (x % 2).asnumpy().tolist() == [1, 1]
    assert (x // 2).asnumpy().tolist() == [2, 3]


def test_multi_output_ops_record_safe():
    # ops returning tuples work under autograd recording
    from incubator_mxnet_tpu import autograd as ag
    x = nd.array(onp.random.normal(size=(3, 5)).astype(onp.float32))
    x.attach_grad()
    with ag.record():
        vals, idx = nd.topk(x, k=2, ret_typ="both")
        loss = vals.sum()
    loss.backward()
    g = x.grad.asnumpy()
    assert (g.sum(axis=1) == 2).all()


# ---------------------------------------------------------------------------
# vision ops (round 3): STN family, Correlation, Crop, batch_take, MakeLoss
# ---------------------------------------------------------------------------

def test_grid_generator_identity_affine():
    # identity affine: theta = [1,0,0, 0,1,0] -> grid == meshgrid in [-1,1]
    theta = mx.nd.array(onp.array([[1, 0, 0, 0, 1, 0]], "float32"))
    g = mx.nd.GridGenerator(theta, transform_type="affine",
                            target_shape=(3, 4)).asnumpy()
    assert g.shape == (1, 2, 3, 4)
    onp.testing.assert_allclose(g[0, 0, 0], onp.linspace(-1, 1, 4), atol=1e-6)
    onp.testing.assert_allclose(g[0, 1, :, 0], onp.linspace(-1, 1, 3),
                                atol=1e-6)


def test_bilinear_sampler_identity():
    rng = onp.random.RandomState(0)
    x = rng.randn(2, 3, 5, 6).astype("float32")
    theta = onp.tile(onp.array([[1, 0, 0, 0, 1, 0]], "float32"), (2, 1))
    out = mx.nd.SpatialTransformer(mx.nd.array(x), mx.nd.array(theta),
                                   target_shape=(5, 6)).asnumpy()
    onp.testing.assert_allclose(out, x, rtol=1e-4, atol=1e-5)


def test_spatial_transformer_translation():
    # shift sampling one pixel right: out[..., j] == x[..., j+1]
    x = onp.arange(2 * 1 * 4 * 4, dtype="float32").reshape(2, 1, 4, 4)
    tx = 2.0 / 3.0   # one pixel in normalized coords for W=4
    theta = onp.tile(onp.array([[1, 0, tx, 0, 1, 0]], "float32"), (2, 1))
    out = mx.nd.SpatialTransformer(mx.nd.array(x), mx.nd.array(theta),
                                   target_shape=(4, 4)).asnumpy()
    onp.testing.assert_allclose(out[..., :3], x[..., 1:], rtol=1e-4,
                                atol=1e-4)


def test_correlation_reference_geometry_and_values():
    rng = onp.random.RandomState(1)
    a = rng.randn(1, 4, 6, 6).astype("float32")
    b = rng.randn(1, 4, 6, 6).astype("float32")
    out = mx.nd.Correlation(mx.nd.array(a), mx.nd.array(b),
                            max_displacement=1).asnumpy()
    # reference shape: border = max_displacement + (k-1)/2 = 1 -> 4x4
    assert out.shape == (1, 9, 4, 4)
    inner = slice(1, -1)
    onp.testing.assert_allclose(
        out[0, 4], (a * b).mean(1)[0][inner, inner], rtol=1e-5)
    # displacement (dy=0, dx=1) = channel index 5: b sampled one col right
    onp.testing.assert_allclose(
        out[0, 5], (a[..., :, 1:-1] * b[..., :, 2:]).mean(1)[0][inner],
        rtol=1e-5)


def test_make_loss_valid_normalization_and_dtype():
    x = mx.nd.array(onp.array([0.5, -1.0, 2.0, 0.2], "float32"))
    x.attach_grad()
    with mx.autograd.record():
        l = mx.nd.MakeLoss(x, grad_scale=6.0, normalization="valid",
                           valid_thresh=0.3)
    l.backward()
    # 2 elements above 0.3 -> scale 6/2 = 3 everywhere
    onp.testing.assert_allclose(x.grad.asnumpy(), [3.0] * 4)
    # dtype follows the primal
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops.vision import make_loss
    g = jax.grad(lambda v: make_loss(v).sum())(
        jnp.ones((3,), jnp.bfloat16))
    assert g.dtype == jnp.bfloat16


def test_crop_center_and_like():
    x = mx.nd.array(onp.arange(36, dtype="float32").reshape(1, 1, 6, 6))
    c = mx.nd.Crop(x, h_w=(2, 2), center_crop=True).asnumpy()
    onp.testing.assert_array_equal(c[0, 0], [[14, 15], [20, 21]])
    ref = mx.nd.zeros((1, 1, 3, 3))
    c2 = mx.nd.Crop(x, ref).asnumpy()
    assert c2.shape == (1, 1, 3, 3)


def test_batch_take():
    a = mx.nd.array(onp.arange(12, dtype="float32").reshape(3, 4))
    idx = mx.nd.array(onp.array([1, 3, 0], "float32"))
    out = mx.nd.batch_take(a, idx).asnumpy()
    onp.testing.assert_array_equal(out, [1.0, 7.0, 8.0])


def test_make_loss_gradient_semantics():
    x = mx.nd.array(onp.array([2.0, -1.0], "float32"))
    x.attach_grad()
    with mx.autograd.record():
        l = mx.nd.MakeLoss(x, grad_scale=3.0)
    l.backward()
    onp.testing.assert_allclose(x.grad.asnumpy(), [3.0, 3.0])


def test_lrn_matches_manual():
    x = onp.random.RandomState(3).randn(2, 7, 3, 3).astype("float32")
    out = mx.nd.LRN(mx.nd.array(x), nsize=5, alpha=1e-4, beta=0.75,
                    knorm=2.0).asnumpy()
    ref = onp.empty_like(x)
    for c in range(7):
        lo, hi = max(0, c - 2), min(7, c + 3)
        s = (x[:, lo:hi] ** 2).sum(1)
        ref[:, c] = x[:, c] * (2.0 + 1e-4 / 5 * s) ** -0.75
    onp.testing.assert_allclose(out, ref, rtol=2e-5)


def test_regression_output_heads():
    d = mx.nd.array(onp.array([[0.5, -1.0]], "float32"))
    lab = mx.nd.array(onp.array([[1.0, 0.0]], "float32"))
    d.attach_grad()
    with mx.autograd.record():
        y = mx.nd.LinearRegressionOutput(d, lab, grad_scale=2.0)
    y.backward()
    onp.testing.assert_allclose(y.asnumpy(), d.asnumpy())
    # grad = (pred - label) * grad_scale / num_output, num_output = 2
    onp.testing.assert_allclose(d.grad.asnumpy(), [[-0.5, -1.0]], rtol=1e-6)

    d2 = mx.nd.array(onp.array([[0.0, 2.0]], "float32"))
    d2.attach_grad()
    with mx.autograd.record():
        y2 = mx.nd.LogisticRegressionOutput(d2, lab)
    y2.backward()
    sig = 1 / (1 + onp.exp(-d2.asnumpy()))
    onp.testing.assert_allclose(y2.asnumpy(), sig, rtol=1e-6)
    onp.testing.assert_allclose(d2.grad.asnumpy(),
                                (sig - lab.asnumpy()) / 2.0, rtol=1e-6)

    d3 = mx.nd.array(onp.array([[0.5, -1.0]], "float32"))
    d3.attach_grad()
    with mx.autograd.record():
        y3 = mx.nd.MAERegressionOutput(d3, lab)
    y3.backward()
    onp.testing.assert_allclose(d3.grad.asnumpy(), [[-0.5, -0.5]])


def test_svm_output_hinge_gradients():
    # class 0 true; scores violate the margin for both classes
    d = mx.nd.array(onp.array([[0.2, 0.5]], "float32"))
    d.attach_grad()
    with mx.autograd.record():
        y = mx.nd.SVMOutput(d, mx.nd.array(onp.array([0.0], "float32")),
                            use_linear=True)
    y.backward()
    # y0=+1: viol=1-0.2=0.8>0 -> -1; y1=-1: viol=1+0.5=1.5>0 -> +1
    onp.testing.assert_allclose(d.grad.asnumpy(), [[-1.0, 1.0]])
    # L2-SVM scales by 2*viol
    d2 = mx.nd.array(onp.array([[0.2, 0.5]], "float32"))
    d2.attach_grad()
    with mx.autograd.record():
        y2 = mx.nd.SVMOutput(d2, mx.nd.array(onp.array([0.0], "float32")))
    y2.backward()
    onp.testing.assert_allclose(d2.grad.asnumpy(), [[-1.6, 3.0]], rtol=1e-6)


def test_np_compat_additions():
    a = mx.nd.array(onp.arange(6, dtype="float32").reshape(2, 3))
    onp.testing.assert_allclose(mx.nd.cumsum(a, axis=1).asnumpy(),
                                onp.cumsum(a.asnumpy(), axis=1))
    onp.testing.assert_allclose(mx.nd.cumprod(a + 1, axis=0).asnumpy(),
                                onp.cumprod(a.asnumpy() + 1, axis=0))
    onp.testing.assert_allclose(mx.nd.trace(a).asnumpy(),
                                onp.trace(a.asnumpy()))
    b = mx.nd.array(onp.array([[0.0, 1.0], [1.0, 0.0]], "float32"))
    onp.testing.assert_allclose(mx.nd.kron(b, a).asnumpy(),
                                onp.kron(b.asnumpy(), a.asnumpy()))
    onp.testing.assert_allclose(
        mx.nd.bincount(mx.nd.array(onp.array([0, 1, 1, 3], "float32")),
                       minlength=5).asnumpy(),
        onp.bincount(onp.array([0, 1, 1, 3]), minlength=5))
    from scipy import special as _sp  # scipy ships with jax
    onp.testing.assert_allclose(
        mx.nd.digamma(a + 1).asnumpy(), _sp.digamma(a.asnumpy() + 1),
        rtol=1e-5)


_GRAD_CASES = [
    ("fullyconnected",
     lambda x, w, b: nd.FullyConnected(x, w, b, num_hidden=4),
     [(3, 5), (4, 5), (4,)]),
    ("im2col",
     lambda x: nd.im2col(x, kernel=(2, 2)) * 0.5,
     [(2, 3, 4, 4)]),
    ("linalg_trmm",
     lambda a, b: nd.linalg_trmm(a, b, lower=True),
     [(3, 3), (3, 2)]),
    ("convolution",
     lambda x, w, b: nd.Convolution(x, w, b, kernel=(3, 3), num_filter=2,
                                    pad=(1, 1)),
     [(2, 3, 5, 5), (2, 3, 3, 3), (2,)]),
    ("layernorm",
     lambda x, g, b: nd.LayerNorm(x, g, b, axis=-1),
     [(4, 6), (6,), (6,)]),
    ("softmax", lambda x: nd.softmax(x, axis=-1), [(3, 7)]),
    ("avgpool",
     lambda x: nd.Pooling(x, pool_type="avg", kernel=(2, 2), stride=(2, 2)),
     [(2, 2, 4, 4)]),
    ("lrn", lambda x: nd.LRN(x, nsize=3), [(2, 5, 3, 3)]),
    ("dot", lambda a, b: nd.dot(a, b), [(3, 4), (4, 2)]),
    ("broadcast_mul", lambda a, b: nd.broadcast_mul(a, b), [(3, 4), (1, 4)]),
    ("smooth_l1", lambda x: nd.smooth_l1(x, scalar=1.0), [(6,)]),
    ("swapaxes", lambda x: nd.SwapAxis(x, dim1=0, dim2=1) * 2.0, [(3, 4)]),
    ("groupnorm",
     lambda x, g, b: nd.GroupNorm(x, g, b, num_groups=2),
     [(2, 4, 3, 3), (4,), (4,)]),
]


@pytest.mark.parametrize("name,fn,shapes",
                         _GRAD_CASES, ids=[c[0] for c in _GRAD_CASES])
def test_numeric_gradient_sweep(name, fn, shapes):
    """Finite-difference autograd checks over the op battery (reference
    mechanism: test_utils.check_numeric_gradient applied per op in
    tests/python/unittest/test_operator.py)."""
    import zlib
    rng = onp.random.RandomState(zlib.crc32(name.encode()) % (2 ** 31))
    inputs = [rng.uniform(-1, 1, s).astype("float32") for s in shapes]
    # conv sums ~27 fp32 products per output: central differences carry a
    # bit more roundoff than the pointwise ops
    atol = 5e-3 if name == "convolution" else 2e-3
    check_numeric_gradient(fn, inputs, rtol=2e-2, atol=atol)


def test_tril_triu_trmm():
    a = onp.random.RandomState(3).randn(4, 4).astype("float32")
    x = nd.array(a)
    assert_almost_equal(nd.tril(x), onp.tril(a))
    assert_almost_equal(nd.triu(x, k=1), onp.triu(a, k=1))
    b = onp.random.RandomState(4).randn(4, 3).astype("float32")
    # trmm uses only the triangular half of A
    assert_almost_equal(nd.linalg_trmm(x, nd.array(b)), onp.tril(a) @ b,
                        rtol=1e-5)
    assert_almost_equal(
        nd.linalg_trmm(x, nd.array(b.T), transpose=True, rightside=True,
                       lower=False, alpha=2.0),
        2.0 * (b.T @ onp.triu(a).T), rtol=1e-5)


def test_softmax_activation_modes():
    x = onp.random.RandomState(5).randn(2, 3, 4).astype("float32")
    inst = nd.SoftmaxActivation(nd.array(x)).asnumpy()
    flat = x.reshape(2, -1)
    e = onp.exp(flat - flat.max(axis=1, keepdims=True))
    assert_almost_equal(inst.reshape(2, -1), e / e.sum(axis=1, keepdims=True),
                        rtol=1e-5)
    chan = nd.SoftmaxActivation(nd.array(x), mode="channel").asnumpy()
    ec = onp.exp(x - x.max(axis=1, keepdims=True))
    assert_almost_equal(chan, ec / ec.sum(axis=1, keepdims=True), rtol=1e-5)


def test_all_finite():
    ok = nd.array(onp.ones((3,), "float32"))
    bad = nd.array(onp.array([1.0, onp.inf], "float32"))
    assert float(nd.all_finite(ok).asnumpy()[0]) == 1.0
    assert float(nd.all_finite(bad).asnumpy()[0]) == 0.0
    out = nd.multi_all_finite(ok, bad, num_arrays=2)
    assert float(out.asnumpy()[0]) == 0.0


def test_boolean_mask_eager_only():
    import jax
    x = onp.arange(12, dtype="float32").reshape(4, 3)
    m = onp.array([1, 0, 1, 0], "float32")
    out = mx.contrib.nd.boolean_mask(nd.array(x), nd.array(m))
    assert_almost_equal(out, x[[0, 2]])
    from incubator_mxnet_tpu.ops import tensor as T
    import jax.numpy as jnp
    with pytest.raises(ValueError, match="boolean_mask"):
        jax.jit(T.boolean_mask)(jnp.asarray(x), jnp.asarray(m))
    # differentiable in data (the concrete mask freezes into static indices)
    xv = nd.array(x)
    xv.attach_grad()
    with mx.autograd.record():
        y = mx.contrib.nd.boolean_mask(xv, nd.array(m)).sum()
    y.backward()
    expect = onp.zeros_like(x)
    expect[[0, 2]] = 1.0
    assert_almost_equal(xv.grad, expect)


def test_im2col_col2im():
    rng = onp.random.RandomState(6)
    x = rng.randn(2, 3, 5, 5).astype("float32")
    col = nd.im2col(nd.array(x), kernel=(3, 3), stride=(1, 1)).asnumpy()
    assert col.shape == (2, 27, 9)
    # numpy reference, channel-major rows (caffe/mxnet layout)
    ref = onp.zeros((2, 27, 3, 3), "float32")
    for c in range(3):
        for i in range(3):
            for j in range(3):
                ref[:, c * 9 + i * 3 + j] = x[:, c, i:i + 3, j:j + 3]
    assert_almost_equal(col, ref.reshape(2, 27, 9), rtol=1e-6)
    # col2im is the linear transpose: scattering ones counts the window
    # overlap multiplicity per pixel
    counts = nd.col2im(nd.array(onp.ones((2, 27, 9), "float32")),
                       output_size=(5, 5), kernel=(3, 3),
                       stride=(1, 1)).asnumpy()
    expect1d = onp.array([1, 2, 3, 2, 1], "float32")
    assert_almost_equal(counts[0, 0], onp.outer(expect1d, expect1d) * 1.0)
    # Schema Shape coercion: the reference frontends emit "(3, 3)" strings
    col_str = nd.im2col(nd.array(x), kernel="(3, 3)").asnumpy()
    assert_almost_equal(col_str, col)
    back = nd.col2im(nd.array(col), output_size="(5, 5)",
                     kernel=(3, 3)).asnumpy()
    assert back.shape == (2, 3, 5, 5)
    with pytest.raises(Exception):  # unknown kwargs now rejected by schema
        nd.im2col(nd.array(x), kernel=(3, 3), bogus=1)


def test_index_copy_contrib():
    old = mx.nd.zeros((5, 3))
    new = mx.nd.array(onp.arange(6, dtype="float32").reshape(2, 3))
    idx = mx.nd.array(onp.array([1, 3], "float32"))
    out = mx.contrib.nd.index_copy(old, idx, new)
    ref = onp.zeros((5, 3), "float32")
    ref[[1, 3]] = new.asnumpy()
    onp.testing.assert_allclose(out.asnumpy(), ref)


def test_index_array_contrib():
    x = mx.nd.zeros((2, 3))
    out = mx.contrib.nd.index_array(x)
    assert out.shape == (2, 3, 2)
    onp.testing.assert_array_equal(out.asnumpy()[1, 2], [1, 2])
    sel = mx.contrib.nd.index_array(x, axes=(1,))
    onp.testing.assert_array_equal(sel.asnumpy()[..., 0],
                                   onp.tile([0, 1, 2], (2, 1)))


def test_index_copy_rejects_out_of_range():
    with pytest.raises(Exception, match="out of range"):
        mx.contrib.nd.index_copy(mx.nd.zeros((3, 2)),
                                 mx.nd.array(onp.array([3.0], "float32")),
                                 mx.nd.ones((1, 2)))


def test_index_array_validates_axes():
    x = mx.nd.zeros((2, 3))
    with pytest.raises(Exception, match="out of range"):
        mx.contrib.nd.index_array(x, axes=(-3,))
    with pytest.raises(Exception, match="non-empty"):
        mx.contrib.nd.index_array(x, axes=())
    neg = mx.contrib.nd.index_array(x, axes=(-1,))
    onp.testing.assert_array_equal(neg.asnumpy()[..., 0],
                                   onp.tile([0, 1, 2], (2, 1)))


def test_index_copy_duplicate_indices_last_wins():
    # reference sequential-copy semantics: the LAST update for a row wins,
    # deterministically on every backend
    out = mx.contrib.nd.index_copy(
        mx.nd.zeros((5,)), mx.nd.array(onp.array([2.0, 2.0], "float32")),
        mx.nd.array(onp.array([7.0, 9.0], "float32")))
    onp.testing.assert_allclose(out.asnumpy(), [0, 0, 9, 0, 0])


def test_index_copy_rejects_shape_mismatch():
    with pytest.raises(Exception, match="must be"):
        mx.contrib.nd.index_copy(mx.nd.zeros((5, 3)),
                                 mx.nd.array(onp.array([1.0, 3.0], "float32")),
                                 mx.nd.ones((1, 3)))
