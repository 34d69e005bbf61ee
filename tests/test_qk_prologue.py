"""The fused q/k prologue (``ops.nn.qk_norm_rope``: per-head RMSNorm, rotary
and the head-major write) and its kernel pair ``ops/pallas/qk_prologue.py``
in interpret mode against the three lines it stands for, ``RMSNorm``,
``rotary`` and ``transpose`` as the attention blocks wrote them; what a call
the kernels do not take traces; and what the blocks that call it keep."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.analysis.hlo.trace import walk_eqns
from incubator_mxnet_tpu.models.afmoe import AfmoeAttention, rotary
from incubator_mxnet_tpu.models.lfm2_moe import Lfm2Attention
from incubator_mxnet_tpu.ndarray import NDArray
from incubator_mxnet_tpu.ops import nn as ops_nn
from incubator_mxnet_tpu.ops.pallas import qk_prologue
from incubator_mxnet_tpu.telemetry import metrics

THETA, EPS = 10000.0, 1e-5
B, L = 2, 64


def _operands(heads, D, dtype=jnp.bfloat16, rows=L):
    keys = jax.random.split(jax.random.PRNGKey(heads + D), 3)
    x = jax.random.normal(keys[0], (B, rows, heads * D), jnp.float32).astype(dtype)
    gamma = 1.0 + 0.1 * jax.random.normal(keys[1], (D,), jnp.float32)
    dy = jax.random.normal(keys[2], (B, heads, rows, D), jnp.float32).astype(dtype)
    # the two rows at different offsets, so a table is a row's own
    positions = jnp.arange(rows, dtype=jnp.int32)[None] + jnp.asarray([[0], [5]], jnp.int32)
    return x, gamma, dy, positions


def _todays_three_lines(x, gamma, positions, heads):
    """What ``AfmoeAttention`` and ``Lfm2Attention`` did before the op: the
    ``RMSNorm`` block's op on the projection's result reshaped by heads,
    ``rotary`` (in a layer that has it), ``transpose``."""
    D = x.shape[-1] // heads

    def f(x, gamma):
        t = mx.nd.RMSNorm(NDArray(x).reshape((B, x.shape[1], heads, D)), NDArray(gamma),
                          eps=EPS)._data
        if positions is not None:
            t = rotary(t, positions, THETA)
        return t.transpose(0, 2, 1, 3)
    return f


def _f32(a):
    return onp.asarray(a, "float32")


@pytest.fixture
def fused(monkeypatch):
    """Steer the op's one decision, in the test and not by an option: take
    the kernels wherever their shapes allow, as on the chip. They still run
    in interpret mode here (``_interpret_for`` is left alone)."""
    monkeypatch.setattr(ops_nn, "_qk_prologue_fused",
                        lambda x, heads: qk_prologue.supported(x, heads))


@pytest.mark.parametrize("D", [128, 256])
@pytest.mark.parametrize("heads", [32, 4, 1])
@pytest.mark.parametrize("rope", [True, False], ids=["rotary", "no_rotary"])
def test_kernels_against_todays_composition(fused, rope, heads, D):
    """Values to one bf16 rounding; ``dx`` and ``d gamma`` against
    ``jax.grad`` of the plain form to ``short_conv_gate``'s tolerance
    (``tests/test_lfm2_moe.py``); the kernels called directly (two tiles a
    row) and through the op's own backward rule (one)."""
    x, gamma, dy, positions = _operands(heads, D)
    positions = positions if rope else None
    today = _todays_three_lines(x, gamma, positions, heads)
    want = _f32(today(x, gamma))
    table = ops_nn.rotary_table(positions, THETA, D) if rope else None
    got = qk_prologue.forward(x, gamma, table, EPS, heads, 32)
    assert got.dtype == jnp.bfloat16 and got.shape == (B, heads, L, D)
    # one rounding of the result: a value is the plain form's or its bf16 neighbour
    onp.testing.assert_allclose(_f32(got), want, rtol=2 ** -7, atol=1e-6)
    assert (_f32(got) != want).mean() < 1e-2
    onp.testing.assert_array_equal(
        _f32(ops_nn.qk_norm_rope(x, gamma, positions, THETA, EPS, heads)), _f32(got))

    def total(f):
        return lambda x, gamma: (f(x, gamma).astype(jnp.float32) * dy.astype(jnp.float32)).sum()
    want_g = jax.grad(total(today), (0, 1))(x, gamma)
    tol = 2e-2
    for got_g in (qk_prologue.backward(x, gamma, table, dy, EPS, heads, 32),
                  jax.grad(total(lambda x, gamma: ops_nn.qk_norm_rope(
                      x, gamma, positions, THETA, EPS, heads)), (0, 1))(x, gamma)):
        for a, b in zip(got_g, want_g):
            assert a.dtype == b.dtype and a.shape == b.shape
            onp.testing.assert_allclose(_f32(a), _f32(b), rtol=5 * tol,
                                        atol=tol * max(1.0, float(onp.abs(_f32(b)).max())))
    assert metrics.gauge("mxtpu_qk_prologue_fused",
                         kernel=f"qk_prologue_h{heads}_d{D}").value == 1


@pytest.mark.parametrize("heads,D,dtype,rows", [(4, 64, jnp.bfloat16, L), (4, 128, jnp.float32, L),
                                                (4, 128, jnp.bfloat16, 600)],
                         ids=["D64", "float32", "L_not_whole_tiles"])
@pytest.mark.parametrize("rope", [True, False], ids=["rotary", "no_rotary"])
def test_a_call_the_kernels_do_not_take_traces_todays_lines(fused, rope, heads, D, dtype, rows):
    """``D`` = 64 (half a lane row: LFM2), fp32 and a sequence that is no
    whole number of row tiles: the traced program is the three lines', equation
    for equation, with no ``pallas_call`` and no rule of the op's own."""
    x, gamma, _, positions = _operands(heads, D, dtype, rows)
    positions = positions if rope else None
    assert not qk_prologue.supported(x, heads)
    got = jax.make_jaxpr(lambda x, gamma: ops_nn.qk_norm_rope(
        x, gamma, positions, THETA, EPS, heads))(x, gamma)
    want = jax.make_jaxpr(_todays_three_lines(x, gamma, positions, heads))(x, gamma)
    assert str(got) == str(want)
    names = {e.primitive.name for e in walk_eqns(got.jaxpr)}
    assert "pallas_call" not in names and not any("custom_vjp" in n for n in names)
    assert metrics.gauge("mxtpu_qk_prologue_fused",
                         kernel=f"qk_prologue_h{heads}_d{D}").value == 0


def test_the_op_takes_the_plain_form_off_the_chip_and_the_kernels_as_on_it(monkeypatch):
    x, gamma, _, positions = _operands(4, 128)
    assert qk_prologue.supported(x, 4)
    assert not qk_prologue.supported(x, 3)                       # heads do not divide the width
    assert not qk_prologue.supported(x.astype(jnp.float16), 4)
    assert not qk_prologue.supported(jnp.zeros((1, 64, 96 * 128), jnp.bfloat16), 96)   # too wide for a step's blocks
    trace = lambda: str(jax.make_jaxpr(lambda x, gamma: ops_nn.qk_norm_rope(  # noqa: E731
        x, gamma, positions, THETA, EPS, 4))(x, gamma))
    assert "pallas_call" not in trace()                          # a CPU: interpret mode, so the plain form
    monkeypatch.setattr(qk_prologue, "_interpret_for", lambda x: False)
    assert trace().count("pallas_call") == 1 and "custom_vjp" in trace()


@pytest.mark.parametrize("block,prefix", [
    (lambda: AfmoeAttention(64, 4, 2, 16, window=8, prefix="attn_"), "attn_"),
    (lambda: Lfm2Attention(64, 4, 2, prefix="attn_"), "attn_")], ids=["afmoe", "lfm2"])
def test_attention_blocks_keep_their_parameters(block, prefix):
    """The norms' blocks stay where they were: a seed gives the weights it
    gave, under the names a checkpoint has."""
    net = block()
    names = list(net.collect_params().keys())
    proj = ["q_weight", "k_weight", "v_weight"] + (
        ["gate_weight"] if isinstance(net, AfmoeAttention) else [])
    assert names == [prefix + n for n in proj + ["o_weight", "q_norm_gamma", "k_norm_gamma"]]
    assert [net.collect_params()[prefix + n].shape for n in ("q_norm_gamma", "k_norm_gamma")] \
        == [(16,), (16,)]

