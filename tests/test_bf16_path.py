"""A bf16 net stays bf16 through the whole training step (PR 26).

``nn.LayerNorm`` keeps gamma/beta float32 in a bf16 net. Its op used to
multiply the bf16 normalised value by the float32 gamma, so every layer
norm returned float32 and every matmul, attention call and layout copy
after it ran on float32 operands. This is the witness that the mechanism
engages: a property of the traced program, not a rate. On the chip the same
fact reads in the benchmark's ``breakdown.device_ops`` as kernel names with
``bf16_384_512_64``.
"""
import collections

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import models, nd, parallel
from incubator_mxnet_tpu.analysis.hlo.trace import walk_eqns
from incubator_mxnet_tpu.parallel.mesh import active_mesh

B, L, P, V = 2, 128, 19, 1000      # chipbench's rehearse size: 2 layers, 128 wide
_ATTN_SCOPE = "attention_op"


def _batch(seed=0):
    rng = onp.random.RandomState(seed)
    return (rng.randint(0, V, (B, L)).astype("int32"),
            rng.randint(0, 2, (B, L)).astype("int32"),
            onp.full((B,), L, "float32"),
            onp.sort(rng.rand(B, L).argsort(1)[:, :P], 1).astype("int32"),
            rng.randint(0, V, (B, P)).astype("float32"),
            onp.ones((B, P), "float32"),
            rng.randint(0, 2, (B,)).astype("float32"))


def _net(dtype, dropout):
    mx.random.seed(11)
    net = models.get_bert("bert_2_128_2", vocab_size=V, max_length=L,
                          dropout=dropout, dtype=dtype)
    net.initialize()
    return net


class _Step:
    """One net's ``ShardedTrainer`` step, traced (nothing compiles): its
    jaxpr and the dtypes ``projected_attention`` was handed."""

    def __init__(self, dtype, attn_dtypes):
        net = _net(dtype, dropout=0.1)
        tr = parallel.ShardedTrainer(
            net, models.bert_pretrain_loss, "adamw",
            {"learning_rate": 1e-4, "multi_precision": True},
            mesh=parallel.make_mesh(devices=jax.devices()[:1]),
            rules=models.bert_sharding_rules(), n_labels=3)
        batch = _batch()
        tr.prepare(*batch)
        attn_dtypes.clear()                # the eager warm-up's calls
        with active_mesh(tr.mesh):
            self.closed = jax.make_jaxpr(tr._step_fn)(*tr.step_trace_args(*batch))
        self.eqns = list(walk_eqns(self.closed.jaxpr))
        self.attn_dtypes = list(attn_dtypes)


@pytest.fixture(scope="module")
def steps():
    seen, real = [], nd.projected_attention

    def spy(qkv, *args, **kwargs):
        # the fused projection: q, k and v are its thirds
        seen.append(str(qkv.dtype))
        # named so the walk can tell the op's own dots (on a CPU the XLA
        # path, on the chip the flash kernels) from the model's
        with jax.named_scope(_ATTN_SCOPE):
            return real(qkv, *args, **kwargs)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(nd, "projected_attention", spy)
        yield {d: _Step(d, seen) for d in ("bfloat16", "float32")}


def test_bf16_net_returns_bf16_outputs():
    net = _net("bfloat16", dropout=0.0)
    out = net(*[nd.array(a, dtype=str(a.dtype)) for a in _batch()[:4]])
    assert [str(o.dtype) for o in out] == ["bfloat16"] * 4
    # the norm's own parameters stay float32 (BatchNorm.cast's discipline)
    norm = {n: str(p.data().dtype) for n, p in net.collect_params().items()
            if n.endswith(("gamma", "beta"))}
    assert norm and set(norm.values()) == {"float32"}


def test_step_matmuls_take_no_float32_activation(steps):
    bad = []
    for e in steps["bfloat16"].eqns:
        if e.primitive.name != "dot_general" or _ATTN_SCOPE in str(e.source_info.name_stack):
            continue
        bad += [str(v.aval) for v in e.invars
                if v.aval.dtype == jnp.float32 and v.aval.ndim >= 3]
    assert not bad, f"dot_general on float32 operands of rank >= 3: {bad}"
    dots = [e for e in steps["bfloat16"].eqns if e.primitive.name == "dot_general"]
    assert len(dots) > 20          # the walk saw the step, not an empty program


def test_attention_is_handed_bf16(steps):
    seen = steps["bfloat16"].attn_dtypes
    assert len(seen) == 2 and set(seen) == {"bfloat16"}
    assert set(steps["float32"].attn_dtypes) == {"float32"}


def test_step_loss_is_float32(steps):
    for step in steps.values():
        assert step.closed.out_avals[0].dtype == jnp.float32
        assert step.closed.out_avals[0].shape == ()


def test_float32_step_is_untouched(steps):
    """The casts round the residual sum are the identity on a float32 net:
    no bf16 value anywhere, and no conversion from float32 to float32."""
    dtypes = collections.Counter()
    for e in steps["float32"].eqns:
        for v in e.outvars:
            if hasattr(v.aval, "dtype"):
                dtypes[str(v.aval.dtype)] += 1
        if e.primitive.name == "convert_element_type":
            src, dst = e.invars[0].aval, e.outvars[0].aval
            assert not (src.dtype == dst.dtype == jnp.float32
                        and src.weak_type == dst.weak_type), f"identity conversion of {src}"
    assert "bfloat16" not in dtypes and "float16" not in dtypes


def test_float32_cell_is_bit_identical_to_the_former_formula():
    cell = models.transformer.TransformerEncoderCell(128, 512, 2, dropout=0.0)
    cell.initialize()
    x = nd.array(onp.random.RandomState(3).normal(size=(2, 16, 128)).astype("float32"))
    got = cell(x)
    h = cell.ln1(x + cell.attention(x, None, None))
    want = cell.ln2(h + cell.ffn(h))
    assert got.dtype == onp.float32
    onp.testing.assert_array_equal(got.asnumpy(), want.asnumpy())


def test_bf16_loss_and_gradients_agree_with_float32():
    """Same (bf16-valued) weights in a bf16 and a float32 net, dropout off:
    the loss within 2e-3 relative, every parameter's gradient within 6% of
    that gradient's own norm (a few bf16 roundings, 2^-8 each, through two
    layers and back), their directions within cos >= 0.9995. Read over three
    batches when the limits were set: 2.7e-4, 3.6%, 0.99994."""
    batch = _batch(5)
    grads, losses = {}, {}
    nets = {d: _net(d, dropout=0.0) for d in ("bfloat16", "float32")}
    args = {d: [nd.array(a, dtype=str(a.dtype)) for a in batch] for d in nets}
    for d, net in nets.items():
        net(*args[d][:4])                                   # finish deferred init
    src = nets["bfloat16"].collect_params()
    for (n, p), q in zip(nets["float32"].collect_params().items(), src.values()):
        p.set_data(q.data().astype("float32"))
    for d, net in nets.items():
        with mx.autograd.record():
            loss = models.bert_pretrain_loss(net(*args[d][:4]), *args[d][4:])
        loss.backward()
        assert loss.dtype == onp.float32
        losses[d] = float(loss.asnumpy())
        grads[d] = [onp.asarray(p.grad().astype("float32").asnumpy(), "float64")
                    for p in net.collect_params().values() if p.grad_req != "null"]
    assert abs(losses["bfloat16"] - losses["float32"]) <= 2e-3 * abs(losses["float32"])
    a = onp.concatenate([g.ravel() for g in grads["bfloat16"]])
    b = onp.concatenate([g.ravel() for g in grads["float32"]])
    assert a @ b / (onp.linalg.norm(a) * onp.linalg.norm(b)) >= 0.9995
    for ga, gb in zip(grads["bfloat16"], grads["float32"]):
        if onp.linalg.norm(gb) > 1e-6 * onp.linalg.norm(b):
            assert onp.linalg.norm(ga - gb) <= 6e-2 * onp.linalg.norm(gb)


# ---------------------------------------------------------------------------
# the decoder (PR 35): q and k between their projections and the attention
# call. Heads of 128 over 64 positions, so the fused q/k prologue's kernels
# take the calls when the test says "as on the chip"
# ---------------------------------------------------------------------------

_DEC = dict(hidden_size=64, num_attention_heads=2, num_key_value_heads=1, head_dim=128,
            intermediate_size=128, moe_intermediate_size=32, num_experts=4,
            num_experts_per_tok=2, num_shared_experts=1, num_dense_layers=1,
            layer_types=["sliding_attention", "full_attention"], sliding_window=16,
            rope_theta=10000, rms_norm_eps=1e-5, route_norm=True, route_scale=2.0,
            mup_enabled=True, vocab_size=96, moe_tile_rows=8)
_DEC_B, _DEC_L = 2, 64


def _decoder_step_eqns(monkeypatch, fused: bool):
    """The afmoe decoder's bf16 ``ShardedTrainer`` step, traced with every
    layer recomputed as the cell runs it; ``fused``: the q/k prologue's one
    decision steered as on the chip (the kernels where their shapes allow)."""
    from incubator_mxnet_tpu.ops import nn as ops_nn
    from incubator_mxnet_tpu.ops.pallas import qk_prologue
    if fused:
        monkeypatch.setattr(ops_nn, "_qk_prologue_fused",
                            lambda x, heads: qk_prologue.supported(x, heads))
    mx.random.seed(5)
    net = models.get_afmoe(_DEC, dtype="bfloat16", remat=True)
    net.initialize(mx.init.Normal(0.05))
    tr = parallel.ShardedTrainer(
        net, models.afmoe_lm_loss, "adamw", {"learning_rate": 1e-4, "multi_precision": True},
        mesh=parallel.make_mesh(devices=jax.devices()[:1]), n_labels=1)
    rng = onp.random.RandomState(0)
    seq = rng.randint(0, _DEC["vocab_size"], (_DEC_B, _DEC_L + 1)).astype("int32")
    batch = (seq[:, :-1], onp.tile(onp.arange(_DEC_L, dtype="int32"), (_DEC_B, 1)),
             onp.full((_DEC_B,), _DEC_L, "float32"), seq[:, 1:])
    tr.prepare(*batch)
    with active_mesh(tr.mesh):
        closed = jax.make_jaxpr(tr._step_fn)(*tr.step_trace_args(*batch))
    return list(walk_eqns(closed.jaxpr))


def _float32_by_heads(eqns):
    """fp32 values of q's or k's size by heads, ``(B, L, H, D)`` or ``(B, H,
    L, D)``, outside the attention op itself (its scope holds the call, the
    result's layout and the gate)."""
    H, Hkv, D = _DEC["num_attention_heads"], _DEC["num_key_value_heads"], _DEC["head_dim"]
    sizes = {(_DEC_B, a, b, D) for h in (H, Hkv) for a, b in ((_DEC_L, h), (h, _DEC_L))}
    return [str(v.aval) for e in eqns if "afmoe_attention" not in str(e.source_info.name_stack)
            for v in e.outvars
            if getattr(v.aval, "dtype", None) == jnp.float32 and v.aval.shape in sizes]


def test_decoder_q_and_k_reach_attention_without_a_float32_copy(monkeypatch):
    """With the fused prologue no fp32 array of q's or k's size lies between
    the ``q_`` / ``k_`` projections and the attention call, forward,
    recomputed or backward: two kernels a tensor and a layer, bf16 in and
    out. The plain form has them (the walk sees what it looks for)."""
    plain = _decoder_step_eqns(monkeypatch, fused=False)
    assert len(_float32_by_heads(plain)) >= 8
    assert not any(e.primitive.name == "pallas_call" for e in plain)
    fused = _decoder_step_eqns(monkeypatch, fused=True)
    assert _float32_by_heads(fused) == []
    kernels = collections.Counter(e.params["name"] for e in fused
                                  if e.primitive.name == "pallas_call")
    # q and k in two layers: a forward, a recomputed forward and a backward each
    assert kernels == {"qk_prologue_fwd": 8, "qk_prologue_bwd": 4}
    # the projections feed the kernels bf16 and the kernels hand bf16 on
    for e in fused:
        if e.primitive.name == "pallas_call":
            big = [v.aval for v in (*e.invars, *e.outvars) if v.aval.size >= _DEC_B * _DEC_L * 128]
            assert big and all(a.dtype == jnp.bfloat16 or a.shape[-1] == 128 and a.ndim == 3
                               for a in big), big


def test_decoder_step_with_the_fused_prologue_is_the_plain_steps(monkeypatch):
    """One SGD step of the recomputed bf16 decoder both ways (a padded row,
    so the key mask is there): the same loss, and every parameter's update
    within 1% of its norm (a bf16 neighbour here and there in ``dx``; read
    0.4% at most when the limit was set)."""
    from incubator_mxnet_tpu.ops import nn as ops_nn
    from incubator_mxnet_tpu.ops.pallas import qk_prologue
    rng = onp.random.RandomState(0)
    seq = rng.randint(0, _DEC["vocab_size"], (_DEC_B, _DEC_L + 1)).astype("int32")
    batch = (seq[:, :-1], onp.tile(onp.arange(_DEC_L, dtype="int32"), (_DEC_B, 1)),
             onp.array([_DEC_L, _DEC_L * 3 // 4], "float32"), seq[:, 1:])

    def step():
        mx.random.seed(5)
        net = models.get_afmoe(_DEC, dtype="bfloat16", remat=True)
        net.initialize(mx.init.Normal(0.05))
        tr = parallel.ShardedTrainer(net, models.afmoe_lm_loss, "sgd", {"learning_rate": 1.0},
                                     mesh=parallel.make_mesh(devices=jax.devices()[:1]), n_labels=1)
        values = lambda: [onp.asarray(p.data().asnumpy(), "float32")  # noqa: E731
                          for p in net.collect_params().values()]
        before = values()
        loss = float(tr.step(*batch).asnumpy())
        tr.sync_to_block()
        return loss, [b - a for b, a in zip(before, values())]
    plain_loss, plain = step()
    monkeypatch.setattr(ops_nn, "_qk_prologue_fused",
                        lambda x, heads: qk_prologue.supported(x, heads))
    fused_loss, fused = step()
    assert abs(fused_loss - plain_loss) <= 1e-3 * abs(plain_loss)
    moved = [(a, b) for a, b in zip(plain, fused) if onp.linalg.norm(a) > 0]
    assert len(moved) > 30
    for a, b in moved:
        assert onp.linalg.norm(a - b) <= 1e-2 * onp.linalg.norm(a)
