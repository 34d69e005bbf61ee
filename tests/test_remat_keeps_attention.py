"""``remat=True`` keeps what the flash kernel returned.

The forward rule of ``ops/pallas/flash_attention.py`` names its output and
log-sum-exp (``REMAT_KEEP``), and ``ops.attention.checkpoint_layer`` is a
``jax.checkpoint`` whose policy saves those names: a recomputed layer then
runs the forward kernel once, not twice. Either half alone buys nothing,
so every case here counts the kernel under both. CPU, kernels in interpret
mode; the models reach the kernel through ``MXTPU_ATTN_IMPL=flash``."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import models, parallel
from incubator_mxnet_tpu.ops import attention
from incubator_mxnet_tpu.ops.pallas import flash_attention as fa
from incubator_mxnet_tpu.parallel.mesh import active_mesh
from incubator_mxnet_tpu.analysis.hlo import walk_eqns

from test_afmoe import CFG as AFMOE_CFG, _batch as afmoe_batch, _net as afmoe_net

#: (batch, query heads, K/V heads, length, head size, flash_attention's keywords)
CASES = {
    "key_mask_bert_heads": (2, 2, 2, 16, 64, dict(causal=False)),
    "causal": (1, 2, 2, 32, 16, dict(causal=True)),
    "causal_window_grouped_kv": (1, 4, 2, 32, 16, dict(causal=True, window=8)),
}


def _forward_kernels(closed) -> int:
    """``pallas_call``s named ``flash_fwd*`` anywhere in a jaxpr."""
    return sum(1 for e in walk_eqns(closed.jaxpr) if e.primitive.name == "pallas_call"
               and e.params["name"].startswith("flash_fwd"))


def _layer(case):
    """A layer round one attention call, as the models have it: a projection
    before the kernel and a gate after it, so that something cheap is there
    to rebuild. Returns ``(loss(x, w), x, w)``."""
    B, H, Hkv, L, D, kwargs = CASES[case]
    rng = onp.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((B, L, H * D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((H * D, (H + 2 * Hkv) * D)) * 0.1, jnp.float32)
    mask = None
    if not kwargs["causal"]:
        mask = jnp.asarray(onp.arange(L)[None, :] < onp.array([L, L - 5])[:, None], jnp.float32)

    def layer(x, w):
        q, k, v = jnp.split(x @ w, [H * D, (H + Hkv) * D], axis=-1)
        q, k, v = (t.reshape(B, L, -1, D).transpose(0, 2, 1, 3) for t in (q, k, v))
        o = fa.flash_attention(q, k, v, mask=mask, **kwargs)
        o = o.transpose(0, 2, 1, 3).reshape(B, L, H * D)
        return jnp.sum(jnp.tanh(o) * jax.nn.sigmoid(x))

    return layer, x, w


@pytest.mark.parametrize("case", list(CASES))
def test_a_kept_layer_runs_the_forward_kernel_once(case):
    layer, x, w = _layer(case)
    grad = lambda f: jax.make_jaxpr(jax.grad(f, argnums=(0, 1)))(x, w)  # noqa: E731
    assert _forward_kernels(grad(layer)) == 1
    assert _forward_kernels(grad(jax.checkpoint(layer))) == 2          # names without the policy
    assert _forward_kernels(grad(attention.checkpoint_layer(layer))) == 1
    # the policy without the names: nothing to keep, the kernel runs twice
    # (jax keeps the forward rule's trace, so it is cleared on both sides)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fa, "checkpoint_name", lambda x, name: x)
        jax.clear_caches()
        try:
            assert _forward_kernels(grad(attention.checkpoint_layer(layer))) == 2
        finally:
            jax.clear_caches()


@pytest.mark.parametrize("case", list(CASES))
def test_kept_rebuilt_and_unchecked_gradients_are_the_same_bits(case):
    layer, x, w = _layer(case)
    plain, rebuilt, kept = (
        jax.jit(jax.grad(f, argnums=(0, 1)))(x, w)
        for f in (layer, jax.checkpoint(layer), attention.checkpoint_layer(layer)))
    for a, b, c in zip(plain, rebuilt, kept):
        assert onp.isfinite(onp.asarray(a)).all() and float(jnp.abs(a).max()) > 0
        onp.testing.assert_array_equal(onp.asarray(a), onp.asarray(c))
        onp.testing.assert_array_equal(onp.asarray(b), onp.asarray(c))


def _afmoe_step():
    net = afmoe_net(remat=True)
    trainer = parallel.ShardedTrainer(
        net, models.afmoe_lm_loss, "sgd", dict(learning_rate=1.0),
        mesh=parallel.make_mesh(devices=jax.devices()[:1]), n_labels=1)
    return trainer, afmoe_batch(), len(AFMOE_CFG["layer_types"])


def _bert_step():
    mx.random.seed(7)
    net = models.get_bert("bert_2_128_2", vocab_size=200, max_length=16, dropout=0.0,
                          remat=True)
    net.initialize()
    rng = onp.random.RandomState(3)
    B, L, P = 4, 16, 2
    batch = (rng.randint(0, 200, (B, L)).astype("int32"),
             rng.randint(0, 2, (B, L)).astype("int32"),
             onp.full((B,), L, "float32"),
             rng.randint(0, L, (B, P)).astype("int32"),
             rng.randint(0, 200, (B, P)).astype("float32"),
             onp.ones((B, P), "float32"),
             rng.randint(0, 2, (B,)).astype("float32"))
    trainer = parallel.ShardedTrainer(
        net, models.bert_pretrain_loss, "sgd", {"learning_rate": 1e-2},
        mesh=parallel.make_mesh(devices=jax.devices()[:1]),
        rules=models.bert_sharding_rules(), n_labels=3)
    return trainer, batch, 2


def _step_jaxpr(trainer, batch):
    trainer.prepare(*batch)
    with active_mesh(trainer.mesh):
        return jax.make_jaxpr(trainer._step_fn)(*trainer.step_trace_args(*batch))


@pytest.mark.parametrize("build", [_afmoe_step, _bert_step], ids=["afmoe", "bert"])
def test_a_remat_training_step_holds_one_forward_kernel_a_layer(build, monkeypatch):
    monkeypatch.setenv("MXTPU_ATTN_IMPL", "flash")
    trainer, batch, layers = build()
    kept = _step_jaxpr(trainer, batch)
    assert _forward_kernels(kept) == layers
    # the layers share one policy object: jax's caches of what the layers
    # have in common (a jitted half, a kernel's lowering) are keyed by it
    policies = [e.params["policy"] for e in walk_eqns(kept.jaxpr)
                if e.primitive.name.startswith("remat")]
    assert len(policies) >= layers and len({id(p) for p in policies}) == 1
    # and a bare jax.checkpoint, the step before this mechanism, holds two
    monkeypatch.setattr(attention, "checkpoint_layer", jax.checkpoint)
    monkeypatch.setattr(models.afmoe, "checkpoint_layer", jax.checkpoint)
    trainer, batch, layers = build()
    assert _forward_kernels(_step_jaxpr(trainer, batch)) == 2 * layers


def _program(closed) -> list:
    """Every equation of a jaxpr, nested ones too, in order: primitive,
    operand types, result types. (The printed text will not do: the printer
    hoists a sub-jaxpr that two equations share by identity, which follows
    jax's trace caches and not the program.)"""
    return [(e.primitive.name, tuple(str(v.aval) for v in e.invars),
             tuple(str(v.aval) for v in e.outvars)) for e in walk_eqns(closed.jaxpr)]


@pytest.mark.parametrize("build", [_afmoe_step, _bert_step], ids=["afmoe", "bert"])
def test_on_the_xla_path_the_remat_step_is_the_bare_checkpoints(build, monkeypatch):
    monkeypatch.setenv("MXTPU_ATTN_IMPL", "xla")
    trainer, batch, _layers = build()
    kept = _step_jaxpr(trainer, batch)
    assert _forward_kernels(kept) == 0
    assert not [e for e in walk_eqns(kept.jaxpr) if e.primitive.name == "name"]
    monkeypatch.setattr(attention, "checkpoint_layer", jax.checkpoint)
    monkeypatch.setattr(models.afmoe, "checkpoint_layer", jax.checkpoint)
    trainer, batch, _layers = build()
    bare = _step_jaxpr(trainer, batch)
    assert any(name.startswith("remat") for name, _, _ in _program(bare))
    assert _program(kept) == _program(bare)
