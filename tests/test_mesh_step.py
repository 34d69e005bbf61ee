"""Compiled mesh collectives (ISSUE 9): the pjit-sharded step as the
default execution path, on the 8-device forced-host-CPU mesh.

Covers the tentpole contract: explicit PartitionSpec in/out resources +
donation, ONE compile per mesh (ledger clean), gradient exchange equal
to the per-parameter kvstore loop it replaced (bit-identical first
update), ZeRO-1 cross-replica-sharded optimizer update by default,
bit-identical checkpoint resume across a mesh-shape change, the MX708
pass, and the cost model's collective/comm-bytes accounting."""
import os

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, parallel
from incubator_mxnet_tpu.analysis import hlo
from incubator_mxnet_tpu.parallel.sharding import P, ShardingRules

# explicit prefix + name_scope pin parameter names (meshstep_dense0_*)
# against gluon's process-global dense counter, so the rule table matches
# identically standalone and mid-suite
RULES = ShardingRules([(r".*meshstep_dense0.*weight", P("tp", None))])


def _batch(n=16, d=24, classes=8):
    rng = onp.random.RandomState(5)
    return (rng.randn(n, d).astype("float32"),
            rng.randint(0, classes, (n,)).astype("float32"))


def _trainer(mesh, opt="adamw", rules=RULES, units=32, in_units=24,
             classes=8, **kw):
    mx.random.seed(13)
    net = gluon.nn.HybridSequential(prefix="meshstep_")
    with net.name_scope():
        net.add(gluon.nn.Dense(units, activation="relu", in_units=in_units),
                gluon.nn.Dense(classes, in_units=units))
    net.initialize(mx.init.Xavier(rnd_type="gaussian"))
    return parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), opt,
        {"learning_rate": 1e-2}, mesh=mesh, rules=rules, **kw)


def _fallback_env(monkeypatch):
    monkeypatch.setenv("MXTPU_KVSTORE_FALLBACK", "1")


def test_pjit_step_explicit_shardings_and_default_dispatch():
    """The step carries explicit in/out NamedShardings: rule layout for
    params, zero1 dp-partition for optimizer states (the default on a
    dp>1 mesh), data sharding for the batch — and step() dispatches the
    pjit path with no opt-in."""
    mesh = parallel.make_mesh(dp=4, tp=2)
    tr = _trainer(mesh)
    x, y = _batch()
    tr.step(x, y)
    assert tr.last_path == "pjit"
    assert tr._zero1          # cross-replica sharded update is the default
    ins, outs = tr.step_shardings(tuple(v.ndim for v in tr.place(x, y)))
    # params in == params out == the rule layout
    assert ins[0] == outs[2] == tuple(tr._param_shardings)
    names = [n for n, _ in sorted(tr._block.collect_params().items())]
    w0 = names.index([n for n in names
                      if "meshstep_dense0" in n and "weight" in n][0])
    assert tuple(tr._param_shardings[w0].spec) == ("tp", None)
    # optimizer states: dp-partitioned (ZeRO-1) in and out
    dp_axes = [a for sh in tr._state_shardings[w0]
               for e in tuple(sh.spec) if e
               for a in ((e,) if isinstance(e, str) else e)]
    assert "dp" in dp_axes
    # batch: dp-sharded on axis 0
    assert tuple(ins[5].spec) == ("dp", None)
    # live arrays actually honor the out contract after a step
    assert tuple(tr._param_vals[w0].sharding.spec) == ("tp", None)


def test_pjit_step_compiles_once():
    """4 same-signature steps = exactly ONE new trainer.step entry in the
    process-wide compile ledger (the compiles-once contract; the CI
    multichip smoke additionally asserts zero post-warmup)."""
    from incubator_mxnet_tpu.telemetry import compile_log
    before = len(compile_log.records("trainer.step"))
    mesh = parallel.make_mesh(dp=4, tp=2)
    tr = _trainer(mesh)
    x, y = _batch()
    for _ in range(4):
        tr.step(x, y)
    assert len(compile_log.records("trainer.step")) == before + 1


def test_loss_bit_identical_to_kvstore_loop(monkeypatch):
    """The compiled all-reduce gradient exchange produces the SAME
    numbers as the per-parameter Python push/pull loop it replaced:
    losses of the first two steps are bit-identical (forward parity +
    first exchanged update), the rest tight-allclose (two different
    compiled graphs compound ulp differences)."""
    mesh = parallel.make_mesh(dp=4, tp=2)
    tr = _trainer(mesh)
    x, y = _batch()
    pjit_losses = [float(tr.step(x, y).asnumpy()) for _ in range(5)]
    assert tr.last_path == "pjit"
    _fallback_env(monkeypatch)
    tr_fb = _trainer(mesh)
    fb_losses = [float(tr_fb.step(x, y).asnumpy()) for _ in range(5)]
    assert tr_fb.last_path == "kvstore_fallback"
    assert pjit_losses[:2] == fb_losses[:2]
    onp.testing.assert_allclose(pjit_losses, fb_losses,
                                rtol=1e-5, atol=1e-6)


def test_loss_matches_unsharded_path():
    mesh = parallel.make_mesh(dp=8)
    tr = _trainer(mesh)
    tr1 = _trainer(parallel.make_mesh(devices=jax.devices()[:1]))
    x, y = _batch()
    l_mesh = [float(tr.step(x, y).asnumpy()) for _ in range(4)]
    l_one = [float(tr1.step(x, y).asnumpy()) for _ in range(4)]
    onp.testing.assert_allclose(l_mesh, l_one, rtol=1e-5, atol=1e-6)


def test_checkpoint_resume_across_mesh_shape_change(tmp_path):
    """Save on dp=4,tp=2, restore onto dp=2,tp=2,sp=2: every parameter
    and optimizer-state array is restored BIT-identically (resharded onto
    the new mesh's live placements), the step/LR position rides along,
    and training resumes to matching losses."""
    x, y = _batch()
    src = _trainer(parallel.make_mesh(dp=4, tp=2))
    for _ in range(3):
        src.step(x, y)
    root = str(tmp_path / "ck")
    src.save_checkpoint(root)
    dst = _trainer(parallel.make_mesh(dp=2, tp=2, sp=2))
    dst.step(x, y)                      # init; state fully overwritten
    step = dst.restore_checkpoint(root)
    assert step == src.num_update == dst.num_update == 3
    for a, b in zip(src._param_vals, dst._param_vals):
        assert onp.array_equal(jax.device_get(a), jax.device_get(b))
    for sa, sb in zip(src._opt_states, dst._opt_states):
        for a, b in zip(sa, sb):
            assert onp.array_equal(jax.device_get(a), jax.device_get(b))
        # the zero1 dp-partition really lives on the NEW mesh
    assert dst._opt_states[0][0].sharding.mesh.shape["sp"] == 2
    l_src = float(src.step(x, y).asnumpy())
    l_dst = float(dst.step(x, y).asnumpy())
    assert l_dst == pytest.approx(l_src, rel=1e-5)


def test_mx708_clean_on_default_trainer_fires_on_undonated():
    """The default (donated) pjit step passes hlo verify with zero
    errors; donate=False on a mesh raises MX708 (error severity) for the
    >=64KiB undonated buffers."""
    mesh = parallel.make_mesh(dp=4, tp=2)
    x = onp.random.RandomState(0).randn(8, 512).astype("float32")
    y = onp.random.RandomState(0).randint(0, 4, (8,)).astype("float32")
    tr = _trainer(mesh, units=64, in_units=512, classes=4, rules=None)
    tr.step(x, y)
    rep = hlo.verify(tr, sample_args=(x, y))
    assert rep.ok and "MX708" not in rep.codes()
    tr2 = _trainer(mesh, units=64, in_units=512, classes=4, rules=None,
                   donate=False)
    tr2.step(x, y)
    rep2 = hlo.verify(tr2, sample_args=(x, y))
    mx708 = [d for d in rep2.diagnostics if d.code == "MX708"]
    assert mx708 and all(d.severity == "error" for d in mx708)
    assert "non-donated" in mx708[0].message


def test_mx708_fires_on_host_callback_in_mesh_step():
    """A host callback inside a mesh-configured train graph is the
    per-parameter host round-trip sneaking back in — error."""
    from incubator_mxnet_tpu.analysis.hlo import TracedGraph, run_hlo_passes

    def stepish(w, g):
        jax.debug.callback(lambda v: None, g.sum())
        return w - 0.1 * g

    closed = jax.make_jaxpr(stepish)(jnp.ones((4, 4)), jnp.ones((4, 4)))
    g = TracedGraph(entry="Step", site="step", closed=closed,
                    arg_names=["w", "g"], roles=["param", "input"],
                    kind="train", donated=(False, False),
                    mesh_axes={"dp": 8})
    rep = run_hlo_passes([g], names=["hlo_mesh_step"])
    assert [d.code for d in rep.errors] == ["MX708"]
    assert "host round-trip" in rep.errors[0].message
    # same graph on a single-device mesh: no contract, no finding
    g1 = TracedGraph(entry="Step", site="step", closed=closed,
                     arg_names=["w", "g"], roles=["param", "input"],
                     kind="train", donated=(False, False),
                     mesh_axes={"dp": 1})
    assert run_hlo_passes([g1], names=["hlo_mesh_step"]).ok


def test_cost_model_explicit_collectives():
    """A shard_map psum traced under the active mesh prices as one
    all-reduce moving 2(N-1)/N of the per-shard payload."""
    from incubator_mxnet_tpu.parallel.collectives import shard_map
    from incubator_mxnet_tpu.parallel.mesh import active_mesh
    mesh = parallel.make_mesh(dp=8)
    fn = shard_map(lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                   in_specs=(P("dp"),), out_specs=P("dp"))
    with active_mesh(mesh):
        rep = hlo.cost(fn, sample_args=(onp.zeros((8, 4), "float32"),))
    r = rep.rows[0]
    assert r.collective_ops == {"all_reduce": 1}
    # per-shard payload (1,4) f32 = 16 bytes; ring all-reduce 2*(7/8)*16
    assert r.comm_bytes == pytest.approx(2 * (7 / 8) * 16)


def test_cost_model_implied_gradient_exchange():
    """A train graph on a dp mesh prices the SPMD-partitioner-inserted
    gradient exchange from its in-resource specs: reduce-scatter +
    all-gather per dp-replicated parameter under zero1 (the default),
    all-reduce without it — both moving 2(N-1)/N of the param bytes."""
    x, y = _batch()
    for zero1, verbs in ((True, {"reduce_scatter", "all_gather"}),
                         (False, {"all_reduce"})):
        tr = _trainer(parallel.make_mesh(dp=8), rules=None, zero1=zero1)
        tr.step(x, y)
        rep = hlo.cost(tr, sample_args=(x, y))
        r = rep.head
        assert r.kind == "train"
        assert set(r.collective_ops) == verbs
        assert sum(r.collective_ops.values()) == (8 if zero1 else 4)
        # r.param_bytes = weights + 2 adamw moments = 3x the weight bytes;
        # only the weights' gradients ride the exchange
        assert r.comm_bytes == pytest.approx(2 * (7 / 8) * r.param_bytes / 3)
        assert rep.comm_bytes_per_step() == int(r.comm_bytes)


def test_gluon_trainer_batched_kvstore_exchange(monkeypatch):
    """gluon.Trainer.allreduce_grads issues ONE batched push/pull for the
    whole key set (single compiled collective) by default, and falls back
    to the per-key loop only under MXTPU_KVSTORE_FALLBACK=1."""
    from incubator_mxnet_tpu import kvstore as kv_mod

    class CountingStore(kv_mod.KVStore):
        def __init__(self):
            super().__init__(comm="local")
            self.push_calls = []

        def push(self, key, value, priority=0):
            self.push_calls.append(key)
            return super().push(key, value, priority)

    def run(store):
        net = gluon.nn.Dense(4, in_units=6)
        net.initialize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1}, kvstore=store)
        xb = mx.nd.array(onp.ones((2, 6), "float32"))
        with mx.autograd.record():
            loss = (net(xb) ** 2).mean()
        loss.backward()
        trainer.step(1)
        return net

    s1 = CountingStore()
    run(s1)
    assert len(s1.push_calls) == 1 and isinstance(s1.push_calls[0], list)
    monkeypatch.setenv("MXTPU_KVSTORE_FALLBACK", "1")
    s2 = CountingStore()
    run(s2)
    assert len(s2.push_calls) == 2          # weight + bias, one push each
    assert all(not isinstance(k, list) for k in s2.push_calls)


@pytest.mark.parametrize("mesh_axes", [None, dict(dp=4, tp=2)],
                         ids=["one_device", "dp4_tp2"])
def test_step_is_traced_once_across_steps_and_restore(mesh_axes, tmp_path):
    """The jit entry keys its trace on argument TYPES: the step counter
    comes back mesh-typed, and a bf16 parameter's multi-precision moments
    come back fp32. State made with any other type makes step 2 a second
    signature — a silent second trace and compile, outside the autotune
    overlay and invisible to the compile ledger (which sees batch
    signatures), with donated buffers that cannot alias. So: one trace,
    through steps, a placed batch, and a checkpoint restore."""
    mesh = (parallel.make_mesh(devices=jax.devices()[:1])
            if mesh_axes is None else parallel.make_mesh(**mesh_axes))

    def bf16_trainer():
        mx.random.seed(13)
        net = gluon.nn.HybridSequential(prefix="meshstep_")
        with net.name_scope():
            net.add(gluon.nn.Dense(32, activation="relu", in_units=24),
                    gluon.nn.Dense(8, in_units=32))
        net.initialize(mx.init.Xavier(rnd_type="gaussian"))
        net.cast("bfloat16")
        return parallel.ShardedTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
            {"learning_rate": 1e-2, "multi_precision": True}, mesh=mesh,
            rules=RULES)

    x, y = _batch()
    x = x.astype(jnp.bfloat16)
    tr = bf16_trainer()
    for _ in range(3):
        tr.step(x, y)
    tr.step(*tr.place(x, y))
    assert tr._step_fn._cache_size() == 1
    # masters and both moments are fp32 from the start, not after step 1
    for states in tr._opt_states:
        assert [s.dtype for s in states] == [jnp.float32] * 3
    tr.save_checkpoint(str(tmp_path))
    tr2 = bf16_trainer()
    tr2.step(x, y)
    tr2.restore_checkpoint(str(tmp_path))
    tr2.step(x, y)
    assert tr2._step_fn._cache_size() == 1
    # and the donation the types now allow is whole: every state byte
    g = hlo.trace_entry(tr, (x, y)).graphs[0]
    assert all(d for d, r in zip(g.donated, g.roles)
               if r in ("param", "state"))
