"""mx.fault — fault-tolerant training runtime tests.

Three families (ISSUE 2 acceptance criteria):

- checkpoint: atomic versioned directories, bit-identical resume of a
  ``ShardedTrainer`` (ZeRO-1 + RNG key included), corrupted/truncated
  rejection, retention, and the KILL-AND-RESUME contract — a run killed
  mid-save resumes from the last complete checkpoint.
- guards/watchdog: NaN skip-and-rollback / halt / warn policies driven by
  seeded chaos NaN injection; watchdog deadline flags with recompile
  provenance.
- kvstore: reconnect-with-backoff across a server restart-from-checkpoint,
  idempotent versioned push resends, the MXNET_KVSTORE_TIMEOUT satellite,
  and MXNetError op/key context instead of bare ConnectionError.

Chaos-marked tests (``-m chaos``) are the seeded injection suite the CI
chaos job runs; the whole file stays well under a minute.
"""
import os
import pickle
import time
import warnings

import numpy as onp
import pytest

import jax

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import fault, gluon, parallel
from incubator_mxnet_tpu.fault import inject
from incubator_mxnet_tpu.kvstore.async_ps import AsyncPSServer, _Client

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_leaked_chaos():
    """Chaos must never leak across tests."""
    inject.disable()
    yield
    inject.disable()


def _mlp():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    return net


def _sharded(zero1=False, **kw):
    return parallel.ShardedTrainer(
        _mlp(), gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
        {"learning_rate": 1e-2}, mesh=parallel.make_mesh(dp=4, tp=2),
        zero1=zero1, **kw)


def _batch(seed=0):
    rng = onp.random.RandomState(seed)
    return (rng.randn(8, 12).astype("float32"),
            rng.randint(0, 4, (8,)).astype("float32"))


# ---------------------------------------------------------------------------
# checkpoint core
# ---------------------------------------------------------------------------

def test_checkpoint_save_load_retention(tmp_path):
    root = str(tmp_path / "ck")
    arrs = {"a": onp.arange(6, dtype="float32").reshape(2, 3),
            "b": onp.ones((4,), "int32")}
    for step in (1, 2, 3, 4):
        fault.save_checkpoint(root, arrs, {"step": step}, step=step, keep=2)
    assert fault.list_checkpoints(root) == [3, 4]
    loaded, meta, step = fault.load_latest(root)
    assert step == 4 and meta["step"] == 4
    onp.testing.assert_array_equal(loaded["a"], arrs["a"])
    assert loaded["b"].dtype == onp.dtype("int32")


def test_checkpoint_scalar_arrays_roundtrip(tmp_path):
    """0-d arrays ride the dmlc container as shape (1,) — the manifest
    restores the original shape, and verification still holds."""
    root = str(tmp_path / "ck")
    fault.save_checkpoint(root, {"w": onp.ones((2, 2), "float32"),
                                 "scale": onp.float32(3.0)}, step=1)
    arrays, _, _ = fault.load_checkpoint(root, 1)
    assert arrays["scale"].shape == () and float(arrays["scale"]) == 3.0


def test_checkpoint_same_step_resave_crash_recovers(tmp_path):
    """A same-step replace that dies between its two renames leaves the
    displaced old copy at step-N.replaced; readers self-heal it back."""
    root = str(tmp_path / "ck")
    fault.save_checkpoint(root, {"w": onp.full(3, 5.0, "float32")}, step=7)
    os.replace(os.path.join(root, "step-0000000007"),
               os.path.join(root, "step-0000000007.replaced"))
    assert fault.list_checkpoints(root) == [7]
    arrays, _, _ = fault.load_latest(root)
    assert arrays["w"][0] == 5.0
    # a completed re-save clears any leftover aside dir
    fault.save_checkpoint(root, {"w": onp.zeros(3, "float32")}, step=7)
    assert not [d for d in os.listdir(root) if d.endswith(".replaced")]


def test_checkpoint_corrupt_rejected_and_skipped(tmp_path):
    root = str(tmp_path / "ck")
    arrs = {"w": onp.arange(8, dtype="float32")}
    fault.save_checkpoint(root, arrs, step=1)
    fault.save_checkpoint(root, arrs, step=2)
    # truncate the newest arrays file
    apath = os.path.join(root, "step-0000000002", "arrays.params")
    blob = open(apath, "rb").read()
    with open(apath, "wb") as f:
        f.write(blob[:-6])
    with pytest.raises(fault.CheckpointCorruptError):
        fault.load_checkpoint(root, 2)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        _, _, step = fault.load_latest(root)
    assert step == 1
    assert any("corrupt" in str(x.message) for x in w)


def test_checkpoint_bitflip_rejected(tmp_path):
    root = str(tmp_path / "ck")
    fault.save_checkpoint(root, {"w": onp.zeros(16, "float32")}, step=5)
    apath = os.path.join(root, "step-0000000005", "arrays.params")
    blob = bytearray(open(apath, "rb").read())
    # flip one byte INSIDE the float payload (container header is 24 bytes,
    # record header 32): size stays right, only the crc can notice
    blob[60] ^= 0xFF
    with open(apath, "wb") as f:
        f.write(bytes(blob))
    with pytest.raises(fault.CheckpointCorruptError, match="checksum"):
        fault.load_checkpoint(root, 5)
    with pytest.raises(fault.CheckpointError):
        fault.load_latest(root)  # the ONLY step is bad -> no usable ckpt


@pytest.mark.chaos
def test_kill_mid_save_leaves_previous_checkpoint(tmp_path):
    """Kill-and-resume, checkpoint layer: a save that dies before the
    atomic rename leaves only a temp dir; load_latest still returns the
    previous complete step, and a later successful save prunes the temp."""
    root = str(tmp_path / "ck")
    arrs = {"w": onp.full(4, 7.0, "float32")}
    fault.save_checkpoint(root, arrs, step=1)
    with inject.chaos(seed=0, crash_sites=["checkpoint.finalize"]):
        with pytest.raises(inject.ChaosCrash):
            fault.save_checkpoint(root, {"w": onp.zeros(4, "float32")},
                                  step=2)
    assert fault.list_checkpoints(root) == [1]
    loaded, _, step = fault.load_latest(root)
    assert step == 1 and loaded["w"][0] == 7.0
    # arrays-then-die (no manifest) is equally invisible
    with inject.chaos(seed=0, crash_sites=["checkpoint.arrays"]):
        with pytest.raises(inject.ChaosCrash):
            fault.save_checkpoint(root, arrs, step=3)
    assert fault.list_checkpoints(root) == [1]
    fault.save_checkpoint(root, arrs, step=4)   # retention clears temps
    assert not [d for d in os.listdir(root) if d.startswith(".tmp-")]


# ---------------------------------------------------------------------------
# ShardedTrainer round trip (ZeRO-1 + RNG)
# ---------------------------------------------------------------------------

def test_sharded_trainer_kill_and_resume_bit_identical(tmp_path):
    """THE acceptance test: train, checkpoint, keep training (the
    uninterrupted reference), then resume a FRESH trainer from the
    checkpoint — after a save at a later step died mid-write — and get a
    bit-identical next-step loss (ZeRO-1 shards + RNG base key restored)."""
    root = str(tmp_path / "ck")
    x, y = _batch()
    mx.random.seed(11)
    tr = _sharded(zero1=True)
    for _ in range(3):
        tr.step(x, y)
    tr.save_checkpoint(root, keep=3)
    # a LATER save dies mid-write (simulated kill): must not shadow step 3
    with inject.chaos(seed=0, crash_sites=["checkpoint.finalize"]):
        tr.step(x, y)
        with pytest.raises(inject.ChaosCrash):
            tr.save_checkpoint(root)
    ref_losses = [float(tr.step(x, y).asnumpy()) for _ in range(2)]

    mx.random.seed(999)   # resume must NOT depend on ambient RNG state
    tr2 = _sharded(zero1=True)
    tr2.step(x, y)        # init (state fully overwritten by restore)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # positional-name restore note
        assert tr2.restore_checkpoint(root) == 3
    assert tr2.num_update == 3
    # the interrupted save advanced the reference by one extra step
    float(tr2.step(x, y).asnumpy())
    res_losses = [float(tr2.step(x, y).asnumpy()) for _ in range(2)]
    assert res_losses == ref_losses  # bit-identical, not allclose


def test_sharded_trainer_restore_rejects_mismatched_block(tmp_path):
    root = str(tmp_path / "ck")
    x, y = _batch()
    tr = _sharded()
    tr.step(x, y)
    tr.save_checkpoint(root)
    small = gluon.nn.Dense(4)
    small.initialize()
    other = parallel.ShardedTrainer(
        small, gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
        {"learning_rate": 1e-2}, mesh=parallel.make_mesh(dp=4, tp=2))
    other.step(x, y)
    with pytest.raises(mx.MXNetError):
        other.restore_checkpoint(root)


def test_gluon_trainer_checkpoint_roundtrip(tmp_path):
    root = str(tmp_path / "ckg")
    net = _mlp()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 1e-2})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x, y = _batch()
    xn, yn = mx.nd.array(x), mx.nd.array(y)

    def one_step():
        with mx.autograd.record():
            l = loss_fn(net(xn), yn).mean()
        l.backward()
        tr.step(1)
        return float(l.asnumpy())

    one_step()
    one_step()
    tr.save_checkpoint(root)
    ref = one_step()
    assert tr.restore_checkpoint(root) == 2
    assert tr.optimizer.num_update == 2
    assert one_step() == ref   # bit-identical replay of step 3


# ---------------------------------------------------------------------------
# multi-host commit protocol (ISSUE 17): drills simulate the pod in ONE
# process by passing process_index/process_count explicitly — the
# protocol is pure filesystem coordination, so sequential calls against
# the same staging dir ARE the concurrent multi-host save
# ---------------------------------------------------------------------------

def _two_host_save(root, arrays, step, **kw):
    """Both halves of the protocol, non-primary first (the primary's
    marker wait needs every peer's marker on disk)."""
    fault.save_checkpoint(root, arrays, step=step, process_index=1,
                          process_count=2, **kw)
    return fault.save_checkpoint(root, arrays, step=step, process_index=0,
                                 process_count=2, **kw)


def test_multihost_save_manifest_ordering(tmp_path):
    """THE commit-ordering contract: every host writes its shard + commit
    marker, the primary writes the manifest LAST — a primary killed
    between the markers and the manifest leaves a manifest-less staging
    dir that load_latest can never see."""
    root = str(tmp_path / "ck")
    arrs = {"w": onp.full(4, 7.0, "float32"), "b": onp.arange(3, dtype="f")}
    _two_host_save(root, arrs, step=1)
    assert fault.list_checkpoints(root) == [1]
    loaded, _, step = fault.load_latest(root)
    assert step == 1 and loaded["w"][0] == 7.0

    # step 2: peer's shard lands, then the PRIMARY dies after gathering
    # the markers but before the manifest write
    fault.save_checkpoint(root, arrs, step=2, process_index=1,
                          process_count=2)
    with inject.chaos(seed=0, crash_sites=["checkpoint.manifest"]):
        with pytest.raises(inject.ChaosCrash):
            fault.save_checkpoint(root, arrs, step=2, process_index=0,
                                  process_count=2)
    # the torn save is invisible: no manifest, no step-2 checkpoint
    assert fault.list_checkpoints(root) == [1]
    _, _, step = fault.load_latest(root)
    assert step == 1
    # a re-driven primary completes the SAME staging dir (shards +
    # markers are already there) and the step becomes visible
    fault.save_checkpoint(root, arrs, step=2, process_index=0,
                          process_count=2)
    assert fault.list_checkpoints(root) == [1, 2]
    loaded, _, step = fault.load_latest(root)
    assert step == 2 and set(loaded) == {"w", "b"}


def test_multihost_save_manifest_names_shards(tmp_path):
    import json as _json
    root = str(tmp_path / "ck")
    arrs = {"w": onp.ones(2, "float32")}
    out = _two_host_save(root, arrs, step=3)
    with open(os.path.join(out, "manifest.json")) as f:
        man = _json.load(f)
    assert sorted(man["shards"]) == ["0", "1"]
    # replicated arrays dedupe to the lowest-index writer's shard file
    assert all(e["file"] == "arrays-p0.params"
               for e in man["arrays"].values())


def test_multihost_save_marker_timeout_names_missing(tmp_path):
    """A primary whose peer never commits must fail LOUDLY, naming the
    missing process index — never hang past the bounded wait."""
    root = str(tmp_path / "ck")
    with pytest.raises(fault.CheckpointError, match=r"\[1\]"):
        fault.save_checkpoint(root, {"w": onp.zeros(2, "f")}, step=1,
                              process_index=0, process_count=2,
                              commit_timeout_s=0.2)
    assert fault.list_checkpoints(root) == []


def test_multihost_save_divergent_shards_refused(tmp_path):
    """Cross-host CRC disagreement on a replicated array = silent SPMD
    divergence. The primary must refuse the manifest."""
    root = str(tmp_path / "ck")
    fault.save_checkpoint(root, {"w": onp.zeros(4, "float32")}, step=1,
                          process_index=1, process_count=2)
    with pytest.raises(fault.CheckpointError, match="diverge"):
        fault.save_checkpoint(root, {"w": onp.ones(4, "float32")}, step=1,
                              process_index=0, process_count=2)
    assert fault.list_checkpoints(root) == []


def test_multihost_reshard_resume_allclose(tmp_path):
    """The membership-change resume contract (2 hosts → 1): a trainer
    checkpoint written through the multi-host protocol restores on a
    single-host membership with losses matching the uninterrupted
    reference. Same process/mesh ⇒ the match is bit-identical; the
    CONTRACT across a real reshard is allclose, so that is what this
    asserts (bit-identity is checked as the stricter bonus here)."""
    root = str(tmp_path / "ck")
    x, y = _batch()
    mx.random.seed(11)
    tr = _sharded(zero1=True)
    for _ in range(3):
        tr.step(x, y)
    arrays = {}
    items = sorted(tr._block.collect_params().items())
    for i in range(len(items)):
        arrays[f"param:{i:04d}"] = jax.device_get(tr._param_vals[i])
        for j, s in enumerate(tr._opt_states[i]):
            arrays[f"opt:{i:04d}:{j}"] = jax.device_get(s)
    if tr._base_key is not None:
        arrays["rng:base_key"] = jax.device_get(
            jax.random.key_data(tr._base_key))
    meta = {"trainer": "ShardedTrainer", "format": tr._CKPT_FORMAT,
            "t": tr._t, "num_update": tr._optimizer.num_update,
            "lr": float(tr._optimizer.learning_rate), "zero1": True,
            "optimizer": "AdamW", "rng_impl": None,
            "param_names": [n for n, _ in items],
            "opt_state_sizes": [len(s) for s in tr._opt_states]}
    _two_host_save(root, arrays, step=3, meta=meta)
    ref_losses = [float(tr.step(x, y).asnumpy()) for _ in range(2)]

    mx.random.seed(999)
    tr2 = _sharded(zero1=True)   # fresh single-host membership
    tr2.step(x, y)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert tr2.restore_checkpoint(root) == 3
    res_losses = [float(tr2.step(x, y).asnumpy()) for _ in range(2)]
    assert onp.allclose(res_losses, ref_losses, rtol=1e-6)
    assert res_losses == ref_losses   # stricter: same mesh ⇒ bit-identical


def test_restore_pairs_parameters_when_a_prefix_counter_gains_a_digit(
        tmp_path):
    """Gluon numbers its layers from a process-wide counter and the
    trainer orders parameters by name, as strings: ``dense9_`` sorts after
    ``dense10_``. A checkpoint of ``dense8_/dense9_`` restored into
    ``dense9_/dense10_`` must still pair first layer with first layer."""
    def trainer(first, second):
        net = gluon.nn.HybridSequential(prefix="")
        net.add(gluon.nn.Dense(16, activation="relu", in_units=8,
                               prefix=f"dense{first}_"),
                gluon.nn.Dense(4, in_units=16, prefix=f"dense{second}_"))
        net.initialize(mx.init.Xavier())
        return parallel.ShardedTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
            {"learning_rate": 1e-2},
            mesh=parallel.make_mesh(devices=jax.devices()[:1]))

    rng = onp.random.RandomState(3)
    x = rng.randn(8, 8).astype("float32")
    y = rng.randint(0, 4, (8,)).astype("float32")
    mx.random.seed(21)
    saved = trainer(8, 9)
    saved.step(x, y)
    saved.save_checkpoint(str(tmp_path))
    mx.random.seed(22)
    fresh = trainer(9, 10)
    fresh.step(x, y)
    assert [p.name for p in fresh._params][0].startswith("dense10_")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # "names differ; by position"
        fresh.restore_checkpoint(str(tmp_path))
    by_shape = lambda tr: {tuple(v.shape): jax.device_get(v)  # noqa: E731
                           for v in tr._param_vals}
    want, got = by_shape(saved), by_shape(fresh)
    assert want.keys() == got.keys() and len(want) == 4
    for shape in want:
        onp.testing.assert_array_equal(got[shape], want[shape])
    assert float(fresh.step(x, y).asnumpy()) == \
        float(saved.step(x, y).asnumpy())


# ---------------------------------------------------------------------------
# guards + watchdog (chaos-driven)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_guard_skip_and_rollback_recovers():
    x, y = _batch()
    guard = fault.StepGuard(policy="skip_and_rollback")
    tr = _sharded(guard=guard)
    tr.step(x, y)
    before = [jax.device_get(v) for v in tr._param_vals]
    t0 = tr.num_update
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        with inject.chaos(seed=0, nan_prob=1.0):
            bad = tr.step(x, y)
    assert not onp.isfinite(float(bad.asnumpy()))
    assert any("fault.guard" in str(x.message) for x in w)
    after = [jax.device_get(v) for v in tr._param_vals]
    for a, b in zip(before, after):
        onp.testing.assert_array_equal(a, b)     # exact rollback
    assert tr.num_update == t0 and guard.skipped == 1
    # training continues cleanly from the rolled-back state
    assert onp.isfinite(float(tr.step(x, y).asnumpy()))
    assert tr.num_update == t0 + 1


@pytest.mark.chaos
def test_guard_halt_raises():
    x, y = _batch()
    tr = _sharded(guard=fault.StepGuard(policy="halt"))
    tr.step(x, y)
    with inject.chaos(seed=0, nan_prob=1.0):
        with pytest.raises(fault.NonFiniteError):
            tr.step(x, y)


@pytest.mark.chaos
def test_guard_warn_keeps_going():
    x, y = _batch()
    guard = fault.StepGuard(policy="warn")
    tr = _sharded(guard=guard)
    tr.step(x, y)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        with inject.chaos(seed=0, nan_prob=1.0):
            tr.step(x, y)
    assert guard.tripped == 1 and guard.skipped == 0
    assert any("non-finite" in str(x.message) for x in w)


def test_guard_grad_norm_limit():
    g = fault.StepGuard(policy="warn", grad_norm_limit=1e-6)
    assert g.is_bad(True, 1.0) is not None        # over the limit
    assert g.is_bad(True, 0.0) is None
    assert g.is_bad(False, 0.0) is not None       # non-finite wins
    with pytest.raises(mx.MXNetError):
        fault.StepGuard(policy="no_such_policy")


def test_guard_escalates_after_max_consecutive():
    g = fault.StepGuard(policy="warn", max_consecutive=2)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        g.decide(1, "non-finite loss")
        g.decide(2, "non-finite loss")
        with pytest.raises(fault.NonFiniteError):
            g.decide(3, "non-finite loss")


def test_all_finite_tree():
    ok = {"a": onp.ones(3, "float32"), "b": [onp.zeros(2, "int32")]}
    assert fault.all_finite(ok)
    bad = {"a": onp.array([1.0, onp.nan], "float32")}
    assert not fault.all_finite(bad)
    assert fault.all_finite()   # vacuous


@pytest.mark.chaos
def test_watchdog_flags_slow_step():
    x, y = _batch()
    wd = fault.Watchdog(deadline=0.15)
    tr = _sharded(watchdog=wd)
    tr.step(x, y)   # warm compile outside chaos: compile may be slow
    assert wd.flags == [] or wd.flags  # compile step may legitimately flag
    n0 = len(wd.flags)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        with inject.chaos(seed=0, slow_prob=1.0, delay_s=0.5):
            tr.step(x, y)
        time.sleep(0.05)   # timer thread delivery
    assert len(wd.flags) > n0
    flag = wd.flags[-1]
    assert flag.deadline == 0.15 and flag.elapsed >= 0.15
    assert any("watchdog" in str(x.message) for x in w)


def test_watchdog_reports_compile_provenance():
    """The diagnostic dump reads the analysis.recompile accounting that
    the hybridize cache records (jit-compile count + recent signatures)."""
    net = _mlp()
    net.hybridize()
    x, _ = _batch()
    xn = mx.nd.array(x)
    net(xn)                        # eager warmup (discovers parameters)
    net(xn)                        # compiled call -> note_compile records
    compiles, recent = fault.Watchdog._compile_state(net)
    assert compiles >= 1 and recent
    flag = fault.WatchdogFlag(step=3, deadline=1.0, elapsed=2.0,
                              compiles=compiles, recent_signatures=recent)
    assert "jit compiles" in str(flag)


# ---------------------------------------------------------------------------
# amp.LossScaler integration
# ---------------------------------------------------------------------------

def test_loss_scaler_uses_shared_finite_check_and_guard():
    from incubator_mxnet_tpu import amp

    class FakeParam:
        def __init__(self, g):
            from incubator_mxnet_tpu.ndarray import NDArray
            self._grad = {"ctx": NDArray(onp.asarray(g, "float32"))}

    sc = amp.LossScaler(init_scale=8.0, guard=fault.StepGuard(
        policy="halt"))
    assert not sc.has_overflow([FakeParam([1.0, 2.0])])
    assert sc.has_overflow([FakeParam([1.0, onp.inf])])
    with pytest.raises(fault.NonFiniteError):
        sc.update_scale(True)
    assert sc.loss_scale == 4.0 and sc.overflows == 1

    sc2 = amp.LossScaler(init_scale=8.0, scale_window=2)
    sc2.update_scale(True)        # no guard: plain dynamic scaling
    assert sc2.loss_scale == 4.0
    sc2.update_scale(False)
    sc2.update_scale(False)
    assert sc2.loss_scale == 8.0  # window regrowth


# ---------------------------------------------------------------------------
# kvstore: timeout satellite, retry/reconnect, idempotent resend
# ---------------------------------------------------------------------------

def test_kvstore_timeout_env(monkeypatch):
    monkeypatch.setenv("MXNET_KVSTORE_TIMEOUT", "3.5")
    srv = AsyncPSServer()
    try:
        c = _Client("127.0.0.1", srv.port)
        assert c._sock.gettimeout() == 3.5
        c.close()
    finally:
        srv.stop()
    monkeypatch.setenv("MXNET_KVSTORE_TIMEOUT", "bogus")
    with pytest.raises(mx.MXNetError, match="MXNET_KVSTORE_TIMEOUT"):
        from incubator_mxnet_tpu.kvstore.async_ps import _io_timeout
        _io_timeout()


def test_kvstore_error_carries_op_and_key(monkeypatch):
    monkeypatch.setenv("MXNET_KVSTORE_RETRIES", "1")
    monkeypatch.setenv("MXNET_KVSTORE_RETRY_DELAY", "0.01")
    srv = AsyncPSServer()
    c = _Client("127.0.0.1", srv.port)
    srv.stop()
    with pytest.raises(mx.MXNetError) as ei:
        c.call("pull", "weight_3")
    msg = str(ei.value)
    assert "pull" in msg and "weight_3" in msg   # context, not bare socket
    c.close()


@pytest.mark.chaos
def test_kvstore_reconnects_across_server_restart(tmp_path, monkeypatch):
    """Kill the PS, restart it from its checkpoint on the same port: the
    client's retry/backoff reconnects and the resumed server continues
    from the checkpointed weights — no manual intervention."""
    monkeypatch.setenv("MXNET_KVSTORE_RETRIES", "8")
    monkeypatch.setenv("MXNET_KVSTORE_RETRY_DELAY", "0.05")
    ckpt = str(tmp_path / "ps.ckpt")
    srv = AsyncPSServer()
    port = srv.port
    c = _Client("127.0.0.1", port)
    c.call("init", "w", onp.zeros(3))
    c.call("set_optimizer",
           pickle.dumps(mx.optimizer.create("sgd", learning_rate=1.0)))
    c.call("push", "w", onp.ones(3), "wid", None)
    srv.stop(checkpoint=ckpt)                    # graceful: severs clients
    srv2 = AsyncPSServer(port=port, restore=ckpt)
    try:
        c.call("push", "w", onp.ones(3), "wid", None)  # reconnect + resend
        onp.testing.assert_allclose(c.call("pull", "w"),
                                    onp.full(3, -2.0))
        stats = c.call("stats")
        assert stats["pushes"] == 2   # push_count survived the restart
    finally:
        c.close()
        srv2.stop()


@pytest.mark.chaos
def test_kvstore_chaos_drop_is_survivable(monkeypatch):
    monkeypatch.setenv("MXNET_KVSTORE_RETRIES", "6")
    monkeypatch.setenv("MXNET_KVSTORE_RETRY_DELAY", "0.02")
    kv = mx.kv.create("dist_async")
    try:
        kv.init("g", mx.nd.zeros((4,)))
        with inject.chaos(seed=3, kv_drop=1.0) as m:
            for i in range(4):
                kv.push("g", mx.nd.full((4,), float(i + 1)))
            out = kv.pull("g")
        assert any(site == "kv_drop" and fired for site, fired in m.log)
        onp.testing.assert_allclose(out.asnumpy(), onp.full(4, 4.0))
    finally:
        kv.close()


def test_kvstore_versioned_push_resend_is_exactly_once():
    srv = AsyncPSServer()
    c = _Client("127.0.0.1", srv.port)
    try:
        c.call("init", "w", onp.zeros(2))
        c.call("set_optimizer",
               pickle.dumps(mx.optimizer.create("sgd", learning_rate=1.0)))
        c.call("push", "w", onp.ones(2), "widA", 1)
        c.call("push", "w", onp.ones(2), "widA", 1)   # resend: acked, no-op
        onp.testing.assert_allclose(c.call("pull", "w"), -onp.ones(2))
        assert c.call("stats")["pushes"] == 1
        c.call("push", "w", onp.ones(2), "widB", 1)   # other worker applies
        onp.testing.assert_allclose(c.call("pull", "w"),
                                    onp.full(2, -2.0))
    finally:
        c.close()
        srv.stop()


@pytest.mark.chaos
def test_chaos_end_to_end_training_survives(monkeypatch):
    """ISSUE 2 acceptance: one seeded chaos run — NaN batches AND dropped
    PS connections together — completes with skip_and_rollback plus client
    reconnect, no manual intervention, finite weights at the end."""
    monkeypatch.setenv("MXNET_KVSTORE_RETRIES", "6")
    monkeypatch.setenv("MXNET_KVSTORE_RETRY_DELAY", "0.02")
    x, y = _batch()
    guard = fault.StepGuard(policy="skip_and_rollback")
    tr = _sharded(guard=guard)
    tr.step(x, y)                       # compile outside chaos
    kv = mx.kv.create("dist_async")     # loss/metric sink over the async PS
    kv.init("loss", mx.nd.zeros((1,)))
    try:
        with warnings.catch_warnings(), \
                inject.chaos(seed=1234, nan_prob=0.4, kv_drop=0.3) as m:
            warnings.simplefilter("ignore")
            for _ in range(10):
                loss = tr.step(x, y)
                kv.push("loss", mx.nd.array(
                    onp.nan_to_num(loss.asnumpy()).reshape(1)))
        assert guard.skipped > 0                      # NaNs actually hit
        assert any(s == "kv_drop" and f for s, f in m.log)  # drops hit
        assert fault.all_finite(list(tr._param_vals))  # weights survived
        assert onp.isfinite(float(kv.pull("loss").asnumpy()[0]))
    finally:
        kv.close()


# ---------------------------------------------------------------------------
# chaos harness determinism + env knob
# ---------------------------------------------------------------------------

def test_chaos_is_seed_deterministic():
    a = inject.ChaosMonkey(seed=42, nan_prob=0.5)
    b = inject.ChaosMonkey(seed=42, nan_prob=0.5)
    assert [a.should("nan_batch") for _ in range(32)] == \
        [b.should("nan_batch") for _ in range(32)]
    c = inject.ChaosMonkey(seed=43, nan_prob=0.5)
    assert [a.should("nan_batch") for _ in range(64)] != \
        [c.should("nan_batch") for _ in range(64)]


def test_chaos_env_spec(monkeypatch):
    monkeypatch.setenv("MXTPU_CHAOS",
                       "seed=7,nan_prob=0.25,crash=nd.save,kv_drop=0.5")
    m = inject.enable_from_env()
    assert m.seed == 7 and m.probs["nan_batch"] == 0.25
    assert m.probs["kv_drop"] == 0.5 and m._armed == {"nd.save": 1}
    inject.disable()
    monkeypatch.setenv("MXTPU_CHAOS", "garbage")
    with pytest.raises(mx.MXNetError):
        inject.enable_from_env()
    inject.disable()


@pytest.mark.chaos
def test_nd_save_atomic_under_crash(tmp_path):
    f = str(tmp_path / "w.params")
    mx.nd.save(f, {"w": mx.nd.ones((3,))})
    with inject.chaos(seed=0, crash_sites=["nd.save"]):
        with pytest.raises(inject.ChaosCrash):
            mx.nd.save(f, {"w": mx.nd.zeros((3,))})
    loaded = mx.nd.load(f)
    onp.testing.assert_allclose(loaded["w"].asnumpy(), onp.ones(3))
    assert not [p for p in os.listdir(str(tmp_path))
                if p.startswith("w.params.tmp")]


# ---------------------------------------------------------------------------
# MX401 lint
# ---------------------------------------------------------------------------

@pytest.mark.lint
def test_mx401_flags_uncheckpointed_training_loop():
    import incubator_mxnet_tpu.analysis as analysis
    fixture = os.path.join(REPO, "tests", "lint_fixtures",
                           "no_checkpoint.py")
    rep = analysis.lint_file(fixture)
    assert rep.codes() == ["MX401"]
    assert rep.warnings and not rep.errors   # hazard, not a build breaker
    assert "fault_lint" == rep.diagnostics[0].pass_name


@pytest.mark.lint
def test_mx401_silent_when_checkpointed_or_loopless():
    import incubator_mxnet_tpu.analysis as analysis
    loop = ("t = Trainer(params, 'sgd')\n"
            "for b in it:\n    t.step(1)\n")
    assert analysis.lint_source(loop).codes() == ["MX401"]
    assert analysis.lint_source(
        loop + "t.save_checkpoint('ck')\n").codes() == []
    assert analysis.lint_source(
        loop + "net.save_parameters('w.params')\n").codes() == []
    # a trainer with no step loop is not a training script
    assert analysis.lint_source(
        "t = Trainer(params, 'sgd')\nt.step(1)\n").codes() == []


@pytest.mark.lint
def test_mx401_in_tree_examples_are_clean():
    """Our own examples must model the behavior the lint asks for."""
    import incubator_mxnet_tpu.analysis as analysis
    rep = analysis.fault_lint.lint_paths([os.path.join(REPO, "examples")])
    assert rep.codes() == [], str(rep)
