"""In-graph numerics observability (ISSUE 14): per-site tensor-stats
telemetry computed INSIDE the one jitted step, host-side decimation,
the drift watchdog escalating to StepGuard before non-finite, flight/
postmortem integration, hist-mode calibration export, the Monitor
bridge, the chaos ramp knobs, and the MX603 lint rule."""
import json
import os
import warnings

import jax
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import fault, gluon, parallel, telemetry
from incubator_mxnet_tpu.telemetry import compile_log
from incubator_mxnet_tpu.telemetry import numerics
from incubator_mxnet_tpu.telemetry.numerics import NumericsConfig

FIXTURES = os.path.join(os.path.dirname(__file__), "lint_fixtures")


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.clear()
    numerics.reset()
    yield
    numerics.reset()


def _batch(n=16, d=12, classes=4, seed=0):
    rng = onp.random.RandomState(seed)
    return (rng.randn(n, d).astype("float32"),
            rng.randint(0, classes, (n,)).astype("float32"))


def _net(prefix, in_units=12, units=16, classes=4):
    mx.random.seed(7)
    net = gluon.nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(gluon.nn.Dense(units, activation="relu",
                               in_units=in_units),
                gluon.nn.Dense(classes, in_units=units))
    net.initialize(mx.init.Xavier())
    return net


def _trainer(prefix, guard=None, numerics_cfg=None, **kw):
    return parallel.ShardedTrainer(
        _net(prefix), gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.05}, mesh=parallel.make_mesh(dp=4, tp=2),
        guard=guard, numerics=numerics_cfg, **kw)


# ---------------------------------------------------------------------------
# config + primitives
# ---------------------------------------------------------------------------

def test_config_off_by_default(monkeypatch):
    monkeypatch.delenv("MXTPU_NUMERICS", raising=False)
    cfg = numerics.config()
    assert cfg.mode is None and not cfg.enabled


def test_config_from_env(monkeypatch):
    monkeypatch.setenv("MXTPU_NUMERICS", "hist")
    monkeypatch.setenv("MXTPU_NUMERICS_EVERY", "3")
    monkeypatch.setenv("MXTPU_NUMERICS_SITES", "grad:*, act:*attn*")
    monkeypatch.setenv("MXTPU_NUMERICS_DRIFT", "rollback")
    cfg = numerics.config()
    assert cfg.mode == "hist" and cfg.hist and cfg.every == 3
    assert cfg.drift_action == "rollback"
    assert cfg.wants("grad:dense0_weight")
    assert cfg.wants("act:enc_attn_out")
    assert not cfg.wants("param:dense0_weight")
    # junk mode string = off, not an error
    monkeypatch.setenv("MXTPU_NUMERICS", "yes-please")
    assert not numerics.config().enabled


def test_tap_is_identity_outside_collection():
    x = onp.arange(4.0)
    assert numerics.tap("anything", x) is x
    assert not numerics.rings()


def test_summary_stats_values():
    x = onp.array([0.0, 1.0, -2.0, onp.nan, onp.inf, 3.0],
                  dtype="float32")
    s = onp.asarray(numerics.summary_stats(x))
    mn, mx_, mean, rms, zf, ff = [float(v) for v in s]
    # finite entries: [0, 1, -2, 3]
    assert mn == -2.0 and mx_ == 3.0
    assert mean == pytest.approx(0.5)
    assert rms == pytest.approx(onp.sqrt((1 + 4 + 9) / 4))
    assert zf == pytest.approx(1 / 6)
    assert ff == pytest.approx(4 / 6)


def test_hist_counts_buckets():
    # |x| = 1.0 -> exponent 0 -> bucket -HIST_LO_EXP; 2.5 -> exp 1
    x = onp.array([1.0, 1.5, 2.5, 0.0, onp.nan], dtype="float32")
    h = onp.asarray(numerics.hist_counts(x, 40))
    b = -numerics.HIST_LO_EXP
    assert h.sum() == 3          # zero and nan carry no weight
    assert h[b] == 2 and h[b + 1] == 1


# ---------------------------------------------------------------------------
# trainer: in-graph stats, one-graph contract, decimation
# ---------------------------------------------------------------------------

def test_trainer_summary_one_graph_ledger_clean():
    cfg = NumericsConfig(mode="summary", every=1)
    guard = fault.StepGuard(policy="warn")
    tr = _trainer("numa_", guard=guard, numerics_cfg=cfg)
    x, y = _batch()
    before = len(compile_log.records("trainer.step"))
    for _ in range(4):
        tr.step(x, y)
    # stats enabled adds ZERO graphs and ZERO extra compiles
    assert tr.last_step_graphs == 1
    assert len(compile_log.records("trainer.step")) == before + 1
    r = numerics.rings()
    names = sorted(n for n, _ in tr._block.collect_params().items())
    # rings are keyed "<scope>/<site>" so a serve stream tapping the
    # same names could never interleave this trainer's drift window
    assert f"trainer.step/param:{names[0]}" in r
    assert f"trainer.step/grad:{names[0]}" in r
    assert len(numerics.ring(f"grad:{names[0]}")) == 4
    rec = numerics.ring(f"grad:{names[0]}")[-1]
    assert rec["step"] == 4 and rec["finite_fraction"] == 1.0
    assert telemetry.counts().get("numerics.step") == 4
    # gauges labeled by site landed in the registry
    snap = telemetry.metrics.to_dict()
    assert any(k.startswith("mxtpu_numerics_rms")
               for k in snap), sorted(snap)[:5]


def test_trainer_numerics_hlo_clean_with_stats_on():
    from incubator_mxnet_tpu.analysis import hlo
    cfg = NumericsConfig(mode="summary", every=1)
    tr = _trainer("numh_", numerics_cfg=cfg)
    x, y = _batch()
    tr.step(x, y)
    rep = hlo.verify(tr, sample_args=(x, y))
    assert rep.ok
    assert "MX704" not in rep.codes() and "MX708" not in rep.codes()


def test_trainer_decimation_every_n():
    cfg = NumericsConfig(mode="summary", every=4)
    guard = fault.StepGuard(policy="warn")
    tr = _trainer("numd_", guard=guard, numerics_cfg=cfg)
    x, y = _batch()
    for _ in range(8):
        tr.step(x, y)
    site = sorted(numerics.rings())[0]
    steps = [r["step"] for r in numerics.ring(site)]
    assert steps == [1, 5]       # first step included, then every 4th


def test_trainer_off_path_unchanged():
    """Numerics off: the step returns its classic arity (no stats
    subtree in out_shardings) and records nothing."""
    off = NumericsConfig(mode=None)
    tr = _trainer("numo_", numerics_cfg=off)
    x, y = _batch()
    tr.step(x, y)
    _, outs = tr.step_shardings(tuple(v.ndim for v in tr.place(x, y)))
    assert len(outs) == 7        # ... + ok, NO stats slot
    on = NumericsConfig(mode="summary")
    tr2 = _trainer("numo2_", numerics_cfg=on)
    tr2.step(x, y)
    _, outs2 = tr2.step_shardings(tuple(v.ndim for v in tr2.place(x, y)))
    assert len(outs2) == 8
    assert not numerics.ring("grad:numo_dense0_weight")


def test_trainer_site_allowlist():
    cfg = NumericsConfig(mode="summary", every=1, sites=("grad:*",))
    guard = fault.StepGuard(policy="warn")
    tr = _trainer("numf_", guard=guard, numerics_cfg=cfg)
    x, y = _batch()
    tr.step(x, y)
    sites = {k.split("/", 1)[1] for k in numerics.rings()}
    assert sites and all(s.startswith("grad:") for s in sites)


class _TappedNet(gluon.HybridBlock):
    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.d1 = gluon.nn.Dense(16, activation="relu", in_units=12)
            self.d2 = gluon.nn.Dense(4, in_units=16)

    def hybrid_forward(self, F, x):
        h = self.d1(x)
        h = numerics.tap("hidden", h)
        return self.d2(h)


def test_tap_site_collected_in_trainer_graph():
    net = _TappedNet(prefix="numtap_")
    net.initialize(mx.init.Xavier())
    guard = fault.StepGuard(policy="warn")
    tr = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.05}, mesh=parallel.make_mesh(dp=4, tp=2),
        guard=guard, numerics=NumericsConfig(mode="summary", every=1))
    x, y = _batch()
    tr.step(x, y)
    assert tr.last_step_graphs == 1
    rec = numerics.ring("act:hidden")
    assert rec and rec[-1]["min"] >= 0.0          # post-relu activation
    assert rec[-1]["finite_fraction"] == 1.0


# ---------------------------------------------------------------------------
# drift watchdog
# ---------------------------------------------------------------------------

def _fake_stats(rms, ff=1.0):
    v = onp.array([0.0, rms, 0.0, rms, 0.0, ff], dtype="float32")
    return {"s": v}


def test_drift_rms_growth_damped():
    cfg = NumericsConfig(mode="summary", every=1)
    # monotonic x2 per sample: crosses ratio 4 within the window
    verdicts = []
    for step, rms in enumerate([1, 2, 4, 8, 16, 32], start=1):
        verdicts.append(numerics.record(
            "test", step, {"site:a": _fake_stats(float(rms))}, cfg))
    fired = [v for v in verdicts if v]
    assert fired and fired[0][0]["reason"] == "rms_growth"
    # damped: 6 samples of explosive growth != 3 identical warnings
    n_events = telemetry.counts().get("numerics.drift")
    assert n_events == len(fired)
    # recovery re-arms: drop, then ramp again -> fires again
    for step, rms in enumerate([1, 1, 1, 1, 2, 8, 32, 128], start=10):
        numerics.record("test", step,
                        {"site:a": _fake_stats(float(rms))}, cfg)
    assert telemetry.counts().get("numerics.drift") > n_events


def test_drift_zero_base_window_does_not_fire():
    """A fresh-bias site growing from rms 0 has no growth ratio — the
    healthy-warmup false positive the zero-base skip exists for."""
    cfg = NumericsConfig(mode="summary", every=1)
    for step, rms in enumerate([0.0, 0.001, 0.002, 0.003], start=1):
        out = numerics.record("test", step,
                              {"site:b": _fake_stats(rms)}, cfg)
    assert out == []
    assert not telemetry.counts().get("numerics.drift")


def test_drift_convergence_rebound_does_not_fire():
    """The healthy-convergence false positive (caught driving a real
    adamw run): a grad rms that decays toward 0 crossing a loss
    minimum, then ticks back up at tiny scale, shows a huge window
    RATIO — but never a new ring-wide high, so it must not flag."""
    cfg = NumericsConfig(mode="summary", every=1)
    series = [0.118, 0.08, 0.048, 0.018, 0.0085, 0.002, 2.3e-05,
              0.0016, 0.0028, 0.0035, 0.0039]      # 150x off the dip
    out = []
    fired = False
    for step, rms in enumerate(series, start=1):
        out = numerics.record("test", step,
                              {"site:g": _fake_stats(rms)}, cfg)
        fired = fired or bool(out)
    assert not fired
    # a REAL blowup from the same history still fires: new highs
    for step, rms in enumerate([0.2, 0.9, 4.0, 18.0], start=20):
        out = numerics.record("test", step,
                              {"site:g": _fake_stats(rms)}, cfg)
    assert out and out[0]["reason"] == "rms_growth"


def test_drift_windows_isolated_per_scope():
    """A trainer and a server recording the SAME site name must not
    interleave one drift window: the diverging stream still flags even
    while a healthy stream writes between its samples."""
    cfg = NumericsConfig(mode="summary", every=1)
    fired = False
    for step, rms in enumerate([1, 4, 16, 64, 256], start=1):
        out = numerics.record("trainer.step", step,
                              {"act:h": _fake_stats(float(rms))}, cfg)
        fired = fired or bool(out)
        # interleaved healthy serve stream on the same site name
        numerics.record("serve.compiled", step,
                        {"act:h": _fake_stats(0.5)}, cfg)
    assert fired
    keys = set(numerics.rings())
    assert keys == {"trainer.step/act:h", "serve.compiled/act:h"}


def test_drift_finite_fraction_decay():
    cfg = NumericsConfig(mode="summary", every=1)
    out = []
    for step, ff in enumerate([1.0, 0.9, 0.7, 0.5], start=1):
        out = numerics.record("test", step,
                              {"site:c": _fake_stats(1.0, ff)}, cfg)
    assert out and out[0]["reason"] == "finite_fraction_decay"


# ---------------------------------------------------------------------------
# chaos ramp + guard escalation ordering
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_scale_ramp_deterministic():
    with fault.inject.chaos(seed=3, grad_blowup=0.5,
                            blowup_factor=4.0) as m1:
        a = [m1.scale_ramp("grad_blowup") for _ in range(10)]
    with fault.inject.chaos(seed=3, grad_blowup=0.5,
                            blowup_factor=4.0) as m2:
        b = [m2.scale_ramp("grad_blowup") for _ in range(10)]
    assert a == b                       # seeded: same draws, same ramp
    assert sorted(a) == a and a[-1] > 1.0   # monotonic, actually ramped
    assert fault.inject.scale_ramp("grad_blowup") == 1.0  # no monkey


@pytest.mark.chaos
def test_drift_fires_before_nonfinite_guard_fused():
    cfg = NumericsConfig(mode="summary", every=1)
    guard = fault.StepGuard(policy="halt")
    tr = _trainer("numc_", guard=guard, numerics_cfg=cfg)
    x, y = _batch()
    with fault.inject.chaos(seed=7, grad_blowup=1.0, blowup_factor=16.0):
        with pytest.raises(fault.NonFiniteError):
            for _ in range(120):
                tr.step(x, y)
    drift = telemetry.get_events("numerics.drift")
    guard_evs = telemetry.get_events("guard")
    assert drift and guard_evs
    assert drift[0].seq < guard_evs[0].seq
    assert tr.last_step_graphs == 1


@pytest.mark.chaos
def test_drift_rollback_escalation_precedence():
    """drift warning -> rollback -> halt precedence: under
    drift_action='rollback' a skip_and_rollback guard rolls the run
    back on DRIFT (all values still finite), and max_consecutive
    escalation to NonFiniteError still wins in the end."""
    cfg = NumericsConfig(mode="summary", every=1,
                         drift_action="rollback")
    guard = fault.StepGuard(policy="skip_and_rollback",
                            max_consecutive=6)
    tr = _trainer("numr_", guard=guard, numerics_cfg=cfg)
    x, y = _batch()
    with fault.inject.chaos(seed=7, grad_blowup=1.0,
                            blowup_factor=16.0), \
            warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(fault.NonFiniteError, match="consecutive"):
            for _ in range(200):
                tr.step(x, y)
    assert guard.skipped > 0
    first = telemetry.get_events("guard")[0]
    # the FIRST guard trip was the drift escalation, not non-finite
    assert "numerics drift" in first.fields["reason"]
    assert guard.tripped > guard.skipped or guard.skipped >= 1


@pytest.mark.chaos
def test_flight_bundle_and_postmortem_numerics(tmp_path):
    from incubator_mxnet_tpu.telemetry import flight
    import tools.postmortem as postmortem
    flight.set_dir(str(tmp_path))
    flight.reset()
    try:
        cfg = NumericsConfig(mode="summary", every=1)
        guard = fault.StepGuard(policy="halt")
        tr = _trainer("numb_", guard=guard, numerics_cfg=cfg)
        x, y = _batch()
        with fault.inject.chaos(seed=7, grad_blowup=1.0,
                                blowup_factor=16.0):
            with pytest.raises(fault.NonFiniteError):
                for _ in range(120):
                    tr.step(x, y)
        bundles = flight.list_bundles(str(tmp_path))
        assert bundles
        doc = flight.load(bundles[-1])
        sites = doc["numerics"]["sites"]
        assert sites
        # the ring history PREDATES the trip: the postmortem shows the
        # divergence trajectory, not just the final verdict
        trip = tr.num_update
        assert any(len(r) >= 2 and r[0]["step"] < trip
                   for r in sites.values())
        text = postmortem.render(doc)
        assert "numerics" in text and "rms" in text
    finally:
        flight.set_dir(None)


# ---------------------------------------------------------------------------
# hist mode -> calibration -> Observer
# ---------------------------------------------------------------------------

def test_hist_mode_calibration_observer_roundtrip():
    from incubator_mxnet_tpu import quantization
    cfg = NumericsConfig(mode="hist", every=1, bins=40)
    guard = fault.StepGuard(policy="warn")
    tr = _trainer("numq_", guard=guard, numerics_cfg=cfg)
    x, y = _batch()
    for _ in range(5):
        tr.step(x, y)
    table = numerics.calibration_table()
    assert table
    site = sorted(table)[0]
    rec = table[site]
    assert rec["bins"] == 40 and rec["samples"] == 5
    assert sum(rec["counts"]) > 0
    # strict-JSON shape survives a dump/load cycle
    table = json.loads(json.dumps(table))
    obs = quantization.Observer(table)
    assert obs.to_table() == table              # byte round-trip
    lo, hi = obs.ranges(percentile=100.0)[site]
    assert lo == -hi and hi > 0
    # percentile clipping can only tighten the range
    assert obs.threshold(site, 99.0) <= obs.threshold(site, 100.0)


def test_observer_merge_and_threshold():
    from incubator_mxnet_tpu import quantization
    obs = quantization.Observer()
    counts = [0.0] * 40
    counts[24] = 90.0                 # |x| in [1, 2): bucket 24 (lo -24)
    counts[30] = 10.0                 # outliers in [64, 128)
    obs.update("act:z", counts, lo_exp=-24, amin=-100.0, amax=100.0)
    obs.update("act:z", counts, lo_exp=-24, amin=-120.0, amax=90.0)
    t = obs.to_table()["act:z"]
    assert t["samples"] == 2 and t["min"] == -120.0 and t["max"] == 100.0
    assert sum(t["counts"]) == 200.0
    # 90% clip drops the [64,128) outlier mass -> threshold 2.0
    assert obs.threshold("act:z", percentile=90.0) == 2.0
    # 100% keeps it, clamped by observed absmax
    assert obs.threshold("act:z", percentile=100.0) == pytest.approx(120.0)
    with pytest.raises(mx.MXNetError):
        obs.update("act:z", [0.0] * 8, lo_exp=-24)


# ---------------------------------------------------------------------------
# serve.CompiledModel
# ---------------------------------------------------------------------------

def test_serve_compiled_output_stats():
    from incubator_mxnet_tpu import serve
    numerics.configure(NumericsConfig(mode="summary", every=2))
    try:
        net = _net("numsrv_", in_units=6, units=8, classes=3)
        net.hybridize()
        x = onp.random.RandomState(0).randn(4, 6).astype("float32")
        net(mx.nd.array(x))
        table = serve.BucketTable({"batch": [4, 8]})
        cm = serve.CompiledModel(net, table, input_axes=[{0: "batch"}])
        cm.warmup()
        for _ in range(5):
            cm.predict(x[:2])
        assert cm.stats["post_warmup_compiles"] == 0
        recs = numerics.ring("serve.out:0")
        assert len(recs) == 3           # requests 1, 3, 5 (every=2)
        assert recs[-1]["finite_fraction"] == 1.0
    finally:
        numerics.configure(None)


def test_serve_compiled_off_by_default():
    from incubator_mxnet_tpu import serve
    net = _net("numsrvo_", in_units=6, units=8, classes=3)
    net.hybridize()
    x = onp.random.RandomState(0).randn(4, 6).astype("float32")
    net(mx.nd.array(x))
    table = serve.BucketTable({"batch": [4, 4]})
    cm = serve.CompiledModel(net, table, input_axes=[{0: "batch"}])
    cm.warmup()
    out = cm.predict(x)
    assert out.shape == (4, 3)
    assert "serve.out:0" not in numerics.rings()


# ---------------------------------------------------------------------------
# Monitor bridge
# ---------------------------------------------------------------------------

def test_monitor_bridge_taps_blocks():
    net = _net("nummon_")
    mon = mx.monitor.Monitor(interval=1, pattern=".*dense.*")
    with pytest.warns(DeprecationWarning):
        mon.install(net)
    try:
        assert mon._tap_sites           # matched the dense children
        guard = fault.StepGuard(policy="warn")
        tr = parallel.ShardedTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.05}, mesh=parallel.make_mesh(dp=4, tp=2),
            guard=guard)                # env off -> bridge override
        x, y = _batch()
        mon.tic()
        tr.step(x, y)
        rows = mon.toc()
        assert rows
        steps, names, stats = zip(*rows)
        assert any(n.startswith("act:") and "dense" in n for n in names)
        assert all(s >= 0 for s in stats)
        # same rows are not re-reported next toc
        mon.tic()
        tr.step(x, y)
        rows2 = mon.toc()
        assert rows2 and min(s for s, _, _ in rows2) > max(steps)
        # detach restores the config override the bridge armed, so a
        # trainer built AFTER is uninstrumented again
        assert numerics.config().enabled
        mon.detach()
        assert not numerics.config().enabled
    finally:
        mon.detach()
        numerics.configure(None)


# ---------------------------------------------------------------------------
# MX603 lint
# ---------------------------------------------------------------------------

@pytest.mark.lint
def test_mx603_fixture_findings():
    from incubator_mxnet_tpu.analysis import telemetry_lint
    rep = telemetry_lint.lint_file(
        os.path.join(FIXTURES, "host_callback_stats.py"))
    found = [d for d in rep.diagnostics if d.code == "MX603"]
    assert len(found) == 3
    assert {d.op for d in found} == {"step", "fwd"}
    assert all(d.severity == "warning" for d in found)
    assert "telemetry.numerics" in found[0].message


@pytest.mark.lint
def test_mx603_clean_controls():
    from incubator_mxnet_tpu.analysis import telemetry_lint
    # a callback in a NON-jitted function, and a jitted fn with an
    # in-graph reduction returned as an output: both clean
    src = (
        "import jax, jax.numpy as jnp\n"
        "def eager_debug(x):\n"
        "    jax.debug.callback(print, jnp.min(x))\n"
        "    return x\n"
        "@jax.jit\n"
        "def good_step(g):\n"
        "    return g * 2, jnp.stack([jnp.min(g), jnp.max(g)])\n")
    rep = telemetry_lint.lint_source(src, "ctrl.py")
    assert not [d for d in rep.diagnostics if d.code == "MX603"]


@pytest.mark.lint
def test_mx603_registered():
    from incubator_mxnet_tpu.analysis import CODES, DEFAULT_SEVERITY
    assert "MX603" in CODES and DEFAULT_SEVERITY["MX603"] == "warning"


# ---------------------------------------------------------------------------
# snapshot / reset integration
# ---------------------------------------------------------------------------

def test_snapshot_carries_numerics_section():
    cfg = NumericsConfig(mode="summary", every=1)
    numerics.record("test", 1, {"site:x": _fake_stats(2.0)}, cfg)
    snap = telemetry.snapshot()
    assert "numerics" in snap
    assert "test/site:x" in snap["numerics"]["sites"]
    # snapshot reports the config that actually RECORDED, not the
    # (unset) env — a ctor-configured trainer's postmortem header must
    # not read "mode=None" above real drift rows
    assert snap["numerics"]["config"]["mode"] == "summary"
    telemetry.reset()
    assert not numerics.rings()
