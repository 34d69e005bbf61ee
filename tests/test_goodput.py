"""Goodput ledger (ISSUE 15): run-level wall-clock attribution, the
measured-vs-roofline MFU headline, rollback-waste accounting, the
PrefetchIter input-wait instrumentation + slow_input chaos knob, the
MX604 stray-sync lint rule, and the perf_history trajectory tool."""
import json
import os
import warnings

import jax
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import fault, gluon, parallel, telemetry
from incubator_mxnet_tpu import io as mio
from incubator_mxnet_tpu.telemetry import goodput

FIXTURES = os.path.join(os.path.dirname(__file__), "lint_fixtures")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_ledger():
    telemetry.clear()
    goodput.reset()
    yield
    goodput.reset()


def _batch(n=16, d=12, classes=4, seed=0):
    rng = onp.random.RandomState(seed)
    return (rng.randn(n, d).astype("float32"),
            rng.randint(0, classes, (n,)).astype("float32"))


def _trainer(prefix, guard=None, **kw):
    mx.random.seed(7)
    net = gluon.nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(gluon.nn.Dense(16, activation="relu", in_units=12),
                gluon.nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    return parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.05},
        mesh=parallel.make_mesh(devices=jax.devices()[:1]),
        guard=guard, **kw)


# ---------------------------------------------------------------------------
# ledger primitives
# ---------------------------------------------------------------------------

def test_off_by_default(monkeypatch):
    monkeypatch.delenv("MXTPU_GOODPUT", raising=False)
    assert not goodput.enabled()
    # notes are no-ops while off — zero state accumulates
    goodput.note("input_wait", 5.0)
    goodput.note_step(step=1, wall_ms=10.0)
    rep = goodput.report()
    assert rep["steps"] == 0 and rep["attributed_ms"] == 0.0
    assert not rep["enabled"]


def test_env_and_configure(monkeypatch):
    monkeypatch.setenv("MXTPU_GOODPUT", "1")
    monkeypatch.setenv("MXTPU_GOODPUT_WINDOW", "7")
    assert goodput.enabled() and goodput.window_steps() == 7
    goodput.configure(on=False)
    assert not goodput.enabled()
    goodput.configure()                      # clears overrides
    assert goodput.enabled()


def test_step_attribution_vector():
    goodput.configure(on=True, window=100)
    goodput.begin()
    # compile step: dispatch wall is one-off compile, not host tax
    goodput.note_step(step=1, wall_ms=50.0, device_wait_ms=5.0,
                      compile_ms=40.0)
    # steady step: device sync reads as compute, remainder as host
    goodput.note_step(step=2, wall_ms=10.0, device_wait_ms=6.0)
    rep = goodput.report()
    cats = {c: v["ms"] for c, v in rep["categories"].items()}
    assert cats["compile"] == pytest.approx(40.0)
    assert cats["compute"] == pytest.approx(11.0)      # 5 + 6
    assert cats["host"] >= 8.9                         # 50-40-5 + 10-6
    assert rep["steps"] == 2 and rep["good_steps"] == 2
    # attributed_ms is exactly the category sum (unattributed excluded)
    assert rep["attributed_ms"] == pytest.approx(
        sum(v["ms"] for c, v in rep["categories"].items()
            if c != "unattributed"))


def test_classification_input_vs_compute_bound():
    # synthetic input-bound run: waits dwarf device time
    goodput.configure(on=True, window=100)
    goodput.begin()
    for i in range(1, 6):
        goodput.note("input_wait", 40.0)
        goodput.note_step(step=i, wall_ms=10.0, device_wait_ms=6.0)
    assert goodput.report()["classification"] == "input_bound"
    # synthetic compute-bound run: device sync dominates each step
    goodput.begin()                          # resets totals
    for i in range(1, 6):
        goodput.note_step(step=i, wall_ms=10.0, device_wait_ms=9.0)
    assert goodput.report()["classification"] == "compute_bound"


def test_dominant_bucket_tie_break_order_pinned():
    """Regression pin (ISSUE 19): the flight director's policy table
    keys off the classification, so the triage tie-break order —
    input_wait > host > collective > compute, first wins exact ties —
    is load-bearing API, not an implementation detail."""
    assert goodput._BOUND_CATEGORIES == ("input_wait", "host",
                                         "collective", "compute")
    # exact ties resolve to the EARLIER triage bucket at every rank
    tie = {"input_wait": 5.0, "host": 5.0, "collective": 5.0,
           "compute": 5.0}
    assert goodput._classify(tie) == "input_bound"
    assert goodput._classify({"host": 5.0, "collective": 5.0,
                              "compute": 5.0}) == "host_bound"
    assert goodput._classify({"collective": 5.0,
                              "compute": 5.0}) == "collective_bound"
    # strictly-larger later bucket still wins
    assert goodput._classify({"input_wait": 5.0,
                              "compute": 5.1}) == "compute_bound"
    # all-zero (or empty) vectors classify as nothing, never a default
    assert goodput._classify({}) is None
    assert goodput._classify({"compute": 0.0}) is None


def test_divergence_gauge_sign_convention_pinned():
    """Regression pin (ISSUE 19): divergence = 100·(measured/predicted
    − 1) — measured MFU BELOW the roofline is NEGATIVE. The director's
    breach test (`div <= -threshold`) depends on this sign; flipping it
    would silently disarm the loop."""
    goodput.configure(on=True)
    prof = goodput.set_cost_profile(flops_per_step=1e9)
    predicted = prof["predicted_mfu"]
    assert predicted is not None and predicted > 0
    # wall long enough that measured MFU falls below the roofline
    slow = goodput._mfu(wall_ms=1e3, good_steps=1)
    assert slow["measured_mfu"] < predicted
    assert slow["divergence_pct"] < 0
    assert slow["divergence_pct"] == pytest.approx(
        100.0 * (slow["measured_mfu"] / predicted - 1.0), abs=0.01)
    # and a run FASTER than predicted reads positive — no breach
    fast_wall_ms = prof["roofline_s"] * 1e3 / 2.0
    fast = goodput._mfu(wall_ms=fast_wall_ms, good_steps=1)
    assert fast["divergence_pct"] > 0


def test_inter_step_gap_lands_in_host():
    import time
    goodput.configure(on=True, window=100)
    goodput.begin()
    goodput.note_step(step=1, wall_ms=1.0, device_wait_ms=0.5)
    time.sleep(0.03)                          # un-noted loop time
    goodput.note_step(step=2, wall_ms=1.0, device_wait_ms=0.5)
    rep = goodput.report()
    # the 30ms gap was attributed as host tax, not left unattributed
    assert rep["categories"]["host"]["ms"] >= 25.0
    assert rep["unattributed_pct"] < 10.0


def test_window_events_and_gauges():
    goodput.configure(on=True, window=3)
    goodput.begin()
    for i in range(1, 8):
        goodput.note_step(step=i, wall_ms=5.0, device_wait_ms=3.0)
    evs = telemetry.get_events("goodput.window")
    assert len(evs) == 2                      # 7 steps / window 3
    f = evs[0].fields
    assert f["steps"] == 3 and "categories" in f
    assert f["categories"]["compute"] == pytest.approx(9.0)
    mets = telemetry.metrics.to_dict()
    assert "mxtpu_goodput_share_pct" in mets
    assert "mxtpu_goodput_unattributed_pct" in mets
    assert mets["mxtpu_goodput_windows_total"]["_"] == 2


def test_rollback_reclassifies_discarded_steps():
    goodput.configure(on=True, window=100)
    goodput.begin()
    # snapshot at step 4; steps 5-7 succeed, step 8 rolls back to 4
    for i in range(1, 8):
        goodput.note_step(step=i, wall_ms=10.0, device_wait_ms=8.0)
    before = goodput.report()["categories"]["compute"]["ms"]
    assert before == pytest.approx(56.0)
    goodput.note_step(step=8, wall_ms=10.0, rolled_back=True,
                      rollback_to=4)
    rep = goodput.report()
    cats = {c: v["ms"] for c, v in rep["categories"].items()}
    # steps 5-7 (8ms compute + 2ms host each) moved to waste, plus the
    # bad step's whole 10ms wall
    assert cats["rollback_waste"] == pytest.approx(40.0)
    assert cats["compute"] == pytest.approx(32.0)      # steps 1-4 remain
    assert cats["host"] == pytest.approx(8.0)
    assert rep["rolled_back_steps"] == 1
    # the discarded steps 5-7 are no longer productive: measured_mfu
    # must count only updates that survived the rollback
    assert rep["good_steps"] == 4


def test_mfu_reconciliation(monkeypatch):
    monkeypatch.setenv("MXTPU_PEAK_TFLOPS", "100")
    goodput.configure(on=True, window=100)
    prof = goodput.set_cost_profile(flops_per_step=1e12,
                                    hbm_bytes_per_step=1e9,
                                    comm_bytes_per_step=0.0)
    # roofline: compute-bound at 10ms/step on a 100 TF chip
    assert prof["roofline_s"] == pytest.approx(0.01)
    assert prof["predicted_mfu"] == pytest.approx(1.0)
    goodput.begin()
    import time
    time.sleep(0.025)                         # real run wall >= 25ms
    goodput.note_step(step=1, wall_ms=20.0, device_wait_ms=15.0)
    rep = goodput.report()
    mfu = rep["mfu"]
    # 1e12 flops over >=25ms of REAL wall on a 100TF peak: measured
    # lands well under the roofline ceiling of 1.0
    assert 0.0 < mfu["measured_mfu"] < 1.0
    assert mfu["predicted_mfu"] == pytest.approx(1.0)
    assert mfu["divergence_pct"] is not None


def test_collective_split_follows_cost_profile(monkeypatch):
    monkeypatch.setenv("MXTPU_PEAK_TFLOPS", "100")
    monkeypatch.setenv("MXTPU_ICI_GBPS", "100")
    goodput.configure(on=True, window=100)
    # compute_s = 1e10/1e14 = 1e-4; comm_s = 1e7/1e11 = 1e-4 -> 50/50
    goodput.set_cost_profile(flops_per_step=1e10,
                             comm_bytes_per_step=1e7)
    goodput.begin()
    goodput.note_step(step=1, wall_ms=10.0, device_wait_ms=8.0)
    cats = {c: v["ms"] for c, v in goodput.report()["categories"].items()}
    assert cats["collective"] == pytest.approx(4.0)
    assert cats["compute"] == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# runtime wiring (trainer / io / checkpoint / flight)
# ---------------------------------------------------------------------------

def test_trainer_notes_steps_and_stays_one_graph():
    goodput.configure(on=True, window=4)
    tr = _trainer("gp_tr_", guard=fault.StepGuard(policy="warn"))
    x, y = _batch()
    goodput.begin()
    for _ in range(6):
        tr.step(x, y)
    rep = goodput.report()
    assert rep["steps"] == 6 and rep["good_steps"] == 6
    assert rep["categories"]["compile"]["ms"] > 0     # first trace wall
    assert rep["categories"]["compute"]["ms"] > 0     # the guard sync
    # real run: attribution never overshoots the measured wall by >5%
    assert rep["attributed_ms"] <= rep["wall_ms"] * 1.05
    assert tr.last_step_graphs == 1                   # ledger untouched
    assert len(telemetry.get_events("goodput.window")) >= 1


def test_trainer_off_means_zero_ledger_state():
    goodput.configure(on=False)
    tr = _trainer("gp_off_", guard=fault.StepGuard(policy="warn"))
    x, y = _batch()
    for _ in range(2):
        tr.step(x, y)
    assert goodput.report()["steps"] == 0


@pytest.mark.chaos
def test_rollback_waste_under_nan_chaos():
    goodput.configure(on=True, window=100)
    guard = fault.StepGuard(policy="skip_and_rollback", snapshot_every=2,
                            max_consecutive=100)
    tr = _trainer("gp_nan_", guard=guard)
    x, y = _batch()
    tr.step(x, y).asnumpy()                   # compile outside the run
    goodput.begin()
    with fault.inject.chaos(seed=5, nan_prob=0.4), \
            warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for _ in range(12):
            tr.step(x, y)
    rep = goodput.report()
    assert rep["rolled_back_steps"] > 0
    assert rep["rolled_back_steps"] == guard.skipped
    waste = rep["categories"]["rollback_waste"]["ms"]
    assert waste > 0.0
    # the run wall stays fully accounted for under chaos too
    assert rep["unattributed_pct"] < 10.0


@pytest.mark.chaos
def test_prefetch_input_wait_and_slow_input_classification():
    goodput.configure(on=True, window=100)
    tr = _trainer("gp_io_", guard=fault.StepGuard(policy="warn"))
    x, y = _batch(n=160)
    tr.step(x[:16], y[:16]).asnumpy()
    it = mio.PrefetchIter(
        mio.NDArrayIter(x, y, batch_size=16,
                        last_batch_handle="discard"),
        place=lambda b: tr.place(*(b.data + b.label)), depth=1)
    goodput.begin()
    with fault.inject.chaos(seed=7, slow_input=1.0, delay_s=0.02):
        for placed in it:
            tr.step(*placed)
    it.close()
    rep = goodput.report()
    assert rep["classification"] == "input_bound"
    assert rep["categories"]["input_wait"]["share_pct"] > 50.0
    # the io metrics + span landed too
    mets = telemetry.metrics.to_dict()
    assert mets["mxtpu_io_wait_ms"]["_"]["count"] >= 10
    assert "mxtpu_io_queue_depth" in mets
    from incubator_mxnet_tpu import profiler
    assert any(r.name == "io.wait" for r in profiler.recent_spans())


def test_checkpoint_note_and_event(tmp_path):
    goodput.configure(on=True, window=100)
    goodput.begin()
    from incubator_mxnet_tpu.fault import checkpoint as ckpt
    ckpt.save_checkpoint(str(tmp_path), {"w": onp.ones((4,), "float32")},
                         {"note": 1}, step=3)
    rep = goodput.report()
    assert rep["categories"]["checkpoint"]["ms"] > 0
    assert rep["checkpoints"] == 1
    evs = telemetry.get_events("checkpoint.save")
    assert len(evs) == 1 and evs[0].fields["arrays"] == 1
    from incubator_mxnet_tpu import profiler
    assert any(r.name == "checkpoint.save"
               for r in profiler.recent_spans())


def test_snapshot_flight_and_postmortem_carry_goodput():
    goodput.configure(on=True, window=100)
    goodput.begin()
    for i in range(1, 4):
        goodput.note_step(step=i, wall_ms=8.0, device_wait_ms=6.0)
    snap = telemetry.snapshot()
    assert snap["goodput"]["steps"] == 3
    from incubator_mxnet_tpu.telemetry import flight
    doc = flight.bundle("manual")
    assert doc["goodput"]["steps"] == 3
    import sys
    sys.path.insert(0, REPO_ROOT)
    try:
        from tools import postmortem
        rendered = postmortem.render(doc)
    finally:
        sys.path.remove(REPO_ROOT)
    assert "goodput" in rendered and "compute" in rendered


def test_price_installs_cost_profile_from_trainer():
    goodput.configure(on=True, window=100)
    tr = _trainer("gp_price_")
    x, y = _batch()
    prof = goodput.price(tr, sample_args=(x, y))
    assert prof["flops_per_step"] > 0
    assert prof["source"] == "analysis.hlo.cost"
    assert goodput.cost_profile()["roofline_s"] > 0


# ---------------------------------------------------------------------------
# MX604 lint rule
# ---------------------------------------------------------------------------

@pytest.mark.lint
def test_mx604_fixture_findings():
    from incubator_mxnet_tpu.analysis import telemetry_lint
    rep = telemetry_lint.lint_file(
        os.path.join(FIXTURES, "stray_sync.py"))
    found = [d for d in rep.diagnostics if d.code == "MX604"]
    assert len(found) == 3
    ops = sorted(d.op for d in found)
    assert ops == ["float(loss)", "loss.block_until_ready()",
                   "loss.item()"]
    # exactly the fixture's three hot-loop lines; the decimated read,
    # the asnumpy idiom, and the post-loop sync are controls
    lines = sorted(int(d.node.rsplit(":", 1)[1]) for d in found)
    assert lines == [14, 15, 16]


@pytest.mark.lint
def test_mx604_controls_stay_clean():
    from incubator_mxnet_tpu.analysis import telemetry_lint
    clean = """
def train(trainer, batches, logger):
    for step, batch in enumerate(batches):
        loss = trainer.step(*batch)
        if step % 10 == 0:
            logger.log(float(loss))          # decimated: cadence ok
        other = compute()
        other.item()                          # not a step result
    return float(loss.asnumpy())              # honest sync, post-loop
"""
    rep = telemetry_lint.lint_source(clean, "clean.py")
    assert not [d for d in rep.diagnostics if d.code == "MX604"]


@pytest.mark.lint
def test_mx604_registered():
    from incubator_mxnet_tpu.analysis.diagnostics import (CODES,
                                                          DEFAULT_SEVERITY)
    assert "MX604" in CODES
    assert DEFAULT_SEVERITY["MX604"] == "warning"


# ---------------------------------------------------------------------------
# perf_history trajectory tool
# ---------------------------------------------------------------------------

def _ph():
    import sys
    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    from tools import perf_history
    return perf_history


def _banked_root(root):
    """A record root as the driver leaves one: two measured rounds, three
    rc=75 rounds that printed nothing, and a BASELINE.md whose sweep
    table holds the best config (no record of this shape is kept at the
    repo root any more)."""
    for n, (step_ms, mfu) in ((1, (68.57, 0.2175)), (2, (43.09, 0.3461))):
        (root / f"BENCH_r{n:02d}.json").write_text(json.dumps({
            "n": n, "rc": 0,
            "parsed": {"metric": "bert_12_768_12_pretrain_tokens_per_sec"
                                 "_per_chip", "value": 1.0,
                       "unit": "tokens/sec/chip",
                       "extra": {"step_ms": step_ms, "mfu": mfu}}}))
    for n in (3, 4, 5):
        (root / f"BENCH_r{n:02d}.json").write_text(json.dumps(
            {"n": n, "rc": 75, "parsed": None}))
    (root / "BASELINE.md").write_text(
        "| Config | step ms | MFU |\n|---|---|---|\n"
        "| flash BQ=256 BK=512 (old default) | 45.5* | 0.328* |\n"
        "| flash BQ=512 BK=512 | **39.4** | **0.3789** |\n"
        "| flash BQ=128 BK=512 | 1066 | pathological |\n")
    return str(root)


def test_perf_history_reproduces_banked_best(tmp_path):
    ph = _ph()
    doc = ph.collect(_banked_root(tmp_path))
    best = doc["best_banked"]
    assert best["mfu"] == pytest.approx(0.3789)
    assert "BQ=512" in best["config"]
    assert doc["blind_rounds"] == 3            # the rc=75 rounds
    assert not doc["regressions"]
    rendered = ph.render(doc)
    assert "BLIND" in rendered and "0.3789" in rendered
    # blind rounds render with a reason, never silently skipped
    assert rendered.count("BLIND") == doc["blind_rounds"]


def test_perf_history_flags_seeded_regression(tmp_path):
    ph = _ph()
    for n, mfu in ((1, 0.40), (2, 0.37)):     # -7.5% — beyond ±5%
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps({
            "n": n, "rc": 0,
            "parsed": {"metric": "m", "value": 1.0, "unit": "u",
                       "extra": {"mfu": mfu}}}))
    doc = ph.collect(str(tmp_path))
    assert len(doc["regressions"]) == 1
    assert "r2" in doc["regressions"][0]
    assert ph.main(["--dir", str(tmp_path), "--check"]) == 1
    # within tolerance: no flag
    (tmp_path / "BENCH_r02.json").write_text(json.dumps({
        "n": 2, "rc": 0,
        "parsed": {"metric": "m", "value": 1.0, "unit": "u",
                   "extra": {"mfu": 0.39}}}))
    assert not ph.collect(str(tmp_path))["regressions"]
    assert ph.main(["--dir", str(tmp_path), "--check"]) == 0


def _watchdog_record(budget, attempts=1):
    return {"error": "device_init_timeout", "attempts": attempts,
            "goodput": None, "metric": None, "value": None, "unit": None,
            "vs_baseline": None,
            "extra": {"timeout_s": budget, "rc": 75, "workload": "bert",
                      "model": "bert_12_768_12"}}


def test_perf_history_renders_goodput_null_abort_record(tmp_path):
    ph = _ph()
    # the structured rc=75 abort record the deleted bench script's
    # watchdog printed, as the driver's old records hold it
    rec = _watchdog_record(1500)
    (tmp_path / "BENCH_r07.json").write_text(json.dumps(
        {"n": 7, "rc": 75, "parsed": rec}))
    doc = ph.collect(str(tmp_path))
    row = doc["bench_rounds"][0]
    assert row["blind"] and row["reason"] == "device_init_timeout"
    assert "device_init_timeout" in ph.render(doc)


def test_perf_history_renders_retry_attempts(tmp_path):
    """ISSUE 17 satellite: a round that wedged THROUGH the bounded retry
    window renders its attempts count; a single-shot timeout renders as
    never having been given one; pre-retry records (no field) render
    neither."""
    ph = _ph()
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "rc": 75, "parsed": _watchdog_record(900, attempts=2)}))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(
        {"n": 2, "rc": 75, "parsed": _watchdog_record(900)}))
    legacy = _watchdog_record(900)
    legacy.pop("attempts")
    (tmp_path / "BENCH_r03.json").write_text(json.dumps(
        {"n": 3, "rc": 75, "parsed": legacy}))
    doc = ph.collect(str(tmp_path))
    by_round = {r["round"]: r for r in doc["bench_rounds"]}
    assert by_round[1]["attempts"] == 2
    assert by_round[2]["attempts"] == 1
    assert by_round[3]["attempts"] is None
    rendered = ph.render(doc)
    assert "after 2 attempts" in rendered
    assert "(no retry window)" in rendered


def test_bench_gate_embeds_perf_history(tmp_path):
    ph = _ph()
    s = ph.summary(_banked_root(tmp_path))
    assert s["best_banked"]["mfu"] == pytest.approx(0.3789)
    assert s["rounds"] == 5 and s["blind_rounds"] == 3
    assert s["regressions"] == []


def test_perf_history_check_passes_on_the_repo_root():
    """CI runs ``perf_history.py --check`` on the checkout, which keeps no
    BENCH_r*/MULTICHIP_r* records: it must still render (BASELINE.md's
    dated tables) and exit 0."""
    ph = _ph()
    assert not ph.collect_bench(REPO_ROOT)
    assert not ph.collect_multichip(REPO_ROOT)
    assert ph.main(["--dir", REPO_ROOT, "--check"]) == 0
