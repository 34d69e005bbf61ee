"""Fusion-aware autotuner + whole-step capture (ISSUE 12).

Covers the tentpole contract: deterministic search (same space → same
winner twice), the CRC-manifested autotune cache (roundtrip + corrupt
eviction), consult-on-build by BOTH ShardedTrainer and CompiledModel
(ledger site attribution + a graph-level proof the winner's env knob
actually applied), fused whole-step capture (ONE jitted graph per
guarded+scheduled step, MX704/MX708 clean), the LR-schedule fold against
the host-evaluated schedule, the device PrefetchIter (ordering + shutdown
under chaos slow_step) and the recalibrated adaptive watchdog default."""
import json
import os

import jax
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autotune, fault, gluon, io as mx_io, \
    lr_scheduler, parallel
from incubator_mxnet_tpu.analysis import hlo
from incubator_mxnet_tpu.fault import inject, watchdog as watchdog_mod
from incubator_mxnet_tpu.telemetry import compile_log, events as tele_events

from benchmark import autotune as driver


def _batch(n=8, d=16, classes=4, seed=3):
    rng = onp.random.RandomState(seed)
    return (rng.randn(n, d).astype("float32"),
            rng.randint(0, classes, (n,)).astype("float32"))


def _trainer(units=24, in_units=16, classes=4, optimizer_params=None, **kw):
    mx.random.seed(17)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(units, activation="relu", in_units=in_units),
            gluon.nn.Dense(classes, in_units=units))
    net.initialize(mx.init.Xavier())
    kw.setdefault("mesh", parallel.make_mesh(devices=jax.devices()[:1]))
    return parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
        optimizer_params or {"learning_rate": 1e-3}, **kw)


# ---------------------------------------------------------------------------
# AutotuneCache
# ---------------------------------------------------------------------------

class TestAutotuneCache:
    def test_roundtrip(self, tmp_path):
        cache = autotune.AutotuneCache(str(tmp_path))
        cfg = {"env": {"MXTPU_FLASH_BK": "256"}, "geometry": {"batch": 8}}
        path = cache.put("bert", "any", "cpu", cfg, 123.5, meta={"n": 6})
        assert os.path.isfile(path)
        entry = cache.get("bert", "single", "cpu")   # falls back to "any"
        assert entry is not None
        assert entry["config"] == cfg
        assert entry["score"] == 123.5
        assert cache.snapshot()["hits"] == 1

    def test_exact_mesh_key_preferred(self, tmp_path):
        cache = autotune.AutotuneCache(str(tmp_path))
        cache.put("bert", "any", "cpu", {"env": {}}, 1.0)
        cache.put("bert", "dp2tp4", "cpu", {"env": {"MXTPU_FLASH_BK": "128"}},
                  2.0)
        entry = cache.get("bert", "dp2tp4", "cpu")
        assert entry["score"] == 2.0

    def test_corrupt_entry_evicted_as_miss(self, tmp_path):
        cache = autotune.AutotuneCache(str(tmp_path))
        path = cache.put("lenet", "any", "cpu", {"env": {}}, 9.0)
        # flip one byte mid-file: CRC must catch it, the entry must be
        # evicted, and the lookup must read as a miss — never applied
        raw = bytearray(open(path, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        open(path, "wb").write(bytes(raw))
        assert cache.get("lenet", "any", "cpu") is None
        assert not os.path.exists(path)
        assert cache.snapshot()["corrupt"] == 1

    def test_unknown_format_rejected(self, tmp_path):
        cache = autotune.AutotuneCache(str(tmp_path))
        path = cache.entry_path("m", "any", "cpu")
        os.makedirs(os.path.dirname(path))
        with open(path, "w") as f:
            json.dump({"format": 99, "crc": 0}, f)
        assert cache.get("m", "any", "cpu") is None

    def test_applied_respects_user_env(self, tmp_path, monkeypatch):
        entry = {"config": {"env": {"MXTPU_FLASH_BK": "256",
                                    "MXTPU_EMBED_ONEHOT_GRAD": "1"}}}
        monkeypatch.setenv("MXTPU_FLASH_BK", "128")   # operator's pin wins
        monkeypatch.delenv("MXTPU_EMBED_ONEHOT_GRAD", raising=False)
        with autotune.applied(entry) as env:
            assert os.environ["MXTPU_FLASH_BK"] == "128"
            assert os.environ["MXTPU_EMBED_ONEHOT_GRAD"] == "1"
            assert "MXTPU_FLASH_BK" not in env
        assert "MXTPU_EMBED_ONEHOT_GRAD" not in os.environ

    def test_applied_allowlist(self):
        # a hostile/corrupt entry cannot set arbitrary variables
        entry = {"config": {"env": {"PATH": "/evil",
                                    "MXTPU_FLASH_BK": "256"}}}
        with autotune.applied(entry, force=True):
            assert os.environ.get("PATH") != "/evil"
            assert os.environ["MXTPU_FLASH_BK"] == "256"
        assert os.environ.get("MXTPU_FLASH_BK") != "256" \
            or "MXTPU_FLASH_BK" not in os.environ


# ---------------------------------------------------------------------------
# search driver
# ---------------------------------------------------------------------------

class TestSearchDriver:
    def test_deterministic_winner_twice(self, tmp_path):
        """Same space → same winner, same scores — the bankable-search
        property the CI autotune-smoke job relies on."""
        r1 = driver.search("lenet", budget=6)
        r2 = driver.search("lenet", budget=6)
        assert r1["winner"] == r2["winner"]
        assert [row["score"] for row in r1["rows"]] \
            == [row["score"] for row in r2["rows"]]
        assert r1["winner_score"] == r2["winner_score"]

    def test_bert_winner_banked_and_verified(self, tmp_path, monkeypatch):
        before = compile_log.summary()["total"]
        cache = autotune.AutotuneCache(str(tmp_path))
        res = driver.search("bert", budget=4, cache=cache)
        assert res["evaluated"] == 4
        assert res["truncated"] == res["space_size"] - 4
        # zero XLA compiles during the search: candidates are priced on
        # the traced jaxpr only (prepare + make_jaxpr)
        assert compile_log.summary()["total"] == before
        entry = cache.get("bert", "any", "cpu")
        assert entry is not None
        assert entry["config"]["geometry"]  # geometry dims recorded
        assert res["winner_metrics"]["graphs"] == 1   # one train graph
        # ...and the banked bert winner is LOADED by both build sites
        # (the acceptance contract): trainer + CompiledModel consult it
        monkeypatch.setenv("MXTPU_AUTOTUNE_DIR", str(tmp_path))
        trainer, batch, _ = driver._train_probe("bert", res["winner"])
        trainer.prepare(*batch)            # consult happens at build
        assert trainer.autotune_entry is not None
        assert trainer.autotune_entry["score"] == entry["score"]
        from incubator_mxnet_tpu import models
        cm = models.hlo_smoke("bert")["compiled"]
        assert cm.autotune_entry is not None

    def test_candidates_deterministic_order(self):
        full = driver.candidates("bert")
        assert full == driver.candidates("bert")
        assert driver.candidates("bert", 5) == full[:5]

    def test_quantize_dim_searched_and_deterministic(self):
        """quantize ∈ {off, int8} is a real searched dimension: it is
        declared LAST so it varies fastest, and a budget-truncated
        serve-family search still covers both precisions. Same space →
        same winner, same scores, twice."""
        r1 = driver.search("bert_encoder", budget=2)
        r2 = driver.search("bert_encoder", budget=2)
        assert r1["winner"] == r2["winner"]
        assert [row["score"] for row in r1["rows"]] \
            == [row["score"] for row in r2["rows"]]
        assert "quantize" in r1["dims"]
        assert [row["config"]["quantize"] for row in r1["rows"]] \
            == ["off", "int8"]
        # the shipped quantized zoo is MX71x-clean, so both rows are
        # electable and nothing lands in the quant-infeasible bucket
        assert all(row["metrics"]["quant_errors"] == 0
                   for row in r1["rows"])
        assert all(row["feasible"] for row in r1["rows"])
        assert r1["quant_infeasible"] == 0

    def test_mx711_dirty_candidate_never_elected(self, monkeypatch):
        """An int8 candidate whose quantized graphs carry MX71x errors
        is scored and reported but NEVER elected — even when its proxy
        score beats every float candidate (the gate excludes it, not the
        ranking)."""
        real = driver.evaluate

        def dirty(family, cfg):
            m = real(family, cfg)
            if str(cfg.get("quantize", "off")) == "int8":
                m = dict(m, quant_errors=1,
                         tokens_per_step=m["tokens_per_step"] * 1000.0)
            return m

        monkeypatch.setattr(driver, "evaluate", dirty)
        res = driver.search("bert_encoder", budget=4)
        assert res["winner"]["quantize"] == "off"
        assert res["quant_infeasible"] == 2
        int8_rows = [r for r in res["rows"]
                     if r["config"]["quantize"] == "int8"]
        assert int8_rows and not any(r["feasible"] for r in int8_rows)
        assert max(r["score"] for r in int8_rows) > res["winner_score"]


# ---------------------------------------------------------------------------
# consult-on-build (trainer + CompiledModel)
# ---------------------------------------------------------------------------

class TestConsultOnBuild:
    def test_trainer_consults_and_applies(self, tmp_path, monkeypatch):
        """A banked winner changes the TRACED GRAPH of a fresh trainer
        build: bank the one-hot embedding-grad path for a model with an
        Embedding — the tuned build's backward prices extra matmul FLOPs
        (one-hot matmul) vs the untuned scatter-add. Plus ledger site
        attribution: the consult event carries the same site string the
        step's compile is recorded under."""
        def embed_trainer():
            mx.random.seed(23)
            net = gluon.nn.HybridSequential()
            net.add(gluon.nn.Embedding(50, 8),
                    gluon.nn.Dense(4, flatten=True, in_units=8 * 6))
            net.initialize(mx.init.Xavier())
            return parallel.ShardedTrainer(
                net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                {"learning_rate": 0.1},
                mesh=parallel.make_mesh(devices=jax.devices()[:1]),
                autotune_key="embedprobe")
        ids = onp.ones((4, 6), "int32")
        lab = onp.zeros((4,), "float32")
        monkeypatch.delenv("MXTPU_EMBED_ONEHOT_GRAD", raising=False)
        monkeypatch.delenv("MXTPU_AUTOTUNE_DIR", raising=False)
        tr_plain = embed_trainer()
        tr_plain.prepare(ids, lab)
        plain = hlo.cost(tr_plain, sample_args=(ids, lab)).head
        assert tr_plain.autotune_entry is None       # nothing to consult

        cache = autotune.AutotuneCache(str(tmp_path))
        cache.put("embedprobe", "any", autotune.chip_kind(),
                  {"env": {"MXTPU_EMBED_ONEHOT_GRAD": "1"}}, 1.0)
        monkeypatch.setenv("MXTPU_AUTOTUNE_DIR", str(tmp_path))
        tele_events.clear()
        tr_tuned = embed_trainer()
        tr_tuned.step(ids, lab)                      # build + trace + run
        assert tr_tuned.autotune_entry is not None
        tuned = hlo.cost(tr_tuned, sample_args=(ids, lab)).head
        assert tuned.matmul_flops > plain.matmul_flops
        consults = [e for e in tele_events.events("autotune.consult")
                    if e.fields.get("model") == "embedprobe"]
        assert consults and consults[-1].fields["outcome"] == "hit"
        # site attribution: consult site == the compile ledger site the
        # step's compile landed under
        assert consults[-1].fields["site"] == "trainer.step"
        assert compile_log.records("trainer.step")

    def test_compiled_model_consults(self, tmp_path, monkeypatch):
        from incubator_mxnet_tpu import models
        cache = autotune.AutotuneCache(str(tmp_path))
        cache.put("lenet", "any", autotune.chip_kind(),
                  {"env": {"MXTPU_FLASH_BK": "256"}}, 1.0)
        monkeypatch.setenv("MXTPU_AUTOTUNE_DIR", str(tmp_path))
        tele_events.clear()
        smoke = models.hlo_smoke("lenet")
        cm = smoke["compiled"]
        assert cm.autotune_entry is not None
        assert cm.autotune_entry["config"]["env"] == {
            "MXTPU_FLASH_BK": "256"}
        consults = [e for e in tele_events.events("autotune.consult")
                    if e.fields.get("model") == "lenet"]
        assert consults and consults[-1].fields["site"] == "serve.compiled"
        assert consults[-1].fields["outcome"] == "hit"

    def test_consult_off_by_default(self, monkeypatch):
        monkeypatch.delenv("MXTPU_AUTOTUNE_DIR", raising=False)
        assert autotune.consult("trainer.step", "whatever") is None
        monkeypatch.setenv("MXTPU_AUTOTUNE_DIR", "/nonexistent-at-dir")
        monkeypatch.setenv("MXTPU_AUTOTUNE", "0")    # kill switch
        assert autotune.consult("trainer.step", "whatever") is None


# ---------------------------------------------------------------------------
# whole-step capture
# ---------------------------------------------------------------------------

class TestFusedStep:
    def test_one_postwarmup_graph_on_ledger(self):
        """The acceptance contract: a guarded + LR-scheduled fused step
        runs steady state with exactly ONE jitted graph — no
        fault.guards.finite entries, zero post-warmup compiles at
        trainer.step."""
        tr = _trainer(guard=fault.StepGuard(policy="warn"),
                      optimizer_params={
                          "learning_rate": 1e-3,
                          "lr_scheduler": lr_scheduler.CosineScheduler(
                              max_update=100, base_lr=1e-3)})
        x, y = _batch()
        tr.step(x, y)                      # warmup compile
        before_finite = len(compile_log.records("fault.guards.finite"))
        compile_log.mark_warmed("trainer.step")
        for _ in range(3):
            tr.step(x, y)
        assert tr.last_step_graphs == 1
        assert tr._lr_fold                 # schedule folded into the graph
        compile_log.assert_zero_post_warmup("trainer.step")
        # the separate jitted finite check never ran
        assert len(compile_log.records("fault.guards.finite")) \
            == before_finite

    def test_lr_fold_matches_host_schedule(self):
        """Folded LR follows the host scheduler's trajectory: two
        trainers track each other across a moving schedule, one with
        the schedule folded into the step, one whose scheduler is a
        plain callable (no ``jax_lr``: what a custom scheduler is), so
        its LR is evaluated on the host and sent each time it moves."""
        def params(wrap):
            return dict(optimizer_params={
                "learning_rate": 0.05,
                "lr_scheduler": wrap(lr_scheduler.FactorScheduler(
                    step=2, factor=0.5, base_lr=0.05))})

        def plain(inner):
            def sched(num_update):
                return inner(num_update)
            return sched

        x, y = _batch()
        tr_f = _trainer(**params(lambda inner: inner))
        lf = [float(tr_f.step(x, y).asnumpy()) for _ in range(6)]
        assert tr_f._lr_fold
        tr_h = _trainer(**params(plain))
        lh = [float(tr_h.step(x, y).asnumpy()) for _ in range(6)]
        assert not tr_h._lr_fold
        # float32-device vs float64-host schedule eval: tight allclose,
        # first step (schedule still at base) bit-identical
        assert lf[0] == lh[0]
        onp.testing.assert_allclose(lf, lh, rtol=1e-5, atol=1e-7)

    def test_lr_fold_live_base_override(self):
        """A mid-run ``sched.base_lr`` override reaches the folded
        schedule through the lr input — no re-trace."""
        sched = lr_scheduler.FactorScheduler(step=1000, factor=1.0,
                                             base_lr=0.05)
        tr = _trainer(optimizer_params={"learning_rate": 0.05,
                                        "lr_scheduler": sched})
        x, y = _batch()
        tr.step(x, y)
        assert tr._lr_fold and float(tr._lr_dev) == pytest.approx(0.05)
        sigs_before = len(tr._step_sigs)
        sched.base_lr = 0.005
        tr.step(x, y)
        assert float(tr._lr_dev) == pytest.approx(0.005)
        assert len(tr._step_sigs) == sigs_before     # same compiled graph

    def test_jax_lr_matches_python_schedulers(self):
        import jax.numpy as jnp
        scheds = [
            lr_scheduler.FactorScheduler(step=3, factor=0.7, base_lr=0.1,
                                         warmup_steps=4,
                                         warmup_begin_lr=0.01),
            lr_scheduler.MultiFactorScheduler(step=[3, 7], factor=0.5,
                                              base_lr=0.2),
            lr_scheduler.PolyScheduler(max_update=20, base_lr=0.3, pwr=2),
            lr_scheduler.CosineScheduler(max_update=20, base_lr=0.3,
                                         final_lr=0.01, warmup_steps=3),
            lr_scheduler.LinearWarmUp(
                lr_scheduler.CosineScheduler(max_update=20, base_lr=0.3),
                start_lr=0.0, length=5),
        ]
        for s in scheds:
            for t in (0, 1, 3, 5, 10, 25):
                got = float(s.jax_lr(jnp.asarray(t, jnp.int32)))
                want = float(s(t))
                assert got == pytest.approx(want, rel=1e-5, abs=1e-7), \
                    (type(s).__name__, t)

    def test_fused_mesh_step_mx704_mx708_clean(self):
        """No non-donated >=64KiB buffer and no host callback survives
        whole-step capture on a real mesh (the MX704/MX708 gate)."""
        mx.random.seed(29)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(256, activation="relu", in_units=64),
                gluon.nn.Dense(8, in_units=256))
        net.initialize(mx.init.Xavier())
        tr = parallel.ShardedTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "adamw",
            {"learning_rate": 1e-3,
             "lr_scheduler": lr_scheduler.CosineScheduler(
                 max_update=100, base_lr=1e-3)},
            mesh=parallel.make_mesh(dp=4, tp=2),
            guard=fault.StepGuard(policy="warn"))
        rng = onp.random.RandomState(1)
        x = rng.randn(16, 64).astype("float32")
        y = rng.randint(0, 8, (16,)).astype("float32")
        tr.prepare(x, y)                   # build WITHOUT dispatching
        rep = hlo.verify(tr, sample_args=(x, y))
        bad = [f for f in rep.errors + rep.warnings
               if "MX704" in str(f) or "MX708" in str(f)]
        assert bad == [], bad

    def test_prepare_compiles_nothing(self):
        before = compile_log.summary()["total"]
        tr = _trainer()
        x, y = _batch()
        tr.prepare(x, y)
        assert compile_log.summary()["total"] == before
        # and the prepared graph is traceable offline
        rep = hlo.cost(tr, sample_args=(x, y))
        assert rep.model_flops_per_step() > 0

    def test_guard_rollback_still_works_fused(self):
        """The rollback decision stays on host: a NaN batch under
        skip_and_rollback restores the snapshot exactly as before."""
        tr = _trainer(guard=fault.StepGuard(policy="skip_and_rollback"))
        x, y = _batch()
        tr.step(x, y)
        t_before = tr.num_update
        bad = onp.full_like(x, onp.nan)
        with pytest.warns(UserWarning):
            tr.step(bad, y)
        assert tr.num_update == t_before   # step rolled back
        assert tr.guard.skipped == 1


# ---------------------------------------------------------------------------
# PrefetchIter
# ---------------------------------------------------------------------------

class TestPrefetchIter:
    def _base(self, n=12, bs=4):
        data = onp.arange(n * 3, dtype="float32").reshape(n, 3)
        label = (onp.arange(n) % 2).astype("float32")
        return mx_io.NDArrayIter(data, label, batch_size=bs)

    def test_ordering_and_exhaustion(self):
        placed = []

        def place(b):
            placed.append(float(b.data[0].asnumpy()[0, 0]))
            return b
        it = mx_io.PrefetchIter(self._base(), place=place)
        seen = [float(b.data[0].asnumpy()[0, 0]) for b in it]
        assert seen == sorted(seen) == placed[:len(seen)]
        assert len(seen) == 3
        # exhausted is exhausted: further next() keeps raising instead
        # of blocking forever on the producer-less queue
        with pytest.raises(StopIteration):
            it.next()
        with pytest.raises(StopIteration):
            it.next()
        it.reset()                              # ...and reset revives it
        assert len(list(it)) == 3
        it.close()

    def test_place_runs_on_worker_thread(self):
        import threading
        names = []

        def place(b):
            names.append(threading.current_thread().name)
            return b
        with mx_io.PrefetchIter(self._base(), place=place) as it:
            it.next()
        assert set(names) == {"mx-io-device-prefetch"}

    def test_device_placement_overlap(self):
        """The documented trainer wiring: worker-placed batches feed
        step() directly."""
        tr = _trainer(in_units=3, classes=2, units=8)
        it = mx_io.PrefetchIter(
            self._base(), place=lambda b: tr.place(*b.data, *b.label))
        n = 0
        for placed in it:
            assert all(isinstance(v, jax.Array) for v in placed)
            tr.step(*placed)
            n += 1
        assert n == 3
        it.close()

    def test_error_propagates(self):
        def boom(b):
            raise ValueError("placement exploded")
        it = mx_io.PrefetchIter(self._base(), place=boom)
        with pytest.raises(ValueError, match="placement exploded"):
            it.next()
        # a retried next() re-raises (no deadlock on the dead worker)
        with pytest.raises(ValueError, match="placement exploded"):
            it.next()
        it.close()

    def test_reset_restarts_stream(self):
        it = mx_io.PrefetchIter(self._base())
        first = float(it.next().data[0].asnumpy()[0, 0])
        it.next()
        it.reset()
        again = float(it.next().data[0].asnumpy()[0, 0])
        assert first == again
        it.close()

    @pytest.mark.chaos
    def test_ordering_and_shutdown_under_chaos_slow_step(self):
        """With slow_step chaos firing in the consumer, prefetched
        batches still arrive in order, and close() mid-stream joins the
        named worker cleanly (no orphan thread)."""
        import threading
        with inject.chaos(seed=5, slow_prob=1.0, delay_s=0.005):
            it = mx_io.PrefetchIter(self._base(n=24, bs=4), depth=2)
            seen = []
            for _ in range(3):                 # consume half, slowly
                inject.maybe_delay("slow_step")
                seen.append(float(it.next().data[0].asnumpy()[0, 0]))
            assert seen == sorted(seen)
            it.close()
        assert not any(t.name == "mx-io-device-prefetch"
                       for t in threading.enumerate())
        with pytest.raises(mx.MXNetError):
            it.next()                          # closed is closed


# ---------------------------------------------------------------------------
# watchdog recalibration
# ---------------------------------------------------------------------------

class TestWatchdogRecalibration:
    def test_adaptive_default(self):
        wd = fault.Watchdog()
        assert wd.deadline is None
        # warmup headroom before any observation (first-step compile)
        assert wd.deadline_for_step() == watchdog_mod.WARMUP_DEADLINE_S
        wd.observe(0.0007)                  # the 0.7ms fused step
        # recalibrated: floored, nowhere near the 40ms-era constants
        assert wd.deadline_for_step() == watchdog_mod.ADAPTIVE_FLOOR_S
        wd2 = fault.Watchdog()
        wd2.observe(1.0)
        assert wd2.deadline_for_step() == pytest.approx(
            watchdog_mod.ADAPTIVE_MULT * 1.0)

    def test_explicit_deadline_unchanged(self):
        wd = fault.Watchdog(deadline=0.2)
        wd.observe(5.0)
        assert wd.deadline_for_step() == 0.2

    def test_fixed_deadline_still_trips(self):
        import time
        # the firing path is unchanged by the recalibration — an
        # explicit tiny deadline keeps the stall test fast; adaptive
        # clamping itself is covered above
        wd_fast = fault.Watchdog(deadline=0.05)
        with pytest.warns(UserWarning, match="watchdog"):
            with wd_fast.watch(step=2):
                time.sleep(0.15)

    def test_clean_steps_feed_ema_via_watch(self):
        import time
        wd = fault.Watchdog()
        # the FIRST watched step is the compile — adaptive mode discards
        # it, so a 2-minute warmup can never seed a 100-minute deadline
        with wd.watch(step=1):
            time.sleep(0.05)
        assert wd._ema_s is None
        with wd.watch(step=2):
            time.sleep(0.002)
        assert wd._ema_s is not None
        assert 0.002 <= wd._ema_s < 0.05

