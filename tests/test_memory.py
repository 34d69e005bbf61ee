"""Device-memory observability (ISSUE 13): the static liveness scan
(``analysis.hlo.cost.peak_live_bytes`` + the MX709 budget pass), the
runtime ``telemetry.memory`` ledger (sampling, per-site attribution,
leak watchdog), OOM forensics (one flight bundle per
RESOURCE_EXHAUSTED, rendered by ``tools/postmortem.py``), the serve
staging memory preflight, and the autotune feasibility constraint."""
import json
import os
import sys

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, telemetry
from incubator_mxnet_tpu.base import MXNetError
from incubator_mxnet_tpu.fault import inject
from incubator_mxnet_tpu.telemetry import flight
from incubator_mxnet_tpu.telemetry import memory as tmemory

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_ledger():
    tmemory.reset()
    telemetry.clear()
    yield
    tmemory.stop()
    tmemory.reset()


def _mlp(units=16, in_units=32, prefix="memmlp_"):
    net = gluon.nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(gluon.nn.Dense(units, activation="relu", in_units=in_units))
        net.add(gluon.nn.Dense(8, in_units=units))
    net.initialize()
    net.hybridize()
    net(mx.nd.array(onp.zeros((2, in_units), "float32")))
    return net


# ---------------------------------------------------------------------------
# static: the liveness scan
# ---------------------------------------------------------------------------

class TestLiveness:
    def test_serve_family_peaks_deterministic(self):
        # two independent builds of the same zoo family price to the
        # SAME peak — the property the MX709 budget gate needs
        from incubator_mxnet_tpu import models
        from incubator_mxnet_tpu.analysis import hlo
        reps = [hlo.cost(models.hlo_smoke("lenet")["compiled"],
                         max_graphs=8) for _ in range(2)]
        assert reps[0].peak_live_bytes() == reps[1].peak_live_bytes() > 0
        assert reps[0].ladder_peak_bytes() == reps[1].ladder_peak_bytes()
        assert reps[0].to_dict() == reps[1].to_dict()
        # residency vs traffic: a row's peak counts params (resident)
        # and is present on every row
        for r in reps[0].rows:
            assert r.peak_live_bytes >= r.param_bytes > 0
            assert r.to_dict()["peak_live_bytes"] == r.peak_live_bytes

    def test_donation_credit(self):
        # a donated input dies at its last use; the same graph without
        # donation keeps the buffer resident for the whole call
        import jax
        import jax.numpy as jnp
        from incubator_mxnet_tpu.analysis import hlo

        def f(x):
            y = x + 1.0
            return (y * 3.0).sum()

        x = jnp.zeros((256, 1024), "float32")
        g_no = hlo.trace_entry(jax.jit(f), (x,)).graphs[0]
        g_don = hlo.trace_entry(jax.jit(f, donate_argnums=0),
                                (x,)).graphs[0]
        assert g_don.donated == (True,)
        assert hlo.peak_live_bytes(g_don) < hlo.peak_live_bytes(g_no)

    def test_guarded_fused_trainer_peak_deterministic(self):
        # the guarded+scheduled whole-step graph reports one
        # deterministic peak (acceptance: "a guarded fused train step
        # reports deterministic peak_live_bytes"); prepare() builds the
        # step without dispatching, so this never XLA-compiles
        import jax
        from incubator_mxnet_tpu import fault, lr_scheduler, parallel
        from incubator_mxnet_tpu.analysis import hlo

        def build():
            mx.random.seed(11)
            net = _mlp(prefix="memfused_%d_" % build.n)
            build.n += 1
            loss = gluon.loss.SoftmaxCrossEntropyLoss()
            tr = parallel.ShardedTrainer(
                net, lambda out, label: loss(out, label), "adamw",
                {"learning_rate": 1e-3,
                 "lr_scheduler": lr_scheduler.CosineScheduler(
                     max_update=100, base_lr=1e-3)},
                mesh=parallel.make_mesh(devices=jax.devices()[:1]),
                guard=fault.StepGuard(policy="warn"))
            return tr
        build.n = 0
        rng = onp.random.RandomState(0)
        x = rng.rand(4, 32).astype("float32")
        y = rng.randint(0, 8, (4,)).astype("float32")
        peaks = []
        for _ in range(2):
            tr = build()
            tr.prepare(x, y)
            peaks.append(hlo.cost(tr, sample_args=(x, y)).peak_live_bytes())
        assert peaks[0] == peaks[1] > 0

    def test_mx709_ladder_flagged_when_buckets_fit_alone(self, monkeypatch):
        # every bucket fits the budget alone, the summed ladder does not
        # -> ONE aggregated MX709 on <entry>[ladder]
        from incubator_mxnet_tpu import serve
        from incubator_mxnet_tpu.analysis import hlo
        net = _mlp(prefix="memladder_")
        cm = serve.CompiledModel(net, serve.BucketTable({"batch": (1, 4)}),
                                 [{0: "batch"}])
        traced = hlo.trace_entry(cm, max_graphs=8)
        peaks = [hlo.peak_live_bytes(g) for g in traced.graphs]
        ladder = hlo.ladder_peak_bytes(traced.graphs)
        assert len(peaks) >= 2 and ladder > max(peaks)
        budget = max(peaks)          # each graph fits, the ladder cannot
        rep = hlo.verify(cm, max_graphs=8, hbm_budget_bytes=budget)
        hits = [d for d in rep if d.code == "MX709"]
        assert len(hits) == 1 and "[ladder]" in hits[0].node
        assert hits[0].severity == "error"

    def test_mxlint_cost_row_carries_peak(self, capsys):
        # the --cost JSON rows CI consumes carry the new key
        from tools import mxlint
        rc = mxlint.main(["--hlo", "lenet", "--cost", "--format=json",
                          "-q"])
        assert rc == 0
        rows = [json.loads(line) for line in
                capsys.readouterr().out.splitlines() if line]
        cost_rows = [r for r in rows if r.get("kind") == "cost"]
        assert cost_rows and all(r["peak_live_bytes"] > 0
                                 for r in cost_rows)


# ---------------------------------------------------------------------------
# runtime: the ledger
# ---------------------------------------------------------------------------

class TestLedger:
    def test_sample_publishes_gauges_and_sites(self):
        calls = []

        def provider():
            calls.append(1)
            return 12345

        unregister = tmemory.register_site("test.site", provider)
        try:
            rec = tmemory.sample()
            assert rec["live_arrays"] >= 0
            assert rec["sites"]["test.site"] == 12345
            table = telemetry.metrics.REGISTRY.to_dict()
            assert "mxtpu_memory_live_bytes" in table
            assert any("test.site" in labels for labels in
                       table["mxtpu_memory_site_bytes"])
        finally:
            unregister()
        assert calls
        assert "test.site" not in tmemory.sample()["sites"]

    def test_trainer_registers_site_and_step_report_segment(self):
        import jax
        from incubator_mxnet_tpu import parallel, profiler
        mx.random.seed(3)
        net = _mlp(prefix="memsite_")
        loss = gluon.loss.SoftmaxCrossEntropyLoss()
        tr = parallel.ShardedTrainer(
            net, lambda out, label: loss(out, label), "sgd",
            {"learning_rate": 0.1},
            mesh=parallel.make_mesh(devices=jax.devices()[:1]))
        x = onp.zeros((4, 32), "float32")
        y = onp.zeros((4,), "float32")
        tr.step(x, y).asnumpy()
        rec = tmemory.sample()
        assert rec["sites"].get("trainer.step", 0) == tr._resident_bytes() \
            > 0
        # the profiler's step report carries the memory segment
        rep = profiler.step_report(frame="step")
        assert rep["memory"]["live_bytes"] >= 0
        assert "trainer.step" in rep["memory"]["sites"]

    def test_snapshot_is_a_pure_read(self):
        # snapshot-driven pollers (monitoring loops, flight dumps) must
        # not feed the watchdog window or emit events as a side effect
        for _ in range(20):
            tmemory.snapshot()
        assert tmemory.snapshot()["history"] == []
        assert telemetry.get_events("memory.leak") == []

    def test_vanished_site_gauge_reads_zero(self):
        unregister = tmemory.register_site("ephemeral.site", lambda: 999)
        tmemory.sample()
        unregister()
        tmemory.sample()
        table = telemetry.metrics.REGISTRY.to_dict()
        vals = {k: v for k, v in
                table["mxtpu_memory_site_bytes"].items()
                if "ephemeral.site" in k}
        assert list(vals.values()) == [0.0], vals

    def test_stable_residency_never_flags_leak(self):
        buf = onp.zeros(1024, "float32")  # noqa: F841 — pinned, constant
        for _ in range(12):
            tmemory.sample()
        assert telemetry.get_events("memory.leak") == []

    @pytest.mark.chaos
    def test_leak_watchdog_flags_injected_slow_leak(self):
        # fault.inject's leak site retains device arrays; a full window
        # of monotonic growth emits the damped memory.leak warning the
        # CI memory smoke forbids
        with inject.chaos(seed=5, leak=1.0, leak_bytes=1 << 20):
            for _ in range(10):
                inject.maybe_leak("trainer.step")
                tmemory.sample()
        evs = telemetry.get_events("memory.leak")
        assert evs, "leak watchdog never fired"
        f = evs[0].fields
        assert f["growth_bytes"] >= tmemory._LEAK_MIN_BYTES
        assert f["window_samples"] == tmemory._LEAK_WINDOW
        assert evs[0].severity == "warning"
        # damped: continuous leaking re-flags per ~1MiB of NEW growth,
        # never once per sample
        assert len(evs) <= 4

    def test_context_aliases_read_the_ledger(self, monkeypatch):
        # pure-CPU runs have no PjRt memory_stats: the reference aliases
        # now fall back to the ledger instead of raising
        import jax
        import jax.numpy as jnp
        held = jnp.zeros((1024,), "float32")
        free, total = mx.tpu_memory_info(0)
        assert total >= held.nbytes and free >= 0
        stats = mx.context.memory_stats(0)
        assert stats["source"] == "ledger"
        assert stats["bytes_in_use"] >= held.nbytes
        monkeypatch.setenv("MXTPU_HBM_BUDGET", "64M")
        free, total = mx.gpu_memory_info(0)
        assert total == 64 << 20 and free == total - \
            mx.telemetry.memory.device_bytes(jax.devices()[0])

    def test_parse_size_forms(self):
        from incubator_mxnet_tpu.util import parse_size
        assert parse_size("16e9") == 16_000_000_000
        assert parse_size("512M") == 512 << 20
        assert parse_size("2GiB") == 2 << 30
        with pytest.raises(ValueError):
            parse_size("chips")


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

class TestOomForensics:
    def test_one_bundle_rendered_by_postmortem(self, tmp_path, capsys):
        flight.set_dir(str(tmp_path))
        flight.reset()
        try:
            tmemory.note_static_peak("serve:mlp", 123 << 20)
            exc = RuntimeError("RESOURCE_EXHAUSTED: Out of memory "
                               "allocating 9876543 bytes")
            assert tmemory.is_oom(exc)
            path = tmemory.record_oom(exc, site="trainer.step", step=41)
            assert path and os.path.exists(path)
            # deduped on the exception object: nested oom_guard layers
            # re-raising the SAME error add no second bundle
            assert tmemory.record_oom(exc, site="trainer.step") is None
            assert len(flight.list_bundles(str(tmp_path))) == 1
            doc = flight.load(path)
            assert doc["reason"] == "resource_exhausted"
            mem = doc["memory"]
            assert mem["static_peaks"]["serve:mlp"] == 123 << 20
            assert "current" in mem and "history" in mem
            from tools import postmortem
            assert postmortem.main([path]) == 0
            out = capsys.readouterr().out
            assert "device memory" in out and "static peak" in out
            assert "resource_exhausted" in out
        finally:
            flight.set_dir(None)

    @pytest.mark.chaos
    def test_trainer_oom_guard_writes_bundle(self, tmp_path):
        import jax
        from incubator_mxnet_tpu import parallel
        mx.random.seed(4)
        net = _mlp(prefix="memoom_")
        loss = gluon.loss.SoftmaxCrossEntropyLoss()
        tr = parallel.ShardedTrainer(
            net, lambda out, label: loss(out, label), "sgd",
            {"learning_rate": 0.1},
            mesh=parallel.make_mesh(devices=jax.devices()[:1]))
        x = onp.zeros((4, 32), "float32")
        y = onp.zeros((4,), "float32")
        tr.step(x, y).asnumpy()              # build + warm
        flight.set_dir(str(tmp_path))
        flight.reset()
        try:
            def boom(*a, **k):
                raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory "
                                   "while trying to allocate 1 GiB")
            tr._step_fn = boom
            with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
                tr.step(x, y)
            bundles = flight.list_bundles(str(tmp_path))
            assert len(bundles) == 1
            doc = flight.load(bundles[0])
            assert doc["reason"] == "resource_exhausted"
            assert doc["site"] == "trainer.step"
            evs = telemetry.get_events("memory.oom")
            assert evs and evs[-1].fields["site"] == "trainer.step"
        finally:
            flight.set_dir(None)

    def test_non_oom_errors_pass_through_unrecorded(self, tmp_path):
        flight.set_dir(str(tmp_path))
        flight.reset()
        try:
            with pytest.raises(ValueError):
                with tmemory.oom_guard("serve.compiled"):
                    raise ValueError("an ordinary bug")
            assert flight.list_bundles(str(tmp_path)) == []
        finally:
            flight.set_dir(None)


# ---------------------------------------------------------------------------
# gating: serve staging preflight + autotune feasibility
# ---------------------------------------------------------------------------

class TestStagingPreflight:
    def test_over_budget_ladder_rejected_active_keeps_serving(
            self, monkeypatch):
        from incubator_mxnet_tpu import serve
        registry = serve.ModelRegistry()
        table = serve.BucketTable({"batch": (1, 4)})

        def factory():
            return _mlp(prefix="mempre1_")

        v1 = registry.load("mlp", table=table, input_axes=[{0: "batch"}],
                           factory=factory, warmup=False)
        assert registry.active_version("mlp") == v1.version
        v1_peak = tmemory.static_peaks()["serve:mlp"]
        assert v1_peak > 0
        # now stage a BIGGER v2 under a budget its ladder cannot fit
        monkeypatch.setenv("MXTPU_HBM_BUDGET", "4K")
        telemetry.clear()
        with pytest.raises(MXNetError, match="MX709|ladder"):
            registry.load("mlp", table=table, input_axes=[{0: "batch"}],
                          factory=lambda: _mlp(units=64,
                                               prefix="mempre2_"),
                          warmup=False)
        # the active version is untouched and still serves
        assert registry.active_version("mlp") == v1.version
        assert registry.models() == {"mlp": [v1.version]}
        # the preflight event carries the ladder + budget
        evs = telemetry.get_events("serve.memory")
        assert evs
        f = evs[-1].fields
        assert f["hbm_budget"] == 4 << 10
        assert f["ladder_peak_bytes"] > f["hbm_budget"]
        # the REJECTED candidate must not overwrite the serving
        # version's noted prediction (OOM forensics shows v1's number)
        assert f["ladder_peak_bytes"] != v1_peak
        assert tmemory.static_peaks()["serve:mlp"] == v1_peak

    def test_generous_budget_loads_clean(self, monkeypatch):
        from incubator_mxnet_tpu import serve
        monkeypatch.setenv("MXTPU_HBM_BUDGET", "1G")
        registry = serve.ModelRegistry()
        v = registry.load("mlp",
                          table=serve.BucketTable({"batch": (1, 2)}),
                          input_axes=[{0: "batch"}],
                          factory=lambda: _mlp(prefix="mempre3_"),
                          warmup=False)
        assert registry.active_version("mlp") == v.version


class TestAutotuneFeasibility:
    def test_infeasible_candidates_never_elected(self, monkeypatch):
        from benchmark import autotune as at
        # unconstrained winner over the lenet batch dim (2, 4, 8)
        free = at.search("lenet")
        assert free["infeasible"] == 0
        metrics = sorted((r["metrics"]["ladder_peak_bytes"],
                          r["config"]["batch"]) for r in free["rows"])
        # budget below the biggest candidate's residency but above the
        # smallest: the search must elect a feasible winner and report
        # the exclusion (no silent caps)
        assert metrics[0][0] < metrics[-1][0]
        budget = metrics[-1][0] - 1
        monkeypatch.setenv("MXTPU_HBM_BUDGET", str(budget))
        gated = at.search("lenet")
        assert gated["infeasible"] >= 1
        assert gated["hbm_budget"] == budget
        winner_rows = [r for r in gated["rows"]
                       if r["config"] == gated["winner"]]
        assert winner_rows[0]["feasible"]
        assert winner_rows[0]["metrics"]["ladder_peak_bytes"] <= budget
        # nothing feasible -> a loud error, not a silent OOM proposal
        monkeypatch.setenv("MXTPU_HBM_BUDGET", "1K")
        with pytest.raises(RuntimeError, match="MXTPU_HBM_BUDGET"):
            at.search("lenet")

    def test_same_budget_same_winner_twice(self, monkeypatch):
        from benchmark import autotune as at
        monkeypatch.setenv("MXTPU_HBM_BUDGET", "1G")
        a = at.search("lenet", budget=2)
        b = at.search("lenet", budget=2)
        assert a["winner"] == b["winner"]
        assert a["winner_metrics"] == b["winner_metrics"]
