"""mx.serve — compiled inference engine + serving runtime tests.

Covers the ISSUE 3 acceptance surface: bucket-table correctness (padding
masked out of results), ZERO post-warmup recompiles asserted via the
compile-cache counters, batcher deadline + backpressure behavior, registry
version swap under a chaos-injected failed load, and a TCP smoke test.
"""
import json
import os
import threading
import time

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, models, nd, serve
from incubator_mxnet_tpu import profiler
from incubator_mxnet_tpu.fault import checkpoint as fault_checkpoint
from incubator_mxnet_tpu.fault import inject

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")


# ---------------------------------------------------------------------------
# BucketTable
# ---------------------------------------------------------------------------
class TestBucketTable:
    def test_pow2_ladder_and_rounding(self):
        t = serve.BucketTable({"batch": (1, 8)})
        assert t.sizes("batch") == [1, 2, 4, 8]
        assert [t.bucket("batch", n) for n in (1, 2, 3, 5, 8)] \
            == [1, 2, 4, 8, 8]

    def test_non_pow2_max_closes_ladder(self):
        t = serve.BucketTable({"seq": (8, 48)})
        assert t.sizes("seq") == [8, 16, 32, 48]
        assert t.bucket("seq", 33) == 48

    def test_overflow_raises(self):
        t = serve.BucketTable({"batch": (1, 4)})
        with pytest.raises(serve.BucketOverflow):
            t.bucket("batch", 5)

    def test_assignments_cross_product(self):
        t = serve.BucketTable({"batch": (1, 2), "seq": (8, 16)})
        got = list(t.assignments())
        assert len(got) == t.num_buckets() == 4
        assert {"batch": 1, "seq": 8} in got
        assert {"batch": 2, "seq": 16} in got

    def test_unknown_axis_and_bad_range(self):
        t = serve.BucketTable({"batch": (1, 4)})
        with pytest.raises(mx.MXNetError):
            t.bucket("seq", 3)
        with pytest.raises(mx.MXNetError):
            serve.BucketTable({"batch": (4, 2)})


# ---------------------------------------------------------------------------
# satellite: profiler spans + Percentile metric
# ---------------------------------------------------------------------------
def test_profiler_spans_recorded_in_dumps(tmp_path):
    profiler.set_config(filename=str(tmp_path / "serve_prof.json"))
    profiler.reset_spans()
    with profiler.Scope("unit_scope"):
        time.sleep(0.002)
    t = profiler.Task("unit_task")
    t.start()
    time.sleep(0.001)
    t.stop()
    profiler.Marker("unit_marker").mark("test")
    doc = json.loads(profiler.dumps())
    assert "xprof" in doc["trace_dir"]
    assert doc["spans"]["unit_scope"]["count"] == 1
    assert doc["spans"]["unit_scope"]["total_ms"] >= 1.0
    assert doc["spans"]["unit_task"]["kind"] == "task"
    for q in ("p50_ms", "p95_ms", "p99_ms", "mean_ms"):
        assert q in doc["spans"]["unit_scope"]
    assert doc["markers"][0]["name"] == "unit_marker"
    # reset=True clears the recorder
    profiler.dumps(reset=True)
    assert json.loads(profiler.dumps())["spans"] == {}


def test_percentile_metric():
    m = mx.metric.Percentile(q=(50, 99), name="lat")
    m.update(None, [onp.arange(1, 101, dtype="float64")])
    names, vals = m.get()
    assert names == ["lat_p50", "lat_p99", "lat_mean"]
    assert vals[0] == pytest.approx(50, abs=2)
    assert vals[1] == pytest.approx(99, abs=2)
    assert vals[2] == pytest.approx(50.5)


# ---------------------------------------------------------------------------
# CompiledModel
# ---------------------------------------------------------------------------
def _mlp(prefix="srvmlp_"):
    net = gluon.nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(gluon.nn.Dense(16, activation="relu", in_units=8))
        net.add(gluon.nn.Dense(4, in_units=16))
    net.initialize()
    return net


class TestCompiledModel:
    def test_padding_masked_and_zero_recompiles(self):
        net = _mlp()
        x = nd.array(onp.random.RandomState(0).randn(2, 8).astype("float32"))
        table = serve.BucketTable({"batch": (1, 8)})
        cm = serve.CompiledModel(net, table, [{0: "batch"}],
                                 example_args=(x,))
        warm = cm.warmup()
        assert warm["compiled"] == table.num_buckets() == 4
        net.hybridize(False)  # eager reference
        rng = onp.random.RandomState(1)
        for b in (1, 2, 3, 5, 7, 8):
            xb = rng.randn(b, 8).astype("float32")
            got = cm.predict(xb)
            assert got.shape == (b, 4)  # padding sliced off
            onp.testing.assert_allclose(got.asnumpy(),
                                        net(nd.array(xb)).asnumpy(),
                                        rtol=1e-5, atol=1e-5)
        info = cm.cache_info()
        assert info["post_warmup_compiles"] == 0
        assert info["hits"] == 6 and info["misses"] == 0

    def test_miss_counted_without_warmup(self):
        net = _mlp(prefix="srvmlp2_")
        x = nd.array(onp.zeros((2, 8), "float32"))
        cm = serve.CompiledModel(net, serve.BucketTable({"batch": (1, 4)}),
                                 [{0: "batch"}], example_args=(x,))
        cm.predict(onp.zeros((3, 8), "float32"))
        info = cm.cache_info()
        assert info["misses"] == 1 and info["compiles"] == 1
        # the same bucket again is a hit
        cm.predict(onp.zeros((4, 8), "float32"))
        assert cm.cache_info()["hits"] == 1

    def test_overflow_propagates(self):
        net = _mlp(prefix="srvmlp3_")
        x = nd.array(onp.zeros((2, 8), "float32"))
        cm = serve.CompiledModel(net, serve.BucketTable({"batch": (1, 2)}),
                                 [{0: "batch"}], example_args=(x,))
        with pytest.raises(serve.BucketOverflow):
            cm.predict(onp.zeros((3, 8), "float32"))

    def test_refresh_params_swaps_weights_without_recompile(self):
        net = _mlp(prefix="srvmlp4_")
        x = onp.random.RandomState(0).randn(2, 8).astype("float32")
        cm = serve.CompiledModel(net, serve.BucketTable({"batch": (1, 2)}),
                                 [{0: "batch"}],
                                 example_args=(nd.array(x),))
        cm.warmup()
        before = cm.predict(x).asnumpy()
        for _, p in net.collect_params().items():
            p.set_data(p.data() * 0)
        cm.refresh_params()
        after = cm.predict(x).asnumpy()
        assert abs(after).sum() == 0.0 and abs(before).sum() > 0.0
        assert cm.cache_info()["post_warmup_compiles"] == 0


@pytest.mark.slow
def test_bert_seq_bucketing_padding_masked():
    """Padded batch+seq results match the unpadded eager forward on the
    valid rows/positions (attention masks the pad)."""
    net = models.get_bert("bert_2_128_2", vocab_size=60, max_length=32,
                          dropout=0.1, use_decoder=False,
                          use_classifier=False, num_layers=1)
    net.initialize()
    net.hybridize()
    rng = onp.random.RandomState(0)
    ids = nd.array(rng.randint(1, 60, (2, 12)).astype("int32"))
    tt = nd.array(onp.zeros((2, 12), "int32"))
    vl = nd.array(onp.full((2,), 12, "float32"))
    net(ids, tt, vl)
    table = serve.BucketTable({"batch": (1, 2), "seq": (8, 16)})
    spec = models.serve_spec("bert_encoder")
    cm = serve.CompiledModel(net, table, spec["input_axes"],
                             output_axes=spec["output_axes"],
                             pad_values=spec["pad_values"])
    cm.warmup()
    B, L = 2, 11  # odd shapes -> bucket (2, 16)
    ids2 = rng.randint(1, 60, (B, L)).astype("int32")
    tt2 = onp.zeros((B, L), "int32")
    vl2 = onp.full((B,), L, "float32")
    seq, pooled = cm.predict(ids2, tt2, vl2)
    assert seq.shape == (B, L, 128)
    net.hybridize(False)
    from incubator_mxnet_tpu import autograd
    with autograd.pause(train_mode=False):
        wseq, wpooled = net(nd.array(ids2), nd.array(tt2), nd.array(vl2))
    onp.testing.assert_allclose(seq.asnumpy(), wseq.asnumpy(),
                                rtol=2e-4, atol=2e-4)
    onp.testing.assert_allclose(pooled.asnumpy(), wpooled.asnumpy(),
                                rtol=2e-4, atol=2e-4)
    assert cm.cache_info()["post_warmup_compiles"] == 0


# ---------------------------------------------------------------------------
# satellite: export/load round-trip for cold registry loads
# ---------------------------------------------------------------------------
class TestExportRoundTrip:
    def test_multi_signature_export_dispatch(self, tmp_path):
        net = _mlp(prefix="srvexp_")
        net.hybridize()
        x = nd.array(onp.random.RandomState(0).randn(2, 8).astype("float32"))
        net(x)
        net(x)
        sigs = [[((b, 8), "float32")] for b in (1, 2, 4)]
        sf, pf = net.export(str(tmp_path / "mlp"), signatures=sigs)
        blk = gluon.SymbolBlock.imports(sf, ["data"], pf)
        assert len(blk.signatures()) == 3
        net.hybridize(False)
        for b in (1, 2, 4):
            xb = onp.random.RandomState(b).randn(b, 8).astype("float32")
            onp.testing.assert_allclose(blk(nd.array(xb)).asnumpy(),
                                        net(nd.array(xb)).asnumpy(),
                                        rtol=1e-5, atol=1e-5)
        with pytest.raises(mx.MXNetError, match="no exported graph"):
            blk(nd.array(onp.zeros((3, 8), "float32")))

    def test_symbolblock_load_parameters_refreshes(self, tmp_path):
        net = _mlp(prefix="srvexp2_")
        net.hybridize()
        x = nd.array(onp.ones((2, 8), "float32"))
        net(x)
        sf, pf = net.export(str(tmp_path / "m"))
        blk = gluon.SymbolBlock.imports(sf, ["data"], pf)
        want = blk(x).asnumpy()
        blk.load_parameters(pf)  # previously raised AssertionError
        onp.testing.assert_allclose(blk(x).asnumpy(), want, rtol=1e-6)

    def test_set_weights_accepts_training_prefix_names(self, tmp_path):
        net = _mlp(prefix="srvexp3_")
        net.hybridize()
        x = nd.array(onp.ones((2, 8), "float32"))
        net(x)
        sf, pf = net.export(str(tmp_path / "m"))
        blk = gluon.SymbolBlock.imports(sf, ["data"], pf)
        swap = {p.name: onp.zeros(p.shape, "float32")
                for _, p in net.collect_params().items()}
        blk.set_weights(swap)  # training-time prefix names
        assert abs(blk(x).asnumpy()).sum() == 0.0
        with pytest.raises(mx.MXNetError, match="not a parameter"):
            blk.set_weights({"nope_weight": onp.zeros((1,))})
        with pytest.raises(mx.MXNetError, match="shape mismatch"):
            blk.set_weights({next(iter(swap)): onp.zeros((3, 3))},
                            allow_missing=True)

    def test_lenet_cold_serving_round_trip(self, tmp_path):
        net = models.LeNet(prefix="srvlenet_")
        net.initialize()
        net.hybridize()
        x = nd.array(onp.random.RandomState(0).randn(
            2, 1, 28, 28).astype("float32"))
        net(x)
        net(x)
        table = serve.BucketTable({"batch": (1, 2)})
        spec = models.serve_spec("lenet")
        sf, pf = serve.export_for_serving(net, str(tmp_path / "lenet"),
                                          table, spec["input_axes"])
        blk = gluon.SymbolBlock.imports(sf, ["data"], pf)
        cm = serve.CompiledModel(blk, table, spec["input_axes"],
                                 output_axes=spec["output_axes"])
        cm.warmup()
        got = cm.predict(x.asnumpy()[:1])
        net.hybridize(False)
        want = net(nd.array(x.asnumpy()[:1]))
        onp.testing.assert_allclose(got.asnumpy(), want.asnumpy(),
                                    rtol=1e-5, atol=1e-5)
        assert cm.cache_info()["post_warmup_compiles"] == 0

    @pytest.mark.slow
    def test_bert_cold_serving_round_trip(self, tmp_path):
        net = models.get_bert("bert_2_128_2", vocab_size=50, max_length=16,
                              dropout=0.0, use_decoder=False,
                              use_classifier=False, num_layers=1)
        net.initialize()
        net.hybridize()
        rng = onp.random.RandomState(0)
        ids = nd.array(rng.randint(1, 50, (1, 8)).astype("int32"))
        tt = nd.array(onp.zeros((1, 8), "int32"))
        vl = nd.array(onp.full((1,), 8, "float32"))
        net(ids, tt, vl)
        net(ids, tt, vl)
        table = serve.BucketTable({"batch": (1, 2), "seq": (8, 8)})
        spec = models.serve_spec("bert_encoder")
        sf, pf = serve.export_for_serving(net, str(tmp_path / "bert"),
                                          table, spec["input_axes"])
        blk = gluon.SymbolBlock.imports(sf, ["d0", "d1", "d2"], pf)
        cm = serve.CompiledModel(blk, table, spec["input_axes"],
                                 output_axes=spec["output_axes"],
                                 pad_values=spec["pad_values"])
        cm.warmup()
        seq, pooled = cm.predict(ids, tt, vl)
        wseq, wpooled = net(ids, tt, vl)
        onp.testing.assert_allclose(pooled.asnumpy(), wpooled.asnumpy(),
                                    rtol=2e-4, atol=2e-4)
        assert cm.cache_info()["post_warmup_compiles"] == 0


# ---------------------------------------------------------------------------
# DynamicBatcher
# ---------------------------------------------------------------------------
def _compiled_mlp(prefix="srvbat_", max_batch=8):
    net = _mlp(prefix=prefix)
    x = nd.array(onp.zeros((2, 8), "float32"))
    cm = serve.CompiledModel(net, serve.BucketTable({"batch": (1, max_batch)}),
                             [{0: "batch"}], example_args=(x,))
    cm.warmup()
    return cm


class TestDynamicBatcher:
    def test_deadline_flushes_partial_batch(self):
        cm = _compiled_mlp()
        b = serve.DynamicBatcher(cm, max_delay_ms=30, max_batch=8).start()
        try:
            t0 = time.perf_counter()
            futs = [b.submit(onp.ones((8,), "float32")) for _ in range(3)]
            res = [f.result(timeout=10) for f in futs]
            dt_ms = (time.perf_counter() - t0) * 1e3
        finally:
            b.stop()
        assert all(r.shape == (4,) for r in res)
        snap = b.metrics.snapshot(cm)
        assert snap["requests"] == 3
        assert snap["batches"] == 1  # coalesced, flushed by deadline
        assert 20 <= dt_ms < 5000
        assert snap["batch_occupancy"] == pytest.approx(3 / 4)

    def test_full_bucket_flushes_immediately(self):
        cm = _compiled_mlp(prefix="srvbat2_", max_batch=4)
        b = serve.DynamicBatcher(cm, max_delay_ms=10_000, max_batch=4).start()
        try:
            futs = [b.submit(onp.ones((8,), "float32")) for _ in range(4)]
            # a full bucket must NOT wait for the 10s deadline
            res = [f.result(timeout=5) for f in futs]
        finally:
            b.stop()
        assert len(res) == 4
        assert b.metrics.snapshot(cm)["batch_occupancy"] == 1.0

    def test_backpressure_queue_full(self):
        cm = _compiled_mlp(prefix="srvbat3_")
        b = serve.DynamicBatcher(cm, max_delay_ms=5, queue_limit=4)
        # worker NOT started: the queue can only fill
        for _ in range(4):
            b.submit(onp.ones((8,), "float32"))
        with pytest.raises(serve.QueueFullError):
            b.submit(onp.ones((8,), "float32"))
        assert b.metrics.rejected == 1
        b.stop()

    def test_malformed_request_rejected_at_submit(self):
        """Bad requests fail fast in submit() so they can never poison the
        innocent requests they would be co-batched with."""
        cm = _compiled_mlp(prefix="srvbat4_")
        b = serve.DynamicBatcher(cm, max_delay_ms=5).start()
        try:
            with pytest.raises(mx.MXNetError, match="takes 1"):
                b.submit(onp.ones((8,), "float32"),
                         onp.ones((8,), "float32"))  # wrong arity
            with pytest.raises(mx.MXNetError, match="rank"):
                b.submit(onp.ones((2, 8), "float32"))  # batch dim included
            # a good request co-submitted with the bad ones still serves
            good = b.submit(onp.ones((8,), "float32")).result(timeout=10)
            assert good.shape == (4,)
        finally:
            b.stop()
        assert b.metrics.snapshot(cm)["requests"] == 1

    def test_failed_flush_routes_to_futures_not_metrics(self):
        """A flush-time failure (model resolve raising mid-serve) fails the
        batch's futures, stays out of served-traffic counters, and does
        NOT kill the worker thread."""
        cm = _compiled_mlp(prefix="srvbat6_")
        state = {"broken": True}

        def thunk():
            if state["broken"]:
                raise mx.MXNetError("model unloaded")
            return cm

        state["broken"] = False
        b = serve.DynamicBatcher(thunk, max_delay_ms=5)  # worker not started
        fut = b.submit(onp.ones((8,), "float32"))  # validated while healthy
        state["broken"] = True  # the unload lands before the flush
        b.start()
        with pytest.raises(mx.MXNetError, match="unloaded"):
            fut.result(timeout=10)
        snap = b.metrics.snapshot(cm)
        assert snap["requests"] == 0 and snap["batches"] == 0
        assert snap["failed"] == 1 and snap["failed_batches"] == 1
        # the worker survived: a later request serves normally
        state["broken"] = False
        assert b.submit(onp.ones((8,), "float32")).result(
            timeout=10).shape == (4,)
        b.stop()

    def test_stop_fails_queued_futures_even_unstarted(self):
        cm = _compiled_mlp(prefix="srvbat7_")
        b = serve.DynamicBatcher(cm, max_delay_ms=5)  # never started
        fut = b.submit(onp.ones((8,), "float32"))
        b.stop()
        with pytest.raises(mx.MXNetError, match="batcher stopped"):
            fut.result(timeout=5)
        # submits after stop are rejected, never silently unresolved
        with pytest.raises(mx.MXNetError, match="batcher stopped"):
            b.submit(onp.ones((8,), "float32"))
        # restart revives the batcher
        b.start()
        assert b.submit(onp.ones((8,), "float32")).result(
            timeout=10).shape == (4,)
        b.stop()

    def test_fresh_metrics_snapshot_is_strict_json(self):
        def no_constants(name):
            raise AssertionError(f"non-strict JSON token {name!r}")

        doc = serve.ServeMetrics().dumps()
        parsed = json.loads(doc, parse_constant=no_constants)
        assert parsed["latency"]["latency_ms_p50"] is None
        assert parsed["batch_occupancy"] is None

    def test_thousand_mixed_requests_zero_recompiles(self):
        """The acceptance demo, in-suite: 1k mixed-size requests through
        the batcher with zero post-warmup recompiles."""
        cm = _compiled_mlp(prefix="srvbat5_")
        b = serve.DynamicBatcher(cm, max_delay_ms=2).start()
        errors = []

        def client(cid):
            rng = onp.random.RandomState(cid)
            for _ in range(250):
                try:
                    b.submit(rng.randn(8).astype("float32")).result(
                        timeout=60)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
                    return

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        b.stop()
        assert not errors
        snap = b.metrics.snapshot(cm)
        assert snap["requests"] == 1000
        assert snap["compile_cache"]["post_warmup_compiles"] == 0
        assert snap["latency"]["latency_ms_p99"] > 0
        assert snap["queue_depth"] == 0  # drained queue reads as empty


# ---------------------------------------------------------------------------
# ModelRegistry
# ---------------------------------------------------------------------------
def _export_lenet(tmp_path, table, spec):
    net = models.LeNet(prefix="srvreg_")
    net.initialize()
    net.hybridize()
    x = nd.array(onp.random.RandomState(0).randn(
        1, 1, 28, 28).astype("float32"))
    net(x)
    net(x)
    sf, pf = serve.export_for_serving(net, str(tmp_path / "lenet"),
                                      table, spec["input_axes"])
    return net, x


def _trainer_ckpt(tmp_path, net, scale=0.0, step=10):
    params = sorted(net.collect_params().items())
    arrays = {f"param:{i:04d}": p.data().asnumpy() * scale
              for i, (_, p) in enumerate(params)}
    meta = {"trainer": "Trainer", "format": 1,
            "param_names": [p.name for _, p in params],
            "opt_state_sizes": [0] * len(params)}
    root = str(tmp_path / "ckpts")
    fault_checkpoint.save_checkpoint(root, arrays, meta, step=step)
    return root


class TestModelRegistry:
    def test_cold_load_and_versioned_swap(self, tmp_path):
        table = serve.BucketTable({"batch": (1, 2)})
        spec = models.serve_spec("lenet")
        net, x = _export_lenet(tmp_path, table, spec)
        reg = serve.ModelRegistry()
        mv1 = reg.load("lenet", table=table, input_axes=spec["input_axes"],
                       artifacts=str(tmp_path / "lenet"),
                       output_axes=spec["output_axes"])
        assert mv1.version == 1 and reg.active_version("lenet") == 1
        out1 = reg.get("lenet").predict(x).asnumpy()
        assert abs(out1).sum() > 0

        # v2 from a newer fault checkpoint (zeroed weights)
        root = _trainer_ckpt(tmp_path, net, scale=0.0)
        mv2 = reg.load("lenet", table=table, input_axes=spec["input_axes"],
                       artifacts=str(tmp_path / "lenet"), ckpt_root=root,
                       output_axes=spec["output_axes"])
        assert mv2.version == 2 and reg.active_version("lenet") == 2
        assert abs(reg.get("lenet").predict(x).asnumpy()).sum() == 0.0
        # the old version stays pinnable
        assert abs(reg.get("lenet", version=1).predict(x).asnumpy()).sum() > 0
        assert reg.models() == {"lenet": [1, 2]}

        # unloading the active version re-activates the newest remaining
        reg.unload("lenet", version=2)
        assert reg.active_version("lenet") == 1

    def test_in_place_weight_swap_zero_recompiles(self, tmp_path):
        table = serve.BucketTable({"batch": (1, 2)})
        spec = models.serve_spec("lenet")
        net, x = _export_lenet(tmp_path, table, spec)
        reg = serve.ModelRegistry()
        mv = reg.load("lenet", table=table, input_axes=spec["input_axes"],
                      artifacts=str(tmp_path / "lenet"),
                      output_axes=spec["output_axes"])
        cm = mv.compiled
        assert abs(cm.predict(x).asnumpy()).sum() > 0
        info_before = cm.cache_info()
        # swap weights in place (same shapes): refresh, not recompile
        swap = {p.name: onp.zeros(p.shape, "float32")
                for _, p in net.collect_params().items()}
        cm._block.set_weights(swap)
        cm.refresh_params()
        assert abs(cm.predict(x).asnumpy()).sum() == 0.0
        info = cm.cache_info()
        assert info["compiles"] == info_before["compiles"]
        assert info["post_warmup_compiles"] == 0

    @pytest.mark.chaos
    def test_chaos_failed_load_keeps_serving_version(self, tmp_path):
        table = serve.BucketTable({"batch": (1, 2)})
        spec = models.serve_spec("lenet")
        net, x = _export_lenet(tmp_path, table, spec)
        reg = serve.ModelRegistry()
        reg.load("lenet", table=table, input_axes=spec["input_axes"],
                 artifacts=str(tmp_path / "lenet"),
                 output_axes=spec["output_axes"])
        want = reg.get("lenet").predict(x).asnumpy()
        root = _trainer_ckpt(tmp_path, net, scale=0.0)
        with inject.chaos(seed=7, crash_sites=["serve.registry.load"]):
            with pytest.raises(mx.MXNetError, match="chaos"):
                reg.load("lenet", table=table,
                         input_axes=spec["input_axes"],
                         artifacts=str(tmp_path / "lenet"), ckpt_root=root,
                         output_axes=spec["output_axes"])
        # the failed load never touched the registry: v1 still serves
        assert reg.models() == {"lenet": [1]}
        assert reg.active_version("lenet") == 1
        onp.testing.assert_allclose(reg.get("lenet").predict(x).asnumpy(),
                                    want, rtol=1e-6)

    def test_registry_errors(self, tmp_path):
        reg = serve.ModelRegistry()
        with pytest.raises(mx.MXNetError, match="no model"):
            reg.get("ghost")
        with pytest.raises(mx.MXNetError, match="exactly one"):
            reg.load("x", table=serve.BucketTable({"batch": (1, 2)}),
                     input_axes=[{0: "batch"}])


# ---------------------------------------------------------------------------
# Server (in-process + TCP smoke)
# ---------------------------------------------------------------------------
def test_server_tcp_smoke(tmp_path):
    table = serve.BucketTable({"batch": (1, 2)})
    spec = models.serve_spec("lenet")
    net, x = _export_lenet(tmp_path, table, spec)
    reg = serve.ModelRegistry()
    reg.load("lenet", table=table, input_axes=spec["input_axes"],
             artifacts=str(tmp_path / "lenet"),
             output_axes=spec["output_axes"])
    srv = serve.Server(reg, max_delay_ms=2).start()
    try:
        assert srv.port > 0
        # inference over the wire
        reply = serve.client_call(
            "127.0.0.1", srv.port,
            {"model": "lenet",
             "inputs": [x.asnumpy()[0].tolist()]})
        assert reply["ok"], reply
        got = onp.asarray(reply["outputs"][0], dtype="float32")
        want = reg.get("lenet").predict(x).asnumpy()[0]
        onp.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        assert reply["latency_ms"] > 0
        # control plane
        assert serve.client_call("127.0.0.1", srv.port,
                                 {"cmd": "models"})["models"] \
            == {"lenet": [1]}
        m = serve.client_call("127.0.0.1", srv.port,
                              {"cmd": "metrics", "model": "lenet"})
        assert m["ok"] and m["metrics"]["requests"] >= 1
        assert m["metrics"]["compile_cache"]["post_warmup_compiles"] == 0
        # protocol errors come back as structured replies, not hangups
        bad = serve.client_call("127.0.0.1", srv.port,
                                {"model": "ghost", "inputs": []})
        assert not bad["ok"] and "ghost" in bad["error"]
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# satellite: MX5xx serving lint
# ---------------------------------------------------------------------------
@pytest.mark.lint
class TestServeLint:
    def test_retrace_fixture_mx501(self):
        from incubator_mxnet_tpu.analysis import serve_lint
        rep = serve_lint.lint_file(
            os.path.join(FIXTURES, "retrace_per_request.py"))
        assert rep.codes() == ["MX501", "MX501"]
        assert all(d.severity == "warning" for d in rep)

    def test_unbucketed_fixture_mx502(self):
        from incubator_mxnet_tpu.analysis import serve_lint
        rep = serve_lint.lint_file(
            os.path.join(FIXTURES, "unbucketed_serve.py"))
        assert rep.codes() == ["MX502"]

    def test_bucket_evidence_silences_mx502(self):
        from incubator_mxnet_tpu.analysis import serve_lint
        src = ("import jax\n"
               "from incubator_mxnet_tpu import serve\n"
               "model = jax.jit(lambda x: x)\n"
               "table = serve.BucketTable({'batch': (1, 8)})\n"
               "def predict(request):\n"
               "    return model(request)\n")
        assert serve_lint.lint_source(src).codes() == []

    def test_merged_into_analysis_lint_source(self):
        import incubator_mxnet_tpu.analysis as analysis
        src = ("import jax\n"
               "def serve(req):\n"
               "    for r in req:\n"
               "        f = jax.jit(lambda x: x)\n")
        assert "MX501" in analysis.lint_source(src).codes()

    def test_mxlint_cli_flags_fixture(self, capsys):
        from tools import mxlint
        rc = mxlint.main([os.path.join(FIXTURES, "unbucketed_serve.py"),
                          "--strict"])
        out = capsys.readouterr().out
        assert rc == 1 and "MX502" in out

    def test_serve_runtime_and_examples_clean(self, capsys):
        from tools import mxlint
        rc = mxlint.main([os.path.join(REPO, "incubator_mxnet_tpu", "serve"),
                          os.path.join(REPO, "examples"), "--strict"])
        out = capsys.readouterr().out
        assert rc == 0, out


def test_compiled_model_serves_from_the_context_its_block_lives_on():
    """The chip path in miniature: a block initialized on a context that is
    NOT the current one (``mx.tpu(0)`` on a TPU host; the second virtual
    CPU device here) must warm up and serve there. Found on the chip by
    ``chip_smoke.py``: the model's eager first forward made ``F.arange`` on
    the current context and met ``valid_length`` across devices; and every
    bucket was compiled for the process default device, which an
    executable's committed parameters then refuse."""
    import jax

    from incubator_mxnet_tpu import models
    ctx = mx.cpu(1)
    assert ctx.jax_device != mx.current_context().jax_device
    net = models.get_bert("bert_2_128_2", vocab_size=100, max_length=16,
                          dropout=0.1, use_decoder=False,
                          use_classifier=False)
    net.initialize(ctx=ctx)
    net.hybridize()
    spec = models.serve_spec("bert_encoder")
    table = serve.BucketTable({"batch": (2, 4), "seq": (8, 16)})
    example = (nd.array(onp.ones((2, 8), "int32"), ctx=ctx),
               nd.array(onp.zeros((2, 8), "int32"), ctx=ctx),
               nd.array(onp.full((2,), 8, "float32"), ctx=ctx))
    cm = serve.CompiledModel(net, table, spec["input_axes"],
                             example_args=example,
                             output_axes=spec["output_axes"],
                             pad_values=spec["pad_values"])
    assert cm.warmup()["compiled"] == 4
    ids = onp.arange(5, dtype="int32")[None] + 1
    seq, pooled = cm.predict(ids, onp.zeros((1, 5), "int32"),
                             onp.asarray([5.0], "float32"))
    assert seq.shape == (1, 5, 128) and pooled.shape == (1, 128)
    assert seq.context == ctx
    assert seq._data.devices() == {ctx.jax_device}
    assert onp.isfinite(seq.asnumpy()).all()
    # the same request inside a padded batch answers the same
    ids4 = onp.concatenate([onp.pad(ids, ((0, 0), (0, 7))),
                            onp.ones((2, 12), "int32")])
    seq4, _ = cm.predict(ids4, onp.zeros((3, 12), "int32"),
                         onp.asarray([5.0, 12.0, 12.0], "float32"))
    onp.testing.assert_allclose(seq4.asnumpy()[0, :5], seq.asnumpy()[0],
                                rtol=1e-4, atol=1e-5)
    assert cm.cache_info()["post_warmup_compiles"] == 0
