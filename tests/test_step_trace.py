"""The trainer's spans on the device trace's clock, and jax's own account of
the first call: ``ShardedTrainer.step`` under an active ``jax.profiler``
trace writes ``step.place`` / ``step.dispatch`` into the host plane (read
back with the benchmark's own ``chipbench.tracered.load``), the ring keeps
the segments ``step_report()`` always gave, one reading feeds span and
event, and ``compile_log.phase_seconds`` holds what the step's compile
went on."""
import jax
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import fault, gluon, models, parallel, profiler, telemetry
from incubator_mxnet_tpu.telemetry import compile_log

PHASES = ("trace_s", "lower_s", "backend_compile_s", "cache_retrieval_s")


def _bert_batch(seed, B=2, L=16, P=3, V=100):
    rng = onp.random.default_rng(seed)
    return (rng.integers(0, V, (B, L)).astype("int32"),
            rng.integers(0, 2, (B, L)).astype("int32"),
            onp.full((B,), L, "float32"),
            onp.sort(rng.permuted(onp.tile(onp.arange(L), (B, 1)), axis=1)[:, :P],
                     axis=1).astype("int32"),
            rng.integers(0, V, (B, P)).astype("float32"),
            onp.ones((B, P), "float32"),
            rng.integers(0, 2, (B,)).astype("float32"))


@pytest.fixture(scope="module")
def bert():
    """A 2-layer BERT under ``ShardedTrainer`` after its first step, with
    what that step left in the ring and in the compile ledger."""
    profiler.reset_spans()
    before = compile_log.phase_seconds("trainer.step")
    mx.random.seed(5)
    net = models.get_bert(dict(num_layers=2, units=32, hidden_size=64, num_heads=2),
                          vocab_size=100, max_length=16, dropout=0.1)
    net.initialize()
    trainer = parallel.ShardedTrainer(
        net, models.bert_pretrain_loss, "adamw", {"learning_rate": 1e-4},
        mesh=parallel.make_mesh(devices=jax.devices()[:1]),
        rules=models.bert_sharding_rules(), n_labels=3)
    trainer.step(*_bert_batch(0)).wait_to_read()
    after = compile_log.phase_seconds("trainer.step")
    first = {k: after[k] - before[k] for k in after}
    return trainer, first, profiler.recent_spans()


def test_first_call_is_accounted_from_inside(bert):
    trainer, first, spans = bert
    by_name = {r.name: r for r in spans}
    # the program's own share of the first call is one span inside the frame
    assert by_name["trainer.init_state"].parent == "step"
    assert 0 < by_name["trainer.init_state"].dur_ms < by_name["step"].dur_ms
    # jax's own account of the step's compile, at the step's site: every
    # phase was paid (no persistent cache here), each event counted once,
    # so together they fit inside the dispatch that held them
    assert first["trace_s"] > 0 and first["lower_s"] > 0 and first["backend_compile_s"] > 0
    assert first["events"] >= 3
    assert sum(first[k] for k in PHASES) <= by_name["step.dispatch"].dur_ms / 1e3
    # and nothing more is paid by later steps of the same signature
    held = compile_log.phase_seconds("trainer.step")
    for i in range(1, 4):
        trainer.step(*_bert_batch(i)).wait_to_read()
    assert compile_log.phase_seconds("trainer.step") == held
    assert compile_log.summary()["phase_seconds"]["trainer.step"] == held
    assert compile_log.phase_seconds()["events"] >= held["events"]     # all sites


def test_step_spans_reach_the_host_plane(bert, tmp_path):
    from chipbench import tracered
    trainer = bert[0]
    tracer = tracered.Tracer(str(tmp_path))
    tracer.start()
    try:
        for i in range(3):
            with tracer.span("bench.step"):
                loss = trainer.step(*_bert_batch(10 + i))
        loss.wait_to_read()
    finally:
        tracer.stop()
    trace = tracered.load(str(tmp_path))
    # the XLA:CPU runtime writes its thunks into the host plane under dotted names too
    host = [e for e in trace.host if e[0] in ("bench.step", "step.place", "step.dispatch")]
    assert [e[0] for e in host] == ["bench.step", "step.place", "step.dispatch"] * 3
    for outer, place, dispatch in zip(host[0::3], host[1::3], host[2::3]):
        assert outer[1] <= place[1] and place[2] <= dispatch[1] and dispatch[2] <= outer[2]
    # the step annotation carries the program's step number for XProf's step view
    raw = jax.profiler.ProfileData.from_file(
        tracered.glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0])
    steps = [dict(e.stats).get("step_num") for p in raw.planes if p.name == "/host:CPU"
             for line in p.lines for e in line.events if e.name == "train"]
    assert steps == [trainer.num_update - 2, trainer.num_update - 1, trainer.num_update]


def test_ring_segments_and_event_fields_come_from_one_reading(bert):
    trainer = bert[0]
    profiler.reset_spans()
    telemetry.clear()
    for i in range(3):
        trainer.step(*_bert_batch(20 + i)).wait_to_read()
    rep = profiler.step_report()
    assert rep["steps"] == 3 and set(rep["segments"]) == {"place", "dispatch", "python"}
    spans = profiler.recent_spans()
    events = telemetry.get_events("train.step")
    assert len(events) == 3
    for ev in events:
        mine = {r.name: r for r in spans if r.step == ev.step}
        assert mine["step"].kind == "frame" and mine["step.place"].parent == "step"
        assert ev.fields["wall_ms"] == round(mine["step"].dur_ms, 3)
        assert ev.fields["place_ms"] == round(mine["step.place"].dur_ms, 3)
        assert ev.fields["dispatch_ms"] == round(mine["step.dispatch"].dur_ms, 3)


def test_sampled_trace_holds_the_frame_and_its_segments_under_the_root_span(bert):
    from incubator_mxnet_tpu.telemetry import trace
    trainer = bert[0]
    trace.set_sample_rate(1.0)
    try:
        trace.clear()
        profiler.reset_spans()
        with trace.span("caller") as caller:
            trainer.step(*_bert_batch(30)).wait_to_read()
        trainer.step(*_bert_batch(31)).wait_to_read()        # no outer context: a root of its own
        assert trace.current() is None
    finally:
        trace.set_sample_rate(None)
    mine = trace.spans(caller.ctx.trace_id)
    alone = [r for r in trace.spans() if r["trace_id"] != caller.ctx.trace_id]
    for recs, root_parent in ((mine[:-1], caller.ctx.span_id), (alone, None)):
        # finished innermost first: no span outlives the one that holds it
        assert [r["name"] for r in recs] == ["step.place", "step.dispatch", "step", "train.step"]
        place, dispatch, frame, root = recs
        assert root["parent_id"] == root_parent and frame["parent_id"] == root["span_id"]
        assert place["parent_id"] == dispatch["parent_id"] == frame["span_id"]
        # the ring's records carry the ids of the spans they became
        ring = {r.name: r.trace for r in profiler.recent_spans() if r.step == root["step"]}
        assert ring == {r["name"]: (r["trace_id"], r["span_id"]) for r in recs[:3]}
    assert trace.orphans(mine) == [] and trace.orphans(alone) == []


def test_guarded_step_times_its_sync_as_device_wait():
    net = gluon.nn.Dense(4, in_units=8, prefix="steptrace_")
    net.initialize()
    l2 = gluon.loss.L2Loss()
    trainer = parallel.ShardedTrainer(
        net, lambda out, label: l2(out, label), "sgd", {"learning_rate": 0.01},
        mesh=parallel.make_mesh(devices=jax.devices()[:1]), n_labels=1,
        guard=fault.StepGuard(policy="warn"))
    x, y = onp.ones((4, 8), "float32"), onp.zeros((4, 4), "float32")
    trainer.step(x, y)
    profiler.reset_spans()
    telemetry.clear()
    trainer.step(x, y)
    assert set(profiler.step_report()["segments"]) == {"place", "dispatch", "device_wait", "python"}
    wait = [r for r in profiler.recent_spans() if r.name == "step.device_wait"]
    assert len(wait) == 1 and wait[0].parent == "step" and wait[0].step == 2
    ev = telemetry.get_events("train.step")[-1]
    assert ev.fields["device_wait_ms"] == round(wait[0].dur_ms, 3)


def test_compile_site_is_the_innermost_block_and_other_outside():
    f = jax.jit(lambda x: x * 3 + 1)
    before = {s: compile_log.phase_seconds(s) for s in ("unit.outer", "unit.inner", "other")}
    with compile_log.at("unit.outer"):
        with compile_log.at("unit.inner"):
            f(onp.ones(3, "float32"))
        f(onp.ones(4, "float32"))
    f(onp.ones(5, "float32"))
    for site in before:
        now = compile_log.phase_seconds(site)
        assert now["trace_s"] > before[site]["trace_s"], site
        assert now["backend_compile_s"] > before[site]["backend_compile_s"], site
    assert compile_log.phase_seconds("unit.never") == dict.fromkeys(PHASES, 0.0) | {"events": 0}


def test_nested_compile_events_count_once():
    # jax reports the inner event first, as it ends; the outer one then
    # keeps only what the inner did not already count
    compile_log._TLS.__dict__.pop("ended", None)
    before = compile_log.phase_seconds("unit.nest")
    with compile_log.at("unit.nest"):
        compile_log._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.0004)
        compile_log._on_duration("/jax/core/compile/backend_compile_duration", 3600.0)
        compile_log._on_duration("/jax/some/other_event", 1.0)
    now = compile_log.phase_seconds("unit.nest")
    assert now["cache_retrieval_s"] - before["cache_retrieval_s"] == pytest.approx(0.0004)
    assert now["backend_compile_s"] - before["backend_compile_s"] == pytest.approx(3600.0 - 0.0004)
    assert now["events"] - before["events"] == 2


def test_hybridize_and_guard_sites_are_set_on_a_new_signature_only():
    net = gluon.nn.Dense(3, in_units=5, prefix="steptrace_site_")
    net.initialize()
    net.hybridize()
    x = mx.nd.array(onp.ones((2, 5), "float32"))
    net(x).wait_to_read()                 # a hybridized block's first call runs eagerly
    for site, call in (("gluon.hybridize", lambda: net(x).wait_to_read()),
                       ("fault.guards.finite", lambda: fault.all_finite({"w": x._data}))):
        before = compile_log.phase_seconds(site)
        call()
        first = compile_log.phase_seconds(site)
        assert first["events"] > before["events"] and first["trace_s"] > before["trace_s"], site
        call()
        assert compile_log.phase_seconds(site) == first, site
