"""The headline bench's runners must run end-to-end (reference mechanism:
benchmark scripts smoke-run in CI; SURVEY §6). Tiny configs on the CPU —
the numbers mean nothing there and ``main()`` refuses to print them; the
contract (one JSON-able dict with value/unit/extra naming its device,
finite loss) is what's under test."""
import json
import os

import jax
import pytest


def _load_bench(name):
    import importlib.util
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(repo, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_bench(monkeypatch, workload, **env):
    """One ``run_<workload>`` record. ``main()`` refuses the CPU, so the
    contract tests call the runner it would dispatch to; the CPU has no
    row in the peak table, so they name a peak themselves."""
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setenv("MXTPU_PEAK_TFLOPS", "1")
    rec = getattr(_load_bench("bench"), "run_" + workload)(None)
    json.loads(json.dumps(rec))  # strictly serializable
    dev = jax.devices()
    assert rec["extra"]["platform"] == dev[0].platform == "cpu"
    assert rec["extra"]["device_kind"] == dev[0].device_kind
    assert rec["extra"]["device_count"] == len(dev)
    assert "backend" not in rec["extra"]
    return rec


def test_bench_bert_contract(monkeypatch):
    rec = _run_bench(monkeypatch, "bert", MXTPU_BENCH_MODEL="bert_2_128_2",
                     MXTPU_BENCH_BATCH="2", MXTPU_BENCH_SEQ="64",
                     MXTPU_BENCH_STEPS="2")
    import math
    assert rec["unit"] == "tokens/sec/chip" and rec["value"] > 0
    assert math.isfinite(rec["extra"]["loss"])


def test_bench_bert_remat_contract(monkeypatch):
    # contract the remat fork on the tiny config so a code bug can't kill
    # a BERT-large run that needs it to fit
    rec = _run_bench(monkeypatch, "bert", MXTPU_BENCH_MODEL="bert_2_128_2",
                     MXTPU_BENCH_BATCH="2", MXTPU_BENCH_SEQ="64",
                     MXTPU_BENCH_STEPS="2", MXTPU_BENCH_REMAT="1")
    import math
    assert rec["unit"] == "tokens/sec/chip" and rec["value"] > 0
    assert rec["extra"]["remat"] is True
    assert math.isfinite(rec["extra"]["loss"])


def test_int8_probe_contract(monkeypatch, capsys):
    # tiny shapes: the contract (one JSON dict, finite timings, HLO verdict
    # booleans) is what's under test — a chip run uses the real sizes
    for k, v in (("MXTPU_INT8_BATCH", "64"), ("MXTPU_INT8_IN", "64"),
                 ("MXTPU_INT8_OUT", "64"), ("MXTPU_INT8_ITERS", "2")):
        monkeypatch.setenv(k, v)
    import importlib.util
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "int8_probe", os.path.join(repo, "benchmark", "int8_probe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.main()
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["metric"] == "int8_dense_vs_bf16"
    assert rec["int8_ms"] > 0 and rec["bf16_ms"] > 0
    assert isinstance(rec["hlo_has_int8_dot"], bool)


def test_bench_resnet_contract(monkeypatch):
    import math
    rec = _run_bench(monkeypatch, "resnet",
                     MXTPU_BENCH_MODEL="resnet18_v1", MXTPU_BENCH_BATCH="2",
                     MXTPU_BENCH_IMG="64", MXTPU_BENCH_STEPS="2")
    assert rec["unit"] == "imgs/sec/chip" and rec["value"] > 0
    assert math.isfinite(rec["extra"]["loss"])


def test_bench_ssd_contract(monkeypatch):
    import math
    rec = _run_bench(monkeypatch, "ssd",
                     MXTPU_BENCH_BATCH="2", MXTPU_BENCH_IMG="64",
                     MXTPU_BENCH_STEPS="2")
    assert rec["unit"] == "imgs/sec/chip" and rec["value"] > 0
    assert math.isfinite(rec["extra"]["loss"])


def test_bench_frcnn_contract(monkeypatch):
    import math
    rec = _run_bench(monkeypatch, "frcnn",
                     MXTPU_BENCH_BATCH="2", MXTPU_BENCH_IMG="64",
                     MXTPU_BENCH_STEPS="2")
    assert rec["unit"] == "imgs/sec/chip" and rec["value"] > 0
    assert math.isfinite(rec["extra"]["loss"])


@pytest.mark.parametrize("workload", ["bert", "resnet", "ssd", "frcnn"])
def test_bench_main_refuses_cpu(monkeypatch, capsys, workload):
    """The measuring path has no CPU fallback: non-proxy ``main()`` exits
    non-zero before any model is built and prints no record."""
    monkeypatch.setenv("MXTPU_BENCH_WORKLOAD", workload)
    monkeypatch.setenv("MXTPU_BENCH_TIMEOUT", "0")  # no watchdog under pytest
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache_dir = jax.config.jax_compilation_cache_dir
    with pytest.raises(SystemExit) as exc:
        _load_bench("bench_cpu").main([])
    assert exc.value.code not in (0, None)
    assert "cpu" in str(exc.value.code)
    assert capsys.readouterr().out == ""
    # and it stopped before the compile cache was pointed anywhere
    assert jax.config.jax_compilation_cache_dir == cache_dir


def test_bench_no_mfu_without_a_peak_table_row(monkeypatch):
    """A device kind missing from ``util.DEVICE_PEAKS_BY_KIND`` is an
    error on the measuring path, not a default peak."""
    from incubator_mxnet_tpu.base import MXNetError
    monkeypatch.delenv("MXTPU_PEAK_TFLOPS", raising=False)
    monkeypatch.setenv("MXTPU_BENCH_MODEL", "bert_2_128_2")
    with pytest.raises(MXNetError, match="no peak-table row.*cpu"):
        _load_bench("bench_nopeak").run_bert(None)


def test_watchdog_abort_record_is_structured(monkeypatch):
    """A device init that never returns (rc=75) must leave a parseable
    {"error": "device_init_timeout"} JSON record on stdout, not silence
    (a harness would read `parsed: null`)."""
    mod = _load_bench("bench_wd")
    monkeypatch.setenv("MXTPU_BENCH_WORKLOAD", "frcnn")
    rec = mod._watchdog_record(1500)
    # same JSON-line contract as a successful run: one flat record with
    # the metric keys present (null) plus the structured error
    assert rec["error"] == "device_init_timeout"
    assert rec["value"] is None and rec["metric"] is None
    assert rec["extra"]["timeout_s"] == 1500 and rec["extra"]["rc"] == 75
    assert rec["extra"]["workload"] == "frcnn"
    json.loads(json.dumps(rec))  # strictly serializable


def test_watchdog_fire_emits_json_line_before_exit(monkeypatch, capsys):
    """The timer path itself: with retries exhausted (0 configured),
    _fire must print the record as the last stdout line before
    os._exit(75)."""
    mod = _load_bench("bench_wd2")
    monkeypatch.setenv("MXTPU_BENCH_TIMEOUT", "1200")
    monkeypatch.setenv("MXTPU_BENCH_RETRIES", "0")
    exits = []
    monkeypatch.setattr(mod.os, "_exit", lambda rc: exits.append(rc))
    wd = mod._arm_watchdog()
    assert wd is not None
    try:
        wd._timer.cancel()        # don't let the real 1200s timer linger
        wd._fire()                # fire the callback synchronously
    finally:
        wd.cancel()
    assert exits == [75]
    out = capsys.readouterr()
    rec = json.loads(out.out.strip().splitlines()[-1])
    assert rec["error"] == "device_init_timeout"
    assert rec["extra"]["timeout_s"] == 1200
    assert rec["attempts"] == 1   # no retry window was configured
    assert "watchdog" in out.err


def test_watchdog_retry_rearms_once_then_aborts(monkeypatch, capsys):
    """Satellite (ISSUE 17): the first expired window re-arms ONE bounded
    retry (budget + backoff) instead of aborting — a pool grant that
    lands late is a recovered round — and only the second expiry prints
    the abort record, with the attempts count."""
    mod = _load_bench("bench_wd3")
    monkeypatch.setenv("MXTPU_BENCH_TIMEOUT", "1200")
    monkeypatch.setenv("MXTPU_BENCH_RETRIES", "1")
    monkeypatch.setenv("MXTPU_BENCH_RETRY_BACKOFF_S", "30")
    exits = []
    monkeypatch.setattr(mod.os, "_exit", lambda rc: exits.append(rc))
    wd = mod._arm_watchdog()
    try:
        wd._timer.cancel()
        wd._fire()                # window 1 expires → re-arm, no abort
        assert exits == [] and wd.attempts == 2
        err = capsys.readouterr().err
        assert "re-arming" in err and "1230" in err  # budget + backoff
        wd._timer.cancel()        # the re-armed retry timer
        wd._fire()                # window 2 expires → abort
    finally:
        wd.cancel()
    assert exits == [75]
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["attempts"] == 2


def test_watchdog_cancel_wins_over_late_fire(monkeypatch, capsys):
    """A result that lands while the timer is in flight must win: a
    cancelled watchdog's _fire is a no-op, never an exit."""
    mod = _load_bench("bench_wd4")
    monkeypatch.setenv("MXTPU_BENCH_TIMEOUT", "1200")
    exits = []
    monkeypatch.setattr(mod.os, "_exit", lambda rc: exits.append(rc))
    wd = mod._arm_watchdog()
    wd.cancel()
    wd._fire()
    assert exits == [] and capsys.readouterr().out == ""
