"""Headline benchmark: BERT pretraining throughput on one chip.

Mirrors the BASELINE.json north-star workload (GluonNLP
scripts/bert/run_pretraining.py): full pretraining step — embeddings, encoder
on flash attention, MLM+NSP heads, loss, grads, AdamW — compiled to one XLA
executable, bf16 activations/params with fp32 master weights.

Prints ONE JSON line:
  {"metric": ..., "value": tokens/sec/chip, "unit": ..., "vs_baseline": MFU/0.40}

Env knobs: MXTPU_BENCH_MODEL (bert_12_768_12|bert_24_1024_16),
MXTPU_BENCH_BATCH, MXTPU_BENCH_SEQ, MXTPU_BENCH_REMAT (1 = jax.checkpoint
per encoder layer, frees HBM for bigger batches), MXTPU_PEAK_TFLOPS
(per-chip bf16 peak, default by device kind).

Device-blind proxy mode (no TPU needed — the CI ``perf-proxy`` gate)::

    python bench.py --proxy                          # every SERVE_SPECS family
    python bench.py --proxy --families bert,lenet
    python bench.py --proxy --out PERF_PROXY.json    # (re-)bank the baseline
    python bench.py --proxy --families bert --check PERF_PROXY.json
    python bench.py --proxy --mesh-step              # + 8-forced-host-device
                                                     #   compiled mesh-step probe

``--proxy`` traces every serving family's compiled graphs on CPU, prices
them with ``analysis.hlo.cost`` (FLOPs/step, bytes/step, fusion counts —
deterministic functions of the graph), measures the host dispatch gap
around a few compiled predict calls via ``profiler.step_report``, and
emits one structured record per family. ``--check`` diffs the
deterministic metrics against a banked baseline with a tolerance gate
(default ±5%): regressions fail (rc=1), improvements warn so the
baseline gets re-banked. These are counts from a CPU trace: they rank
graphs and are never device metrics.

The non-proxy path measures, so it runs on a chip or not at all:
``main()`` exits non-zero on a CPU backend, every record names its device
(``platform`` / ``device_kind`` / ``device_count``), and a device kind
missing from ``util.DEVICE_PEAKS_BY_KIND`` is an error.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as onp


def _peak_tflops() -> float:
    """Per-chip bf16 peak for MFU accounting — the shared
    ``util.peak_tflops`` table (by device kind, public specs;
    MXTPU_PEAK_TFLOPS overrides), the same source the autotuner's
    roofline score and the goodput ledger's MFU headline read."""
    from incubator_mxnet_tpu.util import peak_tflops
    return peak_tflops()


def _device_fields() -> dict:
    """The device every record names, as JAX reports it."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _require_chip() -> None:
    """The measuring path has no CPU fallback: a ``*_per_chip`` rate or an
    MFU from a host run would be written under a device metric's name."""
    dev = _device_fields()
    if dev["platform"] == "cpu":
        raise SystemExit(
            f"bench.py measures on an accelerator and found {dev}; it does "
            "not fall back to the CPU (use --proxy for trace counts)")


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on, before the first
    compile. ``JAX_COMPILATION_CACHE_DIR``, when set, is the only
    location (jax reads it itself; no directory is set in code). Unset,
    the cache is ``<checkout>/.jax_cache`` — a fixed path, because the
    path is part of what a later process must find again. Returns the
    directory in use. THE one place ``bench.py`` and ``chip_smoke.py``
    configure it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


_DEFAULT_MODEL = {"resnet": "resnet50_v1", "bert": "bert_12_768_12"}


def _bench_workload() -> str:
    """THE workload resolution main() uses, shared with the watchdog
    abort record so they can't drift."""
    return os.environ.get("MXTPU_BENCH_WORKLOAD", "bert")


def _bench_model(workload: str):
    """THE workload→model resolution main() uses, shared with the
    watchdog abort record so they can't drift. ssd/frcnn run the fixed
    in-tree model and ignore MXTPU_BENCH_MODEL (returns None)."""
    if workload not in _DEFAULT_MODEL:
        return None
    return os.environ.get("MXTPU_BENCH_MODEL", _DEFAULT_MODEL[workload])


def _watchdog_record(budget: int, attempts: int = 1) -> dict:
    """The structured abort record the watchdog prints as its last stdout
    line: harnesses that parse one-JSON-line-per-run see a machine-readable
    ``{"error": "device_init_timeout"}`` instead of ``parsed: null``, so a
    device init that never returned (rc=75) is distinguishable from
    "produced no data". ``goodput: null`` rides along so the record is
    self-describing (no goodput data was measured this round);
    ``tools/perf_history.py`` classifies the round BLIND off the null
    ``value`` and renders the ``error`` as its reason instead of
    silently skipping it — a run of rc=75 wedges reads as "no device
    data since rN", never as "no regressions". ``attempts`` is the number
    of full watchdog windows waited (1 = no retry configured): a round
    that wedged through a retry is distinguishable from one that was
    never given a second window."""
    workload = _bench_workload()
    model = _bench_model(workload)
    return {
        "error": "device_init_timeout",
        "attempts": int(attempts),
        "goodput": None,
        "metric": None,
        "value": None,
        "unit": None,
        "vs_baseline": None,
        "extra": {"timeout_s": budget, "rc": 75, "workload": workload,
                  "model": model},
    }


class _BenchWatchdog:
    """The device-init watchdog with one bounded retry: a fired window
    re-arms up to ``MXTPU_BENCH_RETRIES`` times (default 1), each retry
    window stretched by ``MXTPU_BENCH_RETRY_BACKOFF_S`` (default 60) —
    a pool grant that lands late is a recovered round, not a blind one.
    Only after the LAST window expires does the abort record print
    (with the ``attempts`` count) and the process ``os._exit(75)``.

    The timer thread cannot un-wedge the blocked device-init call — the
    retry IS the extra bounded window; what it buys is distinguishing
    "wedged forever" from "slow grant", without a human re-launching.
    """

    def __init__(self, budget: int, retries: int, backoff_s: float):
        import threading
        self._threading = threading
        self._budget = budget
        self._retries = max(0, retries)
        self._backoff = max(0.0, backoff_s)
        self._lock = threading.Lock()
        self._attempt = 1
        self._cancelled = False
        self._timer = None
        self._arm(budget)

    def _arm(self, window: float) -> None:
        t = self._threading.Timer(window, self._fire)
        t.daemon = True
        self._timer = t
        t.start()

    def cancel(self) -> None:
        with self._lock:
            self._cancelled = True
            if self._timer is not None:
                self._timer.cancel()
                self._timer = None

    @property
    def attempts(self) -> int:
        return self._attempt

    def _fire(self) -> None:
        import sys
        with self._lock:
            if self._cancelled:
                return
            attempt = self._attempt
            if attempt <= self._retries:
                # the bounded retry: one more window, stretched by the
                # backoff, and the round records that it needed it
                self._attempt = attempt + 1
                window = self._budget + self._backoff
                sys.stderr.write(
                    f"bench.py watchdog: no result within {self._budget}s "
                    f"(attempt {attempt}) — re-arming once with backoff: "
                    f"{window:g}s more before aborting.\n")
                sys.stderr.flush()
                self._arm(window)
                return
            attempts = self._attempt
        sys.stderr.write(
            f"bench.py watchdog: no result after {attempts} attempt(s) "
            f"({self._budget}s budget) — device init is likely hung; "
            "aborting.\n")
        sys.stderr.flush()
        # the one JSON line the bench harness parses: a structured abort
        # record, not silence
        sys.stdout.write(json.dumps(
            _watchdog_record(self._budget, attempts=attempts)) + "\n")
        sys.stdout.flush()
        os._exit(75)  # EX_TEMPFAIL


def _arm_watchdog():
    """Arm and return the watchdog (None when disabled) — callers cancel
    it once the device proves alive (see ``_measure``).

    Fail loudly instead of hanging forever if device init never returns.
    MXTPU_BENCH_TIMEOUT seconds, default 1500; 0 disables. One bounded
    retry with backoff before aborting (MXTPU_BENCH_RETRIES /
    MXTPU_BENCH_RETRY_BACKOFF_S; see :class:`_BenchWatchdog`).

    Uses a daemon timer + os._exit: a Python signal handler could never run
    while the main thread is blocked inside the C++ device-init call (the
    exact hang being guarded against).
    """
    budget = int(os.environ.get("MXTPU_BENCH_TIMEOUT", "1500"))
    if budget <= 0:
        return
    retries = int(os.environ.get("MXTPU_BENCH_RETRIES", "1"))
    backoff = float(os.environ.get("MXTPU_BENCH_RETRY_BACKOFF_S", "60"))
    return _BenchWatchdog(budget, retries, backoff)


# fwd GMACs per image at 224x224 (the canonical He-et-al. multiply-add
# counts); FLOPs = 2x MACs, train step ≈ 3x fwd, spatial cost scales with
# (img/224)^2
_RESNET_FWD_GMACS_224 = {"resnet18_v1": 1.82, "resnet34_v1": 3.67,
                         "resnet50_v1": 3.87, "resnet101_v1": 7.58,
                         "resnet50_v2": 4.10}


def _measure(trainer, batch, steps, watchdog):
    """The shared steady-state measurement protocol: compile step (watchdog
    armed), cancel watchdog once the device proved alive, pre-place resident
    inputs, warm, optional MXTPU_BENCH_TRACE profiled step, timed loop with
    one honest sync at the end. Returns (dt_seconds, final_loss)."""
    import jax

    trainer.step(*batch).asnumpy()  # init + compile
    if watchdog is not None:
        watchdog.cancel()           # device is alive; don't cap a long sweep
    batch = trainer.place(*batch)   # resident inputs: steady-state loop
    trainer.step(*batch).asnumpy()  # warm
    trace_dir = os.environ.get("MXTPU_BENCH_TRACE")
    if trace_dir:
        with jax.profiler.trace(trace_dir):
            trainer.step(*batch).asnumpy()
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(*batch)
    loss.asnumpy()
    return (time.perf_counter() - t0) / steps, loss


def run_resnet(watchdog) -> dict:
    """imgs/sec/chip on a model-zoo ResNet training step (BASELINE.md row:
    GluonCV train_imagenet.py counterpart). Synthetic NCHW batch; whole step
    (fwd, CE loss, grads, SGD-momentum) compiled to one XLA executable."""
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, parallel
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    model_name = _bench_model("resnet")
    if model_name not in _RESNET_FWD_GMACS_224:    # before any device work
        raise SystemExit(
            f"MXTPU_BENCH_MODEL={model_name!r} has no FLOP table entry; "
            f"choose one of {sorted(_RESNET_FWD_GMACS_224)}")
    B = int(os.environ.get("MXTPU_BENCH_BATCH", "32"))
    img = int(os.environ.get("MXTPU_BENCH_IMG", "224"))
    steps = int(os.environ.get("MXTPU_BENCH_STEPS", "20"))
    classes = 1000
    peak_tflops = _peak_tflops()

    net = vision.get_model(model_name, classes=classes)
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = parallel.make_mesh(devices=jax.devices()[:1])
    trainer = parallel.ShardedTrainer(
        net, lambda out, label: ce(out, label), "sgd",
        {"learning_rate": 0.05, "momentum": 0.9, "multi_precision": True},
        mesh=mesh, n_labels=1)

    rng = onp.random.RandomState(0)
    x = rng.randn(B, 3, img, img).astype(onp.float32)
    y = rng.randint(0, classes, (B,)).astype("float32")
    import jax.numpy as jnp
    dt, loss = _measure(trainer, (x.astype(jnp.bfloat16), y), steps, watchdog)

    imgs_per_sec = B / dt
    fwd_gmacs = _RESNET_FWD_GMACS_224[model_name] * (img / 224.0) ** 2
    flops = 3.0 * 2.0 * fwd_gmacs * 1e9 * B   # train = 3x fwd, FLOP = 2x MAC
    mfu = (flops / dt) / (peak_tflops * 1e12)
    return {
        "metric": f"{model_name}_train_imgs_per_sec_per_chip",
        "value": round(imgs_per_sec, 2),
        "unit": "imgs/sec/chip",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {"step_ms": round(dt * 1e3, 2), "mfu": round(mfu, 4),
                  "batch": B, "img": img,
                  **_device_fields(),
                  "loss": float(loss.asnumpy())},
    }


def _ssd_gmacs(img: int, num_classes: int,
               filters=(32, 64, 128, 128, 128),
               anchors_per_pos: int = 4) -> float:
    """Analytic fwd GMACs for the in-tree SSD (models/ssd.py): VGG-style
    trunk of two 3x3 convs per scale + per-scale cls/box heads."""
    macs = 0.0
    cin, s = 3, img
    feats = []
    for f in filters:
        macs += 9 * cin * f * s * s + 9 * f * f * s * s
        s //= 2
        feats.append((f, s))
        cin = f
    for f, sp in feats[1:]:   # heads run on all scales but the stem
        macs += 9 * f * (anchors_per_pos * (num_classes + 1)) * sp * sp
        macs += 9 * f * (anchors_per_pos * 4) * sp * sp
    return macs / 1e9


def run_ssd(watchdog) -> dict:
    """imgs/sec/chip on the SSD-300 training step (BASELINE.md row:
    GluonCV train_ssd.py counterpart; BASELINE.json configs[4]). Whole step
    — forward, MultiBoxTarget matching, CE+SmoothL1, grads, SGD-momentum —
    compiled to one XLA executable."""
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import models, parallel

    B = int(os.environ.get("MXTPU_BENCH_BATCH", "16"))
    img = int(os.environ.get("MXTPU_BENCH_IMG", "300"))
    steps = int(os.environ.get("MXTPU_BENCH_STEPS", "20"))
    classes = 20
    peak_tflops = _peak_tflops()

    net = models.SSD(num_classes=classes)
    net.initialize(mx.init.Xavier())
    loss = models.SSDTargetLoss()
    mesh = parallel.make_mesh(devices=jax.devices()[:1])
    trainer = parallel.ShardedTrainer(
        net, lambda out, label: loss(out[0], out[1], out[2], label), "sgd",
        {"learning_rate": 0.01, "momentum": 0.9}, mesh=mesh, n_labels=1)

    rng = onp.random.RandomState(0)
    x = rng.rand(B, 3, img, img).astype(onp.float32)
    lab = onp.zeros((B, 1, 5), onp.float32)
    lab[:, 0, 0] = rng.randint(0, classes, B)
    lab[:, 0, 1:3] = 0.2
    lab[:, 0, 3:5] = 0.7
    dt, lval = _measure(trainer, (x, lab), steps, watchdog)

    imgs_per_sec = B / dt
    flops = 3.0 * 2.0 * _ssd_gmacs(img, classes) * 1e9 * B
    mfu = (flops / dt) / (peak_tflops * 1e12)
    return {
        "metric": "ssd300_train_imgs_per_sec_per_chip",
        "value": round(imgs_per_sec, 2),
        "unit": "imgs/sec/chip",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {"step_ms": round(dt * 1e3, 2), "mfu": round(mfu, 4),
                  "batch": B, "img": img,
                  **_device_fields(),
                  "loss": float(lval.asnumpy())},
    }


def _frcnn_gmacs(img: int, filters=(32, 64, 128), A: int = 9, R: int = 128,
                 num_classes: int = 20, roi: int = 7, head: int = 128) -> float:
    """Analytic fwd GMACs for the in-tree Faster-RCNN (models/rcnn.py):
    one 3x3 conv per backbone scale, RPN conv + 1x1 heads, per-roi dense
    head over the ROIAlign crop."""
    macs = 0.0
    cin, s = 3, img
    for f in filters:
        macs += 9 * cin * f * s * s
        s //= 2
        cin = f
    f = filters[-1]
    macs += 9 * f * f * s * s                      # rpn trunk conv
    macs += f * (2 * A + 4 * A) * s * s            # rpn cls/reg 1x1
    C1 = num_classes + 1
    macs += R * (f * roi * roi * head + head * C1 + head * 4 * C1)
    return macs / 1e9


def run_frcnn(watchdog) -> dict:
    """imgs/sec/chip on the Faster-RCNN training step (BASELINE.md row:
    GluonCV train_faster_rcnn.py counterpart; BASELINE.json configs[4]
    names Faster-RCNN alongside SSD). Whole two-stage step — backbone, RPN,
    fixed-shape MultiProposal NMS scan, gt-append, ROIAlign, four-way
    AnchorTarget/ProposalTarget loss, grads, SGD-momentum — compiled to one
    XLA executable."""
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import models, parallel

    B = int(os.environ.get("MXTPU_BENCH_BATCH", "8"))
    img = int(os.environ.get("MXTPU_BENCH_IMG", "224"))
    steps = int(os.environ.get("MXTPU_BENCH_STEPS", "20"))
    classes = 20
    R = 128
    peak_tflops = _peak_tflops()

    net = models.FasterRCNN(
        num_classes=classes, scales=(4, 8, 16), ratios=(0.5, 1, 2),
        feature_stride=8, rpn_pre_nms_top_n=1000, rpn_post_nms_top_n=R,
        rpn_min_size=4, backbone_filters=(32, 64, 128), output_rpn=True)
    net.initialize(mx.init.Xavier())
    loss = models.FasterRCNNTargetLoss(
        num_classes=classes, scales=(4, 8, 16), ratios=(0.5, 1, 2),
        feature_stride=8)
    mesh = parallel.make_mesh(devices=jax.devices()[:1])
    trainer = parallel.ShardedTrainer(
        net, lambda out, gt, info: loss(out[0], out[1], out[2], out[3],
                                        out[4], gt, info),
        "sgd", {"learning_rate": 0.01, "momentum": 0.9}, mesh=mesh,
        n_labels=2)

    rng = onp.random.RandomState(0)
    x = rng.rand(B, 3, img, img).astype(onp.float32)
    gt = onp.full((B, 4, 5), -1.0, onp.float32)     # up to 4 boxes, padded
    for b in range(B):
        for m in range(rng.randint(1, 5)):
            w, h = rng.randint(img // 4, img // 2 + 1, 2)
            x0 = rng.randint(0, img - w)
            y0 = rng.randint(0, img - h)
            gt[b, m] = [rng.randint(0, classes), x0, y0,
                        x0 + w - 1, y0 + h - 1]
    info = onp.tile([img, img, 1.0], (B, 1)).astype(onp.float32)
    dt, lval = _measure(trainer, (x, info, gt, gt, info), steps, watchdog)

    imgs_per_sec = B / dt
    gmacs = _frcnn_gmacs(img, A=9, R=R + gt.shape[1], num_classes=classes)
    flops = 3.0 * 2.0 * gmacs * 1e9 * B
    mfu = (flops / dt) / (peak_tflops * 1e12)
    return {
        "metric": "frcnn_train_imgs_per_sec_per_chip",
        "value": round(imgs_per_sec, 2),
        "unit": "imgs/sec/chip",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {"step_ms": round(dt * 1e3, 2), "mfu": round(mfu, 4),
                  "batch": B, "img": img, "rois": R,
                  **_device_fields(),
                  "loss": float(lval.asnumpy())},
    }


# ---------------------------------------------------------------------------
# --proxy: device-blind perf proxy (trace + cost + host-gap, no TPU)
# ---------------------------------------------------------------------------

#: banked-baseline metrics the --check gate compares (deterministic
#: functions of the traced graph only — wall-time metrics like
#: host_gap_ms vary per machine and are reported, never gated).
#: graphs_per_step: jitted-executable invocations one steady-state
#: training step makes — the fused whole-step capture's contract is 1
#: (guard + optimizer + LR inside the one donated pjit step)
#: peak_live_bytes: the liveness-scan residency high-water mark — a
#: config that silently grows what must fit in HBM fails here even
#: though its traffic metrics look unchanged (the ZeRO-1 class of
#: regression)
_PROXY_GATE_KEYS = ("flops_per_step", "bytes_per_step",
                    "comm_bytes_per_step", "graphs_per_step",
                    "peak_live_bytes")
#: measured fields excluded from the banked file so re-banking on a
#: different machine never churns the committed baseline
_PROXY_VOLATILE_KEYS = ("host_gap_ms", "instrumented_pct",
                        "host_gap_ms_fused", "host_gap_ms_unfused",
                        "host_gap_delta_ms")


def _proxy_sync(out) -> None:
    """Block until a predict result is real (host sees the data)."""
    leaves = out if isinstance(out, (tuple, list)) else (out,)
    for leaf in leaves:
        if hasattr(leaf, "asnumpy"):
            leaf.asnumpy()


def _proxy_record(family: str, iters: int = 4) -> dict:
    """One structured proxy record for a ``models.SERVE_SPECS`` family:
    the cost table over every bucket graph (via ``models.hlo_smoke`` —
    the same entry the hlo-lint gate analyzes) plus a measured host-gap
    probe (compile the example bucket once, then ``iters`` steady-state
    predict calls attributed by ``profiler.step_report``)."""
    from incubator_mxnet_tpu import models, profiler, telemetry
    from incubator_mxnet_tpu.analysis import hlo

    smoke = models.hlo_smoke(family)
    cm = smoke["compiled"]
    rep = hlo.cost(cm, max_graphs=max(8, smoke["table"].num_buckets()))
    head = rep.head
    if head is None:
        raise RuntimeError(
            f"--proxy: family {family!r} traced zero graphs "
            f"(skipped: {rep.skipped}) — cannot price it")
    args = smoke["example_args"]
    _proxy_sync(cm.predict(*args))        # compile the example bucket
    profiler.reset_spans()
    for _ in range(iters):
        _proxy_sync(cm.predict(*args))
    sr = profiler.step_report(frame="serve.predict")
    record = {
        "graphs": len(rep.rows),
        "flops_per_step": rep.model_flops_per_step(),
        "bytes_per_step": rep.bytes_per_step(),
        "peak_live_bytes": rep.peak_live_bytes(),
        "ladder_peak_bytes": rep.ladder_peak_bytes(),
        "comm_bytes_per_step": rep.comm_bytes_per_step(),
        "collective_ops": rep.collective_ops_per_step(),
        "param_bytes": head.param_bytes,
        "activation_bytes": head.activation_bytes,
        "transcendentals": head.transcendentals,
        "eqns": head.eqns,
        "fusible_eqns": head.fusible_eqns,
        "fusion_groups": head.fusion_groups,
        "fusion_candidates": head.fusion_candidates,
        "unknown_eqns": head.unknown_eqns,
        "host_gap_ms": sr["host_gap_ms_mean"],
        "instrumented_pct": sr["instrumented_pct"],
    }
    telemetry.emit("perf.proxy", family=family, **record)
    return record


def _proxy_record_int8(family: str, iters: int = 4) -> dict:
    """One structured proxy record for a ``models.QUANT_FAMILIES``
    calibrated int8 twin (``models.quantized_smoke`` — the same entry the
    quant-lint gate analyzes). Same deterministic cost keys as
    :func:`_proxy_record` so ``_proxy_compare`` gates them identically,
    plus the deterministic ratios vs the f32 twin — the banked proof the
    quantization actually pays (bytes strictly below 1.0)."""
    from incubator_mxnet_tpu import models, profiler, telemetry
    from incubator_mxnet_tpu.analysis import hlo

    qsm = models.quantized_smoke(family)
    cm = qsm["compiled"]
    max_g = max(8, qsm["table"].num_buckets())
    rep = hlo.cost(cm, max_graphs=max_g)
    head = rep.head
    if head is None:
        raise RuntimeError(
            f"--proxy: int8 family {family!r} traced zero graphs "
            f"(skipped: {rep.skipped}) — cannot price it")
    f32 = qsm["f32"]["compiled"]
    f32_rep = hlo.cost(f32, max_graphs=max_g)
    args = qsm["example_args"]
    _proxy_sync(cm.predict(*args))        # compile the example bucket
    profiler.reset_spans()
    for _ in range(iters):
        _proxy_sync(cm.predict(*args))
    sr = profiler.step_report(frame="serve.predict")
    record = {
        "graphs": len(rep.rows),
        "flops_per_step": rep.model_flops_per_step(),
        "bytes_per_step": rep.bytes_per_step(),
        "peak_live_bytes": rep.peak_live_bytes(),
        "ladder_peak_bytes": rep.ladder_peak_bytes(),
        "comm_bytes_per_step": rep.comm_bytes_per_step(),
        "collective_ops": rep.collective_ops_per_step(),
        "param_bytes": head.param_bytes,
        "activation_bytes": head.activation_bytes,
        "transcendentals": head.transcendentals,
        "eqns": head.eqns,
        "fusible_eqns": head.fusible_eqns,
        "fusion_groups": head.fusion_groups,
        "fusion_candidates": head.fusion_candidates,
        "unknown_eqns": head.unknown_eqns,
        "bytes_ratio_vs_f32": (rep.bytes_per_step()
                               / max(f32_rep.bytes_per_step(), 1)),
        "ladder_peak_ratio_vs_f32": (rep.ladder_peak_bytes()
                                     / max(f32_rep.ladder_peak_bytes(), 1)),
        "host_gap_ms": sr["host_gap_ms_mean"],
        "instrumented_pct": sr["instrumented_pct"],
    }
    telemetry.emit("perf.proxy", family=family + "_int8", **record)
    return record


def _proxy_compare(current: dict, banked: dict, tol: float):
    """Gate the deterministic metrics against the banked baseline.
    Returns ``(failures, warnings)`` — a metric above ``1 + tol`` times
    the banked value is a regression (fail), below ``1 - tol`` an
    improvement (warn, so the baseline gets re-banked)."""
    failures, warnings = [], []
    for fam in sorted(current):
        rec, base = current[fam], banked.get(fam)
        if base is None:
            warnings.append(f"{fam}: no banked baseline — re-bank "
                            "PERF_PROXY.json (bench.py --proxy --out)")
            continue
        for key in _PROXY_GATE_KEYS:
            b, c = base.get(key), rec.get(key)
            if b is None or c is None:
                continue
            if not b:
                # a zero baseline has no ratio: any appearance IS the
                # regression (e.g. collectives sneaking into a
                # single-device serving graph, comm 0 -> N bytes)
                if c:
                    failures.append(
                        f"{fam}.{key}: {c:.6g} vs banked 0 — the metric "
                        "appeared from zero (new per-step cost)")
                continue
            ratio = c / b
            if ratio > 1.0 + tol:
                failures.append(
                    f"{fam}.{key}: {c:.6g} vs banked {b:.6g} "
                    f"(+{(ratio - 1) * 100:.1f}% > {tol * 100:.0f}% "
                    "tolerance) — the compiled graph got more expensive")
            elif ratio < 1.0 - tol:
                warnings.append(
                    f"{fam}.{key}: {c:.6g} vs banked {b:.6g} "
                    f"({(ratio - 1) * 100:.1f}%) — improvement; re-bank "
                    "the baseline (bench.py --proxy --out PERF_PROXY.json)")
    return failures, warnings


def _fused_step_record(steps: int = 6) -> dict:
    """Device-blind probe of whole-step capture: the SAME tiny guarded +
    LR-scheduled trainer stepped with the fused step (guard verdict +
    schedule position inside the one donated pjit graph — the default)
    and with ``MXTPU_FUSED_STEP=0`` (the before-capture shape: separate
    jitted finite check, per-step host LR eval + transfer). Banked
    metrics are deterministic — ``graphs_per_step`` (jitted-executable
    invocations per steady step: 1 fused vs 2 unfused) and the fused
    train graph's cost-table numbers; the measured host-gap delta is
    reported, never gated."""
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import fault, gluon, lr_scheduler, parallel, \
        profiler, telemetry
    from incubator_mxnet_tpu.analysis import hlo

    rng = onp.random.RandomState(0)
    x = rng.randn(16, 64).astype("float32")
    y = rng.randint(0, 8, (16,)).astype("float32")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def probe(fused):
        prev = os.environ.get("MXTPU_FUSED_STEP")
        os.environ["MXTPU_FUSED_STEP"] = "1" if fused else "0"
        try:
            mx.random.seed(7)
            net = gluon.nn.HybridSequential()
            net.add(gluon.nn.Dense(128, activation="relu", in_units=64),
                    gluon.nn.Dense(8, in_units=128))
            net.initialize(mx.init.Xavier())
            tr = parallel.ShardedTrainer(
                net, loss_fn, "adamw",
                {"learning_rate": 1e-3,
                 "lr_scheduler": lr_scheduler.CosineScheduler(
                     max_update=1000, base_lr=1e-3)},
                mesh=parallel.make_mesh(devices=jax.devices()[:1]),
                guard=fault.StepGuard(policy="warn"))
            tr.step(x, y).asnumpy()        # init + compile
            batch = tr.place(x, y)         # steady state: resident inputs
            tr.step(*batch).asnumpy()      # warm
            profiler.reset_spans()
            for _ in range(steps):
                tr.step(*batch)
            sr = profiler.step_report(frame="step")
            return tr, sr
        finally:
            if prev is None:
                os.environ.pop("MXTPU_FUSED_STEP", None)
            else:
                os.environ["MXTPU_FUSED_STEP"] = prev

    tr_fused, sr_fused = probe(True)
    graphs_fused = tr_fused.last_step_graphs
    tr_unfused, sr_unfused = probe(False)
    graphs_unfused = tr_unfused.last_step_graphs
    rep = hlo.cost(tr_fused, sample_args=(x, y))
    gap_f = sr_fused["host_gap_ms_mean"]
    gap_u = sr_unfused["host_gap_ms_mean"]
    record = {
        "graphs": len(rep.rows),
        "graphs_per_step": graphs_fused,
        "graphs_per_step_unfused": graphs_unfused,
        "flops_per_step": rep.model_flops_per_step(),
        "bytes_per_step": rep.bytes_per_step(),
        "peak_live_bytes": rep.peak_live_bytes(),
        "comm_bytes_per_step": rep.comm_bytes_per_step(),
        "host_gap_ms_fused": gap_f,
        "host_gap_ms_unfused": gap_u,
        "host_gap_delta_ms": round(gap_u - gap_f, 4),
    }
    telemetry.emit("perf.proxy", family="fused_step", **record)
    return record


def _mesh_step_record(steps: int = 6) -> dict:
    """Device-blind probe of the compiled mesh training step on forced
    host devices: the SAME tiny model stepped on an 8-device dp×tp mesh
    (the default pjit path) and on one device, host dispatch gap measured
    by ``profiler.step_report`` over the trainer's own ``step`` frames,
    the mesh step graph priced by ``analysis.hlo.cost`` (collective verbs
    + comm bytes included). ``host_gap_ms_unsharded`` probes the PRE-pjit
    execution path — unsharded (one device), gradients through the
    per-parameter kvstore Python loop (``MXTPU_KVSTORE_FALLBACK=1``) —
    the acceptance signal is ``host_gap_ms_mesh`` at or below it: the
    compiled mesh step does strictly less host work than the loop it
    replaced."""
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, parallel, profiler, telemetry
    from incubator_mxnet_tpu.analysis import hlo

    if len(jax.devices()) < 8:
        raise RuntimeError(
            "--mesh-step needs 8 forced host devices "
            "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    rng = onp.random.RandomState(0)
    x = rng.randn(16, 64).astype("float32")
    y = rng.randint(0, 8, (16,)).astype("float32")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def probe(mesh, fallback=False):
        # pin the path EXPLICITLY both ways: a user-set
        # MXTPU_KVSTORE_FALLBACK=1 in the environment must not turn the
        # "mesh" half of the comparison into a second loop measurement
        prev = os.environ.get("MXTPU_KVSTORE_FALLBACK")
        os.environ["MXTPU_KVSTORE_FALLBACK"] = "1" if fallback else "0"
        try:
            mx.random.seed(7)
            net = gluon.nn.HybridSequential()
            net.add(gluon.nn.Dense(128, activation="relu", in_units=64),
                    gluon.nn.Dense(8, in_units=128))
            net.initialize(mx.init.Xavier())
            tr = parallel.ShardedTrainer(net, loss_fn, "adamw",
                                         {"learning_rate": 1e-3}, mesh=mesh)
            tr.step(x, y).asnumpy()        # init + compile
            batch = tr.place(x, y)         # steady state: resident inputs
            tr.step(*batch).asnumpy()      # warm
            profiler.reset_spans()
            for _ in range(steps):
                tr.step(*batch)
            tr.sync_to_block()             # one honest sync at the end
            sr = profiler.step_report(frame="step")
            return tr, sr
        finally:
            if prev is None:
                os.environ.pop("MXTPU_KVSTORE_FALLBACK", None)
            else:
                os.environ["MXTPU_KVSTORE_FALLBACK"] = prev

    tr_mesh, sr_mesh = probe(parallel.make_mesh(dp=4, tp=2))
    # the pre-pjit path: unsharded, per-parameter kvstore loop
    _, sr_one = probe(parallel.make_mesh(devices=jax.devices()[:1]),
                      fallback=True)
    rep = hlo.cost(tr_mesh, sample_args=(x, y))
    head = rep.head
    record = {
        "mesh": "dp=4,tp=2", "steps": steps,
        "flops_per_step": rep.model_flops_per_step(),
        "bytes_per_step": rep.bytes_per_step(),
        "comm_bytes_per_step": rep.comm_bytes_per_step(),
        # int total under the SAME key shape as the family records; the
        # verb split rides under its own name
        "collective_ops": rep.collective_ops_per_step(),
        "collective_ops_by_verb": dict(head.collective_ops) if head else {},
        "host_gap_ms_mesh": sr_mesh["host_gap_ms_mean"],
        "host_gap_ms_unsharded": sr_one["host_gap_ms_mean"],
        "path": tr_mesh.last_path,
    }
    telemetry.emit("perf.proxy", family="mesh_step", **record)
    return record


def run_proxy(argv) -> int:
    """CPU-only proxy bench: one record per serving family, optional
    banked write (``--out``) and tolerance gate (``--check``)."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="bench.py --proxy",
        description="device-blind perf proxy over the serving zoo")
    ap.add_argument("--proxy", action="store_true")
    ap.add_argument("--families", default="all",
                    help="comma-separated models.SERVE_SPECS families, "
                         "or 'all' (default)")
    ap.add_argument("--mesh-step", action="store_true",
                    help="also probe the compiled mesh training step on 8 "
                         "forced host devices (host-gap vs unsharded + "
                         "collective comm record; reported, never banked)")
    ap.add_argument("--out", default=None,
                    help="write/refresh the banked baseline JSON here")
    ap.add_argument("--check", default=None,
                    help="banked baseline JSON to gate against")
    ap.add_argument("--tolerance", type=float, default=0.05,
                    help="relative gate tolerance (default 0.05 = ±5%%)")
    ap.add_argument("--iters", type=int, default=4,
                    help="steady-state predict calls for the host-gap "
                         "probe")
    args = ap.parse_args(argv)

    # the proxy is device-blind by design: pin cpu so it never takes the
    # chip, which one process owns at a time (same as tools/mxlint); the
    # mesh-step probe needs the 8-device virtual mesh. APPEND the device-count flag
    # when absent (same dance as tools/multichip_smoke) — setdefault would
    # let any pre-set XLA_FLAGS silently defeat it.
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count="
            + ("8" if args.mesh_step else "1")).strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    from incubator_mxnet_tpu import models

    if args.families == "all":
        families = sorted(models.SERVE_SPECS)
    else:
        families = [f.strip() for f in args.families.split(",") if f.strip()]
        unknown = [f for f in families if f not in models.SERVE_SPECS]
        if unknown:
            print(f"bench.py --proxy: unknown families {unknown}; known: "
                  f"{sorted(models.SERVE_SPECS)}", file=sys.stderr)
            return 2

    try:
        fams = {f: _proxy_record(f, iters=args.iters) for f in families}
        # the calibrated int8 twins ride along for every selected family
        # that has one — banked under their own "int8" section so the
        # "families" set stays exactly models.SERVE_SPECS
        int8 = {f + "_int8": _proxy_record_int8(f, iters=args.iters)
                for f in families if f in models.QUANT_FAMILIES}
    except RuntimeError as e:
        print(f"bench.py {e}", file=sys.stderr)
        return 2
    # the train-side record: whole-step capture metrics (fused vs
    # unfused graph counts + the fused step graph's deterministic cost),
    # banked under its own "train" section so the serve-family set stays
    # exactly models.SERVE_SPECS
    train = {"fused_step": _fused_step_record()}
    mesh_step = None
    if args.mesh_step:
        try:
            mesh_step = _mesh_step_record()
        except RuntimeError as e:
            # the probe needs 8 forced host devices; a device shortfall
            # must not void the family gate that needed nothing from it
            print(f"bench.py --mesh-step: {e}", file=sys.stderr)
            mesh_step = {"error": str(e)}

    gate = None
    failures, warns = [], []
    if args.check:
        try:
            with open(args.check) as f:
                baseline = json.load(f)
        except (OSError, ValueError) as e:
            print(f"bench.py --proxy: cannot read baseline {args.check}: "
                  f"{e}", file=sys.stderr)
            return 2
        banked_jax = baseline.get("jax")
        if banked_jax and banked_jax != jax.__version__:
            # the cost table is a function of the jaxpr this jax version
            # emits — a drifted gate result needs this context to diagnose
            print(f"bench.py --proxy: note: baseline was banked on jax "
                  f"{banked_jax}, running jax {jax.__version__} — lowering "
                  "differences can shift the deterministic metrics",
                  file=sys.stderr)
        failures, warns = _proxy_compare(
            fams, baseline.get("families", {}), args.tolerance)
        q_fail, q_warn = _proxy_compare(
            int8, baseline.get("int8", {}), args.tolerance)
        t_fail, t_warn = _proxy_compare(
            train, baseline.get("train", {}), args.tolerance)
        failures += q_fail + t_fail
        warns += q_warn + t_warn
        gate = {"baseline": args.check, "tolerance": args.tolerance,
                "failures": failures, "warnings": warns}
        # the whole-trajectory view rides along with the per-graph gate:
        # best banked config, blind-round count, and any measured-round
        # regression flag from the merged BENCH/BASELINE/PERF_PROXY
        # artifacts (tools/perf_history.py — flags surface as warnings
        # here; the goodput-smoke CI job gates on them via --check)
        try:
            from tools import perf_history as _ph
            hist_root = os.path.dirname(os.path.abspath(args.check)) or "."
            gate["perf_history"] = _ph.summary(hist_root, args.tolerance)
            for flag in gate["perf_history"]["regressions"]:
                warns.append(f"perf_history: {flag}")
        except Exception as e:  # noqa: BLE001 — the trajectory is
            gate["perf_history"] = {"error": str(e)}  # context, not a gate
        for w in warns:
            print(f"bench.py --proxy: WARN {w}", file=sys.stderr)
        for fl in failures:
            print(f"bench.py --proxy: FAIL {fl}", file=sys.stderr)

    if args.out:
        banked = {"format": 1, "tolerance": args.tolerance,
                  "generated_by": "python bench.py --proxy --out",
                  "jax": jax.__version__,
                  "families": {
                      f: {k: v for k, v in rec.items()
                          if k not in _PROXY_VOLATILE_KEYS}
                      for f, rec in sorted(fams.items())},
                  "int8": {
                      f: {k: v for k, v in rec.items()
                          if k not in _PROXY_VOLATILE_KEYS}
                      for f, rec in sorted(int8.items())},
                  "train": {
                      f: {k: v for k, v in rec.items()
                          if k not in _PROXY_VOLATILE_KEYS}
                      for f, rec in sorted(train.items())}}
        tmp = f"{args.out}.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(banked, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, args.out)

    total_flops = sum(rec["flops_per_step"] for rec in fams.values())
    result = {
        "metric": "perf_proxy_flops_per_step",
        "value": total_flops,
        "unit": "flops/step (sum over families)",
        "vs_baseline": None,
        "extra": {"families": fams, "int8": int8, "train": train,
                  "gate": gate, "backend": jax.default_backend()},
    }
    if mesh_step is not None:
        result["extra"]["mesh_step"] = mesh_step
    print(json.dumps(result))
    return 1 if failures else 0


_BERT_VOCAB = 30522


def bert_batch(B: int, L: int, vocab: int = _BERT_VOCAB) -> tuple:
    """THE seeded synthetic pretraining batch: ``(ids, token_types,
    valid_length, masked_positions, mlm_labels, mlm_weights, nsp_labels)``
    with BERT's 15% masking rate. Shared with ``chip_smoke.py``."""
    P = max(1, round(0.15 * L))
    rng = onp.random.RandomState(0)
    ids = rng.randint(0, vocab, (B, L)).astype("int32")
    tt = rng.randint(0, 2, (B, L)).astype("int32")
    vl = onp.full((B,), L, "float32")
    pos = rng.randint(0, L, (B, P)).astype("int32")
    mlm_lab = rng.randint(0, vocab, (B, P)).astype("float32")
    mlm_w = onp.ones((B, P), "float32")
    nsp = rng.randint(0, 2, (B,)).astype("float32")
    return (ids, tt, vl, pos, mlm_lab, mlm_w, nsp)


def bert_trainer(model_name: str, L: int, mesh, vocab: int = _BERT_VOCAB,
                 dropout: float = 0.1, learning_rate: float = 1e-4,
                 **overrides):
    """``(net, trainer)`` of THE BERT pretraining job: bf16 parameters
    with fp32 masters under AdamW, Megatron sharding rules, the whole
    step one compiled program on ``mesh``. ``overrides`` reach
    ``models.get_bert`` (``remat=``, a ``num_layers=`` cut). Shared with
    ``chip_smoke.py``."""
    from incubator_mxnet_tpu import models, parallel

    net = models.get_bert(model_name, vocab_size=vocab, max_length=L,
                          dropout=dropout, dtype="bfloat16", **overrides)
    net.initialize()
    trainer = parallel.ShardedTrainer(
        net, models.bert_pretrain_loss, "adamw",
        {"learning_rate": learning_rate, "multi_precision": True}, mesh=mesh,
        rules=models.bert_sharding_rules(), n_labels=3,
        # banked autotune winners (MXTPU_AUTOTUNE_DIR) apply at build —
        # a tuned config is reproducible per key, not a one-off env
        # recipe pasted into a shell
        autotune_key="bert")
    return net, trainer


def run_bert(watchdog) -> dict:
    """tokens/sec/chip on the BERT pretraining step (the BASELINE.json
    north-star workload): embeddings, encoder on flash attention, MLM+NSP
    heads, loss, grads, AdamW — one XLA executable on one device."""
    import jax
    from incubator_mxnet_tpu import models, parallel

    model_name = _bench_model("bert")
    B = int(os.environ.get("MXTPU_BENCH_BATCH", "8"))
    L = int(os.environ.get("MXTPU_BENCH_SEQ", "512"))
    peak_tflops = _peak_tflops()
    steps = int(os.environ.get("MXTPU_BENCH_STEPS", "20"))
    remat = os.environ.get("MXTPU_BENCH_REMAT", "0") == "1"
    dropout = float(os.environ.get("MXTPU_BENCH_DROPOUT", "0.1"))
    cfg = models.bert.BERT_CONFIGS[model_name]
    net, trainer = bert_trainer(
        model_name, L, parallel.make_mesh(devices=jax.devices()[:1]),
        dropout=dropout, remat=remat)

    dt, loss = _measure(trainer, bert_batch(B, L), steps, watchdog)

    tokens_per_sec = B * L / dt
    # Transformer pretraining FLOPs: 6 * n_params * n_tokens for the
    # matmul-dominated path + attention term 12 * layers * units * L² * B
    # (fwd+bwd), the standard PaLM-appendix accounting.
    n_params = sum(int(onp.prod(p.shape))
                   for _, p in net.collect_params().items())
    flops = 6 * n_params * B * L + 12 * cfg["num_layers"] * cfg["units"] * L * L * B
    mfu = (flops / dt) / (peak_tflops * 1e12)
    return {
        "metric": f"{model_name}_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {"step_ms": round(dt * 1e3, 2), "mfu": round(mfu, 4),
                  "batch": B, "seq": L, "remat": remat, "params": n_params,
                  **_device_fields(),
                  "loss": float(loss.asnumpy())},
    }


_RUNNERS = {"bert": run_bert, "resnet": run_resnet, "ssd": run_ssd,
            "frcnn": run_frcnn}


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    if "--proxy" in argv:
        raise SystemExit(run_proxy(argv))
    watchdog = _arm_watchdog()
    _require_chip()
    use_compile_cache()
    run = _RUNNERS.get(_bench_workload(), run_bert)
    print(json.dumps(run(watchdog)))


if __name__ == "__main__":
    main()
