"""Misc utilities (reference: python/mxnet/util.py + dmlc::GetEnv plane).

The env-var catalog (SURVEY §5.6) is centralized here: every runtime knob the
framework reads goes through :func:`getenv` with its default, and
:func:`env_var_doc` renders the ``env_var.md``-style table.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Dict, Optional

__all__ = ["getenv", "setenv", "env_var_doc", "makedirs", "use_np_shape",
           "is_np_shape", "is_np_array", "set_np", "reset_np", "np_shape",
           "nearest_rank_percentile", "parse_size", "hbm_budget_bytes",
           "device_peaks", "peak_tflops", "roofline_peaks",
           "DEVICE_PEAKS_BY_KIND", "RANKING_NOMINAL_CHIP"]


def parse_size(s: str) -> int:
    """Byte-size string → int bytes: plain/float forms (``"123"``,
    ``"16e9"``) and binary suffixes (``"512M"``, ``"16G"``, ``"1.5T"``,
    optional trailing ``B``/``iB``). THE parse ``MXTPU_HBM_BUDGET``
    consumers share (the MX709 pass, the serve staging preflight, the
    autotune feasibility constraint, the memory ledger)."""
    mult = 1
    low = str(s).strip().lower()
    # strip an optional iB/B after a unit letter, then the unit letter
    if low.endswith("ib"):
        low = low[:-2]
    elif low.endswith("b"):
        low = low[:-1]
    if low and low[-1] in "kmgt":
        mult = {"k": 1 << 10, "m": 1 << 20,
                "g": 1 << 30, "t": 1 << 40}[low[-1]]
        low = low[:-1]
    try:
        if not low:               # suffix-only input ("B", "iB", "G", " ")
            raise ValueError(low)
        return int(float(low) * mult)
    except ValueError:
        raise ValueError(f"cannot parse byte size {s!r} (want e.g. "
                         "'2000000000', '16e9', '512M', '16G')") from None


def hbm_budget_bytes() -> Optional[int]:
    """``MXTPU_HBM_BUDGET`` parsed to bytes via :func:`parse_size`, or
    ``None`` when unset — THE single budget read shared by the MX709
    static pass (``analysis.hlo.cost``), the serve staging preflight,
    the autotune feasibility constraint, and the ``telemetry.memory``
    ledger, so the gates can never read different capacities."""
    raw = getenv("MXTPU_HBM_BUDGET")
    return parse_size(raw) if raw else None


#: per-chip peaks keyed by the lower-cased ``jax.Device.device_kind``,
#: matched whole (never by substring: "tpu v5 lite" must not read the
#: "tpu v5" row): ``(bf16 TFLOP/s, HBM GB/s, ICI GB/s)``. Source: Google
#: Cloud documentation, "TPU v5e" (197 TFLOP/s, 819 GB/s, 1,600 Gbit/s
#: of interconnect) and "TPU v5p" (459 TFLOP/s, 2,765 GB/s, 4,800
#: Gbit/s). THE single table ``benchmark/autotune.py`` and
#: ``telemetry.goodput`` read. A kind that is not here is an error on
#: the measuring path: add its row, with its source, before measuring.
DEVICE_PEAKS_BY_KIND = {
    "tpu v5 lite": (197.0, 819.0, 200.0),
    "tpu v5e": (197.0, 819.0, 200.0),
    "tpu v5": (459.0, 2765.0, 600.0),
    "tpu v5p": (459.0, 2765.0, 600.0),
}
#: the unit chip trace-only callers rank candidates against when no
#: known chip is attached (the autotuner's roofline score, goodput's
#: predicted MFU on CPU): ``(TFLOP/s, HBM GB/s, ICI
#: GB/s)``. It keeps rankings deterministic; it is no device's peak and
#: nothing divided by it is a device metric.
RANKING_NOMINAL_CHIP = (459.0, 1200.0, 90.0)


def _device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind


def device_peaks(kind: Optional[str] = None) -> tuple:
    """``(bf16 TFLOP/s, HBM GB/s, ICI GB/s)`` of the attached chip (or of
    ``kind``) from :data:`DEVICE_PEAKS_BY_KIND`. Raises
    :class:`~incubator_mxnet_tpu.base.MXNetError` for a device that has
    no row — CPU included — so no utilization is ever computed against
    another chip's peak."""
    kind = _device_kind() if kind is None else kind
    try:
        return DEVICE_PEAKS_BY_KIND[kind.lower()]
    except KeyError:
        from .base import MXNetError
        raise MXNetError(
            f"no peak-table row for device kind {kind!r} (known: "
            f"{sorted(DEVICE_PEAKS_BY_KIND)}); add one to "
            "util.DEVICE_PEAKS_BY_KIND with its source, or set "
            "MXTPU_PEAK_TFLOPS") from None


def peak_tflops() -> float:
    """Per-chip bf16 peak TFLOP/s for a MEASURED utilization
    (``MXTPU_PEAK_TFLOPS`` overrides, else :func:`device_peaks` of the
    attached chip). An unknown device kind raises."""
    env = os.environ.get("MXTPU_PEAK_TFLOPS")
    if env:
        return float(env)
    return device_peaks()[0]


def roofline_peaks() -> tuple:
    """``(peak_flops_per_s, hbm_bytes_per_s, ici_bytes_per_s)`` — the
    roofline denominators of the trace-only callers: the attached
    chip's row when it has one, else :data:`RANKING_NOMINAL_CHIP`
    (``MXTPU_PEAK_TFLOPS`` / ``MXTPU_PEAK_GBPS`` / ``MXTPU_ICI_GBPS``
    override either)."""
    tf, bw, ici = DEVICE_PEAKS_BY_KIND.get(_device_kind().lower(),
                                           RANKING_NOMINAL_CHIP)
    tf = float(os.environ.get("MXTPU_PEAK_TFLOPS") or tf)
    bw = float(os.environ.get("MXTPU_PEAK_GBPS") or bw)
    ici = float(os.environ.get("MXTPU_ICI_GBPS") or ici)
    return tf * 1e12, bw * 1e9, ici * 1e9


def nearest_rank_percentile(sorted_vals, q: float) -> float:
    """Nearest-rank percentile over an already-sorted sample list — THE
    shared kernel for every host-side latency summary (``metric.
    Percentile``, the ``profiler`` span recorder). Returns NaN on empty."""
    if not sorted_vals:
        return float("nan")
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]

#: name -> (default, description). The single catalog, reference
#: docs/static_site/src/pages/api/faq/env_var.md.
ENV_VARS: Dict[str, tuple] = {
    "MXNET_ENGINE_TYPE": ("XLA", "Execution engine; XLA async dispatch "
                          "replaces ThreadedEnginePerDevice. 'Naive' maps to "
                          "jax.disable_jit debugging."),
    "MXNET_ENFORCE_DETERMINISM": ("0", "Request deterministic XLA lowering."),
    "MXNET_USE_FUSION": ("1", "XLA fusion is always on; kept for parity."),
    "MXNET_GPU_MEM_POOL_RESERVE": ("0", "PjRt manages HBM pooling."),
    "MXNET_KVSTORE_BIGARRAY_BOUND": ("1000000", "Kept for parity; sharding "
                                     "rules make the layout decision."),
    "MXTPU_KVSTORE_FALLBACK": ("0", "1 opts into the per-parameter Python "
                               "kvstore push/pull loop (the async-PS "
                               "scenario): ShardedTrainer.step exchanges "
                               "gradients host-side per key with the "
                               "store client's retry/exactly-once "
                               "semantics intact. Default 0: gradient "
                               "exchange is compiled XLA collectives — "
                               "the pjit step (ShardedTrainer) or one "
                               "batched store collective (gluon.Trainer)."),
    "MXNET_TEST_SEED": ("", "Fix the test RNG seed."),
    "MXTPU_SERVE_DEADLINE_MS": ("5", "Max milliseconds the oldest queued "
                                "request waits before the serve "
                                "DynamicBatcher flushes a partial batch."),
    "MXTPU_SERVE_QUEUE_LIMIT": ("1024", "Bounded serve request-queue size; "
                                "a full queue rejects submits "
                                "(backpressure, QueueFullError)."),
    "MXTPU_SERVE_MAX_BATCH": ("0", "Cap on the coalesced serve batch size; "
                              "0 = the bucket table's largest batch "
                              "bucket."),
    "MXTPU_SERVE_BENCH_MODEL": ("mlp", "serve_bench workload "
                                "(mlp|lenet|bert)."),
    "MXTPU_SERVE_BENCH_N": ("1000", "serve_bench dynamic-section request "
                            "count."),
    "MXTPU_SERVE_REQUEST_TIMEOUT_S": ("30", "Per-request deadline: the "
                                      "TCP front end and the HA router "
                                      "wait this long for a result, then "
                                      "return a structured "
                                      "deadline_exceeded reply with "
                                      "retry_after instead of a bare "
                                      "exception."),
    "MXTPU_SERVE_HEARTBEAT_MS": ("100", "Router health-check interval: "
                                 "each sweep probes every replica's "
                                 "state, queue depth and flush "
                                 "progress."),
    "MXTPU_SERVE_STALL_S": ("2", "Queued requests with zero flush "
                            "progress for this long mark a replica "
                            "wedged — it is killed and restarted by the "
                            "router's health loop."),
    "MXTPU_SERVE_RETRIES": ("2", "Failover retries per idempotent "
                            "request: each retry moves to a surviving "
                            "replica with capped exponential backoff; "
                            "exhaustion sheds explicitly with "
                            "retry_after."),
    "MXTPU_SERVE_RETRY_BACKOFF_MS": ("10", "Base backoff between router "
                                     "failover retries (doubles per "
                                     "attempt, capped at 200 ms, never "
                                     "past the request deadline)."),
    "MXTPU_SERVE_HEDGE_MS": ("0", "After this many ms without a result "
                             "the router races ONE hedged duplicate on "
                             "a second healthy replica (first result "
                             "wins); 0 disables hedging."),
    "MXTPU_SERVE_SHED_DEPTH": ("0", "Overload shedding: when EVERY "
                               "healthy replica's queue is at/over this "
                               "depth, new requests are rejected with "
                               "retry_after instead of queueing; 0 "
                               "disables (per-replica backpressure "
                               "still applies)."),
    "MXTPU_SERVE_TENANT_INFLIGHT": ("0", "Per-tenant admission cap: "
                                    "concurrent router requests a single "
                                    "tenant may hold before being shed "
                                    "with retry_after; 0 = unlimited."),
    "MXTPU_SERVE_TENANT_TOKENS_PER_S": ("0", "Per-tenant decode QoS: "
                                        "sustained generated-tokens/sec "
                                        "budget (token bucket); requests "
                                        "whose estimated tokens would "
                                        "breach it are shed with "
                                        "retry_after BEFORE queueing; "
                                        "0 = unlimited."),
    "MXTPU_SERVE_TENANT_TOKEN_BURST": ("0", "Token-bucket burst depth for "
                                       "MXTPU_SERVE_TENANT_TOKENS_PER_S "
                                       "(tokens); 0 = one second's "
                                       "budget."),
    "MXTPU_DECODE_MAX_BATCH": ("8", "Decode batch rows: concurrent "
                               "sequences one DecodeEngine steps per "
                               "token boundary (the fixed shape of the "
                               "AOT decode executable)."),
    "MXTPU_DECODE_BLOCK_SIZE": ("16", "Tokens per paged-KV-cache page; "
                                "pages are the allocation unit of the "
                                "decode block pool."),
    "MXTPU_DECODE_MAX_TOKENS": ("64", "Generation cap per sequence = "
                                "pages-per-sequence x block size; must "
                                "fit the model's position table."),
    "MXTPU_DECODE_QUEUE_LIMIT": ("256", "Bounded decode request-queue "
                                 "size; past it submit() sheds with "
                                 "QueueFullError (backpressure)."),
    "MXTPU_DECODE_MAX_REQUEUES": ("3", "Cache-pressure admissions bounce "
                                  "back to the queue at most this many "
                                  "times before the stream is shed with "
                                  "CacheExhausted."),
    "MXTPU_PEAK_TFLOPS": ("", "Override per-chip peak for MFU accounting."),
    "MXTPU_FLASH_ATTENTION": ("1", "Enable the Pallas flash-attention path."),
    "MXTPU_FLASH_BK": ("", "Flash-attention key/value block size override "
                       "(ops/pallas/flash_attention.py); unset = "
                       "auto-sized per sequence length. An autotune "
                       "dimension: benchmark/autotune.py sweeps it and "
                       "banked winners apply it at build time."),
    "MXTPU_FLASH_BQ": ("", "Flash-attention query block size override; "
                       "unset = auto-sized. Autotune dimension like "
                       "MXTPU_FLASH_BK."),
    "MXTPU_EMBED_ONEHOT_GRAD": ("0", "Embedding weight gradient as a one-hot "
                                "MXU matmul instead of scatter-add (sweep "
                                "candidate; numerically identical)."),
    "MXTPU_AUTOTUNE_DIR": ("", "On-disk autotune cache root. When set, "
                           "ShardedTrainer and serve.CompiledModel "
                           "consult it at build time and overlay the "
                           "banked winner's env knobs (flash block "
                           "sizes, embed-grad path) for exactly the "
                           "trace/compile scope; explicitly user-set "
                           "variables always win. Unset = no consult "
                           "(one env read on the build path)."),
    "MXTPU_AUTOTUNE": ("1", "0 disables autotune-cache consults even "
                       "when MXTPU_AUTOTUNE_DIR is set (kill switch "
                       "for debugging a suspect banked winner)."),
    "MXTPU_AUTOTUNE_BUDGET": ("16", "Default candidate budget per family "
                              "for benchmark/autotune.py when --budget "
                              "is not given (candidates enumerate in "
                              "deterministic space order and truncate "
                              "here)."),
    "MXTPU_QUANT_PERCENTILE": ("99.99", "Calibration percentile the "
                               "quantization Observer paths use when no "
                               "explicit percentile is passed "
                               "(quantization.quantize_model, "
                               "Observer.ranges, models.quantized_smoke). "
                               "100 = exact min/max (outlier-hostage "
                               "ranges); 99.99 clips the histogram tail "
                               "the TensorRT way."),
    "MXTPU_INT8_FAMILY": ("lenet", "Quantized zoo family "
                          "benchmark/int8_probe.py censuses for its "
                          "per-bucket MX71x summary (any "
                          "models.QUANT_FAMILIES member)."),
    "MXTPU_HBM_BUDGET": ("", "Per-chip device-memory budget in bytes "
                         "(K/M/G suffixes and float forms accepted). "
                         "When set: the MX709 hlo_memory pass errors on "
                         "any graph (or summed serve bucket ladder) "
                         "whose liveness-scan peak_live_bytes exceeds "
                         "it, serve.ModelRegistry.load rejects "
                         "over-budget ladders at staging while the "
                         "active version keeps serving, "
                         "benchmark/autotune.py excludes infeasible "
                         "candidates from winner election, and the "
                         "telemetry.memory ledger publishes it as "
                         "mxtpu_memory_budget_bytes / uses it as the "
                         "capacity in context.tpu_memory_info's "
                         "ledger fallback. Unset = no memory gating."),
    "MXTPU_MEMORY_SAMPLE_S": ("0", "Interval (seconds) of the "
                              "telemetry.memory background sampler "
                              "(named daemon thread mx-memory-ledger): "
                              "each tick reads jax.live_arrays() + "
                              "device memory_stats + registered site "
                              "providers into mxtpu_memory_* gauges and "
                              "runs the leak watchdog (monotonic growth "
                              "across a full 8-sample window >= 1 MiB "
                              "emits a memory.leak warning event). "
                              "0 = sampler off (manual sample() calls "
                              "still work)."),
    "MXTPU_NUMERICS": ("", "In-graph numerics telemetry "
                       "(telemetry.numerics): 'summary' makes the "
                       "trainer's pjit step and serve.CompiledModel "
                       "return per-site min/max/mean/rms/zero-fraction/"
                       "finite-fraction vectors (param:/grad:/act:/"
                       "serve.out: sites) as extra pinned outputs of "
                       "the SAME jitted graph; 'hist' additionally "
                       "accumulates log2-magnitude histograms per site "
                       "(quantization.Observer calibration tables). "
                       "Unset/other = off: the traced graphs are "
                       "byte-identical to an uninstrumented build. "
                       "Resolved at "
                       "build time like the autotune consult."),
    "MXTPU_NUMERICS_EVERY": ("16", "Host-side decimation of numerics "
                             "stats: the stat outputs are synced (and "
                             "folded into numerics.step events, "
                             "mxtpu_numerics_* gauges, the per-site "
                             "ring) every N steps/requests, riding the "
                             "guard's existing device read — never an "
                             "extra per-step round trip."),
    "MXTPU_NUMERICS_SITES": ("", "Comma-separated fnmatch allowlist "
                             "over numerics site names (e.g. "
                             "'grad:*,act:*attn*'); empty = every "
                             "site. Filtering happens at trace time, "
                             "so excluded sites cost zero graph ops."),
    "MXTPU_NUMERICS_BINS": ("40", "Log2-magnitude histogram buckets "
                            "per site in hist mode (bucket i counts "
                            "|x| in [2^(-24+i), 2^(-24+i+1)))."),
    "MXTPU_NUMERICS_RING": ("128", "Per-site numerics history-ring "
                            "capacity (the drift watchdog's window and "
                            "the postmortem's trajectory live here)."),
    "MXTPU_NUMERICS_DRIFT": ("warn", "Drift-watchdog action: 'warn' "
                             "emits damped numerics.drift warning "
                             "events only; 'rollback' additionally "
                             "escalates a sustained drift (monotonic "
                             "rms growth / finite-fraction decay over "
                             "the recorded window) to the trainer's "
                             "StepGuard — its policy then decides "
                             "warn/skip_and_rollback/halt BEFORE the "
                             "run ever goes non-finite."),
    "MXTPU_GOODPUT": ("0", "1 enables the run-level goodput ledger "
                      "(telemetry.goodput): every wall-second between "
                      "begin() and report() is attributed to compute / "
                      "collective / input_wait / host / compile / "
                      "checkpoint / rollback_waste (unattributed is the "
                      "honesty remainder, gated <10% by the "
                      "goodput-smoke CI job), with a measured-vs-"
                      "roofline MFU headline. Host-side bookkeeping "
                      "only — the compiled graphs are untouched either "
                      "way. Default off: the trainer/io/checkpoint hooks are one "
                      "env read."),
    "MXTPU_GOODPUT_WINDOW": ("32", "Steps per goodput attribution "
                             "window: each window closes with one "
                             "goodput.window event and refreshed "
                             "mxtpu_goodput_* gauges (share per "
                             "category, measured/predicted MFU, "
                             "divergence, unattributed share)."),
    "MXTPU_DIRECTOR": ("0", "1 enables the flight director "
                       "(telemetry.director): a closed adaptive loop "
                       "that watches goodput.window events and "
                       "hot-applies ONE allowlisted remediation per "
                       "breach — prefetch depth for input_bound, a "
                       "staged recompile (ledger site "
                       "director.recompile) for compute_bound, Router "
                       "shed/hedge for a serve SLO burn — with a "
                       "damped hysteresis (cooldown + revert-if-worse, "
                       "exactly one revert) and every decision on an "
                       "audited ring. Host-side only; default off is "
                       "one env read at install()."),
    "MXTPU_DIRECTOR_DIVERGENCE_PCT": ("25", "Flight-director trigger "
                                      "threshold: a goodput window "
                                      "whose measured-vs-roofline MFU "
                                      "divergence is at or below "
                                      "-THRESHOLD percent counts as "
                                      "breached."),
    "MXTPU_DIRECTOR_WINDOWS": ("2", "Consecutive breached (or "
                               "bucket-drifted) goodput windows "
                               "required before the director acts — "
                               "the debounce half of the hysteresis."),
    "MXTPU_DIRECTOR_COOLDOWN": ("2", "Goodput windows the director "
                                "holds after every decision before it "
                                "may act again; the first window after "
                                "the cooldown is the revert-if-worse "
                                "evaluation sample."),
    "MXTPU_DIRECTOR_REVERT_MARGIN_PCT": ("5", "Revert-if-worse margin: "
                                         "the post-cooldown window's "
                                         "divergence must be at least "
                                         "this many points below the "
                                         "pre-action baseline to "
                                         "trigger the (single) "
                                         "revert."),
    "MXTPU_DIRECTOR_RING": ("64", "Flight-director decision-ring "
                            "capacity (the audit trail embedded in "
                            "telemetry.snapshot(), flight bundles and "
                            "tools/postmortem.py)."),
    "MXTPU_DIRECTOR_MAX_DEPTH": ("8", "Cap on the PrefetchIter depth "
                                 "the director's input_bound "
                                 "remediation may grow to (doubling "
                                 "per action up to the cap)."),
    "MXTPU_DIRECTOR_BUDGET": ("4", "Candidate budget for the "
                              "director's rescored trace-only autotune "
                              "search (benchmark.autotune.search with "
                              "the measured attribution folded into "
                              "the roofline score)."),
    "MXTPU_DIRECTOR_HEDGE_MS": ("50", "Hedge deadline the director's "
                                "serve-side remediation enables on a "
                                "Router whose hedging was off when the "
                                "SLO burn fired."),
    "MXTPU_TELEMETRY": ("1", "Master switch for the mx.telemetry event "
                        "bus; 0 turns every emit() into a no-op."),
    "MXTPU_TELEMETRY_RING": ("1024", "Per-kind event ring-buffer capacity; "
                             "aggregate counts keep counting past the "
                             "ring, only raw events drop."),
    "MXTPU_TELEMETRY_JSONL": ("", "When set, every telemetry event is "
                              "appended to this file as one strict-JSON "
                              "line (rotating sink, installed on first "
                              "emission)."),
    "MXTPU_TELEMETRY_JSONL_MAX_MB": ("64", "Rotation threshold for the "
                                     "JSON-lines sink; past it the file "
                                     "moves to <path>.1 (one generation "
                                     "kept)."),
    "MXTPU_LOCKCHECK": ("0", "Runtime lock-order sanitizer: locks "
                        "created through lockcheck.make_lock become "
                        "order-tracking wrappers that flag inversions "
                        "as concurrency.inversion telemetry events "
                        "(also auto-enabled whenever MXTPU_CHAOS is "
                        "set)."),
    "MXTPU_LOCKCHECK_HOLD_MS": ("250", "Lock-hold duration past which a "
                                "tracked lock's release publishes a "
                                "concurrency.hold warning event."),
    "MXTPU_LOCKCHECK_TIMEOUT_S": ("5", "Bound on an acquire that "
                                  "crosses a recorded lock-order "
                                  "inversion; expiry raises "
                                  "LockOrderError instead of "
                                  "deadlocking the process."),
    "MXTPU_TRACE_SAMPLE": ("0.1", "Head-sampling probability for NEW "
                           "distributed traces (0..1). Unsampled traces "
                           "still propagate ids across threads and the "
                           "wire but record nothing — the serve_bench "
                           "tracing-overhead gate holds the p50 tax at "
                           "this default under 3%. CI's trace-smoke "
                           "job sets 1.0 so every request must stitch "
                           "into one rooted span tree."),
    "MXTPU_TRACE_RING": ("65536", "Completed-span ring capacity "
                         "(process-wide; oldest spans drop first)."),
    "MXTPU_FLIGHT_DIR": ("", "When set, the flight recorder writes one "
                         "atomic strict-JSON post-mortem bundle here on "
                         "watchdog trip, guard halt, replica "
                         "crash/stall-kill, and chaos crash sites; "
                         "unset = recorder off (the off path is one "
                         "env read). Render bundles with "
                         "tools/postmortem.py."),
    "MXTPU_FLIGHT_MAX": ("16", "Per-process cap on flight bundles — a "
                         "crash loop produces a few bundles, not a "
                         "full disk."),
    "MXTPU_FLIGHT_MIN_S": ("0", "Minimum seconds between two flight "
                           "bundles (storm damping; 0 = no spacing)."),
    "MXTPU_FLIGHT_SPANS": ("2048", "Most-recent trace spans included in "
                           "a flight bundle."),
    "MXTPU_COLLECTIVE_LEDGER": ("0", "Master switch for the collective-"
                                "schedule ledger (the MX9xx runtime "
                                "twin): 1/true/on/yes banks a "
                                "verb/axis-sequence fingerprint per "
                                "compiled step and crosschecks it "
                                "across the pod at dist.initialize() "
                                "and on post-warmup recompiles. Off "
                                "(default) costs one env read."),
    "MXTPU_COLLECTIVE_LEDGER_RING": ("512", "Capacity of the per-process "
                                     "dispatch ring (most-recent "
                                     "collective dispatches kept for "
                                     "flight bundles; oldest drop "
                                     "first)."),
    "MXTPU_COLLECTIVE_LEDGER_TIMEOUT_S": ("20", "Seconds each process "
                                          "waits for peer fingerprint "
                                          "blobs during a crosscheck "
                                          "before declaring the "
                                          "exchange failed."),
    "MXTPU_ELASTIC": ("0", "Master switch for the elastic multi-host "
                      "control plane (parallel.elastic): 1 starts the "
                      "heartbeat-lease daemon at dist.initialize(), so "
                      "a host that dies mid-run is a detected loss "
                      "(flight bundle + HostLossError at the next step "
                      "boundary) instead of a pod hung inside a "
                      "collective. Off costs one env read."),
    "MXTPU_ELASTIC_LEASE_S": ("10", "Heartbeat-lease validity window: a "
                              "pod member whose newest lease is older "
                              "than this is a detected host loss."),
    "MXTPU_ELASTIC_HEARTBEAT_S": ("", "Beat interval of the lease "
                                  "daemon; unset = a third of the lease "
                                  "(three missed beats expire it)."),
    "MXTPU_ELASTIC_GENERATION": ("0", "Restore-generation counter, "
                                 "stamped by the launcher on each "
                                 "elastic restart: namespaces the lease "
                                 "keys so a restarted pod never reads a "
                                 "dead generation's leases, and rides "
                                 "checkpoint meta."),
    "MXTPU_ELASTIC_COMMIT_TIMEOUT_S": ("60", "Bound on the primary's "
                                       "wait for every peer's commit "
                                       "marker during a multi-host "
                                       "checkpoint save; expiry raises "
                                       "CheckpointError naming the "
                                       "missing process indices instead "
                                       "of hanging the save."),
    "MXTPU_SLO_WINDOWS": ("60:14.4,300:6", "Burn-rate alert windows as "
                          "'seconds:threshold,...' — every window must "
                          "burn over its threshold at once to page "
                          "(multi-window AND; scaled-down analogue of "
                          "the SRE-workbook 1h/6h pair)."),
    "MXTPU_SLO_OBJECTIVE": ("0.99", "Good-fraction objective shared by "
                            "the built-in SLOs (0.99 = 1% error "
                            "budget)."),
    "MXTPU_SLO_SERVE_P99_MS": ("250", "Serve-latency SLO threshold: a "
                               "request slower than this is an "
                               "error-budget spend."),
    "MXTPU_SLO_STEP_MS": ("60000", "Train step-time SLO threshold (ms) "
                          "for the train-step-time objective."),
    "MXTPU_SLO_ITL_P50_MS": ("100", "Decode inter-token-latency SLO "
                             "threshold (ms) for the decode-itl-p50 "
                             "built-in objective."),
    "MXTPU_SLO_ITL_P99_MS": ("500", "Decode inter-token-latency SLO "
                             "threshold (ms) for the decode-itl-p99 "
                             "built-in objective."),
}


def getenv(name: str, default: Optional[str] = None) -> Optional[str]:
    if default is None and name in ENV_VARS:
        default = ENV_VARS[name][0]
    return os.environ.get(name, default)


def setenv(name: str, value: str) -> None:
    os.environ[name] = value


def env_var_doc() -> str:
    lines = ["| Variable | Default | Description |", "|---|---|---|"]
    for k, (d, desc) in sorted(ENV_VARS.items()):
        lines.append(f"| {k} | {d!r} | {desc} |")
    return "\n".join(lines)


def makedirs(d: str) -> None:
    os.makedirs(d, exist_ok=True)


# --- numpy-semantics switches (reference: mx.util.set_np / np_shape) -------
_NP_SHAPE = [True]   # TPU build: numpy semantics are the native behavior
_NP_ARRAY = [False]


def is_np_shape() -> bool:
    return _NP_SHAPE[0]


def is_np_array() -> bool:
    return _NP_ARRAY[0]


def set_np(shape: bool = True, array: bool = True) -> None:
    _NP_SHAPE[0] = shape
    _NP_ARRAY[0] = array


def reset_np() -> None:
    set_np(True, False)


class np_shape:
    """Context manager parity for ``mx.util.np_shape``."""

    def __init__(self, active: bool = True):
        self._active = active
        self._prev = None

    def __enter__(self):
        self._prev = _NP_SHAPE[0]
        _NP_SHAPE[0] = self._active
        return self

    def __exit__(self, *exc):
        _NP_SHAPE[0] = self._prev


def use_np_shape(fn):
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with np_shape(True):
            return fn(*args, **kwargs)
    return wrapped
