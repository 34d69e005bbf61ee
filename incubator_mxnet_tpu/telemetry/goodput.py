"""Goodput ledger — run-level wall-clock attribution + measured MFU.

Reference counterpart: none — the reference (and, until this module,
this repo) could time a step (``profiler.step_report``) and price a
graph device-blind (``analysis.hlo.cost``), but had no notion of
*goodput*: nothing attributed every wall-second of a training run to
where it actually went, so "why is the banked MFU stuck at 0.3789" was
unanswerable from telemetry alone. TVM and the XLA fusion study
(PAPERS.md) both score *whole-run* efficiency, not per-graph cost —
this module is that score for the live runtime.

The ledger folds the runtime's existing per-phase measurements — the
trainer's ``step`` frame segments (place/dispatch/device_wait), the
``io.PrefetchIter`` input-wait instrumentation, ``fault.checkpoint``
save spans, StepGuard rollback verdicts, and the compile ledger's
warmup walls — into one per-window **attribution vector**:

========================  ==================================================
``compute``               device time the host provably blocked on (the
                          guard's single sync), minus the collective share
``collective``            the communication share of device time, split by
                          the cost model's roofline ratio (comm_s vs
                          compute_s) — deterministic, documented, honest
                          about being a model
``input_wait``            host blocked on the input pipeline
                          (``PrefetchIter`` queue pops)
``host``                  per-step host tax: placement, dispatch, and the
                          un-instrumented Python remainder of each step
``compile``               first-signature trace+compile walls (one-off,
                          never steady-state)
``checkpoint``            ``fault.checkpoint`` save walls
``rollback_waste``        wall time of rolled-back steps PLUS the
                          since-snapshot steps a rollback discards (their
                          already-attributed time is *reclassified* — work
                          the run paid for and then threw away)
``unattributed``          run wall-clock not covered by any note — the
                          ledger's own honesty metric, gated ``< 10%`` by
                          the ``goodput-smoke`` CI job
========================  ==================================================

Headline: ``measured_mfu = flops_per_step · good_steps / (wall · PEAK)``
— reconciled against the cost-model roofline (``predicted_mfu``), so
predicted-vs-measured divergence is itself a tracked metric
(``mxtpu_goodput_mfu_divergence_pct``). The cost profile comes from
:func:`price` (one ``analysis.hlo.cost`` trace — zero XLA compiles) or
:func:`set_cost_profile`.

Everything is **off by default** (``MXTPU_GOODPUT`` unset): the hooks in
the trainer/io/checkpoint hot paths are one :func:`enabled` check, the
compiled graphs are untouched either way (the ledger is host-side
bookkeeping only, and the step still runs exactly one jitted graph with
the ledger on).

Usage::

    MXTPU_GOODPUT=1 python train.py     # or goodput.configure(on=True)

    goodput.price(trainer, sample_args=(x, y))   # roofline reconciliation
    goodput.begin()
    for placed in prefetch_iter:
        trainer.step(*placed)                    # notes itself
    rep = goodput.report()
    rep["classification"]                        # "input_bound" | ...
    rep["mfu"]["measured_mfu"]

Every ``MXTPU_GOODPUT_WINDOW`` steps the ledger emits one
``goodput.window`` event and refreshes the ``mxtpu_goodput_*`` gauges;
``telemetry.snapshot()``, flight bundles, and ``tools/postmortem.py``
all carry the full report. ``tools/perf_history.py`` is the offline
twin: it merges the banked ``BENCH_r*.json`` / ``MULTICHIP_r*.json``
artifacts into one trajectory with regression flags.
"""
from __future__ import annotations

import os
import time
from collections import deque
from typing import Any, Dict, Optional

from ..lockcheck import make_lock

__all__ = ["CATEGORIES", "enabled", "configure", "begin", "begin_from_env",
           "note", "note_step", "set_cost_profile", "cost_profile", "price",
           "collective_ms", "report", "snapshot", "reset", "window_steps",
           "note_serve", "set_serve_cost_profile", "serve_report"]

#: the attribution vector, in triage order (docs/observability.md §6):
#: an operator works the list top-down — input starvation first, host
#: tax second, communication third; only then is "make compute faster"
#: the right lever
CATEGORIES = ("input_wait", "host", "collective", "compute", "compile",
              "checkpoint", "rollback_waste")

#: categories eligible to classify a run as X-bound (one-off compile /
#: checkpoint / waste are symptoms, not steady-state regimes)
_BOUND_CATEGORIES = ("input_wait", "host", "collective", "compute")

_LOCK = make_lock("goodput._LOCK")
_ON_OVERRIDE: Optional[bool] = None
_WINDOW_OVERRIDE: Optional[int] = None


def _new_state() -> Dict[str, Any]:
    return {
        "t0": None,              # perf_counter at begin()
        "wall_anchor": None,     # wall clock at begin() (reporting only)
        "ms": {c: 0.0 for c in CATEGORIES},
        "steps": 0, "good_steps": 0, "rolled_back": 0,
        "checkpoints": 0, "windows": 0,
        # per-step attribution ring: the rollback reclassification needs
        # to know where the discarded steps' time originally went
        "ring": deque(maxlen=256),
        # inter-step gap accounting: perf_counter at the last step's
        # end, and note() ms accumulated since — the loop time BETWEEN
        # steps (iterator protocol, logging, the ledger's own overhead)
        # is host tax, attributed at the next note_step instead of
        # leaking into unattributed
        "last_mark": None,
        "gap_notes_ms": 0.0,
        "win": {"t0": None, "ms": {c: 0.0 for c in CATEGORIES},
                "steps": 0, "good_steps": 0, "rolled_back": 0},
        "cost": None,            # set_cost_profile() result
    }


_S = _new_state()


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

def enabled() -> bool:
    """Ledger on? One env read on the hot path (``MXTPU_GOODPUT=1``;
    :func:`configure` overrides) — the same zero-cost-when-off contract
    as ``fault.inject``/``telemetry.numerics``."""
    if _ON_OVERRIDE is not None:
        return _ON_OVERRIDE
    return os.environ.get("MXTPU_GOODPUT", "0") == "1"


def window_steps() -> int:
    """Steps per ``goodput.window`` event (``MXTPU_GOODPUT_WINDOW``,
    default 32; :func:`configure` overrides)."""
    if _WINDOW_OVERRIDE is not None:
        return _WINDOW_OVERRIDE
    try:
        return max(1, int(os.environ.get("MXTPU_GOODPUT_WINDOW", "32")))
    except ValueError:
        return 32


def configure(on: Optional[bool] = None,
              window: Optional[int] = None) -> None:
    """Programmatic override of the env knobs (tests, the smoke tool).
    Calling with no arguments clears both overrides (back to the env)."""
    global _ON_OVERRIDE, _WINDOW_OVERRIDE
    if on is None and window is None:
        _ON_OVERRIDE = None
        _WINDOW_OVERRIDE = None
        return
    if on is not None:
        _ON_OVERRIDE = bool(on)
    if window is not None:
        _WINDOW_OVERRIDE = max(1, int(window))


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------

def begin(reset_totals: bool = True) -> None:
    """Anchor the run clock NOW. Everything between :func:`begin` and
    :func:`report` is the wall this ledger must account for; call it
    right before the training loop so setup/compile of earlier phases
    does not land in ``unattributed``. Notes auto-begin if the caller
    never does."""
    global _S
    with _LOCK:
        if reset_totals:
            cost = _S["cost"]
            _S = _new_state()
            _S["cost"] = cost
        _S["t0"] = time.perf_counter()
        _S["wall_anchor"] = time.time()
        _S["win"]["t0"] = _S["t0"]
        _S["last_mark"] = _S["t0"]
        _S["gap_notes_ms"] = 0.0


def begin_from_env() -> bool:
    """:func:`begin` iff the ledger is enabled — the one-liner drivers
    (serve_bench, training scripts) call unconditionally."""
    if not enabled():
        return False
    begin()
    return True


def _auto_begin_locked() -> None:
    if _S["t0"] is None:
        _S["t0"] = time.perf_counter()
        _S["wall_anchor"] = time.time()
        _S["win"]["t0"] = _S["t0"]
        _S["last_mark"] = _S["t0"]


def note(category: str, dur_ms: float) -> None:
    """Attribute ``dur_ms`` of wall time to one category — the generic
    hook (``io.PrefetchIter`` notes ``input_wait``, ``fault.checkpoint``
    notes ``checkpoint``). No-op when the ledger is off."""
    if not enabled() or category not in _S["ms"]:
        return
    with _LOCK:
        _auto_begin_locked()
        _S["ms"][category] += dur_ms
        _S["win"]["ms"][category] += dur_ms
        _S["gap_notes_ms"] += dur_ms
        if category == "checkpoint":
            _S["checkpoints"] += 1


def collective_ms() -> float:
    """Cumulative wall attributed to the ``collective`` bucket — the
    per-host straggler signal the elastic heartbeat banks with each
    lease, so a host whose collectives are slow is a *gauge* on its
    peers' lease tables before it is a detected failure. 0.0 when the
    ledger is off."""
    with _LOCK:
        return float(_S["ms"].get("collective", 0.0))


def _collective_fraction() -> float:
    """The roofline comm share of device time — ``comm_s / (compute_s +
    comm_s)`` from the cost profile. 0.0 without a profile (all device
    time reads as compute). A *model*, not a measurement: collectives
    execute inside the compiled graph where the host cannot see them,
    so the split is the cost model's — which is exactly what makes
    predicted-vs-measured divergence meaningful."""
    cost = _S["cost"]
    if not cost:
        return 0.0
    comp_s = cost.get("compute_s") or 0.0
    comm_s = cost.get("comm_s") or 0.0
    total = comp_s + comm_s
    return (comm_s / total) if total > 0 else 0.0


def note_step(step: int, wall_ms: float, device_wait_ms: float = 0.0,
              compile_ms: float = 0.0, rolled_back: bool = False,
              rollback_to: Optional[int] = None) -> None:
    """Attribute one training step's wall time (``ShardedTrainer.step``
    calls this from the timings it already measures — the ledger and
    the ``train.step`` event can never disagree).

    Split: ``device_wait_ms`` (the guard's single host sync — the one
    point the host provably blocks on the device) becomes compute +
    collective by the roofline comm fraction; ``compile_ms`` (the
    dispatch wall of a first-signature trace) is one-off compile; the
    rest of the frame — placement, steady dispatch, Python remainder,
    none of which can change the split — is per-step ``host`` tax (the
    finer breakdown lives in ``profiler.step_report``). A rolled-back
    step's ENTIRE wall is ``rollback_waste``, and ``rollback_to`` (the
    snapshot step the trainer restored) additionally reclassifies the
    since-snapshot steps' recorded time as waste — their updates were
    discarded, so their wall bought nothing."""
    if not enabled():
        return
    from . import events as _events
    from . import metrics as _metrics
    now = time.perf_counter()
    with _LOCK:
        _auto_begin_locked()
        # the gap since the previous step's end, minus whatever was
        # already noted inside it (io waits, checkpoint saves), is the
        # loop's host-side time between steps — attribute it so the
        # vector sums to the run wall instead of leaking the loop tax
        # into unattributed
        start = now - wall_ms / 1e3
        mark = _S["last_mark"]
        if mark is not None:
            gap_host = max((start - mark) * 1e3 - _S["gap_notes_ms"], 0.0)
            if gap_host > 0:
                _S["ms"]["host"] += gap_host
                _S["win"]["ms"]["host"] += gap_host
        _S["last_mark"] = now
        _S["gap_notes_ms"] = 0.0
        vec: Dict[str, float] = {}
        if rolled_back:
            vec["rollback_waste"] = wall_ms
        else:
            compile_part = min(max(compile_ms, 0.0), wall_ms)
            device = min(max(device_wait_ms, 0.0),
                         max(wall_ms - compile_part, 0.0))
            coll = device * _collective_fraction()
            vec["compile"] = compile_part
            vec["collective"] = coll
            vec["compute"] = device - coll
            vec["host"] = max(wall_ms - device - compile_part, 0.0)
        for cat, ms in vec.items():
            _S["ms"][cat] += ms
            _S["win"]["ms"][cat] += ms
        _S["steps"] += 1
        _S["win"]["steps"] += 1
        if rolled_back:
            _S["rolled_back"] += 1
            _S["win"]["rolled_back"] += 1
            if rollback_to is not None:
                _reclassify_discarded_locked(rollback_to)
        else:
            _S["good_steps"] += 1
            _S["win"]["good_steps"] += 1
            _S["ring"].append((step, vec))
        close = _S["win"]["steps"] >= window_steps()
        win_doc = _close_window_locked() if close else None
    if win_doc is not None:
        # emit outside the ledger lock (the bus fans out to subscribers)
        _events.emit("goodput.window", step=step, **win_doc)
        _publish_gauges(_metrics, win_doc)


def _reclassify_discarded_locked(rollback_to: int) -> None:
    """A rollback restored the step counter to ``rollback_to``: every
    recorded step AFTER it was work the run paid for and then threw
    away. Move its attributed time — wherever it originally went —
    into ``rollback_waste``, in both the cumulative and current-window
    vectors (window moves are clamped to what the window still holds:
    time attributed in an already-closed window stays reported there)."""
    keep = deque(maxlen=_S["ring"].maxlen)
    discarded = 0
    for step, vec in _S["ring"]:
        if step <= rollback_to:
            keep.append((step, vec))
            continue
        discarded += 1
        for cat, ms in vec.items():
            moved = min(ms, _S["ms"][cat])
            _S["ms"][cat] -= moved
            _S["ms"]["rollback_waste"] += moved
            win_moved = min(ms, _S["win"]["ms"][cat])
            _S["win"]["ms"][cat] -= win_moved
            _S["win"]["ms"]["rollback_waste"] += win_moved
    _S["ring"] = keep
    # the discarded steps are no longer productive: measured_mfu counts
    # only updates that SURVIVED, so a run that trains 99 steps and
    # rolls them all back reads as ~zero goodput, not near-full MFU
    _S["good_steps"] = max(_S["good_steps"] - discarded, 0)
    _S["win"]["good_steps"] = max(_S["win"]["good_steps"] - discarded, 0)


# ---------------------------------------------------------------------------
# serve twin — token-level goodput for the decode path
# ---------------------------------------------------------------------------

def _new_serve_state() -> Dict[str, Any]:
    return {"t0": None,
            "ms": {"prefill": 0.0, "decode": 0.0},
            "tokens": {"prefill": 0, "decode": 0},
            "calls": {"prefill": 0, "decode": 0},
            "cost": None}


_SERVE = _new_serve_state()


def note_serve(kind: str, tokens: int, wall_ms: float) -> None:
    """Attribute one serve-side dispatch: ``kind`` is ``"prefill"`` (one
    prompt, ``tokens`` = prompt length) or ``"decode"`` (one step,
    ``tokens`` = active rows advanced). The DecodeBatcher calls this at
    every token boundary; no-op when the ledger is off — same zero-cost
    contract as the training hooks."""
    if not enabled() or kind not in ("prefill", "decode"):
        return
    with _LOCK:
        if _SERVE["t0"] is None:
            _SERVE["t0"] = time.perf_counter()
        _SERVE["ms"][kind] += float(wall_ms)
        _SERVE["tokens"][kind] += int(tokens)
        _SERVE["calls"][kind] += 1


def set_serve_cost_profile(flops_per_token: float,
                           hbm_bytes_per_token: float = 0.0,
                           source: Optional[str] = None) -> Dict[str, Any]:
    """Install the per-generated-token cost the decode roofline ceiling
    is computed against (same ``util.roofline_peaks()`` constants as the
    training profile). Decode is almost always HBM-bound — every step
    re-reads the params and the live cache pages — so the ceiling is
    ``1 / max(flops/PEAK, hbm/BW)`` tokens/sec. Returns the profile."""
    from ..util import roofline_peaks
    peak_flops, peak_bw, _ici = roofline_peaks()
    compute_s = flops_per_token / peak_flops
    mem_s = hbm_bytes_per_token / peak_bw
    token_s = max(compute_s, mem_s)
    prof = {"flops_per_token": float(flops_per_token),
            "hbm_bytes_per_token": float(hbm_bytes_per_token),
            "compute_s": compute_s, "mem_s": mem_s,
            "roofline_tokens_per_s": (1.0 / token_s) if token_s > 0
            else None,
            "bound": "hbm" if mem_s >= compute_s else "compute",
            "source": source}
    with _LOCK:
        _SERVE["cost"] = prof
    return prof


def serve_report() -> Dict[str, Any]:
    """The decode-goodput twin of :func:`report`: measured tokens/sec vs
    the per-token roofline ceiling, and the prefill-bound vs decode-bound
    wall split (which of the two graphs the serve wall actually went to).
    Publishes the ``mxtpu_goodput_serve_*`` gauges. Strict-JSON-safe."""
    from . import metrics as _metrics
    with _LOCK:
        t0 = _SERVE["t0"]
        wall_ms = ((time.perf_counter() - t0) * 1e3
                   if t0 is not None else 0.0)
        pre_ms = _SERVE["ms"]["prefill"]
        dec_ms = _SERVE["ms"]["decode"]
        dec_tok = _SERVE["tokens"]["decode"]
        doc: Dict[str, Any] = {
            "enabled": enabled(),
            "wall_ms": round(wall_ms, 3),
            "prefill": {"ms": round(pre_ms, 3),
                        "tokens": _SERVE["tokens"]["prefill"],
                        "calls": _SERVE["calls"]["prefill"]},
            "decode": {"ms": round(dec_ms, 3),
                       "tokens": dec_tok,
                       "steps": _SERVE["calls"]["decode"]},
        }
        attributed = pre_ms + dec_ms
        doc["attributed_ms"] = round(attributed, 3)
        doc["unattributed_pct"] = (
            round(100.0 * max(wall_ms - attributed, 0.0) / wall_ms, 2)
            if wall_ms > 0 else 0.0)
        doc["tokens_per_s"] = (round(dec_tok / (wall_ms / 1e3), 3)
                               if wall_ms > 0 else None)
        doc["decode_tokens_per_s"] = (round(dec_tok / (dec_ms / 1e3), 3)
                                      if dec_ms > 0 else None)
        doc["classification"] = (None if attributed == 0 else
                                 ("prefill_bound" if pre_ms > dec_ms
                                  else "decode_bound"))
        cost = _SERVE["cost"]
        doc["cost_profile"] = dict(cost) if cost else None
        ceiling = cost["roofline_tokens_per_s"] if cost else None
        doc["roofline_tokens_per_s"] = (round(ceiling, 3)
                                        if ceiling else None)
        doc["roofline_fraction"] = (
            round((dec_tok / (wall_ms / 1e3)) / ceiling, 6)
            if ceiling and wall_ms > 0 else None)
    if doc["tokens_per_s"] is not None:
        _metrics.gauge("mxtpu_goodput_serve_tokens_per_s",
                       "Generated tokens/sec over the serve ledger window"
                       ).set(doc["tokens_per_s"])
    if doc["roofline_fraction"] is not None:
        _metrics.gauge("mxtpu_goodput_serve_roofline_fraction",
                       "Measured tokens/sec over the per-token roofline "
                       "ceiling").set(doc["roofline_fraction"])
    return doc


# ---------------------------------------------------------------------------
# cost profile / MFU reconciliation
# ---------------------------------------------------------------------------

def set_cost_profile(flops_per_step: float,
                     hbm_bytes_per_step: float = 0.0,
                     comm_bytes_per_step: float = 0.0,
                     source: Optional[str] = None) -> Dict[str, Any]:
    """Install the deterministic per-step cost the MFU headline and the
    collective split are computed against. ``roofline_s`` is the
    steady-state core of ``benchmark/autotune.py``'s score —
    ``max(flops/PEAK, hbm/BW) + comm/ICI`` over the SAME
    ``util.roofline_peaks()`` constants (the autotuner additionally
    amortizes per-kernel launch and warmup-compile terms, which are not
    per-step device time). Returns the profile."""
    from ..util import roofline_peaks
    peak_flops, peak_bw, ici_bw = roofline_peaks()
    compute_s = flops_per_step / peak_flops
    mem_s = hbm_bytes_per_step / peak_bw
    comm_s = comm_bytes_per_step / ici_bw
    roofline_s = max(compute_s, mem_s) + comm_s
    prof = {
        "flops_per_step": float(flops_per_step),
        "hbm_bytes_per_step": float(hbm_bytes_per_step),
        "comm_bytes_per_step": float(comm_bytes_per_step),
        "peak_tflops": peak_flops / 1e12,
        "compute_s": compute_s, "mem_s": mem_s, "comm_s": comm_s,
        "roofline_s": roofline_s,
        "predicted_mfu": ((flops_per_step / (roofline_s * peak_flops))
                          if roofline_s > 0 else None),
        "source": source,
    }
    with _LOCK:
        _S["cost"] = prof
    return prof


def cost_profile() -> Optional[Dict[str, Any]]:
    with _LOCK:
        return dict(_S["cost"]) if _S["cost"] else None


def price(target, sample_args=None) -> Dict[str, Any]:
    """Price ``target`` (a ``ShardedTrainer``, ``CompiledModel``, or any
    ``analysis.hlo`` traceable) with the device-blind cost model — one
    ``make_jaxpr`` trace, zero XLA compiles — and install the result as
    the ledger's cost profile. The one-call roofline reconciliation."""
    from ..analysis import hlo
    prep = getattr(target, "prepare", None)
    if prep is not None and sample_args is not None:
        # a ShardedTrainer that has not stepped yet: prepare() builds
        # the pjit step WITHOUT dispatching, so pricing stays trace-only
        prep(*sample_args)
    rep = hlo.cost(target, sample_args=sample_args)
    return set_cost_profile(
        flops_per_step=rep.model_flops_per_step(),
        hbm_bytes_per_step=rep.bytes_per_step(),
        comm_bytes_per_step=rep.comm_bytes_per_step(),
        source="analysis.hlo.cost")


def _mfu(wall_ms: float, good_steps: int) -> Optional[Dict[str, Any]]:
    """measured vs roofline-predicted MFU over ``wall_ms`` of run time
    containing ``good_steps`` productive steps. None without a profile."""
    cost = _S["cost"]
    if not cost or wall_ms <= 0:
        return None
    peak_flops = cost["peak_tflops"] * 1e12
    measured = (cost["flops_per_step"] * good_steps) \
        / (wall_ms / 1e3 * peak_flops)
    predicted = cost["predicted_mfu"]
    div = (100.0 * (measured / predicted - 1.0)
           if predicted else None)
    return {"measured_mfu": round(measured, 9),
            "predicted_mfu": (round(predicted, 9)
                              if predicted is not None else None),
            "divergence_pct": (round(div, 2) if div is not None else None),
            "flops_per_step": cost["flops_per_step"],
            "peak_tflops": cost["peak_tflops"],
            "cost_source": cost.get("source")}


# ---------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------

def _vector_doc(ms: Dict[str, float], wall_ms: float) -> Dict[str, Any]:
    attributed = sum(ms.values())
    unattr = max(wall_ms - attributed, 0.0)
    cats = {c: {"ms": round(v, 3),
                "share_pct": (round(100.0 * v / wall_ms, 2)
                              if wall_ms > 0 else 0.0)}
            for c, v in ms.items()}
    cats["unattributed"] = {
        "ms": round(unattr, 3),
        "share_pct": (round(100.0 * unattr / wall_ms, 2)
                      if wall_ms > 0 else 0.0)}
    return {"attributed_ms": round(attributed, 3),
            "unattributed_ms": round(unattr, 3),
            "unattributed_pct": cats["unattributed"]["share_pct"],
            "categories": cats}


def _classify(ms: Dict[str, float]) -> Optional[str]:
    """Dominant steady-state bucket → ``"<bucket>_bound"`` (``input_wait``
    reads as ``input_bound``). Ties break in triage order — the runbook's
    input → host → collective → compute."""
    best, best_ms = None, 0.0
    for cat in _BOUND_CATEGORIES:          # triage order: first wins ties
        v = ms.get(cat, 0.0)
        if v > best_ms:
            best, best_ms = cat, v
    if best is None:
        return None
    return ("input_bound" if best == "input_wait" else f"{best}_bound")


def _close_window_locked() -> Dict[str, Any]:
    """Roll the current window into a ``goodput.window`` event payload
    (caller emits outside the lock) and reset it."""
    win = _S["win"]
    now = time.perf_counter()
    wall_ms = (now - win["t0"]) * 1e3 if win["t0"] is not None else 0.0
    _S["windows"] += 1
    doc = {"window": _S["windows"], "wall_ms": round(wall_ms, 3),
           "steps": win["steps"], "good_steps": win["good_steps"],
           "rolled_back_steps": win["rolled_back"]}
    doc.update(_vector_doc(win["ms"], wall_ms))
    doc["classification"] = _classify(win["ms"])
    mfu = _mfu(wall_ms, win["good_steps"])
    if mfu is not None:
        doc["mfu"] = mfu
    # events carry the flat ms vector (strict-JSON scalars); the nested
    # per-category dicts stay in report()/snapshot()
    doc["categories"] = {c: v["ms"] for c, v in doc["categories"].items()}
    _S["win"] = {"t0": now, "ms": {c: 0.0 for c in CATEGORIES},
                 "steps": 0, "good_steps": 0, "rolled_back": 0}
    return doc


def _publish_gauges(_metrics, win_doc: Dict[str, Any]) -> None:
    wall = win_doc["wall_ms"] or 1.0
    for cat, ms in win_doc["categories"].items():
        _metrics.gauge("mxtpu_goodput_share_pct",
                       "Goodput attribution share over the last window",
                       category=cat).set(round(100.0 * ms / wall, 2))
    _metrics.gauge("mxtpu_goodput_unattributed_pct",
                   "Unattributed share of the last goodput window"
                   ).set(win_doc["unattributed_pct"])
    _metrics.counter("mxtpu_goodput_windows_total",
                     "Closed goodput attribution windows").inc()
    mfu = win_doc.get("mfu")
    if mfu:
        _metrics.gauge("mxtpu_goodput_measured_mfu",
                       "Measured MFU over the last goodput window"
                       ).set(mfu["measured_mfu"])
        if mfu.get("predicted_mfu") is not None:
            _metrics.gauge("mxtpu_goodput_predicted_mfu",
                           "Cost-model roofline MFU ceiling"
                           ).set(mfu["predicted_mfu"])
        if mfu.get("divergence_pct") is not None:
            _metrics.gauge("mxtpu_goodput_mfu_divergence_pct",
                           "Measured-vs-roofline MFU divergence"
                           ).set(mfu["divergence_pct"])


def report() -> Dict[str, Any]:
    """The cumulative ledger: run wall since :func:`begin`, the full
    attribution vector (``unattributed`` = wall the ledger never saw),
    rollback-waste accounting, the dominant-bucket classification, and
    the measured-vs-roofline MFU headline. Strict-JSON-safe."""
    with _LOCK:
        on = enabled()
        t0 = _S["t0"]
        wall_ms = ((time.perf_counter() - t0) * 1e3
                   if t0 is not None else 0.0)
        doc: Dict[str, Any] = {
            "enabled": on,
            "window_steps": window_steps(),
            "began_at": _S["wall_anchor"],
            "wall_ms": round(wall_ms, 3),
            "steps": _S["steps"], "good_steps": _S["good_steps"],
            "rolled_back_steps": _S["rolled_back"],
            "checkpoints": _S["checkpoints"],
            "windows": _S["windows"],
        }
        doc.update(_vector_doc(_S["ms"], wall_ms))
        doc["classification"] = _classify(_S["ms"])
        doc["mfu"] = _mfu(wall_ms, _S["good_steps"])
        doc["cost_profile"] = dict(_S["cost"]) if _S["cost"] else None
    # per-host attribution stamp: N hosts emit N ledgers (namespaced
    # JSONL), and the process pair is what lets a straggler host be
    # singled out when the reports are laid side by side
    from ..parallel.dist import world
    idx, count = world()
    doc["process"] = {"index": idx, "count": count}
    return doc


def snapshot() -> Dict[str, Any]:
    """The ledger's section of ``telemetry.snapshot()`` and flight
    bundles — :func:`report` (already a pure read)."""
    return report()


def reset() -> None:
    """Drop all ledger state including the cost profile and any
    :func:`configure` overrides (test isolation)."""
    global _S, _SERVE, _ON_OVERRIDE, _WINDOW_OVERRIDE
    with _LOCK:
        _S = _new_state()
        _SERVE = _new_serve_state()
        _ON_OVERRIDE = None
        _WINDOW_OVERRIDE = None
