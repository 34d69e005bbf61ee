"""Profiler facade over jax.profiler/XProf with a hierarchical span recorder.

Host-side scopes record parented wall-time spans; :func:`step_report`
turns the per-step frames into a host-gap attribution report.

Reference parity (SURVEY §5.1): ``python/mxnet/profiler.py`` —
``set_config(filename=...)``, ``set_state('run'|'stop')``, ``pause``/
``resume``, user scopes (``Scope``/``Task``/``Frame``/``Marker``), ``dump()``,
``dumps()``. The C++ profiler's chrome://tracing JSON becomes an XProf/
TensorBoard trace directory; operator-level aggregation comes from the XLA
trace instead of hand-instrumented engine events. NVTX ranges map to
``jax.profiler.TraceAnnotation``.

Beyond the facade, user scopes *record* — hierarchically. Every
``Scope``/``Task`` exit appends a named wall-time span carrying its
**parent** (the enclosing scope on this thread), nesting **depth**, and the
current telemetry **step/request correlation** id; every ``Marker.mark``
appends an instant. All span timestamps come from one monotonic clock
anchored to the wall clock once at import (``perf_counter`` + a fixed
epoch), so nested spans provably nest on the merged chrome-trace timeline
(``mx.telemetry.chrome_trace``) instead of drifting against each other.

``Scope`` / ``Task`` / ``Frame`` are the ONE way to make a span: each writes
the ring record and a ``jax.profiler.TraceAnnotation``, so under an active
XProf trace the span sits in the ``.xplane.pb`` host plane on the clock of
the device's operations. Runtime code wraps its phases in them live
(``parallel.ShardedTrainer.step`` opens ``Frame("step")`` round
``step.place`` / ``step.dispatch`` / ``step.device_wait``) and reads the
measured ``dur_ms`` back from the scope after exit, so its event fields
and the span can never disagree. :func:`step_report` then aggregates
per-step frames into the host-gap attribution the whole-step-capture work
(ROADMAP open item 2) is judged by: each step split into ``place`` /
``dispatch`` / ``device_wait`` / ``python`` segments, plus the derived
host-gap (everything the host spends not blocked on the device).

:func:`dumps` aggregates spans into a JSON document (count/total/mean/
min/max/p50/p95/p99 per span name); :func:`dump` writes the merged
chrome-trace JSON atomically to the ``set_config(filename=...)`` path.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque as _deque
from collections import namedtuple
from typing import Dict, List, Optional

import jax

from .lockcheck import make_lock

__all__ = ["set_config", "set_state", "pause", "resume", "dump", "dumps",
           "Scope", "Task", "Frame", "Marker", "scope", "span_records",
           "reset_spans", "recent_spans", "step_report",
           "SpanRecord"]

_STATE = {"running": False, "dir": "profile_output", "aggregate": False,
          "started_at": None, "filename": "profile.json"}

# -- host-side span recorder -------------------------------------------------
#: cap per span name so a long-lived server cannot grow without bound; the
#: aggregate counters keep counting past the cap, only raw samples drop
_MAX_SAMPLES_PER_NAME = 8192

_SPAN_LOCK = make_lock("profiler._SPAN_LOCK")
_SPANS: Dict[str, dict] = {}          # name -> {count, total_ms, samples[]}
_MARKERS: List[dict] = []
_MARKERS_DROPPED = [0]                # overflow count past the sample cap

#: one raw span on the shared timeline. ``t_start`` is epoch seconds derived
#: from perf_counter + a fixed anchor, so two spans from one thread compare
#: exactly (a child's [t_start, t_start+dur] interval is contained in its
#: parent's — the property the chrome-trace merge and step_report rely on).
#: ``trace`` is the active distributed-trace correlation at record time —
#: ``(trace_id, span_id)`` or None — so the chrome-trace merge and the
#: otel export can stitch profiler wall-time spans into the request tree
SpanRecord = namedtuple(
    "SpanRecord", ["name", "kind", "t_start", "dur_ms", "parent", "depth",
                   "step", "trace"], defaults=[None])

#: raw span ring for the chrome-trace merge (mx.telemetry.chrome_trace)
#: and step_report — aggregates cannot be placed on a timeline
_RECENT: "_deque[SpanRecord]" = _deque(maxlen=4096)

#: wall-clock anchor for the monotonic span timeline: wall ≈ _EPOCH + perf.
#: ONE reading at import keeps every span on a single comparable clock.
_EPOCH = time.time() - time.perf_counter()

_TLS = threading.local()              # per-thread open-scope stack


def _stack() -> list:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def _current_step() -> Optional[int]:
    # lazy import: telemetry.export imports profiler for the trace merge
    from .telemetry.events import current_step
    return current_step()


def _trace():
    # lazy import, same reason as _current_step
    from .telemetry import trace
    return trace


def _trace_ids():
    """(trace_id, span_id) of the active distributed-trace context, or
    None — stamped onto every SpanRecord."""
    ctx = _trace().current()
    return (ctx.trace_id, ctx.span_id) if ctx is not None else None


def _append(rec: SpanRecord) -> None:
    with _SPAN_LOCK:
        ent = _SPANS.get(rec.name)
        if ent is None:
            ent = _SPANS[rec.name] = {
                "kind": rec.kind, "count": 0, "total_ms": 0.0,
                "min_ms": float("inf"), "max_ms": 0.0, "samples": []}
        ent["count"] += 1
        ent["total_ms"] += rec.dur_ms
        ent["min_ms"] = min(ent["min_ms"], rec.dur_ms)
        ent["max_ms"] = max(ent["max_ms"], rec.dur_ms)
        if len(ent["samples"]) < _MAX_SAMPLES_PER_NAME:
            ent["samples"].append(rec.dur_ms)
        _RECENT.append(rec)


def recent_spans() -> List[SpanRecord]:
    """Newest-last raw :class:`SpanRecord` rows — the timeline form the
    telemetry chrome-trace export merges with bus events and
    :func:`step_report` aggregates (bounded ring; the aggregates in
    :func:`span_records` keep the full counts)."""
    with _SPAN_LOCK:
        return list(_RECENT)


def reset_spans() -> None:
    """Drop all recorded spans and markers (``dumps(reset=True)`` calls
    this after rendering)."""
    with _SPAN_LOCK:
        _SPANS.clear()
        _MARKERS.clear()
        _RECENT.clear()
        _MARKERS_DROPPED[0] = 0


def span_records() -> Dict[str, dict]:
    """Aggregated span table ``{name: {kind, count, total_ms, mean_ms,
    min_ms, max_ms, p50_ms, p95_ms, p99_ms}}`` — the programmatic form of
    what :func:`dumps` serializes."""
    out: Dict[str, dict] = {}
    with _SPAN_LOCK:
        for name, ent in _SPANS.items():
            samples = sorted(ent["samples"])
            # a name with zero completed spans (markers-only usage, or a
            # started-but-never-stopped Task) would serialize min_ms=inf
            # as the invalid JSON token Infinity — normalize to 0.0 here
            # so every consumer sees strict-JSON-safe numbers
            min_ms = ent["min_ms"] if ent["min_ms"] != float("inf") else 0.0
            row = {"kind": ent["kind"], "count": ent["count"],
                   "total_ms": round(ent["total_ms"], 4),
                   "mean_ms": round(ent["total_ms"] / max(ent["count"], 1), 4),
                   "min_ms": round(min_ms, 4),
                   "max_ms": round(ent["max_ms"], 4)}
            from .util import nearest_rank_percentile
            for q in (50, 95, 99):
                p = nearest_rank_percentile(samples, q)
                row[f"p{q}_ms"] = round(p, 4) if p == p else 0.0
            out[name] = row
    return out


#: step_report segments that are device time, not host time — the host gap
#: is the frame total minus these (PyGraph's "dispatch tax" generalized:
#: on TPU the jitted call returns after enqueue, so dispatch/place/python
#: are all host-side; only an explicit sync blocks on the device)
_DEVICE_SEGMENTS = ("device_wait", "compute", "serve.compute")
#: one-off work that is host time but not *per-step* host tax — a
#: cold-bucket XLA compile inside a predict frame must not read as a
#: steady-state dispatch gap (it gets its own visible segment instead)
_ONEOFF_SEGMENTS = ("serve.compile", "compile")


def step_report(frame: str = "step", emit: bool = False) -> Dict:
    """Host-gap attribution over the recorded per-step frames.

    Aggregates every raw span whose ``kind`` is ``"frame"`` and name is
    ``frame`` (the trainer records one per :meth:`ShardedTrainer.step`;
    ``serve.CompiledModel.predict`` records ``"serve.predict"``), plus the
    spans parented to it. Each frame is split into named segments — the
    direct children (``place`` / ``dispatch`` / ``device_wait`` for the
    trainer; ``serve.pad`` / ``serve.compute`` / ``serve.unpad`` for
    serving) — and the remainder is attributed to ``python`` (host-side
    framework time between instrumented phases), so the whole frame is
    always accounted for. The derived ``host_gap_ms_*`` is the frame time
    minus device-side segments (:data:`_DEVICE_SEGMENTS`) and one-off
    compiles (:data:`_ONEOFF_SEGMENTS` — a cold-bucket compile is real
    host time but not steady-state dispatch tax) — the number ROADMAP
    open item 2 drives toward zero.

    Returns a strict-JSON-safe dict: ``{frame, steps, wall_ms_total,
    wall_ms_mean, segments: {name: {total_ms, mean_ms, count,
    share_pct}}, instrumented_pct, host_gap_ms_total, host_gap_ms_mean,
    memory: {live_bytes, live_arrays, sites}}`` — the ``memory``
    segment is the ``telemetry.memory`` ledger's current residency view
    beside the time attribution.
    ``instrumented_pct`` is the share of frame wall time covered by
    *measured* child spans (the ``python`` remainder excluded) — the
    honest instrumentation-coverage signal; the remainder itself is
    always attributed, so the segment table always sums to the frame.
    ``emit=True`` additionally publishes it as one ``perf.step_report``
    telemetry event. The report covers the raw-span ring window (newest
    ~4096 spans), not the whole process lifetime.
    """
    spans = recent_spans()
    frames = [r for r in spans if r.kind == "frame" and r.name == frame]
    n = len(frames)
    wall_total = sum(r.dur_ms for r in frames)
    segs: Dict[str, dict] = {}
    child_total = 0.0
    pfx = frame + "."
    for r in spans:
        if r.parent != frame:
            continue
        key = r.name[len(pfx):] if r.name.startswith(pfx) else r.name
        ent = segs.setdefault(key, {"total_ms": 0.0, "count": 0})
        ent["total_ms"] += r.dur_ms
        ent["count"] += 1
        child_total += r.dur_ms
    if n:
        # the un-instrumented remainder of each frame is host-side Python
        segs["python"] = {"total_ms": max(wall_total - child_total, 0.0),
                          "count": n}
    non_gap_ms = 0.0                  # device time + one-off compiles
    for key, ent in segs.items():
        if key in _DEVICE_SEGMENTS or key in _ONEOFF_SEGMENTS:
            non_gap_ms += ent["total_ms"]
        total = ent["total_ms"]
        ent["total_ms"] = round(total, 4)
        ent["mean_ms"] = round(total / max(n, 1), 4)
        ent["share_pct"] = (round(100.0 * total / wall_total, 2)
                            if wall_total else 0.0)
    instrumented = min(child_total, wall_total)
    host_gap = max(wall_total - non_gap_ms, 0.0)
    report = {
        "frame": frame,
        "steps": n,
        "wall_ms_total": round(wall_total, 4),
        "wall_ms_mean": round(wall_total / max(n, 1), 4),
        "segments": segs,
        "instrumented_pct": (round(100.0 * instrumented / wall_total, 2)
                             if wall_total else 0.0),
        "host_gap_ms_total": round(host_gap, 4),
        "host_gap_ms_mean": round(host_gap / max(n, 1), 4),
    }
    # current device-memory residency beside the time attribution: the
    # telemetry.memory ledger's light view (live bytes + per-site
    # attribution) — "where did the step's time AND memory go" in one
    # report
    from .telemetry import memory as _memory
    report["memory"] = _memory.segment()
    if emit:
        from .telemetry import events as _tele
        _tele.emit("perf.step_report", **{
            k: v for k, v in report.items() if k != "segments"},
            segments={k: v["total_ms"] for k, v in segs.items()})
    return report


def set_config(filename: str = "profile.json", profile_all: bool = False,
               profile_symbolic: bool = True, profile_imperative: bool = True,
               profile_memory: bool = True, profile_api: bool = True,
               aggregate_stats: bool = False, **kwargs) -> None:
    """Accepts the reference kwargs; ``filename`` is where :func:`dump`
    writes the merged chrome-trace JSON, and the XProf trace directory is
    derived from it (XProf writes a directory, not one JSON file)."""
    base = filename[:-5] if filename.endswith(".json") else filename
    _STATE["dir"] = base + "_xprof"
    _STATE["filename"] = filename
    _STATE["aggregate"] = aggregate_stats


def set_state(state: str = "stop") -> None:
    if state == "run" and not _STATE["running"]:
        os.makedirs(_STATE["dir"], exist_ok=True)
        jax.profiler.start_trace(_STATE["dir"])
        _STATE["running"] = True
        _STATE["started_at"] = time.time()
    elif state == "stop" and _STATE["running"]:
        jax.profiler.stop_trace()
        _STATE["running"] = False


def pause(profile_process: str = "worker") -> None:
    if _STATE["running"]:
        jax.profiler.stop_trace()
        _STATE["running"] = False


def resume(profile_process: str = "worker") -> None:
    if not _STATE["running"]:
        jax.profiler.start_trace(_STATE["dir"])
        _STATE["running"] = True


def dump(finished: bool = True, profile_process: str = "worker") -> str:
    """Flush the profile (reference: MXDumpProfile). Stops an active XProf
    trace (XProf writes on stop) and writes the merged chrome-trace JSON
    — recorded spans as nested complete events plus telemetry bus events
    as instants (``mx.telemetry.chrome_trace``) — to the
    ``set_config(filename=...)`` path. The write is atomic (tmp +
    ``os.replace``, the ``nd.save`` pattern), so a reader never sees a
    truncated trace. Returns the path written."""
    if _STATE["running"]:
        set_state("stop")
    from .telemetry.export import chrome_trace
    path = _STATE["filename"]
    doc = chrome_trace()
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp-{os.getpid()}"
    try:
        with open(tmp, "w", encoding="utf-8") as f:
            f.write(doc)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)  # never leave a truncated trace
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return path


def dumps(reset: bool = False) -> str:
    """JSON document of every recorded user span and marker, plus a pointer
    at the XProf trace directory (per-op device detail lives in the trace
    viewer). ``reset=True`` clears the recorder after rendering — the
    serving bench uses this to emit per-phase reports."""
    with _SPAN_LOCK:
        markers = list(_MARKERS)
        dropped = _MARKERS_DROPPED[0]
    doc = {"trace_dir": _STATE["dir"],
           "note": "device-level op table: open trace_dir with "
                   "XProf/TensorBoard profile plugin",
           "spans": span_records(),
           "markers": markers,
           "markers_dropped": dropped}
    if reset:
        reset_spans()
    # strict JSON: any residual non-finite value (a pathological dur, a
    # future aggregate) becomes null instead of the Infinity/NaN tokens
    # json would otherwise emit (allow_nan=False enforces it)
    from .telemetry.export import sanitize
    return json.dumps(sanitize(doc), indent=1, sort_keys=True,
                      allow_nan=False)


class Scope:
    """User annotation scope (reference: mx.profiler.Scope; NVTX parity).
    Entering pushes onto the per-thread scope stack; exiting records a
    named wall-time span carrying its parent scope and nesting depth, so
    nested scopes nest — not interleave — on the merged trace timeline.
    ``dur_ms`` holds the measured duration after exit (None while open):
    the caller's metrics and the span come from the one reading."""

    _kind = "scope"

    def __init__(self, name: str = "<unk>", step: Optional[int] = None):
        self._name = name
        self._step = step
        self.dur_ms: Optional[float] = None
        self._ann = jax.profiler.TraceAnnotation(name)
        self._t0: Optional[float] = None
        self._tspan = None       # open trace.span manager, if sampled
        self._tspan_sp = None    # the Span it returned on enter

    def __enter__(self):
        self._t0 = time.perf_counter()
        _stack().append(self)
        # a sampled distributed trace adopts profiler scopes as spans:
        # serve.pad/compute/unpad land UNDER the request's tree instead
        # of beside it — the "one stitched tree" contract. Unsampled or
        # untraced: two thread-local reads, nothing recorded.
        self._tspan = None
        self._tspan_sp = None
        ctx = _trace().current()
        if ctx is not None and ctx.sampled:
            # the public scoped-span manager owns activation AND finish,
            # so the trace module's context-stack invariants live in one
            # place
            self._tspan = _trace().span(self._name, kind=self._kind)
            self._tspan_sp = self._tspan.__enter__()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        if self._t0 is None:
            return
        trace_ids = None
        if self._tspan is not None:
            self._tspan.__exit__(*(exc if len(exc) == 3
                                   else (None, None, None)))
            trace_ids = (self._tspan_sp.ctx.trace_id,
                         self._tspan_sp.ctx.span_id)
        else:
            trace_ids = _trace_ids()
        st = _stack()
        parent, depth = None, 0
        if self in st:
            i = len(st) - 1 - st[::-1].index(self)   # last occurrence
            parent = st[i - 1]._name if i > 0 else None
            depth = i
            del st[i]
        self.dur_ms = (time.perf_counter() - self._t0) * 1e3
        step = self._step if self._step is not None else _current_step()
        _append(SpanRecord(self._name, self._kind, _EPOCH + self._t0,
                           self.dur_ms, parent, depth, step, trace_ids))
        self._t0 = None


def scope(name: str = "<unk>") -> Scope:
    return Scope(name)


class Task(Scope):
    """Named task annotation (reference: profiler.Task)."""

    _kind = "task"

    def __init__(self, name: str = "task", domain=None,
                 step: Optional[int] = None):
        super().__init__(name, step=step)

    def start(self):
        self.__enter__()

    def stop(self):
        self.__exit__(None, None, None)


class Frame(Task):
    """A per-iteration frame (reference: profiler.Frame). Frames are what
    :func:`step_report` aggregates: one ``Frame("step")`` per training
    step (the trainer records it), children attributed as segments."""

    _kind = "frame"


class Marker:
    """Instant event (reference: profiler.Marker.mark). Each ``mark``
    appends a timestamped instant to the recorder (and emits a zero-length
    TraceAnnotation so it shows in the XProf timeline too)."""

    def __init__(self, name: str = "marker", domain=None):
        self._name = name

    def mark(self, scope_name: str = "process") -> None:
        with jax.profiler.TraceAnnotation(f"{self._name}:{scope_name}"):
            pass
        with _SPAN_LOCK:
            if len(_MARKERS) < _MAX_SAMPLES_PER_NAME:
                _MARKERS.append({"name": self._name, "scope": scope_name,
                                 "t": time.time()})
            else:  # bounded like span samples: a long-lived server must
                _MARKERS_DROPPED[0] += 1  # not grow without limit
