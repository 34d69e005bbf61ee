"""Recompile ledger — every XLA compile in the process, one table.

Reference counterpart: the reference's ``CachedOp`` captured once per
(shape, train-mode) bucket and cache misses were visible in the engine
profile. On a jit runtime a recompile is the *dominant silent failure
mode* — seconds of latency, growing device memory, no exception anywhere
(PyGraph, arXiv 2503.19779; the XLA fusion study, arXiv 2301.13062, makes
the measure-don't-guess argument). Three jit caches already exist
(``CompiledModel`` buckets, ``ShardedTrainer.step``, the hybridize
``_call_cached_op`` cache) and each kept private counters; this ledger is
where they all report, so **"zero unexpected recompiles" is assertable
anywhere** — not just inside serve.

Every :func:`note` records the triggering (shape, dtype) signature, the
wall time the compile cost (when the call site measures it), the call
site, and whether the site considers itself still warming up. Post-warmup
compiles are the bug signal: ``post_warmup_compiles() == 0`` is the
steady-state contract the serve bench, the telemetry CI smoke job, and
``assert_zero_post_warmup()`` all enforce. Each note also publishes a
``compile`` event on the bus (with the current step/request correlation
ids) and bumps ``mxtpu_compiles_total{phase=...}``.

What a compile's wall time went on is asked of jax itself: one
``jax.monitoring`` duration listener, registered at import, adds up per
site the seconds jax reports for tracing, lowering, the backend compile
and the persistent cache's read (:func:`phase_seconds`). The site is the
innermost :func:`at` block open on the thread, which the ``note`` call
sites put round their jit call; anything else is ``"other"``.

What a site's newest program is, as the device runs it, is asked of jax
too, and only on request: :func:`keep_program` holds how to get it, and
:func:`program_text` gets the optimized HLO text, in which every device
operation has its instruction name and the ``op_name`` of the scopes it
was traced under (a profiler trace names an operation by its instruction
alone). Nothing is lowered or read until that call.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

import jax

from ..lockcheck import make_lock

__all__ = ["CompileRecord", "note", "mark_warmed", "is_warmed", "records",
           "summary", "post_warmup_compiles", "assert_zero_post_warmup",
           "at", "phase_seconds", "keep_program", "program_text", "clear",
           "MAX_RECORDS"]

#: ledger ring size — a recompile storm must not grow host memory unbounded
MAX_RECORDS = 4096


class CompileRecord:
    """One compile event: where, what signature, how long, which phase."""

    __slots__ = ("site", "signature", "wall_ms", "warmup", "ts", "step")

    def __init__(self, site: str, signature: str, wall_ms: Optional[float],
                 warmup: bool, ts: float, step: Optional[int]):
        self.site = site
        self.signature = signature
        self.wall_ms = wall_ms
        self.warmup = warmup
        self.ts = ts
        self.step = step

    def to_dict(self) -> Dict:
        return {"site": self.site, "signature": self.signature,
                "wall_ms": self.wall_ms, "warmup": self.warmup,
                "ts": round(self.ts, 6), "step": self.step}

    def __repr__(self):
        phase = "warmup" if self.warmup else "POST-WARMUP"
        ms = f", {self.wall_ms:.1f}ms" if self.wall_ms is not None else ""
        return f"CompileRecord({self.site}, {phase}{ms}, {self.signature})"


_LOCK = make_lock("compile_log._LOCK")
_RECORDS: deque = deque(maxlen=MAX_RECORDS)
_TOTALS = {"warmup": 0, "post_warmup": 0}
_BY_SITE: Dict[str, Dict[str, int]] = {}
_WARMED: set = set()


#: jax's own account of a compile (jax 0.9.0 ``_src/dispatch.py``,
#: ``_src/compiler.py``), by the key :func:`phase_seconds` reports it under
_PHASE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
}
_PHASES: Dict[str, Dict[str, float]] = {}    # site -> seconds by phase
#: ``.site``: the innermost open :func:`at` block; ``.ended``: this
#: thread's reported events not yet inside a later one, ``(end, seconds)``
_TLS = threading.local()
#: a trace holds a thousand jits traced inside it, all of them pending
#: until it ends; past this many the oldest is counted where it stands
_MAX_PENDING = 16384


def _zero_phases() -> Dict[str, float]:
    return dict.fromkeys(_PHASE_OF.values(), 0.0) | {"events": 0}


def _on_duration(event: str, duration: float, **_kw) -> None:
    key = _PHASE_OF.get(event)
    if key is None:
        return
    # jax reports an event as it ends, and events nest (a jit traced
    # inside a trace, the cache read inside the backend compile): what
    # ended after this one began lies inside it and has been counted, so
    # each event adds its own time only and the phases sum to wall time
    now = time.monotonic()
    ended = _TLS.__dict__.setdefault("ended", deque(maxlen=_MAX_PENDING))
    inner = 0.0
    while ended and ended[-1][0] > now - duration:
        inner += ended.pop()[1]
    ended.append((now, duration))
    where = getattr(_TLS, "site", None) or "other"
    with _LOCK:
        ent = _PHASES.setdefault(where, _zero_phases())
        ent[key] += max(duration - inner, 0.0)
        ent["events"] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


class at:
    """Attribute what jax traces, lowers and compiles on this thread inside
    the block to the site ``name`` (the innermost block wins)."""

    __slots__ = ("_name", "_outer")

    def __init__(self, name: str):
        self._name = name

    def __enter__(self):
        self._outer = getattr(_TLS, "site", None)
        _TLS.site = self._name

    def __exit__(self, *exc):
        _TLS.site = self._outer


def phase_seconds(site: Optional[str] = None) -> Dict[str, float]:
    """Seconds jax reported since the last :func:`clear`, at one site or
    (``None``) at all of them: ``{"trace_s", "lower_s",
    "backend_compile_s", "cache_retrieval_s", "events"}``. Each event
    counts its own time, less the events inside it (the jits traced
    inside a trace; the cache read inside the backend compile, so warm
    ``backend_compile_s`` is the key's hashing and the executable's
    loading): the four add up to the wall time jax spent compiling."""
    out = _zero_phases()
    with _LOCK:
        for ent in (_PHASES.values() if site is None
                    else [_PHASES.get(site, {})]):
            for k, v in ent.items():
                out[k] += v
    return out


#: site -> a call that returns the text of the newest program compiled there
_PROGRAMS: Dict[str, Callable[[], Optional[str]]] = {}


def keep_program(site: str, text_of: Callable[[], Optional[str]]) -> None:
    """Keep ``text_of`` as the way to the newest program compiled at
    ``site``: a call, made only by :func:`program_text`, that returns the
    compiled program's ``as_text()`` (or ``None`` once it is gone). It
    should hold the program's owner weakly, so that keeping it keeps
    nothing alive unless the owner asks (``ShardedTrainer`` pins its step
    when a profiler trace records it)."""
    with _LOCK:
        _PROGRAMS[site] = text_of


def program_text(site: str) -> Optional[str]:
    """The optimized HLO text of the newest program compiled at ``site``
    (instruction names and each one's ``metadata={op_name=...}``), or
    ``None`` where nothing was kept. Costs a lowering and a compile-cache
    read: call it after the work it describes, never on a hot path."""
    with _LOCK:
        text_of = _PROGRAMS.get(site)
    return text_of() if text_of is not None else None


def mark_warmed(site: str) -> None:
    """Declare ``site`` past its warmup phase: compiles noted there
    without an explicit ``warmup=`` flag count as post-warmup from now on
    (``CompiledModel.warmup()`` does the equivalent internally; call this
    after your own warmup loop for hybridize/step sites)."""
    with _LOCK:
        _WARMED.add(site)


def is_warmed(site: str) -> bool:
    with _LOCK:
        return site in _WARMED


def note(site: str, signature, wall_ms: Optional[float] = None,
         warmup: Optional[bool] = None) -> CompileRecord:
    """Record one compile at ``site``. ``signature`` is any repr-able
    shape/dtype description; ``warmup=False`` marks it unexpected (the
    site believed it was past its warmup phase). ``warmup=None`` derives
    the phase from :func:`mark_warmed` state. Publishes a ``compile``
    bus event and the ``mxtpu_compiles_total`` counter as side effects."""
    if warmup is None:
        warmup = not is_warmed(site)
    rec = CompileRecord(site, repr(signature)[:300],
                        None if wall_ms is None else round(wall_ms, 3),
                        bool(warmup), time.time(),
                        None)
    from . import events as _events
    rec.step = _events.current_step()
    phase = "warmup" if rec.warmup else "post_warmup"
    with _LOCK:
        _RECORDS.append(rec)
        _TOTALS[phase] += 1
        ent = _BY_SITE.setdefault(site, {"warmup": 0, "post_warmup": 0})
        ent[phase] += 1
    from . import metrics as _metrics
    _metrics.counter("mxtpu_compiles_total",
                     "XLA compile events recorded by the telemetry ledger",
                     site=site, phase=phase).inc()
    _events.emit("compile",
                 severity="info" if rec.warmup else "warning",
                 site=site, signature=rec.signature, wall_ms=rec.wall_ms,
                 warmup=rec.warmup)
    return rec


def records(site: Optional[str] = None) -> List[CompileRecord]:
    with _LOCK:
        out = list(_RECORDS)
    return [r for r in out if site is None or r.site == site]


def summary() -> Dict:
    """The ledger rollup ``telemetry.snapshot()`` inlines."""
    with _LOCK:
        recent = [r.to_dict() for r in list(_RECORDS)[-5:]]
        return {"total": _TOTALS["warmup"] + _TOTALS["post_warmup"],
                "warmup": _TOTALS["warmup"],
                "post_warmup": _TOTALS["post_warmup"],
                "by_site": {k: dict(v) for k, v in _BY_SITE.items()},
                "phase_seconds": {k: dict(v) for k, v in _PHASES.items()},
                "recent": recent}


def post_warmup_compiles(site: Optional[str] = None) -> int:
    with _LOCK:
        if site is not None:
            return _BY_SITE.get(site, {}).get("post_warmup", 0)
        return _TOTALS["post_warmup"]


def assert_zero_post_warmup(site: Optional[str] = None) -> None:
    """Raise ``MXNetError`` if any post-warmup compile was recorded
    (optionally at one site) — the steady-state contract, assertable from
    anywhere. Gated on the exact counters (which never age out), with the
    bounded record ring supplying whatever detail is still held."""
    n = post_warmup_compiles(site)
    if n:
        bad = [r for r in records(site) if not r.warmup]
        detail = ("\n".join(f"  {r!r}" for r in bad[-10:]) if bad else
                  "  (records aged out of the ring; counters are exact)")
        from ..base import MXNetError
        raise MXNetError(
            f"{n} unexpected (post-warmup) XLA compile(s):\n" + detail)


def clear() -> None:
    with _LOCK:
        _RECORDS.clear()
        _TOTALS["warmup"] = _TOTALS["post_warmup"] = 0
        _BY_SITE.clear()
        _WARMED.clear()
        _PHASES.clear()
        _PROGRAMS.clear()
