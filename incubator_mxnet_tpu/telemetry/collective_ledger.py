"""Collective-schedule ledger — the runtime twin of the MX9xx passes.

Reference counterpart: none. The ps-lite lineage's dominant multi-host
failure was visible (a dead server, a dropped connection, a timeout);
the multi-controller SPMD model trades it for an *invisible* one — one
process takes a divergent branch, compiles a different step graph, and
the whole pod blocks inside a collective that part of it never issues.
No crash, no log line, a hung pod burning its reservation.

This ledger makes the invariant checkable at runtime, the same shape as
``MX802 ↔ lockcheck`` and ``MX706 ↔ compile ledger`` one tier down:

- **Bank at build time**: every pjit step / serve bucket build banks a
  cheap *fingerprint* of its compiled collective structure — the ordered
  collective verb/axis schedule (the SAME
  :func:`~...analysis.distributed.schedule.schedule_of` extractor the
  static MX905 pass uses), the cost model's collective-op counts and
  per-device comm bytes, and the triggering signature — keyed by
  ``(site, signature)``.
- **Ring at dispatch time**: each executed step appends ``(site,
  signature)`` to a bounded schedule ring — the "what was this pod
  actually dispatching" half of a post-mortem, snapshotted into every
  flight bundle.
- **Crosscheck at the dangerous moments**: :func:`crosscheck` exchanges
  each process's banked digest table through the jax coordination
  service (key-value store, NOT a collective — a missing peer times out
  loudly instead of hanging) at ``dist.initialize()`` and after any
  post-warmup recompile. A mismatch — or a peer that never shows up,
  which IS the divergence — writes one flight bundle and raises
  ``MXNetError`` instead of letting the pod wedge.

Contract: **off by default, near-zero when off** — every hook is one
env-cached boolean read when ``MXTPU_COLLECTIVE_LEDGER`` is unset.
Banking re-traces the step (no XLA compile) only when enabled, and only
once per new signature — build-time cost, never per-step cost.

Chaos hook: the seeded ``collective_divergence`` knob
(``fault.inject``) perturbs THIS process's digest table with a value
folded over ``process_index()`` just before the exchange, so any
>=2-process crosscheck with the knob fired must trip — the end-to-end
drill ``tools/collective_smoke.py`` and the CI crosscheck smoke run.

Env knobs (catalogued in ``util.ENV_VARS`` / docs/env_vars.md):
``MXTPU_COLLECTIVE_LEDGER`` (master switch),
``MXTPU_COLLECTIVE_LEDGER_RING`` (dispatch ring size),
``MXTPU_COLLECTIVE_LEDGER_TIMEOUT_S`` (peer exchange timeout).
"""
from __future__ import annotations

import hashlib
import time
import warnings
from collections import deque
from typing import Dict, List, Optional

from ..base import MXNetError
from ..lockcheck import make_lock

__all__ = ["enabled", "fingerprint", "bank", "bank_graph", "bank_closed",
           "bank_trainer",
           "banked", "digest_table", "note_dispatch", "schedule_ring",
           "crosscheck", "CollectiveMismatchError", "snapshot", "reset"]

_LOCK = make_lock("collective_ledger._LOCK")
#: (site, signature) -> fingerprint dict (with its "digest" filled in)
_BANKED: Dict[tuple, Dict] = {}
_RING: Optional[deque] = None
_DISPATCHES: Dict[str, int] = {}
#: crosscheck bookkeeping: per-tag epoch counters (the exchange keys
#: must match across processes, so they derive from call order — a
#: process whose call order diverges times out, which IS the finding)
_EPOCHS: Dict[str, int] = {}
_STATS = {"crosschecks": 0, "mismatches": 0, "last": None}
_TRIPPED = [False]


class CollectiveMismatchError(MXNetError):
    """Raised when the cross-process fingerprint exchange disagrees.

    A peer that never publishes raises too: the pod was about to
    diverge inside a collective — die loudly with evidence instead of
    hanging."""


def enabled() -> bool:
    """True when ``MXTPU_COLLECTIVE_LEDGER`` is 1/true/on/yes."""
    from ..util import getenv
    return str(getenv("MXTPU_COLLECTIVE_LEDGER") or "0").lower() \
        in ("1", "true", "on", "yes")


def _ring() -> deque:
    global _RING
    if _RING is None:
        from ..util import getenv
        try:
            n = int(getenv("MXTPU_COLLECTIVE_LEDGER_RING"))
        except (TypeError, ValueError):
            n = 512
        _RING = deque(maxlen=max(16, n))
    return _RING


def _timeout_s() -> float:
    from ..util import getenv
    try:
        return float(getenv("MXTPU_COLLECTIVE_LEDGER_TIMEOUT_S"))
    except (TypeError, ValueError):
        return 20.0


def _sig_key(signature) -> str:
    return repr(signature)[:300]


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------

def fingerprint(schedule: List[str], collective_ops: Dict[str, int],
                comm_bytes: float, signature, mesh_axes=None) -> Dict:
    """One build's collective fingerprint, ``digest`` included.

    The fingerprint carries the ordered explicit schedule, the verb
    counts, per-device comm bytes, the triggering signature, and the
    mesh axes. The ordered explicit schedule comes from the same extractor MX905
    uses; the cost model's verb counts include the implied SPMD gradient
    exchange the jaxpr cannot show. The digest is a sha1 over the
    strict-JSON canonical form — the only thing the exchange ships."""
    from .export import dumps_strict
    if isinstance(mesh_axes, dict):
        axes = [f"{k}={v}" for k, v in sorted(mesh_axes.items())]
    else:
        axes = [str(a) for a in (mesh_axes or ())]
    doc = {"schedule": list(schedule),
           "collective_ops": {k: int(v)
                              for k, v in sorted(collective_ops.items())},
           "comm_bytes": int(comm_bytes),
           "signature": _sig_key(signature),
           "mesh_axes": axes}
    doc["digest"] = hashlib.sha1(
        dumps_strict(doc, sort_keys=True).encode()).hexdigest()
    return doc


def fingerprint_of_graph(g) -> Dict:
    """Fingerprint one :class:`~...analysis.hlo.trace.TracedGraph`."""
    from ..analysis.distributed.schedule import schedule_of
    from ..analysis.hlo.cost import graph_cost
    c = graph_cost(g)
    return fingerprint(schedule_of(g.closed), c.collective_ops,
                       c.comm_bytes, g.signature, g.mesh_axes)


def bank(site: str, signature, fp: Dict) -> None:
    """Bank one build's fingerprint under ``(site, signature)``.

    When this is a POST-WARMUP recompile in a multi-process run it also
    crosschecks immediately — a late recompile only one host performs
    is the classic divergence onset, and the exchange timeout catches
    exactly that."""
    if not enabled():
        return
    key = (site, _sig_key(signature))
    rebank = False
    with _LOCK:
        prev = _BANKED.get(key)
        rebank = prev is not None and prev.get("digest") != fp.get("digest")
        _BANKED[key] = dict(fp)
    from . import events as _events
    from . import metrics as _metrics
    _events.emit("collective.bank", site=site, signature=_sig_key(signature),
                 digest=fp.get("digest"), rebank=rebank,
                 collectives=sum(fp.get("collective_ops", {}).values()))
    _metrics.counter("mxtpu_collective_banked_total",
                     "Collective-schedule fingerprints banked",
                     site=site).inc()
    from . import compile_log
    if compile_log.is_warmed(site) and _num_processes() > 1:
        crosscheck(f"recompile/{site}")


def bank_graph(site: str, g) -> Optional[Dict]:
    """Fingerprint + bank one traced graph (no XLA compile).

    Returns the fingerprint, or None when the ledger is off or tracing
    failed — banking must never become the fault that breaks a step."""
    if not enabled():
        return None
    try:
        fp = fingerprint_of_graph(g)
    except Exception as e:  # noqa: BLE001 — diagnostics never break builds
        warnings.warn(f"[collective_ledger] could not fingerprint "
                      f"{site}: {type(e).__name__}: {e}")
        return None
    bank(site, g.signature, fp)
    return fp


def bank_closed(site: str, closed, signature, mesh_axes=None
                ) -> Optional[Dict]:
    """Fingerprint + bank one (closed) jaxpr — the serving tier's hook.

    Here the build hands us the traced program directly and the cost
    model's per-graph accounting is not in play (verb counts derive from
    the schedule itself; comm bytes are not part of the serve digest)."""
    if not enabled():
        return None
    try:
        from ..analysis.distributed.schedule import schedule_of
        sched = schedule_of(closed)
        counts: Dict[str, int] = {}
        for entry in sched:
            verb = entry.split("@", 1)[0]
            counts[verb] = counts.get(verb, 0) + 1
        fp = fingerprint(sched, counts, 0, signature, mesh_axes)
    except Exception as e:  # noqa: BLE001 — diagnostics never break builds
        warnings.warn(f"[collective_ledger] could not fingerprint "
                      f"{site}: {type(e).__name__}: {e}")
        return None
    bank(site, signature, fp)
    return fp


def bank_trainer(trainer, batch_vals) -> Optional[Dict]:
    """Trace + fingerprint + bank a ShardedTrainer's step graph.

    Called by ``trainer.step`` on each NEW batch signature when the
    ledger is on. Pure tracing — no XLA compile."""
    if not enabled():
        return None
    try:
        from ..analysis.hlo.trace import _trace_trainer
        res = _trace_trainer(trainer, tuple(batch_vals))
        g = res.graphs[0]
    except Exception as e:  # noqa: BLE001 — diagnostics never break steps
        warnings.warn(f"[collective_ledger] could not trace trainer step: "
                      f"{type(e).__name__}: {e}")
        return None
    return bank_graph("trainer.step", g)


def banked() -> Dict[str, Dict[str, Dict]]:
    """Snapshot: ``{site: {signature: fingerprint}}``."""
    with _LOCK:
        out: Dict[str, Dict[str, Dict]] = {}
        for (site, sig), fp in _BANKED.items():
            out.setdefault(site, {})[sig] = dict(fp)
        return out


def digest_table() -> List[List[str]]:
    """The exchange payload: sorted ``[site, signature, digest]`` rows.

    Small and stable; the exchange never ships the schedules
    themselves."""
    with _LOCK:
        return sorted([site, sig, fp.get("digest", "")]
                      for (site, sig), fp in _BANKED.items())


# ---------------------------------------------------------------------------
# dispatch ring
# ---------------------------------------------------------------------------

def note_dispatch(site: str, signature) -> None:
    """Append one executed dispatch to the bounded schedule ring.

    Cheap: one deque append; no tracing, no hashing."""
    if not enabled():
        return
    sig = _sig_key(signature)
    with _LOCK:
        _ring().append({"site": site, "signature": sig,
                        "ts": round(time.time(), 6)})
        _DISPATCHES[site] = _DISPATCHES.get(site, 0) + 1


def schedule_ring() -> List[Dict]:
    """The dispatch ring, oldest first (a copy)."""
    with _LOCK:
        return [] if _RING is None else list(_RING)


# ---------------------------------------------------------------------------
# the cross-process exchange
# ---------------------------------------------------------------------------

def _coord():
    """(client, process_index, num_processes) from the jax coordination
    service WITHOUT initializing any backend — ``(None, 0, 1)`` when the
    process never rendezvoused (single-host runs, unit tests)."""
    # private jax module: if the installed jax moves it this must raise,
    # not turn the cross-process check off in silence
    from jax._src.distributed import global_state
    client = global_state.client
    if client is None:
        return None, 0, 1
    return (client, int(global_state.process_id or 0),
            int(global_state.num_processes or 1))


def _num_processes() -> int:
    return _coord()[2]


def _trip(tag: str, reason: str, detail: str, **ctx) -> None:
    """The mismatch path: one flight bundle per process lifetime, a
    telemetry event, a counter, then the loud raise — a wrong pod must
    die with evidence, not hang without any."""
    from . import events as _events
    from . import flight as _flight
    from . import metrics as _metrics
    with _LOCK:
        _STATS["mismatches"] += 1
        _STATS["last"] = {"tag": tag, "ok": False, "reason": reason}
        first = not _TRIPPED[0]
        _TRIPPED[0] = True
    _events.emit("collective.mismatch", severity="error", tag=tag,
                 reason=reason)
    _metrics.counter("mxtpu_collective_mismatch_total",
                     "Collective-schedule crosscheck trips",
                     reason=reason).inc()
    if first:
        _flight.dump("collective_schedule_mismatch", site=tag,
                     reason=reason, **ctx)
    raise CollectiveMismatchError(
        f"collective-schedule crosscheck failed at {tag!r} ({reason}): "
        f"{detail}\nThis pod would have hung inside a collective; "
        "raising instead. A flight bundle with the local schedule "
        "ledger was written (MXTPU_FLIGHT_DIR).")


def _diff_tables(mine: List, theirs: List) -> str:
    a = {tuple(r[:2]): r[2] for r in mine}
    b = {tuple(r[:2]): r[2] for r in theirs}
    lines = []
    for key in sorted(set(a) | set(b)):
        da, db = a.get(key), b.get(key)
        if da == db:
            continue
        site, sig = key
        lines.append(f"  {site} {sig}: local={da or '(unbanked)'} "
                     f"peer={db or '(unbanked)'}")
    return "\n".join(lines) or "  (tables differ only in chaos salt)"


def crosscheck(tag: str = "manual", peers: Optional[List[str]] = None,
               timeout_s: Optional[float] = None) -> Dict:
    """Exchange the banked digest table across the pod; raise on drift.

    ``peers`` injects peer payloads directly (unit tests); otherwise the
    jax coordination service's key-value store carries the exchange —
    deliberately NOT a collective, so a peer that never reaches this
    call (divergent control flow: the very bug being checked) turns
    into a loud timeout instead of a silent hang.

    Returns ``{"checked": bool, ...}``; raises
    :class:`CollectiveMismatchError` (an ``MXNetError``) on any
    mismatch, absent peer, or chaos-perturbed digest, after writing one
    flight bundle."""
    if not enabled():
        return {"checked": False, "reason": "disabled"}
    from .export import dumps_strict, loads_strict
    table = digest_table()
    blob = dumps_strict(table, sort_keys=True)
    # the seeded divergence drill: fold THIS process's identity into the
    # payload so every >=2-process exchange with the knob fired differs
    from ..fault import inject as _inject
    client, idx, n = _coord()
    if _inject.should("collective_divergence"):
        blob = dumps_strict({"table": table,
                             "chaos": f"divergence-p{idx}"},
                            sort_keys=True)
    with _LOCK:
        _STATS["crosschecks"] += 1
        epoch = _EPOCHS[tag] = _EPOCHS.get(tag, 0) + 1
    if peers is not None:
        for i, peer_blob in enumerate(peers):
            if peer_blob != blob:
                theirs = loads_strict(peer_blob)
                theirs = theirs["table"] if isinstance(theirs, dict) \
                    else theirs
                _trip(tag, "digest_mismatch",
                      f"peer #{i} banked a different collective "
                      f"schedule:\n{_diff_tables(table, theirs)}",
                      peer=i, local_table=table, peer_table=theirs)
        with _LOCK:
            _STATS["last"] = {"tag": tag, "ok": True,
                              "peers": len(peers)}
        return {"checked": True, "processes": len(peers) + 1,
                "entries": len(table)}
    if client is None or n <= 1:
        with _LOCK:
            _STATS["last"] = {"tag": tag, "ok": True,
                              "reason": "single_process"}
        return {"checked": False, "reason": "single_process"}
    timeout_ms = int((_timeout_s() if timeout_s is None
                      else timeout_s) * 1000)
    prefix = f"mxtpu/collective_ledger/{tag}/{epoch}"
    try:
        client.key_value_set(f"{prefix}/{idx}", blob)
    except Exception as e:  # noqa: BLE001 — coordination infra drift
        warnings.warn(f"[collective_ledger] crosscheck {tag!r}: "
                      f"key_value_set failed: {e}")
        return {"checked": False, "reason": "kv_set_failed"}
    for p in range(n):
        if p == idx:
            continue
        try:
            peer_blob = client.blocking_key_value_get(
                f"{prefix}/{p}", timeout_ms)
        except Exception:
            _trip(tag, "peer_timeout",
                  f"process {p} never published its fingerprint table "
                  f"within {timeout_ms} ms — it did not reach this "
                  f"crosscheck (tag {tag!r}, epoch {epoch}): divergent "
                  "control flow or a wedged host",
                  peer=p, local_table=table)
        if peer_blob != blob:
            theirs = loads_strict(peer_blob)
            theirs = theirs["table"] if isinstance(theirs, dict) else theirs
            _trip(tag, "digest_mismatch",
                  f"process {p} banked a different collective "
                  f"schedule:\n{_diff_tables(table, theirs)}",
                  peer=p, local_table=table, peer_table=theirs)
    from . import events as _events
    _events.emit("collective.crosscheck", tag=tag, processes=n,
                 entries=len(table))
    with _LOCK:
        _STATS["last"] = {"tag": tag, "ok": True, "processes": n}
    return {"checked": True, "processes": n, "entries": len(table)}


# ---------------------------------------------------------------------------
# snapshot / reset
# ---------------------------------------------------------------------------

def snapshot() -> Dict:
    """The ledger's flight-bundle / ``telemetry.snapshot()`` section."""
    on = enabled()  # env read outside the lock
    with _LOCK:
        ring = [] if _RING is None else list(_RING)
        return {"enabled": on,
                "banked": {f"{site}|{sig}": dict(fp)
                           for (site, sig), fp in sorted(_BANKED.items())},
                "dispatches": dict(_DISPATCHES),
                "ring": ring[-64:],
                "crosschecks": dict(_STATS, last=_STATS["last"])}


def reset() -> None:
    """Clear every ledger surface (tests; ``telemetry.reset()``)."""
    global _RING
    with _LOCK:
        _BANKED.clear()
        _DISPATCHES.clear()
        _EPOCHS.clear()
        _RING = None
        _STATS["crosschecks"] = _STATS["mismatches"] = 0
        _STATS["last"] = None
        _TRIPPED[0] = False
