"""Flight director — the closed adaptive loop over goodput × autotune.

Reference counterpart: none. PRs 11–14 built every piece of an adaptive
loop and left it open: the autotune cache banks per-(model, mesh, chip)
roofline winners from a trace-only search, and the goodput ledger
measures where wall-clock actually went — including the
``mxtpu_goodput_mfu_divergence_pct`` gauge and a dominant-bucket
classification — yet nothing consumed either signal. This module closes
it: a :class:`FlightDirector` subscribes to ``goodput.window`` events
and, when measured MFU diverges below the roofline by more than a
threshold (or the dominant bucket drifts) across consecutive windows,
re-runs the trace-only autotune search with the *measured* attribution
folded into the roofline score (``benchmark.autotune.score(metrics,
measured=...)``), then hot-applies **one** safe remediation per site
from the allowlisted :data:`POLICY` table:

========================  ==================================================
``input_bound``           grow the prefetch queue —
                          ``io.PrefetchIter.set_depth`` (live resize, no
                          worker restart, no batch dropped)
``compute_bound``         staged recompile — ``ShardedTrainer.retune``
                          swaps the tuned config and rebuilds the pjit
                          step; the one compile the next step pays is
                          banked on the compile ledger under the
                          ``director.recompile`` site, so the
                          ``trainer.step`` zero-post-warmup contract
                          stays assertable across the cutover
``slo.burn`` breach       serve-side shed/hedge —
                          ``Router.set_overload_policy`` (tighter shed
                          depth, hedging enabled)
========================  ==================================================

Every decision is itself first-class observability: a
``director.decision`` event carrying the trigger window, divergence,
candidate table, chosen action and hysteresis state; ``mxtpu_director_*``
gauges; and a bounded decision ring embedded in ``telemetry.snapshot()``
and flight bundles and rendered by ``tools/postmortem.py``. The loop is
*damped*: a trigger needs ``MXTPU_DIRECTOR_WINDOWS`` consecutive breached
windows, every action opens a ``MXTPU_DIRECTOR_COOLDOWN``-window cooldown,
and the first post-cooldown window is compared against the pre-action
baseline — revert-if-worse with **exactly one revert** (a reverted action
kind is vetoed for the rest of the run), so a chaos-injected phase
triggers one correct remediation and can never oscillate A→B→A.

Everything is **off by default** (``MXTPU_DIRECTOR`` unset):
:func:`install` is one env read and returns ``None``; nothing subscribes,
no hot path changes, and the compiled graphs are untouched either way
(host-side bookkeeping only, same as numerics/goodput).

Usage::

    MXTPU_DIRECTOR=1 python train.py   # or director.configure(on=True)

    goodput.configure(on=True); goodput.price(tr, sample_args=(x, y))
    director.install(trainer=tr, prefetch=it)   # None while off
    goodput.begin()
    ...                                         # loop runs itself
    telemetry.snapshot()["director"]["decisions"]   # the audit trail
"""
from __future__ import annotations

import os
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..lockcheck import make_lock

__all__ = ["FlightDirector", "POLICY", "enabled", "configure", "install",
           "uninstall", "get", "snapshot", "reset"]

#: the remediation allowlist — dominant-bucket classification → the ONE
#: action kind the director may hot-apply for it. Classifications absent
#: here (``collective_bound``, ``host_bound``) produce an audited
#: no-action decision: there is no safe single-knob remediation, so the
#: director records the diagnosis and stays hands-off.
POLICY: Dict[str, str] = {
    "input_bound": "io.prefetch_depth",
    "compute_bound": "trainer.retune",
    # a window with rolled-back steps outranks its bound-bucket: the run
    # is paying for work it then discards (grad blowup under chaos or
    # bad geometry), and the safe knob is the same staged recompile —
    # re-stage the tuned config, never touch the guard's policy
    "rollback_storm": "trainer.retune",
    "serve_breach": "router.overload_policy",
}

_ON_OVERRIDE: Optional[bool] = None
_DIRECTOR: Optional["FlightDirector"] = None


def enabled() -> bool:
    """One env read (``MXTPU_DIRECTOR``) unless :func:`configure`
    overrode it — the entire cost of the feature while off."""
    if _ON_OVERRIDE is not None:
        return _ON_OVERRIDE
    return os.environ.get("MXTPU_DIRECTOR", "0") == "1"


def configure(on: Optional[bool] = None) -> None:
    """Process-wide override of the ``MXTPU_DIRECTOR`` switch (tests and
    drivers); ``None`` leaves the env in charge."""
    global _ON_OVERRIDE
    _ON_OVERRIDE = on


def _envf(name: str) -> float:
    from ..util import getenv
    return float(getenv(name))


def _envi(name: str) -> int:
    from ..util import getenv
    return int(getenv(name))


class FlightDirector:
    """The closed loop: goodput windows in, allowlisted remediations out,
    every decision on the audit ring. Host-side only; all state under one
    lock; the event subscription is the only hook into the runtime."""

    def __init__(self, trainer=None, prefetch=None, router=None, *,
                 divergence_pct: Optional[float] = None,
                 windows: Optional[int] = None,
                 cooldown: Optional[int] = None,
                 revert_margin_pct: Optional[float] = None,
                 ring: Optional[int] = None,
                 max_depth: Optional[int] = None,
                 search_budget: Optional[int] = None,
                 hedge_ms: Optional[float] = None):
        self.trainer = trainer
        self.prefetch = prefetch
        self.router = router
        self.divergence_pct = (divergence_pct if divergence_pct is not None
                               else _envf("MXTPU_DIRECTOR_DIVERGENCE_PCT"))
        self.windows_needed = max(1, windows if windows is not None
                                  else _envi("MXTPU_DIRECTOR_WINDOWS"))
        self.cooldown = max(1, cooldown if cooldown is not None
                            else _envi("MXTPU_DIRECTOR_COOLDOWN"))
        self.revert_margin_pct = (
            revert_margin_pct if revert_margin_pct is not None
            else _envf("MXTPU_DIRECTOR_REVERT_MARGIN_PCT"))
        self.max_depth = max(1, max_depth if max_depth is not None
                             else _envi("MXTPU_DIRECTOR_MAX_DEPTH"))
        self.search_budget = max(1, search_budget if search_budget is not None
                                 else _envi("MXTPU_DIRECTOR_BUDGET"))
        self.hedge_ms = (hedge_ms if hedge_ms is not None
                         else _envf("MXTPU_DIRECTOR_HEDGE_MS"))
        self._lock = make_lock("FlightDirector._lock")
        self._ring: deque = deque(maxlen=max(
            1, ring if ring is not None else _envi("MXTPU_DIRECTOR_RING")))
        self._n = 0                  # decision ids (monotonic)
        self._streak = 0             # consecutive breached windows
        self._cooldown_left = 0      # windows the loop still holds
        self._stable_class: Optional[str] = None
        self._last_div: Optional[float] = None
        self._pending: Optional[Dict[str, Any]] = None  # action under eval
        self._vetoed: set = set()    # action kinds disabled after a revert
        self._held: set = set()      # kinds kept but frozen (no effect)
        self._serve_acted: set = set()   # slo names already remediated
        self._reverts = 0
        self._decisions = 0
        self._sub: Optional[Callable] = None

    # ------------------------------------------------------------------
    # wiring
    # ------------------------------------------------------------------
    def start(self) -> "FlightDirector":
        from . import events as _events
        if self._sub is None:
            self._sub = self._on_event
            _events.subscribe(self._sub)
        return self

    def close(self) -> None:
        from . import events as _events
        if self._sub is not None:
            _events.unsubscribe(self._sub)
            self._sub = None

    def _on_event(self, ev) -> None:
        # the one hook: everything else in this module runs only when a
        # window closes or an SLO alert fires — never per step/request
        if ev.kind == "goodput.window":
            self._on_window(dict(ev.fields or {}))
        elif ev.kind == "slo.burn":
            self._on_burn(ev)

    # ------------------------------------------------------------------
    # the loop
    # ------------------------------------------------------------------
    def _on_window(self, win: Dict[str, Any]) -> None:
        mfu = win.get("mfu") or {}
        div = mfu.get("divergence_pct")
        cls = win.get("classification")
        wid = win.get("window")
        evaluate = trigger = False
        with self._lock:
            self._last_div = div
            # divergence sign convention (pinned by test_goodput):
            # 100·(measured/predicted − 1) — measured BELOW the roofline
            # is negative, so the breach test is div <= −threshold
            breach = div is not None and div <= -self.divergence_pct
            drift = (cls is not None and self._stable_class is not None
                     and cls != self._stable_class)
            if cls is not None and self._stable_class is None:
                self._stable_class = cls
            if self._cooldown_left > 0:
                self._cooldown_left -= 1
                self._streak = 0
                # the first fully-post-cooldown window is the evaluation
                # sample for revert-if-worse
                evaluate = (self._cooldown_left == 0
                            and self._pending is not None)
            else:
                self._streak = self._streak + 1 if (breach or drift) else 0
                trigger = self._streak >= self.windows_needed
                if trigger:
                    self._streak = 0
        self._publish_gauges()
        if evaluate:
            self._evaluate(win, div)
        elif trigger:
            self._trigger(win, wid, div, cls, breach, drift)

    def _trigger(self, win: Dict, wid, div, cls, breach: bool,
                 drift: bool) -> None:
        key = ("rollback_storm" if (win.get("rolled_back_steps") or 0) > 0
               else (cls or ""))
        kind = POLICY.get(key)
        candidates: List[Dict[str, Any]] = []
        action: Dict[str, Any]
        undo: Optional[Callable] = None
        baseline = div
        if kind is None:
            action = {"kind": "none", "reason":
                      f"no allowlisted remediation for {key!r}"}
        elif kind in self._vetoed:
            action = {"kind": "none",
                      "reason": f"{kind} vetoed after its one revert"}
        elif kind in self._held:
            action = {"kind": "none",
                      "reason": f"{kind} held: a previous application "
                                "produced no measurable improvement"}
        elif kind == "io.prefetch_depth":
            candidates, action, undo = self._apply_prefetch()
        elif kind == "trainer.retune":
            candidates, action, undo = self._apply_retune(win)
        else:                                    # pragma: no cover
            action = {"kind": "none", "reason": f"unknown policy {kind!r}"}
        with self._lock:
            # any decision — applied or audited no-action — opens a
            # cooldown: the loop never spams one diagnosis per window
            self._cooldown_left = self.cooldown
            if undo is not None:
                self._pending = {"kind": action["kind"], "undo": undo,
                                 "baseline_div": baseline, "window": wid}
            if cls is not None:
                self._stable_class = cls
        self._decide(trigger={"window": wid, "divergence_pct": div,
                              "classification": cls, "policy_key": key,
                              "rolled_back_steps":
                                  win.get("rolled_back_steps"),
                              "breach": breach, "drift": drift},
                     candidates=candidates, action=action)

    def _evaluate(self, win: Dict, post_div: Optional[float]) -> None:
        """The damping half of the loop, one outcome per applied action:
        compare the first post-cooldown window against the pre-action
        baseline. Clearly *worse* → revert (exactly once — the kind is
        vetoed afterwards). Clearly *better* → keep, and the kind stays
        armed (further escalation is allowed while it is measurably
        helping). Neither → keep but **hold** the kind: re-applying a
        knob that did not move the needle is the hunting behavior the
        hysteresis exists to prevent."""
        with self._lock:
            pending, self._pending = self._pending, None
        if pending is None:
            return
        base = pending.get("baseline_div")
        worse = (post_div is not None and base is not None
                 and post_div < base - self.revert_margin_pct)
        better = (post_div is not None and base is not None
                  and post_div > base + self.revert_margin_pct)
        if not worse:
            if not better:
                with self._lock:
                    self._held.add(pending["kind"])
                self._decide(
                    trigger={"window": win.get("window"),
                             "divergence_pct": post_div,
                             "classification": win.get("classification"),
                             "breach": False, "drift": False},
                    candidates=[],
                    action={"kind": "hold", "of": pending["kind"],
                            "baseline_divergence_pct": base,
                            "post_divergence_pct": post_div,
                            "reason": "no measurable improvement — kept, "
                                      "but this kind will not re-fire"})
            return
        try:
            pending["undo"]()
            err = None
        except Exception as e:  # noqa: BLE001 — audit, never propagate
            err = repr(e)[:200]
        with self._lock:
            self._vetoed.add(pending["kind"])
            self._reverts += 1
            self._cooldown_left = self.cooldown
            for dec in self._ring:
                if dec["action"].get("kind") == pending["kind"] \
                        and not dec.get("reverted"):
                    dec["reverted"] = True
        action = {"kind": "revert", "of": pending["kind"],
                  "baseline_divergence_pct": base,
                  "post_divergence_pct": post_div}
        if err:
            action["error"] = err
        self._decide(trigger={"window": win.get("window"),
                              "divergence_pct": post_div,
                              "classification": win.get("classification"),
                              "breach": True, "drift": False},
                     candidates=[], action=action)

    # ------------------------------------------------------------------
    # remediations (the allowlist bodies)
    # ------------------------------------------------------------------
    def _apply_prefetch(self) -> Tuple[List, Dict, Optional[Callable]]:
        it = self.prefetch
        if it is None:
            return [], {"kind": "none",
                        "reason": "input_bound but no PrefetchIter "
                                  "registered"}, None
        old = int(it.depth)
        new = min(max(old * 2, old + 1), self.max_depth)
        cands = [{"depth": d, "current": d == old}
                 for d in sorted({old, new, self.max_depth})]
        if new == old:
            return cands, {"kind": "none",
                           "reason": f"prefetch depth already at the "
                                     f"{self.max_depth} cap"}, None
        it.set_depth(new)
        return (cands,
                {"kind": "io.prefetch_depth", "site": "io.PrefetchIter",
                 "from": old, "to": new},
                lambda: it.set_depth(old))

    def _apply_retune(self, win: Dict) -> Tuple[List, Dict,
                                                Optional[Callable]]:
        tr = self.trainer
        if tr is None:
            return [], {"kind": "none",
                        "reason": "compute_bound but no trainer "
                                  "registered"}, None
        candidates, entry, source = self._retune_candidates(win)
        prev = dict(tr.autotune_entry) if tr.autotune_entry else {}
        try:
            tr.retune(entry, site="director.recompile")
        except Exception as e:  # noqa: BLE001 — audit, never propagate
            return candidates, {"kind": "none", "reason":
                                f"retune failed: {e!r:.200}"}, None
        return (candidates,
                {"kind": "trainer.retune", "site": "director.recompile",
                 "source": source,
                 "from": (prev.get("config") or {}).get("env") or {},
                 "to": (entry.get("config") or {}).get("env") or {}},
                lambda: tr.retune(prev or {}, site="director.recompile"))

    def _retune_candidates(self, win: Dict) -> Tuple[List, Dict, str]:
        """The rescored candidate table: re-run the trace-only autotune
        search with the window's measured attribution folded into the
        roofline score. A family outside the search space falls back to
        re-staging the banked entry (the cutover is still real — a fresh
        pjit build — and still audited)."""
        measured = self._measured_fractions(win)
        tr = self.trainer
        fam = getattr(tr, "_autotune_key", None)
        try:
            from benchmark import autotune as _bench
        except Exception:  # noqa: BLE001 — tools tree absent in prod
            _bench = None
        if _bench is not None and fam in getattr(_bench, "FAMILY_SPACES",
                                                 {}):
            try:
                res = _bench.search(fam, budget=self.search_budget,
                                    measured=measured)
                table = [{"config": r["config"],
                          "score": round(r["score"], 4),
                          "feasible": r["feasible"]}
                         for r in sorted(res["rows"],
                                         key=lambda r: -r["score"])[:3]]
                entry = {"config": _bench.winner_config(fam, res["winner"]),
                         "score": res["winner_score"],
                         "meta": {"measured": measured}}
                return table, entry, "rescored_search"
            except Exception as e:  # noqa: BLE001 — fall back, audited
                fallback_note = repr(e)[:200]
        else:
            fallback_note = f"family {fam!r} not in the search space"
        entry = dict(tr.autotune_entry or {}) or {"config": {"env": {}}}
        table = [{"config": entry.get("config") or {},
                  "score": entry.get("score"), "source": "banked",
                  "note": fallback_note, "measured": measured}]
        return table, entry, "banked"

    @staticmethod
    def _measured_fractions(win: Dict) -> Optional[Dict[str, float]]:
        cats = win.get("categories") or {}
        wall = float(win.get("wall_ms") or 0.0)
        if wall <= 0:
            return None
        def frac(c):
            return round(max(0.0, min(1.0, float(cats.get(c, 0.0)) / wall)),
                         6)
        return {"compute": frac("compute"), "collective": frac("collective"),
                "input_wait": frac("input_wait"), "host": frac("host")}

    # ------------------------------------------------------------------
    # serve-side breach (slo.burn)
    # ------------------------------------------------------------------
    def _on_burn(self, ev) -> None:
        f = dict(ev.fields or {})
        slo = f.get("slo")
        if f.get("recovered"):
            with self._lock:
                self._serve_acted.discard(slo)
            return
        if self.router is None or ev.severity != "error":
            return
        kind = POLICY["serve_breach"]
        with self._lock:
            if slo in self._serve_acted or kind in self._vetoed:
                return
            # one remediation per SLO per breach episode — re-armed only
            # by the recovery event, so a still-burning alert can't stack
            self._serve_acted.add(slo)
        r = self.router
        to_shed = 8 if r.shed_depth <= 0 else max(2, r.shed_depth // 2)
        to_hedge = self.hedge_ms if r.hedge_ms <= 0 else r.hedge_ms
        prev = r.set_overload_policy(hedge_ms=to_hedge, shed_depth=to_shed)
        self._decide(trigger={"slo": slo, "burn": f.get("burn"),
                              "bad_fraction": f.get("bad_fraction")},
                     candidates=[{"shed_depth": to_shed,
                                  "hedge_ms": to_hedge}],
                     action={"kind": kind, "site": "serve.Router",
                             "from": prev,
                             "to": {"hedge_ms": r.hedge_ms,
                                    "shed_depth": r.shed_depth}})

    # ------------------------------------------------------------------
    # the audit trail
    # ------------------------------------------------------------------
    def _decide(self, trigger: Dict, candidates: List,
                action: Dict) -> None:
        with self._lock:
            self._n += 1
            self._decisions += 1
            dec = {"id": self._n, "ts": round(time.time(), 6),
                   "trigger": trigger, "candidates": candidates,
                   "action": action, "reverted": False,
                   "hysteresis": {"cooldown_windows": self.cooldown,
                                  "cooldown_left": self._cooldown_left,
                                  "streak_needed": self.windows_needed,
                                  "vetoed": sorted(self._vetoed),
                                  "held": sorted(self._held)}}
            self._ring.append(dec)
        from . import events as _events
        from . import metrics as _metrics
        applied = action.get("kind") not in (None, "none")
        _events.emit("director.decision",
                     severity="warning" if applied else "info", **dec)
        _metrics.counter("mxtpu_director_decisions_total",
                         "Flight-director decisions (audited, ring-backed)",
                         action=str(action.get("kind"))).inc()
        if action.get("kind") == "revert":
            _metrics.counter("mxtpu_director_reverts_total",
                             "Flight-director revert-if-worse firings"
                             ).inc()

    def _publish_gauges(self) -> None:
        from . import metrics as _metrics
        with self._lock:
            streak, cd, div = (self._streak, self._cooldown_left,
                               self._last_div)
        _metrics.gauge("mxtpu_director_breach_streak",
                       "Consecutive breached goodput windows").set(streak)
        _metrics.gauge("mxtpu_director_cooldown_left",
                       "Windows the director still holds post-action"
                       ).set(cd)
        if div is not None:
            _metrics.gauge("mxtpu_director_last_divergence_pct",
                           "MFU divergence of the last window the "
                           "director saw").set(div)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            pending = (None if self._pending is None else
                       {k: v for k, v in self._pending.items()
                        if k != "undo"})
            return {
                "enabled": True, "installed": True,
                "config": {"divergence_pct": self.divergence_pct,
                           "windows": self.windows_needed,
                           "cooldown": self.cooldown,
                           "revert_margin_pct": self.revert_margin_pct,
                           "max_depth": self.max_depth,
                           "search_budget": self.search_budget},
                "targets": {"trainer": self.trainer is not None,
                            "prefetch": self.prefetch is not None,
                            "router": self.router is not None},
                "state": {"streak": self._streak,
                          "cooldown_left": self._cooldown_left,
                          "stable_class": self._stable_class,
                          "last_divergence_pct": self._last_div,
                          "pending": pending,
                          "vetoed": sorted(self._vetoed),
                          "held": sorted(self._held),
                          "serve_acted": sorted(
                              s for s in self._serve_acted
                              if s is not None),
                          "decisions_total": self._decisions,
                          "reverts_total": self._reverts},
                "decisions": [dict(d) for d in self._ring],
            }


# ---------------------------------------------------------------------------
# module-level singleton (what telemetry.snapshot()/flight bundles embed)
# ---------------------------------------------------------------------------

def install(trainer=None, prefetch=None, router=None,
            **knobs) -> Optional[FlightDirector]:
    """Start the loop over the given remediation targets. One env read
    and ``None`` while ``MXTPU_DIRECTOR`` is off. Installing again
    replaces the previous director (its ring is dropped — snapshot first
    if the audit trail matters)."""
    global _DIRECTOR
    if not enabled():
        return None
    if _DIRECTOR is not None:
        _DIRECTOR.close()
    _DIRECTOR = FlightDirector(trainer=trainer, prefetch=prefetch,
                               router=router, **knobs).start()
    return _DIRECTOR


def get() -> Optional[FlightDirector]:
    """The installed director singleton (``None`` while uninstalled)."""
    return _DIRECTOR


def uninstall() -> None:
    global _DIRECTOR
    if _DIRECTOR is not None:
        _DIRECTOR.close()
        _DIRECTOR = None


def snapshot() -> Dict[str, Any]:
    """The embeddable audit surface: config + hysteresis state + the
    decision ring (``telemetry.snapshot()["director"]``, flight bundles,
    ``tools/postmortem.py``)."""
    d = _DIRECTOR
    if d is None:
        return {"enabled": enabled(), "installed": False, "decisions": []}
    return d.snapshot()


def reset() -> None:
    """Drop the singleton and the configure() override (test isolation —
    mirrors ``goodput.reset``)."""
    global _ON_OVERRIDE
    uninstall()
    _ON_OVERRIDE = None
