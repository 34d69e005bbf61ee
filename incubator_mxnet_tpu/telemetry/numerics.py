"""In-graph numerics observability — per-site tensor statistics.

Reference counterpart: ``python/mxnet/monitor.py`` — the reference's
Monitor re-executed a *second* capture program per monitored batch and
pulled every intermediate to host. On a jit runtime that design is
doubly wrong: a second executable violates the whole-step-capture
contract (PR 11's one-donated-pjit-step invariant, PyGraph's
capture-once argument), and per-step host callbacks inside the graph
are exactly the MX701/MX708 anti-pattern. This module does it the
TPU-native way:

- statistics are **computed in-graph** — ``summary_stats`` /
  ``hist_counts`` are ordinary traceable reductions whose results ride
  out of the SAME jitted graph as a few extra pinned replicated scalar
  outputs (the step stays ONE executable; the compile ledger and
  MX704/MX708 stay clean with stats on, tested);
- the host **decimates**: stat outputs are device arrays the host only
  syncs every ``MXTPU_NUMERICS_EVERY`` steps (default 16), folded into
  the step's existing single host sync (the guard's loss/grad-norm
  read) — never an extra per-step device round trip;
- recorded samples land in ``numerics.step`` events, ``mxtpu_numerics_*``
  gauges, and a bounded per-site history ring — the raw material of the
  **drift watchdog**: monotonic rms growth or finite-fraction decay
  across the ring emits damped ``numerics.drift`` warnings *before* the
  run ever produces a non-finite value, and (``MXTPU_NUMERICS_DRIFT=
  rollback``) can arm the existing ``fault.StepGuard`` escalation;
- ``hist`` mode additionally accumulates in-graph log2-magnitude
  histograms per site, exported via :func:`calibration_table` as
  ``quantization.Observer`` calibration tables — the int8 pipeline's
  range data (ROADMAP item 4) collected from live traffic for free.

Sites are named strings: the trainer publishes ``param:<name>`` /
``grad:<name>`` per parameter, models tag activations explicitly with
:func:`tap` (``act:<name>``), and ``serve.CompiledModel`` publishes
``serve.out:<i>`` per output. ``MXTPU_NUMERICS_SITES`` is an fnmatch
allowlist over those names (empty = all), so a 300-parameter model can
watch just ``grad:*attn*``.

Everything is **off by default** (``MXTPU_NUMERICS`` unset): the traced
graphs are those of a build that never imported this module (the step
keeps its arity and no stat output is traced).

Usage::

    MXTPU_NUMERICS=summary MXTPU_NUMERICS_EVERY=8 python train.py

    # inside a model: tag an activation (identity; collected at trace time)
    from incubator_mxnet_tpu.telemetry import numerics
    h = numerics.tap("encoder_out", h)

    # after a hist-mode run: export calibration for int8 quantization
    from incubator_mxnet_tpu import quantization
    obs = quantization.Observer(numerics.calibration_table())
    obs.ranges()          # {"act:encoder_out": (-3.1, 3.1), ...}
"""
from __future__ import annotations

import fnmatch
import threading
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..lockcheck import make_lock

__all__ = ["NumericsConfig", "config", "configure", "tap", "collecting",
           "summary_stats", "hist_counts", "graph_stats", "record",
           "rings", "ring", "drift_state", "calibration_table",
           "snapshot", "reset",
           "STAT_FIELDS", "HIST_LO_EXP", "MODES"]

MODES = ("summary", "hist")

#: layout of the (6,) summary-stat vector every site publishes
STAT_FIELDS = ("min", "max", "mean", "rms", "zero_fraction",
               "finite_fraction")

#: histogram bucket i counts |x| in [2^(LO+i), 2^(LO+i+1)); underflows
#: clamp into bucket 0, overflows into the last — fixed edges, so the
#: in-graph computation is trace-safe (no data-dependent shapes)
HIST_LO_EXP = -24

_LOCK = make_lock("numerics._LOCK")
_CONFIG_OVERRIDE: Optional["NumericsConfig"] = None
#: "<scope>/<site>" -> deque of {"step": int, "min": ..., ...} host
#: records. Keys carry the recording scope ("trainer.step",
#: "serve.compiled") so a trainer and a server sharing tap names can
#: never interleave into one drift window — the monotonicity evidence
#: stays per recording stream. (Two trainers with IDENTICAL explicit
#: gluon prefixes still share keys; auto-incremented prefixes make
#: parameter names process-unique, so that needs deliberate aliasing.)
_RINGS: Dict[str, deque] = {}
#: per-key drift damping: key -> {"rms_level": float|None,
#: "ff_level": float|None}
_DRIFT: Dict[str, Dict[str, Any]] = {}
#: hist-mode calibration accumulation: key -> {"counts": [floats],
#: "lo_exp": int, "min": float, "max": float, "samples": int}
_CALIB: Dict[str, Dict[str, Any]] = {}
#: the config most recently used to record — what snapshot()/bundles
#: report, so a ctor-configured trainer's postmortem header reflects
#: the build that actually recorded, not the (possibly unset) env
_LAST_CFG: List[Optional["NumericsConfig"]] = [None]


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class NumericsConfig:
    """One resolved numerics-telemetry configuration. Builders
    (``ShardedTrainer._build_step``, ``serve.CompiledModel``) resolve it
    ONCE at build time — flipping the env mid-run does not re-trace a
    compiled step."""

    #: None = off; "summary" = the (6,) stat vector per site; "hist" =
    #: summary + log2-magnitude histogram per site
    mode: Optional[str] = None
    #: host-side decimation: sync + record stats every N steps/requests
    every: int = 16
    #: fnmatch allowlist over site names; empty = every site
    sites: Tuple[str, ...] = ()
    #: log2-magnitude histogram buckets (hist mode)
    bins: int = 40
    #: per-site history-ring capacity
    ring: int = 128
    #: drift-watchdog action: "warn" emits events only; "rollback" also
    #: escalates a sustained drift to the trainer's StepGuard (its
    #: policy decides warn/skip_and_rollback/halt)
    drift_action: str = "warn"
    #: recorded samples the drift verdict needs (monotonic across all)
    drift_window: int = 4
    #: rms growth factor across the window that counts as drift
    drift_ratio: float = 4.0

    @property
    def enabled(self) -> bool:
        return self.mode in MODES

    @property
    def hist(self) -> bool:
        return self.mode == "hist"

    def wants(self, site: str) -> bool:
        """Allowlist check (empty allowlist admits every site)."""
        if not self.sites:
            return True
        return any(fnmatch.fnmatchcase(site, pat) for pat in self.sites)

    @classmethod
    def from_env(cls) -> "NumericsConfig":
        from ..util import getenv
        raw = (getenv("MXTPU_NUMERICS") or "").strip().lower()
        mode = raw if raw in MODES else None

        def _int(name: str, default: int) -> int:
            try:
                return max(1, int(getenv(name) or default))
            except (TypeError, ValueError):
                return default

        sites = tuple(p.strip() for p in
                      (getenv("MXTPU_NUMERICS_SITES") or "").split(",")
                      if p.strip())
        action = (getenv("MXTPU_NUMERICS_DRIFT") or "warn").strip().lower()
        if action not in ("warn", "rollback"):
            action = "warn"
        return cls(mode=mode,
                   every=_int("MXTPU_NUMERICS_EVERY", 16),
                   sites=sites,
                   bins=_int("MXTPU_NUMERICS_BINS", 40),
                   ring=_int("MXTPU_NUMERICS_RING", 128),
                   drift_action=action)


def config() -> NumericsConfig:
    """The active configuration: a :func:`configure` override, else the
    environment (parsed fresh — builders cache the result themselves)."""
    return _CONFIG_OVERRIDE if _CONFIG_OVERRIDE is not None \
        else NumericsConfig.from_env()


def configure(cfg: Optional[NumericsConfig]) -> None:
    """Programmatic override of the env config (tests, the Monitor
    bridge). ``None`` restores env resolution. Only builds that happen
    AFTER the call see it."""
    global _CONFIG_OVERRIDE
    _CONFIG_OVERRIDE = cfg


# ---------------------------------------------------------------------------
# in-graph statistics (traceable; these run INSIDE the jitted step)
# ---------------------------------------------------------------------------

def summary_stats(x):
    """The (6,) f32 stat vector of one tensor — ``STAT_FIELDS`` order —
    as ordinary XLA reductions (traceable; NaN/inf-safe: min/max/mean/
    rms reduce over the finite entries only, so a poisoned tensor still
    reports the magnitude story of its healthy part next to its
    ``finite_fraction``)."""
    import jax.numpy as jnp
    v = getattr(x, "_data", x)
    f = jnp.ravel(v).astype(jnp.float32)
    n = max(int(f.size), 1)
    finite = jnp.isfinite(f)
    nfin = jnp.sum(finite)
    denom = jnp.maximum(nfin, 1).astype(jnp.float32)
    safe = jnp.where(finite, f, 0.0)
    mean = jnp.sum(safe) / denom
    rms = jnp.sqrt(jnp.sum(safe * safe) / denom)
    mn = jnp.min(jnp.where(finite, f, jnp.inf))
    mx = jnp.max(jnp.where(finite, f, -jnp.inf))
    zero = jnp.sum(jnp.logical_and(finite, f == 0.0)).astype(jnp.float32)
    return jnp.stack([mn, mx, mean, rms, zero / n,
                      nfin.astype(jnp.float32) / n])


def hist_counts(x, bins: int):
    """Log2-magnitude histogram of one tensor: bucket ``i`` counts the
    finite non-zero entries with ``|x|`` in ``[2^(LO+i), 2^(LO+i+1))``
    (``LO`` = :data:`HIST_LO_EXP`; under/overflows clamp into the edge
    buckets). Fixed edges make it traceable AND mergeable across steps
    — the calibration accumulator just adds counts."""
    import jax.numpy as jnp
    v = getattr(x, "_data", x)
    f = jnp.ravel(v).astype(jnp.float32)
    mag = jnp.abs(f)
    valid = jnp.logical_and(jnp.isfinite(mag), mag > 0.0)
    # log2 of 0/inf would poison the index; valid entries carry weight 1
    exp = jnp.floor(jnp.log2(jnp.where(valid, mag, 1.0)))
    idx = jnp.clip(exp - HIST_LO_EXP, 0, bins - 1).astype(jnp.int32)
    return jnp.bincount(idx, weights=valid.astype(jnp.float32),
                        length=bins)


def graph_stats(x, cfg: NumericsConfig) -> Dict[str, Any]:
    """One site's full in-graph stat pytree: ``{"s": (6,)}`` plus
    ``{"h": (bins,)}`` in hist mode. This dict IS the extra output the
    jitted graph returns for the site (replicated scalars — donation
    and the sharding contract untouched)."""
    out = {"s": summary_stats(x)}
    if cfg.hist:
        out["h"] = hist_counts(x, cfg.bins)
    return out


# ---------------------------------------------------------------------------
# trace-time tap collection
# ---------------------------------------------------------------------------

class _TapCollector:
    """Collects ``tap()``-tagged activation stats during ONE trace of a
    jitted function. The collected stat tracers must be returned from
    the traced function (the trainer threads them through its aux
    outputs) — they are tracers of the active trace, not values."""

    def __init__(self, cfg: NumericsConfig):
        self.cfg = cfg
        self.names: List[str] = []
        self.values: List[Dict[str, Any]] = []

    def add(self, site: str, x) -> None:
        if not self.cfg.wants(site):
            return
        if site in self.names:            # re-tapped name: newest wins
            self.values[self.names.index(site)] = graph_stats(x, self.cfg)
            return
        self.names.append(site)
        self.values.append(graph_stats(x, self.cfg))


class _TapState(threading.local):
    def __init__(self):
        self.stack: List[_TapCollector] = []


_TAPS = _TapState()


class collecting:
    """Scope a trace with tap collection::

        with numerics.collecting(cfg) as col:
            out = traced_forward(x)      # taps inside record into col
        # col.names / col.values are the extra outputs to return
    """

    def __init__(self, cfg: NumericsConfig):
        self._cfg = cfg
        self.collector: Optional[_TapCollector] = None

    def __enter__(self) -> _TapCollector:
        self.collector = _TapCollector(self._cfg)
        _TAPS.stack.append(self.collector)
        return self.collector

    def __exit__(self, *exc):
        _TAPS.stack.pop()


def tap(name: str, x):
    """Tag an activation for numerics telemetry — an identity op. When
    a collection scope is active (the instrumented trainer/serve build
    is tracing) the tensor's in-graph stats are recorded under site
    ``act:<name>``; otherwise (numerics off, eager execution, an
    uninstrumented trace) it returns ``x`` untouched for free."""
    if _TAPS.stack:
        _TAPS.stack[-1].add(f"act:{name}", x)
    return x


# ---------------------------------------------------------------------------
# host-side recording, rings, drift watchdog
# ---------------------------------------------------------------------------

def _as_float(v) -> float:
    try:
        return float(v)
    except (TypeError, ValueError):
        return float("nan")


def record(scope: str, step: Optional[int],
           stats: Dict[str, Dict[str, Any]],
           cfg: NumericsConfig) -> List[Dict[str, Any]]:
    """Fold one synced batch of per-site stat arrays into the telemetry
    surfaces: the per-site history ring, ``mxtpu_numerics_*`` gauges,
    one ``numerics.step`` event, the hist-mode calibration accumulator
    — then run the drift watchdog. Returns the (possibly empty) list of
    drift verdicts so the caller (the trainer) can escalate to its
    StepGuard under ``drift_action='rollback'``.

    ``stats``: ``{site: {"s": host (6,) array[, "h": (bins,) array]}}``
    — the device_get of the graph's stat outputs. History/drift/
    calibration state is keyed ``"<scope>/<site>"`` so different
    recording streams never interleave one drift window."""
    from . import events as _events
    from . import metrics as _metrics
    verdicts: List[Dict[str, Any]] = []
    if not stats:
        return verdicts
    with _LOCK:
        _LAST_CFG[0] = cfg
    rms_top = ("", float("-inf"))
    ff_bot = ("", float("inf"))
    for site in sorted(stats):
        key = f"{scope}/{site}"
        vec = stats[site].get("s")
        rec: Dict[str, Any] = {"step": step}
        for i, fname in enumerate(STAT_FIELDS):
            rec[fname] = _as_float(vec[i]) if vec is not None else None
        if rec["rms"] is not None and rec["rms"] > rms_top[1]:
            rms_top = (site, rec["rms"])
        if rec["finite_fraction"] is not None \
                and rec["finite_fraction"] < ff_bot[1]:
            ff_bot = (site, rec["finite_fraction"])
        for fname in ("rms", "finite_fraction", "zero_fraction",
                      "min", "max", "mean"):
            val = rec[fname]
            if val is not None and val == val \
                    and abs(val) != float("inf"):
                _metrics.gauge(f"mxtpu_numerics_{fname}",
                               f"Per-site tensor {fname} "
                               "(telemetry.numerics)",
                               site=site, scope=scope).set(val)
        with _LOCK:
            r = _RINGS.get(key)
            if r is None:
                r = _RINGS[key] = deque(maxlen=cfg.ring)
            r.append(rec)
            if cfg.hist and stats[site].get("h") is not None:
                _accumulate_calibration(key, stats[site]["h"], rec, cfg)
            verdict = _drift_verdict(key, list(r), cfg)
        if verdict is not None:
            verdict.update(scope=scope, step=step)
            verdicts.append(verdict)
            _events.emit("numerics.drift", severity="warning", **verdict)
            _metrics.counter("mxtpu_numerics_drift_total",
                             "Drift-watchdog warnings", site=site).inc()
    _events.emit("numerics.step", scope=scope, sites=len(stats),
                 rms_max_site=rms_top[0], rms_max=_finite_or_none(rms_top[1]),
                 finite_min_site=ff_bot[0],
                 finite_min=_finite_or_none(ff_bot[1]))
    _metrics.counter("mxtpu_numerics_records_total",
                     "Decimated numerics samples recorded",
                     scope=scope).inc()
    return verdicts


def _finite_or_none(v: float) -> Optional[float]:
    return v if v == v and abs(v) != float("inf") else None


def _drift_verdict(site: str, ring: List[Dict],
                   cfg: NumericsConfig) -> Optional[Dict[str, Any]]:
    """Drift decision over the site's recorded history (caller holds
    the lock; the newest ``drift_window`` entries are the evidence).
    Two signatures, both *pre-non-finite*:

    - **rms growth**: monotonically non-decreasing rms across a full
      window ending >= ``drift_ratio`` x the window start AND at a new
      ring-wide high — the grad/activation blow-up trajectory hundreds
      of steps before overflow. The new-high requirement kills the
      convergence false positive (a grad rms that decayed to ~0 at a
      loss-minimum crossing then ticked back up shows a huge *ratio*
      at a tiny *scale*; a real blow-up always makes new highs);
    - **finite-fraction decay**: monotonically non-increasing
      finite_fraction that lost ground across the window — values are
      already dying at the edges.

    Damped like the memory-leak watchdog: after flagging, the level
    must move another ratio factor (or the site must recover) before
    the same site re-flags."""
    window = ring[-cfg.drift_window:]
    if len(window) < cfg.drift_window:
        return None
    st = _DRIFT.setdefault(site, {"rms_level": None, "ff_level": None})
    rms = [w["rms"] for w in window]
    ff = [w["finite_fraction"] for w in window]
    if all(v is not None and v == v for v in rms):
        if st["rms_level"] is not None and rms[-1] < st["rms_level"]:
            st["rms_level"] = None              # recovered: re-arm
        base = rms[0]
        hist = [w["rms"] for w in ring[:-1]
                if w["rms"] is not None and w["rms"] == w["rms"]
                and abs(w["rms"]) != float("inf")]
        new_high = not hist or rms[-1] >= max(hist)
        # a zero-rms window start (a fresh bias) has no growth RATIO —
        # skip rather than divide by a floor and flag healthy warmup
        if base > 0.0 and new_high \
                and all(b >= a for a, b in zip(rms, rms[1:])) \
                and rms[-1] >= cfg.drift_ratio * base \
                and (st["rms_level"] is None
                     or rms[-1] >= cfg.drift_ratio * st["rms_level"]):
            st["rms_level"] = rms[-1]
            return {"site": site, "reason": "rms_growth",
                    "rms_first": rms[0], "rms_last": rms[-1],
                    "ratio": rms[-1] / base,
                    "window_steps": [w["step"] for w in window]}
    if all(v is not None and v == v for v in ff):
        if st["ff_level"] is not None and ff[-1] > st["ff_level"]:
            st["ff_level"] = None               # recovered: re-arm
        if all(b <= a for a, b in zip(ff, ff[1:])) and ff[-1] < ff[0] \
                and (st["ff_level"] is None or ff[-1] < st["ff_level"]):
            st["ff_level"] = ff[-1]
            return {"site": site, "reason": "finite_fraction_decay",
                    "finite_first": ff[0], "finite_last": ff[-1],
                    "window_steps": [w["step"] for w in window]}
    return None


def _accumulate_calibration(site: str, counts, rec: Dict,
                            cfg: NumericsConfig) -> None:
    """Merge one step's histogram into the run-long calibration table
    (caller holds the lock). Fixed bucket edges make the merge a plain
    per-bucket add."""
    c = _CALIB.get(site)
    host = [float(v) for v in counts]
    if c is None or len(c["counts"]) != len(host):
        c = _CALIB[site] = {"counts": [0.0] * len(host),
                            "lo_exp": HIST_LO_EXP,
                            "min": float("inf"), "max": float("-inf"),
                            "samples": 0}
    c["counts"] = [a + b for a, b in zip(c["counts"], host)]
    c["samples"] += 1
    for key, fname, pick in (("min", "min", min), ("max", "max", max)):
        v = rec.get(fname)
        if v is not None and v == v and abs(v) != float("inf"):
            c[key] = pick(c[key], v)


# ---------------------------------------------------------------------------
# read surfaces
# ---------------------------------------------------------------------------

def rings() -> Dict[str, List[Dict]]:
    """Every recorded history, oldest first, keyed
    ``"<scope>/<site>"``."""
    with _LOCK:
        return {key: list(r) for key, r in _RINGS.items()}


def ring(site: str) -> List[Dict]:
    """One history: by full ``"<scope>/<site>"`` key, or by bare site
    name (entries merged across scopes, step order) — the form the
    Monitor bridge and tests use."""
    with _LOCK:
        r = _RINGS.get(site)
        if r is not None:
            return list(r)
        out: List[Dict] = []
        for key, rr in _RINGS.items():
            if key.endswith("/" + site):
                out.extend(rr)
    out.sort(key=lambda e: (e.get("step") is None, e.get("step") or 0))
    return out


def drift_state() -> Dict[str, Dict]:
    with _LOCK:
        return {s: dict(v) for s, v in _DRIFT.items()}


def calibration_table() -> Dict[str, Dict]:
    """The accumulated hist-mode calibration data, strict-JSON shaped:
    ``{"<scope>/<site>": {"counts": [...], "lo_exp": int, "bins": int,
    "min": float, "max": float, "samples": int}}`` — the exact table
    ``quantization.Observer`` consumes (and round-trips)."""
    with _LOCK:
        out = {}
        for site, c in _CALIB.items():
            out[site] = {"counts": list(c["counts"]),
                         "lo_exp": int(c["lo_exp"]),
                         "bins": len(c["counts"]),
                         "min": _finite_or_none(c["min"]) or 0.0,
                         "max": _finite_or_none(c["max"]) or 0.0,
                         "samples": int(c["samples"])}
        return out


def snapshot(history: int = 16) -> Dict:
    """Everything numerics knows — the ``numerics`` section of
    ``telemetry.snapshot()`` and flight bundles: active config, the
    newest ``history`` ring entries per site (the drift trajectory a
    postmortem renders), damping state, and the calibration rollup."""
    with _LOCK:
        # prefer the config that actually RECORDED (a ctor-configured
        # trainer with the env unset must not render "mode=None" above
        # its own drift rows); fall back to env/override resolution
        cfg = _LAST_CFG[0]
        sites = {key: list(r)[-history:] for key, r in _RINGS.items()}
        drift = {s: dict(v) for s, v in _DRIFT.items()}
        calib = {s: {"samples": c["samples"],
                     "total": sum(c["counts"])}
                 for s, c in _CALIB.items()}
    if cfg is None:
        cfg = config()
    return {"config": {"mode": cfg.mode, "every": cfg.every,
                       "sites": list(cfg.sites), "bins": cfg.bins,
                       "drift_action": cfg.drift_action},
            "sites": sites,
            "drift": drift,
            "calibration": calib}


def reset() -> None:
    """Clear rings, drift damping, and calibration accumulation
    (tests; ``telemetry.reset()`` calls this)."""
    global _CONFIG_OVERRIDE
    with _LOCK:
        _RINGS.clear()
        _DRIFT.clear()
        _CALIB.clear()
        _LAST_CFG[0] = None
    _CONFIG_OVERRIDE = None
