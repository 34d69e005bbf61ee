"""Step guards — anomaly detection with pluggable recovery policies.

Reference counterpart: the reference's only anomaly handling was AMP's
dynamic loss scaler (skip-update-on-overflow, ``amp/loss_scaler.py``);
everything else — a NaN loss from a bad batch, an exploding gradient —
silently poisoned the weights and the run was lost N steps later when
someone looked at the curves. Here the finite-check is a first-class,
jitted runtime feature: :func:`all_finite` fuses ``isfinite(...).all()``
over a whole pytree into one scalar read, and :class:`StepGuard` turns
that scalar into one of three policies:

``warn``               count + ``warnings.warn``, keep the (bad) update
``skip_and_rollback``  restore the last-good snapshot, drop the step
``halt``               raise :class:`NonFiniteError` with diagnostics

``ShardedTrainer(guard=...)`` owns the snapshot mechanics (device-side
copies every ``snapshot_every`` good steps — rollback must not depend on
the crashed step's donated buffers); the guard itself is trainer-agnostic
state so ``amp.LossScaler`` and custom loops share the same policy object.
"""
from __future__ import annotations

import warnings
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..lockcheck import make_lock

__all__ = ["StepGuard", "NonFiniteError", "all_finite", "POLICIES"]

POLICIES = ("warn", "skip_and_rollback", "halt")


class NonFiniteError(MXNetError):
    """A guarded step produced a non-finite loss/grad under ``halt``."""


@jax.jit
def _tree_finite(tree) -> jax.Array:
    leaves = [l for l in jax.tree_util.tree_leaves(tree)
              if jnp.issubdtype(jnp.asarray(l).dtype, jnp.inexact)]
    ok = jnp.array(True)
    for l in leaves:
        ok = jnp.logical_and(ok, jnp.isfinite(l).all())
    return ok

# a NEW (shape, dtype)-structure through the jitted finite check is an
# extra XLA compile — noted on the process-wide ledger so "how many
# jitted graphs does one training step run" is answerable from the
# ledger alone (ShardedTrainer's fused whole-step capture folds this
# check into the step graph; only the unfused path lands entries here)
_SIG_LOCK = make_lock("guards._SIG_LOCK")
_SEEN_SIGS: set = set()


def all_finite(*trees) -> bool:
    """One fused device reduction over every inexact leaf of the given
    pytrees → a host bool (a single scalar transfer, however many arrays).
    Non-float leaves (int labels, step counters) are ignored. This is a
    SEPARATE jitted call — a training loop that wants the check for free
    uses the fused step's in-graph verdict instead."""
    leaves, treedef = jax.tree_util.tree_flatten(trees)
    sig = (str(treedef), tuple(
        (tuple(getattr(l, "shape", ()) or ()), str(getattr(l, "dtype", "?")))
        for l in leaves))
    with _SIG_LOCK:
        new = sig not in _SEEN_SIGS
        if new:
            _SEEN_SIGS.add(sig)
    if new:
        from ..telemetry import compile_log as _clog
        _clog.note("fault.guards.finite", sig)
        # a new signature compiles inside the call: jax's account of it
        # goes to this site (a seen one pays nothing for the bookkeeping)
        with _clog.at("fault.guards.finite"):
            return bool(_tree_finite(trees))
    return bool(_tree_finite(trees))


class StepGuard:
    """Policy + counters for one training loop.

    ``policy``         one of :data:`POLICIES`
    ``grad_norm_limit`` optional float: a finite-but-huge global grad norm
                       (``> limit``) trips the guard exactly like a NaN
    ``snapshot_every`` how often (in good steps) the trainer refreshes its
                       rollback snapshot; 1 = every step (exact rollback),
                       larger values amortize the copies and roll back to
                       the most recent multiple
    ``max_consecutive`` under ``warn``/``skip_and_rollback``: after this
                       many consecutive bad steps the guard escalates to
                       :class:`NonFiniteError` anyway — an input pipeline
                       emitting NaNs forever should not spin silently
    ``on_trip``        optional callback ``(guard, info: dict)`` invoked on
                       every tripped step (metrics/logging seam)
    """

    def __init__(self, policy: str = "warn",
                 grad_norm_limit: Optional[float] = None,
                 snapshot_every: int = 1, max_consecutive: int = 25,
                 on_trip: Optional[Callable[["StepGuard", dict], None]] = None):
        if policy not in POLICIES:
            raise MXNetError(f"unknown guard policy {policy!r}; "
                             f"choose from {POLICIES}")
        if snapshot_every < 1:
            raise MXNetError("snapshot_every must be >= 1")
        self.policy = policy
        self.grad_norm_limit = grad_norm_limit
        self.snapshot_every = snapshot_every
        self.max_consecutive = max_consecutive
        self.on_trip = on_trip
        #: steps that tripped the guard (any policy)
        self.tripped = 0
        #: steps rolled back under skip_and_rollback
        self.skipped = 0
        self._consecutive = 0
        #: (step, reason) history, newest last (bounded)
        self.history: List[tuple] = []

    # -- decision -------------------------------------------------------
    def is_bad(self, loss_finite: bool, grad_norm: Optional[float]) -> Optional[str]:
        """Classify one step; returns a reason string or None if clean."""
        if not loss_finite:
            return "non-finite loss/grad"
        if grad_norm is not None and self.grad_norm_limit is not None:
            if not (grad_norm <= self.grad_norm_limit):  # NaN-safe compare
                return (f"global grad norm {grad_norm:.3e} exceeds limit "
                        f"{self.grad_norm_limit:.3e}")
        return None

    def decide(self, step: int, reason: str, detail: str = "") -> str:
        """Record a tripped step and return the action to take
        (``"keep"`` | ``"rollback"``; ``halt``/escalation raises)."""
        self.tripped += 1
        self._consecutive += 1
        self.history.append((step, reason))
        del self.history[:-50]
        info = {"step": step, "reason": reason, "policy": self.policy,
                "consecutive": self._consecutive, "detail": detail}
        if self.on_trip is not None:
            self.on_trip(self, info)
        # guard verdicts are telemetry: the escalation trail (warn →
        # rollback → halt) must be reconstructable after the run
        from ..telemetry import events as _tele
        from ..telemetry import metrics as _tmetrics
        _tele.emit("guard", severity="warning", step=step, reason=reason,
                   policy=self.policy, consecutive=self._consecutive,
                   detail=detail)
        _tmetrics.counter("mxtpu_guard_tripped_total",
                          "Guard-tripped steps", policy=self.policy).inc()
        msg = (f"[fault.guard] step {step}: {reason} "
               f"(policy={self.policy}, consecutive={self._consecutive})"
               + (f" {detail}" if detail else ""))
        if self.policy == "halt":
            self._flight_dump(info)
            raise NonFiniteError(msg)
        if self._consecutive > self.max_consecutive:
            self._flight_dump(info, escalated=True)
            raise NonFiniteError(
                msg + f"; {self._consecutive} consecutive bad steps exceeds "
                f"max_consecutive={self.max_consecutive}, halting anyway")
        warnings.warn(msg)
        if self.policy == "skip_and_rollback":
            self.skipped += 1
            return "rollback"
        return "keep"

    @staticmethod
    def _flight_dump(info: dict, escalated: bool = False) -> None:
        """A halting guard is about to take the process down — the last
        moment the event rings, trace ring, and ledger still exist."""
        from ..telemetry import flight as _flight
        _flight.dump("guard_halt", escalated=escalated, **info)

    def good_step(self) -> None:
        self._consecutive = 0

    def __repr__(self):
        return (f"StepGuard(policy={self.policy!r}, tripped={self.tripped}, "
                f"skipped={self.skipped})")
