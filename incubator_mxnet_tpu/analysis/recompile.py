"""Recompilation-hazard detector (pass 3, runtime half).

Reference counterpart: ``CachedOp`` keeps ONE captured graph per
(static-shape, train-mode) bucket and MXNet profiled cache misses through
the engine; here every distinct jit signature is a fresh XLA compile —
seconds of latency and growing device memory, invisible without tooling
("Operator Fusion in XLA", PAPERS.md §recompilation). The hybridize cache
(``gluon/block.py _call_cached_op``) calls :func:`note_compile` on every
cache miss; past :data:`RECOMPILE_WARN_THRESHOLD` distinct signatures a
``RecompileWarning`` fires once per block, and :func:`cache_report` turns
the live cache state of a block tree into MX201 diagnostics.

Typical causes the warning points at: unhashable/varying static leaves in
the call args (Python floats that change per step, freshly-built lists),
shape-churning inputs (unbucketed variable-length batches), or toggling
``autograd.record`` patterns that alternate train/eval signatures.
"""
from __future__ import annotations

import os
import warnings
from typing import List

from .diagnostics import Diagnostic, Report

__all__ = ["RecompileWarning", "note_compile", "cache_report",
           "RECOMPILE_WARN_THRESHOLD"]

#: distinct jit signatures per block before warning (env override)
RECOMPILE_WARN_THRESHOLD = int(os.environ.get("MXTPU_RECOMPILE_WARN", "8"))


class RecompileWarning(UserWarning):
    """A hybridized block has compiled many distinct signatures."""


def note_compile(block, signature) -> bool:
    """Record one compile signature on ``block`` — the (static cache key,
    input shapes/dtypes) pair, since jax.jit re-traces per aval inside one
    cache entry. Dedupes; warns once when the distinct count crosses the
    threshold. Called by the CachedOp path on every compiled call, so the
    steady-state cost is one set lookup (``signature`` must be hashable).
    True when the signature is new: the call about to run compiles."""
    seen = block.__dict__.setdefault("_compile_sigs", set())
    if signature in seen:
        return False
    seen.add(signature)
    block.__dict__.setdefault("_compile_log", []).append(signature)
    n = len(seen)
    # process-wide recompile ledger (mx.telemetry.compile_log): the
    # hybridize cache reports next to CompiledModel and ShardedTrainer,
    # so one table answers "what compiled, when, and was it expected" —
    # mark_warmed("gluon.hybridize") after a warmup loop makes later
    # signatures count as unexpected
    from ..telemetry import compile_log as _compile_log
    _compile_log.note("gluon.hybridize",
                      (type(block).__name__, signature))
    if n == RECOMPILE_WARN_THRESHOLD and \
            not block.__dict__.get("_recompile_warned"):
        block._recompile_warned = True
        warnings.warn(
            f"[MX201] {type(block).__name__}({block.name}): {n} distinct "
            f"jit compile signatures and counting — every new static-arg "
            "value or input shape recompiles. Stabilize static kwargs and "
            "bucket input shapes (mx.analysis.recompile.cache_report(block) "
            "shows the signatures).", RecompileWarning, stacklevel=3)
    return True


def _blocks(block):
    yield block
    for child in getattr(block, "_children", {}).values():
        yield from _blocks(child)


def cache_report(block, threshold: int = None) -> Report:
    """MX201 diagnostics for every block in the tree whose live jit cache
    holds more than ``threshold`` distinct signatures (default: the warn
    threshold). Severity is ``warning``: many signatures are a perf hazard,
    not a correctness error."""
    limit = RECOMPILE_WARN_THRESHOLD if threshold is None else threshold
    report = Report()
    for b in _blocks(block):
        # note_compile() runs on every compiled call, so _compile_log is
        # authoritative; a block without one has compiled nothing
        log = b.__dict__.get("_compile_log") or []
        # >= so the block that just tripped the note_compile warning (which
        # points users here) is visible at exactly the threshold
        if len(log) < limit:
            continue
        sigs: List[str] = [repr(k)[:120] for k in log]
        report.add(Diagnostic(
            "MX201",
            f"{len(log)} distinct jit compile signatures (threshold "
            f"{limit}); recent: {sigs[-3:]}",
            node=getattr(b, "name", type(b).__name__),
            op=type(b).__name__, pass_name="recompile",
            severity="warning"))
    return report
