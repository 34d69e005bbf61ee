"""Observability-hygiene linter (the MX6xx family).

Companion to :mod:`.fault_lint` (protects the run from the machine) and
:mod:`.serve_lint` (protects the request path from the jit cache): this
pass protects the *operator* from flying blind. Hand-rolled
``time.time()`` deltas and ad-hoc counters inside a training loop or a
serving entry point are observability that exists in exactly one
``print`` statement — invisible to the unified event bus, the Prometheus
scrape, and ``telemetry.snapshot()``. One pure-AST check, warning
severity (hygiene, not correctness; ``mxlint --strict`` gates):

- **MX601** — a wall-clock sampling call (``time.time()`` /
  ``time.perf_counter()`` / ``time.monotonic()``) inside a training loop
  (a ``for``/``while`` whose body calls ``.step(...)``) or inside a
  serving entry point (a function named ``predict``/``serve``/``infer``/
  ``handle``/``handle_request``), in a file that shows NO telemetry
  evidence at all. Route the measurement through ``mx.telemetry``
  (``emit`` / ``Histogram`` / ``step_scope``) or ``mx.profiler`` spans
  instead — then it lands in every sink for free.
- **MX602** — an ``emit(...)`` bus call inside a *request-path* function
  (``submit``/``call``/``call_detailed``/``predict``/``_flush``/
  ``handle*``/...) with no correlation whatsoever: the call neither
  passes ``request_id=``/``step=`` nor sits lexically inside a
  correlation ``with`` block (``request_scope``/``step_scope``/
  ``trace.span``/``trace.use``). Such an event lands on the timeline as
  a free-floating fact that can never be stitched into any request or
  step story — the uncorrelated telemetry this PR's tracing layer
  exists to eliminate.
- **MX604** — a **stray device sync inside a step loop**: a
  ``.block_until_ready()`` / ``.item()`` call or ``float(...)``
  coercion on a name bound to a ``.step(...)`` result, executed every
  iteration. The guarded trainer already syncs loss/grad-norm in ONE
  device read per step (the fused step's single-sync cadence); a
  per-iteration extra sync re-serializes the host with the device: the
  host cannot run ahead and enqueue the next step while this one
  computes. Reads decimated behind an ``if step % N`` cadence (or
  performed once after the loop) pass; ``.asnumpy()`` is exempt as the
  sanctioned read (a host copy is a sync on every backend).
- **MX603** — tensor statistics routed through a **host callback inside
  a jitted function**: a ``jax.debug.callback`` / ``jax.debug.print`` /
  ``jax.pure_callback`` / ``io_callback`` call whose arguments carry a
  reduction (``.mean()``, ``jnp.min``, ``linalg.norm``, ...) lexically
  inside a function that is jit-compiled (decorated with
  ``jit``/``jax.jit``/``pjit``, or passed by name to ``jax.jit(...)``
  in the same file). This is the anti-pattern the in-graph numerics
  design forbids: a per-step host callback breaks whole-step capture
  (MX701/MX708 catch it at the HLO level; this is the AST-level twin
  that fires before anything is traced). Return the stats as extra
  pinned outputs and decimate host-side — ``telemetry.numerics`` is
  exactly that machinery.

Heuristics are tuned for zero noise elsewhere: for MX601, any use of
``telemetry``, ``profiler`` scopes, ``emit``, a metrics instrument, or
``ServeMetrics`` anywhere in the file counts as evidence and silences
the pass — code already on the spine (including the serve/bench
internals that IMPLEMENT the spine) lints clean. MX602 is the opposite
polarity (``emit`` IS its subject), so it runs regardless of file-level
evidence; lifecycle emits outside request-path functions (health
transitions, drain, load outcomes) are legitimately uncorrelated and
out of its vocabulary by construction.
"""
from __future__ import annotations

import ast
from typing import List, Optional, Set

from .diagnostics import Diagnostic, Report, walk_lint

__all__ = ["lint_source", "lint_file", "lint_paths"]

#: function/method names treated as request-serving entry points (shared
#: vocabulary with serve_lint MX502)
_ENTRY_NAMES = {"predict", "serve", "infer", "inference", "handle",
                "handle_request"}

#: wall-clock sampling callables (attribute leaf or bare name)
_CLOCK_NAMES = {"time", "perf_counter", "monotonic", "process_time"}

#: any of these identifiers anywhere in the file = the code already
#: publishes into the telemetry spine — MX601 stays quiet
_TELEMETRY_EVIDENCE = {"telemetry", "emit", "step_scope", "request_scope",
                       "Histogram", "Counter", "Gauge", "profiler",
                       "Scope", "Task", "Marker", "ServeMetrics",
                       "record_request", "record_batch", "snapshot"}


def _is_clock_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Attribute):
        # time.time() / time.perf_counter(): receiver must be `time`-ish
        # so .time() methods on arbitrary objects don't fire
        recv = f.value
        return f.attr in _CLOCK_NAMES and isinstance(recv, ast.Name) \
            and recv.id == "time"
    if isinstance(f, ast.Name):
        return f.id in {"perf_counter", "monotonic"}
    return False


def _has_telemetry_evidence(tree: ast.Module) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.Name) and node.id in _TELEMETRY_EVIDENCE:
            return True
        if isinstance(node, ast.Attribute) \
                and node.attr in _TELEMETRY_EVIDENCE:
            return True
    return False


def _step_loops(tree: ast.Module) -> List[ast.AST]:
    loops = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.For, ast.While, ast.AsyncFor)):
            continue
        for inner in ast.walk(node):
            if isinstance(inner, ast.Call) \
                    and isinstance(inner.func, ast.Attribute) \
                    and inner.func.attr == "step":
                loops.append(node)
                break
    return loops


def _entry_functions(tree: ast.Module) -> List[ast.AST]:
    return [n for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            and n.name in _ENTRY_NAMES]


# -- MX602: uncorrelated telemetry on the request path -----------------------

#: functions that handle one request/step — the paths where an
#: uncorrelated event is a stitching failure, not a lifecycle fact
_REQUEST_PATH_NAMES = {"submit", "call", "call_detailed", "predict",
                       "infer", "inference", "serve", "_flush",
                       "_predict", "handle", "handle_request"}
_REQUEST_PATH_PREFIXES = ("handle_", "_handle")

#: with-context callables that establish correlation for everything
#: lexically inside them
_CORRELATION_CTX = {"request_scope", "step_scope", "span", "use",
                    "watch"}

#: emit kwargs that correlate the single event explicitly
_CORRELATION_KWARGS = {"request_id", "step"}


def _is_request_path(name: str) -> bool:
    return name in _REQUEST_PATH_NAMES \
        or name.startswith(_REQUEST_PATH_PREFIXES)


def _is_emit_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    leaf = f.attr if isinstance(f, ast.Attribute) else (
        f.id if isinstance(f, ast.Name) else None)
    return leaf == "emit"


def _correlation_withs(func: ast.AST) -> List[ast.With]:
    out = []
    for node in ast.walk(func):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        for item in node.items:
            expr = item.context_expr
            if not isinstance(expr, ast.Call):
                continue
            f = expr.func
            leaf = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else None)
            if leaf in _CORRELATION_CTX:
                out.append(node)
                break
    return out


def _inside(node: ast.AST, blocks: List[ast.With]) -> bool:
    """Lexical containment by line span (ast has no parent links; the
    end_lineno span is exact for our purpose)."""
    line = getattr(node, "lineno", None)
    if line is None:
        return False
    for blk in blocks:
        if blk.lineno <= line <= (getattr(blk, "end_lineno", blk.lineno)):
            return True
    return False


def _lint_uncorrelated(tree: ast.Module, filename: str,
                       report: Report) -> None:
    """MX602 over every request-path function in the module."""
    funcs = [n for n in ast.walk(tree)
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
             and _is_request_path(n.name)]
    # drop request-path functions nested inside another collected one:
    # ast.walk(outer) already reaches the inner's emits, so keeping both
    # would report the same call twice under two op= names
    spans = [(f.lineno, getattr(f, "end_lineno", f.lineno)) for f in funcs]
    funcs = [f for i, f in enumerate(funcs)
             if not any(j != i and lo < f.lineno <= hi
                        for j, (lo, hi) in enumerate(spans))]
    for func in funcs:
        blocks = _correlation_withs(func)
        for node in ast.walk(func):
            if not _is_emit_call(node):
                continue
            kwargs = {kw.arg for kw in node.keywords if kw.arg}
            if kwargs & _CORRELATION_KWARGS:
                continue
            if _inside(node, blocks):
                continue
            kind = ""
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                kind = f" ({node.args[0].value!r})"
            report.add(Diagnostic(
                "MX602",
                f"bus event{kind} emitted on the request path "
                f"({func.name}()) outside any correlation scope — pass "
                "request_id=/step=, or wrap the path in "
                "telemetry.request_scope()/step_scope()/trace.span() so "
                "the event stitches into a request or step story",
                node=f"{filename}:{getattr(node, 'lineno', 0)}",
                op=func.name, pass_name="telemetry_lint",
                severity="warning"))


# -- MX604: stray device syncs inside step loops -----------------------------

#: method leaves that force a host<->device sync when called on a device
#: array. ``.asnumpy()`` is deliberately NOT here: it is the sanctioned
#: read (a host copy is a sync on every backend), and the sanctioned
#: loop shape syncs it once after the loop or on a decimated cadence.
_SYNC_METHOD_LEAVES = {"block_until_ready", "item"}


def _step_result_names(loop: ast.AST) -> Set[str]:
    """Names bound (anywhere in the loop body) to a ``.step(...)`` call
    result — the device arrays whose every-iteration sync is the smell."""
    out: Set[str] = set()
    for node in ast.walk(loop):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call) \
                and isinstance(node.value.func, ast.Attribute) \
                and node.value.func.attr == "step":
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    out.add(tgt.id)
    return out


def _decimated_ifs(loop: ast.AST) -> List[ast.AST]:
    """``if``-blocks whose test contains a modulo — the decimated-cadence
    idiom (``if step % N == 0:``) that keeps a sync OFF the every-step
    path; syncs inside one respect the single-sync cadence and pass."""
    out: List[ast.AST] = []
    for node in ast.walk(loop):
        if isinstance(node, ast.If):
            for t in ast.walk(node.test):
                if isinstance(t, ast.BinOp) and isinstance(t.op, ast.Mod):
                    out.append(node)
                    break
    return out


def _lint_stray_syncs(tree: ast.Module, filename: str,
                      report: Report) -> None:
    """MX604 over every step loop: a ``.block_until_ready()``/``.item()``
    call — or a ``float(...)`` coercion — on a name bound to a
    ``.step(...)`` result, executed every iteration, is a second device
    round trip per step outside the guard's single-sync cadence."""
    seen: Set[int] = set()
    for loop in _step_loops(tree):
        names = _step_result_names(loop)
        if not names:
            continue
        decimated = _decimated_ifs(loop)
        for node in ast.walk(loop):
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            f = node.func
            hit = None
            if isinstance(f, ast.Attribute) \
                    and f.attr in _SYNC_METHOD_LEAVES \
                    and isinstance(f.value, ast.Name) \
                    and f.value.id in names:
                hit = f"{f.value.id}.{f.attr}()"
            elif isinstance(f, ast.Name) and f.id == "float" and node.args \
                    and isinstance(node.args[0], ast.Name) \
                    and node.args[0].id in names:
                hit = f"float({node.args[0].id})"
            if hit is None:
                continue
            if _inside(node, decimated):
                continue   # decimated (if step % N) — cadence respected
            seen.add(id(node))
            report.add(Diagnostic(
                "MX604",
                f"stray device sync {hit} inside a step loop — every "
                "iteration pays a second host round trip on top of the "
                "guard's single sync, and the host cannot run ahead of "
                "the device; read trainer.last_loss/last_grad_norm (already "
                "synced by the guard), sync once after the loop, or "
                "decimate the read (if step % N == 0)",
                node=f"{filename}:{getattr(node, 'lineno', 0)}",
                op=hit, pass_name="telemetry_lint",
                severity="warning"))


# -- MX603: stats through host callbacks in a jitted region ------------------

#: callback entry points that round-trip to host from inside a jit
_CALLBACK_LEAVES = {"pure_callback", "io_callback", "callback",
                    "debug_callback", "host_callback"}
#: jax.debug.<leaf> forms (print included: it IS a host callback)
_DEBUG_LEAVES = {"callback", "print"}
#: reduction callables whose presence in a callback's arguments marks
#: it as "stats leaving the graph through the side door"
_REDUCTION_LEAVES = {"min", "max", "mean", "sum", "std", "var", "norm",
                     "rms", "amin", "amax", "nanmin", "nanmax",
                     "nanmean", "histogram", "bincount", "quantile",
                     "percentile", "isfinite", "isnan", "any", "all"}
#: decorator names marking a function as jit-compiled
_JIT_NAMES = {"jit", "pjit"}


def _leaf_name(f: ast.AST) -> Optional[str]:
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


def _is_host_callback_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    leaf = _leaf_name(f)
    if leaf in _CALLBACK_LEAVES:
        return True
    # jax.debug.callback / jax.debug.print
    if leaf in _DEBUG_LEAVES and isinstance(f, ast.Attribute) \
            and isinstance(f.value, ast.Attribute) \
            and f.value.attr == "debug":
        return True
    return False


def _carries_reduction(call: ast.Call) -> bool:
    for arg in list(call.args) + [kw.value for kw in call.keywords]:
        for node in ast.walk(arg):
            if isinstance(node, ast.Call) \
                    and _leaf_name(node.func) in _REDUCTION_LEAVES:
                return True
    return False


def _is_jit_decorator(dec: ast.AST) -> bool:
    # @jit / @jax.jit / @pjit / @partial(jax.jit, ...) / @jax.jit(...)
    if isinstance(dec, ast.Call):
        if _leaf_name(dec.func) in ("partial",):
            return any(_leaf_name(getattr(a, "func", a)) in _JIT_NAMES
                       or _leaf_name(a) in _JIT_NAMES for a in dec.args)
        dec = dec.func
    return _leaf_name(dec) in _JIT_NAMES


def _jitted_functions(tree: ast.Module) -> List[ast.AST]:
    """Functions provably jit-compiled in this file: jit-decorated, or
    passed by name as the first argument of a ``jit(...)`` call."""
    jitted_names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _leaf_name(node.func) in _JIT_NAMES:
            if node.args and isinstance(node.args[0], ast.Name):
                jitted_names.add(node.args[0].id)
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name in jitted_names \
                or any(_is_jit_decorator(d) for d in node.decorator_list):
            out.append(node)
    return out


def _lint_callback_stats(tree: ast.Module, filename: str,
                         report: Report) -> None:
    """MX603 over every provably-jitted function in the module."""
    for func in _jitted_functions(tree):
        for node in ast.walk(func):
            if not _is_host_callback_call(node):
                continue
            if not _carries_reduction(node):
                continue   # custom-op style callbacks over raw tensors
                # are MX701's HLO-level business, not a stats smell
            report.add(Diagnostic(
                "MX603",
                f"tensor statistics leave the jitted function "
                f"{func.name}() through a host callback "
                f"({_leaf_name(node.func)}) — this breaks whole-step "
                "capture (one callback round-trip per executed step); "
                "compute the reduction in-graph and return it as an "
                "extra pinned output (telemetry.numerics.graph_stats/"
                "tap), decimating host-side",
                node=f"{filename}:{getattr(node, 'lineno', 0)}",
                op=func.name, pass_name="telemetry_lint",
                severity="warning"))


def lint_source(src: str, filename: str = "<string>") -> Report:
    """Lint one Python source blob for MX6xx findings."""
    report = Report()
    try:
        tree = ast.parse(src, filename=filename)
    except SyntaxError:
        return report  # tracer_lint owns the MX200 parse diagnostic
    # MX602 runs unconditionally: emit() is its subject, so file-level
    # telemetry evidence cannot excuse it
    _lint_uncorrelated(tree, filename, report)
    # MX603 likewise: a host callback carrying reductions out of a jit
    # is the subject itself, never excused by other telemetry in the file
    _lint_callback_stats(tree, filename, report)
    # MX604 likewise: the stray sync IS the subject — a file full of
    # telemetry spine usage can still pay a hidden round trip per step
    _lint_stray_syncs(tree, filename, report)
    if _has_telemetry_evidence(tree):
        return report
    seen_clocks: Set[int] = set()  # one finding per scope; a clock call
    for where, scopes in (("training loop", _step_loops(tree)),  # inside
                          ("serving entry point",  # nested scopes reports
                           _entry_functions(tree))):  # at the outermost
        for scope in scopes:
            clocks = [n for n in ast.walk(scope)
                      if _is_clock_call(n) and id(n) not in seen_clocks]
            if not clocks:
                continue
            seen_clocks.update(id(n) for n in clocks)
            name = getattr(scope, "name", None)
            report.add(Diagnostic(
                "MX601",
                f"ad-hoc wall-clock timing inside a {where} "
                f"({len(clocks)} clock call(s)) — this measurement is "
                "invisible to the event bus, the Prometheus scrape, and "
                "telemetry.snapshot(); emit it through mx.telemetry "
                "(emit()/Histogram/step_scope) or an mx.profiler span "
                "instead",
                node=f"{filename}:{getattr(clocks[0], 'lineno', 0)}",
                op=name or where, pass_name="telemetry_lint",
                severity="warning"))
    return report


def lint_file(path: str) -> Report:
    with open(path) as f:
        return lint_source(f.read(), filename=path)


def lint_paths(paths) -> Report:
    """Lint files and directories (recursing into ``*.py``)."""
    return walk_lint(paths, lint_file)
