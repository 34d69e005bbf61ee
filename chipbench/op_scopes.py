"""Device time by the program's own scopes, and the collectives' time.

The program puts a ``jax.named_scope`` round each of its layers, and jax
writes the scopes open at an operation into its HLO instruction's
``metadata={op_name=...}``: ``jit(step)/transpose(jvp(afmoe_attention))/
checkpoint/rematted_computation/rms_norm/mul`` is a norm in a recomputed
layer's forward, run by the backward pass. XLA gives a fusion one
``op_name``: a loop fusion its root's, a matmul fusion (``kind=kOutput``,
the matmul with what XLA put round it) the matmul's, so a weight-gradient
matmul fused with its AdamW update, or a norm's statistic in a matmul's
epilogue, counts under the matmul's scopes. A profiler trace names an
operation by its instruction alone (``Trace.device``: ``fusion.12 fusion
bf16[8192,2048]``), so the map from
instruction to ``op_name`` comes from the compiled step's text, which the
program hands out after the window (``compile_log.program_text``, asked
once a trace and only where the trace holds device operations), joined by
instruction name with the opcode checked. An operation that does not join,
or has no ``op_name``, holds no scope.

The text also says which operations are collectives, which the event's
name alone does not: on a v5e the compiler puts a reduce-scatter into a
``fusion.N`` that calls ``%all-reduce-scatter``, and an asynchronous
all-gather into ``async-collective-start`` / ``-done`` fusions.

A program that keeps no text (the commit before it learnt to) gives
``None``, never an error.
"""
import re
import sys
import traceback
from typing import NamedTuple

from chipbench import tracered

#: the program's named scopes, as ``jax.named_scope`` writes them
SCOPES = ("optimizer_update", "layer_norm", "rms_norm", "afmoe_attention", "moe_router",
          "moe_dispatch", "moe_experts", "moe_combine", "lm_head", "mla_q", "mla_latent",
          "mla_kv_up", "mla_attention", "lfm2_mixer_conv", "lfm2_mixer_attn", "short_conv",
          "qk_prologue")
NORMS = ("layer_norm", "rms_norm")
#: where jax puts a recomputed layer's forward; the ``checkpoint`` segment
#: round it holds the layer's backward as well, so it is not matched
RECOMPUTE = ("rematted_computation",)
#: the site whose program the benchmark's step runs
SITE = "trainer.step"
#: below this share of busy time joined, the text is not the program that ran
MIN_JOINED = 0.5

_SEGMENT = re.compile(r"[/()]")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)(-start|-done)?$")


class Op(NamedTuple):
    """One instruction of the compiled text."""
    opcode: str
    op_name: str
    #: a collective, or a fusion that calls one
    collective: bool


def segments(op_name: str) -> frozenset:
    """The words of an ``op_name``, with ``/``, ``(`` and ``)`` as bounds."""
    return frozenset(filter(None, _SEGMENT.split(op_name)))


def parse(text: str) -> dict:
    """``{instruction: Op}`` of every instruction in an HLO module's text,
    ``op_name`` ``""`` where it has none. A collective is an instruction
    whose opcode is one, or that calls a computation holding one."""
    ops, calls, body, comp = {}, {}, {}, None
    for raw in text.splitlines():
        head = _COMPUTATION.match(raw)
        if head:
            comp = head.group(1)
            body[comp] = []
            continue
        line = raw.strip().removeprefix("ROOT ")
        words = tracered.short_name(line).split(" ", 2)
        if len(words) < 2 or " = " not in line:
            continue
        m = _OP_NAME.search(line)
        ops[words[0]] = (words[1], m.group(1) if m else "")
        calls[words[0]] = _CALLS.findall(line)
        if comp is not None:
            body[comp].append(words[0])
    holds = {}

    def held(computation) -> bool:
        if computation not in holds:
            holds[computation] = any(
                _COLLECTIVE.match(ops[i][0]) or any(held(c) for c in calls[i] if c in body)
                for i in body[computation])
        return holds[computation]

    return {name: Op(opcode, op_name, bool(_COLLECTIVE.match(opcode))
                     or any(held(c) for c in calls[name] if c in body))
            for name, (opcode, op_name) in ops.items()}


def op_names(trace):
    """The map of the program that ran, kept on ``trace`` as ``op_names``
    the first time it is asked for; ``None`` where there is none."""
    if trace is None or not trace.device:
        return None
    if not hasattr(trace, "op_names"):
        trace.op_names = _from_program()
    return trace.op_names


def _from_program():
    from incubator_mxnet_tpu.telemetry import compile_log
    read = getattr(compile_log, "program_text", None)
    if read is None:
        return None
    try:
        text = read(SITE)
    except Exception:               # a reader reports, the run goes on
        traceback.print_exc(file=sys.stderr)
        return None
    return parse(text) if text else None


def _joined(trace):
    """Per chip, ``[(Op or None, scope words, start_s, end_s), ...]`` in time
    order, ``None`` for an event that does not join the map; ``None`` for
    the whole where there is no map or under ``MIN_JOINED`` of the busy
    time joins."""
    names = op_names(trace)
    if not names:
        return None
    cache, out, joined = {}, {}, 0.0
    for plane, evs in trace.device.items():
        rows = out[plane] = []
        for name, s, e in sorted(evs, key=lambda ev: ev[1]):
            if name not in cache:
                words = name.split(" ", 2)
                op = names.get(words[0]) if len(words) > 1 else None
                op = op if op and op.opcode == words[1] else None
                cache[name] = (op, segments(op.op_name) if op else frozenset())
            op, words = cache[name]
            rows.append((op, words, s, e))
            joined += (e - s) if op else 0.0
    busy = trace.busy_s * len(trace.device)
    return out if busy and joined >= MIN_JOINED * busy else None


def _seconds(trace, keep):
    """Device seconds, averaged over the chips, in the operations whose
    scope words ``keep`` accepts; ``None`` without a map."""
    rows = _joined(trace)
    if rows is None:
        return None
    return sum(e - s for evs in rows.values() for _op, w, s, e in evs if keep(w)) / len(rows)


def scope_ms_per_step(trace, scopes):
    """Device milliseconds a traced step in the operations whose ``op_name``
    holds one of ``scopes`` as a word; ``None`` where none does."""
    steps = trace.span_count("bench.step") if trace else 0
    want = frozenset(scopes)
    seconds = _seconds(trace, lambda w: not w.isdisjoint(want)) if steps else None
    return seconds / steps * 1e3 if seconds else None


def unscoped_share(trace):
    """Per cent of the device's busy time in operations that hold none of
    ``SCOPES`` (the unjoined among them)."""
    want = frozenset(SCOPES)
    seconds = _seconds(trace, lambda w: w.isdisjoint(want))
    return 100.0 * seconds / trace.busy_s if seconds is not None else None


def _minus(intervals, others) -> float:
    """Seconds of the union of ``intervals`` that the union of ``others``
    leaves uncovered."""
    total, cover = 0.0, tracered._union(others)
    for s, e in tracered._union(intervals):
        total += e - s
        for c0, c1 in cover:
            total -= max(0.0, min(e, c1) - max(s, c0))
    return total


def collective_seconds(trace):
    """``(collective, exposed)`` device seconds, averaged over the chips, or
    ``None`` where no collective ran or there is no map: the union of the
    collectives' own events (a ``-start`` and a ``-done`` each for its own
    time, not the span between them), and the part of it in which no other
    operation runs on that chip."""
    rows = _joined(trace)
    if rows is None:
        return None
    total = exposed = 0.0
    found = False
    for evs in rows.values():
        spans = [(s, e) for op, _w, s, e in evs if op is not None and op.collective]
        others = [(s, e) for op, _w, s, e in evs if op is None or not op.collective]
        found = found or bool(spans)
        total += sum(e - s for s, e in tracered._union(spans))
        exposed += _minus(spans, others)
    if not found:
        return None
    return total / len(rows), exposed / len(rows)
