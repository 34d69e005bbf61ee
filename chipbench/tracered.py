"""From a profiler trace to numbers: device busy, idle, gaps, time by name.

``Tracer`` wraps ``jax.profiler`` for the traffic kinds; ``load`` reads the
``.xplane.pb`` a run wrote with ``jax.profiler.ProfileData`` and nothing
else; ``Trace`` holds plain ``(name, start_s, end_s)`` events, so the
reduction can be checked on a handful of events written by hand.

What counts as what (looked at by hand on a v5e trace, PR 23): a device is a
plane named ``/device:TPU:<n>``; its operations are the events of its
``XLA Ops`` line (``Steps`` and ``XLA Modules`` hold one event a step,
``Async XLA Ops`` the copies and slices that run beside the operations); host
spans are the events of the ``/host:CPU`` plane whose names are lower-case
dotted words (``bench.step``, ``serve.batch``: what
``jax.profiler.TraceAnnotation`` and the program's ``profiler.Scope`` write).
Every ``start_ns`` is on one clock. An operation's event name is its whole
HLO instruction, kilobytes long; ``short_name`` keeps the instruction's name,
its opcode, a custom call's target and the output types: a Pallas kernel
reads ``transpose_jvp___.25 custom-call tpu_custom_call (f32[384,512,64], ...)``.
``breakdown`` adds up the instances of one instruction (the twelve layers'
``fusion.N`` of one shape) by dropping the instance number.
"""
import glob
import os
import re

import jax

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_SPAN = re.compile(r"^[a-z_0-9]+(\.[a-z_0-9]+)+$")
#: how a Pallas (Mosaic) kernel reads after ``short_name``
CUSTOM_CALL = r" custom-call tpu_custom_call( |$)"
_HLO = re.compile(r"^%?(\S+) = (.*?)[\s)]([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(text: str) -> str:
    """``<instruction> <opcode>[ <custom_call_target>] <output types>`` of an
    HLO instruction's text (layouts dropped, types cut at 80 characters);
    anything else unchanged."""
    m = _HLO.match(text)
    if not m:
        return text
    target = _TARGET.search(text) if m.group(3) == "custom-call" else None
    out = re.sub(r"\{[^}]*\}", "", m.group(2))
    return " ".join(filter(None, (m.group(1), m.group(3), target and target.group(1), out[:80])))


class Tracer:
    """Start and stop one profiler session; ``span`` marks host work."""

    def __init__(self, directory: str):
        self.directory, self.active = directory, False

    def start(self) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # no per-call Python events: they slow the host they measure
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self.active = True

    def stop(self) -> None:
        if self.active:
            jax.profiler.stop_trace()
            self.active = False

    @staticmethod
    def span(name: str):
        return jax.profiler.TraceAnnotation(name)


def _union(intervals):
    """Merged, sorted ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Trace:
    """``device``: plane name -> ``[(name, start_s, end_s), ...]`` of device
    operations; ``host``: ``[(name, start_s, end_s), ...]`` of host spans."""

    def __init__(self, device: dict, host: list):
        self.device = {k: v for k, v in device.items() if v}
        self.host = sorted(host, key=lambda e: e[1])
        ops = [e for evs in self.device.values() for e in evs]
        # the traced window: from the first host span or device operation to
        # the last device operation's end (profiler start-up is not in it)
        starts = [e[1] for e in ops] + [e[1] for e in self.host]
        self.t0 = min(starts) if ops else 0.0
        self.t1 = max(e[2] for e in ops) if ops else 0.0
        self.window_s = self.t1 - self.t0
        self._busy = {k: _union((s, e) for _, s, e in evs) for k, evs in self.device.items()}
        per_chip = [sum(e - s for s, e in iv) for iv in self._busy.values()]
        self.busy_s = sum(per_chip) / len(per_chip) if per_chip else 0.0

    @property
    def idle_share(self):
        return None if not self.window_s else 1.0 - self.busy_s / self.window_s

    def span_count(self, name: str) -> int:
        return sum(1 for e in self.host if e[0] == name)

    def by_name(self) -> dict:
        """Seconds by operation name, averaged over the chips."""
        out = {}
        for evs in self.device.values():
            for name, s, e in evs:
                out[name] = out.get(name, 0.0) + (e - s) / len(self.device)
        return out

    def seconds_matching(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(v for k, v in self.by_name().items() if rx.search(k))

    def gaps(self) -> list:
        """Idle gaps of the first device inside the window, longest first,
        each with the host span that covers most of it (``none`` if no span
        touches it; among equals the innermost, i.e. latest started)."""
        if not self._busy:
            return []
        busy = next(iter(self._busy.values()))
        edges = [self.t0] + [t for iv in busy for t in iv] + [self.t1]
        out = []
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            best, best_cover = "none", 0.0
            for name, s, e in self.host:
                cover = min(e, g1) - max(s, g0)
                if cover > 0 and cover >= best_cover:
                    best, best_cover = name, cover
            out.append([best, g1 - g0])
        return sorted(out, key=lambda g: -g[1])

    def breakdown(self) -> dict:
        kinds = {}
        for name, seconds in self.by_name().items():
            kind = re.sub(r"^(\S+?)\.\d+ ", r"\1 ", name)
            kinds[kind] = kinds.get(kind, 0.0) + seconds
        ops = sorted(kinds.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": self.gaps()[:10]}


def load(directory: str) -> Trace:
    """The newest ``.xplane.pb`` under ``directory`` as a ``Trace``; an empty
    one where nothing was written."""
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        return Trace({}, [])
    device, host = {}, []
    for plane in jax.profiler.ProfileData.from_file(paths[-1]).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        (short_name(e.name), e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                         for e in line.events if HOST_SPAN.match(e.name)]
    return Trace(device, host)
