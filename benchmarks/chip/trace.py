"""Reduction of a profiler trace to intervals, and of intervals to the
numbers the per-layer metrics report.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes,
with nothing but JAX, into a ``Trace``: per device the operations of its
``XLA Ops`` line, and the host spans this benchmark names ``bench.*``.
On a TPU each operation's event is named by its whole HLO instruction
(``%fusion.12 = bf16[...] fusion(...), ...``); the trace keeps the
instruction's name (``fusion.12``), and leaves out the ``while``,
``conditional`` and ``call`` events, which only contain the operations
that run inside them.
A ``Trace`` also round-trips through a small JSON file, which is how the
tests hold a trimmed trace recorded on the chip.  Times are seconds.
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
_INSTR = re.compile(r"^%(\S+) = .*? ([a-z][a-z0-9-]*)\(")
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str | None:
    """The HLO instruction's name of a device event, ``None`` for a
    container; a name that is no instruction stays as it is."""
    m = _INSTR.match(event_name)
    if not m:
        return event_name
    return None if m.group(2) in CONTAINERS else m.group(1)


@dataclasses.dataclass
class Trace:
    #: {device id: [(op name, start s, end s)]}
    ops: dict[int, list[tuple[str, float, float]]]
    #: [(span name, start s, end s)] of the benchmark's host spans
    spans: list[tuple[str, float, float]]

    def window(self, name: str = "bench.window") -> tuple[float, float]:
        got = [(s, e) for n, s, e in self.spans if n == name]
        if not got:
            raise ValueError(f"trace holds no {name!r} span")
        return got[0]

    def to_json(self) -> str:
        return json.dumps({"ops": {str(d): v for d, v in self.ops.items()},
                           "spans": self.spans})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        raw = json.loads(text)
        return cls({int(d): [tuple(x) for x in v]
                    for d, v in raw["ops"].items()},
                   [tuple(x) for x in raw["spans"]])


def load_xplane(path: str, devices: set[int] | None = None) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: dict[int, list] = {}
    spans: list = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if devices is not None and dev not in devices:
                continue
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = ops.setdefault(dev, [])
                for e in line.events:
                    name = op_name(e.name)
                    if name is not None:
                        evs.append((name, e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops, spans)


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def subtract(a, b) -> list[tuple[float, float]]:
    """The parts of intervals ``a`` that no interval of ``b`` covers."""
    b = union(b)
    out = []
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def busy(trace: Trace, window) -> dict[int, float]:
    """Seconds of the window in which any operation runs, per device."""
    lo, hi = window
    return {d: length(clip([(s, e) for _, s, e in ev], lo, hi))
            for d, ev in trace.ops.items()}


def op_time(trace: Trace, window, names) -> dict[int, float]:
    """Summed device time of the operations named in ``names`` (a set, or
    a predicate on the name), per device."""
    pick = names if callable(names) else names.__contains__
    lo, hi = window
    return {d: sum(e - s for s, e in clip(
        [(s, e) for n, s, e in ev if pick(n)], lo, hi))
        for d, ev in trace.ops.items()}


def exposed(trace: Trace, window, is_collective) -> dict[int, float]:
    """Seconds in which a collective runs on the device and no other
    operation does, per device."""
    lo, hi = window
    out = {}
    for d, ev in trace.ops.items():
        coll = clip([(s, e) for n, s, e in ev if is_collective(n)], lo, hi)
        comp = clip([(s, e) for n, s, e in ev if not is_collective(n)],
                    lo, hi)
        out[d] = sum(e - s for s, e in subtract(coll, comp))
    return out


#: idle intervals shorter than this lie between two device operations
#: and are the device's own; longer ones are named by the host
SHORT_GAP = 1e-6


def idle_gaps(trace: Trace, window, device: int
              ) -> list[tuple[str, float, float, float]]:
    """Idle intervals of ``device`` in the window, each named by the
    ``bench.*`` host span (other than the window) that overlaps it most,
    ``host.other`` where none does, or ``device.between_ops`` when it is
    shorter than ``SHORT_GAP``: ``[(name, seconds, start, end)]``."""
    lo, hi = window
    ev = trace.ops.get(device, [])
    gaps = subtract([(lo, hi)], [(s, e) for _, s, e in ev])
    spans = sorted((s, e, n) for n, s, e in trace.spans
                   if n != "bench.window")
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0.0)
    out = []
    for gs, ge in gaps:
        if ge - gs < SHORT_GAP:
            out.append(("device.between_ops", ge - gs, gs, ge))
            continue
        best, cover = "host.other", 0.0
        first = bisect.bisect_left(starts, gs - longest)
        last = bisect.bisect_right(starts, ge)
        for s, e, n in spans[first:last]:
            c = min(e, ge) - max(s, gs)
            if c > cover:
                best, cover = n, c
        out.append((best, ge - gs, gs, ge))
    return out


def top(pairs, n: int = 10) -> list[list]:
    """The ``n`` largest ``(name, seconds)`` totals, summed by name."""
    tot: dict[str, float] = {}
    for name, sec in pairs:
        tot[name] = tot.get(name, 0.0) + sec
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            [:n]]


def clip_named(events, lo: float, hi: float):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if min(e, hi) > max(s, lo)]
