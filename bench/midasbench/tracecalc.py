"""Reduction of a profiler trace to device busy time, idle gaps and
kernel time.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Each device
plane (``/device:TPU:n``) holds the operations that ran on that device,
with start and duration in nanoseconds; the host plane holds the spans
the benchmark and the program annotate (``bench/...``, ``sweep/...``),
on the same clock.  Busy time is the union of a device's operation
intervals inside the window; idle share is one minus busy over the
window, averaged over the devices; each idle gap is labelled with the
innermost host span that covers its midpoint.
"""

from __future__ import annotations

import bisect
import gzip
from typing import Dict, List, NamedTuple, Sequence, Tuple

Interval = Tuple[float, float]

HOST_PREFIXES = ("bench/", "sweep/", "sim/")


class Op(NamedTuple):
    name: str  # the HLO instruction's name, e.g. "fusion.12"
    start: float  # ns
    end: float  # ns
    module: str  # the XLA program the op belongs to ("" unknown)


class Span(NamedTuple):
    name: str
    start: float
    end: float


class Trace(NamedTuple):
    devices: Dict[str, List[Op]]
    host: List[Span]


def op_name(text: str) -> str:
    """``fusion.12`` from an event named by its HLO text
    (``%fusion.12 = f32[...] fusion(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path) -> Trace:
    """Device operations and host spans of one ``.xplane.pb`` (or its
    gzip).  A device is a ``/device:`` plane with an ``XLA Ops`` line;
    its ops there nest (a loop's op spans its body's ops)."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(str(path))
    devices: Dict[str, List[Op]] = {}
    host: List[Span] = []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            mod_events = (
                lines["XLA Modules"].events if "XLA Modules" in lines else ()
            )
            modules = sorted(
                (
                    Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in mod_events
                ),
                key=lambda m: m.start,
            )
            starts = [m.start for m in modules]
            devices[plane.name] = sorted(
                (
                    Op(op_name(e.name), e.start_ns,
                       e.start_ns + e.duration_ns,
                       _module_at(modules, starts, e.start_ns))
                    for e in lines["XLA Ops"].events
                ),
                key=lambda o: (o.start, -o.end),
            )
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append(
                            Span(e.name, e.start_ns,
                                 e.start_ns + e.duration_ns)
                        )
    return Trace(devices, sorted(host, key=lambda s: s.start))


def _module_at(modules: Sequence[Span], starts, t: float) -> str:
    """The program running at ``t`` (programs on a device do not
    overlap); ``modules`` sorted by start, ``starts`` their starts."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < modules[i].end:
        return modules[i].name
    return ""


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float):
    return [
        (max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi
    ]


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def gaps(merged: Sequence[Interval], lo: float, hi: float):
    """The parts of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in clip(merged, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def window(trace: Trace, name: str = "bench/window") -> Interval:
    """The traced window: the host span the benchmark puts around it."""
    spans = [s for s in trace.host if s.name == name]
    if not spans:
        raise ValueError(f"trace holds no {name!r} span")
    return spans[0].start, spans[-1].end


def busy_ns(trace: Trace, lo: float, hi: float, select=None) -> float:
    """Busy time inside [lo, hi], averaged over the devices; ``select``
    (an ``Op -> bool``) restricts it to some operations."""
    if not trace.devices:
        return 0.0
    tot = 0.0
    for ops in trace.devices.values():
        iv = [(o.start, o.end) for o in ops if select is None or select(o)]
        tot += length(clip(iv, lo, hi))
    return tot / len(trace.devices)


def op_time_ns(trace: Trace, lo: float, hi: float, select):
    """(summed durations, count) of the selected operations inside
    [lo, hi], averaged over the devices."""
    if not trace.devices:
        return 0.0, 0
    tot, n = 0.0, 0
    for ops in trace.devices.values():
        for o in ops:
            if select(o) and o.end > lo and o.start < hi:
                tot += min(o.end, hi) - max(o.start, lo)
                n += 1
    k = len(trace.devices)
    return tot / k, n // k


def self_times(ops: Sequence[Op]) -> List[float]:
    """Each op's duration less that of the ops nested directly in it (a
    loop's op less its body's ops); ``ops`` sorted by (start, -end)."""
    self_t = [o.end - o.start for o in ops]
    stack: List[int] = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end <= o.start:
            stack.pop()
        if stack:
            self_t[stack[-1]] -= o.end - o.start
        stack.append(i)
    return self_t


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10):
    """The ``n`` operations with the most self time inside [lo, hi], in
    seconds averaged over the devices."""
    acc: Dict[str, float] = {}
    for ops in trace.devices.values():
        inside = [o for o in ops if o.start >= lo and o.end <= hi]
        for o, t in zip(inside, self_times(inside)):
            acc[o.name] = acc.get(o.name, 0.0) + t
    k = max(len(trace.devices), 1)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t / k / 1e9] for name, t in top]


def label(gap: Interval, host: Sequence[Span]) -> str:
    """The innermost host span covering the gap's midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    covering = [s for s in host if s.start <= mid < s.end]
    if not covering:
        return "no host span"
    return min(covering, key=lambda s: s.end - s.start).name


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10):
    """The ``n`` longest idle gaps of the first device, each labelled
    with what the host was doing, in seconds."""
    if not trace.devices:
        return []
    first = sorted(trace.devices)[0]
    busy = merge([(o.start, o.end) for o in trace.devices[first]])
    g = sorted(gaps(busy, lo, hi), key=lambda iv: iv[0] - iv[1])[:n]
    return [[label(iv, trace.host), (iv[1] - iv[0]) / 1e9] for iv in g]
