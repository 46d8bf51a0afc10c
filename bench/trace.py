"""Reduce a profiler trace (an ``.xplane.pb`` file) to numbers.

Device operations are the events of the ``XLA Ops`` lines of each
``/device:...`` plane, and whole programs those of its ``XLA Modules``
lines. A trace recorded on the CPU has no device plane; there the events
of the XLA CPU client's threads stand in for device operations, so that
the reduction can be tested without a chip. Host spans are the events of
the ``/host:CPU`` plane whose names start with ``bench.``: the annotations
the harness puts around its own calls into the program.

All times are in seconds on the trace's clock.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]          # (start, end)

HOST_SPAN_PREFIX = "bench."
CPU_XLA_THREAD = "tf_XLAPjRtCpuClient"
CPU_NOISE = ("ThreadpoolListener",)


@dataclass
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    ops: Dict[str, List[Event]] = field(default_factory=dict)      # per device
    modules: Dict[str, List[Event]] = field(default_factory=dict)  # per device
    host: List[Event] = field(default_factory=list)
    device_planes: List[str] = field(default_factory=list)


def find_xplane(root: str) -> str:
    paths = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return paths[-1]


def _events(line) -> List[Event]:
    return [Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                  * 1e-9) for e in line.events]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    cpu_ops: List[Event] = []
    for plane in pd.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:"):
            tr.device_planes.append(plane.name)
            for ln in lines:
                if ln.name == "XLA Ops":
                    tr.ops[plane.name] = _events(ln)
                elif ln.name == "XLA Modules":
                    tr.modules[plane.name] = _events(ln)
        elif plane.name == "/host:CPU":
            for ln in lines:
                evs = _events(ln)
                tr.host += [e for e in evs
                            if e.name.startswith(HOST_SPAN_PREFIX)]
                if ln.name.startswith(CPU_XLA_THREAD):
                    cpu_ops += [e for e in evs if e.dur > 0 and not
                                e.name.startswith(CPU_NOISE)]
    if not tr.device_planes and cpu_ops:
        tr.ops["/host:CPU"] = cpu_ops
    return tr


def union(events: Sequence[Event]) -> List[Interval]:
    """Disjoint, sorted intervals covered by the events."""
    out: List[List[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def covered(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for a, b in intervals:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def window(tr: Trace) -> Interval:
    """From the first to the last event of any kind."""
    evs = [e for v in tr.ops.values() for e in v] + tr.host
    if not evs:
        raise ValueError("empty trace")
    return min(e.start for e in evs), max(e.end for e in evs)


def busy_s(tr: Trace, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which some operation ran, averaged over the
    devices."""
    if not tr.ops:
        return 0.0
    return sum(covered(clip(union(ev), lo, hi))
               for ev in tr.ops.values()) / len(tr.ops)


def group_seconds(events: Sequence[Event], match) -> Tuple[int, float]:
    """(count, summed duration) of the events whose name ``match``es."""
    sel = [e for e in events if match(e.name)]
    return len(sel), sum(e.dur for e in sel)


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """The ``n`` operation names with the most device time, summed over
    devices."""
    tot: Dict[str, float] = defaultdict(float)
    for ev in tr.ops.values():
        for e in ev:
            tot[e.name] += e.dur
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def host_doing(tr: Trace, a: float, b: float) -> str:
    """The innermost (shortest) host span that covers at least half of
    [a, b]; failing that, the span that covers most of it."""
    over = [(min(b, e.end) - max(a, e.start), e) for e in tr.host]
    over = [(ov, e) for ov, e in over if ov > 0]
    if not over:
        return "untraced host"
    half = [e for ov, e in over if ov >= 0.5 * (b - a)]
    if half:
        return min(half, key=lambda e: e.dur).name
    return max(over, key=lambda oe: oe[0])[1].name


def idle_gaps(tr: Trace, lo: float, hi: float, n: int = 10) -> List[List]:
    """The ``n`` longest stretches in which the first device ran nothing,
    each named by what the host was doing."""
    if not tr.ops:
        return []
    dev = sorted(tr.ops)[0]
    g = sorted(gaps(clip(union(tr.ops[dev]), lo, hi), lo, hi),
               key=lambda ab: -(ab[1] - ab[0]))[:n]
    return [[host_doing(tr, a, b), b - a] for a, b in g]
