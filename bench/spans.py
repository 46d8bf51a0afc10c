"""The program's own spans (``lazyvlm.*``, ``src/repro/obs.py``), reduced
per engine batch.

A batch is one ``lazyvlm.engine.batch`` span; a span belongs to the batch
span that contains it. Its stages are the ``lazyvlm.engine.<stage>``
spans, its syncs the ``lazyvlm.sync`` spans (the host waiting on the
device), and its host time the part of it in which the first device ran
no operation, on the trace's shared clock.

``bench.trace.load`` keeps only the harness's host spans; :func:`load`
adds the program's, with their metadata.

    python3 -m bench.spans --workload <cell> --seed <n> [--trace 1]

runs the cell as ``python3 -m bench.run`` does, with the program's spans
kept in the trace (so idle gaps are named after them), prints the run's
result line, then one line ``SPANS {...}``: the engine call per batch
from the tickets and, in a traced run, each stage, the syncs, the host
time and the idle gaps inside ticks, per batch; and what one span costs
with the profiler off and on.
"""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from bench import trace as tr

PREFIX = "lazyvlm."
STAGES = ("plan", "search", "select", "verify", "temporal", "results")
_load_harness_trace = tr.load


@dataclass
class Span(tr.Event):
    meta: Dict[str, object] = field(default_factory=dict)


def load(path: str) -> tr.Trace:
    """``bench.trace.load``'s trace, with the program's spans added to its
    host events."""
    from jax.profiler import ProfileData
    t = _load_harness_trace(path)
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            t.host += [Span(e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9,
                            {k: v for k, v in e.stats})
                       for e in ln.events if e.name.startswith(PREFIX)]
    return t


@dataclass
class Batch:
    span: tr.Event
    stages: Dict[str, float]     # seconds of each stage's spans
    syncs: int
    sync_bytes: int
    host_s: float                # seconds with no operation on the device

    @property
    def covered(self) -> float:
        """Share of the batch span that its stage spans cover."""
        return sum(self.stages.values()) / self.span.dur


def batches(t: tr.Trace) -> List[Batch]:
    spans = sorted((e for e in t.host if e.name.startswith(PREFIX)),
                   key=lambda e: e.start)
    dev = tr.union(t.ops[sorted(t.ops)[0]]) if t.ops else []
    out = []
    for b in (e for e in spans if e.name == PREFIX + "engine.batch"):
        inside = [e for e in spans if e is not b and b.start <= e.start
                  and e.end <= b.end]
        stages = {s: sum(e.dur for e in inside
                         if e.name == f"{PREFIX}engine.{s}") for s in STAGES}
        syncs = [e for e in inside if e.name == PREFIX + "sync"]
        busy = tr.covered(tr.clip(dev, b.start, b.end))
        out.append(Batch(b, stages, len(syncs),
                         sum(int(getattr(e, "meta", {}).get("bytes", 0))
                             for e in syncs), b.dur - busy))
    return out


def stage_ms(run, key: str) -> Optional[float]:
    """Median per batch of ``stats.stage_seconds[key]`` of the window's
    answers, in ms: the wall time of the stage's span as the engine
    records it in every result of the batch."""
    per_batch = {}
    for s in run.done():
        sec = s.ticket.result.stats.stage_seconds.get(key)
        if sec is not None:
            per_batch[s.ticket.execute_started_at] = sec
    return float(np.median(list(per_batch.values()))) * 1e3 if per_batch \
        else None


def tick_gaps(t: tr.Trace, lo: float, hi: float, least: float = 1e-3
              ) -> Dict[str, list]:
    """Idle stretches of the first device of at least ``least`` seconds
    that overlap a ``bench.tick`` span, by the host span that
    ``bench.trace.host_doing`` names for each: name -> [count, seconds].
    A stretch named after one of the harness's own spans is listed under
    ``"bench"`` too, as [seconds, {span: seconds of overlap}]."""
    if not t.ops:
        return {}
    ticks = [e for e in t.host if e.name == "bench.tick"]
    out: Dict[str, list] = defaultdict(lambda: [0, 0.0])
    named_bench = []
    for a, b in tr.gaps(tr.clip(tr.union(t.ops[sorted(t.ops)[0]]), lo, hi),
                        lo, hi):
        if b - a < least or not any(k.start < b and a < k.end
                                    for k in ticks):
            continue
        name = tr.host_doing(t, a, b)
        out[name][0] += 1
        out[name][1] += b - a
        if name.startswith(tr.HOST_SPAN_PREFIX):
            over: Dict[str, float] = defaultdict(float)
            for e in t.host:
                over[e.name] += max(0.0, min(b, e.end) - max(a, e.start))
            named_bench.append([b - a, {k: v for k, v in over.items()
                                        if v > 0}])
    if named_bench:
        out["bench"] = sorted(named_bench, key=lambda g: -g[0])[:5]
    return dict(out)


def span_cost_us(n: int = 20000) -> Dict[str, float]:
    """Microseconds one ``obs.span`` with one keyword costs on this host,
    with no trace active and inside a trace."""
    import jax

    from repro import obs

    def per_span():
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("cost", bytes=8):
                pass
        return (time.perf_counter() - t0) / n * 1e6
    off = per_span()
    d = tempfile.mkdtemp(prefix="bench-span-cost-")
    jax.profiler.start_trace(d)
    on = per_span()
    jax.profiler.stop_trace()
    shutil.rmtree(d, ignore_errors=True)
    return {"off": off, "on": on}


def _median(xs) -> Optional[float]:
    return float(np.median(xs)) if len(xs) else None


def summary(served, trace: Optional[tr.Trace]) -> dict:
    """The engine call per batch from the tickets; with a trace, what the
    program's spans say of each batch and of the idle gaps in ticks."""
    engine: Dict[float, float] = {}
    for s in served:
        t = s.ticket
        if t is not None and t.done and t.error is None:
            engine[t.execute_started_at] = (t.completed_at
                                            - t.execute_started_at)
    out: dict = {"batches": len(engine),
                 "engine_ms": _median([v * 1e3 for v in engine.values()])}
    if trace is None:
        return out
    bs = batches(trace)
    out.update({f"{s}_ms": _median([b.stages[s] * 1e3 for b in bs])
                for s in STAGES})
    out.update({
        "span_batches": len(bs),
        "batch_ms": _median([b.span.dur * 1e3 for b in bs]),
        "engine_host_ms": _median([b.host_s * 1e3 for b in bs]),
        "syncs_per_batch": _median([b.syncs for b in bs]),
        "sync_bytes_per_batch": _median([b.sync_bytes for b in bs]),
        "least_covered": min((b.covered for b in bs), default=None)})
    if bs:
        total = sum(b.span.dur for b in bs)
        out["share"] = {s: sum(b.stages[s] for b in bs) / total
                        for s in STAGES}
    ticket = [engine[k] for k in sorted(engine)]
    if bs and len(ticket) == len(bs):
        diff = [(b.span.dur - t) * 1e3 for b, t in zip(bs, ticket)]
        out["batch_minus_ticket_ms"] = [min(diff), max(diff)]
    lo, hi = tr.window(trace)
    out["tick_gaps"] = tick_gaps(trace, lo, hi)
    return out


def main(argv=None) -> int:
    from bench import run
    args = run.parse_args(argv)
    kept: dict = {"trace": None}

    def keep_trace(path):
        kept["trace"] = load(path)
        return kept["trace"]
    open_loop = run.open_loop

    def keep_served(*a, **k):
        kept["served"] = out = open_loop(*a, **k)
        return out
    tr.load, run.open_loop = keep_trace, keep_served
    out, rc = run.execute(args)
    if out is not None:
        print(json.dumps(out), flush=True)
    if "served" in kept:
        res = summary(kept["served"][0], kept["trace"])
        res["span_cost_us"] = span_cost_us()
        print("SPANS " + json.dumps(res), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
