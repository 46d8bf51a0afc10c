"""The trace reduction, on synthetic events and on a trace recorded on the
CPU (``bench/testdata/cpu_trace.xplane.pb``)."""
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parents[1] / "testdata" / "cpu_trace.xplane.pb"


def ev(name, a, b):
    return tr.Event(name, a, b)


def test_union_merges_overlaps_and_keeps_gaps():
    u = tr.union([ev("a", 0, 2), ev("b", 1, 3), ev("c", 5, 6), ev("d", 6, 7)])
    assert u == [(0, 3), (5, 7)]
    assert tr.covered(u) == 5
    assert tr.gaps(u, -1, 9) == [(-1, 0), (3, 5), (7, 9)]
    assert tr.clip(u, 2, 6) == [(2, 3), (5, 6)]


def test_busy_averages_over_devices_and_groups_by_name():
    t = tr.Trace(ops={"d0": [ev("fusion", 0, 4)],
                      "d1": [ev("fusion", 0, 2), ev("copy", 1, 3)]})
    assert tr.busy_s(t, 0, 10) == pytest.approx((4 + 3) / 2)
    assert tr.group_seconds(t.ops["d1"], lambda n: n == "fusion") == (1, 2)
    assert tr.top_ops(t, 1) == [["fusion", 6]]


def test_idle_gap_named_by_innermost_covering_host_span():
    t = tr.Trace(ops={"d0": [ev("op", 0, 1), ev("op", 5, 6)]},
                 host=[ev("bench.tick", 0, 6), ev("bench.embed", 1.5, 4.5)])
    assert tr.idle_gaps(t, 0, 6) == [["bench.embed", 4]]


def test_recorded_cpu_trace():
    t = tr.load(str(DATA))
    assert [e.name for e in t.host] == ["bench.tick", "bench.wait",
                                        "bench.tick"]
    lo, hi = tr.window(t)
    busy = tr.busy_s(t, lo, hi)
    assert 0 < busy < hi - lo
    wait = t.host[1]
    gaps = tr.idle_gaps(t, lo, hi, 1)
    # the sleep inside bench.wait is the longest stretch with no operation
    assert gaps[0][0] == "bench.wait"
    assert gaps[0][1] == pytest.approx(wait.dur, rel=0.1)
    assert busy + sum(d for _, d in tr.idle_gaps(t, lo, hi, 10 ** 6)) \
        == pytest.approx(hi - lo)
    names = [n for n, _ in tr.top_ops(t)]
    assert any(n.startswith("dot") for n in names)
