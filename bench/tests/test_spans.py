"""The program's spans reduced per engine batch, the idle gaps they name,
and the stage metrics, on synthetic events and runs."""
from types import SimpleNamespace

import pytest

from bench import run, spans
from bench import trace as tr


def ev(name, a, b, **meta):
    return spans.Span(name, a, b, meta)


def batch_trace():
    """One tick holding one batch of 10 s: plan 0-1, search 1-2 (a sync
    1.5-2), select 2-6 (device busy 2-5, a sync 5-6), verify 6-6.5,
    temporal 6.5-9 (device busy 6.5-8, a sync 8-9), results 9-9.8."""
    P = spans.PREFIX
    host = [ev("bench.tick", 0, 10.5), ev(P + "runtime.tick", 0, 10.2),
            ev(P + "runtime.execute", 0, 10.1, batch=0, qids="0 1"),
            ev(P + "engine.batch", 0, 10),
            ev(P + "engine.plan", 0, 1), ev(P + "engine.search", 1, 2),
            ev(P + "sync", 1.5, 2, bytes=64), ev(P + "engine.select", 2, 6),
            ev(P + "sync", 5, 6, bytes=32), ev(P + "engine.verify", 6, 6.5),
            ev(P + "engine.temporal", 6.5, 9),
            ev(P + "sync", 8, 9, bytes=8), ev(P + "engine.results", 9, 9.8),
            ev(P + "runtime.submit", 11, 11.1)]
    ops = {"d0": [tr.Event("search", 1, 1.5), tr.Event("select", 2, 5),
                  tr.Event("bitmaps", 6.5, 8)]}
    return tr.Trace(ops=ops, host=host, device_planes=["d0"])


def test_idle_gap_inside_a_stage_is_named_after_the_stage():
    t = tr.Trace(ops={"d0": [tr.Event("op", 0, 1), tr.Event("op", 4, 5)]},
                 host=[tr.Event("bench.tick", 0, 5),
                       ev("lazyvlm.engine.batch", 0.5, 5),
                       ev("lazyvlm.engine.select", 1, 4)])
    assert tr.idle_gaps(t, 0, 5) == [["lazyvlm.engine.select", 3]]


def test_batches_reduce_stages_syncs_and_host_time():
    [b] = spans.batches(batch_trace())
    assert b.stages == pytest.approx({"plan": 1, "search": 1, "select": 4,
                                      "verify": 0.5, "temporal": 2.5,
                                      "results": 0.8})
    assert b.covered == pytest.approx(0.98)
    assert (b.syncs, b.sync_bytes) == (3, 104)
    # device busy 0.5 + 3 + 1.5 of the batch's 10 s
    assert b.host_s == pytest.approx(5)


def test_tick_gaps_name_each_gap_after_the_innermost_span():
    t = batch_trace()
    gaps = spans.tick_gaps(t, *tr.window(t))
    # idle 0-1 (plan), 1.5-2 (a sync), 5-6.5 (mostly a sync), and 8-11.1
    # (the batch holds most of the tick's part)
    assert gaps == {"lazyvlm.engine.plan": [1, pytest.approx(1)],
                    "lazyvlm.sync": [2, pytest.approx(2)],
                    "lazyvlm.engine.batch": [1, pytest.approx(3.1)]}


def test_gap_across_two_ticks_is_named_by_the_larger_overlap():
    P = spans.PREFIX
    t = tr.Trace(ops={"d0": [tr.Event("op", 0, 1), tr.Event("op", 5, 6)]},
                 host=[ev("bench.tick", 0, 2.9),
                       ev(P + "runtime.tick", 0, 2.8),
                       ev("bench.tick", 3.1, 6),
                       ev(P + "runtime.tick", 3.2, 6)])
    gaps = spans.tick_gaps(t, 0, 6)
    # no span holds half of 1-5; the harness's tick holds most of it
    assert gaps == {"bench.tick": [1, pytest.approx(4)],
                    "bench": [[pytest.approx(4), pytest.approx(
                        {"bench.tick": 3.8, P + "runtime.tick": 3.6})]]}


def answered(started, seconds):
    ticket = SimpleNamespace(
        done=True, error=None, execute_started_at=started,
        completed_at=started + sum(seconds.values()),
        result=SimpleNamespace(stats=SimpleNamespace(stage_seconds=seconds)))
    return run.Served(q={}, due=0.0, ticket=ticket)


@pytest.mark.parametrize("metric,key", [("search_ms.open", "entity_match"),
                                        ("select_ms.open", "symbolic"),
                                        ("temporal_ms.open", "temporal")])
def test_stage_metric_is_the_median_batch(metric, key):
    read = run.metric_reader(metric)
    batches = {0.0: 0.010, 5.0: 0.030, 9.0: 0.020}
    served = [answered(t, {key: s, "other": 1.0}) for t, s in batches.items()
              # two answers of one batch count once
              for _ in range(2 if t == 5.0 else 1)]
    data = run.RunData(cfg={}, served=served, window_s=10.0, batches=3)
    assert read(data) == pytest.approx(20.0)
    assert read(run.RunData(cfg={}, served=[], window_s=10.0,
                            batches=0)) is None


def test_summary_pairs_batch_spans_with_tickets():
    t = batch_trace()
    served = [answered(0.0, {"x": 10.0005}), answered(0.0, {"x": 10.0005})]
    out = spans.summary(served, t)
    assert out["batches"] == out["span_batches"] == 1
    assert out["select_ms"] == pytest.approx(4000)
    assert out["syncs_per_batch"] == 3
    assert out["engine_host_ms"] == pytest.approx(5000)
    assert out["batch_minus_ticket_ms"] == pytest.approx([-0.5, -0.5])
    assert out["share"]["select"] == pytest.approx(0.4)
    assert spans.summary(served, None) == {"batches": 1,
                                           "engine_ms": pytest.approx(10000.5)}
