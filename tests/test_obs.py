"""Program spans (``repro.obs``) on the profiler's clock.

One coalesced batch of three queries is served through ``ServingRuntime``
under ``jax.profiler`` and its trace is read back with the benchmark's
loader: the spans nest tick ⊃ execute ⊃ engine batch ⊃ its stages in
order, every device→host transfer is one ``lazyvlm.sync`` span,
``stage_seconds`` is the stages' span time, and a traced run answers as
an untraced one does.
"""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import spans as bench_spans  # noqa: E402
from bench import trace as bench_trace  # noqa: E402
from repro import obs  # noqa: E402
from repro.core import LazyVLMEngine, executor  # noqa: E402
from repro.semantic import OracleEmbedder  # noqa: E402
from repro.serving import BatchBudget, ServingRuntime  # noqa: E402
from repro.video import (SyntheticWorld, WorldConfig, ingest,  # noqa: E402
                         overlapping_queries)

P = obs.PREFIX
STAGE_KEYS = {"search": "entity_match", "select": "symbolic",
              "verify": "refine", "temporal": "temporal"}


@pytest.fixture(scope="module")
def engine_and_queries():
    world = SyntheticWorld(WorldConfig(num_segments=4, frames_per_segment=8,
                                       objects_per_segment=4, seed=5))
    emb = OracleEmbedder(dim=32)
    engine = LazyVLMEngine(ingest(world, emb), emb)
    return engine, overlapping_queries(world)[5:8]


def _serve(engine, queries):
    rt = ServingRuntime(engine, budget=BatchBudget(max_queries=8))
    tickets = [rt.submit(q, session=f"user{i}")
               for i, q in enumerate(queries)]
    assert rt.tick() == len(queries)
    assert all(t.done and t.error is None for t in tickets)
    return tickets


@pytest.fixture(scope="module")
def traced(engine_and_queries, tmp_path_factory):
    """(untraced tickets, traced tickets, trace, host transfers counted in
    the traced run)."""
    engine, queries = engine_and_queries
    plain = _serve(engine, queries)          # also compiles every program
    calls = [0]
    real = executor._to_host

    def spy(x):
        calls[0] += 1
        return real(x)
    d = tmp_path_factory.mktemp("trace")
    executor._to_host = spy
    try:
        jax.profiler.start_trace(str(d))
        try:
            tickets = _serve(engine, queries)
        finally:
            jax.profiler.stop_trace()
    finally:
        executor._to_host = real
    trace = bench_spans.load(bench_trace.find_xplane(str(d)))
    return plain, tickets, trace, calls[0]


def _program(trace, name=None):
    return sorted((e for e in trace.host if e.name.startswith(P)
                   and (name is None or e.name == P + name)),
                  key=lambda e: e.start)


def _within(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


def test_spans_nest_tick_execute_batch_stages_in_order(traced):
    _, tickets, trace, _ = traced
    [tick] = _program(trace, "runtime.tick")
    [admit] = _program(trace, "runtime.admit")
    [execute] = _program(trace, "runtime.execute")
    [batch] = _program(trace, "engine.batch")
    assert _within(admit, tick) and _within(execute, tick)
    assert admit.end <= execute.start
    assert _within(batch, execute)
    submits = _program(trace, "runtime.submit")
    assert len(submits) == len(tickets)
    assert all(s.end <= tick.start for s in submits)
    stages = [e.name[len(P + "engine."):] for e in _program(trace)
              if e.name.startswith(P + "engine.") and e is not batch]
    assert all(_within(e, batch) for e in _program(trace)
               if e.name.startswith(P + "engine."))
    # the logical plans (query_batch) and the physical ones (execute_batch)
    # are two plan spans
    assert stages == ["plan", "plan", "search", "select", "verify",
                      "temporal", "results"]
    assert stages[1:] == list(bench_spans.STAGES)
    assert execute.meta == {"batch": 0, "qids": " ".join(
        str(t.qid) for t in tickets)}


def test_one_sync_span_per_host_transfer(traced):
    _, _, trace, transfers = traced
    syncs = _program(trace, "sync")
    assert transfers > 0 and len(syncs) == transfers
    assert all(s.meta["bytes"] >= 0 for s in syncs)
    [b] = bench_spans.batches(trace)
    assert 0 < b.syncs <= len(syncs)
    assert b.sync_bytes > 0


def test_stage_seconds_are_the_stage_spans(traced):
    _, tickets, trace, _ = traced
    [batch] = bench_spans.batches(trace)
    for t in tickets:
        got = t.result.stats.stage_seconds
        assert set(got) == set(STAGE_KEYS.values())
        for stage, key in STAGE_KEYS.items():
            assert got[key] == pytest.approx(batch.stages[stage], abs=1e-3)
    assert batch.covered >= 0.95


def test_traced_results_equal_untraced(traced):
    plain, tickets, _, _ = traced
    assert len(plain) == len(tickets)
    for a, b in zip(plain, tickets):
        ra, rb = a.result, b.result
        assert ra.segments == rb.segments and ra.scores == rb.scores
        assert np.array_equal(ra.end_frames, rb.end_frames)
        assert ra.sql == rb.sql


def test_single_query_path_fills_per_operator_buckets(engine_and_queries):
    engine, queries = engine_and_queries
    res = engine.query(queries[0])
    stages = {op.stage for op in engine.physical_for(
        engine.plan_for(queries[0])).ops}
    assert set(res.stats.stage_seconds) == stages
    assert all(v >= 0 for v in res.stats.stage_seconds.values())


def test_span_times_its_body_and_lets_errors_through():
    with obs.span("test.outer", n=1) as s:
        x = sum(range(1000))
    assert x == 499500 and s.seconds > 0
    with pytest.raises(ValueError):
        with obs.span("test.raises") as s:
            raise ValueError("through")
    assert s.seconds >= 0
