"""What the comparison that decides ``correct`` catches.

The control (the reference with its scores one precision lower, bfloat16
for the configuration's float32) put in the program's place has to come
out not correct by the harness's own verdict, at a size a test run holds:
full width D = 4096, fewer videos. Then a whole rehearsal run,
with the timed path broken underneath in each way a serving cell can be,
has to come out with ``correct`` false; unbroken it comes out true.
"""
import argparse

import numpy as np

from bench import run, traffic
from bench.control import control_run
from bench.reference import SCORE_TOL
from bench.world import build_world

SEED = 2 ** 31 + 77


def test_bfloat16_control_is_wrong():
    cfg = dict(run.load_cell("archive.interactive")["cfg"])
    cfg.update(videos=400, entity_capacity=4096,
               relationship_capacity=1 << 17)
    world = build_world(cfg, SEED)
    mix = traffic.load_mix("interactive")
    plan = traffic.schedule(dict(mix, rate_per_s=10), 10, SEED, world,
                            list(cfg["predicates"]))
    out = control_run(cfg, world, SEED, plan)
    assert out["queries"] == 100
    assert out["correct"] is False, out
    checks = out["checks"]
    assert checks["search_score_error"]["limit"] == SCORE_TOL
    assert checks["search_score_error"]["value"] > 10 * SCORE_TOL, out
    assert checks["search_rank_errors"]["value"] > 0, out


def rehearse(cell="archive.interactive"):
    args = argparse.Namespace(workload=cell, seed=SEED, seconds=2.0,
                              trace=0, rehearse=True, sweep="")
    out, rc = run.execute(args, warmup=False)
    assert rc == 1
    return out


def test_sound_rehearsal_is_correct():
    out = rehearse()
    assert out["correct"] and out["failed"] == 0, out
    assert out["checks"]["wrong_answers"]["value"] == 0


def _break(monkeypatch, alter):
    from repro.core.executor import LazyVLMEngine
    real = LazyVLMEngine.execute_batch

    def broken(self, plans):
        return alter(self, plans, real)
    monkeypatch.setattr(LazyVLMEngine, "execute_batch", broken)


def test_an_answer_altered_where_it_is_produced(monkeypatch):
    def alter(self, plans, real):
        res = real(self, plans)
        ef = np.array(res[0].end_frames)
        ef[0, -1] = ~ef[0, -1]
        res[0].end_frames = ef
        return res
    _break(monkeypatch, alter)
    out = rehearse()
    assert not out["correct"]
    assert out["checks"]["wrong_answers"]["value"] > 0


def test_half_of_the_queries_left_out(monkeypatch):
    """Every other query gets an empty answer instead of being run."""
    from repro.core.executor import QueryResult
    seen = [0]

    def alter(self, plans, real):
        res = real(self, plans)
        for i in range(len(res)):
            seen[0] += 1
            if seen[0] % 2 == 0:
                res[i] = QueryResult([], [], np.zeros_like(
                    np.asarray(res[i].end_frames)))
        return res
    _break(monkeypatch, alter)
    out = rehearse()
    assert not out["correct"]


def test_a_batch_that_never_comes(monkeypatch):
    calls = [0]

    def alter(self, plans, real):
        calls[0] += 1
        if calls[0] == 2:
            raise RuntimeError("batch lost")
        return real(self, plans)
    _break(monkeypatch, alter)
    out = rehearse()
    assert not out["correct"]
    assert out["failed"] > 0
