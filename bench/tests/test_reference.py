"""At a rehearsal size on the CPU the reference gives the engine's answers,
query by query, for each traffic mix: the engine driven as the harness
drives it (stores from the append API, the engine's batched path)."""
import numpy as np
import pytest

from bench import run, traffic
from bench.world import build_world

SEED = 2 ** 31 + 4242


def small_cfg(name):
    cfg = dict(run.load_cell(name)["cfg"])
    cfg.update(run.REHEARSAL)
    return cfg


@pytest.mark.parametrize("cell", ["archive.interactive", "archive.chains"])
def test_reference_equals_engine(cell):
    from bench.embed import BenchEmbedder, salt_of
    from repro.session import open_video_store
    spec = run.load_cell(cell)
    cfg = small_cfg(cell)
    world = build_world(cfg, SEED)
    stores = run.build_stores(cfg, world, SEED)
    assert [s.tier for s in stores.segments] == ["cold", "hot", "hot", "hot"]
    eng = cfg["engine"]
    engine = open_video_store(
        stores, BenchEmbedder(cfg["embedding_dim"], salt_of(SEED)),
        use_kernels=False, search_mode=eng["search_mode"]).engine
    mix = traffic.load_mix(spec["cell"]["traffic"])
    plan = traffic.schedule(dict(mix, rate_per_s=8), 4, SEED, world,
                            list(cfg["predicates"]))
    results = []
    for lo in range(0, len(plan), 8):
        results += engine.query_batch([run.to_query(q)
                                       for q in plan[lo: lo + 8]])
    ref = run.make_reference(cfg, world, SEED, plan)
    verdicts = [ref.check(q, r.segments, r.scores, r.end_frames)
                for q, r in zip(plan, results)]
    assert all(v.ok for v in verdicts), [v.detail for v in verdicts
                                         if not v.ok]
    assert sum(v.exact for v in verdicts) >= len(plan) - 2
    # the answers are not trivially empty
    assert sum(bool(r.segments) for r in results) >= len(plan) // 3
    # and the reference's own answer is the one checked
    for q, r in zip(plan[:5], results[:5]):
        lo_ef, hi_ef = ref.answer(q)
        assert (lo_ef <= np.asarray(r.end_frames)).all()
