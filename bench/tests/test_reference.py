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


# interactive is the traffic of a cell left out of BENCHMARK.json until
# lax.top_k keeps ties in index order on the TPU; its mix is still checked
@pytest.mark.parametrize("mix_name", ["interactive", "chains"])
def test_reference_equals_engine(mix_name):
    from bench.embed import BenchEmbedder, salt_of
    from repro.session import open_video_store
    cfg = small_cfg("archive.chains")
    world = build_world(cfg, SEED)
    stores = run.build_stores(cfg, world, SEED)
    assert [s.tier for s in stores.segments] == ["cold", "hot", "hot", "hot"]
    eng = cfg["engine"]
    engine = open_video_store(
        stores, BenchEmbedder(cfg["embedding_dim"], salt_of(SEED)),
        use_kernels=False, search_mode=eng["search_mode"]).engine
    mix = traffic.load_mix(mix_name)
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
