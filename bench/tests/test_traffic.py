"""The traffic generator: what a mix file may say, and what it makes."""
import json

import numpy as np
import pytest

from bench import run, traffic
from bench.world import build_world

SEED = 2 ** 31 + 313


def world_and_mix(name="interactive"):
    cfg = dict(run.load_cell("archive.interactive")["cfg"], videos=600)
    return cfg, build_world(cfg, SEED), traffic.load_mix(name)


def test_popular_texts_follow_the_worlds_popularity():
    cfg, world, mix = world_and_mix()
    plan = traffic.schedule(dict(mix, rate_per_s=40), 10, SEED, world,
                            list(cfg["predicates"]))
    count = np.bincount(world.desc_of.ravel(), minlength=len(world.texts))
    hot = {world.texts[d] for d in np.argsort(-count, kind="stable")[:20]}
    popular = [q for q in plan if q["cls"] == "popular"]
    assert len(popular) == 100                    # a quarter of 400
    asked = [t for q in popular for t in q["entities"]]
    # Zipf(1.1) over 864 ranks puts about half of the draws on the top 20
    assert sum(t in hot for t in asked) > len(asked) // 3
    assert all(len(set(q["entities"])) == 2 for q in popular)


@pytest.mark.parametrize("seed", [SEED, SEED + 1])
def test_every_seed_gets_the_same_work(seed):
    cfg, world, mix = world_and_mix()
    plan = traffic.schedule(mix, 20, seed, world, list(cfg["predicates"]))
    base = traffic.schedule(mix, 20, 7, world, list(cfg["predicates"]))
    assert [q["due"] for q in plan] == [q["due"] for q in base]
    for key in ("cls", "top_k"):
        assert sorted(q[key] for q in plan) == sorted(q[key] for q in base)


def test_classes_of_one_shape_warm_up_as_one():
    cfg, world, mix = world_and_mix()
    batches = list(traffic.warmup_batches(mix, run.MAX_BATCH, world,
                                          list(cfg["predicates"]), SEED))
    # batch sizes 1..8 under each of the two top_k
    assert sorted((len(b), b[0]["top_k"]) for b in batches) == sorted(
        (n, k) for n in range(1, 9) for k in (16, 64))


def test_a_mix_key_the_generator_does_not_do_is_refused(tmp_path,
                                                        monkeypatch):
    mix = dict(traffic.load_mix("interactive"), loop="closed")
    (tmp_path / "closed.json").write_text(json.dumps(mix))
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", tmp_path)
    with pytest.raises(ValueError, match="loop"):
        traffic.load_mix("closed")


def test_a_configuration_key_the_harness_does_not_do_is_refused(
        tmp_path, monkeypatch):
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cfg = dict(run.load_cell("archive.interactive")["cfg"])
    cfg["engine"] = dict(cfg["engine"], verifier="qwen25_vl_7b")
    (tmp_path / "bench").mkdir()
    (tmp_path / "bench" / "cfg.json").write_text(json.dumps(cfg))
    spec["configs"][0]["file"] = "bench/cfg.json"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(run, "ROOT", tmp_path)
    with pytest.raises(SystemExit, match="verifier"):
        run.load_cell("archive.interactive")
