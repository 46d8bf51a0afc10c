"""The world and the entity banks are fixed by the seed."""
import json
from pathlib import Path

import numpy as np

from bench.embed import entity_bank, text_vectors
from bench.world import build_world, descriptions

CFG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                  / "ag-archive-d4096.json").read_text())
SMALL = dict(CFG, videos=60)
BIG_SEED = 2 ** 31 + 977


def test_same_seed_same_world_other_seed_other_world():
    a, b = build_world(SMALL, BIG_SEED), build_world(SMALL, BIG_SEED)
    c = build_world(SMALL, BIG_SEED + 1)
    assert np.array_equal(a.rows, b.rows)
    assert np.array_equal(a.desc_of, b.desc_of)
    assert not np.array_equal(a.rows[:100], c.rows[:100])


def test_world_shapes_follow_the_configuration():
    w = build_world(SMALL, 5)
    assert len(descriptions(CFG)) == 36 * 6 * 4 == len(w.texts)
    assert w.desc_of.shape == (60, 4)
    v, f, s, r, o = w.rows.T
    assert (s != o).all() and (v < 60).all() and (f < 24).all()
    assert set(np.unique(r)) <= set(range(7))
    assert (np.diff(v) >= 0).all()
    # entity 0 is a person in every video
    assert all(w.texts[d].split(" with ")[0].endswith("person")
               for d in w.desc_of[:, 0])
    # Action Genome has 7.3 relationships per frame; ours about 6.6
    assert 5 < len(w.rows) / (60 * 24) < 9


def test_entity_bank_is_fixed_by_seed_and_never_ties():
    texts = ["red cup", "blue door"]
    idx = np.array([0, 0, 1, 0])
    a = np.asarray(entity_bank(texts, idx, 256, BIG_SEED, 0.01))
    b = np.asarray(entity_bank(texts, idx, 256, BIG_SEED, 0.01))
    assert np.array_equal(a, b)
    q = text_vectors(["red cup"], 256, BIG_SEED % (1 << 62))[0]
    s = a @ q
    assert len(set(s[[0, 1, 3]].tolist())) == 3       # no exact ties
    assert s[[0, 1, 3]].min() > 0.8 and abs(s[2]) < 0.3
