"""Action-Genome-scale synthetic world, generated in bulk from a seed.

A vectorised copy of the semantics of ``video/synth.py`` (object layout,
linear trajectories, the geometric predicates and the detector's spurious
triples), written for archives of thousands of videos: numpy over whole
(video, frame, subject, object) arrays instead of Python loops, so a world
of 9,848 videos is made in a few seconds. Nothing here imports the program.

Entity ``e`` of video ``v`` is global entity ``v * entities_per_video + e``,
which is also its row in the entity store (the harness appends entities in
that order). Entity 0 of every video is a person; the others are objects.
Each entity's description is drawn from a Zipf popularity over the
configuration's descriptions.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np

NEAR_T = 0.18
SIDE_T = 0.35
HOLD_T = 0.06


def descriptions(cfg: dict) -> List[str]:
    """Every description text, in the configuration's popularity order:
    class-major, then colour, then accessory state (none first)."""
    out = []
    for cls in cfg["classes"]:
        for color in cfg["colors"]:
            out.append(f"{color} {cls}")
            out.extend(f"{color} {cls} with {acc}"
                       for acc in cfg["accessories"])
    return out


def person_descriptions(cfg: dict) -> np.ndarray:
    """Indices (into :func:`descriptions`) of the person class."""
    names = descriptions(cfg)
    return np.array([i for i, d in enumerate(names)
                     if d.split(" with ")[0].endswith(" person")], np.int64)


def zipf_weights(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** s
    return w / w.sum()


@dataclass
class World:
    desc_of: np.ndarray      # (V, E) int: description index of each entity
    rows: np.ndarray         # (M, 5) int32 (vid, fid, sid, rl, oid), vid-major
    texts: List[str]         # description texts
    frames: int

    @property
    def videos(self) -> int:
        return self.desc_of.shape[0]

    @property
    def entities_per_video(self) -> int:
        return self.desc_of.shape[1]


def _relations(pos: np.ndarray, person: np.ndarray) -> np.ndarray:
    """(V, F, E, E, P) bool: which predicate holds from subject a to object
    b, by ``SyntheticWorld._holds``."""
    d = pos[:, :, :, None, :] - pos[:, :, None, :, :]       # a - b
    dx, dy = d[..., 0], d[..., 1]
    dist = np.sqrt(dx * dx + dy * dy)
    side = dist < SIDE_T
    holds = np.stack([
        dist < NEAR_T,                                        # near
        (dx < -0.02) & side,                                  # left of
        (dx > 0.02) & side,                                   # right of
        (dy < -0.02) & side,                                  # above
        (dy > 0.02) & side,                                   # below
        person[:, None, :, None] & (dist < HOLD_T),           # holding
        (np.abs(dx) < 0.05) & (dy > 0) & (dy < 0.12),         # on
    ], axis=-1)
    e = pos.shape[2]
    return holds & ~np.eye(e, dtype=bool)[None, None, :, :, None]


def build_world(cfg: dict, seed: int) -> World:
    """The world of ``cfg`` for ``seed``: the same seed gives the same
    world, bit for bit."""
    rng = np.random.default_rng([seed, 0x5EED])
    V, F = cfg["videos"], cfg["frames_per_video"]
    E = cfg["entities_per_video"]
    texts = descriptions(cfg)
    n_pred = len(cfg["predicates"])
    persons = person_descriptions(cfg)
    objects = np.setdiff1d(np.arange(len(texts)), persons)
    s = cfg["description_zipf"]
    desc_of = np.empty((V, E), np.int64)
    desc_of[:, 0] = rng.choice(persons, V, p=zipf_weights(len(persons), s))
    desc_of[:, 1:] = rng.choice(objects, (V, E - 1),
                                p=zipf_weights(len(objects), s))
    p0 = rng.random((V, E, 2))
    vel = (rng.random((V, E, 2)) - 0.5) * (2.0 / F)
    f = np.arange(F)
    pos = np.clip(p0[:, None] + vel[:, None] * f[None, :, None, None], 0, 1)
    person = np.zeros((V, E), bool)
    person[:, 0] = True
    truth = _relations(pos, person)                            # V,F,E,E,P
    # detector noise: per frame, Binomial(max(1, #true), p) spurious
    # triples over random (subject != object, predicate), kept when not
    # already true (synth.noisy_scene_graph)
    n_true = truth.sum(axis=(2, 3, 4))                         # V,F
    n_spur = rng.binomial(np.maximum(1, n_true), cfg["spurious_prob"])
    tot = int(n_spur.sum())
    sv, sf = np.nonzero(n_spur)
    rep = n_spur[sv, sf]
    sv, sf = np.repeat(sv, rep), np.repeat(sf, rep)
    a = rng.integers(0, E, tot)
    b = (a + rng.integers(1, E, tot)) % E                      # b != a
    rl = rng.integers(0, n_pred, tot)
    fresh = ~truth[sv, sf, a, b, rl]
    tv, tf, ta, tb, tr = np.nonzero(truth)
    rows = np.concatenate([
        np.stack([tv, tf, ta, tr, tb], axis=1),
        np.stack([sv, sf, a, rl, b], axis=1)[fresh]]).astype(np.int32)
    order = np.lexsort((rows[:, 1], rows[:, 0]))               # vid, fid
    return World(desc_of=desc_of, rows=rows[order], texts=texts, frames=F)
