"""Synthetic video world — ground-truth stand-in for the CV frontends.

The paper's preprocessing uses IETrans (scene graphs) + YOLOv8 (tracking).
Those are modality frontends, stubbed per the assignment; this module replaces
them with a procedural world that emits the *same store schema* plus ground
truth, so the pipeline's accuracy is actually verifiable:

  * objects with categories/attributes move along linear trajectories,
  * per-frame relationships derive from geometry (near / left of / ...),
  * the emitted scene graphs can be corrupted with detector-style noise
    (dropped and spurious triples) — the VLM-refinement stage then has real
    errors to fix, exercising the paper's core claim,
  * ``verify()`` answers ground truth for any (vid, fid, sid, rl, oid) —
    the oracle behind the mock verifier and the accuracy benchmarks.
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

PREDICATES = ["near", "left of", "right of", "above", "below", "holding", "on"]

CATEGORIES = ["man", "woman", "bicycle", "car", "bus", "motorcycle", "dog"]
COLORS = ["red", "blue", "green", "black", "white", "yellow"]
ACCESSORIES = ["backpack", "umbrella", "phone"]

NEAR_T = 0.18
SIDE_T = 0.35
HOLD_T = 0.06


@dataclass
class WorldObject:
    eid: int
    category: str
    color: str
    accessory: Optional[str]
    p0: np.ndarray      # (2,) start position in [0,1]^2
    vel: np.ndarray     # (2,) per-frame velocity

    @property
    def description(self) -> str:
        if self.category in ("man", "woman"):
            if self.accessory:
                return f"{self.category} with {self.accessory}"
            return f"{self.category} in {self.color}"
        return self.category

    def pos(self, frame: int) -> np.ndarray:
        return np.clip(self.p0 + self.vel * frame, 0.0, 1.0)


@dataclass
class WorldConfig:
    num_segments: int = 8
    frames_per_segment: int = 32
    objects_per_segment: int = 6
    seed: int = 0
    fps: float = 2.0
    # detector-noise knobs (scene-graph corruption fed to the stores)
    drop_prob: float = 0.0
    spurious_prob: float = 0.0


class SyntheticWorld:
    def __init__(self, cfg: WorldConfig):
        self.cfg = cfg
        self.segments: List[List[WorldObject]] = []
        rng = np.random.default_rng(cfg.seed)
        for v in range(cfg.num_segments):
            objs = []
            for e in range(cfg.objects_per_segment):
                cat = rng.choice(CATEGORIES)
                acc = (rng.choice(ACCESSORIES)
                       if cat in ("man", "woman") and rng.random() < 0.4
                       else None)
                objs.append(WorldObject(
                    eid=e,
                    category=str(cat),
                    color=str(rng.choice(COLORS)),
                    accessory=acc,
                    p0=rng.random(2),
                    vel=(rng.random(2) - 0.5) * (2.0 / cfg.frames_per_segment),
                ))
            self.segments.append(objs)
        self._rng = rng

    # -- geometry -> relationships -------------------------------------------
    @staticmethod
    def _holds(rel: str, pa: np.ndarray, pb: np.ndarray,
               a: WorldObject, b: WorldObject) -> bool:
        d = float(np.linalg.norm(pa - pb))
        dx, dy = float(pa[0] - pb[0]), float(pa[1] - pb[1])
        if rel == "near":
            return d < NEAR_T
        if rel == "left of":
            return dx < -0.02 and d < SIDE_T
        if rel == "right of":
            return dx > 0.02 and d < SIDE_T
        if rel == "above":
            return dy < -0.02 and d < SIDE_T
        if rel == "below":
            return dy > 0.02 and d < SIDE_T
        if rel == "holding":
            return (a.category in ("man", "woman")) and d < HOLD_T
        if rel == "on":
            return abs(dx) < 0.05 and 0 < dy < 0.12
        return False

    def scene_graph(self, vid: int, fid: int) -> List[Tuple[int, int, int]]:
        """Ground-truth (sid, rl, oid) triples for one frame."""
        objs = self.segments[vid]
        out = []
        for a in objs:
            pa = a.pos(fid)
            for b in objs:
                if a.eid == b.eid:
                    continue
                pb = b.pos(fid)
                for rl, rel in enumerate(PREDICATES):
                    if self._holds(rel, pa, pb, a, b):
                        out.append((a.eid, rl, b.eid))
        return out

    def noisy_scene_graph(self, vid: int, fid: int,
                          rng: np.random.Generator) -> List[Tuple[int, int, int]]:
        gt = self.scene_graph(vid, fid)
        out = [t for t in gt
               if self.cfg.drop_prob == 0 or rng.random() >= self.cfg.drop_prob]
        if self.cfg.spurious_prob > 0:
            objs = self.segments[vid]
            n_spur = rng.binomial(max(1, len(gt)), self.cfg.spurious_prob)
            gt_set = set(gt)
            for _ in range(n_spur):
                a, b = rng.choice(len(objs), 2, replace=False)
                rl = int(rng.integers(len(PREDICATES)))
                cand = (objs[a].eid, rl, objs[b].eid)
                if cand not in gt_set:
                    out.append(cand)
        return out

    # -- oracles ---------------------------------------------------------------
    def verify(self, vid: int, fid: int, sid: int, rl: int, oid: int) -> bool:
        objs = {o.eid: o for o in self.segments[vid]}
        if sid not in objs or oid not in objs or sid == oid:
            return False
        a, b = objs[sid], objs[oid]
        return self._holds(PREDICATES[rl], a.pos(fid), b.pos(fid), a, b)

    def verify_batch(self, rows: np.ndarray) -> np.ndarray:
        """rows: (M, 5) = (vid, fid, sid, rl, oid)."""
        return np.array([self.verify(*map(int, r)) for r in rows], bool)

    def descriptions(self, vid: int) -> List[str]:
        return [o.description for o in self.segments[vid]]

    # -- scripted events (deterministic demo/test fixtures) --------------------
    def stage_event_2_1(self, vid: int) -> None:
        """Overwrite segment ``vid`` with the paper's Example 2.1 event:
        a man with backpack stays near a bicycle while a man in red crosses
        from its left to its right over the segment (> 2 s at 2 fps)."""
        F = self.cfg.frames_per_segment
        self.segments[vid] = [
            WorldObject(0, "man", "blue", "backpack",
                        np.array([0.50, 0.50]), np.zeros(2)),
            WorldObject(1, "bicycle", "black", None,
                        np.array([0.55, 0.50]), np.zeros(2)),
            WorldObject(2, "man", "red", None,
                        np.array([0.30, 0.50]),
                        np.array([0.5 / (F - 1), 0.0])),
        ]

    # -- stub modality frontend -------------------------------------------------
    def frame_patches(self, vid: int, fid: int, num_patches: int,
                      dim: int) -> np.ndarray:
        """Deterministic 'vision encoder output' for a frame (stub frontend).

        Features are a function of the frame's object layout, so a trained
        verifier could in principle read the geometry back out. Seeds are
        CRC-32 digests of the frame and object fields, the same in every
        process (Python's ``hash`` of a string is salted per process).
        """
        rng = np.random.default_rng(zlib.crc32(f"{vid}:{fid}".encode()))
        base = rng.standard_normal((num_patches, dim)).astype(np.float32) * 0.02
        objs = self.segments[vid]
        side = max(1, int(np.sqrt(num_patches)))
        for o in objs:
            p = o.pos(fid)
            cell = min(num_patches - 1,
                       int(p[1] * side) * side + int(p[0] * side))
            orng = np.random.default_rng(zlib.crc32(
                f"{o.category}:{o.color}:{o.accessory}".encode()))
            base[cell] += orng.standard_normal(dim).astype(np.float32) * 0.2
        return base
