"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and makes its queries and arrival times from
the seed.

A query is a plain dict (the harness turns it into the program's query
object; the reference reads the dict itself):

    {"entities": [text, ...],
     "frames": [[(subject, predicate text, object), ...], ...],
     "min_gaps": [gap between frame j and j + 1, ...],
     "top_k": k, "text_threshold": t, "predicate_top_m": m,
     "session": tenant index, "cls": class name}

where subject and object index ``entities``, and with ``"verify_budget"``
where the mix gives one. Every seed gets the same
number of queries of each class and of each ``top_k``, and the same
arrival times: the seed changes which descriptions and predicates are
asked for and in which order, not how much work there is or when.

A class fixes a query's shape: ``frames`` frames, each with one triple
``(a, r_j, b)`` over a distinct predicate ``r_j``; with ``shared_triple``
every frame also holds ``(c, s, b)`` for one more entity ``c`` and one
predicate ``s`` of its own (a condition that holds across the event, like
Example 2.1's "man with backpack near the bicycle"). All of a query's
gaps are one ``min_gap`` drawn from the class's range.

A class also says how its texts are chosen. By default a query is grounded
in one video (see :class:`Picker`). A class with ``zipf`` draws its
entity texts by a Zipf popularity of that exponent over every description,
ranked by its instances in the world, hot ones included, and its
predicates at random: what many analysts ask about, whether or not the
archive holds the event.

A mix says how its queries arrive in one of two ways, and a mix file
names exactly one of them:

* ``rate_per_s``: an open loop (:func:`schedule`), independent analysts
  whose arrival times do not wait for the system;
* ``clients``: a closed loop of that many clients (:func:`stream`, driven
  by ``run.closed_loop``), each submitting its next query when its
  previous one is answered, so that the system sets the pace. A query's due
  time is then its submission time, and latency keeps its meaning. Client
  c is session c, so a closed-loop mix gives no ``sessions``.

``verify_budget``, where a mix gives it, is every query's
``VMRQuery.verify_budget`` (0: the engine verifies every candidate row in
one pass; n > 0: the budgeted cascade). It only matters to a configuration
with a verifier; a mix without it leaves the query's default.

A mix file holds only the keys below; another key is refused, so a mix
cannot ask for something the generator does not do.
"""
from __future__ import annotations

import itertools
import json
from pathlib import Path
from typing import Dict, Iterator, List, Sequence

import numpy as np


TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


MIX_KEYS = {"rate_per_s", "clients", "verify_budget", "sessions",
            "text_threshold", "predicate_top_m", "top_k", "classes",
            "instances_per_k"}
CLASS_KEYS = {"name", "share", "frames", "shared_triple", "min_gap", "zipf"}


def load_mix(name: str) -> dict:
    mix = json.loads((TRAFFIC_DIR / f"{name}.json").read_text())
    unknown = set(mix) - MIX_KEYS
    unknown |= {k for c in mix["classes"] for k in set(c) - CLASS_KEYS}
    if unknown:
        raise ValueError(f"traffic mix {name!r}: keys {sorted(unknown)} are "
                         f"not implemented")
    if ("rate_per_s" in mix) == ("clients" in mix):
        raise ValueError(f"traffic mix {name!r}: give one of rate_per_s "
                         f"(open loop) and clients (closed loop)")
    if "clients" in mix and "sessions" in mix:
        raise ValueError(f"traffic mix {name!r}: sessions is an open loop's "
                         f"key; a closed loop's client c is session c")
    return mix


def _counts(shares: Sequence[float], n: int) -> List[int]:
    """Largest-remainder split of ``n`` by ``shares``."""
    raw = np.asarray(shares, float) / float(sum(shares)) * n
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out), kind="stable")[: n - out.sum()]:
        out[i] += 1
    return [int(x) for x in out]


class Picker:
    """Grounds queries in the world, the way an analyst asks about events
    that happened: a query's entities are the distinct descriptions of
    objects of one video (never entity 0, the person), and each frame's
    predicate is, where the video has one, that of a row of the pair at a
    frame at least the query's gap after the last one used. Up to
    ``GROUNDING_TRIES`` videos are tried for one in which every frame is
    grounded so.

    Only objects whose description has at most ``instances_per_k *
    top_k`` instances in the world are picked. The entity match keeps the
    ``top_k`` instances nearest the text, which for one description differ
    only by each instance's own noise: with a few times more instances than
    ``top_k`` the answer depends on which ones are kept, and so on the
    search's rounding, yet the queried video's own instances are kept
    often enough for answers to be found. (With far more instances, the
    kept ones are an arbitrary few and nearly every answer is empty.)"""

    def __init__(self, world, predicates: List[str], instances_per_k: int):
        self.world = world
        self.predicates = predicates
        self.per_k = instances_per_k
        self.count = np.bincount(world.desc_of.ravel(),
                                 minlength=len(world.texts))
        self.starts = np.searchsorted(world.rows[:, 0],
                                      np.arange(world.videos + 1))
        self._eligible = {}
        self._popular = {}

    def eligible(self, n: int, top_k: int) -> np.ndarray:
        key = (n, top_k)
        if key not in self._eligible:
            objs = self.world.desc_of[:, 1:]
            ok = self.count[objs] <= self.per_k * top_k
            distinct = np.array([len(set(row[m])) for row, m in
                                 zip(objs, ok)])
            self._eligible[key] = np.nonzero(distinct >= n)[0]
        return self._eligible[key]

    def entities(self, n: int, top_k: int, rng) -> tuple:
        """(video, entity ids) of ``n`` objects of distinct descriptions
        with few enough instances."""
        vids = self.eligible(n, top_k)
        if not len(vids):
            raise ValueError(f"no video holds {n} objects of distinct "
                             f"descriptions with at most "
                             f"{self.per_k * top_k} instances")
        v = int(rng.choice(vids))
        row = self.world.desc_of[v]
        by_desc = {}
        for e in range(1, len(row)):
            if self.count[row[e]] <= self.per_k * top_k:
                by_desc.setdefault(int(row[e]), e)
        ents = rng.choice(sorted(by_desc.values()), n, replace=False)
        return v, [int(e) for e in ents]

    def popular(self, n: int, s: float, rng) -> List[int]:
        """``n`` distinct descriptions drawn by Zipf(``s``) popularity over
        every description, ranked by its instances in the world."""
        if s not in self._popular:
            rank = np.argsort(-self.count, kind="stable")
            w = 1.0 / np.arange(1, rank.size + 1) ** s
            self._popular[s] = (rank, w / w.sum())
        rank, w = self._popular[s]
        return [int(d) for d in rng.choice(rank, n, replace=False, p=w)]

    def predicate(self, v: int, s: int, o: int, after: int, used, rng,
                  at: int = None) -> tuple:
        """(predicate index, frame, found) of the earliest row (v, f, s, .,
        o) with f >= ``after`` (or f == ``at``) and a predicate not in
        ``used``; a random unused predicate, ``after`` and False where the
        video has none."""
        rows = self.world.rows[self.starts[v]: self.starts[v + 1]]
        m = (rows[:, 2] == s) & (rows[:, 4] == o)
        m &= (rows[:, 1] >= after) if at is None else (rows[:, 1] == at)
        m &= ~np.isin(rows[:, 3], list(used))
        if m.any():
            # the earliest such frame leaves the most room for the rest
            idx = np.nonzero(m)[0]
            idx = idx[rows[idx, 1] == rows[idx, 1].min()]
            r = rows[rng.choice(idx)]
            return int(r[3]), int(r[1]), True
        free = [p for p in range(len(self.predicates)) if p not in used]
        return int(rng.choice(free)), after, False


# videos tried for a query whose every frame a row of the video grounds
GROUNDING_TRIES = 64


def make_query(mix: dict, cls: dict, top_k: int, pick: Picker,
               rng: np.random.Generator, gap: int = None) -> dict:
    shared = bool(cls.get("shared_triple", False))
    n_frames = int(cls["frames"])
    lo, hi = cls.get("min_gap", [1, 1])
    if gap is None:
        gap = int(rng.integers(lo, hi + 1))
    n_ents = 3 if shared else 2
    names = pick.predicates
    if "zipf" in cls:
        texts = [pick.world.texts[d]
                 for d in pick.popular(n_ents, float(cls["zipf"]), rng)]
        preds = [int(p) for p in rng.choice(len(names), n_frames + shared,
                                            replace=False)]
        return _query(mix, cls, top_k, gap, texts, [names[p] for p in preds])
    for _ in range(GROUNDING_TRIES):
        v, ents = pick.entities(n_ents, top_k, rng)
        used, frame, preds, first, grounded = set(), 0, [], 0, True
        for j in range(n_frames):
            p, f, ok = pick.predicate(v, ents[0], ents[1], frame, used, rng)
            used.add(p)
            preds.append(p)
            first = f if j == 0 else first
            frame, grounded = f + gap, grounded and ok
        if shared:
            p, _, ok = pick.predicate(v, ents[2], ents[1], 0, used, rng,
                                      at=first)
            preds.append(p)
            grounded = grounded and ok
        if grounded:
            break
    row = pick.world.desc_of[v]
    return _query(mix, cls, top_k, gap, [pick.world.texts[row[e]]
                                         for e in ents],
                  [names[p] for p in preds])


def _query(mix: dict, cls: dict, top_k: int, gap: int, texts: List[str],
           preds: List[str]) -> dict:
    """The query of one class over its entity texts and predicate texts
    (one per frame, then the shared triple's)."""
    n_frames = int(cls["frames"])
    frames = [[(0, preds[j], 1)] for j in range(n_frames)]
    if cls.get("shared_triple", False):
        frames[0].append((2, preds[-1], 1))
    q = {"entities": texts, "frames": frames,
         "min_gaps": [gap] * (n_frames - 1), "top_k": int(top_k),
         "text_threshold": float(mix["text_threshold"]),
         "predicate_top_m": int(mix["predicate_top_m"]),
         "cls": cls["name"]}
    if "verify_budget" in mix:
        q["verify_budget"] = int(mix["verify_budget"])
    return q


def _draws(mix: dict, n: int, rng: np.random.Generator) -> tuple:
    """(class index (n,), top_k (n,)) of ``n`` queries: the counts by the
    mix's shares, in an order that ``rng`` shuffles."""
    classes = mix["classes"]
    cls_idx = np.repeat(np.arange(len(classes)),
                        _counts([c["share"] for c in classes], n))
    ks, kshares = zip(*mix["top_k"])
    k_of = np.repeat(np.asarray(ks), _counts(kshares, n))
    rng.shuffle(cls_idx)
    rng.shuffle(k_of)
    return cls_idx, k_of


def schedule(mix: dict, seconds: float, seed: int, world,
             predicates: List[str]) -> List[dict]:
    """The window's queries, each with ``due`` (seconds after the window
    opens) and ``session``, sorted by ``due``. Open loop: a Poisson
    process of ``rate_per_s`` conditioned on its count, i.e. round(rate *
    seconds) arrival times drawn uniformly over the window, the same for
    every seed."""
    rng = np.random.default_rng([seed, 0x7AFF1C])
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    # the arrival times are the same for every seed: with a few tens of
    # queries in a window, which seed bunches them would otherwise decide
    # the latency more than the system does
    due = np.sort(np.random.default_rng(0xA221FA1).random(n) * seconds)
    cls_idx, k_of = _draws(mix, n, rng)
    pick = Picker(world, predicates, int(mix["instances_per_k"]))
    out = []
    for i in range(n):
        q = make_query(mix, mix["classes"][cls_idx[i]], int(k_of[i]), pick,
                       rng)
        q["due"] = float(due[i])
        q["session"] = i % int(mix["sessions"])
        out.append(q)
    return out


# queries over which a closed loop's classes and top_k follow their shares
STREAM_BLOCK = 20


def stream(mix: dict, seed: int, world,
           predicates: List[str]) -> Iterator[dict]:
    """A closed loop's queries, without end, in the order the clients take
    them: blocks of ``STREAM_BLOCK`` queries drawn as :func:`schedule`
    draws its window, each block's order the same for every seed, as an
    open loop's arrival times are; the seed draws the texts."""
    rng = np.random.default_rng([seed, 0x7AFF1C])
    order = np.random.default_rng(0xC105ED)
    pick = Picker(world, predicates, int(mix["instances_per_k"]))
    while True:
        for c, k in zip(*_draws(mix, STREAM_BLOCK, order)):
            yield make_query(mix, mix["classes"][c], int(k), pick, rng)


def warmup_batches(mix: dict, max_batch: int, world, predicates: List[str],
                   seed: int) -> Iterator[List[dict]]:
    """Batches that together reach every program shape the window can:
    for each batch size, every split of the batch over the mix's query
    shapes under each ``top_k``; then, for each shape and gap, every count
    of queries sharing that chain signature. The engine's programs are
    shaped by these counts and by the largest ``top_k`` in a batch, not by
    the texts asked for, so classes of one shape are warmed up as one."""
    rng = np.random.default_rng([seed, 0x3A7])
    shapes: Dict[tuple, dict] = {}
    for c in mix["classes"]:
        shapes.setdefault((int(c["frames"]),
                           bool(c.get("shared_triple", False))), c)
    pick = Picker(world, predicates, int(mix["instances_per_k"]))
    ks = [k for k, _ in mix["top_k"]]

    def one(cls, k, gap=None):
        return make_query(mix, cls, k, pick, rng, gap)

    reps = list(shapes.values())
    for b in range(1, max_batch + 1):
        for split in _compositions(b, len(reps)):
            for k in ks:
                yield [one(c, k) for c, m in zip(reps, split)
                       for _ in range(m)]
    gaps = {(frames, shared, gap)
            for c in mix["classes"]
            for frames, shared in [(int(c["frames"]),
                                    bool(c.get("shared_triple", False)))]
            for gap in range(c.get("min_gap", [1, 1])[0],
                             c.get("min_gap", [1, 1])[1] + 1)
            if frames >= 2}
    if len(gaps) < 2 and len(reps) == 1:
        return           # every such group already had each size above
    for frames, shared, gap in sorted(gaps):
        for b in range(1, max_batch + 1):
            yield [one(shapes[frames, shared], ks[0], gap)
                   for _ in range(b)]


def _compositions(n: int, parts: int) -> Iterator[Sequence[int]]:
    """Every way to write ``n`` as an ordered sum of ``parts`` counts."""
    for cuts in itertools.combinations(range(n + parts - 1), parts - 1):
        prev, out = -1, []
        for c in cuts + (n + parts - 1,):
            out.append(c - prev - 1)
            prev = c
        yield out


def describe(q: Dict) -> str:
    ents = q["entities"]
    fr = " ; ".join(", ".join(f"{ents[a]} {p} {ents[b]}" for a, p, b in f)
                    for f in q["frames"])
    return f"[{fr}] gaps={q['min_gaps']} k={q['top_k']}"
