"""The plain reference: what a query over the archive should answer.

Plain numpy over the world's own rows and embeddings, sharing no code with
the program. It follows the semantics of the program's query path:

1. entity match: for each entity text, the ``top_k`` entity rows by cosine
   score (ties to the lower row), kept where the score reaches
   ``text_threshold``;
2. predicate match: for each relationship text, the ``predicate_top_m``
   predicates by cosine score, kept where the score reaches
   ``text_threshold``; the best one is always kept;
3. triple filter: relationship rows whose (video, subject) and
   (video, object) are matched entities and whose predicate is matched;
4. conjoin: a frame spec holds at (video, frame) where every one of its
   triples has a row there;
5. temporal chain: frame spec j + 1 lands at least ``min_gap`` frames after
   a landing of frame spec j; ``end_frames[v, t]`` says the last frame spec
   can land at frame t of video v;
6. ranking: the ``min(top_k, videos)`` videos with most landings (ties to
   the lower video), those with at least one.

Where the configuration has a verifier, step 3 keeps only the rows it
accepted. The reference does not judge the verifier's answers here (the
harness compares a sample of its margins with a plain model apart): it
takes the verdicts the engine received, as the harness recorded them
(:func:`row_verdicts`). A row the verifier was asked about more than once
may have been answered differently each time (a verdict can change with
the batch it was computed in), so a row counts as accepted for the fewest
candidates where every verdict said yes, and for the most where any did;
a row it was never asked about counts as rejected for the fewest and
accepted for the most.

Scores are exact: float64 dot products of the stored float32 vectors. The
program computes them in float32, which rounds; where the k-th best score
or a threshold lies within the rounding bound ``score_tol`` of another
score, either choice is right. The reference then gives the answers of the
fewest and of the most candidates that rounding can admit; every step
after matching is monotone in its candidates, so a right answer's
``end_frames`` lie between the two, and its ranking is that of its own
``end_frames``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

# Largest |float32 score - exact score| the comparison allows. A D = 4096
# dot product of unit vectors in float32 rounds by about sqrt(D) * 2^-24
# (4e-6) at worst in practice; the program's search on the chip has read
# 3.6e-7 (PERF.md). One pass of bfloat16, the next precision down, rounds
# by about 2^-9 (2e-3).
SCORE_TOL = 4e-6


def topk_bounds(s: np.ndarray, k: int, thr: float, tol: float,
                keep_best: bool = False) -> Tuple[np.ndarray, np.ndarray]:
    """Rows surely kept, and rows possibly kept, by a top-``k`` then
    ``>= thr`` selection over float32 scores within ``tol`` of ``s``."""
    k = min(k, s.size)
    kth = np.partition(s, s.size - k)[s.size - k]
    sure_top = s > kth + 2 * tol
    maybe_top = s >= kth - 2 * tol
    lower = sure_top & (s >= thr + tol)
    upper = maybe_top & (s >= thr - tol)
    if keep_best:
        best = s.max()
        second = np.partition(s, s.size - 2)[s.size - 2] if s.size > 1 \
            else -np.inf
        if best > second + 2 * tol:
            lower |= s == best
        upper |= s >= best - 2 * tol
    return lower, upper


def select_topk(s: np.ndarray, k: int, thr: float,
                keep_best: bool = False) -> np.ndarray:
    """The top-``k`` rows of ``s`` (ties to the lower row) that reach
    ``thr``; with ``keep_best`` the best row is kept regardless."""
    top = np.argsort(-s, kind="stable")[: min(k, s.size)]
    keep = np.zeros(s.size, bool)
    keep[top[s[top] >= thr]] = True
    if keep_best:
        keep[top[0]] = True
    return keep


def shift_right(x: np.ndarray, n: int) -> np.ndarray:
    out = np.zeros_like(x)
    if n < x.shape[-1]:
        out[..., n:] = x[..., : x.shape[-1] - n]
    return out


def chain(frame_maps: List[np.ndarray], min_gaps: List[int]) -> np.ndarray:
    reach = frame_maps[0]
    for j in range(1, len(frame_maps)):
        seen = np.cumsum(reach, axis=-1) > 0
        reach = frame_maps[j] & shift_right(seen, min_gaps[j - 1])
    return reach


def rank(end_frames: np.ndarray, top_k: int) -> Tuple[List[int], List[int]]:
    score = end_frames.sum(axis=-1)
    k = min(top_k, score.shape[0])
    order = np.argsort(-score, kind="stable")[:k]
    keep = score[order] > 0
    return [int(v) for v in order[keep]], [int(x) for x in score[order][keep]]


@dataclass
class Verdict:
    ok: bool
    exact: bool          # no rounding ambiguity: one answer is right
    detail: str = ""


class Reference:
    """Answers over a world's rows. ``entity_scores`` maps an entity text
    to its exact (N,) scores over the entity rows (see
    :func:`exact_scores`); ``pred_scores`` maps a relationship text to its
    exact (P,) scores over the predicate labels. ``accepted``, where a
    verifier judged the rows, is the (lower, upper) pair of per-row masks
    of :func:`row_verdicts`."""

    def __init__(self, rows: np.ndarray, videos: int, frames: int,
                 entities_per_video: int,
                 entity_scores: Dict[str, np.ndarray],
                 pred_scores: Dict[str, np.ndarray],
                 score_tol: float = SCORE_TOL,
                 accepted: Optional[Tuple[np.ndarray, np.ndarray]] = None):
        self.videos, self.frames = videos, frames
        self.vid = rows[:, 0].astype(np.int64)
        self.fid = rows[:, 1].astype(np.int64)
        self.subj = self.vid * entities_per_video + rows[:, 2]
        self.obj = self.vid * entities_per_video + rows[:, 4]
        rl = rows[:, 3]
        self.by_pred = [np.nonzero(rl == p)[0]
                        for p in range(int(rl.max()) + 1)]
        self.entity_scores = entity_scores
        self.pred_scores = pred_scores
        self.tol = score_tol
        self.accepted = accepted

    def candidates(self, q: dict) -> Tuple[Dict, Dict]:
        """Per entity text and per relationship text: (lower, upper) masks."""
        ents = {t: topk_bounds(self.entity_scores[t], q["top_k"],
                               q["text_threshold"], self.tol)
                for t in q["entities"]}
        preds = {p: topk_bounds(self.pred_scores[p], q["predicate_top_m"],
                                q["text_threshold"], self.tol,
                                keep_best=True)
                 for f in q["frames"] for _, p, _ in f}
        return ents, preds

    def triple_rows(self, q: dict, triple: tuple, side: int, ents: Dict,
                    preds: Dict) -> np.ndarray:
        """Indices of the rows the triple filter selects for ``triple``
        (subject, relationship text, object), with the lower (``side`` 0)
        or upper (1) candidate sets, before any verdict."""
        a, p, b = triple
        sa = ents[q["entities"][a]][side]
        ob = ents[q["entities"][b]][side]
        out = []
        for pid in np.nonzero(preds[p][side])[0]:
            r = self.by_pred[pid] if pid < len(self.by_pred) \
                else np.zeros(0, np.int64)
            out.append(r[sa[self.subj[r]] & ob[self.obj[r]]])
        return np.concatenate(out) if out else np.zeros(0, np.int64)

    def candidate_rows(self, q: dict) -> np.ndarray:
        """Indices of every row the triple filter selects for some triple
        of ``q`` with the lower candidate sets: the rows a verifier that
        checks every candidate must have been asked about."""
        ents, preds = self.candidates(q)
        return np.unique(np.concatenate(
            [self.triple_rows(q, t, 0, ents, preds)
             for f in q["frames"] for t in f]))

    def end_frames(self, q: dict, side: int, ents: Dict, preds: Dict
                   ) -> np.ndarray:
        """(V, F) landings of the last frame spec, with the lower
        (``side`` 0) or upper (1) candidate sets."""
        cache: Dict[tuple, np.ndarray] = {}
        maps = []
        for f in q["frames"]:
            fmap = np.ones((self.videos, self.frames), bool)
            for t in map(tuple, f):
                if t not in cache:
                    r = self.triple_rows(q, t, side, ents, preds)
                    if self.accepted is not None:
                        r = r[self.accepted[side][r]]
                    bm = np.zeros((self.videos, self.frames), bool)
                    bm[self.vid[r], self.fid[r]] = True
                    cache[t] = bm
                fmap &= cache[t]
            maps.append(fmap)
        return chain(maps, q["min_gaps"])

    def answer(self, q: dict) -> Tuple[np.ndarray, np.ndarray]:
        ents, preds = self.candidates(q)
        lo = self.end_frames(q, 0, ents, preds)
        exact = self.accepted is None and all(
            (a == b).all() for a, b in ents.values()) and all(
            (a == b).all() for a, b in preds.values())
        hi = lo if exact else self.end_frames(q, 1, ents, preds)
        return lo, hi

    def answer_with(self, q: dict, entity_scores: Dict[str, np.ndarray],
                    pred_scores: Dict[str, np.ndarray]
                    ) -> Tuple[List[int], List[int], np.ndarray]:
        """The answer when matching ranks by the given scores as they are
        (no rounding allowance): the reference put in the program's place,
        as the control computes it in a lower precision."""
        thr = q["text_threshold"]
        ents = {t: (select_topk(entity_scores[t], q["top_k"], thr),) * 2
                for t in q["entities"]}
        preds = {p: (select_topk(pred_scores[p], q["predicate_top_m"], thr,
                                 keep_best=True),) * 2
                 for f in q["frames"] for _, p, _ in f}
        ef = self.end_frames(q, 0, ents, preds)
        segs, scores = rank(ef, q["top_k"])
        return segs, scores, ef

    def check(self, q: dict, segments: List[int], scores: List[int],
              end_frames: np.ndarray) -> Verdict:
        lo, hi = self.answer(q)
        ef = np.asarray(end_frames, bool)
        exact = lo is hi or bool((lo == hi).all())
        if ef.shape != lo.shape:
            return Verdict(False, exact, f"end_frames shape {ef.shape}")
        below = int((lo & ~ef).sum())
        above = int((ef & ~hi).sum())
        if below or above:
            def at(m):   # the first few (video, frame) pairs of a mask
                return [tuple(map(int, x)) for x in np.argwhere(m)[:3]]
            return Verdict(False, exact, f"end_frames: {below} landings "
                           f"missing {at(lo & ~ef)}, {above} beyond what "
                           f"may land {at(ef & ~hi)}")
        want = rank(ef, q["top_k"])
        if (list(segments), list(scores)) != want:
            return Verdict(False, exact, f"ranking {segments[:8]}/"
                           f"{scores[:8]} != {want[0][:8]}/{want[1][:8]}")
        return Verdict(True, exact)


def row_keys(*arrays: np.ndarray) -> List[np.ndarray]:
    """One int64 key per (vid, fid, sid, rl, oid) row of each array, equal
    where the rows are: the same radix for every array given."""
    dims = np.max([a.max(axis=0) for a in arrays if len(a)], axis=0) + 1
    return [np.ravel_multi_index(a.T.astype(np.int64), dims)
            if len(a) else np.zeros(0, np.int64) for a in arrays]


def row_verdicts(rows: np.ndarray, asked: np.ndarray, said: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per row of ``rows`` (M, 5): (lower, upper, seen) masks from the
    verifier's answers ``said`` (n,) to the rows ``asked`` (n, 5), one
    entry per time a row was asked about. ``lower``: asked, and every
    answer yes; ``upper``: never asked, or some answer yes."""
    if not len(asked):
        none = np.zeros(len(rows), bool)
        return none, ~none, none
    k_rows, k_asked = row_keys(rows, asked)
    uniq, inv = np.unique(k_asked, return_inverse=True)
    every = np.ones(len(uniq), bool)
    some = np.zeros(len(uniq), bool)
    np.logical_and.at(every, inv, said)
    np.logical_or.at(some, inv, said)
    pos = np.minimum(np.searchsorted(uniq, k_rows), len(uniq) - 1)
    seen = uniq[pos] == k_rows
    return seen & every[pos], ~seen | some[pos], seen


def exact_scores(bank_rows, queries: np.ndarray, approx: np.ndarray,
                 ks: np.ndarray, thresholds: np.ndarray,
                 margin: float = 1e-4) -> List[np.ndarray]:
    """Exact float64 scores of every row against each query vector.

    ``approx`` (N, T) are float32 scores good to well under ``margin``;
    rows within ``margin`` of a query's k-th best or of its threshold are
    rescored in float64 from ``bank_rows(idx) -> (len(idx), D) float32``,
    so every comparison that decides a selection is made exactly. The
    other rows keep their float32 score, which is more than ``margin`` from
    every boundary on the right side of it."""
    out = []
    for t in range(queries.shape[0]):
        s = approx[:, t].astype(np.float64)
        k = int(min(ks[t], s.size))
        kth = np.partition(s, s.size - k)[s.size - k]
        near = (np.abs(s - kth) <= margin) | (np.abs(s - thresholds[t])
                                              <= margin) | (s >= kth)
        idx = np.nonzero(near)[0]
        if idx.size:
            rows = np.asarray(bank_rows(idx), np.float64)
            s[idx] = rows @ queries[t].astype(np.float64)
        out.append(s)
    return out

