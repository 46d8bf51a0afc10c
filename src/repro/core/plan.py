"""Logical plan IR — the paper's pipeline (Section 2.3) as an explicit,
inspectable, cacheable artifact.

``compile_plan`` lowers a ``VMRQuery`` against a ``VideoStores`` instance
into a tree of typed plan nodes:

    Plan
    ├─ EntityMatch      batched vector top-k over the Entity Store
    ├─ PredicateMatch   relationship texts vs the closed predicate vocab
    ├─ TripleSelect     one fused conjunctive selection for ALL triples
    ├─ VlmVerify        lazy VLM refinement of surviving rows
    ├─ ConjoinFrames    per-frame AND of triple bitmaps
    └─ TemporalChain    chain DP over query frames

Compilation runs the optimizer passes that previously lived as ad-hoc logic
inside the executor:

  * **cross-frame triple dedupe** — a triple appearing in several frame
    specs becomes ONE ``TripleSelect`` row; frames reference triples by
    index.
  * **shared-entity embed reuse** — entities (and relationships) with
    identical description text share one embedding row; the node keeps an
    entity→row map instead of re-embedding duplicates.
  * **static capacity/bucket selection** — top-k/top-m are clamped against
    store capacities at compile time and the fused selection's row count is
    padded to a power-of-two bucket, so the jitted programs are compiled
    once per bucket tier and reused across queries of different shapes.

Plan nodes are frozen dataclasses of primitives — hashable and comparable —
so structurally identical queries compile to *equal* plans and a
``PlanCache`` can skip compilation entirely (the cache powers
``Session.explain``'s cached flag and the warm-vs-cold numbers in
``benchmarks/multi_query.py``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from repro.core import temporal as temporal_lib
from repro.core.query import Triple, VMRQuery
from repro.kernels.topk_similarity import K_PAD


def pow2_bucket(n: int, minimum: int = 4) -> int:
    """Pad a batch-dependent dimension to a power-of-two bucket so fused
    programs are compiled once per bucket tier, not once per shape. Padding
    slots carry all-False validity masks and select nothing."""
    b = minimum
    while b < n:
        b *= 2
    return b


def predicted_search_bytes(mode: str, capacity: int, dim: int,
                           n_texts: int, k: int) -> int:
    """Plan-time HBM-traffic model of ONE entity-search launch.

    fp32 brute force reads the whole fp32 bank; the int8 two-phase path
    reads the int8 codes + per-row scale/err and gathers only k′ candidate
    fp32 rows per query for the exact rescore (k′ = min(4k, 128), the
    kernel's overfetch — see ``repro.kernels.topk_similarity_i8``); the
    int4 cold-tier path reads nibble-packed codes (dim/2 bytes per row,
    ~0.125× the fp32 scan) with a wider k′ = min(8k, 128) overfetch. A
    top-k wider than the kernels' 128 scans fp32 in every mode
    (``repro.semantic.search.range_mode``).
    """
    from repro.semantic.search import range_mode
    mode = range_mode(mode, k)
    out = n_texts * k * 8                        # (scores, idx) results
    if mode == "int8":
        kprime = min(4 * k, 128)
        return (capacity * (dim + 8)             # int8 codes + scale + err
                + n_texts * kprime * dim * 4     # phase-2 fp32 gather
                + out)
    if mode == "int4":
        kprime = min(8 * k, 128)
        return (capacity * ((dim + 1) // 2 + 8)  # packed nibbles + scale/err
                + n_texts * kprime * dim * 4     # phase-2 fp32 gather
                + out)
    return capacity * dim * 4 + out


def predicted_search_bytes_tiered(mode: str, stores, dim: int,
                                  n_texts: int, k: int) -> int:
    """Tier-aware variant of :func:`predicted_search_bytes` for segmented
    stores: each segment range contributes its own tier's scan bytes —
    cold ranges read packed int4 (~0.125× the fp32 rows) and pay their
    own phase-2 gather — so the model prices exactly what the per-range
    dispatch will launch. Stores without a cold segment fall back to the
    uniform model (one launch, one gather), keeping estimates bit-stable
    for everything that existed before tiering."""
    segs = tuple(getattr(stores, "segments", ()))
    tiers = ()
    if segs:
        from repro.core.stores import entity_segment_tiers
        tiers = entity_segment_tiers(stores)
    if "cold" not in tiers:
        return predicted_search_bytes(mode, stores.entities.capacity, dim,
                                      n_texts, k)
    from repro.core.stores import entity_search_bounds
    from repro.semantic.search import range_mode
    total = n_texts * k * 8                      # (scores, idx) results
    for (start, stop), tier in zip(entity_search_bounds(stores), tiers):
        cap = stop - start
        m = range_mode("int4" if tier == "cold" else mode, min(k, cap))
        if m == "int8":
            total += (cap * (dim + 8)
                      + n_texts * min(4 * k, 128) * dim * 4)
        elif m == "int4":
            total += (cap * ((dim + 1) // 2 + 8)
                      + n_texts * min(8 * k, 128) * dim * 4)
        else:
            total += cap * dim * 4
    return total


# ---------------------------------------------------------------------------
# plan nodes
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class EntityMatch:
    """Top-k similarity search of entity descriptions over the Entity Store.

    ``texts`` are the deduped embedding inputs; ``rows[i]`` maps entity i
    (declaration order, named ``names[i]``) to its row in ``texts`` — the
    shared-entity embed-reuse pass.

    ``search_mode`` is the engine's scan precision (``"fp32"`` brute force
    or ``"int8"`` two-phase with exact rescore) and ``predicted_bytes`` the
    plan-time model of HBM bytes the search launches will move — both are
    EXPLAIN artifacts (``Session.explain``). ``fp32_ranges`` counts the
    search ranges whose top-``min(k, rows)`` is wider than the kernels'
    128 columns: those scan fp32 through the jnp reference whatever the
    mode or tier (``repro.semantic.search.range_mode``).
    """

    names: Tuple[str, ...]
    texts: Tuple[str, ...]
    rows: Tuple[int, ...]
    k: int                      # capacity-clamped top-k (static)
    text_threshold: float
    image_search: bool
    image_threshold: float
    search_mode: str = "fp32"
    predicted_bytes: int = 0    # modeled HBM traffic of the search launches
    fp32_ranges: int = 0        # ranges too wide for the top-k kernels

    @property
    def width(self) -> int:
        """Candidate columns per entity after text/image union."""
        return self.k * (2 if self.image_search else 1)

    def describe(self) -> List[str]:
        shared = len(self.names) - len(self.texts)
        head = (f"EntityMatch k={self.k} threshold={self.text_threshold:g}"
                + (f" +image(threshold={self.image_threshold:g})"
                   if self.image_search else "")
                + (f"  [{shared} shared embed row(s)]" if shared else ""))
        out = [head,
               f"  search_mode={self.search_mode} "
               f"predicted_bytes={self.predicted_bytes:,}"]
        if self.fp32_ranges:
            out.append(f"  {self.fp32_ranges} range(s) scan fp32 in jnp: "
                       f"top-k over {K_PAD}, the kernels' width")
        for name, row in zip(self.names, self.rows):
            out.append(f"  {name} ~ {self.texts[row]!r}")
        return out


@dataclass(frozen=True)
class PredicateMatch:
    """Top-m match of relationship texts against the predicate vocab."""

    names: Tuple[str, ...]
    texts: Tuple[str, ...]
    rows: Tuple[int, ...]
    m: int                      # vocab-clamped top-m (static)
    threshold: float

    def describe(self) -> List[str]:
        out = [f"PredicateMatch m={self.m} threshold={self.threshold:g}"]
        for name, row in zip(self.names, self.rows):
            out.append(f"  {name} ~ {self.texts[row]!r}")
        return out


@dataclass(frozen=True)
class TripleSelect:
    """One fused conjunctive selection for every (cross-frame deduped)
    triple. ``subj_row``/``obj_row`` index into ``EntityMatch.texts``'
    candidate rows and ``pred_row`` into ``PredicateMatch.texts``' (the
    embed-reuse maps are already applied at compile time); ``bucket`` is
    the power-of-two padded row count of the fused launch."""

    triples: Tuple[Triple, ...]
    subj_row: Tuple[int, ...]
    obj_row: Tuple[int, ...]
    pred_row: Tuple[int, ...]
    bucket: int

    def describe(self) -> List[str]:
        out = [f"TripleSelect triples={len(self.triples)} "
               f"bucket={self.bucket}"]
        for i, t in enumerate(self.triples):
            out.append(f"  t{i}: ({t.subject} {t.predicate} {t.object})")
        return out


@dataclass(frozen=True)
class VlmVerify:
    """Lazy VLM refinement of rows surviving the symbolic selection,
    deduped by row content.

    ``budget == 0`` verifies every candidate in one pass; ``budget > 0``
    lowers to the physical layer's budgeted cascade — ``budget`` rows per
    round in descending semantic-score order with certificate-backed early
    exit (results stay exact, see ``repro.core.physical.ops``)."""

    enabled: bool
    budget: int = 0

    def describe(self) -> List[str]:
        if not self.enabled:
            return ["VlmVerify (disabled: symbolic stage trusted)"]
        mode = (f"(cascade, budget={self.budget}/round)" if self.budget > 0
                else "(content-deduped rows)")
        return [f"VlmVerify {mode}"]


@dataclass(frozen=True)
class ConjoinFrames:
    """Per query frame: AND of its triples' presence bitmaps (indices into
    ``TripleSelect.triples``). ``idx``/``pad`` are the gather matrices for
    the fused conjunction launch, padded to a power-of-two column count —
    pad slots (True) act as identity under the AND — so execution only
    converts them to device arrays."""

    frames: Tuple[Tuple[int, ...], ...]
    idx: Tuple[Tuple[int, ...], ...]
    pad: Tuple[Tuple[bool, ...], ...]

    def describe(self) -> List[str]:
        out = ["ConjoinFrames"]
        for j, idxs in enumerate(self.frames):
            expr = " & ".join(f"t{i}" for i in idxs) or "TRUE"
            out.append(f"  f{j} <- {expr}")
        return out


@dataclass(frozen=True)
class TemporalChain:
    """Chain DP over consecutive query frames. ``gaps[j]`` is the
    (min_gap, max_gap) window between frames j and j+1 (the normalized
    constraint form); ``top_k`` is the segment-count-clamped ranking k."""

    gaps: Tuple[Tuple[int, Optional[int]], ...]
    top_k: int

    def describe(self) -> List[str]:
        out = [f"TemporalChain steps={len(self.gaps)} top_k={self.top_k}"]
        for j, (lo, hi) in enumerate(self.gaps):
            win = f">= {lo}" if hi is None else f"in [{lo}, {hi}]"
            out.append(f"  f{j + 1} - f{j} {win}")
        return out


@dataclass(frozen=True)
class Plan:
    """A compiled, executable VMR query plan (see module docstring)."""

    entity_match: EntityMatch
    predicate_match: PredicateMatch
    triple_select: TripleSelect
    verify: VlmVerify
    conjoin: ConjoinFrames
    temporal: TemporalChain
    num_segments: int
    frames_per_segment: int

    # -- introspection ------------------------------------------------------
    def chain_signature(self) -> Tuple:
        """Queries with equal signatures share one stacked temporal DP."""
        return (len(self.conjoin.frames), self.temporal.gaps)

    def predicted_launches(self) -> Dict[str, int]:
        """Static per-stage count of device program launches."""
        return {
            "entity_topk": 2 if self.entity_match.image_search else 1,
            "predicate_match": 2,             # einsum + top-k
            "triple_select": 1,
            "bitmaps": 1,
            "conjoin": 1,
            "temporal_chain": max(0, len(self.conjoin.frames) - 1),
            "rank": 1,
        }

    def total_launches(self) -> int:
        return sum(self.predicted_launches().values())

    def sql_template(self, i: int) -> str:
        """Plan-time SQL for triple ``i``: candidate sets are symbolic
        (they bind to actual (vid, eid) pairs at execution)."""
        em, pm, ts = self.entity_match, self.predicate_match, \
            self.triple_select
        t = ts.triples[i]
        subj = em.texts[ts.subj_row[i]]
        obj = em.texts[ts.obj_row[i]]
        pred = pm.texts[ts.pred_row[i]]
        k, m = em.width, pm.m
        return (
            f"SELECT vid, fid FROM relationships\n"
            f"  WHERE (vid, sid) IN (top{k}[{subj!r}])\n"
            f"    AND (vid, oid) IN (top{k}[{obj!r}])\n"
            f"    AND rl IN (top{m}[{pred!r}])  -- triple {i} "
            f"({t.subject} {t.predicate} {t.object})")

    def sql_templates(self) -> List[str]:
        return [self.sql_template(i)
                for i in range(len(self.triple_select.triples))]

    def render_tree(self) -> str:
        """Indented plan tree (EXPLAIN's main artifact)."""
        nodes = [self.entity_match, self.predicate_match, self.triple_select,
                 self.verify, self.conjoin, self.temporal]
        lines = [f"Plan  ({self.num_segments} segments x "
                 f"{self.frames_per_segment} frames, "
                 f"{self.total_launches()} predicted launches)"]
        for n, node in enumerate(nodes):
            head, *rest = node.describe()
            last = n == len(nodes) - 1
            lines.append(("└─ " if last else "├─ ") + head)
            lines += [("   " if last else "│  ") + r for r in rest]
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# compilation
# ---------------------------------------------------------------------------
def _dedupe_texts(items) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
    """Shared-embed pass: unique texts in first-occurrence order plus a
    per-item row map."""
    texts: List[str] = []
    row_of: Dict[str, int] = {}
    rows: List[int] = []
    for it in items:
        if it.text not in row_of:
            row_of[it.text] = len(texts)
            texts.append(it.text)
        rows.append(row_of[it.text])
    return tuple(texts), tuple(rows)


def compile_plan(query: VMRQuery, stores, *, verify: bool,
                 search_mode: str = "fp32") -> Plan:
    """Lower ``query`` to a :class:`Plan` against ``stores``' static shape.

    ``search_mode`` selects the entity-search precision the executing
    engine will use (it is part of the plan so EXPLAIN can show it and the
    cache can key on it). Raises
    :class:`repro.core.query.QueryValidationError` on malformed queries.
    """
    query.validate()

    ent_texts, ent_rows = _dedupe_texts(query.entities)
    rel_texts, rel_rows = _dedupe_texts(query.relationships)
    ent_index = {e.name: i for i, e in enumerate(query.entities)}
    rel_index = {r.name: i for i, r in enumerate(query.relationships)}

    triples = tuple(query.all_triples())       # cross-frame dedupe
    triple_of = {t: i for i, t in enumerate(triples)}
    frames = tuple(tuple(triple_of[t] for t in f.triples)
                   for f in query.frames)
    max_tr = pow2_bucket(max((len(f) for f in frames), default=1) or 1,
                         minimum=2)
    conjoin_idx = tuple(tuple(f[c] if c < len(f) else 0
                              for c in range(max_tr)) for f in frames)
    conjoin_pad = tuple(tuple(c >= len(f) for c in range(max_tr))
                        for f in frames)

    cap = stores.entities.capacity
    k_ent = min(query.top_k, cap)
    dims = (int(stores.entities.text_emb.shape[1]),
            int(stores.entities.image_emb.shape[1]))
    pred_bytes = predicted_search_bytes_tiered(search_mode, stores, dims[0],
                                               len(ent_texts), k_ent)
    if query.image_search:
        pred_bytes += predicted_search_bytes_tiered(search_mode, stores,
                                                    dims[1], len(ent_texts),
                                                    k_ent)
    from repro.core.stores import entity_search_bounds
    fp32_ranges = sum(min(k_ent, stop - start) > K_PAD
                      for start, stop in entity_search_bounds(stores))
    em = EntityMatch(
        names=tuple(e.name for e in query.entities),
        texts=ent_texts, rows=ent_rows,
        k=k_ent,
        text_threshold=query.text_threshold,
        image_search=query.image_search,
        image_threshold=query.image_threshold,
        search_mode=search_mode,
        predicted_bytes=pred_bytes,
        fp32_ranges=fp32_ranges)
    pm = PredicateMatch(
        names=tuple(r.name for r in query.relationships),
        texts=rel_texts, rows=rel_rows,
        m=min(query.predicate_top_m, len(stores.predicates.labels)),
        threshold=query.text_threshold)
    ts = TripleSelect(
        triples=triples,
        subj_row=tuple(ent_rows[ent_index[t.subject]] for t in triples),
        obj_row=tuple(ent_rows[ent_index[t.object]] for t in triples),
        pred_row=tuple(rel_rows[rel_index[t.predicate]] for t in triples),
        bucket=pow2_bucket(len(triples)))
    tc = TemporalChain(
        gaps=tuple(temporal_lib.normalize_constraints(query)),
        top_k=min(query.top_k, stores.num_segments))
    return Plan(entity_match=em, predicate_match=pm, triple_select=ts,
                verify=VlmVerify(verify, budget=query.verify_budget),
                conjoin=ConjoinFrames(frames, conjoin_idx, conjoin_pad),
                temporal=tc, num_segments=stores.num_segments,
                frames_per_segment=stores.frames_per_segment)


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------
def store_fingerprint(stores) -> Tuple:
    """The static store shape a plan depends on: capacity clamps, the
    (segments, frames) grid, and the embedding dims (they size the
    predicted-bytes model)."""
    return (stores.entities.capacity, len(stores.predicates.labels),
            stores.num_segments, stores.frames_per_segment,
            int(stores.entities.text_emb.shape[1]),
            int(stores.entities.image_emb.shape[1]))


class PlanCache:
    """FIFO-bounded compile cache keyed by query signature.

    The signature is the ``VMRQuery`` itself (frozen ⇒ hashable) plus the
    store fingerprint and verifier flag: a repeat or structurally identical
    query — equal entities/relationships/frames/constraints and
    hyperparameters — hits the cache and skips compilation entirely.
    ``hits``/``misses`` are the counters ``Session`` and the benchmarks
    report.
    """

    def __init__(self, max_entries: int = 256):
        self.max_entries = max_entries
        self._cache: Dict[Tuple, Plan] = {}
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._cache)

    def clear(self) -> None:
        """Drop cached plans (counters keep running) — benchmarks use this
        to measure cold-compile latency on an otherwise warm engine."""
        self._cache.clear()

    @staticmethod
    def signature(query: VMRQuery, stores, verify: bool,
                  search_mode: str = "fp32") -> Tuple:
        return (query, store_fingerprint(stores), verify, search_mode)

    def lookup(self, query: VMRQuery, stores, *, verify: bool,
               search_mode: str = "fp32") -> Tuple[Plan, bool]:
        """Return ``(plan, was_cached)``, compiling on miss."""
        key = self.signature(query, stores, verify, search_mode)
        plan = self._cache.get(key)
        if plan is not None:
            self.hits += 1
            return plan, True
        plan = compile_plan(query, stores, verify=verify,
                            search_mode=search_mode)
        self.misses += 1
        self._cache[key] = plan
        while len(self._cache) > self.max_entries:
            self._cache.pop(next(iter(self._cache)))
        return plan, False
