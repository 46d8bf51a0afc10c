"""The LazyVLM query engine (Section 2.3, Figure 1).

Queries enter as ``VMRQuery`` objects (or, through ``repro.session``, as
semi-structured text) and are **compiled twice**:

  1. to a **logical plan** (:mod:`repro.core.plan`) — typed nodes per
     pipeline stage, compile-time optimizer passes (cross-frame triple
     dedupe, shared-entity embed reuse, static capacity/bucket selection),
     cached by query signature;
  2. to a **physical pipeline** (:mod:`repro.core.physical`) — typed
     operators (``EmbedOp``/``TopKSearchOp``/``TripleFilterOp``/
     ``VlmVerifyOp``/``BitmapConjoinOp``/``TemporalChainOp``), each with a
     ``CostEstimate``; a cost-based pass orders independent triple filters
     by estimated selectivity fed from the device-resident store stats.

``execute`` is orchestration only: it walks the pipeline's operators and
assembles the ``QueryResult``; every stage's math is a fused jitted program
(the kernels live in :mod:`repro.core.physical.stages`). ``execute_batch``
drives the same stage kernels with a fused multi-query schedule — one
launch per stage for the whole batch and ONE content-deduped VLM pass.
With the verification cascade off, both paths are bit-identical to the
pre-physical executor (pinned by the equivalence tests); with a
``verify_budget``, ``VlmVerifyOp`` verifies lazily in semantic-score order
and exits early on an exactness certificate.

Host Python only orchestrates; device→host transfers all route through the
``_to_host`` funnel below so tests can spy on transfer shapes.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.fault import (FaultGuard, FaultPolicy, FaultTolerantEmbedder,
                              FaultTolerantVerifier, ServiceUnavailable)
from repro.core.physical import compile_physical
from repro.core.physical.cost import StoreStats
from repro.core.physical.ops import ExecContext, cascade_for_plan
# stage kernels re-exported for compatibility (benchmarks import them here)
from repro.core.physical.stages import (_conjoin_bitmaps,  # noqa: F401
                                        _entity_match, _masks_to_bitmaps,
                                        _predicate_match, _triple_selections,
                                        make_sql_renderer, render_sql)
from repro.core.plan import Plan, PlanCache, pow2_bucket
from repro.core.query import VMRQuery
from repro.core.stores import (REL_SCHEMA, VideoStores, entity_search_bounds,
                               entity_segment_bounds)
from repro.core import temporal as temporal_lib
from repro.semantic.embed import CachingEmbedder
from repro.semantic.search import (SEARCH_MODES, place_segment_banks,
                                   placed_topk_similarity,
                                   sharded_topk_similarity, topk_prefix)
from repro.symbolic.table import Table


def _to_host(x) -> np.ndarray:
    """The single device→host funnel for the execution path.

    Every transfer the executor AND the physical operators make goes
    through here (the operators call ``physical.stages.to_host``, which
    delegates to this attribute at call time) so tests can spy on transfer
    *shapes*: with no verifier configured, the symbolic stage must never
    round-trip a full-capacity ``(ΣT, cap)`` row mask — only the ``(ΣT,)``
    per-triple row counts (a fused device reduction) and the small
    candidate arrays come back to host.

    Each call is a ``lazyvlm.sync`` span carrying the bytes moved: the host
    waiting on the device.
    """
    with obs.span("sync", bytes=getattr(x, "nbytes", 0)):
        return np.asarray(x)


def _is_append_descendant(old: VideoStores, new: VideoStores) -> bool:
    """Whether ``new`` extends ``old`` append-only: a later store version
    whose segment table keeps every previously *sealed* segment byte-for-
    byte (sealed row ranges are immutable, so their placed device slices —
    and anything else keyed on their coordinates — remain valid)."""
    if getattr(new, "store_version", 0) <= getattr(old, "store_version", 0):
        return False
    old_sealed = [s for s in getattr(old, "segments", ()) if s.sealed]
    new_segs = tuple(getattr(new, "segments", ()))
    if len(new_segs) < len(old_sealed):
        return False
    return all(a.sid == b.sid and a.ent_start == b.ent_start
               and a.ent_stop == b.ent_stop and b.sealed
               for a, b in zip(old_sealed, new_segs))


def _is_compaction_descendant(old: VideoStores, new: VideoStores) -> bool:
    """Whether ``new``'s sealed table is a boundary-coarsening of ``old``'s
    — what ``compact_stores`` produces: every new sealed segment's row
    ranges are the concatenation of one or more *consecutive* old sealed
    segments', covering exactly the same rows. Compaction moves no bank
    row, so placed slices of segments that kept their exact range remain
    valid even though sids renumber."""
    if getattr(new, "store_version", 0) <= getattr(old, "store_version", 0):
        return False
    old_sealed = [s for s in getattr(old, "segments", ()) if s.sealed]
    new_sealed = [s for s in getattr(new, "segments", ()) if s.sealed]
    if not old_sealed or len(new_sealed) > len(old_sealed):
        return False
    i = 0
    for ns in new_sealed:
        if (i >= len(old_sealed)
                or old_sealed[i].ent_start != ns.ent_start
                or old_sealed[i].rel_start != ns.rel_start):
            return False
        while i < len(old_sealed) and (
                old_sealed[i].ent_stop != ns.ent_stop
                or old_sealed[i].rel_stop != ns.rel_stop):
            i += 1
        if i >= len(old_sealed):
            return False
        i += 1
    return i == len(old_sealed)


def _to_device(x, device):
    """The single device→device funnel for placed segment execution.

    Every cross-device move the placed search path makes goes through here
    so tests can spy on the moved *shapes*: per query the cross-device
    merge ships only each device's ``(Q, k')`` candidate tuples (scores +
    global row indices) — never a segment bank and never a full-capacity
    ``(ΣT, cap)`` mask — and segment banks move only once, when a segment
    is first placed on its device (sealed banks are immutable and stay
    put, so incremental refreshes re-place only *new* segments).
    """
    return jax.device_put(x, device)


@dataclass
class QueryStats:
    entity_candidates: Dict[str, int] = field(default_factory=dict)
    sql_rows_per_triple: List[int] = field(default_factory=list)
    refine_candidates: int = 0
    refine_passed: int = 0
    refine_verified: int = 0    # candidates whose verdict was resolved
    verify_rounds: int = 0      # cascade rounds (0 = single full pass)
    vlm_calls: int = 0
    frames_scanned_equivalent: int = 0   # what an e2e VLM would have ingested
    # wall time per stage, from the stage's ``obs.span``. The batch path's
    # stages each end in a ``_to_host`` sync, so they hold their device
    # work; the single-query path's per-operator buckets time dispatch
    # only (no sync closes an operator: device work lands on whichever
    # later operator syncs)
    stage_seconds: Dict[str, float] = field(default_factory=dict)
    # -- graceful degradation (verifier ServiceUnavailable mid-query) -------
    degraded: bool = False               # some candidates went unverified
    unverified_rows: Optional[np.ndarray] = None   # (M, 5) unique rows
    degraded_cause: Optional[Exception] = None


@dataclass
class QueryResult:
    """Result of one ``VMRQuery``.

    ``segments`` and ``scores`` are parallel lists: ``scores[i]`` is the
    integer count of valid chain completions (distinct end frames where the
    query's last frame spec can land, see ``temporal.rank_segments``) inside
    ``segments[i]``; more completions = stronger match. Only segments with at
    least one completion are returned, best first.

    ``sql`` (the paper's SQL-generation artifact, one statement per triple)
    is rendered **lazily** on first access from candidate arrays that are
    already on host — query execution itself does no string formatting and
    no extra device transfers for it.

    ``degraded`` is the graceful-degradation contract: when the verifier
    became :class:`ServiceUnavailable` mid-query AND the cascade's
    monotonicity certificate could not complete the answer exactly, the
    result is flagged with the unverified candidate row set in
    ``unverified`` (``(M, 5)`` (vid,fid,sid,rl,oid) rows) — the matched
    windows shown are the *confirmed-only* subset, never a silent guess.
    A False ``degraded`` means the result is exact, faults notwithstanding.
    """

    segments: List[int]                  # ranked segment ids
    scores: List[int]                    # chain-completion count per segment
    end_frames: np.ndarray               # (V, F) bool
    stats: QueryStats = field(default_factory=QueryStats)
    sql_renderer: Optional[Callable[[], List[str]]] = None
    _sql: Optional[List[str]] = field(default=None, repr=False)
    degraded: bool = False
    unverified: Optional[np.ndarray] = None

    @property
    def sql(self) -> List[str]:
        """Generated SQL, one statement per triple (rendered on demand)."""
        if self._sql is None:
            self._sql = self.sql_renderer() if self.sql_renderer else []
        return self._sql


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------
class LazyVLMEngine:
    def __init__(self, stores: VideoStores, embedder, verifier=None, *,
                 mesh=None, use_kernels: bool = False,
                 search_mode: str = "fp32",
                 reorder_filters: bool = True,
                 embed_cache_entries: int = 4096,
                 plan_cache_entries: int = 256,
                 fault_policy: Optional[FaultPolicy] = None,
                 adapt=None):
        self._stores = stores
        # adaptive runtime re-optimization (physical/adapt.py): True or an
        # AdaptPolicy enables the correction memo + budget tuner; default
        # off keeps the engine purely statically costed
        from repro.core.physical.adapt import AdaptPolicy, AdaptiveStats
        if adapt is True:
            adapt = AdaptiveStats()
        elif isinstance(adapt, AdaptPolicy):
            adapt = AdaptiveStats(adapt)
        elif adapt is False:
            adapt = None
        self.adapt: Optional[AdaptiveStats] = adapt
        # retry/backoff/breaker envelope around the remote-shaped services
        # (verifier + embedder); guards are exposed for counter accounting
        self.fault_policy = fault_policy
        self.fault_guards: Dict[str, FaultGuard] = {}
        if fault_policy is not None:
            if verifier is not None and not isinstance(verifier,
                                                       FaultTolerantVerifier):
                verifier = FaultTolerantVerifier(verifier, fault_policy)
                self.fault_guards["verifier"] = verifier.guard
            if not isinstance(embedder, FaultTolerantEmbedder):
                embedder = FaultTolerantEmbedder(embedder, fault_policy)
                self.fault_guards["embedder"] = embedder.guard
        self.embedder = embedder
        # host-side text->embedding memo; both the single-query and the
        # batched path go through it (inner embedders are deterministic, so
        # cached rows are bit-identical to recomputed ones; the fault guard
        # sits INSIDE the cache, so absorbed faults never poison it)
        self._embed = CachingEmbedder(embedder,
                                      max_entries=embed_cache_entries)
        self.verifier = verifier          # None => trust the symbolic stage
        self.mesh = mesh
        self.use_kernels = use_kernels
        if search_mode not in SEARCH_MODES:
            raise ValueError(f"search_mode must be one of {SEARCH_MODES}, "
                             f"got {search_mode!r}")
        if search_mode == "int4":
            raise ValueError("search_mode='int4' is the cold-tier scan: "
                             "engines select it per segment when the "
                             "tiered-storage layer demotes one "
                             "(demote_cold_segments) — configure 'fp32' "
                             "or 'int8' for the hot tier")
        if search_mode == "int8" and (stores.entities.text_i8 is None
                                      or stores.entities.image_i8 is None):
            raise ValueError("search_mode='int8' needs int8 entity banks "
                             "(text and image); this store was built "
                             "without them (build_entity_store quantizes "
                             "at ingest)")
        self.search_mode = search_mode
        # cost-based triple ordering (invariant-preserving; off = keep the
        # query's declaration order in the fused selection)
        self.reorder_filters = reorder_filters
        # query-signature -> compiled Plan (repeat queries skip compilation)
        self.plan_cache = PlanCache(max_entries=plan_cache_entries)
        # (Plan, store_version) -> PhysicalPipeline (FIFO-bounded like the
        # plan cache). Keying on the version means an append can never
        # leave a stale cost order behind: the next lookup after a bump
        # re-costs against the fresh statistics.
        self._physical_cache: Dict[Tuple[Plan, int], object] = {}
        self._physical_cache_entries = plan_cache_entries
        self._store_stats: Optional[StoreStats] = None
        self._store_stats_version: Optional[int] = None
        # (texts, m, threshold) -> runtime predicate candidate label ids
        # (store-independent: query text x the static vocab)
        self._pred_cand_cache: Dict[Tuple, Tuple] = {}
        # (Plan, store_version, adapt_epoch) -> total CostEstimate, with
        # hit/miss counters — serving submits price plans far more often
        # than they compile them
        self._cost_cache: Dict[Tuple, object] = {}
        self.cost_cache_hits = 0
        self.cost_cache_misses = 0
        # -- placed segment execution state (mesh engines) -------------------
        # sids a subscription's chain frontier touches; the placement pass
        # co-locates them (Subscription.refresh keeps this current)
        self.frontier_sids: Tuple[int, ...] = ()
        # store_version -> SegmentPlacement (placement is deterministic and
        # sticky per version, so one entry suffices)
        self._placement_version: Optional[int] = None
        self._placement = None
        # sid -> device from the placement before the last store update:
        # callers append from *their* (unplaced) store handle, so stickiness
        # must not depend on the incoming segments carrying .device
        self._prior_assignment: Dict[int, int] = {}
        # (role, sid, start, stop, dev[, version]) -> placed bank slice.
        # Sealed segments are append-only, so their entries survive store
        # updates (the same append-only lineage Subscription assumes) and
        # an incremental refresh re-places only NEW segments' rows.
        self._seg_bank_cache: Dict[Tuple, object] = {}
        # ordinals reported lost (mark_device_lost); the placement pass
        # excludes them and their segments re-place onto survivors
        self._lost_devices: set = set()

    # -- store snapshot ----------------------------------------------------
    @property
    def stores(self) -> VideoStores:
        return self._stores

    @stores.setter
    def stores(self, stores: VideoStores) -> None:
        """Re-point the engine at (an updated version of) its stores.

        Statistics snapshots, compiled physical pipelines, and predicate
        candidate memos are invalidated — results never depend on stats
        freshness, but cost ordering, segment pruning, and admission
        pricing do. Placed segment banks survive **append-descendant**
        updates (sealed rows are immutable, so their placed slices stay
        valid and an incremental refresh moves only new segments' rows)
        and **compaction-descendant** updates (a merge moves no bank row,
        so untouched segments' slices stay valid — only the merged ranges
        re-place); any other store swap drops them."""
        if _is_append_descendant(self._stores, stores):
            if self._placement is not None:
                # carry the old assignment by sid: the new store's segment
                # objects come from the caller's unplaced lineage
                self._prior_assignment.update(
                    (s.sid, d) for s, d in zip(
                        self._stores.segments, self._placement.assignment))
        elif _is_compaction_descendant(self._stores, stores):
            # sids renumber under compaction, so the sid-keyed prior map
            # is stale — stickiness rides on the StoreSegment.device
            # metadata the compacted table carries (merge_segments keeps
            # the majority device); the bank cache keys on row ranges,
            # not sids, so untouched segments keep their placed slices
            self._prior_assignment = {}
        else:
            self._seg_bank_cache.clear()
            self._prior_assignment = {}
        self._placement = None
        self._placement_version = None
        self._stores = stores
        self.refresh_store_stats()
        self._pred_cand_cache.clear()

    @property
    def store_version(self) -> int:
        return getattr(self._stores, "store_version", 0)

    # -- compilation -------------------------------------------------------
    def plan_for(self, query: VMRQuery) -> Plan:
        """Compile ``query`` to a :class:`Plan` through the plan cache."""
        plan, _ = self.plan_cache.lookup(query, self.stores,
                                         verify=self.verifier is not None,
                                         search_mode=self.search_mode)
        return plan

    @property
    def store_stats(self) -> StoreStats:
        """Symbolic statistics snapshot, keyed by ``store_version``.

        Segmented stores assemble it by summing the per-segment host stats
        (zero device work); hand-built stores pay one fused device
        reduction with small transfers through the funnel. A version bump
        (``append_stores``/``seal_stores``) invalidates it automatically;
        re-pointing the engine at a different store object goes through the
        ``stores`` setter, which drops it too."""
        v = self.store_version
        if self._store_stats is None or self._store_stats_version != v:
            self._store_stats = StoreStats.from_stores(self.stores)
            self._store_stats_version = v
        return self._store_stats

    def refresh_store_stats(self) -> None:
        """Drop the statistics snapshot and compiled physical pipelines
        (their cost ordering priced against the old stats). Called by the
        ``stores`` setter; version-keyed caches make explicit calls
        unnecessary for ``append_stores``-produced updates."""
        self._store_stats = None
        self._store_stats_version = None
        self._physical_cache.clear()
        self._cost_cache.clear()

    def _pred_candidates(self, plan: Plan) -> Tuple[Tuple[int, ...], ...]:
        """Runtime predicate candidate label ids per predicate-text row —
        the exact same einsum + top-m + threshold the execution stage runs
        (one shared implementation, ``stages.predicate_candidates``),
        computed once at compile time (it depends only on the query text
        and the static vocab, never on the store), so the segment-pruning
        pass is provable rather than heuristic."""
        from repro.core.physical.stages import predicate_candidates
        pm = plan.predicate_match
        key = (pm.texts, pm.m, pm.threshold)
        hit = self._pred_cand_cache.get(key)
        if hit is not None:
            return hit
        ids_np, ok_np, _ = predicate_candidates(
            self._embed, self.stores.predicates.embeddings, pm.texts,
            pm.m, pm.threshold)
        out = tuple(tuple(int(p) for p in row[sel])
                    for row, sel in zip(ids_np, ok_np))
        self._pred_cand_cache[key] = out
        return out

    def physical_for(self, plan: Plan):
        """Lower ``plan`` to a :class:`PhysicalPipeline` (cached per
        ``(plan, store_version, adapt_epoch)`` — see the cache comment
        above; the epoch key means new runtime observations recompile
        against the corrected estimates instead of mutating a cached
        pipeline)."""
        version = self.store_version
        epoch = self.adapt.epoch if self.adapt is not None else 0
        key = (plan, version, epoch)
        pipe = self._physical_cache.get(key)
        if pipe is None:
            # predicate candidates sharpen the segment-pruning pass; on a
            # monolithic (segmentless) store the pass has nothing to prune,
            # so skip the embed + device round-trip entirely
            cands = (self._pred_candidates(plan)
                     if self.store_stats.segments else None)
            pipe = compile_physical(plan, self.store_stats,
                                    reorder=self.reorder_filters,
                                    pred_candidates=cands,
                                    store_version=version,
                                    placement=self.segment_placement(),
                                    adapt=self.adapt)
            self._physical_cache[key] = pipe
            while len(self._physical_cache) > self._physical_cache_entries:
                self._physical_cache.pop(next(iter(self._physical_cache)))
        return pipe

    def estimate_cost(self, query: VMRQuery):
        """Total pipeline :class:`CostEstimate` for one query (the serving
        scheduler's admission currency). Memoized per
        ``(plan, store_version, adapt_epoch)`` — submits price plans far
        more often than they compile, and with adaptation on the price
        tracks *corrected* estimates, so admission sees what execution
        actually costs."""
        plan = self.plan_for(query)
        epoch = self.adapt.epoch if self.adapt is not None else 0
        key = (plan, self.store_version, epoch)
        cost = self._cost_cache.get(key)
        if cost is not None:
            self.cost_cache_hits += 1
            return cost
        self.cost_cache_misses += 1
        cost = self.physical_for(plan).total_estimate()
        self._cost_cache[key] = cost
        while len(self._cost_cache) > self._physical_cache_entries:
            self._cost_cache.pop(next(iter(self._cost_cache)))
        return cost

    # -- placed segment execution (mesh engines over segmented stores) -------
    def _mesh_device_table(self):
        """One device per data-axis slice of the engine's mesh — the device
        table placement ordinals index into (memoized; the mesh is fixed
        for the engine's lifetime)."""
        if getattr(self, "_device_table", None) is None:
            from repro.distributed.sharding import dp_size
            devs = np.asarray(self.mesh.devices)
            dp = max(1, min(dp_size(self.mesh), devs.size))
            self._device_table = list(devs.reshape(dp, -1)[:, 0])
        return self._device_table

    def segment_placement(self):
        """The placement-aware pass output for the current store snapshot.

        Runs :func:`repro.core.physical.cost.place_stores` once per
        ``store_version`` (placement is deterministic and sticky, so the
        version fully determines it), writes the assignment back onto the
        ``StoreSegment`` table, and co-locates the registered subscription
        frontier (``frontier_sids``). Returns ``None`` on mesh-less engines
        or unsegmented stores."""
        if self.mesh is None or not getattr(self._stores, "segments", ()):
            return None
        v = self.store_version
        if self._placement is None or self._placement_version != v:
            from repro.core.physical.cost import place_stores
            n_devices = len(self._mesh_device_table())
            self._stores, self._placement = place_stores(
                self._stores, n_devices, frontier=self.frontier_sids,
                prior=self._prior_assignment,
                exclude=frozenset(self._lost_devices))
            self._placement_version = v
        return self._placement

    def mark_device_lost(self, ordinal: int) -> None:
        """Record a (simulated) device loss and trigger sticky re-placement.

        The current assignment is snapshotted into the prior map so
        surviving segments stay put; only the lost device's segments move
        (LPT onto the survivors, ``place_segments``' ``exclude`` path).
        Placement is metadata + bank location, never data — the re-placed
        query is bitwise-equal to the pre-loss run (pinned by the device-
        loss tests)."""
        if self.mesh is not None:
            n = len(self._mesh_device_table())
            if len(self._lost_devices | {int(ordinal)}) >= n:
                raise RuntimeError(
                    f"cannot lose device {ordinal}: no surviving devices")
        self._lost_devices.add(int(ordinal))
        if self._placement is not None:
            self._prior_assignment.update(
                (s.sid, d) for s, d in zip(self._stores.segments,
                                           self._placement.assignment))
        self._placement = None
        self._placement_version = None
        self._physical_cache.clear()     # pipelines embed the placement

    def _segment_modes(self) -> Tuple[str, ...]:
        """Effective per-range scan modes, aligned 1:1 with
        ``entity_search_bounds``: cold-tier segments scan their packed
        int4 banks, hot segments the engine's configured ``search_mode``.
        The tier split never changes a result bit — every mode's
        per-range top-k is exact — only the bytes each range reads."""
        from repro.core.stores import entity_segment_tiers
        return tuple("int4" if t == "cold" else self.search_mode
                     for t in entity_segment_tiers(self.stores))

    def _segment_banks(self, role: str, emb, emb_i8, valid):
        """Per-segment bank slices committed to their assigned devices.

        Cached per segment: sealed segments key on their immutable row
        range (their rows never change and compaction only coarsens
        boundaries, so a placed slice survives store updates — incremental
        refreshes move only NEW or merged ranges' rows); the active/tail
        range keys on ``store_version`` and is re-placed after every
        append. Each range stages only the bank its tier's scan mode
        reads (the mode is part of the key, so a hot→cold demotion
        re-stages the int4 slice instead of resurfacing a mode-less
        bank). All moves go through the ``_to_device`` funnel."""
        placement = self.segment_placement()
        table = self._mesh_device_table()
        modes = self._segment_modes()
        ent = self.stores.entities
        emb_i4 = ent.image_i4 if role == "image" else ent.text_i4
        bounds3 = entity_segment_bounds(self.stores)
        segs = {s.sid: s for s in self.stores.segments}
        fresh: Dict[Tuple, object] = {}
        banks = []
        last = bounds3[-1]
        for j, (start, stop, sid) in enumerate(bounds3):
            m = modes[j]
            dev_ord = placement.device_of(sid)
            sealed = (segs[sid].sealed and (start, stop, sid) != last)
            # the key carries the row range, NOT the sid (compaction
            # renumbers sids without moving rows) and the range's scan
            # mode (a mode only reads its own bank)
            key = (role, m, start, stop, dev_ord) if sealed \
                else (role, m, start, stop, dev_ord, self.store_version)
            bank = self._seg_bank_cache.get(key)
            if bank is None:
                bank = place_segment_banks(
                    emb, valid, ((start, stop),), (dev_ord,),
                    i8=emb_i8 if m == "int8" else None,
                    i4=emb_i4 if m == "int4" else None, modes=(m,),
                    put=lambda x, d: _to_device(x, d),
                    device_table=table)[0]
            fresh[key] = bank
            banks.append(bank)
        self._seg_bank_cache = fresh
        return tuple(banks)

    # -- stage 1 search dispatch (used by TopKSearchOp) ----------------------
    def _search(self, q_emb, emb, emb_i8, valid, k):
        ent = self.stores.entities
        role = "image" if emb is ent.image_emb else "text"
        modes = self._segment_modes()
        cold = any(m == "int4" for m in modes)
        emb_i4 = (ent.image_i4 if role == "image" else ent.text_i4) \
            if cold else None
        if self.mesh is not None:
            bounds = entity_search_bounds(self.stores)
            if len(bounds) > 1:
                # sharded segment execution: per-device segment-local
                # top-k + one fused cross-device merge, bitwise equal to
                # the monolithic sweep (see placed_topk_similarity)
                banks = self._segment_banks(role, emb, emb_i8, valid)
                table = self._mesh_device_table()
                merge_ord = next(i for i in range(len(table))
                                 if i not in self._lost_devices)
                return placed_topk_similarity(
                    q_emb, banks, k, use_kernels=self.use_kernels,
                    mode=self.search_mode, modes=modes,
                    merge_device=table[merge_ord],
                    to_device=lambda x, d: _to_device(x, d))
            # unsegmented (or single-segment) store on a mesh: shard rows
            # over devices and keep the global shard_map sweep, in the
            # lone range's tier mode
            return sharded_topk_similarity(
                q_emb, emb, valid, k, self.mesh,
                use_kernels=self.use_kernels, mode=modes[0],
                i8=emb_i8 if modes[0] != "int4" else None, i4=emb_i4)
        bounds = entity_search_bounds(self.stores)
        if len(bounds) > 1 or cold:
            from repro.core.physical.stages import _entity_match_segmented
            return _entity_match_segmented(q_emb, emb, emb_i8, valid, k,
                                           self.search_mode,
                                           self.use_kernels, bounds,
                                           db_i4=emb_i4,
                                           modes=modes if cold else None)
        return _entity_match(q_emb, emb, emb_i8, valid, k,
                             self.search_mode, self.use_kernels)

    # -- the full pipeline ------------------------------------------------------
    def query(self, query: VMRQuery) -> QueryResult:
        """Compile (with plan-cache) and execute one query."""
        return self.execute(self.plan_for(query))

    def execute(self, plan: Plan, *, _analyze: Optional[dict] = None
                ) -> QueryResult:
        """Walk the physical pipeline's operators and assemble the result.

        ``_analyze`` (EXPLAIN ANALYZE, see ``Session.explain``) collects
        per-operator actual row counts into the given dict — analyze mode
        may issue extra small reductions the hot path skips.

        Each operator's ``run`` is a ``lazyvlm.op.<stage>`` span, summed
        into ``stats.stage_seconds[<stage>]``. That is dispatch time, not
        device work: no sync closes an operator, so the device's time
        lands on whichever later operator syncs.
        """
        st = self.stores
        pipe = self.physical_for(plan)
        ctx = ExecContext(engine=self, plan=plan, pipeline=pipe,
                          stats=QueryStats(), analyze=_analyze is not None)
        for op in pipe.ops:
            with obs.span(f"op.{op.stage}") as s:
                op.run(ctx)
            ctx.stats.stage_seconds[op.stage] = (
                ctx.stats.stage_seconds.get(op.stage, 0.0) + s.seconds)
        scores_np, segs_np, reach = ctx.vals["ranked"]
        keep = scores_np > 0
        ctx.stats.frames_scanned_equivalent = (st.num_segments
                                               * st.frames_per_segment)
        if _analyze is not None:
            _analyze["actual_rows"] = ctx.actual_rows
            _analyze["pipeline"] = pipe
        return QueryResult(
            segments=[int(v) for v in segs_np[keep]],
            scores=[int(s) for s in scores_np[keep]],
            end_frames=_to_host(reach),
            sql_renderer=ctx.vals["sql_renderer"],
            stats=ctx.stats,
            degraded=ctx.stats.degraded,
            unverified=ctx.stats.unverified_rows,
        )

    # -- batched multi-query path -------------------------------------------------
    def _match_entities_batch(self, plans: List[Plan],
                              stats: List[QueryStats]):
        """Entity matching for a whole batch: ONE ``embed_texts`` call over
        every plan's (deduped) entity texts (through the host-side cache)
        and ONE fused top-k launch at the batch-max k; each query's
        smaller-k view is an exact prefix (``topk_prefix``). Returns per
        plan ``(vids, eids, ok)`` host arrays of shape (U_q, width_q), rows
        per unique entity text."""
        ent = self.stores.entities
        cap = ent.capacity
        texts = [t for p in plans for t in p.entity_match.texts]
        offs = np.cumsum([0] + [len(p.entity_match.texts) for p in plans])
        q_emb = jnp.asarray(self._embed.embed_texts(texts))
        kmax = max(p.entity_match.k for p in plans)   # capacity-clamped
        scores, idx = self._search(q_emb, ent.text_emb, ent.text_i8,
                                   ent.table.valid, kmax)
        scores_np, idx_np = _to_host(scores), _to_host(idx)

        img_pids = [i for i, p in enumerate(plans)
                    if p.entity_match.image_search]
        if img_pids:
            img_texts = [t for i in img_pids
                         for t in plans[i].entity_match.texts]
            img_offs = np.cumsum(
                [0] + [len(plans[i].entity_match.texts) for i in img_pids])
            qi_emb = jnp.asarray(self._embed.embed_for_image(img_texts))
            kimax = max(plans[i].entity_match.k for i in img_pids)
            iscores, iidx = self._search(qi_emb, ent.image_emb, ent.image_i8,
                                         ent.table.valid, kimax)
            iscores_np, iidx_np = _to_host(iscores), _to_host(iidx)
        img_pos = {qid: j for j, qid in enumerate(img_pids)}

        vid_col = _to_host(ent.table["vid"])
        eid_col = _to_host(ent.table["eid"])
        out = []
        for qi, p in enumerate(plans):
            em = p.entity_match
            sl = slice(offs[qi], offs[qi + 1])
            s_q, idx_q = topk_prefix(scores_np[sl], idx_np[sl], em.k)
            ok_q = s_q >= em.text_threshold
            if em.image_search:
                j = img_pos[qi]
                isl = slice(img_offs[j], img_offs[j + 1])
                is_q, ii_q = topk_prefix(iscores_np[isl], iidx_np[isl], em.k)
                idx_q = np.concatenate([idx_q, ii_q], axis=1)
                ok_q = np.concatenate([ok_q, is_q >= em.image_threshold],
                                      axis=1)
            ci = np.clip(idx_q, 0, cap - 1)
            for name, row in zip(em.names, em.rows):
                stats[qi].entity_candidates[name] = int(ok_q[row].sum())
            out.append((vid_col[ci], eid_col[ci], ok_q))
        return out

    def _match_predicates_batch(self, plans: List[Plan]):
        """Predicate matching for a whole batch as one einsum + one top-k
        launch. Returns per plan ``(pred_ids, ok, vals)`` host arrays (rows
        per unique relationship text; ``vals`` feed the cascade's
        semantic-score ordering)."""
        texts = [t for p in plans for t in p.predicate_match.texts]
        offs = np.cumsum([0] + [len(p.predicate_match.texts) for p in plans])
        q_emb = jnp.asarray(self._embed.embed_texts(texts))
        sims = _predicate_match(q_emb, jnp.asarray(
            self.stores.predicates.embeddings))            # (ΣU, P)
        mmax = max(p.predicate_match.m for p in plans)     # vocab-clamped
        vals, ids = jax.lax.top_k(sims, mmax)
        vals_np, ids_np = _to_host(vals), _to_host(ids)
        out = []
        for qi, p in enumerate(plans):
            pm = p.predicate_match
            sl = slice(offs[qi], offs[qi + 1])
            v_q, id_q = topk_prefix(vals_np[sl], ids_np[sl], pm.m)
            ok = v_q >= pm.threshold
            ok[:, 0] = True    # always keep the argmax label
            out.append((id_q, ok, v_q))
        return out

    def query_batch(self, queries: List[VMRQuery]) -> List[QueryResult]:
        """Compile every query (through the plan cache) and execute the
        batch; see :meth:`execute_batch` for the fusion/equivalence
        contract. The call is one ``lazyvlm.engine.batch`` span."""
        with obs.span("engine.batch"):
            with obs.span("engine.plan"):
                plans = [self.plan_for(q) for q in queries]
            return self.execute_batch(plans)

    def execute_batch(self, plans: List[Plan]) -> List[QueryResult]:
        """Execute many compiled plans with fused, amortized stage launches.

        Per query the returned ``QueryResult`` is identical to ``query()``:
        smaller per-query top-k's are exact prefixes of the batch-max top-k,
        padded triple rows carry all-False candidate masks (they select
        nothing), and row verdicts depend only on row content. The batch
        amortizes: one embedding call (cached) for every query's texts, one
        entity/predicate top-k launch each, one ``(ΣT, cap)`` selection +
        bitmap launch (ΣT padded to a power-of-two bucket so compiled
        programs are reused across batch shapes; each query's rows sit in
        its pipeline's cost order), one signature-grouped temporal DP, and —
        the expensive part — ONE deduped VLM verification pass shared across
        queries: a candidate row referenced by several queries costs one
        call total. Plans carrying a ``verify_budget`` instead run the
        budgeted cascade on their own row slice, seeded with the fused
        pass's verdict memo (duplicate rows still cost one call; results
        stay exact by the cascade's certificate). Two stats fields carry
        batch-level (not per-query) values on every result:
        ``stats.vlm_calls`` is the verifier's cumulative call count shared
        by the whole batch, and ``stats.stage_seconds`` holds the batch's
        stage wall-times (summing them across a batch's results overcounts
        by the batch size).

        Each stage is a ``lazyvlm.engine.<stage>`` span: ``plan`` (the
        physical pipelines), ``search`` (``entity_match``), ``select``
        (``symbolic``), ``verify`` (``refine``), ``temporal`` and
        ``results``; ``stage_seconds`` takes the keys in brackets from
        their spans. Search, select and temporal each end in a
        ``_to_host`` sync, so their times hold the device work they
        launched.
        """
        if not plans:
            return []
        st = self.stores
        rel = st.relationships.table
        stats = [QueryStats() for _ in plans]
        with obs.span("engine.plan"):
            pipes = [self.physical_for(p) for p in plans]

        # -- stage 1: batched entity + predicate matching ---------------------
        with obs.span("engine.search") as search:
            ent_cands = self._match_entities_batch(plans, stats)
            pred_cands = self._match_predicates_batch(plans)

        # -- stage 2+3a: every query's triples in ONE fused selection ---------
        with obs.span("engine.select") as select:
            counts = [len(p.triple_select.triples) for p in plans]
            row_offs = np.cumsum([0] + counts)
            total = int(row_offs[-1])
            t_pad = pow2_bucket(total)
            width = pow2_bucket(max(v.shape[1] for v, _, _ in ent_cands),
                                minimum=8)
            m_width = pow2_bucket(
                max(ids.shape[1] for ids, _, _ in pred_cands), minimum=2)
            sv = np.zeros((t_pad, width), np.int32)
            se = np.zeros((t_pad, width), np.int32)
            ov = np.zeros((t_pad, width), np.int32)
            oe = np.zeros((t_pad, width), np.int32)
            so = np.zeros((t_pad, width), bool)
            oo = np.zeros((t_pad, width), bool)
            pi = np.zeros((t_pad, m_width), np.int32)
            po = np.zeros((t_pad, m_width), bool)
            for qi, p in enumerate(plans):
                vids, eids, eok = ent_cands[qi]
                pids, pok, _ = pred_cands[qi]
                ts = p.triple_select
                w, m = vids.shape[1], pids.shape[1]
                for pos, orig in enumerate(pipes[qi].order):
                    row = row_offs[qi] + pos
                    s_i, o_i = ts.subj_row[orig], ts.obj_row[orig]
                    p_i = ts.pred_row[orig]
                    sv[row, :w], se[row, :w] = vids[s_i], eids[s_i]
                    so[row, :w] = eok[s_i]
                    ov[row, :w], oe[row, :w] = vids[o_i], eids[o_i]
                    oo[row, :w] = eok[o_i]
                    pi[row, :m] = pids[p_i]
                    po[row, :m] = pok[p_i]
            masks = _triple_selections(
                rel["vid"], rel["fid"], rel["sid"], rel["rl"], rel["oid"],
                rel.valid,
                jnp.asarray(sv), jnp.asarray(se), jnp.asarray(so),
                jnp.asarray(ov), jnp.asarray(oe), jnp.asarray(oo),
                jnp.asarray(pi), jnp.asarray(po))           # (ΣT_pad, cap)
            # symbolic-stage bookkeeping stays device-resident: per-triple
            # row counts come back as ONE fused (ΣT_pad,) reduction, and SQL
            # text is a lazy closure over the (already-host) candidate arrays
            # — the full-capacity (ΣT, cap) mask is only materialized on host
            # further down, if (and only if) a verifier needs row identities
            row_counts = _to_host(masks.sum(axis=1))            # (ΣT_pad,)
            renderers: List[Callable[[], List[str]]] = []
            for qi, p in enumerate(plans):
                lo = row_offs[qi]
                pos_of = pipes[qi].pos_of
                stats[qi].sql_rows_per_triple = [
                    int(row_counts[lo + pos_of[j]]) for j in range(counts[qi])]
                renderers.append(make_sql_renderer(
                    [lo + pos_of[j] for j in range(counts[qi])],
                    sv, se, so, ov, oe, oo, pi, po, st.predicates.labels))
            if self.adapt is not None:
                # feed every query's estimated-vs-actual rows into the memo —
                # the batch keeps its one fused launch (no mid-batch probing;
                # the next compile of a drifted plan picks up the corrections)
                from repro.core.physical.adapt import observe_filters
                for qi, p in enumerate(plans):
                    observe_filters(self.adapt, p, pipes[qi], row_counts,
                                    pipes[qi].store_version,
                                    offset=int(row_offs[qi]))

        # -- stage 3b: ONE deduped VLM pass across the whole batch ------------
        with obs.span("engine.verify") as verify:
            # rows of plans compiled with verify disabled are excluded from the
            # candidate set and keep their symbolic masks; budgeted plans run
            # the cascade on their own slice (seeded with the fused pass's
            # verdict memo), so execution matches each plan's advertised
            # VlmVerify node even in a mixed batch
            verif = np.zeros((t_pad,), bool)
            budgeted: List[int] = []
            for qi, p in enumerate(plans):
                if not p.verify.enabled:
                    continue
                if p.verify.budget > 0:
                    budgeted.append(qi)
                else:
                    verif[row_offs[qi]: row_offs[qi] + counts[qi]] = True
            if self.verifier is not None and (verif.any() or budgeted):
                # row identities are needed now: this is the ONE place the
                # no-verifier fast path never reaches
                masks_np = _to_host(masks)
                memo: Dict[tuple, bool] = {}
                cols = None
                if verif.any():
                    try:
                        out = self._verify_rows(rel, masks_np & verif[:, None])
                    except ServiceUnavailable as exc:
                        # verifier gone during the fused pass: every
                        # full-verify plan in the batch degrades
                        # (confirmed-only = nothing; their candidates are
                        # excluded and attached unverified); budgeted plans
                        # below still run — their cascades may complete from
                        # memo-free certificates or degrade too
                        out = None
                        cols = {k: _to_host(rel[k]) for k in REL_SCHEMA}
                        calls = getattr(self.verifier, "calls", 0)
                        for qi, p in enumerate(plans):
                            if not p.verify.enabled or p.verify.budget > 0:
                                continue
                            lo = row_offs[qi]
                            q_any = masks_np[lo: lo + counts[qi]].any(axis=0)
                            ridx = np.nonzero(q_any)[0]
                            if len(ridx) == 0:
                                continue    # no candidates of its own: exact
                            stats[qi].vlm_calls = calls
                            stats[qi].degraded = True
                            stats[qi].degraded_cause = exc
                            stats[qi].unverified_rows = np.unique(
                                np.stack([cols[k][ridx] for k in REL_SCHEMA],
                                         axis=1), axis=0)
                            stats[qi].refine_candidates = len(
                                stats[qi].unverified_rows)
                        masks = masks & ~jnp.asarray(verif)[:, None]
                    if out is not None:
                        keep_rows, uniq, verdict_u, cols = out
                        for u, vd in zip(uniq, verdict_u):
                            memo[tuple(int(x) for x in u)] = bool(vd)
                        calls = getattr(self.verifier, "calls", 0)
                        for qi, p in enumerate(plans):
                            if not p.verify.enabled or p.verify.budget > 0:
                                continue
                            lo = row_offs[qi]
                            q_any = masks_np[lo: lo + counts[qi]].any(axis=0)
                            ridx = np.nonzero(q_any)[0]
                            stats[qi].vlm_calls = calls
                            if len(ridx) == 0:
                                continue
                            qrows = np.stack(
                                [cols[k][ridx] for k in REL_SCHEMA], axis=1)
                            stats[qi].refine_candidates = len(
                                np.unique(qrows, axis=0))
                            stats[qi].refine_passed = len(
                                np.unique(qrows[keep_rows[ridx]], axis=0))
                            stats[qi].refine_verified = (
                                stats[qi].refine_candidates)
                        masks = masks & (jnp.asarray(keep_rows)[None, :]
                                         | ~jnp.asarray(verif)[:, None])
                if cols is None and budgeted:
                    cols = {k: _to_host(rel[k]) for k in REL_SCHEMA}
                for qi in budgeted:
                    p, pipe = plans[qi], pipes[qi]
                    lo, hi = row_offs[qi], row_offs[qi] + counts[qi]
                    ids_q, ok_q, vals_q = pred_cands[qi]
                    keep_q = cascade_for_plan(
                        engine=self, plan=p, pipeline=pipe,
                        masks=masks[lo:hi], masks_np=masks_np[lo:hi],
                        pred_scores=(vals_q, ids_q, ok_q), stats=stats[qi],
                        memo=memo, cols=cols)
                    if keep_q is not None:
                        sel = np.zeros((t_pad,), bool)
                        sel[lo:hi] = True
                        masks = masks & (jnp.asarray(keep_q)[None, :]
                                         | ~jnp.asarray(sel)[:, None])

        # -- stage 4: conjunction + signature-grouped temporal DP -------------
        with obs.span("engine.temporal") as chain:
            bitmaps = _masks_to_bitmaps(rel["vid"], rel["fid"], masks,
                                        st.num_segments, st.frames_per_segment)
            # frame-spec conjunction: one gather + AND-reduce over every
            # (query, frame) pair; pad slots act as identity (all-True),
            # matching the single path's ones-initialized accumulator
            fcounts = [len(p.conjoin.frames) for p in plans]
            frame_offs = np.cumsum([0] + fcounts)
            n_qf = int(frame_offs[-1])
            max_tr = pow2_bucket(
                max((len(f) for p in plans for f in p.conjoin.frames),
                    default=1) or 1, minimum=2)
            qf_pad = pow2_bucket(n_qf)
            idx_mat = np.zeros((qf_pad, max_tr), np.int32)
            pad_mat = np.ones((qf_pad, max_tr), bool)
            for qi, p in enumerate(plans):
                pos_of = pipes[qi].pos_of
                for fj, fr in enumerate(p.conjoin.frames):
                    r = frame_offs[qi] + fj
                    for c, ti in enumerate(fr):
                        idx_mat[r, c] = row_offs[qi] + pos_of[ti]
                        pad_mat[r, c] = False
            fmaps = _conjoin_bitmaps(bitmaps, jnp.asarray(idx_mat),
                                     jnp.asarray(pad_mat))  # (qf_pad, V, F)
            frame_maps_all = [
                [fmaps[frame_offs[qi] + j] for j in range(fcounts[qi])]
                for qi in range(len(plans))]
            matched = temporal_lib.temporal_match_batch_sigs(
                frame_maps_all, [p.chain_signature() for p in plans])
            ends_stack = jnp.stack([ends for _, ends in matched])  # (B, V, F)
            kmax = max(p.temporal.top_k for p in plans)   # segment-clamped
            scores_b, seg_b = temporal_lib.rank_segments_batch(ends_stack,
                                                               kmax)
            scores_np, seg_np = _to_host(scores_b), _to_host(seg_b)

        seconds = {"entity_match": search.seconds,
                   "symbolic": select.seconds,
                   "refine": verify.seconds, "temporal": chain.seconds}
        with obs.span("engine.results"):
            results = []
            for qi, p in enumerate(plans):
                s_q, g_q = topk_prefix(scores_np[qi], seg_np[qi],
                                       p.temporal.top_k)
                keep = s_q > 0
                stats[qi].frames_scanned_equivalent = (st.num_segments
                                                       * st.frames_per_segment)
                stats[qi].stage_seconds = dict(seconds)
                results.append(QueryResult(
                    segments=[int(v) for v in g_q[keep]],
                    scores=[int(x) for x in s_q[keep]],
                    end_frames=_to_host(matched[qi][1]),
                    sql_renderer=renderers[qi],
                    stats=stats[qi],
                    degraded=stats[qi].degraded,
                    unverified=stats[qi].unverified_rows,
                ))
        return results

    # -- refinement helpers ------------------------------------------------------
    def _verify_rows(self, rel: Table, masks_np: np.ndarray
                     ) -> Optional[Tuple[np.ndarray, np.ndarray,
                                         np.ndarray, dict]]:
        """Verify every relational row under any triple mask, deduped by row
        *content* — identical (vid,fid,sid,rl,oid) rows cost one VLM call no
        matter how many triples (or, in the batched path, queries) touch
        them. Returns ``(keep_rows, uniq, verdict_u, cols)`` where
        ``keep_rows`` is a (capacity,) bool verdict per row index, ``uniq``
        the unique row contents with their per-content ``verdict_u``, and
        ``cols`` is the host copy of the relational columns (so callers
        don't re-transfer them) — or ``None`` if nothing matched."""
        any_mask = masks_np.any(axis=0)
        rows_idx = np.nonzero(any_mask)[0]
        if len(rows_idx) == 0:
            return None
        cols = {k: _to_host(rel[k]) for k in REL_SCHEMA}
        rows = np.stack([cols[k][rows_idx] for k in REL_SCHEMA], axis=1)
        uniq, inv = np.unique(rows, axis=0, return_inverse=True)
        verdict_u = self.verifier.verify(uniq)
        verdicts = verdict_u[inv]
        keep_rows = np.zeros((rel.capacity,), bool)
        keep_rows[rows_idx] = verdicts
        return keep_rows, uniq, verdict_u, cols
