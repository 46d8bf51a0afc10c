"""Semantic search: exact batched top-k similarity over the Entity Store.

Single-device path: fused scores + top-k. ``mode`` selects the scan
precision — ``"fp32"`` brute-force (Pallas kernel or jnp oracle) or
``"int8"`` two-phase (streaming int8 approximate top-k′, then exact fp32
rescore of the candidates — ~4× less HBM read, still exact; see
``repro.kernels.topk_similarity_i8``). Kernel entry points go through
``repro.kernels.ops`` dispatch, so non-TPU backends run the kernels in
interpret mode and ``REPRO_FORCE_REF=1`` pins the jnp oracles.

Distributed path: DB rows sharded over the ``data`` (and ``pod``) mesh
axes via ``shard_map`` — each shard computes a local top-k (either mode;
the int8 banks shard row-wise exactly like the fp32 rows), the k·n_shards
partials are all-gathered, and a final top-k merges them. Exact (not ANN):
on the MXU the Q·DBᵀ matmul is compute-cheap and fully regular, which beats
graph-traversal ANN structures on TPU for per-shard DB sizes in the millions.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.kernels.topk_similarity import K_PAD

# "int4" is the cold-tier scan: engines select it *per segment* (via the
# ``modes`` arguments below) when the tiered-storage layer has demoted a
# segment, never as a whole-engine mode — hot segments keep the engine's
# configured mode.
SEARCH_MODES = ("fp32", "int8", "int4")


def range_mode(mode: str, k: int) -> str:
    """The scan one range of top-``k`` runs: every top-k kernel holds at
    most ``K_PAD`` results per query, so a wider top-k scans fp32 through
    :func:`topk_similarity_ref` whatever the range's mode (EXPLAIN shows
    it, see ``EntityMatch``). Both answers are the same exact top-k."""
    return "fp32" if k > K_PAD else mode


def topk_similarity_ref(queries: jax.Array, db: jax.Array, db_valid: jax.Array,
                        k: int) -> Tuple[jax.Array, jax.Array]:
    """queries: (Q, D) and db: (N, D) L2-normalized. Returns (scores, idx): (Q, k).

    Invalid DB rows score -inf. The scores are fp32 dot products on every
    backend: without ``HIGHEST`` a TPU contracts fp32 in bf16 passes.
    """
    scores = jnp.einsum("qd,nd->qn", queries.astype(jnp.float32),
                        db.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jnp.where(db_valid[None, :], scores, -jnp.inf)
    return jax.lax.top_k(scores, k)


def topk_similarity(queries, db, db_valid, k: int, *, use_kernels: bool = False,
                    mode: str = "fp32", i8=None, i4=None):
    """Mode/kernel dispatch for one device. ``i8``/``i4`` are the store's
    quantized banks backing ``db`` (required for the matching mode).
    A ``k`` beyond the kernels' width scans fp32 (:func:`range_mode`)."""
    if mode not in SEARCH_MODES:
        raise ValueError(f"unknown search mode {mode!r}; one of {SEARCH_MODES}")
    if k > K_PAD:
        return topk_similarity_ref(queries, db, db_valid, k)
    if mode == "int8":
        if i8 is None:
            raise ValueError("mode='int8' needs the store's Int8Rows bank "
                             "(build_entity_store creates it)")
        from repro.kernels import ops as kops
        return kops.topk_similarity_i8(queries, i8, db, db_valid, k)
    if mode == "int4":
        if i4 is None:
            raise ValueError("mode='int4' needs the store's Int4Rows bank "
                             "(ensure_int4_banks builds it on demotion)")
        from repro.kernels import ops as kops
        return kops.topk_similarity_i4(queries, i4, db, db_valid, k)
    if use_kernels:
        from repro.kernels import ops as kops
        return kops.topk_similarity(queries, db, db_valid, k)
    return topk_similarity_ref(queries, db, db_valid, k)


def _slice_rows(bank, start, stop):
    """Row-slice a quantized bank pytree (Int8Rows/Int4Rows) — per-row
    quantization makes the slice *be* the range's own bank."""
    if bank is None:
        return None
    return type(bank)(*(jax.lax.slice_in_dim(f, start, stop) for f in bank))


def topk_similarity_segmented(queries, db, db_valid, k: int, bounds,
                              *, use_kernels: bool = False,
                              mode: str = "fp32", i8=None, i4=None,
                              modes=None):
    """Per-segment top-k with a fused cross-segment merge — bit-identical
    to one monolithic ``topk_similarity`` sweep.

    ``bounds`` is the store's ``entity_search_bounds``: contiguous
    ``(start, stop)`` row ranges covering the whole bank. Each range runs
    its own top-``min(k, size)`` (any mode; the quantized banks slice
    row-wise, exactly like the fp32 rows — per-row quantization makes the
    slice *be* the segment's bank), local indices are remapped to global
    rows by adding the range start, and one final ``lax.top_k`` merges the
    partials. ``modes`` optionally overrides the scan mode per range
    (``modes[j]`` for ``bounds[j]`` — the tiered store passes ``"int4"``
    for cold segments); ranges without an override use ``mode``.
    Exactness: any global top-k row is inside its own segment's
    top-k; partials concatenate in ascending-global-index order and
    ``lax.top_k`` breaks ties by position, so the merged (scores, idx)
    reproduce the monolithic scan's lowest-index-first tie order bitwise —
    every mode's per-range result is itself bitwise equal to the fp32
    scan of that range (two-phase certificate/fallback), so mixing modes
    across ranges cannot change a single merged bit.
    Intended to be called under jit with static ``bounds``/``modes`` (see
    ``repro.core.physical.stages._entity_match_segmented``).
    """
    if len(bounds) <= 1:
        only = modes[0] if modes else mode
        return topk_similarity(queries, db, db_valid, k,
                               use_kernels=use_kernels, mode=only,
                               i8=i8, i4=i4)
    parts_s, parts_i = [], []
    for j, (start, stop) in enumerate(bounds):
        size = stop - start
        m = modes[j] if modes else mode
        dbs = jax.lax.slice_in_dim(db, start, stop)
        dvs = jax.lax.slice_in_dim(db_valid, start, stop)
        i8s = _slice_rows(i8, start, stop) if m == "int8" else None
        i4s = _slice_rows(i4, start, stop) if m == "int4" else None
        s, i = topk_similarity(queries, dbs, dvs, min(k, size),
                               use_kernels=use_kernels, mode=m,
                               i8=i8s, i4=i4s)
        parts_s.append(s)
        parts_i.append(i + start)
    cat_s = jnp.concatenate(parts_s, axis=1)
    cat_i = jnp.concatenate(parts_i, axis=1)
    vals, pos = jax.lax.top_k(cat_s, k)
    return vals, jnp.take_along_axis(cat_i, pos, axis=1)


def sharded_topk_similarity(queries, db, db_valid, k: int, mesh,
                            shard_axes=("data",), *, use_kernels: bool = False,
                            mode: str = "fp32", i8=None, i4=None):
    """Distributed exact top-k. db rows sharded over ``shard_axes``.

    Returns (scores, global_idx): (Q, k) — indices are into the logical
    (unsharded) DB. Each shard's local top-k is exact (both modes), so the
    all-gather + merge of partials is exact too.

    Row counts that don't divide the shard count are padded with
    invalid-masked rows (they score -inf and can only surface on slots a
    monolithic scan would also leave -inf), and a shard holding fewer than
    ``k`` rows contributes its full row count — ``n_shards·min(k, n_local)``
    gathered partials always cover the global top-k when ``k ≤ N``.
    """
    n_shards = 1
    for a in shard_axes:
        n_shards *= int(mesh.shape[a])
    n = db.shape[0]
    pad = (-n) % n_shards
    if pad:
        # invalid-masked padding: -inf scores, never beat a valid row
        db = jnp.pad(db, ((0, pad), (0, 0)))
        db_valid = jnp.pad(db_valid, (0, pad))
        if i8 is not None:
            i8 = type(i8)(jnp.pad(i8.codes, ((0, pad), (0, 0))),
                          jnp.pad(i8.scale, (0, pad)),
                          jnp.pad(i8.err, (0, pad)))
        if i4 is not None:
            i4 = type(i4)(jnp.pad(i4.packed, ((0, pad), (0, 0))),
                          jnp.pad(i4.scale, (0, pad)),
                          jnp.pad(i4.err, (0, pad)))
    n_local = (n + pad) // n_shards
    k_local = min(k, n_local)

    def local(q, dbs, dvs, i8s, i4s):
        s, i = topk_similarity(q, dbs, dvs, k_local, use_kernels=use_kernels,
                               mode=mode, i8=i8s, i4=i4s)
        # global index = shard offset + local index
        ax_index = jax.lax.axis_index(shard_axes)
        offset = ax_index * n_local
        gi = i + offset
        # gather partials from all shards: (n_shards*k_local,) per query
        s_all = jax.lax.all_gather(s, shard_axes, axis=1, tiled=True)
        i_all = jax.lax.all_gather(gi, shard_axes, axis=1, tiled=True)
        sm, im = jax.lax.top_k(s_all, k)
        final_i = jnp.take_along_axis(i_all, im, axis=1)
        return sm, final_i

    spec_db = P(shard_axes)
    # the quantized banks shard row-wise alongside the fp32 rows; None
    # (unused mode) is an empty pytree and needs no spec entries
    i8_spec = jax.tree_util.tree_map(lambda _: spec_db, i8)
    i4_spec = jax.tree_util.tree_map(lambda _: spec_db, i4)
    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(), spec_db, spec_db, i8_spec, i4_spec),
                   out_specs=(P(), P()),
                   check_replication=False)  # holds post all-gather+merge
    return fn(queries, db, db_valid, i8, i4)


# ---------------------------------------------------------------------------
# placed segment execution: per-device segment-local top-k + fused merge
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("k", "mode", "use_kernels"))
def _segment_local_topk(queries, db, db_valid, i8, i4, k: int, mode: str,
                        use_kernels: bool):
    """One segment's local top-k, jitted per (shape, k, mode) — runs on
    whichever device its inputs are committed to."""
    return topk_similarity(queries, db, db_valid, k,
                           use_kernels=use_kernels, mode=mode, i8=i8, i4=i4)


def place_segment_banks(db, db_valid, bounds, devices, *, i8=None, i4=None,
                        modes=None, put=None, device_table=None):
    """Slice the global banks into per-segment row ranges and commit each
    slice to its assigned device.

    ``bounds``/``devices`` are parallel: ``bounds[j]`` is the segment's
    ``(start, stop)`` entity-row range (``entity_search_bounds`` order —
    ascending, the last range extended to capacity) and ``devices[j]`` the
    owning device ordinal from the placement pass. Sealed rows are
    append-only and per-row quantization makes a quantized row slice *be*
    the segment's own bank, so a placed slice stays valid for the
    segment's lifetime. ``modes[j]`` (when given) names the scan mode the
    segment will run, so only the bank that mode reads is staged — a cold
    segment ships its packed int4 rows, never an unused int8 copy.
    Returns per-segment tuples
    ``(start, size, device, db_seg, valid_seg, i8_seg, i4_seg)``.
    """
    put = put or jax.device_put
    devs = device_table if device_table is not None else jax.devices()
    banks = []
    for j, ((start, stop), d) in enumerate(zip(bounds, devices)):
        dev = devs[d % len(devs)]
        m = modes[j] if modes else None
        dbs = put(jax.lax.slice_in_dim(db, start, stop), dev)
        dvs = put(jax.lax.slice_in_dim(db_valid, start, stop), dev)
        i8s = i4s = None
        if i8 is not None and (m is None or m == "int8"):
            i8s = type(i8)(
                *(put(jax.lax.slice_in_dim(f, start, stop), dev) for f in i8))
        if i4 is not None and (m is None or m == "int4"):
            i4s = type(i4)(
                *(put(jax.lax.slice_in_dim(f, start, stop), dev) for f in i4))
        banks.append((start, stop - start, dev, dbs, dvs, i8s, i4s))
    return tuple(banks)


def placed_topk_similarity(queries, banks, k: int, *,
                           use_kernels: bool = False, mode: str = "fp32",
                           modes=None, merge_device=None, to_device=None):
    """Sharded segment execution: per-device segment-local top-k + ONE
    fused cross-device merge — bitwise equal to the monolithic sweep.

    ``banks`` is :func:`place_segment_banks` output. Each segment's device
    runs the same local top-``min(k, size)`` the single-device segmented
    path runs (``topk_similarity_segmented``), remaps local indices to
    global rows by adding the segment's start, and ships **only** its
    ``(Q, k')`` score/global-row candidate tuples — never a segment bank
    or a full-capacity mask — to the merge device through ``to_device``.
    Partials concatenate in ascending-global-index (segment) order, so the
    final ``lax.top_k`` reproduces the monolithic scan's lowest-index-first
    tie order; per-segment dots hit the same kernels on identical slices as
    the segmented single-device path, so scores are bitwise identical too.
    ``modes[j]`` (when given) overrides the scan mode per bank — the
    tiered store runs cold segments in ``"int4"`` — without changing a bit
    of the merged result (every mode is exact per range).
    """
    to_device = to_device or jax.device_put
    merge_device = merge_device or jax.devices()[0]
    parts_s, parts_i = [], []
    for j, (start, size, dev, dbs, dvs, i8s, i4s) in enumerate(banks):
        m = modes[j] if modes else mode
        # broadcast the (small) query block to the segment's device
        q_local = jax.device_put(queries, dev)
        s, i = _segment_local_topk(q_local, dbs, dvs, i8s, i4s, min(k, size),
                                   m, use_kernels)
        parts_s.append(to_device(s, merge_device))
        parts_i.append(to_device(i + start, merge_device))
    cat_s = jnp.concatenate(parts_s, axis=1)
    cat_i = jnp.concatenate(parts_i, axis=1)
    vals, pos = jax.lax.top_k(cat_s, k)
    return vals, jnp.take_along_axis(cat_i, pos, axis=1)


def threshold_candidates(scores: jax.Array, idx: jax.Array, threshold: float
                         ) -> Tuple[jax.Array, jax.Array]:
    """Apply the user's similarity threshold; below-threshold slots invalid."""
    ok = scores >= threshold
    return idx, ok


def topk_prefix(scores, idx, k: int):
    """Exact smaller top-k as a prefix of a larger one.

    ``lax.top_k`` rows are sorted descending with index-order tie-breaking,
    so the first ``k`` columns of a top-K result (K >= k) equal
    ``top_k(..., k)`` exactly. The batched query path runs ONE fused top-K at
    the batch-max k and derives each query's smaller-k view with this —
    works on device arrays and host ndarrays alike.
    """
    return scores[..., :k], idx[..., :k]
