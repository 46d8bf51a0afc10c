"""Fused stage kernels shared by the physical operators and the batched
multi-query path.

These are the jitted device programs the pipeline stages launch (they lived
inside ``core/executor.py`` before the physical layer existed; the executor
re-exports them for compatibility). Host Python only orchestrates — each
stage's math is one fused program regardless of the number of triples or
queries.

``to_host`` is the package's device→host funnel: it delegates to
``repro.core.executor._to_host`` *at call time* (module-attribute lookup),
so the transfer-spy tests that monkeypatch the executor's funnel observe
every transfer the physical operators make too.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.semantic.search import topk_similarity, topk_similarity_segmented
from repro.symbolic import ops as sops
from repro.symbolic.table import Table


def to_host(x) -> np.ndarray:
    """Device→host transfer, routed through the executor's single funnel."""
    from repro.core import executor as _executor
    return _executor._to_host(x)


# ---------------------------------------------------------------------------
# jitted stage kernels
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("k", "mode", "use_kernels"))
def _entity_match(queries, db, db_i8, db_valid, k: int, mode: str,
                  use_kernels: bool):
    """One fused search launch: mode/kernel dispatch happens at trace time
    (the Pallas kernels run in interpret mode off-TPU), so the engine's
    ``use_kernels``/``search_mode`` flags reach the single-device path too,
    not just the sharded one."""
    return topk_similarity(queries, db, db_valid, k, use_kernels=use_kernels,
                           mode=mode, i8=db_i8)


@partial(jax.jit,
         static_argnames=("k", "mode", "use_kernels", "bounds", "modes"))
def _entity_match_segmented(queries, db, db_i8, db_valid, k: int, mode: str,
                            use_kernels: bool, bounds, db_i4=None, modes=None):
    """Segment-aware search launch: per-segment top-k + fused cross-segment
    merge in ONE jitted program (``bounds``/``modes`` are static, so the
    program recompiles only when the store's segmentation layout or tier
    assignment changes). ``modes[j]`` overrides the scan mode per range —
    the tiered store passes ``"int4"`` for cold segments, backed by
    ``db_i4`` — and results stay bit-identical to :func:`_entity_match`
    over the whole bank."""
    return topk_similarity_segmented(queries, db, db_valid, k, bounds,
                                     use_kernels=use_kernels, mode=mode,
                                     i8=db_i8, i4=db_i4, modes=modes)


@partial(jax.jit, static_argnames=("k", "mode", "use_kernels", "bucket"))
def _entity_match_delta(queries, db, db_i8, db_valid, start, k: int,
                        mode: str, use_kernels: bool, bucket: int):
    """Search only the appended entity rows ``[start, start + bucket)``.

    ``start`` is a traced scalar (no recompile per refresh); ``bucket`` is
    the pow2-padded row count, and the caller must keep
    ``start + bucket <= capacity`` (``dynamic_slice`` would silently clamp
    the start and misalign the index remap otherwise). Rows beyond the
    store's current count are invalid-masked, so the padding never
    surfaces. Returns (scores, global_idx): the delta's exact top-k,
    mergeable with a prior top-k into the global one (see
    ``repro.core.streaming``)."""
    s = jnp.asarray(start, jnp.int32)
    dbs = jax.lax.dynamic_slice_in_dim(db, s, bucket)
    dvs = jax.lax.dynamic_slice_in_dim(db_valid, s, bucket)
    i8s = None
    if db_i8 is not None:
        i8s = type(db_i8)(jax.lax.dynamic_slice_in_dim(db_i8.codes, s, bucket),
                          jax.lax.dynamic_slice_in_dim(db_i8.scale, s, bucket),
                          jax.lax.dynamic_slice_in_dim(db_i8.err, s, bucket))
    scores, idx = topk_similarity(queries, dbs, dvs, min(k, bucket),
                                  use_kernels=use_kernels, mode=mode, i8=i8s)
    return scores, idx + s


@jax.jit
def _predicate_match(queries, pred_emb):
    """Similarity of each relationship text to each predicate label."""
    return jnp.einsum("rd,pd->rp", queries, pred_emb)


def predicate_candidates(embed, pred_emb, texts, m: int, threshold: float):
    """Host (ids, ok, vals) of the runtime predicate match for ``texts``.

    THE single host-side implementation of the embed → ``_predicate_match``
    einsum → top-m → threshold → argmax-always-kept sequence. The
    segment-pruning pass and the streaming path both call it, and its
    bitwise agreement with the device operator (``TopKSearchOp``'s
    predicate branch, which runs the same ops on device) is load-bearing:
    pruning is provable only because the candidate set here IS the one
    execution uses."""
    q_emb = jnp.asarray(embed.embed_texts(list(texts)))
    sims = _predicate_match(q_emb, jnp.asarray(pred_emb))
    vals, ids = jax.lax.top_k(sims, m)
    ok = vals >= threshold
    ok = ok.at[:, 0].set(True)
    return to_host(ids), to_host(ok), to_host(vals)


@partial(jax.jit, static_argnames=())
def _triple_selections(rel_cols_vid, rel_cols_fid, rel_cols_sid, rel_cols_rl,
                       rel_cols_oid, rel_valid,
                       subj_vid, subj_eid, subj_ok,
                       obj_vid, obj_eid, obj_ok,
                       pred_ids, pred_ok):
    """Evaluate all triples' conjunctive selections in one fused program.

    subj_*/obj_*: (T, k) candidate (vid,eid) pairs per triple;
    pred_*: (T, m) candidate predicate labels per triple.
    Returns (T, cap) row masks. Rows are independent, so any row order
    (e.g. the cost-based one) produces per-row bit-identical masks. The
    rows' (vid, sid) and (vid, oid) packs are made once, outside the
    per-triple ``vmap``.
    """
    s_vals = sops.pack_pairs(rel_cols_vid, rel_cols_sid)
    o_vals = sops.pack_pairs(rel_cols_vid, rel_cols_oid)

    def one(svid, seid, sok, ovid, oeid, ook, pid, pok):
        m = rel_valid
        m &= sops.isin(s_vals, sops.pack_pairs(svid, seid), sok)
        m &= sops.isin(o_vals, sops.pack_pairs(ovid, oeid), ook)
        m &= sops.isin(rel_cols_rl, pid, pok)
        return m

    return jax.vmap(one)(subj_vid, subj_eid, subj_ok,
                         obj_vid, obj_eid, obj_ok, pred_ids, pred_ok)


@partial(jax.jit, static_argnames=("bucket",))
def _delta_triple_selections(rel_vid, rel_fid, rel_sid, rel_rl, rel_oid,
                             rel_valid, lo, span, bucket: int,
                             subj_vid, subj_eid, subj_ok,
                             obj_vid, obj_eid, obj_ok, pred_ids, pred_ok):
    """:func:`_triple_selections` over the appended row window
    ``[lo, lo + span)`` only — the incremental path's symbolic stage.

    ``bucket`` is the static pow2-padded window size (``lo + bucket`` must
    stay inside capacity, see ``_entity_match_delta``); ``lo``/``span`` are
    traced scalars so consecutive refreshes with the same bucket reuse one
    compiled program. Rows at ``[span, bucket)`` — spare capacity or a
    pruned neighbor segment's rows — are masked invalid. Returns
    ``(T, bucket)`` masks whose columns are bit-identical to the matching
    columns of a full-table selection (rows are evaluated independently).
    """
    l = jnp.asarray(lo, jnp.int32)
    sl = lambda col: jax.lax.dynamic_slice_in_dim(col, l, bucket)
    valid = sl(rel_valid) & (jnp.arange(bucket) < span)
    vid, rl = sl(rel_vid), sl(rel_rl)
    s_vals = sops.pack_pairs(vid, sl(rel_sid))
    o_vals = sops.pack_pairs(vid, sl(rel_oid))

    def one(svid, seid, sok, ovid, oeid, ook, pid, pok):
        m = valid
        m &= sops.isin(s_vals, sops.pack_pairs(svid, seid), sok)
        m &= sops.isin(o_vals, sops.pack_pairs(ovid, oeid), ook)
        m &= sops.isin(rl, pid, pok)
        return m

    masks = jax.vmap(one)(subj_vid, subj_eid, subj_ok,
                          obj_vid, obj_eid, obj_ok, pred_ids, pred_ok)
    return masks, masks.sum(axis=1)


@partial(jax.jit,
         static_argnames=("bucket", "num_segments", "frames_per_segment"))
def _delta_bitmaps(rel_vid, rel_fid, masks, lo, bucket: int,
                   num_segments: int, frames_per_segment: int):
    """Scatter the delta-window masks into full-grid presence bitmaps.

    Presence is an OR-scatter, so ``old_bitmaps | delta_bitmaps`` over
    append-only rows equals the bitmaps of a full-table scatter — the
    algebra the incremental path's exactness rests on."""
    l = jnp.asarray(lo, jnp.int32)
    vid = jax.lax.dynamic_slice_in_dim(rel_vid, l, bucket)
    fid = jax.lax.dynamic_slice_in_dim(rel_fid, l, bucket)
    return _masks_to_bitmaps(vid, fid, masks, num_segments,
                             frames_per_segment)


@jax.jit
def _or_bitmaps(acc, delta):
    """acc |= delta (the incremental bitmap fold, on device)."""
    return acc | delta


@partial(jax.jit, static_argnames=("gaps",))
def _reach_from_bitmaps(bitmaps, idx, pad, gaps):
    """Frame-spec conjunction + chain DP over a (T, V', F) bitmap block in
    one fused program — the incremental path recomputes reach only for the
    temporal-chain frontier (the vid suffix whose bitmaps changed)."""
    from repro.core import temporal as temporal_lib
    fmaps = _conjoin_bitmaps(bitmaps, idx, pad)
    return temporal_lib.chain_reach(fmaps, gaps)


@partial(jax.jit, static_argnames=("num_segments", "frames_per_segment"))
def _masks_to_bitmaps(rel_vid, rel_fid, masks, num_segments: int,
                      frames_per_segment: int):
    """(T, cap) row masks -> (T, V, F) presence bitmaps."""
    def one(mask):
        t = Table({"vid": rel_vid, "fid": rel_fid}, mask)
        return sops.scatter_bitmap(t, "vid", "fid", num_segments,
                                   frames_per_segment)
    return jax.vmap(one)(masks)


@jax.jit
def _conjoin_bitmaps(bitmaps, idx, pad):
    """Frame-spec conjunction for a whole batch in one fused program.

    bitmaps: (T, V, F); idx/pad: (n_frames, max_triples) — row r ANDs the
    bitmaps of its non-pad triple indices (pad slots act as identity/True).
    Returns (n_frames, V, F).
    """
    sel = bitmaps[idx] | pad[:, :, None, None]
    return sel.all(axis=1)


@jax.jit
def _apply_keep(masks, keep):
    """masks &= keep[None, :] — the verify verdict applied on device."""
    return masks & keep[None, :]


@partial(jax.jit,
         static_argnames=("gaps", "num_segments", "frames_per_segment"))
def _cascade_certificate(rel_vid, rel_fid, masks, keep_conf, keep_opt,
                         idx, pad, gaps, num_segments: int,
                         frames_per_segment: int):
    """The cascade's early-exit certificate as ONE fused program.

    Evaluates the whole post-verify tail (bitmap scatter → frame-spec AND →
    chain DP) twice — once with unverified rows excluded (*confirmed*),
    once included (*optimistic*) — and compares the reach bitmaps. The tail
    is monotone in the masks, so equality proves the remaining unverified
    rows cannot change any output. One launch + one scalar transfer per
    cascade round, instead of an eager op-chain.
    """
    from repro.core import temporal as temporal_lib

    def reach(keep):
        m = masks & keep[None, :]
        bm = _masks_to_bitmaps(rel_vid, rel_fid, m, num_segments,
                               frames_per_segment)
        fm = _conjoin_bitmaps(bm, idx, pad)
        return temporal_lib.chain_reach(fm, gaps)

    return jnp.array_equal(reach(keep_conf), reach(keep_opt))


# ---------------------------------------------------------------------------
# SQL rendering (the paper's "SQL Query Generation" artifact)
# ---------------------------------------------------------------------------
def render_sql(triple_idx: int, subj_pairs, obj_pairs, pred_ids,
               predicates) -> str:
    def pairs_sql(pairs):
        return ", ".join(f"({int(v)},{int(e)})" for v, e in pairs[:8]) + (
            ", ..." if len(pairs) > 8 else "")
    preds = ", ".join(f"'{predicates[int(p)]}'" for p in pred_ids)
    return (
        f"SELECT vid, fid FROM relationships\n"
        f"  WHERE (vid, sid) IN ({pairs_sql(subj_pairs)})\n"
        f"    AND (vid, oid) IN ({pairs_sql(obj_pairs)})\n"
        f"    AND rl IN ({preds})  -- triple {triple_idx}"
    )


def make_sql_renderer(rows: Sequence[int],
                      sv, se, so, ov, oe, oo, pi, po, predicates
                      ) -> Callable[[], List[str]]:
    """Closure rendering a query's SQL from host candidate arrays on demand
    (``QueryResult.sql``). ``rows[i]`` is the absolute row of triple ``i``
    (declaration order) inside the candidate arrays — the cost-based pass
    may have permuted execution order, but SQL always renders in the
    query's own triple order."""
    def render() -> List[str]:
        return [render_sql(i,
                           list(zip(sv[r][so[r]], se[r][so[r]])),
                           list(zip(ov[r][oo[r]], oe[r][oo[r]])),
                           pi[r][po[r]], predicates)
                for i, r in enumerate(rows)]
    return render
