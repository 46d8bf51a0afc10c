"""Two-phase int8 entity search — exact top-k at ~1/4 the HBM traffic.

The fp32 kernel in ``topk_similarity.py`` already fuses scoring and
selection, so its HBM cost is the DB read itself: N·D·4 bytes per sweep.
This module attacks that remaining term the way Zelda-style systems rank
cheap candidates before expensive work:

  * **Phase 1 (approximate, int8).** Entity embeddings are stored as
    per-row symmetric int8 codes plus one fp32 scale per row
    (:func:`quantize_rows`). The Pallas kernel streams int8 DB blocks
    through VMEM, forms the score tile as an int8×int8→int32 MXU matmul
    (integer dot products are exact — no accumulation rounding), rescales
    to fp32, and keeps a running over-fetched top-k′ in VMEM scratch,
    k′ = min(4k, 128). HBM sees N·(D + 8) bytes — ~4× less than fp32.
  * **Phase 2 (exact, fp32).** The k′ candidates' fp32 rows are gathered
    and rescored in one small fused program, and the final (scores, idx)
    at k are re-ranked from the exact scores.

**Sufficient-overfetch argument.** Phase 2 is exact iff every true top-k
row is among the k′ candidates. Write q = t·q̂ + εq and dbₙ = sₙ·d̂ₙ + εₙ
with |εq| ≤ t/2, |εₙ| ≤ sₙ/2 elementwise (round-to-nearest). Then

    |q·dbₙ − t·sₙ·(q̂·d̂ₙ)| ≤ t·sₙ·(‖q̂‖₁/2 + ‖d̂ₙ‖₁/2 + D/4) =: ε(q, n)

— a bound computable from stored per-row statistics (``err`` folds the
sₙ·(‖d̂ₙ‖₁/2 + D/4) term). Every non-candidate row's approximate score is
≤ A_min (the k′-th kept score), so its exact score is ≤ A_min + ε_max.
If the k-th *exact* candidate score S_k satisfies S_k > A_min + ε_max,
no non-candidate can reach the top-k (strict: boundary ties are pushed to
the fallback) and the two-phase result equals brute-force fp32. The
wrapper checks exactly this **quantization margin** on device — plus a
coverage check (k′ ≥ #valid rows makes phase 1 lossless) — and falls back
to the fp32 reference inside ``lax.cond`` when neither holds, so the
returned (scores, idx) are **always exact**, pinned bitwise against
``topk_similarity_ref`` in the test suite.

Tie-breaking matches ``jax.lax.top_k`` (lowest index wins): candidates
are sorted by global index before the rescore so positional ties resolve
in index order, and the rescore matmul uses the same (M, D)·(N, D)ᵀ
contraction shape as the reference so the fp32 dot products round
identically (bitwise, for contraction depths the backend reduces in one
panel — D ≤ 128 on current XLA CPU; larger D stays exact up to
reduction-order ulps and is still covered by the margin's fallback
semantics, see docs/performance.md).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.topk_similarity import (K_PAD, NEG_INF, _extract_topk,
                                           db_block_rows)

OVERFETCH = 4          # k' = min(OVERFETCH * k, K_PAD)
# fp multiply slop on the analytic bound (the bound itself is exact real
# arithmetic; the scores it brackets are computed in fp32)
_BOUND_SLACK = 1e-4


class Int8Rows(NamedTuple):
    """Per-row symmetric int8 quantization of a (N, D) embedding matrix.

    ``codes[n] ≈ x[n] / scale[n]`` in int8; ``err[n]`` is the precomputed
    row term of the dot-product error bound (see module docstring).
    NamedTuple ⇒ already a pytree; flows through jit/shard_map untouched.
    """

    codes: jax.Array   # (N, D) int8
    scale: jax.Array   # (N,)  fp32
    err: jax.Array     # (N,)  fp32


def quantize_rows(x: jax.Array) -> Int8Rows:
    """Symmetric per-row int8 quantization with the error-bound row term."""
    x = jnp.asarray(x, jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    codes = jnp.clip(jnp.round(x / scale[:, None]), -127, 127).astype(jnp.int8)
    l1 = jnp.sum(jnp.abs(codes).astype(jnp.int32), axis=1).astype(jnp.float32)
    d = x.shape[1]
    err = scale * (l1 / 2.0 + d / 4.0)
    return Int8Rows(codes, scale, err)


def dequantize_rows(rows: Int8Rows) -> jax.Array:
    return rows.codes.astype(jnp.float32) * rows.scale[:, None]


# ---------------------------------------------------------------------------
# phase 1: int8 streaming approximate top-k' (Pallas)
# ---------------------------------------------------------------------------
def _kernel_i8(q_ref, tq_ref, db_ref, s_ref, valid_ref, sout_ref, iout_ref,
               best_s, best_i, *, kprime: int, blk_n: int, n_db_blocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_s[...] = jnp.full_like(best_s, NEG_INF)
        best_i[...] = jnp.zeros_like(best_i)

    q = q_ref[...]                                          # (blk_q, D) int8
    db = db_ref[...]                                        # (blk_n, D) int8
    # integer dot products are exact: the MXU accumulates int8 pairs in
    # int32, so phase-1 scores carry no reduction rounding at all
    # an explicit precision: under default_matmul_precision("highest")
    # Mosaic would be asked for an fp32 contraction of int8 operands
    acc = jax.lax.dot_general(q, db, (((1,), (1,)), ((), ())),
                              precision=jax.lax.Precision.DEFAULT,
                              preferred_element_type=jnp.int32)
    s = (acc.astype(jnp.float32) * tq_ref[...]) * s_ref[...]
    valid = valid_ref[...] > 0                              # (1, blk_n)
    s = jnp.where(valid, s, NEG_INF)
    base = j * blk_n
    gidx = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)

    blk_vals, blk_ids = _extract_topk(s, gidx, kprime)      # (blk_q, K_PAD)
    merged_s = jnp.concatenate([best_s[...], blk_vals], axis=1)
    merged_i = jnp.concatenate([best_i[...], blk_ids], axis=1)
    best_s[...], best_i[...] = _extract_topk(merged_s, merged_i, kprime)

    @pl.when(j == n_db_blocks - 1)
    def _finalize():
        sout_ref[...] = best_s[...]
        iout_ref[...] = best_i[...]


def topk_i8_phase1(q_codes: jax.Array, q_scale: jax.Array, db: Int8Rows,
                   db_valid: jax.Array, kprime: int, *, blk_q: int = 128,
                   blk_n: Optional[int] = None, interpret: bool = False):
    """Approximate top-k' over int8 codes. Returns (scores, idx) (Q, k').

    Scores are the dequantized int32 dot products (sorted descending,
    lowest-index tie-break — same order ``lax.top_k`` would produce over
    the full approximate score matrix); invalid rows never surface.
    """
    assert kprime <= K_PAD, "phase-1 scratch is K_PAD columns wide"
    Q, D = q_codes.shape
    N = db.codes.shape[0]
    # int8 tiles want >= 32 sublanes; interpret mode doesn't care, compiled
    # mode gets a properly padded block either way
    blk_q = min(blk_q, max(32, Q))
    blk_n = min(blk_n, N) if blk_n else db_block_rows(N, D)
    pad_q = (-Q) % blk_q
    pad_n = (-N) % blk_n
    if pad_q:
        q_codes = jnp.pad(q_codes, ((0, pad_q), (0, 0)))
        q_scale = jnp.pad(q_scale, ((0, pad_q),))
    codes, scale, valid = db.codes, db.scale, db_valid
    if pad_n:
        codes = jnp.pad(codes, ((0, pad_n), (0, 0)))
        scale = jnp.pad(scale, ((0, pad_n),))
        valid = jnp.pad(valid, ((0, pad_n),))
    Qp, Np = Q + pad_q, N + pad_n
    nQ, nN = Qp // blk_q, Np // blk_n

    kern = functools.partial(_kernel_i8, kprime=kprime, blk_n=blk_n,
                             n_db_blocks=nN)
    scores, idx = pl.pallas_call(
        kern,
        grid=(nQ, nN),
        in_specs=[
            pl.BlockSpec((blk_q, D), lambda i, j: (i, 0)),
            pl.BlockSpec((blk_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((blk_n, D), lambda i, j: (j, 0)),
            pl.BlockSpec((1, blk_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, blk_n), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((blk_q, K_PAD), lambda i, j: (i, 0)),
            pl.BlockSpec((blk_q, K_PAD), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qp, K_PAD), jnp.float32),
            jax.ShapeDtypeStruct((Qp, K_PAD), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, K_PAD), jnp.float32),
            pltpu.VMEM((blk_q, K_PAD), jnp.int32),
        ],
        interpret=interpret,
    )(q_codes, q_scale[:, None], codes, scale[None, :],
      valid.astype(jnp.int32)[None, :])
    return scores[:Q, :kprime], idx[:Q, :kprime]


def topk_i8_phase1_ref(q_codes, q_scale, db: Int8Rows, db_valid, kprime: int):
    """Pure-jnp phase-1 oracle: identical math, full score materialization.

    Bitwise-comparable with the kernel: the int32 dot is exact and the
    rescale multiplies in the same order.
    """
    acc = jax.lax.dot_general(q_codes, db.codes, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    s = (acc.astype(jnp.float32) * q_scale[:, None]) * db.scale[None, :]
    s = jnp.where(db_valid[None, :], s, NEG_INF)
    if s.shape[1] < kprime:        # tiny DB: pad junk slots like the kernel
        s = jnp.pad(s, ((0, 0), (0, kprime - s.shape[1])),
                    constant_values=NEG_INF)
    return jax.lax.top_k(s, kprime)


# ---------------------------------------------------------------------------
# phase 2: exact rescore + margin-checked re-rank (one fused program)
# ---------------------------------------------------------------------------
_RESCORE_BLK = 8   # queries per rescore tile (fixed gemm shape, see below)


def _rescore_exact(queries, db, cand_idx, cand_finite, k: int):
    """Gather candidates' fp32 rows and rescore with the reference's own
    (M, D)·(N, D)ᵀ contraction so dot products round identically.

    A naive single contraction over all candidates would score every query
    against every *other* query's candidates too (O(Q²k′D) with a
    (Q, Q·k′) intermediate), so queries are processed in tiles of
    ``_RESCORE_BLK``: each tile is one (tile, D)·(tile·k′, D)ᵀ gemm, the
    same 2-D contraction class as the oracle's, capping cost at ~blk× the
    minimum. Measured on XLA CPU, per-element gemm rounding is insensitive
    to either operand's row count for ≥ 2 lhs rows; only the 1-row gemv
    lowers differently — so a lone query stays a single 1-row tile (the
    oracle is a gemv then too) and multi-query tails are kept ≥ 2 rows by
    letting the last tile absorb a 1-row remainder.

    Candidates arrive sorted by ascending global index, so ``lax.top_k``'s
    positional tie-break reproduces the reference's lowest-index-first
    order. Non-finite (junk-padding) slots rescore to -inf.
    """
    Q, kp = cand_idx.shape
    q32 = queries.astype(jnp.float32)
    tiles = []
    lo = 0
    while lo < Q:
        n = _RESCORE_BLK if Q - lo >= _RESCORE_BLK + 2 else Q - lo
        flat = db[cand_idx[lo:lo + n].reshape(-1)]          # (n*kp, D)
        s_all = jnp.einsum("qd,md->qm", q32[lo:lo + n],
                           flat.astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST)  # (n, n*kp)
        take = (jnp.arange(n, dtype=jnp.int32)[:, None] * kp
                + jnp.arange(kp, dtype=jnp.int32)[None, :])
        tiles.append(jnp.take_along_axis(s_all, take, axis=1))
        lo += n
    exact = jnp.concatenate(tiles, axis=0) if len(tiles) > 1 else tiles[0]
    exact = jnp.where(cand_finite, exact, -jnp.inf)         # (Q, kp)
    vals, pos = jax.lax.top_k(exact, k)
    idx = jnp.take_along_axis(cand_idx, pos, axis=1)
    return vals, idx, exact


def topk_similarity_i8(queries: jax.Array, db_i8: Int8Rows, db: jax.Array,
                       db_valid: jax.Array, k: int, *, blk_q: int = 128,
                       blk_n: Optional[int] = None, interpret: bool = False,
                       use_kernel_phase1: bool = True):
    """Exact two-phase top-k. queries: (Q, D) fp32; db: (N, D) fp32 rows
    backing ``db_i8``. Returns (scores, idx): (Q, k), bit-comparable with
    :func:`repro.semantic.search.topk_similarity_ref` (see module
    docstring for the exactness argument and the D-depth caveat).
    """
    from repro.kernels.ref import naive_topk

    kprime = min(OVERFETCH * k, K_PAD)
    if kprime < k:   # k > K_PAD: scratch can't hold the overfetch
        return naive_topk(queries, db, db_valid, k)

    queries = jnp.asarray(queries, jnp.float32)
    q_rows = quantize_rows(queries)

    if use_kernel_phase1:
        approx, cand_idx = topk_i8_phase1(q_rows.codes, q_rows.scale, db_i8,
                                          db_valid, kprime, blk_q=blk_q,
                                          blk_n=blk_n, interpret=interpret)
    else:
        approx, cand_idx = topk_i8_phase1_ref(q_rows.codes, q_rows.scale,
                                              db_i8, db_valid, kprime)

    # junk slots (fewer than k' valid rows) carry NEG_INF and arbitrary,
    # possibly duplicate indices — mask them out of the rescore
    finite = approx > NEG_INF / 2
    order = jnp.argsort(cand_idx, axis=1, stable=True)
    cand_sorted = jnp.take_along_axis(cand_idx, order, axis=1)
    finite_sorted = jnp.take_along_axis(finite, order, axis=1)
    vals, idx, _ = _rescore_exact(queries, db, cand_sorted, finite_sorted, k)

    # -- exactness certificate ------------------------------------------------
    n_valid = jnp.sum(db_valid.astype(jnp.int32))
    enough = n_valid >= k           # no -inf slots in the final k
    covered = n_valid <= kprime     # every valid row is a candidate
    # quantization margin: S_k must clear the best possible non-candidate
    a_min = approx[:, kprime - 1]                       # k'-th approx score
    l1_q = jnp.sum(jnp.abs(q_rows.codes).astype(jnp.int32),
                   axis=1).astype(jnp.float32)
    s_max = jnp.max(jnp.where(db_valid, db_i8.scale, 0.0))
    e_max = jnp.max(jnp.where(db_valid, db_i8.err, 0.0))
    eps_max = q_rows.scale * (l1_q / 2.0 * s_max + e_max)
    eps_max = eps_max * (1.0 + _BOUND_SLACK) + 1e-12
    margin_ok = jnp.all(vals[:, k - 1] > a_min + eps_max)
    ok = enough & (covered | margin_ok)

    return jax.lax.cond(
        ok,
        lambda: (vals, idx),
        lambda: tuple(naive_topk(queries, db, db_valid, k)))
