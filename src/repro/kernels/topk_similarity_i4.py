"""Two-phase int4 cold-tier search — exact top-k at ~1/8 the HBM traffic.

The int8 two-phase search (``topk_similarity_i8.py``) holds the hot tier;
this module is the same construction one tier deeper, for segments the
tiered-storage layer has demoted to **cold**: embeddings are stored as
per-row symmetric int4 codes packed two-per-byte (``Int4Rows``), so a
cold sweep reads N·(D/2 + 8) bytes — ~8× less than fp32, ~2× less than
int8 — at the price of a coarser phase-1 ranking.

  * **Phase 1 (approximate, int4).** The Pallas kernel streams packed
    bytes through VMEM, sign-extends each nibble in-register (two shifts,
    no extra HBM traffic), forms the score tile as two int8×int8→int32
    MXU matmuls, even columns against low nibbles and odd against high
    (integer dots are exact, so the split changes no bit),
    rescales to fp32, and keeps a running over-fetched top-k′ in VMEM
    scratch. int4 ranks are noisier than int8, so the overfetch is wider:
    k′ = min(8k, 128).
  * **Phase 2 (exact, fp32).** Identical to the int8 path — candidates'
    fp32 rows are gathered and rescored with the reference contraction
    (``topk_similarity_i8._rescore_exact`` is reused verbatim), so dot
    products round identically to the fp32 oracle.

**Exactness.** The sufficient-overfetch bound in ``topk_similarity_i8``
is width-agnostic: with q = t·q̂ + εq, dbₙ = sₙ·d̂ₙ + εₙ and
round-to-nearest (|εq| ≤ t/2, |εₙ| ≤ sₙ/2 elementwise),

    |q·dbₙ − t·sₙ·(q̂·d̂ₙ)| ≤ t·sₙ·(‖q̂‖₁/2 + ‖d̂ₙ‖₁/2 + D/4)

holds whether the codes are 8- or 4-bit — only the step sizes (and hence
the bound's magnitude) change. ``err`` stores the per-row term for the
int4 scales, the wrapper checks the same quantization-margin certificate
(plus the coverage check) on device, and falls back to the fp32 reference
inside ``lax.cond`` when the margin cannot be certified — so cold-tier
(scores, idx) are **always bitwise equal** to the fp32 reference; the
wider step size only makes the fallback fire more often, never changes a
result. Queries stay int8 (they are few; halving their bytes buys
nothing and would double the query-side error term).
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
from repro.kernels.topk_similarity_i8 import (_BOUND_SLACK, _rescore_exact,
                                              quantize_rows)

OVERFETCH_I4 = 8       # k' = min(OVERFETCH_I4 * k, K_PAD) — int4 is noisier


class Int4Rows(NamedTuple):
    """Per-row symmetric int4 quantization, packed two codes per byte.

    ``packed[n, j]`` holds codes for columns ``2j`` (low nibble) and
    ``2j+1`` (high nibble), two's-complement in [-7, 7]; odd-width
    matrices get one zero-padded phantom column. ``scale[n]`` dequantizes
    (``x[n] ≈ scale[n] * codes[n]``); ``err[n]`` is the precomputed row
    term of the dot-product error bound. NamedTuple ⇒ pytree.
    """

    packed: jax.Array  # (N, ceil(D/2)) int8
    scale: jax.Array   # (N,) fp32
    err: jax.Array     # (N,) fp32


def pack_nibbles(codes: jax.Array) -> jax.Array:
    """(N, D) int codes in [-8, 7] -> (N, ceil(D/2)) int8, two per byte.

    The byte is ``odd * 16 + (even & 0xF)``, which is in int8 range, so the
    Pallas kernel reads the bank as stored and sign-extends each nibble."""
    c = jnp.asarray(codes, jnp.int32)
    if c.shape[1] % 2:
        c = jnp.pad(c, ((0, 0), (0, 1)))
    even, odd = c[:, 0::2], c[:, 1::2]
    return ((even & 0xF) | (odd << 4)).astype(jnp.int8)


def unpack_nibbles(packed: jax.Array) -> jax.Array:
    """(N, D2) int8 -> (N, 2*D2) int8 codes, sign-extended nibbles."""
    p = packed.astype(jnp.int32)
    low = jnp.right_shift(jnp.left_shift(p, 28), 28)    # arithmetic >> 28
    high = jnp.right_shift(jnp.left_shift(p, 24), 28)
    return jnp.stack([low, high], axis=-1) \
              .reshape(p.shape[0], -1).astype(jnp.int8)


def quantize_rows_i4(x: jax.Array) -> Int4Rows:
    """Symmetric per-row int4 quantization with the error-bound row term."""
    x = jnp.asarray(x, jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=1)
    scale = jnp.where(absmax > 0, absmax / 7.0, 1.0)
    codes = jnp.clip(jnp.round(x / scale[:, None]), -7, 7).astype(jnp.int32)
    l1 = jnp.sum(jnp.abs(codes), axis=1).astype(jnp.float32)
    d = x.shape[1]
    err = scale * (l1 / 2.0 + d / 4.0)
    return Int4Rows(pack_nibbles(codes), scale, err)


def dequantize_rows_i4(rows: Int4Rows, d: int) -> jax.Array:
    return (unpack_nibbles(rows.packed)[:, :d].astype(jnp.float32)
            * rows.scale[:, None])


# ---------------------------------------------------------------------------
# phase 1: packed-int4 streaming approximate top-k' (Pallas)
# ---------------------------------------------------------------------------
def _kernel_i4(qe_ref, qo_ref, tq_ref, db_ref, s_ref, valid_ref, sout_ref,
               iout_ref, best_s, best_i, *, kprime: int, blk_n: int,
               n_db_blocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_s[...] = jnp.full_like(best_s, NEG_INF)
        best_i[...] = jnp.zeros_like(best_i)

    # each nibble of the packed int8 bytes is sign-extended with two shifts. The even and odd columns
    # stay apart (q·d = q_even·low + q_odd·high): re-interleaving them
    # is a lane shuffle that Mosaic does not compile in reasonable time.
    p = db_ref[...].astype(jnp.int32)                   # (blk_n, D2)
    low = jnp.right_shift(jnp.left_shift(p, 28), 28).astype(jnp.int8)
    high = jnp.right_shift(jnp.left_shift(p, 24), 28).astype(jnp.int8)
    # explicit precision, as in the int8 kernel
    dot = functools.partial(jax.lax.dot_general,
                            dimension_numbers=(((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.DEFAULT,
                            preferred_element_type=jnp.int32)
    acc = dot(qe_ref[...], low) + dot(qo_ref[...], high)
    s = (acc.astype(jnp.float32) * tq_ref[...]) * s_ref[...]
    valid = valid_ref[...] > 0
    s = jnp.where(valid, s, NEG_INF)
    base = j * blk_n
    gidx = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)

    blk_vals, blk_ids = _extract_topk(s, gidx, kprime)
    merged_s = jnp.concatenate([best_s[...], blk_vals], axis=1)
    merged_i = jnp.concatenate([best_i[...], blk_ids], axis=1)
    best_s[...], best_i[...] = _extract_topk(merged_s, merged_i, kprime)

    @pl.when(j == n_db_blocks - 1)
    def _finalize():
        sout_ref[...] = best_s[...]
        iout_ref[...] = best_i[...]


def topk_i4_phase1(q_codes: jax.Array, q_scale: jax.Array, db: Int4Rows,
                   db_valid: jax.Array, kprime: int, *, blk_q: int = 128,
                   blk_n: Optional[int] = None, interpret: bool = False):
    """Approximate top-k' over packed int4 codes. Returns (scores, idx)
    shaped (Q, k'), same ordering contract as the int8 phase 1."""
    assert kprime <= K_PAD, "phase-1 scratch is K_PAD columns wide"
    Q, D = q_codes.shape
    D2 = db.packed.shape[1]
    if 2 * D2 != D:                    # odd D: phantom zero column
        q_codes = jnp.pad(q_codes, ((0, 0), (0, 2 * D2 - D)))
    N = db.packed.shape[0]
    blk_q = min(blk_q, max(32, Q))
    # the body widens each block to two int32 (blk_n, D2) nibble planes
    blk_n = min(blk_n, N) if blk_n else db_block_rows(N, 8 * D2)
    pad_q = (-Q) % blk_q
    pad_n = (-N) % blk_n
    if pad_q:
        q_codes = jnp.pad(q_codes, ((0, pad_q), (0, 0)))
        q_scale = jnp.pad(q_scale, ((0, pad_q),))
    q_even, q_odd = q_codes[:, 0::2], q_codes[:, 1::2]
    packed, scale, valid = db.packed, db.scale, db_valid
    if pad_n:
        packed = jnp.pad(packed, ((0, pad_n), (0, 0)))
        scale = jnp.pad(scale, ((0, pad_n),))
        valid = jnp.pad(valid, ((0, pad_n),))
    Qp, Np = Q + pad_q, N + pad_n
    nQ, nN = Qp // blk_q, Np // blk_n

    kern = functools.partial(_kernel_i4, kprime=kprime, blk_n=blk_n,
                             n_db_blocks=nN)
    scores, idx = pl.pallas_call(
        kern,
        grid=(nQ, nN),
        in_specs=[
            pl.BlockSpec((blk_q, D2), lambda i, j: (i, 0)),
            pl.BlockSpec((blk_q, D2), lambda i, j: (i, 0)),
            pl.BlockSpec((blk_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((blk_n, D2), lambda i, j: (j, 0)),
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
    )(q_even, q_odd, q_scale[:, None], packed, scale[None, :],
      valid.astype(jnp.int32)[None, :])
    return scores[:Q, :kprime], idx[:Q, :kprime]


def topk_i4_phase1_ref(q_codes, q_scale, db: Int4Rows, db_valid, kprime: int):
    """Pure-jnp phase-1 oracle: identical unpack + math, full score matrix."""
    codes = unpack_nibbles(db.packed)
    if codes.shape[1] != q_codes.shape[1]:
        q_codes = jnp.pad(q_codes,
                          ((0, 0), (0, codes.shape[1] - q_codes.shape[1])))
    acc = jax.lax.dot_general(q_codes, codes, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    s = (acc.astype(jnp.float32) * q_scale[:, None]) * db.scale[None, :]
    s = jnp.where(db_valid[None, :], s, NEG_INF)
    if s.shape[1] < kprime:
        s = jnp.pad(s, ((0, 0), (0, kprime - s.shape[1])),
                    constant_values=NEG_INF)
    return jax.lax.top_k(s, kprime)


# ---------------------------------------------------------------------------
# two-phase wrapper: exact rescore + margin certificate + fallback
# ---------------------------------------------------------------------------
def topk_similarity_i4(queries: jax.Array, db_i4: Int4Rows, db: jax.Array,
                       db_valid: jax.Array, k: int, *, blk_q: int = 128,
                       blk_n: Optional[int] = None, interpret: bool = False,
                       use_kernel_phase1: bool = True):
    """Exact two-phase cold-tier top-k. queries: (Q, D) fp32; db: (N, D)
    fp32 rows backing ``db_i4``. Returns (scores, idx): (Q, k), bitwise
    equal to ``topk_similarity_ref`` (certificate or fallback, always)."""
    from repro.kernels.ref import naive_topk

    kprime = min(OVERFETCH_I4 * k, K_PAD)
    if kprime < k:   # k > K_PAD: scratch can't hold the overfetch
        return naive_topk(queries, db, db_valid, k)

    queries = jnp.asarray(queries, jnp.float32)
    q_rows = quantize_rows(queries)       # queries stay int8 (see docstring)

    if use_kernel_phase1:
        approx, cand_idx = topk_i4_phase1(q_rows.codes, q_rows.scale, db_i4,
                                          db_valid, kprime, blk_q=blk_q,
                                          blk_n=blk_n, interpret=interpret)
    else:
        approx, cand_idx = topk_i4_phase1_ref(q_rows.codes, q_rows.scale,
                                              db_i4, db_valid, kprime)

    finite = approx > NEG_INF / 2
    order = jnp.argsort(cand_idx, axis=1, stable=True)
    cand_sorted = jnp.take_along_axis(cand_idx, order, axis=1)
    finite_sorted = jnp.take_along_axis(finite, order, axis=1)
    vals, idx, _ = _rescore_exact(queries, db, cand_sorted, finite_sorted, k)

    # -- exactness certificate (same construction as int8, int4 scales) -----
    n_valid = jnp.sum(db_valid.astype(jnp.int32))
    enough = n_valid >= k
    covered = n_valid <= kprime
    a_min = approx[:, kprime - 1]
    l1_q = jnp.sum(jnp.abs(q_rows.codes).astype(jnp.int32),
                   axis=1).astype(jnp.float32)
    s_max = jnp.max(jnp.where(db_valid, db_i4.scale, 0.0))
    e_max = jnp.max(jnp.where(db_valid, db_i4.err, 0.0))
    eps_max = q_rows.scale * (l1_q / 2.0 * s_max + e_max)
    eps_max = eps_max * (1.0 + _BOUND_SLACK) + 1e-12
    margin_ok = jnp.all(vals[:, k - 1] > a_min + eps_max)
    ok = enough & (covered | margin_ok)

    return jax.lax.cond(
        ok,
        lambda: (vals, idx),
        lambda: tuple(naive_topk(queries, db, db_valid, k)))
