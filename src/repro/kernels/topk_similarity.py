"""Fused similarity + running top-k — the semantic-search hot spot.

The paper's entity matching is "embed the query, scan the store, keep the
best k". Done naively that is a matmul producing a (Q, N) score matrix written
to HBM and a separate top-k pass reading it back — 2·Q·N·4 bytes of avoidable
traffic. This kernel streams DB blocks through VMEM, computes the score tile
on the MXU, and folds it into a running sorted top-k held in VMEM scratch, so
HBM sees only the DB read (plus Q·k outputs): arithmetic intensity goes from
~2 FLOP/byte to ~2·Q FLOP/byte.

Selection is a k-step extract built from row reductions and selects only
(no sort and no gather: Mosaic cannot lower the gather that
``take_along_axis`` becomes, which a v5e compile showed). The score matmul
runs at ``HIGHEST`` precision, as fp32 and not as one bf16 pass. DB blocks
are sized from D so that they fit v5e's scoped VMEM. k ≤ 128: ``ops``
refuses a larger k, and the engine's search scans such ranges in fp32
(``repro.semantic.search.range_mode``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
K_PAD = 128  # scratch column width (TPU lane alignment)
# VMEM one DB block may take, counted at the width the kernel body computes
# on. Pallas double-buffers each input block, and v5e's default scoped VMEM
# is 16 MiB: an fp32 (1024, 4096) block alone is 16 MiB and does not fit.
DB_BLOCK_BYTES = 4 << 20


def db_block_rows(n: int, row_bytes: int) -> int:
    """DB rows per grid step: the largest power of two in [128, 1024] whose
    block stays within ``DB_BLOCK_BYTES``, capped at the row count ``n``."""
    rows = 1024
    while rows > 128 and rows * row_bytes > DB_BLOCK_BYTES:
        rows //= 2
    return min(rows, n)


def _extract_topk(s: jax.Array, idx: jax.Array, k: int):
    """Rowwise top-k of s (R, C) with global indices idx (R, C).

    Returns (vals (R, K_PAD), ids (R, K_PAD)) — first k columns meaningful,
    sorted descending. Each of the k steps takes the row max, finds the
    lowest column holding it (a min over a masked column iota, so ties
    break to the lowest column exactly as ``jnp.argmax`` and
    ``lax.top_k`` do), reads that column's id with a masked sum and knocks
    the column out. Only reductions and selects: Mosaic has no lowering
    for the gather that ``take_along_axis`` becomes. The steps run as a
    ``fori_loop`` so that k = 128 does not unroll into the kernel body.
    """
    R, C = s.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (R, C), 1)
    out_cols = jax.lax.broadcasted_iota(jnp.int32, (R, K_PAD), 1)

    def step(t, carry):
        s, vals, ids = carry
        m = s.max(axis=1, keepdims=True)                       # (R, 1)
        am = jnp.min(jnp.where(s == m, cols, C), axis=1, keepdims=True)
        hit = cols == am                                       # one per row
        gi = jnp.sum(jnp.where(hit, idx, 0), axis=1, keepdims=True)
        vals = jnp.where(out_cols == t, m, vals)
        ids = jnp.where(out_cols == t, gi, ids)
        return jnp.where(hit, NEG_INF, s), vals, ids

    init = (s, jnp.full((R, K_PAD), NEG_INF, jnp.float32),
            jnp.zeros((R, K_PAD), jnp.int32))
    _, vals, ids = jax.lax.fori_loop(0, k, step, init)
    return vals, ids


def _kernel(q_ref, db_ref, valid_ref, sout_ref, iout_ref,
            best_s, best_i, *, k: int, blk_n: int, n_db_blocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_s[...] = jnp.full_like(best_s, NEG_INF)
        best_i[...] = jnp.zeros_like(best_i)

    q = q_ref[...].astype(jnp.float32)                      # (blk_q, D)
    db = db_ref[...].astype(jnp.float32)                    # (blk_n, D)
    # HIGHEST: Mosaic's default contracts fp32 operands at lower precision
    s = jax.lax.dot_general(q, db, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    valid = valid_ref[...] > 0                              # (1, blk_n)
    s = jnp.where(valid, s, NEG_INF)
    base = j * blk_n
    gidx = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)

    blk_vals, blk_ids = _extract_topk(s, gidx, k)           # (blk_q, K_PAD)
    merged_s = jnp.concatenate([best_s[...], blk_vals], axis=1)
    merged_i = jnp.concatenate([best_i[...], blk_ids], axis=1)
    best_s[...], best_i[...] = _extract_topk(merged_s, merged_i, k)

    @pl.when(j == n_db_blocks - 1)
    def _finalize():
        sout_ref[...] = best_s[...]
        iout_ref[...] = best_i[...]


def topk_similarity(queries: jax.Array, db: jax.Array, db_valid: jax.Array,
                    k: int, *, blk_q: int = 128, blk_n: Optional[int] = None,
                    interpret: bool = False):
    """queries: (Q, D); db: (N, D); db_valid: (N,). Returns (scores, idx) (Q, k).

    Exact, sorted descending; invalid rows never surface (score -inf).
    ``blk_n`` defaults to :func:`db_block_rows` for fp32 rows of width D.
    """
    assert k <= K_PAD, "kernel supports k <= 128; use ref for larger"
    Q, D = queries.shape
    N = db.shape[0]
    blk_q = min(blk_q, max(8, Q))
    blk_n = min(blk_n, N) if blk_n else db_block_rows(N, 4 * D)
    pad_q = (-Q) % blk_q
    pad_n = (-N) % blk_n
    if pad_q:
        queries = jnp.pad(queries, ((0, pad_q), (0, 0)))
    if pad_n:
        db = jnp.pad(db, ((0, pad_n), (0, 0)))
        db_valid = jnp.pad(db_valid, ((0, pad_n),))
    Qp, Np = Q + pad_q, N + pad_n
    nQ, nN = Qp // blk_q, Np // blk_n

    kern = functools.partial(_kernel, k=k, blk_n=blk_n, n_db_blocks=nN)
    scores, idx = pl.pallas_call(
        kern,
        grid=(nQ, nN),
        in_specs=[
            pl.BlockSpec((blk_q, D), lambda i, j: (i, 0)),
            pl.BlockSpec((blk_n, D), lambda i, j: (j, 0)),
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
    )(queries, db, db_valid.astype(jnp.int32)[None, :])
    return scores[:Q, :k], idx[:Q, :k]
