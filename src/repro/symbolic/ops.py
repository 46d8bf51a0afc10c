"""Relational operators on masked tables — the symbolic-search offload target.

TPU-native realizations:
  * ``filter_``     — predicate mask intersection (vectorized select).
  * ``semi_join``   — ``col IN keys`` by direct comparison of every row
                      against the (small) key set, a block of keys at a
                      time (``isin``); no sort, search or gather.
  * ``equi_join``   — sort-merge join with a declared output capacity and an
                      overflow flag (never silently drops).
  * ``distinct_pairs`` / ``scatter_bitmap`` — group rows into a dense
                      (segment × frame) presence bitmap; conjunction and
                      temporal logic then become bitwise algebra
                      (see ``repro.core.temporal``).

Every operator is jit-compatible, differentiable-free integer work, and
shardable: tables shard over rows (the ``data`` axis); bitmaps over segments.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.symbolic.table import Table

INVALID = jnp.int32(2**31 - 1)

# ``isin`` compares rows against this many keys per loop step. The key axis
# is padded to a multiple of it with ``INVALID``, which never matches.
KEY_BLOCK = 16

# ``isin_pairs`` packs (first, second) id pairs into one int32 as
# first * PAIR_RADIX + second. Both components must stay inside these
# bounds or packed keys collide / overflow and joins are silently wrong —
# the store builders validate ingested ids against them (see
# ``repro.core.stores.validate_pack_bounds``).
PAIR_RADIX = 1 << 15                        # second component: 0 <= x < 2^15
PAIR_FIRST_LIMIT = (2**31) // PAIR_RADIX    # first component:  0 <= x < 2^16


def filter_(t: Table, mask: jax.Array) -> Table:
    return t.with_valid(t.valid & mask)


def filter_eq(t: Table, col: str, value) -> Table:
    return filter_(t, t[col] == value)


def _masked_col(t: Table, col: str) -> jax.Array:
    """Column with invalid rows replaced by a sentinel larger than any id."""
    return jnp.where(t.valid, t[col], INVALID)


def isin(values: jax.Array, keys: jax.Array, keys_valid: jax.Array
         ) -> jax.Array:
    """Vector membership: values[i] ∈ {keys[j] : keys_valid[j]}.

    Blocked direct comparison, O(n·k) compares and O(n) working memory:
    a ``fori_loop`` over blocks of ``KEY_BLOCK`` keys ORs
    ``values == key`` into one (n,) accumulator (``(T, n)`` under
    ``vmap``). Invalid and padding keys become ``INVALID``, and a value
    equal to ``INVALID`` never matches, so neither can. Duplicate keys
    are harmless. Direct comparison keeps the TPU off per-row dynamic
    gathers (a binary search over sorted keys gathers log k times a row);
    the loop keeps XLA from materialising the (k, n) comparison.
    """
    keys = jnp.where(keys_valid, keys, INVALID)
    keys = jnp.pad(keys, (0, -keys.shape[0] % KEY_BLOCK),
                   constant_values=INVALID)

    def block(b, acc):
        blk = jax.lax.dynamic_slice_in_dim(keys, b * KEY_BLOCK, KEY_BLOCK)
        for j in range(KEY_BLOCK):
            acc = acc | (values == blk[j])
        return acc

    hit = jax.lax.fori_loop(0, keys.shape[0] // KEY_BLOCK, block,
                            jnp.zeros(values.shape, bool))
    return hit & (values != INVALID)


def semi_join(t: Table, col: str, keys: jax.Array, keys_valid: jax.Array
              ) -> Table:
    """Keep rows whose ``col`` appears in the (masked) key set."""
    return filter_(t, isin(_masked_col(t, col), keys, keys_valid))


def pack_pairs(first: jax.Array, second: jax.Array,
               radix: int = PAIR_RADIX) -> jax.Array:
    """Radix-pack id pairs into one int32: ``first * radix + second``."""
    return first * radix + second


def isin_pairs(a1: jax.Array, a2: jax.Array, k1: jax.Array, k2: jax.Array,
               keys_valid: jax.Array, radix: int = PAIR_RADIX) -> jax.Array:
    """Membership of pairs (a1, a2) in the masked key-pair set (k1, k2).

    Both sides are radix-packed (:func:`pack_pairs`) and tested by
    :func:`isin`. Pairs are packed into int32 (JAX default has x64
    disabled), so second components must be < ``radix`` and first
    components < 2^31/radix. Callers that test one row set against many
    key sets pack the rows once and call :func:`isin` directly.
    """
    return isin(pack_pairs(a1, a2, radix), pack_pairs(k1, k2, radix),
                keys_valid)


def sort_by(t: Table, col: str) -> Table:
    """Stable sort rows by column (invalid rows to the end)."""
    order = jnp.argsort(_masked_col(t, col), stable=True)
    cols = {k: v[order] for k, v in t.columns.items()}
    return Table(cols, t.valid[order])


def equi_join(a: Table, b: Table, on: str, out_capacity: int,
              suffixes: Tuple[str, str] = ("_a", "_b")
              ) -> Tuple[Table, jax.Array]:
    """Sort-merge equi-join with fixed output capacity.

    Returns (joined table, overflow: bool scalar — True if results were
    truncated). Output schema: join key ``on`` + all other columns of both
    tables (suffixed on collision).
    """
    sa, sb = sort_by(a, on), sort_by(b, on)
    ka, kb = _masked_col(sa, on), _masked_col(sb, on)
    ca, cb = a.capacity, b.capacity

    # For each row i of a: matches in b form the contiguous run
    # [start[i], end[i]) in sorted-b order.
    start = jnp.searchsorted(kb, ka, side="left")
    end = jnp.searchsorted(kb, ka, side="right")
    counts = jnp.where(sa.valid, end - start, 0)
    offsets = jnp.cumsum(counts) - counts            # output slot base per a-row
    total = counts.sum()
    overflow = total > out_capacity

    # Build output rows by inverting: for output slot s, find a-row via
    # searchsorted over offsets, then b-row = start[i] + (s - offsets[i]).
    slots = jnp.arange(out_capacity)
    ai = jnp.searchsorted(offsets, slots, side="right") - 1
    ai = jnp.clip(ai, 0, ca - 1)
    within = slots - offsets[ai]
    bi = start[ai] + within
    row_ok = (slots < total) & (within < counts[ai]) & (bi < cb)
    bi = jnp.clip(bi, 0, cb - 1)

    cols = {}
    for k, v in sa.columns.items():
        name = k if k == on else (k + suffixes[0] if k in sb.columns else k)
        cols[name] = v[ai]
    for k, v in sb.columns.items():
        if k == on:
            continue
        name = k + suffixes[1] if k in sa.columns else k
        cols[name] = v[bi]
    return Table(cols, row_ok), overflow


def group_count(t: Table, col: str, num_groups: int) -> jax.Array:
    """COUNT(*) GROUP BY col, for col ∈ [0, num_groups)."""
    contrib = jnp.where(t.valid, 1, 0)
    return jnp.zeros((num_groups,), jnp.int32).at[
        jnp.clip(t[col], 0, num_groups - 1)].add(contrib)


def scatter_bitmap(t: Table, seg_col: str, frame_col: str,
                   num_segments: int, frames_per_segment: int) -> jax.Array:
    """Dense presence bitmap: out[v, f] = any valid row with (seg=v, frame=f)."""
    v = jnp.clip(t[seg_col], 0, num_segments - 1)
    f = jnp.clip(t[frame_col], 0, frames_per_segment - 1)
    flat = v * frames_per_segment + f
    grid = jnp.zeros((num_segments * frames_per_segment,), bool)
    grid = grid.at[flat].max(t.valid)
    return grid.reshape(num_segments, frames_per_segment)


def gather_rows(t: Table, idx: jax.Array, idx_valid: jax.Array) -> Table:
    idx = jnp.clip(idx, 0, t.capacity - 1)
    cols = {k: v[idx] for k, v in t.columns.items()}
    return Table(cols, idx_valid & t.valid[idx])
