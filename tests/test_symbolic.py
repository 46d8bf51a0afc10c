"""Property-based tests: the TPU relational engine vs a Python oracle."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.symbolic import ops as sops
from repro.symbolic.table import Table


def make_table(rows, schema, capacity):
    return Table.from_rows([dict(zip(schema, r)) for r in rows], schema,
                           capacity)


def valid_rows(t: Table, schema):
    v = np.asarray(t.valid)
    cols = {k: np.asarray(t[k]) for k in schema}
    return sorted(tuple(int(cols[k][i]) for k in schema)
                  for i in range(t.capacity) if v[i])


rows_strat = st.lists(
    st.tuples(st.integers(0, 7), st.integers(0, 15)), max_size=24)
keys_strat = st.lists(st.integers(0, 15), max_size=12)


@settings(max_examples=40, deadline=None)
@given(rows=rows_strat, keys=keys_strat)
def test_semi_join_matches_python(rows, keys):
    t = make_table(rows, ("a", "b"), 32)
    karr = np.zeros((16,), np.int32)
    kval = np.zeros((16,), bool)
    karr[: len(keys)] = keys
    kval[: len(keys)] = True
    out = sops.semi_join(t, "b", jnp.asarray(karr), jnp.asarray(kval))
    want = sorted((a, b) for a, b in rows if b in set(keys))
    assert valid_rows(out, ("a", "b")) == want


@settings(max_examples=40, deadline=None)
@given(rows=rows_strat,
       rows2=st.lists(st.tuples(st.integers(0, 7), st.integers(0, 15)),
                      max_size=24))
def test_equi_join_matches_python(rows, rows2):
    a = make_table(rows, ("k", "x"), 32)
    b = make_table(rows2, ("k", "y"), 32)
    joined, overflow = sops.equi_join(a, b, "k", out_capacity=1024)
    got = valid_rows(joined, ("k", "x", "y"))
    want = sorted((ka, x, y) for ka, x in rows for kb, y in rows2
                  if ka == kb)
    assert not bool(overflow)
    assert got == want


def test_equi_join_overflow_flag():
    rows = [(1, i) for i in range(8)]
    a = make_table(rows, ("k", "x"), 16)
    b = make_table(rows, ("k", "y"), 16)
    joined, overflow = sops.equi_join(a, b, "k", out_capacity=16)  # 64 > 16
    assert bool(overflow)


@settings(max_examples=40, deadline=None)
@given(rows=st.lists(st.tuples(st.integers(0, 3), st.integers(0, 7)),
                     max_size=20))
def test_scatter_bitmap(rows):
    t = make_table(rows, ("v", "f"), 32)
    bm = np.asarray(sops.scatter_bitmap(t, "v", "f", 4, 8))
    want = np.zeros((4, 8), bool)
    for v, f in rows:
        want[v, f] = True
    assert (bm == want).all()


@settings(max_examples=40, deadline=None)
@given(rows=rows_strat)
def test_sort_preserves_multiset(rows):
    t = make_table(rows, ("a", "b"), 32)
    s = sops.sort_by(t, "a")
    assert valid_rows(s, ("a", "b")) == valid_rows(t, ("a", "b"))
    av = np.asarray(s["a"])[np.asarray(s.valid)]
    # all valid rows sorted to the front and ordered:
    # sort_by pushes invalid rows to the end
    order_positions = np.nonzero(np.asarray(s.valid))[0]
    assert (np.diff(av) >= 0).all()
    assert (order_positions == np.arange(len(av))).all()


@settings(max_examples=30, deadline=None)
@given(rows=st.lists(st.tuples(st.integers(0, 3), st.integers(0, 3),
                               st.integers(0, 3)), max_size=16),
       pairs=st.lists(st.tuples(st.integers(0, 3), st.integers(0, 3)),
                      max_size=8))
def test_isin_pairs(rows, pairs):
    t = make_table(rows, ("v", "e", "x"), 32)
    k1 = np.zeros((8,), np.int32)
    k2 = np.zeros((8,), np.int32)
    kv = np.zeros((8,), bool)
    for i, (p1, p2) in enumerate(pairs):
        k1[i], k2[i], kv[i] = p1, p2, True
    mask = sops.isin_pairs(t["v"], t["e"], jnp.asarray(k1), jnp.asarray(k2),
                           jnp.asarray(kv))
    got = np.asarray(mask & t.valid)
    pset = set(pairs)
    v, e = np.asarray(t["v"]), np.asarray(t["e"])
    val = np.asarray(t.valid)
    for i in range(32):
        want = bool(val[i]) and (int(v[i]), int(e[i])) in pset
        assert bool(got[i]) == want


@settings(max_examples=30, deadline=None)
@given(rows=rows_strat)
def test_group_count(rows):
    t = make_table(rows, ("g", "x"), 32)
    counts = np.asarray(sops.group_count(t, "g", 8))
    want = np.zeros((8,), np.int64)
    for g, _ in rows:
        want[g] += 1
    assert (counts == want).all()


SENTINEL = 2**31 - 1


def _membership_case(width: int):
    """Rows and keys for one key width: every key is some row's pair; a
    third of the keys are invalid (so they equal row values but must not
    match), the last key duplicates the first, a valid key packs to the
    sentinel, and so does the last row."""
    rng = np.random.default_rng(width)
    n = 300
    a1 = rng.integers(0, 8, n).astype(np.int32)
    a2 = rng.integers(0, 16, n).astype(np.int32)
    a1[-1], a2[-1] = sops.PAIR_FIRST_LIMIT - 1, sops.PAIR_RADIX - 1
    pick = rng.integers(0, n - 1, width)
    k1, k2 = a1[pick].copy(), a2[pick].copy()
    kv = np.arange(width) % 3 != 1
    if width >= 2:
        k1[-1], k2[-1], kv[-1] = k1[0], k2[0], kv[0]
    if width >= 3:
        k1[2], k2[2], kv[2] = a1[-1], a2[-1], True
    return a1, a2, k1, k2, kv


def _set_reference(vals, keys, kv):
    keyset = {int(k) for k, ok in zip(keys, kv) if ok}
    return np.array([int(v) in keyset and int(v) != SENTINEL for v in vals])


@pytest.mark.parametrize("width", [1, 3, 8, 16, 17, 128, 1000])
def test_isin_matches_set_reference(width):
    a1, a2, k1, k2, kv = _membership_case(width)
    vals = a1.astype(np.int64) * sops.PAIR_RADIX + a2
    keys = k1.astype(np.int64) * sops.PAIR_RADIX + k2
    assert vals[-1] == SENTINEL
    for valid in (kv, np.zeros(width, bool)):
        want = _set_reference(vals, keys, valid)
        got_pairs = sops.isin_pairs(jnp.asarray(a1), jnp.asarray(a2),
                                    jnp.asarray(k1), jnp.asarray(k2),
                                    jnp.asarray(valid))
        got = sops.isin(jnp.asarray(vals.astype(np.int32)),
                        jnp.asarray(keys.astype(np.int32)),
                        jnp.asarray(valid))
        assert (np.asarray(got_pairs) == want).all()
        assert (np.asarray(got) == want).all()
    assert _set_reference(vals, keys, kv).any()
