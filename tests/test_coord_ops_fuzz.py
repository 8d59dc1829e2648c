"""Property-based fuzz suite for the coordinate-array primitives.

``keyed_union_reduce`` (both the sort-merge and the dense-workspace
paths), sorted intersection, the segment-reduce dispatch table, and the
fusion splice primitive ``coo_to_levels`` are checked against plain
numpy oracles over random keys, duplicates, explicit zeros, and empty
streams. Runs under ``tests/_hypothesis_stub.py`` when hypothesis is
absent (deterministic seeded examples, no shrinking).
"""
import numpy as np
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as hst
except ImportError:
    from _hypothesis_stub import given, settings, strategies as hst

from repro.core import coord_ops as co
from repro.core.fibertree import FiberTree


# -- strategies -------------------------------------------------------------

@hst.composite
def keyed_stream(draw):
    """Random (keys, vals, valid) with duplicates, zeros, empty tails."""
    n = draw(hst.integers(1, 64))
    bound = draw(hst.integers(1, 40))
    seed = draw(hst.integers(0, 2 ** 31 - 1))
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, bound, n)
    vals = rng.integers(-3, 4, n).astype(np.float32)   # incl. exact zeros
    valid = rng.random(n) < draw(hst.integers(0, 10)) / 10.0
    return keys, vals, valid, bound


def _oracle_reduce(keys, vals, valid):
    acc = {}
    for k, v, ok in zip(keys, vals, valid):
        if ok:
            acc[int(k)] = acc.get(int(k), 0.0) + float(v)
    return acc


# -- keyed_union_reduce -----------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(keyed_stream())
def test_keyed_union_reduce_matches_oracle(case):
    keys, vals, valid, bound = case
    acc = _oracle_reduce(keys, vals, valid)
    cap = max(8, len(acc) + 3)
    for key_bound in (None, bound):     # sort path AND dense-workspace path
        uk, uv, ok, count = co.keyed_union_reduce(
            jnp.asarray(keys, jnp.int64), jnp.asarray(vals),
            jnp.asarray(valid), cap, key_bound=key_bound)
        uk, uv, ok = np.asarray(uk), np.asarray(uv), np.asarray(ok)
        assert int(count) == len(acc), f"count (bound={key_bound})"
        got = dict(zip(uk[ok].tolist(), uv[ok].tolist()))
        assert sorted(got) == sorted(acc)
        for k in acc:
            np.testing.assert_allclose(got[k], acc[k], rtol=1e-6,
                                       err_msg=f"key {k} bound={key_bound}")
        # live keys come back sorted with PAD beyond
        assert list(uk[ok]) == sorted(uk[ok])
        assert (uk[~ok] == co.PAD_KEY).all() and (uv[~ok] == 0.0).all()


@settings(max_examples=15, deadline=None)
@given(keyed_stream())
def test_keyed_union_reduce_overflow_reports_true_count(case):
    keys, vals, valid, bound = case
    acc = _oracle_reduce(keys, vals, valid)
    if len(acc) <= 1:
        return
    cap = len(acc) - 1                  # force truncation
    for key_bound in (None, bound):
        *_, count = co.keyed_union_reduce(
            jnp.asarray(keys, jnp.int64), jnp.asarray(vals),
            jnp.asarray(valid), cap, key_bound=key_bound)
        assert int(count) == len(acc)   # overflow detectable, never silent


@settings(max_examples=12, deadline=None)
@given(keyed_stream(), hst.integers(co.DENSE_REDUCE_BOUND + 1,
                                    co.I32_SORT_BOUND - 1))
def test_keyed_union_reduce_int32_sort_is_bit_identical(case, big_bound):
    """Past the dense bound, a key bound below 2**31 - 1 sorts 32-bit
    keys; the permutation, and so every output bit, is the 64-bit
    sort's. Keys spread over the whole bound."""
    keys, vals, valid, bound = case
    keys = keys * (big_bound // bound) + (keys % 7)
    assert keys.max() < big_bound
    cap = max(8, len(keys))
    args = (jnp.asarray(keys, jnp.int64), jnp.asarray(vals),
            jnp.asarray(valid), cap)
    ref = co.keyed_union_reduce(*args, key_bound=None)
    got = co.keyed_union_reduce(*args, key_bound=big_bound)
    for a, b in zip(ref, got):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_keyed_union_reduce_empty_stream():
    for key_bound in (None, 16):
        uk, uv, ok, count = co.keyed_union_reduce(
            jnp.zeros(6, jnp.int64), jnp.zeros(6), jnp.zeros(6, bool), 8,
            key_bound=key_bound)
        assert int(count) == 0 and not np.asarray(ok).any()
        assert (np.asarray(uk) == co.PAD_KEY).all()


def test_keyed_union_reduce_keeps_explicit_zero_slots():
    """A live key whose values sum to zero still occupies a slot (both
    paths must agree on count semantics)."""
    keys = jnp.asarray([4, 4, 9], jnp.int64)
    vals = jnp.asarray([1.0, -1.0, 5.0])
    valid = jnp.ones(3, bool)
    for key_bound in (None, 10):
        uk, uv, ok, count = co.keyed_union_reduce(keys, vals, valid, 8,
                                                  key_bound=key_bound)
        assert int(count) == 2
        assert np.asarray(uk)[np.asarray(ok)].tolist() == [4, 9]
        np.testing.assert_allclose(
            np.asarray(uv)[np.asarray(ok)], [0.0, 5.0])


# -- sorted intersection ----------------------------------------------------

@hst.composite
def sorted_pair(draw):
    seed = draw(hst.integers(0, 2 ** 31 - 1))
    na, nb = draw(hst.integers(1, 48)), draw(hst.integers(1, 48))
    bound = draw(hst.integers(1, 60))
    rng = np.random.default_rng(seed)

    def side(n):
        ks = np.sort(rng.choice(bound, size=min(n, bound), replace=False))
        ks = ks.astype(np.int64)
        valid = rng.random(len(ks)) < 0.8
        keyed = np.where(valid, ks, co.PAD_KEY)
        order = np.argsort(keyed)
        return keyed[order], valid[order]

    return side(na) + side(nb)


@settings(max_examples=40, deadline=None)
@given(sorted_pair())
def test_intersect_keys_matches_set_oracle(case):
    a_key, a_valid, b_key, b_valid = case
    hit, idx = co.intersect_keys(jnp.asarray(a_key), jnp.asarray(a_valid),
                                 jnp.asarray(b_key), jnp.asarray(b_valid))
    hit, idx = np.asarray(hit), np.asarray(idx)
    b_live = set(b_key[b_valid].tolist())
    for i, (k, ok) in enumerate(zip(a_key, a_valid)):
        expect = bool(ok) and k != co.PAD_KEY and int(k) in b_live
        assert bool(hit[i]) == expect, f"pos {i} key {k}"
        if expect:
            assert b_key[idx[i]] == k   # the surviving ref probes b's slot


def test_intersect_keys_empty_sides():
    empty = jnp.full((4,), co.PAD_KEY)
    novalid = jnp.zeros(4, bool)
    some = jnp.asarray([1, 2, 3, co.PAD_KEY], jnp.int64)
    ok = jnp.asarray([True, True, True, False])
    hit, _ = co.intersect_keys(some, ok, empty, novalid)
    assert not np.asarray(hit).any()
    hit, _ = co.intersect_keys(empty, novalid, some, ok)
    assert not np.asarray(hit).any()


# -- segment-reduce dispatch ------------------------------------------------

@hst.composite
def segments(draw):
    n = draw(hst.integers(1, 80))
    nseg = draw(hst.integers(1, 12))
    seed = draw(hst.integers(0, 2 ** 31 - 1))
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, nseg, n)
    vals = rng.standard_normal(n).astype(np.float32)
    return ids, vals, nseg


@settings(max_examples=40, deadline=None)
@given(segments())
def test_segment_sum_dispatch_matches_numpy(case):
    ids, vals, nseg = case
    want = np.zeros(nseg, np.float32)
    np.add.at(want, ids, vals)
    from repro.kernels import ops as kops

    for impl in (co.default_segment_sum,
                 kops.sam_primitive("keyed_segment_sum"),
                 kops.sam_primitive("keyed_segment_sum", backend="tpu")):
        got = np.asarray(impl(jnp.asarray(vals), jnp.asarray(ids), nseg))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5,
                                   err_msg=str(impl))


def test_union_reduce_dispatch_entry_is_the_fallback():
    from repro.kernels import ops as kops

    # CPU resolution keeps the coord_ops fallback; the tpu entry is the
    # Pallas dense-workspace kernel (tests/test_kernel_conformance.py
    # drives every entry differentially)
    assert kops.sam_primitive("keyed_union_reduce", backend="cpu") \
        is co.keyed_union_reduce
    assert kops.sam_primitive("keyed_union_reduce", backend="tpu") \
        is not co.keyed_union_reduce


# -- coo_to_levels (the fusion splice primitive) ----------------------------

@hst.composite
def coo_case(draw):
    nlev = draw(hst.integers(1, 3))
    dims = tuple(draw(hst.integers(2, 6)) for _ in range(nlev))
    seed = draw(hst.integers(0, 2 ** 31 - 1))
    rng = np.random.default_rng(seed)
    total = int(np.prod(dims))
    nnz = draw(hst.integers(0, min(total, 24)))
    keys = np.sort(rng.choice(total, size=nnz, replace=False)).astype(
        np.int64)
    return dims, keys


@settings(max_examples=40, deadline=None)
@given(coo_case())
def test_coo_to_levels_matches_fibertree(case):
    """The on-device level builder must agree with the host FiberTree
    construction from the same coordinates (the materialized rescan)."""
    dims, keys = case
    nnz = len(keys)
    cap = max(8, nnz + 2)
    padded = np.full(cap, co.PAD_KEY, np.int64)
    padded[:nnz] = keys
    valid = np.arange(cap) < nnz
    caps = [cap] * len(dims)
    segs, crds, counts = co.coo_to_levels(
        jnp.asarray(padded), jnp.asarray(valid), list(dims), caps)

    coords = np.zeros((nnz, len(dims)), np.int64)
    rem = keys.copy()
    for ax in range(len(dims) - 1, -1, -1):
        coords[:, ax] = rem % dims[ax]
        rem //= dims[ax]
    ft = FiberTree.from_coords(dims, coords, np.ones(nnz),
                               "c" * len(dims))
    num_parents = 1
    for lvl, level in enumerate(ft.levels):
        cnt = int(counts[lvl])
        assert cnt == len(level.crd), f"level {lvl} count"
        np.testing.assert_array_equal(
            np.asarray(crds[lvl])[:cnt], level.crd, err_msg=f"crd {lvl}")
        np.testing.assert_array_equal(
            np.asarray(segs[lvl])[:num_parents + 1], level.seg,
            err_msg=f"seg {lvl}")
        # padding seg entries stay clamped at the live total
        assert (np.asarray(segs[lvl])[num_parents:] == cnt).all()
        num_parents = cnt
