"""Unit + property tests for the UPE/SCR algorithmic primitives."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import (SENTINEL, count_equal, count_less_than, displacement,
                        filter_lookup, merge_sorted, partition_indices,
                        set_partition, stable_sort_by_key)

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------- partition
def test_displacement_matches_exclusive_cumsum():
    cond = jnp.array([1, 0, 1, 1, 0, 1], bool)
    np.testing.assert_array_equal(displacement(cond), [0, 1, 1, 2, 3, 3])


def test_set_partition_stable():
    vals = jnp.arange(8, dtype=jnp.int32)
    cond = jnp.array([0, 1, 0, 1, 1, 0, 0, 1], bool)
    out, n = set_partition(vals, cond)
    np.testing.assert_array_equal(out, [1, 3, 4, 7, 0, 2, 5, 6])
    assert int(n) == 4


@settings(max_examples=50, deadline=None)
@given(st.lists(st.booleans(), min_size=1, max_size=200))
def test_partition_indices_is_permutation(conds):
    cond = jnp.array(conds, bool)
    dest, n_sel = partition_indices(cond)
    assert sorted(np.asarray(dest).tolist()) == list(range(len(conds)))
    assert int(n_sel) == sum(conds)


# ---------------------------------------------------------------- counting
@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 1000), min_size=1, max_size=500),
       st.lists(st.integers(0, 1001), min_size=1, max_size=64))
def test_count_less_than_matches_searchsorted(xs, ts):
    arr = jnp.array(sorted(xs), jnp.int32)
    targets = jnp.array(ts, jnp.int32)
    got = count_less_than(arr, targets, block=64)
    want = np.searchsorted(np.asarray(arr), np.asarray(targets), side="left")
    np.testing.assert_array_equal(got, want)


def test_count_less_than_unsorted_input():
    # the adder tree does not require sorted input
    arr = jnp.array([5, 1, 9, 1, 3], jnp.int32)
    got = count_less_than(arr, jnp.array([4], jnp.int32), block=4)
    assert int(got[0]) == 3


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 50), min_size=1, max_size=300),
       st.lists(st.integers(0, 50), min_size=1, max_size=32))
def test_count_equal(xs, ts):
    got = count_equal(jnp.array(xs, jnp.int32), jnp.array(ts, jnp.int32),
                      block=32)
    want = [sum(1 for x in xs if x == t) for t in ts]
    np.testing.assert_array_equal(got, want)


def test_filter_lookup_hits_and_misses():
    keys = jnp.array([10, 20, 30, 40], jnp.int32)
    pay = jnp.array([0, 1, 2, 3], jnp.int32)
    got, hit = filter_lookup(keys, pay, jnp.array([20, 25, 40], jnp.int32),
                             block=2)
    np.testing.assert_array_equal(got, [1, -1, 3])
    np.testing.assert_array_equal(hit, [True, False, True])


# ---------------------------------------------------------------- merge/sort
@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 100), min_size=1, max_size=64),
       st.lists(st.integers(0, 100), min_size=1, max_size=64))
def test_merge_sorted(a, b):
    a, b = sorted(a), sorted(b)
    ak = jnp.array(a, jnp.int32)
    bk = jnp.array(b, jnp.int32)
    av = jnp.zeros(len(a), jnp.int32)  # tag A=0
    bv = jnp.ones(len(b), jnp.int32)  # tag B=1
    mk, mv = merge_sorted(ak, av, bk, bv)
    np.testing.assert_array_equal(mk, sorted(a + b))
    # stability: among equal keys, A tags precede B tags
    mk_np, mv_np = np.asarray(mk), np.asarray(mv)
    for val in set(a) & set(b):
        run = mv_np[mk_np == val]
        assert all(run[i] <= run[i + 1] for i in range(len(run) - 1))


@pytest.mark.parametrize("n", [64, 256, 1024])
def test_stable_sort_by_key_global(n):
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 97, size=n).astype(np.int32)
    vals = np.arange(n, dtype=np.int32)
    ks, vs = stable_sort_by_key(jnp.array(keys), jnp.array(vals),
                                key_bound=100)
    order = np.argsort(keys, kind="stable")
    np.testing.assert_array_equal(ks, keys[order])
    np.testing.assert_array_equal(vs, order)


def test_stable_sort_handles_sentinels():
    keys = jnp.array([5, int(SENTINEL), 1, int(SENTINEL)], jnp.int32)
    vals = jnp.array([0, 1, 2, 3], jnp.int32)
    ks, vs = stable_sort_by_key(keys, vals, key_bound=10)
    np.testing.assert_array_equal(ks, [1, 5, int(SENTINEL), int(SENTINEL)])
    np.testing.assert_array_equal(vs, [2, 0, 1, 3])
