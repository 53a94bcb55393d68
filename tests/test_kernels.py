"""Per-kernel allclose vs ref.py oracles — shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

jax.config.update("jax_platform_name", "cpu")


# ------------------------------------------------------------ set_count
@pytest.mark.parametrize("e,t,eb,tb", [(2048, 256, 2048, 256),
                                       (4096, 512, 1024, 128),
                                       (1024, 128, 256, 128)])
def test_set_count_less(e, t, eb, tb):
    rng = np.random.default_rng(4)
    elems = jnp.array(rng.integers(0, 5000, e).astype(np.int32))
    tgts = jnp.array(rng.integers(0, 5000, t).astype(np.int32))
    got = ops.set_count_less(elems, tgts, t_block=tb, e_block=eb)
    np.testing.assert_array_equal(got, ref.set_count_less_ref(elems, tgts))


def test_pallas_count_fn_builds_pointer_array():
    from repro.core import COO, EngineConfig, convert, random_coo
    rng = np.random.default_rng(5)
    dst, src = random_coo(rng, 100, 1500)
    coo = COO.from_arrays(dst, src, 100, capacity=2048)
    csc_pl = convert(coo, EngineConfig(), count_fn=ops.pallas_count_fn)
    csc_jnp = convert(coo, EngineConfig())
    np.testing.assert_array_equal(csc_pl.ptr, csc_jnp.ptr)
    np.testing.assert_array_equal(csc_pl.idx, csc_jnp.idx)


# ------------------------------------------------------ filter_tree_lookup
@pytest.mark.parametrize("e,t", [(2048, 256), (4096, 128)])
def test_filter_tree_lookup(e, t):
    rng = np.random.default_rng(6)
    keys = jnp.array(rng.permutation(10 * e)[:e].astype(np.int32))
    pays = jnp.arange(e, dtype=jnp.int32)
    tgts = jnp.array(rng.integers(0, 10 * e, t).astype(np.int32))
    got_p, got_h = ops.filter_tree_lookup(keys, pays, tgts,
                                          t_block=128, e_block=1024)
    want_p, want_h = ref.filter_tree_lookup_ref(keys, pays, tgts)
    np.testing.assert_array_equal(got_p, want_p)
    np.testing.assert_array_equal(got_h, want_h)


# ---------------------------------------------------------- segment_agg
@pytest.mark.parametrize("e,n,d", [(512, 256, 128), (2048, 512, 256),
                                   (1024, 256, 64)])
def test_segment_sum_sorted(e, n, d):
    rng = np.random.default_rng(7)
    dst = np.sort(rng.integers(0, n, e)).astype(np.int32)
    msgs = rng.normal(size=(e, d)).astype(np.float32)
    got = ops.segment_sum_padded(jnp.array(dst), jnp.array(msgs), n)
    want = ref.segment_sum_sorted_ref(dst, msgs, n)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_segment_sum_sentinel_padding_ignored():
    dst = np.array([0, 0, 1, 0x7FFFFFFF, 0x7FFFFFFF], np.int32)
    msgs = np.ones((5, 4), np.float32)
    got = ops.segment_sum_padded(jnp.array(dst), jnp.array(msgs), 2,
                                 v_block=2, d_block=4, e_block=5)
    np.testing.assert_allclose(got, [[2, 2, 2, 2], [1, 1, 1, 1]])


def test_segment_sum_matches_jax_segment_sum():
    rng = np.random.default_rng(8)
    e, n, d = 1024, 512, 128
    dst = np.sort(rng.integers(0, n, e)).astype(np.int32)
    msgs = rng.normal(size=(e, d)).astype(np.float32)
    got = ops.segment_sum_padded(jnp.array(dst), jnp.array(msgs), n)
    want = jax.ops.segment_sum(jnp.array(msgs), jnp.array(dst), num_segments=n)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
