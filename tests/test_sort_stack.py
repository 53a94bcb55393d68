"""The Ordering: one native sort of the packed key or the (dst, src)
pair, with the clip/restore SENTINEL contract.

Every key scheme must equal ``np.lexsort`` bit for bit — SENTINEL tail
included — across non-pow2 VID spaces on both sides of the packed-key
bound and edge shapes from random to sentinel-heavy, hub and repeats;
``stable_sort_by_key`` must equal ``np.argsort(kind="stable")`` with and
without a payload. No other sorter cross-checks these any more.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (COO, SENTINEL, EngineConfig, convert, convert_xla,
                        random_coo, stable_sort_by_key, supports_packed_keys)
from repro.core.ordering import edge_ordering, edge_ordering_xla
from repro.core.set_partition import gather_sources_from_counts

jax.config.update("jax_platform_name", "cpu")

SEN = int(SENTINEL)


def _coo(n_nodes, e, cap, seed=0):
    rng = np.random.default_rng(seed)
    dst, src = random_coo(rng, n_nodes, e)
    return COO.from_arrays(dst, src, n_nodes, capacity=cap), dst, src


# ------------------------------------------------ the one sort vs lexsort
WIDTHS = [1, 7, 50, 997, 5000, 32767, 32768, 40000, 300000]
SHAPES = ["random", "sentinel_heavy", "hub", "repeats"]


def _shaped_graph(n_nodes, shape):
    """(dst, src, capacity) of one edge shape over ``n_nodes`` ids."""
    rng = np.random.default_rng(n_nodes * 7 + SHAPES.index(shape))
    if shape == "sentinel_heavy":  # capacity ≫ edges
        dst, src = random_coo(rng, n_nodes, 20)
        return dst, src, 1024
    dst, src = random_coo(rng, n_nodes, 300)
    if shape == "hub":  # the widest id holds half the edges
        dst[rng.random(300) < 0.5] = n_nodes - 1
        src[:8] = n_nodes - 1
    elif shape == "repeats":  # 300 draws of 12 (dst, src) pairs
        pick = rng.integers(0, 12, 300)
        dst, src = dst[pick], src[pick]
    return dst, src, 512


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("mode,n_nodes",
                         [("packed", n) for n in WIDTHS
                          if supports_packed_keys(n)]
                         + [("two_pass", n) for n in WIDTHS])
def test_ordering_matches_lexsort(mode, n_nodes, shape):
    """Each key scheme the VID space supports, non-pow2 widths on both
    sides of the packed bound (32767 | 32768), against ``np.lexsort`` —
    this guards ``stable_sort_by_key``'s and ``xla_lex_sort``'s
    clip/restore of the SENTINEL tail in both columns."""
    dst, src, cap = _shaped_graph(n_nodes, shape)
    e = dst.shape[0]
    coo = COO.from_arrays(dst, src, n_nodes, capacity=cap)
    out = edge_ordering(coo, mode=mode)
    order = np.lexsort((src, dst))
    np.testing.assert_array_equal(np.asarray(out.dst)[:e], dst[order])
    np.testing.assert_array_equal(np.asarray(out.src)[:e], src[order])
    assert np.all(np.asarray(out.dst)[e:] == SEN)
    assert np.all(np.asarray(out.src)[e:] == SEN)


@pytest.mark.parametrize("sentinel_share", [0.0, 0.9])
@pytest.mark.parametrize("n", [64, 256, 1024, 4096])
@pytest.mark.parametrize("payload", [False, True])
def test_stable_sort_by_key_matches_argsort(payload, n, sentinel_share):
    """Keys-only and with a payload, against ``np.argsort(kind="stable")``:
    SENTINEL keys (and keys past ``key_bound``) sort last and come back
    as SENTINEL; equal keys keep their input order."""
    rng = np.random.default_rng(n + int(10 * sentinel_share))
    keys = rng.integers(0, 97, n).astype(np.int32)
    keys[rng.random(n) < sentinel_share] = SEN
    vals = jnp.arange(n, dtype=jnp.int32) if payload else None
    ks, vs = stable_sort_by_key(jnp.array(keys), vals, key_bound=97)
    order = np.argsort(keys, kind="stable")
    np.testing.assert_array_equal(np.asarray(ks), keys[order])
    if payload:
        np.testing.assert_array_equal(np.asarray(vs), order)
    else:
        assert vs is None


def test_auto_mode_falls_back_for_wide_vid_space():
    assert supports_packed_keys(32767) and not supports_packed_keys(32768)
    coo, dst, src = _coo(40000, 200, cap=256, seed=1)
    auto = edge_ordering(coo, mode="auto")
    two = edge_ordering(coo, mode="two_pass")
    np.testing.assert_array_equal(np.asarray(auto.dst), np.asarray(two.dst))
    np.testing.assert_array_equal(np.asarray(auto.src), np.asarray(two.src))
    with pytest.raises(ValueError, match="packed"):
        edge_ordering(coo, mode="packed")
    with pytest.raises(ValueError, match="mode"):
        edge_ordering(coo, mode="bogus")


def test_sentinel_heavy_padding_stays_at_tail():
    """Capacity ≫ edges: the padded tail must survive every mode."""
    coo, dst, src = _coo(30, 20, cap=1024, seed=2)
    for mode in ("packed", "two_pass"):
        out = edge_ordering(coo, mode=mode)
        order = np.lexsort((src, dst))
        np.testing.assert_array_equal(np.asarray(out.dst)[:20], dst[order])
        np.testing.assert_array_equal(np.asarray(out.src)[:20], src[order])
        assert np.all(np.asarray(out.dst)[20:] == SEN), mode
        assert np.all(np.asarray(out.src)[20:] == SEN), mode


def test_convert_bit_identical_across_modes_and_vs_xla():
    coo, dst, src = _coo(120, 900, cap=1024, seed=3)
    ref = convert_xla(coo)
    for mode in ("packed", "two_pass", "auto"):
        csc = convert(coo, EngineConfig(sort_mode=mode))
        np.testing.assert_array_equal(csc.ptr[:121], ref.ptr[:121], mode)
        np.testing.assert_array_equal(csc.idx[:900], ref.idx[:900], mode)


def _wide_graph(case):
    """(n_nodes, dst, src) for ``case``, with ids too wide for the packed
    key; 1,500 edges or none, so a 4,096-slot COO is mostly padding."""
    rng = np.random.default_rng(len(case))
    n = 32768 if case == "random_32768" else 300_000
    if case == "empty":
        return n, np.zeros(0, np.int32), np.zeros(0, np.int32)
    dst, src = random_coo(rng, n, 1500)
    if case == "repeats":  # 1,500 draws of 40 (dst, src) pairs
        pick = rng.integers(0, 40, 1500)
        dst, src = dst[pick], src[pick]
    elif case == "self_loops":
        src[::2] = dst[::2]
    elif case == "hub":  # the widest id holds half the edges
        dst[rng.random(1500) < 0.5] = n - 1
        src[:8] = n - 1
    return n, dst, src


@pytest.mark.parametrize("case", ["random_32768", "random_300k", "repeats",
                                  "self_loops", "hub", "empty"])
def test_two_pass_one_sort_on_xla_sort_bit_equal(case):
    """The two-pass scheme is ONE two-key sort over the (dst, src)
    columns: bit-equal to two stable LSD passes (the ``sort_fn`` form the
    mesh-sharded sorter takes) and to the XLA lexsort baseline, SENTINEL
    tail of both columns included."""
    n, dst, src = _wide_graph(case)
    e = dst.shape[0]
    coo = COO.from_arrays(dst, src, n, capacity=4096)
    assert not supports_packed_keys(n)
    one = edge_ordering(coo, mode="two_pass")
    two = edge_ordering(coo, mode="two_pass", sort_fn=stable_sort_by_key)
    base = edge_ordering_xla(coo)
    for name, ref in [("two LSD passes", two), ("lexsort", base)]:
        np.testing.assert_array_equal(np.asarray(one.dst),
                                      np.asarray(ref.dst), name)
        np.testing.assert_array_equal(np.asarray(one.src),
                                      np.asarray(ref.src), name)
    order = np.lexsort((src, dst))
    np.testing.assert_array_equal(np.asarray(one.dst)[:e], dst[order])
    np.testing.assert_array_equal(np.asarray(one.src)[:e], src[order])
    assert np.all(np.asarray(one.dst)[e:] == SEN)
    assert np.all(np.asarray(one.src)[e:] == SEN)
    sorts = [q for q in jax.make_jaxpr(lambda c: edge_ordering(
        c, mode="two_pass"))(coo).eqns
        if q.primitive.name == "sort"]
    assert [len(q.invars) for q in sorts] == [2]
    assert sorts[0].params["num_keys"] == 2
    assert not sorts[0].params["is_stable"]


def test_convert_strategies_bit_identical_incl_pallas():
    """convert — under each key scheme, and with the Pallas SCR count
    kernel building the pointers — equals the XLA baseline CSC."""
    from repro.kernels.ops import pallas_count_fn
    coo, dst, src = _coo(120, 900, cap=1024, seed=26)
    ref = convert_xla(coo)
    for mode in ("packed", "two_pass", "auto"):
        for count_fn in (None, pallas_count_fn):
            csc = convert(coo, EngineConfig(sort_mode=mode),
                          count_fn=count_fn)
            tag = (mode, count_fn)
            np.testing.assert_array_equal(csc.ptr, ref.ptr, tag)
            np.testing.assert_array_equal(csc.idx[:900], ref.idx[:900], tag)


def test_preprocess_strategies_bit_identical_end_to_end():
    """The full pipeline equals the comparison-sort baseline
    (``preprocess_xla_baseline``) bit for bit, under either reindex
    epilogue."""
    from repro.core import preprocess, preprocess_xla_baseline
    coo, dst, src = _coo(150, 1200, cap=2048, seed=27)
    bn = jnp.arange(8, dtype=jnp.int32)
    key = jax.random.PRNGKey(0)
    ref = preprocess_xla_baseline(coo, bn, (4, 3), key)
    for rs in ("fused", "unfused"):
        got = preprocess(coo, bn, (4, 3), key,
                         EngineConfig(reindex_strategy=rs,
                                      selection="keysort"))
        np.testing.assert_array_equal(np.asarray(ref.order),
                                      np.asarray(got.order), rs)
        np.testing.assert_array_equal(np.asarray(ref.csc.ptr),
                                      np.asarray(got.csc.ptr), rs)
        np.testing.assert_array_equal(np.asarray(ref.csc.idx),
                                      np.asarray(got.csc.idx), rs)
        assert int(ref.n_sub_nodes) == int(got.n_sub_nodes), rs


# ------------------------------------------------------------ gather router
def test_gather_router_inverse_randomized():
    """Deterministic sweep of the permutation-inverse property (the
    hypothesis version lives in test_perf_paths.py)."""
    rng = np.random.default_rng(7)
    for _ in range(25):
        n = int(rng.integers(1, 400))
        nb = int(rng.choice([2, 4, 8, 16, 256]))
        k = rng.integers(0, nb, n).astype(np.int32)
        onehot = (k[:, None] == np.arange(nb)[None, :]).astype(np.int32)
        incl = np.cumsum(onehot, axis=0)
        hist = onehot.sum(axis=0)
        base = (np.cumsum(hist) - hist).astype(np.int32)
        src = np.asarray(gather_sources_from_counts(jnp.array(incl),
                                                    jnp.array(base)))
        dest = (incl - onehot)[np.arange(n), k] + base[k]
        np.testing.assert_array_equal(src[dest], np.arange(n))
        np.testing.assert_array_equal(dest[src], np.arange(n))


def test_preprocess_modes_bit_identical_end_to_end():
    """The full pipeline (Selecting/Reindexing included) is mode-invariant:
    same sampled subgraph bit-for-bit."""
    from repro.core import preprocess
    coo, dst, src = _coo(150, 1200, cap=2048, seed=10)
    bn = jnp.arange(8, dtype=jnp.int32)
    key = jax.random.PRNGKey(0)
    subs = [preprocess(coo, bn, (4, 3), key,
                       EngineConfig(sort_mode=m))
            for m in ("packed", "two_pass")]
    np.testing.assert_array_equal(np.asarray(subs[0].order),
                                  np.asarray(subs[1].order))
    np.testing.assert_array_equal(np.asarray(subs[0].csc.ptr),
                                  np.asarray(subs[1].csc.ptr))
    np.testing.assert_array_equal(np.asarray(subs[0].csc.idx),
                                  np.asarray(subs[1].csc.idx))
    assert int(subs[0].n_sub_nodes) == int(subs[1].n_sub_nodes)
