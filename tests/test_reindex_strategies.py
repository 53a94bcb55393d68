"""Fused SCR epilogue: reindex strategy equality and dispatch.

``build_reindex_map`` rides ONE shared native sort and rank-arithmetic
epilogues, and every (strategy × numbering × key scheme) combination is
bit-identical to ``reindex_serial_oracle`` — so the cost-model dispatcher
is free to pick purely on predicted latency.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (COO, EngineConfig, SENTINEL, Workload, convert,
                        pointer_reindex_strategy, random_coo,
                        resolve_reindex_strategy, sample_subgraph)
from repro.core.reindexing import (build_reindex_map, reindex_edges,
                                   reindex_serial_oracle,
                                   reindex_supports_packed)
from repro.core.reshaping import build_pointer_array

jax.config.update("jax_platform_name", "cpu")


def _vid_cases():
    rng = np.random.default_rng(11)
    return {
        "random": rng.integers(0, 2048, 4096).astype(np.int32),
        "sentinel_heavy": np.where(
            rng.random(2048) < 0.7, SENTINEL,
            rng.integers(0, 500, 2048)).astype(np.int32),
        "all_duplicate": np.full(777, 13, np.int32),
        "all_sentinel": np.full(64, SENTINEL, np.int32),
        "nonpow2_capacity": rng.integers(0, 30, 56).astype(np.int32),
        "single": np.array([5], np.int32),
    }


@pytest.mark.parametrize("strategy", ["fused", "unfused"])
@pytest.mark.parametrize("vid_bound", [None, 2100])
def test_first_occurrence_matches_serial_oracle(strategy, vid_bound):
    """Every VID shape × both loop structures × packed and pair shared
    sorts reproduce the hash-map oracle exactly (n_unique, order array,
    lookup including misses and SENTINEL queries)."""
    for name, vids in _vid_cases().items():
        seen, order = reindex_serial_oracle(vids)
        rm = build_reindex_map(jnp.array(vids), strategy=strategy,
                               vid_bound=vid_bound)
        assert int(rm.n_unique) == len(order), name
        got = np.asarray(rm.order)
        np.testing.assert_array_equal(
            got[:len(order)], np.array(order, np.int32).reshape(-1), name)
        assert (got[len(order):] == SENTINEL).all(), name
        q = np.concatenate(
            [vids[:64], np.array([SENTINEL, 99999, -1], np.int32)])
        want = np.array(
            [seen.get(int(v), SENTINEL) if v != SENTINEL else SENTINEL
             for v in q], np.int32)
        np.testing.assert_array_equal(
            np.asarray(rm.lookup(jnp.array(q))), want, name)


@pytest.mark.parametrize("strategy", ["fused", "unfused"])
def test_sorted_numbering_ranks_uniques(strategy):
    """numbering="sorted": new VID = rank among ascending uniques."""
    for name, vids in _vid_cases().items():
        uniq = sorted({int(v) for v in vids if v != SENTINEL})
        rm = build_reindex_map(jnp.array(vids), numbering="sorted",
                               strategy=strategy, vid_bound=2100)
        assert int(rm.n_unique) == len(uniq), name
        got = np.asarray(rm.order)
        np.testing.assert_array_equal(
            got[:len(uniq)], np.array(uniq, np.int32).reshape(-1), name)
        lk = np.asarray(rm.lookup(jnp.array(vids[:64])))
        want = np.array(
            [uniq.index(int(v)) if v != SENTINEL else SENTINEL
             for v in vids[:64]], np.int32)
        np.testing.assert_array_equal(lk, want, name)


@pytest.mark.parametrize("numbering", ["first_occurrence", "sorted"])
@pytest.mark.parametrize("vid_bound", [2048, None], ids=["packed", "pair"])
def test_shared_sort_key_schemes_match_serial_oracle(vid_bound, numbering):
    """The packed ``vid << pos`` stream (a bound that fits) and the pair
    fallback (no bound: the position rides as payload) × both numberings
    reproduce the oracle — map, order and edge rename."""
    vids = _vid_cases()["random"]
    seen, order = reindex_serial_oracle(vids)
    if numbering == "sorted":
        order = sorted(seen)
        seen = {v: i for i, v in enumerate(order)}
    assert reindex_supports_packed(2048, vids.shape[0])
    rm = build_reindex_map(jnp.array(vids), numbering=numbering,
                           vid_bound=vid_bound)
    assert int(rm.n_unique) == len(order)
    got = np.asarray(rm.order)
    np.testing.assert_array_equal(got[:len(order)], np.array(order))
    assert (got[len(order):] == SENTINEL).all()
    e_dst = np.concatenate([vids[:200], np.array([4000, SENTINEL], np.int32)])
    e_src = vids[200:402]
    sub = reindex_edges(rm, jnp.array(e_dst), jnp.array(e_src),
                        n_nodes_cap=vids.shape[0])
    want_d = np.array([seen.get(int(v), SENTINEL) for v in e_dst], np.int32)
    want_s = np.array([seen.get(int(v), SENTINEL) for v in e_src], np.int32)
    bad = (want_d == SENTINEL) | (want_s == SENTINEL)
    np.testing.assert_array_equal(np.asarray(sub.dst),
                                  np.where(bad, SENTINEL, want_d))
    np.testing.assert_array_equal(np.asarray(sub.src),
                                  np.where(bad, SENTINEL, want_s))
    assert int(sub.n_edges) == int((~bad).sum())


def test_packed_predicate_and_pair_fallback_agree():
    """Past the packed bit budget the pair sort takes over with identical
    results (wide-VID regime: bits(bound) + bits(cap-1) > 31)."""
    assert reindex_supports_packed(2048, 8192)
    assert not reindex_supports_packed(70000, 1 << 20)
    rng = np.random.default_rng(5)
    vids = rng.integers(0, 70000, 512).astype(np.int32)
    wide = build_reindex_map(jnp.array(vids), vid_bound=70000)  # packs: 512 pos
    none = build_reindex_map(jnp.array(vids), vid_bound=None)   # pair mode
    np.testing.assert_array_equal(np.asarray(wide.order),
                                  np.asarray(none.order))


def test_pointer_build_unroll_is_bit_identical_and_dispatched():
    """``build_pointer_array(unroll=True)`` equals the fori_loop build,
    and the model's pointer dispatch sits exactly at the documented
    crossover: small target counts fuse, huge ones stay unfused."""
    rng = np.random.default_rng(9)
    dst = np.sort(rng.integers(0, 200, 2048)).astype(np.int32)
    a = build_pointer_array(jnp.array(dst), 200, unroll=True)
    b = build_pointer_array(jnp.array(dst), 200, unroll=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    cfg = EngineConfig()
    assert pointer_reindex_strategy(cfg, Workload(n=200, e=2048)) == "fused"
    assert pointer_reindex_strategy(
        cfg, Workload(n=70000, e=2048)) == "unfused"
    # pinning the axis overrides the model
    pinned = EngineConfig(reindex_strategy="unfused")
    assert pointer_reindex_strategy(pinned, Workload(n=200, e=2048)) \
        == "unfused"
    # the key encodes the pinned axis (jit-cache identity), auto is silent
    assert "unfused" in pinned.key
    assert "fused" not in cfg.key


def test_resolver_crossover_matches_calibration():
    """fused ⟺ queries per pass below loop_trip_s · unroll_bytes_per_s / 4
    (≈375 on the CPU calibration)."""
    from repro.core.costmodel import Calibration
    cal = Calibration()
    crossover = cal.loop_trip_s * cal.unroll_bytes_per_s / 4.0
    cfg = EngineConfig()
    assert resolve_reindex_strategy(cfg, int(crossover) - 8, 2048) == "fused"
    assert resolve_reindex_strategy(cfg, int(crossover) + 8, 2048) \
        == "unfused"


def test_sample_subgraph_bit_identical_across_reindex_strategies():
    """The serving hot path: fused vs unfused vs auto produce the same
    Subgraph bit-for-bit."""
    rng = np.random.default_rng(3)
    d, s = random_coo(rng, n_nodes=200, n_edges=1500)
    coo = COO.from_arrays(d, s, n_nodes=200, capacity=2048)
    csc = convert(coo)
    bn = jnp.arange(8, dtype=jnp.int32)
    key = jax.random.PRNGKey(7)
    subs = {}
    for rs in ("fused", "unfused", "auto"):
        subs[rs] = sample_subgraph(csc, bn, (2, 2), key,
                                   EngineConfig(reindex_strategy=rs))
    ref = subs["fused"]
    for k, sub in subs.items():
        np.testing.assert_array_equal(np.asarray(sub.csc.ptr),
                                      np.asarray(ref.csc.ptr), k)
        np.testing.assert_array_equal(np.asarray(sub.csc.idx),
                                      np.asarray(ref.csc.idx), k)
        np.testing.assert_array_equal(np.asarray(sub.order),
                                      np.asarray(ref.order), k)
        assert int(sub.n_sub_nodes) == int(ref.n_sub_nodes), k
