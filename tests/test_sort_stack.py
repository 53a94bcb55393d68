"""The de-quadratic'd Ordering stack: packed-key single-pass sort,
gather-routed relocation, fused VMEM merges, and the strategy axis
(chunked radix sort + k-ary merge ladder vs the merge-free global radix
sort).

Every path must be *bit-identical*: packed vs two-pass vs the XLA
comparison-sort baseline, chunked_merge vs global_radix vs auto, across
non-pow2 VID spaces, sentinel-heavy padding, the ``radix_bits`` and
``merge_fan_in`` sweeps, and the Pallas kernels (chunk sort + fused k-ary
merge + tiled digit pass) against the jnp formulations.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (COO, SENTINEL, EngineConfig, convert, convert_xla,
                        global_radix_sort_by_key, random_coo,
                        stable_sort_by_key, supports_packed_keys)
from repro.core.ordering import (edge_ordering, edge_ordering_xla,
                                 merge_round_fan_ins, merge_rounds,
                                 merge_sorted_k)
from repro.core.set_partition import (digit_relocation_sources,
                                      gather_sources_from_counts,
                                      tiled_digit_sources)

jax.config.update("jax_platform_name", "cpu")

SEN = int(SENTINEL)


def _coo(n_nodes, e, cap, seed=0):
    rng = np.random.default_rng(seed)
    dst, src = random_coo(rng, n_nodes, e)
    return COO.from_arrays(dst, src, n_nodes, capacity=cap), dst, src


# ------------------------------------------------------ packed vs two-pass
@pytest.mark.parametrize("n_nodes", [1, 7, 50, 997, 5000, 32767])
def test_packed_two_pass_xla_bit_equal_across_vid_widths(n_nodes):
    """Non-pow2 VID spaces, including the widest packed-capable one."""
    e = min(4 * n_nodes, 300)
    coo, dst, src = _coo(n_nodes, e, cap=512, seed=n_nodes)
    packed = edge_ordering(coo, chunk=128, mode="packed")
    two = edge_ordering(coo, chunk=128, mode="two_pass")
    auto = edge_ordering(coo, chunk=128, mode="auto")
    for name, out in [("two_pass", two), ("auto", auto)]:
        np.testing.assert_array_equal(np.asarray(packed.dst),
                                      np.asarray(out.dst), name)
        np.testing.assert_array_equal(np.asarray(packed.src),
                                      np.asarray(out.src), name)
    order = np.lexsort((src, dst))
    np.testing.assert_array_equal(np.asarray(packed.dst)[:e], dst[order])
    np.testing.assert_array_equal(np.asarray(packed.src)[:e], src[order])
    assert np.all(np.asarray(packed.dst)[e:] == SEN)
    assert np.all(np.asarray(packed.src)[e:] == SEN)


def test_auto_mode_falls_back_for_wide_vid_space():
    assert supports_packed_keys(32767) and not supports_packed_keys(32768)
    coo, dst, src = _coo(40000, 200, cap=256, seed=1)
    auto = edge_ordering(coo, chunk=64, mode="auto")
    two = edge_ordering(coo, chunk=64, mode="two_pass")
    np.testing.assert_array_equal(np.asarray(auto.dst), np.asarray(two.dst))
    np.testing.assert_array_equal(np.asarray(auto.src), np.asarray(two.src))
    with pytest.raises(ValueError, match="packed"):
        edge_ordering(coo, chunk=64, mode="packed")
    with pytest.raises(ValueError, match="mode"):
        edge_ordering(coo, chunk=64, mode="bogus")


def test_sentinel_heavy_padding_stays_at_tail():
    """Capacity ≫ edges: the padded tail must survive every mode."""
    coo, dst, src = _coo(30, 20, cap=1024, seed=2)
    for mode in ("packed", "two_pass"):
        out = edge_ordering(coo, chunk=256, mode=mode)
        order = np.lexsort((src, dst))
        np.testing.assert_array_equal(np.asarray(out.dst)[:20], dst[order])
        np.testing.assert_array_equal(np.asarray(out.src)[:20], src[order])
        assert np.all(np.asarray(out.dst)[20:] == SEN), mode
        assert np.all(np.asarray(out.src)[20:] == SEN), mode


def test_convert_bit_identical_across_modes_and_vs_xla():
    coo, dst, src = _coo(120, 900, cap=1024, seed=3)
    ref = convert_xla(coo)
    for mode in ("packed", "two_pass", "auto"):
        csc = convert(coo, EngineConfig(w_upe=256, sort_mode=mode))
        np.testing.assert_array_equal(csc.ptr[:121], ref.ptr[:121], mode)
        np.testing.assert_array_equal(csc.idx[:900], ref.idx[:900], mode)


# ---------------------------------------------------------- radix_bits knob
@pytest.mark.parametrize("radix_bits", [2, 4, 8])
def test_radix_bits_sweep_bit_identical(radix_bits):
    """One EngineConfig.radix_bits value routes through both the jnp chunk
    sorter and (below, via use_pallas) the Pallas kernel — outputs must not
    depend on the digit width."""
    coo, dst, src = _coo(90, 700, cap=1024, seed=4)
    ref = convert(coo, EngineConfig(w_upe=256))  # default radix_bits=4
    csc = convert(coo, EngineConfig(w_upe=256, radix_bits=radix_bits))
    np.testing.assert_array_equal(csc.ptr, ref.ptr)
    np.testing.assert_array_equal(csc.idx, ref.idx)


@pytest.mark.parametrize("radix_bits", [2, 8])
def test_radix_bits_routes_through_pallas_kernel(radix_bits):
    coo, dst, src = _coo(60, 300, cap=512, seed=5)
    ref = convert(coo, EngineConfig(w_upe=128))
    csc = convert(coo, EngineConfig(w_upe=128, radix_bits=radix_bits,
                                    use_pallas=True))
    np.testing.assert_array_equal(csc.ptr, ref.ptr)
    np.testing.assert_array_equal(csc.idx, ref.idx)


def test_stable_sort_radix_bits_sweep():
    rng = np.random.default_rng(6)
    keys = rng.integers(0, 1009, 512).astype(np.int32)
    vals = np.arange(512, dtype=np.int32)
    order = np.argsort(keys, kind="stable")
    for rb in (2, 4, 8):
        ks, vs = stable_sort_by_key(jnp.array(keys), jnp.array(vals),
                                    key_bound=1024, chunk=128,
                                    radix_bits=rb)
        np.testing.assert_array_equal(ks, keys[order], rb)
        np.testing.assert_array_equal(vs, order, rb)


# ------------------------------------------------------- strategy equality
@pytest.mark.parametrize("n_nodes", [1, 7, 50, 997, 5000, 32767, 40000])
def test_strategy_equality_sweep_across_vid_widths(n_nodes):
    """global_radix == chunked_merge == lexsort for every key scheme the
    VID space supports, over non-pow2 VID spaces including the widest
    packed-capable one (32767) and a two-pass-only one (40000)."""
    e = min(4 * n_nodes, 300)
    coo, dst, src = _coo(n_nodes, e, cap=512, seed=n_nodes)
    order = np.lexsort((src, dst))
    modes = ["two_pass"] + (["packed"] if supports_packed_keys(n_nodes)
                            else [])
    for mode in modes:
        for strategy in ("chunked_merge", "global_radix", "xla_sort"):
            out = edge_ordering(coo, chunk=128, mode=mode,
                                strategy=strategy)
            tag = (n_nodes, mode, strategy)
            np.testing.assert_array_equal(np.asarray(out.dst)[:e],
                                          dst[order], tag)
            np.testing.assert_array_equal(np.asarray(out.src)[:e],
                                          src[order], tag)
            assert np.all(np.asarray(out.dst)[e:] == SEN), tag
            assert np.all(np.asarray(out.src)[e:] == SEN), tag


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
    """On ``xla_sort`` the two-pass scheme is ONE two-key sort over the
    (dst, src) columns: bit-equal to the two stable LSD passes (the
    chunked_merge two-pass) and to the XLA lexsort baseline, SENTINEL
    tail of both columns included."""
    n, dst, src = _wide_graph(case)
    e = dst.shape[0]
    coo = COO.from_arrays(dst, src, n, capacity=4096)
    assert not supports_packed_keys(n)
    one = edge_ordering(coo, chunk=128, mode="two_pass", strategy="xla_sort")
    two = edge_ordering(coo, chunk=128, mode="two_pass",
                        strategy="chunked_merge")
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
        c, mode="two_pass", strategy="xla_sort"))(coo).eqns
        if q.primitive.name == "sort"]
    assert [len(q.invars) for q in sorts] == [2]
    assert sorts[0].params["num_keys"] == 2
    assert not sorts[0].params["is_stable"]


@pytest.mark.parametrize("fan_in", [2, 3, 4, 8])
def test_merge_fan_in_sweep_bit_identical(fan_in):
    """The k-ary ladder is a refinement of the binary tree: any fan-in
    yields the same stable-sort output (and the rung count matches
    merge_round_fan_ins)."""
    coo, dst, src = _coo(200, 900, cap=2048, seed=21)
    ref = edge_ordering(coo, chunk=128, fan_in=2)
    got = edge_ordering(coo, chunk=128, fan_in=fan_in)
    np.testing.assert_array_equal(np.asarray(got.dst), np.asarray(ref.dst))
    np.testing.assert_array_equal(np.asarray(got.src), np.asarray(ref.src))
    # rung count drops from log2 to log_k
    assert len(merge_round_fan_ins(2048, 128, fan_in)) <= \
        len(merge_round_fan_ins(2048, 128, 2))


def test_merge_ladder_handles_non_pow2_run_counts():
    """Regression: a run count with no divisor ≤ fan_in (3 runs under
    fan_in=2) merges in one wider rung instead of hanging, and a chunk
    that does not tile n contributes zero rounds to the cost model."""
    assert merge_round_fan_ins(384, 128, 2) == [3]
    assert merge_round_fan_ins(1152, 128, 2) == [3, 3]
    assert merge_round_fan_ins(4096, 3000, 2) == []
    rng = np.random.default_rng(20)
    keys = rng.integers(0, 500, 384).astype(np.int32)
    order = np.argsort(keys, kind="stable")
    ks, vs = stable_sort_by_key(jnp.array(keys),
                                jnp.arange(384, dtype=jnp.int32), 500,
                                chunk=128)
    np.testing.assert_array_equal(ks, keys[order])
    np.testing.assert_array_equal(vs, order)


def test_merge_sorted_k_matches_pairwise_fold():
    rng = np.random.default_rng(22)
    for k, run in [(2, 32), (3, 16), (4, 64), (8, 8)]:
        kr = np.sort(rng.integers(0, 40, (k, run)).astype(np.int32), axis=1)
        vr = np.arange(k * run, dtype=np.int32).reshape(k, run)
        got_k, got_v = merge_sorted_k(jnp.array(kr), jnp.array(vr))
        flat_k = kr.reshape(-1)
        flat_v = vr.reshape(-1)
        order = np.argsort(flat_k, kind="stable")
        np.testing.assert_array_equal(np.asarray(got_k), flat_k[order], k)
        np.testing.assert_array_equal(np.asarray(got_v), flat_v[order], k)
        kk, none = merge_sorted_k(jnp.array(kr), None)
        assert none is None
        np.testing.assert_array_equal(np.asarray(kk), flat_k[order])


def test_global_radix_sentinel_heavy_tail():
    """Capacity ≫ edges under the merge-free strategy: the padded tail
    must stay at the tail through every digit pass."""
    coo, dst, src = _coo(30, 20, cap=1024, seed=23)
    for mode in ("packed", "two_pass"):
        out = edge_ordering(coo, chunk=256, mode=mode,
                            strategy="global_radix")
        order = np.lexsort((src, dst))
        np.testing.assert_array_equal(np.asarray(out.dst)[:20], dst[order])
        np.testing.assert_array_equal(np.asarray(out.src)[:20], src[order])
        assert np.all(np.asarray(out.dst)[20:] == SEN), mode
        assert np.all(np.asarray(out.src)[20:] == SEN), mode


def test_global_radix_keys_only_matches_payload_sort():
    """Keys-only and payload-carrying global radix sorts agree on the key
    stream (the packed Ordering rides no payload)."""
    rng = np.random.default_rng(24)
    keys = jnp.array(rng.integers(0, 700, 1024), jnp.int32)
    vals = jnp.arange(1024, dtype=jnp.int32)
    want_k, want_v = global_radix_sort_by_key(keys, vals, 700, tile=128)
    got_k, none = global_radix_sort_by_key(keys, None, 700, tile=128)
    assert none is None
    np.testing.assert_array_equal(got_k, want_k)
    order = np.argsort(np.asarray(keys), kind="stable")
    np.testing.assert_array_equal(np.asarray(want_v), order)


def test_tiled_digit_sources_equals_flat_router():
    """The two-level rank-arithmetic router is the flat [N, B] router,
    tile by tile — any tile size, any bucket count."""
    rng = np.random.default_rng(25)
    for n, tile, nb in [(256, 32, 4), (512, 128, 16), (64, 64, 8),
                        (128, 256, 2)]:
        d = jnp.array(rng.integers(0, nb, n).astype(np.int32))
        ref, _ = digit_relocation_sources(d, nb)
        got = tiled_digit_sources(d, nb, tile)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref),
                                      (n, tile, nb))


def test_convert_strategies_bit_identical_incl_pallas():
    """convert under every (strategy × backend) — including the Pallas
    tiled digit-pass pair and the k-ary fused merge kernel — equals the
    XLA baseline CSC."""
    coo, dst, src = _coo(120, 900, cap=1024, seed=26)
    ref = convert_xla(coo)
    for strategy in ("chunked_merge", "global_radix", "xla_sort", "auto"):
        for use_pallas in (False, True):
            cfg = EngineConfig(w_upe=256, sort_strategy=strategy,
                               use_pallas=use_pallas, merge_fan_in=4)
            csc = convert(coo, cfg)
            tag = (strategy, use_pallas)
            np.testing.assert_array_equal(csc.ptr, ref.ptr, tag)
            np.testing.assert_array_equal(csc.idx[:900], ref.idx[:900], tag)


def test_preprocess_strategies_bit_identical_end_to_end():
    """The full pipeline is strategy-invariant: same sampled subgraph
    bit-for-bit under chunked_merge, global_radix and auto."""
    from repro.core import preprocess
    coo, dst, src = _coo(150, 1200, cap=2048, seed=27)
    bn = jnp.arange(8, dtype=jnp.int32)
    key = jax.random.PRNGKey(0)
    subs = [preprocess(coo, bn, (4, 3), key,
                       EngineConfig(w_upe=256, sort_strategy=s))
            for s in ("chunked_merge", "global_radix", "xla_sort", "auto")]
    for got in subs[1:]:
        np.testing.assert_array_equal(np.asarray(subs[0].order),
                                      np.asarray(got.order))
        np.testing.assert_array_equal(np.asarray(subs[0].csc.ptr),
                                      np.asarray(got.csc.ptr))
        np.testing.assert_array_equal(np.asarray(subs[0].csc.idx),
                                      np.asarray(got.csc.idx))
        assert int(subs[0].n_sub_nodes) == int(got.n_sub_nodes)


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


# ------------------------------------------------------------- fused merge
@pytest.mark.parametrize("n,run,max_block", [(1024, 64, 65536),
                                             (1024, 64, 256),
                                             (512, 512, 65536),
                                             (2048, 32, 512)])
def test_fused_merge_rounds_matches_jnp_tree(n, run, max_block):
    from repro.kernels.merge import fused_merge_rounds
    rng = np.random.default_rng(8)
    keys = rng.integers(0, 1000, n).astype(np.int32)
    kr = keys.reshape(-1, run)
    order = (np.argsort(kr, axis=1, kind="stable")
             + (np.arange(n // run) * run)[:, None])
    k0 = jnp.array(np.sort(kr, axis=1).reshape(-1))
    v0 = jnp.array(order.reshape(-1).astype(np.int32))
    ref_k, ref_v = merge_rounds(k0, v0, run)
    got_k, got_v = merge_rounds(
        k0, v0, run,
        merge_fn=lambda k, v, r: fused_merge_rounds(k, v, r,
                                                    max_block=max_block))
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(ref_k))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(ref_v))


def test_full_pallas_sort_stack_bit_identical():
    """Pallas chunk sort + fused VMEM merges == jnp path, end to end."""
    coo, dst, src = _coo(80, 600, cap=1024, seed=9)
    for mode in ("packed", "two_pass"):
        ref = convert(coo, EngineConfig(w_upe=256, sort_mode=mode))
        got = convert(coo, EngineConfig(w_upe=256, sort_mode=mode,
                                        use_pallas=True))
        np.testing.assert_array_equal(got.ptr, ref.ptr, mode)
        np.testing.assert_array_equal(got.idx, ref.idx, mode)


def test_preprocess_modes_bit_identical_end_to_end():
    """The full pipeline (Selecting/Reindexing included) is mode-invariant:
    same sampled subgraph bit-for-bit."""
    from repro.core import preprocess
    coo, dst, src = _coo(150, 1200, cap=2048, seed=10)
    bn = jnp.arange(8, dtype=jnp.int32)
    key = jax.random.PRNGKey(0)
    subs = [preprocess(coo, bn, (4, 3), key,
                       EngineConfig(w_upe=256, sort_mode=m))
            for m in ("packed", "two_pass")]
    np.testing.assert_array_equal(np.asarray(subs[0].order),
                                  np.asarray(subs[1].order))
    np.testing.assert_array_equal(np.asarray(subs[0].csc.ptr),
                                  np.asarray(subs[1].csc.ptr))
    np.testing.assert_array_equal(np.asarray(subs[0].csc.idx),
                                  np.asarray(subs[1].csc.idx))
    assert int(subs[0].n_sub_nodes) == int(subs[1].n_sub_nodes)
