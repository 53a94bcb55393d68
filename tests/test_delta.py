"""core.delta: incremental conversion guards — bit-identity of the
delta-merge against a from-scratch convert of the post-update edge list,
across packed/pair key modes, fused/unfused rank lowering,
adversarial delete patterns (duplicates, misses, all-delete, SENTINEL-heavy
tails) and chained updates; plus the merge-vs-rebuild mode equality and a
hypothesis property sweep when hypothesis is installed."""
import jax
import numpy as np
import pytest

from repro.core import pipeline
from repro.core.costmodel import EngineConfig, Workload
from repro.core.delta import EdgeDelta
from repro.core.graph import COO, SENTINEL, next_pow2

jax.config.update("jax_platform_name", "cpu")


# ----------------------------------------------------------------- helpers
def _coo(dst, src, n_nodes, capacity=None):
    cap = capacity or next_pow2(max(1, len(dst)))
    return COO.from_arrays(np.asarray(dst, np.int32),
                           np.asarray(src, np.int32), n_nodes,
                           capacity=cap)


def _oracle_update(dst, src, ins, dels):
    """Post-update edge list by the delta contract: each delete kills at
    most one matching PRE-update edge (multiset semantics, misses no-op);
    same-delta inserts are never the victim."""
    keep = [True] * len(dst)
    avail = {}
    for i, e in enumerate(zip(dst, src)):
        avail.setdefault(e, []).append(i)
    for e in dels:
        for i in avail.get(tuple(e), []):
            if keep[i]:
                keep[i] = False
                break
    nd = [d for i, d in enumerate(dst) if keep[i]] + [d for d, _ in ins]
    ns = [s for i, s in enumerate(src) if keep[i]] + [s for _, s in ins]
    return nd, ns


def _expected_csc(nd, ns, n_nodes, out_cap):
    order = np.lexsort((np.asarray(ns), np.asarray(nd)))
    sd = np.asarray(nd, np.int64)[order]
    ss = np.asarray(ns, np.int32)[order]
    ptr = np.searchsorted(sd, np.arange(n_nodes + 1)).astype(np.int32)
    idx = np.full((out_cap,), int(SENTINEL), np.int32)
    idx[:len(ss)] = ss
    return ptr, idx


def _check(csc, delta, dst, src, ins, dels, cfg=None, mode="auto",
           out_capacity=None):
    out = pipeline.apply_delta(csc, delta, cfg, mode=mode,
                               out_capacity=out_capacity)
    nd, ns = _oracle_update(list(dst), list(src), ins, dels)
    ptr, idx = _expected_csc(nd, ns, csc.n_nodes, out.idx.shape[0])
    assert int(out.n_edges) == len(nd)
    np.testing.assert_array_equal(np.asarray(out.ptr[:csc.n_nodes + 1]),
                                  ptr)
    np.testing.assert_array_equal(np.asarray(out.idx), idx)
    return out


def _rand_case(rng, n_nodes, n_edges, n_ins, n_del, n_miss=0, d_cap=None):
    dst = rng.integers(0, n_nodes, n_edges).astype(np.int32)
    src = rng.integers(0, n_nodes, n_edges).astype(np.int32)
    ins = [(int(rng.integers(n_nodes)), int(rng.integers(n_nodes)))
           for _ in range(n_ins)]
    victims = rng.choice(n_edges, min(n_del, n_edges), replace=False)
    dels = [(int(dst[i]), int(src[i])) for i in victims]
    dels += [(int(rng.integers(n_nodes)), int(rng.integers(n_nodes)))
             for _ in range(n_miss)]
    delta = EdgeDelta.from_arrays(
        [d for d, _ in ins], [s for _, s in ins],
        [d for d, _ in dels], [s for _, s in dels],
        n_nodes=n_nodes, capacity=d_cap)
    return dst, src, ins, dels, delta


# ------------------------------------------------- bit-identity, all axes
@pytest.mark.parametrize("reindex", ["fused", "unfused"])
def test_merge_bit_identical_across_strategies(reindex):
    """The acceptance axis: either reindex_strategy produces the EXACT CSC
    a from-scratch convert of the updated edge list produces — the delta
    path is a pure optimization."""
    rng = np.random.default_rng(7)
    dst, src, ins, dels, delta = _rand_case(rng, 512, 1500, 100, 60,
                                            n_miss=20, d_cap=256)
    cfg = EngineConfig(reindex_strategy=reindex)
    csc = pipeline.convert(_coo(dst, src, 512, capacity=2048), cfg)
    _check(csc, delta, dst, src, ins, dels, cfg=cfg, mode="merge")


@pytest.mark.parametrize("n_nodes", [300, 1 << 17], ids=["packed", "pair"])
@pytest.mark.parametrize("reindex", ["fused", "unfused"])
def test_merge_and_rebuild_match_oracle_per_key_scheme(reindex, n_nodes):
    """Both delta modes under either rank lowering, on a graph whose
    (dst, src) packs into one int32 key and on one too wide to pack (its
    delta streams sort as one two-key sort), equal the oracle CSC."""
    rng = np.random.default_rng(n_nodes % 97)
    dst, src, ins, dels, delta = _rand_case(rng, n_nodes, 700, 40, 30,
                                            n_miss=6, d_cap=64)
    cfg = EngineConfig(reindex_strategy=reindex)
    csc = pipeline.convert(_coo(dst, src, n_nodes, capacity=1024), cfg)
    for mode in ("merge", "rebuild"):
        _check(csc, delta, dst, src, ins, dels, cfg=cfg, mode=mode)


def test_merge_equals_rebuild_mode():
    rng = np.random.default_rng(8)
    dst, src, ins, dels, delta = _rand_case(rng, 300, 900, 50, 40,
                                            n_miss=10, d_cap=128)
    csc = pipeline.convert(_coo(dst, src, 300, capacity=1024))
    a = pipeline.apply_delta(csc, delta, mode="merge")
    b = pipeline.apply_delta(csc, delta, mode="rebuild")
    np.testing.assert_array_equal(np.asarray(a.ptr), np.asarray(b.ptr))
    np.testing.assert_array_equal(np.asarray(a.idx), np.asarray(b.idx))
    assert int(a.n_edges) == int(b.n_edges)


def test_pair_mode_wide_vid_space():
    """VID spaces too wide to pack (dst, src) into one int32 key route the
    delta sorts through the two-pass pair scheme — same output."""
    n_nodes = 1 << 17  # 2*17 bits > 31: supports_packed_keys is False
    rng = np.random.default_rng(9)
    dst = rng.integers(0, n_nodes, 700).astype(np.int32)
    src = rng.integers(0, n_nodes, 700).astype(np.int32)
    ins = [(int(rng.integers(n_nodes)), int(rng.integers(n_nodes)))
           for _ in range(30)]
    dels = [(int(dst[i]), int(src[i])) for i in range(25)]
    delta = EdgeDelta.from_arrays([d for d, _ in ins],
                                  [s for _, s in ins],
                                  [d for d, _ in dels],
                                  [s for _, s in dels],
                                  n_nodes=n_nodes, capacity=64)
    csc = pipeline.convert(_coo(dst, src, n_nodes, capacity=1024))
    _check(csc, delta, dst, src, ins, dels, mode="merge")


# ------------------------------------------------------- adversarial shapes
def test_duplicate_edges_multiset_delete_semantics():
    """k copies of an edge minus m deletes of it leaves max(k-m, 0)
    copies; a delete never kills a same-delta insert of the edge."""
    dst = [3, 3, 3, 5, 5, 7]
    src = [1, 1, 1, 2, 2, 0]
    ins = [(3, 1), (5, 2)]  # re-insert edges also being deleted
    dels = [(3, 1), (3, 1), (5, 2), (5, 2), (5, 2), (9, 9)]  # over-delete
    delta = EdgeDelta.from_arrays([d for d, _ in ins], [s for _, s in ins],
                                  [d for d, _ in dels],
                                  [s for _, s in dels], n_nodes=16)
    csc = pipeline.convert(_coo(dst, src, 16, capacity=16))
    out = _check(csc, delta, dst, src, ins, dels, mode="merge")
    # 6 - 2 - 2 (two (5,2) deletes hit, third misses pre-update set)
    # + 2 inserts
    assert int(out.n_edges) == 6 - 4 + 2


def test_all_edges_deleted_and_inserts_only():
    dst, src = [1, 2, 3], [0, 0, 0]
    delta = EdgeDelta.from_arrays([], [], dst, src, n_nodes=8)
    csc = pipeline.convert(_coo(dst, src, 8))
    out = _check(csc, delta, dst, src, [], list(zip(dst, src)),
                 mode="merge")
    assert int(out.n_edges) == 0
    # inserts into the emptied graph
    ins = [(4, 5), (0, 1)]
    delta2 = EdgeDelta.from_arrays([d for d, _ in ins],
                                   [s for _, s in ins], [], [], n_nodes=8)
    _check(out, delta2, [], [], ins, [], mode="merge")


def test_sentinel_heavy_sparse_buffer():
    """n_edges ≪ capacity: the SENTINEL tail must stay inert (never match
    a delete, never shift an insert's slot)."""
    rng = np.random.default_rng(10)
    dst, src, ins, dels, delta = _rand_case(rng, 64, 20, 10, 8, n_miss=4,
                                            d_cap=32)
    csc = pipeline.convert(_coo(dst, src, 64, capacity=1024))
    _check(csc, delta, dst, src, ins, dels, mode="merge")


def test_single_node_graph():
    dst, src = [0, 0], [0, 0]
    ins, dels = [(0, 0)], [(0, 0)]
    delta = EdgeDelta.from_arrays([0], [0], [0], [0], n_nodes=1)
    csc = pipeline.convert(_coo(dst, src, 1))
    _check(csc, delta, dst, src, ins, dels, mode="merge")


def test_output_capacity_growth_and_ptr_tail():
    """out_capacity above the input bucket grows the index buffer; padded
    pointer tails (ptr longer than n_nodes+1) ride through unchanged."""
    rng = np.random.default_rng(11)
    dst, src, ins, dels, delta = _rand_case(rng, 100, 250, 30, 5, d_cap=32)
    csc = pipeline.convert(_coo(dst, src, 100, capacity=256))
    out = _check(csc, delta, dst, src, ins, dels, mode="merge",
                 out_capacity=512)
    assert out.idx.shape[0] == 512
    assert out.ptr.shape[0] == csc.ptr.shape[0]


def test_chained_deltas_stay_identical():
    """Five successive merges == one convert of the final edge list (the
    living-graph trajectory: errors must not accumulate)."""
    rng = np.random.default_rng(12)
    n_nodes = 200
    dst = list(rng.integers(0, n_nodes, 400).astype(int))
    src = list(rng.integers(0, n_nodes, 400).astype(int))
    csc = pipeline.convert(_coo(dst, src, n_nodes, capacity=1024))
    for step in range(5):
        ins = [(int(rng.integers(n_nodes)), int(rng.integers(n_nodes)))
               for _ in range(20)]
        k = min(15, len(dst))
        victims = rng.choice(len(dst), k, replace=False)
        dels = [(dst[i], src[i]) for i in victims]
        delta = EdgeDelta.from_arrays(
            [d for d, _ in ins], [s for _, s in ins],
            [d for d, _ in dels], [s for _, s in dels],
            n_nodes=n_nodes, capacity=32)
        csc = _check(csc, delta, dst, src, ins, dels, mode="merge")
        dst, src = _oracle_update(dst, src, ins, dels)


# -------------------------------------------------------------- mode resolve
def test_auto_mode_merges_small_deltas_rebuilds_huge_ones():
    from repro.core.costmodel import resolve_delta_mode
    cfg = EngineConfig()
    w = Workload(n=16384, e=131072)
    assert resolve_delta_mode(cfg, w, 256) == "merge"
    assert resolve_delta_mode(cfg, w, 16384) == "merge"  # 12%: measured win
    assert resolve_delta_mode(cfg, w, 131072) == "rebuild"
    # million-edge scale: the rebuild's full sort dwarfs the splice
    assert resolve_delta_mode(cfg, Workload(n=131073, e=1 << 20),
                              131072) == "merge"


def test_delta_program_census_expectations():
    """The numbers the HLO contract prices: resolved delta programs are
    while-free (native delta sorts + fused ranks), and the compiled merge
    path holds three sort ops — one per delta stream in either key scheme
    (the pair is one two-key sort) plus the event-zip merge rung."""
    from repro.analysis.contracts import DELTA_SORTS
    from repro.core.costmodel import delta_while_count
    from repro.launch.hlo_analysis import op_counts
    cfg = EngineConfig()
    w = Workload(n=512, e=2048)
    assert delta_while_count(cfg, w, 256) == 0
    cfg_u = EngineConfig(reindex_strategy="unfused")
    assert delta_while_count(cfg_u, w, 256) == 3  # DELTA_RANK_PASSES
    rng = np.random.default_rng(13)
    for n_nodes in (512, 1 << 17):  # packed key, then the (dst, src) pair
        dst, src, _, _, delta = _rand_case(rng, n_nodes, 300, 20, 10,
                                           d_cap=64)
        csc = pipeline.convert(_coo(dst, src, n_nodes, capacity=512))
        hlo = (jax.jit(lambda c, d: pipeline.apply_delta(c, d, mode="merge"))
               .lower(csc, delta).compile().as_text())
        assert op_counts(hlo).get("sort", 0) == DELTA_SORTS, n_nodes


# ------------------------------------------------------------ property sweep
def test_delta_merge_property_fuzz():
    """Hypothesis property: ANY (graph, delta) in the support produces the
    oracle CSC. Gated — the CI image may not ship hypothesis."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=30, deadline=None)
    @hyp.given(data=st.data())
    def run(data):
        n_nodes = data.draw(st.integers(1, 64), label="n_nodes")
        n_edges = data.draw(st.integers(0, 80), label="n_edges")
        edge = st.tuples(st.integers(0, n_nodes - 1),
                         st.integers(0, n_nodes - 1))
        edges = data.draw(st.lists(edge, min_size=n_edges,
                                   max_size=n_edges), label="edges")
        ins = data.draw(st.lists(edge, max_size=24), label="ins")
        dels = data.draw(st.lists(edge, max_size=24), label="dels")
        dst = [d for d, _ in edges]
        src = [s for _, s in edges]
        delta = EdgeDelta.from_arrays(
            [d for d, _ in ins], [s for _, s in ins],
            [d for d, _ in dels], [s for _, s in dels], n_nodes=n_nodes)
        csc = pipeline.convert(_coo(dst, src, n_nodes, capacity=128))
        _check(csc, delta, dst, src, ins, dels, mode="merge",
               out_capacity=256)

    run()
