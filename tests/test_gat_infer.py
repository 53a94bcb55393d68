"""Whole-graph GAT inference (``engine.service.gat_infer_jit``) against the
plain reference ``bench/references/gat.py``, the sampled GAT forward
against the whole-graph one, and the scatter-free lowering of both."""
import dataclasses
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import pipeline
from repro.core.graph import COO, SENTINEL
from repro.engine import service
from repro.launch import hlo_analysis
from repro.models import gnn
from repro.serve import GnnServeEngine

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from bench.references import gat as ref_gat  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

N, D_FEAT, N_CLASSES = 2000, 16, 7
HUB, LONELY = 11, (5, 17, 1999)
CFG = gnn.GNNConfig(name="gat-test", kind="gat", n_layers=3, d_hidden=8,
                    n_heads=4, out_heads=4, skip=True)
# (row tile, edge chunk, chunks per step, projection rows): tiny sizes so
# that the hub's in-edges span several chunks and steps, and the
# projection's last tile overlaps the one before
SIZES = [(8, 64, 4, 96), (16, 128, 3, 400), (32, 1024, 2, 2000)]


def _graph(seed=0, n_edges=20_000, capacity=1 << 15):
    """A skewed graph with a hub of 700 in-edges, nodes with none, the
    graph's own self loops and repeated edges, and a SENTINEL tail."""
    rng = np.random.default_rng(seed)
    dst = rng.zipf(1.3, n_edges) % N
    src = rng.integers(0, N, n_edges)
    dst[:700] = HUB
    dst[700:800] = src[700:800]                    # self loops
    dst[800:900], src[800:900] = dst[900:1000], src[900:1000]  # repeats
    for v in LONELY:
        dst[dst == v] = (v + 1) % N
    return COO.from_arrays(dst.astype(np.int32), src.astype(np.int32), N,
                           capacity=capacity)


@pytest.fixture(scope="module")
def graph():
    coo = _graph()
    return coo, pipeline.convert(coo)


@pytest.fixture(scope="module")
def inputs():
    params = gnn.gnn_init(CFG, jax.random.PRNGKey(1), d_in=D_FEAT,
                          n_classes=N_CLASSES)
    # biases are zero at init; the reference must see them added
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(a.size),
                                              a.shape), params)
    feats = jax.random.normal(jax.random.PRNGKey(2), (N, D_FEAT))
    return feats, params


@pytest.fixture(scope="module")
def reference(graph, inputs, monkeypatch_module):
    coo, _ = graph
    feats, params = inputs
    monkeypatch_module.setattr(ref_gat, "SLOTS", 1 << 12)
    monkeypatch_module.setattr(ref_gat, "PROJECT_ROWS", 512)
    return ref_gat.logits(coo.dst, coo.src, feats, params, n_nodes=N)


@pytest.fixture(scope="module")
def monkeypatch_module():
    with pytest.MonkeyPatch.context() as mp:
        yield mp


def _infer(csc, feats, params, sizes=None, cfg=CFG):
    return jax.jit(partial(gnn.gat_infer, cfg=cfg, _sizes=sizes))(
        csc, feats, params)


def test_the_graph_has_each_case(graph):
    coo, csc = graph
    deg = np.diff(np.asarray(csc.ptr[:N + 1]))
    assert deg[HUB] >= 700 and all(deg[v] == 0 for v in LONELY)
    d, s = np.asarray(coo.dst[:coo.n_edges]), np.asarray(coo.src[:coo.n_edges])
    assert np.sum(d == s) >= 100
    assert len(set(zip(d.tolist(), s.tolist()))) < len(d)
    assert coo.capacity > int(coo.n_edges)


@pytest.mark.parametrize("sizes", SIZES)
def test_whole_graph_matches_the_reference(graph, inputs, reference, sizes):
    _, csc = graph
    feats, params = inputs
    logits, counters = _infer(csc, feats, params, sizes)
    # both float32 on the CPU, summing each row's terms in another order
    # (chunks, online rescaling and a matrix product against segment
    # sums): a few ulps of the O(1) activations a layer, over 3 layers
    np.testing.assert_allclose(np.asarray(logits), reference, rtol=1e-5,
                               atol=1e-5)
    coo = graph[0]
    d, s = (np.asarray(a[:int(coo.n_edges)]) for a in (coo.dst, coo.src))
    assert int(counters["edges"]) == int(np.sum(d != s)) + N
    w, c, b, _ = sizes
    # the hub's 700 in-edges need at least 3 chunks of 64 or 128
    if c <= 128:
        assert int(counters["split_segments"]) >= 1
        assert int(counters["chunks"]) >= -(-N // w) + 700 // c - 1


def test_chunk_counters_follow_the_tiles(graph, inputs):
    _, csc = graph
    feats, params = inputs
    w, c, b, rows = 8, 64, 4, 96
    _, counters = _infer(csc, feats, params, (w, c, b, rows))
    ptr = np.asarray(csc.ptr[:N + 1])
    ends = ptr[np.minimum(np.arange(-(-N // w) + 1) * w, N)]
    per_tile = np.maximum(1, -(-np.diff(ends) // c))
    assert int(counters["chunks"]) == per_tile.sum()


@pytest.mark.parametrize("maximum", [True, False])
def test_pointer_segment_reduce_equals_segment_ops(maximum):
    """The scan over pointers against ``jax.ops.segment_*``: segments that
    span several scan blocks, nodes with no edges, an edge count that is
    no multiple of the block, and a SENTINEL tail."""
    rng = np.random.default_rng(3)
    deg = rng.integers(0, 9, 60)
    deg[[4, 30]] = 0
    deg[17] = 5 * gnn.SCAN_BLOCK + 3
    n, e = len(deg), int(deg.sum())
    dst = np.concatenate([np.repeat(np.arange(n), deg),
                          np.full(37, int(SENTINEL))]).astype(np.int32)
    ptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int32)
    batch = gnn.GraphBatch(edge_dst=jnp.asarray(dst),
                           edge_src=jnp.zeros(len(dst), jnp.int32),
                           node_feat=jnp.zeros((n, 1)),
                           labels=jnp.zeros(n, jnp.int32),
                           label_mask=jnp.zeros(n, bool), ptr=jnp.asarray(ptr))
    vals = jnp.asarray(rng.normal(size=(len(dst), 3)), jnp.float32)
    valid = jnp.asarray(np.arange(len(dst)) < e)[:, None]
    got = gnn.seg_reduce(batch, vals, valid, maximum)
    want = gnn.seg_reduce(dataclasses.replace(batch, ptr=None), vals, valid,
                          maximum)
    assert (e + 37) % gnn.SCAN_BLOCK
    # the max is exact; sums differ only in their order of addition
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0 if maximum else 1e-6,
                               atol=0 if maximum else 1e-5)


def test_sampled_forward_matches_the_whole_graph(graph, inputs):
    """The sampled layer (pointer-based softmax over a ``GraphBatch``)
    over every node's full neighbourhood is the whole-graph layer."""
    _, csc = graph
    feats, params = inputs
    e_cap = csc.idx.shape[0]
    pos = jnp.arange(e_cap, dtype=jnp.int32)
    ptr = csc.ptr[:N + 1]
    dst = jnp.where(pos < csc.n_edges,
                    jnp.searchsorted(ptr, pos, side="right") - 1, SENTINEL)
    batch = gnn.GraphBatch(edge_dst=dst.astype(jnp.int32), edge_src=csc.idx,
                           node_feat=feats, labels=jnp.zeros(N, jnp.int32),
                           label_mask=jnp.zeros(N, bool), ptr=ptr)
    whole, _ = _infer(csc, feats, params, SIZES[0])
    sampled = jax.jit(partial(gnn.gnn_apply, CFG))(params, batch)
    segments = jax.jit(partial(gnn.gnn_apply, CFG))(
        params, dataclasses.replace(batch, ptr=None))
    # float32, different summation orders (scan, matrix product, scatter)
    for out in (sampled, segments):
        np.testing.assert_allclose(np.asarray(out), np.asarray(whole),
                                   rtol=1e-5, atol=1e-5)


def test_whole_graph_entry_lowers_without_scatter(graph, inputs):
    _, csc = graph
    feats, params = inputs
    text = service.gat_infer_jit.lower(csc, feats, params,
                                       cfg=CFG).compile().as_text()
    counts = hlo_analysis.op_counts(text)
    assert counts.get("scatter", 0) == 0 and counts.get("gather", 0) > 0


def test_sampled_gat_serving_step_lowers_without_scatter(graph, inputs):
    """The GAT served through ``GnnServeEngine``: one vmapped step of
    sample, re-convert, reindex and the pointer-based forward."""
    _, csc = graph
    feats, params = inputs
    eng = GnnServeEngine(CFG, params, csc, feats, fanouts=(3, 2),
                         n_slots=2, seed_cap=4)
    counts = hlo_analysis.op_counts(eng.step_hlo_text())
    assert counts.get("scatter", 0) == 0
    eng.submit([1, 2, 3])
    eng.submit([HUB])
    eng.close_submissions()
    done = eng.run()
    assert sorted(len(r.tokens_out) for r in done) == [1, 3]


def test_gat_cora_keeps_its_published_form():
    """gat-cora: 8 heads of 8 concatenated, then one output head over the
    classes, no skip; it still builds and runs, with and without
    pointers."""
    cfg = get_config("gat-cora")
    p = gnn.gnn_init(cfg, jax.random.PRNGKey(0), d_in=1433, n_classes=7)
    first, last = p["layers"]
    assert first["w"].shape == (1433, 64) and last["w"].shape == (64, 7)
    assert last["a_src"].shape == (1, 7)
    assert "w_skip" not in first and "head" not in p
    coo = _graph(n_edges=3000, capacity=4096)
    csc = pipeline.convert(coo)
    feats = jax.random.normal(jax.random.PRNGKey(3), (N, 1433))
    logits, _ = _infer(csc, feats, p, SIZES[0], cfg=cfg)
    assert logits.shape == (N, 7) and bool(jnp.all(jnp.isfinite(logits)))
