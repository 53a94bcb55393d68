"""repro.serve.gnn: the GNN serving acceptance guards — batched-vs-
sequential prediction bit-equality over mixed fan-outs/capacities, FIFO
admission + lowest-slot-first, slot reuse after retirement, and the
zero-recompile guard (``step_cache_size()==1`` after heterogeneous
requests) — mirroring tests/test_serve.py on the LM side."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.graphsage_reddit import smoke_config
from repro.core import pipeline
from repro.core.graph import COO, SENTINEL, random_coo
from repro.models.gnn import (GraphBatch, gnn_apply, gnn_apply_batched,
                              gnn_init, subgraph_batch)
from repro.serve import GnnServeEngine

jax.config.update("jax_platform_name", "cpu")

N_NODES = 256
D_FEAT = 12
N_CLASSES = 7

_rng = np.random.default_rng(0)
_dst, _src = random_coo(_rng, N_NODES, 1500)
COO_G = COO.from_arrays(_dst, _src, N_NODES, capacity=2048)
CSC_G = pipeline.convert(COO_G)
GCFG = smoke_config()
FEATS = jnp.asarray(_rng.normal(size=(N_NODES, D_FEAT)).astype(np.float32))
PARAMS = gnn_init(GCFG, jax.random.PRNGKey(1), d_in=D_FEAT,
                  n_classes=N_CLASSES)


def _make_engine(n_slots=2, seed_cap=8, fanouts=(3, 2), **kw):
    return GnnServeEngine(GCFG, PARAMS, CSC_G, FEATS, fanouts=fanouts,
                          n_slots=n_slots, seed_cap=seed_cap, **kw)


def _requests(n, rng, seed_cap=8):
    """Mixed-size seed lists: every count in [1, seed_cap]."""
    return [rng.choice(N_NODES, int(rng.integers(1, seed_cap + 1)),
                       replace=False).tolist() for _ in range(n)]


def _sequential_reference(eng, reqs):
    """The batch-1 oracle: one jitted sample→convert→forward per request,
    with the request's own key — what a pre-batcher serving loop runs."""
    fn = jax.jit(eng.slot_fn)
    outs = []
    for rid, seeds in enumerate(reqs):
        row = np.full((eng.seed_cap,), int(SENTINEL), np.int32)
        row[:len(seeds)] = seeds
        preds = fn(eng.params, jnp.asarray(row), eng.request_key(rid))
        outs.append(np.asarray(preds)[:len(seeds)].tolist())
    return outs


# ------------------------------------------------------ batched == sequential
@pytest.mark.parametrize("fanouts,seed_cap,n_slots",
                         [((3, 2), 8, 2), ((2,), 4, 4), ((2, 2, 2), 8, 2)])
def test_batched_serve_matches_sequential_loop(fanouts, seed_cap, n_slots):
    """Slot independence across fan-out depths and capacity buckets: every
    request's predictions are exactly what the batch-1 sequential loop
    produces, regardless of its slot neighbours (admission schedule does
    not leak into results)."""
    rng = np.random.default_rng(1)
    reqs = _requests(6, rng, seed_cap=seed_cap)
    eng = _make_engine(n_slots=n_slots, seed_cap=seed_cap, fanouts=fanouts)
    for seeds in reqs:
        eng.submit(seeds)
    eng.close_submissions()
    completed = eng.run()
    assert len(completed) == len(reqs)
    want = _sequential_reference(eng, reqs)
    for req in completed:
        assert req.tokens_out == want[req.rid], req.rid
        assert len(req.tokens_out) == len(reqs[req.rid])
        assert all(0 <= p < N_CLASSES for p in req.tokens_out)


# ----------------------------------------------------- admission/retirement
def test_admission_is_fifo_and_slots_fill_lowest_first():
    rng = np.random.default_rng(2)
    reqs = _requests(7, rng)
    eng = _make_engine(n_slots=4)
    handles = [eng.submit(s) for s in reqs]
    eng.close_submissions()
    completed = eng.run()
    assert len(completed) == len(reqs)
    admits = [h.admit_t for h in handles]
    assert all(a is not None for a in admits)
    assert admits == sorted(admits)
    # the first wave seats in slot order 0..3 (lowest free slot first)
    assert [h.slot for h in handles[:4]] == [0, 1, 2, 3]


def test_retirement_frees_slots_for_later_requests():
    """More requests than slots: every request still completes with one
    prediction per seed, through slot reuse."""
    rng = np.random.default_rng(3)
    reqs = _requests(9, rng)
    eng = _make_engine(n_slots=2)
    for s in reqs:
        eng.submit(s)
    eng.close_submissions()
    completed = eng.run()
    assert sorted(r.rid for r in completed) == list(range(9))
    for r in completed:
        assert len(r.tokens_out) == len(reqs[r.rid])
    assert eng.stats.admitted == eng.stats.retired == 9
    # one-step retirement: strictly more requests than steps-per-request
    assert eng.stats.steps < 9


# -------------------------------------------------------- zero recompiles
def test_bucket_reuse_zero_recompiles_for_mixed_sizes():
    """The acceptance guard: after warmup, admitting requests of every
    seed count in [1, seed_cap] reuses the ONE compiled step program —
    admission writes SENTINEL-padded rows into fixed pow2 buckets and
    never changes a traced shape."""
    eng = _make_engine(n_slots=4)
    eng.submit([0, 1, 2])  # warmup compile
    eng.close_submissions()
    eng.run()
    assert eng.step_cache_size() == 1
    rng = np.random.default_rng(4)
    eng.reopen()
    reqs = [rng.choice(N_NODES, k, replace=False).tolist()
            for k in range(1, 9)]  # every seed count in [1, 8]
    for s in reqs:
        eng.submit(s)
    eng.close_submissions()
    completed = eng.run()
    assert len(completed) == 8
    assert eng.step_cache_size() == 1  # zero recompiles after warmup


# ----------------------------------------------------------- submit guards
def test_submit_validates_seed_count_and_range():
    eng = _make_engine()
    with pytest.raises(ValueError):
        eng.submit([])
    with pytest.raises(ValueError):
        eng.submit(list(range(eng.seed_cap + 1)))
    with pytest.raises(ValueError):
        eng.submit([N_NODES])  # out of VID range


# ------------------------------------------- batched forward building blocks
def test_ptr_segment_sum_matches_segment_sum():
    """The scatter-free pointer reduction computes the same aggregation as
    jax.ops.segment_sum (float summation order differs → allclose, not
    bit-equal; bit-equality only holds batched-vs-sequential where both
    legs run the pointer path)."""
    sub = pipeline.sample_subgraph(
        CSC_G, jnp.arange(8, dtype=jnp.int32), (3, 2), jax.random.PRNGKey(5))
    batch = subgraph_batch(sub, FEATS)
    assert batch.ptr is not None
    no_ptr = GraphBatch(edge_dst=batch.edge_dst, edge_src=batch.edge_src,
                        node_feat=batch.node_feat, labels=batch.labels,
                        label_mask=batch.label_mask)
    out_ptr = gnn_apply(GCFG, PARAMS, batch)
    out_seg = gnn_apply(GCFG, PARAMS, no_ptr)
    np.testing.assert_allclose(np.asarray(out_ptr), np.asarray(out_seg),
                               rtol=2e-5, atol=2e-5)


def test_gnn_apply_batched_lanes_match_single():
    """vmap lanes of the batched forward are bit-identical to gnn_apply on
    each lane's own batch (the model half of the serving equality)."""
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    rows = jnp.stack([jnp.arange(i * 4, i * 4 + 4, dtype=jnp.int32)
                      for i in range(3)])
    sub = pipeline.sample_subgraph_batched(CSC_G, rows, (2, 2), keys)
    batch = jax.vmap(lambda s: subgraph_batch(s, FEATS))(sub)
    stacked = gnn_apply_batched(GCFG, PARAMS, batch)
    for i in range(3):
        one = pipeline.sample_subgraph(CSC_G, rows[i], (2, 2), keys[i])
        want = gnn_apply(GCFG, PARAMS, subgraph_batch(one, FEATS))
        np.testing.assert_array_equal(np.asarray(stacked[i]),
                                      np.asarray(want))


# ------------------------------------------------------- streaming updates
def test_interleaved_updates_and_inference_match_sequential_oracle():
    """Living-graph serving: updates and inference interleave on one FIFO.
    Every prediction equals the sequential oracle that replays the SAME
    submission order (each query sampling the graph as of its position in
    the stream), the final CSC is bit-identical to oracle-chained
    apply_delta, and the whole stream runs with ZERO step recompiles
    after warmup — the post-update CSC keeps the exact serve shapes."""
    from repro.core.delta import EdgeDelta
    from repro.engine.service import apply_delta_jit
    rng = np.random.default_rng(5)
    eng = _make_engine(n_slots=2, delta_cap=16)
    edges = list(zip(_dst.tolist(), _src.tolist()))

    def rand_update():
        ins = [(int(rng.integers(N_NODES)), int(rng.integers(N_NODES)))
               for _ in range(4)]
        dels = [edges[int(rng.integers(len(edges)))] for _ in range(3)]
        return ins, dels

    # warmup: compile the step AND the delta-apply program
    history = [("q", [0, 1, 2]), ("u", *rand_update()), ("q", [3, 4])]
    for item in history:
        if item[0] == "q":
            eng.submit(item[1])
        else:
            eng.submit_update(item[1], item[2])
    eng.close_submissions()
    completed = eng.run()
    base_cache = eng.step_cache_size()

    eng.reopen()
    stream = []
    for i in range(12):
        if i % 3 == 2:
            stream.append(("u", *rand_update()))
            eng.submit_update(stream[-1][1], stream[-1][2])
        else:
            seeds = rng.choice(
                N_NODES, int(rng.integers(1, eng.seed_cap + 1)),
                replace=False).tolist()
            stream.append(("q", seeds))
            eng.submit(seeds)
    eng.close_submissions()
    completed += eng.run()
    assert eng.step_cache_size() == base_cache  # zero recompiles

    # sequential oracle: replay the submission history in rid order,
    # chaining apply_delta exactly where the updates sat in the stream
    fn = jax.jit(eng.slot_fn)
    oracle_csc = CSC_G
    want = {}
    for rid, item in enumerate(history + stream):
        if item[0] == "q":
            seeds = item[1]
            row = np.full((eng.seed_cap,), int(SENTINEL), np.int32)
            row[:len(seeds)] = seeds
            bundle = {"gnn": eng.params["gnn"], "csc": oracle_csc,
                      "features": FEATS}
            preds = fn(bundle, jnp.asarray(row), eng.request_key(rid))
            want[rid] = np.asarray(preds)[:len(seeds)].tolist()
        else:
            _, ins, dels = item
            delta = EdgeDelta.from_arrays(
                [d for d, _ in ins], [s for _, s in ins],
                [d for d, _ in dels], [s for _, s in dels],
                n_nodes=N_NODES, capacity=eng.delta_cap)
            oracle_csc = apply_delta_jit(
                oracle_csc, delta, cfg=eng.engine_cfg,
                out_capacity=int(oracle_csc.idx.shape[0]))
            want[rid] = []
    assert len(completed) == len(history) + len(stream)
    for req in completed:
        assert req.tokens_out == want[req.rid], req.rid
    np.testing.assert_array_equal(np.asarray(eng.params["csc"].ptr),
                                  np.asarray(oracle_csc.ptr))
    np.testing.assert_array_equal(np.asarray(eng.params["csc"].idx),
                                  np.asarray(oracle_csc.idx))


def test_update_finishes_once_its_csc_is_ready(monkeypatch):
    """A streamed update's ``finish_t`` is stamped after the engine waited
    for the params that hold the updated CSC, not at its dispatch."""
    import time
    eng = _make_engine(delta_cap=8)
    real = jax.block_until_ready
    ready = []

    def spy(x):
        out = real(x)
        if isinstance(x, dict) and "csc" in x:
            ready.append((time.perf_counter(), x["csc"]))
        return out

    monkeypatch.setattr(jax, "block_until_ready", spy)
    upd = eng.submit_update([(1, 2), (3, 4)], [])
    eng.close_submissions()
    eng.run()
    (t_ready, csc), = ready
    assert csc is eng.params["csc"] and csc is not CSC_G
    assert t_ready <= upd.finish_t
    assert upd.admit_t <= upd.finish_t


def test_submit_update_validates_size_and_vids():
    eng = _make_engine(delta_cap=8)
    with pytest.raises(ValueError):
        eng.submit_update([], [])
    with pytest.raises(ValueError):
        eng.submit_update([(0, 1)] * 9, [])  # over the delta bucket
    with pytest.raises(ValueError):
        eng.submit_update([(0, N_NODES)], [])  # VID out of range


def test_service_sample_batched_buckets_and_caches():
    """The engine-service batched entry: per-row pow2 SENTINEL bucketing,
    (config, bucket) accounting, zero recompiles on re-dispatch."""
    from repro.engine.service import (PreprocService,
                                      sample_batched_cache_size)
    svc = PreprocService(fanouts=(2, 2))
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    rows = jnp.arange(6, dtype=jnp.int32).reshape(2, 3)  # buckets to [2, 4]
    sub = svc.sample_batched(CSC_G, rows, keys)
    assert sub.order.shape[0] == 2
    before = sample_batched_cache_size()
    sub2 = svc.sample_batched(CSC_G, rows, keys)
    assert sample_batched_cache_size() == before  # re-dispatch: cache hit
    assert svc.stats.n_dispatches == 2 and svc.stats.n_unique_keys == 1
    np.testing.assert_array_equal(np.asarray(sub.order),
                                  np.asarray(sub2.order))
