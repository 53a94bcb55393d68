"""End-to-end AutoGNN preprocessing pipeline (paper Fig. 14).

COO → [Ordering] → sorted COO → [Reshaping] → CSC → [Selecting] → sampled
nodes/edges → [Reindexing] → sampled Subgraph (itself converted to CSC by a
second Ordering + Reshaping pass, exactly as the paper's dataflow does).

Everything is a single jittable function of static shapes so the whole
preprocessing workflow is one XLA program — the TPU analog of "fully
automated in hardware, removing preprocessing from the critical path".
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import scopes

from .delta import EdgeDelta, delta_merge, rebuild_coo
from .graph import COO, CSC, SENTINEL, Subgraph, next_pow2, pad_to
from .ordering import edge_ordering, edge_ordering_xla
from .reshaping import data_reshaping, build_pointer_array
from .sampling import sample_khop
from .reindexing import build_reindex_map, reindex_edges
from .costmodel import (EngineConfig, Workload, delta_epilogue_strategy,
                        pointer_reindex_strategy, reindex_query_count,
                        resolve_delta_mode, resolve_reindex_strategy)


def convert(coo: COO, cfg: EngineConfig | None = None,
            count_fn=None, _windowed=None) -> CSC:
    """Graph conversion: Ordering + Reshaping under an engine config.

    ``cfg.sort_mode`` selects the packed single-key vs the (dst, src) pair
    Ordering (bit-identical outputs; "auto" packs whenever the VID space
    fits one int32 key); either is one native sort. ``count_fn(sorted_dst,
    targets)`` builds the pointers with a caller's set-count (e.g.
    ``kernels.set_count.pallas_count_fn``).

    Otherwise the program lowered for a TPU builds the pointers with the
    windowed SCR kernel (``kernels/pointer_window.py``) and the one
    lowered for the CPU with the rank search (:func:`pointer_array`);
    ``_windowed`` forces one side, for tests.
    """
    cfg = cfg or EngineConfig()
    with jax.named_scope(scopes.CONVERT_ORDERING):
        sorted_coo = edge_ordering(coo, mode=cfg.sort_mode)
    # pointer build = SCR epilogue: fused (statically unrolled rank
    # rounds) exactly where the model prices it
    ptr_fused = pointer_reindex_strategy(
        cfg, Workload(n=coo.n_nodes, e=coo.capacity)) == "fused"
    with jax.named_scope(scopes.CONVERT_POINTER):
        if count_fn is not None:
            return data_reshaping(sorted_coo, count_fn=count_fn,
                                  unroll=ptr_fused)
        ptr = pointer_array(sorted_coo.dst, coo.n_nodes, unroll=ptr_fused,
                            windowed=_windowed)
    return CSC(ptr=ptr, idx=sorted_coo.src, n_edges=sorted_coo.n_edges,
               n_nodes=coo.n_nodes)


def pointer_array(sorted_dst: jnp.ndarray, n_nodes: int, unroll: bool,
                  windowed: bool | None = None) -> jnp.ndarray:
    """The full-graph convert's CSC pointers, chosen per lowering: the
    rank search (``build_pointer_array``) for the CPU; for a TPU the
    windowed SCR kernel, which reads the stream in order, since there the
    rank search's single-element gathers (N+1 in each of its log2(E)
    rounds) cost ~16 ns each. ``windowed`` forces one side."""
    def rank(d):
        return build_pointer_array(d, n_nodes, unroll=unroll)

    def window(d):
        from repro.kernels.pointer_window import windowed_pointer_array
        return windowed_pointer_array(d, n_nodes)

    if windowed is None:
        return jax.lax.platform_dependent(sorted_dst, cpu=rank,
                                          default=window)
    return (window if windowed else rank)(sorted_dst)


def apply_delta(csc: CSC, delta: EdgeDelta, cfg: EngineConfig | None = None,
                mode: str = "auto", out_capacity: int | None = None) -> CSC:
    """Incremental conversion: splice one insert/delete batch into a
    sorted CSC (paper's conversion kept warm under mutating traffic).

    ``mode="merge"`` runs the O(delta) path (``core.delta.delta_merge``:
    delta-only sorts, SENTINEL-tombstoned deletes through the rank/gather
    router, ONE merge rung, local pointer patch); ``mode="rebuild"``
    tombstones + concatenates and re-converts the combined edge buffer;
    ``"auto"`` is resolved here through the Table-I delta terms
    (``costmodel.resolve_delta_mode``) on this (capacity, delta-bucket)
    workload — so a delta that is a large fraction of the graph falls back
    to the rebuild the model prices cheaper. Both modes return a CSC with
    ``out_capacity`` (default: the input's) index slots, bit-identical to
    a from-scratch :func:`convert` of the post-update edge list. Every
    rank pass lowers fused or unfused as
    ``costmodel.delta_epilogue_strategy`` prices it.

    The caller guarantees the surviving edge count fits ``out_capacity``
    (``engine.service.PreprocService.apply_delta`` grows the bucket on
    overflow — a traced count cannot raise here).
    """
    with jax.named_scope(scopes.DELTA_APPLY):
        return _apply_delta(csc, delta, cfg or EngineConfig(), mode,
                            out_capacity)


def _apply_delta(csc: CSC, delta: EdgeDelta, cfg: EngineConfig, mode: str,
                 out_capacity: int | None) -> CSC:
    e_cap = csc.idx.shape[0]
    d_cap = delta.capacity
    w = Workload(n=csc.n_nodes, e=e_cap)
    if mode == "auto":
        mode = resolve_delta_mode(cfg, w, d_cap)
    if mode not in ("merge", "rebuild"):
        raise ValueError(f"unknown delta mode {mode!r}")
    fused = delta_epilogue_strategy(cfg, w, d_cap) == "fused"
    if mode == "merge":
        return delta_merge(csc, delta, unroll=fused,
                           out_capacity=out_capacity)
    coo = rebuild_coo(csc, delta, unroll=fused)
    sorted_coo = edge_ordering(coo, mode=cfg.sort_mode)
    ptr_fused = pointer_reindex_strategy(
        cfg, Workload(n=coo.n_nodes, e=coo.capacity)) == "fused"
    full = data_reshaping(sorted_coo, unroll=ptr_fused)
    out_cap = e_cap if out_capacity is None else out_capacity
    idx = (full.idx[:out_cap] if out_cap <= full.idx.shape[0]
           else pad_to(full.idx, out_cap, SENTINEL))
    ptr = full.ptr
    if csc.ptr.shape[0] > ptr.shape[0]:  # preserve padded pointer tails
        ptr = pad_to(ptr, csc.ptr.shape[0], ptr[-1])
    return CSC(ptr=ptr, idx=idx, n_edges=full.n_edges, n_nodes=csc.n_nodes)


def convert_xla(coo: COO) -> CSC:
    """Baseline conversion: XLA comparison sort + searchsorted."""
    sorted_coo = edge_ordering_xla(coo)
    ptr = jnp.searchsorted(
        sorted_coo.dst, jnp.arange(coo.n_nodes + 1, dtype=jnp.int32),
        side="left", method="sort").astype(jnp.int32)
    return CSC(ptr=ptr, idx=sorted_coo.src, n_edges=coo.n_edges,
               n_nodes=coo.n_nodes)


def sample_subgraph(csc: CSC, batch_nodes: jnp.ndarray,
                    fanouts: tuple[int, ...], key: jax.Array,
                    cfg: EngineConfig | None = None) -> Subgraph:
    """Selecting + Reindexing + subgraph conversion → sampled CSC subgraph.

    The subgraph re-conversion always qualifies for the packed-key
    Ordering under ``sort_mode="auto"``: the reindexed VID space is
    batch-sized, so (dst, src) packs into one int32 key.
    """
    cfg = cfg or EngineConfig()
    with jax.named_scope(scopes.SAMPLE_SELECT):
        nodes, e_dst, e_src = sample_khop(
            csc, batch_nodes, fanouts, key, selection=cfg.selection)
    n_cap = nodes.shape[0]
    # Reindexing rides the spine: ONE native sort of the collected VID
    # list, then rank-arithmetic epilogue passes whose loop structure is
    # the cfg's reindex_strategy — fused (statically unrolled) or unfused
    # (fori_loops), priced per query count by the Table-I model.
    r_strat = resolve_reindex_strategy(
        cfg, reindex_query_count(n_cap, e_dst.shape[0]), n_cap)
    with jax.named_scope(scopes.SAMPLE_REINDEX):
        rmap = build_reindex_map(nodes, vid_bound=csc.n_nodes,
                                 strategy=r_strat)
        sub_coo_raw = reindex_edges(rmap, e_dst, e_src, n_nodes_cap=n_cap)
    # pad edge buffers to a pow2 bucket
    e_cap = next_pow2(sub_coo_raw.dst.shape[0])
    sub_ptr_fused = resolve_reindex_strategy(cfg, n_cap + 1, e_cap) == "fused"
    with jax.named_scope(scopes.SAMPLE_RECONVERT):
        pad = (0, e_cap - sub_coo_raw.dst.shape[0])
        sub_coo = COO(
            dst=jnp.pad(sub_coo_raw.dst, pad, constant_values=int(SENTINEL)),
            src=jnp.pad(sub_coo_raw.src, pad, constant_values=int(SENTINEL)),
            n_edges=sub_coo_raw.n_edges, n_nodes=n_cap)
        sub_sorted = edge_ordering(sub_coo, mode=cfg.sort_mode)
        sub_csc = data_reshaping(sub_sorted, unroll=sub_ptr_fused)
    return Subgraph(csc=sub_csc, order=rmap.order, n_sub_nodes=rmap.n_unique)


def sample_subgraph_batched(csc: CSC, batch_nodes: jnp.ndarray,
                            fanouts: tuple[int, ...], keys: jax.Array,
                            cfg: EngineConfig | None = None) -> Subgraph:
    """Slot-batched sampling: one :func:`sample_subgraph` lane per row.

    ``batch_nodes`` is [S, B] seed rows (SENTINEL-padded to a shared pow2
    bucket), ``keys`` is [S] per-row PRNG keys; the result is a
    ``Subgraph`` whose every leaf carries a leading [S] axis. Each lane
    runs the exact single-request program — same reindex_strategy
    dispatch, same RNG draws for its (seeds, key) — so lane ``i`` of the
    batched output is bit-identical to ``sample_subgraph(csc,
    batch_nodes[i], fanouts, keys[i], cfg)``, independent of what the
    other lanes sample. That independence is what lets the serve engine
    batch concurrent requests without admission order leaking into
    results (asserted in tests/test_gnn_serve.py).
    """
    cfg = cfg or EngineConfig()

    def one_row(bn, key):
        return sample_subgraph(csc, bn, fanouts, key, cfg)

    return jax.vmap(one_row)(batch_nodes, keys)


@partial(jax.jit, static_argnames=("fanouts", "cfg"))
def preprocess(coo: COO, batch_nodes: jnp.ndarray, fanouts: tuple[int, ...],
               key: jax.Array, cfg: EngineConfig = EngineConfig()
               ) -> Subgraph:
    """The full AutoGNN workflow as one XLA program (paper Fig. 14)."""
    csc = convert(coo, cfg)
    return sample_subgraph(csc, batch_nodes, fanouts, key, cfg)


@partial(jax.jit, static_argnames=("fanouts",))
def preprocess_xla_baseline(coo: COO, batch_nodes: jnp.ndarray,
                            fanouts: tuple[int, ...], key: jax.Array
                            ) -> Subgraph:
    """GPU-baseline analog: comparison sorts + searchsorted throughout."""
    csc = convert_xla(coo)
    nodes, e_dst, e_src = sample_khop(csc, batch_nodes, fanouts, key,
                                      selection="keysort")
    n_cap = nodes.shape[0]
    rmap = build_reindex_map(nodes)
    sub_coo = reindex_edges(rmap, e_dst, e_src, n_nodes_cap=n_cap)
    order = jnp.lexsort((sub_coo.src, sub_coo.dst))
    sd, ss = sub_coo.dst[order], sub_coo.src[order]
    ptr = jnp.searchsorted(sd, jnp.arange(n_cap + 1, dtype=jnp.int32),
                           side="left", method="sort").astype(jnp.int32)
    sub_csc = CSC(ptr=ptr, idx=ss, n_edges=sub_coo.n_edges, n_nodes=n_cap)
    return Subgraph(csc=sub_csc, order=rmap.order, n_sub_nodes=rmap.n_unique)


def gather_features(sub: Subgraph, features: jnp.ndarray) -> jnp.ndarray:
    """Embedding-table extraction for the sampled subgraph (paper Fig. 4b)."""
    safe = jnp.clip(sub.order, 0, features.shape[0] - 1)
    rows = jnp.take(features, safe, axis=0)
    valid = (sub.order != SENTINEL)[:, None]
    return jnp.where(valid, rows, 0)
