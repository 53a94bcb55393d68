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
from .ordering import edge_ordering, edge_ordering_xla, stable_sort_by_key
from .reshaping import data_reshaping, build_pointer_array
from .sampling import sample_khop
from .reindexing import build_reindex_map, reindex_edges
from .costmodel import (EngineConfig, Workload, delta_epilogue_strategy,
                        delta_workload, pointer_reindex_strategy,
                        reindex_query_count, resolve_delta_mode,
                        resolve_delta_sort_strategy,
                        resolve_reindex_strategy, resolve_sort_strategy)


def kernel_fns(cfg: EngineConfig):
    """(chunk_sort_fn, count_fn, merge_fn, digit_pass_fn, rank_fn,
    rename_fn) for ``cfg`` — THE Pallas routing rule. ``use_pallas`` swaps
    in the UPE chunk-sort kernel (digit width = ``cfg.radix_bits``), the
    SCR count kernel, the fused VMEM merge kernel (ladder fan-in =
    ``cfg.merge_fan_in``), the tiled global-radix digit-pass kernel pair
    (histogram tile = ``cfg.w_upe``), and the fused SCR epilogue pair
    (VMEM-resident rank search + rename lookup,
    ``kernels/reindex_epilogue.py``); one definition shared by
    ``convert``, ``sample_subgraph`` and the mesh-sharded engine so no
    path can silently drop a knob. On a TPU backend, each kernel Mosaic
    refuses (``kernels.ops.MOSAIC_REFUSALS``) is a stand-in that raises
    ``NotImplementedError`` naming it when the route asks for it.
    Not listed here: the windowed pointer kernel that the full-graph
    :func:`convert` takes on a TPU whatever ``use_pallas`` says, where
    none of these builds the pointers (:func:`pointer_array`).
    """
    if not cfg.use_pallas:
        return None, None, None, None, None, None
    from repro.kernels import ops as _kops
    tpu = _kops.refused_on_tpu
    return (tpu("radix_sort_chunks",
                _kops.make_pallas_chunk_sort_fn(cfg.radix_bits)),
            _kops.pallas_count_fn,
            tpu("fused_merge_rounds",
                _kops.make_pallas_merge_fn(cfg.merge_fan_in)),
            tpu("global_digit_pass",
                _kops.make_pallas_digit_pass_fn(cfg.radix_bits, cfg.w_upe)),
            tpu("rank_search_tiles", _kops.pallas_rank_fn),
            tpu("reindex_rename_tiles", _kops.pallas_rename_fn))


def convert(coo: COO, cfg: EngineConfig | None = None,
            count_fn=None, chunk_sort_fn=None, _windowed=None) -> CSC:
    """Graph conversion: Ordering + Reshaping under an engine config.

    ``cfg.sort_mode`` selects packed single-pass vs two-pass LSD Ordering
    (bit-identical outputs; "auto" packs whenever the VID space fits one
    int32 key), ``cfg.sort_strategy`` the reduction structure of every
    global sort — chunked radix sort + k-ary merge ladder
    (``cfg.merge_fan_in`` runs per rung) vs the merge-free global radix
    sort; "auto" is resolved here through the Table-I cost model
    (``costmodel.resolve_sort_strategy``) on this graph's (capacity,
    n_nodes) workload, so the dispatched program is the one the model
    priced. ``cfg.radix_bits`` is the digit width of every radix pass on
    both the jnp and Pallas paths. ``cfg.use_pallas`` routes the chunk
    sort / merge ladder / global digit passes / pointer build through the
    Pallas kernels (interpret mode on CPU; Mosaic on TPU). Explicit
    ``count_fn``/``chunk_sort_fn`` override.

    Where no Pallas route builds the pointers, the program lowered for a
    TPU builds them with the windowed SCR kernel
    (``kernels/pointer_window.py``) and the one lowered for the CPU with
    the rank search (:func:`pointer_array`); ``_windowed`` forces one
    side, for tests.
    """
    cfg = cfg or EngineConfig()
    k_sort, k_count, merge_fn, digit_pass_fn, k_rank, _ = kernel_fns(cfg)
    chunk_sort_fn = chunk_sort_fn or k_sort
    count_fn = count_fn or k_count
    w = Workload(n=coo.n_nodes, e=coo.capacity)
    strategy = resolve_sort_strategy(cfg, w)
    with jax.named_scope(scopes.CONVERT_ORDERING):
        sorted_coo = edge_ordering(coo, chunk=min(cfg.w_upe, coo.capacity),
                                   radix_bits=cfg.radix_bits,
                                   map_batch=cfg.n_upe,
                                   chunk_sort_fn=chunk_sort_fn,
                                   merge_fn=merge_fn, mode=cfg.sort_mode,
                                   strategy=strategy,
                                   fan_in=cfg.merge_fan_in,
                                   digit_pass_fn=digit_pass_fn)
    # pointer build = SCR epilogue: fused (statically unrolled rank
    # rounds, Pallas tiles when routed) exactly where the model prices it
    ptr_fused = pointer_reindex_strategy(cfg, w) == "fused"
    rank_fn = k_rank if ptr_fused else None
    with jax.named_scope(scopes.CONVERT_POINTER):
        if count_fn is not None or rank_fn is not None:
            return data_reshaping(sorted_coo, count_fn=count_fn,
                                  unroll=ptr_fused, rank_fn=rank_fn)
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
    a from-scratch :func:`convert` of the post-update edge list. The delta
    sorts dispatch through the SAME reduction machinery as every Ordering
    but resolve through ``costmodel.resolve_delta_sort_strategy``, which
    prices the thunk-materialized output the splice gathers need (the
    native sort wins at delta buckets); every rank pass lowers fused or
    unfused as ``costmodel.delta_epilogue_strategy`` prices it.

    The caller guarantees the surviving edge count fits ``out_capacity``
    (``engine.service.PreprocService.apply_delta`` grows the bucket on
    overflow — a traced count cannot raise here).
    """
    with jax.named_scope(scopes.DELTA_APPLY):
        return _apply_delta(csc, delta, cfg or EngineConfig(), mode,
                            out_capacity)


def _apply_delta(csc: CSC, delta: EdgeDelta, cfg: EngineConfig, mode: str,
                 out_capacity: int | None) -> CSC:
    k_sort, k_count, merge_fn, digit_pass_fn, k_rank, _ = kernel_fns(cfg)
    e_cap = csc.idx.shape[0]
    d_cap = delta.capacity
    w = Workload(n=csc.n_nodes, e=e_cap)
    if mode == "auto":
        mode = resolve_delta_mode(cfg, w, d_cap)
    if mode not in ("merge", "rebuild"):
        raise ValueError(f"unknown delta mode {mode!r}")
    d_strategy = resolve_delta_sort_strategy(cfg, delta_workload(w, d_cap))
    fused = delta_epilogue_strategy(cfg, w, d_cap) == "fused"

    def delta_sort_fn(k, v, bound):
        return stable_sort_by_key(k, v, bound, chunk=min(cfg.w_upe, d_cap),
                                  radix_bits=cfg.radix_bits,
                                  map_batch=cfg.n_upe,
                                  chunk_sort_fn=k_sort, merge_fn=merge_fn,
                                  strategy=d_strategy,
                                  fan_in=cfg.merge_fan_in,
                                  digit_pass_fn=digit_pass_fn)

    if mode == "merge":
        return delta_merge(csc, delta, sort_fn=delta_sort_fn, unroll=fused,
                           out_capacity=out_capacity)
    coo = rebuild_coo(csc, delta, sort_fn=delta_sort_fn, unroll=fused)
    wc = Workload(n=coo.n_nodes, e=coo.capacity)
    sorted_coo = edge_ordering(coo, chunk=min(cfg.w_upe, coo.capacity),
                               radix_bits=cfg.radix_bits,
                               map_batch=cfg.n_upe, chunk_sort_fn=k_sort,
                               merge_fn=merge_fn, mode=cfg.sort_mode,
                               strategy=resolve_sort_strategy(cfg, wc),
                               fan_in=cfg.merge_fan_in,
                               digit_pass_fn=digit_pass_fn)
    ptr_fused = pointer_reindex_strategy(cfg, wc) == "fused"
    full = data_reshaping(sorted_coo, count_fn=k_count, unroll=ptr_fused,
                          rank_fn=k_rank if ptr_fused else None)
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
                    cfg: EngineConfig | None = None,
                    count_fn=None, chunk_sort_fn=None) -> Subgraph:
    """Selecting + Reindexing + subgraph conversion → sampled CSC subgraph.

    The subgraph re-conversion always qualifies for the packed-key
    single-pass Ordering under ``sort_mode="auto"``: the reindexed VID
    space is batch-sized, so (dst, src) packs into one int32 key.
    """
    cfg = cfg or EngineConfig()
    (k_sort, k_count, merge_fn, digit_pass_fn, k_rank,
     k_rename) = kernel_fns(cfg)
    chunk_sort_fn = chunk_sort_fn or k_sort
    count_fn = count_fn or k_count
    with jax.named_scope(scopes.SAMPLE_SELECT):
        nodes, e_dst, e_src = sample_khop(
            csc, batch_nodes, fanouts, key, selection=cfg.selection)
    n_cap = nodes.shape[0]
    # Reindexing rides the spine: ONE shared strategy-dispatched sort of
    # the collected VID list (same reduction machinery as the Ordering,
    # resolved on the VID-stream workload), then rank-arithmetic epilogue
    # passes whose loop structure is the cfg's reindex_strategy — fused
    # (statically unrolled / Pallas VMEM tiles) or unfused (fori_loops),
    # priced per query count by the Table-I model.
    r_sort_strat = resolve_sort_strategy(
        cfg, Workload(n=csc.n_nodes, e=next_pow2(n_cap)))

    def reindex_sort_fn(k, v, bound):
        return stable_sort_by_key(
            k, v, bound, chunk=min(cfg.w_upe, k.shape[0]),
            radix_bits=cfg.radix_bits, map_batch=cfg.n_upe,
            chunk_sort_fn=chunk_sort_fn, merge_fn=merge_fn,
            strategy=r_sort_strat, fan_in=cfg.merge_fan_in,
            digit_pass_fn=digit_pass_fn)

    r_strat = resolve_reindex_strategy(
        cfg, reindex_query_count(n_cap, e_dst.shape[0]), n_cap)
    r_fused = r_strat == "fused"
    with jax.named_scope(scopes.SAMPLE_REINDEX):
        rmap = build_reindex_map(nodes, vid_bound=csc.n_nodes,
                                 strategy=r_strat, sort_fn=reindex_sort_fn,
                                 rank_fn=k_rank if r_fused else None,
                                 rename_fn=k_rename if r_fused else None)
        sub_coo_raw = reindex_edges(rmap, e_dst, e_src, n_nodes_cap=n_cap)
    # pad edge buffers to pow2 for the chunked sorter
    e_cap = next_pow2(sub_coo_raw.dst.shape[0])
    strategy = resolve_sort_strategy(cfg, Workload(n=n_cap, e=e_cap))
    sub_ptr_fused = resolve_reindex_strategy(cfg, n_cap + 1, e_cap) == "fused"
    with jax.named_scope(scopes.SAMPLE_RECONVERT):
        pad = (0, e_cap - sub_coo_raw.dst.shape[0])
        sub_coo = COO(
            dst=jnp.pad(sub_coo_raw.dst, pad, constant_values=int(SENTINEL)),
            src=jnp.pad(sub_coo_raw.src, pad, constant_values=int(SENTINEL)),
            n_edges=sub_coo_raw.n_edges, n_nodes=n_cap)
        sub_sorted = edge_ordering(sub_coo, chunk=min(cfg.w_upe, e_cap),
                                   radix_bits=cfg.radix_bits,
                                   chunk_sort_fn=chunk_sort_fn,
                                   merge_fn=merge_fn, mode=cfg.sort_mode,
                                   strategy=strategy,
                                   fan_in=cfg.merge_fan_in,
                                   digit_pass_fn=digit_pass_fn)
        sub_csc = data_reshaping(sub_sorted, count_fn=count_fn,
                                 unroll=sub_ptr_fused,
                                 rank_fn=k_rank if sub_ptr_fused else None)
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
