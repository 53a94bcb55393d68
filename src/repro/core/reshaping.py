"""Data Reshaping (paper §II-B, §IV-A Fig. 9a): sorted COO → CSC pointer array.

ptr[v] = |{edges : dst < v}| for v in 0..n_nodes — every entry is an
independent set-count, so the whole pointer array is built concurrently
(the paper's key observation; the serial scan-and-bump baseline is kept for
the benchmark comparison).

Counting is order-independent, but we count over the *sorted* dst array
(as the hardware does, consuming the UPE output stream); on sorted input the
blocked compare-reduce equals searchsorted, which tests use as the oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .graph import COO, CSC, SENTINEL, pad_to
from .set_count import count_less_than


def build_pointer_array(sorted_dst: jnp.ndarray, n_nodes: int,
                        ptr_capacity: int | None = None,
                        count_fn=None, block: int = 2048,
                        method: str = "sorted", unroll: bool = False,
                        rank_fn=None) -> jnp.ndarray:
    """Pointer array via set-counting.

    ``method="sorted"`` (default): the paper's reshaper *consumes the sorted
    stream* — each target VID completes when it meets a larger COO element —
    an O(N+E) merge, not an O(N·E) scan. The TPU-native equivalent is a
    parallel rank (searchsorted, method='sort'): same comparator-network
    character, exploits sortedness. (The naive all-pairs compare-reduce was
    3.1e16 comparisons at Reddit scale — §Perf convert iter 2.)

    ``method="scr"``: blocked all-pairs compare-reduce — the literal SCR
    tile formulation; correct on unsorted input too; use for small tiles or
    the Pallas kernel (``count_fn``).

    ``unroll=True`` is the fused SCR epilogue: the rank search's rounds
    unroll statically so the pointer build adds ZERO while ops to the
    convert program (dispatched by ``costmodel.pointer_reindex_strategy``).
    ``rank_fn(sorted, targets, side)`` swaps in the Pallas rank-epilogue
    kernel (``kernels/reindex_epilogue.py``), which runs the same unrolled
    search over VMEM-resident sorted tiles; it outranks ``count_fn``.

    The full-graph convert calls this only where it is lowered for the
    CPU or a Pallas route is set; on a TPU ``pipeline.pointer_array``
    takes the windowed SCR kernel (``kernels/pointer_window.py``).
    Subgraph re-conversion and the delta rebuild keep this path.
    """
    targets = jnp.arange(n_nodes + 1, dtype=jnp.int32)
    if rank_fn is not None:
        ptr = rank_fn(sorted_dst, targets, "left")
    elif count_fn is not None:
        ptr = count_fn(sorted_dst, targets)
    elif method == "sorted":
        from .set_count import rank_in_sorted
        ptr = rank_in_sorted(sorted_dst, targets, side="left",
                             unroll=unroll)
    else:
        ptr = count_less_than(sorted_dst, targets, block=block)
    if ptr_capacity is not None:
        ptr = pad_to(ptr, ptr_capacity, ptr[-1])
    return ptr


def build_pointer_array_serial(sorted_dst: jnp.ndarray, n_nodes: int
                               ) -> jnp.ndarray:
    """The conventional serial scan (baseline): bump a cursor per edge.

    Expressed as a sequential lax.scan to model the dependence chain the
    paper criticizes (each step depends on the previous edge's dst).
    """
    e = sorted_dst.shape[0]

    # hist[v] = #edges with dst == v, accumulated one edge at a time.
    def body(hist, d):
        hist = jax.lax.cond(
            d < n_nodes,
            # repro: allow-scatter-write — this IS the serial scatter
            # baseline the paper's SCR replaces; it exists to be measured
            # against, never dispatched by the engine.
            lambda h: h.at[d].add(1),
            lambda h: h,
            hist)
        return hist, None

    hist, _ = jax.lax.scan(body, jnp.zeros((n_nodes,), jnp.int32), sorted_dst)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(hist)]).astype(jnp.int32)


def data_reshaping(sorted_coo: COO, ptr_capacity: int | None = None,
                   count_fn=None, unroll: bool = False,
                   rank_fn=None) -> CSC:
    """Sorted COO → CSC (pointer array + index array = the sorted src column)."""
    ptr = build_pointer_array(sorted_coo.dst, sorted_coo.n_nodes,
                              ptr_capacity=ptr_capacity, count_fn=count_fn,
                              unroll=unroll, rank_fn=rank_fn)
    return CSC(ptr=ptr, idx=sorted_coo.src, n_edges=sorted_coo.n_edges,
               n_nodes=sorted_coo.n_nodes)


def graph_convert(coo: COO, chunk: int | None = None, count_fn=None,
                  chunk_sort_fn=None, ptr_capacity: int | None = None) -> CSC:
    """Full graph conversion = Ordering + Reshaping (paper Fig. 3).

    ``chunk=None`` resolves to ``ordering.DEFAULT_CHUNK`` — the one routed
    chunk-width default shared with ``EngineConfig.w_upe``."""
    from .ordering import edge_ordering
    sorted_coo = edge_ordering(coo, chunk=chunk, chunk_sort_fn=chunk_sort_fn)
    return data_reshaping(sorted_coo, ptr_capacity=ptr_capacity,
                          count_fn=count_fn)
