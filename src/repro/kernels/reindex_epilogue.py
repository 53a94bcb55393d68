"""Fused SCR epilogue kernels: pointer build + reindex rename in VMEM.

The convert spine's tail phases — CSC pointer construction
(``reshaping.build_pointer_array``) and subgraph VID rename
(``reindexing.ReindexMap.lookup``) — are both batched rank searches over
the sorted stream the Ordering just produced. These kernels run that
search *inside* a Pallas grid over query tiles while the sorted array
stays VMEM-resident (BlockSpec pins the full stream to every grid step),
so the epilogue executes in the sort's shadow: no host round-trip between
rounds, no separately-dispatched jitted phases, zero while ops (the log₂ n
search rounds are statically unrolled in-kernel — the ``fused`` half of
``EngineConfig.reindex_strategy``, priced by
``costmodel.resolve_reindex_strategy``).

``rank_search_tiles`` is the pointer/first-occurrence engine (rank only);
``reindex_rename_tiles`` fuses rank + hit-test + slot-table gather — the
whole ``lookup`` — into one kernel. Both mirror ``set_count.py``'s SCR
tiling: queries are the target blocks, the sorted stream is the element
set, and each search round is one comparator per query against a gathered
pivot.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import pallas_call, pad_pow2_1d

_SENTINEL = 0x7FFFFFFF


def _unrolled_rank(arr, q, n: int, side: str):
    """The statically-unrolled batched binary search (identical rounds to
    ``core.set_count.rank_in_sorted(unroll=True)``, including the
    ``active`` freeze guard — results are bit-identical)."""
    steps = max(1, int(n).bit_length())
    lo = jnp.zeros(q.shape, jnp.int32)
    hi = jnp.full(q.shape, n, jnp.int32)
    for _ in range(steps):
        active = lo < hi
        mid = (lo + hi) >> 1
        pivot = jnp.take(arr, jnp.clip(mid, 0, n - 1), mode="clip")
        go_right = (pivot < q) if side == "left" else (pivot <= q)
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
    return lo


def _rank_kernel(sorted_ref, q_ref, out_ref, *, side: str, n: int):
    out_ref[...] = _unrolled_rank(sorted_ref[...], q_ref[...], n, side)


@partial(jax.jit, static_argnames=("side", "q_block"))
def rank_search_tiles(sorted_arr: jnp.ndarray, queries: jnp.ndarray,
                      side: str = "left", q_block: int = 256) -> jnp.ndarray:
    """rank[t] = searchsorted(sorted_arr, queries[t], side) per query tile,
    the sorted stream VMEM-resident across the whole grid.

    sorted_arr [N] int32 ascending (SENTINEL tail fine — the tail is
    rightmost, so a left rank lands past the valid run only when the query
    outranks every valid element). queries [Q], Q % q_block == 0.
    """
    n = sorted_arr.shape[0]
    q = queries.shape[0]
    assert q % q_block == 0, (q, q_block)
    return pallas_call(
        partial(_rank_kernel, side=side, n=n),
        grid=(q // q_block,),
        in_specs=[
            pl.BlockSpec((n,), lambda i: (0,)),  # full stream, every step
            pl.BlockSpec((q_block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((q_block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((q,), jnp.int32),
    )(sorted_arr, queries)


def _rename_kernel(sorted_ref, table_ref, q_ref, out_ref, *, n: int):
    arr = sorted_ref[...]
    q = q_ref[...]
    rank = _unrolled_rank(arr, q, n, "left")
    rank_c = jnp.clip(rank, 0, n - 1)
    hit = jnp.take(arr, rank_c, mode="clip") == q
    new = jnp.take(table_ref[...], rank_c, mode="clip")
    out_ref[...] = jnp.where(hit & (q != _SENTINEL), new, _SENTINEL)


@partial(jax.jit, static_argnames=("q_block",))
def reindex_rename_tiles(sorted_vids: jnp.ndarray, slot_to_new: jnp.ndarray,
                         queries: jnp.ndarray,
                         q_block: int = 256) -> jnp.ndarray:
    """The whole ``ReindexMap.lookup`` in one kernel: rank + run-head hit
    test + slot-table gather per query tile, stream and table resident.

    sorted_vids/slot_to_new [N] (the shared-sort stream + its new-VID
    table), queries [Q] original VIDs, Q % q_block == 0. Misses and
    SENTINEL queries return SENTINEL.
    """
    n = sorted_vids.shape[0]
    q = queries.shape[0]
    assert q % q_block == 0, (q, q_block)
    assert slot_to_new.shape[0] == n
    return pallas_call(
        partial(_rename_kernel, n=n),
        grid=(q // q_block,),
        in_specs=[
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((q_block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((q_block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((q,), jnp.int32),
    )(sorted_vids, slot_to_new, queries)


def pallas_rank_fn(sorted_arr, queries, side="left"):
    """Adapter for ``build_pointer_array(rank_fn=...)`` /
    ``build_reindex_map(rank_fn=...)``: pads the query tile and slices."""
    t = queries.shape[0]
    q_block = min(256, t)
    qs = pad_pow2_1d(queries, q_block, _SENTINEL)
    return rank_search_tiles(sorted_arr, qs, side=side, q_block=q_block)[:t]


def pallas_rename_fn(sorted_vids, slot_to_new, queries):
    """Adapter for ``ReindexMap.lookup`` (``rename_fn=...``)."""
    t = queries.shape[0]
    q_block = min(256, t)
    qs = pad_pow2_1d(queries, q_block, _SENTINEL)
    return reindex_rename_tiles(sorted_vids, slot_to_new, qs,
                                q_block=q_block)[:t]
