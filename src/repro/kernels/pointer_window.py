"""Windowed SCR pointer build: the CSC pointer array of a sorted edge
stream, with no gather over the stream.

``ptr[v] = |{i : dst[i] < v}|`` for ``v = 0..n_nodes``. The stream is
sorted and the targets are consecutive, so the edges that can change the
count of target block ``[bT, (b+1)T)`` are exactly the window
``[ptr[bT], ptr[(b+1)T])`` — the paper's Reshaper consuming the sorted
stream (Fig. 9a). Summed over all blocks those windows hold every edge
once, so the compare work is O(E·T + N·C) instead of the O(N·E) of the
all-pairs count (``set_count.set_count_less``).

1. **Block bounds.** ``lo[b] = ptr[b·T]`` for ``b = 0..nb`` by the rank
   search (``core.set_count.rank_in_sorted``): nb + 1 queries, not N + 1.
2. **Ragged work list.** Block ``b`` takes the ``C``-edge chunks
   ``first[b]..last[b]`` that hold its window, at least one. The
   ``(block, chunk)`` tiles are laid out flat in a list of static length
   ``E/C + nb`` (a bound: ``last[b] <= first[b+1]``, so the chunk ranges
   overlap in at most one chunk per block). Dead tail steps repeat the
   last live tile's indices, so nothing is fetched or written again.
3. **Kernel.** Each grid step compares the block's ``T`` targets with one
   chunk (comparators + adder tree, as ``set_count._count_kernel``) and
   adds the counts into the block's lane-dense output row, which starts at
   ``first[b]·C``: edges before the window are all ``< v``, edges after
   it all ``>= v``, and the SENTINEL tail never counts, so no mask.

Layout: the targets are made in the kernel from the block index and an
iota; the edges come as ``(E/128, 128)`` rows read ``C/128`` at a time;
the output is ``(nb, 1, T)``. An ``(N+1, 1)`` column instead pads 128-fold
along lanes (gigabytes of temp at ogbn-products' size).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.set_count import rank_in_sorted

from .common import pad_pow2_1d, pallas_call

_SENTINEL = 0x7FFFFFFF
_LANES = 128
# Tiling measured on a TPU v5e at ogbn-products' size (2^27 edge slots,
# 2,449,030 targets): 128 x 4096 took 30.4 ms, 256 x 2048 35.5 ms,
# 256 x 4096 32.5 ms, 128 x 8192 33.9 ms, 128 x 2048 34.0 ms.
T_BLOCK = 128    # targets per block
E_BLOCK = 4096   # edges per chunk


def _window_kernel(block_ref, chunk_ref, live_ref, edge_ref, out_ref, *,
                   e_block: int):
    t = pl.program_id(0)
    b = block_ref[t]

    @pl.when(t < live_ref[0])
    def _count():
        t_block = out_ref.shape[-1]
        tgt = b * t_block + jax.lax.broadcasted_iota(
            jnp.int32, (t_block, _LANES), 0)
        acc = jnp.zeros((t_block, _LANES), jnp.int32)
        for r in range(edge_ref.shape[0]):  # [T, 128] comparators per row
            acc += (edge_ref[r:r + 1, :] < tgt).astype(jnp.int32)
        count = jnp.sum(acc.T, axis=0, keepdims=True)  # adder tree, [1, T]
        # the block's first tile starts its row at first[b]·C
        first = (t == 0) | (block_ref[jnp.maximum(t - 1, 0)] != b)

        @pl.when(first)
        def _init():
            out_ref[...] = jnp.full(out_ref.shape, chunk_ref[t] * e_block,
                                    jnp.int32)

        out_ref[...] += count


def _tile_tables(ends, shift, n_steps: int):
    """(block, chunk) of each of ``n_steps`` tiles, and the live count,
    from the inclusive tile-count prefix ``ends`` of the blocks and each
    block's ``shift = first chunk - first tile``. Gather-free over single
    elements: tile ``t`` lies in block ``#{b : ends[b] <= t}``, and as
    each tile advances the block by at most one, the 128 tiles of a group
    lie in 128 consecutive blocks from the one of its first tile, so in
    two aligned 128-block rows of the tables. Tiles past the last live
    one repeat it."""
    nb = ends.shape[0]
    n_groups = pl.cdiv(n_steps, _LANES)
    t = jnp.arange(n_groups * _LANES, dtype=jnp.int32).reshape(n_groups,
                                                               _LANES)
    b0 = jnp.sum(ends[None, :] <= t[:, :1], axis=1, dtype=jnp.int32)
    rows = pl.cdiv(nb, _LANES) + 1
    pad = rows * _LANES - nb
    table = jnp.stack([
        jnp.concatenate([ends, jnp.full((pad,), _SENTINEL, jnp.int32)]),
        jnp.concatenate([shift, jnp.zeros((pad,), jnp.int32)]),
    ]).reshape(2, rows, _LANES)
    r0 = jnp.minimum(b0 // _LANES, rows - 2)
    win = jnp.concatenate([jnp.take(table, r0, axis=1),
                           jnp.take(table, r0 + 1, axis=1)],
                          axis=2)                      # [2, G, 256]
    k = jnp.sum(win[0, :, None, :] <= t[:, :, None], axis=2,
                dtype=jnp.int32)                       # [G, 128]
    pick = k[:, :, None] == jnp.arange(2 * _LANES, dtype=jnp.int32)
    shift_t = jnp.sum(jnp.where(pick, win[1, :, None, :], 0), axis=2,
                      dtype=jnp.int32)
    live = ends[-1]
    t = t.reshape(-1)[:n_steps]
    block = (r0[:, None] * _LANES + k).reshape(-1)[:n_steps]
    chunk = t + shift_t.reshape(-1)[:n_steps]
    dead = t >= live
    return (jnp.where(dead, nb - 1, block),
            jnp.where(dead, shift[-1] + live - 1, chunk), live)


def work_list(sorted_dst: jnp.ndarray, n_nodes: int, t_block: int = T_BLOCK,
              e_block: int = E_BLOCK):
    """(tile→block, tile→chunk, live tile count) of the windowed build
    over ``sorted_dst`` [E] (E a multiple of ``e_block``)."""
    n_chunks = sorted_dst.shape[0] // e_block
    nb = pl.cdiv(n_nodes + 1, t_block)
    lo = rank_in_sorted(sorted_dst,
                        jnp.arange(nb + 1, dtype=jnp.int32) * t_block)
    first = jnp.minimum(lo[:-1] // e_block, n_chunks - 1)
    last = jnp.maximum(first, (lo[1:] - 1) // e_block)
    ends = jnp.cumsum(last - first + 1, dtype=jnp.int32)
    shift = first - (ends - (last - first + 1))
    return _tile_tables(ends, shift, n_chunks + nb)


@partial(jax.jit, static_argnames=("n_nodes", "t_block", "e_block"))
def windowed_pointer_array(sorted_dst: jnp.ndarray, n_nodes: int,
                           t_block: int = T_BLOCK,
                           e_block: int = E_BLOCK) -> jnp.ndarray:
    """ptr[v] = |{i : sorted_dst[i] < v}| for v = 0..n_nodes, int32.

    ``sorted_dst`` [E] int32 ascending with valid VIDs in ``[0, n_nodes)``
    and a SENTINEL tail. Bit-identical to ``searchsorted(sorted_dst,
    arange(n_nodes + 1), 'left')``. ``e_block`` is a multiple of 1024 and
    ``t_block`` of 128 (Mosaic's (8, 128) tile); E is padded with
    SENTINEL to a multiple of ``e_block``.
    """
    dst = pad_pow2_1d(sorted_dst, e_block, _SENTINEL)
    nb = pl.cdiv(n_nodes + 1, t_block)
    block, chunk, live = work_list(dst, n_nodes, t_block, e_block)
    rows = e_block // _LANES
    out = pallas_call(
        partial(_window_kernel, e_block=e_block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(block.shape[0],),
            in_specs=[pl.BlockSpec((rows, _LANES),
                                   lambda t, bk, ck, lv: (ck[t], 0))],
            out_specs=pl.BlockSpec((None, 1, t_block),
                                   lambda t, bk, ck, lv: (bk[t], 0, 0))),
        out_shape=jax.ShapeDtypeStruct((nb, 1, t_block), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(block, chunk, live.reshape(1), dst.reshape(-1, _LANES))
    return out.reshape(-1)[:n_nodes + 1]


def live_tile_share(sorted_dst: np.ndarray, n_nodes: int,
                    t_block: int = T_BLOCK,
                    e_block: int = E_BLOCK) -> tuple[int, int]:
    """(live tiles, grid steps) of the windowed build over a host copy of
    a sorted dst column: its efficiency, live over steps. NumPy only, for
    reports; never on a timed path."""
    n_chunks = -(-len(sorted_dst) // e_block)
    nb =-(-(n_nodes + 1) // t_block)
    lo = np.searchsorted(sorted_dst, np.arange(nb + 1) * t_block,
                         side="left")
    first = np.minimum(lo[:-1] // e_block, n_chunks - 1)
    last = np.maximum(first, (lo[1:] - 1) // e_block)
    return int(np.sum(last - first + 1)), n_chunks + nb
