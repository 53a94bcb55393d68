"""UPE chunk radix sort kernel (paper §V-A, Fig. 15 "splitting" stage).

Each grid step radix-sorts one VMEM-resident chunk of (key, value) pairs —
one UPE. Every digit pass is a set-partition: per-bucket inclusive prefix
sums (the adder network, B cooperating columns) feed the gather-based
relocation router — a log-depth binary search finds the source of every
output slot and the move is a gather (``jnp.take``), O(N·log N) per pass
versus the O(N²) one-hot MXU matmuls this kernel used to issue. Chunks are
merged outside the kernel by the parallel rank-merge (core/ordering.py,
kernels/merge.py) — the "merging" stage.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.set_partition import (digit_relocation_sources,
                                      rank_gather_sources)

from .common import pallas_call, prefix_sum_tree


def _make_kernel(n_passes: int, radix_bits: int, keys_only: bool = False):
    n_buckets = 1 << radix_bits

    def body(keys, vals):
        for p in range(n_passes):  # static LSD passes
            shift = p * radix_bits
            digit = (keys >> shift) & (n_buckets - 1)
            # the shared router, with the Hillis–Steele adder network as
            # the in-kernel prefix sum (static shifts+adds only)
            src, _ = digit_relocation_sources(digit, n_buckets,
                                              prefix_sum_fn=prefix_sum_tree)
            keys = jnp.take(keys, src, mode="clip")
            if vals is not None:
                vals = jnp.take(vals, src, mode="clip")
        return keys, vals

    if keys_only:
        def kernel(key_ref, out_key_ref):
            out_key_ref[...], _ = body(key_ref[...], None)
    else:
        def kernel(key_ref, val_ref, out_key_ref, out_val_ref):
            out_key_ref[...], out_val_ref[...] = body(key_ref[...],
                                                      val_ref[...])

    return kernel


@partial(jax.jit, static_argnames=("chunk", "key_bits", "radix_bits"))
def radix_sort_chunks(keys: jnp.ndarray, values: jnp.ndarray, chunk: int,
                      key_bits: int, radix_bits: int = 4
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sort each ``chunk``-sized block of (keys, values) independently.

    Stable LSD radix sort per chunk. keys/values [N] int32, N % chunk == 0.
    """
    n = keys.shape[0]
    assert n % chunk == 0, (n, chunk)
    n_passes = max(1, -(-key_bits // radix_bits))
    grid = n // chunk
    out_k, out_v = pallas_call(
        _make_kernel(n_passes, radix_bits),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((chunk,), lambda i: (i,)),
            pl.BlockSpec((chunk,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((chunk,), lambda i: (i,)),
            pl.BlockSpec((chunk,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
        ],
    )(keys, values)
    return out_k, out_v


@partial(jax.jit, static_argnames=("chunk", "key_bits", "radix_bits"))
def radix_sort_chunks_keys(keys: jnp.ndarray, chunk: int, key_bits: int,
                           radix_bits: int = 4) -> jnp.ndarray:
    """Keys-only ``radix_sort_chunks``: one VMEM-resident array per UPE.

    The packed Ordering path sorts a key that carries its own data, so
    skipping the value stream halves the kernel's VMEM footprint and the
    bytes each digit pass gathers.
    """
    n = keys.shape[0]
    assert n % chunk == 0, (n, chunk)
    n_passes = max(1, -(-key_bits // radix_bits))
    grid = n // chunk
    return pallas_call(
        _make_kernel(n_passes, radix_bits, keys_only=True),
        grid=(grid,),
        in_specs=[pl.BlockSpec((chunk,), lambda i: (i,))],
        out_specs=pl.BlockSpec((chunk,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
    )(keys)


# ---------------------------------------------------------------------------
# The global_radix digit pass: tiled histogram/partition + rank-gather.
# One LSD digit pass over the WHOLE edge array — the merge-free Ordering
# strategy — split exactly like the two-level jnp formulation
# (core.set_partition.tiled_digit_sources):
#   kernel 1 streams input tiles HBM→VMEM (pallas_call's pipelined grid =
#     the double buffer), partitions each tile by the digit in VMEM and
#     emits its [B] histogram + in-tile bucket bases;
#   a tiny jnp stage scans the [T, B] tables into global/over-tile bases;
#   kernel 2 tiles the OUTPUT axis: each grid step computes one tile of
#     global source indices by pure rank arithmetic over the VMEM-resident
#     tables (log₂ T search rounds, no full-size state);
#   relocation is one jnp.take by the composed permutation — a gather, so
#   the digit pass stays scatter-free end to end.
# ---------------------------------------------------------------------------


def _make_partition_hist_kernel(shift: int, radix_bits: int,
                                keys_only: bool = False):
    n_buckets = 1 << radix_bits

    def body(keys, vals):
        tile = keys.shape[0]
        digit = (keys >> shift) & (n_buckets - 1)
        src, base = digit_relocation_sources(digit, n_buckets,
                                             prefix_sum_fn=prefix_sum_tree)
        hist = jnp.diff(jnp.concatenate(
            [base, jnp.full((1,), tile, jnp.int32)]))
        pk = jnp.take(keys, src, mode="clip")
        pv = None if vals is None else jnp.take(vals, src, mode="clip")
        return pk, pv, base.reshape(1, 1, -1), hist.reshape(1, 1, -1)

    if keys_only:
        def kernel(key_ref, out_key_ref, lbase_ref, hist_ref):
            pk, _, base, hist = body(key_ref[...], None)
            out_key_ref[...] = pk
            lbase_ref[...] = base
            hist_ref[...] = hist

        return kernel

    def kernel(key_ref, val_ref, out_key_ref, out_val_ref, lbase_ref,
               hist_ref):
        pk, pv, base, hist = body(key_ref[...], val_ref[...])
        out_key_ref[...] = pk
        out_val_ref[...] = pv
        lbase_ref[...] = base
        hist_ref[...] = hist

    return kernel


def _make_rank_gather_kernel(tile: int):
    def kernel(gbase_ref, incl_ref, excl_ref, lbase_ref, out_ref):
        j = pl.program_id(0) * tile + jnp.arange(tile, dtype=jnp.int32)
        out_ref[...] = rank_gather_sources(
            gbase_ref[...], incl_ref[...], excl_ref[...], lbase_ref[...],
            tile, j=j)

    return kernel


@partial(jax.jit, static_argnames=("shift", "tile", "radix_bits"))
def global_digit_pass(keys: jnp.ndarray, values: jnp.ndarray | None,
                      shift: int, tile: int, radix_bits: int = 4
                      ) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    """One tiled global LSD digit pass: stable-partition the WHOLE array by
    ``(key >> shift) & (2^radix_bits - 1)``. keys/values [N] int32,
    N % tile == 0; ``values=None`` relocates the keys alone."""
    n = keys.shape[0]
    assert n % tile == 0, (n, tile)
    n_buckets = 1 << radix_bits
    grid = n // tile
    # each tile's [B] table row is a (1, B) block of a [T, 1, B] array —
    # its last two dims are the array's own, as Mosaic's block rule asks
    row_spec = pl.BlockSpec((1, 1, n_buckets), lambda i: (i, 0, 0))
    tile_spec = pl.BlockSpec((tile,), lambda i: (i,))
    tables = [jax.ShapeDtypeStruct((grid, 1, n_buckets), jnp.int32)] * 2
    if values is None:
        pk, lbase, hist = pallas_call(
            _make_partition_hist_kernel(shift, radix_bits, keys_only=True),
            grid=(grid,),
            in_specs=[tile_spec],
            out_specs=[tile_spec, row_spec, row_spec],
            out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32)] + tables,
        )(keys)
        pv = None
    else:
        pk, pv, lbase, hist = pallas_call(
            _make_partition_hist_kernel(shift, radix_bits),
            grid=(grid,),
            in_specs=[tile_spec, tile_spec],
            out_specs=[tile_spec, tile_spec, row_spec, row_spec],
            out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32)] * 2 + tables,
        )(keys, values)
    # tiny [T, B] table math between the kernels (host of the adder tree)
    lbase = lbase.reshape(grid, n_buckets)
    hist = hist.reshape(grid, n_buckets)
    incl_t = jnp.cumsum(hist, axis=0)
    excl_t = incl_t - hist
    counts = incl_t[-1]
    gbase = jnp.cumsum(counts) - counts
    src = pallas_call(
        _make_rank_gather_kernel(tile),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((n_buckets,), lambda i: (0,)),
            pl.BlockSpec((grid, n_buckets), lambda i: (0, 0)),
            pl.BlockSpec((grid, n_buckets), lambda i: (0, 0)),
            pl.BlockSpec((grid, n_buckets), lambda i: (0, 0)),
        ],
        out_specs=tile_spec,
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
    )(gbase.astype(jnp.int32), incl_t, excl_t, lbase)
    pk = jnp.take(pk, src, mode="clip")
    if pv is not None:
        pv = jnp.take(pv, src, mode="clip")
    return pk, pv


def make_pallas_digit_pass_fn(radix_bits: int = 4, tile: int = None):
    """digit_pass_fn for ``core.ordering.global_radix_sort_by_key`` /
    ``stable_sort_by_key(strategy="global_radix")`` with the digit width
    and histogram tile routed from ``EngineConfig`` (radix_bits, w_upe).
    Honors the keys-only contract: ``vals=None`` skips the value stream."""
    from repro.core.ordering import DEFAULT_CHUNK

    def digit_pass_fn(keys, vals, shift):
        t = min(DEFAULT_CHUNK if tile is None else tile, keys.shape[0])
        return global_digit_pass(keys, vals, shift, tile=t,
                                 radix_bits=radix_bits)

    return digit_pass_fn


def make_pallas_chunk_sort_fn(radix_bits: int = 4):
    """chunk_sort_fn for ``core.ordering.stable_sort_by_key`` with the digit
    width routed from ``EngineConfig.radix_bits`` (one knob, both paths).
    Honors the keys-only contract: ``vals=None`` dispatches the keys-only
    kernel and returns ``(keys, None)``."""

    def chunk_sort_fn(keys, vals, chunk, key_bits):
        if vals is None:
            return radix_sort_chunks_keys(keys, chunk=chunk,
                                          key_bits=key_bits,
                                          radix_bits=radix_bits), None
        return radix_sort_chunks(keys, vals, chunk=chunk, key_bits=key_bits,
                                 radix_bits=radix_bits)

    return chunk_sort_fn


# Default-width adapter (radix_bits=4), kept for existing call sites.
pallas_chunk_sort_fn = make_pallas_chunk_sort_fn()
