"""Jit'd public wrappers for the AutoGNN Pallas kernels.

These are what core/ and models/ call when ``EngineConfig.use_pallas`` is on:
they pad to block multiples, handle sentinels, and dispatch to the kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .merge import fused_merge_rounds, make_pallas_merge_fn, pallas_merge_fn
from .prefix_partition import prefix_partition
from .radix_sort import (global_digit_pass, make_pallas_chunk_sort_fn,
                         make_pallas_digit_pass_fn, pallas_chunk_sort_fn,
                         radix_sort_chunks, radix_sort_chunks_keys)
from .reindex_epilogue import (pallas_rank_fn, pallas_rename_fn,
                               rank_search_tiles, reindex_rename_tiles)
from .set_count import filter_tree_lookup, pallas_count_fn, set_count_less
from .segment_agg import segment_sum_sorted
from .common import pad_pow2_1d

__all__ = [
    "prefix_partition", "radix_sort_chunks", "radix_sort_chunks_keys",
    "pallas_chunk_sort_fn",
    "make_pallas_chunk_sort_fn", "fused_merge_rounds", "pallas_merge_fn",
    "make_pallas_merge_fn", "global_digit_pass", "make_pallas_digit_pass_fn",
    "set_count_less", "filter_tree_lookup", "pallas_count_fn",
    "rank_search_tiles", "reindex_rename_tiles", "pallas_rank_fn",
    "pallas_rename_fn",
    "segment_sum_sorted", "segment_sum_padded", "MOSAIC_REFUSALS",
    "refused_on_tpu",
]

_I32_MAX = 0x7FFFFFFF

# The kernels Mosaic refuses to lower for a TPU, each with the compiler's
# own words. Every one holds an in-kernel gather or a dynamic slice of a
# vector, which the Pallas TPU lowering does not implement.
# tests/test_tpu_compile.py pins each refusal as a strict xfail, so a
# kernel that starts to compile must leave this table.
MOSAIC_REFUSALS = {
    "radix_sort_chunks": "Unimplemented primitive in Pallas TPU lowering "
                         "for KernelType.TC: dynamic_slice",
    "radix_sort_chunks_keys": "Unimplemented primitive in Pallas TPU "
                              "lowering for KernelType.TC: dynamic_slice",
    "global_digit_pass": "Unimplemented primitive in Pallas TPU lowering "
                         "for KernelType.TC: dynamic_slice",
    "prefix_partition": "Unimplemented primitive in Pallas TPU lowering "
                        "for KernelType.TC: dynamic_slice",
    "fused_merge_rounds": "Unsupported gather",
    "rank_search_tiles": "Only 2D gather is supported",
    "reindex_rename_tiles": "Only 2D gather is supported",
}


def refused_on_tpu(name: str, fn):
    """``fn`` where the default backend is not a TPU; on a TPU, a stand-in
    that raises the moment a route asks for kernel ``name`` — neither
    the interpreter nor the jnp reference stands in for it there."""
    if name not in MOSAIC_REFUSALS or jax.default_backend() != "tpu":
        return fn

    def refuse(*args, **kwargs):
        raise NotImplementedError(
            f"Pallas kernel {name} does not compile for TPU (Mosaic: "
            f"{MOSAIC_REFUSALS[name]}); run this route with "
            f"use_pallas=False")

    return refuse


def segment_sum_padded(dst: jnp.ndarray, messages: jnp.ndarray, n_nodes: int,
                       v_block: int = 256, d_block: int = 128,
                       e_block: int = 512) -> jnp.ndarray:
    """segment_sum_sorted with automatic padding of every axis."""
    e, d = messages.shape
    ep = (-e) % e_block
    dp = (-d) % d_block
    np_ = (-n_nodes) % v_block
    dst_p = pad_pow2_1d(dst, e_block, _I32_MAX)
    msg_p = jnp.pad(messages, ((0, ep), (0, dp)))
    out = segment_sum_sorted(dst_p, msg_p, n_nodes + np_, v_block=v_block,
                             d_block=d_block, e_block=e_block)
    return out[:n_nodes, :d]
