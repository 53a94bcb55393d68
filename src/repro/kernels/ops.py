"""Jit'd public wrappers for the AutoGNN Pallas kernels.

They pad to block multiples, handle sentinels, and dispatch to the kernels:
``pallas_count_fn`` is the ``count_fn`` of ``pipeline.convert``, and
``segment_sum_padded`` the aggregation of ``models.gnn`` with
``use_pallas_agg``.
"""
from __future__ import annotations

import jax.numpy as jnp

from .set_count import filter_tree_lookup, pallas_count_fn, set_count_less
from .segment_agg import segment_sum_sorted
from .common import pad_pow2_1d

__all__ = [
    "set_count_less", "filter_tree_lookup", "pallas_count_fn",
    "segment_sum_sorted", "segment_sum_padded",
]

_I32_MAX = 0x7FFFFFFF


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
