"""CSC-consumer aggregation kernel: blocked scatter-add via one-hot MXU matmul.

The GNN aggregation step (paper Fig. 2) consumes exactly the layout Ordering
produces: messages sorted by destination. A [V-block × E-block] one-hot of
(dst == v) matmul'd with the [E-block × D] message tile performs the
scatter-add on the MXU — the systolic array *is* the adder tree, so the
contended atomic adds of the GPU baseline disappear, mirroring the SCR story
at the aggregation layer.

Because dst is sorted, each edge block touches a narrow dst range; tiles
outside that range are skipped via a pl.when guard on the block's dst bounds
(the §Perf iterations tighten this further).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import pallas_call


def _agg_kernel(dst_ref, msg_ref, out_ref, *, v_block: int):
    i = pl.program_id(0)  # node block
    k = pl.program_id(2)  # edge block (minor)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    dst = dst_ref[...]  # [1, Eb] int32 (sorted)
    v_start = i * v_block
    lo = jnp.min(dst)
    hi = jnp.max(dst)
    overlap = (hi >= v_start) & (lo < v_start + v_block)

    @pl.when(overlap)
    def _accum():
        msg = msg_ref[...]  # [Eb, Db] f32
        rel = dst - v_start
        iota = jax.lax.broadcasted_iota(jnp.int32, (v_block, dst.shape[1]), 0)
        onehot = (rel == iota).astype(jnp.float32)  # [Vb, Eb]
        out_ref[...] += jax.lax.dot(onehot, msg,
                                    preferred_element_type=jnp.float32)


@partial(jax.jit, static_argnames=("n_nodes", "v_block", "d_block",
                                   "e_block"))
def segment_sum_sorted(dst: jnp.ndarray, messages: jnp.ndarray, n_nodes: int,
                       v_block: int = 256, d_block: int = 128,
                       e_block: int = 512) -> jnp.ndarray:
    """out[v, :] = sum over edges with dst==v of messages[e, :].

    dst [E] int32 *sorted ascending* (SENTINEL padding sorts to the end and
    lands outside [0, n_nodes) so it never accumulates). messages [E, D] f32.
    n_nodes must be a multiple of v_block, E of e_block, D of d_block.
    """
    e, d = messages.shape
    assert dst.shape[0] == e
    assert n_nodes % v_block == 0 and e % e_block == 0 and d % d_block == 0
    return pallas_call(
        partial(_agg_kernel, v_block=v_block),
        grid=(n_nodes // v_block, d // d_block, e // e_block),
        in_specs=[
            # dst as a [1, E] row: each block is one lane-major row
            pl.BlockSpec((1, e_block), lambda i, j, k: (0, k)),
            pl.BlockSpec((e_block, d_block), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((v_block, d_block), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_nodes, d), jnp.float32),
    )(dst.reshape(1, e), messages)
