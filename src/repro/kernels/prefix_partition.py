"""UPE set-partition kernel (paper Fig. 12): prefix-sum + relocation.

One kernel invocation partitions a VMEM-resident block: the condition array
feeds the log-depth adder network (displacement array), the relocation
router is a gather by the inverse permutation — a log-depth binary search
over the two monotone count columns plus one ``jnp.take`` (O(N·log N),
replacing the O(N²) one-hot MXU matmul). Grid iterates independent blocks
(the multi-UPE configuration); each grid step is one UPE.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.set_partition import gather_sources_from_counts

from .common import pallas_call, prefix_sum_tree


def _partition_kernel(cond_ref, val_ref, out_ref, nsel_ref):
    cond = cond_ref[...].astype(jnp.int32)
    vals = val_ref[...]
    incl_sel = prefix_sum_tree(cond)  # inclusive scan — the adder network
    n_sel = incl_sel[-1]
    incl = jnp.stack([incl_sel, prefix_sum_tree(1 - cond)], axis=1)  # [N, 2]
    base = jnp.stack([jnp.int32(0), n_sel])
    src = gather_sources_from_counts(incl, base)  # inverse-permutation router
    out_ref[...] = jnp.take(vals, src, mode="clip")
    nsel_ref[...] = jnp.full(nsel_ref.shape, n_sel, jnp.int32)


@partial(jax.jit, static_argnames=("block",))
def prefix_partition(values: jnp.ndarray, cond: jnp.ndarray,
                     block: int = 1024) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Blockwise stable partition. values [N] int32, cond [N] bool.

    N must be a multiple of ``block``; each block partitions independently
    (one UPE per block), returning per-block selected counts [N/block] —
    the UPE controller (jnp level) combines blocks.
    """
    n = values.shape[0]
    assert n % block == 0, (n, block)
    grid = n // block
    out, nsel = pallas_call(
        _partition_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            # one (1, 128) lane row per block: a legal Mosaic block that
            # carries the block's count broadcast across the lanes
            pl.BlockSpec((1, 1, 128), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((grid, 1, 128), jnp.int32),
        ],
    )(cond, values)
    return out, nsel[:, 0, 0]
