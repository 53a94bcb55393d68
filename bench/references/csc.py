"""Plain reference of COO -> CSC: one lexicographic sort of the (dst, src)
pairs and a binary search for each column's start.

The CSC it defines: ``idx`` lists the sources of the edges sorted by
(dst, src), then SENTINEL up to the buffer's capacity; ``ptr[v]`` is the
number of edges whose dst is below ``v``, for ``v`` in ``0 .. n_nodes``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("n_nodes",))
def plain_csc(dst, src, *, n_nodes: int):
    """(ptr [n_nodes + 1], idx [capacity]) of the padded edge arrays; the
    SENTINEL padding sorts last."""
    sd, ss = jax.lax.sort((dst, src), num_keys=2)
    ptr = jnp.searchsorted(sd, jnp.arange(n_nodes + 1, dtype=dst.dtype),
                           side="left")
    return ptr.astype(jnp.int32), ss


@partial(jax.jit, static_argnames=("n_nodes",))
def dst_only_csc(dst, src, *, n_nodes: int):
    """The control: the pairs sorted by dst alone (stable, so sources keep
    their input order inside a column). It breaks the CSC's guarantee that
    each column's sources are ascending."""
    sd, ss = jax.lax.sort((dst, src), num_keys=1, is_stable=True)
    ptr = jnp.searchsorted(sd, jnp.arange(n_nodes + 1, dtype=dst.dtype),
                           side="left")
    return ptr.astype(jnp.int32), ss


@jax.jit
def mismatches(ptr, idx, n_edges, ref_ptr, ref_idx, ref_n_edges):
    """How many entries of a CSC differ from the reference: pointer
    entries, index entries (the SENTINEL tail included) and the edge
    count. Zero means the whole CSC is equal."""
    n_ptr = ref_ptr.shape[0]
    bad_ptr = jnp.sum(ptr[:n_ptr] != ref_ptr) + jnp.sum(ptr[n_ptr:]
                                                         != ref_ptr[-1])
    bad_idx = jnp.sum(idx != ref_idx)
    return (bad_ptr + bad_idx
            + (n_edges != ref_n_edges).astype(jnp.int32)).astype(jnp.int32)
