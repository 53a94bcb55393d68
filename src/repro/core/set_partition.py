"""Set-partitioning — the UPE primitive (paper §IV-A, Fig. 8, Fig. 12).

Partition an array into (elements satisfying a condition, the rest), stably,
using an exclusive prefix sum of the condition as each element's write index.
On the FPGA this is the prefix-sum adder network + relocation router; on TPU
the prefix sum is a log-depth ``cumsum`` and the relocation is a **gather by
the inverse permutation** (``gather_sources_from_counts``): the inclusive
per-bucket prefix-sum columns are monotone, so the source of output slot j
(bucket b, local rank r) is the first i with ``count[i, b] == r + 1`` — a
log-depth binary search per slot. The relocation then lowers to ``jnp.take``
(a gather), which shards under GSPMD and compiles to Mosaic cleanly, unlike
the ``.at[dest].set`` scatter or the O(N²) one-hot MXU matmul it replaces.

These jnp implementations are the *algorithmic* contribution in portable
form. The Ordering itself sorts with the native comparison sort
(``core/ordering.py``); these primitives serve the delta splice, the
reindex compaction and the MoE / DLRM dispatch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def prefix_sum(x: jnp.ndarray, axis: int = 0,
               exclusive: bool = False) -> jnp.ndarray:
    """Log-depth prefix sum — the UPE adder network (paper Fig. 12b).

    Uses lax.associative_scan (explicit log-depth slices+adds) rather than
    jnp.cumsum: XLA lowers cumsum to a reduce-window whose SPMD partitioning
    degenerates to O(N·window) work on sharded axes (observed as a 1000×
    per-device FLOP blowup in the MoE dispatch dry-run).
    """
    incl = jax.lax.associative_scan(jnp.add, x, axis=axis)
    return incl - x if exclusive else incl


def displacement(cond: jnp.ndarray) -> jnp.ndarray:
    """Exclusive prefix sum of a boolean condition array.

    displacement[i] = number of selected elements strictly left of i — the
    paper's "displacement array" (Fig. 12b).
    """
    return prefix_sum(cond.astype(jnp.int32), exclusive=True)


def partition_indices(cond: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Destination index of every element under a stable two-way partition.

    Selected elements go left (compacted in order); unselected go right
    (also in order). Returns (dest_index, n_selected).
    """
    c = cond.astype(jnp.int32)
    left = prefix_sum(c, exclusive=True)  # rank among selected
    right = prefix_sum(1 - c, exclusive=True)  # rank among unselected
    n_sel = jnp.sum(c)
    dest = jnp.where(cond, left, n_sel + right)
    return dest.astype(jnp.int32), n_sel.astype(jnp.int32)


def gather_sources_from_counts(incl_counts: jnp.ndarray, base: jnp.ndarray
                               ) -> jnp.ndarray:
    """Inverse-permutation gather router: source index of every output slot.

    ``incl_counts`` [N, B]: inclusive per-bucket prefix sums of the bucket
    one-hot (column b is monotone 0 → counts[b]). ``base`` [B]: exclusive
    bucket start offsets. Output slot j belongs to the last bucket whose
    base is ≤ j (empty buckets own no slots) at local rank r = j - base[b];
    its source is the first i with ``incl_counts[i, b] == r + 1`` — a
    log₂(N)-round binary search per slot, every slot independent (in the
    style of ``set_count.rank_in_sorted``). O(N·log N + N·B) total, versus
    O(N²) for the one-hot MXU router; the caller relocates with
    ``jnp.take(values, sources)`` instead of a scatter.
    """
    n, _ = incl_counts.shape
    nb = incl_counts.shape[1]
    j = jnp.arange(n, dtype=jnp.int32)
    b = jnp.sum((base[None, :] <= j[:, None]).astype(jnp.int32), axis=1) - 1
    r = j - jnp.take(base, b, mode="clip")
    target = r + 1
    flat = incl_counts.reshape(-1)
    lo = jnp.zeros((n,), jnp.int32)
    hi = jnp.full((n,), n, jnp.int32)
    steps = max(1, int(n).bit_length())
    for _ in range(steps):  # static log-depth rounds — Pallas-friendly
        mid = (lo + hi) >> 1
        pivot = jnp.take(flat, jnp.clip(mid, 0, n - 1) * nb + b, mode="clip")
        go_right = pivot < target
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
    return lo.astype(jnp.int32)


def set_partition(values: jnp.ndarray, cond: jnp.ndarray
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Stable partition of ``values`` by ``cond``; returns (partitioned, n_selected).

    Multi-column variant: ``values`` may be [N] or [N, k]; rows move together
    (the UPE moves 64-bit (dst,src) pairs as one element). Relocation is the
    gather router — no scatter in the lowered program.
    """
    c = cond.astype(jnp.int32)
    incl = jnp.stack([prefix_sum(c), prefix_sum(1 - c)], axis=1)  # [N, 2]
    n_sel = incl[-1, 0]
    base = jnp.stack([jnp.int32(0), n_sel])
    src = gather_sources_from_counts(incl, base)
    return jnp.take(values, src, axis=0, mode="clip"), n_sel.astype(jnp.int32)
