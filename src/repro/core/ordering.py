"""Edge Ordering (paper §II-B, §V-A, Fig. 15, Algorithm 1).

Sort the COO edge array by (dst, src). The paper concatenates each pair into a
64-bit key and LSD-radix-sorts it on UPEs; here the platform's native
comparison sort (one ``lax.sort``) is the only global sort. JAX disables
int64 by default, so two equivalent 32-bit key schemes are provided,
selected by ``mode``:

* ``"packed"`` — the paper's concatenated key, shrunk to fit int32: when
  ``2 · bits(n_nodes) ≤ 31`` (all graphs ≤ 32767 nodes — always true for the
  subgraph re-conversion inside ``sample_subgraph``), pack
  ``(dst << src_bits) | src`` into ONE key, sort it keys-only, then unpack
  ``(dst, src)``.
* ``"two_pass"`` — for wide VID spaces: ONE two-key comparison sort over
  the (dst, src) columns (``xla_lex_sort``). The pair is the whole record,
  so equal keys are identical records and the unstable sort is exact. With
  a caller ``sort_fn`` (the mesh-sharded sorter, ``engine/shard.py``) it
  is the LSD scheme: a stable sort by src, then a stable sort by dst.
* ``"auto"`` (default) — ``"packed"`` whenever the VID space allows it.

Both modes produce bit-identical output: the (dst, src) lexicographic
order, SENTINEL padding at the tail.

Sentinel handling: padded entries carry SENTINEL; keys are clipped to
``n_nodes`` (one past any valid VID) before sorting, so padding sorts last
and the packed key stays inside int32, and restored afterwards.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .graph import COO, SENTINEL
from .set_count import rank_in_sorted


def _bits_for(n: int) -> int:
    return max(1, int(n).bit_length())


def supports_packed_keys(n_nodes: int) -> bool:
    """True when (dst, src) pairs fit one non-negative int32 packed key."""
    return 2 * _bits_for(n_nodes) <= 31


def merge_sorted(a_keys, a_vals, b_keys, b_vals):
    """Stable parallel merge of two sorted (key, val) runs.

    A-elements win ties (stability). Fully parallel and scatter-free:
    ``pos_a`` (own index + rank within the sibling run) is strictly
    increasing, so for output slot j the count ``r_a`` of a-elements placed
    at slots ≤ j is one more binary search; slot j holds ``a[r_a - 1]`` when
    that element sits exactly at j, else ``b[j - r_a]``. Relocation is two
    gathers — the inverse-permutation router — instead of four scatters.

    ``a_vals``/``b_vals`` may both be None (keys-only merge, the packed
    Ordering path); then ``out_v`` is None and no payload bytes move.
    """
    la = a_keys.shape[0]
    lb = b_keys.shape[0]
    n = la + lb
    # rank_in_sorted: jnp.searchsorted's 'scan' method is sequential over
    # queries (a 65536-trip while loop at Reddit scale) and its 'sort'
    # method replicates an XLA sort per device under GSPMD; the explicit
    # log-depth binary search stays parallel AND sharded (§Perf convert).
    pos_a = jnp.arange(la, dtype=jnp.int32) + rank_in_sorted(
        b_keys, a_keys, side="left")
    j = jnp.arange(n, dtype=jnp.int32)
    r_a = rank_in_sorted(pos_a, j, side="right")
    ia = jnp.clip(r_a - 1, 0, la - 1)
    from_a = (r_a > 0) & (jnp.take(pos_a, ia, mode="clip") == j)
    ib = jnp.clip(j - r_a, 0, lb - 1)
    out_k = jnp.where(from_a, jnp.take(a_keys, ia, mode="clip"),
                      jnp.take(b_keys, ib, mode="clip"))
    if a_vals is None:
        return out_k, None
    sel = from_a.reshape((n,) + (1,) * (a_vals.ndim - 1))
    out_v = jnp.where(sel, jnp.take(a_vals, ia, axis=0, mode="clip"),
                      jnp.take(b_vals, ib, axis=0, mode="clip"))
    return out_k, out_v


def merge_rounds(ks: jnp.ndarray, vs: jnp.ndarray | None, run: int
                 ) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    """Pairwise merge tree: sorted runs of length ``run`` → one sorted
    array in log₂(n/run) rounds of ``merge_sorted`` (earlier runs win
    ties, so the result is the stable sort). The mesh-sharded sorter
    (``engine/shard.py``) completes its per-device runs with these
    cross-device rounds; ``n/run`` must be a power of two. ``vs=None``
    merges keys alone."""
    n = ks.shape[0]
    while run < n:
        kr = ks.reshape(-1, 2, run)
        if vs is None:
            ks = jax.vmap(lambda a: merge_sorted(a[0], None, a[1],
                                                 None)[0])(kr)
        else:
            vr = vs.reshape(-1, 2, run)
            ks, vs = jax.vmap(lambda a, b: merge_sorted(a[0], b[0], a[1],
                                                        b[1]))(kr, vr)
            vs = vs.reshape(n)
        ks = ks.reshape(n)
        run *= 2
    return ks, vs


def stable_sort_by_key(keys: jnp.ndarray, vals: jnp.ndarray | None,
                       key_bound: int
                       ) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    """Global stable sort of ``keys`` (``vals`` riding along): one
    ``lax.sort``, or ``jnp.sort`` when ``vals is None`` (keys-only — the
    packed keys ARE the data, so no payload moves).

    ``key_bound``: exclusive upper bound of valid keys; keys at or past it
    (SENTINEL padding) are clipped to ``key_bound`` so they sort last, and
    restored to SENTINEL afterwards. Under GSPMD an XLA sort replicates
    (see ``set_count.rank_in_sorted``), so the mesh-sharded engine sorts
    each device's span and merges the runs (``engine/shard.py``).
    """
    clipped = jnp.minimum(keys, jnp.int32(key_bound))
    if vals is None:
        ks, vs = jnp.sort(clipped), None
    else:
        ks, vs = jax.lax.sort([clipped, vals], num_keys=1, is_stable=True)
    ks = jnp.where(ks >= key_bound, SENTINEL, ks)
    return ks, vs


def xla_lex_sort(dst: jnp.ndarray, src: jnp.ndarray, bound: int
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The two-pass Ordering as ONE two-key sort.

    Both columns are clipped to ``bound`` so padding sorts last, and one
    ``lax.sort`` orders the (dst, src) pair lexicographically. The pair is
    the whole record, so records with equal keys are identical and the
    result equals two stable LSD passes bit for bit. It is unstable on
    purpose: XLA:TPU makes a sort stable by sorting an extra ``iota``
    operand, which would put a third array back through the sort network.
    Sentinels are restored as the two passes restore them.
    """
    d = jnp.minimum(dst, jnp.int32(bound))
    s = jnp.minimum(src, jnp.int32(bound))
    d, s = jax.lax.sort([d, s], num_keys=2, is_stable=False)
    d = jnp.where(d >= bound, SENTINEL, d)
    s = jnp.where((s >= bound) | (d == SENTINEL), SENTINEL, s)
    return d, s


def edge_ordering(coo: COO, mode: str = "auto", sort_fn=None) -> COO:
    """Sort edges by (dst, src) — packed single key or the (dst, src) pair.

    ``mode``: "auto" (packed when the VID space fits), "packed", or
    "two_pass"; requesting "packed" on a too-wide VID space raises.
    ``sort_fn(keys, vals, key_bound) -> (keys, vals)`` replaces
    ``stable_sort_by_key`` — the mesh-sharded engine passes its shard_map
    sorter so both paths share ONE copy of the packing/sentinel-restore
    logic; under a ``sort_fn`` the two-pass scheme sorts by src, then
    stably by dst.
    """
    bound = coo.n_nodes
    if mode == "auto":
        mode = "packed" if supports_packed_keys(bound) else "two_pass"
    if mode == "packed":
        if not supports_packed_keys(bound):
            raise ValueError(
                f"packed-key ordering needs 2*bits(n_nodes) <= 31; "
                f"n_nodes={bound} does not fit — use mode='two_pass'")
        bits = _bits_for(bound)
        # clip BOTH columns to bound so sentinels stay in range; the packed
        # key orders by (dst, src) lexicographically in one sort
        d = jnp.minimum(coo.dst, jnp.int32(bound))
        s = jnp.minimum(coo.src, jnp.int32(bound))
        packed = (d << bits) | s
        pk, _ = (sort_fn or stable_sort_by_key)(packed, None,
                                                (bound << bits) | bound)
        # unpack; all-sentinel rows were restored to SENTINEL by the sorter
        mask = (1 << bits) - 1
        sent = pk == SENTINEL
        dst2 = jnp.where(sent, SENTINEL, pk >> bits)
        src2 = jnp.where(sent, SENTINEL, pk & mask)
        dst2 = jnp.where(dst2 >= bound, SENTINEL, dst2)
        src2 = jnp.where((src2 >= bound) | (dst2 == SENTINEL), SENTINEL,
                         src2)
        return COO(dst=dst2, src=src2, n_edges=coo.n_edges,
                   n_nodes=coo.n_nodes)
    if mode != "two_pass":
        raise ValueError(f"unknown ordering mode {mode!r}")
    if sort_fn is None:
        dst2, src2 = xla_lex_sort(coo.dst, coo.src, bound)
        return COO(dst=dst2, src=src2, n_edges=coo.n_edges,
                   n_nodes=coo.n_nodes)
    # pass 1: by src (secondary key), dst rides along as payload
    src1, dst1 = sort_fn(coo.src, coo.dst, bound)
    # pass 2: by dst (primary key), src rides along; stability keeps src order
    dst2, src2 = sort_fn(dst1, src1, bound)
    # restore src sentinels (payload positions that were padding)
    src2 = jnp.where(dst2 == SENTINEL, SENTINEL, src2)
    return COO(dst=dst2, src=src2, n_edges=coo.n_edges, n_nodes=coo.n_nodes)


def edge_ordering_xla(coo: COO) -> COO:
    """Comparison-sort baseline (what DGL-on-GPU effectively does)."""
    order = jnp.lexsort((coo.src, coo.dst))
    return COO(dst=coo.dst[order], src=coo.src[order],
               n_edges=coo.n_edges, n_nodes=coo.n_nodes)
