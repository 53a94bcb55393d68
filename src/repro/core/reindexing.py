"""Subgraph Reindexing (paper §II-B Fig. 4b, §IV-A Fig. 9b).

Map sampled original VIDs to compact new VIDs without a hash map, riding
the convert spine's own machinery instead of private argsort round-trips:

1. **One shared sort.** Pack ``(vid << pos_bits) | pos`` into a single
   int32 key (the position in the low bits makes ANY sort stable and
   carries the payload for free) and run ONE keys-only
   ``ordering.stable_sort_by_key`` over the whole collected VID list —
   the native sort the edge Ordering uses. When the VID space is too wide
   to pack (``bits(vid_bound) + bits(cap-1) > 31``) the same sorter runs
   once in pair mode (position payload).
2. **Rank arithmetic instead of a second sort.** The old path argsorted
   the first-occurrence positions and inverted that permutation with a
   scatter. Now: one left-rank pass of the original VIDs against the
   sorted stream lands every element on its run head, whose carried
   position IS the first occurrence; a prefix sum over the
   first-occurrence flags numbers the runs in first-occurrence order, and
   a rank search over that (monotone) prefix sum compacts the ``order``
   array — gathers only, zero scatters, zero extra sorts.
3. **Gather lookups over the sorted stream.** ``lookup`` is a left-rank
   search over the full sorted stream (duplicates included — a left rank
   always lands on the run head) plus one gather from the slot→new-VID
   table, i.e. the SCR filter-tree query expressed on sorted data.

Every rank pass runs ``fused`` (statically unrolled search rounds — zero
while ops, no loop dispatch between rounds) or ``unfused`` (``fori_loop``
rank searches). Both are
bit-identical; ``EngineConfig.reindex_strategy`` selects, priced by
``costmodel.resolve_reindex_strategy``.

New VIDs are assigned in first-occurrence order, matching the paper's
counter-based numbering; a ``sorted`` order is also available.
"""
from __future__ import annotations

import jax.numpy as jnp

from .graph import COO, SENTINEL
from .ordering import _bits_for, stable_sort_by_key
from .set_count import rank_in_sorted
from .set_partition import prefix_sum


def reindex_supports_packed(vid_bound: int, capacity: int) -> bool:
    """True when (vid, position) pairs fit one non-negative int32 packed
    key — the single-stream shared-sort regime. Wider than the edge
    Ordering's ``supports_packed_keys`` bound: the position side needs
    only ``bits(capacity - 1)`` bits, not a second VID width."""
    return _bits_for(vid_bound) + _pos_bits(capacity) <= 31


def _pos_bits(capacity: int) -> int:
    return max(1, int(capacity - 1).bit_length()) if capacity > 1 else 1


class ReindexMap:
    """Static-shape reindex mapping.

    Attributes (all padded to ``capacity`` = len(vid list)):
      sorted_vids: the FULL sorted VID stream, duplicates included
                   (SENTINEL tail) — lookups left-rank into it and land on
                   run heads
      slot_to_new: new VID for each slot of ``sorted_vids``; valid at run
                   heads (the only slots a left-rank lookup can hit)
      order:       original VID for each new VID (the Subgraph order array)
      n_unique:    valid count
    """

    def __init__(self, sorted_vids, slot_to_new, order, n_unique,
                 unroll: bool = False):
        self.sorted_vids = sorted_vids
        self.slot_to_new = slot_to_new
        self.order = order
        self.n_unique = n_unique
        self.unroll = unroll

    def lookup(self, vids: jnp.ndarray) -> jnp.ndarray:
        """Original VIDs → new VIDs (SENTINEL where not in the map).

        rank = set-count(sorted stream < vid); hit test = one comparator;
        the new VID is a gather from the slot table.
        """
        rank = rank_in_sorted(self.sorted_vids, vids, side="left",
                              unroll=self.unroll)
        rank_c = jnp.clip(rank, 0, self.sorted_vids.shape[0] - 1)
        hit = jnp.take(self.sorted_vids, rank_c, mode="clip") == vids
        new = jnp.take(self.slot_to_new, rank_c, mode="clip")
        return jnp.where(hit & (vids != SENTINEL), new, SENTINEL)


def _sort_vid_stream(vids: jnp.ndarray, vid_bound: int | None
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The ONE shared sort: → (sorted vids, their original positions).

    Packed single-stream when the key fits (position in the low bits =
    free stability + free payload); pair mode otherwise.
    """
    n = vids.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    bound = SENTINEL if vid_bound is None else int(vid_bound)
    if vid_bound is not None and reindex_supports_packed(bound, n):
        pb = _pos_bits(n)
        # sentinels/out-of-range clip to bound → past key_bound → restored
        # to SENTINEL by the sorter's clip/restore contract
        v = jnp.minimum(vids, jnp.int32(bound))
        pk, _ = stable_sort_by_key((v << pb) | pos, None, bound << pb)
        valid = pk != SENTINEL
        sv = jnp.where(valid, pk >> pb, SENTINEL)
        sp = jnp.where(valid, pk & ((1 << pb) - 1), n - 1)
    else:
        # pair fallback: sort by vid with the position riding as payload
        # (stable, so positions stay ascending inside each run)
        sv, sp = stable_sort_by_key(vids, pos, bound)
        sp = jnp.where(sv != SENTINEL, sp, n - 1)
    return sv, sp


def build_reindex_map(vids: jnp.ndarray, numbering: str = "first_occurrence",
                      vid_bound: int | None = None,
                      strategy: str = "unfused") -> ReindexMap:
    """Build the mapping from a (duplicated, SENTINEL-padded) VID list.

    ``vid_bound``: static exclusive upper bound on valid VIDs (the graph's
    node count) — enables the packed single-stream shared sort; ``None``
    falls back to the pair sort. ``strategy``: ``"fused"`` (statically
    unrolled rank rounds, zero while ops) or ``"unfused"`` (fori_loop rank
    searches) — resolved ABOVE this layer (``costmodel
    .resolve_reindex_strategy`` via ``pipeline.sample_subgraph``), keeping
    Reindexing itself model-free exactly like Ordering.
    """
    if numbering not in ("first_occurrence", "sorted"):
        raise ValueError(numbering)
    if strategy not in ("fused", "unfused"):
        raise ValueError(strategy)
    unroll = strategy == "fused"

    def rank(arr, q):
        return rank_in_sorted(arr, q, side="left", unroll=unroll)

    n = vids.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    sv, sp = _sort_vid_stream(vids, vid_bound)
    valid = sv != SENTINEL
    is_head = valid & jnp.concatenate(
        [jnp.ones((1,), bool), sv[1:] != sv[:-1]])
    if numbering == "first_occurrence":
        # left rank of each original element lands on its run HEAD, whose
        # carried position is the run's first occurrence — no second sort
        i0 = rank(sv, vids)
        i0c = jnp.clip(i0, 0, n - 1)
        hit = (jnp.take(sv, i0c, mode="clip") == vids) & (vids != SENTINEL)
        first_pos = jnp.take(sp, i0c, mode="clip")
        occ_first = hit & (first_pos == pos)
        cum = prefix_sum(occ_first.astype(jnp.int32))  # inclusive
        n_unique = cum[-1]
        # per-slot new id: correct at run heads (sp there IS the first
        # occurrence), and heads are the only slots left-rank lookups hit
        slot_to_new = jnp.take(cum, jnp.clip(sp, 0, n - 1), mode="clip") - 1
        # order = gather-compaction of the first occurrences: src of new
        # VID j is the first position whose inclusive flag-count is j+1 —
        # one more rank search over the monotone prefix sum (the
        # gather_sources_from_counts trick in 1-D), not a set_partition
        # round-trip
        src = rank(cum, pos + 1)
        order = jnp.where(
            pos < n_unique,
            jnp.take(vids, jnp.clip(src, 0, n - 1), mode="clip"), SENTINEL)
    else:  # "sorted": new VID = rank among sorted uniques
        headcnt = prefix_sum(is_head.astype(jnp.int32))
        n_unique = headcnt[-1]
        slot_to_new = headcnt - 1
        src = rank(headcnt, pos + 1)
        order = jnp.where(
            pos < n_unique,
            jnp.take(sv, jnp.clip(src, 0, n - 1), mode="clip"), SENTINEL)
    return ReindexMap(sv, slot_to_new, order, n_unique.astype(jnp.int32),
                      unroll=unroll)


def reindex_edges(rmap: ReindexMap, edge_dst: jnp.ndarray,
                  edge_src: jnp.ndarray, n_nodes_cap: int) -> COO:
    """Renumber edge endpoints; invalid (sentinel-child) edges stay SENTINEL.

    Both endpoint columns rename through ONE rank pass over the shared
    sorted stream (concatenated queries — halves the loop dispatches of
    two separate lookups on the unfused path).
    """
    e = edge_dst.shape[0]
    both = rmap.lookup(jnp.concatenate([edge_dst, edge_src]))
    nd, ns = both[:e], both[e:]
    bad = (nd == SENTINEL) | (ns == SENTINEL)
    nd = jnp.where(bad, SENTINEL, nd)
    ns = jnp.where(bad, SENTINEL, ns)
    n_edges = jnp.sum(~bad).astype(jnp.int32)
    return COO(dst=nd, src=ns, n_edges=n_edges, n_nodes=n_nodes_cap)


def reindex_serial_oracle(vids) -> tuple:
    """Hash-map style sequential numbering (numpy oracle for tests)."""
    import numpy as np
    seen: dict[int, int] = {}
    order = []
    for v in np.asarray(vids):
        v = int(v)
        if v == int(SENTINEL):
            continue
        if v not in seen:
            seen[v] = len(order)
            order.append(v)
    return seen, order
