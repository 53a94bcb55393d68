"""Incremental conversion — delta-merge CSC updates at cost O(delta).

Production graphs mutate under traffic; a full re-convert per edge batch is
the serialization bottleneck the preprocessing pipeline exists to kill
(ROADMAP: "Incremental conversion for living graphs"). The sorted-CSC
invariant makes updates local: the CSC *is* a sorted (dst, src) stream plus
a rank-arithmetic pointer table, so an insert/delete batch splices in
positionally — every search the update issues runs either over the
delta-sized streams or with delta-many queries; the existing edge array is
never searched element-by-element, only streamed once at the end:

1. one **delta sort** — ``ordering.edge_ordering`` over just the delta
   stream (packed ``(dst << bits) | src`` keys when the VID space fits
   int32, one two-key (dst, src) sort otherwise),
2. **delete resolution** — each delete kills at most one matching existing
   edge (multiset semantics, misses are no-ops). Its victim's absolute slot
   is found by a two-level row search: ``ptr`` gathers bound the dst row,
   a delta-query rank over ``idx`` locates the src run, and the delete's
   occurrence index inside its equal-key run picks the copy. The resulting
   tombstone *positions* are compacted by the existing rank/gather router
   (``set_partition`` — zero scatters, same HLO discipline as the spine),
3. **ONE merge rung** — a single delta-sized sort zips insert slots and
   delete activation points into one sorted event table of 2·|delta|
   entries (the sort thunk doubles as the materialization barrier that
   keeps CPU fusion from re-evaluating the table elementally inside the
   splice gathers); a prefix sum over it prices every output slot's net
   shift,
4. **splice + local pointer patch** — one rank of the output positions
   over the event table routes every output slot to its source (surviving
   ``idx`` gather or sorted insert), and ``ptr'[v] = ptr[v] +
   |inserts < v| - |effective deletes < v|`` patches the pointers with two
   (n+1)-query ranks over delta-sized tables — no full pointer rebuild.

Everything is scatter-free (rank searches + gathers), fixed-shape and
jittable; deletes apply to the *pre-update* edge set (a delete whose edge
is also inserted in the same delta removes a pre-existing copy if any,
never the fresh insert). The result is bit-identical to a from-scratch
``pipeline.convert`` of the final edge list — the property
tests/test_delta.py fuzzes — while the only sort in the program runs on
the delta. Mode resolution lives above this layer (``pipeline.apply_delta``
via ``costmodel.resolve_delta_mode``), keeping this module model-free like
``ordering``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .graph import COO, CSC, SENTINEL, next_pow2, pad_to
from .ordering import edge_ordering
from .set_count import rank_in_sorted
from .set_partition import prefix_sum, set_partition

# Rank-search passes whose fused/unfused lowering the epilogue strategy
# controls (everything else the merge issues is delta-sized and always
# statically unrolled): the output-splice event rank plus the two pointer
# corrections. The while census (costmodel.delta_while_count) and the HLO
# contract both price this constant — keep them in lockstep.
DELTA_RANK_PASSES = 3

# Even event-table pad: sorts after every real event key (insert events are
# odd ``2*slot + 1``, delete events even ``2*slot``) without ever equaling
# an insert key, so a padded entry can neither rank below a query nor fake
# an insert hit.
_EVENT_PAD = jnp.int32(0x7FFFFFFE)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EdgeDelta:
    """One batched graph update: edge inserts + deletes, SENTINEL-padded.

    Both streams share one pow2 ``capacity`` (the delta bucket the service
    keys its jit cache on — repeated updates of any size up to the bucket
    hit one compiled program). ``n_ins``/``n_del`` count valid leading
    entries; padded rows carry SENTINEL in both columns and never match or
    merge as real edges.
    """

    ins_dst: jnp.ndarray  # int32 [D_cap]
    ins_src: jnp.ndarray  # int32 [D_cap]
    del_dst: jnp.ndarray  # int32 [D_cap]
    del_src: jnp.ndarray  # int32 [D_cap]
    n_ins: jnp.ndarray  # int32 scalar — valid insert count
    n_del: jnp.ndarray  # int32 scalar — valid delete count
    n_nodes: int  # static — VID space size

    def tree_flatten(self):
        return ((self.ins_dst, self.ins_src, self.del_dst, self.del_src,
                 self.n_ins, self.n_del), (self.n_nodes,))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, n_nodes=aux[0])

    @property
    def capacity(self) -> int:
        return self.ins_dst.shape[0]

    @staticmethod
    def from_arrays(ins_dst, ins_src, del_dst, del_src, n_nodes: int,
                    capacity: int | None = None) -> "EdgeDelta":
        ins_dst = jnp.asarray(ins_dst, jnp.int32)
        ins_src = jnp.asarray(ins_src, jnp.int32)
        del_dst = jnp.asarray(del_dst, jnp.int32)
        del_src = jnp.asarray(del_src, jnp.int32)
        n_ins, n_del = ins_dst.shape[0], del_dst.shape[0]
        cap = capacity or next_pow2(max(1, n_ins, n_del))
        return EdgeDelta(
            ins_dst=pad_to(ins_dst, cap, SENTINEL),
            ins_src=pad_to(ins_src, cap, SENTINEL),
            del_dst=pad_to(del_dst, cap, SENTINEL),
            del_src=pad_to(del_src, cap, SENTINEL),
            n_ins=jnp.int32(n_ins), n_del=jnp.int32(n_del),
            n_nodes=n_nodes)


def reconstruct_sorted_dst(csc: CSC, unroll: bool = False) -> jnp.ndarray:
    """Recover the sorted dst column the Reshaping consumed: slot j's dst
    is the number of pointer entries ≤ j, minus one (edges of vertex v
    occupy ``[ptr[v], ptr[v+1])``). Padded slots land at ``n_nodes`` — the
    clip value every sort already uses for sentinels. One
    E-query rank pass over the (n+1)-long pointer table; tolerant of
    pointer tails padded with ``ptr[-1]`` (the duplicates only inflate the
    clipped padding value). Only the rebuild fallback pays this — the
    merge path never rematerializes existing keys."""
    e_cap = csc.idx.shape[0]
    d = rank_in_sorted(csc.ptr, jnp.arange(e_cap, dtype=jnp.int32),
                       side="right", unroll=unroll) - 1
    return jnp.clip(d, 0, csc.n_nodes).astype(jnp.int32)


def _run_occurrence(is_new_run: jnp.ndarray) -> jnp.ndarray:
    """occ[j] = j - start of j's equal-key run, via a log-depth cumulative
    max over run-head positions (``associative_scan`` — zero while ops)."""
    j = jnp.arange(is_new_run.shape[0], dtype=jnp.int32)
    head_pos = jnp.where(is_new_run, j, 0)
    run_start = jax.lax.associative_scan(jnp.maximum, head_pos)
    return j - run_start


def _rank_in_rows(arr: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
                  queries: jnp.ndarray, side: str = "left") -> jnp.ndarray:
    """Bounded batched binary search: query t's rank is taken over
    ``arr[lo[t]:hi[t])`` only, returned as an absolute index into ``arr``.
    The two-level row search of the delta path: ``ptr`` gathers supply the
    per-query dst-row bounds, this locates the src run inside the row.
    Delta-many queries, statically unrolled rounds — never a while op."""
    n = arr.shape[0]
    steps = max(1, int(n).bit_length())
    l, h = lo, hi
    for _ in range(steps):  # static rounds — delta-sized work per round
        active = l < h
        mid = (l + h) >> 1
        pivot = jnp.take(arr, jnp.clip(mid, 0, n - 1), mode="clip")
        go_right = (pivot < queries) if side == "left" else \
            (pivot <= queries)
        l = jnp.where(active & go_right, mid + 1, l)
        h = jnp.where(active & ~go_right, mid, h)
    return l.astype(jnp.int32)


def _sorted_delta_stream(dst, src, n_nodes: int):
    """Sort one (dst, src) delta stream lexicographically — the full
    Ordering (``edge_ordering``, "auto" key scheme) on delta-sized
    columns. SENTINEL pads sort to the tail."""
    out = edge_ordering(COO(dst=dst, src=src, n_edges=jnp.int32(0),
                            n_nodes=n_nodes))
    return out.dst, out.src


def _delete_positions(csc: CSC, delta: EdgeDelta):
    """Resolve the delete stream to tombstone *positions*: sorted absolute
    slots of the victims in the existing CSC (SENTINEL-padded tail), plus
    the effective delete count. Each delete kills at most one copy — its
    occurrence index among equal delete keys must stay below the victim
    key's multiplicity, read off two bounded row ranks. All delta-sized."""
    n = csc.n_nodes
    d_cap = delta.capacity
    dd, ds = _sorted_delta_stream(delta.del_dst, delta.del_src, n)
    k = jnp.arange(d_cap, dtype=jnp.int32)
    row = jnp.clip(dd, 0, n - 1)
    lo = jnp.take(csc.ptr, row, mode="clip")
    hi = jnp.take(csc.ptr, row + 1, mode="clip")
    rl = _rank_in_rows(csc.idx, lo, hi, ds, side="left")
    rr = _rank_in_rows(csc.idx, lo, hi, ds, side="right")
    prev_d = jnp.concatenate([dd[:1] - 1, dd[:-1]])
    prev_s = jnp.concatenate([ds[:1] - 1, ds[:-1]])
    occ = _run_occurrence((dd != prev_d) | (ds != prev_s))
    valid = (k < delta.n_del) & (dd < n) & (ds < n) & (occ < rr - rl)
    # rl + occ is strictly increasing over the valid entries (equal keys
    # walk their run, greater keys start at or past the previous run's
    # right rank), so routing the misses to the tail leaves positions
    # sorted — the rank/gather compaction, zero scatters.
    pos, _ = set_partition(jnp.where(valid, rl + occ, SENTINEL),
                           valid)
    return pos, jnp.sum(valid.astype(jnp.int32)).astype(jnp.int32)


def delta_merge(csc: CSC, delta: EdgeDelta, *, unroll: bool = False,
                out_capacity: int | None = None) -> CSC:
    """Splice one EdgeDelta into a sorted CSC — the O(delta) update path.

    Sorts run only on delta-sized streams; the existing edges never
    re-sort and are never searched element-by-element — every binary
    search either issues delta-many queries (delete row
    ranks) or runs over a delta-sized table (the event rank that drives
    the splice). ``unroll`` selects the fused SCR epilogue for the
    :data:`DELTA_RANK_PASSES` full-width rank passes (statically unrolled
    rounds — zero while ops — ``fori_loop``s otherwise). ``out_capacity``
    (default: the input's edge capacity) sizes the output index buffer;
    the caller guarantees the surviving edge count fits
    (``engine.service.PreprocService.apply_delta`` grows the bucket on
    overflow).

    The splice itself is positional. Sorted inserts land at output slots
    ``outb[k] = |survivors before insert k| + k``; each effective delete
    starts shifting sources one slot later from its activation point.
    Zipping both (the ONE merge rung — a delta-sized sort) into an event
    table ``B2`` — insert events odd-coded, delete events even-coded — makes
    every output slot j a single left rank ``g`` of ``2j+1`` over ``B2``:
    with ``ci`` inserts among those g events, slot j reads
    ``inserts[ci]`` when the next event sits exactly at j, else survives
    ``idx[j + g - 2·ci]`` (g − ci deletes skipped forward, ci inserts
    pushed back).

    Bit-identity with from-scratch convert holds per *key*: duplicate
    (dst, src) edges are indistinguishable int32 pairs, so which physical
    copy a delete tombstones can never surface in the output.
    """
    n = csc.n_nodes
    e_cap = csc.idx.shape[0]
    d_cap = delta.capacity
    out_cap = e_cap if out_capacity is None else out_capacity
    k = jnp.arange(d_cap, dtype=jnp.int32)

    # -------- deletes → sorted tombstone positions (delta-sized)
    pos, n_del_eff = _delete_positions(csc, delta)

    # -------- inserts → output slots (delta-sized)
    bd, bs = _sorted_delta_stream(delta.ins_dst, delta.ins_src, n)
    valid_i = (k < delta.n_ins) & (bd < n) & (bs < n)
    pairs, _ = set_partition(jnp.stack([bd, bs], axis=1), valid_i)
    n_ins_eff = jnp.sum(valid_i.astype(jnp.int32)).astype(jnp.int32)
    live_i = k < n_ins_eff
    bd_c = jnp.where(live_i, pairs[:, 0], SENTINEL)
    bs_c = jnp.where(live_i, pairs[:, 1], SENTINEL)
    row = jnp.clip(bd_c, 0, n - 1)
    lo = jnp.take(csc.ptr, row, mode="clip")
    hi = jnp.take(csc.ptr, row + 1, mode="clip")
    # absolute right rank among ALL existing edges (rows partition the
    # sorted stream), minus the tombstones before it = survivors before
    ra = _rank_in_rows(csc.idx, lo, hi, bs_c, side="right")
    surv_before = ra - rank_in_sorted(pos, ra, side="left", unroll=True)
    outb = jnp.where(live_i, surv_before + k, _EVENT_PAD >> 1)

    # -------- deletes → activation points in output coordinates
    live_d = k < n_del_eff
    q_thresh = jnp.where(live_d, pos - k, SENTINEL)  # survivor-index space
    r_tab = jnp.where(live_i, surv_before, SENTINEL)  # = outb[k] - k
    c_t = rank_in_sorted(r_tab, q_thresh - 1, side="right", unroll=True)
    jdel = jnp.where(live_d, q_thresh + c_t, _EVENT_PAD >> 1)

    # -------- the ONE merge rung: zip events into one sorted table.
    # A single delta-sized sort op zips the two event streams. A
    # rank-merge (``merge_sorted``) computes the same table in pure
    # elementwise+gather form — but a gather's operand that is itself an
    # elementwise chain gets re-evaluated *per gathered element* inside
    # every consumer fusion (observed on the CPU backend: the splice
    # rank's pivot gathers each re-derived the whole merge, turning the
    # O(e·log d) event rank into O(e·log²d) recompute). A sort lowers to
    # a real thunk whose output buffer all downstream gathers stream
    # from, so the rung doubles as the materialization barrier.
    e_ins = jnp.where(live_i, (outb << 1) | 1, _EVENT_PAD)  # odd
    e_del = jnp.where(live_d, jdel << 1, _EVENT_PAD)  # even
    b2 = jnp.sort(jnp.concatenate([e_ins, e_del]))
    ci_tab = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              prefix_sum(b2 & 1)])

    # -------- splice: one event rank per output slot + gathers
    j = jnp.arange(out_cap, dtype=jnp.int32)
    g = rank_in_sorted(b2, (j << 1) | 1, side="left", unroll=unroll)
    # Every slot gathers its event row (next event key, inserts so far) as
    # two 1-D gathers. Not one [out_cap, 3] gather of stacked columns: a
    # TPU lays a 3-wide minor dimension out across 128 lanes, a 64 GB
    # buffer at a 2^27-edge capacity.
    b2_ext = jnp.concatenate([b2, jnp.full((1,), _EVENT_PAD)])
    nxt = jnp.take(b2_ext, g, mode="clip")
    ci = jnp.take(ci_tab, g, mode="clip")
    g = jnp.clip(g, 0, b2.shape[0])
    is_ins = nxt == ((j << 1) | 1)
    src = j + g - 2 * ci  # ci inserts pushed j back, g-ci deletes skipped
    n_edges_new = (csc.n_edges + n_ins_eff - n_del_eff).astype(jnp.int32)
    idx_new = jnp.where(
        j >= n_edges_new, SENTINEL,
        jnp.where(is_ins,
                  jnp.take(bs_c, jnp.clip(ci, 0, d_cap - 1), mode="clip"),
                  jnp.take(csc.idx, jnp.clip(src, 0, e_cap - 1),
                           mode="clip"))).astype(jnp.int32)

    # -------- pointer patch: delta-only rank corrections
    targets = jnp.arange(n + 1, dtype=jnp.int32)
    ptr_v = jnp.take(csc.ptr, targets, mode="clip")
    ins_lt = rank_in_sorted(bd_c, targets, side="left", unroll=unroll)
    del_lt = rank_in_sorted(pos, ptr_v, side="left", unroll=unroll)
    ptr_new = ptr_v + ins_lt - del_lt
    pad = csc.ptr.shape[0] - (n + 1)
    if pad > 0:
        ptr_new = jnp.concatenate(
            [ptr_new, jnp.broadcast_to(ptr_new[-1], (pad,))])
    return CSC(ptr=ptr_new.astype(jnp.int32), idx=idx_new,
               n_edges=n_edges_new, n_nodes=n)


def rebuild_coo(csc: CSC, delta: EdgeDelta, *,
                unroll: bool = False) -> COO:
    """The fallback's front half: apply deletes as SENTINEL tombstones and
    concatenate the inserts into one pow2 COO for a full re-convert
    (``pipeline.apply_delta`` mode="rebuild" — dispatched when the cost
    model prices the delta as a large-enough graph fraction that the
    positional splice loses to one full sort).

    Shares the positional delete matching with :func:`delta_merge` (only
    the delete stream is sorted here); tombstones need no
    compaction — the full sort pushes SENTINEL rows to the tail itself.
    """
    n = csc.n_nodes
    e_cap = csc.idx.shape[0]
    pos, n_del_eff = _delete_positions(csc, delta)
    d_ex = reconstruct_sorted_dst(csc, unroll=unroll)
    slot = jnp.arange(e_cap, dtype=jnp.int32)
    hit = jnp.take(pos, rank_in_sorted(pos, slot, side="left",
                                       unroll=True),
                   mode="clip")
    live = (d_ex < n) & (hit != slot)
    dst_all = jnp.concatenate([jnp.where(live, d_ex, SENTINEL),
                               delta.ins_dst])
    src_all = jnp.concatenate([jnp.where(live, csc.idx, SENTINEL),
                               delta.ins_src])
    cap = next_pow2(dst_all.shape[0])
    n_edges_new = (csc.n_edges + delta.n_ins - n_del_eff).astype(jnp.int32)
    return COO(dst=pad_to(dst_all, cap, SENTINEL),
               src=pad_to(src_all, cap, SENTINEL),
               n_edges=n_edges_new, n_nodes=n)
