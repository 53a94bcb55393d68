"""Cost model (paper §V-B, Table I), TPU-recalibrated.

The paper's closed forms, verbatim:

  Ordering:   m = log2(e / w_upe) - 1
              cycles = 2 * m * e / (n_upe * w_upe)
  Selecting:  s = b * k^(l+1) - 1
              cycles = s / n_upe
  Reshaping:  cycles = max(n / n_scr, e / w_scr)

The paper's leading 2 in Ordering is its fixed pass count (LSD by src, then
by dst). Our Ordering stack can pack (dst, src) into one int32 key whenever
``2·bits(n_nodes) ≤ 31`` and sort once, so the constant becomes a
``sort_pass_count(cfg, w)`` term.

On TPU the "hardware configuration" is an EngineConfig (chunk width = UPE
width, lane count = UPE count, count tile = SCR width, target blocks = SCR
slot count). The Ordering is one native comparison sort whatever the
config, so the UPE geometry prices only the paper's Table-I cycles. Cycle
counts convert to seconds through per-primitive throughput constants
(``Calibration``; CPU-measured defaults).
"""
from __future__ import annotations

import dataclasses
import math

from .ordering import supports_packed_keys
from .graph import next_pow2


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """The reconfigurable knobs — the bitstream parameter analog.

    w_upe: UPE chunk width of the Table-I Ordering term
    n_upe: UPE lane count (Ordering and Selecting terms)
    w_scr: set-count element-block width (COO elements compared per pass)
    n_scr: set-count target-block height (pointer entries produced per pass)
    selection: selector algorithm
    sort_mode: edge-Ordering key scheme — "auto" (packed single-key sort
        when 2·bits(n_nodes) ≤ 31, the (dst, src) pair otherwise),
        "packed", or "two_pass"
    reindex_strategy: loop structure of every SCR rank-search epilogue
        (pointer build + reindex/rename lookups) — "fused" (statically
        unrolled search rounds: zero while ops, no per-round loop
        dispatch, at the cost of materializing per-round intermediates),
        "unfused" (``fori_loop`` rank searches: no materialization, one
        loop dispatch per pass), or "auto" (priced per query count by
        ``resolve_reindex_strategy`` — fused wins the small-query phases,
        unfused the bulk rename passes on CPU calibration)
    """

    w_upe: int = 4096
    n_upe: int = 8
    w_scr: int = 2048
    n_scr: int = 256
    selection: str = "floyd"
    sort_mode: str = "auto"
    reindex_strategy: str = "auto"

    @property
    def key(self) -> str:
        mode = "" if self.sort_mode == "auto" else f"_{self.sort_mode}"
        ridx = ("" if self.reindex_strategy == "auto"
                else f"_{self.reindex_strategy}")
        return (f"u{self.n_upe}x{self.w_upe}_s{self.n_scr}x{self.w_scr}"
                f"_{self.selection}{mode}{ridx}")


# Resource budget analog of the paper's 70:30 UPE:SCR split: the product of
# width × lanes is bounded (VMEM footprint stands in for LUT count).
UPE_BUDGET = 4096 * 64
SCR_BUDGET = 2048 * 2048


def bitstream_library() -> list[EngineConfig]:
    """Pre-compiled configuration library (paper: ten UPE × ten SCR variants).

    Start from one wide engine and iteratively halve width / double count,
    exactly the paper's generation rule. Every entry inherits
    ``sort_mode="auto"`` (packed-key Ordering whenever the VID space fits
    one int32 key), scored by ``sort_pass_count``.
    """
    out = []
    w_upe, n_upe = 65536, 4
    upes = []
    while w_upe >= 256:
        upes.append((w_upe, n_upe))
        w_upe //= 2
        n_upe *= 2
    w_scr, n_scr = 65536, 64
    scrs = []
    while w_scr >= 256:
        scrs.append((w_scr, n_scr))
        w_scr //= 2
        n_scr *= 2
    for wu, nu in upes:
        for ws, ns in scrs:
            out.append(EngineConfig(w_upe=wu, n_upe=nu, w_scr=ws, n_scr=ns))
    return out


@dataclasses.dataclass
class Calibration:
    """Per-primitive throughput (elements/sec per unit engine).

    Defaults are CPU-host-measured; no chip reading has replaced them yet.
    """

    scr_cmps_per_s: float = 5.0e9  # comparisons/sec (tile compare-reduce)
    sel_nodes_per_s: float = 5.0e6  # Floyd draws/sec per lane
    reidx_elems_per_s: float = 1.0e8
    # random-access gather throughput: bytes/sec the delta path's row
    # searches sustain when each pivot probes the edge stream at random
    # (cache-miss-bound on CPU, ~100 MB/s effective)
    hbm_bytes_per_s: float = 1.0e8
    # native comparison sort: sustained compare-exchange throughput of one
    # e·log₂(e) keys-only sort (payload-carrying pair sorts square the
    # stream factor), plus a fixed per-sort dispatch overhead.
    xla_cmp_per_s: float = 3.5e8
    sort_dispatch_s: float = 2.0e-4
    # SCR epilogue (reindex/pointer rank searches) strategy constants:
    # per-trip dispatch overhead of one fori_loop rank search (the
    # unfused path pays rounds·loop_trip_s per pass) vs the streaming
    # throughput at which the fused path materializes its per-round
    # intermediates (rounds·queries·4 bytes). CPU-measured; crossover at
    # ~375 queries/pass — fused pointer builds on small graphs, unfused
    # bulk renames. A TPU recalibration raises loop_trip_s ~50× (each
    # trip is a device round-trip) and flips everything to fused, the
    # same platform story as sort_dispatch_s above.
    loop_trip_s: float = 1.0e-7
    unroll_bytes_per_s: float = 1.5e10


@dataclasses.dataclass(frozen=True)
class Workload:
    n: int  # nodes
    e: int  # edges
    l: int = 2  # GNN layers
    k: int = 10  # fanout
    b: int = 1024  # batch nodes


def sort_pass_count(cfg: EngineConfig, w: Workload) -> int:
    """Key passes per edge Ordering (Table-I amendment).

    The packed-key scheme folds (dst, src) into one int32 key: 1. The pair
    scheme orders two keys: 2 — one two-key sort on one device, two stable
    sorts under the mesh-sharded sorter. Uses the SAME
    ``ordering.supports_packed_keys`` predicate ``edge_ordering`` resolves
    "auto" with, so the model scores the pass count that actually executes
    for this workload's VID width.
    """
    if cfg.sort_mode == "two_pass":
        return 2
    if cfg.sort_mode == "packed" or supports_packed_keys(w.n):
        return 1
    return 2


def convert_while_count(cfg: EngineConfig, w: Workload) -> int:
    """While ops in the whole compiled ``pipeline.convert`` (CPU
    lowering): the Ordering is one native sort, so only the
    ``rank_in_sorted`` pointer build can loop — one fori_loop when the
    pointer epilogue resolves unfused, ZERO when it resolves fused (the
    search rounds unroll statically). ``pointer_reindex_strategy`` is the
    same predicate ``pipeline.convert`` dispatches with: n=200 grid points
    build their 201-target pointer fused, the n=70000 point unfused."""
    return 0 if pointer_reindex_strategy(cfg, w) == "fused" else 1


def _cross_rounds(n_dev: int) -> int:
    """Cross-device merge rounds of the sharded sort: log₂(n_dev)."""
    return max(0, int(n_dev).bit_length() - 1)


def shard_sort_while_count(cfg: EngineConfig, w: Workload,
                           n_dev: int) -> int:
    """Census for ``engine.shard.shard_sort_by_key``: each device sorts its
    span natively (no while), then log₂(n_dev) cross-device merge rounds
    run two rank-search fori_loops each — per global sort."""
    return sort_pass_count(cfg, w) * 2 * _cross_rounds(n_dev)


def shard_convert_while_count(cfg: EngineConfig, w: Workload,
                              n_dev: int) -> int:
    """While census of the compiled ``shard_convert`` (sharded Ordering +
    the pointer build, fused/unfused-resolved exactly like the
    single-device census)."""
    return shard_sort_while_count(cfg, w, n_dev) + convert_while_count(cfg, w)


def shard_collective_bytes_budget(cfg: EngineConfig, w: Workload,
                                  n_dev: int) -> float:
    """Ceiling on loop-trip-multiplied collective bytes in the compiled
    sharded convert (``hlo_analysis.collective_bytes`` census).

    The ideal schedule all-gathers one int32 stream per cross-device merge
    round per global sort (two streams when the two-pass key scheme carries
    a payload); the 2× slack covers the pointer-build's replicated-input
    all-gather and partitioner bookkeeping, while still flagging an
    accidental fall-back to fully replicated sorting (≳ n_dev× the ideal).
    """
    passes = sort_pass_count(cfg, w)
    streams = 1 if passes == 1 else 2
    rounds = max(1, _cross_rounds(n_dev))
    return 2.0 * passes * streams * rounds * 4.0 * next_pow2(w.e)


REINDEX_STRATEGIES = ("fused", "unfused")
DELTA_MODES = ("merge", "rebuild")


# ---------------------------------------------------------------------------
# Incremental-conversion (delta merge) terms — Table-I amendment #4.
# The update path (core/delta.py) sorts TWO delta-sized streams (inserts +
# deletes; one native sort each, packed key or (dst, src) pair) plus the
# ONE event-zip merge rung (a 2·d keys-only native sort), then splices
# positionally: three bounded row searches with
# delta-many queries over the existing stream, one full-width event rank
# (e queries over the 2·d event table) and two (n+1)-query pointer
# corrections — the DELTA_RANK_PASSES whose loop structure is the
# fused/unfused SCR-epilogue axis. ``resolve_delta_mode`` prices this
# against a full re-convert of the combined edge set so
# ``pipeline.apply_delta(mode="auto")`` falls back to a rebuild exactly
# where a large delta makes the splice lose.
# ---------------------------------------------------------------------------

def delta_workload(w: Workload, d_cap: int) -> Workload:
    """The delta sorts' workload: the graph's VID space over the pow2
    delta bucket."""
    return Workload(n=w.n, e=next_pow2(d_cap), l=w.l, k=w.k, b=w.b)


def delta_epilogue_strategy(cfg: EngineConfig, w: Workload,
                            d_cap: int | None = None,
                            cal: "Calibration | None" = None) -> str:
    """fused/unfused resolution for the DELTA_RANK_PASSES full-width rank
    passes of one delta merge — one uniform strategy (the passes share
    the loop structure so the while census is ``0`` or exactly
    ``DELTA_RANK_PASSES``), resolved on the dominant load: the event rank
    (e queries over the 2·d event table) plus the two (n+1)-query pointer
    corrections.

    Per search round the fused path streams one pivot gather and one
    materialized carry per query (8 bytes); the unfused ``fori_loop``
    moves the same pivots plus its two loop-carried bound buffers through
    the while body (≈24 bytes) and pays one trip dispatch — so fused wins
    the delta splice at every measured CPU scale (1.2 ms vs 3.0 ms at
    131k/0.1%), and a TPU recalibration raising ``loop_trip_s`` only
    widens the gap. A forced ``cfg.reindex_strategy`` short-circuits."""
    if cfg.reindex_strategy != "auto":
        return cfg.reindex_strategy
    cal = cal or Calibration()
    wd = delta_workload(w, d_cap if d_cap is not None else 1)
    rounds = reindex_round_count(2 * wd.e)
    q = next_pow2(w.e) + 2 * (w.n + 1)
    t_fused = rounds * q * 8.0 / cal.unroll_bytes_per_s
    t_unfused = rounds * (q * 24.0 / cal.unroll_bytes_per_s
                          + cal.loop_trip_s)
    return "fused" if t_fused <= t_unfused else "unfused"


def delta_while_count(cfg: EngineConfig, w: Workload, d_cap: int,
                      cal: "Calibration | None" = None) -> int:
    """While ops the compiled ``apply_delta`` merge path lowers to: the
    delta sorts are native sorts and every delta-sized search unrolls
    statically, so only the rank passes loop — ``DELTA_RANK_PASSES``
    fori_loops unfused, ZERO fused. The ``delta_update`` contract in
    ``analysis/contracts.py`` asserts the compiled program agrees."""
    from .delta import DELTA_RANK_PASSES
    return (0 if delta_epilogue_strategy(cfg, w, d_cap, cal) == "fused"
            else DELTA_RANK_PASSES)


def delta_merge_seconds(cfg: EngineConfig, w: Workload, d_cap: int,
                        cal: "Calibration | None" = None) -> float:
    """Latency of one delta merge: two delta-bucket sorts + the event-zip
    rung, the bounded row searches (delta-many queries whose pivot
    gathers hit the existing stream at random — the cache-miss-bound
    regime ``hbm_bytes_per_s`` calibrates), the full-width event rank and
    pointer corrections at SCR throughput, the output splice streams, and
    the resolved epilogue strategy's own extra."""
    from .delta import DELTA_RANK_PASSES
    cal = cal or Calibration()
    wd = delta_workload(w, d_cap)
    # All three delta-sized sorts (two streams + the event zip) live in
    # the ONE compiled update program, so they share a single fixed
    # dispatch instead of paying per-pass like a standalone Ordering.
    passes = sort_pass_count(cfg, wd)
    t_sort = (cal.sort_dispatch_s
              + 2 * max(0.0, _ordering_seconds(cfg, wd, cal)
                        - passes * cal.sort_dispatch_s))
    zipn = 2 * wd.e
    t_zip = zipn * math.log2(max(2.0, zipn)) / cal.xla_cmp_per_s
    e_cap = next_pow2(w.e)
    log_e = reindex_round_count(e_cap)
    log_d = reindex_round_count(wd.e)
    log_2d = reindex_round_count(zipn)
    # three bounded row searches: each pivot gather is a random probe
    # into the e-sized stream (first rounds are row-local and cached —
    # charge the uncached tail)
    t_rows = 3 * min(log_e, 6) * wd.e * 4.0 / cal.hbm_bytes_per_s
    # full-width passes + delta-local cross-ranks at SCR throughput
    cmps = (e_cap * log_2d  # the event rank driving the splice
            + 2 * (w.n + 1) * log_d  # pointer corrections
            + 3 * wd.e * log_d)  # survivor/activation/occurrence ranks
    t_rank = cmps / cal.scr_cmps_per_s
    # splice output traffic: event-row gather (3 cols), survivor gather,
    # select chain, writeback — ~6 int32 streams over the output
    t_mem = 6.0 * 4.0 * e_cap / cal.unroll_bytes_per_s
    rounds = log_2d + 2 * log_d
    q = e_cap + 2 * (w.n + 1)
    if delta_epilogue_strategy(cfg, w, d_cap, cal) == "fused":
        t_extra = rounds * q * 8.0 / cal.unroll_bytes_per_s / 3
    else:
        t_extra = (rounds * q * 24.0 / cal.unroll_bytes_per_s / 3
                   + DELTA_RANK_PASSES * rounds * cal.loop_trip_s / 3)
    return t_sort + t_zip + t_rows + t_rank + t_mem + t_extra


def delta_rebuild_seconds(cfg: EngineConfig, w: Workload, d_cap: int,
                          cal: "Calibration | None" = None) -> float:
    """Latency of the fallback: sort the delete stream, tombstone-match it
    (reconstruction + membership rank over the existing stream), then
    fully re-convert the combined pow2 edge buffer (Ordering + pointer
    build + reshaping streams)."""
    cal = cal or Calibration()
    wd = delta_workload(w, d_cap)
    comb = Workload(n=w.n, e=next_pow2(w.e + wd.e), l=w.l, k=w.k, b=w.b)
    t = _ordering_seconds(cfg, wd, cal) / 2  # the delete stream only
    t += _ordering_seconds(cfg, comb, cal)
    log_d = reindex_round_count(wd.e)
    log_c = reindex_round_count(comb.e)
    cmps = (w.e * (reindex_round_count(w.n + 1) + 2 * log_d)
            + (w.n + 1) * log_c)
    # tombstone matching probes the existing stream at random per delete —
    # same cache-miss regime as the merge path's row searches
    t_rows = 2 * min(reindex_round_count(next_pow2(w.e)), 6) \
        * wd.e * 4.0 / cal.hbm_bytes_per_s
    # concat/pad + reshaping + pointer-build streams over the combined
    # buffer
    t_mem = 6.0 * 4.0 * comb.e / cal.unroll_bytes_per_s
    return t + cmps / cal.scr_cmps_per_s + t_rows + t_mem


def resolve_delta_mode(cfg: EngineConfig, w: Workload, d_cap: int,
                       cal: "Calibration | None" = None) -> str:
    """Resolve ``apply_delta(mode="auto")`` — merge while the delta is a
    small graph fraction, full rebuild once the delta-linear row searches
    price above one combined sort. The SAME predicate
    ``pipeline.apply_delta`` dispatches with, so the census and benchmark
    record the program that runs."""
    cal = cal or Calibration()
    return ("merge"
            if delta_merge_seconds(cfg, w, d_cap, cal)
            <= delta_rebuild_seconds(cfg, w, d_cap, cal)
            else "rebuild")


def sample_vid_capacity(w: Workload) -> int:
    """Collected-VID-list length of one ``sample_subgraph`` pass: the seed
    batch plus every frontier (b · Σ_{i≤l} k^i) — the SCR epilogue's
    sorted-stream length (Table-I Selecting arithmetic reused)."""
    frontier = nodes = w.b
    for _ in range(w.l):
        frontier *= w.k
        nodes += frontier
    return nodes


def sample_edge_capacity(w: Workload) -> int:
    """Pow2 capacity of the sampled edge buffer ``sample_subgraph``
    re-converts (b · Σ_{1≤i≤l} k^i, bucketed)."""
    frontier, edges = w.b, 0
    for _ in range(w.l):
        frontier *= w.k
        edges += frontier
    return next_pow2(max(1, edges))


def reindex_round_count(capacity: int) -> int:
    """Rank-search rounds per SCR epilogue pass over a ``capacity``-long
    sorted stream: the log₂ depth of the batched binary search (the
    fused/unfused axis changes how the rounds lower, never how many)."""
    return max(1, int(capacity).bit_length())


def reindex_query_count(capacity: int, e: int) -> int:
    """Total rank-search queries of one reindex build + edge rename: the
    first-occurrence pass (capacity), the order compaction (capacity), and
    the dst/src rename lookups (2·e)."""
    return 2 * capacity + 2 * e


def reindex_dispatch_count(strategy: str) -> int:
    """Sequential loop dispatches the reindex epilogue issues: the fused
    path unrolls everything (zero); unfused runs three fori_loops
    (first-occurrence rank, order compaction, the concatenated rename)."""
    return 0 if strategy == "fused" else 3


def rename_gather_bytes(capacity: int, e: int) -> float:
    """Bytes the rename lookups gather from the sorted stream + slot table
    (Table-I amendment: one int32 pivot per query per round, plus the final
    hit/table gathers) — the traffic term separating the strategies at
    scale."""
    return 4.0 * (reindex_round_count(capacity) + 2) * 2 * e


def resolve_reindex_strategy(cfg: EngineConfig, queries: int, stream: int,
                             cal: "Calibration | None" = None) -> str:
    """Resolve ``reindex_strategy="auto"`` for one SCR rank-search pass of
    ``queries`` targets over a ``stream``-long sorted array.

    Per search round the unfused path pays one loop-trip dispatch
    (``loop_trip_s``), the fused path materializes ``queries`` int32
    intermediates (``unroll_bytes_per_s``) — so fused wins exactly the
    small-query phases (CPU crossover ≈ 375 queries: the n=200 pointer
    build fuses, the 70k-target one doesn't, and the bulk subgraph rename
    stays unfused until a TPU recalibration raises ``loop_trip_s``). The
    SAME predicate ``pipeline.convert``/``sample_subgraph`` dispatch with,
    so the model prices the program that runs.
    """
    if cfg.reindex_strategy != "auto":
        return cfg.reindex_strategy
    cal = cal or Calibration()
    rounds = reindex_round_count(stream)
    t_fused = rounds * queries * 4.0 / cal.unroll_bytes_per_s
    t_unfused = rounds * cal.loop_trip_s
    return "fused" if t_fused <= t_unfused else "unfused"


def pointer_reindex_strategy(cfg: EngineConfig, w: Workload,
                             cal: "Calibration | None" = None) -> str:
    """The convert pointer build's resolved epilogue strategy: n+1 pointer
    targets ranked over the pow2 sorted-dst stream."""
    return resolve_reindex_strategy(cfg, w.n + 1, next_pow2(w.e), cal)


def _ordering_seconds(cfg: EngineConfig, w: Workload,
                      cal: "Calibration") -> float:
    """Ordering latency: the native sort's e·log₂(e) compare-exchange term
    (squared stream factor for the pair scheme) plus a fixed dispatch
    overhead per pass."""
    passes = sort_pass_count(cfg, w)
    streams = 1 if passes == 1 else 2
    cmps = passes * streams**2 * w.e * math.log2(max(2.0, w.e))
    return passes * cal.sort_dispatch_s + cmps / cal.xla_cmp_per_s


def _reindex_seconds(cfg: EngineConfig, w: Workload,
                     cal: "Calibration") -> float:
    """Reindexing latency (Table-I Reindexing term, epilogue-refit): ONE
    shared native sort of the collected VID list, the SCR
    rank-search passes (comparisons at SCR throughput), the
    head/prefix/compaction element passes, the rename gather traffic, and
    the resolved strategy's own extra (loop trips or round
    materialization)."""
    cap = next_pow2(sample_vid_capacity(w))
    e = sample_edge_capacity(w)
    wsub = Workload(n=w.n, e=cap)
    # _ordering_seconds prices sort_pass_count global sorts; the reindex
    # stream sorts exactly once (packed vid<<pos key or pair mode)
    t_sort = _ordering_seconds(cfg, wsub, cal) / sort_pass_count(cfg, wsub)
    q = reindex_query_count(cap, e)
    rounds = reindex_round_count(cap)
    t_rank = rounds * q / cal.scr_cmps_per_s
    t_pass = 3 * cap / cal.reidx_elems_per_s  # head flags, prefix, order
    rstrat = resolve_reindex_strategy(cfg, q, cap, cal)
    if rstrat == "fused":
        t_extra = rounds * q * 4.0 / cal.unroll_bytes_per_s
    else:
        t_extra = reindex_dispatch_count(rstrat) * rounds * cal.loop_trip_s
    return (t_sort + t_rank + t_pass + t_extra
            + rename_gather_bytes(cap, e) / cal.unroll_bytes_per_s)


def ordering_cycles(cfg: EngineConfig, w: Workload) -> float:
    m = max(1.0, math.log2(max(2.0, w.e / cfg.w_upe)) - 1)
    return sort_pass_count(cfg, w) * m * w.e / (cfg.n_upe * cfg.w_upe)


def selecting_cycles(cfg: EngineConfig, w: Workload) -> float:
    s = w.b * (w.k ** (w.l + 1)) - 1
    return s / cfg.n_upe


def reshaping_cycles(cfg: EngineConfig, w: Workload) -> float:
    return max(w.n / cfg.n_scr, w.e / cfg.w_scr)


def estimate_seconds(cfg: EngineConfig, w: Workload,
                     cal: Calibration | None = None) -> dict[str, float]:
    """Cycle model → seconds via calibrated throughputs (the Ordering is
    the native sort's price, ``_ordering_seconds``)."""
    cal = cal or Calibration()
    t_order = _ordering_seconds(cfg, w, cal)
    s = w.b * (w.k ** (w.l + 1)) - 1
    t_select = s / (cal.sel_nodes_per_s * cfg.n_upe)
    t_reshape = max(w.n / cfg.n_scr, w.e / cfg.w_scr) * (
        cfg.n_scr * cfg.w_scr / cal.scr_cmps_per_s)
    t_reindex = _reindex_seconds(cfg, w, cal)
    return {
        "ordering": t_order,
        "selecting": t_select,
        "reshaping": t_reshape,
        "reindexing": t_reindex,
        "total": t_order + t_select + t_reshape + t_reindex,
    }


def best_config(w: Workload, library: list[EngineConfig] | None = None,
                cal: Calibration | None = None) -> EngineConfig:
    """DynPre's decision: score every pre-compiled config, pick the min."""
    lib = library or bitstream_library()
    return min(lib, key=lambda c: estimate_seconds(c, w, cal)["total"])


def choose_config(w: Workload, library: list[EngineConfig] | None = None,
                  cal: Calibration | None = None) -> EngineConfig:
    """``best_config`` with the subgraph rename pass's
    ``reindex_strategy`` resolved and pinned on the returned config, so
    the dispatched program is exactly the one the model priced — the
    engine-service entry point.
    """
    cal = cal or Calibration()
    best = best_config(w, library, cal)
    cap = next_pow2(sample_vid_capacity(w))
    q = reindex_query_count(cap, sample_edge_capacity(w))
    return dataclasses.replace(
        best, reindex_strategy=resolve_reindex_strategy(best, q, cap, cal))
