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
``sort_pass_count(cfg, w)`` term; ``digit_pass_count`` likewise scores the
chunk-radix digit passes ``ceil(key_bits / radix_bits)`` that actually
execute for the configured ``EngineConfig.radix_bits``.

On TPU the "hardware configuration" is an EngineConfig (chunk width = UPE
width, lane count = UPE count analog via map batch, count tile = SCR width,
target blocks = SCR slot count). Cycle counts convert to seconds through
per-primitive throughput constants calibrated by benchmarks/fig24_costmodel.py
(`calibrate()` measures them; defaults are CPU-measured fallbacks).
"""
from __future__ import annotations

import dataclasses
import math

from .ordering import (DEFAULT_CHUNK, _bits_for, merge_round_fan_ins,
                       supports_packed_keys)
from .graph import next_pow2


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """The reconfigurable knobs — the bitstream parameter analog.

    w_upe: radix-sort chunk width (elements sorted fully in VMEM); the
        default is ``ordering.DEFAULT_CHUNK`` — the ONE routed chunk
        constant, so direct ``stable_sort_by_key`` callers and the engine
        path share a ladder depth
    n_upe: parallel sort lanes (chunks processed concurrently)
    w_scr: set-count element-block width (COO elements compared per pass)
    n_scr: set-count target-block height (pointer entries produced per pass)
    selection: selector algorithm
    radix_bits: digit width of every LSD radix pass — ONE value routed
        through both the jnp chunk sorter and the Pallas UPE kernel, so the
        cost model scores what actually executes
    sort_mode: edge-Ordering key scheme — "auto" (packed single-pass sort
        when 2·bits(n_nodes) ≤ 31, two-pass LSD otherwise), "packed", or
        "two_pass"
    sort_strategy: reduction structure of every global sort — "auto"
        (Table-I scored per workload, see ``resolve_sort_strategy``),
        "chunked_merge" (chunk radix sort + k-ary merge ladder),
        "global_radix" (per-digit tiled histogram + rank-gather relocation
        over the whole edge array; zero merge rounds), or "xla_sort" (the
        platform's native comparison-sort unit)
    merge_fan_in: runs merged per ladder rung on the chunked_merge path —
        round count drops from log₂(e/w_upe) to log_k at k²-per-rung
        search cost (an HBM-rounds-for-compute trade: the default stays 2
        on compute-bound hosts; raise it where relocation traffic
        dominates — the model prices both sides)
    reindex_strategy: loop structure of every SCR rank-search epilogue
        (pointer build + reindex/rename lookups) — "fused" (statically
        unrolled search rounds: zero while ops, no per-round loop
        dispatch, at the cost of materializing per-round intermediates),
        "unfused" (``fori_loop`` rank searches: no materialization, one
        loop dispatch per pass), or "auto" (priced per query count by
        ``resolve_reindex_strategy`` — fused wins the small-query phases,
        unfused the bulk rename passes on CPU calibration)
    """

    w_upe: int = DEFAULT_CHUNK
    n_upe: int = 8
    w_scr: int = 2048
    n_scr: int = 256
    selection: str = "floyd"
    use_pallas: bool = False
    radix_bits: int = 4
    sort_mode: str = "auto"
    sort_strategy: str = "auto"
    merge_fan_in: int = 2
    reindex_strategy: str = "auto"

    @property
    def key(self) -> str:
        mode = "" if self.sort_mode == "auto" else f"_{self.sort_mode}"
        strat = ("" if self.sort_strategy == "auto"
                 else f"_{self.sort_strategy}")
        fan = "" if self.merge_fan_in == 2 else f"_k{self.merge_fan_in}"
        ridx = ("" if self.reindex_strategy == "auto"
                else f"_{self.reindex_strategy}")
        return (f"u{self.n_upe}x{self.w_upe}_s{self.n_scr}x{self.w_scr}"
                f"_{self.selection}_r{self.radix_bits}{mode}{strat}{fan}"
                f"{ridx}{'_pl' if self.use_pallas else ''}")


# Resource budget analog of the paper's 70:30 UPE:SCR split: the product of
# width × lanes is bounded (VMEM footprint stands in for LUT count).
UPE_BUDGET = 4096 * 64
SCR_BUDGET = 2048 * 2048


def bitstream_library() -> list[EngineConfig]:
    """Pre-compiled configuration library (paper: ten UPE × ten SCR variants).

    Start from one wide engine and iteratively halve width / double count,
    exactly the paper's generation rule. Every entry inherits the default
    ``radix_bits=4`` digit width and ``sort_mode="auto"`` (packed-key
    single-pass Ordering whenever the VID space fits one int32 key); both
    knobs are scored by ``sort_pass_count``/``digit_pass_count``, so a
    caller extending the library with other digit widths gets them priced.
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

    Defaults are CPU-host-measured (BENCH_convert.json trajectory); the
    strategy crossovers they produce match the benchmark — global_radix
    above chunked_merge wherever the ladder has rounds, the native sort
    above both at every CPU scale. A TPU deployment recalibrates
    (benchmarks/fig24_costmodel.py): there ``hbm_bytes_per_s`` rises by
    ~3 orders (the relocation gathers stream through VMEM-resident
    Pallas tiles) while ``xla_cmp_per_s`` collapses (XLA sorts replicate
    under GSPMD and have no Mosaic fast path), flipping the dispatch to
    the radix strategies.
    """

    upe_elems_per_s: float = 2.0e8  # per lane, per digit/merge pass
    scr_cmps_per_s: float = 5.0e9  # comparisons/sec (tile compare-reduce)
    sel_nodes_per_s: float = 5.0e6  # Floyd draws/sec per lane
    reidx_elems_per_s: float = 1.0e8
    # relocation-traffic throughput: bytes/sec the global relocation
    # gathers sustain (random access — on CPU this is cache-miss-bound,
    # ~100 MB/s effective, the term that makes a 10-pass radix lose to
    # the native sort at 1M edges)
    hbm_bytes_per_s: float = 1.0e8
    # per-element cost of one merge-rung rank-search step relative to one
    # digit-pass element op; a rung of fan-in k performs k² searches at
    # log₂(e) depth (k(k-1) cross-run + k slot ranks)
    merge_step_weight: float = 1.0
    # native comparison-sort unit (the xla_sort strategy): sustained
    # compare-exchange throughput of one e·log₂(e) keys-only sort
    # (payload-carrying pair sorts square the stream factor), plus the
    # fixed per-sort dispatch overhead that hands small arrays to the
    # radix strategies.
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
    # same platform story as xla_cmp_per_s above.
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
    """Global stable sorts per edge Ordering (Table-I amendment).

    The packed-key scheme folds (dst, src) into one int32 key and sorts
    once; the LSD fallback sorts twice. Uses the SAME
    ``ordering.supports_packed_keys`` predicate ``edge_ordering`` resolves
    "auto" with, so the model scores the pass count that actually executes
    for this workload's VID width.
    """
    if cfg.sort_mode == "two_pass":
        return 2
    if cfg.sort_mode == "packed" or supports_packed_keys(w.n):
        return 1
    return 2


def digit_pass_count(cfg: EngineConfig, w: Workload) -> int:
    """Total chunk-radix digit passes per edge Ordering.

    Each global sort runs ceil(key_bits / radix_bits) set-partition passes;
    the packed key is twice as wide but sorted once, so narrowing
    ``radix_bits`` hurts both modes equally.
    """
    bits = _bits_for(w.n)
    key_bits = 2 * bits if sort_pass_count(cfg, w) == 1 else bits
    return sort_pass_count(cfg, w) * max(1, -(-key_bits // cfg.radix_bits))


def _merge_fan_ins(cfg: EngineConfig, w: Workload) -> list[int]:
    """Per-rung fan-ins of the chunked_merge ladder this workload runs
    (computed on the pow2 capacity bucket the engine actually dispatches)."""
    e = next_pow2(w.e)
    return merge_round_fan_ins(e, min(cfg.w_upe, e), cfg.merge_fan_in)


def merge_round_count(cfg: EngineConfig, w: Workload,
                      strategy: str | None = None) -> int:
    """Full-array merge rounds per edge Ordering (Table-I amendment #2).

    0 for the global_radix strategy (its digit passes relocate the whole
    array directly — no ladder); ``sort_pass_count ·
    len(merge_round_fan_ins(...))`` for chunked_merge, i.e. log_k instead
    of log₂ once ``merge_fan_in`` > 2. The HLO guard in
    tests/test_perf_paths.py checks the compiled ladder against this exact
    count. ``strategy=None`` prices the cfg's resolved strategy.
    """
    strategy = strategy or resolve_sort_strategy(cfg, w)
    if strategy in ("global_radix", "xla_sort"):
        return 0
    return sort_pass_count(cfg, w) * len(_merge_fan_ins(cfg, w))


def _ladder_while_count(fan_ins: list[int]) -> int:
    """While ops one chunked_merge ladder traversal lowers to: a fan-in-2
    rung is a pair of rank-search fori_loops; a k-ary rung runs the full
    k² cross-run search grid."""
    return sum(2 if k == 2 else k * k for k in fan_ins)


def sort_while_count(cfg: EngineConfig, w: Workload,
                     strategy: str | None = None) -> int:
    """While ops the compiled edge Ordering lowers to — the census side of
    ``merge_round_count``, consumed by the ``repro.analysis`` contract
    checker (model and program must agree for every library config).

    chunked_merge: per global sort, one digit-scan ``lax.scan`` over the
    chunk grid (+1 when the lane batch routes through ``lax.map``, i.e.
    ``0 < n_upe < n_chunks``) plus the ladder rungs. global_radix unrolls
    its digit passes statically and xla_sort is a single native sort op —
    both lower to zero while ops.
    """
    strategy = strategy or resolve_sort_strategy(cfg, w)
    if strategy in ("global_radix", "xla_sort"):
        return 0
    e = next_pow2(w.e)
    n_chunks = e // min(cfg.w_upe, e)
    lax_map = 1 if 0 < cfg.n_upe < n_chunks else 0
    return sort_pass_count(cfg, w) * (
        1 + lax_map + _ladder_while_count(_merge_fan_ins(cfg, w)))


def convert_while_count(cfg: EngineConfig, w: Workload,
                        strategy: str | None = None) -> int:
    """While ops in the whole compiled ``pipeline.convert``: the Ordering
    census plus the ``rank_in_sorted`` pointer build — one fori_loop when
    the pointer epilogue resolves unfused, ZERO when it resolves fused
    (the search rounds unroll statically). ``pointer_reindex_strategy``
    is the same predicate ``pipeline.convert`` dispatches with, so the
    census tracks the program that runs: n=200 grid points build their
    201-target pointer fused, the n=70000 point unfused."""
    ptr = 0 if pointer_reindex_strategy(cfg, w) == "fused" else 1
    return sort_while_count(cfg, w, strategy) + ptr


def sort_op_count(cfg: EngineConfig, w: Workload,
                  strategy: str | None = None) -> int:
    """Native ``sort`` ops in the compiled Ordering: the radix strategies
    must lower to zero (their order is produced by histogram + gather);
    xla_sort dispatches one in either key scheme — the packed key's one
    pass, or the two-pass scheme's ONE two-key sort
    (``ordering.xla_lex_sort``)."""
    strategy = strategy or resolve_sort_strategy(cfg, w)
    return 1 if strategy == "xla_sort" else 0


def sort_fn_op_count(cfg: EngineConfig, w: Workload,
                     strategy: str | None = None) -> int:
    """Native ``sort`` ops of one (dst, src) stream sorted pass by pass
    through a ``sort_fn`` (the delta streams): one per global pass on
    xla_sort, zero on the radix strategies."""
    strategy = strategy or resolve_sort_strategy(cfg, w)
    return sort_pass_count(cfg, w) if strategy == "xla_sort" else 0


def shard_sort_while_count(cfg: EngineConfig, w: Workload, n_dev: int,
                           strategy: str | None = None) -> int:
    """Census for ``engine.shard.shard_sort_by_key``: per global sort, the
    local per-device Ordering (on the e/n_dev shard) plus log₂(n_dev)
    cross-device merge rounds at two rank-search fori_loops each (the
    cross rounds are always fan-in 2)."""
    strategy = strategy or resolve_sort_strategy(cfg, w)
    e = next_pow2(w.e)
    local = max(1, e // max(1, n_dev))
    if strategy in ("global_radix", "xla_sort"):
        local_whiles = 0
    else:
        # the sharded local sort always vmaps (devices ARE the lanes:
        # shard_sort_by_key passes map_batch=0), so no lax.map term here
        chunk = min(cfg.w_upe, local)
        local_whiles = 1 + _ladder_while_count(
            merge_round_fan_ins(local, chunk, cfg.merge_fan_in))
    cross = 2 * len(merge_round_fan_ins(e, local, 2))
    return sort_pass_count(cfg, w) * (local_whiles + cross)


def shard_convert_while_count(cfg: EngineConfig, w: Workload, n_dev: int,
                              strategy: str | None = None) -> int:
    """While census of the compiled ``shard_convert`` (sharded Ordering +
    the pointer build, fused/unfused-resolved exactly like the
    single-device census)."""
    ptr = 0 if pointer_reindex_strategy(cfg, w) == "fused" else 1
    return shard_sort_while_count(cfg, w, n_dev, strategy) + ptr


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
    e = next_pow2(w.e)
    rounds = max(1, len(merge_round_fan_ins(e, e // max(1, n_dev), 2)))
    return 2.0 * passes * streams * rounds * 4.0 * e


def relocation_bytes(cfg: EngineConfig, w: Workload,
                     strategy: str | None = None) -> float:
    """HBM bytes the Ordering's full-array relocations stream (Table-I
    amendment #3) — the term that separates the strategies.

    chunked_merge keeps each digit pass VMEM-resident (the chunk is the
    working set), so it streams the array once for the whole chunk-sort
    stage plus once per merge rung; global_radix streams it once per digit
    pass. Keys-only packed Ordering moves one int32 stream, the two-pass
    scheme two (key + payload); every pass reads and writes.
    """
    strategy = strategy or resolve_sort_strategy(cfg, w)
    streams = 1 if sort_pass_count(cfg, w) == 1 else 2
    bytes_per_elem = 4 * streams * 2  # int32, read + write
    if strategy == "xla_sort":
        return 0.0  # relocation is internal to the native sort's compares
    if strategy == "global_radix":
        return float(digit_pass_count(cfg, w) * w.e * bytes_per_elem)
    passes = sort_pass_count(cfg, w)
    rounds = passes * len(_merge_fan_ins(cfg, w))
    return float((passes + rounds) * w.e * bytes_per_elem)


SORT_STRATEGIES = ("chunked_merge", "global_radix", "xla_sort")
REINDEX_STRATEGIES = ("fused", "unfused")
DELTA_MODES = ("merge", "rebuild")


# ---------------------------------------------------------------------------
# Incremental-conversion (delta merge) terms — Table-I amendment #4.
# The update path (core/delta.py) sorts TWO delta-sized streams (inserts +
# deletes; one global sort each in packed-key mode, the two-pass pair
# scheme otherwise) plus the ONE event-zip merge rung (a 2·d keys-only
# native sort), then splices positionally: three bounded row searches with
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
    delta bucket (what ``pipeline.apply_delta`` resolves its sort strategy
    on)."""
    return Workload(n=w.n, e=next_pow2(d_cap), l=w.l, k=w.k, b=w.b)


def resolve_delta_sort_strategy(cfg: EngineConfig, wd: Workload,
                                cal: "Calibration | None" = None) -> str:
    """Sort-strategy resolution for the delta streams.

    The delta path consumes its sorted streams through gathers (the row
    searches bracket every query against them), so they must land in
    thunk-materialized buffers. The radix strategies end in elementwise
    merge/relocation chains that CPU fusion re-evaluates per downstream
    gathered element (the hazard core/delta.py documents at its merge
    rung), so dispatching one would force the path to append a d-sized
    materializing sort anyway — price every strategy as its Ordering
    latency plus that barrier sort, which the native sort gets for free.
    At delta buckets the native sort therefore wins outright; a forced
    ``cfg.sort_strategy`` is still honored (the barrier inside
    ``delta_merge`` keeps any strategy correct, just not optimal)."""
    if cfg.sort_strategy != "auto":
        return cfg.sort_strategy
    cal = cal or Calibration()

    def price(s: str) -> float:
        t = _ordering_seconds(cfg, wd, cal, s)
        if s != "xla_sort":
            t += _ordering_seconds(cfg, wd, cal, "xla_sort")
        return t

    return min(SORT_STRATEGIES, key=price)


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
                      strategy: str | None = None,
                      cal: "Calibration | None" = None) -> int:
    """While ops the compiled ``apply_delta`` merge path lowers to: two
    delta-stream sorts (each a full Ordering census on the delta bucket —
    ``sort_while_count`` already folds in the packed-vs-pair pass count)
    plus the rank passes, which contribute ``DELTA_RANK_PASSES``
    fori_loops unfused and ZERO fused (every delta-sized search unrolls
    statically regardless; the event-zip rung is a native sort, not a
    loop). Under the resolved delta strategy (native sort) the whole
    merge program is while-free. The ``delta_update`` contract in
    ``analysis/contracts.py`` asserts the compiled program agrees."""
    from .delta import DELTA_RANK_PASSES
    wd = delta_workload(w, d_cap)
    if strategy is None:
        strategy = resolve_delta_sort_strategy(cfg, wd, cal)
    ranks = (0 if delta_epilogue_strategy(cfg, w, d_cap, cal) == "fused"
             else DELTA_RANK_PASSES)
    return 2 * sort_while_count(cfg, wd, strategy) + ranks


def delta_sort_op_count(cfg: EngineConfig, w: Workload, d_cap: int,
                        strategy: str | None = None,
                        cal: "Calibration | None" = None) -> int:
    """Native sort ops in the compiled merge path: the two delta sorts
    dispatch one per global pass under xla_sort (zero on the radix
    strategies) plus the ONE event-zip rung, which is always a native
    sort — it doubles as the materialization barrier. Nothing else in
    the path may sort (the existing stream never re-sorts; that is the
    point)."""
    wd = delta_workload(w, d_cap)
    if strategy is None:
        strategy = resolve_delta_sort_strategy(cfg, wd, cal)
    return 2 * sort_fn_op_count(cfg, wd, strategy) + 1


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
    strat = resolve_delta_sort_strategy(cfg, wd, cal)
    # All three delta-sized sorts (two streams + the event zip) live in
    # the ONE compiled update program, so they share a single fixed
    # dispatch instead of paying per-pass like a standalone Ordering.
    passes = sort_pass_count(cfg, wd)
    t_sort = (cal.sort_dispatch_s
              + 2 * max(0.0, _ordering_seconds(cfg, wd, cal, strat)
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
    t = _ordering_seconds(cfg, wd, cal,
                          resolve_delta_sort_strategy(cfg, wd, cal))
    t /= 2  # one delete-stream sort, not both delta streams
    t += _ordering_seconds(cfg, comb, cal,
                           resolve_sort_strategy(cfg, comb, cal))
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


def reindex_sort_op_count(cfg: EngineConfig, vid_bound: int,
                          capacity: int,
                          cal: "Calibration | None" = None) -> int:
    """Native sort ops of the ONE shared reindex sort: the VID stream sort
    is strategy-dispatched like any Ordering (keys-only, single pass), so
    it contributes exactly one native sort when the resolved strategy is
    xla_sort and zero on the radix paths — the census term
    ``analysis.contracts.sample_expectation`` prices."""
    strat = resolve_sort_strategy(
        cfg, Workload(n=vid_bound, e=capacity), cal)
    return 1 if strat == "xla_sort" else 0


def _ordering_seconds(cfg: EngineConfig, w: Workload, cal: "Calibration",
                      strategy: str) -> float:
    """Ordering latency under one concrete strategy: digit-pass compute +
    (chunked only) per-rung rank-search compute + relocation traffic; the
    native-sort strategy is a pure e·log₂(e) compare-exchange term plus a
    fixed dispatch overhead (its relocation is internal to the sort)."""
    passes = sort_pass_count(cfg, w)
    if strategy == "xla_sort":
        streams = 1 if passes == 1 else 2
        cmps = passes * streams**2 * w.e * math.log2(max(2.0, w.e))
        return passes * cal.sort_dispatch_s + cmps / cal.xla_cmp_per_s
    lanes = max(1, cfg.n_upe)  # n_upe=0 = "all lanes at once" (full vmap)
    digits = digit_pass_count(cfg, w)
    t = digits * w.e / (cal.upe_elems_per_s * lanes)
    if strategy == "chunked_merge":
        depth = math.log2(max(2.0, w.e))  # rank-search rounds per rung
        steps = passes * sum(k * k for k in _merge_fan_ins(cfg, w)) * depth
        t += (cal.merge_step_weight * steps * w.e
              / (cal.upe_elems_per_s * lanes))
    return t + relocation_bytes(cfg, w, strategy) / cal.hbm_bytes_per_s


def resolve_sort_strategy(cfg: EngineConfig, w: Workload,
                          cal: "Calibration | None" = None) -> str:
    """Resolve ``sort_strategy="auto"`` — the Table-I scored dispatch.

    The SAME predicate ``pipeline.convert`` / ``sample_subgraph`` and the
    benchmark harness use, so the model's pick is the program that runs:
    global_radix exactly where its pass-linear cost undercuts the chunk
    sort + merge ladder (large e/w_upe ratios; at e ≤ w_upe the two
    coincide and the chunked path wins on relocation traffic).
    """
    if cfg.sort_strategy != "auto":
        return cfg.sort_strategy
    cal = cal or Calibration()
    return min(SORT_STRATEGIES,
               key=lambda s: _ordering_seconds(cfg, w, cal, s))


def _reindex_seconds(cfg: EngineConfig, w: Workload,
                     cal: "Calibration") -> float:
    """Reindexing latency (Table-I Reindexing term, epilogue-refit): ONE
    shared strategy-dispatched sort of the collected VID list, the SCR
    rank-search passes (comparisons at SCR throughput), the
    head/prefix/compaction element passes, the rename gather traffic, and
    the resolved strategy's own extra (loop trips or round
    materialization)."""
    cap = next_pow2(sample_vid_capacity(w))
    e = sample_edge_capacity(w)
    wsub = Workload(n=w.n, e=cap)
    strat = resolve_sort_strategy(cfg, wsub, cal)
    # _ordering_seconds prices sort_pass_count global sorts; the reindex
    # stream sorts exactly once (packed vid<<pos key or pair mode)
    t_sort = _ordering_seconds(cfg, wsub, cal, strat) / sort_pass_count(
        cfg, wsub)
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
    """Cycle model → seconds via calibrated throughputs.

    Ordering is priced per strategy (digit-pass compute + merge-rung
    rank-search compute + relocation traffic — see ``_ordering_seconds``);
    ``sort_strategy="auto"`` scores as the min of both, which is what the
    dispatcher will run.
    """
    cal = cal or Calibration()
    if cfg.sort_strategy == "auto":
        t_order = min(_ordering_seconds(cfg, w, cal, s)
                      for s in SORT_STRATEGIES)
    else:
        t_order = _ordering_seconds(cfg, w, cal, cfg.sort_strategy)
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
    """``best_config`` with the strategy axes resolved: score the library
    (auto entries score as their best strategy), then pin the winning
    ``sort_strategy`` AND the subgraph rename pass's ``reindex_strategy``
    on the returned config so the dispatched program is exactly the one
    the model priced — the engine-service entry point.
    """
    cal = cal or Calibration()
    best = best_config(w, library, cal)
    cap = next_pow2(sample_vid_capacity(w))
    q = reindex_query_count(cap, sample_edge_capacity(w))
    return dataclasses.replace(
        best, sort_strategy=resolve_sort_strategy(best, w, cal),
        reindex_strategy=resolve_reindex_strategy(best, q, cap, cal))
