"""Declarative contracts over the compiled hot paths.

Every jitted hot path — ``pipeline.convert`` per sort strategy,
``sample_subgraph``, the ``engine.shard`` sorted convert, the serve step —
registers machine-checkable invariants over its lowered HLO:

* **forbidden / required ops** — no ``scatter`` anywhere on the convert
  spine; no native ``sort`` on the radix strategies (their order comes from
  histogram + gather); exactly the priced number of ``sort`` ops on
  xla_sort paths.
* **while-op budgets** — computed FROM the cost model
  (``costmodel.convert_while_count`` / ``shard_convert_while_count``, which
  are ``merge_round_count``/``digit_pass_count`` re-expressed as a lowering
  census). The model and the compiled program must agree for every config
  in ``bitstream_library()`` across the workload grid — a disagreement
  means the model is pricing a program that does not run.
* **collective-byte ceilings** — ``hlo_analysis.collective_bytes`` on the
  sharded paths must stay under ``costmodel.shard_collective_bytes_budget``.
* **recompile guards** — re-dispatching the module-level jit entry
  (``engine.service.convert_jit``) with an already-seen ``(cfg, bucket)``
  must add ZERO cache entries (cache-size==1 per key).

The registry is pure data + model arithmetic (this module does lower
nothing); ``analysis/checker.py`` lowers one representative program per
structure group and evaluates every case against it.
"""
from __future__ import annotations

import dataclasses

from repro.core.costmodel import (EngineConfig, SORT_STRATEGIES, Workload,
                                  bitstream_library, convert_while_count,
                                  delta_epilogue_strategy,
                                  delta_sort_op_count, delta_while_count,
                                  delta_workload, merge_round_count,
                                  pointer_reindex_strategy,
                                  reindex_dispatch_count,
                                  reindex_sort_op_count,
                                  resolve_delta_mode,
                                  resolve_delta_sort_strategy,
                                  sample_edge_capacity, sample_vid_capacity,
                                  shard_collective_bytes_budget,
                                  shard_convert_while_count,
                                  sort_fn_op_count, sort_op_count,
                                  sort_pass_count, sort_while_count)
from repro.core.graph import next_pow2
from repro.core.ordering import supports_packed_keys


@dataclasses.dataclass(frozen=True)
class Expectation:
    """What the lowered program must look like. ``None`` = not asserted."""

    forbidden_ops: tuple[str, ...] = ()   # opcode substrings, e.g. "scatter"
    required_ops: tuple[str, ...] = ()
    while_count: int | None = None        # exact while-op census
    sort_count: int | None = None         # exact native-sort-op census
    collective_ceiling: float | None = None  # loop-multiplied bytes


@dataclasses.dataclass(frozen=True)
class Case:
    """One (config, workload) point of one contract.

    ``structure`` is the dedupe key: cases with equal keys lower to the
    same HLO (the program depends on shapes + the resolved strategy knobs,
    not on SCR geometry), so the checker compiles once per key and
    evaluates every member case against that one program — which also
    proves the members' expectations are mutually consistent.
    """

    contract: str
    label: str
    cfg: EngineConfig
    workload: Workload
    strategy: str
    structure: tuple
    expect: Expectation
    n_dev: int = 1
    d_cap: int = 0  # delta bucket (delta_update contract only)


@dataclasses.dataclass(frozen=True)
class Violation:
    contract: str
    case: str
    invariant: str
    message: str

    def __str__(self) -> str:
        return (f"[{self.contract}] {self.case}: {self.invariant} — "
                f"{self.message}")


# Workload grid: three edge scales in the packed-key regime plus one node
# scale past the packed-key bound (2·bits(70000) > 31 → two-pass Ordering).
CONVERT_WORKLOADS = (
    Workload(n=200, e=512),
    Workload(n=200, e=2048),
    Workload(n=200, e=8192),
    Workload(n=70000, e=2048),
)
SMOKE_WORKLOADS = (Workload(n=200, e=2048),)

# Off-library configs that exercise program shapes the generated library
# never hits: a k-ary ladder, the lax.map lane path (0 < n_upe < n_chunks),
# a wide digit, and the forced two-pass key scheme.
EXTRA_CONFIGS = (
    EngineConfig(w_upe=256, n_upe=8, merge_fan_in=4),
    EngineConfig(w_upe=256, n_upe=2),
    EngineConfig(w_upe=512, n_upe=8, radix_bits=8),
    EngineConfig(w_upe=256, n_upe=8, sort_mode="two_pass"),
)
SMOKE_CONFIGS = (
    EngineConfig(),
    EngineConfig(w_upe=256, n_upe=2),
    EngineConfig(w_upe=512, n_upe=8, merge_fan_in=4),
)


def convert_structure(cfg: EngineConfig, w: Workload,
                      strategy: str) -> tuple:
    """Program-identity key for the compiled ``pipeline.convert``.

    Two configs with equal keys trace to the same jaxpr: the program is a
    function of shapes (n, pow2 edge capacity), the resolved key scheme,
    the strategy, and — on the radix paths — the chunk width, digit width,
    ladder fan-in and the lane-batch routing (vmap when ``n_upe`` covers
    the chunk grid, ``lax.map`` over ``n_upe``-sized batches otherwise).
    SCR geometry (w_scr/n_scr) prices Reshaping but never changes the
    program, which is what collapses the 81-config library to a handful of
    lowered programs per workload.
    """
    e = next_pow2(w.e)
    passes = sort_pass_count(cfg, w)
    if strategy == "xla_sort":
        extra: tuple = ()
    else:
        chunk = min(cfg.w_upe, e)
        n_chunks = e // chunk
        lax_map = 0 < cfg.n_upe < n_chunks
        extra = (chunk, cfg.radix_bits, cfg.merge_fan_in,
                 cfg.n_upe if lax_map else 0)
    return (strategy, passes, w.n, e) + extra


def convert_expectation(cfg: EngineConfig, w: Workload,
                        strategy: str) -> Expectation:
    """The census ``costmodel`` prices for this (cfg, workload, strategy):
    scatter-free always, native sorts only on xla_sort, while ops exactly
    ``convert_while_count`` (= the merge-round/digit-pass structure of
    ``merge_round_count``, plus the pointer-build rank search when — and
    only when — ``pointer_reindex_strategy`` resolves it unfused; the
    fused epilogue unrolls the search rounds to zero whiles)."""
    forbidden = ("scatter",)
    if strategy != "xla_sort":
        forbidden = ("scatter", "sort")
    return Expectation(
        forbidden_ops=forbidden,
        required_ops=("gather",),
        while_count=convert_while_count(cfg, w, strategy),
        sort_count=sort_op_count(cfg, w, strategy),
    )


def convert_cases(grid: str = "full") -> list[Case]:
    """The tentpole sweep: every library config × the workload grid × every
    sort strategy (strategy forced, so all three programs are checked for
    every config — ``auto`` would only check the model's winner)."""
    if grid == "smoke":
        workloads, configs = SMOKE_WORKLOADS, SMOKE_CONFIGS
    else:
        workloads = CONVERT_WORKLOADS
        configs = tuple(bitstream_library()) + EXTRA_CONFIGS
    cases = []
    for w in workloads:
        for base in configs:
            for strategy in SORT_STRATEGIES:
                cfg = dataclasses.replace(base, sort_strategy=strategy)
                cases.append(Case(
                    contract="convert",
                    label=f"{cfg.key} n={w.n} e={w.e}",
                    cfg=cfg, workload=w, strategy=strategy,
                    structure=convert_structure(cfg, w, strategy),
                    expect=convert_expectation(cfg, w, strategy)))
    return cases


SAMPLE_FANOUTS = (2, 2)
SAMPLE_BATCH = 8


def _sample_case_workload() -> Workload:
    """The graph-level workload of the registered sample cases — its
    (l, k, b) are the Table-I sampling knobs the capacity helpers read."""
    return Workload(n=200, e=2048, l=len(SAMPLE_FANOUTS),
                    k=max(SAMPLE_FANOUTS), b=SAMPLE_BATCH)


def _sample_sub_workload() -> Workload:
    """The padded subgraph ``sample_subgraph`` re-converts: capacity is the
    pow2 bucket of the sampled edge count, VID space is the node budget
    (seeds + every frontier) — the exact ``costmodel.sample_vid_capacity``
    / ``sample_edge_capacity`` arithmetic, so the contract and the model
    price the same buffers."""
    w = _sample_case_workload()
    return Workload(n=sample_vid_capacity(w), e=sample_edge_capacity(w))


def sample_expectation(cfg: EngineConfig, strategy: str) -> Expectation:
    """``sample_subgraph``'s program: Selecting + Reindexing + the sub-COO
    re-conversion. The RNG primitives lower to while loops (threefry), so
    the while census is not model-owned here; the contract pins what IS
    priced: scatter-free relocation and the exact native-sort census.

    Reindexing rides the spine since the fused-SCR-epilogue refit: the VID
    list is sorted by ONE shared strategy-dispatched sort (replacing the
    old pair of private argsorts), so it contributes exactly
    ``reindex_sort_op_count`` native sorts — 1 on the xla_sort strategy,
    0 on the radix strategies — on top of the sub-convert's own census."""
    sub = _sample_sub_workload()
    sub_sorts = sort_op_count(cfg, sub, strategy)
    reindex_sorts = reindex_sort_op_count(
        cfg, _sample_case_workload().n, next_pow2(sub.n))
    return Expectation(
        forbidden_ops=("scatter",),
        required_ops=("gather",),
        sort_count=reindex_sorts + sub_sorts,
    )


def sample_cases(grid: str = "full") -> list[Case]:
    w = _sample_case_workload()
    cases = []
    for strategy in SORT_STRATEGIES:
        cfg = EngineConfig(w_upe=256, n_upe=8, sort_strategy=strategy)
        cases.append(Case(
            contract="sample",
            label=f"{cfg.key} fanouts={SAMPLE_FANOUTS} b={SAMPLE_BATCH}",
            cfg=cfg, workload=w, strategy=strategy,
            structure=("sample", strategy),
            expect=sample_expectation(cfg, strategy)))
    return cases


GNN_SERVE_FANOUTS = (3, 2)  # = configs.graphsage_reddit smoke sample_sizes
GNN_SERVE_SEED_CAP = 8


def _gnn_serve_workload() -> Workload:
    """One slot lane of the GNN serving step, as a Table-I workload: the
    seed-row capacity is the sampling batch, the fan-outs give (l, k)."""
    return Workload(n=200, e=2048, l=len(GNN_SERVE_FANOUTS),
                    k=max(GNN_SERVE_FANOUTS), b=GNN_SERVE_SEED_CAP)


def _gnn_serve_sub_workload() -> Workload:
    """The padded per-lane subgraph the slot re-converts — the same
    ``sample_vid_capacity``/``sample_edge_capacity`` arithmetic as the
    sample contract, so both price the same buffers."""
    w = _gnn_serve_workload()
    return Workload(n=sample_vid_capacity(w), e=sample_edge_capacity(w))


def gnn_serve_expectation(cfg: EngineConfig, strategy: str) -> Expectation:
    """The ``GnnServeEngine`` step: every occupied slot's whole
    sample → reindex/re-convert → feature gather → forward → argmax as vmap
    lanes of ONE program. vmap batches ops instead of replicating them, so
    the step's native-sort census equals ONE lane's — exactly the sample
    contract's ``reindex_sort_op_count + sort_op_count`` arithmetic — and
    the forward must ride the pointer-based segment reduction (cumsum +
    boundary gathers), never ``scatter``: a ``jax.ops.segment_sum`` in the
    batched forward would lower to scatter and fail here. RNG threefry
    whiles are unasserted, as in the sample contract."""
    sub = _gnn_serve_sub_workload()
    return Expectation(
        forbidden_ops=("scatter",),
        required_ops=("gather",),
        sort_count=(reindex_sort_op_count(cfg, _gnn_serve_workload().n,
                                          next_pow2(sub.n))
                    + sort_op_count(cfg, sub, strategy)),
    )


def gnn_serve_cases(grid: str = "full") -> list[Case]:
    w = _gnn_serve_workload()
    cases = []
    for strategy in SORT_STRATEGIES:
        cfg = EngineConfig(w_upe=256, n_upe=8, sort_strategy=strategy)
        cases.append(Case(
            contract="gnn_serve",
            label=(f"{cfg.key} fanouts={GNN_SERVE_FANOUTS} "
                   f"cap={GNN_SERVE_SEED_CAP}"),
            cfg=cfg, workload=w, strategy=strategy,
            structure=("gnn_serve", strategy),
            expect=gnn_serve_expectation(cfg, strategy)))
    return cases


# Delta grid: the convert smoke graph at two delta buckets, plus the
# pair-key regime (n=70000 defeats packing → 2 passes per delta sort).
DELTA_WORKLOADS = (
    (Workload(n=200, e=2048), 64),
    (Workload(n=200, e=2048), 256),
    (Workload(n=70000, e=2048), 64),
)
SMOKE_DELTA_WORKLOADS = ((Workload(n=200, e=2048), 64),)


def delta_structure(cfg: EngineConfig, w: Workload, d_cap: int,
                    strategy: str) -> tuple:
    """Program-identity key for the compiled ``apply_delta`` merge path:
    shapes (n, e_cap, delta bucket), the delta sorts' pass count and
    strategy knobs, and the rank passes' fused/unfused lowering."""
    wd = delta_workload(w, d_cap)
    fused = delta_epilogue_strategy(cfg, w, d_cap) == "fused"
    if strategy == "xla_sort":
        extra: tuple = ()
    else:
        chunk = min(cfg.w_upe, wd.e)
        extra = (chunk, cfg.radix_bits, cfg.merge_fan_in)
    return (("delta", strategy, sort_pass_count(cfg, wd), w.n,
             next_pow2(w.e), wd.e, fused) + extra)


def delta_expectation(cfg: EngineConfig, w: Workload, d_cap: int,
                      strategy: str) -> Expectation:
    """The incremental-conversion census the Table-I delta terms price:
    scatter-free like the whole spine (tombstones compact through the
    rank/gather router), while ops exactly ``delta_while_count`` (ZERO on
    the resolved program: native delta sorts + fused rank passes — the
    whole merge is while-free), native sorts exactly
    ``delta_sort_op_count`` (2 delta streams × passes, plus the ONE
    event-zip merge rung, which is always a native sort: it doubles as
    the materialization barrier against elemental re-evaluation of the
    event table inside the splice gathers)."""
    return Expectation(
        forbidden_ops=("scatter",),
        required_ops=("gather", "sort"),
        while_count=delta_while_count(cfg, w, d_cap, strategy),
        sort_count=delta_sort_op_count(cfg, w, d_cap, strategy),
    )


def delta_cases(grid: str = "full") -> list[Case]:
    """The delta sweep: every sort strategy forced (as in the convert
    contract) × both rank lowerings, over the delta workload grid."""
    points = SMOKE_DELTA_WORKLOADS if grid == "smoke" else DELTA_WORKLOADS
    reindex = ("auto",) if grid == "smoke" else ("auto", "unfused")
    cases = []
    for w, d_cap in points:
        for rs in reindex:
            for strategy in SORT_STRATEGIES:
                cfg = EngineConfig(sort_strategy=strategy,
                                   reindex_strategy=rs)
                cases.append(Case(
                    contract="delta_update",
                    label=f"{cfg.key} n={w.n} e={w.e} d={d_cap}",
                    cfg=cfg, workload=w, strategy=strategy,
                    structure=delta_structure(cfg, w, d_cap, strategy),
                    expect=delta_expectation(cfg, w, d_cap, strategy),
                    d_cap=d_cap))
    return cases


def shard_expectation(cfg: EngineConfig, w: Workload, n_dev: int,
                      strategy: str) -> Expectation:
    """The sharded convert: scatter-free, while census from
    ``shard_convert_while_count`` (local Ordering + 2 rank searches per
    cross-device merge round + pointer build), collective bytes under
    ``shard_collective_bytes_budget``. Native sorts are allowed — the
    xla_sort strategy sorts inside the shard_map body."""
    return Expectation(
        forbidden_ops=("scatter",),
        required_ops=("all-gather",),
        while_count=shard_convert_while_count(cfg, w, n_dev, strategy),
        collective_ceiling=shard_collective_bytes_budget(cfg, w, n_dev),
    )


def shard_cases(n_dev: int, grid: str = "full") -> list[Case]:
    w = Workload(n=200, e=2048)
    cases = []
    for strategy in SORT_STRATEGIES:
        cfg = EngineConfig(w_upe=256, n_upe=8, sort_strategy=strategy)
        cases.append(Case(
            contract="shard",
            label=f"{cfg.key} e={w.e} nd={n_dev}",
            cfg=cfg, workload=w, strategy=strategy,
            structure=("shard", strategy, n_dev),
            expect=shard_expectation(cfg, w, n_dev, strategy),
            n_dev=n_dev))
    return cases


def serve_expectation() -> Expectation:
    """The serve decode step: a fixed-slot ring of dynamic-update-slices —
    no scatter, no sort, ever. Its while census belongs to the model stack
    (scan over layers), not the preprocessing model, so it is unasserted;
    the recompile guard (step_cache_size()==1 across heterogeneous request
    traffic) is enforced by the checker's runtime leg."""
    return Expectation(
        forbidden_ops=("scatter", "sort"),
        required_ops=("dynamic-update-slice",),
    )


def two_pass_boundary_nodes() -> int:
    """First workload-grid node count past the packed-key bound (documents
    why CONVERT_WORKLOADS carries n=70000)."""
    assert not supports_packed_keys(70000)
    return 70000


def registry_summary() -> dict:
    """Contract registry overview (docs + ``--json`` report header)."""
    convert = convert_cases("full")
    return {
        "contracts": ["convert", "sample", "shard", "serve", "gnn_serve",
                      "delta_update"],
        "convert_cases": len(convert),
        "convert_groups": len({c.structure for c in convert}),
        "delta_cases": len(delta_cases("full")),
        "workloads": [dataclasses.asdict(w) for w in CONVERT_WORKLOADS],
        "strategies": list(SORT_STRATEGIES),
        "library_size": len(bitstream_library()),
    }


def model_self_consistency(cfg: EngineConfig, w: Workload,
                           strategy: str) -> str | None:
    """Cross-check the census arithmetic against the model's own terms:
    the ladder the census counts k² rank searches over must have exactly
    the rounds ``merge_round_count`` prices, and the convert census's
    pointer term must be the resolved SCR-epilogue strategy's dispatch
    structure (fused ⇒ zero loop dispatches ⇒ zero extra whiles).
    Returns an error string or None.
    """
    from repro.core.costmodel import _merge_fan_ins
    rounds = merge_round_count(cfg, w, strategy)
    if strategy in ("global_radix", "xla_sort"):
        want = 0
    else:
        want = sort_pass_count(cfg, w) * len(_merge_fan_ins(cfg, w))
    if rounds != want:
        return (f"merge_round_count={rounds} but the census ladder has "
                f"{want} rounds")
    ptr = (convert_while_count(cfg, w, strategy)
           - sort_while_count(cfg, w, strategy))
    ptr_strat = pointer_reindex_strategy(cfg, w)
    if ptr != (0 if ptr_strat == "fused" else 1):
        return (f"convert pointer while term {ptr} inconsistent with "
                f"resolved pointer strategy {ptr_strat!r}")
    if reindex_dispatch_count("fused") != 0:
        return "fused reindex epilogue must price zero loop dispatches"
    # Delta-term ties (priced at a canonical 64-edge bucket): the while
    # census must decompose into the two delta-stream sorts plus the rank
    # passes exactly as the resolved epilogue strategy dictates, the sort
    # census must be the two streams' passes plus the ONE event-zip rung,
    # and a single-edge delta must always resolve to the merge path.
    from repro.core.delta import DELTA_RANK_PASSES
    wd = delta_workload(w, 64)
    ds = resolve_delta_sort_strategy(cfg, wd)
    ranks = (0 if delta_epilogue_strategy(cfg, w, 64) == "fused"
             else DELTA_RANK_PASSES)
    if delta_while_count(cfg, w, 64) != \
            2 * sort_while_count(cfg, wd, ds) + ranks:
        return "delta while census inconsistent with its sort + rank terms"
    if delta_sort_op_count(cfg, w, 64) != \
            2 * sort_fn_op_count(cfg, wd, ds) + 1:
        return ("delta sort census must be 2·stream passes + the event-zip "
                "rung")
    # Below ~2048 edges both paths finish inside one dispatch quantum and
    # the model's fixed constants dominate either side of the tie, so the
    # mode assertion is only meaningful at real workload sizes.
    if next_pow2(w.e) >= 2048 and resolve_delta_mode(cfg, w, 1) != "merge":
        return "a single-edge delta must never price above a full rebuild"
    return None
