"""Declarative contracts over the compiled hot paths.

Every jitted hot path — ``pipeline.convert``, ``sample_subgraph``, the
``engine.shard`` sorted convert, the serve steps, the delta update —
registers machine-checkable invariants over its lowered HLO:

* **forbidden / required ops** — no ``scatter`` anywhere on the convert
  spine; exactly one native ``sort`` per Ordering (the packed key, or the
  (dst, src) pair as one two-key sort).
* **while-op budgets** — computed FROM the cost model
  (``costmodel.convert_while_count`` / ``shard_convert_while_count`` /
  ``delta_while_count``: the rank-search loops the resolved SCR-epilogue
  strategy leaves, plus the sharded sort's cross-device merge rounds).
  The model and the compiled program must agree for every config in
  ``bitstream_library()`` across the workload grid — a disagreement means
  the model is pricing a program that does not run.
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

from repro.core.costmodel import (EngineConfig, Workload,
                                  bitstream_library, convert_while_count,
                                  delta_epilogue_strategy,
                                  delta_while_count, delta_workload,
                                  pointer_reindex_strategy,
                                  reindex_dispatch_count,
                                  resolve_delta_mode,
                                  shard_collective_bytes_budget,
                                  shard_convert_while_count,
                                  sort_pass_count)
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
    same HLO (the program depends on shapes + the resolved key scheme and
    epilogue strategy, not on UPE or SCR geometry), so the checker
    compiles once per key and evaluates every member case against that
    one program — which also proves the members' expectations are
    mutually consistent.
    """

    contract: str
    label: str
    cfg: EngineConfig
    workload: Workload
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
# never hits: the forced two-pass key scheme and the pinned unfused
# pointer epilogue.
EXTRA_CONFIGS = (
    EngineConfig(sort_mode="two_pass"),
    EngineConfig(reindex_strategy="unfused"),
)
SMOKE_CONFIGS = (
    EngineConfig(),
    EngineConfig(sort_mode="two_pass"),
)


def convert_structure(cfg: EngineConfig, w: Workload) -> tuple:
    """Program-identity key for the compiled ``pipeline.convert``.

    Two configs with equal keys trace to the same jaxpr: the program is a
    function of shapes (n, pow2 edge capacity), the resolved key scheme
    and the pointer epilogue's resolved strategy. UPE and SCR geometry
    price the Table-I terms but never change the program, which is what
    collapses the 81-config library to one lowered program per workload.
    """
    return (sort_pass_count(cfg, w), w.n, next_pow2(w.e),
            pointer_reindex_strategy(cfg, w))


def convert_expectation(cfg: EngineConfig, w: Workload) -> Expectation:
    """The census ``costmodel`` prices for this (cfg, workload):
    scatter-free always, ONE native sort (either key scheme), while ops
    exactly ``convert_while_count`` (the pointer-build rank search when —
    and only when — ``pointer_reindex_strategy`` resolves it unfused; the
    fused epilogue unrolls the search rounds to zero whiles)."""
    return Expectation(
        forbidden_ops=("scatter",),
        required_ops=("gather",),
        while_count=convert_while_count(cfg, w),
        sort_count=1,
    )


def convert_cases(grid: str = "full") -> list[Case]:
    """The tentpole sweep: every library config × the workload grid."""
    if grid == "smoke":
        workloads, configs = SMOKE_WORKLOADS, SMOKE_CONFIGS
    else:
        workloads = CONVERT_WORKLOADS
        configs = tuple(bitstream_library()) + EXTRA_CONFIGS
    return [Case(contract="convert", label=f"{cfg.key} n={w.n} e={w.e}",
                 cfg=cfg, workload=w, structure=convert_structure(cfg, w),
                 expect=convert_expectation(cfg, w))
            for w in workloads for cfg in configs]


SAMPLE_FANOUTS = (2, 2)
SAMPLE_BATCH = 8


def _sample_case_workload() -> Workload:
    """The graph-level workload of the registered sample cases."""
    return Workload(n=200, e=2048, l=len(SAMPLE_FANOUTS),
                    k=max(SAMPLE_FANOUTS), b=SAMPLE_BATCH)


# Native sorts in one ``sample_subgraph`` lane: the ONE shared sort of
# the collected VID list (packed ``vid << pos`` key) plus the sub-COO
# re-conversion's Ordering. vmap batches ops instead of replicating them,
# so the slot-batched serve step has the same census.
SAMPLE_SORTS = 2


def sample_expectation() -> Expectation:
    """``sample_subgraph``'s program: Selecting + Reindexing + the sub-COO
    re-conversion. The RNG primitives lower to while loops (threefry), so
    the while census is not model-owned here; the contract pins what IS
    priced: scatter-free relocation and the exact native-sort census."""
    return Expectation(
        forbidden_ops=("scatter",),
        required_ops=("gather",),
        sort_count=SAMPLE_SORTS,
    )


def sample_cases(grid: str = "full") -> list[Case]:
    cfg = EngineConfig()
    return [Case(contract="sample",
                 label=f"{cfg.key} fanouts={SAMPLE_FANOUTS} b={SAMPLE_BATCH}",
                 cfg=cfg, workload=_sample_case_workload(),
                 structure=("sample",), expect=sample_expectation())]


GNN_SERVE_FANOUTS = (3, 2)  # = configs.graphsage_reddit smoke sample_sizes
GNN_SERVE_SEED_CAP = 8


def _gnn_serve_workload() -> Workload:
    """One slot lane of the GNN serving step, as a Table-I workload: the
    seed-row capacity is the sampling batch, the fan-outs give (l, k)."""
    return Workload(n=200, e=2048, l=len(GNN_SERVE_FANOUTS),
                    k=max(GNN_SERVE_FANOUTS), b=GNN_SERVE_SEED_CAP)


def gnn_serve_expectation() -> Expectation:
    """The ``GnnServeEngine`` step: every occupied slot's whole
    sample → reindex/re-convert → feature gather → forward → argmax as vmap
    lanes of ONE program. vmap batches ops instead of replicating them, so
    the step's native-sort census equals ONE lane's (``SAMPLE_SORTS``),
    and the forward must ride the pointer-based segment reduction (cumsum
    + boundary gathers), never ``scatter``: a ``jax.ops.segment_sum`` in
    the batched forward would lower to scatter and fail here. RNG
    threefry whiles are unasserted, as in the sample contract."""
    return Expectation(
        forbidden_ops=("scatter",),
        required_ops=("gather",),
        sort_count=SAMPLE_SORTS,
    )


def gnn_serve_cases(grid: str = "full") -> list[Case]:
    cfg = EngineConfig()
    return [Case(contract="gnn_serve",
                 label=(f"{cfg.key} fanouts={GNN_SERVE_FANOUTS} "
                        f"cap={GNN_SERVE_SEED_CAP}"),
                 cfg=cfg, workload=_gnn_serve_workload(),
                 structure=("gnn_serve",), expect=gnn_serve_expectation())]


# Delta grid: the convert smoke graph at two delta buckets, plus the
# pair-key regime (n=70000 defeats packing → 2 passes per delta sort).
DELTA_WORKLOADS = (
    (Workload(n=200, e=2048), 64),
    (Workload(n=200, e=2048), 256),
    (Workload(n=70000, e=2048), 64),
)
SMOKE_DELTA_WORKLOADS = ((Workload(n=200, e=2048), 64),)


# Native sorts in the compiled merge path: one per delta stream (inserts,
# deletes; packed key or one two-key sort) plus the ONE event-zip rung,
# which doubles as the materialization barrier. Nothing else may sort
# (the existing stream never re-sorts; that is the point).
DELTA_SORTS = 3


def delta_structure(cfg: EngineConfig, w: Workload, d_cap: int) -> tuple:
    """Program-identity key for the compiled ``apply_delta`` merge path:
    shapes (n, e_cap, delta bucket), the delta sorts' key scheme, and the
    rank passes' fused/unfused lowering."""
    wd = delta_workload(w, d_cap)
    fused = delta_epilogue_strategy(cfg, w, d_cap) == "fused"
    return ("delta", sort_pass_count(cfg, wd), w.n, next_pow2(w.e), wd.e,
            fused)


def delta_expectation(cfg: EngineConfig, w: Workload,
                      d_cap: int) -> Expectation:
    """The incremental-conversion census the Table-I delta terms price:
    scatter-free like the whole spine (tombstones compact through the
    rank/gather router), while ops exactly ``delta_while_count`` (ZERO
    with fused rank passes — the whole merge is while-free), native sorts
    exactly ``DELTA_SORTS``."""
    return Expectation(
        forbidden_ops=("scatter",),
        required_ops=("gather", "sort"),
        while_count=delta_while_count(cfg, w, d_cap),
        sort_count=DELTA_SORTS,
    )


def delta_cases(grid: str = "full") -> list[Case]:
    """The delta sweep: both rank lowerings over the delta workload
    grid."""
    points = SMOKE_DELTA_WORKLOADS if grid == "smoke" else DELTA_WORKLOADS
    reindex = ("auto",) if grid == "smoke" else ("auto", "unfused")
    cases = []
    for w, d_cap in points:
        for rs in reindex:
            cfg = EngineConfig(reindex_strategy=rs)
            cases.append(Case(
                contract="delta_update",
                label=f"{cfg.key} n={w.n} e={w.e} d={d_cap}",
                cfg=cfg, workload=w,
                structure=delta_structure(cfg, w, d_cap),
                expect=delta_expectation(cfg, w, d_cap), d_cap=d_cap))
    return cases


def shard_expectation(cfg: EngineConfig, w: Workload,
                      n_dev: int) -> Expectation:
    """The sharded convert: scatter-free, while census from
    ``shard_convert_while_count`` (2 rank searches per cross-device merge
    round + pointer build), collective bytes under
    ``shard_collective_bytes_budget``. Each device sorts its span with a
    native sort inside the shard_map body."""
    return Expectation(
        forbidden_ops=("scatter",),
        required_ops=("all-gather",),
        while_count=shard_convert_while_count(cfg, w, n_dev),
        collective_ceiling=shard_collective_bytes_budget(cfg, w, n_dev),
    )


def shard_cases(n_dev: int, grid: str = "full") -> list[Case]:
    w = Workload(n=200, e=2048)
    cfg = EngineConfig()
    return [Case(contract="shard", label=f"{cfg.key} e={w.e} nd={n_dev}",
                 cfg=cfg, workload=w, structure=("shard", n_dev),
                 expect=shard_expectation(cfg, w, n_dev), n_dev=n_dev)]


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
        "library_size": len(bitstream_library()),
    }


def model_self_consistency(cfg: EngineConfig, w: Workload) -> str | None:
    """Cross-check the model's own terms: the fused reindex epilogue must
    price zero loop dispatches (the census's fused ⇒ zero whiles), and a
    single-edge delta must always resolve to the merge path. Returns an
    error string or None.
    """
    if reindex_dispatch_count("fused") != 0:
        return "fused reindex epilogue must price zero loop dispatches"
    # Below ~2048 edges both paths finish inside one dispatch quantum and
    # the model's fixed constants dominate either side of the tie, so the
    # mode assertion is only meaningful at real workload sizes.
    if next_pow2(w.e) >= 2048 and resolve_delta_mode(cfg, w, 1) != "merge":
        return "a single-edge delta must never price above a full rebuild"
    return None
