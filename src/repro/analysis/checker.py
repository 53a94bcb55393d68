"""Contract checker: lower each registered hot path once per structure
group, evaluate every contract case against the compiled HLO.

The flow per contract:

1. ``contracts.*_cases`` enumerates (config, workload, strategy) cases,
   each carrying a model-derived :class:`~repro.analysis.contracts.Expectation`
   and a ``structure`` dedupe key.
2. Cases are grouped by key; ONE representative is lowered per group
   (``jax.jit(...).lower(...).compile().as_text()``), and every member
   case is evaluated against that one program. Members of a group whose
   expectations disagree therefore can't all pass — the group is also a
   model-consistency check, and it is what makes the full 81-config ×
   workload × strategy sweep compile ~40 programs instead of ~1000.
3. The convert contract additionally runs the recompile guard: dispatching
   the module-level ``engine.service.convert_jit`` twice with the group's
   (cfg, bucket) must add zero cache entries on the second call.

Checks run in-process against whatever devices jax was initialized with;
the sharded contract needs ≥ 2 devices (the CLI sets
``--xla_force_host_platform_device_count`` before importing jax) and is
reported as skipped otherwise.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import contracts
from repro.analysis.contracts import Case, Violation
from repro.core import pipeline
from repro.core.graph import COO, random_coo
from repro.launch.hlo_analysis import collective_bytes, op_counts
from repro.launch.mesh import make_mesh


# ---------------------------------------------------------------------------
# HLO evaluation
# ---------------------------------------------------------------------------
def evaluate_hlo(hlo_text: str, case: Case) -> list[Violation]:
    """Evaluate one case's expectation against a compiled program's text."""
    ops = op_counts(hlo_text)
    exp = case.expect
    out: list[Violation] = []

    def v(invariant: str, message: str) -> None:
        out.append(Violation(case.contract, case.label, invariant, message))

    for pat in exp.forbidden_ops:
        hits = {k: n for k, n in ops.items() if pat in k}
        if hits:
            v(f"no-{pat}", f"forbidden ops in HLO: {hits}")
    for pat in exp.required_ops:
        if not any(pat in k for k in ops):
            v(f"has-{pat}", "required op missing from HLO")
    if exp.while_count is not None:
        got = ops.get("while", 0)
        if got != exp.while_count:
            v("while-census",
              f"model prices {exp.while_count} while ops, program has "
              f"{got}")
    if exp.sort_count is not None:
        got = ops.get("sort", 0)
        if got != exp.sort_count:
            v("sort-census",
              f"model prices {exp.sort_count} sort ops, program has {got}")
    if exp.collective_ceiling is not None:
        got = collective_bytes(hlo_text).total_bytes
        if got > exp.collective_ceiling:
            v("collective-bytes",
              f"{got:.0f} collective bytes exceed the "
              f"{exp.collective_ceiling:.0f} budget")
    return out


# ---------------------------------------------------------------------------
# Program builders (one compile per structure group)
# ---------------------------------------------------------------------------
def _make_coo(w) -> COO:
    rng = np.random.default_rng(0)
    n_edges = max(1, min(w.e - w.e // 4, w.e))
    dst, src = random_coo(rng, w.n, n_edges)
    return COO.from_arrays(dst, src, w.n, capacity=w.e)


def _lower_convert(case: Case) -> str:
    coo = _make_coo(case.workload)
    # repro: allow-raw-jit — AOT lowering probe; the compiled object is
    # discarded after its HLO text is read, nothing dispatches through it.
    return (jax.jit(lambda c: pipeline.convert(c, case.cfg))
            .lower(coo).compile().as_text())


def _lower_sample(case: Case) -> str:
    coo = _make_coo(case.workload)
    csc = pipeline.convert(coo, case.cfg)
    batch = jnp.arange(contracts.SAMPLE_BATCH, dtype=jnp.int32)
    # repro: allow-raw-jit — AOT lowering probe; the compiled object is
    # discarded after its HLO text is read, nothing dispatches through it.
    fn = jax.jit(pipeline.sample_subgraph, static_argnames=("fanouts",
                                                            "cfg"))
    return (fn.lower(csc, batch, fanouts=contracts.SAMPLE_FANOUTS,
                     key=jax.random.PRNGKey(0), cfg=case.cfg)
            .compile().as_text())


def _make_delta(w, d_cap: int):
    from repro.core.delta import EdgeDelta
    rng = np.random.default_rng(3)
    k = max(1, d_cap // 2)
    return EdgeDelta.from_arrays(
        rng.integers(0, w.n, k), rng.integers(0, w.n, k),
        rng.integers(0, w.n, k), rng.integers(0, w.n, k),
        n_nodes=w.n, capacity=d_cap)


def _lower_delta(case: Case) -> str:
    csc = pipeline.convert(_make_coo(case.workload), case.cfg)
    delta = _make_delta(case.workload, case.d_cap)
    # repro: allow-raw-jit — AOT lowering probe; the compiled object is
    # discarded after its HLO text is read, nothing dispatches through it.
    return (jax.jit(lambda c, d: pipeline.apply_delta(c, d, case.cfg,
                                                      mode="merge"))
            .lower(csc, delta).compile().as_text())


def _delta_cache_guard(cases: list[Case], progress=None) -> Report:
    """Recompile guard on the module-level delta-update dispatch: the
    second call with an identical (cfg, e_cap, delta bucket, out_cap) must
    hit the cache — the serve path's zero-recompile update stream depends
    on exactly this."""
    from repro.engine import service
    rep = Report()
    seen: set[tuple] = set()
    for case in cases:
        if case.structure in seen:
            continue
        seen.add(case.structure)
        rep.checks += 1
        if progress:
            progress(f"delta cache guard {case.label}")
        csc = pipeline.convert(_make_coo(case.workload), case.cfg)
        delta = _make_delta(case.workload, case.d_cap)
        service.apply_delta_jit(csc, delta, cfg=case.cfg)
        mid = service.apply_delta_jit._cache_size()
        service.apply_delta_jit(csc, delta, cfg=case.cfg)
        after = service.apply_delta_jit._cache_size()
        if after != mid:
            rep.violations.append(Violation(
                "delta_update", case.label, "cache-size",
                f"re-dispatching an already-seen (cfg, bucket) grew the "
                f"module-level jit cache {mid} → {after}"))
    return rep


def _lower_shard(case: Case) -> str:
    from repro.engine.shard import shard_convert
    mesh = make_mesh((case.n_dev,), ("data",))
    coo = _make_coo(case.workload)
    # repro: allow-raw-jit — AOT lowering probe; the compiled object is
    # discarded after its HLO text is read, nothing dispatches through it.
    return (jax.jit(lambda c: shard_convert(mesh, c, case.cfg))
            .lower(coo).compile().as_text())


# ---------------------------------------------------------------------------
# Reports
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Report:
    """Structured result of one checker run."""

    checks: int = 0
    groups: int = 0
    violations: list[Violation] = dataclasses.field(default_factory=list)
    skipped: list[str] = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations

    def merge(self, other: "Report") -> "Report":
        self.checks += other.checks
        self.groups += other.groups
        self.violations.extend(other.violations)
        self.skipped.extend(other.skipped)
        return self

    def to_json(self) -> dict:
        return {
            "checks": self.checks,
            "groups": self.groups,
            "ok": self.ok,
            "violations": [dataclasses.asdict(v) for v in self.violations],
            "skipped": self.skipped,
        }


def _check_grouped(cases: list[Case], lower, progress=None) -> Report:
    """Group cases by structure key, lower one representative per group,
    evaluate every member (+ its model self-consistency tie)."""
    groups: dict[tuple, list[Case]] = {}
    for c in cases:
        groups.setdefault(c.structure, []).append(c)
    rep = Report(groups=len(groups))
    for key, members in sorted(groups.items(), key=lambda kv: str(kv[0])):
        if progress:
            progress(f"lowering {members[0].contract} group {key} "
                     f"({len(members)} cases)")
        hlo = lower(members[0])
        for m in members:
            rep.checks += 1
            rep.violations.extend(evaluate_hlo(hlo, m))
            err = contracts.model_self_consistency(m.cfg, m.workload,
                                                   m.strategy)
            if err:
                rep.violations.append(Violation(
                    m.contract, m.label, "model-consistency", err))
    return rep


def _convert_cache_guard(cases: list[Case], progress=None) -> Report:
    """Recompile guard on the module-level convert dispatch: the second
    call with an identical (cfg, capacity bucket) must hit the cache."""
    from repro.engine import service
    rep = Report()
    seen: set[tuple] = set()
    for case in cases:
        if case.structure in seen:
            continue
        seen.add(case.structure)
        rep.checks += 1
        if progress:
            progress(f"cache guard {case.label}")
        coo = _make_coo(case.workload)
        service.convert_jit(coo, cfg=case.cfg)
        mid = service.convert_jit._cache_size()
        service.convert_jit(coo, cfg=case.cfg)
        after = service.convert_jit._cache_size()
        if after != mid:
            rep.violations.append(Violation(
                "convert", case.label, "cache-size",
                f"re-dispatching an already-seen (cfg, bucket) grew the "
                f"module-level jit cache {mid} → {after}"))
    return rep


# ---------------------------------------------------------------------------
# Per-contract entry points
# ---------------------------------------------------------------------------
def check_convert(grid: str = "full", progress=None) -> Report:
    cases = contracts.convert_cases(grid)
    rep = _check_grouped(cases, _lower_convert, progress)
    return rep.merge(_convert_cache_guard(cases, progress))


def check_sample(grid: str = "full", progress=None) -> Report:
    return _check_grouped(contracts.sample_cases(grid), _lower_sample,
                          progress)


def check_delta(grid: str = "full", progress=None) -> Report:
    cases = contracts.delta_cases(grid)
    rep = _check_grouped(cases, _lower_delta, progress)
    return rep.merge(_delta_cache_guard(cases, progress))


def check_shard(grid: str = "full", progress=None) -> Report:
    nd = jax.device_count()
    nd = 1 << (nd.bit_length() - 1)  # pow2 floor
    nd = min(nd, 8)
    if nd < 2:
        return Report(skipped=[
            "shard contract needs ≥ 2 devices (run the CLI with "
            "--devices N, which sets "
            "--xla_force_host_platform_device_count before jax imports)"])
    return _check_grouped(contracts.shard_cases(nd, grid), _lower_shard,
                          progress)


def check_serve(grid: str = "full", progress=None) -> Report:
    """Lower the serve decode step, check its HLO contract, then run two
    heterogeneous requests end-to-end and assert zero recompiles."""
    from repro.configs import get_config
    from repro.models.transformer import lm_init
    from repro.serve.engine import ServeEngine
    if progress:
        progress("building smoke serve engine")
    cfg = get_config("gemma2-9b", smoke=True)
    params = lm_init(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, n_slots=2, max_len=32, prompt_cap=8)
    case = Case(contract="serve", label="gemma2-9b smoke step",
                cfg=contracts.EngineConfig(), workload=contracts.Workload(
                    n=0, e=0), strategy="-", structure=("serve",),
                expect=contracts.serve_expectation())
    hlo = eng._step.lower(eng.params, eng.state).compile().as_text()
    rep = Report(groups=1, checks=1,
                 violations=evaluate_hlo(hlo, case))
    if progress:
        progress("running serve recompile guard (2 requests)")
    eng.submit([1, 2, 3], 3)
    eng.submit([4, 5], 2)
    eng.close_submissions()
    eng.run()
    rep.checks += 1
    size = eng.step_cache_size()
    if size != 1:
        rep.violations.append(Violation(
            "serve", case.label, "cache-size",
            f"step_cache_size()={size} after heterogeneous traffic "
            f"(expected exactly 1 compiled step)"))
    return rep


def _gnn_serve_engine(cfg):
    """A smoke GnnServeEngine on the contract workload's graph — shared by
    the lowering probe (per sort strategy) and the runtime cache guard."""
    from repro.configs.graphsage_reddit import smoke_config
    from repro.models.gnn import gnn_init
    from repro.serve.gnn import GnnServeEngine
    w = contracts._gnn_serve_workload()
    csc = pipeline.convert(_make_coo(w))
    gcfg = smoke_config()
    rng = np.random.default_rng(1)
    feats = jnp.asarray(rng.normal(size=(w.n, 8)).astype(np.float32))
    params = gnn_init(gcfg, jax.random.PRNGKey(0), d_in=8, n_classes=5)
    return GnnServeEngine(gcfg, params, csc, feats,
                          fanouts=contracts.GNN_SERVE_FANOUTS, n_slots=2,
                          seed_cap=contracts.GNN_SERVE_SEED_CAP, cfg=cfg)


def _lower_gnn_serve(case: Case) -> str:
    eng = _gnn_serve_engine(case.cfg)
    return eng._step.lower(eng.params, eng.state).compile().as_text()


def check_gnn_serve(grid: str = "full", progress=None) -> Report:
    """Lower the GNN serving step once per sort strategy and check the
    scatter-free / sort-census contract, then run two heterogeneous
    inference requests end-to-end and assert zero recompiles — the same
    two-leg shape as the LM serve contract."""
    cases = contracts.gnn_serve_cases(grid)
    rep = _check_grouped(cases, _lower_gnn_serve, progress)
    if progress:
        progress("running gnn_serve recompile guard (2 requests)")
    eng = _gnn_serve_engine(None)
    eng.submit([1, 2, 3])
    eng.submit([4, 5])
    eng.close_submissions()
    eng.run()
    rep.checks += 1
    size = eng.step_cache_size()
    if size != 1:
        rep.violations.append(Violation(
            "gnn_serve", cases[0].label, "cache-size",
            f"step_cache_size()={size} after heterogeneous traffic "
            f"(expected exactly 1 compiled step)"))
    return rep


CONTRACT_CHECKS = {
    "convert": check_convert,
    "sample": check_sample,
    "shard": check_shard,
    "serve": check_serve,
    "gnn_serve": check_gnn_serve,
    "delta_update": check_delta,
}


def check_all(grid: str = "full",
              parts: tuple[str, ...] = ("convert", "sample", "shard",
                                        "serve", "gnn_serve",
                                        "delta_update"),
              progress=None) -> Report:
    """Run every registered contract; ``grid="smoke"`` shrinks the convert
    sweep to the smoke configs/workload (used by the test suite — CI's
    static-analysis job runs the full grid)."""
    rep = Report()
    for part in parts:
        rep.merge(CONTRACT_CHECKS[part](grid, progress))
    return rep
