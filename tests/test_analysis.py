"""repro.analysis: lint-rule fixtures (positive / suppressed / clean),
contract-violation detection on deliberately broken programs, and the
full-registry smoke sweep asserting the shipped tree is violation-free."""
import textwrap

import pytest

from repro.analysis.lint import RULES, lint_source, lint_tree

jax = pytest.importorskip("jax")
jax.config.update("jax_platform_name", "cpu")


def _rules(src: str, path: str = "core/ordering.py") -> list[str]:
    return [v.rule for v in lint_source(textwrap.dedent(src), path)]


# ------------------------------------------------------------- lint: raw-jit
def test_raw_jit_flags_call_in_function():
    src = """
        import jax
        def make(fn):
            return jax.jit(fn)
    """
    assert _rules(src) == ["raw-jit"]


def test_raw_jit_flags_from_import_alias_and_nested_decorator():
    src = """
        from jax import jit as J
        def factory():
            @J
            def step(x):
                return x
            return step
    """
    assert _rules(src) == ["raw-jit"]


def test_raw_jit_allows_module_level_cache_and_partial_decorator():
    src = """
        import functools
        import jax
        convert_jit = jax.jit(convert, static_argnames=("cfg",))

        @jax.jit
        def top(x):
            return x

        @functools.partial(jax.jit, static_argnames=("n",))
        def top2(x, n):
            return x
    """
    assert _rules(src) == []


def test_raw_jit_suppressed_with_reason():
    src = """
        import jax
        def probe(fn, x):
            # repro: allow-raw-jit — one-shot AOT lowering probe
            return jax.jit(fn).lower(x).compile()
    """
    assert _rules(src) == []


def test_bare_suppression_is_itself_a_violation():
    src = """
        import jax
        def probe(fn):
            return jax.jit(fn)  # repro: allow-raw-jit
    """
    assert _rules(src) == ["bare-suppression"]


def test_suppression_for_unknown_rule_is_flagged():
    src = "x = 1  # repro: allow-nonsense-rule because reasons\n"
    assert _rules(src) == ["bare-suppression"]


# ------------------------------------------------------- lint: scatter-write
def test_scatter_write_flagged_in_spine_module_only():
    src = """
        import jax.numpy as jnp
        def relocate(buf, dest, vals):
            return buf.at[dest].set(vals)
    """
    assert _rules(src, "core/ordering.py") == ["scatter-write"]
    assert _rules(src, "models/gnn.py") == []


def test_scatter_write_suppressed_with_reason():
    src = """
        import jax.numpy as jnp
        def baseline(h, d):
            # repro: allow-scatter-write — serial baseline, measured only
            return h.at[d].add(1)
    """
    assert _rules(src, "core/reshaping.py") == []


# ----------------------------------------------------------- lint: traced-if
def test_traced_if_flags_jnp_condition():
    src = """
        import jax.numpy as jnp
        def f(x):
            if jnp.any(x > 0):
                return x
            return -x
    """
    assert _rules(src) == ["traced-if"]


def test_traced_if_flags_lax_while_and_allows_static_branch():
    src = """
        from jax import lax
        def f(x, cfg):
            while lax.lt(x, 3):
                x = x + 1
            if cfg.fused:
                return x
            return -x
    """
    assert _rules(src) == ["traced-if"]


# --------------------------------------------------- lint: host-numpy-in-jit
def test_host_numpy_in_jit_flags_compute_but_not_metadata():
    src = """
        import jax
        import numpy as np
        @jax.jit
        def f(x):
            y = np.cumsum(x)
            return y.astype(np.int32) + np.iinfo(np.int32).max
    """
    assert _rules(src) == ["host-numpy-in-jit"]


def test_host_numpy_outside_jit_is_clean():
    src = """
        import numpy as np
        def reference(x):
            return np.cumsum(x)
    """
    assert _rules(src) == []


# ----------------------------------------------------- lint: mutable-default
def test_mutable_default_flagged_and_none_clean():
    bad = """
        def enqueue(item, queue=[]):
            queue.append(item)
    """
    good = """
        def enqueue(item, queue=None):
            queue = queue or []
    """
    assert _rules(bad) == ["mutable-default"]
    assert _rules(good) == []


def test_rule_catalog_is_complete():
    """Every rule the linter can emit is documented in RULES (docs and the
    ANALYSIS.md catalog are generated from the same registry)."""
    for rule_id in ("raw-jit", "scatter-write", "traced-if",
                    "host-numpy-in-jit", "mutable-default",
                    "bare-suppression"):
        assert rule_id in RULES
        assert RULES[rule_id].history  # each rule names its bug


# ------------------------------------------------------- shipped tree sweep
def test_shipped_tree_is_lint_clean():
    violations = lint_tree()
    assert not violations, "\n".join(str(v) for v in violations)


# ------------------------------------------------------- contract violations
def _toy_case(expect):
    from repro.analysis.contracts import Case, Expectation
    from repro.core.costmodel import EngineConfig, Workload
    return Case(contract="toy", label="toy", cfg=EngineConfig(),
                workload=Workload(n=8, e=8), structure=("toy",),
                expect=expect)


def test_checker_reports_pinned_scatter():
    """Deliberately break the no-scatter invariant. A scatter op in
    synthetic program text is reported; and a ``.at[].set`` pinned into a
    jitted function is caught in its COMPILED program too: XLA:CPU keeps
    it as a ``scatter`` (inside a ``wrapped_scatter`` fusion on JAX 0.9),
    so the no-scatter invariant reports it there, not only in synthetic
    text."""
    import jax.numpy as jnp

    from repro.analysis.checker import evaluate_hlo
    from repro.analysis.contracts import Expectation

    synthetic = ("ENTRY %m (a: s32[16]) -> s32[16] {\n"
                 "  ROOT %s = s32[16]{0} scatter(%a, %i, %u), "
                 "to_apply=%assign\n}\n")
    vios = evaluate_hlo(synthetic, _toy_case(Expectation(
        forbidden_ops=("scatter",))))
    assert [v.invariant for v in vios] == ["no-scatter"]

    def scatter_convert(dest, vals):
        return jnp.zeros((16,), jnp.int32).at[dest].set(vals)

    hlo = (jax.jit(scatter_convert)
           .lower(jnp.arange(16), jnp.arange(16))
           .compile().as_text())
    compiled = evaluate_hlo(hlo, _toy_case(Expectation(
        forbidden_ops=("scatter",))))
    assert [v.invariant for v in compiled] == ["no-scatter"], hlo


def test_checker_reports_while_census_mismatch():
    import jax.numpy as jnp
    from jax import lax

    from repro.analysis.checker import evaluate_hlo
    from repro.analysis.contracts import Expectation

    def looped(x):
        return lax.fori_loop(0, 4, lambda i, a: a + i, x)

    hlo = jax.jit(looped).lower(jnp.int32(0)).compile().as_text()
    ok = evaluate_hlo(hlo, _toy_case(Expectation(while_count=1)))
    assert not ok
    bad = evaluate_hlo(hlo, _toy_case(Expectation(while_count=3)))
    assert [v.invariant for v in bad] == ["while-census"]


def test_checker_reports_collective_ceiling_breach():
    from repro.analysis.checker import evaluate_hlo
    from repro.analysis.contracts import Expectation
    hlo = ("ENTRY %m (a: f32[64]) -> f32[64] {\n"
           "  ROOT %r = f32[64]{0} all-reduce(%a), channel_id=1, "
           "replica_groups={{0,1}}\n}\n")
    bad = evaluate_hlo(hlo, _toy_case(Expectation(collective_ceiling=8.0)))
    assert [v.invariant for v in bad] == ["collective-bytes"]
    ok = evaluate_hlo(hlo, _toy_case(Expectation(
        collective_ceiling=1e9)))
    assert not ok


def test_model_self_consistency_ties_census_to_merge_round_count():
    from repro.analysis.contracts import (CONVERT_WORKLOADS,
                                          model_self_consistency)
    from repro.core.costmodel import EngineConfig
    for w in CONVERT_WORKLOADS:
        for cfg in (EngineConfig(), EngineConfig(sort_mode="two_pass"),
                    EngineConfig(reindex_strategy="unfused")):
            assert model_self_consistency(cfg, w) is None


# --------------------------------------------------- full-registry (smoke)
def test_registry_smoke_sweep_is_violation_free():
    """The shipped tree satisfies every contract on the smoke grid (CI's
    static-analysis job runs the full 81-config grid; this keeps tier-1
    runtime bounded while still lowering both key schemes' programs)."""
    from repro.analysis import checker
    rep = checker.check_all(grid="smoke", parts=("convert", "sample"))
    assert rep.checks > 0
    assert rep.ok, "\n".join(str(v) for v in rep.violations)


def test_convert_structure_dedup_collapses_library():
    """The 81-config library × the workload grid dedupes to a handful of
    lowered programs: the program depends on shapes, the key scheme and
    the pointer epilogue, never on UPE or SCR geometry — the observation
    that makes the full sweep compile a few programs instead of ~340."""
    from repro.analysis.contracts import CONVERT_WORKLOADS, convert_cases
    cases = convert_cases("full")
    groups = {c.structure for c in cases}
    assert len(cases) >= 81 * len(CONVERT_WORKLOADS)  # library × workloads
    assert len(groups) < len(cases) / 10


def test_registry_summary_shape():
    from repro.analysis.contracts import registry_summary
    s = registry_summary()
    assert s["library_size"] == 81
    assert s["convert_cases"] >= 81 * 4
    assert set(s["contracts"]) == {"convert", "sample", "shard", "serve",
                                   "gnn_serve", "delta_update"}
