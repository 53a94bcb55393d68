"""Tests for the §Perf machinery: rank_in_sorted, sharded/local MoE,
scan-vs-unrolled layers, sorted-stream reshaping, and the HLO regression
guards for the gather-routed convert spine.

Only the property tests need ``hypothesis``; the rest of the module runs
without it (the old module-level importorskip silently skipped the perf
guards on machines without the dep).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; everything else still runs
    hypothesis = None

from repro.core.set_count import rank_in_sorted
from repro.models.moe import moe_apply, moe_apply_local, moe_init

jax.config.update("jax_platform_name", "cpu")


# -------------------------------------------------------- rank_in_sorted
if hypothesis is not None:
    @settings(max_examples=60, deadline=None)
    @given(st.lists(st.integers(-100, 100), min_size=1, max_size=200),
           st.lists(st.integers(-105, 105), min_size=1, max_size=64),
           st.sampled_from(["left", "right"]))
    def test_rank_in_sorted_matches_searchsorted(arr, qs, side):
        a = jnp.array(sorted(arr), jnp.int32)
        q = jnp.array(qs, jnp.int32)
        got = rank_in_sorted(a, q, side=side)
        want = np.searchsorted(np.asarray(a), np.asarray(q), side=side)
        np.testing.assert_array_equal(got, want)

    @settings(max_examples=40, deadline=None)
    @given(st.lists(st.integers(0, 7), min_size=1, max_size=200),
           st.sampled_from([2, 4, 8, 16]))
    def test_gather_router_is_permutation_inverse(keys, n_buckets):
        """The gather router's source map is exactly the inverse of the
        scatter formulation's destination map (prefix-sum + bucket base)."""
        from repro.core.set_partition import gather_sources_from_counts
        k = np.array([x % n_buckets for x in keys], np.int32)
        n = k.shape[0]
        onehot = (k[:, None] == np.arange(n_buckets)[None, :]).astype(np.int32)
        incl = np.cumsum(onehot, axis=0)
        hist = onehot.sum(axis=0)
        base = np.cumsum(hist) - hist
        src = np.asarray(gather_sources_from_counts(
            jnp.array(incl), jnp.array(base.astype(np.int32))))
        dest = (incl - onehot)[np.arange(n), k] + base[k]
        assert sorted(src.tolist()) == list(range(n))  # a permutation
        np.testing.assert_array_equal(src[dest], np.arange(n))
        np.testing.assert_array_equal(dest[src], np.arange(n))


def test_rank_in_sorted_2d_batched():
    a = jnp.array([0, 2, 4, 6], jnp.int32)
    q = jnp.array([[1, 5], [0, 7]], jnp.int32)
    got = rank_in_sorted(a, q)
    np.testing.assert_array_equal(got, [[1, 3], [0, 4]])


def test_rank_in_sorted_single_element_array():
    a = jnp.array([5], jnp.int32)
    q = jnp.array([4, 5, 6], jnp.int32)
    np.testing.assert_array_equal(rank_in_sorted(a, q, "left"), [0, 0, 1])
    np.testing.assert_array_equal(rank_in_sorted(a, q, "right"), [0, 1, 1])


# --------------------------------------------------- HLO regression guards
def _convert_hlo(cfg):
    from repro.core import COO, convert, random_coo
    rng = np.random.default_rng(0)
    dst, src = random_coo(rng, 200, 1500)
    coo = COO.from_arrays(dst, src, 200, capacity=2048)
    return jax.jit(lambda c: convert(c, cfg)).lower(coo).compile().as_text()


@pytest.mark.parametrize("mode", ["packed", "two_pass"])
def test_jitted_convert_hlo_has_no_scatter(mode):
    """The convert spine relocates exclusively through the gather router:
    a scatter op in the compiled program means a ``.at[].set`` crept back
    in (scatters serialize under GSPMD and lower poorly to Mosaic)."""
    from repro.core import EngineConfig
    from repro.launch.hlo_analysis import op_counts
    ops = op_counts(_convert_hlo(EngineConfig(w_upe=256, sort_mode=mode)))
    scatters = {k: v for k, v in ops.items() if "scatter" in k}
    assert not scatters, f"scatter ops in convert HLO ({mode}): {scatters}"
    assert any("gather" in k for k in ops), sorted(ops)


def test_packed_convert_runs_one_global_sort():
    """Packed-key convert must not contain the second sort pass: one
    chunk-sort + merge-tree instead of two. Counted on compiled sort ops
    (line-count comparisons are no longer meaningful now the fused
    pointer epilogue flattens each program differently). On xla_sort
    (what this workload resolves to) the two-pass scheme is one sort too,
    over the two (dst, src) columns where the packed key sorts one."""
    import re

    from repro.core import EngineConfig
    from repro.launch.hlo_analysis import op_counts
    packed = _convert_hlo(EngineConfig(w_upe=256, sort_mode="packed"))
    two = _convert_hlo(EngineConfig(w_upe=256, sort_mode="two_pass"))
    assert op_counts(packed).get("sort", 0) == 1
    assert op_counts(two).get("sort", 0) == 1

    def operands(text):
        line = next(x for x in text.splitlines() if " sort(" in x)
        return len(re.findall(r"%[\w.\-]+",
                              line.split(" sort(", 1)[1].split(")", 1)[0]))
    assert operands(packed) == 1
    assert operands(two) == 2


# The while-op budgets are no longer hand-derived here: the contract
# registry (repro.analysis.contracts) computes them from the cost model
# (costmodel.convert_while_count — pointer build + per-sort chunk scan +
# Σ k² rank searches over the merge_round_fan_ins rungs), and the tests
# below evaluate the compiled program against that registry exactly the
# way `python -m repro.analysis --hlo` does.
def _convert_contract_violations(cfg, w):
    from repro.analysis.checker import evaluate_hlo
    from repro.analysis.contracts import (Case, convert_expectation,
                                          convert_structure)
    from repro.core.costmodel import resolve_sort_strategy
    strategy = resolve_sort_strategy(cfg, w)
    case = Case(contract="convert", label=cfg.key, cfg=cfg, workload=w,
                strategy=strategy,
                structure=convert_structure(cfg, w, strategy),
                expect=convert_expectation(cfg, w, strategy))
    return evaluate_hlo(_convert_hlo(cfg), case)


def test_global_radix_convert_hlo_has_zero_merge_rounds():
    """The jitted global_radix convert contains ZERO merge rounds AND — at
    this 201-target scale, where ``pointer_reindex_strategy`` resolves the
    SCR epilogue fused — zero while ops outright: the pointer-build rank
    search unrolls statically, so the registry expectation prices exactly
    convert_while_count == 0. It stays scatter- and native-sort-free."""
    from repro.core import EngineConfig, Workload, pointer_reindex_strategy
    from repro.core.costmodel import convert_while_count
    cfg = EngineConfig(w_upe=256, sort_strategy="global_radix")
    w = Workload(n=200, e=2048)  # _convert_hlo's graph: 2048-capacity
    assert pointer_reindex_strategy(cfg, w) == "fused"
    assert convert_while_count(cfg, w, "global_radix") == 0
    # past the fused crossover (~375 queries/pass) the build stays a loop
    assert convert_while_count(
        cfg, Workload(n=70000, e=2048), "global_radix") == 1
    vios = _convert_contract_violations(cfg, w)
    assert not vios, "\n".join(str(v) for v in vios)


@pytest.mark.parametrize("fan_in", [2, 4])
def test_chunked_ladder_round_count_matches_costmodel(fan_in):
    """The compiled merge ladder has exactly the round structure
    ``costmodel.merge_round_count`` prices: the registry expectation's
    while census is pointer + per-sort chunk scan + Σ k² rank searches
    over the rungs of ``merge_round_fan_ins``."""
    from repro.core import EngineConfig, Workload, merge_round_count
    from repro.core.ordering import merge_round_fan_ins
    cfg = EngineConfig(w_upe=256, sort_strategy="chunked_merge",
                       merge_fan_in=fan_in)
    w = Workload(n=200, e=2048)  # _convert_hlo's graph: 2048-capacity
    fans = merge_round_fan_ins(2048, 256, fan_in)
    assert merge_round_count(cfg, w, "chunked_merge") == len(fans)
    assert merge_round_count(cfg, w, "global_radix") == 0
    vios = _convert_contract_violations(cfg, w)
    assert not vios, (fan_in, fans, [str(v) for v in vios])


def _bytes_accessed(jitted, *args) -> float:
    ca = jitted.lower(*args).compile().cost_analysis()
    return float(ca["bytes accessed"])


def test_packed_ordering_keys_only_moves_fewer_bytes():
    """The packed key IS the data — the keys-only Ordering (default) must
    route no edge-id payload through the chunk sorts and merge rounds.

    Two guards. (1) Compiled packed-mode convert accesses strictly fewer
    bytes than two-pass (one keys-only global sort vs two payload-carrying
    ones). (2) The keys-only *traced program* is strictly smaller than the
    payload-carrying A/B variant (``keys_only=False``): jaxpr-level DCE
    already strips the dead payload before XLA:CPU ever sees it, so
    compiled bytes can't separate the two — but the opaque Mosaic kernels
    (``radix_sort_chunks`` / ``fused_merge_rounds``) execute whatever they
    were handed, so the payload stream must be gone at trace level, not
    merely dead."""
    from functools import partial

    from repro.core import COO, EngineConfig, convert, random_coo
    from repro.core.ordering import edge_ordering
    rng = np.random.default_rng(0)
    dst, src = random_coo(rng, 200, 1500)
    coo = COO.from_arrays(dst, src, 200, capacity=2048)

    packed = _bytes_accessed(jax.jit(partial(
        convert, cfg=EngineConfig(w_upe=256, sort_mode="packed"))), coo)
    two_pass = _bytes_accessed(jax.jit(partial(
        convert, cfg=EngineConfig(w_upe=256, sort_mode="two_pass"))), coo)
    assert packed < two_pass, (packed, two_pass)

    def traced_size(keys_only):
        return len(str(jax.make_jaxpr(partial(
            edge_ordering, chunk=256, mode="packed",
            keys_only=keys_only))(coo)))

    assert traced_size(True) < traced_size(False)


def test_keys_only_sort_matches_payload_sort_keys():
    """The keys-only stack (jnp and Pallas chunk sorters, fused merge)
    returns exactly the key stream of the payload-carrying sort."""
    from repro.core.ordering import stable_sort_by_key
    from repro.kernels.ops import make_pallas_chunk_sort_fn, pallas_merge_fn
    rng = np.random.default_rng(1)
    keys = jnp.array(rng.integers(0, 500, 1024), jnp.int32)
    vals = jnp.arange(1024, dtype=jnp.int32)
    want, _ = stable_sort_by_key(keys, vals, 500, chunk=128)
    got, none = stable_sort_by_key(keys, None, 500, chunk=128)
    assert none is None
    np.testing.assert_array_equal(got, want)
    got_p, none_p = stable_sort_by_key(
        keys, None, 500, chunk=128,
        chunk_sort_fn=make_pallas_chunk_sort_fn(4),
        merge_fn=pallas_merge_fn)
    assert none_p is None
    np.testing.assert_array_equal(got_p, want)


# ------------------------------------------------- sorted-stream reshaping
def test_pointer_array_sorted_method_equals_scr_method():
    from repro.core.reshaping import build_pointer_array
    rng = np.random.default_rng(0)
    dst = np.sort(rng.integers(0, 50, 400)).astype(np.int32)
    a = build_pointer_array(jnp.array(dst), 50, method="sorted")
    b = build_pointer_array(jnp.array(dst), 50, method="scr", block=64)
    np.testing.assert_array_equal(a, b)


# ------------------------------------------------------------ MoE local
def test_moe_local_falls_back_off_mesh_and_matches():
    """Without a mesh, moe_apply_local == moe_apply exactly."""
    p = moe_init(jax.random.PRNGKey(0), 16, 32, 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 16))
    y1, a1 = moe_apply(p, x, top_k=2)
    y2, a2 = moe_apply_local(p, x, top_k=2)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5)


def test_moe_sharded_dispatch_matches_global_when_no_drops():
    """Per-shard capacity groups == global dispatch when capacity is ample
    (run under 4 virtual devices in a subprocess)."""
    import os
    import subprocess
    import sys
    import textwrap
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        from repro.models.moe import moe_apply, moe_apply_local, moe_init
        p = moe_init(jax.random.PRNGKey(0), 16, 32, 4)
        x = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
        y_ref, _ = moe_apply(p, x, top_k=2, capacity_factor=8.0)
        with jax.set_mesh(mesh):
            y, _ = jax.jit(lambda p, x: moe_apply_local(
                p, x, top_k=2, capacity_factor=8.0))(p, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-5)
        print("OK")
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ,
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
             "PYTHONPATH": os.path.join(root, "src")},
        cwd=root, timeout=600)
    assert "OK" in r.stdout, r.stdout + r.stderr


# --------------------------------------------------- scan vs unrolled
def test_unrolled_layers_match_scan():
    from repro.configs import get_config
    from repro.models.transformer import lm_forward, lm_init
    cfg_s = get_config("codeqwen1.5-7b", smoke=True)
    cfg_u = dataclasses.replace(cfg_s, scan_layers=False)
    # same per-layer keys requires same init path — init separately and
    # copy weights across structures
    ps = lm_init(cfg_s, jax.random.PRNGKey(0))
    pu = lm_init(cfg_u, jax.random.PRNGKey(0))
    n_layers = cfg_s.n_layers
    pu["blocks_list"] = [
        jax.tree.map(lambda s: s[i], ps["blocks"]) for i in range(n_layers)]
    pu["embed"] = ps["embed"]
    pu["ln_final"] = ps["ln_final"]
    if "lm_head" in ps:
        pu["lm_head"] = ps["lm_head"]
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0,
                                cfg_s.vocab)
    l1, _ = lm_forward(cfg_s, ps, tokens)
    l2, _ = lm_forward(cfg_u, pu, tokens)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=2e-5,
                               atol=2e-5)
