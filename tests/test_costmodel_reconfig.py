"""Cost model (Table I) + reconfiguration controller properties."""
import jax
import jax.numpy as jnp
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import (EngineConfig, Workload, best_config,
                        bitstream_library, estimate_seconds)
from repro.core.reconfig import DynPre, autopre, statpre

jax.config.update("jax_platform_name", "cpu")


def test_library_generation_rule():
    """Paper: start wide, iteratively halve width / double count."""
    lib = bitstream_library()
    widths = sorted({c.w_upe for c in lib})
    for a, b in zip(widths, widths[1:]):
        assert b == 2 * a
    assert all(c.w_upe * c.n_upe == lib[0].w_upe * lib[0].n_upe
               for c in lib)  # constant resource product


@settings(max_examples=30, deadline=None)
@given(st.integers(10, 10**6), st.integers(100, 10**8))
def test_cost_positive_and_monotone_in_edges(n, e):
    cfg = EngineConfig()
    w1 = Workload(n=n, e=e)
    w2 = Workload(n=n, e=e * 2)
    c1 = estimate_seconds(cfg, w1)
    c2 = estimate_seconds(cfg, w2)
    assert all(v >= 0 for v in c1.values())
    assert c2["ordering"] >= c1["ordering"]
    assert c2["reshaping"] >= c1["reshaping"]


def test_selection_cost_scales_with_node_explosion():
    """Paper Fig. 25: sampling cost ~ b·k^(l+1)."""
    cfg = EngineConfig()
    shallow = estimate_seconds(cfg, Workload(n=10**5, e=10**6, l=1, k=10))
    deep = estimate_seconds(cfg, Workload(n=10**5, e=10**6, l=3, k=10))
    assert deep["selecting"] > 50 * shallow["selecting"]


def test_best_config_prefers_wide_scr_for_edge_heavy():
    """Edge-dominated reshaping wants wide SCR slots (paper Fig. 23a)."""
    lib = bitstream_library()
    edge_heavy = best_config(Workload(n=1000, e=10**8), lib)
    node_heavy = best_config(Workload(n=10**7, e=10**7), lib)
    assert edge_heavy.w_scr >= node_heavy.w_scr


def test_dynpre_reconfigures_on_diverse_graphs():
    from repro.core import COO
    dyn = DynPre(fanouts=(10, 10))
    small = COO(dst=jnp.zeros(1024, jnp.int32), src=jnp.zeros(1024, jnp.int32),
                n_edges=jnp.int32(1000), n_nodes=500)
    w_small = dyn.profile(small, batch_size=64)
    d1 = dyn.decide(w_small)
    assert d1.reconfigure  # first graph always configures
    dyn.engine = object()  # pretend engine built with d1.config
    dyn.engine = type("E", (), {"cfg": d1.config})()
    big = COO(dst=jnp.zeros(1024, jnp.int32), src=jnp.zeros(1024, jnp.int32),
              n_edges=jnp.int32(10**8), n_nodes=3 * 10**6)
    d2 = dyn.decide(dyn.profile(big, batch_size=1024))
    # a 5-orders-of-magnitude workload change must trigger reconfiguration
    assert d2.config != d1.config


def test_statpre_autopre_lane_split():
    """AutoPre statically halves UPE lanes vs StatPre (paper §VI)."""
    s = statpre((10, 10))
    a = autopre((10, 10))
    assert a.cfg.n_upe * 2 == s.cfg.n_upe


def test_cost_model_ranks_match_simulated_hardware():
    """The model must rank configs correctly for its OWN cycle semantics
    (sanity: more lanes → fewer cycles; wider SCR → fewer edge cycles).
    Lane count is a UPE knob of the Table-I Ordering term; the native
    sort the engine runs ignores it."""
    from repro.core.costmodel import ordering_cycles, reshaping_cycles
    w = Workload(n=10**5, e=10**7)
    c_few = EngineConfig(n_upe=4)
    c_many = EngineConfig(n_upe=64)
    assert ordering_cycles(c_many, w) < ordering_cycles(c_few, w)
    assert (reshaping_cycles(EngineConfig(w_scr=8192), w)
            < reshaping_cycles(EngineConfig(w_scr=512), w))
    assert (estimate_seconds(c_many, w)["ordering"]
            == estimate_seconds(c_few, w)["ordering"])
