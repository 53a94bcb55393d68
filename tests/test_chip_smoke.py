"""chip_smoke.py rehearsed on the CPU at tiny sizes: every phase function
runs its own checks (the four-chip phase under 4 virtual devices), and
the script refuses any platform but TPU."""
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import ROOT, run_under_devices

sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402
from repro.configs.graphsage_reddit import smoke_config  # noqa: E402


@pytest.fixture(scope="module")
def graph():
    g, report = chip_smoke.phase_convert(300, 3000, d_feat=12, n_classes=5,
                                         seed=0)
    assert report["capacity"] == 4096
    return g


def test_host_csc_is_lexsort_order():
    rng = np.random.default_rng(0)
    dst = rng.integers(0, 50, 400).astype(np.int32)
    src = rng.integers(0, 50, 400).astype(np.int32)
    ptr, idx = chip_smoke.host_csc(dst, src, 50)
    order = np.lexsort((src, dst))
    np.testing.assert_array_equal(idx, src[order])
    np.testing.assert_array_equal(np.diff(ptr), np.bincount(dst, minlength=50))


def test_convert_phase(graph):
    assert int(graph.csc.n_edges) == 3000


def test_sweep_phase():
    report = chip_smoke.phase_sweep(300, 4096, seed=1, fanouts=(3, 2),
                                    n_seeds=8, delta_cap=16)
    assert "bit-identical" in report["result"]


def test_serve_phase(graph):
    report = chip_smoke.phase_serve(graph, seed=2, n_requests=6, n_after=2,
                                    n_slots=2, seed_cap=4, delta_cap=8,
                                    gcfg=smoke_config())
    assert report["logits_vs_cpu"]["max_abs_err"] == 0.0  # same backend


def test_gat_phase():
    report = chip_smoke.phase_gat(400, 5000, seed=4, d_feat=12, width=8,
                                  n_classes=5, sizes=(16, 256, 4, 128))
    assert report["counters"]["chunks"] >= 25
    assert max(report["max_abs_err"].values()) <= 1e-5 * report[
        "logit_scale"]  # the CPU is both sides here


def test_kernels_phase():
    report = chip_smoke.phase_kernels(3, n_elems=4096, n_targets=512,
                                      sweep=(300, 4096), agg=(256, 512, 128),
                                      flash=(2, 512, 128))
    assert set(report["kernels"]) >= {"set_count_less", "filter_tree_lookup",
                                      "segment_sum_sorted",
                                      "flash_attention_fwd"}


def test_four_chips_phase_on_virtual_devices():
    out = run_under_devices(f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        import chip_smoke
        r = chip_smoke.phase_four_chips(500, 6000, seed=0, fanouts=(4, 3))
        print("SPANS", r["result"])
    """, n=4)
    assert "bit-identical" in out


def test_main_refuses_a_platform_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_follows_env_else_repo_dir(monkeypatch, tmp_path):
    import jax
    from repro.launch import cache
    old = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == old  # JAX reads the env
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert cache.enable_compile_cache() == os.path.join(ROOT,
                                                            ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def test_script_exits_nonzero_on_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
