"""Mesh-sharded preprocessing (repro.engine.shard) under 8 virtual devices.

Subprocess pattern (device count must be set before jax initializes; the
main test process keeps 1 device) — shared harness in tests/conftest.py.
"""
from conftest import run_under_devices


def test_shard_preprocess_bit_identical_to_single_device():
    """Acceptance: shard_preprocess == pipeline.preprocess exactly
    (ptr/idx/order) for two graph sizes × two EngineConfigs."""
    out = run_under_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        from repro.core import COO, EngineConfig, preprocess, random_coo
        from repro.engine.shard import jit_shard_preprocess
        rng = np.random.default_rng(0)
        cfgs = [EngineConfig(),
                EngineConfig(selection="keysort"),
                EngineConfig(sort_mode="two_pass")]
        for (n, e, cap) in [(200, 2000, 2048), (500, 6000, 8192)]:
            dst, src = random_coo(rng, n, e)
            coo = COO.from_arrays(dst, src, n, capacity=cap)
            bn = jnp.arange(16, dtype=jnp.int32)
            key = jax.random.PRNGKey(0)
            for cfg in cfgs:
                ref = preprocess(coo, bn, (4, 3), key, cfg)
                with jax.set_mesh(mesh):
                    got = jit_shard_preprocess(mesh)(
                        coo, bn, fanouts=(4, 3), key=key, cfg=cfg)
                tag = f"{n}/{e}/{cfg.key}"
                np.testing.assert_array_equal(
                    np.asarray(got.order), np.asarray(ref.order), tag)
                np.testing.assert_array_equal(
                    np.asarray(got.csc.ptr), np.asarray(ref.csc.ptr), tag)
                np.testing.assert_array_equal(
                    np.asarray(got.csc.idx), np.asarray(ref.csc.idx), tag)
                assert int(got.n_sub_nodes) == int(ref.n_sub_nodes)
        print("OK")
    """)
    assert "OK" in out


def test_shard_convert_matches_single_device():
    """Ordering + Reshaping alone: sharded CSC == single-device CSC."""
    out = run_under_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        from repro.core import COO, EngineConfig, convert, random_coo
        from repro.engine.shard import shard_convert
        rng = np.random.default_rng(3)
        dst, src = random_coo(rng, 300, 3000)
        coo = COO.from_arrays(dst, src, 300, capacity=4096)
        cfg = EngineConfig()
        ref = convert(coo, cfg)
        with jax.set_mesh(mesh):
            got = jax.jit(lambda c: shard_convert(mesh, c, cfg))(coo)
        np.testing.assert_array_equal(np.asarray(got.ptr),
                                      np.asarray(ref.ptr))
        np.testing.assert_array_equal(np.asarray(got.idx),
                                      np.asarray(ref.idx))
        print("OK")
    """)
    assert "OK" in out


def test_shard_preprocess_on_2d_mesh_dp_axes_only():
    """On a (data, model) mesh the engine shards over dp axes only and
    still matches the single-device pipeline exactly."""
    out = run_under_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        from repro.core import COO, EngineConfig, preprocess, random_coo
        from repro.engine.shard import jit_shard_preprocess
        rng = np.random.default_rng(7)
        dst, src = random_coo(rng, 200, 1500)
        coo = COO.from_arrays(dst, src, 200, capacity=2048)
        bn = jnp.arange(8, dtype=jnp.int32)
        key = jax.random.PRNGKey(1)
        cfg = EngineConfig()
        ref = preprocess(coo, bn, (3, 2), key, cfg)
        with jax.set_mesh(mesh):
            got = jit_shard_preprocess(mesh)(
                coo, bn, fanouts=(3, 2), key=key, cfg=cfg)
        np.testing.assert_array_equal(np.asarray(got.order),
                                      np.asarray(ref.order))
        np.testing.assert_array_equal(np.asarray(got.csc.ptr),
                                      np.asarray(ref.csc.ptr))
        print("OK")
    """)
    assert "OK" in out


def test_shard_sort_falls_back_on_non_pow2_device_count():
    """A 6-device dp mesh can't host the binary merge tree — the sorter
    must fall back to the single-device path, not crash at trace time."""
    out = run_under_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((6,), ("data",))
        from repro.core import COO, EngineConfig, preprocess, random_coo
        from repro.engine.shard import shard_preprocess
        rng = np.random.default_rng(11)
        dst, src = random_coo(rng, 120, 1000)
        coo = COO.from_arrays(dst, src, 120, capacity=2048)
        bn = jnp.arange(8, dtype=jnp.int32)
        key = jax.random.PRNGKey(2)
        cfg = EngineConfig()
        with jax.set_mesh(mesh):
            got = jax.jit(lambda c, b, k: shard_preprocess(
                mesh, c, b, (3, 2), k, cfg))(coo, bn, key)
        ref = preprocess(coo, bn, (3, 2), key, cfg)
        np.testing.assert_array_equal(np.asarray(got.order),
                                      np.asarray(ref.order))
        np.testing.assert_array_equal(np.asarray(got.csc.ptr),
                                      np.asarray(ref.csc.ptr))
        print("OK")
    """, n=6)
    assert "OK" in out


def test_preprocess_cells_construct_with_shard_route():
    """launch.steps.preprocess_cells routes through engine.shard and the
    specs/shardings trees stay structurally consistent."""
    out = run_under_devices("""
        import jax
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        from repro.launch.steps import preprocess_cells
        cells = preprocess_cells(mesh)
        keys = [c.key for c in cells]
        assert "autognn-convert__reddit" in keys, keys
        assert "autognn-preprocess__reddit-e2e" in keys, keys
        for c in cells:
            ta = jax.tree.structure(c.args)
            ts = jax.tree.structure(c.in_shardings)
            assert ta == ts, (c.key, ta, ts)
        print("OK", len(cells))
    """)
    assert "OK" in out


def test_shard_convert_strategy_equality():
    """The mesh-sharded convert (per-device native sorts + cross-device
    merge rounds) is bit-identical to the single-device one under every
    key scheme: the packed key, and the pair as two stable passes."""
    out = run_under_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        from repro.core import COO, EngineConfig, convert, random_coo
        from repro.engine.shard import shard_convert
        rng = np.random.default_rng(13)
        dst, src = random_coo(rng, 300, 3000)
        coo = COO.from_arrays(dst, src, 300, capacity=4096)
        ref = convert(coo, EngineConfig())
        for mode in ("auto", "packed", "two_pass"):
            cfg = EngineConfig(sort_mode=mode)
            with jax.set_mesh(mesh):
                got = jax.jit(lambda c, cfg=cfg: shard_convert(
                    mesh, c, cfg))(coo)
            tag = mode
            np.testing.assert_array_equal(np.asarray(got.ptr),
                                          np.asarray(ref.ptr), tag)
            np.testing.assert_array_equal(np.asarray(got.idx),
                                          np.asarray(ref.idx), tag)
        print("OK")
    """)
    assert "OK" in out


def test_shard_preprocess_reindex_strategy_equality():
    """Acceptance (PR 7): the mesh-sharded e2e pipeline is bit-identical
    to the single-device one under every reindex_strategy — the fused SCR
    epilogue (unrolled pointer build + rename gathers) composes with the
    shard_map'd Ordering without divergence."""
    out = run_under_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        from repro.core import COO, EngineConfig, preprocess, random_coo
        from repro.engine.shard import jit_shard_preprocess
        rng = np.random.default_rng(17)
        dst, src = random_coo(rng, 300, 3000)
        coo = COO.from_arrays(dst, src, 300, capacity=4096)
        bn = jnp.arange(16, dtype=jnp.int32)
        key = jax.random.PRNGKey(4)
        ref = preprocess(coo, bn, (4, 3), key, EngineConfig())
        for strat in ("fused", "unfused", "auto"):
            cfg = EngineConfig(reindex_strategy=strat)
            with jax.set_mesh(mesh):
                got = jit_shard_preprocess(mesh)(
                    coo, bn, fanouts=(4, 3), key=key, cfg=cfg)
            tag = strat
            np.testing.assert_array_equal(np.asarray(got.order),
                                          np.asarray(ref.order), tag)
            np.testing.assert_array_equal(np.asarray(got.csc.ptr),
                                          np.asarray(ref.csc.ptr), tag)
            np.testing.assert_array_equal(np.asarray(got.csc.idx),
                                          np.asarray(ref.csc.idx), tag)
            assert int(got.n_sub_nodes) == int(ref.n_sub_nodes), tag
        print("OK")
    """)
    assert "OK" in out
