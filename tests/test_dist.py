"""Distribution tests under 8 virtual devices (subprocess: device count must
be set before jax initializes, and the main test process must keep 1 —
shared harness in tests/conftest.py)."""
from conftest import run_under_devices


def test_sharded_decode_matches_unsharded():
    out = run_under_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        from repro.dist.collectives import sharded_decode_attention
        from repro.models.attention import decode_attention
        b, h, hkv, s, dh = 2, 4, 2, 64, 16
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(k1, (b, h, 1, dh))
        kc = jax.random.normal(k2, (b, hkv, s, dh))
        vc = jax.random.normal(k3, (b, hkv, s, dh))
        clen = jnp.full((b,), 48, jnp.int32)
        want = decode_attention(q, kc, vc, clen)
        with jax.set_mesh(mesh):
            got = jax.jit(lambda q, kc, vc, c: sharded_decode_attention(
                mesh, q, kc, vc, c))(q, kc, vc, clen)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        print("OK")
    """)
    assert "OK" in out


def test_seq_sharded_decode_matches_unsharded():
    """Flash-decoding: sequence-sharded cache, LSE-combined across shards."""
    out = run_under_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        from repro.dist.collectives import sharded_decode_attention_seq
        from repro.models.attention import decode_attention
        b, h, hkv, s, dh = 2, 4, 2, 128, 16
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(k1, (b, h, 1, dh))
        kc = jax.random.normal(k2, (b, hkv, s, dh))
        vc = jax.random.normal(k3, (b, hkv, s, dh))
        clen = jnp.array([100, 17], jnp.int32)  # straddles shard boundaries
        want = decode_attention(q, kc, vc, clen)
        with jax.set_mesh(mesh):
            got = jax.jit(lambda q, kc, vc, c: sharded_decode_attention_seq(
                mesh, q, kc, vc, c))(q, kc, vc, clen)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        print("OK")
    """)
    assert "OK" in out


def test_seq_sharded_decode_heads_on_model_axis_with_int8():
    """(data, model) mesh: KV heads stay sharded over 'model' (no cache
    replication) and int8 scales dequantize per shard — output still
    matches the dense reference."""
    out = run_under_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        from repro.dist.collectives import sharded_decode_attention_seq
        from repro.models.attention import decode_attention, quantize_kv
        b, h, hkv, s, dh = 2, 8, 4, 128, 16
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(k1, (b, h, 1, dh))
        kc = jax.random.normal(k2, (b, hkv, s, dh))
        vc = jax.random.normal(k3, (b, hkv, s, dh))
        kq, ks = quantize_kv(kc)
        vq, vs = quantize_kv(vc)
        clen = jnp.array([100, 17], jnp.int32)
        want = decode_attention(q, kq, vq, clen, k_scale=ks, v_scale=vs)
        with jax.set_mesh(mesh):
            got = jax.jit(lambda *a: sharded_decode_attention_seq(
                mesh, *a[:4], k_scale=a[4], v_scale=a[5]))(
                q, kq, vq, clen, ks, vs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        print("OK")
    """)
    assert "OK" in out


def test_long_context_decode_step_with_seq_sharded_attn():
    """The long_500k wiring: lm_decode_step with the sequence-sharded
    LSE-combine attn_fn matches the dense decode step exactly (gemma2-class
    local/global config, B=1, cache sharded over 8 devices)."""
    out = run_under_devices("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        from repro.configs import get_config
        from repro.dist.collectives import seq_sharded_decode_attn_fn
        from repro.dist.sharding import lm_cache_shardings
        from repro.models.transformer import (lm_decode_step, lm_init,
                                              make_cache)
        cfg = get_config("gemma2-9b", smoke=True).padded(1)
        params = lm_init(cfg, jax.random.PRNGKey(0))
        cache = make_cache(cfg, 1, 128)
        # a mid-stream position: the valid prefix straddles shard boundaries
        tok = jnp.array([[7]], jnp.int32)
        pos = jnp.int32(77)
        want_tok, want_cache = jax.jit(
            lambda p, c, t, q: lm_decode_step(cfg, p, c, t, q)
        )(params, cache, tok, pos)
        attn = seq_sharded_decode_attn_fn(mesh)
        with jax.set_mesh(mesh):
            c_sh = lm_cache_shardings(mesh, cache, seq_sharded=True)
            cache_s = jax.device_put(cache, c_sh)
            got_tok, got_cache = jax.jit(
                lambda p, c, t, q: lm_decode_step(cfg, p, c, t, q,
                                                  attn_fn=attn)
            )(params, cache_s, tok, pos)
        np.testing.assert_array_equal(np.asarray(got_tok),
                                      np.asarray(want_tok))
        for a, b in zip(jax.tree.leaves(got_cache),
                        jax.tree.leaves(want_cache)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2e-5, atol=2e-5)
        print("OK")
    """)
    assert "OK" in out


def test_long500k_cell_wires_seq_sharded_collective():
    """build_cell(gemma2-9b, long_500k) must construct the sequence-sharded
    decode cell (LSE-combine collective) with consistent spec trees."""
    out = run_under_devices("""
        import jax
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        from repro.launch.steps import build_cell
        cell = build_cell("gemma2-9b", "long_500k", mesh)
        assert not cell.skipped, cell.skipped
        assert "sequence-sharded" in cell.note, cell.note
        assert "LSE-combined" in cell.note, cell.note
        ta = jax.tree.structure(cell.args)
        ts = jax.tree.structure(cell.in_shardings)
        assert ta == ts, (ta, ts)
        print("OK")
    """)
    assert "OK" in out


def test_lm_train_cell_runs_on_tiny_mesh():
    """Actually EXECUTE one sharded LM train step (not just compile)."""
    out = run_under_devices("""
        import jax, jax.numpy as jnp, numpy as np
        import dataclasses
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        from repro.configs import get_config
        from repro.dist.sharding import lm_param_shardings
        from repro.models.transformer import lm_init, lm_loss
        from repro.train.optim import AdamWConfig, adamw_init, adamw_update
        from jax.sharding import NamedSharding, PartitionSpec as P
        cfg = get_config("granite-moe-1b-a400m", smoke=True)
        cfg = dataclasses.replace(cfg, n_layers=2).padded(2)
        params = lm_init(cfg, jax.random.PRNGKey(0))
        with jax.set_mesh(mesh):
            p_sh = lm_param_shardings(mesh, params, fsdp=True,
                                      n_experts=cfg.moe_experts)
            params = jax.device_put(params, p_sh)
            opt = adamw_init(params)
            tokens = jax.device_put(
                jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                   cfg.vocab),
                NamedSharding(mesh, P("data", None)))
            ocfg = AdamWConfig()
            @jax.jit
            def step(p, o, t):
                loss, g = jax.value_and_grad(
                    lambda pp: lm_loss(cfg, pp, t))(p)
                return adamw_update(ocfg, g, o, p) + (loss,)
            p2, o2, m, loss = step(params, opt, tokens)
            assert np.isfinite(float(loss)), loss
            # numerics must match the single-device run
            params_r = jax.device_get(params)
            loss_ref = lm_loss(cfg, params_r, jax.device_get(tokens))
            np.testing.assert_allclose(float(loss), float(loss_ref),
                                       rtol=5e-3)
        print("OK")
    """)
    assert "OK" in out


def test_gnn_cell_sharded_executes():
    out = run_under_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        from repro.configs import get_config
        from repro.models.gnn import GraphBatch, gnn_init, gnn_loss
        from jax.sharding import NamedSharding, PartitionSpec as P
        cfg = get_config("graphsage-reddit", smoke=True)
        n, e, f = 64, 256, 8
        dst = jnp.sort(jax.random.randint(jax.random.PRNGKey(0), (e,), 0, n))
        src = jax.random.randint(jax.random.PRNGKey(1), (e,), 0, n)
        batch = GraphBatch(dst, src,
                           jax.random.normal(jax.random.PRNGKey(2), (n, f)),
                           jax.random.randint(jax.random.PRNGKey(3), (n,),
                                              0, 3),
                           jnp.ones((n,), bool))
        params = gnn_init(cfg, jax.random.PRNGKey(4), d_in=f, n_classes=3)
        loss_ref = gnn_loss(cfg, params, batch)
        with jax.set_mesh(mesh):
            sh = GraphBatch(
                NamedSharding(mesh, P("data")),
                NamedSharding(mesh, P("data")),
                NamedSharding(mesh, P("data", None)),
                NamedSharding(mesh, P("data")),
                NamedSharding(mesh, P("data")))
            batch_s = jax.device_put(batch, sh)
            loss = jax.jit(lambda p, b: gnn_loss(cfg, p, b))(params, batch_s)
        np.testing.assert_allclose(float(loss), float(loss_ref), rtol=1e-5)
        print("OK")
    """)
    assert "OK" in out


def test_preprocess_pipeline_sharded_executes():
    """The paper's pipeline with edges sharded over devices — correctness
    equals the single-device run."""
    out = run_under_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        from repro.core import COO, EngineConfig, preprocess, random_coo
        from jax.sharding import NamedSharding, PartitionSpec as P
        rng = np.random.default_rng(0)
        dst, src = random_coo(rng, 200, 2000)
        coo = COO.from_arrays(dst, src, 200, capacity=2048)
        bn = jnp.arange(16, dtype=jnp.int32)
        key = jax.random.PRNGKey(0)
        cfg = EngineConfig(w_upe=256, n_upe=0)
        sub_ref = preprocess(coo, bn, (4, 3), key, cfg)
        with jax.set_mesh(mesh):
            coo_s = COO(
                dst=jax.device_put(coo.dst, NamedSharding(mesh, P("data"))),
                src=jax.device_put(coo.src, NamedSharding(mesh, P("data"))),
                n_edges=coo.n_edges, n_nodes=coo.n_nodes)
            sub = preprocess(coo_s, bn, (4, 3), key, cfg)
        np.testing.assert_array_equal(np.asarray(sub.order),
                                      np.asarray(sub_ref.order))
        np.testing.assert_array_equal(np.asarray(sub.csc.ptr),
                                      np.asarray(sub_ref.csc.ptr))
        print("OK")
    """)
    assert "OK" in out


def test_build_cell_all_archs_construct():
    """Cell construction (specs + shardings) for every (arch, shape) must
    not require devices: validate tree structure matching."""
    out = run_under_devices("""
        import jax
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        from repro.configs import all_cells
        from repro.launch.steps import build_cell
        n = 0
        for arch, shape in all_cells():
            cell = build_cell(arch, shape, mesh)
            if cell.skipped:
                continue
            ta = jax.tree.structure(cell.args)
            ts = jax.tree.structure(cell.in_shardings)
            assert ta == ts, (arch, shape, ta, ts)
            n += 1
        print("OK", n)
    """)
    assert "OK" in out
