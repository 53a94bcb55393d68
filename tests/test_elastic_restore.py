"""Elastic re-mesh: a checkpoint written under one mesh restores onto a
different device count with identical numerics (node-failure recovery with
changed cluster size)."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code, n_dev):
    env = {**os.environ,
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_dev}",
           "PYTHONPATH": os.path.join(ROOT, "src")}
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_checkpoint_restores_on_different_mesh(tmp_path):
    ck = str(tmp_path / "ck")
    # phase 1: train 3 steps on a 4-device mesh, checkpoint
    _run(f"""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train import checkpoint as ckpt
        from repro.train.optim import AdamWConfig, adamw_init, adamw_update
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        params = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
        with jax.set_mesh(mesh):
            params = jax.device_put(params, {{"w": NamedSharding(
                mesh, P("data", None))}})
            opt = adamw_init(params)
            cfg = AdamWConfig(lr=0.1, warmup_steps=1)
            @jax.jit
            def step(p, o, x):
                loss, g = jax.value_and_grad(
                    lambda pp: jnp.sum((pp["w"] @ x) ** 2))(p)
                return adamw_update(cfg, g, o, p)[:2]
            x = jnp.ones((8,))
            for _ in range(3):
                params, opt = step(params, opt, x)
        ckpt.save({ck!r}, 3, (params, opt))
        print("saved", float(jnp.sum(params["w"])))
    """, 4)
    # phase 2: restore on an 8-device mesh, continue one step
    out = _run(f"""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train import checkpoint as ckpt
        from repro.train.optim import AdamWConfig, adamw_init, adamw_update
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        like_p = {{"w": jnp.zeros((8, 8), jnp.float32)}}
        like_o = adamw_init(like_p)
        sh = {{"w": NamedSharding(mesh, P("data", None))}}
        sh_o = {{"m": sh, "v": sh, "step": NamedSharding(mesh, P())}}
        with jax.set_mesh(mesh):
            (params, opt), meta = ckpt.restore(
                {ck!r}, 3, (like_p, like_o), shardings=(sh, sh_o))
            assert meta["step"] == 3
            assert int(opt["step"]) == 3
            cfg = AdamWConfig(lr=0.1, warmup_steps=1)
            @jax.jit
            def step(p, o, x):
                loss, g = jax.value_and_grad(
                    lambda pp: jnp.sum((pp["w"] @ x) ** 2))(p)
                return adamw_update(cfg, g, o, p)[:2]
            params, opt = step(params, opt, jnp.ones((8,)))
        print("resumed OK on 8 devices")
    """, 8)
    assert "resumed OK" in out
