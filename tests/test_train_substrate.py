"""Fault tolerance, checkpointing, compression, sampler-driven training."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import checkpoint as ckpt
from repro.train.compress import dequantize, quantize_ef, zeros_like_error
from repro.train.loop import FailureInjector, LoopConfig, train
from repro.train.optim import (AdamWConfig, adamw_init, adamw_update,
                               SGDConfig, sgd_init, sgd_update, global_norm)

jax.config.update("jax_platform_name", "cpu")


# ----------------------------------------------------------------- optim
def test_adamw_reduces_quadratic_loss():
    params = {"w": jnp.array([5.0, -3.0])}
    opt = adamw_init(params)
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1)

    def loss(p):
        return jnp.sum(p["w"] ** 2)

    for _ in range(100):
        g = jax.grad(loss)(params)
        params, opt, _ = adamw_update(cfg, g, opt, params)
    assert float(loss(params)) < 1e-2


def test_adamw_bf16_moments_close_to_fp32():
    params = {"w": jnp.ones((16,))}
    g = {"w": jnp.linspace(-1, 1, 16)}
    o32 = adamw_init(params)
    o16 = adamw_init(params, jnp.bfloat16)
    c32 = AdamWConfig(lr=0.01)
    c16 = AdamWConfig(lr=0.01, mom_dtype=jnp.bfloat16)
    p32, p16 = params, params
    for _ in range(5):
        p32, o32, _ = adamw_update(c32, g, o32, p32)
        p16, o16, _ = adamw_update(c16, g, o16, p16)
    np.testing.assert_allclose(p32["w"], p16["w"], rtol=0.05, atol=1e-3)
    assert o16["m"]["w"].dtype == jnp.bfloat16


def test_sgd_momentum():
    params = {"w": jnp.array([2.0])}
    opt = sgd_init(params)
    cfg = SGDConfig(lr=0.05)
    for _ in range(60):
        g = {"w": 2 * params["w"]}
        params, opt, _ = sgd_update(cfg, g, opt, params)
    assert abs(float(params["w"][0])) < 0.1


def test_grad_clip_bounds_update():
    params = {"w": jnp.zeros((4,))}
    opt = adamw_init(params)
    cfg = AdamWConfig(lr=1.0, grad_clip=1.0, weight_decay=0.0,
                      warmup_steps=1)
    g = {"w": jnp.full((4,), 1e6)}
    new_p, _, m = adamw_update(cfg, g, opt, params)
    assert float(m["grad_norm"]) == pytest.approx(2e6)
    assert np.all(np.abs(np.asarray(new_p["w"])) < 1.5)


# ------------------------------------------------------------ checkpoint
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(5, dtype=jnp.float32),
            "b": {"c": jnp.ones((2, 3), jnp.bfloat16)}}
    ckpt.save(str(tmp_path), 7, tree)
    restored, meta = ckpt.restore(str(tmp_path), 7, tree)
    assert meta["step"] == 7
    np.testing.assert_array_equal(restored["a"], tree["a"])
    assert restored["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_keep_k_and_latest(tmp_path):
    tree = {"x": jnp.zeros(1)}
    for s in [10, 20, 30, 40]:
        ckpt.save(str(tmp_path), s, tree, keep=2)
    assert ckpt.all_steps(str(tmp_path)) == [30, 40]
    assert ckpt.latest_step(str(tmp_path)) == 40


def test_checkpoint_partial_write_ignored(tmp_path):
    tree = {"x": jnp.zeros(3)}
    ckpt.save(str(tmp_path), 5, tree)
    # simulate a crash mid-write: tmp dir without commit
    os.makedirs(tmp_path / "step_000000009.tmp")
    assert ckpt.latest_step(str(tmp_path)) == 5


# -------------------------------------------------------- fault tolerance
def _toy_problem():
    params = {"w": jnp.array([4.0])}
    opt = adamw_init(params)
    cfg = AdamWConfig(lr=0.05, weight_decay=0.0, warmup_steps=1)

    @jax.jit
    def step_fn(p, o, batch):
        loss, g = jax.value_and_grad(
            lambda pp: jnp.sum((pp["w"] - batch) ** 2))(p)
        np_, no, m = adamw_update(cfg, g, o, p)
        return np_, no, {"loss": loss}

    def batch_fn(step):
        return jnp.asarray(float(step % 3))  # pure f(step)

    return params, opt, step_fn, batch_fn


def test_restart_equivalence_after_injected_failure(tmp_path):
    """Crash at step 12, restart, final params must equal a clean run."""
    params, opt, step_fn, batch_fn = _toy_problem()
    cfg = LoopConfig(total_steps=20, ckpt_every=5, ckpt_dir=str(tmp_path),
                     log_every=1)
    with pytest.raises(RuntimeError, match="injected failure"):
        train(cfg, step_fn, params, opt, batch_fn,
              failure=FailureInjector(12))
    # restart: resumes from step 10 checkpoint
    p1, o1, hist = train(cfg, step_fn, params, opt, batch_fn)
    assert hist[0]["step"] == 10  # resumed, not restarted

    # clean run (separate dir)
    params2, opt2, step_fn2, batch_fn2 = _toy_problem()
    cfg2 = LoopConfig(total_steps=20, ckpt_every=5,
                      ckpt_dir=str(tmp_path) + "_clean", log_every=1)
    p2, _, _ = train(cfg2, step_fn2, params2, opt2, batch_fn2)
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                               rtol=1e-6)


def test_straggler_watchdog_fires(tmp_path):
    params, opt, step_fn, batch_fn = _toy_problem()

    def slow_step(p, o, b):
        import time
        time.sleep(0.2)
        return step_fn(p, o, b)

    cfg = LoopConfig(total_steps=3, ckpt_every=100, ckpt_dir=str(tmp_path),
                     step_timeout_s=0.05)
    with pytest.raises(TimeoutError, match="straggler"):
        train(cfg, slow_step, params, opt, batch_fn)


# ------------------------------------------------------------ compression
def test_quantize_error_feedback_converges():
    """Error feedback: accumulated quantized values track the true sum."""
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    err = jnp.zeros_like(g)
    acc_q = jnp.zeros_like(g)
    steps = 50
    for _ in range(steps):
        q, scale, err = quantize_ef(g, err)
        acc_q = acc_q + dequantize(q, scale)
    np.testing.assert_allclose(np.asarray(acc_q), np.asarray(g) * steps,
                               rtol=0.01, atol=0.01)


def test_compressed_psum_matches_mean_under_shard_map():
    """int8 psum across a 4-way axis ≈ fp32 mean (one step, fresh error)."""
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.train.compress import make_compressed_allreduce
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pod",))
        g = jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 7.3
        e = jnp.zeros_like(g)
        fn = make_compressed_allreduce(mesh, {"g": P("pod", None)})
        out, err = fn({"g": g}, {"g": e})
        want = jnp.broadcast_to(jnp.mean(g, axis=0, keepdims=True), g.shape)
        np.testing.assert_allclose(np.asarray(out["g"]), np.asarray(want),
                                   rtol=0.02, atol=0.02)
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**os.environ,
                                       "PYTHONPATH": "src"},
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_global_norm():
    t = {"a": jnp.array([3.0]), "b": jnp.array([4.0])}
    assert float(global_norm(t)) == pytest.approx(5.0)
