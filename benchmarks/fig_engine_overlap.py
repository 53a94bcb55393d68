"""Engine-service benchmark: preprocessing overlap + sharded conversion.

Beyond the paper's figures — this measures the two promises of
``repro.engine`` end to end:

* **overlap** — GNN training wall-time with the synchronous batch_fn vs
  the double-buffered ``Prefetcher`` (subgraph ``i+1`` sampled while the
  model consumes subgraph ``i``). The paper's off-critical-path claim,
  as a ratio.
* **shard** — single-device ``convert`` vs ``engine.shard.shard_convert``
  when the host exposes more than one device (run under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to exercise it
  on CPU; on one device the row reports the single-device fallback).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import COO, EngineConfig, random_coo
from repro.core.pipeline import convert
from repro.data.sampler import SampledDataset
from repro.engine.prefetch import Prefetcher
from repro.engine.shard import shard_convert
from repro.models.gnn import gnn_init, gnn_loss
from repro.train.optim import AdamWConfig, adamw_init, adamw_update
from repro.configs import get_config

from .common import emit, time_fn

STEPS = 24


def _dataset(n_nodes=2048, n_edges=16384, d_feat=32, n_classes=7):
    rng = np.random.default_rng(0)
    dst, src = random_coo(rng, n_nodes, n_edges)
    feats = rng.standard_normal((n_nodes, d_feat)).astype(np.float32)
    labels = rng.integers(0, n_classes, n_nodes).astype(np.int32)
    return SampledDataset(
        coo=COO.from_arrays(dst, src, n_nodes),
        features=jnp.asarray(feats), labels=jnp.asarray(labels),
        fanouts=(5, 5), batch_size=128, seed=0), n_classes


def _train_setup(ds, n_classes):
    cfg = get_config("graphsage-reddit", smoke=True)
    params = gnn_init(cfg, jax.random.PRNGKey(0),
                      d_in=ds.features.shape[1], n_classes=n_classes)
    opt_cfg = AdamWConfig(lr=1e-3)
    opt = adamw_init(params)

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: gnn_loss(cfg, p, batch))(params)
        return adamw_update(opt_cfg, grads, opt_state, params)

    return step, params, opt


def run() -> dict:
    out = {}
    ds, n_classes = _dataset()
    step_fn, params, opt = _train_setup(ds, n_classes)
    # warm both programs
    b0 = ds.batch(0)
    jax.block_until_ready(step_fn(params, opt, b0))

    # synchronous: preprocess then step, serialized
    p, o = params, opt
    t0 = time.perf_counter()
    for s in range(STEPS):
        p, o, _ = step_fn(p, o, ds.batch(s))
    jax.block_until_ready(p)
    t_sync = (time.perf_counter() - t0) * 1e6

    # prefetched: subgraph s+1 sampled while step s runs
    p, o = params, opt
    t0 = time.perf_counter()
    with Prefetcher(ds.batch, start=0, stop=STEPS) as pf:
        for s, batch in pf:
            p, o, _ = step_fn(p, o, batch)
    jax.block_until_ready(p)
    t_pref = (time.perf_counter() - t0) * 1e6

    emit("engine/overlap/sync", t_sync / STEPS)
    emit("engine/overlap/prefetch", t_pref / STEPS,
         f"speedup={t_sync / max(t_pref, 1e-9):.2f}x")
    out["overlap"] = {"sync_us": t_sync / STEPS,
                      "prefetch_us": t_pref / STEPS}

    # sharded conversion (needs >1 device to differ from the baseline)
    n_dev = jax.device_count()
    rng = np.random.default_rng(1)
    dst, src = random_coo(rng, 4096, 1 << 16)
    coo = COO.from_arrays(dst, src, 4096)
    ecfg = EngineConfig(w_upe=1024, n_upe=0)
    t_single = time_fn(jax.jit(lambda c: convert(c, ecfg)), coo, iters=3)
    if n_dev > 1:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((n_dev,), ("data",))
        with jax.set_mesh(mesh):
            t_shard = time_fn(
                jax.jit(lambda c: shard_convert(mesh, c, ecfg)), coo,
                iters=3)
    else:
        t_shard = t_single
    emit("engine/shard/convert_single", t_single)
    emit("engine/shard/convert_sharded", t_shard,
         f"devices={n_dev};speedup={t_single / max(t_shard, 1e-9):.2f}x")
    out["shard"] = {"single_us": t_single, "sharded_us": t_shard,
                    "devices": n_dev}
    return out
