"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch graphsage-reddit \
      --steps 100 --smoke            # AutoGNN-sampled GNN training
  PYTHONPATH=src python -m repro.launch.train --arch gemma2-9b --smoke \
      --steps 50                     # LM training (reduced config on CPU)

Full-size configs train with the same code path on real TPU meshes; this
CLI exists so the whole stack (data → AutoGNN preprocessing → model →
optimizer → checkpoint/restart) runs end to end anywhere.
"""
from __future__ import annotations

import argparse
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, get_config
from repro.core import COO
from repro.data.sampler import SampledDataset
from repro.data import synthetic
from repro.models.gnn import gnn_init, gnn_loss
from repro.models.transformer import lm_init, lm_loss
from repro.models.dlrm import dlrm_init, dlrm_loss
from repro.train.loop import FailureInjector, LoopConfig, train
from repro.train.optim import AdamWConfig, adamw_init, adamw_update


def _train_step_factory(loss_fn, opt_cfg):
    # repro: allow-raw-jit — the factory runs once per training run (the
    # returned step is the loop's only jitted entry), not per step/object.
    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch))(params)
        new_p, new_o, m = adamw_update(opt_cfg, grads, opt_state, params)
        return new_p, new_o, {"loss": loss, **m}
    return step


def run_gnn(arch: str, steps: int, smoke: bool, ckpt_dir: str,
            fail_at: int | None, seed: int = 0):
    cfg = get_config(arch, smoke=smoke)
    n_nodes, n_edges, d_feat, n_classes = (
        (512, 4096, 32, 7) if smoke else (232965, 114615892, 602, 41))
    fanouts = cfg.sample_sizes or (5, 3)
    batch = 32 if smoke else 1024
    dst, src, feats, labels = synthetic.graph_dataset(
        seed, n_nodes, n_edges, d_feat, n_classes)
    ds = SampledDataset(
        coo=COO.from_arrays(dst, src, n_nodes),
        features=jnp.asarray(feats), labels=jnp.asarray(labels),
        fanouts=fanouts, batch_size=batch, seed=seed)
    node_reg = cfg.kind == "meshgraphnet"
    params = gnn_init(cfg, jax.random.PRNGKey(seed), d_in=d_feat, d_edge=4,
                      n_classes=0 if node_reg else n_classes)
    if node_reg:  # regression targets from labels
        def loss_fn(p, b):
            import dataclasses as dc
            tgt = jax.nn.one_hot(b.labels, cfg.d_out)
            b = dc.replace(b, labels=tgt)
            return gnn_loss(cfg, p, b)
    else:
        def loss_fn(p, b):
            return gnn_loss(cfg, p, b)
    opt_cfg = AdamWConfig(lr=1e-3)
    opt = adamw_init(params)
    step_fn = _train_step_factory(loss_fn, opt_cfg)
    # prefetch: the engine samples subgraph i+1 while the model runs step i
    # (batch_fn is pure in step, so restart determinism is unchanged)
    loop_cfg = LoopConfig(total_steps=steps, ckpt_every=max(steps // 4, 10),
                          ckpt_dir=ckpt_dir, prefetch=True)
    inj = FailureInjector(fail_at)
    return train(loop_cfg, step_fn, params, opt, ds.batch, failure=inj)


def run_lm(arch: str, steps: int, smoke: bool, ckpt_dir: str,
           fail_at: int | None, seed: int = 0):
    cfg = get_config(arch, smoke=smoke)
    batch, seq = (4, 64) if smoke else (256, 4096)
    params = lm_init(cfg, jax.random.PRNGKey(seed))
    opt_cfg = AdamWConfig(lr=3e-4)
    opt = adamw_init(params)
    step_fn = _train_step_factory(lambda p, t: lm_loss(cfg, p, t), opt_cfg)

    def batch_fn(step):
        return jnp.asarray(synthetic.lm_batch(seed, step, batch, seq,
                                              cfg.vocab))

    loop_cfg = LoopConfig(total_steps=steps, ckpt_every=max(steps // 4, 10),
                          ckpt_dir=ckpt_dir)
    return train(loop_cfg, step_fn, params, opt, batch_fn,
                 failure=FailureInjector(fail_at))


def run_recsys(arch: str, steps: int, smoke: bool, ckpt_dir: str,
               fail_at: int | None, seed: int = 0):
    cfg = get_config(arch, smoke=smoke)
    batch = 64 if smoke else 65536
    params = dlrm_init(cfg, jax.random.PRNGKey(seed))
    opt_cfg = AdamWConfig(lr=1e-3)
    opt = adamw_init(params)

    def loss_fn(p, b):
        dense, idx, labels = b
        return dlrm_loss(cfg, p, dense, idx, labels)

    step_fn = _train_step_factory(loss_fn, opt_cfg)

    def batch_fn(step):
        dense, idx, labels = synthetic.dlrm_batch(
            seed, step, batch, cfg.n_dense, cfg.n_sparse, cfg.hot,
            cfg.vocab_size)
        return (jnp.asarray(dense), jnp.asarray(idx), jnp.asarray(labels))

    loop_cfg = LoopConfig(total_steps=steps, ckpt_every=max(steps // 4, 10),
                          ckpt_dir=ckpt_dir)
    return train(loop_cfg, step_fn, params, opt, batch_fn,
                 failure=FailureInjector(fail_at))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a crash at this step (chaos drill)")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    family = get_arch(args.arch).family
    runner = {"gnn": run_gnn, "lm": run_lm, "recsys": run_recsys}[family]
    _, _, history = runner(args.arch, args.steps, args.smoke, args.ckpt_dir,
                           args.fail_at)
    for h in history:
        print(h)


if __name__ == "__main__":
    main()
