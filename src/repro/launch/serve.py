"""Serving driver — a thin CLI over the ``repro.serve`` batchers.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --smoke \
      --requests 16 --gen 32
  PYTHONPATH=src python -m repro.launch.serve --arch graphsage-reddit \
      --smoke --requests 16

LM archs submit a mixed-length stream of random-token requests to a
``serve.ServeEngine`` (continuous batching: admission/prefill/decode/
retirement in one jitted slot step); GNN archs submit mixed seed-count
inference requests over a random graph to a ``serve.GnnServeEngine``
(every occupied slot's sample → ``sample_subgraph`` → forward as one vmap
lane of one step). Both report throughput, admission latency and the
compiled-program count. Full configs serve with the same code path on TPU
meshes — the decode_32k / long_500k dry-run cells lower exactly the LM
step function; --smoke runs the reduced config end to end on CPU.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_arch, get_config
from repro.serve import GnnServeEngine, ServeEngine


def _make_lm_engine(cfg, args):
    from repro.models.transformer import lm_init
    params = lm_init(cfg, jax.random.PRNGKey(args.seed))
    return ServeEngine(cfg, params, n_slots=args.slots,
                       max_len=args.max_len, prompt_cap=args.prompt_len)


def _make_gnn_engine(cfg, args):
    from repro.core import pipeline
    from repro.core.graph import COO, random_coo
    from repro.models.gnn import gnn_init
    rng = np.random.default_rng(args.seed)
    dst, src = random_coo(rng, args.nodes, 6 * args.nodes)
    csc = pipeline.convert(COO.from_arrays(dst, src, args.nodes,
                                           capacity=8 * args.nodes))
    feats = np.asarray(rng.normal(size=(args.nodes, 16)), np.float32)
    params = gnn_init(cfg, jax.random.PRNGKey(args.seed), d_in=16,
                      n_classes=8)
    return GnnServeEngine(cfg, params, csc, feats, n_slots=args.slots,
                          seed_cap=args.seed_cap)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="LM: max prompt length; actual lengths are mixed")
    ap.add_argument("--gen", type=int, default=32,
                    help="LM: max new tokens; actual budgets are mixed")
    ap.add_argument("--nodes", type=int, default=1024,
                    help="GNN: random-graph node count")
    ap.add_argument("--seed-cap", type=int, default=8,
                    help="GNN: max batch nodes per request; counts mixed")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    family = get_arch(args.arch).family
    assert family in ("lm", "gnn"), f"no serving path for family {family!r}"
    cfg = get_config(args.arch, smoke=args.smoke)
    rng = np.random.default_rng(args.seed + 1)

    if family == "lm":
        eng = _make_lm_engine(cfg, args)
        unit = "tok"
        t0 = time.perf_counter()
        for _ in range(args.requests):
            plen = int(rng.integers(1, args.prompt_len + 1))
            gen = int(rng.integers(1, args.gen + 1))
            eng.submit(rng.integers(0, cfg.vocab, plen).tolist(), gen)
    else:
        eng = _make_gnn_engine(cfg, args)
        unit = "pred"
        t0 = time.perf_counter()
        for _ in range(args.requests):
            k = int(rng.integers(1, args.seed_cap + 1))
            eng.submit(rng.choice(args.nodes, k, replace=False).tolist())
    eng.close_submissions()
    completed = eng.run()
    dt = time.perf_counter() - t0

    for req in sorted(completed, key=lambda r: r.rid):
        label = "prompt_len" if family == "lm" else "seeds"
        out = "gen" if family == "lm" else "preds"
        print(f"req{req.rid}: {label}={req.prompt_len} "
              f"{out}={req.tokens_out}")
    lat = sorted(r.admission_latency_s for r in completed)
    done = (eng.stats.tokens_processed if family == "lm"
            else eng.stats.tokens_generated)
    print(f"{done / dt:.1f} {unit}/s over {len(completed)} requests "
          f"({eng.stats.steps} steps, {eng.step_cache_size()} compiled "
          f"programs, {dt:.2f}s total)")
    print(f"admission latency p50={lat[len(lat) // 2] * 1e3:.2f}ms "
          f"p99={lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3:.2f}ms")


if __name__ == "__main__":
    main()
