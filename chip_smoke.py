#!/usr/bin/env python3
"""Bring-up smoke test: graph preprocessing + GraphSAGE serving on one TPU.

Run from the repository root on a machine with a TPU::

    python chip_smoke.py [--seed N]          # one chip, phases 1-5
    python chip_smoke.py --four-chips        # the 4-chip mesh phase only

Everything runs in this one process, through the entry points a user calls:

1. ``convert`` — a seeded graph of the Reddit shape
   (``GNN_SHAPES["minibatch_lg"]``: 232,965 nodes, 114,615,892 edges)
   goes COO → CSC through ``engine.service.convert_jit`` and must equal a
   host NumPy CSC bit for bit.
2. ``sweep`` — on a 2^20-edge graph, every ``sort_strategy`` ×
   ``reindex_strategy`` convert, one sample per reindex strategy and both
   ``apply_delta`` modes must agree bit for bit.
3. ``serve`` — ``GnnServeEngine`` runs graphsage-reddit at its published
   widths over the Reddit CSC and a 602-wide feature table: 64 requests,
   two streamed edge updates, then 8 more requests. Predictions must equal
   the sequential ``slot_fn`` oracle, the updated CSC a host re-convert,
   and two requests' subgraphs a CPU-backend run of the same program.
4. ``gat`` — whole-graph GAT inference (``engine.service.gat_infer_jit``)
   over a 2^20-edge graph's CSC must agree with the sampled GAT forward
   over full neighbourhoods and with a CPU-backend run, and lower with no
   scatter.
5. ``kernels`` — each Pallas kernel that compiles for the chip against its
   ``kernels/ref.py`` oracle, the one Pallas preprocessing route that
   compiles, and the refusal of one that does not.

``--four-chips`` runs ``engine.shard.shard_preprocess`` over a 4-chip
``("data",)`` mesh on the Reddit-shaped graph against the single-chip
pipeline in the same process.

Each phase prints its checks, wall time, the compiled program's
``memory_analysis()`` and ``peak_bytes_in_use``. A failed check raises and
the process exits nonzero. The last line is one JSON object naming the
device. On any platform but TPU the script exits 2 before doing anything.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SENTINEL = 0x7FFFFFFF
REDDIT = "minibatch_lg"
SWEEP_NODES, SWEEP_EDGES = 20_000, 1 << 20
# TPU f32 matmuls round their inputs to bfloat16 by default (relative
# error 2^-8 per operand); across two GraphSAGE layers that stays well
# inside 2 % of the logits' scale, which is the bound held against the
# CPU backend's full-precision logits.
LOGIT_RTOL = 2e-2


class SmokeFailure(AssertionError):
    """A check of the smoke test did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# ----------------------------------------------------------- host helpers
def host_csc(dst: np.ndarray, src: np.ndarray, n_nodes: int
             ) -> tuple[np.ndarray, np.ndarray]:
    """CSC of an edge list, on the host: ``idx`` in (dst, src) order —
    the order of ``np.lexsort((src, dst))``, taken by one sort of a
    packed int64 key — and ``ptr`` from ``bincount`` + ``cumsum``."""
    key = (dst.astype(np.int64) << 32) | src.astype(np.int64)
    key.sort()
    ptr = np.zeros(n_nodes + 1, np.int64)
    np.cumsum(np.bincount(dst, minlength=n_nodes), out=ptr[1:])
    return ptr.astype(np.int32), (key & 0xFFFFFFFF).astype(np.int32)


def check_csc(csc, ptr_ref: np.ndarray, idx_ref: np.ndarray, tag: str
              ) -> None:
    """The device CSC equals the host one: pointers, the valid index
    prefix, and a SENTINEL tail."""
    e = idx_ref.shape[0]
    ptr = np.asarray(csc.ptr)[:ptr_ref.shape[0]]
    idx = np.asarray(csc.idx)
    check(int(csc.n_edges) == e, f"{tag}: n_edges {int(csc.n_edges)} != {e}")
    check(np.array_equal(ptr, ptr_ref), f"{tag}: ptr differs from host")
    check(np.array_equal(idx[:e], idx_ref), f"{tag}: idx differs from host")
    check(bool(np.all(idx[e:] == SENTINEL)), f"{tag}: idx tail not SENTINEL")


def make_graph(n_nodes: int, n_edges: int, seed: int):
    """Seeded power-law edge list (``core.graph.random_coo``) and its COO
    at the pow2 capacity bucket."""
    from repro.core.graph import COO, next_pow2, random_coo
    dst, src = random_coo(np.random.default_rng(seed), n_nodes, n_edges)
    return dst, src, COO.from_arrays(dst, src, n_nodes,
                                     capacity=next_pow2(n_edges))


def compiled_memory(compiled) -> dict | None:
    m = compiled.memory_analysis()
    if m is None:
        return None
    return {k: int(getattr(m, f"{k}_size_in_bytes"))
            for k in ("argument", "output", "temp", "generated_code")}


def peak_bytes(devices=None) -> list:
    """``peak_bytes_in_use`` of each device (None where not reported)."""
    import jax
    out = []
    for d in devices or jax.devices()[:1]:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def same_tree(a, b, tag: str) -> None:
    import jax
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    check(len(la) == len(lb), f"{tag}: tree structure differs")
    for i, (x, y) in enumerate(zip(la, lb)):
        check(np.array_equal(np.asarray(x), np.asarray(y)),
              f"{tag}: leaf {i} differs")


@dataclasses.dataclass
class Graph:
    """The Reddit-shaped graph the convert phase leaves for serving."""

    dst: np.ndarray
    src: np.ndarray
    csc: object
    n_nodes: int
    d_feat: int
    n_classes: int


# ------------------------------------------------------------------ phases
def phase_convert(n_nodes: int, n_edges: int, d_feat: int, n_classes: int,
                  seed: int) -> tuple[Graph, dict]:
    """COO → CSC through ``convert_jit`` under the default (``auto``)
    engine config, against the host CSC."""
    import jax
    from repro.core.costmodel import (EngineConfig, Workload,
                                      pointer_reindex_strategy,
                                      resolve_sort_strategy,
                                      sort_pass_count)
    from repro.engine.service import convert_jit

    t0 = time.perf_counter()
    dst, src, coo = make_graph(n_nodes, n_edges, seed)
    ptr_ref, idx_ref = host_csc(dst, src, n_nodes)
    t_setup = time.perf_counter() - t0
    cfg = EngineConfig()
    w = Workload(n=n_nodes, e=coo.capacity)
    t0 = time.perf_counter()
    compiled = convert_jit.lower(coo, cfg=cfg).compile()
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    csc = jax.block_until_ready(compiled(coo))
    t_run = time.perf_counter() - t0
    check_csc(csc, ptr_ref, idx_ref, "convert")
    return Graph(dst, src, csc, n_nodes, d_feat, n_classes), {
        "result": "CSC bit-identical to the host NumPy CSC",
        "n_nodes": n_nodes, "n_edges": n_edges, "capacity": coo.capacity,
        "auto_dispatch (CPU-measured constants)": {
            "sort_strategy": resolve_sort_strategy(cfg, w),
            "sort_passes": sort_pass_count(cfg, w),
            "pointer_epilogue": pointer_reindex_strategy(cfg, w)},
        "setup_s": t_setup, "compile_s": t_compile, "run_s": t_run,
        "memory_analysis": compiled_memory(compiled),
    }


def phase_sweep(n_nodes: int, n_edges: int, seed: int,
                fanouts: tuple[int, ...], n_seeds: int = 64,
                delta_cap: int = 64) -> dict:
    """Every sort × reindex strategy convert, a sample per reindex
    strategy, and both delta modes — all bit-identical."""
    import jax
    import jax.numpy as jnp
    from repro.core.costmodel import (REINDEX_STRATEGIES, SORT_STRATEGIES,
                                      EngineConfig)
    from repro.core.delta import EdgeDelta
    from repro.engine.service import apply_delta_jit, convert_jit, sample_jit

    dst, src, coo = make_graph(n_nodes, n_edges, seed)
    ptr_ref, idx_ref = host_csc(dst, src, n_nodes)
    t0 = time.perf_counter()
    csc, memory = None, {}
    for s in SORT_STRATEGIES:
        for r in REINDEX_STRATEGIES:
            cfg = EngineConfig(sort_strategy=s, reindex_strategy=r)
            compiled = convert_jit.lower(coo, cfg=cfg).compile()
            memory[f"convert {s}/{r}"] = compiled_memory(compiled)
            csc = compiled(coo)
            check_csc(csc, ptr_ref, idx_ref, f"convert {s}/{r}")
    rng = np.random.default_rng(seed + 1)
    seeds = jnp.asarray(rng.choice(n_nodes, n_seeds, replace=False),
                        jnp.int32)
    key = jax.random.PRNGKey(seed)
    subs = [sample_jit(csc, seeds, fanouts, key,
                       EngineConfig(reindex_strategy=r))
            for r in REINDEX_STRATEGIES]
    same_tree(subs[0], subs[1], "sample fused vs unfused")
    ins_d = rng.integers(0, n_nodes, delta_cap).astype(np.int32)
    ins_s = rng.integers(0, n_nodes, delta_cap).astype(np.int32)
    gone = rng.choice(n_edges, delta_cap, replace=False)
    delta = EdgeDelta.from_arrays(ins_d, ins_s, dst[gone], src[gone],
                                  n_nodes=n_nodes, capacity=delta_cap)
    ptr2, idx2 = host_csc(np.concatenate([np.delete(dst, gone), ins_d]),
                          np.concatenate([np.delete(src, gone), ins_s]),
                          n_nodes)
    for mode in ("merge", "rebuild"):
        compiled = apply_delta_jit.lower(csc, delta, cfg=EngineConfig(),
                                         mode=mode,
                                         out_capacity=coo.capacity).compile()
        memory[f"apply_delta {mode}"] = compiled_memory(compiled)
        check_csc(compiled(csc, delta), ptr2, idx2, f"apply_delta {mode}")
    return {"result": f"{len(SORT_STRATEGIES) * len(REINDEX_STRATEGIES)} "
                      f"converts, {len(subs)} samples and 2 delta modes "
                      f"bit-identical",
            "n_nodes": n_nodes, "n_edges": n_edges,
            "wall_s_incl_compile": time.perf_counter() - t0,
            "memory_analysis": memory}


def _logits_fn(gcfg, fanouts, cfg):
    """One request's subgraph and its logits (the slot program before its
    argmax)."""
    from repro.core import pipeline
    from repro.models.gnn import gnn_apply, subgraph_batch

    def fn(bundle, seeds, key):
        sub = pipeline.sample_subgraph(bundle["csc"], seeds, fanouts, key,
                                       cfg)
        return sub, gnn_apply(gcfg, bundle["gnn"],
                              subgraph_batch(sub, bundle["features"]))

    return fn


def phase_serve(g: Graph, seed: int, n_requests: int = 64,
                n_after: int = 8, n_slots: int = 8, seed_cap: int = 8,
                delta_cap: int = 64, n_cpu_checks: int = 2,
                gcfg=None) -> dict:
    """Batched GraphSAGE serving with streamed graph updates; ``gcfg``
    defaults to graphsage-reddit at its published widths."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.costmodel import Workload, resolve_delta_mode
    from repro.models.gnn import gnn_init
    from repro.serve import GnnServeEngine

    gcfg = gcfg or get_config("graphsage-reddit")
    params = gnn_init(gcfg, jax.random.PRNGKey(seed), d_in=g.d_feat,
                      n_classes=g.n_classes)
    feats = jax.random.normal(jax.random.PRNGKey(seed + 1),
                              (g.n_nodes, g.d_feat), jnp.float32)
    eng = GnnServeEngine(gcfg, params, g.csc, feats, n_slots=n_slots,
                         seed_cap=seed_cap, delta_cap=delta_cap)
    rng = np.random.default_rng(seed + 2)

    def draw(n):
        return [rng.choice(g.n_nodes, int(rng.integers(1, seed_cap + 1)),
                           replace=False).tolist() for _ in range(n)]

    def row(seeds):
        r = np.full((eng.seed_cap,), SENTINEL, np.int32)
        r[:len(seeds)] = seeds
        return r

    oracle = jax.jit(eng.slot_fn)

    def check_oracle(handles, bundle, tag):
        for h in handles:
            want = np.asarray(oracle(bundle, jnp.asarray(row(h.prompt)),
                                     eng.request_key(h.rid)))
            check(h.tokens_out == want[:len(h.prompt)].tolist(),
                  f"{tag}: request {h.rid} differs from the slot_fn oracle")

    t0 = time.perf_counter()
    first = [eng.submit(s) for s in draw(n_requests)]
    eng.close_submissions()
    done = eng.run()
    t_first = time.perf_counter() - t0
    check(len(done) == n_requests, f"{len(done)} of {n_requests} retired")
    bundle0 = eng.params
    check_oracle(first, bundle0, "serve")

    # the same program on the CPU backend: equal subgraphs, close logits
    logits = jax.jit(_logits_fn(gcfg, eng.fanouts, eng.engine_cfg))
    cpu = jax.devices("cpu")[0]
    bundle_cpu = jax.device_put(bundle0, cpu)
    max_err, max_logit = 0.0, 0.0
    for h in first[:n_cpu_checks]:
        seeds, key = row(h.prompt), eng.request_key(h.rid)
        sub_d, lg_d = logits(bundle0, jnp.asarray(seeds), key)
        sub_c, lg_c = logits(bundle_cpu, jax.device_put(seeds, cpu),
                             jax.device_put(key, cpu))
        same_tree(sub_d, sub_c, f"request {h.rid} subgraph vs CPU")
        lg_d, lg_c = np.asarray(lg_d), np.asarray(lg_c)
        max_err = max(max_err, float(np.max(np.abs(lg_d - lg_c))))
        max_logit = max(max_logit, float(np.max(np.abs(lg_c))))
    check(max_err <= LOGIT_RTOL * max(max_logit, 1.0),
          f"logits vs CPU: max error {max_err} > {LOGIT_RTOL} x "
          f"{max(max_logit, 1.0)}")

    # two streamed updates (inserts, then deletes of existing edges)
    e = g.dst.shape[0]
    ins_d = rng.integers(0, g.n_nodes, delta_cap).astype(np.int32)
    ins_s = rng.integers(0, g.n_nodes, delta_cap).astype(np.int32)
    gone = rng.choice(e, delta_cap, replace=False)
    eng.reopen()
    t0 = time.perf_counter()
    updates = [eng.submit_update(zip(ins_d, ins_s)),
               eng.submit_update((), deletes=zip(g.dst[gone], g.src[gone]))]
    after = [eng.submit(s) for s in draw(n_after)]
    eng.close_submissions()
    done = eng.run()
    t_after = time.perf_counter() - t0
    check(len(done) == len(updates) + n_after,
          f"{len(done)} of {len(updates) + n_after} retired after updates")
    ptr2, idx2 = host_csc(np.concatenate([np.delete(g.dst, gone), ins_d]),
                          np.concatenate([np.delete(g.src, gone), ins_s]),
                          g.n_nodes)
    check_csc(eng.params["csc"], ptr2, idx2, "serve after updates")
    check_oracle(after, eng.params, "serve after updates")
    check(eng.step_cache_size() == 1,
          f"step compiled {eng.step_cache_size()} times")
    # the step program, already compiled, for its memory figures
    step_mem = compiled_memory(
        eng._step.lower(eng.params, eng.state).compile())
    cap = int(g.csc.idx.shape[0])
    return {
        "result": f"{n_requests}+{n_after} requests equal the slot_fn "
                  f"oracle; CSC after 2 updates equals the host "
                  f"re-convert; step_cache_size 1; {n_cpu_checks} "
                  f"subgraphs integer-equal to the CPU backend",
        "arch": gcfg.name, "d_hidden": gcfg.d_hidden,
        "sample_sizes": list(eng.fanouts), "n_slots": eng.n_slots,
        "seed_cap": eng.seed_cap,
        "logits_vs_cpu": {"max_abs_err": max_err,
                          "max_abs_logit": max_logit,
                          "bound": LOGIT_RTOL * max(max_logit, 1.0)},
        "auto_dispatch (CPU-measured constants)": {
            "delta_mode": resolve_delta_mode(
                eng.engine_cfg, Workload(n=g.n_nodes, e=cap),
                eng.delta_cap)},
        "first_wave_wall_s_incl_compile": t_first,
        "updates_wall_s_incl_compile": t_after,
        "steps": eng.stats.steps,
        "step_memory_analysis": step_mem,
    }


def phase_gat(n_nodes: int, n_edges: int, seed: int, d_feat: int = 64,
              width: int = 32, n_classes: int = 16, sizes=None) -> dict:
    """Whole-graph GAT inference (``engine.service.gat_infer_jit``, 3
    layers of 4 heads with skips, the ogbn-products form at smaller
    widths) over a seeded graph's CSC, against the sampled GAT forward
    over every node's full neighbourhood on the chip and against the same
    entry on the CPU backend, all at ``highest`` matmul precision. ``sizes``
    (row tile, edge chunk, chunks per step, projection rows) is for the
    CPU rehearsal."""
    import jax
    import jax.numpy as jnp
    from functools import partial
    from repro.core.graph import SENTINEL as SEN
    from repro.engine import service
    from repro.launch import hlo_analysis
    from repro.models.gnn import (GNNConfig, GraphBatch, gat_infer,
                                  gnn_apply, gnn_init)

    _, _, coo = make_graph(n_nodes, n_edges, seed)
    csc = service.convert_jit(coo)
    gcfg = GNNConfig(name="gat-smoke", kind="gat", n_layers=3,
                     d_hidden=width, n_heads=4, out_heads=4, skip=True)
    params = gnn_init(gcfg, jax.random.PRNGKey(seed), d_in=d_feat,
                      n_classes=n_classes)
    feats = jax.random.normal(jax.random.PRNGKey(seed + 1),
                              (n_nodes, d_feat))
    infer = jax.jit(partial(gat_infer, cfg=gcfg, _sizes=sizes))
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        compiled = infer.lower(csc, feats, params).compile()
        t_compile = time.perf_counter() - t0
        check(hlo_analysis.op_counts(compiled.as_text()).get("scatter", 0)
              == 0, "the whole-graph GAT lowers to a scatter")
        t0 = time.perf_counter()
        logits, counters = jax.block_until_ready(compiled(csc, feats,
                                                          params))
        t_run = time.perf_counter() - t0
        pos = jnp.arange(csc.idx.shape[0], dtype=jnp.int32)
        ptr = csc.ptr[:n_nodes + 1]
        dst = jnp.where(pos < csc.n_edges,
                        jnp.searchsorted(ptr, pos, side="right") - 1, SEN)
        batch = GraphBatch(edge_dst=dst.astype(jnp.int32), edge_src=csc.idx,
                           node_feat=feats,
                           labels=jnp.zeros(n_nodes, jnp.int32),
                           label_mask=jnp.zeros(n_nodes, bool), ptr=ptr)
        sampled = jax.jit(partial(gnn_apply, gcfg))(params, batch)
        cpu = jax.devices("cpu")[0]
        on_cpu, _ = infer(*jax.device_put((csc, feats, params), cpu))
    scale = max(float(np.max(np.abs(np.asarray(on_cpu)))), 1.0)
    errs = {name: float(np.max(np.abs(np.asarray(logits)
                                      - np.asarray(other))))
            for name, other in (("sampled", sampled), ("cpu", on_cpu))}
    for name, err in errs.items():
        check(err <= 1e-3 * scale,
              f"whole-graph GAT vs {name}: max error {err} > 1e-3 x {scale}")
    return {"n_nodes": n_nodes, "n_edges": n_edges,
            "counters": {k: int(v) for k, v in counters.items()},
            "max_abs_err": errs, "logit_scale": scale,
            "compile_s": t_compile, "run_s": t_run,
            "memory_analysis": compiled_memory(compiled)}


def phase_kernels(seed: int, n_elems: int = 1 << 16, n_targets: int = 4096,
                  sweep=(SWEEP_NODES, SWEEP_EDGES),
                  agg=(2304, 4096, 640), flash=(8, 2048, 128)) -> dict:
    """Every Pallas kernel that compiles for the chip, against its
    ``kernels/ref.py`` oracle; the Pallas pointer-build route end to end;
    and, on a TPU, the refusal of a route whose kernel Mosaic rejects."""
    import jax
    import jax.numpy as jnp
    from repro.core.costmodel import EngineConfig
    from repro.engine.service import convert_jit
    from repro.kernels import ops, ref
    from repro.kernels.flash_attention import flash_attention_fwd

    rng = np.random.default_rng(seed)
    out = {}
    # SCR set count over a sorted stream (the pointer build's kernel)
    elems = np.sort(rng.integers(0, n_targets, n_elems)).astype(np.int32)
    tgts = np.arange(n_targets, dtype=np.int32)
    got = np.asarray(ops.set_count_less(jnp.asarray(elems),
                                        jnp.asarray(tgts)))
    check(np.array_equal(got, ref.set_count_less_ref(elems, tgts)),
          "set_count_less differs from ref")
    out["set_count_less"] = "equal"
    # SCR filter-tree lookup: half the targets hit, half miss
    keys = rng.permutation(4 * n_elems)[:n_elems].astype(np.int32)
    pays = rng.integers(0, 1 << 20, n_elems).astype(np.int32)
    tgts = np.concatenate([keys[:n_targets // 2],
                           rng.integers(4 * n_elems, 8 * n_elems,
                                        n_targets // 2)]).astype(np.int32)
    got_p, got_h = ops.filter_tree_lookup(jnp.asarray(keys),
                                          jnp.asarray(pays),
                                          jnp.asarray(tgts))
    want_p, want_h = ref.filter_tree_lookup_ref(keys, pays, tgts)
    check(np.array_equal(np.asarray(got_p), want_p)
          and np.array_equal(np.asarray(got_h), want_h),
          "filter_tree_lookup differs from ref")
    out["filter_tree_lookup"] = "equal"
    # segment sum at a serve subgraph's first-layer shape; the bound is
    # bfloat16 rounding of each message (2^-8) with a 2x margin
    n_nodes, n_edges, d = agg
    dst = np.sort(rng.integers(0, n_nodes, n_edges)).astype(np.int32)
    msgs = rng.normal(size=(n_edges, d)).astype(np.float32)
    got = np.asarray(ops.segment_sum_sorted(jnp.asarray(dst),
                                            jnp.asarray(msgs), n_nodes))
    want = ref.segment_sum_sorted_ref(dst, msgs, n_nodes)
    bound = 2.0 ** -7 * ref.segment_sum_sorted_ref(dst, np.abs(msgs),
                                                   n_nodes) + 1e-6
    err = np.abs(got - want)
    check(bool(np.all(err <= bound)), "segment_sum_sorted outside bound")
    out["segment_sum_sorted"] = {"max_abs_err": float(err.max())}
    # flash attention forward, bf16 inputs, against f32 softmax attention
    bh, s, dh = flash
    q, k, v = (rng.normal(size=(bh, s, dh)).astype(np.float32)
               for _ in range(3))
    qb, kb, vb = (jnp.asarray(x, jnp.bfloat16) for x in (q, k, v))
    got = np.asarray(flash_attention_fwd(qb, kb, vb), np.float32)
    qf, kf, vf = (np.asarray(x, np.float32) for x in (qb, kb, vb))
    logits = np.einsum("bqd,bkd->bqk", qf, kf) / np.sqrt(dh)
    logits = np.where(np.tril(np.ones((s, s), bool)), logits, -np.inf)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    want = np.einsum("bqk,bkd->bqd", p / p.sum(-1, keepdims=True), vf)
    err = float(np.max(np.abs(got - want)))
    check(err <= 3e-2, f"flash_attention_fwd max error {err} > 3e-2")
    out["flash_attention_fwd"] = {"max_abs_err": err}
    # the Pallas route that compiles: SCR-kernel pointer build
    n, e = sweep
    dst, src, coo = make_graph(n, e, seed)
    ptr_ref, idx_ref = host_csc(dst, src, n)
    pallas_cfg = EngineConfig(use_pallas=True, sort_strategy="xla_sort",
                              reindex_strategy="unfused")
    compiled = convert_jit.lower(coo, cfg=pallas_cfg).compile()
    check("tpu_custom_call" in compiled.as_text()
          or jax.default_backend() != "tpu",
          "the use_pallas route holds no Mosaic kernel")
    check_csc(compiled(coo), ptr_ref, idx_ref,
              "convert use_pallas xla_sort/unfused")
    out["route use_pallas xla_sort/unfused"] = {
        "result": "CSC equal to host",
        "memory_analysis": compiled_memory(compiled)}
    if jax.default_backend() == "tpu":
        refused = EngineConfig(use_pallas=True,
                               sort_strategy="chunked_merge")
        try:
            convert_jit(coo, cfg=refused)
        except NotImplementedError as exc:
            check("radix_sort_chunks" in str(exc), f"wrong refusal: {exc}")
            out["route use_pallas chunked_merge"] = f"refused: {exc}"
        else:
            raise SmokeFailure("a refused Pallas route ran on the TPU")
    return {"result": "every compiling kernel matches its oracle",
            "kernels": out}


def phase_four_chips(n_nodes: int, n_edges: int, seed: int,
                     fanouts: tuple[int, ...], n_seeds: int = 8) -> dict:
    """``shard_preprocess`` over a 4-device ``("data",)`` mesh against
    the single-device pipeline, bit for bit, with work on every device."""
    import jax
    import jax.numpy as jnp
    from repro.core.costmodel import EngineConfig
    from repro.engine.service import preprocess_jit
    from repro.engine.shard import jit_shard_preprocess
    from repro.launch.mesh import make_mesh

    devices = jax.devices()[:4]
    check(len(devices) == 4, f"needs 4 devices, found {len(devices)}")
    mesh = make_mesh((4,), ("data",), devices=devices)
    dst, src, coo = make_graph(n_nodes, n_edges, seed)
    rng = np.random.default_rng(seed + 1)
    seeds = jnp.asarray(rng.choice(n_nodes, n_seeds, replace=False),
                        jnp.int32)
    key = jax.random.PRNGKey(seed)
    cfg = EngineConfig()
    sharded = jit_shard_preprocess(mesh)
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        compiled = sharded.lower(coo, seeds, fanouts=fanouts, key=key,
                                 cfg=cfg).compile()
        t_compile = time.perf_counter() - t0
        t0 = time.perf_counter()
        sub4 = jax.block_until_ready(compiled(coo, seeds, key=key))
        t_run = time.perf_counter() - t0
    sub1 = preprocess_jit(coo, seeds, fanouts, key, cfg)
    same_tree(sub4, sub1, "4-chip shard_preprocess vs single chip")
    spans = sorted(len(x.sharding.device_set) for x in jax.tree.leaves(sub4))
    check(spans[0] == 4, f"output spans {spans[0]} devices, not 4")
    peaks = peak_bytes(devices)
    if devices[0].platform == "tpu":
        check(all(p and p > 0 for p in peaks),
              f"a device did no work: peak_bytes_in_use {peaks}")
    return {"result": "4-chip shard_preprocess bit-identical to the "
                      "single-chip preprocess; output on 4 devices",
            "n_nodes": n_nodes, "n_edges": n_edges,
            "compile_s": t_compile, "run_s": t_run,
            "memory_analysis_per_device": compiled_memory(compiled),
            "peak_bytes_in_use": peaks}


def run_phase(name: str, fn, *args, **kwargs):
    """Run one phase, print its report and the device peak; a failure
    propagates."""
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    ret, report = out if isinstance(out, tuple) else (None, out)
    report = {**report, "wall_s": time.perf_counter() - t0,
              "peak_bytes_in_use": report.get("peak_bytes_in_use",
                                               peak_bytes())}
    log(name, json.dumps(report, default=str))
    return ret


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded preprocess phase")
    args = ap.parse_args(argv)

    import jax
    # the serve phase runs two requests on the CPU backend as well
    platforms = jax.config.jax_platforms
    if platforms and "cpu" not in platforms.split(","):
        jax.config.update("jax_platforms", platforms + ",cpu")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this smoke test runs only on the chip", file=sys.stderr)
        return 2
    from repro.configs import get_config
    from repro.configs.base import GNN_SHAPES
    from repro.launch.cache import enable_compile_cache
    log("setup", f"compile cache: {enable_compile_cache()}; device "
                 f"{dev.device_kind} x{len(jax.devices())}")
    shape = GNN_SHAPES[REDDIT]
    fanouts = get_config("graphsage-reddit").sample_sizes
    if args.four_chips:
        run_phase("four_chips", phase_four_chips, shape["n_nodes"],
                  shape["n_edges"], args.seed, fanouts)
    else:
        graph = run_phase("convert", phase_convert, shape["n_nodes"],
                          shape["n_edges"], shape["d_feat"],
                          shape["n_classes"], args.seed)
        run_phase("sweep", phase_sweep, SWEEP_NODES, SWEEP_EDGES,
                  args.seed + 1, fanouts)
        run_phase("serve", phase_serve, graph, args.seed + 2)
        run_phase("gat", phase_gat, SWEEP_NODES, SWEEP_EDGES, args.seed + 4)
        run_phase("kernels", phase_kernels, args.seed + 3)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
