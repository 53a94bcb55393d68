"""Convert/sort microbench — strategy-dispatched engine vs XLA baseline.

Seeds the BENCH trajectory: emits ``BENCH_convert.json`` (repo root) with
median wall-clock per call for the graph-conversion paths at three scales —
the shape ``sample_subgraph`` re-converts every step (16k), a mid graph
(131k) and a large graph (1M edges) — comparing the three ``sort_strategy``
values, the Table-I auto dispatch, the two-pass key scheme and the XLA
comparison-sort baseline. The headline series is
``speedup_packed_vs_xla``: the auto-dispatched engine path over the XLA
lexsort baseline, which the chunked-merge ladder used to LOSE at scale
(0.71× at 131k in PR 3). The dispatch wins it back twice over: the
global-radix strategy halves the radix path (zero merge rounds), and on
CPU hosts the calibrated model hands large graphs to the native-sort
strategy (packed keys-only, rank-searched pointers) — each strategy a
different winner per platform, which is the §V reconfiguration story.
CPU-host proxy numbers: absolute times are not TPU times, but the
pass-structure contrast (zero merge rounds vs log_k ladder vs comparison
sort) is schedule-level and survives the port.

Trajectory note (PR 5): ``packed_us`` and ``speedup_packed_vs_two_pass``
up to the PR-3/PR-4 records measured the pinned ``sort_mode="packed"``
chunked path; from PR 5 they alias the auto-DISPATCHED engine path
(``auto_us`` is the canonical name — at 1M edges the dispatch isn't even
the packed key scheme, the VID space forces two-pass). Compare across
PRs on ``auto_us``/strategy columns, not on the legacy names.

The Reindexing primitive re-runs on every sampled subgraph, so its tail
bounds steady-state serve throughput; it is a fused SCR epilogue (ONE
shared VID sort + rank-arithmetic numbering + unrolled rename gathers,
dispatched per ``reindex_strategy``), and the ``subgraph_reconvert`` case
times the full ``sample_subgraph`` hot path end-to-end per reindex
strategy, recording what ``auto`` picked. Per-stage device time comes
from the named scopes in a chip trace (``bench/scopes.py``), not from
this CPU proxy.

Trajectory note (PR 10): the ``delta_update`` case times the incremental
conversion path — ``apply_delta`` splicing an insert/delete batch into a
sorted CSC at O(delta) — against both a full re-convert of the combined
buffer (``rebuild``) and the from-scratch ``convert`` of the graph, at
the delta fractions a living-graph serve path sees (0.1% / 1% / 10%).
The headline series is ``speedup_vs_rebuild`` at fractions ≤ 1%, plus
the Table-I delta model's merge→rebuild crossover fraction.

``run(smoke=True)`` (CI: ``python -m benchmarks.run convert --smoke``)
shrinks the cases and asserts STRUCTURE instead of wall-clock: bit-equal
CSC outputs across every strategy, one compiled program per jitted path,
the cost model dispatching global_radix exactly where the merge
ladder is non-empty, and (PR 7) the auto reindex dispatch tracing the
exact program of the strategy the model priced, with subgraphs
bit-identical across fused/unfused/auto.
"""
from __future__ import annotations

import dataclasses
import json
import os
from functools import partial

import jax
import numpy as np

import jax.numpy as jnp

from repro.core import (EdgeDelta, EngineConfig, Workload, apply_delta,
                        convert, convert_xla, merge_round_count,
                        resolve_delta_mode, resolve_reindex_strategy,
                        resolve_sort_strategy, sample_subgraph)
from repro.core.costmodel import (digit_pass_count, reindex_query_count,
                                  sample_edge_capacity, sample_vid_capacity)
from repro.core.graph import next_pow2

from .common import emit, make_graph, time_fn

OUT_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_convert.json")
# smoke runs must not clobber the committed BENCH trajectory (CI uploads
# BENCH_*.json artifacts either way)
SMOKE_OUT_PATH = OUT_PATH.replace(".json", "_smoke.json")

# (label, n_edges, w_upe): subgraph-conversion scale (what sample_subgraph
# re-converts per training step), graph-conversion scale, and the 1M-edge
# scale where the PR-3 chunked ladder lost to XLA. w_upe=1024 puts the
# merge ladder (where global_radix wins its rounds back) at realistic
# depth; 1M keeps the same chunk so the ladder is 10 rounds deep.
CASES = [
    ("subgraph_16k", 16384, 1024, 7),
    ("graph_131k", 131072, 1024, 7),
    ("graph_1m", 1 << 20, 1024, 5),
]

SMOKE_CASES = [
    ("smoke_4k", 4096, 256, 2),
    ("smoke_16k", 16384, 256, 2),
]

# (label, n_edges, iters): delta-splice scales. Edge counts sit BELOW the
# pow2 index capacity so the insert batch fits the bucket without growing
# it — ONE compiled program per scale, the serve-path steady state.
DELTA_CASES = [
    ("graph_131k", (1 << 17) - (1 << 14), 7),
    ("graph_1m", (1 << 20) - (1 << 17), 5),
]
SMOKE_DELTA_CASES = [
    ("smoke_16k", (1 << 14) - (1 << 11), 2),
]
DELTA_FRACTIONS = (0.001, 0.01, 0.1)


def _make_delta(coo, frac: float, rng) -> EdgeDelta:
    """Insert/delete batch of ``frac * n_edges`` edges each: deletes
    sampled (without replacement) from the live edge list, inserts drawn
    uniformly — the churn shape of a living graph."""
    n_edges = int(coo.n_edges)
    d = max(1, int(n_edges * frac))
    kill = rng.choice(n_edges, size=d, replace=False)
    dst = np.asarray(coo.dst)[:n_edges]
    src = np.asarray(coo.src)[:n_edges]
    ins_dst = rng.integers(0, coo.n_nodes, d).astype(np.int32)
    ins_src = rng.integers(0, coo.n_nodes, d).astype(np.int32)
    return EdgeDelta.from_arrays(ins_dst, ins_src, dst[kill], src[kill],
                                 n_nodes=int(coo.n_nodes))


def _delta_update_case(smoke: bool) -> dict:
    """Incremental conversion (PR 10): ``apply_delta`` splices an
    insert/delete batch into a sorted CSC at O(delta) — delta-only sorts,
    SENTINEL tombstone routing, ONE merge rung, local pointer patch —
    timed against a full re-convert of the combined buffer (``rebuild``)
    and the from-scratch ``convert``. Records what the Table-I delta
    terms dispatch for ``mode="auto"`` per fraction and the model's
    merge→rebuild crossover fraction.

    Smoke asserts STRUCTURE: merge and rebuild outputs bit-identical,
    one compiled program per pinned mode, and the auto dispatch tracing
    the exact program of the mode the model priced. The full run asserts
    speedup floors instead: ≥5× over rebuild at 0.1% deltas (both
    scales) and ≥3× at 1% (131k measures ~4.4×, 1M ~30×).
    """
    out: dict = {}
    for label, n_edges, iters in (SMOKE_DELTA_CASES if smoke
                                  else DELTA_CASES):
        coo = make_graph(n_edges)
        cap = coo.capacity
        base = EngineConfig(w_upe=256 if smoke else 1024, n_upe=8)
        conv = _jit_convert(base)
        csc = jax.block_until_ready(conv(coo))
        convert_us = time_fn(conv, coo, iters=iters, warmup=2)
        rng = np.random.default_rng(1)
        row: dict = {"n_edges": n_edges, "capacity": cap,
                     "convert_us": convert_us, "fractions": {}}
        for frac in DELTA_FRACTIONS:
            delta = _make_delta(coo, frac, rng)
            d = max(1, int(n_edges * frac))
            w = Workload(n=int(csc.n_nodes), e=cap)
            mode_auto = resolve_delta_mode(base, w, delta.capacity)
            fns = {m: jax.jit(partial(apply_delta, cfg=base, mode=m,
                                      out_capacity=cap))
                   for m in ("merge", "rebuild")}
            merge_us = time_fn(fns["merge"], csc, delta, iters=iters,
                               warmup=2)
            rebuild_us = time_fn(fns["rebuild"], csc, delta, iters=iters,
                                 warmup=2)
            fr = {"d": d, "d_cap": delta.capacity, "mode_auto": mode_auto,
                  "merge_us": merge_us, "rebuild_us": rebuild_us,
                  "speedup_vs_rebuild": rebuild_us / merge_us,
                  "speedup_vs_convert": convert_us / merge_us}
            emit(f"delta/{label}/frac_{frac}", merge_us,
                 f"rebuild={rebuild_us:.1f},auto={mode_auto}")
            if smoke:
                got_m = jax.block_until_ready(fns["merge"](csc, delta))
                got_r = jax.block_until_ready(fns["rebuild"](csc, delta))
                assert int(got_m.n_edges) == int(got_r.n_edges)
                assert np.array_equal(np.asarray(got_m.ptr),
                                      np.asarray(got_r.ptr))
                assert np.array_equal(np.asarray(got_m.idx),
                                      np.asarray(got_r.idx))
                for m, fn in fns.items():
                    assert fn._cache_size() == 1, (m, fn._cache_size())
                jx_auto = str(jax.make_jaxpr(partial(
                    apply_delta, cfg=base, mode="auto",
                    out_capacity=cap))(csc, delta))
                jx_pin = str(jax.make_jaxpr(partial(
                    apply_delta, cfg=base, mode=mode_auto,
                    out_capacity=cap))(csc, delta))
                assert jx_auto == jx_pin, ("auto delta dispatch traced a "
                                           f"different program than "
                                           f"{mode_auto}")
            elif frac <= 0.001:
                assert fr["speedup_vs_rebuild"] >= 5.0, (label, frac, fr)
            elif frac <= 0.01:
                # the 131k scale sits at ~4.4× here (the splice's
                # E·log D pass is a real fraction of the 262k combined
                # sort); 1M is ~30× — floor both as regression canaries
                assert fr["speedup_vs_rebuild"] >= 3.0, (label, frac, fr)
            row["fractions"][str(frac)] = fr
        # model crossover: the smallest delta fraction where the Table-I
        # delta terms hand the splice back to a full rebuild
        for frac in (0.001, 0.01, 0.05, 0.1, 0.15, 0.2,
                     0.25, 0.3, 0.4, 0.5):
            d_cap = next_pow2(max(1, int(n_edges * frac)))
            if resolve_delta_mode(base, Workload(n=int(csc.n_nodes), e=cap),
                                  d_cap) == "rebuild":
                row["auto_crossover_fraction"] = frac
                break
        else:
            row["auto_crossover_fraction"] = None
        if smoke:
            emit(f"delta/{label}/structure", 0.0, "asserts=passed")
        out[label] = row
    return out


def _jit_convert(cfg: EngineConfig):
    return jax.jit(partial(convert, cfg=cfg))


def _subgraph_reconvert_case(smoke: bool, iters: int) -> dict:
    """The serving hot path end-to-end: ``sample_subgraph`` re-converts a
    fresh subgraph every step (select → reindex → sub-sort → pointers).
    Timed per ``reindex_strategy`` so the fused SCR epilogue's win over
    the loop-based build is measured where it matters, plus what the
    Table-I model dispatches for ``auto``.

    Smoke asserts: the auto dispatch TRACED the exact program of the
    strategy the model priced (jaxpr equality, the same gate the sort
    dispatch gets), and subgraphs are bit-identical across strategies.
    """
    coo = make_graph(4096 if smoke else 16384)
    base = EngineConfig(w_upe=256 if smoke else 1024, n_upe=8)
    csc = jax.block_until_ready(jax.jit(partial(convert, cfg=base))(coo))
    fanouts, batch = (4, 3), 64
    bn = jnp.arange(batch, dtype=jnp.int32)
    key = jax.random.PRNGKey(0)
    w = Workload(n=int(csc.n_nodes), e=int(csc.idx.shape[0]),
                 l=len(fanouts), k=max(fanouts), b=batch)
    n_cap = next_pow2(sample_vid_capacity(w))
    r_auto = resolve_reindex_strategy(
        base, reindex_query_count(n_cap, sample_edge_capacity(w)), n_cap)
    row: dict = {"n_edges": int(coo.n_edges), "batch": batch,
                 "fanouts": list(fanouts), "reindex_strategy_auto": r_auto}
    jits, subs = {}, {}
    for strat in ("fused", "unfused", "auto"):
        cfg = dataclasses.replace(base, reindex_strategy=strat)
        jits[strat] = jax.jit(partial(sample_subgraph, fanouts=fanouts,
                                      cfg=cfg))
        us = time_fn(jits[strat], csc, bn, key=key, iters=iters, warmup=2)
        row[f"sample_{strat}_us"] = us
        emit(f"subgraph_reconvert/{strat}", us, f"auto={r_auto}")
        if smoke:
            subs[strat] = jax.block_until_ready(jits[strat](csc, bn, key=key))
    if smoke:
        ref = subs["fused"]
        for strat, sub in subs.items():
            assert np.array_equal(np.asarray(sub.csc.ptr),
                                  np.asarray(ref.csc.ptr)), strat
            assert np.array_equal(np.asarray(sub.csc.idx),
                                  np.asarray(ref.csc.idx)), strat
            assert np.array_equal(np.asarray(sub.order),
                                  np.asarray(ref.order)), strat
        auto_cfg = dataclasses.replace(base, reindex_strategy="auto")
        pinned_cfg = dataclasses.replace(base, reindex_strategy=r_auto)
        jx_auto = str(jax.make_jaxpr(
            partial(sample_subgraph, fanouts=fanouts, cfg=auto_cfg))(
                csc, bn, key=key))
        jx_pinned = str(jax.make_jaxpr(
            partial(sample_subgraph, fanouts=fanouts, cfg=pinned_cfg))(
                csc, bn, key=key))
        assert jx_auto == jx_pinned, \
            f"auto reindex dispatch traced a different program than {r_auto}"
        emit("subgraph_reconvert/structure", 0.0, "asserts=passed")
    return row


def run(smoke: bool = False) -> dict:
    results: dict = {"cases": {}}
    for label, n_edges, w_upe, iters in (SMOKE_CASES if smoke else CASES):
        coo = make_graph(n_edges)
        base = EngineConfig(w_upe=w_upe, n_upe=8)
        w = Workload(n=coo.n_nodes, e=coo.capacity)
        strategy_auto = resolve_sort_strategy(base, w)
        rows: dict = {}
        jits: dict = {}
        # the three reduction structures, pinned, + the Table-I dispatch
        for strat in ("chunked_merge", "global_radix", "xla_sort", "auto"):
            cfg = dataclasses.replace(base, sort_strategy=strat)
            jits[strat] = _jit_convert(cfg)
            rows[strat] = time_fn(jits[strat], coo, iters=iters, warmup=2)
            emit(f"convert/{label}/{strat}", rows[strat], f"e={n_edges}")
        # key-scheme A/B (the packed row IS the engine path when the VID
        # space fits; at 1M the auto mode falls back to two-pass LSD)
        cfg_two = dataclasses.replace(base, sort_mode="two_pass")
        rows["two_pass"] = time_fn(_jit_convert(cfg_two), coo, iters=iters,
                                   warmup=2)
        emit(f"convert/{label}/two_pass", rows["two_pass"], f"e={n_edges}")
        rows["xla"] = time_fn(jax.jit(convert_xla), coo, iters=iters,
                              warmup=2)
        emit(f"convert/{label}/xla", rows["xla"], f"e={n_edges}")
        speedup_two = rows["two_pass"] / rows["auto"]
        speedup_xla = rows["xla"] / rows["auto"]
        emit(f"convert/{label}/speedup_packed_vs_xla", speedup_xla,
             f"auto={strategy_auto}")
        results["cases"][label] = {
            "n_edges": n_edges,
            "n_nodes": int(coo.n_nodes),
            "w_upe": w_upe,
            "strategy_auto": strategy_auto,
            "merge_rounds_chunked": merge_round_count(base, w,
                                                      "chunked_merge"),
            "digit_passes": digit_pass_count(base, w),
            "chunked_merge_us": rows["chunked_merge"],
            "global_radix_us": rows["global_radix"],
            "xla_sort_us": rows["xla_sort"],
            "auto_us": rows["auto"],
            "packed_us": rows["auto"],  # trajectory alias — see docstring
            "two_pass_us": rows["two_pass"],
            "xla_us": rows["xla"],
            "speedup_packed_vs_two_pass": speedup_two,
            "speedup_packed_vs_xla": speedup_xla,
        }
        if smoke:
            _assert_structure(coo, base, jits, results["cases"][label])
    results["subgraph_reconvert"] = _subgraph_reconvert_case(
        smoke, iters=2 if smoke else 7)
    results["delta_update"] = _delta_update_case(smoke)
    with open(SMOKE_OUT_PATH if smoke else OUT_PATH, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")
    return results


def _assert_structure(coo, base: EngineConfig, jits: dict, row: dict) -> None:
    """CI smoke gates — structure, not wall-clock (CPU runners jitter).

    1. bit-identical CSC across every sort_strategy and vs the XLA sort;
    2. exactly one compiled program per jitted strategy path (the timing
       loop must not have re-traced);
    3. the model's zero-merge-round claim holds for global_radix, the
       auto dispatch TRACED the exact program of the strategy the model
       priced (jaxpr equality against the pinned-strategy convert — this
       is where a divergence between ``convert``'s internal resolution
       and the benchmark's would surface), and global_radix outranks
       chunked_merge wherever the benchmark measured it winning (every
       case with a ladder ≥ 3 rounds deep).
    """
    from repro.core.costmodel import Calibration, _ordering_seconds
    ref = jax.block_until_ready(convert_xla(coo))
    for strat, fn in jits.items():
        got = jax.block_until_ready(fn(coo))
        assert np.array_equal(np.asarray(got.ptr), np.asarray(ref.ptr)), strat
        e = int(coo.n_edges)
        assert np.array_equal(np.asarray(got.idx)[:e],
                              np.asarray(ref.idx)[:e]), strat
        assert fn._cache_size() == 1, (strat, fn._cache_size())
    w = Workload(n=coo.n_nodes, e=coo.capacity)
    assert merge_round_count(base, w, "global_radix") == 0
    auto_cfg = dataclasses.replace(base, sort_strategy="auto")
    pinned_cfg = dataclasses.replace(base, sort_strategy=row["strategy_auto"])
    jaxpr_auto = str(jax.make_jaxpr(partial(convert, cfg=auto_cfg))(coo))
    jaxpr_pinned = str(jax.make_jaxpr(partial(convert, cfg=pinned_cfg))(coo))
    assert jaxpr_auto == jaxpr_pinned, \
        f"auto dispatch traced a different program than {pinned_cfg.key}"
    if row["merge_rounds_chunked"] >= 3:
        cal = Calibration()
        assert (_ordering_seconds(base, w, cal, "global_radix")
                < _ordering_seconds(base, w, cal, "chunked_merge")), row
    emit(f"convert/{row['n_edges']}/structure", 0.0, "asserts=passed")


if __name__ == "__main__":
    import sys
    jax.config.update("jax_platform_name", "cpu")
    print("name,us_per_call,derived")
    run(smoke="--smoke" in sys.argv)
