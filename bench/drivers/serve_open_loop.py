"""Open-loop GraphSAGE serving: ``serve.GnnServeEngine`` over the whole
graph, fed requests at their scheduled arrival times.

Set-up makes the graph, converts it to the CSC the engine serves
(``engine.service.convert_jit``), makes the features and weights, builds
the engine and warms its programs with a few requests. The window then
submits every request of the schedule at its due time from this thread
while the engine's loop runs in another. A request is timed from its
scheduled arrival to the moment its predictions are on the host. Requests
still open when the window closes are waited for, up to
``traffic["drain_s"]``; any that never answer count as failed, and make
the run not correct.

Correctness: a sample of the answered requests, drawn from the seed with
the longest requests in it, is run through the plain reference
(``references/graphsage.py``) once the engine is freed. The number
compared is the widest gap by which a served class's reference logit lies
below the reference's best logit for that seed node.
"""
from __future__ import annotations

import gc
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import graphgen, harness, tracing, traffic as traffic_gen
from bench.references import graphsage as ref
from bench.references.csc import plain_csc

SENTINEL = 0x7FFFFFFF


class Readings:
    """What the serving cell's per-layer readers read: per-request host
    times of the window (seconds on ``time.perf_counter``), the engine's
    counters over the window, and the trace."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def key_seed(seed: int) -> int:
    """The engine's sampling seed: a 31-bit fold of ``seed``."""
    return (int(seed) ^ (int(seed) >> 31)) & 0x7FFFFFFF


def build(cell, seed, log):
    """(engine, CSC) for ``seed``: graph, convert, features, weights,
    engine. Shared with the knee sweep and the control."""
    from repro.core.costmodel import EngineConfig
    from repro.core.graph import COO
    from repro.engine import service
    from repro.models.gnn import GNNConfig
    from repro.serve import GnnServeEngine

    cfg, eng_spec = cell.config, cell.traffic["engine"]
    g, m = cfg["graph"], cfg["model"]
    jax.config.update("jax_default_matmul_precision", m["matmul_precision"])
    dst, src = graphgen.graph_arrays(seed, cfg)
    coo = COO(dst=dst, src=src, n_edges=jnp.int32(g["n_edges"]),
              n_nodes=g["n_nodes"])
    csc = jax.block_until_ready(service.convert_jit(coo, cfg=EngineConfig()))
    del coo, dst, src
    feats, params = graphgen.model_inputs(seed, cfg)
    gcfg = GNNConfig(name=cfg["name"], kind="graphsage",
                     n_layers=m["n_layers"], d_hidden=m["d_hidden"],
                     aggregator=m["aggregator"],
                     sample_sizes=tuple(m["sample_sizes"]),
                     dtype=jnp.dtype(m["dtype"]))
    eng = GnnServeEngine(gcfg, params, csc, feats,
                         n_slots=eng_spec["n_slots"],
                         seed_cap=eng_spec["seed_cap"],
                         key_seed=key_seed(seed))
    return eng


def warm_up(eng, cell, seed):
    """Compile (or load) the engine's programs with a few requests."""
    from repro.serve.slots import ServeStats
    n = cell.config["graph"]["n_nodes"]
    warm = traffic_gen.schedule(cell.traffic, n, seed, 0.0, stream="warmup")
    handles = [eng.submit(s) for s in warm.seeds]
    eng.close_submissions()
    done = eng.run()
    if len(done) != len(handles):
        raise RuntimeError(f"warm-up answered {len(done)} of {len(handles)}")
    eng.reopen()
    eng.stats = ServeStats()


def open_loop(eng, sched, seconds, drain_s, session=None, trace_s=0.0):
    """Drive one window. Returns (handles, due times, window start, window
    end, counters at the window's end, time the engine loop stopped)."""
    done: list = []
    loop = threading.Thread(target=lambda: done.extend(eng.run()),
                            name="bench-serve-loop", daemon=True)
    loop.start()
    t0 = time.perf_counter()
    due = t0 + sched.arrival_s
    handles = []
    trace_at = t0 + seconds - trace_s
    with jax.profiler.TraceAnnotation("bench.window"):
        for t_due, seeds in zip(due, sched.seeds):
            if session and session.t_start is None and t_due >= trace_at:
                session.start()
            wait = t_due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            with jax.profiler.TraceAnnotation("bench.submit"):
                handles.append(eng.submit(seeds))
        wait = t0 + seconds - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
    t_end = time.perf_counter()
    stats = (eng.stats.steps, eng.stats.admitted)
    eng.close_submissions()
    loop.join(timeout=drain_s)
    t_stop = time.perf_counter()
    if session and session.t_start is not None:
        session.stop()
    return handles, due, t0, t_end, stats, t_stop


def checked_requests(handles, traffic, seed, seed_cap):
    """(padded seed rows, request ids, served classes) of the answered
    requests the reference checks: up to ``check["longest"]`` of the
    longest, then others, drawn from the seed, ``check["requests"]`` in
    all."""
    spec = traffic["check"]
    rng = traffic_gen.rng_for(seed, "check")
    answered = [i for i, h in enumerate(handles) if h.finish_t is not None]
    top = max((len(handles[i].prompt) for i in answered), default=0)
    longest = [i for i in answered if len(handles[i].prompt) == top]
    rest = [i for i in answered if len(handles[i].prompt) != top]
    a = rng.permutation(longest)[:spec["longest"]].tolist()
    b = rng.permutation(rest)[:spec["requests"] - len(a)].tolist()
    pick = sorted(a + b)
    rows = np.full((len(pick), seed_cap), SENTINEL, np.int32)
    for j, i in enumerate(pick):
        rows[j, :len(handles[i].prompt)] = handles[i].prompt
    return (rows, np.array([handles[i].rid for i in pick], np.int64),
            [list(handles[i].tokens_out) for i in pick])


def reference_logits(cell, seed, rows, rids, dtype=jnp.float32,
                     precision="highest"):
    """Reference logits [n_seeds, n_classes] of each checked request, in
    blocks of ``check["block"]`` requests. ``rows`` [R, seed_cap] padded
    seed ids, ``rids`` [R] request ids."""
    cfg = cell.config
    g, m = cfg["graph"], cfg["model"]
    block = cell.traffic["check"]["block"]
    dst, src = graphgen.graph_arrays(seed, cfg)
    ptr, idx = plain_csc(dst, src, n_nodes=g["n_nodes"])
    del dst, src
    feats, params = graphgen.model_inputs(seed, cfg)
    ks = key_seed(seed)
    out = []
    for b0 in range(0, len(rids), block):
        n = min(block, len(rids) - b0)
        rows_b = np.full((block, rows.shape[1]), SENTINEL, np.int32)
        rows_b[:n] = rows[b0:b0 + n]
        keys = jnp.stack([ref.request_key(ks, int(r))
                          for r in rids[b0:b0 + n]]
                         + [ref.request_key(ks, 0)] * (block - n))
        logits = np.asarray(ref.request_logits(
            ptr, idx, feats, params, jnp.asarray(rows_b), keys,
            n_nodes=g["n_nodes"], fanouts=tuple(m["sample_sizes"]),
            dtype=dtype, precision=precision))
        out += [logits[j, :(rows[b0 + j] != SENTINEL).sum()]
                for j in range(n)]
    return out


def widest_gap(logits, classes) -> float:
    """The widest gap, over every seed of every request, by which the
    logit of the class given lies below the best logit."""
    worst = 0.0
    for lg, c in zip(logits, classes):
        gap = lg.max(axis=1) - lg[np.arange(len(c)), c]
        worst = max(worst, float(gap.max()))
    return worst


def latency_profile(latency, offset, wait, step_s, seconds) -> str:
    """One stderr line on how the window's latency is spread: its
    percentiles, the 95th by five seconds of the window, the mean step
    period, the share of requests that waited longer than a step for a
    slot (a full wave, or a host stall), and when the slowest was due."""
    ms = 1e3 * latency
    q = np.percentile(ms, [50, 90, 95, 99, 100])
    edges = np.linspace(0.0, seconds, max(1, round(seconds / 5)) + 1)
    by_part = [np.percentile(ms[(offset >= a) & (offset < b)], 95)
               if np.any((offset >= a) & (offset < b)) else float("nan")
               for a, b in zip(edges[:-1], edges[1:])]
    missed = 100.0 * float(np.mean(~(wait <= 1.1 * step_s)))
    return ("latency ms: p50 {:.4f} p90 {:.4f} p95 {:.4f} p99 {:.4f} max "
            "{:.4f}; p95 by 5 s [{}]; step period {:.4f} ms; waited over a "
            "step for a slot {:.4f} %; slowest due at {:.4f} s".format(
                *q, " ".join(f"{v:.2f}" for v in by_part), 1e3 * step_s,
                missed, float(offset[np.argmax(ms)])))


def run(cell, devs, *, seed, seconds, trace, t_process, peaks, log):
    tr = cell.traffic
    n_nodes = cell.config["graph"]["n_nodes"]
    eng = build(cell, seed, log)
    warm_up(eng, cell, seed)
    sched = traffic_gen.schedule(tr, n_nodes, seed, seconds)
    session = (tracing.Session(harness.REPO / ".bench_traces" / cell.name)
               if trace else None)
    setup_end = time.perf_counter()
    handles, due, t0, t_end, (steps, admitted), t_stop = open_loop(
        eng, sched, seconds, tr["drain_s"], session, tr["trace_s"])
    setup_s = t0 - t_process
    log(f"setup {setup_s:.4f} s (schedule ready {setup_end - t_process:.4f}"
        f" s); {len(handles)} requests; {steps} steps")
    summary = session.summary(devs) if session else None
    peak = devs[0].memory_stats() or {}

    finish = np.array([h.finish_t if h.finish_t is not None else np.nan
                       for h in handles])
    admit = np.array([h.admit_t if h.admit_t is not None else np.nan
                      for h in handles])
    submit = np.array([h.enqueue_t for h in handles])
    latency = np.where(np.isnan(finish), t_stop, finish) - due
    failed = int(np.isnan(finish).sum())
    n_seeds = np.array([len(h.prompt) for h in handles])
    preds_in_window = int(n_seeds[finish <= t_end].sum())
    window_s = t_end - t0
    late = submit - due
    log(f"generator late: p50 {1e3 * np.median(late):.4f} ms, max "
        f"{1e3 * late.max():.4f} ms")
    log(latency_profile(latency, due - t0, admit - due,
                        window_s / max(1, steps), seconds))

    # correctness, once the engine is freed
    rows, rids, served = checked_requests(handles, tr, seed, eng.seed_cap)
    step_compiles = eng.step_cache_size()
    del eng
    gc.collect()
    t_ref = time.perf_counter()
    gap = widest_gap(reference_logits(cell, seed, rows, rids), served)
    log(f"reference check of {len(rids)} requests "
        f"({sum(map(len, served))} predictions): "
        f"{time.perf_counter() - t_ref:.4f} s; step programs {step_compiles}")
    limits = cell.config["limits"]
    readings = Readings(
        cell=cell, trace=summary, peaks=peaks, due=due, admit=admit,
        finish=finish, n_seeds=n_seeds, t0=t0, t_end=t_end,
        window_s=window_s, steps=steps, admitted=admitted,
        trace_start=session.t_start if session else None,
        n_slots=tr["engine"]["n_slots"], preds_in_window=preds_in_window,
        latency=latency)
    return harness.Outcome(
        end_to_end={"preds_per_s": preds_in_window / window_s,
                    "setup_s": setup_s},
        readings=readings, attempted=len(handles), failed=failed,
        checks=[("unanswered", failed, limits["unanswered"]),
                ("pred_gap", gap, limits["pred_gap"])],
        memory_peak_bytes=peak.get("peak_bytes_in_use"), trace=summary)
