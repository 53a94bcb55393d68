"""Closed loop of whole-graph GAT inferences: ``engine.service.gat_infer_jit``
over the CSC that ``convert_jit`` makes of the configuration's graph, back
to back on one graph, features and weights.

Set-up makes the graph, converts it (the ``products-convert`` program),
frees the COO, makes the features and weights, and compiles and runs one
inference. The loop keeps ``traffic["in_flight"]`` inferences dispatched
ahead of the one it waits for. The window runs from the first dispatch
until every inference dispatched before ``seconds`` had elapsed has
completed; each predicts every node of the graph. Correctness: the last
inference's logits against the plain reference (``references/gat.py``),
over every node, once the window is closed and the program's buffers are
freed.
"""
from __future__ import annotations

import collections
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import graphgen, harness, inputs_gat, tracing
from bench.references import gat as ref_gat


class Readings:
    """What the inference cell's per-layer readers read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def gnn_config(cell, dtype=None):
    """The program's ``GNNConfig`` of the cell's model block."""
    from repro.models.gnn import GNNConfig
    m = cell.config["model"]
    return GNNConfig(name=cell.config["name"], kind="gat",
                     n_layers=m["n_layers"], d_hidden=m["d_head"],
                     n_heads=m["heads"], out_heads=m["out_heads"],
                     skip=m["skip"],
                     dtype=jnp.dtype(dtype or m["dtype"]))


def build(cell, seed):
    """(CSC, features, weights) for ``seed``; the COO is freed."""
    from repro.core.costmodel import EngineConfig
    from repro.core.graph import COO
    from repro.engine import service
    g = cell.config["graph"]
    dst, src = graphgen.graph_arrays(seed, cell.config)
    coo = COO(dst=dst, src=src, n_edges=jnp.int32(g["n_edges"]),
              n_nodes=g["n_nodes"])
    csc = jax.block_until_ready(service.convert_jit(coo, cfg=EngineConfig()))
    del coo, dst, src
    feats, params = inputs_gat.gat_inputs(seed, cell.config)
    return csc, feats, params


def reference_logits(cell, seed, feats, params, dtype=jnp.float32,
                     precision="highest") -> np.ndarray:
    """The plain reference's logits of every node, on the host."""
    g = cell.config["graph"]
    dst, src = graphgen.graph_arrays(seed, cell.config)
    return ref_gat.logits(dst, src, feats, params, n_nodes=g["n_nodes"],
                          dtype=dtype, precision=precision)


def gaps(logits: np.ndarray, ref: np.ndarray) -> tuple[float, float]:
    """(pred_gap, logit_err): the widest gap, over every node, by which the
    reference logit of the class ``logits`` puts first lies below the
    reference's best, and the widest absolute difference of any logit."""
    pick = np.argmax(logits, axis=1)
    gap = ref.max(axis=1) - ref[np.arange(len(ref)), pick]
    return float(gap.max()), float(np.abs(logits - ref).max())


def run(cell, devs, *, seed, seconds, trace, t_process, peaks, log):
    from repro.engine.service import gat_infer_jit
    from repro.models.gnn import EDGE_CHUNK

    g, m = cell.config["graph"], cell.config["model"]
    jax.config.update("jax_default_matmul_precision", m["matmul_precision"])
    gcfg = gnn_config(cell)
    csc, feats, params = build(cell, seed)
    jax.block_until_ready(gat_infer_jit(csc, feats, params, cfg=gcfg))
    session = (tracing.Session(harness.REPO / ".bench_traces" / cell.name)
               if trace else None)
    if session:
        session.start()
    ahead = int(cell.traffic["in_flight"])
    t0 = time.perf_counter()
    setup_s = t0 - t_process
    n, done, last = 0, 0, None
    flight = collections.deque()
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() - t0 < seconds:
            with jax.profiler.TraceAnnotation("bench.infer"):
                last = gat_infer_jit(csc, feats, params, cfg=gcfg)
            flight.append(last)
            n += 1
            if len(flight) > ahead:
                jax.block_until_ready(flight.popleft())
                done += 1
            if session and session.t_stop is None and (
                    time.perf_counter() - session.t_start
                    >= cell.traffic["trace_s"]):
                session.stop()
        jax.block_until_ready(list(flight))
        done += len(flight)
        flight.clear()
    window_s = time.perf_counter() - t0
    if session and session.t_stop is None:
        session.stop()
    summary = session.summary(devs) if session else None
    log(f"{n} inferences in {window_s:.4f} s; setup {setup_s:.4f} s")
    peak = devs[0].memory_stats() or {}
    logits = np.asarray(last[0])
    counters = {k: int(v) for k, v in last[1].items()}
    log(f"counters {counters}")

    # the reference, once the window is closed and the program is freed
    del csc, last
    gc.collect()
    t_ref = time.perf_counter()
    pred_gap, logit_err = gaps(logits, reference_logits(cell, seed, feats,
                                                        params))
    log(f"reference check {time.perf_counter() - t_ref:.4f} s")
    limits = cell.config["limits"]
    readings = Readings(cell=cell, trace=summary, peaks=peaks,
                        n_inferences=n, window_s=window_s,
                        counters=counters, chunk_edges=EDGE_CHUNK)
    return harness.Outcome(
        end_to_end={"preds_per_s": g["n_nodes"] * done / window_s,
                    "setup_s": setup_s},
        readings=readings, attempted=n, failed=n - done,
        checks=[("failed", n - done, limits["failed"]),
                ("pred_gap", pred_gap, limits["pred_gap"]),
                ("logit_err", logit_err, limits["logit_err"])],
        memory_peak_bytes=peak.get("peak_bytes_in_use"), trace=summary)
