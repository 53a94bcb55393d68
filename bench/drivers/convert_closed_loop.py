"""Closed loop of full-graph conversions: ``engine.service.convert_jit``
under the default ``EngineConfig()``, back to back on one COO.

The loop keeps ``traffic["in_flight"]`` converts dispatched ahead of the
one it waits for, so the chip stays fed while the host stands still. The
window runs from the first dispatch until every convert dispatched before
``seconds`` had elapsed has completed: all of that work over all of that
time. Correctness: the CSC of one convert drawn from the seed among the
first three, and of the last, against the plain reference, entry for
entry.
"""
from __future__ import annotations

import collections
import gc
import time

import jax
import jax.numpy as jnp

from bench import graphgen, harness, tracing
from bench.references import csc as ref_csc
from bench.traffic import rng_for


class Readings:
    """What the convert cell's per-layer readers read."""

    def __init__(self, cell, trace, n_converts, window_s, peaks):
        self.cell, self.trace = cell, trace
        self.n_converts, self.window_s = n_converts, window_s
        self.peaks = peaks


def run(cell, devs, *, seed, seconds, trace, t_process, peaks, log):
    from repro.core.graph import COO
    from repro.core.costmodel import EngineConfig
    from repro.engine import service

    g = cell.config["graph"]
    n_nodes, n_edges = g["n_nodes"], g["n_edges"]
    dst, src = graphgen.graph_arrays(seed, cell.config)
    coo = COO(dst=dst, src=src, n_edges=jnp.int32(n_edges), n_nodes=n_nodes)
    cfg = EngineConfig()
    jax.block_until_ready(service.convert_jit(coo, cfg=cfg))   # warm-up
    keep_at = 1 + int(rng_for(seed, "check").integers(0, 3))
    session = (tracing.Session(harness.REPO / ".bench_traces" / cell.name)
               if trace else None)
    if session:
        session.start()
    ahead = int(cell.traffic["in_flight"])
    t0 = time.perf_counter()
    setup_s = t0 - t_process
    n, kept, last = 0, None, None
    flight = collections.deque()
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() - t0 < seconds:
            with jax.profiler.TraceAnnotation("bench.convert"):
                last = service.convert_jit(coo, cfg=cfg)
            flight.append(last)
            n += 1
            if n == keep_at:
                kept = last
            if len(flight) > ahead:
                jax.block_until_ready(flight.popleft())
            if session and session.t_stop is None and (
                    time.perf_counter() - session.t_start
                    >= cell.traffic["trace_s"]):
                session.stop()
        jax.block_until_ready(list(flight))
        flight.clear()
    t_end = time.perf_counter()
    window_s = t_end - t0
    if session and session.t_stop is None:
        session.stop()
    summary = session.summary(devs) if session else None
    log(f"{n} converts in {window_s:.4f} s; setup {setup_s:.4f} s")
    peak = devs[0].memory_stats() or {}
    memory_peak = peak.get("peak_bytes_in_use")

    # the reference, once the window is closed and the input is freed
    del coo, dst, src
    gc.collect()
    t_ref = time.perf_counter()
    dst, src = graphgen.graph_arrays(seed, cell.config)
    ref_ptr, ref_idx = ref_csc.plain_csc(dst, src, n_nodes=n_nodes)
    del dst, src
    bad = 0
    for out in (kept if kept is not None else last, last):
        bad += int(ref_csc.mismatches(out.ptr, out.idx, out.n_edges,
                                      ref_ptr, ref_idx, jnp.int32(n_edges)))
    log(f"reference check {time.perf_counter() - t_ref:.4f} s")
    limit = cell.config["limits"]["csc_mismatch"]
    return harness.Outcome(
        end_to_end={"convert_ms": 1e3 * window_s / n, "setup_s": setup_s},
        readings=Readings(cell, summary, n, window_s, peaks),
        attempted=n, failed=0, checks=[("csc_mismatch", bad, limit)],
        memory_peak_bytes=memory_peak, trace=summary)
