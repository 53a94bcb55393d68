#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: the program's number on
many seeds, and the control's, which must fail.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 7,8,9 --seconds 8 [--out FILE]

The benchmark's own runs never run this. For each seed it runs the cell's
own timed path at the cell's size and load (a window of ``--seconds``),
and compares what it produced with the plain reference, as ``run.py``
does. For the control seeds it also reads the control:

* a serving cell: the program's own slot function one step of precision
  below the configuration's (float32 at ``high`` below ``highest``;
  bfloat16, the weights cast, below other float32), and each further step
  below, and as a second witness the reference at each such precision, all
  over the same requests: the gap of the class the lower precision puts
  first;
* a convert cell: a CSC sorted by destination alone, which breaks the
  guarantee that each column's sources are ascending.

Prints one JSON object per seed and writes them all to ``--out``.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]


# The precisions below each one a configuration may state, nearest first:
# float32 at ``high`` (three bfloat16 passes) below ``highest``, then
# bfloat16; bfloat16 below any other float32.
LOWER = {("float32", "highest"): [("float32", "high"), ("bfloat16", "default")],
         ("float32", "high"): [("bfloat16", "default")],
         ("float32", "default"): [("bfloat16", "default")]}


def lower_precisions(model: dict) -> list[tuple[str, str]]:
    """The (dtype, matmul precision) steps below the model's, nearest
    first; the first is the control."""
    return LOWER[(model["dtype"], model["matmul_precision"])]


def serve_readings(cell, seed, seconds, control, log):
    """{"program": gap, "unanswered": n, and with ``control`` the gaps of
    each lower precision} for one seed."""
    import jax.numpy as jnp
    from bench import traffic as traffic_gen
    from bench.drivers import serve_open_loop as drv

    eng = drv.build(cell, seed, log)
    drv.warm_up(eng, cell, seed)
    n_nodes = cell.config["graph"]["n_nodes"]
    sched = traffic_gen.schedule(cell.traffic, n_nodes, seed, seconds)
    handles, *_ = drv.open_loop(eng, sched, seconds,
                                cell.traffic["drain_s"])
    rows, rids, served = drv.checked_requests(handles, cell.traffic, seed,
                                              eng.seed_cap)
    out = {"seed": seed, "requests": len(rids),
           "predictions": int(sum(map(len, served))),
           "unanswered": int(sum(h.finish_t is None for h in handles))}
    steps = lower_precisions(cell.config["model"]) if control else []
    low = {f"{d}@{p}": program_classes(eng, rows, rids, served, d, p)
           for d, p in steps}
    del eng
    gc.collect()
    logits = drv.reference_logits(cell, seed, rows, rids)
    out["program"] = drv.widest_gap(logits, served)
    for d, p in steps:
        out[f"control_program_{d}@{p}"] = drv.widest_gap(logits,
                                                         low[f"{d}@{p}"])
        low_ref = [lg.argmax(-1) for lg in drv.reference_logits(
            cell, seed, rows, rids, dtype=jnp.dtype(d), precision=p)]
        out[f"control_reference_{d}@{p}"] = drv.widest_gap(logits, low_ref)
    return out


def program_classes(eng, rows, rids, served, dtype, precision):
    """The classes the program's own slot function serves for the same
    requests with the model's ``dtype`` (weights cast) and matmul
    ``precision`` changed, on the same graph, features and per-request
    keys."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.serve.gnn import build_slot_fn
    gcfg = dataclasses.replace(eng.gcfg, dtype=jnp.dtype(dtype))
    bundle = {**eng.params, "gnn": jax.tree.map(
        lambda x: x.astype(dtype), eng.params["gnn"])}
    slot = jax.jit(build_slot_fn(gcfg, eng.fanouts, eng.seed_cap,
                                 eng.engine_cfg))
    with jax.default_matmul_precision(precision):
        return [np.asarray(slot(bundle, jnp.asarray(rows[j]),
                                eng.request_key(int(rids[j]))))[
                    :len(served[j])].tolist() for j in range(len(rids))]


def convert_readings(cell, seed, control, log):
    """{"program": mismatches, and with ``control`` the control's}."""
    import jax
    import jax.numpy as jnp
    from bench import graphgen
    from bench.references import csc as ref_csc
    from repro.core.costmodel import EngineConfig
    from repro.core.graph import COO
    from repro.engine import service

    g = cell.config["graph"]
    n, e = g["n_nodes"], g["n_edges"]
    dst, src = graphgen.graph_arrays(seed, cell.config)
    coo = COO(dst=dst, src=src, n_edges=jnp.int32(e), n_nodes=n)
    csc = jax.block_until_ready(service.convert_jit(coo, cfg=EngineConfig()))
    ref_ptr, ref_idx = ref_csc.plain_csc(dst, src, n_nodes=n)
    out = {"seed": seed, "program": int(ref_csc.mismatches(
        csc.ptr, csc.idx, csc.n_edges, ref_ptr, ref_idx, jnp.int32(e)))}
    del csc, coo
    if control:
        c_ptr, c_idx = ref_csc.dst_only_csc(dst, src, n_nodes=n)
        out["control_dst_only"] = int(ref_csc.mismatches(
            c_ptr, c_idx, jnp.int32(e), ref_ptr, ref_idx, jnp.int32(e)))
    return out


def readings(name, seeds, control_seeds, seconds, bench_dir=None,
             spec_path=None, log=None):
    from bench import harness
    log = log or (lambda m: print(m, file=sys.stderr, flush=True))
    cell = harness.load_cell(name, bench_dir or harness.BENCH_DIR, spec_path)
    out = []
    for s in dict.fromkeys([*seeds, *control_seeds]):
        t = time.perf_counter()
        if cell.traffic["kind"] == "serve_open_loop":
            r = serve_readings(cell, s, seconds, s in control_seeds, log)
        else:
            r = convert_readings(cell, s, s in control_seeds, log)
        r["seconds"] = time.perf_counter() - t
        print(json.dumps(r), flush=True)
        out.append(r)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from bench import harness
    try:
        harness.devices(1)
    except harness.NoDevice as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    ints = [int(x) for x in args.seeds.split(",") if x]
    ctl = [int(x) for x in args.control_seeds.split(",") if x]
    out = readings(args.workload, ints, ctl, args.seconds)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
