#!/usr/bin/env python3
"""Readings that set the inference cell's correctness limits: the
program's numbers on many seeds, and the control's, which must fail.

    python3 bench/control_infer.py --workload products-gat-infer \\
        --seeds 1,2,... --control-seeds 7,8 [--out FILE]

The benchmark's own runs never run this. For each seed it makes the
cell's graph, features and weights, runs one whole-graph inference as the
timed path runs it, and compares its logits with the plain reference
(float32 at ``highest``) over every node, as ``run.py`` does. For the
control seeds it also reads the reference one precision below the
configuration's (float32 at ``high``): bfloat16, weights and features
cast, against the float32 reference.

Prints one JSON object per seed (with the program's device memory peak
and the reference's seconds) and writes those so far to ``--out`` after
each.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def readings(cell, seed, control, log):
    """{"pred_gap", "logit_err"} of the program for ``seed``, and with
    ``control`` the same of the reference in bfloat16."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench.drivers import infer_closed_loop as drv
    from repro.engine.service import gat_infer_jit

    m = cell.config["model"]
    jax.config.update("jax_default_matmul_precision", m["matmul_precision"])
    csc, feats, params = drv.build(cell, seed)
    out, counters = gat_infer_jit(csc, feats, params,
                                  cfg=drv.gnn_config(cell))
    logits = np.asarray(out, np.float32)
    log(f"seed {seed}: {dict((k, int(v)) for k, v in counters.items())}")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    del csc, out
    gc.collect()
    t = time.perf_counter()
    ref = drv.reference_logits(cell, seed, feats, params)
    r = {"seed": seed, "program": dict(zip(("pred_gap", "logit_err"),
                                           drv.gaps(logits, ref))),
         "memory_peak_bytes": peak,
         "reference_s": time.perf_counter() - t}
    if control:
        low = drv.reference_logits(cell, seed, feats, params,
                                   dtype=jnp.bfloat16, precision="default")
        r["control_reference_bfloat16"] = dict(zip(
            ("pred_gap", "logit_err"), drv.gaps(low, ref)))
    return r


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from bench import harness
    try:
        harness.devices(1)
    except harness.NoDevice as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    seeds = [int(x) for x in args.seeds.split(",") if x]
    ctl = [int(x) for x in args.control_seeds.split(",") if x]
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    out = []
    for s in dict.fromkeys([*seeds, *ctl]):
        t = time.perf_counter()
        r = readings(cell, s, s in ctl, log)
        r["seconds"] = time.perf_counter() - t
        print(json.dumps(r), flush=True)
        out.append(r)
        if args.out:
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
