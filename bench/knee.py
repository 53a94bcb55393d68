#!/usr/bin/env python3
"""The sweep that finds a serving cell's knee: the highest offered rate
the engine sustains with no growing backlog.

    python3 bench/knee.py --workload reddit-serve --seed 5 \\
        --rates 100,200,400 --seconds 8 [--out FILE]

Set-up runs once; each rate then gets an open-loop window of the cell's
traffic at that rate. A rate is sustained when the requests still open at
the window's end are at most two slot waves, and the 95th percentile
latency of the window's last third is at most 1.5 times that of its first
third (plus 5 ms). The sweep stops after two rates in a row that are not
sustained. The benchmark's own runs never run this; its result is the
rate written into the cell's traffic file.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def one_rate(eng, cell, seed, rate, seconds):
    import numpy as np
    from bench import traffic as traffic_gen
    from bench.drivers import serve_open_loop as drv
    from repro.serve.slots import ServeStats
    tr = copy.deepcopy(cell.traffic)
    tr["arrivals"]["rate_per_s"] = rate
    sched = traffic_gen.schedule(tr, cell.config["graph"]["n_nodes"], seed,
                                 seconds)
    handles, due, t0, t_end, (steps, admitted), _ = drv.open_loop(
        eng, sched, seconds, tr["drain_s"])
    eng.reopen()
    eng.stats = ServeStats()
    finish = np.array([np.inf if h.finish_t is None else h.finish_t
                       for h in handles])
    lat = 1e3 * (finish - due)
    third = max(1, len(handles) // 3)
    first = float(np.percentile(lat[:third], 95))
    last = float(np.percentile(lat[-third:], 95))
    backlog = int(np.sum(finish > t_end))
    n_slots = tr["engine"]["n_slots"]
    preds = sum(len(h.prompt) for h, f in zip(handles, finish) if f <= t_end)
    return {"rate_per_s": rate, "requests": len(handles),
            "preds_per_s": preds / (t_end - t0),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "p95_first_third_ms": first, "p95_last_third_ms": last,
            "open_at_end": backlog, "steps": steps,
            "slot_fill_pct": 100.0 * admitted / max(1, steps * n_slots),
            "sustained": bool(backlog <= 2 * n_slots
                              and last <= 1.5 * first + 5.0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from bench import harness
    from bench.drivers import serve_open_loop as drv
    try:
        harness.devices(1)
    except harness.NoDevice as exc:
        print(f"knee: {exc}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    eng = drv.build(cell, args.seed, print)
    drv.warm_up(eng, cell, args.seed)
    rows, misses = [], 0
    for rate in (float(r) for r in args.rates.split(",")):
        row = one_rate(eng, cell, args.seed, rate, args.seconds)
        print(json.dumps(row), flush=True)
        rows.append(row)
        misses = 0 if row["sustained"] else misses + 1
        if misses == 2:
            break
    knee = max((r["rate_per_s"] for r in rows if r["sustained"]),
               default=None)
    print(json.dumps({"knee_per_s": knee}), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"rows": rows, "knee": knee},
                                             indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
