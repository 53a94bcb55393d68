"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Pass module names to run a
subset: ``python -m benchmarks.run fig5 fig18``. ``--smoke`` shrinks any
suite whose ``run`` accepts a ``smoke`` flag to CI-sized cases with
structural asserts instead of wall-clock gates (the bench-smoke CI job
runs ``python -m benchmarks.run convert --smoke``); in smoke mode a
suite failure exits non-zero so CI catches broken structure.
"""
from __future__ import annotations

import inspect
import sys


def main() -> None:
    import jax
    jax.config.update("jax_platform_name", "cpu")

    from . import (bench_convert, bench_serve, fig5_preproc_fraction,
                   fig10_serialization, fig18_end2end, fig22_reconfig,
                   fig24_costmodel, fig25_sensitivity, fig_engine_overlap,
                   roofline)
    suites = {
        "convert": bench_convert.run,  # emits BENCH_convert.json
        "serve": bench_serve.run,  # emits BENCH_serve.json
        "fig5": fig5_preproc_fraction.run,
        "fig10": fig10_serialization.run,
        "fig18": fig18_end2end.run,
        "fig22": fig22_reconfig.run,
        "fig24": fig24_costmodel.run,
        "fig25": fig25_sensitivity.run,
        "engine": fig_engine_overlap.run,
        "roofline": roofline.run,
    }
    smoke = "--smoke" in sys.argv[1:]
    wanted = [a for a in sys.argv[1:] if a in suites] or list(suites)
    print("name,us_per_call,derived")
    failed = False
    for name in wanted:
        fn = suites[name]
        kwargs = ({"smoke": True} if smoke
                  and "smoke" in inspect.signature(fn).parameters else {})
        try:
            fn(**kwargs)
        except Exception as e:  # noqa: BLE001 — a suite failing is a result
            failed = True
            print(f"{name}/SUITE_ERROR,0,{type(e).__name__}:{e}")
    if smoke and failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
