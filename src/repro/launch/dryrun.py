import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
cell on the production meshes and record memory/cost/collective analysis.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-9b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --preprocess --mesh single

Results are cached as JSON under benchmarks/results/dryrun/.
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import all_cells, get_arch
from repro.launch.mesh import make_production_mesh
from repro.launch.hlo_analysis import collective_bytes, loop_aware_stats
from repro.launch.steps import Cell, build_cell, preprocess_cells

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")


def run_cell(cell: Cell, mesh, mesh_name: str) -> dict:
    """lower → compile → analyze one cell. Returns the result record."""
    rec: dict = {
        "cell": cell.key, "mesh": mesh_name, "note": cell.note,
        "mesh_shape": dict(mesh.shape),
    }
    if cell.skipped:
        rec["status"] = "skipped"
        rec["skip_reason"] = cell.skipped
        return rec
    t0 = time.time()
    with jax.set_mesh(mesh):
        # repro: allow-raw-jit — one-shot compile probe per cell; the CLI
        # measures lower/compile time, nothing re-dispatches this wrapper.
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         donate_argnums=cell.donate_argnums)
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    rec["status"] = "ok"
    rec["t_lower_s"] = round(t_lower, 2)
    rec["t_compile_s"] = round(t_compile, 2)
    rec["memory"] = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        "generated_code_bytes": getattr(
            mem, "generated_code_size_in_bytes", None),
        "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
    }
    rec["cost"] = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "transcendentals": float(cost.get("transcendentals", 0.0)),
    }
    hlo = compiled.as_text()
    stats = collective_bytes(hlo)
    rec["collectives"] = {
        "bytes_by_kind": stats.bytes_by_kind,
        "count_by_kind": stats.count_by_kind,
        "total_bytes": stats.total_bytes,
    }
    # XLA cost_analysis counts while bodies once (not ×trip-count); these
    # loop-aware totals are what §Roofline uses.
    las = loop_aware_stats(hlo)
    rec["loop_aware"] = {
        "dot_flops": las.dot_flops,
        "hbm_bytes": las.hbm_bytes,
        "transcendental_elems": las.transcendental_elems,
        "flash_tile_bytes": las.flash_tile_bytes,
    }
    rec["hlo_size_chars"] = len(hlo)
    return rec


def result_path(key: str, mesh_name: str) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    return os.path.join(RESULTS_DIR, f"{key}__{mesh_name}.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--preprocess", action="store_true",
                    help="run the AutoGNN pipeline cells")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    meshes = {"single": False, "multi": True}
    mesh_names = (["single", "multi"] if args.mesh == "both"
                  else [args.mesh])

    if args.all:
        cells = all_cells()
    elif args.arch and args.shape:
        cells = [(args.arch, args.shape)]
    elif args.arch:
        cells = [(a, s) for a, s in all_cells() if a == args.arch]
    elif args.preprocess:
        cells = []
    else:
        ap.error("--arch/--shape, --all, or --preprocess required")
        return

    failures = 0
    for mesh_name in mesh_names:
        mesh = make_production_mesh(multi_pod=meshes[mesh_name])
        todo: list[Cell] = []
        for arch_id, shape in cells:
            todo.append(build_cell(arch_id, shape, mesh))
        if args.preprocess:
            todo.extend(preprocess_cells(mesh))
        for cell in todo:
            path = result_path(cell.key, mesh_name)
            if os.path.exists(path) and not args.force:
                print(f"[cached] {cell.key} ({mesh_name})")
                continue
            print(f"[run] {cell.key} ({mesh_name}) ...", flush=True)
            try:
                rec = run_cell(cell, mesh, mesh_name)
            except Exception as e:  # noqa: BLE001 — record and continue
                rec = {"cell": cell.key, "mesh": mesh_name,
                       "status": "error", "error": repr(e),
                       "traceback": traceback.format_exc()}
                failures += 1
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            status = rec["status"]
            extra = ""
            if status == "ok":
                # CPU backend reports no peak-memory analysis → None
                pk = rec["memory"]["peak_bytes"]
                pk = "n/a" if pk is None else f"{pk/1e9:.2f}GB"
                extra = (f" peak={pk} "
                         f"flops={rec['cost']['flops']:.3e} "
                         f"coll={rec['collectives']['total_bytes']:.3e}B "
                         f"compile={rec['t_compile_s']}s")
            elif status == "error":
                extra = " " + rec["error"][:200]
            print(f"[{status}] {cell.key} ({mesh_name}){extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()
