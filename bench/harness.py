"""Discovery by name, the device check and the result line.

Everything that belongs to one configuration, traffic mix, workload kind
or per-layer metric is a file of its own, found from the names in
``BENCHMARK.json``:

* ``configs/<config>.json``: the configuration as it is run;
* ``traffic/<traffic>.json``: the traffic mix, whose ``kind`` names
* ``drivers/<kind>.py``: the loop that sets up, measures and checks;
* ``metrics/<metric>.py``: one ``read(readings)`` for a per-layer metric,
  returning ``None`` where it finds nothing to read.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
REPO = BENCH_DIR.parent


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


@dataclasses.dataclass
class Outcome:
    """What a driver hands back from one run."""

    end_to_end: dict[str, float]
    readings: object            # what the per-layer readers read
    attempted: int
    failed: int
    checks: list[tuple[str, float, float]]   # (name, value, limit)
    memory_peak_bytes: int | None
    trace: object = None        # tracing.TraceSummary of a --trace 1 run

    @property
    def correct(self) -> bool:
        return all(v <= lim for _, v, lim in self.checks)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_dir: Path = BENCH_DIR,
              spec_path: Path | None = None) -> Cell:
    spec = load_json(spec_path or bench_dir.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = load_json(bench_dir / "configs" / f"{w['config']}.json")
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")

    def applies(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name, int(w["chips"]), cfg, traffic, e2e, per_layer)


def driver(kind: str):
    return importlib.import_module(f"bench.drivers.{kind}")


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices(chips: int, allow_cpu: bool = False):
    """The chips the cell runs on; raises :class:`NoDevice` on any
    platform but a TPU (unless ``allow_cpu``, which only tests use)."""
    import jax
    devs = jax.devices()
    if not allow_cpu and devs[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {devs[0].platform!r}); "
                       f"the benchmark runs only on the chip")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compile cache at the repository's fixed path (or
    ``JAX_COMPILATION_CACHE_DIR``), for every program however short."""
    import jax
    from repro.launch.cache import enable_compile_cache as enable
    where = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def layer_metrics(cell: Cell, readings, bench_dir: Path = BENCH_DIR,
                  strict: bool = True) -> dict:
    """The cell's per-layer metrics read from ``readings``. A metric listed
    for the cell that finds nothing to read raises when ``strict`` (on the
    chip: a trace or counter name that no longer matches), and is left out
    otherwise (a CPU trace has no device plane)."""
    metrics = {}
    for m in cell.per_layer:
        value = metric_reader(m["name"], bench_dir)(readings)
        if value is None:
            if strict:
                raise RuntimeError(f"per-layer metric {m['name']!r}, listed "
                                   f"for {cell.name!r}, found nothing to read")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, bench_dir: Path = BENCH_DIR,
             allow_cpu: bool = False, compile_cache: bool = True,
             spec_path: Path | None = None, log=None) -> dict:
    """One run of one cell; returns the result line's object."""
    import jax
    from bench import peaks
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell = load_cell(name, bench_dir, spec_path)
    devs = devices(cell.chips, allow_cpu)
    kind = devs[0].device_kind
    peak = peaks.peaks_for(kind) if not allow_cpu else peaks.PEAKS[
        "TPU v5 lite"]
    if compile_cache:
        log(f"compile cache: {enable_compile_cache()}")
    drv = driver(cell.traffic["kind"])
    out: Outcome = drv.run(cell, devs, seed=seed, seconds=seconds,
                           trace=trace, t_process=t_process, peaks=peak,
                           log=log)
    if trace:
        metrics = layer_metrics(cell, out.readings, bench_dir,
                                strict=not allow_cpu)
    else:
        missing = [m["name"] for m in cell.end_to_end
                   if m["name"] not in out.end_to_end]
        if missing:
            raise RuntimeError(f"driver gave no {missing}")
        metrics = {m["name"]: {"value": float(out.end_to_end[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": out.correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": device}
    if trace and out.trace is not None:
        device["busy_s"] = out.trace.busy_s
        device["window_s"] = out.trace.window_s
        result["breakdown"] = {"device_ops": out.trace.top_ops(10),
                               "idle_gaps": out.trace.idle_gaps(10)}
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in out.checks}
    return result
