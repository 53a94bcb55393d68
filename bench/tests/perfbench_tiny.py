"""A tiny copy of the benchmark's cells for CPU tests: the same drivers,
readers and widths over a 3,000-node graph."""
import json
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import harness  # noqa: E402

SERVE, CONVERT = "reddit-serve", "products-convert"


def make(tmp: Path, rate: float = 60.0, d_feat: int = 64) -> Path:
    """A bench directory under ``tmp`` whose two cells keep their names,
    drivers and readers but run a tiny graph; returns its spec path."""
    shutil.copytree(REPO / "bench" / "metrics", tmp / "metrics")
    (tmp / "configs").mkdir()
    (tmp / "traffic").mkdir()
    cfg = json.loads(
        (REPO / "bench/configs/graphsage-reddit.json").read_text())
    cfg["graph"].update(n_nodes=3000, n_edges=40000, capacity=1 << 16,
                        d_feat=d_feat)
    (tmp / "configs" / "tiny.json").write_text(json.dumps(cfg))
    tr = json.loads(
        (REPO / "bench/traffic/open-zipf-reddit.json").read_text())
    tr["arrivals"]["rate_per_s"] = rate
    tr["trace_s"] = 0.5
    tr["drain_s"] = 30.0
    tr["check"].update(requests=48, longest=12, block=8)
    (tmp / "traffic" / "tiny-serve.json").write_text(json.dumps(tr))
    conv = json.loads((REPO / "bench/traffic/convert-loop.json").read_text())
    conv["trace_s"] = 0.2
    (tmp / "traffic" / "tiny-convert.json").write_text(json.dumps(conv))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["workloads"] = [
        {"name": SERVE, "config": "tiny", "traffic": "tiny-serve",
         "chips": 1, "why": "tiny"},
        {"name": CONVERT, "config": "tiny", "traffic": "tiny-convert",
         "chips": 1, "why": "tiny"}]
    path = tmp / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return path


def run(spec: Path, cell: str, seed: int = 5, seconds: float = 1.5,
        trace: bool = False) -> dict:
    """One run of a tiny cell on the CPU: the harness's look for a chip
    skipped, everything else as on the chip."""
    return harness.run_cell(cell, seed, seconds, trace,
                            t_process=time.perf_counter(),
                            bench_dir=spec.parent, allow_cpu=True,
                            compile_cache=False, spec_path=spec,
                            log=lambda msg: None)
