"""The whole-graph inference cell: its metric sets, a tiny run on the CPU
that is correct, a run with wrong logits that is refused, the bfloat16
control that fails the limits, the work counts and the readers."""
import json
import shutil
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import control_infer, harness, inputs_gat, work_gat  # noqa: E402
from bench.references import gat as ref_gat  # noqa: E402
from repro.engine import service  # noqa: E402

CELL = "products-gat-infer"
LAYER = {"infer_edge_ms", "infer_project_ms", "edge_roofline", "infer_mfu",
         "idle_share.infer", "chunk_fill"}


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    """A bench directory whose inference cell keeps its name, driver and
    readers but runs a 3,000-node graph at 16 wide heads."""
    tmp = tmp_path_factory.mktemp("bench")
    shutil.copytree(REPO / "bench" / "metrics", tmp / "metrics")
    (tmp / "configs").mkdir()
    (tmp / "traffic").mkdir()
    cfg = json.loads((REPO / "bench/configs/gat-products.json").read_text())
    cfg["graph"].update(n_nodes=3000, n_edges=40000, capacity=1 << 16,
                        d_feat=32)
    cfg["model"].update(d_head=16)
    (tmp / "configs" / "tiny-gat.json").write_text(json.dumps(cfg))
    tr = json.loads((REPO / "bench/traffic/infer-loop.json").read_text())
    tr["trace_s"] = 0.5
    (tmp / "traffic" / "tiny-infer.json").write_text(json.dumps(tr))
    full = json.loads((REPO / "BENCHMARK.json").read_text())
    full["workloads"] = [{"name": CELL, "config": "tiny-gat",
                          "traffic": "tiny-infer", "chips": 1, "why": "t"}]
    path = tmp / "BENCHMARK.json"
    path.write_text(json.dumps(full))
    return path


@pytest.fixture(autouse=True)
def small_reference_blocks(monkeypatch):
    monkeypatch.setattr(ref_gat, "SLOTS", 1 << 13)
    monkeypatch.setattr(ref_gat, "PROJECT_ROWS", 1024)


def _run(spec, seed, trace=False):
    return harness.run_cell(CELL, seed, 1.0, trace,
                            t_process=time.perf_counter(),
                            bench_dir=spec.parent, allow_cpu=True,
                            compile_cache=False, spec_path=spec,
                            log=lambda msg: None)


def test_the_cell_reports_its_metric_sets():
    c = harness.load_cell(CELL)
    assert c.chips == 1 and c.config["name"] == "gat-products"
    assert {m["name"] for m in c.end_to_end} == {"preds_per_s", "setup_s"}
    assert {m["name"] for m in c.per_layer} == LAYER
    assert {m["moves"] for m in c.per_layer} == {"preds_per_s"}
    # the other cells are as they were
    assert {m["name"] for m in harness.load_cell("reddit-serve").end_to_end
            } == {"preds_per_s", "setup_s"}
    assert not LAYER & {m["name"] for m in harness.load_cell(
        "products-convert").per_layer}


@pytest.mark.parametrize("trace,names", [
    (False, {"preds_per_s", "setup_s"}),
    # the CPU has no device plane: only the program's counter reads
    (True, {"chunk_fill"}),
])
def test_a_tiny_run_is_correct(spec, trace, names):
    res = _run(spec, 3_116_000_011 + trace, trace)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 2
    assert set(res["metrics"]) == names
    checks = res["checks"]
    assert set(checks) == {"failed", "pred_gap", "logit_err"}
    # both float32 on the CPU: a few ulps apart
    assert checks["logit_err"]["value"] <= 1e-5
    if trace:
        assert 0 < res["metrics"]["chunk_fill"]["value"] <= 100


@pytest.mark.parametrize("fault", ["swap_classes", "one_node"])
def test_wrong_logits_are_refused(spec, monkeypatch, fault):
    real = service.gat_infer_jit

    def planted(*a, **k):
        logits, counters = real(*a, **k)
        if fault == "swap_classes":
            logits = logits[:, ::-1]
        else:
            logits = logits.at[1234].add(5.0)
        return logits, counters
    monkeypatch.setattr(service, "gat_infer_jit", planted)
    res = _run(spec, 3_116_000_021)
    assert res["correct"] is False
    assert res["checks"]["logit_err"]["value"] > res["checks"]["logit_err"][
        "limit"]


@pytest.mark.parametrize("seed", [1, 2])
def test_bfloat16_fails_the_limits_that_float32_passes(spec, seed):
    """The program passes the limits; the reference in bfloat16 fails one
    of them at least (the chip readings that set them are in PERF.md)."""
    cell = harness.load_cell(CELL, spec.parent, spec)
    r = control_infer.readings(cell, seed, True, lambda msg: None)
    limits = cell.config["limits"]
    assert all(r["program"][k] <= limits[k] for k in r["program"])
    low = r["control_reference_bfloat16"]
    assert any(low[k] > limits[k] for k in low)


def _loop_logits(dst, src, feats, params, n):
    """GATConv with add_self_loops and a skip, edge by edge in float64:
    SENTINEL entries are no edges, a graph self loop is dropped, and every
    node attends to itself once."""
    edges = [(int(t), int(j)) for t, j in zip(dst, src) if t < n]
    h = np.asarray(feats, np.float64)
    layers = params["layers"]
    for li, lp in enumerate(layers):
        lp = {k: np.asarray(v, np.float64) for k, v in lp.items()}
        heads, d = lp["a_src"].shape
        z = (h @ lp["w"]).reshape(n, heads, d)
        out = np.empty((n, lp["b"].shape[0]))
        for i in range(n):
            terms = [j for t, j in edges if t == i and j != i] + [i]
            agg = np.zeros((heads, d))
            for hh in range(heads):
                e = np.array([z[j, hh] @ lp["a_src"][hh]
                              + z[i, hh] @ lp["a_dst"][hh] for j in terms])
                e = np.where(e > 0, e, 0.2 * e)
                alpha = np.exp(e - e.max())
                alpha /= alpha.sum()
                for a, j in zip(alpha, terms):
                    agg[hh] += a * z[j, hh]
            merged = agg.mean(0) if li == len(layers) - 1 else agg.ravel()
            out[i] = merged + lp["b"] + h[i] @ lp["w_skip"] + lp["b_skip"]
        if li < len(layers) - 1:
            out = np.where(out > 0, out, np.expm1(np.minimum(out, 0)))
        h = out
    return h


def test_the_reference_equals_an_edge_by_edge_loop():
    """The reference against the layer's equations written edge by edge,
    on a graph with self loops, repeated edges, a hub whose in-edges need
    a wider row than the rest, nodes with no in-edges and a SENTINEL
    tail. Both float64-close: the reference is float32 at highest, so the
    two differ by float32 rounding, ~1e-6 on values of ~1."""
    rng = np.random.default_rng(5)
    n, cap, sentinel = 40, 512, 0x7FFFFFFF
    dst = rng.integers(0, n - 4, 200)          # nodes n-4.. have no in-edges
    src = rng.integers(0, n, 200)
    dst = np.concatenate([dst, [3, 7, 7], np.full(90, 1)])  # loops, hub 1
    src = np.concatenate([src, [3, 7, 7], rng.integers(0, n, 90)])
    src[-10:] = src[-11]                       # repeated hub edges
    dst = np.concatenate([dst, np.full(cap - len(dst), sentinel)])
    src = np.concatenate([src, np.full(cap - len(src), sentinel)])
    assert np.sum(dst == 1) > ref_gat.MIN_WIDTH
    dims = ((6, 2, 4, 8), (8, 2, 4, 8), (8, 2, 5, 5))
    layers = []
    for d_in, heads, width, d_out in dims:
        layers.append({
            "w": rng.normal(size=(d_in, heads * width)) / np.sqrt(d_in),
            "a_src": rng.normal(size=(heads, width)),
            "a_dst": rng.normal(size=(heads, width)),
            "b": rng.normal(size=d_out) * 0.1,
            "w_skip": rng.normal(size=(d_in, d_out)) / np.sqrt(d_in),
            "b_skip": rng.normal(size=d_out) * 0.1})
    params = {"layers": [{k: jax.numpy.asarray(v, np.float32)
                          for k, v in lp.items()} for lp in layers]}
    feats = rng.normal(size=(n, 6)).astype(np.float32)
    got = ref_gat.logits(jax.numpy.asarray(dst, np.int32),
                         jax.numpy.asarray(src, np.int32),
                         jax.numpy.asarray(feats), params, n_nodes=n)
    want = _loop_logits(dst, src, feats, params, n)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_work_counts_at_ogbn_products():
    cfg = json.loads((REPO / "bench/configs/gat-products.json").read_text())
    dims = inputs_gat.layer_dims(cfg)
    assert dims == ((100, 4, 128, 512), (512, 4, 128, 512),
                    (512, 4, 47, 47))
    n, e = 2_449_029, work_gat.attended_edges(cfg)
    assert e == 123_718_280 + n
    assert work_gat.gat_flops(n, e, dims) == pytest.approx(3.97e12,
                                                           rel=2e-3)
    assert work_gat.edge_bytes(n, e, dims) == pytest.approx(0.643e12,
                                                            rel=2e-3)


def test_chunk_fill_reads_the_counters():
    read = harness.metric_reader("chunk_fill")

    class R:
        cell = harness.load_cell(CELL)
        counters = {"edges": 2_449_029 + 1000, "chunks": 4}
        chunk_edges = 500
    assert read(R()) == pytest.approx(50.0)
    R.counters = {}
    assert read(R()) is None


def test_inputs_are_made_from_the_seed():
    cfg = json.loads((REPO / "bench/configs/gat-products.json").read_text())
    cfg["graph"].update(n_nodes=50, d_feat=8)
    a = inputs_gat.gat_inputs(2**40 + 5, cfg)
    b = inputs_gat.gat_inputs(2**40 + 5, cfg)
    c = inputs_gat.gat_inputs(5, cfg)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))


class _Readings:
    def __init__(self, cell, trace, peaks):
        self.cell, self.trace, self.peaks = cell, trace, peaks


# A traced run of the cell's driver on a TPU v5e over a reduced graph
# (20,000 nodes, 1,000,000 edges, the widths as published): two whole
# inferences. Trimmed to the device plane's XLA Modules and XLA Ops lines,
# each op's metadata name cut after its instruction and each event's own
# stats (which no reader reads) dropped. The run's own result line read
# these values from the untrimmed trace.
RECORDED = {"window_s": 0.060457812999999305, "infer_edge_ms": 28.993632,
            "infer_project_ms": 0.927033, "edge_roofline": 21.898960,
            "infer_mfu": 0.545831, "idle_share.infer": 0.475323}


@pytest.fixture
def recorded(monkeypatch, tmp_path):
    from bench import peaks, scopes, tracing
    raw = (Path(__file__).parent / "data" / "tpu_gat_infer_20k.xplane.pb.gz"
           ).read_bytes()
    where = tmp_path / CELL / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    import gzip
    (where / "host.xplane.pb").write_bytes(gzip.decompress(raw))
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)
    cell = harness.load_cell(CELL)
    cell.config = json.loads(json.dumps(cell.config))
    cell.config["graph"].update(n_nodes=20000, n_edges=1000000)
    return _Readings(cell, tracing.reduce_trace(raw, 1, RECORDED["window_s"]),
                     peaks.PEAKS["TPU v5 lite"])


@pytest.mark.parametrize("name", ["infer_edge_ms", "infer_project_ms",
                                  "edge_roofline", "infer_mfu",
                                  "idle_share.infer"])
def test_each_reader_reads_the_recorded_chip_trace(recorded, name):
    value = harness.metric_reader(name)(recorded)
    assert value == pytest.approx(RECORDED[name], rel=1e-4)


def test_the_two_scopes_cover_the_inference(recorded):
    from bench import scopes
    raw = scopes.recorded_trace(recorded)
    n, by, coverage = scopes.scope_seconds(
        recorded.trace, r"^jit_gat_infer\b", scopes.trace_op_scopes(raw))
    _, module_s = recorded.trace.module_calls(r"^jit_gat_infer\b")
    assert n == 2 and coverage >= scopes.MIN_COVERAGE
    assert sum(by.values()) == pytest.approx(module_s, rel=1e-3)
    edge, project = by["infer.edge"], by["infer.project"]
    assert (edge + project) / module_s >= 0.95 and edge / module_s >= 0.9
