"""The benchmark finds configurations, traffic mixes, workload kinds and
per-layer metrics by name, and picks up new ones added as files."""
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import harness  # noqa: E402

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())


def test_every_name_in_the_spec_has_its_file():
    for c in SPEC["configs"]:
        assert (REPO / c["file"]).is_file()
        assert json.loads((REPO / c["file"]).read_text())["name"] == c["name"]
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert harness.driver(cell.traffic["kind"]).run
    for m in SPEC["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


@pytest.mark.parametrize("cell,e2e,layer", [
    ("reddit-serve", {"preds_per_s", "setup_s"},
     {"request_p95_ms", "queue_wait_p95_ms", "slot_fill", "step_device_ms",
      "serve_mfu", "idle_share.serve"}),
    ("products-convert", {"convert_ms", "setup_s"},
     {"sort_share.convert", "convert_roofline", "idle_share.convert"}),
])
def test_cell_metrics_follow_the_workloads_keys(cell, e2e, layer):
    c = harness.load_cell(cell)
    assert {m["name"] for m in c.end_to_end} == e2e
    assert {m["name"] for m in c.per_layer} == layer
    moved = {m["moves"] for m in c.per_layer}
    assert moved <= e2e


def test_spec_keeps_to_the_contract_shapes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}


def _copy_bench(tmp_path):
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(REPO / "bench" / sub, tmp_path / sub)
    return json.loads(json.dumps(SPEC))


def test_a_new_cell_and_metric_are_picked_up_from_files_alone(tmp_path):
    spec = _copy_bench(tmp_path)
    traffic = json.loads(
        (tmp_path / "traffic" / "open-zipf-reddit.json").read_text())
    traffic["popularity"] = {"law": "zipf", "s": 0.8}
    (tmp_path / "traffic" / "open-uniform-test.json").write_text(
        json.dumps(traffic))
    (tmp_path / "metrics" / "throwaway_fill.py").write_text(
        "def read(r):\n    return 2.0 * r.admitted\n")
    spec["workloads"].append({"name": "reddit-uniform", "config":
                              "graphsage-reddit", "traffic":
                              "open-uniform-test", "chips": 1, "why": "t"})
    spec["per_layer"].append({"name": "throwaway_fill", "unit": "%",
                              "better": "higher", "source": "host_clock",
                              "layer": "admission", "moves": "preds_per_s",
                              "workloads": ["reddit-uniform"]})
    for m in spec["end_to_end"]:
        if "reddit-serve" in m.get("workloads", []):
            m["workloads"].append("reddit-uniform")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("reddit-uniform", tmp_path,
                             tmp_path / "BENCHMARK.json")
    assert cell.traffic["popularity"] == {"law": "zipf", "s": 0.8}
    assert cell.config["name"] == "graphsage-reddit"
    assert [m["name"] for m in cell.per_layer] == ["throwaway_fill"]
    read = harness.metric_reader("throwaway_fill", tmp_path)

    class R:
        admitted = 21
    assert read(R()) == 42.0


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell")
    with pytest.raises(ModuleNotFoundError):
        harness.driver("no_such_kind")
    with pytest.raises(FileNotFoundError):
        harness.metric_reader("no_such_metric")


def test_a_listed_metric_that_reads_nothing_raises_on_the_chip():
    cell = harness.load_cell("products-convert")

    class NoTrace:
        trace = None
    with pytest.raises(RuntimeError, match="found nothing to read"):
        harness.layer_metrics(cell, NoTrace())
    assert harness.layer_metrics(cell, NoTrace(), strict=False) == {}
