"""Whole runs of both cells on the CPU at a tiny size: the result line
keeps to the contract and a sound run is correct."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import perfbench_tiny as tiny  # noqa: E402
from bench import harness  # noqa: E402


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("bench"))


def _keeps_to_the_contract(res, names):
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == names
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}


def test_serve_run_is_correct_and_reports_its_end_to_end_metrics(spec):
    res = tiny.run(spec, tiny.SERVE, seed=11)
    _keeps_to_the_contract(res, {"preds_per_s", "setup_s"})
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == round(60.0 * 1.5)
    assert res["checks"]["pred_gap"]["value"] <= 1e-6


def test_serve_traced_run_reports_the_host_side_layer_metrics(spec):
    # the queue wait reads the requests due a second before tracing starts
    res = tiny.run(spec, tiny.SERVE, seed=12, seconds=2.5, trace=True)
    # the CPU has no device plane: the trace readers find nothing to read
    _keeps_to_the_contract(res, {"request_p95_ms", "queue_wait_p95_ms",
                                 "slot_fill", "serve_mfu"})
    assert res["correct"] is True
    assert 0 < res["metrics"]["slot_fill"]["value"] <= 100
    assert res["breakdown"] == {"device_ops": [], "idle_gaps": []}


@pytest.mark.parametrize("trace,names,ahead", [
    (False, {"convert_ms", "setup_s"}, None),
    (True, set(), None),
    (False, {"convert_ms", "setup_s"}, 0),
])
def test_convert_run_is_correct(spec, monkeypatch, trace, names, ahead):
    if ahead is not None:       # the blocking loop: nothing dispatched ahead
        real = harness.load_cell

        def load_cell(*a, **k):
            cell = real(*a, **k)
            cell.traffic["in_flight"] = ahead
            return cell
        monkeypatch.setattr(harness, "load_cell", load_cell)
    res = tiny.run(spec, tiny.CONVERT, seed=13, trace=trace)
    _keeps_to_the_contract(res, names)
    assert res["correct"] is True and res["attempted"] > 1
    assert res["checks"]["csc_mismatch"] == {"value": 0, "limit": 0}
