"""The controls fail the cells' comparisons while the program passes them,
at a size a test run can hold (the chip readings that set the limits are
in PERF.md)."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import perfbench_tiny as tiny  # noqa: E402
from bench import control  # noqa: E402

# More classes than Reddit's 41: a lower precision shows only where it
# flips a near tie, and with 400 classes a few hundred predictions hold
# some on every seed.
N_CLASSES = 400


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    d = tmp_path_factory.mktemp("bench")
    spec = tiny.make(d, d_feat=602)
    cfg = json.loads((d / "configs" / "tiny.json").read_text())
    cfg["graph"]["n_classes"] = N_CLASSES
    (d / "configs" / "tiny.json").write_text(json.dumps(cfg))
    tr = json.loads((d / "traffic" / "tiny-serve.json").read_text())
    tr["check"].update(requests=96, longest=24)
    (d / "traffic" / "tiny-serve.json").write_text(json.dumps(tr))
    return spec


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_bfloat16_serving_fails_the_logit_gap_that_float32_passes(spec,
                                                                  seed):
    cfg = json.loads((spec.parent / "configs" / "tiny.json").read_text())
    limit = cfg["limits"]["pred_gap"]
    assert control.lower_precisions(cfg["model"])[0] == ("bfloat16",
                                                         "default")
    (r,) = control.readings(tiny.SERVE, [], [seed], 2.0, spec.parent, spec,
                            log=lambda m: None)
    assert r["unanswered"] == 0 and r["predictions"] > 250
    assert r["program"] <= limit
    assert r["control_program_bfloat16@default"] > limit
    assert r["control_reference_bfloat16@default"] > limit


def test_a_destination_only_sort_fails_the_csc_comparison(spec):
    rows = control.readings(tiny.CONVERT, [], [1, 2], 1.0, spec.parent,
                            spec, log=lambda m: None)
    for r in rows:
        assert r["program"] == 0
        assert r["control_dst_only"] > 1000
