"""The reduction from a device trace to the per-layer numbers."""
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import tracing  # noqa: E402


def _events(rows):
    """Events from (name, start_ns, end_ns) rows."""
    names = sorted({r[0] for r in rows})
    return tracing.Events(
        names, np.array([names.index(r[0]) for r in rows], np.int64),
        np.array([r[1] for r in rows], float),
        np.array([r[2] for r in rows], float))


@pytest.fixture
def summary():
    # two executions of jit_convert, one of jit_step; a gap 40..60 during
    # which the host thread is in "dispatch"
    modules = _events([("jit_convert(1)", 0, 40), ("jit_step(2)", 60, 90),
                       ("jit_convert(1)", 100, 140)])
    ops = _events([("sort.1", 0, 20), ("fusion.2", 20, 40),
                   ("sort.1", 60, 70), ("fusion.3", 70, 90),
                   ("sort.1", 100, 130), ("fusion.2", 125, 140)])
    host = [("main", _events([("dispatch", 35, 65), ("wait", 0, 200)]))]
    return tracing.TraceSummary(200e-9, [tracing.Device(modules, ops)], host)


def test_busy_is_the_union_of_operations(summary):
    # 0..40, 60..90, 100..140 (the overlap 125..130 counted once)
    assert summary.busy_s == pytest.approx(110e-9)


def test_module_calls_and_op_time_inside_a_module(summary):
    assert summary.module_calls(r"^jit_convert\b") == (2, pytest.approx(
        80e-9))
    assert summary.module_calls(r"^jit_step\b") == (1, pytest.approx(30e-9))
    # the sort inside jit_step does not count for jit_convert
    assert summary.op_seconds("sort", r"^jit_convert\b") == pytest.approx(
        50e-9)


def test_top_ops_name_their_program(summary):
    top = dict(summary.top_ops(3))
    assert top["jit_convert:sort.1"] == pytest.approx(50e-9)
    assert top["jit_convert:fusion.2"] == pytest.approx(35e-9)


def test_idle_gaps_are_named_by_the_host_event_in_them(summary):
    gaps = summary.idle_gaps(5)
    assert gaps[0] == ["main:dispatch", pytest.approx(20e-9)]
    assert gaps[1] == ["main:wait", pytest.approx(10e-9)]


def test_within_and_merged():
    s, e = tracing.merged(np.array([5., 0., 1.]), np.array([6., 2., 3.]))
    assert s.tolist() == [0., 5.] and e.tolist() == [3., 6.]
    m = tracing.within(np.array([0., 2., 4., 9.]), np.array([1., 8.]),
                       np.array([3., 10.]))
    assert m.tolist() == [False, True, False, True]


def test_a_recorded_trace_reduces_to_its_host_threads():
    """A real profiler trace (a CPU run of the tiny convert cell, so it has
    host threads and no device plane)."""
    raw = (Path(__file__).parent / "data" / "cpu_convert.xplane.pb.gz"
           ).read_bytes()
    s = tracing.reduce_trace(raw, 0, 0.05)
    threads = dict(s.host)
    assert {"bench.convert", "PjitFunction(convert)"} <= set(
        threads["python"].names)
    assert s.devices == [] and s.busy_s == 0.0 and s.idle_gaps() == []
    with pytest.raises(RuntimeError, match="device planes"):
        tracing.reduce_trace(raw, 1, 0.05)


def test_an_execution_cut_by_the_trace_stop_is_not_counted(summary):
    cut = _events([("jit_convert(1)", 0, 40), ("jit_convert(1)", 100, 140),
                   ("jit_convert(1)", 140, 141)])
    s = tracing.TraceSummary(200e-9, [tracing.Device(
        cut, summary.devices[0].ops)], summary.host)
    assert s.module_calls(r"^jit_convert\b") == (2, pytest.approx(80e-9))


def test_a_recorded_chip_trace_reduces_to_whole_converts():
    """A real trace of the products-convert cell on a TPU v5e: three whole
    converts and a fourth still running when the profiler stopped."""
    raw = (Path(__file__).parent / "data" / "tpu_convert.xplane.pb.gz"
           ).read_bytes()
    s = tracing.reduce_trace(raw, 1, 6.790381063)
    n, seconds = s.module_calls(r"^jit_convert\b")
    assert n == 3 and seconds / n == pytest.approx(2.263, rel=1e-3)
    assert s.busy_s == pytest.approx(6.789, rel=1e-3)
    sort_s = s.op_seconds(r"sort", r"^jit_convert\b")
    assert 100 * sort_s / seconds == pytest.approx(51.42, abs=0.05)
    assert s.top_ops(1)[0][0].startswith("jit_convert:%while")
