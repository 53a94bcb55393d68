"""Device time by named scope and host time by span: the reduction in
``bench/scopes.py`` and the readers built on it, on recorded chip traces."""
import gzip
import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import harness, scopes, tracing  # noqa: E402
from repro import scopes as names  # noqa: E402

DATA = Path(__file__).parent / "data"


def _events(rows):
    names_ = sorted({r[0] for r in rows})
    return tracing.Events(
        names_, np.array([names_.index(r[0]) for r in rows], np.int64),
        np.array([r[1] for r in rows], float),
        np.array([r[2] for r in rows], float))


def test_outermost_counts_a_loop_body_once():
    start = np.array([0., 2., 3., 10., 10., 20.])
    end = np.array([9., 5., 4., 15., 15., 21.])
    # 2..5 and 3..4 lie in 0..9; the second of two equal intervals is inner
    assert scopes.outermost(start, end).tolist() == [
        True, False, False, True, False, True]


def test_an_unnamed_loop_takes_the_scope_of_its_body():
    modules = _events([("jit_convert(1)", 0, 100)])
    ops = _events([("%sort.1 = s32[8] sort(...)", 0, 40),
                   ("%while.1 = (s32[]) while(...)", 40, 90),
                   ("%fusion.8 = s32[8] fusion(...)", 41, 60),
                   ("%fusion.9 = s32[8] fusion(...)", 60, 89),
                   ("%copy.3 = s32[8] copy(...)", 90, 100)])
    s = tracing.TraceSummary(1e-7, [tracing.Device(modules, ops)], [])
    smap = {"sort.1": "a", "fusion.8": "b", "fusion.9": "b"}
    n, by, cov = scopes.scope_seconds(s, r"^jit_convert\b", smap)
    assert n == 1
    assert by == {"a": pytest.approx(40e-9), "b": pytest.approx(50e-9),
                  None: pytest.approx(10e-9)}
    assert cov == pytest.approx(0.9)      # the copy is named by nothing


def test_host_spans_are_summed_over_threads():
    host = [("loop", _events([("serve.step", 0, 10), ("serve.step", 20, 25),
                              ("serve.route", 10, 14)])),
            ("feeder", _events([("serve.feed", 3, 4)]))]
    s = tracing.TraceSummary(1e-7, [], host)
    assert scopes.span_seconds(s, "serve.step") == (2, pytest.approx(15e-9))
    assert scopes.span_seconds(s, "serve.idle") == (0, 0.0)


def test_the_chip_convert_trace_counts_the_pointer_loop_once():
    """The products-convert trace (recorded before the scopes existed), with
    a hand-written map: the pointer build is the ``while`` and its body,
    everything else the ordering."""
    raw = (DATA / "tpu_convert.xplane.pb.gz").read_bytes()
    s = tracing.reduce_trace(raw, 1, 6.790381063)
    paths = scopes.trace_op_paths(raw)
    assert paths["sort.8"] == "jit(convert)/sort"
    assert "while.1" not in paths     # the loop carries no op_name here
    ops = {scopes.instruction(e) for e in s.devices[0].ops.names}
    smap = {op: names.CONVERT_POINTER
            if op == "while.1" or "/while/" in paths.get(op, "")
            else names.CONVERT_ORDERING for op in ops}
    n, by, cov = scopes.scope_seconds(s, r"^jit_convert\b", smap)
    _, module_s = s.module_calls(r"^jit_convert\b")
    assert n == 3 and cov >= scopes.MIN_COVERAGE
    pointer_ms = 1e3 * by[names.CONVERT_POINTER] / n
    assert pointer_ms == pytest.approx(1096.06, abs=0.5)
    # while.1 alone: fusion.8, which lies inside it, is not counted again
    assert pointer_ms == pytest.approx(
        1e3 * s.op_seconds(r"^%while\.1 ", r"^jit_convert") / n)
    assert s.op_seconds(r"^%fusion\.8 ", r"^jit_convert") > 3.2
    assert cov == 1.0 and set(by) == {names.CONVERT_ORDERING,
                                      names.CONVERT_POINTER}
    assert sum(by.values()) == pytest.approx(module_s, rel=1e-3)


class Readings:
    def __init__(self, cell, trace, **kw):
        self.cell = type("Cell", (), {"name": cell})()
        self.trace = trace
        self.__dict__.update(kw)


def _recorded(monkeypatch, tmp_path, cell, data, window_s):
    """Readings of a recorded chip trace, placed where the cell's
    traced run keeps its profile."""
    raw = (DATA / data).read_bytes()
    where = tmp_path / cell / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(gzip.decompress(raw))
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)
    return Readings(cell, tracing.reduce_trace(raw, 1, window_s))


@pytest.fixture
def serve(monkeypatch, tmp_path):
    """Three steps of the reddit-serve cell on a TPU v5e, cut from the
    last 3 s of a traced window (device op metadata keeps its op_name
    alone), with the ``op_scopes`` map of the step's compiled text."""
    r = _recorded(monkeypatch, tmp_path, "reddit-serve",
                  "tpu_serve.xplane.pb.gz", 0.1874)
    r.text_map = json.loads((DATA / "tpu_serve.op_scopes.json").read_text())
    return r


def _read(name, r):
    return harness.metric_reader(name)(r)


def test_each_serving_reader_reads_the_recorded_steps(serve):
    got = {m: _read(m, serve) for m in (
        "step_sample_ms", "step_reindex_ms", "step_reconvert_ms",
        "step_model_ms", "admit_host_ms", "route_host_ms")}
    assert got["step_sample_ms"] == pytest.approx(0.4396, abs=1e-3)
    assert got["step_reindex_ms"] == pytest.approx(43.912, abs=1e-2)
    assert got["step_reconvert_ms"] == pytest.approx(1.7284, abs=1e-3)
    assert got["step_model_ms"] == pytest.approx(5.217, abs=1e-2)
    # three serve.step spans; two admission windows of ~3.6 ms
    steps, _ = scopes.span_seconds(serve.trace, names.STEP)
    _, window_s = scopes.span_seconds(serve.trace, names.ADMIT_WINDOW)
    assert steps == 3 and window_s > 0
    assert got["admit_host_ms"] > 1e3 * window_s / steps
    assert 0 < got["route_host_ms"] < 10
    # the four stages and the unscoped rest make up the step's device time
    stages = sum(got[m] for m in ("step_sample_ms", "step_reindex_ms",
                                  "step_reconvert_ms", "step_model_ms"))
    step_ms = _read("step_device_ms", serve)
    assert 0.94 * step_ms < stages < step_ms


def test_the_trace_map_agrees_with_the_compiled_text(serve):
    raw = scopes.recorded_trace(serve)
    by_text = scopes.scope_seconds(serve.trace, r"^jit_step\b",
                                   serve.text_map)
    by_trace = scopes.scope_seconds(serve.trace, r"^jit_step\b",
                                    scopes.trace_op_scopes(raw))
    assert by_text[2] == 1.0                  # every op is in the text
    assert scopes.MIN_COVERAGE < by_trace[2] < 1.0   # copies carry none
    for scope in names.DEVICE_SCOPES:
        assert by_trace[1].get(scope, 0.0) == pytest.approx(
            by_text[1].get(scope, 0.0), abs=5e-5)   # 50 us over 3 steps
    # the 67 % loop is the reindex's, in the text and in the trace
    assert serve.text_map["while.26"] == names.SAMPLE_REINDEX


def test_each_convert_reader_reads_the_recorded_converts(monkeypatch,
                                                         tmp_path):
    r = _recorded(monkeypatch, tmp_path, "products-convert",
                  "tpu_convert_scoped.xplane.pb.gz", 6.790849648)
    ordering = _read("ordering_ms.convert", r)
    pointer = _read("pointer_ms.convert", r)
    assert ordering == pytest.approx(1165.28, abs=0.05)
    assert pointer == pytest.approx(1096.14, abs=0.05)
    n, seconds = r.trace.module_calls(r"^jit_convert\b")
    assert ordering + pointer == pytest.approx(1e3 * seconds / n, rel=1e-3)


def test_window_yield_reads_the_engine_counters():
    r = Readings("reddit-serve", None, window_waits=40, window_seated=10)
    assert _read("window_yield", r) == 0.25
    assert _read("window_yield", Readings("reddit-serve", None)) is None


def test_readers_read_nothing_without_what_they_read(serve, monkeypatch):
    device = ("step_sample_ms", "step_model_ms", "pointer_ms.convert")
    host = ("admit_host_ms", "route_host_ms")
    # no trace, or a CPU trace with neither device plane nor spans
    cpu = tracing.reduce_trace(
        (DATA / "cpu_convert.xplane.pb.gz").read_bytes(), 0, 0.05)
    for trace in (None, cpu):
        for m in device + host:
            assert _read(m, Readings("reddit-serve", trace)) is None
    # a trace whose op metadata names too little of the step
    monkeypatch.setattr(scopes, "trace_op_scopes", lambda raw: {})
    assert _read("step_reindex_ms", serve) is None
    # a program from before the scopes
    monkeypatch.setattr(scopes, "names", None)
    for m in device + host:
        assert _read(m, serve) is None
