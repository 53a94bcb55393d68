"""Device time by named scope and host time by span, from a profiler
trace of one run.

The program names its device stages with ``jax.named_scope``s and its
serving loop with host spans, once, in ``repro.scopes``. A scope lands in
the ``op_name`` of the operations it covers; the profiler writes that path
into the trace as each operation's ``tf_op`` stat, which
``trace_op_scopes`` reads. ``repro.launch.hlo_analysis.op_scopes`` gives
the same map from a compiled program's text.

Two rules make the device sums add up to the program's device time:

* outermost operations only: an operation whose interval lies inside
  another's on the same device (the body of a ``while``) is not counted a
  second time, and an outermost operation that the map does not name
  (a ``while`` or a copy the compiler added) takes the scope of the named
  operations that ran inside it;
* coverage: a reading is given only where at least ``MIN_COVERAGE`` of
  the outermost operations' time is named, so a map made from another
  program, or a trace without op metadata, reads nothing. The profiler
  writes no ``op_name`` for the layout copies the compiler adds: 1.6 % of
  the serving step's time on a TPU v5e, 0.07 % of the convert's.
"""
from __future__ import annotations

import glob
import gzip
from pathlib import Path

import numpy as np

from bench import harness
from bench.tracing import DEVICE_PLANE, within

try:
    from repro import scopes as names
    from repro.launch.hlo_analysis import op_scope
except ImportError:         # a program from before the scopes
    names = op_scope = None

# where a cell's traced run keeps its profile, one directory per cell
TRACE_DIR = harness.REPO / ".bench_traces"
MIN_COVERAGE = 0.95


# ---------------------------------------------------- the trace's metadata
def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for every other wire type."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield field, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def trace_op_paths(xspace: bytes) -> dict[str, str]:
    """Instruction name -> ``op_name`` path of every device operation in a
    serialized XSpace (optionally gzipped) that carries one. Reads the
    event and stat metadata of the device planes (XPlane fields 2, 4, 5;
    XEventMetadata 2, 5; XStatMetadata 1, 2; XStat 1, 5, 7)."""
    if xspace[:2] == b"\x1f\x8b":
        xspace = gzip.decompress(xspace)
    out: dict[str, str] = {}
    for field, plane in _fields(memoryview(xspace)):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = _text(v)
            elif f == 4:
                events.append(v)
            elif f == 5:
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        if not DEVICE_PLANE.match(name):
            continue
        tf_op = [k for k, v in stat_names.items() if v == "tf_op"]
        if not tf_op:
            continue
        for entry in events:
            op, path = "", ""
            for f, v in _fields(dict(_fields(entry)).get(2, b"")):
                if f == 2:
                    op = instruction(_text(v))
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) == tf_op[0]:
                        path = (_text(stat[5]) if 5 in stat
                                else stat_names.get(stat.get(7), ""))
            if op and path:
                out[op] = path.rstrip(":")
    return out


def trace_op_scopes(xspace: bytes) -> dict[str, str | None]:
    """Instruction name -> the program's scope (None: unscoped) of every
    device operation whose trace metadata carries an ``op_name``."""
    return {op: op_scope(path, names.DEVICE_SCOPES)
            for op, path in trace_op_paths(xspace).items()}


def instruction(event_name: str) -> str:
    """``%sort.8 = (s32[...]) sort(...)`` -> ``sort.8``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def recorded_trace(r) -> bytes | None:
    """The serialized trace the run's profiler wrote, if any."""
    paths = glob.glob(str(TRACE_DIR / r.cell.name / "**" / "*.xplane.pb"),
                      recursive=True)
    return Path(paths[0]).read_bytes() if len(paths) == 1 else None


# ------------------------------------------------------------- reductions
def owners(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """For each interval, the index of the outermost interval it lies in
    (itself where it lies inside no other; of two equal intervals the
    first is outer)."""
    order = np.lexsort((-end, start))
    reach = np.maximum.accumulate(end[order])
    inner = np.concatenate([[False], end[order][1:] <= reach[:-1]])
    last_outer = np.maximum.accumulate(
        np.where(inner, 0, np.arange(len(order))))
    out = np.empty(len(start), np.int64)
    out[order] = order[last_outer]
    return out


def outermost(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Mask of the intervals that lie inside no other."""
    return owners(start, end) == np.arange(len(start))


def scope_seconds(summary, module_pattern: str,
                  scope_map: dict) -> tuple[int, dict, float]:
    """(executions, {scope: device seconds}, coverage) of the programs
    matching ``module_pattern``. Only outermost operations are summed;
    unscoped time is under None; coverage is the share of their time that
    ``scope_map`` names, itself or through the operations inside it."""
    n, _ = summary.module_calls(module_pattern)
    by_scope: dict = {}
    named = total = 0.0
    for d in summary.devices:
        if not len(d.ops.start):
            continue
        m = d.modules.select(module_pattern)
        inside = within(d.ops.start, d.modules.start[m], d.modules.end[m])
        ops = [instruction(name) for name in d.ops.names]
        known = np.array([op in scope_map for op in ops])[d.ops.name]
        dur = d.ops.end - d.ops.start
        owner = owners(d.ops.start, d.ops.end)
        outer = np.flatnonzero(inside & (owner == np.arange(len(owner))))
        # an outer operation the map does not name takes the scope with
        # the most named time among the operations inside it
        unnamed = set(outer[~known[outer]].tolist())
        inner_time: dict = {}
        for j in np.flatnonzero(known & (owner != np.arange(len(owner)))):
            if owner[j] in unnamed:
                key = (owner[j], scope_map[ops[d.ops.name[j]]])
                inner_time[key] = inner_time.get(key, 0.0) + dur[j]
        adopted = {}
        for (k, scope), t in inner_time.items():
            if t > adopted.get(k, (None, -1.0))[1]:
                adopted[k] = (scope, t)
        for k in outer:
            if known[k]:
                scope = scope_map[ops[d.ops.name[k]]]
            elif k in adopted:
                scope = adopted[k][0]
            else:
                scope = None
            if known[k] or k in adopted:
                named += dur[k]
            total += dur[k]
            by_scope[scope] = by_scope.get(scope, 0.0) + dur[k] / 1e9
    return n, by_scope, (named / total if total else 0.0)


def span_seconds(summary, name: str) -> tuple[int, float]:
    """(count, host seconds) of the spans called ``name``, over every host
    thread of the trace."""
    count, total = 0, 0.0
    for _, ev in summary.host:
        if name in ev.names:
            hit = ev.name == ev.names.index(name)
            count += int(hit.sum())
            total += float(np.sum(ev.end[hit] - ev.start[hit])) / 1e9
    return count, total


# ------------------------------------------------------ what readers read
def device_ms(r, module_pattern: str, *wanted: str) -> float | None:
    """Device ms per execution of the programs matching ``module_pattern``
    in the ``wanted`` scopes, from the run's trace and the scope map its
    own metadata gives; None where the run has no trace, the trace no
    execution, or the map covers too little of it."""
    trace = getattr(r, "trace", None)
    raw = recorded_trace(r) if trace is not None else None
    if raw is None:
        return None
    n, by_scope, coverage = scope_seconds(trace, module_pattern,
                                          trace_op_scopes(raw))
    if not n or coverage < MIN_COVERAGE:
        return None
    return 1e3 * sum(by_scope.get(s, 0.0) for s in wanted) / n


def host_ms_per_step(r, *spans: str) -> float | None:
    """Host ms in the ``spans`` per ``serve.step`` span of the run's
    trace; None where the trace has no step span."""
    trace = getattr(r, "trace", None)
    if trace is None:
        return None
    steps, _ = span_seconds(trace, names.STEP)
    if not steps:
        return None
    return 1e3 * sum(span_seconds(trace, s)[1] for s in spans) / steps
