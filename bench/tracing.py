"""Profiler session and the reduction from a device trace to numbers.

A trace holds device planes (``/device:TPU:<n>``) whose ``XLA Modules``
line has one event per program execution (``jit_<function>(<id>)``) and
whose ``XLA Ops`` line has one event per HLO operation, and host planes
whose lines are threads. Times are nanoseconds on one clock.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import re
import shutil
import time
from pathlib import Path

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


class Session:
    """The profiler over part of a run: ``start()`` and ``stop()`` bound
    the traced window, whose host-clock length is ``window_s``."""

    def __init__(self, out_dir: Path):
        self.out_dir = Path(out_dir)
        self.t_start = self.t_stop = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        import jax
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    @property
    def window_s(self) -> float:
        return self.t_stop - self.t_start

    def summary(self, devs) -> "TraceSummary":
        """The trace reduced for the accelerators among ``devs`` (a CPU
        has no device plane)."""
        n_devices = sum(d.platform != "cpu" for d in devs)
        paths = glob.glob(str(self.out_dir / "**" / "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace under {self.out_dir}, "
                               f"found {paths}")
        return reduce_trace(Path(paths[0]).read_bytes(), n_devices,
                            self.window_s)


@dataclasses.dataclass
class Events:
    """Events of one line as arrays; ``name`` indexes ``names``."""

    names: list[str]
    name: np.ndarray
    start: np.ndarray   # ns
    end: np.ndarray     # ns

    @classmethod
    def of(cls, line) -> "Events":
        names, index, rows = [], {}, []
        for ev in line.events:
            i = index.setdefault(ev.name, len(index))
            if i == len(names):
                names.append(ev.name)
            rows.append((i, ev.start_ns, ev.start_ns + ev.duration_ns))
        arr = np.asarray(rows, np.float64).reshape(-1, 3)
        return cls(names, arr[:, 0].astype(np.int64), arr[:, 1], arr[:, 2])

    def select(self, pattern: str) -> np.ndarray:
        """Mask of the events whose name matches the regex ``pattern``."""
        hit = np.array([bool(re.search(pattern, n)) for n in self.names],
                       bool)
        return hit[self.name] if len(self.names) else np.zeros(0, bool)


@dataclasses.dataclass
class Device:
    modules: Events
    ops: Events


@dataclasses.dataclass
class TraceSummary:
    """A trace reduced to what the per-layer readers need."""

    window_s: float
    devices: list[Device]
    host: list[tuple[str, Events]]   # (thread name, events)

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        return float(np.mean([union_ns(d.ops.start, d.ops.end)
                              for d in self.devices])) / 1e9

    def module_calls(self, pattern: str) -> tuple[int, float]:
        """(executions, device seconds) of programs matching ``pattern``,
        summed over devices. An execution in which no operation started,
        as one that began as the trace stopped, is not counted."""
        n, total = 0, 0.0
        for d in self.devices:
            starts = np.sort(d.ops.start)
            has_op = (np.searchsorted(starts, d.modules.end, side="left")
                      > np.searchsorted(starts, d.modules.start, side="left"))
            m = d.modules.select(pattern) & has_op
            n += int(m.sum())
            total += float(np.sum(d.modules.end[m] - d.modules.start[m]))
        return n, total / 1e9

    def op_seconds(self, op_pattern: str, module_pattern: str) -> float:
        """Device seconds of operations matching ``op_pattern`` that ran
        inside an execution of a program matching ``module_pattern``."""
        total = 0.0
        for d in self.devices:
            m = d.modules.select(module_pattern)
            inside = within(d.ops.start, d.modules.start[m], d.modules.end[m])
            o = d.ops.select(op_pattern) & inside
            total += float(np.sum(d.ops.end[o] - d.ops.start[o]))
        return total / 1e9

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` operations (by ``module:op`` name) that took the most
        device time, in seconds."""
        acc: dict[str, float] = {}
        for d in self.devices:
            mod = module_of(d)
            for k in range(len(d.ops.name)):
                key = f"{mod[k]}:{d.ops.names[d.ops.name[k]]}"
                acc[key] = acc.get(key, 0.0) + (d.ops.end[k]
                                                - d.ops.start[k]) / 1e9
        return [[k, v] for k, v in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The ``n`` longest gaps between operations on the first device,
        each named by the host event that overlaps it most (the shortest
        such event on ties), in seconds."""
        if not self.devices or len(self.devices[0].ops.start) == 0:
            return []
        d = self.devices[0]
        s, e = merged(d.ops.start, d.ops.end)
        gaps = sorted(zip(e[:-1], s[1:]), key=lambda g: g[0] - g[1])[:n]
        out = []
        for g0, g1 in gaps:
            best, label = (0.0, 0.0), "no host event"
            for thread, ev in self.host:
                ov = np.minimum(ev.end, g1) - np.maximum(ev.start, g0)
                for k in np.flatnonzero(ov > 0):
                    score = (ov[k], -(ev.end[k] - ev.start[k]))
                    if score > best:
                        best = score
                        label = f"{thread}:{ev.names[ev.name[k]]}"
            out.append([label, (g1 - g0) / 1e9])
        return out


def merged(start: np.ndarray, end: np.ndarray):
    """Sorted, non-overlapping cover of the intervals."""
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    new = np.concatenate([[True], s[1:] > reach[:-1]])
    idx = np.flatnonzero(new)
    return s[idx], np.maximum.reduceat(e, idx) if len(idx) else e[:0]


def union_ns(start: np.ndarray, end: np.ndarray) -> float:
    if len(start) == 0:
        return 0.0
    s, e = merged(start, end)
    return float(np.sum(e - s))


def within(t: np.ndarray, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Mask of the times ``t`` that fall inside one of the (disjoint)
    intervals ``[lo, hi)``."""
    if len(lo) == 0:
        return np.zeros(len(t), bool)
    order = np.argsort(lo)
    lo, hi = lo[order], hi[order]
    k = np.searchsorted(lo, t, side="right") - 1
    return (k >= 0) & (t < hi[np.clip(k, 0, None)])


def module_of(d: Device) -> list[str]:
    """The program name (``jit_step``) each op of ``d`` ran in."""
    lo, hi = d.modules.start, d.modules.end
    order = np.argsort(lo)
    k = np.searchsorted(lo[order], d.ops.start, side="right") - 1
    out = []
    for t, kk in zip(d.ops.start, k):
        if kk >= 0 and t < hi[order][kk]:
            name = d.modules.names[d.modules.name[order][kk]]
            out.append(name.split("(")[0])
        else:
            out.append("?")
    return out


def reduce_trace(xspace: bytes, n_devices: int,
                 window_s: float) -> TraceSummary:
    """Reduce a serialized XSpace (optionally gzipped) to a summary of the
    first ``n_devices`` device planes and every host thread."""
    from jax.profiler import ProfileData
    if xspace[:2] == b"\x1f\x8b":
        xspace = gzip.decompress(xspace)
    pd = ProfileData.from_serialized_xspace(xspace)
    devices, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                devices.append((plane.name, Device(
                    Events.of(lines[MODULES_LINE]) if MODULES_LINE in lines
                    else Events([], np.zeros(0, np.int64), np.zeros(0),
                                np.zeros(0)),
                    Events.of(lines[OPS_LINE]))))
        elif plane.name.startswith("/host:"):
            host.extend((ln.name, Events.of(ln)) for ln in plane.lines)
    devices.sort(key=lambda pd_: int(pd_[0].rsplit(":", 1)[1]))
    if len(devices) < n_devices:
        raise RuntimeError(f"trace has {len(devices)} device planes with "
                           f"ops, expected {n_devices}")
    return TraceSummary(window_s, [d for _, d in devices[:n_devices]], host)

