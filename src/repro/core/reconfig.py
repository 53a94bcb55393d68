"""Execution modes and dynamic reconfiguration (paper §V-B, §VI).

COMPATIBILITY SHIM — the engine-management implementation moved to
``repro.engine.service`` (profiling, cost-model scoring, shape bucketing,
module-level jit dispatch, optional mesh sharding). The paper's three
system variants keep their names here:

* ``AutoPre``  — the UPE region is statically split into an ordering-only
  and a selection-only engine (half "resources" each; here: half lanes).
* ``StatPre``  — one time-multiplexed engine with a fixed configuration
  (tuned for an intermediate graph, as the paper tunes for MV).
* ``DynPre``   — StatPre + runtime reconfiguration, now a thin wrapper
  over ``PreprocService``.

On TPU, "reprogramming a bitstream" = switching to a different pre-jitted
executable. The jit cache is *module-level* (``core.pipeline.preprocess``
is jitted once at import): the first call per (config, input shape) pays
XLA compilation (the analog of the paper's offline Vivado synthesis);
every later Engine/DynPre/service — including freshly constructed ones —
hits that shared cache (the analog of bitstreams staged in DRAM,
~230 ms → ~0 here). The shim dispatches inputs exactly as given;
``PreprocService`` additionally pow2 shape-buckets them so the number of
compiled programs stays bounded. We model the paper's reconfiguration
latency explicitly so benchmarks can reproduce the Fig. 28 trade-off.
"""
from __future__ import annotations

import dataclasses

from .costmodel import (Calibration, EngineConfig, Workload,
                        bitstream_library, choose_config, estimate_seconds)

# Paper: 230 ms full reconfig; halved when only one region changes.
RECONFIG_S_FULL = 0.230
RECONFIG_S_PARTIAL = 0.115


@dataclasses.dataclass
class ReconfigDecision:
    reconfigure: bool
    config: EngineConfig
    predicted_gain_s: float
    reconfig_cost_s: float


def decide(w: Workload, current: EngineConfig | None,
           library: list[EngineConfig], cal: Calibration,
           switch_threshold: float = 1.5,
           reconfig_cost_s: float = RECONFIG_S_PARTIAL) -> ReconfigDecision:
    """DynPre's decision rule: score the library, switch when the predicted
    gain over the current configuration amortizes the reconfiguration.
    (Shared by ``DynPre`` and ``repro.engine.service.PreprocService``.)
    The candidate carries a concrete ``reindex_strategy``
    (``choose_config`` pins the Table-I winner), so the dispatched program
    is the one the model priced."""
    cand = choose_config(w, library, cal)
    if current is None:
        return ReconfigDecision(True, cand, float("inf"), reconfig_cost_s)
    cur = estimate_seconds(current, w, cal)["total"]
    new = estimate_seconds(cand, w, cal)["total"]
    gain = cur - new
    go = cur > new * switch_threshold and gain > reconfig_cost_s * 0.1
    return ReconfigDecision(go, cand, gain, reconfig_cost_s)


class Engine:
    """A preprocessing engine bound to one EngineConfig.

    Dispatches to the module-level jitted ``pipeline.preprocess`` — NOT a
    per-instance ``jax.jit`` wrapper. (The old per-``__init__`` wrapper
    carried an empty cache, so re-creating an engine with a previously
    used config recompiled, contradicting the staged-bitstream analogy.)
    """

    def __init__(self, cfg: EngineConfig, fanouts: tuple[int, ...]):
        self.cfg = cfg
        self.fanouts = fanouts

    def preprocess(self, coo, batch_nodes, key):
        # drop-in compatibility: inputs dispatch exactly as given (the old
        # Engine never padded); only PreprocService shape-buckets.
        from repro.engine.service import preprocess_jit
        return preprocess_jit(coo, batch_nodes, self.fanouts, key, self.cfg)


class DynPre:
    """Dynamic reconfiguration controller (thin wrapper over the service)."""

    def __init__(self, fanouts: tuple[int, ...],
                 library: list[EngineConfig] | None = None,
                 cal: Calibration | None = None,
                 switch_threshold: float = 1.5,
                 reconfig_cost_s: float = RECONFIG_S_PARTIAL):
        self.library = library or bitstream_library()
        self.cal = cal or Calibration()
        self.fanouts = fanouts
        self.threshold = switch_threshold
        self.reconfig_cost_s = reconfig_cost_s
        self.engine: Engine | None = None
        self.n_reconfigs = 0

    def profile(self, coo, batch_size: int) -> Workload:
        """Light-weight graph metadata capture (paper: <0.1 ms host-side)."""
        return Workload(n=coo.n_nodes, e=int(coo.n_edges), l=len(self.fanouts),
                        k=max(self.fanouts), b=batch_size)

    def decide(self, w: Workload) -> ReconfigDecision:
        current = self.engine.cfg if self.engine is not None else None
        return decide(w, current, self.library, self.cal, self.threshold,
                      self.reconfig_cost_s)

    def ensure(self, coo, batch_size: int) -> Engine:
        d = self.decide(self.profile(coo, batch_size))
        if d.reconfigure or self.engine is None:
            self.engine = Engine(d.config, self.fanouts)
            self.n_reconfigs += 1
        return self.engine

    def preprocess(self, coo, batch_nodes, key):
        eng = self.ensure(coo, int(batch_nodes.shape[0]))
        return eng.preprocess(coo, batch_nodes, key)


def statpre(fanouts: tuple[int, ...],
            cfg: EngineConfig | None = None) -> Engine:
    """StatPre: fixed intermediate-graph tuning (paper: tuned for MV)."""
    return Engine(cfg or EngineConfig(w_upe=4096, n_upe=16,
                                      w_scr=2048, n_scr=512), fanouts)


def autopre(fanouts: tuple[int, ...]) -> Engine:
    """AutoPre: statically split lanes (half for ordering, half for
    selection). In the cycle model this halves n_upe for each stage; the
    executable is the same program with a half-lane config."""
    return Engine(EngineConfig(w_upe=4096, n_upe=8, w_scr=2048, n_scr=512),
                  fanouts)
