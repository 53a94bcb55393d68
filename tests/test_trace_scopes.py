"""The program's trace annotations: every named scope reaches the
compiled programs' op metadata, no instruction carries two of them,
``op_scopes`` maps every instruction, the serving loop's host spans land
in a profiler trace once per step, and the admission-window counters."""
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scopes
from repro.configs.graphsage_reddit import smoke_config
from repro.core import pipeline
from repro.core.costmodel import EngineConfig
from repro.core.delta import EdgeDelta
from repro.core.graph import COO, random_coo
from repro.engine import service
from repro.launch import hlo_analysis
from repro.models.gnn import GNNConfig, gnn_init
from repro.serve import GnnServeEngine

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from bench import tracing  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

N_NODES = 256
_rng = np.random.default_rng(0)
_dst, _src = random_coo(_rng, N_NODES, 1500)
COO_G = COO.from_arrays(_dst, _src, N_NODES, capacity=2048)
CSC_G = pipeline.convert(COO_G)
FEATS = jnp.asarray(_rng.normal(size=(N_NODES, 12)).astype(np.float32))
GCFG = smoke_config()
PARAMS = gnn_init(GCFG, jax.random.PRNGKey(1), d_in=12, n_classes=7)

GAT_CFG = GNNConfig(name="gat-scopes", kind="gat", n_layers=2, d_hidden=4,
                    n_heads=2, out_heads=2, skip=True)
GAT_PARAMS = gnn_init(GAT_CFG, jax.random.PRNGKey(2), d_in=12, n_classes=7)

STEP_SCOPES = {scopes.SAMPLE_SELECT, scopes.SAMPLE_REINDEX,
               scopes.SAMPLE_RECONVERT, scopes.SERVE_GATHER,
               scopes.SERVE_FORWARD}


def _engine(**kw):
    return GnnServeEngine(GCFG, PARAMS, CSC_G, FEATS, fanouts=(3, 2),
                          n_slots=kw.pop("n_slots", 2), seed_cap=8, **kw)


def _entry_instructions(text: str) -> list[str]:
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    return [m.group(1) for m in map(hlo_analysis._INSTR_RE.match,
                                    entry.splitlines()[1:]) if m]


@pytest.fixture(scope="module")
def texts():
    delta = EdgeDelta.from_arrays([1, 2], [3, 4], [], [], n_nodes=N_NODES,
                                  capacity=8)
    return {
        "convert": service.convert_jit.lower(
            COO_G, cfg=EngineConfig()).compile().as_text(),
        # the route a TPU takes, forced here: the windowed pointer kernel
        # in the interpreter, with its work list
        "convert_windowed": jax.jit(lambda c: pipeline.convert(
            c, EngineConfig(), _windowed=True)).lower(
                COO_G).compile().as_text(),
        "step": _engine().step_hlo_text(),
        "delta": service.apply_delta_jit.lower(
            CSC_G, delta, cfg=EngineConfig()).compile().as_text(),
        "gat_infer": service.gat_infer_jit.lower(
            CSC_G, FEATS, GAT_PARAMS, cfg=GAT_CFG).compile().as_text(),
    }


@pytest.mark.parametrize("program,wanted", [
    ("convert", {scopes.CONVERT_ORDERING, scopes.CONVERT_POINTER}),
    ("convert_windowed", {scopes.CONVERT_ORDERING, scopes.CONVERT_POINTER}),
    ("step", STEP_SCOPES),
    ("delta", {scopes.DELTA_APPLY}),
    ("gat_infer", {scopes.INFER_PROJECT, scopes.INFER_EDGE}),
])
def test_every_scope_reaches_the_compiled_program(texts, program, wanted):
    # op_scopes raises on an instruction that carries two listed scopes
    m = hlo_analysis.op_scopes(texts[program])
    assert set(m.values()) - {None} == wanted
    entry = _entry_instructions(texts[program])
    assert entry and all(op in m for op in entry)


def test_a_path_with_two_scopes_raises():
    assert hlo_analysis.op_scope(
        "jit(step)/vmap(sample.reindex)/jit(sort)/sort") == "sample.reindex"
    assert hlo_analysis.op_scope("jit(f)/sample.selection/x") is None
    with pytest.raises(ValueError, match="carries the scopes"):
        hlo_analysis.op_scope("jit(f)/serve.gather/serve.forward/dot")


def test_each_step_leaves_one_step_and_one_route_span(tmp_path):
    eng = _engine()
    rng = np.random.default_rng(3)
    for _ in range(7):
        eng.submit(rng.choice(N_NODES, 3, replace=False).tolist())
    eng.close_submissions()
    with jax.profiler.trace(str(tmp_path)):
        done = eng.run()
    assert len(done) == 7 and eng.stats.steps >= 4
    (path,) = tmp_path.glob("**/*.xplane.pb")
    s = tracing.reduce_trace(path.read_bytes(), 0, 1.0)
    counts = {}
    for _, ev in s.host:
        for name in scopes.HOST_SPANS:
            if name in ev.names:
                counts[name] = counts.get(name, 0) + int(
                    (ev.name == ev.names.index(name)).sum())
    assert counts[scopes.STEP] == eng.stats.steps
    assert counts[scopes.ROUTE] == eng.stats.steps
    assert counts[scopes.WAIT] == eng.stats.steps
    assert counts[scopes.FEED] == 7


def test_admission_window_counters():
    """Five requests on four slots: whatever the waves, one leaves a slot
    free while the stream is open, so an admission window opens; windows
    seat no more requests than were admitted in all."""
    eng = _engine(n_slots=4)
    rng = np.random.default_rng(4)
    done = []
    loop = threading.Thread(target=lambda: done.extend(eng.run()))
    loop.start()
    for _ in range(5):
        eng.submit(rng.choice(N_NODES, 2, replace=False).tolist())
    deadline = time.monotonic() + 120
    while eng.stats.retired < 5 and time.monotonic() < deadline:
        time.sleep(0.01)
    eng.close_submissions()
    loop.join(timeout=120)
    st = eng.stats
    assert len(done) == 5 and st.admitted == 5
    assert st.window_waits > 0
    assert 0 <= st.window_seated <= st.admitted
