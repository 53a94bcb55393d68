"""With the timed path broken underneath, a run comes out not correct:
once for each fault a cell can have (one chip, so no exchange between
chips to leave out)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import perfbench_tiny as tiny  # noqa: E402
from repro.core.graph import CSC  # noqa: E402
from repro.engine import service  # noqa: E402
from repro.serve import GnnServeEngine, gnn  # noqa: E402
from repro.serve.request import Request  # noqa: E402

N_CLASSES = 41


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("bench"))


def _altered_answer(route):
    """Every served class moved to the next class id, where the engine
    turns the step's row into predictions."""
    def broken(req, emission):
        done = route(req, emission)
        if done:
            n = len(req.prompt)
            req.tokens_out[-n:] = [(c + 1) % N_CLASSES
                                   for c in req.tokens_out[-n:]]
        return done
    return broken


def _half_left_out(route):
    """Every other request of the stream is never computed: it gets class
    0 for each seed."""
    count = [0]

    def broken(req, emission):
        done = route(req, emission)
        if done:
            count[0] += 1
            if count[0] % 2:
                n = len(req.prompt)
                req.tokens_out[-n:] = [0] * n
        return done
    return broken


@pytest.mark.parametrize("fault", [_altered_answer, _half_left_out])
def test_broken_serving_is_not_correct(spec, monkeypatch, fault):
    monkeypatch.setattr(gnn, "gnn_route", fault(gnn.gnn_route))
    res = tiny.run(spec, tiny.SERVE, seed=21)
    assert res["correct"] is False
    assert res["checks"]["pred_gap"]["value"] > \
        res["checks"]["pred_gap"]["limit"]


def _unchanged(convert):
    """A convert that hands its input back: the sources in input order."""
    def broken(coo, cfg=None):
        csc = convert(coo, cfg=cfg)
        return CSC(ptr=csc.ptr, idx=coo.src, n_edges=coo.n_edges,
                   n_nodes=coo.n_nodes)
    return broken


def _altered_entry(convert):
    """A convert whose first index entry is wrong."""
    def broken(coo, cfg=None):
        csc = convert(coo, cfg=cfg)
        return CSC(ptr=csc.ptr, idx=csc.idx.at[0].add(1),
                   n_edges=csc.n_edges, n_nodes=coo.n_nodes)
    return broken


@pytest.mark.parametrize("fault,least", [(_unchanged, 100),
                                         (_altered_entry, 1)])
def test_broken_convert_is_not_correct(spec, monkeypatch, fault, least):
    monkeypatch.setattr(service, "convert_jit", fault(service.convert_jit))
    res = tiny.run(spec, tiny.CONVERT, seed=22)
    assert res["correct"] is False
    assert res["checks"]["csc_mismatch"]["value"] >= least


def test_a_request_that_never_answers_is_not_correct(spec, monkeypatch):
    """A request the engine loses counts as failed once the drain ends,
    and the run is not correct."""
    submit, count = GnnServeEngine.submit, [0]

    def losing(self, seeds):
        count[0] += 1
        if count[0] == 30:      # the 14th request of the window
            return Request(rid=-1, prompt=list(seeds), max_new=1)
        return submit(self, seeds)
    monkeypatch.setattr(GnnServeEngine, "submit", losing)
    res = tiny.run(spec, tiny.SERVE, seed=23)
    assert res["correct"] is False
    assert res["failed"] == 1
    assert res["checks"]["unanswered"] == {"value": 1, "limit": 0}
