"""Inputs made from ``--seed``: the same seed gives the same graph,
weights and traffic; every seed gets the same amount of work."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import graphgen, traffic  # noqa: E402

SENTINEL = 0x7FFFFFFF
TINY = {"name": "tiny",
        "graph": {"n_nodes": 500, "n_edges": 6000, "capacity": 8192,
                  "d_feat": 12, "n_classes": 5, "assumed": {"alpha": 0.5}},
        "model": {"n_layers": 2, "d_hidden": 8}}
SERVE = json.loads(
    (REPO / "bench/traffic/open-zipf-reddit.json").read_text())


def _graph(seed):
    return [np.asarray(x) for x in graphgen.graph_arrays(seed, TINY)]


@pytest.mark.parametrize("seed", [0, 3, 2**31 + 17, 2**40 + 3])
def test_same_seed_same_graph_and_weights(seed):
    a, b = _graph(seed), _graph(seed)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    fa, pa = graphgen.model_inputs(seed, TINY)
    fb, pb = graphgen.model_inputs(seed, TINY)
    assert np.array_equal(fa, fb)
    assert np.array_equal(pa["head"], pb["head"])


def test_seeds_that_differ_only_above_32_bits_differ():
    a, b = _graph(5), _graph(5 + 2**32)
    assert not np.array_equal(a[0], b[0])


def test_graph_is_symmetric_padded_and_in_range():
    dst, src = _graph(1)
    e, n = TINY["graph"]["n_edges"], TINY["graph"]["n_nodes"]
    assert np.all(dst[e:] == SENTINEL) and np.all(src[e:] == SENTINEL)
    assert np.all((dst[:e] >= 0) & (dst[:e] < n))
    half = e // 2
    assert np.array_equal(dst[:half], src[half:e])
    assert np.array_equal(src[:half], dst[half:e])


def test_power_law_keeps_the_top_node_small():
    big = {"graph": {**TINY["graph"], "n_nodes": 20000, "n_edges": 400000,
                     "capacity": 1 << 19}}
    dst, _ = [np.asarray(x) for x in graphgen.graph_arrays(2, big)]
    deg = np.bincount(dst[:400000], minlength=20000)
    # alpha 0.5 over 20,000 ranks: the top rank holds ~0.15 % of draws
    assert deg.max() / 400000 < 0.005
    assert deg.mean() == 20


def test_traffic_is_the_same_for_a_seed_and_the_same_work_across_seeds():
    a = traffic.schedule(SERVE, 1000, 9, 4.0)
    b = traffic.schedule(SERVE, 1000, 9, 4.0)
    c = traffic.schedule(SERVE, 1000, 10, 4.0)
    assert np.array_equal(a.arrival_s, b.arrival_s) and a.seeds == b.seeds
    rate = SERVE["arrivals"]["rate_per_s"]
    assert len(a.seeds) == len(c.seeds) == round(rate * 4.0)
    assert sorted(map(len, a.seeds)) == sorted(map(len, c.seeds))
    assert a.seeds != c.seeds
    assert np.all(np.diff(a.arrival_s) >= 0) and a.arrival_s[-1] < 4.0
    for s in a.seeds:
        assert len(set(s)) == len(s) and all(0 <= v < 1000 for v in s)


def test_seed_counts_follow_the_inverse_law_exactly():
    counts = traffic.exact_counts(10000, SERVE["seeds_per_request"])
    k, p = traffic.count_law(SERVE["seeds_per_request"])
    assert len(counts) == 10000
    assert np.allclose(np.bincount(counts, minlength=9)[1:] / 10000, p,
                       atol=1e-4)
    assert abs(counts.mean() - 2.943) < 1e-3


def test_zipf_popularity_top_share():
    rng = np.random.default_rng(0)
    pop = traffic.Popularity({"law": "zipf", "s": 1.0}, 232965, rng)
    top = pop.perm[0]
    draws = np.concatenate([pop.draw(rng, 1) for _ in range(20000)])
    assert abs(np.mean(draws == top) - 0.0773) < 0.01
