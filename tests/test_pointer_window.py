"""The windowed SCR pointer build (``kernels/pointer_window.py``) in the
Pallas interpreter: equal to a binary search on sorted streams of every
shape its work list has to handle, equal to the rank search through
``pipeline.convert``, and its live-tile count."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pipeline
from repro.core.costmodel import EngineConfig
from repro.core.graph import COO, SENTINEL_I, random_coo
from repro.core.set_count import searchsorted_oracle
from repro.kernels import pointer_window as pw

jax.config.update("jax_platform_name", "cpu")

T, C = 128, 1024  # the smallest tiling Mosaic takes, so the cases stay small


def _stream(n_nodes, valid, capacity):
    """A sorted dst column: ``valid`` VIDs, then the SENTINEL tail."""
    out = np.full(capacity, SENTINEL_I, np.int32)
    out[:len(valid)] = np.sort(np.asarray(valid, np.int64))
    assert out[:len(valid)].max(initial=0) < n_nodes
    return out


def _hub(rng):
    # one node holds 70 % of 12,000 edges: its window spans ~8 chunks
    d = rng.integers(0, 3000, 12000)
    d[:8400] = 1234
    return 3000, d, 16384


def _empty_runs(rng):
    # edges only on [0, 60) and [2900, 3000): blocks 1-21 have no edge
    d = np.concatenate([rng.integers(0, 60, 3000),
                        rng.integers(2900, 3000, 3000)])
    return 3000, d, 8192


CASES = {
    "hub_window_spans_many_chunks": _hub,
    "runs_of_empty_target_blocks": _empty_runs,
    "no_edges": lambda rng: (500, np.zeros(0, np.int64), 2048),
    "targets_not_a_multiple_of_T": lambda rng: (
        1000, rng.integers(0, 1000, 5000), 8192),
    "capacity_of_one_chunk": lambda rng: (777, rng.integers(0, 777, C), C),
    "sentinel_tail_longer_than_edges": lambda rng: (
        4000, rng.integers(0, 4000, 700), 8192),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_windowed_pointer_array_matches_searchsorted(case):
    n_nodes, valid, capacity = CASES[case](np.random.default_rng(7))
    dst = jnp.asarray(_stream(n_nodes, valid, capacity))
    got = pw.windowed_pointer_array(dst, n_nodes, t_block=T, e_block=C)
    want = searchsorted_oracle(dst, jnp.arange(n_nodes + 1, dtype=jnp.int32))
    np.testing.assert_array_equal(got, want)
    live, steps = pw.live_tile_share(np.asarray(dst), n_nodes, T, C)
    assert 0 < live <= steps == capacity // C + -(-(n_nodes + 1) // T)


def test_convert_gives_the_same_csc_on_both_pointer_routes():
    rng = np.random.default_rng(3)
    dst, src = random_coo(rng, 2000, 9000)
    coo = COO.from_arrays(dst, src, 2000, capacity=16384)
    rank = pipeline.convert(coo, EngineConfig(), _windowed=False)
    window = pipeline.convert(coo, EngineConfig(), _windowed=True)
    default = pipeline.convert(coo, EngineConfig())  # the CPU's rank search
    for csc in (window, default):
        np.testing.assert_array_equal(csc.ptr, rank.ptr)
        np.testing.assert_array_equal(csc.idx, rank.idx)
        assert int(csc.n_edges) == int(rank.n_edges)


def test_live_tile_share_counts_the_work_list():
    # block 0 (VIDs 0-127) fills chunks 0-2 exactly, block 1 has no edge:
    # 3 tiles + 1 for the empty block, of 8 chunks + 2 blocks of steps
    dst = _stream(255, np.repeat(np.arange(128), 24), 8192)
    assert pw.live_tile_share(dst, 255, T, C) == (4, 10)
    block, chunk, live = pw.work_list(jnp.asarray(dst), 255, T, C)
    assert int(live) == 4
    np.testing.assert_array_equal(block, [0, 0, 0, 1] + [1] * 6)
    np.testing.assert_array_equal(chunk, [0, 1, 2, 3] + [3] * 6)
