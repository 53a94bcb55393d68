"""Work counts against hand-worked values, and the peaks table."""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import peaks, work  # noqa: E402


@pytest.mark.parametrize("n_nodes,n_edges,want", [
    # 12 B per edge (8 read, 4 written) + 4 B per pointer entry
    (2_449_029, 123_718_280, 1_494_415_480),   # ogbn-products
    (232_965, 114_615_892, 1_376_322_568),     # Reddit
    (3, 5, 12 * 5 + 4 * 4),
])
def test_convert_bytes(n_nodes, n_edges, want):
    assert work.convert_bytes(n_nodes, n_edges) == want


@pytest.mark.parametrize("fanouts,want", [
    ((25, 10), [26, 1]),
    ((15, 10, 5), [166, 16, 1]),
    ((4,), [1]),
])
def test_nodes_per_layer_follow_the_fanout_tree(fanouts, want):
    assert work.sage_nodes_per_layer(fanouts) == want


@pytest.mark.parametrize("args,want", [
    # graphsage-reddit: 26 nodes x 2(602+602)128 + 1 x 2(128+128)128
    # + head 2 x 128 x 41
    ((602, 128, 41, (25, 10)), 8_013_824 + 65_536 + 10_496),
    # graphsage-products: 166 x 2(100+100)256 + 16 x 2(256+256)256
    # + 1 x 2(256+256)256 + head 2 x 256 x 47
    ((100, 256, 47, (15, 10, 5)),
     16_998_400 + 4_194_304 + 262_144 + 24_064),
])
def test_sage_flops_per_prediction(args, want):
    assert work.sage_flops_per_prediction(*args) == want


def test_v5e_peaks_and_their_source():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert "TPU v5e" in p["source"]


def test_an_unknown_device_raises():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
