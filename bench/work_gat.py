"""Work counts of one whole-graph GAT inference, from sizes alone: the
model's FLOPs and the least bytes its edge stage moves. They do not
depend on how the program does the work.

Sizes: ``n`` nodes, ``e`` edges attended per layer (the graph's edges
plus one self loop per node), and per layer (d_in, heads, width, d_out)
as ``inputs_gat.layer_dims`` gives them.
"""
from __future__ import annotations

F32 = 4


def attended_edges(cfg: dict) -> int:
    """Edges each layer attends over: the graph's, plus a self loop per
    node (the few self loops the graph has of its own are not taken
    off)."""
    g = cfg["graph"]
    return g["n_edges"] + g["n_nodes"]


def gat_flops(n: int, e: int, dims) -> int:
    """Multiply-add FLOPs of one inference: per layer the projection
    ``h W`` (2 n d_in H D), the skip (2 n d_in d_out) and the
    attention-weighted sum (2 e H D). Scores, softmax and ELU are left
    out."""
    return sum(2 * n * d_in * heads * width + 2 * n * d_in * d_out
               + 2 * e * heads * width
               for d_in, heads, width, d_out in dims)


def edge_bytes(n: int, e: int, dims) -> int:
    """Least HBM bytes of the edge stage of one inference: per layer, each
    edge reads its source id, its z row and its H attention terms
    (``e (4 + 4 H D + 4 H)``), and each node reads its own z row and
    writes its output row (``n 8 H D``)."""
    return sum(e * (F32 + F32 * heads * width + F32 * heads)
               + n * 2 * F32 * heads * width
               for _, heads, width, _ in dims)
