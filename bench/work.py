"""Work counts: the bytes and operations the algorithms need, from sizes
alone. They do not depend on how the program implements the work, so a
later change cannot make them stale.
"""
from __future__ import annotations

INT32 = 4


def convert_bytes(n_nodes: int, n_edges: int) -> int:
    """Least HBM traffic of one COO -> CSC conversion: read the COO's
    (dst, src) int32 pairs, write the CSC index array (int32 per edge) and
    its pointer array (int32 per node, plus one). Real ``n_edges`` and
    ``n_nodes``, never the padded capacity."""
    return 2 * INT32 * n_edges + INT32 * n_edges + INT32 * (n_nodes + 1)


def sage_nodes_per_layer(fanouts: tuple[int, ...]) -> list[int]:
    """Nodes layer ``k`` (1-based) of an L-layer GraphSAGE minibatch
    computes for one seed: those within ``L - k`` hops of it in the
    sampled fan-out tree, ``sum_{h <= L-k} prod(fanouts[:h])``."""
    n_layers = len(fanouts)
    out = []
    for k in range(1, n_layers + 1):
        total, width = 0, 1
        for h in range(n_layers - k + 1):
            total += width
            if h < len(fanouts):
                width *= fanouts[h]
        out.append(total)
    return out


def sage_flops_per_prediction(d_in: int, d_hidden: int, n_classes: int,
                              fanouts: tuple[int, ...]) -> int:
    """Multiply-add FLOPs one GraphSAGE prediction needs: at layer k each
    of its nodes applies ``w_self`` and ``w_nb`` (2 * 2 * d_k_in * d_hidden),
    then the classifier head on the seed (2 * d_hidden * n_classes). The
    mean aggregation's additions, padded seeds and nodes recomputed by an
    implementation are not counted."""
    flops, d = 0, d_in
    for nodes in sage_nodes_per_layer(fanouts):
        flops += nodes * 2 * (d + d) * d_hidden
        d = d_hidden
    return flops + 2 * d_hidden * n_classes
