"""Share of the edge slots of the whole-graph GAT's chunks that hold a
graph edge: the program's counters, graph edges attended (``edges`` less
the one self loop per node, which no chunk holds) over ``chunks`` times
the chunk size, in %. Layer: inference."""


def read(r):
    counters = getattr(r, "counters", None)
    size = getattr(r, "chunk_edges", None)
    if not counters or not size or not counters.get("chunks"):
        return None
    edges = counters["edges"] - r.cell.config["graph"]["n_nodes"]
    return 100.0 * edges / (counters["chunks"] * size)
