"""Share of the memory roofline the whole-graph GAT's edge stage reaches:
its least bytes per inference (``work_gat.edge_bytes``) over the chip's
peak HBM bandwidth, divided by the stage's device time per inference
(``infer_edge_ms``), in %. Layer: inference."""
from bench import harness, inputs_gat, work_gat


def read(r):
    edge_ms = harness.metric_reader("infer_edge_ms")(r)
    if not edge_ms:
        return None
    cfg = r.cell.config
    least_s = work_gat.edge_bytes(
        cfg["graph"]["n_nodes"], work_gat.attended_edges(cfg),
        inputs_gat.layer_dims(cfg)) / r.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (edge_ms / 1e3)
