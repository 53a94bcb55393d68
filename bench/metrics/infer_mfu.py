"""The GAT's model FLOPs per inference (``work_gat.gat_flops``) over the
chip's peak bf16 FLOP/s, divided by the device time of one execution of
``jit_gat_infer``, in %. Layer: inference."""
from bench import inputs_gat, work_gat


def read(r):
    trace = getattr(r, "trace", None)
    if trace is None:
        return None
    n, seconds = trace.module_calls(r"^jit_gat_infer\b")
    if not n:
        return None
    cfg = r.cell.config
    flops = work_gat.gat_flops(cfg["graph"]["n_nodes"],
                               work_gat.attended_edges(cfg),
                               inputs_gat.layer_dims(cfg))
    return 100.0 * flops / r.peaks["bf16_flops_per_s"] / (seconds / n)
