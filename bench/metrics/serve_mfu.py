"""GraphSAGE FLOPs the published model needs per prediction
(``work.sage_flops_per_prediction``) times predictions completed per
second in the window, over the chip's peak bf16 FLOP/s, in %. Layer: serve
step."""
from bench import work


def read(r):
    preds = getattr(r, "preds_in_window", 0)
    if not preds:
        return None
    g, m = r.cell.config["graph"], r.cell.config["model"]
    flops = work.sage_flops_per_prediction(
        g["d_feat"], m["d_hidden"], g["n_classes"], tuple(m["sample_sizes"]))
    return 100.0 * flops * preds / r.window_s / r.peaks["bf16_flops_per_s"]
