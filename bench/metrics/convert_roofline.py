"""Share of the memory roofline one convert reaches: the least bytes a
COO -> CSC conversion moves (``work.convert_bytes`` of the real node and
edge counts) over the chip's peak HBM bandwidth, divided by the device
time of one execution of the convert program, in %. Layer: convert."""
from bench import work


def read(r):
    trace = getattr(r, "trace", None)
    if trace is None:
        return None
    n, seconds = trace.module_calls(r"^jit_convert\b")
    if not n:
        return None
    g = r.cell.config["graph"]
    least_s = (work.convert_bytes(g["n_nodes"], g["n_edges"])
               / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (seconds / n)
