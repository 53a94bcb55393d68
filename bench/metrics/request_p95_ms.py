"""95th percentile of a request's latency, from its scheduled arrival to
its predictions on the host (a request never answered counts until the
drain ended), in ms, over the window's requests due at least a second
before the profiler started: starting it stalls the submitting thread,
and tracing slows the host. Layer: serving loop (``serve/slots.py``
``run``: admission, step and routing together)."""
import numpy as np

CLEAR_OF_TRACE_S = 1.0


def read(r):
    latency = getattr(r, "latency", None)
    if latency is None:
        return None
    keep = np.ones(len(latency), bool)
    if getattr(r, "trace_start", None) is not None:
        keep = r.due < r.trace_start - CLEAR_OF_TRACE_S
    if not keep.any():
        return None
    return 1e3 * float(np.percentile(latency[keep], 95))
