"""95th percentile of the wait from a request's scheduled arrival to its
seating in a slot (``Request.admit_t``), in ms, over the window's requests
due at least a second before the profiler started: starting it stalls the
submitting thread, and tracing slows the host. Layer: admission
(``serve/slots.py``, ``serve/feeder.py``, ``serve/scheduler.py``)."""
import numpy as np

CLEAR_OF_TRACE_S = 1.0


def read(r):
    admit = getattr(r, "admit", None)
    if admit is None:
        return None
    keep = np.ones(len(admit), bool)
    if getattr(r, "trace_start", None) is not None:
        keep = r.due < r.trace_start - CLEAR_OF_TRACE_S
    if not np.isfinite(admit[keep]).any():
        return None
    wait = np.where(np.isnan(admit), r.t_end, admit) - r.due
    return 1e3 * float(np.percentile(wait[keep], 95))
