"""Device time of the sort operations inside the convert program
(``jit_convert``) over that program's device time, in %. Layer: convert
(``core/ordering.py``, ``core/reshaping.py``)."""


def read(r):
    trace = getattr(r, "trace", None)
    if trace is None:
        return None
    _, total = trace.module_calls(r"^jit_convert\b")
    if total <= 0:
        return None
    return 100.0 * trace.op_seconds(r"sort", r"^jit_convert\b") / total
