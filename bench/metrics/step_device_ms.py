"""Device time of one execution of the serving step program
(``jit_step``: sample, re-convert and reindex, gather, forward for every
slot), averaged over the executions in the trace, in ms. Layer: serve
step."""


def read(r):
    trace = getattr(r, "trace", None)
    if trace is None:
        return None
    n, seconds = trace.module_calls(r"^jit_step\b")
    return 1e3 * seconds / n if n else None
