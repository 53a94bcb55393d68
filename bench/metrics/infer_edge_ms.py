"""Device time of the whole-graph GAT's edge stage (scope ``infer.edge``:
chunk gathers, online softmax, attention-weighted sums, writes) per
execution of ``jit_gat_infer``, in ms, over outermost operations
(``bench/scopes.py``). Layer: inference."""
from bench import scopes


def read(r):
    scope = getattr(scopes.names, "INFER_EDGE", None)
    if scope is None:
        return None
    return scopes.device_ms(r, r"^jit_gat_infer\b", scope)
