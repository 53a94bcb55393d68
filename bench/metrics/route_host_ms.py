"""Host time of the serving loop's routing (span ``serve.route``: the
emission to the host and ``scheduler.process``) per ``serve.step`` span
of the trace, in ms. Layer: serving loop."""
from bench import scopes


def read(r):
    if not scopes.names:
        return None
    return scopes.host_ms_per_step(r, scopes.names.ROUTE)
