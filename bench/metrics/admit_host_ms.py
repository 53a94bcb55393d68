"""Host time of the serving loop's admission (spans ``serve.admit`` and
``serve.admit_window``) per ``serve.step`` span of the trace, in ms.
Layer: admission."""
from bench import scopes


def read(r):
    if not scopes.names:
        return None
    return scopes.host_ms_per_step(r, scopes.names.ADMIT,
                                   scopes.names.ADMIT_WINDOW)
