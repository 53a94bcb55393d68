"""Device time of the serving step's VID dedup and rename (scope
``sample.reindex``) per execution of ``jit_step``, in ms, over outermost
operations (``bench/scopes.py``). Layer: serve step."""
from bench import scopes


def read(r):
    if not scopes.names:
        return None
    return scopes.device_ms(r, r"^jit_step\b", scopes.names.SAMPLE_REINDEX)
