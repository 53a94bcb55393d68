"""Device time of the convert's key build and full-capacity sorts (scope
``convert.ordering``) per execution of ``jit_convert``, in ms, over
outermost operations (``bench/scopes.py``). Layer: convert."""
from bench import scopes


def read(r):
    if not scopes.names:
        return None
    return scopes.device_ms(r, r"^jit_convert\b",
                            scopes.names.CONVERT_ORDERING)
