"""Share of the slots the window's steps ran that held a request:
requests admitted over steps times slots, in %. Layer: admission."""


def read(r):
    steps = getattr(r, "steps", 0)
    if not steps:
        return None
    return 100.0 * r.admitted / (steps * r.n_slots)
