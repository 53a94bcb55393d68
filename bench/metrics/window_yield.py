"""Requests an admission window seats on average: ``ServeStats``'s
``window_seated`` over ``window_waits`` across the window. Layer:
admission."""


def read(r):
    waits = getattr(r, "window_waits", 0)
    if not waits:
        return None
    return r.window_seated / waits
