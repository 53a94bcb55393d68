"""Share of the traced window of the inference cell in which no operation
ran on the device, in %. Layer: device."""


def read(r):
    trace = getattr(r, "trace", None)
    if trace is None or trace.busy_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
