"""Layer: device. 1 - union of device-op intervals / traced window."""


def read(r):
    if r.trace is None:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
