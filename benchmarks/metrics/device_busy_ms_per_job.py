"""Layer: group program. Device busy time of the traced window over the
jobs traced."""


def read(r):
    if r.trace is None or not r.traced_jobs():
        return None
    return 1e3 * r.trace.busy_s / r.traced_jobs()
