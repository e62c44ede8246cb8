"""Layer: readback + scan. The harness's own span from ``sess.run``
returned to the answer's columns on the host, over the window's jobs.
None for a pipeline whose job has no such step of its own."""


def read(r):
    secs = r.span_seconds_in_window("scan")
    if secs is None:
        return None
    return 1e3 * secs / r.window_jobs()
