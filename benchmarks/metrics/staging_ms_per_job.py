"""Layer: staging + upload. The executor's own staging breakdown (read,
decode, assemble, upload seconds, host clock) recorded inside the
window, summed over ops, over the window's jobs."""


def read(r):
    secs = r.staging_seconds_in_window()
    if secs is None or not r.window_jobs():
        return None
    return 1e3 * secs / r.window_jobs()
