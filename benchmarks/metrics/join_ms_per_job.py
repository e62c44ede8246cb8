"""Layer: group program. Host time of the ``dispatch`` and ``settle``
spans of the waves of the join groups (the ops that carry a ``join``
block), over the window's jobs: what the two lookup joins — their own
sort and carry and the stages fused behind them — cost a job."""

from benchmarks.metrics import join_window


def read(r):
    found = join_window.host_seconds_and_probe_rows(r)
    if found is None or not r.window_jobs():
        return None
    return 1e3 * found[0] / r.window_jobs()
