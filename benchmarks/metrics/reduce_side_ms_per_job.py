"""Layer: group program. Host time of the ``dispatch`` and ``settle``
spans — the hub's per-op record of them — of the waves of every group
AFTER the map-side one (the ops whose group ran no map-side combine),
over the window's jobs: what the reduce side's waves cost the host,
however few rows they carry."""

from benchmarks.metrics import combine_window


def read(r):
    found = [d for after, before in combine_window.window_ops(r)
             if "combine" not in after
             for d in (combine_window.delta(after, before, "waves", f)
                       for f in ("dispatch_s", "settle_s"))
             if d is not None]
    if not found or not r.window_jobs():
        return None
    return 1e3 * sum(found) / r.window_jobs()
