"""Layer: staging + upload. Seconds the ``meshwave-prefetch`` threads
held a staged wave that the full queue would not take — the stager
waiting for the compute thread (``prefetch_blocked_s`` of the per-op
``waves`` blocks). Near 0 the stager never runs ahead of its depth: a
deeper queue alone buys nothing. Over the window's jobs."""

from benchmarks.metrics import combine_window


def read(r):
    found = [d for d in (
        combine_window.delta(after, before, "waves", "prefetch_blocked_s")
        for after, before in combine_window.window_ops(r))
        if d is not None]
    if not found or not r.window_jobs():
        return None
    return 1e3 * sum(found) / r.window_jobs()
