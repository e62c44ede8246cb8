"""Layer: group program. The program's ``mutex_wait`` span, opened
only where a group found the wave mutex held by another: seconds
concurrent groups of one job waited for each other's waves. A program
that opens the span on a contended wait alone (its table splits
``dispatch`` at the enqueue) and has none has waited 0.0: groups that
run one after another never contend. A program whose ``mutex_wait``
times every acquire gives nothing to read. Over the window's jobs."""

from benchmarks.metrics import wave_books


def read(r):
    if not wave_books.closed(r) or not r.window_jobs():
        return None
    return wave_books.span_ms_per_job(r, "mutex_wait", "total_s") or 0.0
