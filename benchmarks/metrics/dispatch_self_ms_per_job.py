"""Layer: group program. Self time of the program's ``dispatch`` span:
what a dispatch holds outside its ``enqueue`` child, which is this
executor's part — the wave's arrays, its slack, the program's key and
lookup, a map stage's extra arguments, the exchange record. With
``enqueue_ms_per_job`` it sums to ``dispatch_ms_per_job``. Over the
window's jobs."""

from benchmarks.metrics import wave_books


def read(r):
    return wave_books.span_ms_per_job(r, "dispatch", "self_s")
