"""Layer: group program. Self time of the program's ``group`` span:
what is left of a group on its own thread outside every child span
(``stage_wait``, ``dispatch``, ``settle``, ``merge``, ``sync.*``,
``mutex_wait``, ``shuffle_plan``) — the wave loop's bookkeeping, the
per-wave telemetry records and ``memory_stats()`` sample, the building
of outputs. Over the window's jobs."""

from benchmarks.metrics import wave_books


def read(r):
    return wave_books.span_ms_per_job(r, "group", "self_s")
