"""Layer: group program. The program's ``merge`` span: host time of
the cross-wave merge (program lookup and enqueue of one W-way concat).
Over the window's jobs."""

from benchmarks.harness import spans


def read(r):
    return spans.per_job(r, ("merge",), "total_s", 1e3)
