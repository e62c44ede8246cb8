"""Layer: group program. The program's ``dispatch`` span: host time
to look up a wave's program and enqueue it (the jit call returns at
enqueue). Over the window's jobs."""

from benchmarks.harness import spans


def read(r):
    return spans.per_job(r, ("dispatch",), "total_s", 1e3)
