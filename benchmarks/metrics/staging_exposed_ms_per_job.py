"""Layer: staging + upload. The program's ``stage_wait`` span: the
part of staging the compute thread sat waiting on (wave 0's inline
stage and every wait for the prefetch thread). Over the window's
jobs."""

from benchmarks.harness import spans


def read(r):
    return spans.per_job(r, ("stage_wait",), "total_s", 1e3)
