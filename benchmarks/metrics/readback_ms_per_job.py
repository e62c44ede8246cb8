"""Layer: readback + scan. The program's ``readback`` span: a group
output's device arrays brought to host chunks for a result scan. Over
the window's jobs."""

from benchmarks.harness import spans


def read(r):
    return spans.per_job(r, ("readback",), "total_s", 1e3)
