"""Layer: group program. The program's ``settle`` span: the host
blocked on a wave's signal scalars, that is on the device. Over the
window's jobs."""

from benchmarks.harness import spans


def read(r):
    return spans.per_job(r, ("settle",), "total_s", 1e3)
