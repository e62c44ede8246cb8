"""Layer: group program. The program's ``enqueue`` span, the child of
``dispatch`` around the jit call and the start of the signals' copy to
the host: the runtime's part of a dispatch — argument handling and the
enqueue itself — with the compile seam's lookup
(``program_lookup_ms_per_job``) in it. Over the window's jobs."""

from benchmarks.harness import spans


def read(r):
    return spans.per_job(r, ("enqueue",), "total_s", 1e3)
