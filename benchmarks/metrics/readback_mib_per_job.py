"""Layer: readback + scan. Bytes the program's ``readback`` spans
moved device to host (what crossed, not what was valid), in MiB. Over
the window's jobs."""

from benchmarks.harness import spans


def read(r):
    return spans.per_job(r, ("readback",), "bytes", 2.0 ** -20)
