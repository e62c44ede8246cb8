"""Layer: exchange. Bytes the ``all_to_all`` buckets of the window's
waves put on the interconnect, from the static plan (every bucket is
moved whole, valid rows or padding: ``record_exchange``'s
``ici_bytes``), in MiB. Over the window's jobs."""

from benchmarks.metrics import exchange_window


def read(r):
    return exchange_window.per_job(r, "ici_bytes", 2.0 ** -20)
