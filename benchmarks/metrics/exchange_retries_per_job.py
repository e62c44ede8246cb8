"""Layer: exchange. Wave programs dispatched again after a bucket
overflowed, inside the window, over its jobs: 0 once every shuffle op
has settled on a slack that holds its keys."""

from benchmarks.metrics import exchange_window


def read(r):
    return exchange_window.per_job(r, "retries")
