"""Layer: exchange. The largest bucket slack the window's shuffle ops
ran their last wave at: the receive buffer of a wave is slack x the
shard's capacity, and the merged buffer behind it the waves' sum."""

from benchmarks.metrics import exchange_window


def read(r):
    ops = exchange_window.window_ops(r)
    if not ops:
        return None
    return max(after["slack"] for after, _ in ops)
