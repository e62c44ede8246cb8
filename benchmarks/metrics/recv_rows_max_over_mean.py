"""Layer: exchange. Rows a device of the map-side outputs the window's
shuffle ops merged: the fullest device over the mean (1 = no skew)."""

from benchmarks.metrics import exchange_window


def read(r):
    rows = None
    for after, was in exchange_window.window_ops(r):
        old = was.get("recv_rows", [])
        new = [n - (old[i] if i < len(old) else 0)
               for i, n in enumerate(after["recv_rows"])]
        rows = new if rows is None else [a + b for a, b in zip(rows, new)]
    if not rows or not sum(rows):
        return None
    return max(rows) * len(rows) / sum(rows)
