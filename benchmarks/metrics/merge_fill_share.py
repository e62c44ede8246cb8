"""Layer: group program. Of the slots the window's cross-wave merges
read and sorted, the share that can hold a row: ``rows_bound`` — a
wave's fullest device's row count, which its settle brought home, times
the devices, summed over the merged waves — over ``slots`` of the
per-op ``merge`` blocks. A merge reads a wave up to the power-of-two
bucket of the largest count, so a wave just over a bucket reads 50 %
and waves that fill their capacity 100 %; what is left under 100 % is
that bucket's padding, which the merge's sort carries."""

from benchmarks.metrics import combine_window


def read(r):
    slots = rows = 0
    for after, before in combine_window.window_ops(r):
        n = combine_window.delta(after, before, "merge", "slots")
        if n is None:
            continue
        slots += n
        rows += combine_window.delta(after, before, "merge",
                                     "rows_bound") or 0
    if not slots:
        return None
    return 100.0 * rows / slots
