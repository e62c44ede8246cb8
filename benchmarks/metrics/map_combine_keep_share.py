"""Layer: group program. Rows the map-side combine emitted over the
rows its groups took, over the window, in percent: what is left for the
shuffle, the merge and the reduce side. 100 % would mean the combine
ran after the shuffle."""

from benchmarks.metrics import combine_window


def read(r):
    rows_in = rows_out = 0
    for after, before in combine_window.window_ops(r):
        took = combine_window.delta(after, before, "combine", "rows_in")
        if took:
            rows_in += took
            rows_out += combine_window.delta(after, before, "combine",
                                             "rows_out")
    return 100.0 * rows_out / rows_in if rows_in else None
