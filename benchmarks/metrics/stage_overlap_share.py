"""Layer: staging + upload. Of the window's pipelined waves — one a
wave behind a group's first, the ``stage_waits`` of the per-op
``waves`` blocks — the share whose stage began while another stage of
the same group was still under way (``stages_overlapped``): how often a
group that uploads its waves kept two stages in flight. A group that
stages zero-copy views keeps one worker and adds waits but no overlap,
so a cell reads well under 100 %. A program that does not count
``stages_overlapped`` gives nothing to read."""

from benchmarks.metrics import combine_window


def read(r):
    waits = overlapped = 0
    for after, before in combine_window.window_ops(r):
        n = combine_window.delta(after, before, "waves",
                                 "stages_overlapped")
        if n is None:
            continue
        overlapped += n
        waits += combine_window.delta(after, before, "waves",
                                      "stage_waits") or 0
    if not waits:
        return None
    return 100.0 * overlapped / waits
