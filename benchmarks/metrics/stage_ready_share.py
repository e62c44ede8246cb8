"""Layer: staging + upload. Of the window's pipelined ``stage_wait``s
— one a wave behind a group's first — the share whose wave the
prefetch thread had staged before the compute thread asked for it
(``stage_waits_ready`` over ``stage_waits`` of the per-op ``waves``
blocks). Under 100 % the compute thread waits for its uploads; how
long is ``staging_exposed_ms_per_job``."""

from benchmarks.metrics import combine_window


def read(r):
    waits = ready = 0
    for after, before in combine_window.window_ops(r):
        n = combine_window.delta(after, before, "waves", "stage_waits")
        if n is None:
            continue
        waits += n
        ready += combine_window.delta(after, before, "waves",
                                      "stage_waits_ready") or 0
    if not waits:
        return None
    return 100.0 * ready / waits
