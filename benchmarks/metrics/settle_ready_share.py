"""Layer: group program. Of the window's settles — one a dispatched
wave: the host reading that wave's signal vector — the share whose wave
had already finished when the settle opened (``settles_ready`` over
``settles`` of the per-op ``waves`` blocks), so that the read found the
copy which the dispatch had started and cost the host a memcpy. Under
100 % the device, or the copy, is what the remaining settles waited
for."""

from benchmarks.metrics import combine_window


def read(r):
    settles = ready = 0
    for after, before in combine_window.window_ops(r):
        n = combine_window.delta(after, before, "waves", "settles")
        if n is None:
            continue
        settles += n
        ready += combine_window.delta(after, before, "waves",
                                      "settles_ready") or 0
    if not settles:
        return None
    return 100.0 * ready / settles
