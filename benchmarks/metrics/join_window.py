"""Not a metric: what the two readers of the per-op ``join`` block
share.

``Session.telemetry_summary()["ops"][op]`` holds, for an op whose group
ran a lookup join, ``join`` — ``probe_rows``, ``build_rows``,
``matched_rows`` (summed over its waves since the session began),
``lowering`` and ``wide_columns`` — and in ``waves`` the host seconds of
the op's ``dispatch`` and ``settle`` spans. A program without the block
(one that has no such join) gives nothing to read and both metrics are
left out of the line."""

from benchmarks.metrics import combine_window


def host_seconds_and_probe_rows(reading):
    """``(seconds, rows)`` inside the window, over the ops that carry a
    ``join`` block: host seconds of their waves' ``dispatch`` +
    ``settle``, and the probe rows that reached them. None without such
    an op."""
    seconds = rows = 0
    found = False
    for after, before in combine_window.window_ops(reading):
        probed = combine_window.delta(after, before, "join", "probe_rows")
        if probed is None:
            continue
        found = True
        rows += probed
        seconds += sum(
            combine_window.delta(after, before, "waves", f) or 0.0
            for f in ("dispatch_s", "settle_s"))
    return (seconds, rows) if found else None
