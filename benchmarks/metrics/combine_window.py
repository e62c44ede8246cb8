"""Not a metric: what the readers of the per-op ``combine`` and
``waves`` blocks share.

``Session.telemetry_summary()["ops"][op]`` holds, summed since the
session began: ``combine`` — for an op whose group ran a map-side
combine — ``rows_in`` (the rows its waves staged), ``rows_out`` (the
rows of its merged output), ``lowering`` and ``wide_columns``; and in
``waves`` the host seconds of the op's ``dispatch`` and ``settle`` spans
(``dispatch_s``, ``settle_s``). The harness snapshots the summary before
and after the window; a reader takes the difference, op by op. A
program without these fields gives nothing to read and the metric is
left out of the line."""


def window_ops(reading) -> list:
    """``(after, before)`` records of every op of the window's
    summary (``before`` empty for an op the window began)."""
    before = reading.window.telemetry_before.get("ops", {})
    return [(rec, before.get(op, {})) for op, rec in
            reading.window.telemetry_after.get("ops", {}).items()]


def delta(after: dict, before: dict, block: str, field: str):
    """``field`` of ``block`` accumulated inside the window, or None
    where the program recorded no such field."""
    if field not in after.get(block, {}):
        return None
    return after[block][field] - before.get(block, {}).get(field, 0)
