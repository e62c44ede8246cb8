"""The program's span table, read over the window.

``Session.telemetry_summary()["spans"]`` (the program's
``utils/trace.span``) holds, per span name, ``count``, ``total_s``,
``self_s`` and for some spans ``bytes``, summed since the session
began. The harness snapshots the summary before and after the window
(``WindowResult.telemetry_before`` / ``telemetry_after``); a reader
takes the difference. A program without the table, or without that
span, gives None — never 0 — and the metric is left out of the line."""


def window_delta(reading, span: str, field: str):
    """``field`` of ``span`` accumulated inside the window, or None
    where the program recorded no such span (or not that field)."""
    after = reading.window.telemetry_after.get("spans", {}).get(span)
    if after is None or field not in after:
        return None
    before = reading.window.telemetry_before.get("spans", {}).get(span, {})
    return after[field] - before.get(field, 0)


def per_job(reading, spans, field: str, scale: float):
    """Sum of ``field`` over ``spans`` inside the window, times
    ``scale``, a window job; None where none of them was recorded."""
    found = [d for d in (window_delta(reading, s, field) for s in spans)
             if d is not None]
    if not found or not reading.window_jobs():
        return None
    return scale * sum(found) / reading.window_jobs()
