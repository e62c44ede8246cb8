"""Not a metric: what the readers of the ``exchange`` layer share.

``Session.telemetry_summary()["ops"][op]["exchange"]`` holds, for a
shuffle op whose collective moved something, ``waves``, ``ici_bytes``,
``ici_messages``, ``slack``, ``retries`` and ``recv_rows`` (the rows a
device of the op's merged map-side output), summed since the session
began. The harness snapshots the summary before and after the window;
the readers take the difference, op by op. A program without the block
gives nothing to read and the metric is left out of the line."""


def window_ops(reading) -> list:
    """``(after, before)`` exchange blocks of every op that exchanged a
    wave inside the window (``before`` empty for an op the window
    began)."""
    before = reading.window.telemetry_before.get("ops", {})
    found = []
    for op, rec in reading.window.telemetry_after.get("ops", {}).items():
        after = rec.get("exchange")
        if after is None:
            continue
        was = before.get(op, {}).get("exchange", {})
        if after["waves"] > was.get("waves", 0):
            found.append((after, was))
    return found


def per_job(reading, field: str, scale: float = 1.0):
    """``field`` accumulated inside the window over its shuffle ops,
    times ``scale``, a window job; None where nothing exchanged."""
    ops = window_ops(reading)
    if not ops or not reading.window_jobs():
        return None
    total = sum(after[field] - was.get(field, 0) for after, was in ops)
    return scale * total / reading.window_jobs()
