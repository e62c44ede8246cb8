"""Not a metric: what the readers of the wave loop's books share.

Since the program splits ``dispatch`` at the enqueue its span table
holds ``enqueue`` (the jit call and the start of the signals' copy, a
child of ``dispatch``), names every blocking device-to-host read of a
group ``sync.<what>``, opens ``mutex_wait`` only where the wave mutex
was held by another thread, and records no zero-second ``decode``: so
``dispatch``'s and ``group``'s SELF time and ``mutex_wait``'s seconds
mean something else in a program without ``enqueue`` — the whole
dispatch, the loop with its unnamed reads in it, every acquire. There
these readers find nothing, and the metric is left out of the line.

The device plane (``telemetry_summary()["device"]``) holds sums since
the session began: ``totals.lookup_s`` (what the compile seam took of
every cache-hit program call before it reached the executable) and
``hbm.sample_s`` (what the per-wave ``memory_stats()`` samples took on
the thread that made them)."""

from benchmarks.harness import spans


def closed(reading) -> bool:
    """Does the program's span table split ``dispatch`` at the
    enqueue?"""
    return spans.window_delta(reading, "enqueue", "total_s") is not None


def span_ms_per_job(reading, span: str, field: str):
    """``field`` of ``span`` inside the window, ms a job; None in a
    program whose books are not closed or that has no such span."""
    if not closed(reading):
        return None
    return spans.per_job(reading, (span,), field, 1e3)


def device_ms_per_job(reading, block: str, field: str):
    """``field`` of the device plane's ``block`` inside the window, ms
    a job; None where the program recorded no such field."""
    after = reading.window.telemetry_after.get(
        "device", {}).get(block, {})
    if field not in after or not reading.window_jobs():
        return None
    before = reading.window.telemetry_before.get(
        "device", {}).get(block, {})
    return (1e3 * (after[field] - before.get(field, 0))
            / reading.window_jobs())
