"""Layer: group program. The program's ``sync.*`` spans, one around
each blocking device-to-host read on a group's path that is neither a
``settle`` nor a ``readback`` (``sync.keyrange``, ``sync.subid_count``,
``sync.shuffle_counts``; whatever name the table holds under the
prefix): seconds the group's thread waited for the device, or for the
copy, outside the wave loop's own waits. Over the window's jobs."""

from benchmarks.harness import spans

PREFIX = "sync."


def read(r):
    names = [name for name in r.window.telemetry_after.get("spans", {})
             if name.startswith(PREFIX)]
    return spans.per_job(r, names, "total_s", 1e3)
