"""Layer: group program. Probe rows that reached the join groups over
the host seconds of those groups' ``dispatch`` and ``settle`` spans, in
millions a second: the rate at which the wave loop gets rows through a
lookup join."""

from benchmarks.metrics import join_window


def read(r):
    found = join_window.host_seconds_and_probe_rows(r)
    if found is None or not found[0]:
        return None
    return found[1] / found[0] / 1e6
