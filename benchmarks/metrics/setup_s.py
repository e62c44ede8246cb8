"""End to end: wall time from the start of the process to the start of
the window — imports, data from the seed, session start, the first job
(compilation or cache load), settling and warm jobs."""


def read(r):
    return r.window.setup_s
