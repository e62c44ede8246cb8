"""Layer: compile. Wall time of the first job of the process: it
compiles every program, or loads it from the persistent cache."""


def read(r):
    return r.window.first_job_s
