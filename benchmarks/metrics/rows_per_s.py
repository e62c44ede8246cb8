"""End to end: input rows of all jobs COMPLETED in the window over the
whole window (start of the first job to return of the last). A job that
failed completed no rows; its time counts like any other's."""


def read(r):
    done = sum(1 for j in r.window.jobs if not j.error)
    return r.stats.window_rate(done * r.work["input_rows"],
                               r.window.window_s)
