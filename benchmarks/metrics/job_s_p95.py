"""End to end: 95th percentile (nearest rank) of job wall time — fresh
slices built to result on the host — over ALL jobs of the window. Over
fewer than 20 jobs it is the slowest job."""


def read(r):
    return r.stats.percentile_nearest_rank(
        [j.seconds for j in r.window.jobs], 95)
