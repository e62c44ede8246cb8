"""Layer: group program. The bytes the job needs whatever implements it
(the configuration's ``work()["least_bytes"]``, from shapes) at the
HBM peak of the cell's chips together, as a share of the device busy
time a job really took (averaged over the chips). Bound by bytes: these
pipelines do no matrix arithmetic."""


def read(r):
    if r.trace is None or not r.traced_jobs():
        return None
    return r.share_of_peak_pct(
        r.work["least_bytes"], r.chips * r.peaks["hbm_bytes_per_s"],
        r.trace.busy_s / r.traced_jobs(), "hbm_roofline_share")
