"""Layer: kernels. The Mosaic hash partitioner's bytes per call (each
row's key read, a 4-byte partition id written: the configuration's
``work()["kernel_bytes_per_call"]``, from shapes) times the calls the
trace shows, at the HBM peak, as a share of the summed device time of
the trace's events of that name. A cell that lists this metric and
traces no such event is an error, never 0."""

KERNEL = "bigslice_hash_partition"


def read(r):
    if r.trace is None:
        return None
    per_call = r.work.get("kernel_bytes_per_call", {}).get(KERNEL)
    if per_call is None:
        return None
    calls, secs = r.trace.kernel_calls(KERNEL), r.trace.kernel_s(KERNEL)
    if not calls or secs <= 0:
        raise LookupError(f"no device event named {KERNEL} in the trace")
    return r.share_of_peak_pct(
        calls * per_call, r.peaks["hbm_bytes_per_s"], secs,
        "hash_partition_roofline")
