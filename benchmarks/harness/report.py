"""From a finished window to the contract's last line.

A metric's reader (``metrics/<name>.py``) gets one ``Reading`` and
returns a number, or None where it finds nothing to read — the metric
is then left out of the line, never written as 0."""

from __future__ import annotations

import dataclasses

from . import peaks, stats, tracered


@dataclasses.dataclass
class Reading:
    """What a metric's reader may read."""

    window: object        # window.WindowResult
    trace: object         # tracered.TraceReduction, or None untraced
    peaks: dict           # this device kind's row of peaks.json: ONE chip's
    chips: int            # chips the cell runs on
    work: dict            # the configuration's work per job, from shapes
    stats: object = stats
    share_of_peak_pct: object = staticmethod(peaks.share_of_peak_pct)

    # ---- helpers shared by readers
    def traced_jobs(self) -> int:
        return self.window.traced[1] if self.window.traced else 0

    def window_jobs(self) -> int:
        return len(self.window.jobs)

    def staging_seconds_in_window(self):
        """Seconds of the executor's staging breakdown (read, decode,
        assemble, upload) recorded between the start and the end of the
        window, summed over ops; None where the program recorded none."""
        def total(summary):
            found, secs = False, 0.0
            for op in summary.get("ops", {}).values():
                phases = op.get("waves", {}).get("staging_breakdown")
                if phases:
                    found = True
                    secs += sum(phases.values())
            return secs if found else None

        after = total(self.window.telemetry_after)
        if after is None:
            return None
        return after - (total(self.window.telemetry_before) or 0.0)

    def span_seconds_in_window(self, name: str):
        """Summed host-clock seconds of the harness span ``name`` over
        the window's jobs; None where no job has that span."""
        found = [e - s for j in self.window.jobs
                 for n, s, e in j.spans if n == name]
        return sum(found) if found else None


def result_line(cell, res, trace: int, trace_dir, devices, log) -> dict:
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res.memory_peak_bytes}
    reduction = breakdown = None
    if trace:
        if res.traced is None:
            raise RuntimeError(
                "the window closed before the traced jobs began: "
                f"{len(res.jobs)} job(s) in it")
        xplane = tracered.newest_xplane(trace_dir)
        plain = tracered.load_xplane(xplane, tracered.host_spans_only)
        try:
            reduction = tracered.reduce_trace(plain)
        except tracered.NoDeviceOps:
            if dev0.platform == "tpu":
                raise
            # A CPU rehearsal's trace has no device plane: the metrics
            # that read the trace find nothing and are left out.
        else:
            device["busy_s"] = reduction.busy_s
            device["window_s"] = reduction.window_s
            breakdown = {"device_ops": reduction.top(reduction.ops),
                         "idle_gaps": reduction.top(reduction.gaps)}
    try:
        row = peaks.peaks_for(dev0.device_kind)
    except KeyError:
        if dev0.platform == "tpu":
            raise
        row = {}  # a CPU rehearsal has no peaks and reads no share
    reading = Reading(window=res, trace=reduction, peaks=row,
                      chips=len(devices), work=res.work)
    metrics = {}
    for entry, reader in (cell.per_layer if trace else cell.end_to_end):
        value = reader.read(reading)
        if value is not None:
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
    log({"phase": "window", "jobs": len(res.jobs),
         "window_s": res.window_s,
         "job_s": [round(j.seconds, 4) for j in res.jobs],
         "job_cpu_s": [round(j.cpu_s, 3) for j in res.jobs],
         "span_s": {name: [round(e - s, 4) for j in res.jobs
                           for n, s, e in j.spans if n == name]
                    for name in dict.fromkeys(
                        n for j in res.jobs for n, _, _ in j.spans)},
         "compiles_in_window": res.window_compiles,
         "errors": [j.error for j in res.setup_jobs + res.jobs
                    if j.error][:5],
         "notes": res.notes})
    line = {
        "correct": bool(res.correct),
        "attempted": len(res.jobs),
        "failed": sum(1 for j in res.jobs if j.error),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = res.checks
    return line
