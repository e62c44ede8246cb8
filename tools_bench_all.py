#!/usr/bin/env python
"""Run the full bench matrix on the live chip in ONE process.

A chip belongs to one process, and every process start pays device
init and a cold jit cache. This harness starts once, then walks every
BASELINE config at a pyramid of sizes, appending one JSON line per
measurement to bench_results/all.jsonl as it goes — a failure mid-run
keeps everything already measured. It needs a TPU and exits non-zero
without one.

It also owns the PR-over-PR bench series: ``trajectory`` consolidates
the scattered per-PR ``BENCH_pr*.json`` snapshots into
``BENCH_trajectory.json`` (one entry per PR: scenario, rows/sec,
speedup, overlap efficiency, staging breakdown — readable as a
series), and ``compare`` checks a fresh ``bench.py reduce-wave`` run
against the trajectory, emitting a GitHub-Actions warning above 15%
regression. The comparison uses the pipelined-vs-serial SPEEDUP
(``vs_baseline``), not absolute rows/sec: CI runners and authors'
hosts differ wildly in absolute throughput, but both run serial and
pipelined interleaved on the same machine, so the ratio travels —
floored on the trajectory's most conservative (minimum) entry,
because core count still dominates the ratio's magnitude across host
classes.

Usage: python tools_bench_all.py [fast|full]
       python tools_bench_all.py trajectory
       python tools_bench_all.py compare BENCH_LINES.json
"""

import json
import os
import sys
import time
import traceback

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "bench_results", "all.jsonl")


def record(entry: dict) -> None:
    entry["ts"] = time.time()
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as fp:
        fp.write(json.dumps(entry) + "\n")
    print("RESULT", json.dumps(entry), flush=True)


def already_measured() -> set:
    """Bench names recorded with a value SINCE the last completed sweep:
    a retried sweep after a mid-run failure skips them instead of
    re-paying compiles, while a fresh sweep after a DONE sentinel
    re-measures everything."""
    done = set()
    try:
        with open(OUT) as fp:
            for line in fp:
                try:
                    e = json.loads(line)
                except ValueError:
                    continue
                if e.get("bench") == "DONE":
                    done.clear()
                elif "value" in e:
                    done.add(e["bench"])
    except OSError:
        pass
    return done


_DONE = None


def run(name: str, fn) -> None:
    global _DONE
    if _DONE is None:
        _DONE = already_measured()
    if name in _DONE:
        print(f"skip {name} (already measured)", flush=True)
        return
    t0 = time.time()
    try:
        value, baseline = fn()
        record({"bench": name, "value": round(value, 3),
                "vs_baseline": round(value / baseline, 3) if baseline
                else None, "wall_s": round(time.time() - t0, 1)})
    except Exception as exc:  # keep walking the matrix
        record({"bench": name, "error": f"{type(exc).__name__}: {exc}",
                "wall_s": round(time.time() - t0, 1)})
        traceback.print_exc()


# ------------------------------------------------- bench trajectory

REPO = os.path.dirname(os.path.abspath(__file__))
TRAJECTORY = os.path.join(REPO, "BENCH_trajectory.json")
TRACKED_METRIC = "reduce_wave_e2e_rows_per_sec"
REGRESSION_THRESHOLD = 0.15


def build_trajectory() -> list:
    """One entry per PR snapshot, oldest first, from BENCH_pr*.json."""
    import glob

    entries = []
    for path in sorted(glob.glob(os.path.join(REPO, "BENCH_pr*.json"))):
        try:
            with open(path) as fp:
                d = json.load(fp)
        except (OSError, ValueError):
            continue
        after = d.get("after", {})
        entry = {
            "pr": d.get("pr"),
            "title": d.get("title"),
            "metric": d.get("metric"),
            "scenario": d.get("scenario"),
            "rows_per_sec": after.get("rows_per_sec"),
            "speedup": d.get("speedup"),
            "overlap_efficiency": after.get("overlap_efficiency"),
            "environment": d.get("environment"),
            "date": d.get("date"),
            "source": os.path.basename(path),
        }
        if after.get("staging_breakdown"):
            entry["staging_breakdown"] = after["staging_breakdown"]
        if after.get("device"):
            entry["device"] = after["device"]
        entries.append(entry)
    entries.sort(key=lambda e: (e["pr"] is None, e["pr"]))
    return entries


def write_trajectory(out_path: str = TRAJECTORY) -> list:
    entries = build_trajectory()
    with open(out_path, "w") as fp:
        json.dump({
            "tracked_metric": TRACKED_METRIC,
            "note": ("one entry per PR, oldest first; 'speedup' is the "
                     "host-portable tracked number (pipelined vs serial "
                     "measured interleaved on one machine)"),
            "series": entries,
        }, fp, indent=1)
        fp.write("\n")
    print(f"trajectory: {len(entries)} entries -> {out_path}")
    return entries


def compare_tracked(bench_lines_path: str,
                    trajectory_path: str = TRAJECTORY) -> int:
    """Compare a fresh bench.py reduce-wave run (JSON lines) against
    the last tracked trajectory entry; emit a GitHub-Actions
    ``::warning::`` above the regression threshold. Always exits 0 —
    cross-host numbers gate nothing, they warn."""
    fresh = None
    try:
        with open(bench_lines_path) as fp:
            for line in fp:
                try:
                    e = json.loads(line)
                except ValueError:
                    continue
                if e.get("metric") == TRACKED_METRIC:
                    fresh = e
    except OSError as exc:
        print(f"compare: cannot read {bench_lines_path}: {exc}")
        return 0
    if fresh is None:
        print(f"compare: no {TRACKED_METRIC} line in "
              f"{bench_lines_path}; nothing to compare")
        return 0
    try:
        with open(trajectory_path) as fp:
            series = json.load(fp).get("series", [])
    except (OSError, ValueError):
        print(f"compare: no trajectory at {trajectory_path}")
        return 0
    tracked = [e for e in series
               if e.get("metric") == TRACKED_METRIC
               and e.get("speedup")]
    if not tracked:
        print("compare: trajectory has no tracked entries")
        return 0
    last = tracked[-1]
    # Floor on the MOST CONSERVATIVE tracked speedup, not the last
    # entry: the trajectory's own data shows core count dominates the
    # absolute ratio across snapshot hosts (1.47x on 1 vCPU vs 4.61x
    # wide), so a small CI runner compared against a wide-host entry
    # would warn on every run. The minimum (the 1-vCPU-class bound)
    # still catches a real pipeline regression, whose speedup
    # collapses toward 1.0x on any host.
    floor_base = min(float(e["speedup"]) for e in tracked)
    fresh_speedup = fresh.get("vs_baseline") or 0.0
    floor = (1.0 - REGRESSION_THRESHOLD) * floor_base
    print(f"compare: fresh pipelined-vs-serial speedup "
          f"{fresh_speedup:.2f}x vs tracked last "
          f"{last['speedup']:.2f}x (PR {last.get('pr')}), "
          f"conservative floor {floor:.2f}x")
    if fresh_speedup < floor:
        print(f"::warning title=reduce-wave regression::pipelined-vs-"
              f"serial speedup {fresh_speedup:.2f}x fell more than "
              f"{REGRESSION_THRESHOLD:.0%} below the most "
              f"conservative tracked speedup {floor_base:.2f}x "
              f"(last: {last['speedup']:.2f}x, PR {last.get('pr')}, "
              f"{last.get('source')})")
    return 0


def main() -> None:
    arg0 = (sys.argv[1:] or ["fast"])[0]
    if arg0 == "trajectory":
        write_trajectory()
        return
    if arg0 == "compare":
        if len(sys.argv) < 3:
            sys.exit("usage: tools_bench_all.py compare BENCH_LINES.json")
        sys.exit(compare_tracked(sys.argv[2]))
    full = arg0 == "full"
    import numpy as np

    from bigslice_tpu.utils.hermetic import configure_compile_cache

    configure_compile_cache()
    import jax

    t0 = time.time()
    devs = jax.devices()
    record({"bench": "connect", "platform": devs[0].platform,
            "n_devices": len(devs), "wall_s": round(time.time() - t0, 1)})
    if devs[0].platform != "tpu":
        print("not a TPU; aborting", file=sys.stderr)
        sys.exit(1)

    import bench

    # Upload bandwidth probe: sizes the host->device transfer cost
    # that every e2e number includes.
    def upload_probe():
        x = np.random.RandomState(0).randint(
            0, 1 << 30, 1 << 22).astype(np.int32)
        jax.block_until_ready(jax.device_put(x))  # warm
        t = time.time()
        jax.block_until_ready(jax.device_put(x))
        dt = time.time() - t
        return (x.nbytes / dt / 1e6, None)  # MB/s

    run("upload_MBps", upload_probe)

    rng = np.random.RandomState(42)
    sizes = [1 << 20, 1 << 22] + ([1 << 24] if full else [])
    for n in sizes:
        keys = rng.randint(0, 1 << 16, n).astype(np.int32)
        vals = np.ones(n, np.int32)
        run(f"reduce_kernel_{n}",
            lambda: (bench.reduce_kernel_bench(keys, vals),
                     bench.cpu_reduce_baseline(keys, vals)))
        run(f"reduce_e2e_{n}",
            lambda: (bench.reduce_e2e_bench(keys, vals),
                     bench.cpu_reduce_baseline(keys, vals)))
        run(f"reduce_dense_{n}",
            lambda: (bench.reduce_e2e_bench(keys, vals,
                                            dense_keys=1 << 16),
                     bench.cpu_reduce_baseline(keys, vals)))

    for n in [1 << 19, 1 << 21] + ([1 << 23] if full else []):
        run(f"join_e2e_{n}",
            lambda: (bench.join_e2e_bench(n),
                     bench.cpu_join_baseline(*bench.join_inputs(n))))
        run(f"join_dense_{n}",
            lambda: (bench.join_e2e_bench(n, dense=True),
                     bench.cpu_join_baseline(*bench.join_inputs(n))))

    run(f"cogroup_{1 << 20}", lambda: bench.cogroup_bench(1 << 20))
    run(f"wordcount_{1 << 20}", lambda: bench.wordcount_bench(1 << 20))
    run(f"sortshuffle_{1 << 22}",
        lambda: bench.sortshuffle_bench(1 << 22))
    nkm = 1 << 17 if full else 1 << 15
    run(f"kmeans_{nkm}", lambda: bench.kmeans_bench(nkm, d=64, k=64))
    seq, h, d = bench.attention_config(None, False, max(1, len(devs)))
    run(f"attention_{seq}x{h}x{d}",
        lambda: bench.attention_bench(seq, h=h, d=d))
    record({"bench": "DONE", "wall_s": round(time.time() - t0, 1)})


if __name__ == "__main__":
    main()
