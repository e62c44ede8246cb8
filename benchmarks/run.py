#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

finds the cell in ``BENCHMARK.json``, and its configuration, traffic and
per-layer metric readers by name under ``paths`` (harness/discover.py),
runs one window on the TPU this machine holds and prints the contract's
line last on standard output. Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero before any work and prints no
result. ``--cpu-rehearsal`` runs the same files at the configuration's
tiny ``rehearsal`` size on the CPU, to prove control flow; it never
prints the contract's line and none of its numbers is a device number.

One process touches JAX. The only children are the program's host parse
pool (spawned workers, which re-import this file: hence the guard at the
bottom) and its ``cc`` build of the native parse kernels.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def emit(obj: dict) -> None:
    print(json.dumps(obj, default=str), flush=True)


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU; never prints the "
                         "contract's line")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmarks.harness import process, report, window

    try:
        cell, devs, cache_dir = process.start(root, args.workload,
                                              args.cpu_rehearsal)
    except process.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    import jax

    platform = devs[0].platform
    emit({"phase": "start", "workload": cell.name,
          "config": cell.config_name, "traffic": cell.traffic_name,
          "seed": args.seed, "seconds": args.seconds,
          "trace": args.trace, "platform": platform,
          "kind": devs[0].device_kind, "count": len(devs),
          "compile_cache_dir": cache_dir, "jax": jax.__version__,
          "rehearsal": bool(args.cpu_rehearsal)})

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") \
        if args.trace else None
    try:
        res = window.run_window(cell, args.seed, args.seconds, trace_dir,
                                _T_PROCESS, devs, platform, emit)
        line = report.result_line(cell, res, args.trace, trace_dir,
                                  devs, emit)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    if args.cpu_rehearsal:
        # Not the contract's line: a rehearsal's numbers are the CPU's.
        emit({"rehearsal": line})
    else:
        emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
