#!/usr/bin/env python3
"""The readings the limits of ``correct`` stand on, in one process.

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed: one short window of the cell through the program (the
lower reading: every number compared, which has to sit at its limit),
then the cell's controls — the plain reference with one stated guarantee
broken each — put in the program's place and compared with the
reference the same way (the upper reading: each has to fail). Prints one
JSON line a seed and a summary line; exits 1 unless every program run
was correct and every control came out not correct. The benchmark's own
runs never run this. ``--cpu-rehearsal`` as in ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _log_failures(obj: dict) -> None:
    if "evidence_failed" in obj:
        print(json.dumps(obj), file=sys.stderr, flush=True)


def control_readings(cell, seed: int) -> dict:
    """``{control: wrong rows}`` with each control's answers in the
    program's place, against the plain reference."""
    from benchmarks.harness import compare

    data = cell.pipeline.make_data(cell.cfg, seed)
    try:
        want = cell.pipeline.reference(cell.cfg, data)
        return {
            name: compare.compare_answers(answers, want)[0]
            for name, answers in cell.pipeline.controls(
                cell.cfg, data).items()
        }
    finally:
        cell.pipeline.close(data)


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmarks.harness import process, window

    try:
        cell, devs, _ = process.start(root, args.workload,
                                      args.cpu_rehearsal)
    except process.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        res = window.run_window(cell, seed, args.seconds, None,
                                time.perf_counter(), devs,
                                devs[0].platform, _log_failures)
        controls = control_readings(cell, seed)
        ok = ok and res.correct and all(v > res.checks["wrong_rows"]["limit"]
                                        for v in controls.values())
        print(json.dumps({
            "workload": cell.name, "seed": seed, "jobs": len(res.jobs),
            "job_s": [round(j.seconds, 3) for j in res.jobs],
            "program": {k: c["value"] for k, c in res.checks.items()},
            "rows_compared": res.notes["rows_compared"],
            "program_correct": res.correct,
            "control_wrong_rows": controls,
            "platform": devs[0].platform,
        }), flush=True)
    print(json.dumps({"workload": cell.name, "all_as_expected": ok}),
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
