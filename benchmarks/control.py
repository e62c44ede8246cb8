#!/usr/bin/env python3
"""The readings the limits of ``correct`` stand on, in one process.

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed: the cell's controls — the plain reference with one
stated guarantee broken each — put in the program's place and compared
with the reference (the upper reading: each has to fail), then one
short window of the cell through the program (the lower reading: every
number compared, which has to sit at its limit). Prints one JSON line a
seed and a summary line; exits 1 unless every program run was correct
and every control came out not correct. The benchmark's own runs never
run this. ``--cpu-rehearsal`` as in ``run.py``.

A configuration with a ``compare`` block (harness/compare.py) has to
bring a control whose name starts with ``lower_precision``: the
reference computed in a lower precision than the configuration states.
Without one it is refused (exit 2) before any window. For each table
compared within a tolerance the summary gives the readings the
tolerance stands on: the program's largest margin over the seeds (under
1) and each control's smallest.
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


LOWER_PRECISION = "lower_precision"


def readings(cell, seed: int) -> dict:
    """``{control: (wrong rows, {table: margin})}`` with each control's
    answers in the program's place, against the plain reference; the
    margins are those of the tables compared within a tolerance."""
    from benchmarks.harness import compare

    tols = cell.tolerances
    data = cell.pipeline.make_data(cell.cfg, seed)
    try:
        want = cell.pipeline.reference(cell.cfg, data)
        return {
            name: (compare.compare_answers(answers, want, tols)[0],
                   compare.margins(answers, want, tols))
            for name, answers in cell.pipeline.controls(
                cell.cfg, data).items()
        }
    finally:
        cell.pipeline.close(data)


def control_readings(cell, seed: int) -> dict:
    """``{control: wrong rows}`` (``readings`` without the margins)."""
    return {name: w for name, (w, _) in readings(cell, seed).items()}


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
    lower = {}    # table -> the program's largest margin
    upper = {}    # table -> control -> its smallest margin
    for seed in (int(s) for s in args.seeds.split(",")):
        controls = readings(cell, seed)
        if cell.tolerances and not any(
                n.startswith(LOWER_PRECISION) for n in controls):
            print(f"control: refused: {cell.config_name} compares "
                  f"{sorted(cell.tolerances)} within a tolerance and has "
                  f"no control named {LOWER_PRECISION}*: {sorted(controls)}",
                  file=sys.stderr)
            return 2
        res = window.run_window(cell, seed, args.seconds, None,
                                time.perf_counter(), devs,
                                devs[0].platform, _log_failures)
        ok = ok and res.correct and all(
            w > res.checks["wrong_rows"]["limit"]
            for w, _ in controls.values())
        line = {
            "workload": cell.name, "seed": seed, "jobs": len(res.jobs),
            "job_s": [round(j.seconds, 3) for j in res.jobs],
            "program": {k: c["value"] for k, c in res.checks.items()},
            "rows_compared": res.notes["rows_compared"],
            "program_correct": res.correct,
            "control_wrong_rows": {n: w for n, (w, _) in controls.items()},
            "platform": devs[0].platform,
        }
        if cell.tolerances:
            line["program_margin"] = res.notes["margin_by_table"]
            line["control_margin"] = {n: m for n, (_, m) in controls.items()}
            for table, m in res.notes["margin_by_table"].items():
                lower[table] = max(m, lower.get(table, 0.0))
            for n, (_, by_table) in controls.items():
                for table, m in by_table.items():
                    got = upper.setdefault(table, {})
                    got[n] = min(m, got.get(n, m))
        print(json.dumps(line), flush=True)
    summary = {"workload": cell.name, "all_as_expected": ok}
    if cell.tolerances:
        summary["tolerance"] = {
            table: {"program_max_margin": lower.get(table),
                    "control_min_margin": upper.get(table, {})}
            for table in cell.tolerances}
    print(json.dumps(summary), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
