"""A profiler trace of the process, read by the program's own spans.

From the ``.xplane.pb`` that ``jax.profiler`` writes (the benchmark's
``--trace 1`` run, ``/debug/profile?seconds=N``) this prints two tables:

- a device plane: **busy seconds by program name** — the events of the
  plane's ``XLA Modules`` line, ``jit_bs_group_shuffle(<fingerprint>)``
  as ``jit_bs_group_shuffle``, with their calls — beside the plane's
  busy time (the union of its ``XLA Ops`` events) and the window;
- for the first device: **idle seconds by the innermost span** open
  meanwhile on the thread that runs a group (``bigslice:group`` and
  what nests in it: ``bigslice:enqueue``, ``bigslice:sync.*``,
  ``bigslice:stage_wait`` ...). Several groups open at once (a job
  whose map sides run side by side) share the instant equally. Where
  no group is open the instant goes to the innermost annotation on a
  thread that holds top-level ones (``bigslice:session.run``, and with
  ``--also bench:`` the benchmark's own); where nothing is open, to
  ``(no span)``. A thread that only stages (a prefetch worker, named
  ``meshwave-prefetch-<i>``: its ``bigslice:stage`` spans stand beside
  a group, in none) is left out.

The window is first start to last end of the host annotations kept
(``bigslice:*`` and the ``--also`` prefixes). ``--jobs N`` prints
milliseconds, and calls, a job instead of seconds and calls in all.

Everything works on a plain form of the trace — ``{"planes": [{"name",
"lines": [{"name", "events": [[name, start_ns, duration_ns], ...]}]}]}``
— which ``load`` makes and ``--dump`` writes, so a small fixture checks
the reduction without a chip (tests/test_xplanespans.py).

Usage: python -m bigslice_tpu.tools.xplanespans <trace dir | .xplane.pb>
           [--also PREFIX]... [--jobs N] [--json] [--dump PLAIN.json]
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from bigslice_tpu.utils.trace import ANNOTATION_PREFIX

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
GROUP = ANNOTATION_PREFIX + "group"
STAGE = ANNOTATION_PREFIX + "stage"
NO_SPAN = "(no span)"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def newest_xplane(path: str) -> str:
    """``path`` itself, or the newest ``.xplane.pb`` under it."""
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load(path: str, prefixes: Sequence[str] = (ANNOTATION_PREFIX,)) -> dict:
    """The plain form of one trace: every device plane's module and op
    lines, and of the host's threads the annotations that begin with
    one of ``prefixes``."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(newest_xplane(path)).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and line.name not in (MODULES_LINE, OPS_LINE):
                continue
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(tuple(prefixes))]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def program_name(module: str) -> str:
    """``jit_bs_group_shuffle(1234567890)`` -> ``jit_bs_group_shuffle``."""
    return _FINGERPRINT.sub("", module)


def _union(intervals) -> List[List[int]]:
    """Sorted, disjoint ``[start, end)`` covering the same points."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _innermost(events) -> List[Tuple[int, int, str, str, bool]]:
    """One thread's nested events as disjoint ``(start, end, innermost
    name, outermost name, inside a group)`` segments, in time order."""
    out = []
    stack: List[list] = []      # [end, name]; a cursor walks the time

    def emit(a, b):
        if b > a and stack:
            out.append((a, b, stack[-1][1], stack[0][1],
                        any(n == GROUP for _, n in stack)))

    cursor = 0
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            emit(cursor, stack[-1][0])
            cursor = max(cursor, stack.pop()[0])
        emit(cursor, s)
        cursor = max(cursor, s)
        stack.append([s + d, name])
    while stack:
        emit(cursor, stack[-1][0])
        cursor = max(cursor, stack.pop()[0])
    return out


def _open_at(threads, t) -> list:
    """Of ``(segments, their starts)`` a thread, the segments that
    cover instant ``t``."""
    found = []
    for segments, starts in threads:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and segments[i][1] > t:
            found.append(segments[i])
    return found


def reduce(plain: dict) -> dict:
    """``{"window_s", "devices": {plane: {"busy_s", "programs": {name:
    {"calls", "busy_s"}}}}, "idle": {"device", "idle_s", "by_span":
    {name: seconds}}}`` of a plain trace (``idle`` None where no device
    ran anything)."""
    threads = [_innermost(ln["events"])
               for p in plain["planes"] if not DEVICE_PLANE.match(p["name"])
               for ln in p["lines"]]
    threads = [t for t in threads if t]
    if not threads:
        raise ValueError("the trace holds no host annotation of the "
                         "prefixes asked for")
    lo = min(t[0][0] for t in threads)
    hi = max(seg[1] for t in threads for seg in t)
    # Who may be charged: a thread inside its groups; outside any
    # group every thread but one that stages beside a group (its
    # outermost annotation is then the ``stage`` itself).
    grouped = [[seg for seg in t if seg[4]] for t in threads]
    top = [[seg for seg in t if not seg[4] and seg[3] != STAGE]
           for t in threads]
    grouped, top = [t for t in grouped if t], [t for t in top if t]

    devices: Dict[str, dict] = {}
    first_busy: Optional[list] = None
    first_name = None
    planes = sorted((p for p in plain["planes"]
                     if DEVICE_PLANE.match(p["name"])),
                    key=lambda p: int(DEVICE_PLANE.match(p["name"])[1]))
    for p in planes:
        programs: Dict[str, dict] = {}
        busy = []
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                if s + d <= lo or s >= hi:
                    continue
                if ln["name"] == OPS_LINE:
                    busy.append((max(s, lo), min(s + d, hi)))
                elif ln["name"] == MODULES_LINE:
                    row = programs.setdefault(
                        program_name(name), {"calls": 0, "busy_s": 0.0})
                    row["calls"] += 1
                    row["busy_s"] += d / 1e9
        busy = _union(busy)
        if not busy and not programs:
            continue
        devices[p["name"]] = {
            "busy_s": sum(e - s for s, e in busy) / 1e9,
            "programs": dict(sorted(programs.items(),
                                    key=lambda kv: -kv[1]["busy_s"])),
        }
        if first_busy is None:
            first_busy, first_name = busy, p["name"]

    idle = None
    if first_busy is not None:
        idle = {"device": first_name,
                "by_span": _idle_by_span(first_busy, grouped, top, lo, hi)}
        idle["idle_s"] = sum(idle["by_span"].values())
    return {"window_s": (hi - lo) / 1e9, "devices": devices, "idle": idle}


def _idle_by_span(busy, grouped, top, lo, hi) -> Dict[str, float]:
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    cuts = sorted({t for a, b in gaps for t in (a, b)}
                  | {t for th in grouped + top for seg in th
                     for t in seg[:2] if lo < t < hi})
    grouped, top = ([(th, [seg[0] for seg in th]) for th in threads]
                    for threads in (grouped, top))
    gap_starts = [a for a, _ in gaps]
    out: Dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        i = bisect.bisect_right(gap_starts, a) - 1
        if i < 0 or gaps[i][1] <= a:
            continue                           # the device is busy
        open_ = _open_at(grouped, a) or _open_at(top, a)
        share = (b - a) / 1e9 / max(1, len(open_))
        for name in [seg[2] for seg in open_] or [NO_SPAN]:
            out[name] = out.get(name, 0.0) + share
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def render(red: dict, jobs: Optional[int] = None) -> str:
    scale, unit = (1e3 / jobs, "ms a job") if jobs else (1.0, "s")
    fmt = lambda v: f"{v * scale:12.6f}"       # noqa: E731
    lines = [f"window {fmt(red['window_s'])} {unit}"]
    for name, dev in red["devices"].items():
        lines.append(f"{name}: busy {fmt(dev['busy_s'])} {unit}")
        total = 0.0
        for prog, row in dev["programs"].items():
            total += row["busy_s"]
            lines.append(f"  {prog:44s} {row['calls'] / (jobs or 1):6g} "
                         f"calls {fmt(row['busy_s'])}")
        lines.append(f"  {'(sum of programs)':44s} {'':12s} {fmt(total)}")
    idle = red["idle"]
    if idle is not None:
        lines.append(f"{idle['device']}: idle {fmt(idle['idle_s'])} "
                     f"{unit}, by the innermost span")
        for name, secs in idle["by_span"].items():
            lines.append(f"  {name:44s} {fmt(secs)}")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m bigslice_tpu.tools.xplanespans",
        description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a trace directory or an .xplane.pb "
                                  "(or a plain form's .json)")
    ap.add_argument("--also", action="append", default=[],
                    metavar="PREFIX",
                    help="keep host annotations of this prefix too "
                         "(the benchmark's: bench:)")
    ap.add_argument("--jobs", type=int, default=None,
                    help="print milliseconds a job of this many")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--dump", metavar="PLAIN.json",
                    help="also write the plain form here")
    args = ap.parse_args(argv)
    if args.trace.endswith(".json"):
        with open(args.trace) as fp:
            plain = json.load(fp)
    else:
        plain = load(args.trace, [ANNOTATION_PREFIX] + args.also)
    if args.dump:
        with open(args.dump, "w") as fp:
            json.dump(plain, fp)
    red = reduce(plain)
    print(json.dumps(red) if args.json else render(red, args.jobs))
    return 0


if __name__ == "__main__":
    sys.exit(main())
