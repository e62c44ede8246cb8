"""Reduction of a profiler trace to numbers.

``load_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes
into a plain form — planes, their lines, and events as ``[name,
start_ns, duration_ns]`` — and ``reduce_trace`` works on that form
alone, so a small recorded trace (``tests/``) checks it without a chip.

What it yields, inside the traced window (first start to last end of
the harness's own host spans, ``bench:*``):

    busy_s        union of the intervals in which an operation ran on a
                  device, averaged over the devices that ran any
    window_s      length of the traced window
    ops           device seconds by operation label — the instruction's
                  name without its ``.<n>`` and its result shape, as in
                  ``sort s32[131072]`` or ``<kernel> s32[1024,128]`` (the
                  trace prints whole HLO instructions) — where an op that
                  encloses others is charged only what they leave
    gaps          idle seconds by the host span that covers each gap
    kernel_s(n)   summed device time, and ``kernel_calls(n)`` the number,
                  of the events whose label contains ``n``
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench:"
_SUFFIX = re.compile(r"\.\d+$")
_HLO = re.compile(r"^%?([\w\-]+?)(?:\.\d+)? = \(?([a-z]\w*\[[\d,]*\])?")


def op_label(name: str) -> str:
    """``%fusion.2 = s32[131072]{0:T(1024)} fusion(...)`` ->
    ``fusion s32[131072]``; a plain name loses only its ``.<n>``."""
    m = _HLO.match(name)
    if not m:
        return _SUFFIX.sub("", name)
    base, shape = m.groups()
    return f"{base} {shape}" if shape else base


class NoDeviceOps(ValueError):
    pass


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, keep_host=lambda name: True) -> dict:
    """The plain form of one ``.xplane.pb``: every device plane's op
    line, and the host events ``keep_host`` admits."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            events = [[op_label(e.name) if device else e.name,
                       int(e.start_ns), int(e.duration_ns)]
                      for e in line.events
                      if device or keep_host(e.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def host_spans_only(name: str) -> bool:
    return name.startswith(HOST_SPAN_PREFIX)


def union(intervals) -> list:
    """Sorted, disjoint ``[start, end)`` covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _self_times(events) -> dict:
    """Seconds by name, where an event that encloses others (a loop
    around its body) is charged only what its children leave."""
    out: dict = {}
    stack = []  # (end, name, start, covered-by-children)
    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, start, covered = stack.pop()
            own = max(0, (end - start) - covered)
            out[name] = out.get(name, 0) + own
            if stack:
                stack[-1][3] += end - start
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        close(s)
        stack.append([s + d, name, s, 0])
    close(float("inf"))
    return {k: v / 1e9 for k, v in out.items()}


@dataclasses.dataclass
class TraceReduction:
    window_s: float
    busy_s: float
    devices: int
    ops: dict       # name -> device seconds (self time), all devices
    gaps: dict      # host span name -> idle seconds (first device)
    events: list    # (label, start_ns, dur_ns) on device op lines

    def kernel_s(self, needle: str) -> float:
        """Summed device time of the events whose name contains
        ``needle``, over the devices used; 0.0 when there is none (the
        caller decides whether that is an error)."""
        return sum(d for n, _, d in self.events if needle in n) / 1e9

    def kernel_calls(self, needle: str) -> int:
        return sum(1 for n, _, _ in self.events if needle in n)

    def top(self, table: dict, n: int = 10) -> list:
        return [[k, v] for k, v in sorted(
            table.items(), key=lambda kv: -kv[1])[:n]]


def reduce_trace(plain: dict) -> TraceReduction:
    spans = [tuple(e) for p in plain["planes"]
             if not DEVICE_PLANE.match(p["name"])
             for ln in p["lines"] for e in ln["events"]
             if e[0].startswith(HOST_SPAN_PREFIX)]
    if not spans:
        raise ValueError("the trace holds none of the harness's host "
                         f"spans ({HOST_SPAN_PREFIX}*)")
    lo = min(s for _, s, _ in spans)
    hi = max(s + d for _, s, d in spans)
    busy, ops, events, first_busy = [], {}, [], None
    for p in plain["planes"]:
        if not DEVICE_PLANE.match(p["name"]):
            continue
        evs = [e for ln in p["lines"] if ln["name"] == OPS_LINE
               for e in ln["events"]
               if e[1] + e[2] > lo and e[1] < hi]
        if not evs:
            continue
        u = union(_clip([(s, s + d) for _, s, d in evs], lo, hi))
        busy.append(sum(e - s for s, e in u))
        if first_busy is None:
            first_busy = u
        for k, v in _self_times(evs).items():
            ops[k] = ops.get(k, 0.0) + v
        events.extend(tuple(e) for e in evs)
    if not busy:
        raise NoDeviceOps("no operation ran on a device inside the "
                          "traced window")
    return TraceReduction(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy) / len(busy) / 1e9,
        devices=len(busy), ops=ops,
        gaps=_gaps_by_span(first_busy, spans, lo, hi),
        events=events,
    )


def _gaps_by_span(busy, spans, lo, hi) -> dict:
    """Idle seconds of one device by what the host was doing: each gap's
    overlap with each host span (the harness's spans do not nest), and
    ``between jobs`` for what no span covers."""
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    out: dict = {}
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        left = b - a
        for name, s, d in spans:
            over = min(b, s + d) - max(a, s)
            if over > 0:
                out[name] = out.get(name, 0.0) + over / 1e9
                left -= over
        if left > 0:
            out["between jobs"] = out.get("between jobs", 0.0) + left / 1e9
    return out
