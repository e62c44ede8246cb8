"""Session tracing: one span primitive and the Chrome-trace recorder.

``span(name, **ids)`` is the program's only span recorder. It marks a
layer boundary of the job path (``session.run`` → ``evaluate`` →
``group`` → ``dispatch`` ...: docs/observability.md, Spans) and does
three things:

- enters ``jax.profiler.TraceAnnotation("bigslice:<name>", **ids)``, so
  the span is in the profiler's ``.xplane.pb`` on the profiler's clock,
  beside the device ops (free while no profiler is live);
- stamps start and end once (``perf_counter_ns``) and keeps a
  per-thread stack, so each span knows its parent and its *self time*:
  its duration minus what its children cover;
- on exit adds ``count`` / ``total_s`` / ``self_s`` (and ``bytes``) to
  the session's table (``telemetry_summary()["spans"]``) and, with a
  ``Tracer`` attached, appends a Chrome ``X`` event with ``id`` /
  ``parent`` / ``inv``.

Spans nest on one thread. Two cases cross threads, and no others:
a span opened with ``parent=`` (a ``group`` on an executor worker,
under the ``evaluate`` span of its invocation, which only waits
meanwhile) takes the *union* of such children out of the parent's self
time; a span opened with ``cause=`` (a ``stage`` on the prefetch
thread) runs beside the span that caused it and is subtracted from
nobody.

The ``Tracer`` mirrors the reference's tracer (exec/tracer.go:29-219 +
internal/trace): task lifecycle events as Chrome trace "X" (complete)
events — executors are "processes", concurrent tasks get virtual thread
lanes — written as one JSON file per session (``TracePath`` option,
exec/session.go:160-164). The offline analyzer is ``python -m
bigslice_tpu.tools.slicetrace`` (cmd/slicetrace analog). Spans and task
events share one clock (``CLOCK``): ``ts`` is microseconds since the
process's clock pair was taken, and the file's ``otherData`` carries the
pair's unix-epoch stamp, so ``ts`` converts to the profiler's clock.
"""

from __future__ import annotations

import itertools
import json
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation


class Clock:
    """One ``(perf_counter_ns, time_ns)`` pair: a ``perf_counter_ns``
    stamp converts to the unix-epoch clock the jax profiler stamps its
    events with."""

    __slots__ = ("perf_ns", "unix_ns")

    def __init__(self):
        self.perf_ns = time.perf_counter_ns()
        self.unix_ns = time.time_ns()

    def to_unix_ns(self, stamp_ns: int) -> int:
        return stamp_ns - self.perf_ns + self.unix_ns

    def to_us(self, stamp_ns: int) -> float:
        """Microseconds since the pair was taken (a trace's ``ts``)."""
        return (stamp_ns - self.perf_ns) / 1e3


CLOCK = Clock()

#: The ``pid`` of span events in the Chrome trace (task events use
#: "tasks"; slicetrace tells the two apart by it).
SPAN_PID = "spans"
#: Prefix of every span's annotation in the profiler's trace.
ANNOTATION_PREFIX = "bigslice:"


class Tracer:
    def __init__(self, clock: Clock = CLOCK):
        self._lock = threading.Lock()
        self._events: List[dict] = []
        self._open: Dict[str, dict] = {}
        self._tids: Dict[str, int] = {}
        self._free_tids: List[int] = []
        # Monotonic allocator for fresh lanes. Deriving a fresh tid from
        # len(_tids)+1 collides with a LIVE lane after mixed begin/end
        # interleavings (a re-begun key overwrites its _tids entry,
        # leaking the old tid without freeing it, so len(_tids) no
        # longer bounds the live tid set).
        self._next_tid = 1
        self.clock = clock

    def _now_us(self) -> float:
        return self.clock.to_us(time.perf_counter_ns())

    def begin(self, key: str, name: str, pid: str = "executor",
              **args) -> None:
        with self._lock:
            if self._free_tids:
                tid = self._free_tids.pop()
            else:
                tid = self._next_tid
                self._next_tid += 1
            self._tids[key] = tid
            self._open[key] = {
                "name": name,
                "pid": pid,
                "tid": tid,
                "ts": self._now_us(),
                "args": args,
            }

    def end(self, key: str, **args) -> None:
        with self._lock:
            ev = self._open.pop(key, None)
            if ev is None:
                return
            tid = self._tids.pop(key, 1)
            self._free_tids.append(tid)
            ev["args"].update(args)
            # B/E coalesced to one X event (exec/tracer.go:185-219).
            self._events.append({
                "name": ev["name"],
                "ph": "X",
                "pid": ev["pid"],
                "tid": ev["tid"],
                "ts": ev["ts"],
                "dur": self._now_us() - ev["ts"],
                "args": ev["args"],
            })

    def instant(self, name: str, pid: str = "session", **args) -> None:
        with self._lock:
            self._events.append({
                "name": name,
                "ph": "i",
                "pid": pid,
                "tid": 0,
                "ts": self._now_us(),
                "s": "g",
                "args": args,
            })

    def span_event(self, name: str, t0_ns: int, t1_ns: int,
                   args: dict) -> None:
        """One closed span (``pid`` "spans", lane = the thread it ran
        on), from the span's own stamps."""
        with self._lock:
            self._events.append({
                "name": name,
                "ph": "X",
                "pid": SPAN_PID,
                "tid": threading.get_ident(),
                "ts": self.clock.to_us(t0_ns),
                "dur": (t1_ns - t0_ns) / 1e3,
                "args": args,
            })

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def save(self, path: str) -> None:
        with open(path, "w") as fp:
            json.dump({"traceEvents": self.events(),
                       "otherData": {
                           "clock_unix_ns": self.clock.unix_ns}}, fp)


class TaskTraceMonitor:
    """An evaluator monitor recording task state transitions as trace
    events (wired by Session when trace_path is set)."""

    def __init__(self, tracer: Tracer):
        self.tracer = tracer

    def __call__(self, task, state) -> None:
        from bigslice_tpu.exec.task import TaskState

        key = str(task.name)
        if state == TaskState.RUNNING:
            self.tracer.begin(key, task.name.op, pid="tasks",
                              shard=task.name.shard,
                              shards=task.name.num_shard,
                              inv=task.name.inv_index)
        elif state in (TaskState.OK, TaskState.ERR, TaskState.LOST):
            self.tracer.end(key, state=state.name)


# -- spans -------------------------------------------------------------

_TLS = threading.local()

#: The clock spans are stamped with, for a boundary that needs one more
#: stamp than its spans give it.
now_ns = time.perf_counter_ns


def current() -> Optional["span"]:
    """The innermost span open on this thread, or None."""
    stack = getattr(_TLS, "stack", None)
    return stack[-1] if stack else None


class SpanRecorder:
    """Where one session's spans go: the telemetry hub's table (None
    with ``BIGSLICE_TELEMETRY=0``: the annotation stays, the table is
    dropped) and the session's ``Tracer`` (None without
    ``trace_path``). Also holds the open spans that adopt children from
    other threads, by invocation."""

    def __init__(self, hub=None, tracer: Optional[Tracer] = None):
        self.hub = hub
        self.tracer = tracer
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        self._adopting: Dict[int, "span"] = {}

    def adopter(self, inv) -> Optional["span"]:
        """The open span of invocation ``inv`` that adopts children
        from other threads (its ``evaluate``), or None."""
        return self._adopting.get(inv)


def _union_ns(intervals, lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi] that ``intervals`` cover together."""
    covered, end = 0, lo
    for a, b in sorted(intervals):
        a, b = max(a, end), min(b, hi)
        if b > a:
            covered += b - a
            end = b
    return covered


class span:
    """``with span(name, **ids):`` — see the module docstring.

    ``rec`` names the session's recorder on a thread's first span;
    nested spans inherit it (and ``inv``) from the enclosing one.
    ``parent`` adopts this span into a span open on ANOTHER thread;
    ``cause`` names the span this one runs beside. ``adopts`` registers
    this span as its invocation's adopter (``SpanRecorder.adopter``).
    A ``bytes`` field (``set(bytes=n)``) is summed in the table."""

    __slots__ = ("name", "rec", "ids", "inv", "id", "parent", "cause",
                 "adopts", "t0", "t1", "_adopted", "_covered",
                 "_beside", "_ann")

    def __init__(self, name: str, rec: Optional[SpanRecorder] = None,
                 parent: Optional["span"] = None,
                 cause: Optional["span"] = None, adopts: bool = False,
                 **ids):
        self.name = name
        self.rec = rec
        self.ids = ids
        self.inv = ids.get("inv")
        self.id = 0
        self.parent = parent
        self.cause = cause
        self.adopts = adopts
        self.t0 = self.t1 = 0
        self._adopted = parent is not None
        self._covered = 0  # ns under same-thread children
        # Intervals of adopted children. Appended from their threads
        # and copied here at exit: each a single list operation, which
        # the interpreter lock makes atomic.
        self._beside: List[tuple] = []
        self._ann = None

    def __enter__(self) -> "span":
        stack = getattr(_TLS, "stack", None)
        if stack is None:
            stack = _TLS.stack = []
        if self.parent is None and self.cause is None and stack:
            self.parent = stack[-1]
        near = self.parent or self.cause
        if near is not None:
            if self.rec is None:
                self.rec = near.rec
            if self.inv is None:
                self.inv = near.inv
        rec = self.rec
        if rec is not None:
            self.id = next(rec._ids)
            if self.adopts:
                with rec._lock:
                    rec._adopting[self.inv] = self
        self._ann = TraceAnnotation(
            ANNOTATION_PREFIX + self.name, **self.ids)
        self._ann.__enter__()
        stack.append(self)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = t1 = time.perf_counter_ns()
        _TLS.stack.pop()
        self._ann.__exit__(*exc)
        rec, parent = self.rec, self.parent
        if parent is not None:
            if self._adopted:
                parent._beside.append((self.t0, t1))
            else:
                parent._covered += t1 - self.t0
        if rec is None:
            return False
        if self.adopts:
            with rec._lock:
                if rec._adopting.get(self.inv) is self:
                    del rec._adopting[self.inv]
        covered = self._covered
        if self._beside:
            covered += _union_ns(list(self._beside), self.t0, t1)
        self._record(self.name, self.t0, t1,
                     max(0, t1 - self.t0 - covered), self.ids, self.id,
                     parent.id if parent is not None else None)
        return False

    def _record(self, name, t0, t1, self_ns, ids, id_, parent_id) -> None:
        rec = self.rec
        if rec.hub is not None:
            rec.hub.record_span(name, t1 - t0, self_ns, ids.get("bytes"))
        if rec.tracer is not None:
            args = dict(ids, id=id_, inv=self.inv, self_us=self_ns / 1e3)
            if parent_id is not None:
                args["parent"] = parent_id
            if self.cause is not None:
                args["cause"] = self.cause.id
            rec.tracer.span_event(name, t0, t1, args)

    def set(self, **ids) -> None:
        """More fields for an OPEN span, known only inside it."""
        self.ids.update(ids)
        self._ann.set_metadata(**ids)

    @property
    def seconds(self) -> float:
        """Duration of the closed span."""
        return (self.t1 - self.t0) * 1e-9

    def charge(self, name: str, seconds: float) -> None:
        """Record ``seconds`` of this OPEN span as a child ``name`` whose
        time was accumulated piecewise by a clock of its own (the
        codec's per-thread decode clock inside ``read``), so it has no
        interval: in the traces it is a marker where the charge is
        made, with ``seconds`` among its arguments; in the table it
        counts like any child, and this span's self time excludes
        it. A charge of nothing (a source that decoded nothing) leaves
        no marker and no row."""
        ns = int(seconds * 1e9)
        if ns <= 0:
            return
        ids = dict(self.ids, seconds=seconds)
        with TraceAnnotation(ANNOTATION_PREFIX + name, **ids):
            pass
        self._covered += ns
        if self.rec is not None:
            now = time.perf_counter_ns()
            self._record(name, now - ns, now, ns, ids,
                         next(self.rec._ids), self.id)
