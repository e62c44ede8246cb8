"""Sessions: compile + evaluate + result scanning.

Mirrors exec/session.go: a Session owns an executor, compiles Func
invocations into task graphs (memoizing per invocation), evaluates them,
and returns ``Result``s — which are themselves Slices, so results feed
later invocations without recomputation (the iterative-workload mechanism,
exec/session.go:391-442 + exec/compile.go:226-261).
"""

from __future__ import annotations

import itertools
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

from bigslice_tpu import typecheck
from bigslice_tpu.ops.base import Slice, make_name
from bigslice_tpu.ops.func import Func, Invocation
from bigslice_tpu import sliceio
from bigslice_tpu.exec import compile as compile_mod
from bigslice_tpu.exec.evaluate import evaluate
from bigslice_tpu.exec.task import Task, TaskState
from bigslice_tpu.utils import metrics as metrics_mod
from bigslice_tpu.utils import trace as trace_mod


def _is_gang_loss(e: BaseException) -> bool:
    """Is this failure the gang/host-loss class the elastic retry can
    recover from by re-forming the mesh? (Ordinary application errors
    re-raise — re-running them on a different mesh is useless.)"""
    from bigslice_tpu.exec.meshexec import HostLostError
    from bigslice_tpu.exec.task import TaskError
    from bigslice_tpu.utils.distributed import PeerLostError

    seen = set()
    stack = [e]
    while stack:
        err = stack.pop()
        if id(err) in seen or err is None:
            continue
        seen.add(id(err))
        if isinstance(err, (HostLostError, PeerLostError)):
            return True
        if isinstance(err, TaskError):
            stack.append(err.cause)
        stack.append(err.__cause__)
        # Implicit chaining too: a HostLostError raised during an except
        # block without `from` hangs off __context__, not __cause__.
        stack.append(err.__context__)
    return False


def _elastic_backoff_delay(attempt: int) -> float:
    """Delay before elastic recovery round ``attempt`` (0-based):
    base * 2^attempt, capped at 30s, with up to 25% jitter."""
    import os
    import random

    base = float(os.environ.get("BIGSLICE_ELASTIC_BACKOFF", "0.2"))
    if base <= 0:
        return 0.0
    return min(base * (2 ** attempt), 30.0) * (
        1.0 + 0.25 * random.random()
    )


class _InvocationGate:
    """Reader-writer isolation for exclusive invocations: normal runs
    share the session (readers); an exclusive Func's run takes the whole
    session (writer) — the single-host analog of the reference's
    dedicated cluster per exclusive Func (exec/bigmachine.go:314-319),
    preserving intra-invocation shard parallelism (unlike per-task
    Pragma.Exclusive, which takes the whole proc budget per task)."""

    def __init__(self):
        self._cond = threading.Condition()
        self._readers = 0
        self._writer = False

    def acquire(self, exclusive: bool) -> None:
        with self._cond:
            if exclusive:
                self._cond.wait_for(
                    lambda: not self._writer and self._readers == 0
                )
                self._writer = True
            else:
                self._cond.wait_for(lambda: not self._writer)
                self._readers += 1

    def release(self, exclusive: bool) -> None:
        with self._cond:
            if exclusive:
                self._writer = False
            else:
                self._readers -= 1
            self._cond.notify_all()


class Result(Slice):
    """A computed slice: the output of a session run (exec/session.go:391).

    Usable anywhere a Slice is: pass it to another Func, Cogroup it, etc.
    The compiler reuses its tasks directly (inserting shuffle adapters as
    needed). Reading re-evaluates lost tasks first — post-run fault
    tolerance for result scans (newEvalReader, exec/bigmachine.go:1485-1535).
    """

    def __init__(self, session: "Session", slice_: Slice,
                 tasks: Sequence[Task]):
        super().__init__(slice_.schema, len(tasks), make_name("result"))
        self.session = session
        self.tasks = list(tasks)
        self.scope = metrics_mod.Scope()
        for t in self.tasks:
            self.scope.merge(t.scope)

    def reader(self, shard: int, deps) -> sliceio.Reader:
        task = self.tasks[shard]

        def read():
            from bigslice_tpu.exec.evaluate import MAX_CONSECUTIVE_LOST
            from bigslice_tpu.exec.store import Missing

            # Re-evaluate-before-read with retry: outputs may vanish
            # between evaluation and the scan (machine loss); mark the
            # task lost and re-run its (transitive) producers
            # (newEvalReader, exec/bigmachine.go:1485-1535). Missing
            # can also surface MID-STREAM (a corrupt frame quarantined
            # by the FileStore during the scan) — same recovery, but
            # frames already yielded must not repeat, so the re-read
            # restarts the shard's stream from scratch only if nothing
            # was emitted yet; a partially-consumed stream re-raises.
            last = None
            for _ in range(MAX_CONSECUTIVE_LOST):
                if task.state != TaskState.OK:
                    evaluate(self.session.executor, [task])
                try:
                    r = self.session.executor.reader(task, 0)
                except Missing as e:
                    last = e
                    task.mark_lost(e)
                    continue
                emitted = False
                try:
                    for f in r:
                        emitted = True
                        yield f
                except Missing as e:
                    task.mark_lost(e)
                    if emitted:
                        raise
                    last = e
                    continue
                return
            raise last

        return read()

    # -- convenience scanning (Scanner analog, exec/session.go:407-410) ---

    def frames(self) -> sliceio.Reader:
        for shard in range(self.num_shards):
            yield from self.reader(shard, ())

    def rows(self) -> List[Tuple]:
        out: List[Tuple] = []
        for f in self.frames():
            out.extend(f.rows())
        return out

    def _merged(self, frames) -> "Frame":
        from bigslice_tpu.frame.frame import Frame

        frames = list(frames)
        return Frame.concat(frames) if frames else Frame.empty(
            self.schema
        )

    def to_arrow(self, names=None):
        """All result rows as one ``pyarrow.Table`` (frame/arrow.py
        mapping: vector columns → FixedSizeList, ragged group lists →
        List, strings → String)."""
        from bigslice_tpu.frame import arrow

        return arrow.to_arrow(self._merged(self.frames()), names=names)

    def to_pandas(self, names=None):
        """All result rows as a ``pandas.DataFrame``."""
        return self.to_arrow(names=names).to_pandas()

    def write_parquet(self, url_prefix: str, names=None) -> None:
        """Write one parquet file PER SHARD as
        ``{url_prefix}-NNNN-of-MMMM.parquet`` (the Cache family's
        sharded naming, over any fsspec scheme). Empty shards write
        empty files so the set is complete."""
        from bigslice_tpu.frame import arrow

        m = self.num_shards
        for shard in range(m):
            arrow.write_parquet(
                self._merged(self.reader(shard, ())),
                f"{url_prefix}-{shard:04d}-of-{m:04d}.parquet",
                names=names,
            )

    def discard(self) -> None:
        """Drop this result's own stored task outputs. What it was
        computed from stays stored: see ``discard_graph``."""
        for t in self.tasks:
            self.session.executor.discard(t)

    def discard_graph(self, keep: Sequence["Result"] = ()) -> None:
        """Drop the stored outputs of the whole subgraph of tasks this
        result was computed from (exec/session.go Discard), except the
        tasks of the ``keep`` Results and everything behind them. A run
        leaves every intermediate op group's output stored — on the
        mesh executor, resident in HBM — until the session ends; an
        iterative driver that reuses one base Result frees each round
        with ``round_result.discard_graph(keep=[base])``."""
        seen = set()
        stack = [t for r in keep for t in r.tasks]
        while stack:  # the kept subgraphs: visited, never discarded
            t = stack.pop()
            if id(t) not in seen:
                seen.add(id(t))
                stack.extend(p for d in t.deps for p in d.tasks)
        self._discard_behind(self.tasks, seen)

    def discard_inputs(self) -> None:
        """Keep this result's own stored output and drop everything it
        was computed from: the stored outputs of every task behind its
        own. The result still reads and still feeds a later run; what
        is gone is recomputed only if this result's own output is lost.
        A job that keeps its answer for a later one and wants the rest
        of its memory back calls this in place of ``discard_graph``."""
        mine = {id(t) for t in self.tasks}
        self._discard_behind(
            [p for t in self.tasks for d in t.deps for p in d.tasks],
            mine)

    def _discard_behind(self, tasks, seen: set) -> None:
        """Discard ``tasks`` and everything behind them, but for the
        tasks whose ids ``seen`` holds (and what only they lead to)."""
        stack = list(tasks)
        while stack:
            t = stack.pop()
            if id(t) not in seen:
                seen.add(id(t))
                self.session.executor.discard(t)
                stack.extend(p for d in t.deps for p in d.tasks)


class Session:
    """Lifecycle + options (exec/session.go:68-176).

    Options mirror the reference's session options:
    - ``parallelism``: local proc limit (exec/session.go:127-140)
    - ``trace_path``: write a Chrome trace of task scheduling on
      shutdown (TracePath, exec/session.go:160-164); analyze with
      ``python -m bigslice_tpu.tools.slicetrace``
    - ``status``: live per-op task-state lines on stderr
      (base/status display analog)
    - ``eventer``: callable ``(event_name, **fields)`` receiving coarse
      session analytics events (sessionStart/taskComplete,
      exec/session.go:256-261, exec/eval.go:160-165)
    - ``machine_combiners``: share one combiner buffer per process
      across all of a shuffle's producer tasks (MachineCombiners,
      exec/session.go:166-176) — fewer, larger combines at the cost of
      coarser retry granularity
    - ``monitor``: raw ``(task, state)`` transition callback
    - ``elastic``: max mesh-recovery retries per run. When a run dies
      with a gang/host-loss class error (``HostLostError`` in the
      failure chain), the session asks ``mesh_provider`` for the
      current healthy mesh, resizes the executor onto it (salvaging
      reachable outputs, re-marking unreachable ones LOST), and
      re-evaluates — completed tasks keep their results; the SPMD
      analog of the reference's machine-loss→task-resubmit loop
      (exec/slicemachine.go:148-227) at mesh granularity. The same
      seam grows: a provider returning a bigger mesh is demand-driven
      capacity (exec/slicemachine.go:586-601).
    - ``mesh_provider``: zero-arg callable returning the mesh to use
      for the next elastic attempt (platform-specific discovery of
      surviving/available devices).
    """

    def __init__(self, executor=None, parallelism: Optional[int] = None,
                 monitor=None, trace_path: Optional[str] = None,
                 status: bool = False, eventer=None,
                 machine_combiners: bool = False,
                 debug_port: Optional[int] = None,
                 elastic: int = 0, mesh_provider=None,
                 fleet_dir: Optional[str] = None):
        from bigslice_tpu.utils import status as status_mod

        if executor is None:
            from bigslice_tpu.exec.local import LocalExecutor

            executor = LocalExecutor(procs=parallelism)
        self.executor = executor
        self.elastic = elastic
        if (elastic and mesh_provider is None
                and getattr(executor, "resize", None) is not None):
            # Built-in demand-driven capacity: elastic sessions default
            # to probing currently-healthy devices for the retry mesh
            # (exec/slicemachine.go:586-601's loop at device
            # granularity). Single-process only — multi-process needs a
            # coordinated platform provider (the default returns None
            # there, and the session re-raises the gang loss).
            # Topology-aware: a 2-D (dcn, ici) executor recovers onto
            # a reshaped (D', I) grid of the surviving devices —
            # losing a pod row shrinks the DCN axis, not the session.
            from bigslice_tpu.parallel.meshutil import MeshTopology
            from bigslice_tpu.utils.distributed import (
                default_mesh_provider,
            )

            topo = MeshTopology(executor.mesh)
            mesh_provider = default_mesh_provider(
                axis=topo.axis if isinstance(topo.axis, str)
                else "shards",
                shape=topo.shape if topo.is_hier else None,
            )
        self.mesh_provider = mesh_provider
        self.eventer = eventer
        self.trace_path = trace_path
        self.tracer = trace_mod.Tracer() if trace_path else None
        # Session-scoped telemetry hub (utils/telemetry.py): subscribes
        # to the monitor + on_phase channels below and to executor
        # shuffle/staging seams; queried via telemetry_summary(), the
        # status display's annotations, and /debug/metrics. Its compact
        # skew/overlap instants ride self._event into the Chrome trace
        # for tools/slicetrace.py. BIGSLICE_TELEMETRY=0 disables the
        # hub entirely (every executor seam no-ops on the missing hub)
        # — the overhead floor for perf A/Bs of the collection itself.
        import os

        self.telemetry = None
        if os.environ.get("BIGSLICE_TELEMETRY", "1").lower() not in (
            "0", "false", "off"
        ):
            from bigslice_tpu.utils import telemetry as telemetry_mod

            self.telemetry = telemetry_mod.TelemetryHub(
                eventer=self._event
            )
        # Fleet telemetry plane (utils/fleettelemetry.py): with a fleet
        # dir configured (kwarg or BIGSLICE_FLEET_DIR — any fsspec URL)
        # and the hub enabled, this rank exports its mergeable snapshot
        # through the Store seam periodically, at every run end, and at
        # shutdown; rank 0 pulls + merges every rank's file into
        # telemetry_summary(scope="fleet") / fleet.json. No fleet dir
        # (or BIGSLICE_TELEMETRY=0) → no exporter, zero files written.
        self.fleet = None
        fleet_dir = fleet_dir or os.environ.get("BIGSLICE_FLEET_DIR") \
            or None
        if fleet_dir and self.telemetry is not None:
            from bigslice_tpu.utils import fleettelemetry as fleet_mod

            try:
                self.fleet = fleet_mod.FleetExporter(
                    self.telemetry, fleet_dir
                )
                self.fleet.start()
            except Exception:  # telemetry must never break the run
                self.fleet = None
        # Where this session's spans go (utils/trace.span): the hub's
        # table and, with trace_path, the tracer.
        self.spans = trace_mod.SpanRecorder(self.telemetry, self.tracer)
        self.status = status_mod.Status()
        self.status.set_telemetry(self.telemetry)
        stats_fn = getattr(self.executor, "resource_stats", None)
        if stats_fn is not None:
            self.status.set_resources_provider(stats_fn)
        self._printer = None
        if status:
            self._printer = status_mod.StatusPrinter(self.status)
            self._printer.start()
        monitors = [monitor, self.status, self.telemetry]
        if self.tracer is not None:
            monitors.append(trace_mod.TaskTraceMonitor(self.tracer))
        if eventer is not None:
            monitors.append(self._event_monitor)
        self.monitor = status_mod.chain_monitors(*monitors)
        self.machine_combiners = machine_combiners
        # Serving plane (serve/server.py): a ServeServer attached to
        # this session sets itself here so shutdown() can drain
        # in-flight invocations BEFORE the executor goes away.
        self.serve = None
        self.debug = None
        if debug_port is not None:
            from bigslice_tpu.utils.debughttp import DebugServer

            self.debug = DebugServer(self, debug_port)
        # XLA-level profiling (SURVEY.md §5.1 mapping) is windowed and
        # on-demand (utils/xprof.py): /debug/profile?seconds=N on the
        # DebugServer traces a live session's next N seconds with no
        # restart; the program's spans (utils/trace.span) are in it.
        from bigslice_tpu.utils import xprof as xprof_mod

        self.profiler = xprof_mod.Profiler()
        # Slice/callable runs draw from the SAME process-global counter
        # as Func invocations (ops/func._invocation_counter): two
        # counters would collide on index, merging distinct invocations
        # in traces and task names.
        from bigslice_tpu.ops import func as func_mod

        self._inv_index = func_mod._invocation_counter
        self._gate = _InvocationGate()
        # Adaptive execution (exec/adaptive.py): BIGSLICE_ADAPTIVE
        # engages the telemetry→action loop — hot-shard skew splitting,
        # speculative straggler duplicates, cost-driven wave/prefetch
        # shaping. Unset = planner_from_env returns None and NOTHING
        # here attaches: the chicken-bit contract (bit-identical legacy
        # behavior, zero bigslice_adaptive_* samples).
        self.adaptive = None
        from bigslice_tpu.exec import adaptive as adaptive_mod

        planner = adaptive_mod.planner_from_env(self.telemetry)
        if planner is not None:
            self.adaptive = planner
            if self.telemetry is not None:
                self.telemetry.adaptive = planner.stats
            executor.adaptive = planner
        # Kernel auto-selection (parallel/kernelselect.py):
        # BIGSLICE_KERNEL_SELECT engages measured per-op lowering
        # choice (sort vs hash vs dense) at every combine/shuffle
        # boundary. Same chicken-bit contract as the planner: unset =
        # selector_from_env returns None and NOTHING here attaches —
        # legacy lowerings, bit-identical programs, zero
        # bigslice_kernel_select_* samples.
        self.kernel_select = None
        from bigslice_tpu.parallel import kernelselect as kselect_mod

        selector = kselect_mod.selector_from_env(self.telemetry)
        if selector is not None:
            self.kernel_select = selector
            if self.telemetry is not None:
                self.telemetry.kernel_select = selector.stats
            if hasattr(executor, "kernel_select"):
                executor.kernel_select = selector
        # Coded k-of-n redundant combines (exec/codedplan.py):
        # BIGSLICE_CODED engages proactive straggler tolerance — combine
        # boundaries over-decompose into striped coverage groups, the
        # consumer wave fires at any covering k-subset, stragglers are
        # cooperatively cancelled. Same chicken-bit contract: unset =
        # planner_from_env returns None and NOTHING here attaches —
        # byte-identical task graphs and zero bigslice_coded_* samples.
        self.coded = None
        from bigslice_tpu.exec import codedplan as codedplan_mod

        coded = codedplan_mod.planner_from_env(self.telemetry)
        if coded is not None:
            self.coded = coded
            if self.telemetry is not None:
                self.telemetry.coded = coded.stats
            executor.coded = coded
        executor.start(self)
        # Rank-stamp the start event on multi-process gangs so
        # slicetrace's N-file merge (--merge) can assign each per-rank
        # trace its lane without relying on filenames; single-process
        # traces stay byte-identical (no rank field).
        from bigslice_tpu.utils.telemetry import _process_rank

        rank = _process_rank()
        if rank is None:
            self._event("bigslice:sessionStart", executor=executor.name)
        else:
            self._event("bigslice:sessionStart", executor=executor.name,
                        rank=rank)

    def _event(self, name: str, **fields) -> None:
        if self.eventer is not None:
            self.eventer(name, **fields)
        if self.tracer is not None:
            self.tracer.instant(name, **fields)

    def _event_monitor(self, task, state) -> None:
        from bigslice_tpu.exec.task import TaskState

        if state == TaskState.OK:
            self.eventer("bigslice:taskComplete", task=str(task.name))

    def run(self, func: Any, *args, corr: Optional[str] = None,
            deadline_s: Optional[float] = None) -> Result:
        """Compile and evaluate ``func(*args)`` (exec/session.go:214-225).

        ``func`` may be a registered ``Func``, a plain slice-returning
        callable, or a ``Slice`` directly (test convenience, mirroring
        slicetest.Run).

        ``deadline_s`` bounds THIS invocation's evaluation wall time:
        when it expires, in-flight tasks are cooperatively cancelled
        at their next seam (frame, coverage unit, wave boundary), the
        executor's slots are drained, and ``DeadlineExceeded``
        (exec/evaluate.py) propagates — the tasks stay resubmittable,
        so a later run of the same graph picks up where this one was
        cut off. The serving plane threads its per-request budget here.

        ``corr`` is the cross-rank correlation id: the serving plane
        mints one per request (deterministic across SPMD ranks — every
        rank's ServeServer sees the identical request stream) and
        threads it here, so the invocation instant in every rank's
        trace carries the same id and slicetrace's merged timeline can
        join one serve request to its waves and tasks on every rank.
        Defaults to ``inv<index>`` — itself identical across ranks by
        the shared-invocation-counter contract.
        """
        # The deadline clock starts BEFORE slice construction and
        # compilation: the caller's budget is for the invocation, and a
        # pathological build or compile must not silently eat it
        # without ever being charged.
        deadline = None
        if deadline_s is not None:
            if deadline_s <= 0:
                raise typecheck.errorf(
                    "run: deadline_s must be > 0, got %r", deadline_s
                )
            import time as _time

            deadline = _time.monotonic() + float(deadline_s)
        exclusive = False
        if isinstance(func, Func):
            inv = func.invocation(*args)
            slice_ = inv.invoke()
            inv_index = inv.index
            exclusive = func.exclusive
        elif isinstance(func, Slice):
            typecheck.check(not args, "run: args given with a literal slice")
            slice_ = func
            inv_index = next(self._inv_index)
        elif callable(func):
            slice_ = func(*args)
            typecheck.check(
                isinstance(slice_, Slice),
                "run: callable returned %s, expected a Slice",
                type(slice_).__name__,
            )
            inv_index = next(self._inv_index)
        else:
            raise typecheck.errorf(
                "run: expected Func, Slice, or callable, got %s",
                type(func).__name__,
            )
        corr = corr or f"inv{inv_index}"
        with trace_mod.span("session.run", rec=self.spans,
                            inv=inv_index):
            tasks = self._run_invocation(
                slice_, inv_index, exclusive, corr, args, deadline,
                deadline_s,
            )
        res = Result(self, slice_, tasks)
        res.corr = corr
        return res

    def _run_invocation(self, slice_, inv_index: int, exclusive: bool,
                        corr: str, args, deadline, deadline_s):
        """Compile ``slice_`` and evaluate its tasks (the body of
        ``run``, under its ``session.run`` span); returns the root
        tasks."""
        # Invocation record for the offline trace analyzer
        # (cmd/slicetrace invocation-category events: index, caller
        # location, stringified args). Built only when something
        # consumes events; reprlib bounds the arg stringification
        # (repr(huge_list)[:64] would materialize the whole string).
        if self.eventer is not None or self.tracer is not None:
            import reprlib

            loc = typecheck.caller_location()
            self._event(
                f"bigslice:invocation:{inv_index}",
                inv=inv_index,
                corr=corr,
                location=f"{loc[0]}:{loc[1]}" if loc else "?",
                args=", ".join(reprlib.repr(a) for a in args),
            )
        from bigslice_tpu.exec import shuffleplan as shuffleplan_mod

        with trace_mod.span("compile_tasks") as sp:
            tasks = compile_mod.Compiler(
                inv_index, machine_combiners=self.machine_combiners,
                mesh_signature=self._mesh_signature(),
                shuffle_mode=shuffleplan_mod.plan_mode() or "",
                kernel_select_mode=(self.kernel_select.mode
                                    if self.kernel_select is not None
                                    else None),
                coded=self.coded,
            ).compile(slice_)
            sp.set(tasks=len(tasks))  # root tasks
        if self.debug is not None:
            self.debug.register_roots(tasks)
        # Exclusive invocations evaluate in isolation from concurrent
        # runs of this session; their own shards stay parallel.
        self._gate.acquire(exclusive)
        try:
            attempts = 0
            while True:
                run_token = self._plan_run(tasks)
                err = None
                try:
                    # ``adopts``: the executor's worker threads run this
                    # invocation's groups while this thread waits; their
                    # ``group`` spans are this span's children.
                    with trace_mod.span("evaluate", adopts=True):
                        evaluate(self.executor, tasks,
                                 monitor=self.monitor, deadline=deadline)
                except Exception as e:  # noqa: BLE001
                    err = e
                finally:
                    # finish_run BEFORE the retry decision: it flushes
                    # an aborted run's parked tasks to the fallback so
                    # they settle (the recover step waits for them).
                    finish = getattr(self.executor, "finish_run", None)
                    if finish is not None:
                        finish(token=run_token, failed=err is not None)
                    if err is not None:
                        # Dead-run liveness: resolve remote waiters on
                        # this process's owned host tasks (the owner is
                        # healthy — its run is what died).
                        abort = getattr(
                            self.executor, "abort_run_outputs", None
                        )
                        if abort is not None:
                            abort(tasks, err)
                if err is None:
                    # KV hygiene for distributed host tasks: peers have
                    # all finished this run (barrier inside), so the
                    # run's non-root namespaces can be deleted.
                    release = getattr(
                        self.executor, "release_run_outputs", None
                    )
                    if release is not None:
                        release(tasks)
                    if deadline_s is not None:
                        self._record_deadline("met", deadline_s)
                    break
                from bigslice_tpu.exec.evaluate import DeadlineExceeded

                if isinstance(err, DeadlineExceeded):
                    # Not a loss the elastic ladder can buy back: the
                    # caller's budget is spent. Attribute and raise.
                    self._record_deadline("expired", deadline_s)
                    raise err
                if attempts >= self.elastic or not _is_gang_loss(err):
                    # Fatal for this run: dump the flight recorder's
                    # event ring beside the raise so the post-mortem
                    # has the last thing every wave/compile/recovery
                    # channel saw (no-op unless BIGSLICE_FLIGHTREC_DIR
                    # or an explicit dir is configured).
                    self._dump_flight(inv_index, err)
                    raise err
                # Bounded exponential backoff + jitter between elastic
                # rounds: a just-died mesh re-probed instantly tends to
                # be the same dead mesh, and a tight retry loop burns
                # every elastic attempt inside the outage window
                # (BIGSLICE_ELASTIC_BACKOFF = base seconds; 0 disables).
                delay = _elastic_backoff_delay(attempts)
                if delay > 0:
                    self._event("bigslice:elasticBackoff",
                                attempt=attempts,
                                delay_s=round(delay, 3))
                    import time as _time

                    _time.sleep(delay)
                # Recovery mutates the shared executor (mesh swap), so
                # quiesce the session first: trade our reader slot for
                # the writer (waits out concurrent runs; new runs block
                # until recovery is done), then trade back.
                if not exclusive:
                    self._gate.release(False)
                    self._gate.acquire(True)
                try:
                    recovered = self._elastic_recover(tasks, err)
                finally:
                    if not exclusive:
                        self._gate.release(True)
                        self._gate.acquire(False)
                if not recovered:
                    self._dump_flight(inv_index, err)
                    raise err
                attempts += 1
        finally:
            self._gate.release(exclusive)
            # Run-end fleet export (success or fatal): the snapshot
            # file is the one artifact a peer's merge can read, so it
            # must be current the moment this rank's run settles — the
            # periodic thread alone could lag a full period.
            if self.fleet is not None:
                try:
                    self.fleet.export()
                except Exception:
                    pass
        return tasks

    def _record_deadline(self, outcome: str, deadline_s) -> None:
        """Attribute a deadline outcome to the telemetry hub's deadline
        stats (lazily created there — zero samples until the first
        deadline-carrying run). Best-effort."""
        hub = self.telemetry
        if hub is None:
            return
        try:
            hub.record_deadline(outcome, deadline_s=deadline_s,
                                source="session")
        except Exception:
            pass

    def _mesh_signature(self):
        """The executor's repr-stable mesh-topology signature (axis
        names, shape) for compile.Compiler — computed per run, since
        elastic resize can swap the mesh between runs. None for
        mesh-less executors (the local tier)."""
        mesh = getattr(self.executor, "mesh", None)
        if mesh is None:
            return None
        from bigslice_tpu.parallel.meshutil import MeshTopology

        try:
            return MeshTopology(mesh).signature()
        except Exception:
            return None

    def _plan_run(self, tasks):
        """Register this evaluation attempt's deterministic group launch
        order with an ordered-dispatch executor; returns the run token
        (None when the executor doesn't plan)."""
        plan_groups = getattr(self.executor, "plan_groups", None)
        if plan_groups is None:
            return None
        from bigslice_tpu.exec.task import TaskState, iter_tasks

        # Post-order DFS is deterministic given the same program —
        # the ordered dispatcher's cross-process launch sequence.
        # Groups whose members are all already OK (Result reuse)
        # are omitted: nothing of theirs will launch.
        groups: Dict[Any, list] = {}
        order = []
        for t in iter_tasks(tasks):
            if t.group_key is None:
                continue
            if t.group_key not in groups:
                groups[t.group_key] = []
                order.append(t.group_key)
            groups[t.group_key].append(t)
        run_token = object()  # collision-free per-run identity
        # Consumer-driven gather marks (and any late-gather debts for
        # already-resident outputs this run reads on host) must precede
        # the group entries in the dispatch plan.
        plan_gather = getattr(self.executor, "plan_gather", None)
        if plan_gather is not None:
            plan_gather(tasks, token=run_token)
        plan_groups(
            ((k, groups[k]) for k in order
             if not all(m.state == TaskState.OK
                        for m in groups[k])),
            token=run_token,
        )
        return run_token

    def _elastic_recover(self, tasks, cause) -> bool:
        """Between elastic attempts: move the executor onto the current
        healthy mesh and return fatal tasks to INIT so the next
        evaluation re-runs them (completed tasks keep their — salvaged —
        results). Returns False — retry is unsafe, re-raise — when any
        task is still in flight (a thread wedged inside a collective
        outlived the evaluator's drain: a fresh evaluation would wait on
        it forever)."""
        import time

        from bigslice_tpu.exec.task import TaskState, iter_tasks

        all_tasks = iter_tasks(tasks)
        # Flushed/parked tasks settle through the fallback executor
        # shortly after finish_run; a thread truly wedged inside a
        # collective never will. Bounded wait separates the two.
        deadline = time.monotonic() + 30.0
        while any(t.state in (TaskState.WAITING, TaskState.RUNNING)
                  for t in all_tasks):
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.05)
        mesh = self.mesh_provider() if self.mesh_provider else None
        resize = getattr(self.executor, "resize", None)
        if resize is None or mesh is None:
            # No way to swap the dead mesh: retrying would re-evaluate on
            # the same one and burn every elastic attempt predictably.
            return False
        resize(mesh)
        for t in all_tasks:
            if t.state == TaskState.ERR:
                t.reset_for_retry()
        self._event("bigslice:elasticRetry", cause=repr(cause))
        return True

    def _dump_flight(self, inv_index, err) -> None:
        """Best-effort flight-recorder dump on a fatal run outcome
        (utils/telemetry.py dump_flight_record; opt-in via
        BIGSLICE_FLIGHTREC_DIR)."""
        if self.telemetry is None:
            return
        try:
            path = self.telemetry.dump_flight_record(
                inv=inv_index, reason=repr(err)
            )
            if path:
                self._event("bigslice:flightRecorder",
                            inv=inv_index, path=path)
        except Exception:
            pass
        # Fleet post-mortem: push this rank's flight doc through the
        # store, and let the coordinator collate every rank's dump
        # into one bundle — a multihost failure leaves one coherent
        # artifact instead of N scattered per-host files.
        if self.fleet is not None:
            try:
                self.fleet.export_flight(
                    self.telemetry.flight_doc(inv=inv_index,
                                              reason=repr(err))
                )
                bundle = self.fleet.collate_flights()
                if bundle:
                    self._event("bigslice:postmortem",
                                inv=inv_index, bundle=bundle)
            except Exception:
                pass

    def telemetry_summary(self, scope: str = "session") -> dict:
        """The telemetry hub's aggregated signals (utils/telemetry.py):
        per-op task-duration quantiles + stragglers, shuffle-boundary
        skew (per-shard rows/bytes, max/median ratio, hot shard),
        wave-pipeline overlap accounting (staging vs exposed time,
        overlap-efficiency), and the ``device`` plane (compile/cost/
        memory attribution, HBM watermarks, donation effectiveness —
        utils/devicetelemetry.py). The benchmark's per-layer metrics
        read it (benchmarks/README.md); tests assert skew flagging
        through it. Empty when the hub is disabled
        (BIGSLICE_TELEMETRY=0).

        ``scope="fleet"`` returns the cross-rank merge instead: every
        rank's exported snapshot pulled through the store and merged
        (utils/fleettelemetry.py) — per-op skew recomputed from the
        elementwise-summed partition vectors, task quantiles from the
        merged fixed-bin histograms, compile/exchange/HBM attribution
        per rank. Without a fleet exporter it degrades to merging this
        process's own snapshot (a 1-rank fleet), so the fleet shape is
        always available for tooling."""
        if self.telemetry is None:
            return {}
        if scope == "fleet":
            from bigslice_tpu.utils import fleettelemetry as fleet_mod

            if self.fleet is not None:
                return self.fleet.fleet_summary()
            return fleet_mod.merge_snapshots(
                [self.telemetry.snapshot()]
            )
        return self.telemetry.summary()

    # Go-flavored alias (Session.Must): raise on error is Python's default.
    must = run

    def shutdown(self) -> None:
        # Drain the serving surface FIRST: in-flight invocations are
        # evaluating on this session's executor, so the server must
        # stop admitting and let them finish before the executor (and
        # its mesh state) is torn down — the SIGTERM half of the
        # serving plane's graceful-shutdown contract (the server's
        # close() also flushes its final telemetry snapshot).
        if self.serve is not None:
            try:
                self.serve.close()
            except Exception:
                pass
        # Final fleet export BEFORE the executor (and its mesh) goes
        # away: everything is recorded by now, and rank 0's close also
        # waits (bounded) for peer files and writes the merged
        # fleet.json beside them.
        if self.fleet is not None:
            try:
                self.fleet.close()
            except Exception:
                pass
        close = getattr(self.executor, "close", None)
        if close is not None:
            close()
        if self._printer is not None:
            self._printer.stop()
        if self.debug is not None:
            self.debug.close()
        if self.tracer is not None and self.trace_path:
            self.tracer.save(self.trace_path)
            self._event("bigslice:traceSaved", path=self.trace_path)


def start(executor=None, **kwargs) -> Session:
    """Create a session (mirrors exec.Start, exec/session.go:191-207)."""
    return Session(executor=executor, **kwargs)
