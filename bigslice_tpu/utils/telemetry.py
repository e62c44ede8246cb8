"""Session-scoped telemetry hub: skew, stragglers, wave overlap.

The reference surfaces *raw* observability channels — task-state
transitions (base/status), Chrome traces (exec/tracer.go), per-machine
resource gauges (exec/slicemachine.go:238-257) — but leaves their
interpretation to the operator. At production scale the questions that
matter are already aggregates: is this shuffle skewed, which shard is
the straggler, and how much of the wave pipeline's prefetch window
actually hides compute. ``TelemetryHub`` subscribes to the existing
channels (the ``(task, state)`` monitor chain, the ``on_phase`` wave
channel of exec/evaluate.py, and executor shuffle/staging seams) and
computes three actionable signal families:

1. **Shuffle skew** — per-shard row/byte sizes at every shuffle
   boundary, accumulated per op, with a skew ratio (max/median) and the
   hot shard's index. Executors report at their natural boundary: the
   local tier reports rows *routed* per partition (pre-combine — the
   honest work signal for combiner-bearing shuffles), the mesh tier
   reports per-device output counts (post-combine for fused
   shuffle+combine programs; multi-process meshes skip the host-side
   count sync entirely).
2. **Stragglers** — per-task duration quantiles per op (from the
   authoritative ``Task.state_times`` stamps), flagging a completed
   task whose duration exceeds ``straggler_factor`` × the p50 of its
   op's previously-completed siblings, and (live) a RUNNING task whose
   elapsed time already does.
3. **Wave-overlap accounting** — per staged wave, total staging time
   vs. the portion the compute thread actually *waited* on it
   (exposed). ``hidden / total`` is the pipeline's overlap-efficiency:
   1.0 means prefetch fully hid staging behind compute, 0.0 is the
   serial executor. The staging record also carries a
   read/decode/assemble/upload breakdown (the staging fast path's
   stages, exec/staging.py), so a low overlap number comes with the
   *why*: which stage of staging the time went to.

Surfaced three ways: ``prometheus_text()`` (the ``/debug/metrics``
endpoint of utils/debughttp.py), ``status_lines()`` (live skew /
straggler annotations in the utils/status.py display), and
``summary()`` (the ``Session.telemetry_summary()`` dict the
benchmark's harness reads). Each record additionally emits a
compact instant event through the session's eventer/tracer so
``tools/slicetrace.py`` can render skew/overlap sections offline.

All entry points are exception-safe by design (telemetry must never
take down an evaluation) and cheap: O(shards) per shuffle boundary,
O(1) per task transition amortized.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List, Optional, Tuple

from bigslice_tpu.utils import faultinject

# Flagging thresholds. Deliberately conservative defaults: a production
# alert that fires on balanced workloads is worse than none. Tests (and
# operators) tune per-hub attributes directly.
DEFAULT_SKEW_RATIO = 4.0          # max/median per-shard rows
DEFAULT_SKEW_MIN_ROWS = 512       # don't flag toy shuffles
DEFAULT_STRAGGLER_FACTOR = 3.0    # task > k * p50(completed siblings)
DEFAULT_STRAGGLER_MIN_SIBLINGS = 3
DEFAULT_STRAGGLER_MIN_SECS = 0.05  # 3x of a 1ms task is noise

# Prometheus histogram buckets for per-shard shuffle sizes.
ROWS_BUCKETS = (100, 1_000, 10_000, 100_000, 1_000_000, 10_000_000)

# Retained per-op records. Iterative drivers mint fresh ``#N``-suffixed
# op names every invocation, so a week-long session would otherwise
# grow the hub without bound; oldest ops (insertion order) evict first,
# Prometheus-counter monotonicity be damned — an evicted op is one
# nobody scraped for hundreds of invocations.
MAX_OPS = 1024

# Bounds on the recovery ladder's bookkeeping: latency samples per site
# and simultaneously-pending lost tasks tracked (beyond it, recoveries
# still count — only the latency sample is dropped).
MAX_RECOVERY_SAMPLES = 4096
MAX_RECOVERY_PENDING = 4096

# Flight-recorder ring: the last N structured hub events, dumped as a
# flightrec-<inv>.json artifact on fatal error / drain timeout. Small
# on purpose: the recorder answers "what was the run doing right
# before it died", not "replay the whole session".
FLIGHT_MAX_EVENTS = 512


def quantile(sorted_xs: List[float], p: float) -> float:
    """Linear-interpolated quantile of an ascending list."""
    n = len(sorted_xs)
    if n == 0:
        return 0.0
    if n == 1:
        return sorted_xs[0]
    i = p * (n - 1)
    lo = int(i)
    hi = min(lo + 1, n - 1)
    return sorted_xs[lo] + (sorted_xs[hi] - sorted_xs[lo]) * (i - lo)


def _escape_label(v: str) -> str:
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _process_rank() -> Optional[int]:
    """Rank tag for per-process artifacts: the SPMD process index when
    this is a multi-process gang, else None — single-process artifact
    names (and docs) stay byte-stable."""
    try:
        import jax

        if int(jax.process_count()) > 1:
            return int(jax.process_index())
    except Exception:
        pass
    return None


class _OpRecord:
    """Per-op accumulation (one instance per distinct op name; iterative
    drivers re-invoke under fresh ``#N``-suffixed names, so an op key is
    naturally per-invocation-site-per-run)."""

    def __init__(self, inv: Optional[int] = None):
        self.inv = inv
        # -- task durations / stragglers
        self.durations: List[float] = []      # completed (OK) tasks
        self.running: Dict[str, float] = {}   # task key -> start stamp
        self.shards: Dict[str, int] = {}      # task key -> shard index
        self.stragglers: List[dict] = []
        # -- shuffle sizes (elementwise-accumulated across producers)
        self.part_rows: List[int] = []
        self.part_bytes: List[int] = []
        self.shuffle_boundaries = 0
        self.worst_ratio = 0.0
        self.worst_max_shard = -1
        self.skew_flagged = False
        self.rows_hist = [0] * (len(ROWS_BUCKETS) + 1)
        self.rows_hist_sum = 0
        self.rows_hist_count = 0
        # -- wave pipeline accounting
        self.staging_s = 0.0
        self.exposed_s = 0.0
        self.compute_s = 0.0
        self.staged_waves = 0
        self.max_wave = -1
        self.phase_counts: Dict[str, int] = {}
        # staging breakdown: where staging time went (the *why* behind
        # overlap_efficiency) — read (store/reader drain), decode
        # (codec), assemble (arena copy+pad), upload (device_put).
        self.stage_phases: Dict[str, float] = {}
        # -- map-side combine cardinality (exec/local.py seam): rows
        # INTO the boundary's combiner vs rows out (~distinct keys),
        # accumulated across producer tasks. The post-combine shuffle
        # vector alone hides true cardinality; the kernel selector's
        # probe corpora and the coded planner's k/n sizing need it.
        self.combine_in_rows = 0
        self.combine_out_rows = 0
        self.combine_boundaries = 0
        # The mesh executor's map-side combine: rows its waves staged
        # since the last boundary (they become combine_in_rows there),
        # the lowering picked, the 64-bit value columns carried.
        self.staged_rows = 0
        # -- the rows the op's waves took as input, since the session
        # began: read in place from a stored device output, or staged
        # from the host (record_wave_staging)
        self.rows_resident = 0
        self.rows_staged = 0
        self.combine_lowering = ""
        self.combine_wide_columns = 0
        # -- host seconds of the op's dispatch / settle spans, and of
        # its settles how many found their signals already computed
        self.wave_host_s: Dict[str, float] = {}
        self.settles = 0
        self.settles_ready = 0
        # -- of the pipelined waves' ``stage_wait``s, how many found
        # their wave staged already, and the seconds the prefetch
        # workers waited for the compute thread to take one, with the
        # stages that began beside another of their group
        # (record_prefetch_blocked): None until a pipelined group ended
        self.stage_waits = 0
        self.stage_waits_ready = 0
        self.prefetch_blocked_s: Optional[float] = None
        self.stages_overlapped = 0
        # -- a lookup join's waves (record_join): None for every other
        # op, which then has no ``join`` block
        self.join: Optional[dict] = None
        # -- the cross-wave merges of a waved shuffle's outputs
        # (record_merge): None for an op that merged nothing
        self.merge: Optional[dict] = None
        # -- the dense tables its waves filled (record_table): None for
        # an op whose programs fill none
        self.table: Optional[dict] = None


class DeadlineStats:
    """Deadline-ladder attribution (exec/evaluate.DeadlineExceeded,
    serve/server.py admission/expiry): outcome counts per tenant plus
    session-level outcomes. Created lazily by the hub's first
    ``record_deadline`` call — the zero-sample contract for
    deadline-free processes."""

    MAX_TENANTS = 64

    def __init__(self):
        self._lock = threading.Lock()
        # (tenant, outcome) -> count; tenant "" = non-serving (session).
        self._counts: Dict[Tuple[str, str], int] = {}
        self._sources: Dict[str, int] = {}

    def record(self, outcome: str, tenant: str = "",
               deadline_s=None, source: str = "") -> None:
        tenant = str(tenant or "")
        with self._lock:
            known = {t for t, _ in self._counts}
            if tenant not in known and len(known) >= self.MAX_TENANTS:
                tenant = "_overflow"
            k = (tenant, str(outcome))
            self._counts[k] = self._counts.get(k, 0) + 1
            if source:
                self._sources[source] = self._sources.get(source, 0) + 1

    def count(self, outcome: str, tenant: Optional[str] = None) -> int:
        with self._lock:
            return sum(
                n for (t, o), n in self._counts.items()
                if o == outcome and (tenant is None or t == tenant)
            )

    def summary(self) -> dict:
        with self._lock:
            by_tenant: Dict[str, Dict[str, int]] = {}
            for (t, o), n in sorted(self._counts.items()):
                by_tenant.setdefault(t or "_session", {})[o] = n
            return {
                "by_tenant": by_tenant,
                "by_source": dict(sorted(self._sources.items())),
            }

    def prometheus_lines(self, metric, line) -> None:
        with self._lock:
            counts = dict(self._counts)
        metric("bigslice_deadline_outcomes_total",
               "Deadline-ladder outcomes (met, expired, "
               "rejected_admission, queue_timeout) per tenant; tenant "
               "_session = non-serving Session.run(deadline_s=) calls.",
               "counter")
        for (t, o), n in sorted(counts.items()):
            line("bigslice_deadline_outcomes_total",
                 {"tenant": t or "_session", "outcome": o}, n)


class TelemetryHub:
    """The aggregation layer. Participates in the monitor chain (it is
    a ``(task, state)`` callable exposing ``on_phase``) and receives
    executor seam calls (``record_shuffle`` / ``record_wave_staging`` /
    ``record_wave_compute``)."""

    def __init__(self, eventer=None,
                 skew_ratio: float = DEFAULT_SKEW_RATIO,
                 skew_min_rows: int = DEFAULT_SKEW_MIN_ROWS,
                 straggler_factor: float = DEFAULT_STRAGGLER_FACTOR,
                 straggler_min_siblings: int =
                 DEFAULT_STRAGGLER_MIN_SIBLINGS,
                 straggler_min_secs: float = DEFAULT_STRAGGLER_MIN_SECS):
        self._lock = threading.Lock()
        self._ops: Dict[str, _OpRecord] = {}
        self._state_counts: Dict[tuple, int] = {}
        # Recovery ladder (the fault-tolerance signal family): LOST
        # tasks pending recovery (task key -> (first-loss stamp, site)),
        # per-site recovered/fatal counters, and recovery-latency
        # samples per site. ``site`` is the chaos plane's injection
        # site when the loss's failure chain carries a fault marker
        # (utils/faultinject.py), else "organic".
        self._recovery_pending: Dict[str, Tuple[float, str]] = {}
        self._recovered: Dict[str, int] = {}
        self._recovery_fatal: Dict[str, int] = {}
        self._recovery_lat: Dict[str, List[float]] = {}
        # Drain-timeout census (exec/evaluate._drain's wedged report).
        self._drain_timeouts = 0
        self._drain_wedged: List[dict] = []
        # Span table (utils/trace.span): name -> [count, total ns,
        # self ns, bytes or None]. Session-level and monotonic, not
        # per-op, so MAX_OPS eviction never makes a window's delta
        # negative. Its own lock: spans close on every executor thread.
        self._spans: Dict[str, list] = {}
        self._span_lock = threading.Lock()
        self._eventer = eventer
        # Flight recorder: every event _emit sends (wave staging/
        # compute, shuffle sizes, compile, hbm, recovery...) also lands
        # in this bounded ring; dump_flight_record writes it out on
        # fatal error / drain timeout when a dump dir is configured.
        self._flight: collections.deque = collections.deque(
            maxlen=FLIGHT_MAX_EVENTS
        )
        # Own lock (never nests under executor/monitor paths): appends
        # happen on whatever thread emitted, and the dump snapshot must
        # not race them — a deque mutated mid-iteration raises, and the
        # dump's best-effort except would silently eat the one artifact
        # a live failure exists to leave behind.
        self._flight_lock = threading.Lock()
        self._flight_dumped: Dict[object, str] = {}
        # Device plane (utils/devicetelemetry.py): compile/cost/memory
        # attribution, HBM watermarks, donation effectiveness. Shares
        # this hub's eventer so its instants ride the same tracer lane
        # (and this flight ring).
        from bigslice_tpu.utils import devicetelemetry

        self.device = devicetelemetry.DeviceTelemetry(
            eventer=self._emit
        )
        # Serving plane (serve/server.py): the invocation server hooks
        # its per-tenant request/latency/admission stats here so they
        # ride telemetry_summary()["serving"] and /debug/metrics like
        # every other signal family. None outside a serving process.
        self.serving = None
        # Adaptive plane (exec/adaptive.py): the Session attaches its
        # planner's AdaptiveStats here when BIGSLICE_ADAPTIVE engages
        # at least one policy, so decisions ride summary()["adaptive"]
        # and the bigslice_adaptive_* Prometheus families. None with
        # the knob unset — neither family ever emits a sample then.
        self.adaptive = None
        # Kernel-selection plane (parallel/kernelselect.py): the
        # Session attaches its selector's KernelSelectStats here when
        # BIGSLICE_KERNEL_SELECT engages a mode, so lowering decisions
        # ride summary()["kernel_select"] and the
        # bigslice_kernel_select_* Prometheus families. None with the
        # knob unset — neither family ever emits a sample then.
        self.kernel_select = None
        # Coded k-of-n plane (exec/codedplan.py): the Session attaches
        # its planner's CodedStats here when BIGSLICE_CODED engages, so
        # coverage/cancel/mask decisions ride summary()["coded"] and
        # the bigslice_coded_* Prometheus families. None with the knob
        # unset — neither family ever emits a sample then.
        self.coded = None
        # Deadline plane (exec/evaluate.py / serve/server.py): created
        # lazily by the FIRST record_deadline call — a process that
        # never runs with a deadline exports zero bigslice_deadline_*
        # samples, the same zero-sample discipline as the knob planes.
        self.deadline = None
        self.skew_ratio = skew_ratio
        self.skew_min_rows = skew_min_rows
        self.straggler_factor = straggler_factor
        self.straggler_min_siblings = straggler_min_siblings
        self.straggler_min_secs = straggler_min_secs

    def _op(self, op: str, inv: Optional[int] = None) -> _OpRecord:
        rec = self._ops.get(op)
        if rec is None:
            while len(self._ops) >= MAX_OPS:
                evicted = next(iter(self._ops))
                del self._ops[evicted]
                for k in [k for k in self._state_counts
                          if k[0] == evicted]:
                    del self._state_counts[k]
            rec = self._ops[op] = _OpRecord(inv)
        if rec.inv is None:
            rec.inv = inv
        return rec

    def _emit(self, name: str, **fields) -> None:
        try:
            with self._flight_lock:
                self._flight.append(
                    (time.time(), name,
                     {k: v for k, v in fields.items()
                      if v is not None})
                )
        except Exception:
            pass
        ev = self._eventer
        if ev is None:
            return
        try:
            ev(name, **fields)
        except Exception:  # telemetry must never break the run
            pass

    # -- monitor protocol (chained by Session) ----------------------------

    def __call__(self, task, state) -> None:
        from bigslice_tpu.exec.task import TaskState

        now = time.monotonic()
        key = str(task.name)
        straggler = None
        recovered = None
        with self._lock:
            sk = (task.name.op, state.name)
            self._state_counts[sk] = self._state_counts.get(sk, 0) + 1
            rec = self._op(task.name.op, task.name.inv_index)
            if state == TaskState.RUNNING:
                # Task.state_times is authoritative (stamped inside the
                # transition, before subscribers run); our own stamp is
                # the fallback for hand-rolled tasks in tests.
                times = getattr(task, "state_times", None) or {}
                rec.running[key] = times.get(TaskState.RUNNING, now)
                rec.shards[key] = task.name.shard
            elif state == TaskState.OK:
                pend = self._recovery_pending.pop(key, None)
                if pend is not None:
                    # LOST → ... → OK: the ladder recovered this task.
                    t_lost, site = pend
                    times = getattr(task, "state_times", None) or {}
                    lat = max(0.0, times.get(TaskState.OK, now) - t_lost)
                    self._recovered[site] = \
                        self._recovered.get(site, 0) + 1
                    lats = self._recovery_lat.setdefault(site, [])
                    if len(lats) < MAX_RECOVERY_SAMPLES:
                        lats.append(lat)
                    recovered = {"site": site,
                                 "latency_s": round(lat, 6)}
                start = rec.running.pop(key, None)
                if start is not None:
                    # End stamp from state_times too: the hub may be
                    # called after slower chain members, and that
                    # monitor latency must not inflate durations (or
                    # mint false stragglers on fast ops).
                    times = getattr(task, "state_times", None) or {}
                    dur = max(0.0, times.get(TaskState.OK, now) - start)
                    siblings = sorted(rec.durations)
                    rec.durations.append(dur)
                    if (len(siblings) >= self.straggler_min_siblings
                            and dur >= self.straggler_min_secs):
                        p50 = quantile(siblings, 0.5)
                        if dur > self.straggler_factor * p50:
                            straggler = {
                                "task": key,
                                "shard": rec.shards.get(key, -1),
                                "duration_s": round(dur, 6),
                                "p50_s": round(p50, 6),
                            }
                            rec.stragglers.append(straggler)
            elif state == TaskState.LOST:
                rec.running.pop(key, None)
                if (key not in self._recovery_pending
                        and len(self._recovery_pending)
                        < MAX_RECOVERY_PENDING):
                    # First loss opens the recovery window (repeat
                    # losses keep the original stamp: time-to-recovery
                    # measures loss → healthy, retries included).
                    site = faultinject.fault_site_of(
                        getattr(task, "error", None)
                    ) or "organic"
                    times = getattr(task, "state_times", None) or {}
                    self._recovery_pending[key] = (
                        times.get(TaskState.LOST, now), site,
                    )
            elif state == TaskState.CANCELLED:
                # Cooperative cancellation (coded coverage settled /
                # deadline expired): no duration sample — a cancelled
                # body's wall says nothing about the op — and the task
                # must leave the running ledger or live_stragglers
                # would keep flagging a body that already stopped.
                rec.running.pop(key, None)
            elif state == TaskState.ERR:
                rec.running.pop(key, None)
                pend = self._recovery_pending.pop(key, None)
                if pend is not None:
                    # The ladder gave up (consecutive-loss cap / fatal
                    # reclassification): a non-recovery, by site.
                    self._recovery_fatal[pend[1]] = \
                        self._recovery_fatal.get(pend[1], 0) + 1
        if recovered is not None:
            self._emit("bigslice:taskRecovered", op=task.name.op,
                       inv=task.name.inv_index, task=key, **recovered)
        if straggler is not None:
            self._emit("bigslice:straggler", op=task.name.op,
                       inv=task.name.inv_index, **straggler)

    def on_phase(self, task, phase: str, wave: int) -> None:
        with self._lock:
            rec = self._op(task.name.op, task.name.inv_index)
            rec.phase_counts[phase] = rec.phase_counts.get(phase, 0) + 1
            rec.max_wave = max(rec.max_wave, int(wave))

    def on_drain_timeout(self, wedged: List[dict]) -> None:
        """exec/evaluate._drain's expiry census: which tasks were still
        in flight when an aborted evaluation gave up waiting."""
        with self._lock:
            self._drain_timeouts += 1
            self._drain_wedged = list(wedged)[:64]
        self._emit("bigslice:drainTimeout", n=len(wedged),
                   tasks=[w["task"] for w in wedged[:8]])
        # The drain census IS the wedge evidence a post-mortem needs:
        # dump the flight ring next to it (no-op unless a dump dir is
        # configured — see dump_flight_record).
        self.dump_flight_record(reason="drain_timeout")

    # -- flight recorder --------------------------------------------------

    @staticmethod
    def flightrec_dir(out_dir: Optional[str] = None) -> Optional[str]:
        """Where flight-recorder dumps go: explicit arg, else the
        ``BIGSLICE_FLIGHTREC_DIR`` env var, else None (dumping is
        opt-in: a failing unit test must not litter /tmp)."""
        import os

        return out_dir or os.environ.get("BIGSLICE_FLIGHTREC_DIR") \
            or None

    def dump_flight_record(self, inv: Optional[int] = None,
                           reason: str = "",
                           out_dir: Optional[str] = None
                           ) -> Optional[str]:
        """Write the event ring (filtered to ``inv`` when given — events
        with no inv tag ride along) plus the task-state census and the
        active chaos plan to ``flightrec-<inv>.json``. Best-effort and
        deduped per inv — matching the one-file-per-inv naming, so a
        later outcome for the same invocation can never silently
        overwrite the first dump (whose ring, closest to the original
        failure, is the evidence a post-mortem wants). Returns the
        path, or None when no dump dir is configured or writing
        failed."""
        dirname = self.flightrec_dir(out_dir)
        if dirname is None:
            return None
        key = inv
        try:
            with self._lock:
                if key in self._flight_dumped:
                    return self._flight_dumped[key]
            doc = self.flight_doc(inv=inv, reason=reason)
            import json
            import os

            os.makedirs(dirname, exist_ok=True)
            stem = f"flightrec-{inv if inv is not None else 'session'}"
            rank = doc.get("rank")
            if rank is not None:
                # Multi-process gang: every rank dumps its own ring
                # (same dir may be shared storage) — the rank suffix
                # keeps them from clobbering each other, and the
                # coordinator's post-mortem collation
                # (fleettelemetry.FleetExporter.collate_flights) joins
                # them into one bundle.
                stem += f"-rank{rank}"
            path = os.path.join(dirname, stem + ".json")
            with open(path, "w") as fp:
                json.dump(doc, fp, indent=1, default=str)
            with self._lock:
                self._flight_dumped[key] = path
            return path
        except Exception:  # telemetry must never break the run
            return None

    def flight_doc(self, inv: Optional[int] = None,
                   reason: str = "") -> dict:
        """The flight-recorder document (event ring filtered to
        ``inv`` when given, task-state census, active chaos plan),
        rank-tagged on multi-process gangs — what
        ``dump_flight_record`` writes locally and what the fleet
        exporter pushes through the store for coordinator collation
        into one post-mortem bundle."""
        with self._flight_lock:
            ring = list(self._flight)
        with self._lock:
            events = [
                {"ts": ts, "name": name, **fields}
                for ts, name, fields in ring
                if inv is None or fields.get("inv") in (None, inv)
            ]
            states: Dict[str, int] = {}
            for (_, st), n in self._state_counts.items():
                states[st] = states.get(st, 0) + n
        doc = {
            "inv": inv,
            "reason": reason,
            "ts": time.time(),
            "task_states": states,
            "events": events,
        }
        rank = _process_rank()
        if rank is not None:
            doc["rank"] = rank
        plan = faultinject.active_plan()
        if plan is not None:
            doc["chaos"] = plan.snapshot()
        return doc

    # -- executor seams ---------------------------------------------------

    def record_shuffle(self, op: str, inv: Optional[int],
                       rows, nbytes=None, indices=None,
                       rank: Optional[int] = None) -> None:
        """One producer's (or one whole group's) per-partition sizes at
        a shuffle boundary. Contributions accumulate elementwise per op,
        so per-producer host-tier calls and single whole-group mesh
        calls land in the same per-op partition-size vector.

        ``indices`` places the contributions at explicit *global*
        partition positions — the multi-process SPMD path, where each
        rank only reads its addressable shards of the count array and
        reports them at their global offsets. Only the provided
        entries are observed by the size histogram (the unaddressable
        rest of the vector stays untouched zeros), so a post-hoc
        cross-rank merge of per-rank snapshots reconstructs exactly
        the single-process vector and histogram. ``rank`` tags the
        emitted event for trace attribution."""
        rows = [max(0, int(r)) for r in rows]
        if not rows:
            return
        if nbytes is None:
            nbytes = [0] * len(rows)
        nbytes = [max(0, int(b)) for b in nbytes][:len(rows)]
        if indices is not None:
            indices = [int(i) for i in indices]
            if len(indices) != len(rows) or any(i < 0
                                                for i in indices):
                return  # malformed caller: drop, don't corrupt
            top = max(indices) + 1
        else:
            top = len(rows)
        with self._lock:
            rec = self._op(op, inv)
            if len(rec.part_rows) < top:
                rec.part_rows.extend(
                    [0] * (top - len(rec.part_rows)))
                rec.part_bytes.extend(
                    [0] * (top - len(rec.part_bytes)))
            for i, r in enumerate(rows):
                rec.part_rows[indices[i] if indices is not None
                              else i] += r
            for i, b in enumerate(nbytes):
                rec.part_bytes[indices[i] if indices is not None
                               else i] += b
            rec.shuffle_boundaries += 1
            for r in rows:  # histogram observes per-shard sizes
                for bi, le in enumerate(ROWS_BUCKETS):
                    if r <= le:
                        rec.rows_hist[bi] += 1
                        break
                else:
                    rec.rows_hist[-1] += 1
                rec.rows_hist_sum += r
                rec.rows_hist_count += 1
            ratio, max_shard, median, total = self._skew_of(
                rec.part_rows
            )
            max_rows = rec.part_rows[max_shard]
            if ratio > rec.worst_ratio:
                rec.worst_ratio = ratio
                rec.worst_max_shard = max_shard
            flagged = (total >= self.skew_min_rows
                       and ratio >= self.skew_ratio)
            rec.skew_flagged = rec.skew_flagged or flagged
        # All accumulated-vector values (this call's contribution is
        # already folded in) so slicetrace's last-event-per-op view
        # reads the op's final state.
        self._emit(
            "bigslice:shuffleSizes", op=op, inv=inv,
            rows=rows if len(rows) <= 64 else None,
            indices=(indices if indices is not None
                     and len(indices) <= 64 else None),
            rank=rank,
            total_rows=total, max_rows=max_rows, median_rows=median,
            ratio=round(ratio, 3), max_shard=max_shard,
            flagged=flagged,
        )

    @staticmethod
    def _skew_of(rows: List[int]):
        total = sum(rows)
        mx = max(rows)
        max_shard = rows.index(mx)
        median = quantile(sorted(float(r) for r in rows), 0.5)
        ratio = mx / max(median, 1.0)
        return ratio, max_shard, median, total

    def record_combine_input(self, op: str, inv: Optional[int],
                             in_rows: Optional[int], out_rows: int,
                             lowering: str = "host",
                             wide_columns: int = 0) -> None:
        """One producer task's map-side combine cardinality: rows INTO
        the boundary's combiner and rows out (~distinct keys for the
        full boundary once every producer reports). The executor calls
        this per combine-bearing task (exec/local.py); post-combine
        shuffle sizes alone understate cardinality by exactly the
        combine's collapse factor. The mesh executor calls it once a
        group with ``in_rows`` None — the rows the group's waves staged
        since the op's last boundary (``record_wave_staging``) — and
        says which ``lowering`` ran and how many 64-bit value columns
        it carried."""
        out_rows = max(0, int(out_rows))
        with self._lock:
            rec = self._op(op, inv)
            if in_rows is None:
                in_rows = rec.staged_rows
            in_rows = max(0, int(in_rows))
            rec.staged_rows = 0
            rec.combine_in_rows += in_rows
            rec.combine_out_rows += out_rows
            rec.combine_boundaries += 1
            rec.combine_lowering = lowering
            rec.combine_wide_columns = int(wide_columns)
        self._emit("bigslice:combineInput", op=op, inv=inv,
                   in_rows=in_rows, out_rows=out_rows)

    def record_join(self, op: str, inv: Optional[int],
                    probe_rows: int, build_rows: int,
                    matched_rows: int, lowering: str,
                    wide_columns: int = 0) -> None:
        """One wave of a lookup join (``JoinLookup``), from the wave's
        own signals: the rows of the probe and of the build side that
        reached the join, the probe rows that found their build row,
        which ``lowering`` joined them and how many 64-bit value
        columns the output carries. Sums since the session began, so a
        window reads them as deltas."""
        with self._lock:
            rec = self._op(op, inv)
            if rec.join is None:
                rec.join = {"waves": 0, "probe_rows": 0,
                            "build_rows": 0, "matched_rows": 0}
            j = rec.join
            j["waves"] += 1
            j["probe_rows"] += int(probe_rows)
            j["build_rows"] += int(build_rows)
            j["matched_rows"] += int(matched_rows)
            j["lowering"] = lowering
            j["wide_columns"] = int(wide_columns)

    def record_merge(self, op: str, inv: Optional[int], waves: int,
                     slots: int, slots_full: int,
                     rows_bound: int) -> None:
        """One cross-wave merge of a waved shuffle's outputs, from host
        integers: how many ``waves`` it concatenated, the ``slots`` it
        read and sorted over all devices, the waves' whole capacities
        (``slots_full``: what a merge at full capacity reads) and
        ``rows_bound``, the rows those waves can hold at most (a
        wave's fullest device's count times the devices). Sums since
        the session began, so a window reads them as deltas."""
        with self._lock:
            rec = self._op(op, inv)
            if rec.merge is None:
                rec.merge = {"merges": 0, "waves": 0, "slots": 0,
                             "slots_full": 0, "rows_bound": 0}
            m = rec.merge
            m["merges"] += 1
            m["waves"] += int(waves)
            m["slots"] += int(slots)
            m["slots_full"] += int(slots_full)
            m["rows_bound"] += int(rows_bound)

    def record_table(self, op: str, inv: Optional[int],
                     tables) -> None:
        """One dispatched wave of a group program that fills dense
        tables (``parallel/dense.py``'s table pass), from the host data
        its trace recorded: per table ``(slots, lowerings)``, one
        lowering a value column as ``dense.table_column_lowering``
        picked it. Sums since the session began, so a window reads
        them as deltas."""
        with self._lock:
            rec = self._op(op, inv)
            if rec.table is None:
                rec.table = {"waves": 0, "slots": 0,
                             "columns_compare": 0,
                             "columns_scatter": 0}
            t = rec.table
            t["waves"] += 1
            for slots, lowerings in tables:
                t["slots"] += int(slots)
                for how in lowerings:
                    t["columns_" + how] += 1

    def record_deadline(self, outcome: str, tenant: str = "",
                        deadline_s=None, source: str = "") -> None:
        """One deadline-ladder outcome (met / expired /
        rejected_admission / queue_timeout ...), attributed per tenant.
        The DeadlineStats holder is created lazily HERE: a process that
        never sees a deadline keeps ``hub.deadline is None`` and emits
        zero bigslice_deadline_* samples."""
        with self._lock:
            if self.deadline is None:
                self.deadline = DeadlineStats()
        self.deadline.record(outcome, tenant=tenant,
                             deadline_s=deadline_s, source=source)
        self._emit("bigslice:deadline", outcome=outcome,
                   tenant=tenant or None, deadline_s=deadline_s,
                   source=source or None)

    # The staging-breakdown phases an executor may report (the staging
    # fast path's read → decode → assemble → upload chain); unknown
    # keys are dropped so a buggy caller can't grow the record.
    STAGE_PHASES = ("read_s", "decode_s", "assemble_s", "upload_s")

    def record_wave_staging(self, op: str, inv: Optional[int],
                            wave: int, dur_s: float,
                            exposed_s: float,
                            breakdown: Optional[dict] = None,
                            ready: Optional[int] = None) -> None:
        """One wave's input staging: total duration, the portion the
        compute thread actually blocked on (== dur_s on the serial
        path; the wait for the prefetch workers on the pipelined path),
        and optionally the read/decode/assemble/upload breakdown of
        where the staging time went, with the rows the wave uploaded
        (``rows``) and read in place from a stored device output
        (``resident_rows``). A pipelined wave passes ``ready``,
        its ``stage_wait`` span's field of that name: ``stage_waits``
        counts them and ``stage_waits_ready`` those whose wave a
        prefetch worker had staged before the compute thread asked."""
        dur_s = max(0.0, float(dur_s))
        exposed_s = min(max(0.0, float(exposed_s)), dur_s)
        clean: Dict[str, float] = {}
        if breakdown:
            for k in self.STAGE_PHASES:
                v = breakdown.get(k)
                if v:
                    clean[k] = max(0.0, float(v))
        with self._lock:
            rec = self._op(op, inv)
            rec.staging_s += dur_s
            rec.exposed_s += exposed_s
            rec.staged_waves += 1
            uploaded = int((breakdown or {}).get("rows", 0))
            rec.staged_rows += uploaded
            rec.rows_staged += uploaded
            rec.rows_resident += int(
                (breakdown or {}).get("resident_rows", 0))
            rec.max_wave = max(rec.max_wave, int(wave))
            if ready is not None:
                rec.stage_waits += 1
                rec.stage_waits_ready += bool(ready)
            for k, v in clean.items():
                rec.stage_phases[k] = rec.stage_phases.get(k, 0.0) + v
        self._emit("bigslice:waveStaging", op=op, inv=inv, wave=wave,
                   ms=round(dur_s * 1e3, 3),
                   exposed_ms=round(exposed_s * 1e3, 3),
                   **{k[:-2] + "_ms": round(v * 1e3, 3)
                      for k, v in clean.items()})

    def record_wave_host(self, op: str, inv: Optional[int],
                         field: str, dur_s: float,
                         ready: Optional[int] = None,
                         enqueue_s: Optional[float] = None) -> None:
        """Host seconds of one wave's ``dispatch_s`` or ``settle_s``
        (the spans of those names), summed by op. A settle passes
        ``ready``, the span's field of that name: ``settles`` counts
        them and ``settles_ready`` those whose wave had finished. A
        dispatch passes ``enqueue_s``, the seconds of its ``enqueue``
        child (the jit call and the start of the signals' copy): what
        is left of ``dispatch_s`` is the executor's own."""
        with self._lock:
            rec = self._op(op, inv)
            host = rec.wave_host_s
            host[field] = host.get(field, 0.0) + max(0.0, float(dur_s))
            if ready is not None:
                rec.settles += 1
                rec.settles_ready += bool(ready)
            if enqueue_s is not None:
                host["enqueue_s"] = (host.get("enqueue_s", 0.0)
                                     + max(0.0, float(enqueue_s)))

    def record_prefetch_blocked(self, op: str, inv: Optional[int],
                                blocked_s: float,
                                overlapped: int = 0) -> None:
        """Once a pipelined group: the seconds its prefetch workers
        could begin no stage because the waves they had begun were not
        taken yet — the stagers waiting for the compute thread, summed
        over the workers — and ``stages_overlapped``, its stages that
        began while another stage of the group was under way (0 for a
        group with one worker)."""
        with self._lock:
            rec = self._op(op, inv)
            rec.prefetch_blocked_s = ((rec.prefetch_blocked_s or 0.0)
                                      + max(0.0, float(blocked_s)))
            rec.stages_overlapped += int(overlapped)

    def record_wave_compute(self, op: str, inv: Optional[int],
                            wave: int, dur_s: float) -> None:
        dur_s = max(0.0, float(dur_s))
        with self._lock:
            rec = self._op(op, inv)
            rec.compute_s += dur_s
            rec.max_wave = max(rec.max_wave, int(wave))
        self._emit("bigslice:waveRun", op=op, inv=inv, wave=wave,
                   ms=round(dur_s * 1e3, 3))

    def record_span(self, name: str, total_ns: int, self_ns: int,
                    nbytes: Optional[int] = None) -> None:
        """One closed span (utils/trace.span) into the session's table."""
        with self._span_lock:
            row = self._spans.get(name)
            if row is None:
                row = self._spans[name] = [0, 0, 0, None]
            row[0] += 1
            row[1] += total_ns
            row[2] += self_ns
            if nbytes is not None:
                row[3] = (row[3] or 0) + int(nbytes)

    def span_table(self) -> Dict[str, dict]:
        """``summary()["spans"]``: per span name ``count``, ``total_s``,
        ``self_s`` and, where the boundary counts them, ``bytes`` —
        sums since the session began."""
        with self._span_lock:
            rows = {k: list(v) for k, v in self._spans.items()}
        out = {}
        for name, (count, total_ns, self_ns, nbytes) in rows.items():
            out[name] = {"count": count, "total_s": total_ns * 1e-9,
                         "self_s": self_ns * 1e-9}
            if nbytes is not None:
                out[name]["bytes"] = nbytes
        return out

    # -- queries ----------------------------------------------------------

    def skew_of_op(self, op: str) -> Optional[dict]:
        """One op's CURRENT shuffle-skew verdict (the adaptive
        planner's hot-shard signal, exec/adaptive.py): ratio, hot
        shard, totals and the flag, from the accumulated per-partition
        row vector. None before the op's first shuffle boundary."""
        with self._lock:
            rec = self._ops.get(op)
            if rec is None or not rec.part_rows:
                return None
            ratio, max_shard, median, total = self._skew_of(
                rec.part_rows
            )
            out = {
                "ratio": ratio,
                "max_shard": max_shard,
                "median_rows": median,
                "total_rows": total,
                "max_rows": rec.part_rows[max_shard],
                "flagged": (total >= self.skew_min_rows
                            and ratio >= self.skew_ratio),
            }
            if rec.combine_boundaries:
                # True pre-combine cardinality at the op's map-side
                # combine boundary (record_combine_input): input rows
                # and the distinct-key ratio (rows out / rows in; 1.0
                # = all-distinct, small = heavy collapse).
                out["combine_input_rows"] = rec.combine_in_rows
                out["distinct_key_ratio"] = (
                    rec.combine_out_rows
                    / max(1, rec.combine_in_rows)
                )
            return out

    def live_stragglers(self) -> List[dict]:
        """RUNNING tasks whose elapsed time already exceeds the
        straggler threshold of their op's completed siblings."""
        now = time.monotonic()
        out = []
        with self._lock:
            for op, rec in self._ops.items():
                if len(rec.durations) < self.straggler_min_siblings:
                    continue
                p50 = quantile(sorted(rec.durations), 0.5)
                floor = max(self.straggler_factor * p50,
                            self.straggler_min_secs)
                for key, start in rec.running.items():
                    elapsed = now - start
                    if elapsed > floor:
                        out.append({
                            "op": op, "task": key,
                            "shard": rec.shards.get(key, -1),
                            "elapsed_s": round(elapsed, 3),
                            "p50_s": round(p50, 6),
                        })
        out.sort(key=lambda d: -d["elapsed_s"])
        return out

    def task_durations(self) -> List[float]:
        """Every completed (OK) task duration across all ops, sorted —
        the raw distribution behind the per-op p50/p90 rollups. The
        adaptive A/B bench and CI smoke compute tail quantiles (p99)
        from this to judge what speculation bought."""
        with self._lock:
            out: List[float] = []
            for rec in self._ops.values():
                out.extend(rec.durations)
        out.sort()
        return out

    def summary(self) -> dict:
        """The ``Session.telemetry_summary()`` payload: per-op skew /
        straggler / wave / exchange sections plus session-wide
        rollups."""
        # Device plane first (its own lock): the per-op ``exchange``
        # blocks below join its collective plan to this hub's rows a
        # device.
        try:
            device = self.device.summary()
        except Exception:
            device = {}
        exchanged = device.get("exchange", {})
        with self._lock:
            ops = {}
            total_staging = total_hidden = 0.0
            flagged_ops = []
            straggler_total = 0
            for op, rec in self._ops.items():
                entry: dict = {"inv": rec.inv}
                if rec.durations:
                    ds = sorted(rec.durations)
                    entry["tasks"] = {
                        "n": len(ds),
                        "p50_s": round(quantile(ds, 0.5), 6),
                        "p90_s": round(quantile(ds, 0.9), 6),
                        "max_s": round(ds[-1], 6),
                        "total_s": round(sum(ds), 6),
                    }
                if rec.stragglers:
                    entry["stragglers"] = list(rec.stragglers)
                    straggler_total += len(rec.stragglers)
                if rec.part_rows:
                    ratio, max_shard, median, total = self._skew_of(
                        rec.part_rows
                    )
                    flagged = (total >= self.skew_min_rows
                               and ratio >= self.skew_ratio)
                    nonempty = sorted(
                        float(r) for r in rec.part_rows if r > 0
                    )
                    entry["skew"] = {
                        "rows": list(rec.part_rows),
                        "bytes": list(rec.part_bytes),
                        "total_rows": total,
                        "median_rows": median,
                        "ratio": round(ratio, 3),
                        "max_shard": max_shard,
                        "flagged": flagged,
                        "boundaries": rec.shuffle_boundaries,
                        # Per-shard key-count distribution from the
                        # exchange manifest vector — the one signal the
                        # adaptive planner and the future kernel
                        # selector (ROADMAP item 4) both read.
                        "per_shard": {
                            "n": len(rec.part_rows),
                            "nonempty": len(nonempty),
                            "p50_rows": round(
                                quantile(nonempty, 0.5), 1
                            ) if nonempty else 0.0,
                            "p90_rows": round(
                                quantile(nonempty, 0.9), 1
                            ) if nonempty else 0.0,
                            "max_rows": int(max(rec.part_rows)),
                            "mean_rows": round(
                                total / max(1, len(rec.part_rows)), 1
                            ),
                        },
                    }
                    if flagged:
                        flagged_ops.append(op)
                if rec.staged_waves or rec.max_wave >= 0:
                    hidden = max(0.0, rec.staging_s - rec.exposed_s)
                    eff = (hidden / rec.staging_s
                           if rec.staging_s > 0 else 0.0)
                    entry["waves"] = {
                        "n_waves": rec.max_wave + 1,
                        "staged": rec.staged_waves,
                        "staging_s": round(rec.staging_s, 6),
                        "exposed_s": round(rec.exposed_s, 6),
                        "hidden_s": round(hidden, 6),
                        "compute_s": round(rec.compute_s, 6),
                        "overlap_efficiency": round(eff, 4),
                        "phases": dict(rec.phase_counts),
                    }
                    if rec.stage_phases:
                        entry["waves"]["staging_breakdown"] = {
                            k: round(v, 6)
                            for k, v in rec.stage_phases.items()
                        }
                    for k, v in rec.wave_host_s.items():
                        entry["waves"][k] = round(v, 6)
                    if rec.settles:
                        entry["waves"]["settles"] = rec.settles
                        entry["waves"]["settles_ready"] = (
                            rec.settles_ready)
                    if rec.stage_waits:
                        entry["waves"]["stage_waits"] = rec.stage_waits
                        entry["waves"]["stage_waits_ready"] = (
                            rec.stage_waits_ready)
                    if rec.prefetch_blocked_s is not None:
                        entry["waves"]["prefetch_blocked_s"] = round(
                            rec.prefetch_blocked_s, 6)
                        entry["waves"]["stages_overlapped"] = (
                            rec.stages_overlapped)
                    total_staging += rec.staging_s
                    total_hidden += hidden
                if rec.rows_resident or rec.rows_staged:
                    # The rows the op's waves read in place from a
                    # stored device output, and those they staged.
                    entry["input"] = {
                        "resident_rows": rec.rows_resident,
                        "staged_rows": rec.rows_staged,
                    }
                if rec.combine_boundaries:
                    # The op's map-side combine (record_combine_input).
                    entry["combine"] = {
                        "boundaries": rec.combine_boundaries,
                        "rows_in": rec.combine_in_rows,
                        "rows_out": rec.combine_out_rows,
                        "lowering": rec.combine_lowering,
                        "wide_columns": rec.combine_wide_columns,
                    }
                if rec.join is not None:
                    # The op's lookup join (record_join).
                    entry["join"] = dict(rec.join)
                if rec.merge is not None:
                    # The op's cross-wave merges (record_merge).
                    entry["merge"] = dict(rec.merge)
                if rec.table is not None:
                    # The dense tables its waves filled (record_table).
                    entry["table"] = dict(rec.table)
                ex = exchanged.get(op)
                if ex and ex["ici_messages"] + ex["dcn_messages"]:
                    # A shuffle whose collective moved something (a
                    # mesh of one exchanges nothing and has no block).
                    entry["exchange"] = {
                        **{k: ex[k] for k in (
                            "waves", "ici_bytes", "ici_messages",
                            "slack", "retries")},
                        "recv_rows": list(rec.part_rows),
                    }
                ops[op] = entry
            states: Dict[str, int] = {}
            for (_, st), n in self._state_counts.items():
                states[st] = states.get(st, 0) + n
            out = {
                "ops": ops,
                "task_states": states,
                "skew_flagged_ops": sorted(flagged_ops),
                "straggler_total": straggler_total,
                "overlap_efficiency": round(
                    total_hidden / total_staging, 4
                ) if total_staging > 0 else None,
            }
            recovery = self._recovery_summary_locked()
            if recovery is not None:
                out["recovery"] = recovery
            if self._drain_timeouts:
                out["drain"] = {
                    "timeouts": self._drain_timeouts,
                    "wedged": list(self._drain_wedged),
                }
        out["spans"] = self.span_table()
        plan = faultinject.active_plan()
        if plan is not None:
            snap = plan.snapshot()
            out["chaos"] = {
                "seed": snap["seed"],
                "spec": snap["spec"],
                "injected": snap["injected"],
                "by_kind": snap["by_kind"],
            }
        # Device plane: compile attribution, HBM watermarks, donation
        # effectiveness (utils/devicetelemetry.py). Always present so
        # consumers need no existence dance; empty sub-dicts mean "no
        # device work observed".
        out["device"] = device
        # Cross-Session compiled-program cache (serve/programcache.py):
        # process-scope, so the numbers cover every session this
        # process ever ran — the serving plane's zero-recompile
        # evidence. Always present (zeros before any program ran).
        try:
            from bigslice_tpu.serve.programcache import (
                program_cache_stats,
            )

            out["program_cache"] = program_cache_stats()
        except Exception:
            out["program_cache"] = {}
        # Cross-request result cache (ops/cache.py writethrough tiers):
        # process-scope hit/miss counts — serving cache effectiveness.
        try:
            from bigslice_tpu.ops.cache import result_cache_counts

            out["result_cache"] = result_cache_counts()
        except Exception:
            out["result_cache"] = {}
        serving = self.serving
        if serving is not None:
            try:
                out["serving"] = serving.summary()
            except Exception:
                out["serving"] = {}
        adaptive = self.adaptive
        if adaptive is not None:
            try:
                out["adaptive"] = adaptive.summary()
            except Exception:
                out["adaptive"] = {}
        kselect = self.kernel_select
        if kselect is not None:
            try:
                out["kernel_select"] = kselect.summary()
            except Exception:
                out["kernel_select"] = {}
        coded = self.coded
        if coded is not None:
            try:
                out["coded"] = coded.summary()
            except Exception:
                out["coded"] = {}
        deadline = self.deadline
        if deadline is not None:
            try:
                out["deadline"] = deadline.summary()
            except Exception:
                out["deadline"] = {}
        return out

    def snapshot(self, rank: Optional[int] = None,
                 nranks: Optional[int] = None) -> dict:
        """This process's telemetry as a serializable, rank-tagged,
        *mergeable* snapshot — the fleet plane's exchange format
        (utils/fleettelemetry.py). Unlike ``summary()`` (rendered for
        humans, quantiles from raw sample lists), every field here
        merges losslessly across ranks: counters add, per-partition
        vectors add elementwise, maxima take max, and task/recovery
        durations ride fixed-bin histograms
        (``fleettelemetry.DUR_BUCKETS_S``) whose merged quantiles are
        within one bin of the raw-sample values."""
        from bigslice_tpu.utils import fleettelemetry as fleet_mod

        if rank is None:
            rank = fleet_mod.process_rank()
        if nranks is None:
            nranks = fleet_mod.process_count()
        with self._lock:
            ops: Dict[str, dict] = {}
            for op, rec in self._ops.items():
                ops[op] = {
                    "inv": rec.inv,
                    "durations": fleet_mod.duration_hist(
                        rec.durations),
                    "stragglers": list(rec.stragglers)[:16],
                    "part_rows": list(rec.part_rows),
                    "part_bytes": list(rec.part_bytes),
                    "boundaries": rec.shuffle_boundaries,
                    "rows_hist": list(rec.rows_hist),
                    "rows_hist_sum": rec.rows_hist_sum,
                    "rows_hist_count": rec.rows_hist_count,
                    "staging_s": rec.staging_s,
                    "exposed_s": rec.exposed_s,
                    "compute_s": rec.compute_s,
                    "staged_waves": rec.staged_waves,
                    "max_wave": rec.max_wave,
                    "phase_counts": dict(rec.phase_counts),
                    "stage_phases": dict(rec.stage_phases),
                }
            states: Dict[str, int] = {}
            for (_, st), n in self._state_counts.items():
                states[st] = states.get(st, 0) + n
            recovery = {
                "recovered": dict(self._recovered),
                "fatal": dict(self._recovery_fatal),
                "pending": len(self._recovery_pending),
                "latency": fleet_mod.duration_hist(
                    [v for ls in self._recovery_lat.values()
                     for v in ls]
                ),
            }
            drain_timeouts = self._drain_timeouts
        doc = {
            "schema": fleet_mod.SNAPSHOT_SCHEMA,
            "rank": int(rank),
            "nranks": int(nranks),
            "ts": time.time(),
            "ops": ops,
            "task_states": states,
            "recovery": recovery,
            "drain_timeouts": drain_timeouts,
        }
        try:
            doc["device"] = self.device.snapshot()
        except Exception:  # telemetry must never break the run
            doc["device"] = {}
        return doc

    @staticmethod
    def _lat_stats(lats: List[float]) -> dict:
        ls = sorted(lats)
        return {
            "n": len(ls),
            "p50_s": round(quantile(ls, 0.5), 6),
            "p90_s": round(quantile(ls, 0.9), 6),
            "max_s": round(ls[-1], 6) if ls else 0.0,
        }

    def _recovery_summary_locked(self) -> Optional[dict]:
        if not (self._recovered or self._recovery_fatal
                or self._recovery_pending):
            return None
        by_site = {}
        for site in sorted(set(self._recovered)
                           | set(self._recovery_fatal)):
            entry = {
                "recovered": self._recovered.get(site, 0),
                "fatal": self._recovery_fatal.get(site, 0),
            }
            lats = self._recovery_lat.get(site)
            if lats:
                entry["latency"] = self._lat_stats(lats)
            by_site[site] = entry
        all_lats = [v for ls in self._recovery_lat.values()
                    for v in ls]
        out = {
            "recovered_total": sum(self._recovered.values()),
            "fatal_total": sum(self._recovery_fatal.values()),
            "pending": len(self._recovery_pending),
            "by_site": by_site,
        }
        if all_lats:
            out["latency"] = self._lat_stats(all_lats)
        return out

    def status_lines(self, limit: int = 4) -> List[str]:
        """Live annotations for the status display: flagged skew and
        current/flagged stragglers, worst first, bounded — plus a
        recovery-ladder line when losses were seen."""
        lines: List[str] = []
        with self._lock:
            rec_total = sum(self._recovered.values())
            fatal_total = sum(self._recovery_fatal.values())
            pending = len(self._recovery_pending)
            if rec_total or fatal_total or pending:
                lines.append(
                    f"  recovery: {rec_total} recovered, "
                    f"{fatal_total} fatal, {pending} pending"
                )
            skews = []
            for op, rec in self._ops.items():
                if rec.skew_flagged:
                    skews.append((rec.worst_ratio, op,
                                  rec.worst_max_shard))
            for ratio, op, shard in sorted(skews, reverse=True)[:limit]:
                lines.append(
                    f"  skew {op}: ratio {ratio:.1f} (hot shard {shard})"
                )
            flagged = [
                (s["duration_s"], s["task"], s["p50_s"])
                for rec in self._ops.values() for s in rec.stragglers
            ]
        for dur, task, p50 in sorted(flagged, reverse=True)[:limit]:
            lines.append(
                f"  straggler {task}: {dur:.2f}s vs p50 {p50:.2f}s"
            )
        for s in self.live_stragglers()[:limit]:
            lines.append(
                f"  straggler (live) {s['task']}: {s['elapsed_s']:.2f}s"
                f" vs p50 {s['p50_s']:.2f}s"
            )
        try:
            hbm = self.device.status_line()
            if hbm:
                lines.append(hbm)
        except Exception:
            pass
        return lines

    # -- Prometheus export ------------------------------------------------

    def prometheus_text(self) -> str:
        """The hub's signals in Prometheus text exposition format
        (text/plain; version=0.0.4) — counters, gauges, a per-op task
        duration summary, and a per-op shuffle-size histogram — plus
        the framework's internal stats.Map counters and host RSS."""
        from bigslice_tpu.utils import resources as resources_mod
        from bigslice_tpu.utils import stats as stats_mod

        out: List[str] = []

        def metric(name, help_, type_):
            out.append(f"# HELP {name} {help_}")
            out.append(f"# TYPE {name} {type_}")

        def line(name, labels, value):
            if labels:
                lab = ",".join(
                    f'{k}="{_escape_label(v)}"'
                    for k, v in labels.items()
                )
                out.append(f"{name}{{{lab}}} {value}")
            else:
                out.append(f"{name} {value}")

        with self._lock:
            states = sorted(self._state_counts.items())
            ops = {op: rec for op, rec in self._ops.items()}

            metric("bigslice_task_state_total",
                   "Task state transitions observed, by op and state.",
                   "counter")
            for (op, st), n in states:
                line("bigslice_task_state_total",
                     {"op": op, "state": st}, n)

            metric("bigslice_task_duration_seconds",
                   "Completed task durations per op.", "summary")
            for op, rec in ops.items():
                if not rec.durations:
                    continue
                ds = sorted(rec.durations)
                for q in (0.5, 0.9, 0.99):
                    line("bigslice_task_duration_seconds",
                         {"op": op, "quantile": str(q)},
                         f"{quantile(ds, q):.6f}")
                line("bigslice_task_duration_seconds_sum", {"op": op},
                     f"{sum(ds):.6f}")
                line("bigslice_task_duration_seconds_count", {"op": op},
                     len(ds))

            metric("bigslice_op_straggler_total",
                   "Tasks flagged as stragglers "
                   "(duration > factor * sibling p50).", "counter")
            for op, rec in ops.items():
                if rec.stragglers:
                    line("bigslice_op_straggler_total", {"op": op},
                         len(rec.stragglers))

            metric("bigslice_op_skew_ratio",
                   "Worst max/median per-shard row ratio observed at "
                   "this op's shuffle boundary.", "gauge")
            for op, rec in ops.items():
                if rec.part_rows:
                    line("bigslice_op_skew_ratio", {"op": op},
                         f"{rec.worst_ratio:.4f}")
            metric("bigslice_op_skew_flagged",
                   "1 when the op's shuffle skew exceeded the flag "
                   "threshold.", "gauge")
            for op, rec in ops.items():
                if rec.part_rows:
                    line("bigslice_op_skew_flagged", {"op": op},
                         int(rec.skew_flagged))

            metric("bigslice_shuffle_partition_rows",
                   "Per-shard row counts observed at shuffle "
                   "boundaries.", "histogram")
            for op, rec in ops.items():
                if rec.rows_hist_count == 0:
                    continue
                cum = 0
                for bi, le in enumerate(ROWS_BUCKETS):
                    cum += rec.rows_hist[bi]
                    line("bigslice_shuffle_partition_rows_bucket",
                         {"op": op, "le": str(le)}, cum)
                cum += rec.rows_hist[-1]
                line("bigslice_shuffle_partition_rows_bucket",
                     {"op": op, "le": "+Inf"}, cum)
                line("bigslice_shuffle_partition_rows_sum", {"op": op},
                     rec.rows_hist_sum)
                line("bigslice_shuffle_partition_rows_count",
                     {"op": op}, rec.rows_hist_count)

            metric("bigslice_wave_overlap_efficiency",
                   "Fraction of wave staging time hidden behind "
                   "compute by the prefetch pipeline (1.0 = fully "
                   "hidden, 0.0 = serial).", "gauge")
            for op, rec in ops.items():
                if rec.staged_waves:
                    hidden = max(0.0, rec.staging_s - rec.exposed_s)
                    eff = (hidden / rec.staging_s
                           if rec.staging_s > 0 else 0.0)
                    line("bigslice_wave_overlap_efficiency", {"op": op},
                         f"{eff:.4f}")

            metric("bigslice_wave_staging_seconds_total",
                   "Cumulative wave input staging time, split into "
                   "compute-exposed and prefetch-hidden.", "counter")
            for op, rec in ops.items():
                if rec.staged_waves:
                    line("bigslice_wave_staging_seconds_total",
                         {"op": op, "kind": "exposed"},
                         f"{rec.exposed_s:.6f}")
                    line("bigslice_wave_staging_seconds_total",
                         {"op": op, "kind": "hidden"},
                         f"{max(0.0, rec.staging_s - rec.exposed_s):.6f}")

            metric("bigslice_wave_staging_phase_seconds_total",
                   "Cumulative wave staging time by phase "
                   "(read/decode/assemble/upload — why staging is "
                   "slow).", "counter")
            for op, rec in ops.items():
                for ph, v in sorted(rec.stage_phases.items()):
                    line("bigslice_wave_staging_phase_seconds_total",
                         {"op": op, "phase": ph[:-2]}, f"{v:.6f}")

            metric("bigslice_wave_compute_seconds_total",
                   "Cumulative wave compute (dispatch to settle) time.",
                   "counter")
            for op, rec in ops.items():
                if rec.compute_s > 0:
                    line("bigslice_wave_compute_seconds_total",
                         {"op": op}, f"{rec.compute_s:.6f}")

            metric("bigslice_wave_phase_total",
                   "Wave pipeline phase events per op "
                   "(wavePrefetch/waveCompute).", "counter")
            for op, rec in ops.items():
                for phase, n in sorted(rec.phase_counts.items()):
                    line("bigslice_wave_phase_total",
                         {"op": op, "phase": phase}, n)

            # -- recovery ladder / chaos plane ------------------------
            metric("bigslice_task_recovered_total",
                   "Lost tasks the recovery ladder brought back to OK, "
                   "by attributed fault site ('organic' = no chaos "
                   "marker in the failure chain).", "counter")
            for site, n in sorted(self._recovered.items()):
                line("bigslice_task_recovered_total", {"site": site}, n)
            metric("bigslice_task_recovery_fatal_total",
                   "Lost tasks that turned fatal (ERR) instead of "
                   "recovering, by attributed fault site.", "counter")
            for site, n in sorted(self._recovery_fatal.items()):
                line("bigslice_task_recovery_fatal_total",
                     {"site": site}, n)
            all_lats = sorted(
                v for ls in self._recovery_lat.values() for v in ls
            )
            if all_lats:
                metric("bigslice_task_recovery_seconds",
                       "Time from first loss to recovered-OK per task.",
                       "summary")
                for q in (0.5, 0.9, 0.99):
                    line("bigslice_task_recovery_seconds",
                         {"quantile": str(q)},
                         f"{quantile(all_lats, q):.6f}")
                line("bigslice_task_recovery_seconds_sum", {},
                     f"{sum(all_lats):.6f}")
                line("bigslice_task_recovery_seconds_count", {},
                     len(all_lats))
            metric("bigslice_drain_timeout_total",
                   "Aborted-evaluation drains that expired with tasks "
                   "still in flight.", "counter")
            line("bigslice_drain_timeout_total", {},
                 self._drain_timeouts)

        # -- device plane (compile / HBM / donation gauges) -----------
        try:
            self.device.prometheus_lines(metric, line)
        except Exception:
            pass

        # -- cross-Session program cache (serve/programcache.py) ------
        try:
            from bigslice_tpu.serve.programcache import (
                program_cache_stats,
            )

            pc = program_cache_stats()
            metric("bigslice_program_cache_total",
                   "Cross-Session compiled-program cache outcomes "
                   "(process scope; serve/programcache.py).",
                   "counter")
            for outcome, key in (("hit", "hits"), ("miss", "misses"),
                                 ("insert", "inserts"),
                                 ("evict", "evictions"),
                                 ("discard", "discards")):
                line("bigslice_program_cache_total",
                     {"outcome": outcome}, pc.get(key, 0))
            metric("bigslice_program_cache_entries",
                   "Compiled executables currently held by the "
                   "cross-Session program cache.", "gauge")
            line("bigslice_program_cache_entries", {},
                 pc.get("entries", 0))
            metric("bigslice_program_cache_compile_seconds_saved_total",
                   "XLA compile wall time the cross-Session program "
                   "cache spared fresh sessions.", "counter")
            line("bigslice_program_cache_compile_seconds_saved_total",
                 {}, f"{pc.get('compile_s_saved', 0.0):.6f}")
        except Exception:
            pass

        # -- cross-request result cache (ops/cache.py) ----------------
        try:
            from bigslice_tpu.ops.cache import result_cache_counts

            rc = result_cache_counts()
            metric("bigslice_result_cache_total",
                   "Per-shard result-cache reads by outcome (hit = "
                   "served from cache, miss = computed + written "
                   "through; ops/cache.py).", "counter")
            for outcome, n in sorted(rc.items()):
                line("bigslice_result_cache_total",
                     {"outcome": outcome}, n)
        except Exception:
            pass

        # -- serving plane (serve/server.py per-tenant stats) ---------
        serving = self.serving
        if serving is not None:
            try:
                serving.prometheus_lines(metric, line)
            except Exception:
                pass

        # -- adaptive plane (exec/adaptive.py decision attribution) ---
        adaptive = self.adaptive
        if adaptive is not None:
            try:
                adaptive.prometheus_lines(metric, line)
            except Exception:
                pass

        # -- kernel-selection plane (parallel/kernelselect.py) --------
        kselect = self.kernel_select
        if kselect is not None:
            try:
                kselect.prometheus_lines(metric, line)
            except Exception:
                pass

        # -- coded k-of-n plane (exec/codedplan.py) -------------------
        coded = self.coded
        if coded is not None:
            try:
                coded.prometheus_lines(metric, line)
            except Exception:
                pass

        # -- deadline ladder (exec/evaluate.py / serve/server.py) -----
        deadline = self.deadline
        if deadline is not None:
            try:
                deadline.prometheus_lines(metric, line)
            except Exception:
                pass

        plan = faultinject.active_plan()
        if plan is not None:
            snap = plan.snapshot()
            metric("bigslice_fault_injected_total",
                   "Chaos-plane injected faults by site and kind "
                   "(utils/faultinject.py).", "counter")
            for site in sorted(snap["by_kind"]):
                for kind, n in sorted(snap["by_kind"][site].items()):
                    line("bigslice_fault_injected_total",
                         {"site": site, "kind": kind}, n)

        metric("bigslice_stat_total",
               "Framework-internal stats.Map counters.", "counter")
        for name, v in sorted(stats_mod.DEFAULT.snapshot().items()):
            line("bigslice_stat_total", {"name": name}, v)

        rss = resources_mod.host_rss_bytes()
        if rss is not None:
            metric("bigslice_host_rss_bytes",
                   "Driver process resident set size.", "gauge")
            line("bigslice_host_rss_bytes", {}, rss)
        out.append("")
        return "\n".join(out)
