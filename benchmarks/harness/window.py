"""One run of one cell: set-up, the measured window, then the checks.

Set-up is everything from the start of the process to the start of the
window: the compile cache is placed, the cell's data is made from the
seed, the session starts, the job runs once (which compiles, or loads
the cache), a settling job where the executor discovered a capacity,
and a warm job — held to the evidence that its work stayed on the mesh.

The window is the closed loop a batch user makes: the next job starts
when the last has returned, while the window is open; it closes when
the last started job returns. Every job builds fresh slices. Answers
are kept and compared after the window, when the session is shut down
and the device's memory peak has been read."""

from __future__ import annotations

import dataclasses
import gc
import random
import time

from . import compare, evidence

#: Window jobs whose FULL answer is held back on the device and
#: compared after the window (besides the set-up jobs').
FULL_CHECKS = 3
#: With ``--trace 1``: the window's jobs 1 and 2 are traced.
TRACE_FROM_JOB, TRACE_JOBS = 1, 2


@dataclasses.dataclass
class JobRecord:
    index: int           # -1, -2, ...: set-up jobs; 0..: window jobs
    start: float
    end: float
    spans: list          # (name, start, end) on time.perf_counter
    cpu_s: float = 0.0   # CPU seconds of the whole process meanwhile
    answers: dict = None
    error: str = ""
    job: object = None   # kept only while it holds a late answer

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class WindowResult:
    setup_s: float
    first_job_s: float
    jobs: list                 # window JobRecords, in order
    setup_jobs: list
    window_s: float
    work: dict                 # pipeline.work(): rows and bytes a job
    window_compiles: dict
    traced: object             # (first index, count, trace dir) or None
    telemetry_before: dict
    telemetry_after: dict
    counters: dict
    memory_peak_bytes: object
    checks: dict               # name -> {"value": n, "limit": n}
    notes: dict

    @property
    def correct(self) -> bool:
        return all(c["value"] <= c["limit"] for c in self.checks.values())


def open_session(devices):
    """The session of ``sliceconfig.make_session`` with the mesh
    executor, on exactly the cell's chips (a 1-D mesh)."""
    import numpy as np
    from jax.sharding import Mesh

    from bigslice_tpu.exec.meshexec import MeshExecutor
    from bigslice_tpu.exec.session import Session

    mesh = Mesh(np.array(devices), ("shards",))
    return Session(executor=MeshExecutor(mesh))


def run_job(pipeline, sess, data, index: int, keep: bool,
            inspect=None) -> JobRecord:
    """One job, step by step under the harness's own spans (on the
    profiler's clock too, when a trace is being taken): the pipeline's
    own steps, then ``discard`` — every job frees what it stored, as
    upstream ``Discard`` does; a job with ``keep`` spares only what its
    late answer needs. ``inspect(job)`` looks at the job's stored
    Results before they are freed."""
    import jax

    spans = []
    start, cpu0 = time.perf_counter(), time.process_time()
    job = pipeline.Job(sess, data, keep)
    rec = JobRecord(index=index, start=start, end=start, spans=spans)
    steps = list(job.steps())
    if inspect is not None:
        steps.append(("inspect", lambda: inspect(job)))
    steps.append(("discard", job.discard))
    try:
        for name, step in steps:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(f"bench:{name}"):
                step()
            spans.append((name, t0, time.perf_counter()))
        rec.answers = job.answers
    except Exception as e:  # noqa: BLE001 — a failed job is counted
        import traceback

        traceback.print_exc()
        rec.error = f"{type(e).__name__}: {e}"[:400]
    rec.end = time.perf_counter()
    rec.cpu_s = time.process_time() - cpu0
    if keep and not rec.error:
        rec.job = job
    return rec


def full_collection_recorder(into: list):
    """A ``gc.callbacks`` entry that records CPython's full (generation
    2) collections as ``(perf_counter at start, seconds)``: each stops
    the job it falls into for tens of milliseconds, at a point the
    allocation count fixes — the same job of every run."""
    started = []

    def on_gc(phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            started.append(time.perf_counter())
        elif started:
            t0 = started.pop()
            into.append((t0, time.perf_counter() - t0))

    return on_gc


def sample_kept(seed: int, expected_jobs: int, count: int) -> set:
    """Window jobs whose FULL answer is held back on the device and
    compared after the window: drawn from the seed, among the jobs the
    window is expected to hold."""
    pool = list(range(max(1, expected_jobs)))
    return set(random.Random(seed).sample(pool, min(count, len(pool))))


@dataclasses.dataclass
class _Driven:
    """What the session part of a run leaves behind."""

    setup_jobs: list
    jobs: list
    setup_s: float
    window_s: float
    window_compiles: dict
    traced: object
    telemetry_before: dict
    telemetry_after: dict
    counters: dict
    work: dict
    memory_peak_bytes: object


def _drive(cell, data, seed, seconds, trace_dir, t_process, devices,
           platform, log, notes, held) -> _Driven:
    """Set-up, window and held-back answers, inside one session."""
    import jax

    pipeline, cfg = cell.pipeline, cell.cfg
    meter = evidence.CompileMeter.get()
    sess = open_session(devices)
    ex = sess.executor
    try:
        # -------------------------------------------------------- set-up
        mark = meter.mark()
        first = run_job(pipeline, sess, data, -1, keep=True)
        setup_jobs = [first]
        notes["first_job"] = meter.since(mark)
        if not first.error and (ex._cogroup_caps or ex._slack_memo):
            # The first job DISCOVERED a capacity or a slack wave by
            # wave; one settling job reaches the shapes every later job
            # runs at (chip_smoke.cold_warm).
            setup_jobs.append(run_job(pipeline, sess, data, -2, False))
            notes["settled"] = True

        def warm_evidence(job):
            # A job that exposes no Result is held to the ladders and
            # ops-on-mesh checks after the window alone.
            if job.results:
                notes["warm_job_evidence"] = held(
                    "warm job", lambda: evidence.device_evidence(
                        sess, job.results, platform))

        warm = run_job(pipeline, sess, data, -3, True, warm_evidence)
        setup_jobs.append(warm)
        notes["lowering"] = held("lowering", lambda: pipeline.lowering(
            sess, evidence, platform))
        expected = max(1, int(seconds / max(warm.seconds, 1e-3)))
        kept = sample_kept(seed, expected, FULL_CHECKS)
        tele_before = sess.telemetry_summary()
        trace_first, trace_count = TRACE_FROM_JOB, TRACE_JOBS
        log({"phase": "setup", "make_data_s": notes["make_data_s"],
             "first_job_seconds": first.seconds,
             "warm_job_seconds": warm.seconds,
             "first_job_compiles": notes["first_job"],
             "expected_jobs": expected, "kept": sorted(kept),
             "lowering": notes["lowering"]})

        # ---------------------------------------------------- the window
        jobs, t_trace, traced = [], None, None

        def stop_trace():
            seconds_traced = time.perf_counter() - t_trace
            jax.profiler.stop_trace()
            return (trace_first, len(jobs) - trace_first, seconds_traced)

        mark = meter.mark()
        collections = []
        on_gc = full_collection_recorder(collections)
        gc.callbacks.append(on_gc)
        window_start = time.perf_counter()
        setup_s = window_start - t_process
        while not jobs or time.perf_counter() - window_start < seconds:
            i = len(jobs)
            if trace_dir and i == trace_first:
                # Device ops and the harness's own spans only: the
                # Python tracer would record every call of a host-bound
                # job's line loop and slow it many times over.
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=options)
                t_trace = time.perf_counter()
            jobs.append(run_job(pipeline, sess, data, i, i in kept))
            if t_trace is not None and traced is None \
                    and len(jobs) == trace_first + trace_count:
                traced = stop_trace()
            if len(jobs) >= 3 and all(j.error for j in jobs[-3:]):
                break  # nothing works: do not spin the window out
        if t_trace is not None and traced is None:
            traced = stop_trace()
        window_s = time.perf_counter() - window_start
        window_compiles = meter.since(mark)
        gc.callbacks.remove(on_gc)
        notes["full_gc"] = [[round(t - window_start, 3), round(d, 4)]
                            for t, d in collections]

        # ------------------------------------- after the window has closed
        tele_after = sess.telemetry_summary()
        peak = device_memory_peak(devices)
        held("ladders", lambda: evidence.ladders_silent(sess))
        held("ops on mesh", lambda: evidence.require_ops_on_mesh(
            tele_after, pipeline.MESH_OPS))
        for rec in setup_jobs + jobs:
            if rec.job is not None:
                try:
                    rec.answers = {**rec.answers, **rec.job.late_answers()}
                except Exception as e:  # noqa: BLE001
                    rec.error = f"late answer: {type(e).__name__}: {e}"
                rec.job = None
        return _Driven(
            setup_jobs=setup_jobs, jobs=jobs, setup_s=setup_s,
            window_s=window_s, window_compiles=window_compiles,
            traced=traced, telemetry_before=tele_before,
            telemetry_after=tele_after,
            counters=pipeline.counters(data),
            work=pipeline.work(cfg, data), memory_peak_bytes=peak)
    finally:
        sess.shutdown()


def run_window(cell, seed: int, seconds: float, trace_dir, t_process,
               devices, platform: str, log) -> WindowResult:
    pipeline, cfg, traffic = cell.pipeline, cell.cfg, cell.traffic
    if traffic.get("loop") != "closed" or traffic.get("clients") != 1:
        raise NotImplementedError(
            "the generator drives a closed loop of one client; got "
            f"{traffic}")
    notes, off_mesh = {}, []

    def held(what, check):
        try:
            return check()
        except evidence.EvidenceFailed as e:
            off_mesh.append(f"{what}: {e}")
            log({"evidence_failed": what, "why": str(e)[:600]})
            return None

    t0 = time.perf_counter()
    data = pipeline.make_data(cfg, seed)
    notes["make_data_s"] = time.perf_counter() - t0
    try:
        d = _drive(cell, data, seed, seconds, trace_dir, t_process,
                   devices, platform, log, notes, held)
        # The plain reference, once, after the program's state is freed.
        t0 = time.perf_counter()
        want = pipeline.reference(cfg, data)
        notes["reference_s"] = time.perf_counter() - t0
    finally:
        pipeline.close(data)
    every = d.setup_jobs + d.jobs
    tols = cell.tolerances
    wrong = rows = compared = 0
    margin = {}
    for rec in every:
        if rec.error:
            continue
        w, r = compare.compare_answers(rec.answers, want, tols)
        for table, m in compare.margins(rec.answers, want, tols).items():
            margin[table] = max(m, margin.get(table, 0.0))
        if w:
            rec.error = f"{w} of {r} rows differ from the reference"
        wrong, rows, compared = wrong + w, rows + r, compared + 1
    notes.update(rows_compared=rows, answers_compared=compared,
                 off_mesh=off_mesh)
    checks = {
        "wrong_rows": {"value": wrong, "limit": 0},
        "jobs_failed": {"value": sum(1 for j in every if j.error),
                        "limit": 0},
        "off_mesh": {"value": len(off_mesh), "limit": 0},
        "jobs_uncompared": {"value": len(every) - compared, "limit": 0},
    }
    if tols:
        notes["margin_by_table"] = margin
        checks["tolerance_margin"] = {
            "value": max(margin.values(), default=0.0), "limit": 1.0}
    return WindowResult(
        setup_s=d.setup_s, first_job_s=d.setup_jobs[0].seconds,
        jobs=d.jobs, setup_jobs=d.setup_jobs, window_s=d.window_s,
        work=d.work, window_compiles=d.window_compiles, traced=d.traced,
        telemetry_before=d.telemetry_before,
        telemetry_after=d.telemetry_after, counters=d.counters,
        memory_peak_bytes=d.memory_peak_bytes, checks=checks,
        notes=notes,
    )


def device_memory_peak(devices):
    """Peak bytes in use on the fullest chip, or None where the backend
    reports none (the CPU)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
