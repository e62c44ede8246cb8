"""The program's one span vocabulary (utils/trace.span): self-time
arithmetic and parent links, the clock it shares with the jax profiler,
the spans a waved Reduce leaves on the CPU mesh and their accounting,
stable program names, and the Chrome trace slicetrace still loads."""

import glob
import json
import threading
import time

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

import bigslice_tpu as bs
from bigslice_tpu.exec import wavestage
from bigslice_tpu.exec.meshexec import MeshExecutor, _program_name
from bigslice_tpu.exec.session import Session
from bigslice_tpu.utils import trace as trace_mod
from bigslice_tpu.utils.telemetry import TelemetryHub
from bigslice_tpu.utils.trace import SpanRecorder, Tracer, span

#: Every span of docs/observability.md's table.
TABLE = ("session.run", "compile_tasks", "evaluate", "group",
         "shuffle_plan", "stage", "read", "decode", "assemble", "upload",
         "stage_wait", "mutex_wait", "dispatch", "enqueue", "settle",
         "sync.keyrange", "sync.subid_count", "sync.shuffle_counts",
         "merge", "split", "readback")
#: Of them, what one client's job over host rows does NOT leave: its
#: source decodes nothing, and nobody else holds the wave mutex.
QUIET = ("decode", "mutex_wait")
WAVES = 4


def recorder():
    return SpanRecorder(TelemetryHub(), Tracer())


def events(rec):
    return {e["name"]: e for e in rec.tracer.events()}


def mesh_session(**kw):
    ex = MeshExecutor(Mesh(np.array(jax.devices()), ("shards",)))
    return Session(executor=ex, **kw)


def waved_reduce(sess, seed=0, scan=True):
    """A Reduce of WAVES waves on the 8-device mesh whose every key is
    on every shard already, so no wave overflows its slack and retries;
    scanned, unless ``scan`` is false, so the result is read back."""
    n = len(jax.devices()) * WAVES
    keys = np.tile(np.arange(64, dtype=np.int32), n * 4)
    vals = np.random.default_rng(seed).integers(
        1, 9, len(keys)).astype(np.int32)
    res = sess.run(bs.Reduce(bs.Const(n, keys, vals), lambda a, b: a + b))
    if scan:
        assert sum(r[1] for r in res.rows()) == int(vals.sum())
    return res


# ------------------------------------------------- (i) self time, links

def test_nested_and_sibling_spans_self_time_and_parents():
    rec = recorder()
    with span("outer", rec=rec, inv=7) as outer:
        with span("first") as first:
            time.sleep(0.01)
        with span("second") as second:
            with span("inner") as inner:
                time.sleep(0.01)
    assert (first.parent, second.parent, inner.parent) == \
        (outer, outer, second)
    assert outer.parent is None and inner.inv == 7
    ev = events(rec)
    assert ev["first"]["args"]["parent"] == outer.id
    assert ev["inner"]["args"]["parent"] == second.id
    assert "parent" not in ev["outer"]["args"]
    assert len({e["args"]["id"] for e in ev.values()}) == 4
    table = rec.hub.span_table()
    dur = {k: v["total_s"] for k, v in table.items()}
    assert table["outer"]["self_s"] == pytest.approx(
        dur["outer"] - dur["first"] - dur["second"], abs=1e-9)
    assert table["second"]["self_s"] == pytest.approx(
        dur["second"] - dur["inner"], abs=1e-9)
    assert table["inner"]["self_s"] == dur["inner"] >= 0.01
    assert {v["count"] for v in table.values()} == {1}
    assert trace_mod.current() is None


def test_adopted_children_leave_their_union_not_their_sum():
    """Two overlapping ``group`` children on other threads: ``evaluate``
    keeps what neither covers."""
    rec = recorder()
    bounds = {}
    a_open, b_inside = threading.Event(), threading.Event()

    def group(name):
        if name == "b":
            assert a_open.wait(10)
        with span("group", rec=rec, parent=rec.adopter(3), op=name) as g:
            if name == "a":
                a_open.set()
                assert b_inside.wait(10)   # b sleeps 30 ms inside a
            else:
                time.sleep(0.03)
                b_inside.set()
                time.sleep(0.01)
        bounds[name] = (g.t0, g.t1)
        assert g.parent is evaluating and g.inv == 3

    with span("evaluate", rec=rec, adopts=True, inv=3) as evaluating:
        assert rec.adopter(3) is evaluating
        threads = [threading.Thread(target=group, args=(n,))
                   for n in "ab"]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
            assert not t.is_alive()
    assert rec.adopter(3) is None
    table = rec.hub.span_table()
    union = (max(b[1] for b in bounds.values())
             - min(b[0] for b in bounds.values())) * 1e-9
    total = table["group"]["total_s"]
    assert total >= union + 0.03           # they did overlap
    assert table["evaluate"]["self_s"] == pytest.approx(
        table["evaluate"]["total_s"] - union, abs=1e-9)
    assert table["group"]["self_s"] == total


def test_a_stage_beside_its_group_names_it_and_is_subtracted_from_nobody():
    rec = recorder()
    staged = {}

    def prefetch(cause):
        with span("stage", rec=rec, cause=cause, wave=1) as s:
            with span("upload") as up:
                time.sleep(0.02)
                up.set(bytes=4096)
        staged["stage"] = s

    with span("group", rec=rec, inv=5) as group:
        t = threading.Thread(target=prefetch, args=(group,))
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    s = staged["stage"]
    assert s.parent is None and s.cause is group and s.inv == 5
    ev = events(rec)
    assert ev["stage"]["args"]["cause"] == group.id
    assert "parent" not in ev["stage"]["args"]
    assert ev["upload"]["args"]["parent"] == s.id
    table = rec.hub.span_table()
    assert table["group"]["self_s"] == table["group"]["total_s"] >= 0.02
    assert table["upload"]["bytes"] == 4096
    assert "bytes" not in table["stage"]


def test_a_charged_child_counts_in_the_table_and_leaves_self_time():
    rec = recorder()
    with span("read", rec=rec) as reading:
        time.sleep(0.02)
        reading.charge("decode", 0.015)
    table = rec.hub.span_table()
    assert table["decode"] == {"count": 1,
                               "total_s": pytest.approx(0.015),
                               "self_s": pytest.approx(0.015)}
    assert table["read"]["self_s"] == pytest.approx(
        table["read"]["total_s"] - 0.015, abs=1e-9)
    assert events(rec)["decode"]["args"]["parent"] == reading.id


def test_a_charge_of_nothing_leaves_no_row_and_no_event():
    rec = recorder()
    with span("read", rec=rec) as reading:
        reading.charge("decode", 0.0)
    table = rec.hub.span_table()
    assert set(table) == {"read"} == set(events(rec))
    assert table["read"]["self_s"] == table["read"]["total_s"]


def test_without_a_recorder_a_span_is_an_annotation_only():
    with span("group", rec=None, parent=None, op="x") as g:
        with span("dispatch", wave=0) as d:
            pass
    assert d.parent is g and g.rec is None and g.id == 0
    assert SpanRecorder().adopter(1) is None


# ------------------------------------------------- (ii) the shared clock

def test_span_is_in_the_profilers_trace_on_the_profilers_clock(tmp_path):
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    spans = []
    try:
        for wave in range(5):
            with span("unit", wave=wave) as sp:
                time.sleep(0.002)
            spans.append(sp)
    finally:
        jax.profiler.stop_trace()
    (xplane,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
    profile = ProfileData.from_file(xplane)
    # Event starts are relative to the trace's start, which the "Task
    # Environment" plane carries in unix-epoch nanoseconds.
    started = [dict(pl.stats)["profile_start_time"]
               for pl in profile.planes if pl.name == "Task Environment"]
    found = sorted((ev for pl in profile.planes for ln in pl.lines
                    for ev in ln.events if ev.name == "bigslice:unit"),
                   key=lambda ev: ev.start_ns)
    assert len(started) == 1 and len(found) == 5
    assert [dict(ev.stats) for ev in found] == [
        {"wave": w} for w in range(5)]
    # The annotation opens just before the span's own stamp; a loaded
    # machine may preempt the thread between the two once, not always.
    apart = [abs(started[0] + ev.start_ns
                 - trace_mod.CLOCK.to_unix_ns(sp.t0))
             for ev, sp in zip(found, spans)]
    assert min(apart) < 1e6 and sorted(apart)[2] < 1e6
    assert min(abs(ev.duration_ns - (sp.t1 - sp.t0))
               for ev, sp in zip(found, spans)) < 1e6


# --------------------------------- (iii), (iv), (vi): a waved run's spans

@pytest.fixture(scope="module")
def waved(tmp_path_factory):
    """Two waved Reduce jobs in one traced session: (summary, events)."""
    path = str(tmp_path_factory.mktemp("spans") / "trace.json")
    sess = mesh_session(trace_path=path)
    try:
        waved_reduce(sess, seed=1)
        waved_reduce(sess, seed=2)
        summary = sess.telemetry_summary()
    finally:
        sess.shutdown()
    with open(path) as fp:
        doc = json.load(fp)
    return summary, doc, path


def test_waved_reduce_leaves_every_span_of_the_table(waved):
    summary, _, _ = waved
    spans = summary["spans"]
    # No ``decode``: the rows were never encoded, and a charge of
    # nothing leaves no row. No ``mutex_wait``: one client, so no
    # acquire of the wave mutex ever waited.
    assert set(spans) == set(TABLE) - set(QUIET)
    jobs, groups = 2, 2 * 2               # map side + reduce side a job
    waves = groups * WAVES
    want = {"session.run": jobs, "compile_tasks": jobs, "evaluate": jobs,
            "group": groups, "shuffle_plan": groups, "merge": jobs,
            # The reduce side's views of the merged output, built once:
            # the count of its regions comes home, then the split.
            "split": jobs, "sync.subid_count": jobs,
            "stage": waves, "stage_wait": waves, "dispatch": waves,
            "enqueue": waves, "settle": waves,
            # The map side's merged counts, read for the skew record.
            "sync.shuffle_counts": jobs,
            # Wave 0 of the group with a dense candidate (the map
            # side's combiner) probes its key column's range.
            "sync.keyrange": jobs,
            # Only the map side reads its rows from the host.
            "read": jobs * WAVES,
            "assemble": jobs * WAVES, "upload": jobs * WAVES,
            # The scanned result is ONE readback, of every wave.
            "readback": jobs}
    assert {k: spans[k]["count"] for k in want} == want
    assert spans["upload"]["bytes"] > 0 and spans["readback"]["bytes"] > 0
    assert all(spans[k]["bytes"] > 0 for k in spans
               if k.startswith("sync."))
    assert all(v["self_s"] <= v["total_s"] + 1e-12 for v in spans.values())


def test_a_settle_says_whether_its_signals_were_ready(waved):
    """Every ``settle`` span carries ``ready`` (0 / 1: had the wave
    finished when the settle opened), and the per-op ``waves`` blocks
    count the same settles beside their seconds."""
    summary, doc, _ = waved
    settles = [e for e in doc["traceEvents"]
               if e.get("pid") == trace_mod.SPAN_PID
               and e["name"] == "settle"]
    assert len(settles) == summary["spans"]["settle"]["count"]
    assert {e["args"]["ready"] for e in settles} <= {0, 1}
    blocks = [op["waves"] for op in summary["ops"].values()
              if "settles" in op.get("waves", {})]
    # Map side and reduce side of both jobs, WAVES settles each.
    assert [b["settles"] for b in blocks] == [WAVES] * 4
    assert all(0 <= b["settles_ready"] <= b["settles"] for b in blocks)
    assert sum(b["settles_ready"] for b in blocks) == \
        sum(e["args"]["ready"] for e in settles)
    assert sum(b["settle_s"] for b in blocks) == pytest.approx(
        summary["spans"]["settle"]["total_s"], abs=2e-5)
    assert not any("ready" in e["args"] for e in doc["traceEvents"]
                   if e.get("pid") == trace_mod.SPAN_PID
                   and e["name"] == "dispatch")


def span_events(doc, *names):
    return [e for e in doc["traceEvents"]
            if e.get("pid") == trace_mod.SPAN_PID and e["name"] in names]


def test_an_enqueue_is_the_runtimes_part_of_its_dispatch(waved):
    """``enqueue`` (the jit call and the start of the signals' copy) is
    the one child of ``dispatch``: what ``dispatch`` keeps for itself is
    the executor's part, and the per-op ``waves`` blocks sum the same
    seconds beside ``dispatch_s``."""
    summary, doc, _ = waved
    spans = summary["spans"]
    by_id = {e["args"]["id"]: e for e in span_events(doc, *TABLE)}
    enqueues = span_events(doc, "enqueue")
    assert len(enqueues) == spans["dispatch"]["count"]
    for e in enqueues:
        parent = by_id[e["args"]["parent"]]
        assert parent["name"] == "dispatch"
        assert parent["dur"] == pytest.approx(
            parent["args"]["self_us"] + e["dur"], abs=1e-3)
    assert spans["dispatch"]["self_s"] + spans["enqueue"]["total_s"] == \
        pytest.approx(spans["dispatch"]["total_s"], abs=1e-9)
    assert spans["enqueue"]["self_s"] == spans["enqueue"]["total_s"]
    blocks = [op["waves"] for op in summary["ops"].values()
              if "dispatch_s" in op.get("waves", {})]
    assert len(blocks) == 4 and all(
        0 < b["enqueue_s"] <= b["dispatch_s"] for b in blocks)
    assert sum(b["enqueue_s"] for b in blocks) == pytest.approx(
        spans["enqueue"]["total_s"], abs=2e-5)


def test_every_blocking_read_of_a_group_has_a_sync_span(waved):
    """The three device-to-host reads on a group's path that are
    neither a settle nor a readback, each where it is made: the key
    range probe under wave 0 of the map side's group, the subid counts
    under the reduce side's first ``stage`` (ahead of ``split``), the
    merged counts under the map side's group after its merge."""
    _, doc, _ = waved
    by_id = {e["args"]["id"]: e for e in span_events(doc, *TABLE)}
    parent = lambda e: by_id[e["args"]["parent"]]      # noqa: E731
    for e in span_events(doc, "sync.keyrange", "sync.shuffle_counts"):
        assert parent(e)["name"] == "group"
        assert parent(e)["args"]["op"].startswith("const@")
        assert e["args"]["bytes"] > 0
    for e in span_events(doc, "sync.subid_count"):
        stage = parent(e)
        assert stage["name"] == "stage" and stage["args"]["wave"] == 0
        assert parent(stage)["name"] == "stage_wait"
        assert parent(parent(stage))["args"]["op"].startswith("reduce@")
        (split,) = [s for s in span_events(doc, "split")
                    if s["args"]["parent"] == stage["args"]["id"]]
        assert e["ts"] + e["dur"] <= split["ts"]
        assert e["args"]["bytes"] > 0
    invs = {e["args"]["inv"] for e in span_events(doc, "session.run")}
    assert len(invs) == 2
    for inv in invs:
        key, counts = (
            [e for e in span_events(doc, name) if e["args"]["inv"] == inv]
            for name in ("sync.keyrange", "sync.shuffle_counts"))
        (merge,) = [e for e in span_events(doc, "merge")
                    if e["args"]["inv"] == inv]
        first = min(e["ts"] for e in span_events(doc, "dispatch")
                    if e["args"]["inv"] == inv)
        assert len(key) == len(counts) == 1
        assert key[0]["ts"] + key[0]["dur"] <= first
        assert counts[0]["ts"] >= merge["ts"] + merge["dur"]


def test_a_stage_wait_says_whether_its_wave_was_staged(waved):
    """Every pipelined ``stage_wait`` carries ``ready`` (0 / 1: was the
    wave in the queue when the compute thread asked), wave 0's inline
    stage carries none, and the ``waves`` blocks count the same
    waits."""
    summary, doc, _ = waved
    waits = span_events(doc, "stage_wait")
    inline = [e for e in waits if "ready" not in e["args"]]
    queued = [e for e in waits if "ready" in e["args"]]
    assert {e["args"]["wave"] for e in inline} == {0}
    assert len(inline) == 4 and len(queued) == 4 * (WAVES - 1)
    assert {e["args"]["ready"] for e in queued} <= {0, 1}
    blocks = [op["waves"] for op in summary["ops"].values()
              if "stage_waits" in op.get("waves", {})]
    assert [b["stage_waits"] for b in blocks] == [WAVES - 1] * 4
    assert sum(b["stage_waits_ready"] for b in blocks) == \
        sum(e["args"]["ready"] for e in queued)
    assert all(b["prefetch_blocked_s"] >= 0 for b in blocks)


def slowed(monkeypatch, ex, name, seconds):
    """``ex.<name>`` as it is, ``seconds`` later."""
    real = getattr(ex, name)

    def slow(*args, **kw):
        time.sleep(seconds)
        return real(*args, **kw)

    monkeypatch.setattr(ex, name, slow)


def map_side_waves(sess):
    """The ``waves`` block of the map side of the session's last job."""
    return [op["waves"]
            for name, op in sess.telemetry_summary()["ops"].items()
            if name.startswith("const@")][-1]


@pytest.mark.parametrize("workers", [1, 2])
def test_a_slow_stager_is_waited_for_and_never_waits(monkeypatch, workers):
    """One worker 200 ms a wave behind a short loop is never ready and
    never blocked. Two run those stages in pairs: the first wave of a
    pair is waited for, the second is staged (or all but) when the loop
    comes for it."""
    monkeypatch.setattr(wavestage, "STAGE_WORKERS", workers)
    sess = mesh_session()
    try:
        waved_reduce(sess, scan=False)        # compiled: waves are short
        before = map_side_waves(sess)
        slowed(monkeypatch, sess.executor, "_stage", 0.2)
        waved_reduce(sess, seed=3, scan=False)
        block = map_side_waves(sess)
    finally:
        sess.shutdown()
    assert before is not block
    assert before["stage_waits"] == block["stage_waits"] == WAVES - 1
    if workers == 1:
        assert block["stage_waits_ready"] == 0
        assert block["prefetch_blocked_s"] == 0.0
        assert block["stages_overlapped"] == 0
    else:
        assert block["stage_waits_ready"] <= (WAVES - 1) // 2
        assert block["prefetch_blocked_s"] < 0.1
        assert block["stages_overlapped"] >= (WAVES - 1) // 2


def test_a_stager_that_runs_ahead_is_ready_and_waits_for_the_compute_thread(
        monkeypatch):
    sess = mesh_session()
    try:
        waved_reduce(sess, scan=False)
        slowed(monkeypatch, sess.executor, "_dispatch_wave", 0.2)
        waved_reduce(sess, seed=3, scan=False)
        block = map_side_waves(sess)
    finally:
        sess.shutdown()
    assert block["stage_waits_ready"] == block["stage_waits"] == WAVES - 1
    # Depth 1: with wave w + 1 in the queue the stager holds w + 2
    # until the compute thread, 200 ms a wave, takes the first.
    assert block["prefetch_blocked_s"] >= 0.1 * (WAVES - 2)


def test_mutex_wait_is_a_contended_wait_only():
    """One client never waits for the wave mutex and leaves no
    ``mutex_wait``; a group that finds it held (by another group's
    wave, here by this thread) waits under the span."""
    sess = mesh_session()
    ex = sess.executor
    try:
        waved_reduce(sess, scan=False)
        assert "mutex_wait" not in sess.telemetry_summary()["spans"]
        done = []
        ex._wave_mutex.acquire()
        try:
            job = threading.Thread(target=lambda: done.append(
                waved_reduce(sess, seed=4, scan=False)))
            job.start()
            time.sleep(1.0)                # its wave 0 is waiting by now
            assert not done
        finally:
            ex._wave_mutex.release()
        job.join(60)
        assert done and not job.is_alive()
        waited = sess.telemetry_summary()["spans"]["mutex_wait"]
    finally:
        sess.shutdown()
    assert 1 <= waited["count"] <= 2 * WAVES * 2
    assert 0.05 <= waited["total_s"] == waited["self_s"]


def scanned_output(sess, res):
    """The executor's waved group output behind a scanned result."""
    from bigslice_tpu.exec.meshexec import WavedGroupOutput

    ex = sess.executor
    with ex._lock:
        key, _ = ex._task_index[res.tasks[0].name]
        out = ex._outputs[key]
    assert isinstance(out, WavedGroupOutput) and len(out.waves) == WAVES
    return out


def readbacks(sess):
    return sess.telemetry_summary()["spans"].get(
        "readback", {"count": 0, "bytes": 0})


def test_one_readback_an_output_carries_every_waves_bytes(tmp_path):
    from bigslice_tpu.parallel import shuffle as shuffle_mod

    path = str(tmp_path / "trace.json")
    sess = mesh_session(trace_path=path)
    try:
        out = scanned_output(sess, waved_reduce(sess))
        crossed = []
        for w in out.waves:   # what each wave moves, read on its own
            shuffle_mod.unshard_columns(w.cols, w.counts, w.capacity,
                                        crossed=crossed)
        got = readbacks(sess)
        assert (got["count"], got["bytes"]) == (1, sum(crossed))
    finally:
        sess.shutdown()
    with open(path) as fp:
        (ev,) = [e for e in json.load(fp)["traceEvents"]
                 if e.get("pid") == trace_mod.SPAN_PID
                 and e["name"] == "readback"]
    assert ev["args"]["waves"] == WAVES
    assert ev["args"]["bytes"] == sum(crossed)
    # Every wave's counts, and a prefix a (column, non-empty shard).
    assert ev["args"]["arrays"] == WAVES + len(crossed)


def test_reading_one_shard_memoizes_every_wave():
    sess = mesh_session()
    try:
        res = waved_reduce(sess, scan=False)
        out = scanned_output(sess, res)
        assert all(w._chunks is None for w in out.waves)
        assert readbacks(sess)["count"] == 0
        first = list(res.reader(3, ()))
        assert readbacks(sess)["count"] == 1
        assert all(w._chunks is not None for w in out.waves)
        moved = readbacks(sess)["bytes"]
        # A shard of another wave, then the whole scan: no new span.
        list(res.reader(res.num_shards - 1, ()))
        rows = res.rows()
        got = readbacks(sess)
        assert (got["count"], got["bytes"]) == (1, moved)
        assert first and len(rows) == 64
    finally:
        sess.shutdown()


def test_concurrent_shard_readers_share_one_readback():
    """More reader threads than cores, each on a shard of its own, all
    let go at once: one span between them, every shard's rows right."""
    import sys

    sess = mesh_session()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        res = waved_reduce(sess, scan=False)
        out = scanned_output(sess, res)
        nshards = res.num_shards
        gate = threading.Barrier(nshards)
        got, errors = {}, []

        def read(shard):
            try:
                gate.wait(30)
                got[shard] = [r for f in res.reader(shard, ())
                              for r in f.rows()]
            except BaseException as e:   # reported by the assert below
                errors.append(e)

        threads = [threading.Thread(target=read, args=(s,))
                   for s in range(nshards)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errors and not any(t.is_alive() for t in threads)
        assert readbacks(sess)["count"] == 1
        for shard in range(nshards):
            w = out.waves[shard // out.nmesh]
            want = list(zip(*(c[shard % out.nmesh].tolist()
                              for c in w._chunks)))
            assert got[shard] == want
        assert sorted(r for rows in got.values() for r in rows) \
            == sorted(res.rows())
    finally:
        sys.setswitchinterval(old)
        sess.shutdown()


def test_staging_records_equal_what_the_spans_summed(waved):
    summary, doc, _ = waved
    spans = summary["spans"]
    phases = {"read_s": 0.0, "decode_s": 0.0, "assemble_s": 0.0,
              "upload_s": 0.0}
    exposed = staging = 0.0
    for op in summary["ops"].values():
        waves = op.get("waves", {})
        for k, v in waves.get("staging_breakdown", {}).items():
            phases[k] += v
        exposed += waves.get("exposed_s", 0.0)
        staging += waves.get("staging_s", 0.0)
    tol = dict(abs=2e-5)                  # the records round to 1 us each
    assert phases["read_s"] == pytest.approx(spans["read"]["self_s"],
                                             **tol)
    assert phases["decode_s"] == pytest.approx(
        spans.get("decode", {"total_s": 0.0})["total_s"], **tol)
    assert phases["assemble_s"] == pytest.approx(
        spans["assemble"]["total_s"], **tol)
    assert phases["upload_s"] == pytest.approx(
        spans["upload"]["total_s"], **tol)
    assert staging == pytest.approx(spans["stage"]["total_s"], **tol)
    # Exposed: a wave's ``stage_wait``, capped by its ``stage``.
    by_wave = {}
    for e in doc["traceEvents"]:
        if e.get("pid") == trace_mod.SPAN_PID \
                and e["name"] in ("stage", "stage_wait"):
            a = e["args"]
            by_wave.setdefault((a["inv"], a["wave"], e["name"]),
                               []).append(e)
    # Both groups of an invocation have a wave w: pair them in order.
    capped = 0.0
    for (inv, wave, name), waits in by_wave.items():
        if name != "stage_wait":
            continue
        stages = by_wave[(inv, wave, "stage")]
        assert len(stages) == len(waits)
        for w, s in zip(sorted(waits, key=lambda e: e["ts"]),
                        sorted(stages, key=lambda e: e["ts"])):
            capped += min(w["dur"], s["dur"]) * 1e-6
    assert exposed == pytest.approx(capped, **tol)
    assert exposed <= spans["stage_wait"]["total_s"] + 2e-5


def test_accounting_identity_of_an_invocation(waved):
    """session.run = the self time of session.run, compile_tasks and
    evaluate + the union of the invocation's groups; a group = its self
    time + its same-thread children."""
    _, doc, _ = waved
    sp = [e for e in doc["traceEvents"]
          if e.get("pid") == trace_mod.SPAN_PID]
    by_id = {e["args"]["id"]: e for e in sp}
    runs = [e for e in sp if e["name"] == "session.run"]
    assert len(runs) == 2
    for run in runs:
        inv = run["args"]["inv"]
        mine = [e for e in sp if e["args"]["inv"] == inv]
        self_us = sum(e["args"]["self_us"] for e in mine
                      if e["name"] in ("session.run", "compile_tasks",
                                       "evaluate"))
        groups = sorted((e["ts"], e["ts"] + e["dur"]) for e in mine
                        if e["name"] == "group")
        union, end = 0.0, 0.0
        for a, b in groups:
            a = max(a, end)
            if b > a:
                union, end = union + b - a, b
        assert run["dur"] == pytest.approx(self_us + union, rel=0.01)
        evaluate = [e for e in mine if e["name"] == "evaluate"]
        for g in (e for e in mine if e["name"] == "group"):
            assert g["args"]["parent"] == evaluate[0]["args"]["id"]
            kids = sum(e["dur"] for e in mine
                       if e["args"].get("parent") == g["args"]["id"])
            assert g["dur"] == pytest.approx(
                g["args"]["self_us"] + kids, rel=1e-6)
    for e in sp:                          # a stage beside its group
        if e["name"] == "stage" and "cause" in e["args"]:
            assert by_id[e["args"]["cause"]]["name"] == "group"
            assert "parent" not in e["args"]


def test_trace_file_has_span_events_slicetrace_still_loads(waved, capsys):
    from bigslice_tpu.tools import slicetrace

    _, doc, path = waved
    sp = [e for e in doc["traceEvents"]
          if e["ph"] == "X" and e.get("pid") == trace_mod.SPAN_PID]
    assert {e["name"] for e in sp} == set(TABLE) - set(QUIET)
    assert all({"id", "inv", "self_us"} <= set(e["args"]) for e in sp)
    assert doc["otherData"]["clock_unix_ns"] == trace_mod.CLOCK.unix_ns
    tasks = [e for e in doc["traceEvents"]
             if e["ph"] == "X" and e.get("pid") == "tasks"]
    # Task events and span events are on one clock: a task runs inside
    # its invocation's session.run span.
    runs = {e["args"]["inv"]: e for e in sp if e["name"] == "session.run"}
    for t in tasks:
        run = runs[t["args"]["inv"]]
        assert run["ts"] <= t["ts"]
        assert t["ts"] + t["dur"] <= run["ts"] + run["dur"] + 1.0
    assert slicetrace.main([path]) == 0
    out = capsys.readouterr().out
    assert f"{len(tasks)} task runs" in out   # spans are not task runs
    assert ":spans" in out and "settle" in out
    assert ":overlap" in out and ":staging" in out


def test_no_hub_leaves_the_annotation_and_drops_the_table(monkeypatch):
    monkeypatch.setenv("BIGSLICE_TELEMETRY", "0")
    sess = mesh_session()
    try:
        assert sess.telemetry is None and sess.spans.hub is None
        waved_reduce(sess)
        assert sess.telemetry_summary() == {}
    finally:
        sess.shutdown()


# ------------------------------------------- (v) stable program names

def lowered_names(ex):
    """kind -> module name of every program ``ex`` built."""
    names = {}
    for key, (prog, _) in ex._programs.items():
        fn = getattr(prog, "_fn", prog)    # through the telemetry seam
        kind = key[0] if isinstance(key[0], str) else "group"
        names.setdefault(kind, set()).add(fn.__name__)
    return names


def run_every_program_kind():
    """One executor driven through group, merge, rowslice, subid_count,
    subid_split and keyrange programs."""
    sess = mesh_session()
    ex = sess.executor
    try:
        waved_reduce(sess)                 # group, merge, subid_*
        inputs = ex._group_inputs(
            bs_tasks(sess, bs.Const(8, np.arange(64, dtype=np.int32))))
        cols, counts, cap, _, _ = inputs[0]
        ex._slice_wave_program(("int32",), cap, cap // 2)(
            np.int32(0), counts, *cols)
        ex._key_range(cols, counts, cap, False)
        return lowered_names(ex)
    finally:
        sess.shutdown()


def bs_tasks(sess, slice_):
    from bigslice_tpu.exec import compile as compile_mod

    return compile_mod.Compiler(10 ** 6).compile(slice_)


def test_every_program_kind_lowers_under_a_stable_name():
    first, second = run_every_program_kind(), run_every_program_kind()
    assert first == second                 # two fresh executors
    kinds = {"group", "merge", "rowslice", "subidcount", "subidsplit",
             "keyrange"}
    assert set(first) == kinds
    assert first["merge"] == {"bs_merge"}
    assert first["rowslice"] == {"bs_rowslice"}
    assert first["subidcount"] == {"bs_subid_count"}
    assert first["subidsplit"] == {"bs_subid_split"}
    assert first["keyrange"] == {"bs_keyrange"}
    assert all(n.startswith("bs_group_") for n in first["group"])


def test_group_program_name_is_its_structure_not_its_op_index():
    """Two jobs whose ops differ in index (``reduce@...#1``) lower to
    the same module name — the persistent cache's key begins with it."""
    sess = mesh_session()
    try:
        lowered = []
        for seed in (1, 2):
            waved_reduce(sess, seed)
            ex = sess.executor
            for key, (prog, _) in list(ex._programs.items()):
                if not isinstance(key[0], str):
                    fn = getattr(prog, "_fn", prog)
                    lowered.append(fn.__name__)
        ops = [op for op in sess.telemetry_summary()["ops"]]
        assert any("#" in op for op in ops)    # indices did differ
        assert len(set(lowered)) == 2          # map side, reduce side
    finally:
        sess.shutdown()
    name = _program_name("group", ("map", "shuffle"))
    assert name == "bs_group_map_shuffle"
    assert _program_name("group", ("a-b", "c d")) == "bs_group_a_b_c_d"
    assert len(_program_name("group", ("shuffle",) * 40)) == 64

    def stepped(x):
        return x + 1

    from bigslice_tpu.exec.meshexec import _named

    text = jax.jit(_named(stepped, "group", ("map", "shuffle"))).lower(
        np.int32(1)).as_text()
    assert "module @jit_bs_group_map_shuffle" in text
