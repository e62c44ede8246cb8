"""Telemetry hub tests: skew detection, straggler flagging, wave
overlap accounting, monitor-channel hardening, tracer lane allocation,
status printer final snapshot (utils/telemetry.py and friends)."""

import io
import json
import time

import numpy as np
import pytest

import bigslice_tpu as bs
from bigslice_tpu.exec.session import Session
from bigslice_tpu.exec.task import TaskName, TaskState
from bigslice_tpu.utils import telemetry as telemetry_mod


def _mesh_session(**kwargs):
    import jax
    from jax.sharding import Mesh

    from bigslice_tpu.exec.meshexec import MeshExecutor

    mesh = Mesh(np.array(jax.devices()[:8]), ("shards",))
    return Session(executor=MeshExecutor(mesh), **kwargs)


# --------------------------------------------------------------- skew

def test_hot_key_workload_flagged_hot_shard_identified():
    """Acceptance: a synthetic hot-key shuffle is flagged by the skew
    detector and the hot shard is identified in telemetry_summary();
    see test_balanced_workload_not_flagged for the negative."""
    sess = Session()
    n = 20000
    keys = np.zeros(n, dtype=np.int32)  # ~90% of rows on key 0
    keys[: n // 10] = np.arange(n // 10, dtype=np.int32) % 97 + 1
    res = sess.run(bs.Reduce(bs.Const(8, keys, np.ones(n, np.int32)),
                             lambda a, b: a + b))
    summary = sess.telemetry_summary()
    assert summary["skew_flagged_ops"], summary["ops"].keys()
    op = summary["skew_flagged_ops"][0]
    skew = summary["ops"][op]["skew"]
    assert skew["flagged"]
    assert skew["ratio"] >= telemetry_mod.DEFAULT_SKEW_RATIO
    # The hot shard is the partition key 0 hashes to — identified, and
    # it holds the max row count.
    hot = skew["max_shard"]
    assert skew["rows"][hot] == max(skew["rows"])
    assert skew["rows"][hot] >= 0.8 * sum(skew["rows"])
    # Bytes accounting rides along (local tier: routed bytes).
    assert sum(skew["bytes"]) > 0
    res.discard()


def test_balanced_workload_not_flagged():
    sess = Session()
    n = 20000
    rng = np.random.RandomState(3)
    keys = rng.randint(0, 1 << 14, n).astype(np.int32)
    res = sess.run(bs.Reduce(bs.Const(8, keys, np.ones(n, np.int32)),
                             lambda a, b: a + b))
    summary = sess.telemetry_summary()
    assert summary["skew_flagged_ops"] == []
    # The boundary was still observed (just not flagged).
    skews = [e["skew"] for e in summary["ops"].values() if "skew" in e]
    assert skews and all(s["ratio"] < 2.0 for s in skews)
    res.discard()


def test_mesh_shuffle_skew_recorded_combinerless():
    """The mesh tier records per-device output counts at partitioned
    group boundaries; a combiner-less hot-key Reshuffle shows the raw
    routed skew there."""
    sess = _mesh_session()
    n = 1 << 14
    keys = np.zeros(n, dtype=np.int32)
    keys[: n // 8] = np.arange(n // 8, dtype=np.int32) % 53 + 1
    res = sess.run(bs.Reshuffle(bs.Const(8, keys,
                                         np.ones(n, np.int32))))
    total = sum(len(f) for f in res.frames())
    assert total == n
    summary = sess.telemetry_summary()
    if sess.executor.device_group_count() == 0:
        pytest.skip("reshuffle fell back to host tier")
    assert summary["skew_flagged_ops"], summary["ops"]
    op = summary["skew_flagged_ops"][0]
    skew = summary["ops"][op]["skew"]
    assert skew["rows"][skew["max_shard"]] == max(skew["rows"])
    res.discard()


def test_hub_record_shuffle_accumulates_elementwise():
    hub = telemetry_mod.TelemetryHub()
    hub.record_shuffle("op1", 1, [10, 10, 10], [80, 80, 80])
    hub.record_shuffle("op1", 1, [90, 10, 10], [720, 80, 80])
    s = hub.summary()
    skew = s["ops"]["op1"]["skew"]
    assert skew["rows"] == [100, 20, 20]
    assert skew["bytes"] == [800, 160, 160]
    assert skew["max_shard"] == 0
    assert skew["boundaries"] == 2


def test_hub_bounds_op_records():
    """Iterative drivers mint fresh op names per invocation; the hub
    evicts oldest ops past MAX_OPS instead of growing forever."""
    hub = telemetry_mod.TelemetryHub()
    for i in range(telemetry_mod.MAX_OPS + 50):
        hub.record_shuffle(f"op{i}", i, [1, 2], [8, 16])
    assert len(hub._ops) == telemetry_mod.MAX_OPS
    assert "op0" not in hub._ops  # oldest evicted
    assert f"op{telemetry_mod.MAX_OPS + 49}" in hub._ops


# --------------------------------------------------------- stragglers

class _FakeTask:
    def __init__(self, op, shard, num_shard=8, inv=1):
        self.name = TaskName(inv, op, shard, num_shard)
        self.state_times = {}


def test_straggler_flagged_deterministic():
    """Unit-level: a task 10x slower than its completed siblings' p50
    is flagged; siblings within the envelope are not."""
    hub = telemetry_mod.TelemetryHub()
    now = time.monotonic()
    for shard in range(6):
        t = _FakeTask("slowop", shard)
        slow = shard == 5
        dur = 1.0 if slow else 0.1
        t.state_times[TaskState.RUNNING] = now - dur
        hub(t, TaskState.RUNNING)
        # Monkeypatch-free determinism: RUNNING stamp is read from
        # state_times; duration = monotonic() - stamp.
        hub(t, TaskState.OK)
    s = hub.summary()
    rec = s["ops"]["slowop"]
    assert s["straggler_total"] == 1
    assert len(rec["stragglers"]) == 1
    assert rec["stragglers"][0]["shard"] == 5
    assert rec["stragglers"][0]["duration_s"] > 0.9
    assert rec["tasks"]["n"] == 6


def test_straggler_flagged_end_to_end():
    """Integration: one sleeping shard in a real session is flagged."""
    def gen(shard):
        if shard == 5:
            time.sleep(0.5)
        yield ([np.int32(shard)],)

    sess = Session()
    res = sess.run(bs.ReaderFunc(6, gen, out=[np.int32]))
    assert len(res.rows()) == 6
    summary = sess.telemetry_summary()
    stragglers = [s for e in summary["ops"].values()
                  for s in e.get("stragglers", ())]
    assert stragglers, summary["ops"]
    assert any(s["shard"] == 5 for s in stragglers)
    res.discard()


def test_live_straggler_detection():
    hub = telemetry_mod.TelemetryHub()
    now = time.monotonic()
    for shard in range(5):
        t = _FakeTask("liveop", shard)
        t.state_times[TaskState.RUNNING] = now - 0.01
        hub(t, TaskState.RUNNING)
        hub(t, TaskState.OK)
    hung = _FakeTask("liveop", 7)
    hung.state_times[TaskState.RUNNING] = now - 5.0
    hub(hung, TaskState.RUNNING)
    live = hub.live_stragglers()
    assert len(live) == 1 and live[0]["shard"] == 7
    # ...and it annotates the status line.
    lines = hub.status_lines()
    assert any("straggler" in ln for ln in lines)


# ------------------------------------------------------- wave overlap

def test_wave_overlap_accounting_pipelined_vs_serial():
    """A waved reduce records staging/exposed time; serial staging is
    100% exposed (efficiency 0), the pipelined efficiency is a valid
    fraction and the summary carries a session-wide rollup."""
    from bigslice_tpu.exec.meshexec import MeshExecutor
    import jax
    from jax.sharding import Mesh

    n = 1 << 13
    rng = np.random.RandomState(42)
    keys = rng.randint(0, 1 << 18, n).astype(np.int32)
    vals = np.ones(n, np.int32)

    def run(prefetch_depth):
        mesh = Mesh(np.array(jax.devices()[:8]), ("shards",))
        sess = Session(executor=MeshExecutor(
            mesh, prefetch_depth=prefetch_depth))
        res = sess.run(bs.Reduce(bs.Const(16, keys, vals),
                                 lambda a, b: a + b))
        sum(len(f) for f in res.frames())
        out = sess.telemetry_summary()
        res.discard()
        return out

    serial = run(0)
    waved = [e["waves"] for e in serial["ops"].values()
             if e.get("waves", {}).get("n_waves", 0) > 1]
    assert waved, serial["ops"]
    for w in waved:
        assert w["staging_s"] >= w["exposed_s"] >= 0
        assert w["overlap_efficiency"] == 0.0  # serial: all exposed
    assert serial["overlap_efficiency"] == 0.0

    piped = run(1)
    waved = [e["waves"] for e in piped["ops"].values()
             if e.get("waves", {}).get("n_waves", 0) > 1]
    assert waved, piped["ops"]
    for w in waved:
        assert 0.0 <= w["overlap_efficiency"] <= 1.0
        # abs tolerance: the three fields are rounded independently
        # to 6 decimals in summary().
        assert w["hidden_s"] == pytest.approx(
            w["staging_s"] - w["exposed_s"], abs=5e-6)
        assert w["compute_s"] > 0
        # Phase events flowed through on_phase into the hub too.
        assert w["phases"].get("waveCompute", 0) >= w["n_waves"]
    assert piped["overlap_efficiency"] is not None


def test_wave_host_record_counts_settles_and_those_found_ready():
    """``record_wave_host``: seconds by field; a settle also passes
    ``ready`` and is counted, a dispatch passes none and is not."""
    hub = telemetry_mod.TelemetryHub()
    hub.record_wave_compute("reduce@x", 1, 0, 0.5)
    hub.record_wave_compute("const@x", 1, 0, 0.5)
    for ready in (1, 0, 1, True):
        hub.record_wave_host("reduce@x", 1, "dispatch_s", 0.001)
        hub.record_wave_host("reduce@x", 1, "settle_s", 0.002,
                             ready=ready)
    hub.record_wave_host("const@x", 1, "dispatch_s", 0.001)
    ops = hub.summary()["ops"]
    waves = ops["reduce@x"]["waves"]
    assert waves["dispatch_s"] == pytest.approx(0.004)
    assert waves["settle_s"] == pytest.approx(0.008)
    assert (waves["settles"], waves["settles_ready"]) == (4, 3)
    # No settle recorded: no count of them, not a count of 0.
    assert "settles" not in ops["const@x"]["waves"]
    assert "settles_ready" not in ops["const@x"]["waves"]
    json.dumps(ops)


def test_wave_records_carry_the_enqueue_the_ready_waits_and_the_stager():
    """The fields that ride on calls a wave already makes: a dispatch's
    ``enqueue_s`` on ``record_wave_host``, a pipelined wait's ``ready``
    on ``record_wave_staging``; and once a group
    ``record_prefetch_blocked``. An op that passed none has none."""
    hub = telemetry_mod.TelemetryHub()
    for wave, ready in enumerate((None, 0, 1, 1)):   # wave 0 is inline
        hub.record_wave_staging("const@x", 1, wave, 0.004, 0.001,
                                ready=ready)
        hub.record_wave_host("const@x", 1, "dispatch_s", 0.001,
                             enqueue_s=0.0007)
    hub.record_prefetch_blocked("const@x", 1, 0.25, 3)
    hub.record_prefetch_blocked("const@x", 1, 0.0)   # one worker: 0
    hub.record_wave_staging("serial@x", 1, 0, 0.004, 0.004)
    hub.record_wave_host("serial@x", 1, "dispatch_s", 0.001)
    ops = hub.summary()["ops"]
    waves = ops["const@x"]["waves"]
    assert waves["dispatch_s"] == pytest.approx(0.004)
    assert waves["enqueue_s"] == pytest.approx(0.0028)
    assert (waves["stage_waits"], waves["stage_waits_ready"]) == (3, 2)
    assert waves["staged"] == 4
    assert waves["prefetch_blocked_s"] == 0.25
    assert waves["stages_overlapped"] == 3
    assert not {"enqueue_s", "stage_waits", "stage_waits_ready",
                "prefetch_blocked_s", "stages_overlapped"} \
        & set(ops["serial@x"]["waves"])
    json.dumps(ops)


def test_merge_record_sums_slots_read_full_and_the_rows_bound():
    """``record_merge``: one call a cross-wave merge, host integers
    only; the block holds sums since the session began, so a window
    reads them as the difference of two summaries; an op that merged
    nothing has no block."""
    hub = telemetry_mod.TelemetryHub()
    hub.record_wave_compute("reduce@x", 1, 0, 0.5)
    hub.record_merge("const@x", 1, 46, slots=46 * 1024,
                     slots_full=46 * 135168, rows_bound=46 * 640)
    before = hub.summary()["ops"]
    assert before["const@x"]["merge"] == {
        "merges": 1, "waves": 46, "slots": 47104,
        "slots_full": 6217728, "rows_bound": 29440}
    assert "merge" not in before["reduce@x"]
    hub.record_merge("const@x", 2, 12, slots=12 * 65536,
                     slots_full=12 * 262144, rows_bound=12 * 63700)
    after = hub.summary()["ops"]["const@x"]["merge"]
    grown = {k: after[k] - before["const@x"]["merge"][k] for k in after}
    assert grown == {"merges": 1, "waves": 12, "slots": 786432,
                     "slots_full": 3145728, "rows_bound": 764400}
    # The summary hands out a copy, not the hub's own record.
    after["slots"] = 0
    assert hub.summary()["ops"]["const@x"]["merge"]["slots"] == \
        47104 + 786432
    json.dumps(hub.summary()["ops"])


@pytest.mark.parametrize("ndev", [1, 8])
def test_waved_shuffle_feeds_the_merge_block_from_its_settles(ndev):
    """A waved Filter + Reduce: the map side's merge is recorded once a
    job under the op that shuffled, in whole devices — slots read,
    the waves' capacities, and the waves' ``rows_max`` as the bound —
    and a second job adds to it."""
    import jax
    from jax.sharding import Mesh

    from bigslice_tpu.exec.meshexec import MeshExecutor

    rows, waves = 256, 3
    n = waves * ndev * rows
    keys = np.random.default_rng(7).integers(0, 1 << 20, n).astype(np.int32)
    sess = Session(executor=MeshExecutor(
        Mesh(np.array(jax.devices()[:ndev]), ("shards",))))
    try:
        def job():
            kept = bs.Filter(bs.Const(waves * ndev, keys, np.ones_like(keys)),
                             lambda k, v: k % 8 == 0)
            return len(sess.run(bs.Reduce(kept, lambda a, b: a + b)).rows())

        def blocks():
            return [(op, rec["merge"]) for op, rec in
                    sess.telemetry_summary()["ops"].items()
                    if "merge" in rec]

        assert job() == len(np.unique(keys[keys % 8 == 0]))
        ((op, first),) = blocks()
        job()
        (second,) = [m for o, m in blocks() if o != op]
    finally:
        sess.shutdown()
    for m in (first, second):
        assert m["merges"] == 1 and m["waves"] == waves
        assert m["slots_full"] == waves * ndev * rows
        # An eighth of the rows pass: their bucket, not the capacity.
        assert m["rows_bound"] <= m["slots"] < m["slots_full"]
        assert m["slots"] % (waves * ndev) == 0
        assert m["rows_bound"] >= len(np.unique(keys[keys % 8 == 0]))
    assert first == second


def test_table_record_sums_waves_slots_and_columns_by_lowering():
    """``record_table``: one call a dispatched wave, from the tables
    its program's trace recorded; sums since the session began, and an
    op whose programs fill no table has no block."""
    hub = telemetry_mod.TelemetryHub()
    hub.record_wave_compute("map@x", 1, 0, 0.5)
    wave = [(65, ("scatter", "compare"))]
    hub.record_table("reduce@x", 1, wave)
    hub.record_table("reduce@x", 1, wave)
    hub.record_table("join@x", 2, [(9, ("compare",)),
                                   (9, ("scatter", "compare"))])
    ops = hub.summary()["ops"]
    assert ops["reduce@x"]["table"] == {
        "waves": 2, "slots": 130, "columns_compare": 2,
        "columns_scatter": 2}
    assert ops["join@x"]["table"] == {
        "waves": 1, "slots": 18, "columns_compare": 2,
        "columns_scatter": 1}
    assert "table" not in ops["map@x"]
    json.dumps(ops)


@pytest.mark.parametrize("slots,shape,want", [
    # The k-means round's table: float32 vectors and their counts.
    (65, (16,), {"columns_scatter": 1, "columns_compare": 1}),
    # TPC-H Q1's: six scalar sums into 6 keys and the drop lane.
    (7, (), {"columns_scatter": 0, "columns_compare": 6}),
])
def test_dense_reduce_feeds_the_table_block_a_wave(slots, shape, want):
    """A dense Reduce on the mesh: every wave that fills a table counts
    its slots and each value column's lowering, as
    ``dense.table_column_lowering`` picks it."""
    rng = np.random.default_rng(slots)
    n, K = 4096, slots - 1
    keys = rng.integers(0, K, n).astype(np.int32)
    if shape:
        cols = (rng.random((n,) + shape, dtype=np.float32),
                np.ones(n, np.float32))
    else:
        cols = tuple(rng.integers(0, 100, n).astype(np.int32)
                     for _ in range(6))
    sess = _mesh_session()
    try:
        res = sess.run(bs.Reduce(
            bs.Const(4, keys, *cols),
            lambda a, b: tuple(x + y for x, y in zip(a, b)),
            dense_keys=K))
        assert len(res.rows()) == K
        blocks = [rec["table"] for rec in
                  sess.telemetry_summary()["ops"].values()
                  if "table" in rec]
    finally:
        sess.shutdown()
    assert blocks
    for t in blocks:
        waves = t["waves"]
        assert waves >= 1
        assert t == {"waves": waves, "slots": slots * waves,
                     "columns_scatter": 0,
                     **{k: v * waves for k, v in want.items()}}


# ---------------------------------------------- monitor hardening

def test_raising_monitor_does_not_break_evaluation(capsys):
    """Satellite: an exception in one monitor must not propagate into
    the evaluator or the prefetcher thread — logged once, evaluation
    completes, and later monitors in the chain still run."""
    calls = []

    class BadMonitor:
        def __call__(self, task, state):
            raise RuntimeError("broken monitor")

        def on_phase(self, task, phase, wave):
            raise RuntimeError("broken phase monitor")

    sess = Session(monitor=BadMonitor())
    res = sess.run(bs.Const(4, np.arange(8, dtype=np.int32)))
    assert len(res.rows()) == 8
    # The chain's later members (status, telemetry) still saw every
    # transition despite the bad first member.
    assert sess.telemetry_summary()["task_states"].get("OK") == 4
    assert "4/4 done" in sess.status.render()
    err = capsys.readouterr().err
    assert "monitor" in err and "broken monitor" in err
    # Logged once (one suppression header), not once per transition.
    assert err.count("raised (suppressed") == 1
    res.discard()
    del calls


def test_raising_phase_monitor_does_not_break_waved_run():
    """The prefetcher thread path: a raising on_phase fires from the
    staging thread during the overlapped wave pipeline and must not
    poison staging."""
    class BadPhase:
        def __call__(self, task, state):
            pass

        def on_phase(self, task, phase, wave):
            raise RuntimeError("phase boom")

    sess = _mesh_session(monitor=BadPhase())
    n = 1 << 12
    keys = np.arange(n, dtype=np.int32) % 257
    res = sess.run(bs.Reduce(bs.Const(16, keys, np.ones(n, np.int32)),
                             lambda a, b: a + b))
    assert sum(len(f) for f in res.frames()) == 257
    res.discard()


# ------------------------------------------------- tracer lane reuse

def test_tracer_no_tid_collision_after_rebegin():
    """Satellite: mixed begin/end interleavings (a re-begun key leaks
    its old lane) must never hand a fresh begin a tid that is still
    live — the old len(_tids)+1 derivation did."""
    from bigslice_tpu.utils.trace import Tracer

    t = Tracer()
    t.begin("k1", "a")
    t.begin("k2", "b")
    t.begin("k1", "a-again")  # re-begin: old k1 lane leaks
    t.begin("k3", "c")        # must NOT collide with k1's live lane
    live = list(t._tids.values())
    assert len(live) == len(set(live)), live
    t.end("k1")
    t.end("k2")
    t.end("k3")
    # Freed lanes are reused, fresh lanes stay unique.
    t.begin("k4", "d")
    t.begin("k5", "e")
    t.begin("k6", "f")
    t.begin("k7", "g")
    live = list(t._tids.values())
    assert len(live) == len(set(live)), live
    # Events remain well-formed X events.
    for e in t.events():
        assert e["ph"] == "X" and e["dur"] >= 0


# -------------------------------------- status printer final snapshot

def test_status_printer_prints_final_snapshot_on_stop():
    """Satellite: a session shorter than the print interval must not
    exit with an empty/stale status block — stop() renders once."""
    from bigslice_tpu.utils.status import Status, StatusPrinter

    stream = io.StringIO()
    status = Status()
    printer = StatusPrinter(status, interval=60.0, stream=stream)
    printer.start()
    sess = Session(monitor=status)
    res = sess.run(bs.Const(3, np.arange(6, dtype=np.int32)))
    assert stream.getvalue() == ""  # interval never elapsed
    printer.stop()
    out = stream.getvalue()
    assert "3/3 done" in out
    # A second stop with unchanged state does not duplicate the block.
    printer.stop()
    assert stream.getvalue() == out
    res.discard()


def test_status_render_carries_skew_annotation():
    sess = Session()
    n = 20000
    keys = np.zeros(n, dtype=np.int32)
    keys[: n // 10] = np.arange(n // 10, dtype=np.int32) % 97 + 1
    res = sess.run(bs.Reduce(bs.Const(8, keys, np.ones(n, np.int32)),
                             lambda a, b: a + b))
    rendered = sess.status.render()
    assert "skew" in rendered and "hot shard" in rendered
    res.discard()


# ------------------------------------------------ slicetrace sections

def test_slicetrace_renders_skew_and_overlap_sections(tmp_path, capsys):
    """Acceptance: tools/slicetrace.py renders the new skew/straggler/
    overlap sections from a recorded trace."""
    path = str(tmp_path / "telem.json")
    sess = _mesh_session(trace_path=path)
    n = 1 << 13
    keys = np.arange(n, dtype=np.int32) % 509
    res = sess.run(bs.Reduce(bs.Const(16, keys, np.ones(n, np.int32)),
                             lambda a, b: a + b))
    sum(len(f) for f in res.frames())
    sess.shutdown()
    from bigslice_tpu.tools import slicetrace

    assert slicetrace.main([path]) == 0
    out = capsys.readouterr().out
    assert ":straggler" in out
    assert ":overlap" in out and "overlap" in out
    assert ":skew" in out and "hot" in out
    # The overlap table carries real staging numbers.
    assert "stage_ms" in out


# ------------------------------------------------------ obsdump tool

def test_obsdump_writes_trace_and_summary(tmp_path):
    from bigslice_tpu.tools import obsdump

    trace = str(tmp_path / "t.json")
    summary_path = str(tmp_path / "s.json")
    assert obsdump.main(["--trace", trace, "--summary", summary_path,
                         "--rows", "4096"]) == 0
    with open(trace) as fp:
        doc = json.load(fp)
    assert doc["traceEvents"]
    with open(summary_path) as fp:
        summary = json.load(fp)
    assert summary["ops"]
    assert summary["workload"]["rows"] == 4096
    assert summary["task_states"].get("OK", 0) > 0


# ----------------------------------------------------- summary shape

def test_telemetry_summary_is_json_serializable():
    sess = Session()
    res = sess.run(bs.Reduce(
        bs.Const(4, np.arange(4096, dtype=np.int32) % 97,
                 np.ones(4096, np.int32)),
        lambda a, b: a + b))
    s = sess.telemetry_summary()
    json.dumps(s)  # must not raise (the harness and CI record it)
    assert "ops" in s and "task_states" in s
    res.discard()


# ---------------------------------------------------- device plane

def test_device_summary_on_waved_mesh_run():
    """Acceptance: a CPU-mesh reduce-wave run reports per-op compile
    time, cache hit/miss counts, cost/memory analysis numbers, and a
    per-wave HBM watermark under telemetry_summary()["device"]."""
    sess = _mesh_session()
    n = 1 << 14
    rng = np.random.RandomState(3)
    keys = rng.randint(0, 1 << 18, n).astype(np.int32)
    # 32 shards on 8 devices -> 4 waves (waved compile + HBM samples).
    res = sess.run(bs.Reduce(bs.Const(32, keys, np.ones(n, np.int32)),
                             lambda a, b: a + b))
    sum(len(f) for f in res.frames())
    dev = sess.telemetry_summary()["device"]
    json.dumps(dev)  # JSON-clean (bench/CI record it)
    totals = dev["totals"]
    assert totals["compiles"] > 0
    assert totals["compile_s"] > 0
    # Waves 1..3 reuse wave 0's compiled program: hits must show up.
    assert totals["cache_hits"] > 0
    reduce_ops = [o for o in dev["compile"] if "reduce" in o]
    assert reduce_ops, dev["compile"].keys()
    entry = dev["compile"][reduce_ops[0]]
    assert entry["compile_s"] > 0
    progs = entry["programs"]
    assert progs
    # cost_analysis numbers (CPU backend reports flops/bytes).
    assert any(p.get("flops") for p in progs)
    assert any(p.get("bytes_accessed") for p in progs)
    # memory_analysis numbers ride beside them where the backend
    # reports (CPU does).
    assert any("argument_bytes" in p or "temp_bytes" in p
               for p in progs)
    # Per-wave HBM watermarks: the virtual CPU mesh has no allocator
    # stats, so the live-array fallback must have recorded instead of
    # raising.
    hbm = dev["hbm"]
    assert hbm["samples"] > 0
    assert hbm["source"] == "live_arrays"
    assert hbm["peak_bytes"] > 0
    assert any(s.get("wave") is not None for s in hbm["per_wave"])
    res.discard()
    sess.shutdown()


def test_hbm_sample_memory_stats_none_falls_back():
    """The CPU-backend contract: devices whose memory_stats() returns
    None (or raises) must not break sampling — the live-array byte sum
    records instead."""
    from bigslice_tpu.utils.devicetelemetry import DeviceTelemetry

    class NoStats:
        def memory_stats(self):
            return None

    class Raises:
        def memory_stats(self):
            raise RuntimeError("no allocator here")

    dev = DeviceTelemetry()
    sample = dev.sample_hbm([NoStats(), Raises()], op="x", wave=0)
    assert sample is not None
    assert sample["bytes_in_use"] >= 0
    assert dev.summary()["hbm"]["source"] == "live_arrays"


def test_hbm_sample_with_allocator_stats_and_limit():
    from bigslice_tpu.utils.devicetelemetry import DeviceTelemetry

    class Fake:
        def __init__(self, used, peak, limit):
            self._s = {"bytes_in_use": used, "peak_bytes_in_use": peak,
                       "bytes_limit": limit}

        def memory_stats(self):
            return self._s

    dev = DeviceTelemetry()
    dev.sample_hbm([Fake(100, 150, 1000), Fake(300, 400, 1000)],
                   op="x", wave=1)
    hbm = dev.summary()["hbm"]
    assert hbm["source"] == "memory_stats"
    assert hbm["current_bytes"] == 300  # max across devices
    assert hbm["peak_bytes"] == 400
    assert hbm["limit_bytes"] == 1000
    assert hbm["peak_frac"] == 0.4
    # ...and the live status annotation renders the percentage.
    line = dev.status_line()
    assert line and "hbm 30%" in line


def test_disabled_hub_is_noop(monkeypatch):
    """BIGSLICE_TELEMETRY=0: no hub is built, every executor seam
    no-ops, runs still work, and telemetry_summary() is empty — the
    collection-off floor for perf A/Bs."""
    monkeypatch.setenv("BIGSLICE_TELEMETRY", "0")
    sess = _mesh_session()
    assert sess.telemetry is None
    n = 4096
    res = sess.run(bs.Reduce(
        bs.Const(16, np.arange(n, dtype=np.int32) % 531,
                 np.ones(n, np.int32)),
        lambda a, b: a + b))
    assert sum(len(f) for f in res.frames()) == 531
    assert sess.telemetry_summary() == {}
    # No instrumentation wrapper on cached programs either.
    from bigslice_tpu.utils.devicetelemetry import _InstrumentedProgram

    for prog, _refs in sess.executor._programs.values():
        assert not isinstance(prog, _InstrumentedProgram)
    res.discard()
    sess.shutdown()


def test_donation_effectiveness_recorded():
    from bigslice_tpu.utils.devicetelemetry import DeviceTelemetry

    dev = DeviceTelemetry()
    dev.record_donation("op_a", 1, expected_bytes=1000,
                        aliased_bytes=750, buffers=4,
                        aliased_buffers=3)
    s = dev.summary()
    d = s["donation"]["op_a"]
    assert d["effectiveness"] == 0.75
    assert s["totals"]["donation_effectiveness"] == 0.75


def test_flight_recorder_dump_on_fatal(tmp_path, monkeypatch):
    """Acceptance: a fatal run dumps flightrec-<inv>.json (bounded
    event ring + task-state census + reason) when a dump dir is
    configured; without one, dumping is a no-op."""
    import glob

    monkeypatch.setenv("BIGSLICE_FLIGHTREC_DIR", str(tmp_path))

    def boom(x):
        raise ValueError("injected fatal for flightrec")

    sess = Session()
    with pytest.raises(Exception):
        sess.run(bs.Map(bs.Const(2, np.arange(8, dtype=np.int32)),
                        boom, out=[np.int32]))
    dumps = glob.glob(str(tmp_path / "flightrec-*.json"))
    assert dumps, "fatal run did not dump a flight record"
    with open(dumps[0]) as fp:
        doc = json.load(fp)
    assert "injected fatal for flightrec" in doc["reason"]
    assert doc["task_states"]
    assert isinstance(doc["events"], list)
    sess.shutdown()


def test_flight_recorder_noop_without_dir(monkeypatch):
    monkeypatch.delenv("BIGSLICE_FLIGHTREC_DIR", raising=False)
    hub = telemetry_mod.TelemetryHub()
    hub._emit("bigslice:test", op="x")
    assert hub.dump_flight_record(inv=1, reason="r") is None


def test_slicetrace_renders_compile_and_device_sections(tmp_path,
                                                        capsys):
    """The hub's compile/hbm instants ride the tracer, so a recorded
    trace renders the invN:compile and invN:device sections offline."""
    from bigslice_tpu.tools import slicetrace

    trace = str(tmp_path / "t.json")
    sess = _mesh_session(trace_path=trace)
    n = 1 << 13
    res = sess.run(bs.Reduce(
        bs.Const(16, np.arange(n, dtype=np.int32) % 997,
                 np.ones(n, np.int32)),
        lambda a, b: a + b))
    sum(len(f) for f in res.frames())
    res.discard()
    sess.shutdown()  # writes the trace
    report = slicetrace.analyze(trace)
    assert ":compile" in report
    assert "wall_ms" in report
    assert ":device" in report
    assert "in_use_MB" in report
