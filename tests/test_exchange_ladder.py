"""The ``all_to_all`` exchange on meshes of more than one device: a
Q18-shaped job against the benchmark pipeline's numpy reference, the
slack ladder's sized rungs, the ``exchange`` block of
``telemetry_summary()`` and the retried wave's ``dispatch`` span."""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

import bigslice_tpu as bs
from bigslice_tpu.exec import meshexec
from bigslice_tpu.exec.meshexec import MeshExecutor
from bigslice_tpu.exec.session import Session
from bigslice_tpu.parallel import shuffle as shuffle_mod
from bigslice_tpu.utils import trace as trace_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: Q18's shapes at a tiny scale: 20,000 sparse keys, 1..7 lines an
#: order, 80,000 rows in random order, 20 shards.
CFG = {"orders_per_sf": 5000, "scale_factor": 4, "lines_per_order_max": 7,
       "quantity_max": 50, "having_sum_over": 200, "rows_per_shard": 4096}
ROWS = 4096


@pytest.fixture(scope="module")
def pipeline():
    spec = importlib.util.spec_from_file_location(
        "q18agg_pipeline", os.path.join(
            ROOT, "benchmarks", "configs", "tpch-q18agg", "pipeline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def mesh_session(ndev, **kw):
    mesh = Mesh(np.array(jax.devices()[:ndev]), ("shards",))
    return Session(executor=MeshExecutor(mesh), **kw)


# ------------------------------------------- (a) answers, mesh by mesh

@pytest.mark.parametrize("lowering", ["sort", "hash"])
@pytest.mark.parametrize("ndev", [1, 2, 4])
def test_q18_shaped_job_matches_the_reference(pipeline, monkeypatch,
                                              ndev, lowering):
    if lowering == "hash":  # asked for; "sort" is every backend's default
        monkeypatch.setenv("BIGSLICE_HASH_AGGREGATE", "1")
    data = pipeline.make_data(CFG, seed=28 + ndev)
    assert data.shards == 20 > ndev
    want = pipeline.reference(CFG, data)
    sess = mesh_session(ndev)
    try:
        job = pipeline.Job(sess, data, keep=True)
        for _, step in job.steps():
            step()
        job.discard()
        got = {**job.answers, **job.late_answers()}
    finally:
        sess.shutdown()
    assert set(got) == {"aggregate", "having"}
    assert len(want["having"][0]) > 0
    for name, (keys, sums) in want.items():
        np.testing.assert_array_equal(got[name][0], keys)
        np.testing.assert_array_equal(got[name][1], sums)


# ------------------------------------------------ the ladder's rungs

@pytest.mark.parametrize("slack,need,rung", [
    (1.0, 1.0028, 1.03125), (1.0, 1.0, 1.03125), (1.0, 1.03125, 1.03125),
    (1.0, 1.21, 1.21875), (1.96875, 1.9, 2.0), (1.96875, 1.97, 2.0),
    (2.0, 2.0, 2.0625), (2.0, 2.3, 2.3125), (1.0, 3.99, 4.0),
    (1.0, 4.0, 4.0), (1.0, 7.9, 8.0), (2.0, 17.0, 17.0),
])
def test_slack_rung_holds_the_need_and_moves_up(slack, need, rung):
    """Thirty-two rungs an octave: the smallest that holds ``need``,
    and always above the slack that overflowed."""
    assert meshexec._slack_rung(slack, need) == rung
    assert rung >= need and rung > slack


# ------------------------------------ (b), (c): the exchange, observed

def distinct_keys_job(sess, waves, ndev, seed):
    """A Reduce whose keys never combine inside a shard, so at slack
    1.0 a destination's mean load IS its bucket: the first wave
    overflows. Returns (result, number of groups)."""
    n = waves * ndev * ROWS
    keys = np.random.default_rng(seed).permutation(n).astype(np.int32)
    res = sess.run(bs.Reduce(
        bs.Const(waves * ndev, keys, np.ones(n, np.int32)),
        lambda a, b: a + b))
    return res, n


def exchange_blocks(summary):
    return {op: rec["exchange"] for op, rec in summary["ops"].items()
            if "exchange" in rec}


@pytest.fixture(scope="module")
def overflowing(tmp_path_factory):
    """Two jobs of 3 waves on a mesh of 4, in one traced session:
    (blocks after job 1, blocks after job 2, the executor's gauges,
    groups a job, trace events)."""
    path = str(tmp_path_factory.mktemp("exchange") / "trace.json")
    sess = mesh_session(4, trace_path=path)
    try:
        res, groups = distinct_keys_job(sess, 3, 4, seed=1)
        assert len(res.rows()) == groups
        first = exchange_blocks(sess.telemetry_summary())
        res, _ = distinct_keys_job(sess, 3, 4, seed=2)
        assert len(res.rows()) == groups
        second = exchange_blocks(sess.telemetry_summary())
        gauges = sess.executor.resource_stats()["gauges"]
    finally:
        sess.shutdown()
    with open(path) as fp:
        events = [e for e in json.load(fp)["traceEvents"]
                  if e.get("pid") == trace_mod.SPAN_PID]
    return first, second, gauges, groups, events


def test_first_job_retries_and_settles_on_a_sized_rung(overflowing):
    first, second, gauges, groups, _ = overflowing
    (op1, job1), = first.items()
    (op2, job2), = ((op, b) for op, b in second.items() if op != op1)
    assert second[op1] == job1            # a finished op's block stands
    # Uniform keys that miss slack 1.0 by a few percent settle on a rung
    # sized from the overflow signal — a thirty-second of an octave —
    # not on the worst-case-skew buffers (slack = 4 devices).
    (slack,) = gauges["shuffle_slack"].values()
    assert slack == job1["slack"] == job2["slack"]
    assert 1.0 < slack < 1.5 and slack * 32 == int(slack * 32)
    assert job1["retries"] >= 1 and job2["retries"] == 0
    assert job1["waves"] == 3 + job1["retries"] and job2["waves"] == 3
    # Rows a device of the merged map-side output: every group once.
    for job in (job1, job2):
        assert len(job["recv_rows"]) == 4
        assert sum(job["recv_rows"]) == groups
    # The static plan: every wave moves N(N-1) whole buckets of
    # (key, value, subid) rows.
    send_cap = shuffle_mod.send_capacity(ROWS, 4, slack)
    assert job2["ici_messages"] == 3 * 4 * 3
    assert job2["ici_bytes"] == 3 * 4 * 3 * send_cap * (8 + 4)
    assert job1["ici_bytes"] > job2["ici_bytes"]


def test_retried_waves_dispatch_span_carries_attempt(overflowing):
    first, _, _, _, events = overflowing
    (job1,) = first.values()
    dispatches = [e["args"] for e in events if e["name"] == "dispatch"]
    again = [a for a in dispatches if "attempt" in a]
    assert len(again) == job1["retries"] >= 1
    assert {a["attempt"] for a in again} == {1}
    assert all(a["program"] == "bs_group_shuffle" for a in again)
    # A wave's first dispatch carries none.
    assert len(dispatches) - len(again) == 2 * (3 + 3)


def test_mesh_of_one_has_no_exchange_block_and_no_ladder():
    sess = mesh_session(1)
    try:
        res, groups = distinct_keys_job(sess, 3, 1, seed=3)
        assert len(res.rows()) == groups
        summary = sess.telemetry_summary()
        gauges = sess.executor.resource_stats()["gauges"]
    finally:
        sess.shutdown()
    assert exchange_blocks(summary) == {}
    assert gauges["shuffle_slack"] == {}
    moved = summary["device"]["exchange"]
    assert moved and all(
        e["ici_bytes"] == 0 and e["retries"] == 0 for e in moved.values())


# ------------------------- the ladder with a wave in flight behind it

@pytest.mark.parametrize("ndev", [2, 4])
def test_slack_only_climbs_when_waves_settle_after_the_next_dispatch(
        monkeypatch, ndev):
    """The pipelined loop as a TPU runs it (on the CPU its in-flight
    window is 0, so it is given another backend name to find): every
    wave overflows slack 1.0, each was dispatched before the one ahead
    of it retried, and the op's memoised slack is read at every settle:
    it never falls, every retry is dispatched on a rung at least the
    memo's, and the job's answer is the serial loop's."""
    monkeypatch.setattr(jax, "default_backend", lambda: "in-flight")
    mesh = Mesh(np.array(jax.devices()[:ndev]), ("shards",))
    ex = MeshExecutor(mesh, prefetch_depth=1)
    seen, attempts = [], []
    read, dispatch = ex._read_signals, ex._dispatch_wave_on

    def logged_read(signals):
        got = read(signals)
        seen.append((max(ex._slack_memo.values(), default=1.0), got[0]))
        return got

    def logged_dispatch(tasks, wave, inputs, attempt=0):
        out = dispatch(tasks, wave, inputs, attempt)
        if out[1][-1][0] == "shuffle":
            attempts.append((wave, attempt, out[2]))
        return out

    ex._read_signals, ex._dispatch_wave_on = logged_read, logged_dispatch
    sess = Session(executor=ex)
    try:
        res, groups = distinct_keys_job(sess, 3, ndev, seed=5)
        assert len(res.rows()) == groups
        (block,) = exchange_blocks(sess.telemetry_summary()).values()
    finally:
        sess.shutdown()
    memos = [m for m, _ in seen]
    assert memos == sorted(memos) and memos[-1] == block["slack"] > 1.0
    firsts = [a for a in attempts if a[1] == 0]
    retries = [a for a in attempts if a[1] > 0]
    assert [w for w, _, _ in firsts] == [0, 1, 2]
    assert firsts[0][2] == firsts[1][2] == 1.0   # wave 1: before the retry
    assert block["retries"] == len(retries) >= 2
    running = 1.0
    for _, _, slack in retries:
        assert slack >= running
        running = slack


# ------------------------------------ where the ladder starts and ends

def _shuffling_task(combiner, nparts=46):
    from types import SimpleNamespace as NS

    return NS(num_partition=nparts, name=NS(op="shuffle@here"),
              partitioner=NS(combiner=combiner))


@pytest.mark.parametrize("ndev,combiner,start", [
    (1, None, 1.0), (4, None, 2.0), (8, None, 2.0),
    (1, object(), 1.0), (4, object(), 1.0), (8, object(), 1.0),
])
def test_a_shuffle_starts_no_higher_than_the_top_rung(ndev, combiner,
                                                      start):
    """A plain shuffle starts at slack 2.0, a combiner-bearing one at
    1.0 — and neither above the rung at which overflow is impossible:
    to one destination a bucket of slack 1.0 holds a wave's every row."""
    ex = MeshExecutor(Mesh(np.array(jax.devices()[:ndev]), ("shards",)))
    assert ex._full_slack(_shuffling_task(combiner)) == float(ndev)
    assert ex._wave_slack(_shuffling_task(combiner)) == start
    # Fewer partitions than devices: the destinations are what counts.
    assert ex._wave_slack(_shuffling_task(None, nparts=2)) == min(
        2.0, float(min(2, ndev)))
    # What a retry remembered stands.
    ex._slack_memo["shuffle@here"] = 1.25
    assert ex._wave_slack(_shuffling_task(combiner)) == 1.25


def test_top_rung_of_the_ladder_and_of_the_start_are_one_function():
    """``_full_slack`` alone says where the ladder ends: lowered to 1.0
    on a mesh of four, a plain shuffle starts there, and the wave whose
    buckets overflow it has no rung left to retry on."""
    from bigslice_tpu.exec.task import TaskError

    ex = MeshExecutor(Mesh(np.array(jax.devices()[:4]), ("shards",)))
    asked = []
    ex._full_slack = lambda task: asked.append(task.num_partition) or 1.0
    assert ex._wave_slack(_shuffling_task(None)) == 1.0
    assert asked == [46]
    sess = Session(executor=ex)
    try:
        with pytest.raises((TaskError, RuntimeError),
                           match="even at full slack"):
            distinct_keys_job(sess, 3, 4, seed=7)
    finally:
        sess.shutdown()
    # The start of every dispatched wave, and the retry that found no
    # rung: the job's own shuffle of 12 partitions.
    assert set(asked[1:]) == {12} and len(asked) > 2
    assert not ex._slack_memo


def test_every_row_to_one_partition_on_a_mesh_of_one_settles_at_1():
    """A plain shuffle on a mesh of one has one destination: at slack
    1.0 its bucket is the wave's capacity, so the most skewed routing
    there is — every row to partition 0 — fits without a retry, and the
    wave emits ``cap`` slots, not 2 × ``cap``."""
    ex = MeshExecutor(Mesh(np.array(jax.devices()[:1]), ("shards",)))
    dispatch, settle = ex._dispatch_wave_on, ex._execute_wave_on_locked
    slacks, outs = [], []

    def logged_dispatch(tasks, wave, inputs, attempt=0):
        out = dispatch(tasks, wave, inputs, attempt)
        if any(kind == "shuffle" for kind, _, _ in out[1]):
            slacks.append((attempt, out[2], inputs[0][2]))
        return out

    def logged_settle(*args):
        out = settle(*args)
        if out.partitioned:
            outs.append(out.capacity)
        return out

    ex._dispatch_wave_on = logged_dispatch
    ex._execute_wave_on_locked = logged_settle
    sess = Session(executor=ex)
    try:
        n = 3 * ROWS
        keys = np.arange(n, dtype=np.int32)
        res = sess.run(bs.Repartition(bs.Const(3, keys, keys),
                                      lambda k, nparts: 0 * k))
        assert sorted(res.rows()) == [(k, k) for k in range(n)]
        summary = sess.telemetry_summary()
    finally:
        sess.shutdown()
    assert slacks == [(0, 1.0, ROWS)] * 3
    assert outs == [ROWS] * 3
    assert not ex._slack_memo
    assert all(e["retries"] == 0
               for e in summary["device"]["exchange"].values())
    # The merge behind it: three full waves, nothing to leave out.
    (merge,) = [op["merge"] for op in summary["ops"].values()
                if "merge" in op]
    assert merge == {"merges": 1, "waves": 3, "slots": n,
                     "slots_full": n, "rows_bound": n}
