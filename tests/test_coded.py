"""Coded k-of-n redundant combines (exec/codedplan.py) + the
end-to-end deadline/cancellation ladder (PR-20).

The acceptance criteria this file pins:

- ``BIGSLICE_CODED`` unset is a TRUE chicken bit: no planner attaches,
  task partition_configs (the program-cache key seed) are byte-
  identical to the legacy shape, and the telemetry summary /
  Prometheus surface carry ZERO coded or deadline samples;
- the striped coverage map tolerates ANY r member losses: every unit
  has exactly r+1 distinct owners and any k-of-n subset covers every
  unit at least once;
- an engaged run is bit-identical to the off arm (duplicate coverage
  partials masked at the consumer read), with the full lifecycle
  visible in CodedStats (group → unit → covered → cancelled/masked);
- combine-boundary input cardinality (rows in, distinct-key ratio)
  lands in ``skew_of_op`` under the LOGICAL op name on both arms;
- ``Session.run(deadline_s=)`` cancels + drains past the budget and
  raises DeadlineExceeded, with per-outcome DeadlineStats accounting.
"""

import time

import numpy as np
import pytest

import bigslice_tpu as bs
from bigslice_tpu.exec import codedplan
from bigslice_tpu.exec.evaluate import DeadlineExceeded
from bigslice_tpu.exec.local import LocalExecutor
from bigslice_tpu.exec.session import Session
from bigslice_tpu.exec.task import TaskState, iter_tasks


def _add(a, b):
    return a + b


def _oracle(keys, vals):
    out = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        out[k] = out.get(k, 0) + v
    return out


def _keyed(rows=2000, nkeys=37, seed=7):
    rng = np.random.RandomState(seed)
    return (rng.randint(0, nkeys, rows).astype(np.int32),
            rng.randint(1, 5, rows).astype(np.int32))


@pytest.fixture
def no_coded(monkeypatch):
    monkeypatch.delenv("BIGSLICE_CODED", raising=False)
    monkeypatch.delenv("BIGSLICE_CODED_REDUNDANCY", raising=False)


# ------------------------------------------------- planner unit layer

def test_plan_mode_parsing(monkeypatch):
    monkeypatch.delenv("BIGSLICE_CODED", raising=False)
    assert codedplan.plan_mode() == "off"
    assert codedplan.plan_mode("off") == "off"
    assert codedplan.plan_mode("combine") == "combine"
    with pytest.raises(ValueError):
        codedplan.plan_mode("parity")
    monkeypatch.setenv("BIGSLICE_CODED", "combine")
    assert codedplan.plan_mode() == "combine"


def test_redundancy_defaults_and_override():
    # Default: ceil(k/8), floored at 1 — ~12% overhead at scale, one
    # spare at test scale.
    assert codedplan.redundancy(2) == 1
    assert codedplan.redundancy(8) == 1
    assert codedplan.redundancy(9) == 2
    assert codedplan.redundancy(64) == 8
    assert codedplan.redundancy(8, "3") == 3
    with pytest.raises(ValueError):
        codedplan.redundancy(8, "0")
    with pytest.raises(ValueError):
        codedplan.redundancy(8, "nope")


@pytest.mark.parametrize("k,r", [(2, 1), (5, 1), (8, 1), (8, 3),
                                 (9, 2), (16, 2)])
def test_striped_coverage_tolerates_any_r_losses(k, r):
    grp = codedplan.CoverageGroup(1, "op", k, r)
    assert grp.n == k + r
    # Every unit has exactly r+1 DISTINCT owners; owners/covers agree.
    for u in range(k):
        owners = grp.owners(u)
        assert len(owners) == r + 1 == len(set(owners))
        for i in owners:
            assert u in grp.covers(i)
    # Total assigned work is exactly k units per... (r+1) replicas.
    assert sum(len(grp.covers(i)) for i in range(grp.n)) == k * (r + 1)
    # ANY r losses leave every unit at least one live owner (exhaustive
    # over single+adjacent-run loss patterns, the stripe's worst case,
    # plus a deterministic scatter).
    import itertools

    pats = [set(range(s, s + r)) for s in range(grp.n - r + 1)]
    pats += [set(p) for p in itertools.islice(
        itertools.combinations(range(grp.n), r), 64)]
    for lost in pats:
        lost = {x % grp.n for x in lost}
        for u in range(k):
            assert any(i not in lost for i in grp.owners(u)), (u, lost)


def test_cover_name_is_per_unit_and_collision_free():
    grp = codedplan.CoverageGroup(3, "reduce@x:8", 8, 2)
    names = {grp.cover_name(u, i)
             for u in range(grp.k) for i in range(4)}
    assert len(names) == 8 * 4
    nm = grp.cover_name(5, 2)
    assert nm.inv_index == 3 and nm.shard == 2


def test_group_for_respects_mode_and_min_k(no_coded):
    assert codedplan.planner_from_env() is None
    planner = codedplan.CodedPlanner(mode="combine")
    assert planner.group_for(1, "op", 1) is None  # k < MIN_K
    grp = planner.group_for(1, "op", 8)
    assert grp is not None and (grp.k, grp.r) == (8, 1)
    assert planner.stats.count("group") == 1
    off = codedplan.CodedPlanner(mode="off")
    assert off.group_for(1, "op", 8) is None


# ------------------------------------- chicken bit: off is bit-legacy

def test_unset_knob_leaves_no_trace(no_coded):
    """The load-bearing chicken-bit assertion: with BIGSLICE_CODED
    unset nothing attaches, partition_config keeps the legacy shape
    (program-cache keys unchanged), and the telemetry summary +
    Prometheus surface carry zero coded/deadline samples."""
    sess = Session(executor=LocalExecutor(procs=4))
    assert sess.coded is None
    assert sess.telemetry.coded is None
    keys, vals = _keyed()
    res = sess.run(bs.Reduce(bs.Const(8, keys, vals), _add))
    assert dict(res.rows()) == _oracle(keys, vals)
    for t in iter_tasks(res.tasks):
        assert getattr(t, "coded_group", None) is None
        assert not any(str(c).startswith("coded:")
                       for c in t.partition_config if c is not None)
        assert "~k" not in t.name.op and "~cov" not in t.name.op
    doc = sess.telemetry.summary()
    assert "coded" not in doc and "deadline" not in doc
    text = sess.telemetry.prometheus_text()
    assert "bigslice_coded" not in text
    assert "bigslice_deadline" not in text


# ----------------------------------- engaged: parity + lifecycle

def _run_reduce(procs=4, shards=8, **env):
    keys, vals = _keyed()
    sess = Session(executor=LocalExecutor(procs=procs))
    res = sess.run(bs.Reduce(bs.Const(shards, keys, vals), _add))
    return sess, sorted(res.rows())


def test_coded_combine_is_bit_identical_to_off(monkeypatch):
    monkeypatch.delenv("BIGSLICE_CODED", raising=False)
    _, off_rows = _run_reduce()
    monkeypatch.setenv("BIGSLICE_CODED", "combine")
    sess, coded_rows = _run_reduce()
    assert coded_rows == off_rows
    st = sess.telemetry.coded
    assert st is not None and st.mode == "combine"
    assert st.count("group") == 1
    assert st.count("covered") == 1
    # k=8, r=1: coverage needs >= k units; every replica that ran
    # counts, so unit lands in [k, k*(r+1)].
    assert 8 <= st.count("unit") <= 16
    # The ladder's lifecycle is visible end to end.
    doc = sess.telemetry.summary()["coded"]
    assert doc["mode"] == "combine" and doc["counts"]["covered"] == 1
    text = sess.telemetry.prometheus_text()
    assert 'bigslice_coded_mode{mode="combine"} 1' in text
    assert 'bigslice_coded_events_total{action="covered"} 1' in text


def test_coded_members_carry_plan_marked_config(monkeypatch):
    monkeypatch.setenv("BIGSLICE_CODED", "combine")
    monkeypatch.setenv("BIGSLICE_CODED_REDUNDANCY", "2")
    keys, vals = _keyed()
    sess = Session(executor=LocalExecutor(procs=4))
    res = sess.run(bs.Reduce(bs.Const(8, keys, vals), _add))
    assert dict(res.rows()) == _oracle(keys, vals)
    members = [t for t in iter_tasks(res.tasks)
               if getattr(t, "coded_group", None) is not None]
    assert len(members) == 10  # n = k + r = 8 + 2
    grp = members[0].coded_group
    assert (grp.k, grp.r) == (8, 2)
    for t in members:
        assert t.partition_config[-1] == "coded:k8r2"
        assert t.spill_ineligible == "coded coverage partials"
    # Consumers keep the legacy config (their cache keys are
    # plan-independent — the coded suffix lives on members only).
    for t in iter_tasks(res.tasks):
        if getattr(t, "coded_group", None) is None:
            assert not any(str(c).startswith("coded:")
                           for c in t.partition_config
                           if c is not None)


def test_stragglers_cancelled_not_computed(monkeypatch):
    """Once coverage settles, redundant members flip to CANCELLED
    (cooperative, not fatal) instead of finishing work nobody reads —
    the no-speculative-duplicate half of the coded contract."""
    monkeypatch.setenv("BIGSLICE_CODED", "combine")
    keys, vals = _keyed()
    sess = Session(executor=LocalExecutor(procs=2))
    res = sess.run(bs.Reduce(bs.Const(8, keys, vals), _add))
    assert dict(res.rows()) == _oracle(keys, vals)
    st = sess.telemetry.coded
    members = [t for t in iter_tasks(res.tasks)
               if getattr(t, "coded_group", None) is not None]
    # A RUNNING straggler asked to stop settles at its next seam, which
    # may come after run() has returned: wait for that transition (the
    # timeout only bounds a hang), then read the states.
    states = {t.wait_state(TaskState.OK, timeout=60) for t in members}
    assert states <= {TaskState.OK, TaskState.CANCELLED}
    cancelled = sum(1 for t in members
                    if t.state == TaskState.CANCELLED)
    assert st.count("cancelled") >= cancelled
    if cancelled:
        # A cancelled member never committed its units — the masked
        # consumer read must have skipped it without a recompute.
        assert st.count("recovered") == 0


# ------------------------------- combine-boundary input cardinality

def test_combine_input_lands_in_skew_of_op(monkeypatch):
    """Satellite 3: rows INTO the map-side combine and the distinct-
    key ratio are recorded per op — on the off arm and, attributed to
    the LOGICAL op, on the coded arm."""
    keys = (np.arange(2000, dtype=np.int32) % 37)
    vals = np.ones(2000, dtype=np.int32)

    def run():
        sess = Session(executor=LocalExecutor(procs=4))
        sess.run(bs.Reduce(bs.Const(8, keys, vals), _add))
        ops = [op for op in sess.telemetry._ops
               if "~" not in op and "reduce" not in op]
        assert len(ops) == 1
        return sess.telemetry.skew_of_op(ops[0])

    monkeypatch.delenv("BIGSLICE_CODED", raising=False)
    off = run()
    assert off["combine_input_rows"] == 2000
    assert off["distinct_key_ratio"] == pytest.approx(
        (8 * 37) / 2000)
    monkeypatch.setenv("BIGSLICE_CODED", "combine")
    coded = run()
    # Coded counts every unit replica that ran: >= the logical rows,
    # same collapse ratio (combine is per-unit either way).
    assert coded["combine_input_rows"] >= 2000
    assert coded["distinct_key_ratio"] == pytest.approx(
        off["distinct_key_ratio"], rel=0.05)
    assert coded["total_rows"] >= off["total_rows"]


# ------------------------------------------------ deadline ladder

def test_deadline_exceeded_cancels_and_raises(no_coded):
    sess = Session(executor=LocalExecutor(procs=2))

    def slow(k, v):
        time.sleep(0.4)
        return (int(k), int(v))

    keys, vals = _keyed(rows=8)
    sl = bs.Map(bs.Const(4, keys, vals), slow,
                out=[np.int32, np.int32], mode="host")
    t0 = time.monotonic()
    with pytest.raises(DeadlineExceeded) as ei:
        sess.run(sl, deadline_s=0.05)
    assert ei.value.pending > 0
    assert time.monotonic() - t0 < 15.0  # drain is bounded
    st = sess.telemetry.deadline
    assert st is not None
    assert st.count("expired") == 1
    doc = sess.telemetry.summary()["deadline"]
    assert doc["by_source"].get("session", 0) == 1
    text = sess.telemetry.prometheus_text()
    assert ('bigslice_deadline_outcomes_total{tenant="_session",'
            'outcome="expired"} 1') in text


def test_deadline_met_and_validation(no_coded):
    sess = Session(executor=LocalExecutor(procs=4))
    keys, vals = _keyed(rows=400)
    res = sess.run(bs.Reduce(bs.Const(4, keys, vals), _add),
                   deadline_s=120.0)
    assert dict(res.rows()) == _oracle(keys, vals)
    assert sess.telemetry.deadline.count("met") == 1
    with pytest.raises(Exception):
        sess.run(bs.Const(2, keys), deadline_s=0.0)
    with pytest.raises(Exception):
        sess.run(bs.Const(2, keys), deadline_s=-1)


def test_deadline_not_retried_by_elastic_ladder(no_coded):
    """DeadlineExceeded must short-circuit Session.run's retry
    ladders — a budget miss retried from scratch would blow the
    budget again and double the caller's wait for the same 504."""
    sess = Session(executor=LocalExecutor(procs=2))
    calls = []

    def slow(x):
        calls.append(1)
        time.sleep(0.3)
        return int(x)

    with pytest.raises(DeadlineExceeded):
        sess.run(bs.Map(bs.Const(2, np.arange(4, dtype=np.int32)),
                        slow, out=[np.int32], mode="host"),
                 deadline_s=0.05)
    n_first = len(calls)
    time.sleep(0.8)  # would-be retry window
    assert len(calls) == n_first  # no second evaluation started
