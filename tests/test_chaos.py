"""Fault-injection tests (mirrors exec/chaosmonkey_test.go:44-103):
random loss of stored task outputs while a shuffle pipeline runs; the
run must still complete correctly via lost-task resubmission — plus the
deterministic fault-injection plane (utils/faultinject.py): seeded
plans over named seams in every recovery-critical layer, replayable
injection logs, and the chaos matrix over the mesh executor."""

import json
import threading
import time

import numpy as np
import pytest

import bigslice_tpu as bs
from bigslice_tpu.exec import store as store_mod
from bigslice_tpu.exec.local import LocalExecutor
from bigslice_tpu.exec.session import Session
from bigslice_tpu.exec.task import TaskName
from bigslice_tpu.utils import faultinject


class FlakyStore(store_mod.MemoryStore):
    """Randomly drops committed outputs on read — the moral equivalent of
    machines dying between producing and serving shuffle data."""

    def __init__(self, rng, loss_rate=0.04, max_losses=8):
        super().__init__()
        self.rng = rng
        self.loss_rate = loss_rate
        self.losses = 0
        self.max_losses = max_losses
        self._flock = threading.Lock()

    def read(self, name, partition):
        with self._flock:
            sabotage = (self.losses < self.max_losses
                        and self.rng.rand() < self.loss_rate)
            if sabotage:
                self.losses += 1
        if sabotage:
            self.discard(name)
        return super().read(name, partition)


def test_reduce_survives_random_output_loss(monkeypatch):
    # Loosen the consecutive-loss cap the way the reference's chaos test
    # shortens ProbationTimeout (exec/chaosmonkey_test.go:58-61): the
    # point is recovery, not the cap.
    import sys

    import bigslice_tpu.exec.evaluate  # noqa: F401 — ensure module import

    evaluate_mod = sys.modules["bigslice_tpu.exec.evaluate"]
    monkeypatch.setattr(evaluate_mod, "MAX_CONSECUTIVE_LOST", 25)
    rng = np.random.RandomState(0)
    store = FlakyStore(rng)
    sess = Session(executor=LocalExecutor(procs=4, store=store))
    keys = np.arange(2000, dtype=np.int32) % 97
    vals = np.ones(2000, dtype=np.int32)
    r = bs.Reduce(bs.Const(10, keys, vals), lambda a, b: a + b)
    res = sess.run(r)
    oracle = {}
    for k in keys.tolist():
        oracle[k] = oracle.get(k, 0) + 1
    assert dict(res.rows()) == oracle
    assert store.losses > 0  # chaos actually happened


def test_discard_races_evaluation():
    """Concurrent discard + re-read (TestDiscardChaos analog)."""
    sess = Session()
    base = sess.run(bs.Const(6, np.arange(600, dtype=np.int32)))
    stop = threading.Event()
    errs = []

    def discarder():
        while not stop.is_set():
            base.tasks[0].session = None  # no-op poke
            base.discard()
            time.sleep(0.01)

    t = threading.Thread(target=discarder, daemon=True)
    t.start()
    try:
        for _ in range(10):
            rows = sorted(base.rows())
            assert rows == [(i,) for i in range(600)]
    finally:
        stop.set()
        t.join(timeout=5)


def test_slicer_oom_mode(capsys):
    """Round-5 verdict #8: the memory-pressure scenario must drive BOTH
    relief paths — the HBM-budget wave splitter and the host shuffle
    spill — and complete exactly (cmd/slicer/main.go:20-36's oom mode,
    re-expressed for budgets instead of the OS OOM killer)."""
    from bigslice_tpu import sliceconfig
    from bigslice_tpu.tools import slicer

    assert slicer.main(["-local", "oom", "-rows", "20000",
                        "-shards", "8"]) == 0
    out = capsys.readouterr().out
    assert "slicer oom" in out
    assert "split K=" in out and "spilled" in out


# -- the deterministic fault-injection plane (utils/faultinject.py) -------


@pytest.fixture
def chaos():
    """Install a seeded fault plan for the test; always cleared after."""
    def _install(spec):
        return faultinject.install(faultinject.parse_plan(spec))

    yield _install
    faultinject.clear()


def _reduce_oracle(keys, vals):
    out = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        out[k] = out.get(k, 0) + v
    return out


def _keyed(rows=800, nkeys=41, seed=11):
    rng = np.random.RandomState(seed)
    return (rng.randint(0, nkeys, rows).astype(np.int32),
            rng.randint(0, 100, rows).astype(np.int32))


def test_faultplan_decisions_are_seed_deterministic():
    spec = "7:store.read=0.3x50,io.read=0.2"
    seq = ["store.read"] * 40 + ["io.read"] * 40
    a = faultinject.parse_plan(spec)
    b = faultinject.parse_plan(spec)
    da = [a.fire(s) is not None for s in seq]
    db = [b.fire(s) is not None for s in seq]
    assert da == db and any(da)
    # A different seed must produce a different firing pattern.
    c = faultinject.parse_plan("8:" + spec.split(":", 1)[1])
    assert [c.fire(s) is not None for s in seq] != da
    # The log is the decisions, keyed by (site, inv_id) — identical up
    # to the wall-clock stamp.
    strip = lambda log: [(e["site"], e["kind"], e["inv_id"])  # noqa: E731
                         for e in log]
    assert strip(a.snapshot()["log"]) == strip(b.snapshot()["log"])


def test_faultplan_budget_caps_fires():
    plan = faultinject.parse_plan("3:io.read=1.0x2")
    fired = [plan.fire("io.read") for _ in range(10)]
    assert sum(f is not None for f in fired) == 2
    assert plan.snapshot()["calls"]["io.read"] == 10


def test_faultplan_spec_validation():
    for bad in ("nocolon", "x:io.read=0.5", "7:io.read",
                "7:frobnicate=0.5", "7:io.read=2.0",
                "7:io.read=0.5~frob", "7:io.read=0.5x-1"):
        with pytest.raises(ValueError):
            faultinject.parse_plan(bad)
    # Globs skip site validation; kinds resolve per matched site.
    plan = faultinject.parse_plan("7:store.*=1.0x1")
    assert plan.fire("store.read").kind == "lose"


def test_injected_errors_carry_attributable_site():
    f = faultinject.Fault("io.read", "io", 3)
    e = faultinject.injected_error(f)
    assert isinstance(e, IOError)
    wrapped = RuntimeError("outer")
    wrapped.__cause__ = e
    assert faultinject.fault_site_of(wrapped) == "io.read"
    assert faultinject.fault_site_of(RuntimeError("clean")) is None
    infra = faultinject.injected_error(
        faultinject.Fault("mesh.dispatch", "infra", 0))
    from bigslice_tpu.exec.meshexec import _looks_like_infra_error

    assert _looks_like_infra_error(infra)


def test_install_from_env(monkeypatch):
    monkeypatch.setenv("BIGSLICE_CHAOS", "5:io.read=0.5x1")
    try:
        plan = faultinject.install_from_env()
        assert plan is not None and plan.seed == 5
        assert faultinject.active_plan() is plan
    finally:
        faultinject.clear()
    assert faultinject.active_plan() is None


# -- store/file tier: quarantine, retries, prefetch isolation -------------


def _put_one(store, name, rows=64):
    frame_src = bs.Const(1, np.arange(rows, dtype=np.int32))
    frames = list(frame_src.reader(0, []))
    store.put(name, 0, frames)
    return [tuple(r) for f in frames for r in f.rows()]


def test_filestore_corruption_quarantined_to_missing(tmp_path):
    store = store_mod.FileStore(str(tmp_path))
    name = TaskName(0, "op", 0, 1)
    _put_one(store, name)
    path = store._path(name, 0)
    with open(path, "r+b") as fp:  # flip one payload byte mid-file
        fp.seek(40)
        b = fp.read(1)
        fp.seek(40)
        fp.write(bytes([b[0] ^ 0x40]))
    with pytest.raises(store_mod.Missing):
        list(store.read(name, 0))
    assert store.quarantined == 1
    # Quarantined file stops counting as committed -> recompute path.
    assert not store.committed(name, 0)
    import os

    assert any(fn.endswith(".quarantine") for fn in os.listdir(
        os.path.dirname(path)))


def test_injected_codec_corruption_quarantines(tmp_path, chaos):
    store = store_mod.FileStore(str(tmp_path))
    name = TaskName(0, "op", 0, 1)
    _put_one(store, name)
    chaos("3:codec.read=1.0x1~truncate")
    with pytest.raises(store_mod.Missing):
        list(store.read(name, 0))
    assert store.quarantined == 1


def test_io_read_transient_retried(tmp_path, chaos):
    store = store_mod.FileStore(str(tmp_path))
    name = TaskName(0, "op", 0, 1)
    rows = _put_one(store, name)
    # Two injected transient failures, default budget of 2 retries:
    # the read succeeds without surfacing anything.
    chaos("3:io.read=1.0x2")
    got = [tuple(r) for f in store.read(name, 0) for r in f.rows()]
    assert got == rows


def test_io_retries_exhaust(tmp_path, chaos, monkeypatch):
    monkeypatch.setenv("BIGSLICE_IO_RETRIES", "0")
    monkeypatch.setenv("BIGSLICE_IO_BACKOFF", "0")
    store = store_mod.FileStore(str(tmp_path))
    name = TaskName(0, "op", 0, 1)
    _put_one(store, name)
    chaos("3:io.read=1.0x1")
    with pytest.raises(faultinject.InjectedIOError):
        list(store.read(name, 0))


def test_store_put_transient_retried(tmp_path, chaos):
    chaos("3:store.put=1.0x2")
    store = store_mod.FileStore(str(tmp_path))
    name = TaskName(0, "op", 0, 1)
    rows = _put_one(store, name)  # injected entry faults retried away
    got = [tuple(r) for f in store.read(name, 0) for r in f.rows()]
    assert got == rows


def test_prefetch_worker_survives_poisoned_item(tmp_path):
    """Satellite regression: one raising prefetch read can never kill
    the prefetch worker (or its respawn) for the session."""
    store = store_mod.FileStore(str(tmp_path))
    bad = TaskName(0, "bad", 0, 1)
    good = TaskName(0, "good", 0, 1)
    _put_one(store, bad)
    rows = _put_one(store, good)

    orig = store._prefetch_one

    def poisoned(key, gen):
        if key[0] is bad or key[0] == bad:
            raise RuntimeError("poisoned prefetch bookkeeping")
        return orig(key, gen)

    store._prefetch_one = poisoned
    store.prefetch(bad, 0)
    store.prefetch(good, 0)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        with store._warm_lock:
            if (good, 0) in store._warm:
                break
        time.sleep(0.01)
    with store._warm_lock:
        assert (good, 0) in store._warm
        assert not store._warm_pending
    # Warm hit serves the read; the poisoned key's direct read works.
    got = [tuple(r) for f in store.read(good, 0) for r in f.rows()]
    assert got == rows
    assert list(store.read(bad, 0)) is not None
    # The worker retired cleanly: a later hint spawns a fresh one.
    store._prefetch_one = orig
    store.discard(good)
    _put_one(store, good)
    store.prefetch(good, 0)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        with store._warm_lock:
            if (good, 0) in store._warm:
                break
        time.sleep(0.01)
    with store._warm_lock:
        assert (good, 0) in store._warm


# -- full-plan chaos runs: local executor ---------------------------------


def test_local_chaos_plan_recovers_bit_identical(tmp_path, chaos):
    keys, vals = _keyed(rows=4000, nkeys=97)
    oracle = _reduce_oracle(keys, vals)

    def run(store_dir):
        sess = Session(executor=LocalExecutor(
            procs=4, store=store_mod.FileStore(str(store_dir))))
        res = sess.run(bs.Reduce(bs.Const(8, keys, vals),
                                 lambda a, b: a + b))
        return dict(res.rows()), sess

    base, _ = run(tmp_path / "base")
    assert base == oracle
    plan = chaos("7:store.read=0.15x5,codec.read=0.2x3~flip,"
                 "io.read=0.3x4,store.put=0.3x3,eval.resubmit=0.1x2")
    got, sess = run(tmp_path / "chaos")
    assert got == base  # bit-identical to the fault-free run
    snap = plan.snapshot()
    assert sum(snap["injected"].values()) > 0
    summary = sess.telemetry_summary()
    rec = summary["recovery"]
    assert rec["recovered_total"] > 0 and rec["fatal_total"] == 0
    assert "store.read" in rec["by_site"]
    assert summary["chaos"]["injected"] == snap["injected"]
    # Prometheus surfaces both the injections and the recoveries.
    text = sess.telemetry.prometheus_text()
    assert "bigslice_fault_injected_total" in text
    assert 'bigslice_task_recovered_total{site="store.read"}' in text
    assert "bigslice_task_recovery_seconds" in text


# -- full-plan chaos runs: mesh executor (the chaos matrix) ---------------


def _mesh(n=4):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n]), ("shards",))


def _mesh_run(prefetch, arena, keys, vals, elastic=0):
    from bigslice_tpu.exec.meshexec import MeshExecutor

    sess = Session(
        executor=MeshExecutor(_mesh(), prefetch_depth=prefetch,
                              staging_arena=arena),
        elastic=elastic,
    )
    res = sess.run(bs.Reduce(bs.Const(8, keys, vals),
                             lambda a, b: a + b))
    return dict(res.rows()), sess


MESH_CHAOS_SPEC = ("5:mesh.dispatch=1.0x1~infra,staging.assemble=1.0x2,"
                   "shuffle.upload=1.0x2,store.read=0.25x4,"
                   "eval.resubmit=0.15x2")


@pytest.mark.parametrize("arena", [True, False], ids=["arena", "noarena"])
@pytest.mark.parametrize("prefetch", [0, 2], ids=["pf0", "pf2"])
def test_mesh_chaos_matrix(prefetch, arena, chaos):
    """The seeded chaos matrix of ISSUE 5: under a fixed plan mixing an
    SPMD infra fault (probation -> host resubmit), staging/upload
    transients, memory-store loss, and lost submissions, every
    (arena, prefetch) config completes bit-identical to fault-free."""
    keys, vals = _keyed()
    base, _ = _mesh_run(prefetch, arena, keys, vals)
    assert base == _reduce_oracle(keys, vals)
    plan = chaos(MESH_CHAOS_SPEC)
    got, sess = _mesh_run(prefetch, arena, keys, vals)
    assert got == base
    snap = plan.snapshot()
    assert snap["injected"].get("mesh.dispatch") == 1
    rec = sess.telemetry_summary().get("recovery")
    assert rec is not None and rec["fatal_total"] == 0


def test_mesh_chaos_deterministic_replay(chaos):
    """Same seed -> same injection log, (site, kind, inv_id) for
    (site, kind, inv_id) — chaos failures replay, they don't flake."""
    keys, vals = _keyed()

    def one_run():
        plan = chaos(MESH_CHAOS_SPEC)
        got, _ = _mesh_run(0, True, keys, vals)
        faultinject.clear()
        return got, [(e["site"], e["kind"], e["inv_id"])
                     for e in plan.snapshot()["log"]]

    got1, log1 = one_run()
    got2, log2 = one_run()
    assert got1 == got2 == _reduce_oracle(keys, vals)
    assert sorted(log1) == sorted(log2) and log1


def test_mesh_injected_host_loss_elastic(chaos, monkeypatch):
    """One injected gang-member loss: the session backs off, re-forms
    the mesh (elastic), and completes bit-identical."""
    monkeypatch.setenv("BIGSLICE_ELASTIC_BACKOFF", "0.01")
    keys, vals = _keyed()
    events = []

    def eventer(name, **fields):
        events.append(name)

    from bigslice_tpu.exec.meshexec import MeshExecutor

    plan = chaos("9:mesh.dispatch=1.0x1~hostloss")
    sess = Session(executor=MeshExecutor(_mesh()), elastic=1,
                   eventer=eventer)
    res = sess.run(bs.Reduce(bs.Const(8, keys, vals),
                             lambda a, b: a + b))
    assert dict(res.rows()) == _reduce_oracle(keys, vals)
    assert plan.snapshot()["injected"] == {"mesh.dispatch": 1}
    assert "bigslice:elasticBackoff" in events
    assert "bigslice:elasticRetry" in events


def test_elastic_backoff_knob(monkeypatch):
    from bigslice_tpu.exec.session import _elastic_backoff_delay

    monkeypatch.setenv("BIGSLICE_ELASTIC_BACKOFF", "0")
    assert _elastic_backoff_delay(0) == 0.0
    monkeypatch.setenv("BIGSLICE_ELASTIC_BACKOFF", "0.2")
    d0, d2 = _elastic_backoff_delay(0), _elastic_backoff_delay(2)
    assert 0.2 <= d0 <= 0.3 and 0.8 <= d2 <= 1.1


# -- drain-timeout census -------------------------------------------------


def test_drain_timeout_reports_wedged_tasks():
    import sys

    import bigslice_tpu.exec.evaluate  # noqa: F401 — module import

    evaluate_mod = sys.modules["bigslice_tpu.exec.evaluate"]
    from bigslice_tpu.exec.task import Partitioner, Task, TaskState
    from bigslice_tpu.utils.status import chain_monitors
    from bigslice_tpu.utils.telemetry import TelemetryHub

    hub = TelemetryHub()
    task = Task(TaskName(0, "wedged-op", 0, 1), do=None, deps=(),
                partitioner=Partitioner(), schema=None)
    task.set_state(TaskState.RUNNING)
    ev = evaluate_mod._Evaluation(None, [task], chain_monitors(hub))
    ev._drain(timeout=0.3)
    summary = hub.summary()
    assert summary["drain"]["timeouts"] == 1
    wedged = summary["drain"]["wedged"]
    assert wedged and wedged[0]["task"].endswith("wedged-op@1:0")
    assert wedged[0]["state"] == "RUNNING"
    assert "bigslice_drain_timeout_total 1" in hub.prometheus_text()


# -- the chaosslice CLI ---------------------------------------------------


def test_chaosslice_cli_local(tmp_path, capsys):
    from bigslice_tpu.tools import chaosslice

    out_json = tmp_path / "matrix.json"
    rc = chaosslice.main([
        "-chaos", "7:store.read=0.2x3,io.read=0.5x2,codec.read=0.3x1~flip",
        "-rows", "2000", "-shards", "4", "-json", str(out_json),
    ])
    captured = capsys.readouterr().out
    assert rc == 0, captured
    assert "recovery matrix" in captured
    assert "bit-identical" in captured
    doc = json.loads(out_json.read_text())
    assert doc["ok"] and doc["bit_identical"]
    assert any(r["site"] == "store.read" for r in doc["matrix"])
    assert faultinject.active_plan() is None  # CLI cleans up


# -- the out-of-core spill exchange's chaos sites -------------------------
#
# Under BIGSLICE_SHUFFLE=spill every shuffle boundary writes its
# partitions through the spill FileStore (exec/shuffleplan.py), so the
# run exercises the new spill.write/spill.read seams plus the existing
# codec corruption -> quarantine ladder on the spilled files.


@pytest.fixture
def spill_mode(monkeypatch):
    monkeypatch.setenv("BIGSLICE_SHUFFLE", "spill")


def _spill_run(keys, vals, elastic=0, **ex):
    from bigslice_tpu.exec.meshexec import MeshExecutor

    sess = Session(executor=MeshExecutor(_mesh(), **ex),
                   elastic=elastic)
    res = sess.run(bs.Reduce(bs.Const(16, keys, vals),
                             lambda a, b: a + b))
    rows = list(map(tuple, res.rows()))
    return rows, sess


def test_spill_write_transient_retried(spill_mode, chaos):
    keys, vals = _keyed()
    base, _ = _spill_run(keys, vals)
    assert dict(base) == _reduce_oracle(keys, vals)
    plan = chaos("3:spill.write=1.0x2")
    got, sess = _spill_run(keys, vals)
    assert got == base  # raw order included: retried, not degraded
    assert plan.snapshot()["injected"] == {"spill.write": 2}
    # Transient write retries never lose a task.
    assert sess.telemetry_summary().get("recovery") is None


def test_spill_read_loss_recomputes_bit_identical(spill_mode, chaos):
    """An injected spill-partition loss surfaces as Missing ->
    DepLost for the WHOLE producer group (a spilled partition holds
    every shard's rows) -> the group re-runs, re-spills, and the
    consumer completes bit-identical; the recovery is attributed to
    the spill.read site."""
    keys, vals = _keyed()
    base, _ = _spill_run(keys, vals)
    plan = chaos("5:spill.read=1.0x1")
    got, sess = _spill_run(keys, vals)
    assert got == base
    assert plan.snapshot()["injected"] == {"spill.read": 1}
    rec = sess.telemetry_summary()["recovery"]
    assert rec["fatal_total"] == 0
    site = rec["by_site"]["spill.read"]
    assert site["recovered"] > 0 and site["fatal"] == 0


def test_spill_corruption_quarantined_and_recovers(spill_mode, chaos):
    """Bit-flip corruption of a spilled frame rides the organic
    CorruptionError -> quarantine -> Missing -> recompute ladder of
    the spill FileStore (PR 5's machinery, by construction)."""
    keys, vals = _keyed()
    base, _ = _spill_run(keys, vals)
    chaos("9:codec.read=1.0x1~flip")
    got, sess = _spill_run(keys, vals, prefetch_depth=0)
    assert got == base
    spill_store = sess.executor._spill
    assert spill_store is not None and spill_store.quarantined >= 1


def test_spill_loss_under_elastic_recovery(spill_mode, chaos,
                                           monkeypatch):
    """A gang-member loss mid-run under the spill plan: elastic mesh
    recovery re-forms the mesh and the rerun — re-reading or
    re-spilling as needed — stays bit-identical."""
    monkeypatch.setenv("BIGSLICE_ELASTIC_BACKOFF", "0.01")
    keys, vals = _keyed()
    base, _ = _spill_run(keys, vals)
    plan = chaos("9:mesh.dispatch=1.0x1~hostloss")
    got, sess = _spill_run(keys, vals, elastic=1)
    assert got == base
    assert plan.snapshot()["injected"] == {"mesh.dispatch": 1}
    tot = sess.telemetry_summary()["device"]["shuffle_plan"]["totals"]
    assert tot["spill_boundaries"] >= 1


def test_chaosslice_cli_spill(tmp_path, capsys, monkeypatch):
    from bigslice_tpu.tools import chaosslice

    # The CLI exports BIGSLICE_SHUFFLE for its runs; seed it through
    # monkeypatch so the env mutation is undone at teardown.
    monkeypatch.setenv("BIGSLICE_SHUFFLE", "spill")
    out_json = tmp_path / "spill-matrix.json"
    rc = chaosslice.main([
        "-chaos", "7:spill.read=0.5x2,spill.write=0.5x2",
        "-rows", "4000", "-shards", "16", "-mesh",
        "-shuffle", "spill", "-json", str(out_json),
    ])
    captured = capsys.readouterr().out
    assert rc == 0, captured
    assert "bit-identical" in captured
    doc = json.loads(out_json.read_text())
    assert doc["ok"] and doc["bit_identical"]
    assert doc["shuffle"] == "spill"
    sites = {r["site"] for r in doc["matrix"]}
    assert sites & {"spill.read", "spill.write"}, doc["matrix"]


# -- coded k-of-n coverage under chaos (exec/codedplan.py, PR-20) ---------


def _coded_reduce(procs=4, shards=8, seed=13):
    rng = np.random.RandomState(seed)
    keys = rng.randint(0, 41, 1600).astype(np.int32)
    vals = rng.randint(1, 5, 1600).astype(np.int32)
    sess = Session(executor=LocalExecutor(procs=procs))
    res = sess.run(bs.Reduce(bs.Const(shards, keys, vals),
                             lambda a, b: a + b))
    return sess, res, _reduce_oracle2(keys, vals)


def _reduce_oracle2(keys, vals):
    out = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        out[k] = out.get(k, 0) + v
    return out


def test_coded_completes_with_exactly_r_losses(monkeypatch, chaos):
    """Satellite 1a: with k=8, r=1, losing exactly r coverage members
    (the design point) completes SILENTLY — no resubmission, no
    recompute, the surviving k members cover every unit."""
    monkeypatch.setenv("BIGSLICE_CODED", "combine")
    plan = chaos("5:coded.cover=1.0x1~lose")
    sess, res, oracle = _coded_reduce()
    assert dict(res.rows()) == oracle
    assert plan.snapshot()["injected"] == {"coded.cover": 1}
    st = sess.telemetry.coded
    assert st.count("covered") == 1
    assert st.count("recovered") == 0  # within the r budget: no redo
    from bigslice_tpu.exec.task import TaskState, iter_tasks

    lost = [t for t in iter_tasks(res.tasks)
            if getattr(t, "coded_group", None) is not None
            and t.state == TaskState.LOST]
    assert len(lost) == 1  # the lost member stays lost — nobody needs it


def test_coded_recomputes_loudly_past_r(monkeypatch, chaos):
    """Satellite 1b: losses beyond r break coverage; the evaluator
    resubmits uncovered members (the LOUD path: 'recovered' events)
    and still completes bit-identically."""
    monkeypatch.setenv("BIGSLICE_CODED", "combine")
    plan = chaos("5:coded.cover=1.0x12~lose")
    sess, res, oracle = _coded_reduce()
    assert dict(res.rows()) == oracle
    assert plan.snapshot()["injected"] == {"coded.cover": 12}
    st = sess.telemetry.coded
    assert st.count("covered") >= 1
    assert st.count("recovered") > 0  # resubmission happened, loudly


def test_coded_stuck_member_cancelled_on_coverage(monkeypatch, chaos):
    """Satellite 1c (~stuck kind): a member parked on its cancel
    event is woken by the coverage cancellation and lands CANCELLED —
    the cooperative-cancel ladder, not the 120s loud timeout."""
    monkeypatch.setenv("BIGSLICE_CODED", "combine")
    chaos("5:coded.cover=1.0x1~stuck")
    sess, res, oracle = _coded_reduce()
    assert dict(res.rows()) == oracle
    st = sess.telemetry.coded
    assert st.count("covered") == 1
    assert st.count("cancelled") >= 1
    from bigslice_tpu.exec.task import TaskState, iter_tasks

    # The parked member leaves RUNNING when its cancel event wakes it,
    # which may be after run() returned: wait for the transition. The
    # loud timeout would land it LOST, the cancel lands it CANCELLED.
    states = [t.wait_state(TaskState.OK, timeout=faultinject.STUCK_MAX_S)
              for t in iter_tasks(res.tasks)
              if getattr(t, "coded_group", None) is not None]
    assert TaskState.CANCELLED in states
    assert set(states) <= {TaskState.OK, TaskState.CANCELLED}


def test_stuck_task_times_out_to_loss_without_coded(monkeypatch,
                                                    chaos):
    """~stuck on the generic task.run seam with the coded plane OFF:
    nothing ever cancels, so the park must hit the loud STUCK_MAX_S
    timeout, surface as an injected LOSS, and recover by
    resubmission."""
    monkeypatch.delenv("BIGSLICE_CODED", raising=False)
    monkeypatch.setattr(faultinject, "STUCK_MAX_S", 0.3)
    plan = chaos("5:task.run=1.0x1~stuck")
    sess, res, oracle = _coded_reduce(procs=4)
    assert dict(res.rows()) == oracle
    assert plan.snapshot()["injected"] == {"task.run": 1}
    assert sess.telemetry.coded is None  # chicken bit stayed off
