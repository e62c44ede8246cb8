"""Mesh executor tests: SPMD op-group execution on the 8-device CPU mesh,
with transparent fallback interop (the executor-parameterized test idea
from SURVEY.md §4, applied to the mesh path)."""

import numpy as np
import pytest

import jax

import bigslice_tpu as bs
from bigslice_tpu.exec.meshexec import MeshExecutor
from bigslice_tpu.exec.session import Session


@pytest.fixture
def mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:8]), ("shards",))


@pytest.fixture
def sess(mesh):
    return Session(executor=MeshExecutor(mesh))


def rows_sorted(res):
    return sorted(res.rows())


def test_const_map_on_mesh(sess):
    s = bs.Const(8, np.arange(64, dtype=np.int32))
    m = bs.Map(s, lambda x: x * 2)
    res = sess.run(m)
    assert rows_sorted(res) == [(2 * i,) for i in range(64)]
    # The group actually ran on the device path.
    assert sess.executor.device_group_count() >= 1


def test_reduce_on_mesh(sess):
    rng = np.random.RandomState(0)
    keys = rng.randint(0, 40, 800).astype(np.int32)
    vals = rng.randint(0, 10, 800).astype(np.int32)
    r = bs.Reduce(bs.Const(8, keys, vals), lambda a, b: a + b)
    res = sess.run(r)
    oracle = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        oracle[k] = oracle.get(k, 0) + v
    assert dict(res.rows()) == oracle
    # Both producer and reducer groups device-resident.
    assert sess.executor.device_group_count() >= 2


def test_filter_map_chain_on_mesh(sess):
    s = bs.Const(8, np.arange(160, dtype=np.int32))
    f = bs.Filter(s, lambda x: x % 3 == 0)
    m = bs.Map(f, lambda x: x + 1)
    res = sess.run(m)
    assert rows_sorted(res) == [(i + 1,) for i in range(0, 160, 3)]


def test_reshuffle_on_mesh(sess):
    keys = np.arange(80, dtype=np.int32)
    r = bs.Reshuffle(bs.Const(8, keys))
    res = sess.run(r)
    assert rows_sorted(res) == [(i,) for i in range(80)]


def test_host_pipeline_falls_back(sess):
    words = ["a", "b", "a", "c"] * 10
    r = bs.Reduce(
        bs.Const(8, words, np.ones(40, dtype=np.int32)),
        lambda a, b: a + b,
    )
    res = sess.run(r)
    assert dict(res.rows()) == {"a": 20, "b": 10, "c": 10}


def test_mesh_producer_host_consumer(sess):
    """Device-resident producer feeding a host-tier Fold: the store
    bridge materializes device outputs as frames."""
    keys = np.arange(64, dtype=np.int32) % 4
    vals = np.ones(64, dtype=np.int32)
    m = bs.Map(bs.Const(8, keys, vals), lambda k, v: (k, v))
    f = bs.Fold(m, lambda acc, v: acc + int(v), init=0, out_value=np.int32)
    res = sess.run(f)
    assert dict(res.rows()) == {0: 16, 1: 16, 2: 16, 3: 16}


def test_host_producer_mesh_consumer(sess):
    """Host-tier source (shard count != hmm — host fn) feeding a
    device-eligible reduce."""
    def gen(shard):
        yield ([shard % 4] * 10, [1] * 10)

    src = bs.ReaderFunc(8, gen, out=[np.int32, np.int32])
    # ReaderFunc with a host generator is still device-schema; the group
    # runs on the mesh with host sourcing at the edge.
    r = bs.Reduce(src, lambda a, b: a + b)
    res = sess.run(r)
    assert dict(res.rows()) == {0: 20, 1: 20, 2: 20, 3: 20}


def test_small_shard_count_runs_padded(mesh):
    sess = Session(executor=MeshExecutor(mesh))
    # 5 shards on an 8-device mesh: runs SPMD with 3 empty-padded
    # devices (routing modulo 5, matching the host tier).
    r = bs.Reduce(
        bs.Const(5, np.arange(50, dtype=np.int32) % 7,
                 np.ones(50, dtype=np.int32)),
        lambda a, b: a + b,
    )
    res = sess.run(r)
    assert dict(res.rows()) == {i: 50 // 7 + (1 if i < 50 % 7 else 0)
                                for i in range(7)}
    assert sess.executor.device_group_count() >= 2


def test_large_shard_count_full_device(mesh):
    sess = Session(executor=MeshExecutor(mesh))
    # 11 shards exceed the 8-device mesh: the 11-partition producer
    # shuffles through the subid lane and the 11-shard reduce consumer
    # runs in two waves — BOTH groups device-resident.
    r = bs.Reduce(
        bs.Const(11, np.arange(110, dtype=np.int32) % 7,
                 np.ones(110, dtype=np.int32)),
        lambda a, b: a + b,
    )
    res = sess.run(r)
    assert dict(res.rows()) == {i: 110 // 7 + (1 if i < 110 % 7 else 0)
                                for i in range(7)}
    assert sess.executor.device_group_count() >= 2


def test_result_reuse_across_runs(sess):
    base = sess.run(bs.Const(8, np.arange(32, dtype=np.int32)))
    m = sess.run(bs.Map(base, lambda x: x + 100))
    assert rows_sorted(m) == [(i + 100,) for i in range(32)]


def test_map_with_args_on_mesh(sess):
    offsets = np.float32(5.0)
    s = bs.Const(8, np.arange(16, dtype=np.float32))
    m = bs.Map(s, lambda x, off: x + off, args=(offsets,))
    res = sess.run(m)
    assert rows_sorted(res) == [(float(i) + 5.0,) for i in range(16)]


def test_mesh_matches_local_executor(mesh):
    """Executor-parameterized equivalence (slice_test.go:64-66 pattern)."""
    rng = np.random.RandomState(7)
    keys = rng.randint(0, 25, 400).astype(np.int32)
    vals = rng.rand(400).astype(np.float32)

    def build():
        import jax.numpy as jnp

        s = bs.Const(8, keys, vals)
        f = bs.Filter(s, lambda k, v: k % 2 == 0)
        return bs.Reduce(f, lambda a, b: jnp.maximum(a, b))

    local = dict(Session().run(build()).rows())
    meshr = dict(Session(executor=MeshExecutor(mesh)).run(build()).rows())
    assert set(local) == set(meshr)
    for k in local:
        assert abs(local[k] - meshr[k]) < 1e-6


def test_same_op_different_configs_not_merged(mesh):
    """A slice consumed by both a Reduce and a Reshuffle compiles into
    two producer task sets; the mesh executor must not merge them into
    one op group."""
    sess = Session(executor=MeshExecutor(mesh))
    keys = np.array([1, 1, 2, 2] * 16, dtype=np.int32)
    vals = np.ones(64, dtype=np.int32)
    s = bs.Const(8, keys, vals)
    r = bs.Reduce(s, lambda a, b: a + b)
    p = bs.Reshuffle(s)
    cg = bs.Cogroup(
        bs.Map(r, lambda k, v: (k, v)),
        bs.Map(p, lambda k, v: (k, v)),
    )
    rows = sorted(sess.run(cg).rows())
    assert [(k, len(a), len(b)) for k, a, b in rows] == [
        (1, 1, 32), (2, 1, 32)
    ]


def test_head_on_mesh(sess):
    s = bs.Const(8, np.arange(800, dtype=np.int32))
    h = bs.Head(bs.Filter(s, lambda x: x % 2 == 0), 5)
    rows = sess.run(h).rows()
    assert len(rows) == 40  # 5 per shard
    assert all(v % 2 == 0 for (v,) in rows)
    assert sess.executor.device_group_count() >= 1  # ran on the device path


def test_ordered_dispatch_mode(mesh):
    """ordered_dispatch serializes group launches through one dispatcher
    in deterministic order; results identical to concurrent mode."""
    rng = np.random.RandomState(11)
    keys = rng.randint(0, 30, 640).astype(np.int32)
    vals = rng.randint(0, 5, 640).astype(np.int32)

    def build():
        s = bs.Const(8, keys, vals)
        return bs.Reduce(bs.Filter(s, lambda k, v: k % 2 == 0),
                         lambda a, b: a + b)

    base = dict(Session(executor=MeshExecutor(mesh)).run(build()).rows())
    sess = Session(executor=MeshExecutor(mesh, ordered_dispatch=True))
    got = dict(sess.run(build()).rows())
    assert got == base
    assert sess.executor.device_group_count() >= 2
    # A second run through the same ordered executor also works
    # (dispatcher thread persists).
    got2 = dict(sess.run(build()).rows())
    assert got2 == base


def test_concurrent_result_scans_on_mesh(sess):
    """Concurrent scans of a discarded mesh Result force simultaneous
    re-evaluations of shared tasks through the group/claim machinery."""
    import threading

    base = sess.run(bs.Map(bs.Const(8, np.arange(80, dtype=np.int32)),
                           lambda x: x * 3))
    expect = sorted((3 * i,) for i in range(80))
    errs = []

    for round_ in range(3):
        base.discard()

        def scan():
            try:
                assert rows_sorted(base) == expect
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=scan, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        # A silent join timeout would mask the very deadlock this test
        # exists to catch.
        assert not any(t.is_alive() for t in threads), "scan deadlocked"
        assert not errs, errs


def test_ordered_dispatch_slow_host_deps_no_deadlock(mesh):
    """Plan heads whose deps run slowly on the fallback path used to be
    popped by the dispatch timeout and then parked in _ready_set forever
    when their tasks finally arrived (round-1 advisor, high): the run
    must complete and still use the device path for the reduce group."""
    import threading
    import time

    sess = Session(executor=MeshExecutor(mesh, ordered_dispatch=True))

    def slow_ident(k, v):
        time.sleep(0.05)
        return (k, v)

    def build():
        s = bs.Const(8, np.arange(64, dtype=np.int32) % 4,
                     np.ones(64, dtype=np.int32))
        m = bs.Map(s, slow_ident, out=[np.int32, np.int32], mode="host")
        return bs.Reduce(m, lambda a, b: a + b)

    out = {}

    def run():
        out["rows"] = dict(sess.run(build()).rows())

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive(), "ordered dispatch deadlocked"
    assert out["rows"] == {0: 16, 1: 16, 2: 16, 3: 16}


def test_map_out_dtype_cast_on_mesh(sess):
    """Map with out= declaring a different dtype than the traced output
    must yield the declared dtype on the mesh path too (round-1 advisor,
    medium: the mesh program used to vmap the uncast fn)."""
    s = bs.Const(8, np.arange(32, dtype=np.int32))
    m = bs.Map(s, lambda x: x, out=[np.float32])
    res = sess.run(m)
    assert sess.executor.device_group_count() >= 1
    for f in res.frames():
        assert np.asarray(f.cols[0]).dtype == np.float32
    assert rows_sorted(res) == [(float(i),) for i in range(32)]


def test_program_cache_guards_recycled_fn_ids(mesh):
    """A program-cache entry whose stage function has been GC'd (dead
    weakref) must recompile rather than reuse the stale program keyed by
    a recycled id (round-1 advisor, medium)."""
    import weakref

    from bigslice_tpu.exec import compile as compile_mod

    ex = MeshExecutor(mesh)
    Session(executor=ex)
    s = bs.Map(bs.Const(8, np.arange(16, dtype=np.int32)),
               lambda x: x + 1)
    task = compile_mod.compile_slice(s)[0]
    prog1, _, _ = ex._program(task, (8,))
    assert len(ex._programs) == 1
    key = next(iter(ex._programs))

    class _Tmp:
        pass

    dead = weakref.ref(_Tmp())  # dies immediately
    assert dead() is None
    ex._programs[key] = ("stale", ((dead,), []))
    prog2, _, _ = ex._program(task, (8,))
    assert prog2 != "stale"


def test_fixed_fanout_flatmap_on_mesh(mesh):
    """Fixed-fanout Flatmap lowers to a device stage (plane-flatten +
    mask), including a downstream shuffle sized for the fanout."""
    import jax.numpy as jnp

    sess = Session(executor=MeshExecutor(mesh))

    def dup(x):
        # Emit x and x+1000; drop the second when x is odd.
        mask = jnp.array([True, True]) & jnp.array([True, False]) | (
            jnp.array([False, True]) & (x % 2 == 0)
        )
        return mask, jnp.stack([x, x + 1000])

    src = bs.Const(8, np.arange(64, dtype=np.int32))
    fm = bs.Flatmap(src, dup, out=[np.int32], fanout=2)
    r = bs.Reduce(bs.Map(fm, lambda x: (x % 4, x)),
                  lambda a, b: a + b)
    res = sess.run(r)
    oracle = {}
    for x in range(64):
        outs = [x] + ([x + 1000] if x % 2 == 0 else [])
        for o in outs:
            oracle[o % 4] = oracle.get(o % 4, 0) + o
    assert dict(res.rows()) == oracle
    assert sess.executor.device_group_count() >= 2


def test_device_repartition_on_mesh(mesh):
    """A traceable row partitioner runs inside the mesh shuffle kernel
    (round-1 verdict: kernel support existed but was unreachable)."""
    sess = Session(executor=MeshExecutor(mesh))

    def by_range(k, nparts):
        return (k * nparts) // 64

    src = bs.Const(8, np.arange(64, dtype=np.int32))
    rp = bs.Repartition(src, by_range)
    res = sess.run(rp)
    assert sorted(res.rows()) == [(i,) for i in range(64)]
    assert sess.executor.device_group_count() >= 1
    # Partition placement: shard s must hold exactly the range block s.
    for shard in range(8):
        vals = sorted(
            v for f in res.reader(shard, ()) for (v,) in f.rows()
        )
        assert vals == list(range(shard * 8, (shard + 1) * 8))


def test_repartition_matches_local(mesh):
    """Device and host tiers evaluate the same traced partitioner, so
    placement agrees exactly across executors."""
    def by_mod3(k, nparts):
        return (k * 7 + 3) % nparts

    def build():
        return bs.Repartition(
            bs.Const(8, np.arange(48, dtype=np.int32)), by_mod3
        )

    local = Session()
    meshs = Session(executor=MeshExecutor(mesh))
    rl = local.run(build())
    rm = meshs.run(build())
    for shard in range(8):
        lv = sorted(v for f in rl.reader(shard, ())
                    for (v,) in f.rows())
        mv = sorted(v for f in rm.reader(shard, ())
                    for (v,) in f.rows())
        assert lv == mv


def test_reshard_down_on_mesh(mesh):
    """Reshard to a smaller shard count: the producer's shuffle routes
    modulo nparts=3 on the device with idle trailing devices."""
    sess = Session(executor=MeshExecutor(mesh))
    src = bs.Const(8, np.arange(64, dtype=np.int32))
    rs = bs.Reshard(bs.Prefixed(src, 1), 3)
    res = sess.run(rs)
    assert sorted(res.rows()) == [(i,) for i in range(64)]
    assert res.num_shards == 3
    # BOTH groups device-resident: the 8-shard producer with its
    # 3-partition shuffle AND the 3-shard consumer (non-vacuous: the
    # producer is the one exercising nparts < nmesh routing).
    assert sess.executor.device_group_count() >= 2


def test_device_partitioner_range_error(mesh):
    """Out-of-range ids from a device partitioner raise the host
    tier's range error, not a slack-overflow retry loop."""
    import pytest

    from bigslice_tpu.exec.task import TaskError

    sess = Session(executor=MeshExecutor(mesh))

    def bad(k, nparts):
        return (k % nparts) + 1  # can yield nparts (out of range)

    rp = bs.Repartition(bs.Const(8, np.arange(64, dtype=np.int32)), bad)
    with pytest.raises(TaskError, match="outside"):
        sess.run(rp)


def test_wave_scheduling_more_shards_than_devices(mesh):
    """20 shards on an 8-device mesh: 3 waves stream through the
    device; the reduce's partitioned output merges across waves."""
    sess = Session(executor=MeshExecutor(mesh))
    rng = np.random.RandomState(13)
    keys = rng.randint(0, 31, 20 * 40).astype(np.int32)
    vals = rng.randint(1, 5, 20 * 40).astype(np.int32)
    # Consumer resharded to the mesh: Reduce over a 20-shard source
    # with an 8-shard reduce (device-resident end to end).
    src = bs.Const(20, keys, vals)
    r = bs.Reduce(bs.Reshard(bs.Prefixed(src, 1), 8),
                  lambda a, b: a + b)
    res = sess.run(r)
    oracle = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        oracle[k] = oracle.get(k, 0) + v
    assert dict(res.rows()) == oracle
    assert sess.executor.device_group_count() >= 2


def test_wave_unpartitioned_root(mesh):
    """An unpartitioned (root) 20-shard map chain runs in waves with
    per-wave shard identity preserved for the result scan."""
    sess = Session(executor=MeshExecutor(mesh))
    src = bs.Const(20, np.arange(200, dtype=np.int32))
    m = bs.Map(src, lambda x: x * 3)
    res = sess.run(m)
    assert sorted(res.rows()) == [(3 * i,) for i in range(200)]
    assert sess.executor.device_group_count() >= 1
    # Per-shard readback matches the shard split of Const.
    got0 = sorted(v for f in res.reader(0, ()) for (v,) in f.rows())
    assert got0 == [3 * i for i in range(10)]
    got19 = sorted(v for f in res.reader(19, ()) for (v,) in f.rows())
    assert got19 == [3 * i for i in range(190, 200)]


def waved_root(mesh):
    """A scanned-by-nobody 20-shard map chain: (session, result, its
    3-wave output on the 8-device mesh)."""
    from bigslice_tpu.exec.meshexec import WavedGroupOutput

    sess = Session(executor=MeshExecutor(mesh))
    res = sess.run(bs.Map(bs.Const(20, np.arange(200, dtype=np.int32)),
                          lambda x: x * 3))
    ex = sess.executor
    with ex._lock:
        key, _ = ex._task_index[res.tasks[0].name]
        out = ex._outputs[key]
    assert isinstance(out, WavedGroupOutput) and len(out.waves) == 3
    return sess, res, out


def test_waved_readback_fills_each_wave_as_host_chunks_would(mesh):
    """The one batched read of a waved output leaves every wave the
    chunks its own ``host_chunks()`` reads, and its crossed bytes
    counted by the span."""
    from bigslice_tpu.exec.meshexec import DeviceGroupOutput

    sess, res, out = waved_root(mesh)
    assert list(res.reader(7, ()))
    span = sess.telemetry_summary()["spans"]["readback"]
    moved = 0
    for w in out.waves:
        alone = DeviceGroupOutput(w.cols, w.counts, w.capacity,
                                  w.schema, False, nmesh=w.nmesh)
        want = alone.host_chunks()
        moved += alone.readback_nbytes
        assert w.readback_nbytes == alone.readback_nbytes > 0
        assert len(w._chunks) == len(want)
        for got_col, want_col in zip(w._chunks, want):
            for g, c in zip(got_col, want_col):
                assert g.dtype == c.dtype
                np.testing.assert_array_equal(g, c)
    assert (span["count"], span["bytes"]) == (1, moved)
    assert sorted(res.rows()) == [(3 * i,) for i in range(200)]


def test_waved_readback_moves_only_waves_not_yet_on_the_host(mesh):
    """A wave read through its own ``host_chunks()`` first (the spill
    sink's and ``drop_device``'s entry) stays as read; the output's
    readback moves the others."""
    sess, res, out = waved_root(mesh)
    early = out.waves[1].host_chunks()
    out.waves[2].drop_device()
    assert out.waves[2].cols is None
    assert "readback" not in sess.telemetry_summary()["spans"]
    assert sorted(res.rows()) == [(3 * i,) for i in range(200)]
    assert out.waves[1]._chunks is early
    assert sess.telemetry_summary()["spans"]["readback"]["count"] == 1
    assert out.waves[1].host_chunks() is early


def test_ungathered_wave_raises_before_anything_is_read(mesh):
    """A wave that is not fully addressable fails the whole read as a
    classified error, with no wave's arrays fetched."""
    from bigslice_tpu.exec import meshexec

    sess, res, out = waved_root(mesh)

    class DeviceOnly:
        is_fully_addressable = False

    out.waves[2].cols = [DeviceOnly()]
    with pytest.raises(meshexec.UngatheredOutputError):
        meshexec._fill_host_chunks(out.waves)
    assert all(w._chunks is None for w in out.waves)
    # Through the store bridge: the retriable ``Missing`` contract.
    from bigslice_tpu.exec.store import Missing

    with pytest.raises(Missing):
        sess.executor.store.read(res.tasks[0].name, 0)
    assert all(w._chunks is None for w in out.waves)


def test_wave_aligned_chain(mesh):
    """Waved producer feeding an aligned waved consumer (materialize
    boundary): per-wave zero-copy chaining."""
    sess = Session(executor=MeshExecutor(mesh))
    src = bs.Const(12, np.arange(120, dtype=np.int32))
    m = bs.Map(src, lambda x: x + 1)
    m.pragmas = (bs.Materialize(),)
    m2 = bs.Map(m, lambda x: x * 2)
    res = sess.run(m2)
    assert sorted(res.rows()) == [(2 * (i + 1),) for i in range(120)]
    assert sess.executor.device_group_count() >= 2


def test_wave_matches_local(mesh):
    rng = np.random.RandomState(17)
    keys = rng.randint(0, 50, 600).astype(np.int32)
    vals = rng.rand(600).astype(np.float32)

    def build():
        import jax.numpy as jnp

        s = bs.Const(24, keys, vals)
        f = bs.Filter(s, lambda k, v: k % 3 != 1)
        return bs.Reduce(bs.Reshard(bs.Prefixed(f, 1), 6),
                         lambda a, b: jnp.minimum(a, b))

    local = dict(Session().run(build()).rows())
    meshr = dict(Session(executor=MeshExecutor(mesh)).run(build()).rows())
    assert set(local) == set(meshr)
    for k in local:
        assert abs(local[k] - meshr[k]) < 1e-6


def test_wave_partitioned_shuffle_beyond_mesh(mesh):
    """num_partition > mesh: the shuffle routes per device with a subid
    lane; waved consumers filter their own partition. BOTH the 20-way
    partitioned producer and the 20-shard consumer run on the device."""
    sess = Session(executor=MeshExecutor(mesh))
    rng = np.random.RandomState(41)
    keys = rng.randint(0, 71, 20 * 50).astype(np.int32)
    vals = rng.randint(1, 6, 20 * 50).astype(np.int32)
    r = bs.Reduce(bs.Const(20, keys, vals), lambda a, b: a + b)
    res = sess.run(r)
    oracle = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        oracle[k] = oracle.get(k, 0) + v
    assert dict(res.rows()) == oracle
    assert sess.executor.device_group_count() >= 2
    # Per-shard placement must agree with the host tier's hash % 20.
    from bigslice_tpu.frame.frame import Frame
    from bigslice_tpu.slicetype import Schema

    for shard in (0, 7, 13, 19):
        got = sorted(
            k for f in res.reader(shard, ()) for k, _ in f.rows()
        )
        uk = np.asarray(sorted(oracle), np.int32)
        f = Frame([uk], Schema([np.int32], prefix=1))
        expect = sorted(uk[f.partition_ids(20) == shard].tolist())
        assert got == expect, (shard, got[:5], expect[:5])


def test_wave_partitioned_reshuffle_roundtrip(mesh):
    """Reshuffle at 24 shards on an 8-device mesh: every row arrives
    exactly once through the subid-routed exchange."""
    sess = Session(executor=MeshExecutor(mesh))
    keys = np.arange(24 * 30, dtype=np.int32)
    r = bs.Reshuffle(bs.Const(24, keys))
    res = sess.run(r)
    assert sorted(res.rows()) == [(i,) for i in range(24 * 30)]
    assert sess.executor.device_group_count() >= 1


def test_infra_error_probation_falls_back_then_recovers(mesh, caplog):
    """XLA-runtime failures are the 'machine lost' class (SURVEY §5.3):
    the op's tasks go LOST (not ERR), the evaluator resubmits, and the
    op's device path sits on probation so the retry runs on the host
    fallback — then re-engages the device once probation decays
    (exec/slicemachine.go probation analog)."""
    import jax

    ex = MeshExecutor(mesh)
    sess = Session(executor=ex)
    real = ex._execute_group
    fails = {"n": 0}

    def flaky(key, tasks):
        if fails["n"] == 0:
            fails["n"] += 1
            raise jax.errors.JaxRuntimeError("INTERNAL: injected")
        return real(key, tasks)

    ex._execute_group = flaky

    keys = (np.arange(64, dtype=np.int32) % 7)
    vals = np.ones(64, np.int32)

    def add(a, b):
        return a + b

    def build():
        # Op names embed the construction site: both runs must build
        # here so probation (keyed by op) covers the retry.
        return bs.Reduce(bs.Const(8, keys, vals), add)

    with caplog.at_level("WARNING", logger="bigslice.meshexec"):
        got = dict(sess.run(build()).rows())
    assert got == {i: 10 if i < 1 else (10 if i < 64 % 7 else 9)
                   for i in range(7)}
    assert fails["n"] == 1
    # Leaving the device is loud: ONE warning naming op and error.
    warned = [r.getMessage() for r in caplog.records
              if "on probation" in r.getMessage()]
    assert len(warned) == 1 and "INTERNAL: injected" in warned[0]
    assert any(op in warned[0] for op in ex._probation)
    # The failed op retried on the host fallback and is on probation
    # (other groups in the graph may still run on device).
    assert ex._probation, "op should be on probation"
    probed_ops = set(ex._probation)
    count_before = ex.device_group_count()

    # Probation decays -> the op's device path re-engages.
    for op in list(ex._probation):
        ex._probation[op] = 0.0
    got2 = dict(sess.run(build()).rows())
    assert got2 == got
    assert not (set(ex._probation) & probed_ops), "probation not lifted"
    assert ex.device_group_count() > count_before


def test_user_error_stays_fatal_on_mesh(sess):
    """User-code failures must NOT be retried as infra losses."""
    from bigslice_tpu.exec.task import TaskError

    def boom(x):
        raise ValueError("user bug")

    with pytest.raises(TaskError):
        sess.run(bs.Map(bs.Const(4, np.arange(16, dtype=np.int32)),
                        boom, out=[np.int32]))


def test_vector_value_reduce_on_mesh(mesh):
    """Vector VALUE columns ([n, d] payloads) ride the fused
    combine+shuffle via permutation gathers and trailing-dim scatters —
    the k-means session-path shape. Keys stay scalar."""
    rng = np.random.RandomState(3)
    n, d = 2048, 8
    keys = rng.randint(0, 23, n).astype(np.int32)
    vecs = rng.rand(n, d).astype(np.float32)

    def add(a, b):
        return a + b

    def build():
        return bs.Reduce(bs.Const(8, keys, vecs), add)

    oracle = {}
    for i in range(n):
        k = int(keys[i])
        oracle[k] = oracle.get(k, np.zeros(d, np.float32)) + vecs[i]

    local = Session().run(build())
    sess = Session(executor=MeshExecutor(mesh))
    meshr = sess.run(build())
    for res, name in ((local, "local"), (meshr, "mesh")):
        got = {}
        for f in res.frames():
            kcol = np.asarray(f.cols[0])
            vcol = np.asarray(f.cols[1])
            for j in range(len(f)):
                got[int(kcol[j])] = vcol[j]
        assert set(got) == set(oracle), name
        for k in oracle:
            np.testing.assert_allclose(got[k], oracle[k],
                                       rtol=1e-4, atol=1e-4)
    # The vector-payload group genuinely engaged the device path.
    assert sess.executor.device_group_count() >= 2


class _FakeOut:
    """Stand-in group output for gather-plan tests."""

    def __init__(self):
        self.gather_calls = 0
        self._gathered = False

    def gather(self):
        self.gather_calls += 1
        self._gathered = True

    @property
    def gathered(self):
        return self._gathered


def _mk_task(op, shard, num_shard, group_key, deps=(), chain=None,
             num_partition=1):
    from bigslice_tpu.exec.task import (
        Partitioner, Task, TaskDep, TaskName,
    )
    from bigslice_tpu.slicetype import Schema

    t = Task(
        TaskName(inv_index=1, op=op, shard=shard, num_shard=num_shard),
        None,
        [TaskDep(tasks=tuple(d), partition=0) for d in deps],
        Partitioner(num_partition=num_partition),
        Schema([np.int32]),
    )
    t.group_key = group_key
    t.chain = chain  # None => mesh-ineligible (host tier)
    return t


def test_plan_gather_marks_and_pays_late_debt(mesh):
    """Consumer-driven gather: (a) producers feeding host-tier
    consumers and run roots are marked; device-consumed partitioned
    producers are not; (b) an already-resident unmarked output that a
    re-plan newly marks becomes a _GatherEntry debt the dispatcher
    pays in plan order (the elastic-replan safety net)."""
    ex = MeshExecutor(mesh)
    ex.multiprocess = True  # exercise the SPMD-only plan logic
    ex.ordered_dispatch = True

    # Producer group P (partitioned shuffle output) feeding a host-tier
    # consumer C (chain None -> ineligible).
    prods = [_mk_task("const-0", s, 2, "P", num_partition=2)
             for s in range(2)]
    cons = [_mk_task("map-0", s, 2, "C", deps=[prods]) for s in range(2)]
    out = _FakeOut()
    ex._outputs["P"] = out
    ex.plan_gather(cons, token="t1")
    assert "P" in ex._gather_marked          # host consumer => marked
    assert "C" in ex._gather_marked          # run root => marked
    assert {"P", "C"} <= set(ex._gather_analyzed)
    # Resident + newly marked => queued as a dispatcher debt, paid
    # in plan order by the (single) dispatcher thread.
    import time
    deadline = time.monotonic() + 10.0
    while not out.gathered and time.monotonic() < deadline:
        time.sleep(0.01)
    assert out.gather_calls == 1
    with ex._lock:
        assert "P" not in ex._gather_pending

    # Device-consumed partitioned producer: NOT marked — its data stays
    # mesh-resident. (The device consumer C2 is itself read by the
    # host-tier root R, so C2 IS marked.)
    from bigslice_tpu.ops.const import Const
    prods2 = [_mk_task("const-1", s, 2, "P2", num_partition=2)
              for s in range(2)]
    chain = (Const(2, np.arange(8, dtype=np.int32)),)
    dev_cons = [_mk_task("reduce-1", s, 2, "C2", deps=[prods2],
                         chain=chain) for s in range(2)]
    roots = [_mk_task("tail-1", 0, 1, "R", deps=[dev_cons])]
    ex.plan_gather(roots, token="t2")
    assert "P2" not in ex._gather_marked     # device-chained, stays put
    assert "C2" in ex._gather_marked         # feeds the host-tier root


def test_machine_combiners_ride_device_path(mesh):
    """combine_key groups with device combiners are mesh-eligible
    (round-2 verdict #7a): correctness matches, and the groups actually
    engage the device instead of the forced fallback of round 2."""
    sess = Session(executor=MeshExecutor(mesh), machine_combiners=True)
    rng = np.random.RandomState(11)
    keys = rng.randint(0, 60, 1600).astype(np.int32)
    vals = rng.randint(0, 10, 1600).astype(np.int32)
    r = bs.Reduce(bs.Const(8, keys, vals), lambda a, b: a + b)
    got = dict(sess.run(r).rows())
    oracle = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        oracle[k] = oracle.get(k, 0) + v
    assert got == oracle
    assert sess.executor.device_group_count() >= 2
    # The local machine-combiner buffers were never engaged.
    assert not sess.executor.local._mc_keys_committed


def test_machine_combiners_waved_cross_wave_recombine(mesh):
    """S > N machine-combined producers re-combine across waves in
    _merge_outputs (the shared per-machine buffer analog): the merged
    partition holds at most one row per (subid, key) before consumers
    read it."""
    sess = Session(executor=MeshExecutor(mesh), machine_combiners=True)
    rng = np.random.RandomState(12)
    nsh = 16  # 2 waves on the 8-device mesh
    keys = rng.randint(0, 30, 3200).astype(np.int32)
    vals = np.ones(3200, np.int32)
    r = bs.Reduce(bs.Const(nsh, keys, vals), lambda a, b: a + b)
    got = dict(sess.run(r).rows())
    oracle = {}
    for k in keys.tolist():
        oracle[k] = oracle.get(k, 0) + 1
    assert got == oracle
    # The producer group's merged output was re-combined: per device,
    # at most one row per (subid, key).
    ex = sess.executor
    with ex._lock:
        merged = [o for o in ex._outputs.values()
                  if getattr(o, "partitioned", False)]
    assert merged
    for out in merged:
        chunks = out.host_chunks()
        for d in range(out.nmesh):
            cols = [np.asarray(c[d]) for c in chunks]
            if not len(cols[0]):
                continue
            pairs = list(zip(*[c.tolist() for c in
                               cols[:2 if out.subid else 1]]))
            assert len(pairs) == len(set(pairs)), \
                "duplicate (subid, key) rows survived the re-combine"


def test_hbm_budget_splits_wave(mesh):
    """A wave whose estimated working set exceeds the per-device budget
    runs as K row-slices (round-2 verdict #6): results are exact, the
    compiled sub-programs see bounded capacities, and the partitioned
    sub-outputs merge as multiple producer contributions."""
    tiny = 2_000  # bytes — far below any real wave
    sess = Session(executor=MeshExecutor(mesh,
                                         device_budget_bytes=tiny))
    rng = np.random.RandomState(13)
    keys = rng.randint(0, 50, 4096).astype(np.int32)
    vals = rng.randint(0, 7, 4096).astype(np.int32)
    r = bs.Reduce(bs.Const(8, keys, vals), lambda a, b: a + b)
    got = dict(sess.run(r).rows())
    oracle = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        oracle[k] = oracle.get(k, 0) + v
    assert got == oracle
    ex = sess.executor
    assert ex.split_runs, "the split path should have engaged"
    K = max(ex.split_runs.values())
    assert K > 1
    # Peak compiled capacity is bounded: every sub-run's input slice is
    # cap/K rows (the slicer programs record the B actually used).
    bs_used = [k[3] for k in ex._programs if k[0] == "rowslice"]
    assert bs_used and all(b * K <= 4096 for b in bs_used)

    # Unbudgeted baseline agrees.
    base = dict(
        Session(executor=MeshExecutor(mesh)).run(
            bs.Reduce(bs.Const(8, keys, vals), lambda a, b: a + b)
        ).rows()
    )
    assert base == oracle


def test_wave_stress_64_shards(mesh):
    """The north-star dispatcher shape: S=64 shards stream 8 waves
    through the 8-device mesh (wave-partitioned subid shuffle +
    waved re-combine). Regression guard for the control plane at
    pod-scale task counts (the BenchmarkEval analog)."""
    import time

    sess = Session(executor=MeshExecutor(mesh))
    shards, per = 64, 512
    n = shards * per
    rng = np.random.RandomState(17)
    keys = rng.randint(0, 997, n).astype(np.int32)
    r = bs.Reduce(bs.Const(shards, keys, np.ones(n, np.int32)),
                  lambda a, b: a + b)
    t0 = time.perf_counter()
    got = dict(sess.run(r).rows())
    dt = time.perf_counter() - t0
    assert sum(got.values()) == n
    oracle = {}
    for k in keys.tolist():
        oracle[k] = oracle.get(k, 0) + 1
    assert got == oracle
    assert sess.executor.device_group_count() >= 2
    # Generous wall bound (compile included): catches control-plane
    # regressions an order of magnitude before they hurt.
    assert dt < 60.0, f"wave-stress run took {dt:.1f}s"


def test_daemon_pool_recycles_and_survives_exceptions():
    """The shared group pool: bounded thread count under load, task
    exceptions never strand queued work, and idle workers retire (the
    process-global pool must not accumulate threads across sessions)."""
    import threading
    import time

    from bigslice_tpu.exec.meshexec import _DaemonPool

    pool = _DaemonPool(max_workers=4, idle_secs=0.2)
    done = []
    lock = threading.Lock()

    def work(i):
        if i % 3 == 0:
            raise RuntimeError("boom")  # must not kill the worker
        with lock:
            done.append(i)

    for i in range(40):
        pool.submit(work, i)
    deadline = time.time() + 10
    while time.time() < deadline:
        with lock:
            if len(done) == len([i for i in range(40) if i % 3]):
                break
        time.sleep(0.01)
    assert len(done) == len([i for i in range(40) if i % 3])
    with pool._lock:
        assert pool._nthreads <= 4
    # Idle retirement: workers exit after idle_secs without work.
    deadline = time.time() + 5
    while time.time() < deadline:
        with pool._lock:
            if pool._nthreads == 0:
                break
        time.sleep(0.05)
    with pool._lock:
        assert pool._nthreads == 0
    # The pool still serves after full retirement.
    pool.submit(work, 1)
    deadline = time.time() + 5
    while time.time() < deadline:
        with lock:
            if done.count(1) == 2:
                break
        time.sleep(0.01)
    assert done.count(1) == 2


# ------------------------------------------- error classification

def _runtime_error(msg: str):
    """The XLA runtime's exception as the installed jax raises it."""
    import jax

    return jax.errors.JaxRuntimeError(msg)


def test_infra_error_classified_by_type():
    from bigslice_tpu.exec.meshexec import _looks_like_infra_error

    import jax

    class Sub(jax.errors.JaxRuntimeError):
        """Subclasses classify too (isinstance)."""

    assert _looks_like_infra_error(_runtime_error("boom"))
    assert _looks_like_infra_error(Sub("wrapped boom"))
    # A class that merely shares the old jaxlib name is not it.
    assert not _looks_like_infra_error(
        type("XlaRuntimeError", (RuntimeError,), {})("boom")
    )
    # ...anywhere in the failure chain, not just at the top: the new
    # seams (instrumented programs, staging retries) re-raise with
    # context.
    try:
        try:
            raise _runtime_error("device died")
        except RuntimeError as inner:
            raise ValueError("wrapper") from inner
    except ValueError as outer:
        assert _looks_like_infra_error(outer)


def test_infra_error_string_fallback_and_negatives():
    from bigslice_tpu.exec.meshexec import _looks_like_infra_error

    # Marker-string fallback (backends that stringify runtime errors).
    assert _looks_like_infra_error(
        RuntimeError("RESOURCE_EXHAUSTED: while allocating 2G")
    )
    assert _looks_like_infra_error(RuntimeError("DMA error on chip 3"))
    # A user error merely *mentioning* suggestive words must not be
    # rerouted to the host tier: multi-word markers only.
    assert not _looks_like_infra_error(
        ValueError("user asked about dma and memory budgets")
    )
    assert not _looks_like_infra_error(ValueError("plain user error"))


def test_host_loss_classified_by_type_then_string():
    from bigslice_tpu.exec.meshexec import (
        HostLostError,
        _looks_like_host_loss,
    )
    from bigslice_tpu.utils.distributed import PeerLostError

    assert _looks_like_host_loss(PeerLostError("peer 3 gone"))
    assert _looks_like_host_loss(HostLostError("already wrapped"))
    # Typed loss buried in an implicit (__context__) chain.
    try:
        try:
            raise PeerLostError("peer lost mid-collective")
        except PeerLostError:
            raise RuntimeError("collective failed")
    except RuntimeError as outer:
        assert _looks_like_host_loss(outer)
    # String fallback for opaque runtime errors.
    assert _looks_like_host_loss(
        RuntimeError("Gloo allreduce failed: connection reset by peer")
    )
    # Mentioning "peer" alone is not a loss.
    assert not _looks_like_host_loss(
        ValueError("peer review feedback pending")
    )


def test_exception_chain_is_cycle_safe():
    from bigslice_tpu.exec.meshexec import _exception_chain

    a = ValueError("a")
    b = RuntimeError("b")
    a.__cause__ = b
    b.__cause__ = a  # pathological cycle must not hang
    assert {repr(e) for e in _exception_chain(a)} == {repr(a), repr(b)}


def test_task_error_cause_is_walked():
    """TaskError carries its cause on .cause (not __cause__); the
    classifier must follow it — that's how device errors surface to
    the session's gang-loss check."""
    import types

    from bigslice_tpu.exec.meshexec import _looks_like_infra_error
    from bigslice_tpu.exec.task import TaskError, TaskName

    t = types.SimpleNamespace(name=TaskName(1, "op", 0, 1))
    err = TaskError(t, _runtime_error("oom"))
    assert _looks_like_infra_error(err)
