"""Multi-host smoke test: the SPMD session across real process boundaries.

Spawns N Python processes on localhost, each a jax.distributed
participant with its own CPU device(s); together they form one global
mesh. This is a CPU simulation of a multi-host gang — every process
pins the CPU platform, because a chip belongs to one process and on a
host with chips one process drives all of them. The smoke run
exercises, across actual process boundaries (the DCN shape of a TPU
pod):

- distributed bootstrap + Func-registry digest verification,
- a data-parallel psum step (mesh k-means),
- the full mesh reduce (hash + all_to_all + segmented combines).

Usage (parent):  python -m bigslice_tpu.tools.multihost_smoke [N]
The parent acts as process 0; children run the same module with
``--worker``. ``--telemetry [--out DIR]`` runs the fleet-observability
smoke instead: 2 ranks with per-rank traces and a shared fleet store,
asserting the merged fleet summary carries both ranks' attribution.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def worker(num_processes: int, process_id: int, port: int,
           hard_exit: bool = True) -> int:
    from bigslice_tpu.utils.hermetic import force_hermetic_cpu

    force_hermetic_cpu()
    import jax
    import numpy as np
    from jax.experimental import multihost_utils
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bigslice_tpu.utils import distributed

    distributed.initialize(
        coordinator=f"127.0.0.1:{port}",
        num_processes=num_processes,
        process_id=process_id,
    )
    assert jax.process_count() == num_processes
    mesh = distributed.global_mesh()
    n = int(mesh.devices.size)
    n_local = len([d for d in mesh.devices.flat
                   if d.process_index == process_id])

    def make_global(local_rows: "np.ndarray", global_shape):
        return jax.make_array_from_process_local_data(
            NamedSharding(mesh, P("shards")), local_rows, global_shape
        )

    # 1. Data-parallel psum step (mesh k-means) across processes.
    from bigslice_tpu.models.kmeans import mesh_kmeans_step

    rng = np.random.RandomState(0)
    pts = rng.rand(n * 16, 4).astype(np.float32)
    cents = pts[:2].copy()
    local_pts = pts.reshape(num_processes, -1, 4)[process_id]
    step = mesh_kmeans_step(mesh, k=2, d=4)
    out = np.asarray(step(make_global(local_pts, pts.shape), cents))
    assert out.shape == (2, 4) and np.isfinite(out).all()

    # 2. Full mesh reduce (hash + all_to_all + segmented combines)
    # across processes: every row carries value 1, keys in [0, 7).
    from bigslice_tpu.parallel import shuffle as shuffle_mod

    per, cap = 32, 64
    local_keys = np.concatenate([
        np.concatenate([rng.randint(0, 7, per).astype(np.int32),
                        np.zeros(cap - per, np.int32)])
        for _ in range(n_local)
    ])
    local_vals = np.concatenate([
        np.concatenate([np.ones(per, np.int32),
                        np.zeros(cap - per, np.int32)])
        for _ in range(n_local)
    ])
    kcols = make_global(local_keys, (n * cap,))
    vcols = make_global(local_vals, (n * cap,))
    counts = make_global(np.full(n_local, per, np.int32), (n,))
    red = shuffle_mod.MeshReduceByKey(mesh, 1, 1, cap,
                                      lambda a, b: a + b)
    k_out, v_out, out_counts, overflow = red([kcols], [vcols], counts)
    assert int(np.asarray(overflow)) == 0

    # Global row count must be preserved. Only each shard's valid prefix
    # counts — the compacted tail holds non-survivor remnants.
    counts_by_dev = {
        s.device: int(s.data[0]) for s in out_counts.addressable_shards
    }
    local_sum = sum(
        int(np.asarray(s.data)[: counts_by_dev[s.device]].sum())
        for s in v_out[0].addressable_shards
    )
    sums = np.asarray(multihost_utils.process_allgather(
        np.asarray([local_sum], np.int64)
    ))
    assert int(sums.sum()) == n * per, (int(sums.sum()), n * per)

    # 3. The full distributed session: Session + MeshExecutor(spmd) on
    # every process — compile, ordered device-group launch, collective
    # execution, and result scan, all across real process boundaries
    # (the exec/bigmachine.go:79-533 role, SPMD-style).
    from bigslice_tpu.exec import spmd as spmd_mod
    from bigslice_tpu.parallel.join import join_count_oracle
    import bigslice_tpu as bs

    sess = spmd_mod.spmd_session(mesh)

    def add(a, b):
        return a + b

    skeys = rng.randint(0, 9, n * 24).astype(np.int32)
    red = bs.Reduce(
        bs.Filter(bs.Const(n, skeys, np.ones(len(skeys), np.int32)),
                  lambda k, v: k != 4),
        add,
    )
    got = dict(sess.run(red).rows())
    expect: dict = {}
    for kk in skeys.tolist():
        if kk != 4:
            expect[kk] = expect.get(kk, 0) + 1
    assert got == expect, (got, expect)
    assert sess.executor.device_group_count() >= 2

    # Consumer-driven gather (meshexec.plan_gather): the shuffle-write
    # producer group is consumed on-device by the reduce (partitioned
    # zero-copy chain) and must stay mesh-resident — its data never
    # crosses DCN. Only the root (result-scanned) group gathers.
    ex = sess.executor
    with ex._lock:
        outs = dict(ex._outputs)
    assert any(not o.gathered for o in outs.values()), \
        "a device-chained intermediate should stay mesh-resident"
    assert any(o.gathered for o in outs.values()), \
        "the root output must gather for result scans"

    ak = rng.randint(0, 13, n * 16).astype(np.int32)
    bk = rng.randint(5, 18, n * 16).astype(np.int32)
    join = bs.JoinAggregate(
        bs.Const(n, ak, np.ones(len(ak), np.int32)),
        bs.Const(n, bk, np.ones(len(bk), np.int32)),
        add, add,
    )
    got_j = {k: (int(a), int(b)) for k, a, b in sess.run(join).rows()}
    assert got_j == join_count_oracle(ak.tolist(), bk.tolist())

    # Dense lowerings under SPMD: the static-routed table all_to_all
    # and the rank-indexed table join must agree with the sort path
    # across real process boundaries too.
    dred = bs.Reduce(
        bs.Const(n, skeys, np.ones(len(skeys), np.int32)),
        add, dense_keys=9,
    )
    assert dred.frame_combiner.dense_keys == 9
    got_d = dict(sess.run(dred).rows())
    expect_d: dict = {}
    for kk in skeys.tolist():
        expect_d[kk] = expect_d.get(kk, 0) + 1
    assert got_d == expect_d, (got_d, expect_d)
    djoin = bs.JoinAggregate(
        bs.Const(n, ak, np.ones(len(ak), np.int32)),
        bs.Const(n, bk, np.ones(len(bk), np.int32)),
        add, add, dense_keys=18,
    )
    got_dj = {k: (int(a), int(b)) for k, a, b in sess.run(djoin).rows()}
    assert got_dj == join_count_oracle(ak.tolist(), bk.tolist())

    # Device cogroup under SPMD: the tagged-sort group kernel with
    # capacity discovery (deficit is a cross-process pmax; a hot key
    # exercises the collective retry identically on every process).
    cg_keys = np.concatenate([
        np.zeros(n * 8, np.int32),  # hot key >> default capacity 8
        rng.randint(1, 5, n * 8).astype(np.int32),
    ])
    cg_vals = np.arange(len(cg_keys), dtype=np.int32)
    cg = bs.Cogroup(bs.Const(n, cg_keys, cg_vals))
    cg_rows = {int(k): sorted(int(v) for v in g)
               for k, g in sess.run(cg).rows()}
    cg_expect: dict = {}
    for kk, vv in zip(cg_keys.tolist(), cg_vals.tolist()):
        cg_expect.setdefault(kk, []).append(vv)
    assert cg_rows == {k: sorted(v) for k, v in cg_expect.items()}
    assert any("cogroup" in t.op for t in ex._task_index)
    assert max(ex._cogroup_caps.values()) >= n * 8

    # Slice-level ring attention across REAL process boundaries: the
    # attend stage's ppermute ring and count all_gather ride DCN.
    from bigslice_tpu.parallel.ulysses import dense_mha_reference

    a_seq, a_d = n * 8, 8
    aq, akk, av = (rng.randn(a_seq, a_d).astype(np.float32) * 0.3
                   for _ in range(3))
    att = bs.SelfAttend(bs.Const(n, aq, akk, av), causal=True)
    a_out = np.stack([np.asarray(o)
                      for (o,) in sess.run(att).rows()])
    a_ref = dense_mha_reference(
        aq[:, None, :], akk[:, None, :], av[:, None, :], causal=True
    )[:, 0, :]
    assert np.allclose(a_out, a_ref, rtol=3e-4, atol=3e-4), \
        np.abs(a_out - a_ref).max()
    assert any("attend" in t.op for t in ex._task_index)

    # Iterative reuse across runs (Result as input) under SPMD.
    base = sess.run(bs.Const(n, np.arange(n * 8, dtype=np.int32)))
    doubled = sorted(sess.run(bs.Map(base, lambda x: x * 2)).rows())
    assert doubled == [(2 * i,) for i in range(n * 8)]

    # Mixed-tier gather marking: a device producer feeding a HOST-tier
    # consumer (object-keyed Map) is marked at plan time and gathers at
    # production, while device-consumed intermediates from earlier runs
    # stay mesh-resident throughout (their data never crosses DCN).
    shared_keys = rng.randint(0, 6, n * 16).astype(np.int32)
    shared = bs.Reduce(
        bs.Const(n, shared_keys, np.ones(len(shared_keys), np.int32)),
        add,
    )
    dev_rows = dict(sess.run(
        bs.Map(shared, lambda k, v: (k, v * 2))
    ).rows())
    with ex._lock:
        outs_before = set(ex._outputs)
        resident_before = {k for k, o in ex._outputs.items()
                           if not o.gathered}
    assert resident_before  # shared producer output lives on-mesh
    host_rows = dict(sess.run(
        bs.Map(shared, lambda k, v: (str(k), v + 100),
               out=[str, np.int32])
    ).rows())
    expect_s: dict = {}
    for kk in shared_keys.tolist():
        expect_s[kk] = expect_s.get(kk, 0) + 1
    assert dev_rows == {k: 2 * c for k, c in expect_s.items()}
    assert host_rows == {str(k): c + 100 for k, c in expect_s.items()}, \
        host_rows
    with ex._lock:
        new_outs = {k: o for k, o in ex._outputs.items()
                    if k not in outs_before}
        still_resident = {k for k, o in ex._outputs.items()
                          if not o.gathered}
    # The host-tier run's only device group is its producer — gathered
    # because its consumer is mesh-ineligible (no root device group:
    # the root chain itself is host-tier).
    assert new_outs and all(o.gathered for o in new_outs.values()), \
        new_outs
    # Nothing device-consumed was dragged across DCN by the host run.
    assert resident_before <= still_resident

    # 4. Host-tier distribution (exec/hostdist.py): object (string)
    # keys are mesh-ineligible, so these tasks route through the
    # HostTaskExchange — each task runs on exactly ONE deterministic
    # owner process (shard % nprocs), outputs exchanged lazily through
    # the coordination KV. The exec/bigmachine.go:731-1036 remote-
    # placement role, without the redundant-execution model.
    vocab = ["tpu", "mesh", "ici", "hbm", "mxu"]

    def gen_lines(shard):
        yield ([" ".join(vocab[(shard + j + i) % len(vocab)]
                         for j in range(3))
                for i in range(6)],)

    lines = bs.ReaderFunc(4, gen_lines, out=[str])
    words = bs.Flatmap(lines, lambda l: [(w,) for w in l.split()],
                       out=[str])
    ones = bs.Map(words, lambda w: (w, 1), out=[str, np.int32])
    wc = bs.Reduce(ones, add)
    got_h = dict(sess.run(wc).rows())
    expect_h: dict = {}
    for shard in range(4):
        for (batch,) in gen_lines(shard):
            for line in batch:
                for w in line.split():
                    expect_h[w] = expect_h.get(w, 0) + 1
    assert got_h == expect_h, (got_h, expect_h)
    hd = sess.executor._hostdist
    assert hd is not None and hd.active
    split = np.asarray(multihost_utils.process_allgather(
        np.asarray([hd.owned_count, hd.remote_count], np.int64)
    ))
    # Every process owned SOME host tasks and deferred to peers for
    # the rest — the work actually split instead of running N times.
    assert (split[:, 0] > 0).all(), split
    assert (split[:, 1] > 0).all(), split

    def _hd_keys():
        try:
            return list(hd.client.key_value_dir_get("bigslice/hostdist/"))
        except Exception:  # noqa: BLE001 — empty directory
            return []

    # KV hygiene: release_run (inside sess.run) deleted every NON-root
    # namespace after the cross-process barrier; the run's root
    # (result) outputs stay published for post-run scans.
    left = _hd_keys()
    assert left, "root outputs should remain published"
    assert all("reduce" in k[0] if isinstance(k, tuple) else "reduce" in k
               for k in left), left

    # 5. State-keyed SPMD probation (round-2 verdict #7b): an
    # infra-classified failure raised from a collective program
    # (injected symmetrically — both processes run this same code, so
    # both inject) puts the op on probation; resubmission routes to the
    # host tier on every process and the run SUCCEEDS without an
    # elastic restart. The device-resident producer becomes readable
    # through the retriable Missing → DepLost → host-re-run ladder.
    from bigslice_tpu.exec import meshexec as meshexec_mod

    orig_exec = meshexec_mod.MeshExecutor._execute_group_inner
    armed = {"n": 0}

    def failing_exec(self, gkey, gtasks):
        if (any("reduce" in t.name.op for t in gtasks)
                and "#" in gtasks[0].name.op and armed["n"] == 0):
            armed["n"] = 1
            raise RuntimeError(
                "injected device failure: RESOURCE_EXHAUSTED out of "
                "memory while allocating scratch"
            )
        return orig_exec(self, gkey, gtasks)

    meshexec_mod.MeshExecutor._execute_group_inner = failing_exec
    try:
        pk = rng.randint(0, 11, n * 24).astype(np.int32)
        pred = bs.Reduce(bs.Const(n, pk, np.ones(len(pk), np.int32)),
                         add)
        got_p = dict(sess.run(pred).rows())
    finally:
        meshexec_mod.MeshExecutor._execute_group_inner = orig_exec
    expect_p: dict = {}
    for kk in pk.tolist():
        expect_p[kk] = expect_p.get(kk, 0) + 1
    assert got_p == expect_p, (got_p, expect_p)
    assert armed["n"] == 1  # the failure actually fired
    assert ex._spmd_probation, "op should be on state-keyed probation"

    # Teardown deletes this process's remaining published namespaces;
    # after both sides close, the KV prefix is empty (no landfill).
    # Quiesce first: a peer may still be lazily fetching this process's
    # published roots for ITS result scans — closing early would delete
    # them mid-read (the tombstone bounds that to an error, but the
    # clean protocol is barrier → close → barrier → check).
    import time

    groups = sess.executor.device_group_count()
    try:
        hd.client.wait_at_barrier("bigslice_hostdist_quiesce", 60_000)
    except Exception:  # noqa: BLE001
        pass
    sess.shutdown()
    try:
        hd.client.wait_at_barrier("bigslice_hostdist_smoke_done", 60_000)
    except Exception:  # noqa: BLE001
        pass
    deadline = time.time() + 10.0
    while _hd_keys() and time.time() < deadline:
        time.sleep(0.2)
    assert not _hd_keys(), _hd_keys()

    if process_id == 0:
        print(f"MULTIHOST_SMOKE_OK processes={num_processes} devices={n}",
              flush=True)
        print("MULTIHOST_SESSION_OK "
              f"groups={groups}", flush=True)
        print(f"HOSTDIST_OK owned={split[:, 0].tolist()} "
              f"remote={split[:, 1].tolist()}", flush=True)
    try:
        jax.distributed.shutdown()
    except Exception:
        pass
    sys.stdout.flush()
    if hard_exit:
        # Children hard-exit: distributed service threads otherwise hang
        # interpreter shutdown. The parent returns so it can reap them.
        os._exit(0)
    return 0


def chaos_worker(num_processes: int, process_id: int, port: int) -> int:
    """Host-loss chaos (SURVEY §5.3's fault-injection idea at the
    process level): a full SPMD session runs healthy, then one peer
    dies abruptly; the survivor's next run must fail FAST with a
    classified HostLostError — not hang in a collective."""
    from bigslice_tpu.utils.hermetic import force_hermetic_cpu

    force_hermetic_cpu()
    import numpy as np

    from bigslice_tpu.utils import distributed

    distributed.initialize(
        coordinator=f"127.0.0.1:{port}",
        num_processes=num_processes,
        process_id=process_id,
    )
    import bigslice_tpu as bs
    from bigslice_tpu.exec import spmd as spmd_mod
    from bigslice_tpu.exec.meshexec import HostLostError
    from bigslice_tpu.exec.task import TaskError

    mesh = distributed.global_mesh()
    n = int(mesh.devices.size)
    sess = spmd_mod.spmd_session(mesh)

    def add(a, b):
        return a + b

    keys = np.arange(n * 16, dtype=np.int32) % 5
    red = bs.Reduce(bs.Const(n, keys, np.ones(len(keys), np.int32)), add)
    assert dict(sess.run(red).rows()) == {i: n * 16 // 5 + (
        1 if i < (n * 16) % 5 else 0) for i in range(5)}

    if process_id == 1:
        print("CHAOS: process 1 dying abruptly", flush=True)
        os._exit(1)

    import time

    t0 = time.time()
    try:
        sess.run(bs.Reduce(
            bs.Const(n, keys, np.ones(len(keys), np.int32)), add
        ))
        print("CHAOS_FAIL: second run succeeded with a dead peer",
              flush=True)
        os._exit(1)
    except TaskError as e:
        took = time.time() - t0
        ok = isinstance(e.cause, HostLostError) and took < 60
        print(f"CHAOS_{'OK' if ok else 'FAIL'}: "
              f"{type(e.cause).__name__} after {took:.1f}s", flush=True)
        os._exit(0 if ok else 1)


def wedge_worker(num_processes: int, process_id: int, port: int) -> int:
    """Wedged-peer chaos: unlike --chaos (abrupt death — caught by the
    collective error or the coordination service's own heartbeats), a
    WEDGED peer stays TCP-alive and service-heartbeat-healthy while its
    interpreter never reaches the next collective. Only the
    application-level keepalive (utils.distributed.Keepalive) can see
    it: the survivor's next run must fail fast with HostLostError
    (wrapping PeerLostError) at launch time — before entering the
    collective it would otherwise hang in forever."""
    from bigslice_tpu.utils.hermetic import force_hermetic_cpu

    force_hermetic_cpu()
    os.environ["BIGSLICE_KEEPALIVE_INTERVAL"] = "0.5"
    os.environ["BIGSLICE_KEEPALIVE_TIMEOUT"] = "5"
    import time

    import numpy as np

    from bigslice_tpu.utils import distributed

    distributed.initialize(
        coordinator=f"127.0.0.1:{port}",
        num_processes=num_processes,
        process_id=process_id,
    )
    import bigslice_tpu as bs
    from bigslice_tpu.exec import spmd as spmd_mod
    from bigslice_tpu.exec.meshexec import HostLostError
    from bigslice_tpu.exec.task import TaskError

    mesh = distributed.global_mesh()
    n = int(mesh.devices.size)
    sess = spmd_mod.spmd_session(mesh)
    client = distributed._coordination_client()

    def add(a, b):
        return a + b

    keys = np.arange(n * 16, dtype=np.int32) % 5
    red = bs.Reduce(bs.Const(n, keys, np.ones(len(keys), np.int32)), add)
    assert len(dict(sess.run(red).rows())) == 5

    if process_id == 1:
        # Simulate the hang: stop beating but keep the process (and the
        # coordination service connection) alive.
        sess.executor._keepalive.stop()
        client.key_value_set("bigslice/test/wedged", "1")
        print("WEDGE: process 1 hung (alive, not beating)", flush=True)
        time.sleep(300)  # parent kills us
        os._exit(1)

    client.blocking_key_value_get("bigslice/test/wedged", 60_000)
    time.sleep(7)  # let the peer's beat go stale past the 5s timeout
    t0 = time.time()
    try:
        sess.run(bs.Reduce(
            bs.Const(n, keys, np.ones(len(keys), np.int32)), add
        ))
        print("WEDGE_FAIL: run succeeded with a wedged peer", flush=True)
        os._exit(1)
    except TaskError as e:
        took = time.time() - t0
        ok = isinstance(e.cause, HostLostError) and took < 30
        print(f"WEDGE_{'OK' if ok else 'FAIL'}: "
              f"{type(e.cause).__name__} after {took:.1f}s", flush=True)
        sys.stdout.flush()
        os._exit(0 if ok else 1)


def killrun_worker(num_processes: int, process_id: int,
                   port: int) -> int:
    """Mid-collective kill chaos (round-5 verdict #8; the
    exec/chaosmonkey_test.go:44-103 shape at its harshest): a peer is
    SIGKILLed while an SPMD collective is EXECUTING — not between runs
    (--chaos) and not before launch (--wedge). The survivor's in-flight
    collective must error and classify as HostLostError, not hang.

    Mechanics: both processes warm-compile the reduce (so the killed
    run is pure execution), rendezvous through the coordination KV,
    and enter the run together. Each announces through the KV that it
    has reached the dispatch of the run's first wave program (the one
    that holds the exchange); process 1 then waits there for process
    0's announcement and SIGKILLs itself, so the survivor's collective
    is in flight, short of its peer's half, when the peer dies. No
    timer races the run: every interleaving ends in the survivor's
    error, and only a hang (the driver's 300 s) fails."""
    from bigslice_tpu.utils.hermetic import force_hermetic_cpu

    force_hermetic_cpu()
    import time

    import numpy as np

    from bigslice_tpu.utils import distributed

    distributed.initialize(
        coordinator=f"127.0.0.1:{port}",
        num_processes=num_processes,
        process_id=process_id,
    )
    import bigslice_tpu as bs
    from bigslice_tpu.exec import spmd as spmd_mod
    from bigslice_tpu.exec.meshexec import HostLostError
    from bigslice_tpu.exec.task import TaskError

    mesh = distributed.global_mesh()
    n = int(mesh.devices.size)
    sess = spmd_mod.spmd_session(mesh)
    client = distributed._coordination_client()

    def add(a, b):
        return a + b

    rows = n * (1 << 20)
    keys = (np.arange(rows, dtype=np.int64) % 65537).astype(np.int32)
    ones = np.ones(rows, np.int32)

    def pipeline():
        return bs.Reduce(bs.Const(n, keys, ones), add)

    assert sum(v for _, v in sess.run(pipeline()).rows()) == rows

    # Rendezvous: enter the killed run together.
    client.key_value_set(f"bigslice/test/killrun/{process_id}", "1")
    for p in range(num_processes):
        client.blocking_key_value_get(
            f"bigslice/test/killrun/{p}", 60_000
        )
    dispatch = sess.executor._dispatch_wave_on

    def announcing_dispatch(*args, **kwargs):
        sess.executor._dispatch_wave_on = dispatch  # first wave only
        client.key_value_set(
            f"bigslice/test/killrun/dispatch/{process_id}", "1")
        if process_id == 1:
            client.blocking_key_value_get(
                "bigslice/test/killrun/dispatch/0", 60_000)
            os.kill(os.getpid(), 9)
        return dispatch(*args, **kwargs)

    sess.executor._dispatch_wave_on = announcing_dispatch
    if process_id == 1:
        try:
            sess.run(pipeline())
        finally:
            os._exit(1)  # pragma: no cover — dies at the dispatch seam

    t0 = time.time()
    try:
        sess.run(pipeline())
        print("KILLRUN_FAIL: run succeeded with a peer killed "
              "mid-collective", flush=True)
        os._exit(1)
    except TaskError as e:
        ok = isinstance(e.cause, HostLostError)
        print(f"KILLRUN_{'OK' if ok else 'FAIL'}: "
              f"{type(e.cause).__name__} after {time.time() - t0:.1f}s "
              f"[{repr(e.cause)[:220]}]", flush=True)
        os._exit(0 if ok else 1)
    except SystemExit:  # pragma: no cover
        raise
    except BaseException as e:  # noqa: BLE001 — coordination-layer abort
        # The jax coordination service may kill the survivor's run with
        # its own fatal "peer died" error before our classification
        # sees it — the platform's host-loss detector doing the job.
        print(f"KILLRUN_OK: platform abort {type(e).__name__} after "
              f"{time.time() - t0:.1f}s", flush=True)
        os._exit(0)


def telemetry_worker(num_processes: int, process_id: int, port: int,
                     out_dir: str) -> int:
    """Fleet-telemetry smoke (the observability plane across REAL
    process boundaries): every rank runs the same skewed reduce with a
    per-rank trace file and a shared fleet store, exports its mergeable
    snapshot, and rank 0 pulls + merges and asserts the fleet summary
    actually carries BOTH ranks' attribution — per-rank shuffle rows at
    global partition offsets (the lifted multiprocess shuffle-boundary
    skip), per-rank compile counts (the lifted AOT seam), and per-rank
    exchange messages."""
    from bigslice_tpu.utils.hermetic import force_hermetic_cpu

    force_hermetic_cpu()
    import json

    import numpy as np

    from bigslice_tpu.utils import distributed

    distributed.initialize(
        coordinator=f"127.0.0.1:{port}",
        num_processes=num_processes,
        process_id=process_id,
    )
    import bigslice_tpu as bs
    from bigslice_tpu.exec import spmd as spmd_mod

    mesh = distributed.global_mesh()
    n = int(mesh.devices.size)
    sess = spmd_mod.spmd_session(
        mesh,
        trace_path=os.path.join(out_dir, f"trace-rank{process_id}.json"),
        fleet_dir=out_dir,
    )
    assert sess.fleet is not None
    client = distributed._coordination_client()

    def add(a, b):
        return a + b

    # Skewed keys (identical on every rank — same-driver contract): a
    # hot head so the fleet skew section carries real numbers.
    rng = np.random.RandomState(11)
    keys = (rng.zipf(1.3, n * 64) % 23).astype(np.int32)
    red = bs.Reduce(bs.Const(n, keys, np.ones(len(keys), np.int32)),
                    add)
    res = sess.run(red, corr="smoke:1")
    assert res.corr == "smoke:1"
    got = dict(res.rows())
    expect: dict = {}
    for kk in keys.tolist():
        expect[kk] = expect.get(kk, 0) + 1
    assert got == expect, (got, expect)

    # Publish this rank's snapshot NOW (the periodic exporter may not
    # have ticked yet), then rendezvous so rank 0's pull sees everyone.
    assert sess.fleet.export() is not None
    try:
        client.wait_at_barrier("bigslice_fleettelem_exported", 60_000)
    except Exception:  # noqa: BLE001
        pass

    if process_id == 0:
        fleet = sess.telemetry_summary(scope="fleet")
        assert fleet.get("scope") == "fleet"
        assert fleet.get("ranks") == list(range(num_processes)), \
            fleet.get("ranks")
        per_rank = fleet.get("per_rank") or {}
        assert set(per_rank) == {str(r) for r in range(num_processes)}, \
            sorted(per_rank)
        # The lifted AOT seam: compile attribution on EVERY rank.
        for r, pr in per_rank.items():
            assert pr["compiles"] > 0, (r, pr)
            assert pr["exchange_messages"] > 0, (r, pr)
        # The lifted shuffle-boundary skip: the reduce op's merged skew
        # vector spans the global partition space, with every rank's
        # addressable contribution tagged in per_rank_rows.
        skews = {op: e["skew"] for op, e in fleet["ops"].items()
                 if "skew" in e}
        assert skews, sorted(fleet["ops"])
        op, skew = next(iter(skews.items()))
        # Rows per partition are post-combine (distinct keys): the
        # merged vector spans the global partition space and sums to
        # the global distinct-key count — each rank contributed only
        # its addressable shards, so the total being right PROVES the
        # offsets interleaved instead of double-counting.
        assert len(skew["rows"]) == n, skew["rows"]
        assert sum(skew["rows"]) == len(expect), (skew, len(expect))
        prr = skew["per_rank_rows"]
        assert set(prr) == {str(r) for r in range(num_processes)}, prr
        assert all(v > 0 for v in prr.values()), prr
        with open(os.path.join(out_dir, "fleet-summary.json"),
                  "w") as fp:
            json.dump(fleet, fp, indent=2, sort_keys=True)

    try:
        client.wait_at_barrier("bigslice_fleettelem_checked", 60_000)
    except Exception:  # noqa: BLE001
        pass
    # shutdown(): final export, rank 0 merges fleet.json into the
    # store, every rank writes its trace-rank<r>.json.
    sess.shutdown()
    try:
        client.wait_at_barrier("bigslice_fleettelem_done", 60_000)
    except Exception:  # noqa: BLE001
        pass
    if process_id == 0:
        # Offline counterpart: obsdump --fleet over the same store must
        # reconstruct the same rank set from the exported snapshots.
        from bigslice_tpu.utils import fleettelemetry as fleet_mod

        snaps = fleet_mod.load_snapshots(out_dir)
        assert [s["rank"] for s in snaps] == list(range(num_processes))
        merged = fleet_mod.merge_snapshots(snaps)
        assert merged["ranks"] == list(range(num_processes))
        print(f"FLEETTELEM_OK ranks={merged['ranks']} "
              f"ops={len(merged['ops'])}", flush=True)
    sys.stdout.flush()
    os._exit(0)


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    if argv and argv[0] == "--telemetry-worker":
        return telemetry_worker(int(argv[1]), int(argv[2]),
                                int(argv[3]), argv[4])
    if argv and argv[0] == "--telemetry":
        import tempfile

        out_dir = None
        rest = argv[1:]
        if rest and rest[0] == "--out":
            out_dir = rest[1]
            os.makedirs(out_dir, exist_ok=True)
        if out_dir is None:
            out_dir = tempfile.mkdtemp(prefix="bigslice-fleet-")
        port = _free_port()
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        cap = tempfile.TemporaryFile(mode="w+")
        procs = [
            subprocess.Popen(
                [sys.executable, "-m",
                 "bigslice_tpu.tools.multihost_smoke",
                 "--telemetry-worker", "2", str(i), str(port), out_dir],
                env=env,
                stdout=cap if i == 0 else None,
                stderr=cap if i == 0 else None,
            )
            for i in (0, 1)
        ]
        rc = 1
        try:
            p0rc = procs[0].wait(timeout=240)
            cap.seek(0)
            text = cap.read()
            if p0rc == 0 and "FLEETTELEM_OK" in text:
                print(f"FLEETTELEM_OK: fleet summary merged from both "
                      f"ranks under {out_dir}", flush=True)
                rc = 0
            else:
                print(f"FLEETTELEM_FAIL: rc={p0rc}\n{text[-2000:]}",
                      flush=True)
        except subprocess.TimeoutExpired:
            print("FLEETTELEM_FAIL: workers hung past 240s", flush=True)
            procs[0].kill()
        finally:
            for p in procs:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()
        sys.exit(rc)
    if argv and argv[0] == "--killrun-worker":
        return killrun_worker(int(argv[1]), int(argv[2]), int(argv[3]))
    if argv and argv[0] == "--killrun":
        import tempfile

        port = _free_port()
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        cap = tempfile.TemporaryFile(mode="w+")
        procs = [
            subprocess.Popen(
                [sys.executable, "-m",
                 "bigslice_tpu.tools.multihost_smoke",
                 "--killrun-worker", "2", str(i), str(port)],
                env=env,
                stdout=cap if i == 0 else None,
                stderr=cap if i == 0 else None,
            )
            for i in (0, 1)
        ]
        # Same two legitimate fast-failure shapes as --chaos: (a) the
        # in-flight collective errors → classified HostLostError; (b)
        # the jax coordination service's own peer-death detection
        # terminates the survivor first (PollForError / heartbeat
        # fatals). Only a hang fails.
        rc = 1
        try:
            p0rc = procs[0].wait(timeout=300)
            cap.seek(0)
            text = cap.read()
            if p0rc == 0 and "KILLRUN_OK" in text:
                print("KILLRUN_OK: classified HostLostError mid-"
                      "collective", flush=True)
                rc = 0
            elif ("detected fatal errors" in text
                  or "stopped sending heartbeats" in text
                  or "CoordinationService" in text):
                print("KILLRUN_OK: coordination-service peer-death "
                      "detection terminated the survivor", flush=True)
                rc = 0
            else:
                print(f"KILLRUN_FAIL: rc={p0rc}\n{text[-1500:]}",
                      flush=True)
        except subprocess.TimeoutExpired:
            print("KILLRUN_FAIL: survivor hung past 300s", flush=True)
            procs[0].kill()
        finally:
            procs[1].kill()
            procs[1].wait(timeout=30)
        sys.exit(rc)
    if argv and argv[0] == "--chaos-worker":
        return chaos_worker(int(argv[1]), int(argv[2]), int(argv[3]))
    if argv and argv[0] == "--wedge-worker":
        return wedge_worker(int(argv[1]), int(argv[2]), int(argv[3]))
    if argv and argv[0] == "--wedge":
        port = _free_port()
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        procs = [
            subprocess.Popen(
                [sys.executable, "-m",
                 "bigslice_tpu.tools.multihost_smoke",
                 "--wedge-worker", "2", str(i), str(port)],
                env=env,
            )
            for i in (0, 1)
        ]
        rc = 1
        try:
            rc = procs[0].wait(timeout=150)
        except subprocess.TimeoutExpired:
            print("WEDGE_FAIL: survivor hung past 150s", flush=True)
            procs[0].kill()
        finally:
            procs[1].kill()  # wedged by design; reap it
            procs[1].wait(timeout=30)
        sys.exit(rc)
    if argv and argv[0] == "--chaos":
        import tempfile

        port = _free_port()
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        cap = tempfile.TemporaryFile(mode="w+")
        procs = [
            subprocess.Popen(
                [sys.executable, "-m",
                 "bigslice_tpu.tools.multihost_smoke",
                 "--chaos-worker", "2", str(i), str(port)],
                env=env,
                stdout=cap if i == 0 else None,
                stderr=cap if i == 0 else None,
            )
            for i in (0, 1)
        ]
        # Process 1 exits 1 by design (the chaos); process 0 carries
        # the verdict. Two legitimate fast-failure shapes:
        # (a) the collective errors first → our classified
        #     HostLostError (CHAOS_OK), or
        # (b) the jax coordination service's heartbeat detection kills
        #     the survivor with a fatal "another task died" report —
        #     the platform's own host-loss detector doing the job.
        # A hang (timeout) is the only failure.
        rc = 1
        try:
            p0rc = procs[0].wait(timeout=150)
            cap.seek(0)
            text = cap.read()
            if p0rc == 0 and "CHAOS_OK" in text:
                print("CHAOS_OK: classified HostLostError", flush=True)
                rc = 0
            elif ("detected fatal errors" in text
                  or "stopped sending heartbeats" in text):
                print("CHAOS_OK: coordination-service heartbeat "
                      "detection terminated the survivor", flush=True)
                rc = 0
            else:
                print(f"CHAOS_FAIL: rc={p0rc}\n{text[-1500:]}",
                      flush=True)
        except subprocess.TimeoutExpired:
            print("CHAOS_FAIL: survivor hung past 150s", flush=True)
        finally:
            for p in procs:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()
        sys.exit(rc)
    if argv and argv[0] == "--worker":
        return worker(int(argv[1]), int(argv[2]), int(argv[3]))
    nproc = int(argv[0]) if argv else 2
    port = _free_port()  # fresh ephemeral port per run: no collisions
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "bigslice_tpu.tools.multihost_smoke",
             "--worker", str(nproc), str(i), str(port)],
            env=env,
        )
        for i in range(1, nproc)
    ]
    rc = 1  # failure until the parent worker completes
    try:
        rc = worker(nproc, 0, port, hard_exit=False)
    finally:
        for p in procs:
            try:
                rc |= p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                rc |= 1
    # All children reaped; now hard-exit past any lingering service
    # threads in this (parent) process too.
    sys.stdout.flush()
    os._exit(rc)


if __name__ == "__main__":
    sys.exit(main())
