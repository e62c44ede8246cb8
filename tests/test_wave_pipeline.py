"""The overlapped wave pipeline: prefetch staging, non-blocking
dispatch, and buffer donation in the mesh executor (S > N wave
streaming).

Pins the two contracts the pipeline must keep:

- PARITY: prefetch_depth=0 (the strictly serial loop) and
  prefetch_depth>=1 (staging overlap + in-flight dispatch window)
  produce identical merged outputs — the pipeline reorders nothing
  observable, it only hides host staging behind device compute.
- DONATION SAFETY: per-wave buffers the executor staged itself are
  donated (and so deleted) after their wave, yet merged/streamed
  outputs never observe the reuse — zero-copy producer outputs are
  never donated, and wave outputs are donated only into the cross-wave
  merge that consumes them.
"""

import numpy as np
import pytest

import jax

import bigslice_tpu as bs
from bigslice_tpu.exec.evaluate import (
    PHASE_WAVE_COMPUTE,
    PHASE_WAVE_PREFETCH,
)
from bigslice_tpu.exec.meshexec import MeshExecutor
from bigslice_tpu.exec.session import Session


@pytest.fixture
def mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:8]), ("shards",))


def _sess(mesh, depth, **kw):
    return Session(executor=MeshExecutor(mesh, prefetch_depth=depth,
                                         **kw))


def _waved_reduce_rows(mesh, depth, **kw):
    """S=32 shards on the 8-device mesh (4×N): keyed Reduce through the
    wave-partitioned shuffle + cross-wave merge."""
    rng = np.random.RandomState(23)
    keys = rng.randint(0, 97, 32 * 64).astype(np.int32)
    vals = rng.randint(1, 9, 32 * 64).astype(np.int32)
    sess = _sess(mesh, depth, **kw)
    res = sess.run(bs.Reduce(bs.Const(32, keys, vals),
                             lambda a, b: a + b))
    rows = sorted(res.rows())
    assert sess.executor.device_group_count() >= 2
    oracle = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        oracle[k] = oracle.get(k, 0) + v
    assert dict(rows) == oracle
    return rows


def test_prefetch_parity_waved_reduce(mesh):
    """The acceptance contract: prefetch 0 and 1 (and 2) yield
    identical merged outputs on an S=4×N wave-streamed keyed Reduce."""
    serial = _waved_reduce_rows(mesh, depth=0)
    piped = _waved_reduce_rows(mesh, depth=1)
    deep = _waved_reduce_rows(mesh, depth=2)
    assert serial == piped == deep


def test_prefetch_parity_waved_cogroup(mesh):
    """S=4×N ragged Cogroup (unpartitioned waved output, per-wave shard
    identity): serial and pipelined runs agree group for group."""
    rng = np.random.RandomState(5)
    keys = rng.randint(0, 41, 32 * 40).astype(np.int32)
    vals = rng.randint(0, 1000, 32 * 40).astype(np.int32)

    def run(depth):
        sess = _sess(mesh, depth)
        res = sess.run(bs.Cogroup(bs.Const(32, keys, vals)))
        out = sorted(
            (k, sorted(g)) for k, g in res.rows()
        )
        assert sess.executor.device_group_count() >= 1
        return out

    serial = run(0)
    piped = run(1)
    assert serial == piped
    oracle = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        oracle.setdefault(k, []).append(v)
    assert serial == sorted((k, sorted(g)) for k, g in oracle.items())


def test_prefetch_parity_float_reduce(mesh):
    """Float combine (min) across waves: the pipelined schedule must
    not change floating-point results — same programs, same inputs,
    same dispatch order, bit-equal outputs."""
    import jax.numpy as jnp

    rng = np.random.RandomState(11)
    keys = rng.randint(0, 60, 32 * 50).astype(np.int32)
    vals = rng.rand(32 * 50).astype(np.float32)

    def run(depth):
        sess = _sess(mesh, depth)
        res = sess.run(bs.Reduce(bs.Const(32, keys, vals),
                                 lambda a, b: jnp.minimum(a, b)))
        return sorted(res.rows())

    r0, r1 = run(0), run(1)
    assert [k for k, _ in r0] == [k for k, _ in r1]
    np.testing.assert_array_equal(
        np.array([v for _, v in r0]), np.array([v for _, v in r1])
    )


def test_donated_wave_buffers_consumed_not_aliased(mesh):
    """Donation engages on staged wave uploads (XLA deletes the donated
    buffers whose shapes alias an output — the steady-state case, where
    input and receive capacities match) AND the merged output never
    observes the reuse: results still match the oracle after donated
    HBM has been recycled. auto_dense pinned off so the generic wave
    program (whose receive buffer matches the input capacity at slack
    1.0) runs — donation at the XLA level is input→output ALIASING, so
    a shape-changing lowering legitimately declines it."""
    from bigslice_tpu.parallel.jitutil import donation_supported

    if not donation_supported():
        pytest.skip("backend does not implement buffer donation")
    ex = MeshExecutor(mesh, prefetch_depth=1, donate_buffers=True,
                      auto_dense=False)
    staged = []
    orig = ex._upload

    def spy_upload(frames):
        out = orig(frames)
        staged.append(out)
        return out

    ex._upload = spy_upload
    sess = Session(executor=ex)
    rng = np.random.RandomState(7)
    keys = rng.randint(0, 24, 32 * 200).astype(np.int32)
    vals = rng.randint(1, 7, 32 * 200).astype(np.int32)
    res = sess.run(bs.Reduce(bs.Const(32, keys, vals),
                             lambda a, b: a + b))
    oracle = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        oracle[k] = oracle.get(k, 0) + v
    # Correctness first: a donated buffer aliased into a live output
    # would corrupt these sums.
    assert dict(res.rows()) == oracle
    assert staged, "waved source never staged uploads"
    deleted = [
        all(c.is_deleted() for c in cols)
        for cols, _counts, _cap, _sub, owned in staged if owned
    ]
    # Donation actually engaged: staged wave inputs were consumed.
    assert any(deleted), (
        "no staged upload was ever consumed by its wave program"
    )
    # And reading the result AGAIN (store-bridge re-materialization)
    # still works — merged outputs hold their own buffers.
    assert dict(res.rows()) == oracle


def test_donation_off_knob(mesh):
    """donate_buffers=False keeps every staged buffer alive (the
    debugging/off switch documented in docs/wave_pipeline.md)."""
    ex = MeshExecutor(mesh, prefetch_depth=1, donate_buffers=False)
    staged = []
    orig = ex._upload

    def spy_upload(frames):
        out = orig(frames)
        staged.append(out)
        return out

    ex._upload = spy_upload
    sess = Session(executor=ex)
    keys = np.arange(32 * 16, dtype=np.int32) % 19
    vals = np.ones(32 * 16, np.int32)
    res = sess.run(bs.Reduce(bs.Const(32, keys, vals),
                             lambda a, b: a + b))
    assert len(dict(res.rows())) == 19
    assert staged
    assert not any(
        c.is_deleted() for cols, *_ in staged for c in cols
    )


def test_wave_phase_events(mesh):
    """Monitors opting in via ``on_phase`` see the pipeline's
    prefetch/compute markers in wave order (evaluate.notify_phase →
    status.chain_monitors forwarding)."""
    events = []

    class PhaseMonitor:
        def __call__(self, task, state):
            pass

        def on_phase(self, task, phase, wave):
            events.append((phase, wave))

    ex = MeshExecutor(mesh, prefetch_depth=1)
    sess = Session(executor=ex, monitor=PhaseMonitor())
    keys = (np.arange(32 * 16, dtype=np.int32) * 7) % 23
    res = sess.run(bs.Reduce(bs.Const(32, keys,
                                      np.ones(32 * 16, np.int32)),
                             lambda a, b: a + b))
    assert len(dict(res.rows())) == 23
    computes = [w for p, w in events if p == PHASE_WAVE_COMPUTE]
    prefetches = [w for p, w in events if p == PHASE_WAVE_PREFETCH]
    # Every wave of the 32-shard groups dispatched in order, and the
    # prefetcher staged every wave past the first.
    assert computes, events
    assert sorted(set(computes)) == list(range(max(computes) + 1))
    assert prefetches and 0 not in prefetches


def test_budget_clamps_prefetch_depth(mesh):
    """prefetch never busts device_budget_bytes: when one wave's
    estimated working set already fills the budget, the effective
    depth collapses to 0 (serial), and results stay correct."""
    ex = MeshExecutor(mesh, prefetch_depth=2,
                      device_budget_bytes=2_000)
    sess = Session(executor=ex)
    rng = np.random.RandomState(3)
    keys = rng.randint(0, 29, 32 * 64).astype(np.int32)
    vals = np.ones(32 * 64, np.int32)
    res = sess.run(bs.Reduce(bs.Const(32, keys, vals),
                             lambda a, b: a + b))
    oracle = {}
    for k in keys.tolist():
        oracle[k] = oracle.get(k, 0) + 1
    assert dict(res.rows()) == oracle
    # The knob itself stays as configured; only the per-group effective
    # depth clamps.
    assert ex.prefetch_depth == 2
    fake_inputs = [([np.zeros(512, np.int32)], np.zeros(8, np.int32),
                    512, False, True)]
    t0 = _first_waved_task(sess)
    assert ex._effective_prefetch_depth(t0, fake_inputs, 4) == 0


def _first_waved_task(sess):
    """Any waved task recorded by the executor (for unit-poking the
    depth calculation)."""
    ex = sess.executor
    with ex._lock:
        for _name, (_key, t) in ex._task_index.items():
            return t
    raise AssertionError("no device task recorded")


def test_prefetch_depth_env_default(mesh, monkeypatch):
    monkeypatch.setenv("BIGSLICE_PREFETCH_DEPTH", "3")
    ex = MeshExecutor(mesh)
    assert ex.prefetch_depth == 3
    monkeypatch.setenv("BIGSLICE_PREFETCH_DEPTH", "0")
    ex = MeshExecutor(mesh)
    assert ex.prefetch_depth == 0


def test_hash_reduce_kernel_matches_sort_kernel(mesh):
    """The standalone sortless kernel (hashagg.MeshHashReduceByKey)
    agrees with the sort-pipeline kernel and the numpy oracle; its
    donated variant consumes its inputs."""
    from bigslice_tpu.parallel import hashagg as hashagg_mod
    from bigslice_tpu.parallel import shuffle as shuffle_mod
    from bigslice_tpu.parallel.jitutil import donation_supported

    rng = np.random.RandomState(19)
    n, per = 8, 256
    cap = per
    # Key space sized for the hash table's per-region capacity
    # (combine_region_size(256, 8) = 32 slots vs ~13 distinct keys per
    # region): a cascade overflow here would be a planner bug, not skew.
    keys = rng.randint(0, 100, n * per).astype(np.int32)
    vals = rng.randint(1, 10, n * per).astype(np.int32)
    kc = [keys[i * per:(i + 1) * per] for i in range(n)]
    vc = [vals[i * per:(i + 1) * per] for i in range(n)]

    def staged():
        cols, counts = shuffle_mod.shard_columns(
            mesh, [kc, vc], [per] * n, cap
        )
        return cols, counts

    cols, counts = staged()
    hashed = hashagg_mod.MeshHashReduceByKey(
        mesh, nkeys=1, nvals=1, capacity=cap, ops=["add"]
    )
    hk, hv, hn, hov = hashed([cols[0]], [cols[1]], counts)
    assert int(np.asarray(hov)) == 0
    sorted_red = shuffle_mod.MeshReduceByKey(
        mesh, nkeys=1, nvals=1, capacity=cap,
        combine_fn=lambda a, b: a + b,
    )
    cols2, counts2 = staged()
    sk, sv, sn, sov = sorted_red([cols2[0]], [cols2[1]], counts2)
    assert int(np.asarray(sov)) == 0

    def rowset(k, v, cnt, capacity):
        chunks = shuffle_mod.unshard_columns([k, v], np.asarray(cnt),
                                             capacity)
        return sorted(
            (int(kk), int(vv))
            for ks, vs in zip(*chunks)
            for kk, vv in zip(np.asarray(ks), np.asarray(vs))
        )

    got_h = rowset(hk[0], hv[0], hn, hashed.out_capacity)
    got_s = rowset(sk[0], sv[0], sn, sorted_red.out_capacity)
    oracle = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        oracle[k] = oracle.get(k, 0) + v
    assert got_h == sorted(oracle.items())
    assert got_h == got_s

    if donation_supported():
        cols3, counts3 = staged()
        donating = hashagg_mod.MeshHashReduceByKey(
            mesh, nkeys=1, nvals=1, capacity=cap, ops=["add"],
            donate=True,
        )
        dk, dv, dn, dov = donating([cols3[0]], [cols3[1]], counts3)
        assert int(np.asarray(dov)) == 0
        assert rowset(dk[0], dv[0], dn,
                      donating.out_capacity) == sorted(oracle.items())
        assert cols3[0].is_deleted() and cols3[1].is_deleted()


@pytest.mark.parametrize("ndev,shards", [(8, 32), (4, 20)])
def test_subid_split_parity_and_engagement(ndev, shards):
    """The one-pass subid pre-split (consumer waves chain on their own
    compacted partition rows instead of subid-filtering the full
    receive buffer) changes nothing observable: split on/off produce
    identical rows, and the split views actually engage (the producer's
    wave-partitioned output grows per-wave views). 8 devices run 4
    waves, 4 devices 5."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:ndev]), ("shards",))
    rng = np.random.RandomState(31)
    keys = rng.randint(0, 1 << 14, shards * 80).astype(np.int32)
    vals = rng.randint(1, 5, shards * 80).astype(np.int32)

    def run(split):
        ex = MeshExecutor(mesh, prefetch_depth=1, subid_split=split)
        sess = Session(executor=ex)
        res = sess.run(bs.Reduce(bs.Const(shards, keys, vals),
                                 lambda a, b: a + b))
        rows = sorted(res.rows())
        views = [
            getattr(o, "_wave_views", None)
            for o in ex._outputs.values()
        ]
        return rows, any(v is not None for v in views)

    on_rows, on_views = run(True)
    off_rows, off_views = run(False)
    assert on_rows == off_rows
    assert on_views and not off_views
    oracle = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        oracle[k] = oracle.get(k, 0) + v
    assert dict(on_rows) == oracle


def test_subid_split_declines_under_budget(mesh):
    """Under a tuned device_budget_bytes the split's W-view residency
    blowup must decline (consumers keep the subid-filter program) and
    results stay correct."""
    ex = MeshExecutor(mesh, prefetch_depth=0, subid_split=True,
                      device_budget_bytes=1_000)
    sess = Session(executor=ex)
    rng = np.random.RandomState(9)
    keys = rng.randint(0, 300, 32 * 64).astype(np.int32)
    vals = np.ones(32 * 64, np.int32)
    res = sess.run(bs.Reduce(bs.Const(32, keys, vals),
                             lambda a, b: a + b))
    oracle = {}
    for k in keys.tolist():
        oracle[k] = oracle.get(k, 0) + 1
    assert dict(res.rows()) == oracle
    for o in ex._outputs.values():
        views = getattr(o, "_wave_views", None)
        if views is not None:
            assert views[1] is None  # declined, decline cached


# ----------------------------------------------------------------------
# The merged map-side output is grouped by subid by ONE stable sort and
# the reduce side's views are slices of it: checked against a plain
# numpy split, on wave outputs built by hand.

from types import SimpleNamespace

from bigslice_tpu.exec.meshexec import DeviceGroupOutput
from bigslice_tpu.parallel import segment
from bigslice_tpu.parallel import shuffle as shuffle_mod
from bigslice_tpu.parallel.jitutil import bucket_size


def _ct(dtype, shape=()):
    return SimpleNamespace(dtype=np.dtype(dtype), shape=shape)


def _task0(schema, combiner=None):
    return SimpleNamespace(
        schema=schema,
        partitioner=SimpleNamespace(
            combiner=combiner,
            combine_key="mc" if combiner is not None else "",
        ),
    )


#: name -> (devices, subids W, caps of the producer waves, how a wave's
#: valid rows draw their subid, vector payload)
SPLIT_CASES = {
    # Ragged waves: every wave leaves invalid rows INSIDE the
    # concatenation, and those rows carry in-range subids.
    "ragged": (8, 4, (64, 64, 64, 64), "uniform", False),
    "empty_subid": (8, 4, (64, 64, 64), "skip2", False),
    # One subid holds nearly everything: capr is a bucket above cap.
    "one_subid_nearly_all": (8, 4, (48, 48, 48), "heavy0", False),
    "unequal_caps": (8, 4, (64, 80, 64, 96), "uniform", False),
    "vector_payload": (8, 4, (64, 64, 64), "uniform", True),
    "w_not_power_of_two": (8, 5, (64, 64, 64, 64, 64), "uniform", False),
    "four_devices": (4, 6, (32, 32, 40, 32, 32, 32), "uniform", False),
}


def _draw_subid(rng, how, n, W):
    if how == "skip2":
        return rng.choice([w for w in range(W) if w != 2], n)
    if how == "heavy0":
        return np.where(rng.rand(n) < 0.97, 0, rng.randint(0, W, n))
    return rng.randint(0, W, n)


def _hand_built_waves(case, seed=0, full=False):
    """(executor, wave outputs, task0, W, per-device host rows): the
    host rows are each device's valid rows in wave order — what a
    stable compaction of the concatenated waves keeps."""
    from jax.sharding import Mesh

    ndev, W, caps, how, vector = SPLIT_CASES[case]
    mesh = Mesh(np.array(jax.devices()[:ndev]), ("shards",))
    ex = MeshExecutor(mesh)
    rng = np.random.RandomState(seed)
    schema = [_ct("int32"), _ct("int32")]
    if vector:
        schema.append(_ct("float32", (3,)))
    outs, host = [], [[] for _ in range(ndev)]
    for cap in caps:
        counts = (np.full(ndev, cap) if full
                  else rng.randint(0, cap + 1, ndev)).astype(np.int32)
        # Rows past a wave's count are garbage with in-range subids.
        cols = [rng.randint(0, W, ndev * cap).astype(np.int32),
                rng.randint(0, 1 << 20, ndev * cap).astype(np.int32),
                rng.randint(1, 50, ndev * cap).astype(np.int32)]
        if vector:
            cols.append(rng.rand(ndev * cap, 3).astype(np.float32))
        for d in range(ndev):
            n = int(counts[d])
            cols[0][d * cap : d * cap + n] = _draw_subid(rng, how, n, W)
            host[d].append([c[d * cap : d * cap + n] for c in cols])
        gcols, gcounts = shuffle_mod.place_global_columns(
            mesh, cols, counts
        )
        outs.append(DeviceGroupOutput(
            list(gcols), gcounts, cap, schema, partitioned=True,
            subid=True, nmesh=ndev,
        ))
    rows = [
        [np.concatenate([w[j] for w in waves])
         for j in range(len(schema) + 1)]
        for waves in host
    ]
    return ex, outs, _task0(schema), W, rows


def _per_device(arr, ndev):
    arr = np.asarray(arr)
    return arr.reshape((ndev, arr.shape[0] // ndev) + arr.shape[1:])


def _assert_views_match(views, rows, W):
    """Region w of device d == the rows of subid w in their order in
    ``rows[d]``, zeros behind them, at the bucket of the fullest
    (device, subid) cell."""
    ndev = len(rows)
    fullest = max(
        int((r[0] == w).sum()) for r in rows for w in range(W)
    )
    assert len(views) == W
    for w, view in enumerate(views):
        assert view.capacity == bucket_size(fullest)
        assert not view.subid and view.partitioned
        counts = np.asarray(view.counts)
        for d in range(ndev):
            sel = rows[d][0] == w
            assert counts[d] == sel.sum()
            for got, want in zip(view.cols, rows[d][1:]):
                got = _per_device(got, ndev)[d]
                np.testing.assert_array_equal(got[: sel.sum()],
                                              want[sel])
                assert not got[sel.sum():].any()


@pytest.mark.parametrize("case", sorted(SPLIT_CASES))
def test_wave_views_equal_numpy_split(case):
    ex, outs, task0, W, rows = _hand_built_waves(case)
    merged = ex._merge_waves(outs, task0)
    _assert_views_match(ex._build_wave_views(merged, W), rows, W)


@pytest.mark.parametrize("case", sorted(SPLIT_CASES))
def test_merged_output_is_front_packed_and_subid_ordered(case):
    """The merged output's contract: capacity sum(caps), counts exact,
    the valid rows first and the tail zeros (as compact_by_mask leaves
    them), grouped by subid with the order of arrival kept inside a
    subid — the stable argsort of what compact_by_mask returns."""
    ex, outs, task0, W, rows = _hand_built_waves(case)
    caps = SPLIT_CASES[case][2]
    ndev = len(rows)
    merged = ex._merge_waves(outs, task0)
    assert merged.subid and merged.subid_ordered and merged.partitioned
    assert merged.capacity == sum(caps)
    counts = np.asarray(merged.counts)
    for d in range(ndev):
        n = len(rows[d][0])
        assert counts[d] == n
        # compact_by_mask over the same concatenation keeps rows[d].
        mask = np.zeros(sum(caps), bool)
        mask[:n] = True
        padded = [
            np.concatenate([c, np.zeros((sum(caps) - n,) + c.shape[1:],
                                        c.dtype)])
            for c in rows[d]
        ]
        kept_n, kept = segment.compact_by_mask(mask, padded)
        assert int(kept_n) == n
        order = np.argsort(np.asarray(kept[0])[:n], kind="stable")
        for got, want in zip(merged.cols, kept):
            got = _per_device(got, ndev)[d]
            np.testing.assert_array_equal(got[:n],
                                          np.asarray(want)[:n][order])
            assert not got[n:].any()


def _op_shapes(text):
    """Element counts of every tensor type in a StableHLO module."""
    import re

    return {
        int(np.prod([int(x) for x in dims.split("x") if x]))
        for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]", text)
    }


def _split_on_one_device(W, cap, capr, presorted):
    """(bs_subid_split for a mesh of one, its argument shapes)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("shards",))
    col = NamedSharding(mesh, P("shards"))
    prog = MeshExecutor(mesh)._subid_split_program(
        ("int32",) * 3, W, cap, capr, presorted
    )
    return prog, [jax.ShapeDtypeStruct((1,), np.int32, sharding=col)] + [
        jax.ShapeDtypeStruct((cap,), np.int32, sharding=col)
    ] * 3


@pytest.mark.parametrize("presorted", [True, False])
def test_split_program_holds_no_scatter_and_nothing_cap_by_w(presorted):
    W, cap, capr = 8, 1 << 12, 1 << 10
    prog, args = _split_on_one_device(W, cap, capr, presorted)
    text = prog.lower(*args).as_text()
    assert "bs_subid_split" in text
    assert "scatter" not in text
    assert text.count("stablehlo.sort") == (0 if presorted else 1)
    # The widest array is a column with its capr rows of padding.
    assert max(_op_shapes(text)) == cap + capr < cap * W


def test_merge_program_with_a_subid_is_one_sort_and_no_scatter():
    ex, outs, task0, W, rows = _hand_built_waves("ragged")
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
             for a in [o.counts for o in outs]
             + [c for o in outs for c in o.cols]]
    ex._merge_waves(outs, task0)
    (prog,) = [v[0] for k, v in ex._programs.items() if k[0] == "merge"]
    text = prog.lower(*specs).as_text()
    assert "bs_merge" in text
    assert text.count("stablehlo.sort") == 1
    assert "scatter" not in text


def test_split_temporaries_do_not_grow_with_the_number_of_waves():
    """At a fixed receive capacity the split's scratch is O(cap): the
    one-hot it replaced was cap x W."""
    cap, capr = 1 << 16, 1 << 11

    def temp(W):
        prog, args = _split_on_one_device(W, cap, capr, True)
        mem = prog.lower(*args).compile().memory_analysis()
        return mem.temp_size_in_bytes

    few, many = temp(8), temp(32)
    # The lane and two padded columns; 24 more subids add no column.
    assert many <= 4 * 3 * (cap + capr)
    assert many - few < 4 * cap


@pytest.mark.parametrize("case", ["ragged", "vector_payload"])
def test_unordered_output_takes_the_same_split_program(case):
    """An output nobody ordered (one wave as a map-side program leaves
    it: front-packed, subids in no order, no merge ran) goes through
    bs_subid_split as well, which orders it first: same views."""
    ex, outs, _, W, _ = _hand_built_waves(case)
    out, ndev = outs[0], SPLIT_CASES[case][0]
    assert out.subid and not out.subid_ordered
    counts = np.asarray(out.counts)
    rows = [[_per_device(c, ndev)[d][: counts[d]] for c in out.cols]
            for d in range(ndev)]
    _assert_views_match(ex._build_wave_views(out, W), rows, W)
    kinds = {k[0]: k[-1] for k in ex._programs if k[0] == "subidsplit"}
    assert kinds == {"subidsplit": False}


def test_machine_combined_merge_is_ordered_and_sliced():
    """mc=True: the cross-wave re-combine sorts by (validity, subid,
    key), so its output is subid-ordered too and the split slices it
    without a sort of its own."""
    ex, outs, task0, W, rows = _hand_built_waves("ragged", seed=3)
    fc = SimpleNamespace(fn=lambda a, b: a + b, nkeys=1, nvals=1,
                         device=True)
    merged = ex._merge_waves(outs, _task0(task0.schema, combiner=fc))
    assert merged.subid_ordered
    want = []
    for sub, key, val in rows:
        acc = {}
        for s, k, v in zip(sub.tolist(), key.tolist(), val.tolist()):
            acc[(s, k)] = acc.get((s, k), 0) + v
        keys = sorted(acc)
        want.append([np.array([s for s, _ in keys], np.int32),
                     np.array([k for _, k in keys], np.int32),
                     np.array([acc[sk] for sk in keys], np.int32)])
    _assert_views_match(ex._build_wave_views(merged, W), want, W)
    kinds = {k[0]: k[-1] for k in ex._programs if k[0] == "subidsplit"}
    assert kinds == {"subidsplit": True}


# ------------------------- the merge reads a wave up to its rows' bucket
#
# A settled wave carries its fullest device's row count on the host
# (``rows_max``, the fifth signal); the cross-wave merge reads wave w up
# to ``min(cap_w, bucket_size(largest count))`` slots. What it leaves
# out was masked out before, so the merged rows are the full-capacity
# merge's bit for bit.

#: name -> (caps of the waves, rows a device keeps of ``cap``, the wave
#: that carries no ``rows_max``)
FILL_CASES = {
    "about_half": ((128, 128, 128),
                   lambda rng, cap: rng.binomial(cap, .375), None),
    "under_one_percent": ((256, 256, 256, 256),
                          lambda rng, cap: rng.randint(0, 3), None),
    "all": ((64, 64, 64), lambda rng, cap: cap, None),
    "none": ((64, 64, 64), lambda rng, cap: 0, None),
    # A slack retry between waves: the later waves' buckets are larger,
    # and the bucket of the largest count cuts some waves and not all.
    "unequal_caps": ((32, 66, 80), lambda rng, cap: rng.randint(20, 33),
                     None),
    "one_wave_without_a_count": (
        (128, 128, 128), lambda rng, cap: rng.binomial(cap, .375), 1),
}


def _settled_waves(case, ndev, known=True, seed=0):
    """(executor, wave outputs as a settle leaves them, per-device
    counts a wave): subid outputs of two int32 columns whose rows past
    a wave's count are garbage. ``known`` False: the same waves with no
    ``rows_max``, which merge at full capacity."""
    caps, keep, blind = FILL_CASES[case]
    ex = MeshExecutor(_mesh_of(ndev))
    rng = np.random.RandomState(seed)
    schema = [_ct("int32"), _ct("int32")]
    outs, kept = [], []
    for w, cap in enumerate(caps):
        counts = np.array([keep(rng, cap) for _ in range(ndev)], np.int32)
        cols = [rng.randint(0, 4, ndev * cap).astype(np.int32),
                rng.randint(0, 1 << 20, ndev * cap).astype(np.int32),
                rng.randint(1, 50, ndev * cap).astype(np.int32)]
        gcols, gcounts = shuffle_mod.place_global_columns(
            ex.mesh, cols, counts)
        outs.append(DeviceGroupOutput(
            list(gcols), gcounts, cap, schema, partitioned=True,
            subid=True, nmesh=ndev,
            rows_max=int(counts.max()) if known and w != blind else None,
        ))
        kept.append(counts)
    return ex, outs, _task0(schema), kept


def _merge_key(ex):
    (key,) = [k for k in ex._programs if k[0] == "merge"]
    return key


def _assert_same_merged_rows(got, want, ndev):
    counts = np.asarray(want.counts)
    np.testing.assert_array_equal(np.asarray(got.counts), counts)
    for g, w in zip(got.cols, want.cols):
        for d in range(ndev):
            n = counts[d]
            g_d, w_d = _per_device(g, ndev)[d], _per_device(w, ndev)[d]
            np.testing.assert_array_equal(g_d[:n], w_d[:n])
            assert not g_d[n:].any()


@pytest.mark.parametrize("ndev", [1, 8])
@pytest.mark.parametrize("case", sorted(FILL_CASES) + ["machine_combined"])
def test_merge_reads_each_wave_up_to_the_bucket_of_the_largest_count(
        case, ndev):
    fc = None
    if case == "machine_combined":
        case = "about_half"
        fc = SimpleNamespace(fn=lambda a, b: a + b, nkeys=1, nvals=1,
                             device=True)
    caps, _, blind = FILL_CASES[case]
    ex, outs, task0, kept = _settled_waves(case, ndev)
    full_ex, full_outs, _, _ = _settled_waves(case, ndev, known=False)
    task0 = _task0(task0.schema, combiner=fc)
    merged = ex._merge_waves(outs, task0)
    at_full = full_ex._merge_waves(full_outs, task0)
    # The same rows a partition, in the same order, zeros behind them.
    assert at_full.capacity == sum(caps)
    assert merged.subid_ordered and merged.rows_max is None
    _assert_same_merged_rows(merged, at_full, ndev)
    if fc is None:
        np.testing.assert_array_equal(np.asarray(merged.counts),
                                      np.sum(kept, axis=0))
    B = bucket_size(max(int(c.max()) for c in kept))
    read = tuple(min(cap, B) for cap in caps)
    if blind is not None or case == "all":
        # Nothing to leave out, or a wave whose count nobody knows: the
        # parent's shapes, and its program under its key.
        read = caps
        assert _merge_key(ex) == _merge_key(full_ex)
    else:
        assert sum(read) < sum(caps)
        assert _merge_key(ex) == (
            _merge_key(full_ex)[:2] + (read,) + _merge_key(full_ex)[3:]
            + (caps,))
    assert merged.capacity == sum(read)
    assert merged.cols[0].shape[0] == ndev * sum(read)


# ------------------------------------- the wave programs hold no scatter

def _row_indexed_scatters(text):
    """Index-tensor types of the scatters of a StableHLO module that
    carry more than one scatter index. The static ``.at[0]`` /
    ``.at[1:]`` / ``.at[:-1]`` updates of ``diff`` and ``is_last``
    lower to scatters of ONE index (a window update, no scatter once
    compiled) and are not listed."""
    import re

    found = []
    for types in re.findall(
            r'"stablehlo\.scatter"\(.*?\n\s*\}\) : \(([^)]*)\) ->', text,
            flags=re.S):
        types = re.findall(r"tensor<([^>]*)>", types)
        index = types[len(types) // 2]  # operands, indices, updates
        dims = [int(d) for d in index.split("x")[:-1]]
        if int(np.prod(dims[:-1])) > 1:
            found.append(index)
    return found


def test_row_indexed_scatter_predicate_tells_the_two_kinds_apart():
    import jax.numpy as jnp

    def body(x, i):
        return (x.at[0].set(7).at[1:].set(x[:-1]),
                jnp.zeros_like(x).at[i].set(x, mode="drop"))

    text = jax.jit(body).lower(
        jax.ShapeDtypeStruct((64,), np.int32),
        jax.ShapeDtypeStruct((64,), np.int32)).as_text()
    assert text.count('"stablehlo.scatter"') == 3
    assert _row_indexed_scatters(text) == ["64x1xi32"]


def _wave_program_texts(ndev, rows):
    """``{program name: lowered text}`` of the map-side, reduce-side
    and filter wave programs of a keyed Reduce + Filter — the pipeline
    of the ``q18agg`` cells — as the executor builds them (``rows`` a
    shard, two waves)."""
    import re

    from jax.sharding import Mesh

    rng = np.random.default_rng(ndev)
    n = 2 * ndev * rows
    # Sparse as dbgen's order keys (the first 8 of every 32), so the
    # keyed combine is the generic one and not the dense table.
    order = rng.integers(0, n // 4, n)
    keys = (((order >> 3) << 5 | (order & 7)) + 1).astype(np.int32)
    sess = Session(executor=MeshExecutor(
        Mesh(np.array(jax.devices()[:ndev]), ("shards",))))
    try:
        agg = sess.run(bs.Reduce(
            bs.Const(2 * ndev, keys, np.ones(n, np.int32)),
            lambda a, b: a + b))
        big = sess.run(bs.Filter(agg, lambda k, total: total > 4))
        assert 0 < len(big.rows()) < n // 4
        with sess.executor._lock:
            programs = [p for p, _ in sess.executor._programs.values()
                        if getattr(p, "_kind", None) == "group"]
        texts = {}
        for prog in programs:
            for sig in prog._compiled:
                # (shape, dtype[, sharding]) an argument.
                text = prog.lower(*[
                    jax.ShapeDtypeStruct(a[0], a[1], sharding=a[2])
                    if len(a) > 2 else jax.ShapeDtypeStruct(*a)
                    for a in sig]).as_text()
                (name,) = set(re.findall(r"bs_group_\w+", text))
                texts[name] = text
    finally:
        sess.shutdown()
    return texts


@pytest.mark.parametrize("ndev,rows", [(1, 1 << 17), (4, 1 << 13)])
def test_wave_programs_hold_no_row_indexed_scatter(ndev, rows):
    """The map-side, reduce-side and filter wave programs of a keyed
    Reduce + Filter, as the executor builds them (``rows`` a shard, two
    waves): compaction and the bucket fill are sorts and slices. A
    scatter of a wave's rows runs row by row on the TPU (PERF.md §5,
    PR 31)."""
    seen = {name: (_row_indexed_scatters(text),
                   text.count("stablehlo.sort"))
            for name, text in _wave_program_texts(ndev, rows).items()}
    # Sorts: the fused (validity, lane, subid, key) sort, the lane
    # grouping and the packing of what arrived; (validity, key) and
    # the packing; the packing.
    assert seen == {"bs_group_shuffle": ([], 3),
                    "bs_group_combine": ([], 2),
                    "bs_group_filter": ([], 1)}


@pytest.mark.parametrize("ndev,rows", [(1, 4096), (8, 512)])
def test_wave_programs_of_32_bit_columns_hold_no_64_bit_type(ndev, rows):
    """The same three programs hold no 64-bit tensor: 64-bit integer
    columns reach XLA through JAX's 64-bit mode scoped to the programs
    that carry one (jitutil.ScopedJit), and a pipeline of 32-bit
    columns — both ``q18agg`` cells — must not be widened by it, now
    or by accident later (the TPU emulates 64-bit integers)."""
    import re

    texts = _wave_program_texts(ndev, rows)
    assert set(texts) == {"bs_group_shuffle", "bs_group_combine",
                          "bs_group_filter"}
    for name, text in texts.items():
        # Element types of the tensors a program computes on: MLIR
        # spells its own attributes (dimensions, paddings, replica
        # groups) in i64 whatever the program computes in.
        text = re.sub(r"dense<[^>]*> : tensor<[^>]*>", "", text)
        wide = re.findall(
            r"tensor<(?:[0-9?]+x)*(?:[su]?i64|f64)>", text)
        assert not wide, f"{name}: {sorted(set(wide))}"


# ------------------------------------------------ the signal vector
#
# A wave's four signals and its output's row count come home as ONE
# replicated int32[5] whose host copy its dispatch started; a settle
# reads it once (_read_signals) and acts on it before the wave's output
# is delivered. On the CPU the
# pipelined loop's in-flight window is 0, so these tests give
# _execute_waves_pipelined another backend name to find: wave w-1 is
# then settled after wave w's dispatch, as on a TPU.

def _mesh_of(ndev):
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:ndev]), ("shards",))


def _loop_executor(monkeypatch, ndev, loop, **kw):
    """A MeshExecutor running its waved groups on the ``serial`` loop
    (prefetch_depth 0) or the ``pipelined`` one with a wave in flight,
    and the list its dispatches and signal reads are logged to:
    ``("dispatch", wave, attempt)`` / ``("read", (overflow, badrange,
    gbover, hashov, rows_max))``."""
    if loop == "pipelined":
        monkeypatch.setattr(jax, "default_backend", lambda: "in-flight")
    ex = MeshExecutor(_mesh_of(ndev),
                      prefetch_depth=0 if loop == "serial" else 1, **kw)
    log = []
    dispatch, read = ex._dispatch_wave_on, ex._read_signals

    def logged_dispatch(tasks, wave, inputs, attempt=0):
        log.append(("dispatch", wave, attempt))
        return dispatch(tasks, wave, inputs, attempt)

    def logged_read(signals):
        got = read(signals)
        log.append(("read", got))
        return got

    ex._dispatch_wave_on, ex._read_signals = logged_dispatch, logged_read
    return ex, log


def _in_flight_settles(log):
    """Reads that came after a LATER wave's first dispatch: the i-th
    dispatched attempt is the i-th read, so a read preceded by more
    dispatches than reads settles a wave with another in flight."""
    ahead, dispatched, read = 0, 0, 0
    for e in log:
        if e[0] == "dispatch":
            dispatched += 1
        else:
            read += 1
            ahead += dispatched > read
    return ahead


def _sum_oracle(keys, vals):
    uniq, inv = np.unique(keys, return_inverse=True)
    return dict(zip(uniq.tolist(),
                    np.bincount(inv, weights=vals).astype(int).tolist()))


def _case_bucket_overflow(sess, ndev):
    """All-distinct keys: at slack 1.0 the fullest bucket of every wave
    lacks rows, the slack ladder retries it."""
    n = 3 * ndev * 512
    keys = np.random.default_rng(ndev).permutation(n).astype(np.int32)
    res = sess.run(bs.Reduce(bs.Const(3 * ndev, keys,
                                      np.ones(n, np.int32)),
                             lambda a, b: a + b))
    assert dict(res.rows()) == {k: 1 for k in range(n)}
    memo = sess.executor._slack_memo
    assert len(memo) == 1 and max(memo.values()) > 1.0
    return "overflow"


def _case_cogroup_deficit(sess, ndev):
    """A group of 300 rows against a starting capacity of 8: the
    deficit rides ``overflow`` into the capacity retry."""
    rng = np.random.default_rng(5)
    keys = np.concatenate([np.zeros(300, np.int32),
                           rng.integers(1, 10, 212).astype(np.int32)])
    vals = np.arange(512, dtype=np.int32)
    perm = rng.permutation(512)
    keys, vals = keys[perm], vals[perm]
    got = {int(k): sorted(int(v) for v in g) for k, g in
           sess.run(bs.Cogroup(bs.Const(2 * ndev, keys, vals))).rows()}
    want = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        want.setdefault(k, []).append(v)
    assert got == {k: sorted(v) for k, v in want.items()}
    assert max(sess.executor._cogroup_caps.values()) >= 300
    return "overflow"


def _case_groupby_capacity(sess, ndev):
    """One group of 40 rows a shard against ``capacity=4`` with
    ``on_overflow='error'``: raises, and names the capacity."""
    from bigslice_tpu.exec.task import TaskError

    n = 2 * ndev * 40
    g = bs.GroupByKey(bs.Const(2 * ndev, np.zeros(n, np.int32),
                               np.arange(n, dtype=np.int32)),
                      capacity=4, on_overflow="error")
    with pytest.raises((TaskError, ValueError), match="capacity"):
        sess.run(g).rows()
    return "gbover"


def _case_partition_out_of_range(sess, ndev):
    """A partitioner that returns ``nparts``: a user error, raised as
    the host tier raises it, not chased up the slack ladder."""
    from bigslice_tpu.exec.task import TaskError

    rp = bs.Repartition(
        bs.Const(2 * ndev, np.arange(2 * ndev * 32, dtype=np.int32)),
        lambda k, nparts: (k % nparts) + 1)
    with pytest.raises(TaskError, match="outside"):
        sess.run(rp)
    assert not sess.executor._slack_memo
    return "badrange"


def _case_hash_cascade(sess, ndev):
    """All-distinct keys at load factor 1: the claim cascade cannot
    place them, the op rebuilds on the sort path for good."""
    n = 2 * ndev * (8192 if ndev == 1 else 2048)
    keys = (np.random.default_rng(13).permutation(n).astype(np.int32)
            + (1 << 20))
    res = sess.run(bs.Reduce(bs.Const(2 * ndev, keys,
                                      np.ones(n, np.int32)),
                             lambda a, b: a + b))
    assert dict(res.rows()) == {k: 1 for k in keys.tolist()}
    assert len(sess.executor._hash_off) == 1
    return "hashov"


#: name -> (job, executor options, mesh sizes it can happen on).
SIGNAL_CASES = {
    # A mesh of one exchanges nothing: no bucket to overflow.
    "bucket_overflow": (_case_bucket_overflow, {}, (8,)),
    "cogroup_deficit": (_case_cogroup_deficit, {}, (1, 8)),
    "groupby_capacity": (_case_groupby_capacity, {}, (1, 8)),
    "partition_out_of_range": (_case_partition_out_of_range, {}, (1, 8)),
    "hash_cascade": (_case_hash_cascade,
                     {"auto_dense": False, "hash_aggregate": True},
                     (1, 8)),
}
_SIGNAL_ORDER = ("overflow", "badrange", "gbover", "hashov")


@pytest.mark.parametrize("loop", ["serial", "pipelined"])
@pytest.mark.parametrize("case,ndev", [
    (c, n) for c, (_, _, meshes) in sorted(SIGNAL_CASES.items())
    for n in meshes])
def test_each_signal_raises_or_retries_on_both_loops(monkeypatch, case,
                                                     ndev, loop):
    job, opts, _ = SIGNAL_CASES[case]
    ex, log = _loop_executor(monkeypatch, ndev, loop, **opts)
    sess = Session(executor=ex)
    try:
        signal = job(sess, ndev)
    finally:
        sess.shutdown()
    reads = [e[1] for e in log if e[0] == "read"]
    dispatches = [e for e in log if e[0] == "dispatch"]
    # A wave raises one signal at a time, each in its own slot of the
    # vector, and the one this case is about came home in its own.
    assert all(sum(1 for v in r[:4] if v) <= 1 for r in reads)
    at = _SIGNAL_ORDER.index(signal)
    mine = [r for r in reads if r[at] > 0]
    assert mine
    retries = [d for d in dispatches if d[2] > 0]
    if signal in ("gbover", "badrange"):
        # An error, raised at the first wave that says so: nothing is
        # read after it, and the wave in flight behind it is dropped
        # unread and undelivered.
        assert len(mine) == 1 and reads[-1] == mine[0]
        assert log[-1][0] == "read"
        assert len(dispatches) - len(reads) == (loop == "pipelined")
    else:
        # Every dispatched attempt was read, once; a retry dispatches
        # the SAME wave again right after the read that asked for it.
        assert len(reads) == len(dispatches)
        assert len(retries) >= 1
        for i, e in enumerate(log):
            if e[0] == "dispatch" and e[2] > 0:
                assert log[i - 1][0] == "read" and any(log[i - 1][1])
    assert (_in_flight_settles(log) > 0) == (loop == "pipelined")


@pytest.mark.parametrize("ndev,rows", [(1, 512), (8, 512)])
def test_wave_program_returns_one_replicated_signal_vector(ndev, rows):
    """The lowered wave programs hold ONE int32[5] signal output — not
    five scalars — between the counts and the columns; replicated: five
    elements whatever the mesh, where the counts have one a device."""
    import re

    texts = _wave_program_texts(ndev, rows)
    assert set(texts) == {"bs_group_shuffle", "bs_group_combine",
                          "bs_group_filter"}
    for name, text in texts.items():
        (results,) = re.findall(
            r"func\.func public @main\(.*?\)\s*->\s*\((.*?)\)\s*\{",
            text, re.S)
        types = re.findall(
            r'(tensor<[^>]*>) \{jax\.result_info = "([^"]*)"', results)
        assert types[0] == (f"tensor<{ndev}xi32>", "result[0]"), name
        assert types[1] == ("tensor<5xi32>", "result[1]"), name
        assert all(info.startswith("result[2][") for _, info in types[2:])
        assert not [t for t, _ in types if t == "tensor<i32>"], name


@pytest.mark.parametrize("loop", ["serial", "pipelined"])
def test_a_settle_is_one_host_read(monkeypatch, loop):
    """Every ``settle`` span goes through ``_read_signals`` once, and
    that turns ONE device array into a host array."""
    ex, log = _loop_executor(monkeypatch, 8, loop)
    logged_read = ex._read_signals
    conversions = []

    class Counted:
        def __init__(self, signals):
            self.signals = signals

        def __array__(self, *args, **kwargs):
            conversions.append(self.signals.shape)
            return np.asarray(self.signals)

    ex._read_signals = lambda signals: logged_read(Counted(signals))
    sess = Session(executor=ex)
    try:
        keys = np.tile(np.arange(64, dtype=np.int32), 24 * 4)
        res = sess.run(bs.Reduce(bs.Const(24, keys, np.ones_like(keys)),
                                 lambda a, b: a + b))
        assert dict(res.rows()) == {k: 96 for k in range(64)}
        spans = sess.telemetry_summary()["spans"]
    finally:
        sess.shutdown()
    waves = 2 * 3                         # map side + reduce side
    assert spans["settle"]["count"] == spans["dispatch"]["count"] == waves
    assert conversions == [(5,)] * waves
    assert len([e for e in log if e[0] == "read"]) == waves


def _job_keyed_shuffle(sess, ndev):
    """A keyed Reduce: a shuffle program, then a reduce-side combine."""
    n = 2 * ndev * 300
    keys = np.random.default_rng(2).integers(0, 500, n).astype(np.int32)
    res = sess.run(bs.Reduce(bs.Const(2 * ndev, keys, np.ones_like(keys)),
                             lambda a, b: a + b))
    assert sum(v for _, v in res.rows()) == n
    return "bs_group_shuffle"


def _job_map_only(sess, ndev):
    """Two uploaded columns through one Map: the counts pass through,
    and some shards of a wave hold a row fewer than the others."""
    n = 2 * ndev * 100 - 37
    x = np.arange(n, dtype=np.int32)
    res = sess.run(bs.Map(bs.Const(2 * ndev, x, x), lambda a, b: (a, a + b)))
    assert sorted(res.rows()) == [(i, 2 * i) for i in range(n)]
    return "bs_group_map"


def _job_lookup_join(sess, ndev):
    pk = np.random.default_rng(4).integers(0, 64, 2 * ndev * 90)
    bk = np.arange(0, 64, 2)
    j = bs.JoinLookup(bs.Const(2 * ndev, pk.astype(np.int32),
                               np.ones(len(pk), np.int32)),
                      bs.Const(ndev, bk.astype(np.int32),
                               bk.astype(np.int32)))
    assert len(sess.run(j).rows()) == int(np.isin(pk, bk).sum())
    return "bs_group_joinlookup"


def _job_retried_shuffle(sess, ndev):
    assert _case_bucket_overflow(sess, ndev) == "overflow"
    return "bs_group_shuffle"


@pytest.mark.parametrize("job,ndev", [
    (_job_keyed_shuffle, 8), (_job_map_only, 8), (_job_lookup_join, 8),
    (_job_retried_shuffle, 8), (_job_keyed_shuffle, 1)])
def test_fifth_signal_is_the_fullest_devices_output_rows(job, ndev):
    """Every dispatched attempt of every wave program — a shuffle, a
    counts-pass-through Map, a lookup join (whose four ride behind), a
    wave retried up the slack ladder — returns ``int32[5]`` (``[9]``)
    whose element 4 is ``max(out_counts)``, and the attempt that stands
    leaves it on its output as ``rows_max``."""
    ex = MeshExecutor(_mesh_of(ndev))
    dispatch, settle = ex._dispatch_wave_on, ex._execute_wave_on_locked
    seen, settled = [], []

    def logged_dispatch(tasks, wave, inputs, attempt=0):
        out = dispatch(tasks, wave, inputs, attempt)
        (counts, signals, _), stages, _ = out
        seen.append((_program_name_of(stages), attempt, signals.shape,
                     int(np.asarray(signals)[4]), np.asarray(counts)))
        return out

    def logged_settle(*args):
        out = settle(*args)
        settled.append((out.rows_max, int(np.asarray(out.counts).max())))
        return out

    ex._dispatch_wave_on = logged_dispatch
    ex._execute_wave_on_locked = logged_settle
    sess = Session(executor=ex)
    try:
        program = job(sess, ndev)
    finally:
        sess.shutdown()
    assert any(name.startswith(program) for name, *_ in seen)
    for name, _, shape, rows, counts in seen:
        assert shape == ((9,) if "joinlookup" in name else (5,)), name
        assert rows == counts.max(), name
    assert any(attempt for _, attempt, *_ in seen) == (
        job is _job_retried_shuffle)
    assert settled and all(known == n for known, n in settled)
    # The fullest device is not every device: a maximum, not a copy.
    assert ndev == 1 or any(0 < counts.min() < counts.max()
                            for *_, counts in seen)


def _program_name_of(stages):
    from bigslice_tpu.exec.meshexec import _program_name

    return _program_name("group", tuple(k for k, _, _ in stages))


@pytest.mark.parametrize("ndev", [4, 8])
def test_overflow_settled_after_the_next_dispatch_reruns_alone(
        monkeypatch, ndev):
    """Wave 0 overflows a bucket and is settled AFTER wave 1 was
    dispatched (at slack 1.0): wave 0 alone is dispatched again, on the
    rung its signal sized; wave 1, which fits, is not; its settle does
    not lower the op's memoised slack, and wave 2 is dispatched on it."""
    rows = 512
    rng = np.random.default_rng(33 + ndev)
    n0 = ndev * rows
    # Wave 0: all-distinct keys. Waves 1, 2: 16 keys, on every shard.
    keys = np.concatenate([
        rng.permutation(n0).astype(np.int32) + 1000,
        np.tile(np.arange(16, dtype=np.int32), 2 * n0 // 16)])
    vals = rng.integers(1, 9, len(keys)).astype(np.int32)

    def run(loop):
        ex, log = _loop_executor(monkeypatch, ndev, loop)
        slacks = []
        program = ex._program

        def logged_program(task, caps, slack, **kw):
            slacks.append(slack)
            return program(task, caps, slack, **kw)

        ex._program = logged_program
        sess = Session(executor=ex)
        try:
            res = sess.run(bs.Reduce(bs.Const(3 * ndev, keys, vals),
                                     lambda a, b: a + b))
            rows_out = dict(res.rows())
            blocks = [rec["exchange"] for rec in
                      sess.telemetry_summary()["ops"].values()
                      if "exchange" in rec]
            memo = dict(ex._slack_memo)
        finally:
            sess.shutdown()
            monkeypatch.undo()
        return rows_out, blocks, memo, log, slacks

    got, (block,), memo, log, slacks = run("pipelined")
    assert got == _sum_oracle(keys, vals)
    assert got == run("serial")[0]
    map_side = log[:log.index(("dispatch", 0, 0), 1)]
    rung = block["slack"]
    assert block["retries"] == 1 and rung > 1.0
    assert list(memo.values()) == [rung]
    kinds = [e if e[0] == "dispatch" else ("read", e[1][0] > 0)
             for e in map_side]
    assert kinds == [
        ("dispatch", 0, 0), ("dispatch", 1, 0),
        ("read", True),                   # wave 0, wave 1 in flight
        ("dispatch", 0, 1), ("read", False),
        ("dispatch", 2, 0), ("read", False),    # wave 1, at slack 1.0
        ("read", False)]
    assert slacks[:4] == [1.0, 1.0, rung, rung]


# -- the prefetch workers (exec/wavestage.py): a group that uploads its
# waves keeps two stages in flight and hands them over in wave order.

STAGED_WAVES = 8        # Const(64, ...) on the 8-device mesh


class _StageLog:
    """``ex._stage``, ``ex._dispatch_wave`` and the loop's asks for a
    staged wave, logged in the order they happen. A group is known by
    whether its waves are uploaded (``up``: a Const's map side) or are
    views of a device-resident output (the reduce side). ``delay(up,
    wave)`` seconds pass before a stage, ``loop_delay`` before a
    dispatch, and the stage at ``fail_at = (up, wave)`` raises."""

    def __init__(self, monkeypatch, ex, delay=None, loop_delay=0.0,
                 fail_at=None):
        import threading
        import time

        from bigslice_tpu.exec import wavestage

        self.events = []
        lock = threading.Lock()
        real_stage, real_dispatch = ex._stage, ex._dispatch_wave
        real_take = wavestage.WaveStagers.take

        def log(*event):
            with lock:
                self.events.append(event)

        def stage(tasks, wave, cause=None, before=None):
            up = not tasks[0].deps
            log("begin", up, wave, threading.current_thread().name)
            try:
                if delay is not None:
                    time.sleep(delay(up, wave))
                if fail_at == (up, wave):
                    raise RuntimeError("stage failed")
                return real_stage(tasks, wave, cause=cause, before=before)
            finally:
                log("end", up, wave, threading.current_thread().name)

        def dispatch(tasks, wave, inputs):
            log("dispatch", not tasks[0].deps, wave, None)
            time.sleep(loop_delay)
            return real_dispatch(tasks, wave, inputs)

        def take(stagers, wave):
            log("ask", None, wave, None)        # BEFORE the take
            return real_take(stagers, wave)

        monkeypatch.setattr(ex, "_stage", stage)
        monkeypatch.setattr(ex, "_dispatch_wave", dispatch)
        monkeypatch.setattr(wavestage.WaveStagers, "take", take)

    def of(self, what, up):
        return [(wave, who) for kind, group, wave, who in self.events
                if kind == what and group is up]

    def workers(self, up):
        return {who for wave, who in self.of("begin", up) if wave}


def _staged_job(sess, seed=0):
    rng = np.random.RandomState(seed)
    n = 8 * STAGED_WAVES
    keys = rng.randint(0, 97, n * 16).astype(np.int32)
    vals = rng.randint(1, 9, n * 16).astype(np.int32)
    res = sess.run(bs.Reduce(bs.Const(n, keys, vals), lambda a, b: a + b))
    return sorted(res.rows())


def _prefetch_threads():
    import threading

    return [t.name for t in threading.enumerate()
            if t.name.startswith("meshwave-prefetch")]


def _last_waves(sess, prefix):
    """The ``waves`` block of the session's last pipelined op that
    ``prefix`` names (a job's ops are its own)."""
    return [op["waves"]
            for name, op in sess.telemetry_summary()["ops"].items()
            if name.startswith(prefix)
            and "stage_waits" in op.get("waves", {})][-1]


def _settled_sess(mesh, depth):
    """A session that ran the job once: programs compiled, capacities
    discovered, so the next run stages every wave of a group once."""
    sess = _sess(mesh, depth)
    return sess, _staged_job(sess)


def test_a_wave_staged_before_its_predecessor_is_delivered_after_it(
        mesh, monkeypatch):
    """Wave 1 stages 400 ms slower than the others, so with two
    workers wave 2 is staged before it — and is still dispatched
    after it; the rows are the serial loop's."""
    want = _staged_job(_sess(mesh, 0))
    sess, rows = _settled_sess(mesh, 1)
    assert rows == want
    log = _StageLog(monkeypatch, sess.executor,
                    delay=lambda up, wave: 0.4 * (up and wave == 1))
    assert _staged_job(sess) == want
    ended = [w for w, _ in log.of("end", True)]
    assert ended.index(2) < ended.index(1)          # out of order
    for up in (True, False):
        assert [w for w, _ in log.of("dispatch", up)] == \
            list(range(STAGED_WAVES))
    assert _prefetch_threads() == []


@pytest.mark.parametrize("k", [1, 4, STAGED_WAVES - 1])
def test_a_stage_error_is_raised_in_wave_order_and_stops_the_workers(
        mesh, monkeypatch, k):
    """The stage of wave k raises while wave k-1's is still under way:
    every wave before k is dispatched first, no wave beyond k+1 is
    begun, and both workers have exited when the group has raised."""
    from bigslice_tpu.exec.task import TaskError

    sess, _ = _settled_sess(mesh, 1)
    log = _StageLog(monkeypatch, sess.executor, fail_at=(True, k),
                    delay=lambda up, wave: 0.1 * (up and wave == k - 1))
    with pytest.raises(TaskError, match="stage failed"):
        _staged_job(sess)
    assert [w for w, _ in log.of("dispatch", True)] == list(range(k))
    assert max(w for w, _ in log.of("begin", True)) <= k + 1
    assert log.of("dispatch", False) == []
    assert _prefetch_threads() == []


@pytest.mark.parametrize("depth", [1, 2])
def test_never_more_than_depth_plus_one_waves_begun_and_not_taken(
        mesh, monkeypatch, depth):
    """A loop 50 ms a wave behind workers that stage at once: they run
    ahead as far as the bound lets them and no further, in both kinds
    of group, and wait for the loop meanwhile."""
    sess, _ = _settled_sess(mesh, depth)
    log = _StageLog(monkeypatch, sess.executor, loop_delay=0.05)
    _staged_job(sess)
    # An ask is logged BEFORE its take: at every begin the loop has
    # taken at most the wave it last asked this group's stagers for.
    asked = {True: 0, False: 0}
    ahead = {True: 0, False: 0}
    up = True                       # the map side runs first
    for kind, which, wave, _who in log.events:
        if kind == "dispatch" and wave == 0:
            up = which
        elif kind == "ask":
            asked[up] = wave
        elif kind == "begin" and wave:
            ahead[which] = max(ahead[which], wave - asked[which])
    # On a machine that is not stalled both reach the bound exactly.
    assert 0 < ahead[True] <= depth + 1
    assert 0 < ahead[False] <= depth + 1
    for prefix in ("const@", "reduce@"):
        assert _last_waves(sess, prefix)["prefetch_blocked_s"] > 0
    assert _prefetch_threads() == []


def test_a_group_of_views_keeps_one_worker_and_an_uploading_group_two(
        mesh, monkeypatch):
    sess, _ = _settled_sess(mesh, 1)
    log = _StageLog(monkeypatch, sess.executor,
                    delay=lambda up, wave: 0.05)
    _staged_job(sess)
    const, reduce_ = (_last_waves(sess, p) for p in ("const@", "reduce@"))
    assert log.workers(False) == {"meshwave-prefetch-0"}
    assert log.workers(True) == {"meshwave-prefetch-0",
                                 "meshwave-prefetch-1"}
    assert reduce_["stages_overlapped"] == 0
    assert 0 < const["stages_overlapped"] <= const["stage_waits"]
    assert const["stage_waits"] == reduce_["stage_waits"] == \
        STAGED_WAVES - 1
    assert _prefetch_threads() == []


def test_two_workers_are_ready_where_one_is_always_waited_for(
        mesh, monkeypatch):
    """A stage of 500 ms beside a loop of 250 ms a wave and whatever a
    loaded machine adds: one worker is the slower side and its waves
    are not ready (a wave or two may be, where the machine stalled the
    loop for 250 ms more); two stage a wave every 250 ms, the loop is
    the slower side, and but for the first pair's every wave is staged
    when the loop asks."""
    from bigslice_tpu.exec import wavestage

    def counted(workers):
        monkeypatch.setattr(wavestage, "STAGE_WORKERS", workers)
        sess, _ = _settled_sess(mesh, 1)
        with monkeypatch.context() as patch:
            _StageLog(patch, sess.executor, loop_delay=0.25,
                      delay=lambda up, wave: 0.5 * up)
            _staged_job(sess)
        got = _last_waves(sess, "const@")
        assert got["stage_waits"] == STAGED_WAVES - 1
        return got["stage_waits_ready"], got["stages_overlapped"]

    one, overlapped = counted(1)
    assert one <= 2 and overlapped == 0
    two, overlapped = counted(2)
    assert two >= STAGED_WAVES - 3 and overlapped > 0


# -- WaveStagers alone: no executor, a stage is a sleep.

def _stagers(nwaves, depth, uploads, stage):
    from bigslice_tpu.exec import wavestage

    return wavestage.WaveStagers(nwaves, depth, uploads, stage)


@pytest.mark.parametrize("uploads", [False, True])
@pytest.mark.parametrize("depth", [1, 3])
def test_stagers_hand_every_wave_over_once_in_wave_order(depth, uploads):
    import threading
    import time

    second = threading.Event()

    def stage(w):
        if w == 2:
            second.set()
        if w == 1 and uploads:
            second.wait(10)               # two workers: 2 begins beside 1
        time.sleep(0.002 * (w % 3))       # later waves finish first
        return w, 0.0, {}

    stagers = _stagers(12, depth, uploads, stage)
    try:
        got = [stagers.take(w) for w in range(1, 12)]
    finally:
        stagers.close()
    assert got == [(w, None, 0.0, {}) for w in range(1, 12)]
    assert (stagers.overlapped > 0) == uploads
    assert _prefetch_threads() == []


@pytest.mark.parametrize("uploads", [False, True])
def test_stagers_closed_while_they_wait_for_the_loop_exit(uploads):
    import time

    begun = []
    stagers = _stagers(9, 1, uploads, lambda w: (begun.append(w), 0.0, {}))
    deadline = time.time() + 5
    while len(begun) < 2 and time.time() < deadline:
        time.sleep(0.001)
    time.sleep(0.05)                      # the workers wait at the bound
    stagers.close()
    assert sorted(begun) == [1, 2]        # depth + 1, never taken
    assert stagers.blocked_ns >= 0.02e9
    assert _prefetch_threads() == []


def test_stagers_begin_no_wave_after_one_that_raised():
    import time

    begun = []

    def stage(w):
        begun.append(w)
        if w == 2:
            raise ValueError("wave 2")
        time.sleep(0.05)                  # wave 1 outlasts wave 2
        return w, 0.0, {}

    stagers = _stagers(9, 3, True, stage)
    try:
        assert stagers.take(1)[:2] == (1, None)
        inputs, err, _dur, _stats = stagers.take(2)
    finally:
        stagers.close()
    assert inputs is None and isinstance(err, ValueError)
    assert sorted(begun) == [1, 2]
    assert _prefetch_threads() == []


def test_many_stagers_under_a_short_switch_interval_keep_order_and_bound(
        monkeypatch):
    """Sixteen workers, more than this machine's cores, switching every
    microsecond: each of 400 waves is handed over once, in order, and
    no stage begins more than ``depth + 1`` waves ahead of what the
    loop has asked for (an ask is counted BEFORE its take)."""
    import sys
    import threading
    import time

    from bigslice_tpu.exec import wavestage

    monkeypatch.setattr(wavestage, "STAGE_WORKERS", 16)
    depth, nwaves = 15, 400
    asked = [0]
    ahead, begun, lock = [], [], threading.Lock()

    def stage(w):
        with lock:
            begun.append(w)
            ahead.append(w - asked[0])
        return w, 0.0, None

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    deadline = time.time() + 60
    try:
        stagers = _stagers(nwaves, depth, True, stage)
        try:
            got = []
            for w in range(1, nwaves):
                assert time.time() < deadline
                asked[0] = w
                got.append(stagers.take(w)[0])
        finally:
            stagers.close()
    finally:
        sys.setswitchinterval(interval)
    assert got == list(range(1, nwaves))
    assert sorted(begun) == got
    assert max(ahead) <= depth + 1
    assert _prefetch_threads() == []
