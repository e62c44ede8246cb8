"""SPMD shuffle + mesh reduce tests on the 8-device virtual CPU mesh.

The hermetic multi-"chip" validation strategy (SURVEY.md §4 takeaway):
the full collective path — hash bucket, all_to_all, counts exchange,
compaction, segmented combines — runs in-process on virtual devices.
"""

import numpy as np
import pytest

import jax

from bigslice_tpu.frame import ops as frame_ops
from bigslice_tpu.parallel import shuffle as shuffle_mod


@pytest.fixture(scope="module")
def mesh():
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:8])
    return Mesh(devs, ("shards",))


def make_sharded(mesh, rng, total, cap, nkeys=1, nvals=1, key_range=100):
    n = mesh.devices.size
    per = total // n
    key_chunks = [[rng.randint(0, key_range, per).astype(np.int32)
                   for _ in range(n)] for _ in range(nkeys)]
    val_chunks = [[rng.randint(0, 10, per).astype(np.int32)
                   for _ in range(n)] for _ in range(nvals)]
    cols, counts = shuffle_mod.shard_columns(
        mesh, key_chunks + val_chunks, [per] * n, cap
    )
    return key_chunks, val_chunks, cols, counts


def test_mesh_shuffle_routes_by_hash(mesh):
    rng = np.random.RandomState(0)
    n = mesh.devices.size
    cap = 256
    key_chunks, val_chunks, cols, counts = make_sharded(
        mesh, rng, total=8 * 100, cap=cap
    )
    sh = shuffle_mod.MeshShuffle(mesh, ncols=2, nkeys=1, capacity=cap)
    out_cols, out_counts, overflow = sh(cols, counts)
    assert int(overflow) == 0
    chunks = shuffle_mod.unshard_columns(out_cols, out_counts,
                                         sh.out_capacity)

    # Oracle: every input row must appear on the shard its key hashes to.
    all_in = sorted(
        zip(np.concatenate(key_chunks[0]).tolist(),
            np.concatenate(val_chunks[0]).tolist())
    )
    all_out = sorted(
        zip(np.concatenate(chunks[0]).tolist(),
            np.concatenate(chunks[1]).tolist())
    )
    assert all_in == all_out  # no loss, no dup
    for s in range(n):
        keys = chunks[0][s]
        if not len(keys):
            continue
        h = frame_ops.hash_device_column(np.asarray(keys), 0)
        np.testing.assert_array_equal(
            (h % np.uint32(n)).astype(np.int32), np.full(len(keys), s)
        )


def test_mesh_shuffle_overflow_detected(mesh):
    # All rows share one key → everything routes to one shard; with
    # capacity < total rows the overflow must be reported, not silent.
    n = mesh.devices.size
    cap = 16
    per = 16
    key_chunks = [[np.full(per, 7, np.int32) for _ in range(n)]]
    val_chunks = [[np.arange(per, dtype=np.int32) for _ in range(n)]]
    cols, counts = shuffle_mod.shard_columns(
        mesh, key_chunks + val_chunks, [per] * n, cap
    )
    sh = shuffle_mod.MeshShuffle(mesh, ncols=2, nkeys=1, capacity=cap)
    _, _, overflow = sh(cols, counts)
    assert int(overflow) > 0


def test_mesh_reduce_by_key_matches_oracle(mesh):
    rng = np.random.RandomState(1)
    cap = 512
    key_chunks, val_chunks, cols, counts = make_sharded(
        mesh, rng, total=8 * 200, cap=cap, key_range=37
    )
    red = shuffle_mod.MeshReduceByKey(
        mesh, nkeys=1, nvals=1, capacity=cap,
        combine_fn=lambda a, b: a + b,
    )
    k_out, v_out, out_counts, overflow = red(
        [cols[0]], [cols[1]], counts
    )
    assert int(overflow) == 0
    chunks = shuffle_mod.unshard_columns(k_out + v_out, out_counts,
                                         red.out_capacity)
    got = {}
    for s in range(mesh.devices.size):
        for k, v in zip(chunks[0][s].tolist(), chunks[1][s].tolist()):
            assert k not in got, f"key {k} on two shards"
            got[k] = v
    oracle = {}
    for k, v in zip(np.concatenate(key_chunks[0]).tolist(),
                    np.concatenate(val_chunks[0]).tolist()):
        oracle[k] = oracle.get(k, 0) + v
    assert got == oracle


def test_mesh_reduce_multikey_multival(mesh):
    import jax.numpy as jnp

    rng = np.random.RandomState(2)
    n = mesh.devices.size
    cap = 256
    per = 64
    k1 = [rng.randint(0, 5, per).astype(np.int32) for _ in range(n)]
    k2 = [rng.randint(0, 5, per).astype(np.int32) for _ in range(n)]
    v1 = [rng.randint(0, 100, per).astype(np.int32) for _ in range(n)]
    v2 = [rng.rand(per).astype(np.float32) for _ in range(n)]
    cols, counts = shuffle_mod.shard_columns(
        mesh, [k1, k2, v1, v2], [per] * n, cap
    )

    def fn(a, b):
        return (a[0] + b[0], jnp.maximum(a[1], b[1]))

    red = shuffle_mod.MeshReduceByKey(mesh, nkeys=2, nvals=2,
                                      capacity=cap, combine_fn=fn)
    k_out, v_out, out_counts, overflow = red(cols[:2], cols[2:], counts)
    assert int(overflow) == 0
    chunks = shuffle_mod.unshard_columns(k_out + v_out, out_counts,
                                         red.out_capacity)
    got = {}
    for s in range(n):
        for a, b, x, y in zip(*(c[s].tolist() for c in chunks)):
            got[(a, b)] = (x, y)
    oracle = {}
    for a, b, x, y in zip(
        np.concatenate(k1).tolist(), np.concatenate(k2).tolist(),
        np.concatenate(v1).tolist(), np.concatenate(v2).tolist(),
    ):
        cur = oracle.get((a, b))
        oracle[(a, b)] = (
            (cur[0] + x, max(cur[1], y)) if cur else (x, y)
        )
    assert set(got) == set(oracle)
    for k in got:
        assert got[k][0] == oracle[k][0]
        assert abs(got[k][1] - oracle[k][1]) < 1e-6


def test_mesh_shuffle_custom_partitioner(mesh):
    n = mesh.devices.size
    cap = 128
    per = 32
    keys = [np.arange(per, dtype=np.int32) + s * per for s in range(n)]
    cols, counts = shuffle_mod.shard_columns(mesh, [keys], [per] * n, cap)
    sh = shuffle_mod.MeshShuffle(
        mesh, ncols=1, nkeys=1, capacity=cap,
        partition_fn=lambda k: k % 2,  # everything to shards 0/1
    )
    out_cols, out_counts, overflow = sh(cols, counts)
    assert int(overflow) == 0
    counts_host = np.asarray(out_counts)
    assert counts_host[0] + counts_host[1] == n * per
    assert all(c == 0 for c in counts_host[2:])


def test_empty_shards(mesh):
    n = mesh.devices.size
    cap = 64
    keys = [np.zeros(0, np.int32) for _ in range(n)]
    vals = [np.zeros(0, np.int32) for _ in range(n)]
    cols, counts = shuffle_mod.shard_columns(mesh, [keys, vals],
                                             [0] * n, cap)
    red = shuffle_mod.MeshReduceByKey(mesh, nkeys=1, nvals=1, capacity=cap,
                                      combine_fn=lambda a, b: a + b)
    _, _, out_counts, overflow = red([cols[0]], [cols[1]], counts)
    assert int(np.asarray(out_counts).sum()) == 0
    assert int(overflow) == 0


def test_mesh_shuffle_pallas_hash_path(mesh):
    """The Pallas hash path (interpret mode here, Mosaic on TPU) routes
    identically to the XLA hash path."""
    rng = np.random.RandomState(3)
    n = mesh.devices.size
    cap = 128
    per = 64
    kc = [rng.randint(-1000, 1000, per).astype(np.int32)
          for _ in range(n)]
    vc = [np.arange(per, dtype=np.int32) for _ in range(n)]
    cols, counts = shuffle_mod.shard_columns(mesh, [kc, vc], [per] * n, cap)

    import jax
    from jax.sharding import PartitionSpec as P

    from bigslice_tpu.parallel.meshutil import get_shard_map

    outs = {}
    for use_pallas in (False, True):
        body = shuffle_mod.make_shuffle_fn(
            n, 1, cap, "shards", use_pallas=use_pallas
        )

        def stepped(cnt, k, v):
            c, ov, out = body(cnt[0], k, v)
            return c.reshape(1), tuple(out)

        f = jax.jit(get_shard_map()(
            stepped, mesh=mesh,
            in_specs=(P("shards"), P("shards"), P("shards")),
            out_specs=(P("shards"), (P("shards"), P("shards"))),
            check_rep=False,
        ))
        oc, (ok, ov) = f(counts, cols[0], cols[1])
        outs[use_pallas] = (np.asarray(oc), np.asarray(ok),
                            np.asarray(ov))
    np.testing.assert_array_equal(outs[False][0], outs[True][0])
    np.testing.assert_array_equal(outs[False][1], outs[True][1])
    np.testing.assert_array_equal(outs[False][2], outs[True][2])


@pytest.mark.parametrize("nparts_mult", [1, 3])
def test_mesh_shuffle_routing_matches_numpy(mesh, nparts_mult):
    """The routing sort delivers to every partition exactly the rows a
    numpy ``hash % nparts`` sends there, flat and waved (where partition
    p arrives on device ``p % n`` under subid ``p // n``)."""
    from jax.sharding import PartitionSpec as P

    from bigslice_tpu.parallel.meshutil import get_shard_map

    rng = np.random.RandomState(4)
    n = mesh.devices.size
    cap = 256
    per = 96
    nparts = n * nparts_mult
    waved = nparts > n
    kc = [rng.randint(0, 500, per).astype(np.int32) for _ in range(n)]
    vc = [rng.randint(0, 100, per).astype(np.int32) for _ in range(n)]
    cols, counts = shuffle_mod.shard_columns(mesh, [kc, vc], [per] * n, cap)

    body = shuffle_mod.make_shuffle_fn(n, 1, cap, "shards", nparts=nparts)

    def stepped(cnt, k, v):
        c, ov, out = body(cnt[0], k, v)
        return c.reshape(1), ov, tuple(out)

    f = jax.jit(get_shard_map()(
        stepped, mesh=mesh,
        in_specs=(P("shards"), P("shards"), P("shards")),
        out_specs=(P("shards"), P(),
                   tuple(P("shards") for _ in range(2 + waved))),
        check_rep=False,
    ))
    oc, ov, out = f(counts, cols[0], cols[1])
    assert int(ov) == 0
    out_cap = np.asarray(out[0]).shape[0] // n
    chunks = shuffle_mod.unshard_columns(list(out), oc, out_cap)
    got = {}
    for dev in range(n):
        subid = chunks[0][dev] if waved else np.zeros(
            len(chunks[0][dev]), np.int32)
        keys, vals = chunks[waved][dev], chunks[waved + 1][dev]
        for p in np.unique(subid * n + dev):
            sel = subid * n + dev == p
            got[int(p)] = sorted(zip(keys[sel].tolist(),
                                     vals[sel].tolist()))
    keys, vals = np.concatenate(kc), np.concatenate(vc)
    part = frame_ops.hash_host_column(keys, 0) % np.uint32(nparts)
    want = {int(p): sorted(zip(keys[part == p].tolist(),
                               vals[part == p].tolist()))
            for p in np.unique(part)}
    assert got == want


# ------------------------------------------------------- the bucket cut

CUT_DEVICES = 4
CUT_ROWS = 64
CUT_SLACK = 2.0  # send_cap = 64 * 2 / 4 = 32 rows a (source, lane)


def _cut_rows(scenario, nparts, rng):
    """(keys, vals, valid) a source device: routing is ``key % nparts``
    (lane ``key % 4``), so the keys choose the lanes. Invalid rows hold
    garbage."""
    n, rows = CUT_DEVICES, CUT_ROWS
    keys, vals, valid = [], [], []
    for dev in range(n):
        k = rng.integers(0, 40 * nparts, rows)
        ok = rng.random(rows) < 0.8
        if scenario == "empty_lane":
            k = k - k % n + rng.choice([0, 1, 3], rows)  # none to lane 2
        elif scenario == "over_send_cap" and dev == 0:
            # 48 distinct keys of lane 1, every one valid: 16 over
            # at least.
            k[:48] = 1 + n * rng.permutation(16 * nparts)[:48]
            ok[:48] = True
        elif scenario == "all_invalid":
            ok[:] = False
        keys.append(k.astype(np.int32))
        vals.append(rng.integers(1, 100, rows).astype(np.int32))
        valid.append(ok)
    return keys, vals, valid


def _cut_reference(kind, keys, vals, valid, nparts, send_cap):
    """What each device receives: recv[d][s] = the (subid, key, val)
    rows source s sends lane d in bucket order, clipped to the lane's
    first ``send_cap``; and the summed excess of every source's fullest
    lane."""
    n = CUT_DEVICES
    recv = [[None] * n for _ in range(n)]
    overflow = 0
    for s in range(n):
        k, v = keys[s][valid[s]], vals[s][valid[s]]
        if kind == "fused":  # combined: one row a key, its sum
            k, inv = np.unique(k, return_inverse=True)
            v = np.bincount(inv, weights=v, minlength=len(k)).astype(
                np.int32)
        part = k % nparts
        lane, subid = part % n, part // n
        order = (np.lexsort((k, subid, lane)) if kind == "fused"
                 else np.argsort(lane, kind="stable"))
        worst = 0
        for d in range(n):
            sel = order[lane[order] == d]
            worst = max(worst, len(sel) - send_cap)
            sel = sel[:send_cap]
            recv[d][s] = (subid[sel], k[sel], v[sel])
        overflow += worst
    return recv, overflow


@pytest.mark.parametrize("scenario", ["spread", "empty_lane",
                                      "over_send_cap", "all_invalid"])
@pytest.mark.parametrize("waved", [False, True])
@pytest.mark.parametrize("kind", ["plain", "fused"])
def test_bucket_cut_matches_numpy(kind, waved, scenario):
    """The send buckets are slices of the lane-grouped rows: every
    (source, lane) bucket holds the lane's rows in sorted order, its
    first ``send_cap`` when the lane holds more, and ``overflow`` reads
    the excess — for the combinerless and the fused body alike."""
    from jax.sharding import Mesh, PartitionSpec as P

    from bigslice_tpu.parallel import segment
    from bigslice_tpu.parallel.meshutil import get_shard_map

    n = CUT_DEVICES
    nparts = n * 3 if waved else n
    send_cap = shuffle_mod.send_capacity(CUT_ROWS, n, CUT_SLACK)
    keys, vals, valid = _cut_rows(
        scenario, nparts, np.random.default_rng(7 + 2 * waved))
    pfn = lambda k: k % np.int32(nparts)  # noqa: E731
    if kind == "fused":
        body = shuffle_mod.make_combine_shuffle_fn(
            n, 1, 1, segment.canonical_combine(lambda a, b: a + b, 1),
            "shards", partition_fn=pfn, slack=CUT_SLACK, nparts=nparts)
    else:
        body = shuffle_mod.make_shuffle_fn(
            n, 1, CUT_ROWS, "shards", partition_fn=pfn,
            slack=CUT_SLACK, nparts=nparts)

    def stepped(ok, k, v):
        mask, ov, bad, cols = body.masked(ok, k, v)
        return mask, ov, bad, tuple(cols)

    mesh = Mesh(np.array(jax.devices()[:n]), ("shards",))
    row = P("shards")
    mask, ov, bad, cols = jax.jit(get_shard_map()(
        stepped, mesh=mesh, in_specs=(row,) * 3,
        out_specs=(row, P(), P(), (row,) * (2 + waved)),
        check_rep=False,
    ))(np.concatenate(valid), np.concatenate(keys), np.concatenate(vals))

    want, want_ov = _cut_reference(kind, keys, vals, valid, nparts,
                                   send_cap)
    assert int(bad) == 0
    assert int(ov) == want_ov
    assert (want_ov >= 16) == (scenario == "over_send_cap")
    mask = np.asarray(mask).reshape(n, n, send_cap)
    cols = [np.asarray(c).reshape(n, n, send_cap) for c in cols]
    for d in range(n):
        for s in range(n):
            subid, k, v = want[d][s]
            np.testing.assert_array_equal(
                mask[d, s], np.arange(send_cap) < len(k))
            got = [c[d, s, :len(k)] for c in cols]
            for g, w in zip(got, ((subid,) if waved else ()) + (k, v)):
                np.testing.assert_array_equal(g, w)
            assert not any(c[d, s, len(k):].any() for c in cols)
    if scenario == "empty_lane":
        assert not mask[2].any()
    if scenario == "all_invalid":
        assert not mask.any()
