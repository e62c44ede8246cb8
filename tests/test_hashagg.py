"""Hash-aggregate lowering: the sortless combiningFrame analog.

Covers the claim cascade's correctness guarantees (exactness, the
frozen-slot invariant, overflow signalling), the destination-contiguous
exchange, the join align, and the executor-level fallback ladder —
mirroring the reference's combiner tests (exec/combiner_test.go) plus
the retry semantics this design adds.
"""

import collections

import numpy as np
import pytest

import bigslice_tpu as bs
from bigslice_tpu.exec.meshexec import MeshExecutor
from bigslice_tpu.exec.session import Session


def _mesh():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:8]), ("shards",))


def _hash_session(**kw):
    return Session(executor=MeshExecutor(
        _mesh(), auto_dense=False, hash_aggregate=True, **kw
    ))


def _shardmap_call(fn, nouts, *arrays):
    """Run a per-device body over the 8-device mesh (columns sharded on
    axis 0) and return the global outputs."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bigslice_tpu.parallel.meshutil import get_shard_map

    mesh = _mesh()
    sharding = NamedSharding(mesh, P("shards"))
    placed = [jax.device_put(a, sharding) for a in arrays]
    jitted = jax.jit(get_shard_map()(
        fn, mesh=mesh,
        in_specs=tuple(P("shards") for _ in arrays),
        out_specs=tuple(P("shards") for _ in range(nouts)),
        check_rep=False,
    ))
    return [np.asarray(o) for o in jitted(*placed)]


def test_claim_cascade_exact_and_frozen_slots():
    """Every distinct key gets exactly one slot; duplicate keys resolve
    to it; slots claimed early are never stolen by later rounds
    (the round-5 overwrite bug regression)."""
    import jax.numpy as jnp

    from bigslice_tpu.parallel import hashagg

    n = 1 << 12
    rng = np.random.RandomState(3)
    # Heavy skew: a few hot keys + a long distinct tail, the shape that
    # exercises both the same-round race and the later-round probes.
    keys = np.where(rng.rand(8 * n) < 0.5,
                    rng.randint(0, 4, 8 * n),
                    rng.randint(0, 1 << 20, 8 * n)).astype(np.int32)
    vals = rng.randint(0, 100, 8 * n).astype(np.int32)

    def body(k, v):
        valid = jnp.ones(n, bool)
        part = jnp.zeros(n, np.int32)
        present, ok, ov, over = hashagg.hash_aggregate(
            valid, (k,), (v,), ("add",), part, 1, n
        )
        return present, ok[0], ov[0], over.reshape(1)

    pres, ko, vo, over = _shardmap_call(body, 4, keys, vals)
    assert int(over.sum()) == 0
    got = {}
    for i in np.flatnonzero(pres):
        dev = i // n
        key = int(ko[i])
        # One slot per distinct key per device table.
        assert (dev, key) not in got
        got[(dev, key)] = int(vo[i])
    ref = collections.defaultdict(int)
    for dev in range(8):
        for k, v in zip(keys[dev * n:(dev + 1) * n],
                        vals[dev * n:(dev + 1) * n]):
            ref[(dev, int(k))] += int(v)
    assert got == dict(ref)


def test_claim_cascade_overflow_signal_at_full_load():
    """All-distinct keys at load factor 1.0 must either fully place or
    raise the overflow signal — never silently drop rows."""
    import jax.numpy as jnp

    from bigslice_tpu.parallel import hashagg

    n = 1 << 10
    keys = np.arange(8 * n, dtype=np.int32)  # all distinct, load = 1.0
    vals = np.ones(8 * n, np.int32)

    def body(k, v):
        valid = jnp.ones(n, bool)
        part = jnp.zeros(n, np.int32)
        present, ok, ov, over = hashagg.hash_aggregate(
            valid, (k,), (v,), ("add",), part, 1, n
        )
        return present, ok[0], over.reshape(1)

    pres, ko, over = _shardmap_call(body, 3, keys, vals)
    placed = int(pres.sum())
    assert placed + int(over.sum()) == 8 * n


def test_hash_combine_shuffle_matches_sort_shuffle():
    """The fused hash combine+shuffle routes every key to the same
    device as the sort pipeline (shared partition_ids contract) with
    identical per-key sums."""
    import jax.numpy as jnp

    from bigslice_tpu.parallel import hashagg, segment, shuffle

    n = 1 << 12
    rng = np.random.RandomState(5)
    keys = rng.randint(0, 1 << 10, 8 * n).astype(np.int32)
    vals = rng.randint(0, 50, 8 * n).astype(np.int32)
    fused = hashagg.make_hash_combine_shuffle(8, 1, 1, ("add",),
                                              "shards")
    recv = hashagg.make_hash_combine(1, 1, ("add",))

    def body(k, v):
        valid = jnp.ones(n, bool)
        rm, ov, bad, oc = fused.masked(valid, k, v)
        m2, k2, v2, ov2 = recv(rm, (oc[0],), (oc[1],))
        cnt, packed = segment.compact_by_mask(m2, tuple(k2) + tuple(v2))
        return (cnt.reshape(1), (ov + ov2).reshape(1), packed[0],
                packed[1])

    cnt, over, ko, vo = _shardmap_call(body, 4, keys, vals)
    assert int(over.sum()) == 0
    size = len(ko) // 8
    out_keys, out_vals, out_dev = [], [], []
    for d in range(8):
        c = int(cnt[d])
        out_keys.extend(ko[d * size: d * size + c].tolist())
        out_vals.extend(vo[d * size: d * size + c].tolist())
        out_dev.extend([d] * c)
    ref = collections.defaultdict(int)
    for k, v in zip(keys, vals):
        ref[int(k)] += int(v)
    assert dict(zip(out_keys, out_vals)) == dict(ref)
    assert len(out_keys) == len(ref)
    # Routing contract: key k lands on device hash(k) % 8, exactly as
    # the sort shuffle routes it.
    part, _, _ = shuffle.partition_ids(
        (jnp.asarray(np.array(out_keys, np.int32)),), 8, 0,
        use_pallas=False,
    )
    assert np.array_equal(np.asarray(part), np.array(out_dev))


def test_hash_combine_shuffle_waved_partitions():
    """More partitions than devices (W=2): the subid regroup must route
    partition p to device p % nmesh carrying subid p // nmesh, with
    per-key sums intact — the trickiest layout code in the module."""
    import jax.numpy as jnp

    from bigslice_tpu.parallel import hashagg, shuffle

    n = 1 << 11
    nparts = 16  # 2 waves over the 8-device mesh
    rng = np.random.RandomState(29)
    keys = rng.randint(0, 1 << 9, 8 * n).astype(np.int32)
    vals = rng.randint(0, 20, 8 * n).astype(np.int32)
    fused = hashagg.make_hash_combine_shuffle(
        8, 1, 1, ("add",), "shards", nparts=nparts
    )

    def body(k, v):
        valid = jnp.ones(n, bool)
        rm, ov, bad, oc = fused.masked(valid, k, v)
        # out cols: subid, key, val
        return rm, ov.reshape(1), oc[0], oc[1], oc[2]

    rm, over, sub, ko, vo = _shardmap_call(body, 5, keys, vals)
    assert int(over.sum()) == 0
    size = len(ko) // 8
    got = collections.defaultdict(int)
    seen = set()
    for dev in range(8):
        sl = slice(dev * size, (dev + 1) * size)
        for m, s_, k, v in zip(rm[sl], sub[sl], ko[sl], vo[sl]):
            if not m:
                continue
            p = int(s_) * 8 + dev  # partition = subid * nmesh + device
            # A key appears at most once per (source, partition).
            got[(p, int(k))] += int(v)
            seen.add(p)
    # Per-key totals survive, and every key sits in its contract
    # partition.
    part, _, _ = shuffle.partition_ids(
        (jnp.asarray(keys),), nparts, 0, use_pallas=False
    )
    part = np.asarray(part)
    ref = collections.defaultdict(int)
    for p, k, v in zip(part, keys, vals):
        ref[(int(p), int(k))] += int(v)
    assert dict(got) == dict(ref)


def test_hash_join_align_inner_join():
    import jax.numpy as jnp

    from bigslice_tpu.parallel import hashagg, segment

    n = 1 << 10
    rng = np.random.RandomState(7)
    ka = rng.randint(0, 64, 8 * n).astype(np.int32)
    kb = rng.randint(32, 96, 8 * n).astype(np.int32)
    align = hashagg.make_hash_join_align(1, ("add",), ("add",))

    def body(a, b):
        va = jnp.ones(n, np.int32)
        vb = jnp.full(n, 2, np.int32)
        m = jnp.ones(n, bool)
        mask, cols, ov = align(m, (a, va), m, (b, vb))
        cnt, packed = segment.compact_by_mask(mask, cols)
        return cnt.reshape(1), ov.reshape(1), packed[0], packed[1], packed[2]

    cnt, over, ko, va_o, vb_o = _shardmap_call(body, 5, ka, kb)
    assert int(over.sum()) == 0
    size = len(ko) // 8
    for d in range(8):
        c = int(cnt[d])
        sl = slice(d * size, d * size + c)
        ca = collections.Counter(ka[d * n:(d + 1) * n].tolist())
        cb = collections.Counter(kb[d * n:(d + 1) * n].tolist())
        expect = {k: (ca[k], 2 * cb[k]) for k in ca if k in cb}
        got = {int(k): (int(x), int(y))
               for k, x, y in zip(ko[sl], va_o[sl], vb_o[sl])}
        assert got == expect


def test_e2e_reduce_hash_path_matches_local():
    """Session-level Reduce through the hash path (auto-dense off, hash
    forced on) agrees with the host tier."""
    n_rows = 1 << 14
    rng = np.random.RandomState(11)
    # Sparse non-dense keys: the auto-dense probe would decline these.
    keys = (rng.randint(0, 1 << 28, n_rows) | 1).astype(np.int32)
    vals = rng.randint(0, 100, n_rows).astype(np.int32)
    sess = _hash_session()
    res = sess.run(bs.Reduce(bs.Const(8, keys, vals), lambda a, b: a + b))
    got = {}
    for f in res.frames():
        h = f.to_host()
        for k, v in zip(h.cols[0], h.cols[1]):
            assert k not in got
            got[int(k)] = int(v)
    assert sess.executor.device_group_count() > 0
    ref = collections.defaultdict(int)
    for k, v in zip(keys, vals):
        ref[int(k)] += int(v)
    assert got == dict(ref)


def test_e2e_overflow_falls_back_to_sort_path():
    """A workload the cascade cannot place (all-distinct keys at load
    1.0 across a wide value range) must still produce exact results via
    the sort-path fallback, and blacklist the op."""
    n_rows = 1 << 13
    rng = np.random.RandomState(13)
    keys = rng.permutation(n_rows).astype(np.int32) + (1 << 20)
    vals = np.ones(n_rows, np.int32)
    sess = _hash_session()
    res = sess.run(bs.Reduce(bs.Const(8, keys, vals),
                             lambda a, b: a + b))
    total = sum(len(f) for f in res.frames())
    assert total == n_rows  # every key distinct
    # Either the cascade handled it (fine) or the op was blacklisted;
    # in both cases results are exact. If blacklisted, a re-run stays
    # on the sort path without error.
    res2 = sess.run(bs.Reduce(bs.Const(8, keys, vals),
                              lambda a, b: a + b))
    assert sum(len(f) for f in res2.frames()) == n_rows


def test_hash_declines_general_combine_fn():
    """A non-classifiable combine fn (not add/max/min) must ride the
    sort path and still be exact — the hash gate returns None."""
    n_rows = 1 << 12
    rng = np.random.RandomState(17)
    keys = rng.randint(0, 1 << 24, n_rows).astype(np.int32)
    vals = rng.randint(1, 10, n_rows).astype(np.int32)
    sess = _hash_session()

    def weird(a, b):  # associative but not add/max/min
        return a * b % 1000003

    res = sess.run(bs.Reduce(bs.Const(8, keys, vals), weird))
    got = {}
    for f in res.frames():
        h = f.to_host()
        for k, v in zip(h.cols[0], h.cols[1]):
            got[int(k)] = int(v)
    ref = {}
    order = collections.defaultdict(list)
    for k, v in zip(keys, vals):
        order[int(k)].append(int(v))
    for k, vs in order.items():
        acc = vs[0]
        for v in vs[1:]:
            acc = acc * v % 1000003
        ref[k] = acc
    assert got == ref


def test_hash_shuffle_vector_value_columns():
    """Vector value columns ([n, d] rows — the k-means point-sum shape)
    ride the hash combine+shuffle intact (round-5 reshape regression)."""
    import jax.numpy as jnp

    from bigslice_tpu.parallel import hashagg, segment

    n, d = 1 << 10, 4
    rng = np.random.RandomState(23)
    keys = rng.randint(0, 128, 8 * n).astype(np.int32)
    vecs = rng.randint(0, 10, (8 * n, d)).astype(np.int32)
    fused = hashagg.make_hash_combine_shuffle(8, 1, 1, ("add",),
                                              "shards")
    recv = hashagg.make_hash_combine(1, 1, ("add",))

    def body(k, v):
        valid = jnp.ones(n, bool)
        rm, ov, bad, oc = fused.masked(valid, k, v)
        m2, k2, v2, ov2 = recv(rm, (oc[0],), (oc[1],))
        cnt, packed = segment.compact_by_mask(m2, tuple(k2) + tuple(v2))
        return cnt.reshape(1), (ov + ov2).reshape(1), packed[0], packed[1]

    cnt, over, ko, vo = _shardmap_call(body, 4, keys, vecs)
    assert int(over.sum()) == 0
    size = len(ko) // 8
    got = {}
    for dev in range(8):
        c = int(cnt[dev])
        for i in range(dev * size, dev * size + c):
            got[int(ko[i])] = vo[i].tolist()
    ref = collections.defaultdict(lambda: np.zeros(d, np.int64))
    for k, v in zip(keys, vecs):
        ref[int(k)] += v
    assert got == {k: v.tolist() for k, v in ref.items()}


def test_e2e_join_hash_path_matches_local():
    n_rows = 1 << 13
    rng = np.random.RandomState(19)
    ak = rng.randint(0, 1 << 24, n_rows).astype(np.int32)
    bk = rng.randint(0, 1 << 24, n_rows).astype(np.int32)
    # Force overlap so the join is non-trivial.
    bk[: n_rows // 2] = ak[: n_rows // 2]
    ones = np.ones(n_rows, np.int32)
    sess = _hash_session()

    def add(a, b):
        return a + b

    res = sess.run(bs.JoinAggregate(
        bs.Const(8, ak, ones), bs.Const(8, bk, ones), add, add
    ))
    got = {}
    for f in res.frames():
        h = f.to_host()
        for k, x, y in zip(*h.cols):
            assert k not in got
            got[int(k)] = (int(x), int(y))
    ca = collections.Counter(ak.tolist())
    cb = collections.Counter(bk.tolist())
    expect = {k: (ca[k], cb[k]) for k in ca if k in cb}
    assert got == expect


def test_default_keyed_combine_is_the_sort_pipeline(monkeypatch):
    """With nothing asked for, an eligible combiner (``add`` over int32
    keys) takes the sort pipeline on every backend — the CPU mesh builds
    the family of programs the chip runs; ``hash_aggregate=True`` is
    what routes it to the hash lowering."""
    from bigslice_tpu.slicetype import ColType, Schema

    monkeypatch.delenv("BIGSLICE_HASH_AGGREGATE", raising=False)
    schema = Schema([ColType(np.dtype(np.int32), "", ()),
                     ColType(np.dtype(np.int32), "", ())], 1)

    class FC:  # minimal combiner stand-in for the gate call
        fn = staticmethod(lambda a, b: a + b)
        nvals = 1
        dense_keys = None

    default = MeshExecutor(_mesh(), auto_dense=False)
    assert default._hash_combine_ops("op", FC(), schema) is None
    asked = MeshExecutor(_mesh(), auto_dense=False, hash_aggregate=True)
    assert asked._hash_combine_ops("op", FC(), schema) == ("add",)


def test_float_keys_route_to_sort_lowering():
    """Float keys never take the hash lowering (ADVICE r5): the claim
    cascade slot-hashes bit patterns but compares with ==, so -0.0/0.0
    would claim separate slots and NaN keys could never match their own
    slot. The gate itself plus a parity pin: float-key reduce results
    are identical with the hash path enabled and disabled (both route
    to the sort lowering), including the -0.0 == 0.0 merge."""
    from bigslice_tpu.slicetype import ColType, Schema

    ex = _hash_session().executor
    fschema = Schema([ColType(np.dtype(np.float32), "", ()),
                      ColType(np.dtype(np.int32), "", ())], 1)

    class FC:  # minimal combiner stand-in for the gate call
        fn = staticmethod(lambda a, b: a + b)
        nvals = 1
        dense_keys = None

    assert ex._hash_combine_ops("op", FC(), fschema) is None

    n_rows = 1 << 12
    rng = np.random.RandomState(23)
    keys = rng.randint(-8, 8, n_rows).astype(np.float32)
    keys[keys == 0.0] = np.where(
        rng.rand(int((keys == 0.0).sum())) < 0.5, -0.0, 0.0
    ).astype(np.float32)
    vals = np.ones(n_rows, np.int32)

    def run(hash_aggregate):
        sess = Session(executor=MeshExecutor(
            _mesh(), auto_dense=False, hash_aggregate=hash_aggregate
        ))
        res = sess.run(bs.Reduce(bs.Const(8, keys, vals),
                                 lambda a, b: a + b))
        assert sess.executor.device_group_count() > 0
        rows = sorted(
            (float(k), int(v)) for f in res.frames()
            for k, v in zip(*f.to_host().cols)
        )
        return rows

    hash_on = run(True)
    hash_off = run(False)
    assert hash_on == hash_off
    # -0.0 and 0.0 merged into ONE key row under IEEE == semantics.
    zero_rows = [r for r in hash_on if r[0] == 0.0]
    assert len(zero_rows) == 1
    ref = collections.defaultdict(int)
    for k in keys.tolist():
        ref[float(k)] += 1
    assert hash_on == sorted(ref.items())
