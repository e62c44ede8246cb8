"""Dense-keyed Reduce: the sort-free table+collective lowering
(parallel/dense.py) must agree exactly with the sort pipeline and with
the host oracle, across ops, dtypes, shard counts, and misdeclaration."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigslice_tpu as bs
from bigslice_tpu.exec.meshexec import MeshExecutor
from bigslice_tpu.exec.session import Session
from bigslice_tpu.parallel import dense
from bigslice_tpu.parallel import segment


@pytest.fixture
def mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:8]), ("shards",))


def mesh_sess(mesh):
    return Session(executor=MeshExecutor(mesh))


# ---------------------------------------------------------- classifier

def canon(fn, nvals):
    return segment.canonical_combine(fn, nvals)


def test_classify_add_max_min():
    assert dense.classify_combine_ops(
        canon(lambda a, b: a + b, 1), [np.int32]) == ("add",)
    assert dense.classify_combine_ops(
        canon(jnp.maximum, 1), [np.float32]) == ("max",)
    assert dense.classify_combine_ops(
        canon(jnp.minimum, 1), [np.int32]) == ("min",)


def test_classify_per_column_mix():
    def fn(a, b):
        return (a[0] + b[0], jnp.maximum(a[1], b[1]))

    assert dense.classify_combine_ops(
        canon(fn, 2), [np.int32, np.float32]) == ("add", "max")


def test_classify_rejects_nonstandard():
    assert dense.classify_combine_ops(
        canon(lambda a, b: a * b, 1), [np.int32]) is None
    # Cross-column dependence must not classify.
    assert dense.classify_combine_ops(
        canon(lambda a, b: (a[0] + b[1], a[1] + b[0]), 2),
        [np.int32, np.int32]) is None


def test_routing_matches_sort_path_hash():
    from bigslice_tpu.parallel import shuffle as shuffle_mod

    K, P = 1000, 8
    table, maxc = dense.routing_tables(K, P, 0)
    part, _, _ = shuffle_mod.partition_ids(
        (np.arange(K, dtype=np.int32),), P, 0, use_pallas=False
    )
    part = np.asarray(part)
    for p in range(P):
        slots = table[p][table[p] != K]
        assert set(slots.tolist()) == set(
            np.flatnonzero(part == p).tolist()
        )
    assert table.shape == (P, maxc)


# ------------------------------------------------------------- e2e mesh

def oracle(keys, vals, op):
    out = {}
    f = {"add": lambda a, b: a + b, "max": max, "min": min}[op]
    for k, v in zip(keys.tolist(), vals.tolist()):
        out[k] = f(out[k], v) if k in out else v
    return out


@pytest.mark.parametrize("op,fn", [
    ("add", lambda a, b: a + b),
    ("max", jnp.maximum),
    ("min", jnp.minimum),
])
def test_dense_reduce_matches_oracle(mesh, op, fn):
    rng = np.random.RandomState(3)
    K = 500
    keys = rng.randint(0, K, 6000).astype(np.int32)
    vals = rng.randint(-100, 100, 6000).astype(np.int32)
    sess = mesh_sess(mesh)
    r = bs.Reduce(bs.Const(8, keys, vals), fn, dense_keys=K)
    assert r.frame_combiner.dense_keys == K
    res = sess.run(r)
    assert dict(res.rows()) == oracle(keys, vals, op)
    assert sess.executor.device_group_count() >= 1


def test_dense_matches_sort_path_exactly(mesh):
    rng = np.random.RandomState(4)
    K = 300
    keys = rng.randint(0, K, 4000).astype(np.int32)
    vals = rng.randn(4000).astype(np.float32)

    def add(a, b):
        return a + b

    dense_res = mesh_sess(mesh).run(
        bs.Reduce(bs.Const(8, keys, vals), add, dense_keys=K))
    # auto_dense=False pins the generic sort path (auto-discovery
    # would otherwise promote these undeclared dense keys too).
    sort_sess = Session(executor=MeshExecutor(mesh, auto_dense=False))
    sort_res = sort_sess.run(
        bs.Reduce(bs.Const(8, keys, vals), add))
    d = dict(dense_res.rows())
    s = dict(sort_res.rows())
    assert set(d) == set(s)
    for k in d:
        # Both reassociate float adds; equal up to accumulation order.
        assert abs(d[k] - s[k]) < 1e-3


def test_dense_multi_value_mixed_ops(mesh):
    def fn(a, b):
        return (a[0] + b[0], jnp.maximum(a[1], b[1]))

    rng = np.random.RandomState(5)
    K = 64
    keys = rng.randint(0, K, 3000).astype(np.int32)
    v1 = rng.randint(0, 50, 3000).astype(np.int32)
    v2 = rng.randn(3000).astype(np.float32)
    r = bs.Reduce(bs.Const(8, keys, v1, v2), fn, dense_keys=K)
    assert r.frame_combiner.dense_ops == ("add", "max")
    res = mesh_sess(mesh).run(r)
    got = {k: (a, b) for k, a, b in res.rows()}
    want = {}
    for k, a, b in zip(keys.tolist(), v1.tolist(), v2.tolist()):
        if k in want:
            want[k] = (want[k][0] + a, max(want[k][1], b))
        else:
            want[k] = (a, b)
    assert set(got) == set(want)
    for k in want:
        assert got[k][0] == want[k][0]
        assert abs(got[k][1] - want[k][1]) < 1e-6


def test_unclassifiable_fn_ignores_dense_hint(mesh):
    r = bs.Reduce(
        bs.Const(8, np.arange(100, dtype=np.int32) % 7,
                 np.ones(100, np.int32)),
        lambda a, b: a * b, dense_keys=7,
    )
    assert r.frame_combiner.dense_keys is None  # sort path
    res = mesh_sess(mesh).run(r)
    assert dict(res.rows()) == {k: 1 for k in range(7)}


def test_out_of_range_keys_fail_loudly(mesh):
    keys = np.array([0, 1, 2, 99], dtype=np.int32)  # 99 >= K
    r = bs.Reduce(bs.Const(8, keys, np.ones(4, np.int32)),
                  lambda a, b: a + b, dense_keys=10)
    assert r.frame_combiner.dense_keys == 10
    with pytest.raises(Exception) as ei:
        res = mesh_sess(mesh).run(r)
        list(res.rows())
    assert "dense_keys" in repr(ei.value) or "partitioner" in repr(
        ei.value)


def test_dense_result_feeds_downstream_consumers(mesh):
    """Partition routing must match the hash contract: a consumer
    compiled against the dense producer reads aligned partitions."""
    rng = np.random.RandomState(6)
    K = 128
    keys = rng.randint(0, K, 2000).astype(np.int32)
    sess = mesh_sess(mesh)
    red = bs.Reduce(bs.Const(8, keys, np.ones(2000, np.int32)),
                    lambda a, b: a + b, dense_keys=K)
    m = bs.Map(red, lambda k, c: (k, c * 10))
    res = sess.run(m)
    want = {k: int(c) * 10 for k, c in
            zip(*np.unique(keys, return_counts=True))}
    assert dict(res.rows()) == want


def test_wordcount_model_uses_dense_path(tmp_path):
    from bigslice_tpu.exec.local import LocalExecutor
    import bigslice_tpu.models.urls as urls_mod

    p = tmp_path / "urls.txt"
    lines = [f"http://site{i % 5}.com/p{i}" for i in range(100)]
    p.write_text("\n".join(lines) + "\n")
    sess = Session(executor=LocalExecutor())
    rows = urls_mod.domain_count_encoded(sess, 2, str(p))
    assert dict(rows) == {f"site{i}.com": 20 for i in range(5)}


def test_dense_combine_single_partition_one_device_mesh():
    """1-chip shape (the real-TPU bench case): no shuffle stage at all;
    the map-side combine stage itself takes the dense-table path."""
    from jax.sharding import Mesh

    mesh1 = Mesh(np.array(jax.devices()[:1]), ("shards",))
    rng = np.random.RandomState(7)
    K = 200
    keys = rng.randint(0, K, 5000).astype(np.int32)
    vals = rng.randint(-5, 5, 5000).astype(np.int32)
    sess = mesh_sess(mesh1)
    res = sess.run(bs.Reduce(bs.Const(1, keys, vals),
                             lambda a, b: a + b, dense_keys=K))
    assert dict(res.rows()) == oracle(keys, vals, "add")
    assert sess.executor.device_group_count() >= 1


def test_dense_combine_out_of_range_single_partition_raises():
    from jax.sharding import Mesh

    mesh1 = Mesh(np.array(jax.devices()[:1]), ("shards",))
    keys = np.array([0, 1, 50], dtype=np.int32)
    sess = mesh_sess(mesh1)
    r = bs.Reduce(bs.Const(1, keys, np.ones(3, np.int32)),
                  lambda a, b: a + b, dense_keys=10)
    assert r.frame_combiner.dense_keys == 10
    with pytest.raises(Exception) as ei:
        res = sess.run(r)
        list(res.rows())
    assert "dense_keys" in repr(ei.value) or "partitioner" in repr(
        ei.value)


# -------------------------------------------------------------- dense join

def join_oracle(ak, av, bk, bv):
    A, B = {}, {}
    for k, v in zip(ak.tolist(), av.tolist()):
        A[k] = A.get(k, 0) + v
    for k, v in zip(bk.tolist(), bv.tolist()):
        B[k] = B.get(k, 0) + v
    return {k: (A[k], B[k]) for k in A if k in B}


def test_dense_join_matches_oracle(mesh):
    rng = np.random.RandomState(8)
    K = 400
    ak = rng.randint(0, K, 4000).astype(np.int32)
    bk = rng.randint(0, K // 2, 4000).astype(np.int32)  # partial overlap
    av = rng.randint(1, 5, 4000).astype(np.int32)
    bv = rng.randint(1, 5, 4000).astype(np.int32)
    j = bs.JoinAggregate(
        bs.Const(8, ak, av), bs.Const(8, bk, bv),
        lambda a, b: a + b, lambda a, b: a + b, dense_keys=K,
    )
    assert j.frame_combiners[0].dense_keys == K
    res = mesh_sess(mesh).run(j)
    got = {k: (x, y) for k, x, y in res.rows()}
    assert got == join_oracle(ak, av, bk, bv)


def test_dense_join_matches_sort_join(mesh):
    rng = np.random.RandomState(9)
    K = 256
    ak = rng.randint(0, K, 3000).astype(np.int32)
    bk = rng.randint(0, K, 3000).astype(np.int32)
    av = np.ones(3000, np.int32)
    bv = np.ones(3000, np.int32)

    def add(a, b):
        return a + b

    jd = mesh_sess(mesh).run(bs.JoinAggregate(
        bs.Const(8, ak, av), bs.Const(8, bk, bv), add, add,
        dense_keys=K))
    js = mesh_sess(mesh).run(bs.JoinAggregate(
        bs.Const(8, ak, av), bs.Const(8, bk, bv), add, add))
    assert sorted(jd.rows()) == sorted(js.rows())


def test_dense_join_single_device():
    from jax.sharding import Mesh

    mesh1 = Mesh(np.array(jax.devices()[:1]), ("shards",))
    rng = np.random.RandomState(10)
    K = 100
    ak = rng.randint(0, K, 1000).astype(np.int32)
    bk = rng.randint(0, K, 1000).astype(np.int32)
    av = rng.randint(1, 3, 1000).astype(np.int32)
    bv = rng.randint(1, 3, 1000).astype(np.int32)
    res = mesh_sess(mesh1).run(bs.JoinAggregate(
        bs.Const(1, ak, av), bs.Const(1, bk, bv),
        lambda a, b: a + b, lambda a, b: a + b, dense_keys=K))
    got = {k: (x, y) for k, x, y in res.rows()}
    assert got == join_oracle(ak, av, bk, bv)


def test_dense_join_then_narrower_shard_count_no_cache_collision(mesh):
    """Same fn objects + dense_keys at two shard widths: the program
    cache must not reuse the 8-wide dense-join lowering for the 4-shard
    run (its routing/ownership checks would spuriously flag bad keys)."""
    rng = np.random.RandomState(11)
    K = 64
    ak = rng.randint(0, K, 512).astype(np.int32)
    bk = rng.randint(0, K, 512).astype(np.int32)
    ones = np.ones(512, np.int32)

    def add(a, b):
        return a + b

    sess = mesh_sess(mesh)
    r8 = sess.run(bs.JoinAggregate(
        bs.Const(8, ak, ones), bs.Const(8, bk, ones), add, add,
        dense_keys=K))
    want = join_oracle(ak, ones, bk, ones)
    assert {k: (x, y) for k, x, y in r8.rows()} == want
    r4 = sess.run(bs.JoinAggregate(
        bs.Const(4, ak, ones), bs.Const(4, bk, ones), add, add,
        dense_keys=K))
    assert {k: (x, y) for k, x, y in r4.rows()} == want


def test_dense_vector_value_columns(mesh):
    """Vector value columns scatter whole rows (the kmeans shape:
    Reduce of (cid, [d] vec, weight) with dense centroid ids)."""
    rng = np.random.RandomState(12)
    K, d = 16, 8
    keys = rng.randint(0, K, 2000).astype(np.int32)
    vecs = rng.randn(2000, d).astype(np.float32)
    w = np.ones(2000, np.float32)

    def fn(a, b):
        return (a[0] + b[0], a[1] + b[1])

    r = bs.Reduce(bs.Const(8, keys, vecs, w), fn, dense_keys=K)
    assert r.frame_combiner.dense_keys == K
    res = mesh_sess(mesh).run(r)
    got = {int(k): (np.asarray(v), float(c)) for k, v, c in res.rows()}
    for k in range(K):
        sel = keys == k
        assert got[k][1] == sel.sum()
        np.testing.assert_allclose(got[k][0], vecs[sel].sum(0),
                                   rtol=1e-4, atol=1e-4)


# ------------------------------------------- the table pass, per column

def _table(idx, vals, ops, size):
    """``_scatter_tables`` jitted, as a group program traces it."""
    idents = [dense._identity(op, v.dtype) for v, op in zip(vals, ops)]
    fn = jax.jit(lambda i, *vs: dense._scatter_tables(
        i, list(vs), list(ops), idents, size))
    present, tables = fn(idx, *vals)
    return np.asarray(present), [np.asarray(t) for t in tables]


def _scattered(idx, v, size):
    """A plain scatter-add of one float column."""
    out = jnp.zeros((size,) + v.shape[1:], v.dtype).at[idx].add(v)
    return np.asarray(out)


@pytest.mark.parametrize("size,d", [
    (2, 1), (2, 128), (17, 8), (17, 128), (65, 1), (65, 8), (65, 128),
    (128, 8), (128, 128),
])
def test_small_table_vector_sums_match_float64_oracle(size, d):
    """A float32 vector column and a scalar column into a small table,
    the last slot the drop lane: the vector sums by scatter and the
    counts by the compare agree with a float64 oracle, ``present``
    exactly. The coordinates are positive, as the k-means points are,
    so that float32's own rounding of a sum, in any order, stays under
    the tolerance (a sum that cancels to near 0 would not)."""
    rng = np.random.RandomState(size * 1000 + d)
    n = 3000
    idx = rng.randint(0, size, n).astype(np.int32)
    x = rng.rand(n, d).astype(np.float32)
    w = rng.rand(n).astype(np.float32)
    assert dense.table_column_lowering(size, (d,)) == "scatter"
    assert dense.table_column_lowering(size, ()) == "compare"
    present, (sums, counts) = _table(idx, [x, w], ["add", "add"], size)
    kept = size - 1
    want = np.zeros((size, d))
    np.add.at(want, idx, x.astype(np.float64))
    want_w = np.zeros(size)
    np.add.at(want_w, idx, w.astype(np.float64))
    np.testing.assert_array_equal(
        present, np.bincount(idx, minlength=size) > 0)
    assert sums.shape == (size, d) and sums.dtype == np.float32
    np.testing.assert_allclose(sums[:kept], want[:kept], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(counts[:kept], want_w[:kept], rtol=1e-5,
                               atol=1e-5)


def test_small_table_vector_sums_are_exact_to_the_bit():
    """One row a slot: the table is each row's float32 value to the
    last bit, over every exponent a row may have (a lowering that
    rounds the rows on the way, as a bfloat16 pass would, fails)."""
    rng = np.random.RandomState(25)
    size, d = 65, 32
    x = (rng.randn(size, d) * np.exp2(rng.randint(-60, 60, (size, d)))
         ).astype(np.float32)
    x[-1] = np.nan          # the drop lane's row: sliced off, never read
    idx = np.arange(size, dtype=np.int32)
    _, (sums,) = _table(idx, [x], ["add"], size)
    np.testing.assert_array_equal(sums[:size - 1].view(np.uint32),
                                  x[:size - 1].view(np.uint32))


@pytest.mark.parametrize("value", [np.nan, np.inf, -np.inf])
def test_non_finite_row_gets_the_scatter_paths_table(value):
    """A NaN or inf in one kept row stays in its own slot and element
    (where a one-hot product would carry it to every slot: 0 * NaN is
    NaN), and the table is a plain scatter's, bit for bit."""
    rng = np.random.RandomState(21)
    size, n, d = 65, 4096, 16
    idx = rng.randint(0, size - 1, n).astype(np.int32)
    x = rng.randn(n, d).astype(np.float32)
    row, col = 123, 5
    x[row, col] = value
    _, (sums,) = _table(idx, [x], ["add"], size)
    np.testing.assert_array_equal(sums, _scattered(idx, x, size))
    bad = np.argwhere(~np.isfinite(sums))
    np.testing.assert_array_equal(bad, [[idx[row], col]])


def test_non_finite_drop_lane_row_stays_out_of_the_kept_slots():
    """A NaN in a row sent to the drop lane (masked out, out of range:
    the caller slices the lane off) reaches no kept slot, and the kept
    slots hold the sums of the kept rows."""
    rng = np.random.RandomState(22)
    size, n, d = 65, 4096, 16
    idx = rng.randint(0, size, n).astype(np.int32)
    x = rng.randn(n, d).astype(np.float32)
    dropped = np.flatnonzero(idx == size - 1)
    x[dropped[0], 3] = np.nan
    x[dropped[1], 7] = np.inf
    _, (sums,) = _table(idx, [x], ["add"], size)
    assert np.isfinite(sums[:size - 1]).all()
    np.testing.assert_allclose(sums[:size - 1],
                               _scattered(idx, x, size)[:size - 1],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("op,dtype", [
    ("add", np.float32), ("max", np.float32), ("min", np.float32),
    ("add", np.int32), ("max", np.int32),
])
def test_small_table_vector_columns_scatter(op, dtype):
    """A vector column of a small table takes its scatter, beside a
    scalar column's compare, and stays exact (the float32 sums are
    small integers here, so any order of addition gives them)."""
    rng = np.random.RandomState(23)
    size, n, d = 17, 2000, 4
    idx = rng.randint(0, size, n).astype(np.int32)
    v = np.round(rng.randn(n, d) * 100).astype(dtype)
    c = np.ones(n, np.int32)
    assert dense.table_column_lowering(size, (d,)) == "scatter"
    ops = [op, "add"]
    idents = [dense._identity(o, t.dtype) for o, t in zip(ops, (v, c))]
    text = jax.jit(lambda i, a, b: dense._scatter_tables(
        i, [a, b], ops, idents, size)).lower(idx, v, c).as_text()
    assert text.count('"stablehlo.scatter"(') == 1
    assert "dot_general" not in text
    present, (table, counts) = _table(idx, [v, c], ops, size)
    want = np.full((size, d), dense._identity(op, dtype), dtype)
    ufunc = {"add": np.add, "max": np.maximum, "min": np.minimum}[op]
    ufunc.at(want, idx, v)
    np.testing.assert_array_equal(table, want)
    np.testing.assert_array_equal(counts, np.bincount(idx, minlength=size))
    np.testing.assert_array_equal(present, counts > 0)


@pytest.mark.parametrize("size,shape,want", [
    (65, (), "compare"),
    (7, (), "compare"),
    (dense.SMALL_TABLE, (), "compare"),
    (65, (128,), "scatter"),
    (2, (1,), "scatter"),
    (128, (4, 2), "scatter"),
    (129, (), "scatter"),
    (129, (128,), "scatter"),
])
def test_table_column_lowering_choices(size, shape, want):
    """The one source of truth for a column's lowering: the compare for
    a scalar column of a table of at most ``SMALL_TABLE`` slots, a
    scatter for a vector column and for every column of a larger
    table."""
    assert dense.table_column_lowering(size, shape) == want


def test_small_vector_table_present_and_counts_take_no_scatter():
    """The kmeans table's shape: a float32 vector and a float32 count
    into 65 slots — ``present`` and the counts are the compare's, so the
    one scatter is the vector sums'."""
    rng = np.random.RandomState(24)
    n = 1024
    idx = rng.randint(0, 65, n).astype(np.int32)
    x = rng.randn(n, 8).astype(np.float32)
    w = np.ones(n, np.float32)
    text = jax.jit(lambda i, a, b: dense._scatter_tables(
        i, [a, b], ["add", "add"], [np.float32(0)] * 2, 65)).lower(
        idx, x, w).as_text()
    assert text.count('"stablehlo.scatter"(') == 1
    assert "dot_general" not in text


# -------------------------------------------------------------- dense fold

def test_dense_fold_max_matches_oracle(mesh):
    """BASELINE config #1's named shape (Fold max over keyed ints,
    example/max.go analog) on the dense lowering, init respected."""
    import jax.numpy as jnp

    rng = np.random.RandomState(13)
    K = 100
    keys = rng.randint(0, K, 3000).astype(np.int32)
    vals = rng.randint(-1000, 1000, 3000).astype(np.int32)

    def fmax(acc, v):
        return jnp.maximum(acc, v)

    f = bs.Fold(bs.Const(8, keys, vals), fmax, init=-50,
                dense_keys=K)
    assert f.dense_op == "max"
    res = mesh_sess(mesh).run(f)
    want = {int(k): max(int(vals[keys == k].max()), -50)
            for k in np.unique(keys)}
    assert dict(res.rows()) == want


def test_dense_fold_add_with_wider_acc(mesh):
    rng = np.random.RandomState(14)
    K = 64
    keys = rng.randint(0, K, 2000).astype(np.int32)
    vals = rng.randint(0, 100, 2000).astype(np.int32)

    def fadd(acc, v):
        return acc + v

    f = bs.Fold(bs.Const(8, keys, vals), fadd, init=7,
                out_value=np.int32, dense_keys=K)
    assert f.dense_op == "add"
    res = mesh_sess(mesh).run(f)
    want = {int(k): int(vals[keys == k].sum()) + 7
            for k in np.unique(keys)}
    assert dict(res.rows()) == want


def test_nonassociative_fold_keeps_scan_path(mesh):
    def weird(acc, v):
        return acc * 2 + v  # order-dependent: must NOT classify

    f = bs.Fold(bs.Const(4, np.zeros(10, np.int32),
                         np.ones(10, np.int32)), weird, init=0,
                dense_keys=5)
    assert f.dense_keys is None


def test_out_of_range_fails_even_when_heuristic_reverts(mesh):
    """Declared-range enforcement must not depend on which lowering the
    size heuristic picks: tiny input + big declared K reverts to the
    sort/scan path, and the violation must still fail loudly."""
    import jax.numpy as jnp

    keys = np.array([0, 1, 5000], dtype=np.int32)  # 5000 >= K... no:
    K = 4000  # K > 2 * input rows → heuristic keeps the scan path
    sess = mesh_sess(mesh)
    f = bs.Fold(bs.Const(1, keys, np.ones(3, np.int32)),
                lambda acc, v: jnp.maximum(acc, v), init=0,
                dense_keys=K)
    assert f.dense_keys == K
    with pytest.raises(Exception) as ei:
        res = sess.run(f)
        list(res.rows())
    assert "dense_keys" in repr(ei.value) or "partitioner" in repr(
        ei.value)
