"""Automatic dense-key discovery: an undeclared
Reduce/Fold over dense int32 keys takes the table+collective lowering
via a staging-time min/max probe; misprobes (keys a later wave never
showed wave 0) retract through the badrange signal and re-run on the
sort path; ineligible shapes stay on the sort path untouched."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigslice_tpu as bs
from bigslice_tpu.exec.meshexec import MeshExecutor
from bigslice_tpu.exec.session import Session


@pytest.fixture
def mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:8]), ("shards",))


def mesh_sess(mesh, **kw):
    return Session(executor=MeshExecutor(mesh, **kw))


def oracle_sum(keys, vals):
    out = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        out[k] = out.get(k, 0) + v
    return out


def test_undeclared_reduce_discovers_dense(mesh):
    rng = np.random.RandomState(7)
    keys = rng.randint(0, 400, 6000).astype(np.int32)
    vals = rng.randint(-50, 50, 6000).astype(np.int32)
    sess = mesh_sess(mesh)
    r = bs.Reduce(bs.Const(8, keys, vals), lambda a, b: a + b)
    assert r.frame_combiner.dense_keys is None  # nothing declared
    res = sess.run(r)
    assert dict(res.rows()) == oracle_sum(keys, vals)
    # The probe declared the observed bound on the shared combiner.
    assert r.frame_combiner.dense_keys == int(keys.max()) + 1
    assert getattr(r.frame_combiner, "_auto_declared", False)
    assert sess.executor.device_group_count() >= 1


def test_auto_dense_disabled_by_option(mesh):
    rng = np.random.RandomState(8)
    keys = rng.randint(0, 100, 2000).astype(np.int32)
    vals = np.ones(2000, np.int32)
    sess = mesh_sess(mesh, auto_dense=False)
    r = bs.Reduce(bs.Const(8, keys, vals), lambda a, b: a + b)
    res = sess.run(r)
    assert dict(res.rows()) == oracle_sum(keys, vals)
    assert r.frame_combiner.dense_keys is None  # stayed generic


def test_negative_keys_stay_on_sort_path(mesh):
    rng = np.random.RandomState(9)
    keys = rng.randint(-50, 50, 2000).astype(np.int32)
    vals = np.ones(2000, np.int32)
    sess = mesh_sess(mesh)
    r = bs.Reduce(bs.Const(8, keys, vals), lambda a, b: a + b)
    res = sess.run(r)
    assert dict(res.rows()) == oracle_sum(keys, vals)
    assert r.frame_combiner.dense_keys is None


def test_sparse_keys_stay_on_sort_path(mesh):
    # Range far beyond 2x capacity: the league guard must refuse.
    keys = (np.arange(2000, dtype=np.int64) * 1_000_000 % (1 << 30)
            ).astype(np.int32)
    vals = np.ones(2000, np.int32)
    sess = mesh_sess(mesh)
    r = bs.Reduce(bs.Const(8, keys, vals), lambda a, b: a + b)
    res = sess.run(r)
    assert dict(res.rows()) == oracle_sum(keys, vals)
    assert r.frame_combiner.dense_keys is None


def test_unclassifiable_fn_stays_on_sort_path(mesh):
    keys = np.arange(100, dtype=np.int32) % 7
    vals = np.full(100, 2, np.int32)
    sess = mesh_sess(mesh)
    r = bs.Reduce(bs.Const(8, keys, vals), lambda a, b: a * b)
    res = sess.run(r)
    want = {k: 2 ** int((keys == k).sum()) for k in range(7)}
    assert dict(res.rows()) == want
    assert r.frame_combiner.dense_keys is None


def test_misprobe_retracts_and_recovers(mesh):
    """20 shards on 8 devices → 3 waves. Wave 0 shows keys in [0, 8);
    a later wave holds key 500_000 — outside the probed bound. The
    badrange signal must retract the auto declaration and the group
    must re-run (correctly) on the sort path."""
    n_shards, per = 20, 64
    rows = n_shards * per
    keys = np.zeros(rows, np.int32)
    rng = np.random.RandomState(11)
    keys[:] = rng.randint(0, 8, rows)
    # Const splits rows evenly in order: the last shard's rows are the
    # tail. Plant the out-of-probe key there (wave 2 on an 8-mesh).
    keys[-per:] = 500_000
    vals = np.ones(rows, np.int32)
    sess = mesh_sess(mesh)
    r = bs.Reduce(bs.Const(n_shards, keys, vals), lambda a, b: a + b)
    res = sess.run(r)
    assert dict(res.rows()) == oracle_sum(keys, vals)
    # Retracted + site blacklisted: the sort path served the run.
    assert r.frame_combiner.dense_keys is None
    ex = sess.executor
    assert any(op in repr(ex._auto_dense_off) or True
               for op in ex._auto_dense_off)  # non-empty
    assert len(ex._auto_dense_off) >= 1


def test_blacklisted_site_not_reprobed(mesh):
    """After a misprobe retraction, a rebuilt slice at the same
    pipeline site must not re-declare (routing honesty beats speed)."""
    n_shards, per = 20, 64
    rows = n_shards * per

    def build(keys, vals):
        return bs.Reduce(bs.Const(n_shards, keys, vals),
                         lambda a, b: a + b)

    rng = np.random.RandomState(13)
    keys = rng.randint(0, 8, rows).astype(np.int32)
    keys[-per:] = 400_000
    vals = np.ones(rows, np.int32)
    sess = mesh_sess(mesh)
    r1 = build(keys, vals)
    assert dict(sess.run(r1).rows()) == oracle_sum(keys, vals)
    assert r1.frame_combiner.dense_keys is None
    # Second invocation, dense-friendly data, SAME site: stays off.
    keys2 = rng.randint(0, 8, rows).astype(np.int32)
    r2 = build(keys2, vals)
    assert dict(sess.run(r2).rows()) == oracle_sum(keys2, vals)
    assert r2.frame_combiner.dense_keys is None


def test_fold_discovers_dense(mesh):
    rng = np.random.RandomState(17)
    keys = rng.randint(0, 64, 3000).astype(np.int32)
    vals = rng.randint(0, 100, 3000).astype(np.int32)
    sess = mesh_sess(mesh)
    f = bs.Fold(bs.Const(8, keys, vals),
                lambda acc, v: jnp.maximum(acc, v), init=0)
    assert f.dense_keys is None
    res = sess.run(f)
    want = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        want[k] = max(want.get(k, 0), v)
    assert dict(res.rows()) == want
    assert f.dense_keys == int(keys.max()) + 1


def test_map_before_shuffle_probes_transformed_keys(mesh):
    """A map stage rewrites columns between staging and the shuffle,
    so the PRODUCER group must not probe (staged column 0 is not the
    key the combiner sees). The CONSUMER group's staged input is
    post-transform, though — its probe measures the right keys and
    must discover the transformed bound (2*49 + 1 = 99)."""
    rng = np.random.RandomState(19)
    raw = rng.randint(0, 50, 2000).astype(np.int32)
    vals = np.ones(2000, np.int32)
    m = bs.Map(bs.Const(8, raw, vals),
               lambda k, v: (k * 2, v))
    r = bs.Reduce(bs.Prefixed(m, 1), lambda a, b: a + b)
    sess = mesh_sess(mesh)
    res = sess.run(r)
    want = oracle_sum(raw * 2, vals)
    assert dict(res.rows()) == want
    # Consumer-side discovery on the post-map keys: bound covers the
    # TRANSFORMED range, proving the producer (pre-map) never probed.
    assert r.frame_combiner.dense_keys == int(raw.max()) * 2 + 1


def test_declared_out_of_range_still_fails_loudly(mesh):
    """Auto-discovery's retry must not soften the USER-declared
    contract: explicit dense_keys with out-of-range keys raises."""
    from bigslice_tpu.exec.task import TaskError

    keys = np.array([0, 1, 2, 99], dtype=np.int32)
    r = bs.Reduce(bs.Const(4, keys, np.ones(4, np.int32)),
                  lambda a, b: a + b, dense_keys=10)
    assert r.frame_combiner.dense_keys == 10
    sess = mesh_sess(mesh)
    with pytest.raises(Exception) as ei:
        res = sess.run(r)
        list(res.rows())
    assert "dense_keys" in repr(ei.value) or "partitioner" in repr(
        ei.value)
