"""Device/host keyed-reduction kernel tests (mirrors exec/combiner_test.go
and sortio/sort_test.go roles)."""

import numpy as np
import pytest
import jax.numpy as jnp

from bigslice_tpu.parallel import segment


def _dict_oracle(keys, vals, fn):
    acc = {}
    for k, v in zip(keys, vals):
        acc[k] = fn(acc[k], v) if k in acc else v
    return acc


def test_device_reduce_by_key_sum():
    rng = np.random.RandomState(0)
    keys = rng.randint(0, 50, size=1000).astype(np.int32)
    vals = rng.randint(0, 100, size=1000).astype(np.int32)
    red = segment.DeviceReduceByKey(lambda a, b: a + b, nkeys=1, nvals=1)
    (ok,), (ov,) = red([keys], [vals], len(keys))
    oracle = _dict_oracle(keys.tolist(), vals.tolist(), lambda a, b: a + b)
    assert len(ok) == len(oracle)
    np.testing.assert_array_equal(ok, np.sort(np.asarray(list(oracle), np.int32)))
    for k, v in zip(ok.tolist(), ov.tolist()):
        assert oracle[k] == v


def test_device_reduce_by_key_max_multikey():
    rng = np.random.RandomState(1)
    k1 = rng.randint(0, 10, size=500).astype(np.int32)
    k2 = rng.randint(0, 10, size=500).astype(np.int32)
    v = rng.rand(500).astype(np.float32)
    red = segment.DeviceReduceByKey(
        lambda a, b: jnp.maximum(a, b), nkeys=2, nvals=1
    )
    (ok1, ok2), (ov,) = red([k1, k2], [v], 500)
    oracle = _dict_oracle(
        list(zip(k1.tolist(), k2.tolist())), v.tolist(), max
    )
    assert len(ok1) == len(oracle)
    for a, b, val in zip(ok1.tolist(), ok2.tolist(), ov.tolist()):
        assert abs(oracle[(a, b)] - val) < 1e-6


def test_device_reduce_ragged_sizes():
    """Bucket padding must not contaminate results at any size."""
    red = segment.DeviceReduceByKey(lambda a, b: a + b, nkeys=1, nvals=1)
    for n in (1, 2, 3, 7, 8, 9, 100):
        keys = (np.arange(n) % 3).astype(np.int32)
        vals = np.ones(n, dtype=np.int32)
        (ok,), (ov,) = red([keys], [vals], n)
        oracle = _dict_oracle(keys.tolist(), vals.tolist(), lambda a, b: a + b)
        assert dict(zip(ok.tolist(), ov.tolist())) == oracle


def test_device_reduce_multival():
    keys = np.array([1, 2, 1, 2, 1], np.int32)
    a = np.array([1, 2, 3, 4, 5], np.int32)
    b = np.array([10.0, 20.0, 30.0, 40.0, 50.0], np.float32)

    def fn(x, y):
        return (x[0] + y[0], jnp.minimum(x[1], y[1]))

    red = segment.DeviceReduceByKey(fn, nkeys=1, nvals=2)
    (ok,), (oa, ob) = red([keys], [a, b], 5)
    out = dict(zip(ok.tolist(), zip(oa.tolist(), ob.tolist())))
    assert out == {1: (9, 10.0), 2: (6, 20.0)}


def test_host_reduce_by_key():
    keys = [np.array(["a", "b", "a", "c"], dtype=object)]
    vals = [np.array([1, 2, 3, 4], np.int32)]
    ok, ov = segment.host_reduce_by_key(keys, vals, lambda a, b: a + b, 1)
    assert dict(zip(ok[0].tolist(), ov[0].tolist())) == {
        "a": 4, "b": 2, "c": 4
    }


def test_canonical_combine_multi():
    cfn = segment.canonical_combine(lambda a, b: (a[0] + b[0], a[1] * b[1]), 2)
    assert cfn((1, 2), (3, 4)) == (4, 8)
    cfn1 = segment.canonical_combine(lambda a, b: a + b, 1)
    assert cfn1((5,), (6,)) == (11,)


class TestDeviceFold:
    def _oracle(self, keys, vals, fn, init):
        acc = {}
        for k, v in zip(keys.tolist(), vals.tolist()):
            acc[k] = fn(acc.get(k, init), v)
        return acc

    def test_sorted_fold_matches_dict_oracle(self):
        from bigslice_tpu.parallel import segment

        rng = np.random.RandomState(5)
        keys = rng.randint(0, 20, 500).astype(np.int32)
        vals = rng.randint(1, 6, 500).astype(np.int32)
        # Non-associative fold: acc*2 + v (order-sensitive).
        kern = segment.DeviceSortedFold(
            lambda acc, v: acc * 2 + v, 1, 1, 0, np.dtype(np.int32)
        )
        (k_out,), (a_out,) = kern([keys], [vals], len(keys))
        oracle = self._oracle(keys, vals, lambda a, v: a * 2 + v, 0)
        got = dict(zip(k_out.tolist(), a_out.tolist()))
        # int32 overflow wraps identically in numpy and jax; compare mod 2^32
        assert got.keys() == oracle.keys()
        for k in got:
            assert got[k] == np.int32(oracle[k] & 0xFFFFFFFF).item() or \
                got[k] == np.int32(oracle[k]).item()

    def test_fold_slice_device_tier(self):
        """Fold over a traceable fn classifies device and matches the
        host dict tier."""
        import bigslice_tpu as bs
        from bigslice_tpu.exec.session import Session

        keys = (np.arange(120, dtype=np.int32) * 7) % 10
        vals = np.arange(120, dtype=np.float32)

        def fmax(acc, v):
            import jax.numpy as jnp

            return jnp.maximum(acc, v)

        f = bs.Fold(bs.Const(4, keys, vals), fmax, init=-1.0,
                    out_value=np.float32)
        assert f.device
        got = dict(Session().run(f).rows())
        oracle = {}
        for k, v in zip(keys.tolist(), vals.tolist()):
            oracle[k] = max(oracle.get(k, -1.0), v)
        assert got == oracle

    def test_fold_host_tier_for_callable_init(self):
        import bigslice_tpu as bs
        from bigslice_tpu.exec.session import Session

        keys = np.arange(20, dtype=np.int32) % 3
        vals = np.ones(20, np.int32)
        f = bs.Fold(bs.Const(2, keys, vals),
                    lambda acc, v: acc + [v], init=list,
                    out_value=bs.ColType(np.dtype(object), tag="list"))
        assert not f.device
        got = dict(Session().run(f).rows())
        assert {k: len(v) for k, v in got.items()} == {0: 7, 1: 7, 2: 6}

    def test_fold_on_mesh(self):
        """Device fold runs as an SPMD stage on the mesh executor."""
        import jax

        import bigslice_tpu as bs
        from jax.sharding import Mesh
        from bigslice_tpu.exec.meshexec import MeshExecutor
        from bigslice_tpu.exec.session import Session

        mesh = Mesh(np.array(jax.devices()[:8]), ("shards",))
        sess = Session(executor=MeshExecutor(mesh))
        keys = (np.arange(160, dtype=np.int32) * 3) % 12
        vals = np.ones(160, np.int32)
        f = bs.Fold(bs.Const(8, keys, vals), lambda acc, v: acc + v,
                    init=0, out_value=np.int32)
        assert f.device
        got = dict(sess.run(f).rows())
        oracle = {}
        for k in keys.tolist():
            oracle[k] = oracle.get(k, 0) + 1
        assert got == oracle
        assert sess.executor.device_group_count() >= 2


def _mask_case(name, n, rng):
    if name == "empty":
        return np.zeros(n, bool)
    if name == "full":
        return np.ones(n, bool)
    if name == "alternating":
        return np.arange(n) % 2 == 1
    if name == "last_row_only":
        return np.arange(n) == n - 1
    assert name == "random"
    return rng.random(n) < 0.4


@pytest.mark.parametrize("payload", ["int32", "float32", "int8+vector"])
@pytest.mark.parametrize("mask_case", ["empty", "full", "alternating",
                                       "last_row_only", "random"])
def test_compact_by_mask_matches_numpy(mask_case, payload):
    """Count, the survivors first in their order, a zero tail — with
    garbage in the masked rows, scalar and vector columns."""
    import jax

    n = 96
    rng = np.random.default_rng(len(mask_case) * 7 + len(payload))
    mask = _mask_case(mask_case, n, rng)
    if payload == "int8+vector":
        cols = [rng.integers(-100, 100, n).astype(np.int8),
                rng.normal(size=(n, 3)).astype(np.float32),
                rng.integers(1, 1 << 30, n).astype(np.int32)]
    else:
        dt = np.dtype(payload)
        cols = [(rng.integers(1, 1 << 20, n)).astype(dt),
                (rng.integers(-50, 50, n) - 0.5).astype(dt)]
    count, packed = jax.jit(segment.compact_by_mask)(mask, tuple(cols))
    k = int(mask.sum())
    assert int(count) == k and count.dtype == np.int32
    assert len(packed) == len(cols)
    for c, p in zip(cols, packed):
        p = np.asarray(p)
        assert p.shape == c.shape and p.dtype == c.dtype
        np.testing.assert_array_equal(p[:k], c[mask])
        assert not p[k:].any()


def test_group_by_lane_packs_lanes_in_order_like_numpy():
    """compact_by_mask's body with lanes: selected rows grouped by lane
    ascending, order kept inside a lane, zero tail."""
    import jax

    n = 200
    rng = np.random.default_rng(31)
    mask = rng.random(n) < 0.6
    lane = rng.integers(0, 5, n).astype(np.int32)
    lane[~mask] = rng.integers(-9, 99, int((~mask).sum()))  # garbage
    col = rng.integers(1, 1 << 20, n).astype(np.int32)
    count, s_lane, (s_col,) = jax.jit(segment.group_by_lane)(
        mask, lane, (col,))
    order = np.argsort(lane[mask], kind="stable")
    k = int(mask.sum())
    assert int(count) == k
    np.testing.assert_array_equal(np.asarray(s_lane)[:k],
                                  lane[mask][order])
    np.testing.assert_array_equal(np.asarray(s_col)[:k],
                                  col[mask][order])
    assert not np.asarray(s_lane)[k:].any()
    assert not np.asarray(s_col)[k:].any()
