"""64-bit integer columns on the device path: a column declared
``np.int64`` is staged, computed on, shuffled, combined, merged and read
back as 64 bits — against plain numpy ``int64`` references — and an
undeclared 64-bit input that does not fit 32 bits raises instead of
wrapping."""

import numpy as np
import pytest

import jax

import bigslice_tpu as bs
from bigslice_tpu.exec.meshexec import MeshExecutor
from bigslice_tpu.exec.session import Session
from bigslice_tpu.frame.frame import Frame
from bigslice_tpu.slicetype import Schema


def _add(a, b):
    return a + b


def _add_each(a, b):
    # Several value columns arrive as tuples.
    return tuple(x + y for x, y in zip(a, b))


def _mesh_session(ndev, **kw):
    from jax.sharding import Mesh

    return Session(executor=MeshExecutor(
        Mesh(np.array(jax.devices()[:ndev]), ("shards",)), **kw))


def _stayed_on_mesh(sess, groups, lowering=None, wide_columns=None):
    """Nothing left the device path; with ``lowering``, the map-side
    combine's ``combine`` block says which one ran."""
    ex = sess.executor
    assert ex.device_group_count() >= groups
    assert not ex._probation and not ex._spmd_probation
    summary = sess.telemetry_summary()
    assert summary["device"]["totals"]["fallbacks"] == 0
    if lowering is not None:
        (block,) = [op["combine"] for op in summary["ops"].values()
                    if "combine" in op]
        assert block["lowering"] == lowering
        assert block["wide_columns"] == wide_columns
        assert 0 < block["rows_out"] <= block["rows_in"]


def _sums(keys, *vals):
    """Plain reference: each value column summed by key in int64."""
    uniq, inv = np.unique(keys, axis=0, return_inverse=True)
    out = []
    for v in vals:
        s = np.zeros(len(uniq), np.int64)
        np.add.at(s, inv.reshape(-1), v.astype(np.int64))
        out.append(s)
    return uniq, out


def _wide_values(rng, keys):
    """Values whose group sums pass 2^31 and 2^32, and go negative for
    the odd keys."""
    return (rng.integers(1 << 30, 1 << 40, len(keys))
            * np.where(keys % 2 == 0, 1, -1))


@pytest.mark.parametrize("lowering", ["sort", "dense"])
@pytest.mark.parametrize("ndev,waves", [(1, 1), (1, 3), (8, 1), (8, 3)])
def test_reduce_add_over_int64_values(ndev, waves, lowering):
    rng = np.random.default_rng([ndev, waves, lowering == "dense"])
    n = ndev * waves * 96
    if lowering == "dense":
        keys = rng.integers(0, 11, n).astype(np.int32)
    else:  # sparse: the dense probe must not engage
        keys = (rng.integers(0, 40, n) * 1_000_003).astype(np.int32)
    vals = _wide_values(rng, keys)
    sess = _mesh_session(ndev, auto_dense=False)
    try:
        res = sess.run(bs.Reduce(
            bs.Const(ndev * waves, keys, vals,
                     schema=Schema([np.int32, np.int64])),
            _add, dense_keys=11 if lowering == "dense" else None))
        assert res.schema == Schema([np.int32, np.int64])
        got = dict(res.rows())
        # One shard on one device has no shuffle, so no map-side
        # combine to report.
        _stayed_on_mesh(sess, 2, *((lowering, 1) if ndev * waves > 1
                                   else ()))
    finally:
        sess.shutdown()
    uniq, (want,) = _sums(keys, vals)
    assert got == dict(zip(uniq.tolist(), want.tolist()))
    assert max(abs(w) for w in want.tolist()) > 1 << 32
    assert min(want.tolist()) < 0


@pytest.mark.parametrize("ndev,shards", [(1, 1), (1, 4), (8, 8), (8, 24)])
def test_map_from_int32_to_int64(ndev, shards):
    """A Map whose output is wider than its inputs, declared with
    ``out=``: every product passes 2^32 exactly."""
    rng = np.random.default_rng([ndev, shards])
    n = shards * 50
    a = rng.integers(1, 1 << 30, n).astype(np.int32)
    b = rng.integers(-(1 << 30), 1 << 30, n).astype(np.int32)

    def widen(x, y):
        return x, x.astype(np.int64) * y * 7

    sess = _mesh_session(ndev)
    try:
        res = sess.run(bs.Map(bs.Const(shards, a, b), widen,
                              out=[np.int32, np.int64]))
        assert res.schema == Schema([np.int32, np.int64])
        got = sorted(res.rows())
        _stayed_on_mesh(sess, 1)
    finally:
        sess.shutdown()
    want = sorted(zip(a.tolist(),
                      (a.astype(np.int64) * b * 7).tolist()))
    assert got == want
    assert any(abs(v) > 1 << 32 for _, v in want)


@pytest.mark.parametrize("dtype", [np.int64, np.uint64])
def test_const_of_64_bit_column_round_trips(sess, dtype):
    """``sess``: the local executor and the 8-device mesh."""
    top = np.iinfo(dtype).max
    vals = np.array([0, 1, 1 << 31, 1 << 32, 1 << 40, top, top - 1, 5],
                    dtype)
    if dtype == np.int64:
        vals[3] = np.iinfo(dtype).min
    keys = np.arange(len(vals), dtype=np.int32)
    res = sess.run(bs.Const(3, keys, vals,
                            schema=Schema([np.int32, dtype])))
    assert res.schema[1].dtype == np.dtype(dtype)
    assert sorted(res.rows()) == sorted(zip(keys.tolist(),
                                            vals.tolist()))


@pytest.mark.parametrize("lowering", ["sort", "dense"])
@pytest.mark.parametrize("ndev,shards", [(1, 1), (1, 5), (8, 8), (8, 24)])
def test_two_column_key_prefix_with_six_value_columns(ndev, shards,
                                                      lowering):
    """The shape of TPC-H Q1: Filter, a widening Map, and a Reduce whose
    key is a prefix of two small-domain columns over six value
    columns, four of them 64-bit — through the sort pipeline, and as
    one dense code when the dictionaries' sizes are declared."""
    rng = np.random.default_rng([ndev, shards, 1])
    n = shards * 80
    rf = rng.integers(0, 3, n).astype(np.int32)
    ls = rng.integers(0, 2, n).astype(np.int32)
    qty = rng.integers(1, 51, n).astype(np.int32)
    price = rng.integers(90_000, 10_500_000, n).astype(np.int32)
    disc = rng.integers(0, 11, n).astype(np.int32)
    tax = rng.integers(0, 9, n).astype(np.int32)
    day = rng.integers(0, 100, n).astype(np.int32)

    def keep(rf, ls, qty, price, disc, tax, day):
        return day <= 90

    def widen(rf, ls, qty, price, disc, tax, day):
        p = price.astype(np.int64)
        dp = p * (100 - disc)
        return (rf, ls, qty.astype(np.int64), p, dp, dp * (100 + tax),
                disc, np.int32(1))

    out = [np.int32, np.int32, np.int64, np.int64, np.int64, np.int64,
           np.int32, np.int32]
    sess = _mesh_session(ndev)
    try:
        m = bs.Map(bs.Filter(
            bs.Const(shards, rf, ls, qty, price, disc, tax, day), keep),
            widen, out=out)
        res = sess.run(bs.Reduce(
            bs.Prefixed(m, 2), _add_each,
            dense_keys=(3, 2) if lowering == "dense" else None))
        assert res.schema == Schema(out, prefix=2)
        got = {r[:2]: r[2:] for r in res.rows()}
        _stayed_on_mesh(sess, 2, *((lowering, 4) if shards > 1 else ()))
    finally:
        sess.shutdown()
    k = day <= 90
    p = price[k].astype(np.int64)
    dp = p * (100 - disc[k])
    uniq, want = _sums(np.stack([rf[k], ls[k]], 1), qty[k], p, dp,
                       dp * (100 + tax[k]), disc[k], np.ones(k.sum()))
    assert got == {tuple(u): tuple(w[i] for w in want)
                   for i, u in enumerate(uniq.tolist())}
    assert max(want[3].tolist()) > 1 << 32


def test_reduce_over_int64_key(sess):
    """A 64-bit KEY: keys that differ only above bit 31 stay apart."""
    keys = (np.arange(40, dtype=np.int64) % 10) << 33
    vals = np.arange(40, dtype=np.int32)
    res = sess.run(bs.Reduce(
        bs.Const(4, keys, vals, schema=Schema([np.int64, np.int32])),
        _add))
    uniq, (want,) = _sums(keys, vals)
    assert dict(res.rows()) == dict(zip(uniq.tolist(), want.tolist()))


# -- the narrowing check ------------------------------------------------


@pytest.mark.parametrize("col", [
    np.array([1, 2, 1 << 31]),
    np.array([-(1 << 31) - 1, 0]),
    np.array([1 << 32], np.uint64),
    [7, 1 << 40],
], ids=["int64-high", "int64-low", "uint64", "list"])
def test_undeclared_64_bit_input_that_does_not_fit_raises(col):
    with pytest.raises(OverflowError, match="column 1"):
        Frame([np.zeros(len(col), np.int32), col])
    with pytest.raises(OverflowError, match="column 1"):
        bs.Const(1, np.zeros(len(col), np.int32), col)


@pytest.mark.parametrize("col,want", [
    (np.arange(5), np.int32),
    (np.array([-(1 << 31), (1 << 31) - 1]), np.int32),
    (np.array([0, (1 << 32) - 1], np.uint64), np.uint32),
    ([1, 2, 3], np.int32),
], ids=["arange", "int32-limits", "uint64", "list"])
def test_undeclared_64_bit_input_that_fits_narrows(col, want):
    f = Frame([col])
    assert f.cols[0].dtype == want and f.schema[0].dtype == want
    assert f.cols[0].tolist() == np.asarray(col).tolist()


def test_declared_int32_column_checks_too():
    with pytest.raises(OverflowError, match="column 0"):
        Frame([np.array([1 << 40])], Schema([np.int32]))
    f = Frame([np.arange(3, dtype=np.int32)], Schema([np.int64]))
    assert f.cols[0].dtype == np.int64


# -- TPC-H Q1, the deployment that drives all of it ----------------------


@pytest.mark.parametrize("seed", [5, 2147483999])
def test_q1_cell_rehearses_correct_with_the_dense_table(
        capsys, benchmark_modules, compile_cache_as_found, seed):
    """``benchmarks/run.py --workload q1.sf1 --cpu-rehearsal``: the
    pipeline as the cell runs it, every sum equal to the ``int64``
    reference, on the mesh, through the dense table."""
    import json

    root, run, _ = benchmark_modules
    rc = run.main(["--workload", "q1.sf1", "--seed", str(seed),
                   "--seconds", "0.5", "--trace", "1",
                   "--cpu-rehearsal"], root=root)
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.splitlines() if ln.strip()]
    assert rc == 0
    last = lines[-1]["rehearsal"]
    assert last["correct"] is True
    assert last["checks"]["wrong_rows"] == {"value": 0, "limit": 0}
    assert last["checks"]["off_mesh"]["value"] == 0
    (setup,) = [ln for ln in lines if ln.get("phase") == "setup"]
    (block,) = setup["lowering"].values()
    assert (block["lowering"], block["wide_columns"]) == ("dense", 5)
    assert 0 < last["metrics"]["map_combine_keep_share"]["value"] < 1
    assert last["metrics"]["reduce_side_ms_per_job"]["value"] > 0


@pytest.mark.parametrize("seed", [1, 2, 3000000019])
def test_q1_controls_come_out_not_correct(benchmark_modules, seed):
    """A row left out, sums carried in 32 bits (what the parent
    computed), ``<`` for ``<=``: each reads at least one wrong row."""
    from benchmarks.harness import discover

    root, _, control = benchmark_modules
    cell = discover.find_cell(root, "q1.sf1", rehearsal=True)
    readings = control.control_readings(cell, seed)
    assert set(readings) == {"row_dropped", "sums_in_int32",
                             "cutoff_exclusive"}
    assert all(v >= 1 for v in readings.values()), readings
