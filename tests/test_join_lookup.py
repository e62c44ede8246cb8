"""``JoinLookup``, the N:1 inner join, against a plain numpy / dict
reference: on CPU meshes of 1, 4 and 8 devices with fewer shards than
devices, as many and more (waves), with 32- and 64-bit value columns
and keys of one and two columns; on the host tier; the duplicate build
key raising its typed error on both; ``JoinAggregate`` with 64-bit
values on both sides; and TPC-H Q3's cell rehearsed end to end."""

import numpy as np
import pytest

import jax

import bigslice_tpu as bs
from bigslice_tpu.exec.meshexec import MeshExecutor
from bigslice_tpu.exec.session import Session
from bigslice_tpu.exec.task import TaskError
from bigslice_tpu.slicetype import Schema


def _mesh_session(ndev, **kw):
    from jax.sharding import Mesh

    return Session(executor=MeshExecutor(
        Mesh(np.array(jax.devices()[:ndev]), ("shards",)), **kw))


def _keyed(shards, nkeys, cols, dtypes):
    s = bs.Const(shards, *cols, schema=Schema(list(dtypes)))
    return bs.Prefixed(s, nkeys) if nkeys > 1 else s


def _reference(nkeys, probe, build):
    """Row at a time, in Python's own integers."""
    index = {}
    for row in zip(*(c.tolist() for c in build)):
        assert row[:nkeys] not in index
        index[row[:nkeys]] = row[nkeys:]
    return sorted(row + index[row[:nkeys]]
                  for row in zip(*(c.tolist() for c in probe))
                  if row[:nkeys] in index)


def _sides(rng, nkeys, wide, n_probe=900, n_build=120):
    """A build side of unique keys, a third of which no probe row has;
    a probe side a third of whose rows have no build row; values that
    pass 2^31 when ``wide``."""
    space = 3 * n_build // 2
    codes = rng.permutation(space)[:n_build]
    pcodes = rng.integers(space // 3, space + space // 3, n_probe)

    def keys(c):
        # Sparse, some negative; with two columns, codes that share
        # the first differ in the second.
        if nkeys == 1:
            return [(c * 1_000_003 - 7_000_000).astype(np.int32)]
        return [((c // 2) * 1_000_003 - 7_000_000).astype(np.int32),
                (c % 2).astype(np.int32)]

    bk, pk = keys(codes), keys(pcodes)
    vt = np.int64 if wide else np.int32
    top = 1 << (40 if wide else 20)
    pv = [rng.integers(-top, top, n_probe).astype(vt),
          np.arange(n_probe, dtype=np.int32)]
    bv = [rng.integers(-top, top, n_build).astype(vt)]
    return (pk + pv, [np.int32] * nkeys + [vt, np.int32],
            bk + bv, [np.int32] * nkeys + [vt])


def _join_blocks(sess):
    return [op["join"] for op in sess.telemetry_summary()["ops"].values()
            if "join" in op]


def _stayed_on_mesh(sess, groups):
    ex = sess.executor
    assert ex.device_group_count() >= groups
    assert not ex._probation and not ex._spmd_probation
    assert sess.telemetry_summary()["device"]["totals"]["fallbacks"] == 0


@pytest.mark.parametrize("wide", [False, True], ids=["int32", "int64"])
@pytest.mark.parametrize("nkeys", [1, 2])
@pytest.mark.parametrize("shards", ["under", "at", "over"])
@pytest.mark.parametrize("ndev", [1, 4, 8])
def test_joinlookup_matches_the_reference_on_the_mesh(ndev, shards, nkeys,
                                                      wide):
    rng = np.random.default_rng([ndev, len(shards), nkeys, wide])
    probe, ptypes, build, btypes = _sides(rng, nkeys, wide)
    s_probe = {"under": max(1, ndev // 2), "at": ndev,
               "over": 2 * ndev + 1}[shards]
    s_build = max(1, s_probe // 2)
    sess = _mesh_session(ndev)
    try:
        j = bs.JoinLookup(_keyed(s_probe, nkeys, probe, ptypes),
                          _keyed(s_build, nkeys, build, btypes))
        assert j.num_shards == s_probe and j.prefix == nkeys
        assert [ct.dtype for ct in j.schema] == \
            [np.dtype(t) for t in ptypes + btypes[nkeys:]]
        res = sess.run(j)
        got = sorted(res.rows())
        want = _reference(nkeys, probe, build)
        assert 0 < len(want) < len(probe[0])
        assert got == want
        _stayed_on_mesh(sess, 3)
        (block,) = _join_blocks(sess)
        assert block == {
            "waves": -(-s_probe // ndev), "probe_rows": len(probe[0]),
            "build_rows": len(build[0]), "matched_rows": len(want),
            "lowering": "sort", "wide_columns": 2 * wide}
    finally:
        sess.shutdown()


@pytest.mark.parametrize("tier", ["local", "mesh"])
@pytest.mark.parametrize("case", ["empty-build", "empty-probe",
                                  "one-key", "no-match"])
def test_joinlookup_edges(tier, case):
    """An empty side, every probe row on one key, no key in common."""
    rng = np.random.default_rng(7)
    bk = np.arange(0, 60, 3, dtype=np.int32)
    bv = (bk * 11).astype(np.int32)
    pk = rng.integers(0, 60, 300).astype(np.int32)
    if case == "empty-build":
        bk, bv = bk[:0], bv[:0]
    elif case == "empty-probe":
        pk = pk[:0]
    elif case == "one-key":
        pk = np.full(300, 27, np.int32)
    else:
        pk = pk * 3 + 1
    pv = np.arange(len(pk), dtype=np.int32)
    sess = Session() if tier == "local" else _mesh_session(4)
    try:
        got = sorted(sess.run(bs.JoinLookup(
            bs.Const(9, pk, pv), bs.Const(2, bk, bv))).rows())
        assert got == _reference(1, [pk, pv], [bk, bv])
        assert len(got) == (300 if case == "one-key" else 0)
        if tier == "mesh":
            _stayed_on_mesh(sess, 3)
    finally:
        sess.shutdown()


@pytest.mark.parametrize("tier", ["local", "mesh"])
def test_a_duplicate_build_key_raises_the_typed_error(tier):
    bk = np.array([1, 5, 9, 5, 12], np.int32)
    pk = np.arange(20, dtype=np.int32)
    sess = Session() if tier == "local" else _mesh_session(4)
    try:
        j = bs.JoinLookup(bs.Const(9, pk, pk), bs.Const(2, bk, bk))
        with pytest.raises((TaskError, bs.DuplicateBuildKeyError)) as exc:
            sess.run(j).rows()
        cause = getattr(exc.value, "cause", exc.value)
        assert isinstance(cause, bs.DuplicateBuildKeyError)
        assert isinstance(cause, ValueError)
        assert "joinlookup" in cause.op and cause.dups == 1
        assert "joinlookup" in str(cause)
    finally:
        sess.shutdown()


def test_an_object_key_joins_on_the_host_tier():
    """String keys are host columns: the group falls back to the host
    tier under the mesh executor, same answer."""
    names = np.array(["ash", "elm", "oak", "yew"], dtype=object)
    pk = np.array(["oak", "fir", "ash", "oak", "yew", "fir"], dtype=object)
    pv = np.arange(6, dtype=np.int32)
    bv = np.array([10, 20, 30, 40], np.int64)
    want = [("ash", 2, 10), ("oak", 0, 30), ("oak", 3, 30),
            ("yew", 4, 40)]
    for sess in (Session(), _mesh_session(4)):
        try:
            j = bs.JoinLookup(
                bs.Const(3, pk, pv),
                bs.Const(2, names, bv,
                         schema=Schema([object, np.int64])))
            assert sorted(sess.run(j).rows()) == want
            assert not _join_blocks(sess)  # never a device group
        finally:
            sess.shutdown()


def test_two_key_columns_on_the_host_tier():
    rng = np.random.default_rng(3)
    probe, ptypes, build, btypes = _sides(rng, 2, True)
    sess = Session()
    try:
        j = bs.JoinLookup(_keyed(5, 2, probe, ptypes),
                          _keyed(3, 2, build, btypes))
        assert sorted(sess.run(j).rows()) == _reference(2, probe, build)
    finally:
        sess.shutdown()


def test_mismatched_key_types_are_a_typecheck_error():
    from bigslice_tpu.typecheck import TypecheckError

    a = bs.Const(2, np.arange(4, dtype=np.int32), np.arange(4))
    b = bs.Const(2, np.arange(4, dtype=np.float32), np.arange(4))
    with pytest.raises(TypecheckError, match="joinlookup: key column"):
        bs.JoinLookup(a, b)


def test_a_joined_slice_feeds_a_map_a_filter_and_a_reduce():
    """The join group fuses the stages behind it and shuffles their
    output on: lookup -> Map (re-key, widen) -> Reduce, two chained
    joins deep, as TPC-H Q3 does."""
    rng = np.random.default_rng(11)
    n_dim, n_mid, n_fact = 40, 300, 2000
    dim_k = np.arange(n_dim, dtype=np.int32) * 3
    dim_v = rng.integers(0, 5, n_dim).astype(np.int32)
    mid_k = np.arange(n_mid, dtype=np.int32) * 7 + 1
    mid_dim = rng.integers(0, 3 * n_dim, n_mid).astype(np.int32)
    fact_mid = rng.integers(0, 7 * n_mid + 1, n_fact).astype(np.int32)
    fact_v = rng.integers(1 << 28, 1 << 30, n_fact).astype(np.int32)

    def rekey(dim, mid, tag):
        return mid, tag

    def widen(mid, v):
        return mid, v.astype(np.int64) * 100

    def regroup(mid, v, tag):
        return tag, v

    def add(a, b):
        return a + b

    tag_of_mid = {}
    dims = dict(zip(dim_k.tolist(), dim_v.tolist()))
    for mk, md in zip(mid_k.tolist(), mid_dim.tolist()):
        if md in dims and dims[md] != 2:
            tag_of_mid[mk] = dims[md]
    want = {}
    for fm, fv in zip(fact_mid.tolist(), fact_v.tolist()):
        if fm in tag_of_mid:
            t = tag_of_mid[fm]
            want[t] = want.get(t, 0) + fv * 100
    assert max(want.values()) > 1 << 33

    sess = _mesh_session(4)
    try:
        mids = bs.JoinLookup(bs.Const(6, mid_dim, mid_k),
                             bs.Const(2, dim_k, dim_v))
        mids = bs.Map(bs.Filter(mids, lambda d, m, t: t != 2), rekey,
                      out=[np.int32, np.int32])
        facts = bs.Map(bs.Const(9, fact_mid, fact_v), widen,
                       out=[np.int32, np.int64])
        tagged = bs.Map(bs.JoinLookup(facts, mids), regroup,
                        out=[np.int32, np.int64])
        got = dict(sess.run(bs.Reduce(tagged, add)).rows())
        assert got == want
        _stayed_on_mesh(sess, 6)
        blocks = _join_blocks(sess)
        assert sorted(b["wide_columns"] for b in blocks) == [0, 1]
    finally:
        sess.shutdown()


@pytest.mark.parametrize("ndev,shards", [(1, 3), (8, 8)])
def test_joinaggregate_sums_int64_values_on_both_sides(ndev, shards):
    """64-bit value columns through ``JoinAggregate``'s sort lowering
    (the scoped 64-bit mode was tested for Reduce / Map / Filter only)."""
    rng = np.random.default_rng([ndev, shards])
    ak = (rng.integers(0, 50, 700) * 1_000_003).astype(np.int32)
    bk = (rng.integers(25, 80, 500) * 1_000_003).astype(np.int32)
    av = rng.integers(1 << 30, 1 << 40, len(ak)) * np.where(
        ak % 2 == 0, 1, -1)
    bv = rng.integers(1 << 30, 1 << 40, len(bk))

    def add(a, b):
        return a + b

    def sums(k, v):
        out = {}
        for key, val in zip(k.tolist(), v.tolist()):
            out[key] = out.get(key, 0) + val
        return out

    sa, sb = sums(ak, av), sums(bk, bv)
    want = sorted((k, sa[k], sb[k]) for k in sa.keys() & sb.keys())
    wide = Schema([np.int32, np.int64])
    sess = _mesh_session(ndev, auto_dense=False)
    try:
        j = bs.JoinAggregate(bs.Const(shards, ak, av, schema=wide),
                             bs.Const(shards, bk, bv, schema=wide),
                             add, add)
        res = sess.run(j)
        assert [ct.dtype for ct in res.schema] == [
            np.dtype(t) for t in (np.int32, np.int64, np.int64)]
        assert sorted(res.rows()) == want
        assert max(abs(r[1]) for r in want) > 1 << 33
        _stayed_on_mesh(sess, 3)
    finally:
        sess.shutdown()


# -- TPC-H Q3, the deployment that drives all of it ----------------------


@pytest.mark.parametrize("seed", [5, 2147483999])
def test_q3_cell_rehearses_correct_with_both_joins_on_the_mesh(
        capsys, benchmark_modules, compile_cache_as_found, seed):
    """``benchmarks/run.py --workload q3.sf1 --cpu-rehearsal``: the
    pipeline as the cell runs it, every order's revenue equal to the
    ``int64`` reference, both joins on the mesh."""
    import json

    root, run, _ = benchmark_modules
    # A traced window needs its second job: three seconds for the
    # first to return in, on a machine that runs six test workers.
    rc = run.main(["--workload", "q3.sf1", "--seed", str(seed),
                   "--seconds", "3", "--trace", "1",
                   "--cpu-rehearsal"], root=root)
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.splitlines() if ln.strip()]
    assert rc == 0
    last = lines[-1]["rehearsal"]
    assert last["correct"] is True
    assert last["checks"]["wrong_rows"] == {"value": 0, "limit": 0}
    assert last["checks"]["off_mesh"]["value"] == 0
    (setup,) = [ln for ln in lines if ln.get("phase") == "setup"]
    blocks = setup["lowering"]
    assert len(blocks) == 2
    assert sorted(b["wide_columns"] for b in blocks.values()) == [0, 1]
    assert all(b["lowering"] == "sort" and 0 < b["matched_rows"]
               < b["probe_rows"] for b in blocks.values())
    assert last["metrics"]["join_ms_per_job"]["value"] > 0
    assert last["metrics"]["join_probe_mrows_per_s"]["value"] > 0


@pytest.mark.parametrize("seed", [1, 2, 3000000019])
def test_q3_controls_come_out_not_correct(benchmark_modules, seed):
    from benchmarks.harness import discover

    root, _, control = benchmark_modules
    cell = discover.find_cell(root, "q3.sf1", rehearsal=True)
    readings = control.control_readings(cell, seed)
    assert set(readings) == {"row_dropped", "sums_in_int32",
                             "date_inclusive", "segment_ignored",
                             "one_order_a_customer"}
    assert all(v >= 1 for v in readings.values()), readings
