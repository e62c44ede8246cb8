"""Hierarchical 2-D (DCN × ICI) shuffle: the two-stage exchange must
route every row to the same shard the flat 1-D shuffle picks, on the
same 8 virtual devices (2×4 grid vs flat)."""

import numpy as np
import pytest

import jax

from bigslice_tpu.parallel import hier, shuffle as shuffle_mod


@pytest.fixture(scope="module")
def meshes():
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:8])
    flat = Mesh(devs, ("shards",))
    grid = Mesh(devs.reshape(2, 4), ("dcn", "ici"))
    return flat, grid


def _shard_rows(cols, counts, capacity, nshards):
    chunks = shuffle_mod.unshard_columns(cols, counts, capacity)
    return [
        sorted(zip(*(np.asarray(c[s]).tolist() for c in chunks)))
        for s in range(nshards)
    ]


def test_hier_matches_flat_shuffle(meshes):
    flat, grid = meshes
    rng = np.random.RandomState(7)
    cap = 256
    per = 100
    n = 8
    kc = [rng.randint(0, 1000, per).astype(np.int32) for _ in range(n)]
    vc = [np.arange(per, dtype=np.int32) + 1000 * s for s in range(n)]

    cols_f, counts_f = shuffle_mod.shard_columns(
        flat, [kc, vc], [per] * n, cap
    )
    sh_f = shuffle_mod.MeshShuffle(flat, ncols=2, nkeys=1, capacity=cap)
    out_f, cnt_f, ov_f = sh_f(cols_f, counts_f)
    assert int(ov_f) == 0

    cols_g, counts_g = shuffle_mod.shard_columns(
        grid, [kc, vc], [per] * n, cap
    )
    sh_g = hier.HierMeshShuffle(grid, ncols=2, nkeys=1, capacity=cap)
    out_g, cnt_g, ov_g = sh_g(cols_g, counts_g)
    assert int(ov_g) == 0

    np.testing.assert_array_equal(np.asarray(cnt_f), np.asarray(cnt_g))
    rows_f = _shard_rows(out_f, cnt_f, sh_f.out_capacity, n)
    rows_g = _shard_rows(out_g, cnt_g, sh_g.out_capacity, n)
    assert rows_f == rows_g
    assert sum(len(r) for r in rows_g) == n * per


def test_hier_overflow_detected(meshes):
    _, grid = meshes
    cap = 16
    per = 16
    n = 8
    kc = [np.full(per, 3, np.int32) for _ in range(n)]
    cols, counts = shuffle_mod.shard_columns(grid, [kc], [per] * n, cap)
    sh = hier.HierMeshShuffle(grid, ncols=1, nkeys=1, capacity=cap)
    _, _, ov = sh(cols, counts)
    assert int(ov) > 0


def test_hier_custom_partitioner(meshes):
    _, grid = meshes
    cap = 128
    per = 32
    n = 8
    keys = [np.arange(per, dtype=np.int32) + s * per for s in range(n)]
    cols, counts = shuffle_mod.shard_columns(grid, [keys], [per] * n,
                                             cap)
    sh = hier.HierMeshShuffle(
        grid, ncols=1, nkeys=1, capacity=cap,
        partition_fn=lambda k: (k % np.int32(3)).astype(np.int32),
    )
    out, cnt, ov = sh(cols, counts)
    assert int(ov) == 0
    counts_host = np.asarray(cnt)
    assert counts_host[:3].sum() == n * per
    assert all(c == 0 for c in counts_host[3:])


def test_hier_reduce_matches_oracle_and_flat(meshes):
    """HierMeshReduceByKey: combine → two-stage shuffle → combine over
    the 2-D grid equals both the Python oracle and the flat
    MeshReduceByKey's per-shard results."""
    flat, grid = meshes
    rng = np.random.RandomState(12)
    cap = 512
    per = 150
    n = 8
    kc = [rng.randint(0, 41, per).astype(np.int32) for _ in range(n)]
    vc = [rng.randint(0, 10, per).astype(np.int32) for _ in range(n)]

    def add(a, b):
        return a + b

    cols_g, counts_g = shuffle_mod.shard_columns(
        grid, [kc, vc], [per] * n, cap
    )
    red_g = hier.HierMeshReduceByKey(grid, nkeys=1, nvals=1,
                                     capacity=cap, combine_fn=add)
    kg, vg, cnt_g, ov_g = red_g([cols_g[0]], [cols_g[1]], counts_g)
    assert int(ov_g) == 0

    cols_f, counts_f = shuffle_mod.shard_columns(
        flat, [kc, vc], [per] * n, cap
    )
    red_f = shuffle_mod.MeshReduceByKey(flat, nkeys=1, nvals=1,
                                        capacity=cap, combine_fn=add)
    kf, vf, cnt_f, ov_f = red_f([cols_f[0]], [cols_f[1]], counts_f)
    assert int(ov_f) == 0

    g_rows = _shard_rows(kg + vg, cnt_g, red_g.out_capacity, n)
    f_rows = _shard_rows(kf + vf, cnt_f, red_f.out_capacity, n)
    assert g_rows == f_rows

    oracle = {}
    for k, v in zip(np.concatenate(kc).tolist(),
                    np.concatenate(vc).tolist()):
        oracle[k] = oracle.get(k, 0) + v
    got = {}
    for shard in g_rows:
        for k, v in shard:
            assert k not in got
            got[k] = v
    assert got == oracle


def test_hier_reduce_fused_matches_unfused_and_oracle(meshes):
    """The fused hier reduce (map-side combine folded into stage 1's
    routing sort by reusing the flat make_combine_shuffle_fn in waved
    mode) produces the same per-shard row sets as the unfused path,
    the flat reduce, and the Python oracle; ``fused=False`` is kept as
    that reference."""
    flat, grid = meshes
    rng = np.random.RandomState(21)
    cap = 512
    per = 140
    n = 8
    kc = [rng.randint(0, 37, per).astype(np.int32) for _ in range(n)]
    vc = [rng.randint(0, 9, per).astype(np.int32) for _ in range(n)]

    def add(a, b):
        return a + b

    def run(fused):
        cols_g, counts_g = shuffle_mod.shard_columns(
            grid, [kc, vc], [per] * n, cap
        )
        red = hier.HierMeshReduceByKey(
            grid, nkeys=1, nvals=1, capacity=cap, combine_fn=add,
            fused=fused,
        )
        assert red.fused == fused
        kg, vg, cnt, ov = red([cols_g[0]], [cols_g[1]], counts_g)
        assert int(ov) == 0
        return _shard_rows(kg + vg, cnt, red.out_capacity, n)

    fused_rows = run(True)
    unfused_rows = run(False)
    assert fused_rows == unfused_rows

    cols_f, counts_f = shuffle_mod.shard_columns(
        flat, [kc, vc], [per] * n, cap
    )
    red_f = shuffle_mod.MeshReduceByKey(flat, nkeys=1, nvals=1,
                                        capacity=cap, combine_fn=add)
    kf, vf, cnt_f, ov_f = red_f([cols_f[0]], [cols_f[1]], counts_f)
    assert int(ov_f) == 0
    assert fused_rows == _shard_rows(kf + vf, cnt_f,
                                     red_f.out_capacity, n)

    oracle = {}
    for k, v in zip(np.concatenate(kc).tolist(),
                    np.concatenate(vc).tolist()):
        oracle[k] = oracle.get(k, 0) + v
    got = dict(kv for shard in fused_rows for kv in shard)
    assert got == oracle


def test_hier_reduce_fused_donate_consumes_inputs(meshes):
    """donate=True on the hier reduce consumes staged inputs when the
    backend aliases them — wave-streaming HBM reuse at kernel level."""
    from bigslice_tpu.parallel.jitutil import donation_supported

    if not donation_supported():
        import pytest

        pytest.skip("backend does not implement buffer donation")
    _flat, grid = meshes
    rng = np.random.RandomState(4)
    cap = 256
    per = 100
    n = 8
    kc = [rng.randint(0, 19, per).astype(np.int32) for _ in range(n)]
    vc = [np.ones(per, np.int32) for _ in range(n)]
    cols_g, counts_g = shuffle_mod.shard_columns(
        grid, [kc, vc], [per] * n, cap
    )
    red = hier.HierMeshReduceByKey(
        grid, nkeys=1, nvals=1, capacity=cap,
        combine_fn=lambda a, b: a + b, fused=True, donate=True,
    )
    kg, vg, cnt, ov = red([cols_g[0]], [cols_g[1]], counts_g)
    assert int(ov) == 0
    oracle = {}
    for k in np.concatenate(kc).tolist():
        oracle[k] = oracle.get(k, 0) + 1
    got = dict(
        kv for shard in _shard_rows(kg + vg, cnt, red.out_capacity, n)
        for kv in shard
    )
    assert got == oracle
