"""Pallas native-tier kernel tests (interpret mode on CPU; the same
kernels compile via Mosaic on TPU)."""

import numpy as np
import pytest

from bigslice_tpu.frame import ops as frame_ops
from bigslice_tpu.parallel import pallas_kernels as pk

# Every test here runs the kernels through the interpreter on CPU. A
# jax build whose interpret mode can't execute a trivial kernel (the
# capability probe builds and runs one) would fail ALL of them for one
# environmental reason — skip with a clean signal instead of carrying
# reds through tier-1.
pytestmark = pytest.mark.skipif(
    not pk.interpret_capable(),
    reason="pallas interpret mode cannot execute kernels on this "
           "jax build (pk.interpret_capable() probe failed)",
)


@pytest.mark.parametrize("n", [1, 7, 128, 1000, 4096, 5000])
@pytest.mark.parametrize("nparts", [2, 8, 37])
def test_hash_partition_matches_reference(n, nparts):
    rng = np.random.RandomState(n + nparts)
    keys = rng.randint(-(2**31), 2**31 - 1, n).astype(np.int32)
    ids, counts = pk.hash_partition(keys, nparts, seed=0)
    ids = np.asarray(ids)
    counts = np.asarray(counts)
    ref = (
        frame_ops.hash_device_column(keys, 0) % np.uint32(nparts)
    ).astype(np.int32)
    np.testing.assert_array_equal(ids, ref)
    np.testing.assert_array_equal(
        counts, np.bincount(ref, minlength=nparts)
    )


def test_hash_partition_seed_changes_routing():
    keys = np.arange(512, dtype=np.int32)
    ids0, _ = pk.hash_partition(keys, 8, seed=0)
    ids1, _ = pk.hash_partition(keys, 8, seed=1)
    assert not np.array_equal(np.asarray(ids0), np.asarray(ids1))


def test_hash_partition_many_partitions():
    # More partitions than one lane group (crosses the 128-lane histogram
    # boundary).
    keys = np.arange(2048, dtype=np.int32)
    ids, counts = pk.hash_partition(keys, 200, seed=3)
    ref = (
        frame_ops.hash_device_column(keys, 3) % np.uint32(200)
    ).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(ids), ref)
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(ref, minlength=200)
    )


@pytest.mark.parametrize("dtype", [np.int32, np.uint32, np.float32])
def test_hash_partition_dtypes(dtype):
    rng = np.random.RandomState(9)
    if dtype == np.float32:
        keys = (rng.randn(1500) * 100).astype(np.float32)
        keys[::97] = 0.0
        keys[1::97] = -0.0  # -0.0 must route like +0.0
    else:
        keys = rng.randint(0, 2**31 - 1, 1500).astype(dtype)
    ids, counts = pk.hash_partition(keys, 11, seed=2)
    ref = (
        frame_ops.hash_device_column(keys, 2) % np.uint32(11)
    ).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(ids), ref)
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(ref, minlength=11)
    )


def test_hash_partition_multikey():
    rng = np.random.RandomState(4)
    k1 = rng.randint(0, 1000, 2000).astype(np.int32)
    k2 = (rng.randn(2000)).astype(np.float32)
    ids, counts = pk.hash_partition([k1, k2], 13, seed=5)
    h = frame_ops.hash_device_column(k1, 5)
    h = frame_ops.combine_hashes(
        h, frame_ops.hash_device_column(k2, 5)
    )
    ref = (h % np.uint32(13)).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(ids), ref)
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(ref, minlength=13)
    )


def test_hash_partition_mask_routes_and_excludes():
    rng = np.random.RandomState(6)
    keys = rng.randint(0, 10000, 1000).astype(np.int32)
    valid = rng.rand(1000) < 0.6
    ids, counts = pk.hash_partition(keys, 7, seed=1, valid=valid)
    ids = np.asarray(ids)
    ref = (
        frame_ops.hash_device_column(keys, 1) % np.uint32(7)
    ).astype(np.int32)
    np.testing.assert_array_equal(ids[valid], ref[valid])
    assert (ids[~valid] == 7).all()  # drop lane
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(ref[valid], minlength=7)
    )


def test_shuffle_pallas_path_matches_xla_path():
    """The full shuffle body with use_pallas on/off produces identical
    routing, counts, and payloads (interpret mode here; Mosaic on TPU
    via the bench gate)."""
    import jax
    import jax.numpy as jnp

    from bigslice_tpu.parallel.shuffle import make_shuffle_fn

    rng = np.random.RandomState(12)
    cap, nshards = 256, 4
    keys = rng.randint(0, 5000, cap).astype(np.int32)
    vals = rng.randint(0, 100, cap).astype(np.int32)
    n = 200

    outs = []
    for use_pallas in (False, True):
        # The kernel-histogram (with_counts → kernel_counts)
        # plumbing's value-parity coverage.
        body = make_shuffle_fn(nshards, 1, cap, axis="s",
                               use_pallas=use_pallas)

        def run(n_, keys_, vals_):
            c, o, out_cols = body(n_[0], keys_, vals_)
            return c.reshape(1), o, tuple(out_cols)

        mesh = jax.sharding.Mesh(np.array(jax.devices()[:nshards]),
                                 ("s",))
        from bigslice_tpu.parallel.meshutil import get_shard_map
        from jax.sharding import PartitionSpec as P

        sm = get_shard_map()
        # check_rep=False: pallas_call has no replication rule, the
        # same contract every executor shard_map call site honors.
        prog = jax.jit(sm(
            run, mesh=mesh,
            in_specs=(P("s"), P("s"), P("s")),
            out_specs=(P("s"), P(), tuple([P("s"), P("s")])),
            check_rep=False,
        ))
        from jax.sharding import NamedSharding

        sh = NamedSharding(mesh, P("s"))
        out_counts, ov, cols = prog(
            jax.device_put(np.full(nshards, n, np.int32), sh),
            jax.device_put(np.tile(keys, nshards), sh),
            jax.device_put(np.tile(vals, nshards), sh),
        )
        outs.append((np.asarray(out_counts), int(ov),
                     [np.asarray(c) for c in cols]))
    (c0, o0, cols0), (c1, o1, cols1) = outs
    np.testing.assert_array_equal(c0, c1)
    assert o0 == o1
    for a, b in zip(cols0, cols1):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------- hash-aggregate kernel


def _agg_rows(present, keys, vals):
    """Sorted (key..., val...) rows of the occupied slots — the ONLY
    valid cross-backend comparison: slot ASSIGNMENT differs between
    the sequential claim cascade and the batched scatter-min cascade
    (first-come-wins resolves differently), but per-region key sets
    and per-key combined values must be identical."""
    p = np.asarray(present)
    cols = [np.asarray(c)[p] for c in list(keys) + list(vals)]
    return sorted(zip(*[c.tolist() for c in cols]))


def _agg_regions(present, keys, part_of, nparts, R):
    """slot//R of every occupied slot must equal the partition id of
    the key resident there (the destination-contiguity invariant the
    shuffle lowering routes by)."""
    p = np.asarray(present)
    slots = np.nonzero(p)[0]
    key_rows = [np.asarray(k)[p] for k in keys]
    want = part_of(key_rows)
    np.testing.assert_array_equal(slots // R, want)


@pytest.mark.parametrize("case", ["int1k", "uint", "f32vals",
                                  "multikey", "maxmin"])
def test_hash_aggregate_kernel_matches_xla(case):
    """Bit-parity of the Mosaic claim-cascade kernel (interpret mode
    here) against the hashagg.py XLA scatter path: same occupied key
    sets, same combined values, same overflow verdict, same region
    invariant. Key cardinality is held under T/4 so neither cascade
    overflows (overflow runs are legitimately divergent — the executor
    discards both and retries on sort)."""
    import jax.numpy as jnp

    from bigslice_tpu.parallel import hashagg

    rng = np.random.RandomState(hash(case) % (2**31))
    nparts, R = 4, 256
    T = nparts * R
    n = 3000
    distinct = T // 4
    k1 = rng.randint(0, distinct, n).astype(np.int32)
    keys = [k1]
    ops = ["add"]
    vals = [rng.randint(1, 100, n).astype(np.int32)]
    if case == "uint":
        keys = [k1.view(np.uint32)]
        vals = [vals[0].view(np.uint32)]
    elif case == "f32vals":
        v = rng.randn(n).astype(np.float32)
        v[::53] = -0.0  # sign-bit round-trips must be exact
        vals = [v]
        ops = ["max"]
    elif case == "multikey":
        keys = [k1, (k1 % 7).astype(np.int32)]
        vals = [vals[0], rng.randint(0, 9, n).astype(np.int32)]
        ops = ["add", "min"]
    elif case == "maxmin":
        vals = [vals[0], rng.randint(-50, 50, n).astype(np.int32)]
        ops = ["max", "min"]
    valid = rng.rand(n) < 0.9

    def part(key_cols):
        h = frame_ops.hash_device_column(key_cols[0], 0)
        for k in key_cols[1:]:
            h = frame_ops.combine_hashes(
                h, frame_ops.hash_device_column(k, 0))
        return (h % np.uint32(nparts)).astype(np.int32)

    assert pk.aggregate_supported([k.dtype for k in keys],
                                  [v.dtype for v in vals], nparts, R)
    pid = jnp.asarray(part(keys))
    got = pk.hash_aggregate_pallas(
        jnp.asarray(valid), [jnp.asarray(k) for k in keys],
        [jnp.asarray(v) for v in vals], ops, pid,
        nparts, R, interpret=True)
    ref = hashagg.hash_aggregate(
        jnp.asarray(valid), [jnp.asarray(k) for k in keys],
        [jnp.asarray(v) for v in vals], ops, pid,
        nparts, R, backend="xla")
    g_present, g_keys, g_vals, g_ov = got
    r_present, r_keys, r_vals, r_ov = ref
    assert int(g_ov) == 0 and int(r_ov) == 0
    assert _agg_rows(g_present, g_keys, g_vals) == \
        _agg_rows(r_present, r_keys, r_vals)
    _agg_regions(g_present, g_keys, part, nparts, R)
    _agg_regions(r_present, r_keys, part, nparts, R)


def test_hash_aggregate_kernel_float_bits_exact():
    """float32 payloads round-trip through the kernel's int32 table
    bit-exactly: -0.0 stays -0.0 and NaN stays the same NaN pattern
    (values only — float KEYS are rejected upstream by keyutil)."""
    import jax.numpy as jnp

    from bigslice_tpu.parallel import hashagg

    nparts, R = 2, 128
    keys = [np.arange(8, dtype=np.int32)]
    v = np.array([0.0, -0.0, np.nan, 1.5, -2.5, np.inf, -np.inf, 3.0],
                 np.float32)
    valid = np.ones(8, bool)

    pid = jnp.asarray((keys[0] % nparts).astype(np.int32))

    for backend in ("kernel", "xla"):
        if backend == "kernel":
            present, okeys, ovals, ov = pk.hash_aggregate_pallas(
                jnp.asarray(valid), [jnp.asarray(keys[0])],
                [jnp.asarray(v)], ["max"], pid, nparts, R,
                interpret=True)
        else:
            present, okeys, ovals, ov = hashagg.hash_aggregate(
                jnp.asarray(valid), [jnp.asarray(keys[0])],
                [jnp.asarray(v)], ["max"], pid, nparts, R,
                backend="xla")
        p = np.asarray(present)
        got = dict(zip(np.asarray(okeys[0])[p].tolist(),
                       np.asarray(ovals[0])[p].view(np.int32)
                       .tolist()))
        want = dict(zip(keys[0].tolist(),
                        v.view(np.int32).tolist()))
        assert got == want, backend


def test_aggregate_supported_bounds():
    """The capability gate: pow2 lane-aligned regions, supported
    dtypes only, and the VMEM ceiling on the resident table."""
    ok = pk.aggregate_supported
    assert ok(["int32"], ["int32"], 4, 256)
    assert not ok(["int32"], ["int32"], 4, 100)     # non-pow2 R
    assert not ok(["int32"], ["int32"], 4, 64)      # R < LANES
    assert not ok(["float32"], ["int32"], 4, 256)   # float key
    assert not ok(["int64"], ["int32"], 4, 256)     # unsupported key
    assert not ok(["int32"], ["int64"], 4, 256)     # unsupported val
    assert ok(["int32"], ["float32"], 4, 256)       # f32 vals OK
    # VMEM ceiling: T*(1+nkeys+nvals)*4 must fit the table budget.
    big_T = pk.AGG_TABLE_VMEM_BYTES // (3 * 4) * 2
    R = 1 << (int(big_T).bit_length())
    assert not ok(["int32"], ["int32"], 1, R)


def test_hashagg_backend_env_round_trip(monkeypatch):
    """BIGSLICE_HASHAGG_BACKEND resolves loudly; unset keeps the
    platform default (xla off-TPU)."""
    from bigslice_tpu.parallel import hashagg

    monkeypatch.delenv("BIGSLICE_HASHAGG_BACKEND", raising=False)
    assert hashagg._kernel_backend() == "xla"  # CPU test host
    monkeypatch.setenv("BIGSLICE_HASHAGG_BACKEND", "pallas_interpret")
    assert hashagg._kernel_backend() == "pallas_interpret"
    monkeypatch.setenv("BIGSLICE_HASHAGG_BACKEND", "frobnicate")
    with pytest.raises(ValueError):
        hashagg._kernel_backend()
