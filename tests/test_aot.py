"""Compile-for-the-chip checks: the device tier's kernels and pipelines
must lower and compile for a real TPU target without hardware.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``jax.experimental.topologies``). These tests
keep the main path's kernels at the smoke's real widths honest at no
chip time: interpret-mode tests cannot see what Mosaic refuses. The
code under test picks its TPU branches from ``jax.default_backend()``,
which still says "cpu" here — the ``tpu_branches`` fixture steers it
in the test, not through an option of the program. The full sweep with
cost stats is ``python -m bigslice_tpu.tools.aotcheck``.

Everything that touches the topology lives in fixtures of THIS file:
only one process may load the TPU's library, so nothing here runs at
import time, and all such tests stay in one file (one xdist worker).
"""

import re

import numpy as np
import pytest


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described device can be written to JAX's
    persistent cache but never read back without a chip; keep the cache
    off around these tests."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices), ("shards",))


@pytest.fixture
def tpu_branches(monkeypatch):
    """Make the code under test take the branches it takes on the chip
    (Mosaic instead of the interpreter, the Pallas hash-aggregate
    backend, the sort-based routing)."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _mosaic_calls_in(text: str, kernel: str) -> int:
    return sum(kernel in line and "tpu_custom_call" in line
               for line in text.splitlines())


def _mosaic_calls(compiled, kernel: str) -> int:
    return _mosaic_calls_in(compiled.as_text(), kernel)


# The smoke's real widths: a wave of 2^21 rows through the partitioner,
# a 2^19-slot table through the aggregate kernel.
PART_ROWS = 1 << 21
AGG_ROWS = 1 << 19


@pytest.mark.parametrize("keys,nparts,valid,counts", [
    (("int32",), 8, False, True),
    (("int32",), 8, True, False),
    (("int32", "float32"), 64, True, True),
])
def test_hash_partition_compiles_as_mosaic(one_chip, tpu_branches, keys,
                                           nparts, valid, counts):
    """The fused hash+mask+partition(+histogram) kernel, NOT
    interpreted, with and without the validity mask and the counts."""
    import jax

    from bigslice_tpu.parallel import pallas_kernels as pk

    def fn(mask, *cols):
        return pk.hash_partition(list(cols), nparts, 0,
                                 with_counts=counts,
                                 valid=mask if valid else None)

    S = lambda dt: jax.ShapeDtypeStruct((PART_ROWS,), np.dtype(dt),
                                        sharding=one_chip)
    compiled = jax.jit(fn).lower(S(np.bool_), *[S(k) for k in keys]
                                 ).compile()
    assert _mosaic_calls(compiled, pk.HASH_PARTITION_KERNEL) == 1


AGG_CASES = [
    (("int32",), ("int32",), ("add",), 8),
    # 2 keys + 1 value = 4 planes of 2^19 slots: exactly the 8 MiB gate.
    (("int32", "uint32"), ("int32",), ("max",), 4),
    (("int32", "uint32"), ("uint32",), ("min",), 4),
    (("int32",), ("float32",), ("add",), 8),
    (("int32", "uint32"), ("float32",), ("max",), 4),
    (("int32",), ("float32", "float32"), ("add", "min"), 8),
]


@pytest.mark.parametrize("keys,vals,ops,nparts", AGG_CASES)
def test_hash_aggregate_compiles_as_mosaic(one_chip, keys, vals, ops,
                                           nparts):
    """The VMEM-resident table kernel with interpret=False, for every
    value dtype ``aggregate_supported`` admits (float32 payloads were
    refused by Mosaic: a scalar bitcast) at the table gate."""
    import jax

    from bigslice_tpu.parallel import pallas_kernels as pk

    assert set(vals) <= set(pk.SUPPORTED_AGG_VAL_DTYPES)
    R = AGG_ROWS // nparts
    assert pk.aggregate_supported(keys, vals, nparts, R)

    def fn(valid, part, *cols):
        return pk.hash_aggregate_pallas(
            valid, cols[:len(keys)], cols[len(keys):], ops, part,
            nparts, R, interpret=False,
        )

    S = lambda dt: jax.ShapeDtypeStruct((AGG_ROWS,), np.dtype(dt),
                                        sharding=one_chip)
    compiled = jax.jit(fn).lower(
        S(np.bool_), S(np.int32), *[S(d) for d in keys + vals]
    ).compile()
    assert _mosaic_calls(compiled, pk.HASH_AGGREGATE_KERNEL) == 1


def test_every_admitted_value_dtype_is_compiled_above():
    """The gate and the compile cases cannot drift apart."""
    from bigslice_tpu.parallel import pallas_kernels as pk

    assert {d for _, vals, _, _ in AGG_CASES for d in vals} \
        == set(pk.SUPPORTED_AGG_VAL_DTYPES)


def test_aot_pallas_hash_partition_compiles_for_tpu(mesh, tpu_branches):
    """The partitioner inside a shard_map over the 4-chip mesh — the
    form the executor's group programs call it in."""
    import jax
    from jax.sharding import PartitionSpec as P

    from bigslice_tpu.parallel import pallas_kernels as pk
    from bigslice_tpu.parallel.meshutil import get_shard_map

    n = mesh.devices.size

    def body(k):
        ids, counts = pk.hash_partition([k], n, 0, with_counts=True)
        return ids, counts

    fn = jax.jit(get_shard_map()(
        body, mesh=mesh, in_specs=(P("shards"),),
        out_specs=(P("shards"), P("shards")), check_rep=False,
    ))
    compiled = fn.lower(
        jax.ShapeDtypeStruct((n * 4096,), np.int32)
    ).compile()
    assert _mosaic_calls(compiled, pk.HASH_PARTITION_KERNEL) == 1


def test_aot_hash_reduce_compiles_for_tpu(mesh, tpu_branches):
    """The hash-aggregate pipeline as the chip builds it — the Pallas
    table kernel on both sides of the region all_to_all — compiles for
    v5e. (Pinned to the CPU branch this compiled the XLA scatter
    cascade and proved nothing about the kernel.)"""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from bigslice_tpu.parallel import hashagg, segment
    from bigslice_tpu.parallel import pallas_kernels as pk
    from bigslice_tpu.parallel.meshutil import get_shard_map

    n = mesh.devices.size
    fused = hashagg.make_hash_combine_shuffle(n, 1, 1, ("add",),
                                              "shards")
    recv = hashagg.make_hash_combine(1, 1, ("add",))
    size = 4096

    def body(k, v):
        m = jnp.ones(size, bool)
        rm, ov, bad, oc = fused.masked(m, k, v)
        m2, k2, v2, ov2 = recv(rm, (oc[0],), (oc[1],))
        n_out, packed = segment.compact_by_mask(m2,
                                                tuple(k2) + tuple(v2))
        return n_out.reshape(1), packed[0], packed[1]

    fn = jax.jit(get_shard_map()(
        body, mesh=mesh, in_specs=(P("shards"), P("shards")),
        out_specs=(P("shards"),) * 3, check_rep=False,
    ))
    compiled = fn.lower(jax.ShapeDtypeStruct((n * size,), np.int32),
                        jax.ShapeDtypeStruct((n * size,), np.int32)
                        ).compile()
    assert _mosaic_calls(compiled, pk.HASH_AGGREGATE_KERNEL) == 2
    assert "all-to-all" in compiled.as_text()


def _rows_max(n_out, chips):
    """The wave's fifth signal as ``meshexec._program`` computes it:
    every device fills its own slot of a vector, the slots are summed
    and the fullest is taken — a psum, like the signals ahead of it."""
    import jax.numpy as jnp
    from jax import lax

    mine = jnp.arange(chips, dtype=np.int32) == lax.axis_index("shards")
    return lax.psum(jnp.where(mine, n_out, 0), "shards").max()


@pytest.mark.parametrize("chips", [1, 4])
def test_aot_fused_map_side_wave_holds_no_scatter(topo, tpu_branches,
                                                  chips):
    """The map-side wave body — Mosaic partitioner, fused combine +
    shuffle, the packing of what arrived — compiled for v5e on both of
    the cells' layouts: sorts and slices, no ``scatter`` op (one of a
    wave's rows runs row by row on the chip: PERF.md §5, PR 31)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from bigslice_tpu.parallel import pallas_kernels as pk
    from bigslice_tpu.parallel import segment, shuffle
    from bigslice_tpu.parallel.meshutil import get_shard_map

    mesh = Mesh(np.array(topo.devices[:chips]), ("shards",))
    size = 4096
    fused = shuffle.make_combine_shuffle_fn(
        chips, 1, 1, segment.canonical_combine(lambda a, b: a + b, 1),
        "shards", slack=1.0, nparts=46 * chips)

    def body(n, k, v):
        mask = jnp.arange(size, dtype=np.int32) < n[0]
        rm, ov, bad, oc = fused.masked(mask, k, v)
        n_out, packed = segment.compact_by_mask(rm, oc)
        # The signals as meshexec._program returns them: one vector,
        # the fullest device's output rows behind the four.
        zero = jnp.int32(0)
        signals = jnp.stack([ov, bad, zero, zero, _rows_max(n_out, chips)])
        return n_out.reshape(1), signals, packed

    row = P("shards")
    fn = jax.jit(get_shard_map()(
        body, mesh=mesh, in_specs=(row,) * 3,
        out_specs=(row, P(), (row,) * 3), check_rep=False,
    ))
    S = lambda rows: jax.ShapeDtypeStruct(  # noqa: E731
        (chips * rows,), np.int32)
    text = fn.lower(S(1), S(size), S(size)).compile().as_text()
    assert " scatter(" not in text
    assert text.count(" sort(") == 3
    assert _mosaic_calls_in(text, pk.HASH_PARTITION_KERNEL) == 1
    assert ("all-to-all" in text) == (chips > 1)
    # The row count rides the all-reduce the overflow and bad-range
    # signals already share: one a wave on four chips, none on one.
    assert len(re.findall(r"= .* all-reduce(?:-start)?\(", text)) == (
        chips > 1)


@pytest.mark.parametrize("chips", [1, 4])
def test_aot_wide_dense_map_side_wave_compiles_for_tpu(topo, tpu_branches,
                                                       chips):
    """The map-side wave of TPC-H Q1's shape, in JAX's 64-bit mode as
    ``jitutil.ScopedJit`` runs it: a widening Map, the two-column key as
    one dense code, a 6-slot table of five int64 sums and one int32
    filled by compare-and-sum (no scatter), then the table's rows
    through the routing shuffle and the packing sort. The Mosaic
    partitioner inside it is traced in 32-bit mode — with i64 index maps
    Mosaic refuses the kernel (what the chip said first: PERF.md §6,
    PR 32)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from bigslice_tpu.parallel import dense, segment, shuffle
    from bigslice_tpu.parallel import pallas_kernels as pk
    from bigslice_tpu.parallel.jitutil import jit
    from bigslice_tpu.parallel.meshutil import get_shard_map

    mesh = Mesh(np.array(topo.devices[:chips]), ("shards",))
    size = 4096
    dtypes = [np.int64] * 5 + [np.int32]
    core = dense.make_dense_combine((3, 2), ("add",) * 6, dtypes)
    routed = shuffle.make_shuffle_fn(chips, 2, 6, "shards",
                                     slack=float(chips),
                                     nparts=46 * chips)

    def widen(flag, status, qty, price, disc, tax, day):
        price = price.astype(np.int64)
        disc_price = price * (100 - disc)
        return (flag, status, qty.astype(np.int64), price, disc_price,
                disc_price * (100 + tax), disc.astype(np.int64),
                np.int32(1))

    def body(n, *cols):
        mask = (jnp.arange(size, dtype=np.int32) < n[0]) \
            & (cols[6] <= 2436)
        cols = jax.vmap(widen)(*cols)
        m, keys, vals = core(mask, tuple(cols[:2]), tuple(cols[2:]))
        rm, ov, bad, oc = routed.masked(m, *keys, *vals)
        n_out, packed = segment.compact_by_mask(rm, oc)
        zero = jnp.int32(0)
        signals = jnp.stack(
            [ov, bad, zero, zero, _rows_max(n_out, chips)]
        ).astype(np.int32)
        return n_out.reshape(1), signals, tuple(packed)

    row = P("shards")
    fn = jit(get_shard_map()(
        body, mesh=mesh, in_specs=(row,) * 8,
        out_specs=(row, P(), (row,) * 9), check_rep=False,
    ), wide=True)
    S = lambda rows: jax.ShapeDtypeStruct(  # noqa: E731
        (chips * rows,), np.int32)
    text = fn.lower(S(1), *[S(size)] * 7).compile().as_text()
    assert " scatter(" not in text
    assert "s64[" in text
    assert _mosaic_calls_in(text, pk.HASH_PARTITION_KERNEL) == 1
    assert ("all-to-all" in text) == (chips > 1)


def test_aot_small_vector_table_scatters_the_vectors_alone(one_chip):
    """The k-means round's table at its real size, compiled for one
    v5e: 2^23 float32 rows of 128 and their counts into 64 slots and the
    drop lane. ``present`` and the counts are the compare's reductions;
    the one scatter is the vector sums', and no contraction stands in
    for it (on the chip an MXU contraction's sums lean low: PERF.md
    §6). The compare is read where it is made, not stored: the
    temporaries stay small."""
    import jax

    from bigslice_tpu.parallel import dense

    n, d = 1 << 23, 128
    core = dense.make_dense_combine(64, ("add", "add"),
                                    [np.float32, np.float32])

    def fn(valid, key, x, w):
        return core(valid, (key,), (x, w))

    S = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, np.dtype(dt), sharding=one_chip)
    compiled = jax.jit(fn).lower(
        S((n,), np.bool_), S((n,), np.int32), S((n, d), np.float32),
        S((n,), np.float32)).compile()
    text = compiled.as_text()
    assert " convolution(" not in text and " dot(" not in text
    scatters = re.findall(r"= (\S+) scatter\(", text)
    assert len(scatters) == 1 and scatters[0].startswith("f32[65,128]")
    assert compiled.memory_analysis().temp_size_in_bytes <= 64 << 20
