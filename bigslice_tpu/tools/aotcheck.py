"""AOT-compile the device tier for real TPU targets — no chip needed.

``jax.experimental.topologies`` describes a TPU slice (v5e:2x4 by
default) and the PJRT TPU compiler lowers + compiles every SPMD program
of the framework against it ahead of time:

  shuffle (sort + dense + hash lowerings), the fused combine+shuffle
  pipelines, the Cogroup tagged-sort align, ring and Ulysses attention,
  the k-means step, and the Mosaic lowering of the Pallas kernels.

A compile that passes is not a chip run and says nothing about results
or times; what it buys is that Mosaic rejections, layout errors, and
collective lowering bugs surface here instead of on chip time — the
hermetic-testing ethos of the reference's testsystem
(exec/slicemachine_test.go:299) applied to the compiler boundary.

Per-program XLA cost stats (flops, bytes accessed, optimal seconds) are
recorded to ``AOT_TPU.json``.

Run: ``python -m bigslice_tpu.tools.aotcheck [topology]``.
"""

from __future__ import annotations

import json
import sys
import time
import traceback

import numpy as np

DEFAULT_TOPOLOGY = "v5e:2x4"

# Per-device row budget for the data-plane programs: big enough that
# cost stats are meaningful, small enough that 10+ TPU AOT compiles
# stay bounded on a 1-vCPU fallback box.
SIZE = 1 << 14


def _programs(mesh, axis: str):
    """name -> (jitted_fn, [ShapeDtypeStruct args]). Every program is
    the REAL builder the executor uses, not a simplified stand-in."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from bigslice_tpu.parallel import (
        dense as dense_mod,
        hashagg,
        segment,
        shuffle as shuffle_mod,
    )
    from bigslice_tpu.parallel.meshutil import get_shard_map

    shard_map = get_shard_map()
    nmesh = mesh.devices.size
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt)
    i32 = jnp.int32
    f32 = jnp.float32
    progs = {}

    def smap(fn, n_in, n_out, scalar_out=0):
        return jax.jit(shard_map(
            fn, mesh=mesh,
            in_specs=tuple(P(axis) for _ in range(n_in)),
            out_specs=tuple(P(axis) for _ in range(n_out))
            + tuple(P() for _ in range(scalar_out)),
            check_rep=False,
        ))

    # 1. Routing shuffle (the combinerless exchange).
    body = shuffle_mod.make_shuffle_fn(nmesh, 1, SIZE, axis)

    def shuffle_route(counts, k, v):
        n, ov, cols = body(counts[0], k, v)
        return (n.reshape(1), cols[0], cols[1], ov)

    progs["shuffle_sort"] = (
        smap(shuffle_route, 3, 3, scalar_out=1),
        [S((nmesh,), i32), S((nmesh * SIZE,), i32),
         S((nmesh * SIZE,), i32)],
    )

    # 2. Fused combine+shuffle + reduce-side combine (sort pipeline).
    cfn = segment.canonical_combine(lambda a, b: a + b, 1)
    fused_sort = shuffle_mod.make_combine_shuffle_fn(
        nmesh, 1, 1, cfn, axis
    )
    final = segment.make_segmented_reduce_masked(1, 1, cfn, compact=True)

    def reduce_sort(counts, k, v):
        m = jnp.arange(SIZE, dtype=np.int32) < counts[0]
        rm, ov, bad, oc = fused_sort.masked(m, k, v)
        n3, k3, v3 = final(rm, (oc[0],), (oc[1],))
        return (n3.reshape(1), k3[0], v3[0], ov)

    progs["reduce_sort"] = (
        smap(reduce_sort, 3, 3, scalar_out=1),
        [S((nmesh,), i32), S((nmesh * SIZE,), i32),
         S((nmesh * SIZE,), i32)],
    )

    # 3. Hash-aggregate pipeline as the chip builds it: the Pallas
    # table kernel on both sides of the region a2a (the XLA claim
    # cascade where a table exceeds the kernel's VMEM gate).
    fused_hash = hashagg.make_hash_combine_shuffle(
        nmesh, 1, 1, ("add",), axis
    )
    recv_hash = hashagg.make_hash_combine(1, 1, ("add",))

    def reduce_hash(counts, k, v):
        m = jnp.arange(SIZE, dtype=np.int32) < counts[0]
        rm, ov, bad, oc = fused_hash.masked(m, k, v)
        m2, k2, v2, ov2 = recv_hash(rm, (oc[0],), (oc[1],))
        n3, packed = segment.compact_by_mask(m2, tuple(k2) + tuple(v2))
        return (n3.reshape(1), packed[0], packed[1], ov + ov2)

    progs["reduce_hash"] = (
        smap(reduce_hash, 3, 3, scalar_out=1),
        [S((nmesh,), i32), S((nmesh * SIZE,), i32),
         S((nmesh * SIZE,), i32)],
    )

    # 4. Dense-table combine+shuffle.
    K = 1 << 16
    dense_body = dense_mod.make_dense_combine_shuffle(
        nmesh, K, ("add",), [np.dtype(np.int32)], axis
    )

    def reduce_dense(counts, k, v):
        m = jnp.arange(SIZE, dtype=np.int32) < counts[0]
        rm, ov, bad, oc = dense_body.masked(m, k, v)
        n3, packed = segment.compact_by_mask(rm, oc)
        return (n3.reshape(1), packed[0], packed[1], bad)

    progs["reduce_dense"] = (
        smap(reduce_dense, 3, 3, scalar_out=1),
        [S((nmesh,), i32), S((nmesh * SIZE,), i32),
         S((nmesh * SIZE,), i32)],
    )

    # 5. Cogroup tagged-sort align (2 inputs, discovered capacity 64).
    from bigslice_tpu.parallel.cogroup import make_cogroup_align

    align = make_cogroup_align(1, (1, 1), 64, axis)

    def cogroup(ca, cb, ka, va, kb, vb):
        ma = jnp.arange(SIZE, dtype=np.int32) < ca[0]
        mb = jnp.arange(SIZE, dtype=np.int32) < cb[0]
        mask, cols, deficit = align((ma, mb), ((ka, va), (kb, vb)))
        n, packed = segment.compact_by_mask(mask, cols)
        return (n.reshape(1),) + tuple(packed) + (deficit,)

    progs["cogroup"] = (
        smap(cogroup, 6, 6, scalar_out=1),
        [S((nmesh,), i32), S((nmesh,), i32),
         S((nmesh * SIZE,), i32), S((nmesh * SIZE,), i32),
         S((nmesh * SIZE,), i32), S((nmesh * SIZE,), i32)],
    )

    # 6/7. Sequence-parallel attention — the builders jit internally.
    from bigslice_tpu.parallel import ringattention as ra
    from bigslice_tpu.parallel import ulysses as ul

    seq, hd = nmesh * 512, 128
    ring = ra.make_ring_attention(mesh, d=hd, causal=True,
                                  dtype=jnp.bfloat16, block_q=128)
    progs["ring_attention"] = (
        ring, [S((seq, hd), f32)] * 3
    )
    heads = nmesh
    uly = ul.make_ulysses_attention(mesh, nheads=heads, d=hd,
                                    causal=True, dtype=jnp.bfloat16)
    progs["ulysses_attention"] = (
        uly, [S((seq, heads, hd), f32)] * 3
    )

    # 8. k-means step (MXU + psum).
    from bigslice_tpu.models.kmeans import mesh_kmeans_step

    k_, d_ = 64, 128
    progs["kmeans_step"] = (
        mesh_kmeans_step(mesh, k_, d_),
        [S((nmesh * SIZE, d_), f32), S((k_, d_), f32)],
    )

    # 8b. Hierarchical 2-D (DCN × ICI) shuffle: the two-stage exchange
    # over a (nmesh/4, 4) grid of the same topology devices — proves
    # the multi-pod collective pattern (ici all_to_all + aggregated
    # dcn all_to_all) lowers and compiles for TPU.
    if nmesh % 4 == 0 and nmesh >= 8:
        from jax.sharding import Mesh as _Mesh

        from bigslice_tpu.parallel import hier

        grid = _Mesh(mesh.devices.reshape(nmesh // 4, 4),
                     ("dcn", "ici"))
        hier_body = hier.make_hier_shuffle_fn(
            nmesh // 4, 4, 1, SIZE
        )

        def shuffle_hier(counts, k, v):
            c, ov, out = hier_body(counts[0], k, v)
            return (c.reshape(1), out[0], out[1], ov)

        gspec = P(("dcn", "ici"))
        progs["shuffle_hier"] = (
            jax.jit(shard_map(
                shuffle_hier, mesh=grid,
                in_specs=(gspec, gspec, gspec),
                out_specs=(gspec, gspec, gspec, P()),
                check_rep=False,
            )),
            [S((nmesh,), i32), S((nmesh * SIZE,), i32),
             S((nmesh * SIZE,), i32)],
        )

        # 8c. The COMPOSED hier reduce (map combine → two-stage
        # exchange → final combine) — the exact program
        # HierMeshReduceByKey jits, so "TPU-AOT-proven" covers the
        # composition, not just the exchange.
        h_local = segment.make_segmented_reduce_masked(
            1, 1, cfn, compact=False
        )
        h_final = segment.make_segmented_reduce_masked(
            1, 1, cfn, compact=True
        )

        def reduce_hier(counts, k, v):
            m = jnp.arange(SIZE, dtype=np.int32) < counts[0]
            keep, k1, v1 = h_local(m, (k,), (v,))
            m2, ov, _bad, oc = hier_body.masked(keep, k1[0], v1[0])
            n3, k3, v3 = h_final(m2, (oc[0],), (oc[1],))
            return (n3.reshape(1), k3[0], v3[0], ov)

        progs["reduce_hier"] = (
            jax.jit(shard_map(
                reduce_hier, mesh=grid,
                in_specs=(gspec, gspec, gspec),
                out_specs=(gspec, gspec, gspec, P()),
                check_rep=False,
            )),
            [S((nmesh,), i32), S((nmesh * SIZE,), i32),
             S((nmesh * SIZE,), i32)],
        )

    # 9. Mosaic Pallas: the fused hash+validity+histogram kernel, and
    # the VMEM-resident hash-aggregate table (float32 payloads, the
    # case Mosaic once refused).
    from bigslice_tpu.parallel import pallas_kernels as pk

    def pallas_hash(k):
        ids, counts = pk.hash_partition([k], nmesh, 0, with_counts=True)
        return ids, counts

    progs["pallas_hash_partition"] = (
        jax.jit(shard_map(
            pallas_hash, mesh=mesh, in_specs=(P(axis),),
            out_specs=(P(axis), P(axis)), check_rep=False,
        )),
        [S((nmesh * SIZE,), i32)],
    )

    R = SIZE // nmesh

    def pallas_agg(k, v):
        part = (k % nmesh).astype(np.int32)
        present, ok, ov, overflow = pk.hash_aggregate_pallas(
            jnp.ones(SIZE, bool), (k,), (v,), ("max",), part, nmesh, R,
            interpret=False,
        )
        return present, ok[0], ov[0]

    progs["pallas_hash_aggregate_f32"] = (
        jax.jit(shard_map(
            pallas_agg, mesh=mesh, in_specs=(P(axis), P(axis)),
            out_specs=(P(axis),) * 3, check_rep=False,
        )),
        [S((nmesh * SIZE,), i32), S((nmesh * SIZE,), f32)],
    )
    return progs


def _tpu_branches():
    """The code under compile picks its lowerings from
    ``jax.default_backend()``, which says "cpu" in this process: left
    alone, ``reduce_hash`` would compile the XLA scatter cascade and
    ``pallas_*`` the INTERPRETED kernels for a TPU target, proving
    nothing about what the chip runs. Nothing executes here, so the
    answer can simply be forced for the duration of the sweep."""
    from unittest import mock

    import jax

    return mock.patch.object(jax, "default_backend",
                             return_value="tpu")


def _compile(name: str, fn, args) -> dict:
    """Lower + compile one program for the described target; its
    report entry."""
    t0 = time.perf_counter()
    try:
        compiled = fn.lower(*args).compile()
    except Exception as exc:  # noqa: BLE001 — per-program report
        print(f"aot {name}: FAIL {type(exc).__name__}: "
              f"{str(exc)[:200]}", file=sys.stderr)
        traceback.print_exc()
        return {"ok": False,
                "error": f"{type(exc).__name__}: {exc}"[:500]}
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0] if ca else {}
    ca = ca or {}
    entry = {
        "ok": True,
        "compile_seconds": round(time.perf_counter() - t0, 2),
        "flops": ca.get("flops"),
        "bytes_accessed": ca.get("bytes accessed"),
        "optimal_seconds": ca.get("optimal_seconds"),
    }
    print(f"aot {name}: OK ({entry['compile_seconds']}s, "
          f"flops={ca.get('flops')}, "
          f"bytes={ca.get('bytes accessed')})", file=sys.stderr)
    return entry


def run(topology: str = DEFAULT_TOPOLOGY, out_path: str = "AOT_TPU.json"):
    # Topology descriptions and the TPU compiler need no live backend.
    from bigslice_tpu.utils.hermetic import force_hermetic_cpu

    force_hermetic_cpu()

    from jax.experimental import topologies
    from jax.sharding import Mesh

    from bigslice_tpu.parallel.meshutil import mesh_axis

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology)
    mesh = Mesh(np.array(topo.devices), ("shards",))
    axis = mesh_axis(mesh)
    with _tpu_branches():
        results = {
            name: _compile(name, fn, args)
            for name, (fn, args) in _programs(mesh, axis).items()
        }
    ok_all = all(r["ok"] for r in results.values())
    payload = {
        "topology": topology,
        "device_kind": str(getattr(topo.devices[0], "device_kind", "")),
        "n_devices": len(topo.devices),
        "per_device_rows": SIZE,
        "ok": ok_all,
        "programs": results,
    }
    with open(out_path, "w") as fp:
        json.dump(payload, fp, indent=1)
    print(json.dumps({"metric": "aot_tpu_programs_ok",
                      "value": sum(1 for r in results.values() if r["ok"]),
                      "unit": f"of {len(results)} programs",
                      "vs_baseline": 1.0 if ok_all else 0.0}))
    return ok_all


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if len(argv) > 2:
        sys.exit(f"usage: aotcheck [topology] [out.json]; got {argv}")
    topology = argv[0] if argv else DEFAULT_TOPOLOGY
    out_path = argv[1] if len(argv) > 1 else "AOT_TPU.json"
    ok = run(topology, out_path)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
