"""k-means — the iterative-workload pattern, TPU-first.

The reference expresses iteration as repeated ``sess.Run`` calls feeding
``Result``s back as Func args (SURVEY.md §3.5). The per-iteration compute
here is the flagship device workload: the assignment step is one big
matmul (points × centroidsᵀ) on the MXU, and the update step is a
one-hot matmul reduction — both fused by XLA into a single program, with
cross-device aggregation as ``psum`` over the mesh (the "combiner →
psum/reduce-scatter" lowering from BASELINE.json's north star).
"""

from __future__ import annotations

import numpy as np


def kmeans_step(points, centroids):
    """One k-means iteration on one device (jittable).

    points: f32[n, d]; centroids: f32[k, d] → new centroids f32[k, d].
    Distance ranking via the ‖x−c‖² = ‖x‖² − 2x·c + ‖c‖² expansion: the
    x·cᵀ term is an [n,d]×[d,k] matmul (MXU); ‖x‖² is rank-invariant and
    dropped.
    """
    import jax
    import jax.numpy as jnp

    dots = points @ centroids.T  # [n, k] — the MXU hot loop
    c2 = jnp.sum(centroids * centroids, axis=1)  # [k]
    assign = jnp.argmin(c2[None, :] - 2.0 * dots, axis=1)  # [n]
    onehot = jax.nn.one_hot(assign, centroids.shape[0],
                            dtype=points.dtype)  # [n, k]
    sums = onehot.T @ points  # [k, d] — second MXU matmul
    counts = jnp.sum(onehot, axis=0)  # [k]
    return sums / jnp.maximum(counts, 1.0)[:, None]


def mesh_kmeans_step(mesh, k: int, d: int):
    """Build the SPMD k-means step over a device mesh: points are
    data-parallel sharded on the mesh axis; centroid sums/counts aggregate
    with ``psum`` over ICI. Returns a jitted fn
    ``(points_global, centroids) -> centroids``.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from bigslice_tpu.parallel.meshutil import get_shard_map, mesh_axis

    axis = mesh_axis(mesh)
    shard_map = get_shard_map()

    def step(points, centroids):
        dots = points @ centroids.T
        c2 = jnp.sum(centroids * centroids, axis=1)
        assign = jnp.argmin(c2[None, :] - 2.0 * dots, axis=1)
        onehot = jax.nn.one_hot(assign, k, dtype=points.dtype)
        sums = lax.psum(onehot.T @ points, axis)
        counts = lax.psum(jnp.sum(onehot, axis=0), axis)
        return sums / jnp.maximum(counts, 1.0)[:, None]

    return jax.jit(
        shard_map(
            step, mesh=mesh,
            in_specs=(P(axis), P()),
            out_specs=P(),
        )
    )


def _init_centroids(points: np.ndarray, k: int, seed: int):
    rng = np.random.RandomState(seed)
    return points[rng.choice(len(points), size=k, replace=False)].copy()


def kmeans_rounds(sess, points: np.ndarray, k: int,
                  num_shards: int = 4, seed: int = 0):
    """k-means through the slice API, one round per ``next()``:
    demonstrates the iterative session pattern (repeated runs over a
    reused Result, exec/compile.go:226-261). Yields ``(centroids
    f32[k, d], counts f32[k])`` after every round — the caller decides
    how many to take. The points upload once, before the first round.
    Initial centroids are
    ``points[RandomState(seed).choice(n, size=k, replace=False)]``.

    Points ride as ONE [n, d] float32 vector column (the data plane's
    trailing-dim tier): the per-row assignment is a [d]×[k,d] distance
    reduction, and the per-centroid sum Reduce carries the whole [d]
    vector through the fused combine+shuffle via permutation gathers —
    d-way vectorized end-to-end, instead of d scalar columns.
    """
    import bigslice_tpu as bs

    centroids = _init_centroids(points, k, seed)
    base = sess.run(
        bs.Const(num_shards, points.astype(np.float32, copy=False))
    )  # materialized once

    while True:
        # _assign_vec/_sum_combine are module-level, and centroids ride
        # as an unbatched Map arg (data, not a trace constant): every
        # iteration reuses the same compiled assignment and reduce
        # kernels instead of recompiling per round.
        assigned = bs.Map(base, _assign_vec, args=(centroids,))
        # Centroid ids are dense in [0, k) by construction: the
        # per-centroid vector sums take the sort-free scatter-table
        # lowering ([k, d] tables instead of sorting n [d]-vectors).
        summed = bs.Reduce(assigned, _sum_combine, dense_keys=k)
        res = sess.run(summed)
        counts = np.zeros(k, np.float32)
        for cid, vec, cnt in res.rows():
            counts[int(cid)] = cnt
            if cnt > 0:
                centroids[int(cid)] = np.asarray(vec, np.float32) / cnt
        # The assignment group's output is a second copy of the points
        # in HBM: without this every round would leave one behind.
        res.discard_graph(keep=[base])
        yield centroids.copy(), counts


def kmeans(sess, points: np.ndarray, k: int, iters: int = 10,
           num_shards: int = 4, seed: int = 0):
    """``iters`` rounds of ``kmeans_rounds``; returns the centroids."""
    import itertools

    centroids = _init_centroids(points, k, seed)
    for centroids, _ in itertools.islice(
        kmeans_rounds(sess, points, k, num_shards, seed), iters
    ):
        pass
    return centroids


def _assign_vec(x, c):
    """Per-row nearest-centroid assignment: x is the row's [d] point
    vector, c the unbatched [k, d] centroid matrix.

    Written as the ‖c‖² − 2c·x rank expansion (matching kmeans_step):
    under the executor's vmap the c·x matvec batches into the
    [n,d]×[d,k] matmul — the MXU form — where the naive
    ‖c − x‖² broadcast would lower to an [n,k,d] elementwise reduction
    (3x the FLOPs, no matmul, and the round-4 bench's 0.26x gap)."""
    import jax.numpy as jnp

    c2 = jnp.sum(c * c, axis=1)
    d2 = c2 - 2.0 * jnp.dot(c, x)
    return (jnp.argmin(d2).astype(jnp.int32), x, jnp.float32(1.0))


def _sum_combine(a, b):
    return tuple(x + y for x, y in zip(a, b))


def kmeans_oracle(points: np.ndarray, k: int, iters: int, seed: int = 0):
    """Reference numpy implementation for tests."""
    centroids = _init_centroids(points, k, seed)
    for _ in range(iters):
        d2 = ((points[:, None, :] - centroids[None, :, :]) ** 2).sum(-1)
        assign = d2.argmin(1)
        for c in range(k):
            m = assign == c
            if m.any():
                centroids[c] = points[m].mean(0)
    return centroids
