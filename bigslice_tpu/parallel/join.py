"""Device-tier keyed join aggregation over a mesh.

The general Cogroup materializes ragged per-key groups and is host-tier
by nature (ops/cogroup.py). The common *aggregating* joins — count or
combine matched pairs per key — never need the ragged groups, and lower
fully onto the device:

1. reduce each side to one row per key (MeshReduceByKey: local combine →
   all_to_all → final combine; both sides share the hash seed so equal
   keys land on the same device),
2. align the two reduced sides on-device: concatenate with a side tag,
   sort by (key, tag), and match adjacent (A,B) rows with equal keys,
3. emit (key, a_agg, b_agg) for matched keys (inner join), compacted.

This is the TPU lowering of the BASELINE "Reduce+Cogroup join" headline:
the whole join is two shuffles and three sorts, all on-chip, with no
host materialization.
"""

from __future__ import annotations

from typing import Callable, Sequence, Tuple

import numpy as np

from bigslice_tpu.parallel import jitutil
from bigslice_tpu.parallel.meshutil import get_shard_map, mesh_axis
from bigslice_tpu.parallel import shuffle as shuffle_mod


def make_align(nkeys: int, nvals_a: int, nvals_b: int):
    """Build the tagged-sort align kernel shared by the kernel tier
    (MeshJoinAggregate) and the Slice tier (meshexec join groups).

    ``align(keep_a, key_cols_a, val_cols_a, keep_b, key_cols_b,
    val_cols_b) -> (match_mask, out_cols)`` where each side's rows are
    selected by its ``keep`` mask and have at most one row per key
    (post-reduction). Sides are concatenated with a side tag, stable-
    sorted by (validity, keys..., tag), and an inner-join match is an
    adjacent valid (tag 0, tag 1) pair with equal keys. ``out_cols`` is
    keys + A's values + B's values (shifted from the adjacent row),
    valid where ``match_mask`` — callers compact or chain the mask.
    """
    import jax.numpy as jnp
    from jax import lax

    def align(keep_a, key_a, val_a, keep_b, key_b, val_b):
        size_a = key_a[0].shape[0]
        size_b = key_b[0].shape[0]
        size = size_a + size_b
        keys = [jnp.concatenate([x, y]) for x, y in zip(key_a, key_b)]
        tag = jnp.concatenate([
            jnp.zeros(size_a, np.int32), jnp.ones(size_b, np.int32)
        ])
        avals = [
            jnp.concatenate([v, jnp.zeros((size_b,), v.dtype)])
            for v in val_a
        ]
        bvals = [
            jnp.concatenate([jnp.zeros((size_a,), v.dtype), v])
            for v in val_b
        ]
        invalid = (~jnp.concatenate([keep_a, keep_b])).astype(np.int32)
        ops = ((invalid,) + tuple(keys) + (tag,)
               + tuple(avals) + tuple(bvals))
        srt = lax.sort(ops, num_keys=2 + nkeys, is_stable=True)
        s_inv, s_keys = srt[0], srt[1 : 1 + nkeys]
        s_tag = srt[1 + nkeys]
        s_avals = srt[2 + nkeys : 2 + nkeys + nvals_a]
        s_bvals = srt[2 + nkeys + nvals_a :]
        eq = jnp.ones(size - 1, dtype=bool)
        for k in s_keys:
            eq = eq & (k[:-1] == k[1:])
        match = jnp.zeros(size, dtype=bool).at[:-1].set(
            eq & (s_tag[:-1] == 0) & (s_tag[1:] == 1)
            & (s_inv[:-1] == 0) & (s_inv[1:] == 0)
        )
        b_next = [jnp.concatenate([v[1:], v[-1:]]) for v in s_bvals]
        return match, list(s_keys) + list(s_avals) + list(b_next)

    return align


def make_lookup_align(nkeys: int):
    """Build the N:1 lookup kernel of the Slice tier's ``JoinLookup``
    groups (meshexec).

    ``align(mask_p, cols_p, mask_b, cols_b) -> (match, out_cols, dup)``:
    the probe side keeps every selected row, the build side holds at
    most one selected row a key. The build rows are concatenated AHEAD
    of the probe rows and the union is stable-sorted by (validity,
    keys) alone, so a key's build row leads its segment without a tag
    among the sort keys; one segmented carry
    (``segment.carry_segment_head``) then hands the build row's values
    to the probe rows behind it. ``match`` selects the probe rows whose
    key has a build row; ``out_cols`` is keys + probe values + build
    values in sorted position (callers chain the mask or compact).
    ``dup`` counts the build rows that follow another build row of
    their key — the evidence of a build side that is not unique.
    """
    import jax.numpy as jnp

    from bigslice_tpu.parallel import segment

    def align(mask_p, cols_p, mask_b, cols_b):
        size_p = cols_p[0].shape[0]
        size_b = cols_b[0].shape[0]
        keys = [jnp.concatenate([b, p])
                for b, p in zip(cols_b[:nkeys], cols_p[:nkeys])]
        is_build = jnp.concatenate([jnp.ones(size_b, np.int32),
                                    jnp.zeros(size_p, np.int32)])
        pvals = [jnp.concatenate([jnp.zeros((size_b,), v.dtype), v])
                 for v in cols_p[nkeys:]]
        bvals = [jnp.concatenate([v, jnp.zeros((size_p,), v.dtype)])
                 for v in cols_b[nkeys:]]
        s_inv, s_keys, s_pay, diff = segment.sort_and_segment(
            nkeys, jnp.concatenate([mask_b, mask_p]), keys,
            [is_build] + pvals + bvals,
        )
        s_isb = s_pay[0]
        s_pvals = s_pay[1 : 1 + len(pvals)]
        head = segment.carry_segment_head(
            diff, (s_isb,) + tuple(s_pay[1 + len(pvals):]))
        dup = jnp.sum(((s_isb == 1) & ~diff).astype(np.int32))
        match = (s_isb == 0) & (head[0] == 1) & (s_inv == 0)
        return (match, list(s_keys) + list(s_pvals) + list(head[1:]),
                dup)

    return align


class MeshJoinAggregate:
    """Inner-join two keyed, single-value-column sides after per-side
    reduction. ``__call__`` takes per-side (keys, vals, counts) global
    sharded arrays (as produced by shard_columns) and returns
    (keys, a_vals, b_vals, out_counts, overflow) with one row per key
    present in *both* sides.
    """

    def __init__(self, mesh, capacity: int, a_combine: Callable,
                 b_combine: Callable, seed: int = 0,
                 slack: float = 2.0):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        self.mesh = mesh
        nmesh = int(mesh.devices.size)
        self.nmesh = nmesh
        axis = mesh_axis(mesh)
        shard_map = get_shard_map()
        self.a_reduce = shuffle_mod.MeshReduceByKey(
            mesh, 1, 1, capacity, a_combine, seed=seed, slack=slack
        )
        self.b_reduce = shuffle_mod.MeshReduceByKey(
            mesh, 1, 1, capacity, b_combine, seed=seed, slack=slack
        )
        cap_a = self.a_reduce.out_capacity
        cap_b = self.b_reduce.out_capacity
        self.out_capacity = cap_a + cap_b
        align_core = make_align(1, 1, 1)

        def align(counts_a, counts_b, ka, va, kb, vb):
            from bigslice_tpu.parallel.segment import compact_by_mask

            na = counts_a[0]
            nb = counts_b[0]
            keep_a = jnp.arange(cap_a, dtype=np.int32) < na
            keep_b = jnp.arange(cap_b, dtype=np.int32) < nb
            match, cols = align_core(keep_a, (ka,), (va,),
                                     keep_b, (kb,), (vb,))
            n_out, packed = compact_by_mask(match, cols)
            return (n_out.reshape(1),) + tuple(packed)

        col = P(axis)
        self._align = jitutil.jit(shard_map(
            align, mesh=mesh,
            in_specs=(col, col, col, col, col, col),
            out_specs=(col, col, col, col),
            check_rep=False,
        ))

    def __call__(self, a_cols, a_counts, b_cols, b_counts):
        # Dispatch both reduces before any host sync so the two
        # independent SPMD programs overlap; overflows convert to host
        # only after the align is dispatched.
        ka, va, na, ov_a = self.a_reduce([a_cols[0]], [a_cols[1]],
                                         a_counts)
        kb, vb, nb, ov_b = self.b_reduce([b_cols[0]], [b_cols[1]],
                                         b_counts)
        out_counts, keys, avals, bvals = self._align(
            na, nb, ka[0], va[0], kb[0], vb[0]
        )
        return (keys, avals, bvals, out_counts,
                np.asarray(ov_a) + np.asarray(ov_b))


def join_count_oracle(a_keys, b_keys) -> dict:
    """Host oracle: keys present in both sides with (countA, countB)."""
    from collections import Counter

    ca, cb = Counter(a_keys), Counter(b_keys)
    return {k: (ca[k], cb[k]) for k in ca.keys() & cb.keys()}
