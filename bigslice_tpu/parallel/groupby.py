"""Fixed-capacity device grouping: ragged groups as (matrix, counts).

The general Cogroup materializes ragged per-key lists on the host
(ops/cogroup.py). When the group size is bounded (or a bounded sample
per key suffices), grouping lowers to the device as the classic
fixed-capacity encoding (SURVEY.md §7.3(1) pad/overflow strategy):

    keys:   int32[n_keys]
    values: dtype[n_keys, G]   (rows beyond a key's count are padding)
    counts: int32[n_keys]      (true group size, may exceed G; only the
                               first G values are kept)

Mechanics (one jitted program): sort rows by key, segment offsets by
running position within each segment, scatter into the (max_keys, G)
matrix, with per-key counts from segment sums. Overflowing rows are
dropped deterministically (the sorted order's tail) and visible via
counts > G.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from bigslice_tpu.parallel.jitutil import bucket_size, jit, pad_cols


class DeviceGroupByKey:
    """Jitted fixed-capacity grouping over device columns.

    ``__call__(key_cols, val_col, n)`` → (keys int32[k], groups
    dtype[k, G], counts int32[k]) host-compacted, sorted by key.
    """

    def __init__(self, nkeys: int, capacity: int):
        import jax
        import jax.numpy as jnp

        self.nkeys = nkeys
        self.capacity = capacity
        core = make_group_by_key_masked(nkeys, capacity)

        def kernel(n, *cols):
            from bigslice_tpu.parallel.segment import compact_by_mask

            size = cols[0].shape[0]
            mask = jnp.arange(size, dtype=np.int32) < n
            is_head, keys, groups_row, counts_row = core(
                mask, tuple(cols[:nkeys]), cols[nkeys]
            )
            n_groups, packed = compact_by_mask(
                is_head, tuple(keys) + (groups_row, counts_row)
            )
            return (n_groups, packed[:nkeys], packed[nkeys],
                    packed[nkeys + 1])

        self._jitted = jit(kernel)

    def __call__(self, key_cols: Sequence, val_col, n: int):
        import jax.numpy as jnp

        size = bucket_size(n)
        cols = pad_cols(list(key_cols) + [val_col], n, size)
        k, keys, groups, counts = self._jitted(jnp.int32(n), *cols)
        k = int(k)
        return (
            [np.asarray(c)[:k] for c in keys],
            np.asarray(groups)[:k],
            np.asarray(counts)[:k],
        )


def make_group_by_key_masked(nkeys: int, capacity: int):
    """Mask-chained grouping core for the mesh executor's SPMD programs:
    ``core(mask, key_cols, val_col) -> (head_mask, keys, groups, counts)``
    where rows stay in sorted position, group-head rows carry the
    [capacity]-wide group matrix row and the true count, and
    ``head_mask`` selects them (compact with the vector-capable
    segment.compact_by_mask)."""
    import jax.numpy as jnp

    from bigslice_tpu.parallel.segment import sort_and_segment

    G = capacity

    def core(mask, key_cols, val_col):
        size = val_col.shape[0]
        s_invalid, s_keys, (s_val,), diff = sort_and_segment(
            nkeys, mask, key_cols, (val_col,)
        )
        valid_row = s_invalid == 0
        is_head = diff & valid_row
        seg_id = jnp.cumsum(diff.astype(np.int32)) - 1
        seg_len_all = jnp.zeros((size + 1,), np.int32).at[
            jnp.where(valid_row, seg_id, size)
        ].add(1, mode="drop")[:size]
        counts_row = seg_len_all[seg_id]
        idx = jnp.arange(size, dtype=np.int32)
        # Segment rows are contiguous post-sort: each head gathers its
        # own [G] window (clipped), masked by the true length.
        offsets = jnp.minimum(
            idx[:, None] + jnp.arange(G, dtype=np.int32)[None, :],
            size - 1,
        )
        gathered = s_val[offsets]
        in_group = (jnp.arange(G, dtype=np.int32)[None, :]
                    < jnp.minimum(counts_row, G)[:, None])
        groups_row = jnp.where(in_group & is_head[:, None], gathered,
                               jnp.zeros((), val_col.dtype))
        counts_row = jnp.where(is_head, counts_row, 0)
        return is_head, list(s_keys), groups_row, counts_row

    return core


_GROUPBY_CACHE: dict = {}


def cached_group_by_key(nkeys: int, capacity: int) -> DeviceGroupByKey:
    """Shared instances per (nkeys, capacity) — repeated construction
    must not recompile (no user fn in the key, unlike the combiner
    caches)."""
    key = (nkeys, capacity)
    kern = _GROUPBY_CACHE.get(key)
    if kern is None:
        kern = _GROUPBY_CACHE[key] = DeviceGroupByKey(nkeys, capacity)
    return kern
