"""Device-tier keyed reduction: sort + segmented associative scan.

This is the TPU-native replacement for the reference's open-addressed
hash-table combiner (combiningFrame, exec/combiner.go:56-209) and its
sortio spill/merge path: rows are sorted by key with ``lax.sort`` (multi-
operand, stable), segment boundaries are found by adjacent-key comparison,
and an arbitrary *associative* user combine function is applied per segment
via a segmented ``lax.associative_scan`` — O(log n) depth, fully
parallel, no data-dependent control flow (XLA-friendly, SURVEY.md §7.1).

Ragged batch sizes are handled by bucket padding with a validity sort key:
padded rows sort last and form their own segments, so results are exact
for the valid region (parallel/jitutil.py rationale).
"""

from __future__ import annotations

from typing import Callable, Sequence, Tuple

import numpy as np

from bigslice_tpu.parallel.jitutil import bucket_size, jit, pad_cols
from bigslice_tpu.frame.frame import obj_col as _obj_col


def canonical_combine(fn: Callable, nvals: int) -> Callable:
    """Normalize a user combine fn to ``cfn(a_tuple, b_tuple) -> tuple``.

    Single-value-column reduces use the natural ``fn(a, b) -> v`` form
    (mirroring bigslice.Reduce's ``func(v, w) V``, reduce.go:42).
    """
    if nvals == 1:
        return lambda a, b: (fn(a[0], b[0]),)

    def cfn(a, b):
        out = fn(a, b)
        if not isinstance(out, tuple):
            out = tuple(out)
        return out

    return cfn


def sort_with_payload(sort_keys, num_keys: int, payload):
    """Stable-sort rows by ``sort_keys`` (scalar int/float columns)
    carrying ``payload`` columns along — THE shared idiom for every
    keyed kernel. Scalar payloads ride the multi-operand sort directly;
    vector payloads (trailing dims — e.g. [n, d] k-means point sums)
    can't be sort operands, so the sort instead carries a permutation
    and every payload column moves with one gather. Returns
    (sorted_key_tuple, sorted_payload_tuple)."""
    import jax.numpy as jnp
    from jax import lax

    sort_keys = tuple(sort_keys)
    payload = tuple(payload)
    if any(getattr(c, "ndim", 1) > 1 for c in payload):
        size = sort_keys[0].shape[0]
        iota = jnp.arange(size, dtype=np.int32)
        s = lax.sort(sort_keys + (iota,), num_keys=num_keys,
                     is_stable=True)
        perm = s[-1]
        return s[:num_keys], tuple(
            jnp.take(c, perm, axis=0) for c in payload
        )
    s = lax.sort(sort_keys + payload, num_keys=num_keys, is_stable=True)
    return s[:num_keys], s[num_keys:]


def sort_and_segment(nkeys: int, valid_mask, key_cols, payload):
    """Shared prelude for keyed kernels: stable-sort rows by (validity,
    keys) with payload columns riding along, and mark segment starts
    (row 0, any key change, validity change; invalid rows isolate into
    their own segments). Returns (s_invalid, s_keys, s_payload, diff)."""
    import jax.numpy as jnp

    size = key_cols[0].shape[0]
    invalid = (~valid_mask).astype(np.int32)
    sorted_keys, s_payload = sort_with_payload(
        (invalid,) + tuple(key_cols), 1 + nkeys, payload
    )
    s_invalid = sorted_keys[0]
    s_keys = sorted_keys[1:]
    diff = jnp.zeros(size, dtype=bool).at[0].set(True)
    for k in (s_invalid,) + tuple(s_keys):
        diff = diff.at[1:].set(diff[1:] | (k[1:] != k[:-1]))
    diff = diff | (s_invalid == 1)
    return s_invalid, s_keys, s_payload, diff


#: Sort key of a row outside the mask in ``group_by_lane``: above every
#: lane a valid row can carry, so those rows go last.
_NO_LANE = np.iinfo(np.int32).max


def zero_rows_unless(live, col):
    """``col`` with the rows outside the bool mask ``live`` (the
    leading dims of ``col``) read as zeros; trailing dims follow their
    row."""
    import jax.numpy as jnp

    return jnp.where(
        live.reshape(live.shape + (1,) * (col.ndim - live.ndim)), col,
        jnp.zeros_like(col),
    )


def group_by_lane(mask, lane, payload):
    """Front-pack the rows selected by ``mask`` GROUPED by the int32
    column ``lane`` (ascending), order kept inside a lane: ONE stable
    single-key sort with the payload riding along (vector columns by
    permutation, ``sort_with_payload``). Returns (count, lane,
    payload): count = selected rows, the tail reads as zeros, and every
    lane's rows are contiguous, so a consumer cuts a lane out as a
    slice instead of scattering to it."""
    import jax.numpy as jnp

    key = jnp.where(mask, lane, _NO_LANE)
    (s_key,), s_payload = sort_with_payload((key,), 1, payload)
    count = mask.sum().astype(np.int32)
    live = jnp.arange(key.shape[0], dtype=np.int32) < count
    return count, zero_rows_unless(live, s_key), tuple(
        zero_rows_unless(live, c) for c in s_payload
    )


def compact_by_mask(mask, cols):
    """Front-compact rows selected by ``mask`` (stable; preserves the
    relative order of survivors). Returns (count, cols); the vacated
    tail reads as zeros (callers slice to ``count``). The one shared
    implementation of the capacity+validity → front-packed conversion.

    It is ``group_by_lane`` with every survivor in one lane: ONE stable
    single-key sort carrying every column. A scatter to the survivors'
    ranks runs row by row on the TPU — one 2^17-row column costs what
    three sorts of all columns do (PERF.md §5, PR 31)."""
    import jax.numpy as jnp

    count, _, packed = group_by_lane(
        mask, jnp.zeros(mask.shape, np.int32), cols
    )
    return count, packed


def segmented_combine(diff, s_vals, cfn):
    """Apply an associative combine within each segment of sorted rows.

    ``diff`` marks segment starts; returns ``(is_last, reduced)`` where
    ``is_last`` marks each segment's final row (which holds the full
    segment reduction in ``reduced``). Shared by the standalone reduce
    core and the fused combine+shuffle kernel (parallel/shuffle.py).
    """
    import jax.numpy as jnp
    from jax import lax

    size = diff.shape[0]

    def scan_op(x, y):
        fx, vx = x
        fy, vy = y
        merged = cfn(vx, vy)
        # Broadcast the boundary flag over any trailing (vector) dims.
        return (fx | fy, tuple(
            jnp.where(fy.reshape(fy.shape + (1,) * (b.ndim - 1)), b, m)
            for b, m in zip(vy, merged)
        ))

    _, red = lax.associative_scan(scan_op, (diff, tuple(s_vals)))
    is_last = jnp.ones(size, dtype=bool).at[:-1].set(diff[1:])
    return is_last, tuple(red)


def carry_segment_head(diff, cols):
    """Every row reads its segment's FIRST row: the segmented scan of
    ``segmented_combine`` with "keep the earlier" as the combine.
    ``diff`` marks segment starts; returns the carried columns. What a
    lookup join needs after its sort — the build row leads its key's
    segment, and the probe rows behind it read its values."""
    _, carried = segmented_combine(diff, cols, lambda a, b: a)
    return carried


def make_segmented_reduce_masked(nkeys: int, nvals: int, cfn,
                                 compact: bool = False):
    """Mask-based variant of the segmented reduce core.

    ``core(valid_mask, key_cols, val_cols)`` reduces the rows selected by
    ``valid_mask`` (bool[size]). With ``compact=False`` it returns
    ``(keep_mask, keys, vals)`` — reduced rows *in sorted position* with
    a survivor mask, skipping the compaction sort entirely (chained
    stages that accept masks, e.g. the shuffle, don't need front-packed
    rows). With ``compact=True`` it returns ``(count, keys, vals)``
    front-compacted (the output contract).
    """

    def core(valid_mask, key_cols, val_cols):
        s_invalid, s_keys, s_vals, diff = sort_and_segment(
            nkeys, valid_mask, key_cols, val_cols
        )
        is_last, red = segmented_combine(diff, s_vals, cfn)
        keep = is_last & (s_invalid == 0)
        if not compact:
            return keep, s_keys, tuple(red)
        count, packed = compact_by_mask(keep, tuple(s_keys) + tuple(red))
        return count, packed[:nkeys], packed[nkeys:]

    return core


def make_segmented_reduce(nkeys: int, nvals: int, cfn):
    """Count-based wrapper over the masked core: ``core(n, key_cols,
    val_cols) -> (count, keys, vals)`` with survivors front-compacted
    (sorted by key). One kernel body serves both this and the mask-
    chained mesh stages.
    """
    import jax.numpy as jnp

    masked = make_segmented_reduce_masked(nkeys, nvals, cfn, compact=True)

    def core(n, key_cols, val_cols):
        size = key_cols[0].shape[0]
        mask = jnp.arange(size, dtype=np.int32) < n
        return masked(mask, key_cols, val_cols)

    return core


class DeviceReduceByKey:
    """Jitted keyed reduction over device columns.

    ``__call__(key_cols, val_cols, n)`` returns host-compacted
    ``(key_cols, val_cols)`` with one row per distinct key, sorted by key.
    Compiled once per (nkeys, nvals, dtypes, bucket) — the jit cache stays
    bounded thanks to power-of-two bucketing.
    """

    def __init__(self, fn: Callable, nkeys: int, nvals: int):
        import jax

        cfn = canonical_combine(fn, nvals)
        self.nkeys = nkeys
        self.nvals = nvals
        core = make_segmented_reduce(nkeys, nvals, cfn)

        def kernel(n, *cols):
            return core(n, cols[:nkeys], cols[nkeys:])

        self._jitted = jit(kernel)

    def __call__(self, key_cols: Sequence, val_cols: Sequence, n: int):
        import jax.numpy as jnp

        size = bucket_size(n)
        cols = pad_cols(list(key_cols) + list(val_cols), n, size)
        count, keys, vals = self._jitted(jnp.int32(n), *cols)
        count = int(count)
        return (
            [np.asarray(k)[:count] for k in keys],
            [np.asarray(v)[:count] for v in vals],
        )


# Keyed by id(fn) with an aliveness guard; bounded FIFO (see
# jitutil._VMAP_CACHE rationale).
_KERNEL_CACHE: dict = {}
_KERNEL_CACHE_MAX = 128


def cached_reduce_kernel(fn: Callable, nkeys: int, nvals: int
                         ) -> DeviceReduceByKey:
    """Share DeviceReduceByKey instances (and their jit caches) across
    combiners built from the same function object — iterative sessions
    re-running the same Reduce then compile once, not once per run."""
    import weakref

    key = (id(fn), nkeys, nvals)
    entry = _KERNEL_CACHE.get(key)
    if entry is not None:
        ref, kern = entry
        if ref is None or ref() is fn:
            return kern
    kern = DeviceReduceByKey(fn, nkeys, nvals)
    try:
        ref = weakref.ref(fn)
    except TypeError:  # unweakrefable callables
        ref = None
    _KERNEL_CACHE[key] = (ref, kern)
    while len(_KERNEL_CACHE) > _KERNEL_CACHE_MAX:
        _KERNEL_CACHE.pop(next(iter(_KERNEL_CACHE)))
    return kern


def make_sequential_fold_masked(nkeys: int, nvals: int, fold_fn,
                                init_val, acc_dtype):
    """Device-tier keyed Fold: sort by key, then one ``lax.scan`` over
    rows folds each segment sequentially (``acc = fn(acc, *vals)``).

    Fold functions are NOT required to be associative (bigslice.Fold,
    slice.go:885), so the parallel associative-scan kernel can't serve
    them; the scan is O(rows) sequential steps with a fused tiny body —
    still orders of magnitude faster than the per-row Python dict loop
    it replaces, and it keeps Fold mesh-eligible.

    ``core(valid_mask, key_cols, val_cols) -> (keep_mask, keys,
    (accs,))`` with reduced rows in sorted position (mask-chained
    contract, like make_segmented_reduce_masked(compact=False)).
    """
    import jax.numpy as jnp
    from jax import lax

    def core(valid_mask, key_cols, val_cols):
        size = key_cols[0].shape[0]
        s_invalid, s_keys, s_vals, diff = sort_and_segment(
            nkeys, valid_mask, key_cols, val_cols
        )
        zero = jnp.asarray(init_val, dtype=acc_dtype)

        def step(carry, x):
            is_start, vals = x[0], x[1:]
            acc = jnp.where(is_start, zero, carry)
            acc = jnp.asarray(fold_fn(acc, *vals)).astype(acc_dtype)
            return acc, acc

        _, accs = lax.scan(step, zero, (diff,) + tuple(s_vals))
        is_last = jnp.ones(size, dtype=bool).at[:-1].set(diff[1:])
        keep = is_last & (s_invalid == 0)
        return keep, s_keys, (accs,)

    return core


class DeviceSortedFold:
    """Jitted host-callable wrapper over the sequential fold kernel:
    ``__call__(key_cols, val_cols, n) -> (keys, [accs])`` compacted,
    key-sorted (one row per distinct key)."""

    def __init__(self, fold_fn, nkeys: int, nvals: int, init_val,
                 acc_dtype):
        import jax
        import jax.numpy as jnp

        core = make_sequential_fold_masked(
            nkeys, nvals, fold_fn, init_val, acc_dtype
        )

        def kernel(n, *cols):
            size = cols[0].shape[0]
            mask = jnp.arange(size, dtype=np.int32) < n
            keep, keys, accs = core(mask, cols[:nkeys], cols[nkeys:])
            count, packed = compact_by_mask(
                keep, tuple(keys) + tuple(accs)
            )
            return count, packed[:nkeys], packed[nkeys:]

        self._jitted = jit(kernel)

    def __call__(self, key_cols, val_cols, n: int):
        import jax.numpy as jnp

        size = bucket_size(n)
        cols = pad_cols(list(key_cols) + list(val_cols), n, size)
        count, keys, accs = self._jitted(jnp.int32(n), *cols)
        count = int(count)
        return (
            [np.asarray(k)[:count] for k in keys],
            [np.asarray(a)[:count] for a in accs],
        )


_FOLD_CACHE: dict = {}
_FOLD_CACHE_MAX = 128


def cached_sorted_fold(fn, nkeys: int, nvals: int, init_val,
                       acc_dtype) -> DeviceSortedFold:
    """Share DeviceSortedFold instances across Fold reconstructions
    (same id-keyed weakref pattern as cached_reduce_kernel)."""
    import weakref

    key = (id(fn), nkeys, nvals, repr(init_val), str(acc_dtype))
    entry = _FOLD_CACHE.get(key)
    if entry is not None:
        ref, kern = entry
        if ref is None or ref() is fn:
            return kern
    kern = DeviceSortedFold(fn, nkeys, nvals, init_val, acc_dtype)
    try:
        ref = weakref.ref(fn)
    except TypeError:  # unweakrefable callables
        ref = None
    _FOLD_CACHE[key] = (ref, kern)
    while len(_FOLD_CACHE) > _FOLD_CACHE_MAX:
        _FOLD_CACHE.pop(next(iter(_FOLD_CACHE)))
    return kern


HOST_REDUCEAT = {"add": np.add, "max": np.maximum, "min": np.minimum}


def grouped_reduceat(key_cols, val_cols, ops):
    """Segmented reduce of KEY-SORTED host columns: group boundaries
    from adjacent key change, one classified ``ufunc.reduceat`` per
    value column. The one shared implementation of the idiom (used by
    the host combiner here and sortio's streaming reduce) — float sums
    follow reduceat's blocking, the documented reassociation
    contract. Returns (keys_at_bounds, reduced_vals)."""
    n = len(key_cols[0])
    diff = np.zeros(n, dtype=bool)
    diff[0] = True
    for c in key_cols:
        c = np.asarray(c)
        diff[1:] |= c[1:] != c[:-1]
    bounds = np.flatnonzero(diff)
    keys_out = [np.asarray(c)[bounds] for c in key_cols]
    vals_out = [
        HOST_REDUCEAT[op].reduceat(np.asarray(c), bounds, axis=0)
        for op, c in zip(ops, val_cols)
    ]
    return keys_out, vals_out


def classified_host_ops(fn, nvals: int, val_cols):
    """Per-column add/max/min classification for host columns (memoized
    through dense.classified_ops_cached); None for object columns,
    empty input, unhashable fns, or unclassified semantics."""
    if not val_cols or not len(val_cols[0]):
        return None
    if any(getattr(c, "dtype", np.dtype(object)) == np.dtype(object)
           for c in val_cols):
        return None
    from bigslice_tpu.parallel.dense import classified_ops_cached

    try:
        return classified_ops_cached(
            fn, nvals,
            tuple(np.asarray(c).dtype for c in val_cols),
            tuple(np.asarray(c).shape[1:] for c in val_cols),
        )
    except TypeError:  # unhashable fn: classify is skipped, not run
        return None


def host_reduce_by_key(key_cols, val_cols, fn, nvals: int):
    """Host-tier fallback keyed reduction (object keys / non-traceable fn).

    Combine fns that classify as per-column add/max/min (the probe the
    dense/hash-aggregate tiers trust) with numeric value columns take
    a vectorized lexsort + ``reduceat`` pass — string keys compare in
    C inside np.lexsort, so no per-row Python remains; incomparable
    key types (lexsort TypeError) and unclassified fns keep the exact
    dict pass. Output is key-sorted either way (the dict pass sorts at
    emit), and float sums agree modulo reassociation — the same
    contract as the device tier's tree scan.
    """
    n = len(key_cols[0])
    ops = classified_host_ops(fn, nvals, val_cols)
    if ops is not None:
        try:
            order = np.lexsort(
                tuple(reversed([np.asarray(c) for c in key_cols]))
            )
        except TypeError:
            order = None  # incomparable keys: dict pass below
        if order is not None:
            return grouped_reduceat(
                [np.asarray(c)[order] for c in key_cols],
                [np.asarray(c)[order] for c in val_cols],
                ops,
            )

    cfn = canonical_combine(fn, nvals)
    acc = {}
    order = []
    for i in range(n):
        k = tuple(c[i] for c in key_cols)
        v = tuple(c[i] for c in val_cols)
        if k in acc:
            acc[k] = cfn(acc[k], v)
        else:
            acc[k] = v
            order.append(k)
    # Emit key-sorted, matching the device kernel — combined partition
    # streams must be sorted for the expand (merge) read path.
    try:
        order.sort()
    except TypeError:
        pass  # incomparable key types: emit in insertion order
    keys_out = []
    for j, col in enumerate(key_cols):
        vals = [k[j] for k in order]
        if getattr(col, "dtype", None) == np.dtype(object):
            keys_out.append(_obj_col(vals))
        else:
            keys_out.append(np.asarray(vals, dtype=col.dtype))
    vals_out = []
    for j in range(nvals):
        vals = [acc[k][j] for k in order]
        col = val_cols[j]
        if getattr(col, "dtype", None) == np.dtype(object):
            vals_out.append(_obj_col(vals))
        else:
            vals_out.append(np.asarray(vals, dtype=col.dtype))
    return keys_out, vals_out



