"""Dense-keyed combine + shuffle: the sort-free reduce path.

When a Reduce's keys are dense int32 codes in ``[0, K)`` — dictionary
encodings (frame/dictenc.py), categorical ids, bucketed features — the
sort-dominated combine+shuffle pipeline (parallel/shuffle.py
make_combine_shuffle_fn) collapses to:

  1. per-shard dense value tables, one accumulate pass over the rows
     (no sorts, no overflow slack, no retries);
  2. ONE all_to_all of the tables, pre-gathered through a *static*
     routing permutation so each device receives exactly the table
     slots of its own partition;
  3. an elementwise reduction over the received per-shard planes.

This is the BASELINE north star's "combiners lower to
psum/reduce-scatter" realized literally (an all_to_all + local reduce
is reduce_scatter generalized to max/min). The routing permutation is
computed from the SAME ``partition_ids`` contract as the sorting
shuffle — key k lands on the same device under either lowering, so
consumers (including other deps of a Cogroup/JoinAggregate compiled
through the sort path) stay aligned.

Eligibility is decided by the executor (meshexec): single int32 key,
a declared ``dense_keys`` bound, a combine fn that classifies as
per-column add/max/min (``classify_combine_ops``), no custom
partitioner. Keys outside ``[0, K)`` raise through the shuffle's
bad-partition signal rather than silently dropping.

The reference has no analog (its combiningFrame is always a hash
table, exec/combiner.go:56-99); this is a TPU-first specialization the
hardware rewards: scatter-accumulate + collectives instead of
comparison sorts.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Optional, Sequence, Tuple

import numpy as np

from bigslice_tpu.parallel.jitutil import any_wide, wide_scope

# Largest declared key space the dense path accepts: beyond this the
# per-shard tables (K rows x nvals columns) start competing with the
# data itself for memory and the sort pipeline wins anyway.
MAX_DENSE_KEYS = 1 << 22

# Largest table filled by compare-and-sum instead of scatters: one
# [slots, rows] compare a column, which the TPU's vector unit does at
# memory speed, where a scatter of a wave's rows runs row by row
# (PERF.md §5: 2^17 rows into 2 or 5 bins, 0.02 ms against 1.17 ms).
SMALL_TABLE = 128


def key_dims(dense_keys) -> Tuple[int, ...]:
    """A ``dense_keys`` declaration as per-key-column sizes: an int for
    one key column, a tuple (the dictionaries' sizes) for several."""
    if isinstance(dense_keys, (tuple, list)):
        return tuple(int(d) for d in dense_keys)
    return (int(dense_keys),)


def key_space(dense_keys) -> int:
    """How many keys a declaration spans (the table's rows)."""
    return int(np.prod(key_dims(dense_keys), dtype=np.int64))


def dense_code(keys, dims):
    """``(code, in_range)``: the key columns as one row-major dense
    code in ``[0, prod(dims))``, and whether every column is inside its
    declared range (the code of a row that is not means nothing)."""
    code = ok = None
    for k, d in zip(keys, dims):
        inside = (k >= 0) & (k < d)
        ok = inside if ok is None else ok & inside
        code = k if code is None else code * np.int32(d) + k
    return code, ok


def dense_decode(code, dims) -> tuple:
    """The key columns of dense codes (``dense_code``'s inverse)."""
    cols = []
    for d in reversed(dims[1:]):
        cols.append(code % np.int32(d))
        code = code // np.int32(d)
    return tuple(reversed(cols + [code]))


def classify_combine_ops(cfn, val_dtypes: Sequence,
                         val_shapes: Optional[Sequence] = None
                         ) -> Optional[Tuple[str, ...]]:
    """Classify a canonical combine fn as per-column ('add'|'max'|'min')
    by probing it on random vectors of the actual value dtypes (and
    trailing shapes — vector value columns classify too); None when any
    column doesn't match (the sort path handles it).

    A user fn that equals one of the candidates on 64 random pairs per
    column but diverges elsewhere is implausible; cross-column fns
    (col j reading side b's column i) diverge on the probe and
    classify None.
    """
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    if val_shapes is None:
        val_shapes = [() for _ in val_dtypes]
    a = [_probe_sample(rng, dt, sh, slot=0) for dt, sh in
         zip(val_dtypes, val_shapes)]
    b = [_probe_sample(rng, dt, sh, slot=1) for dt, sh in
         zip(val_dtypes, val_shapes)]
    if any(x is None for x in a):
        return None
    try:
        import jax

        # Probe scalar-wise under vmap — the same application shape the
        # segment kernels use, so anything the device tier accepts
        # classifies consistently.
        with wide_scope(any_wide(a)):
            out = jax.vmap(lambda xs, ys: cfn(xs, ys))(
                tuple(jnp.asarray(x) for x in a),
                tuple(jnp.asarray(x) for x in b),
            )
        out = [np.asarray(o) for o in out]
    except Exception:
        return None
    ops = []
    for x, y, o in zip(a, b, out):
        op = _match_op(o, x, y)
        if op is None:
            return None
        ops.append(op)
    return tuple(ops)


_PROBE_N = 64


def _probe_sample(rng, dt, shape=(), slot=0):
    """Random sample with dtype extremes planted so range-dependent fns
    (saturating/clipped add, anything that coincides with add/max/min
    only on small values) fail classification and stay on the sort path,
    which honors the real fn. Extremes land at disjoint positions per
    operand slot (the other operand stays small there) so a genuine
    float add never sees inf + -inf → NaN and misclassifies."""
    dt = np.dtype(dt)
    full = (_PROBE_N,) + tuple(shape)
    if dt.kind == "f":
        out = (rng.randn(*full) * 8).astype(dt)
        extremes = [np.inf, -np.inf, 0.0, 1e30, -1e30]
    elif dt.kind in "iu":
        lo, hi = (-(1 << 15), 1 << 15) if dt.kind == "i" else (0, 1 << 16)
        out = rng.randint(lo, hi, full).astype(dt)
        info = np.iinfo(dt)
        extremes = [info.min, info.max, 0]
    else:
        return None
    base = slot * len(extremes)
    for i, v in enumerate(extremes):
        out[base + i] = dt.type(v)
    return out


def _match_op(out, x, y):
    """Which of add/max/min does ``out`` equal on this probe pair?"""
    if out.dtype != x.dtype or out.shape != x.shape:
        return None
    if np.array_equal(out, x + y):
        return "add"
    if np.array_equal(out, np.maximum(x, y)):
        return "max"
    if np.array_equal(out, np.minimum(x, y)):
        return "min"
    return None


@functools.lru_cache(maxsize=256)
def classified_ops_cached(fn, nvals: int, val_dtypes: tuple,
                          val_shapes: tuple = None
                          ) -> Optional[Tuple[str, ...]]:
    """Memoized classify_combine_ops keyed on the fn object + value
    dtypes/shapes: iterative drivers rebuild Reduce slices every round
    (the id(fn)-keyed program caches depend on exactly that), and the
    vmap probe must not recur per step. The cache pins fn, like the
    program caches do."""
    from bigslice_tpu.parallel import segment

    return classify_combine_ops(
        segment.canonical_combine(fn, nvals), list(val_dtypes),
        list(val_shapes) if val_shapes is not None else None,
    )


def _identity(op: str, dtype) -> np.generic:
    dt = np.dtype(dtype)
    if op == "add":
        return dt.type(0)
    if op == "max":
        return dt.type(-np.inf) if dt.kind == "f" else np.iinfo(dt).min
    if op == "min":
        return dt.type(np.inf) if dt.kind == "f" else np.iinfo(dt).max
    raise ValueError(op)


def table_column_lowering(size: int, shape) -> str:
    """How the table pass fills one value column of a ``size``-slot
    table: ``"compare"`` (a masked reduction over the [size, rows]
    compare: a scalar column of a table of at most ``SMALL_TABLE``
    slots) or ``"scatter"`` (any vector column, and every column of a
    larger table). ``shape`` is the column's per-row shape. A float32
    vector column keeps its scatter: a one-hot contraction on a v5e's
    MXU does not sum as float32 does — at HIGHEST precision, or over
    three exact bfloat16 parts, the sums lean low (PERF.md §6), which
    ``chip_smoke.py``'s ``dense-table`` phase would catch. ONE source of
    truth: ``_scatter_tables`` branches on it and the ``table``
    telemetry block counts it."""
    if size <= SMALL_TABLE and not tuple(shape):
        return "compare"
    return "scatter"


# The tables a trace fills, for the group program being traced
# (``recording_tables``): each a (slots, per-column lowerings) pair.
_SINK = threading.local()


@contextlib.contextmanager
def recording_tables(sink: list):
    """Within the block, every table this thread's trace fills is
    appended to ``sink`` as ``(slots, lowerings)``: host data the
    executor's ``table`` telemetry block counts a wave, with no device
    read."""
    prev = getattr(_SINK, "tables", None)
    _SINK.tables = sink
    try:
        yield
    finally:
        _SINK.tables = prev


def _scatter_column(idx, v, op, ident, size: int):
    """One value column by scatter-accumulate into an
    identity-initialized [size(, ...trailing)] table."""
    import jax.numpy as jnp

    upd = jnp.full((size,) + tuple(v.shape[1:]), ident, v.dtype).at[idx]
    return (upd.add(v) if op == "add"
            else upd.max(v) if op == "max"
            else upd.min(v))


def _scatter_tables(idx, vals, ops, idents, size: int):
    """The shared table pass: (present bool[size], tables), one
    [size(, ...trailing)] table a value column (idx == size-1 may serve
    as the caller's drop lane). Each column takes the lowering
    ``table_column_lowering`` picks; in a table of at most
    ``SMALL_TABLE`` slots ``present`` is the compare's any."""
    import jax.numpy as jnp

    how = [table_column_lowering(size, v.shape[1:]) for v in vals]
    sink = getattr(_SINK, "tables", None)
    if sink is not None:
        sink.append((size, tuple(how)))
    if size > SMALL_TABLE:
        present = jnp.zeros((size,), bool).at[idx].set(True)
    else:
        # Few slots: each is a masked reduction over the rows.
        hit = idx[None, :] == jnp.arange(size, dtype=np.int32)[:, None]
        present = hit.any(axis=1)
    reduce_ = {"add": jnp.sum, "max": jnp.max, "min": jnp.min}
    return present, [
        reduce_[op](jnp.where(hit, v[None, :], ident),
                    axis=1).astype(v.dtype)
        if h == "compare" else _scatter_column(idx, v, op, ident, size)
        for v, op, ident, h in zip(vals, ops, idents, how)]


@functools.lru_cache(maxsize=32)
def routing_tables(K: int, nparts: int, seed: int) -> Tuple[np.ndarray, int]:
    """Static slot routing: ``slot_table[p]`` lists the keys owned by
    partition p (padded with the ``K`` sentinel), under the SAME
    hash-routing contract as the sorting shuffle (partition_ids with
    the stock XLA path — bit-identical to the Pallas tier, checked on
    the chip by chip_smoke.py's kernels phase). Returns (slot_table int32[nparts, maxc], maxc)."""
    from bigslice_tpu.parallel import shuffle as shuffle_mod

    keys = np.arange(K, dtype=np.int32)
    part, _, _ = shuffle_mod.partition_ids(
        (keys,), nparts, seed, use_pallas=False
    )
    part = np.asarray(part)
    order = np.argsort(part, kind="stable")
    counts = np.bincount(part, minlength=nparts)[:nparts]
    maxc = max(int(counts.max()) if K else 0, 1)
    slot_table = np.full((nparts, maxc), K, dtype=np.int32)
    start = 0
    for p in range(nparts):
        c = int(counts[p])
        slot_table[p, :c] = order[start : start + c]
        start += c
    return slot_table, maxc


def make_dense_combine(dense_keys, ops: Tuple[str, ...],
                       val_dtypes: Sequence):
    """Shuffle-free dense combine for a single partition (or the
    map-side stage before a routing shuffle of the table's rows): one
    accumulate pass into a [K] table, unpacked to (keys, vals) rows
    under a presence mask. ``dense_keys`` is the declaration (an int,
    or the per-column sizes of a several-column key, which becomes one
    row-major code). ``masked(valid, keys, vals) -> (mask, keys, vals)``
    — the make_segmented_reduce_masked contract (output size K instead
    of the input size; downstream mask-chaining handles both)."""
    import jax.numpy as jnp

    dims = key_dims(dense_keys)
    K = key_space(dense_keys)
    idents = [_identity(op, dt) for op, dt in zip(ops, val_dtypes)]

    def masked(valid, keys, vals):
        code, in_range = dense_code(keys, dims)
        # Out-of-range keys route to the drop lane; the CALLER counts
        # them into the pipeline's bad signal (this contract has no
        # channel for it) so declared-range violations still fail the
        # run loudly instead of dropping rows.
        idx = jnp.where(valid & in_range, code, np.int32(K))
        present, tables = _scatter_tables(idx, vals, ops, idents, K + 1)
        out_keys = dense_decode(jnp.arange(K, dtype=np.int32), dims)
        return present[:K], out_keys, tuple(t[:K] for t in tables)

    return masked


@functools.lru_cache(maxsize=32)
def rank_tables(K: int, nparts: int, seed: int):
    """Static inverse routing: for key k, ``pid[k]`` is its owning
    partition and ``rank[k]`` its slot position within that partition's
    ``slot_table`` row. One [K] table each, shared by every device.
    Returns (pid int32[K], rank int32[K], maxc)."""
    slot_table, maxc = routing_tables(K, nparts, seed)
    pid = np.empty(K, dtype=np.int32)
    rank = np.empty(K, dtype=np.int32)
    for p in range(nparts):
        slots = slot_table[p]
        valid = slots != K
        pid[slots[valid]] = p
        rank[slots[valid]] = np.flatnonzero(valid).astype(np.int32)
    return pid, rank, maxc


def make_dense_join(K: int, ops_a: Tuple[str, ...],
                    ops_b: Tuple[str, ...], dtypes_a: Sequence,
                    dtypes_b: Sequence, nparts: int, axis: str,
                    seed: int = 0):
    """Sort-free aggregating inner join for dense-coded keys: each side
    scatter-accumulates into a [maxc] local table indexed by the static
    within-partition rank of its keys (this device holds exactly its
    partition's keys, by the shared routing contract), then the match
    is an elementwise AND of the presence planes — no segmented
    reduces, no alignment sort.

    Returns ``fn(mask_a, cols_a, mask_b, cols_b) -> (mask, cols, bad)``
    with cols = (key, *vals_a, *vals_b), each [maxc]; ``bad`` counts
    rows whose key is outside [0, K) or not owned by this device
    (either violates the declared contract)."""
    import jax.numpy as jnp
    from jax import lax

    slot_table_np, maxc = routing_tables(K, nparts, seed)
    pid_np, rank_np, _ = rank_tables(K, nparts, seed)
    idents_a = [_identity(op, dt) for op, dt in zip(ops_a, dtypes_a)]
    idents_b = [_identity(op, dt) for op, dt in zip(ops_b, dtypes_b)]

    def side(mask, key, vals, ops, idents, pid, rank, me):
        in_range = (key >= 0) & (key < K)
        safe_key = jnp.where(in_range, key, 0)
        owned = in_range & (pid[safe_key] == me)
        bad = jnp.sum((mask & ~owned).astype(np.int32))  # local
        idx = jnp.where(mask & owned, rank[safe_key], np.int32(maxc))
        present, tables = _scatter_tables(idx, vals, ops, idents,
                                          maxc + 1)
        return present[:maxc], [t[:maxc] for t in tables], bad

    def join(mask_a, cols_a, mask_b, cols_b):
        slot_table = jnp.asarray(slot_table_np)
        pid = jnp.asarray(pid_np)
        rank = jnp.asarray(rank_np)
        me = lax.axis_index(axis)
        pa, ta, bad_a = side(mask_a, cols_a[0], cols_a[1:], ops_a,
                             idents_a, pid, rank, me)
        pb, tb, bad_b = side(mask_b, cols_b[0], cols_b[1:], ops_b,
                             idents_b, pid, rank, me)
        my_slots = slot_table[me]
        mask = pa & pb & (my_slots != K)
        # One collective for both sides' bad counts.
        bad = lax.psum(bad_a + bad_b, axis)
        return mask, [my_slots, *ta, *tb], bad

    return join, maxc


@functools.lru_cache(maxsize=256)
def classified_fold_op_cached(fn, acc_dtype, val_dtype) -> Optional[str]:
    """Classify a fold fn ``fn(acc, v) -> acc`` as 'add'|'max'|'min' by
    the same vmap probe (None → the sequential-scan fold runs). A
    classified fold op is associative+commutative, so scatter order is
    immaterial."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    accd, vald = np.dtype(acc_dtype), np.dtype(val_dtype)
    acc, v = _probe_sample(rng, accd), _probe_sample(rng, vald)
    if acc is None or v is None:
        return None
    try:
        with wide_scope(any_wide((acc, v))):
            out = np.asarray(
                jax.vmap(fn)(jnp.asarray(acc), jnp.asarray(v)))
    except Exception:
        return None
    op = _match_op(out, acc, v.astype(accd))
    # Fold's contract is SEQUENTIAL (non-associative fns allowed,
    # slice.go:885), and the scan path honors it bit-for-bit. Float
    # 'add' reassociates under scatter, so the dense lowering would
    # diverge from the sequential result in low bits — keep float sums
    # on the scan path. max/min are exactly associative for floats
    # (NaN propagates identically in either order).
    if op == "add" and accd.kind == "f":
        return None
    return op


def make_dense_fold(K: int, op: str, acc_dtype, init_val):
    """Sort-free dense Fold for classified (associative) fold fns:
    scatter-accumulate into a [K] table, then apply the fold's init
    through the op (``acc = op(init, fold(vals))`` — exactly the
    sequential result for an associative, commutative op). Same
    contract as make_sequential_fold_masked's core."""
    import jax.numpy as jnp

    accd = np.dtype(acc_dtype)
    ident = _identity(op, accd)

    def masked(valid, keys, vals):
        (key,) = keys
        (v,) = vals
        in_range = (key >= 0) & (key < K)
        idx = jnp.where(valid & in_range, key, np.int32(K))
        present, (table,) = _scatter_tables(
            idx, [v.astype(accd)], [op], [ident], K + 1
        )
        table = table[:K]
        init = jnp.asarray(init_val, accd)
        acc = (table + init if op == "add"
               else jnp.maximum(table, init) if op == "max"
               else jnp.minimum(table, init))
        out_key = jnp.arange(K, dtype=np.int32)
        return present[:K], (out_key,), (acc,)

    return masked


def make_dense_combine_shuffle(nmesh: int, K: int, ops: Tuple[str, ...],
                               val_dtypes: Sequence, axis: str,
                               seed: int = 0):
    """Build the dense lowering; ``.masked(valid, key, *vals)`` returns
    ``(recv_valid_mask, overflow, bad, out_cols)`` — the same contract
    as make_combine_shuffle_fn(...).masked (out_cols = key column then
    value columns, front-packing deferred to the caller's compaction).
    Output capacity per device is ``maxc`` rows."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    slot_table_np, maxc = routing_tables(K, nmesh, seed)
    idents = [_identity(op, dt) for op, dt in zip(ops, val_dtypes)]

    def masked(valid, key, *vals):
        slot_table = jnp.asarray(slot_table_np)
        in_range = (key >= 0) & (key < K)
        # psum: the caller reads bad/overflow through a replicated out
        # spec, which takes one device's copy — every device must hold
        # the global count.
        bad = lax.psum(
            jnp.sum((valid & ~in_range).astype(np.int32)), axis
        )
        idx = jnp.where(valid & in_range, key, np.int32(K))

        # 1. Per-shard dense tables: one scatter-accumulate pass (the
        # K-th row is the drop lane for invalid/out-of-range rows).
        present, tables = _scatter_tables(idx, vals, ops, idents, K + 1)

        # 2. Gather through the static routing permutation, then ONE
        # all_to_all: device p receives every shard's partition-p
        # plane.
        def route(x):
            planes = x[slot_table]  # [nmesh, maxc]
            return lax.all_to_all(planes, axis, split_axis=0,
                                  concat_axis=0, tiled=True)

        recv_present = route(present)          # [nmesh, maxc]
        recv_tables = [route(t) for t in tables]

        # 3. Elementwise reduce over the shard planes.
        present_any = jnp.any(recv_present, axis=0)
        out_vals = []
        for r, op in zip(recv_tables, ops):
            out_vals.append(
                jnp.sum(r, axis=0) if op == "add"
                else jnp.max(r, axis=0) if op == "max"
                else jnp.min(r, axis=0)
            )
        my_slots = slot_table[lax.axis_index(axis)]  # [maxc]
        mask = present_any & (my_slots != K)
        # Identity values never leak: masked rows are dropped by the
        # caller's compaction before any consumer sees them.
        return mask, jnp.int32(0), bad, (my_slots, *out_vals)

    class _Body:
        pass

    body = _Body()
    body.masked = masked
    body.capacity = maxc
    return body
