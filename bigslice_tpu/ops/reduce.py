"""Reduce — keyed pairwise combination with map-side combining.

Mirrors bigslice.Reduce (reduce.go:42-78): the input is shuffled by key
prefix; an associative combine function merges values per key, both
*map-side* (in the producer task, before the shuffle — the executor applies
``Slice.combiner()``) and *reduce-side* (in this slice's reader). The
shuffle dep sets ``expand=True`` (reduce.go:70) so partition streams merge
rather than concatenate.

TPU lowering: the combine is the sort+segmented-scan kernel
(parallel/segment.py) on the device tier; when keys or the function live on
the host tier it falls back to dict combining.
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

from bigslice_tpu import typecheck
from bigslice_tpu.frame.frame import Frame
from bigslice_tpu.slicetype import Schema
from bigslice_tpu import sliceio
from bigslice_tpu.ops.base import Combiner, Dep, Slice, make_name
from bigslice_tpu.parallel import segment
from bigslice_tpu.parallel.jitutil import wide_scope


_TRACE_CACHE: dict = {}
_TRACE_CACHE_MAX = 256


def _vals_traceable(fn: Callable, schema: Schema) -> bool:
    """Can `fn` combine this schema's value columns on device?

    Memoized on (fn, value signature): iterative drivers construct the
    same Reduce every round, and the abstract trace below costs more
    than the rest of op construction combined. Keying on the fn OBJECT
    (identity hash, entry holds it alive — no stale id reuse) matches
    the kernel caches' stable-identity contract."""
    if not all(ct.is_device for ct in schema):
        return False
    if any(ct.shape != () for ct in schema.key):
        # Keys must be scalar (sort operands / hashable); VALUE columns
        # may be vectors — the kernels route them via permutation
        # gathers (sort_and_segment) and trailing-dim scatters.
        return False
    try:
        key = (fn, tuple((ct.dtype, ct.shape) for ct in schema.values))
        hit = _TRACE_CACHE.get(key)
    except TypeError:  # unhashable fn: classify uncached
        key = hit = None
    if hit is not None:
        return hit
    with wide_scope(schema.wide):
        out = _vals_traceable_uncached(fn, schema)
    if key is not None:
        _TRACE_CACHE[key] = out
        while len(_TRACE_CACHE) > _TRACE_CACHE_MAX:
            _TRACE_CACHE.pop(next(iter(_TRACE_CACHE)))
    return out


def _vals_traceable_uncached(fn: Callable, schema: Schema) -> bool:
    try:
        import jax

        nvals = len(schema.values)
        cfn = segment.canonical_combine(fn, nvals)
        specs = tuple(
            jax.ShapeDtypeStruct(ct.shape, ct.dtype)
            for ct in schema.values
        )
        out = jax.eval_shape(lambda *v: cfn(v[:nvals], v[nvals:]),
                             *(specs + specs))
        return all(
            o.shape == ct.shape and np.dtype(o.dtype) == np.dtype(ct.dtype)
            for o, ct in zip(out, schema.values)
        )
    except Exception:
        return False


class FrameCombiner:
    """Combines frames by key; device kernel when possible, host dict
    otherwise. This is what executors invoke for map-side combining."""

    def __init__(self, fn: Callable, schema: Schema,
                 dense_keys: Optional[int] = None):
        self.fn = fn
        self.schema = schema
        self.nkeys = schema.prefix
        self.nvals = len(schema) - schema.prefix
        typecheck.check(self.nvals >= 1,
                        "reduce: slice must have at least one value column")
        self.device = _vals_traceable(fn, schema)
        self._kernel = (
            segment.cached_reduce_kernel(fn, self.nkeys, self.nvals)
            if self.device
            else None
        )
        # Dense-key declaration (parallel/dense.py): keys are int32
        # codes in [0, dense_keys) — for a key of several columns, a
        # tuple of per-column sizes (the dictionaries' lengths).
        # dense_ops is the per-column add/max/min classification; None
        # (fn unclassifiable, wrong key shape/dtype, host tier) quietly
        # keeps the sort lowering.
        self.dense_keys = None
        self.dense_ops = None
        # Executors may auto-discover a dense bound from the data (a
        # min/max probe at staging time) when the user declared none.
        # Off by default: Reduce opts in below; JoinAggregate must NOT
        # (its two sides' shuffles have to route identically, which
        # independent per-side discovery can't guarantee).
        self.auto_dense = False
        if dense_keys is not None:
            self.try_declare_dense(dense_keys)

    def dense_eligible(self) -> bool:
        """Structural half of the dense contract: scalar int32 key
        columns on the device tier. (The fn-classification half is
        checked by try_declare_dense.)"""
        return self.device and all(
            np.dtype(ct.dtype) == np.dtype(np.int32) and ct.shape == ()
            for ct in self.schema.key)

    def try_declare_dense(self, dense_keys) -> bool:
        """Declare keys dense in [0, dense_keys) — an int for the one
        key column, a tuple of sizes for a key of several; True if the
        dense lowering engaged. Oversized/invalid bounds quietly keep
        the sort path (callers derive the bound from data size — e.g.
        dictenc's len(vocab) — and must not start crashing when the
        data grows past the table cap). Vector VALUE columns are fine
        (rows scatter whole); the KEY must be scalar."""
        from bigslice_tpu.parallel import dense

        dims = dense.key_dims(dense_keys)
        if not self.dense_eligible() or len(dims) != self.nkeys:
            return False
        ops = None
        if min(dims) > 0 and dense.key_space(dims) <= dense.MAX_DENSE_KEYS:
            ops = dense.classified_ops_cached(
                self.fn, self.nvals,
                tuple(np.dtype(ct.dtype) for ct in self.schema.values),
                tuple(tuple(ct.shape) for ct in self.schema.values),
            )
        if ops is None:
            return False
        self.dense_keys = dims[0] if len(dims) == 1 else dims
        self.dense_ops = ops
        return True

    def retract_dense(self) -> None:
        """Undo an auto-discovered declaration (a later wave proved the
        probed bound wrong): programs rebuilt after this use the sort
        lowering, which is range-agnostic."""
        self.dense_keys = None
        self.dense_ops = None

    def combine(self, frame: Frame) -> Frame:
        """Combine equal keys within one frame."""
        if not len(frame):
            return frame
        if self._kernel is not None:
            keys, vals = self._kernel(
                frame.key_cols(), frame.value_cols(), len(frame)
            )
        else:
            host = frame.to_host()
            keys, vals = segment.host_reduce_by_key(
                host.key_cols(), host.value_cols(), self.fn, self.nvals
            )
        return Frame(list(keys) + list(vals), self.schema)

    def combine_frames(self, frames) -> Frame:
        frames = [f for f in frames if f is not None and len(f)]
        if not frames:
            return Frame.empty(self.schema)
        return self.combine(Frame.concat(frames))


class Reduce(Slice):
    def __init__(self, slice_: Slice, fn: Callable, dense_keys=None):
        """``dense_keys``: optional declaration that the int32 key
        holds dense codes in ``[0, dense_keys)`` — dictionary
        encodings, categorical ids; for a key of several columns, a
        tuple of their sizes (``(len(flags), len(statuses))``). When
        the combine fn classifies as per-column add/max/min, the mesh
        executor lowers the combine to the sort-free dense-table path
        (parallel/dense.py); otherwise the declaration is ignored.
        Keys outside the declared range fail the run loudly."""
        typecheck.check(
            slice_.prefix >= 1, "reduce: input slice must have a key prefix"
        )
        typecheck.check(
            len(slice_.schema) > slice_.prefix,
            "reduce: input slice must have value columns",
        )
        for ct in slice_.schema.key:
            from bigslice_tpu.frame import ops as frame_ops

            typecheck.check(
                frame_ops.can_hash(ct),
                "reduce: key column type %s is not partitionable", ct,
            )
        super().__init__(slice_.schema, slice_.num_shards,
                         make_name("reduce"), pragmas=slice_.pragmas)
        self.dep_slice = slice_
        self.fn = fn
        self._combiner = Combiner(fn, name="reduce")
        self.frame_combiner = FrameCombiner(fn, slice_.schema,
                                            dense_keys=dense_keys)
        # One FrameCombiner serves both the producer shuffle's map-side
        # combine and this slice's reduce-side combine, so an executor
        # discovering a dense key range at the producer automatically
        # wires the consumer too.
        self.frame_combiner.auto_dense = True

    def deps(self):
        return (Dep(self.dep_slice, shuffle=True, partitioner=None,
                    expand=True),)

    def combiner(self):
        return self._combiner

    def reader(self, shard, deps):
        def read():
            out = self.frame_combiner.combine_frames(list(deps[0]()))
            if len(out):
                yield out

        return read()
