"""Fold — keyed sequential aggregation with a typed accumulator.

Mirrors bigslice.Fold (slice.go:870-955): requires a shuffle dep; each
shard accumulates ``acc = fn(acc, *values)`` per key and emits
``(key, acc)``. Unlike Reduce, the fold function is *not* required to be
associative, so it cannot be map-side combined (slice.go:885).

Two tiers (the reference's typed accumulator maps, accum.go:20-186):
- **device**: jax-traceable fold fns over scalar-device schemas run the
  sort + sequential-``lax.scan`` kernel (segment.DeviceSortedFold) —
  vectorized sort, one fused scan over rows, no per-row Python; also
  mesh-eligible (the fold becomes an SPMD program stage).
- **host**: arbitrary fns / mutable accumulators (callable ``init``) /
  object keys keep the dict loop.
"""

from __future__ import annotations

from typing import Any, Callable

import numpy as np

from bigslice_tpu import typecheck
from bigslice_tpu.slicetype import ColType, Schema
from bigslice_tpu.frame.frame import Frame
from bigslice_tpu import sliceio
from bigslice_tpu.ops.base import Dep, Slice, make_name


class Fold(Slice):
    """``Fold(slice, fn, init, out_value)``.

    ``fn(acc, *vals) -> acc``; ``init`` is the zero accumulator (a value or
    a zero-arg callable); ``out_value`` declares the accumulator column
    type (defaults to the first value column's type).
    """

    def __init__(self, slice_: Slice, fn: Callable, init: Any = 0,
                 out_value=None, dense_keys=None):
        typecheck.check(
            slice_.prefix >= 1, "fold: input slice must have a key prefix"
        )
        typecheck.check(
            len(slice_.schema) > slice_.prefix,
            "fold: input slice must have value columns",
        )
        from bigslice_tpu.frame import ops as frame_ops

        for ct in slice_.schema.key:
            typecheck.check(
                frame_ops.can_hash(ct),
                "fold: key column type %s is not partitionable", ct,
            )
        acc_type = (
            out_value
            if out_value is not None
            else slice_.schema.cols[slice_.prefix]
        )
        schema = Schema(
            list(slice_.schema.key) + [acc_type], prefix=slice_.prefix
        )
        super().__init__(schema, slice_.num_shards, make_name("fold"),
                         pragmas=slice_.pragmas)
        self.dep_slice = slice_
        self.fn = fn
        self.init = init
        self.acc_dtype = schema.cols[slice_.prefix].dtype
        self.device = self._device_eligible()
        # ``dense_keys``: single int32 key holds dense codes in
        # [0, dense_keys); classified associative fold fns take the
        # sort-free scatter-table lowering (parallel/dense.py) —
        # ignored otherwise (Reduce's dense_keys contract).
        self.dense_keys = None
        self.dense_op = None
        # Executors may auto-discover the bound from a staging-time
        # key-range probe (FrameCombiner.auto_dense contract).
        self.auto_dense = True
        if dense_keys is not None:
            self.try_declare_dense(dense_keys)

    def dense_eligible(self) -> bool:
        return (self.device and self.dep_slice.prefix == 1
                and len(self.dep_slice.schema) == 2
                and np.dtype(self.dep_slice.schema.cols[0].dtype)
                == np.dtype(np.int32)
                and self.dep_slice.schema.cols[0].shape == ()
                and self.dep_slice.schema.cols[1].shape == ()
                and not callable(self.init))

    def try_declare_dense(self, dense_keys: int) -> bool:
        if not self.dense_eligible():
            return False
        from bigslice_tpu.parallel import dense

        op = None
        if 0 < dense_keys <= dense.MAX_DENSE_KEYS:
            op = dense.classified_fold_op_cached(
                self.fn, np.dtype(self.acc_dtype),
                np.dtype(self.dep_slice.schema.cols[1].dtype),
            )
        if op is None:
            return False
        self.dense_keys = int(dense_keys)
        self.dense_op = op
        return True

    def retract_dense(self) -> None:
        self.dense_keys = None
        self.dense_op = None

    def _device_eligible(self) -> bool:
        """Traceable fold fn + scalar device schema + literal init →
        the sort+scan kernel serves this fold."""
        if callable(self.init):
            return False  # mutable/stateful zero: host semantics
        in_schema = self.dep_slice.schema
        out_ct = self.schema.cols[self.prefix]
        if not all(ct.is_device and ct.shape == ()
                   for ct in list(in_schema) + [out_ct]):
            return False
        try:
            import jax

            from bigslice_tpu.parallel.jitutil import wide_scope

            with wide_scope(in_schema.wide or self.schema.wide):
                acc_spec = jax.ShapeDtypeStruct((), self.acc_dtype)
                val_specs = [jax.ShapeDtypeStruct((), ct.dtype)
                             for ct in in_schema.values]
                out = jax.eval_shape(self.fn, acc_spec, *val_specs)
            if isinstance(out, (tuple, list)):
                return False
            return out.shape == ()
        except Exception:
            return False

    def deps(self):
        return (Dep(self.dep_slice, shuffle=True),)

    def _zero(self):
        return self.init() if callable(self.init) else self.init

    def reader(self, shard, deps):
        if self.device:
            return self._read_device(deps)
        return self._read_host(deps)

    def _read_device(self, deps):
        def read():
            from bigslice_tpu.parallel import segment

            frame = sliceio.read_all(deps[0](), self.dep_slice.schema)
            if not len(frame):
                return
            host = frame.to_host()
            nk = self.prefix
            kern = segment.cached_sorted_fold(
                self.fn, nk, len(self.dep_slice.schema) - nk,
                self.init, self.acc_dtype,
            )
            keys, accs = kern(list(host.key_cols()),
                              list(host.value_cols()), len(host))
            yield Frame(list(keys) + list(accs), self.schema)

        return read()

    def _read_host(self, deps):
        def read():
            acc = {}
            order = []
            for f in deps[0]():
                host = f.to_host()
                nk = host.prefix
                for r in host.rows():
                    k, vals = r[:nk], r[nk:]
                    if k not in acc:
                        acc[k] = self._zero()
                        order.append(k)
                    acc[k] = self.fn(acc[k], *vals)
            rows = [k + (acc[k],) for k in order]
            for i in range(0, len(rows), sliceio.DEFAULT_CHUNK_ROWS):
                yield Frame.from_rows(
                    rows[i : i + sliceio.DEFAULT_CHUNK_ROWS], self.schema
                )

        return read()
