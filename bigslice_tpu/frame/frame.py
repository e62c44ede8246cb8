"""Frame — a columnar, struct-of-arrays table.

The framework's rectangular data currency, mirroring ``frame.Frame``
(frame/frame.go:82-95): an ordered tuple of equal-length columns whose
leading ``prefix`` columns are the key. Where the reference builds columns
from reflected Go slices with unsafe copy/zero kernels (frame/unsafe.go),
here a column is either

- a **device** column: numpy/jax numeric array, moved to TPU HBM by the
  executor and operated on by XLA-compiled kernels, or
- a **host** column: numpy object array (strings, lists), which stays on
  the host and is aligned row-wise with the device columns.

O(1) slicing, bulk copy, row hashing over the key prefix, and sort-index
computation are the operations the rest of the system builds on (the
reference's Swap/Less/Hash row ops, frame/frame.go:353-395, become
vectorized column ops here).
"""

from __future__ import annotations

from typing import Any, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from bigslice_tpu.slicetype import ColType, Schema, is_wide
from bigslice_tpu.frame import ops as frame_ops


def _is_jax_array(x) -> bool:
    return type(x).__module__.startswith("jax")


def obj_col(vals) -> np.ndarray:
    """Build a host (object) column from a list of Python values. The
    canonical constructor — plain ``np.asarray`` would try to make
    string/list values into 2-D or unicode arrays."""
    col = np.empty(len(vals), dtype=object)
    col[:] = vals
    return col


def _as_host(col):
    """Bring a column to host numpy."""
    if isinstance(col, np.ndarray):
        return col
    return np.asarray(col)


def _infer_coltype(col) -> ColType:
    dt = np.dtype(col.dtype) if hasattr(col, "dtype") else np.dtype(object)
    if dt == np.dtype(object):
        tag = ""
        for v in col:
            if v is not None:
                tag = {str: "str", bytes: "bytes"}.get(type(v), "")
                break
        return ColType(dt, tag)
    # Route through coltype() so the device-dtype whitelist applies to
    # inferred ndarray columns too (a raw float64 ndarray must not get
    # past _coerce's downcast).
    from bigslice_tpu.slicetype import coltype

    ct = coltype(dt)
    shape = tuple(getattr(col, "shape", (0,))[1:])
    if shape:
        ct = ColType(ct.dtype, ct.tag, shape)
    return ct


def _narrowed(a: np.ndarray, to, j: int) -> np.ndarray:
    """``a`` (64-bit integers, not declared so) as ``to``, or an
    OverflowError naming column ``j`` and the value that does not fit."""
    if a.size:
        info = np.iinfo(to)
        lo, hi = a.min(), a.max()
        if lo < info.min or hi > info.max:
            raise OverflowError(
                f"column {j}: value {lo if lo < info.min else hi} does "
                f"not fit {np.dtype(to)}, which an undeclared {a.dtype} "
                f"column narrows to; declare the column 64-bit "
                f"(schema=Schema([..., np.{a.dtype}, ...]) on a Const, "
                f"out=[..., np.{a.dtype}, ...] on a Map or reader)"
            )
    return a.astype(to)


class Frame:
    """An immutable columnar batch of rows."""

    __slots__ = ("cols", "schema")

    def __init__(self, cols: Sequence[Any], schema: Optional[Schema] = None,
                 prefix: int = 1):
        if schema is not None and len(cols) != len(schema):
            raise ValueError(
                f"frame has {len(cols)} columns but schema has {len(schema)}"
            )
        cols = [self._coerce(c, j, schema[j] if schema is not None else None)
                for j, c in enumerate(cols)]
        if schema is None:
            schema = Schema([_infer_coltype(c) for c in cols], prefix)
        n = None
        for c in cols:
            cn = int(c.shape[0])
            if n is None:
                n = cn
            elif cn != n:
                raise ValueError(f"ragged columns: {cn} != {n}")
        self.cols: Tuple[Any, ...] = tuple(cols)
        self.schema = schema

    @staticmethod
    def _coerce(c, j: int = 0, declared: Optional[ColType] = None):
        """Column ``j`` as it enters a frame. The device tier is
        32-bit-first (TPU-native; see slicetype): an int64 / uint64
        ndarray or a list of Python ints is an int32 / uint32 column —
        unless the schema DECLARES the column 64-bit, which keeps every
        bit. The narrowing is checked (one min/max on the host, paid by
        64-bit inputs only): a value that does not fit raises instead
        of wrapping. float64 narrows to float32 unchecked, as ever."""
        if _is_jax_array(c):
            return c
        if not isinstance(c, np.ndarray):
            a = np.asarray(c)
            if a.dtype == np.dtype(object) or a.dtype.kind in ("U", "S"):
                return obj_col(list(c))
        else:
            a = c
        if declared is not None and is_wide(declared.dtype):
            if a.dtype != declared.dtype and a.dtype.kind in "iub":
                a = a.astype(declared.dtype)
            return a
        if a.dtype == np.int64:
            a = _narrowed(a, np.int32, j)
        elif a.dtype == np.uint64:
            a = _narrowed(a, np.uint32, j)
        elif a.dtype == np.float64:
            a = a.astype(np.float32)
        return a

    # -- constructors -----------------------------------------------------

    @staticmethod
    def from_rows(rows: Sequence[Tuple], schema: Schema) -> "Frame":
        cols = []
        for i, ct in enumerate(schema):
            vals = [r[i] for r in rows]
            if ct.is_device:
                cols.append(
                    np.asarray(vals, dtype=ct.dtype).reshape(
                        (len(vals),) + ct.shape
                    )
                )
            else:
                cols.append(obj_col(vals))
        return Frame(cols, schema)

    @staticmethod
    def empty(schema: Schema) -> "Frame":
        cols = [
            np.empty((0,) + ct.shape,
                     dtype=ct.dtype if ct.is_device else object)
            for ct in schema
        ]
        return Frame(cols, schema)

    # -- basics -----------------------------------------------------------

    def __len__(self) -> int:
        return int(self.cols[0].shape[0]) if self.cols else 0

    @property
    def prefix(self) -> int:
        return self.schema.prefix

    @property
    def num_cols(self) -> int:
        return len(self.cols)

    def col(self, i: int):
        return self.cols[i]

    def key_cols(self) -> Tuple[Any, ...]:
        return self.cols[: self.prefix]

    def value_cols(self) -> Tuple[Any, ...]:
        return self.cols[self.prefix :]

    def slice(self, i: int, j: int) -> "Frame":
        """O(1) row-range view (mirrors frame.Slice, frame/frame.go:246)."""
        return Frame([c[i:j] for c in self.cols], self.schema)

    def take(self, idx) -> "Frame":
        """Gather rows by index array."""
        idx_host = _as_host(idx)
        out = []
        for c in self.cols:
            if isinstance(c, np.ndarray):
                out.append(c[idx_host])
            else:
                out.append(c[idx])
        return Frame(out, self.schema)

    def with_prefix(self, prefix: int) -> "Frame":
        return Frame(self.cols, self.schema.with_prefix(prefix))

    def with_cols(self, cols: Sequence[Any], schema: Schema) -> "Frame":
        return Frame(cols, schema)

    @staticmethod
    def concat(frames: Sequence["Frame"]) -> "Frame":
        frames = [f for f in frames if f is not None]
        if not frames:
            raise ValueError("concat of zero frames")
        if len(frames) == 1:
            return frames[0]
        schema = frames[0].schema
        out = []
        for i in range(len(schema)):
            cols = [_as_host(f.cols[i]) for f in frames]
            out.append(np.concatenate(cols))
        return Frame(out, schema)

    # -- host/device movement --------------------------------------------

    def to_host(self) -> "Frame":
        if all(isinstance(c, np.ndarray) for c in self.cols):
            return self  # immutable; already host-resident
        return Frame([_as_host(c) for c in self.cols], self.schema)

    def device_cols(self) -> List[Any]:
        """The device-tier columns (for shipping into a jitted pipeline)."""
        return [c for c, ct in zip(self.cols, self.schema) if ct.is_device]

    def host_cols(self) -> List[np.ndarray]:
        return [c for c, ct in zip(self.cols, self.schema) if ct.is_host]

    # -- key ops ----------------------------------------------------------

    def hash_keys(self, seed: int = 0) -> np.ndarray:
        """uint32 hash of each row's key prefix.

        Device columns hash with the vectorized murmur mix (XLA-fusable);
        host columns with stable CRC32. Multi-column keys combine in order
        (mirrors Frame.HashWithSeed over prefix, frame/frame.go:381-395).
        """
        if self.prefix == 0:
            raise ValueError("hash_keys on frame with prefix=0")
        h = None
        for c, ct in zip(self.key_cols(), self.schema.key):
            o = frame_ops.ops_for(ct)
            if not o.can_hash:
                raise TypeError(f"column type {ct} is not hashable")
            if ct.is_device:
                ch = frame_ops.hash_device_column(c, seed)
            elif o.hash_fn is not None:
                ch = o.hash_fn(_as_host(c), seed)
            else:
                ch = frame_ops.hash_host_column(_as_host(c), seed)
            h = ch if h is None else frame_ops.combine_hashes(h, ch)
        return h

    def partition_ids(self, nparts: int, seed: int = 0) -> np.ndarray:
        """Shuffle partition for each row: hash(key) % nparts (mirrors the
        default partitioner, exec/compile.go:20-24)."""
        return (self.hash_keys(seed) % np.uint32(nparts)).astype(np.int32)

    def sort_indices(self) -> np.ndarray:
        """Stable argsort of rows by the key prefix (lexicographic)."""
        if self.prefix == 0:
            raise ValueError("sort_indices on frame with prefix=0")
        keys = [_as_host(c) for c in self.key_cols()]
        if any(k.dtype == np.dtype(object) for k in keys):
            n = len(self)
            return np.asarray(
                sorted(range(n), key=lambda i: tuple(k[i] for k in keys)),
                dtype=np.int64,
            )
        # np.lexsort sorts by the *last* key first.
        return np.lexsort(tuple(reversed(keys)))

    def sorted_by_key(self) -> "Frame":
        """Stable sort by the key prefix: one jitted ``lax.sort`` on the
        device for all-scalar-device frames above the dispatch
        threshold; host lexsort otherwise (object keys, vector payload
        columns, tiny frames)."""
        from bigslice_tpu.parallel import sortkernel

        if sortkernel.device_sortable(self):
            return sortkernel.device_sorted_by_key(self)
        return self.take(self.sort_indices())

    # -- row access (tests, scanners, host functions) ---------------------

    def row(self, i: int) -> Tuple:
        out = []
        for c in self.cols:
            v = c[i]
            if getattr(v, "ndim", 0):
                out.append(np.asarray(v))  # vector column cell
            elif isinstance(c, np.ndarray) and c.dtype == object:
                out.append(v)
            else:
                out.append(v.item() if hasattr(v, "item") else v)
        return tuple(out)

    def rows(self) -> Iterator[Tuple]:
        host = self.to_host()
        pycols = []
        for c in host.cols:
            # Object columns and vector columns (ndim>1) keep per-row
            # cells as-is — a nested list would make host-fn arithmetic
            # like `v + v` concatenate instead of adding elementwise.
            if c.dtype == object or c.ndim > 1:
                pycols.append(list(c))
            else:
                pycols.append(c.tolist())
        return iter(zip(*pycols)) if pycols else iter(())

    def to_pylists(self) -> List[list]:
        host = self.to_host()
        return [
            c.tolist() if c.dtype != object else list(c) for c in host.cols
        ]

    def __repr__(self) -> str:
        return (
            f"Frame(n={len(self)}, schema={self.schema})"
        )

    def __eq__(self, other) -> bool:
        if not isinstance(other, Frame):
            return NotImplemented
        if self.schema != other.schema or len(self) != len(other):
            return False
        for a, b in zip(self.to_host().cols, other.to_host().cols):
            if a.dtype == object or b.dtype == object:
                if list(a) != list(b):
                    return False
            elif not np.array_equal(a, b):
                return False
        return True

    def __hash__(self):
        raise TypeError("Frame is not hashable")
