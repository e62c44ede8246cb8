"""Column type tuples — the framework's type system.

Mirrors the reference's ``slicetype`` package (slicetype/slicetype.go:17-27):
a slice's type is an ordered tuple of column types plus a *prefix* count
marking how many leading columns form the key for
shuffling/sorting/grouping.

TPU-first difference: instead of arbitrary Go ``reflect.Type`` columns, a
column is either

- a **device** column: a fixed-width numpy dtype resident as a jax Array
  (int8/16/32/64, uint8/16/32/64, float16/bfloat16/float32, bool), or
- a **host** column: arbitrary Python objects (strings, lists, tuples)
  carried in numpy object arrays on the host, never shipped to the device.

This is the "tier the columns" strategy from SURVEY.md §7.3(2): numeric
work happens on the MXU/VPU; variable-width payloads ride along on the host
and are rejoined at the edges.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Iterable, Sequence, Tuple

import numpy as np

# Device-supported dtypes. The device tier is 32-bit-first: ``int`` is
# int32 and an UNDECLARED 64-bit integer input narrows to 32 bits (checked:
# a value that does not fit raises, frame.Frame). A column DECLARED
# ``np.int64`` / ``np.uint64`` (a Const's ``schema=``, a Map's ``out=``, a
# reader's schema) is a device column of 64 bits end to end — exact sums
# past 2^31; the programs that carry one run in JAX's 64-bit mode, scoped
# to them (parallel/jitutil.py). float64 is not a device dtype: a float64
# input narrows to float32, as it always has.


def _bfloat16_dtype():
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16)


@dataclasses.dataclass(frozen=True)
class ColType:
    """The type of one column.

    ``dtype`` is a numpy dtype for device columns, or ``np.dtype(object)``
    for host columns. ``tag`` optionally names the host payload kind
    (e.g. "str"). ``shape`` is the per-row trailing shape — () for
    scalar columns, (G,) for fixed-width vector columns (GroupByKey's
    group matrices).
    """

    dtype: np.dtype
    tag: str = ""
    shape: tuple = ()

    def __post_init__(self):
        object.__setattr__(self, "dtype", np.dtype(self.dtype))
        object.__setattr__(self, "shape", tuple(self.shape))

    @property
    def is_device(self) -> bool:
        return self.dtype != np.dtype(object)

    @property
    def is_host(self) -> bool:
        return self.dtype == np.dtype(object)

    def __repr__(self) -> str:
        if self.is_host:
            return f"host[{self.tag or 'object'}]"
        if self.shape:
            return f"{self.dtype}{list(self.shape)}"
        return str(self.dtype)


def coltype(spec: Any) -> ColType:
    """Coerce a user spec (dtype-like, type, or ColType) to a ColType."""
    if isinstance(spec, ColType):
        return spec
    if spec is str:
        return ColType(np.dtype(object), "str")
    if spec is bytes:
        return ColType(np.dtype(object), "bytes")
    if spec is int:
        return ColType(np.dtype(np.int32))
    if spec is float:
        return ColType(np.dtype(np.float32))
    if spec is bool:
        return ColType(np.dtype(np.bool_))
    if spec is object:
        return ColType(np.dtype(object))
    dt = np.dtype(spec)
    if dt == np.dtype(object):
        return ColType(dt)
    if dt not in _device_dtypes():
        raise TypeError(
            f"dtype {dt} is not supported on the device tier; use an "
            f"integer dtype or a float dtype of at most 32 bits, or "
            f"declare the column as a host column (object/str)"
        )
    return ColType(dt)


def is_wide(dtype) -> bool:
    """Is ``dtype`` a 64-bit integer — a column only JAX's 64-bit mode
    carries without narrowing it?"""
    dt = np.dtype(dtype)
    return dt.kind in "iu" and dt.itemsize == 8


def _device_dtypes() -> frozenset:
    global _DEVICE_DTYPES_FULL
    try:
        return _DEVICE_DTYPES_FULL
    except NameError:
        base = {
            np.dtype(t)
            for t in (
                np.bool_,
                np.int8,
                np.int16,
                np.int32,
                np.int64,
                np.uint8,
                np.uint16,
                np.uint32,
                np.uint64,
                np.float16,
                np.float32,
            )
        }
        try:
            base.add(_bfloat16_dtype())
        except ImportError:  # pragma: no cover
            pass
        _DEVICE_DTYPES_FULL = frozenset(base)
        return _DEVICE_DTYPES_FULL


class Schema:
    """An ordered tuple of column types with a key prefix.

    Mirrors slicetype.Type (slicetype/slicetype.go:17-27): ``NumOut`` →
    ``len(schema)``, ``Out(i)`` → ``schema[i]``, ``Prefix()`` →
    ``schema.prefix``.
    """

    __slots__ = ("cols", "prefix", "wide")

    def __init__(self, cols: Iterable[Any], prefix: int = 1):
        self.cols: Tuple[ColType, ...] = tuple(coltype(c) for c in cols)
        if not 0 <= prefix <= len(self.cols):
            raise ValueError(
                f"prefix {prefix} out of range for {len(self.cols)} columns"
            )
        self.prefix = prefix
        #: Does any column hold 64-bit integers (``is_wide``)?
        self.wide = any(is_wide(ct.dtype) for ct in self.cols
                        if ct.is_device)

    def __len__(self) -> int:
        return len(self.cols)

    def __getitem__(self, i) -> ColType:
        return self.cols[i]

    def __iter__(self):
        return iter(self.cols)

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, Schema)
            and self.cols == other.cols
            and self.prefix == other.prefix
        )

    def __hash__(self) -> int:
        return hash((self.cols, self.prefix))

    def __repr__(self) -> str:
        inner = ", ".join(repr(c) for c in self.cols)
        return f"Schema[{inner}; prefix={self.prefix}]"

    @property
    def key(self) -> Tuple[ColType, ...]:
        """The key (prefix) column types."""
        return self.cols[: self.prefix]

    @property
    def values(self) -> Tuple[ColType, ...]:
        """The non-key column types."""
        return self.cols[self.prefix :]

    def with_prefix(self, prefix: int) -> "Schema":
        return Schema(self.cols, prefix)

    def assignable_to(self, other: "Schema") -> bool:
        """Column-wise type compatibility (ignores prefix), mirroring
        slicetype.Assignable (slicetype/slicetype.go:129-143)."""
        return self.cols == other.cols

    @staticmethod
    def concat(a: "Schema", b: "Schema", prefix: int = 1) -> "Schema":
        return Schema(a.cols + b.cols, prefix)


def schema_of(cols: Sequence[Any], prefix: int = 1) -> Schema:
    return Schema(cols, prefix)
