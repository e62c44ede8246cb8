"""Per-dtype operations: hashing, comparison, encoding capability.

Mirrors the reference's ops registry (frame/ops.go:31-105): each column type
carries ``{Less, HashWithSeed, Encode, Decode}`` and the registry gates which
types may be used as shuffle/sort keys (``CanCompare``/``CanHash``).

TPU-first difference: for device columns the hash and comparison are *jax*
ops — a murmur3-finalizer-style integer mix that XLA fuses into the
surrounding pipeline (replacing the reference's generated Go per-type
hashers, frame/ops_builtin.go:1-160). Host (object) columns hash via a
stable CRC32 on the host, so shuffle partitioning is deterministic across
processes (the reference seeds per-process entropy, exec/combiner.go:39-43;
we need cross-process determinism for SPMD workers instead).
"""

from __future__ import annotations

import zlib
from typing import Callable, Dict, Optional

import jax.numpy as jnp
import numpy as np

from bigslice_tpu.slicetype import ColType

_GOLDEN32 = np.uint32(0x9E3779B9)


def fmix32(x):
    """murmur3 32-bit finalizer over a uint32 jax/numpy array.

    Replaces the reference's murmur3-based int hashing
    (frame/ops_builtin.go:1-160) with a vectorized, XLA-fusable mix.
    """
    x = x ^ (x >> 16)
    x = (x * np.uint32(0x85EBCA6B)).astype(np.uint32)
    x = x ^ (x >> 13)
    x = (x * np.uint32(0xC2B2AE35)).astype(np.uint32)
    x = x ^ (x >> 16)
    return x


def _bits32(col):
    """Reinterpret a device column as uint32 lanes for hashing."""
    dt = np.dtype(col.dtype)
    xp = np if isinstance(col, np.ndarray) else jnp
    if dt.kind in ("i", "u", "b"):
        if dt.itemsize == 8:
            # Fold the high word into the low one: keys that differ
            # only above bit 31 must not all share a partition.
            col = col ^ (col >> 32)
        return col.astype(np.uint32)
    if dt.kind == "f" or dt.name == "bfloat16":
        # Normalize -0.0 to +0.0 so equal keys hash equally.
        col = xp.where(col == 0, xp.zeros_like(col), col)
        if dt.itemsize == 4:
            return xp.asarray(col).view(np.uint32)
        # f16/bf16 → widen via uint16 view.
        return xp.asarray(col).view(np.uint16).astype(np.uint32)
    raise TypeError(f"cannot hash device column of dtype {dt}")


def _seed32(seed: int) -> np.uint32:
    return np.uint32((seed * 0x9E3779B9) & 0xFFFFFFFF)


def hash_device_column(col, seed: int = 0):
    """Hash one device column to uint32 with a seed (vectorized)."""
    h = _bits32(col)
    return fmix32(h ^ _seed32(seed))


def combine_hashes(a, b):
    """Order-dependent combination of two uint32 hash arrays."""
    # boost::hash_combine-style mix.
    return fmix32(a ^ ((b + _GOLDEN32 + (a << 6) + (a >> 2)).astype(np.uint32)))


def _stable_obj_hash(v) -> int:
    """Stable (cross-process) 32-bit hash of a host object."""
    if isinstance(v, str):
        return zlib.crc32(v.encode("utf-8", "surrogatepass"))
    if isinstance(v, bytes):
        return zlib.crc32(v)
    if isinstance(v, bool):
        return int(v)
    if isinstance(v, (int, np.integer)):
        return int(v) & 0xFFFFFFFF
    if isinstance(v, float):
        return zlib.crc32(repr(v).encode())
    if isinstance(v, tuple):
        h = np.uint32(len(v) * 0x85EBCA6B & 0xFFFFFFFF)
        for e in v:
            h = combine_hashes(
                np.asarray(h, np.uint32), np.asarray(_stable_obj_hash(e), np.uint32)
            )
        return int(h)
    raise TypeError(f"cannot hash host value of type {type(v).__name__}")


def hash_host_column(col: np.ndarray, seed: int = 0) -> np.ndarray:
    """Hash a host (object) column to uint32 on the host.

    All-string columns ride the native CRC kernel (bit-identical to
    the per-row path — both are zlib CRC-32 of the UTF-8 bytes); any
    non-string (or surrogate-bearing) element falls back to the exact
    per-row hash."""
    # Spot-check before materializing a full Python list: mixed/non-str
    # columns (ints, tuples) must not pay an O(n) copy just for the
    # kernel to reject them.
    if len(col) and isinstance(col[0], str) \
            and isinstance(col[len(col) // 2], str):
        from bigslice_tpu import native

        if native.enabled():
            h = native.crc32_strings(
                col.tolist() if isinstance(col, np.ndarray) else col
            )
            if h is not None:
                return fmix32(h ^ _seed32(seed))
    out = np.fromiter(
        (_stable_obj_hash(v) for v in col), dtype=np.uint32, count=len(col)
    )
    return fmix32(out ^ _seed32(seed))


class Ops:
    """Operations for one column type (mirrors frame.Ops, frame/ops.go:31)."""

    def __init__(
        self,
        can_hash: bool = True,
        can_compare: bool = True,
        hash_fn: Optional[Callable[[np.ndarray, int], np.ndarray]] = None,
        less_key: Optional[Callable] = None,
    ):
        self.can_hash = can_hash
        self.can_compare = can_compare
        self.hash_fn = hash_fn
        self.less_key = less_key  # sort key fn for host columns


_REGISTRY: Dict[str, Ops] = {}


def register_ops(tag: str, ops: Ops) -> None:
    """Register custom ops for a host-column tag (mirrors RegisterOps,
    frame/ops.go:31-97)."""
    _REGISTRY[tag] = ops


def ops_for(ct: ColType) -> Ops:
    if ct.is_device:
        if getattr(ct, "shape", ()) != ():
            # Vector columns (GroupByKey matrices) are payload-only:
            # they can't serve as shuffle/sort keys.
            return Ops(can_hash=False, can_compare=False)
        return Ops(can_hash=True, can_compare=True)
    if ct.tag in _REGISTRY:
        return _REGISTRY[ct.tag]
    # Default host ops: str/bytes/int-ish objects hash via CRC and compare
    # via Python's natural ordering.
    return Ops(can_hash=True, can_compare=True, hash_fn=hash_host_column)


def can_hash(ct: ColType) -> bool:
    return ops_for(ct).can_hash


def can_compare(ct: ColType) -> bool:
    return ops_for(ct).can_compare
