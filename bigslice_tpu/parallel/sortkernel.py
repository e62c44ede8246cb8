"""Device run sort: jitted stable ``lax.sort`` over whole frames.

The external sort's in-run sorting (sortio.sort_reader) dispatches here
for frames whose columns are all scalar-device — the TPU replacement for
the reference's reflection-comparator sort (sortio/sort.go:22-77,
frame/frame.go:353-395). Padded rows carry a validity sort key that
orders them last (jitutil bucketing rationale: one compiled program per
power-of-two size, regardless of run length).
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from bigslice_tpu.parallel.jitutil import bucket_size, jit, pad_cols


class DeviceRunSort:
    """Stable sort of (key..., payload...) scalar columns by the key
    prefix, compiled once per (nkeys, dtypes, bucket)."""

    def __init__(self, nkeys: int, ncols: int):
        import jax
        import jax.numpy as jnp
        from jax import lax

        def kernel(n, *cols):
            size = cols[0].shape[0]
            invalid = (jnp.arange(size, dtype=np.int32) >= n).astype(
                np.int32
            )
            srt = lax.sort((invalid,) + tuple(cols), num_keys=1 + nkeys,
                           is_stable=True)
            return srt[1:]

        self._jitted = jit(kernel)

    def __call__(self, cols: Sequence, n: int):
        import jax.numpy as jnp

        size = bucket_size(n)
        padded = pad_cols(list(cols), n, size)
        out = self._jitted(jnp.int32(n), *padded)
        return [np.asarray(c)[:n] for c in out]


_SORT_CACHE: dict = {}
_SORT_CACHE_MAX = 64


def cached_run_sort(nkeys: int, ncols: int, dtypes: tuple) -> DeviceRunSort:
    key = (nkeys, ncols, dtypes)
    kern = _SORT_CACHE.get(key)
    if kern is None:
        kern = _SORT_CACHE[key] = DeviceRunSort(nkeys, ncols)
        while len(_SORT_CACHE) > _SORT_CACHE_MAX:
            _SORT_CACHE.pop(next(iter(_SORT_CACHE)))
    return kern


# Below this row count the host lexsort wins on dispatch overhead alone.
DEVICE_SORT_MIN_ROWS = 4096


def device_sort_default() -> bool:
    """Whether device-schema frames sort with the jitted ``lax.sort``.
    On real TPU that keeps rows on-chip and rides the fast XLA sort;
    on CPU backends the XLA sort is the measured ~40×-slow primitive
    (a CPU-mesh A/B), so frames route to the host lexsort
    instead — same per-backend knob convention as the hash-aggregate
    and sortless-shuffle lowerings. Override with
    BIGSLICE_DEVICE_SORT=1/0."""
    import os

    env = os.environ.get("BIGSLICE_DEVICE_SORT")
    if env:
        return env not in ("0", "false", "off")
    import jax

    return jax.default_backend() == "tpu"


def device_sortable(frame) -> bool:
    return (
        frame.prefix >= 1
        and len(frame) >= DEVICE_SORT_MIN_ROWS
        and all(ct.is_device and ct.shape == () for ct in frame.schema)
        and device_sort_default()
    )


def device_sorted_by_key(frame):
    """Sort a device-schema frame by its key prefix on the device."""
    from bigslice_tpu.frame.frame import Frame

    kern = cached_run_sort(
        frame.prefix, frame.num_cols,
        tuple(str(ct.dtype) for ct in frame.schema),
    )
    return Frame(kern(list(frame.cols), len(frame)), frame.schema)
