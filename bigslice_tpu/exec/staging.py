"""Wave-staging fast path: reusable host arena + two-pass assembly.

The slow path this replaces paid four host copies per column to stage a
wave: ``codec.decode_frame`` copied every column out of the stream
buffer, ``Frame.concat`` copied the per-shard frames together,
``_upload`` copied each shard chunk against its padding, then copied the
padded chunks into one global array for ``jax.device_put``. With BSF4
zero-copy decode (frame/codec.py) the columns arrive as views, and this
module's two-pass assembly writes them straight into the global padded
destination — ONE host copy per column, into a buffer the arena reuses
wave over wave:

1. **Scan** — exact per-shard row counts from the frames' lengths (or a
   header-only ``codec.scan_frames`` pass when staging from raw stream
   bytes), fixing the bucketed capacity before any payload moves.
2. **Assemble** — acquire (or reuse) one ``(nmesh * capacity, ...)``
   host buffer per column from the arena, copy each shard's frame
   columns into their row slices, zero the padding tail.

The assembled buffers upload as ONE batched ``jax.device_put`` with an
explicit sharding (``parallel/shuffle.py place_global_columns``) instead
of a put per column. What happens to the host buffer afterwards is a
probed per-backend policy (``staging_mode``): on backends whose
device_put can ALIAS an aligned host buffer (XLA CPU), the arena
allocates 64-aligned buffers so the upload pass costs nothing and never
reuses them; on backends that copy (TPU/GPU), it allocates deliberately
MISALIGNED buffers — pinning the copy semantics — and recycles each one
the moment its transfer settles. Donation composes with both: the wave
program donates the *device* buffers as before, while in recycle mode
the *host* slot returns to the arena — a donated wave's slot is
recycled, not reallocated.

Store reads for different shards fan out on a small shared thread pool
(``map_shards``) inside the wave prefetcher, so per-shard disk/GCS
latency overlaps instead of accumulating.

Knobs: ``BIGSLICE_STAGE_THREADS`` (per-shard read fan-out, default 4,
0/1 = serial reads), ``BIGSLICE_STAGING_ARENA_BYTES`` (retained
free-buffer bound).
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bigslice_tpu.frame.frame import Frame
from bigslice_tpu.parallel.jitutil import bucket_size
from bigslice_tpu.utils import faultinject


class StagingFallback(Exception):
    """Raised by ``assemble`` when the input shape is outside the fast
    path's contract (object columns, cross-shard dtype drift); the
    caller falls back to the legacy concat+pad upload."""


def stage_threads_default() -> int:
    env = os.environ.get("BIGSLICE_STAGE_THREADS")
    if env:
        return max(0, int(env))
    return 4


# -- per-shard read fan-out ----------------------------------------------

_POOL = None
_POOL_WORKERS = 0
_POOL_LOCK = threading.Lock()


def map_shards(fn, items: Sequence, threads: Optional[int] = None):
    """``[fn(x) for x in items]`` with per-item fan-out on a small
    shared thread pool (order preserved, first exception re-raised).
    Serial when the pool can't help (0/1 items or threads<2). Used for
    store reads, where each shard's I/O latency is independent — NOT
    for user reader functions, whose thread-safety is their business."""
    items = list(items)
    if threads is None:
        threads = stage_threads_default()
    if threads < 2 or len(items) < 2:
        return [fn(x) for x in items]
    global _POOL, _POOL_WORKERS
    with _POOL_LOCK:
        if _POOL is None or _POOL_WORKERS != threads:
            from concurrent.futures import ThreadPoolExecutor

            # A resized pool ABANDONS the old one rather than shutting
            # it down: a concurrent caller may still be mapping on it,
            # and shutdown would fail that caller's wave. The stale
            # pool drains its in-flight work and its idle threads park
            # until interpreter exit (resizes are rare — env changes
            # between executor constructions).
            _POOL = ThreadPoolExecutor(
                max_workers=threads, thread_name_prefix="bs-stage"
            )
            _POOL_WORKERS = threads
        pool = _POOL
    return list(pool.map(fn, items))


# -- arena allocation modes / device_put semantics probe ------------------
#
# XLA's CPU client ZERO-COPIES a host buffer into a "device" array when
# the buffer is 64-byte aligned — and numpy's allocator makes that a
# per-allocation coin flip. The arena turns the coin flip into policy,
# probed once per process with buffers from its own allocator:
#
# - ``zerocopy`` — a put of an ALIGNED buffer aliases it (CPU): the
#   arena hands out deliberately 64-aligned buffers so the upload pass
#   costs nothing at all, and NEVER recycles them (the device array
#   owns the memory for life — recycling would scribble over live
#   data; the base allocation stays referenced by the jax buffer).
# - ``recycle`` — a put of a MISALIGNED (ptr ≡ 32 mod 64) buffer
#   detaches (TPU/GPU, and CPU's copy path): the arena hands out
#   misaligned buffers — forcing the copy deterministically — and
#   recycles each one once its transfer settles.
# - ``norecycle`` — neither property verified (multi-process meshes,
#   where the read-back check is unavailable, or an exotic backend):
#   fresh buffers every wave, never reused. Always correct.

_ALIGN = 64
_MODE: Optional[str] = None


def _alloc_empty(dtype: np.dtype, shape: Tuple[int, ...],
                 misalign: bool) -> np.ndarray:
    """An uninitialized array at a CHOSEN alignment: ptr ≡ 0 (mod 64)
    for the zero-copy fast path, ptr ≡ 32 (mod 64) to force the copy
    path. The base allocation stays referenced via ``.base``."""
    dtype = np.dtype(dtype)
    nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
    base = np.empty(nbytes + _ALIGN, np.uint8)
    want = _ALIGN // 2 if misalign else 0
    off = (want - base.ctypes.data) % _ALIGN
    return base[off : off + nbytes].view(dtype).reshape(shape)


def _put_aliases(mesh, sharding, misalign: bool) -> bool:
    """Does a sharded device_put of an arena-style buffer alias it?"""
    import jax

    buf = _alloc_empty(np.int32, (int(mesh.devices.size) * 16384,),
                       misalign)
    buf[:] = 0
    arr = jax.device_put(buf, sharding)
    jax.block_until_ready(arr)
    buf[:] = 1
    aliased = int(np.asarray(arr)[0]) == 1
    if aliased:
        buf[:] = 0  # restore before the device array is released
    return aliased


def staging_mode(mesh) -> str:
    """The arena policy for this process/backend (see module note):
    ``zerocopy`` | ``recycle`` | ``norecycle``."""
    global _MODE
    from bigslice_tpu.parallel.shuffle import is_multiprocess_mesh

    if is_multiprocess_mesh(mesh):
        return "norecycle"
    if _MODE is None:
        try:
            from jax.sharding import NamedSharding, PartitionSpec as P

            sharding = NamedSharding(mesh, P(tuple(mesh.axis_names)))
            if _put_aliases(mesh, sharding, misalign=False):
                _MODE = "zerocopy"
            elif not _put_aliases(mesh, sharding, misalign=True):
                _MODE = "recycle"
            else:  # aliases even misaligned: never reuse anything
                _MODE = "norecycle"
        except Exception:  # no backend: stay conservative
            _MODE = "norecycle"
    return _MODE


class StagingArena:
    """A bounded pool of host staging buffers, keyed by (dtype, shape),
    whose allocation/reuse policy is the probed ``staging_mode``:
    zerocopy (64-aligned, upload aliases, never reused), recycle
    (misaligned, copied, reused wave over wave — one allocation per
    shape per session instead of one per wave), or norecycle (fresh
    misaligned buffers, always correct). ``mode`` is set lazily by the
    executor from ``staging_mode(mesh)``; unset behaves as norecycle."""

    def __init__(self, enabled: bool = True,
                 max_bytes: Optional[int] = None,
                 mode: Optional[str] = None):
        self.enabled = bool(enabled)
        if max_bytes is None:
            env = os.environ.get("BIGSLICE_STAGING_ARENA_BYTES")
            max_bytes = int(env) if env else 1 << 28
        self.max_bytes = int(max_bytes)
        self.mode = mode
        self._lock = threading.Lock()
        self._free: Dict[Tuple[str, Tuple[int, ...]],
                         List[np.ndarray]] = {}
        self._held_bytes = 0
        # observability (resource_stats / tests)
        self.hits = 0
        self.misses = 0
        self.recycled = 0

    def acquire(self, dtype, shape: Tuple[int, ...]) -> np.ndarray:
        if self.mode == "zerocopy":
            with self._lock:
                self.misses += 1
            return _alloc_empty(dtype, tuple(shape), misalign=False)
        if self.mode == "recycle":
            key = (np.dtype(dtype).str,
                   tuple(int(d) for d in shape))
            with self._lock:
                free = self._free.get(key)
                if free:
                    buf = free.pop()
                    self._held_bytes -= buf.nbytes
                    self.hits += 1
                    return buf
                self.misses += 1
        else:
            with self._lock:
                self.misses += 1
        return _alloc_empty(dtype, tuple(shape), misalign=True)

    def release(self, bufs: Sequence[np.ndarray]) -> None:
        """Return staging buffers for reuse — recycle mode only, and
        only once the caller has settled their transfers. Buffers
        beyond the byte bound are dropped (the allocator's problem
        again, bounded memory ours)."""
        if self.mode != "recycle":
            return
        with self._lock:
            for b in bufs:
                if self._held_bytes + b.nbytes > self.max_bytes:
                    continue
                key = (b.dtype.str, b.shape)
                self._free.setdefault(key, []).append(b)
                self._held_bytes += b.nbytes
                self.recycled += 1

    def stats(self) -> dict:
        with self._lock:
            return {
                "enabled": self.enabled,
                "mode": self.mode,
                "held_bytes": self._held_bytes,
                "hits": self.hits,
                "misses": self.misses,
                "recycled": self.recycled,
            }


def assemble(per_shard_frames: Sequence[Sequence[Frame]],
             schema, nmesh: int, arena: StagingArena):
    """Two-pass arena assembly of per-shard frame lists into global
    padded per-column host buffers.

    Pass 1 scans exact per-shard row counts (frame lengths — headers
    only, payloads untouched for zero-copy decoded frames until the
    copy below). Pass 2 acquires one destination buffer per column and
    decodes/copies every frame's column directly into its row slice —
    no ``Frame.concat`` intermediate, no per-chunk pad concat.

    Returns ``(host_cols, counts, capacity, bufs)`` where ``bufs`` are
    the arena buffers to release after upload. Raises StagingFallback
    for shapes outside the contract (object columns, dtype drift)."""
    # Chaos seam at ENTRY (before any arena state moves): an injected
    # transient here is retried by the executor's staging retry loop.
    if faultinject.ENABLED:
        faultinject.maybe_raise("staging.assemble")
    lists = [list(fl) for fl in per_shard_frames]
    if len(lists) > nmesh:
        raise ValueError(
            f"{len(lists)} shard lists for a {nmesh}-slot mesh"
        )
    while len(lists) < nmesh:
        lists.append([])
    counts = [sum(len(f) for f in fl) for fl in lists]
    capacity = bucket_size(max(counts + [1]))

    # Column dtypes/shapes: from the data when any frame exists (the
    # legacy path used the first frame's schema), declared otherwise.
    first = next((f for fl in lists for f in fl), None)
    if first is not None:
        coltypes = [
            (np.dtype(getattr(c, "dtype", object)),
             tuple(int(d) for d in getattr(c, "shape", (0,))[1:]))
            for c in first.cols
        ]
    else:
        if schema is None:
            raise StagingFallback("no frames and no schema")
        coltypes = [(np.dtype(ct.dtype), tuple(ct.shape))
                    for ct in schema]
    if any(dt == np.dtype(object) for dt, _ in coltypes):
        raise StagingFallback("object column")

    host_cols: List[np.ndarray] = []
    bufs: List[np.ndarray] = []
    for j, (dt, dims) in enumerate(coltypes):
        buf = arena.acquire(dt, (nmesh * capacity,) + dims)
        for i, fl in enumerate(lists):
            off = i * capacity
            for f in fl:
                c = f.cols[j]
                n = int(c.shape[0]) if hasattr(c, "shape") else len(c)
                if getattr(c, "dtype", None) != dt or \
                        tuple(getattr(c, "shape", (0,))[1:]) != dims:
                    arena.release(bufs + [buf])
                    raise StagingFallback("column dtype/shape drift")
                buf[off : off + n] = np.asarray(c)
                off += n
            buf[off : i * capacity + capacity] = 0
        host_cols.append(buf)
        bufs.append(buf)
    return host_cols, counts, capacity, bufs
