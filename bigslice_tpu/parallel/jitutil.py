"""Jit-friendly batching utilities.

XLA compiles one program per (function, shapes) — data-dependent batch
sizes would recompile endlessly (SURVEY.md §7.3(1)). The framework
therefore pads ragged batches up to power-of-two *buckets* before entering
jitted kernels and slices the valid region off afterwards: a bounded set of
compiled programs regardless of data skew.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

# Probed once per process (see donation_supported): whether the active
# backend honors jit buffer donation by actually releasing the donated
# input. None = not yet probed.
_DONATION_OK: Optional[bool] = None
# One-time install of the donation-downgrade warning filter (see
# jit_maybe_donate).
_DONATION_FILTER_INSTALLED = False


def donation_supported() -> bool:
    """Does the active JAX backend implement input-buffer donation?

    Donation (``jax.jit(..., donate_argnums=...)``) lets XLA alias a
    dead input's buffer for an output instead of allocating fresh HBM —
    the steady-state wave-streaming allocator contract. Backends that
    don't implement aliasing silently ignore the annotation (correct
    but useless), so callers gate donated program VARIANTS on this
    probe rather than compiling them for nothing. The probe donates one
    tiny buffer and checks it was actually released."""
    global _DONATION_OK
    if _DONATION_OK is None:
        import warnings

        import jax
        import jax.numpy as jnp

        x = jnp.zeros(8, np.int32)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            jax.jit(
                lambda v: v + np.int32(1), donate_argnums=(0,)
            )(x).block_until_ready()
        _DONATION_OK = bool(x.is_deleted())
    return _DONATION_OK


_WIDE_DTYPES = (np.dtype(np.int64), np.dtype(np.uint64))


def any_wide(args) -> bool:
    """Is any array among ``args`` (nested tuples / lists of columns
    included) a 64-bit integer array?"""
    for a in args:
        if isinstance(a, (tuple, list)):
            if any_wide(a):
                return True
        elif getattr(a, "dtype", None) in _WIDE_DTYPES:
            return True
    return False


def wide_scope(wide: bool = True):
    """JAX's 64-bit mode for the calling thread while the block runs,
    when ``wide``; nothing otherwise. THE way 64-bit integer columns
    reach XLA: the mode is scoped to the uploads and programs that
    carry such a column, never set for the process — that would turn
    every weak Python scalar and ``jnp.arange`` of every 32-bit program
    into 64 bits, and the TPU emulates 64-bit integers."""
    if not wide:
        return contextlib.nullcontext()
    import jax

    return jax.enable_x64(True)


class ScopedJit:
    """A jitted callable that is traced, lowered and run under
    ``wide_scope`` exactly when it carries 64 bits: an argument is a
    64-bit integer array, or the builder said so (``wide=True``: the
    arguments do not show it — a Map from int32 columns to an int64
    one). A program without such a column is the jit it always was."""

    __slots__ = ("_jitted", "_wide")

    def __init__(self, jitted, wide: bool = False):
        self._jitted = jitted
        self._wide = bool(wide)

    def __call__(self, *args, **kw):
        with wide_scope(self._wide or any_wide(args)):
            return self._jitted(*args, **kw)

    def lower(self, *args, **kw):
        with wide_scope(self._wide or any_wide(args)):
            return self._jitted.lower(*args, **kw)

    def __getattr__(self, name):
        # ``__name__`` and the rest of the jit's own surface.
        return getattr(self._jitted, name)


def jit(fn: Callable, wide: bool = False, **jit_kwargs) -> ScopedJit:
    """``jax.jit`` for every program of the package that columns pass
    through (see ``ScopedJit``)."""
    import jax

    return ScopedJit(jax.jit(fn, **jit_kwargs), wide)


def jit_maybe_donate(fn: Callable, donate_argnums: Sequence[int] = (),
                     wide: bool = False):
    """``jax.jit`` with donation applied only when requested AND the
    backend honors it — THE one place donated program variants are
    built, so every caller (the mesh executor's SPMD programs, the
    standalone shuffle/hashagg/hier kernels, PaddedVmap) shares one
    gate and one warning policy. Donated and undonated variants are
    distinct compilations; callers key their caches on the donation
    signature (a bool / tuple of bools), which bounds the blowup at
    2× per cache, not one entry per call site."""
    nums = tuple(donate_argnums)
    if nums and donation_supported():
        global _DONATION_FILTER_INSTALLED
        if not _DONATION_FILTER_INSTALLED:
            import warnings

            # An output that can't alias its donated input (shape or
            # layout mismatch) downgrades to a copy — correct, just not
            # free; the per-execution warning would otherwise spam
            # every wave. Installed ONCE: repeated filterwarnings calls
            # would grow the process-global filter list on every
            # donated compile.
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            _DONATION_FILTER_INSTALLED = True
        return jit(fn, wide, donate_argnums=nums)
    return jit(fn, wide)


def bucket_size(n: int, minimum: int = 8) -> int:
    """Smallest power of two ≥ n (≥ minimum)."""
    b = minimum
    while b < n:
        b <<= 1
    return b


def pad_cols(cols: Sequence, n: int, target: int) -> list:
    """Pad columns from n to target rows by repeating the last row (stays
    in the user function's domain, unlike zero fill).

    Deliberately *numpy*: eager jnp ops would compile one tiny XLA program
    per distinct shape — ragged batch sizes would thrash the compile
    cache. Host padding costs a memcpy; the jitted kernel downstream is
    the only XLA program in the path.
    """
    if n == target:
        return list(cols)
    out = []
    for c in cols:
        c = np.asarray(c)
        if n == 0:
            out.append(np.zeros((target,) + c.shape[1:], c.dtype))
        else:
            fill = np.broadcast_to(
                c[n - 1 : n], (target - n,) + c.shape[1:]
            )
            out.append(np.concatenate([c, fill]))
    return out


class PaddedVmap:
    """vmap+jit a per-row function, amortized over bucketed batch sizes.

    ``extra`` arguments are passed unbatched (in_axes=None) — dynamic
    data, not trace constants, so callers can vary them per call (e.g.
    k-means centroids per iteration) without recompiling.
    """

    def __init__(self, fn: Callable):
        self.fn = fn
        # (ncols, nextra, donate, wide) -> jitted vmapped fn; ``wide``:
        # the caller's output schema has a 64-bit column its input
        # columns do not show (ScopedJit). The donate bit
        # keys the cache so donated and undonated callers of the SAME
        # shared instance (get_padded_vmap) coexist at a bounded 2×,
        # instead of thrashing one entry back and forth.
        self._jitted = {}

    def _get(self, ncols: int, nextra: int, donate: bool = False,
             wide: bool = False):
        key = (ncols, nextra, donate, wide)
        j = self._jitted.get(key)
        if j is None:
            import jax

            vf = jax.vmap(
                self.fn, in_axes=(0,) * ncols + (None,) * nextra
            )
            j = jit_maybe_donate(
                vf, tuple(range(ncols)) if donate else (), wide
            )
            self._jitted[key] = j
        return j

    def __call__(self, cols: Sequence, n: int,
                 extra: Sequence = (),
                 donate: bool = False,
                 wide: bool = False) -> Tuple[list, int]:
        """Apply to n valid rows of equal-length columns; returns (out
        columns sliced to n, n).

        ``donate=True`` donates the padded column buffers to the
        program (HBM reuse for steady-state batch loops); callers must
        hand in columns they own exclusively — device arrays they will
        never read again. Host (numpy) columns are always safe: the
        transfer copy is the program's to donate."""
        target = bucket_size(n)
        padded = pad_cols(cols, n, target)
        out = self._get(len(cols), len(extra), donate, wide)(
            *padded, *extra)
        if not isinstance(out, (tuple, list)):
            out = (out,)
        # Slice on the host: an eager device slice would compile one XLA
        # program per distinct n.
        return [np.asarray(o)[:n] for o in out], n


# Keyed by id(fn) with an aliveness guard; bounded FIFO so loops that
# construct fresh lambdas can't grow the cache (and its compiled
# executables) without limit.
_VMAP_CACHE: "dict" = {}
_VMAP_CACHE_MAX = 128


def get_padded_vmap(fn: Callable) -> PaddedVmap:
    """Share PaddedVmap instances (and their jit caches) across slices
    built from the same function object — re-constructing a Map with the
    same fn in a loop compiles once, not once per construction."""
    import weakref

    key = id(fn)
    entry = _VMAP_CACHE.get(key)
    if entry is not None:
        ref, pv = entry
        if ref is None or ref() is fn:
            return pv
    pv = PaddedVmap(fn)
    try:
        ref = weakref.ref(fn)
    except TypeError:  # unweakrefable callables
        ref = None
    _VMAP_CACHE[key] = (ref, pv)
    while len(_VMAP_CACHE) > _VMAP_CACHE_MAX:
        _VMAP_CACHE.pop(next(iter(_VMAP_CACHE)))
    return pv
