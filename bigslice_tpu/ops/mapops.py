"""Row-wise combinators: Map, Filter, Flatmap, Head, Scan, Prefixed.

Mirrors slice.go's combinators. The key TPU-first change: where the
reference calls the user function *per record via reflection*
(slice.go:621-632 — its noted perf weakness), these combinators classify
the user function as either

- **traceable** (jax): vmapped + jitted over device columns, fused by XLA
  into the surrounding pipeline; or
- **host**: arbitrary Python, run batch-at-a-time on the host tier
  (the ReaderFunc/WriterFunc class of functions — SURVEY.md §7.3(3)).

Classification is automatic (``mode='auto'`` attempts an abstract jax
trace) and overridable.
"""

from __future__ import annotations

from typing import Callable, Iterable, Optional, Sequence

import numpy as np

from bigslice_tpu import typecheck
from bigslice_tpu.slicetype import ColType, Schema
from bigslice_tpu.frame.frame import Frame
from bigslice_tpu import sliceio
from bigslice_tpu.ops.base import (
    Combiner,
    Dep,
    Slice,
    make_name,
    single_dep,
)
from bigslice_tpu.parallel.jitutil import get_padded_vmap, wide_scope


def _as_schema(out, default_prefix: int = 1) -> Schema:
    if isinstance(out, Schema):
        return out
    cols = list(out)
    return Schema(cols, prefix=min(default_prefix, len(cols)))


_TRY_TRACE_CACHE: dict = {}
_TRY_TRACE_CACHE_MAX = 256


def _try_trace(fn: Callable, in_schema: Schema, extra: tuple = (),
               why: list = None, wide: bool = False):
    """Attempt an abstract trace of fn over scalar avals of the input
    columns (plus unbatched ``extra`` args). Returns the output Schema
    or None if fn must run host-tier; when ``why`` is passed, a reason
    string is appended on None returns that aren't plain
    untraceability. The trace runs in JAX's 64-bit mode when an input
    column is 64-bit or the caller's declared output is (``wide``):
    outside it an int64 aval narrows to int32 and the traced dtypes lie.

    Memoized on (fn, input signature, extra-arg signature) — iterative
    drivers rebuild the same Map each round with fresh extra VALUES
    but identical shapes, and the abstract trace dominates op
    construction. The fn object itself is the key (identity hash, held
    alive by the entry), matching the downstream jit/program caches'
    stable-identity contract; recorded `why` reasons replay on hits."""
    if not all(ct.is_device for ct in in_schema):
        return None
    wide = wide or in_schema.wide
    try:
        key = (
            fn, wide,
            tuple((ct.dtype, ct.shape, ct.is_device) for ct in in_schema),
            tuple((tuple(np.shape(e)),
                   np.asarray(e).dtype if not hasattr(e, "dtype") else e.dtype)
                  for e in extra),
        )
        hit = _TRY_TRACE_CACHE.get(key)
    except Exception:  # unhashable fn/extra: classify uncached
        key = hit = None
    if hit is not None:
        out, msgs = hit
        if why is not None:
            why.extend(msgs)
        return out
    msgs: list = []
    with wide_scope(wide):
        out = _try_trace_uncached(fn, in_schema, extra, msgs)
    if key is not None:
        _TRY_TRACE_CACHE[key] = (out, tuple(msgs))
        while len(_TRY_TRACE_CACHE) > _TRY_TRACE_CACHE_MAX:
            _TRY_TRACE_CACHE.pop(next(iter(_TRY_TRACE_CACHE)))
    if why is not None:
        why.extend(msgs)
    return out


def _try_trace_uncached(fn: Callable, in_schema: Schema, extra: tuple,
                        why: list):
    try:
        import jax
        import jax.numpy as jnp

        from bigslice_tpu.utils import metrics as metrics_mod

        # Per-row avals carry each column's trailing shape (vector
        # columns, e.g. GroupByKey matrices, present as [G] per row).
        specs = [jax.ShapeDtypeStruct(ct.shape, ct.dtype)
                 for ct in in_schema]
        especs = [jax.ShapeDtypeStruct(jnp.shape(e), jnp.asarray(e).dtype)
                  for e in extra]
        # Metrics probe: a counter touched during the trace means the
        # fn must run host-tier, where per-record increments are real
        # (a traced incr would count compiles, not rows). Data-
        # DEPENDENT increments can't reach here — branching on a
        # tracer raises and classifies host already.
        probe = metrics_mod.TraceProbe()
        with metrics_mod.scope_context(probe):
            out = jax.eval_shape(fn, *(specs + especs))
        if probe.touched:
            if why is not None:
                why.append(
                    "function increments metrics counters, which only "
                    "count correctly on the host tier (a traced incr "
                    "runs per compile, not per row)"
                )
            return None
        if not isinstance(out, (tuple, list)):
            out = (out,)
        cols = [
            ColType(np.dtype(o.dtype), shape=tuple(o.shape)) for o in out
        ]
        return Schema(cols, prefix=min(1, len(cols)))
    except Exception:
        return None


_CAST_WRAPPERS: "dict" = {}
_CAST_WRAPPERS_MAX = 128


def _cast_wrapper(base_fn: Callable, dtypes: tuple) -> Callable:
    """An output-casting wrapper around ``base_fn``, shared across
    constructions (keyed like jitutil._VMAP_CACHE: id + weakref
    aliveness guard, bounded FIFO)."""
    import weakref

    key = (id(base_fn), dtypes)
    entry = _CAST_WRAPPERS.get(key)
    if entry is not None:
        ref, wrapper = entry
        if ref is None or ref() is base_fn:
            return wrapper

    def wrapper(*args, _f=base_fn, _dts=dtypes):
        import jax.numpy as jnp

        o = _f(*args)
        if not isinstance(o, (tuple, list)):
            o = (o,)
        return tuple(
            jnp.asarray(v).astype(dt) for v, dt in zip(o, _dts)
        )

    try:
        ref = weakref.ref(base_fn)
    except TypeError:  # unweakrefable callables
        ref = None
    _CAST_WRAPPERS[key] = (ref, wrapper)
    while len(_CAST_WRAPPERS) > _CAST_WRAPPERS_MAX:
        _CAST_WRAPPERS.pop(next(iter(_CAST_WRAPPERS)))
    return wrapper


class _Pipelined(Slice):
    """Base for single-dep, non-shuffle (fusable) slices."""

    def __init__(self, dep_slice: Slice, schema: Schema, name, pragmas=()):
        super().__init__(schema, dep_slice.num_shards, name,
                         pragmas=tuple(pragmas) + tuple(dep_slice.pragmas))
        self.dep_slice = dep_slice

    def deps(self):
        return single_dep(self.dep_slice)


class Map(_Pipelined):
    """Per-record transform (mirrors bigslice.Map, slice.go:566-638).

    ``fn(*row, *args) -> value | tuple``. Traceable fns run vmapped+jitted
    on device; host fns require ``out=`` (a Schema or list of column
    types). ``args`` are passed unbatched as trailing arguments — dynamic
    data rather than trace constants, so iterative drivers can rebuild
    the Map with fresh args each round without recompiling (jit caches
    are shared per function object).
    """

    def __init__(self, slice_: Slice, fn: Callable, out=None, mode="auto",
                 args: tuple = ()):
        name = make_name("map")
        self.fn = fn
        self.mode = mode
        self.args = tuple(args)
        traced = None
        why: list = []
        # A 64-bit column in or out: classify, cast and run under
        # JAX's 64-bit mode (a Map from int32 columns to an int64 one
        # says so with out=; nothing else can).
        self.wide = slice_.schema.wide or (
            out is not None and _as_schema(out).wide)
        if mode in ("auto", "jax"):
            traced = _try_trace(fn, slice_.schema, self.args, why=why,
                                wide=self.wide)
        if traced is not None:
            self.mode = "jax"
            if out is None:
                schema = traced
            else:
                # Reconcile a declared out= schema with the traced output:
                # cast device outputs to the declared dtypes so the frame's
                # schema never lies about its columns.
                schema = _as_schema(out)
                if len(schema) != len(traced):
                    raise typecheck.errorf(
                        "map: out= declares %d columns but function "
                        "returns %d", len(schema), len(traced),
                    )
                if not all(ct.is_device for ct in schema):
                    raise typecheck.errorf(
                        "map: jax-traceable function cannot produce host "
                        "columns; declare mode='host'"
                    )
                if tuple(c.shape for c in schema) != tuple(
                    c.shape for c in traced
                ):
                    # Declared out= types are shape-agnostic; the traced
                    # trailing shapes are authoritative.
                    schema = Schema(
                        [ColType(d.dtype, d.tag, t.shape)
                         for d, t in zip(schema, traced)],
                        schema.prefix,
                    )
                if tuple(c.dtype for c in schema) != tuple(
                    c.dtype for c in traced
                ):
                    # The cast wrapper IS the op's function from here on:
                    # executors that trace self.fn directly (the mesh
                    # path vmaps it inside the SPMD program) must see the
                    # same dtypes the schema declares. Memoized per
                    # (user fn, dtypes) so rebuilding the Map each round
                    # of an iterative driver keeps a stable function
                    # identity (jit/program caches key on id(fn)).
                    fn = _cast_wrapper(
                        fn, tuple(c.dtype for c in schema)
                    )
                    self.fn = fn

            self._vfn = get_padded_vmap(fn)
        else:
            if mode == "jax":
                raise typecheck.errorf(
                    "map: %s",
                    why[0] if why else
                    f"function is not jax-traceable over {slice_.schema}",
                )
            if out is None:
                raise typecheck.errorf(
                    "map: host-mode function requires out= column "
                    "types%s",
                    f" ({why[0]})" if why else "",
                )
            self.mode = "host"
            schema = _as_schema(out)
        super().__init__(slice_, schema, name)

    def reader(self, shard, deps):
        def read():
            for f in deps[0]():
                if not len(f):
                    continue
                if self.mode == "jax":
                    cols, n = self._vfn(f.cols, len(f), extra=self.args,
                                        wide=self.wide)
                    yield Frame(cols, self.schema)
                else:
                    rows = [self.fn(*r, *self.args) for r in f.rows()]
                    rows = [
                        r if isinstance(r, tuple) else (r,) for r in rows
                    ]
                    yield Frame.from_rows(rows, self.schema)

        return read()


class MapBatches(_Pipelined):
    """Batch-level host transform: ``fn(frame) -> frame-like`` applied to
    whole columnar batches (vectorized numpy on the host tier).

    The reference's per-record surface has no analog; this is the natural
    escape hatch for host work that vectorizes (dictionary encoding,
    string ops over whole columns) without per-row Python dispatch.
    ``out`` declares the output schema; fn may return a Frame or a tuple
    of columns.
    """

    def __init__(self, slice_: Slice, fn: Callable, out):
        super().__init__(slice_, _as_schema(out), make_name("mapbatches"))
        self.fn = fn

    def reader(self, shard, deps):
        def read():
            for f in deps[0]():
                if not len(f):
                    continue
                o = self.fn(f)
                cols = list(o.cols) if isinstance(o, Frame) else list(o)
                yield Frame(_conform(cols, self.schema), self.schema)

        return read()


def _conform(cols, schema):
    """Coerce device columns to the declared dtypes so the frame schema
    never lies about its columns (the invariant Map's jax path enforces
    by casting). Raises on column-count mismatch rather than silently
    truncating."""
    if len(cols) != len(schema):
        raise typecheck.errorf(
            "batch function returned %d columns but out= declares %d",
            len(cols), len(schema),
        )
    out = []
    for c, ct in zip(cols, schema):
        if ct.is_device:
            a = np.asarray(c)
            if a.dtype != ct.dtype:
                a = a.astype(ct.dtype)
            out.append(a)
        else:
            out.append(c)
    return out


class Filter(_Pipelined):
    """Predicate filter (mirrors bigslice.Filter, slice.go:657-726)."""

    def __init__(self, slice_: Slice, pred: Callable, mode="auto"):
        name = make_name("filter")
        self.pred = pred
        traced = None
        if mode in ("auto", "jax"):
            traced = _try_trace(pred, slice_.schema)
        if traced is not None:
            if (len(traced) != 1
                    or traced[0].dtype != np.dtype(np.bool_)
                    or traced[0].shape != ()):
                raise typecheck.errorf(
                    "filter: predicate must return a scalar bool, got %s",
                    traced,
                )
            self.mode = "jax"
            self._vfn = get_padded_vmap(pred)
        else:
            if mode == "jax":
                raise typecheck.errorf("filter: predicate not jax-traceable")
            self.mode = "host"
        super().__init__(slice_, slice_.schema, name)

    def reader(self, shard, deps):
        def read():
            for f in deps[0]():
                if not len(f):
                    continue
                if self.mode == "jax":
                    (mask,), _ = self._vfn(f.cols, len(f))
                    idx = np.flatnonzero(np.asarray(mask))
                else:
                    idx = np.fromiter(
                        (i for i, r in enumerate(f.rows()) if self.pred(*r)),
                        dtype=np.int64,
                    )
                if len(idx):
                    yield f.take(idx)

        return read()


class Flatmap(_Pipelined):
    """1→N transform (mirrors bigslice.Flatmap, slice.go:745-841).

    Two modes:
    - **host** (default): ``fn(*row)`` yields output rows (any iterable
      of tuples) — arbitrary, dynamic fan-out on the host tier.
    - **device** (``fanout=k``): ``fn(*row) -> (mask, col0, col1, ...)``
      where ``mask`` is bool[k] selecting valid outputs and each column
      is a [k]-shaped array — the XLA-compatible fixed-capacity shape
      for data-dependent fan-out (SURVEY.md §7.3(1) pad/overflow
      strategy). The vmapped fn produces [n, k] planes which flatten and
      compact columnar-ly, never per row.
    """

    def __init__(self, slice_: Slice, fn: Callable, out,
                 fanout: Optional[int] = None):
        name = make_name("flatmap")
        self.fn = fn
        self.fanout = fanout
        schema = _as_schema(out)
        if fanout is not None:
            typecheck.check(fanout >= 1, "flatmap: fanout must be >= 1")
            typecheck.check(
                all(ct.is_device for ct in schema),
                "flatmap: fixed-fanout mode requires device column types",
            )
            if not all(ct.is_device for ct in slice_.schema):
                raise typecheck.errorf(
                    "flatmap: fixed-fanout mode requires device inputs"
                )
            self._check_fixed_trace(slice_, fn, schema, fanout)
            self._vfn = get_padded_vmap(fn)
            self.mode = "jax"
        else:
            self.mode = "host"
        super().__init__(slice_, schema, name)

    @staticmethod
    def _check_fixed_trace(slice_, fn, schema, fanout):
        """Construction-time shape/traceability check (matches Map's
        altitude: clear errors at the call site, not mid-run in vmap)."""
        try:
            import jax

            with wide_scope(slice_.schema.wide or schema.wide):
                specs = [jax.ShapeDtypeStruct((), ct.dtype)
                         for ct in slice_.schema]
                out = jax.eval_shape(fn, *specs)
        except Exception as e:
            raise typecheck.errorf(
                "flatmap: fixed-fanout function is not jax-traceable "
                "over %s (%s)", slice_.schema, e,
            )
        if not isinstance(out, (tuple, list)) or len(out) != 1 + len(schema):
            raise typecheck.errorf(
                "flatmap: fixed-fanout function must return (mask, %d "
                "columns), got %d outputs",
                len(schema),
                len(out) if isinstance(out, (tuple, list)) else 1,
            )
        for i, o in enumerate(out):
            if tuple(o.shape) != (fanout,):
                raise typecheck.errorf(
                    "flatmap: output %d has shape %s, want (%d,) — every "
                    "output (including the mask) must be fanout-wide",
                    i, tuple(o.shape), fanout,
                )
        if np.dtype(out[0].dtype) != np.dtype(np.bool_):
            raise typecheck.errorf(
                "flatmap: first output must be a bool mask, got %s",
                out[0].dtype,
            )

    def reader(self, shard, deps):
        if self.mode == "jax":
            return self._read_fixed(deps)
        return self._read_host(deps)

    def _read_host(self, deps):
        def read():
            pending = []
            npending = 0
            for f in deps[0]():
                for r in f.rows():
                    for o in self.fn(*r):
                        pending.append(o if isinstance(o, tuple) else (o,))
                        npending += 1
                    if npending >= sliceio.DEFAULT_CHUNK_ROWS:
                        yield Frame.from_rows(pending, self.schema)
                        pending, npending = [], 0
            if pending:
                yield Frame.from_rows(pending, self.schema)

        return read()

    def _read_fixed(self, deps):
        def read():
            for f in deps[0]():
                if not len(f):
                    continue
                outs, n = self._vfn(f.cols, len(f),
                                    wide=self.schema.wide)
                mask = np.asarray(outs[0]).reshape(-1)
                cols = [np.asarray(o).reshape(-1) for o in outs[1:]]
                idx = np.flatnonzero(mask)
                if len(idx):
                    yield Frame(_conform([c[idx] for c in cols],
                                         self.schema), self.schema)

        return read()


class Head(_Pipelined):
    """First n rows of each shard (mirrors bigslice.Head, slice.go:966)."""

    def __init__(self, slice_: Slice, n: int):
        super().__init__(slice_, slice_.schema, make_name("head"))
        self.n = n

    def reader(self, shard, deps):
        def read():
            left = self.n
            for f in deps[0]():
                if left <= 0:
                    break
                take = min(left, len(f))
                if take:
                    yield f.slice(0, take)
                left -= take

        return read()


class Scan(_Pipelined):
    """Terminal per-shard sink (mirrors bigslice.Scan, slice.go:1005):
    ``fn(shard, reader)`` consumes the shard's stream; the resulting slice
    is empty.

    By default any stream remainder the sink did not consume is drained
    afterwards, so upstream side effects (WriterFunc taps, metrics)
    always observe the full shard even for sinks that return early — a
    deliberate divergence from the reference, which leaves unread
    remainders unread (slice.go:1022-1028). Pass ``drain=False`` for
    early-exit sinks over expensive sources: skipping the drain avoids
    computing the discarded remainder, and also means a sink's external
    side effects can't be retried due to a post-success upstream loss
    surfacing mid-drain."""

    def __init__(self, slice_: Slice, fn: Callable, drain: bool = True):
        super().__init__(slice_, slice_.schema, make_name("scan"))
        self.fn = fn
        self.drain = drain

    def reader(self, shard, deps):
        r = deps[0]()
        self.fn(shard, r)
        if self.drain:
            for _ in r:  # drain the remainder
                pass
        return sliceio.empty_reader()


class _PrefixedSlice(_Pipelined):
    """Key-prefix widening (mirrors bigslice.Prefixed, slice.go:1044)."""

    def __init__(self, slice_: Slice, prefix: int):
        typecheck.check(prefix >= 1,
                        "prefixed: prefix must include at least one column")
        typecheck.check(
            prefix <= len(slice_.schema),
            "prefixed: prefix %d is greater than number of columns %d",
            prefix, len(slice_.schema),
        )
        super().__init__(slice_, slice_.schema.with_prefix(prefix),
                         make_name("prefixed"))

    def reader(self, shard, deps):
        def read():
            for f in deps[0]():
                yield Frame(f.cols, self.schema)

        return read()


def Prefixed(slice_: Slice, prefix: int) -> Slice:
    return _PrefixedSlice(slice_, prefix)


def Unwrap(slice_: Slice) -> Slice:
    from bigslice_tpu.ops.base import unwrap

    return unwrap(slice_)
