"""The device-tier join family: ``JoinAggregate`` and ``JoinLookup``.

Which join to use when:

- ``JoinAggregate(a, b, a_fn, b_fn)`` — both sides are REDUCED to one
  row a key (each with its own combine fn) and the keys present in both
  are matched 1:1. Counts, sums, maxima per key on both sides; never a
  row of either side as it was.
- ``JoinLookup(probe, build)`` — an N:1 inner join: ``build`` holds at
  most one row a key (a dimension table, a filtered parent), ``probe``
  any number (the fact rows), and every probe row whose key is in
  ``build`` comes out once with the build row's values behind its own.
  Upstream spells it ``Cogroup`` + ``Flatmap``; here that would run on
  the host (below), so it is a combinator of its own (PARITY.md).
- ``Cogroup`` (ops/cogroup.py) — the general N:M grouping with ragged
  per-key lists: host-tier by nature (cogroup.go:46-272 semantics).

``JoinAggregate(a, b, a_fn, b_fn)``:

1. each side is shuffled by key prefix with *its own* map-side combiner
   (``a_fn`` / ``b_fn``) — the compiler's per-dep combiner plumbing
   routes equal keys of both sides to the same consumer shard
   (cogroup.go's shared-shuffle contract, realized as all_to_all on the
   mesh path);
2. the join task finishes each side's reduction (sort + segmented
   scan — one row per key per side) and aligns the two sides by a
   tagged key sort, matching adjacent (A, B) rows with equal keys;
3. output rows are (key..., a_agg..., b_agg...) for keys present in
   BOTH sides (inner join).

On the mesh executor the whole join group is one SPMD program per
device — two segmented reduces and one alignment sort, no host
materialization; the shuffles ride the producer edges as all_to_all.
This is the TPU lowering of BASELINE.json's "Reduce+Cogroup join"
headline shape. The host tier runs the same contract on numpy for
ineligible inputs (host keys, non-traceable combine fns).

``JoinLookup(probe, build)`` shuffles both sides by key with no
combiner (every probe row survives the shuffle) and joins each consumer
shard's two partitions: on the mesh executor one stable sort of the
union with the build rows ahead and one segmented carry of the build
row's values (parallel/join.make_lookup_align), on the host tier a
sorted-key lookup in numpy. A build side with two rows of one key is
the user's error and raises ``DuplicateBuildKeyError`` on both tiers.
"""

from __future__ import annotations

from typing import Callable, List

import numpy as np

from bigslice_tpu import typecheck
from bigslice_tpu.slicetype import Schema
from bigslice_tpu.frame.frame import Frame
from bigslice_tpu.ops.base import Dep, Slice, make_name
from bigslice_tpu.ops.reduce import FrameCombiner


class DuplicateBuildKeyError(ValueError):
    """``JoinLookup``'s build side held two rows of one key."""

    def __init__(self, op, dups: int):
        self.op, self.dups = str(op), int(dups)
        super().__init__(
            f"joinlookup: the build side of {op} holds {dups} row(s) "
            f"whose key another build row has; JoinLookup needs at "
            f"most one build row a key (Reduce the build side first, "
            f"or use JoinAggregate)")


def _check_join_sides(op: str, sides) -> None:
    """The typechecks every join of this family shares: each side keyed,
    the key prefixes of the same types, every key column joinable."""
    from bigslice_tpu.frame import ops as frame_ops

    (a, _), (b, _) = sides
    for s, side in sides:
        typecheck.check(
            s.prefix >= 1,
            "%s: %s input must have a key prefix", op, side,
        )
    typecheck.check(
        tuple(c.dtype for c in a.schema.key)
        == tuple(c.dtype for c in b.schema.key)
        and a.prefix == b.prefix,
        "%s: key column types mismatch: %s vs %s",
        op, a.schema.key, b.schema.key,
    )
    for ct in a.schema.key:
        typecheck.check(
            frame_ops.can_hash(ct) and frame_ops.can_compare(ct),
            "%s: key column type %s is not joinable", op, ct,
        )


class JoinAggregate(Slice):
    """Inner-join two keyed slices after per-side keyed reduction.

    Output schema: key columns (shared by both sides, typechecked) +
    side A's value columns + side B's value columns; one row per key
    present in both sides. ``a_fn``/``b_fn`` are associative pairwise
    combine functions over each side's value columns (bigslice.Reduce
    form for single-value sides).
    """

    def __init__(self, a: Slice, b: Slice, a_fn: Callable,
                 b_fn: Callable, dense_keys=None):
        sides = ((a, "left"), (b, "right"))
        _check_join_sides("join", sides)
        for s, side in sides:
            typecheck.check(
                len(s.schema) > s.prefix,
                "join: %s input must have value columns", side,
            )
        schema = Schema(
            list(a.schema.key) + list(a.schema.values)
            + list(b.schema.values),
            prefix=a.prefix,
        )
        num_shards = max(a.num_shards, b.num_shards)
        super().__init__(schema, num_shards, make_name("join"),
                         pragmas=tuple(a.pragmas) + tuple(b.pragmas))
        self.a, self.b = a, b
        # Per-dep map-side combiners: the compiler attaches
        # frame_combiners[i] to dep i's producer tasks (exec/compile.py
        # _frame_combiner), so each side pre-reduces before its shuffle.
        # ``dense_keys``: both sides' (single int32) keys are dense
        # codes in [0, dense_keys) — each side's map-side combine +
        # shuffle AND the join's alignment take the sort-free dense
        # lowering (parallel/dense.py) when the combine fns classify as
        # add/max/min; otherwise the declaration is ignored.
        self.frame_combiners = (
            FrameCombiner(a_fn, a.schema, dense_keys=dense_keys),
            FrameCombiner(b_fn, b.schema, dense_keys=dense_keys),
        )

    def deps(self):
        return (Dep(self.a, shuffle=True, expand=True),
                Dep(self.b, shuffle=True, expand=True))

    def reader(self, shard, deps):
        def read():
            fa = self.frame_combiners[0].combine_frames(list(deps[0]()))
            fb = self.frame_combiners[1].combine_frames(list(deps[1]()))
            out = _inner_join(fa, fb, self.prefix, self.schema)
            if len(out):
                yield out

        return read()


def _inner_join(fa: Frame, fb: Frame, nkeys: int, schema: Schema) -> Frame:
    """Inner-join two reduced frames (unique keys per side) on their key
    prefixes. Device single-key sides use vectorized intersect; general
    keys fall back to a tuple-keyed dict."""
    if not len(fa) or not len(fb):
        return Frame.empty(schema)
    ka = [np.asarray(c) for c in fa.cols[:nkeys]]
    kb = [np.asarray(c) for c in fb.cols[:nkeys]]
    if nkeys == 1 and ka[0].dtype != object and kb[0].dtype != object:
        _, ia, ib = np.intersect1d(
            ka[0], kb[0], assume_unique=True, return_indices=True
        )
    else:
        index = {
            tuple(c[i] for c in kb): i for i in range(len(fb))
        }
        ia_list: List[int] = []
        ib_list: List[int] = []
        for i in range(len(fa)):
            j = index.get(tuple(c[i] for c in ka))
            if j is not None:
                ia_list.append(i)
                ib_list.append(j)
        ia = np.asarray(ia_list, dtype=np.int64)
        ib = np.asarray(ib_list, dtype=np.int64)
    cols = (
        [c[ia] for c in fa.cols[:nkeys]]
        + [c[ia] for c in fa.cols[nkeys:]]
        + [c[ib] for c in fb.cols[nkeys:]]
    )
    return Frame(cols, schema)


class JoinLookup(Slice):
    """N:1 inner join: every ``probe`` row whose key is in ``build``,
    with the build row's values behind its own.

    ``probe`` and ``build`` are keyed slices whose key prefixes have the
    same types; ``build`` holds at most one row a key, ``probe`` any
    number. Output schema: key columns + the probe side's value columns
    + the build side's value columns, one row for every probe row with
    a match; probe rows without one are dropped, and the order inside a
    shard is unspecified. Two build rows with one key raise
    ``DuplicateBuildKeyError``. Value columns of either side may be
    64-bit (declared).
    """

    def __init__(self, probe: Slice, build: Slice):
        _check_join_sides("joinlookup",
                          ((probe, "probe"), (build, "build")))
        schema = Schema(
            list(probe.schema.key) + list(probe.schema.values)
            + list(build.schema.values),
            prefix=probe.prefix,
        )
        super().__init__(
            schema, max(probe.num_shards, build.num_shards),
            make_name("joinlookup"),
            pragmas=tuple(probe.pragmas) + tuple(build.pragmas))
        self.probe, self.build = probe, build

    def deps(self):
        return (Dep(self.probe, shuffle=True),
                Dep(self.build, shuffle=True))

    def reader(self, shard, deps):
        def read():
            build = [f.to_host() for f in deps[1]() if len(f)]
            if not build:
                return
            lookup = _build_index(Frame.concat(build), self.prefix,
                                  self.name)
            for f in deps[0]():
                if len(f):
                    out = lookup(f.to_host(), self.schema)
                    if len(out):
                        yield out

        return read()


def _build_index(build: Frame, nkeys: int, op) -> Callable:
    """``lookup(probe_frame, schema) -> joined frame`` over the build
    side's rows: a sorted key column and a binary search for one
    numeric key column, a tuple-keyed dict otherwise. Raises
    ``DuplicateBuildKeyError`` when two build rows share a key."""
    keys = [np.asarray(c) for c in build.cols[:nkeys]]
    bvals = [np.asarray(c) for c in build.cols[nkeys:]]
    if nkeys == 1 and keys[0].dtype != object:
        order = np.argsort(keys[0], kind="stable")
        skeys = keys[0][order]
        dups = int(np.count_nonzero(skeys[1:] == skeys[:-1]))

        def find(pkeys):
            at = np.minimum(np.searchsorted(skeys, pkeys[0]),
                            len(skeys) - 1)
            hit = np.flatnonzero(skeys[at] == pkeys[0])
            return hit, order[at[hit]]
    else:
        index = {}
        for i, k in enumerate(zip(*keys)):
            index.setdefault(k, i)
        dups = len(build) - len(index)

        def find(pkeys):
            rows = [index.get(k, -1) for k in zip(*pkeys)]
            rows = np.asarray(rows, dtype=np.int64).reshape(-1)
            hit = np.flatnonzero(rows >= 0)
            return hit, rows[hit]

    if dups:
        raise DuplicateBuildKeyError(op, dups)

    def lookup(probe: Frame, schema: Schema) -> Frame:
        pcols = [np.asarray(c) for c in probe.cols]
        ip, ib = find(pcols[:nkeys])
        return Frame([c[ip] for c in pcols] + [c[ib] for c in bvals],
                     schema)

    return lookup
