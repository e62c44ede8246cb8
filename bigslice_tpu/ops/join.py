"""JoinAggregate — aggregating inner join, the device-tier join family.

The general ``Cogroup`` (ops/cogroup.py) materializes ragged per-key
groups and is host-tier by nature (cogroup.go:46-272 semantics). The
common *aggregating* joins — combine each side's values per key, then
match keys — never need the ragged groups and lower fully onto the
device. ``JoinAggregate(a, b, a_fn, b_fn)``:

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
"""

from __future__ import annotations

from typing import Callable, List

import numpy as np

from bigslice_tpu import typecheck
from bigslice_tpu.slicetype import Schema
from bigslice_tpu.frame.frame import Frame
from bigslice_tpu.ops.base import Dep, Slice, make_name
from bigslice_tpu.ops.reduce import FrameCombiner


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
        for s, side in ((a, "left"), (b, "right")):
            typecheck.check(
                s.prefix >= 1,
                "join: %s input must have a key prefix", side,
            )
            typecheck.check(
                len(s.schema) > s.prefix,
                "join: %s input must have value columns", side,
            )
        typecheck.check(
            tuple(c.dtype for c in a.schema.key)
            == tuple(c.dtype for c in b.schema.key)
            and a.prefix == b.prefix,
            "join: key column types mismatch: %s vs %s",
            a.schema.key, b.schema.key,
        )
        from bigslice_tpu.frame import ops as frame_ops

        for ct in a.schema.key:
            typecheck.check(
                frame_ops.can_hash(ct) and frame_ops.can_compare(ct),
                "join: key column type %s is not joinable", ct,
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
