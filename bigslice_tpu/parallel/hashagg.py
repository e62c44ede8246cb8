"""Open-addressed hash aggregation on device: sortless keyed combine.

This is the device analog of the reference's combiningFrame — an
open-addressed hash table with linear probing that absorbs rows as they
arrive (exec/combiner.go:56-209) — re-expressed for XLA: claiming a
table slot is a ``scatter-min`` of row indices, probing is a vectorized
re-hash of the rows that lost, and the per-key combine is a
``scatter``-accumulate for classified ops (add/max/min — the same
probe-classification gate as parallel/dense.py; arbitrary combine fns
keep the sort+segmented-scan path, which honors them exactly).

Why it exists: the sort-based pipeline's roofline is the multi-operand
stable sort — ~40x the cost of a scatter pass at the sizes the shuffle
runs (a CPU-mesh A/B). Hash aggregation replaces every sort in
the Reduce/JoinAggregate pipeline with O(rows) scatter/gather passes:

  map side     claim cascade + one scatter-accumulate  (was: sort)
  exchange     the table IS destination-contiguous — its top-level
               regions are partitions, so routing is ONE all_to_all of
               table regions (was: sort-derived bucket scatter)
  reduce side  claim cascade + scatter-accumulate      (was: sort)

Slot layout: ``slot = part * R + (h % R)`` where ``part`` comes from THE
routing contract (parallel/shuffle.partition_ids — bit-identical to the
host tier), so region ``p`` of every device's table holds exactly the
keys of partition ``p`` and the exchange needs no reordering at all.

The claim cascade bounds data-dependent work without dynamic shapes:
a fixed number of full-width rounds resolves the vast majority of rows,
then the stragglers are compacted into a quarter-width buffer and a
``lax.while_loop`` finishes them (static shapes; expected rounds are
O(1) at the load factors the capacity planner produces). Pathological
inputs (near-distinct keys at load → 1, NaN keys, adversarial
collisions) surface as an ``overflow`` signal and the executor retries
the group on the sort path — the same loud-retry philosophy as bucket
skew (exec/meshexec.py slack ladder).
"""

from __future__ import annotations

import os
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

from bigslice_tpu.parallel.jitutil import bucket_size

#: Table-build backends: ``xla`` = the scatter lowering below,
#: ``pallas`` = the Mosaic kernel (parallel/pallas_kernels.py,
#: VMEM-resident table; compiles natively on TPU),
#: ``pallas_interpret`` = the same kernel forced through the pallas
#: interpreter (CPU parity tests / debugging).
BACKENDS = ("xla", "pallas", "pallas_interpret")


def _kernel_backend() -> str:
    """Resolve the table-build backend: BIGSLICE_HASHAGG_BACKEND wins
    (unknown values fail loudly); unset = ``pallas`` on real TPU (the
    whole point — the scatter lowering is what loses there), ``xla``
    everywhere else (bit-identical legacy behavior on CPU meshes)."""
    env = os.environ.get("BIGSLICE_HASHAGG_BACKEND", "").strip().lower()
    if env:
        if env not in BACKENDS:
            raise ValueError(
                f"BIGSLICE_HASHAGG_BACKEND must be one of {BACKENDS}, "
                f"got {env!r}"
            )
        return env
    import jax

    return "pallas" if jax.default_backend() == "tpu" else "xla"

# Claim-cascade shape: FULL_ROUNDS full-width probe rounds, then the
# pending stragglers compact into a size/CASCADE_DIV buffer where a
# while_loop probes up to CASCADE_MAX_ROUNDS more. At the load factors
# the planner produces (<= 0.5 typical) round 1 resolves ~80% of rows
# and the cascade a handful of survivors; the bounds exist for the
# adversarial tail, which exits via the overflow signal instead of
# spinning.
FULL_ROUNDS = 2
CASCADE_DIV = 4
CASCADE_MAX_ROUNDS = 48

_BIG = np.int32(2**31 - 1)


def _slot_hash(key_cols, seed: int):
    """Within-region slot hash — independent of the routing hash (a
    different seed stream), so a partition's keys spread over its region
    instead of clustering on their shared routing residue."""
    from bigslice_tpu.frame import ops as frame_ops

    h = None
    for k in key_cols:
        kh = frame_ops.hash_device_column(k, seed ^ 0x51ED2770)
        h = kh if h is None else frame_ops.combine_hashes(h, kh)
    return h  # uint32[n]


def claim_cascade(valid, key_cols, part, nparts: int, R: int,
                  seed: int = 0):
    """Assign one table slot per distinct key of the selected rows.

    ``part`` (int32[n], sentinel >= nparts excluded) picks the region;
    probing stays inside the region so region p only ever holds
    partition-p keys. ``R`` must be a power of two.

    Returns ``(winner, placed, overflow)``: ``winner`` int32[T+1]
    (T = nparts*R) holding the claiming row index per slot (or INT_MAX),
    ``placed`` int32[n] each row's resolved slot (-1 for excluded or
    unresolved rows), ``overflow`` int32 — rows the cascade could not
    place (0 on success; callers must treat any nonzero as "discard and
    retry elsewhere").
    """
    import jax.numpy as jnp
    from jax import lax

    n = key_cols[0].shape[0]
    T = nparts * R
    mask_R = np.int32(R - 1)
    h = _slot_hash(key_cols, seed)
    off = (h & np.uint32(R - 1)).astype(np.int32)
    # Double hashing: an odd stride is coprime with the pow2 region, so
    # the probe sequence visits every slot; keys sharing a start slot
    # separate immediately instead of convoying (linear probing's
    # clustering is what pushed the while_loop to 15 rounds in the
    # round-5 calibration).
    stride = (((h >> np.uint32(9)) | np.uint32(1))
              & np.uint32(mask_R)).astype(np.int32)
    in_range = part < nparts
    base = jnp.where(in_range, part, nparts).astype(np.int32) * np.int32(R)
    pending = valid & in_range
    iota = jnp.arange(n, dtype=np.int32)
    winner = jnp.full(T + 1, _BIG, np.int32)
    placed = jnp.full(n, np.int32(-1))

    def full_round(state):
        pending, off, winner, placed = state
        slot = base + off
        # Claim only EMPTY slots: a slot claimed in an earlier round is
        # frozen — letting a smaller row index steal it later would
        # merge two keys' accumulations into one slot. Within-round
        # races still resolve by scatter-min; losers re-probe.
        slot_c = jnp.minimum(slot, np.int32(T - 1))
        empty = winner[slot_c] == _BIG
        cand = jnp.where(pending & empty, slot, np.int32(T))
        winner = winner.at[cand].min(
            jnp.where(pending, iota, _BIG), mode="drop"
        )
        win = winner[slot_c]
        has = win < n
        winc = jnp.minimum(win, np.int32(n - 1))
        eq = has
        for kc in key_cols:
            eq = eq & (kc[winc] == kc)
        matched = pending & eq
        placed = jnp.where(matched, slot, placed)
        pending = pending & ~matched
        off = jnp.where(pending, (off + stride) & mask_R, off)
        return pending, off, winner, placed

    state = (pending, off, winner, placed)
    for _ in range(FULL_ROUNDS):
        state = full_round(state)
    pending, off, winner, placed = state

    # Compact the stragglers' row ids into a quarter-width buffer; the
    # originals' key/value columns are reached through the indirection.
    C = max(n // CASCADE_DIV, 1)
    pi = pending.astype(np.int32)
    rank = jnp.cumsum(pi).astype(np.int32) - pi
    pcount = pi.sum().astype(np.int32)
    overflow = jnp.maximum(pcount - np.int32(C), 0)
    dest = jnp.where(pending & (rank < C), rank, np.int32(C))
    ridx = jnp.full(C + 1, np.int32(n)).at[dest].set(
        jnp.where(pending, iota, np.int32(n)), mode="drop"
    )[:C]

    def gat(x, fill):
        v = x[jnp.minimum(ridx, np.int32(n - 1))]
        return jnp.where(ridx < n, v, fill)

    offc = gat(off, np.int32(0))
    basec = gat(base, np.int32(T))
    stridec = gat(stride, np.int32(1))

    def cond(st):
        i, ridx, offc, winner, placed = st
        return (ridx < n).any() & (i < CASCADE_MAX_ROUNDS)

    def body(st):
        i, ridx, offc, winner, placed = st
        act = ridx < n
        slot = jnp.minimum(basec + offc, np.int32(T))
        slot_c = jnp.minimum(slot, np.int32(T - 1))
        empty = winner[slot_c] == _BIG
        cand = jnp.where(act & empty, slot, np.int32(T))
        winner = winner.at[cand].min(
            jnp.where(act, ridx, _BIG), mode="drop"
        )
        win = winner[slot_c]
        has = win < n
        winc = jnp.minimum(win, np.int32(n - 1))
        rc = jnp.minimum(ridx, np.int32(n - 1))
        eq = has
        for kc in key_cols:
            eq = eq & (kc[winc] == kc[rc])
        matched = act & eq
        placed = placed.at[jnp.where(matched, rc, np.int32(n))].set(
            jnp.where(matched, slot, np.int32(-1)), mode="drop"
        )
        ridx = jnp.where(matched, np.int32(n), ridx)
        offc = jnp.where(act & ~matched, (offc + stridec) & mask_R, offc)
        return i + 1, ridx, offc, winner, placed

    i, ridx, offc, winner, placed = lax.while_loop(
        cond, body, (jnp.int32(0), ridx, offc, winner, placed)
    )
    overflow = overflow + (ridx < n).sum().astype(np.int32)
    return winner, placed, overflow


def hash_aggregate(valid, key_cols, val_cols, ops: Sequence[str],
                   part, nparts: int, R: int, seed: int = 0,
                   backend: Optional[str] = None):
    """Aggregate the selected rows by key into a [nparts*R] open table.

    Returns ``(present, out_keys, out_vals, overflow)`` — slot-resident
    results: ``present`` bool[T], key/value columns [T] (junk where not
    present; callers chain masks or compact). ``ops`` are the per-column
    classified combine ops ('add'|'max'|'min').

    ``backend`` picks the table build: None resolves via
    ``_kernel_backend()`` (env knob, then platform default). The Mosaic
    kernel serves supported shapes/dtypes; anything it cannot take
    falls back to the XLA scatter path below — slot layout may differ
    between backends (sequential vs batched claim resolution) but the
    per-region key sets and per-key combined values do not.
    """
    import jax.numpy as jnp

    from bigslice_tpu.parallel.dense import _identity, _scatter_tables

    be = _kernel_backend() if backend is None else backend
    if be != "xla":
        from bigslice_tpu.parallel import pallas_kernels as pk

        if pk.aggregate_supported(
            [k.dtype for k in key_cols],
            [v.dtype for v in val_cols], nparts, R,
        ):
            return pk.hash_aggregate_pallas(
                valid, key_cols, val_cols, ops, part, nparts, R, seed,
                interpret=(True if be == "pallas_interpret" else None),
            )

    n = key_cols[0].shape[0]
    T = nparts * R
    winner, placed, ov = claim_cascade(valid, key_cols, part, nparts, R,
                                       seed)
    idx = jnp.where(placed >= 0, placed, np.int32(T))
    idents = [_identity(op, v.dtype) for op, v in zip(ops, val_cols)]
    present, tables = _scatter_tables(idx, list(val_cols), list(ops),
                                      idents, T + 1)
    winc = jnp.minimum(winner[:T], np.int32(n - 1))
    out_keys = [kc[winc] for kc in key_cols]
    return present[:T], out_keys, [t[:T] for t in tables], ov


def combine_region_size(size: int, nparts: int) -> int:
    """Power-of-two region size for an input of ``size`` rows split over
    ``nparts`` partitions: the table matches the input's row budget
    (load factor <= 1; typically far lower after map-side reduction),
    so the exchanged volume never exceeds what the sort pipeline's
    receive buffers already carried."""
    return bucket_size(max(1, -(-size // nparts)))


def make_hash_combine(nkeys: int, nvals: int, ops: Sequence[str],
                      seed: int = 0, backend: Optional[str] = None):
    """Sortless replacement for make_segmented_reduce_masked (classified
    ops only): ``core(valid, key_cols, val_cols) -> (mask, keys, vals,
    overflow)`` with results slot-resident in a bucket_size(n) table.
    Unlike the sort core the output is hash-ordered, which no consumer
    observes (combined streams are re-combined or compacted, never
    merge-read — exec/local.py _dep_factory)."""
    import jax.numpy as jnp

    def core(valid, key_cols, val_cols):
        n = key_cols[0].shape[0]
        R = bucket_size(n)
        part = jnp.zeros(n, np.int32)
        present, ok, ovs, ov = hash_aggregate(
            valid, tuple(key_cols), tuple(val_cols), ops, part, 1, R,
            seed, backend=backend,
        )
        return present, tuple(ok), tuple(ovs), ov

    return core


def make_hash_combine_shuffle(nmesh: int, nkeys: int, nvals: int,
                              ops: Sequence[str], axis: str,
                              seed: int = 0,
                              partition_fn: Optional[Callable] = None,
                              nparts: Optional[int] = None,
                              backend: Optional[str] = None):
    """Fused map-side combine + shuffle with zero sorts.

    The aggregation table is destination-contiguous (region p = the keys
    partition_ids routes to p), so the shuffle is ONE all_to_all of the
    table's regions — same ``.masked`` contract as
    make_combine_shuffle_fn: ``(recv_mask, overflow, bad, out_cols)``
    with out_cols = [subid?] + keys + vals of nmesh*W*R rows per device
    (W = wave count when partitions outnumber the mesh; the subid
    column leads, as in the sort shuffle).

    ``overflow`` here means the claim cascade failed (load factor too
    high / adversarial keys) — the caller must discard the result and
    fall back to the sort pipeline, NOT grow slack.
    """
    import jax.numpy as jnp
    from jax import lax

    from bigslice_tpu.parallel import shuffle as shuffle_mod

    if nparts is None:
        nparts = nmesh
    W = -(-nparts // nmesh)

    def body_masked(valid, *cols):
        size = cols[0].shape[0]
        keys = cols[:nkeys]
        vals = cols[nkeys:]
        part, bad, _ = shuffle_mod.partition_ids(
            keys, nparts, seed, valid=valid, partition_fn=partition_fn,
        )
        n_bad = (
            jnp.int32(0) if bad is None
            else (bad & valid).sum().astype(np.int32)
        )
        R = combine_region_size(size, nparts)
        present, ok, ovs, ov = hash_aggregate(
            valid, keys, vals, ops, part, nparts, R, seed,
            backend=backend,
        )

        def route(x):
            planes = x.reshape((nparts, R) + x.shape[1:])
            if nparts < nmesh * W:
                pad = jnp.zeros(
                    (nmesh * W - nparts, R) + x.shape[1:], x.dtype
                )
                planes = jnp.concatenate([planes, pad], 0)
            if W > 1:
                # Region p -> device p % nmesh carrying subid p // nmesh:
                # regroup region rows device-major so the a2a split
                # hands each device its own W regions from every source.
                planes = planes.reshape((W, nmesh, R) + x.shape[1:])
                planes = planes.swapaxes(0, 1)
                planes = planes.reshape((nmesh, W * R) + x.shape[1:])
            recv = lax.all_to_all(planes, axis, 0, 0, tiled=False)
            return recv.reshape((nmesh * W * R,) + x.shape[1:])

        recv_mask = route(present)
        out_cols = [route(c) for c in list(ok) + list(ovs)]
        if W > 1:
            subid = jnp.tile(
                jnp.repeat(jnp.arange(W, dtype=np.int32), R), nmesh
            )
            out_cols = [subid] + out_cols
        total_ov = lax.psum(ov, axis)
        total_bad = lax.psum(n_bad, axis)
        return recv_mask, total_ov, total_bad, tuple(out_cols)

    class _Body:
        masked = staticmethod(body_masked)

    return _Body()


def make_hash_join_align(nkeys: int, ops_a: Sequence[str],
                         ops_b: Sequence[str], seed: int = 0):
    """Sortless aggregating inner join: ONE claim cascade over the union
    of both sides' rows assigns every distinct key a slot, each side
    scatter-accumulates into its own value tables, and the match is an
    elementwise AND of the presence planes — replacing the two
    segmented reduces + tagged alignment sort of the generic path
    (exec/meshexec.py join_prelude; reference: the cogroup sort-merge,
    cogroup.go:46-272, specialized to the aggregating join).

    ``align(mask_a, cols_a, mask_b, cols_b) -> (mask, cols, overflow)``
    with cols = (keys..., vals_a..., vals_b...) of bucket_size(nA+nB)
    rows.
    """
    import jax.numpy as jnp

    from bigslice_tpu.parallel.dense import _identity, _scatter_tables

    def align(mask_a, cols_a, mask_b, cols_b):
        ka = cols_a[:nkeys]
        va = cols_a[nkeys:]
        kb = cols_b[:nkeys]
        vb = cols_b[nkeys:]
        na = ka[0].shape[0]
        nb = kb[0].shape[0]
        n = na + nb
        keys = tuple(
            jnp.concatenate([a, b]) for a, b in zip(ka, kb)
        )
        valid = jnp.concatenate([mask_a, mask_b])
        R = bucket_size(n)
        part = jnp.zeros(n, np.int32)
        winner, placed, ov = claim_cascade(valid, keys, part, 1, R, seed)
        T = R

        def side(placed_side, vals, ops):
            idx = jnp.where(placed_side >= 0, placed_side, np.int32(T))
            idents = [_identity(op, v.dtype)
                      for op, v in zip(ops, vals)]
            present, tables = _scatter_tables(
                idx, list(vals), list(ops), idents, T + 1
            )
            return present[:T], [t[:T] for t in tables]

        pa, ta = side(placed[:na], va, ops_a)
        pb, tb = side(placed[na:], vb, ops_b)
        winc = jnp.minimum(winner[:T], np.int32(n - 1))
        out_keys = [kc[winc] for kc in keys]
        mask = pa & pb
        return mask, list(out_keys) + ta + tb, ov

    return align


class MeshHashReduceByKey:
    """Mesh-wide keyed reduction with ZERO sorts, as one jitted SPMD
    program: fused hash combine + region all_to_all (map side) →
    claim-cascade re-combine (reduce side) → mask compaction. The
    standalone-kernel counterpart of shuffle.MeshReduceByKey for
    classified combine ops ('add'|'max'|'min' per value column) — the
    same lowering the mesh executor fuses into op groups, exposed at
    kernel granularity for benches and wave-streaming drivers.

    ``__call__(key_cols, val_cols, counts)`` with columns globally
    shaped [nshards*capacity, ...] sharded on axis 0 and counts
    int32[nshards]; returns (key_cols, val_cols, out_counts, overflow).
    ``overflow`` > 0 means a claim cascade failed (load factor ~1 /
    adversarial keys): discard the result and re-run on the sort path
    (shuffle.MeshReduceByKey) — the executor's fallback contract.

    ``donate=True`` donates the staged inputs to the program
    (jitutil.jit_maybe_donate): steady-state wave streaming re-stages
    fresh columns per call and reuses their HBM here.
    """

    def __init__(self, mesh, nkeys: int, nvals: int, capacity: int,
                 ops: Sequence[str], seed: int = 0,
                 donate: bool = False):
        from jax.sharding import PartitionSpec as P

        from bigslice_tpu.parallel.jitutil import jit_maybe_donate
        from bigslice_tpu.parallel.meshutil import (
            get_shard_map,
            mesh_axis,
        )
        from bigslice_tpu.parallel.segment import compact_by_mask

        shard_map = get_shard_map()
        axis = mesh_axis(mesh)
        nshards = mesh.devices.size
        self.mesh = mesh
        self.nshards = nshards
        self.capacity = capacity
        ncols = nkeys + nvals
        fused = make_hash_combine_shuffle(
            nshards, nkeys, nvals, ops, axis, seed
        )
        recv_rows = nshards * combine_region_size(capacity, nshards)
        self.out_capacity = bucket_size(recv_rows)
        final = make_hash_combine(nkeys, nvals, ops, seed)

        def stepped(counts, *cols):
            import jax.numpy as jnp
            from jax import lax

            n = counts[0]
            size = cols[0].shape[0]
            mask0 = jnp.arange(size, dtype=np.int32) < n
            recv_mask, ov1, _bad, out_cols = fused.masked(mask0, *cols)
            mask2, k2, v2, ov2 = final(
                recv_mask, tuple(out_cols[:nkeys]),
                tuple(out_cols[nkeys:]),
            )
            out_n, packed = compact_by_mask(
                mask2, list(k2) + list(v2)
            )
            overflow = ov1 + lax.psum(ov2, axis)
            return out_n.reshape(1), overflow, tuple(packed)

        col_spec = P(axis)
        in_specs = (col_spec,) + tuple(col_spec for _ in range(ncols))
        out_specs = (col_spec, P(),
                     tuple(col_spec for _ in range(ncols)))
        self._jitted = jit_maybe_donate(
            shard_map(stepped, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_rep=False),
            tuple(range(1 + ncols)) if donate else (),
        )

    def __call__(self, key_cols: Sequence, val_cols: Sequence, counts):
        nkeys = len(key_cols)
        out_counts, overflow, cols = self._jitted(
            counts, *(list(key_cols) + list(val_cols))
        )
        return (list(cols[:nkeys]), list(cols[nkeys:]), out_counts,
                overflow)
