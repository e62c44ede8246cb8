"""Hierarchical shuffle over a 2-D (DCN × ICI) device mesh.

The 1-D shuffle (parallel/shuffle.py) issues ONE all_to_all over a
flat axis — ideal when every link is ICI. Multi-pod topologies are
not flat: chips within a pod slice talk over ICI, pods talk over DCN,
and a flat all_to_all over the combined mesh sends (D·I)² small
messages with no regard for which link each crosses. This module is
the multi-axis re-expression (the "collectives ride ICI, not DCN"
recipe; SURVEY.md §5.8, design.md future-work #1): shuffle a 2-D mesh
``Mesh(devices.reshape(D, I), ("dcn", "ici"))`` in TWO stages —

1. **ICI stage**: every device buckets its rows by destination ICI
   lane and exchanges along the fast intra-group axis. Afterward,
   device (g, i) holds every row of group g destined to lane i of ANY
   group.
2. **DCN stage**: rows bucket by destination group and exchange along
   the slow axis. Each (source-group, dest-group) pair per lane moves
   as ONE aggregated message — I× fewer, I× larger DCN transfers than
   the flat exchange, which is exactly how DCN latency amortizes.

Routing, capacity, slack, and overflow semantics mirror the 1-D
shuffle: fixed-capacity buckets (static shapes), counts ride a tiny
all_to_all per stage, skew surfaces as a global overflow count and the
caller retries with more slack. Both stages reuse the shared routing
contract (shuffle.partition_ids — the same murmur hash % nparts as
every other tier) and the shared bucket exchange
(shuffle.bucket_exchange), so the hierarchical path cannot drift from
the flat one; a parity test pins per-destination row sets against the
1-D shuffle on the flattened mesh.

Shard numbering over the 2-D mesh is row-major: global shard
``s = g * I + i`` lives on device (g, i) — matching
``mesh.devices.reshape(D, I)`` of the flat device list, so a 1-D
shuffle over the same devices produces the same per-shard contents.

The out-of-core shuffle plan (exec/shuffleplan.py) composes with this
module unchanged: under ``BIGSLICE_SHUFFLE=spill`` each map-side wave
still runs the two-stage hierarchical exchange built here — only the
CROSS-WAVE merge's device residency is replaced by store-mediated
spill entries, addressed through the same flat output contract
(partition p on device p % N, wave-partitioned subid leading column)
the executor's partition_cols helper reads back. Spill-vs-in-memory
bit-parity on a (D, I) grid is pinned in tests/test_spill_shuffle.py.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import numpy as np

from bigslice_tpu.parallel.jitutil import jit, jit_maybe_donate
from bigslice_tpu.parallel.meshutil import get_shard_map
from bigslice_tpu.parallel.shuffle import (
    bucket_exchange,
    make_combine_shuffle_fn,
    partition_ids,
    route_to_buckets,
    send_capacity,
)


def exchange_plan(ndcn: int, nici: int, nparts: int, capacity: int,
                  slack: float) -> dict:
    """THE capacity/structure plan of the two-stage exchange — the ONE
    source both the kernel builders (make_hier_shuffle_fn /
    make_hier_combine_shuffle_fn) and the executor's exchange
    telemetry consume, so the recorded per-axis traffic can never
    drift from the buckets the program actually moves.

    Returns: ``waved`` (nparts > D·I — quotient/subid columns engage),
    ``ndest1`` (stage-1 ICI lanes addressed), ``cap1`` (per-lane
    stage-1 bucket rows), ``ngroups`` (DCN groups addressed), ``cap2``
    (per-group stage-2 bucket rows), ``stage1_extra_cols`` /
    ``stage2_extra_cols`` (int32 routing columns riding each stage's
    payload: the quotient on ICI — reported in the fused kernel's
    shape, present when nparts > I; the plain kernel also carries it
    in the tiny nparts ≤ I padded edge, a 4 B/row underestimate
    there — and the subid on DCN when waved)."""
    nshards = ndcn * nici
    waved = nparts > nshards
    ndest1 = max(1, min(nici, nparts))
    # Stage 2's logical per-group share is capacity/groups-used (a
    # device's post-stage-1 VALID rows total ~capacity under a uniform
    # hash); basing cap2 on stage 1's receive buffer would compound
    # slack twice and double the DCN payload for the same skew
    # tolerance.
    ngroups = ndcn if waved else max(1, min(ndcn, -(-nparts // nici)))
    return {
        "waved": waved,
        "ndest1": ndest1,
        "cap1": send_capacity(capacity, ndest1, slack),
        "ngroups": ngroups,
        "cap2": send_capacity(capacity, ngroups, slack),
        "stage1_extra_cols": 1 if nparts > nici else 0,
        "stage2_extra_cols": 1 if waved else 0,
    }


def dcn_stage(mask1, dest_g, payload_cols, ndcn: int, cap2: int,
              dcn_axis: str, waved: bool = False):
    """Stage 2 of the hierarchical exchange — ONE implementation shared
    by the plain two-stage shuffle and the fused combine+shuffle reduce:
    received rows carry their destination group-index in ``dest_g``;
    bucket by it and exchange along the slow DCN axis. Each
    (source-group, dest-group) pair per lane moves as ONE aggregated
    message. ``waved`` handles wave-partitioned outputs (nparts >
    D·I): ``dest_g`` is then the combined quotient ``part // nici`` =
    ``subid * ndcn + group``, whose group selects the DCN lane and
    whose subid rides out as the leading int32 output column — the
    same subid contract the flat waved shuffle emits. Returns
    (mask2, local_overflow, out_cols)."""
    import jax.numpy as jnp

    if waved:
        g2 = jnp.where(mask1, dest_g % np.int32(ndcn), np.int32(ndcn))
        payload_cols = (
            (dest_g // np.int32(ndcn)).astype(np.int32),
        ) + tuple(payload_cols)
    else:
        g2 = jnp.where(mask1, dest_g, np.int32(ndcn))
    cols2, counts2 = route_to_buckets(
        g2, tuple(payload_cols), ndcn,
    )
    mask2, out_cols = bucket_exchange(
        dcn_axis, ndcn, cap2, counts2, cols2,
    )
    ov2 = jnp.maximum(counts2.max() - cap2, 0)
    return mask2, ov2, out_cols


def make_hier_shuffle_fn(ndcn: int, nici: int, nkeys: int,
                         capacity: int,
                         dcn_axis: str = "dcn", ici_axis: str = "ici",
                         seed: int = 0,
                         partition_fn: Optional[Callable] = None,
                         slack: float = 2.0,
                         nparts: Optional[int] = None):
    """Build the per-device two-stage shuffle body (wrap in shard_map
    over a ("dcn", "ici") mesh).

    ``body(n, *cols) -> (out_count, overflow, out_cols)`` with
    ``out_cols`` carrying ``nici * cap1`` rows after stage 1 re-bucketed
    into ``ndcn * cap2`` rows after stage 2, valid rows compacted to
    the front. Capacities: cap1 = slack-padded per-lane share of
    ``capacity``; cap2 = slack-padded per-group share of stage 1's
    receive buffer.

    ``nparts`` (default ``ndcn * nici``) is the executor's partition
    count, with the same contract as ``make_shuffle_fn``: it may be
    SMALLER than the mesh (padded groups — trailing shards receive
    nothing) or LARGER (wave-partitioned outputs: partition p lives on
    shard ``p % (D·I)`` with subid ``p // (D·I)`` emitted as the extra
    leading int32 output column). Shard numbering stays row-major
    (``s = g·I + i``), so per-destination row sets match the flat
    shuffle's for every nparts.
    """
    import jax.numpy as jnp
    from jax import lax

    nshards = ndcn * nici
    if nparts is None:
        nparts = nshards
    plan = exchange_plan(ndcn, nici, nparts, capacity, slack)
    waved = plan["waved"]
    cap1 = plan["cap1"]
    cap2 = plan["cap2"]

    def body_masked(valid, *cols):
        size = cols[0].shape[0]
        keys = cols[:nkeys]
        # Global destination partition from the SHARED routing contract;
        # out-of-range partitioner ids park at the drop sentinel.
        part, bad, _ = partition_ids(
            keys, nparts, seed, valid=valid, partition_fn=partition_fn,
        )
        n_bad = (
            jnp.int32(0) if bad is None
            else (bad & valid).sum().astype(np.int32)
        )
        routable = part < nparts
        # Quotient index: plain dest group for nparts <= D·I, the
        # combined subid·D + group encoding in waved mode (dcn_stage
        # splits it back apart). Non-routable rows drop at stage 1, so
        # their quotient value never travels.
        dest_g = jnp.where(routable, part // np.int32(nici),
                           np.int32(0))
        dest_i = jnp.where(routable, part % np.int32(nici),
                           np.int32(nici))

        # ---- Stage 1: bucket by destination ICI lane, exchange on
        # the fast axis. dest_g rides along as a payload column.
        stage1_cols = (dest_g.astype(np.int32),) + tuple(cols)
        cols1, counts1 = route_to_buckets(
            dest_i, stage1_cols, nici,
        )
        mask1, recv_cols = bucket_exchange(
            ici_axis, nici, cap1, counts1, cols1,
        )
        ov1 = jnp.maximum(counts1.max() - cap1, 0)

        # ---- Stage 2: received rows carry their destination group in
        # the leading column; bucket by it and exchange on DCN. Each
        # (src group, dst group) pair moves as one message PER ICI
        # LANE — I messages per pod pair, down from the flat
        # exchange's I².
        mask2, ov2, out_cols = dcn_stage(
            mask1, recv_cols[0], recv_cols[1:], ndcn, cap2, dcn_axis,
            waved=waved,
        )

        # Global signals: any stage's bucket overflow anywhere, plus
        # out-of-range partitioner ids (caller raises — user error).
        total_overflow = lax.psum(
            lax.psum(ov1 + ov2, ici_axis), dcn_axis
        )
        total_bad = lax.psum(lax.psum(n_bad, ici_axis), dcn_axis)
        return mask2, total_overflow, total_bad, out_cols

    def body(n, *cols):
        from bigslice_tpu.parallel.segment import compact_by_mask

        size = cols[0].shape[0]
        valid = jnp.arange(size, dtype=np.int32) < n
        mask, overflow, bad, out_cols = body_masked(valid, *cols)
        out_count, out_cols = compact_by_mask(mask, out_cols)
        return out_count, overflow + bad, list(out_cols)

    body.masked = body_masked
    return body


def make_hier_combine_shuffle_fn(ndcn: int, nici: int, nkeys: int,
                                 nvals: int, cfn,
                                 dcn_axis: str = "dcn",
                                 ici_axis: str = "ici", seed: int = 0,
                                 slack: float = 2.0,
                                 nparts: Optional[int] = None,
                                 partition_fn: Optional[Callable] = None):
    """Fused hierarchical combine+shuffle for the executor's 2-D group
    programs — the combiner-bearing counterpart of
    ``make_hier_shuffle_fn`` with the same ``.masked`` contract as the
    flat ``make_combine_shuffle_fn``:

    1. **Stage 1** reuses THE flat fused kernel
       (shuffle.make_combine_shuffle_fn) in waved mode over the ICI
       axis: one (validity, lane, quotient, keys) sort segments the
       map-side combine AND orders the ICI routing, and its leading
       quotient output column (``part // I``) is exactly what stage 2
       buckets on.
    2. **ICI-stage combine**: the ≤I group-local partials per
       (destination shard, key) that stage 1 collected on one device
       merge into ONE partial *before anything crosses DCN* — the
       quotient rides as an extra leading key so rows of different
       destination shards never merge. On top of the I-fold message
       amortization this shrinks the DCN payload itself: one partial
       per (source group, key) instead of one per (source device,
       key).
    3. **DCN stage**: the shared ``dcn_stage`` exchange (one
       aggregated message per pod pair per lane; waved subids ride
       out as the leading column).

    Received rows are per-source-group partials; consumers re-combine
    by the map-side-combine contract exactly as they do for the flat
    fused kernel's per-source-device partials.
    """
    import jax.numpy as jnp
    from jax import lax

    from bigslice_tpu.parallel import segment

    nshards = ndcn * nici
    if nparts is None:
        nparts = nshards
    # Stage 1 (the flat fused kernel over ICI) emits the quotient
    # column only when it routes more partitions than ICI lanes.
    stage1_waved = nparts > nici
    fused1 = make_combine_shuffle_fn(
        nici, nkeys, nvals, cfn, ici_axis, seed,
        partition_fn=partition_fn, slack=slack, nparts=nparts,
    )
    recombine = segment.make_segmented_reduce_masked(
        1 + nkeys, nvals, cfn, compact=False
    )

    def body_masked(valid, *cols):
        size = cols[0].shape[0]
        plan = exchange_plan(ndcn, nici, nparts, size, slack)
        waved_out = plan["waved"]
        cap2 = plan["cap2"]
        mask1, ov1, bad1, s1 = fused1.masked(valid, *cols)
        if stage1_waved:
            gq = s1[0]
            keys1 = tuple(s1[1:1 + nkeys])
            vals1 = tuple(s1[1 + nkeys:])
        else:
            # nparts <= I: every partition lives in group 0 and the
            # flat kernel emitted no quotient column.
            gq = jnp.zeros(s1[0].shape[0], np.int32)
            keys1 = tuple(s1[:nkeys])
            vals1 = tuple(s1[nkeys:])
        mask_c, kc, vc = recombine(mask1, (gq,) + keys1, vals1)
        mask2, ov2, out_cols = dcn_stage(
            mask_c, kc[0], tuple(kc[1:]) + tuple(vc), ndcn, cap2,
            dcn_axis, waved=waved_out,
        )
        # fused1's signals are already psummed over ICI; lift both to
        # global totals.
        overflow = (
            lax.psum(ov1, dcn_axis)
            + lax.psum(lax.psum(ov2, ici_axis), dcn_axis)
        )
        bad = lax.psum(bad1, dcn_axis)
        return mask2, overflow, bad, out_cols

    class _Body:
        masked = staticmethod(body_masked)

    return _Body()


class HierMeshReduceByKey:
    """Keyed reduction over a 2-D ("dcn", "ici") mesh: map-side
    combine → two-stage hierarchical shuffle → reduce-side combine,
    one jitted SPMD program — the multi-pod counterpart of
    shuffle.MeshReduceByKey, so its results are the per-shard row sets
    the flat reduce produces.

    ``fused`` (the default) folds the map-side segmented combine into
    stage 1's routing sort by reusing THE flat fused kernel
    (shuffle.make_combine_shuffle_fn) in waved mode over the ICI axis:
    global shard ``s = g*I + i`` is device ``s % I`` of the ICI group
    with subid ``s // I`` — which IS the destination group — so the
    kernel's one (validity, lane, subid, keys) sort segments the
    combine AND orders the ICI routing, and its leading subid output
    column is exactly the dest-group payload stage 2 buckets on
    (dcn_stage). This drops the separate (validity, keys) combine sort
    the unfused path pays before the routing sort.
    ``fused=False`` keeps that unfused path as the reference test_hier
    pins the fused one against.

    ``donate=True`` donates the staged input buffers to the program
    (jitutil.jit_maybe_donate): wave-streamed callers that re-stage
    fresh columns per call reuse HBM instead of reallocating."""

    def __init__(self, mesh, nkeys: int, nvals: int, capacity: int,
                 combine_fn: Callable, seed: int = 0,
                 slack: float = 2.0, fused: Optional[bool] = None,
                 donate: bool = False):
        from jax.sharding import PartitionSpec as P

        from bigslice_tpu.parallel import segment

        shard_map = get_shard_map()
        dcn_axis, ici_axis = mesh.axis_names
        ndcn, nici = mesh.devices.shape
        self.mesh = mesh
        self.nshards = ndcn * nici
        self.capacity = capacity
        self.out_capacity = ndcn * send_capacity(capacity, ndcn, slack)
        self.fused = fused is None or bool(fused)
        ncols = nkeys + nvals
        cfn = segment.canonical_combine(combine_fn, nvals)
        combine_final = segment.make_segmented_reduce_masked(
            nkeys, nvals, cfn, compact=True
        )
        if self.fused:
            # Stage 1 = the flat fused combine+shuffle in waved mode
            # over ICI (nparts = the global shard count): one sort
            # serves segmentation and lane routing; out_cols[0] is the
            # subid = destination group.
            cap2 = send_capacity(capacity, ndcn, slack)
            fused1 = make_combine_shuffle_fn(
                nici, nkeys, nvals, cfn, ici_axis, seed, slack=slack,
                nparts=self.nshards,
            )

            def stepped(counts, *cols):
                import jax.numpy as jnp
                from jax import lax

                n = counts[0]
                size = cols[0].shape[0]
                mask0 = jnp.arange(size, dtype=np.int32) < n
                mask1, ov1, _bad, s1_cols = fused1.masked(mask0, *cols)
                mask2, ov2, out_cols = dcn_stage(
                    mask1, s1_cols[0], s1_cols[1:], ndcn, cap2,
                    dcn_axis,
                )
                overflow = (
                    lax.psum(ov1, dcn_axis)  # ov1 already psummed (ici)
                    + lax.psum(lax.psum(ov2, ici_axis), dcn_axis)
                )
                n3, k3, v3 = combine_final(
                    mask2, tuple(out_cols[:nkeys]),
                    tuple(out_cols[nkeys:]),
                )
                return (n3.reshape(1), overflow, tuple(k3) + tuple(v3))
        else:
            combine_local = segment.make_segmented_reduce_masked(
                nkeys, nvals, cfn, compact=False
            )
            body = make_hier_shuffle_fn(
                ndcn, nici, nkeys, capacity, dcn_axis, ici_axis, seed,
                slack=slack,
            )

            def stepped(counts, *cols):
                import jax.numpy as jnp

                n = counts[0]
                size = cols[0].shape[0]
                mask0 = jnp.arange(size, dtype=np.int32) < n
                keep, k1, v1 = combine_local(mask0, cols[:nkeys],
                                             cols[nkeys:])
                mask2, overflow, _bad, out_cols = body.masked(
                    keep, *(tuple(k1) + tuple(v1))
                )
                n3, k3, v3 = combine_final(
                    mask2, tuple(out_cols[:nkeys]),
                    tuple(out_cols[nkeys:])
                )
                return (n3.reshape(1), overflow, tuple(k3) + tuple(v3))

        col_spec = P((dcn_axis, ici_axis))
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


class HierMeshShuffle:
    """A compiled two-stage SPMD shuffle over a 2-D ("dcn", "ici")
    mesh — the multi-pod counterpart of shuffle.MeshShuffle, same
    call contract: ``__call__(cols, counts) -> (out_cols, out_counts,
    overflow)`` with columns globally shaped [D*I*capacity, ...]
    sharded over both axes and counts int32[D*I] (row-major shard s =
    g * I + i)."""

    def __init__(self, mesh, ncols: int, nkeys: int, capacity: int,
                 seed: int = 0, partition_fn=None, slack: float = 2.0):
        import jax
        from jax.sharding import PartitionSpec as P

        shard_map = get_shard_map()
        dcn_axis, ici_axis = mesh.axis_names
        ndcn, nici = (mesh.devices.shape[0], mesh.devices.shape[1])
        self.mesh = mesh
        self.nshards = ndcn * nici
        self.capacity = capacity
        self.out_capacity = ndcn * send_capacity(capacity, ndcn, slack)
        body = make_hier_shuffle_fn(
            ndcn, nici, nkeys, capacity, dcn_axis, ici_axis, seed,
            partition_fn, slack,
        )

        col_spec = P((dcn_axis, ici_axis))
        in_specs = (col_spec,) + tuple(col_spec for _ in range(ncols))
        out_specs = (col_spec, P(),
                     tuple(col_spec for _ in range(ncols)))

        def stepped(counts, *cols):
            n = counts[0]
            out_count, overflow, out_cols = body(n, *cols)
            return (out_count.reshape(1), overflow, tuple(out_cols))

        self._jitted = jit(
            shard_map(stepped, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_rep=False)
        )

    def __call__(self, cols: Sequence, counts):
        out_counts, overflow, out_cols = self._jitted(counts, *cols)
        return list(out_cols), out_counts, overflow
