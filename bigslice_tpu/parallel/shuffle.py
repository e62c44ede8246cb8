"""SPMD shuffle: hash-bucket + all_to_all over a device mesh.

This is the TPU-native replacement for the reference's shuffle — gob
streams pulled worker→worker over TCP with randomized read order
(exec/bigmachine.go:818-908, SURVEY.md §5.8) — re-expressed as XLA
collectives over ICI:

1. each device hashes its rows' key prefixes (murmur-style mix, fused),
2. rows are sorted by destination shard and each destination's run is
   cut out as one fixed-capacity bucket — a slice, not a scatter
   (static shapes — XLA requirement, SURVEY.md §7.3(1)),
3. one ``all_to_all`` moves the buckets; a second tiny ``all_to_all``
   carries the per-destination row counts,
4. receivers compact their buckets into a (rows, count) pair.

Everything runs inside one ``shard_map``-decorated jitted program: the
whole shuffle is a single XLA computation per phase, with the collective
riding ICI. Skew beyond the static bucket capacity is detected on device
and surfaced as an overflow count (the caller retries with a larger
capacity — the recompile-averse bucketing strategy).
"""

from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from bigslice_tpu.parallel.jitutil import (
    any_wide,
    jit,
    jit_maybe_donate,
    wide_scope,
)
from bigslice_tpu.parallel.meshutil import get_shard_map, mesh_axis


def send_capacity(capacity: int, nshards: int, slack: float = 2.0) -> int:
    """Per-(source,dest) bucket rows. A uniform hash sends ~capacity/nshards
    rows to each destination; ``slack`` is the skew headroom before the
    overflow signal fires. The receive buffer is nshards*send_cap ≈
    slack × capacity."""
    return max(1, int(np.ceil(capacity * slack / nshards)))


def partition_ids(keys, nparts: int, seed: int, valid=None,
                  partition_fn: Optional[Callable] = None,
                  use_pallas: Optional[bool] = None,
                  with_counts: bool = False):
    """Destination partition ids for rows keyed by ``keys`` — THE one
    implementation of the device tier's routing contract (murmur-style
    ``hash % nparts``, bit-matching the host tier), shared by the
    routing-sort shuffle and the fused combine+shuffle so the two can
    never drift.

    Returns ``(part, bad, counts)``: ids int32[n] with invalid rows
    (``valid`` False) and out-of-range partitioner ids parked at the
    ``nparts`` sentinel; ``bad`` the bool mask of out-of-range ids
    (None under hash routing, which cannot produce them); ``counts``
    the per-partition histogram of routable rows when ``with_counts``
    and the fused Pallas kernel served the request, else None.
    """
    import jax
    import jax.numpy as jnp

    from bigslice_tpu.frame import ops as frame_ops
    from bigslice_tpu.parallel import pallas_kernels as pk

    if partition_fn is not None:
        part = jnp.asarray(partition_fn(*keys)).astype(np.int32)
        bad = (part < 0) | (part >= nparts)
        part = jnp.where(bad, np.int32(nparts), part)
        if valid is not None:
            part = jnp.where(valid, part, np.int32(nparts))
        return part, bad, None
    enable_pallas = use_pallas
    if enable_pallas is None:
        # Mosaic-compiled on TPU; on CPU the interpreter is slower
        # than the fused XLA ops, so default off.
        enable_pallas = jax.default_backend() == "tpu"
    if enable_pallas and pk.supports(keys):
        # Native tier: ONE fused VMEM sweep for murmur hash, combine
        # chain, validity routing, and (optionally) the destination
        # histogram. Bit-identical to the XLA path below.
        part, counts = pk.hash_partition(
            list(keys), nparts, seed, with_counts=with_counts,
            valid=valid,
        )
        return part, None, counts
    h = None
    for k in keys:
        kh = frame_ops.hash_device_column(k, seed)
        h = kh if h is None else frame_ops.combine_hashes(h, kh)
    part = (h % np.uint32(nparts)).astype(np.int32)
    if valid is not None:
        part = jnp.where(valid, part, np.int32(nparts))
    return part, None, None


def lane_counts(lane, nlanes: int, mask=None):
    """Rows a lane, int32[nlanes], of the rows selected by ``mask``
    (all rows without one): a compare-and-sum over the lanes, which
    are few (a mesh axis). A scatter-add of the rows into so few bins
    serialises on the TPU (PERF.md §5, PR 31); lanes outside
    [0, nlanes) count nowhere."""
    import jax.numpy as jnp

    hit = lane[None, :] == jnp.arange(nlanes, dtype=np.int32)[:, None]
    if mask is not None:
        hit = hit & mask[None, :]
    return hit.sum(axis=1).astype(np.int32)


def route_to_buckets(dest, cols, ndest: int, kernel_counts=None):
    """THE shared routing sort (used by the 1-D shuffle and each stage
    of the hierarchical 2-D shuffle, so the routings cannot drift):
    rows reorder by destination with one stable sort (payload follows
    via the carried permutation), which leaves every destination's
    rows one contiguous run for ``bucket_exchange`` to slice.

    ``dest`` int32[size] with values ≥ ndest parking at the drop
    sentinel (they sort last). Returns (cols', counts): the permuted
    rows and each destination's row count, int32[ndest], sentinel rows
    excluded."""
    from bigslice_tpu.parallel.segment import sort_with_payload

    _, s_cols = sort_with_payload((dest,), 1, cols)
    counts = (
        kernel_counts if kernel_counts is not None
        else lane_counts(dest, ndest)
    )
    return s_cols, counts


def bucket_exchange(axis: str, nshards: int, send_cap: int, counts,
                    cols):
    """Cut rows into per-destination send buckets and run the two
    all_to_alls (counts then data). ``cols`` hold the rows GROUPED by
    destination device lane and front-packed (``route_to_buckets``,
    ``segment.group_by_lane``) and ``counts`` the rows a lane, int32 of
    at most ``nshards`` lanes (a mesh padded beyond the partitions
    sends its trailing devices nothing). Lane ``d`` starts at the
    exclusive cumsum of ``counts`` and its bucket is the ONE slice of
    ``send_cap`` rows from there, with the rows past the lane's count
    — the next lane's — masked to zero; a lane over ``send_cap`` sends
    its first ``send_cap`` rows (the caller reports the excess).
    Returns (recv_valid_mask, out_cols) with out_cols holding
    ``nshards * send_cap`` rows — bucket from each source shard, row j
    of source bucket s valid iff j < recv_counts[s]. Shared by the
    routing-sort shuffle, the fused combine+shuffle and both stages of
    the 2-D shuffle (``hier.py``).

    The cut unrolls ``nshards`` slices a column and ``lane_counts`` a
    ``[nlanes, n]`` compare. Known bound: 8 lanes is the most that has
    been compiled (the tests' meshes; ``tools/aotcheck`` for a described
    v5e:2x4) — a larger mesh rechecks the compile before it relies on
    this form (one gather of ``starts[d] + arange(send_cap)`` is the
    other way)."""
    import jax.numpy as jnp
    from jax import lax

    from bigslice_tpu.parallel.segment import zero_rows_unless

    counts = jnp.concatenate(
        [counts.astype(np.int32),
         jnp.zeros(nshards - counts.shape[0], np.int32)]
    )
    starts = jnp.cumsum(counts).astype(np.int32) - counts
    send_counts = jnp.minimum(counts, send_cap)
    row_in_bucket = jnp.arange(send_cap, dtype=np.int32)
    live = row_in_bucket[None, :] < send_counts[:, None]
    out_buckets = []
    for c in cols:
        # send_cap rows of zeros behind the column: a slice never
        # clamps, wherever a lane starts.
        padded = jnp.concatenate(
            [c, jnp.zeros((send_cap,) + c.shape[1:], c.dtype)]
        )
        out_buckets.append(zero_rows_unless(live, jnp.stack([
            lax.dynamic_slice_in_dim(padded, starts[d], send_cap)
            for d in range(nshards)
        ])))
    recv_counts = lax.all_to_all(
        send_counts.reshape(nshards, 1), axis, 0, 0, tiled=False
    ).reshape(nshards)
    recv = [
        lax.all_to_all(b, axis, 0, 0, tiled=False)
        for b in out_buckets
    ]
    out_cols = [r.reshape((nshards * send_cap,) + r.shape[2:])
                for r in recv]
    valid_mask = (row_in_bucket[None, :]
                  < recv_counts[:, None]).reshape(-1)
    return valid_mask, out_cols


def make_shuffle_fn(nshards: int, nkeys: int, capacity: int,
                    axis: str = "shards", seed: int = 0,
                    partition_fn: Optional[Callable] = None,
                    slack: float = 2.0,
                    use_pallas: Optional[bool] = None,
                    nparts: Optional[int] = None):
    """Build the per-device shuffle body (to be wrapped in shard_map).

    Operates on ``cols`` (each shape [capacity]) plus a valid-row count
    ``n``. Returns (out_count, overflow, out_cols) where out_cols have
    ``nshards * send_capacity(...)`` rows, valid rows compacted to the
    front.

    ``partition_fn(*key_cols) -> int32 ids`` (vectorized, one positional
    arg per key column) overrides hash partitioning (Repartition
    support). Ids outside [0, nparts) are dropped and counted into the
    overflow signal — same observability as the host executor's range
    check (exec/local.py partition_frame).

    ``nparts`` (default ``nshards``) is the partition count the routing
    modulo uses — it may be smaller than the mesh (padded-mesh groups:
    a 5-shard op on an 8-device mesh routes to partitions 0..4 and
    devices 5..7 receive nothing) or LARGER (wave-partitioned outputs:
    partition p routes to device ``p % nshards`` carrying a subid
    ``p // nshards`` as an extra leading output column, which waved
    consumers filter on). It must agree with the host tier's
    ``hash % nparts`` so mixed-tier dep edges stay consistent.

    With ``nparts > nshards`` the returned ``out_cols`` carry the int32
    subid column FIRST (callers drop or filter it); capacity per device
    grows to hold every subid's rows.
    """
    import jax.numpy as jnp
    from jax import lax

    if nparts is None:
        nparts = nshards
    waved = nparts > nshards
    # Destinations per device lane: one partition each when nparts fits
    # the mesh; W partitions share a device (distinguished by subid)
    # when it doesn't — per-device send volume scales accordingly.
    send_cap = send_capacity(
        capacity, nshards if waved else nparts, slack
    )

    def body_masked(valid, *cols):
        """Mask-based core: rows where ``valid`` route; returns
        (recv_valid_mask, overflow, out_cols) with received rows left in
        bucket position (no compaction sort) — consumers that accept
        masks (segmented reduce) chain without the extra sort."""
        size = cols[0].shape[0]
        keys = cols[:nkeys]
        # Out-of-range partitioner ids route to the drop lane and are
        # counted separately; invalid rows route to a virtual shard
        # that sorts last. The fused Pallas kernel (when engaged) also
        # returns the destination histogram in route_to_buckets' place.
        # The waved path counts per DEVICE lane, so the histogram is
        # only requested when the non-waved path will consume it.
        part, bad, kernel_counts = partition_ids(
            keys, nparts, seed, valid=valid, partition_fn=partition_fn,
            use_pallas=use_pallas,
            with_counts=not waved,
        )
        n_bad = (
            jnp.int32(0) if bad is None
            else (bad & valid).sum().astype(np.int32)
        )
        if waved:
            # Device lane + subid: rows carry subid = p // nshards as
            # an extra leading payload column, for waved consumers to
            # filter their own partition post-exchange.
            dev = jnp.where(part < nparts, part % np.int32(nshards),
                            np.int32(nshards))
            subid = jnp.where(part < nparts,
                              part // np.int32(nshards), np.int32(0))
            cols = (subid.astype(np.int32),) + tuple(cols)
            part = dev
            ndest = nshards
        else:
            ndest = nparts

        s_cols, counts = route_to_buckets(
            part, cols, ndest,
            kernel_counts=kernel_counts if not waved else None,
        )
        # Rows beyond a bucket's capacity (or invalid) stay behind —
        # reported via `overflow`.
        valid_mask, out_cols = bucket_exchange(
            axis, nshards, send_cap, counts, s_cols,
        )
        # Bucket overflow (capacity skew — caller retries with slack)
        # and out-of-range partitioner ids (a user error — caller should
        # raise, matching the host tier's range check) surface as
        # separate global signals.
        total_overflow = lax.psum(
            jnp.maximum(counts.max() - send_cap, 0), axis
        )
        total_bad = lax.psum(n_bad, axis)
        return valid_mask, total_overflow, total_bad, out_cols

    def body(n, *cols):
        from bigslice_tpu.parallel.segment import compact_by_mask

        size = cols[0].shape[0]
        valid = jnp.arange(size, dtype=np.int32) < n
        valid_mask, total_overflow, total_bad, out_cols = body_masked(
            valid, *cols
        )
        # Compact valid rows to the front (count-based output contract).
        out_count, out_cols = compact_by_mask(valid_mask, out_cols)
        return out_count, total_overflow + total_bad, list(out_cols)

    body.masked = body_masked
    return body


def make_combine_shuffle_fn(nshards: int, nkeys: int, nvals: int,
                            cfn, axis: str = "shards", seed: int = 0,
                            partition_fn: Optional[Callable] = None,
                            slack: float = 2.0,
                            nparts: Optional[int] = None,
                            use_pallas: Optional[bool] = None):
    """Fused map-side combine + shuffle routing: ONE stable sort serves
    both stages.

    The separate pipeline (make_segmented_reduce_masked → body_masked)
    pays two full-payload stable sorts: by (validity, keys) to segment
    for the combine, then by destination to route. But a row's
    destination is a pure function of its key prefix, so sorting once by
    ``(validity, destination[, subid], keys)`` yields intact equal-key
    segments (equal keys share a destination) whose combined survivors
    come out already destination-ordered — one single-key sort then
    front-packs them by device lane (segment.group_by_lane) and every
    send bucket is a slice (bucket_exchange); no second full-key sort,
    no scatter.

    Guaranteed equivalences with combine-then-shuffle: the same set of
    combined rows reaches the same (device, subid) destinations, and
    the overflow / bad-partition signals are zero exactly when the
    unfused pipeline's are. NOT guaranteed identical: within-bucket row
    order in waved mode (the fused sort is subid-major where the
    unfused one interleaves subids in key order — which also changes
    *which* rows clip on overflow), and the bad count's unit (combined
    segments here vs post-combine rows there). Consumers are
    order-insensitive and treat bad as a boolean, so both differences
    are unobservable through the public ops.

    Returns a ``body`` whose ``.masked(valid, *cols)`` gives
    ``(recv_valid_mask, overflow, bad, out_cols)`` — same contract as
    ``make_shuffle_fn(...).masked`` (with the combine already applied).
    ``cols`` = nkeys key columns then nvals value columns; with
    ``nparts > nshards`` the out_cols carry the int32 subid column
    first, like the unfused shuffle.
    """
    import jax.numpy as jnp
    from jax import lax

    from bigslice_tpu.parallel import segment

    if nparts is None:
        nparts = nshards
    waved = nparts > nshards

    def body_masked(valid, *cols):
        size = cols[0].shape[0]
        cap_send = send_capacity(
            size, nshards if waved else nparts, slack
        )
        keys = cols[:nkeys]
        vals = cols[nkeys:]

        # Destination from the key prefix — computed BEFORE the sort
        # (shared routing contract: partition_ids).
        part, bad, _ = partition_ids(
            keys, nparts, seed, valid=valid, partition_fn=partition_fn,
            use_pallas=use_pallas,
        )

        # Device lane (+ subid when partitions outnumber devices).
        # Sentinel lane nshards: bad-partitioner rows (valid — counted)
        # and invalid rows (masked) both park there; `invalid` is the
        # leading sort key so they stay distinguishable after the sort.
        routable = part < nparts
        if waved:
            dev = jnp.where(routable, part % np.int32(nshards),
                            np.int32(nshards))
            subid = jnp.where(routable, part // np.int32(nshards),
                              np.int32(0))
        else:
            dev = jnp.where(routable, part, np.int32(nshards))
            subid = None

        # THE sort: (validity, device lane[, subid], keys) with values
        # as payload — combine segmentation and routing order in one
        # (vector values follow via segment.sort_with_payload's
        # carried permutation). Validity and the device lane pack into
        # ONE int32 operand — their lexicographic order is preserved by
        # invalid * (nshards+2) + dev (dev ≤ nshards) — trimming an
        # operand from every pass of the sort network.
        invalid = (~valid).astype(np.int32)
        route = invalid * np.int32(nshards + 2) + dev
        sort_keys = ((route, subid, *keys) if waved
                     else (route, *keys))
        nsort = len(sort_keys)
        s, s_vals = segment.sort_with_payload(sort_keys, nsort, vals)
        s_route = s[0]
        s_invalid = (s_route >= nshards + 2).astype(np.int32)
        s_dev = s_route - s_invalid * np.int32(nshards + 2)
        s_subid = s[1] if waved else None
        s_keys = s[1 + waved : nsort]

        # Segment boundaries: any routing/key change starts a segment
        # (equal keys can't split — they share dev/subid; the packed
        # route covers validity + device in one comparison).
        diff = jnp.zeros(size, dtype=bool).at[0].set(True)
        for k in (s_route,) + (
            (s_subid,) if waved else ()
        ) + tuple(s_keys):
            diff = diff.at[1:].set(diff[1:] | (k[1:] != k[:-1]))
        diff = diff | (s_invalid == 1)

        is_last, red = segment.segmented_combine(diff, s_vals, cfn)
        keep = is_last & (s_invalid == 0)

        n_bad = (
            jnp.int32(0) if bad is None
            else (keep & (s_dev == nshards)).sum().astype(np.int32)
        )

        # Survivors (each holds its segment's full reduction) are
        # dev-ordered but sparse: front-pack them grouped by device
        # lane, order inside a lane — (subid, key) — kept, so on
        # overflow a lane sends its first cap_send survivors. The
        # sentinel lane's rows stay behind.
        routed = keep & (s_dev < nshards)
        counts = lane_counts(s_dev, nshards, routed)
        _, _, payload = segment.group_by_lane(
            routed, s_dev,
            ((s_subid,) if waved else ()) + tuple(s_keys) + tuple(red),
        )
        valid_mask, out_cols = bucket_exchange(
            axis, nshards, cap_send, counts, payload,
        )
        total_overflow = lax.psum(
            jnp.maximum(counts.max() - cap_send, 0), axis
        )
        total_bad = lax.psum(n_bad, axis)
        return valid_mask, total_overflow, total_bad, out_cols

    class _Body:
        masked = staticmethod(body_masked)

    return _Body()


class MeshShuffle:
    """A compiled SPMD shuffle over a mesh (one jitted program).

    ``__call__(sharded_cols, counts)`` where each column is a global array
    of shape [nshards * capacity, ...] sharded on axis 0, and ``counts``
    is an int32[nshards] of valid rows per shard. Returns
    (out_cols, out_counts, overflow_total).

    ``donate=True`` donates the input buffers to the compiled program
    (jitutil.jit_maybe_donate): callers streaming fresh batches through
    the same kernel — the wave-pipeline steady state — reuse HBM
    instead of reallocating it, at the price that inputs are dead after
    the call.
    """

    def __init__(self, mesh, ncols: int, nkeys: int, capacity: int,
                 seed: int = 0, partition_fn=None, slack: float = 2.0,
                 donate: bool = False):
        from jax.sharding import PartitionSpec as P

        shard_map = get_shard_map()
        axis = mesh_axis(mesh)
        nshards = mesh.devices.size
        self.mesh = mesh
        self.nshards = nshards
        self.capacity = capacity
        # Received rows per device: nshards buckets of send_cap rows.
        self.out_capacity = nshards * send_capacity(capacity, nshards, slack)
        body = make_shuffle_fn(nshards, nkeys, capacity, axis,
                               seed, partition_fn, slack)

        col_spec = P(axis)
        in_specs = (P(axis),) + tuple(col_spec for _ in range(ncols))
        out_specs = (P(axis), P(), tuple(col_spec for _ in range(ncols)))

        def stepped(counts, *cols):
            # Per-device view: counts is int32[1], cols are [capacity,...]
            n = counts[0]
            out_count, overflow, out_cols = body(n, *cols)
            return (out_count.reshape(1), overflow, tuple(out_cols))

        self._jitted = jit_maybe_donate(
            shard_map(stepped, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_rep=False),
            tuple(range(1 + ncols)) if donate else (),
        )

    def __call__(self, cols: Sequence, counts):
        out_counts, overflow, out_cols = self._jitted(counts, *cols)
        return list(out_cols), out_counts, overflow


class MeshReduceByKey:
    """Mesh-wide keyed reduction: local combine → all_to_all shuffle →
    final combine, as one jitted SPMD program.

    The end-to-end TPU lowering of Reduce (SURVEY.md §7.1): map-side
    combining (exec/bigmachine.go:1084-1210) becomes an on-device
    sort+segmented-scan; the TCP shuffle becomes all_to_all over ICI; the
    reduce-side merge becomes a second segmented scan.
    """

    def __init__(self, mesh, nkeys: int, nvals: int, capacity: int,
                 combine_fn: Callable, seed: int = 0,
                 slack: float = 2.0, donate: bool = False):
        from jax.sharding import PartitionSpec as P

        from bigslice_tpu.parallel import segment

        shard_map = get_shard_map()
        axis = mesh_axis(mesh)
        nshards = mesh.devices.size
        self.mesh = mesh
        self.nshards = nshards
        self.capacity = capacity
        self.out_capacity = nshards * send_capacity(capacity, nshards, slack)
        ncols = nkeys + nvals
        cfn = segment.canonical_combine(combine_fn, nvals)
        # Fused map-side combine + routing: one stable sort by
        # (validity, destination, keys) serves both stages — see
        # make_combine_shuffle_fn. The final combine stays separate
        # (received rows interleave across sources).
        fused = make_combine_shuffle_fn(
            nshards, nkeys, nvals, cfn, axis, seed, slack=slack
        )
        combine_final = segment.make_segmented_reduce_masked(
            nkeys, nvals, cfn, compact=True
        )

        def stepped(counts, *cols):
            import jax.numpy as jnp

            n = counts[0]
            size = cols[0].shape[0]
            mask0 = jnp.arange(size, dtype=np.int32) < n
            # 1+2. fused combine + shuffle (hash routing can't produce
            # out-of-range ids, so `bad` is dropped)
            recv_mask, overflow, _bad, out_cols = fused.masked(
                mask0, *cols
            )
            k2 = tuple(out_cols[:nkeys])
            v2 = tuple(out_cols[nkeys:])
            # 3. reduce-side combine (front-compacted output contract)
            n3, k3, v3 = combine_final(recv_mask, k2, v2)
            return (n3.reshape(1), overflow,
                    tuple(k3) + tuple(v3))

        col_spec = P(axis)
        in_specs = (P(axis),) + tuple(col_spec for _ in range(ncols))
        out_specs = (P(axis), P(), tuple(col_spec for _ in range(ncols)))
        # donate: same steady-state HBM-reuse contract as MeshShuffle.
        self._jitted = jit_maybe_donate(
            shard_map(stepped, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_rep=False),
            tuple(range(1 + ncols)) if donate else (),
        )

    def __call__(self, key_cols: Sequence, val_cols: Sequence, counts):
        """All columns globally shaped [nshards*capacity,...], sharded on
        axis 0; counts int32[nshards]. Returns (key_cols, val_cols,
        out_counts, overflow)."""
        nkeys = len(key_cols)
        out_counts, overflow, cols = self._jitted(
            counts, *(list(key_cols) + list(val_cols))
        )
        return (list(cols[:nkeys]), list(cols[nkeys:]), out_counts,
                overflow)


def is_multiprocess_mesh(mesh) -> bool:
    return len({d.process_index for d in mesh.devices.flat}) > 1


def shard_columns(mesh, cols: Sequence[np.ndarray], counts: Sequence[int],
                  capacity: int):
    """Place per-shard host column chunks onto the mesh as global padded
    arrays: chunk i → device i, padded to `capacity` rows.

    Multi-process meshes: every process calls with the SAME full
    per-shard data (the SPMD driver model — deterministic host
    computation everywhere); each contributes the rows of its own
    devices via make_array_from_process_local_data.

    Returns (global_cols, global_counts) ready for MeshShuffle /
    MeshReduceByKey.
    """
    nshards = mesh.devices.size
    globs = []
    for per_shard in cols:
        assert len(per_shard) == nshards
        padded = []
        for chunk in per_shard:
            chunk = np.asarray(chunk)
            if len(chunk) > capacity:
                raise ValueError(
                    f"shard chunk of {len(chunk)} rows exceeds capacity "
                    f"{capacity}"
                )
            pad = np.zeros((capacity - len(chunk),) + chunk.shape[1:],
                           chunk.dtype)
            padded.append(np.concatenate([chunk, pad]))
        globs.append(np.concatenate(padded))
    return place_global_columns(mesh, globs, counts)


def place_global_columns(mesh, globs: Sequence[np.ndarray], counts):
    """Place already-assembled global padded column arrays (shard s's
    rows at ``[s*capacity, (s+1)*capacity)``) onto the mesh, plus the
    per-shard counts vector — ONE batched ``jax.device_put`` with an
    explicit sharding on single-process meshes (the transfer engine
    sees the whole wave at once, instead of a put per column), the
    process-local-rows construction on multi-process meshes.

    The staging arena (exec/staging.py) assembles directly into this
    layout; ``shard_columns`` feeds it from per-shard chunks."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    # Chaos seam at entry (also covers shard_columns, which lands
    # here): an injected transient upload failure is retried by the
    # executor's staging retry loop — the call is functional, so a
    # retry re-places the same host data.
    from bigslice_tpu.utils import faultinject

    if faultinject.ENABLED:
        faultinject.maybe_raise("shuffle.upload")

    nshards = mesh.devices.size
    # Shard axis 0 over EVERY mesh axis: 1-D meshes get the usual
    # P("shards"); 2-D (dcn, ici) meshes get P(("dcn","ici")) — shard
    # s lives on mesh.devices.flat[s] (row-major) either way, so the
    # flat and hierarchical shuffles see identical placements.
    sharding = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    counts_host = np.asarray(counts, np.int32)
    # A put outside JAX's 64-bit mode narrows a 64-bit integer column.
    with wide_scope(any_wide(globs)):
        if not is_multiprocess_mesh(mesh):
            placed = jax.device_put(list(globs) + [counts_host],
                                    sharding)
            return placed[:-1], placed[-1]
        pid = jax.process_index()
        local = [i for i, d in enumerate(mesh.devices.flat)
                 if d.process_index == pid]

        def place(glob):
            rows_per = glob.shape[0] // nshards
            local_rows = np.concatenate([
                glob[i * rows_per : (i + 1) * rows_per] for i in local
            ])
            return jax.make_array_from_process_local_data(
                sharding, local_rows, glob.shape
            )

        return [place(g) for g in globs], place(counts_host)


# Most bytes of bucketed prefixes one readback keeps in flight to the
# host at a time: a result of a few KB is one batch, one of gigabytes a
# short pipeline that never asks the host for all of it at once.
READBACK_BATCH_BYTES = 64 << 20


def unshard_columns(cols: Sequence, counts, capacity: int,
                    crossed: Optional[List[int]] = None
                    ) -> List[List[np.ndarray]]:
    """Inverse of shard_columns: global padded arrays → per-shard valid
    host chunks (``[ncols][nshards]``); ``unshard_many`` of one output.

    ``crossed``, when given, collects the bytes of every array brought
    to the host (the bucketed prefixes, not the valid rows alone)."""
    per: List[List[int]] = [[]]
    (chunks,) = unshard_many([(cols, counts, capacity)], per)
    if crossed is not None:
        crossed.extend(per[0])
    return chunks


def unshard_many(outputs: Sequence[Tuple[Sequence, object, int]],
                 crossed: Optional[List[List[int]]] = None
                 ) -> List[List[List[np.ndarray]]]:
    """``unshard_columns`` of many outputs ``(cols, counts, capacity)``
    at once — the waves of a waved group output — as two overlapped
    device→host reads instead of one blocking read an array.

    Device-resident columns transfer only each shard's valid prefix
    (rounded up to a power-of-two bucket so the tiny slice programs
    don't thrash the compile cache): combiner outputs are typically far
    smaller than their padded capacity, and on TPU the readback rides
    the host link — moving ``capacity`` rows to read ``count`` is the
    difference between a result scan and a full-buffer download. A
    round trip to the device costs about the same whatever it carries,
    so: (1) ONE ``jax.device_get`` of every output's counts (it starts
    every copy before it waits for any); (2) with the counts on the
    host, every non-empty (output, shard) has its columns' prefixes
    sliced on the device by one ``bs_prefix`` call without being read
    (dispatch is asynchronous), and they are fetched by one
    ``device_get`` a batch of at most ``READBACK_BATCH_BYTES``, in
    output order.

    ``crossed[i]``, when given, collects the bytes of every array of
    output ``i`` brought to the host."""
    import jax

    from bigslice_tpu.parallel.jitutil import bucket_size

    counts_host = [np.asarray(c) for c in
                   jax.device_get([counts for _, counts, _ in outputs])]
    device_slice = _slices_on_device()
    result: List[List[List[np.ndarray]]] = []
    batch: list = []  # (device array, chunk list, shard, k, output)
    batch_bytes = 0

    def collect():
        nonlocal batch_bytes
        hosts = jax.device_get([a for a, *_ in batch])
        for host, (_, chunks, s, k, i) in zip(hosts, batch):
            # .copy() on CPU: np.asarray over a CPU shard is zero-copy
            # and a view would pin the whole capacity-row buffer in
            # memoized chunk storage past drop_device().
            chunks[s] = host[:k] if device_slice else host[:k].copy()
            if crossed is not None:
                crossed[i].append(host.nbytes)
        batch.clear()
        batch_bytes = 0

    for i, ((cols, _, capacity), counts) in enumerate(
            zip(outputs, counts_host)):
        nshards = len(counts)
        out_chunks: List[list] = []
        resident = []  # (shard arrays, chunk list) a device column
        for c in cols:
            by_row = _shard_rows(c, capacity, nshards)
            if by_row is None:
                # Host columns / multi-process gathers (already numpy)
                # / unexpected layouts: the plain full-copy path.
                c = np.asarray(c)
                if crossed is not None:
                    crossed[i].append(c.nbytes)
                out_chunks.append(
                    [c[s * capacity : s * capacity + int(counts[s])]
                     for s in range(nshards)])
                continue
            out_chunks.append(
                [np.empty((0,) + tuple(c.shape[1:]), c.dtype)
                 if counts[s] == 0 else None for s in range(nshards)])
            resident.append((by_row, out_chunks[-1]))
        result.append(out_chunks)
        if not resident:
            continue
        for s in range(nshards):
            k = int(counts[s])
            if k == 0:
                continue
            arrays = [by_row[s] for by_row, _ in resident]
            b = min(capacity, bucket_size(k)) if device_slice \
                else capacity
            nbytes = sum(a.nbytes for a in arrays) // capacity * b
            if batch and batch_bytes + nbytes > READBACK_BATCH_BYTES:
                collect()
            if b < capacity:
                arrays = _prefix_program()(b, *arrays)
            batch.extend((a, chunks, s, k, i)
                         for a, (_, chunks) in zip(arrays, resident))
            batch_bytes += nbytes
    if batch:
        collect()
    return result


@functools.lru_cache(maxsize=None)
def _prefix_program():
    """``bs_prefix(b, *cols)``: the first ``b`` rows of every column of
    one shard, in one device program — keyed (by jit) on the bucket and
    the columns' shapes, never on how many outputs are read or on their
    counts. One call a shard, not an eager ``c[:b]`` a column: an eager
    slice is a ``dynamic_slice`` whose start index is put on the device
    at every call, 0.66 ms of host time each on a v5e's host."""
    import jax

    def bs_prefix(b, *cols):
        return tuple(c[:b] for c in cols)

    return jit(bs_prefix, static_argnums=0)


def _slices_on_device() -> bool:
    """On TPU, slicing the valid prefix on-device before readback is
    the point of ``unshard_many``; on CPU backends a whole-shard
    np.asarray is a plain copy that costs less than dispatching a
    device slice program, so slice host-side."""
    import jax

    return jax.default_backend() == "tpu"


def _shard_rows(c, capacity: int, nshards: int) -> Optional[list]:
    """Shard s's single-device array of row-sharded ``c``, for every s;
    None when ``c`` is not device-resident in that layout."""
    shards = getattr(c, "addressable_shards", None)
    if shards is None or len(shards) != nshards:
        return None
    by_row = {}
    for sh in shards:
        start = sh.index[0].start or 0
        if start % capacity == 0:
            by_row[start // capacity] = sh.data
    if set(by_row) != set(range(nshards)):
        return None
    return [by_row[s] for s in range(nshards)]


def partition_cols(chunks: Sequence[Sequence[np.ndarray]], partition: int,
                   nmesh: int, subid: bool) -> List[np.ndarray]:
    """ONE partition's valid rows from a partitioned group output's
    host chunks (``unshard_columns`` layout: [ncols][ndevice]) — THE
    host-side statement of the executor's partition-addressing
    contract, shared by the store bridge's per-partition reads and the
    spill exchange's per-partition writes so the two can never drift:
    partition p lives on device ``p % nmesh``; wave-partitioned
    outputs carry a leading int32 subid column selecting
    ``p // nmesh`` (rows keep their device order — wave-major when the
    cross-wave merge concatenated them)."""
    dev_cols = [np.asarray(c[partition % nmesh]) for c in chunks]
    if not subid:
        return dev_cols
    sel = dev_cols[0] == (partition // nmesh)
    return [c[sel] for c in dev_cols[1:]]


def partition_chunks(chunks: Sequence[Sequence[np.ndarray]],
                     nparts: int, nmesh: int,
                     subid: bool) -> List[List[np.ndarray]]:
    """Every partition's valid rows (see ``partition_cols``), in
    partition order — the spill exchange's map-side split."""
    return [partition_cols(chunks, p, nmesh, subid)
            for p in range(nparts)]
