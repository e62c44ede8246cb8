"""Pallas TPU kernels — the framework's "native tier".

The reference's native tier is Go/unsafe kernels for the columnar hot
ops (frame/unsafe.go, SURVEY.md §2.9); here it is Mosaic/Pallas. The
resident kernel fuses the shuffle's hottest pass — murmur-mix key
hashing (multi-column, combine-chained), validity masking, partition-id
assignment, and the per-destination histogram — into one VMEM-resident
sweep. Unfused, those are separate XLA ops with an HBM round trip and a
scatter-lowered bincount.

Layout: keys are processed as (rows, 128) lane-aligned blocks (the VPU's
8×128 shape; last dim always 128 — pallas_guide.md tiling constraints).
The histogram accumulates across sequential grid steps in a VMEM
accumulator block (revisiting-output pattern).

Key dtypes: int32/uint32 (value-cast) and float32 (bitcast with -0.0
normalized), matching frame/ops.py ``_bits32`` bit-for-bit — the pallas
path and the stock-XLA path must route every key identically.

On CPU (tests, virtual mesh) the kernels run in interpreter mode;
Mosaic compiles them natively on TPU (chip_smoke.py's ``kernels``
phase checks them there against the stock-XLA paths; tests/test_aot.py
compiles them for a described v5e).
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import numpy as np

LANES = 128

_GOLDEN32 = 0x9E3779B9

SUPPORTED_KEY_DTYPES = ("int32", "uint32", "float32")

#: Stable kernel names: they reach the compiled program's text and the
#: profiler trace, so a check (chip_smoke.py) or a trace reduction can
#: find each kernel by name.
HASH_PARTITION_KERNEL = "bigslice_hash_partition"
HASH_AGGREGATE_KERNEL = "bigslice_hash_aggregate"


def _interpret() -> bool:
    import jax

    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=1)
def interpret_capable() -> bool:
    """Capability probe: can this environment build AND run a pallas
    kernel at all (interpret mode off-TPU, Mosaic on TPU)? Probed once
    per process with a trivial kernel; tier-1 tests skip-gate on it so
    a jax build without a working pallas stack reads as SKIPPED, not
    as a red the suite carries forever. On a TPU backend a failing
    probe RAISES: there the kernels are the product, and returning
    False would let the kernel selector drop its Pallas core in
    silence."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[:] = x_ref[:] + jnp.int32(1)

    try:
        x = jnp.zeros((8, LANES), jnp.int32)
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((8, LANES), np.int32),
            interpret=_interpret(),
        )(x)
        return bool(np.asarray(out)[0, 0] == 1)
    except Exception:
        if not _interpret():
            raise
        return False


def supports(key_cols: Sequence) -> bool:
    """Can the fused kernel hash these key columns?"""
    return all(
        str(np.dtype(getattr(k, "dtype", None))) in SUPPORTED_KEY_DTYPES
        for k in key_cols
    )


@functools.lru_cache(maxsize=64)
def _build_hash_partition(nparts: int, block_rows: int, seed32: int,
                          key_dtypes: tuple, interpret: bool,
                          with_counts: bool = True):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    nkeys = len(key_dtypes)
    # Histogram lanes: one partition per lane, padded to a lane multiple.
    hist_lanes = ((nparts + LANES - 1) // LANES) * LANES

    def fmix(x):
        # murmur3 finalizer (matches frame/ops.py fmix32 bit-for-bit).
        x = x ^ (x >> 16)
        x = x * jnp.uint32(0x85EBCA6B)
        x = x ^ (x >> 13)
        x = x * jnp.uint32(0xC2B2AE35)
        x = x ^ (x >> 16)
        return x

    def bits(ref, dtype):
        v = ref[:]
        if dtype == "float32":
            # Normalize -0.0 → +0.0, then bitcast (frame/ops._bits32).
            v = jnp.where(v == 0, jnp.zeros_like(v), v)
            return jax.lax.bitcast_convert_type(v, jnp.uint32)
        return v.astype(jnp.uint32)

    def kernel(*refs):
        mask_ref = refs[0]
        key_refs = refs[1 : 1 + nkeys]
        ids_ref = refs[1 + nkeys]
        counts_ref = refs[2 + nkeys] if with_counts else None
        step = pl.program_id(0)

        h = None
        for ref, dtype in zip(key_refs, key_dtypes):
            kh = fmix(bits(ref, dtype) ^ jnp.uint32(seed32))
            if h is None:
                h = kh
            else:
                # combine_hashes (frame/ops.py): boost-style mix.
                h = fmix(h ^ (kh + jnp.uint32(_GOLDEN32)
                              + (h << 6) + (h >> 2)))
        ids = (h % jnp.uint32(nparts)).astype(jnp.int32)
        # Invalid (and padding) rows route to the drop lane `nparts`.
        ids = jnp.where(mask_ref[:] != 0, ids, jnp.int32(nparts))
        ids_ref[:] = ids

        if counts_ref is not None:
            # Per-block histogram. All-pairs compare per 128-lane chunk
            # of the histogram, in 3D (block_rows, LANES, LANES) — no
            # reshapes/re-layouts, which Mosaic rejects (a (8,128)→
            # (1024,1) shape cast fails infer-vector-layout on real
            # hardware). The drop lane id == nparts never matches a
            # counted lane (counts are sliced to [:nparts]); invalid
            # rows therefore never count.
            @pl.when(step == 0)
            def _init():
                counts_ref[:] = jnp.zeros_like(counts_ref)

            ids3 = ids[:, :, None]  # (block_rows, LANES, 1)
            for c in range(hist_lanes // LANES):
                pid = jax.lax.broadcasted_iota(
                    jnp.int32, (1, 1, LANES), dimension=2
                ) + jnp.int32(c * LANES)
                onehot = (ids3 == pid).astype(jnp.int32)
                local = jnp.sum(onehot, axis=(0, 1), keepdims=True)
                counts_ref[0:1, c * LANES : (c + 1) * LANES] += local[0]

    def run(mask2d, *keys2d):
        rows = mask2d.shape[0]
        grid = (rows // block_rows,)
        blk = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
        out_specs = [blk]
        out_shape = [jax.ShapeDtypeStruct((rows, LANES), np.int32)]
        if with_counts:
            # Same accumulator block revisited every step.
            out_specs.append(pl.BlockSpec((1, hist_lanes),
                                          lambda i: (0, 0)))
            out_shape.append(
                jax.ShapeDtypeStruct((1, hist_lanes), np.int32)
            )
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[blk] * (1 + nkeys),
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
            name=HASH_PARTITION_KERNEL,
        )(mask2d, *keys2d)
        return out if with_counts else (out[0], None)

    return jax.jit(run)


def hash_partition(keys, nparts: int, seed: int = 0,
                   block_rows: int = 8,
                   with_counts: bool = True,
                   valid=None) -> Tuple:
    """Fused hash+mask+partition(+histogram) over key column(s).

    ``keys`` is one array or a list of key columns (multi-column keys
    combine in order, matching Frame.hash_keys). ``valid`` is an
    optional bool mask; invalid rows get partition id ``nparts`` (the
    drop lane) and are excluded from the histogram. Returns
    (ids int32[n], counts int32[nparts] | None). Bit-identical to the
    stock-XLA path: hash_device_column/combine_hashes % nparts.
    """
    import jax
    import jax.numpy as jnp

    from bigslice_tpu.frame import ops as frame_ops

    key_list = list(keys) if isinstance(keys, (list, tuple)) else [keys]
    key_list = [jnp.asarray(k) for k in key_list]
    n = key_list[0].shape[0]
    if n == 0:
        return (jnp.zeros((0,), jnp.int32),
                jnp.zeros((nparts,), jnp.int32) if with_counts else None)
    per_block = block_rows * LANES
    padded = ((n + per_block - 1) // per_block) * per_block
    npad = padded - n

    def pad2d(col, fill):
        flat = jnp.concatenate(
            [col, jnp.full((npad,), fill, col.dtype)]
        )
        return flat.reshape(-1, LANES)

    if valid is None:
        valid = jnp.ones((n,), jnp.int32)
    else:
        valid = jnp.asarray(valid).astype(jnp.int32)
    mask2d = pad2d(valid, 0)  # padding rows are invalid by construction
    keys2d = [pad2d(k, k.dtype.type(0)) for k in key_list]
    fn = _build_hash_partition(
        nparts, block_rows, int(frame_ops._seed32(seed)),
        tuple(str(k.dtype) for k in key_list), _interpret(),
        with_counts,
    )
    # The kernel's operands are 32-bit whatever program holds it; traced
    # inside a program of JAX's 64-bit mode its index maps would come
    # out as i64, which Mosaic refuses.
    with jax.enable_x64(False):
        ids2d, counts = fn(mask2d, *keys2d)
    ids = ids2d.reshape(-1)[:n]
    if not with_counts:
        return ids, None
    return ids, counts.reshape(-1)[:nparts]


# -- open-addressed hash aggregation ------------------------------------
#
# The Mosaic analog of hashagg.hash_aggregate: a destination-contiguous
# open table ([nparts * R] slots, region p = partition p's keys) held
# RESIDENT IN VMEM as revisited accumulator blocks, with the claim ->
# key-compare -> combine cascade fused into one sequential insert pass
# per row. The XLA path lowers the same cascade to HBM scatter rounds
# (scatter-min claim + scatter-accumulate), which is exactly the
# lowering XLA's cost analysis for a v5e target prices far above the
# sort path (AOT_TPU.json); here every probe touches VMEM only.
#
# Layout: tables are (T // 128, 128) planes — slot s lives at sublane
# s // 128, lane s % 128. Probing needs dynamic SUBLANE indexing only
# (``ref[pl.ds(sub, 1), :]``); the dynamic-lane access Mosaic cannot do
# is replaced by an iota-masked select over the loaded (1, 128) row
# (bitcast through int32 for float payloads, so -0.0 and NaN round-trip
# bit-exactly). Insertion is sequential per row — the TPU has no
# scatter atomics, and the grid's sequential-step contract plus the
# fori_loop make first-come-wins claims well defined with no races.

#: Probe bound per row. Double hashing over a pow2 region at the load
#: factors the capacity planner produces (<= 0.5) resolves in ~2 probes
#: expected; 16 covers the tail. Unresolved rows exit via the overflow
#: signal and the executor retries the group on the sort path — the
#: same contract as the XLA cascade's FULL_ROUNDS + while_loop bounds.
AGG_PROBE_MAX = 16

#: VMEM budget for the resident table (present + key + value planes).
#: ~16 MiB/core total; half is left for the input block, Mosaic
#: scratch, and double-buffered pipelines.
AGG_TABLE_VMEM_BYTES = 8 * 1024 * 1024

SUPPORTED_AGG_KEY_DTYPES = ("int32", "uint32")
SUPPORTED_AGG_VAL_DTYPES = ("int32", "uint32", "float32")


def aggregate_supported(key_dtypes: Sequence, val_dtypes: Sequence,
                        nparts: int, R: int) -> bool:
    """Can the Mosaic hash-aggregate kernel serve this table shape?
    Callers fall back to the hashagg.py XLA path when not."""
    if R < LANES or R & (R - 1):
        return False  # probe masking needs a pow2 region, lane-aligned
    T = nparts * R
    if T % LANES:
        return False
    if any(str(np.dtype(d)) not in SUPPORTED_AGG_KEY_DTYPES
           for d in key_dtypes):
        return False
    if any(str(np.dtype(d)) not in SUPPORTED_AGG_VAL_DTYPES
           for d in val_dtypes):
        return False
    planes = 1 + len(key_dtypes) + len(val_dtypes)
    return T * planes * 4 <= AGG_TABLE_VMEM_BYTES


@functools.lru_cache(maxsize=64)
def _build_hash_aggregate(nparts: int, R: int, block_rows: int,
                          key_dtypes: tuple, val_dtypes: tuple,
                          ops: tuple, idents: tuple, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    nkeys = len(key_dtypes)
    nvals = len(val_dtypes)
    T = nparts * R
    TS = T // LANES
    mask_R = np.int32(R - 1)

    def _is_f32(dt) -> bool:
        return str(np.dtype(dt)) == "float32"

    def kernel(*refs):
        mask_ref, off_ref, stride_ref, base_ref = refs[:4]
        key_refs = refs[4 : 4 + nkeys]
        val_refs = refs[4 + nkeys : 4 + nkeys + nvals]
        o = 4 + nkeys + nvals
        pres_ref = refs[o]
        tkey_refs = refs[o + 1 : o + 1 + nkeys]
        tval_refs = refs[o + 1 + nkeys : o + 1 + nkeys + nvals]
        ovf_ref = refs[o + 1 + nkeys + nvals]
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            pres_ref[:] = jnp.zeros_like(pres_ref)
            for tk in tkey_refs:
                tk[:] = jnp.zeros_like(tk)
            for tv, ident in zip(tval_refs, idents):
                tv[:] = jnp.full_like(tv, ident)
            ovf_ref[:] = jnp.zeros_like(ovf_ref)

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

        def get(ref, sub, ln):
            # Scalar gather with a dynamic sublane index + iota-masked
            # lane select. Float payloads extract through int32 so the
            # masked sum is bit-exact (-0.0, NaN) and come back as a
            # lane-BROADCAST (1, 128) vector, never a scalar: Mosaic's
            # tpu.bitcast takes vectors only. ``put``/``combine``
            # blend per lane, so they take either form.
            row = ref[pl.ds(sub, 1), :]
            f32 = _is_f32(ref.dtype)
            if f32:
                row = jax.lax.bitcast_convert_type(row, jnp.int32)
            elif row.dtype != jnp.int32:
                row = row.astype(jnp.int32)
            v = jnp.sum(jnp.where(lane == ln, row, jnp.int32(0)))
            if f32:
                return jax.lax.bitcast_convert_type(
                    jnp.full((1, LANES), v, jnp.int32), jnp.float32
                )
            return v.astype(ref.dtype)

        def put(ref, sub, ln, value):
            # Read-modify-write one (1, 128) row, blending the target
            # lane — the dynamic-lane scatter Mosaic lacks. ``value``
            # is a scalar or a lane-broadcast row (float payloads).
            row = ref[pl.ds(sub, 1), :]
            ref[pl.ds(sub, 1), :] = jnp.where(
                lane == ln, jnp.asarray(value, ref.dtype), row
            )

        def combine(op, cur, new):
            if op == "add":
                return cur + new
            if op == "max":
                return jnp.maximum(cur, new)
            return jnp.minimum(cur, new)

        def row_body(r, ov):
            sub = r // np.int32(LANES)
            ln = r % np.int32(LANES)
            pend = get(mask_ref, sub, ln) != 0
            off0 = get(off_ref, sub, ln)
            stride = get(stride_ref, sub, ln)
            base = get(base_ref, sub, ln)
            ks = [get(kr, sub, ln) for kr in key_refs]
            vs = [get(vr, sub, ln) for vr in val_refs]

            def probe_body(_j, st):
                off, done = st
                act = pend & ~done
                slot = base + off
                ssub = slot // np.int32(LANES)
                sl = slot % np.int32(LANES)
                empty = get(pres_ref, ssub, sl) == 0
                match = ~empty
                for tk, k in zip(tkey_refs, ks):
                    match = match & (get(tk, ssub, sl) == k)
                claim = act & empty
                hit = act & match

                @pl.when(claim)
                def _claim():
                    put(pres_ref, ssub, sl, jnp.int32(1))
                    for tk, k in zip(tkey_refs, ks):
                        put(tk, ssub, sl, k)
                    # combine(ident, v) == v for add/max/min: write
                    # the row's value directly.
                    for tv, v in zip(tval_refs, vs):
                        put(tv, ssub, sl, v)

                @pl.when(hit)
                def _combine():
                    for tv, v, op in zip(tval_refs, vs, ops):
                        put(tv, ssub, sl,
                            combine(op, get(tv, ssub, sl), v))

                done = done | claim | hit
                off = jnp.where(pend & ~done,
                                (off + stride) & mask_R, off)
                return off, done

            _off, done = jax.lax.fori_loop(
                0, AGG_PROBE_MAX, probe_body, (off0, ~pend)
            )
            return ov + jnp.where(pend & ~done, np.int32(1),
                                  np.int32(0))

        ov = jax.lax.fori_loop(0, np.int32(block_rows * LANES),
                               row_body, jnp.int32(0))
        ovf_ref[0:1, 0:1] = ovf_ref[0:1, 0:1] + ov

    def run(mask2d, off2d, stride2d, base2d, *cols2d):
        rows = mask2d.shape[0]
        grid = (rows // block_rows,)
        blk = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
        tbl = pl.BlockSpec((TS, LANES), lambda i: (0, 0))
        out_specs = (
            [tbl]
            + [tbl] * nkeys
            + [tbl] * nvals
            + [pl.BlockSpec((1, LANES), lambda i: (0, 0))]
        )
        out_shape = (
            [jax.ShapeDtypeStruct((TS, LANES), np.int32)]
            + [jax.ShapeDtypeStruct((TS, LANES), np.dtype(d))
               for d in key_dtypes]
            + [jax.ShapeDtypeStruct((TS, LANES), np.dtype(d))
               for d in val_dtypes]
            + [jax.ShapeDtypeStruct((1, LANES), np.int32)]
        )
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[blk] * (4 + nkeys + nvals),
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
            name=HASH_AGGREGATE_KERNEL,
        )(mask2d, off2d, stride2d, base2d, *cols2d)

    return run


def hash_aggregate_pallas(valid, key_cols, val_cols, ops: Sequence[str],
                          part, nparts: int, R: int, seed: int = 0,
                          block_rows: int = 8,
                          interpret: bool | None = None):
    """Mosaic open-addressed hash aggregation: same contract as
    hashagg.hash_aggregate — ``(present bool[T], out_keys, out_vals,
    overflow int32)`` with T = nparts * R, region p holding exactly
    partition-p keys (slot = part * R + probe, probing in-region).

    Same slot-hash stream as the XLA path (hashagg._slot_hash double
    hashing), so both paths probe the same sequences; resolution order
    differs (sequential first-come-wins here vs batched scatter-min
    rounds there), which relocates keys WITHIN their region but never
    across regions and never changes the per-key combined values for
    the classified ops. Results are slot-resident; callers chain masks
    or compact, exactly as with the XLA table.
    """
    import jax.numpy as jnp

    from bigslice_tpu.parallel.dense import _identity
    from bigslice_tpu.parallel.hashagg import _slot_hash

    key_cols = tuple(jnp.asarray(k) for k in key_cols)
    val_cols = tuple(jnp.asarray(v) for v in val_cols)
    n = key_cols[0].shape[0]
    T = nparts * R
    idents = tuple(_identity(op, v.dtype)
                   for op, v in zip(ops, val_cols))
    if n == 0:
        present = jnp.zeros((T,), bool)
        out_keys = [jnp.zeros((T,), k.dtype) for k in key_cols]
        out_vals = [jnp.full((T,), ident, v.dtype)
                    for v, ident in zip(val_cols, idents)]
        return present, out_keys, out_vals, jnp.int32(0)

    h = _slot_hash(key_cols, seed)
    off = (h & np.uint32(R - 1)).astype(np.int32)
    stride = (((h >> np.uint32(9)) | np.uint32(1))
              & np.uint32(R - 1)).astype(np.int32)
    part = jnp.asarray(part).astype(np.int32)
    in_range = part < nparts
    base = jnp.clip(part, 0, np.int32(nparts - 1)) * np.int32(R)
    pend = (jnp.asarray(valid) & in_range).astype(np.int32)

    per_block = block_rows * LANES
    padded = ((n + per_block - 1) // per_block) * per_block
    npad = padded - n

    def pad2d(col, fill):
        flat = jnp.concatenate(
            [col, jnp.full((npad,), fill, col.dtype)]
        )
        return flat.reshape(-1, LANES)

    fn = _build_hash_aggregate(
        nparts, R, block_rows,
        tuple(str(k.dtype) for k in key_cols),
        tuple(str(v.dtype) for v in val_cols),
        tuple(ops), idents,
        _interpret() if interpret is None else interpret,
    )
    out = fn(
        pad2d(pend, 0), pad2d(off, 0), pad2d(stride, 1),
        pad2d(base, 0),
        *[pad2d(k, k.dtype.type(0)) for k in key_cols],
        *[pad2d(v, v.dtype.type(0)) for v in val_cols],
    )
    pres2d = out[0]
    tkeys = out[1 : 1 + len(key_cols)]
    tvals = out[1 + len(key_cols) : 1 + len(key_cols) + len(val_cols)]
    ovf = out[-1]
    present = pres2d.reshape(-1)[:T] != 0
    out_keys = [t.reshape(-1)[:T] for t in tkeys]
    out_vals = [t.reshape(-1)[:T] for t in tvals]
    return present, out_keys, out_vals, ovf[0, 0]
