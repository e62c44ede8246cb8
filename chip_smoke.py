#!/usr/bin/env python3
"""Bring-up smoke: the Session -> MeshExecutor path on a real TPU chip.

Runs the system's main path once, through the entry points a user
calls (``bs.Reduce``/``JoinAggregate``/``Cogroup``/``ScanReader``
pipelines run by ``Session(executor=MeshExecutor(mesh))``), at sizes a
bigslice user would call real, and checks every phase against a plain
numpy reference computed here from ``--seed``. It is the quickest proof
that the system still starts on the chip, and it treats every way the
work could quietly leave the chip — host-tier probation, the AOT-seam
fallback, a blacklisted lowering, a group that ran on the fallback
executor — as a failure.

    python chip_smoke.py                 one chip, every phase
    python chip_smoke.py --chips 4       reduce-generic and join only,
                                         on a 4-device mesh vs a
                                         1-device mesh vs numpy
    python chip_smoke.py --cpu-rehearsal [--chips 4]
                                         the same control flow at a
                                         tiny size on the CPU; prints
                                         its phase lines and never the
                                         contract line

Output: one JSON object per phase, then — only when every phase passed
on a TPU — the contract line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
as the last line. Without a TPU (and without ``--cpu-rehearsal``) it
exits non-zero before doing any work.

One process touches JAX. The only children are the host parse pool
(spawned workers that must stay off any backend — checked in the
``urls`` phase) and the ``cc`` build of the native parse kernels.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

KMEANS_ROUNDS = 3
#: The benchmark's k-means configuration (BASELINE.json config 5 at its
#: published widths): its generator, precision and tolerance are the
#: ones the kmeans phase holds the chip to.
KMEANS_CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "benchmarks", "configs", "kmeans-10m-128")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Row counts per phase. Cuts for time or memory go here, never to
    widths, dtypes or key distributions; ``cuts`` says what was cut.

    Two shard counts: the sort, hash, join and cogroup pipelines run
    ``shards`` = 8 shards (on one device: eight waves, so the wave
    pipeline, the prefetcher, donation and the dispatch window all
    run); the dense-table lowerings engage only when a shuffle's
    partition count equals the mesh size (meshexec ``_program``), so
    the phases that are about them run one shard per device."""

    shards: int
    kernel_rows: int       # kernels phase, rows per kernel call
    table_slots: int       # kernels phase, hash-aggregate table size
    dense_rows: int        # reduce-dense
    dense_keys: int
    generic_rows: int      # reduce-generic (both runs)
    rows_per_key: int      # reduce-generic and join: rows / key space
    join_rows: int         # per side
    url_lines: int
    url_domains: int
    kmeans_points: int
    cuts: tuple = ()


REAL = Sizes(
    shards=8,
    # 2^19 slots x 4 planes (2 keys + 1 value) is exactly the Pallas
    # table's 8 MiB VMEM gate (pallas_kernels.aggregate_supported).
    kernel_rows=1 << 19, table_slots=1 << 19,
    dense_rows=1 << 24, dense_keys=1 << 16,
    generic_rows=1 << 18, rows_per_key=16,
    join_rows=1 << 18,
    url_lines=1 << 20, url_domains=5000,
    kmeans_points=1 << 23,
    cuts=(
        "reduce-generic: 2^18 rows and join: 2^18 rows a side (the "
        "cells owed at a real size: PERF.md section 7), keys cut with "
        "them at 16 rows a key: the TPU compiler takes 20-70 s for "
        "EACH multi-operand sort of 2^21 rows (390 s for one map-side group "
        "program, compiled for a described v5e), and a cold run must "
        "compile every program inside the smoke's time limit",
        "kmeans: 2^23 points (config 5 has 10M): one shard a device "
        "pads 10M rows to 2^24, 8.6 GB before the assignment step's "
        "copy; 2^23 is the largest power of two that fits",
    ),
)

TINY = Sizes(
    shards=8,
    kernel_rows=1 << 10, table_slots=1 << 10,
    dense_rows=1 << 13, dense_keys=1 << 8,
    generic_rows=1 << 13, rows_per_key=8,
    join_rows=1 << 11,
    # Big enough that a shard's batch engages the parse pool
    # (strparse.domains_codes: >= 2 * 16384 rows per batch).
    url_lines=1 << 16, url_domains=50,
    # The k-means configuration's own rehearsal size: a centroid holds
    # about 1,000 points, so a point on a near-tie that float32 and
    # float64 split differently moves it by less than the configuration's
    # tolerance (at 2^12, 64 points a centroid, one such point moves it
    # by several times the tolerance).
    kmeans_points=1 << 16,
    cuts=("cpu rehearsal: every row count cut to a tiny size",),
)


@dataclasses.dataclass
class Ctx:
    mesh: object
    sizes: Sizes
    seed: int
    on_tpu: bool
    workdir: str


class PhaseFailed(AssertionError):
    pass


def require(cond, msg: str) -> None:
    """A check that survives ``python -O``."""
    if not cond:
        raise PhaseFailed(msg)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# ------------------------------------------------------------ compiles

class CompileMeter:
    """Every XLA compile request of this process, from JAX's own
    monitoring events: the executor's telemetry sees only programs
    behind its AOT seam, and 'compiles nothing' has to mean nothing."""

    _instance = None

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.names: list = []
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    @classmethod
    def get(cls) -> "CompileMeter":
        # Listeners cannot be unregistered one by one: one meter per
        # process.
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs
            self.names.append(kw.get("fun_name", "?"))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self):
        return (self.compiles, self.seconds, self.cache_hits,
                self.cache_misses, len(self.names))

    def since(self, mark) -> dict:
        c, s, h, m, n = mark
        return {
            "compiles": self.compiles - c,
            "compile_s": round(self.seconds - s, 3),
            "persistent_cache_hits": self.cache_hits - h,
            "persistent_cache_misses": self.cache_misses - m,
            "names": self.names[n:],
        }


def metered(fn):
    """``(fn(), seconds, what the process compiled meanwhile)``."""
    meter = CompileMeter.get()
    mark = meter.mark()
    t0 = time.perf_counter()
    value = fn()
    return value, time.perf_counter() - t0, meter.since(mark)


# ------------------------------------------------- staying on the chip

def new_session(ctx: Ctx, **executor_kw):
    from bigslice_tpu.exec.meshexec import MeshExecutor
    from bigslice_tpu.exec.session import Session

    ex = MeshExecutor(ctx.mesh, **executor_kw)
    spy_uploads(ex)
    return Session(executor=ex)


def spy_uploads(ex) -> None:
    """Record what every staged upload put on each device (the
    executor's ``_upload`` seam): rows per device, and which devices
    the shards landed on."""
    real = ex._upload
    ex.upload_rows = np.zeros(ex.nmesh, np.int64)
    ex.upload_devices = set()

    def upload(per_shard_frames):
        out = real(per_shard_frames)
        cols, counts = out[0], out[1]
        ex.upload_rows += np.asarray(counts, np.int64)
        for sh in getattr(cols[0], "addressable_shards", ()):
            ex.upload_devices.add(sh.device)
        return out

    ex._upload = upload


def planned_device_groups(ex, results) -> set:
    """Group keys of every device-eligible op group in the task graphs
    behind ``results`` — what the compiler planned for the device."""
    seen, keys = set(), set()
    stack = [t for r in results for t in r.tasks]
    while stack:
        t = stack.pop()
        if id(t) in seen:
            continue
        seen.add(id(t))
        if t.group_key is not None and ex._eligible(t):
            keys.add(t.group_key)
        for d in t.deps:
            stack.extend(d.tasks)
    return keys


def group_programs(ex) -> list:
    """HLO text of every compiled op-group program the executor
    holds."""
    texts = []
    with ex._lock:
        progs = [p for p, _ in ex._programs.values()]
    for p in progs:
        if getattr(p, "_kind", None) != "group":
            continue
        for compiled in list(getattr(p, "_compiled", {}).values()):
            texts.append(compiled.as_text())
    return texts


def mosaic_kernels(texts) -> dict:
    """How many group programs carry each named Pallas kernel AS A
    MOSAIC CUSTOM CALL (interpret mode leaves no custom call)."""
    from bigslice_tpu.parallel import pallas_kernels as pk

    out = {}
    for name in (pk.HASH_PARTITION_KERNEL, pk.HASH_AGGREGATE_KERNEL):
        out[name] = sum(
            any(name in line and "tpu_custom_call" in line
                for line in t.splitlines())
            for t in texts
        )
    return out


def ladders_silent(sess):
    """None of the ladders that move work off the device engaged in
    this session. Returns (telemetry summary, resource stats)."""
    ex = sess.executor
    require(not ex._probation and not ex._spmd_probation,
            f"device path on probation: {dict(ex._probation)} "
            f"{sorted(ex._spmd_probation)}")
    summary = sess.telemetry_summary()
    require(not summary.get("recovery"),
            f"recovery ladder engaged: {summary.get('recovery')}")
    states = summary.get("task_states", {})
    require(not states.get("LOST") and not states.get("ERR"),
            f"lost or failed tasks: {states}")
    fallbacks = summary["device"]["totals"]["fallbacks"]
    require(fallbacks == 0,
            f"AOT seam fell back to plain jit {fallbacks}x")
    stats = ex.resource_stats()
    require(not stats["gauges"]["hash_off"],
            f"hash lowering blacklisted: {stats['gauges']['hash_off']}")
    return summary, stats


def hbm_in_use(stats):
    used = [d.get("bytes_in_use") for d in stats["devices"]
            if d.get("bytes_in_use") is not None]
    return max(used) if used else None


def device_evidence(ctx: Ctx, sess, results) -> dict:
    """The after-phase assertions: nothing left the device path, every
    planned device group ran there, and (across chips) every device
    held input and received partitions. Returns the fields the phase
    line reports."""
    ex = sess.executor
    summary, stats = ladders_silent(sess)
    planned = planned_device_groups(ex, results)
    with ex._lock:
        ran = planned & set(ex._outputs)
        outs = [ex._outputs[k] for k in ran]
    require(planned and ran == planned,
            f"{len(planned) - len(ran)} of {len(planned)} planned "
            f"device groups did not run on the device")
    want = "tpu" if ctx.on_tpu else "cpu"
    for out in outs:
        for w in getattr(out, "waves", None) or [out]:
            for c in getattr(w, "cols", None) or ():
                devs = getattr(c, "devices", None)
                if devs is None:
                    continue
                plats = {d.platform for d in devs()}
                require(plats == {want},
                        f"result column resident on {plats}, not "
                        f"{want}")
    fields = {
        "device_groups": len(ran),
        "planned_groups": len(planned),
        "seam_compiles": summary["device"]["totals"]["compiles"],
        "resident_output_bytes": stats["resident_output_bytes"],
        "hbm_bytes_in_use": hbm_in_use(stats),
    }
    if ex.nmesh > 1:
        # Code that has never seen more than one real chip may place
        # everything on the first.
        received = [op["skew"]["rows"]
                    for op in summary["ops"].values() if "skew" in op]
        require(len(ex.upload_devices) == ex.nmesh
                and bool((ex.upload_rows > 0).all()),
                f"input shards on {len(ex.upload_devices)} of "
                f"{ex.nmesh} devices: rows {ex.upload_rows.tolist()}")
        require(received and all(len(r) == ex.nmesh and min(r) > 0
                                 for r in received),
                f"shuffle partitions per device: {received}")
        require(any("all-to-all" in t for t in group_programs(ex)),
                "no compiled group program contains an all-to-all")
        fields["uploaded_rows_per_device"] = ex.upload_rows.tolist()
        fields["received_rows_per_device"] = received
    return fields


def require_ops_on_mesh(summary, kinds) -> None:
    """Every op of these kinds ran its waves through the mesh executor
    (only the mesh path records waves) — for pipelines whose Results
    are gone by the time the phase can look."""
    for kind in kinds:
        waves = [op.get("waves", {}).get("n_waves", 0)
                 for name, op in summary["ops"].items()
                 if name.split("@")[0] == kind]
        require(waves and min(waves) >= 1,
                f"the {kind} group did not run on the device: {waves}")


def require_dense_programs(ex) -> None:
    """The dense table routes by key range: a group program that
    hashes its keys (either Mosaic kernel) is the sort or the hash
    pipeline, not the table. Evidence exists on a TPU only."""
    kernels = mosaic_kernels(group_programs(ex))
    require(not any(kernels.values()),
            f"a group program hashes keys — not the dense lowering: "
            f"{kernels}")


def result_columns(res) -> list:
    """All result rows as host numpy columns."""
    frames = [f.to_host() for f in res.frames()]
    return [
        np.concatenate([np.asarray(f.cols[j]) for f in frames])
        if frames else np.empty(0)
        for j in range(len(res.schema))
    ]


def by_key(cols) -> list:
    order = np.argsort(cols[0], kind="stable")
    return [c[order] for c in cols]


def require_equal(got, want, what: str) -> None:
    require(len(got) == len(want), f"{what}: column count")
    for j, (g, w) in enumerate(zip(got, want)):
        require(g.shape == w.shape and np.array_equal(g, w),
                f"{what}: column {j} differs from the reference "
                f"({g.shape} vs {w.shape})")


def cold_warm(ctx: Ctx, sess, run_once, reference, what: str) -> dict:
    """Run a pipeline twice in one session: cold (compiles), then warm
    with freshly built slices — which must compile NOTHING — and hold
    both to the reference and to ``device_evidence``. ``run_once``
    returns ``(results, canonical columns)``; results are discarded
    here after the evidence is read. Where the cold run discovered
    capacities, one settling run comes between the two."""
    def one_run(tag):
        (results, got), secs, compiled = metered(run_once)
        require_equal(got, reference, f"{what} ({tag})")
        fields = device_evidence(ctx, sess, results)
        for r in results:
            r.discard()
        return got, secs, compiled, fields

    got, cold_s, cold, fields = one_run("cold")
    ex = sess.executor
    settle = None
    if ex._cogroup_caps or ex._slack_memo:
        # The cold run DISCOVERED a capacity or a slack wave by wave,
        # so its early waves ran at smaller shapes than every later
        # run will (the memo only grows). One settling run reaches the
        # steady state; what it compiles (the readback's per-shape,
        # per-device prefix slices of the new shapes) is reported.
        _, _, settle, fields = one_run("settling")
    seam0 = fields["seam_compiles"]
    _, warm_s, warm, fields = one_run("warm")
    require(warm["compiles"] == 0 and fields["seam_compiles"] == seam0,
            f"{what}: the second run compiled "
            f"{warm['compiles']} programs ({warm['names'][:6]}), "
            f"seam {fields['seam_compiles'] - seam0}")
    fields.update({
        "result": got,
        "cold_s": round(cold_s, 3),
        "warm_s": round(warm_s, 3),
        "compiles": cold["compiles"],
        "compile_s": cold["compile_s"],
        "persistent_cache_hits": cold["persistent_cache_hits"],
        "persistent_cache_misses": cold["persistent_cache_misses"],
        "warm_compiles": warm["compiles"],
    })
    if settle is not None:
        fields["settle_compiles"] = settle["names"]
    return fields


def _add(a, b):
    # Module-level: program caches key on the combine fn's identity, so
    # the warm run reuses what the cold run compiled.
    return a + b


def sparse_keys(ids: np.ndarray) -> np.ndarray:
    """Spread ids in [0, 2^20] over a non-dense int32 key space."""
    return (ids.astype(np.int64) * 2039 + 7).astype(np.int32)


def reduce_reference(keys, vals) -> list:
    uniq, inv = np.unique(keys, return_inverse=True)
    sums = np.bincount(inv, weights=vals, minlength=len(uniq))
    return [uniq, sums.astype(np.int64).astype(np.int32)]


# --------------------------------------------------------------- phases

def phase_kernels(ctx: Ctx):
    """The Pallas kernels alone against the stock-XLA paths: Mosaic on
    a TPU, the interpreter in a CPU rehearsal."""
    import jax
    import jax.numpy as jnp

    from bigslice_tpu.parallel import hashagg
    from bigslice_tpu.parallel import pallas_kernels as pk
    from bigslice_tpu.parallel import shuffle as shuffle_mod

    require(pk.interpret_capable(), "pallas cannot build a kernel here")
    require(pk._interpret() == (not ctx.on_tpu),
            "kernels would run in the wrong mode for this platform")
    rng = np.random.default_rng(ctx.seed)
    n = ctx.sizes.kernel_rows
    t0 = time.perf_counter()
    checked = []

    # hash_partition: ids and histogram, with and without a validity
    # mask and the counts, single and multi-column (float32) keys.
    k_i = jnp.asarray(rng.integers(0, 1 << 30, n, dtype=np.int32))
    k_f = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    valid = jnp.asarray(rng.random(n) < 0.9)
    for keys, nparts, mask, counts in (
        ([k_i], 8, None, True),
        ([k_i], 8, valid, False),
        ([k_i, k_f], 64, valid, True),
    ):
        got, _, got_counts = shuffle_mod.partition_ids(
            keys, nparts, ctx.seed, valid=mask, use_pallas=True,
            with_counts=counts,
        )
        want, _, _ = shuffle_mod.partition_ids(
            keys, nparts, ctx.seed, valid=mask, use_pallas=False,
        )
        got, want = np.asarray(got), np.asarray(want)
        require(np.array_equal(got, want),
                f"hash_partition ids diverge (nparts={nparts})")
        if counts:
            require(np.array_equal(
                np.asarray(got_counts),
                np.bincount(want, minlength=nparts + 1)[:nparts],
            ), f"hash_partition histogram diverges (nparts={nparts})")
        checked.append(
            f"hash_partition/{len(keys)}key/p{nparts}"
            f"{'/valid' if mask is not None else ''}"
            f"{'/counts' if counts else ''}")

    # hash_aggregate: per-region equality with the XLA scatter path,
    # on a table of the size the VMEM gate allows.
    T = ctx.sizes.table_slots
    nkeys = max(16, min(n, T) // 8)
    ids = rng.integers(0, nkeys, n, dtype=np.int32)
    k1 = sparse_keys(ids)
    k2 = (ids % 7).astype(np.uint32)
    ints = rng.integers(-50, 50, n, dtype=np.int32)
    # Floats whose sums are exact in any order, plus keys that own the
    # special values: id 0 only -0.0, id 1 a NaN, id 2 +Inf, id 3 both
    # infinities (add -> NaN), id 4 -Inf.
    flo = rng.integers(-8, 8, n).astype(np.float32) * np.float32(0.5)
    for kid, vs in ((0, [-0.0]), (1, [np.nan, 1.5]), (2, [np.inf, 2.0]),
                    (3, [np.inf, -np.inf]), (4, [-np.inf, -3.0])):
        rows = np.flatnonzero(ids == kid)
        flo[rows] = np.resize(np.asarray(vs, np.float32), len(rows))
    flo2 = np.roll(flo, 1)
    flo2[np.isin(ids, (0, 1, 2, 3, 4))] = np.float32(1.0)
    valid_np = rng.random(n) < 0.9
    interpret = not ctx.on_tpu
    warm_s = {}
    for tag, keys, vals, ops, nparts in (
        ("i32/add", [k1], [ints], ["add"], 8),
        # 2 keys + 1 value, 4 planes: at real size exactly the gate.
        ("2key/f32/max", [k1, k2], [flo], ["max"], 4),
        ("f32/add+min", [k1], [flo, flo2], ["add", "min"], 8),
    ):
        R = T // nparts
        require(pk.aggregate_supported(
            [k.dtype for k in keys], [v.dtype for v in vals], nparts, R,
        ), f"{tag}: the table gate refuses nparts={nparts} R={R}")
        part, _, _ = shuffle_mod.partition_ids(
            [jnp.asarray(k) for k in keys], nparts, ctx.seed,
            use_pallas=False,
        )
        args = (jnp.asarray(valid_np), [jnp.asarray(k) for k in keys],
                [jnp.asarray(v) for v in vals], ops, part, nparts, R)
        got = pk.hash_aggregate_pallas(*args, seed=ctx.seed,
                                       interpret=interpret)
        jax.block_until_ready(got)
        t1 = time.perf_counter()
        jax.block_until_ready(pk.hash_aggregate_pallas(
            *args, seed=ctx.seed, interpret=interpret))
        warm_s[tag] = round(time.perf_counter() - t1, 4)
        want = hashagg.hash_aggregate(*args, seed=ctx.seed,
                                      backend="xla")
        require(int(got[3]) == 0 and int(want[3]) == 0,
                f"{tag}: overflow {int(got[3])}/{int(want[3])}")
        _require_same_table(tag, got, want, ops, R)
        checked.append(f"hash_aggregate/{tag}")
    line = {
        "phase": "kernels", "rows": n, "table_slots": T, "ok": True,
        "mode": "interpret" if interpret else "mosaic",
        "checked": checked,
        "hash_aggregate_warm_s": warm_s,
        "cold_s": round(time.perf_counter() - t0, 3),
    }
    return line, None


def _require_same_table(tag, got, want, ops, R) -> None:
    """Two hash tables hold the same (region, keys) -> values: max/min
    bit-exact, add by value (the scatter path adds into a +0.0
    identity, so an all -0.0 key sums to +0.0 there), NaN as NaN."""

    def rows(table):
        present, keys, vals, _ = table
        p = np.asarray(present)
        slots = np.flatnonzero(p)
        cols = [slots // R] + [np.asarray(k)[p].astype(np.int64)
                               for k in keys]
        order = np.lexsort(cols[::-1])
        return ([c[order] for c in cols],
                [np.asarray(v)[p][order] for v in vals])

    gk, gv = rows(got)
    wk, wv = rows(want)
    require(all(np.array_equal(a, b) for a, b in zip(gk, wk))
            and len(gk[0]) == len(wk[0]),
            f"{tag}: per-region key sets differ")
    for op, a, b in zip(ops, gv, wv):
        if a.dtype.kind != "f":
            require(np.array_equal(a, b), f"{tag}: {op} values differ")
            continue
        nan = np.isnan(a)
        require(np.array_equal(nan, np.isnan(b)),
                f"{tag}: {op} NaN placement differs")
        if op == "add":
            same = a[~nan] == b[~nan]
        else:
            same = a[~nan].view(np.int32) == b[~nan].view(np.int32)
        require(bool(np.all(same)), f"{tag}: {op} values differ")


def phase_reduce_dense(ctx: Ctx):
    """``Reduce(Const(S, keys, vals), add)`` with no annotation: the
    executor's staging-time probe has to find the dense lowering
    itself."""
    import bigslice_tpu as bs

    sz = ctx.sizes
    shards = int(ctx.mesh.devices.size)
    rng = np.random.default_rng(ctx.seed + 1)
    keys = rng.integers(0, sz.dense_keys, sz.dense_rows, dtype=np.int32)
    vals = rng.integers(0, 100, sz.dense_rows, dtype=np.int32)
    reference = reduce_reference(keys, vals)
    sess = new_session(ctx)
    built = []

    def run_once():
        r = bs.Reduce(bs.Const(shards, keys, vals), _add)
        built.append(r)
        res = sess.run(r)
        return [res], by_key(result_columns(res))

    try:
        fields = cold_warm(ctx, sess, run_once, reference,
                           "reduce-dense")
        for r in built:
            fc = r.frame_combiner
            require(fc.dense_keys is not None
                    and getattr(fc, "_auto_declared", False),
                    "the probe did not pick the dense lowering")
        require(not sess.executor._auto_dense_off,
                "a discovered dense bound was retracted")
        require_dense_programs(sess.executor)
    finally:
        sess.shutdown()
    got = fields.pop("result")
    line = {"phase": "reduce-dense", "rows": sz.dense_rows,
            "keys": len(reference[0]), "shards": shards,
            "lowering": "auto-dense", "ok": True, **fields}
    return line, got


def phase_reduce_generic(ctx: Ctx):
    """Keys spread over a non-dense int32 space (``rows_per_key`` rows
    a key), ``auto_dense=False``: first the platform's default generic
    lowering (the sort pipeline on a TPU: Mosaic ``hash_partition``,
    ``all_to_all``, segmented combine), then the hash-aggregate
    lowering switched on by constructor argument so the Pallas table
    kernel runs inside a group program."""
    import bigslice_tpu as bs

    sz = ctx.sizes
    rows, shards = sz.generic_rows, sz.shards
    rng = np.random.default_rng(ctx.seed + 2)
    k = sparse_keys(rng.integers(0, max(16, rows // sz.rows_per_key),
                                 rows, dtype=np.int32))
    v = rng.integers(0, 100, rows, dtype=np.int32)
    reference = reduce_reference(k, v)
    out = {"phase": "reduce-generic", "ok": True, "rows": rows,
           "keys": len(reference[0]), "shards": shards, "runs": {}}
    canon = {}
    for tag, kw in (("default", {}),
                    ("hash", {"hash_aggregate": True})):
        sess = new_session(ctx, auto_dense=False, **kw)
        ex = sess.executor

        def run_once():
            res = sess.run(bs.Reduce(bs.Const(shards, k, v), _add))
            return [res], by_key(result_columns(res))

        try:
            fields = cold_warm(ctx, sess, run_once, reference,
                               f"reduce-generic/{tag}")
            hash_on = ex._hashagg_enabled()
            kernels = mosaic_kernels(group_programs(ex))
            if tag == "hash":
                require(hash_on, "hash-aggregate did not engage")
            if ctx.on_tpu:
                # What only the chip does: the default is the sort
                # pipeline behind the Mosaic partitioner, and the hash
                # lowering runs the Pallas table kernel in-program.
                from bigslice_tpu.parallel import pallas_kernels as pk

                need = (pk.HASH_AGGREGATE_KERNEL if tag == "hash"
                        else pk.HASH_PARTITION_KERNEL)
                require(tag == "hash" or not hash_on,
                        "the TPU default took the hash lowering")
                require(kernels[need] > 0,
                        f"no group program carries the {need} kernel")
        finally:
            sess.shutdown()
        canon[tag] = fields.pop("result")
        out["runs"][tag] = {
            "lowering": "hash-aggregate" if hash_on else "sort",
            "mosaic_kernels": kernels, **fields,
        }
    return out, canon


def join_inputs(ctx: Ctx):
    sz = ctx.sizes
    rng = np.random.default_rng(ctx.seed + 3)
    nk = max(16, sz.join_rows // sz.rows_per_key)
    return tuple(
        (sparse_keys(rng.integers(0, nk, sz.join_rows, dtype=np.int32)),
         rng.integers(0, 1 << 20, sz.join_rows, dtype=np.int32))
        for _ in range(2)
    )


def phase_join(ctx: Ctx):
    """Two keyed slices joined two ways in one session:
    ``JoinAggregate`` (per-side combine, shuffle, on-device align) and
    the general ragged ``Cogroup`` with executor-discovered
    capacity."""
    import bigslice_tpu as bs

    sz = ctx.sizes
    (ak, av), (bk, bv) = join_inputs(ctx)
    # JoinAggregate reference: per-side sums, inner join on the key.
    ra, rb = reduce_reference(ak, av % 100), reduce_reference(bk, bv % 100)
    common, ia, ib = np.intersect1d(ra[0], rb[0], assume_unique=True,
                                    return_indices=True)
    join_ref = [common, ra[1][ia], rb[1][ib]]
    # Cogroup reference: every (key, value) pair of each side exactly
    # once under its key, one group per key of the union.
    pairs_ref = [_sorted_pairs(ak, av), _sorted_pairs(bk, bv)]
    keys_ref = np.union1d(ak, bk)
    reference = join_ref + [keys_ref] + [c for p in pairs_ref for c in p]

    sess = new_session(ctx)
    ex = sess.executor

    def run_once():
        a = bs.Const(sz.shards, ak, av % 100)
        b = bs.Const(sz.shards, bk, bv % 100)
        jres = sess.run(bs.JoinAggregate(a, b, _add, _add))
        cres = sess.run(bs.Cogroup(bs.Const(sz.shards, ak, av),
                                   bs.Const(sz.shards, bk, bv)))
        ckeys, la, lb = result_columns(cres)
        require(len(np.unique(ckeys)) == len(ckeys),
                "cogroup: a key came out in two groups")
        got = by_key(result_columns(jres)) + [np.sort(ckeys)]
        for lists in (la, lb):
            sizes = np.fromiter((len(x) for x in lists), np.int64,
                                len(lists))
            flat = (np.concatenate([np.asarray(x) for x in lists])
                    if len(lists) else np.empty(0, np.int32))
            got += _sorted_pairs(np.repeat(ckeys, sizes),
                                 flat.astype(np.int32))
        return [jres, cres], got

    try:
        fields = cold_warm(ctx, sess, run_once, reference, "join")
        caps = dict(ex._cogroup_caps)
        require(caps, "cogroup ran without discovering a capacity: "
                      "the device lowering did not serve it")
        kernels = mosaic_kernels(group_programs(ex))
    finally:
        sess.shutdown()
    got = fields.pop("result")
    line = {"phase": "join", "rows_per_side": sz.join_rows,
            "shards": sz.shards, "matched_keys": len(common),
            "cogroup_groups": len(keys_ref),
            "cogroup_capacity": sorted(caps.values()),
            "lowering": ("hash-aggregate" if ex._hashagg_enabled()
                         else "sort") + " join + tagged-sort cogroup",
            "mosaic_kernels": kernels, "ok": True, **fields}
    return line, got


def _sorted_pairs(keys, vals) -> list:
    order = np.lexsort((vals, keys))
    return [keys[order], vals[order]]


def write_url_files(ctx: Ctx):
    """``url_lines`` URLs over a Zipf-skewed domain population, one
    line in eight upper-cased (the parse lowers hosts), written to
    ``shards`` files. Returns (paths, expected {domain: count})."""
    sz = ctx.sizes
    rng = np.random.default_rng(ctx.seed + 4)
    doms = (rng.zipf(1.5, sz.url_lines) % sz.url_domains)
    counts = np.bincount(doms, minlength=sz.url_domains)
    expected = {f"site{d}.example.com": int(c)
                for d, c in enumerate(counts) if c}
    lines = [
        (f"HTTP://Site{d}.Example.COM/P/{i & 1023}" if i % 8 == 0
         else f"http://site{d}.example.com/p/{i & 1023}")
        for i, d in enumerate(doms.tolist())
    ]
    per = -(-len(lines) // sz.shards)
    paths = []
    for s in range(sz.shards):
        path = os.path.join(ctx.workdir, f"urls-{s:02d}.txt")
        with open(path, "w") as fp:
            fp.write("\n".join(lines[s * per:(s + 1) * per]) + "\n")
        paths.append(path)
    return paths, expected


def _worker_holds_libtpu(_):
    """Runs in a parse-pool worker: is the TPU runtime mapped into this
    process? (It is, for good, once a TPU backend initialises.)"""
    time.sleep(0.02)  # let the map spread over the pool
    with open("/proc/self/maps") as fp:
        return os.getpid(), any("libtpu" in line for line in fp)


def delete_native_objects() -> None:
    """Delete the (git-ignored) native parse objects so the urls phase
    builds them again from the committed C sources: the staleness test
    is mtime-only, and an object from another toolchain could
    otherwise ride along with a copy of the tree."""
    from bigslice_tpu import native

    for so in (native._SO, native._LIST_SO):
        if os.path.exists(so):
            os.unlink(so)


def phase_urls(ctx: Ctx):
    """The upstream ``cmd/urls`` shape: URL files -> ``ScanReader`` ->
    host parse + dictionary encoding -> device ``Reduce``. Cold and
    warm stream the files (the in-process C kernel serves 4096-row
    batches); a third run feeds the same lines from memory so the
    batches are big enough for the parse POOL, whose spawned workers
    must never load the TPU runtime while this process holds the
    chip."""
    from bigslice_tpu import native
    from bigslice_tpu.frame import strparse
    from bigslice_tpu.models.urls import domain_count_encoded

    sz = ctx.sizes
    shards = int(ctx.mesh.devices.size)
    require(native._load() is not None,
            "strscan.c did not build (cc missing?)")
    require(native._load_list() is not None,
            "strlist.c did not build (cc or Python.h missing?)")
    paths, expected = write_url_files(ctx)
    reference = [np.array(sorted(expected)),
                 np.array([expected[k] for k in sorted(expected)])]

    def stream():
        for p in paths:
            with open(p) as fp:
                for line in fp:
                    yield line.rstrip("\n")

    sess = new_session(ctx)
    tiers = strparse.ParseTiers()

    def counted(source):
        rows = dict(domain_count_encoded(sess, shards, source,
                                         parse_tiers=tiers))
        names = sorted(rows)
        return [np.array(names), np.array([rows[k] for k in names])]

    try:
        got, cold_s, cold = metered(lambda: counted(stream))
        require_equal(got, reference, "urls (cold)")
        got, warm_s, warm = metered(lambda: counted(stream))
        require_equal(got, reference, "urls (warm)")
        require(warm["compiles"] == 0,
                f"urls: the second run compiled {warm['names'][:6]}")
        streamed = dict(tiers.rows)
        require(set(streamed) == {"c"},
                f"the C parse tier did not serve every row: {streamed}")

        # Same lines from memory: 65536-row batches engage the pool.
        lines = list(stream())
        got, pool_s, _ = metered(lambda: counted(lines))
        require_equal(got, reference, "urls (parse pool)")
        pooled = {k: v - streamed.get(k, 0)
                  for k, v in tiers.rows.items()
                  if v - streamed.get(k, 0)}
        require(set(pooled) <= {"c", "c_pool"} and pooled.get("c_pool"),
                f"the parse pool's C tier did not serve: {pooled}")
        pool = strparse._pool()
        require(pool is not None, "no parse pool on this host")
        probes = pool.map(_worker_holds_libtpu,
                          range(4 * strparse.parse_procs()), 1)
        require(not any(held for _, held in probes),
                "a parse worker loaded the TPU runtime")
        # No Result survives domain_count_encoded, so the planned
        # groups cannot be walked here: the ladders must be silent,
        # and both device op groups of the pipeline (count attach +
        # map-side combine, reduce side) must have run their waves on
        # the mesh.
        summary, _ = ladders_silent(sess)
        require_ops_on_mesh(summary, ("map", "reduce"))
        require_dense_programs(sess.executor)
    finally:
        sess.shutdown()
        strparse.shutdown_pool()
    line = {
        "phase": "urls", "rows": sz.url_lines,
        "domains": len(expected), "shards": shards, "ok": True,
        "lowering": "declared dense",
        "parse_tier": {"streamed": streamed, "from_memory": pooled},
        "parse_workers_checked": len({pid for pid, _ in probes}),
        "parse_workers_with_libtpu": 0,
        "cold_s": round(cold_s, 3), "warm_s": round(warm_s, 3),
        "pool_s": round(pool_s, 3),
        "compiles": cold["compiles"], "compile_s": cold["compile_s"],
        "persistent_cache_hits": cold["persistent_cache_hits"],
        "warm_compiles": warm["compiles"],
    }
    return line, None


def kmeans_configuration():
    """``(config, pipeline module)`` of the benchmark's k-means
    configuration: the sizes, precision and tolerance it states, and
    the generator of its points."""
    import importlib.util

    with open(os.path.join(KMEANS_CONFIG, "config.json")) as fp:
        cfg = json.load(fp)
    spec = importlib.util.spec_from_file_location(
        "kmeans_pipeline", os.path.join(KMEANS_CONFIG, cfg["pipeline"]))
    pipeline = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pipeline)
    return cfg, pipeline


def phase_kmeans(ctx: Ctx):
    """BASELINE.json config 5 at its published widths (d=128, k=64,
    f32 at the configuration's matmul precision): upload once, three
    rounds over the reused Result, and no compile after round one.
    Each round is held to the float64 reference round from the same
    centroids: every point assigned exactly once, the centroids within
    the configuration's tolerance."""
    from bigslice_tpu.models.kmeans import (
        kmeans_reference_round,
        kmeans_rounds,
    )

    sz = ctx.sizes
    cfg, pipeline = kmeans_configuration()
    tol = cfg["compare"]["centroids"]
    shards = int(ctx.mesh.devices.size)
    data = pipeline.make_data({**cfg, "points": sz.kmeans_points},
                              ctx.seed)
    pts, cents = data.points, data.init
    sess = new_session(ctx)
    ex = sess.executor
    round_s, round_compiles, margins = [], [], []
    try:
        rounds = kmeans_rounds(sess, pts, cfg["k"], num_shards=shards,
                               seed=ctx.seed,
                               precision=cfg["matmul_precision"])
        for r in range(KMEANS_ROUNDS):
            start = cents
            (cents, counts), secs, since = metered(lambda: next(rounds))
            round_s.append(round(secs, 3))
            round_compiles.append(since["compiles"])
            want, _ = kmeans_reference_round(pts, start)
            require(int(counts.sum()) == sz.kmeans_points,
                    f"kmeans round {r + 1}: {int(counts.sum())} points "
                    f"assigned, not {sz.kmeans_points}")
            margins.append(float(np.max(
                np.abs(cents - want)
                / (tol["atol"] + tol["rtol"] * np.abs(want)))))
            require(np.isfinite(cents).all() and margins[-1] <= 1,
                    f"kmeans round {r + 1}: centroids {margins[-1]:g} "
                    f"times the tolerance off")
            require(r == 0 or since["compiles"] == 0,
                    f"kmeans round {r + 1} compiled "
                    f"{since['names'][:6]}")
        rounds.close()
        summary, stats = ladders_silent(sess)
        require_ops_on_mesh(summary, ("const", "map", "reduce"))
        require_dense_programs(ex)
        # Each round frees what it computed (discard_graph): only the
        # uploaded points stay resident.
        require(ex.device_group_count() == 1,
                f"{ex.device_group_count()} op-group outputs resident "
                f"after {KMEANS_ROUNDS} rounds, not just the points")
        require(int(ex.upload_rows.sum()) == sz.kmeans_points,
                f"points uploaded {int(ex.upload_rows.sum())} rows, "
                f"not once")
    finally:
        sess.shutdown()
    line = {
        "phase": "kmeans", "rows": sz.kmeans_points, "d": cfg["d"],
        "k": cfg["k"], "shards": shards, "ok": True,
        "lowering": "declared dense (vector values)",
        "precision": cfg["matmul_precision"],
        "rounds": KMEANS_ROUNDS, "round_s": round_s,
        "cold_s": round_s[0], "warm_s": round_s[-1],
        "compiles_per_round": round_compiles,
        "tolerance_margin": margins, "rtol": tol["rtol"],
        "atol": tol["atol"],
        "resident_output_bytes": stats["resident_output_bytes"],
        "hbm_bytes_in_use": hbm_in_use(stats),
    }
    return line, None


#: Largest mean signed error of a small table's float32 vector sums,
#: over the sum of the magnitudes they add. On a v5e, over the k-means
#: points: the scatter +1.2e-7; a one-hot contraction over three exact
#: bfloat16 parts of the rows -2.8e-6, an f32 dot at HIGHEST -8.0e-5.
DENSE_TABLE_MEAN_ERROR = 1e-6


def phase_dense_table(ctx: Ctx):
    """The k-means round's table alone, at the k-means phase's size:
    its points summed a centroid into a ``k + 1``-slot dense table (the
    last slot the drop lane) against float64 sums of the same rows.
    Float32 rounding in any order reads either way and averages out; a
    lowering whose sums lean one way (a one-hot contraction on the MXU)
    fails on the mean signed error. The counts and ``present`` are
    exact."""
    import jax

    from bigslice_tpu.parallel import dense

    cfg, pipeline = kmeans_configuration()
    n, d, k = ctx.sizes.kmeans_points, int(cfg["d"]), int(cfg["k"])
    x = pipeline.make_data({**cfg, "points": n}, ctx.seed).points
    rng = np.random.default_rng([ctx.seed, 41])
    key = rng.integers(0, k, n, dtype=np.int32)
    valid = rng.random(n) >= 1 / 64
    w = np.ones(n, np.float32)
    core = dense.make_dense_combine(k, ("add", "add"),
                                    [np.float32, np.float32])
    fn = jax.jit(lambda v, c, a, b: core(v, (c,), (a, b)))
    present, _, (sums, counts) = jax.device_get(fn(valid, key, x, w))

    # float64 sums a slot, over the rows sorted by slot, a block of
    # columns at a time.
    kept = np.where(valid, key, k)
    order = np.argsort(kept, kind="stable")
    want_n = np.bincount(kept, minlength=k + 1)[:k]
    starts = np.concatenate([[0], np.cumsum(want_n)[:-1]])
    want = np.zeros((k, d))
    scale = np.zeros((k, d))
    for j in range(0, d, 16):
        block = np.take(x[:, j:j + 16], order[:want_n.sum()], axis=0)
        want[:, j:j + 16] = np.add.reduceat(block, starts, axis=0,
                                            dtype=np.float64)
        scale[:, j:j + 16] = np.add.reduceat(np.abs(block), starts,
                                             axis=0, dtype=np.float64)
    require((want_n > 0).all(), "a slot with no rows")
    require(np.array_equal(counts, want_n.astype(np.float32))
            and np.array_equal(present, want_n > 0),
            "the counts or present differ from the rows'")
    err = (sums.astype(np.float64) - want) / scale
    mean_err, max_err = float(err.mean()), float(np.abs(err).max())
    require(np.isfinite(err).all()
            and abs(mean_err) <= DENSE_TABLE_MEAN_ERROR,
            f"the table's sums lean {mean_err:.3g} of their magnitude "
            f"(limit {DENSE_TABLE_MEAN_ERROR:g}; largest {max_err:.3g})")
    line = {
        "phase": "dense-table", "rows": n, "d": d, "slots": k + 1,
        "ok": True, "lowering": dense.table_column_lowering(k + 1, (d,)),
        "mean_signed_error": mean_err, "max_abs_error": max_err,
        "limit": DENSE_TABLE_MEAN_ERROR,
    }
    return line, None


ONE_CHIP_PHASES = {
    "kernels": phase_kernels,
    "reduce-dense": phase_reduce_dense,
    "reduce-generic": phase_reduce_generic,
    "join": phase_join,
    "urls": phase_urls,
    "kmeans": phase_kmeans,
    "dense-table": phase_dense_table,
}

#: What exists only across chips: the shuffles as collectives.
MULTI_CHIP_PHASES = ("reduce-generic", "join")


# ------------------------------------------------------------- driving

def run_phase(name: str, ctx: Ctx):
    """One phase, one JSON line. Returns (ok, canonical result)."""
    t0 = time.perf_counter()
    try:
        line, canon = ONE_CHIP_PHASES[name](ctx)
    except Exception as e:  # noqa: BLE001 — report, go on, fail at end
        import traceback

        traceback.print_exc()
        emit({"phase": name, "ok": False,
              "devices": int(ctx.mesh.devices.size),
              "error": f"{type(e).__name__}: {e}"[:600],
              "seconds": round(time.perf_counter() - t0, 3)})
        return False, None
    finally:
        gc.collect()
    line["devices"] = int(ctx.mesh.devices.size)
    line["seconds"] = round(time.perf_counter() - t0, 3)
    emit(line)
    return True, canon


def run_multi_chip(ctx1: Ctx, ctxn: Ctx) -> bool:
    """The shuffle-as-collective path on ``n`` devices of one host in
    one process, against the same data on one device and against numpy
    (each phase checks the reference itself)."""
    ok = True
    n = int(ctxn.mesh.devices.size)
    require(tuple(ctxn.mesh.axis_names) == ("shards",),
            f"{n} chips of one host must come out as a 1-D mesh, got "
            f"{ctxn.mesh.axis_names}")
    for name in MULTI_CHIP_PHASES:
        ok_n, canon_n = run_phase(name, ctxn)
        ok_1, canon_1 = run_phase(name, ctx1)
        same = ok_n and ok_1 and _canon_equal(canon_n, canon_1)
        emit({"phase": f"{name}/compare", "ok": bool(same),
              "devices": [n, 1],
              "what": f"{n}-device result == 1-device result == numpy"})
        ok = ok and same
    return ok


def _canon_equal(a, b) -> bool:
    if isinstance(a, dict):
        return set(a) == set(b) and all(_canon_equal(a[k], b[k])
                                        for k in a)
    return len(a) == len(b) and all(
        np.array_equal(x, y) for x, y in zip(a, b)
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds all generated data")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the multi-chip comparison")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU; never prints the "
                         "contract line")
    ap.add_argument("--phases", default="",
                    help="comma-separated subset (debugging; a partial "
                         "run never prints the contract line)")
    args = ap.parse_args(argv)

    from bigslice_tpu.utils import hermetic

    if args.cpu_rehearsal:
        flag = f"--xla_force_host_platform_device_count={args.chips}"
        if "xla_force_host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
        hermetic.force_hermetic_cpu()
    cache_dir = hermetic.configure_compile_cache()
    import jax

    devs = jax.devices()
    dev0 = devs[0]
    if not args.cpu_rehearsal and dev0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev0.platform!r} "
              f"(--cpu-rehearsal runs the tiny CPU rehearsal)",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2

    import tempfile

    from jax.sharding import Mesh

    from bigslice_tpu.parallel.meshutil import shape_device_mesh

    on_tpu = dev0.platform == "tpu"
    sizes = TINY if args.cpu_rehearsal else REAL
    emit({"phase": "start", "platform": dev0.platform,
          "kind": dev0.device_kind, "count": len(devs),
          "chips": args.chips, "seed": args.seed,
          "compile_cache_dir": cache_dir,
          "jax": jax.__version__, "cuts": list(sizes.cuts)})
    t0 = time.perf_counter()
    meter = CompileMeter.get()
    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as workdir:
        def ctx_for(mesh):
            return Ctx(mesh=mesh, sizes=sizes, seed=args.seed,
                       on_tpu=on_tpu, workdir=workdir)

        # The mesh as sliceconfig.make_session builds it for one chip.
        ctx1 = ctx_for(Mesh(np.array(devs[:1]), ("shards",)))
        if args.chips > 1:
            meshn = shape_device_mesh(devs[:args.chips])
            ok = run_multi_chip(ctx1, ctx_for(meshn))
            full = True
        else:
            names = [p for p in args.phases.split(",") if p] \
                or list(ONE_CHIP_PHASES)
            full = names == list(ONE_CHIP_PHASES)
            if "urls" in names:
                delete_native_objects()
            ok = True
            for name in names:
                ok = run_phase(name, ctx1)[0] and ok
    emit({"phase": "total", "ok": ok,
          "seconds": round(time.perf_counter() - t0, 3),
          "compiles": meter.compiles,
          "compile_s": round(meter.seconds, 3),
          "persistent_cache_hits": meter.cache_hits,
          "persistent_cache_misses": meter.cache_misses,
          "compile_cache_dir": cache_dir})
    if not ok:
        return 1
    if on_tpu and full:
        # The contract line: the platform this run really used.
        emit({"ok": True, "device": {"platform": dev0.platform,
                                     "kind": dev0.device_kind,
                                     "count": len(devs)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
