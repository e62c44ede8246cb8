"""Benchmark harness: the five BASELINE.md configs, kernel and end-to-end.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Modes (argv[1], default "reduce"):

- ``reduce``      end-to-end keyed Reduce through Session+MeshExecutor —
                  host rows in, result scan out (config #1/#2 shape).
                  The honest framework number: includes host→device
                  upload, compile-cache lookups, the evaluator, and
                  result readback, not just the kernel.
- ``reduce-dense``  same workload with the key space declared
                  (``dense_keys``): the sort-free dense-table +
                  collective lowering. 32x the sort path on the CPU
                  mesh; the fast path for dictionary/categorical keys.
- ``reduce-kernel``  the raw MeshReduceByKey SPMD kernel on pre-staged
                  device arrays (the round-1 metric; upper bound).
- ``join``        end-to-end JoinAggregate through the Session (config
                  #3, the BASELINE Reduce+Cogroup headline shape).
- ``join-kernel`` raw MeshJoinAggregate kernel.
- ``wordcount``   config #2 (cmd/urls shape): synthetic URL corpus →
                  ScanReader → host parse → dict-encode → device Reduce,
                  all through the Session (models/urls).
- ``sortshuffle`` config #4: Reshuffle + per-shard device sort.
- ``serve-qps``   sustained serving load against a live ServeServer
                  (serve/server.py): QPS + p50/p99 latency, warm-vs-
                  cold first-request latency across a FRESH Session
                  (zero XLA compiles via the cross-Session program
                  cache — enforced), program-cache hit rate.
- ``kernel-select``  the measured kernel-selector A/B: one generic-key
                  Reduce forced onto the sort pipeline, forced onto
                  the hash-aggregate cascade, then run under
                  BIGSLICE_KERNEL_SELECT=measured; bit-parity and
                  picked-the-winner are enforced, vs_baseline is the
                  forced-worst arm.
- ``cogroup``     the general ragged Cogroup: device tagged-sort +
                  rank-scatter lowering (discovered capacity) vs the
                  exact host sorted-merge tier as baseline.
- ``kmeans``      config #5: iterative Session k-means (Map with
                  unbatched centroid arg + Reduce over a reused Result);
                  raw jitted-step TFLOP/s noted as the MXU roofline.

CPU baselines are numpy implementations of each workload measured on
this host (BASELINE.md: the reference publishes no numbers; numpy is
deliberately generous vs bigslice's per-record reflection). The device
path runs the full SPMD pipeline on however many chips are visible.

End-to-end modes assert that op groups actually engaged the device path
(round-1 verdict: a silent fallback must not masquerade as a TPU
number).
"""

import json
import sys
import time

import numpy as np


def _add(a, b):
    """THE combine fn every bench shares. Module-level on purpose:
    program/jit caches key on fn identity (and the cross-Session
    program cache on fn *content*), so a fresh lambda per bench — or
    per timing iteration — would recompile every kernel and pollute
    the warm-path numbers the serve-qps bench depends on. Each bench
    still runs an explicit warm pass before its timed region."""
    return a + b


def emit(metric: str, value: float, unit: str, baseline: float,
         **extra) -> None:
    """One bench JSON line; ``extra`` fields (e.g. the telemetry hub's
    overlap_efficiency) ride along so BENCH_*.json snapshots can carry
    them next to throughput."""
    print(json.dumps({
        "metric": metric,
        "value": round(value, 3),
        "unit": unit,
        "vs_baseline": round(value / baseline, 3) if baseline else 0.0,
        **extra,
    }))


def note(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr)


def _mesh():
    # Honors BIGSLICE_MESH_SHAPE=DxI (the 2-D DCN × ICI hierarchy) and
    # the real-TPU topology probe; unset on a flat fleet this is the
    # same 1-D ("shards",) mesh every prior bench built.
    import jax

    from bigslice_tpu.parallel.meshutil import shape_device_mesh

    return shape_device_mesh(jax.devices())


def _mesh_session(mesh):
    from bigslice_tpu.exec.meshexec import MeshExecutor
    from bigslice_tpu.exec.session import Session

    return Session(executor=MeshExecutor(mesh))


def _bytes_roofline(metric: str, rows: int, row_bytes: int,
                    secs: float, passes: int) -> None:
    """HBM-traffic estimate for the sort-dominated pipelines: bytes
    moved vs time, for comparison against the chip's HBM bandwidth
    (v5e ≈ 819 GB/s; the sort pipeline is bandwidth-bound, not MXU-
    bound, so bandwidth utilization is the roofline that matters)."""
    gb = rows * row_bytes * passes / 1e9
    note(f"{metric}: ~{gb:.2f} GB est. HBM traffic in {secs*1e3:.1f} ms "
         f"→ {gb/secs:.0f} GB/s effective ({passes} passes × {row_bytes}B/row)")


# ---------------------------------------------------------------- reduce

def cpu_reduce_baseline(keys: np.ndarray, vals: np.ndarray) -> float:
    """rows/sec for numpy sort-based reduce-by-key (single core)."""
    t0 = time.perf_counter()
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    sv = vals[order]
    bounds = np.flatnonzero(np.concatenate([[True], sk[1:] != sk[:-1]]))
    np.add.reduceat(sv, bounds)
    dt = time.perf_counter() - t0
    return len(keys) / dt


def reduce_kernel_bench(keys, vals, iters: int = 5):
    import jax

    from bigslice_tpu.parallel import shuffle as shuffle_mod

    mesh = _mesh()
    n = mesh.devices.size
    total = len(keys)
    per = total // n
    cap = per
    key_chunks = [keys[i * per : (i + 1) * per] for i in range(n)]
    val_chunks = [vals[i * per : (i + 1) * per] for i in range(n)]
    cols, counts = shuffle_mod.shard_columns(
        mesh, [key_chunks, val_chunks], [per] * n, cap
    )
    red = shuffle_mod.MeshReduceByKey(
        mesh, nkeys=1, nvals=1, capacity=cap,
        combine_fn=_add,
    )

    def run_once():
        k_out, v_out, out_counts, overflow = red([cols[0]], [cols[1]],
                                                 counts)
        jax.block_until_ready(v_out[0])
        return out_counts, overflow

    out_counts, overflow = run_once()  # compile + warm
    if int(np.asarray(overflow)) != 0:
        note("warning: shuffle overflow in reduce-kernel bench")
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        run_once()
        times.append(time.perf_counter() - t0)
    best = min(times)
    # Pipeline passes over the working set (rows×8B for key+val int32):
    # ~4 sorts (combine, bucket, final combine×2 operand groups) + a2a.
    _bytes_roofline("reduce_kernel", n * per, 8, best, passes=10)
    return (n * per) / best


def reduce_e2e_bench(keys, vals, iters: int = 3, dense_keys=None,
                     auto_dense: bool = True, hash_aggregate=None):
    """End-to-end: Session + MeshExecutor + result scan, fresh slices
    per iteration (compile caches warm after iteration 0 — the
    iterative-driver steady state). ``dense_keys`` engages the
    sort-free dense-table lowering (parallel/dense.py) explicitly;
    with neither declared nor disabled, the executor's staging-time
    probe discovers dense ranges itself. ``auto_dense=False`` pins the
    generic-key path (hash-aggregate by default; pass
    ``hash_aggregate=False`` too for the pure sort-pipeline A/B)."""
    import bigslice_tpu as bs
    from bigslice_tpu.exec.meshexec import MeshExecutor
    from bigslice_tpu.exec.session import Session

    mesh = _mesh()
    sess = Session(executor=MeshExecutor(
        mesh, auto_dense=auto_dense, hash_aggregate=hash_aggregate
    ))
    n = mesh.devices.size

    def run_once():
        # Stable fn identity across iterations: program/jit caches key
        # on id(fn), so rebuilding the slice each round reuses the
        # compiled SPMD program (the iterative-driver steady state).
        r = bs.Reduce(bs.Const(n, keys, vals), _add,
                      dense_keys=dense_keys)
        res = sess.run(r)
        total = 0
        for f in res.frames():
            total += len(f)
        res.discard()
        return total

    run_once()  # warm compile caches
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        distinct = run_once()
        times.append(time.perf_counter() - t0)
    if sess.executor.device_group_count() == 0:
        raise RuntimeError("e2e reduce never engaged the device path")
    best = min(times)
    # The pass count is the declared roofline risk (BASELINE.md): the
    # hash-aggregate pipeline holds it at ~6 full-data passes (claim
    # rounds + accumulate + one region a2a + receive-side cascade +
    # compaction) vs ~12 for the sort pipeline. Printed AND asserted:
    # if the generic path silently regressed to sorts (blacklist,
    # classification drift), this bench fails loudly.
    ex = sess.executor
    generic = dense_keys is None and not auto_dense
    hash_on = generic and ex._hashagg_enabled() and not ex._hash_off
    # Honest per-lowering pass estimates: the sort pipeline's ~12
    # (BASELINE.md roofline), the hash cascade's ~6 (claim rounds +
    # accumulate + region a2a + receive cascade + compaction), the
    # dense table's ~4 (scatter + routed a2a + plane reduce + compact).
    passes = 12 if (generic and not hash_on) else 6 if hash_on else 4
    lowering = ("hash-aggregate" if hash_on
                else "sort" if generic
                else "dense" if dense_keys else "auto-dense")
    note(f"reduce_e2e lowering: {lowering}; ~{passes} HBM passes")
    if generic and ex._hashagg_enabled():
        # The generic-key mode must actually run the 6-pass hash
        # pipeline: a mid-bench blacklist (cascade overflow) or
        # classification drift silently regressing to 12-pass sorts is
        # a bench failure, not a footnote.
        assert hash_on, (
            f"hash-aggregate path did not engage: off={ex._hash_off}"
        )
    note(f"reduce_e2e: {distinct} distinct keys, "
         f"device groups {sess.executor.device_group_count()}")
    _bytes_roofline("reduce_e2e", len(keys), 8, best, passes=passes)
    return len(keys) / best


# --------------------------------------------------------- kernel-select

def kernel_select_bench(n_rows: int, iters: int = 3):
    """The PR-18 kernel-selector A/B: the SAME generic-key (non-dense)
    keyed Reduce run three ways on one mesh — combine lowering forced
    to the sort pipeline, forced to the hash-aggregate cascade, and
    chosen by the measured selector (BIGSLICE_KERNEL_SELECT=measured:
    one-shot timed probes of both cores at the observed shuffle scale,
    probe programs landing in the cross-Session program cache).

    Bit-parity across all three arms is ENFORCED (sorted result rows
    compared), the measured arm's decision log is returned as evidence,
    and the measured arm must both pick the kernel the forced A/B says
    is faster AND beat the forced-WORST arm — the number that judges
    what auto-selection buys over guessing wrong."""
    import os

    import bigslice_tpu as bs
    from bigslice_tpu.exec.meshexec import MeshExecutor
    from bigslice_tpu.exec.session import Session

    mesh = _mesh()
    n = mesh.devices.size
    rng = np.random.RandomState(42)
    # Sparse keys (multiplicative scramble over 2^30): the auto-dense
    # staging probe declines, so the generic sort-vs-hash choice — the
    # one the selector owns — is actually exercised. Cardinality stays
    # moderate (2^12 distinct → ~128 rows/key) — the regime the
    # probe's synthetic corpus (distinct = rows/4) models; a near-
    # unique-key corpus has nothing to combine map-side and the hash
    # cascade loses its reason to exist (docs/kernels.md).
    keys = ((rng.randint(0, 1 << 12, n_rows).astype(np.int64)
             * 92821 + 17) % (1 << 30)).astype(np.int32)
    vals = np.ones(n_rows, dtype=np.int32)

    def arm(env_mode, hash_aggregate, warm: int = 1):
        """One configuration: fresh Session, warm pass(es), best-of-
        iters wall, sorted result rows for the parity check. The env
        knob is set around Session construction only — selector wiring
        happens in Session.__init__."""
        prev = os.environ.pop("BIGSLICE_KERNEL_SELECT", None)
        if env_mode is not None:
            os.environ["BIGSLICE_KERNEL_SELECT"] = env_mode
        try:
            sess = Session(executor=MeshExecutor(
                mesh, auto_dense=False, hash_aggregate=hash_aggregate
            ))
        finally:
            os.environ.pop("BIGSLICE_KERNEL_SELECT", None)
            if prev is not None:
                os.environ["BIGSLICE_KERNEL_SELECT"] = prev

        def run_once(collect=False):
            r = bs.Reduce(bs.Const(n, keys, vals), _add)
            res = sess.run(r)
            out = (sorted(map(tuple, res.rows())) if collect
                   else sum(len(f) for f in res.frames()))
            res.discard()
            return out

        # Warm compile caches; the measured arm gets an extra settle
        # pass so a first-wave skew reselection (no hub stats exist
        # before wave 0) lands before the timed region.
        for _ in range(warm):
            run_once()
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            run_once()
            times.append(time.perf_counter() - t0)
        rows = run_once(collect=True)
        if sess.executor.device_group_count() == 0:
            raise RuntimeError(
                "kernel-select arm never engaged the device path")
        sel = getattr(sess, "kernel_select", None)
        evidence = sel.stats.summary() if sel is not None else None
        sess.shutdown()
        return len(keys) / min(times), rows, evidence

    sort_rps, sort_rows, _ = arm(None, False)
    hash_rps, hash_rows, _ = arm(None, True)
    measured_rps, measured_rows, evidence = arm("measured", None,
                                                warm=2)
    if sort_rows != hash_rows or sort_rows != measured_rows:
        raise RuntimeError(
            "kernel-select arms disagree: forced-sort/forced-hash/"
            "measured results must be bit-identical")

    forced_best = "hash" if hash_rps >= sort_rps else "sort"
    forced_worst_rps = min(sort_rps, hash_rps)
    # The selector's live verdict for the DOMINANT boundary: latest
    # sort-vs-hash decision per op (reselection re-decides), dominant
    # = the op probing the largest observed corpus — the map-side
    # combine that carries the e2e number. Dense-bound/ineligible
    # entries are static facts about other boundaries, not choices.
    finals = {}
    probes = []
    for d in (evidence or {}).get("decisions", ()):
        if d.get("kernel") in ("hash", "sort"):
            finals[d.get("op")] = d
        if d.get("walls_ms"):
            probes.append(d["walls_ms"])
    picked = None
    if finals:
        dom = max(finals.values(),
                  key=lambda d: d.get("max_rows")
                  or d.get("probe_rows") or 0)
        picked = dom["kernel"]
    if picked != forced_best:
        raise RuntimeError(
            f"measured selector picked {picked!r} but the forced A/B "
            f"says {forced_best} is faster "
            f"(sort {sort_rps:,.0f} vs hash {hash_rps:,.0f} rows/s)")
    note(f"kernel_select: forced-sort {sort_rps:,.0f} rows/s, "
         f"forced-hash {hash_rps:,.0f} rows/s, measured "
         f"{measured_rps:,.0f} rows/s (picked {picked}; "
         f"{measured_rps / forced_worst_rps:.2f}x vs forced-worst)")
    return {
        "measured_rps": measured_rps,
        "sort_rps": sort_rps,
        "hash_rps": hash_rps,
        "forced_best": forced_best,
        "forced_worst_rps": forced_worst_rps,
        "picked": picked,
        "probe_walls_ms": probes,
        "decisions": (evidence or {}).get("decisions", []),
        "select_counts": (evidence or {}).get("counts", {}),
    }


# ----------------------------------------------------------- reduce-wave

def _timed_waved_reduce(sess, keys, vals, num_shards: int, iters: int,
                        collect_rows: bool = False):
    """THE warm + best-of-iters protocol shared by the waved keyed-
    Reduce benches (reduce-wave and reduce-wave-2d): one warm pass for
    compile caches (and the slack memo), then ``iters`` timed runs.
    Returns (best_seconds, last_result) where result is the distinct
    row count, or the sorted result rows when ``collect_rows`` (the
    2-D A/B's parity evidence)."""
    import bigslice_tpu as bs

    def run_once():
        res = sess.run(bs.Reduce(bs.Const(num_shards, keys, vals),
                                 _add))
        if collect_rows:
            out = sorted(map(tuple, res.rows()))
        else:
            out = sum(len(f) for f in res.frames())
        res.discard()
        return out

    result = run_once()  # warm compile caches
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        result = run_once()
        times.append(time.perf_counter() - t0)
    if sess.executor.device_group_count() == 0:
        raise RuntimeError("wave reduce never engaged the device path")
    return min(times), result


def reduce_wave_bench(keys, vals, num_shards: int, iters: int = 3,
                      pipelined: bool = True):
    """Wave-streamed keyed Reduce (S >= 4×N shards on the N-device
    mesh): the beyond-HBM shape, ceil(S/N) waves per op group.

    ``pipelined=False`` pins every wave-pipeline feature off —
    prefetch_depth=0 (strictly serial staging), no buffer donation, no
    consumer-side subid pre-split — which is exactly the pre-pipeline
    executor's behavior: the BENCH_pr01 "before". ``pipelined=True``
    is the shipped default (prefetch depth 1, donated wave buffers,
    one-pass subid split so each consumer wave reads only its own
    partition's rows instead of re-scanning the full receive buffer
    W times). On a many-core host the prefetch overlap adds on top;
    on a 1-vCPU runner the split + donation carry the win (overlap
    needs a second core to stand on)."""
    from bigslice_tpu.exec.meshexec import MeshExecutor
    from bigslice_tpu.exec.session import Session

    mesh = _mesh()
    if pipelined:
        ex = MeshExecutor(mesh, prefetch_depth=1)
    else:
        ex = MeshExecutor(mesh, prefetch_depth=0,
                          donate_buffers=False, subid_split=False)
    sess = Session(executor=ex)
    best, distinct = _timed_waved_reduce(sess, keys, vals, num_shards,
                                         iters)
    # Wave-overlap accounting (utils/telemetry.py): how much of the
    # staging time the prefetch pipeline hid behind compute across the
    # whole session — recorded into BENCH json beside rows/sec so the
    # perf trajectory carries pipeline efficiency, not just throughput.
    summary = sess.telemetry_summary()
    overlap = summary.get("overlap_efficiency")
    # Device-plane rollup (utils/devicetelemetry.py): compile cost,
    # instrumented-cache hit/miss, HBM peak — recorded beside rows/sec
    # so the trajectory carries what each PR paid in compiles and
    # device memory, not just throughput.
    device = (summary.get("device") or {}).get("totals", {})
    note(f"reduce_wave[{'pipelined' if pipelined else 'serial'}]: "
         f"{distinct} distinct keys, {num_shards} shards on "
         f"{mesh.devices.size} devices, best {best*1e3:.0f} ms, "
         f"overlap efficiency "
         f"{overlap if overlap is not None else 'n/a'}, "
         f"compile {device.get('compile_s', 0)}s "
         f"({device.get('compiles', 0)} compiles / "
         f"{device.get('cache_hits', 0)} hits), "
         f"hbm peak {device.get('hbm_peak_bytes', 0)}")
    return len(keys) / best, overlap, device


# ------------------------------------------------------- reduce-wave-2d

def reduce_wave_2d_bench(keys, vals, num_shards: int, shape=None,
                         iters: int = 3):
    """Waved keyed Reduce on an explicit mesh topology: ``shape=None``
    is the flat 1-D mesh, ``shape=(D, I)`` the 2-D DCN × ICI hierarchy
    whose shuffles route through the two-stage exchange
    (parallel/hier.py). Returns (rows/sec, sorted result rows, the
    device-plane exchange totals) — the A/B caller asserts bit-parity
    between the legs and prints the measured DCN reduction."""
    import jax
    from jax.sharding import Mesh

    from bigslice_tpu.exec.meshexec import MeshExecutor
    from bigslice_tpu.exec.session import Session

    devs = np.array(jax.devices())
    if shape is None:
        mesh = Mesh(devs, ("shards",))
    else:
        d, i = shape
        if d * i != devs.size:
            raise RuntimeError(
                f"reduce-wave-2d needs a {d}x{i} device grid; got "
                f"{devs.size} devices (force a CPU mesh with "
                f"XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{d * i})"
            )
        mesh = Mesh(devs.reshape(d, i), ("dcn", "ici"))
    sess = Session(executor=MeshExecutor(mesh))
    best, rows = _timed_waved_reduce(sess, keys, vals, num_shards,
                                     iters, collect_rows=True)
    totals = (sess.telemetry_summary().get("device") or {}).get(
        "totals", {}
    )
    exchange = {
        k: totals.get(k, 0)
        for k in ("dcn_messages", "dcn_bytes", "ici_messages",
                  "ici_bytes", "flat_dcn_messages", "flat_dcn_bytes",
                  "dcn_message_reduction")
    }
    label = "1d" if shape is None else f"{shape[0]}x{shape[1]}"
    note(f"reduce_wave_2d[{label}]: best {best*1e3:.0f} ms, "
         f"dcn msgs {exchange['dcn_messages']} "
         f"(flat-equiv {exchange['flat_dcn_messages']}), "
         f"ici msgs {exchange['ici_messages']}")
    return len(keys) / best, rows, exchange


# ---------------------------------------------------- reduce-wave-spill

def reduce_wave_spill_bench(n_rows: int, iters: int = 3):
    """The out-of-core shuffle (exec/shuffleplan.py), two phases:

    **A/B (bit-parity ENFORCED)** — the same waved keyed Reduce
    (S = 4×N shards, non-dense keys) runs interleaved with
    ``BIGSLICE_SHUFFLE`` unset (today's in-program exchange) and
    ``=spill`` (every boundary through the store-mediated spill
    exchange). Raw result rows must match bit-for-bit; the ratio is
    what spilling costs when you DIDN'T need it.

    **Out-of-core** — S = 32×N shards with the spill budget set to
    ``corpus_bytes // 4``: the corpus is 4× the aggregate device
    residency the run is allowed, standing in for a dataset 4× HBM
    (on CPU meshes the budget is the honest stand-in for the
    allocator limit; on real TPU the PR-6 measured limit applies).
    ``BIGSLICE_SHUFFLE=auto`` must choose spill from the estimate,
    the run must complete, and the recorded per-wave HBM watermark
    must stay under the budget — all ASSERTED, not just printed.

    Returns a dict the run_mode entry emits."""
    import gc
    import os

    import jax

    from bigslice_tpu.exec.meshexec import MeshExecutor
    from bigslice_tpu.exec.session import Session

    ndev = max(1, len(jax.devices()))
    rng = np.random.RandomState(42)
    # ~8x key reduction: low enough that map-side combining cannot
    # hide the exchange (the out-of-core shape), high enough that the
    # result stays result-shaped rather than corpus-shaped.
    keys = rng.randint(0, max(64, n_rows >> 3), n_rows).astype(np.int32)
    vals = np.ones(n_rows, dtype=np.int32)

    def run(mode, num_shards, budget=None, collect=True):
        if mode is None:
            os.environ.pop("BIGSLICE_SHUFFLE", None)
        else:
            os.environ["BIGSLICE_SHUFFLE"] = mode
        if budget is None:
            os.environ.pop("BIGSLICE_SPILL_BUDGET_BYTES", None)
        else:
            os.environ["BIGSLICE_SPILL_BUDGET_BYTES"] = str(budget)
        sess = None
        try:
            sess = Session(executor=MeshExecutor(_mesh()))
            best, rows = _timed_waved_reduce(sess, keys, vals,
                                             num_shards, iters,
                                             collect_rows=collect)
            summary = sess.telemetry_summary()
            return len(keys) / best, rows, summary
        finally:
            if sess is not None:
                sess.shutdown()  # failure paths must not leak the
            os.environ.pop("BIGSLICE_SHUFFLE", None)  # spill temp dir
            os.environ.pop("BIGSLICE_SPILL_BUDGET_BYTES", None)

    # -- phase 1: interleaved A/B, bit-parity enforced ------------------
    S_ab = 4 * ndev
    mem_rps, mem_rows, _ = run(None, S_ab)
    spill_rps, spill_rows, s_ab = run("spill", S_ab)
    if spill_rows != mem_rows:
        raise RuntimeError(
            "spill result differs from the in-program exchange"
        )
    ab_tot = (s_ab.get("device") or {}).get("shuffle_plan", {}).get(
        "totals", {}
    )
    if not ab_tot.get("spill_boundaries"):
        raise RuntimeError("forced spill plan never engaged")
    note(f"reduce_wave_spill A/B: in-program {mem_rps:,.0f} rows/s, "
         f"spill {spill_rps:,.0f} rows/s → "
         f"{spill_rps / mem_rps:.2f}x, bit-identical "
         f"({ab_tot['spill_bytes']} spill bytes)")

    # -- phase 2: the >= 4x-budget out-of-core run -----------------------
    gc.collect()
    corpus = int(keys.nbytes + vals.nbytes)
    budget = corpus // 4
    S_ooc = 32 * ndev
    ooc_rps, _, s_ooc = run("auto", S_ooc, budget=budget,
                            collect=False)
    splan = (s_ooc.get("device") or {}).get("shuffle_plan", {})
    tot = splan.get("totals", {})
    if not tot.get("spill_boundaries"):
        raise RuntimeError(
            f"auto planner kept the in-program exchange under a "
            f"{budget}-byte budget ({splan})"
        )
    # One op entry per timed invocation (fresh #N-suffixed slices);
    # they all describe the same boundary — take the largest.
    entry = max(
        (e for e in splan["ops"].values() if e["plans"].get("spill")),
        key=lambda e: e.get("spill_bytes", 0),
    )
    if entry["reason"] != "estimate":
        raise RuntimeError(f"expected estimate-driven spill: {entry}")
    peak = tot.get("hbm_peak_bytes", 0)
    if not tot.get("within_budget"):
        raise RuntimeError(
            f"per-wave HBM watermark {peak} exceeded the "
            f"{budget}-byte budget"
        )
    note(f"reduce_wave_spill out-of-core: corpus {corpus} B = "
         f"{corpus / budget:.1f}x the {budget} B budget; "
         f"{ooc_rps:,.0f} rows/s over {entry['map_waves']} map waves "
         f"→ {entry['sub_waves']} reduce sub-waves, "
         f"{entry['spill_bytes']} B spilled across "
         f"{entry['partitions']} partitions, hbm peak {peak} B "
         f"(within budget)")
    return {
        "inmem_rps": mem_rps,
        "spill_rps": spill_rps,
        "ooc_rps": ooc_rps,
        "corpus_bytes": corpus,
        "budget_bytes": budget,
        "hbm_peak_bytes": peak,
        "within_budget": True,
        "spill_bytes": entry["spill_bytes"],
        "partitions": entry["partitions"],
        "map_waves": entry["map_waves"],
        "sub_waves": entry["sub_waves"],
        "est_bytes": entry["est_bytes"],
    }


# ------------------------------------------------- reduce-wave-adaptive

def _bump(k, v):
    """Row-local consumer map for the adaptive skew A/B (module-level:
    stable fn identity across legs, like ``_add``)."""
    return (k, v + 0)


def reduce_wave_adaptive_bench(n_rows: int, slow_s: float = 0.5,
                               slow_count: int = 2):
    """The adaptive-execution A/B (exec/adaptive.py), two phases:

    **Speculation under slow-host chaos (ASSERTED)** — the same keyed
    Reduce runs with ``BIGSLICE_ADAPTIVE=off`` and ``=all`` under an
    identical fixed-seed fault plan that makes the first
    ``slow_count`` store reads sleep ``slow_s``–``2*slow_s`` seconds
    (a deterministic slow host, utils/faultinject.py ``~slow``).
    Results must be value-identical; with ``all`` the straggler
    watcher must race duplicates (launched >= 1, won >= 1) and both
    the p99 completed-task duration AND the e2e wall-clock must come
    in BELOW the ``off`` leg — the acceptance criteria, asserted not
    printed. The phase runs a small fixed corpus so the injected
    sleeps, not per-row work, dominate the tail.

    **Hot-shard splitting (parity ASSERTED)** — a skewed-key waved
    pipeline (one hub partition carrying most rows) runs on the mesh
    executor ``off`` vs ``all``: the flagged consumer wave must split
    into row-slices (skew_splits >= 1) and re-merge value-identical.
    Timing is reported, not asserted: on a CPU mesh the split's win is
    tail-latency on real multi-host fleets, not local throughput.

    Returns the dict the run_mode entry emits."""
    import os

    import bigslice_tpu as bs
    from bigslice_tpu.exec.local import LocalExecutor
    from bigslice_tpu.exec.meshexec import MeshExecutor
    from bigslice_tpu.exec.session import Session
    from bigslice_tpu.utils import faultinject
    from bigslice_tpu.utils.telemetry import quantile

    env_keys = ("BIGSLICE_ADAPTIVE", "BIGSLICE_ADAPTIVE_POLL_S",
                "BIGSLICE_CHAOS_SLOW_S")
    prev = {k: os.environ.get(k) for k in env_keys}

    def set_env(mode):
        os.environ["BIGSLICE_ADAPTIVE"] = mode
        os.environ["BIGSLICE_ADAPTIVE_POLL_S"] = "0.005"
        os.environ["BIGSLICE_CHAOS_SLOW_S"] = str(slow_s)

    def restore_env():
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    # -- phase 1: speculative duplicates vs a deterministic slow host --
    spec_rows = 16000
    rng = np.random.RandomState(3)
    keys1 = rng.randint(0, 199, spec_rows).astype(np.int32)
    vals1 = np.ones(spec_rows, np.int32)
    plan_spec = f"11:store.read=1.0x{slow_count}~slow"

    def spec_leg(mode):
        set_env(mode)
        sess = None
        try:
            sess = Session(executor=LocalExecutor(procs=4))
            # Bench-scale straggler thresholds: flag a RUNNING task
            # 1.5x beyond 2 finished siblings (the knobs exist for
            # exactly this — production defaults assume minutes-long
            # tasks).
            sess.telemetry.straggler_factor = 1.5
            sess.telemetry.straggler_min_secs = 0.05
            sess.telemetry.straggler_min_siblings = 2
            r = bs.Reduce(bs.Const(8, keys1, vals1), _add)
            res = sess.run(r)          # chaos-free warm: page-in, no
            rows = sorted(res.rows())  # fault budget spent
            res.discard()
            faultinject.install(faultinject.parse_plan(plan_spec))
            try:
                t0 = time.perf_counter()
                res = sess.run(bs.Reduce(bs.Const(8, keys1, vals1),
                                         _add))
                rows = sorted(res.rows())
                wall = time.perf_counter() - t0
                res.discard()
            finally:
                faultinject.clear()
            spec = {"launched": 0, "won": 0, "wasted": 0}
            if sess.adaptive is not None:
                st = sess.adaptive.stats
                # Attribution settles when the losing original
                # finishes its injected sleep; wait for it.
                deadline = time.monotonic() + 10.0
                while time.monotonic() < deadline:
                    if (st.speculative_won + st.speculative_wasted
                            >= st.speculative_launched
                            and st.speculative_launched >= 1):
                        break
                    time.sleep(0.02)
                spec = {"launched": st.speculative_launched,
                        "won": st.speculative_won,
                        "wasted": st.speculative_wasted}
            ds = sess.telemetry.task_durations()
            p99 = quantile(ds, 0.99) if ds else 0.0
            return rows, wall, p99, spec
        finally:
            if sess is not None:
                sess.shutdown()
            restore_env()

    off_rows, off_wall, off_p99, _ = spec_leg("off")
    all_rows, all_wall, all_p99, spec = spec_leg("all")
    if all_rows != off_rows:
        raise RuntimeError(
            "adaptive=all result differs from adaptive=off"
        )
    if spec["launched"] < 1 or spec["won"] < 1:
        raise RuntimeError(
            f"speculation never engaged/won under slow chaos: {spec}"
        )
    if not (all_p99 < off_p99 and all_wall < off_wall):
        raise RuntimeError(
            f"adaptive leg did not beat the tail: p99 {all_p99:.3f}s "
            f"vs {off_p99:.3f}s, wall {all_wall:.3f}s vs "
            f"{off_wall:.3f}s"
        )
    note(f"reduce_wave_adaptive spec: off wall {off_wall:.2f}s "
         f"p99 {off_p99:.2f}s; all wall {all_wall:.2f}s "
         f"p99 {all_p99:.2f}s ({spec['launched']} raced, "
         f"{spec['won']} won, {spec['wasted']} wasted), "
         f"value-identical")

    # -- phase 2: hot-shard splitting on the mesh, parity enforced ----
    rng = np.random.RandomState(7)
    keys2 = np.where(rng.rand(n_rows) < 0.6, 0,
                     rng.randint(0, 1 << 10, n_rows)).astype(np.int32)
    vals2 = np.ones(n_rows, np.int32)

    def skew_leg(mode):
        set_env(mode)
        sess = None
        try:
            sess = Session(executor=MeshExecutor(_mesh()))

            def run_once():
                r = bs.Reduce(
                    bs.Map(bs.Reshuffle(bs.Const(8, keys2, vals2)),
                           _bump),
                    _add,
                )
                res = sess.run(r)
                out = sorted(map(tuple, res.rows()))
                res.discard()
                return out

            run_once()  # warm compile caches
            t0 = time.perf_counter()
            rows = run_once()
            wall = time.perf_counter() - t0
            splits = (sess.adaptive.stats.skew_splits
                      if sess.adaptive is not None else 0)
            if sess.executor.device_group_count() == 0:
                raise RuntimeError(
                    "adaptive skew bench never engaged the device path"
                )
            return rows, wall, splits
        finally:
            if sess is not None:
                sess.shutdown()
            restore_env()

    base_rows, base_wall, _ = skew_leg("off")
    split_rows, split_wall, splits = skew_leg("all")
    if split_rows != base_rows:
        raise RuntimeError(
            "skew-split result differs from the unsplit wave"
        )
    if splits < 1:
        raise RuntimeError("hot-shard split never engaged")
    note(f"reduce_wave_adaptive skew: {splits} hot-wave splits, "
         f"off {n_rows/base_wall:,.0f} rows/s, all "
         f"{n_rows/split_wall:,.0f} rows/s, value-identical")

    return {
        "off_rps": spec_rows / off_wall,
        "all_rps": spec_rows / all_wall,
        "off_wall_s": off_wall,
        "all_wall_s": all_wall,
        "off_p99_s": off_p99,
        "all_p99_s": all_p99,
        "speculative": spec,
        "skew_splits": splits,
        "skew_off_rps": n_rows / base_wall,
        "skew_all_rps": n_rows / split_wall,
    }


# --------------------------------------------------- reduce-wave-coded

def reduce_wave_coded_bench(n_rows: int, slow_s: float = 1.2):
    """The coded k-of-n straggler-tolerance A/B (exec/codedplan.py),
    three arms under an IDENTICAL fixed-seed fault plan that makes the
    first map-side task sleep ``slow_s``–``2*slow_s`` seconds
    (utils/faultinject.py ``task.run`` ``~slow`` — a deterministic
    slow host):

    - **off**: the baseline pays the straggler in full — its wall is
      bounded BELOW by the injected sleep.
    - **spec** (reactive): the straggler watcher detects the slow task
      after the fact and races a duplicate; the duplicate wins, but
      only after the detection latency already elapsed.
    - **coded** (proactive, spec policy STILL ARMED): the planner
      over-decomposed the combine boundary into n = k + r members
      before anything ran; coverage settles on the k fastest, the
      sleeper is cooperatively cancelled, and ZERO speculative
      duplicates dispatch — redundancy was pre-paid, not raced.

    Asserted, not printed: all three arms value-identical; spec
    launched >= 1 and won >= 1; coded covered with launched == 0; and
    the coded wall at least 2x better than off (the k-th-slowest
    bound vs the straggler-bound baseline)."""
    import os

    import bigslice_tpu as bs
    from bigslice_tpu.exec.local import LocalExecutor
    from bigslice_tpu.exec.session import Session
    from bigslice_tpu.utils import faultinject

    env_keys = ("BIGSLICE_ADAPTIVE", "BIGSLICE_ADAPTIVE_POLL_S",
                "BIGSLICE_CHAOS_SLOW_S", "BIGSLICE_CODED",
                "BIGSLICE_CODED_REDUNDANCY")
    prev = {k: os.environ.get(k) for k in env_keys}

    def restore_env():
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    rng = np.random.RandomState(3)
    keys = rng.randint(0, 199, n_rows).astype(np.int32)
    vals = np.ones(n_rows, np.int32)
    plan_spec = "11:task.run=1.0x1~slow"

    def leg(adaptive, coded):
        os.environ["BIGSLICE_ADAPTIVE"] = adaptive
        os.environ["BIGSLICE_ADAPTIVE_POLL_S"] = "0.005"
        os.environ["BIGSLICE_CHAOS_SLOW_S"] = str(slow_s)
        if coded:
            os.environ["BIGSLICE_CODED"] = "combine"
        else:
            os.environ.pop("BIGSLICE_CODED", None)
        sess = None
        try:
            sess = Session(executor=LocalExecutor(procs=4))
            # Detection floor at a quarter of the injected sleep:
            # the 1.2s+ sleeper is flagged, honest sub-0.3s shards
            # never are — both reactive arms see the same signal.
            sess.telemetry.straggler_factor = 1.5
            sess.telemetry.straggler_min_secs = slow_s / 4.0
            sess.telemetry.straggler_min_siblings = 2
            res = sess.run(bs.Reduce(bs.Const(8, keys, vals), _add))
            rows = sorted(res.rows())  # chaos-free warm
            res.discard()
            faultinject.install(faultinject.parse_plan(plan_spec))
            try:
                t0 = time.perf_counter()
                res = sess.run(bs.Reduce(bs.Const(8, keys, vals),
                                         _add))
                rows = sorted(res.rows())
                wall = time.perf_counter() - t0
            finally:
                faultinject.clear()
            # Settle before teardown: cancelled/raced stragglers may
            # still be draining their current frame on worker threads;
            # the wall above is already measured, but exiting the
            # process mid-native-op aborts the runtime.
            from bigslice_tpu.exec.task import TaskState, iter_tasks

            settle = time.monotonic() + 2 * slow_s + 5.0
            while time.monotonic() < settle and any(
                    t.state == TaskState.RUNNING
                    for t in iter_tasks(res.tasks)):
                time.sleep(0.02)
            res.discard()
            spec = {"launched": 0, "won": 0, "wasted": 0}
            if sess.adaptive is not None:
                st = sess.adaptive.stats
                deadline = time.monotonic() + 10.0
                while time.monotonic() < deadline:
                    if (st.speculative_won + st.speculative_wasted
                            >= st.speculative_launched):
                        break
                    time.sleep(0.02)
                spec = {"launched": st.speculative_launched,
                        "won": st.speculative_won,
                        "wasted": st.speculative_wasted}
            cd = sess.telemetry.coded
            coded_counts = (dict(cd.summary()["counts"])
                            if cd is not None else {})
            return rows, wall, spec, coded_counts
        finally:
            if sess is not None:
                sess.shutdown()
            restore_env()

    off_rows, off_wall, _, off_coded = leg("off", coded=False)
    spec_rows_, spec_wall, spec, _ = leg("spec", coded=False)
    coded_rows, coded_wall, coded_spec, coded_counts = leg(
        "spec", coded=True)

    if spec_rows_ != off_rows or coded_rows != off_rows:
        raise RuntimeError("coded A/B arms are not value-identical")
    if off_coded:
        raise RuntimeError(
            f"chicken bit leaked: off arm has coded events {off_coded}"
        )
    if off_wall < slow_s:
        raise RuntimeError(
            f"off arm finished below the injected sleep "
            f"({off_wall:.3f}s < {slow_s}s) — the fault never landed"
        )
    if spec["launched"] < 1 or spec["won"] < 1:
        raise RuntimeError(
            f"speculation never engaged/won in the spec arm: {spec}"
        )
    if coded_spec["launched"] != 0:
        raise RuntimeError(
            f"coded arm dispatched speculative duplicates: "
            f"{coded_spec} — redundancy is pre-paid, racing it "
            f"double-spends"
        )
    if coded_counts.get("covered", 0) < 1:
        raise RuntimeError(
            f"coded arm never settled coverage: {coded_counts}"
        )
    if not coded_wall * 2 <= off_wall:
        raise RuntimeError(
            f"coded wall not >=2x better than off: {coded_wall:.3f}s "
            f"vs {off_wall:.3f}s"
        )
    note(f"reduce_wave_coded: off {off_wall:.2f}s, spec "
         f"{spec_wall:.2f}s ({spec['launched']} raced, {spec['won']} "
         f"won), coded {coded_wall:.2f}s "
         f"(covered, {coded_counts.get('cancelled', 0)} cancelled, "
         f"0 raced), value-identical x3")

    return {
        "off_wall_s": off_wall,
        "spec_wall_s": spec_wall,
        "coded_wall_s": coded_wall,
        "off_rps": n_rows / off_wall,
        "spec_rps": n_rows / spec_wall,
        "coded_rps": n_rows / coded_wall,
        "speculative": spec,
        "coded_counts": coded_counts,
    }


# ------------------------------------------------------------- staging

def staging_bench(n_rows: int, dim: int = 16, iters: int = 7):
    """Staging fast-path microbench (one wave's worth of shard I/O):
    stage N per-shard codec streams into global padded device columns.

    Legacy chain: BSF3 ``np.load`` decode (a copy per column per
    frame) → ``Frame.concat`` (another copy) → per-shard pad concat +
    global concat + a ``device_put`` per column. Fast path: BSF4
    zero-copy view decode → arena two-pass assembly (ONE copy per
    column, into a reused buffer) → one batched ``device_put``.
    Same bytes, same result layout; rows/sec per full stage."""
    import jax

    from bigslice_tpu.exec import staging as staging_mod
    from bigslice_tpu.frame import codec
    from bigslice_tpu.frame.frame import Frame
    from bigslice_tpu.parallel import shuffle as shuffle_mod
    from bigslice_tpu.parallel.jitutil import bucket_size

    mesh = _mesh()
    n = mesh.devices.size
    per = max(1, n_rows // n)
    frame_rows = 8192
    rng = np.random.RandomState(13)
    legacy_blobs, fast_blobs = [], []
    for s in range(n):
        keys = rng.randint(0, 4096, per).astype(np.int32)
        vals = rng.rand(per, dim).astype(np.float32)
        legacy = fast = b""
        for i in range(0, per, frame_rows):
            f = Frame([keys[i : i + frame_rows], vals[i : i + frame_rows]])
            legacy += codec.encode_frame_v3(f)
            fast += codec.encode_frame(f)
        legacy_blobs.append(legacy)
        fast_blobs.append(fast)
    nbytes = sum(len(b) for b in fast_blobs)
    arena = staging_mod.StagingArena(enabled=True)

    def stage_legacy():
        frames = [Frame.concat(list(codec.read_frames(b)))
                  for b in legacy_blobs]
        counts = [len(f) for f in frames]
        capacity = bucket_size(max(counts + [1]))
        per_shard_cols = [[f.cols[j] for f in frames]
                          for j in range(frames[0].num_cols)]
        cols, cnt = shuffle_mod.shard_columns(
            mesh, per_shard_cols, counts, capacity
        )
        jax.block_until_ready(list(cols) + [cnt])

    arena.mode = staging_mod.staging_mode(mesh)
    note(f"staging arena mode: {arena.mode}")

    def stage_fast():
        # Two-pass: header-only scan pins the exact row counts (and so
        # the bucketed capacity) before any payload bytes move.
        total = sum(ext.nrows for b in fast_blobs
                    for ext in codec.scan_frames(b))
        assert total == n * per
        lists = [list(codec.read_frames(b)) for b in fast_blobs]
        host_cols, counts, capacity, bufs = staging_mod.assemble(
            lists, None, n, arena
        )
        cols, cnt = shuffle_mod.place_global_columns(
            mesh, host_cols, counts
        )
        jax.block_until_ready(list(cols) + [cnt])
        arena.release(bufs)

    out = {}
    for name, fn in (("legacy", stage_legacy), ("fast", stage_fast)):
        fn()  # warm (compile nothing; page in)
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t0)
        best = min(times)
        out[name] = (n * per) / best
        note(f"staging[{name}]: {n * per} rows / {nbytes / 1e6:.1f} MB "
             f"in {best * 1e3:.1f} ms → {out[name]:,.0f} rows/s")
    return out["fast"], out["legacy"]


# ------------------------------------------- reduce-wave, file-staged

def reduce_wave_staged_bench(n_rows: int, dim: int = 16,
                             rounds: int = 3):
    """The serving-shape waved Reduce: shard input staged from encoded
    per-shard stream FILES (doc.go's serverless sharded evaluation —
    shard I/O must keep up with the device), dense int32 keys so the
    device lowering is fast and staging is the exposed cost, and a
    ``dim``-wide float32 vector payload per row (feature/embedding
    aggregation).

    Measures two configs INTERLEAVED (drift on a shared host must not
    masquerade as a staging delta), best-of per config:

    - ``legacy``: the PR-2 staging path — BSF3-encoded corpus (np.load
      decode copies), BIGSLICE_STAGING_ARENA-off executor
      (Frame.concat + pad-concat + per-column puts).
    - ``fast``: the shipped defaults — BSF4 zero-copy decode, arena
      assembly, batched transfer.

    Returns {name: (rows_per_sec, overlap_efficiency, breakdown)}."""
    import shutil
    import tempfile

    import bigslice_tpu as bs
    from bigslice_tpu.exec.meshexec import MeshExecutor
    from bigslice_tpu.exec.session import Session
    from bigslice_tpu.frame import codec
    from bigslice_tpu.frame.frame import Frame
    from bigslice_tpu.slicetype import ColType, Schema

    mesh = _mesh()
    S = 4 * max(1, int(mesh.devices.size))
    per = max(1, n_rows // S)
    total_rows = S * per
    schema = Schema([ColType(np.dtype(np.int32), "", ()),
                     ColType(np.dtype(np.float32), "", (dim,))], 1)

    def corpus(encode, d):
        rng = np.random.RandomState(17)
        for s in range(S):
            keys = rng.randint(0, 4096, per).astype(np.int32)
            vals = rng.rand(per, dim).astype(np.float32)
            with open(f"{d}/{s}", "wb") as fp:
                for i in range(0, per, 8192):
                    fp.write(encode(Frame([keys[i : i + 8192],
                                           vals[i : i + 8192]])))

    def reader_for(d):
        def read_shard(shard):
            with open(f"{d}/{shard}", "rb") as fp:
                data = fp.read()
            yield from codec.read_frames(data)

        return read_shard

    dirs = []
    try:
        sessions = {}
        for name, encode, arena in (
                ("legacy", codec.encode_frame_v3, False),
                ("fast", codec.encode_frame, True)):
            d = tempfile.mkdtemp(prefix=f"bs-stagebench-{name}-")
            dirs.append(d)
            corpus(encode, d)
            sessions[name] = (
                Session(executor=MeshExecutor(
                    mesh, prefetch_depth=1, staging_arena=arena
                )),
                reader_for(d),
            )

        def run_once(name):
            sess, read_shard = sessions[name]
            r = bs.Reduce(bs.ReaderFunc(S, read_shard, out=schema),
                          _add)
            res = sess.run(r)
            total = 0
            for f in res.frames():
                total += len(f)
            res.discard()
            return total

        distinct = {name: run_once(name) for name in sessions}  # warm
        best = {name: float("inf") for name in sessions}
        for _ in range(rounds):
            for name in sessions:
                t0 = time.perf_counter()
                run_once(name)
                best[name] = min(best[name],
                                 time.perf_counter() - t0)
        out = {}
        for name, (sess, _) in sessions.items():
            if sess.executor.device_group_count() == 0:
                raise RuntimeError(
                    "staged wave reduce never engaged the device path"
                )
            summary = sess.telemetry_summary()
            overlap = summary.get("overlap_efficiency")
            breakdown = {}
            for entry in summary["ops"].values():
                for k, v in entry.get("waves", {}).get(
                        "staging_breakdown", {}).items():
                    breakdown[k] = round(breakdown.get(k, 0.0) + v, 6)
            note(f"reduce_wave_staged[{name}]: {distinct[name]} keys, "
                 f"{S} file shards x {per} rows (payload dim {dim}), "
                 f"best {best[name] * 1e3:.0f} ms, overlap {overlap}, "
                 f"breakdown {breakdown}")
            out[name] = (total_rows / best[name], overlap, breakdown)
        return out
    finally:
        for d in dirs:
            shutil.rmtree(d, ignore_errors=True)


# ------------------------------------------------------------- serve-qps

# Module-level pipeline state: the serve-qps bench registers ONE
# pipeline whose slice builder (and combine fn) keep stable identity
# and stable op site across sessions — the cross-Session program
# cache keys on exactly that (op site + structure + fn content).
_QPS_DATA = {}


def _qps_pipeline():
    import bigslice_tpu as bs

    d = _QPS_DATA
    return bs.Reduce(bs.Const(d["shards"], d["keys"], d["vals"]),
                     _add)


def serve_qps_bench(n_rows: int, seconds: float = 8.0,
                    concurrency: int = 8, slots: int = 2):
    """Sustained serving load against a live ServeServer (the
    'heavy traffic' number): one resident server process, a waved
    keyed-Reduce pipeline, measured over three phases —

    1. **cold**: first invocation on a fresh process (pays every XLA
       compile) on Session 1;
    2. **warm-first**: the server swaps onto a FRESH Session 2 (same
       process) and serves the same pipeline — the cross-Session
       program cache must hand back every executable, so this request
       performs **zero XLA compiles** (asserted from Session 2's
       device telemetry; the acceptance criterion);
    3. **sustained**: ``concurrency`` closed-loop HTTP clients (4
       tenants) fire for ``seconds`` — QPS, p50/p99 latency, rows/sec,
       shed count.

    Returns the result dict the serve-qps JSON line carries."""
    import json as json_mod
    import threading
    import urllib.request

    import jax

    from bigslice_tpu.exec.meshexec import MeshExecutor
    from bigslice_tpu.exec.session import Session
    from bigslice_tpu.serve.programcache import global_program_cache
    from bigslice_tpu.serve.server import ServeServer

    mesh = _mesh()
    n = mesh.devices.size
    S = 2 * max(1, int(n))  # waved: 2 waves per group
    rng = np.random.RandomState(42)
    _QPS_DATA.update(
        shards=S,
        keys=rng.randint(0, 1 << 12, n_rows).astype(np.int32),
        vals=np.ones(n_rows, dtype=np.int32),
    )

    sess1 = Session(executor=MeshExecutor(mesh))
    server = ServeServer(sess1, port=0, slots=slots,
                         queue_depth=max(64, 4 * concurrency))
    server.register("qps", _qps_pipeline,
                    description="waved keyed Reduce (serve-qps)")

    def invoke(tenant="bench", want_rows=False, timeout=300):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/serve/invoke",
            data=json_mod.dumps({
                "pipeline": "qps", "tenant": tenant,
                "rows": want_rows,
            }).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json_mod.loads(r.read())

    # Phase 1 — cold: the fresh process pays the compiles.
    cold = invoke()
    cold_s = cold["latency_s"]
    t1 = (sess1.telemetry_summary().get("device") or {}).get(
        "totals", {})
    note(f"serve_qps cold: {cold_s * 1e3:.0f} ms "
         f"({t1.get('compiles', 0)} XLA compiles, "
         f"{t1.get('compile_s', 0)}s compile)")

    # Phase 2 — fresh Session, same server process: the program cache
    # must make this request compile-free.
    pc0 = global_program_cache().stats()
    sess2 = Session(executor=MeshExecutor(_mesh()))
    server.attach_session(sess2)
    sess1.shutdown()
    warm = invoke()
    warm_first_s = warm["latency_s"]
    t2 = (sess2.telemetry_summary().get("device") or {}).get(
        "totals", {})
    pc1 = global_program_cache().stats()
    cache_hits = pc1["hits"] - pc0["hits"]
    if t2.get("fallbacks", 0):
        raise RuntimeError(
            f"AOT fallback during warm phase — compile accounting "
            f"blind: {t2}"
        )
    if t2.get("compiles", 1) != 0 or cache_hits < 1:
        raise RuntimeError(
            f"fresh session was not compile-free: compiles="
            f"{t2.get('compiles')} program-cache hits={cache_hits}"
        )
    note(f"serve_qps warm-first (fresh Session): "
         f"{warm_first_s * 1e3:.0f} ms, 0 XLA compiles, "
         f"{cache_hits} program-cache hits, "
         f"{pc1['compile_s_saved'] - pc0['compile_s_saved']:.2f}s "
         f"compile saved")

    # Warm pass for the sustained phase (page in each client tenant).
    invoke(tenant="t0")

    # Phase 3 — sustained closed-loop load.
    latencies = []
    errors = []
    lat_lock = threading.Lock()
    stop_at = time.perf_counter() + seconds

    def client(i):
        tenant = f"t{i % 4}"
        while time.perf_counter() < stop_at:
            t0 = time.perf_counter()
            try:
                invoke(tenant=tenant)
            except Exception as e:  # noqa: BLE001
                with lat_lock:
                    errors.append(repr(e))
                return
            with lat_lock:
                latencies.append(time.perf_counter() - t0)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(concurrency)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t_start
    if errors:
        raise RuntimeError(f"serve_qps client errors: {errors[:3]}")
    if not latencies:
        raise RuntimeError("serve_qps: no requests completed")
    ls = sorted(latencies)
    # The server's own quantile helper: the bench's p50/p99 must agree
    # with the self-reported /serve/stats quantiles by construction.
    from bigslice_tpu.serve.server import _quantile

    def q(p):
        return _quantile(ls, p)

    stats = server.serving_stats()
    pc = stats["program_cache"]
    out = {
        "qps": len(ls) / elapsed,
        "requests": len(ls),
        "duration_s": round(elapsed, 3),
        "concurrency": concurrency,
        "slots": slots,
        "rows_per_sec": n_rows * len(ls) / elapsed,
        "p50_ms": round(q(0.5) * 1e3, 3),
        "p99_ms": round(q(0.99) * 1e3, 3),
        "cold_first_ms": round(cold_s * 1e3, 3),
        "warm_first_ms": round(warm_first_s * 1e3, 3),
        "warm_vs_cold": round(cold_s / warm_first_s, 3),
        "fresh_session_compiles": t2.get("compiles", 0),
        "fresh_session_cache_hits": cache_hits,
        "program_cache_hit_rate": pc.get("hit_rate"),
        "program_cache": {k: pc.get(k) for k in
                          ("hits", "misses", "entries", "evictions",
                           "compile_s_saved")},
        "shed": stats["totals"].get("shed", 0),
    }
    note(f"serve_qps sustained: {out['qps']:.2f} req/s x {n_rows} "
         f"rows ({out['rows_per_sec']:,.0f} rows/s), p50 "
         f"{out['p50_ms']:.0f} ms p99 {out['p99_ms']:.0f} ms, "
         f"{out['shed']} shed, program-cache hit rate "
         f"{out['program_cache_hit_rate']}")
    sess2.shutdown()  # drains the server (final snapshot on stderr)
    return out


# ------------------------------------------------------------------ join

def join_key_space(n_rows: int) -> int:
    return max(16, n_rows // 16)


def join_inputs(n_rows: int):
    """The join benches' synthetic two-sided keyed input — ONE
    derivation shared by the bench bodies, main(), and tools_bench_all
    so the measured workload and its CPU baseline can't drift apart."""
    nk = join_key_space(n_rows)
    r1, r2 = np.random.RandomState(1), np.random.RandomState(2)
    return (r1.randint(0, nk, n_rows).astype(np.int32),
            r2.randint(0, nk, n_rows).astype(np.int32))


def cpu_join_baseline(ak, bk) -> float:
    """rows/sec for the single-core numpy equivalent of the measured
    JoinAggregate(add, add) over unit values: aggregate each side by
    key, inner-join the key sets, and gather both sides' aggregates
    for every matched key — the same (key, agg_a, agg_b) output the
    framework produces (the previous baseline stopped at the key
    intersection, under-counting the baseline's work)."""
    t0 = time.perf_counter()
    ka, ca = np.unique(ak, return_counts=True)
    kb, cb = np.unique(bk, return_counts=True)
    common, pa, pb = np.intersect1d(ka, kb, assume_unique=True,
                                    return_indices=True)
    _ = (common, ca[pa], cb[pb])
    return (len(ak) + len(bk)) / (time.perf_counter() - t0)


def join_kernel_bench(n_rows: int, iters: int = 3):
    import jax

    from bigslice_tpu.parallel import join as join_mod
    from bigslice_tpu.parallel import shuffle as shuffle_mod

    mesh = _mesh()
    n = mesh.devices.size
    per = n_rows // n
    nkeys = max(16, n_rows // 16)

    def side(seed):
        r = np.random.RandomState(seed)
        kc = [r.randint(0, nkeys, per).astype(np.int32)
              for _ in range(n)]
        vc = [np.ones(per, np.int32) for _ in range(n)]
        return shuffle_mod.shard_columns(mesh, [kc, vc], [per] * n, per)

    a_cols, a_counts = side(1)
    b_cols, b_counts = side(2)
    j = join_mod.MeshJoinAggregate(mesh, per, _add, _add)

    def run_once():
        out = j(a_cols, a_counts, b_cols, b_counts)
        jax.block_until_ready(out[0])
        return out

    out = run_once()  # warm
    if int(np.asarray(out[4])) != 0:
        note("warning: join overflow — throughput excludes dropped rows")
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        run_once()
        times.append(time.perf_counter() - t0)
    return (2 * n * per) / min(times)


def join_e2e_bench(n_rows: int, iters: int = 3, dense: bool = False):
    """Config #3 end-to-end: JoinAggregate through the Session — the
    BASELINE 'Reduce+Cogroup join' headline, host rows in, scan out.
    ``dense`` declares the key space (keys ARE dense in this workload)
    and takes the sort-free table join."""
    import bigslice_tpu as bs

    mesh = _mesh()
    sess = _mesh_session(mesh)
    n = mesh.devices.size
    ak, bk = join_inputs(n_rows)
    ones = np.ones(n_rows, np.int32)
    dense_k = join_key_space(n_rows) if dense else None

    def run_once():
        j = bs.JoinAggregate(
            bs.Const(n, ak, ones), bs.Const(n, bk, ones), _add, _add,
            dense_keys=dense_k,
        )
        res = sess.run(j)
        total = 0
        for f in res.frames():
            total += len(f)
        res.discard()
        return total

    run_once()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        matched = run_once()
        times.append(time.perf_counter() - t0)
    if sess.executor.device_group_count() == 0:
        raise RuntimeError("e2e join never engaged the device path")
    best = min(times)
    note(f"join_e2e: {matched} matched keys, device groups "
         f"{sess.executor.device_group_count()}")
    return 2 * n_rows / best


# ------------------------------------------------------------- wordcount

def _synth_urls(n_rows: int):
    """Zipf-distributed synthetic URL corpus (cmd/urls workload shape)."""
    rng = np.random.RandomState(7)
    doms = (rng.zipf(1.5, n_rows) % 5000).astype(np.int64)
    return [f"http://site{d}.example.com/p/{i & 1023}"
            for i, d in enumerate(doms.tolist())]


def cpu_wordcount_baseline(lines) -> float:
    """Host dict count over parsed domains — what a tuned single-core
    Python/bigslice-local run of cmd/urls does."""
    from collections import Counter

    from bigslice_tpu.models.urls import _domain

    t0 = time.perf_counter()
    Counter(_domain(u) for u in lines)
    return len(lines) / (time.perf_counter() - t0)


def wordcount_bench(n_rows: int, iters: int = 2):
    """Config #2 (cmd/urls): ReaderFunc → host Map(parse) → dict-encode
    → device Reduce, via models/urls.domain_count_encoded — the full
    two-tier pipeline, host parsing included. One session across
    iterations (the iterative-driver steady state, like the other e2e
    modes — a fresh executor per round would recompile every SPMD
    program)."""
    from bigslice_tpu.models.urls import domain_count_encoded

    lines = _synth_urls(n_rows)
    mesh = _mesh()
    sess = _mesh_session(mesh)
    n = mesh.devices.size

    def run_once():
        # Sequence source: shards stripe by random access instead of
        # each re-scanning the whole generator (ops/source.py).
        return len(domain_count_encoded(sess, n, lines))

    run_once()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        distinct = run_once()
        times.append(time.perf_counter() - t0)
    if sess.executor.device_group_count() == 0:
        raise RuntimeError("wordcount never engaged the device path")
    note(f"wordcount: {distinct} distinct domains, device groups "
         f"{sess.executor.device_group_count()}")
    return len(lines) / min(times), cpu_wordcount_baseline(lines)


# ----------------------------------------------------------- sortshuffle

def cpu_sortshuffle_baseline(keys: np.ndarray) -> float:
    t0 = time.perf_counter()
    np.sort(keys, kind="stable")
    return len(keys) / (time.perf_counter() - t0)


def sortshuffle_bench(n_rows: int, iters: int = 3):
    """Config #4: Reshuffle + sorted scan — rows hash-route to their
    partition, each partition sorts on device (sortio in-run device
    sort via Frame.sorted_by_key)."""
    import bigslice_tpu as bs

    rng = np.random.RandomState(11)
    keys = rng.randint(0, 1 << 30, n_rows).astype(np.int32)
    mesh = _mesh()
    sess = _mesh_session(mesh)
    n = mesh.devices.size

    def run_once():
        shuf = bs.Reshuffle(bs.Const(n, keys))
        res = sess.run(shuf)
        total = 0
        for shard in range(res.num_shards):
            for f in res.reader(shard, ()):
                total += len(f.sorted_by_key())
        res.discard()
        return total

    assert run_once() == n_rows
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        run_once()
        times.append(time.perf_counter() - t0)
    if sess.executor.device_group_count() == 0:
        raise RuntimeError("sortshuffle never engaged the device path")
    return n_rows / min(times), cpu_sortshuffle_baseline(keys)


# --------------------------------------------------------------- cogroup

def cogroup_bench(n_rows: int, n_keys: int = 1 << 12, iters: int = 2):
    """The general ragged Cogroup: device lowering (one tagged sort +
    rank-scatter with discovered capacity, parallel/cogroup.py) vs the
    host sorted-merge tier on the same pipeline — the cogroup.go:46-272
    workhorse, beyond the aggregating-join config #3."""
    import bigslice_tpu as bs
    from bigslice_tpu.exec.session import Session

    rng = np.random.RandomState(13)
    keys = rng.randint(0, n_keys, n_rows).astype(np.int32)
    vals = rng.randint(0, 1 << 20, n_rows).astype(np.int32)
    mesh = _mesh()
    sess = _mesh_session(mesh)
    n = mesh.devices.size

    def run_once(s):
        res = s.run(bs.Cogroup(bs.Const(n, keys, vals)))
        groups = 0
        rows = 0
        for f in res.frames():
            groups += len(f)
            for g in f.to_host().cols[1]:
                rows += len(g)
        res.discard()
        # No silent row loss: discovered capacity must never truncate.
        assert rows == n_rows, (rows, n_rows)
        return groups

    groups = run_once(sess)
    note(f"cogroup: {groups} groups from {n_rows} rows, device "
         f"groups {sess.executor.device_group_count()}")
    if sess.executor.device_group_count() == 0:
        raise RuntimeError("cogroup never engaged the device path")
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        run_once(sess)
        times.append(time.perf_counter() - t0)

    host_sess = Session()  # the exact sorted-merge tier as baseline
    t0 = time.perf_counter()
    run_once(host_sess)
    host_dt = time.perf_counter() - t0
    return n_rows / min(times), n_rows / host_dt


# ---------------------------------------------------------------- kmeans

def kmeans_bench(n_points: int, d: int, k: int, rounds: int = 3,
                 fallback: bool = False):
    """Config #5: iterative k-means *through the framework* — repeated
    sess.run of Map(assign, centroids as unbatched arg) + Reduce over a
    reused Result (models/kmeans.kmeans; the exec/compile.go:226
    Result-reuse pattern). Also notes the raw jitted-step TFLOP/s (the
    MXU roofline the framework path is converging toward)."""
    import jax

    from bigslice_tpu.models.kmeans import kmeans, kmeans_step

    rng = np.random.RandomState(0)
    pts = rng.rand(n_points, d).astype(np.float32)

    # Roofline reference: the raw jitted step (not the framework).
    cents = pts[:k].copy()
    step = jax.jit(kmeans_step)
    cents = np.asarray(step(pts, cents))  # warm
    t0 = time.perf_counter()
    for _ in range(rounds):
        cents = step(pts, cents)
    jax.block_until_ready(cents)
    raw_dt = time.perf_counter() - t0
    flops = 2.0 * n_points * d * k * 2 * rounds  # two matmuls/round
    note(f"kmeans raw step: {flops/raw_dt/1e12:.2f} TFLOP/s "
         f"({rounds} rounds, {n_points}x{d}, k={k})")

    # The measured metric: the Session-driven iterative pipeline.
    mesh = _mesh()
    sess = _mesh_session(mesh)
    n = mesh.devices.size
    kmeans(sess, pts, k=k, iters=1, num_shards=n)  # warm compiles
    g0 = sess.executor.device_group_count()
    t0 = time.perf_counter()
    kmeans(sess, pts, k=k, iters=rounds, num_shards=n)
    dt = time.perf_counter() - t0
    if sess.executor.device_group_count() == 0:
        raise RuntimeError("kmeans never engaged the device path")
    # The iterative-session overhead contract (round-5 verdict #3):
    # <= 2 device groups per round (assign+combine+shuffle fused into
    # the producer group; one reduce-side group) and session throughput
    # within hailing distance of the raw jitted step. The base Const
    # materialization accounts for the +1.
    groups_per_round = (sess.executor.device_group_count() - g0 - 1
                        ) / rounds
    ratio = raw_dt / dt
    note(f"kmeans session path: {n_points*rounds/dt:.0f} points/s, "
         f"device groups/round {groups_per_round:.1f}, "
         f"session/raw-step ratio {100*ratio:.0f}%")
    assert groups_per_round <= 2.01, groups_per_round

    # CPU baseline: numpy one round, scaled.
    t0 = time.perf_counter()
    d2 = ((pts ** 2).sum(1)[:, None]
          + (np.asarray(cents) ** 2).sum(1)[None, :]
          - 2 * pts @ np.asarray(cents).T)
    assign = d2.argmin(1)
    np.add.at(np.zeros((k, d), np.float32), assign, pts)
    base_dt = time.perf_counter() - t0
    return (n_points * rounds) / dt, n_points / base_dt


# ------------------------------------------------------------- attention

# Advertised peak bf16 TFLOP/s per chip (Google Cloud TPU system
# architecture pages), keyed by the exact ``device_kind`` jax reports.
# MFU = model FLOP/s ÷ (per-chip peak × chips). A kind that is not
# here is an error where a rate is divided by a peak, never a default.
_PEAK_TFLOPS = {
    "TPU v6 lite": 918.0,
    "TPU v5": 459.0,
    "TPU v5 lite": 197.0,
    "TPU v4": 275.0,
    "TPU v3": 123.0,
    "TPU v2": 45.0,
}


def _mesh_peak_tflops(mesh):
    kind = mesh.devices.flat[0].device_kind
    if kind not in _PEAK_TFLOPS:
        raise KeyError(
            f"no peak TFLOP/s on record for device kind {kind!r}; add "
            f"it to bench._PEAK_TFLOPS with its source"
        )
    return _PEAK_TFLOPS[kind] * mesh.devices.size


def attention_bench(seq: int, h: int, d: int, iters: int = 5):
    """Beyond-reference long-context mode: ring vs Ulysses sequence-
    parallel attention over the mesh, reported as model TFLOP/s
    (4·seq²·h·d forward FLOPs). Not a BASELINE config — evidence that
    the long-context tier drives the MXU, and (on TPU) that the ICI
    collective patterns (ppermute ring, all_to_all re-shard) compile
    and overlap."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bigslice_tpu.parallel import ringattention as ra
    from bigslice_tpu.parallel import ulysses as ul

    mesh = _mesh()
    rng = np.random.RandomState(0)
    q, k, v = (rng.randn(seq, h, d).astype(np.float32) * 0.3
               for _ in range(3))
    from bigslice_tpu.parallel.meshutil import mesh_axis

    sharding = NamedSharding(mesh, P(mesh_axis(mesh)))
    qg, kg, vg = (jax.device_put(x, sharding) for x in (q, k, v))
    flops = 4.0 * seq * seq * h * d

    def time_fn(fn, *args):
        out = fn(*args)
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    u_fn = ul.make_ulysses_attention(mesh, nheads=h, d=d, causal=True)
    t_u = time_fn(u_fn, qg, kg, vg)
    note(f"attention ulysses fp32: {flops/t_u/1e12:.3f} TFLOP/s "
         f"(seq={seq}, h={h}, d={d})")
    import jax.numpy as jnp

    ub_fn = ul.make_ulysses_attention(mesh, nheads=h, d=d, causal=True,
                                      dtype=jnp.bfloat16)
    t_ub = time_fn(ub_fn, qg, kg, vg)
    note(f"attention ulysses bf16: {flops/t_ub/1e12:.3f} TFLOP/s")
    r_fn = ra.make_ring_attention(mesh, d=d, causal=True,
                                  dtype=jnp.bfloat16,
                                  block_q=max(128, seq // 64))
    h0 = (jax.device_put(x[:, 0], sharding) for x in (q, k, v))
    t_r = time_fn(r_fn, *h0) * h  # one head timed; scale to h heads
    note(f"attention ring bf16 blocked: {flops/t_r/1e12:.3f} TFLOP/s "
         f"(per-head timing × {h})")
    t_u = min(t_u, t_ub)
    if mesh.devices.flat[0].platform == "cpu":
        note("attention MFU: n/a (CPU pinned)")
    else:
        peak = _mesh_peak_tflops(mesh)
        mfu = flops / min(t_u, t_r) / 1e12 / peak
        note(f"attention MFU: {100 * mfu:.1f}% of {peak:.0f} TFLOP/s "
             f"mesh peak")

    # CPU baseline: the dense float64 oracle on one head of a REDUCED
    # sequence (the [seq, seq] temporaries are O(seq²·8B) — at
    # seq=32k that's ~8.6 GB apiece), scaled by the seq² FLOP ratio.
    bs_seq = min(seq, 2048)
    t0 = time.perf_counter()
    ul.dense_mha_reference(q[:bs_seq, :1], k[:bs_seq, :1],
                           v[:bs_seq, :1], causal=True)
    base_t = (time.perf_counter() - t0) * h * (seq / bs_seq) ** 2
    return flops / min(t_u, t_r) / 1e12, flops / base_t / 1e12


def attention_config(size, fallback: bool, nmesh: int):
    """(seq, heads, head_dim) for the attention mode — one derivation
    shared by main() and tools_bench_all so the sizing rules (HBM-safe
    seq cap, heads divisible over the mesh, seq a mesh multiple) can't
    drift."""
    # seq is bounded by the Ulysses [h_local, seq, seq] score
    # temporaries: seq=8k → ~0.5 GB over two temporaries — safe in
    # v5e's 16 GB HBM; 32k would need ~17 GB and OOM.
    seq = size or (1 << 12 if fallback else 1 << 13)
    # Heads must divide over the mesh (Ulysses re-shard).
    h = nmesh * (1 if fallback else 2)
    d = 32 if fallback else 128
    # Sequence shards over the mesh: round up to a multiple.
    seq = max(seq, nmesh * 8)
    seq = ((seq + nmesh - 1) // nmesh) * nmesh
    return seq, h, d


# ------------------------------------------------------------------ main

def run_mode(mode: str, size, fallback: bool) -> None:
    if mode == "reduce":
        # No annotation: the executor's staging-time probe discovers
        # the dense 65k-key range itself — the honest
        # headline is what a user gets without tuning.
        n_rows = size or (1 << 21 if fallback else 1 << 24)
        n_keys = 1 << 16
        rng = np.random.RandomState(42)
        keys = rng.randint(0, n_keys, n_rows).astype(np.int32)
        vals = np.ones(n_rows, dtype=np.int32)
        base = cpu_reduce_baseline(keys, vals)
        dev = reduce_e2e_bench(keys, vals)
        emit("reduce_by_key_e2e_rows_per_sec", dev, "rows/sec", base)
    elif mode in ("reduce-sort", "reduce-nohash"):
        # The generic-key pipeline, auto-discovery pinned off — the
        # A/B partner for `reduce` and the number that stands for
        # workloads whose keys genuinely aren't dense. Served by the
        # hash-aggregate lowering where enabled; `reduce-nohash` pins
        # that off too, measuring the pure sort pipeline for the
        # BASELINE.md A/B record.
        n_rows = size or (1 << 21 if fallback else 1 << 24)
        n_keys = 1 << 16
        rng = np.random.RandomState(42)
        keys = rng.randint(0, n_keys, n_rows).astype(np.int32)
        vals = np.ones(n_rows, dtype=np.int32)
        base = cpu_reduce_baseline(keys, vals)
        dev = reduce_e2e_bench(
            keys, vals, auto_dense=False,
            hash_aggregate=False if mode == "reduce-nohash" else None,
        )
        emit(f"reduce_by_key_{'nohash' if mode == 'reduce-nohash' else 'sort'}"
             f"_e2e_rows_per_sec", dev, "rows/sec", base)
    elif mode == "reduce-dense":
        # The same workload as `reduce` with the key space declared
        # (dense int32 codes in [0, 2^16)) — the sort-free
        # table+collective lowering (parallel/dense.py). Separate mode
        # so the headline `reduce` number stays the generic-key path.
        n_rows = size or (1 << 21 if fallback else 1 << 24)
        n_keys = 1 << 16
        rng = np.random.RandomState(42)
        keys = rng.randint(0, n_keys, n_rows).astype(np.int32)
        vals = np.ones(n_rows, dtype=np.int32)
        base = cpu_reduce_baseline(keys, vals)
        dev = reduce_e2e_bench(keys, vals, dense_keys=n_keys)
        emit("reduce_by_key_dense_e2e_rows_per_sec", dev, "rows/sec",
             base)
    elif mode == "kernel-select":
        # The measured kernel-selector A/B (see kernel_select_bench):
        # vs_baseline is the forced-WORST lowering on the same corpus
        # — what auto-selection buys over shipping the wrong static
        # choice. Bit-parity across all three arms and the picked-
        # the-winner check are asserted inside the bench; the emitted
        # line carries the decision log the CI smoke re-checks.
        n_rows = size or (1 << 19 if fallback else 1 << 22)
        r = kernel_select_bench(n_rows)
        emit("kernel_select_e2e_rows_per_sec", r["measured_rps"],
             "rows/sec", r["forced_worst_rps"],
             parity="bit-identical",
             picked=r["picked"],
             forced_best=r["forced_best"],
             forced_sort_rows_per_sec=round(r["sort_rps"], 3),
             forced_hash_rows_per_sec=round(r["hash_rps"], 3),
             probe_walls_ms=r["probe_walls_ms"],
             select_counts=r["select_counts"],
             decisions=r["decisions"])
    elif mode == "reduce-wave":
        # Wave streaming: S = 4×N shards force ceil(S/N)=4 waves
        # through the device per group, keys drawn from a genuinely
        # NON-dense space (2^20 — the auto-dense probe declines, the
        # generic pipeline runs). vs_baseline here is the pre-pipeline
        # SERIAL wave executor (prefetch 0, no donation, no subid
        # split), not the CPU — the number that judges the overlapped
        # wave pipeline itself.
        import jax as _jax

        n_rows = size or (1 << 22 if fallback else 1 << 25)
        S = 4 * max(1, len(_jax.devices()))
        rng = np.random.RandomState(42)
        keys = rng.randint(0, 1 << 20, n_rows).astype(np.int32)
        vals = np.ones(n_rows, dtype=np.int32)
        serial, serial_overlap, _ = reduce_wave_bench(keys, vals, S,
                                                      pipelined=False)
        piped, piped_overlap, device = reduce_wave_bench(
            keys, vals, S, pipelined=True
        )
        note(f"reduce_wave: serial {serial:,.0f} rows/s, pipelined "
             f"{piped:,.0f} rows/s → {piped/serial:.2f}x")
        emit("reduce_wave_e2e_rows_per_sec", piped, "rows/sec", serial,
             overlap_efficiency=piped_overlap,
             serial_overlap_efficiency=serial_overlap,
             device=device)
    elif mode == "reduce-wave-2d":
        # The multi-pod exchange A/B: the same waved keyed reduce on a
        # flat 1-D mesh vs the 2-D (dcn, ici) hierarchy over the SAME
        # devices (2 × N/2 — force an 8-device CPU grid with
        # --xla_force_host_platform_device_count=8). Results must be
        # bit-identical; the emitted line carries the measured
        # dcn-message/bytes columns: the two-stage exchange crosses
        # DCN with I-fold fewer, I-fold larger messages than the flat
        # exchange over the same topology.
        import jax as _jax

        ndev = max(1, len(_jax.devices()))
        if ndev < 4 or ndev % 2:
            raise RuntimeError(
                f"reduce-wave-2d needs an even device count >= 4 "
                f"(got {ndev}); force a CPU mesh with "
                f"XLA_FLAGS=--xla_force_host_platform_device_count=8"
            )
        shape = (2, ndev // 2)
        n_rows = size or (1 << 20)
        S = 2 * ndev
        rng = np.random.RandomState(42)
        keys = rng.randint(0, 1 << 20, n_rows).astype(np.int32)
        vals = np.ones(n_rows, dtype=np.int32)
        flat_rps, flat_rows, _flat_ex = reduce_wave_2d_bench(
            keys, vals, S, shape=None
        )
        hier_rps, hier_rows, ex = reduce_wave_2d_bench(
            keys, vals, S, shape=shape
        )
        if hier_rows != flat_rows:
            raise RuntimeError("2-D result differs from the 1-D mesh")
        note(f"reduce_wave_2d: 1d {flat_rps:,.0f} rows/s, "
             f"{shape[0]}x{shape[1]} {hier_rps:,.0f} rows/s, dcn "
             f"messages {ex['dcn_messages']} vs flat-equivalent "
             f"{ex['flat_dcn_messages']} "
             f"({ex.get('dcn_message_reduction', 0)}x reduction)")
        emit("reduce_wave_2d_e2e_rows_per_sec", hier_rps, "rows/sec",
             flat_rps, mesh_shape=f"{shape[0]}x{shape[1]}",
             parity="bit-identical",
             dcn_messages=ex["dcn_messages"],
             dcn_bytes=ex["dcn_bytes"],
             ici_messages=ex["ici_messages"],
             ici_bytes=ex["ici_bytes"],
             flat_dcn_messages=ex["flat_dcn_messages"],
             flat_dcn_bytes=ex["flat_dcn_bytes"],
             dcn_message_reduction=ex.get("dcn_message_reduction"))
    elif mode == "reduce-wave-spill":
        # The out-of-core shuffle A/B + beyond-budget run (see
        # reduce_wave_spill_bench): vs_baseline is the in-program
        # exchange on the SAME corpus (what forcing spill costs when
        # in-memory would have fit); the emitted line carries the
        # 4x-budget out-of-core evidence (plan choice, spill bytes,
        # wave schedule, hbm-peak-under-budget) the CI smoke asserts.
        n_rows = size or (1 << 20)
        r = reduce_wave_spill_bench(n_rows)
        emit("reduce_wave_spill_e2e_rows_per_sec", r["spill_rps"],
             "rows/sec", r["inmem_rps"],
             parity="bit-identical",
             ooc_rows_per_sec=round(r["ooc_rps"], 3),
             corpus_bytes=r["corpus_bytes"],
             budget_bytes=r["budget_bytes"],
             corpus_vs_budget=round(
                 r["corpus_bytes"] / r["budget_bytes"], 2),
             hbm_peak_bytes=r["hbm_peak_bytes"],
             within_budget=r["within_budget"],
             spill_bytes=r["spill_bytes"],
             partitions=r["partitions"],
             map_waves=r["map_waves"],
             sub_waves=r["sub_waves"])
    elif mode == "reduce-wave-adaptive":
        # The telemetry→action loop A/B (see reduce_wave_adaptive_
        # bench): vs_baseline is the SAME run with BIGSLICE_ADAPTIVE
        # unset under the identical fixed-seed slow-host fault plan —
        # the number that judges what closing the loop buys when the
        # fleet misbehaves. Value parity, speculation engagement, and
        # the p99/wall-clock win are asserted inside the bench; the
        # emitted line carries the evidence the CI smoke re-checks.
        n_rows = size or (1 << 18 if fallback else 1 << 20)
        r = reduce_wave_adaptive_bench(n_rows)
        emit("reduce_wave_adaptive_e2e_rows_per_sec", r["all_rps"],
             "rows/sec", r["off_rps"],
             parity="value-identical",
             off_wall_s=round(r["off_wall_s"], 3),
             all_wall_s=round(r["all_wall_s"], 3),
             off_p99_task_s=round(r["off_p99_s"], 4),
             all_p99_task_s=round(r["all_p99_s"], 4),
             p99_improvement=round(
                 r["off_p99_s"] / r["all_p99_s"], 2)
             if r["all_p99_s"] else None,
             speculative=r["speculative"],
             skew_splits=r["skew_splits"],
             skew_off_rows_per_sec=round(r["skew_off_rps"], 3),
             skew_all_rows_per_sec=round(r["skew_all_rps"], 3))
    elif mode == "reduce-wave-coded":
        # Proactive straggler tolerance A/B (see reduce_wave_coded_
        # bench): off vs reactive speculation vs coded k-of-n coverage
        # under the identical fixed-seed slow-host plan. Value parity
        # x3, zero speculative dispatch in the coded arm, and the 2x
        # wall win over off are asserted inside the bench; the emitted
        # line carries the evidence the CI smoke re-checks.
        n_rows = size or (1 << 16 if fallback else 1 << 18)
        r = reduce_wave_coded_bench(n_rows)
        emit("reduce_wave_coded_e2e_rows_per_sec", r["coded_rps"],
             "rows/sec", r["off_rps"],
             parity="value-identical-x3",
             off_wall_s=round(r["off_wall_s"], 3),
             spec_wall_s=round(r["spec_wall_s"], 3),
             coded_wall_s=round(r["coded_wall_s"], 3),
             wall_improvement=round(
                 r["off_wall_s"] / r["coded_wall_s"], 2),
             speculative_in_coded_arm=0,
             spec_arm=r["speculative"],
             coded=r["coded_counts"])
    elif mode == "reduce-wave-staged":
        # The serving shape: waved Reduce whose shards stage from
        # encoded stream files (read → decode → assemble → upload is
        # the exposed cost; dense keys keep the device side fast).
        # vs_baseline is the PR-2 staging path (BSF3 decode copies,
        # concat+pad staging, per-column puts) on the same corpus
        # shape, interleaved on the same host — the number that judges
        # the staging fast path e2e.
        n_rows = size or (1 << 22 if fallback else 1 << 24)
        results = reduce_wave_staged_bench(n_rows)
        legacy, legacy_overlap, legacy_bd = results["legacy"]
        fastv, fast_overlap, fast_bd = results["fast"]
        note(f"reduce_wave_staged: legacy {legacy:,.0f} rows/s, fast "
             f"{fastv:,.0f} rows/s → {fastv / legacy:.2f}x")
        emit("reduce_wave_staged_e2e_rows_per_sec", fastv, "rows/sec",
             legacy,
             overlap_efficiency=fast_overlap,
             staging_breakdown=fast_bd,
             legacy_overlap_efficiency=legacy_overlap,
             legacy_staging_breakdown=legacy_bd)
    elif mode == "serve-qps":
        # The serving plane's sustained-load number: a resident
        # ServeServer fields concurrent HTTP invocations of a waved
        # keyed Reduce; the warm phase runs on a FRESH Session whose
        # programs come entirely from the cross-Session program cache
        # (zero XLA compiles — enforced inside the bench). vs_baseline
        # is the warm-vs-cold first-request latency ratio: the
        # host-portable number for what the program cache buys.
        n_rows = size or (1 << 18 if fallback else 1 << 20)
        r = serve_qps_bench(n_rows,
                            seconds=4.0 if fallback else 10.0,
                            concurrency=4 if fallback else 8)
        # vs_baseline == warm_vs_cold (emit divides value/baseline).
        emit("serve_qps_req_per_sec", r["qps"], "req/sec",
             r["qps"] / r["warm_vs_cold"],
             **{k: v for k, v in r.items() if k != "qps"})
    elif mode == "staging":
        # Host-staging microbench: the BSF4 + arena + batched-put fast
        # path vs the BSF3 + concat + per-column-put legacy chain, on
        # one wave's worth of per-shard streams.
        n_rows = size or (1 << 19 if fallback else 1 << 22)
        fastv, legacy = staging_bench(n_rows)
        emit("staging_rows_per_sec", fastv, "rows/sec", legacy)
    elif mode == "reduce-kernel":
        n_rows = size or (1 << 21 if fallback else 1 << 24)
        rng = np.random.RandomState(42)
        keys = rng.randint(0, 1 << 16, n_rows).astype(np.int32)
        vals = np.ones(n_rows, dtype=np.int32)
        base = cpu_reduce_baseline(keys, vals)
        dev = reduce_kernel_bench(keys, vals)
        emit("reduce_by_key_rows_per_sec", dev, "rows/sec", base)
    elif mode == "join":
        n_rows = size or (1 << 18 if fallback else 1 << 23)
        dev = join_e2e_bench(n_rows)
        base = cpu_join_baseline(*join_inputs(n_rows))
        emit("join_aggregate_e2e_rows_per_sec", dev, "rows/sec", base)
    elif mode == "join-dense":
        # Config #3 with the key space declared: per-side dense-table
        # combine+shuffle and the rank-indexed table join.
        n_rows = size or (1 << 18 if fallback else 1 << 23)
        dev = join_e2e_bench(n_rows, dense=True)
        base = cpu_join_baseline(*join_inputs(n_rows))
        emit("join_aggregate_dense_e2e_rows_per_sec", dev, "rows/sec",
             base)
    elif mode == "join-kernel":
        n_rows = size or (1 << 19 if fallback else 1 << 23)
        dev = join_kernel_bench(n_rows)
        base = cpu_join_baseline(*join_inputs(n_rows))
        emit("join_aggregate_rows_per_sec", dev, "rows/sec", base)
    elif mode == "wordcount":
        n_rows = size or (1 << 20 if fallback else 1 << 24)
        dev, base = wordcount_bench(n_rows)
        emit("wordcount_rows_per_sec", dev, "rows/sec", base)
    elif mode == "sortshuffle":
        n_rows = size or (1 << 20 if fallback else 1 << 24)
        dev, base = sortshuffle_bench(n_rows)
        emit("shuffle_sort_rows_per_sec", dev, "rows/sec", base)
    elif mode == "cogroup":
        n_rows = size or (1 << 18 if fallback else 1 << 22)
        dev, base = cogroup_bench(n_rows)
        emit("cogroup_rows_per_sec", dev, "rows/sec", base)
    elif mode == "attention":
        import jax

        seq, h, d = attention_config(
            size, fallback, max(1, len(jax.devices()))
        )
        dev, base = attention_bench(seq, h, d)
        emit("seq_parallel_attention_tflops", dev, "TFLOP/s", base)
    elif mode == "kmeans":
        # Framework path carries points as ONE [n, d] vector column
        # (permutation-gather reduce); CPU-fallback sizes stay
        # compute-dominant but bounded (the session/raw ratio is
        # meaningless when per-round control-plane ms dominate a
        # sub-ms step), TPU runs the raw-MXU shape.
        n_points = size or (1 << 16 if fallback else 1 << 17)
        d, k = (32, 32) if fallback else (64, 64)
        dev, base = kmeans_bench(n_points, d=d, k=k, fallback=fallback)
        emit("kmeans_points_per_sec", dev, "points/sec", base)


# Matrix order: the honest e2e reduce headline runs LAST because the
# driver parses the tail JSON line. Fast sizes so the
# full sweep stays bounded even on the 1-vCPU fallback.
MATRIX = ("reduce-sort", "reduce-dense", "reduce-wave", "staging",
          "reduce-wave-staged", "join",
          "join-dense", "wordcount", "sortshuffle", "cogroup",
          "kmeans", "attention", "reduce")

# Fast matrix sizes per mode (None → the mode's own fallback default).
_MATRIX_SIZES = {
    "reduce": 1 << 20,
    "reduce-sort": 1 << 20,
    "reduce-dense": 1 << 20,
    "reduce-wave": 1 << 20,
    "staging": 1 << 19,
    "reduce-wave-staged": 1 << 19,
    "join": 1 << 17,
    "join-dense": 1 << 17,
    "wordcount": 1 << 17,
    "sortshuffle": 1 << 19,
    "kmeans": 1 << 15,
    "cogroup": 1 << 16,
    "attention": 1 << 10,
}


def run_matrix(fallback: bool) -> bool:
    """One JSON line per config; a config crash emits an error line and
    the sweep keeps walking (the headline must still reach the tail).
    Returns whether every config ran — main() exits non-zero
    otherwise."""
    import traceback

    ok = True
    for mode in MATRIX:
        size = _MATRIX_SIZES.get(mode) if fallback else None
        try:
            run_mode(mode, size, fallback)
        except Exception as exc:
            ok = False
            note(f"{mode} failed: {type(exc).__name__}: {exc}")
            traceback.print_exc()
            print(json.dumps({
                "metric": f"{mode}_error", "value": 0.0, "unit": "error",
                "vs_baseline": 0.0,
                "error": f"{type(exc).__name__}: {exc}",
            }))
    return ok


def main():
    if "--aot-check" in sys.argv[1:]:
        # AOT-compile the whole device tier for a real TPU topology —
        # no chip needed (tools/aotcheck.py); writes AOT_TPU.json.
        from bigslice_tpu.tools import aotcheck

        rest = [a for a in sys.argv[1:] if a != "--aot-check"]
        aotcheck.main(rest)
        return

    from bigslice_tpu.utils.hermetic import (
        accelerator_or_pinned_cpu,
        configure_compile_cache,
    )

    configure_compile_cache()
    # The headline sizes assume TPU throughput; the scaled-down sizes
    # are reachable only by pinning the CPU on purpose.
    fallback = accelerator_or_pinned_cpu("bench")
    args = sys.argv[1:]
    known = ("reduce", "reduce-sort", "reduce-nohash", "reduce-dense",
             "reduce-wave", "reduce-wave-2d", "reduce-wave-staged",
             "reduce-wave-spill", "reduce-wave-adaptive",
             "reduce-wave-coded",
             "kernel-select", "staging", "serve-qps",
             "reduce-kernel", "join", "join-dense",
             "join-kernel", "wordcount", "sortshuffle", "cogroup",
             "kmeans", "attention", "matrix")
    mode = "matrix"
    if args and args[0] in known:
        mode = args.pop(0)
    size = int(args[0]) if args else None

    if mode == "matrix":
        if not run_matrix(fallback):
            sys.exit(1)
    else:
        run_mode(mode, size, fallback)


if __name__ == "__main__":
    main()
