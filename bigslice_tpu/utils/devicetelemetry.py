"""Device-plane telemetry: XLA compile/cost/memory attribution, HBM
watermarks, donation effectiveness.

PR 2's hub made the *host* plane visible (skew, stragglers, wave
overlap); this module is the *device* half the telemetry hub carries as
``hub.device``:

1. **Compile telemetry** — a seam around every jitted SPMD program the
   mesh executor builds (`_InstrumentedProgram`): the first call per
   input signature is compiled ahead-of-time (``jit.lower().compile()``
   — the exact path tools/aotcheck.py proves on TPU topologies),
   recording compile wall time, ``cost_analysis()`` (FLOPs / bytes
   accessed) and ``memory_analysis()`` (argument / output / temp /
   alias bytes) keyed by op + partition config — the digest that will
   key ROADMAP item 3's AOT compiled-program cache. Subsequent calls
   reuse the held executable and count as cache hits, so per-op
   hit/miss ratios fall out of the call accounting itself (no extra
   bookkeeping at the executor's program-cache sites).
2. **HBM accounting** — per-wave device-memory watermarks from the
   backend allocator (``device.memory_stats()``; real on TPU/GPU) with
   a ``jax.live_arrays()`` byte-sum fallback where the backend reports
   nothing (virtual CPU meshes), plus donation effectiveness: bytes
   the executor *expected* to alias through the PR-1 donation seams
   vs. buffers the runtime actually consumed.

Everything is exception-safe and cheap by construction: when no hub is
attached the executor never wraps a program (collection is a no-op),
and an attached recorder costs one signature tuple per program call.
The hub surfaces this module's ``summary()`` as
``Session.telemetry_summary()["device"]``, its ``prometheus_lines()``
under ``/debug/metrics``, and its instant events as the
``invN:compile`` / ``invN:device`` slicetrace sections.
"""

from __future__ import annotations

import hashlib
import logging
import threading
import time
from typing import Dict, List, Optional

# Per-wrapper AOT executables held (input signatures per program). The
# executor's program cache already bounds programs FIFO; this bounds
# pathological per-program signature churn (shouldn't happen — shapes
# are part of the executor's cache key — but a leak here would pin
# compiled executables).
MAX_SIGNATURES = 8

# Per-op compiled-program detail entries retained in the summary
# (aggregate counters keep counting past the bound).
MAX_PROGRAMS_PER_OP = 32

# Retained per-op records (the hub's MAX_OPS rationale: iterative
# drivers mint fresh #N-suffixed ops each invocation).
MAX_OPS = 1024

# Per-wave HBM watermark samples retained for the summary (rollup
# max/peak keeps accumulating past the bound).
MAX_HBM_SAMPLES = 256


def program_digest(op: str, kind: str, parts) -> str:
    """Stable digest of (op site, program kind, partition/shape
    config) — the forward-compatible cache key shape for ROADMAP item
    3's AOT compiled-program cache (registry digest + partition
    config). ``parts`` must be repr-stable (no ids)."""
    payload = repr((op, kind, parts)).encode()
    return hashlib.sha1(payload).hexdigest()[:16]


def _cost_dict(compiled) -> dict:
    """Normalized subset of ``compiled.cost_analysis()`` (which returns
    a dict or a 1-list of dicts depending on jax version)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = ca or {}
    out = {}
    for src, dst in (("flops", "flops"),
                     ("bytes accessed", "bytes_accessed"),
                     ("optimal_seconds", "optimal_seconds")):
        v = ca.get(src)
        if v is not None:
            out[dst] = float(v)
    return out


def _memory_dict(compiled) -> dict:
    """Normalized subset of ``compiled.memory_analysis()`` (None /
    unimplemented on some backends — callers treat {} as 'unknown')."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    out = {}
    for attr, dst in (("argument_size_in_bytes", "argument_bytes"),
                      ("output_size_in_bytes", "output_bytes"),
                      ("temp_size_in_bytes", "temp_bytes"),
                      ("alias_size_in_bytes", "alias_bytes"),
                      ("generated_code_size_in_bytes", "code_bytes")):
        v = getattr(ma, attr, None)
        if v is not None:
            out[dst] = int(v)
    return out


def _arg_signature(a) -> tuple:
    """Cheap per-argument identity for the executable cache: shape,
    dtype, and — for committed device arrays — the sharding (hashable
    on jax shardings; a numpy host arg and a mesh-sharded device arg
    must not share an AOT executable, whose input shardings are baked
    at compile time)."""
    shape = getattr(a, "shape", None)
    if shape is None:
        return (type(a).__name__, repr(a))
    dtype = str(getattr(a, "dtype", ""))
    sharding = getattr(a, "sharding", None)
    if sharding is not None:
        try:
            hash(sharding)  # signatures are dict keys downstream
            return (shape, dtype, sharding)
        except Exception:  # unhashable exotic sharding: coarse tag
            return (shape, dtype, "sharded")
    return (shape, dtype)


class _InstrumentedProgram:
    """Transparent wrapper over a jitted program: ahead-of-time
    compiles on first call per input signature (recording wall time +
    cost/memory analysis into the recorder), reuses the held executable
    after (recording cache hits). Any AOT-path surprise — an argument
    aval/sharding the baked executable rejects, a lowering quirk —
    permanently falls back to the plain jitted callable for this
    wrapper, with one WARNING (correctness never depends on
    instrumentation).

    When the program carries a cross-session digest (``serve_key`` —
    the serving plane's content-fingerprinted identity from
    serve/programcache.py), a local miss additionally probes the
    process-global program cache before touching XLA: a hit there is a
    *cross-session* hit (a fresh Session reusing an executable some
    earlier Session compiled — zero XLA work), and every fresh compile
    is published back. ``serve_key=None`` (unfingerprintable closures,
    or the cache disabled) keeps the program session-local, exactly
    the pre-serving behavior.

    Argument-compatibility errors raise *before* execution (donated
    buffers are not yet consumed), so the fallback re-call is safe; a
    genuine runtime failure (OOM, DMA) re-raises unchanged into the
    executor's classification ladder."""

    __slots__ = ("_fn", "_rec", "_op", "_inv", "_kind", "_digest",
                 "_serve_key", "_compiled", "_cross", "_fell_back",
                 "_lock")

    def __init__(self, fn, recorder: "DeviceTelemetry", op: str,
                 inv: Optional[int], kind: str, digest: str,
                 serve_key: Optional[str] = None):
        self._fn = fn
        self._rec = recorder
        self._op = op
        self._inv = inv
        self._kind = kind
        self._digest = digest
        self._serve_key = serve_key
        self._compiled: Dict[tuple, object] = {}
        # Signatures served from the cross-session cache: a baked-
        # executable rejection for one of these must also invalidate
        # the global entry (a poisoned executable must not keep
        # fanning out to future sessions).
        self._cross: set = set()
        self._fell_back = False
        # Cached wrapped programs are shared across concurrent group
        # threads; the probe/compile/bookkeeping must not race (two
        # threads both missing would each pay a multi-second compile —
        # the raw jit this wraps serializes compilation internally).
        # Held only around probe + compile, never around execution.
        self._lock = threading.Lock()

    # The executor's retry ladder re-enters with identical shapes;
    # expose lower for anything that held the raw jit before.
    def lower(self, *args, **kw):
        return self._fn.lower(*args, **kw)

    def __call__(self, *args):
        # Entry to the executable's call is the seam's own part of a
        # program call (lock, signature, lookup): ``lookup_s``, which
        # the cache-hit record below carries.
        t0 = time.perf_counter()
        hit = False
        with self._lock:
            if self._fell_back:
                compiled = None
            else:
                try:
                    # Signature build AND cache probe both inside the
                    # guard: a signature that defeats the hashability
                    # probe must fall back, never crash the wave.
                    sig = tuple(_arg_signature(a) for a in args)
                    compiled = self._compiled.get(sig)
                except Exception as e:
                    compiled = None
                    self._fall_back_locked(e)
                if compiled is None and not self._fell_back:
                    if len(self._compiled) >= MAX_SIGNATURES:
                        # Signature churn the executor's cache key
                        # should have prevented: stop holding
                        # executables, keep running.
                        self._fall_back_locked(
                            f"more than {MAX_SIGNATURES} signatures"
                        )
                    else:
                        compiled = self._serve_probe(sig)
                        if compiled is not None:
                            # Cross-session hit: an executable some
                            # earlier Session compiled — no XLA work
                            # at all for this program.
                            self._compiled[sig] = compiled
                            self._cross.add(sig)
                            self._rec.record_cache_hit(
                                self._op, self._inv, self._kind,
                                cross_session=True,
                            )
                        else:
                            compiled = self._compile_locked(sig, args)
                elif compiled is not None:
                    hit = True
        if compiled is None:
            return self._fn(*args)
        if hit:
            self._rec.record_cache_hit(
                self._op, self._inv, self._kind,
                lookup_s=time.perf_counter() - t0)
        try:
            return compiled(*args)
        except (TypeError, ValueError) as e:
            # Baked-executable argument rejection (aval/sharding/layout
            # mismatch our signature missed) — raised before execution,
            # args intact: run the flexible jit path instead, for good.
            with self._lock:
                self._fall_back_locked(e)
            return self._fn(*args)

    def _serve_probe(self, sig):
        """Cross-session lookup; never raises (the serving cache is an
        accelerator, not a dependency)."""
        if self._serve_key is None:
            return None
        try:
            from bigslice_tpu.serve.programcache import (
                global_program_cache,
            )

            return global_program_cache().get(self._serve_key, sig)
        except Exception:
            return None

    def _compile_locked(self, sig, args):
        """AOT-compile under the wrapper lock: record compile wall
        time + cost/memory, publish to the cross-session cache when
        the program carries a serve key. Returns the executable, or
        None after falling back."""
        t0 = time.perf_counter()
        try:
            compiled = self._fn.lower(*args).compile()
        except Exception as e:
            # Lowering quirk: plain jit from here on (a genuine compile
            # error raises again there, into the executor's ladder).
            self._fall_back_locked(e)
            return None
        wall = time.perf_counter() - t0
        self._rec.record_compile(
            self._op, self._inv, self._kind, self._digest, wall,
            cost=_cost_dict(compiled), memory=_memory_dict(compiled),
        )
        self._compiled[sig] = compiled
        if self._serve_key is not None:
            try:
                from bigslice_tpu.serve.programcache import (
                    global_program_cache,
                )

                global_program_cache().put(self._serve_key, sig,
                                           compiled, wall)
            except Exception:
                pass
        return compiled

    def _fall_back_locked(self, why) -> None:
        """Permanently route this wrapper to the plain jit, releasing
        every held executable (a fallen-back wrapper must not pin AOT
        programs the jit path will recompile on its own). Signatures
        this wrapper had taken from the cross-session cache are
        invalidated there too — an executable this process just
        rejected must not keep fanning out to future sessions. Logged
        once per wrapper: compiles from here on are invisible to the
        recorder, and an operator must be able to see why."""
        if not self._fell_back:
            logging.getLogger("bigslice.devicetelemetry").warning(
                "AOT seam of %s (%s) fell back to plain jit: %r",
                self._op, self._kind, why,
            )
        self._fell_back = True
        if self._serve_key is not None and self._cross:
            try:
                from bigslice_tpu.serve.programcache import (
                    global_program_cache,
                )

                cache = global_program_cache()
                for sig in self._cross:
                    cache.discard(self._serve_key, sig)
            except Exception:
                pass
        self._cross.clear()
        self._compiled.clear()
        try:
            self._rec.record_fallback(self._op, self._inv, self._kind)
        except Exception:
            pass


class _OpDeviceRecord:
    def __init__(self, inv: Optional[int] = None):
        self.inv = inv
        self.compiles = 0
        self.cache_hits = 0
        self.lookup_s = 0.0
        self.cross_session_hits = 0
        self.fallbacks = 0
        self.compile_wall_s = 0.0
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.programs: List[dict] = []
        # donation effectiveness
        self.donation_expected_bytes = 0
        self.donation_aliased_bytes = 0
        self.donation_buffers = 0
        self.donation_aliased_buffers = 0
        # collective-exchange attribution, split by axis kind (the 2-D
        # DCN × ICI hierarchy's measured column): messages/bytes the
        # op's shuffle programs put on each interconnect class, plus
        # the flat-exchange equivalent a 1-stage all_to_all over the
        # same topology would have sent across DCN.
        self.exchange_waves = 0
        self.dcn_messages = 0
        self.dcn_bytes = 0
        self.ici_messages = 0
        self.ici_bytes = 0
        self.flat_dcn_messages = 0
        self.flat_dcn_bytes = 0
        # the bucket slack of the op's last dispatched wave (settled,
        # once no wave overflows) and the waves dispatched again after
        # an overflow
        self.exchange_slack = 0.0
        self.exchange_retries = 0
        # shuffle-plan attribution (exec/shuffleplan.py): per-boundary
        # exchange choice + the spill path's written bytes/partitions
        # and its map-wave / reduce-sub-wave schedule.
        self.plan_counts: Dict[str, int] = {}
        self.plan_reason = ""
        self.plan_est_bytes = 0
        self.plan_budget_bytes = 0
        self.spill_bytes = 0
        self.spill_rows = 0
        self.spill_partitions = 0
        self.spill_map_waves = 0
        self.spill_sub_waves = 0


class DeviceTelemetry:
    """The device-plane recorder the telemetry hub owns (``hub.device``).
    All entry points are lock-protected, exception-safe, and O(1)."""

    def __init__(self, eventer=None):
        self._lock = threading.Lock()
        self._ops: Dict[str, _OpDeviceRecord] = {}
        self._hbm: List[dict] = []
        self._hbm_peak_bytes = 0
        self._hbm_limit_bytes: Optional[int] = None
        self._hbm_source: Optional[str] = None
        # Samples taken and the seconds they took on the sampling
        # thread (the window above forgets; these never do).
        self._hbm_samples = 0
        self._hbm_sample_s = 0.0
        # The seam's seconds over every cache-hit call of the session:
        # per op in the records, which MAX_OPS evicts; whole here.
        self._lookup_s = 0.0
        self._eventer = eventer

    def _emit(self, name: str, **fields) -> None:
        ev = self._eventer
        if ev is None:
            return
        try:
            ev(name, **fields)
        except Exception:  # telemetry must never break the run
            pass

    def _op(self, op: str, inv: Optional[int]) -> _OpDeviceRecord:
        rec = self._ops.get(op)
        if rec is None:
            while len(self._ops) >= MAX_OPS:
                del self._ops[next(iter(self._ops))]
            rec = self._ops[op] = _OpDeviceRecord(inv)
        if rec.inv is None:
            rec.inv = inv
        return rec

    # -- the program seam -------------------------------------------------

    def instrument(self, prog, op: str, inv: Optional[int], kind: str,
                   key_parts, fns=None,
                   extra=None) -> _InstrumentedProgram:
        """Wrap a freshly-built jitted program. ``kind`` names the
        program family (``group`` for the op's SPMD program, or the
        auxiliary ``rowslice``/``merge``/``subid_count``/``subid_split``
        /``keyrange`` helpers); ``key_parts`` is the repr-stable
        partition/shape config the digest derives from.

        ``fns`` drives the cross-session program cache
        (serve/programcache.py): the user functions the program closes
        over (``()`` for purely structural helpers). ``None`` — the
        default, so a call site that never audited its closures stays
        safe — marks the program session-local. ``extra`` is
        repr-stable serve-key-only material (output schema, lowering-
        selection bits) the session-local digest deliberately omits."""
        serve_key = None
        if fns is not None:
            try:
                from bigslice_tpu.serve import programcache as pc

                if pc.cache_capacity() > 0:
                    fp = pc.fn_fingerprint(fns)
                    if fp is not None:
                        serve_key = pc.serve_digest(
                            op, kind, key_parts, extra, fp
                        )
            except Exception:
                serve_key = None
        return _InstrumentedProgram(
            prog, self, op, inv, kind,
            program_digest(op, kind, key_parts),
            serve_key=serve_key,
        )

    def record_compile(self, op: str, inv: Optional[int], kind: str,
                       digest: str, wall_s: float,
                       cost: Optional[dict] = None,
                       memory: Optional[dict] = None) -> None:
        wall_s = max(0.0, float(wall_s))
        cost = cost or {}
        memory = memory or {}
        with self._lock:
            rec = self._op(op, inv)
            rec.compiles += 1
            rec.compile_wall_s += wall_s
            rec.flops += float(cost.get("flops") or 0.0)
            rec.bytes_accessed += float(cost.get("bytes_accessed")
                                        or 0.0)
            if len(rec.programs) < MAX_PROGRAMS_PER_OP:
                entry = {"kind": kind, "key": digest,
                         "compile_s": round(wall_s, 6)}
                entry.update({k: v for k, v in cost.items()})
                entry.update({k: v for k, v in memory.items()})
                rec.programs.append(entry)
        self._emit("bigslice:compile", op=op, inv=inv, kind=kind,
                   key=digest, ms=round(wall_s * 1e3, 3),
                   flops=cost.get("flops"),
                   bytes_accessed=cost.get("bytes_accessed"),
                   temp_bytes=memory.get("temp_bytes"),
                   arg_bytes=memory.get("argument_bytes"),
                   out_bytes=memory.get("output_bytes"))

    def record_cache_hit(self, op: str, inv: Optional[int],
                         kind: str,
                         cross_session: bool = False,
                         lookup_s: float = 0.0) -> None:
        """``cross_session=True`` marks a hit served from the process-
        global program cache (serve/programcache.py) — an executable a
        *previous* Session compiled. Counted inside ``cache_hits`` (it
        is a hit) and again in the ``cross_session_hits`` subset (it
        is the zero-XLA-compile evidence the serving acceptance
        criterion keys on). ``lookup_s``: what the seam took of this
        call before it reached the executable."""
        with self._lock:
            rec = self._op(op, inv)
            rec.cache_hits += 1
            rec.lookup_s += lookup_s
            self._lookup_s += lookup_s
            if cross_session:
                rec.cross_session_hits += 1

    def record_fallback(self, op: str, inv: Optional[int],
                        kind: str) -> None:
        """The wrapper abandoned the AOT path (lowering quirk, baked-
        executable rejection, signature churn): XLA compiles from here
        on happen inside plain jit where this recorder cannot see
        them — the counter that keeps 'compiles == 0' claims honest."""
        with self._lock:
            self._op(op, inv).fallbacks += 1

    # -- HBM watermarks ---------------------------------------------------

    def sample_hbm(self, devices, op: Optional[str] = None,
                   inv: Optional[int] = None,
                   wave: Optional[int] = None) -> Optional[dict]:
        """One device-memory watermark sample: the backend allocator's
        ``memory_stats()`` where it reports (TPU/GPU), else the
        ``jax.live_arrays()`` byte sum (virtual CPU meshes report no
        allocator stats; the fallback must not raise — the CPU-backend
        contract the tests pin). Returns the recorded sample. What
        the sample cost the calling thread is summed as ``sample_s``."""
        t0 = time.perf_counter()
        in_use = peak = 0
        limit: Optional[int] = None
        source = "memory_stats"
        got = False
        try:
            for d in devices:
                try:
                    stats = d.memory_stats()
                except Exception:
                    stats = None
                if not stats:
                    continue
                got = True
                in_use = max(in_use, int(stats.get("bytes_in_use")
                                         or 0))
                peak = max(peak, int(stats.get("peak_bytes_in_use")
                                     or stats.get("bytes_in_use")
                                     or 0))
                lim = stats.get("bytes_limit")
                if lim:
                    limit = max(limit or 0, int(lim))
            if not got:
                source = "live_arrays"
                import jax

                in_use = sum(
                    int(getattr(a, "nbytes", 0) or 0)
                    for a in jax.live_arrays()
                )
                peak = in_use
        except Exception:
            return None
        return self.record_hbm(in_use, peak, limit, source=source,
                               op=op, inv=inv, wave=wave,
                               sample_s=time.perf_counter() - t0)

    def record_hbm(self, bytes_in_use: int, peak_bytes: int,
                   limit_bytes: Optional[int], source: str = "",
                   op: Optional[str] = None, inv: Optional[int] = None,
                   wave: Optional[int] = None,
                   sample_s: float = 0.0) -> dict:
        sample = {
            "bytes_in_use": int(bytes_in_use),
            "peak_bytes": int(max(peak_bytes, bytes_in_use)),
        }
        if op is not None:
            sample["op"] = op
        if wave is not None:
            sample["wave"] = int(wave)
        if limit_bytes:
            sample["limit_bytes"] = int(limit_bytes)
            sample["frac"] = round(
                sample["bytes_in_use"] / int(limit_bytes), 4
            )
        with self._lock:
            self._hbm_peak_bytes = max(self._hbm_peak_bytes,
                                       sample["peak_bytes"])
            if limit_bytes:
                self._hbm_limit_bytes = max(
                    self._hbm_limit_bytes or 0, int(limit_bytes)
                )
            if source:
                self._hbm_source = source
            self._hbm_samples += 1
            self._hbm_sample_s += sample_s
            self._hbm.append(sample)
            if len(self._hbm) > MAX_HBM_SAMPLES:
                del self._hbm[0]
        self._emit("bigslice:hbm", op=op, inv=inv, wave=wave,
                   bytes_in_use=sample["bytes_in_use"],
                   peak_bytes=sample["peak_bytes"],
                   limit_bytes=sample.get("limit_bytes"),
                   frac=sample.get("frac"))
        return sample

    # -- donation effectiveness -------------------------------------------

    def record_donation(self, op: str, inv: Optional[int],
                        expected_bytes: int, aliased_bytes: int,
                        buffers: int = 0,
                        aliased_buffers: int = 0) -> None:
        """One wave's donation outcome: bytes the executor handed to
        XLA under donate_argnums (expected to alias) vs. bytes whose
        buffers the runtime actually consumed (``is_deleted`` after
        dispatch — the backend-honored subset)."""
        with self._lock:
            rec = self._op(op, inv)
            rec.donation_expected_bytes += max(0, int(expected_bytes))
            rec.donation_aliased_bytes += max(0, int(aliased_bytes))
            rec.donation_buffers += max(0, int(buffers))
            rec.donation_aliased_buffers += max(0, int(aliased_buffers))
        self._emit("bigslice:donation", op=op, inv=inv,
                   expected_bytes=int(expected_bytes),
                   aliased_bytes=int(aliased_bytes))

    # -- exchange attribution (DCN × ICI axis split) ----------------------

    def record_exchange(self, op: str, inv: Optional[int],
                        wave: Optional[int],
                        dcn_messages: int = 0, dcn_bytes: int = 0,
                        ici_messages: int = 0, ici_bytes: int = 0,
                        flat_dcn_messages: int = 0,
                        flat_dcn_bytes: int = 0,
                        slack: float = 0.0) -> None:
        """One wave's collective-exchange plan, split by interconnect
        axis kind: messages/bytes the shuffle's all_to_all buckets put
        on the slow DCN axis vs the fast ICI axis (derived from the
        static exchange structure — bucket capacities × row bytes are
        the bytes the collective actually moves, valid or padding).
        ``flat_dcn_*`` is the counterfactual a single flat all_to_all
        over the same (D, I) topology would have crossed DCN with —
        the denominator of the I-fold reduction column. 1-D meshes
        record everything as ICI with dcn = 0. ``slack`` is the bucket
        slack the wave was dispatched at."""
        with self._lock:
            rec = self._op(op, inv)
            rec.exchange_waves += 1
            rec.exchange_slack = float(slack)
            rec.dcn_messages += max(0, int(dcn_messages))
            rec.dcn_bytes += max(0, int(dcn_bytes))
            rec.ici_messages += max(0, int(ici_messages))
            rec.ici_bytes += max(0, int(ici_bytes))
            rec.flat_dcn_messages += max(0, int(flat_dcn_messages))
            rec.flat_dcn_bytes += max(0, int(flat_dcn_bytes))
        self._emit("bigslice:exchange", op=op, inv=inv, wave=wave,
                   dcn_messages=int(dcn_messages),
                   dcn_bytes=int(dcn_bytes),
                   ici_messages=int(ici_messages),
                   ici_bytes=int(ici_bytes),
                   flat_dcn_messages=int(flat_dcn_messages),
                   flat_dcn_bytes=int(flat_dcn_bytes))

    def record_exchange_retry(self, op: str,
                              inv: Optional[int]) -> None:
        """A wave whose buckets overflowed and whose program is
        dispatched again at a larger slack (in the trace: the
        ``dispatch`` span with ``attempt``)."""
        with self._lock:
            self._op(op, inv).exchange_retries += 1

    # -- shuffle-plan attribution (out-of-core spill exchange) ------------

    def record_shuffle_plan(self, op: str, inv: Optional[int],
                            plan: str, reason: str = "",
                            est_bytes: Optional[int] = None,
                            budget_bytes: Optional[int] = None,
                            spill_bytes: int = 0, spill_rows: int = 0,
                            partitions: int = 0, map_waves: int = 0,
                            sub_waves: int = 0) -> None:
        """One shuffle boundary's exchange decision
        (exec/shuffleplan.py): ``plan`` is ``in_program`` or ``spill``,
        ``reason`` why (forced knob / budget estimate / ineligibility),
        and the spill fields describe what the store-mediated exchange
        actually moved — bytes/rows written, distinct partitions, and
        the map-wave → reduce-sub-wave schedule."""
        with self._lock:
            rec = self._op(op, inv)
            rec.plan_counts[plan] = rec.plan_counts.get(plan, 0) + 1
            rec.plan_reason = reason
            if est_bytes:
                rec.plan_est_bytes = max(rec.plan_est_bytes,
                                         int(est_bytes))
            if budget_bytes:
                rec.plan_budget_bytes = int(budget_bytes)
            rec.spill_bytes += max(0, int(spill_bytes))
            rec.spill_rows += max(0, int(spill_rows))
            rec.spill_partitions += max(0, int(partitions))
            if map_waves:
                rec.spill_map_waves = int(map_waves)
            if sub_waves:
                rec.spill_sub_waves = int(sub_waves)
        self._emit("bigslice:spill", op=op, inv=inv, plan=plan,
                   reason=reason, est_bytes=est_bytes,
                   budget_bytes=budget_bytes,
                   spill_bytes=int(spill_bytes),
                   spill_rows=int(spill_rows),
                   partitions=int(partitions),
                   map_waves=int(map_waves),
                   sub_waves=int(sub_waves))

    def hbm_budget(self) -> Optional[int]:
        """The measured aggregate device-memory limit the HBM sampler
        observed (backend allocator ``bytes_limit``; None where no
        backend reports one, e.g. virtual CPU meshes) — the
        ``auto`` shuffle planner's budget source when no explicit
        knob is set."""
        with self._lock:
            return self._hbm_limit_bytes

    def cost_bytes(self, op_base: str) -> Optional[int]:
        """The measured ``cost_analysis()`` bytes-accessed for one
        pipeline site: the max across compiled programs of every op
        whose #N-suffix-stripped base matches ``op_base`` (iterative
        drivers re-invoke the same site under fresh suffixed names).
        None when no program of that site ever compiled under
        telemetry — callers fall back to their staged-bytes
        heuristics."""
        best = 0.0
        with self._lock:
            for op, rec in self._ops.items():
                if op.split("#", 1)[0] != op_base:
                    continue
                if rec.bytes_accessed > best:
                    best = rec.bytes_accessed
        return int(best) if best > 0 else None

    def total_cost_bytes(self) -> int:
        """Session-total ``cost_analysis()`` bytes-accessed across
        every compiled program. Deltas around an invocation measure
        its compile-time cost footprint (the serving plane's predicted
        invocation cost; cached programs contribute once — at their
        first compile — which is exactly the prediction-stability the
        admission gate wants)."""
        with self._lock:
            return int(sum(r.bytes_accessed
                           for r in self._ops.values()))

    # -- queries ----------------------------------------------------------

    def status_line(self) -> Optional[str]:
        """The live ``hbm %`` annotation for the status display, plus
        a spill tail when the out-of-core exchange is active."""
        with self._lock:
            if not self._hbm:
                return None
            cur = self._hbm[-1]
            peak = self._hbm_peak_bytes
            limit = self._hbm_limit_bytes
            spill = sum(r.spill_bytes for r in self._ops.values())
        tail = f", spilled {spill / 1e6:.0f}MB" if spill else ""
        mb = cur["bytes_in_use"] / 1e6
        if limit:
            return (f"  hbm {100.0 * cur['bytes_in_use'] / limit:.0f}%"
                    f" in use ({mb:.0f}MB,"
                    f" peak {100.0 * peak / limit:.0f}%{tail})")
        return (f"  device mem {mb:.0f}MB in use (no allocator "
                f"limit{tail})")

    def summary(self) -> dict:
        """The ``telemetry_summary()["device"]`` payload."""
        with self._lock:
            compile_ops = {}
            tot_compiles = tot_hits = tot_cross = tot_fb = 0
            tot_wall = tot_flops = tot_bytes = 0.0
            donation = {}
            don_expected = don_aliased = 0
            shuffle_plan: dict = {}
            sp_tot: dict = {"spill_bytes": 0, "spill_rows": 0,
                            "spill_partitions": 0,
                            "spill_boundaries": 0,
                            "in_program_boundaries": 0}
            exchange = {}
            ex_tot = {"dcn_messages": 0, "dcn_bytes": 0,
                      "ici_messages": 0, "ici_bytes": 0,
                      "flat_dcn_messages": 0, "flat_dcn_bytes": 0}
            for op, rec in self._ops.items():
                if rec.compiles or rec.cache_hits or rec.fallbacks:
                    compile_ops[op] = {
                        "inv": rec.inv,
                        "compiles": rec.compiles,
                        "cache_hits": rec.cache_hits,
                        "lookup_s": round(rec.lookup_s, 6),
                        "cross_session_hits": rec.cross_session_hits,
                        "fallbacks": rec.fallbacks,
                        "compile_s": round(rec.compile_wall_s, 6),
                        "flops": rec.flops,
                        "bytes_accessed": rec.bytes_accessed,
                        "programs": list(rec.programs),
                    }
                    tot_compiles += rec.compiles
                    tot_hits += rec.cache_hits
                    tot_cross += rec.cross_session_hits
                    tot_fb += rec.fallbacks
                    tot_wall += rec.compile_wall_s
                    tot_flops += rec.flops
                    tot_bytes += rec.bytes_accessed
                if rec.donation_buffers:
                    eff = (rec.donation_aliased_bytes
                           / rec.donation_expected_bytes
                           if rec.donation_expected_bytes else 0.0)
                    donation[op] = {
                        "expected_bytes": rec.donation_expected_bytes,
                        "aliased_bytes": rec.donation_aliased_bytes,
                        "buffers": rec.donation_buffers,
                        "aliased_buffers": rec.donation_aliased_buffers,
                        "effectiveness": round(eff, 4),
                    }
                    don_expected += rec.donation_expected_bytes
                    don_aliased += rec.donation_aliased_bytes
                if rec.plan_counts:
                    entry = {
                        "plans": dict(rec.plan_counts),
                        "reason": rec.plan_reason,
                    }
                    if rec.plan_est_bytes:
                        entry["est_bytes"] = rec.plan_est_bytes
                    if rec.plan_budget_bytes:
                        entry["budget_bytes"] = rec.plan_budget_bytes
                    if rec.spill_bytes or rec.plan_counts.get("spill"):
                        entry.update({
                            "spill_bytes": rec.spill_bytes,
                            "spill_rows": rec.spill_rows,
                            "partitions": rec.spill_partitions,
                            "map_waves": rec.spill_map_waves,
                            "sub_waves": rec.spill_sub_waves,
                        })
                        # The per-wave watermark evidence for THIS op:
                        # the max HBM sample stamped with it — the
                        # line the out-of-core acceptance holds
                        # against the budget.
                        op_hbm = [
                            s["bytes_in_use"] for s in self._hbm
                            if s.get("op") == op
                        ]
                        if op_hbm:
                            entry["max_wave_hbm_bytes"] = max(op_hbm)
                    shuffle_plan[op] = entry
                    sp_tot["spill_bytes"] += rec.spill_bytes
                    sp_tot["spill_rows"] += rec.spill_rows
                    sp_tot["spill_partitions"] += rec.spill_partitions
                    sp_tot["spill_boundaries"] += \
                        rec.plan_counts.get("spill", 0)
                    sp_tot["in_program_boundaries"] += \
                        rec.plan_counts.get("in_program", 0)
                    if rec.plan_budget_bytes:
                        sp_tot["budget_bytes"] = max(
                            sp_tot.get("budget_bytes", 0),
                            rec.plan_budget_bytes,
                        )
                if rec.exchange_waves:
                    entry = {
                        "waves": rec.exchange_waves,
                        "dcn_messages": rec.dcn_messages,
                        "dcn_bytes": rec.dcn_bytes,
                        "ici_messages": rec.ici_messages,
                        "ici_bytes": rec.ici_bytes,
                        "slack": rec.exchange_slack,
                        "retries": rec.exchange_retries,
                    }
                    if rec.flat_dcn_messages:
                        entry["flat_dcn_messages"] = rec.flat_dcn_messages
                        entry["flat_dcn_bytes"] = rec.flat_dcn_bytes
                        if rec.dcn_messages:
                            entry["dcn_message_reduction"] = round(
                                rec.flat_dcn_messages
                                / rec.dcn_messages, 4
                            )
                    exchange[op] = entry
                    for k in ex_tot:
                        ex_tot[k] += getattr(rec, k)
            hbm: dict = {}
            if self._hbm:
                hbm = {
                    "samples": self._hbm_samples,
                    "sample_s": round(self._hbm_sample_s, 6),
                    "source": self._hbm_source,
                    "current_bytes": self._hbm[-1]["bytes_in_use"],
                    "peak_bytes": self._hbm_peak_bytes,
                    "per_wave": list(self._hbm[-32:]),
                }
                if self._hbm_limit_bytes:
                    hbm["limit_bytes"] = self._hbm_limit_bytes
                    hbm["peak_frac"] = round(
                        self._hbm_peak_bytes / self._hbm_limit_bytes, 4
                    )
        totals = {
            "compiles": tot_compiles,
            "cache_hits": tot_hits,
            "lookup_s": round(self._lookup_s, 6),
            "cross_session_hits": tot_cross,
            "fallbacks": tot_fb,
            "compile_s": round(tot_wall, 6),
            "flops": tot_flops,
            "bytes_accessed": tot_bytes,
            "hbm_peak_bytes": self._hbm_peak_bytes,
            "donation_effectiveness": round(
                don_aliased / don_expected, 4
            ) if don_expected else None,
        }
        if exchange:
            totals.update(ex_tot)
            if ex_tot["dcn_messages"] and ex_tot["flat_dcn_messages"]:
                totals["dcn_message_reduction"] = round(
                    ex_tot["flat_dcn_messages"]
                    / ex_tot["dcn_messages"], 4
                )
        splan: dict = {}
        if shuffle_plan:
            # The per-boundary plan choices plus the watermark line the
            # out-of-core acceptance keys on: the session-wide HBM peak
            # held against the spill budget.
            splan = {"ops": shuffle_plan, "totals": dict(sp_tot)}
            splan["totals"]["hbm_peak_bytes"] = self._hbm_peak_bytes
            if sp_tot.get("budget_bytes"):
                splan["totals"]["within_budget"] = bool(
                    self._hbm_peak_bytes <= sp_tot["budget_bytes"]
                )
        out = {
            "compile": compile_ops,
            "hbm": hbm,
            "donation": donation,
            "exchange": exchange,
            "shuffle_plan": splan,
            "totals": totals,
        }
        return out

    def snapshot(self) -> dict:
        """The device plane's serializable mergeable snapshot (the
        ``device`` section of ``TelemetryHub.snapshot()``): flat per-op
        counters that add across ranks, plus the HBM watermark rollup
        that max-merges. Per-program detail lists and plan free-text
        stay local — they don't merge and the fleet plane doesn't need
        them."""
        with self._lock:
            ops: Dict[str, dict] = {}
            for op, rec in self._ops.items():
                ops[op] = {
                    "inv": rec.inv,
                    "compiles": rec.compiles,
                    "cache_hits": rec.cache_hits,
                    "cross_session_hits": rec.cross_session_hits,
                    "fallbacks": rec.fallbacks,
                    "compile_s": rec.compile_wall_s,
                    "flops": rec.flops,
                    "bytes_accessed": rec.bytes_accessed,
                    "donation_expected_bytes":
                        rec.donation_expected_bytes,
                    "donation_aliased_bytes":
                        rec.donation_aliased_bytes,
                    "donation_buffers": rec.donation_buffers,
                    "donation_aliased_buffers":
                        rec.donation_aliased_buffers,
                    "exchange_waves": rec.exchange_waves,
                    "dcn_messages": rec.dcn_messages,
                    "dcn_bytes": rec.dcn_bytes,
                    "ici_messages": rec.ici_messages,
                    "ici_bytes": rec.ici_bytes,
                    "flat_dcn_messages": rec.flat_dcn_messages,
                    "flat_dcn_bytes": rec.flat_dcn_bytes,
                    "plan_counts": dict(rec.plan_counts),
                    "spill_bytes": rec.spill_bytes,
                    "spill_rows": rec.spill_rows,
                    "spill_partitions": rec.spill_partitions,
                }
            hbm: dict = {
                "peak_bytes": self._hbm_peak_bytes,
                "samples": self._hbm_samples,
            }
            if self._hbm_limit_bytes:
                hbm["limit_bytes"] = self._hbm_limit_bytes
            if self._hbm_source:
                hbm["source"] = self._hbm_source
        return {"ops": ops, "hbm": hbm}

    def prometheus_lines(self, metric, line) -> None:
        """Append this recorder's gauges/counters through the hub's
        Prometheus helpers (metric(name, help, type) / line(name,
        labels, value))."""
        with self._lock:
            ops = dict(self._ops)
            hbm_last = self._hbm[-1] if self._hbm else None
            hbm_peak = self._hbm_peak_bytes
            hbm_limit = self._hbm_limit_bytes
        metric("bigslice_compile_total",
               "XLA program compilations and instrumented-cache hits "
               "per op.", "counter")
        for op, rec in ops.items():
            if rec.compiles:
                line("bigslice_compile_total",
                     {"op": op, "result": "compile"}, rec.compiles)
            local_hits = rec.cache_hits - rec.cross_session_hits
            if local_hits:
                line("bigslice_compile_total",
                     {"op": op, "result": "cache_hit"}, local_hits)
            if rec.cross_session_hits:
                line("bigslice_compile_total",
                     {"op": op, "result": "cross_session_hit"},
                     rec.cross_session_hits)
            if rec.fallbacks:
                line("bigslice_compile_total",
                     {"op": op, "result": "fallback"}, rec.fallbacks)
        metric("bigslice_compile_seconds_total",
               "Cumulative XLA compile wall time per op.", "counter")
        for op, rec in ops.items():
            if rec.compile_wall_s > 0:
                line("bigslice_compile_seconds_total", {"op": op},
                     f"{rec.compile_wall_s:.6f}")
        metric("bigslice_program_flops_total",
               "XLA cost-analysis FLOPs of compiled programs per op.",
               "counter")
        for op, rec in ops.items():
            if rec.flops > 0:
                line("bigslice_program_flops_total", {"op": op},
                     f"{rec.flops:.0f}")
        metric("bigslice_program_bytes_accessed_total",
               "XLA cost-analysis bytes accessed per op.", "counter")
        for op, rec in ops.items():
            if rec.bytes_accessed > 0:
                line("bigslice_program_bytes_accessed_total",
                     {"op": op}, f"{rec.bytes_accessed:.0f}")
        metric("bigslice_donation_bytes_total",
               "Wave-input bytes donated to XLA (expected) vs. "
               "actually consumed by the runtime (aliased).", "counter")
        for op, rec in ops.items():
            if rec.donation_buffers:
                line("bigslice_donation_bytes_total",
                     {"op": op, "kind": "expected"},
                     rec.donation_expected_bytes)
                line("bigslice_donation_bytes_total",
                     {"op": op, "kind": "aliased"},
                     rec.donation_aliased_bytes)
        metric("bigslice_exchange_messages_total",
               "Collective-exchange messages per op, split by "
               "interconnect axis kind (dcn/ici; dcn_flat = the "
               "flat-exchange counterfactual).", "counter")
        metric("bigslice_exchange_bytes_total",
               "Collective-exchange bucket bytes per op, split by "
               "interconnect axis kind.", "counter")
        for op, rec in ops.items():
            if not rec.exchange_waves:
                continue
            for axis, msgs, nbytes in (
                ("dcn", rec.dcn_messages, rec.dcn_bytes),
                ("ici", rec.ici_messages, rec.ici_bytes),
                ("dcn_flat", rec.flat_dcn_messages,
                 rec.flat_dcn_bytes),
            ):
                if msgs:
                    line("bigslice_exchange_messages_total",
                         {"op": op, "axis": axis}, msgs)
                    line("bigslice_exchange_bytes_total",
                         {"op": op, "axis": axis}, nbytes)
        if any(rec.plan_counts for rec in ops.values()):
            metric("bigslice_shuffle_plan_total",
                   "Shuffle-boundary exchange decisions per op "
                   "(in_program vs store-mediated spill; "
                   "exec/shuffleplan.py).", "counter")
            metric("bigslice_shuffle_spill_bytes_total",
                   "Bytes written through the out-of-core spill "
                   "exchange per op.", "counter")
            metric("bigslice_shuffle_spill_partitions_total",
                   "Spill-store partition entries written per op "
                   "(one per map wave x nonempty partition).",
                   "counter")
            for op, rec in ops.items():
                for plan, n in sorted(rec.plan_counts.items()):
                    line("bigslice_shuffle_plan_total",
                         {"op": op, "plan": plan}, n)
                if rec.spill_bytes:
                    line("bigslice_shuffle_spill_bytes_total",
                         {"op": op}, rec.spill_bytes)
                if rec.spill_partitions:
                    line("bigslice_shuffle_spill_partitions_total",
                         {"op": op}, rec.spill_partitions)
        if hbm_last is not None:
            metric("bigslice_hbm_bytes",
                   "Device-memory watermark (max across devices; "
                   "live_arrays fallback on backends without "
                   "allocator stats).", "gauge")
            line("bigslice_hbm_bytes", {"kind": "in_use"},
                 hbm_last["bytes_in_use"])
            line("bigslice_hbm_bytes", {"kind": "peak"}, hbm_peak)
            if hbm_limit:
                line("bigslice_hbm_bytes", {"kind": "limit"},
                     hbm_limit)
