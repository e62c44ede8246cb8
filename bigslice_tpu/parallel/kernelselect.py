"""Measured per-op kernel auto-selection: sort vs hash vs dense.

ROADMAP item 4's second half. The executor has three lowerings for a
keyed combine/shuffle boundary — the sort+segmented-scan pipeline, the
open-addressed hash table (parallel/hashagg.py; Mosaic kernel on TPU,
XLA scatter elsewhere), and the dense rank table (parallel/dense.py) —
and without a selector the choice is fixed (sort unless
``hash_aggregate`` asks for the table, dense on declaration). Dato's
argument (PAPERS.md) is that lowering decisions on dataflow
accelerators should be kernel-granular and *measured*; this module is
that decision maker.

``BIGSLICE_KERNEL_SELECT`` — unset (or ``off``) = no selector object
exists, no selection code path executes, lowerings are bit-identical
to the legacy defaults (the same chicken-bit contract as
BIGSLICE_ADAPTIVE / BIGSLICE_SHUFFLE). Unknown values fail loudly.

- ``static`` — choose from static signals only: lowering eligibility
  (the shared keyutil gate + op classification), platform (the Mosaic
  hash-aggregate kernel flips the TPU default), and whatever per-op
  ``cost_analysis()`` bytes the device plane already recorded.

- ``measured`` — additionally run ONE-SHOT timed probes per op-shape:
  the sort core and the hash core compile (through
  ``DeviceTelemetry.instrument``, so their cost/memory analyses are
  recorded and the executables land in the PR-14 cross-session program
  cache — exploration is amortized across every future Session) and
  race on a corpus shaped from the hub's per-shard key-count stats
  (PR 16, ``summary()['ops'][op]['skew']['per_shard']``). The winner
  must beat the loser by ``PROBE_MIN_MARGIN`` or the static choice
  stands — and a winner that *disagrees* with the static default must
  clear the stricter ``PROBE_OVERRIDE_MARGIN`` bar with fully
  separated samples (see the constant's rationale). Probes are
  single-process only: wall-clock diverges across
  SPMD ranks, and a rank-diverging lowering choice would deadlock the
  collective — multiprocess gangs take the static (deterministic)
  path, attributed as such.

Re-selection: the selector keeps the per-shard skew snapshot its
decision was based on; ``observe_wave`` (called from the adaptive
planner's wave boundary — the first cross-plane consumer of device
telemetry) drops the decision when the measured profile shifts by
``RESELECT_RATIO``, so the next program build re-probes against the
corpus the op is *now* seeing.

Every decision is attributed: counters + a bounded evidence log in
``telemetry_summary()['kernel_select']``, Prometheus
``bigslice_kernel_select_total{kernel,reason}``, and
``bigslice:kernel_select`` trace instants slicetrace renders as an
``invN:kernels`` section. With the knob unset none of these families
ever emits a sample.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

MODES = ("off", "static", "measured")

#: Bounded decision/evidence log (newest kept) — the adaptive
#: planner's MAX_DECISIONS contract.
MAX_DECISIONS = 256

#: Probe corpus rows when the hub has no per-shard stats for the op
#: yet (first boundary of a fresh Session).
DEFAULT_PROBE_ROWS = 4096

#: Probe rows ceiling — probing is a microbench, not a rerun.
MAX_PROBE_ROWS = 1 << 16

#: A measured winner must beat the loser by this factor or the static
#: choice stands (timer noise must not flap lowerings).
PROBE_MIN_MARGIN = 1.05

#: OVERRIDING the static platform default takes more than winning: the
#: probe times only the combine core, but the lowering also reshapes
#: the exchange downstream (the hash cascade's destination-contiguous
#: regions halve the pipeline's HBM passes — BASELINE r5) — an effect
#: a core microbench structurally cannot see. So a verdict that
#: *disagrees* with the static choice must be decisive (median margin
#: >= this) AND repeatable (every winner sample faster than every
#: loser sample) before it overturns the default; anything weaker
#: stands on the static choice, attributed ``measured:margin``.
PROBE_OVERRIDE_MARGIN = 1.25

#: Timed iterations per candidate (after one warm-up/compile call),
#: interleaved sort/hash/sort/hash so host drift hits both candidates
#: equally; the verdict compares MEDIANS (a GC pause can't flip a
#: lowering the way it could under best-of or mean).
PROBE_ITERS = 5

#: observe_wave drops a decision when the op's measured total-row or
#: skew profile shifts by this factor vs the decision-time snapshot.
RESELECT_RATIO = 2.0


def mode_from_env(env: Optional[str] = None) -> Optional[str]:
    """Parse ``BIGSLICE_KERNEL_SELECT``: unset/empty/``off`` → None
    (fully disengaged — the chicken bit); ``static``/``measured`` pass
    through; anything else fails loudly (a typo'd knob silently running
    legacy lowerings would defeat every A/B this exists for)."""
    if env is None:
        env = os.environ.get("BIGSLICE_KERNEL_SELECT", "")
    env = env.strip().lower()
    if not env or env == "off":
        return None
    if env not in MODES:
        raise ValueError(
            f"BIGSLICE_KERNEL_SELECT must be off|static|measured, "
            f"got {env!r}"
        )
    return env


def selector_from_env(hub=None) -> Optional["KernelSelector"]:
    """Session-construction entry point: a ``KernelSelector`` when the
    knob engages a mode, else None (callers hold ``selector is None``
    and run the legacy lowering defaults untouched)."""
    mode = mode_from_env()
    if mode is None:
        return None
    return KernelSelector(mode, hub)


class KernelSelectStats:
    """Decision attribution, shaped like exec/adaptive.AdaptiveStats:
    the telemetry hub calls through to ``summary()`` /
    ``prometheus_lines()`` only when a selector is attached — which is
    what guarantees zero ``bigslice_kernel_select_*`` samples with the
    knob unset."""

    def __init__(self, mode: str, eventer=None):
        self._lock = threading.Lock()
        self.mode = mode
        self._eventer = eventer
        # (kernel, reason) -> count.
        self._counts: Dict[Tuple[str, str], int] = {}
        self.decisions: List[dict] = []
        self._t0 = time.monotonic()

    def record(self, kernel: str, reason: str, **detail) -> None:
        """One selection: count it, log it (bounded), and emit a
        ``bigslice:kernel_select`` instant so the tracer/slicetrace see
        the choice in wave context. Never raises — selection
        bookkeeping must not be able to fail a run."""
        entry = {
            "kernel": kernel, "reason": reason,
            "t_s": round(time.monotonic() - self._t0, 6),
        }
        entry.update({k: v for k, v in detail.items()
                      if v is not None})
        with self._lock:
            key = (kernel, reason)
            self._counts[key] = self._counts.get(key, 0) + 1
            self.decisions.append(entry)
            if len(self.decisions) > MAX_DECISIONS:
                del self.decisions[
                    : len(self.decisions) - MAX_DECISIONS]
        ev = self._eventer
        if ev is not None:
            try:
                ev("bigslice:kernel_select", kernel=kernel,
                   reason=reason,
                   **{k: v for k, v in detail.items()
                      if v is not None})
            except Exception:
                pass

    def count(self, kernel: str, reason: Optional[str] = None) -> int:
        with self._lock:
            if reason is not None:
                return self._counts.get((kernel, reason), 0)
            return sum(n for (k, _), n in self._counts.items()
                       if k == kernel)

    @property
    def samples(self) -> int:
        with self._lock:
            return sum(self._counts.values())

    def summary(self) -> dict:
        """The ``telemetry_summary()['kernel_select']`` payload."""
        with self._lock:
            counts: Dict[str, Dict[str, int]] = {}
            for (kernel, reason), n in sorted(self._counts.items()):
                counts.setdefault(kernel, {})[reason] = n
            return {
                "mode": self.mode,
                "counts": counts,
                "decisions": [dict(d) for d in self.decisions],
            }

    def prometheus_lines(self, metric, line) -> None:
        with self._lock:
            counts = dict(self._counts)
            mode = self.mode
        metric("bigslice_kernel_select_mode",
               "Kernel auto-selection mode engaged by "
               "BIGSLICE_KERNEL_SELECT (parallel/kernelselect.py); "
               "absent entirely when the knob is unset.", "gauge")
        for m in ("static", "measured"):
            line("bigslice_kernel_select_mode", {"mode": m},
                 1 if m == mode else 0)
        metric("bigslice_kernel_select_total",
               "Kernel-selector lowering decisions by chosen kernel "
               "and reason (sort / hash / dense per combine or "
               "shuffle boundary).", "counter")
        for (kernel, reason), n in sorted(counts.items()):
            line("bigslice_kernel_select_total",
                 {"kernel": kernel, "reason": reason}, n)


class KernelSelector:
    """The per-boundary lowering decision maker. One per Session; the
    mesh executor keeps a reference and consults it only where
    ``self.kernel_select is not None`` — the structural chicken bit.

    Decisions cache per (op, site); ``token(op)`` folds the live
    decision set into the executor's program cache key and the
    cross-session serve digest, so two lowerings of one op can never
    collide on a compiled program."""

    def __init__(self, mode: str, hub=None):
        self.mode = mode
        self.hub = hub
        self.stats = KernelSelectStats(
            mode,
            eventer=getattr(hub, "_emit", None)
            if hub is not None else None,
        )
        self._lock = threading.Lock()
        # (opbase, site) -> {"kernel", "reason", "skew": snapshot}
        self._decisions: Dict[Tuple[str, str], dict] = {}
        # opbase -> hub op name (iterative drivers suffix op names;
        # the executor teaches us the real hub key at observe time).
        self._hub_alias: Dict[str, str] = {}
        # Advisory invocation hint (the executor sets it at program
        # build / wave boundaries) so decision instants land in the
        # right invN trace bucket. Attribution only — never keyed on.
        self.current_inv: Optional[int] = None
        # probe signature -> {"winner", "walls_ms"} — one-shot per
        # op-shape, shared across ops with identical signatures.
        self._probes: Dict[tuple, dict] = {}

    # -- decision ----------------------------------------------------------

    def choose(self, opbase: str, site: str, *, nkeys: int, nvals: int,
               ops: Tuple[str, ...], key_dtypes: Tuple[str, ...],
               val_dtypes: Tuple[str, ...], hash_eligible: bool,
               dense_bound: bool, legacy_hash: bool) -> str:
        """Pick the lowering for one combine/shuffle boundary:
        ``"dense" | "hash" | "sort"``. ``hash_eligible`` is the shared
        gate verdict (keyutil + op classification + blacklist);
        ``dense_bound`` means a dense key space is declared/discovered
        (the rank-table lowering takes precedence, as it always has);
        ``legacy_hash`` is what the executor does with no selector
        (its ``hash_aggregate`` setting) — the static baseline the
        measured probe must beat."""
        dkey = (opbase, site)
        with self._lock:
            cached = self._decisions.get(dkey)
        if cached is not None:
            return cached["kernel"]
        if dense_bound:
            kernel, reason, evidence = "dense", "dense-bound", {}
        elif not hash_eligible:
            kernel, reason, evidence = "sort", "hash-ineligible", {}
        else:
            kernel, reason, evidence = self._static_choice(
                opbase, legacy_hash)
            if self.mode == "measured":
                kernel, reason, evidence = self._measured_choice(
                    opbase, site, kernel, reason, evidence,
                    nkeys=nkeys, nvals=nvals, ops=ops,
                    key_dtypes=key_dtypes, val_dtypes=val_dtypes,
                )
        decision = {"kernel": kernel, "reason": reason,
                    "skew": self._skew_snapshot(opbase)}
        with self._lock:
            # First decision wins under a race: every later caller
            # (program key, trace, retry router) must agree with it.
            cached = self._decisions.setdefault(dkey, decision)
        if cached is decision:
            self.stats.record(kernel, reason, op=opbase, site=site,
                              inv=self.current_inv, **evidence)
        return cached["kernel"]

    def _static_choice(self, opbase: str,
                       legacy_hash: bool) -> Tuple[str, str, dict]:
        """The no-probe verdict: off-TPU the scatter lowering (a
        CPU-mesh A/B, no chip measurement), on real TPU the Mosaic
        hash-aggregate kernel when it can serve the shapes, else the
        executor's own setting. A backend gate that waits for a cell on
        each side of the choice (ROADMAP C2)."""
        import jax

        evidence = {}
        device = getattr(self.hub, "device", None) \
            if self.hub is not None else None
        if device is not None:
            try:
                b = device.cost_bytes(opbase)
                if b:
                    evidence["cost_bytes"] = int(b)
            except Exception:
                pass
        if jax.default_backend() != "tpu":
            return "hash", "static:cpu-scatter-wins", evidence
        from bigslice_tpu.parallel import pallas_kernels as pk

        if pk.interpret_capable():
            return "hash", "static:mosaic-kernel", evidence
        return ("hash" if legacy_hash else "sort",
                "static:tpu-no-kernel", evidence)

    # -- measured probes ---------------------------------------------------

    def _measured_choice(self, opbase: str, site: str,
                         static_kernel: str, static_reason: str,
                         static_evidence: dict, *, nkeys, nvals, ops,
                         key_dtypes, val_dtypes):
        import jax

        if jax.process_count() > 1:
            # Wall-clock diverges across ranks; a rank-diverging
            # lowering would deadlock the collective. Deterministic
            # static choice only.
            return (static_kernel, "static:multiprocess",
                    static_evidence)
        rows, distinct, skew = self._probe_corpus_shape(opbase)
        sig = ("kselect", nkeys, nvals, tuple(ops),
               tuple(key_dtypes), tuple(val_dtypes), rows, distinct)
        with self._lock:
            probe = self._probes.get(sig)
        if probe is None:
            try:
                probe = self._run_probe(opbase, sig, rows, distinct,
                                        nkeys, nvals, ops, val_dtypes)
            except Exception as e:  # probe failure must not fail a run
                probe = {"winner": None, "error": repr(e)}
            with self._lock:
                probe = self._probes.setdefault(sig, probe)
        evidence = dict(static_evidence)
        evidence.update({k: v for k, v in probe.items()
                         if k != "winner"})
        evidence["probe_rows"] = rows
        if skew is not None:
            evidence["max_rows"] = skew.get("max_rows")
        if probe.get("winner") is None:
            return static_kernel, "measured:probe-failed", evidence
        walls = probe.get("walls_ms", {})
        if len(walls) < 2 or min(walls.values()) <= 0:
            return static_kernel, "measured:margin", evidence
        winner = min(walls, key=walls.get)
        margin = max(walls.values()) / min(walls.values())
        if margin < PROBE_MIN_MARGIN:
            return static_kernel, "measured:margin", evidence
        if winner == static_kernel:
            return winner, "measured:probe", evidence
        # The probe disagrees with the platform default. A core-only
        # microbench can't price the exchange-shape consequences of
        # the lowering (PROBE_OVERRIDE_MARGIN above), so overturning
        # the default demands a decisive AND repeatable verdict:
        # median margin past the override bar, and complete sample
        # separation (the winner's worst beats the loser's best).
        samples = probe.get("walls_all_ms") or {
            k: [v] for k, v in walls.items()}
        loser = next(k for k in walls if k != winner)
        separated = (max(samples.get(winner, [float("inf")]))
                     < min(samples.get(loser, [0.0])))
        if margin >= PROBE_OVERRIDE_MARGIN and separated:
            return winner, "measured:probe", evidence
        return static_kernel, "measured:margin", evidence

    def _probe_corpus_shape(self, opbase: str):
        """Probe rows/distinct from the hub's measured per-shard stats
        for this op (PR 16) — the probe runs the corpus the op is
        actually seeing, not a synthetic guess — with defaults for the
        first boundary of a fresh pipeline."""
        skew = self._skew_snapshot(opbase)
        rows = DEFAULT_PROBE_ROWS
        if skew is not None and skew.get("max_rows"):
            rows = int(skew["max_rows"])
        rows = max(256, min(int(rows), MAX_PROBE_ROWS))
        distinct = max(1, rows // 4)
        return rows, distinct, skew

    def _skew_snapshot(self, opbase: str) -> Optional[dict]:
        hub = self.hub
        if hub is None:
            return None
        fn = getattr(hub, "skew_of_op", None)
        if fn is None:
            return None
        with self._lock:
            hub_op = self._hub_alias.get(opbase, opbase)
        try:
            return fn(hub_op)
        except Exception:
            return None

    def _run_probe(self, opbase: str, sig: tuple, rows: int,
                   distinct: int, nkeys: int, nvals: int, ops,
                   val_dtypes) -> dict:
        """Time the sort core against the hash core on a deterministic
        corpus of the op's measured shape. Both candidates compile
        through the device plane's instrument seam, so their
        cost/memory analyses are recorded and the executables land in
        the cross-session program cache (kind=``kselect``) — the next
        Session's probe is a cache hit, not a compile."""
        import jax
        import jax.numpy as jnp

        from bigslice_tpu.parallel import hashagg, segment

        ops = tuple(ops)

        def cfn(a, b):
            out = []
            for op, x, y in zip(ops, a, b):
                if op == "add":
                    out.append(x + y)
                elif op == "max":
                    out.append(jnp.maximum(x, y))
                else:
                    out.append(jnp.minimum(x, y))
            return tuple(out)

        sort_core = segment.make_segmented_reduce_masked(
            nkeys, nvals, cfn)
        hash_core = hashagg.make_hash_combine(nkeys, nvals, ops)

        def run_sort(valid, *cols):
            m, k, v = sort_core(valid, cols[:nkeys], cols[nkeys:])
            return m, k, v

        def run_hash(valid, *cols):
            m, k, v, ov = hash_core(valid, cols[:nkeys],
                                    cols[nkeys:])
            return m, k, v, ov

        rng = np.random.default_rng(0xB165)
        keys = [rng.integers(0, distinct, rows).astype(np.int32)
                for _ in range(nkeys)]
        vals = [np.ones(rows, np.dtype(d)) for d in val_dtypes]
        valid = np.ones(rows, bool)
        args = [jnp.asarray(valid)] + [jnp.asarray(c)
                                       for c in keys + vals]

        device = getattr(self.hub, "device", None) \
            if self.hub is not None else None
        progs = {}
        for name, fn in (("sort", run_sort), ("hash", run_hash)):
            prog = jax.jit(fn)
            if device is not None:
                # fns=() → a purely structural serve key: any Session
                # probing this op-shape shares the executable.
                prog = device.instrument(
                    prog, opbase, None, "kselect",
                    (name,) + sig[1:], fns=(), extra=None,
                )
            jax.block_until_ready(prog(*args))  # compile / cache hit
            progs[name] = prog
        # Interleaved timing: sort,hash,sort,hash… so a host-load
        # drift during the probe window penalizes both candidates.
        samples: Dict[str, List[float]] = {n: [] for n in progs}
        for _ in range(PROBE_ITERS):
            for name, prog in progs.items():
                t0 = time.perf_counter()
                jax.block_until_ready(prog(*args))
                samples[name].append(time.perf_counter() - t0)
        walls_ms = {n: round(float(np.median(s)) * 1e3, 4)
                    for n, s in samples.items()}
        winner = min(walls_ms, key=walls_ms.get)
        return {
            "winner": winner,
            "walls_ms": walls_ms,
            "walls_all_ms": {n: [round(x * 1e3, 4) for x in s]
                             for n, s in samples.items()},
        }

    # -- program-key token / re-selection ---------------------------------

    def token(self, opbase: str) -> tuple:
        """The op's live decision set, repr-stable — folded into the
        executor's program cache key AND the cross-session serve
        digest, so programs compiled under different selections can
        never collide."""
        with self._lock:
            return tuple(sorted(
                (site, d["kernel"])
                for (op, site), d in self._decisions.items()
                if op == opbase
            ))

    def decision(self, opbase: str, site: str) -> Optional[str]:
        with self._lock:
            d = self._decisions.get((opbase, site))
            return None if d is None else d["kernel"]

    def observe_wave(self, opbase: str,
                     hub_op: Optional[str] = None) -> None:
        """Wave-boundary re-selection consult (called via the adaptive
        planner — exec/adaptive.py): when the op's measured per-shard
        profile has shifted by RESELECT_RATIO against the snapshot a
        decision was based on, drop the decision (and its probe) so
        the next program build re-decides against current reality.
        ``hub_op`` is the hub's key for this op when it differs from
        the decision-time base name (iterative #N suffixes)."""
        if self.mode != "measured":
            return
        if hub_op is not None and hub_op != opbase:
            with self._lock:
                self._hub_alias[opbase] = hub_op
        now = self._skew_snapshot(opbase)
        if not now:
            return
        stale: List[Tuple[str, str]] = []
        with self._lock:
            for (op, site), d in self._decisions.items():
                if op != opbase:
                    continue
                if d["kernel"] not in ("hash", "sort"):
                    # dense-bound / hash-ineligible verdicts are
                    # static facts — no profile shift changes them.
                    continue
                if self._shifted(d.get("skew"), now):
                    stale.append((op, site))
            for key in stale:
                del self._decisions[key]
            if stale:
                self._probes.clear()
        for op, site in stale:
            self.stats.record(
                "reselect", "measured:skew-shift", op=op, site=site,
                inv=self.current_inv,
                max_rows=now.get("max_rows"),
                total_rows=now.get("total_rows"),
            )

    @staticmethod
    def _shifted(then: Optional[dict], now: dict) -> bool:
        if not then:
            # Decided before the op had any measured profile: the
            # first real measurement IS a profile shift.
            return bool(now.get("total_rows"))
        for field in ("max_rows", "total_rows"):
            a = float(then.get(field) or 0.0)
            b = float(now.get(field) or 0.0)
            if a <= 0 and b <= 0:
                continue
            lo, hi = min(a, b), max(a, b)
            if lo <= 0 or hi / lo >= RESELECT_RATIO:
                return True
        return False
