"""Evidence that a job's work stayed on the mesh, and the meter of every
XLA compile of the process. Copied from ``chip_smoke.py`` (PR 22), where
these checks ran on the chip; kept here so that a later PR to the
program cannot change the yardstick."""

from __future__ import annotations


class EvidenceFailed(AssertionError):
    pass


def require(cond, msg: str) -> None:
    """A check that survives ``python -O``."""
    if not cond:
        raise EvidenceFailed(msg)


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


def device_evidence(sess, results, platform: str) -> dict:
    """Nothing left the device path, every planned device group of the
    task graphs behind ``results`` ran there, and every result column
    is resident on ``platform``."""
    ex = sess.executor
    summary, stats = ladders_silent(sess)
    planned = planned_device_groups(ex, results)
    with ex._lock:
        ran = planned & set(ex._outputs)
        outs = [ex._outputs[k] for k in ran]
    require(planned and ran == planned,
            f"{len(planned) - len(ran)} of {len(planned)} planned "
            f"device groups did not run on the device")
    for out in outs:
        for w in getattr(out, "waves", None) or [out]:
            for c in getattr(w, "cols", None) or ():
                devs = getattr(c, "devices", None)
                if devs is None:
                    continue
                plats = {d.platform for d in devs()}
                require(plats == {platform},
                        f"result column resident on {plats}, not "
                        f"{platform}")
    if ex.nmesh > 1:
        # Code that has never seen more than one real chip may place
        # everything on the first.
        received = [op["skew"]["rows"]
                    for op in summary["ops"].values() if "skew" in op]
        require(received and all(len(r) == ex.nmesh and min(r) > 0
                                 for r in received),
                f"shuffle partitions per device: {received}")
        require(any("all-to-all" in t for t in group_programs(ex)),
                "no compiled group program contains an all-to-all")
    return {
        "device_groups": len(ran),
        "planned_groups": len(planned),
        "seam_compiles": summary["device"]["totals"]["compiles"],
    }


def require_ops_on_mesh(summary, kinds) -> None:
    """Every op of these kinds, in every job the session ran, ran its
    waves through the mesh executor (only the mesh path records waves)
    — for the jobs whose Results are gone by the time we can look."""
    for kind in kinds:
        waves = [op.get("waves", {}).get("n_waves", 0)
                 for name, op in summary["ops"].items()
                 if name.split("@")[0] == kind]
        require(waves and min(waves) >= 1,
                f"a {kind} group did not run on the device: "
                f"{sorted(waves)[:4]}")
