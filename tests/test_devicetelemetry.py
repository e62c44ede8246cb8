"""Device-plane telemetry unit tests (utils/devicetelemetry.py): the
instrumented-program seam (AOT compile timing + cost/memory recording,
cache-hit accounting, fallback safety), the program-key digest, and
the windowed profiler gate (utils/xprof.py)."""

import numpy as np
import pytest

from bigslice_tpu.utils.devicetelemetry import (
    DeviceTelemetry,
    _InstrumentedProgram,
    program_digest,
)


def test_program_digest_stable_and_distinct():
    a = program_digest("op", "group", ((8,), 4))
    assert a == program_digest("op", "group", ((8,), 4))
    assert a != program_digest("op", "group", ((16,), 4))
    assert a != program_digest("op", "merge", ((8,), 4))


def test_instrumented_program_records_compile_then_hits():
    import jax

    dev = DeviceTelemetry()
    prog = dev.instrument(
        jax.jit(lambda x: x * 2), "op_a", 1, "group", (8,)
    )
    x = np.arange(8, dtype=np.int32)
    out = np.asarray(prog(x))
    assert (out == x * 2).all()
    s = dev.summary()
    entry = s["compile"]["op_a"]
    assert entry["compiles"] == 1
    assert entry["cache_hits"] == 0
    assert entry["compile_s"] > 0
    prog(x)
    prog(x)
    s = dev.summary()
    assert s["compile"]["op_a"]["compiles"] == 1
    assert s["compile"]["op_a"]["cache_hits"] == 2
    # cost/memory analysis rode along (CPU backend reports both).
    p = s["compile"]["op_a"]["programs"][0]
    assert p["kind"] == "group" and p["compile_s"] > 0
    assert "flops" in p or "bytes_accessed" in p


def test_instrumented_program_new_shape_new_compile():
    import jax

    dev = DeviceTelemetry()
    prog = dev.instrument(
        jax.jit(lambda x: x + 1), "op_b", None, "group", ()
    )
    prog(np.arange(8, dtype=np.int32))
    prog(np.arange(16, dtype=np.int32))  # new aval -> second compile
    s = dev.summary()["compile"]["op_b"]
    assert s["compiles"] == 2
    assert len(s["programs"]) == 2


def test_instrumented_program_falls_back_without_aot_api(caplog):
    """A callable with no .lower (or any AOT surprise) must run
    correctly through the plain path — instrumentation can never be
    load-bearing."""
    dev = DeviceTelemetry()
    calls = []

    def plain(x):
        calls.append(1)
        return x * 3

    prog = _InstrumentedProgram(plain, dev, "op_c", None, "group", "k")
    with caplog.at_level("WARNING", logger="bigslice.devicetelemetry"):
        assert prog(7) == 21
        assert prog(7) == 21
    assert prog._fell_back
    # Loud exactly once, naming the op and the error.
    warned = [r.getMessage() for r in caplog.records]
    assert len(warned) == 1
    assert "op_c" in warned[0] and "lower" in warned[0]
    assert len(calls) == 2
    # The abandonment itself is recorded (the counter that keeps
    # 'compiles == 0' serving claims honest); no compiles, no hits.
    entry = dev.summary()["compile"]["op_c"]
    assert entry["fallbacks"] == 1
    assert entry["compiles"] == 0 and entry["cache_hits"] == 0


def test_instrumented_donated_program_consumes_buffers():
    """Donation survives the AOT path: a donated device input is
    consumed by the instrumented call exactly as by the raw jit (the
    executor's restage-on-retry logic keys on is_deleted)."""
    import jax

    from bigslice_tpu.parallel.jitutil import (
        donation_supported,
        jit_maybe_donate,
    )

    if not donation_supported():
        pytest.skip("backend ignores donation")
    dev = DeviceTelemetry()
    prog = dev.instrument(
        jit_maybe_donate(lambda x: x + 1, (0,)), "op_d", None,
        "group", (),
    )
    x = jax.device_put(np.arange(8, dtype=np.int32))
    out = np.asarray(prog(x))
    assert (out == np.arange(8) + 1).all()
    assert x.is_deleted()


def test_summary_totals_roll_up():
    dev = DeviceTelemetry()
    dev.record_compile("a", 1, "group", "k1", 0.5,
                       cost={"flops": 100.0, "bytes_accessed": 10.0})
    dev.record_compile("b", 1, "merge", "k2", 0.25,
                       cost={"flops": 50.0})
    dev.record_cache_hit("a", 1, "group")
    t = dev.summary()["totals"]
    assert t["compiles"] == 2
    assert t["cache_hits"] == 1
    assert t["compile_s"] == 0.75
    assert t["flops"] == 150.0


def test_lookup_seconds_grow_with_every_cache_hit_call():
    """What the seam takes of a program call before it reaches the
    executable, by op in the compile block and as one sum in the
    totals — the first call of a signature compiles and adds none."""
    import jax

    dev = DeviceTelemetry()
    progs = [dev.instrument(jax.jit(lambda x: x + 1), op, 1, "group", ())
             for op in ("op_a", "op_b")]
    x = np.arange(8, dtype=np.int32)
    seen = []
    for _ in range(3):
        for prog in progs:
            prog(x)
        s = dev.summary()
        seen.append((s["totals"]["lookup_s"],
                     [s["compile"][op]["lookup_s"]
                      for op in ("op_a", "op_b")]))
    assert seen[0] == (0.0, [0.0, 0.0])         # compiles, not hits
    assert 0 < seen[1][0] < seen[2][0] < 0.1
    assert all(a < b for a, b in zip(seen[1][1], seen[2][1]))
    assert seen[2][0] == pytest.approx(sum(seen[2][1]), abs=2e-6)
    assert dev.summary()["compile"]["op_a"]["cache_hits"] == 2


def test_total_lookup_seconds_outlive_an_evicted_op():
    from bigslice_tpu.utils import devicetelemetry as dt

    dev = DeviceTelemetry()
    for i in range(dt.MAX_OPS + 10):
        dev.record_cache_hit(f"op{i}", None, "group", lookup_s=0.001)
    s = dev.summary()
    assert s["totals"]["lookup_s"] == pytest.approx(
        0.001 * (dt.MAX_OPS + 10))
    assert sum(op["lookup_s"] for op in s["compile"].values()) == \
        pytest.approx(0.001 * dt.MAX_OPS)


def test_hbm_block_counts_every_sample_and_its_seconds():
    """``samples`` and ``sample_s`` are sums since the session began,
    past the window of samples the block keeps."""
    import jax

    from bigslice_tpu.utils import devicetelemetry as dt

    dev = DeviceTelemetry()
    assert dev.summary()["hbm"] == {}
    devices = jax.devices()[:1]
    for wave in range(3):
        dev.sample_hbm(devices, op="op_a", inv=1, wave=wave)
    hbm = dev.summary()["hbm"]
    assert hbm["samples"] == 3 and 0 < hbm["sample_s"] < 1.0
    for _ in range(dt.MAX_HBM_SAMPLES):
        dev.record_hbm(1, 1, None, sample_s=0.001)
    later = dev.summary()["hbm"]
    assert later["samples"] == 3 + dt.MAX_HBM_SAMPLES
    assert later["sample_s"] == pytest.approx(
        hbm["sample_s"] + 0.001 * dt.MAX_HBM_SAMPLES, abs=2e-6)
    assert len(dev._hbm) == dt.MAX_HBM_SAMPLES
    assert dev.snapshot()["hbm"]["samples"] == later["samples"]


def test_op_records_bounded():
    from bigslice_tpu.utils import devicetelemetry as dt

    dev = DeviceTelemetry()
    for i in range(dt.MAX_OPS + 10):
        dev.record_cache_hit(f"op{i}", None, "group")
    assert len(dev._ops) == dt.MAX_OPS


# ------------------------------------------------- windowed profiler

def test_profiler_window_writes_loadable_trace(tmp_path):
    from bigslice_tpu.utils.xprof import Profiler

    out = Profiler().window(0.1, out_dir=str(tmp_path / "w"))
    assert out["files"], out
    assert any(f.endswith(".xplane.pb") for f in out["files"])


def test_profiler_busy_rejects_second_window(tmp_path):
    import threading
    import time

    from bigslice_tpu.utils.xprof import Profiler, ProfilerBusy

    prof = Profiler()
    started = threading.Event()
    done = []

    def long_window():
        started.set()
        done.append(prof.window(1.0, out_dir=str(tmp_path / "a")))

    t = threading.Thread(target=long_window)
    t.start()
    started.wait()
    time.sleep(0.2)
    with pytest.raises(ProfilerBusy):
        prof.window(0.1, out_dir=str(tmp_path / "b"))
    t.join()
    assert done
