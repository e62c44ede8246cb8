"""The trace reduction on a small RECORDED chip trace (see its
``recorded`` key) and on hand-made traces whose answers are known."""

import json
import os

import pytest

from benchmarks.harness import tracered as t

HERE = os.path.dirname(os.path.abspath(__file__))
KERNEL = "bigslice_hash_partition"


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as fp:
        return t.reduce_trace(json.load(fp))


def test_recorded_busy_and_window(recorded):
    assert recorded.devices == 1
    assert recorded.window_s == pytest.approx(0.226524609, abs=1e-12)
    assert recorded.busy_s == pytest.approx(0.019055485, abs=1e-12)
    assert 0 < recorded.busy_s < recorded.window_s


def test_recorded_per_name_sums(recorded):
    # No op encloses another in this cut: self times add up to busy.
    assert sum(recorded.ops.values()) == pytest.approx(recorded.busy_s)
    top = recorded.top(recorded.ops, 3)
    assert [n for n, _ in top] == ["fusion s32[131072]",
                                   "fusion s32[262144]", "fusion s32[2]"]
    assert top[0][1] == pytest.approx(0.00850336, abs=1e-9)
    assert recorded.kernel_calls(KERNEL) == 3
    assert recorded.kernel_s(KERNEL) == pytest.approx(1.2812e-05, abs=1e-12)
    assert recorded.kernel_s("no_such_kernel") == 0.0


def test_recorded_gap_attribution(recorded):
    gaps = recorded.gaps
    # Idle and busy tile the window.
    assert sum(gaps.values()) + recorded.busy_s == pytest.approx(
        recorded.window_s)
    # The device sits idle through the whole scan, then through the
    # host's staging of the next job's first wave.
    assert gaps["bench:scan"] == pytest.approx(0.129828663, abs=1e-9)
    assert gaps["bench:run"] == pytest.approx(0.077008941, abs=1e-9)
    assert gaps["between jobs"] < 0.001
    assert recorded.top(gaps)[0][0] == "bench:scan"


def plain(device_events, spans, devices=1):
    planes = [{"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Ops", "events": device_events},
        {"name": "XLA Modules", "events": [["jit_x", 0, 10 ** 9]]}]}
        for i in range(devices)]
    planes.append({"name": "/host:CPU", "lines": [
        {"name": "python3", "events": spans}]})
    return {"planes": planes}


def test_union_self_time_and_gaps_on_a_known_trace():
    r = t.reduce_trace(plain(
        [["while", 100, 800], ["sort", 150, 200],
         ["fusion", 400, 100], ["fusion", 1000, 100],
         ["late", 5000, 100]],                       # outside the window
        [["bench:run", 0, 1500], ["bench:scan", 1600, 400]]))
    assert r.window_s == pytest.approx(2000e-9)
    assert r.busy_s == pytest.approx(900e-9)          # union, clipped
    assert r.ops == pytest.approx(
        {"while": 500e-9, "sort": 200e-9, "fusion": 200e-9})
    assert r.gaps == pytest.approx(
        {"bench:run": 600e-9, "bench:scan": 400e-9,
         "between jobs": 100e-9})


def test_busy_is_averaged_over_the_devices_used():
    r = t.reduce_trace(plain([["op", 0, 500]], [["bench:run", 0, 1000]],
                             devices=4))
    assert r.devices == 4
    assert r.busy_s == pytest.approx(500e-9)
    assert r.ops == pytest.approx({"op": 2000e-9})


def test_only_the_op_line_of_a_device_plane_counts():
    # The module line's one long event must not make the device busy.
    r = t.reduce_trace(plain([["op", 10, 10]], [["bench:run", 0, 100]]))
    assert r.busy_s == pytest.approx(10e-9)


def test_nothing_to_read_is_an_error_not_a_zero():
    with pytest.raises(t.NoDeviceOps):
        t.reduce_trace(plain([], [["bench:run", 0, 100]]))
    with pytest.raises(ValueError):
        t.reduce_trace(plain([["op", 0, 10]], []))


@pytest.mark.parametrize("name,label", [
    ("%fusion.2 = s32[131072]{0:T(1024)} fusion(s32[131072]{0} %x)",
     "fusion s32[131072]"),
    ("%sort.16 = (s32[131072]{0:T(1024)S(1)}, s32[131072]{0}) sort(...)",
     "sort s32[131072]"),
    ("%bigslice_hash_partition.1 = s32[1024,128]{1,0:T(8,128)S(1)} "
     "custom-call(...), custom_call_target=\"tpu_custom_call\"",
     "bigslice_hash_partition s32[1024,128]"),
    ("%copy-start.2 = (s32[8]{0}, u32[]{:S(2)}) copy-start(...)",
     "copy-start s32[8]"),
    ("fusion.12", "fusion"),
    ("all-to-all", "all-to-all"),
])
def test_op_label(name, label):
    assert t.op_label(name) == label


def test_share_of_peak_refuses_more_than_the_peak():
    from benchmarks.harness import peaks

    row = peaks.peaks_for("TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["bf16_flops_per_s"] == 197e12
    assert peaks.share_of_peak_pct(819e9, 819e9, 2.0, "x") == 50.0
    with pytest.raises(ArithmeticError):
        peaks.share_of_peak_pct(819e9, 819e9, 0.5, "x")
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
