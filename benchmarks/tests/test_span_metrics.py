"""The readers of the program's span table on synthetic before/after
summaries: window deltas a job, and None — never 0 — where the program
recorded no such span (a parent commit without the table)."""

import json
import os
import types

import pytest

import run
from benchmarks.harness import discover, report, spans

REPO = run.ROOT
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
SPAN_METRICS = [m for m in BENCH["per_layer"]
                if m["source"] == "program_span"
                and m["name"] != "scan_ms_per_job"]

BEFORE = {"spans": {
    "session.run": {"count": 6, "total_s": 6.0, "self_s": 0.006},
    "compile_tasks": {"count": 6, "total_s": 0.012, "self_s": 0.012},
    "evaluate": {"count": 6, "total_s": 5.9, "self_s": 0.03},
    "stage_wait": {"count": 276, "total_s": 0.3, "self_s": 0.01},
    "dispatch": {"count": 276, "total_s": 0.6, "self_s": 0.6},
    "settle": {"count": 276, "total_s": 1.5, "self_s": 1.5},
    "merge": {"count": 3, "total_s": 0.03, "self_s": 0.03},
    "readback": {"count": 6, "total_s": 0.12, "self_s": 0.12,
                 "bytes": 3 * 2 ** 20},
}}
AFTER = {"spans": {
    "session.run": {"count": 26, "total_s": 26.0, "self_s": 0.026},
    "compile_tasks": {"count": 26, "total_s": 0.052, "self_s": 0.052},
    "evaluate": {"count": 26, "total_s": 25.9, "self_s": 0.13},
    "stage_wait": {"count": 1196, "total_s": 1.3, "self_s": 0.05},
    "dispatch": {"count": 1196, "total_s": 2.6, "self_s": 2.6},
    "settle": {"count": 1196, "total_s": 6.5, "self_s": 6.5},
    "merge": {"count": 13, "total_s": 0.13, "self_s": 0.13},
    "readback": {"count": 26, "total_s": 0.52, "self_s": 0.52,
                 "bytes": 13 * 2 ** 20},
}}
#: 10 window jobs (two invocations each) between BEFORE and AFTER.
WANT = {
    "evaluator_self_ms_per_job": (0.020 + 0.040 + 0.100) * 1e3 / 10,
    "staging_exposed_ms_per_job": 100.0,
    "dispatch_ms_per_job": 200.0,
    "settle_wait_ms_per_job": 500.0,
    "merge_host_ms_per_job": 10.0,
    "readback_ms_per_job": 40.0,
    "readback_mib_per_job": 1.0,
}


def reading(before, after, jobs=10):
    window = types.SimpleNamespace(
        telemetry_before=before, telemetry_after=after,
        jobs=[object()] * jobs)
    return report.Reading(window=window, trace=None, peaks={}, chips=1,
                          work={})


def reader(name):
    return discover._load_module(
        os.path.join(REPO, "benchmarks", "metrics", name + ".py"),
        "bench_metric_" + name)


def test_every_span_metric_of_the_benchmark_is_covered():
    assert {m["name"] for m in SPAN_METRICS} == set(WANT)
    assert all("workloads" not in m for m in SPAN_METRICS)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_takes_the_window_delta_a_job(name):
    assert reader(name).read(reading(BEFORE, AFTER)) == \
        pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
@pytest.mark.parametrize("after", [{}, {"spans": {}},
                                   {"ops": {}, "spans": {"other": {
                                       "count": 1, "total_s": 1.0,
                                       "self_s": 1.0}}}])
def test_reader_finds_nothing_where_the_program_has_no_such_span(
        name, after):
    assert reader(name).read(reading({}, after)) is None


def test_window_delta_is_none_not_zero_and_counts_from_zero():
    r = reading({}, AFTER)
    assert spans.window_delta(r, "settle", "total_s") == 6.5
    assert spans.window_delta(r, "settle", "bytes") is None
    assert spans.window_delta(r, "absent", "total_s") is None
    assert spans.window_delta(reading(AFTER, AFTER), "merge",
                              "total_s") == 0.0
    assert spans.per_job(reading(BEFORE, AFTER, jobs=0), ("settle",),
                         "total_s", 1e3) is None
