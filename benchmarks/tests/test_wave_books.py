"""The nine readers of the wave loop's books on synthetic before/after
summaries: the window's delta a job, None — never 0 — where the
program recorded no such span, block or field (a parent commit), and
the entries ``BENCHMARK.json`` gives them."""

import json
import os
import types

import pytest

import run
from benchmarks.harness import discover, report

REPO = run.ROOT
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
JOBS = 10


def row(count, total, self_s=None, **more):
    return {"count": count, "total_s": total,
            "self_s": total if self_s is None else self_s, **more}


def waves(waits=None, ready=0, blocked=None, **more):
    block = {"n_waves": 46, "dispatch_s": 0.1, "settle_s": 0.2, **more}
    if waits is not None:
        block.update(stage_waits=waits, stage_waits_ready=ready)
    if blocked is not None:
        block["prefetch_blocked_s"] = blocked
    return {"waves": block}


#: Two set-up jobs before the window, JOBS jobs inside it.
BEFORE = {
    "spans": {
        "group": row(6, 1.0, 0.2), "dispatch": row(276, 0.3, 0.1),
        "enqueue": row(276, 0.2), "stage_wait": row(276, 0.1),
        "sync.keyrange": row(2, 0.02, bytes=16),
        "sync.subid_count": row(2, 0.01, bytes=368),
        "sync.shuffle_counts": row(2, 0.03, bytes=8),
        "mutex_wait": row(4, 0.01),
    },
    "ops": {"const@x": waves(90, 10, 0.001),
            "reduce@x": waves(90, 80, 0.5)},
    "device": {"totals": {"cache_hits": 300, "lookup_s": 0.02},
               "hbm": {"samples": 276, "sample_s": 0.03}},
}
AFTER = {
    "spans": {
        "group": row(36, 6.0, 0.9), "dispatch": row(1656, 1.8, 0.5),
        "enqueue": row(1656, 1.3), "stage_wait": row(1656, 0.9),
        "sync.keyrange": row(12, 0.12, bytes=96),
        "sync.subid_count": row(12, 0.06, bytes=2208),
        "sync.shuffle_counts": row(12, 0.23, bytes=48),
        # A span the issue allows a later builder: summed by prefix.
        "sync.other": row(10, 0.05),
        "mutex_wait": row(44, 0.41),
    },
    "ops": {"const@x": waves(540, 40, 0.004),
            "reduce@x": waves(540, 510, 3.0),
            "filter@y": waves(450, 450, 0.25),
            "serial@z": waves()},
    "device": {"totals": {"cache_hits": 2000, "lookup_s": 0.14},
               "hbm": {"samples": 1656, "sample_s": 0.18}},
}
WANT = {
    "enqueue_ms_per_job": 110.0,
    "dispatch_self_ms_per_job": 40.0,
    "program_lookup_ms_per_job": 12.0,
    "sync_ms_per_job": (0.10 + 0.05 + 0.20 + 0.05) * 1e3 / JOBS,
    "group_self_ms_per_job": 70.0,
    "stage_ready_share": 100.0 * (30 + 430 + 450) / (450 + 450 + 450),
    "prefetch_blocked_ms_per_job": (0.003 + 2.5 + 0.25) * 1e3 / JOBS,
    "hbm_sample_ms_per_job": 15.0,
    "mutex_wait_ms_per_job": 40.0,
}
#: A parent commit's summary: ``dispatch``, ``group`` and ``mutex_wait``
#: are there and mean something else; no ``enqueue``, no ``sync.*``, no
#: ``lookup_s``, no ``sample_s``, no ``stage_waits``.
PARENT = {
    "spans": {k: v for k, v in AFTER["spans"].items()
              if k in ("group", "dispatch", "stage_wait", "mutex_wait")},
    "ops": {"const@x": waves(), "reduce@x": {"inv": 3}},
    "device": {"totals": {"cache_hits": 2000},
               "hbm": {"samples": 256, "peak_bytes": 1}},
}


def reading(before, after, jobs=JOBS):
    window = types.SimpleNamespace(
        telemetry_before=before, telemetry_after=after,
        jobs=[object()] * jobs)
    return report.Reading(window=window, trace=None, peaks={}, chips=1,
                          work={})


def reader(name):
    return discover._load_module(
        os.path.join(REPO, "benchmarks", "metrics", name + ".py"),
        "bench_metric_" + name)


def test_the_nine_entries_are_appended_as_counters_of_their_layers():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert all(names.count(name) == 1 for name in WANT)
    got = {m["name"]: m for m in BENCH["per_layer"]}
    layers = {"program_lookup_ms_per_job": "compile",
              "stage_ready_share": "staging + upload",
              "prefetch_blocked_ms_per_job": "staging + upload",
              "hbm_sample_ms_per_job": "device"}
    higher = {"stage_ready_share", "prefetch_blocked_ms_per_job"}
    for name in WANT:
        want = {"name": name,
                "unit": "%" if name == "stage_ready_share" else "ms",
                "better": "higher" if name in higher else "lower",
                "source": "program_counter",
                "layer": layers.get(name, "group program"),
                "moves": "rows_per_s"}
        # No ``workloads`` list, mutex_wait_ms_per_job neither: a cell
        # whose groups never contend reads 0.0 (below).
        assert got[name] == want


@pytest.mark.parametrize("name", list(WANT))
def test_reader_takes_the_window_delta_a_job(name):
    assert reader(name).read(reading(BEFORE, AFTER)) == \
        pytest.approx(WANT[name])


@pytest.mark.parametrize("name", list(WANT))
@pytest.mark.parametrize("after", [{}, {"spans": {}, "ops": {},
                                        "device": {}}, PARENT])
def test_reader_finds_nothing_in_a_program_without_the_books(name, after):
    assert reader(name).read(reading({}, after)) is None
    assert reader(name).read(reading(after, after)) is None


@pytest.mark.parametrize("name", list(WANT))
def test_reader_counts_from_zero_where_the_window_began_the_session(name):
    assert reader(name).read(reading({}, AFTER)) is not None
    if name != "stage_ready_share":       # a share, not a job's part
        assert reader(name).read(reading({}, AFTER, jobs=0)) is None


def test_the_two_halves_of_a_dispatch_sum_to_it():
    r = reading(BEFORE, AFTER)
    assert reader("enqueue_ms_per_job").read(r) \
        + reader("dispatch_self_ms_per_job").read(r) == pytest.approx(
            reader("dispatch_ms_per_job").read(r))


def test_a_window_without_a_wait_reads_none_not_zero_over_zero():
    assert reader("stage_ready_share").read(reading(AFTER, AFTER)) is None
    assert reader("stage_ready_share").read(reading(
        {}, {"ops": {"const@x": waves(46, 0)}})) == 0.0


def test_a_stager_that_never_blocked_reads_zero_not_none():
    assert reader("prefetch_blocked_ms_per_job").read(reading(
        {}, {"ops": {"const@x": waves(45, 0, 0.0)}})) == 0.0


def test_no_contended_wait_in_a_program_with_the_books_reads_zero():
    after = {"spans": {k: v for k, v in AFTER["spans"].items()
                       if k != "mutex_wait"}}
    assert reader("mutex_wait_ms_per_job").read(reading({}, after)) == 0.0
    assert reader("group_self_ms_per_job").read(reading({}, after)) == \
        pytest.approx(90.0)
