"""The reader of the ``waves`` blocks' ``stages_overlapped`` on a
hand-built ``Reading``: the window's overlapped stages over the waits of
the ops that count them, None — never 0 / 0 — where the program has no
such field (a parent commit) or the window held no wait, and the entry
``BENCHMARK.json`` gives it."""

import json
import os
import types

import pytest

import run
from benchmarks.harness import discover, report

REPO = run.ROOT
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NAME = "stage_overlap_share"


def op(waits=None, ready=0, overlapped=None):
    rec = {"waves": {"n_waves": 46, "dispatch_s": 0.1, "settle_s": 0.2}}
    if waits is not None:
        rec["waves"].update(stage_waits=waits, stage_waits_ready=ready,
                            prefetch_blocked_s=0.0)
    if overlapped is not None:
        rec["waves"]["stages_overlapped"] = overlapped
    return rec


#: One set-up job before the window, two jobs inside it: a map side
#: that uploads (two workers) and a reduce side of views (one).
BEFORE = {"ops": {"const@x": op(45, 30, 40), "reduce@x": op(45, 45, 0)}}
AFTER = {"ops": {"const@x": op(135, 100, 124),
                 "reduce@x": op(135, 135, 0),
                 "filter@y": op(45, 45, 0),
                 "serial@z": op()}}


def reading(before, after, jobs=2):
    window = types.SimpleNamespace(
        telemetry_before=before, telemetry_after=after,
        jobs=[object()] * jobs)
    return report.Reading(window=window, trace=None, peaks={}, chips=1,
                          work={})


def read(before, after):
    reader = discover._load_module(
        os.path.join(REPO, "benchmarks", "metrics", NAME + ".py"),
        "bench_metric_" + NAME)
    return reader.read(reading(before, after))


def test_the_entry_is_a_counter_of_staging_in_every_cell():
    names = [m["name"] for m in BENCH["per_layer"]]
    m = BENCH["per_layer"][names.index(NAME)]
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter",
                 "layer": "staging + upload", "moves": "rows_per_s"}
    # Appended behind the books it is read like: nothing moved.
    assert names.index(NAME) > names.index("stage_ready_share")
    assert names.index(NAME) > names.index("mutex_wait_ms_per_job")


def test_share_is_the_windows_overlapped_stages_over_its_waits():
    # const@x grew by 90 waits (84 overlapped), reduce@x by 90 (0), and
    # filter@y began inside the window: 45 (0).
    assert read(BEFORE, AFTER) == pytest.approx(100.0 * 84 / 225)


def test_counts_from_zero_where_the_window_began_the_session():
    assert read({}, AFTER) == pytest.approx(100.0 * 124 / 315)


def test_one_worker_everywhere_reads_zero_not_none():
    assert read({}, {"ops": {"reduce@x": op(45, 45, 0)}}) == 0.0


@pytest.mark.parametrize("after", [
    {}, {"ops": {}},
    {"ops": {"const@x": op(45, 1), "reduce@x": {"inv": 3}}},  # a parent
])
def test_a_program_without_the_field_reads_none(after):
    assert read({}, after) is None
    assert read(after, after) is None


def test_a_window_without_a_wait_reads_none_not_zero_over_zero():
    assert read(AFTER, AFTER) is None
