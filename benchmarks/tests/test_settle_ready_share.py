"""The reader of the ``waves`` blocks' ``settles`` / ``settles_ready``
on a hand-built ``Reading``: the window's delta, None — never 0, never
0 / 0 — where the program has no such fields (a parent commit) or the
window held no settle, and the entry ``BENCHMARK.json`` gives it."""

import json
import os
import types

import pytest

import run
from benchmarks.harness import discover, report

REPO = run.ROOT
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NAME = "settle_ready_share"


def op(settles=None, ready=0):
    rec = {"waves": {"n_waves": 46, "dispatch_s": 0.1, "settle_s": 0.2}}
    if settles is not None:
        rec["waves"].update(settles=settles, settles_ready=ready)
    return rec


#: One set-up job before the window, two jobs inside it.
BEFORE = {"ops": {"const@x": op(46, 20), "reduce@x": op(46, 46)}}
AFTER = {"ops": {"const@x": op(138, 66), "reduce@x": op(138, 136),
                 "filter@y": op(46, 46)}}


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


def test_the_entry_is_a_counter_of_the_group_program_in_every_cell():
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "group program",
                 "moves": "rows_per_s"}


def test_share_is_the_windows_ready_settles_over_its_settles():
    # const@x grew by 92 settles (46 ready), reduce@x by 92 (90), and
    # filter@y began inside the window: 46 (46).
    assert read(BEFORE, AFTER) == pytest.approx(
        100.0 * (46 + 90 + 46) / (92 + 92 + 46))


def test_counts_from_zero_where_the_window_began_the_session():
    assert read({}, AFTER) == pytest.approx(
        100.0 * (66 + 136 + 46) / (138 + 138 + 46))


def test_no_settle_ready_reads_zero_not_none():
    assert read({}, {"ops": {"reduce@x": op(46, 0)}}) == 0.0


@pytest.mark.parametrize("after", [
    {}, {"ops": {}},
    {"ops": {"const@x": op(), "reduce@x": {"inv": 3}}},   # a parent
])
def test_a_program_without_the_fields_reads_none(after):
    assert read({}, after) is None
    assert read(after, after) is None


def test_a_window_without_a_settle_reads_none_not_zero_over_zero():
    assert read(AFTER, AFTER) is None
