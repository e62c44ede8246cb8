"""The reader of the per-op ``merge`` blocks on a hand-built
``Reading``: the window's delta of ``rows_bound`` over ``slots``, None
— never 0, never 0 / 0 — where the program has no such block (a parent
commit) or the window held no merge, and the entry ``BENCHMARK.json``
gives it."""

import json
import os
import types

import pytest

import run
from benchmarks.harness import discover, report

REPO = run.ROOT
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NAME = "merge_fill_share"


def op(merges=None, slots=0, rows=0, full=0):
    rec = {"waves": {"n_waves": 46, "dispatch_s": 0.1, "settle_s": 0.2}}
    if merges is not None:
        rec["merge"] = {"merges": merges, "waves": 46 * merges,
                        "slots": slots, "slots_full": full,
                        "rows_bound": rows}
    return rec


#: One set-up job before the window, two jobs inside it: the `lineitem`
#: side's merge (a wave's 70,650 rows in a 131,072-slot bucket) and the
#: second join's (640 rows in 1,024 slots of 135,168).
BEFORE = {"ops": {
    "filter@l": op(1, 6029312, 3249900, 6029312),
    "join@2": op(1, 47104, 29440, 6217728)}}
AFTER = {"ops": {
    "filter@l": op(3, 3 * 6029312, 3 * 3249900, 3 * 6029312),
    "join@2": op(3, 3 * 47104, 3 * 29440, 3 * 6217728),
    "filter@o": op(1, 786432, 764400, 3145728),
    "reduce@x": op()}}


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
    # Behind what the benchmark had: nothing of it moved.
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(NAME) > names.index("join_probe_mrows_per_s")


def test_share_is_the_windows_row_bound_over_the_slots_it_read():
    # filter@l and join@2 merged twice inside the window, filter@o
    # began in it and merged once; reduce@x merged nothing.
    assert read(BEFORE, AFTER) == pytest.approx(
        100.0 * (2 * 3249900 + 2 * 29440 + 764400)
        / (2 * 6029312 + 2 * 47104 + 786432))


def test_counts_from_zero_where_the_window_began_the_session():
    assert read({}, AFTER) == pytest.approx(
        100.0 * (3 * 3249900 + 3 * 29440 + 764400)
        / (3 * 6029312 + 3 * 47104 + 786432))


def test_full_waves_read_a_hundred_and_the_full_capacity_is_not_read():
    # What the merge would have read at full capacity is in the block
    # (``slots_full``) and not in the share.
    assert read({}, {"ops": {"const@x": op(1, 4096, 4096, 8192)}}) == 100.0
    assert read({}, {"ops": {"const@x": op(1, 4096, 0, 8192)}}) == 0.0


@pytest.mark.parametrize("after", [
    {}, {"ops": {}},
    {"ops": {"const@x": op(), "reduce@x": {"inv": 3}}},   # a parent
])
def test_a_program_without_the_block_reads_none(after):
    assert read({}, after) is None
    assert read(after, after) is None


def test_a_window_without_a_merge_reads_none_not_zero_over_zero():
    assert read(AFTER, AFTER) is None
