"""``tpch-q3`` and its cell: the generator's shapes, the plain numpy
reference against a row-at-a-time Python dict join, the rehearsal size
still passing 2^31, the five controls coming out not correct, and the
two readers of the ``join`` / ``waves`` blocks on a hand-built
``Reading`` — None, never 0, where the program has no such block (a
parent commit)."""

import json
import os
import types

import numpy as np
import pytest

import run
from benchmarks.harness import compare, discover, report

REPO = run.ROOT
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELL = "q3.sf1"
READERS = ("join_ms_per_job", "join_probe_mrows_per_s")
CONTROLS = {"row_dropped", "sums_in_int32", "date_inclusive",
            "segment_ignored", "one_order_a_customer"}


@pytest.fixture(scope="module")
def cell():
    return discover.find_cell(REPO, CELL, rehearsal=True)


def test_the_cell_is_one_chip_of_the_q3_configuration():
    (w,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == \
        ("tpch-q3", "closed1", 1)
    mine = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in mine} == set(READERS)
    assert all(m["layer"] == "group program"
               and m["moves"] == "rows_per_s"
               and m["source"] == "program_counter" for m in mine)
    cfg = json.load(open(os.path.join(
        REPO, "benchmarks", "configs", "tpch-q3", "config.json")))
    assert set(cfg["controls"]) == CONTROLS
    # SF 1 as the issue counts it: 46 + 12 + 2 shards of 2^17 rows.
    rows = (4 * cfg["orders_per_sf"], cfg["orders_per_sf"],
            cfg["customers_per_sf"])
    assert [-(-r // cfg["rows_per_shard"]) for r in rows] == [46, 12, 2]


@pytest.mark.parametrize("seed", [1, 7, 2147483999])
def test_generator_keeps_the_sources_shapes(cell, seed):
    cfg, p = cell.cfg, cell.pipeline
    d = p.make_data(cfg, seed)
    c_key, c_seg = d.customer
    o_cust, o_key, o_date, o_prio = d.orders
    l_key, price, disc, ship = d.lineitem
    customers, orders = cfg["customers_per_sf"], cfg["orders_per_sf"]
    assert all(c.dtype == np.int32
               for c in d.customer + d.orders + d.lineitem)
    # customer: dense keys, five segments at even odds.
    assert sorted(c_key.tolist()) == list(range(1, customers + 1))
    odds = np.bincount(c_seg, minlength=5) / customers
    assert len(odds) == 5 and odds.min() > 0.17 and odds.max() < 0.23
    # orders: dbgen's sparse keys; a third of the customers never order.
    assert len(o_key) == orders == len(set(o_key.tolist()))
    assert set(((o_key - 1) & 31).tolist()) == set(range(8))
    assert not np.any(o_cust % 3 == 0)
    assert o_cust.min() >= 1 and o_cust.max() <= customers
    assert len(np.unique(o_cust)) > 0.6 * customers
    assert (o_date.min(), o_date.max()) == (0, cfg["orderdate_days"])
    assert not o_prio.any()
    # lineitem: exactly 4 x orders lines, 1..7 an order, shipped 1..121
    # days after the order's date.
    assert len(l_key) == 4 * orders
    per_order = np.unique(l_key, return_counts=True)
    assert set(per_order[0].tolist()) == set(o_key.tolist())
    assert (per_order[1].min(), per_order[1].max()) == (1, 7)
    date_of = dict(zip(o_key.tolist(), o_date.tolist()))
    after = ship - np.array([date_of[k] for k in l_key.tolist()])
    assert (after.min(), after.max()) == (1, cfg["ship_after_days_max"])
    unit = price / d.qty
    assert 90000 <= unit.min() and unit.max() <= 90000 + 20000 + 99900
    assert (disc.min(), disc.max()) == (0, 10)
    # The three filters' pass rates: about 20 %, 48.6 % and 53.9 %.
    assert 0.17 < np.mean(c_seg == d.segment) < 0.23
    assert 0.47 < np.mean(o_date < d.date) < 0.50
    assert 0.525 < np.mean(ship > d.date) < 0.555
    assert d.shards == tuple(-(-n // cfg["rows_per_shard"])
                             for n in (customers, orders, 4 * orders))


def test_same_seed_same_rows_other_seed_other_rows(cell):
    a, b, c = (cell.pipeline.make_data(cell.cfg, s) for s in (5, 5, 6))
    for table in ("customer", "orders", "lineitem"):
        assert all(np.array_equal(x, y) for x, y in
                   zip(getattr(a, table), getattr(b, table)))
    assert not np.array_equal(a.price, c.price)
    assert not np.array_equal(a.o_custkey, c.o_custkey)


def _row_at_a_time(d):
    """Q3 as two dict joins and a dict of Python integers."""
    building = {k for k, s in zip(*(c.tolist() for c in d.customer))
                if s == d.segment}
    kept = {ok: (od, op) for oc, ok, od, op in
            zip(*(c.tolist() for c in d.orders))
            if od < d.date and oc in building}
    revenue = {}
    for lk, p, di, sd in zip(*(c.tolist() for c in d.lineitem)):
        if sd > d.date and lk in kept:
            revenue[lk] = revenue.get(lk, 0) + p * (100 - di)
    top = sorted(revenue, key=lambda k: (-revenue[k], kept[k][0], k))
    return revenue, kept, top[:10]


@pytest.mark.parametrize("seed", [2, 3000000019])
def test_reference_agrees_with_a_dict_join_and_passes_2_to_31(cell, seed):
    p = cell.pipeline
    d = p.make_data(cell.cfg, seed)
    revenue, kept, top = _row_at_a_time(d)
    got = p.reference(cell.cfg, d)
    assert list(got) == ["revenue", "o_orderdate", "o_shippriority",
                         "top10"]
    keys, sums = got["revenue"]
    assert sums.dtype == np.int64
    assert dict(zip(keys.tolist(), sums.tolist())) == revenue
    assert dict(zip(*(c.tolist() for c in got["o_orderdate"]))) == \
        {k: kept[k][0] for k in revenue}
    assert dict(zip(*(c.tolist() for c in got["o_shippriority"]))) == \
        {k: kept[k][1] for k in revenue}
    assert got["top10"][0].tolist() == list(range(10))
    assert got["top10"][1].tolist() == top
    assert max(revenue.values()) > 1 << 31
    # The report: best first, revenue in dollars.
    rows = p.report(got)
    assert [r[0] for r in rows] == top
    assert rows[0][1] == revenue[top[0]] / 1e4


@pytest.mark.parametrize("seed", range(1, 9))
def test_every_control_reads_at_least_one_wrong_row(cell, seed):
    p = cell.pipeline
    d = p.make_data(cell.cfg, seed)
    want = p.reference(cell.cfg, d)
    assert compare.compare_answers(want, want)[0] == 0
    controls = p.controls(cell.cfg, d)
    assert set(controls) == CONTROLS
    for name, answers in controls.items():
        wrong, _ = compare.compare_answers(answers, want)
        assert wrong >= 1, name


def test_work_counts_the_three_tables(cell):
    cfg, p = cell.cfg, cell.pipeline
    d = p.make_data(cfg, 1)
    w = p.work(cfg, d)
    orders, customers = cfg["orders_per_sf"], cfg["customers_per_sf"]
    assert w["input_rows"] == 4 * orders + orders + customers
    assert w["least_bytes"] == (4 * orders * 16 + orders * 16
                                + customers * 8
                                + cfg["answer_rows_per_sf"] * 20)


# ------------------------------------------------- the two readers

def op(probe_rows=None, dispatch_s=None, settle_s=None):
    rec = {"waves": {"n_waves": 46}}
    if probe_rows is not None:
        rec["join"] = {"waves": 46, "probe_rows": probe_rows,
                       "build_rows": 10, "matched_rows": 5,
                       "lowering": "sort", "wide_columns": 1}
    if dispatch_s is not None:
        rec["waves"].update(dispatch_s=dispatch_s, settle_s=settle_s)
    return rec


#: One set-up job before the window, two jobs inside it.
BEFORE = {"ops": {"joinlookup_map@x": op(1000, 0.1, 0.2),
                  "reduce@x": op(None, 0.04, 0.06)}}
AFTER = {"ops": {"joinlookup_map@x": op(3000, 0.3, 0.5),
                 "joinlookup_map_prefixed@y": op(6000, 0.02, 0.08),
                 "reduce@x": op(None, 0.10, 0.20)}}


def reading(before, after, jobs=2):
    window = types.SimpleNamespace(
        telemetry_before=before, telemetry_after=after,
        jobs=[object()] * jobs)
    return report.Reading(window=window, trace=None, peaks={}, chips=1,
                          work={})


def reader(name):
    return discover._load_module(
        os.path.join(REPO, "benchmarks", "metrics", name + ".py"),
        "bench_metric_" + name)


def test_join_ms_is_dispatch_and_settle_of_the_join_groups():
    got = reader("join_ms_per_job").read(reading(BEFORE, AFTER))
    # joinlookup_map@x grew by 0.2 + 0.3 s; the other began inside the
    # window; the reduce side has no join block and is left out.
    assert got == pytest.approx(1e3 * (0.5 + 0.1) / 2)


def test_probe_rate_is_probe_rows_over_those_seconds():
    got = reader("join_probe_mrows_per_s").read(reading(BEFORE, AFTER))
    assert got == pytest.approx((2000 + 6000) / 0.6 / 1e6)


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_block_reads_none(name):
    bare = {"ops": {"const@x": {"waves": {"n_waves": 46,
                                          "dispatch_s": 0.1,
                                          "settle_s": 0.1}},
                    "reduce@x": {"inv": 3}}}
    assert reader(name).read(reading(bare, bare)) is None
    assert reader(name).read(reading({}, {})) is None
