"""``tpch-q1`` and its cell: the generator's shapes, the plain ``int64``
reference against exact Python integers, the rehearsal size still
passing 2^32, the three controls coming out not correct, and the two
readers of the ``combine`` / ``waves`` blocks on a hand-built
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
CELL = "q1.sf1"


@pytest.fixture(scope="module")
def cell():
    return discover.find_cell(REPO, CELL, rehearsal=True)


def test_the_cell_is_one_chip_of_the_q1_configuration():
    (w,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == \
        ("tpch-q1", "closed1", 1)
    mine = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in mine} == {"map_combine_keep_share",
                                         "reduce_side_ms_per_job"}
    assert all(m["layer"] == "group program"
               and m["moves"] == "rows_per_s" for m in mine)


@pytest.mark.parametrize("seed", [1, 7, 2147483999])
def test_generator_keeps_the_sources_shapes(cell, seed):
    cfg, p = cell.cfg, cell.pipeline
    d = p.make_data(cfg, seed)
    flag, status, qty, price, disc, tax, ship = d.cols
    assert len(flag) == 4 * cfg["orders_per_sf"] * cfg["scale_factor"]
    assert all(c.dtype == np.int32 for c in d.cols)
    assert (qty.min(), qty.max()) == (1, cfg["quantity_max"])
    assert (disc.min(), disc.max()) == (0, 10)
    assert (tax.min(), tax.max()) == (0, 8)
    # price = quantity x a retail price of 900.00 .. 2098.99 + 0.01 steps.
    unit = price / qty
    assert 90000 <= unit.min() and unit.max() <= 90000 + 20000 + 99900
    # The four groups the flag rules allow, and no other.
    groups = set(zip(flag.tolist(), status.tolist()))
    A, N, R = (p.RETURNFLAGS.index(c) for c in "ANR")
    F, O = (p.LINESTATUSES.index(c) for c in "FO")
    assert groups == {(A, F), (N, F), (N, O), (R, F)}
    share = np.mean(ship <= d.cutoff)
    assert 0.975 < share < 0.995          # about 98.6 % pass
    n_o = np.mean((flag == N) & (status == O))
    assert 0.45 < n_o < 0.53              # 2,920,374 of 6,001,215
    assert d.shards == -(-len(flag) // cfg["rows_per_shard"])


def test_same_seed_same_rows_other_seed_other_rows(cell):
    a, b, c = (cell.pipeline.make_data(cell.cfg, s) for s in (5, 5, 6))
    assert all(np.array_equal(x, y) for x, y in zip(a.cols, b.cols))
    assert not np.array_equal(a.cols[3], c.cols[3])


def _exact(d, keep):
    """Q1's sums in Python's own integers, row by row."""
    out = {}
    for f, s, q, p, di, t in zip(*(c[keep].tolist() for c in d.cols[:6])):
        dp = p * (100 - di)
        row = (q, p, dp, dp * (100 + t), di, 1)
        acc = out.setdefault(f * 2 + s, [0] * 6)
        for i, v in enumerate(row):
            acc[i] += v
    return out


@pytest.mark.parametrize("seed", [2, 3000000019])
def test_reference_is_exact_and_the_rehearsal_passes_2_to_32(cell, seed):
    p = cell.pipeline
    d = p.make_data(cell.cfg, seed)
    want = _exact(d, d.cols[6] <= d.cutoff)
    got = p.reference(cell.cfg, d)
    assert list(got) == list(p.SUMS)
    for i, name in enumerate(p.SUMS):
        codes, sums = got[name]
        assert sums.dtype == np.int64
        assert dict(zip(codes.tolist(), sums.tolist())) == \
            {k: v[i] for k, v in want.items()}
    assert max(got["sum_base_price"][1]) > 1 << 32
    assert max(got["sum_charge"][1]) > 1 << 40


@pytest.mark.parametrize("seed", range(1, 9))
def test_every_control_reads_at_least_one_wrong_row(cell, seed):
    p = cell.pipeline
    d = p.make_data(cell.cfg, seed)
    want = p.reference(cell.cfg, d)
    assert compare.compare_answers(want, want)[0] == 0
    controls = p.controls(cell.cfg, d)
    assert set(controls) == {"row_dropped", "sums_in_int32",
                             "cutoff_exclusive"}
    for name, answers in controls.items():
        wrong, rows = compare.compare_answers(answers, want)
        assert wrong >= 1, name
        assert rows == 6 * len(want["count_order"][0])


def test_report_decodes_orders_and_averages_from_exact_sums(cell):
    p = cell.pipeline
    rows = p.report(np.array([2, 0, 1]), np.array([0, 0, 1]),
                    [np.array(v) for v in (
                        [10, 20, 30], [1000, 2000, 3000],
                        [90000, 180000, 270000],
                        [9450000, 18900000, 28350000],
                        [5, 10, 15], [2, 4, 6])])
    assert [r[:2] for r in rows] == [("A", "F"), ("N", "O"), ("R", "F")]
    assert rows[0][2:] == (20.0, 20.0, 18.0, 18.9, 5.0, 5.0, 0.025, 4)


# ------------------------------------------------- the two readers

def op(rows_in=None, rows_out=0, dispatch_s=None, settle_s=None):
    rec = {"waves": {"n_waves": 46}}
    if rows_in is not None:
        rec["combine"] = {"boundaries": 1, "rows_in": rows_in,
                          "rows_out": rows_out, "lowering": "dense",
                          "wide_columns": 5}
    if dispatch_s is not None:
        rec["waves"].update(dispatch_s=dispatch_s, settle_s=settle_s)
    return rec


#: One set-up job before the window, two jobs inside it.
BEFORE = {"ops": {"const_filter_map_prefixed@x": op(6000, 20, 0.1, 0.2),
                  "reduce@x": op(None, 0, 0.04, 0.06)}}
AFTER = {"ops": {"const_filter_map_prefixed@x": op(18000, 56, 0.3, 0.6),
                 "reduce@x": op(None, 0, 0.10, 0.20),
                 "reduce@y": op(None, 0, 0.01, 0.03)}}


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


def test_keep_share_is_rows_out_over_rows_in_inside_the_window():
    got = reader("map_combine_keep_share").read(reading(BEFORE, AFTER))
    assert got == pytest.approx(100.0 * 36 / 12000)


def test_reduce_side_is_dispatch_and_settle_of_the_later_groups():
    got = reader("reduce_side_ms_per_job").read(reading(BEFORE, AFTER))
    # reduce@x grew by 0.06 + 0.14 s, reduce@y began inside the window.
    assert got == pytest.approx(1e3 * (0.06 + 0.14 + 0.04) / 2)


@pytest.mark.parametrize("name", ["map_combine_keep_share",
                                  "reduce_side_ms_per_job"])
def test_a_program_without_the_blocks_reads_none(name):
    bare = {"ops": {"const@x": {"waves": {"n_waves": 46}},
                    "reduce@x": {"inv": 3}}}
    assert reader(name).read(reading(bare, bare)) is None
    assert reader(name).read(reading({}, {})) is None
