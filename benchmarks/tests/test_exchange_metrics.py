"""The readers of the ``exchange`` layer on a hand-built ``Reading``:
window deltas of the program's per-op ``exchange`` blocks, the
collectives' share of a trace, None — never 0 — where the program has
no such block (a parent commit) or the run was not traced, and an error
where a traced run holds no collective. Then the four-chip cell's
rehearsal on four forced host devices."""

import json
import os
import types

import pytest

import run
from benchmarks.harness import discover, report, tracered

REPO = run.ROOT
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
EXCHANGE = [m for m in BENCH["per_layer"] if m["layer"] == "exchange"]
CELL = "q18agg.sf4.x4"


def block(waves, ici_bytes, slack, retries, recv_rows):
    return {"exchange": {"waves": waves, "ici_bytes": ici_bytes,
                         "ici_messages": 12 * waves, "slack": slack,
                         "retries": retries, "recv_rows": recv_rows}}


#: A set-up job that retried twice on its way from slack 1.0 to 1.25,
#: one op half done when the window began, and no block for a filter.
BEFORE = {"ops": {
    "reduce@setup": block(48, 48 * 2 ** 20, 1.25, 2, [10, 10, 10, 10]),
    "reduce@straddles": block(20, 20 * 2 ** 20, 1.25, 0, []),
    "filter@setup": {"inv": 2},
}}
AFTER = {"ops": {
    **BEFORE["ops"],
    "reduce@straddles": block(46, 46 * 2 ** 20, 1.25, 0,
                              [100, 104, 96, 100]),
    "reduce@job1": block(47, 47 * 2 ** 20, 1.5, 1, [100, 96, 100, 104]),
    "filter@job1": {"inv": 9},
}}
#: 2 window jobs: 26 + 47 waves of 1 MiB, one retry, 800 rows of which
#: the fullest device holds 204.
WANT = {
    "exchange_mib_per_job": (26 + 47) / 2,
    "exchange_retries_per_job": 0.5,
    "shuffle_slack_settled": 1.5,
    "recv_rows_max_over_mean": 204 * 4 / 800,
}


def reading(before, after, jobs=2, trace=None):
    window = types.SimpleNamespace(
        telemetry_before=before, telemetry_after=after,
        jobs=[object()] * jobs)
    return report.Reading(window=window, trace=trace, peaks={}, chips=4,
                          work={})


def reader(name):
    return discover._load_module(
        os.path.join(REPO, "benchmarks", "metrics", name + ".py"),
        "bench_metric_" + name)


def traced(ops: dict, busy_s=2.0, devices=4):
    return tracered.TraceReduction(window_s=3.0, busy_s=busy_s,
                                   devices=devices, ops=ops, gaps={},
                                   events=[])


def test_the_exchange_layer_is_five_metrics_of_the_four_chip_cell():
    assert {m["name"] for m in EXCHANGE} == \
        set(WANT) | {"exchange_share_of_busy"}
    assert all(m["workloads"] == [CELL] and m["moves"] == "rows_per_s"
               for m in EXCHANGE)


@pytest.mark.parametrize("name", sorted(WANT))
def test_counter_reader_takes_the_window_delta(name):
    assert reader(name).read(reading(BEFORE, AFTER)) == \
        pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
@pytest.mark.parametrize("after", [
    {}, {"ops": {}},
    # A parent commit: ops, and the device plane's table, but no block.
    {"ops": {"reduce@job1": {"inv": 1, "skew": {"rows": [1, 2]}}},
     "device": {"exchange": {"reduce@job1": {"waves": 46,
                                             "ici_bytes": 1}}}},
    # Nothing exchanged inside the window.
    BEFORE,
])
def test_counter_reader_finds_nothing_without_the_block(name, after):
    assert reader(name).read(reading(BEFORE, after)) is None


def test_no_window_jobs_is_none_not_a_division():
    for name in ("exchange_mib_per_job", "exchange_retries_per_job"):
        assert reader(name).read(reading(BEFORE, AFTER, jobs=0)) is None


def test_share_of_busy_sums_the_collectives_over_the_devices_used():
    # As the chip's trace labels them: the instruction's own name.
    ops = {"all_to_all s32[4,1,32768]": 0.10, "all-to-all": 0.02,
           "all-reduce": 0.04, "all-reduce-start s32[]": 0.01,
           "sort s32[131072]": 3.0, "fusion s32[4]": 1.0}
    got = reader("exchange_share_of_busy").read(
        reading({}, {}, trace=traced(ops)))
    assert got == pytest.approx(100 * 0.17 / (2.0 * 4))


def test_share_of_busy_untraced_is_none_and_no_collective_is_an_error():
    share = reader("exchange_share_of_busy")
    assert share.read(reading(BEFORE, AFTER)) is None
    with pytest.raises(LookupError, match="all-to-all"):
        share.read(reading({}, {}, trace=traced(
            {"sort s32[131072]": 3.0, "fusion all-to-all-like": 1.0})))


def test_four_chip_cell_rehearses_on_four_forced_host_devices(capsys):
    """Seed 5: at the rehearsal's size the CPU's default lowering of a
    keyed combine (the hash aggregate) overflows its claim cascade on
    seeds 3 and 6 of 1..10 and is blacklisted for the session, which
    the evidence checks count as ``off_mesh`` (PERF.md section 6, PR
    25); the TPU takes the sort pipeline and has no such seeds. Seeds
    1, 2, 4, 5 and 7..10 stay on the lowering they began with."""
    import jax

    assert len(jax.devices()) >= 4, "benchmarks/conftest.py forces 4"
    rc = run.main(["--workload", CELL, "--seed", "5", "--seconds", "0.5",
                   "--trace", "1", "--cpu-rehearsal"])
    out = capsys.readouterr().out
    lines = [json.loads(ln) for ln in out.splitlines() if ln.strip()]
    assert rc == 0 and lines[0]["count"] == 4
    last = lines[-1]["rehearsal"]
    assert last["correct"] is True
    assert last["checks"]["off_mesh"]["value"] == 0
    assert last["device"]["count"] == 4
    # The counters of the exchange are read; the trace's share is not
    # (a CPU trace has no device plane).
    assert set(WANT) <= set(last["metrics"])
    assert "exchange_share_of_busy" not in last["metrics"]
    rehearsal = discover.find_cell(REPO, CELL, rehearsal=True).cfg
    rows = 4 * rehearsal["orders_per_sf"] * rehearsal["scale_factor"]
    waves = -(-rows // rehearsal["rows_per_shard"]) // 4
    assert waves >= 3
    # Every wave moves 4 x 3 whole buckets of (subid, key, sum) rows.
    per_wave = 12 * 12 * rehearsal["rows_per_shard"] // 4
    assert last["metrics"]["exchange_mib_per_job"]["value"] == \
        pytest.approx(waves * per_wave / 2 ** 20)
