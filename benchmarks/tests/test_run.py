"""The command end to end in the CPU rehearsal: discovery by name, the
contract's line never printed off a TPU, the controls and the planted
faults all coming out not correct."""

import json
import os

import numpy as np
import pytest

import control
import dummy_files
import run
from benchmarks.harness import discover

REPO = run.ROOT
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))

@pytest.fixture(scope="session")
def full_root():
    return REPO


CELLS = [w["name"] for w in BENCH["workloads"]]


def rehearse(capsys, workload, root, trace=0, seed=5):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "0.5", "--trace", str(trace),
                   "--cpu-rehearsal"], root=root)
    out = capsys.readouterr()
    lines = [json.loads(ln) for ln in out.out.splitlines() if ln.strip()]
    return rc, lines, out.err


def test_without_a_tpu_exits_nonzero_and_prints_no_result(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "correct" not in out.out
    assert "needs a TPU" in out.err


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_each_cell_and_never_prints_the_contract_line(
        capsys, full_root, workload, trace):
    rc, lines, err = rehearse(capsys, workload, full_root, trace=trace)
    assert rc == 0
    assert all("correct" not in ln for ln in lines)  # no contract line
    last = lines[-1]["rehearsal"]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    assert list(last)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check ")
    bench = json.load(open(os.path.join(full_root, "BENCHMARK.json")))
    if trace == 0:
        wanted = {m["name"] for m in bench["end_to_end"]}
        assert set(last["metrics"]) == wanted
        assert all(m["value"] > 0 for m in last["metrics"].values())
    else:
        # Readers of the device trace find nothing on the CPU and are
        # left out — never written as 0.
        from_trace = {m["name"] for m in bench["per_layer"]
                      if m["source"] == "device_trace"}
        assert not from_trace & set(last["metrics"])
        assert "window_compiles" in last["metrics"]


def test_a_cell_added_as_files_only_is_found_and_run(capsys, tmp_path):
    dummy_files.write(str(tmp_path))
    rc, lines, _ = rehearse(capsys, "dummy.cell", root=str(tmp_path))
    assert rc == 0
    last = lines[-1]["rehearsal"]
    assert last["correct"] is True
    assert set(last["metrics"]) == {"dummy_rate", "setup_s"}
    rc, lines, _ = rehearse(capsys, "dummy.cell", root=str(tmp_path),
                            trace=1)
    # Its own per-layer metric, and not the one listed for another cell.
    assert lines[-1]["rehearsal"]["metrics"] == {
        "dummy_metric": {"value": 42.0, "unit": "count"}}


def test_an_unknown_name_is_an_error_not_a_default(tmp_path):
    dummy_files.write(str(tmp_path))
    with pytest.raises(discover.NotFound):
        discover.find_cell(str(tmp_path), "no.such.cell")


def test_harness_holds_no_cell_config_or_metric_name(full_root):
    bench = json.load(open(os.path.join(full_root, "BENCHMARK.json")))
    names = {e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]}
    names |= {w["traffic"] for w in bench["workloads"]}
    here = os.path.join(REPO, "benchmarks")
    files = [os.path.join(here, "run.py"), os.path.join(here, "control.py")]
    files += [os.path.join(here, "harness", f)
              for f in os.listdir(os.path.join(here, "harness"))
              if f.endswith(".py")]
    for path in files:
        text = open(path).read()
        found = [n for n in names if f'"{n}"' in text or f"'{n}'" in text]
        assert not found, f"{path} names {found}"


@pytest.mark.parametrize("workload", CELLS)
def test_controls_come_out_not_correct(capsys, full_root, workload):
    rc = control.main(["--workload", workload, "--seeds", "1,2,4",
                       "--seconds", "0.3", "--cpu-rehearsal"],
                      root=full_root)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert rc == 0 and lines[-1]["all_as_expected"] is True
    for ln in lines[:-1]:
        assert ln["program_correct"] is True
        assert ln["control_wrong_rows"]
        assert all(v > 0 for v in ln["control_wrong_rows"].values())


# ------------------------------------------------------- planted faults

def _half_left_out(job_cls):
    """Half of the batch never reaches the program."""
    class Halved(job_cls):
        def __init__(self, sess, data, keep):
            import copy

            half = copy.copy(data)
            n = len(data.keys) // 2
            half.keys, half.qty = data.keys[:n], data.qty[:n]
            super().__init__(sess, half, keep)
    return Halved


def _answer_altered(job_cls):
    """One value altered where the answer is produced."""
    class Altered(job_cls):
        def steps(self):
            steps = list(super().steps())
            name, last = steps[-1]

            def altered():
                last()
                for table, (k, v) in self.answers.items():
                    v = np.array(v)
                    v[len(v) // 2] += 1
                    self.answers[table] = (k, v)
            return steps[:-1] + [(name, altered)]
    return Altered


def _late_answer_altered(job_cls):
    """The full aggregate the timed path left on the device is wrong in
    one group (only jobs that keep it can show this)."""
    class Altered(job_cls):
        def late_answers(self):
            late = super().late_answers()
            for table, (k, v) in late.items():
                v = np.array(v)
                v[0] += 1
                late[table] = (k, v)
            return late
    return Altered


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", [_half_left_out, _answer_altered,
                                   _late_answer_altered])
def test_a_broken_timed_path_is_not_correct(capsys, monkeypatch, full_root,
                                            workload, fault):
    real = discover.find_cell

    def broken(*a, **kw):
        cell = real(*a, **kw)
        cell.pipeline.Job = fault(cell.pipeline.Job)
        return cell

    monkeypatch.setattr(discover, "find_cell", broken)
    rc, lines, err = rehearse(capsys, workload, full_root)
    last = lines[-1]["rehearsal"]
    assert rc == 0
    assert last["correct"] is False
    assert last["checks"]["wrong_rows"]["value"] > 0
    assert "check wrong_rows" in err


def test_a_job_that_raises_is_counted_failed(capsys, monkeypatch, full_root):
    real = discover.find_cell

    def broken(*a, **kw):
        cell = real(*a, **kw)

        class Raises(cell.pipeline.Job):
            def steps(self):
                def boom():
                    raise RuntimeError("planted")
                return (("run", boom),)
        cell.pipeline.Job = Raises
        return cell

    monkeypatch.setattr(discover, "find_cell", broken)
    rc, lines, _ = rehearse(capsys, CELLS[0], full_root)
    last = lines[-1]["rehearsal"]
    assert last["correct"] is False
    assert last["failed"] == last["attempted"] >= 1
