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
    # Integer answers, compared exactly: no tolerance, no margin.
    assert list(last["checks"]) == ["wrong_rows", "jobs_failed",
                                    "off_mesh", "jobs_uncompared"]
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


# ------------------------------------- a float answer within a tolerance

FLOAT = "dummy.float"


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 2147483659])
def test_a_float_cell_is_correct_within_its_tolerance(capsys, tmp_path,
                                                      seed):
    dummy_files.write(str(tmp_path))
    rc, lines, err = rehearse(capsys, FLOAT, str(tmp_path), seed=seed)
    last = lines[-1]["rehearsal"]
    assert rc == 0 and last["correct"] is True
    assert last["checks"]["wrong_rows"]["value"] == 0
    assert list(last["checks"])[-1] == "tolerance_margin"
    margin = last["checks"]["tolerance_margin"]
    assert margin["limit"] == 1.0 and 0 < margin["value"] < 1
    assert err.strip().splitlines()[-1].startswith("check tolerance_margin")


def _one_element_past_its_tolerance(job_cls, tol):
    """The first job's answer has one element of one vector moved by ten
    times its tolerance where the answer is produced."""
    class Moved(job_cls):
        moved = False

        def _run(self):
            super()._run()
            if Moved.moved:
                return
            Moved.moved = True
            k, v = self.answers["sums"]
            v = np.array(v)
            v[1, 3] += 10 * (tol.atol + tol.rtol * abs(v[1, 3]))
            self.answers["sums"] = (k, v)
    return Moved


def _float_cell_with(monkeypatch, change):
    real = discover.find_cell

    def changed(*a, **kw):
        cell = real(*a, **kw)
        change(cell)
        return cell

    monkeypatch.setattr(discover, "find_cell", changed)


def test_a_float_answer_past_its_tolerance_is_not_correct(
        capsys, monkeypatch, tmp_path):
    dummy_files.write(str(tmp_path))

    def plant(cell):
        cell.pipeline.Job = _one_element_past_its_tolerance(
            cell.pipeline.Job, cell.tolerances["sums"])

    _float_cell_with(monkeypatch, plant)
    rc, lines, err = rehearse(capsys, FLOAT, str(tmp_path))
    last = lines[-1]["rehearsal"]
    assert rc == 0 and last["correct"] is False
    assert last["checks"]["wrong_rows"]["value"] == 1
    assert last["checks"]["tolerance_margin"]["value"] == \
        pytest.approx(10, rel=1e-3)
    assert "check wrong_rows = 1" in err


def test_the_float_cells_lower_precision_control_is_caught(capsys,
                                                           tmp_path):
    dummy_files.write(str(tmp_path))
    rc = control.main(["--workload", FLOAT, "--seeds", "1,2,4",
                       "--seconds", "0.3", "--cpu-rehearsal"],
                      root=str(tmp_path))
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert rc == 0 and lines[-1]["all_as_expected"] is True
    for ln in lines[:-1]:
        assert ln["program_correct"] is True
        assert ln["control_wrong_rows"]["lower_precision_bf16"] >= 1
    readings = lines[-1]["tolerance"]["sums"]
    assert readings["program_max_margin"] < 1
    assert readings["control_min_margin"]["lower_precision_bf16"] > 1


def test_control_refuses_a_float_cell_without_a_lower_precision_control(
        capsys, monkeypatch, tmp_path):
    dummy_files.write(str(tmp_path))

    def drop(cell):
        controls = cell.pipeline.controls
        cell.pipeline.controls = lambda cfg, data: {
            n: a for n, a in controls(cfg, data).items()
            if not n.startswith("lower_precision")}

    _float_cell_with(monkeypatch, drop)
    rc = control.main(["--workload", FLOAT, "--seeds", "1",
                       "--seconds", "0.3", "--cpu-rehearsal"],
                      root=str(tmp_path))
    out = capsys.readouterr()
    assert rc == 2
    assert "no control named lower_precision" in out.err
    assert not out.out.strip()              # refused before any window


@pytest.mark.parametrize("entry", [
    {"rtol": 2e-3, "atol": 0, "why": "over the ceiling"},
    {"rtol": 1e-5, "atol": 0, "why": ""},
])
def test_a_compare_block_out_of_bounds_is_refused_before_any_work(
        tmp_path, entry):
    dummy_files.write(str(tmp_path))
    path = tmp_path / "bm" / "configs" / "dummyf" / "config.json"
    cfg = json.loads(path.read_text())
    path.write_text(json.dumps({**cfg, "compare": {"sums": entry}}))
    with pytest.raises(ValueError):
        discover.find_cell(str(tmp_path), FLOAT, rehearsal=True)


def test_a_tolerance_on_integer_answers_fails_the_run(capsys, tmp_path):
    dummy_files.write(str(tmp_path))
    path = tmp_path / "bm" / "configs" / "dummy" / "config.json"
    cfg = json.loads(path.read_text())
    path.write_text(json.dumps({**cfg, "compare": {"counts": {
        "rtol": 1e-5, "atol": 0, "why": "counts are never approximate"}}}))
    with pytest.raises(ValueError, match="floating"):
        rehearse(capsys, "dummy.cell", str(tmp_path))
    assert "correct" not in capsys.readouterr().out


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
