"""CPU rehearsal of ``chip_smoke.py``: every phase function at a tiny
size on the virtual CPU mesh, the four-device comparison on four
virtual devices, the rule that the contract line is never printed for a
platform the run was not on, and the proof that the smoke FAILS when
work quietly leaves the device path (the probation ladder keeps such a
run correct and exit-0 by design). Plus the compile-cache helper's
placement contract.

No time, rate or other device number is read from these runs.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402

from bigslice_tpu.utils import faultinject, hermetic  # noqa: E402


def _ctx(ndev: int, tmp_path) -> cs.Ctx:
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:ndev]), ("shards",))
    return cs.Ctx(mesh=mesh, sizes=cs.TINY, seed=3, on_tpu=False,
                  workdir=str(tmp_path))


def _lines(capsys):
    return [json.loads(line)
            for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]


@pytest.mark.parametrize("phase", list(cs.ONE_CHIP_PHASES))
def test_phase_rehearsal(phase, tmp_path, capsys, monkeypatch):
    """Each phase agrees with its numpy reference, stays on the
    (virtual) device path, and compiles nothing on its second run."""
    monkeypatch.setenv("BIGSLICE_PARSE_PROCS", "2")
    ok, _ = cs.run_phase(phase, _ctx(1, tmp_path))
    (line,) = _lines(capsys)
    assert ok and line["ok"] and line["phase"] == phase, line
    assert line["devices"] == 1
    if phase == "urls":
        assert line["parse_tier"]["streamed"] == {
            "c": 2 * cs.TINY.url_lines}
        assert line["parse_tier"]["from_memory"]["c_pool"] > 0
        assert line["parse_workers_with_libtpu"] == 0
    if phase == "kmeans":
        assert line["compiles_per_round"][1:] == [0, 0]
    if phase == "reduce-dense":
        assert (line["warm_compiles"], line["device_groups"],
                line["planned_groups"]) == (0, 2, 2)


def test_four_device_comparison(tmp_path, capsys):
    """``--chips 4`` on four virtual devices: both shuffle phases on
    the 4-device mesh equal the 1-device mesh and numpy, every device
    held input and received partitions, and the group programs carry
    the all_to_all."""
    assert cs.run_multi_chip(_ctx(1, tmp_path), _ctx(4, tmp_path))
    lines = _lines(capsys)
    assert [ln["phase"] for ln in lines] == [
        "reduce-generic", "reduce-generic", "reduce-generic/compare",
        "join", "join", "join/compare"]
    assert all(ln["ok"] for ln in lines)
    four = lines[0]["runs"]["default"]
    assert lines[0]["devices"] == 4 and lines[1]["devices"] == 1
    assert len(four["uploaded_rows_per_device"]) == 4
    assert min(four["uploaded_rows_per_device"]) > 0
    assert all(len(r) == 4 and min(r) > 0
               for r in four["received_rows_per_device"])


def test_phase_fails_when_work_leaves_the_device(tmp_path, capsys):
    """An injected device failure (the ``mesh.dispatch`` chaos site)
    puts the op on probation and the evaluator re-runs it on the host
    tier: results stay right and a plain run exits 0. The smoke must
    call that a failure."""
    faultinject.install(
        faultinject.parse_plan("5:mesh.dispatch=1.0x1~infra"))
    try:
        ok, _ = cs.run_phase("reduce-dense", _ctx(1, tmp_path))
    finally:
        faultinject.clear()
    (line,) = _lines(capsys)
    assert not ok and not line["ok"]
    assert "probation" in line["error"], line


def test_dense_table_phase_fails_on_sums_that_lean(tmp_path, capsys,
                                                  monkeypatch):
    """The CPU computes any precision exactly, so the chip's lean can
    only be planted here: vector sums 2.8e-6 low, as a one-hot
    contraction over three exact bfloat16 parts reads on a v5e, fail
    the ``dense-table`` phase."""
    from bigslice_tpu.parallel import dense

    exact = dense._scatter_column
    monkeypatch.setattr(
        dense, "_scatter_column",
        lambda *a: exact(*a) * np.float32(1 - 2.8e-6))
    ok, _ = cs.run_phase("dense-table", _ctx(1, tmp_path))
    (line,) = _lines(capsys)
    assert not ok and not line["ok"]
    assert "lean" in line["error"], line


def _run_script(*args, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env={**os.environ, **(env or {})}, capture_output=True,
        text=True, timeout=300,
    )


def test_no_contract_line_off_tpu():
    """Without a TPU the script exits non-zero before any work and
    prints nothing; asked for the CPU rehearsal it prints phase lines
    and still never the contract line — it does not report a platform
    it did not run on."""
    out = _run_script(env={"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0 and out.stdout == ""
    assert "needs a TPU" in out.stderr

    out = _run_script("--cpu-rehearsal", "--phases", "reduce-dense")
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()]
    assert [ln.get("phase") for ln in lines] == [
        "start", "reduce-dense", "total"]
    assert lines[0]["platform"] == "cpu" and lines[0]["cuts"]
    assert not any("device" in ln for ln in lines)


_CACHE_PROBE = (
    "import jax\n"
    "from bigslice_tpu.utils.hermetic import configure_compile_cache\n"
    "a = configure_compile_cache()\n"
    "b = configure_compile_cache()\n"
    "print(a == b, a, jax.config.jax_compilation_cache_dir)\n"
)


def _cache_probe(env, cwd):
    env = {k: v for k, v in {**os.environ, "JAX_PLATFORMS": "cpu",
                             "PYTHONPATH": REPO, **env}.items()
           if v is not None}
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         cwd=cwd, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_compile_cache_env_is_left_alone(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and the
    helper sets no other directory."""
    placed = str(tmp_path / "placed")
    same, returned, configured = _cache_probe(
        {"JAX_COMPILATION_CACHE_DIR": placed}, tmp_path)
    assert (same, returned, configured) == ("True", placed, placed)


def test_compile_cache_default_is_one_fixed_path(tmp_path):
    """Unset: one fixed directory inside the checkout — the same on
    every call, in every process, from any working directory."""
    want = os.path.join(REPO, ".jax_cache")
    assert hermetic.COMPILE_CACHE_DIR == want
    for cwd in (tmp_path, REPO):
        same, returned, configured = _cache_probe(
            {"JAX_COMPILATION_CACHE_DIR": None,
             "TMPDIR": str(tmp_path)}, cwd)
        assert (same, returned, configured) == ("True", want, want)
