"""Multi-host (multi-process jax.distributed) smoke: the DCN-shaped
validation of the SPMD model — separate OS processes form one mesh and
run psum + all_to_all collectives across real process boundaries
(SURVEY.md §5.8's control/data-plane replacement, tested hermetically
like the reference's bigmachine/testsystem)."""

import os
import subprocess
import sys

import pytest


def _skip_if_no_cpu_collectives(out):
    """This jaxlib build may lack multi-process CPU collectives (gloo);
    the capability only surfaces inside the spawned workers — convert
    that environment limitation into a skip, same as the telemetry
    smoke below."""
    if "Multiprocess computations aren't implemented" in (
            out.stdout + out.stderr):
        pytest.skip("jaxlib cannot run multiprocess CPU collectives")


def test_two_process_distributed_smoke():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "bigslice_tpu.tools.multihost_smoke", "2"],
        capture_output=True, text=True, timeout=180, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    _skip_if_no_cpu_collectives(out)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MULTIHOST_SMOKE_OK processes=2" in out.stdout
    # The distributed Session ran end-to-end (compile → ordered SPMD
    # group launch → collective execution → result scan) across the
    # two processes with the device path engaged.
    assert "MULTIHOST_SESSION_OK" in out.stdout
    # Host-tier (object-key) tasks were owner-routed across the two
    # processes — each owned some and resolved the rest remotely —
    # and the coordination KV was left empty at teardown.
    assert "HOSTDIST_OK" in out.stdout


def test_wedged_peer_detected_by_keepalive():
    """A peer that hangs WITHOUT dying (TCP alive, coordination-service
    heartbeats healthy, interpreter stuck) is invisible to both the
    collective layer and the service's own liveness — only the
    application keepalive (utils.distributed.Keepalive) sees its beat
    stall. The survivor must fail fast with HostLostError at group
    launch, before entering the collective it would hang in."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "bigslice_tpu.tools.multihost_smoke",
         "--wedge"],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    _skip_if_no_cpu_collectives(out)
    assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-1500:])
    assert "WEDGE_OK" in out.stdout


def test_host_loss_surfaces_fast():
    """A peer dying mid-session fails the survivor's next run FAST with
    a classified HostLostError (the gang-scheduled analog of machine
    loss, SURVEY §5.3) — never a hang in a collective."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "bigslice_tpu.tools.multihost_smoke",
         "--chaos"],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    _skip_if_no_cpu_collectives(out)
    assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-1500:])
    assert "CHAOS_OK" in out.stdout


def test_two_process_fleet_telemetry_smoke(tmp_path):
    """Fleet observability across REAL process boundaries: both ranks
    export mergeable snapshots through the shared store; rank 0's
    merged fleet summary carries BOTH ranks' shuffle/compile/exchange
    attribution (asserted inside the worker) and the per-rank trace +
    fleet-summary artifacts land in --out."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "bigslice_tpu.tools.multihost_smoke",
         "--telemetry", "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    if "Multiprocess computations aren't implemented" in (
            out.stdout + out.stderr):
        pytest.skip("jaxlib cannot run multiprocess CPU collectives")
    assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-1500:])
    assert "FLEETTELEM_OK" in out.stdout
    names = sorted(p.name for p in tmp_path.iterdir())
    assert "fleet-summary.json" in names
    assert "trace-rank0.json" in names and "trace-rank1.json" in names
    assert "aux" in names  # the store-side snapshots + fleet.json


def test_mid_collective_kill_classified_fast():
    """Round-5 verdict #8: a peer SIGKILLed while an SPMD collective is
    EXECUTING (not between runs, not before launch) must surface on the
    survivor as a classified HostLostError fast — the in-flight
    collective errors instead of hanging (the peer dies at its dispatch
    seam once the survivor has announced its own dispatch: no timer
    races the run, and only a hang fails). Also pins the hyphenated
    Gloo error spellings in the host-loss classifier, which this smoke
    discovered live."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "bigslice_tpu.tools.multihost_smoke",
         "--killrun"],
        capture_output=True, text=True, timeout=400, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    _skip_if_no_cpu_collectives(out)
    assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-1500:])
    assert "KILLRUN_OK" in out.stdout
