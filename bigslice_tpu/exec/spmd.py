"""SPMD sessions: the multi-host distributed session model.

The reference runs sessions over ad-hoc clusters by shipping invocations
to bigmachine workers over RPC (exec/bigmachine.go:79-533). The
TPU-native replacement runs the SAME driver program on every host
(jax.distributed): compilation is deterministic by construction (the
Func-registry guarantee, SURVEY.md §7.1), so every process builds the
identical task graph, evaluates it with an ordered device-group
dispatcher (launch decisions are pure functions of task state — no
wall-clock skips), and enters every jitted collective in the same order.
Host-tier work runs redundantly on every process (deterministic), device
groups run once across the global mesh with all_to_all/psum riding
ICI/DCN, and group outputs gather to every host in launch order so
result scans are collective-free.

Contract: one driver thread per process, the same program on every
process. Concurrent ``sess.run`` calls from multiple threads are a
single-process-session feature only.

Usage (every process runs this, same code)::

    from bigslice_tpu.exec import spmd
    sess = spmd.spmd_session()        # jax.distributed must be live
    result = sess.run(build_pipeline)
    if spmd.is_coordinator():
        print(result.rows())
"""

from __future__ import annotations

from typing import Optional

from bigslice_tpu.utils.distributed import global_mesh, is_coordinator  # noqa: F401


def spmd_session(mesh=None, parallelism: Optional[int] = None,
                 coordinator_debug_port: Optional[int] = None,
                 **kwargs):
    """A Session over the global multi-host mesh (call after
    jax.distributed initialization; single-process meshes also work —
    handy for tests).

    ``coordinator_debug_port`` starts the DebugServer — and with it the
    device-plane endpoints (``/debug/device``,
    ``/debug/profile?seconds=N``) — on the COORDINATOR process only:
    every process runs this same driver line, so a plain
    ``debug_port=`` would bind the same port N times on a multi-process
    host (and profiling windows are per-process anyway; the
    coordinator's is the one an operator asks for first).

    Telemetry is fleet-wide: every signal family — compile
    attribution (the AOT seam now instruments multi-process meshes
    too; the SPMD same-driver contract keeps its signature bake and
    fallback decisions identical on every rank), shuffle-boundary
    partition counts (each rank records its addressable shards at
    their global offsets — no hot-path collective), HBM watermarks,
    stragglers, exchange and recovery — records process-locally per
    rank. Set ``BIGSLICE_FLEET_DIR`` (or the ``fleet_dir=`` session
    kwarg) to a shared store URL and each rank exports its mergeable
    snapshot there; rank 0 merges them into
    ``telemetry_summary(scope="fleet")``, ``/debug/fleet``, and
    ``fleet.json`` at shutdown (utils/fleettelemetry.py).

    Mesh shape: ``BIGSLICE_MESH_SHAPE=DxI`` builds the 2-D DCN × ICI
    hierarchy (``Mesh(devices.reshape(D, I), ("dcn", "ici"))`` —
    shuffles route through the two-stage hierarchical exchange); unset,
    real multi-slice/multi-host TPU jobs auto-derive the grid from the
    device fleet's slice/host structure and everything else stays 1-D
    (meshutil.shape_device_mesh — the identical mesh every prior
    session built)."""
    from bigslice_tpu.exec.meshexec import MeshExecutor
    from bigslice_tpu.exec.session import Session
    from bigslice_tpu.parallel.meshutil import shape_device_mesh

    if mesh is None:
        mesh = shape_device_mesh()
    if coordinator_debug_port is not None and is_coordinator():
        kwargs.setdefault("debug_port", coordinator_debug_port)
    ex = MeshExecutor(mesh, fallback_procs=parallelism, spmd=True)
    return Session(executor=ex, **kwargs)
