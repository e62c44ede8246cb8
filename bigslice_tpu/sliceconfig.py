"""Session bootstrap from a config profile + flags.

Mirrors the reference's ``sliceconfig`` (sliceconfig/sliceconfig.go:39-65):
a user profile at ``~/.bigslice_tpu/config`` (JSON) supplies defaults
(parallelism, executor, mesh shape, trace path); command-line flags
override; ``parse()`` returns a ready Session.

The reference's EC2 cluster provisioning (``bigslice setup-ec2``) has no
TPU analog here — TPU pods are provisioned by the platform; this config
selects local vs mesh execution and jax.distributed coordination for
multi-host (utils/distributed.py).
"""

from __future__ import annotations

import argparse
import json
import os
from typing import Optional

CONFIG_PATH = os.path.join(
    os.path.expanduser("~"), ".bigslice_tpu", "config"
)

DEFAULTS = {
    "executor": "auto",       # auto | local | mesh
    "parallelism": 0,          # 0 = ncpu (local) / nd devices (mesh)
    "status": False,
    "trace_path": "",
    "distributed": False,      # jax.distributed multi-host init
    "coordinator": "",        # host:port for jax.distributed
    "num_processes": 0,
    "process_id": -1,
}


def load_profile(path: Optional[str] = None) -> dict:
    if path is None:
        path = CONFIG_PATH  # late-bound so tests can repoint it
    cfg = dict(DEFAULTS)
    if os.path.exists(path):
        with open(path) as fp:
            cfg.update(json.load(fp))
    return cfg


def write_profile(values: dict, path: Optional[str] = None) -> None:
    if path is None:
        path = CONFIG_PATH
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fp:
        json.dump(values, fp, indent=2)


def make_session(cfg: dict):
    """Instantiate a Session per config (the sliceconfig.Parse tail)."""
    from bigslice_tpu.exec.session import Session

    if cfg.get("distributed"):
        from bigslice_tpu.utils import distributed

        distributed.initialize(
            coordinator=cfg.get("coordinator") or None,
            num_processes=cfg.get("num_processes") or None,
            process_id=(cfg["process_id"]
                        if cfg.get("process_id", -1) >= 0 else None),
        )
    executor = None
    kind = cfg.get("executor", "auto")
    if kind in ("auto", "mesh"):
        import jax

        devs = jax.devices()
        if kind == "mesh" or len(devs) > 1:
            import numpy as np
            from jax.sharding import Mesh

            from bigslice_tpu.exec.meshexec import MeshExecutor

            mesh = Mesh(np.array(devs), ("shards",))
            # Multi-process jobs need the SPMD dispatch contract
            # (ordered launches, eager gathers — exec/spmd.py).
            executor = MeshExecutor(
                mesh, spmd=jax.process_count() > 1
            )
    return Session(
        executor=executor,
        parallelism=cfg.get("parallelism") or None,
        status=bool(cfg.get("status")),
        trace_path=cfg.get("trace_path") or None,
    )


_current_session = None


def current_session():
    """The session configured by the run CLI (tools/run), if any."""
    return _current_session


def set_current_session(sess) -> None:
    global _current_session
    _current_session = sess


def parse(argv=None):
    """Merge profile + flags and build a Session (sliceconfig.Parse
    analog). Returns (session, leftover_args)."""
    cfg = load_profile()
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("-local", action="store_true",
                    help="force the local executor")
    ap.add_argument("-parallelism", type=int, default=None)
    ap.add_argument("-status", action="store_true", default=None)
    ap.add_argument("-trace", dest="trace_path", default=None)
    ap.add_argument("-spmd", action="store_true", default=None,
                    help="multi-host SPMD session (jax.distributed; "
                         "run the SAME command on every host)")
    ap.add_argument("-coordinator", default=None,
                    help="host:port for jax.distributed (omit on TPU "
                         "pods — auto-detected from the platform)")
    ap.add_argument("-nprocs", type=int, default=None)
    ap.add_argument("-procid", type=int, default=None)
    args, rest = ap.parse_known_args(argv)
    if args.local:
        cfg["executor"] = "local"
    if args.parallelism is not None:
        cfg["parallelism"] = args.parallelism
    if args.status is not None:
        cfg["status"] = args.status
    if args.trace_path is not None:
        cfg["trace_path"] = args.trace_path
    if (args.spmd or args.coordinator is not None
            or args.nprocs is not None or args.procid is not None):
        # Any multi-host flag implies the SPMD session — a coordinator
        # address on a non-distributed session would silently run a
        # single-host job the user believes is a gang.
        cfg["distributed"] = True
        cfg["executor"] = "mesh"
    if args.coordinator is not None:
        cfg["coordinator"] = args.coordinator
    if args.nprocs is not None:
        cfg["num_processes"] = args.nprocs
    if args.procid is not None:
        cfg["process_id"] = args.procid
    return make_session(cfg), rest
