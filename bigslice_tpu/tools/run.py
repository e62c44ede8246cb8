"""Program runner (cmd/bigslice `run` analog).

The reference's CLI builds fat binaries so one artifact serves driver
and cloud workers (cmd/bigslice/bigslicecmd/build.go:28-77). The SPMD
model needs no artifact split: every host runs the SAME Python
program, so `run` reduces to "bootstrap a configured session, execute
the user program" — and the pod story reduces to starting this same
command once per host.

Usage:
    python -m bigslice_tpu.tools.run [flags] program.py [args...]

Flags (sliceconfig.parse): -local, -parallelism N, -status, -trace T,
and for multi-host: -spmd [-coordinator host:port -nprocs N
-procid I], -launch N.

**On a TPU pod** (the "start this same program on every host of a
v5e-16" recipe): have the platform run, on EVERY host of the slice,

    python -m bigslice_tpu.tools.run -spmd program.py

GKE/queued-resources already start one identical container command per
host, which is exactly this model. `-spmd` calls
``jax.distributed.initialize`` — with no further flags on TPU the
coordinator, process count, and process id are auto-detected from the
platform metadata — verifies the Func registry across hosts, and
builds a Session over the global mesh with the SPMD dispatch contract
(exec/spmd.py). Driver-only side effects (writing result files,
printing) belong under ``spmd.is_coordinator()``.

**Simulation on the CPU**: `-launch N` starts N local processes of
the identical command wired together over a loopback coordinator —
the single-host stand-in for a pod launch (each process contributes
its own virtual CPU devices to the global mesh). It requires
``JAX_PLATFORMS=cpu``: a chip belongs to one process, so on a host
with chips one process drives all of them and N would fight over
them:

    JAX_PLATFORMS=cpu python -m bigslice_tpu.tools.run -launch 2 \\
        program.py

The program receives the configured session via
``bigslice_tpu.sliceconfig.current_session()`` (also re-exported
here).
"""

from __future__ import annotations

import os
import runpy
import socket
import subprocess
import sys

from bigslice_tpu import sliceconfig
from bigslice_tpu.utils.hermetic import (
    configure_compile_cache,
    is_cpu_pinned,
)


def current_session():
    return sliceconfig.current_session()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch(n: int, argv) -> int:
    """Pod-launch simulation: run the identical command in ``n`` local
    processes over a loopback coordinator. All streams pass through
    (process 0 is the coordinator/driver — programs gate driver-only
    printing on ``spmd.is_coordinator()``); the exit code is 0 only
    when the whole gang succeeded, else the first failure's (with
    signal deaths shell-normalized to 128+signum so they can't read
    as success)."""
    if not is_cpu_pinned():
        # A chip belongs to one process: N local processes can only
        # ever be the CPU simulation of a gang. On a host with chips
        # ONE process drives all of them (-spmd without -launch).
        print("-launch N simulates a multi-host gang on the CPU: set "
              "JAX_PLATFORMS=cpu", file=sys.stderr)
        return 2
    port = _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "bigslice_tpu.tools.run", "-spmd",
             "-coordinator", f"127.0.0.1:{port}",
             "-nprocs", str(n), "-procid", str(i), *argv],
            env=dict(os.environ),
        )
        for i in range(n)
    ]
    rcs = [p.wait() for p in procs]
    for rc in rcs:
        if rc != 0:
            return rc if rc > 0 else 128 - rc
    return 0


# Runner flags that consume a value — the -launch scan below must hop
# them to find the first positional (the program path), so a -launch
# that BELONGS to the user program is never intercepted.
_VALUE_FLAGS = ("-parallelism", "-trace", "-coordinator", "-nprocs",
                "-procid", "-launch")


def _extract_launch(argv):
    """(n, argv-without-launch) when a pre-program -launch N is
    present; (None, argv) otherwise. Raises SystemExit with usage on a
    malformed count."""
    i = 0
    while i < len(argv):
        a = argv[i]
        if a == "-launch":
            if i + 1 >= len(argv) or not argv[i + 1].isdigit():
                print("usage: -launch N (process count)",
                      file=sys.stderr)
                raise SystemExit(2)
            return int(argv[i + 1]), argv[:i] + argv[i + 2:]
        if a in _VALUE_FLAGS:
            i += 2
        elif a.startswith("-"):
            i += 1
        else:
            break  # first positional: the program path
    return None, argv


def main(argv=None):
    argv = argv if argv is not None else sys.argv[1:]
    n, argv = _extract_launch(argv)
    if n is not None:
        return launch(n, argv)
    configure_compile_cache()
    sess, rest = sliceconfig.parse(argv)
    if not rest:
        print("usage: python -m bigslice_tpu.tools.run [flags] "
              "program.py [args...]", file=sys.stderr)
        return 2
    sliceconfig.set_current_session(sess)
    prog, prog_args = rest[0], rest[1:]
    sys.argv = [prog] + prog_args
    try:
        runpy.run_path(prog, run_name="__main__")
    finally:
        sess.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
