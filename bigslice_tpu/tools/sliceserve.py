"""sliceserve: the long-lived serving process (serve/server.py CLI).

Starts one Session owning the mesh, registers named pipelines, and
serves HTTP/JSON invocations until SIGTERM/SIGINT — the SNIPPETS
``exec.Start(exec.TPU)`` shape with admission control, per-tenant
quotas, the cross-Session compiled-program cache, and an optional
cross-request result cache. The debug surface (``/debug/metrics``
Prometheus scrape, ``/debug/status``, on-demand ``/debug/profile``)
rides on the same port.

Pipelines come from ``--module``: any importable module exposing
``register_pipelines(server)`` (called with the ``ServeServer`` —
register with ``server.register(name, fn, cache=...)``). With no
module, two built-in demo pipelines are registered:

- ``reduce``: keyed Reduce over a synthetic corpus —
  ``args = [n_rows, n_keys]`` (defaults 1<<18, 1<<12).
- ``wordcount``: the cmd/urls domain count over a synthetic URL
  corpus — ``args = [n_rows]`` (default 1<<15).

Shutdown is graceful by contract: SIGTERM/SIGINT stop admission
(503s), drain in-flight invocations, flush a final telemetry snapshot
(StatusPrinter-style), then close the session.

Usage:
    python -m bigslice_tpu.tools.sliceserve --port 8710 \
        [--slots 2] [--queue 16] [--tenant-quota 8] \
        [--result-cache DIR] [--module my.pipelines]
"""

from __future__ import annotations

import argparse
import json
import signal
import sys
import threading


def _demo_pipelines(server) -> None:
    """The built-in demo pipelines: module-level slice builders with
    stable fn identity, so repeated invocations — and fresh sessions —
    reuse compiled programs."""
    import numpy as np

    import bigslice_tpu as bs

    def _add(a, b):
        return a + b

    def reduce_pipeline(n_rows=1 << 18, n_keys=1 << 12):
        rng = np.random.RandomState(42)
        keys = rng.randint(0, int(n_keys),
                           int(n_rows)).astype(np.int32)
        vals = np.ones(int(n_rows), dtype=np.int32)
        import jax

        shards = max(1, len(jax.devices()))
        return bs.Reduce(bs.Const(shards, keys, vals), _add)

    def wordcount_pipeline(n_rows=1 << 15):
        from bigslice_tpu.models.urls import domain_count

        rng = np.random.RandomState(7)
        doms = (rng.zipf(1.5, int(n_rows)) % 500).astype(np.int64)
        lines = [f"http://site{d}.example.com/p/{i & 255}"
                 for i, d in enumerate(doms.tolist())]
        import jax

        shards = max(1, len(jax.devices()))
        return domain_count(shards, lines)

    server.register("reduce", reduce_pipeline,
                    description="keyed Reduce over a synthetic corpus "
                                "(args: n_rows, n_keys)")
    try:
        from bigslice_tpu.models import urls  # noqa: F401 — probe

        server.register("wordcount", wordcount_pipeline,
                        description="domain count over a synthetic "
                                    "URL corpus (args: n_rows)")
    except Exception:
        pass


def build_server(port: int = 0, slots: int = 2, queue: int = 16,
                 tenant_quota=None, result_cache=None, module=None,
                 status: bool = False):
    """Session + ServeServer, pipelines registered. Returns the
    server (its ``session`` attribute owns the mesh)."""
    import jax

    from bigslice_tpu.exec.meshexec import MeshExecutor
    from bigslice_tpu.exec.session import Session
    from bigslice_tpu.parallel.meshutil import shape_device_mesh
    from bigslice_tpu.serve.server import ServeServer

    mesh = shape_device_mesh(jax.devices())
    session = Session(executor=MeshExecutor(mesh), status=status)
    server = ServeServer(
        session, port=port, slots=slots, queue_depth=queue,
        tenant_quota=tenant_quota, result_cache_dir=result_cache,
    )
    if module:
        import importlib

        mod = importlib.import_module(module)
        register = getattr(mod, "register_pipelines", None)
        if register is None:
            raise SystemExit(
                f"sliceserve: module {module!r} has no "
                f"register_pipelines(server)"
            )
        register(server)
    else:
        _demo_pipelines(server)
    return server


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="sliceserve",
        description="persistent multi-tenant pipeline server",
    )
    ap.add_argument("--port", type=int, default=8710,
                    help="listen port (0 = ephemeral; printed on "
                         "stdout as JSON)")
    ap.add_argument("--slots", type=int, default=2,
                    help="concurrent invocations on the shared mesh")
    ap.add_argument("--queue", type=int, default=16,
                    help="admission queue depth beyond the slots "
                         "(beyond -> 503)")
    ap.add_argument("--tenant-quota", type=int, default=None,
                    help="max in-flight+queued invocations per tenant "
                         "(beyond -> 429)")
    ap.add_argument("--result-cache", default=None,
                    help="directory for the cross-request result "
                         "cache (enables cache=True pipelines)")
    ap.add_argument("--module", default=None,
                    help="import MODULE and call its "
                         "register_pipelines(server)")
    ap.add_argument("--status", action="store_true",
                    help="live status lines on stderr")
    args = ap.parse_args(argv)

    from bigslice_tpu.utils.hermetic import configure_compile_cache

    configure_compile_cache()
    server = build_server(
        port=args.port, slots=args.slots, queue=args.queue,
        tenant_quota=args.tenant_quota,
        result_cache=args.result_cache, module=args.module,
        status=args.status,
    )
    print(json.dumps({
        "serving": True,
        "port": server.port,
        "pipelines": sorted(server.pipelines()),
        "slots": server.slots,
        "queue_depth": server.queue_depth,
        "tenant_quota": server.tenant_quota,
    }), flush=True)

    stop = threading.Event()

    def _on_signal(signum, frame):
        print(f"sliceserve: signal {signum}, draining",
              file=sys.stderr, flush=True)
        stop.set()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    try:
        stop.wait()
    finally:
        # Graceful drain: the session closes its serving surface
        # first (in-flight invocations finish, final telemetry
        # snapshot flushes), then the executor.
        server.session.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
