"""Serving plane: persistent multi-tenant session serving.

The SNIPPETS north star is ``exec.Start(exec.TPU)`` serving pipelines
with no workers in the loop — the millions-of-users story is one
long-lived server process owning the mesh. Two pieces make that real:

- ``serve/programcache.py`` — the cross-Session compiled-program
  cache. PR 6's ``_obs_program`` seam already AOT-compiles every SPMD
  program once per (op site, partition-config, mesh-signature) digest
  and reuses the held executable *within* a session; this module is
  the process-global tier above it, so a **fresh Session in the same
  server process performs zero XLA compiles** for pipelines the
  process has served before.
- ``serve/server.py`` — the invocation server: named pipelines
  (deterministic ``bigslice.Func`` framing), HTTP/JSON invocations
  scheduled onto shared wave slots with an admission-control queue,
  per-tenant quotas and metrics, an optional ``ops/cache.py``-backed
  cross-request result cache, and a graceful drain on SIGTERM.

``tools/sliceserve.py`` is the CLI entry.
"""

from bigslice_tpu.serve.programcache import (  # noqa: F401
    ProgramCache,
    fn_fingerprint,
    global_program_cache,
    program_cache_stats,
)
from bigslice_tpu.serve.server import (  # noqa: F401
    Pipeline,
    ServeServer,
    ServingStats,
)

__all__ = [
    "ProgramCache",
    "fn_fingerprint",
    "global_program_cache",
    "program_cache_stats",
    "Pipeline",
    "ServeServer",
    "ServingStats",
]
