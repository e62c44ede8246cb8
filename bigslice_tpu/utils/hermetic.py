"""Process-level JAX placement: which platform a process runs on, and
where its compiled programs are cached.

The program runs two ways: on the CPU (tests and ``-local`` tooling pin
it with ``JAX_PLATFORMS=cpu``) and on whatever accelerator JAX finds by
default. Nothing here probes, retries or degrades: a process that was
not pinned to the CPU and finds no accelerator fails in JAX itself.
"""

from __future__ import annotations

import os

#: The one in-checkout home of JAX's persistent compilation cache when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset. Fixed on purpose: the
#: directory is part of the cache key, so a path that moves never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)


def is_cpu_pinned() -> bool:
    """True when the primary JAX platform is pinned to cpu via the
    environment (tests, -local tooling) — the one shared definition."""
    return os.environ.get(
        "JAX_PLATFORMS", ""
    ).split(",")[0].strip() == "cpu"


def accelerator_or_pinned_cpu(tool: str) -> bool:
    """The measuring tools' start-up check: an accelerator, or the CPU
    pinned on purpose — never a quiet CPU run under device metric
    names. Returns whether the CPU was pinned; exits non-zero when JAX
    found no accelerator and nobody asked for the CPU."""
    import jax

    pinned = is_cpu_pinned()
    if not pinned and jax.devices()[0].platform == "cpu":
        raise SystemExit(
            f"{tool}: JAX found no accelerator; set JAX_PLATFORMS=cpu "
            f"to run on the CPU on purpose"
        )
    return pinned


def force_hermetic_cpu() -> None:
    """Pin this process to the CPU platform, whatever the ambient
    environment says (tests, the CPU simulations of a multi-host gang,
    the compile-only tools). Call before the first JAX computation."""
    import jax

    os.environ["JAX_PLATFORMS"] = "cpu"
    # libtpu's topology path (jax.experimental.topologies, used by the
    # AOT compile checks) queries the GCP instance-metadata server for
    # host-bounds variables — 30 HTTP retries per variable, ~8 minutes
    # of pure network wait on any non-GCP host before it gives up and
    # proceeds anyway. Hermetic means no metadata courtship; AOT
    # topology descriptions never need it. setdefault so an explicit
    # operator choice still wins.
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    jax.config.update("jax_platforms", "cpu")


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its
    directory. Entry points call this before their first JAX use; a
    job is a fresh process, so what it compiles is only ever reused
    through this cache.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing
    here (or anywhere else in the code) sets another directory. Unset:
    the fixed ``COMPILE_CACHE_DIR`` inside the checkout."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
