"""What every entry point of the benchmark does first: place the
compile cache, find the cell, and refuse to go on without the chips it
asks for. One process touches JAX."""

from __future__ import annotations

from . import discover


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def start(root: str, workload: str, rehearsal: bool):
    """``(cell, devices, compile cache directory)``. ``rehearsal`` pins
    the CPU and loads the configuration's tiny sizes; otherwise anything
    but a TPU with enough chips raises ``NoChip`` before any work."""
    from bigslice_tpu.utils import hermetic

    if rehearsal:
        hermetic.force_hermetic_cpu()
    cache_dir = hermetic.configure_compile_cache()
    import jax

    # Every program goes to the persistent cache, however quickly it
    # compiled, so that only a checkout's first run of a cell compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    cell = discover.find_cell(root, workload, rehearsal=rehearsal)
    devs = jax.devices()
    if not rehearsal and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r} "
                     f"(--cpu-rehearsal runs the tiny CPU rehearsal)")
    if len(devs) < cell.chips:
        raise NoChip(f"{cell.name} asks for {cell.chips} chip(s), JAX "
                     f"found {len(devs)}")
    return cell, devs[:cell.chips], cache_dir
