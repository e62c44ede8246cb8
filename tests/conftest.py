"""Test configuration: force an 8-device virtual CPU platform.

Multi-chip TPU hardware is not available in CI; sharding and collective
paths are validated on 8 virtual CPU devices, mirroring the reference's
in-process fake cluster strategy (bigmachine/testsystem,
exec/slicemachine_test.go:299-310): the full distributed control path runs
hermetically in unit tests.
"""

import os

# Hard-set, not setdefault: on a machine with a chip JAX would default
# to it; unit tests run on virtual CPU devices regardless.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

from bigslice_tpu.utils.hermetic import force_hermetic_cpu  # noqa: E402

force_hermetic_cpu()


import pytest  # noqa: E402


@pytest.fixture(params=["local", "mesh"])
def sess(request):
    """Executor-parameterized sessions (the slice_test.go:64-66 pattern):
    tests taking this fixture run on the local executor AND the mesh
    executor (device-eligible op groups go SPMD; the rest exercise the
    fallback interop)."""
    from bigslice_tpu.exec.session import Session

    if request.param == "local":
        return Session()
    import numpy as np
    import jax
    from jax.sharding import Mesh

    from bigslice_tpu.exec.meshexec import MeshExecutor

    mesh = Mesh(np.array(jax.devices()[:8]), ("shards",))
    return Session(executor=MeshExecutor(mesh))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: full-matrix recompile variants outside the tier-1 "
        "'not slow' budget",
    )


@pytest.fixture
def benchmark_modules():
    """``(root, run, control)``: the benchmark's two commands as
    modules, for the tests that rehearse a cell end to end."""
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (root, os.path.join(root, "benchmarks")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import control
    import run

    return root, run, control


@pytest.fixture
def compile_cache_as_found():
    """The benchmark's command places JAX's persistent compile cache
    for its process; a test process takes it away again."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    found = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in found.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()
