"""Four forced host devices for the harness's own tests, so that a cell
that asks for four chips rehearses on the CPU as it would from the
command line with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``. A cell of one
chip takes the first device. Set before anything imports JAX."""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()
