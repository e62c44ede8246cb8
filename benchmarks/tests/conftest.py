"""The harness's own tests run on the CPU: ``JAX_PLATFORMS=cpu python -m
pytest benchmarks/tests -q``. They prove control flow and arithmetic;
no number they see is a device number."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "benchmarks")):
    if p not in sys.path:
        sys.path.insert(0, p)
