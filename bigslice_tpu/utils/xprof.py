"""Windowed on-demand XLA profiling (jax.profiler plumbing).

``Profiler.window(seconds)`` starts a trace now, holds it for the
window, stops, and reports the trace directory + files. This is what
``/debug/profile?seconds=N`` (utils/debughttp.py) serves: profile a
live production session on demand, no restart, no session-long
overhead. The program's spans (utils/trace.span) are in the trace, on
host lines beside the device ops.

jax supports a single live profiler per process, so a window request
while another is live is rejected rather than crashing the run.
"""

from __future__ import annotations

import os
import threading
import time
from typing import List, Optional


class ProfilerBusy(RuntimeError):
    """A profiling window was requested while another is live — jax
    allows one profiler per process."""


class Profiler:
    """Session-scoped profiler gate."""

    # Window clamp: long windows pin the (single) process-wide
    # profiler and grow the trace unboundedly.
    MAX_WINDOW_SECS = 120.0

    def __init__(self):
        self._lock = threading.Lock()

    # -- on-demand window -------------------------------------------------

    def window(self, seconds: float,
               out_dir: Optional[str] = None) -> dict:
        """Profile the process for ``seconds`` (clamped to
        (0, MAX_WINDOW_SECS]), blocking for the window; returns
        ``{"dir", "seconds", "files"}`` where ``files`` are the trace
        artifacts written under ``dir`` (TensorBoard/xprof loads the
        directory). Raises ProfilerBusy when another trace is live."""
        seconds = min(max(0.05, float(seconds)), self.MAX_WINDOW_SECS)
        if out_dir is None:
            import tempfile

            out_dir = tempfile.mkdtemp(prefix="bigslice-xprof-")
        if not self._lock.acquire(blocking=False):
            raise ProfilerBusy(
                "another profiling window is already running (one "
                "jax profiler per process)"
            )
        try:
            import jax

            jax.profiler.start_trace(out_dir)
            try:
                time.sleep(seconds)
            finally:
                jax.profiler.stop_trace()
        finally:
            self._lock.release()
        return {
            "dir": out_dir,
            "seconds": seconds,
            "files": self._trace_files(out_dir),
        }

    @staticmethod
    def _trace_files(out_dir: str) -> List[str]:
        files = []
        for root, _, names in os.walk(out_dir):
            for n in names:
                files.append(os.path.relpath(
                    os.path.join(root, n), out_dir
                ))
        return sorted(files)
