"""The prefetch workers of a pipelined wave loop
(``MeshExecutor._execute_waves_pipelined``): the waves behind a group's
first are staged ahead of the loop by one or two threads and handed to
it in wave order."""

from __future__ import annotations

import threading
from typing import Dict, Optional

from bigslice_tpu.utils import trace as trace_mod

# Stages that a pipelined group which UPLOADS its waves keeps in flight
# beside its wave loop: while wave w+1's device_put is under way (it
# releases the interpreter lock), wave w+2's read and assemble run on a
# second worker. Never more than the group's bound of waves begun and
# not yet taken, prefetch depth + 1.
STAGE_WORKERS = 2


class WaveStagers:
    """Waves 1.. of a pipelined group, staged ahead of its wave loop by
    worker threads and handed to it in wave order.

    ``stage(wave)`` runs on a worker and returns ``(inputs, seconds,
    breakdown)``. A group whose waves are uploaded (``uploads``) keeps
    ``STAGE_WORKERS`` of them in flight; a group that stages zero-copy
    views is always ready and keeps one. Either way at most ``depth +
    1`` waves are begun and not yet taken by the loop: the host and HBM
    bytes held ahead of it are the one-worker pipeline's. A stage that
    raises is handed over as its wave's error, in wave order, and no
    wave is begun after it."""

    def __init__(self, nwaves: int, depth: int, uploads: bool, stage):
        self._stage = stage
        self._nwaves = nwaves
        self._bound = depth + 1
        self._cv = threading.Condition()
        self._next = 1       # the next wave to begin
        self._taken = 0      # waves the loop has taken
        self._running = 0    # stages under way
        self._closed = False  # begin no more: closed, or a stage raised
        self._done: Dict[int, tuple] = {}
        # Nanoseconds the workers could begin nothing because the bound
        # was reached (the stagers waiting for the loop), and the stages
        # that began while another of the group was under way.
        self.blocked_ns = 0
        self.overlapped = 0
        self._threads = [
            threading.Thread(target=self._work, daemon=True,
                             name=f"meshwave-prefetch-{i}")
            for i in range(min(STAGE_WORKERS, depth + 1) if uploads else 1)
        ]
        for t in self._threads:
            t.start()

    def _begin(self) -> Optional[int]:
        """The wave this worker stages next, waited for while the bound
        is reached; None once there is nothing more to begin."""
        with self._cv:
            t_full = 0
            wave = None
            while not self._closed and self._next < self._nwaves:
                if self._next - 1 - self._taken < self._bound:
                    wave = self._next
                    self._next += 1
                    self.overlapped += bool(self._running)
                    self._running += 1
                    break
                t_full = t_full or trace_mod.now_ns()
                self._cv.wait()
            if t_full:
                self.blocked_ns += trace_mod.now_ns() - t_full
            return wave

    def _work(self) -> None:
        while (wave := self._begin()) is not None:
            try:
                inputs, dur, wstats = self._stage(wave)
                item = (inputs, None, dur, wstats)
            except BaseException as e:  # noqa: BLE001 — re-raised in
                item = (None, e, 0.0, None)  # wave order on the loop
            with self._cv:
                self._running -= 1
                self._done[wave] = item
                if item[1] is not None:
                    self._closed = True
                self._cv.notify_all()

    def ready(self, wave: int) -> bool:
        """Is ``wave`` staged already?"""
        with self._cv:
            return wave in self._done

    def take(self, wave: int) -> tuple:
        """``(inputs, error, seconds, breakdown)`` of ``wave``, the next
        in wave order, waited for."""
        with self._cv:
            while wave not in self._done:
                self._cv.wait()
            self._taken += 1
            self._cv.notify_all()
            return self._done.pop(wave)

    def close(self) -> None:
        """Begin no more stages and wait for those under way: every
        worker has exited on return. What was staged and not taken is
        dropped (its arena buffers went back inside ``_upload``)."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        for t in self._threads:
            t.join()
        self._done.clear()
