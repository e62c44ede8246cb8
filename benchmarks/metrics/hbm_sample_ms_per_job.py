"""Layer: device. What the per-wave device-memory samples
(``memory_stats()`` of every device, after each settled wave) took on
the group's thread: the device plane's ``hbm.sample_s``. Over the
window's jobs."""

from benchmarks.metrics import wave_books


def read(r):
    return wave_books.device_ms_per_job(r, "hbm", "sample_s")
