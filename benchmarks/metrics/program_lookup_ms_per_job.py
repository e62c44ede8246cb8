"""Layer: compile. What the compile seam (the instrumented program
every ``_obs_program`` returns) took of each cache-hit program call
before it reached the executable — its lock, the signature of every
argument, the lookup — summed over every program of the session (wave
programs, ``bs_merge``, ``bs_prefix`` ... alike): the device plane's
``totals.lookup_s``. Over the window's jobs."""

from benchmarks.metrics import wave_books


def read(r):
    return wave_books.device_ms_per_job(r, "totals", "lookup_s")
