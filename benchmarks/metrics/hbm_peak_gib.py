"""Layer: device. ``memory_stats()["peak_bytes_in_use"]`` after the
window, on the fullest chip."""


def read(r):
    if r.window.memory_peak_bytes is None:
        return None
    return r.window.memory_peak_bytes / 2 ** 30
