"""The table of peaks, keyed by JAX's ``device_kind``. A device that is
not in the table is an error, never a default; a share of a peak above
100 % is an error in the harness, never a result."""

from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_TABLE) as fp:
        table = json.load(fp)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_TABLE}: add it with its source")
    return table[device_kind]


def share_of_peak_pct(needed: float, peak_per_s: float,
                      seconds: float, what: str) -> float:
    """``needed`` units at ``peak_per_s`` is the least time the chip
    could take; its share of the ``seconds`` really taken, in %."""
    if seconds <= 0:
        raise ValueError(f"{what}: {seconds} s of device time")
    pct = 100.0 * (needed / peak_per_s) / seconds
    if pct > 100.0:
        raise ArithmeticError(
            f"{what}: {pct:.1f} % of peak — the work is counted too "
            f"high or the time leaves part of it out")
    return pct
