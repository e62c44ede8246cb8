"""The comparison that decides ``correct``.

An answer is a keyed table ``(keys, values)``: a 1-D array of keys and
an array of values with one row a key — a number, or a vector where the
values are ``[n, d]``. The program's answer is compared with the plain
reference's row by row: the number compared is how many keys have a
different value on the two sides, are on one side only, or come out
more than once. Its limit is 0. A row counts once, however many of its
elements differ.

Values are compared exactly, unless the configuration names the table
in its ``compare`` block::

    "compare": {"<table>": {"rtol": r, "atol": a, "why": "<why these>"}}

Such a table's reference values must be floating point, and an element
is wrong where ``|got - want| > atol + rtol * |want|``, where it is not
finite and the reference's is, or where its row's shape differs from
the reference row's. Keys are always compared exactly. How close a run
came to its tolerance is its margin: the largest ``|got - want| ÷ (atol
+ rtol * |want|)`` over the elements compared, which is over 1 exactly
where an element is wrong."""

from __future__ import annotations

import dataclasses
import math

import numpy as np

#: The loosest ``rtol`` a configuration may state. float32 rounds at
#: 6e-8 and bfloat16 at 3.9e-3 (relative), so a tolerance at this
#: ceiling still fails arithmetic done in bfloat16: no stated tolerance
#: can admit a lower precision than the configuration's.
RTOL_CEILING = 1e-3

#: The margin of an element that no tolerance admits: not finite where
#: the reference's is, of another shape, or off where the tolerance is
#: 0. The largest float, so that the line stays plain JSON.
NO_MARGIN = float(np.finfo(np.float64).max)


@dataclasses.dataclass(frozen=True)
class Tolerance:
    rtol: float
    atol: float


def tolerances(cfg: dict) -> dict:
    """``{table: Tolerance}`` from the configuration's ``compare``
    block; raises ``ValueError`` for an entry without a ``why``, with a
    negative ``rtol`` or ``atol``, or with ``rtol`` over
    ``RTOL_CEILING``."""
    block = cfg.get("compare", {})
    if not isinstance(block, dict):
        raise ValueError(f"'compare' is not a table of tables: {block!r}")
    out = {}
    for table, entry in block.items():
        if not isinstance(entry, dict) or set(entry) != {"rtol", "atol",
                                                         "why"}:
            raise ValueError(f"compare[{table!r}] needs exactly 'rtol', "
                             f"'atol' and 'why': {entry!r}")
        why = entry["why"]
        if not isinstance(why, str) or not why.strip():
            raise ValueError(f"compare[{table!r}] says no why")
        rtol, atol = entry["rtol"], entry["atol"]
        for name, x in (("rtol", rtol), ("atol", atol)):
            if (isinstance(x, bool) or not isinstance(x, (int, float))
                    or not math.isfinite(x) or x < 0):
                raise ValueError(f"compare[{table!r}].{name} = {x!r}: "
                                 "not a finite number >= 0")
        if rtol > RTOL_CEILING:
            raise ValueError(f"compare[{table!r}].rtol = {rtol} is over "
                             f"the ceiling {RTOL_CEILING}")
        out[table] = Tolerance(float(rtol), float(atol))
    return out


def _aligned(got, want):
    """``(wrong, got values, want values)``: the rows of both sides
    paired by key, and the number of keys that pair with nothing — on
    one side only, or a second row of a key of ``got``."""
    gk, gv = (np.asarray(c) for c in got)
    wk, wv = (np.asarray(c) for c in want)
    if len(gk) != len(gv) or len(wk) != len(wv):
        raise ValueError("an answer's columns differ in length")
    if (gk.shape == wk.shape and np.array_equal(gk, wk)
            and len(np.unique(wk)) == len(wk)):
        return 0, gv, wv
    wrong = 0
    uk, first, counts = np.unique(gk, return_index=True,
                                  return_counts=True)
    wrong += int(np.sum(counts - 1))          # a key in two rows
    wu, wfirst = np.unique(wk, return_index=True)
    common, iu, iw = np.intersect1d(uk, wu, assume_unique=True,
                                    return_indices=True)
    wrong += len(uk) - len(common)            # only in got
    wrong += len(wu) - len(common)            # only in want
    return wrong, gv[first][iu], wv[wfirst][iw]


def _by_row(x: np.ndarray) -> np.ndarray:
    """``x`` as one row of elements a key (also where it has no rows)."""
    return x.reshape(len(x), int(np.prod(x.shape[1:])))


def _row_margins(gv, wv, tol: Tolerance) -> np.ndarray:
    """Each paired row's largest element margin (module doc)."""
    n = len(wv)
    if gv.shape[1:] != wv.shape[1:]:
        return np.full(n, NO_MARGIN)
    g, w = gv.astype(np.float64), wv.astype(np.float64)
    with np.errstate(invalid="ignore", divide="ignore", over="ignore"):
        m = np.abs(g - w) / (tol.atol + tol.rtol * np.abs(w))
    same = (g == w) | (np.isnan(g) & np.isnan(w))
    m = np.where(same, 0.0, np.where(np.isfinite(m), m, NO_MARGIN))
    return _by_row(m).max(axis=1, initial=0.0)


def _check_float(name: str, want) -> None:
    values = np.asarray(want[1])
    if not np.issubdtype(values.dtype, np.floating):
        raise ValueError(f"table {name!r} is compared within a tolerance, "
                         f"but its reference values are {values.dtype}: "
                         "only floating-point answers may be")


def wrong_rows(got, want, tol: Tolerance = None) -> int:
    """Rows of ``got`` and ``want`` that disagree (see module doc)."""
    wrong, gv, wv = _aligned(got, want)
    if tol is not None:
        return wrong + int(np.count_nonzero(_row_margins(gv, wv, tol) > 1))
    if gv.shape[1:] != wv.shape[1:]:
        return wrong + len(wv)
    return wrong + int(np.count_nonzero(_by_row(gv != wv).any(axis=1)))


def compare_answers(got: dict, want: dict, tols: dict = None) -> tuple:
    """``(wrong rows, rows compared)`` over every table of ``got``, the
    tables of ``tols`` within their tolerance; a table the reference
    lacks counts every row wrong."""
    tols = tols or {}
    wrong = rows = 0
    for name, table in got.items():
        if name not in want:
            wrong += len(table[0])
            rows += len(table[0])
            continue
        if name in tols:
            _check_float(name, want[name])
        wrong += wrong_rows(table, want[name], tols.get(name))
        rows += max(len(table[0]), len(want[name][0]))
    return wrong, rows


def margins(got: dict, want: dict, tols: dict) -> dict:
    """``{table: margin}`` for each table of ``tols`` that both sides
    answered: the largest normalised error of its paired rows."""
    out = {}
    for name, tol in tols.items():
        if name in got and name in want:
            _check_float(name, want[name])
            _, gv, wv = _aligned(got[name], want[name])
            out[name] = float(_row_margins(gv, wv, tol).max(initial=0.0))
    return out
