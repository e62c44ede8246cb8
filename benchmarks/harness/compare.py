"""The comparison that decides ``correct``.

An answer is a keyed table ``(keys, values)``: two 1-D arrays of equal
length, one row a key. The program's answer is compared with the plain
reference's row by row, exactly: the number compared is how many keys
have a different value on the two sides, are on one side only, or come
out more than once. Its limit is 0."""

from __future__ import annotations

import numpy as np


def wrong_rows(got, want) -> int:
    """Rows of ``got`` and ``want`` that disagree (see module doc)."""
    gk, gv = (np.asarray(c) for c in got)
    wk, wv = (np.asarray(c) for c in want)
    if len(gk) != len(gv) or len(wk) != len(wv):
        raise ValueError("an answer's columns differ in length")
    if (gk.shape == wk.shape and np.array_equal(gk, wk)
            and len(np.unique(wk)) == len(wk)):
        return int(np.count_nonzero(gv != wv))
    wrong = 0
    uk, first, counts = np.unique(gk, return_index=True,
                                  return_counts=True)
    wrong += int(np.sum(counts - 1))          # a key in two rows
    uv = gv[first]
    wu, wfirst = np.unique(wk, return_index=True)
    common, iu, iw = np.intersect1d(uk, wu, assume_unique=True,
                                    return_indices=True)
    wrong += len(uk) - len(common)            # only in got
    wrong += len(wu) - len(common)            # only in want
    wrong += int(np.count_nonzero(uv[iu] != wv[wfirst][iw]))
    return wrong


def compare_answers(got: dict, want: dict) -> tuple:
    """``(wrong rows, rows compared)`` over every table of ``got``;
    a table the reference lacks counts every row wrong."""
    wrong = rows = 0
    for name, table in got.items():
        if name not in want:
            wrong += len(table[0])
            rows += len(table[0])
            continue
        wrong += wrong_rows(table, want[name])
        rows += max(len(table[0]), len(want[name][0]))
    return wrong, rows
