import numpy as np
import pytest

from benchmarks.harness import compare

K = np.array([1, 2, 3, 4])
V = np.array([10, 20, 30, 40])


@pytest.mark.parametrize("got,wrong", [
    ((K, V), 0),
    ((K, np.array([10, 20, 31, 40])), 1),            # a value altered
    ((K[:3], V[:3]), 1),                              # a row missing
    ((np.array([1, 2, 3, 4, 5]), np.array([10, 20, 30, 40, 50])), 1),
    ((np.array([1, 2, 3, 3]), np.array([10, 20, 30, 40])), 2),  # twice + missing
    ((np.array([], int), np.array([], int)), 4),
])
def test_wrong_rows(got, wrong):
    assert compare.wrong_rows(got, (K, V)) == wrong


def test_string_keys_and_unknown_tables():
    want = {"counts": (np.array(["a.com", "b.com"]), np.array([2, 1]))}
    got = {"counts": (np.array(["A.com", "a.com", "b.com"]),
                      np.array([1, 1, 1]))}
    assert compare.compare_answers(got, want) == (2, 3)
    assert compare.compare_answers({"other": (K, V)}, want) == (4, 4)
