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


# ------------------------------------------- vector rows and tolerances

VK = np.array([1, 2, 3, 4])
VV = np.arange(12, dtype=np.float32).reshape(4, 3) + 1
TOL = compare.Tolerance(rtol=1e-5, atol=1e-6)


def _moved(rows_cols, by):
    v = VV.copy()
    for r, c in rows_cols:
        v[r, c] += by(v[r, c])
    return v


def _ulp(x):
    return np.spacing(np.float32(x))


def _ten_tolerances(x):
    return 10 * (TOL.atol + TOL.rtol * abs(x))


@pytest.mark.parametrize("got,tol,wrong", [
    # a row counts once, however many of its elements differ
    ((VK, _moved([(0, 0), (0, 2), (2, 1)], lambda x: 1)), None, 2),
    ((VK, VV[:, :2]), None, 4),                        # other row shape
    ((VK, _moved([(1, 1)], _ulp)), None, 1),           # exact: one ulp
    ((VK, _moved([(1, 1)], _ulp)), TOL, 0),
    ((VK, _moved([(1, 1), (1, 2)], _ten_tolerances)), TOL, 1),
    ((VK, _moved([(3, 0)], lambda x: np.nan)), TOL, 1),
    ((VK, _moved([(3, 0)], lambda x: np.inf)), TOL, 1),
    ((VK, VV[:, :2]), TOL, 4),
    ((VK[:3], VV[:3]), TOL, 1),                         # keys stay exact
    ((np.array([1, 2, 3, 3]), VV), TOL, 2),
])
def test_vector_rows_exactly_and_within_a_tolerance(got, tol, wrong):
    assert compare.wrong_rows(got, (VK, VV.astype(np.float64)), tol) == wrong


def test_the_margin_is_the_largest_normalised_error():
    v = VV.astype(np.float64)
    v[0, 1] += 0.5 * (TOL.atol + TOL.rtol * v[0, 1])
    v[2, 2] -= 0.25 * (TOL.atol + TOL.rtol * v[2, 2])
    want = {"sums": (VK, VV.astype(np.float64))}
    got = {"sums": (VK, v)}
    assert compare.margins(got, want, {"sums": TOL})["sums"] == \
        pytest.approx(0.5)
    assert compare.compare_answers(got, want, {"sums": TOL}) == (0, 4)
    off = {"sums": (VK, _moved([(1, 1)], _ten_tolerances))}
    assert compare.margins(off, want, {"sums": TOL})["sums"] == \
        pytest.approx(10, rel=1e-3)
    nan = {"sums": (VK, _moved([(1, 1)], lambda x: np.nan))}
    assert compare.margins(nan, want, {"sums": TOL})["sums"] > 1


def test_a_tolerance_on_an_integer_table_raises():
    want = {"counts": (K, V)}
    with pytest.raises(ValueError, match="floating"):
        compare.compare_answers(want, want, {"counts": TOL})
    with pytest.raises(ValueError, match="floating"):
        compare.margins(want, want, {"counts": TOL})


GOOD = {"rtol": 1e-5, "atol": 0, "why": "float32 sums against float64"}


@pytest.mark.parametrize("entry", [
    {**GOOD, "rtol": 2 * compare.RTOL_CEILING},
    {**GOOD, "why": ""},
    {**GOOD, "why": "  "},
    {k: v for k, v in GOOD.items() if k != "why"},
    {**GOOD, "atol": -1e-9},
    {**GOOD, "rtol": -1e-9},
    {**GOOD, "rtol": "1e-5"},
    {**GOOD, "rtol": float("nan")},
    {**GOOD, "rtol_typo": 1e-5},
])
def test_a_compare_block_out_of_bounds_is_refused(entry):
    with pytest.raises(ValueError):
        compare.tolerances({"compare": {"sums": entry}})


def test_a_compare_block_within_bounds_is_read():
    assert compare.tolerances({}) == {}
    assert compare.tolerances({"compare": {"sums": GOOD}}) == {
        "sums": compare.Tolerance(1e-5, 0.0)}
    ceiling = {**GOOD, "rtol": compare.RTOL_CEILING}
    assert compare.tolerances({"compare": {"sums": ceiling}})
