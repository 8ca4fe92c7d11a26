"""The comparison itself: a gap or an answer that is not a number fails
the run, on either side."""
from __future__ import annotations

import numpy as np

from bench import check

LIMITS = {"worst_rel_err": 1e-9, "median_rel_err": 1e-9}
REF = np.array([1.0, 2.0, 4.0])


def test_equal_answers_pass():
    checks = check.compare([REF.copy(), REF.copy()], REF, LIMITS, 3, 0)
    assert check.passed(checks)
    assert checks["worst_rel_err"]["value"] == 0.0
    assert checks["median_rel_err"]["value"] == 0.0


def test_a_shift_of_every_answer_fails_on_the_median():
    limits = {"worst_rel_err": 1e-4, "median_rel_err": 1e-9}
    checks = check.compare([REF * (1 + 1e-6)], REF, limits, 3, 0)
    assert checks["worst_rel_err"]["value"] < 1e-4
    assert checks["median_rel_err"]["value"] > 1e-9
    assert not check.passed(checks)


def test_nan_in_the_reference_fails():
    ref = REF.copy()
    ref[1] = np.nan
    checks = check.compare([REF.copy()], ref, LIMITS, 3, 0)
    assert checks["answers_missing"]["value"] == 1
    assert not check.passed(checks)


def test_nan_answer_fails():
    out = REF.copy()
    out[0] = np.nan
    checks = check.compare([out], REF, LIMITS, 3, 0)
    assert checks["answers_missing"]["value"] == 1
    assert not check.passed(checks)


def test_a_gap_that_is_not_a_number_fails():
    ref, out = REF.copy(), REF.copy()
    ref[2] = out[2] = 0.0
    checks = check.compare([out], ref, LIMITS, 3, 0)
    assert checks["worst_rel_err"]["value"] == np.inf
    assert not check.passed(checks)


def test_short_sweep_and_inputs_off_fail():
    checks = check.compare([REF[:2]], REF, LIMITS, 3, 0)
    assert checks["answers_missing"]["value"] == 1
    assert not check.passed(check.compare([REF], REF, LIMITS, 3, 2))


def test_widest_rows_name_the_rows_that_differ():
    out = REF.copy()
    out[2] *= 1 + 1e-3
    other = REF.copy()
    other[0] = np.nan
    rows = check.widest_rows([out, other], REF, n=2)
    assert rows[0] == (0, np.inf)
    assert rows[1][0] == 2 and abs(rows[1][1] - 1e-3) < 1e-12
