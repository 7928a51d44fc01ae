"""The gaps `correct` is judged by, on arrays worked by hand."""

import numpy as np
import pytest

from benchmarks import compare


def test_a_fixed_effect_is_held_by_its_frobenius_gap():
    ref = np.array([3.0, 4.0])
    assert compare.coefficient_gap(ref + np.array([0.0, 0.5]), ref) == pytest.approx(0.1)
    assert compare.coefficient_gap(np.zeros(2), ref) == pytest.approx(1.0)
    assert compare.coefficient_gap(np.zeros(3), ref) == float("inf")


def test_a_random_effect_is_held_by_the_99th_percentile_of_its_entities():
    rng = np.random.default_rng(0)
    ref = rng.standard_normal((1000, 8))
    one_off = ref.copy()
    one_off[17] *= 1.5  # one entity of a thousand took another step
    assert compare.coefficient_gap(one_off, ref) == 0.0
    assert compare.frobenius_gap(one_off, ref) > 0.01
    assert compare.notes([{"coefficients": {"re": one_off}}], {"coefficients": {"re": ref}})[
        "worst_entity_gap.re"
    ] == pytest.approx(0.5)
    every = ref * 1.002  # a lower precision moves every entity
    assert compare.coefficient_gap(every, ref) == pytest.approx(0.002, rel=0.3)
    assert compare.coefficient_gap(np.zeros_like(ref), ref) == pytest.approx(1.0, rel=0.3)


def test_an_all_but_zero_entity_is_measured_against_the_median_entity():
    ref = np.ones((5, 4))
    ref[0] = 1e-9
    program = ref.copy()
    program[0] = 2e-9  # twice a row that is nought to rounding
    assert compare.entity_gaps(program, ref)[0] == pytest.approx(1e-9 * 2 / 2.0)


def test_a_number_without_a_limit_fails():
    rows = compare.judge({"coef_gap.global": 1e-9, "metric_gap": 1e-9}, {"metric_gap": 1e-6})
    assert [r["ok"] for r in rows] == [False, True]
