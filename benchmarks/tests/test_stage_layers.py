"""The readers of what the program itself records (stage seconds, objective
evaluations): the window is the process total less the warm fit, and a
program that records nothing reads None, never 0."""

import pytest

from benchmarks.layers import (
    fe_evals_per_fit,
    fit_glue_share_pct,
    re_evals_per_fit,
    validation_share_pct,
)
from photon_ml_tpu.utils import telemetry

STAGES = (
    "fit", "fit/revalidate", "fit/validation_prep", "fit/coordinates", "fit/descent",
    "cd/validation_score", "cd/validation_evaluate", "fit/final_evaluate", "fit/publish",
)


@pytest.fixture(autouse=True)
def clean_registry():
    telemetry.METRICS.reset()
    yield
    telemetry.METRICS.reset()


def record_fit(stages_s, fn_evals):
    """What `GameEstimator.fit` publishes at the end of one fit."""
    for stage, seconds in stages_s.items():
        telemetry.METRICS.observe("fit_stage_s", seconds, labels=(("stage", stage),))
    for (cid, kind), n in fn_evals.items():
        telemetry.METRICS.increment(
            "objective_evaluations", n, labels=(("coordinate", cid), ("kind", kind))
        )


def a_run(fits, warm_stages, warm_evals, kinds):
    return {
        "window_s": 2.0, "records": [{"seconds": 0.5}] * fits, "kinds": kinds,
        "warm_fit_timing": {"stages_s": warm_stages, "fn_evals": warm_evals},
    }


def test_the_window_is_the_process_less_the_warm_fit():
    warm = dict.fromkeys(STAGES, 0.0) | {
        "cd/validation_score": 0.3, "cd/validation_evaluate": 0.9, "fit/final_evaluate": 0.7,
        "fit/revalidate": 0.25, "fit/validation_prep": 0.05, "fit/coordinates": 1.5, "fit/publish": 0.01,
    }
    a_fit = dict.fromkeys(STAGES, 0.0) | {
        "cd/validation_score": 0.01, "cd/validation_evaluate": 0.09, "fit/final_evaluate": 0.1,
        "fit/revalidate": 0.004, "fit/validation_prep": 0.003, "fit/coordinates": 0.002, "fit/publish": 0.001,
    }
    kinds = {"global": "fixed", "per-user": "random", "per-movie": "random"}
    record_fit(warm, {("global", "fixed"): 14, ("per-user", "random"): 900, ("per-movie", "random"): 300})
    for _ in range(4):
        record_fit(a_fit, {("global", "fixed"): 11, ("per-user", "random"): 800, ("per-movie", "random"): 200})
    run = a_run(4, warm, {"global": 14, "per-user": 900, "per-movie": 300}, kinds)
    # 4 fits x (0.01 + 0.09 + 0.1) s of a 2 s window; 4 x 0.01 s of glue.
    assert validation_share_pct.read(run) == pytest.approx(100 * 0.8 / 2.0)
    assert fit_glue_share_pct.read(run) == pytest.approx(100 * 0.04 / 2.0)
    assert fe_evals_per_fit.read(run) == pytest.approx(11.0)
    assert re_evals_per_fit.read(run) == pytest.approx(1000.0)


def test_a_program_that_records_nothing_reads_none_not_zero():
    """An earlier commit: no histogram, no counter, no `stages_s`."""
    run = {
        "window_s": 2.0, "records": [{"seconds": 0.5}] * 4, "kinds": {"global": "fixed"},
        "warm_fit_timing": {"prepare_s": 0.1, "solve_s": 1.0},
    }
    for reader in (validation_share_pct, fit_glue_share_pct, fe_evals_per_fit, re_evals_per_fit):
        assert reader.read(run) is None


def test_a_coordinate_kind_the_configuration_lacks_reads_none():
    warm = dict.fromkeys(STAGES, 0.0)
    record_fit(warm, {("global", "fixed"): 12})
    record_fit(warm, {("global", "fixed"): 11})
    run = a_run(1, warm, {"global": 12}, {"global": "fixed"})
    assert fe_evals_per_fit.read(run) == pytest.approx(11.0)
    assert re_evals_per_fit.read(run) is None
    # Every stage recorded and all of them zero: a share of 0 is a reading.
    assert fit_glue_share_pct.read(run) == 0.0
