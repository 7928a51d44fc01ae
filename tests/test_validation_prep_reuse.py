"""A validation data set is prepared once per estimator: the first fit that
sees it resolves its entity rows and projects its random-effect shards
(`GameEstimator._validation_prepared`), and every later fit or sweep on the
same object reuses those views, with the same scores as a fresh build."""

import numpy as np
import pytest

import jax.numpy as jnp

from photon_ml_tpu.data import device_assemble
from photon_ml_tpu.data.containers import SparseFeatures
from photon_ml_tpu.data.game_dataset import (
    FixedEffectDataConfig,
    GameDataset,
    RandomEffectDataConfig,
)
from photon_ml_tpu.estimators import game_estimator
from photon_ml_tpu.estimators.game_estimator import GameEstimator
from photon_ml_tpu.evaluation.suite import EvaluatorType
from photon_ml_tpu.optimize.config import (
    CoordinateOptimizationConfig,
    OptimizerConfig,
    RegularizationContext,
)
from photon_ml_tpu.types import OptimizerType, RegularizationType, TaskType
from photon_ml_tpu.utils import telemetry

CIDS = ("global", "per-user", "per-movie")
D, K = 40, 4


def _dataset(rng, n):
    idx = rng.integers(0, D, size=(n, K)).astype(np.int32)
    val = rng.normal(size=(n, K)).astype(np.float32)
    return GameDataset.build(
        {"g": SparseFeatures(jnp.asarray(idx), jnp.asarray(val), D)},
        (rng.uniform(size=n) < 0.5).astype(np.float32),
        id_tags={"u": rng.integers(0, 40, size=n), "m": rng.integers(0, 15, size=n)},
    )


def _estimator():
    est = GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {
            "global": FixedEffectDataConfig("g"),
            "per-user": RandomEffectDataConfig("u", "g", min_bucket=8),
            "per-movie": RandomEffectDataConfig("m", "g", min_bucket=8),
        },
        coordinate_descent_iterations=1,
        validation_evaluators=[EvaluatorType.parse("AUC")],
        pipeline=False,
    )
    tron = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(OptimizerType.TRON, max_iterations=2, tolerance=1e-9),
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=1.0,
    )
    cfg = {
        "global": CoordinateOptimizationConfig(optimizer=OptimizerConfig(max_iterations=3)),
        "per-user": tron,
        "per-movie": tron,
    }
    return est, cfg


def _coefficients(result):
    model = result.model
    return {
        "global": np.asarray(model["global"].coefficients.means),
        **{cid: np.asarray(model[cid].coefficients_matrix) for cid in CIDS[1:]},
    }


def _assert_same_fit(a, b):
    for cid, coef in _coefficients(a).items():
        np.testing.assert_array_equal(coef, _coefficients(b)[cid], err_msg=cid)
    assert a.evaluation.primary_value == b.evaluation.primary_value


@pytest.fixture(scope="module")
def runs():
    """Fits of one estimator on one training set: twice on one validation
    set, then on a second; beside them fresh estimators' fits on each
    validation set. What each fit prepared is counted as it ran."""
    mp = pytest.MonkeyPatch()
    # The chip's path: the projector is built on the device, and projects
    # validation rows through `project_ell_device`.
    mp.setenv("PHOTON_DEVICE_ASSEMBLY", "1")
    calls = {"prepare": 0, "project": 0}

    def counting(name, fn):
        def wrapped(*args, **kwargs):
            calls[name] += 1
            return fn(*args, **kwargs)

        return wrapped

    mp.setattr(
        game_estimator,
        "prepare_coordinate_data",
        counting("prepare", game_estimator.prepare_coordinate_data),
    )
    mp.setattr(
        device_assemble,
        "project_ell_device",
        counting("project", device_assemble.project_ell_device),
    )
    rng = np.random.default_rng(3)
    train, val, val2 = _dataset(rng, 2400), _dataset(rng, 600), _dataset(rng, 600)
    est, cfg = _estimator()
    fits = []

    def fit(estimator, validation):
        before = dict(calls)
        counted = telemetry.METRICS.labeled_counters("validation_prepared")
        result = estimator.fit(train, validation, [cfg])[0]
        after = telemetry.METRICS.labeled_counters("validation_prepared")
        fits.append(
            {
                "result": result,
                "calls": {k: calls[k] - before[k] for k in calls},
                "counter": {k: n - counted.get(k, 0) for k, n in after.items() if n != counted.get(k, 0)},
                "verdict": estimator.fit_timing["validation_prep"],
                "stage_s": estimator.fit_timing["stages_s"]["fit/validation_prep"],
                "scored": estimator.run_profile()["dispatch"]["ell_planes_scored"],
            }
        )
        return fits[-1]

    try:
        out = {
            "first": fit(est, val),
            "second": fit(est, val),
            "fresh": fit(_estimator()[0], val),
            "other": fit(est, val2),
            "other_again": fit(est, val2),
            "fresh_other": fit(_estimator()[0], val2),
        }
        # A sweep on the estimator's last validation set finds it prepared.
        before = dict(calls)
        counted = telemetry.METRICS.labeled_counters("validation_prepared")
        est.sweep_executor(train, val2, cfg)
        after = telemetry.METRICS.labeled_counters("validation_prepared")
        out["sweep"] = {
            "calls": {k: calls[k] - before[k] for k in calls},
            "counter": {k: n - counted.get(k, 0) for k, n in after.items() if n != counted.get(k, 0)},
        }
        yield out
    finally:
        mp.undo()


def _counted(outcome):
    return {f"coordinate={cid},outcome={outcome}": 1 for cid in CIDS}


def test_a_second_fit_prepares_nothing(runs):
    assert runs["first"]["calls"]["prepare"] == len(CIDS)
    # The training shards' projections and the validation rows' both ran.
    assert runs["first"]["calls"]["project"] >= 2
    assert runs["second"]["calls"] == {"prepare": 0, "project": 0}
    assert runs["other_again"]["calls"] == {"prepare": 0, "project": 0}


def test_the_counter_reads_built_then_reused(runs):
    for name, outcome in (
        ("first", "built"), ("second", "reused"), ("fresh", "built"),
        ("other", "built"), ("other_again", "reused"),
    ):
        assert runs[name]["counter"] == _counted(outcome), name
        assert runs[name]["verdict"] == outcome, name


def test_a_reusing_fit_is_bitwise_a_fresh_fit(runs):
    _assert_same_fit(runs["second"]["result"], runs["first"]["result"])
    _assert_same_fit(runs["second"]["result"], runs["fresh"]["result"])


def test_another_validation_set_is_built_again(runs):
    assert runs["other"]["calls"]["prepare"] == len(CIDS)
    assert runs["other"]["calls"]["project"] == 2
    _assert_same_fit(runs["other"]["result"], runs["fresh_other"]["result"])
    _assert_same_fit(runs["other_again"]["result"], runs["fresh_other"]["result"])
    assert (
        runs["other"]["result"].evaluation.primary_value
        != runs["first"]["result"].evaluation.primary_value
    )


def test_the_stage_and_the_scored_note_are_on_every_fit(runs):
    for name in ("first", "second", "other", "other_again"):
        assert runs[name]["stage_s"] > 0.0, name
        assert runs[name]["scored"] == runs["first"]["scored"] != "none", name


def test_a_sweep_after_a_fit_reuses_the_views(runs):
    assert runs["sweep"]["calls"] == {"prepare": 0, "project": 0}
    assert runs["sweep"]["counter"] == _counted("reused")
