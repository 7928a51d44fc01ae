"""Evaluation tests with sklearn as the external oracle (the reference's
equivalent role is played by spark.mllib BinaryClassificationMetrics)."""

import jax.numpy as jnp
import numpy as np
import pytest
from sklearn import metrics as skm

from photon_ml_tpu.data.game_dataset import FixedEffectDataConfig, GameDataset
from photon_ml_tpu.estimators.game_estimator import GameEstimator
from photon_ml_tpu.evaluation import metrics
from photon_ml_tpu.evaluation.suite import (
    EvaluationSuite,
    EvaluatorType,
    StreamingWindowEvaluator,
    better_than,
    build_grouped_index,
    default_evaluator_for_task,
    resolve_metric_fn,
)
from photon_ml_tpu.optimize.config import (
    CoordinateOptimizationConfig,
    OptimizerConfig,
)
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils import compile_cache, telemetry


def test_auc_matches_sklearn(rng):
    n = 500
    scores = rng.normal(size=n).astype(np.float32)
    labels = (rng.uniform(size=n) > 0.4).astype(np.float32)
    ours = metrics.area_under_roc_curve(jnp.asarray(scores), jnp.asarray(labels))
    ref = skm.roc_auc_score(labels, scores)
    np.testing.assert_allclose(float(ours), ref, rtol=1e-5)


def test_auc_with_ties_and_weights(rng):
    n = 300
    scores = rng.integers(0, 5, size=n).astype(np.float32)  # heavy ties
    labels = (rng.uniform(size=n) > 0.5).astype(np.float32)
    weights = rng.uniform(0.5, 3.0, size=n).astype(np.float32)
    ours = metrics.area_under_roc_curve(
        jnp.asarray(scores), jnp.asarray(labels), jnp.asarray(weights)
    )
    ref = skm.roc_auc_score(labels, scores, sample_weight=weights)
    np.testing.assert_allclose(float(ours), ref, rtol=1e-5)


def test_auc_padding_mask(rng):
    n = 100
    scores = rng.normal(size=n).astype(np.float32)
    labels = (rng.uniform(size=n) > 0.5).astype(np.float32)
    base = metrics.area_under_roc_curve(jnp.asarray(scores), jnp.asarray(labels))
    # Add garbage rows with zero weight.
    s2 = np.concatenate([scores, rng.normal(size=20).astype(np.float32)])
    l2 = np.concatenate([labels, np.ones(20, np.float32)])
    w2 = np.concatenate([np.ones(n, np.float32), np.zeros(20, np.float32)])
    padded = metrics.area_under_roc_curve(jnp.asarray(s2), jnp.asarray(l2), jnp.asarray(w2))
    np.testing.assert_allclose(float(padded), float(base), rtol=1e-5)


def test_aupr_close_to_sklearn(rng):
    n = 400
    scores = rng.normal(size=n).astype(np.float32)
    labels = (rng.uniform(size=n) > 0.6).astype(np.float32)
    ours = metrics.area_under_pr_curve(jnp.asarray(scores), jnp.asarray(labels))
    # sklearn's average_precision is the step-function integral; our trapezoid
    # matches spark mllib. They agree loosely on smooth data.
    ref = skm.average_precision_score(labels, scores)
    assert abs(float(ours) - ref) < 0.02


def test_rmse_and_losses(rng):
    n = 200
    scores = rng.normal(size=n).astype(np.float32)
    labels = rng.normal(size=n).astype(np.float32)
    np.testing.assert_allclose(
        float(metrics.rmse(jnp.asarray(scores), jnp.asarray(labels))),
        np.sqrt(np.mean((scores - labels) ** 2)),
        rtol=1e-5,
    )
    y = (labels > 0).astype(np.float32)
    ll = float(metrics.logistic_loss(jnp.asarray(scores), jnp.asarray(y)))
    ref_ll = np.mean(np.log1p(np.exp(-(2 * y - 1) * scores)))
    np.testing.assert_allclose(ll, ref_ll, rtol=1e-4)


def test_precision_at_k():
    scores = jnp.asarray([5.0, 4.0, 3.0, 2.0, 1.0])
    labels = jnp.asarray([1.0, 0.0, 1.0, 1.0, 0.0])
    np.testing.assert_allclose(float(metrics.precision_at_k(2, scores, labels)), 0.5)
    np.testing.assert_allclose(float(metrics.precision_at_k(4, scores, labels)), 0.75)


def test_evaluator_type_parsing():
    assert EvaluatorType.parse("AUC") == EvaluatorType("AUC")
    assert EvaluatorType.parse("rmse").name == "RMSE"
    g = EvaluatorType.parse("AUC:queryId")
    assert g.is_grouped and g.id_tag == "queryId"
    p = EvaluatorType.parse("PRECISION@5:documentId")
    assert p.k == 5 and p.id_tag == "documentId"
    assert str(p) == "PRECISION@5:documentId"
    with pytest.raises(ValueError):
        EvaluatorType.parse("NOT_A_METRIC")


def test_better_than_directions():
    auc = EvaluatorType("AUC")
    rmse_t = EvaluatorType("RMSE")
    assert better_than(auc, 0.9, 0.8) and not better_than(auc, 0.7, 0.8)
    assert better_than(rmse_t, 0.1, 0.2) and not better_than(rmse_t, 0.3, 0.2)
    assert better_than(auc, 0.1, None)


def test_grouped_auc_equals_per_group_mean(rng):
    n, g = 300, 7
    gids = rng.integers(0, g, size=n)
    scores = rng.normal(size=n).astype(np.float32)
    labels = (rng.uniform(size=n) > 0.5).astype(np.float32)
    suite = EvaluationSuite(
        [EvaluatorType.parse("AUC:q")],
        jnp.asarray(labels),
        id_tag_values={"q": gids},
    )
    res = suite.evaluate(jnp.asarray(scores))
    per_group = []
    for gid in np.unique(gids):
        m = gids == gid
        if len(np.unique(labels[m])) < 2:
            per_group.append(0.5)
        else:
            per_group.append(skm.roc_auc_score(labels[m], scores[m]))
    np.testing.assert_allclose(res.primary_value, np.mean(per_group), rtol=1e-4)


def test_suite_multiple_metrics(rng):
    n = 100
    labels = (rng.uniform(size=n) > 0.5).astype(np.float32)
    scores = rng.normal(size=n).astype(np.float32)
    suite = EvaluationSuite(
        [EvaluatorType("AUC"), EvaluatorType("LOGISTIC_LOSS")], jnp.asarray(labels)
    )
    res = suite.evaluate(jnp.asarray(scores))
    assert set(res.results) == {"AUC", "LOGISTIC_LOSS"}
    assert res.primary == EvaluatorType("AUC")


def test_default_evaluators():
    assert default_evaluator_for_task(TaskType.LOGISTIC_REGRESSION).name == "AUC"
    assert default_evaluator_for_task(TaskType.LINEAR_REGRESSION).name == "RMSE"
    assert default_evaluator_for_task(TaskType.POISSON_REGRESSION).name == "POISSON_LOSS"


def test_build_grouped_index_shapes(rng):
    gids = np.array([3, 1, 3, 3, 2, 1])
    idx = build_grouped_index(gids)
    assert idx.gather.shape == (3, 3)
    assert float(idx.mask.sum()) == 6.0


class TestLegacyMetrics:
    """R^2 / peak-F1 and the legacy Evaluation.evaluate metric map
    (photon-client evaluation/Evaluation.scala:31), cross-checked vs sklearn."""

    def test_r_squared_vs_sklearn(self, rng):
        from sklearn.metrics import r2_score

        from photon_ml_tpu.evaluation.metrics import r_squared

        y = rng.normal(size=200).astype(np.float32)
        pred = (y + rng.normal(size=200) * 0.5).astype(np.float32)
        ours = float(r_squared(jnp.asarray(pred), jnp.asarray(y)))
        assert ours == pytest.approx(r2_score(y, pred), abs=1e-5)
        # Weighted form vs sklearn sample_weight.
        w = rng.uniform(0.5, 2.0, size=200).astype(np.float32)
        ours_w = float(r_squared(jnp.asarray(pred), jnp.asarray(y), jnp.asarray(w)))
        assert ours_w == pytest.approx(r2_score(y, pred, sample_weight=w), abs=1e-5)

    def test_peak_f1_vs_sklearn(self, rng):
        from sklearn.metrics import precision_recall_curve

        from photon_ml_tpu.evaluation.metrics import peak_f1

        y = (rng.uniform(size=300) > 0.6).astype(np.float32)
        s = (y + rng.normal(size=300)).astype(np.float32)
        p, r, _ = precision_recall_curve(y, s)
        f1 = 2 * p * r / np.maximum(p + r, 1e-12)
        expected = float(np.max(f1))
        ours = float(peak_f1(jnp.asarray(s), jnp.asarray(y)))
        assert ours == pytest.approx(expected, abs=1e-5)

    def test_peak_f1_tied_scores_and_padding(self):
        from photon_ml_tpu.evaluation.metrics import peak_f1

        # Ties: scores [1, 1, 0]; labels [1, 0, 1]. Realizable cuts are
        # {>=1} (P=0.5, R=0.5, F1=0.5) and {>=0} (P=2/3, R=1, F1=0.8).
        s = jnp.asarray([1.0, 1.0, 0.0])
        y = jnp.asarray([1.0, 0.0, 1.0])
        assert float(peak_f1(s, y)) == pytest.approx(0.8, abs=1e-6)
        # Padding rows (weight 0) must not contribute.
        s2 = jnp.asarray([1.0, 1.0, 0.0, 9.0])
        y2 = jnp.asarray([1.0, 0.0, 1.0, 0.0])
        w2 = jnp.asarray([1.0, 1.0, 1.0, 0.0])
        assert float(peak_f1(s2, y2, w2)) == pytest.approx(0.8, abs=1e-6)

    def test_evaluate_glm_map(self, rng):
        from photon_ml_tpu.data.containers import dense_data
        from photon_ml_tpu.evaluation import legacy
        from photon_ml_tpu.models.glm import create_model
        from photon_ml_tpu.types import TaskType

        n, d = 150, 5
        X = rng.normal(size=(n, d)).astype(np.float32)
        w = rng.normal(size=d).astype(np.float32)
        ybin = (X @ w + rng.normal(size=n) * 0.3 > 0).astype(np.float32)
        ylin = (X @ w + rng.normal(size=n) * 0.3).astype(np.float32)

        logit = create_model(TaskType.LOGISTIC_REGRESSION, jnp.asarray(w))
        m = legacy.evaluate_glm(logit, dense_data(X, ybin))
        assert {
            legacy.AREA_UNDER_ROC,
            legacy.AREA_UNDER_PRECISION_RECALL,
            legacy.PEAK_F1_SCORE,
            legacy.DATA_LOG_LIKELIHOOD,
            legacy.AKAIKE_INFORMATION_CRITERION,
        } <= set(m)
        assert 0.8 < m[legacy.AREA_UNDER_ROC] <= 1.0
        assert m[legacy.DATA_LOG_LIKELIHOOD] < 0.0

        lin = create_model(TaskType.LINEAR_REGRESSION, jnp.asarray(w))
        m2 = legacy.evaluate_glm(lin, dense_data(X, ylin))
        from sklearn.metrics import mean_squared_error

        pred = np.asarray(X @ w)
        assert m2[legacy.MEAN_SQUARE_ERROR] == pytest.approx(
            mean_squared_error(ylin, pred), rel=1e-5
        )
        assert m2[legacy.R_SQUARED] > 0.8
        assert legacy.PEAK_F1_SCORE not in m2


# ---- one compiled program and one fetch an evaluation (ISSUE 28) ----

_PARITY_SPECS = [
    "AUC",
    "AUPR",
    "RMSE",
    "LOGISTIC_LOSS",
    "POISSON_LOSS",
    "SQUARED_LOSS",
    "SMOOTHED_HINGE_LOSS",
    "AUC:q",
    "PRECISION@3:q",
]


def _parity_arrays():
    """Weighted rows with tied scores, a padding-weight row, and one group
    (id 0) of a single class."""
    rng = np.random.default_rng(28)
    n = 240
    gids = rng.integers(0, 9, size=n)
    scores = np.round(rng.normal(size=n), 1).astype(np.float32)  # ties
    labels = (rng.uniform(size=n) > 0.5).astype(np.float32)
    labels[gids == 0] = 1.0
    weights = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    weights[3] = 0.0
    return gids, jnp.asarray(scores), jnp.asarray(labels), jnp.asarray(weights)


@pytest.mark.parametrize("spec", _PARITY_SPECS)
def test_suite_evaluate_equals_bare_metric(spec):
    """The compiled evaluation computes what the bare `resolve_metric_fn`
    callable computes operation by operation."""
    gids, scores, labels, weights = _parity_arrays()
    et = EvaluatorType.parse(spec)
    # The suite evaluates all of them in one program; each case reads its own.
    suite = EvaluationSuite(
        [EvaluatorType.parse(s) for s in _PARITY_SPECS],
        labels,
        weights,
        id_tag_values={"q": gids},
        primary=et,
    )
    got = suite.evaluate(scores)
    assert list(got.results) == _PARITY_SPECS
    grouped = build_grouped_index(gids) if et.is_grouped else None
    bare = float(resolve_metric_fn(et, grouped)(scores, labels, weights))
    assert np.isfinite(bare)
    np.testing.assert_allclose(got.primary_value, bare, rtol=1e-6, atol=1e-6)


def _evaluation_counts():
    """Evaluations made, and the times JAX made the evaluation program ready
    anew: its entries in the record of every program of the process."""
    compile_cache.listen()
    made = [r for r in compile_cache.programs() if r["program"] == "jit(evaluate_metrics)"]
    return telemetry.METRICS.get_counter("evaluation_calls"), len(made)


def test_evaluation_program_is_keyed_on_shapes_not_on_the_suite(rng):
    """Suites built afresh over arrays of one shape and one evaluator list
    share ONE traced program; a new row count traces one more."""
    ets = [EvaluatorType("AUC"), EvaluatorType.parse("PRECISION@2:q")]

    def fresh_suite(n):
        labels = (rng.uniform(size=n) > 0.5).astype(np.float32)
        weights = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
        # Every group id appears exactly four times: one gather shape.
        gids = rng.permutation(np.repeat(np.arange(n // 4), 4))
        return EvaluationSuite(
            ets, jnp.asarray(labels), jnp.asarray(weights), id_tag_values={"q": gids}
        )

    n = 52  # a row count no other test of this file evaluates with these ets
    calls0, traces0 = _evaluation_counts()
    for _ in range(3):
        suite = fresh_suite(n)
        for _ in range(2):
            suite.evaluate(jnp.asarray(rng.normal(size=n).astype(np.float32)))
    calls1, traces1 = _evaluation_counts()
    assert (calls1 - calls0, traces1 - traces0) == (6, 1)

    fresh_suite(n + 4).evaluate(
        jnp.asarray(rng.normal(size=n + 4).astype(np.float32))
    )
    calls2, traces2 = _evaluation_counts()
    assert (calls2 - calls1, traces2 - traces1) == (1, 1)

    # The streaming evaluator goes through the same function: a window size
    # traces once, however many evaluators are built.
    plain = [EvaluatorType("AUC"), EvaluatorType("RMSE")]
    for _ in range(3):
        StreamingWindowEvaluator(plain).evaluate_window(
            jnp.asarray(rng.normal(size=n).astype(np.float32)),
            jnp.asarray((rng.uniform(size=n) > 0.5).astype(np.float32)),
        )
    calls3, traces3 = _evaluation_counts()
    assert (calls3 - calls2, traces3 - traces2) == (3, 1)


def test_second_fit_does_not_trace_the_evaluation_again(rng):
    """`GameEstimator.fit` builds a new EvaluationSuite in every fit; the
    second fit of a process must find the first one's program."""
    n, n_val, d = 600, 150, 6
    X = rng.normal(size=(n + n_val, d)).astype(np.float32)
    y = (rng.uniform(size=n + n_val) > 0.5).astype(np.float32)
    train = GameDataset.build({"g": X[:n]}, y[:n])
    val = GameDataset.build({"g": X[n:]}, y[n:])
    est = GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {"global": FixedEffectDataConfig("g")},
        validation_evaluators=[EvaluatorType("AUC")],
        pipeline=False,
    )
    cfg = {
        "global": CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=3)
        )
    }
    calls0, traces0 = _evaluation_counts()
    first = est.fit(train, val, [cfg])
    calls1, traces1 = _evaluation_counts()
    second = est.fit(train, val, [cfg])
    calls2, traces2 = _evaluation_counts()
    # The one inside coordinate descent, which is also the final one (ISSUE 41).
    assert calls1 - calls0 == 1 and calls2 - calls1 == 1
    # 0 where an earlier test of the process evaluated AUC over 150 rows.
    assert traces1 - traces0 <= 1
    assert traces2 - traces1 == 0
    assert (
        second[0].evaluation.primary_value == first[0].evaluation.primary_value
    )
