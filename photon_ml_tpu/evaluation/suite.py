"""Evaluator objects, grouped (multi) evaluators, and the evaluation suite.

Counterpart of photon-lib evaluation/ (Evaluator.scala:22,
EvaluationSuite.scala:33-56, MultiEvaluator.scala:36, EvaluatorType.scala:57-65,
MultiEvaluatorType.scala:24-74, EvaluationResults.scala) and the photon-api
evaluator implementations + EvaluatorFactory.scala:26-36.

Structural translation: the reference joins an RDD of scores with the
(label, offset, weight) RDD once and fans out to evaluators; here scores and
labels live in fixed sample order in device arrays, so single evaluators are
direct reductions. MultiEvaluators (per-query AUC, precision@k) replace the
groupBy-id shuffle with a precomputed padded gather: group rows are collected
host-side once into a (num_groups, max_group_size) index matrix, and the
grouped metric is a vmap of the local metric with padding masked by weight 0 —
the reference's LocalEvaluator-per-group loop becomes one batched kernel.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Callable, Dict, Mapping, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.evaluation import metrics
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils import telemetry

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class EvaluatorType:
    """Parsed evaluator spec (EvaluatorType.scala + MultiEvaluatorType.scala).

    Plain: AUC, AUPR, RMSE, LOGISTIC_LOSS, POISSON_LOSS, SQUARED_LOSS,
    SMOOTHED_HINGE_LOSS. Grouped: "AUC:<idTag>", "PRECISION@<k>:<idTag>"
    (MultiEvaluatorType.scala:52-74 regex parsing).
    """

    name: str
    id_tag: Optional[str] = None
    k: Optional[int] = None

    @property
    def is_grouped(self) -> bool:
        return self.id_tag is not None

    def __str__(self) -> str:
        base = f"PRECISION@{self.k}" if self.name == "PRECISION" else self.name
        return f"{base}:{self.id_tag}" if self.id_tag else base

    _PRECISION_RE = re.compile(r"(?i)^PRECISION@(\d+):(.+)$")
    _AUC_GROUP_RE = re.compile(r"(?i)^AUC:(.+)$")
    _PLAIN = {
        "AUC",
        "AUPR",
        "RMSE",
        "LOGISTIC_LOSS",
        "POISSON_LOSS",
        "SQUARED_LOSS",
        "SMOOTHED_HINGE_LOSS",
    }

    @classmethod
    def parse(cls, spec: str) -> "EvaluatorType":
        spec = spec.strip()
        m = cls._PRECISION_RE.match(spec)
        if m:
            return cls("PRECISION", id_tag=m.group(2), k=int(m.group(1)))
        m = cls._AUC_GROUP_RE.match(spec)
        if m:
            return cls("AUC", id_tag=m.group(1))
        up = spec.upper()
        if up in cls._PLAIN:
            return cls(up)
        raise ValueError(f"Unrecognized evaluator type: {spec!r}")


# Metrics where larger is better (Evaluator.betterThan direction).
_LARGER_IS_BETTER = {"AUC", "AUPR", "PRECISION"}

_METRIC_FNS: Dict[str, Callable] = {
    "AUC": metrics.area_under_roc_curve,
    "AUPR": metrics.area_under_pr_curve,
    "RMSE": metrics.rmse,
    "LOGISTIC_LOSS": metrics.logistic_loss,
    "POISSON_LOSS": metrics.poisson_loss,
    "SQUARED_LOSS": metrics.squared_loss,
    "SMOOTHED_HINGE_LOSS": metrics.smoothed_hinge_loss,
}


def default_evaluator_for_task(task: TaskType) -> EvaluatorType:
    """Task -> default validation evaluator (GameEstimator.scala:614-625)."""
    return {
        TaskType.LOGISTIC_REGRESSION: EvaluatorType("AUC"),
        TaskType.LINEAR_REGRESSION: EvaluatorType("RMSE"),
        TaskType.POISSON_REGRESSION: EvaluatorType("POISSON_LOSS"),
        TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM: EvaluatorType("AUC"),
    }[task]


def better_than(evaluator: EvaluatorType, a: float, b: Optional[float]) -> bool:
    """Is metric value `a` better than `b`? (Evaluator.betterThan)"""
    if b is None:
        return True
    if evaluator.name in _LARGER_IS_BETTER:
        return a > b
    return a < b


def regression(
    evaluator: EvaluatorType, challenger: float, champion: float
) -> float:
    """Signed regression of `challenger` vs `champion` — positive means the
    challenger is WORSE, direction-aware per evaluator (AUC down and RMSE up
    both come out positive). The shadow decision loop compares this against
    its tolerance band; keeping the direction logic next to
    `_LARGER_IS_BETTER` means a new evaluator cannot drift between offline
    `better_than` ranking and the online gate."""
    if evaluator.name in _LARGER_IS_BETTER:
        return champion - challenger
    return challenger - champion


def resolve_metric_fn(
    et: EvaluatorType, grouped: Optional["GroupedIndex"] = None
) -> Callable:
    """The bare metric callable `(scores, labels, weights) -> device scalar`
    for one evaluator — PRECISION k-binding and grouped-gather wrapping
    resolved HERE, the single dispatch point shared by `evaluate_metrics`
    (the one compiled program behind offline `EvaluationSuite.evaluate()`
    and the online `StreamingWindowEvaluator`, serving/shadow.py) and the
    sweep executor's jitted trial-valuation program
    (hyperparameter/sweep.py) — so one metric means the same thing in
    every world and a new evaluator variant cannot drift between them.
    The callable is plain traceable jax: called bare it dispatches one
    device program per operation, so callers on a hot path call it
    inside a `jax.jit`, as those three do."""
    if et.name == "PRECISION":
        base = lambda s, l, w, _k=et.k: metrics.precision_at_k(_k, s, l, w)
    else:
        base = _METRIC_FNS[et.name]
    if et.is_grouped:
        if grouped is None:
            raise ValueError(
                f"Evaluator {et} is grouped and needs its GroupedIndex"
            )
        return lambda s, l, w, _f=base, _i=grouped: _grouped_metric(
            _f, _i, s, l, w
        )
    return base


class GroupedIndex(NamedTuple):
    """Precomputed padded group gather for one id tag."""

    gather: Array  # (G, S) int32 row indices into the sample axis
    mask: Array  # (G, S) 1.0 valid / 0.0 padding


def build_grouped_index(group_ids: np.ndarray, *, max_group_size: Optional[int] = None) -> GroupedIndex:
    """Host-side: bucket sample rows by group id into a padded index matrix.

    Replaces MultiEvaluator's groupBy(idTag) shuffle. Padding slots gather row
    0 but are masked out via the mask channel.
    """
    order = np.argsort(group_ids, kind="stable")
    sorted_ids = group_ids[order]
    uniq, starts = np.unique(sorted_ids, return_index=True)
    bounds = np.append(starts, len(sorted_ids))
    sizes = np.diff(bounds)
    s_max = int(sizes.max()) if max_group_size is None else int(max_group_size)
    g = len(uniq)
    gather = np.zeros((g, s_max), np.int32)
    mask = np.zeros((g, s_max), np.float32)
    for gi in range(g):
        rows = order[bounds[gi] : bounds[gi + 1]][:s_max]
        gather[gi, : len(rows)] = rows
        mask[gi, : len(rows)] = 1.0
    return GroupedIndex(jnp.asarray(gather), jnp.asarray(mask))


def _grouped_metric(
    fn: Callable, idx: GroupedIndex, scores: Array, labels: Array, weights: Array
) -> Array:
    """Average of the local metric over groups (MultiEvaluator.scala:36).

    Groups with no signal (e.g. single-class for AUC) still count, as in the
    reference's unfiltered average of per-group LocalEvaluator results; the
    local metrics return neutral values (0.5 AUC) for degenerate groups.
    """
    s = scores[idx.gather]
    l = labels[idx.gather]
    w = weights[idx.gather] * idx.mask
    per_group = jax.vmap(fn)(s, l, w)
    return jnp.mean(per_group)


@functools.partial(jax.jit, static_argnums=0)
def evaluate_metrics(
    evaluator_types: Tuple[EvaluatorType, ...],
    scores: Array,
    labels: Array,
    weights: Array,
    grouped: Mapping[str, GroupedIndex],
) -> Array:
    """Every evaluator's metric over one score vector, stacked float32 in
    `evaluator_types` order: ONE compiled device program an evaluation.

    Static in the evaluator tuple alone (`k` of PRECISION@k rides in it);
    labels, weights and each id tag's gather are arguments, so the program
    is found again by shapes and shardings whichever suite calls — a suite
    built anew in every fit hits JAX's in-process cache from the second fit
    on (a retrace would show as one more `jit(evaluate_metrics)` in
    `compile_cache.programs()`).
    """
    return jnp.stack(
        [
            jnp.asarray(
                resolve_metric_fn(et, grouped.get(et.id_tag))(
                    scores, labels, weights
                ),
                jnp.float32,
            )
            for et in evaluator_types
        ]
    )


def _evaluate(
    evaluator_types: Sequence[EvaluatorType],
    primary: EvaluatorType,
    scores: Array,
    labels: Array,
    weights: Array,
    grouped: Mapping[str, GroupedIndex],
) -> "EvaluationResults":
    """One `evaluate_metrics` program, one fetch, the results by name."""
    fetched = np.asarray(
        evaluate_metrics(tuple(evaluator_types), scores, labels, weights, grouped)
    )
    return _results(evaluator_types, primary, fetched)


def _results(
    evaluator_types: Sequence[EvaluatorType], primary: EvaluatorType, fetched
) -> "EvaluationResults":
    """The fetched metric vector by name: one evaluation made."""
    telemetry.METRICS.increment("evaluation_calls")
    return EvaluationResults(
        primary=primary,
        results={str(et): float(v) for et, v in zip(evaluator_types, fetched)},
    )


class EvaluationSuite:
    """Holds validation (labels, offsets, weights) + evaluators; one `evaluate`
    call computes every metric for a score vector (EvaluationSuite.scala:33-56).

    `id_tag_values`: map id-tag name -> per-sample group keys (host numpy) for
    grouped evaluators; grouped gathers are built once here.
    """

    def __init__(
        self,
        evaluator_types: Sequence[EvaluatorType],
        labels: Array,
        weights: Optional[Array] = None,
        *,
        id_tag_values: Optional[Dict[str, np.ndarray]] = None,
        primary: Optional[EvaluatorType] = None,
    ):
        if not evaluator_types:
            raise ValueError("EvaluationSuite requires at least one evaluator")
        self.evaluator_types = list(evaluator_types)
        self.primary = primary or self.evaluator_types[0]
        self.labels = labels
        self.weights = (
            weights if weights is not None else jnp.ones_like(labels)
        )
        self._grouped: Dict[str, GroupedIndex] = {}
        for et in self.evaluator_types:
            if et.is_grouped:
                if id_tag_values is None or et.id_tag not in id_tag_values:
                    raise ValueError(
                        f"Evaluator {et} needs id tag values for {et.id_tag!r}"
                    )
                if et.id_tag not in self._grouped:
                    self._grouped[et.id_tag] = build_grouped_index(
                        np.asarray(id_tag_values[et.id_tag])
                    )

    def metric_fn(self, et: EvaluatorType) -> Callable:
        """The bare metric callable `(scores, labels, weights) -> device
        scalar` for one evaluator — delegates to the module-level
        `resolve_metric_fn` dispatch point, binding this suite's grouped
        gather when the evaluator is grouped."""
        return resolve_metric_fn(et, self._grouped.get(et.id_tag))

    def evaluate(self, scores: Array) -> "EvaluationResults":
        """Every metric of the suite: one compiled program
        (`evaluate_metrics`) and one fetch. Scores stay on the device; the
        host waits once, for the stacked vector."""
        return _evaluate(
            self.evaluator_types,
            self.primary,
            scores,
            self.labels,
            self.weights,
            self._grouped,
        )

    def metrics(self, scores: Array) -> Array:
        """`evaluate` handed to the device and not waited on: the float32
        metric vector in `evaluator_types` order. A caller that fetches it
        with other values reads it back through `results`."""
        return evaluate_metrics(
            tuple(self.evaluator_types), scores, self.labels, self.weights, self._grouped
        )

    def results(self, fetched) -> "EvaluationResults":
        """The results of a metric vector `metrics` made, fetched."""
        return _results(self.evaluator_types, self.primary, fetched)


@dataclasses.dataclass(frozen=True)
class EvaluationResults:
    """Metric name -> value, with a designated primary evaluator
    (EvaluationResults.scala)."""

    primary: EvaluatorType
    results: Dict[str, float]

    @property
    def primary_value(self) -> float:
        return self.results[str(self.primary)]

    def better_than(self, other: Optional["EvaluationResults"]) -> bool:
        return better_than(
            self.primary, self.primary_value, None if other is None else other.primary_value
        )


class StreamingWindowEvaluator:
    """Online windowed evaluation through the SAME program as offline.

    The shadow decision loop (serving/shadow.py, ISSUE 18) scores each
    joined (scores, labels) window through `evaluate_metrics`, the one
    compiled program `EvaluationSuite.evaluate` runs — compiled once per
    window size, one fetch a window — so an online regression threshold
    means precisely what it means against an offline validation set (the
    photon-lib validator gate taken online). Unlike a suite, labels
    arrive WITH each window instead of being fixed at construction.

    Grouped evaluators (AUC:<idTag>, PRECISION@k:<idTag>) are refused:
    their gather matrices are built against one fixed validation sample
    order, which a streaming window does not have.
    """

    def __init__(
        self,
        evaluator_types: Sequence[EvaluatorType],
        *,
        primary: Optional[EvaluatorType] = None,
    ):
        if not evaluator_types:
            raise ValueError(
                "StreamingWindowEvaluator requires at least one evaluator"
            )
        grouped = [str(et) for et in evaluator_types if et.is_grouped]
        if grouped:
            raise ValueError(
                "StreamingWindowEvaluator does not support grouped "
                f"evaluators (got {grouped}); grouped gathers assume a "
                "fixed validation sample order"
            )
        self.evaluator_types = list(evaluator_types)
        self.primary = primary or self.evaluator_types[0]

    def evaluate_window(
        self,
        scores: Array,
        labels: Array,
        weights: Optional[Array] = None,
    ) -> "EvaluationResults":
        """Every metric over one window: the program and the fetch of
        `EvaluationSuite.evaluate` (bitwise on identical arrays)."""
        labels = jnp.asarray(labels)
        if int(labels.shape[0]) == 0:
            raise ValueError(
                "empty evaluation window: a windowed metric over zero rows "
                "is undefined — the caller must skip or carry the window"
            )
        scores = jnp.asarray(scores)
        w = weights if weights is not None else jnp.ones_like(labels)
        return _evaluate(
            self.evaluator_types, self.primary, scores, labels, w, {}
        )
