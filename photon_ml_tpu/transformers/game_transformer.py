"""GameTransformer: batch scoring of a GAME model over a GameDataset.

Counterpart of photon-api transformers/GameTransformer.scala:39-318 and the
model scoring paths it drives (GameModel.scala:99-110,
FixedEffectModel.score — broadcast + mapValues dot products;
RandomEffectModel.score — re-key by REId + join, RandomEffectModel.scala:239+).

TPU translation: scoring a dataset is one jitted program per coordinate —
fixed effects are a (sharded) matvec, random effects a coefficient-row gather
plus batched dot products; the per-coordinate score RDD join becomes an
elementwise sum because every coordinate scores the same static sample axis.

The transformer also owns the *data plumbing* that scoring a NEW dataset
needs (which the reference rebuilds inside transform():150-263):
  * mapping each sample's entity key to a coefficient row through the
    training-time entity index (unseen entities -> the pinned zero row);
  * projecting the random-effect feature shard through the training-time
    projector (scoring happens in projected space — same math as training,
    avoiding RandomEffectModelInProjectedSpace back-projection);
  * folding normalization into effective coefficients.

That plumbing is dataset-bound, so it is factored into
`prepare_coordinate_data`; repeated scoring of the same dataset (the
coordinate-descent validation loop, every later fit of one estimator on the
same validation set: `GameEstimator._validation_prepared`) reuses the
prepared features/entity rows.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.containers import Features, LabeledData, SparseFeatures, span_note
from photon_ml_tpu.data.game_dataset import GameDataset
from photon_ml_tpu.evaluation.suite import EvaluationResults, EvaluationSuite
from photon_ml_tpu.game.model import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
    random_effect_margins,
)
from photon_ml_tpu.ops import objective
from photon_ml_tpu.ops.losses import mean_for_task
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils.observability import set_stage_note

Array = jax.Array


@dataclasses.dataclass
class CoordinateScoringSpec:
    """Everything needed to score one coordinate on a fresh dataset.

    `shard` is the ORIGINAL feature-shard name as it appears in incoming
    datasets; `projector`/`entity_index` are the training-time artifacts for
    random-effect coordinates (None for fixed effects).
    """

    shard: str
    norm: Optional[NormalizationContext] = None
    random_effect_type: Optional[str] = None
    entity_index: Optional[Dict[object, int]] = None
    projector: Optional[object] = None

    @property
    def is_random_effect(self) -> bool:
        return self.random_effect_type is not None


@dataclasses.dataclass
class PreparedCoordinateData:
    """One coordinate's scoring view of one dataset: (projected) features +
    per-sample entity rows (None for fixed effects)."""

    features: Features
    entity_rows: Optional[Array]


def entity_rows_for_dataset(
    dataset: GameDataset, spec: CoordinateScoringSpec
) -> np.ndarray:
    """Per-sample coefficient-row indices through the training entity index;
    unseen entities get the pinned zero row (the reference's prior-model
    scoring of new entities)."""
    keys = dataset.id_tags[spec.random_effect_type]
    index = spec.entity_index
    unseen = len(index)
    # Entity ids are strings in persisted artifacts (REId = String,
    # Types.scala:9-25) but may be ints in in-memory datasets; coerce lookup
    # keys to the index's key type so reloaded models resolve entities.
    coerce = (
        index
        and isinstance(next(iter(index)), str)
        and keys.dtype.kind not in "USO"
    )
    # Ingest-factorized columns: resolve the small value table through the
    # index and gather — no n_samples sort at all.
    ct = getattr(dataset, "tag_codes", {}).get(spec.random_effect_type)
    if ct is not None:
        codes, tbl = ct
        tbl_rows = np.fromiter(
            (index.get(k, unseen) for k in tbl.tolist()),
            np.int64,
            count=len(tbl),
        )
        return tbl_rows[codes]
    # Dict-lookup the UNIQUE keys only (entities repeat ~n/E times), then
    # scatter through the inverse — the per-row Python loop was the last
    # O(n) interpreter cost in the scoring path. np.unique needs orderable
    # keys (it sorts); hand-built object-dtype tags with mixed types keep
    # the hash-based per-row path.
    try:
        uniq, inv = np.unique(keys, return_inverse=True)
    except TypeError:
        return np.fromiter(
            (
                index.get(str(k) if coerce else k, unseen)
                for k in keys.tolist()
            ),
            np.int64,
            count=len(keys),
        )
    uniq_rows = np.fromiter(
        (
            index.get(str(k) if coerce else k, unseen)
            for k in uniq.tolist()
        ),
        np.int64,
        count=len(uniq),
    )
    return uniq_rows[inv]


def prefetch_fixed_effect_shards(
    specs: Mapping[str, CoordinateScoringSpec],
    coordinate_ids,
    dataset: GameDataset,
    pipeline: Optional[bool] = None,
) -> None:
    """Kick the async upload of every fixed-effect shard (ShardDict
    prefetch, double-buffered) so the transfers overlap the host-side
    entity-row resolution and projection of the random-effect coordinates
    instead of each faulting synchronously in sequence. Random-effect
    shards are NOT prefetched: their scoring view is the projected shard
    `prepare_coordinate_data` builds/uploads itself — prefetching the raw
    ELL would ship bytes scoring never reads. No-op when the host
    data-plane pipeline is off (`pipeline` override, else data/pipeline.py
    gating) — the single switch that must keep forced-synchronous runs
    thread-free."""
    from photon_ml_tpu.data.pipeline import pipeline_enabled

    if not pipeline_enabled(pipeline) or not hasattr(dataset.shards, "prefetch"):
        return
    for cid in coordinate_ids:
        if not specs[cid].is_random_effect:
            dataset.shards.prefetch(specs[cid].shard)


def prepare_coordinate_data(
    spec: CoordinateScoringSpec, dataset: GameDataset
) -> PreparedCoordinateData:
    """Resolve entity rows and run the projector: once per (coordinate,
    dataset) where the caller keeps the result, as
    `GameEstimator._validation_prepared` does for a validation set.
    Everything downstream is pure device compute."""
    if not spec.is_random_effect:
        # An ELL shard is scored with its dense-span annotation, which the
        # data set reads once and keeps (`GameDataset.annotated_shard`).
        feats = dataset.annotated_shard(spec.shard)
        if isinstance(feats, SparseFeatures):
            set_stage_note("ell_planes_scored", span_note(feats))
        return PreparedCoordinateData(feats, None)
    rows = entity_rows_for_dataset(dataset, spec)
    host_planes = getattr(dataset, "host_ell", {}).get(spec.shard)
    if spec.projector is not None and host_planes is not None:
        # Project from ingest's host planes: the raw ELL never ships to
        # the device (ShardDict lazy upload) — only the projected shard
        # does, inside project_features.
        feats = (
            dataset.peek_shard(spec.shard)
            if hasattr(dataset, "peek_shard")
            else dataset.shards[spec.shard]
        )
        feats = spec.projector.project_features(
            feats, rows, host_planes=host_planes
        )
    else:
        feats = dataset.shards[spec.shard]
        if spec.projector is not None:
            feats = spec.projector.project_features(feats, rows)
    return PreparedCoordinateData(feats, jnp.asarray(rows, jnp.int32))


@jax.jit
def _re_margins(features: Features, entity_rows: Array, matrix: Array, norm) -> Array:
    # The scope names these operations in a device trace; training and
    # validation rows run the same code as programs of different shapes.
    with jax.named_scope("score/random"):
        return random_effect_margins(features, entity_rows, matrix, norm)


def _entity_sharded_mesh(matrix):
    """The 1-D mesh a row-sharded coefficient matrix lives on, if any."""
    from photon_ml_tpu.parallel.mesh import leading_axis_mesh

    return leading_axis_mesh(matrix, require_divisible=True)


# Dense batches up to this many rows score sharded matrices through the psum
# broadcast-gather; beyond it (dataset-scale scoring) the replicated (N, D)
# gathered block would cost more HBM than the ring rotation it avoids.
_BCAST_SCORING_MAX_ROWS = 4096


def dense_margins(features: Array, w: Array, norm) -> Array:
    """Row-stable dense margins: multiply-broadcast + per-row reduction
    instead of the matvec `features @ w`. The matvec's CPU/TPU lowering picks
    blocking by the BATCH dimension, so the same row can score differently at
    different batch sizes (observed 2e-6 drift on CPU between a 7-row and a
    padded 16-row call); the per-row reduction's within-row order is fixed
    regardless of how many rows ride along. That batch-size invariance is
    what lets the online serving engine score padded power-of-two buckets
    bitwise-identically to this offline path (serving/engine.py), and makes
    a request's score independent of which micro-batch it lands in. Margins
    are bandwidth-bound (one multiply-add per X element), so giving up the
    matvec costs little. jit-traceable; shared by `_fe_margins` and the
    serving engine's fused program — keep both on this one code path."""
    w_eff, shift = objective.margin_params(w, norm)
    return jnp.sum(features * w_eff, axis=-1) + shift


@jax.jit
def _fe_margins(features: Features, w: Array, norm) -> Array:
    # `features` may be an ELL SparseFeatures (either layout), a dense
    # matrix, or the trained coordinate's BucketedSparseFeatures or
    # DenseBlock (training_prepared's preference) — each exposes the
    # logical (n_rows, dim) via .shape, and compute_margins handles each. Dense
    # matrices take the row-stable path (see `dense_margins`); the sparse
    # layouts' gather + per-row-K reductions are already batch-invariant.
    with jax.named_scope("score/fixed"):
        if isinstance(features, (jax.Array, np.ndarray)):
            return dense_margins(features, w, norm)
        n = features.shape[0]
        zeros = jnp.zeros((n,), w.dtype)
        return objective.compute_margins(
            w, LabeledData(features, zeros, zeros, zeros), norm
        )


def coordinate_margins(
    spec: CoordinateScoringSpec, model, prepared: PreparedCoordinateData
) -> Array:
    """Score one coordinate's model over prepared data."""
    if spec.is_random_effect:
        assert isinstance(model, RandomEffectModel)
        matrix = model.coefficients_matrix
        mesh = _entity_sharded_mesh(matrix)
        from photon_ml_tpu.ops.normalization import PerEntityNormalization

        if mesh is not None and not isinstance(spec.norm, PerEntityNormalization):
            # Mesh-trained row-sharded matrix: the full (E+1, D) matrix is
            # never replicated on one device (the whole point of the
            # entity-sharded store). Dense small batches take the psum
            # broadcast-gather (one collective of N*D floats — the serving
            # engine's dispatch, bitwise-equal to the replicated branch);
            # sparse or dataset-scale sample axes keep the ring, whose wire
            # cost is independent of N.
            from photon_ml_tpu.game.model import (
                random_effect_margins_bcast,
                random_effect_margins_sharded,
            )

            dense = isinstance(prepared.features, (jax.Array, np.ndarray))
            if dense and prepared.entity_rows.shape[0] <= _BCAST_SCORING_MAX_ROWS:
                return random_effect_margins_bcast(
                    prepared.features, prepared.entity_rows, matrix, spec.norm, mesh
                )
            return random_effect_margins_sharded(
                prepared.features, prepared.entity_rows, matrix, spec.norm, mesh
            )
        return _re_margins(prepared.features, prepared.entity_rows, matrix, spec.norm)
    assert isinstance(model, FixedEffectModel)
    return _fe_margins(prepared.features, model.coefficients.means, spec.norm)


@dataclasses.dataclass
class TransformResult:
    """ModelDataScores equivalent: raw summed margins (incl. offsets) plus the
    task-link mean response (ScoredGameDatum fields)."""

    scores: Array
    means: Array
    per_coordinate: Dict[str, Array]


class GameTransformer:
    """Scores GameDatasets with a trained GAME model (GameTransformer.scala).

    `specs` must cover every coordinate of the model; built by GameEstimator
    (training) or reconstructed from a model store (scoring driver).
    """

    def __init__(
        self,
        model: GameModel,
        specs: Mapping[str, CoordinateScoringSpec],
        task: TaskType,
        *,
        pipeline: Optional[bool] = None,
    ):
        missing = [c for c in model.coordinate_ids if c not in specs]
        if missing:
            raise ValueError(f"No scoring spec for coordinates {missing}")
        self.model = model
        self.specs = dict(specs)
        self.task = task
        # Host data-plane pipelining override (see GameEstimator.pipeline);
        # None = the data/pipeline.py env/auto gate.
        self.pipeline = pipeline

    def prepare(self, dataset: GameDataset) -> Dict[str, PreparedCoordinateData]:
        """One-time host prep of `dataset` for every coordinate; pass the
        result to transform() when scoring the same dataset repeatedly.

        When the host data-plane pipeline is enabled, fixed-effect shard
        uploads start asynchronously first so they overlap the
        random-effect host prep (see `prefetch_fixed_effect_shards`)."""
        prefetch_fixed_effect_shards(
            self.specs, self.model.coordinate_ids, dataset, self.pipeline
        )
        return {
            cid: prepare_coordinate_data(self.specs[cid], dataset)
            for cid in self.model.coordinate_ids
        }

    def score_coordinate(
        self,
        cid: str,
        dataset: GameDataset,
        prepared: Optional[PreparedCoordinateData] = None,
    ) -> Array:
        spec = self.specs[cid]
        if prepared is None:
            prepared = prepare_coordinate_data(spec, dataset)
        return coordinate_margins(spec, self.model[cid], prepared)

    def transform(
        self,
        dataset: GameDataset,
        prepared: Optional[Dict[str, PreparedCoordinateData]] = None,
    ) -> TransformResult:
        """GameTransformer.transform:150 / scoreGameDataset:263 — sum of
        coordinate scores + offsets, and the link-function mean."""
        if prepared is None:
            prepared = self.prepare(dataset)
        per_coordinate = {
            cid: coordinate_margins(self.specs[cid], self.model[cid], prepared[cid])
            for cid in self.model.coordinate_ids
        }
        total = dataset.offsets
        for s in per_coordinate.values():
            total = total + s
        means = mean_for_task(self.task, total)
        return TransformResult(scores=total, means=means, per_coordinate=per_coordinate)

    def evaluate(
        self,
        dataset: GameDataset,
        suite: EvaluationSuite,
        prepared: Optional[Dict[str, PreparedCoordinateData]] = None,
    ) -> EvaluationResults:
        """Optional validation path of the transformer (GameTransformer.scala
        logValidationMetrics)."""
        return suite.evaluate(self.transform(dataset, prepared).scores)
