"""GAME model containers: coefficients, per-coordinate models, composite model.

Counterpart of:
  - photon-lib model/Coefficients.scala:31 (means + optional variances)
  - photon-api model/FixedEffectModel.scala:33 (broadcast GLM)
  - photon-api model/RandomEffectModel.scala:36-239 (RDD[(REId, GLM)])
  - photon-lib model/GameModel.scala:32-110 (Map[CoordinateId -> model],
    score = sum of coordinate scores)
  - photon-api supervised/* link-function wrappers (GeneralizedLinearModel.scala:33)

TPU-native translation: a random-effect model is not a distributed collection
of tiny JVM objects but one dense (num_entities, dim) coefficient matrix
sharded over the mesh's entity axis; scoring is a gather of per-row entity
indices + batched dot products instead of an RDD join. The fixed-effect model
is a single replicated vector. A GameModel scores a dataset by summing
coordinate scores in a fixed sample order — the reference's by-uid score-RDD
joins become pure elementwise adds because every coordinate shares the same
static sample layout.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, Optional

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.containers import LabeledData
from photon_ml_tpu.ops import objective
from photon_ml_tpu.ops.losses import mean_for_task
from photon_ml_tpu.types import TaskType

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Coefficients:
    """Model coefficients: means + optional variances (Coefficients.scala:31).

    The leading axes may be batched: (D,) for a fixed effect, (E, D) for a
    random-effect block.
    """

    means: Array
    variances: Optional[Array] = None

    @property
    def dim(self) -> int:
        return self.means.shape[-1]

    def compute_score(self, x: Array) -> Array:
        """means . x (Coefficients.computeScore, Coefficients.scala:53-60)."""
        return jnp.einsum("...d,...d->...", self.means, x)


def zero_coefficients(dim: int, dtype=jnp.float32) -> Coefficients:
    return Coefficients(jnp.zeros((dim,), dtype))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FixedEffectModel:
    """One GLM applied to every sample (FixedEffectModel.scala:33).

    `task` determines the link function for mean-response scoring
    (GeneralizedLinearModel.computeMean).
    """

    coefficients: Coefficients
    task: TaskType = dataclasses.field(metadata=dict(static=True))

    def score(self, data: LabeledData) -> Array:
        """Raw margins x.w (no offset), matching DatumScoringModel semantics —
        offsets/other-coordinate scores are added by the caller."""
        return objective.compute_margins(
            self.coefficients.means,
            dataclasses.replace(data, offsets=jnp.zeros_like(data.offsets)),
            None,
        )

    def predict_mean(self, data: LabeledData) -> Array:
        return mean_for_task(self.task, self.score(data) + data.offsets)


import functools as _functools


@_functools.lru_cache(maxsize=16)
def _margins_sharded_fn(mesh):
    """One jitted program per mesh (scoring is a per-CD-iteration hot path;
    an eager pad + vmap + einsum chain would dispatch op-by-op)."""
    return jax.jit(
        _functools.partial(_random_effect_margins_sharded_impl, mesh=mesh)
    )


def random_effect_margins_sharded(
    features, entity_rows: Array, matrix: Array, norm, mesh
) -> Array:
    return _margins_sharded_fn(mesh)(features, entity_rows, matrix, norm)


def _random_effect_margins_sharded_impl(
    features, entity_rows: Array, matrix: Array, norm, *, mesh
) -> Array:
    """Sharded-gather scoring: the row-sharded coefficient matrix is read via
    the ring collective (parallel/mesh.ring_gather_rows) so no device ever
    materializes the full (E+1, D) matrix — the sharded counterpart of
    RandomEffectModel.score's re-key + join (RandomEffectModel.scala:239+).

    Normalization is applied to the gathered per-sample rows (same row-wise
    algebra as the replicated path). Per-entity normalization is not
    supported here — its factor/shift tables are themselves entity-sized and
    would need the same sharding; callers keep the replicated path for it.

    NOTE: the norm algebra and sparse/dense dot below deliberately mirror
    `random_effect_margins`; they cannot share code without materializing
    (N, D) gathered rows on the replicated sparse path (a memory regression
    there). tests/test_parallel.py asserts numerical parity between the two,
    with and without normalization — keep both in sync.
    """
    from photon_ml_tpu.data.containers import SparseFeatures as _SF
    from photon_ml_tpu.ops.normalization import PerEntityNormalization
    from photon_ml_tpu.parallel.mesh import ring_gather_rows

    if isinstance(norm, PerEntityNormalization) and not norm.is_identity:
        raise NotImplementedError(
            "sharded scoring with per-entity normalization: use the "
            "replicated path"
        )
    n = entity_rows.shape[0]
    ndev = mesh.devices.size
    rem = (-n) % ndev  # ring collectives need evenly splittable requests
    rows_q = jnp.pad(entity_rows, (0, rem)) if rem else entity_rows
    w_rows = ring_gather_rows(matrix, rows_q, mesh)[:n]  # (N, D), sample-sharded
    shift = None
    if norm is not None and not norm.is_identity:
        w_rows = jax.vmap(norm.effective_coefficients)(w_rows)
        if norm.shifts is not None:
            # Per-row reduce, in lockstep with `random_effect_margins` and
            # `gathered_row_margins` (see the note there).
            shift = -jnp.sum(w_rows * norm.shifts, axis=-1)
    if isinstance(features, _SF):
        if features.ell_axis == -2:  # transposed (K, N) projected planes
            g = jnp.take_along_axis(
                w_rows.T, features.indices.astype(jnp.int32), axis=0
            )
            out = jnp.sum(g * features.values, axis=0)
        else:
            g = jnp.take_along_axis(w_rows, features.indices, axis=1)
            out = jnp.sum(g * features.values, axis=-1)
    else:
        # Batch-invariant per-row reduce, mirroring `random_effect_margins`
        # (see the note there) — keep both dense branches in sync.
        out = jnp.sum(features * w_rows, axis=-1)
    if shift is not None:
        out = out + shift
    return out


def gathered_row_margins(features: Array, w_rows: Array, norm) -> Array:
    """Dense margins from already-gathered per-sample coefficient rows:
    normalization folded per row, then the batch-invariant per-row reduce.

    BITWISE-equal to `random_effect_margins`' dense branch on the same
    rows: folding norm into the matrix before the gather and into the
    gathered rows after it are the same elementwise ops on the same
    values, and the row-shift dot runs in the same order over D. This is
    the shared tail of every path that moves rows instead of replicating
    the matrix — the psum-gather margins below and the serving engine's
    two-tier / entity-sharded bucket programs — and what keeps them all
    bitwise-equal to the replicated offline scorer."""
    from photon_ml_tpu.ops.normalization import PerEntityNormalization

    if isinstance(norm, PerEntityNormalization) and not norm.is_identity:
        raise NotImplementedError(
            "gathered-row margins with per-entity normalization: its "
            "factor/shift tables are entity-indexed — use the replicated path"
        )
    shift = None
    if norm is not None and not norm.is_identity:
        w_rows = jax.vmap(norm.effective_coefficients)(w_rows)
        if norm.shifts is not None:
            # Per-row reduce, NOT `w_rows @ shifts`: the matvec's reduction
            # order varies with the batch dimension (same pitfall as
            # dense_margins), which would break bitwise parity between the
            # (N, D) gathered path here and the (E+1, D) matrix-folded path
            # in `random_effect_margins` — both now reduce row-wise.
            shift = -jnp.sum(w_rows * norm.shifts, axis=-1)
    out = jnp.sum(features * w_rows, axis=-1)
    if shift is not None:
        out = out + shift
    return out


@_functools.lru_cache(maxsize=16)
def _margins_bcast_fn(mesh):
    return jax.jit(
        _functools.partial(_random_effect_margins_bcast_impl, mesh=mesh)
    )


def random_effect_margins_bcast(
    features: Array, entity_rows: Array, matrix: Array, norm, mesh
) -> Array:
    """Small-batch sharded scoring: the row-sharded matrix is read via the
    psum broadcast-gather (`parallel/mesh.bcast_gather_rows`) — each shard
    contributes the requested rows it owns, one all-reduce returns the
    gathered block everywhere — instead of rotating matrix chunks around
    the ring. For serving-bucket-sized batches (replicated request
    buffers) this is one collective of N*D floats vs a full matrix
    rotation, and the gather is exact row movement, so scores stay
    BITWISE-equal to the replicated `random_effect_margins` dense branch
    (asserted in tests/test_parallel.py). Dense features only — the
    high-volume sparse/sample-sharded paths keep the ring
    (`random_effect_margins_sharded`)."""
    return _margins_bcast_fn(mesh)(features, entity_rows, matrix, norm)


def _random_effect_margins_bcast_impl(
    features: Array, entity_rows: Array, matrix: Array, norm, *, mesh
) -> Array:
    from photon_ml_tpu.parallel.mesh import bcast_gather_rows

    w_rows = bcast_gather_rows(matrix, entity_rows, mesh)
    return gathered_row_margins(features, w_rows, norm)


def random_effect_margins(features, entity_rows: Array, matrix: Array, norm) -> Array:
    """Per-sample random-effect margins: gather each sample's coefficient row
    and dot, with normalization folded in once per entity row (the same
    algebra the training objective uses), for BOTH dense and sparse features.
    Shared by RandomEffectCoordinate scoring and GameTransformer. jit-safe.
    """
    from photon_ml_tpu.data.containers import SparseFeatures as _SF
    from photon_ml_tpu.ops.normalization import PerEntityNormalization

    shift = None
    if isinstance(norm, PerEntityNormalization) and not norm.is_identity:
        # Projected-space normalization: each entity row has its own
        # factors/shifts (IndexMapProjectorRDD.scala:133).
        matrix = norm.effective_matrix(matrix)
        if norm.shifts is not None:
            shift = -jnp.sum(norm.shifts * matrix, axis=1)  # (E+1,)
    elif norm is not None and not norm.is_identity:
        matrix = jax.vmap(norm.effective_coefficients)(matrix)
        if norm.shifts is not None:
            # Per-row reduce (batch-invariant), matching
            # `gathered_row_margins` / the sharded twin bitwise — a matvec
            # here would reduce in an (E+1)-dependent order and diverge
            # from the (N, D) gathered paths at the last ulp.
            shift = -jnp.sum(matrix * norm.shifts, axis=-1)  # (E+1,)
    if isinstance(features, _SF):
        if features.ell_axis == -2:
            # Transposed (K, N) projected planes: broadcast the entity rows
            # across K — same gather, no transpose materialization.
            rows = matrix[entity_rows[None, :], features.indices.astype(jnp.int32)]
            out = jnp.sum(rows * features.values, axis=0)
        else:
            # (N, K) gather out of the (E+1, D) matrix, then sparse dot.
            # Gathered through the (K, N) transpose of the index plane and
            # transposed back (same values, same places): a gather indexed
            # by a long, narrow (N, K) array costs XLA's TPU compiler
            # minutes (157-181 s at 200k x 9), its transpose a second.
            rows = matrix[entity_rows[None, :], features.indices.T].T
            out = jnp.sum(rows * features.values, axis=-1)
    else:
        # Multiply-broadcast + per-row reduce, NOT einsum("nd,nd->n"): the
        # einsum lowers to a dot_general whose reduction order varies with
        # the batch dimension (a 1-row batch measurably diverges from the
        # same row inside a 9-row batch on CPU), while the per-row reduce
        # is batch-size invariant — required for the serving engine's
        # padded-bucket scoring to match this offline path bitwise (see
        # transformers.game_transformer.dense_margins).
        out = jnp.sum(features * matrix[entity_rows], axis=-1)
    if shift is not None:
        out = out + shift[entity_rows]
    return out


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RandomEffectModel:
    """Per-entity GLMs as one (num_entities, dim) matrix
    (RandomEffectModel.scala:36-239).

    Row e holds the coefficients of entity e in this coordinate's (projected)
    feature space. Samples carry an `entity_row` index; scoring gathers the
    matching coefficient row per sample — the RDD re-key + join of the
    reference (RandomEffectModel.scala:239+) becomes a gather. Samples whose
    entity was unseen at training time use row `num_entities` which is pinned
    to zeros (the reference scores those with the prior/zero model).
    """

    coefficients_matrix: Array  # (>= E + 1, D); row E (pinned zero) scores
    # unseen entities; rows past E + 1 exist only when the matrix is padded
    # to a device-mesh multiple (entity-sharded store) and are all-zero.
    variances_matrix: Optional[Array]
    task: TaskType = dataclasses.field(metadata=dict(static=True))
    # Logical entity count E. None = unpadded matrix (E = rows - 1); set by
    # mesh-trained coordinates whose matrices are row-padded.
    n_entities: Optional[int] = dataclasses.field(
        default=None, metadata=dict(static=True)
    )

    @property
    def num_entities(self) -> int:
        if self.n_entities is not None:
            return self.n_entities
        return self.coefficients_matrix.shape[0] - 1

    @property
    def unseen_row(self) -> int:
        """Row index scoring uses for entities unseen at training time."""
        return self.num_entities

    @property
    def dim(self) -> int:
        return self.coefficients_matrix.shape[-1]

    def score_rows(self, features: Array, entity_rows: Array) -> Array:
        """Score dense per-sample features (N, D) against their entity rows."""
        w = self.coefficients_matrix[entity_rows]
        return jnp.einsum("nd,nd->n", features, w)


@dataclasses.dataclass
class GameModel:
    """coordinate id -> model (GameModel.scala:32); host-side container.

    Scoring sums per-coordinate scores over a shared sample layout
    (GameModel.scala:99-110); done by GameTransformer / scoring drivers which
    own the per-coordinate datasets.
    """

    models: Dict[str, object]

    def __getitem__(self, cid: str):
        return self.models[cid]

    def __contains__(self, cid: str) -> bool:
        return cid in self.models

    def items(self):
        return self.models.items()

    def updated(self, cid: str, model) -> "GameModel":
        new = dict(self.models)
        new[cid] = model
        return GameModel(new)

    @property
    def coordinate_ids(self):
        return list(self.models.keys())
