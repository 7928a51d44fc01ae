"""GAME coordinates: per-coordinate training + scoring units.

Counterpart of photon-lib algorithm/Coordinate.scala + ModelCoordinate.scala
and photon-api algorithm/ (FixedEffectCoordinate.scala:33-156,
RandomEffectCoordinate.scala:37-221, FixedEffectModelCoordinate.scala,
RandomEffectModelCoordinate.scala, CoordinateFactory.scala:51).

Execution model:
  * FixedEffectCoordinate: one distributed GLM solve. The reference
    broadcasts coefficients and treeAggregates gradients per L-BFGS/TRON
    iteration (FixedEffectCoordinate.scala:126-133); here the whole optimizer
    loop is one jitted XLA program over the (sharded) batch — coefficient
    "broadcast" is replication, gradient reduction is an ICI all-reduce
    inserted by XLA.
  * RandomEffectCoordinate: the reference joins co-partitioned activeData
    with per-entity problems and runs a JVM optimizer per entity
    (RandomEffectCoordinate.scala:95-131); here each size-bucket of entities
    is one vmapped solver call over (E, S, ...) blocks — thousands of
    co-resident L-BFGS/TRON instances in one XLA program, each stopping via
    its own convergence mask. Per-entity warm start (:110-121) is a gather of
    the previous coefficient matrix. Same-shape buckets additionally fuse
    into ONE lax.scan program per sweep (sweep_scan_enabled, r06): block
    gather, vmapped solve, coefficient scatter and variance all run inside
    it, so a sweep costs O(distinct block shapes) dispatches instead of
    3-4 per bucket — bitwise equal to the per-bucket loop. On an
    entity-sharded mesh (r07) the scan keeps the coefficient matrix
    row-sharded end to end: warm-start gathers and coefficient scatters
    ride the ring collectives INSIDE the scan body, so per-device
    coefficient state stays total/n_devices — the reference's
    RDD-partitioned store (RandomEffectModel.scala:36-239) with XLA
    collectives instead of Spark shuffles.

Each coordinate builds its jitted train/score callables ONCE (per bucket
shape); repeated coordinate-descent iterations and regularization-weight
sweeps hit the compile cache because reg weights and PRNG keys are traced
arguments, not constants.

Residuals enter through the offsets argument (`dataset.addScoresToOffsets`
in the reference, Coordinate.scala); train/score take explicit offset vectors.
"""

from __future__ import annotations

import dataclasses
import logging
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger(__name__)

from photon_ml_tpu.data.containers import LabeledData, SparseFeatures, span_note
from photon_ml_tpu.data.game_dataset import (
    GameDataset,
    RandomEffectDataset,
    gather_block_data,
)
from photon_ml_tpu.data.sampling import down_sample_weights, down_sampler_for_task
from photon_ml_tpu.ops import objective
from photon_ml_tpu.ops.losses import PointwiseLoss, loss_for_task
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.optimize import problem
from photon_ml_tpu.optimize.common import OptResult
from photon_ml_tpu.utils import faults
from photon_ml_tpu.utils.knobs import get_knob
from photon_ml_tpu.utils.observability import set_stage_note
from photon_ml_tpu.optimize.config import CoordinateOptimizationConfig
from photon_ml_tpu.game.model import (
    Coefficients,
    FixedEffectModel,
    RandomEffectModel,
)
from photon_ml_tpu.types import TaskType, VarianceComputationType

Array = jax.Array

# bucketed_cache sentinel: distinguishes "never evaluated" from a cached
# decline (None), so pack economics are decided once per dataset shard.
_PACK_UNDECIDED = object()

# Process-wide jitted-program cache for random-effect bucket solvers,
# keyed by the STATIC training recipe (optimizer config statics, task,
# sampling). Two coordinates with the same recipe (e.g. per-user and
# per-movie trained under one GameOptimizationConfiguration) then share
# compiled programs for equal block shapes — with the canonical bucket
# shapes from build_random_effect_dataset this cuts a GLMix fit's XLA
# program count by ~2x (each compile costs seconds on the chip's
# compiler). Only the norm-free case caches (normalization contexts carry
# arrays, which must not leak across coordinates via a closure).
_RE_JIT_CACHE: dict = {}


@dataclasses.dataclass(frozen=True)
class RandomEffectSolveStats:
    """What one random-effect sweep's solves did. `iterations` and
    `fn_evals` (value+gradient evaluations: an L-BFGS solve makes its
    first and one per line-search trial, 1 + iterations + rejected trials)
    are summed over every entity, and `solves` counts the solves summed
    (every lane of every bucket, dummy-padded slots included); `buckets`
    has one record per bucket shape (`capacity`, `entities`, `buckets`,
    `iterations`, `fn_evals`).
    `per_entity` keeps each dispatch's (bucket indices, iterations,
    fn_evals) with the arrays still on the device:
    `RandomEffectCoordinate.entity_counts` fetches them when a caller
    asks which entity took how many."""

    buckets: List[dict]
    iterations: int
    fn_evals: int
    solves: int
    # Not compared: a scan sweep and the per-bucket loop count the same
    # solves in dispatches of different shapes.
    per_entity: List[tuple] = dataclasses.field(compare=False, repr=False)


@jax.jit
def _solve_totals(counts):
    """[(iterations, fn_evals)] per dispatch -> one (dispatches, 2) array of
    their sums: the sweep's solve record in a single program, so reading it
    is a single fetch."""
    return jnp.stack(
        [jnp.stack([jnp.sum(its), jnp.sum(evals)]) for its, evals in counts]
    )


def _config_with_traced_weight(
    config: CoordinateOptimizationConfig, reg_weight: Array
) -> CoordinateOptimizationConfig:
    """Swap the (static) reg weight for a traced scalar inside jit."""
    return dataclasses.replace(config, reg_weight=reg_weight)


def sweep_scan_enabled() -> bool:
    """Scan-dispatch the random-effect bucket sweep (PHOTON_SWEEP_SCAN,
    default on): same-shape entity buckets run as ONE lax.scan program —
    block gather, vmapped solve, coefficient scatter and (optional)
    variance all inside it — instead of 3-4 XLA dispatches per bucket.
    Flare's whole-pipeline-compilation thesis applied to the solver loop:
    at bench scale the per-sweep program count drops from O(buckets) to
    O(distinct block shapes), which is what dominates small-coordinate
    fits, whose wall is host dispatch latency rather than device time.

    Reads through the typed knob registry at DISPATCH-DECISION time only
    (train_sweep's host-side gate) — never from inside a traced body, so
    the compiled programs stay pure (analysis/jit_purity)."""
    return bool(get_knob("PHOTON_SWEEP_SCAN"))


def _fusion_chunks(idxs, shape, planned_shapes):
    """Split one same-shape bucket index list into scan-dispatch chunks
    per the planned fusion granularity (ISSUE 14): scan_fusion_max 0 =
    unbounded (the pre-planner default, one program per shape); shapes
    absent from the plan's proven re_bucket_shapes set additionally cap
    at NOVEL_SHAPE_FUSE. Consecutive chunks preserve bucket order, so
    any split is bitwise-identical to the fused program."""
    from photon_ml_tpu import planner
    from photon_ml_tpu.planner.plan import NOVEL_SHAPE_FUSE

    cap = max(0, int(planner.planned_value("scan_fusion_max")))
    if planned_shapes is not None and tuple(shape) not in planned_shapes:
        cap = min(cap, NOVEL_SHAPE_FUSE) if cap else NOVEL_SHAPE_FUSE
    if cap <= 0 or len(idxs) <= cap:
        return [list(idxs)]
    return [list(idxs[i : i + cap]) for i in range(0, len(idxs), cap)]




class FixedEffectCoordinate:
    """One fixed-effect coordinate (FixedEffectCoordinate.scala:33-156)."""

    def __init__(
        self,
        dataset: GameDataset,
        config_data_shard: str,
        opt_config: CoordinateOptimizationConfig,
        task: TaskType,
        norm: Optional[NormalizationContext] = None,
    ):
        self.dataset = dataset
        self.shard = config_data_shard
        self.config = opt_config
        self.task = task
        self.loss: PointwiseLoss = loss_for_task(task)
        self.norm = norm
        # Decide the fused-Pallas objective path ONCE here, on the concrete
        # array — its dtype/shape/sharding are all visible, unlike inside the
        # jit trace where should_use would have to guess. The decision is
        # closed over by the jitted train_fn (ragged tails are masked inside
        # the kernel, so no alignment precondition). Batch-sharded data gets
        # a ShardedDispatch: per-device fused kernel + psum under shard_map.
        from photon_ml_tpu.ops import pallas_glm

        # Peek without forcing a device upload: if the bucketed pack
        # engages below, the raw ELL never ships to the device at all.
        feats = (
            dataset.peek_shard(config_data_shard)
            if hasattr(dataset, "peek_shard")
            else dataset.shards[config_data_shard]
        )
        if not isinstance(feats, SparseFeatures) and pallas_glm.prefers_bf16_storage(
            feats, jnp.zeros((feats.shape[-1],), feats.dtype)
        ):
            # bf16-STORED design matrix for the fused kernels: half the HBM
            # bytes per objective pass, single MXU pass in hilo mode. The
            # converted array is coordinate-local and used for BOTH train
            # and score so CD residuals stay consistent; the dataset's f32
            # shard is untouched for other consumers. Cached on the dataset
            # so sweep steps that rebuild coordinates convert once. It lies
            # as the device lays an array of its shape by default — on a
            # v5e column-major at [400000, 2000], row-major at d = 512 —
            # and is never relaid: the kernels read it as it lies (below).
            cache = getattr(dataset, "bucketed_cache", {})
            ckey = ("bf16x", config_data_shard)
            feats = cache.get(ckey)
            if feats is None:
                feats = dataset.shards[config_data_shard].astype(jnp.bfloat16)
                cache[ckey] = feats
        self._use_pallas = (
            False
            if isinstance(feats, SparseFeatures)
            else pallas_glm.dispatch(
                feats, jnp.zeros((feats.shape[-1],), jnp.float32)
            )
        )
        # What computes a TRON solve's Hessian-vector products here: the fused
        # dense kernel wherever it computes the value and gradient, else the
        # XLA composition (every sparse shard's, whatever its products run on).
        self.hessian_vector_kernel = "xla" if self._use_pallas is False else "pallas"
        # How the matrix the fused kernels will read lies, read ONCE here
        # from the concrete array (inside train_fn's trace it is a tracer
        # with no layout) and handed to them with the data: where it lies
        # column-major they take (d, tile) blocks of X^T, a bitcast, and no
        # program relays the matrix (until PR 37 a 1.6 GB `copy` in every
        # execution of train_fn on `lr-epsilon`; pallas_glm, "How X lies").
        self._column_major = False
        if self._use_pallas is not False:
            feats = jnp.asarray(feats)
            self._column_major = not pallas_glm.lies_row_major(feats)
            set_stage_note(
                "dense_storage",
                {
                    "layout": "column_major" if self._column_major else "row_major",
                    "dtype": jnp.dtype(feats.dtype).name,
                    # What the coordinate holds beside the dataset's shard.
                    "bytes": 0
                    if feats is dataset.shards[config_data_shard]
                    else int(feats.nbytes),
                },
            )
        # Sparse shards repack once into the bucketed layout so the
        # objective's matvec/rmatvec run the Pallas sparse kernels
        # (ops/pallas_sparse.py) instead of XLA gather/scatter — the sparse
        # counterpart of the dense fused-kernel decision above. maybe_pack
        # owns the whole decision (backend, dtype, sharding, size, padding
        # economics) and returns None when the ELL/XLA path should stay; it
        # decides from the shard's shapes before it moves any data.
        self._features = feats
        if isinstance(feats, SparseFeatures):
            from photon_ml_tpu.ops import pallas_sparse

            bf = None
            if pallas_sparse.kernels_eligible():
                # Pack once per dataset: sweeps/warm-start chains that
                # rebuild this coordinate reuse the cached layout — and a
                # cached DECLINE, so a shard whose pack isn't worth it is
                # evaluated once, not re-pulled per configuration.
                cache = getattr(dataset, "bucketed_cache", {})
                cached = cache.get(config_data_shard, _PACK_UNDECIDED)
                if cached is _PACK_UNDECIDED:
                    # Preferred path: pack from the host CSR the ingest
                    # stashed on the dataset — no device->host pull of the
                    # ELL arrays (a synchronous copy of the whole shard
                    # back over PCIe for data the host already holds). The
                    # stash is consumed here so the arrays don't pin host
                    # RAM for the run's lifetime; COO expansion is deferred
                    # to this point so ingest never pays it. Fallback keeps
                    # the device-ELL pack for hand-built datasets.
                    csr = getattr(dataset, "host_csr", {}).pop(
                        config_data_shard, None
                    )
                    if csr is not None:
                        # The stash holds the same matrix as the device ELL,
                        # so its pack decision is authoritative — a decline
                        # (size/padding economics) must NOT fall through to
                        # maybe_pack's device->host pull of identical data.
                        # Ingest normally started the host pack on a
                        # background thread (begin_pack_async); this joins
                        # it and pays only the upload.
                        bf = pallas_sparse.finish_pack(
                            csr, dataset.num_samples
                        )
                    else:
                        bf = pallas_sparse.maybe_pack(
                            feats, dataset.num_samples
                        )
                    cache[config_data_shard] = bf
                else:
                    bf = cached
            if bf is not None:
                # A kernel the chip's compiler refuses is an error HERE,
                # with the compiler's message — not inside train_fn's
                # trace, and never a quiet switch to XLA.
                set_stage_note(
                    "sparse_objective",
                    pallas_sparse.require_compiles(bf, self.loss),
                )
                self._features = bf
                # The bucketed repack succeeded, so the objective's fused
                # sparse gate (objective.value_and_gradient: `use_pallas is
                # not False and isinstance(..., BucketedSparseFeatures)`)
                # must be allowed to engage: None = auto.  False stays the
                # caller's genuine escape hatch for shards where the pack was
                # declined and the ELL/XLA composition is the right path.
                self._use_pallas = None
            else:
                # The ELL objective through XLA's gather and scatter-add is a
                # path of its own, named like the packed ones; why the pack
                # was declined is beside it (`pack_declined`).
                set_stage_note("sparse_objective", "ell_xla")
        if isinstance(self._features, SparseFeatures) and not isinstance(
            self._features.indices, jax.Array
        ):
            # ELL path it is (pack declined/ineligible): materialize the
            # device copy through the dataset so other consumers share it.
            self._features = dataset.shards[config_data_shard]
        if isinstance(self._features, SparseFeatures):
            # Which planes are narrow enough for their margins to be a dense
            # span of the coefficients is read from the concrete arrays, once a
            # data set (the data set keeps the annotated shard: a rebuilt
            # coordinate, the next fit and scoring fetch nothing), and noted:
            # how many planes of how many, in which classes, under which limit.
            self._features = dataset.annotated_shard(config_data_shard)
            set_stage_note("ell_planes", span_note(self._features))
        # Sample-sharded rows (parallel/mesh.py): noted with the fit's other
        # dispatch decisions. An ELL shard's objective is then summed over
        # the mesh once an evaluation by the program itself
        # (objective._summed_over_samples); a dense shard's was decided above.
        from photon_ml_tpu.parallel.mesh import leading_axis_mesh

        mesh = None
        if not isinstance(self._features, SparseFeatures):
            mesh = leading_axis_mesh(self._features)
        elif self._features.ell_axis == -1 and self._features.indices.ndim == 2:
            mesh = leading_axis_mesh(self._features.indices, require_divisible=True)
            if mesh is not None:
                self._use_pallas = pallas_glm.ShardedDispatch(mesh, mesh.axis_names[0])
        # What every device adds to the reduction of one evaluation, by the
        # design: the (d,) gradient and the value, float32. 0 on one device.
        self.allreduce_bytes_per_evaluation = 0
        if mesh is not None:
            self.allreduce_bytes_per_evaluation = 4 * (self._features.shape[-1] + 1)
            set_stage_note(
                "sample_sharding",
                {
                    "devices": mesh.devices.size,
                    "rows_per_device": dataset.num_samples // mesh.devices.size,
                    "pad_rows": getattr(dataset, "pad_rows", 0),
                },
            )
        self._build_jits()

    def _build_jits(self) -> None:
        cfg = self.config
        loss = self.loss
        norm = self.norm
        task = self.task
        use_sampling = cfg.down_sampling_rate < 1.0
        use_pallas = self._use_pallas
        column_major = self._column_major

        @jax.jit
        def train_fn(features, labels, offsets, weights, w0, reg_weight, key):
            if use_sampling:
                weights = down_sample_weights(
                    key,
                    labels,
                    weights,
                    cfg.down_sampling_rate,
                    negatives_only=down_sampler_for_task(task),
                )
            data = LabeledData(features, labels, offsets, weights, column_major=column_major)
            with jax.named_scope("fe_solve"):
                res = problem.solve(
                    loss,
                    data,
                    _config_with_traced_weight(cfg, reg_weight),
                    w0,
                    norm,
                    use_pallas=use_pallas,
                )
            return res

        def score_fn(features, w):
            # The transformer's jitted _fe_margins IS the scoring program:
            # CD residual scoring compiles it and evaluation of the
            # training dataset (training_prepared passes this coordinate's
            # `_features`) reuses the compiled program.
            from photon_ml_tpu.transformers.game_transformer import _fe_margins

            return _fe_margins(features, w, norm)

        @jax.jit
        def variance_fn(features, labels, offsets, weights, w, reg_weight):
            data = LabeledData(features, labels, offsets, weights, column_major=column_major)
            return problem.compute_variances(
                loss, data, _config_with_traced_weight(cfg, reg_weight), w, norm
            )

        self._train_fn = train_fn
        self._score_fn = score_fn
        self._variance_fn = variance_fn

    def train(
        self,
        offsets: Array,
        initial_model: Optional[FixedEffectModel] = None,
        *,
        reg_weight: Optional[float] = None,
        key: Optional[jax.Array] = None,
    ) -> Tuple[FixedEffectModel, OptResult]:
        ds = self.dataset
        feats = self._features
        dim = feats.dim if hasattr(feats, "dim") else feats.shape[-1]
        w0 = (
            initial_model.coefficients.means
            if initial_model is not None
            else jnp.zeros((dim,), ds.labels.dtype)
        )
        rw = jnp.asarray(
            self.config.reg_weight if reg_weight is None else reg_weight,
            ds.labels.dtype,
        )
        if key is None:
            key = jax.random.PRNGKey(0)
        res = self._train_fn(feats, ds.labels, offsets, ds.weights, w0, rw, key)
        variances = None
        if self.config.variance_computation != VarianceComputationType.NONE:
            variances = self._variance_fn(
                feats, ds.labels, offsets, ds.weights, res.coefficients, rw
            )
        model = FixedEffectModel(Coefficients(res.coefficients, variances), self.task)
        return model, res

    @property
    def training_features(self):
        """The representation training actually ran on (bucketed layout,
        bf16-stored matrix, or the ELL) — scoring the training dataset
        through it reuses compiled programs and device residency."""
        return self._features

    # -- stacked-trial hooks (hyperparameter/sweep.py) ----------------------
    # Traceable single-trial train/score: the SAME jitted recipes train()
    # and score() dispatch, taken with traced (offsets, w0, reg_weight)
    # so the sweep executor can lax.scan k reg-weight trials inside ONE
    # XLA program. A jitted callable invoked under tracing inlines, and
    # scan sequences the trial axis (it does NOT vmap it — batched matmul
    # lowering changes reduction order), so each trial's ops — and bits —
    # are identical to a standalone train()/score() call.

    def trial_train(self, offsets, w0, reg_weight, key):
        """One trial's solve as traced values; returns the (coefficients,
        variances) arrays (variances None unless configured)."""
        ds = self.dataset
        res = self._train_fn(
            self._features, ds.labels, offsets, ds.weights, w0, reg_weight, key
        )
        variances = None
        if self.config.variance_computation != VarianceComputationType.NONE:
            variances = self._variance_fn(
                self._features, ds.labels, offsets, ds.weights,
                res.coefficients, reg_weight,
            )
        return res.coefficients, variances

    def trial_score(self, coefficients):
        return self._score_fn(self._features, coefficients)

    def prefetch(self) -> None:
        """Start any pending device upload this coordinate's train/score
        will fault on (coordinate-descent calls this on coordinate k+1
        while coordinate k solves). Fixed effects train and score through
        `self._features`, which construction already materialized — and
        deliberately NOT through the raw ELL shard when the bucketed pack
        engaged — so there is nothing to ship: prefetching the shard here
        would force the very upload the lazy ShardDict avoids."""

    def score(self, model: FixedEffectModel) -> Array:
        """Raw per-sample margins x.w — residual bookkeeping happens in the
        coordinate-descent loop, so no offsets here."""
        return self._score_fn(self._features, model.coefficients.means)


def _infer_entity_mesh(re_dataset):
    """The 1-D mesh the RE dataset's entity blocks are sharded over, if any."""
    from photon_ml_tpu.parallel.mesh import leading_axis_mesh

    if not re_dataset.buckets:
        return None
    return leading_axis_mesh(re_dataset.buckets[0].entity_rows)


class RandomEffectCoordinate:
    """One random-effect coordinate (RandomEffectCoordinate.scala:37-221)."""

    def __init__(
        self,
        dataset: GameDataset,
        re_dataset: RandomEffectDataset,
        opt_config: CoordinateOptimizationConfig,
        task: TaskType,
        norm: Optional[NormalizationContext] = None,
    ):
        self.dataset = dataset
        self.re_dataset = re_dataset
        self.config = opt_config
        self.task = task
        self.loss = loss_for_task(task)
        self.norm = norm
        # Peek: construction needs only the dim — the shard's device upload
        # is deferred to the first gather (prefetch-overlapped with the
        # previous coordinate's solve by the coordinate-descent loop).
        feats = (
            dataset.peek_shard(re_dataset.feature_shard)
            if hasattr(dataset, "peek_shard")
            else dataset.shards[re_dataset.feature_shard]
        )
        self.dim = feats.dim if isinstance(feats, SparseFeatures) else feats.shape[-1]
        # Entity-sharded coefficient store: when the RE dataset's entity
        # blocks are sharded over a mesh, the (E+1, D) matrix is row-sharded
        # over the same axis and accessed through ring collectives
        # (parallel/mesh.py) — per-device coefficient state is total/n_devices
        # instead of a full replica, which is what lets the framework chase
        # the reference's RDD-partitioned coefficient scale
        # (RandomEffectModel.scala:36-239). PerEntityNormalization keeps the
        # replicated path: its per-entity factor/shift arrays would need the
        # same sharding treatment to be meaningful at that scale.
        self._entity_mesh = None
        from photon_ml_tpu.ops.normalization import PerEntityNormalization as _PEN

        if not isinstance(norm, _PEN):
            self._entity_mesh = _infer_entity_mesh(re_dataset)
        self._build_jits()

    def _build_jits(self) -> None:
        cfg = self.config
        loss = self.loss
        norm = self.norm
        from photon_ml_tpu.ops.normalization import PerEntityNormalization

        per_entity_norm = isinstance(norm, PerEntityNormalization)

        if per_entity_norm:
            # Projected-space normalization: each entity's solve gets its own
            # (factors, shifts) row, vmapped alongside its data block
            # (IndexMapProjectorRDD.scala:133).
            @jax.jit
            def train_bucket(block_data, w0_block, f_block, s_block, reg_weight):
                def one(data_e, w0_e, f_e, s_e):
                    return problem.solve(
                        loss,
                        data_e,
                        _config_with_traced_weight(cfg, reg_weight),
                        w0_e,
                        norm.row_context(f_e, s_e),
                        use_pallas=False,
                    )

                return jax.vmap(one)(block_data, w0_block, f_block, s_block)

            @jax.jit
            def variance_bucket(block_data, w_block, f_block, s_block, reg_weight):
                def one(data_e, w_e, f_e, s_e):
                    return problem.compute_variances(
                        loss,
                        data_e,
                        _config_with_traced_weight(cfg, reg_weight),
                        w_e,
                        norm.row_context(f_e, s_e),
                    )

                return jax.vmap(one)(block_data, w_block, f_block, s_block)

            def norm_blocks(entity_rows):
                f = None if norm.factors is None else norm.factors[entity_rows]
                s = None if norm.shifts is None else norm.shifts[entity_rows]
                return f, s

            self._norm_blocks = norm_blocks
        else:
            cache_key = None
            if norm is None:
                from photon_ml_tpu.optimize.config import static_config_key

                cache_key = ("re", static_config_key(cfg), self.task)
            cached = _RE_JIT_CACHE.get(cache_key) if cache_key else None
            if cached is not None:
                train_bucket, variance_bucket = cached
            else:

                @jax.jit
                def train_bucket(block_data: LabeledData, w0_block, reg_weight):
                    # use_pallas=False: the per-entity solves are vmapped;
                    # the fused kernels are single-problem programs and the
                    # vmapped XLA path is the one that batches these small
                    # solves efficiently.
                    def one(data_e, w0_e):
                        return problem.solve(
                            loss,
                            data_e,
                            _config_with_traced_weight(cfg, reg_weight),
                            w0_e,
                            norm,
                            use_pallas=False,
                        )

                    return jax.vmap(one)(block_data, w0_block)

                @jax.jit
                def variance_bucket(block_data: LabeledData, w_block, reg_weight):
                    def one(data_e, w_e):
                        return problem.compute_variances(
                            loss, data_e, _config_with_traced_weight(cfg, reg_weight), w_e, norm
                        )

                    return jax.vmap(one)(block_data, w_block)

                if cache_key:
                    _RE_JIT_CACHE[cache_key] = (train_bucket, variance_bucket)
            self._norm_blocks = None
        self._per_entity_norm = per_entity_norm

        def score_fn(features, entity_rows, matrix):
            # THE shared scoring program: the transformer's jitted
            # _re_margins, with norm passed as a pytree argument. The
            # coordinate-descent residual scoring compiles it, and
            # GameTransformer evaluation of the training dataset
            # (training_prepared: same feature arrays, same shapes) then
            # reuses the compiled program instead of paying a fresh
            # multi-second remote compile per coordinate.
            from photon_ml_tpu.transformers.game_transformer import _re_margins

            return _re_margins(features, entity_rows, matrix, norm)

        self._train_bucket = train_bucket
        self._variance_bucket = variance_bucket
        self._score_fn = score_fn

        # Scan-dispatched sweep (sweep_scan_enabled): all same-shape entity
        # buckets run as ONE XLA program — block gather, vmapped solve,
        # coefficient scatter, optional variance — with (matrix, variances)
        # as the scan carry. Same update order and the same ops as the
        # per-bucket loop, so results are bitwise identical
        # (tests/test_game.py::test_sweep_scan_matches_bucket_loop); only
        # the dispatch count changes: O(distinct shapes) programs per sweep
        # instead of 3-4 dispatches per bucket.
        scan_cache_key = None
        if norm is None:
            from photon_ml_tpu.optimize.config import static_config_key

            scan_cache_key = ("re_scan", static_config_key(cfg), self.task)
        cached_scan = (
            _RE_JIT_CACHE.get(scan_cache_key) if scan_cache_key else None
        )
        if cached_scan is not None:
            self._train_scan = cached_scan
            self._build_sharded_scan()
            return

        @jax.jit
        def train_scan(
            features,
            labels,
            weights,
            offsets,
            matrix,
            var_matrix,
            gathers,
            masks,
            ents,
            feature_mask,
            norm_factors,
            norm_shifts,
            reg_weight,
        ):
            from photon_ml_tpu.data.game_dataset import gather_block_arrays

            traced_cfg = _config_with_traced_weight(cfg, reg_weight)

            def step(carry, xs):
                m, v = carry
                gather, mask, ent = xs
                block = gather_block_arrays(
                    features, labels, weights, offsets, gather, mask, ent,
                    feature_mask,
                )
                w0 = m[ent]
                if per_entity_norm:
                    # Per-entity norm rows arrive as ARGUMENTS (closing
                    # over norm.factors would bake the whole (E+1, D)
                    # matrix into the program as a constant).
                    f_blk = (
                        None if norm_factors is None else norm_factors[ent]
                    )
                    s_blk = (
                        None if norm_shifts is None else norm_shifts[ent]
                    )

                    def one(data_e, w0_e, f_e, s_e):
                        return problem.solve(
                            loss, data_e, traced_cfg, w0_e,
                            norm.row_context(f_e, s_e), use_pallas=False,
                        )

                    res = jax.vmap(one)(block, w0, f_blk, s_blk)
                else:

                    def one(data_e, w0_e):
                        return problem.solve(
                            loss, data_e, traced_cfg, w0_e, norm,
                            use_pallas=False,
                        )

                    res = jax.vmap(one)(block, w0)
                m = m.at[ent].set(res.coefficients)
                if v is not None:
                    if per_entity_norm:

                        def onev(data_e, w_e, f_e, s_e):
                            return problem.compute_variances(
                                loss, data_e, traced_cfg, w_e,
                                norm.row_context(f_e, s_e),
                            )

                        vv = jax.vmap(onev)(
                            block, res.coefficients, f_blk, s_blk
                        )
                    else:

                        def onev(data_e, w_e):
                            return problem.compute_variances(
                                loss, data_e, traced_cfg, w_e, norm
                            )

                        vv = jax.vmap(onev)(block, res.coefficients)
                    v = v.at[ent].set(vv)
                return (m, v), (res.iterations, res.fn_evals)

            # One scope per bucket shape: (K, E, S) operands, capacity S.
            with jax.named_scope(f"re_scan/{gathers.shape[2]}"):
                (matrix, var_matrix), solved = jax.lax.scan(
                    step, (matrix, var_matrix), (gathers, masks, ents)
                )
            return matrix, var_matrix, solved

        if scan_cache_key:
            _RE_JIT_CACHE[scan_cache_key] = train_scan
        self._train_scan = train_scan
        self._build_sharded_scan()

    def _build_sharded_scan(self) -> None:
        """Scan-dispatched sweep for the ENTITY-SHARDED store: same shape
        grouping as the replicated scan, but the coefficient matrix carry
        stays row-sharded over the mesh and every bucket step moves rows
        through the ring collectives (parallel/mesh.py) INSIDE the program —
        gather w0, vmapped shard-local solves, scatter coefficients (and
        variances) — so a sweep is O(distinct block shapes) XLA programs
        with per-device coefficient state of total/n_devices, never a full
        replica. Ops per entity are identical to the sharded per-bucket
        loop, so the two are bitwise equal
        (tests/test_parallel.py::test_sharded_scan_sweep_matches_bucket_loop).
        """
        self._train_scan_sharded = None
        mesh = self._entity_mesh
        if mesh is None or self._per_entity_norm:
            return
        cfg = self.config
        loss = self.loss
        norm = self.norm
        sh_cache_key = None
        if norm is None:
            from photon_ml_tpu.optimize.config import static_config_key

            sh_cache_key = ("re_scan_sh", static_config_key(cfg), self.task, mesh)
        cached = _RE_JIT_CACHE.get(sh_cache_key) if sh_cache_key else None
        if cached is not None:
            self._train_scan_sharded = cached
            return

        from photon_ml_tpu.parallel.mesh import ring_gather_rows, ring_scatter_rows

        @jax.jit
        def train_scan_sharded(
            features,
            labels,
            weights,
            offsets,
            matrix,
            var_matrix,
            gathers,
            masks,
            ents,
            feature_mask,
            reg_weight,
        ):
            from photon_ml_tpu.data.game_dataset import gather_block_arrays

            traced_cfg = _config_with_traced_weight(cfg, reg_weight)

            def step(carry, xs):
                m, v = carry
                gather, mask, ent = xs
                block = gather_block_arrays(
                    features, labels, weights, offsets, gather, mask, ent,
                    feature_mask,
                )
                w0 = ring_gather_rows(m, ent, mesh)

                def one(data_e, w0_e):
                    return problem.solve(
                        loss, data_e, traced_cfg, w0_e, norm, use_pallas=False
                    )

                res = jax.vmap(one)(block, w0)
                m = ring_scatter_rows(m, ent, res.coefficients, mesh)
                if v is not None:

                    def onev(data_e, w_e):
                        return problem.compute_variances(
                            loss, data_e, traced_cfg, w_e, norm
                        )

                    vv = jax.vmap(onev)(block, res.coefficients)
                    v = ring_scatter_rows(v, ent, vv, mesh)
                return (m, v), (res.iterations, res.fn_evals)

            with jax.named_scope(f"re_scan/{gathers.shape[2]}"):
                (matrix, var_matrix), solved = jax.lax.scan(
                    step, (matrix, var_matrix), (gathers, masks, ents)
                )
            return matrix, var_matrix, solved

        if sh_cache_key:
            _RE_JIT_CACHE[sh_cache_key] = train_scan_sharded
        self._train_scan_sharded = train_scan_sharded

    def train(
        self,
        offsets: Array,
        initial_model: Optional[RandomEffectModel] = None,
        *,
        reg_weight: Optional[float] = None,
    ) -> Tuple[RandomEffectModel, RandomEffectSolveStats]:
        """Train every entity bucket; returns the new coefficient matrix model.

        Per-entity warm start: gather previous rows (the reference's
        leftOuterJoin of prior models, RandomEffectCoordinate.scala:110-121).
        """
        ds = self.dataset
        red = self.re_dataset
        dtype = ds.labels.dtype
        e_total = red.num_entities
        mesh = self._entity_mesh
        n_rows = e_total + 1
        if mesh is not None:
            from photon_ml_tpu.parallel.mesh import (
                matrix_row_sharding,
                pad_rows_for_mesh,
                put_row_sharded,
                ring_gather_rows,
                ring_scatter_rows,
                sharded_zeros,
            )

            n_rows = pad_rows_for_mesh(n_rows, mesh)
            row_sh = matrix_row_sharding(mesh)
        if initial_model is not None:
            matrix = initial_model.coefficients_matrix
            if matrix.shape[0] < n_rows:
                matrix = np.pad(
                    np.asarray(matrix), ((0, n_rows - matrix.shape[0]), (0, 0))
                )
            if mesh is not None:
                matrix = put_row_sharded(matrix, row_sh)
        elif mesh is not None:
            matrix = sharded_zeros((n_rows, self.dim), dtype, row_sh)
        else:
            matrix = jnp.zeros((n_rows, self.dim), dtype)
        want_var = self.config.variance_computation != VarianceComputationType.NONE
        if not want_var:
            var_matrix = None
        elif mesh is not None:
            var_matrix = sharded_zeros((n_rows, self.dim), dtype, row_sh)
        else:
            var_matrix = jnp.zeros((n_rows, self.dim), dtype)
        rw = jnp.asarray(
            self.config.reg_weight if reg_weight is None else reg_weight, dtype
        )

        # Analytic wire bytes this sweep will move through the entity-shard
        # collectives (0 on the replicated path) — read by the
        # coordinate-descent loop / estimator for the sharding artifact keys.
        self.last_train_collective_bytes = self.sweep_collective_bytes()
        # No host syncs inside the loop: bucket programs dispatch back-to-back
        # and stats materialize once at the end. One entry per dispatch:
        # (bucket indices, per-entity iterations, per-entity fn_evals), the
        # arrays (K, E) from a scan group and (E,) from a single bucket.
        solved: List[tuple] = []
        if (
            red.buckets
            and sweep_scan_enabled()
            and (mesh is None or self._train_scan_sharded is not None)
        ):
            # Scan-dispatched sweep: one program per distinct block shape
            # (on the entity-sharded path with ring gather/scatter on
            # shard-local rows INSIDE it). Each group dispatch runs under
            # the mesh failure domain: the `collective` fault site +
            # bounded re-dispatch (entity-sharded groups), the optional
            # hang watchdog, and — when retries exhaust — a degraded
            # fallback to the bitwise-equal per-bucket loop for exactly
            # that group's buckets (entity buckets are disjoint, so the
            # carry update order across groups cannot change any row).
            from photon_ml_tpu.parallel.mesh import (
                collective_faults_suppressed,
            )
            from photon_ml_tpu.utils.watchdog import Watchdog, watchdog_ms

            wd_ms = watchdog_ms()
            wd = Watchdog() if wd_ms > 0 else None
            try:
                for group in self._scan_group_list():
                    idxs = group[0]
                    try:
                        matrix, var_matrix, counts = self._dispatch_scan_group(
                            group, matrix, var_matrix, offsets, rw, wd, wd_ms
                        )
                    except BaseException as exc:  # noqa: BLE001 - gated below
                        if not faults.is_device_error(exc):
                            raise
                        # Bounded re-dispatches exhausted on a device-shaped
                        # failure: degrade THIS group to the per-bucket
                        # loop, with the armed `collective` site suppressed
                        # (a degradation tier must keep working precisely
                        # while the primary path is broken).
                        faults.COUNTERS.increment("collective_fallbacks")
                        logger.warning(
                            "scan sweep group of %d bucket(s) failed (%s); "
                            "degrading to the per-bucket loop",
                            len(idxs),
                            exc,
                        )
                        with collective_faults_suppressed():
                            matrix, var_matrix = self._train_buckets(
                                idxs, matrix, var_matrix, solved, offsets, rw
                            )
                        continue
                    solved.append((list(idxs), *counts))
            finally:
                if wd is not None:
                    wd.close()
            return self._finish_train(matrix, var_matrix, solved)
        matrix, var_matrix = self._train_buckets(
            range(len(red.buckets)), matrix, var_matrix, solved, offsets, rw
        )
        return self._finish_train(matrix, var_matrix, solved)

    def _dispatch_scan_group(
        self, group, matrix, var_matrix, offsets, rw, wd, wd_ms
    ):
        """One scan-group device dispatch under the mesh failure domain:
        `collective` fault site (entity-sharded groups — the program's ring
        gather/scatters are inside the trace, so the host dispatch carries
        the site), bounded re-dispatch (PHOTON_COLLECTIVE_RETRIES), and
        the hang watchdog when armed. Deterministic programs make a
        re-dispatch bitwise-identical; with the watchdog armed the carry
        is blocked on INSIDE the guard so a wedged dispatch is observable
        (trading the back-to-back pipelining for hang detection)."""
        from photon_ml_tpu.parallel.mesh import collective_retry_policy

        idxs, gathers, masks, ents = group
        ds, red = self.dataset, self.re_dataset
        mesh = self._entity_mesh

        def run():
            if mesh is not None:
                m, v, counts = self._train_scan_sharded(
                    ds.shards[red.feature_shard], ds.labels, ds.weights,
                    offsets, matrix, var_matrix, gathers, masks, ents,
                    red.feature_mask, rw,
                )
            else:
                norm_f = norm_s = None
                if self._per_entity_norm:
                    norm_f, norm_s = self.norm.factors, self.norm.shifts
                m, v, counts = self._train_scan(
                    ds.shards[red.feature_shard], ds.labels, ds.weights,
                    offsets, matrix, var_matrix, gathers, masks, ents,
                    red.feature_mask, norm_f, norm_s, rw,
                )
            if wd is not None:
                jax.block_until_ready(m)
            return m, v, counts

        def attempt():
            if mesh is not None:
                faults.fault_point("collective")
            if wd is None:
                return run()
            with wd.guard(wd_ms, f"scan sweep group ({len(idxs)} buckets)"):
                return run()

        return faults.retry(
            attempt,
            collective_retry_policy(),
            label=f"scan sweep group of {len(idxs)} bucket(s)",
            counter="collective_retries" if mesh is not None else "retries",
        )

    def _train_buckets(
        self, bucket_indices, matrix, var_matrix, solved, offsets, rw
    ):
        """The per-bucket dispatch loop over `bucket_indices` — the default
        path with the scan sweep off, and the degraded fallback tier for a
        scan group whose collective dispatch exhausted its retries (bitwise
        equal to the scan by construction — same ops per entity)."""
        ds, red = self.dataset, self.re_dataset
        mesh = self._entity_mesh
        if mesh is not None:
            from photon_ml_tpu.parallel.mesh import (
                ring_gather_rows,
                ring_scatter_rows,
            )
        for bi in bucket_indices:
            blocks = red.buckets[bi]
            block_data = gather_block_data(
                ds, red.feature_shard, blocks, offsets, feature_mask=red.feature_mask
            )
            if mesh is not None:
                w0 = ring_gather_rows(matrix, blocks.entity_rows, mesh)
            else:
                w0 = matrix[blocks.entity_rows]
            if self._per_entity_norm:
                f_blk, s_blk = self._norm_blocks(blocks.entity_rows)
                res: OptResult = self._train_bucket(block_data, w0, f_blk, s_blk, rw)
            else:
                res = self._train_bucket(block_data, w0, rw)
            if mesh is not None:
                matrix = ring_scatter_rows(
                    matrix, blocks.entity_rows, res.coefficients, mesh
                )
            else:
                matrix = matrix.at[blocks.entity_rows].set(res.coefficients)
            if var_matrix is not None:
                if self._per_entity_norm:
                    v = self._variance_bucket(
                        block_data, res.coefficients, f_blk, s_blk, rw
                    )
                else:
                    v = self._variance_bucket(block_data, res.coefficients, rw)
                if mesh is not None:
                    var_matrix = ring_scatter_rows(
                        var_matrix, blocks.entity_rows, v, mesh
                    )
                else:
                    var_matrix = var_matrix.at[blocks.entity_rows].set(v)
            solved.append(([bi], res.iterations, res.fn_evals))
        return matrix, var_matrix

    def _scan_group_list(self):
        """Buckets grouped by block shape, each stacked into (K, E, S)
        scan operands. Built once per coordinate; every (capacity, E)
        shape comes from the canonical discrete set, so the group count —
        and hence the per-sweep program count — is small by construction.
        On the entity-sharded path the stacked operands are re-laid-out
        with the ENTITY axis (axis 1) sharded over the mesh, so the scan's
        per-step slices arrive already shard-local."""
        groups = getattr(self, "_scan_groups_cache", None)
        if groups is None:
            by_shape: dict = {}
            bl = self.re_dataset.buckets
            for i, b in enumerate(bl):
                by_shape.setdefault((b.num_entities, b.capacity), []).append(i)
            # Scan-fusion granularity is a PLANNED quantity (ISSUE 14):
            # default 0 = unbounded (one program per shape, the pre-
            # planner behavior). A plan caps how many same-shape buckets
            # fuse into one scan dispatch — and shapes the plan's profile
            # never proved on this hardware (re_bucket_shapes) chunk at
            # the cap even when proven shapes fuse unboundedly, so a
            # first-dispatch failure or hang costs one small group.
            # Chunking preserves per-bucket op order (the scan body runs
            # buckets sequentially either way), so ANY cap is bitwise-
            # identical to unbounded fusion.
            shape_chunks = []
            for shape, idxs in by_shape.items():
                for chunk in _fusion_chunks(
                    idxs, shape, self._planned_shape_set()
                ):
                    shape_chunks.append(chunk)
            groups = [
                (
                    idxs,
                    jnp.stack([bl[i].gather for i in idxs]),
                    jnp.stack([bl[i].mask for i in idxs]),
                    jnp.stack([bl[i].entity_rows for i in idxs]),
                )
                for idxs in shape_chunks
            ]
            if self._entity_mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                mesh = self._entity_mesh
                ax = mesh.axis_names[0]
                s3 = NamedSharding(mesh, P(None, ax, None))
                s2 = NamedSharding(mesh, P(None, ax))
                groups = [
                    (
                        idxs,
                        jax.device_put(g, s3),
                        jax.device_put(mk, s3),
                        jax.device_put(e, s2),
                    )
                    for idxs, g, mk, e in groups
                ]
            self._scan_groups_cache = groups
        return groups

    def _planned_shape_set(self):
        """The (entities, capacity) shapes the installed plan's profile
        proved on this hardware, or None when no plan carries shape
        evidence (then every shape fuses unboundedly, the default)."""
        from photon_ml_tpu import planner

        plan = planner.current_plan()
        if plan is None or "re_bucket_shapes" not in plan.decisions:
            return None
        planned = plan.decisions["re_bucket_shapes"].value or {}
        shapes = {
            (int(pair[0]), int(pair[1]))
            for shape_list in planned.values()
            for pair in shape_list
        }
        return shapes or None

    @property
    def entity_mesh(self):
        """The mesh this coordinate's entity store is sharded over (None =
        replicated). Public because the elastic-resume layer keys on it:
        a device-shaped failure that beats this coordinate's own failure
        domain is a MESH loss only when there IS a mesh
        (game/coordinate_descent.py's sweep-boundary handler)."""
        return self._entity_mesh

    def sweep_collective_bytes(self) -> int:
        """Analytic wire bytes one full sweep moves through the ring
        collectives (gather of warm starts + scatter of coefficients and,
        when enabled, variances) — 0 on the replicated path. Purely a
        function of the bucket layout and mesh, so it is exact for both
        the per-bucket loop and the scan sweep (same calls, same shapes)."""
        mesh = self._entity_mesh
        if mesh is None:
            return 0
        from photon_ml_tpu.parallel.mesh import (
            pad_rows_for_mesh,
            ring_gather_wire_bytes,
            ring_scatter_wire_bytes,
        )

        n_rows = pad_rows_for_mesh(self.re_dataset.num_entities + 1, mesh)
        want_var = self.config.variance_computation != VarianceComputationType.NONE
        scatters = 2 if want_var else 1
        total = 0
        for b in self.re_dataset.buckets:
            total += ring_gather_wire_bytes(mesh, n_rows, self.dim)
            total += scatters * ring_scatter_wire_bytes(
                mesh, b.num_entities, self.dim
            )
        return total

    def sharding_info(self) -> dict:
        """The sharding decision this coordinate trains under, as the
        proper-JSON keys `fit_timing`/bench artifacts record."""
        mesh = self._entity_mesh
        n_rows = self.re_dataset.num_entities + 1
        if mesh is None:
            return {
                "entity_sharded": False,
                "axis_size": 1,
                "rows_per_shard": int(n_rows),
                "collective_bytes_per_sweep": 0,
            }
        from photon_ml_tpu.parallel.mesh import pad_rows_for_mesh

        padded = pad_rows_for_mesh(n_rows, mesh)
        return {
            "entity_sharded": True,
            "axis_size": int(mesh.devices.size),
            "rows_per_shard": int(padded // mesh.devices.size),
            "collective_bytes_per_sweep": self.sweep_collective_bytes(),
        }

    def _finish_train(self, matrix, var_matrix, solved):
        """The trained model and the sweep's solve record. ONE fetch,
        whatever the bucket count: every dispatch's totals are reduced on
        the device in one program and read back together."""
        red = self.re_dataset
        e_total = red.num_entities
        totals = (
            jax.device_get(_solve_totals([(its, ev) for _, its, ev in solved]))
            if solved
            else np.zeros((0, 2), np.int32)
        )
        by_shape: dict = {}
        for (idxs, _, _), (iterations, fn_evals) in zip(solved, totals):
            b = red.buckets[idxs[0]]
            rec = by_shape.setdefault(
                (b.capacity, b.num_entities),
                dict(
                    capacity=b.capacity,
                    entities=b.num_entities,
                    buckets=0,
                    iterations=0,
                    fn_evals=0,
                ),
            )
            rec["buckets"] += len(idxs)
            rec["iterations"] += int(iterations)
            rec["fn_evals"] += int(fn_evals)
        stats = RandomEffectSolveStats(
            buckets=list(by_shape.values()),
            iterations=int(totals[:, 0].sum()),
            fn_evals=int(totals[:, 1].sum()),
            solves=sum(int(its.size) for _, its, _ in solved),
            per_entity=solved,
        )
        # Keep the unseen-entity row pinned to zero — in BOTH matrices:
        # dummy-padded chunk entities (build_random_effect_dataset block
        # splitting) scatter their inert solves into this row.
        matrix = matrix.at[e_total].set(0.0)
        if var_matrix is not None:
            var_matrix = var_matrix.at[e_total].set(0.0)
        model = RandomEffectModel(
            matrix,
            var_matrix,
            self.task,
            n_entities=e_total if matrix.shape[0] != e_total + 1 else None,
        )
        return model, stats

    def entity_counts(
        self, stats: RandomEffectSolveStats
    ) -> Tuple[np.ndarray, np.ndarray]:
        """(iterations, fn_evals) of one `train` call by coefficient-matrix
        row, from its stats' `per_entity` arrays (one fetch). Row
        `num_entities` sums the inert solves of dummy-padded block slots."""
        red = self.re_dataset
        out = np.zeros((2, red.num_entities + 1), np.int64)
        for idxs, its, evals in jax.device_get(stats.per_entity):
            rows = np.concatenate(
                [np.asarray(red.buckets[bi].entity_rows) for bi in idxs]
            )
            np.add.at(out[0], rows, np.reshape(its, -1))
            np.add.at(out[1], rows, np.reshape(evals, -1))
        return out[0], out[1]

    # -- stacked-trial hooks (hyperparameter/sweep.py) ----------------------

    def trial_train(self, offsets, matrix, var_matrix, reg_weight):
        """One trial's full bucket sweep as traced values (replicated store
        only): every scan group's `_train_scan` program runs in bucket
        order with the trial's (offsets, matrix, reg_weight), then the
        unseen-entity row pins to zero — the exact op sequence train()
        dispatches, so a lax.scan of this body over a trial axis is
        bitwise-equal per trial to the serial per-trial loop
        (tests/test_sweep.py). Entity-sharded coordinates evaluate trials
        via shard groups instead (SweepExecutor)."""
        if self._entity_mesh is not None:
            raise ValueError(
                "trial_train is the replicated stacked-trial hook; "
                "entity-sharded coordinates run one trial per shard group"
            )
        ds, red = self.dataset, self.re_dataset
        for group in self._scan_group_list():
            _idxs, gathers, masks, ents = group
            norm_f = norm_s = None
            if self._per_entity_norm:
                norm_f, norm_s = self.norm.factors, self.norm.shifts
            matrix, var_matrix, _counts = self._train_scan(
                ds.shards[red.feature_shard], ds.labels, ds.weights, offsets,
                matrix, var_matrix, gathers, masks, ents, red.feature_mask,
                norm_f, norm_s, reg_weight,
            )
        matrix = matrix.at[red.num_entities].set(0.0)
        if var_matrix is not None:
            var_matrix = var_matrix.at[red.num_entities].set(0.0)
        return matrix, var_matrix

    def trial_score(self, matrix):
        return self._score_fn(
            self.dataset.shards[self.re_dataset.feature_shard],
            self.re_dataset.sample_entity_rows,
            matrix,
        )

    def prefetch(self) -> None:
        """Start the background device upload of the feature shard the
        entity-block gathers and residual scoring read — so the transfer
        overlaps the previous coordinate's solve instead of faulting
        synchronously at this coordinate's first gather."""
        shards = self.dataset.shards
        if hasattr(shards, "prefetch"):
            shards.prefetch(self.re_dataset.feature_shard)

    def score(self, model: RandomEffectModel) -> Array:
        if self._entity_mesh is not None and model.coefficients_matrix.shape[0] % (
            self._entity_mesh.devices.size
        ) == 0:
            from photon_ml_tpu.game.model import random_effect_margins_sharded

            return random_effect_margins_sharded(
                self.dataset.shards[self.re_dataset.feature_shard],
                self.re_dataset.sample_entity_rows,
                model.coefficients_matrix,
                self.norm,
                self._entity_mesh,
            )
        return self._score_fn(
            self.dataset.shards[self.re_dataset.feature_shard],
            self.re_dataset.sample_entity_rows,
            model.coefficients_matrix,
        )
