"""GameEstimator: the spark.ml-style fit() entry of the GAME layer.

Counterpart of photon-api estimators/GameEstimator.scala:54-773:
  * validates coordinate configurations against the update sequence
    (validateInput);
  * builds per-coordinate training datasets ONCE and reuses them across every
    optimization configuration (prepareTrainingDatasets:453-557 — here:
    entity-blocked RandomEffectDatasets + projected shards + normalization
    contexts);
  * builds the validation dataset and EvaluationSuite
    (prepareValidationDatasetAndEvaluators:567, default evaluator per task
    :614-625);
  * for each GameOptimizationConfiguration runs coordinate descent via the
    Coordinate objects (train:698-753), warm-starting each configuration from
    the previous one's model (fit:214-230);
  * returns (model, config, evaluation) triples for model selection by the
    driver.

Coordinate objects are cached across the sweep keyed by their *static*
configuration (everything but the regularization weight, which is a traced
scalar) so a reg-weight sweep reuses the same compiled XLA programs — the
TPU version of the reference's single mutable opt problem reused across the
sweep (ModelTraining.scala:165-213).
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import time
from typing import Dict, List, Mapping, Optional, Sequence, Set, Tuple

import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.containers import SparseFeatures
from photon_ml_tpu.data.game_dataset import (
    FixedEffectDataConfig,
    GameDataset,
    RandomEffectDataConfig,
    RandomEffectDataset,
    build_random_effect_dataset,
)
from photon_ml_tpu.data.stats import summarize
from photon_ml_tpu.evaluation.suite import (
    EvaluationResults,
    EvaluationSuite,
    EvaluatorType,
    default_evaluator_for_task,
)
from photon_ml_tpu.game.coordinate import FixedEffectCoordinate, RandomEffectCoordinate
from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent
from photon_ml_tpu.game.model import GameModel
from photon_ml_tpu.game.projector import project_shard
from photon_ml_tpu.ops.normalization import NormalizationContext, from_feature_stats
from photon_ml_tpu.optimize.config import CoordinateOptimizationConfig
from photon_ml_tpu.transformers.game_transformer import (
    CoordinateScoringSpec,
    GameTransformer,
    PreparedCoordinateData,
    coordinate_margins,
    prefetch_fixed_effect_shards,
    prepare_coordinate_data,
)
from photon_ml_tpu.types import NormalizationType, TaskType
from photon_ml_tpu.utils import compile_cache, telemetry
from photon_ml_tpu.utils.observability import (
    CheckpointEvent,
    CoordinateUpdateEvent,
    EventEmitter,
    SweepConfigEvent,
    TimingRegistry,
    TrainingFinishEvent,
    TrainingStartEvent,
    open_stage,
    stage_scope,
    stage_timer,
)

logger = logging.getLogger(__name__)

GameOptimizationConfiguration = Mapping[str, CoordinateOptimizationConfig]

# The prepare-stage breakdown keys reported in `fit_timing` (VERDICT r05
# "Next round" #1): host RE dataset builds, projection, feature statistics,
# bucketed pack, device uploads, program construction/compile, and the
# residual host glue. In a synchronous run they tile `prepare_s`; in a
# pipelined run stages record where the work happens, so overlapped stages
# can sum past the wall they were hidden behind. The schema itself lives
# in utils/contracts.py (re-exported here for the existing importers).
from photon_ml_tpu.utils.contracts import (
    PREPARE_STAGES,
    ROBUSTNESS_CLEAN_ZERO_KEYS,
    SOLVE_STAGES,
)


from photon_ml_tpu.optimize.config import static_config_key as _static_config_key


@dataclasses.dataclass
class GameResult:
    """One (GameModel, configuration, evaluation) triple
    (GameEstimator.fit's Seq element, GameEstimator.scala:169-172)."""

    model: GameModel
    config: Dict[str, CoordinateOptimizationConfig]
    evaluation: Optional[EvaluationResults]
    best_model: GameModel
    timing: Dict[str, float]


@dataclasses.dataclass
class _PreparedCoordinate:
    """Training-time artifacts for one coordinate, reused across configs."""

    data_config: object
    original_shard: str
    shard: str  # projected shard name for REs
    norm: Optional[NormalizationContext]
    re_dataset: Optional[RandomEffectDataset] = None
    projector: Optional[object] = None


@dataclasses.dataclass
class _ValidationSlot:
    """One validation data set's scoring views (`_validation_prepared`)."""

    dataset: GameDataset
    # Per coordinate: (id, shard, projector, entity index) the view was
    # made through; the last two are compared by identity.
    views: Tuple
    prepared: Dict[str, PreparedCoordinateData]
    # The `ell_planes_scored` note the build set, set again on a reuse.
    scored_note: Optional[dict]


class GameEstimator:
    """fit(data, validation, configs) -> [GameResult] (GameEstimator.scala:54).

    `coordinate_data_configs` is an ORDERED mapping coordinate id ->
    FixedEffectDataConfig | RandomEffectDataConfig; its order is the
    coordinate update sequence unless `update_sequence` overrides it.
    """

    def __init__(
        self,
        task: TaskType,
        coordinate_data_configs: Mapping[str, object],
        *,
        update_sequence: Optional[Sequence[str]] = None,
        coordinate_descent_iterations: int = 1,
        normalization: NormalizationType = NormalizationType.NONE,
        validation_evaluators: Optional[Sequence[EvaluatorType]] = None,
        locked_coordinates: Optional[Set[str]] = None,
        intercept_indices: Optional[Mapping[str, int]] = None,
        seed: int = 0,
        checkpoint_dir: Optional[str] = None,
        pipeline: Optional[bool] = None,
        event_emitter: Optional[EventEmitter] = None,
    ):
        self.task = task
        self.data_configs = dict(coordinate_data_configs)
        self.update_sequence = list(update_sequence or self.data_configs.keys())
        unknown = [c for c in self.update_sequence if c not in self.data_configs]
        if unknown:
            raise ValueError(f"update sequence names unknown coordinates {unknown}")
        missing = [c for c in self.data_configs if c not in self.update_sequence]
        if missing:
            raise ValueError(f"coordinates missing from update sequence {missing}")
        self.cd_iterations = coordinate_descent_iterations
        self.normalization = normalization
        self.validation_evaluators = list(validation_evaluators or [])
        self.locked = set(locked_coordinates or ())
        self.intercept_indices = dict(intercept_indices or {})
        self.seed = seed
        # Outer-loop checkpoint root (SURVEY §5.3); each optimization
        # configuration in the sweep checkpoints under config-<i>/.
        self.checkpoint_dir = checkpoint_dir
        # Host data-plane pipelining: None = auto (PHOTON_PIPELINE env, else
        # effective host parallelism > 1); True/False forces. A pipelined
        # fit is bitwise-identical to a synchronous one — the pipeline only
        # moves WHEN host builds/uploads run (tests/test_pipeline.py).
        self.pipeline = pipeline
        # Lifecycle event bus (ISSUE 11 satellite): library callers get
        # the same start/coordinate/sweep/checkpoint/finish record as CLI
        # jobs — register a telemetry journal_listener (or any listener)
        # on this emitter. None keeps fit() emission-free.
        self.event_emitter = event_emitter
        # Per-stage prepare walls (PREPARE_STAGES) accumulated across
        # prepare() + coordinate construction; surfaced via `fit_timing`.
        self.timing_registry = TimingRegistry()
        self._prepared: Optional[Dict[str, _PreparedCoordinate]] = None
        self._prepared_dataset: Optional[GameDataset] = None
        self._validation_slot: Optional[_ValidationSlot] = None
        self._coordinate_cache: Dict[Tuple, object] = {}

    # ------------------------------------------------------------------ prep

    @contextlib.contextmanager
    def _exclusive_stage(self, name: str):
        """Like stage_timer, but attributes only the block's wall NOT
        already recorded to the nested `pack`/`upload` stages (a projector
        block that faults a synchronous ShardDict upload must not count
        the same seconds twice — the sync-run breakdown tiles prepare_s).
        Must run inside an open stage_scope on this registry. The programs
        made ready inside are filed under `name` (`compile` holds a
        coordinate's construction: `compile_cache.programs()` shows what
        that makes ready, and it compiles none of the solve)."""
        reg = self.timing_registry
        t0 = time.perf_counter()
        nested0 = reg.get("pack") + reg.get("upload")
        try:
            with open_stage(name):
                yield
        finally:
            elapsed = time.perf_counter() - t0
            nested = reg.get("pack") + reg.get("upload") - nested0
            reg.record(name, max(0.0, elapsed - nested))

    def _norm_for_shard(
        self,
        dataset: GameDataset,
        shard: str,
        *,
        intercept_shard: Optional[str] = None,
        projected: bool = False,
    ) -> Optional[NormalizationContext]:
        """`intercept_shard` is the ORIGINAL shard name users configure
        intercepts under; `shard` may be a projected view (the RANDOM
        projector's dense space, where the global intercept column is mixed
        away, so shift-based normalization is not expressible — factor-only
        types are safe: a constant column gets factor 1 via the zero-variance
        guard)."""
        if self.normalization == NormalizationType.NONE:
            return None
        intercept = self.intercept_indices.get(intercept_shard or shard)
        if projected:
            if self.normalization == NormalizationType.STANDARDIZATION:
                raise ValueError(
                    "STANDARDIZATION is not supported on randomly-projected "
                    "shards (the intercept column is mixed into every "
                    "projected dimension); use a factor-only normalization "
                    "type, INDEX_MAP or IDENTITY projection"
                )
            intercept = None
        # Stats need a full pass over the entries, so a device transfer is
        # unavoidable — but make it a TRANSIENT copy (freed after the
        # summary) rather than ShardDict's cached materialization, which
        # would pin the raw ELL in HBM for a training run that then uses
        # only the bucketed/projected layouts.
        feats = dataset.peek_shard(shard) if hasattr(dataset, "peek_shard") else dataset.shards[shard]
        if isinstance(feats, SparseFeatures) and not isinstance(
            feats.indices, jnp.ndarray
        ):
            feats = dataclasses.replace(
                feats,
                indices=jnp.asarray(feats.indices),
                values=jnp.asarray(feats.values),
            )
        stats = summarize(feats, intercept_index=intercept)
        return from_feature_stats(
            self.normalization,
            mean=stats.mean,
            variance=stats.variance,
            max_abs=stats.max_abs,
            intercept_index=intercept,
        )

    def _norm_for_projected_re(self, dataset: GameDataset, original_shard: str, ps):
        """Normalization for a projected random-effect coordinate.

        INDEX_MAP compaction projects the GLOBAL context (computed on the
        original shard) into every entity's local slots — the reference's
        per-entity projected NormalizationContexts
        (IndexMapProjectorRDD.scala:133), so STANDARDIZATION works on
        projected shards. RANDOM projection cannot carry an affine
        per-feature transform through the Gaussian mix; factor-only types
        fall back to statistics of the projected (dense) space.
        """
        from photon_ml_tpu.game.projector import IndexMapProjector
        from photon_ml_tpu.ops.normalization import project_normalization

        if self.normalization == NormalizationType.NONE:
            return None
        if isinstance(ps.projector, IndexMapProjector):
            stats = getattr(ps.projector, "original_stats", None)
            if stats is not None:
                # Fused auxiliary pass (device assembly): the summary was
                # computed in the SAME device program as the projector key
                # sort — identical ops to summarize(), no second sweep.
                intercept = self.intercept_indices.get(original_shard)
                global_norm = from_feature_stats(
                    self.normalization,
                    mean=stats.mean,
                    variance=stats.variance,
                    max_abs=stats.max_abs,
                    intercept_index=intercept,
                )
            else:
                global_norm = self._norm_for_shard(dataset, original_shard)
            return project_normalization(global_norm, ps.projector.slot_tables)
        return self._norm_for_shard(
            dataset, ps.shard_name, intercept_shard=original_shard, projected=True
        )

    def prepare(self, dataset: GameDataset) -> Dict[str, _PreparedCoordinate]:
        """Build per-coordinate datasets/projections/normalizations once
        (prepareTrainingDatasets + prepareNormalizationContextWrappers).
        Bound to the first dataset seen — an estimator instance trains one
        dataset (as in the reference, where datasets are fit() arguments but
        coordinates cache RDD views).

        When the host data-plane pipeline is enabled (see `pipeline` in
        __init__), the entity-grouping builds of later random-effect
        coordinates run on a small worker pool, overlapping the current
        coordinate's projector/statistics work — the single-host stand-in
        for the reference's executor-parallel RandomEffectDataset
        construction (RandomEffectDataset.scala:229-438). Build ORDER of
        consumption is unchanged, so results are bitwise-identical to the
        synchronous path.
        """
        if self._prepared is not None:
            if dataset is not self._prepared_dataset:
                raise ValueError(
                    "This GameEstimator already prepared a different training "
                    "dataset; create a new estimator per training dataset"
                )
            return self._prepared
        self._prepared_dataset = dataset

        from photon_ml_tpu.data.pipeline import (
            effective_host_parallelism,
            pipeline_enabled,
        )

        re_futures: Dict[str, object] = {}
        pending_re: List[str] = []
        pool = None
        prepared: Dict[str, _PreparedCoordinate] = {}
        try:
            with stage_scope(self.timing_registry):
                if pipeline_enabled(self.pipeline):
                    from concurrent.futures import ThreadPoolExecutor

                    re_cids = [
                        cid
                        for cid in self.update_sequence
                        if isinstance(
                            self.data_configs[cid], RandomEffectDataConfig
                        )
                    ]
                    if len(re_cids) > 1:
                        workers = max(
                            1, min(4, effective_host_parallelism() - 1)
                        )
                        pool = ThreadPoolExecutor(
                            max_workers=workers,
                            thread_name_prefix="photon-prepare",
                        )
                        # Rolling submission, not queue-everything: at most
                        # `workers + 1` block layouts exist at once (the one
                        # being consumed plus the in-flight builds) — a
                        # completed layout is GB-scale at MovieLens-20M, so
                        # finished-but-unconsumed results must not pile up.
                        pending_re = list(re_cids)
                        reg = self.timing_registry
                        span_h = telemetry.span_handoff()

                        def _build_in_scope(cfg_re):
                            # Stage scopes are thread-local: hand the
                            # spawning fit's registry to the worker so its
                            # re_build wall lands in THIS fit's breakdown
                            # (and its re_build span under the fit span).
                            with stage_scope(reg), telemetry.adopt_span(
                                span_h
                            ):
                                return build_random_effect_dataset(
                                    dataset, cfg_re
                                )

                        def _submit_re() -> None:
                            while pending_re and len(re_futures) <= workers:
                                nxt = pending_re.pop(0)
                                re_futures[nxt] = pool.submit(
                                    _build_in_scope, self.data_configs[nxt]
                                )

                        _submit_re()
                for cid in self.update_sequence:
                    cfg = self.data_configs[cid]
                    if isinstance(cfg, RandomEffectDataConfig):
                        fut = re_futures.pop(cid, None)
                        if fut is not None:
                            try:
                                red = fut.result()
                            except Exception:
                                # A failed producer thread must not kill the
                                # fit: rebuild synchronously on this thread
                                # (the pipeline moves only WHEN work runs, so
                                # the fallback result is identical).
                                from photon_ml_tpu.utils import faults

                                logger.warning(
                                    "background build of coordinate %r "
                                    "failed; rebuilding synchronously",
                                    cid,
                                    exc_info=True,
                                )
                                faults.COUNTERS.increment(
                                    "fallback_sync_builds"
                                )
                                red = build_random_effect_dataset(dataset, cfg)
                        else:
                            red = build_random_effect_dataset(dataset, cfg)
                        if pending_re:
                            _submit_re()
                        original_shard = cfg.feature_shard
                        with self._exclusive_stage("projector"):
                            ps = project_shard(
                                dataset,
                                red,
                                cfg.projector_type,
                                projected_dim=cfg.projected_dim,
                                seed=self.seed,
                                # Fused pass: a device-built index-map
                                # projector folds the feature summary into
                                # its key-sort sweep when normalization
                                # will need it.
                                want_stats=(
                                    self.normalization
                                    != NormalizationType.NONE
                                ),
                            )
                        with stage_timer("stats"):
                            if ps.shard_name != original_shard:
                                norm = self._norm_for_projected_re(
                                    dataset, original_shard, ps
                                )
                            else:
                                norm = self._norm_for_shard(dataset, original_shard)
                        prepared[cid] = _PreparedCoordinate(
                            cfg, original_shard, ps.shard_name, norm, red, ps.projector
                        )
                        logger.info(
                            "coordinate %s: %d entities, %d active / %d passive "
                            "samples, projected dim %d",
                            cid,
                            red.num_entities,
                            red.num_active_samples,
                            red.num_passive_samples,
                            ps.projector.projected_dim,
                        )
                    elif isinstance(cfg, FixedEffectDataConfig):
                        with stage_timer("stats"):
                            norm = self._norm_for_shard(dataset, cfg.feature_shard)
                        prepared[cid] = _PreparedCoordinate(
                            cfg, cfg.feature_shard, cfg.feature_shard, norm
                        )
                    else:
                        raise TypeError(f"unknown data config for {cid}: {type(cfg)}")
        finally:
            if pool is not None:
                pool.shutdown(wait=True)
        self._prepared = prepared
        return prepared

    # ----------------------------------------------------------- coordinates

    def _coordinate_for(
        self,
        dataset: GameDataset,
        cid: str,
        prep: _PreparedCoordinate,
        opt_config: CoordinateOptimizationConfig,
    ):
        """CoordinateFactory.build (CoordinateFactory.scala:51) with a cache
        keyed by the static parts of the config — the reg weight is traced, so
        sweep steps share compiled programs.

        Construction is where the data-plane pack join, the packed-layout /
        ELL device uploads, and the program construction happen; the first
        two record their own stages, and the remainder of the construction
        wall is attributed to `compile` (dispatch decisions + jit/program
        building). A fixed effect's construction adds one to the counter
        `fixed_effect_blocks{coordinate=<id>,layout=dense|packed|ell}`."""
        key = (cid, _static_config_key(opt_config))
        coord = self._coordinate_cache.get(key)
        if coord is None:
            with stage_scope(self.timing_registry), self._exclusive_stage(
                "compile"
            ):
                # Coordinates are constructed with the weight zeroed so the
                # baked-in config carries no sweep-step value (the real
                # weight is a traced argument to every train call).
                static_cfg = dataclasses.replace(opt_config, reg_weight=0.0)
                if prep.re_dataset is not None:
                    coord = RandomEffectCoordinate(
                        dataset, prep.re_dataset, static_cfg, self.task, prep.norm
                    )
                else:
                    coord = FixedEffectCoordinate(
                        dataset, prep.shard, static_cfg, self.task, prep.norm
                    )
                    telemetry.METRICS.increment(
                        "fixed_effect_blocks",
                        labels=(("coordinate", cid), ("layout", coord.layout)),
                    )
            self._coordinate_cache[key] = coord
        return coord

    # ------------------------------------------------------------ validation

    def _make_transformer(self, model: GameModel) -> GameTransformer:
        specs = self.scoring_specs()
        return GameTransformer(model, specs, self.task, pipeline=self.pipeline)

    def scoring_specs(self) -> Dict[str, CoordinateScoringSpec]:
        """Scoring metadata for the trained coordinates (consumed by
        GameTransformer and by model save)."""
        if self._prepared is None:
            raise RuntimeError("fit()/prepare() must run first")
        specs = {}
        for cid, prep in self._prepared.items():
            if prep.re_dataset is not None:
                specs[cid] = CoordinateScoringSpec(
                    shard=prep.original_shard,
                    norm=prep.norm,
                    random_effect_type=prep.re_dataset.config.random_effect_type,
                    entity_index=prep.re_dataset.entity_index,
                    projector=prep.projector,
                )
            else:
                specs[cid] = CoordinateScoringSpec(shard=prep.shard, norm=prep.norm)
        return specs

    def training_prepared(self) -> Dict[str, "PreparedCoordinateData"]:
        """Scoring-prep views of the TRAINING dataset, reusing the arrays
        prepare() already built — the projected shard registered on the
        dataset and each RandomEffectDataset's per-sample entity rows.
        Scoring/evaluating the training dataset with GameTransformer must
        pass this instead of letting transform() re-run the projector and
        entity resolution over data fit() already resolved (the reference's
        transform():150-263 rebuilds them; its scoring of training data
        reuses the training RDD views the same way)."""
        if self._prepared is None:
            raise RuntimeError("fit()/prepare() must run first")
        out: Dict[str, PreparedCoordinateData] = {}
        for cid, prep in self._prepared.items():
            if prep.re_dataset is not None:
                out[cid] = PreparedCoordinateData(
                    self._prepared_dataset.shards[prep.shard],
                    prep.re_dataset.sample_entity_rows,
                )
            else:
                # Prefer the trained coordinate's features (bucketed layout
                # or bf16-stored matrix): scoring through them avoids
                # materializing the raw ELL on device when training never
                # did (ShardDict lazy upload). All sweep entries of a cid
                # share the same feature representation, so any cache hit
                # serves (training_features is the public accessor).
                feats = next(
                    (
                        coord.training_features
                        for key, coord in self._coordinate_cache.items()
                        if isinstance(key, tuple) and key and key[0] == cid
                    ),
                    None,
                )
                if feats is None:
                    feats = self._prepared_dataset.shards[prep.shard]
                out[cid] = PreparedCoordinateData(feats, None)
        return out

    def _validation_prepared(
        self,
        validation_data: GameDataset,
        specs: Mapping[str, CoordinateScoringSpec],
    ) -> Tuple[Dict[str, PreparedCoordinateData], str]:
        """Each coordinate's scoring view of `validation_data` (entity rows
        and projected features, `prepare_coordinate_data`), built once a
        validation data set and handed to every later fit and sweep on the
        same object, with the verdict "built" or "reused".

        One slot holds the data set, compared with `is` as `prepare` holds
        the training set, and each coordinate's shard, projector and entity
        index, so a view made through other artifacts is never handed back;
        another data set replaces the slot. The `projector` stage is entered
        either way, and a reuse sets the build's `ell_planes_scored` note
        again. Adds one a coordinate to the counter
        `validation_prepared{coordinate=<id>,outcome=built|reused}`."""
        views = tuple(
            (cid, specs[cid].shard, specs[cid].projector, specs[cid].entity_index)
            for cid in self.update_sequence
        )
        slot = self._validation_slot
        reused = (
            slot is not None
            and slot.dataset is validation_data
            and len(slot.views) == len(views)
            and all(
                a[:2] == b[:2] and a[2] is b[2] and a[3] is b[3]
                for a, b in zip(slot.views, views)
            )
        )
        reg = self.timing_registry
        with stage_scope(reg):
            if not reused:
                # Free the old views before the new ones are made.
                self._validation_slot = None
                reg.clear_notes("ell_planes_scored")
                # Prefetch INSIDE the scope: AsyncUploader captures the
                # submitter's registry at submit time, so these uploads'
                # walls land in the breakdown's `upload` stage.
                prefetch_fixed_effect_shards(
                    specs, self.update_sequence, validation_data, self.pipeline
                )
            with self._exclusive_stage("projector"):
                if not reused:
                    prepared = {
                        cid: prepare_coordinate_data(specs[cid], validation_data)
                        for cid in self.update_sequence
                    }
                    slot = self._validation_slot = _ValidationSlot(
                        validation_data,
                        views,
                        prepared,
                        reg.get_note("ell_planes_scored"),
                    )
        if slot.scored_note is not None:
            reg.set_note("ell_planes_scored", slot.scored_note)
        outcome = "reused" if reused else "built"
        for cid in self.update_sequence:
            telemetry.METRICS.increment(
                "validation_prepared",
                labels=(("coordinate", cid), ("outcome", outcome)),
            )
        return slot.prepared, outcome

    def _validation_suite(self, validation: GameDataset) -> EvaluationSuite:
        evaluators = self.validation_evaluators or [
            default_evaluator_for_task(self.task)
        ]
        return EvaluationSuite(
            evaluators,
            validation.labels,
            validation.weights,
            id_tag_values=validation.id_tags,
        )

    # ------------------------------------------------------------------- fit

    def fit(
        self,
        data: GameDataset,
        validation_data: Optional[GameDataset],
        opt_configs: Sequence[GameOptimizationConfiguration],
        *,
        initial_model: Optional[GameModel] = None,
    ) -> List[GameResult]:
        """Train one GameModel per optimization configuration
        (GameEstimator.fit:169-230), warm-starting successive configurations.

        `initial_model` seeds the first configuration (the driver's warm-start
        path, GameTrainingDriver.scala:370-378) and must contain every locked
        coordinate's model.

        The whole fit runs under a root `fit` trace span (so a traced run's
        spans cover the full wall), and when an `event_emitter` was given,
        start/sweep/coordinate/checkpoint/finish lifecycle events flow
        through it — the same record cli/train jobs get (ISSUE 11).
        """
        emit = self.event_emitter.send if self.event_emitter is not None else None
        # The adaptive-runtime gate (ISSUE 14): install a plan when
        # PHOTON_PLAN/PHOTON_PLAN_PROFILE ask for one and none is ambient
        # (CLI drivers install earlier so ingest is planned too) — OWNED:
        # a plan this fit installed is uninstalled on every exit path, so
        # library callers re-fitting under a changed env never silently
        # reuse a stale plan (the journal/tracer owned-slot discipline).
        from photon_ml_tpu import planner

        plan_owned = planner.current_plan() is None
        installed = planner.ensure_ambient_plan()
        # Where this fit's SOLVE_STAGES walls are recorded. Pipelined: the
        # estimator's own registry, as the solve's scope always was there.
        # Synchronous: a registry of this fit's own, because the
        # estimator's must stay closed outside prepare work — with it open
        # across the solve, solve-time uploads would land in `upload` and
        # the PREPARE_STAGES would no longer tile `prepare_s`. The data
        # plane's own scopes (prepare, validation prep, coordinate
        # construction) nest inside and still reach the estimator's.
        from photon_ml_tpu.data.pipeline import pipeline_enabled

        stages = (
            self.timing_registry
            if pipeline_enabled(self.pipeline)
            else TimingRegistry()
        )
        stages_base = dict(stages.sections)
        try:
            with stage_scope(stages), stage_timer(
                "fit", num_configs=len(opt_configs)
            ):
                if emit is not None:
                    emit(TrainingStartEvent(num_samples=int(data.num_samples)))
                results = self._fit(
                    data, validation_data, opt_configs, initial_model=initial_model
                )
                if emit is not None:
                    best_eval = (
                        select_best_result(results)[1].evaluation
                        if results
                        else None
                    )
                    emit(
                        TrainingFinishEvent(
                            num_configs=len(results),
                            best_metric=(
                                None
                                if best_eval is None
                                else float(best_eval.primary_value)
                            ),
                        )
                    )
            self._publish_stages(stages, stages_base)
            return results
        finally:
            if plan_owned and installed is not None:
                planner.uninstall_plan()

    def _publish_stages(self, stages: TimingRegistry, base: Dict[str, float]):
        """This fit's stage walls and evaluation counts, per fit and per
        process: `fit_timing["stages_s"]` (plain floats; a stage that did
        not run reads 0.0) beside `fit_timing["fn_evals"]`,
        `fit_timing["line_search_rejected"]`, `fit_timing["hv_evals"]`
        (TRON coordinates only), `fit_timing["lane_passes"]` (random
        effects solved with TRON: `{"used", "paid"}`) and
        `fit_timing["gradient_allreduce_bytes"]` (empty on one device), and
        the same numbers into `telemetry.METRICS` — histogram
        `fit_stage_s{stage=<name>}`, counters `objective_evaluations`,
        `line_search_rejected_trials` and `hessian_vector_products`
        `{coordinate=<id>,kind=fixed|random}`,
        `random_effect_lane_passes{coordinate=<id>,kind=used|paid}`,
        `random_effect_blocks{coordinate=<id>,layout=dense|sparse}` (a
        random effect's buckets by the layout its solves read them in),
        `gradient_allreduce_bytes{coordinate=<id>}` — so a reader that sees only
        the process gets a window's totals as the process total less the
        fits made before it — and the gauge
        `random_effect_data{coordinate=<id>,kind=entities|active_rows}`."""
        self.fit_timing["stages_s"] = {
            k: float(stages.get(k) - base.get(k, 0.0)) for k in SOLVE_STAGES
        }
        for stage, seconds in self.fit_timing["stages_s"].items():
            telemetry.METRICS.observe(
                "fit_stage_s", seconds, labels=(("stage", stage),)
            )
        def labels(cid):
            kind = "random" if self._prepared[cid].re_dataset is not None else "fixed"
            return (("coordinate", cid), ("kind", kind))

        for cid, evals in self.fit_timing["fn_evals"].items():
            telemetry.METRICS.increment(
                "objective_evaluations", evals, labels=labels(cid)
            )
        for cid, trials in self.fit_timing["line_search_rejected"].items():
            telemetry.METRICS.increment(
                "line_search_rejected_trials", trials, labels=labels(cid)
            )
        for cid, products in self.fit_timing["hv_evals"].items():
            telemetry.METRICS.increment(
                "hessian_vector_products", products, labels=labels(cid)
            )
        for cid, passes in self.fit_timing["lane_passes"].items():
            for kind, n in passes.items():
                telemetry.METRICS.increment(
                    "random_effect_lane_passes", n,
                    labels=(("coordinate", cid), ("kind", kind)),
                )
        for cid, note in (self.timing_registry.get_note("re_block_layout") or {}).items():
            for layout, n in note["layout"].items():
                telemetry.METRICS.increment(
                    "random_effect_blocks", n,
                    labels=(("coordinate", cid), ("layout", layout)),
                )
        for cid, nbytes in self.fit_timing["gradient_allreduce_bytes"].items():
            telemetry.METRICS.increment(
                "gradient_allreduce_bytes", nbytes, labels=(("coordinate", cid),)
            )
        for cid, prepared in self._prepared.items():
            re = prepared.re_dataset
            if re is None:
                continue
            for kind, n in (("entities", re.num_entities), ("active_rows", re.num_active_samples)):
                telemetry.METRICS.set_gauge(
                    "random_effect_data", n, labels=(("coordinate", cid), ("kind", kind))
                )

    def _on_cd_event(self, etype: str, **fields) -> None:
        """run_coordinate_descent's event hook -> typed bus events
        (listener failures are isolated by EventEmitter.send)."""
        if self.event_emitter is None:
            return
        if etype == "coordinate":
            self.event_emitter.send(CoordinateUpdateEvent(**fields))
        elif etype == "checkpoint":
            self.event_emitter.send(CheckpointEvent(**fields))

    def _fit(
        self,
        data: GameDataset,
        validation_data: Optional[GameDataset],
        opt_configs: Sequence[GameOptimizationConfiguration],
        *,
        initial_model: Optional[GameModel] = None,
    ) -> List[GameResult]:
        if not opt_configs:
            raise ValueError("at least one optimization configuration required")
        from photon_ml_tpu import planner
        from photon_ml_tpu.data.pipeline import pipeline_enabled

        pipelined = pipeline_enabled(self.pipeline)
        # Stage breakdown (prepare = host-side dataset/coordinate builds,
        # solve = coordinate descent + validation): exposed as
        # `self.fit_timing` so drivers/benchmarks report where fit wall
        # goes without instrumenting internals. Both are sums of this
        # fit's SOLVE_STAGES walls: `prepare_s` of `fit/revalidate`,
        # `fit/validation_prep` and every configuration's
        # `fit/coordinates`, `solve_s` of every `fit/descent` and
        # `fit/final_evaluate` (which takes the descent's own last
        # validation and evaluates only where there is none).
        # `prepare_s` additionally splits into the
        # PREPARE_STAGES keys (+ `other`, the residual glue) recorded by
        # the data-plane functions themselves.
        stage_base = dict(self.timing_registry.sections)
        # Per-fit note evidence: the placement/layout notes describe THIS
        # fit's decisions (a second fit on cached packs legitimately
        # reports "none" — it packed nothing), never a previous fit's.
        # Stage WALLS are delta'd against stage_base instead; notes have
        # no delta, so they reset.
        self.timing_registry.clear_notes(
            "pack_path", "re_path", "sparse_layout", "sparse_objective",
            "pack_declined", "sample_sharding", "dense_storage",
            "ell_planes", "ell_planes_scored", "tron", "re_block_layout",
        )
        # Snapshot the pod-scale robustness counters so fit_timing reports
        # THIS fit's events (the process-wide counters are cumulative).
        from photon_ml_tpu.utils import faults as _faults

        robustness_base = {
            k: _faults.COUNTERS.get(k) for k in ROBUSTNESS_CLEAN_ZERO_KEYS
        }
        # The first fit prepares here; every later fit of a refit loop
        # finds the estimator prepared and only checks and rebuilds.
        with stage_timer("fit/revalidate") as revalidate:
            prepared = self.prepare(data)
            for cfgs in opt_configs:
                missing = [c for c in self.update_sequence if c not in cfgs and c not in self.locked]
                if missing:
                    raise ValueError(f"optimization config missing coordinates {missing}")

            suite = self._validation_suite(validation_data) if validation_data is not None else None
            specs = self.scoring_specs()

        # The validation data set's scoring views (projection + entity-row
        # resolution), built by the first fit that sees the data set and
        # reused by every later one and across every CD step; attributed to
        # the `projector` stage.
        val_prep = outcome = None
        with stage_timer("fit/validation_prep") as validation_prep:
            if validation_data is not None:
                val_prep, outcome = self._validation_prepared(validation_data, specs)

        self.fit_timing = {
            "prepare_s": revalidate.seconds + validation_prep.seconds,
            "solve_s": 0.0,
            "validation_prep": outcome,
        }

        results: List[GameResult] = []
        prev_model: Optional[GameModel] = initial_model
        diverged_steps = 0
        collective_bytes = 0
        fn_evals: Dict[str, int] = {}
        line_search_rejected: Dict[str, int] = {}
        hv_evals: Dict[str, int] = {}
        lane_passes: Dict[str, dict] = {}
        allreduce_bytes: Dict[str, int] = {}
        sharding_infos: Dict[str, dict] = {}
        default_cfg = CoordinateOptimizationConfig()
        for ci, cfgs in enumerate(opt_configs):
            if self.event_emitter is not None:
                self.event_emitter.send(
                    SweepConfigEvent(index=ci, total=len(opt_configs))
                )
            with stage_timer("fit/coordinates") as construction:
                coordinates = {
                    cid: self._coordinate_for(
                        data, cid, prepared[cid], cfgs.get(cid, default_cfg)
                    )
                    for cid in self.update_sequence
                }
            self.fit_timing["prepare_s"] += construction.seconds
            if ci == 0:
                # The sharding decision each coordinate trains under
                # (entity axis size, rows per shard, collective bytes) —
                # recorded once per fit; it is a property of the dataset
                # layout, not the optimization configuration.
                for cid, coord in coordinates.items():
                    info = getattr(coord, "sharding_info", None)
                    if info is not None:
                        sharding_infos[cid] = info()
                # How each random effect's solves read its buckets, dense or
                # ELL (data/game_dataset.block_layout): a property of the
                # bucket shapes, as the sharding is.
                layouts = {
                    cid: coord.block_layout
                    for cid, coord in coordinates.items()
                    if getattr(coord, "block_layout", None) is not None
                }
                if layouts:
                    self.timing_registry.set_note("re_block_layout", layouts)
            if ci == 0:
                # Every fixed-effect coordinate that wanted the ingest's
                # host-COO stash has consumed it by now (its pack decision
                # is cached on the dataset); shards that feed only
                # random-effect coordinates never pop theirs — release them
                # so the triplets don't pin host RAM for the rest of fit.
                # The validation dataset never trains, so its stash has no
                # consumer at all.
                getattr(data, "release_stash", lambda: None)()
                if validation_data is not None:
                    getattr(validation_data, "release_stash", lambda: None)()
            reg_weights = {cid: cfgs[cid].reg_weight for cid in cfgs}

            validation_scorer = None
            if validation_data is not None:
                def validation_scorer(cid, model):
                    return coordinate_margins(specs[cid], model, val_prep[cid])

            # The ambient stage scope (opened by fit()) is the estimator's
            # registry only when pipelined: the prefetched uploads (which
            # run DURING coordinate descent, on background threads) then
            # land in the `upload` stage — the breakdown must show
            # overlapped transfers even though no prepare wall waited on
            # them. In a synchronous run solve-time uploads are solve work,
            # and the stage keys must tile prepare_s exactly.
            with stage_timer("fit/descent") as descent:
                cd = run_coordinate_descent(
                    coordinates,
                    self.cd_iterations,
                    initial_models=prev_model,
                    locked_coordinates=self.locked or None,
                    validation_scorer=validation_scorer,
                    validation_suite=suite,
                    validation_offsets=(
                        validation_data.offsets
                        if validation_data is not None
                        else None
                    ),
                    reg_weights=reg_weights,
                    seed=self.seed + ci,
                    checkpoint_dir=(
                        None
                        if self.checkpoint_dir is None
                        else f"{self.checkpoint_dir}/config-{ci}"
                    ),
                    # Overlap coordinate k+1's device-shard upload with the
                    # solve of coordinate k (ShardDict.prefetch on a
                    # background thread) — the stage the reference hides
                    # inside executor-parallel dataset construction.
                    prefetch=pipelined,
                    on_event=(
                        self._on_cd_event
                        if self.event_emitter is not None
                        else None
                    ),
                )
            # The descent's last validation is the returned model's own
            # evaluation wherever it made one; only a model it never
            # evaluated (a finished checkpoint resumed, every update
            # rejected, a mesh-loss rollback) is scored and evaluated here.
            evaluation = None
            with stage_timer("fit/final_evaluate") as final_evaluate:
                if validation_data is not None and suite is not None:
                    evaluation = cd.evaluation
                    final_evaluate.set(reused=evaluation is not None)
                    if evaluation is None:
                        transformer = self._make_transformer(cd.model)
                        evaluation = transformer.evaluate(
                            validation_data, suite, val_prep
                        )
            results.append(
                GameResult(
                    model=cd.model,
                    config=dict(cfgs),
                    evaluation=evaluation,
                    best_model=cd.best_model,
                    timing=cd.timing,
                )
            )
            prev_model = cd.model
            diverged_steps += cd.diverged_steps
            collective_bytes += cd.collective_bytes
            for cid, evals in cd.fn_evals.items():
                fn_evals[cid] = fn_evals.get(cid, 0) + evals
                # A sample-sharded fixed effect reduces its (d,) gradient
                # and its value over the mesh once an evaluation.
                per_evaluation = getattr(
                    coordinates[cid], "allreduce_bytes_per_evaluation", 0
                )
                if per_evaluation:
                    allreduce_bytes[cid] = (
                        allreduce_bytes.get(cid, 0) + evals * per_evaluation
                    )
            for cid, trials in cd.line_search_rejected.items():
                line_search_rejected[cid] = line_search_rejected.get(cid, 0) + trials
            for cid, counts in cd.tron.items():
                hv_evals[cid] = hv_evals.get(cid, 0) + counts["hessian_vector_products"]
            for cid, counts in cd.lane_passes.items():
                passes = lane_passes.setdefault(cid, {"used": 0, "paid": 0})
                for kind, n in counts.items():
                    passes[kind] += n
            self.fit_timing["solve_s"] += descent.seconds + final_evaluate.seconds
            logger.info(
                "configuration %d/%d trained%s",
                ci + 1,
                len(opt_configs),
                f": {evaluation.results}" if evaluation else "",
            )
        with stage_timer("fit/publish"):
            self.fit_timing["fn_evals"] = fn_evals
            self.fit_timing["line_search_rejected"] = line_search_rejected
            self.fit_timing["hv_evals"] = hv_evals
            self.fit_timing["lane_passes"] = lane_passes
            self.fit_timing["gradient_allreduce_bytes"] = allreduce_bytes
            # Finalize the per-stage prepare breakdown: deltas of the timing
            # registry over this fit call. In a synchronous run the stages +
            # `other` tile `prepare_s`; in a pipelined run overlapped stages
            # record where they ran, so their sum can exceed the wall they hid
            # behind (that excess IS the overlap win).
            stages = {
                k: self.timing_registry.get(k) - stage_base.get(k, 0.0)
                for k in PREPARE_STAGES
            }
            stages["other"] = max(
                0.0, self.fit_timing["prepare_s"] - sum(stages.values())
            )
            self.fit_timing.update(stages)
            # Pack placement split (nested inside the `pack` stage, so NOT part
            # of the tiling sum above): where the bucketed placement pass
            # actually ran, plus which implementation ran it. The keys are
            # always present — the bench e2e contract fails loudly on their
            # absence like the stage keys — and `pack_path` is "none" when no
            # pack engaged this fit.
            self.fit_timing["pack_device_s"] = self.timing_registry.get(
                "pack_device"
            ) - stage_base.get("pack_device", 0.0)
            self.fit_timing["pack_host_s"] = self.timing_registry.get(
                "pack_host"
            ) - stage_base.get("pack_host", 0.0)
            self.fit_timing["pack_path"] = (
                self.timing_registry.get_note("pack_path") or "none"
            )
            # RE-assembly placement split (nested inside the `re_build` stage,
            # so NOT part of the tiling sum): where the entity-block build ran
            # (device_assemble vs the host loops). Keys always present —
            # `re_path` is "none" when no random-effect coordinate was built.
            self.fit_timing["re_device_s"] = self.timing_registry.get(
                "re_device"
            ) - stage_base.get("re_device", 0.0)
            self.fit_timing["re_host_s"] = self.timing_registry.get(
                "re_host"
            ) - stage_base.get("re_host", 0.0)
            self.fit_timing["re_path"] = (
                self.timing_registry.get_note("re_path") or "none"
            )
            # Robustness counter: coordinate updates rejected by the divergence
            # guard across every configuration of this fit (0 on a clean fit —
            # nonzero in a bench artifact is a loud regression signal).
            self.fit_timing["diverged_steps"] = diverged_steps
            # Pod-scale robustness counters for THIS fit (ISSUE 10): collective
            # re-dispatches, shard-staging retries, failed promotions, watchdog
            # trips — all keys always present and all-zero on a clean fit (the
            # bench clean-run contract enforces it).
            self.fit_timing["robustness"] = {
                k: _faults.COUNTERS.get(k) - robustness_base[k]
                for k in ROBUSTNESS_CLEAN_ZERO_KEYS
            }
            # The pod-scale sharding decision as proper JSON keys (ISSUE 7):
            # always present — `entity_sharded` False with axis_size 1 on the
            # single-device path — so the bench e2e contract can fail loudly on
            # absence rather than ship an artifact that silently lost it.
            # The adaptive-runtime plan block (ISSUE 14): always present —
            # inactive ({"active": False, ...}) on an unplanned fit — so the
            # bench e2e contract can fail loudly on absence, and an auditor
            # can tell "planner off" from "block lost".
            self.fit_timing["plan"] = planner.plan_block()
            re_infos = [i for i in sharding_infos.values() if i is not None]
            self.fit_timing["sharding"] = {
                "entity_sharded": any(i["entity_sharded"] for i in re_infos),
                "axis_size": max(
                    [i["axis_size"] for i in re_infos], default=1
                ),
                "rows_per_shard": {
                    cid: i["rows_per_shard"] for cid, i in sharding_infos.items()
                },
                "collective_bytes_per_sweep": sum(
                    i["collective_bytes_per_sweep"] for i in re_infos
                ),
                # Actually moved across the whole fit (every accepted sweep of
                # every configuration) — 0 on the replicated path.
                "collective_bytes_total": int(collective_bytes),
            }
        return results

    # -------------------------------------------------------------- sweeps

    def sweep_executor(
        self,
        data: GameDataset,
        validation_data: GameDataset,
        base_config: GameOptimizationConfiguration,
        tuned_ids: Optional[Sequence[str]] = None,
        *,
        mode: Optional[str] = None,
        warm_start: bool = True,
        max_stack: Optional[int] = None,
        shard_groups: Optional[int] = None,
        on_event=None,
    ):
        """The batched trial executor for hyperparameter sweeps (ISSUE 12):
        wires this estimator's prepared coordinates, validation scorers and
        shard-group builder into a `hyperparameter.sweep.SweepExecutor`.

        `base_config` fixes every coordinate's optimizer statics (and the
        reg weight of untuned coordinates); `tuned_ids` (default: every
        coordinate) names the coordinates whose reg weight the candidate
        columns drive, in column order. The executor's `evaluate_batch` is
        the `BatchEvaluationFunction` the searchers' `find_batched` calls;
        `finalize()` cold-refits the winner (bitwise-equal to a standalone
        fit of the winning config). The trial VALUE is the validation
        suite's primary metric of each trial's final model — the same
        definition in every evaluation mode."""
        from photon_ml_tpu.evaluation.suite import better_than
        from photon_ml_tpu.hyperparameter.sweep import SweepExecutor
        from photon_ml_tpu.transformers.game_transformer import (
            _fe_margins,
            _re_margins,
        )

        if validation_data is None:
            raise ValueError(
                "sweep_executor needs validation data — the trial value is "
                "the validation suite's primary metric"
            )
        if self.locked:
            raise ValueError(
                "hyperparameter sweeps retrain every coordinate; locked "
                "coordinates are not supported"
            )
        missing = [c for c in self.update_sequence if c not in base_config]
        if missing:
            raise ValueError(f"base configuration missing coordinates {missing}")
        prepared = self.prepare(data)
        coordinates = {
            cid: self._coordinate_for(data, cid, prepared[cid], base_config[cid])
            for cid in self.update_sequence
        }
        suite = self._validation_suite(validation_data)
        specs = self.scoring_specs()
        val_prep, _ = self._validation_prepared(validation_data, specs)
        # Traceable per-coordinate validation scorers: model ARRAYS ->
        # margins through the same jitted programs the serial validation
        # path dispatches (`coordinate_margins`' replicated branches), so
        # the stacked program can compute them in-trace.
        trial_scorers = {}
        for cid in self.update_sequence:
            spec, vp = specs[cid], val_prep[cid]
            if spec.is_random_effect:
                def scorer(arrays, _f=vp.features, _r=vp.entity_rows, _n=spec.norm):
                    return _re_margins(_f, _r, arrays["m"], _n)
            else:
                def scorer(arrays, _f=vp.features, _n=spec.norm):
                    return _fe_margins(_f, arrays["w"], _n)
            trial_scorers[cid] = scorer
        return SweepExecutor(
            coordinates,
            list(tuned_ids) if tuned_ids is not None else list(self.update_sequence),
            self.cd_iterations,
            task=self.task,
            base_reg_weights={
                cid: base_config[cid].reg_weight for cid in self.update_sequence
            },
            validation_suite=suite,
            validation_offsets=validation_data.offsets,
            num_validation_samples=validation_data.num_samples,
            trial_scorers=trial_scorers,
            maximize=better_than(suite.primary, 1.0, 0.0),
            seed=self.seed,
            mode=mode,
            warm_start=warm_start,
            max_stack=max_stack,
            shard_groups=shard_groups,
            group_builder=self._sweep_group_builder(data, base_config),
            on_event=on_event,
        )

    def _sweep_group_builder(self, data: GameDataset, base_config):
        """Shard-group coordinate factory: `build(devices)` clones this
        estimator's prepared coordinates onto a device group so one trial's
        full serial fit runs there concurrently with the other groups'.
        Single-device groups are plain device_put clones (bitwise-equal
        programs on another chip); multi-device groups replicate the sample
        data over a group mesh and row-shard the RE coefficient store —
        the PR 7 entity-sharded ring-collective sweep inside the group."""

        def build(devices):
            import jax

            from photon_ml_tpu.data.game_dataset import EntityBlocks

            prepared = self._prepared
            if prepared is None:
                raise RuntimeError("prepare() must run before group builds")
            multi = len(devices) > 1
            if multi:
                from photon_ml_tpu.parallel.mesh import (
                    make_mesh,
                    replicated,
                    shard_random_effect_dataset,
                )

                mesh = make_mesh(devices)
                target = rep = replicated(mesh)
                # Only the RE ENTITY axis shards (the PR 7 ring-collective
                # sweep, bitwise-equal to replicated) — what shard groups
                # buy is the row-sharded coefficient store for fits whose
                # RE matrix exceeds one device.
                # replicate_sample_rows: the group's SAMPLE axis stays
                # replicated (see ds_g below), and batch-sharding
                # sample_entity_rows would demand mesh-divisible sample
                # counts the sweep never promised.
                put_red = lambda red: dataclasses.replace(
                    shard_random_effect_dataset(
                        red, mesh, replicate_sample_rows=True
                    ),
                    feature_mask=put(red.feature_mask),
                )
            else:
                target = devices[0]

                def put_red(red):
                    buckets = []
                    for b in red.buckets:
                        nb = EntityBlocks.__new__(EntityBlocks)
                        nb.gather = put(b.gather)
                        nb.mask = put(b.mask)
                        nb.entity_rows = put(b.entity_rows)
                        buckets.append(nb)
                    return dataclasses.replace(
                        red,
                        buckets=buckets,
                        sample_entity_rows=put(red.sample_entity_rows),
                        feature_mask=put(red.feature_mask),
                    )

            put = lambda a: None if a is None else jax.device_put(a, target)

            def put_feat(f):
                if isinstance(f, SparseFeatures):
                    return dataclasses.replace(
                        f, indices=put(f.indices), values=put(f.values)
                    )
                return put(f)

            # SAMPLE data stays replicated inside a multi-device group
            # (committed to the one device of a single-device group): a
            # batch-sharded fixed-effect solve would reorder the gradient
            # all-reduce and break the bitwise-parity contract.
            ds_g = GameDataset(
                shards={
                    name: put_feat(data.shards[name])
                    for name in {p.shard for p in prepared.values()}
                },
                labels=put(data.labels),
                offsets=put(data.offsets),
                weights=put(data.weights),
                id_tags=data.id_tags,
            )

            coords = {}
            for cid in self.update_sequence:
                prep = prepared[cid]
                static_cfg = dataclasses.replace(
                    base_config[cid], reg_weight=0.0
                )
                # Norm contexts are NamedTuple pytrees: device_put moves
                # their factor/shift arrays with the group's data.
                norm_g = (
                    None
                    if prep.norm is None
                    else jax.device_put(prep.norm, target)
                )
                if prep.re_dataset is not None:
                    coord = RandomEffectCoordinate(
                        ds_g, put_red(prep.re_dataset), static_cfg,
                        self.task, norm_g,
                    )
                    if multi:
                        # The ring-gather scoring path emits SAMPLE-sharded
                        # margins; left alone they propagate sample
                        # sharding into the next fixed-effect solve, whose
                        # partitioned gradient reduction would break the
                        # bitwise contract. Re-replicating is an exact
                        # all-gather (same bits), so the group fit keeps
                        # every residual replicated while the coefficient
                        # store stays row-sharded.
                        _orig_score = coord.score
                        coord.score = lambda m, _s=_orig_score, _r=rep: (
                            jax.device_put(_s(m), _r)
                        )
                    coords[cid] = coord
                else:
                    coords[cid] = FixedEffectCoordinate(
                        ds_g, prep.shard, static_cfg, self.task, norm_g
                    )
            return coords

        return build

    # ---------------------------------------------------------- run profile

    def run_profile(self) -> Dict[str, object]:
        """The machine-readable run profile of the LAST fit (ISSUE 11):
        stage breakdown, ingest breakdown, dispatch decisions, bucket
        shapes, device topology, roofline annotation, and a metrics
        snapshot — the artifact the adaptive-runtime planner consumes.
        Persist with `telemetry.write_profile(path, est.run_profile())`;
        consumers re-read it through `telemetry.read_profile` (loud
        missing-key contract)."""
        if not hasattr(self, "fit_timing"):
            raise RuntimeError("run_profile() needs a completed fit()")
        ft = dict(self.fit_timing)
        stages = {k: round(float(ft[k]), 4) for k in (*PREPARE_STAGES, "other")}
        stages["prepare_s"] = round(float(ft["prepare_s"]), 4)
        stages["solve_s"] = round(float(ft["solve_s"]), 4)
        # Every runtime decision this fit took — the knobs the Spark-ML
        # performance study shows dominate end-to-end cost, recorded so a
        # planner (or a human) can audit WHY this run ran the way it did.
        from photon_ml_tpu.data.pipeline import pipeline_enabled

        dispatch = {
            "pack_path": ft["pack_path"],
            "re_path": ft["re_path"],
            "sharding": dict(ft["sharding"]),
            "pipeline": bool(pipeline_enabled(self.pipeline)),
            # The level-1 sparse layout this fit packed ("none" when no
            # sparse shard packed) — the evidence the planner's
            # sparse_layout rule adopts next run.
            "layout": self.timing_registry.get_note("sparse_layout")
            or "none",
            # Which objective a sparse fixed effect runs: "pallas_fused"
            # (one entry stream), "pallas_composed" (matvec + rmatvec
            # kernels), "ell_xla" (the ELL planes through XLA's gather and
            # scatter-add; `pack_declined` says why the bucketed pack was
            # not made: too_small, dtype, sharded, pad_blowup), or "none"
            # (no sparse fixed effect built its coordinate in this fit).
            "sparse_objective": self.timing_registry.get_note(
                "sparse_objective"
            )
            or "none",
            "pack_declined": self.timing_registry.get_note("pack_declined")
            or "none",
            # {devices, rows_per_device, pad_rows} where a fixed effect built
            # its coordinate on sample-sharded rows in this fit, else "none".
            "sample_sharding": self.timing_registry.get_note("sample_sharding")
            or "none",
            # {layout, dtype, bytes} where a dense fixed effect the fused
            # kernels run built its coordinate in this fit: how the matrix
            # it keeps lies on the device ("column_major": the kernels read
            # (d, tile) blocks of X^T; "row_major": (tile, d) blocks of X;
            # either way nothing relays it), its dtype, and the bytes the
            # coordinate holds beside the shard. "none" where no such
            # coordinate was built.
            "dense_storage": self.timing_registry.get_note("dense_storage")
            or "none",
            # {planes, dense_span, classes, limit} where a fixed effect built
            # its coordinate on an ELL shard in this fit: how many of the
            # shard's planes have their margins multiplied as a dense span of
            # the coefficients (data/containers, "dense span": the planes
            # whose ids the shard shows to be neighbours), in which span
            # classes, under which limit; the other planes' are gathered.
            # `ell_planes_scored` is the same of the ELL shard scoring last
            # prepared: in a fit, the validation rows'.
            "ell_planes": self.timing_registry.get_note("ell_planes") or "none",
            "ell_planes_scored": self.timing_registry.get_note("ell_planes_scored")
            or "none",
            # {accepted, rejected, hessian_vector_products, kernel} of the
            # fixed effect a TRON solve last updated in this fit, summed over
            # its updates (CoordinateDescentResult.tron); the refused steps
            # are reported here and nowhere else.
            "tron": self.timing_registry.get_note("tron") or "none",
            # Per random effect, {layout, shapes, dense_bytes}: how many of
            # its buckets its solves read as a dense (E, D, S) block and how
            # many as ELL planes (data/game_dataset.block_layout), its
            # distinct (entities, capacity, layout) shapes, and the bytes of
            # its widest dense block. "none" where no random effect was built.
            "re_block_layout": self.timing_registry.get_note("re_block_layout")
            or "none",
        }
        bucket_shapes: Dict[str, object] = {}
        for cid, prep in (self._prepared or {}).items():
            if prep.re_dataset is not None:
                bucket_shapes[cid] = [
                    [b.num_entities, b.capacity]
                    for b in prep.re_dataset.buckets
                ]
        ingest = dict(
            getattr(self._prepared_dataset, "ingest_timing", None) or {}
        )
        profile = telemetry.build_profile(
            "fit",
            wall_s=float(ft["prepare_s"]) + float(ft["solve_s"]),
            stages=stages,
            dispatch=dispatch,
            bucket_shapes=bucket_shapes,
            fit_timing=ft,
            ingest=ingest,
        )
        # The plan block rides the profile too (ISSUE 14) so plan
        # decisions round-trip through write_profile/read_profile —
        # deliberately NOT a PROFILE_*_KEYS contract key: r06-era
        # profiles (pre-planner) must keep loading for the cold start.
        profile["plan"] = dict(ft["plan"])
        # Every program this PROCESS has made ready so far, by stage, and
        # every miss by name (utils/compile_cache.py); empty where nothing
        # called `compile_cache.listen()`. Not a contract key either.
        profile["programs"] = compile_cache.summary()
        return profile


def select_best_result(
    results: Sequence[GameResult],
) -> Tuple[int, GameResult]:
    """Pick the configuration whose validation metric is best
    (GameTrainingDriver.selectModels:683-710); falls back to the last result
    when no validation ran."""
    best_i = len(results) - 1
    best: Optional[EvaluationResults] = None
    for i, r in enumerate(results):
        if r.evaluation is not None and r.evaluation.better_than(best):
            best, best_i = r.evaluation, i
    return best_i, results[best_i]
