"""GAME training driver: the end-to-end train CLI.

Counterpart of photon-client cli/game/training/GameTrainingDriver.scala:55-855
(see SURVEY.md §3.1 for the reference call stack). Pipeline:

    parse args -> read training/validation Avro data -> (warm-start model)
    -> GameEstimator.fit over the expanded reg-weight sweep
    -> optional hyperparameter tuning (RANDOM | BAYESIAN)
    -> model selection -> save models + metadata under the output root.

Output layout mirrors ModelProcessingUtils.saveGameModelToHDFS:
    <root>/models/best/...               (unless output mode NONE)
    <root>/models/explicit-<i>/...       (EXPLICIT | ALL)
    <root>/models/tuned-<i>/...          (TUNED | ALL)
Option names match the reference's scopt surface (kebab-cased Param names,
e.g. --coordinate-configurations with the compound mini-DSL of
ScoptParserHelpers — README.md:283-292 examples parse verbatim).

Usage: python -m photon_ml_tpu.cli.train --help
"""

from __future__ import annotations

import argparse
import enum
import json
import logging
import os
import sys
from typing import Dict, List, Optional

import numpy as np

from photon_ml_tpu.cli.config import (
    CoordinateConfiguration,
    coordinate_config_to_string,
    expand_game_opt_configs,
    feature_shard_config_to_string,
    parse_coordinate_config,
    parse_feature_shard_config,
)
from photon_ml_tpu.data.game_dataset import RandomEffectDataConfig
from photon_ml_tpu.estimators.game_estimator import (
    GameEstimator,
    GameResult,
    select_best_result,
)
from photon_ml_tpu.evaluation.suite import EvaluatorType, better_than
from photon_ml_tpu.hyperparameter.search import HyperparameterConfig
from photon_ml_tpu.hyperparameter.tuner import HyperparameterTuningMode, get_tuner
from photon_ml_tpu.io import avro_data, model_bridge, model_store
from photon_ml_tpu.types import (
    DataValidationType,
    NormalizationType,
    ProjectorType,
    RegularizationType,
    TaskType,
    VarianceComputationType,
)

logger = logging.getLogger("photon_ml_tpu.cli.train")

# Default tuning range for regularization weights (the reference's tuning
# JSON defaults, GameHyperparameterDefaults.scala:20: log-scale weights).
TUNING_REG_WEIGHT_RANGE = (1e-4, 1e4)


class ModelOutputMode(enum.Enum):
    """Reference: io/ModelOutputMode.scala."""

    NONE = "NONE"
    BEST = "BEST"
    EXPLICIT = "EXPLICIT"
    TUNED = "TUNED"
    ALL = "ALL"

    @classmethod
    def parse(cls, name: str) -> "ModelOutputMode":
        return cls[name.strip().upper()]


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="photon_ml_tpu.cli.train",
        description="Train GAME/GLMix models (TPU-native Photon ML)",
    )
    p.add_argument("--training-task", required=True, type=TaskType.parse,
                   help="LINEAR_REGRESSION | LOGISTIC_REGRESSION | POISSON_REGRESSION | "
                        "SMOOTHED_HINGE_LOSS_LINEAR_SVM")
    p.add_argument("--input-data-directories", required=True, nargs="+",
                   help="training data dirs/files (Avro TrainingExample records)")
    p.add_argument("--input-column-names", default=None,
                   help="Rename record fields: 'response=the_label,weight=w,"
                        "offset=o,uid=id,metadataMap=meta' (inputColumnsNames,"
                        " InputColumnsNames.scala:65-73)")
    p.add_argument("--input-data-date-range", default=None,
                   help="Inclusive 'yyyyMMdd-yyyyMMdd' range of daily input "
                        "subdirectories <dir>/yyyy/MM/dd (inputDataDateRange, "
                        "GameDriver.scala:64)")
    p.add_argument("--input-data-days-range", default=None,
                   help="Relative '<start days ago>-<end days ago>' range "
                        "(inputDataDaysRange, GameDriver.scala:69)")
    p.add_argument("--validation-data-date-range", default=None,
                   help="Date range for validation dirs "
                        "(validationDataDateRange, GameTrainingDriver.scala:91)")
    p.add_argument("--validation-data-days-range", default=None,
                   help="Days range for validation dirs "
                        "(validationDataDaysRange, GameTrainingDriver.scala:96)")
    p.add_argument("--validation-data-directories", nargs="*", default=[],
                   help="validation data dirs/files")
    p.add_argument("--root-output-directory", required=True)
    p.add_argument("--override-output-directory", action="store_true",
                   help="overwrite an existing output directory")
    p.add_argument("--feature-shard-configurations", required=True, nargs="+",
                   metavar="DSL",
                   help='e.g. "name=globalShard,feature.bags=features|context,intercept=true"')
    p.add_argument("--coordinate-configurations", required=True, nargs="+",
                   metavar="DSL",
                   help='e.g. "name=global,feature.shard=globalShard,optimizer=LBFGS,'
                        'tolerance=1.0E-6,max.iter=50,regularization=L2,reg.weights=0.1|1|10"')
    p.add_argument("--coordinate-update-sequence", default=None,
                   help="comma-separated coordinate ids (default: config order)")
    p.add_argument("--coordinate-descent-iterations", type=int, default=1)
    p.add_argument("--normalization", type=NormalizationType.parse,
                   default=NormalizationType.NONE)
    p.add_argument("--validation-evaluators", nargs="*", default=[],
                   help="e.g. AUC RMSE PRECISION@5:queryId AUC:documentId")
    p.add_argument("--offheap-indexmap-dir", default=None,
                   help="directory of prebuilt persistent feature-index "
                        "partitions (cli.build_index output; the reference's "
                        "off-heap PalDB index dir, GameDriver.scala:231-236)")
    p.add_argument("--model-input-directory", default=None,
                   help="warm-start / partial-retrain model directory")
    p.add_argument("--partial-retrain-locked-coordinates", default=None,
                   help="comma-separated coordinate ids to lock (reuse from "
                        "--model-input-directory)")
    p.add_argument("--variance-computation-type", type=VarianceComputationType.parse,
                   default=VarianceComputationType.NONE)
    p.add_argument("--data-validation", type=lambda s: DataValidationType[s.strip().upper()],
                   default=DataValidationType.VALIDATE_FULL)
    p.add_argument("--checkpoint-directory", default=None,
                   help="Checkpoint-restart root for the coordinate-descent "
                        "outer loop (SURVEY §5.3): a rerun with identical "
                        "arguments resumes from the last completed "
                        "coordinate update")
    p.add_argument("--data-summary-directory", default=None,
                   help="Write per-feature-shard summary statistics as "
                        "FeatureSummarizationResultAvro under this directory "
                        "(dataSummaryDirectory, GameTrainingDriver.scala:582)")
    p.add_argument("--output-mode", type=ModelOutputMode.parse, default=ModelOutputMode.BEST)
    p.add_argument("--model-sparsity-threshold", type=float, default=0.0)
    p.add_argument("--hyper-parameter-tuning", type=HyperparameterTuningMode.parse,
                   default=HyperparameterTuningMode.NONE)
    p.add_argument("--hyper-parameter-tuning-iter", type=int, default=20)
    p.add_argument("--hyper-parameter-tuning-batch-size", type=int, default=1,
                   help="trials proposed per round (>1: constant-liar qEI for "
                        "BAYESIAN, Sobol batches for RANDOM); evaluations run "
                        "sequentially in this driver but proposals are batched")
    p.add_argument("--random-seed", type=int, default=0)
    p.add_argument("--profile", default=None,
                   help="a persisted run profile (profile.json from a prior "
                        "run) the adaptive planner consumes for layout/"
                        "routing/batching decisions; refuses loudly on a "
                        "mismatched device topology. Overrides "
                        "PHOTON_PLAN_PROFILE; explicit PHOTON_* knobs "
                        "override individual plan decisions")
    p.add_argument("--logging-level", default="INFO")
    p.add_argument("--application-name", default="photon-ml-tpu-training")
    p.add_argument("--multihost", type=int, default=0, metavar="N",
                   help="production multi-host mode: supervise N worker "
                        "processes forming one global mesh over ICI+DCN; "
                        "each host ingests a disjoint file slice, a "
                        "whole-host loss is absorbed by relaunching the "
                        "survivors from the last committed sweep "
                        "(requires --checkpoint-directory and "
                        "--offheap-indexmap-dir; N=1 is the parity "
                        "baseline running the same worker pipeline)")
    p.add_argument("--multihost-devices-per-host", type=int, default=4,
                   metavar="M",
                   help="devices each multi-host worker drives (virtual "
                        "CPU devices under JAX_PLATFORMS=cpu; the global "
                        "mesh has N*M devices)")
    # Internal worker flags, set only by the supervisor's build_argv —
    # never by hand (hidden from --help).
    p.add_argument("--mh-worker", action="store_true", help=argparse.SUPPRESS)
    p.add_argument("--mh-attempt", type=int, default=0, help=argparse.SUPPRESS)
    p.add_argument("--mh-coordinator", default=None, help=argparse.SUPPRESS)
    p.add_argument("--mh-num-hosts", type=int, default=0, help=argparse.SUPPRESS)
    p.add_argument("--mh-host-id", type=int, default=0, help=argparse.SUPPRESS)
    p.add_argument("--mh-rendezvous", default=None, help=argparse.SUPPRESS)
    return p


def _read_data(args, coordinate_configs: Dict[str, CoordinateConfiguration]):
    """readTrainingData/readValidationData (GameTrainingDriver.scala:503-547)."""
    shard_configs = dict(
        parse_feature_shard_config(s) for s in args.feature_shard_configurations
    )
    id_tags = [
        c.data_config.random_effect_type
        for c in coordinate_configs.values()
        if isinstance(c.data_config, RandomEffectDataConfig)
    ]
    for ev in args.validation_evaluators:
        et = EvaluatorType.parse(ev)
        if et.is_grouped and et.id_tag not in id_tags:
            id_tags.append(et.id_tag)

    # prepareFeatureMaps (GameDriver.scala:231-236): prebuilt off-heap index
    # partitions when given, else index maps derived from the data itself.
    prebuilt = None
    if getattr(args, "offheap_indexmap_dir", None):
        # prepareFeatureMaps (GameDriver.scala:231-236): PalDB or PHIDX
        # partitions, auto-detected per shard.
        from photon_ml_tpu.io.paldb import resolve_offheap_index_maps

        prebuilt = resolve_offheap_index_maps(
            args.offheap_indexmap_dir, shard_configs
        )

    # Date-range resolution (IOUtils.resolveRange + pathsForDateRange,
    # GameTrainingDriver.scala:508-509): expand base dirs to daily subdirs.
    from photon_ml_tpu.utils.date_range import paths_for_date_range, resolve_range

    train_range = resolve_range(
        getattr(args, "input_data_date_range", None),
        getattr(args, "input_data_days_range", None),
    )
    train_paths = paths_for_date_range(args.input_data_directories, train_range)
    columns = (
        avro_data.InputColumnNames.parse(args.input_column_names)
        if getattr(args, "input_column_names", None)
        else None
    )
    # NOTE: read_game_dataset supports per-process file slicing
    # (process_index/process_count) for multi-host ingest, but this driver
    # deliberately does NOT auto-engage it: the estimator trains on
    # process-local arrays, so handing each host a disjoint slice without
    # assembling global sharded arrays first (the
    # jax.make_array_from_process_local_data step parallel/multihost.py
    # demonstrates) would silently fit N divergent models. Multi-host
    # pipelines call the reader directly and own that assembly.
    train, index_maps = avro_data.read_game_dataset(
        train_paths,
        shard_configs,
        index_maps=prebuilt,
        id_tag_fields=id_tags,
        columns=columns,
    )

    validation = None
    if args.validation_data_directories:
        val_range = resolve_range(
            getattr(args, "validation_data_date_range", None),
            getattr(args, "validation_data_days_range", None),
        )
        val_paths = paths_for_date_range(
            args.validation_data_directories, val_range
        )
        validation, _ = avro_data.read_game_dataset(
            val_paths,
            shard_configs,
            index_maps=index_maps,
            id_tag_fields=id_tags,
            columns=columns,
        )
    return train, validation, index_maps, shard_configs


def _validate_rows(dataset, task: TaskType, mode: DataValidationType) -> None:
    """DataValidators.sanityCheckDataFrameForTraining (DataValidators.scala:32)."""
    from photon_ml_tpu.data.validators import validate_game_dataset

    validate_game_dataset(dataset, task, mode)


def _tuning_dimensions(
    coordinate_configs: Dict[str, CoordinateConfiguration],
    tunable_ids,
) -> List[HyperparameterConfig]:
    """One LOG-scale dimension per regularized TRAINABLE coordinate
    (GameEstimatorEvaluationFunction.configurationToVector:152); locked
    coordinates have no config entry in the sweep and are not tuned."""
    dims = []
    for cid, cfg in coordinate_configs.items():
        if cid not in tunable_ids:
            continue
        if cfg.opt_config.regularization.reg_type != RegularizationType.NONE:
            dims.append(
                HyperparameterConfig(
                    name=cid,
                    min_value=TUNING_REG_WEIGHT_RANGE[0],
                    max_value=TUNING_REG_WEIGHT_RANGE[1],
                    transform="LOG",
                )
            )
    return dims


def run(args, event_emitter=None) -> Dict[str, object]:
    logging.basicConfig(
        level=getattr(logging, args.logging_level.upper(), logging.INFO),
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    out_root = args.root_output_directory
    models_root = os.path.join(out_root, "models")
    if os.path.exists(models_root):
        if not args.override_output_directory:
            raise FileExistsError(
                f"{models_root} exists; pass --override-output-directory to replace"
            )
        # Clean replace — never mix stale model subdirs into the new run
        # (cleanOutputDirs, GameTrainingDriver.scala:487).
        import shutil

        shutil.rmtree(models_root)
    os.makedirs(out_root, exist_ok=True)

    # Job-scoped observability: file log under the output root (the
    # reference's PhotonLogger HDFS file), timed sections, lifecycle
    # events — and since ISSUE 11 the run journal (every lifecycle event
    # as a typed JSONL line), optional span tracing (PHOTON_TRACE=1 ->
    # Perfetto-loadable trace.json), and the persisted run profile.
    from photon_ml_tpu.utils import telemetry
    from photon_ml_tpu.utils.observability import (
        EventEmitter,
        PhotonLogger,
        PhotonSetupEvent,
        Timed,
        TimingRegistry,
        journal_listener,
    )

    timings = TimingRegistry()
    job_logger = PhotonLogger(
        os.path.join(out_root, "photon-ml-tpu.log"), level=args.logging_level
    )
    if event_emitter is None:
        event_emitter = EventEmitter()
    journal = telemetry.RunJournal(os.path.join(out_root, "journal.jsonl"))
    event_emitter.register(journal_listener(journal))
    # Only adopt the process-ambient slots we own (same discipline for
    # journal and tracer): a caller's pre-installed journal/tracer must
    # survive this run, not be clobbered and uninstalled to None.
    journal_owned = telemetry.current_journal() is None
    if journal_owned:
        telemetry.install_journal(journal)
    tracer_owned = telemetry.current_tracer() is None
    tracer = telemetry.start_tracing_if_enabled()
    event_emitter.send(PhotonSetupEvent(args=str(vars(args))))
    # Adaptive runtime planner (ISSUE 14): installed HERE — after the
    # journal (plan_decision events land in it) and before ingest (chunk
    # rows are a planned quantity). --profile beats PHOTON_PLAN_PROFILE;
    # explicit PHOTON_* knobs beat the plan; owned so a caller's ambient
    # plan survives this run.
    from photon_ml_tpu import planner

    plan_owned = planner.current_plan() is None
    if not plan_owned and getattr(args, "profile", None):
        logger.warning(
            "--profile %s ignored: a runtime plan is already installed "
            "by the caller (uninstall it to let this run plan itself)",
            args.profile,
        )
    try:
        if plan_owned:
            planner.ensure_ambient_plan(getattr(args, "profile", None))
        return _run_job(
            args, event_emitter, out_root, models_root, timings, Timed,
        )
    except Exception as e:
        from photon_ml_tpu.utils.observability import PhotonFailureEvent

        logger.exception("training job failed")
        event_emitter.send(PhotonFailureEvent(error=repr(e)))
        raise
    finally:
        if plan_owned:
            planner.uninstall_plan()
        if tracer is not None and tracer_owned:
            tracer.export(os.path.join(out_root, "trace.json"))
            telemetry.uninstall_tracer()
            logger.info("trace written to %s", os.path.join(out_root, "trace.json"))
        if journal_owned:
            telemetry.uninstall_journal()
        journal.close()
        job_logger.close()


def _run_job(
    args, event_emitter, out_root, models_root, timings, Timed,
) -> Dict[str, object]:
    coordinate_configs = {}
    for s in args.coordinate_configurations:
        cfg = parse_coordinate_config(s)
        coordinate_configs[cfg.name] = cfg
    update_sequence = (
        [c.strip() for c in args.coordinate_update_sequence.split(",")]
        if args.coordinate_update_sequence
        else list(coordinate_configs.keys())
    )
    locked = (
        {c.strip() for c in args.partial_retrain_locked_coordinates.split(",")}
        if args.partial_retrain_locked_coordinates
        else set()
    )

    # Log the effective config back out (the scopt parsers' round-trip print).
    logger.info("effective feature shard configurations:")
    shard_configs_parsed = dict(
        parse_feature_shard_config(s) for s in args.feature_shard_configurations
    )
    for name, fc in shard_configs_parsed.items():
        logger.info("  %s", feature_shard_config_to_string(name, fc))
    logger.info("effective coordinate configurations:")
    for cfg in coordinate_configs.values():
        logger.info("  %s", coordinate_config_to_string(cfg))

    with Timed("read data", registry=timings):
        train, validation, index_maps, shard_configs = _read_data(args, coordinate_configs)
    logger.info(
        "training data: %d samples, shards %s",
        train.num_samples,
        {k: v.size for k, v in index_maps.items()},
    )
    with Timed("validate data", registry=timings):
        _validate_rows(train, args.training_task, args.data_validation)
        if validation is not None:
            _validate_rows(validation, args.training_task, args.data_validation)

    # Feature-shard summarization output (calculateAndSaveFeatureShardStats,
    # GameTrainingDriver.scala:575-593 -> writeBasicStatistics).
    if args.data_summary_directory:
        from photon_ml_tpu.data.stats import summarize
        from photon_ml_tpu.io.model_store import write_basic_statistics

        with Timed("feature summarization", registry=timings):
            for shard, imap in index_maps.items():
                stats = summarize(
                    train.shards[shard], intercept_index=imap.intercept_index
                )
                n_written = write_basic_statistics(
                    os.path.join(args.data_summary_directory, shard), stats, imap
                )
                logger.info(
                    "feature summary: shard %s -> %d records", shard, n_written
                )

    # Per-coordinate variance type (driver-level param applied to every
    # coordinate, GameTrainingDriver varianceComputationType).
    if args.variance_computation_type != VarianceComputationType.NONE:
        import dataclasses as _dc

        for cfg in coordinate_configs.values():
            cfg.opt_config = _dc.replace(
                cfg.opt_config, variance_computation=args.variance_computation_type
            )

    # Box-constraint maps (constraints.file in the coordinate DSL): resolve
    # the legacy JSON constraint string against the shard's index map
    # (GLMSuite.createConstraintFeatureMap:190-265) into (lower, upper)
    # vectors for the projected-L-BFGS optimizer.
    for cfg in coordinate_configs.values():
        if not cfg.constraint_file:
            continue
        import dataclasses as _dc

        from photon_ml_tpu.optimize.constraints import (
            bounds_arrays,
            create_constraint_feature_map,
        )

        if args.normalization != NormalizationType.NONE:
            # The bounds are original-space per-feature boxes; the optimizer
            # clips TRANSFORMED-space coefficients, so with normalization a
            # clipped model could still violate the user's bounds after the
            # original-space fold-out. Refuse rather than silently violate.
            raise ValueError(
                f"coordinate {cfg.name!r}: box constraints cannot combine "
                "with --normalization (bounds apply in original feature "
                "space; the optimizer works in normalized space)"
            )
        dc_cfg = cfg.data_config
        if isinstance(dc_cfg, RandomEffectDataConfig) and dc_cfg.projector_type not in (
            ProjectorType.IDENTITY,
        ):
            raise ValueError(
                f"coordinate {cfg.name!r}: box constraints require the "
                "IDENTITY projector (bounds are per global feature index)"
            )
        imap = index_maps[dc_cfg.feature_shard]
        with open(cfg.constraint_file) as f:
            cmap = create_constraint_feature_map(f.read(), imap)
        box = bounds_arrays(cmap, imap.size)
        if box is not None:
            cfg.opt_config = _dc.replace(
                cfg.opt_config,
                optimizer=_dc.replace(cfg.opt_config.optimizer, box_constraints=box),
            )
            logger.info(
                "coordinate %s: box constraints on %d feature(s)",
                cfg.name,
                len(cmap),
            )

    estimator = GameEstimator(
        args.training_task,
        {cid: c.data_config for cid, c in coordinate_configs.items()},
        update_sequence=update_sequence,
        coordinate_descent_iterations=args.coordinate_descent_iterations,
        normalization=args.normalization,
        validation_evaluators=[EvaluatorType.parse(e) for e in args.validation_evaluators],
        locked_coordinates=locked or None,
        intercept_indices={
            shard: index_maps[shard].intercept_index
            for shard in index_maps
            if index_maps[shard].intercept_index is not None
        },
        seed=args.random_seed,
        checkpoint_dir=getattr(args, "checkpoint_directory", None),
        # The estimator emits start/sweep/coordinate/checkpoint/finish
        # events itself (ISSUE 11 satellite), so library fits and CLI
        # fits produce the same journal record.
        event_emitter=event_emitter,
    )

    # Warm start / partial retrain (GameTrainingDriver.scala:370-409).
    initial_model = None
    if args.model_input_directory:
        artifact = model_store.load_game_model(
            os.path.join(args.model_input_directory), index_maps
        )
        estimator.prepare(train)
        initial_model = model_bridge.warm_start_model_for_estimator(
            artifact, estimator.scoring_specs()
        )
        logger.info("warm start from %s", args.model_input_directory)
    elif locked:
        raise ValueError("--partial-retrain-locked-coordinates requires "
                         "--model-input-directory")

    sweep = expand_game_opt_configs(
        {cid: coordinate_configs[cid] for cid in update_sequence if cid not in locked}
    )
    logger.info("training %d explicit configuration(s)", len(sweep))
    with Timed("train explicit configurations", registry=timings):
        explicit_results = estimator.fit(
            train, validation, sweep, initial_model=initial_model
        )

    # Hyperparameter tuning (GameTrainingDriver.runHyperparameterTuning:643).
    tuned_results: List[GameResult] = []
    if (
        args.hyper_parameter_tuning != HyperparameterTuningMode.NONE
        and validation is not None
    ):
        dims = _tuning_dimensions(coordinate_configs, set(explicit_results[0].config))
        if dims:
            _, base = select_best_result(explicit_results)
            evaluator = base.evaluation.primary
            maximize = better_than(evaluator, 1.0, 0.0)

            def evaluate(point: np.ndarray) -> float:
                cfgs = dict(base.config)
                for d, cid in zip(point, [c.name for c in dims]):
                    import dataclasses as _dc

                    cfgs[cid] = _dc.replace(cfgs[cid], reg_weight=float(d))
                res = estimator.fit(
                    train, validation, [cfgs], initial_model=base.model
                )[0]
                tuned_results.append(res)
                return res.evaluation.primary_value

            tuner = get_tuner(args.hyper_parameter_tuning)
            tuner.search(
                args.hyper_parameter_tuning_iter,
                dims,
                args.hyper_parameter_tuning,
                evaluate,
                maximize=maximize,
                seed=args.random_seed + 1,
                batch_size=args.hyper_parameter_tuning_batch_size,
            )
            logger.info("hyperparameter tuning: %d trials", len(tuned_results))

    # Model selection + save (GameTrainingDriver.scala:683-779).
    all_results = explicit_results + tuned_results
    best_i, best = select_best_result(all_results)
    specs = estimator.scoring_specs()
    summary: Dict[str, object] = {
        "num_samples": int(train.num_samples),
        "num_explicit": len(explicit_results),
        "num_tuned": len(tuned_results),
        "best_index": best_i,
        "best_evaluation": None if best.evaluation is None else best.evaluation.results,
    }

    def _save(result: GameResult, subdir: str) -> None:
        artifact = model_bridge.artifact_from_game_model(
            result.model,
            specs,
            args.training_task,
            opt_configs={
                cid: {
                    "optimizer": c.optimizer.optimizer_type.value,
                    "max_iterations": c.optimizer.max_iterations,
                    "tolerance": c.optimizer.tolerance,
                    "regularization": c.regularization.reg_type.value,
                    "reg_weight": c.reg_weight,
                }
                for cid, c in result.config.items()
            },
        )
        mdir = os.path.join(models_root, subdir)
        model_store.save_game_model(
            mdir,
            artifact,
            index_maps,
            sparsity_threshold=args.model_sparsity_threshold,
        )
        # Ship the feature index maps with the model so the scoring driver
        # resolves names identically (stands in for the off-heap index dir).
        idx_dir = os.path.join(mdir, "feature-indexes")
        os.makedirs(idx_dir, exist_ok=True)
        for shard, imap in index_maps.items():
            imap.save(os.path.join(idx_dir, f"{shard}.json"))

    mode = args.output_mode
    if mode != ModelOutputMode.NONE:
        with Timed("save models", registry=timings):
            _save(best, "best")
            if mode in (ModelOutputMode.EXPLICIT, ModelOutputMode.ALL):
                for i, r in enumerate(explicit_results):
                    _save(r, f"explicit-{i}")
            if mode in (ModelOutputMode.TUNED, ModelOutputMode.ALL):
                for i, r in enumerate(tuned_results):
                    _save(r, f"tuned-{i}")

    for i, r in enumerate(all_results):
        logger.info(
            "config %d%s: %s",
            i,
            " (best)" if i == best_i else "",
            None if r.evaluation is None else r.evaluation.results,
        )
    # Fold per-coordinate descent timings into the job summary so profiling
    # data from inside the estimator reaches the final report.
    for r in all_results:
        for section, seconds in r.timing.items():
            timings.record(f"coordinate {section}", seconds)
    # Persist stage walls with the summary: benchmarks and users read the
    # ingest/train/save split from the artifact instead of scraping logs
    # (the reference logs its Timed sections the same way,
    # GameTrainingDriver.scala:360-480).
    summary["timings_s"] = {
        name: round(total, 3) for name, total in timings.sections.items()
    }
    with open(os.path.join(out_root, "training-summary.json"), "w") as f:
        json.dump(summary, f, indent=2, default=str)
    # The persisted run profile (ISSUE 11): the machine-readable artifact
    # the adaptive-runtime planner consumes — stage breakdown, dispatch
    # decisions, bucket shapes, topology, metrics snapshot. Validated on
    # write; consumers re-read through telemetry.read_profile (loud).
    from photon_ml_tpu.utils import telemetry

    profile_path = telemetry.write_profile(
        os.path.join(out_root, "profile.json"), estimator.run_profile()
    )
    logger.info("run profile written to %s", profile_path)
    logger.info("timing summary:\n%s", timings.summary())
    return summary


def main(argv: Optional[List[str]] = None) -> None:
    from photon_ml_tpu.utils import compile_cache

    compile_cache.enable()
    raw_argv = list(sys.argv[1:] if argv is None else argv)
    args = build_parser().parse_args(raw_argv)
    if args.mh_worker:
        # One host of a supervised process group (spawned by
        # run_supervisor's build_argv; never invoked by hand).
        from photon_ml_tpu.cli import train_multihost

        raise SystemExit(train_multihost.run_worker(args))
    if args.multihost:
        from photon_ml_tpu.cli import train_multihost

        train_multihost.run_supervisor(args, raw_argv)
        return
    run(args)


if __name__ == "__main__":
    main(sys.argv[1:])
