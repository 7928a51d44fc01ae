"""GAME scoring driver: load model -> score dataset -> write scores.

Counterpart of photon-client cli/game/scoring/GameScoringDriver.scala:39-284
(see SURVEY.md §3.2): read data with the model's feature index maps, load the
GAME model artifact, transform through GameTransformer, optionally evaluate,
and write ScoringResultAvro records (saveScoresToHDFS:229-260).

Usage: python -m photon_ml_tpu.cli.score --help
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
from typing import List, Optional

import numpy as np

from photon_ml_tpu.cli.config import parse_feature_shard_config
from photon_ml_tpu.evaluation.suite import EvaluationSuite, EvaluatorType
from photon_ml_tpu.io import avro_data, model_bridge, model_store, score_store
from photon_ml_tpu.io.avro_data import UID
from photon_ml_tpu.transformers.game_transformer import GameTransformer

logger = logging.getLogger("photon_ml_tpu.cli.score")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="photon_ml_tpu.cli.score",
        description="Score data with a trained GAME model (TPU-native Photon ML)",
    )
    p.add_argument("--input-data-directories", required=True, nargs="+")
    p.add_argument("--model-input-directory", required=True,
                   help="a model directory written by the training driver "
                        "(e.g. <root>/models/best)")
    p.add_argument("--root-output-directory", required=True)
    p.add_argument("--feature-shard-configurations", required=True, nargs="+",
                   metavar="DSL")
    p.add_argument("--offheap-indexmap-dir", default=None,
                   help="prebuilt feature-index partitions (PalDB or PHIDX); "
                        "default: the JSON maps saved beside the model")
    p.add_argument("--input-column-names", default=None,
                   help="Rename record fields (see the training driver)")
    p.add_argument("--input-data-date-range", default=None,
                   help="Inclusive 'yyyyMMdd-yyyyMMdd' range of daily input "
                        "subdirectories (inputDataDateRange, GameDriver.scala:64)")
    p.add_argument("--input-data-days-range", default=None,
                   help="Relative '<start>-<end>' days-ago range "
                        "(inputDataDaysRange, GameDriver.scala:69)")
    p.add_argument("--evaluators", nargs="*", default=[],
                   help="optional validation metrics computed on the scored data")
    p.add_argument("--model-id", default=None,
                   help="model id tag written into every score record")
    p.add_argument("--logging-level", default="INFO")
    return p


def run(args) -> dict:
    logging.basicConfig(
        level=getattr(logging, args.logging_level.upper(), logging.INFO),
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    shard_configs = dict(
        parse_feature_shard_config(s) for s in args.feature_shard_configurations
    )

    # Feature index maps: an explicit off-heap store (the reference's PalDB
    # partitions or this framework's PHIDX, prepareFeatureMaps
    # GameDriver.scala:231-236) or, by default, the JSON maps the training
    # driver saved beside the model artifact.
    from photon_ml_tpu.data.index_map import IndexMap

    if getattr(args, "offheap_indexmap_dir", None):
        from photon_ml_tpu.io.paldb import resolve_offheap_index_maps

        index_maps = resolve_offheap_index_maps(
            args.offheap_indexmap_dir, shard_configs
        )
    else:
        index_dir = os.path.join(args.model_input_directory, "feature-indexes")
        index_maps = {
            shard: IndexMap.load(os.path.join(index_dir, f"{shard}.json"))
            for shard in shard_configs
        }
    artifact = model_store.load_game_model(args.model_input_directory, index_maps)
    model, specs = model_bridge.game_model_from_artifact(artifact)

    id_tags = [
        spec.random_effect_type for spec in specs.values() if spec.is_random_effect
    ]
    for ev in args.evaluators:
        et = EvaluatorType.parse(ev)
        if et.is_grouped and et.id_tag not in id_tags:
            id_tags.append(et.id_tag)

    from photon_ml_tpu.utils.date_range import paths_for_date_range, resolve_range

    in_range = resolve_range(
        getattr(args, "input_data_date_range", None),
        getattr(args, "input_data_days_range", None),
    )
    dataset, _ = avro_data.read_game_dataset(
        paths_for_date_range(args.input_data_directories, in_range),
        shard_configs,
        index_maps=index_maps,
        id_tag_fields=id_tags,
        columns=(
            avro_data.InputColumnNames.parse(args.input_column_names)
            if getattr(args, "input_column_names", None)
            else None
        ),
    )
    # Scoring never packs a bucketed layout; cancel ingest's background
    # pack and drop the CSR stash rather than compute a layout nothing
    # will consume / pin ~12 bytes/nnz of host RAM for the run.
    dataset.release_stash()
    logger.info("scoring %d samples", dataset.num_samples)

    transformer = GameTransformer(model, specs, artifact.task)
    result = transformer.transform(dataset)

    out_root = args.root_output_directory
    os.makedirs(out_root, exist_ok=True)
    # Columns go to the writer as-is (device score array, host uid column):
    # save_scores streams them in fixed-size chunks, so a large scoring job
    # never holds a full host copy of any column (the former uids.tolist()
    # materialized an n-element Python string list, and scores/labels/
    # weights were each np.asarray'd whole).
    uids = (
        dataset.id_tags[UID]
        if UID in dataset.id_tags
        else np.arange(dataset.num_samples)
    )
    scores_dir = os.path.join(out_root, "scores")
    score_store.save_scores(
        scores_dir,
        result.scores,
        args.model_id or "game-model",
        uids=uids,
        labels=dataset.labels,
        weights=dataset.weights,
    )
    logger.info("scores written to %s", scores_dir)

    summary = {"num_scored": dataset.num_samples}
    if args.evaluators:
        suite = EvaluationSuite(
            [EvaluatorType.parse(e) for e in args.evaluators],
            dataset.labels,
            dataset.weights,
            id_tag_values=dataset.id_tags,
        )
        evaluation = suite.evaluate(result.scores)
        summary["evaluation"] = evaluation.results
        logger.info("evaluation: %s", evaluation.results)
    with open(os.path.join(out_root, "scoring-summary.json"), "w") as f:
        json.dump(summary, f, indent=2, default=str)
    return summary


def main(argv: Optional[List[str]] = None) -> None:
    from photon_ml_tpu.utils import compile_cache

    compile_cache.enable()
    run(build_parser().parse_args(argv))


if __name__ == "__main__":
    main(sys.argv[1:])
