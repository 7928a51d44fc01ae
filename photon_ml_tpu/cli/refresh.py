"""Continuous-refresh loop driver: data -> incremental fit -> delta swap.

The ISSUE 16 runbook entry point. The reference's production cadence is
"retrain from scratch, redeploy the whole artifact" (GameTrainingDriver
-> new model dir -> serving restart); this driver runs the incremental
alternative end to end against a LIVE engine:

    round 0: full fit -> stage serving bundle
    each round: ingest delta batch -> fingerprint diff -> warm-start
        incremental fit (changed coordinates/entities only) -> delta
        bundle -> in-place generation flip (serving/delta.apply_delta)

and records per-round freshness (`data_to_served_s` — delta batch in
hand to new generation live) in `refresh-summary.json`, with every
`delta_fit_start`/`delta_fit_finish`/`delta_apply`/`delta_rollback`
event in `journal.jsonl` and the characterized parity trail in
`checkpoints/delta_records.jsonl`.

Data source: `--synthetic` draws a base dataset plus streamed delta
batches (entity churn + brand-new entities) — the self-contained demo /
smoke mode tests/test_incremental.py mirrors. Batch size
targets PHOTON_REFRESH_BATCH_ROWS (planner-routed: `refresh_batch_rows`)
unless --batch-rows overrides; churn past
PHOTON_REFRESH_MAX_DELTA_FRACTION of the merged rows escapes to one
warm-started full refit (see game/incremental.plan_delta_fit).

`--shadow-gate` (ISSUE 18) puts every round's delta behind the online
shadow gate instead of committing it blind: the challenger state is
staged as a shadow tenant next to the live engine, probe traffic with
known labels is mirrored into it (serving/shadow.ShadowController with
`auto_actuate=False`), and the delta only commits — the usual
apply_delta generation flip — on a clean `promote` verdict. A
regression (or no verdict at all) journals `delta_rollback`, leaves the
live engine on its current generation untouched, and the loop carries
on from the previous state. Gated runs draw signal-bearing labels so
champion/challenger quality is measurable; the per-round summary gains
a `shadow` block (the controller's SHADOW_BLOCK_KEYS evidence).

Usage: python -m photon_ml_tpu.cli.refresh --help
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import time
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import planner
from photon_ml_tpu.data.game_dataset import (
    FixedEffectDataConfig,
    GameDataset,
    RandomEffectDataConfig,
    concat_datasets,
)
from photon_ml_tpu.game import incremental
from photon_ml_tpu.optimize.config import (
    L2,
    CoordinateOptimizationConfig,
    OptimizerConfig,
)
from photon_ml_tpu.serving.bundle import ScoreRequest, ServingBundle
from photon_ml_tpu.serving.delta import (
    apply_delta,
    apply_delta_for_tenant,
    build_delta_bundle,
)
from photon_ml_tpu.serving.engine import ServingEngine
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils import faults, telemetry

logger = logging.getLogger("photon_ml_tpu.cli.refresh")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="photon_ml_tpu.cli.refresh",
        description="Continuous refresh: incremental fits + delta-bundle "
        "swaps against a live serving engine",
    )
    p.add_argument("--root-output-directory", required=True)
    p.add_argument("--synthetic", action="store_true",
                   help="draw a synthetic base dataset + streamed delta "
                        "batches (the self-contained demo mode)")
    p.add_argument("--rounds", type=int, default=3,
                   help="number of delta batches to stream (default 3)")
    p.add_argument("--base-rows", type=int, default=512,
                   help="synthetic base dataset rows (default 512)")
    p.add_argument("--batch-rows", type=int, default=None,
                   help="rows per streamed delta batch (default: the "
                        "PHOTON_REFRESH_BATCH_ROWS knob via the planner)")
    p.add_argument("--entities", type=int, default=24,
                   help="synthetic entity count in the base data")
    p.add_argument("--new-entities-per-round", type=int, default=2,
                   help="brand-new entities appearing in each delta batch")
    p.add_argument("--churn-entities", type=int, default=3,
                   help="existing entities each delta batch touches")
    p.add_argument("--training-task", type=TaskType.parse,
                   default=TaskType.LOGISTIC_REGRESSION)
    p.add_argument("--shadow-gate", action="store_true",
                   help="land each round's delta as a SHADOW tenant first "
                        "and only commit on a clean online verdict "
                        "(regressions journal delta_rollback and leave the "
                        "live generation untouched)")
    p.add_argument("--probe-rows", type=int, default=48,
                   help="labelled probe requests mirrored through the "
                        "shadow per round (two evaluation windows; only "
                        "used with --shadow-gate)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--logging-level", default="INFO")
    return p


def _synthetic_batch(rng, n: int, entities: np.ndarray, d_fe: int, d_re: int,
                     w_true: Optional[np.ndarray] = None):
    """One data batch over the given entity pool (rows cycle the pool so
    every listed entity actually appears — deterministic churn). With
    `w_true` the labels carry signal (a noisy linear rule on the fixed
    features) instead of coin flips — the shadow gate compares champion
    and challenger QUALITY, which only means something when there is a
    signal to learn; the default coin labels keep the ungated loop's
    draws bitwise-identical to previous releases."""
    ent = np.resize(entities, n)
    Xg = rng.normal(size=(n, d_fe)).astype(np.float32)
    Xre = rng.normal(size=(n, d_re)).astype(np.float32)
    if w_true is None:
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    else:
        y = (Xg @ w_true + 0.25 * rng.normal(size=n) > 0.0).astype(np.float32)
    return GameDataset.build(
        {"g": jnp.asarray(Xg), "re": jnp.asarray(Xre)},
        y,
        id_tags={"eid": ent},
    )


def _probe_requests(rng, n: int, entities: int, d_fe: int, d_re: int,
                    w_true: np.ndarray, round_idx: int):
    """Fresh labelled probe traffic for one shadow-gated round: rows the
    models have never seen, drawn from the same distribution as the
    training stream, with ground-truth labels from the same noisy linear
    rule. Entity ids cycle the BASE pool, so both champion and
    challenger answer warm."""
    ent = np.resize(np.arange(entities, dtype=np.int64), n)
    Xg = rng.normal(size=(n, d_fe)).astype(np.float32)
    Xre = rng.normal(size=(n, d_re)).astype(np.float32)
    y = (Xg @ w_true + 0.25 * rng.normal(size=n) > 0.0).astype(np.float32)
    reqs = [
        ScoreRequest(
            features={"g": Xg[i], "re": Xre[i]},
            entity_ids={"eid": int(ent[i])},
            uid=f"probe-r{round_idx}-{i}",
        )
        for i in range(n)
    ]
    return reqs, y


def _shadow_gate_round(
    registry, r: int, result, delta, data_configs, task: TaskType, *,
    entities: int, d_fe: int, d_re: int, w_true: np.ndarray,
    probe_rows: int, seed: int,
):
    """ISSUE 18: land one round's delta as a SHADOW before committing it.

    The freshly-fit challenger state is staged as a shadow tenant on the
    live registry, labelled probe traffic is mirrored into it, and the
    round's delta commits to the live engine (the normal apply_delta
    generation flip) ONLY on a clean `promote` verdict. A `reject` — or
    no verdict at all before the timeout — journals `delta_rollback`
    and leaves the live engine untouched. Returns
    `(apply_info_or_None, shadow_block, verdict)`."""
    from photon_ml_tpu.serving.shadow import ShadowController

    chall_bundle = ServingBundle.from_model(
        result.state.model,
        incremental.scoring_specs(data_configs, result.state.entity_indices),
        task,
    )
    window = max(4, probe_rows // 2)
    controller = ShadowController(
        registry, "live", f"delta-r{r}", chall_bundle,
        auto_actuate=False,
        window_size=window,
        min_windows=2,
        cooldown_s=0.0,
        mirror_fraction=1.0,
    )
    probe_rng = np.random.default_rng(seed + 7919 * (r + 1))
    reqs, labels = _probe_requests(
        probe_rng, 2 * window, entities, d_fe, d_re, w_true, r
    )
    try:
        futures = []
        for req, label in zip(reqs, labels):
            fut = registry.submit("live", req, block=True)
            futures.append(fut)
            if controller.mirror(req, fut):
                controller.record_label(req.uid, float(label))
        for fut in futures:
            fut.result(timeout=60.0)
        verdict = controller.wait_for_verdict(timeout_s=120.0)
        shadow_block = controller.summary()
    finally:
        # Idempotent: a rejected shadow is already torn down; a
        # promote-ready one exits WITHOUT a verdict counter (the commit
        # below is the real actuation, via the delta path).
        controller.close()
    if verdict == "promote":
        info = apply_delta_for_tenant(registry, "live", delta)
        return info, shadow_block, verdict
    reason = (
        "shadow gate: challenger regressed on probe traffic"
        if verdict == "reject"
        else "shadow gate: no clean verdict before timeout"
    )
    live_version = int(registry.tenant("live").engine._state.version)
    telemetry.emit_event("delta_rollback", version=live_version, reason=reason)
    faults.COUNTERS.increment("delta_rollbacks")
    logger.warning("round %d delta rejected by shadow gate: %s", r, reason)
    return None, shadow_block, verdict or "no-verdict"


def run_refresh_loop(
    out_root: str,
    *,
    rounds: int,
    base_rows: int,
    batch_rows: Optional[int],
    entities: int,
    new_entities_per_round: int,
    churn_entities: int,
    task: TaskType,
    seed: int,
    d_fe: int = 6,
    d_re: int = 4,
    shadow_gate: bool = False,
    probe_rows: int = 48,
) -> Dict[str, object]:
    """The full synthetic loop; returns (and writes) the refresh summary."""
    rng = np.random.default_rng(seed)
    if batch_rows is None:
        batch_rows = int(planner.planned_value("refresh_batch_rows"))
    data_configs = {
        "fixed": FixedEffectDataConfig("g"),
        "per-entity": RandomEffectDataConfig("eid", "re", min_bucket=4),
    }
    oc = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=25),
        regularization=L2,
        reg_weight=1.0,
    )
    opt_configs = {"fixed": oc, "per-entity": oc}
    ckpt_dir = os.path.join(out_root, "checkpoints")
    os.makedirs(ckpt_dir, exist_ok=True)

    # Shadow-gated runs need measurable model quality (see
    # _synthetic_batch); the ungated stream keeps its coin labels.
    w_true = (
        np.linspace(1.5, -1.5, d_fe).astype(np.float32)
        if shadow_gate
        else None
    )
    t_full = time.perf_counter()
    dataset = _synthetic_batch(
        rng, base_rows, np.arange(entities, dtype=np.int64), d_fe, d_re,
        w_true=w_true,
    )
    state = incremental.full_fit(
        dataset, data_configs, opt_configs, task, seed=seed
    )
    full_fit_s = time.perf_counter() - t_full
    specs = incremental.scoring_specs(data_configs, state.entity_indices)
    bundle0 = ServingBundle.from_model(state.model, specs, task)
    registry = None
    if shadow_gate:
        from photon_ml_tpu.serving.tenancy import TenantRegistry

        registry = TenantRegistry(max_batch=16)
        registry.admit("live", bundle0)
        engine = registry.tenant("live").engine
    else:
        engine = ServingEngine(bundle0, max_batch=16)
    next_entity = entities
    round_records: List[Dict[str, object]] = []
    try:
        for r in range(rounds):
            churn = rng.choice(entities, size=min(churn_entities, entities),
                               replace=False)
            fresh = np.arange(next_entity,
                              next_entity + new_entities_per_round)
            next_entity += new_entities_per_round
            pool = np.concatenate([churn, fresh]).astype(np.int64)
            t_data = time.perf_counter()
            batch = _synthetic_batch(rng, batch_rows, pool, d_fe, d_re,
                                     w_true=w_true)
            dataset = concat_datasets(dataset, batch)
            result = incremental.incremental_fit(
                dataset, data_configs, opt_configs, task,
                prev=state, seed=seed, checkpoint_dir=ckpt_dir,
            )
            delta = build_delta_bundle(
                state, result.state,
                source=f"round-{r}", mode=result.plan.mode,
                delta_rows=result.plan.delta_rows,
                total_rows=result.plan.total_rows,
            )
            shadow_block = verdict = None
            if shadow_gate:
                info, shadow_block, verdict = _shadow_gate_round(
                    registry, r, result, delta, data_configs, task,
                    entities=entities, d_fe=d_fe, d_re=d_re, w_true=w_true,
                    probe_rows=probe_rows, seed=seed,
                )
            else:
                info = apply_delta(engine, delta)
            data_to_served_s = time.perf_counter() - t_data
            committed = info is not None and bool(info["committed"])
            if committed:
                # A rejected round does NOT advance the model: the next
                # delta is fit from the last state the gate let through
                # (the data is kept — only the weights roll back).
                state = result.state
            generation = (
                int(info["version"]) if info is not None
                else int(engine._state.version)
            )
            record = {
                "round": r,
                "mode": result.plan.mode,
                "delta": delta.manifest(),
                "incremental_fit_s": round(result.seconds, 4),
                "max_rel_diff": result.max_rel_diff,
                "generation": generation,
                "committed": committed,
                "data_to_served_s": round(data_to_served_s, 4),
            }
            if shadow_block is not None:
                record["shadow"] = shadow_block
                record["shadow_verdict"] = verdict
            round_records.append(record)
            logger.info(
                "round %d: mode=%s delta_rows=%d/%d generation=%d "
                "committed=%s data->served %.3fs",
                r, result.plan.mode, result.plan.delta_rows,
                result.plan.total_rows, generation, committed,
                data_to_served_s,
            )
        provenance = dict(engine.bundle.provenance)
        metrics = engine.metrics()
    finally:
        if registry is not None:
            registry.close(release_bundles=True)
        else:
            engine.close()
            engine.bundle.release()
    summary = {
        "rounds": round_records,
        "full_fit_s": round(full_fit_s, 4),
        "batch_rows": int(batch_rows),
        "provenance": provenance,
        "bundle_deltas": metrics["bundle_deltas"],
        "plan": planner.plan_block(),
    }
    with open(os.path.join(out_root, "refresh-summary.json"), "w") as f:
        json.dump(summary, f, indent=2, default=str)
    return summary


def main(argv: Optional[List[str]] = None) -> None:
    from photon_ml_tpu.utils import compile_cache

    compile_cache.enable()
    args = build_parser().parse_args(argv)
    logging.basicConfig(
        level=getattr(logging, args.logging_level.upper(), logging.INFO)
    )
    if not args.synthetic:
        raise SystemExit(
            "only --synthetic data is supported; production refresh loops "
            "drive game.incremental + serving.delta directly against their "
            "ingest (see the README 'Continuous refresh' runbook)"
        )
    out_root = args.root_output_directory
    os.makedirs(out_root, exist_ok=True)
    journal = telemetry.RunJournal(os.path.join(out_root, "journal.jsonl"))
    telemetry.install_journal(journal)
    try:
        summary = run_refresh_loop(
            out_root,
            rounds=args.rounds,
            base_rows=args.base_rows,
            batch_rows=args.batch_rows,
            entities=args.entities,
            new_entities_per_round=args.new_entities_per_round,
            churn_entities=args.churn_entities,
            task=args.training_task,
            seed=args.seed,
            shadow_gate=args.shadow_gate,
            probe_rows=args.probe_rows,
        )
    finally:
        telemetry.uninstall_journal()
        journal.close()
    served = [r["data_to_served_s"] for r in summary["rounds"]]
    logger.info(
        "refresh loop done: %d round(s), data->served %s s, summary at %s",
        len(served),
        [round(s, 3) for s in served],
        os.path.join(out_root, "refresh-summary.json"),
    )


if __name__ == "__main__":
    main()
