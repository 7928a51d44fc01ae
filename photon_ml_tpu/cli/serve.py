"""Online serving driver: load a model bundle once, replay a request stream.

A deliberate extension beyond the reference (GameScoringDriver only scores
full datasets offline): this driver stages the model into device memory
exactly once (serving/bundle.py), warms the engine's bounded bucket set,
and streams scoring requests through the deadline micro-batcher —
reporting latency percentiles, qps, cold-start fraction, and recompile
counts at exit.

Request formats:
  * JSON lines (`.json`/`.jsonl`, the native format): one object per line,
        {"uid": "r1", "offset": 0.0, "ids": {"userId": "u3"},
         "features": {"shardA": {"f1": 0.5, "f2t": 1.0}}}
    Feature payloads per shard may be a {feature_key: value} mapping
    (resolved through the model's index maps), an {"indices": [...],
    "values": [...]} pair, or a dense list.
  * Avro (a file or part-file directory of reference-shaped records with
    name/term/value feature bags): pass the same feature-shard DSL the
    training/scoring drivers use, so a replayed record builds exactly the
    feature row offline ingest would.

Usage: python -m photon_ml_tpu.cli.serve --help
"""

from __future__ import annotations

import argparse
import itertools
import json
import logging
import os
import sys
import time
from typing import Iterator, List, Optional

import numpy as np

from photon_ml_tpu.io import score_store
from photon_ml_tpu.serving.bundle import (
    ScoreRequest,
    ServingBundle,
    load_bundle,
    request_from_record,
)
from photon_ml_tpu.serving.engine import ServingEngine

logger = logging.getLogger("photon_ml_tpu.cli.serve")

# Stream requests through the batcher in bounded windows: submit a window,
# drain its futures, write its scores — memory stays O(window), not O(stream).
REPLAY_WINDOW = 8192


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="photon_ml_tpu.cli.serve",
        description="Replay scoring requests through the online serving "
        "engine (TPU-native Photon ML)",
    )
    p.add_argument("--model-input-directory", required=False, default=None,
                   help="a model directory written by the training driver "
                        "(single-tenant mode; or use --tenant)")
    p.add_argument("--tenant", action="append", default=None,
                   metavar="NAME=MODEL_DIR",
                   help="multi-tenant mode (repeatable): serve N named "
                        "model bundles on one device fleet through the "
                        "TenantRegistry — per-tenant admission quotas, "
                        "deadlines and failure domains, weighted-fair "
                        "cross-tenant co-batching. Replay traffic is "
                        "assigned round-robin across tenants; the summary "
                        "gains a per-tenant block")
    p.add_argument("--requests", required=True,
                   help="request stream: a .json/.jsonl file (one request "
                        "object per line) or an Avro file/part-directory")
    p.add_argument("--root-output-directory", required=True)
    p.add_argument("--feature-shard-configurations", nargs="+", default=None,
                   metavar="DSL",
                   help="required for Avro request replay: the same shard "
                        "DSL the scoring driver takes")
    p.add_argument("--offheap-indexmap-dir", default=None,
                   help="prebuilt feature-index partitions; default: the "
                        "JSON maps saved beside the model")
    p.add_argument("--max-batch", type=int, default=None,
                   help="largest micro-batch / compiled bucket size "
                        "(default: the installed plan's choice, else 256; "
                        "an explicit value overrides the planner)")
    p.add_argument("--max-wait-ms", type=float, default=None,
                   help="flush a partial batch once its oldest request has "
                        "waited this long (default: the installed plan's "
                        "choice, else 2.0 ms; explicit overrides the "
                        "planner)")
    p.add_argument("--profile", default=None,
                   help="a persisted run profile (profile.json from a prior "
                        "run) the adaptive planner consumes for bucket/wait "
                        "decisions; topology-checked loudly. Overrides "
                        "PHOTON_PLAN_PROFILE")
    p.add_argument("--max-pending", type=int, default=None,
                   help="admission-control bound on the pending queue "
                        "(default: 4x max-batch); replay submits are "
                        "backpressured, live submits past the bound shed "
                        "with a typed Overloaded rejection")
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="per-request deadline budget; a request queued past "
                        "it fails with DeadlineExceeded instead of wasting "
                        "a device slot (default: no deadline)")
    p.add_argument("--reshard-to", type=int, default=None,
                   help="live mesh elasticity drill: once replay traffic is "
                        "flowing, reshard the engine's coefficient layout "
                        "to this many entity shards (1 = replicated) on a "
                        "background worker — zero failed requests, rollback "
                        "on any staging/commit failure; the summary gains a "
                        "'reshard' block")
    p.add_argument("--model-id", default=None,
                   help="model id tag written into every score record")
    p.add_argument("--shadow", default=None, metavar="NAME=MODEL_DIR",
                   help="shadow deployment (single-tenant mode only): admit "
                        "a challenger bundle as a shadow tenant receiving "
                        "mirrored traffic co-batched with the champion — its "
                        "answers are never returned; online evaluation "
                        "windows (see --labels) drive a journaled "
                        "promote/reject verdict through the atomic "
                        "generation flip, and the summary gains a 'shadow' "
                        "block")
    p.add_argument("--labels", default=None, metavar="PATH",
                   help="label stream for the shadow's online evaluation: a "
                        ".json/.jsonl file of {\"uid\": ..., \"label\": ..., "
                        "\"weight\"?: ...} joined by uid into the scoring "
                        "windows; without it the shadow mirrors but no "
                        "verdict can fire")
    p.add_argument("--shadow-window", type=int, default=64,
                   help="joined rows per shadow evaluation window (default "
                        "64); the verdict needs PHOTON_SHADOW_MIN_WINDOWS "
                        "consecutive windows agreeing")
    p.add_argument("--autopilot", action="store_true",
                   help="closed-loop autoscaling (multi-tenant mode only): "
                        "run the photon-autopilot control loop over the "
                        "tenant fleet — shard grow from load skew, hot-row "
                        "rebalance, the HBM demote/restore ladder, batch-"
                        "wait retune — with hysteresis/cooldown/budget "
                        "hygiene (PHOTON_AUTOPILOT_* knobs), every decision "
                        "journaled; the summary gains an 'autopilot' block")
    p.add_argument("--multihost", type=int, default=0, metavar="N",
                   help="multi-host production serving: N share-nothing "
                        "OS-process hosts, each staging only its own "
                        "partition of every random-effect coordinate's "
                        "rows (host-local two-tier stores); a host killed "
                        "mid-replay costs fidelity (its rows answer "
                        "FE-only through the survivors), never a failed "
                        "request, and rejoins by restaging its partition")
    p.add_argument("--multihost-devices-per-host", type=int, default=4,
                   metavar="M",
                   help="virtual devices per serving host (the per-host "
                        "shard count of each coordinate's store); only "
                        "meaningful with --multihost")
    # Hidden plumbing between the multi-host serve supervisor and the
    # worker processes it spawns — never passed by operators.
    p.add_argument("--mh-serve-worker", action="store_true",
                   help=argparse.SUPPRESS)
    p.add_argument("--mh-host-id", type=int, default=0,
                   help=argparse.SUPPRESS)
    p.add_argument("--mh-num-hosts", type=int, default=0,
                   help=argparse.SUPPRESS)
    p.add_argument("--mh-attempt", type=int, default=0,
                   help=argparse.SUPPRESS)
    p.add_argument("--mh-resume-window", type=int, default=0,
                   help=argparse.SUPPRESS)
    p.add_argument("--logging-level", default="INFO")
    return p


def _encode_json_request(bundle: ServingBundle, doc: dict) -> ScoreRequest:
    """One parsed JSON request document -> ScoreRequest against `bundle`
    (shared by the single-tenant stream and the multi-tenant round-robin,
    which encodes each document against its ASSIGNED tenant's bundle)."""
    features = {}
    for shard, payload in (doc.get("features") or {}).items():
        if isinstance(payload, dict) and "indices" in payload:
            features[shard] = (
                np.asarray(payload["indices"], np.int32),
                np.asarray(payload.get("values", []), np.float32),
            )
        elif isinstance(payload, dict):
            features[shard] = payload  # named features -> index maps
        else:
            features[shard] = np.asarray(payload, np.float32)
    return bundle.encode_request(
        features,
        entity_ids=doc.get("ids") or {},
        offset=float(doc.get("offset") or 0.0),
        uid=None if doc.get("uid") is None else str(doc["uid"]),
    )


def _iter_json_docs(path: str, malformed: List[int]) -> Iterator[dict]:
    """Parsed JSON request documents; a malformed line costs ONE record
    (counted), never the rest of the stream."""
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                yield json.loads(line)
            except Exception as exc:  # noqa: BLE001 - per-record isolation
                malformed[0] += 1
                logger.warning(
                    "skipping malformed request at %s:%d: %s", path, lineno, exc
                )


def _iter_json_requests(
    path: str, bundle: ServingBundle, malformed: List[int]
) -> Iterator[ScoreRequest]:
    for doc in _iter_json_docs(path, malformed):
        # One malformed line costs ONE record (counted), never the
        # rest of the stream — same isolation the per-future harvest
        # gives requests that fail at scoring time.
        try:
            req = _encode_json_request(bundle, doc)
        except Exception as exc:  # noqa: BLE001 - per-record isolation
            malformed[0] += 1
            logger.warning("skipping malformed request in %s: %s", path, exc)
            continue
        yield req


def _iter_avro_requests(
    path: str, bundle: ServingBundle, shard_configs, malformed: List[int]
) -> Iterator[ScoreRequest]:
    from photon_ml_tpu.io import avro as avro_io

    paths = (
        avro_io.list_container_files(path) if os.path.isdir(path) else [path]
    )
    for p in paths:
        # Block-streaming read: only one Avro block's decoded records are
        # live at a time, keeping replay memory O(window), not O(file).
        # quarantine=True: one corrupt block costs its requests (counted),
        # never the rest of the replay file. A decodable record that fails
        # request conversion (missing/garbage field) likewise costs one
        # record, not the stream.
        for _, rec in avro_io.iter_container(p, quarantine=True):
            try:
                req = request_from_record(bundle, rec, shard_configs)
            except Exception as exc:  # noqa: BLE001 - per-record isolation
                malformed[0] += 1
                logger.warning(
                    "skipping malformed replay record in %s: %s", p, exc
                )
                continue
            yield req


def run(args) -> dict:
    logging.basicConfig(
        level=getattr(logging, args.logging_level.upper(), logging.INFO),
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    # Validate BEFORE staging anything: a missing shard DSL must not cost a
    # full bundle load + warmup before erroring (and the request-iterator
    # generator body would only run on first consumption).
    is_json = args.requests.endswith((".json", ".jsonl"))
    if not is_json and not args.feature_shard_configurations:
        raise ValueError(
            "Avro request replay needs --feature-shard-configurations "
            "(the bag -> shard mapping offline ingest uses)"
        )
    if getattr(args, "multihost", 0) or getattr(args, "mh_serve_worker", False):
        # Loud, not a silent single-process fallback: the multi-host
        # paths are dispatched by main(); run() is one serving host.
        raise ValueError(
            "--multihost serving dispatches in serve.main(); run() is "
            "the single-process path"
        )
    tenants = getattr(args, "tenant", None)
    if bool(tenants) == bool(args.model_input_directory):
        raise ValueError(
            "pass exactly one of --model-input-directory (single-tenant) "
            "or --tenant NAME=MODEL_DIR (repeatable, multi-tenant)"
        )
    if tenants and getattr(args, "reshard_to", None) is not None:
        # Loud refusal, not a silent no-op: the reshard drill drives ONE
        # engine's orchestrator and has no multi-tenant form yet.
        raise ValueError(
            "--reshard-to is a single-tenant drill; it cannot be combined "
            "with --tenant"
        )
    shadow_spec = getattr(args, "shadow", None)
    if shadow_spec:
        # Loud refusals (ISSUE 18): the shadow rides the SINGLE-tenant
        # replay (one champion, one challenger); the round-robin
        # multi-tenant path has no champion to mirror, and the reshard
        # drill would race the promotion's generation flip.
        if tenants:
            raise ValueError(
                "--shadow mirrors one champion's traffic; it cannot be "
                "combined with --tenant"
            )
        if getattr(args, "reshard_to", None) is not None:
            raise ValueError(
                "--shadow and --reshard-to both drive generation flips; "
                "run them separately"
            )
    if getattr(args, "autopilot", False):
        # Loud refusals (ISSUE 19): the autopilot supervises a tenant
        # FLEET — its sensors and actuators are the TenantRegistry's;
        # and it owns the reshard actuator, so the manual drill and the
        # controller must not both drive generation flips.
        if not tenants:
            raise ValueError(
                "--autopilot supervises a multi-tenant fleet; combine it "
                "with --tenant"
            )
        if getattr(args, "reshard_to", None) is not None:
            raise ValueError(
                "--autopilot owns the reshard actuator; it cannot be "
                "combined with the --reshard-to drill"
            )
    tenant_specs: List[tuple] = []
    for spec in tenants or []:
        name, sep, model_dir = spec.partition("=")
        if not sep or not name or not model_dir:
            raise ValueError(
                f"--tenant {spec!r}: expected NAME=MODEL_DIR"
            )
        if name in dict(tenant_specs):
            raise ValueError(f"duplicate tenant name {name!r}")
        tenant_specs.append((name, model_dir))
    index_maps = None
    if getattr(args, "offheap_indexmap_dir", None):
        from photon_ml_tpu.cli.config import parse_feature_shard_config
        from photon_ml_tpu.io.paldb import resolve_offheap_index_maps

        cfgs = dict(
            parse_feature_shard_config(s)
            for s in (args.feature_shard_configurations or [])
        )
        index_maps = resolve_offheap_index_maps(args.offheap_indexmap_dir, cfgs)

    # Run telemetry (ISSUE 11): the journal records health transitions,
    # swaps, watchdog trips and shard loss during the replay; PHOTON_TRACE
    # exports a Perfetto-loadable trace; the serve profile persists below.
    from photon_ml_tpu.utils import telemetry

    out_root = args.root_output_directory
    os.makedirs(out_root, exist_ok=True)
    journal = telemetry.RunJournal(os.path.join(out_root, "journal.jsonl"))
    # Adaptive runtime planner (ISSUE 14): installed AFTER the journal
    # (inside the try below) so plan_decision events land in it, owned so
    # a caller's ambient plan survives this run. Explicit
    # --max-batch/--max-wait-ms still win.
    from photon_ml_tpu import planner

    plan_owned = planner.current_plan() is None
    if not plan_owned and getattr(args, "profile", None):
        logger.warning(
            "--profile %s ignored: a runtime plan is already installed "
            "by the caller (uninstall it to let this run plan itself)",
            args.profile,
        )
    # Only adopt the process-ambient slots we own (same discipline for
    # journal and tracer): a caller's pre-installed journal/tracer must
    # survive this run, not be clobbered and uninstalled to None.
    journal_owned = telemetry.current_journal() is None
    if journal_owned:
        telemetry.install_journal(journal)
    tracer_owned = telemetry.current_tracer() is None
    tracer = telemetry.start_tracing_if_enabled()

    # The ambient journal/tracer/plan uninstall on EVERY exit path —
    # including a failed bundle load — or the process-global sinks leak
    # into the next run in this process (and its trace would never
    # export).
    try:
        if plan_owned:
            # After install_journal so every plan_decision event lands in
            # THIS run's journal. Loud on topology mismatch by design.
            planner.ensure_ambient_plan(getattr(args, "profile", None))
        if tenant_specs:
            return _run_multi_tenant(args, tenant_specs, index_maps)
        if shadow_spec:
            return _run_with_shadow(args, index_maps)
        bundle = load_bundle(args.model_input_directory, index_maps=index_maps)
        logger.info(
            "bundle pinned: %d coordinate(s), %.1f MB uploaded in %.3fs",
            len(bundle.coordinates),
            bundle.upload_bytes / 1e6,
            bundle.upload_s,
        )
        # Release on EVERY exit path (finally below): a two-tier store's
        # async promotion worker must be joined while the XLA runtime is
        # still alive — a daemon thread dispatching device updates during
        # interpreter teardown aborts the process ("terminate called
        # without an active exception"), which on an error path would mask
        # the real traceback.
        try:
            return _run_with_bundle(args, bundle)
        finally:
            bundle.release()
    finally:
        if plan_owned:
            planner.uninstall_plan()
        if tracer is not None and tracer_owned:
            tracer.export(os.path.join(out_root, "trace.json"))
            telemetry.uninstall_tracer()
        if journal_owned:
            telemetry.uninstall_journal()
        journal.close()


def _write_score_part(scores_dir: str, k: int, results, model_id: str) -> str:
    """Write one replay window's scores as a crash-safe Avro part file:
    a dot-prefixed temp name (invisible to list_container_files) then
    os.replace into place — a SIGKILL mid-write tears the temp file,
    never a part a reader would pick up. `results` is a list of (stream
    position, ScoreResult); uids default to the position. Shared by the
    single-tenant and multi-tenant replay paths."""
    from photon_ml_tpu.io import avro as avro_io
    from photon_ml_tpu.io import schemas

    os.makedirs(scores_dir, exist_ok=True)
    part = os.path.join(scores_dir, f"part-{k:05d}.avro")
    tmp = os.path.join(scores_dir, f".part-{k:05d}.avro.tmp")
    avro_io.write_container(
        tmp,
        schemas.SCORING_RESULT,
        score_store.score_records(
            np.asarray([r.score for _, r in results], np.float64),
            model_id,
            uids=[
                r.uid if r.uid is not None else str(pos)
                for pos, r in results
            ],
        ),
    )
    os.replace(tmp, part)
    return part


def _run_with_bundle(args, bundle: ServingBundle) -> dict:
    from photon_ml_tpu import planner as _planner_mod

    # Explicit CLI flags that override planned serving decisions — fed
    # into the recorded plan block so it reports source "knob" for them.
    _cli_plan_overrides = {}
    if args.max_batch is not None:
        _cli_plan_overrides["serving_max_batch"] = int(args.max_batch)
    if args.max_wait_ms is not None:
        _cli_plan_overrides["serving_max_wait_ms"] = float(args.max_wait_ms)

    is_json = args.requests.endswith((".json", ".jsonl"))
    shard_configs = None
    if args.feature_shard_configurations:
        from photon_ml_tpu.cli.config import parse_feature_shard_config

        shard_configs = dict(
            parse_feature_shard_config(s)
            for s in args.feature_shard_configurations
        )

    malformed = [0]  # records dropped at parse time, before submission
    if is_json:
        stream = _iter_json_requests(args.requests, bundle, malformed)
    else:
        stream = _iter_avro_requests(
            args.requests, bundle, shard_configs, malformed
        )

    from photon_ml_tpu.utils import telemetry

    out_root = args.root_output_directory
    os.makedirs(out_root, exist_ok=True)
    engine = ServingEngine(bundle, max_batch=args.max_batch)
    t_warm = time.perf_counter()
    with telemetry.span("serve_warmup"):
        compiles = engine.warmup()
    warmup_s = time.perf_counter() - t_warm
    logger.info("engine warm: %d bucket program(s) compiled", compiles)

    # Scores are written one part file per replay window, so memory stays
    # O(window) end to end — accumulating the whole stream's scores/uids
    # host-side would re-create exactly the pattern the chunked
    # score_records path removed from cli/score.py.

    scores_dir = os.path.join(out_root, "scores")
    os.makedirs(scores_dir, exist_ok=True)
    model_id = args.model_id or "game-model"
    n_requests = 0
    n_failed = 0
    # Live reshard drill (--reshard-to): kicked on a background worker
    # once the first replay window has answered, so the generation flip
    # happens UNDER traffic — the live-reshard contract, driveable
    # from the CLI. Joined before the summary so the outcome is recorded.
    reshard_to = getattr(args, "reshard_to", None)
    reshard_info: dict = {}
    reshard_thread = None

    def _live_reshard():
        try:
            from photon_ml_tpu.parallel.mesh import surviving_mesh

            reshard_info.update(
                engine.reshard_orchestrator.reshard(
                    surviving_mesh(reshard_to)
                )
            )
            logger.info("live reshard committed: %s", reshard_info)
        except Exception as exc:  # noqa: BLE001 - recorded, replay goes on
            reshard_info["error"] = repr(exc)
            logger.warning("live reshard rolled back: %r", exc)

    t_replay = time.perf_counter()
    with telemetry.span("serve_replay"), engine, engine.batcher(
        max_wait_ms=args.max_wait_ms,
        max_pending=args.max_pending,
        default_deadline_ms=args.deadline_ms,
    ) as batcher:
      # The reshard worker must be joined on EVERY exit path, inside the
      # engine context: a replay error escaping this loop would otherwise
      # close the engine while the worker is mid-stage/mid-commit.
      try:
        for k in itertools.count():
            window = list(itertools.islice(stream, REPLAY_WINDOW))
            if not window:
                break
            if k == 1 and reshard_to is not None and reshard_thread is None:
                import threading

                reshard_thread = threading.Thread(
                    target=_live_reshard, name="photon-reshard-cli"
                )
                reshard_thread.start()
            # Per-future harvesting, not score_all: one malformed request
            # must cost ONE failed record (logged, counted), never the
            # window's healthy co-batched answers or the summary. Replay is
            # a closed-loop client: block=True backpressures against the
            # bounded queue instead of shedding its own offline traffic.
            futures = [batcher.submit(r, block=True) for r in window]
            results = []  # (stream position, ScoreResult) of the successes
            for i, fut in enumerate(futures):
                try:
                    results.append((n_requests + i, fut.result()))
                except Exception as exc:  # noqa: BLE001 - per-request isolation
                    n_failed += 1
                    logger.warning(
                        "request %r failed: %s",
                        window[i].uid if window[i].uid is not None
                        else str(n_requests + i),
                        exc,
                    )
            if results:
                _write_score_part(scores_dir, k, results, model_id)
            n_requests += len(window)
        if reshard_to is not None and reshard_thread is None:
            # Single-window replay: the drill still runs (and is still
            # recorded), just without concurrent traffic to flow past it.
            _live_reshard()
      finally:
        if reshard_thread is not None:
            reshard_thread.join()
        metrics = batcher.metrics()
        # The PLANNED-or-overridden values actually served with (the
        # argparse values may be None = "let the planner decide").
        resolved_wait_ms = batcher.max_wait_s * 1e3
    replay_s = time.perf_counter() - t_replay
    logger.info(
        "replayed %d request(s), %d failed, %d malformed record(s) skipped; "
        "scores written to %s",
        n_requests,
        n_failed,
        malformed[0],
        scores_dir,
    )

    # Drain-on-shutdown already ran (the context exits answered every
    # pending future); the health machine must have landed CLOSED.
    from photon_ml_tpu.utils import faults
    from photon_ml_tpu.utils.contracts import ROBUSTNESS_CLEAN_ZERO_KEYS

    summary = {
        "num_requests": n_requests,
        "failed_requests": n_failed,
        "malformed_records": malformed[0],
        "serving": metrics,
        "health": engine.health.snapshot(),
        # The pod-scale mesh counters (ROBUSTNESS_CLEAN_ZERO_KEYS) are
        # always present — an all-zero block is the clean-run proof, and
        # a missing key would read as one.
        "robustness_counters": {
            **{k: 0 for k in ROBUSTNESS_CLEAN_ZERO_KEYS},
            **faults.counters(),
        },
        # The adaptive-runtime plan block (ISSUE 14): always present —
        # inactive on an unplanned replay — mirroring fit_timing["plan"].
        # Explicit --max-batch/--max-wait-ms flags re-source their
        # decisions as "knob" so the audit shows what actually served.
        "plan": _planner_mod.plan_block(overrides=_cli_plan_overrides),
        # The per-tenant block (ISSUE 15): always present so absence is
        # loud — empty on a single-tenant replay, one TENANT_BLOCK_KEYS
        # dict per tenant under --tenant.
        "tenants": {},
        # Bundle lineage (ISSUE 16, BUNDLE_PROVENANCE_KEYS): where the
        # served model came from and how many delta applies it absorbed.
        "provenance": dict(engine.bundle.provenance),
        # The shadow-deployment block (ISSUE 18): always present so
        # absence is loud — empty here, SHADOW_BLOCK_KEYS under --shadow.
        "shadow": {},
        # ISSUE 19: the autopilot block — empty on this open-loop path.
        "autopilot": {},
    }
    if reshard_to is not None:
        summary["reshard"] = reshard_info
    with open(os.path.join(out_root, "serving-summary.json"), "w") as f:
        json.dump(summary, f, indent=2, default=str)
    # The persisted serve profile (ISSUE 11): latency/dispatch record the
    # planner consumes beside the fit profile (same loud-read contract).
    profile = telemetry.build_profile(
        "serve",
        wall_s=warmup_s + replay_s,
        stages={
            "warmup_s": round(warmup_s, 4),
            "replay_s": round(replay_s, 4),
        },
        dispatch={
            "max_batch": int(engine.max_batch),
            "max_wait_ms": float(resolved_wait_ms),
            "sharding": metrics.get("sharding"),
        },
        bucket_shapes={"engine_buckets": list(engine.buckets)},
        serving=metrics,
    )
    # Plan decisions round-trip through the profile (ISSUE 14), with the
    # same explicit-flag re-sourcing as the summary block.
    profile["plan"] = _planner_mod.plan_block(overrides=_cli_plan_overrides)
    telemetry.write_profile(os.path.join(out_root, "profile.json"), profile)
    logger.info("serving metrics: %s", metrics)
    return summary


def _run_multi_tenant(args, tenant_specs, index_maps) -> dict:
    """Multi-tenant replay (`--tenant NAME=MODEL_DIR` repeatable): every
    tenant's bundle pins onto ONE device fleet behind a TenantRegistry —
    per-tenant admission quotas, deadline budgets and failure domains,
    weighted-fair cross-tenant co-batching — and the replay stream is
    assigned round-robin across tenants (each record encoded against its
    assigned tenant's bundle). Scores land under scores/<tenant>/, and
    the summary carries one TENANT_BLOCK_KEYS dict per tenant."""
    from photon_ml_tpu import planner as _planner_mod
    from photon_ml_tpu.serving.tenancy import TenantRegistry
    from photon_ml_tpu.utils import faults, telemetry
    from photon_ml_tpu.utils.contracts import ROBUSTNESS_CLEAN_ZERO_KEYS

    _cli_plan_overrides = {}
    if args.max_batch is not None:
        _cli_plan_overrides["serving_max_batch"] = int(args.max_batch)
    if args.max_wait_ms is not None:
        _cli_plan_overrides["serving_max_wait_ms"] = float(args.max_wait_ms)

    is_json = args.requests.endswith((".json", ".jsonl"))
    shard_configs = None
    if args.feature_shard_configurations:
        from photon_ml_tpu.cli.config import parse_feature_shard_config

        shard_configs = dict(
            parse_feature_shard_config(s)
            for s in args.feature_shard_configurations
        )

    out_root = args.root_output_directory
    os.makedirs(out_root, exist_ok=True)
    t_warm = time.perf_counter()
    registry = TenantRegistry(
        max_batch=args.max_batch, max_wait_ms=args.max_wait_ms
    )
    names: List[str] = []
    pilot = None
    autopilot_block: dict = {}
    try:
        for name, model_dir in tenant_specs:
            bundle = load_bundle(model_dir, index_maps=index_maps)
            registry.admit(
                name,
                bundle,
                max_pending=args.max_pending,
                deadline_ms=args.deadline_ms,
            )
            names.append(name)
            logger.info(
                "tenant %r pinned: %d coordinate(s), %.1f MB",
                name,
                len(bundle.coordinates),
                bundle.upload_bytes / 1e6,
            )
        warmup_s = time.perf_counter() - t_warm

        if getattr(args, "autopilot", False):
            # Closed-loop autoscaling (ISSUE 19): the photon-autopilot
            # worker ticks beside the replay, every decision journaled;
            # knob-deferred hygiene (PHOTON_AUTOPILOT_*).
            from photon_ml_tpu.autopilot import Autopilot

            pilot = Autopilot(registry)
            logger.info(
                "autopilot armed: %d rule(s), tick %dms",
                len(pilot.rules),
                pilot.tick_ms,
            )

        malformed = [0]
        if is_json:
            raw_stream = _iter_json_docs(args.requests, malformed)
        else:
            raw_stream = _iter_avro_records(args.requests)


        scores_root = os.path.join(out_root, "scores")
        model_id = args.model_id or "game-model"
        n_requests = 0
        n_failed = 0
        assigned = 0  # round-robin cursor over raw records
        t_replay = time.perf_counter()
        with telemetry.span("serve_replay", tenants=names):
            for k in itertools.count():
                window = []  # (tenant name, request)
                for raw in itertools.islice(raw_stream, REPLAY_WINDOW):
                    name = names[assigned % len(names)]
                    assigned += 1
                    bundle = registry.tenant(name).bundle
                    try:
                        if is_json:
                            req = _encode_json_request(bundle, raw)
                        else:
                            req = request_from_record(
                                bundle, raw, shard_configs
                            )
                    except Exception as exc:  # noqa: BLE001 - per-record
                        malformed[0] += 1
                        logger.warning(
                            "skipping malformed request for tenant %r: %s",
                            name,
                            exc,
                        )
                        continue
                    window.append((name, req))
                if not window:
                    break
                futures = [
                    (name, registry.submit(name, r, block=True))
                    for name, r in window
                ]
                by_tenant: dict = {}
                for i, (name, fut) in enumerate(futures):
                    try:
                        res = fut.result()
                    except Exception as exc:  # noqa: BLE001 - per-request
                        n_failed += 1
                        logger.warning(
                            "tenant %r request %d failed: %s",
                            name,
                            n_requests + i,
                            exc,
                        )
                        continue
                    by_tenant.setdefault(name, []).append(
                        (n_requests + i, res)
                    )
                for name, results in by_tenant.items():
                    _write_score_part(
                        os.path.join(scores_root, name),
                        k,
                        results,
                        model_id,
                    )
                n_requests += len(window)
        replay_s = time.perf_counter() - t_replay
        metrics = registry.metrics()
        health = {
            name: registry.tenant(name).engine.health.snapshot()
            for name in names
        }
        provenance = {
            name: dict(registry.tenant(name).bundle.provenance)
            for name in names
        }
    finally:
        if pilot is not None:
            pilot.close()
            autopilot_block = pilot.summary()
        registry.close(release_bundles=True)
    logger.info(
        "replayed %d request(s) across %d tenant(s), %d failed, %d "
        "malformed skipped",
        n_requests,
        len(names),
        n_failed,
        malformed[0],
    )

    summary = {
        "num_requests": n_requests,
        "failed_requests": n_failed,
        "malformed_records": malformed[0],
        "serving": metrics,
        "health": health,
        "robustness_counters": {
            **{k: 0 for k in ROBUSTNESS_CLEAN_ZERO_KEYS},
            **faults.counters(),
        },
        "plan": _planner_mod.plan_block(overrides=_cli_plan_overrides),
        "tenants": metrics["tenants"],
        # Per-tenant bundle lineage (ISSUE 16, BUNDLE_PROVENANCE_KEYS).
        "provenance": provenance,
        # ISSUE 18: always present, empty off the --shadow path.
        "shadow": {},
        # ISSUE 19: always present — AUTOPILOT_BLOCK_KEYS under
        # --autopilot, empty on an open-loop replay.
        "autopilot": autopilot_block,
    }
    with open(os.path.join(out_root, "serving-summary.json"), "w") as f:
        json.dump(summary, f, indent=2, default=str)
    profile = telemetry.build_profile(
        "serve",
        wall_s=warmup_s + replay_s,
        stages={
            "warmup_s": round(warmup_s, 4),
            "replay_s": round(replay_s, 4),
        },
        dispatch={
            "max_batch": int(registry.max_batch),
            "max_wait_ms": float(registry.max_wait_s * 1e3),
            "tenants": names,
        },
        bucket_shapes={"registry_buckets": list(registry.buckets)},
        serving=metrics,
    )
    profile["plan"] = _planner_mod.plan_block(overrides=_cli_plan_overrides)
    telemetry.write_profile(os.path.join(out_root, "profile.json"), profile)
    logger.info("multi-tenant serving metrics: %s", metrics)
    return summary


def _load_labels(path: str) -> dict:
    """uid -> (label, weight) from a .json/.jsonl label stream; a
    malformed line costs ONE label (logged), never the join."""
    labels: dict = {}
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                doc = json.loads(line)
                labels[str(doc["uid"])] = (
                    float(doc["label"]),
                    float(doc.get("weight", 1.0)),
                )
            except Exception as exc:  # noqa: BLE001 - per-record isolation
                logger.warning(
                    "skipping malformed label at %s:%d: %s", path, lineno, exc
                )
    return labels


def _run_with_shadow(args, index_maps) -> dict:
    """Single-tenant replay with a shadow challenger (ISSUE 18,
    `--shadow NAME=MODEL_DIR`): the champion bundle serves as a tenant on
    a TenantRegistry, the challenger rides as a shadow tenant receiving
    mirrored traffic co-batched with the champion — its answers are never
    returned (scores are written for the champion ONLY) — and `--labels`
    joins labels into the online evaluation windows that drive the
    journaled promote/reject verdict. Champion and challenger must share
    the feature space (one request encoding serves both); that is the
    refresh-challenger shape by construction."""
    from photon_ml_tpu import planner as _planner_mod
    from photon_ml_tpu.serving.shadow import ShadowController
    from photon_ml_tpu.serving.tenancy import TenantRegistry
    from photon_ml_tpu.utils import faults, telemetry
    from photon_ml_tpu.utils.contracts import ROBUSTNESS_CLEAN_ZERO_KEYS

    shadow_name, sep, shadow_dir = args.shadow.partition("=")
    if not sep or not shadow_name or not shadow_dir:
        raise ValueError(f"--shadow {args.shadow!r}: expected NAME=MODEL_DIR")
    champion_name = "champion"
    if shadow_name == champion_name:
        raise ValueError(
            f"--shadow name {shadow_name!r} collides with the champion "
            "tenant name"
        )

    _cli_plan_overrides = {}
    if args.max_batch is not None:
        _cli_plan_overrides["serving_max_batch"] = int(args.max_batch)
    if args.max_wait_ms is not None:
        _cli_plan_overrides["serving_max_wait_ms"] = float(args.max_wait_ms)

    is_json = args.requests.endswith((".json", ".jsonl"))
    shard_configs = None
    if args.feature_shard_configurations:
        from photon_ml_tpu.cli.config import parse_feature_shard_config

        shard_configs = dict(
            parse_feature_shard_config(s)
            for s in args.feature_shard_configurations
        )
    labels = _load_labels(args.labels) if args.labels else {}

    out_root = args.root_output_directory
    os.makedirs(out_root, exist_ok=True)
    t_warm = time.perf_counter()
    registry = TenantRegistry(
        max_batch=args.max_batch, max_wait_ms=args.max_wait_ms
    )
    controller = None
    try:
        champ_bundle = load_bundle(
            args.model_input_directory, index_maps=index_maps
        )
        registry.admit(
            champion_name,
            champ_bundle,
            max_pending=args.max_pending,
            deadline_ms=args.deadline_ms,
        )
        chall_bundle = load_bundle(shadow_dir, index_maps=index_maps)
        controller = ShadowController(
            registry,
            champion_name,
            shadow_name,
            chall_bundle,
            window_size=args.shadow_window,
            max_pending=args.max_pending,
            deadline_ms=args.deadline_ms,
        )
        warmup_s = time.perf_counter() - t_warm
        logger.info(
            "champion pinned; challenger %r riding shadow (window=%d, "
            "%d label(s) preloaded)",
            shadow_name,
            args.shadow_window,
            len(labels),
        )

        malformed = [0]
        if is_json:
            raw_stream = _iter_json_docs(args.requests, malformed)
        else:
            raw_stream = _iter_avro_records(args.requests)

        scores_dir = os.path.join(out_root, "scores")
        model_id = args.model_id or "game-model"
        n_requests = 0
        n_failed = 0
        t_replay = time.perf_counter()
        with telemetry.span("serve_replay", shadow=shadow_name):
            for k in itertools.count():
                window = []
                # Encode against the champion's CURRENT bundle: after a
                # promotion flips the generation, later windows encode
                # against the promoted challenger.
                bundle = registry.tenant(champion_name).bundle
                for raw in itertools.islice(raw_stream, REPLAY_WINDOW):
                    try:
                        if is_json:
                            req = _encode_json_request(bundle, raw)
                        else:
                            req = request_from_record(
                                bundle, raw, shard_configs
                            )
                    except Exception as exc:  # noqa: BLE001 - per-record
                        malformed[0] += 1
                        logger.warning(
                            "skipping malformed request: %s", exc
                        )
                        continue
                    window.append(req)
                if not window:
                    break
                futures = []
                for req in window:
                    fut = registry.submit(champion_name, req, block=True)
                    futures.append(fut)
                    # Mirror AFTER the champion submit so the pair lands
                    # in the same dispatch round; a False return (fraction
                    # gate, fault, post-verdict) is champion-only, never
                    # an error.
                    if controller.mirror(req, fut) and req.uid in labels:
                        lab, w = labels[req.uid]
                        controller.record_label(req.uid, lab, weight=w)
                results = []
                for i, fut in enumerate(futures):
                    try:
                        results.append((n_requests + i, fut.result()))
                    except Exception as exc:  # noqa: BLE001 - per-request
                        n_failed += 1
                        logger.warning(
                            "request %d failed: %s", n_requests + i, exc
                        )
                if results:
                    _write_score_part(scores_dir, k, results, model_id)
                n_requests += len(window)
        replay_s = time.perf_counter() - t_replay
        if labels:
            # A short replay outruns the async evaluation worker (the
            # first metric compile alone can cost more than the whole
            # replay): drain the joined-window backlog so the verdict
            # loop gets its chance to actuate before the snapshot. With
            # too few joined rows for a verdict this returns as soon as
            # the backlog is digested, not after the full timeout.
            controller.drain(timeout_s=120.0)
        # The shadow block snapshots BEFORE the controller closes (its
        # champion-generation field reads the live engine); close()
        # retires a still-observing shadow without a verdict.
        shadow_block = controller.summary()
        controller.close()
        metrics = registry.metrics()
        health = registry.tenant(champion_name).engine.health.snapshot()
        provenance = dict(registry.tenant(champion_name).bundle.provenance)
    finally:
        if controller is not None:
            controller.close()
        registry.close(release_bundles=True)
    logger.info(
        "replayed %d request(s), %d failed, %d malformed skipped; shadow "
        "%r finished %s",
        n_requests,
        n_failed,
        malformed[0],
        shadow_name,
        shadow_block["status"],
    )

    summary = {
        "num_requests": n_requests,
        "failed_requests": n_failed,
        "malformed_records": malformed[0],
        "serving": metrics,
        "health": health,
        "robustness_counters": {
            **{k: 0 for k in ROBUSTNESS_CLEAN_ZERO_KEYS},
            **faults.counters(),
        },
        "plan": _planner_mod.plan_block(overrides=_cli_plan_overrides),
        "tenants": metrics["tenants"],
        "provenance": provenance,
        # The online-quality-gate evidence (SHADOW_BLOCK_KEYS).
        "shadow": shadow_block,
        # ISSUE 19: the autopilot block — empty on the shadow path (the
        # shadow controller owns this run's actuations).
        "autopilot": {},
    }
    with open(os.path.join(out_root, "serving-summary.json"), "w") as f:
        json.dump(summary, f, indent=2, default=str)
    profile = telemetry.build_profile(
        "serve",
        wall_s=warmup_s + replay_s,
        stages={
            "warmup_s": round(warmup_s, 4),
            "replay_s": round(replay_s, 4),
        },
        dispatch={
            "max_batch": int(registry.max_batch),
            "max_wait_ms": float(registry.max_wait_s * 1e3),
            "tenants": [champion_name, shadow_name],
        },
        bucket_shapes={"registry_buckets": list(registry.buckets)},
        serving=metrics,
    )
    profile["plan"] = _planner_mod.plan_block(overrides=_cli_plan_overrides)
    telemetry.write_profile(os.path.join(out_root, "profile.json"), profile)
    logger.info("shadow serving metrics: %s", metrics)
    return summary


def _iter_avro_records(path: str) -> Iterator[dict]:
    """Raw reference-shaped Avro replay records (block-streaming,
    corrupt blocks quarantined) — the multi-tenant round-robin encodes
    each against its assigned tenant's bundle."""
    from photon_ml_tpu.io import avro as avro_io

    paths = (
        avro_io.list_container_files(path) if os.path.isdir(path) else [path]
    )
    for p in paths:
        for _, rec in avro_io.iter_container(p, quarantine=True):
            yield rec


def main(argv: Optional[List[str]] = None) -> None:
    from photon_ml_tpu.utils import compile_cache

    compile_cache.enable()
    raw_argv = list(sys.argv[1:] if argv is None else argv)
    args = build_parser().parse_args(raw_argv)
    if args.mh_serve_worker:
        # Spawned by the multi-host serve supervisor: one share-nothing
        # serving host (host-local store + mirrored replay).
        from photon_ml_tpu.cli import serve_multihost

        raise SystemExit(serve_multihost.run_worker(args))
    if args.multihost:
        from photon_ml_tpu.cli import serve_multihost

        serve_multihost.run_supervisor(args, raw_argv)
        return
    run(args)


if __name__ == "__main__":
    main(sys.argv[1:])
