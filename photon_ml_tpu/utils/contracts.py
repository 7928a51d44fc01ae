"""The repo's loud-contract key schemas, in one place.

Every fit timing, run profile, journal line and serving summary enforces
a "loud missing-key" contract: an artifact that silently lost a metric
is a measurement bug, so the producer fails the run rather than ship it.
A required-key tuple re-typed at an enforcement site — the estimator,
the serving engine, a test — is exactly how a renamed key drifts out of
one copy and the contract silently stops checking it. These tuples are
the single source of truth; the static analyzer's `contract-key-drift`
check (photon_ml_tpu/analysis/) fails the build when any other file
re-types two or more of them as literals instead of importing them.

Producers build dicts from these tuples (e.g. the serving engine zips
SERVING_SHARDING_KEYS); consumers assert against them. Key ORDER in the
zipped producers is part of the schema — append, don't reorder.

Stdlib-only on purpose: the analyzer imports this before jax is up.
"""

from __future__ import annotations

# --------------------------------------------------------------- fit timing
# Per-stage prepare breakdown recorded by GameEstimator.fit (PR 1): the
# stages tile prepare_s in a synchronous run; pipelined runs record where
# the work happened.
PREPARE_STAGES = ("re_build", "projector", "stats", "pack", "upload", "compile")

# Every boundary of a fit (ISSUE 27), each a `stage_timer` of this name where
# the work happens: a wall in the fit's registry (`fit_timing["stages_s"]`,
# histogram `fit_stage_s{stage=<name>}`), a telemetry span, and a
# `photon/<name>` annotation on the profiler's clock. Nesting is
# SOLVE_STAGE_PARENT; siblings tile their parent up to the glue between them.
# Dispatch is asynchronous: `cd/residual`, `cd/train`, `cd/score`,
# `cd/validation_score` and `cd/validation_evaluate` are DISPATCH walls (the
# host handing programs to the device; a random-effect `cd/train` also waits
# once, for its solves' counts), and the host WAITS for the device once an
# update, in `cd/commit`: a validated update hands its validation scoring
# and evaluation over inside that stage, behind the guard's program and
# before the verdict is known (its span carries `deferred_validation`), and
# one fetch reads the guard's boolean, the solve's counts and the metrics,
# so the wall is the solve's, the scoring's and the evaluation's device work
# plus one round trip. `fit/final_evaluate`
# takes the descent's last validation as the returned model's evaluation
# (`CoordinateDescentResult.evaluation`; microseconds, no device work) and
# scores, evaluates and waits as `cd/validation_evaluate` does only where
# the descent evaluated nothing (a finished checkpoint resumed, every update
# rejected, a mesh-loss rollback). A stage that did not run in a fit reads
# 0.0.
SOLVE_STAGES = (
    "fit",
    "fit/revalidate",
    "fit/validation_prep",
    "fit/coordinates",
    "fit/descent",
    "coordinate_update",
    "cd/residual",
    "cd/train",
    "cd/score",
    "cd/commit",
    "cd/validation_score",
    "cd/validation_evaluate",
    "cd/checkpoint",
    "fit/final_evaluate",
    "fit/publish",
)
SOLVE_STAGE_PARENT = {
    "fit": None,
    "fit/revalidate": "fit",
    "fit/validation_prep": "fit",
    "fit/coordinates": "fit",
    "fit/descent": "fit",
    "coordinate_update": "fit/descent",
    "cd/residual": "coordinate_update",
    "cd/train": "coordinate_update",
    "cd/score": "coordinate_update",
    "cd/commit": "coordinate_update",
    "cd/validation_score": "cd/commit",
    "cd/validation_evaluate": "cd/commit",
    "cd/checkpoint": "fit/descent",
    "fit/final_evaluate": "fit",
    "fit/publish": "fit",
}

# Every key a fit_timing artifact must carry: the stage breakdown plus the
# residual, the top-level walls, the pack placement split (r06), the
# entity-sharding decision (r07) and the RE-assembly placement split (r09
# — where the entity-block build ran, mirroring the pack split).
FIT_TIMING_REQUIRED_KEYS = (
    *PREPARE_STAGES,
    "other",
    "prepare_s",
    "solve_s",
    "pack_device_s",
    "pack_host_s",
    "pack_path",
    "re_device_s",
    "re_host_s",
    "re_path",
    "sharding",
    # r10: the pod-scale robustness counters for THIS fit (a dict zipping
    # ROBUSTNESS_CLEAN_ZERO_KEYS) — all-zero on a clean fit.
    "robustness",
    # r14: the adaptive-runtime plan block (PLAN_BLOCK_KEYS) — always
    # present; {"active": False, ...} on an unplanned fit so a missing
    # block is loud, never ambiguous with "planner off".
    "plan",
    # ISSUE 27: this fit's wall per SOLVE_STAGES name, and its objective
    # evaluations per coordinate (line-search trials and Hessian-vector
    # products included), counted by the optimizers themselves.
    "stages_s",
    "fn_evals",
    # ISSUE 30: line-search trials per coordinate that failed the Armijo
    # test (L-BFGS evaluations beyond the first and one an iteration).
    "line_search_rejected",
    # ISSUE 40: per fixed effect solved with TRON, the Hessian-vector
    # products among its `fn_evals`; empty where no fixed effect solves
    # with TRON. ISSUE 42: a random effect's TRON solves too.
    "hv_evals",
    # ISSUE 42: per random effect solved with TRON, {"used", "paid"}: the
    # passes its entities needed and its batched loops executed times
    # their lanes; empty where none solves with TRON.
    "lane_passes",
    # ISSUE 36: bytes each device added to the all-reduce of a
    # sample-sharded fixed effect's value and gradient, per coordinate
    # (evaluations x 4 (d + 1)); empty on one device.
    "gradient_allreduce_bytes",
)

# ------------------------------------------------------------------- ingest
# Per-stage ingest breakdown recorded by read_game_dataset (r09 streaming
# data plane) and attached to the returned dataset as `ingest_timing`.
# The stages tile the ingest wall in a synchronous run; a streaming run
# records where the work happened (decode on the reader pool can sum past
# the wall it was hidden behind — that excess IS the overlap win).
INGEST_STAGES = ("decode", "assemble", "tags", "ell", "stash")

# Every key an ingest_timing artifact must carry: the stage breakdown plus
# the path taken and the chunk accounting that proves streaming engaged.
INGEST_TIMING_REQUIRED_KEYS = (
    *INGEST_STAGES,
    "other",
    "ingest_path",
    "streaming",
    "chunks",
)

# ------------------------------------------------------------------- serving
# Latency/quality metrics a serving run must report (batcher.metrics()).
SERVING_METRIC_KEYS = (
    "p50_ms",
    "p95_ms",
    "p99_ms",
    "qps",
    "cold_start_fraction",
    "recompiles_after_warmup",
)

# The sharding-decision block inside serving metrics (engine.metrics()
# zips exactly these, in this order — all present even on a single-tier
# replicated bundle so absence is loud). r10 appends the per-shard
# health keys: how many coefficient shards are currently LOST (serving
# degraded pinned-zero-row answers for their entities) and how many
# requests resolved through that degradation.
SERVING_SHARDING_KEYS = (
    "entity_sharded",
    "axis_size",
    "rows_per_shard",
    "hot_set_fraction",
    "all_to_all_bytes_per_batch",
    "shards_lost",
    "shard_loss_fallbacks",
)

# Robustness events that must be ZERO on a clean (un-faulted,
# un-overloaded) serving run — the clean-run zero contract (PR 5).
SERVING_CLEAN_ZERO_KEYS = (
    "shed",
    "deadline_missed",
    "circuit_opens",
    "fe_only_answers",
)

# Robustness events of the pod-scale mesh failure domain (ISSUE 10) that
# must be ZERO on a clean run: collective re-dispatches, per-shard
# staging retries, failed two-tier promotions, and watchdog trips — plus
# the live-elasticity events (ISSUE 13): mesh losses recovered mid-fit
# and reshard staging retries/rollbacks. The clean-run contract reads
# these from faults.COUNTERS; fit_timing ("robustness") and
# serving-summary.json ("robustness_counters") always carry every key so
# absence is loud.
ROBUSTNESS_CLEAN_ZERO_KEYS = (
    "collective_retries",
    "shard_upload_retries",
    "promote_failures",
    "watchdog_trips",
    "mesh_losses",
    "reshard_retries",
    "reshard_rollbacks",
    # ISSUE 16: delta-bundle applies rolled back to the old generation —
    # zero on a clean continuous-refresh loop.
    "delta_rollbacks",
    # ISSUE 17: whole-host losses in the multi-host process group and the
    # heartbeat misses that detected them — zero on a clean run (any
    # single-process run is trivially clean; a multi-host run is clean
    # only when every peer stayed live end to end).
    "host_losses",
    "host_heartbeat_misses",
    # ISSUE 18: shadow deployment — mirror submissions that degraded to
    # champion-only, label joins that failed (label dropped, champion
    # untouched), and challengers torn down on a regression verdict (or a
    # failed promotion). A clean run with a healthy challenger promotes
    # with all three at zero.
    "shadow_mirror_failures",
    "label_join_failures",
    "shadow_rollbacks",
    # ISSUE 19: autopilot — actions reverted because the post-action
    # contract probe regressed, and rules quarantined after their
    # rollback. A clean closed-loop run adapts without ever reverting.
    "autopilot_rollbacks",
    "autopilot_quarantines",
    # ISSUE 20: precision-tier ladder — a clean fit/replay never walks
    # the ladder, so demotions, restores, AND rollbacks are all zero;
    # a ladder drill asserts the exact non-zero counts it caused.
    "tier_demotions",
    "tier_restores",
    "tier_rollbacks",
)

# Top-level serving-summary.json keys written by cli/serve.py. r14
# appends the adaptive-runtime plan block (PLAN_BLOCK_KEYS), inactive on
# an unplanned replay; r15 appends the per-tenant block ({} on a
# single-tenant replay, one TENANT_BLOCK_KEYS dict per tenant under
# --tenant) so a missing block is loud, never ambiguous; r16 appends the
# bundle provenance block (BUNDLE_PROVENANCE_KEYS) so operators can audit
# what a swapped engine is actually running; r18 appends the shadow
# deployment block ({} on a replay without --shadow, SHADOW_BLOCK_KEYS
# otherwise); r19 appends the autopilot block ({} without --autopilot,
# AUTOPILOT_BLOCK_KEYS otherwise).
SERVING_SUMMARY_KEYS = (
    "num_requests",
    "failed_requests",
    "malformed_records",
    "serving",
    "health",
    "robustness_counters",
    "plan",
    "tenants",
    "provenance",
    "shadow",
    "autopilot",
)

# The served bundle's lineage block (ISSUE 16): every ServingBundle
# carries exactly these, stamped at from_model/from_artifact time and
# updated in place by each committed delta apply — so an operator reading
# serving-summary.json can tell a freshly full-fit engine from one that
# has absorbed N incremental deltas, and where the last delta came from.
BUNDLE_PROVENANCE_KEYS = (
    "origin",
    "generation",
    "deltas_applied",
    "last_delta_source",
    "last_delta_ts",
)

# -------------------------------------------------------------- multi-tenant
# Per-tenant metrics block (serving/tenancy.TenantRegistry.metrics() zips
# exactly these per tenant — the serving-summary "tenants" block
# consumes it; every key always present so absence is loud).
TENANT_BLOCK_KEYS = (
    "completed",
    "failed",
    "shed",
    "deadline_missed",
    "fe_only_answers",
    "degraded_batches",
    "cobatched_requests",
    "p50_ms",
    "p95_ms",
    "p99_ms",
    "state",
    "degraded_reasons",
    "circuit_state",
    "demoted",
    "device_bytes",
    "watchdog_trips",
    "tier",
)

# Per-tenant precision-ladder sub-block (ISSUE 20): nested under the
# tenant block's "tier" key — the tenant's current rung plus its ladder
# history, so serving-summary.json can audit HOW a tenant got to the
# precision it serves at. "tier" is the
# rung name ("f32"/"bf16"/"int8"; the host rung keeps the tenant's last
# quantized rung beside demoted=True), "quantized_coords" counts RE
# coordinates currently serving dequantized rows, and "quant_error_max"
# is the worst recorded per-coordinate relative round-trip error (None
# until the first quantization).
TIER_BLOCK_KEYS = (
    "tier",
    "quantized_coords",
    "demotions",
    "restores",
    "rollbacks",
    "quant_error_max",
)

# The characterized-parity contract (ISSUE 20): per-rung allclose
# tolerances for scores served from quantized RE rows, compared against
# the same tenant's f32 answers. THE one home for these numbers — the
# photon-lint `tolerance-pin` check flags any allclose-style tolerance
# literal outside this module, so the characterized contract cannot
# drift test-by-test. f32 pins zeros: an un-quantized tenant is still
# bitwise. int8 is per-row symmetric (scale = max|row|/127), so its
# worst case is half an LSB of the largest row entry — the atol term
# absorbs near-zero margins where rtol alone is meaningless.
TIER_TOLERANCES = {
    "f32": {"rtol": 0.0, "atol": 0.0},
    "bf16": {"rtol": 1e-2, "atol": 1e-3},
    "int8": {"rtol": 8e-2, "atol": 3e-2},
}

# The pallas_glm kernel-health smoke gate's discrimination thresholds
# (ops/pallas_glm.kernels_healthy): broken-kernel detection bars, NOT
# parity tolerances — the XLA reference itself runs bf16 MXU passes on
# TPU, so the f32-input bar sits at bf16 rounding level and the
# bf16-input bar at ~3x it. Pinned here for the same tolerance-pin
# reason as TIER_TOLERANCES.
PALLAS_GATE_TOLERANCES = {
    "f32": {"rtol": 1e-2},
    "bf16": {"rtol": 3e-2},
}

# chip_smoke.py's serving check: margins answered by `cli.serve` on the chip
# against a plain numpy float32 recomputation from the written model
# (fixed-effect dot + gathered random-effect rows + offset). Both sides are
# f32 multiply + per-row reduce — no MXU pass on the serving path
# (game/model.gathered_row_margins) — so they differ only by the order of
# at most ~200 additions per coordinate: a few ulp of an O(1) margin. A
# mis-resolved entity row or feature index is off by O(0.1-1).
CHIP_SMOKE_SERVING_TOLERANCE = {"rtol": 1e-5, "atol": 1e-5}

# Two DIFFERENT programs over the same data: a fit or a bucket program
# partitioned over a mesh against the same computation on one device.
# Sharding changes which partial sums exist and the order they combine in
# (psum over per-device partials, ring gathers, a per-shard then cross-shard
# reduce), so equality is numerical, not structural; bitwise equality
# between the SAME program on the same inputs (restore, replay) stays a
# separate, exact contract. "fit": coefficients and scores after an
# iterative solve, where a last-ulp difference in one gradient is amplified
# through line searches over the remaining iterations. "serve": one
# bucket program's margins (a gather and a row reduce — reduction-order
# noise only). chip_smoke.py --four-chips holds the chip to these, and
# tests/conftest.assert_sharded_close the sharded-vs-single tests on the
# CPU mesh.
SHARDED_VS_SINGLE_TOLERANCES = {
    "fit": {"rtol": 5e-3, "atol": 5e-4},
    "serve": {"rtol": 1e-5, "atol": 1e-5},
}

# ------------------------------------------------------- incremental refresh
# The delta-bundle manifest (serving/delta.DeltaBundle.manifest zips
# exactly these, ISSUE 16): what an incremental fit shipped to serving —
# the refresh journal and cli/refresh both persist it, so a delta that
# silently dropped a coordinate is loud.
DELTA_BUNDLE_KEYS = (
    "source",
    "mode",
    "coordinates",
    "delta_rows",
    "total_rows",
    "bytes",
)


# --------------------------------------------------------- shadow deployment
# The shadow block inside serving-summary.json (ISSUE 18):
# ShadowController.summary() zips exactly these — what challenger
# mirrored against which champion, how far the decision loop got
# (status: observing | promote_ready | promoting | promoted | rejected |
# closed), the evidence the
# last evaluated window carried, and the champion's serving generation
# (so a promotion is visible as the generation flip it performed).
# Every key always present so a quality-blind replay is loud, never
# silent.
SHADOW_BLOCK_KEYS = (
    "champion",
    "challenger",
    "status",
    "windows",
    "mirrored_requests",
    "mirror_failures",
    "label_join_failures",
    "champion_metric",
    "challenger_metric",
    "evaluator",
    "score_drift_p50",
    "generation",
)


# ---------------------------------------------------------------- autopilot
# The closed-loop controller block (ISSUE 19, photon_ml_tpu/autopilot/):
# Autopilot.summary() zips exactly these, and serving-summary.json
# carries the block under "autopilot" ({} on a run without --autopilot)
# so an operator can always tell open-loop from self-operating. Counts
# are cumulative over the controller's lifetime; "quarantined" lists the
# rules currently benched after a rollback (empty on a healthy loop).
AUTOPILOT_BLOCK_KEYS = (
    "status",
    "ticks",
    "rules",
    "decisions",
    "actions",
    "suppressed",
    "rollbacks",
    "quarantined",
    "tick_ms",
    "cooldown_s",
    "action_budget",
    "last_outcome",
)

# -------------------------------------------------------------------- sweep
# Per-trial timing record (the shape of the executor's TrialRecord export,
# hyperparameter/sweep.py): every evaluated trial reports its round,
# execution mode, wall seconds (stacked rounds amortize the one-dispatch
# round wall across their trials), value, and divergence-guard count.
SWEEP_TRIAL_KEYS = (
    "trial",
    "round",
    "mode",
    "seconds",
    "value",
    "diverged_steps",
)

# ------------------------------------------------------------------ journal
# The run journal (utils/telemetry.RunJournal, ISSUE 11): every JSONL
# line carries the common envelope keys plus EXACTLY its event type's
# schema fields — emit validates before writing, `validate_journal` and
# `cli/obs journal --validate` re-validate after the fact, and
# tests/test_telemetry.py round-trips every type. Append fields, don't
# reorder; adding an event type means adding its schema here first.
JOURNAL_LINE_KEYS = ("ts", "type")
JOURNAL_EVENT_SCHEMAS = {
    # -- training lifecycle (EventEmitter -> journal_listener) --
    "setup": ("args",),
    "fit_start": ("num_samples",),
    "sweep_config": ("index", "total"),
    "coordinate_update": ("iteration", "coordinate", "seconds", "accepted",
                          "fn_evals"),
    "checkpoint": ("step", "coordinate"),
    "fit_finish": ("num_configs", "best_metric"),
    "failure": ("error",),
    # -- a program JAX compiled, none read back (utils/compile_cache.py) --
    "program_compiled": ("program", "stage", "seconds"),
    # -- infra sites (emitted through the ambient journal) --
    "health_transition": ("from_state", "to_state", "reasons"),
    "bundle_swap": ("version", "outcome"),
    "fault_retry": ("label", "counter", "attempt", "error"),
    "fault_injected": ("site", "invocation"),
    "watchdog_trip": ("label",),
    "shard_loss": ("coordinate", "shard_index"),
    "shard_restage": ("coordinate", "shard_index", "bytes"),
    # -- live mesh elasticity (serving/reshard.py + elastic resume) --
    "reshard_start": ("old_shards", "new_shards", "moved_rows",
                      "moved_bytes"),
    "reshard_commit": ("old_shards", "new_shards", "version",
                       "restaged_bytes"),
    "reshard_rollback": ("old_shards", "new_shards", "reason"),
    "mesh_loss": ("iteration", "coordinate", "surviving_devices", "source"),
    # -- hyperparameter sweep lifecycle (SweepExecutor / cli/tune.py) --
    "trial_start": ("round", "trial", "mode"),
    "trial_finish": ("round", "trial", "mode", "seconds", "value",
                     "diverged_steps"),
    # -- adaptive runtime planner (planner/plan.install_plan) --
    "plan_decision": ("decision", "value", "source", "fallback"),
    # -- multi-tenant serving (serving/tenancy.TenantRegistry) --
    "tenant_admit": ("tenant", "device_bytes", "demoted_tenants"),
    "tenant_evict": ("tenant", "reason", "freed_bytes", "hot_rows"),
    "tenant_restore": ("tenant", "reason", "device_bytes"),
    "tenant_degraded": ("tenant", "reasons"),
    # -- incremental refresh (game/incremental.py + serving/delta.py) --
    "delta_fit_start": ("mode", "changed_coordinates", "delta_rows",
                        "total_rows"),
    "delta_fit_finish": ("mode", "changed_coordinates",
                         "carried_coordinates", "seconds", "max_rel_diff"),
    "delta_apply": ("version", "coordinates", "rows", "bytes", "source"),
    "delta_rollback": ("version", "reason"),
    # -- multi-host production mode (parallel/hostmesh.py, ISSUE 17) --
    "host_loss": ("host", "missed_beats", "num_hosts", "source"),
    "host_join": ("host", "num_hosts", "restaged_rows"),
    "multihost_barrier": ("name", "host", "num_hosts", "seconds"),
    # -- shadow deployment & online evaluation (serving/shadow.py, ISSUE 18) --
    "shadow_start": ("champion", "challenger", "window_size", "min_windows",
                     "mirror_fraction"),
    "shadow_window": ("champion", "challenger", "window", "rows",
                      "champion_metric", "challenger_metric", "evaluator",
                      "healthy"),
    "shadow_verdict": ("champion", "challenger", "decision", "windows",
                       "champion_metric", "challenger_metric", "evaluator",
                       "reason"),
    "shadow_promote": ("champion", "challenger", "version"),
    "shadow_rollback": ("champion", "challenger", "reason"),
    # -- closed-loop autoscaling (photon_ml_tpu/autopilot/, ISSUE 19) --
    "autopilot_decision": ("rule", "action", "evidence", "outcome"),
    "autopilot_rollback": ("rule", "action", "reason"),
    "rule_quarantined": ("rule", "reason", "rollbacks"),
    # -- precision-tier ladder (serving/tenancy.py, ISSUE 20) --
    "tier_demote": ("tenant", "from_tier", "to_tier", "reason",
                    "freed_bytes", "evidence"),
    "tier_restore": ("tenant", "from_tier", "to_tier", "reason",
                     "repinned_bytes", "evidence"),
}

# ------------------------------------------------------------------- profile
# The persisted run profile (utils/telemetry.build_profile/read_profile):
# the machine-readable artifact the adaptive-runtime planner consumes.
# Every profile carries the common keys; fit and serve runs add their
# kind's sections. read_profile enforces these loudly.
PROFILE_REQUIRED_KEYS = (
    "kind",
    "wall_s",
    "stages",
    "dispatch",
    "bucket_shapes",
    "device_topology",
    "roofline",
    "metrics",
)
PROFILE_FIT_KEYS = (*PROFILE_REQUIRED_KEYS, "fit_timing", "ingest")
PROFILE_SERVE_KEYS = (*PROFILE_REQUIRED_KEYS, "serving")

# ------------------------------------------------------------------- planner
# The adaptive-runtime plan (ISSUE 14, photon_ml_tpu/planner/). Every
# fit_timing and serving-summary.json carries a `plan` block zipping
# PLAN_BLOCK_KEYS; each entry of its `decisions` list zips
# PLAN_DECISION_KEYS. Profiles written by planned runs ALSO carry the
# block (top-level "plan" key) so decisions round-trip through
# read_profile — but it is deliberately NOT in PROFILE_*_KEYS: an
# r06-era profile (pre-planner) must still load for the cold-start path.
PLAN_BLOCK_KEYS = ("active", "source", "profile", "decisions")
PLAN_DECISION_KEYS = ("decision", "value", "source", "evidence", "fallback")


# Every schema this module exports, for the analyzer's drift check and
# for tests that want to iterate all contracts.
ALL_CONTRACTS = {
    "PREPARE_STAGES": PREPARE_STAGES,
    "SOLVE_STAGES": SOLVE_STAGES,
    "FIT_TIMING_REQUIRED_KEYS": FIT_TIMING_REQUIRED_KEYS,
    "INGEST_STAGES": INGEST_STAGES,
    "INGEST_TIMING_REQUIRED_KEYS": INGEST_TIMING_REQUIRED_KEYS,
    "SERVING_METRIC_KEYS": SERVING_METRIC_KEYS,
    "SERVING_SHARDING_KEYS": SERVING_SHARDING_KEYS,
    "SERVING_CLEAN_ZERO_KEYS": SERVING_CLEAN_ZERO_KEYS,
    "ROBUSTNESS_CLEAN_ZERO_KEYS": ROBUSTNESS_CLEAN_ZERO_KEYS,
    "SERVING_SUMMARY_KEYS": SERVING_SUMMARY_KEYS,
    "BUNDLE_PROVENANCE_KEYS": BUNDLE_PROVENANCE_KEYS,
    "TENANT_BLOCK_KEYS": TENANT_BLOCK_KEYS,
    "TIER_BLOCK_KEYS": TIER_BLOCK_KEYS,
    "DELTA_BUNDLE_KEYS": DELTA_BUNDLE_KEYS,
    "SHADOW_BLOCK_KEYS": SHADOW_BLOCK_KEYS,
    "AUTOPILOT_BLOCK_KEYS": AUTOPILOT_BLOCK_KEYS,
    "SWEEP_TRIAL_KEYS": SWEEP_TRIAL_KEYS,
    "JOURNAL_LINE_KEYS": JOURNAL_LINE_KEYS,
    "PROFILE_REQUIRED_KEYS": PROFILE_REQUIRED_KEYS,
    "PROFILE_FIT_KEYS": PROFILE_FIT_KEYS,
    "PROFILE_SERVE_KEYS": PROFILE_SERVE_KEYS,
    "PLAN_BLOCK_KEYS": PLAN_BLOCK_KEYS,
    "PLAN_DECISION_KEYS": PLAN_DECISION_KEYS,
}
