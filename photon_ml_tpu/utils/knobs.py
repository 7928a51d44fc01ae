"""Central typed registry for the PHOTON_* environment knobs.

The reference stack carries its configuration through typed Scala case
classes (photon-api GameTrainingDriver params), so a knob cannot exist
without a declared type, default, and docstring. The TPU port grew its
knobs one `os.environ.get` at a time — ~27 raw reads scattered across the
data plane, kernels, solver and serving tier by r07 — exactly the
"untracked config knobs silently rot tuning decisions" failure mode the
Spark-ML performance study (PAPERS.md) documents. This module is the
single choke point:

* `KNOBS` — every `PHOTON_*` env var the system reads, with name, type,
  default, and a one-line doc. Registration is closed: `get_knob` on an
  unregistered name raises, and the static analyzer's `knob-registry`
  check (photon_ml_tpu/analysis/) fails the build on any raw
  `os.environ` read of a `PHOTON_*` name outside this file — so a knob
  cannot be added without landing here, and cannot land here without
  appearing in README's knob table (also machine-checked).

* `get_knob(name)` — the one accessor. Typed parsing with *lenient*
  validation (the kernel modules' long-standing contract): a malformed
  value logs a warning and falls back to the default instead of making
  the package unimportable for code paths that never touch the knob.
  Empty/unset always means the default.

* `python -m photon_ml_tpu.utils.knobs --table` — prints the README
  markdown table from the registry (the same source of truth the
  analyzer verifies README against), mirroring
  `python -m photon_ml_tpu.utils.faults --list-sites`.

Bool knobs parse canonically: 1/true/yes/on and 0/false/no/off
(case-insensitive); anything else warns and reads as the default.
Tri-state knobs (auto | on | off, e.g. PHOTON_DEVICE_PACK) stay `str`
typed with the empty string meaning "auto" — their policy lives at the
call site where the hardware context is.

This module imports only the stdlib, so it is safe to read from
conftest-style code that must run before jax initializes a backend.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Dict, Optional, Tuple, Union

logger = logging.getLogger(__name__)

Value = Union[str, int, float, bool]

_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


@dataclasses.dataclass(frozen=True)
class Knob:
    """One registered environment knob: its name, python type, default,
    and the one-line doc the README table is generated from."""

    name: str
    type: type
    default: Value
    doc: str
    choices: Optional[Tuple[str, ...]] = None  # str knobs: legal values

    def parse(self, raw: str) -> Value:
        """Parse an env string leniently: empty -> default; malformed ->
        warn + default (a bad knob must never make the package
        unimportable for code that never touches it)."""
        raw = raw.strip()
        if raw == "":
            return self.default
        if self.type is bool:
            low = raw.lower()
            if low in _TRUE:
                return True
            if low in _FALSE:
                return False
            logger.warning(
                "%s=%r: expected one of %s; using default %r",
                self.name,
                raw,
                "/".join((*_TRUE, *_FALSE)),
                self.default,
            )
            return self.default
        if self.type in (int, float):
            try:
                return self.type(raw)
            except ValueError:
                logger.warning(
                    "ignoring malformed %s=%r (default %r)",
                    self.name,
                    raw,
                    self.default,
                )
                return self.default
        value = raw.strip().lower() if self.choices is not None else raw
        if self.choices is not None and value not in self.choices:
            logger.warning(
                "%s=%r: expected one of %s; using default %r",
                self.name,
                raw,
                sorted(self.choices),
                self.default,
            )
            return self.default
        return value


KNOBS: Dict[str, Knob] = {}


def _register(
    name: str,
    type_: type,
    default: Value,
    doc: str,
    choices: Optional[Tuple[str, ...]] = None,
) -> None:
    if not name.startswith("PHOTON_"):
        raise ValueError(f"knob {name!r} must be PHOTON_-prefixed")
    if name in KNOBS:
        raise ValueError(f"duplicate knob registration: {name!r}")
    if not isinstance(default, type_):
        raise TypeError(f"{name}: default {default!r} is not {type_.__name__}")
    KNOBS[name] = Knob(name, type_, default, doc, choices)


# ---------------------------------------------------------------- data plane
_register(
    "PHOTON_PIPELINE",
    str,
    "",
    "Host data-plane overlap: 1 forces threaded decode/pack/upload overlap, "
    "0 forces synchronous; empty = auto (on when >1 effective core).",
    choices=("", *_TRUE, *_FALSE),
)
_register(
    "PHOTON_HOST_THREADS",
    int,
    -1,
    "Usable host cores for the pipeline/prepare pools; unset = auto from "
    "the scheduler affinity mask (cgroup-aware), explicit values clamp "
    "to >= 1 (so 0 forces single-threaded).",
)
_register(
    "PHOTON_INGEST_THREADS",
    int,
    0,
    "Native Avro decode worker count; 0 = hardware auto.",
)
_register(
    "PHOTON_PACK_THREADS",
    int,
    -1,
    "Cores the native bucketed pack may shard over; unset = effective "
    "host parallelism, explicit values clamp to >= 1 (so 0 forces a "
    "single-threaded pack).",
)
_register(
    "PHOTON_STREAM_INGEST",
    str,
    "",
    "Streaming chunked ingest (decode of chunk k+1 overlaps assembly of "
    "chunk k): 1 forces, 0 forces the monolithic read; empty = auto (on "
    "when >1 effective core).",
    choices=("", *_TRUE, *_FALSE),
)
_register(
    "PHOTON_STREAM_CHUNK_ROWS",
    int,
    262_144,
    "Rows per streamed ingest chunk on the pure-Python codec path "
    "(bounds decoded-record residency); the native path chunks per "
    "container file.",
)
_register(
    "PHOTON_DEVICE_ASSEMBLY",
    str,
    "",
    "Random-effect entity-block assembly + index-map projection on device "
    "(stable-sort/segment/scatter XLA programs): 1 forces, 0 forces the "
    "host path; empty = auto (on for tpu/gpu backends).",
    choices=("", *_TRUE, *_FALSE),
)
_register(
    "PHOTON_DEVICE_PACK",
    str,
    "",
    "Bucketed placement on device (one XLA program): 1 forces, 0 forces "
    "host; empty = auto (on for tpu/gpu backends).",
    choices=("", *_TRUE, *_FALSE),
)
_register(
    "PHOTON_SPARSE_LAYOUT",
    str,
    "",
    "Sparse level-1 layout: rowalign|grouped force a layout; empty/auto = "
    "Poisson-adaptive economics per shard (data/bucketed.choose_layout).",
    choices=("", "auto", "rowalign", "row_aligned", "aligned", "grouped", "feature", "legacy"),
)
_register(
    "PHOTON_DISABLE_NATIVE",
    bool,
    False,
    "Kill switch for the native C library (Avro/libsvm/pack); honored per "
    "call, not only at first load.",
)

# ------------------------------------------------------------------- kernels
_register(
    "PHOTON_DISABLE_PALLAS",
    bool,
    False,
    "Kill switch for the fused Pallas objective kernels; affects programs "
    "traced after the flip.",
)
_register(
    "PHOTON_PALLAS_TILE",
    int,
    1024,
    "Dense kernel row-tile height; multiple of 128, capped at the "
    "measured-good 1024.",
)
_register(
    "PHOTON_PALLAS_PRECISION",
    str,
    "hilo",
    "Dense MXU operand precision: hilo (two bf16 passes ~= f32) or "
    "highest|high|default (classic lax precisions).",
    choices=("hilo", "highest", "high", "default"),
)
_register(
    "PHOTON_SPARSE_PRECISION",
    str,
    "hilo",
    "Sparse kernel MXU operand precision: hilo|default|highest.",
    choices=("hilo", "default", "highest"),
)
_register(
    "PHOTON_DENSE_BF16X",
    bool,
    True,
    "Pre-scale dense f32 features into bf16-exact space so hilo runs one "
    "bf16 MXU pass; 0 opts out.",
)

# -------------------------------------------------------------------- solver
_register(
    "PHOTON_SWEEP_SCAN",
    bool,
    True,
    "Scan-dispatch the random-effect bucket sweep (one lax.scan program "
    "per block shape); 0 reverts to the per-bucket dispatch loop.",
)
_register(
    "PHOTON_SWEEP_TRIAL_STACK",
    str,
    "",
    "Trial-stacked hyperparameter sweep evaluation (k reg-weight trials "
    "scanned inside ONE XLA dispatch): 1 forces, 0 disables (shard-group "
    "or serial evaluation instead); empty = auto (on when every "
    "coordinate's store is replicated).",
    choices=("", *_TRUE, *_FALSE),
)
_register(
    "PHOTON_SWEEP_MAX_STACK",
    int,
    8,
    "Trials per stacked sweep dispatch; larger candidate batches split "
    "into rounds of at most this many (further tightened by the HBM "
    "charge when the device reports a bytes limit).",
)
_register(
    "PHOTON_SWEEP_SHARD_GROUPS",
    int,
    0,
    "Trial groups the device fleet partitions into for shard-group sweep "
    "scheduling (one concurrent trial per group; groups of >1 device run "
    "the entity-sharded sweep inside the group); 0 = auto (one group per "
    "device).",
)
_register(
    "PHOTON_SOLVE_RETRIES",
    int,
    1,
    "Extra solve attempts the divergence guard grants a non-finite "
    "coordinate update before keeping last-good.",
)

# ------------------------------------------------------------ failure domain
_register(
    "PHOTON_FAULTS",
    str,
    "",
    'Deterministic fault-injection plan, e.g. "decode:1,upload:2,'
    'solve@3,pack:p0.25" (see utils/faults.py).',
)
_register(
    "PHOTON_FAULTS_SEED",
    int,
    0,
    "Seed for probabilistic fault sites (site:pX) — reproducible chaos "
    "schedules.",
)
_register(
    "PHOTON_RETRY_MAX_ATTEMPTS",
    int,
    3,
    "Bounded-backoff retry attempts for transient failures (min 1).",
)
_register(
    "PHOTON_RETRY_BASE_DELAY_S",
    float,
    0.05,
    "Retry backoff base delay in seconds (doubles per attempt).",
)
_register(
    "PHOTON_RETRY_MAX_DELAY_S",
    float,
    2.0,
    "Retry backoff delay cap in seconds.",
)
_register(
    "PHOTON_WATCHDOG_MS",
    int,
    0,
    "Hang-watchdog deadline (ms) armed around scanned-sweep and serving "
    "device dispatches; an over-deadline dispatch raises a typed "
    "DeviceHang (sweep re-dispatch / serving FE-only degradation). 0 = "
    "off.",
)
_register(
    "PHOTON_COLLECTIVE_RETRIES",
    int,
    1,
    "Extra re-dispatches a failed mesh collective program gets before "
    "the sweep degrades to the bitwise-equal per-bucket loop.",
)
_register(
    "PHOTON_SHARD_UPLOAD_RETRIES",
    int,
    2,
    "Extra attempts a failed per-shard serving staging/restage gets "
    "before the failure surfaces (hot-swap rollback / shard stays "
    "degraded).",
)
_register(
    "PHOTON_RESHARD_RETRIES",
    int,
    2,
    "Extra attempts a failed per-shard upload gets during a live mesh "
    "reshard before the whole reshard rolls back to the old generation.",
)
_register(
    "PHOTON_REBALANCE_MIN_PROMOTIONS",
    int,
    2,
    "Observed two-tier promotions a coefficient row needs before a "
    "hot-row rebalance plan counts it as hot (serving/reshard.py).",
)

# ------------------------------------------------------------------- serving
_register(
    "PHOTON_SERVING_ENTITY_SHARD",
    bool,
    False,
    "Stage serving RE matrices row-sharded over all local devices "
    "(no-op with one device).",
)
_register(
    "PHOTON_SERVING_HOT_ROWS",
    int,
    0,
    "Two-tier serving store hot-set size (rows kept in HBM); 0 = "
    "single-tier (everything resident).",
)
_register(
    "PHOTON_SERVING_HBM_BUDGET_BYTES",
    int,
    0,
    "HBM budget a bundle hot-swap must fit in; 0 = use the device's "
    "reported bytes_limit (or skip the check where unknown).",
)
_register(
    "PHOTON_TENANT_MAX_PENDING",
    int,
    64,
    "Default per-tenant admission quota in the multi-tenant registry "
    "(bounded pending requests per tenant; submits past it shed with a "
    "typed Overloaded naming the tenant).",
)
_register(
    "PHOTON_TENANT_HBM_FRACTION",
    float,
    1.0,
    "Fraction of the device HBM budget the multi-tenant fleet may pin; "
    "admission past it demotes the coldest READY tenant's RE rows to "
    "the host tier (never fails the tenant) before refusing.",
)

# ------------------------------------------------------------------- refresh
_register(
    "PHOTON_REFRESH_BATCH_ROWS",
    int,
    4096,
    "Continuous-refresh loop (cli/refresh): target rows per streamed "
    "delta batch before triggering an incremental fit + delta swap; "
    "smaller batches trade solve efficiency for data->served freshness.",
)
_register(
    "PHOTON_REFRESH_MAX_DELTA_FRACTION",
    float,
    0.5,
    "Incremental fit escape hatch (game/incremental.py): when a delta "
    "batch churns more than this fraction of the merged dataset's rows, "
    "the delta path forces a warm-started FULL refit — past that point "
    "re-solving per changed entity costs more than one fused solve.",
)

# ----------------------------------------------------------------- shadow
_register(
    "PHOTON_SHADOW_MIN_WINDOWS",
    int,
    3,
    "Shadow deployment (serving/shadow): consecutive evaluation windows "
    "that must agree before a verdict fires — ALL healthy promotes, ALL "
    "regressed rejects, a mixed run holds (the hysteresis band between "
    "the two).",
)
_register(
    "PHOTON_SHADOW_REGRESSION_TOL",
    float,
    0.02,
    "Shadow deployment: a window is regressed when the challenger's "
    "primary metric is worse than the champion's by more than this "
    "(direction-aware — AUC down or RMSE up); the same tolerance a "
    "threshold means offline, because online windows run the exact "
    "jitted EvaluationSuite metric programs.",
)
_register(
    "PHOTON_SHADOW_COOLDOWN_S",
    float,
    0.0,
    "Shadow deployment: minimum seconds between shadow start (or the "
    "last verdict) and the next verdict — lets windows accumulate past "
    "a transient before actuating; 0 disables the cooldown.",
)
_register(
    "PHOTON_SHADOW_MIRROR_FRACTION",
    float,
    1.0,
    "Shadow deployment: fraction of champion traffic mirrored to the "
    "challenger tenant (deterministic credit accumulator, no RNG); 1.0 "
    "mirrors everything, 0.25 every fourth request.",
)

# --------------------------------------------------------------- autopilot
_register(
    "PHOTON_AUTOPILOT_MS",
    int,
    500,
    "Closed-loop autoscaling (photon_ml_tpu/autopilot/): control-loop "
    "tick period in milliseconds — each tick snapshots the sensors and "
    "evaluates every armed ControlRule against fresh evidence.",
)
_register(
    "PHOTON_AUTOPILOT_MAX_ACTIONS",
    int,
    4,
    "Autopilot: bounded-actions budget — the most actuations the "
    "controller may apply within one cooldown window; rules that fire "
    "past the budget are journaled as suppressed, never applied.",
)
_register(
    "PHOTON_AUTOPILOT_COOLDOWN_S",
    float,
    2.0,
    "Autopilot: per-rule cooldown — minimum seconds between two "
    "actuations of the SAME rule (and the width of the global action-"
    "budget window), so the loop settles between interventions instead "
    "of oscillating; 0 disables the cooldown.",
)

# --------------------------------------------------------- precision tiers
_register(
    "PHOTON_TIER_LADDER",
    bool,
    False,
    "Precision-tier graceful degradation (ISSUE 20): 1 makes the HBM "
    "pressure valve and the autopilot's hbm-demote rule walk the "
    "f32 -> bf16 -> int8 -> host ladder (quantize-in-place before host-"
    "tier demotion); 0 (default) keeps the PR 15 all-or-nothing host "
    "demotion and the bitwise serving contract. Opt-in because a "
    "quantized tenant answers under a CHARACTERIZED tolerance "
    "(contracts.TIER_TOLERANCES), not bitwise.",
)
_register(
    "PHOTON_TIER_BF16_PRESSURE",
    float,
    0.85,
    "Precision ladder: HBM pressure (pinned bytes / fleet budget) above "
    "which the autopilot's ladder-aware hbm-demote rule quantizes the "
    "coldest f32 tenant's RE rows to bf16 (the first, cheapest rung).",
)
_register(
    "PHOTON_TIER_INT8_PRESSURE",
    float,
    0.92,
    "Precision ladder: HBM pressure above which a bf16 tenant steps down "
    "to int8 rows (per-row symmetric scales); past int8 the only rung "
    "left is the PR 15 host tier. Must be >= PHOTON_TIER_BF16_PRESSURE "
    "for the ladder to walk in order.",
)
_register(
    "PHOTON_TIER_INT8_ERROR_CEILING",
    float,
    0.1,
    "Precision ladder: refuse an int8 quantization whose measured worst "
    "per-coordinate relative round-trip error exceeds this ceiling — the "
    "tenant stays at bf16 and pressure relief falls through to the host "
    "tier instead of serving answers outside the characterized "
    "tolerance.",
)

# ------------------------------------------------------------------- planner
_register(
    "PHOTON_PLAN",
    str,
    "",
    "Adaptive runtime planner (photon_ml_tpu/planner/): 1 forces planning "
    "(from PHOTON_PLAN_PROFILE, else a fast startup calibration), 0 "
    "disables it entirely; empty = auto (plan only when a profile is "
    "supplied). Explicit PHOTON_* knobs always override plan decisions.",
    choices=("", *_TRUE, *_FALSE),
)
_register(
    "PHOTON_PLAN_PROFILE",
    str,
    "",
    "Path to a persisted run profile (telemetry.write_profile / cli "
    "--profile) the planner consumes; a profile from a mismatched device "
    "topology refuses loudly naming the field.",
)

# ------------------------------------------------------------- observability
_register(
    "PHOTON_TRACE",
    bool,
    False,
    "Span tracing (utils/telemetry.py): 1 records spans across the "
    "worker fleet and exports Chrome trace-event JSON (Perfetto-"
    "loadable) from the CLI drivers; 0 (default) keeps span() a no-op.",
)

# ---------------------------------------------------------- multihost / test
_register(
    "PHOTON_MH_DATA",
    str,
    "",
    "Scratch directory handshake written by the multihost dryrun launcher "
    "for its worker processes; never set by hand.",
)
_register(
    "PHOTON_MULTIHOST",
    int,
    0,
    "Multi-host production mode (parallel/hostmesh.py): the number of "
    "OS-process hosts a `--multihost N` run spans; 0 = single-process. "
    "Set by the supervisor for its workers; the CLI flag is the "
    "operator-facing switch.",
)
_register(
    "PHOTON_HOST_HEARTBEAT_MS",
    int,
    500,
    "Host-liveness heartbeat period (ms) in multi-host mode; a peer whose "
    "beat counter stalls for hostmesh.MISS_THRESHOLD (20) consecutive "
    "periods is declared lost (typed HostLoss, supervisor relaunch on the "
    "survivor set). The generous threshold rides out XLA compilation "
    "stalls; lower the period, not the threshold, for faster detection.",
)
_register(
    "PHOTON_HOST_LOSS_RETRIES",
    int,
    1,
    "Whole-host losses a multi-host supervisor absorbs before giving up "
    "(each costs one relaunch on the survivor set + one repeated sweep).",
)
_register(
    "PHOTON_TEST_PLATFORM",
    str,
    "cpu",
    "Backend the test harness forces before jax init (tests/conftest.py).",
)


def get_knob(name: str, raw: Optional[str] = None) -> Value:
    """Read knob `name` from the environment (or parse `raw` when given),
    returning its typed value. Raises KeyError for unregistered names —
    the registry is the closed set of knobs this system admits."""
    knob = KNOBS.get(name)
    if knob is None:
        raise KeyError(
            f"unregistered knob {name!r} — add it to "
            f"photon_ml_tpu.utils.knobs.KNOBS (known: {len(KNOBS)} knobs)"
        )
    if raw is None:
        raw = os.environ.get(name, "")
    return knob.parse(raw)


def knob_is_set(name: str) -> bool:
    """True when the knob is EXPLICITLY set (non-empty) in the
    environment — the planner's knob-beats-plan precedence test (an
    operator who typed a PHOTON_* value wins over any plan decision).
    Raises KeyError for unregistered names like get_knob."""
    if name not in KNOBS:
        raise KeyError(
            f"unregistered knob {name!r} — add it to "
            f"photon_ml_tpu.utils.knobs.KNOBS (known: {len(KNOBS)} knobs)"
        )
    return os.environ.get(name, "").strip() != ""


def readme_table() -> str:
    """The README markdown knob table, generated from the registry (the
    analyzer's knob-registry check requires every registered name to
    appear in README; regenerate with `--table` after editing)."""
    rows = ["| Knob | Type | Default | What it does |", "| --- | --- | --- | --- |"]
    for name in sorted(KNOBS):
        k = KNOBS[name]
        default = "`(empty)`" if k.default == "" else f"`{k.default}`"
        rows.append(f"| `{name}` | {k.type.__name__} | {default} | {k.doc} |")
    return "\n".join(rows)


def main(argv=None) -> int:
    """`python -m photon_ml_tpu.utils.knobs --table`: print the registry
    as the README markdown table (mirrors utils.faults --list-sites)."""
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m photon_ml_tpu.utils.knobs",
        description="Inspect the typed PHOTON_* knob registry.",
    )
    p.add_argument(
        "--table",
        action="store_true",
        help="print the registry as the README markdown table",
    )
    args = p.parse_args(argv)
    if not args.table:
        p.print_help()
        return 2
    print(readme_table())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
