"""The typed runtime plan: decisions, precedence, and the ambient install.

Photon ML inherited Spark's pathology of hand-tuned runtime knobs — the
Spark-ML performance study (PAPERS.md) measures exactly our knob set
(partitioning/layout, batch granularity, host-vs-executor routing)
dominating end-to-end cost, and Flare's whole-pipeline-compilation thesis
argues those decisions should be made once, from measured cost, per
hardware. This module is the decision SUBSTRATE: a `Plan` is a typed set
of `PlanDecision`s (name, chosen value, source, the evidence that chose
it, and the default it displaced), built by `photon_ml_tpu.planner.rules`
from a persisted run profile (utils/telemetry.read_profile) or a startup
calibration, installed process-ambient, and consulted by every site that
used to hard-code the quantity:

    value = planner.planned_value("ingest_chunk_rows")

Precedence is fixed and auditable: an EXPLICITLY SET `PHOTON_*` knob
always wins over the plan (recorded as `source: "knob"`), the plan wins
over the built-in default, and with no plan installed every site returns
exactly the default it returned before the planner existed — `PHOTON_PLAN=0`
(or simply never supplying a profile) is bitwise-identical to the
pre-planner tree by construction.

Every fit and serving run records the active plan as a `plan` block
(contracts.PLAN_BLOCK_KEYS) in `fit_timing` / `serving-summary.json`, and
`install_plan` journals one `plan_decision` event per decision so
`cli/obs journal --validate` covers planned runs.

`DEFAULTS` below is the ONE home for the planned-quantity constants; the
static analyzer's `planner-constant` check fails the build when a planned
quantity is re-hard-coded as a magic number anywhere else in the package.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
from typing import Dict, Optional

from photon_ml_tpu.utils.contracts import (
    PLAN_BLOCK_KEYS,
    PLAN_DECISION_KEYS,
)
from photon_ml_tpu.utils.knobs import (
    _FALSE,
    _TRUE,
    KNOBS,
    get_knob,
    knob_is_set,
)

logger = logging.getLogger(__name__)


class PlanTopologyError(ValueError):
    """A profile measured on different hardware must not silently plan
    this run: the refusal names the mismatching topology field."""


# The planned quantities and their built-in defaults — the values every
# consulting site used before the planner existed, so an absent plan is
# bitwise-identical to the pre-planner tree. Knob-backed quantities
# (KNOB_FOR) take their default from the typed knob registry instead so
# the two sources cannot drift.
DEFAULTS: Dict[str, object] = {
    # Host data plane: how many upcoming coordinates the coordinate-
    # descent loop prefetches while the current one solves.
    "prefetch_depth": 1,
    # RE sweep fusion: max same-shape buckets fused into one lax.scan
    # program (0 = unbounded, today's behavior: one program per shape).
    "scan_fusion_max": 0,
    # RE bucket shape set the profile proved on this hardware (list of
    # [entities, capacity] pairs per coordinate); consulted by the scan
    # grouping to fuse proven shapes unboundedly while novel shapes
    # chunk conservatively. Empty = no evidence, everything fuses.
    "re_bucket_shapes": {},
    # Serving: the compiled bucket ceiling (bucket set = the power-of-two
    # ladder up to it) and the micro-batcher's partial-batch flush wait.
    "serving_max_batch": 256,
    "serving_max_wait_ms": 2.0,
}

# Scan-fuse cap for RE bucket shapes the plan's profile never proved on
# this hardware: a novel shape's first dispatch (fresh compile, unknown
# cost) runs in small chunks so a failure/hang costs one group. Proven
# shapes (re_bucket_shapes) fuse per scan_fusion_max.
NOVEL_SHAPE_FUSE = 8

# Decision -> the PHOTON_* knob whose EXPLICIT setting overrides the plan
# (and whose registry default is the decision's fallback).
KNOB_FOR: Dict[str, str] = {
    "ingest_chunk_rows": "PHOTON_STREAM_CHUNK_ROWS",
    "sparse_layout": "PHOTON_SPARSE_LAYOUT",
    "pack_routing": "PHOTON_DEVICE_PACK",
    "assembly_routing": "PHOTON_DEVICE_ASSEMBLY",
    # Continuous refresh (ISSUE 16): how many streamed rows to batch
    # before an incremental fit + delta swap, and how much churn the
    # delta path absorbs before forcing a warm full refit.
    "refresh_batch_rows": "PHOTON_REFRESH_BATCH_ROWS",
    "refresh_max_delta_fraction": "PHOTON_REFRESH_MAX_DELTA_FRACTION",
    # Precision ladder (ISSUE 20): the HBM-pressure thresholds at which
    # the autopilot quantizes a tenant down one rung.
    "tier_bf16_pressure": "PHOTON_TIER_BF16_PRESSURE",
    "tier_int8_pressure": "PHOTON_TIER_INT8_PRESSURE",
}

# Knob-value -> decision-vocabulary normalizers: tri-state str knobs
# store "" for "auto" and accept the registry's bool spellings (imported
# from utils/knobs so a new spelling there cannot silently drift past
# these maps); the decision vocabulary says "auto"/"device"/"host"
# (routing) and "auto"/"rowalign"/"grouped" (layout) so plan blocks read
# unambiguously.


def _norm_routing(raw: object) -> str:
    low = str(raw).strip().lower()
    if low in _TRUE:
        return "device"
    if low in _FALSE:
        return "host"
    return "auto"


def _norm_layout(raw: object) -> str:
    low = str(raw).strip().lower()
    if low in ("rowalign", "row_aligned", "aligned"):
        return "rowalign"
    if low in ("grouped", "feature", "legacy"):
        return "grouped"
    return "auto"


_NORMALIZE = {
    "pack_routing": _norm_routing,
    "assembly_routing": _norm_routing,
    "sparse_layout": _norm_layout,
}


def normalize(name: str, value: object) -> object:
    fn = _NORMALIZE.get(name)
    return value if fn is None else fn(value)


def default_for(name: str) -> object:
    """The value a consulting site gets with no plan installed — knob
    registry default for knob-backed decisions, DEFAULTS otherwise."""
    knob = KNOB_FOR.get(name)
    if knob is not None:
        return normalize(name, KNOBS[knob].default)
    if name not in DEFAULTS:
        raise KeyError(
            f"unknown planned quantity {name!r} "
            f"(known: {sorted((*DEFAULTS, *KNOB_FOR))})"
        )
    return DEFAULTS[name]


@dataclasses.dataclass(frozen=True)
class PlanDecision:
    """One planned quantity: what was chosen, by what, from what."""

    decision: str
    value: object
    source: str  # "profile" | "calibration" | "knob" | "default"
    evidence: Dict[str, object]
    fallback: object  # the default the chosen value displaced

    def as_dict(self) -> Dict[str, object]:
        return {k: getattr(self, k) for k in PLAN_DECISION_KEYS}


@dataclasses.dataclass(frozen=True)
class Plan:
    """A typed runtime plan: the decision set plus its provenance."""

    source: str  # "profile" | "calibration"
    profile_path: Optional[str]
    topology: Dict[str, object]
    decisions: Dict[str, PlanDecision]

    # NOTE: deliberately no per-plan value accessor — planned_value() is
    # the ONE precedence implementation (knob > plan > default); a
    # plan-local lookup would silently skip operator knob overrides.

    def block(self) -> Dict[str, object]:
        """The `plan` block fit_timing / serving-summary.json carry
        (contracts.PLAN_BLOCK_KEYS, in order)."""
        return dict(
            zip(
                PLAN_BLOCK_KEYS,
                (
                    True,
                    self.source,
                    self.profile_path,
                    [
                        self.decisions[k].as_dict()
                        for k in sorted(self.decisions)
                    ],
                ),
            )
        )


def inactive_block() -> Dict[str, object]:
    """The `plan` block of an unplanned run — always present so a missing
    block is loud, never ambiguous with 'planner off'."""
    return dict(zip(PLAN_BLOCK_KEYS, (False, "off", None, [])))


# ------------------------------------------------------------ ambient plan
# One plan per process, installed by the CLI drivers / estimator startup
# and consulted by the decision sites. A module global guarded by
# a lock (install/uninstall only; reads are a single attribute load).
_LOCK = threading.Lock()
_ACTIVE: Optional[Plan] = None


def install_plan(plan: Plan) -> Plan:
    """Make `plan` the process-ambient plan and journal every decision
    (one `plan_decision` event each — cli/obs journal --validate covers
    planned runs)."""
    global _ACTIVE
    from photon_ml_tpu.utils import telemetry

    with _LOCK:
        _ACTIVE = plan
    for name in sorted(plan.decisions):
        d = plan.decisions[name]
        telemetry.emit_event(
            "plan_decision",
            decision=d.decision,
            value=d.value,
            source=d.source,
            fallback=d.fallback,
        )
    logger.info(
        "runtime plan installed (%s%s): %d decision(s)",
        plan.source,
        f" from {plan.profile_path}" if plan.profile_path else "",
        len(plan.decisions),
    )
    return plan


def uninstall_plan() -> None:
    global _ACTIVE
    with _LOCK:
        _ACTIVE = None


def apply_online_decision(
    name: str,
    value: object,
    *,
    evidence: Optional[Dict[str, object]] = None,
) -> Optional[PlanDecision]:
    """The autopilot's online re-plan (ISSUE 19): update ONE planned
    quantity mid-run, with exactly the startup precedence — an EXPLICITLY
    SET `PHOTON_*` knob for the quantity pins it (operator intent
    outranks the controller; returns None, nothing changes), otherwise
    the decision lands in the ambient plan (installing a minimal
    `source="autopilot"` plan when none is active) where every future
    `planned_value` consult sees it, and is journaled as a
    `plan_decision` with `source: "autopilot"` like any other decision.
    Returns the applied PlanDecision, whose `fallback` is the value the
    decision displaced — what a rollback restores."""
    global _ACTIVE
    from photon_ml_tpu.utils import telemetry

    knob = KNOB_FOR.get(name)
    if knob is not None and knob_is_set(knob):
        return None
    with _LOCK:
        plan = _ACTIVE
        prior = plan.decisions.get(name) if plan is not None else None
        fallback = prior.value if prior is not None else default_for(name)
        d = PlanDecision(
            decision=name,
            value=normalize(name, value),
            source="autopilot",
            evidence=dict(evidence or {}),
            fallback=fallback,
        )
        if plan is None:
            plan = Plan(
                source="autopilot",
                profile_path=None,
                topology={},
                decisions={name: d},
            )
        else:
            decisions = dict(plan.decisions)
            decisions[name] = d
            plan = dataclasses.replace(plan, decisions=decisions)
        _ACTIVE = plan
    telemetry.emit_event(
        "plan_decision",
        decision=d.decision,
        value=d.value,
        source=d.source,
        fallback=d.fallback,
    )
    return d


def current_plan() -> Optional[Plan]:
    return _ACTIVE


def plan_block(
    overrides: Optional[Dict[str, object]] = None,
) -> Dict[str, object]:
    """The active plan's block, or the inactive block — what every
    fit_timing / serving summary records unconditionally.

    `overrides` (decision name -> value actually used) re-sources those
    decisions as `"knob"` in the recorded block: an explicit CLI flag is
    operator intent exactly like an env knob, and the audit trail must
    show what the run actually ran with, not what the plan proposed."""
    plan = current_plan()
    if plan is None:
        return inactive_block()
    block = plan.block()
    if overrides:
        decisions = [dict(d) for d in block["decisions"]]
        for d in decisions:
            name = d.get("decision")
            # Re-source unconditionally — even when the flag happens to
            # equal the plan's choice, the OPERATOR pinned this value and
            # the audit must say so (a "profile" source implies the next
            # replan may move it; a pinned value will not move).
            if name in overrides:
                d["value"] = overrides[name]
                d["source"] = "knob"
                d["evidence"] = {
                    **dict(d.get("evidence") or {}),
                    "explicit_override": True,
                }
        block["decisions"] = decisions
    return block


_UNSET = object()


def planned_value(name: str, *, default: object = _UNSET) -> object:
    """The one accessor decision sites call. Precedence, in order:

    1. an EXPLICITLY SET `PHOTON_*` knob for this quantity (the operator
       said so; the plan block records it as `source: "knob"`),
    2. the installed plan's decision,
    3. the built-in default (`default` argument when given, else the
       knob-registry / DEFAULTS value) — with no plan installed this is
       exactly the pre-planner behavior, bit for bit.
    """
    knob = KNOB_FOR.get(name)
    if knob is not None and knob_is_set(knob):
        return normalize(name, get_knob(knob))
    plan = current_plan()
    if plan is not None and name in plan.decisions:
        return plan.decisions[name].value
    if default is not _UNSET:
        return default
    return default_for(name)
