"""photon-planner: the adaptive runtime plan layer (ISSUE 14).

A `Plan` replaces the tree's hand-tuned runtime constants — sparse
layout, pack/assembly device-vs-host routing, ingest chunk rows,
coordinate prefetch depth, RE scan-fusion granularity, the serving
bucket ceiling and micro-batch wait — with typed, evidence-carrying
decisions built from a persisted run profile
(`utils/telemetry.read_profile`) or a fast startup calibration.

Precedence everywhere: explicit `PHOTON_*` knob > plan > default. With
no plan installed (or `PHOTON_PLAN=0`) every consulting site returns the
exact pre-planner default — bitwise-identical behavior by construction.
Every run records the active plan as a `plan` block
(contracts.PLAN_BLOCK_KEYS) in `fit_timing` / `serving-summary.json`.

See `plan.py` (types, ambient install, consult accessor) and `rules.py`
(profile rules, calibration, topology guard, the env gate).
"""

from photon_ml_tpu.planner.plan import (  # noqa: F401
    DEFAULTS,
    KNOB_FOR,
    Plan,
    PlanDecision,
    PlanTopologyError,
    apply_online_decision,
    current_plan,
    default_for,
    inactive_block,
    install_plan,
    plan_block,
    planned_value,
    uninstall_plan,
)
from photon_ml_tpu.planner.rules import (  # noqa: F401
    TOPOLOGY_MATCH_FIELDS,
    calibration_probe,
    check_topology,
    ensure_ambient_plan,
    plan_from_calibration,
    plan_from_profile,
    plan_mode,
)

__all__ = [
    "DEFAULTS",
    "KNOB_FOR",
    "Plan",
    "PlanDecision",
    "PlanTopologyError",
    "TOPOLOGY_MATCH_FIELDS",
    "apply_online_decision",
    "calibration_probe",
    "check_topology",
    "current_plan",
    "default_for",
    "ensure_ambient_plan",
    "inactive_block",
    "install_plan",
    "plan_block",
    "plan_from_calibration",
    "plan_from_profile",
    "plan_mode",
    "planned_value",
    "uninstall_plan",
]
