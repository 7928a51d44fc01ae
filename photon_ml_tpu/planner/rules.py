"""Plan construction: profile-driven rules, calibration, the env gate.

`plan_from_profile` turns a persisted run profile (utils/telemetry
`read_profile` — the loud-contract artifact every fit/serve run writes)
into a typed Plan. Each rule is small, monotone, and evidence-first: it
reads the measured stage walls / dispatch decisions the profile recorded,
chooses a value, and records WHY (the evidence dict) beside WHAT (the
value) and WHAT IT DISPLACED (the fallback). A profile measured on
different hardware refuses loudly (`check_topology` names the
mismatching field) — planning this container from that container's cost
model is exactly the silent mis-tuning the planner exists to end.

The rules deliberately ADOPT what the profile measured wherever the
measured run already made the decision (layout, pack/assembly routing):
those decisions were made by the same auto policies on the same
hardware, so a matching-topology plan reproduces today's defaults — and
therefore today's bits. The genuinely cost-model rules (prefetch depth,
chunk rows, fusion granularity, serving wait/bucket ceiling) only plan
quantities that are bitwise-neutral by construction (PR 9 pins ingest
parity across chunk sizes; scan chunking preserves per-bucket op order;
prefetch is an async upload of data that uploads anyway).

`plan_from_calibration` is the cold-start path for a run with no profile
(PHOTON_PLAN=1): a fast startup probe — host parallelism, backend, a
small host->device bandwidth / dispatch round-trip measurement —
feeding the subset of rules that need no stage history.
`ensure_ambient_plan` is the one gate the CLI drivers and the estimator
call: explicit `--profile` beats
`PHOTON_PLAN_PROFILE`, `PHOTON_PLAN=0` kills everything, and an
r06-era profile (no `plan` block) still loads — the block is provenance,
not a requirement.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Dict, Mapping, Optional

from photon_ml_tpu.planner.plan import (
    KNOB_FOR,
    NOVEL_SHAPE_FUSE,
    Plan,
    PlanDecision,
    PlanTopologyError,
    current_plan,
    default_for,
    install_plan,
    normalize,
)
from photon_ml_tpu.utils.knobs import _FALSE, _TRUE, get_knob, knob_is_set

logger = logging.getLogger(__name__)

# Topology fields a profile must match before its measurements may plan
# this run. host_cpus is deliberately absent: the cgroup-visible core
# count varies across schedulers of the SAME machine class, and every
# host-parallelism decision re-reads the live effective parallelism.
TOPOLOGY_MATCH_FIELDS = (
    "platform",
    "device_count",
    "device_kind",
    "process_count",
)

# Cost-model constants (rule thresholds, not planned quantities): see
# each rule's comment for the measurement grounding.
_INGEST_SKEW = 4.0  # decode/assemble imbalance before chunk size moves
_CHUNK_ROWS_MIN = 65_536
_CHUNK_ROWS_MAX = 1_048_576
_WAIT_FLOOR_MS = 0.5


def check_topology(
    profile_topology: Mapping[str, object],
    current: Optional[Mapping[str, object]] = None,
) -> Dict[str, object]:
    """Loud refusal when the profile was measured on different hardware;
    returns the current topology on success."""
    if current is None:
        from photon_ml_tpu.utils.telemetry import device_topology

        current = device_topology()
    for field in TOPOLOGY_MATCH_FIELDS:
        have, want = current.get(field), profile_topology.get(field)
        if str(have) != str(want):
            raise PlanTopologyError(
                f"profile topology mismatch on {field!r}: the profile was "
                f"measured with {field}={want!r} but this run has "
                f"{field}={have!r} — refusing to plan from another "
                "machine's cost model (re-profile on this topology, or "
                "run without a profile)"
            )
    return dict(current)


def _decide(
    decisions: Dict[str, PlanDecision],
    name: str,
    value: object,
    source: str,
    evidence: Dict[str, object],
) -> None:
    """Record one decision — knob precedence applied HERE as well as at
    consult time, so the audit block shows `source: "knob"` the moment an
    operator override is in play (the consult-time check in
    planned_value keeps them honest if the env changes afterwards)."""
    fallback = default_for(name)
    knob = KNOB_FOR.get(name)
    if knob is not None and knob_is_set(knob):
        value = normalize(name, get_knob(knob))
        source = "knob"
        evidence = {**evidence, "knob": knob}
    decisions[name] = PlanDecision(
        decision=name,
        value=value,
        source=source,
        evidence=evidence,
        fallback=fallback,
    )


def plan_from_profile(
    profile: Mapping[str, object], profile_path: Optional[str] = None
) -> Plan:
    """Build a Plan from a run profile (fit or serve kind), refusing a
    mismatched topology loudly. r06-era profiles (no `plan` block) are
    the cold-start input this function exists for — the block is what
    THIS plan will add when its run persists a profile."""
    topology = check_topology(profile["device_topology"])
    decisions: Dict[str, PlanDecision] = {}
    src = "profile"
    dispatch = dict(profile.get("dispatch") or {})
    stages = dict(profile.get("stages") or {})

    if profile.get("kind") == "fit":
        ft = dict(profile.get("fit_timing") or {})

        # -- pack / RE-assembly routing: adopt where the measured run
        # placed the pass. The auto policy chose that placement on this
        # same hardware and the walls prove it ran; re-deriving it from
        # the backend would just be auto again, while the profile also
        # covers forced runs an operator validated.
        pack_path = str(dispatch.get("pack_path") or ft.get("pack_path") or "none")
        if pack_path != "none":
            _decide(
                decisions,
                "pack_routing",
                "device" if pack_path == "device" else "host",
                src,
                {
                    "pack_path": pack_path,
                    "pack_device_s": ft.get("pack_device_s"),
                    "pack_host_s": ft.get("pack_host_s"),
                },
            )
        re_path = str(dispatch.get("re_path") or ft.get("re_path") or "none")
        if re_path != "none":
            _decide(
                decisions,
                "assembly_routing",
                "device" if re_path == "device" else "host",
                src,
                {
                    "re_path": re_path,
                    "re_device_s": ft.get("re_device_s"),
                    "re_host_s": ft.get("re_host_s"),
                },
            )

        # -- sparse level-1 layout: adopt the recorded choice (it is the
        # Poisson-economics output for this data/hardware). NOTE this is
        # the one results-affecting decision the planner makes: forcing
        # a layout has exactly the semantics of the PHOTON_SPARSE_LAYOUT
        # knob (rowalign and grouped packings are allclose-, not
        # bitwise-, equivalent), so it is only planned when the profiled
        # run's packs all agreed on ONE layout — a mixed-layout fit
        # records "mixed" and plans nothing, letting each shard's
        # economics re-decide.
        layout = normalize("sparse_layout", dispatch.get("layout") or "auto")
        # normalize maps "mixed"/"none" to "auto", so both skip here.
        if layout != "auto":
            _decide(
                decisions,
                "sparse_layout",
                layout,
                src,
                {"recorded_layout": dispatch.get("layout")},
            )

        # -- prefetch depth: on a pipelined fit, go two coordinates ahead
        # when the host has cores to feed concurrent shard uploads.
        # Deliberately NOT keyed on the profile's upload-stage wall: the
        # stage records where upload work RAN, and prefetched uploads
        # that were fully hidden behind the solve still land there, so
        # the wall cannot distinguish hidden from un-hidden transfers.
        # Host parallelism is re-read LIVE (it is the one topology field
        # check_topology deliberately does not pin). Async prefetch is
        # bitwise-neutral (the shards upload either way).
        from photon_ml_tpu.data.pipeline import effective_host_parallelism

        pipelined = bool(dispatch.get("pipeline"))
        cores = int(effective_host_parallelism())
        depth = int(default_for("prefetch_depth"))
        if pipelined and cores > 2:
            depth = 2
        _decide(
            decisions,
            "prefetch_depth",
            depth,
            src,
            {"pipeline": pipelined, "host_parallelism": cores},
        )

        # -- ingest chunk rows: streamed pure-Python ingest balances the
        # decode pool against in-order assembly; a heavy skew either way
        # means the chunk boundary is in the wrong place. Bitwise-neutral
        # (tests pin parity across chunk sizes), bounded both ways.
        ingest = dict(profile.get("ingest") or {})
        chunk_rows = int(default_for("ingest_chunk_rows"))
        decode_s = float(ingest.get("decode") or 0.0)
        assemble_s = float(ingest.get("assemble") or 0.0)
        if bool(ingest.get("streaming")) and min(decode_s, assemble_s) > 0:
            if decode_s > _INGEST_SKEW * assemble_s:
                chunk_rows //= 2  # decode-bound: smaller chunks overlap more
            elif assemble_s > _INGEST_SKEW * decode_s:
                chunk_rows *= 2  # assembly-bound: fewer chunk boundaries
        chunk_rows = min(max(chunk_rows, _CHUNK_ROWS_MIN), _CHUNK_ROWS_MAX)
        _decide(
            decisions,
            "ingest_chunk_rows",
            chunk_rows,
            src,
            {"decode_s": decode_s, "assemble_s": assemble_s,
             "streaming": bool(ingest.get("streaming"))},
        )

        # -- RE bucket shape set + scan fusion granularity: shapes the
        # profile proved on this hardware fuse unboundedly (one scan
        # program per shape, today's default); shapes it never saw chunk
        # at a conservative cap so a first-dispatch failure or hang costs
        # one small group, not the whole shape. A fit whose robustness
        # counters show collective re-dispatches or watchdog trips caps
        # EVERY group: a re-dispatch repeats one chunk's work instead of
        # the whole fused program. Chunking preserves per-bucket op
        # order, so any cap is bitwise-identical to unbounded fusion.
        shapes = {
            cid: [list(map(int, s)) for s in shape_list]
            for cid, shape_list in dict(
                profile.get("bucket_shapes") or {}
            ).items()
        }
        _decide(
            decisions,
            "re_bucket_shapes",
            shapes,
            src,
            {"coordinates": sorted(shapes)},
        )
        robustness = dict(ft.get("robustness") or {})
        flaky = int(robustness.get("collective_retries") or 0) + int(
            robustness.get("watchdog_trips") or 0
        )
        fuse = int(default_for("scan_fusion_max"))
        if flaky > 0:
            fuse = NOVEL_SHAPE_FUSE
        _decide(
            decisions,
            "scan_fusion_max",
            fuse,
            src,
            {
                "collective_retries": robustness.get("collective_retries"),
                "watchdog_trips": robustness.get("watchdog_trips"),
            },
        )

    else:  # serve profile
        serving = dict(profile.get("serving") or {})

        # -- serving bucket ceiling: the power-of-two bucket ladder only
        # needs to reach the batches traffic actually forms. p95 batch
        # size (recorded by the batcher) rounded up to a power of two,
        # floored at 8 so a warm engine never compiles a degenerate set,
        # bounded by the BUILT-IN ceiling — deliberately not the prior
        # run's planned ceiling, so round-over-round re-planning is not a
        # one-way downward ratchet. Saturated evidence (p95 at the prior
        # run's own ceiling) means traffic wanted MORE than that run
        # could form, so the plan recovers to the larger of the default
        # and the observed ceiling instead of pinning the shrink.
        observed_ceiling = int(
            dispatch.get("max_batch") or default_for("serving_max_batch")
        )
        hard_ceiling = int(default_for("serving_max_batch"))
        p95_batch = serving.get("batch_size_p95")
        if p95_batch is None:
            # The batcher observes every batch into the mergeable
            # serving_batch_size histogram; the profile's metrics
            # snapshot carries it.
            hist = (dict(profile.get("metrics") or {}).get("histograms") or {}).get(
                "serving_batch_size"
            )
            if hist:
                from photon_ml_tpu.utils.telemetry import snapshot_quantile

                p95_batch = snapshot_quantile(hist, 0.95)
        # The clamp ceiling honors BOTH bounds upward: the built-in
        # default and a larger operator-validated ceiling the profile
        # ran (a 512-ceiling run whose p95 was 300 must not be planned
        # DOWN to 256 — never plan below demonstrated traffic).
        upper = max(hard_ceiling, observed_ceiling)
        max_batch = observed_ceiling
        if p95_batch:
            if int(p95_batch) >= observed_ceiling:
                # Saturated: the observed p95 itself hit the prior run's
                # ceiling (not the 8-floored ladder value, which would
                # misread every small-ceiling run as saturated).
                max_batch = upper
            else:
                b = 8
                while b < int(p95_batch):
                    b <<= 1
                max_batch = min(max(b, 8), upper)
        _decide(
            decisions,
            "serving_max_batch",
            max_batch,
            src,
            {"profile_max_batch": observed_ceiling, "batch_size_p95": p95_batch},
        )

        # -- micro-batch wait: a partial batch should not wait longer
        # than the latency budget traffic demonstrated. Half the observed
        # p50, clamped to [floor, BUILT-IN default] — each round derives
        # from that round's fresh p50, never min'd against the prior
        # plan's wait, so the wait recovers when latency grows back.
        # Without p50 evidence, adopt the profile's recorded wait.
        # `is None`, not `or`: a recorded wait of 0.0 (immediate flush, a
        # valid operator config) must be adopted, not silently replanned
        # to the default.
        profile_wait = dispatch.get("max_wait_ms")
        p50 = serving.get("p50_ms")
        if p50:
            # Clamp ceiling honors BOTH bounds upward (the bucket-ceiling
            # rule's discipline): the built-in default and a LARGER
            # operator-validated recorded wait — evidence may tighten the
            # wait within that ceiling, never ignore the bigger budget
            # the profiled run validated.
            upper_wait = max(
                float(default_for("serving_max_wait_ms")),
                0.0 if profile_wait is None else float(profile_wait),
            )
            wait = min(upper_wait, max(float(p50) / 2.0, _WAIT_FLOOR_MS))
        else:
            wait = float(
                default_for("serving_max_wait_ms")
                if profile_wait is None
                else profile_wait
            )
        _decide(
            decisions,
            "serving_max_wait_ms",
            wait,
            src,
            {"p50_ms": p50, "profile_max_wait_ms": dispatch.get("max_wait_ms")},
        )

    return Plan(
        source="profile",
        profile_path=profile_path,
        topology=topology,
        decisions=decisions,
    )


def calibration_probe() -> Dict[str, object]:
    """The fast cold-start measurement (no profile): backend + effective
    host parallelism + one small host->device upload bandwidth / dispatch
    round-trip sample, cheap enough for startup (<~1s, one tiny
    compile)."""
    from photon_ml_tpu.data.pipeline import effective_host_parallelism
    from photon_ml_tpu.utils.telemetry import device_topology

    topo = device_topology()
    probe: Dict[str, object] = {
        "host_parallelism": effective_host_parallelism(),
        "platform": topo.get("platform"),
        "device_count": topo.get("device_count"),
    }
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np

        buf = np.zeros((1 << 20,), np.float32)  # 4 MB: small but > caches
        t0 = time.perf_counter()
        dev = jax.device_put(buf)
        jax.block_until_ready(dev)
        probe["upload_gb_per_s"] = round(
            buf.nbytes / max(time.perf_counter() - t0, 1e-9) / 1e9, 3
        )
        one = jnp.ones((8,))
        fn = jax.jit(lambda x: x + 1.0)
        jax.block_until_ready(fn(one))  # compile outside the sample
        t0 = time.perf_counter()
        jax.block_until_ready(fn(one))
        probe["dispatch_rtt_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 3
        )
    except Exception:  # noqa: BLE001 - a probe must never kill a run
        logger.debug("calibration device probe failed", exc_info=True)
    return probe


def plan_from_calibration(
    probe: Optional[Mapping[str, object]] = None,
) -> Plan:
    """Cold-start plan (PHOTON_PLAN=1, no profile): only the rules whose
    evidence a startup probe can supply. Routing follows the measured
    backend (identical to the auto policies — bitwise); prefetch depth
    follows host parallelism (deeper prefetch needs cores to feed it)."""
    from photon_ml_tpu.utils.telemetry import device_topology

    probe = dict(probe if probe is not None else calibration_probe())
    decisions: Dict[str, PlanDecision] = {}
    src = "calibration"
    accel = str(probe.get("platform")) in ("tpu", "gpu")
    routing = "device" if accel else "host"
    _decide(
        decisions, "pack_routing", routing, src, {"platform": probe.get("platform")}
    )
    _decide(
        decisions,
        "assembly_routing",
        routing,
        src,
        {"platform": probe.get("platform")},
    )
    cores = int(probe.get("host_parallelism") or 1)
    _decide(
        decisions,
        "prefetch_depth",
        2 if cores > 2 else int(default_for("prefetch_depth")),
        src,
        {"host_parallelism": cores},
    )
    _decide(
        decisions,
        "ingest_chunk_rows",
        int(default_for("ingest_chunk_rows")),
        src,
        {"host_parallelism": cores},
    )
    return Plan(
        source="calibration",
        profile_path=None,
        topology=device_topology(),
        decisions=decisions,
    )


def plan_mode() -> Optional[bool]:
    """PHOTON_PLAN tri-state: True = force (calibrate without a
    profile), False = off, None = auto (plan only when a profile is
    supplied via --profile / PHOTON_PLAN_PROFILE)."""
    env = str(get_knob("PHOTON_PLAN")).strip().lower()
    if env in _TRUE:
        return True
    if env in _FALSE:
        return False
    return None


def ensure_ambient_plan(profile_path: Optional[str] = None) -> Optional[Plan]:
    """The one planner gate (CLI drivers / estimator startup):
    install a plan if configuration asks for one and none is installed.
    Explicit `profile_path` (--profile) beats PHOTON_PLAN_PROFILE;
    PHOTON_PLAN=0 disables everything; topology mismatches and broken
    profiles refuse LOUDLY (a mis-planned run is worse than an unplanned
    one). Returns the active plan, or None when planning is off."""
    active = current_plan()
    if active is not None:
        return active
    mode = plan_mode()
    if mode is False:
        return None
    path = profile_path or str(get_knob("PHOTON_PLAN_PROFILE")).strip()
    if path and profile_path is None and not os.path.exists(path):
        # PHOTON_PLAN_PROFILE is a cache HANDLE, not only an input: a
        # repeat-round workflow points it at the path the run will WRITE
        # its profile to, so on the first round the file does
        # not exist yet. Run unplanned and let this round populate it —
        # but an explicit --profile argument stays loud: the operator
        # named a specific artifact, and a missing one is an error.
        logger.info(
            "PHOTON_PLAN_PROFILE=%s does not exist yet; running unplanned "
            "(this run can write it for the next round)",
            path,
        )
        path = ""
    if path:
        from photon_ml_tpu.utils.telemetry import read_profile

        return install_plan(plan_from_profile(read_profile(path), path))
    if mode is True:
        return install_plan(plan_from_calibration())
    return None
