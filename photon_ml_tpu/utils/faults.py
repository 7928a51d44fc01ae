"""Deterministic fault injection + bounded retry for the failure domain.

The reference inherits mid-job failure recovery from its substrate: Spark
lineage re-computes lost partitions and the driver re-tries failed stages,
with DISK_ONLY persists bounding the recompute (CoordinateDescent.scala:
325-341). The TPU port replaced that substrate with an explicit checkpoint
(game/checkpoint.py) and a threaded host data plane (data/pipeline.py) —
which means every failure path is now OURS to exercise and recover. This
module is the shared machinery:

* `FaultPlan` / `install` / `fault_point(site)` — a seeded, deterministic
  fault-injection registry. Sites are the data-plane and solver boundaries
  (`decode`, `pack`, `upload`, `solve`, `checkpoint_write`), the serving
  tier (`lookup`/`score`/`admit`/`swap_*`), and the pod-scale mesh layers
  (`collective`, `shard_upload`, `promote`, `resume_load`); a plan arms a
  site for its first N invocations, explicit invocation indices, or a
  seeded probability — all reproducible, so a chaos test can replay the
  exact same failure schedule. Configured programmatically (tests) or via
  `PHOTON_FAULTS` / `PHOTON_FAULTS_SEED` env (subprocess chaos runs):

      PHOTON_FAULTS="decode:1,upload:2,solve@3,pack:p0.25"

  `site:N` fails the first N invocations, `site@i+j` fails exactly the
  1-based invocations i and j, `site:pX` fails each invocation with
  probability X keyed on (seed, site, invocation) — deterministic per
  seed. An armed `fault_point` raises `InjectedFault` (always classified
  transient by the retry policy below).

* `retry(fn, policy)` — bounded exponential backoff around transient
  failures. Default policy: 3 attempts, 50 ms base delay doubling to a
  2 s cap, retrying `InjectedFault`, `OSError`/`ConnectionError`/
  `TimeoutError`, and the XLA runtime errors whose status can clear by
  itself (`UNAVAILABLE`, `DEADLINE_EXCEEDED`, `ABORTED`, `CANCELLED`);
  a refused compile or `RESOURCE_EXHAUSTED` is deterministic on an
  attached chip and propagates at once. Knobs:
  `PHOTON_RETRY_MAX_ATTEMPTS`, `PHOTON_RETRY_BASE_DELAY_S`,
  `PHOTON_RETRY_MAX_DELAY_S`.

* `COUNTERS` — process-wide robustness event counters (`retries`,
  `fallback_sync_uploads`, `fallback_sync_builds`, `fallback_sync_packs`,
  `injected_faults`, `serving_degraded_batches`, `serving_shed_requests`,
  `serving_deadline_misses`, `serving_circuit_opens`,
  `serving_fe_only_requests`, `serving_swaps`, `serving_swap_rollbacks`,
  `serving_flush_thread_failures`, `quarantined_blocks`, and the pod-scale
  mesh counters `collective_retries` / `collective_fallbacks` /
  `shard_upload_retries` / `promote_failures` / `watchdog_trips` /
  `shard_loss_fallbacks` and the elastic-mesh counters `mesh_losses` /
  `reshard_retries` / `reshard_rollbacks` / `rebalanced_rows` — the ones
  in contracts.ROBUSTNESS_CLEAN_ZERO_KEYS are additionally carried by
  every fit_timing and serving summary). Zero on a clean run by
  construction, so a nonzero value in a run's artifact is a loud
  robustness regression signal, and tests assert exact counts.

Everything here changes only WHETHER work is retried/degraded, never what
it computes: a run under injected transient faults must produce the same
model, bit for bit, as a fault-free run (tests/test_faults.py).
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
import zlib
from contextlib import contextmanager
from typing import Callable, Dict, FrozenSet, Mapping, Optional, Tuple

from photon_ml_tpu.utils import telemetry
from photon_ml_tpu.utils.knobs import get_knob

logger = logging.getLogger(__name__)

# The injection sites wired into the framework. fault_point accepts any
# string (the registry is open for future subsystems), but plans naming an
# unknown site fail fast at parse time — a typo'd PHOTON_FAULTS that
# silently injects nothing would be a chaos test that tests nothing.
# `python -m photon_ml_tpu.utils.faults --list-sites` prints this table,
# and tests/conftest.py fails the run if any fault_point() call in the
# tree names a site missing from it.
SITE_DESCRIPTIONS = {
    "decode": "Avro block decode in the ingest data plane",
    "pack": "host-side CSR->ELL pack (background pack pool)",
    "upload": "host->device shard upload (AsyncUploader jobs)",
    "solve": "per-coordinate device solve in coordinate descent",
    "checkpoint_write": "durable checkpoint writes (state.json + model npz)",
    # Online serving (serving/engine.py): entity-row resolution and the
    # batched device dispatch. The micro-batcher degrades a faulted batch
    # to per-request dispatch (serving/batcher.py) instead of dying.
    "lookup": "serving entity-id -> coefficient-row resolution",
    "score": "serving batched device dispatch (upload + fused program)",
    # Serving lifecycle (serving/lifecycle.py): admission into the
    # micro-batcher queue and the two phases of a bundle hot-swap.
    "admit": "serving admission control (an armed fault sheds the request)",
    "swap_stage": "bundle hot-swap staging (build + upload + warm the next bundle)",
    "swap_commit": "bundle hot-swap commit (the atomic flip between batches)",
    # Pod-scale mesh failure domain (ISSUE 10): the distributed layers'
    # own fault sites. Each has a bounded retry plus a degraded fallback —
    # a failed collective re-dispatches then falls back to the bitwise-
    # equal per-bucket loop for that sweep, a failed promotion leaves the
    # row cold (counted, never fatal), a failed shard upload rolls a
    # hot-swap back / leaves the shard degraded-FE-only, and a failed
    # checkpoint-shard read retries before refusing with an integrity
    # error naming the shard.
    "collective": "mesh collective program dispatch (ring gather/scatter, "
    "psum bcast-gather, scan sweeps over them)",
    "shard_upload": "per-shard serving model staging (bundle build + "
    "shard restage after loss)",
    "promote": "two-tier serving store promotion (cold row -> HBM hot set)",
    "resume_load": "checkpoint model/shard file reads on resume",
    # Live mesh elasticity (ISSUE 13): resharding a READY serving engine
    # between mesh shapes under traffic, and losing part of the training
    # mesh mid-fit. Reshard staging/commit failures roll back to the old
    # generation (zero failed requests); a mesh loss is caught at the
    # coordinate-descent sweep boundary and costs one repeated sweep.
    "mesh_loss": "device-mesh loss during a sharded coordinate update "
    "(sweep-boundary elastic resume)",
    "reshard_stage": "live serving reshard staging (per-shard upload of "
    "moved coefficient rows)",
    "reshard_commit": "live serving reshard commit (the atomic generation "
    "flip between batches)",
    # Multi-tenant serving (ISSUE 15): admitting a named tenant's bundle
    # onto the shared fleet, and demoting/evicting a cold tenant's RE
    # rows to the host tier under HBM pressure. An admit failure leaves
    # the registry unchanged (the new tenant simply is not admitted); a
    # demotion failure rolls back and the tenant keeps serving its old
    # device-resident generation.
    "tenant_admit": "multi-tenant registry admission (staging a named "
    "tenant's bundle onto the shared fleet)",
    "tenant_evict": "multi-tenant cold-tenant demotion (RE rows to the "
    "host tier under HBM pressure)",
    # Multi-host production mode (ISSUE 17): losing a whole OS process
    # (one "host" of the DCN-spanning process group) mid-fit, and a lost
    # host rejoining the serving fleet. A host loss escalates HostLoss
    # through the MeshLoss sweep-boundary machinery — the supervisor
    # relaunches on the survivor set and the fit resumes from the
    # multi-host checkpoint, replaying exactly one sweep. A rejoin
    # restages the host's row partition back from FE-only degradation.
    "host_loss": "whole-host loss in the multi-host process group "
    "(heartbeat-detected dead peer; supervisor relaunch on survivors)",
    "host_join": "host rejoin into the multi-host serving fleet "
    "(restage of the lost host's row partition)",
    # Shadow deployment & online evaluation (ISSUE 18): mirroring champion
    # traffic to a challenger tenant, joining labels into evaluation
    # windows, and flipping a promoted challenger to champion. A mirror or
    # join failure degrades to champion-only serving (counted, NEVER a
    # failed client request); a promote failure aborts the flip and the
    # champion keeps serving its old generation bitwise.
    "shadow_mirror": "shadow traffic mirroring (submit of the challenger's "
    "co-batched copy of a champion request)",
    "label_join": "online-evaluation label join (uid -> label arrival into "
    "the shadow scoring window)",
    "shadow_promote": "shadow promotion (the challenger -> champion "
    "BundleManager generation flip)",
    # Closed-loop autoscaling (ISSUE 19): the autopilot actuation site —
    # armed between a ControlRule's decision and its effect, so every
    # actuator path (reshard, rebalance, demote/restore, batch retune)
    # exercises the rollback + quarantine machinery under injection. A
    # faulted actuation rolls back to the pre-action state and counts
    # toward the rule's quarantine threshold; client requests never fail.
    "autopilot_act": "autopilot actuation (applying a ControlRule's "
    "decided action through the serving actuators)",
    # Precision-tier ladder (ISSUE 20): both sites fire inside the
    # stage->pre-warm->commit->drain transition, BEFORE anything is
    # committed — an injected (or real) mid-quantize death leaves the
    # old generation serving bitwise.
    "quantize_stage": "precision-ladder demotion build (quantizing a "
    "tenant's RE row planes to bf16/int8 — bounded retry; a terminal "
    "failure rolls back with the old generation still serving)",
    "tier_restore": "precision-ladder restore build (walking a tenant's "
    "RE row planes back toward f32 from the retained host copies — "
    "bounded retry; a terminal failure leaves the quantized generation "
    "serving)",
}
KNOWN_SITES = tuple(SITE_DESCRIPTIONS)


class InjectedFault(RuntimeError):
    """Raised by an armed `fault_point`. Always classified transient."""


class DeviceHang(RuntimeError):
    """A device dispatch exceeded its watchdog deadline (utils/watchdog.py).

    Classified transient/device-shaped: the coordinate sweep converts it to
    a bounded re-dispatch (then the per-bucket fallback), and the serving
    breaker counts it toward opening — the 'stuck forever on a bad device'
    hole becomes a typed, counted degradation instead of a silent stall."""


class MeshLoss(RuntimeError):
    """Part of the device mesh is GONE mid-fit (a dead shard group, a host
    dropping out of the pod) — the fault no in-place retry can fix, because
    re-dispatching onto the same mesh re-hits the same dead devices.

    Deliberately NOT in the transient set: `retry()` must never spin on it.
    The handler lives one level up, at the coordinate-descent sweep
    boundary (game/coordinate_descent.py): roll the interrupted sweep back,
    re-form the mesh from the surviving devices, reassemble the coordinate
    state in memory (the elastic checkpoint's any-shape reassembly without
    the filesystem round trip), and repeat the sweep — a mesh shrink costs
    one sweep, not the job. Raised by the armed `mesh_loss` fault site and
    by watchdog-escalated DeviceHang / exhausted device-shaped failures on
    an entity-sharded coordinate."""


class HostLoss(MeshLoss):
    """A whole HOST of the multi-host process group is gone (ISSUE 17) —
    the DCN-scale specialization of MeshLoss, detected by the host-liveness
    heartbeat (parallel/hostmesh.py) or a collective dispatch wedging on a
    dead peer.

    Subclasses MeshLoss so the coordinate-descent sweep boundary already
    classifies it correctly, but the recovery is NOT in-process: with
    jax.distributed the surviving processes cannot shrink the global mesh
    mid-flight, so the worker exits with hostmesh.EXIT_HOST_LOSS after
    journaling a `host_loss` event, and the multi-host SUPERVISOR
    (cli/train --multihost) relaunches the survivor set. The relaunched fit
    resumes from the multi-host checkpoint's last committed sweep — the
    Spark parity (PARITY.md): executor loss + YARN relaunch + lineage
    refetch, here as process loss + supervisor relaunch + checkpoint
    resume. Cost: exactly one repeated sweep."""


# --------------------------------------------------------------- fault plans


def _mix64(*parts: int) -> int:
    """splitmix64-style avalanche over the parts — the same deterministic
    keyed-hash idiom as the data layer's reservoir priorities
    (data/game_dataset._row_priorities)."""
    x = 0x9E3779B97F4A7C15
    for p in parts:
        x = (x ^ (p & 0xFFFFFFFFFFFFFFFF)) * 0xBF58476D1CE4E5B9 & 0xFFFFFFFFFFFFFFFF
        x ^= x >> 30
        x = x * 0x94D049BB133111EB & 0xFFFFFFFFFFFFFFFF
        x ^= x >> 31
    return x


@dataclasses.dataclass(frozen=True)
class SiteSpec:
    """When one site fires: first-N invocations, explicit 1-based
    invocation indices, and/or a seeded per-invocation probability."""

    first_n: int = 0
    indices: FrozenSet[int] = frozenset()
    probability: float = 0.0

    def should_fail(self, site: str, invocation: int, seed: int) -> bool:
        if invocation <= self.first_n or invocation in self.indices:
            return True
        if self.probability > 0.0:
            h = _mix64(seed, zlib.crc32(site.encode()), invocation)
            return (h >> 11) / float(1 << 53) < self.probability
        return False


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """Immutable site -> SiteSpec schedule plus the probability seed."""

    sites: Mapping[str, SiteSpec]
    seed: int = 0

    @classmethod
    def parse(cls, spec: str, seed: int = 0) -> "FaultPlan":
        """`"decode:1,upload:2,solve@3+5,pack:p0.25"` — see module doc."""
        sites: Dict[str, SiteSpec] = {}
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            if "@" in part:
                site, _, idx = part.partition("@")
                entry = SiteSpec(
                    indices=frozenset(int(i) for i in idx.split("+"))
                )
            elif ":" in part:
                site, _, val = part.partition(":")
                val = val.strip()
                if val.startswith("p"):
                    entry = SiteSpec(probability=float(val[1:]))
                else:
                    entry = SiteSpec(first_n=int(val))
            else:
                site, entry = part, SiteSpec(first_n=1)
            site = site.strip()
            if site not in KNOWN_SITES:
                raise ValueError(
                    f"unknown fault site {site!r} in {spec!r} "
                    f"(known: {', '.join(KNOWN_SITES)})"
                )
            prev = sites.get(site, SiteSpec())
            sites[site] = SiteSpec(
                first_n=max(prev.first_n, entry.first_n),
                indices=prev.indices | entry.indices,
                probability=max(prev.probability, entry.probability),
            )
        return cls(sites=sites, seed=seed)


class FaultInjector:
    """A plan plus thread-safe per-site invocation/injection counters."""

    def __init__(self, plan: FaultPlan):
        self.plan = plan
        self.invocations: Dict[str, int] = {}
        self.injected: Dict[str, int] = {}
        self._lock = threading.Lock()

    def fire(self, site: str) -> None:
        with self._lock:
            n = self.invocations.get(site, 0) + 1
            self.invocations[site] = n
            spec = self.plan.sites.get(site)
            fail = spec is not None and spec.should_fail(site, n, self.plan.seed)
            if fail:
                self.injected[site] = self.injected.get(site, 0) + 1
        if fail:
            COUNTERS.increment("injected_faults")
            telemetry.emit_event("fault_injected", site=site, invocation=n)
            logger.warning("injected fault at site %r (invocation %d)", site, n)
            raise InjectedFault(f"injected fault at site {site!r} (invocation {n})")


_LOCK = threading.Lock()
_INJECTOR: Optional[FaultInjector] = None
_ENV_CHECKED = False


def install(plan, seed: int = 0) -> FaultInjector:
    """Arm a plan process-wide. `plan` is a FaultPlan or a spec string."""
    global _INJECTOR, _ENV_CHECKED
    if isinstance(plan, str):
        plan = FaultPlan.parse(plan, seed=seed)
    with _LOCK:
        _INJECTOR = FaultInjector(plan)
        _ENV_CHECKED = True
    return _INJECTOR


def clear() -> None:
    """Disarm fault injection (env re-read on next fault_point)."""
    global _INJECTOR, _ENV_CHECKED
    with _LOCK:
        _INJECTOR = None
        _ENV_CHECKED = False


def active_injector() -> Optional[FaultInjector]:
    """The armed injector, arming from PHOTON_FAULTS on first call."""
    global _INJECTOR, _ENV_CHECKED
    if _INJECTOR is not None:
        return _INJECTOR
    if _ENV_CHECKED:
        return None
    with _LOCK:
        if not _ENV_CHECKED:
            _ENV_CHECKED = True
            spec = str(get_knob("PHOTON_FAULTS")).strip()
            if spec:
                seed = int(get_knob("PHOTON_FAULTS_SEED"))
                _INJECTOR = FaultInjector(FaultPlan.parse(spec, seed=seed))
    return _INJECTOR


def fault_point(site: str) -> None:
    """Raise InjectedFault when `site` is armed; free no-op otherwise."""
    inj = active_injector()
    if inj is not None:
        inj.fire(site)


@contextmanager
def inject(spec: str, seed: int = 0):
    """Test scope: arm `spec`, yield the injector, disarm on exit."""
    inj = install(spec, seed=seed)
    try:
        yield inj
    finally:
        clear()


# ------------------------------------------------------------------ counters


class _Counters:
    """Process-wide robustness event counters — since ISSUE 11 a view
    over the typed telemetry metrics registry (utils/telemetry.METRICS),
    so every counter name is declared exactly once in
    METRIC_DESCRIPTIONS (the analyzer's `metric-name-sync` check fails
    the build on an undeclared increment) and robustness counters ride
    the same snapshot/merge machinery as every other metric."""

    def increment(self, name: str, by: int = 1, labels=None) -> None:
        telemetry.METRICS.increment(name, by, labels=labels)

    def get(self, name: str) -> int:
        return telemetry.METRICS.get_counter(name)

    def snapshot(self) -> Dict[str, int]:
        return telemetry.METRICS.counters()

    def reset(self) -> None:
        # Counters ONLY: a reset between the phases of a run must not
        # wipe unrelated histogram/gauge state.
        telemetry.METRICS.reset_counters()


COUNTERS = _Counters()


def counters() -> Dict[str, int]:
    return COUNTERS.snapshot()


def reset_counters() -> None:
    COUNTERS.reset()


# --------------------------------------------------------------------- retry


# Status codes of an XLA runtime error that can clear on their own with
# the chip attached to this host: a peer of a multi-host collective went
# away or a rendezvous timed out. Every other status is deterministic for
# the same program on the same device — RESOURCE_EXHAUSTED (out of HBM),
# INTERNAL / INVALID_ARGUMENT / UNIMPLEMENTED (the compiler refused, or
# Mosaic failed, the program), FAILED_PRECONDITION — and re-fails
# identically, so retrying or degrading around it only hides it.
_TRANSIENT_XLA_STATUSES = (
    "UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED", "CANCELLED",
)


def _xla_status(exc: BaseException) -> Optional[str]:
    """The leading status code of an XLA runtime error ("RESOURCE_EXHAUSTED:
    ..." -> "RESOURCE_EXHAUSTED"); None for any other exception."""
    if type(exc).__name__ not in ("JaxRuntimeError", "XlaRuntimeError"):
        return None
    return str(exc).split(":", 1)[0].strip()


def _default_transient(exc: BaseException) -> bool:
    """Transient by default: injected faults, host I/O failures, and XLA
    runtime errors whose status can clear by itself
    (_TRANSIENT_XLA_STATUSES). Deliberately NOT retried: programming
    errors (TypeError/ValueError/KeyError...) and the deterministic XLA
    failures — a compile the chip refuses, an allocation HBM cannot hold
    — which would re-fail identically and mask the bug."""
    if isinstance(
        exc, (InjectedFault, DeviceHang, OSError, ConnectionError, TimeoutError)
    ):
        return True
    return _xla_status(exc) in _TRANSIENT_XLA_STATUSES


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded exponential backoff: attempt k sleeps
    min(base * backoff**(k-1), max_delay) before retrying."""

    max_attempts: int = 3
    base_delay_s: float = 0.05
    max_delay_s: float = 2.0
    backoff: float = 2.0
    is_transient: Callable[[BaseException], bool] = _default_transient

    def delay(self, attempt: int) -> float:
        return min(
            self.base_delay_s * self.backoff ** max(0, attempt - 1),
            self.max_delay_s,
        )


def default_policy() -> RetryPolicy:
    """The env-tunable default (PHOTON_RETRY_* knobs, see module doc)."""
    return RetryPolicy(
        max_attempts=max(1, int(get_knob("PHOTON_RETRY_MAX_ATTEMPTS"))),
        base_delay_s=float(get_knob("PHOTON_RETRY_BASE_DELAY_S")),
        max_delay_s=float(get_knob("PHOTON_RETRY_MAX_DELAY_S")),
    )


def bounded_policy(extra_attempts: int) -> RetryPolicy:
    """The default backoff/transient classification with an explicit
    attempt bound: 1 initial try + `extra_attempts` retries. The one
    builder behind every per-site retry knob (collective re-dispatch,
    per-shard staging), so backoff/classification changes cannot drift
    across sites."""
    return dataclasses.replace(
        default_policy(), max_attempts=1 + max(0, int(extra_attempts))
    )


def retry(
    fn: Callable[[], object],
    policy: Optional[RetryPolicy] = None,
    *,
    label: str = "operation",
    counter: str = "retries",
    sleep: Callable[[float], None] = time.sleep,
):
    """Run `fn`, retrying transient failures under `policy`. Every retry
    increments COUNTERS[counter]; the final failure (attempts exhausted or
    a non-transient error) propagates unchanged."""
    policy = policy or default_policy()
    attempt = 1
    while True:
        try:
            return fn()
        except BaseException as exc:  # noqa: BLE001 - re-raised when final
            if attempt >= policy.max_attempts or not policy.is_transient(exc):
                raise
            delay = policy.delay(attempt)
            COUNTERS.increment(counter)
            telemetry.emit_event(
                "fault_retry",
                label=label,
                counter=counter,
                attempt=attempt,
                error=repr(exc),
            )
            logger.warning(
                "transient failure in %s (attempt %d/%d): %s — retrying in %.2fs",
                label,
                attempt,
                policy.max_attempts,
                exc,
                delay,
            )
            sleep(delay)
            attempt += 1


def is_device_error(exc: BaseException) -> bool:
    """True for failures the device layer may recover from — the class
    the serving circuit breaker counts toward opening (a malformed
    request raising TypeError/ValueError is the REQUEST's fault and must
    never trip the breaker; a refused compile or an out-of-memory is the
    PROGRAM's, and failing the request is the honest answer). Same
    classification as the retry policy's transient set."""
    return _default_transient(exc)


def solve_retry_attempts() -> int:
    """Extra solve attempts the divergence guard grants a rejected
    (non-finite) coordinate update before keeping the last-good model
    (PHOTON_SOLVE_RETRIES, default 1). One retry is what makes a TRANSIENT
    non-finite solve — an injected fault, a flaky accelerator — converge
    back to the fault-free result bitwise; a deterministic divergence
    reproduces on retry and falls through to last-good after one extra
    solve."""
    return max(0, int(get_knob("PHOTON_SOLVE_RETRIES")))


# ------------------------------------------------------------------ CLI


def main(argv=None) -> int:
    """`python -m photon_ml_tpu.utils.faults --list-sites`: print the
    registered fault-site table (site, description, and what the ambient
    PHOTON_FAULTS plan arms at it) so operators can see what a chaos spec
    can target without reading the source."""
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m photon_ml_tpu.utils.faults",
        description="Inspect the deterministic fault-injection registry.",
    )
    p.add_argument(
        "--list-sites",
        action="store_true",
        help="print every registered fault site and any armed plan",
    )
    args = p.parse_args(argv)
    if not args.list_sites:
        p.print_help()
        return 2
    inj = active_injector()
    armed = dict(inj.plan.sites) if inj is not None else {}
    width = max(len(s) for s in KNOWN_SITES)
    print(f"{'site'.ljust(width)}  armed  description")
    for site in KNOWN_SITES:
        spec = armed.get(site)
        if spec is None:
            tag = "-"
        else:
            bits = []
            if spec.first_n:
                bits.append(f"first {spec.first_n}")
            if spec.indices:
                bits.append("@" + "+".join(str(i) for i in sorted(spec.indices)))
            if spec.probability:
                bits.append(f"p={spec.probability}")
            tag = ",".join(bits) or "-"
        print(f"{site.ljust(width)}  {tag:5s}  {SITE_DESCRIPTIONS[site]}")
    if inj is not None:
        unknown = sorted(set(armed) - set(KNOWN_SITES))
        if unknown:  # unreachable via parse(), but be honest if it happens
            print(f"WARNING: armed plan names unregistered sites: {unknown}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
