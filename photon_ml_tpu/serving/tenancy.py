"""Multi-tenant serving: N isolated model bundles on one device fleet.

Photon ML serves one GAME model per Spark job, and every isolation
property — memory, admission, failure blast radius — comes free from the
one-job-per-model deployment. The TPU engine runs N models IN ONE
PROCESS on one device fleet, so everything Spark's job boundary gave for
free must be enforced here explicitly. `TenantRegistry` is that layer —
the generalization of `BundleManager` from "a model server" to "a
serving platform" (the ROADMAP's multi-tenant open item):

* **Per-tenant admission quotas and deadline budgets.** Each tenant owns
  a bounded pending count (`PHOTON_TENANT_MAX_PENDING` default); a
  submit past it sheds with a typed `Overloaded` NAMING the tenant —
  one tenant's overload is its own typed rejection, never a shared-queue
  backlog that starves its neighbors (the Spark-ML performance study's
  finding that contention knobs dominate tail latency, PAPERS.md,
  applied as per-tenant bounds instead of one shared queue). Deadlines
  default per tenant and enforce at claim time exactly like the
  single-tenant micro-batcher: an expired request is failed before it
  wastes a device slot.

* **Weighted-fair cross-tenant batch assembly.** The registry's one
  dispatch thread (`photon-tenant-dispatch`) claims up to `max_batch`
  requests per round, splitting slots across backlogged tenants in
  proportion to their weights (every backlogged tenant gets at least
  one slot — weighted fairness, not starvation), then CO-BATCHES
  compatible tenants' requests into ONE device dispatch: requests from
  different bundles share a padded bucket, each slot gathering ITS
  tenant's parameters (fixed-effect planes via a stacked per-slot row
  gather, random-effect rows via a per-tenant gather + exact where-
  select). Both kernels reuse the engine's margin code paths
  (`dense_margins`, `gathered_row_margins`), so a co-batched slice is
  BITWISE-equal to dispatching that tenant alone — the same invariance
  argument that lets the micro-batcher degrade to per-request dispatch
  without changing an answer. Co-batch eligibility is structural (all
  coordinates "fe"/"re", no normalization, same task and dims, no lost
  shards); anything else — demoted tenants, sharded/two-tier stores,
  open circuits — dispatches SOLO through the tenant's own hardened
  micro-batcher, which already owns the retry/FE-only/deadline policy.

* **Fully per-tenant failure domains.** Every tenant owns a complete
  `ServingEngine`: its own health machine, circuit breaker, watchdog,
  jit cache, and flush thread (`photon-tenant-<name>-flush`). One
  tenant's open circuit or `DeviceHang` routes only ITS requests to the
  FE-only tier; a chaos drill confines an armed fault plan to one
  tenant via the engine's `inject_faults` gate (site invocation
  counters are process-global, so deterministic targeting needs a
  per-engine gate). The process-global serving robustness counters are
  additionally scoped per tenant via telemetry metric labels — the
  aggregate stays, and each tenant's clean-run zero contract is its own
  labeled sub-count.

* **HBM-pressure eviction of cold tenants.** Admission charges every
  tenant's per-shard device bytes against the fleet budget
  (`PHOTON_TENANT_HBM_FRACTION` of the device limit). When tenant N+1
  does not fit, the registry DEMOTES the coldest (least-recently-
  active) tenant's random-effect rows to the host tier — the
  `TwoTierEntityStore` as cross-tenant eviction engine
  (`bundle.demote_bundle_to_host_tier`): the demoted tenant keeps
  answering BITWISE through per-request override rows (Snap ML's
  hierarchical host/device memory management, PAPERS.md, arbitrating
  HBM across tenants), it just stops pinning its matrix. Admission may
  demote, never fail, a READY tenant; only a fleet that cannot fit even
  after demoting every candidate refuses with `HbmBudgetExceeded`.

* **Precision-tier graceful degradation (ISSUE 20).** With
  `PHOTON_TIER_LADDER` on, the pressure valve (and the autopilot's
  hbm rules) walks a tenant DOWN a ladder instead of leaping to the
  host tier: f32 -> bf16 -> int8 -> host (`demote_tier`), each quantize
  rung halving/quartering the pinned RE bytes via planes dequantized
  INSIDE the bucket programs, and each step the same stage->pre-warm->
  commit->drain generation flip as a hot-swap. Quantization always
  reads the retained ORIGINAL f32 rows, so `restore_tier` walks back up
  and the final f32 step — and any host-tier round trip — is BITWISE
  vs. the pre-demotion self. A quantized tenant answers under the
  CHARACTERIZED contract (contracts.TIER_TOLERANCES), not the bitwise
  one; that trade is opt-in, journaled (`tier_demote`/`tier_restore`
  with evidence), error-histogrammed per tenant, and refused outright
  when int8's measured error would exceed the configured ceiling.

Fault sites: `tenant_admit` (staging a tenant onto the fleet — bounded
retry, an exhausted failure leaves the registry unchanged),
`tenant_evict` (the demotion build — bounded retry, a terminal failure
rolls back and the tenant keeps serving its device-resident
generation), and `quantize_stage`/`tier_restore` (the ladder builds —
same rollback story, counted in `tier_rollbacks`). Journal events
`tenant_admit`/`tenant_evict`/`tenant_degraded`/`tier_demote`/
`tier_restore` record the platform's lifecycle per tenant.
"""

from __future__ import annotations

import collections
import logging
import threading
import time
from concurrent.futures import Future
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.game.model import gathered_row_margins
from photon_ml_tpu.ops.losses import mean_for_task
from photon_ml_tpu.serving.bundle import (
    PRECISION_LADDER,
    ScoreRequest,
    ServingBundle,
    demote_bundle_to_host_tier,
    promote_bundle_from_host_tier,
    quantize_bundle_rows,
    restore_bundle_precision,
)
from photon_ml_tpu.serving.engine import (
    ScoreResult,
    ServingEngine,
    _bucket_sizes,
)
from photon_ml_tpu.serving.lifecycle import (
    BatcherUnhealthy,
    DeadlineExceeded,
    HbmBudgetExceeded,
    Overloaded,
    _bundle_device_bytes,
    device_memory_budget_bytes,
)
from photon_ml_tpu.transformers.game_transformer import dense_margins
from photon_ml_tpu.utils import faults, telemetry
from photon_ml_tpu.utils.contracts import TENANT_BLOCK_KEYS, TIER_BLOCK_KEYS
from photon_ml_tpu.utils.knobs import get_knob
from photon_ml_tpu.utils.watchdog import Watchdog, watchdog_ms

logger = logging.getLogger(__name__)

# One queued request: (request, future, submit time, absolute expiry or None)
# — the micro-batcher's pending shape, kept per tenant.
_Pending = Tuple[ScoreRequest, Future, float, Optional[float]]


class TierErrorCeilingExceeded(RuntimeError):
    """An int8 quantization's measured round-trip error exceeded
    PHOTON_TIER_INT8_ERROR_CEILING: the build is discarded BEFORE commit,
    the tenant stays on its current rung, and ladder walkers fall through
    to the (bitwise) host tier for pressure relief instead of serving
    answers outside the characterized tolerance."""


def _cobatch_program(offsets, tids, feats, rows, params, *, kinds, task):
    """The fused cross-tenant bucket program: one device dispatch scoring
    a padded bucket whose slots belong to DIFFERENT tenants' bundles.

    Per coordinate position k (eligibility guarantees every tenant in the
    group shares the (kind, dim) structure and carries no normalization):

      * "fe": the group's weight vectors stack to (T, dim) and each slot
        gathers ITS tenant's row — `dense_margins` on gathered (B, dim)
        rows runs the identical multiply + per-row reduce the solo engine
        runs on the broadcast (dim,) vector, so the slice is bitwise the
        solo answer (stack/gather move bits, never arithmetic).
      * "re": each tenant's (E_t + 1, dim) matrix is gathered at its OWN
        per-slot rows (foreign slots point at that tenant's pinned zero
        row, keeping every gather in bounds), then an exact `where`
        select by tenant id picks each slot's true row — a select, not a
        sum, so no foreign zero ever touches the arithmetic. The margin
        is `gathered_row_margins`, the shared tail that already keeps the
        two-tier and entity-sharded paths bitwise-equal to the
        replicated one.

    Padding slots carry tenant id 0 and pinned zero rows; their outputs
    are discarded and — both kernels being batch-size invariant — never
    influence a real slot."""
    total = offsets
    for k, kind in enumerate(kinds):
        f = feats[k]
        if kind == "fe":
            w = jnp.stack(params[k])[tids]
            total = total + dense_margins(f, w, None)
        else:
            w = params[k][0][rows[k][0]]
            for t in range(1, len(params[k])):
                w = jnp.where(
                    (tids == t)[:, None], params[k][t][rows[k][t]], w
                )
            total = total + gathered_row_margins(f, w, None)
    return total, mean_for_task(task, total)


class Tenant:
    """One named tenant's complete serving stack: its pinned bundle, its
    OWN engine (health/circuit/watchdog/jit cache), its own micro-batcher
    (the solo/fallback dispatch path, `photon-tenant-<name>-flush`), its
    admission quota and deadline default, and its registry-side queue for
    the co-batched fast path."""

    def __init__(
        self,
        name: str,
        engine: ServingEngine,
        batcher,
        *,
        quota: int,
        deadline_ms: Optional[float],
        weight: float,
        order: int,
    ):
        self.name = name
        self.engine = engine
        self.batcher = batcher
        self.quota = int(quota)
        self.deadline_ms = deadline_ms
        self.weight = float(weight)
        self.order = int(order)  # admission order: the stable group index
        self.queue: Deque[_Pending] = collections.deque()
        self.in_flight = 0  # both paths: submitted, not yet resolved
        self.demoted = False
        self.draining = False  # remove() in progress: refuse new submits
        self.last_active = time.monotonic()
        self.completed = 0
        self.failed = 0
        self.shed = 0
        self.deadline_missed = 0
        self.cobatched = 0  # requests answered by the co-batched fast path
        self.cobatch_degraded = 0  # co-batches this tenant degraded out of
        self.latency = telemetry.LatencyStats()
        self._seen_reasons: Tuple[str, ...] = ()
        # Precision-ladder bookkeeping (ISSUE 20): the tenant's current
        # rung ("f32"/"bf16"/"int8" — the host rung keeps the last
        # quantized rung beside demoted=True), per-tenant transition
        # tallies, and the worst quantization error ever measured (None
        # until the first quantization) — the metrics() tier sub-block.
        self.tier = "f32"
        self.tier_demotions = 0
        self.tier_restores = 0
        self.tier_rollbacks = 0
        self.quant_error_max: Optional[float] = None

    @property
    def bundle(self) -> ServingBundle:
        return self.engine.bundle

    def device_bytes(self) -> int:
        return _bundle_device_bytes(self.engine._state.bundle)

    def can_demote(self) -> bool:
        """Whether HBM-pressure eviction may pick this tenant: not
        already demoted, and no entity-sharded coordinate (a mesh-sharded
        matrix already divides over the fleet — pulling it whole into
        host RAM would change the placement story, and
        demote_bundle_to_host_tier refuses it loudly)."""
        if self.demoted:
            return False
        st = self.engine._state
        return all(kind != "re_sh" for kind in st.kinds)

    def can_quantize(self) -> bool:
        """Whether a precision-ladder step down may pick this tenant: not
        demoted, not already on the last quantized rung, no entity-
        sharded coordinate (quantize_bundle_rows refuses it loudly), and
        at least one replicated RE matrix left to shrink — an all-FE or
        all-two-tier tenant frees nothing by quantizing."""
        if self.demoted or self.tier == PRECISION_LADDER[-1]:
            return False
        st = self.engine._state
        if any(kind == "re_sh" for kind in st.kinds):
            return False
        return any(kind in ("re", "re_bf16") for kind in st.kinds)

    def signature(self) -> Optional[tuple]:
        """The co-batch compatibility key, or None when this tenant must
        dispatch solo: every coordinate "fe"/"re" (replicated single-tier
        — two-tier and mesh-sharded stores gather differently), no
        normalization (norm algebra folds per tenant and would break the
        shared-kernel bitwise argument), no lost shards (the solo path
        owns the pinned-zero remap), and not demoted."""
        if self.demoted:
            return None
        st = self.engine._state
        for k, c in enumerate(st.coords):
            if st.kinds[k] not in ("fe", "re"):
                return None
            if c.norm is not None:
                return None
            sh = getattr(c, "shard_health", None)
            if sh is not None and sh.any_lost:
                return None
        return (
            self.engine.task,
            st.kinds,
            tuple(c.dim for c in st.coords),
        )


class TenantRegistry:
    """N named tenants sharing one device fleet, with per-tenant
    isolation enforced in-process (see module doc). `admit()` stages a
    tenant, `submit(name, request)` routes one request, `close()` drains
    and joins every worker. One registry per fleet; tenant engines share
    ONE device mutex so concurrent multi-device dispatches interleave
    instead of deadlocking the collective rendezvous."""

    def __init__(
        self,
        *,
        max_batch: Optional[int] = None,
        max_wait_ms: Optional[float] = None,
        hbm_budget_bytes: Optional[int] = None,
        watchdog_ms_override: Optional[float] = None,
    ):
        # Both batching quantities are PLANNED (ISSUE 14): explicit
        # arguments win, None defers to the installed plan and then the
        # pre-planner defaults — the same deferral the engine/batcher use.
        from photon_ml_tpu import planner

        if max_batch is None:
            max_batch = int(planner.planned_value("serving_max_batch"))
        if max_wait_ms is None:
            max_wait_ms = float(planner.planned_value("serving_max_wait_ms"))
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.max_batch = int(max_batch)
        self.max_wait_s = float(max_wait_ms) / 1e3
        self.buckets = _bucket_sizes(self.max_batch)
        self._hbm_budget_override = hbm_budget_bytes
        self._watchdog_ms = (
            float(watchdog_ms()) if watchdog_ms_override is None
            else float(watchdog_ms_override)
        )
        self._watchdog = Watchdog()
        self._cv = threading.Condition()
        self._tenants: Dict[str, Tenant] = {}
        self._order = 0
        self._rr = 0  # weighted-fair rotation cursor
        self._stop = False
        self._unhealthy: Optional[BaseException] = None
        self._service_tail_s = 0.0
        self._cobatch_dispatches = 0
        self._cobatch_compiles = 0
        # ONE device mutex across every tenant engine: N flush threads
        # dispatching (possibly collective) programs over one fleet must
        # interleave, never overlap (the ISSUE 13 rendezvous deadlock,
        # now cross-engine).
        self._device_mutex = threading.Lock()

        # Private jit instance (the engine's per-instance trampoline
        # discipline): _cobatch_compiles honestly counts THIS registry's
        # cross-tenant programs.
        def _registry_cobatch_program(*args, **kwargs):
            return _cobatch_program(*args, **kwargs)

        donate = () if jax.default_backend() == "cpu" else (0, 1, 2, 3)
        self._jit = jax.jit(
            _registry_cobatch_program,
            static_argnames=("kinds", "task"),
            donate_argnums=donate,
        )
        self._thread = threading.Thread(
            target=self._dispatch_loop,
            name="photon-tenant-dispatch",
            daemon=True,
        )
        self._thread.start()

    # ------------------------------------------------------------ admission

    def _fleet_budget(self) -> Optional[int]:
        if self._hbm_budget_override is not None:
            return int(self._hbm_budget_override)
        budget = device_memory_budget_bytes()
        if budget is None:
            return None
        return int(budget * float(get_knob("PHOTON_TENANT_HBM_FRACTION")))

    def admit(
        self,
        name: str,
        bundle,
        *,
        max_pending: Optional[int] = None,
        deadline_ms: Optional[float] = None,
        weight: float = 1.0,
        inject_faults: bool = True,
        warm: bool = True,
        watchdog_ms_override: Optional[float] = None,
    ) -> Tenant:
        """Stage `bundle` (a ServingBundle or zero-arg builder) as tenant
        `name`. The fleet HBM budget is enforced BEFORE the new engine
        pins anything beyond the staged bundle: while over budget, the
        coldest demotable tenant's RE rows demote to the host tier
        (`tenant_evict` path — the tenant keeps answering bitwise;
        entity-sharded tenants are never victims); only a fleet that
        cannot fit after demoting every candidate refuses. Staging runs
        under the `tenant_admit` fault site with the bounded retry
        policy; ANY failure (staging exhausted, engine bring-up) leaves
        the registry without the new tenant — nothing staged stays
        pinned, though demotions already made to fit it are kept (a
        demoted tenant keeps answering bitwise from the host tier).
        `inject_faults=False` excludes this tenant's dispatches from an
        armed fault plan (chaos-drill targeting); `watchdog_ms_override`
        arms a per-tenant dispatch deadline."""
        with self._cv:
            if self._stop:
                raise RuntimeError("TenantRegistry is closed")
            if name in self._tenants:
                raise ValueError(f"tenant {name!r} already admitted")
        builder = bundle if callable(bundle) else None

        def _stage():
            faults.fault_point("tenant_admit")
            return builder() if builder is not None else bundle

        with telemetry.metric_label_scope(tenant=name):
            staged = faults.retry(_stage, label=f"tenant {name} admission")
        if getattr(staged, "released", False):
            raise ValueError(f"tenant {name!r} bundle is already released")

        # HBM pressure: demote, never fail, resident tenants to fit the
        # newcomer; refuse only when no demotion can free enough. With
        # PHOTON_TIER_LADDER on (ISSUE 20), each relief step walks the
        # coldest steppable tenant ONE precision rung down (quantize-in-
        # place before host-tier demotion); off keeps the PR 15 all-or-
        # nothing host demotion and the bitwise contract.
        ladder = bool(get_knob("PHOTON_TIER_LADDER"))
        demoted: List[str] = []
        need = _bundle_device_bytes(staged)
        budget = self._fleet_budget()
        try:
            while budget is not None:
                with self._cv:
                    have = sum(
                        t.device_bytes() for t in self._tenants.values()
                    )
                    victims = sorted(
                        (
                            t
                            for t in self._tenants.values()
                            if t.can_demote()
                            or (ladder and t.can_quantize())
                        ),
                        key=lambda t: (t.last_active, t.order),
                    )
                if have + need <= budget:
                    break
                if not victims:
                    raise HbmBudgetExceeded(
                        f"admitting tenant {name!r} needs {need} bytes "
                        f"beside {have} resident bytes (budget {budget}); "
                        "every demotable resident tenant is already on "
                        "the host tier"
                    )
                # Quantize-in-place is tried before host-tier demotion
                # FLEET-WIDE: the coldest quantizable tenant steps a rung
                # down first, even when an already-int8 tenant is colder
                # — otherwise the valve would walk one tenant straight
                # through to host while its neighbors still had lossless-
                # er rungs to give.
                victim = next(
                    (t for t in victims if ladder and t.can_quantize()),
                    victims[0],
                )
                if ladder and victim.can_quantize():
                    try:
                        self.demote_tier(
                            victim.name, reason="hbm_pressure"
                        )
                    except TierErrorCeilingExceeded:
                        # int8 would answer outside the characterized
                        # tolerance: fall through to the bitwise host
                        # tier for this victim's relief instead.
                        self.demote(victim.name, reason="hbm_pressure")
                else:
                    self.demote(victim.name, reason="hbm_pressure")
                demoted.append(victim.name)
        except BaseException:
            if builder is not None and staged is not None:
                try:
                    staged.release()
                except Exception:  # noqa: BLE001 - rollback best-effort
                    pass
            raise

        engine = None
        try:
            engine = ServingEngine(
                staged,
                max_batch=self.max_batch,
                inject_faults=inject_faults,
                device_mutex=self._device_mutex,
                watchdog_ms_override=watchdog_ms_override,
            )
            if warm:
                engine.warmup()
            quota = (
                int(get_knob("PHOTON_TENANT_MAX_PENDING"))
                if max_pending is None
                else int(max_pending)
            )
            batcher = engine.batcher(
                max_wait_ms=self.max_wait_s * 1e3,
                max_pending=quota,
                default_deadline_ms=deadline_ms,
                thread_name=f"photon-tenant-{name}-flush",
                metric_labels={"tenant": name},
            )
        except BaseException:
            # Engine bring-up failed (compile error, OOM at the budget
            # edge): the tenant is NOT admitted, so nothing may stay
            # pinned or threaded — close the half-built engine (joins
            # its watchdog/batchers) and release a builder-staged bundle
            # (a caller-owned prebuilt bundle stays the caller's).
            # Demotions already performed to make room are KEPT: demoted
            # tenants answer bitwise from the host tier, and re-promoting
            # them on this error path would thrash HBM for no request.
            if engine is not None:
                try:
                    engine.close()
                except Exception:  # noqa: BLE001 - rollback best-effort
                    pass
            if builder is not None:
                try:
                    staged.release()
                except Exception:  # noqa: BLE001 - rollback best-effort
                    pass
            raise
        with self._cv:
            t = Tenant(
                name,
                engine,
                batcher,
                quota=quota,
                deadline_ms=deadline_ms,
                weight=weight,
                order=self._order,
            )
            self._order += 1
            self._tenants[name] = t
        telemetry.emit_event(
            "tenant_admit",
            tenant=name,
            device_bytes=int(need),
            demoted_tenants=demoted,
        )
        logger.info(
            "tenant %r admitted: %.2f MB device-resident%s",
            name,
            need / 1e6,
            f" (demoted {demoted} to the host tier)" if demoted else "",
        )
        return t

    def demote(self, name: str, *, hot_rows: int = 0, reason: str = "manual") -> int:
        """Demote tenant `name`'s random-effect rows to the host tier
        (TwoTierEntityStore, `hot_rows` rows kept in HBM). The tenant
        keeps answering BITWISE throughout — the new generation pre-warms
        before the atomic flip, in-flight batches drain on the old one —
        and a terminal `tenant_evict` failure rolls back with the old
        generation still serving. Returns the device bytes freed."""
        t = self._tenant(name)
        if t.demoted:
            return 0
        # Serialize with hot-swaps on the engine's own swap mutex — a
        # model push and a demotion must order, not race, the state flip.
        with t.engine.bundle_manager.mutex:
            old_state = t.engine._state
            old_bytes = _bundle_device_bytes(old_state.bundle)

            def _build():
                faults.fault_point("tenant_evict")
                return demote_bundle_to_host_tier(
                    old_state.bundle, hot_rows=hot_rows
                )

            with telemetry.metric_label_scope(tenant=name):
                demoted_bundle = faults.retry(
                    _build, label=f"tenant {name} demotion"
                )
                new_state = t.engine._build_state(
                    demoted_bundle, version=old_state.version + 1
                )
                # Pre-warm the demoted generation's bucket programs (the
                # kinds changed re -> re2, so these ARE new programs) so
                # the flip compiles nothing on live traffic; the compile
                # delta bumps the warmup baseline like a hot-swap's.
                before = t.engine.compiles
                t.engine._warm_state(new_state)
                t.engine._commit_state(
                    new_state, baseline_bump=t.engine.compiles - before
                )
                t.demoted = True
                t.engine._drain_state(old_state, timeout_s=30.0)
                # close_stores=False: any store-bearing coordinate was
                # carried over INTO the demoted bundle, which owns it now.
                old_state.bundle.release(close_stores=False)
                faults.COUNTERS.increment("tenant_demotions")
        freed = old_bytes - _bundle_device_bytes(demoted_bundle)
        telemetry.emit_event(
            "tenant_evict",
            tenant=name,
            reason=reason,
            freed_bytes=int(freed),
            hot_rows=int(hot_rows),
        )
        logger.info(
            "tenant %r demoted to the host tier (%s): %.2f MB HBM freed",
            name,
            reason,
            freed / 1e6,
        )
        return int(freed)

    def restore(self, name: str, *, reason: str = "manual") -> int:
        """Promote a demoted tenant's random-effect rows back to full
        HBM residency (the exact inverse of `demote` — the rebuilt
        single-tier matrices come bitwise from the two-tier store's cold
        tier). Same discipline as demotion: serialized with hot-swaps on
        the engine's swap mutex, the restored generation pre-warms before
        the atomic flip, in-flight batches drain on the old one. The
        autopilot's HBM-ladder restore actuator (ISSUE 19). Returns the
        device bytes the restore re-pinned (0 if not demoted)."""
        t = self._tenant(name)
        if not t.demoted:
            return 0
        with t.engine.bundle_manager.mutex:
            old_state = t.engine._state
            old_bytes = _bundle_device_bytes(old_state.bundle)

            def _build():
                return promote_bundle_from_host_tier(old_state.bundle)

            with telemetry.metric_label_scope(tenant=name):
                restored_bundle = faults.retry(
                    _build, label=f"tenant {name} restore"
                )
                new_state = t.engine._build_state(
                    restored_bundle, version=old_state.version + 1
                )
                # The kinds changed back re2 -> re: these are new bucket
                # programs — pre-warm so the flip compiles nothing on
                # live traffic (the demotion's own discipline, inverted).
                before = t.engine.compiles
                t.engine._warm_state(new_state)
                t.engine._commit_state(
                    new_state, baseline_bump=t.engine.compiles - before
                )
                t.demoted = False
                # The cold tier holds the ORIGINAL f32 rows (a quantized
                # tenant's host demotion was built from its retained
                # host_f32 copy), so a host restore always lands on the
                # full-precision rung — quantized rungs are only
                # re-entered by a new demote_tier() (ISSUE 20).
                t.tier = "f32"
                t.engine._drain_state(old_state, timeout_s=30.0)
                # close_stores=True: the restored generation owns plain
                # device matrices — the old bundle's two-tier stores (and
                # their promotion workers) retire with it.
                old_state.bundle.release(close_stores=True)
                faults.COUNTERS.increment("tenant_restores")
        repinned = _bundle_device_bytes(restored_bundle) - old_bytes
        telemetry.emit_event(
            "tenant_restore",
            tenant=name,
            reason=reason,
            device_bytes=int(repinned),
        )
        logger.info(
            "tenant %r restored to HBM residency (%s): %.2f MB re-pinned",
            name,
            reason,
            repinned / 1e6,
        )
        return int(repinned)

    # ------------------------------------------------------ precision ladder

    def demote_tier(
        self, name: str, *, to: Optional[str] = None, reason: str = "manual"
    ) -> int:
        """Walk tenant `name` DOWN the precision ladder (ISSUE 20):
        f32 -> bf16 -> int8 -> host, one rung per call by default, or to
        the named rung `to` ("bf16"/"int8"/"host"). Each quantize step is
        the same stage->pre-warm->commit->drain generation flip as a
        hot-swap, under the `quantize_stage` fault site with the bounded
        retry policy — a terminal mid-quantize failure (or SIGKILL)
        leaves the OLD generation serving and counts `tier_rollbacks`.
        An int8 step whose measured round-trip error exceeds
        PHOTON_TIER_INT8_ERROR_CEILING raises `TierErrorCeilingExceeded`
        before commit (when walking past it to "host", the ceiling trip
        falls through to the bitwise host tier instead). The host rung
        delegates to `demote()` — the PR 15 whole-bundle host demotion,
        built from the retained ORIGINAL f32 rows, never a lossy plane.
        Returns total device bytes freed."""
        t = self._tenant(name)
        ladder = (*PRECISION_LADDER, "host")
        if to is not None and to not in ladder[1:]:
            raise ValueError(
                f"unknown precision rung {to!r} (ladder: {ladder[1:]})"
            )
        if t.demoted:
            return 0
        idx = ladder.index(t.tier)
        tgt = idx + 1 if to is None else ladder.index(to)
        if tgt <= idx:
            return 0
        freed = 0
        for rung in ladder[idx + 1 : tgt + 1]:
            if rung == "host":
                freed += self.demote(name, reason=reason)
                continue
            try:
                freed += self._quantize_step(t, rung, reason)
            except TierErrorCeilingExceeded:
                if tgt > ladder.index(rung):
                    # Walking past int8 anyway: the host rung below is
                    # bitwise — skip the refused rung, keep descending.
                    continue
                raise
        return int(freed)

    def restore_tier(
        self, name: str, *, to: str = "f32", reason: str = "manual"
    ) -> int:
        """Walk tenant `name` back UP the ladder toward `to` (default all
        the way to f32): host -> int8 -> bf16 -> f32, under the existing
        demote/restore discipline per step. The host rung delegates to
        `restore()`; quantized rungs rebuild under the `tier_restore`
        fault site — the final step to f32 is BITWISE (rebuilt from the
        retained original rows), intermediate re-quantizations
        (int8 -> bf16) re-round the same originals. Returns total device
        bytes re-pinned."""
        t = self._tenant(name)
        ladder = (*PRECISION_LADDER, "host")
        if to not in PRECISION_LADDER:
            raise ValueError(
                f"unknown precision rung {to!r} (ladder: {PRECISION_LADDER})"
            )
        repinned = 0
        if t.demoted:
            repinned += self.restore(name, reason=reason)
        tgt = ladder.index(to)
        while ladder.index(t.tier) > tgt:
            repinned += self._restore_step(
                t, ladder[ladder.index(t.tier) - 1], reason
            )
        return int(repinned)

    def _quantize_step(self, t: Tenant, rung: str, reason: str) -> int:
        """One committed rung down: quantize, pre-warm, flip, drain.
        Serialized with hot-swaps on the engine's swap mutex, like
        `demote()` — a model push and a ladder step must order, never
        race, the state flip."""
        from_tier = t.tier
        with t.engine.bundle_manager.mutex:
            old_state = t.engine._state
            old_bytes = _bundle_device_bytes(old_state.bundle)

            def _build():
                faults.fault_point("quantize_stage")
                return quantize_bundle_rows(old_state.bundle, rung)

            with telemetry.metric_label_scope(tenant=t.name):
                try:
                    new_bundle, errors = faults.retry(
                        _build, label=f"tenant {t.name} {rung} quantization"
                    )
                except BaseException:
                    # Retry exhausted mid-stage: nothing committed, the
                    # old generation never stopped serving.
                    t.tier_rollbacks += 1
                    faults.COUNTERS.increment("tier_rollbacks")
                    raise
                err_max = max(errors.values(), default=0.0)
                ceiling = float(
                    get_knob("PHOTON_TIER_INT8_ERROR_CEILING")
                )
                if rung == "int8" and err_max > ceiling:
                    new_bundle.release(close_stores=False)
                    t.tier_rollbacks += 1
                    faults.COUNTERS.increment("tier_rollbacks")
                    raise TierErrorCeilingExceeded(
                        f"tenant {t.name!r}: int8 round-trip error "
                        f"{err_max:.4g} exceeds the "
                        f"PHOTON_TIER_INT8_ERROR_CEILING of {ceiling}; "
                        f"staying at {from_tier!r}"
                    )
                for err in errors.values():
                    # Ambient tenant label: the per-tenant quantization-
                    # error histogram the characterized contract audits.
                    telemetry.METRICS.observe("tier_quant_error", err)
                new_state = t.engine._build_state(
                    new_bundle, version=old_state.version + 1
                )
                # The kinds changed re -> re_bf16/re_i8: new bucket
                # programs — pre-warm so the flip compiles nothing on
                # live traffic (the demotion's own discipline).
                before = t.engine.compiles
                t.engine._warm_state(new_state)
                t.engine._commit_state(
                    new_state, baseline_bump=t.engine.compiles - before
                )
                t.tier = rung
                t.tier_demotions += 1
                t.quant_error_max = max(t.quant_error_max or 0.0, err_max)
                t.engine._drain_state(old_state, timeout_s=30.0)
                old_state.bundle.release(close_stores=False)
                faults.COUNTERS.increment("tier_demotions")
        freed = old_bytes - _bundle_device_bytes(new_bundle)
        telemetry.emit_event(
            "tier_demote",
            tenant=t.name,
            from_tier=from_tier,
            to_tier=rung,
            reason=reason,
            freed_bytes=int(freed),
            evidence={
                "quant_error_max": err_max,
                "quantized_coordinates": len(errors),
            },
        )
        logger.info(
            "tenant %r stepped down the precision ladder %s -> %s (%s): "
            "%.2f MB HBM freed, worst round-trip error %.4g",
            t.name,
            from_tier,
            rung,
            reason,
            freed / 1e6,
            err_max,
        )
        return int(freed)

    def _restore_step(self, t: Tenant, rung: str, reason: str) -> int:
        """One committed rung up: rebuild toward `rung` from the retained
        original rows, pre-warm, flip, drain — under the `tier_restore`
        fault site. The step to "f32" is bitwise; int8 -> bf16 re-rounds
        the same originals (never the int8 plane)."""
        from_tier = t.tier
        with t.engine.bundle_manager.mutex:
            old_state = t.engine._state
            old_bytes = _bundle_device_bytes(old_state.bundle)

            def _build():
                faults.fault_point("tier_restore")
                if rung == "f32":
                    return restore_bundle_precision(old_state.bundle), {}
                return quantize_bundle_rows(old_state.bundle, rung)

            with telemetry.metric_label_scope(tenant=t.name):
                try:
                    new_bundle, errors = faults.retry(
                        _build, label=f"tenant {t.name} {rung} restore"
                    )
                except BaseException:
                    t.tier_rollbacks += 1
                    faults.COUNTERS.increment("tier_rollbacks")
                    raise
                for err in errors.values():
                    telemetry.METRICS.observe("tier_quant_error", err)
                new_state = t.engine._build_state(
                    new_bundle, version=old_state.version + 1
                )
                before = t.engine.compiles
                t.engine._warm_state(new_state)
                t.engine._commit_state(
                    new_state, baseline_bump=t.engine.compiles - before
                )
                t.tier = rung
                t.tier_restores += 1
                if errors:
                    t.quant_error_max = max(
                        t.quant_error_max or 0.0, max(errors.values())
                    )
                t.engine._drain_state(old_state, timeout_s=30.0)
                old_state.bundle.release(close_stores=False)
                faults.COUNTERS.increment("tier_restores")
        repinned = _bundle_device_bytes(new_bundle) - old_bytes
        telemetry.emit_event(
            "tier_restore",
            tenant=t.name,
            from_tier=from_tier,
            to_tier=rung,
            reason=reason,
            repinned_bytes=int(repinned),
            evidence={"quantized_coordinates": len(errors)},
        )
        logger.info(
            "tenant %r stepped up the precision ladder %s -> %s (%s): "
            "%.2f MB re-pinned",
            t.name,
            from_tier,
            rung,
            reason,
            repinned / 1e6,
        )
        return int(repinned)

    def retune(self, *, max_wait_ms: Optional[float] = None) -> Dict[str, float]:
        """Live-adjust the micro-batching flush wait (the autopilot's
        batch/wait retune actuator, ISSUE 19). Only the WAIT is mutable
        online: the bucket ladder is compiled state — changing max_batch
        live would recompile every program, which is a reshard-class
        action, not a retune. Returns the displaced values so a rollback
        can restore them."""
        with self._cv:
            prev = {"max_wait_ms": self.max_wait_s * 1e3}
            if max_wait_ms is not None:
                if max_wait_ms < 0:
                    raise ValueError("max_wait_ms must be >= 0")
                self.max_wait_s = float(max_wait_ms) / 1e3
                self._cv.notify_all()
        return prev

    # -------------------------------------------------------------- scoring

    def _tenant(self, name: str) -> Tenant:
        with self._cv:
            t = self._tenants.get(name)
        if t is None:
            raise KeyError(
                f"unknown tenant {name!r} (admitted: "
                f"{sorted(self._tenants)})"
            )
        return t

    def submit(
        self,
        name: str,
        request: ScoreRequest,
        *,
        block: bool = False,
        deadline_ms: Optional[float] = None,
    ) -> "Future[ScoreResult]":
        """Enqueue one request for tenant `name`. Sheds with a typed
        `Overloaded` NAMING the tenant once its quota is full
        (`block=True` backpressures instead); deadline budget defaults
        per request, then per tenant. Co-batch-eligible tenants ride the
        registry's weighted-fair cross-tenant dispatch; everyone else
        goes straight to their own micro-batcher."""
        t = self._tenant(name)
        fut: "Future[ScoreResult]" = Future()
        now = time.monotonic()
        budget_ms = (
            deadline_ms
            if deadline_ms is not None
            else (
                request.deadline_ms
                if request.deadline_ms is not None
                else t.deadline_ms
            )
        )
        expiry = None if budget_ms is None else now + budget_ms / 1e3
        with telemetry.metric_label_scope(tenant=name):
            eligible = t.signature() is not None
            with self._cv:
                first_pass = True
                while True:
                    if self._stop:
                        raise RuntimeError("TenantRegistry is closed")
                    if self._unhealthy is not None:
                        raise BatcherUnhealthy(
                            f"tenant dispatch thread died: "
                            f"{self._unhealthy!r}"
                        ) from self._unhealthy
                    if t.draining:
                        # remove() is draining this tenant: refuse loudly
                        # instead of racing the teardown (ISSUE 18 — a
                        # retired shadow tenant must never accept traffic).
                        raise KeyError(
                            f"tenant {name!r} is being removed; no new "
                            "submits accepted while it drains"
                        )
                    if first_pass and eligible:
                        # One admission fault per submit, after the
                        # closed/unhealthy checks (the micro-batcher fires
                        # its own site for the direct path). Gated per
                        # tenant so a chaos plan targets one tenant's
                        # admissions.
                        first_pass = False
                        try:
                            if t.engine.inject_faults:
                                faults.fault_point("admit")
                        except faults.InjectedFault as exc:
                            t.shed += 1
                            faults.COUNTERS.increment(
                                "serving_shed_requests"
                            )
                            raise Overloaded(
                                f"admission fault injected: {exc}",
                                tenant=name,
                            ) from exc
                    if t.in_flight < t.quota:
                        break
                    if not block:
                        t.shed += 1
                        faults.COUNTERS.increment("serving_shed_requests")
                        raise Overloaded(
                            f"tenant {name!r} pending quota full "
                            f"({t.quota} requests); shed by per-tenant "
                            "admission control",
                            tenant=name,
                        )
                    self._cv.wait()
                t.in_flight += 1
                t.last_active = now
                if eligible:
                    t.queue.append((request, fut, now, expiry))
                    self._cv.notify_all()
            if not eligible:
                self._submit_direct(t, request, fut, now, expiry, block)
        return fut

    def score(self, name: str, request: ScoreRequest) -> ScoreResult:
        return self.submit(name, request, block=True).result()

    def _submit_direct(
        self,
        t: Tenant,
        request: ScoreRequest,
        fut: Future,
        t0: float,
        expiry: Optional[float],
        block: bool,
    ) -> None:
        """Route one request straight to the tenant's own micro-batcher
        (solo path: demoted / sharded / normalized tenants), chaining its
        future to the registry's so accounting stays uniform."""
        remaining = None
        if expiry is not None:
            remaining = max(0.0, (expiry - time.monotonic()) * 1e3)
        try:
            inner = t.batcher.submit(
                request, block=block, deadline_ms=remaining
            )
        except Overloaded as exc:
            self._resolve(
                t, fut, None, t0,
                error=Overloaded(str(exc), tenant=t.name),
            )
            return
        except BaseException as exc:  # noqa: BLE001 - surfaced via future
            self._resolve(t, fut, None, t0, error=exc)
            return
        self._chain(t, fut, inner, t0)

    def _chain(self, t: Tenant, fut: Future, inner: Future, t0: float) -> None:
        def _done(inner_fut: Future) -> None:
            exc = inner_fut.exception()
            if exc is not None:
                if isinstance(exc, Overloaded) and exc.tenant is None:
                    exc = Overloaded(str(exc), tenant=t.name)
                elif isinstance(exc, DeadlineExceeded) and exc.tenant is None:
                    exc = DeadlineExceeded(str(exc), tenant=t.name)
                self._resolve(t, fut, None, t0, error=exc)
            else:
                self._resolve(t, fut, inner_fut.result(), t0)

        inner.add_done_callback(_done)

    def _resolve(
        self,
        t: Tenant,
        fut: Future,
        result: Optional[ScoreResult],
        t0: float,
        *,
        error: Optional[BaseException] = None,
        cobatched: bool = False,
    ) -> None:
        """The one completion path for every route: per-tenant latency +
        counters, in-flight release (wakes blocked submitters), future
        resolution."""
        wall_ms = (time.monotonic() - t0) * 1e3
        with self._cv:
            t.in_flight -= 1
            if error is None:
                t.completed += 1
                t.latency.record(wall_ms)
                if cobatched:
                    t.cobatched += 1
            else:
                if isinstance(error, DeadlineExceeded):
                    t.deadline_missed += 1
                elif isinstance(error, Overloaded):
                    t.shed += 1
                t.failed += 1
            self._cv.notify_all()
        self._note_health(t)
        if fut.done():
            return
        if error is None:
            # Labeled observe (ISSUE 19): the aggregate series is
            # unchanged; the per-tenant sub-histogram is what the
            # autopilot's p95 retune rule reads.
            telemetry.METRICS.observe(
                "serving_latency_ms", wall_ms, labels=(("tenant", t.name),)
            )
            fut.set_result(result)
        else:
            fut.set_exception(error)

    def _note_health(self, t: Tenant) -> None:
        """Journal newly-appeared per-tenant degradation reasons (the
        `tenant_degraded` event): the per-tenant isolation story needs
        WHICH tenant degraded on the record, not just a health flip."""
        reasons = tuple(t.engine.health.degraded_reasons)
        if reasons and reasons != t._seen_reasons:
            new = [r for r in reasons if r not in t._seen_reasons]
            if new:
                telemetry.emit_event(
                    "tenant_degraded", tenant=t.name, reasons=list(new)
                )
        t._seen_reasons = reasons

    # --------------------------------------------------------- dispatch loop

    def _dispatch_loop(self) -> None:
        try:
            self._dispatch_loop_inner()
        except BaseException as exc:  # noqa: BLE001 - terminal thread guard
            logger.error("tenant dispatch thread died: %r", exc)
            faults.COUNTERS.increment("serving_flush_thread_failures")
            with self._cv:
                self._unhealthy = exc
                doomed: List[Tuple[Tenant, _Pending]] = []
                for t in self._tenants.values():
                    while t.queue:
                        doomed.append((t, t.queue.popleft()))
                self._cv.notify_all()
            for t, (_, fut, t0, _) in doomed:
                if fut.set_running_or_notify_cancel():
                    self._resolve(t, fut, None, t0, error=exc)
            for t in self._tenants.values():
                t.engine.health.add_degraded(
                    f"tenant_dispatch_dead: {exc!r}"
                )

    def _dispatch_loop_inner(self) -> None:
        while True:
            with self._cv:
                while not self._stop and not self._ripe_locked():
                    self._cv.wait(timeout=self._wait_timeout_locked())
                if self._stop and not any(
                    t.queue for t in self._tenants.values()
                ):
                    return
                claimed, expired = self._claim_locked()
                self._cv.notify_all()
            for t, fut, t0 in expired:
                with telemetry.metric_label_scope(tenant=t.name):
                    faults.COUNTERS.increment("serving_deadline_misses")
                self._resolve(
                    t, fut, None, t0,
                    error=DeadlineExceeded(
                        "request expired in the tenant queue before "
                        "batch assembly",
                        tenant=t.name,
                    ),
                )
            if not claimed:
                continue
            # Partition by co-batch signature; each partition is one
            # device dispatch (a tenant whose signature changed since
            # submit re-routes through its own batcher inside).
            groups: Dict[tuple, List[Tuple[Tenant, _Pending]]] = {}
            stale: List[Tuple[Tenant, _Pending]] = []
            for t, item in claimed:
                sig = t.signature()
                if sig is None:
                    stale.append((t, item))
                else:
                    groups.setdefault(sig, []).append((t, item))
            for t, item in stale:
                self._fallback(t, [item])
            for sig, items in groups.items():
                self._dispatch_cobatch(sig, items)

    def _ripe_locked(self) -> bool:
        now = time.monotonic()
        pending = 0
        for t in self._tenants.values():
            if not t.queue:
                continue
            pending += len(t.queue)
            front = t.queue[0]
            if front[3] is not None and now >= front[3]:
                return True  # expired head: claim promptly to fail it
            if (now - front[2]) >= self.max_wait_s:
                return True
        return pending >= self.max_batch

    def _wait_timeout_locked(self) -> Optional[float]:
        wake: Optional[float] = None
        for t in self._tenants.values():
            if not t.queue:
                continue
            front = t.queue[0]
            w = front[2] + self.max_wait_s
            if front[3] is not None:
                w = min(w, front[3])
            wake = w if wake is None else min(wake, w)
        if wake is None:
            return None
        return max(0.0, wake - time.monotonic())

    def _claim_locked(self):
        """Weighted-fair claim: up to max_batch slots split across
        backlogged tenants proportionally to weight (each gets at least
        one), rotation-started so equal-weight tenants alternate who
        claims first; leftover slots round-robin. Expired and cancelled
        requests are filtered here, before a slot is assembled for them."""
        now = time.monotonic()
        horizon = now + self._service_tail_s
        backlogged = [t for t in self._tenants.values() if t.queue]
        claimed: List[Tuple[Tenant, _Pending]] = []
        expired: List[Tuple[Tenant, Future, float]] = []
        if not backlogged:
            return claimed, expired
        start = self._rr % len(backlogged)
        self._rr += 1
        order = backlogged[start:] + backlogged[:start]
        slots = self.max_batch
        total_w = sum(t.weight for t in order) or 1.0

        def _take(t: Tenant, n: int) -> int:
            took = 0
            while took < n and t.queue:
                item = t.queue.popleft()
                claim = item[1].set_running_or_notify_cancel()
                if not claim:
                    # Client-cancelled while queued: the future resolves
                    # itself, but the admission slot must be released
                    # HERE — _resolve never runs for a cancelled future,
                    # and a leaked in_flight count would wedge the
                    # tenant's quota shut forever.
                    t.in_flight -= 1
                    continue
                if item[3] is not None and horizon >= item[3]:
                    expired.append((t, item[1], item[2]))
                    continue
                claimed.append((t, item))
                took += 1
            return took

        for t in order:
            if slots <= 0:
                break
            share = max(1, int(self.max_batch * t.weight / total_w))
            slots -= _take(t, min(share, slots))
        while slots > 0:
            progressed = False
            for t in order:
                if slots <= 0:
                    break
                got = _take(t, 1)
                slots -= got
                progressed = progressed or bool(got)
            if not progressed:
                break
        if expired and not claimed:
            # Same decay rule as the micro-batcher: an expiry round with
            # no dispatch must re-probe the true service time, or a
            # one-off spike pre-fails short-budget requests forever.
            self._service_tail_s *= 0.5
        return claimed, expired

    def _fallback(
        self, t: Tenant, items: Sequence[_Pending], *, degraded: bool = False
    ) -> None:
        """Route claimed items to the tenant's own micro-batcher (which
        owns the retry / FE-only / circuit policy). Called for stale
        signatures, circuit-open tenants, per-tenant injected faults, and
        whole-co-batch failures — isolation means ONLY this tenant's
        items re-route."""
        with telemetry.metric_label_scope(tenant=t.name):
            if degraded:
                t.cobatch_degraded += 1
                faults.COUNTERS.increment("serving_degraded_batches")
            now = time.monotonic()
            for req, fut, t0, expiry in items:
                if expiry is not None and now >= expiry:
                    self._resolve(
                        t, fut, None, t0,
                        error=DeadlineExceeded(
                            "request expired before its co-batch fallback",
                            tenant=t.name,
                        ),
                    )
                    continue
                remaining = (
                    None if expiry is None else (expiry - now) * 1e3
                )
                try:
                    inner = t.batcher.submit(
                        req, block=False, deadline_ms=remaining
                    )
                except Overloaded as exc:
                    self._resolve(
                        t, fut, None, t0,
                        error=Overloaded(str(exc), tenant=t.name),
                    )
                except BaseException as exc:  # noqa: BLE001 - via future
                    self._resolve(t, fut, None, t0, error=exc)
                else:
                    self._chain(t, fut, inner, t0)

    def _dispatch_cobatch(
        self, sig: tuple, items: List[Tuple[Tenant, _Pending]]
    ) -> None:
        """One cross-tenant device dispatch. Group membership is EVERY
        registry tenant sharing the signature (stable program shapes —
        an idle member still contributes its parameter arrays), slots
        carry the claimed items. Per-tenant fault sites fire inside the
        tenant's label scope and degrade ONLY that tenant's slice to its
        solo path; a whole-dispatch failure (device error, watchdog
        DeviceHang) degrades every slice to its OWN tenant's batcher —
        one tenant's blast radius never fails another's future."""
        with self._cv:
            members = sorted(
                (
                    t
                    for t in self._tenants.values()
                    if t.signature() == sig
                ),
                key=lambda t: t.order,
            )
        member_index = {t.name: j for j, t in enumerate(members)}
        # Circuit routing + per-tenant permits: an open breaker routes
        # the tenant's items through its batcher (FE-only answers there).
        by_tenant: Dict[str, List[_Pending]] = {}
        for t, item in items:
            by_tenant.setdefault(t.name, []).append(item)
        live: List[Tuple[Tenant, List[_Pending]]] = []
        permits: Dict[str, object] = {}
        for name, t_items in by_tenant.items():
            t = self._tenants[name]
            if name not in member_index:
                self._fallback(t, t_items)
                continue
            permit = t.engine.breaker.acquire()
            if permit is None:
                self._fallback(t, t_items)
                continue
            permits[name] = permit
            live.append((t, t_items))
        if not live:
            return

        # Per-tenant engine-state snapshots (active++ so a concurrent
        # demotion's drain waits for this dispatch). The inner dispatch
        # pops permits as it resolves them, so the set of tenants whose
        # active count must be released is captured HERE.
        states = {}
        active_names = set(permits)
        for t in members:
            with t.engine._lock:
                st = t.engine._state
                if t.name in active_names:
                    st.active += 1
                states[t.name] = st
        try:
            self._dispatch_cobatch_inner(
                sig, members, member_index, live, permits, states
            )
        finally:
            for t in members:
                if t.name in active_names:
                    with t.engine._lock:
                        states[t.name].active -= 1
                        t.engine._lock.notify_all()

    def _dispatch_cobatch_inner(
        self, sig, members, member_index, live, permits, states
    ) -> None:
        task, kinds, dims = sig
        # Per-tenant pack: lookup faults fire per tenant inside its label
        # scope; an injected lookup degrades ONLY that tenant's slice.
        packed: List[Tuple[Tenant, _Pending, int, List]] = []
        survivors: List[Tuple[Tenant, List[_Pending]]] = []
        for t, t_items in live:
            st = states[t.name]
            try:
                with telemetry.metric_label_scope(tenant=t.name):
                    if t.engine.inject_faults:
                        faults.fault_point("lookup")
                        faults.fault_point("score")
                    rows_cold = self._lookup_tenant(st, t_items)
            except faults.InjectedFault:
                t.engine.breaker.on_abandon(permits.pop(t.name))
                self._fallback(t, t_items, degraded=True)
                continue
            survivors.append((t, t_items))
            for item, rc in zip(t_items, rows_cold):
                packed.append((t, item, member_index[t.name], rc))
        if not packed:
            return

        n = len(packed)
        # The claim phase bounds every round at max_batch slots total, so
        # a partition can never exceed the bucket ladder.
        assert n <= self.max_batch, (n, self.max_batch)
        bucket = next(b for b in self.buckets if b >= n)
        t_d = time.monotonic()
        try:
            total, means, cold_flags = self._pack_and_dispatch(
                sig, members, states, packed, bucket, survivors
            )
        except BaseException as exc:  # noqa: BLE001 - isolated below
            # A whole-dispatch failure is ambiguous across tenants, and a
            # malformed request poisons the shared PACK exactly like a
            # device error poisons the shared program — so the guard
            # covers packing AND dispatch: abandon every permit and let
            # each tenant's OWN solo path judge its own requests (the
            # micro-batcher's per-request isolation fails only the
            # offending future). The isolation contract is that no
            # tenant's future fails — and the dispatch thread never dies
            # — because of a co-batched neighbor.
            logger.warning(
                "co-batch of %d across %d tenant(s) degraded to solo "
                "dispatch: %s",
                n,
                len(survivors),
                exc,
            )
            for t, t_items in survivors:
                t.engine.breaker.on_abandon(permits.pop(t.name))
                self._fallback(t, t_items, degraded=True)
            return
        t_done = time.monotonic()
        with self._cv:
            self._cobatch_dispatches += 1
            self._cobatch_compiles = int(self._jit._cache_size())
            # Decaying max of dispatch service time (claim -> answers),
            # the micro-batcher's deadline-horizon estimate.
            self._service_tail_s = max(
                t_done - t_d, 0.9 * self._service_tail_s
            )
        faults.COUNTERS.increment("tenant_cobatch_dispatches")
        for t, _ in survivors:
            t.engine.breaker.on_success(permits.pop(t.name))
        for i, (t, item, _, rc) in enumerate(packed):
            flags = cold_flags[i]
            res = ScoreResult(
                score=float(total[i]),
                mean=float(means[i]),
                uid=item[0].uid,
                cold_start=bool(flags.any()),
                n_cold=int(flags.sum()),
                fe_only=False,
            )
            self._resolve(t, item[1], res, item[2], cobatched=True)

    def _pack_and_dispatch(
        self, sig, members, states, packed, bucket, survivors
    ):
        """Assemble the shared bucket (per-coordinate feature buffers,
        per-tenant row arrays, tenant ids) and run ONE device dispatch.
        Raises on ANY failure — packing a malformed payload included —
        and the caller degrades every tenant's slice to its own solo
        path; nothing here may kill the dispatch thread."""
        task, kinds, dims = sig
        n = len(packed)
        offsets = np.zeros(bucket, np.float32)
        tids = np.zeros(bucket, np.int32)
        feats = [np.zeros((bucket, d), np.float32) for d in dims]
        re_positions = [k for k, kind in enumerate(kinds) if kind == "re"]
        rows = {
            k: [
                np.full(
                    bucket,
                    states[m.name].coords[k].unseen_row,
                    np.int32,
                )
                for m in members
            ]
            for k in re_positions
        }
        cold_flags = np.zeros((bucket, len(re_positions)), bool)
        for i, (t, item, tj, rc) in enumerate(packed):
            req = item[0]
            offsets[i] = req.offset
            tids[i] = tj
            st = states[t.name]
            for k, c in enumerate(st.coords):
                payload = req.features.get(c.shard)
                if payload is None:
                    continue
                if isinstance(payload, tuple):
                    idx, vals = payload
                    np.add.at(
                        feats[k][i], np.asarray(idx, np.int64), vals
                    )
                else:
                    feats[k][i, :] = payload
            for j, k in enumerate(re_positions):
                rows[k][tj][i] = rc[j]
                cold_flags[i, j] = rc[j] == st.coords[k].unseen_row

        params = tuple(
            tuple(states[m.name].coords[k].params for m in members)
            for k in range(len(kinds))
        )
        rows_arg = tuple(
            tuple(jnp.asarray(r) for r in rows[k]) if k in rows else None
            for k in range(len(kinds))
        )
        with telemetry.span(
            "tenant_cobatch",
            size=n,
            bucket=bucket,
            tenants=[t.name for t, _ in survivors],
        ):
            with self._watchdog.guard(
                self._watchdog_ms,
                f"tenant co-batch dispatch (bucket {bucket})",
            ):
                with self._device_mutex:
                    total, means = self._jit(
                        jnp.asarray(offsets),
                        jnp.asarray(tids),
                        tuple(jnp.asarray(f) for f in feats),
                        rows_arg,
                        params,
                        kinds=kinds,
                        task=task,
                    )
                total, means = jax.device_get((total, means))
        return np.asarray(total), np.asarray(means), cold_flags

    def _lookup_tenant(self, state, t_items) -> List[List[int]]:
        """Resolve one tenant's claimed items to per-RE-position rows
        (shard-load telemetry recorded exactly like the solo path)."""
        out = [[] for _ in t_items]
        for k, c in enumerate(state.coords):
            if not c.is_random_effect:
                continue
            ids = [
                item[0].entity_ids.get(c.random_effect_type)
                for item in t_items
            ]
            resolved, _ = c.lookup_rows(ids)
            sh = getattr(c, "shard_health", None)
            if sh is not None:
                sh.record_loads(resolved, c.unseen_row)
            for i, r in enumerate(resolved):
                out[i].append(int(r))
        return out

    # -------------------------------------------------------------- metrics

    def metrics(self) -> Dict[str, object]:
        """One snapshot: registry-level co-batch accounting plus a
        per-tenant block zipping TENANT_BLOCK_KEYS (the serving-summary
        `tenants` block consumes it:
        every key always present so absence is loud)."""
        with self._cv:
            tenants = list(self._tenants.values())
            cobatch = self._cobatch_dispatches
        wd_labeled = telemetry.METRICS.labeled_counters("watchdog_trips")
        out: Dict[str, object] = {
            "n_tenants": len(tenants),
            "max_batch": self.max_batch,
            "cobatch_dispatches": cobatch,
            "cobatch_compiles": self._cobatch_compiles,
            "tenants": {},
        }
        for t in tenants:
            bm = t.batcher.metrics()
            health = t.engine.health.snapshot()
            block = {
                "completed": t.completed,
                "failed": t.failed,
                # Registry-side tallies only: every shed/deadline outcome
                # resolves through the registry future (submit raise,
                # claim expiry, or a chained batcher error), so adding
                # the batcher's own counters would double-count fallback
                # rejections.
                "shed": t.shed,
                "deadline_missed": t.deadline_missed,
                "fe_only_answers": int(bm["fe_only_answers"]),
                "degraded_batches": (
                    t.cobatch_degraded + int(bm["degraded_batches"])
                ),
                "cobatched_requests": t.cobatched,
                "p50_ms": (
                    round(float(t.latency.percentile(50.0)), 4)
                    if t.latency.count
                    else None
                ),
                "p95_ms": (
                    round(float(t.latency.percentile(95.0)), 4)
                    if t.latency.count
                    else None
                ),
                "p99_ms": (
                    round(float(t.latency.percentile(99.0)), 4)
                    if t.latency.count
                    else None
                ),
                "state": health["state"],
                "degraded_reasons": health["degraded_reasons"],
                "circuit_state": t.engine.breaker.snapshot()[
                    "circuit_state"
                ],
                "demoted": t.demoted,
                "device_bytes": t.device_bytes(),
                "watchdog_trips": int(
                    wd_labeled.get(f"tenant={t.name}", 0)
                ),
                # Precision-ladder sub-block (ISSUE 20): the tenant's
                # rung + ladder history, TIER_BLOCK_KEYS order.
                "tier": {
                    "tier": t.tier,
                    "quantized_coords": sum(
                        1
                        for k in t.engine._state.kinds
                        if k in ("re_bf16", "re_i8")
                    ),
                    "demotions": t.tier_demotions,
                    "restores": t.tier_restores,
                    "rollbacks": t.tier_rollbacks,
                    "quant_error_max": t.quant_error_max,
                },
            }
            assert set(block) == set(TENANT_BLOCK_KEYS), (
                "tenant metrics block drifted from utils/contracts."
                "TENANT_BLOCK_KEYS"
            )
            assert set(block["tier"]) == set(TIER_BLOCK_KEYS), (
                "tenant tier sub-block drifted from utils/contracts."
                "TIER_BLOCK_KEYS"
            )
            out["tenants"][t.name] = block
        return out

    # ------------------------------------------------------------ lifecycle

    @property
    def tenant_names(self) -> List[str]:
        with self._cv:
            return list(self._tenants)

    def tenant(self, name: str) -> Tenant:
        return self._tenant(name)

    def remove(
        self,
        name: str,
        *,
        release_bundle: bool = False,
        drain_timeout_s: float = 30.0,
    ) -> None:
        """Retire ONE tenant while the rest of the fleet keeps serving
        (ISSUE 18: a rejected shadow challenger is torn down with zero
        champion impact). New submits refuse immediately; queued and
        in-flight requests drain to completion (the dispatch thread may
        hold claimed items, so the tenant entry stays visible until
        in-flight hits zero — deleting early would strand them); then the
        tenant's engine closes (batcher + watchdog join there) and its
        bundle is optionally released. A tenant that cannot drain within
        `drain_timeout_s` raises loudly and stays admitted."""
        t = self._tenant(name)
        deadline = time.monotonic() + drain_timeout_s
        with self._cv:
            t.draining = True
            self._cv.notify_all()
            while t.queue or t.in_flight:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    t.draining = False
                    raise RuntimeError(
                        f"tenant {name!r} did not drain within "
                        f"{drain_timeout_s}s ({len(t.queue)} queued, "
                        f"{t.in_flight} in flight); still admitted"
                    )
                self._cv.wait(timeout=min(0.1, remaining))
            del self._tenants[name]
        t.engine.close()
        if release_bundle and not t.engine._state.bundle.released:
            t.engine._state.bundle.release()

    def close(self, release_bundles: bool = False) -> None:
        """Drain the co-batch queue (pending requests still answered),
        join the dispatch thread, close every tenant's engine (its
        batcher + watchdog join there) and the registry watchdog.
        Idempotent."""
        with self._cv:
            if self._stop:
                return
            self._stop = True
            self._cv.notify_all()
        self._thread.join()
        with self._cv:
            tenants = list(self._tenants.values())
        for t in tenants:
            t.engine.close()
            if release_bundles and not t.engine._state.bundle.released:
                t.engine._state.bundle.release()
        self._watchdog.close()

    def __enter__(self) -> "TenantRegistry":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False
