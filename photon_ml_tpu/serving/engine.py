"""Online scoring engine: jitted padded-bucket programs over a pinned bundle.

Design constraints (the DrJAX lesson from PAPERS.md — fixed, jit-stable
program shapes — applied to a serving hot path):

  * The compile set is BOUNDED and declared up front: one XLA program per
    power-of-two bucket size up to `max_batch`. A batch of n requests pads
    to the smallest bucket >= n; after `warmup()` has compiled every
    bucket, a request stream of arbitrary batch sizes triggers ZERO new
    compiles (`recompiles_after_warmup` in metrics, asserted in tests).
  * One device round trip per batch: pack host-side, upload the request
    buffers, dispatch one fused program (all coordinates + link function),
    fetch (scores, means) together.
  * Bitwise offline parity: the fused program reuses the transformer's own
    margin kernels (`dense_margins`, `random_effect_margins`) and sums
    coordinates in the same order, and those kernels are batch-size
    invariant (see dense_margins' docstring) — so a request scores
    bitwise-identically to `GameTransformer.transform` on the same row,
    whatever bucket it pads into. That also makes scores independent of
    micro-batch composition, which is what lets the batcher degrade to
    per-request dispatch under faults without changing any answer.
  * Cold start: entities absent from the bundle's hash index gather the
    pinned zero row, i.e. score with the fixed effects (+ offset) only —
    GLMix's prior-model semantics for unseen entities. Counted per lookup
    and surfaced per request.
  * Request buffers are donated to the program on accelerator backends
    (they are per-batch scratch; donation lets XLA reuse the HBM). Model
    planes are never donated — they are the bundle's pinned state.

Lifecycle tier (serving/lifecycle.py) additions on top of PR 4:

  * The bundle is no longer construction-pinned: every batch snapshots an
    immutable `_EngineState` (bundle + derived coordinate metadata), and a
    `BundleManager.swap()` flips that snapshot atomically between batches
    — in-flight batches finish on the generation they started on, which
    the per-state in-flight counter drains before the old bundle is
    released.
  * `score_batch_fe_only` is the circuit-open degradation tier: every
    random-effect lookup is forced to the pinned zero row and no fault
    site fires in the path, so it keeps answering (bitwise-equal to
    FE-only `GameTransformer` output) while the full path is broken.
  * `health` (STARTING/READY/DEGRADED/DRAINING/CLOSED) and `breaker` (the
    circuit over the lookup/score fault sites) surface through
    `metrics()`.

Fault sites: `lookup` (entity-row resolution) and `score` (device
dispatch), via utils/faults.py. The engine itself raises; degradation
policy (retry, per-request fallback, circuit routing) lives in the batcher
so direct callers keep raw failure semantics.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.game.model import random_effect_margins
from photon_ml_tpu.ops.losses import mean_for_task
from photon_ml_tpu.serving.bundle import ScoreRequest, ServingBundle, ServingCoordinate
from photon_ml_tpu.serving.lifecycle import (
    BundleManager,
    CircuitBreaker,
    HealthStateMachine,
    ServingState,
)
from photon_ml_tpu.transformers.game_transformer import dense_margins
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils import faults, telemetry
from photon_ml_tpu.utils.observability import TimingRegistry, stage_scope, stage_timer
from photon_ml_tpu.utils.watchdog import Watchdog, watchdog_ms

Array = jax.Array


@dataclasses.dataclass
class ScoreResult:
    """One answered request: raw summed margin + link-function mean
    (ScoredGameDatum fields), plus cold-start accounting. `fe_only` marks
    an answer produced by the circuit-open fixed-effect-only tier (the
    score is the FE-only score, NOT the full-model one)."""

    score: float
    mean: float
    uid: Optional[str] = None
    cold_start: bool = False  # any random-effect lookup fell back
    n_cold: int = 0  # how many of the request's RE lookups fell back
    fe_only: bool = False
    # How many of the fallbacks were shard-loss degradations (the row is
    # RESIDENT in the artifact but its shard is marked LOST on this
    # server) — distinct from genuine cold starts, which no replica could
    # answer. A multi-host merge prefers the answer with the fewest.
    n_lost: int = 0


@dataclasses.dataclass
class _EngineState:
    """One bundle generation's scoring state. Immutable after build except
    `active` (in-flight batch count, guarded by the engine lock) — the
    swap drain waits on it before releasing the generation's bundle.

    `kinds` name each coordinate's storage mode and pick its margin kernel:
    "fe" (weight vector), "re" (single-tier matrix), "re_sh" (row-sharded
    matrix over `meshes[k]` — the fused program becomes a pjit program over
    the mesh), "re2" (two-tier hot/cold store), "re_bf16"/"re_i8"
    (precision-ladder quantized planes, dequantized inside the fused
    program — ISSUE 20)."""

    bundle: ServingBundle
    coords: List[ServingCoordinate]
    kinds: Tuple[str, ...]
    coord_shards: Tuple[str, ...]
    shard_dims: Dict[str, int]
    meshes: Tuple[Optional[object], ...] = ()
    version: int = 0
    active: int = 0


def _score_program(
    offsets,
    shard_feats,
    rows,
    overrides,
    params,
    norms,
    *,
    kinds,
    shards,
    meshes,
    task,
):
    """The fused per-bucket program: offsets + per-coordinate margins (same
    kernels and summation order as GameTransformer.transform) + link mean.

    Request features arrive as ONE buffer per shard (`shard_feats`), with
    coordinates resolving their shard by the static `shards` tuple — never
    as a per-coordinate tuple, which would pass the same device array
    twice when two coordinates share a shard and make buffer donation
    alias one buffer to two parameters (undefined on accelerators).

    Storage-mode kernels, all BITWISE-equal to the single-tier path:
      * "re_sh": the row-sharded matrix is read via the psum
        broadcast-gather (exact row movement over the mesh —
        game.model.random_effect_margins_bcast) so no device materializes
        the full (E + 1, D) matrix;
      * "re2": rows resolve against the hot-tier snapshot, with cold-tier
        hits overridden by the rows the pack stage copied out of host RAM
        (`overrides[k]` = (values, flags)) — the override row IS the
        matrix row, so the margin is unchanged."""
    from photon_ml_tpu.game.model import (
        _random_effect_margins_bcast_impl,
        gathered_row_margins,
    )

    total = offsets
    for k, kind in enumerate(kinds):
        feats = shard_feats[shards[k]]
        if kind == "fe":
            total = total + dense_margins(feats, params[k], norms[k])
        elif kind == "re_sh":
            total = total + _random_effect_margins_bcast_impl(
                feats, rows[k], params[k], norms[k], mesh=meshes[k]
            )
        elif kind == "re2":
            ovr_vals, ovr_flags = overrides[k]
            w = params[k][rows[k]]
            w = jnp.where(ovr_flags[:, None], ovr_vals, w)
            total = total + gathered_row_margins(feats, w, norms[k])
        elif kind == "re_bf16":
            # Quantized rung (ISSUE 20): the gathered bf16 rows widen to
            # f32 INSIDE the fused program — one extra cast on (B, dim)
            # request rows, never a host-side dequant of the full matrix.
            w = params[k][rows[k]].astype(jnp.float32)
            total = total + gathered_row_margins(feats, w, norms[k])
        elif kind == "re_i8":
            # int8 rung: params[k] is (int8 plane, per-row f32 scales);
            # dequant is fused per gathered row — widen + one broadcast
            # multiply by the row's symmetric scale.
            plane, scales = params[k]
            w = plane[rows[k]].astype(jnp.float32) * scales[rows[k]][:, None]
            total = total + gathered_row_margins(feats, w, norms[k])
        else:
            total = total + random_effect_margins(
                feats, rows[k], params[k], norms[k]
            )
    return total, mean_for_task(task, total)


def _bucket_sizes(max_batch: int) -> Tuple[int, ...]:
    sizes = []
    b = 1
    while b < max_batch:
        sizes.append(b)
        b <<= 1
    sizes.append(max_batch)
    return tuple(sizes)


class ServingEngine:
    """Scores request batches against a swappable pinned `ServingBundle`.

    Thread-safety: `score_batch` may be called from any thread (the
    batcher's flush thread, a caller's worker pool); metrics updates are
    lock-protected, and each batch runs against one atomic state snapshot.
    One engine owns one private jit cache, so `compiles` counts exactly
    this engine's XLA programs.
    """

    def __init__(
        self,
        bundle: ServingBundle,
        *,
        max_batch: Optional[int] = None,
        task: Optional[TaskType] = None,
        circuit_threshold: int = 5,
        circuit_probe_interval_s: float = 1.0,
        watchdog_ms_override: Optional[float] = None,
        inject_faults: bool = True,
        device_mutex: Optional[threading.Lock] = None,
    ):
        # The compiled-bucket ceiling is a PLANNED quantity (ISSUE 14):
        # an explicit argument wins (the operator/test said so); None
        # defers to the installed plan's serving_max_batch (observed-p95
        # batch size rounded up) and falls back to the pre-planner
        # default. The bucket SET is the power-of-two ladder up to it.
        if max_batch is None:
            from photon_ml_tpu import planner

            max_batch = int(planner.planned_value("serving_max_batch"))
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.task = task or bundle.task
        self.max_batch = int(max_batch)
        self.buckets = _bucket_sizes(self.max_batch)
        # Per-engine jit instance = private compile cache, so _cache_size()
        # is an honest XLA-compile counter for THIS engine. jit caches key
        # on the underlying callable, and wrappers over the same module
        # function SHARE entries — a fresh per-engine trampoline keeps this
        # engine's count isolated from every other engine in the process.
        def _engine_score_program(*args, **kwargs):
            return _score_program(*args, **kwargs)

        # Donate the per-batch request scratch (offsets, shard buffers,
        # rows, two-tier overrides) — never the model planes.
        donate = () if jax.default_backend() == "cpu" else (0, 1, 2, 3)
        self._jit = jax.jit(
            _engine_score_program,
            static_argnames=("kinds", "shards", "meshes", "task"),
            donate_argnums=donate,
        )
        self.stages = TimingRegistry()
        # Condition, not Lock: the hot-swap drain waits on per-state
        # in-flight counts reaching zero (notified by score_batch exits).
        self._lock = threading.Condition()
        # Multi-device program dispatches serialize on this mutex: two
        # host threads concurrently launching collective programs over
        # overlapping device sets (live traffic + a reshard's pre-warm of
        # the NEW mesh's pjit programs) can deadlock the runtime's
        # participant rendezvous — the warm path and the score path must
        # interleave, never overlap. Uncontended cost: one lock hop per
        # batch. The multi-tenant registry (serving/tenancy.py) passes
        # ONE shared mutex to every tenant engine for the same reason:
        # N tenant flush threads dispatching collective programs over the
        # same fleet must interleave across engines too.
        self._device_mutex = (
            device_mutex if device_mutex is not None else threading.Lock()
        )
        # Per-engine fault-injection gate (ISSUE 15): the process-global
        # fault plan fires at this engine's lookup/score sites only when
        # True. The multi-tenant chaos drills use it to CONFINE an armed
        # plan to one tenant's dispatches — the isolation proof needs
        # deterministic targeting, and site invocation counters are
        # process-wide. Production engines leave it True (an unarmed
        # fault_point is a free no-op).
        self.inject_faults = bool(inject_faults)
        self._state = self._build_state(bundle, version=0)
        self.health = HealthStateMachine()
        self.breaker = CircuitBreaker(
            threshold=circuit_threshold,
            probe_interval_s=circuit_probe_interval_s,
            on_open=lambda: self.health.add_degraded("circuit_open"),
            on_close=lambda: self.health.clear_degraded("circuit_open"),
        )
        self._bundle_manager: Optional[BundleManager] = None
        self._reshard_orchestrator = None
        self._requests = 0
        self._batches = 0
        self._lookups = 0
        self._cold_lookups = 0
        self._slots_total = 0
        self._slots_padded = 0
        self._fe_only_requests = 0
        self._shard_loss_fallbacks = 0
        # Hang watchdog around live-traffic dispatches (PHOTON_WATCHDOG_MS,
        # constructor override for tests; 0 = off). Warmup and the FE-only
        # degradation tier are exempt: compiles legitimately exceed a
        # serving deadline, and the degraded tier must keep answering —
        # warm up BEFORE arming a tight deadline on live traffic.
        self._watchdog_ms = (
            float(watchdog_ms()) if watchdog_ms_override is None
            else float(watchdog_ms_override)
        )
        self._watchdog = Watchdog(on_trip=self._on_watchdog_trip)
        self._hang_seen = False
        self._warmup_compiles: Optional[int] = None
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None
        self._batchers: List[object] = []
        self._closed = False

    # ----------------------------------------------------------- lifecycle

    @property
    def bundle(self) -> ServingBundle:
        """The ACTIVE bundle generation (swappable; snapshot per batch)."""
        return self._state.bundle

    @property
    def bundle_version(self) -> int:
        return self._state.version

    @property
    def bundle_manager(self) -> BundleManager:
        """The engine's hot-swap manager (created on first use)."""
        with self._lock:
            if self._bundle_manager is None:
                self._bundle_manager = BundleManager(self)
            return self._bundle_manager

    @property
    def reshard_orchestrator(self):
        """The engine's live mesh-elasticity orchestrator (created on
        first use; serving/reshard.py): shrink/grow the coefficient shard
        layout or rebalance the two-tier hot set under live traffic,
        serialized with bundle hot-swaps on the manager's mutex."""
        manager = self.bundle_manager  # created first: shares its mutex
        with self._lock:
            if self._reshard_orchestrator is None:
                from photon_ml_tpu.serving.reshard import (
                    MeshReshardOrchestrator,
                )

                self._reshard_orchestrator = MeshReshardOrchestrator(self)
            return self._reshard_orchestrator

    def batcher(self, **kwargs) -> "MicroBatcher":  # noqa: F821
        """Create a MicroBatcher bound to this engine; `close()` joins it."""
        if self._closed:
            # close() already ran and will never revisit _batchers — a
            # batcher created now would leak its flush thread.
            raise RuntimeError("ServingEngine is closed")
        from photon_ml_tpu.serving.batcher import MicroBatcher

        b = MicroBatcher(self, **kwargs)
        self._batchers.append(b)
        return b

    def close(self) -> None:
        """Graceful drain-on-shutdown: DRAINING while every batcher created
        via `batcher()` answers its pending futures and joins its flush
        thread, then CLOSED. Idempotent. The bundle stays usable — model
        planes are plain device arrays owned by the bundle, not the
        engine."""
        if self._closed:
            return
        self._closed = True
        self.health.begin_drain()
        for b in self._batchers:
            b.close()
        self._watchdog.close()
        self.health.close()

    def _on_watchdog_trip(self, label: str) -> None:
        """A device dispatch blew its deadline — fired FROM the monitor
        thread while the dispatch may still be stuck, so a hung-forever
        device flips health immediately; the next successful dispatch
        clears the reason."""
        self._hang_seen = True
        self.health.add_degraded("device_hang")

    # --------------------------------------------------- shard loss/recovery

    def mark_shard_lost(self, cid: str, shard_index: int) -> Tuple[int, int]:
        """Record one coefficient shard LOST (see ServingBundle): its
        entities degrade to bitwise FE-only pinned-zero-row answers, the
        engine stays up, health reports DEGRADED with the shard named."""
        rng = self._state.bundle.mark_shard_lost(cid, shard_index)
        self.health.add_degraded(f"shard_loss:{cid}/{shard_index}")
        telemetry.emit_event("shard_loss", coordinate=cid, shard_index=shard_index)
        return rng

    def restage_shard(
        self, cid: str, shard_index: int, rows=None
    ) -> int:
        """Recover one lost shard (re-uploads ONLY its rows, under the
        `shard_upload` fault site); clears the shard's degraded reason on
        success. A terminal staging failure re-raises and the shard stays
        lost — the engine keeps serving its entities FE-only."""
        nbytes = self._state.bundle.restage_shard(cid, shard_index, rows=rows)
        self.health.clear_degraded(f"shard_loss:{cid}/{shard_index}")
        telemetry.emit_event(
            "shard_restage", coordinate=cid, shard_index=shard_index, bytes=nbytes
        )
        return nbytes

    def _on_batcher_unhealthy(self, exc: BaseException) -> None:
        """A batcher's flush thread died (serving/batcher.py failed all its
        pending futures); the engine is degraded until operators replace
        the batcher — this reason never self-clears."""
        self.health.add_degraded(f"batcher_unhealthy: {exc!r}")

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # ------------------------------------------------------- state plumbing

    def _build_state(self, bundle: ServingBundle, *, version: int) -> _EngineState:
        if bundle.released:
            raise RuntimeError("cannot serve a released bundle")
        coords = [bundle.coordinates[cid] for cid in bundle.coordinate_ids]

        def _kind(c: ServingCoordinate) -> str:
            if not c.is_random_effect:
                return "fe"
            if getattr(c, "store", None) is not None:
                return "re2"
            if getattr(c, "mesh", None) is not None:
                return "re_sh"
            tier = getattr(c, "tier", "f32")
            if tier == "bf16":
                return "re_bf16"
            if tier == "int8":
                return "re_i8"
            return "re"

        return _EngineState(
            bundle=bundle,
            coords=coords,
            kinds=tuple(_kind(c) for c in coords),
            coord_shards=tuple(c.shard for c in coords),
            shard_dims=bundle.shard_dims(),
            meshes=tuple(getattr(c, "mesh", None) for c in coords),
            version=version,
        )

    def _warm_state(self, state: _EngineState) -> None:
        """Compile every bucket program for `state`'s parameter shapes
        (inert all-cold zero batches; no fault sites, no request metrics).
        Used by warmup() on the live state and by the hot-swap staging on
        the NEXT state — so the atomic flip compiles nothing."""
        for b in self.buckets:
            self._dispatch(
                self._pack([], b, state, inject=False), state, inject=False
            )

    def _commit_state(
        self, new_state: _EngineState, *, baseline_bump: int = 0
    ) -> _EngineState:
        """The hot-swap flip: one assignment under the lock. The warmup
        baseline grows by exactly the programs STAGING compiled
        (`baseline_bump`) — never reset to the current total, which would
        silently absorb any pre-swap hot-path recompiles and wipe the
        regression signal recompiles_after_warmup exists to carry."""
        with self._lock:
            old = self._state
            self._state = new_state
            if self._warmup_compiles is not None:
                self._warmup_compiles += max(0, baseline_bump)
        return old

    def _drain_state(self, state: _EngineState, *, timeout_s: float) -> bool:
        """Wait until no in-flight batch still scores on `state`."""
        deadline = time.monotonic() + timeout_s
        with self._lock:
            while state.active > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._lock.wait(timeout=remaining)
        return True

    # ------------------------------------------------------------- scoring

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.max_batch

    def warmup(self) -> int:
        """Compile every declared bucket (inert all-cold zero batches that
        do not count toward request metrics). Returns the compile count;
        afterwards `recompiles_after_warmup` tracks cache misses — zero for
        any request stream whose batches fit max_batch. Transitions the
        health machine STARTING -> READY."""
        t0 = time.perf_counter()
        # inject=False inside _warm_state: warmup is not the request path —
        # an armed lookup/score fault must fire on (and be counted against)
        # real traffic, not kill engine bring-up.
        self._warm_state(self._state)
        # Warmup wall (mostly XLA compiles) is recorded under its own stage
        # key; no ambient scope is open here, so the inner serve_pack/
        # serve_score timers stay warmup-free.
        self.stages.record("serve_warmup", time.perf_counter() - t0)
        compiles = self.compiles
        with self._lock:
            self._warmup_compiles = compiles
        self.health.mark_ready()
        return compiles

    def score_batch(
        self, requests: Sequence[ScoreRequest], *, fe_only: bool = False
    ) -> List[ScoreResult]:
        """Score one micro-batch: pad to the bucket, one device round trip.
        Batches larger than max_batch split internally. `fe_only=True` is
        the circuit-open tier: every RE lookup forced to the pinned zero
        row, no fault sites in the path."""
        if not requests:
            return []
        if len(requests) > self.max_batch:
            out: List[ScoreResult] = []
            for lo in range(0, len(requests), self.max_batch):
                out.extend(
                    self.score_batch(
                        requests[lo : lo + self.max_batch], fe_only=fe_only
                    )
                )
            return out
        n = len(requests)
        bucket = self.bucket_for(n)
        with self._lock:
            st = self._state
            st.active += 1
        try:
            with stage_scope(self.stages):
                packed = self._pack(
                    requests, bucket, st, inject=not fe_only, fe_only=fe_only
                )
                scores, means = self._dispatch(packed, st, inject=not fe_only)
        finally:
            with self._lock:
                st.active -= 1
                self._lock.notify_all()
        flags = packed["cold_flags"]
        lflags = packed["lost_flags"]
        results = [
            ScoreResult(
                score=float(scores[i]),
                mean=float(means[i]),
                uid=requests[i].uid,
                cold_start=bool(flags[i].any()),
                n_cold=int(flags[i].sum()),
                fe_only=fe_only,
                n_lost=int(lflags[i].sum()),
            )
            for i in range(n)
        ]
        now = time.monotonic()
        with self._lock:
            self._requests += n
            self._batches += 1
            if fe_only:
                # FE-only answers are forced cold by construction; keeping
                # them out of the lookup counters preserves
                # cold_start_fraction's meaning (unknown entities on the
                # HEALTHY path).
                self._fe_only_requests += n
            else:
                self._lookups += int(flags.size)
                self._cold_lookups += int(flags.sum())
            self._slots_total += bucket
            self._slots_padded += bucket - n
            if self._t_first is None:
                self._t_first = now
            self._t_last = now
        if self.health.state is ServingState.STARTING:
            self.health.mark_ready()  # serving without explicit warmup()
        return results

    def score_batch_fe_only(
        self, requests: Sequence[ScoreRequest]
    ) -> List[ScoreResult]:
        """The circuit-open degradation tier: score with fixed effects (+
        offset) only, bitwise-equal to FE-only GameTransformer output via
        the pinned zero-row path. No fault site fires here — this tier
        must keep answering precisely when the full path is broken."""
        return self.score_batch(requests, fe_only=True)

    # ------------------------------------------------------------ internals

    def _pack(
        self,
        requests: Sequence[ScoreRequest],
        bucket: int,
        state: _EngineState,
        *,
        inject: bool = True,
        fe_only: bool = False,
    ) -> dict:
        """Host-side batch assembly: per-shard dense buffers, per-RE-coordinate
        entity rows (padding slots gather the pinned zero row), offsets."""
        n = len(requests)
        with stage_timer("serve_pack"):
            buffers = {
                s: np.zeros((bucket, d), np.float32)
                for s, d in state.shard_dims.items()
            }
            offsets = np.zeros(bucket, np.float32)
            for i, r in enumerate(requests):
                offsets[i] = r.offset
                for s, payload in r.features.items():
                    buf = buffers.get(s)
                    if buf is None:
                        continue
                    if isinstance(payload, tuple):
                        idx, vals = payload
                        np.add.at(buf[i], np.asarray(idx, np.int64), vals)
                    else:
                        buf[i, :] = payload
        with stage_timer("serve_lookup"):
            if inject and self.inject_faults:
                faults.fault_point("lookup")
            re_coords = [c for c in state.coords if c.is_random_effect]
            cold_flags = np.zeros((n, len(re_coords)), bool)
            # Which cold flags are shard-loss fallbacks (resident row,
            # LOST shard) rather than genuinely unseen entities — kept
            # separate so ScoreResult.n_lost can tell a degraded answer
            # from one nobody could improve on.
            lost_flags = np.zeros((n, len(re_coords)), bool)
            rows_by_cid: Dict[str, np.ndarray] = {}
            # Two-tier coordinates: per-batch override buffers (cold-tier
            # rows copied from host RAM) + the hot-matrix snapshot captured
            # ATOMICALLY with the slot resolution — a concurrent promotion
            # can then never remap an in-flight batch (the snapshot matrix
            # is immutable; promotions build a new one).
            overrides_by_cid: Dict[str, tuple] = {}
            tier_params: Dict[str, Array] = {}
            for k, c in enumerate(re_coords):
                store = getattr(c, "store", None)
                if fe_only:
                    # Every slot gathers the pinned zero row: the margin
                    # contribution is exactly +0.0, i.e. FE-only scoring
                    # without touching the (possibly failing) index path.
                    if store is not None:
                        rows_by_cid[c.cid] = np.full(
                            bucket, store.zero_slot, np.int32
                        )
                        overrides_by_cid[c.cid] = (
                            np.zeros((bucket, c.dim), np.float32),
                            np.zeros(bucket, bool),
                        )
                        tier_params[c.cid] = store.snapshot()
                    else:
                        rows_by_cid[c.cid] = np.full(
                            bucket, c.unseen_row, np.int32
                        )
                    continue
                ids = [r.entity_ids.get(c.random_effect_type) for r in requests]
                rows, _ = c.lookup_rows(ids)
                sh = getattr(c, "shard_health", None)
                if sh is not None:
                    # Per-shard load telemetry (cold starts excluded) —
                    # what a reshard/rebalance plan reads to name the
                    # overloaded shard.
                    sh.record_loads(rows[:n], c.unseen_row)
                if sh is not None and sh.any_lost:
                    # Shard-loss degradation: rows living in a LOST shard
                    # resolve to the pinned zero row — bitwise FE-only for
                    # exactly those entities; every other row keeps
                    # full-fidelity answers.
                    # Rows ALREADY at the pinned zero row (cold starts)
                    # are excluded: they were FE-only by design, and
                    # counting them would report cold-start traffic as
                    # shard-loss degradation.
                    lost = sh.lost_mask(rows) & (rows != c.unseen_row)
                    if lost.any():
                        lost_flags[:, k] = lost
                        rows = np.where(lost, c.unseen_row, rows).astype(
                            np.int32
                        )
                        n_lost = int(lost.sum())
                        faults.COUNTERS.increment(
                            "shard_loss_fallbacks", n_lost
                        )
                        with self._lock:
                            self._shard_loss_fallbacks += n_lost
                cold_flags[:, k] = rows == c.unseen_row
                if store is not None:
                    slots, ovr, flags, snapshot = store.lookup(rows, bucket)
                    rows_by_cid[c.cid] = slots
                    overrides_by_cid[c.cid] = (ovr, flags)
                    tier_params[c.cid] = snapshot
                else:
                    padded = np.full(bucket, c.unseen_row, np.int32)
                    padded[:n] = rows
                    rows_by_cid[c.cid] = padded
        return {
            "bucket": bucket,
            "buffers": buffers,
            "offsets": offsets,
            "rows_by_cid": rows_by_cid,
            "overrides_by_cid": overrides_by_cid,
            "tier_params": tier_params,
            "cold_flags": cold_flags,
            "lost_flags": lost_flags,
        }

    def _dispatch(
        self, packed: dict, state: _EngineState, *, inject: bool = True
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Upload request buffers, run the fused program, fetch both outputs
        in one transfer."""
        with stage_timer("serve_score"):
            if inject and self.inject_faults:
                faults.fault_point("score")
            # Hang watchdog (live traffic only — warmup/FE-only exempt):
            # the guard wraps upload + fused program + fetch; an
            # over-deadline dispatch raises a typed DeviceHang that the
            # batcher's breaker counts toward circuit-open FE-only routing.
            wd_ms = self._watchdog_ms if inject else 0.0
            with self._watchdog.guard(
                wd_ms, f"serving dispatch (bucket {packed['bucket']})"
            ):
                out = self._dispatch_device(packed, state)
            if wd_ms > 0 and self._hang_seen:
                # A GUARDED dispatch finished inside its deadline: the
                # device answered again, so the hang degradation
                # self-clears (an unguarded FE-only dispatch proves
                # nothing about the full path).
                self._hang_seen = False
                self.health.clear_degraded("device_hang")
        host_total, host_means = out
        return np.asarray(host_total), np.asarray(host_means)

    def _dispatch_device(
        self, packed: dict, state: _EngineState
    ) -> Tuple[np.ndarray, np.ndarray]:
        with self._device_mutex:
            return self._dispatch_device_locked(packed, state)

    def _dispatch_device_locked(
        self, packed: dict, state: _EngineState
    ) -> Tuple[np.ndarray, np.ndarray]:
        dev_buffers = {
            s: jnp.asarray(b) for s, b in packed["buffers"].items()
        }
        rows = tuple(
            jnp.asarray(packed["rows_by_cid"][c.cid])
            if c.is_random_effect
            else None
            for c in state.coords
        )
        overrides = tuple(
            (
                jnp.asarray(packed["overrides_by_cid"][c.cid][0]),
                jnp.asarray(packed["overrides_by_cid"][c.cid][1]),
            )
            if c.is_random_effect
            and c.cid in packed["overrides_by_cid"]
            else None
            for c in state.coords
        )
        # Two-tier coordinates score against the hot-matrix snapshot
        # the pack stage captured with the slots; int8 coordinates pass
        # (plane, per-row scales) so the program's fused dequant gathers
        # both; everyone else serves the bundle's pinned planes.
        params = tuple(
            (c.params, c.scales)
            if state.kinds[k] == "re_i8"
            else packed["tier_params"].get(c.cid, c.params)
            for k, c in enumerate(state.coords)
        )
        norms = tuple(c.norm for c in state.coords)
        total, means = self._jit(
            jnp.asarray(packed["offsets"]),
            dev_buffers,
            rows,
            overrides,
            params,
            norms,
            kinds=state.kinds,
            shards=state.coord_shards,
            meshes=state.meshes,
            task=self.task,
        )
        return jax.device_get((total, means))

    # -------------------------------------------------------------- metrics

    def warmup_buffer_bytes(self, state: Optional[_EngineState] = None) -> int:
        """Peak per-batch transient request-buffer bytes (largest bucket):
        offsets + per-shard feature buffers + per-RE rows + two-tier
        override buffers + both outputs. This is what a hot-swap's
        pre-warm allocates BESIDE the two resident bundle generations, so
        BundleManager charges it against the HBM budget."""
        st = state if state is not None else self._state
        b = self.max_batch
        total = b * 4  # offsets
        total += sum(b * d * 4 for d in st.shard_dims.values())
        for k, c in enumerate(st.coords):
            if c.is_random_effect:
                total += b * 4  # rows
                if st.kinds[k] == "re2":
                    total += b * (c.dim * 4 + 1)  # override values + flags
        total += 2 * b * 4  # (scores, means)
        return total

    def _sharding_metrics(self, state: _EngineState) -> Dict[str, object]:
        """The serving sharding decision as proper JSON keys (the
        serving-summary contract): mesh axis size, peak coefficient
        rows resident per shard, two-tier hot-set fraction, and the
        analytic collective bytes one max_batch bucket moves."""
        from photon_ml_tpu.parallel.mesh import bcast_gather_wire_bytes

        sharded = False
        axis = 1
        rows_per_shard = 0
        hot_fraction = 1.0
        wire = 0
        shards_lost = 0
        for k, c in enumerate(state.coords):
            kind = state.kinds[k]
            sh = getattr(c, "shard_health", None)
            if sh is not None:
                shards_lost += len(sh.lost)
            if kind == "re_sh":
                sharded = True
                ndev = int(c.mesh.devices.size)
                axis = max(axis, ndev)
                rows_per_shard = max(
                    rows_per_shard, int(c.params.shape[0]) // ndev
                )
                wire += bcast_gather_wire_bytes(c.mesh, self.max_batch, c.dim)
            elif kind == "re2":
                hot_fraction = min(hot_fraction, c.store.hot_fraction)
                rows_per_shard = max(rows_per_shard, c.store.capacity + 1)
            elif kind == "re":
                rows_per_shard = max(rows_per_shard, int(c.params.shape[0]))
        # Explicit keys (immune to schema-tuple reorders), checked against
        # the shared schema so the producer cannot drift from what
        # cli/serve and the tests assert on.
        from photon_ml_tpu.utils.contracts import SERVING_SHARDING_KEYS

        with self._lock:
            loss_fallbacks = self._shard_loss_fallbacks
        out = {
            "entity_sharded": sharded,
            "axis_size": axis,
            "rows_per_shard": rows_per_shard,
            "hot_set_fraction": round(hot_fraction, 6),
            "all_to_all_bytes_per_batch": wire,
            "shards_lost": shards_lost,
            "shard_loss_fallbacks": loss_fallbacks,
        }
        assert set(out) == set(SERVING_SHARDING_KEYS), (
            "serving sharding block drifted from utils/contracts."
            "SERVING_SHARDING_KEYS"
        )
        return out

    @property
    def compiles(self) -> int:
        """XLA programs compiled by THIS engine: the jit wrapper's cache
        size (an honest compile count)."""
        return int(self._jit._cache_size())

    @property
    def recompiles_after_warmup(self) -> Optional[int]:
        """Compiles since warmup(), or None when warmup never ran — a 0
        here must MEAN zero hot-path compiles, not 'nobody measured'; an
        un-warmed engine compiling on live traffic has no baseline to
        count from, and None trips the summary's missing-key contract."""
        with self._lock:
            base = self._warmup_compiles
        return None if base is None else max(0, self.compiles - base)

    def metrics(self) -> Dict[str, object]:
        """Engine-side counters; the batcher's metrics() merges these with
        request latency percentiles. Includes the lifecycle tier: health
        state (+ degraded reasons), circuit snapshot, bundle version and
        swap counters."""
        compiles = self.compiles  # before the lock: the fallback path locks
        manager = self._bundle_manager
        with self._lock:
            st = self._state
            lookups = self._lookups
            cold = self._cold_lookups
            slots = self._slots_total
            padded = self._slots_padded
            elapsed = (
                (self._t_last - self._t_first)
                if self._t_first is not None and self._t_last > self._t_first
                else 0.0
            )
            out = {
                "requests": self._requests,
                "batches": self._batches,
                "cold_start_lookups": cold,
                "cold_start_fraction": (cold / lookups) if lookups else 0.0,
                "padding_waste": (padded / slots) if slots else 0.0,
                "compiles": compiles,
                "recompiles_after_warmup": (
                    None
                    if self._warmup_compiles is None
                    else max(0, compiles - self._warmup_compiles)
                ),
                "fe_only_requests": self._fe_only_requests,
                "bundle_version": st.version,
                "upload_bytes": st.bundle.upload_bytes,
                "upload_s": round(st.bundle.upload_s, 4),
                "engine_qps": (
                    round(self._requests / elapsed, 1) if elapsed > 0 else None
                ),
            }
        # Pod-scale accounting: the sharding decision this bundle serves
        # under + the two-tier store counters (all keys always present —
        # 0/False on a single-tier replicated bundle — so the summary's
        # missing-key contract can be loud).
        out["sharding"] = self._sharding_metrics(st)
        tier = {
            "hot_tier_hits": 0,
            "cold_tier_hits": 0,
            "promotions": 0,
            "evictions": 0,
            "promote_failures": 0,
            "pending_promotions": 0,
        }
        for c in st.coords:
            store = getattr(c, "store", None)
            if store is not None:
                sm = store.metrics()
                for key in tier:
                    tier[key] += int(sm[key])
        out.update(tier)
        health = self.health.snapshot()
        out["state"] = health["state"]
        out["degraded_reasons"] = health["degraded_reasons"]
        out.update(self.breaker.snapshot())
        out["bundle_swaps"] = manager.swaps if manager is not None else 0
        out["bundle_swap_rollbacks"] = (
            manager.rollbacks if manager is not None else 0
        )
        orch = self._reshard_orchestrator
        out["bundle_reshards"] = orch.reshards if orch is not None else 0
        out["bundle_rebalances"] = orch.rebalances if orch is not None else 0
        out["bundle_deltas"] = orch.deltas if orch is not None else 0
        out["bundle_reshard_rollbacks"] = (
            orch.rollbacks if orch is not None else 0
        )
        out["stage_walls_s"] = {
            k: round(v, 4) for k, v in sorted(self.stages.sections.items())
        }
        return out
