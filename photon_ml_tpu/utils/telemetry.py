"""photon-trace: unified telemetry — spans, mergeable metrics, run journal,
and the persisted run profile (ISSUE 11).

Ten PRs of instrumentation left the repo's telemetry fragmented: stage
walls in `TimingRegistry`, robustness counters in `utils/faults.py`
process globals, serving latency in an unbounded sample list, collective
bytes in `fit_timing` — and nothing recorded *when* things happened or
*why* a dispatch path was chosen. This module is the one substrate the
four signal kinds share; the Spark-ML performance study (PAPERS.md,
arXiv:1612.01437) shows the runtime decisions it records — layout,
parallelism, batching — dominate end-to-end cost, and the ROADMAP's
adaptive-runtime planner consumes the profile it persists.

Four coordinated parts:

* **Spans** — a thread-aware tracer layered on the `stage_scope` handoff
  pattern (utils/observability.py): `span(name)` opens a span under this
  thread's innermost open span; `span_handoff()` captures the current
  span context at submit time and `adopt_span(handoff)` parents a worker
  thread's spans under the submitter's — the same discipline
  `AsyncUploader` uses for stage registries, so spans flow across the
  named worker fleet (photon-ingest-decode, photon-ckpt-write-shard<k>,
  photon-serving-promote, photon-serving-flush, ...). Export is Chrome
  trace-event JSON (`Tracer.to_chrome_trace`), loadable in Perfetto.
  Gated by the `PHOTON_TRACE` knob: with no tracer installed `span()`
  returns a shared no-op context manager — one global read, no
  allocation — so library code instruments unconditionally (the same
  near-zero-overhead discipline as `record_stage`). Every recorded span
  also enters a `photon/<name>` `jax.profiler.TraceAnnotation`
  (`profiler_annotation`), so under a profiler session the program's
  spans sit on the host plane of the `.xplane.pb`, on the device
  operations' clock.

* **Metrics** — typed Counter/Gauge/Histogram behind one registry
  (`METRICS`). Histograms use FIXED log-spaced bucket bounds
  (`BUCKET_BOUNDS`, 16 per decade over 1e-4..1e7) shared by every
  histogram in every process, so snapshots merge associatively and
  order-independently across threads and across processes
  (`merge_histogram_snapshots`). Metric NAMES are a
  closed registry (`METRIC_DESCRIPTIONS`, the `SITE_DESCRIPTIONS`
  discipline): incrementing an undeclared name raises, and the static
  analyzer's `metric-name-sync` check (photon_ml_tpu/analysis/) fails
  the build when an incremented literal is missing here or a declared
  name is never incremented. `utils/faults.COUNTERS` delegates to this
  registry, so the scattered fault/serving/tier/watchdog/collective
  counters are all declared once, below.

* **Run journal** — a JSONL sink (`RunJournal`): health transitions,
  bundle swaps, fault retries, watchdog trips, shard loss/restage, and
  the training lifecycle events `EventEmitter` carries. Each line is a
  typed schema in `utils/contracts.JOURNAL_EVENT_SCHEMAS`; `emit_event`
  validates BEFORE writing, so a journal can never hold a line its
  schema rejects. Install process-wide with `install_journal` (the
  infra sites emit through the ambient journal exactly like
  `fault_point` fires through the ambient injector).

* **Run profile** — `build_profile`/`write_profile`/`read_profile`: the
  machine-readable `profile.json` every fit and serve run persists
  (stage breakdown, ingest breakdown, dispatch decisions, bucket
  shapes, roofline annotation, device topology, metrics snapshot) — the
  artifact the future planner consumes. `read_profile` enforces the
  `PROFILE_*_KEYS` contracts loudly.

Import discipline: stdlib-only at module level (utils/faults.py imports
this, and conftest-adjacent code must not initialize a jax backend);
`device_topology()` imports jax lazily.
"""

from __future__ import annotations

import bisect
import dataclasses
import itertools
import json
import logging
import math
import os
import threading
import time
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

from photon_ml_tpu.utils.contracts import (
    JOURNAL_EVENT_SCHEMAS,
    JOURNAL_LINE_KEYS,
    PROFILE_FIT_KEYS,
    PROFILE_REQUIRED_KEYS,
    PROFILE_SERVE_KEYS,
)
from photon_ml_tpu.utils.knobs import get_knob

logger = logging.getLogger(__name__)


# ------------------------------------------------------------ metric registry
#
# Every counter/gauge/histogram NAME the system increments, declared once
# with a one-line doc (the SITE_DESCRIPTIONS discipline). The analyzer's
# `metric-name-sync` check enforces both directions: an incremented
# literal missing here fails the build, and a declared name nothing
# increments is advertised observability that does not exist.

METRIC_DESCRIPTIONS = {
    # -- failure-domain counters (historically utils/faults.COUNTERS) --
    "retries": "bounded-backoff retries of transient failures (faults.retry)",
    "fallback_sync_uploads": "async shard uploads degraded to in-thread",
    "fallback_sync_builds": "prepare-pool RE builds degraded to in-thread",
    "fallback_sync_packs": "background packs degraded to in-thread",
    "fallback_sync_ckpt_writes": "staged checkpoint writes degraded to sync",
    "injected_faults": "faults fired by the deterministic injector",
    "quarantined_blocks": "corrupt Avro blocks quarantined on read",
    "serving_degraded_batches": "batches degraded to per-request dispatch",
    "serving_shed_requests": "submits shed by admission control",
    "serving_deadline_misses": "requests failed past their deadline budget",
    "serving_circuit_opens": "circuit-breaker CLOSED->OPEN transitions",
    "serving_fe_only_requests": "requests answered by the FE-only tier",
    "serving_swaps": "bundle hot-swaps committed",
    "serving_swap_rollbacks": "bundle hot-swaps rolled back",
    "serving_flush_thread_failures": "micro-batcher flush-thread deaths",
    "collective_retries": "mesh collective program re-dispatches",
    "collective_fallbacks": "sweep groups degraded to the per-bucket loop",
    "shard_upload_retries": "per-shard serving staging retries",
    "promote_failures": "failed two-tier hot-set promotions",
    "watchdog_trips": "device dispatches past the watchdog deadline",
    "shard_loss_fallbacks": "requests answered pinned-zero for a lost shard",
    "mesh_losses": "mesh-loss faults recovered at a sweep boundary",
    "reshard_retries": "per-shard staging retries during a live reshard",
    "reshard_rollbacks": "live mesh reshards rolled back to the old generation",
    "rebalanced_rows": "hot coefficient rows re-placed by a rebalance plan",
    "tenant_demotions": "cold tenants' RE rows demoted to the host tier "
    "under HBM pressure",
    "tenant_restores": "demoted tenants promoted back to full HBM "
    "residency when headroom returned",
    "tenant_cobatch_dispatches": "cross-tenant co-batched device dispatches",
    "delta_applies": "delta-bundle generation flips committed to a live "
    "engine",
    "delta_rollbacks": "delta-bundle applies rolled back to the old "
    "generation",
    "delta_rows_staged": "changed/added RE rows staged by delta applies",
    "host_losses": "whole-host losses detected in the multi-host process "
    "group (heartbeat or wedged collective)",
    "host_heartbeat_misses": "per-host heartbeat beats missed by a peer "
    "before it was declared lost",
    "shadow_mirrored_requests": "champion requests mirrored to a shadow "
    "challenger tenant",
    "shadow_mirror_failures": "mirror submits degraded to champion-only "
    "serving (never a failed client request)",
    "label_join_failures": "online-evaluation label joins dropped (label "
    "lost, champion path untouched)",
    "shadow_windows": "shadow evaluation windows scored through the "
    "jitted metric programs",
    "shadow_promotions": "challengers promoted to champion via the "
    "BundleManager generation flip",
    "shadow_rollbacks": "challengers torn down on a regression verdict "
    "or a failed promotion",
    "autopilot_actions": "control-rule actuations applied by the "
    "autopilot loop (reshard, rebalance, demote/restore, retune)",
    "autopilot_suppressed": "control-rule firings suppressed by "
    "hysteresis, cooldown, quarantine, or the action budget",
    "autopilot_rollbacks": "autopilot actions reverted because the "
    "post-action contract probe regressed",
    "autopilot_quarantines": "control rules benched after a rollback "
    "until an operator reset",
    # Precision-tier ladder (ISSUE 20): every completed ladder step in
    # either direction, plus transitions that exhausted their retry
    # policy and rolled back to the generation still serving. All three
    # are ROBUSTNESS_CLEAN_ZERO_KEYS — a clean run never walks the
    # ladder.
    "tier_demotions": "precision-ladder steps down (f32->bf16->int8->"
    "host) committed on a serving tenant",
    "tier_restores": "precision-ladder steps back up toward f32 "
    "committed on a serving tenant",
    "tier_rollbacks": "ladder transitions abandoned after retry "
    "exhaustion, the old generation still serving",
    # Persistent compilation cache traffic (utils/compile_cache.py):
    # programs compiled by this process = requests - hits. Both are
    # incremented under the label stage=<name>, the calling thread's
    # innermost open stage_timer or "none"; the totals are the sums.
    "compile_cache_requests": "compile requests that consulted JAX's "
    "persistent compilation cache (labeled stage=<name>)",
    "compile_cache_hits": "compile requests answered from the persistent "
    "compilation cache instead of compiling (labeled stage=<name>)",
    # Objective evaluations as the optimizers count them (OptResult.fn_evals:
    # value+gradient evaluations — L-BFGS's first and one per line-search
    # trial — and TRON's Hessian-vector products), added once a fit, labeled
    # coordinate=<id>,kind=fixed|random.
    "objective_evaluations": "objective evaluations the optimizers made, "
    "per coordinate (labeled coordinate=<id>,kind=fixed|random)",
    # How often the line search's thrown-away work engages: an L-BFGS
    # solve's evaluations beyond its first and one an iteration, each a
    # value+gradient evaluation at a point the Armijo test refused.
    "line_search_rejected_trials": "line-search trials that failed the "
    "Armijo test, per coordinate (labeled coordinate=<id>,"
    "kind=fixed|random; L-BFGS, OWL-QN and box solves)",
    # What a coordinate's TRON solves did beyond `objective_evaluations`
    # (which counts every pass over the data, these products included):
    # OptResult.hv_evals, summed over the entities of a random effect.
    # Added once a fit; absent where no coordinate solves with TRON. The
    # trial steps a fixed effect's trust region refused are in the stage
    # note `tron` (run_profile()["dispatch"]), not a counter.
    "hessian_vector_products": "Hessian-vector products a coordinate's "
    "TRON solves made, one a CG iteration, summed over a random effect's "
    "entities (labeled coordinate=<id>,kind=fixed|random)",
    # A random effect's TRON sweep under vmap: `used`, the passes over the
    # data (evaluations and products) its real entities' solves needed;
    # `paid`, the passes its batched loops executed times their lanes,
    # dummy-padded slots included (RandomEffectSolveStats). used / paid is
    # the lanes' occupancy. Added once a fit; absent for other solvers.
    "random_effect_lane_passes": "passes over the data a random effect's "
    "TRON sweep needed (kind=used) and its batched loops executed times "
    "their lanes (kind=paid) (labeled coordinate=<id>,kind=used|paid)",
    # A random effect's buckets by the layout its solves read them in:
    # `dense`, one (E, D, S) block read once a pass (or a dense shard's
    # rows), or `sparse`, the ELL planes gathered and scatter-added one at a
    # time (data/game_dataset.block_layout). Added once a fit.
    "random_effect_blocks": "a random effect's entity buckets by the layout "
    "its solves read them in (labeled coordinate=<id>,layout=dense|sparse)",
    # A fixed effect's shard by the layout its solves read it in: `dense`,
    # one float32 X^T block (containers.DenseBlock) or a dense matrix;
    # `packed`, the bucketed layout; `ell`, the ELL planes
    # (FixedEffectCoordinate.layout). Added once a coordinate construction.
    "fixed_effect_blocks": "a fixed effect's shard by the layout its solves "
    "read it in (labeled coordinate=<id>,layout=dense|packed|ell)",
    # A coordinate's scoring view of a validation data set: `built` by the
    # first fit or sweep that sees the data set, `reused` by every later one
    # on the same object (GameEstimator._validation_prepared). Added once a
    # coordinate a fit.
    "validation_prepared": "a coordinate's view of the validation data set, "
    "built or reused (labeled coordinate=<id>,outcome=built|reused)",
    # What a random effect trains on (RandomEffectDataset.num_entities and
    # num_active_samples: each entity's rows, or its cap where it has
    # more), set at every fit: a pass of one entity reads its share.
    "random_effect_data": "a random effect's entities (kind=entities) and "
    "their active rows (kind=active_rows) (labeled coordinate=<id>,kind)",
    # A sample-sharded fixed effect (parallel/mesh.sample_sharded_dataset)
    # sums its objective over the mesh once an evaluation: evaluations x
    # 4 (d + 1) bytes from each device, added once a fit. Absent on one device.
    "gradient_allreduce_bytes": "bytes each device contributed to the "
    "all-reduce of a sample-sharded fixed effect's value and gradient "
    "(labeled coordinate=<id>)",
    # A sparse fixed effect whose bucketed pack was declined keeps the ELL
    # objective through XLA (ops/pallas_sparse.pack_decline_reason).
    "sparse_pack_declined": "bucketed packs of a sparse fixed-effect shard "
    "declined, per reason (labeled reason=too_small|dtype|sharded|"
    "pad_blowup)",
    # An evaluation is one compiled program and one fetch
    # (evaluation/suite.evaluate_metrics); whether JAX made that program
    # ready anew is in compile_cache.programs(), as for every program.
    "evaluation_calls": "evaluations made (EvaluationSuite.evaluate, "
    "StreamingWindowEvaluator.evaluate_window, a validated coordinate "
    "update whose candidate was accepted): one device program and one "
    "fetch each",
    # A validated coordinate update evaluates its candidate before the
    # guard's verdict is known (game/coordinate_descent); the guard's
    # rejection drops that evaluation unread.
    "validation_discarded": "validations of a candidate update dropped "
    "because the divergence guard rejected it (labeled coordinate=<id>)",
    # -- histograms (fixed log-spaced buckets, mergeable) --
    "serving_latency_ms": "per-request wall latency through the batcher",
    "serving_queue_wait_ms": "submit-to-claim queue wait per request",
    "serving_batch_size": "requests per dispatched micro-batch",
    "coordinate_update_s": "wall seconds per coordinate-descent update",
    # The coordinate-descent loop's own blocking fetches, one observation
    # an update: 1 where the verdict and the validation's metrics ride in
    # one fetch, one more a retried attempt; a random effect's fetch inside
    # its train is not the loop's.
    "cd_host_waits": "blocking device-to-host fetches one coordinate-"
    "descent update made (labeled coordinate=<id>)",
    "fit_stage_s": "wall seconds per fit of each contracts.SOLVE_STAGES "
    "stage (labeled stage=<name>); the unlabeled aggregate mixes stages "
    "and means nothing",
    "program_ready_s": "self seconds of every trace, lowering and backend "
    "step JAX reported while making a program ready (labeled "
    "phase=trace|lower|cache_read|compile,stage=<name>; "
    "utils/compile_cache.py); the unlabeled aggregate mixes phases",
    "shadow_score_drift": "per-request |champion - challenger| mean-score "
    "drift observed at window evaluation",
    "shadow_calibration_champion": "per-request |champion mean - label| "
    "calibration error per evaluated window",
    "shadow_calibration_challenger": "per-request |challenger mean - label| "
    "calibration error per evaluated window",
    "tier_quant_error": "per-coordinate worst relative round-trip error "
    "measured at each quantization (labeled per tenant) — the "
    "characterized-parity evidence behind contracts.TIER_TOLERANCES",
    # -- gauges (last-write-wins) --
    "serving_pending_depth": "batcher queue depth observed at batch claim",
    "serving_bundle_generation": "live bundle generation after a hot-swap",
}

# Fixed log-spaced histogram bounds: 16 buckets per decade over
# [1e-4, 1e7). FIXED bounds (not per-histogram, not adaptive) are what
# make merges associative: two snapshots merge by adding counts
# bucket-wise, whatever order they were taken or combined in. The
# geometric bucket width (10^(1/16) ~= 1.155x) bounds the quantile
# error: a histogram quantile lands within one bucket of the exact one.
_BUCKETS_PER_DECADE = 16
_MIN_DECADE, _MAX_DECADE = -4, 7
BUCKET_BOUNDS: Tuple[float, ...] = tuple(
    10.0 ** (k / _BUCKETS_PER_DECADE)
    for k in range(
        _MIN_DECADE * _BUCKETS_PER_DECADE,
        _MAX_DECADE * _BUCKETS_PER_DECADE + 1,
    )
)


class Histogram:
    """Thread-safe histogram over the shared fixed bounds, plus exact
    count/sum/min/max. Values at or below the first bound land in bucket
    0; values past the last bound land in the overflow bucket."""

    def __init__(self) -> None:
        self._counts: Dict[int, int] = {}
        self.count = 0
        self.sum = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None
        self._lock = threading.Lock()

    def record(self, value: float) -> None:
        value = float(value)
        idx = bisect.bisect_left(BUCKET_BOUNDS, value)
        with self._lock:
            self._counts[idx] = self._counts.get(idx, 0) + 1
            self.count += 1
            self.sum += value
            self.min = value if self.min is None else min(self.min, value)
            self.max = value if self.max is None else max(self.max, value)

    def snapshot(self) -> Dict[str, object]:
        """JSON-serializable state (sparse bucket counts keyed by index).
        Merge snapshots with `merge_histogram_snapshots`."""
        with self._lock:
            return {
                "buckets": {str(k): v for k, v in sorted(self._counts.items())},
                "count": self.count,
                "sum": self.sum,
                "min": self.min,
                "max": self.max,
            }

    def quantile(self, q: float) -> Optional[float]:
        with self._lock:
            return _snapshot_quantile(
                {
                    "buckets": dict(self._counts),
                    "count": self.count,
                    "min": self.min,
                    "max": self.max,
                },
                q,
            )


def _bucket_value(idx: int) -> float:
    """A representative value for bucket `idx`: the geometric midpoint of
    its bounds (clamped at the edges)."""
    if idx <= 0:
        return BUCKET_BOUNDS[0]
    if idx >= len(BUCKET_BOUNDS):
        return BUCKET_BOUNDS[-1]
    return math.sqrt(BUCKET_BOUNDS[idx - 1] * BUCKET_BOUNDS[idx])


def _snapshot_quantile(snap: Mapping[str, object], q: float) -> Optional[float]:
    count = int(snap.get("count") or 0)
    if count == 0:
        return None
    target = q * count
    seen = 0
    buckets = snap["buckets"]
    items = sorted((int(k), int(v)) for k, v in dict(buckets).items())
    for idx, n in items:
        seen += n
        if seen >= target:
            value = _bucket_value(idx)
            lo, hi = snap.get("min"), snap.get("max")
            if lo is not None:
                value = max(value, float(lo))
            if hi is not None:
                value = min(value, float(hi))
            return value
    return snap.get("max")


def snapshot_quantile(snap: Mapping[str, object], q: float) -> Optional[float]:
    """Quantile from a histogram SNAPSHOT (possibly merged): within one
    bucket width of the exact value by construction of the fixed bounds."""
    return _snapshot_quantile(snap, q)


def merge_histogram_snapshots(*snaps: Mapping[str, object]) -> Dict[str, object]:
    """Associative, order-independent merge of histogram snapshots — the
    cross-thread / cross-subprocess aggregation primitive. Works because
    every histogram shares BUCKET_BOUNDS."""
    buckets: Dict[str, int] = {}
    count = 0
    total = 0.0
    lo: Optional[float] = None
    hi: Optional[float] = None
    for s in snaps:
        for k, v in dict(s.get("buckets") or {}).items():
            buckets[str(int(k))] = buckets.get(str(int(k)), 0) + int(v)
        count += int(s.get("count") or 0)
        total += float(s.get("sum") or 0.0)
        for bound, pick in ((s.get("min"), min), (s.get("max"), max)):
            if bound is not None:
                prev = lo if pick is min else hi
                merged = float(bound) if prev is None else pick(prev, float(bound))
                if pick is min:
                    lo = merged
                else:
                    hi = merged
    return {
        "buckets": {k: buckets[k] for k in sorted(buckets, key=int)},
        "count": count,
        "sum": total,
        "min": lo,
        "max": hi,
    }


# ------------------------------------------------------------- metric labels
#
# Ambient per-thread metric labels (ISSUE 15): the multi-tenant serving
# tier scopes the process-global robustness counters per tenant WITHOUT
# touching the increment sites — a dispatch path runs inside
# `metric_label_scope(tenant=...)` and every counter it bumps lands in
# both the process-wide aggregate (unchanged) and a labeled sub-count.
# The name stays the declared literal (the metric-name-sync analyzer
# keeps working); only the attribution dimension is ambient.

_LABEL_TLS = threading.local()


def current_metric_labels() -> Optional[Tuple[Tuple[str, str], ...]]:
    """The thread's ambient metric labels (sorted key/value pairs), or
    None outside any `metric_label_scope`."""
    return getattr(_LABEL_TLS, "labels", None)


class metric_label_scope:
    """Context manager attaching labels (e.g. tenant="a") to every
    counter increment on THIS thread for the scope's duration. Nested
    scopes replace, not merge — the inner scope's attribution wins."""

    __slots__ = ("_labels", "_prev")

    def __init__(self, **labels: str):
        self._labels = tuple(sorted((k, str(v)) for k, v in labels.items()))
        self._prev: Optional[Tuple[Tuple[str, str], ...]] = None

    def __enter__(self) -> "metric_label_scope":
        self._prev = getattr(_LABEL_TLS, "labels", None)
        _LABEL_TLS.labels = self._labels
        return self

    def __exit__(self, *exc) -> bool:
        _LABEL_TLS.labels = self._prev
        return False


def label_key(labels: Tuple[Tuple[str, str], ...]) -> str:
    """Canonical string form of a label set ("tenant=a"), the key the
    labeled sub-counters and snapshots use."""
    return ",".join(f"{k}={v}" for k, v in labels)


class MetricsRegistry:
    """Typed Counter/Gauge/Histogram store over the closed name registry.

    Names must be declared in METRIC_DESCRIPTIONS — an undeclared name
    raises (the knob-registry discipline), so a metric cannot be added
    without landing in the declaration table the analyzer checks.

    Counters additionally carry per-label sub-counts (ISSUE 15): an
    increment inside a `metric_label_scope` (or with an explicit
    `labels=`) bumps the aggregate AND the label's sub-count, so one
    tenant's degradations are visible per tenant without losing the
    process-wide signal. ISSUE 19 extends the same attribution to gauges
    and histograms — a labeled observe records into the aggregate
    histogram AND a per-label one over the same fixed bucket bounds, so
    labeled sub-series merge exactly as associatively as the aggregates
    and the autopilot can read per-tenant p95s instead of process-global
    ones."""

    def __init__(self) -> None:
        self._counters: Dict[str, int] = {}
        self._labeled: Dict[str, Dict[str, int]] = {}
        self._gauges: Dict[str, float] = {}
        self._labeled_gauges: Dict[str, Dict[str, float]] = {}
        self._hists: Dict[str, Histogram] = {}
        self._labeled_hists: Dict[str, Dict[str, Histogram]] = {}
        self._lock = threading.Lock()

    @staticmethod
    def _check(name: str) -> None:
        if name not in METRIC_DESCRIPTIONS:
            raise KeyError(
                f"undeclared metric {name!r} — add it to "
                "photon_ml_tpu.utils.telemetry.METRIC_DESCRIPTIONS"
            )

    def increment(
        self,
        name: str,
        by: int = 1,
        labels: Optional[Tuple[Tuple[str, str], ...]] = None,
    ) -> None:
        self._check(name)
        if labels is None:
            labels = current_metric_labels()
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + by
            if labels:
                sub = self._labeled.setdefault(name, {})
                key = label_key(labels)
                sub[key] = sub.get(key, 0) + by

    def get_counter(self, name: str) -> int:
        with self._lock:
            return self._counters.get(name, 0)

    def labeled_counters(self, name: str) -> Dict[str, int]:
        """Per-label sub-counts of one counter ({"tenant=a": 3}); empty
        when nothing labeled incremented it. The aggregate counter is the
        sum of these plus any unlabeled increments."""
        with self._lock:
            return dict(self._labeled.get(name, {}))

    def set_gauge(
        self,
        name: str,
        value: float,
        labels: Optional[Tuple[Tuple[str, str], ...]] = None,
    ) -> None:
        self._check(name)
        if labels is None:
            labels = current_metric_labels()
        with self._lock:
            self._gauges[name] = float(value)
            if labels:
                sub = self._labeled_gauges.setdefault(name, {})
                sub[label_key(labels)] = float(value)

    def labeled_gauges(self, name: str) -> Dict[str, float]:
        """Per-label last-write-wins values of one gauge; empty when
        nothing labeled set it."""
        with self._lock:
            return dict(self._labeled_gauges.get(name, {}))

    def observe(
        self,
        name: str,
        value: float,
        labels: Optional[Tuple[Tuple[str, str], ...]] = None,
    ) -> None:
        self._check(name)
        if labels is None:
            labels = current_metric_labels()
        with self._lock:
            hist = self._hists.get(name)
            if hist is None:
                hist = self._hists[name] = Histogram()
            labeled = None
            if labels:
                sub = self._labeled_hists.setdefault(name, {})
                key = label_key(labels)
                labeled = sub.get(key)
                if labeled is None:
                    labeled = sub[key] = Histogram()
        hist.record(value)
        if labeled is not None:
            labeled.record(value)

    def histogram(self, name: str) -> Optional[Histogram]:
        with self._lock:
            return self._hists.get(name)

    def labeled_histogram(
        self, name: str, labels: Tuple[Tuple[str, str], ...]
    ) -> Optional[Histogram]:
        """One label's live sub-histogram, or None if never observed."""
        with self._lock:
            return self._labeled_hists.get(name, {}).get(label_key(labels))

    def labeled_histograms(self, name: str) -> Dict[str, Dict[str, object]]:
        """Per-label mergeable snapshots of one histogram
        ({"tenant=a": {...}}); empty when nothing labeled observed it.
        The aggregate histogram covers these plus unlabeled observes —
        same fixed bucket bounds, so sub-series merge associatively."""
        with self._lock:
            sub = dict(self._labeled_hists.get(name, {}))
        return {k: h.snapshot() for k, h in sorted(sub.items())}

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)

    def snapshot(self) -> Dict[str, object]:
        """One JSON-serializable snapshot of everything; histograms as
        mergeable snapshots, labeled counter sub-counts beside the
        aggregates."""
        with self._lock:
            hists = dict(self._hists)
            labeled_hists = {
                k: dict(v) for k, v in sorted(self._labeled_hists.items())
            }
            out = {
                "counters": dict(self._counters),
                "labeled_counters": {
                    k: dict(v) for k, v in sorted(self._labeled.items())
                },
                "gauges": dict(self._gauges),
                "labeled_gauges": {
                    k: dict(v)
                    for k, v in sorted(self._labeled_gauges.items())
                },
            }
        out["histograms"] = {k: h.snapshot() for k, h in sorted(hists.items())}
        out["labeled_histograms"] = {
            k: {lk: h.snapshot() for lk, h in sorted(v.items())}
            for k, v in labeled_hists.items()
        }
        return out

    def reset_counters(self) -> None:
        """Zero the counters ONLY — the faults.reset_counters contract.
        Callers resetting fault counters between the phases of a run
        must not destroy unrelated histogram/gauge state mid-run. Labeled
        sub-counts reset with their aggregates (they are the same events)."""
        with self._lock:
            self._counters.clear()
            self._labeled.clear()

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._labeled.clear()
            self._gauges.clear()
            self._labeled_gauges.clear()
            self._hists.clear()
            self._labeled_hists.clear()


METRICS = MetricsRegistry()


class LatencyStats:
    """Bounded latency accounting: a mergeable histogram plus a small
    bounded reservoir of the FIRST `reservoir` samples for exact
    small-run percentiles. Replaces the unbounded per-request sample
    list the micro-batcher carried (ISSUE 11 satellite): memory is
    O(reservoir + fixed buckets) under sustained traffic, and past the
    reservoir the histogram quantile is within one bucket width of
    exact."""

    def __init__(self, reservoir: int = 4096):
        self._reservoir_cap = int(reservoir)
        self._reservoir: List[float] = []
        self._hist = Histogram()
        self._lock = threading.Lock()

    def record(self, value_ms: float) -> None:
        self._hist.record(value_ms)
        with self._lock:
            if len(self._reservoir) < self._reservoir_cap:
                self._reservoir.append(float(value_ms))

    @property
    def count(self) -> int:
        return self._hist.count

    def percentile(self, q_pct: float) -> Optional[float]:
        """Exact while every sample is still in the reservoir; histogram
        quantile (one-bucket-width accuracy) beyond it."""
        with self._lock:
            exact = (
                list(self._reservoir)
                if self._hist.count <= len(self._reservoir)
                else None
            )
        if exact is not None:
            if not exact:
                return None
            exact.sort()
            # Nearest-rank with linear interpolation (numpy default).
            pos = (len(exact) - 1) * q_pct / 100.0
            lo = int(math.floor(pos))
            hi = min(lo + 1, len(exact) - 1)
            return exact[lo] + (exact[hi] - exact[lo]) * (pos - lo)
        return self._hist.quantile(q_pct / 100.0)

    def snapshot(self) -> Dict[str, object]:
        return self._hist.snapshot()


# ------------------------------------------------------------------- tracing


def trace_from_env() -> bool:
    """The PHOTON_TRACE knob: drivers start a tracer when it is on."""
    return bool(get_knob("PHOTON_TRACE"))


# What tells the program's spans from a driver's own in a profiler trace.
PROFILER_PREFIX = "photon/"
_TRACE_ANNOTATION = None


def profiler_annotation(name: str):
    """A `jax.profiler.TraceAnnotation` named `photon/<name>`: a host span
    on the profiler's clock, the one the device operations are stamped on.
    A TraceMe is a no-op outside a profiler session, so callers enter it
    unconditionally. jax is imported on first use (this module stays
    stdlib-only at import)."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _TRACE_ANNOTATION = TraceAnnotation
    return _TRACE_ANNOTATION(PROFILER_PREFIX + name)


class _NullSpan:
    """Shared no-op context manager: the entire cost of an un-traced
    `span()` call is one global read plus returning this singleton."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    """One open span: records a Chrome 'X' (complete) event on exit."""

    __slots__ = (
        "tracer", "name", "args", "span_id", "parent_id", "_t0", "_annotation"
    )

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, object]):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.span_id = tracer._next_id()
        self.parent_id: Optional[int] = None
        self._t0 = 0
        self._annotation = profiler_annotation(name)

    def set(self, **args) -> None:
        """Attach/overwrite span args mid-flight (e.g. outcome fields)."""
        self.args.update(args)

    def __enter__(self) -> "_Span":
        stack = self.tracer._stack()
        if stack:
            self.parent_id = stack[-1]
        elif getattr(self.tracer._tls, "adopted_parent", None) is not None:
            self.parent_id = self.tracer._tls.adopted_parent
        stack.append(self.span_id)
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter_ns()
        self._annotation.__exit__(exc_type, exc, tb)
        stack = self.tracer._stack()
        if stack and stack[-1] == self.span_id:
            stack.pop()
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        self.tracer._record(self, self._t0, t1)
        return False


class Tracer:
    """Thread-aware span collector exporting Chrome trace-event JSON.

    One tracer per run; `install_tracer` makes it the process-ambient
    sink for `span()`. Parentage is per-thread (innermost open span on
    the same thread), with `span_handoff`/`adopt_span` carrying the
    parent across thread submits — the stage_scope handoff pattern."""

    def __init__(self) -> None:
        self.trace_id = f"{os.getpid():x}-{time.time_ns():x}"
        self._events: List[dict] = []
        self._threads: Dict[int, str] = {}
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._ids = itertools.count(1)
        # Synthetic track ids, handed out when the OS reuses a dead
        # worker's thread ident (see _tid); offset far past real idents.
        self._synth_tids = itertools.count(1 << 48)
        # One reading of both clocks, taken together: span timestamps are
        # perf_counter_ns less `_t0_ns`, so the pair lays an exported trace
        # beside anything stamped in Unix time (a profiler's .xplane.pb).
        self._t0_ns = time.perf_counter_ns()
        self._wall_t0_ns = time.time_ns()

    # -- internals ----------------------------------------------------------

    def _next_id(self) -> int:
        return next(self._ids)

    def _stack(self) -> List[int]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _tid(self, thread: threading.Thread) -> int:
        """A stable per-thread track id, cached thread-locally. The OS
        reuses thread idents after a thread exits — routine with the
        short-lived worker fleet — so a successor reusing a recorded
        ident under a DIFFERENT name gets a synthetic track id instead;
        otherwise Perfetto would render its spans inside the dead
        worker's mislabeled track."""
        tid = getattr(self._tls, "tid", None)
        if tid is None:
            with self._lock:
                tid = thread.ident
                if self._threads.get(tid, thread.name) != thread.name:
                    tid = next(self._synth_tids)
                self._threads[tid] = thread.name
            self._tls.tid = tid
        return tid

    def _record(self, span: _Span, t0_ns: int, t1_ns: int) -> None:
        thread = threading.current_thread()
        args = dict(span.args)
        args["span_id"] = span.span_id
        if span.parent_id is not None:
            args["parent_id"] = span.parent_id
        event = {
            "name": span.name,
            "ph": "X",
            "ts": (t0_ns - self._t0_ns) / 1e3,  # microseconds
            "dur": max(0.0, (t1_ns - t0_ns) / 1e3),
            "pid": os.getpid(),
            "tid": self._tid(thread),
            "args": args,
        }
        with self._lock:
            self._events.append(event)

    # -- public -------------------------------------------------------------

    @property
    def num_spans(self) -> int:
        with self._lock:
            return len(self._events)

    def spans(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def to_chrome_trace(self) -> Dict[str, object]:
        """The Chrome trace-event JSON object format Perfetto loads: the
        span events plus thread_name metadata so the named worker fleet
        reads as named tracks."""
        with self._lock:
            events = list(self._events)
            threads = dict(self._threads)
        meta = [
            {
                "name": "thread_name",
                "ph": "M",
                "pid": os.getpid(),
                "tid": tid,
                "args": {"name": tname},
            }
            for tid, tname in sorted(threads.items())
        ]
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {
                "trace_id": self.trace_id,
                "wall_t0_unix_s": self._wall_t0_ns / 1e9,
                "clock_anchor": {
                    "perf_counter_ns": self._t0_ns,
                    "time_ns": self._wall_t0_ns,
                },
            },
        }

    def export(self, path: str) -> str:
        """Atomic write of the Chrome trace JSON; returns `path`."""
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        os.replace(tmp, path)
        return path


_TRACER: Optional[Tracer] = None


def install_tracer(tracer: Tracer) -> Tracer:
    global _TRACER
    _TRACER = tracer
    return tracer


def uninstall_tracer() -> Optional[Tracer]:
    global _TRACER
    tracer, _TRACER = _TRACER, None
    return tracer


def current_tracer() -> Optional[Tracer]:
    return _TRACER


def start_tracing_if_enabled() -> Optional[Tracer]:
    """Driver entry: install a fresh tracer when PHOTON_TRACE is on."""
    if trace_from_env() and _TRACER is None:
        return install_tracer(Tracer())
    return _TRACER


def span(name: str, **args):
    """Open a span under this thread's innermost open span. With no
    tracer installed this is the shared no-op context manager."""
    tracer = _TRACER
    if tracer is None:
        return _NULL_SPAN
    return _Span(tracer, name, args)


def span_handoff() -> Optional[Tuple[Tracer, Optional[int]]]:
    """Capture (tracer, current span id) at submit time — hand it to a
    worker thread so its spans parent under the submitter's."""
    tracer = _TRACER
    if tracer is None:
        return None
    stack = tracer._stack()
    parent = stack[-1] if stack else getattr(
        tracer._tls, "adopted_parent", None
    )
    return (tracer, parent)


class _Adopt:
    __slots__ = ("handoff", "_prev")

    def __init__(self, handoff):
        self.handoff = handoff
        self._prev = None

    def __enter__(self):
        if self.handoff is not None:
            tracer, parent = self.handoff
            self._prev = getattr(tracer._tls, "adopted_parent", None)
            tracer._tls.adopted_parent = parent
        return self

    def __exit__(self, *exc):
        if self.handoff is not None:
            tracer, _ = self.handoff
            tracer._tls.adopted_parent = self._prev
        return False


def adopt_span(handoff: Optional[Tuple[Tracer, Optional[int]]]):
    """Worker-thread side of `span_handoff`: spans opened inside adopt
    under the submitter's span (no-op for a None handoff)."""
    return _Adopt(handoff)


# ------------------------------------------------------------------- journal


class RunJournal:
    """JSONL sink of typed run events — append-only within a run, but a
    fresh journal TRUNCATES its file: journal.jsonl is a per-run
    artifact like trace.json/profile.json, and a re-run into the same
    output directory must not interleave two runs' events. Every line is
    validated against its `contracts.JOURNAL_EVENT_SCHEMAS` schema
    BEFORE writing — the journal cannot hold a line its schema rejects."""

    def __init__(self, path: str):
        self.path = path
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        self._f = open(path, "w")
        self._lock = threading.Lock()
        self._closed = False
        self.lines_written = 0

    def emit(self, etype: str, **fields) -> None:
        schema = JOURNAL_EVENT_SCHEMAS.get(etype)
        if schema is None:
            raise KeyError(
                f"unknown journal event type {etype!r} — declare its schema "
                "in utils/contracts.JOURNAL_EVENT_SCHEMAS"
            )
        missing = [k for k in schema if k not in fields]
        extra = [k for k in fields if k not in schema]
        if missing or extra:
            raise ValueError(
                f"journal event {etype!r} does not match its schema: "
                f"missing {missing}, unexpected {extra}"
            )
        line = {"ts": round(time.time(), 6), "type": etype, **fields}
        text = json.dumps(line, default=str)
        with self._lock:
            if self._closed:
                return
            self._f.write(text + "\n")
            self._f.flush()
            self.lines_written += 1

    def close(self) -> None:
        with self._lock:
            if not self._closed:
                self._closed = True
                self._f.close()

    def __enter__(self) -> "RunJournal":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


_JOURNAL: Optional[RunJournal] = None


def install_journal(journal: RunJournal) -> RunJournal:
    global _JOURNAL
    _JOURNAL = journal
    return journal


def uninstall_journal() -> Optional[RunJournal]:
    global _JOURNAL
    journal, _JOURNAL = _JOURNAL, None
    return journal


def current_journal() -> Optional[RunJournal]:
    return _JOURNAL


def emit_event(etype: str, **fields) -> None:
    """Emit into the ambient journal (free no-op without one). Schema
    violations RAISE — a mistyped emit site is a bug, not a log line."""
    journal = _JOURNAL
    if journal is not None:
        journal.emit(etype, **fields)


def validate_journal(path: str) -> Tuple[int, List[str]]:
    """Re-validate a journal file line by line; returns (valid_lines,
    errors) — the `cli/obs journal --validate` engine and the e2e
    contract's journal check."""
    n_ok = 0
    errors: List[str] = []
    with open(path) as f:
        for lineno, raw in enumerate(f, 1):
            raw = raw.strip()
            if not raw:
                continue
            try:
                doc = json.loads(raw)
            except ValueError as exc:
                errors.append(f"line {lineno}: not JSON ({exc})")
                continue
            etype = doc.get("type")
            schema = JOURNAL_EVENT_SCHEMAS.get(etype)
            if schema is None:
                errors.append(f"line {lineno}: unknown event type {etype!r}")
                continue
            body = {
                k: v for k, v in doc.items() if k not in JOURNAL_LINE_KEYS
            }
            missing = [k for k in schema if k not in body]
            extra = [k for k in body if k not in schema]
            if "ts" not in doc:
                errors.append(f"line {lineno}: missing ts")
            elif missing or extra:
                errors.append(
                    f"line {lineno}: {etype} schema mismatch "
                    f"(missing {missing}, unexpected {extra})"
                )
            else:
                n_ok += 1
    return n_ok, errors


# ------------------------------------------------------------------- profile

# Peak HBM bandwidth per chip (GB/s), keyed by the `device_kind` string
# the chip reports through JAX — recorded in the run profile's roofline
# block (`run_profile`, printed by cli/obs) so the planner can judge
# achieved bandwidth without re-deriving hardware constants. Keyed by
# kind, not platform: every TPU generation has its own peak, and a number
# judged against another chip's roofline is wrong without looking wrong.
# Source: Google Cloud documentation, "TPU v5e" (16 GB HBM2e at 819 GB/s);
# a v5e reports itself as "TPU v5 lite".
HBM_PEAK_GB_S = {"TPU v5 lite": 819.0}


def hbm_peak_gb_s(platform: object, device_kind: object) -> Optional[float]:
    """The chip's peak HBM bandwidth. None off the TPU (a CPU run carries
    no roofline annotation); a TPU of a kind not in the table is an error,
    never a default."""
    if platform != "tpu":
        return None
    if device_kind not in HBM_PEAK_GB_S:
        raise KeyError(
            f"no HBM peak recorded for TPU device_kind {device_kind!r}: add "
            f"it to telemetry.HBM_PEAK_GB_S with its source "
            f"(known: {sorted(HBM_PEAK_GB_S)})"
        )
    return HBM_PEAK_GB_S[device_kind]


def device_topology() -> Dict[str, object]:
    """The device landscape a profile was measured on (jax imported
    lazily; degrades to a host-only record when jax is unavailable)."""
    try:
        import jax

        devices = jax.devices()
        return {
            "platform": devices[0].platform if devices else "unknown",
            "device_count": len(devices),
            "device_kind": getattr(devices[0], "device_kind", "unknown")
            if devices
            else "unknown",
            "process_count": jax.process_count(),
            "host_cpus": os.cpu_count(),
        }
    except Exception:  # noqa: BLE001 - profile must not require a backend
        return {
            "platform": "unavailable",
            "device_count": 0,
            "device_kind": "unknown",
            "process_count": 0,
            "host_cpus": os.cpu_count(),
        }


def build_profile(
    kind: str,
    *,
    wall_s: float,
    stages: Mapping[str, float],
    dispatch: Mapping[str, object],
    bucket_shapes: Mapping[str, object],
    fit_timing: Optional[Mapping[str, object]] = None,
    ingest: Optional[Mapping[str, object]] = None,
    serving: Optional[Mapping[str, object]] = None,
    metrics: Optional[Mapping[str, object]] = None,
    topology: Optional[Mapping[str, object]] = None,
) -> Dict[str, object]:
    """Assemble a run profile. `kind` is "fit" or "serve"; the kind's
    extra sections are required (read_profile enforces them loudly)."""
    if kind not in ("fit", "serve"):
        raise ValueError(f"profile kind must be 'fit' or 'serve', not {kind!r}")
    topo = dict(topology if topology is not None else device_topology())
    profile: Dict[str, object] = {
        "kind": kind,
        "wall_s": round(float(wall_s), 4),
        "stages": {k: v for k, v in stages.items()},
        "dispatch": dict(dispatch),
        "bucket_shapes": dict(bucket_shapes),
        "device_topology": topo,
        "roofline": {
            "hbm_gb_per_s": hbm_peak_gb_s(
                topo.get("platform"), topo.get("device_kind")
            ),
        },
        "metrics": dict(metrics if metrics is not None else METRICS.snapshot()),
    }
    if kind == "fit":
        if fit_timing is None:
            raise ValueError("fit profiles need fit_timing")
        profile["fit_timing"] = dict(fit_timing)
        profile["ingest"] = dict(ingest or {})
    else:
        if serving is None:
            raise ValueError("serve profiles need the serving metrics block")
        profile["serving"] = dict(serving)
    return profile


def _profile_schema(kind: str) -> Sequence[str]:
    if kind == "fit":
        return PROFILE_FIT_KEYS
    if kind == "serve":
        return PROFILE_SERVE_KEYS
    return PROFILE_REQUIRED_KEYS


def write_profile(path: str, profile: Mapping[str, object]) -> str:
    """Validate against the kind's contract, then write atomically."""
    missing = [k for k in _profile_schema(str(profile.get("kind"))) if k not in profile]
    if missing:
        raise ValueError(
            f"refusing to write a profile missing contract keys {missing}"
        )
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(profile, f, indent=2, default=str)
    os.replace(tmp, path)
    return path


def read_profile(path: str, kind: Optional[str] = None) -> Dict[str, object]:
    """Read a profile back with the loud missing-key contract: a profile
    that silently lost a section is a measurement bug, so the CONSUMER
    fails rather than plan from it."""
    with open(path) as f:
        profile = json.load(f)
    found_kind = profile.get("kind")
    if kind is not None and found_kind != kind:
        raise ValueError(
            f"profile at {path} has kind {found_kind!r}, expected {kind!r}"
        )
    missing = [k for k in _profile_schema(str(found_kind)) if k not in profile]
    if missing:
        raise ValueError(
            f"profile at {path} is missing contract keys {missing} "
            f"(got {sorted(profile)}) — the run-profile contract is broken"
        )
    return profile
