"""Deadline micro-batching: coalesce single requests into engine batches.

Latency/throughput tradeoff of every online scorer: dispatching each
request alone wastes the accelerator (a bucket-1 program per request);
waiting for a full batch starves low-traffic periods. The batcher flushes
the pending queue when EITHER `max_batch` requests are waiting (throughput
bound) or the OLDEST pending request has waited `max_wait_ms`
(tail-latency bound) — the standard deadline policy.

Production-traffic hardening (serving/lifecycle.py types):

* ADMISSION CONTROL — the pending queue is bounded by `max_pending`; a
  submit against a full queue is shed with a typed `Overloaded` rejection
  (counted per batcher and in COUNTERS["serving_shed_requests"]), never an
  unbounded backlog. Closed-loop clients (replay drivers, `score()`)
  can pass `block=True` to wait for space instead — backpressure, bounded
  by the flush loop's progress. The `admit` fault site fires per submit:
  an armed fault sheds deterministically (chaos-testable admission).
* DEADLINE ENFORCEMENT — each request carries a deadline budget
  (`ScoreRequest.deadline_ms`, falling back to the batcher's
  `default_deadline_ms`). A request still queued past its budget is failed
  with `DeadlineExceeded` at batch-assembly time, BEFORE wasting a device
  slot — an expired request is never co-batched. The budget check
  subtracts a decaying max of recent batch service time: a request whose
  answer could only arrive past its deadline is failed up front too, so
  admitted-request tail latency stays under the configured deadline even
  at sustained overload (a stale estimate decays on dispatch-less expiry
  rounds, so a one-off spike can never wedge the queue shut).
* CIRCUIT ROUTING — the engine's breaker counts consecutive device-class
  failures that survived the bounded retry policy; once OPEN, batches are
  routed to the engine's fixed-effect-only tier (bitwise-equal to FE-only
  GameTransformer output) instead of failing, with half-open probing to
  recover the full path.
* FLUSH-THREAD DEATH — an exception escaping the flush loop no longer
  leaves every pending and future submit() hanging: all pending futures
  are failed with the error, the batcher is marked unhealthy (a
  `BatcherUnhealthy` on later submits, a permanent DEGRADED reason on the
  engine's health machine), and `close()` stays joinable.

Failure domain (utils/faults.py): the engine's `lookup`/`score` fault
points surface transient failures mid-batch. The batcher DEGRADES instead
of dying: ANY failed batch re-dispatches per request — transient failures
get the bounded retry policy; a non-transient error (one malformed
request poisoning the pack) fails only the offending request's future,
never its co-batched neighbors. One poisoned buffer or transient device
error costs latency, not availability — and because the engine's kernels
are batch-size invariant, the degraded answers are bitwise-identical to
the batched ones (tests/test_serving.py asserts this under injected
faults). Each degradation increments the per-batcher `degraded_batches`
metric and the process-wide COUNTERS["serving_degraded_batches"], zero on
clean runs by construction.

Observability: per-request wall latency is recorded at completion into a
BOUNDED tracker (utils/telemetry.LatencyStats — a mergeable fixed-bucket
histogram plus a small reservoir for exact small-run percentiles; the
former unbounded sample list grew without bound under sustained traffic,
ISSUE 11 satellite). `metrics()` reports p50/p95/p99 (exact while the
run fits the reservoir, within one log-bucket width beyond it), qps,
shed/deadline-miss/fe-only counts, and the engine's counters in one
snapshot — the serving counterpart of PR 1's fit_timing stage breakdown.
Queue wait, batch size and latency also feed the process metrics
registry, and each dispatched batch opens a `serving_batch` trace span
carrying queue-wait and deadline-budget attribution.

Generation changes are transparent here: a bundle hot-swap OR a live
mesh reshard (serving/reshard.py) flips the engine's state between
batches — a batch claimed before the flip scores (and drains) on the
generation it started on, one claimed after scores on the new one, and
because both generations answer bitwise-identically the batcher never
has to know a flip happened. During a reshard's pre-warm the engine's
device mutex briefly serializes dispatches; the added queue wait rides
the same decaying service-tail estimate deadline enforcement already
uses.

The flush thread is named `photon-serving-flush` and MUST be joined via
`close()` (or the engine's close, or context-manager exit) — the test
suite's thread-leak fixture asserts no such thread survives a test.
"""

from __future__ import annotations

import collections
import logging
import threading
import time
from concurrent.futures import Future
from typing import Deque, Dict, Iterable, List, Optional, Tuple

from photon_ml_tpu.serving.bundle import ScoreRequest
from photon_ml_tpu.serving.engine import ScoreResult, ServingEngine
from photon_ml_tpu.serving.lifecycle import (
    BatcherUnhealthy,
    DeadlineExceeded,
    Overloaded,
)
from photon_ml_tpu.utils import faults, telemetry

logger = logging.getLogger(__name__)

# One queued request: (request, future, submit time, absolute expiry or None).
_Pending = Tuple[ScoreRequest, Future, float, Optional[float]]


class MicroBatcher:
    """Bounded queue + flush thread in front of a ServingEngine.

    `submit()` returns a Future[ScoreResult]; `score()` is the blocking
    convenience (backpressured, never shed). Use as a context manager or
    call `close()` — close drains the queue (pending requests are still
    answered) and joins the flush thread.
    """

    def __init__(
        self,
        engine: ServingEngine,
        *,
        max_batch: Optional[int] = None,
        max_wait_ms: Optional[float] = None,
        max_pending: Optional[int] = None,
        default_deadline_ms: Optional[float] = None,
        latency_reservoir: int = 4096,
        thread_name: Optional[str] = None,
        metric_labels: Optional[Dict[str, str]] = None,
    ):
        # The partial-batch flush wait is a PLANNED quantity (ISSUE 14):
        # an explicit argument wins; None defers to the installed plan's
        # serving_max_wait_ms (observed-latency rule) and falls back to
        # the pre-planner default.
        if max_wait_ms is None:
            from photon_ml_tpu import planner

            max_wait_ms = float(planner.planned_value("serving_max_wait_ms"))
        self.engine = engine
        self.max_batch = int(
            engine.max_batch if max_batch is None else max_batch
        )
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.max_batch > engine.max_batch:
            raise ValueError(
                f"max_batch {self.max_batch} exceeds the engine's declared "
                f"bucket ceiling {engine.max_batch} (would recompile)"
            )
        # Admission bound: a few batches' worth by default — deep enough to
        # ride a burst, shallow enough that queueing delay stays within a
        # small multiple of the batch service time (shed, don't backlog).
        self.max_pending = int(
            max(4 * self.max_batch, 64) if max_pending is None else max_pending
        )
        if self.max_pending < 1:
            raise ValueError(
                f"max_pending must be >= 1, got {self.max_pending}"
            )
        self.default_deadline_ms = (
            None if default_deadline_ms is None else float(default_deadline_ms)
        )
        self.max_wait_s = float(max_wait_ms) / 1e3
        self._pending: Deque[_Pending] = collections.deque()
        self._cv = threading.Condition()
        self._stop = False
        self._unhealthy: Optional[BaseException] = None
        # Bounded latency accounting (ISSUE 11 satellite): the mergeable
        # fixed-bucket histogram + a `latency_reservoir`-sample reservoir
        # replace the unbounded per-request list — memory stays O(1) in
        # request count under sustained traffic, percentiles stay exact
        # for small runs and within one bucket width beyond.
        self._latency = telemetry.LatencyStats(reservoir=latency_reservoir)
        # Per-batcher batch-size percentiles: the planner's bucket-
        # ceiling evidence (the process-global serving_batch_size
        # histogram mixes every batcher in the process).
        self._batch_sizes = telemetry.LatencyStats(reservoir=latency_reservoir)
        self._completed = 0
        self._failed = 0
        self._shed = 0
        self._deadline_missed = 0
        # Decaying MAX of batch service time (claim -> answers), subtracted
        # from a request's remaining budget at claim: a request that cannot
        # FINISH inside its deadline is failed up front, not co-batched
        # into an answer that arrives past its budget anyway. A decaying
        # max (not a mean) because the contract is about the admitted
        # TAIL: the p99 request pays the p99 service time.
        self._service_tail_s = 0.0
        self._fe_only = 0  # requests answered by the circuit-open FE tier
        self._degraded = 0  # THIS batcher's degraded batches (the global
        # faults counter aggregates process-wide and would cross-contaminate
        # metrics when several engines serve in one process)
        self._t_first_submit: Optional[float] = None
        self._t_last_done: Optional[float] = None
        # Per-tenant attribution (ISSUE 15): a batcher serving one tenant
        # of a multi-tenant registry carries that tenant's metric labels
        # — every process-global robustness counter it bumps (shed,
        # deadline, degraded, FE-only, flush death) lands in both the
        # aggregate and the tenant's labeled sub-count, whatever thread
        # fires it. None (the single-tenant default) keeps increments
        # unlabeled, bit-for-bit the pre-tenancy behavior.
        self._metric_labels = (
            tuple(sorted((k, str(v)) for k, v in metric_labels.items()))
            if metric_labels
            else None
        )
        self._thread = threading.Thread(
            target=self._flush_loop,
            name=thread_name or "photon-serving-flush",
            daemon=True,
        )
        self._thread.start()

    # ------------------------------------------------------------ lifecycle

    @property
    def closed(self) -> bool:
        return self._stop

    @property
    def healthy(self) -> bool:
        return self._unhealthy is None

    def close(self) -> None:
        """Drain pending requests, stop and JOIN the flush thread."""
        with self._cv:
            if self._stop:
                return
            self._stop = True
            self._cv.notify_all()
        self._thread.join()

    def __enter__(self) -> "MicroBatcher":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # -------------------------------------------------------------- scoring

    def submit(
        self,
        request: ScoreRequest,
        *,
        block: bool = False,
        deadline_ms: Optional[float] = None,
    ) -> "Future[ScoreResult]":
        """Enqueue one request. Raises `Overloaded` when the bounded queue
        is full (`block=True` waits for space instead — replay/closed-loop
        backpressure), `BatcherUnhealthy` after a flush-thread death,
        RuntimeError after close. `deadline_ms` overrides the request's
        own budget and the batcher default."""
        fut: "Future[ScoreResult]" = Future()
        now = time.monotonic()
        budget_ms = (
            deadline_ms
            if deadline_ms is not None
            else (
                request.deadline_ms
                if request.deadline_ms is not None
                else self.default_deadline_ms
            )
        )
        expiry = None if budget_ms is None else now + budget_ms / 1e3
        with self._cv:
            first_pass = True
            while True:
                if self._stop:
                    raise RuntimeError("MicroBatcher is closed")
                if self._unhealthy is not None:
                    raise BatcherUnhealthy(
                        f"flush thread died: {self._unhealthy!r}"
                    ) from self._unhealthy
                if first_pass:
                    # AFTER the closed/unhealthy checks: an armed admit
                    # fault simulates admission failing for a live batcher
                    # — it must never mask the typed closed/unhealthy
                    # rejections (nor count sheds for requests that would
                    # have been refused regardless). Once per submit. The
                    # engine's per-tenant injection gate applies: a chaos
                    # drill arming `admit` must target one tenant's
                    # admissions, not every batcher in the process.
                    first_pass = False
                    try:
                        if getattr(self.engine, "inject_faults", True):
                            faults.fault_point("admit")
                    except faults.InjectedFault as exc:
                        self._shed += 1
                        faults.COUNTERS.increment(
                            "serving_shed_requests",
                            labels=self._metric_labels,
                        )
                        raise Overloaded(
                            f"admission fault injected: {exc}"
                        ) from exc
                if len(self._pending) < self.max_pending:
                    break
                if not block:
                    self._shed += 1
                    faults.COUNTERS.increment(
                        "serving_shed_requests", labels=self._metric_labels
                    )
                    raise Overloaded(
                        f"pending queue full ({self.max_pending} requests); "
                        "shed by admission control"
                    )
                self._cv.wait()
            if self._t_first_submit is None:
                self._t_first_submit = now
            self._pending.append((request, fut, now, expiry))
            self._cv.notify_all()
        return fut

    def score(self, request: ScoreRequest) -> ScoreResult:
        return self.submit(request, block=True).result()

    def score_all(self, requests: Iterable[ScoreRequest]) -> List[ScoreResult]:
        """Replay helper: submit a stream (backpressured, never shed), wait
        for every result in order."""
        futures = [self.submit(r, block=True) for r in requests]
        return [f.result() for f in futures]

    # ----------------------------------------------------------- flush loop

    def _flush_loop(self) -> None:
        # Satellite hardening: an exception escaping the loop used to kill
        # the thread silently — every pending and future submit() then hung
        # forever. Now: fail ALL pending futures with the error, mark the
        # batcher unhealthy (typed rejections on later submits + a
        # permanent DEGRADED reason on the engine), stay joinable.
        try:
            if self._metric_labels is not None:
                # The tenant attribution scope lives for the thread's
                # whole life: everything the dispatch path fires from
                # HERE — including watchdog guards, whose trips are
                # recorded by the MONITOR thread with the labels captured
                # at arm time — lands in this tenant's sub-counts.
                with telemetry.metric_label_scope(
                    **dict(self._metric_labels)
                ):
                    self._flush_loop_inner()
                return
            self._flush_loop_inner()
        except BaseException as exc:  # noqa: BLE001 - terminal thread guard
            logger.error("serving flush thread died: %r", exc)
            faults.COUNTERS.increment(
                "serving_flush_thread_failures", labels=self._metric_labels
            )
            with self._cv:
                self._unhealthy = exc
                doomed = list(self._pending)
                self._pending.clear()
                self._failed += len(doomed)
                self._cv.notify_all()  # wake blocked submitters
            for _, fut, _, _ in doomed:
                if fut.set_running_or_notify_cancel():
                    fut.set_exception(exc)
            try:
                self.engine._on_batcher_unhealthy(exc)
            except Exception:  # noqa: BLE001 - health is best-effort here
                pass

    def _flush_loop_inner(self) -> None:
        while True:
            with self._cv:
                while not self._stop and not self._ripe_locked():
                    self._cv.wait(timeout=self._wait_timeout_locked())
                if self._stop and not self._pending:
                    return
                # Transition each future to RUNNING as it is claimed; a
                # client-cancelled future is dropped HERE — once running it
                # can no longer be cancelled, so the completion paths'
                # set_result/set_exception cannot race a cancel and blow
                # InvalidStateError through the flush thread. Requests past
                # their deadline budget are failed HERE, before a device
                # slot is assembled for them — never co-batched.
                batch: List[_Pending] = []
                expired: List[Future] = []
                now = time.monotonic()
                horizon = now + self._service_tail_s  # when answers would land
                while len(batch) < self.max_batch and self._pending:
                    item = self._pending.popleft()
                    if item[3] is not None and horizon >= item[3]:
                        if item[1].set_running_or_notify_cancel():
                            expired.append(item[1])
                        continue
                    if item[1].set_running_or_notify_cancel():
                        batch.append(item)
                telemetry.METRICS.set_gauge(
                    "serving_pending_depth", len(self._pending)
                )
                if expired:
                    self._deadline_missed += len(expired)
                    self._failed += len(expired)
                    if not batch:
                        # Everything expired and nothing dispatched: a
                        # stale/spiked service-tail estimate could otherwise
                        # pre-fail every short-budget request FOREVER (no
                        # dispatch -> no new measurement). Decay it so the
                        # batcher re-probes the true service time.
                        self._service_tail_s *= 0.5
                self._cv.notify_all()  # queue space freed: wake submitters
            for fut in expired:
                faults.COUNTERS.increment(
                    "serving_deadline_misses", labels=self._metric_labels
                )
                fut.set_exception(
                    DeadlineExceeded(
                        "request expired in queue before batch assembly"
                    )
                )
            if batch:
                try:
                    self._dispatch(batch)
                except BaseException as exc:
                    # The claimed batch is no longer in _pending — fail its
                    # futures HERE before the terminal guard handles the
                    # queued remainder, or they would hang unanswered.
                    # Mark unhealthy FIRST: once any client observes its
                    # future fail with the thread-death error, later
                    # submits must already be typed-rejected — not race
                    # the terminal guard a few frames up the unwind.
                    with self._cv:
                        self._unhealthy = exc
                        self._failed += sum(
                            1 for _, f, _, _ in batch if not f.done()
                        )
                    for _, fut, _, _ in batch:
                        if not fut.done():
                            fut.set_exception(exc)
                    raise

    def _ripe_locked(self) -> bool:
        if not self._pending:
            return False
        if len(self._pending) >= self.max_batch:
            return True
        front = self._pending[0]
        now = time.monotonic()
        if front[3] is not None and now >= front[3]:
            return True  # expired head: claim promptly to fail it
        return (now - front[2]) >= self.max_wait_s

    def _wait_timeout_locked(self) -> Optional[float]:
        if not self._pending:
            return None  # sleep until a submit/close notifies
        front = self._pending[0]
        wake = front[2] + self.max_wait_s
        if front[3] is not None:
            wake = min(wake, front[3])
        return max(0.0, wake - time.monotonic())

    def _update_service_tail(self, wall_s: float) -> None:
        with self._cv:
            self._service_tail_s = max(wall_s, 0.9 * self._service_tail_s)

    def _dispatch(self, batch: List[_Pending]) -> None:
        # Request-path telemetry (ISSUE 11): queue wait per claimed
        # request, batch size, and one `serving_batch` span carrying the
        # queue-wait and remaining-deadline-budget attribution — the
        # engine's serve_pack/serve_lookup/serve_score stage spans nest
        # under it, so a traced replay shows queue-wait -> assembly ->
        # device dispatch -> harvest per batch.
        now = time.monotonic()
        waits_ms = [(now - t0) * 1e3 for _, _, t0, _ in batch]
        for w in waits_ms:
            telemetry.METRICS.observe("serving_queue_wait_ms", w)
        telemetry.METRICS.observe("serving_batch_size", len(batch))
        self._batch_sizes.record(float(len(batch)))
        budgets = [(e - now) * 1e3 for _, _, _, e in batch if e is not None]
        with telemetry.span(
            "serving_batch",
            size=len(batch),
            queue_wait_ms_max=round(max(waits_ms), 3),
            deadline_budget_ms_min=(
                round(min(budgets), 3) if budgets else None
            ),
        ):
            self._dispatch_batch(batch)

    def _dispatch_batch(self, batch: List[_Pending]) -> None:
        requests = [r for r, _, _, _ in batch]
        t_d = time.monotonic()
        breaker = self.engine.breaker
        permit = breaker.acquire()
        if permit is None:
            # Circuit OPEN (and no probe due): degrade the whole batch to
            # the fixed-effect-only tier — answers, not errors.
            self._dispatch_fe_only(batch)
            return
        try:
            results = self.engine.score_batch(requests)
        except faults.DeviceHang:
            # A batch-level watchdog trip is UNAMBIGUOUS device evidence
            # (unlike a poisoned pack): feed the breaker directly and
            # answer the WHOLE batch FE-only — re-probing a wedged device
            # once per co-batched request would stall the flush thread
            # for many watchdog periods while the queue blows deadlines.
            breaker.on_failure(permit)
            faults.COUNTERS.increment(
                "serving_degraded_batches", labels=self._metric_labels
            )
            with self._cv:
                self._degraded += 1
            logger.warning(
                "batch of %d hit the dispatch watchdog; answering FE-only",
                len(requests),
            )
            self._dispatch_fe_only(batch)
            return
        except BaseException as exc:  # noqa: BLE001 - isolated below
            # ANY mid-batch failure degrades to per-request dispatch:
            # transient faults (injected, device blip) get the bounded
            # retry policy inside the fallback, while a non-transient error
            # (one malformed request poisoning the pack) re-raises
            # immediately there and fails ONLY the offending request's
            # future — co-batched healthy requests still get answers.
            # Batch-size-invariant kernels keep the degraded scores
            # bitwise-identical to what the batch would have produced. The
            # batch-level failure is INCONCLUSIVE for the breaker (one bad
            # request poisons a pack too): the permit is returned and each
            # per-request outcome is judged individually.
            breaker.on_abandon(permit)
            faults.COUNTERS.increment(
                "serving_degraded_batches", labels=self._metric_labels
            )
            with self._cv:
                self._degraded += 1
            logger.warning(
                "batch of %d degraded to per-request dispatch: %s",
                len(requests),
                exc,
            )
            self._dispatch_degraded(batch)
            return
        breaker.on_success(permit)
        now = time.monotonic()
        self._update_service_tail(now - t_d)
        for (_, fut, t0, _), res in zip(batch, results):
            self._complete(fut, res, now - t0)

    def _dispatch_degraded(self, batch: List[_Pending]) -> None:
        breaker = self.engine.breaker
        for req, fut, t0, _ in batch:
            permit = breaker.acquire()
            if permit is None:
                # The circuit opened mid-loop (this batch supplied the last
                # consecutive failures): remaining requests get FE-only
                # answers instead of piling more errors on a dead device.
                self._dispatch_fe_only([(req, fut, t0, None)])
                continue
            try:
                res = faults.retry(
                    lambda req=req: self.engine.score_batch([req])[0],
                    label="serving per-request fallback",
                )
            except BaseException as exc:  # noqa: BLE001 - surfaced via future
                if faults.is_device_error(exc):
                    # Survived the bounded retry policy and still looks
                    # like the device: evidence toward opening the circuit.
                    breaker.on_failure(permit)
                else:
                    breaker.on_abandon(permit)  # the request's fault, not the device's
                if isinstance(exc, faults.DeviceHang):
                    # A watchdog-tripped dispatch that outlived its bounded
                    # retries still ANSWERS: the hang contract (ISSUE 10)
                    # is a DEGRADED health transition + FE-only answers,
                    # never a stuck-or-failed future — the FE-only tier
                    # has no watchdog (it must work while the full path is
                    # wedged).
                    self._dispatch_fe_only([(req, fut, t0, None)])
                    continue
                with self._cv:
                    self._failed += 1
                fut.set_exception(exc)
                continue
            breaker.on_success(permit)
            self._complete(fut, res, time.monotonic() - t0)

    def _dispatch_fe_only(self, batch: List[_Pending]) -> None:
        """Circuit-open tier: fixed-effect-only answers via the pinned
        zero-row path (no fault sites fire — this must work while the full
        path is down)."""
        requests = [r for r, _, _, _ in batch]
        try:
            results = self.engine.score_batch_fe_only(requests)
        except BaseException as exc:  # noqa: BLE001 - surfaced via futures
            logger.error("FE-only degradation tier failed: %r", exc)
            with self._cv:
                self._failed += len(batch)
            for _, fut, _, _ in batch:
                fut.set_exception(exc)
            return
        with self._cv:
            self._fe_only += len(batch)
        faults.COUNTERS.increment(
            "serving_fe_only_requests", len(batch), labels=self._metric_labels
        )
        now = time.monotonic()
        for (_, fut, t0, _), res in zip(batch, results):
            self._complete(fut, res, now - t0)

    def _complete(self, fut: Future, res: ScoreResult, wall_s: float) -> None:
        self._latency.record(wall_s * 1e3)
        telemetry.METRICS.observe("serving_latency_ms", wall_s * 1e3)
        with self._cv:
            self._completed += 1
            self._t_last_done = time.monotonic()
        fut.set_result(res)

    # -------------------------------------------------------------- metrics

    def metrics(self) -> Dict[str, object]:
        """One snapshot: request latency percentiles + qps + admission/
        deadline/circuit accounting + the engine's counters. Keys are
        contracts.SERVING_METRIC_KEYS and the engine's own."""
        with self._cv:
            completed = self._completed
            failed = self._failed
            degraded = self._degraded
            shed = self._shed
            deadline_missed = self._deadline_missed
            fe_only = self._fe_only
            unhealthy = self._unhealthy
            t0, t1 = self._t_first_submit, self._t_last_done
        out: Dict[str, object] = {
            "completed": completed,
            "failed": failed,
            "degraded_batches": degraded,
            "shed": shed,
            "deadline_missed": deadline_missed,
            "fe_only_answers": fe_only,
            "max_pending": self.max_pending,
            "unhealthy": None if unhealthy is None else repr(unhealthy),
        }
        if self._latency.count:
            # Exact while the run still fits the reservoir; histogram
            # quantile (one log-bucket accuracy) under sustained traffic.
            out.update(
                p50_ms=round(float(self._latency.percentile(50.0)), 4),
                p95_ms=round(float(self._latency.percentile(95.0)), 4),
                p99_ms=round(float(self._latency.percentile(99.0)), 4),
            )
        else:
            out.update(p50_ms=None, p95_ms=None, p99_ms=None)
        # The observed-batch-size percentile the planner's serving bucket
        # rule consumes (serve profiles carry metrics(), so this is the
        # rule's REAL production evidence, not a fixture-only key).
        out["batch_size_p95"] = (
            round(float(self._batch_sizes.percentile(95.0)), 2)
            if self._batch_sizes.count
            else None
        )
        wall = (t1 - t0) if (t0 is not None and t1 is not None and t1 > t0) else 0.0
        out["qps"] = round(completed / wall, 1) if wall > 0 else None
        out.update(self.engine.metrics())
        return out
