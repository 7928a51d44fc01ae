"""Observability: section timing, job file logging, event bus, state summaries.

Counterparts:
  * `Timed` — photon-lib util/Timed.scala:33-60: wall-clock section profiling
    wrapping every pipeline stage; here a context manager/decorator that logs
    on exit and records into an optional registry for end-of-job summaries.
  * `PhotonLogger` — photon-lib util/PhotonLogger.scala:34-120: per-job log
    file with settable level (the reference writes to HDFS; here a local
    file handler on the standard logging tree).
  * `EventEmitter`/`Event` — photon-client event/ (EventEmitter.scala:24,
    Event.scala:28, EventListener.scala): synchronous listener bus for job
    lifecycle events.
  * `summarize_opt_result` — OptimizationStatesTracker.toSummaryString
    (OptimizationStatesTracker.scala:1-121): human-readable convergence
    summary of an OptResult, including vmapped (per-entity) results.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from contextlib import ContextDecorator, contextmanager
from typing import Callable, Dict, List, Optional, Type

import numpy as np

from photon_ml_tpu.optimize.common import ConvergenceReason, OptResult
from photon_ml_tpu.utils import telemetry

logger = logging.getLogger("photon_ml_tpu")


# --------------------------------------------------------------------- Timed


class TimingRegistry:
    """Accumulates (section -> seconds) across a job for a final summary.

    Thread-safe: the host data-plane pipeline records stage walls from
    producer threads (background pack, shard prefetch) concurrently with
    the main thread's recording.
    """

    def __init__(self) -> None:
        self.sections: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        # Non-time annotations (e.g. which pack path ran): last write wins,
        # read back by the estimator into fit_timing.
        self.notes: Dict[str, str] = {}
        self._lock = threading.Lock()

    def record(self, name: str, seconds: float) -> None:
        with self._lock:
            self.sections[name] = self.sections.get(name, 0.0) + seconds
            self.counts[name] = self.counts.get(name, 0) + 1

    def set_note(self, name: str, value: str) -> None:
        with self._lock:
            self.notes[name] = value

    def merge_note(self, name: str, value: str, conflict: str) -> None:
        """Atomic set-or-conflict: first writer records `value`, a later
        DIFFERENT value collapses the note to `conflict` (and it stays
        there). For notes that must reflect every concurrent writer —
        e.g. the sparse-layout note, where per-shard background packs may
        disagree and a last-write-wins record would let the planner force
        one shard's layout onto a genuinely mixed fit."""
        with self._lock:
            prior = self.notes.get(name)
            if prior is None:
                self.notes[name] = value
            elif prior != value:
                self.notes[name] = conflict

    def get_note(self, name: str, default: Optional[str] = None) -> Optional[str]:
        return self.notes.get(name, default)

    def clear_notes(self, *names: str) -> None:
        """Drop the named annotations — per-fit evidence (pack path, RE
        path, sparse layout) is cleared at fit start so a reused
        registry/estimator never reports a PREVIOUS fit's decisions as
        this fit's evidence."""
        with self._lock:
            for name in names:
                self.notes.pop(name, None)

    def get(self, name: str, default: float = 0.0) -> float:
        return self.sections.get(name, default)

    def summary(self) -> str:
        if not self.sections:
            return "(no timed sections)"
        width = max(len(k) for k in self.sections)
        lines = [
            f"{k.ljust(width)}  {self.sections[k]:10.3f}s  x{self.counts[k]}"
            for k in sorted(self.sections, key=self.sections.get, reverse=True)
        ]
        return "\n".join(lines)


class Timed(ContextDecorator):
    """`with Timed("read data"):` or `@Timed("fit")` — logs elapsed wall
    clock on exit (Timed.scala usage throughout GameTrainingDriver:360-480).
    """

    def __init__(
        self,
        message: str,
        *,
        log: Optional[logging.Logger] = None,
        registry: Optional[TimingRegistry] = None,
        level: int = logging.INFO,
    ):
        self.message = message
        self.log = log or logger
        self.registry = registry
        self.level = level
        self.elapsed: Optional[float] = None

    def __enter__(self) -> "Timed":
        self._t0 = time.perf_counter()
        # Timed sections double as trace spans (utils/telemetry.py): the
        # driver's section structure shows up as named tracks in Perfetto
        # for free. span() is the shared no-op when tracing is off.
        self._span = telemetry.span(self.message)
        self._span.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._span.__exit__(exc_type, exc, tb)
        self.elapsed = time.perf_counter() - self._t0
        status = "" if exc_type is None else f" (FAILED: {exc_type.__name__})"
        self.log.log(self.level, "%s: %.3fs%s", self.message, self.elapsed, status)
        if self.registry is not None:
            self.registry.record(self.message, self.elapsed)
        return False


# ------------------------------------------------------------- stage timing
#
# Ambient per-stage accounting for the host data plane (the counterpart of
# the reference wrapping every pipeline stage in Timed,
# GameTrainingDriver.scala:360-480). A caller that wants a stage breakdown
# (GameEstimator.fit) opens a `stage_scope(registry)`; the data-plane
# functions (RE dataset build, projector, stats, bucketed pack, device
# uploads) then record their walls into it through `record_stage` /
# `stage_timer`. The scope stack is THREAD-LOCAL: two estimators fitting
# on parallel threads (a thread-parallel hyperparameter sweep) must not
# cross-attribute each other's stage walls. Pipeline worker threads are
# handed the spawner's registry explicitly — `AsyncUploader` captures
# `current_stage_registry()` at submit time, and the prepare pool wraps
# each build in `stage_scope(registry)` — so producer work still lands in
# the fit that spawned it. With no scope open every record is a no-op, so
# library code can instrument unconditionally.

_STAGE_TLS = threading.local()


def _stage_stack() -> List[TimingRegistry]:
    stack = getattr(_STAGE_TLS, "stack", None)
    if stack is None:
        stack = _STAGE_TLS.stack = []
    return stack


@contextmanager
def stage_scope(registry: TimingRegistry):
    """Make `registry` this thread's ambient sink for `record_stage`."""
    stack = _stage_stack()
    stack.append(registry)
    try:
        yield registry
    finally:
        stack.pop()


def current_stage_registry() -> Optional[TimingRegistry]:
    """This thread's innermost open stage registry, or None."""
    stack = _stage_stack()
    return stack[-1] if stack else None


def record_stage(name: str, seconds: float) -> None:
    """Record into this thread's innermost stage scope (no-op without one)."""
    registry = current_stage_registry()
    if registry is not None:
        registry.record(name, seconds)


def open_stages() -> List[str]:
    """The names of this thread's open `stage_timer`s, outermost first (the
    live list: read it, do not keep it)."""
    names = getattr(_STAGE_TLS, "open", None)
    if names is None:
        names = _STAGE_TLS.open = []
    return names


def current_stage() -> str:
    """This thread's innermost open stage, or "none" outside every stage:
    what `utils/compile_cache` files a program under."""
    names = getattr(_STAGE_TLS, "open", None)
    return names[-1] if names else "none"


@contextmanager
def open_stage(name: str):
    """Mark `name` as this thread's innermost open stage and nothing else:
    for a block that keeps its own clock (the estimator's exclusive
    stages) and still wants its programs filed under its name."""
    names = open_stages()
    names.append(name)
    try:
        yield
    finally:
        names.pop()


def set_stage_note(name: str, value: str) -> None:
    """Attach a non-time annotation (e.g. `pack_path`) to this thread's
    innermost stage scope (no-op without one)."""
    registry = current_stage_registry()
    if registry is not None:
        registry.set_note(name, value)


class stage_timer:
    """`with stage_timer("upload"):` — the one boundary primitive, with
    three sinks: the block's wall clock goes to the ambient stage scope
    (`record_stage`), a trace span of the same name opens
    (utils/telemetry.py: the stages become Perfetto tracks without a
    second instrumentation pass), and a `photon/<name>` annotation lands
    on the profiler's clock, beside the device operations of the same
    `.xplane.pb`. Each is a free no-op when its sink is absent: no scope
    open, no `Tracer` installed, no profiler session. While the block runs
    its name is the thread's `current_stage()`.

    Keyword arguments are the span's; `set(**args)` adds to them
    mid-flight. After the block `seconds` holds its wall, so a caller
    that reports the wall itself reads the one the three sinks got."""

    __slots__ = ("name", "seconds", "_span", "_annotation", "_t0", "_open")

    def __init__(self, name: str, **args):
        self.name = name
        self.seconds = 0.0
        self._span = telemetry.span(name, **args)
        # A recorded span (it has an id) annotates itself; the shared
        # no-op does not, so untraced the stage does.
        self._annotation = (
            telemetry.profiler_annotation(name)
            if getattr(self._span, "span_id", None) is None
            else None
        )

    def set(self, **args) -> None:
        self._span.set(**args)

    def __enter__(self) -> "stage_timer":
        self._span.__enter__()
        if self._annotation is not None:
            self._annotation.__enter__()
        self._open = open_stages()
        self._open.append(self.name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.seconds = time.perf_counter() - self._t0
        self._open.pop()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        self._span.__exit__(exc_type, exc, tb)
        record_stage(self.name, self.seconds)
        return False


# -------------------------------------------------------------- PhotonLogger

_LEVELS = {
    "DEBUG": logging.DEBUG,
    "INFO": logging.INFO,
    "WARN": logging.WARNING,
    "WARNING": logging.WARNING,
    "ERROR": logging.ERROR,
    "CRITICAL": logging.CRITICAL,
    "FATAL": logging.CRITICAL,
}


def _resolve_level(level: str) -> int:
    """Unknown levels fall back to INFO with a warning (the CLI tolerates
    arbitrary --logging-level values; a typo must not abort a training job).
    """
    resolved = _LEVELS.get(level.upper())
    if resolved is None:
        logger.warning("unknown log level %r; falling back to INFO", level)
        return logging.INFO
    return resolved


class PhotonLogger:
    """Job-scoped file logger (PhotonLogger.scala:34-120): attaches a file
    handler to the package logger for the job's lifetime; `close()` (or use
    as a context manager) detaches, flushes, and restores the package logger
    level."""

    def __init__(self, log_path: str, level: str = "INFO"):
        resolved = _resolve_level(level)  # before opening the file
        self.log_path = log_path
        self.handler = logging.FileHandler(log_path)
        self.handler.setFormatter(
            logging.Formatter("%(asctime)s %(levelname)s %(name)s - %(message)s")
        )
        self.handler.setLevel(resolved)
        self._prev_logger_level = logger.level
        logger.addHandler(self.handler)
        if logger.level == logging.NOTSET or logger.level > resolved:
            logger.setLevel(resolved)

    def set_level(self, level: str) -> None:
        self.handler.setLevel(_resolve_level(level))

    def close(self) -> None:
        logger.removeHandler(self.handler)
        self.handler.close()
        logger.setLevel(self._prev_logger_level)

    def __enter__(self) -> "PhotonLogger":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


# ----------------------------------------------------------------- EventBus


@dataclasses.dataclass(frozen=True)
class Event:
    """Base lifecycle event (Event.scala:28)."""

    timestamp: float = dataclasses.field(default_factory=time.time)


@dataclasses.dataclass(frozen=True)
class PhotonSetupEvent(Event):
    args: str = ""


@dataclasses.dataclass(frozen=True)
class TrainingStartEvent(Event):
    num_samples: int = 0


@dataclasses.dataclass(frozen=True)
class TrainingFinishEvent(Event):
    num_configs: int = 0
    best_metric: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class PhotonFailureEvent(Event):
    error: str = ""


@dataclasses.dataclass(frozen=True)
class SweepConfigEvent(Event):
    """One optimization configuration of the reg-weight sweep starting
    (GameEstimator.fit's outer loop)."""

    index: int = 0
    total: int = 0


@dataclasses.dataclass(frozen=True)
class CoordinateUpdateEvent(Event):
    """One coordinate-descent update finished (accepted or rejected by
    the divergence guard). `fn_evals`: the objective evaluations its
    solves made, every attempt counted (a random effect's: summed over
    its entities); None where the coordinate reports none."""

    iteration: int = 0
    coordinate: str = ""
    seconds: float = 0.0
    accepted: bool = True
    fn_evals: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class CheckpointEvent(Event):
    """One durable checkpoint step committed (state.json + model npz)."""

    step: int = 0
    coordinate: str = ""


class EventEmitter:
    """Synchronous listener bus (EventEmitter.scala:24-58). Listeners
    register per event type (or Event for all); send() dispatches in
    registration order and never lets one listener's failure break the job.
    """

    def __init__(self) -> None:
        self._listeners: List[tuple] = []

    def register(
        self, listener: Callable[[Event], None], event_type: Type[Event] = Event
    ) -> None:
        self._listeners.append((event_type, listener))

    def send(self, event: Event) -> None:
        for etype, listener in self._listeners:
            if isinstance(event, etype):
                try:
                    listener(event)
                except Exception:  # noqa: BLE001 — listener isolation
                    logger.exception("event listener failed for %r", event)

    def clear(self) -> None:
        self._listeners.clear()


def journal_listener(journal) -> Callable[[Event], None]:
    """An EventEmitter listener writing lifecycle events into a
    `telemetry.RunJournal` — the JSONL sink behind the event bus
    (ISSUE 11). Each Event class maps to one typed journal schema
    (contracts.JOURNAL_EVENT_SCHEMAS); an Event type without a mapping
    is skipped, never an error (the bus is open for callers' own
    types)."""

    def _listen(event: Event) -> None:
        if isinstance(event, PhotonSetupEvent):
            journal.emit("setup", args=event.args)
        elif isinstance(event, TrainingStartEvent):
            journal.emit("fit_start", num_samples=event.num_samples)
        elif isinstance(event, SweepConfigEvent):
            journal.emit("sweep_config", index=event.index, total=event.total)
        elif isinstance(event, CoordinateUpdateEvent):
            journal.emit(
                "coordinate_update",
                iteration=event.iteration,
                coordinate=event.coordinate,
                seconds=round(event.seconds, 6),
                accepted=event.accepted,
                fn_evals=event.fn_evals,
            )
        elif isinstance(event, CheckpointEvent):
            journal.emit("checkpoint", step=event.step, coordinate=event.coordinate)
        elif isinstance(event, TrainingFinishEvent):
            journal.emit(
                "fit_finish",
                num_configs=event.num_configs,
                best_metric=event.best_metric,
            )
        elif isinstance(event, PhotonFailureEvent):
            journal.emit("failure", error=event.error)

    return _listen


# ------------------------------------------------- optimization summaries


def summarize_opt_result(result: OptResult, name: str = "optimization") -> str:
    """OptimizationStatesTracker.toSummaryString /
    RandomEffectOptimizationTracker summaries (CoordinateDescent.scala:
    230-251): convergence reasons, iteration stats, final loss stats. Works
    for a single solve (scalar fields) and vmapped solves (leading axes)."""
    its = np.atleast_1d(np.asarray(result.iterations))
    loss = np.atleast_1d(np.asarray(result.loss))
    gnorm = np.atleast_1d(np.asarray(result.gradient_norm))
    reasons = np.atleast_1d(np.asarray(result.reason))
    n = its.size
    counts = {
        ConvergenceReason(code).name: int((reasons == code).sum())
        for code in np.unique(reasons)
    }
    lines = [
        f"{name}: {n} problem(s)",
        f"  convergence: {counts}",
        f"  iterations:  mean {its.mean():.1f}  max {int(its.max())}",
        f"  final loss:  mean {loss.mean():.6g}  max {loss.max():.6g}",
        f"  |gradient|:  mean {gnorm.mean():.3g}  max {gnorm.max():.3g}",
    ]
    hist = np.asarray(result.loss_history)
    if hist.size:
        first = hist.reshape(-1, hist.shape[-1])[0]
        valid = first[np.isfinite(first)]
        if valid.size > 1:
            lines.append(
                f"  loss path:   {valid[0]:.6g} -> {valid[-1]:.6g} "
                f"({valid.size} tracked iterations)"
            )
    return "\n".join(lines)
