"""Hang watchdog: a deadline armed around device dispatches.

The failure mode no counter observed before ISSUE 10: a wedged device (a
dead ICI link mid-collective, a runtime deadlock) blocks the dispatching host thread FOREVER — the fit never
fails, the serving request never resolves, and every robustness counter
reads zero because nothing ever *errored*. Spark's substrate covers this
with speculative re-execution and executor-loss timeouts; our pjit mesh
has nothing, so this module is the explicit replacement.

`Watchdog.guard(deadline_ms, label)` is a context manager that arms a
deadline on a shared monitor thread (`photon-watchdog`, joinable via
`close()` — the conftest leak guard asserts none survives a test):

  * if the guarded scope exits before the deadline, the guard is free
    (one lock hop to arm, one to disarm);
  * if the deadline passes first, the monitor TRIPS: it increments
    `COUNTERS["watchdog_trips"]`, logs, and fires the optional `on_trip`
    callback immediately — so a truly-stuck dispatch at least flips the
    owning engine's health to DEGRADED while it is still stuck;
  * when (if) the guarded scope finally returns, the tripped guard raises
    a typed `faults.DeviceHang` at exit — the result of an over-deadline
    dispatch is DISCARDED, exactly like a timed-out RPC. Device work is
    deterministic here, so the caller's bounded re-dispatch reproduces
    the same bits; a dispatch that never returns cannot be interrupted
    from Python, which is why the trip-time callback (not the exception)
    carries the degradation signal for that case.

Consumers: the scanned coordinate sweep (game/coordinate.py — a trip
becomes a bounded sweep re-dispatch, then the per-bucket fallback) and
the serving score path (serving/engine.py — a trip raises through
score_batch, the batcher's breaker counts it as a device failure, and
the circuit routes traffic to the FE-only tier). `PHOTON_WATCHDOG_MS`
arms both; 0 (the default) keeps the watchdog off and `guard()` free —
no thread is ever started.
"""

from __future__ import annotations

import itertools
import logging
import threading
import time
from contextlib import contextmanager
from typing import Callable, Dict, Optional, Tuple

from photon_ml_tpu.utils import faults, telemetry
from photon_ml_tpu.utils.knobs import get_knob

logger = logging.getLogger(__name__)


def watchdog_ms() -> float:
    """The env-configured dispatch deadline (PHOTON_WATCHDOG_MS); <= 0
    means the watchdog is off."""
    return float(int(get_knob("PHOTON_WATCHDOG_MS")))


class Watchdog:
    """One monitor thread arming deadlines over concurrent guarded scopes.

    Thread-safe: any number of dispatching threads may hold guards at
    once (the serving engine's batcher + direct callers). The monitor is
    started lazily on the first armed guard and joined by `close()`; a
    closed watchdog's `guard()` is a free no-op, so shutdown order never
    races a late dispatch.
    """

    def __init__(self, on_trip: Optional[Callable[[str], None]] = None):
        self._on_trip = on_trip
        self._cv = threading.Condition()
        # guard id -> (absolute deadline, label, [tripped] flag holder,
        # the dispatching thread's ambient metric labels — captured at
        # arm time because the trip fires from the MONITOR thread, where
        # the tenant attribution scope is not ambient)
        self._armed: Dict[int, Tuple[float, str, list, object]] = {}
        self._ids = itertools.count()
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self.trips = 0

    # ------------------------------------------------------------ monitor

    def _ensure_thread_locked(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._monitor, name="photon-watchdog", daemon=True
            )
            self._thread.start()

    def _monitor(self) -> None:
        with self._cv:
            while not self._closed:
                if not self._armed:
                    # Idle: park until the next arm (or close) notifies.
                    self._cv.wait()
                    continue
                now = time.monotonic()
                pending = [
                    d for d, _, flag, _ in self._armed.values() if not flag[0]
                ]
                if not pending:
                    # Every armed guard already tripped: park until its
                    # scope disarms (or a new guard arms).
                    self._cv.wait()
                    continue
                next_deadline = min(pending)
                if now < next_deadline:
                    self._cv.wait(timeout=next_deadline - now)
                    continue
                tripped = [
                    (gid, label, flag, mlabels)
                    for gid, (d, label, flag, mlabels) in self._armed.items()
                    if d <= now and not flag[0]
                ]
                for gid, label, flag, mlabels in tripped:
                    flag[0] = True
                    self.trips += 1
                    faults.COUNTERS.increment("watchdog_trips", labels=mlabels)
                    telemetry.emit_event("watchdog_trip", label=label)
                    logger.warning(
                        "watchdog tripped: %s exceeded its deadline "
                        "(dispatch still in flight)",
                        label,
                    )
                if tripped and self._on_trip is not None:
                    # Callbacks run with the cv RELEASED: a callback that
                    # takes engine locks must not deadlock against a
                    # dispatching thread arming a guard.
                    labels = [label for _, label, _, _ in tripped]
                    self._cv.release()
                    try:
                        for label in labels:
                            try:
                                self._on_trip(label)
                            except Exception:  # noqa: BLE001 - best-effort
                                logger.debug(
                                    "watchdog on_trip failed", exc_info=True
                                )
                    finally:
                        self._cv.acquire()

    # ------------------------------------------------------------- guards

    @contextmanager
    def guard(self, deadline_ms: float, label: str):
        """Arm `deadline_ms` around the scope; raise DeviceHang at exit if
        the deadline passed first. `deadline_ms <= 0` (watchdog off) is a
        free no-op."""
        if deadline_ms is None or deadline_ms <= 0:
            yield
            return
        flag = [False]
        gid = None
        with self._cv:
            if not self._closed:
                gid = next(self._ids)
                self._armed[gid] = (
                    time.monotonic() + deadline_ms / 1e3,
                    label,
                    flag,
                    telemetry.current_metric_labels(),
                )
                self._ensure_thread_locked()
                self._cv.notify_all()
        try:
            yield
        finally:
            if gid is not None:
                with self._cv:
                    self._armed.pop(gid, None)
                    self._cv.notify_all()
        if flag[0]:
            raise faults.DeviceHang(
                f"{label}: device dispatch exceeded the "
                f"{deadline_ms:.0f} ms watchdog deadline — result discarded"
            )

    # ------------------------------------------------------------ lifecycle

    def close(self) -> None:
        """Stop and JOIN the monitor thread (idempotent)."""
        with self._cv:
            self._closed = True
            thread = self._thread
            self._cv.notify_all()
        if thread is not None and thread is not threading.current_thread():
            thread.join(timeout=10)

    def __enter__(self) -> "Watchdog":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False
