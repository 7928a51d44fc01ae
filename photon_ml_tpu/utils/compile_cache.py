"""JAX's persistent compilation cache, placed from outside or at one fixed
path, and the record of every program this process makes ready.

Every entry point that compiles (cli/train, cli/serve, cli/score,
cli/refresh, chip_smoke.py) calls `enable()` before its
first compile. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it
itself and no directory is set here; where it is not, the cache lives at
`<checkout>/.jax_cache` — a fixed path, because the path is part of what a
cached entry is found by: a directory named after a pid, a time or a
temporary file never hits.

**The record** (`listen()`, which `enable()` calls): JAX reports every
trace, every lowering and every backend step (a cache read on a hit, a
compilation on a miss) through `jax.monitoring`, with the function's name
and the span's start and end. A backend step closes one record — `program`
(`jit(train_fn)`; an eager dispatch under its primitive's name,
`jit(broadcast_in_dim)`), `stage` (the calling thread's innermost open
`observability.stage_timer`, or `none`), `hit`, the wall-clock `start`, and
the seconds of each of `PHASES`:

* `trace`, `lower`: SELF time. Spans nest (the trace of `train_fn` holds
  the trace of `_minimize`; a trace can hold a whole eager dispatch), so a
  span counts its duration less what its children cover, and the phases of
  a stage never add to more than the stage's wall.
* `cache_read`: the backend step of a hit, whole — the key, the read and
  the deserialisation (`/jax/compilation_cache/cache_retrieval_time_sec`
  reports the last two and lies inside it); 0 on a miss.
* `compile`: the backend step of a miss; 0 on a hit.

A trace that reaches no backend step (`jax.eval_shape`, `.lower()` alone)
is counted in its stage's seconds and rides in the thread's next record.

Sinks, always on: histogram `program_ready_s{phase,stage}` (one observation
a span), counters `compile_cache_requests` / `compile_cache_hits` labelled
`stage=<name>` (the unlabelled totals are what they were: programs compiled
= requests - hits), the records themselves (`programs()`, `summary()` in
`run_profile()["programs"]`), a journal line `program_compiled` for every
miss, and a WARNING for a miss inside a fit after a fit has completed: a
steady refit loop compiles nothing. The listeners run only when JAX traces,
lowers or compiles.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Dict, List, Optional, Sequence

from photon_ml_tpu.utils import telemetry
from photon_ml_tpu.utils.observability import current_stage, open_stages

logger = logging.getLogger(__name__)

ENV = "JAX_COMPILATION_CACHE_DIR"

# The checkout that holds this package (photon_ml_tpu/utils/ -> two up).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

PHASES = ("trace", "lower", "cache_read", "compile")

_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_HIT = "/jax/compilation_cache/cache_hits"
_BACKEND = "/jax/core/compile/backend_compile_duration"
# Span event -> phase; the backend step's phase is the hit's or the miss's.
_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    _BACKEND: None,
}


class _Thread(threading.local):
    """One thread's open spans and the record it is filling."""

    def __init__(self) -> None:
        self.covered: List[float] = []  # per open span: seconds its children took
        self.hit = False
        self.start: Optional[float] = None
        self.seconds = dict.fromkeys(PHASES, 0.0)


_THREAD = _Thread()
_RECORDS: List[Dict[str, object]] = []
_listening = False


def _on_event(event: str, **_kwargs) -> None:
    if event == _REQUEST:
        telemetry.METRICS.increment(
            "compile_cache_requests", labels=(("stage", current_stage()),)
        )
    elif event == _HIT:
        _THREAD.hit = True
        telemetry.METRICS.increment(
            "compile_cache_hits", labels=(("stage", current_stage()),)
        )


def _on_span_start(event: str, _start: float, **_kwargs) -> None:
    # JAX reports a span's start as a scalar when it opens.
    if event in _SPANS:
        _THREAD.covered.append(0.0)


def _on_span(event: str, start: float, end: float, fun_name: str = "", **_kwargs) -> None:
    if event not in _SPANS:
        return
    t = _THREAD
    wall = end - start
    covered = t.covered.pop() if t.covered else 0.0
    if t.covered:
        t.covered[-1] += wall
    own = max(0.0, wall - covered)
    stage = current_stage()
    phase = _SPANS[event] or ("cache_read" if t.hit else "compile")
    telemetry.METRICS.observe(
        "program_ready_s", own, labels=(("phase", phase), ("stage", stage))
    )
    t.seconds[phase] += own
    t.start = start if t.start is None else min(t.start, start)
    if event != _BACKEND:
        return
    record = {"program": fun_name, "stage": stage, "hit": t.hit, "start": t.start, **t.seconds}
    t.hit, t.start, t.seconds = False, None, dict.fromkeys(PHASES, 0.0)
    _RECORDS.append(record)
    if not record["hit"]:
        _on_miss(record)


def _on_miss(record: Dict[str, object]) -> None:
    telemetry.emit_event(
        "program_compiled",
        program=record["program"],
        stage=record["stage"],
        seconds=round(sum(record[p] for p in PHASES), 6),
    )
    # A fit publishes its stage walls when it ends: the histogram is there
    # from the first completed fit of the process on.
    fit_done = telemetry.METRICS.labeled_histogram("fit_stage_s", (("stage", "fit"),))
    if fit_done is not None and "fit" in open_stages():
        logger.warning(
            "%s compiled under stage %s (%.3f s) after a fit had completed: "
            "a refit loop should find every program made",
            record["program"], record["stage"], record["compile"],
        )


def listen() -> None:
    """Start the record for this process. Idempotent, and apart from
    `enable()`: JAX reports the backend step with or without the
    persistent cache."""
    global _listening
    if _listening:
        return
    import jax

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_scalar_listener(_on_span_start)
    jax.monitoring.register_event_time_span_listener(_on_span)
    _listening = True


def programs() -> List[Dict[str, object]]:
    """Every program made ready since `listen()`, in the order their
    backend steps ended: `program`, `stage`, `hit`, `start` (Unix seconds)
    and the seconds of each of `PHASES`."""
    return list(_RECORDS)


def summary(records: Optional[Sequence[Dict[str, object]]] = None) -> Dict[str, object]:
    """The `programs` block of a run profile: per stage the programs, the
    hits and the seconds by phase, and every miss in full."""
    records = programs() if records is None else records
    stages: Dict[str, Dict[str, object]] = {}
    for r in records:
        s = stages.setdefault(
            r["stage"], {"programs": 0, "hits": 0, "seconds": dict.fromkeys(PHASES, 0.0)}
        )
        s["programs"] += 1
        s["hits"] += bool(r["hit"])
        for p in PHASES:
            s["seconds"][p] += r[p]
    for s in stages.values():
        s["seconds"] = {p: round(v, 6) for p, v in s["seconds"].items()}
    return {"stages": stages, "misses": [dict(r) for r in records if not r["hit"]]}


def enable() -> str:
    """Turn the persistent cache on for this process; returns its directory.
    Idempotent. Must run before the first compile (JAX decides once per
    process whether the cache is in use)."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program, not only the slow ones: a cold chip run compiles
    # hundreds of sub-second programs, and together they are most of what a
    # second run would otherwise pay again.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    listen()
    return path
