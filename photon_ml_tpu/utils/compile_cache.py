"""JAX's persistent compilation cache, placed from outside or at one fixed path.

Every entry point that compiles (cli/train, cli/serve, cli/score,
cli/refresh, chip_smoke.py) calls `enable()` before its
first compile. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it
itself and no directory is set here; where it is not, the cache lives at
`<checkout>/.jax_cache` — a fixed path, because the path is part of what a
cached entry is found by: a directory named after a pid, a time or a
temporary file never hits.

Cache traffic is counted into the metrics registry (`compile_cache_requests`
/ `compile_cache_hits`), so a run's profile.json says how many programs it
compiled and how many it read back.
"""

from __future__ import annotations

import os

from photon_ml_tpu.utils import telemetry

ENV = "JAX_COMPILATION_CACHE_DIR"

# The checkout that holds this package (photon_ml_tpu/utils/ -> two up).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_listening = False


def _on_event(event: str, **_kwargs) -> None:
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        telemetry.METRICS.increment("compile_cache_requests")
    elif event == "/jax/compilation_cache/cache_hits":
        telemetry.METRICS.increment("compile_cache_hits")


def enable() -> str:
    """Turn the persistent cache on for this process; returns its directory.
    Idempotent. Must run before the first compile (JAX decides once per
    process whether the cache is in use)."""
    global _listening
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program, not only the slow ones: a cold chip run compiles
    # hundreds of sub-second programs, and together they are most of what a
    # second run would otherwise pay again.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return path
