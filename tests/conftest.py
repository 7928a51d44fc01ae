"""Test harness configuration.

Mirrors the reference's SparkTestUtils strategy (photon-test-utils
SparkTestUtils.scala:55-75): where the reference spins up a local[*] Spark
cluster so shuffles/broadcasts/treeAggregate run the real code paths with
threads as executors, we force an 8-device virtual CPU mesh so pjit/shard_map
and the XLA collectives run the real multi-chip code paths on one host.

Tests always run on the CPU: the env vars are set before jax initializes a
backend, and the platform is pinned again through jax.config after import.
The persistent compilation cache stays off in the test process — entry
points that turn it on (utils/compile_cache.enable) must not make one test's
compiles another test's cache hits.
"""

import os

from photon_ml_tpu.utils.knobs import get_knob

# Light import: utils.knobs is stdlib-only, so reading the platform knob
# through the typed registry cannot initialize a backend early.
_PLATFORM = str(get_knob("PHOTON_TEST_PLATFORM"))
os.environ["JAX_PLATFORMS"] = _PLATFORM
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", _PLATFORM)
jax.config.update("jax_enable_compilation_cache", False)

import threading
import time

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 run"
    )
    config.addinivalue_line(
        "markers",
        "chaos: failure-domain tests (fault injection, kill-resume parity); "
        "the serving subset (-m 'chaos and serving') runs inside tier-1",
    )
    config.addinivalue_line(
        "markers",
        "serving: online serving engine tests (bundle/engine/batcher/"
        "lifecycle)",
    )
    config.addinivalue_line(
        "markers",
        "perf: perf-regression guards (engagement + non-dominance contracts "
        "on bench-like shapes); the heavy ones are also slow-marked",
    )
    config.addinivalue_line(
        "markers",
        "multihost: OS-process jax.distributed runs (coordinator + workers "
        "over virtual CPU devices); the SIGKILL drills are also slow-marked",
    )
    config.addinivalue_line(
        "markers",
        "elastic: live mesh elasticity (reshard under traffic, mid-fit "
        "mesh-loss resume); the SIGKILL and rollback-under-traffic drills "
        "are also slow-marked",
    )
    _assert_fault_sites_registered()


def _assert_fault_sites_registered():
    """Guard: planted fault sites and SITE_DESCRIPTIONS must agree at
    collection time. Promoted from a local regex to photon-lint's
    AST-based `fault-site-sync` check (photon_ml_tpu/analysis/), which
    also enforces the REVERSE direction — a described site nobody plants
    is advertised chaos coverage that does not exist — and that every
    site is a string literal."""
    from photon_ml_tpu.analysis import run_checks

    # Pragma-hygiene findings also ride along in any run; those belong to
    # the tier-1 analysis gate (test_analysis.py), not this collection
    # guard, which must fail ONLY for fault-site drift.
    findings = [
        f
        for f in run_checks(checks=["fault-site-sync"])
        if f.check == "fault-site-sync"
    ]
    if findings:
        import pytest as _pytest

        raise _pytest.UsageError(
            "fault-site-sync findings (run `python -m "
            "photon_ml_tpu.analysis --check fault-site-sync`):\n  "
            + "\n  ".join(f.render() for f in findings)
        )


@pytest.fixture
def rng():
    return np.random.default_rng(20260729)


@pytest.fixture
def assert_sharded_close():
    """`check(actual, desired, kind)`: a sharded program against the
    single-device program of the same model. Sharding changes which partial
    sums exist and the order they combine in, so the two agree to
    `contracts.SHARDED_VS_SINGLE_TOLERANCES[kind]` ("fit": coefficients and
    metrics after an iterative solve; "serve": one bucket program's
    scores), never bitwise. The SAME program on the same inputs (restore,
    replay, a rollback to the old generation) stays `array_equal`."""
    from photon_ml_tpu.utils.contracts import SHARDED_VS_SINGLE_TOLERANCES

    def check(actual, desired, kind):
        np.testing.assert_allclose(
            np.asarray(actual),
            np.asarray(desired),
            **SHARDED_VS_SINGLE_TOLERANCES[kind],
        )

    return check


@pytest.fixture(autouse=True)
def _failure_domain_hygiene(monkeypatch):
    """Per-test failure-domain invariants:

    * fault injection armed by one test never leaks into the next (the
      registry is process-global by design — production arms it once via
      env), and an ambient PHOTON_FAULTS/PHOTON_RETRY_* exported in the
      developer's shell never arms injection inside unrelated tests
      (faults.clear() forces an env re-read, so the env must be scrubbed);
    * robustness counters start at zero so tests can assert exact counts;
    * no `photon-async-upload` thread outlives the test that spawned it —
      AsyncUploader workers are per-job and must drain once their job
      completes; a lingering one means a job wedged (or a future leaked)
      and would make later tests' upload behavior order-dependent;
    * no `photon-serving-flush` thread outlives the test — a MicroBatcher's
      flush thread must be joined by engine/batcher close(); a survivor
      means serving work kept running against a torn-down fixture;
    * no `photon-serving-promote` thread outlives the test — a two-tier
      store's promotion worker is short-lived and joined by
      store.close()/bundle.release(); a survivor means promotions kept
      mutating a torn-down store;
    * no `photon-ckpt-write` thread outlives the test — a staged
      checkpoint write is joined by save() before the state.json commit
      (sharded checkpoints fan out `photon-ckpt-write-shard<k>` workers,
      joined the same way); a survivor means a step committed without its
      model file durable;
    * no `photon-watchdog` monitor outlives the test — a Watchdog is
      joined by its owner's close() (the serving engine, the sweep's
      per-train instance); a survivor means deadlines kept arming against
      a torn-down dispatcher;
    * no `photon-reshard` staging worker outlives the test — the live
      reshard orchestrator joins its per-shard upload workers before the
      generation flip; a survivor means staged uploads kept running
      against a rolled-back (or torn-down) generation;
    * no `photon-tenant-*` worker outlives the test — the multi-tenant
      registry's dispatch thread and per-tenant flush threads are joined
      by `TenantRegistry.close()`; a survivor means one tenant's traffic
      kept dispatching against a torn-down fleet;
    * no `photon-refresh-*` worker outlives the test — continuous-refresh
      loop helpers (traffic replays riding a delta apply) join before the
      loop returns; a survivor means requests kept scoring against a
      retired generation;
    * no `photon-hostmesh-*` heartbeat outlives the test — a multi-host
      worker's HostHeartbeat is stopped by its owner (the worker's
      finally); a survivor would keep writing beat files into a
      torn-down rendezvous and could declare phantom host losses;
    * no `photon-shadow-*` evaluation worker outlives the test — a
      ShadowController's window-evaluation thread is joined by
      `close()`; a survivor means mirrored windows kept scoring (and
      could journal verdicts) against a torn-down registry;
    * no `photon-tier-*` worker outlives the test — precision-ladder
      helpers (traffic replays riding a quantize/restore flip) join
      before the transition commits; a survivor means requests kept
      scoring against a drained generation.
    """
    from photon_ml_tpu.utils import faults, telemetry

    for var in (
        "PHOTON_FAULTS",
        "PHOTON_FAULTS_SEED",
        "PHOTON_RETRY_MAX_ATTEMPTS",
        "PHOTON_RETRY_BASE_DELAY_S",
        "PHOTON_RETRY_MAX_DELAY_S",
        "PHOTON_SOLVE_RETRIES",
        "PHOTON_WATCHDOG_MS",
        "PHOTON_COLLECTIVE_RETRIES",
        "PHOTON_SHARD_UPLOAD_RETRIES",
        "PHOTON_RESHARD_RETRIES",
        "PHOTON_REBALANCE_MIN_PROMOTIONS",
        # Multi-tenant serving (ISSUE 15): ambient quota/budget knobs in
        # the developer's shell must never reshape admission control or
        # HBM-pressure demotion inside unrelated tests.
        "PHOTON_TENANT_MAX_PENDING",
        "PHOTON_TENANT_HBM_FRACTION",
        # The adaptive planner (ISSUE 14): an ambient PHOTON_PLAN* in the
        # developer's shell must never install a plan inside unrelated
        # tests, and a plan installed by one test never leaks into the
        # next (estimator fits call ensure_ambient_plan).
        "PHOTON_PLAN",
        "PHOTON_PLAN_PROFILE",
        # Continuous refresh (ISSUE 16): ambient refresh knobs must never
        # resize delta batches or flip the full-refit escape hatch inside
        # unrelated tests.
        "PHOTON_REFRESH_BATCH_ROWS",
        "PHOTON_REFRESH_MAX_DELTA_FRACTION",
        # Multi-host production mode (ISSUE 17): an ambient mode flag or
        # heartbeat/retry tuning in the developer's shell must never make
        # unrelated tests believe they run inside a process group (knob
        # readers branch on PHOTON_MULTIHOST) or reshape loss detection.
        "PHOTON_MULTIHOST",
        "PHOTON_HOST_HEARTBEAT_MS",
        "PHOTON_HOST_LOSS_RETRIES",
        # Shadow deployment (ISSUE 18): ambient decision-loop tuning in
        # the developer's shell must never reshape verdict hysteresis,
        # regression tolerance, cooldowns, or mirror sampling inside
        # unrelated tests.
        "PHOTON_SHADOW_MIN_WINDOWS",
        "PHOTON_SHADOW_REGRESSION_TOL",
        "PHOTON_SHADOW_COOLDOWN_S",
        "PHOTON_SHADOW_MIRROR_FRACTION",
        # Closed-loop autoscaling (ISSUE 19): ambient control-loop tuning
        # in the developer's shell must never reshape tick cadence,
        # action budgets, or cooldowns inside unrelated tests.
        "PHOTON_AUTOPILOT_MS",
        "PHOTON_AUTOPILOT_MAX_ACTIONS",
        "PHOTON_AUTOPILOT_COOLDOWN_S",
        # Precision ladder (ISSUE 20): an ambient ladder opt-in or
        # pressure/ceiling tuning in the developer's shell must never
        # switch unrelated tests from host-tier demotion to quantization
        # or reshape the characterized-error gate.
        "PHOTON_TIER_LADDER",
        "PHOTON_TIER_BF16_PRESSURE",
        "PHOTON_TIER_INT8_PRESSURE",
        "PHOTON_TIER_INT8_ERROR_CEILING",
    ):
        monkeypatch.delenv(var, raising=False)
    from photon_ml_tpu import planner as _planner

    _planner.uninstall_plan()
    faults.clear()
    telemetry.METRICS.reset()  # counters AND histograms/gauges start clean
    yield
    _planner.uninstall_plan()
    faults.clear()
    telemetry.METRICS.reset()
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        leaked = [
            t
            for t in threading.enumerate()
            if t.name.startswith(
                (
                    "photon-async-upload",
                    "photon-serving-flush",
                    "photon-serving-promote",
                    "photon-ckpt-write",
                    "photon-watchdog",
                    "photon-reshard",
                    "photon-tenant",
                    "photon-refresh",
                    "photon-hostmesh",
                    "photon-shadow",
                    "photon-autopilot",
                    "photon-tier",
                )
            )
            and t.is_alive()
        ]
        if not leaked:
            break
        time.sleep(0.02)
    assert not leaked, f"leaked async-upload/serving-flush threads: {leaked}"
