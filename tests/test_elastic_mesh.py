"""Live mesh elasticity (ISSUE 13): reshard under traffic, mid-fit
mesh-loss resume, and hot-row rebalancing.

The contracts:

* `plan_reshard` computes the honest row-movement plan between shard
  layouts — only rows whose owning device changes count, padding never;
* a live reshard (shrink 8->4, regrow 4->8, collapse to replicated)
  keeps every answer BITWISE-equal to a cold-started engine at the new
  shape, drops zero requests under live traffic, and any failure at any
  step (staging, commit, a SIGKILL mid-restage) rolls back to the old
  generation with zero failed requests;
* a mid-fit `MeshLoss` costs exactly one repeated sweep: the resumed fit
  is bitwise the uninterrupted one, whether the state reassembles in
  memory or through the durable-checkpoint fallback;
* hot-row rebalancing closes the telemetry->placement loop: the two-tier
  store's observed promotions become the new hot-tier preload through
  the same stage/flip/rollback machinery, bitwise-neutral by
  construction.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.game.model import (
    Coefficients,
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_ml_tpu.parallel.mesh import make_mesh, surviving_mesh
from photon_ml_tpu.serving import (
    ScoreRequest,
    ServingBundle,
    ServingEngine,
    plan_rebalance,
    plan_reshard,
)
from photon_ml_tpu.transformers.game_transformer import CoordinateScoringSpec
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils import faults, telemetry
from photon_ml_tpu.utils.contracts import SHARDED_VS_SINGLE_TOLERANCES

pytestmark = pytest.mark.serving

# Live traffic crosses generations of different mesh shapes, each another
# program than the reference's: an answer is right within this, and a
# wrong or FE-only answer is off by orders of magnitude more.
_SERVE_TOL = SHARDED_VS_SINGLE_TOLERANCES["serve"]

TASK = TaskType.LOGISTIC_REGRESSION
D_FE, D_RE, E = 7, 5, 24


def _fixture(rng, n=16):
    w = rng.normal(size=D_FE).astype(np.float32)
    M = np.zeros((E + 1, D_RE), np.float32)
    M[:E] = rng.normal(size=(E, D_RE))
    model = GameModel(
        {
            "fixed": FixedEffectModel(Coefficients(jnp.asarray(w)), TASK),
            "per-e": RandomEffectModel(jnp.asarray(M), None, TASK),
        }
    )
    specs = {
        "fixed": CoordinateScoringSpec(shard="g"),
        "per-e": CoordinateScoringSpec(
            shard="re",
            random_effect_type="eid",
            entity_index={str(i): i for i in range(E)},
        ),
    }
    X = rng.normal(size=(n, D_FE)).astype(np.float32)
    Xe = rng.normal(size=(n, D_RE)).astype(np.float32)
    reqs = [
        ScoreRequest(
            features={"g": X[i], "re": Xe[i]},
            entity_ids={"eid": str((2 * i) % (E + 6))},
            uid=str(i),
        )
        for i in range(n)
    ]
    return model, specs, reqs


def _scores(results):
    return np.asarray([r.score for r in results], np.float64)


def _cold_scores(model, specs, reqs, mesh=None):
    with ServingEngine(
        ServingBundle.from_model(model, specs, TASK, mesh=mesh), max_batch=16
    ) as eng:
        return _scores(eng.score_batch(reqs))


# --------------------------------------------------------------- plan math


class TestReshardPlan:
    def test_shrink_plan_matches_brute_force_row_movement(self, rng):
        model, specs, _ = _fixture(rng)
        mesh8 = make_mesh()
        mesh4 = surviving_mesh(4)
        bundle = ServingBundle.from_model(model, specs, TASK, mesh=mesh8)
        plan = plan_reshard(bundle, mesh4)
        assert plan.old_shards == 8 and plan.new_shards == 4
        (cplan,) = plan.coordinates
        logical = E + 1
        assert cplan.logical_rows == logical
        assert cplan.padded_rows % 4 == 0
        # Brute force: a logical row moves iff its owning device changes.
        old_devs = list(np.asarray(mesh8.devices).flat)
        new_devs = list(np.asarray(mesh4.devices).flat)
        rows_per_old = bundle.coordinates["per-e"].shard_health.rows_per_shard
        rows_per_new = cplan.padded_rows // 4
        moved = sum(
            1
            for r in range(logical)
            if old_devs[r // rows_per_old] is not new_devs[r // rows_per_new]
        )
        assert cplan.moved_rows == moved > 0
        assert cplan.moved_bytes == moved * D_RE * 4
        assert plan.moved_rows == moved
        # Segments tile each new shard's block exactly.
        for k, segs in enumerate(cplan.segments):
            lo, hi = k * rows_per_new, (k + 1) * rows_per_new
            assert segs[0].row_lo == lo and segs[-1].row_hi == hi
            for a, b in zip(segs, segs[1:]):
                assert a.row_hi == b.row_lo

    def test_plan_requires_a_shard_tracked_coordinate(self, rng):
        model, specs, _ = _fixture(rng)
        bundle = ServingBundle.from_model(model, specs, TASK, hot_rows=4)
        try:
            # Drop the FE-only structure down to just the two-tier coord:
            # nothing left to mesh-reshard.
            with pytest.raises(ValueError, match="rebalance"):
                plan_reshard(
                    ServingBundle(
                        task=TASK,
                        coordinates={
                            "per-e": bundle.coordinates["per-e"]
                        },
                    ),
                    make_mesh(),
                )
        finally:
            bundle.release()

    def test_shard_loads_feed_the_plan(self, rng):
        """The engine records per-shard request load (cold starts
        excluded); the plan surfaces it so operators can see the
        overloaded shard."""
        model, specs, reqs = _fixture(rng)
        mesh8 = make_mesh()
        bundle = ServingBundle.from_model(model, specs, TASK, mesh=mesh8)
        with ServingEngine(bundle, max_batch=16) as eng:
            eng.score_batch(reqs)
            plan = plan_reshard(eng.bundle, surviving_mesh(4))
        (cplan,) = plan.coordinates
        known = sum(
            1 for r in reqs if int(r.entity_ids["eid"]) < E
        )
        assert sum(cplan.shard_loads) == known
        assert len(cplan.shard_loads) == 8


# ------------------------------------------------------------ live reshard


@pytest.mark.elastic
class TestLiveReshard:
    """Multi-device reshard drills on the 8-virtual-device mesh."""

    def test_shrink_regrow_replicate_parity(self, rng, assert_sharded_close):
        """8 -> 4 -> 8 -> replicated, each generation bitwise-equal to a
        cold start at that shape (the same program on the same rows) and
        within the `serve` tolerance of the replicated reference, zero
        hot-path recompiles after each pre-warm, and the generation
        counter advancing."""
        model, specs, reqs = _fixture(rng)
        ref = _cold_scores(model, specs, reqs)
        cold4 = _cold_scores(model, specs, reqs, mesh=surviving_mesh(4))
        cold8 = _cold_scores(model, specs, reqs, mesh=make_mesh())
        assert_sharded_close(cold4, ref, "serve")
        assert_sharded_close(cold8, ref, "serve")
        bundle = ServingBundle.from_model(model, specs, TASK, mesh=make_mesh())
        with ServingEngine(bundle, max_batch=16) as eng:
            eng.warmup()
            orch = eng.reshard_orchestrator
            info = orch.reshard(surviving_mesh(4))
            assert info["version"] == 1 and info["old_released"]
            assert info["old_shards"] == 8 and info["new_shards"] == 4
            assert np.array_equal(_scores(eng.score_batch(reqs)), cold4)
            assert eng.recompiles_after_warmup == 0  # pre-warm covered it
            info2 = orch.reshard(make_mesh())
            assert info2["new_shards"] == 8
            assert np.array_equal(_scores(eng.score_batch(reqs)), cold8)
            info3 = orch.reshard(None)  # collapse to replicated
            assert info3["new_shards"] == 1
            assert np.array_equal(_scores(eng.score_batch(reqs)), ref)
            m = eng.metrics()
            assert m["bundle_reshards"] == 3
            assert m["bundle_version"] == 3
            assert m["sharding"]["entity_sharded"] is False
            # The load-time bundle HANDLE stays a live view of the
            # current generation across every flip — callers that encode
            # requests through it (the CLI's lazy replay stream) must
            # keep working, never hit a release()-gutted husk.
            assert not bundle.released
            rows, cold = bundle.coordinates["per-e"].lookup_rows(["3"])
            assert rows[0] == 3 and cold == 0
        assert faults.counters().get("reshard_rollbacks", 0) == 0

    def test_reshard_under_live_traffic_zero_failed(self, rng):
        """The acceptance drill: shrink 8->4 and regrow 4->8 while a
        closed-loop client scores continuously through the batcher —
        zero failed requests, every answer the reference's within the
        `serve` tolerance whichever generation gave it, post-reshard
        probe bitwise the engine's own answers before it left that
        shape."""
        model, specs, reqs = _fixture(rng)
        ref = _cold_scores(model, specs, reqs)
        bundle = ServingBundle.from_model(model, specs, TASK, mesh=make_mesh())
        eng = ServingEngine(bundle, max_batch=16)
        eng.warmup()
        before = _scores(eng.score_batch(reqs))
        stop = threading.Event()
        failures: list = []
        answered = [0]

        def _traffic(b):
            j = 0
            while not stop.is_set():
                r = reqs[j % len(reqs)]
                try:
                    res = b.score(r)
                    if not np.isclose(
                        res.score, ref[j % len(reqs)], **_SERVE_TOL
                    ):
                        failures.append(
                            f"answer drift at {j}: {res.score}"
                        )
                    answered[0] += 1
                except Exception as exc:  # noqa: BLE001 - recorded
                    failures.append(repr(exc))
                j += 1

        with eng, eng.batcher(max_wait_ms=0.5) as batcher:
            th = threading.Thread(
                target=_traffic, args=(batcher,), name="elastic-traffic"
            )
            th.start()
            time.sleep(0.2)
            info = eng.reshard_orchestrator.reshard(surviving_mesh(4))
            time.sleep(0.2)
            info2 = eng.reshard_orchestrator.reshard(make_mesh())
            time.sleep(0.2)
            stop.set()
            th.join(timeout=60)
            assert not th.is_alive()
            probe = _scores(eng.score_batch(reqs))
        assert not failures, failures[:3]
        assert answered[0] > 0
        assert info["new_shards"] == 4 and info2["new_shards"] == 8
        assert np.array_equal(probe, before)
        assert faults.counters().get("reshard_rollbacks", 0) == 0


# ------------------------------------------------------------ rollback drills


@pytest.mark.elastic
@pytest.mark.chaos
class TestReshardRollback:
    def test_stage_failure_rolls_back_and_keeps_serving(
        self, rng, monkeypatch, assert_sharded_close
    ):
        monkeypatch.setenv("PHOTON_RETRY_BASE_DELAY_S", "0.001")
        model, specs, reqs = _fixture(rng)
        ref = _cold_scores(model, specs, reqs)
        bundle = ServingBundle.from_model(model, specs, TASK, mesh=make_mesh())
        with ServingEngine(bundle, max_batch=16) as eng:
            eng.warmup()
            before = _scores(eng.score_batch(reqs))
            assert_sharded_close(before, ref, "serve")
            with faults.inject("reshard_stage:9999"):
                with pytest.raises(faults.InjectedFault):
                    eng.reshard_orchestrator.reshard(surviving_mesh(4))
                # Old generation NEVER stopped serving, bitwise intact.
                assert np.array_equal(_scores(eng.score_batch(reqs)), before)
            c = faults.counters()
            assert c["reshard_rollbacks"] == 1
            assert c["reshard_retries"] > 0
            m = eng.metrics()
            assert m["bundle_version"] == 0
            assert m["bundle_reshards"] == 0
            assert m["bundle_reshard_rollbacks"] == 1
            # A later clean reshard still succeeds (no wedged state).
            info = eng.reshard_orchestrator.reshard(surviving_mesh(4))
            assert info["version"] == 1
            assert_sharded_close(_scores(eng.score_batch(reqs)), ref, "serve")

    def test_commit_failure_rolls_back(
        self, rng, monkeypatch, assert_sharded_close
    ):
        monkeypatch.setenv("PHOTON_RETRY_BASE_DELAY_S", "0.001")
        model, specs, reqs = _fixture(rng)
        ref = _cold_scores(model, specs, reqs)
        bundle = ServingBundle.from_model(model, specs, TASK, mesh=make_mesh())
        with ServingEngine(bundle, max_batch=16) as eng:
            before = _scores(eng.score_batch(reqs))
            assert_sharded_close(before, ref, "serve")
            with faults.inject("reshard_commit:1"):
                with pytest.raises(faults.InjectedFault):
                    eng.reshard_orchestrator.reshard(surviving_mesh(4))
            assert np.array_equal(_scores(eng.score_batch(reqs)), before)
            assert eng.bundle_version == 0
            assert faults.counters()["reshard_rollbacks"] == 1

    @pytest.mark.slow
    def test_rollback_under_live_traffic_zero_failed(
        self, rng, monkeypatch
    ):
        """An injected staging failure mid-traffic: every request keeps
        answering off the old generation while the reshard dies."""
        monkeypatch.setenv("PHOTON_RETRY_BASE_DELAY_S", "0.001")
        model, specs, reqs = _fixture(rng)
        ref = _cold_scores(model, specs, reqs)
        bundle = ServingBundle.from_model(model, specs, TASK, mesh=make_mesh())
        eng = ServingEngine(bundle, max_batch=16)
        eng.warmup()
        stop = threading.Event()
        failures: list = []
        answered = [0]

        def _traffic(b):
            j = 0
            while not stop.is_set():
                try:
                    res = b.score(reqs[j % len(reqs)])
                    if not np.isclose(
                        res.score, ref[j % len(reqs)], **_SERVE_TOL
                    ):
                        failures.append(f"drift at {j}")
                    answered[0] += 1
                except Exception as exc:  # noqa: BLE001 - recorded
                    failures.append(repr(exc))
                j += 1

        with eng, eng.batcher(max_wait_ms=0.5) as batcher:
            th = threading.Thread(
                target=_traffic, args=(batcher,), name="elastic-rb-traffic"
            )
            th.start()
            time.sleep(0.1)
            with faults.inject("reshard_stage:9999"):
                with pytest.raises(faults.InjectedFault):
                    eng.reshard_orchestrator.reshard(surviving_mesh(4))
            time.sleep(0.1)
            stop.set()
            th.join(timeout=60)
            assert not th.is_alive()
        assert not failures, failures[:3]
        assert answered[0] > 0
        assert eng.bundle_version == 0

    @pytest.mark.slow
    def test_midstage_sigkill_leaves_old_generation_intact(self, tmp_path):
        """SIGKILL in the middle of the restage: the dying process had
        answered every request correctly up to the kill (zero failed in
        its log), and a restarted engine on the SAME model serves the old
        generation bitwise — a torn reshard leaves nothing behind."""
        script = _SIGKILL_CHILD_SCRIPT
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        out = str(tmp_path)

        def _run(mode):
            return subprocess.Popen(
                [sys.executable, "-c", script, out, mode],
                env=env,
                cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )

        proc = _run("serve-and-reshard")
        marker = os.path.join(out, "staging")
        deadline = time.monotonic() + 120
        try:
            while not os.path.exists(marker):
                if proc.poll() is not None:
                    _, err = proc.communicate()
                    raise AssertionError(
                        f"child exited before staging: {err[-2000:]}"
                    )
                if time.monotonic() > deadline:
                    raise AssertionError("child never reached staging")
                time.sleep(0.05)
            time.sleep(0.1)  # inside the deliberately-slow restage
            os.kill(proc.pid, signal.SIGKILL)
        finally:
            proc.wait()
        log = json.load(open(os.path.join(out, "traffic.json")))
        assert log["failed"] == 0
        assert log["answered"] > 0
        # Restart: the old generation is fully intact — bitwise replay.
        proc2 = _run("restart-probe")
        _, err2 = proc2.communicate(timeout=300)
        assert proc2.returncode == 0, err2[-2000:]
        pre = np.load(os.path.join(out, "pre_scores.npy"))
        post = np.load(os.path.join(out, "post_scores.npy"))
        assert np.array_equal(pre, post)


_SIGKILL_CHILD_SCRIPT = r"""
import json, os, sys, threading, time
import numpy as np
import jax.numpy as jnp
from photon_ml_tpu.game.model import (
    Coefficients, FixedEffectModel, GameModel, RandomEffectModel,
)
from photon_ml_tpu.parallel.mesh import make_mesh, surviving_mesh
from photon_ml_tpu.serving import ScoreRequest, ServingBundle, ServingEngine
from photon_ml_tpu.serving.reshard import MeshReshardOrchestrator
from photon_ml_tpu.transformers.game_transformer import CoordinateScoringSpec
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils.contracts import SHARDED_VS_SINGLE_TOLERANCES

SERVE_TOL = SHARDED_VS_SINGLE_TOLERANCES["serve"]
out, mode = sys.argv[1], sys.argv[2]
TASK = TaskType.LOGISTIC_REGRESSION
D_FE, D_RE, E = 7, 5, 24
rng = np.random.default_rng(7)
w = rng.normal(size=D_FE).astype(np.float32)
M = np.zeros((E + 1, D_RE), np.float32)
M[:E] = rng.normal(size=(E, D_RE))
model = GameModel({
    "fixed": FixedEffectModel(Coefficients(jnp.asarray(w)), TASK),
    "per-e": RandomEffectModel(jnp.asarray(M), None, TASK),
})
specs = {
    "fixed": CoordinateScoringSpec(shard="g"),
    "per-e": CoordinateScoringSpec(
        shard="re", random_effect_type="eid",
        entity_index={str(i): i for i in range(E)},
    ),
}
n = 16
X = rng.normal(size=(n, D_FE)).astype(np.float32)
Xe = rng.normal(size=(n, D_RE)).astype(np.float32)
reqs = [ScoreRequest(features={"g": X[i], "re": Xe[i]},
                     entity_ids={"eid": str(i % E)}) for i in range(n)]
bundle = ServingBundle.from_model(model, specs, TASK, mesh=make_mesh())
eng = ServingEngine(bundle, max_batch=16)
eng.warmup()
probe = np.asarray([r.score for r in eng.score_batch(reqs)], np.float64)

if mode == "restart-probe":
    np.save(os.path.join(out, "post_scores.npy"), probe)
    eng.close()
    sys.exit(0)

np.save(os.path.join(out, "pre_scores.npy"), probe)
log = {"answered": 0, "failed": 0}

def flush():
    tmp = os.path.join(out, ".traffic.json.tmp")
    with open(tmp, "w") as f:
        json.dump(log, f)
    os.replace(tmp, os.path.join(out, "traffic.json"))

stop = threading.Event()

def traffic(b):
    j = 0
    while not stop.is_set():
        try:
            res = b.score(reqs[j % n])
            # The batcher's one-request bucket is another program than
            # the probe's 16-row batch: right within the serve tolerance.
            if not np.isclose(res.score, probe[j % n], **SERVE_TOL):
                log["failed"] += 1
            else:
                log["answered"] += 1
        except Exception:
            log["failed"] += 1
        if j % 8 == 0:
            flush()
        j += 1

orig = MeshReshardOrchestrator._stage_resharded_params

def slow_stage(self, coord, cplan, new_mesh):
    open(os.path.join(out, "staging"), "w").close()
    time.sleep(60)  # the parent SIGKILLs us inside this window
    return orig(self, coord, cplan, new_mesh)

MeshReshardOrchestrator._stage_resharded_params = slow_stage
with eng, eng.batcher(max_wait_ms=0.5) as batcher:
    th = threading.Thread(target=traffic, args=(batcher,), name="t")
    th.start()
    time.sleep(0.2)
    flush()
    eng.reshard_orchestrator.reshard(surviving_mesh(4))
"""


# --------------------------------------------------------------- rebalance


class TestRebalance:
    def _hot_fixture(self, rng):
        """Requests hammering the tail entities (NOT the default preload
        prefix), so every pass pays cold-tier hits until a rebalance."""
        model, specs, _ = _fixture(rng)
        n = 16
        X = rng.normal(size=(n, D_FE)).astype(np.float32)
        Xe = rng.normal(size=(n, D_RE)).astype(np.float32)
        reqs = [
            ScoreRequest(
                features={"g": X[i], "re": Xe[i]},
                entity_ids={"eid": str(18 + (i % 6))},
            )
            for i in range(n)
        ]
        return model, specs, reqs

    def test_rebalance_preloads_observed_hot_rows_bitwise(self, rng):
        model, specs, reqs = self._hot_fixture(rng)
        ref = _cold_scores(model, specs, reqs)
        bundle = ServingBundle.from_model(model, specs, TASK, hot_rows=6)
        store = bundle.coordinates["per-e"].store
        with ServingEngine(bundle, max_batch=16) as eng:
            eng.warmup()
            for _ in range(2):
                assert np.array_equal(_scores(eng.score_batch(reqs)), ref)
                store.drain()
            hot = plan_rebalance(
                eng.bundle.coordinates["per-e"], min_promotions=1
            )
            assert set(hot) == set(range(18, 24))
            info = eng.reshard_orchestrator.rebalance(
                "per-e", min_promotions=1
            )
            assert info["rebalanced_rows"] == 6
            assert sorted(info["preloaded_rows"]) == list(range(18, 24))
            new_store = eng.bundle.coordinates["per-e"].store
            before = new_store.cold_hits
            assert np.array_equal(_scores(eng.score_batch(reqs)), ref)
            # The observed-hot rows now live in the hot tier: zero cold
            # hits on the replayed stream.
            assert new_store.cold_hits == before
            assert store._closed  # the replaced store joined its worker
            m = eng.metrics()
            assert m["bundle_rebalances"] == 1
        assert faults.counters()["rebalanced_rows"] == 6

    def test_rebalance_noop_below_min_promotions(self, rng):
        model, specs, reqs = self._hot_fixture(rng)
        bundle = ServingBundle.from_model(model, specs, TASK, hot_rows=6)
        store = bundle.coordinates["per-e"].store
        with ServingEngine(bundle, max_batch=16) as eng:
            eng.score_batch(reqs)
            store.drain()
            # Each hot entity promoted once; a floor of 100 means nothing
            # has earned a move — no generation flip.
            info = eng.reshard_orchestrator.rebalance(
                "per-e", min_promotions=100
            )
            assert info == {
                "rebalanced_rows": 0,
                "version": 0,
                "committed": False,
            }
            assert eng.bundle_version == 0
        bundle.release()

    def test_rebalance_stage_failure_rolls_back(self, rng, monkeypatch):
        monkeypatch.setenv("PHOTON_RETRY_BASE_DELAY_S", "0.001")
        model, specs, reqs = self._hot_fixture(rng)
        ref = _cold_scores(model, specs, reqs)
        bundle = ServingBundle.from_model(model, specs, TASK, hot_rows=6)
        store = bundle.coordinates["per-e"].store
        with ServingEngine(bundle, max_batch=16) as eng:
            assert np.array_equal(_scores(eng.score_batch(reqs)), ref)
            store.drain()
            with faults.inject("reshard_stage:9999"):
                with pytest.raises(faults.InjectedFault):
                    eng.reshard_orchestrator.rebalance(
                        "per-e", min_promotions=1
                    )
            # Old store still live and serving bitwise.
            assert not store._closed
            assert np.array_equal(_scores(eng.score_batch(reqs)), ref)
            assert eng.bundle_version == 0
            assert faults.counters()["reshard_rollbacks"] == 1
        bundle.release()


# ------------------------------------------------------- journal coverage


class TestElasticJournal:
    def test_reshard_and_mesh_loss_events_validate(self, rng, tmp_path):
        """The new journal event types round-trip through a real run:
        reshard_start/commit on a live shrink, reshard_rollback on an
        injected failure — every line schema-valid."""
        path = str(tmp_path / "journal.jsonl")
        journal = telemetry.RunJournal(path)
        telemetry.install_journal(journal)
        try:
            model, specs, reqs = _fixture(rng)
            bundle = ServingBundle.from_model(
                model, specs, TASK, mesh=make_mesh()
            )
            with ServingEngine(bundle, max_batch=16) as eng:
                eng.reshard_orchestrator.reshard(surviving_mesh(4))
                with faults.inject("reshard_commit:1"):
                    with pytest.raises(faults.InjectedFault):
                        eng.reshard_orchestrator.reshard(make_mesh())
            telemetry.emit_event(
                "mesh_loss",
                iteration=1,
                coordinate="per-e",
                surviving_devices=4,
                source="memory",
            )
        finally:
            telemetry.uninstall_journal()
            journal.close()
        n_ok, errors = telemetry.validate_journal(path)
        assert not errors
        types = [
            json.loads(line)["type"] for line in open(path) if line.strip()
        ]
        for expected in (
            "reshard_start",
            "reshard_commit",
            "reshard_rollback",
            "mesh_loss",
        ):
            assert expected in types, (expected, types)


# --------------------------------------------------- mid-fit mesh-loss resume


@pytest.mark.chaos
@pytest.mark.elastic
class TestMeshLossResume:
    N_ENTITIES, ROWS_EACH, D = 40, 6, 5

    def _coords(self, mesh=None):
        from photon_ml_tpu.data.game_dataset import (
            GameDataset,
            RandomEffectDataConfig,
            build_random_effect_dataset,
        )
        from photon_ml_tpu.game.coordinate import RandomEffectCoordinate
        from photon_ml_tpu.optimize.config import (
            L2,
            CoordinateOptimizationConfig,
            OptimizerConfig,
        )
        from photon_ml_tpu.parallel.mesh import (
            pad_game_dataset,
            shard_game_dataset,
            shard_random_effect_dataset,
        )

        cfg = CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=8, tolerance=1e-7),
            regularization=L2,
            reg_weight=1.0,
        )
        re_cfg = RandomEffectDataConfig("entityId", "re", min_bucket=8)
        rng = np.random.default_rng(0)
        n = self.N_ENTITIES * self.ROWS_EACH
        Xe = rng.normal(size=(n, self.D)).astype(np.float32)
        ent = np.repeat(np.arange(self.N_ENTITIES), self.ROWS_EACH)
        y = (rng.uniform(size=n) > 0.5).astype(np.float32)
        ds = GameDataset.build(
            {"re": jnp.asarray(Xe)}, y, id_tags={"entityId": ent}
        )
        if mesh is not None:
            ds = shard_game_dataset(
                pad_game_dataset(ds, mesh.devices.size), mesh
            )
            red = shard_random_effect_dataset(
                build_random_effect_dataset(ds, re_cfg), mesh
            )
        else:
            red = build_random_effect_dataset(ds, re_cfg)
        return {"re": RandomEffectCoordinate(ds, red, cfg, TASK)}

    def _matrix(self, result):
        m = np.asarray(result.model.models["re"].coefficients_matrix)
        return m[: self.N_ENTITIES + 1]

    def test_injected_loss_costs_exactly_one_repeated_sweep(self):
        from photon_ml_tpu.game.coordinate_descent import (
            run_coordinate_descent,
        )

        clean = self._matrix(
            run_coordinate_descent(self._coords(make_mesh()), 2, seed=3)
        )
        with faults.inject("mesh_loss@2") as inj:
            res = run_coordinate_descent(
                self._coords(make_mesh()),
                2,
                seed=3,
                mesh_rebuilder=lambda: self._coords(surviving_mesh(4)),
            )
        assert inj.injected == {"mesh_loss": 1}
        assert res.mesh_losses == 1
        assert res.repeated_sweeps == 1
        np.testing.assert_array_equal(self._matrix(res), clean)
        assert faults.counters()["mesh_losses"] == 1

    def test_checkpoint_fallback_resumes_bitwise(self, tmp_path, monkeypatch):
        """The in-memory reassembly failing (the device blocks really are
        gone) falls back to the durable checkpoint — still bitwise."""
        import photon_ml_tpu.game.checkpoint as ckpt_mod
        from photon_ml_tpu.game.coordinate_descent import (
            run_coordinate_descent,
        )

        clean = self._matrix(
            run_coordinate_descent(self._coords(make_mesh()), 2, seed=3)
        )

        def unreachable(model):
            raise OSError("device blocks unreachable")

        monkeypatch.setattr(
            ckpt_mod, "reassemble_model_in_memory", unreachable
        )
        with faults.inject("mesh_loss@2"):
            res = run_coordinate_descent(
                self._coords(make_mesh()),
                2,
                seed=3,
                checkpoint_dir=str(tmp_path / "ck"),
                mesh_rebuilder=lambda: self._coords(surviving_mesh(4)),
            )
        assert res.mesh_losses == 1
        np.testing.assert_array_equal(self._matrix(res), clean)

    def test_no_recovery_source_reraises(self, monkeypatch):
        """In-memory reassembly broken AND no checkpoint configured: the
        MeshLoss surfaces instead of silently continuing on torn state."""
        import photon_ml_tpu.game.checkpoint as ckpt_mod
        from photon_ml_tpu.game.coordinate_descent import (
            run_coordinate_descent,
        )

        monkeypatch.setattr(
            ckpt_mod,
            "reassemble_model_in_memory",
            lambda m: (_ for _ in ()).throw(OSError("gone")),
        )
        with faults.inject("mesh_loss@2"):
            with pytest.raises(faults.MeshLoss):
                run_coordinate_descent(
                    self._coords(make_mesh()), 2, seed=3
                )

    def test_exhausted_losses_reraise(self):
        from photon_ml_tpu.game.coordinate_descent import (
            run_coordinate_descent,
        )

        with faults.inject("mesh_loss:9999"):
            with pytest.raises(faults.MeshLoss):
                run_coordinate_descent(
                    self._coords(make_mesh()),
                    2,
                    seed=3,
                    max_mesh_losses=1,
                    mesh_rebuilder=lambda: self._coords(surviving_mesh(4)),
                )
        assert faults.counters()["mesh_losses"] == 2

    def test_device_error_on_sharded_coordinate_escalates(self):
        """A device-shaped failure that escaped the coordinate's own
        failure domain (re-dispatch AND bucket-loop fallback both dead)
        on an entity-sharded coordinate becomes a MeshLoss recovery."""
        from photon_ml_tpu.game.coordinate_descent import (
            run_coordinate_descent,
        )

        clean = self._matrix(
            run_coordinate_descent(self._coords(make_mesh()), 2, seed=3)
        )
        coords = self._coords(make_mesh())
        orig = coords["re"].train
        calls = [0]

        def hang_once(*a, **k):
            calls[0] += 1
            if calls[0] == 1:
                raise faults.DeviceHang("dead shard group")
            return orig(*a, **k)

        coords["re"].train = hang_once
        res = run_coordinate_descent(
            coords,
            2,
            seed=3,
            mesh_rebuilder=lambda: self._coords(surviving_mesh(4)),
        )
        assert res.mesh_losses == 1
        np.testing.assert_array_equal(self._matrix(res), clean)

    def test_counters_roll_back_with_the_interrupted_sweep(self):
        """A divergence-guard rejection INSIDE the interrupted sweep
        replays deterministically after the rollback — it must be counted
        once, not twice (the sweep snapshot restores the counters too).

        Two coordinates so the rejection (coordinate a) can precede the
        loss (coordinate b) within one sweep. solve invocations: it0 a=1
        b=2; it1 a=3,4 (both armed -> rejected, +2) then b hits
        mesh_loss@4 (its 4th update) -> rollback; the replayed a update
        rejects again on invocations 5,6. With the counter rollback the
        run reports ONE logical rejection's worth (2 attempts)."""
        from photon_ml_tpu.data.game_dataset import (
            GameDataset,
            RandomEffectDataConfig,
            build_random_effect_dataset,
        )
        from photon_ml_tpu.game.coordinate import RandomEffectCoordinate
        from photon_ml_tpu.game.coordinate_descent import (
            run_coordinate_descent,
        )
        from photon_ml_tpu.optimize.config import (
            L2,
            CoordinateOptimizationConfig,
            OptimizerConfig,
        )
        from photon_ml_tpu.parallel.mesh import (
            pad_game_dataset,
            shard_game_dataset,
            shard_random_effect_dataset,
        )

        cfg = CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=6, tolerance=1e-7),
            regularization=L2,
            reg_weight=1.0,
        )
        rng = np.random.default_rng(0)
        n = self.N_ENTITIES * self.ROWS_EACH
        Xe = rng.normal(size=(n, self.D)).astype(np.float32)
        ent_a = np.repeat(np.arange(self.N_ENTITIES), self.ROWS_EACH)
        ent_b = np.tile(np.arange(self.ROWS_EACH), self.N_ENTITIES)
        y = (rng.uniform(size=n) > 0.5).astype(np.float32)

        def coords(mesh):
            ds = GameDataset.build(
                {"re": jnp.asarray(Xe)},
                y,
                id_tags={"a": ent_a, "b": ent_b},
            )
            if mesh is not None:
                ds = shard_game_dataset(
                    pad_game_dataset(ds, mesh.devices.size), mesh
                )
                build = lambda tag: shard_random_effect_dataset(
                    build_random_effect_dataset(
                        ds, RandomEffectDataConfig(tag, "re", min_bucket=8)
                    ),
                    mesh,
                )
            else:
                build = lambda tag: build_random_effect_dataset(
                    ds, RandomEffectDataConfig(tag, "re", min_bucket=8)
                )
            return {
                "a": RandomEffectCoordinate(ds, build("a"), cfg, TASK),
                "b": RandomEffectCoordinate(ds, build("b"), cfg, TASK),
            }

        with faults.inject("solve@3+4+5+6,mesh_loss@4"):
            res = run_coordinate_descent(
                coords(make_mesh()),
                2,
                seed=3,
                mesh_rebuilder=lambda: coords(surviving_mesh(4)),
            )
        assert res.mesh_losses == 1 and res.repeated_sweeps == 1
        assert res.diverged_steps == 2, res.diverged_steps

    def test_non_device_error_still_propagates(self):
        """A programming error must never be laundered into an elastic
        'recovery' — same discipline as the collective fallback."""
        from photon_ml_tpu.game.coordinate_descent import (
            run_coordinate_descent,
        )

        coords = self._coords(make_mesh())

        def boom(*a, **k):
            raise ValueError("a bug, not weather")

        coords["re"].train = boom
        with pytest.raises(ValueError, match="a bug"):
            run_coordinate_descent(
                coords,
                1,
                seed=3,
                mesh_rebuilder=lambda: self._coords(surviving_mesh(4)),
            )
