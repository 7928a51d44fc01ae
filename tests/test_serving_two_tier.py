"""Pod-scale serving: two-tier entity store + entity-sharded bundles.

The load-bearing contract is unchanged from PR 4: every score must be
BITWISE-identical to the single-tier replicated path, whatever storage mode
the bundle stages — hot-tier hit, cold-tier override row, entity-sharded
psum gather, or the pinned zero-row miss. On top of that the two-tier store
must promote asynchronously, evict under a tiny hot-set budget without ever
changing an answer, and the HBM budget accounting must charge the hot tier
plus warmup buffers per shard.
"""

from __future__ import annotations

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
from photon_ml_tpu.serving import (
    HbmBudgetExceeded,
    ScoreRequest,
    ServingBundle,
    ServingEngine,
    TwoTierEntityStore,
)
from photon_ml_tpu.transformers.game_transformer import (
    CoordinateScoringSpec,
    GameTransformer,
)
from photon_ml_tpu.types import TaskType

pytestmark = pytest.mark.serving

TASK = TaskType.LOGISTIC_REGRESSION
D_FE, D_RE, E = 7, 5, 24


def _fixture(rng, n=16):
    """(model, specs, requests, dataset): FE + RE coordinates; the request
    stream mixes repeated hot entities, one-shot cold entities, and
    unknowns."""
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
    # hot (preloaded prefix), cold (tail rows), unknown — all in one batch:
    # even ids 0..E-1 are trained entities (low ones preloaded hot), even
    # values >= E resolve to nothing (zero-row cold starts).
    ids = [str((2 * i) % (E + 6)) for i in range(n)]
    offsets = rng.normal(size=n).astype(np.float32)
    reqs = [
        ScoreRequest(
            features={"g": X[i], "re": Xe[i]},
            entity_ids={"eid": ids[i]},
            offset=float(offsets[i]),
            uid=str(i),
        )
        for i in range(n)
    ]
    from photon_ml_tpu.data.game_dataset import GameDataset

    ds = GameDataset.build(
        {"g": X, "re": Xe},
        np.zeros(n, np.float32),
        offsets=offsets,
        id_tags={"eid": np.asarray(ids)},
    )
    return model, specs, reqs, ds


def _scores(results):
    return np.asarray([r.score for r in results], np.float64)


def _ref_scores(model, specs, reqs):
    with ServingEngine(
        ServingBundle.from_model(model, specs, TASK), max_batch=16
    ) as eng:
        return _scores(eng.score_batch(reqs))


class TestTwoTierStore:
    def test_mixed_hot_cold_unknown_bitwise(self, rng):
        """One batch mixing hot-tier hits, cold-tier override rows and
        unknown entities scores bitwise-equal to the single-tier path AND
        to the offline transformer."""
        model, specs, reqs, ds = _fixture(rng)
        ref = _ref_scores(model, specs, reqs)
        offline = np.asarray(
            GameTransformer(model, specs, TASK).transform(ds).scores,
            np.float64,
        )
        bundle = ServingBundle.from_model(model, specs, TASK, hot_rows=6)
        with ServingEngine(bundle, max_batch=16) as eng:
            got = _scores(eng.score_batch(reqs))
            m = eng.metrics()
        assert np.array_equal(got, ref)
        assert np.array_equal(got, offline)
        assert m["cold_tier_hits"] > 0 and m["hot_tier_hits"] > 0
        # Unknown entities are COLD STARTS (zero row), not cold-tier hits.
        assert m["cold_start_lookups"] > 0

    def test_promotion_moves_cold_rows_hot(self, rng):
        model, specs, reqs, _ = _fixture(rng)
        ref = _ref_scores(model, specs, reqs)
        bundle = ServingBundle.from_model(model, specs, TASK, hot_rows=8)
        store = bundle.coordinates["per-e"].store
        with ServingEngine(bundle, max_batch=16) as eng:
            s1 = _scores(eng.score_batch(reqs))
            store.drain()
            s2 = _scores(eng.score_batch(reqs))
            store.drain()  # pass 2's own cold hits re-queue (LRU thrash)
            m = eng.metrics()
        assert np.array_equal(s1, ref) and np.array_equal(s2, ref)
        assert m["promotions"] > 0
        sm = store.metrics()
        assert sm["pending_promotions"] == 0
        # Promoted rows really moved tiers: the promoted entities resolve
        # hot on the second pass (hot hits grew across passes).
        assert sm["hot_tier_hits"] > 0

    def test_eviction_under_tiny_budget_never_changes_answers(self, rng):
        """hot_rows=2: every distinct entity beyond two forces an LRU
        eviction; answers stay bitwise-correct throughout."""
        model, specs, reqs, _ = _fixture(rng)
        ref = _ref_scores(model, specs, reqs)
        bundle = ServingBundle.from_model(model, specs, TASK, hot_rows=2)
        store = bundle.coordinates["per-e"].store
        with ServingEngine(bundle, max_batch=16) as eng:
            for _ in range(3):
                got = _scores(eng.score_batch(reqs))
                assert np.array_equal(got, ref)
                store.drain()
            m = eng.metrics()
        assert m["evictions"] > 0
        assert store.capacity == 2
        assert len(store._slot_of_row) <= 2

    def test_zero_capacity_serves_everything_from_cold_tier(self, rng):
        model, specs, reqs, _ = _fixture(rng)
        ref = _ref_scores(model, specs, reqs)
        bundle = ServingBundle.from_model(model, specs, TASK, hot_rows=0)
        with ServingEngine(bundle, max_batch=16) as eng:
            got = _scores(eng.score_batch(reqs))
            m = eng.metrics()
        assert np.array_equal(got, ref)
        assert m["hot_tier_hits"] == 0 and m["promotions"] == 0
        assert m["sharding"]["hot_set_fraction"] == 0.0

    def test_unknown_entity_is_zero_row_fallback(self, rng):
        """The final miss tier: ids in neither tier score FE-only."""
        model, specs, _, _ = _fixture(rng)
        n = 4
        X = rng.normal(size=(n, D_FE)).astype(np.float32)
        Xe = rng.normal(size=(n, D_RE)).astype(np.float32)
        reqs = [
            ScoreRequest(
                features={"g": X[i], "re": Xe[i]},
                entity_ids={"eid": f"nope-{i}"},
            )
            for i in range(n)
        ]
        ref = _ref_scores(model, specs, reqs)
        bundle = ServingBundle.from_model(model, specs, TASK, hot_rows=4)
        with ServingEngine(bundle, max_batch=8) as eng:
            res = eng.score_batch(reqs)
        assert all(r.cold_start for r in res)
        assert np.array_equal(_scores(res), ref)

    def test_store_unit_lru_and_snapshot_consistency(self):
        cold = np.arange(12, dtype=np.float32).reshape(6, 2)
        cold[5] = 0.0  # pinned zero row
        store = TwoTierEntityStore(cold, hot_rows=2)
        try:
            # rows 0,1 preloaded hot; 3 is a cold hit with override row.
            slots, ovr, flags, snap = store.lookup(
                np.asarray([0, 3, 5]), bucket=4
            )
            assert slots[0] == 0 and not flags[0]
            assert flags[1] and np.array_equal(ovr[1], cold[3])
            assert slots[2] == store.zero_slot and not flags[2]
            got = np.asarray(snap)[slots]
            got = np.where(flags[:, None], ovr, got)
            assert np.array_equal(got, cold[[0, 3, 5, 5]])
            store.drain()
            # 3 promoted, evicting the LRU slot (row 1: never touched).
            slots2, _, flags2, snap2 = store.lookup(
                np.asarray([3]), bucket=1
            )
            assert not flags2[0]
            assert np.array_equal(np.asarray(snap2)[slots2[0]], cold[3])
            assert 1 not in store._slot_of_row
        finally:
            store.close()

    def test_released_bundle_closes_store(self, rng):
        model, specs, reqs, _ = _fixture(rng)
        bundle = ServingBundle.from_model(model, specs, TASK, hot_rows=4)
        store = bundle.coordinates["per-e"].store
        with ServingEngine(bundle, max_batch=16) as eng:
            eng.score_batch(reqs)
        bundle.release()
        assert store._closed
        # conftest's leak check asserts no photon-serving-promote survivor.


class TestNormalizedParity:
    def test_norm_with_shifts_stays_bitwise_across_storage_modes(self, rng):
        """A shifted+scaled normalization must not break bitwise parity:
        every margin path reduces the shift ROW-WISE (batch-invariant), so
        the (E+1, D) matrix-folded replicated path and the (N, D)
        gathered two-tier/sharded paths agree to the last bit."""
        from photon_ml_tpu.ops.normalization import NormalizationContext
        from photon_ml_tpu.parallel.mesh import make_mesh

        model, specs, reqs, _ = _fixture(rng)
        norm = NormalizationContext(
            factors=jnp.asarray(
                rng.uniform(0.5, 2.0, size=D_RE).astype(np.float32)
            ),
            shifts=jnp.asarray(rng.normal(size=D_RE).astype(np.float32)),
        )
        specs = dict(specs)
        specs["per-e"] = CoordinateScoringSpec(
            shard="re",
            norm=norm,
            random_effect_type="eid",
            entity_index={str(i): i for i in range(E)},
        )
        ref = _ref_scores(model, specs, reqs)
        for kw in ({"hot_rows": 6}, {"mesh": make_mesh()}):
            bundle = ServingBundle.from_model(model, specs, TASK, **kw)
            with ServingEngine(bundle, max_batch=16) as eng:
                got = _scores(eng.score_batch(reqs))
            assert np.array_equal(got, ref), kw


class TestEntityShardedServing:
    def test_sharded_bundle_parity_and_sharding_metrics(
        self, rng, assert_sharded_close
    ):
        from photon_ml_tpu.parallel.mesh import make_mesh

        model, specs, reqs, _ = _fixture(rng)
        ref = _ref_scores(model, specs, reqs)
        mesh = make_mesh()
        bundle = ServingBundle.from_model(model, specs, TASK, mesh=mesh)
        c = bundle.coordinates["per-e"]
        assert c.mesh is mesh and c.logical_rows == E + 1
        assert c.unseen_row == E  # the LOGICAL pinned row, not a pad row
        shard_bytes = [s.data.nbytes for s in c.params.addressable_shards]
        assert len(shard_bytes) == mesh.devices.size
        assert max(shard_bytes) <= c.params.nbytes // mesh.devices.size
        with ServingEngine(bundle, max_batch=16) as eng:
            eng.warmup()
            got = _scores(eng.score_batch(reqs))
            m = eng.metrics()
            assert eng.recompiles_after_warmup == 0
        assert_sharded_close(got, ref, "serve")
        assert m["sharding"]["entity_sharded"] is True
        assert m["sharding"]["axis_size"] == mesh.devices.size
        assert m["sharding"]["all_to_all_bytes_per_batch"] > 0

    def test_mesh_trained_model_adopts_sharding(
        self, rng, assert_sharded_close
    ):
        """A row-sharded trained matrix stages sharded with NO mesh
        argument: training's sharding decision flows into serving."""
        from photon_ml_tpu.parallel.mesh import make_mesh, matrix_row_sharding

        model, specs, reqs, _ = _fixture(rng)
        ref = _ref_scores(model, specs, reqs)
        mesh = make_mesh()
        M = np.asarray(model["per-e"].coefficients_matrix)
        padded = np.zeros((-(-(E + 1) // 8) * 8, D_RE), np.float32)
        padded[: E + 1] = M
        sharded_m = RandomEffectModel(
            jax.device_put(jnp.asarray(padded), matrix_row_sharding(mesh)),
            None,
            TASK,
            n_entities=E,
        )
        bundle = ServingBundle.from_model(
            GameModel({"fixed": model["fixed"], "per-e": sharded_m}),
            specs,
            TASK,
        )
        assert bundle.coordinates["per-e"].mesh is not None
        with ServingEngine(bundle, max_batch=16) as eng:
            got = _scores(eng.score_batch(reqs))
        assert_sharded_close(got, ref, "serve")


class TestPromotionFaults:
    """ISSUE 10 promotion-worker fault cases: an armed `promote` fault
    never loses a request, never leaks the `photon-serving-promote` thread
    (conftest guard), and the cold row still scores bitwise through the
    override-buffer path."""

    pytestmark = [pytest.mark.serving, pytest.mark.chaos]

    def test_failed_promotion_leaves_rows_cold_and_bitwise(
        self, rng, monkeypatch
    ):
        from photon_ml_tpu.utils import faults

        monkeypatch.setenv("PHOTON_RETRY_BASE_DELAY_S", "0.001")
        model, specs, reqs, _ = _fixture(rng)
        ref = _ref_scores(model, specs, reqs)
        bundle = ServingBundle.from_model(model, specs, TASK, hot_rows=6)
        store = bundle.coordinates["per-e"].store
        with faults.inject("promote:1"):
            with ServingEngine(bundle, max_batch=16) as eng:
                s1 = _scores(eng.score_batch(reqs))
                store.drain()
                s2 = _scores(eng.score_batch(reqs))
                store.drain()
                m = eng.metrics()
        # Never a lost request, never a changed answer.
        assert np.array_equal(s1, ref) and np.array_equal(s2, ref)
        # The first promotion batch failed (counted), the worker LIVED ON
        # (not fatal): later touches re-queued and promoted successfully.
        assert m["promote_failures"] > 0
        assert m["promotions"] > 0
        assert not store._closed
        assert faults.counters()["promote_failures"] == m["promote_failures"]
        bundle.release()

    def test_persistent_promotion_failure_serves_from_cold_tier(
        self, rng, monkeypatch
    ):
        from photon_ml_tpu.utils import faults

        monkeypatch.setenv("PHOTON_RETRY_BASE_DELAY_S", "0.001")
        model, specs, reqs, _ = _fixture(rng)
        ref = _ref_scores(model, specs, reqs)
        bundle = ServingBundle.from_model(model, specs, TASK, hot_rows=6)
        store = bundle.coordinates["per-e"].store
        with faults.inject("promote:9999"):
            with ServingEngine(bundle, max_batch=16) as eng:
                for _ in range(3):
                    got = _scores(eng.score_batch(reqs))
                    assert np.array_equal(got, ref)
                    store.drain()
                m = eng.metrics()
        # Rows stayed cold forever — counted, never fatal, never wrong.
        assert m["promote_failures"] > 0
        assert m["cold_tier_hits"] > 0
        assert not store._closed
        bundle.release()
        # conftest's leak guard asserts no photon-serving-promote survivor.


class TestShardLossDegradation:
    """ISSUE 10 serving shard loss: the engine keeps serving — requests
    resolving to a LOST shard get the pinned zero row (FE-only for exactly
    those entities), per-shard health reports in metrics()["sharding"],
    and recovery re-stages ONLY the lost shard. Against the single-device
    references the sharded engine holds the `serve` tolerance; against its
    own answers before the loss (the same program) it is bitwise."""

    pytestmark = [pytest.mark.serving, pytest.mark.chaos]

    def _fe_only_ref(self, model, specs, reqs):
        with ServingEngine(
            ServingBundle.from_model(model, specs, TASK), max_batch=16
        ) as eng:
            return _scores(eng.score_batch_fe_only(reqs))

    def test_lost_shard_serves_fe_only_exactly_its_entities(
        self, rng, assert_sharded_close
    ):
        from photon_ml_tpu.parallel.mesh import make_mesh

        model, specs, reqs, _ = _fixture(rng)
        ref = _ref_scores(model, specs, reqs)
        ref_fe = self._fe_only_ref(model, specs, reqs)
        mesh = make_mesh()
        bundle = ServingBundle.from_model(model, specs, TASK, mesh=mesh)
        c = bundle.coordinates["per-e"]
        assert c.shard_health.n_shards == mesh.devices.size
        with ServingEngine(bundle, max_batch=16) as eng:
            full = _scores(eng.score_batch(reqs))
            assert_sharded_close(full, ref, "serve")
            lo, hi = eng.mark_shard_lost("per-e", 1)
            degraded = _scores(eng.score_batch(reqs))
            m = eng.metrics()
            # Exactly the lost shard's entities are FE-only; all others
            # keep their full-fidelity bitwise answers.
            rows, _ = c.lookup_rows(
                [r.entity_ids.get("eid") for r in reqs]
            )
            lost_mask = (rows >= lo) & (rows < hi)
            assert lost_mask.any() and not lost_mask.all()
            assert_sharded_close(
                degraded, np.where(lost_mask, ref_fe, ref), "serve"
            )
            assert np.array_equal(degraded[~lost_mask], full[~lost_mask])
            assert m["state"] == "DEGRADED"
            assert "shard_loss:per-e/1" in m["degraded_reasons"]
            assert m["sharding"]["shards_lost"] == 1
            assert m["sharding"]["shard_loss_fallbacks"] == int(
                lost_mask.sum()
            )
            # Recovery: restage ONLY the lost shard, back to bitwise-full.
            nbytes = eng.restage_shard("per-e", 1)
            assert nbytes == (hi - lo) * c.dim * 4
            assert np.array_equal(_scores(eng.score_batch(reqs)), full)
            m2 = eng.metrics()
            assert m2["state"] == "READY"
            assert m2["sharding"]["shards_lost"] == 0

    def test_failed_restage_keeps_serving_degraded(
        self, rng, monkeypatch, assert_sharded_close
    ):
        from photon_ml_tpu.parallel.mesh import make_mesh
        from photon_ml_tpu.utils import faults

        monkeypatch.setenv("PHOTON_RETRY_BASE_DELAY_S", "0.001")
        model, specs, reqs, _ = _fixture(rng)
        ref = _ref_scores(model, specs, reqs)
        ref_fe = self._fe_only_ref(model, specs, reqs)
        mesh = make_mesh()
        bundle = ServingBundle.from_model(model, specs, TASK, mesh=mesh)
        c = bundle.coordinates["per-e"]
        with ServingEngine(bundle, max_batch=16) as eng:
            full = _scores(eng.score_batch(reqs))
            lo, hi = eng.mark_shard_lost("per-e", 0)
            with faults.inject("shard_upload:9999"):
                with pytest.raises(faults.InjectedFault):
                    eng.restage_shard("per-e", 0)
                # Still serving, still degraded, still FE-only for the
                # lost shard's entities.
                degraded = _scores(eng.score_batch(reqs))
            assert faults.counters()["shard_upload_retries"] > 0
            rows, _ = c.lookup_rows([r.entity_ids.get("eid") for r in reqs])
            lost_mask = (rows >= lo) & (rows < hi)
            assert_sharded_close(
                degraded, np.where(lost_mask, ref_fe, ref), "serve"
            )
            assert np.array_equal(degraded[~lost_mask], full[~lost_mask])
            assert eng.metrics()["state"] == "DEGRADED"
            # A later (un-faulted) restage recovers fully.
            eng.restage_shard("per-e", 0)
            assert np.array_equal(_scores(eng.score_batch(reqs)), full)

    def test_two_coordinate_shard_loss_is_isolated(
        self, rng, assert_sharded_close
    ):
        """ISSUE 13 satellite: per-coordinate ShardHealth isolation with
        TWO random-effect coordinates — losing cid_a's shard 0 degrades
        ONLY cid_a's rows in that range (cid_b keeps every full-fidelity
        answer), and each coordinate's shards recover
        independently. PR 10's drill only exercised a single-RE bundle,
        which could not catch a health/loss state accidentally shared
        across coordinates."""
        from photon_ml_tpu.parallel.mesh import make_mesh

        n = 16
        E2 = 16
        w = rng.normal(size=D_FE).astype(np.float32)
        Ma = np.zeros((E + 1, D_RE), np.float32)
        Ma[:E] = rng.normal(size=(E, D_RE))
        Mb = np.zeros((E2 + 1, D_RE), np.float32)
        Mb[:E2] = rng.normal(size=(E2, D_RE))
        task = TASK

        def _model(a, b):
            return GameModel(
                {
                    "fixed": FixedEffectModel(Coefficients(jnp.asarray(w)), task),
                    "cid_a": RandomEffectModel(jnp.asarray(a), None, task),
                    "cid_b": RandomEffectModel(jnp.asarray(b), None, task),
                }
            )

        specs = {
            "fixed": CoordinateScoringSpec(shard="g"),
            "cid_a": CoordinateScoringSpec(
                shard="ra",
                random_effect_type="aid",
                entity_index={str(i): i for i in range(E)},
            ),
            "cid_b": CoordinateScoringSpec(
                shard="rb",
                random_effect_type="bid",
                entity_index={str(i): i for i in range(E2)},
            ),
        }
        X = rng.normal(size=(n, D_FE)).astype(np.float32)
        Xa = rng.normal(size=(n, D_RE)).astype(np.float32)
        Xb = rng.normal(size=(n, D_RE)).astype(np.float32)
        reqs = [
            ScoreRequest(
                features={"g": X[i], "ra": Xa[i], "rb": Xb[i]},
                entity_ids={"aid": str(i % E), "bid": str(i % E2)},
            )
            for i in range(n)
        ]

        def _ref(a, b):
            with ServingEngine(
                ServingBundle.from_model(_model(a, b), specs, task),
                max_batch=16,
            ) as eng:
                return _scores(eng.score_batch(reqs))

        ref = _ref(Ma, Mb)
        mesh = make_mesh()
        bundle = ServingBundle.from_model(
            _model(Ma, Mb), specs, task, mesh=mesh
        )
        ca, cb = bundle.coordinates["cid_a"], bundle.coordinates["cid_b"]
        assert ca.shard_health is not cb.shard_health
        with ServingEngine(bundle, max_batch=16) as eng:
            full = _scores(eng.score_batch(reqs))
            assert_sharded_close(full, ref, "serve")
            # Lose cid_a shard 0: expected = the reference with cid_a's
            # lost LOGICAL rows zeroed (lost entities score the pinned
            # zero row for cid_a ONLY); cid_b untouched.
            lo_a, hi_a = eng.mark_shard_lost("cid_a", 0)
            Ma_deg = Ma.copy()
            Ma_deg[lo_a : min(hi_a, E)] = 0.0
            expected_a = _ref(Ma_deg, Mb)
            # The drill bites, by far more than the tolerance forgives.
            assert np.abs(expected_a - ref).max() > 1e-2
            assert_sharded_close(
                _scores(eng.score_batch(reqs)), expected_a, "serve"
            )
            m = eng.metrics()
            assert m["sharding"]["shards_lost"] == 1
            assert "shard_loss:cid_a/0" in m["degraded_reasons"]
            assert cb.shard_health.lost == ()
            # Lose cid_b shard 1 ON TOP: both degradations compose, each
            # scoped to its own coordinate's rows.
            lo_b, hi_b = eng.mark_shard_lost("cid_b", 1)
            Mb_deg = Mb.copy()
            Mb_deg[lo_b : min(hi_b, E2)] = 0.0
            expected_ab = _ref(Ma_deg, Mb_deg)
            assert_sharded_close(
                _scores(eng.score_batch(reqs)), expected_ab, "serve"
            )
            assert eng.metrics()["sharding"]["shards_lost"] == 2
            # Independent recovery: restaging cid_a/0 restores cid_a's
            # rows while cid_b/1 stays degraded...
            eng.restage_shard("cid_a", 0)
            assert_sharded_close(
                _scores(eng.score_batch(reqs)), _ref(Ma, Mb_deg), "serve"
            )
            m2 = eng.metrics()
            assert "shard_loss:cid_a/0" not in m2["degraded_reasons"]
            assert "shard_loss:cid_b/1" in m2["degraded_reasons"]
            # ...and recovering cid_b/1 returns the full bitwise answers.
            eng.restage_shard("cid_b", 1)
            assert np.array_equal(_scores(eng.score_batch(reqs)), full)
            assert eng.metrics()["state"] == "READY"

    def test_staging_fault_retried_bitwise(self, rng, monkeypatch):
        from photon_ml_tpu.utils import faults

        monkeypatch.setenv("PHOTON_RETRY_BASE_DELAY_S", "0.001")
        model, specs, reqs, _ = _fixture(rng)
        ref = _ref_scores(model, specs, reqs)
        with faults.inject("shard_upload:1") as inj:
            bundle = ServingBundle.from_model(model, specs, TASK)
        assert inj.injected == {"shard_upload": 1}
        assert faults.counters()["shard_upload_retries"] == 1
        with ServingEngine(bundle, max_batch=16) as eng:
            assert np.array_equal(_scores(eng.score_batch(reqs)), ref)


class TestServingWatchdog:
    """ISSUE 10 hang watchdog in the serving score path: an over-deadline
    dispatch becomes a typed DeviceHang, the health machine goes DEGRADED,
    and every request still gets an answer (FE-only once the circuit
    opens) — never a hang, never a lost future."""

    pytestmark = [pytest.mark.serving, pytest.mark.chaos]

    def test_wedged_dispatch_degrades_to_fe_only_answers(
        self, rng, monkeypatch
    ):
        import time as _time

        from photon_ml_tpu.utils import faults

        monkeypatch.setenv("PHOTON_RETRY_BASE_DELAY_S", "0.001")
        model, specs, reqs, _ = _fixture(rng)
        ref_fe = None
        with ServingEngine(
            ServingBundle.from_model(model, specs, TASK), max_batch=16
        ) as ref_eng:
            ref = _scores(ref_eng.score_batch(reqs))
            ref_fe = _scores(ref_eng.score_batch_fe_only(reqs))
        eng = ServingEngine(
            ServingBundle.from_model(model, specs, TASK),
            max_batch=16,
            circuit_threshold=1,
            circuit_probe_interval_s=60.0,
            watchdog_ms_override=10.0,
        )
        eng.warmup()  # warmup is watchdog-exempt (compiles are slow)
        real = eng._dispatch_device

        def wedged(packed, state):
            out = real(packed, state)
            _time.sleep(0.08)  # every full-path dispatch blows the 10ms
            return out

        eng._dispatch_device = wedged
        with eng, eng.batcher(max_wait_ms=0.5) as batcher:
            futs = [batcher.submit(r, block=True) for r in reqs]
            results = [f.result(timeout=120) for f in futs]
            m = eng.metrics()
        # Every request answered — the hang hole is closed with ANSWERS.
        assert len(results) == len(reqs)
        assert faults.counters()["watchdog_trips"] >= 1
        assert m["circuit_state"] == "OPEN"
        assert m["state"] == "DEGRADED"
        # FE-only answers are bitwise the FE-only reference; any requests
        # answered before the circuit opened are bitwise the full path.
        got = _scores(results)
        fe_mask = np.asarray([r.fe_only for r in results])
        assert fe_mask.any()
        assert np.array_equal(got[fe_mask], ref_fe[fe_mask])
        assert np.array_equal(got[~fe_mask], ref[~fe_mask])

    def test_recovered_dispatch_clears_degradation(self, rng):
        """A guarded dispatch finishing inside its deadline clears the
        device_hang reason (self-healing)."""
        model, specs, reqs, _ = _fixture(rng)
        with ServingEngine(
            ServingBundle.from_model(model, specs, TASK),
            max_batch=16,
            watchdog_ms_override=60_000.0,
        ) as eng:
            eng.warmup()
            eng._hang_seen = True
            eng.health.add_degraded("device_hang")
            eng.score_batch(reqs)
            m = eng.metrics()
        assert "device_hang" not in m["degraded_reasons"]
        assert m["state"] in ("READY", "DRAINING", "CLOSED")


class TestBudgetAccounting:
    def test_device_bytes_per_shard_divides_sharded_state(self, rng):
        from photon_ml_tpu.parallel.mesh import make_mesh

        model, specs, _, _ = _fixture(rng)
        mesh = make_mesh()
        repl = ServingBundle.from_model(model, specs, TASK)
        sh = ServingBundle.from_model(model, specs, TASK, mesh=mesh)
        tt = ServingBundle.from_model(model, specs, TASK, hot_rows=4)
        # Sharded: the RE matrix divides by the mesh; FE vector replicated.
        fe_bytes = D_FE * 4
        assert sh.device_bytes_per_shard() < repl.device_bytes_per_shard()
        assert sh.device_bytes_per_shard() >= fe_bytes
        # Two-tier: only the hot set counts against device budgets.
        assert tt.device_bytes() == fe_bytes + (4 + 1) * D_RE * 4

    def test_swap_budget_counts_hot_tier_and_warmup_buffers(self, rng):
        """The swap's HBM check must include the staged bundle's hot tier
        AND the per-bucket warmup request buffers — a budget that fits the
        matrices alone but not the buffers must refuse before staging."""
        model, specs, reqs, _ = _fixture(rng)
        bundle = ServingBundle.from_model(model, specs, TASK, hot_rows=4)
        with ServingEngine(bundle, max_batch=16) as eng:
            eng.score_batch(reqs)
            warm = eng.warmup_buffer_bytes()
            assert warm > 0
            have = bundle.device_bytes_per_shard()
            next_builder_calls = [0]

            def builder():
                next_builder_calls[0] += 1
                return ServingBundle.from_model(
                    model, specs, TASK, hot_rows=4
                )

            # Budget covers both generations but NOT the warmup buffers.
            budget = 2 * have + warm // 2
            with pytest.raises(HbmBudgetExceeded, match="warmup"):
                eng.bundle_manager.swap(
                    builder, expected_bytes=have, hbm_budget_bytes=budget
                )
            assert next_builder_calls[0] == 0  # refused BEFORE staging
            # With the buffers accounted, the same swap fits and commits.
            info = eng.bundle_manager.swap(
                builder,
                expected_bytes=have,
                hbm_budget_bytes=2 * have + warm + 1024,
            )
            assert info["version"] == 1
            assert next_builder_calls[0] == 1
