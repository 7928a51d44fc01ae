"""Incremental refresh (ISSUE 16): warm-start delta fits + delta-bundle
swaps close the data->served freshness gap.

The contracts:

* fingerprint diffs localize change exactly: per coordinate, per ENTITY
  for random effects; append/update only (entity removal is loud);
* an incremental fit carries unchanged coordinates BITWISE and — on the
  entity fast path — carries unchanged ENTITIES bitwise, re-solving only
  the churned/new rows (characterized `max_rel_diff` journaled);
* model growth moves carried rows by KEY through an index re-sort;
* a delta bundle is the bitwise model diff (changed rows + changed FE
  planes only), and applying it to a live engine is an in-place
  generation flip through the reshard stage -> pre-warm -> commit ->
  rollback primitive: scores land bitwise-equal to a cold engine on the
  new model, zero requests fail during the swap, and an injected
  `shard_upload` / `reshard_commit` fault mid-apply leaves the OLD
  generation serving bitwise with zero failed requests;
* per-tenant refresh touches exactly one tenant's generation.
"""

from __future__ import annotations

import json
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data.fingerprints import (
    diff_fingerprints,
    fingerprint_dataset,
)
from photon_ml_tpu.data.game_dataset import (
    FixedEffectDataConfig,
    GameDataset,
    RandomEffectDataConfig,
    concat_datasets,
    take_rows,
)
from photon_ml_tpu.game import incremental
from photon_ml_tpu.game.checkpoint import read_delta_records
from photon_ml_tpu.game.model import RandomEffectModel
from photon_ml_tpu.optimize.config import (
    L2,
    CoordinateOptimizationConfig,
    OptimizerConfig,
)
from photon_ml_tpu.serving import ScoreRequest, ServingBundle, ServingEngine
from photon_ml_tpu.serving.delta import (
    apply_delta,
    apply_delta_for_tenant,
    build_delta_bundle,
)
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils import faults, telemetry

pytestmark = pytest.mark.serving

TASK = TaskType.LOGISTIC_REGRESSION
D_FE, D_RE, E = 6, 4, 10

DATA_CONFIGS = {
    "fixed": FixedEffectDataConfig("g"),
    "per-e": RandomEffectDataConfig("eid", "re", min_bucket=4),
}
_OC = CoordinateOptimizationConfig(
    optimizer=OptimizerConfig(max_iterations=25),
    regularization=L2,
    reg_weight=1.0,
)
OPT_CONFIGS = {"fixed": _OC, "per-e": _OC}


def _dataset(rng, n, ent):
    return GameDataset.build(
        {
            "g": jnp.asarray(rng.normal(size=(n, D_FE)).astype(np.float32)),
            "re": jnp.asarray(rng.normal(size=(n, D_RE)).astype(np.float32)),
        },
        (rng.uniform(size=n) < 0.5).astype(np.float32),
        id_tags={"eid": np.asarray(ent, np.int64)},
    )


def _base(rng, n=64):
    return _dataset(rng, n, rng.integers(0, E, size=n))


def _fit(dataset, **kw):
    return incremental.full_fit(
        dataset, DATA_CONFIGS, OPT_CONFIGS, TASK, **kw
    )


def _refit(merged, prev, **kw):
    return incremental.incremental_fit(
        merged, DATA_CONFIGS, OPT_CONFIGS, TASK, prev=prev, **kw
    )


def _delta_batch(rng, n=12, ent=(2, 5, E)):
    """n delta rows over the given entity pool (E = one brand-new id)."""
    return _dataset(rng, n, np.resize(np.asarray(ent), n))


def _requests(n=14):
    return [
        ScoreRequest(
            features={
                "g": np.full(D_FE, 0.25 * (i + 1), np.float32),
                "re": np.full(D_RE, 0.1 * (i + 1), np.float32),
            },
            entity_ids={"eid": i % (E + 3)},
            uid=str(i),
        )
        for i in range(n)
    ]

# ------------------------------------------------------------ fingerprints


class TestFingerprints:
    def test_diff_localizes_churned_and_new_entities(self, rng):
        base = _base(rng)
        prev = fingerprint_dataset(base, DATA_CONFIGS)
        merged = concat_datasets(base, _delta_batch(rng, ent=(2, 5, E)))
        new = fingerprint_dataset(merged, DATA_CONFIGS)
        diffs = diff_fingerprints(prev, new)
        # FE covers every row, so appended rows change it.
        assert diffs["fixed"].changed
        d = diffs["per-e"]
        assert set(d.changed_entities) == {2, 5, E}
        assert set(d.new_entities) == {E}
        # delta_rows counts the NEW dataset's rows of changed entities.
        tags = np.asarray(merged.id_tags["eid"])
        assert d.delta_rows == int(np.isin(tags, [2, 5, E]).sum())

    def test_identical_snapshot_diffs_clean(self, rng):
        base = _base(rng)
        a = fingerprint_dataset(base, DATA_CONFIGS)
        b = fingerprint_dataset(base, DATA_CONFIGS)
        assert all(not d.changed for d in diff_fingerprints(a, b).values())

    def test_entity_removal_is_loud(self, rng):
        base = _base(rng)
        prev = fingerprint_dataset(base, DATA_CONFIGS)
        tags = np.asarray(base.id_tags["eid"])
        keep = np.nonzero(tags != int(tags[0]))[0]
        shrunk = fingerprint_dataset(take_rows(base, keep), DATA_CONFIGS)
        with pytest.raises(ValueError, match="append/update-only"):
            diff_fingerprints(prev, shrunk)

    def test_in_place_re_edit_localizes_to_one_entity(self, rng):
        base = _base(rng)
        prev = fingerprint_dataset(base, DATA_CONFIGS)
        tags = np.asarray(base.id_tags["eid"])
        target = int(tags[0])
        re_plane = np.array(np.asarray(base.peek_shard("re")))
        re_plane[tags == target] += 1.0
        edited = GameDataset.build(
            {"g": base.peek_shard("g"), "re": jnp.asarray(re_plane)},
            np.asarray(base.labels),
            id_tags={"eid": tags},
        )
        diffs = diff_fingerprints(
            prev, fingerprint_dataset(edited, DATA_CONFIGS)
        )
        # The FE shard/labels/offsets/weights are untouched bytes.
        assert not diffs["fixed"].changed
        assert diffs["per-e"].changed_entities == (target,)
        assert diffs["per-e"].new_entities == ()


class TestDeltaPlan:
    def test_modes(self, rng):
        base = _base(rng)
        prev = fingerprint_dataset(base, DATA_CONFIGS)
        same = incremental.plan_delta_fit(
            prev, fingerprint_dataset(base, DATA_CONFIGS)
        )
        assert same.mode == "none" and same.changed_coordinates == ()
        merged = concat_datasets(base, _delta_batch(rng))
        new = fingerprint_dataset(merged, DATA_CONFIGS)
        assert (
            incremental.plan_delta_fit(prev, new, max_delta_fraction=0.9).mode
            == "delta"
        )
        # The escape hatch: churn past the fraction forces a full refit.
        assert (
            incremental.plan_delta_fit(
                prev, new, max_delta_fraction=0.01
            ).mode
            == "full"
        )

    def test_fraction_knob_default_routes_through_planner(
        self, rng, monkeypatch
    ):
        monkeypatch.setenv("PHOTON_REFRESH_MAX_DELTA_FRACTION", "0.0001")
        base = _base(rng)
        prev = fingerprint_dataset(base, DATA_CONFIGS)
        merged = concat_datasets(base, _delta_batch(rng))
        plan = incremental.plan_delta_fit(
            prev, fingerprint_dataset(merged, DATA_CONFIGS)
        )
        assert plan.mode == "full"


# ------------------------------------------------------------ model growth


class TestModelGrowth:
    def test_grow_moves_rows_by_key_through_a_resort(self, rng):
        mat = rng.normal(size=(4, D_RE)).astype(np.float32)
        mat[3] = 0.0
        model = RandomEffectModel(jnp.asarray(mat), None, TASK)
        prev_idx = {2: 0, 5: 1, 9: 2}
        # Key -1 sorts FIRST: every carried row moves position.
        new_idx = {-1: 0, 2: 1, 5: 2, 7: 3, 9: 4}
        grown = incremental.grow_random_effect_model(model, prev_idx, new_idx)
        g = np.asarray(grown.coefficients_matrix)
        assert g.shape == (6, D_RE)
        for k, old_row in prev_idx.items():
            assert np.array_equal(g[new_idx[k]], mat[old_row])
        assert not g[0].any() and not g[3].any() and not g[5].any()

    def test_grow_carries_variances(self, rng):
        mat = rng.normal(size=(3, D_RE)).astype(np.float32)
        var = rng.uniform(size=(3, D_RE)).astype(np.float32)
        model = RandomEffectModel(jnp.asarray(mat), jnp.asarray(var), TASK)
        grown = incremental.grow_random_effect_model(
            model, {1: 0, 4: 1}, {1: 0, 2: 1, 4: 2}
        )
        v = np.asarray(grown.variances_matrix)
        assert np.array_equal(v[0], var[0]) and np.array_equal(v[2], var[1])
        assert not v[1].any()


# --------------------------------------------------------- incremental fit


class TestIncrementalFit:
    def test_nothing_changed_carries_the_model_object(self, rng):
        base = _base(rng)
        st = _fit(base)
        res = _refit(base, st)
        assert res.plan.mode == "none"
        assert res.state.model is st.model
        assert res.max_rel_diff == 0.0

    def test_unchanged_coordinate_carried_bitwise(self, rng):
        """An RE-only in-place edit: the fixed effect's data is untouched,
        so its model is carried BITWISE (the ISSUE 16 parity contract on
        unchanged coordinates)."""
        base = _base(rng)
        st = _fit(base)
        tags = np.asarray(base.id_tags["eid"])
        target = int(tags[0])
        re_plane = np.array(np.asarray(base.peek_shard("re")))
        re_plane[tags == target] *= 1.5
        edited = GameDataset.build(
            {"g": base.peek_shard("g"), "re": jnp.asarray(re_plane)},
            np.asarray(base.labels),
            id_tags={"eid": tags},
        )
        res = _refit(edited, st)
        assert res.plan.mode == "delta"
        assert res.plan.changed_coordinates == ("per-e",)
        assert "fixed" in res.carried_coordinates
        assert np.array_equal(
            np.asarray(res.state.model["fixed"].coefficients.means),
            np.asarray(st.model["fixed"].coefficients.means),
        )
        # And within the RE coordinate, every OTHER entity is bitwise.
        pm = np.asarray(st.model["per-e"].coefficients_matrix)
        nm = np.asarray(res.state.model["per-e"].coefficients_matrix)
        for k, row in st.entity_indices["per-e"].items():
            if k != target:
                assert np.array_equal(pm[row], nm[row]), k
        assert not np.array_equal(pm[st.entity_indices["per-e"][target]],
                                  nm[st.entity_indices["per-e"][target]])
        assert res.max_rel_diff > 0.0

    def test_unchanged_entities_bitwise_on_append(self, rng):
        """Appended rows for a few entities (+ one brand-new): unchanged
        entities' coefficient rows are bitwise-equal to the previous
        from-scratch fit, through the index re-map."""
        base = _base(rng)
        st = _fit(base)
        merged = concat_datasets(base, _delta_batch(rng, ent=(2, 5, E)))
        res = _refit(merged, st)
        assert res.plan.mode == "delta"
        changed = set(res.plan.changed_entities["per-e"])
        assert E in set(res.plan.new_entities["per-e"])
        pm = np.asarray(st.model["per-e"].coefficients_matrix)
        nm = np.asarray(res.state.model["per-e"].coefficients_matrix)
        prev_idx = st.entity_indices["per-e"]
        new_idx = res.state.entity_indices["per-e"]
        unchanged = [k for k in prev_idx if k not in changed]
        assert unchanged
        for k in unchanged:
            assert np.array_equal(pm[prev_idx[k]], nm[new_idx[k]]), k
        # The new entity actually learned something.
        assert np.asarray(nm[new_idx[E]]).any()

    def test_full_mode_grows_then_refits_everything(self, rng):
        base = _base(rng)
        st = _fit(base)
        merged = concat_datasets(base, _delta_batch(rng))
        res = _refit(merged, st, max_delta_fraction=0.01)
        assert res.plan.mode == "full"
        assert set(res.state.entity_indices["per-e"]) == set(
            np.unique(np.asarray(merged.id_tags["eid"])).tolist()
        )

    def test_full_mode_discards_stale_checkpoint_from_prior_round(
        self, rng, tmp_path
    ):
        """Two consecutive full-mode rounds sharing one checkpoint_dir
        (the refresh-loop shape): round 2's merged dataset has a new
        config fingerprint, so round 1's leftover checkpoint is stale by
        construction — the full refit must discard it and start fresh
        instead of refusing to resume."""
        ckpt_dir = str(tmp_path / "ckpt")
        base = _base(rng)
        st = _fit(base)
        merged1 = concat_datasets(base, _delta_batch(rng))
        res1 = _refit(
            merged1, st, max_delta_fraction=0.01, checkpoint_dir=ckpt_dir
        )
        assert res1.plan.mode == "full"
        merged2 = concat_datasets(merged1, _delta_batch(rng))
        res2 = _refit(
            merged2, res1.state,
            max_delta_fraction=0.01, checkpoint_dir=ckpt_dir,
        )
        assert res2.plan.mode == "full"
        # The second round refit everything over the bigger index —
        # stale state from round 1 neither resumed nor blocked it.
        assert set(res2.state.entity_indices["per-e"]) == set(
            np.unique(np.asarray(merged2.id_tags["eid"])).tolist()
        )

    def test_delta_records_and_journal(self, rng, tmp_path):
        base = _base(rng)
        st = _fit(base)
        merged = concat_datasets(base, _delta_batch(rng))
        path = str(tmp_path / "journal.jsonl")
        journal = telemetry.RunJournal(path)
        telemetry.install_journal(journal)
        try:
            res = _refit(merged, st, checkpoint_dir=str(tmp_path))
        finally:
            telemetry.uninstall_journal()
            journal.close()
        n_ok, errors = telemetry.validate_journal(path)
        assert not errors and n_ok > 0
        types = [
            json.loads(line)["type"] for line in open(path) if line.strip()
        ]
        assert "delta_fit_start" in types and "delta_fit_finish" in types
        (rec,) = read_delta_records(str(tmp_path))
        assert rec["mode"] == "delta"
        assert rec["max_rel_diff"] == res.max_rel_diff
        assert rec["total_rows"] == merged.num_samples


# ------------------------------------------------------------ delta bundle


def _serving_state(rng):
    base = _base(rng)
    st = _fit(base)
    merged = concat_datasets(base, _delta_batch(rng, ent=(2, 5, E)))
    res = _refit(merged, st)
    delta = build_delta_bundle(
        st, res.state, source="test", mode=res.plan.mode,
        delta_rows=res.plan.delta_rows, total_rows=res.plan.total_rows,
    )
    return base, st, res, delta


class TestDeltaBundle:
    def test_bundle_is_the_bitwise_model_diff(self, rng):
        _, st, res, delta = _serving_state(rng)
        d = delta.coordinates["per-e"]
        changed = set(res.plan.changed_entities["per-e"])
        new_idx = res.state.entity_indices["per-e"]
        # Exactly the churned + new entities' rows ride the wire...
        assert set(d.rows.tolist()) == {new_idx[k] for k in changed}
        nm = np.asarray(res.state.model["per-e"].coefficients_matrix)
        assert np.array_equal(d.values, nm[d.rows])
        # ...and the FE plane ships whole iff it changed.
        assert ("fixed" in delta.coordinates) == (
            "fixed" in res.plan.changed_coordinates
        )
        assert d.logical_rows == len(new_idx) + 1

    def test_manifest_matches_contract_keys(self, rng):
        from photon_ml_tpu.utils.contracts import DELTA_BUNDLE_KEYS

        _, _, _, delta = _serving_state(rng)
        assert tuple(delta.manifest()) == DELTA_BUNDLE_KEYS

    def test_identical_states_make_an_empty_bundle(self, rng):
        base = _base(rng)
        st = _fit(base)
        delta = build_delta_bundle(st, st, source="noop", mode="none")
        assert delta.is_empty and delta.nbytes == 0

    def test_resort_rides_the_carry_map_not_the_wire(self, rng):
        """A new entity that sorts FIRST (-1) moves every carried row: the
        moved-but-unchanged rows go in the carry map, not the payload."""
        base = _base(rng)
        st = _fit(base)
        merged = concat_datasets(base, _delta_batch(rng, ent=(-1,)))
        res = _refit(merged, st)
        delta = build_delta_bundle(st, res.state, source="resort")
        d = delta.coordinates["per-e"]
        assert d.carry_old is not None
        # Carried rows moved by exactly one position (the -1 prepend).
        assert np.array_equal(d.carry_new, d.carry_old + 1)
        new_idx = res.state.entity_indices["per-e"]
        assert set(d.rows.tolist()) == {new_idx[-1]}


# ------------------------------------------------------- live delta apply


def _live_engine(model, indices, **kw):
    specs = incremental.scoring_specs(DATA_CONFIGS, indices)
    return ServingEngine(
        ServingBundle.from_model(model, specs, TASK, **kw), max_batch=16
    )


def _scores(results):
    return [r.score for r in results]


class TestApplyDelta:
    def test_apply_matches_cold_engine_bitwise(self, rng):
        _, st, res, delta = _serving_state(rng)
        reqs = _requests()
        with _live_engine(res.state.model, res.state.entity_indices) as cold:
            want = _scores(cold.score_batch(reqs))
        eng = _live_engine(st.model, st.entity_indices)
        try:
            info = apply_delta(eng, delta)
            assert info["committed"] and info["version"] == 1
            assert info["delta_rows_staged"] == len(
                delta.coordinates["per-e"].rows
            )
            got = _scores(eng.score_batch(reqs))
            assert got == want
            prov = eng.bundle.provenance
            assert prov["origin"] == "incremental"
            assert prov["deltas_applied"] == 1
            assert prov["last_delta_source"] == "test"
            assert prov["generation"] == 1
            assert eng.metrics()["bundle_deltas"] == 1
            assert faults.counters()["delta_applies"] == 1
            assert faults.counters()["delta_rows_staged"] == info[
                "delta_rows_staged"
            ]
        finally:
            eng.close()
            eng.bundle.release()

    def test_empty_bundle_is_a_noop(self, rng):
        base = _base(rng)
        st = _fit(base)
        delta = build_delta_bundle(st, st, source="noop")
        with _live_engine(st.model, st.entity_indices) as eng:
            info = apply_delta(eng, delta)
            assert not info["committed"]
            assert eng.bundle_version == 0
            assert eng.bundle.provenance["deltas_applied"] == 0

    def test_two_tier_delta_rebuilds_the_cold_store(self, rng):
        _, st, res, delta = _serving_state(rng)
        reqs = _requests()
        with _live_engine(res.state.model, res.state.entity_indices) as cold:
            want = _scores(cold.score_batch(reqs))
        eng = _live_engine(st.model, st.entity_indices, hot_rows={"per-e": 4})
        try:
            info = apply_delta(eng, delta)
            assert info["committed"]
            assert _scores(eng.score_batch(reqs)) == want
        finally:
            eng.close()
            eng.bundle.release()

    def _apply_under_traffic(self, eng, reqs, delta, fault=None):
        """`apply_delta` on a live engine while a closed-loop client scores
        `reqs` through the batcher. Returns the answers as (request index,
        score) and what `apply_delta` returned (None under `fault`, which
        must make it raise)."""
        stop = threading.Event()
        failures: list = []
        answers: list = []

        def _traffic(b):
            j = 0
            while not stop.is_set():
                i = j % len(reqs)
                try:
                    answers.append((i, b.score(reqs[i]).score))
                except Exception as exc:  # noqa: BLE001 - recorded
                    failures.append(repr(exc))
                j += 1

        info = None
        with eng, eng.batcher(max_wait_ms=0.5) as batcher:
            th = threading.Thread(
                target=_traffic,
                args=(batcher,),
                name="photon-refresh-traffic",
            )
            th.start()
            time.sleep(0.05)
            if fault is None:
                info = apply_delta(eng, delta)
            else:
                with faults.inject(fault), pytest.raises(faults.InjectedFault):
                    apply_delta(eng, delta)
            time.sleep(0.05)
            stop.set()
            th.join(timeout=60)
            assert not th.is_alive()
        assert not failures, failures[:3]
        assert answers
        return answers, info

    def test_upload_fault_mid_apply_rolls_back_under_traffic(
        self, rng, monkeypatch
    ):
        """The ISSUE 16 rollback drill: an injected `shard_upload` fault
        mid-delta-apply leaves the OLD generation serving bitwise with
        zero failed requests, and journals the rollback."""
        monkeypatch.setenv("PHOTON_RETRY_BASE_DELAY_S", "0.001")
        _, st, res, delta = _serving_state(rng)
        reqs = _requests()
        eng = _live_engine(st.model, st.entity_indices)
        eng.warmup()
        ref = _scores(eng.score_batch(reqs))
        try:
            answers, _ = self._apply_under_traffic(
                eng, reqs, delta, fault="shard_upload:9999"
            )
            assert all(s == ref[i] for i, s in answers)
            assert eng.bundle_version == 0
            assert _scores(eng.score_batch(reqs)) == ref
            assert faults.counters()["delta_rollbacks"] == 1
            assert "delta_applies" not in faults.counters()
            prov = eng.bundle.provenance
            assert prov["deltas_applied"] == 0 and prov["generation"] == 0
        finally:
            eng.close()
            eng.bundle.release()

    def test_clean_apply_under_traffic_answers_every_request(self, rng):
        """The freshness flip itself: a delta applied to a LIVE engine
        while a closed-loop client scores through the batcher — zero
        failed requests, every answer bitwise the old generation's or the
        new one's, and the engine ends on generation 1."""
        _, st, res, delta = _serving_state(rng)
        reqs = _requests()
        eng = _live_engine(st.model, st.entity_indices)
        eng.warmup()
        old = _scores(eng.score_batch(reqs))
        try:
            answers, info = self._apply_under_traffic(eng, reqs, delta)
            new = _scores(eng.score_batch(reqs))
            assert all(s in (old[i], new[i]) for i, s in answers)
            assert new != old  # the delta did change answers
            assert info["version"] == 1 and eng.bundle_version == 1
            assert faults.counters().get("delta_rollbacks", 0) == 0
        finally:
            eng.close()
            eng.bundle.release()

    def test_commit_fault_rolls_back_and_journals(
        self, rng, monkeypatch, tmp_path
    ):
        monkeypatch.setenv("PHOTON_RETRY_BASE_DELAY_S", "0.001")
        _, st, _, delta = _serving_state(rng)
        reqs = _requests()
        path = str(tmp_path / "journal.jsonl")
        journal = telemetry.RunJournal(path)
        telemetry.install_journal(journal)
        eng = _live_engine(st.model, st.entity_indices)
        try:
            ref = _scores(eng.score_batch(reqs))
            with faults.inject("reshard_commit:1"):
                with pytest.raises(faults.InjectedFault):
                    apply_delta(eng, delta)
            assert eng.bundle_version == 0
            assert _scores(eng.score_batch(reqs)) == ref
            # Second attempt (fault spent) commits the SAME delta.
            info = apply_delta(eng, delta)
            assert info["committed"] and eng.bundle_version == 1
        finally:
            eng.close()
            eng.bundle.release()
            telemetry.uninstall_journal()
            journal.close()
        n_ok, errors = telemetry.validate_journal(path)
        assert not errors and n_ok > 0
        types = [
            json.loads(line)["type"] for line in open(path) if line.strip()
        ]
        assert "delta_rollback" in types and "delta_apply" in types


class TestTenantRefresh:
    def test_per_tenant_delta_touches_one_generation(self, rng):
        from photon_ml_tpu.serving.tenancy import TenantRegistry

        _, st, res, delta = _serving_state(rng)
        specs = incremental.scoring_specs(DATA_CONFIGS, st.entity_indices)
        reqs = _requests(6)
        with _live_engine(res.state.model, res.state.entity_indices) as cold:
            want = _scores(cold.score_batch(reqs))
        with TenantRegistry(max_batch=16, max_wait_ms=2.0) as reg:
            reg.admit(
                "fresh", ServingBundle.from_model(st.model, specs, TASK)
            )
            reg.admit(
                "stale", ServingBundle.from_model(st.model, specs, TASK)
            )
            before = [reg.score("stale", r).score for r in reqs]
            info = apply_delta_for_tenant(reg, "fresh", delta)
            assert info["committed"]
            got = [reg.score("fresh", r).score for r in reqs]
            assert got == want
            # The OTHER tenant's generation and lineage are untouched.
            assert [reg.score("stale", r).score for r in reqs] == before
            assert reg.tenant("stale").engine.bundle_version == 0
            assert reg.tenant("stale").bundle.provenance["deltas_applied"] == 0
            assert reg.tenant("fresh").bundle.provenance["deltas_applied"] == 1
