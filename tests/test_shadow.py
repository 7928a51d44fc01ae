"""Shadow deployment & online evaluation suite (ISSUE 18).

The load-bearing contracts:

  * the online windowed evaluator runs the EXACT metric programs offline
    evaluation runs — `StreamingWindowEvaluator.evaluate_window` is
    bitwise-equal to `EvaluationSuite.evaluate` on identical arrays, so
    an online regression tolerance means the same thing in both worlds;
  * mirrored traffic NEVER touches the champion: a mirror or label-join
    fault degrades to champion-only serving (counted), the champion's
    answers stay bitwise vs. serving solo, and zero client requests
    fail;
  * verdicts actuate the existing machinery: reject tears the shadow
    tenant down (champion untouched), promote flips the challenger in
    through the BundleManager's atomic generation flip, and a promotion
    failure leaves the champion serving its OLD generation bitwise.
"""

from __future__ import annotations

import json
import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

from photon_ml_tpu.evaluation.suite import (
    EvaluationSuite,
    EvaluatorType,
    StreamingWindowEvaluator,
    regression,
)
from photon_ml_tpu.game.model import (
    Coefficients,
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_ml_tpu.serving import (
    ScoreRequest,
    ServingBundle,
    ServingEngine,
    TenantRegistry,
)
from photon_ml_tpu.serving.shadow import ShadowController
from photon_ml_tpu.transformers.game_transformer import CoordinateScoringSpec
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils import faults, telemetry

pytestmark = pytest.mark.serving

TASK = TaskType.LOGISTIC_REGRESSION
D_FE, D_RE, E = 7, 5, 24


def _make_model(seed: int, n_entities: int = E, scale: float = 1.0):
    rng = np.random.default_rng(seed)
    w = (scale * rng.normal(size=D_FE)).astype(np.float32)
    M = np.zeros((n_entities + 1, D_RE), np.float32)
    M[:n_entities] = scale * rng.normal(size=(n_entities, D_RE))
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
            entity_index={str(i): i for i in range(n_entities)},
        ),
    }
    return model, specs


def _bundle(seed: int, scale: float = 1.0) -> ServingBundle:
    model, specs = _make_model(seed, scale=scale)
    return ServingBundle.from_model(model, specs, TASK)


def _requests(seed: int, n: int):
    """Offset-free traffic: the negated-weights challenger in the reject
    drill must score the EXACT inverse of the champion."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, D_FE)).astype(np.float32)
    Xe = rng.normal(size=(n, D_RE)).astype(np.float32)
    ids = rng.integers(0, E + 6, size=n)  # trained + cold starts
    return [
        ScoreRequest(
            features={"g": X[i], "re": Xe[i]},
            entity_ids={"eid": str(int(ids[i]))},
            uid=str(i),
        )
        for i in range(n)
    ]


def _solo_scores(seed: int, reqs, scale: float = 1.0) -> np.ndarray:
    """The parity anchor: that bundle alone on a plain engine."""
    with ServingEngine(_bundle(seed, scale=scale), max_batch=32) as eng:
        return np.asarray(
            [r.score for r in eng.score_batch(reqs)], np.float64
        )


def _champ_scores(reg, reqs) -> np.ndarray:
    """The champion's answers through the registry's own submit path."""
    return np.asarray(
        [
            reg.submit("champ", r, block=True).result(timeout=30).score
            for r in reqs
        ],
        np.float64,
    )


def _labels_from(scores: np.ndarray) -> np.ndarray:
    """Champion-separable labels: the champion ranks them perfectly
    (AUC exactly 1.0), so verdicts are deterministic functions of the
    challenger's ordering."""
    return (scores > 0.0).astype(np.float64)


def _drive(reg, controller, reqs, labels):
    """The serving loop's shadow hookup: submit to the champion, mirror,
    join the label. Returns the champion's scores (every future MUST
    resolve — a failed client request fails the test)."""
    futs = []
    for req, lab in zip(reqs, labels):
        fut = reg.submit("champ", req, block=True)
        futs.append(fut)
        if controller.mirror(req, fut):
            controller.record_label(req.uid, float(lab))
    return np.asarray([f.result(timeout=30).score for f in futs], np.float64)


class TestStreamingEvaluator:
    def test_windowed_matches_offline_bitwise(self):
        """One metric program, two worlds: the streaming window evaluator
        and the offline suite produce bitwise-identical values on
        identical (scores, labels, weights) arrays."""
        rng = np.random.default_rng(5)
        n = 96
        scores = jnp.asarray(rng.normal(size=n).astype(np.float32))
        labels = jnp.asarray(
            (rng.uniform(size=n) < 0.5).astype(np.float32)
        )
        weights = jnp.asarray(
            rng.uniform(0.5, 2.0, size=n).astype(np.float32)
        )
        ets = [EvaluatorType("AUC"), EvaluatorType("RMSE")]
        offline = EvaluationSuite(ets, labels, weights).evaluate(scores)
        online = StreamingWindowEvaluator(ets).evaluate_window(
            scores, labels, weights
        )
        assert online.results == offline.results  # exact, not approx
        assert online.primary_value == offline.primary_value

    def test_single_row_window(self):
        res = StreamingWindowEvaluator(
            [EvaluatorType("RMSE")]
        ).evaluate_window(jnp.asarray([0.25]), jnp.asarray([1.0]))
        assert np.isfinite(res.primary_value)

    def test_empty_window_refused(self):
        ev = StreamingWindowEvaluator([EvaluatorType("AUC")])
        with pytest.raises(ValueError, match="empty evaluation window"):
            ev.evaluate_window(jnp.zeros((0,)), jnp.zeros((0,)))

    def test_grouped_evaluators_refused(self):
        with pytest.raises(ValueError, match="grouped"):
            StreamingWindowEvaluator([EvaluatorType.parse("AUC:eid")])

    def test_regression_direction_aware(self):
        # AUC down and RMSE up must BOTH read as positive regressions.
        assert regression(EvaluatorType("AUC"), 0.7, 0.9) > 0
        assert regression(EvaluatorType("RMSE"), 0.9, 0.7) > 0
        assert regression(EvaluatorType("AUC"), 0.9, 0.7) < 0


class TestHistogramMerge:
    def test_merge_order_independent(self):
        """Per-window drift/calibration snapshots merge to the same
        histogram regardless of window arrival order."""
        h = telemetry.METRICS
        for v in (0.1, 0.2, 0.3):
            h.observe("shadow_score_drift", v)
        snap_a = h.histogram("shadow_score_drift").snapshot()
        h.reset()
        for v in (0.6, 0.7):
            h.observe("shadow_score_drift", v)
        snap_b = h.histogram("shadow_score_drift").snapshot()
        ab = telemetry.merge_histogram_snapshots(snap_a, snap_b)
        ba = telemetry.merge_histogram_snapshots(snap_b, snap_a)
        assert ab == ba
        assert telemetry.snapshot_quantile(
            ab, 0.5
        ) == telemetry.snapshot_quantile(ba, 0.5)


@pytest.mark.chaos
class TestMirrorIsolation:
    def test_mirror_fault_degrades_to_champion_only(self):
        """An armed `shadow_mirror` fault drops the MIRROR, never the
        client request: every champion future resolves bitwise vs. solo
        and the failure is counted."""
        reqs = _requests(31, 12)
        ref = _solo_scores(1, reqs)
        with TenantRegistry(max_batch=32, max_wait_ms=2.0) as reg:
            reg.admit("champ", _bundle(1))
            controller = ShadowController(
                reg, "champ", "cand", _bundle(2),
                window_size=64, min_windows=1, cooldown_s=0.0,
            )
            try:
                with faults.inject("shadow_mirror:2"):
                    got = _drive(
                        reg, controller, reqs, _labels_from(ref)
                    )
                summary = controller.summary()
            finally:
                controller.close()
            m = reg.metrics()
            reg.close(release_bundles=True)
        assert np.array_equal(got, ref)
        assert m["tenants"]["champ"]["failed"] == 0
        assert summary["mirror_failures"] == 2
        assert summary["mirrored_requests"] == len(reqs) - 2
        assert faults.COUNTERS.get("shadow_mirror_failures") == 2

    def test_label_join_fault_drops_label_only(self):
        reqs = _requests(32, 6)
        ref = _solo_scores(1, reqs)
        with TenantRegistry(max_batch=32, max_wait_ms=2.0) as reg:
            reg.admit("champ", _bundle(1))
            controller = ShadowController(
                reg, "champ", "cand", _bundle(2),
                window_size=64, min_windows=1, cooldown_s=0.0,
            )
            try:
                futs = [reg.submit("champ", r, block=True) for r in reqs]
                for r, f in zip(reqs, futs):
                    assert controller.mirror(r, f)
                with faults.inject("label_join:1"):
                    assert not controller.record_label(reqs[0].uid, 1.0)
                assert controller.record_label(reqs[1].uid, 1.0)
                got = np.asarray(
                    [f.result(timeout=30).score for f in futs], np.float64
                )
                assert controller.summary()["label_join_failures"] == 1
            finally:
                controller.close()
            reg.close(release_bundles=True)
        assert np.array_equal(got, ref)
        assert faults.COUNTERS.get("label_join_failures") == 1

    def test_mirror_fraction_deterministic(self):
        """fraction=0.5 mirrors exactly every 2nd eligible request — a
        credit accumulator, not an RNG."""
        reqs = _requests(33, 8)
        with TenantRegistry(max_batch=32, max_wait_ms=2.0) as reg:
            reg.admit("champ", _bundle(1))
            controller = ShadowController(
                reg, "champ", "cand", _bundle(2),
                window_size=64, min_windows=1, mirror_fraction=0.5,
            )
            try:
                picks = []
                for r in reqs:
                    fut = reg.submit("champ", r, block=True)
                    picks.append(controller.mirror(r, fut))
                    fut.result(timeout=30)
                # No uid -> no join key -> never mirrored.
                anon = ScoreRequest(
                    features=dict(reqs[0].features),
                    entity_ids=dict(reqs[0].entity_ids),
                )
                fut = reg.submit("champ", anon, block=True)
                assert not controller.mirror(anon, fut)
                fut.result(timeout=30)
            finally:
                controller.close()
            reg.close(release_bundles=True)
        assert picks == [False, True] * 4


@pytest.mark.chaos
class TestVerdicts:
    def test_reject_tears_down_shadow_champion_untouched(self, tmp_path):
        """A regressed challenger (negated weights: the exact inverse
        ranking, AUC 0 vs. the champion's 1) is rejected from shadow
        metrics ALONE and torn down; the champion serves bitwise
        throughout and after."""
        reqs = _requests(41, 16)
        ref = _solo_scores(1, reqs)
        labels = _labels_from(ref)
        journal_path = str(tmp_path / "journal.jsonl")
        journal = telemetry.install_journal(
            telemetry.RunJournal(journal_path)
        )
        try:
            with TenantRegistry(max_batch=32, max_wait_ms=2.0) as reg:
                reg.admit("champ", _bundle(1))
                v0 = int(reg.tenant("champ").engine._state.version)
                controller = ShadowController(
                    reg, "champ", "cand", _bundle(1, scale=-1.0),
                    window_size=len(reqs), min_windows=1, cooldown_s=0.0,
                )
                try:
                    got = _drive(reg, controller, reqs, labels)
                    assert (
                        controller.wait_for_verdict(timeout_s=60.0)
                        == "reject"
                    )
                    assert controller.status == "rejected"
                    # The shadow tenant is GONE from the fleet.
                    with pytest.raises(KeyError):
                        reg.tenant("cand")
                finally:
                    controller.close()
                # Champion: same generation, bitwise on fresh traffic.
                assert int(reg.tenant("champ").engine._state.version) == v0
                reqs2 = _requests(42, 8)
                ref2 = _solo_scores(1, reqs2)
                got2 = np.asarray(
                    [
                        reg.submit("champ", r, block=True)
                        .result(timeout=30)
                        .score
                        for r in reqs2
                    ],
                    np.float64,
                )
                m = reg.metrics()
                reg.close(release_bundles=True)
        finally:
            telemetry.uninstall_journal()
            journal.close()
        assert np.array_equal(got, ref)
        assert np.array_equal(got2, ref2)
        assert m["tenants"]["champ"]["failed"] == 0
        assert faults.COUNTERS.get("shadow_rollbacks") == 1
        n_ok, errors = telemetry.validate_journal(journal_path)
        assert errors == []
        events = [json.loads(l) for l in open(journal_path)]
        by_type = {}
        for e in events:
            by_type.setdefault(e["type"], []).append(e)
        assert len(by_type["shadow_start"]) == 1
        assert by_type["shadow_window"][0]["healthy"] is False
        (verdict,) = by_type["shadow_verdict"]
        assert verdict["decision"] == "reject"
        assert verdict["champion_metric"] == 1.0  # separable by design
        (rollback,) = by_type["shadow_rollback"]
        assert rollback["challenger"] == "cand"
        assert "shadow_promote" not in by_type

    def test_promote_flips_generation_atomically(self):
        """A healthy challenger (identical ranking) promotes through the
        BundleManager generation flip: the champion tenant now serves
        the challenger's bundle at version+1, and the shadow tenant is
        retired."""
        reqs = _requests(43, 16)
        ref = _solo_scores(1, reqs)
        labels = _labels_from(ref)
        chall_bundle = _bundle(1)  # same weights: equal metric, new bundle
        with TenantRegistry(max_batch=32, max_wait_ms=2.0) as reg:
            reg.admit("champ", _bundle(1))
            v0 = int(reg.tenant("champ").engine._state.version)
            controller = ShadowController(
                reg, "champ", "cand", chall_bundle,
                window_size=len(reqs), min_windows=1, cooldown_s=0.0,
            )
            try:
                got = _drive(reg, controller, reqs, labels)
                assert (
                    controller.wait_for_verdict(timeout_s=60.0) == "promote"
                )
                assert controller.status == "promoted"
            finally:
                controller.close()
            engine = reg.tenant("champ").engine
            assert int(engine._state.version) == v0 + 1
            assert engine._state.bundle is chall_bundle
            with pytest.raises(KeyError):
                reg.tenant("cand")
            # Post-promotion serving: bitwise vs. the challenger solo
            # (same weights as the old champion here, so the same ref).
            got2 = _champ_scores(reg, reqs)
            m = reg.metrics()
            reg.close(release_bundles=True)
        assert np.array_equal(got, ref)
        assert np.array_equal(got2, ref)
        assert m["tenants"]["champ"]["failed"] == 0
        assert faults.COUNTERS.get("shadow_rollbacks") == 0

    def test_promotion_failure_keeps_old_generation_bitwise(self):
        """`shadow_promote` faults past the retry budget abort the
        promotion BEFORE the swap stages: the champion keeps serving its
        old generation bitwise and the failed promotion is a rollback."""
        reqs = _requests(44, 16)
        ref = _solo_scores(1, reqs)
        labels = _labels_from(ref)
        chall_bundle = _bundle(1)
        with TenantRegistry(max_batch=32, max_wait_ms=2.0) as reg:
            reg.admit("champ", _bundle(1))
            v0 = int(reg.tenant("champ").engine._state.version)
            controller = ShadowController(
                reg, "champ", "cand", chall_bundle,
                window_size=len(reqs), min_windows=1, cooldown_s=0.0,
                auto_actuate=False,
            )
            try:
                _drive(reg, controller, reqs, labels)
                assert (
                    controller.wait_for_verdict(timeout_s=60.0) == "promote"
                )
                assert controller.status == "promote_ready"
                with faults.inject("shadow_promote:99"):
                    assert (
                        controller.promote(raise_on_failure=False) is None
                    )
                assert controller.status == "rejected"
            finally:
                controller.close()
            assert int(reg.tenant("champ").engine._state.version) == v0
            got = _champ_scores(reg, reqs)
            m = reg.metrics()
            reg.close(release_bundles=True)
        assert chall_bundle.released  # a failed promotion cleans up
        assert np.array_equal(got, ref)
        assert m["tenants"]["champ"]["failed"] == 0
        assert faults.COUNTERS.get("shadow_rollbacks") == 1

    def test_promotion_stalled_before_commit_keeps_champion_serving(
        self, monkeypatch
    ):
        """A promotion held at `swap_commit` (staged, warmed, not yet
        flipped — where a killed promoter would die) neither blocks nor
        changes the champion: requests submitted mid-stall answer bitwise
        off the old generation, and the flip lands only when the commit
        runs."""
        reqs = _requests(45, 16)
        ref = _solo_scores(1, reqs)
        stalled, release = threading.Event(), threading.Event()
        orig_fault_point = faults.fault_point

        def _stalling_fault_point(site):
            if site == "swap_commit":
                stalled.set()
                assert release.wait(timeout=60.0)
            return orig_fault_point(site)

        with TenantRegistry(max_batch=32, max_wait_ms=2.0) as reg:
            reg.admit("champ", _bundle(1))
            v0 = int(reg.tenant("champ").engine._state.version)
            controller = ShadowController(
                reg, "champ", "cand", _bundle(2),
                window_size=len(reqs), min_windows=1, cooldown_s=0.0,
                auto_actuate=False,
            )
            monkeypatch.setattr(faults, "fault_point", _stalling_fault_point)
            promoter = threading.Thread(
                target=lambda: controller.promote(raise_on_failure=False),
                name="shadow-promote-drive",
            )
            try:
                promoter.start()  # an operator's promote, no verdict needed
                assert stalled.wait(timeout=60.0)
                mid = _champ_scores(reg, reqs)
                assert int(reg.tenant("champ").engine._state.version) == v0
            finally:
                release.set()
                promoter.join(timeout=60.0)
                controller.close()
            assert not promoter.is_alive()
            assert int(reg.tenant("champ").engine._state.version) == v0 + 1
            after = _champ_scores(reg, reqs)
            m = reg.metrics()
            reg.close(release_bundles=True)
        assert np.array_equal(mid, ref)
        assert np.array_equal(after, _solo_scores(2, reqs))
        assert not np.array_equal(after, ref)  # the flip did change answers
        assert m["tenants"]["champ"]["failed"] == 0


class TestDrain:
    def test_drain_digests_backlog_without_verdict(self):
        """A short replay can outrun the async evaluation worker (the
        first metric compile alone costs more than the replay): drain()
        must block until every already-joined full window has been
        evaluated, then return immediately — None when min_windows has
        not been reached — instead of sleeping out its full timeout."""
        reqs = _requests(61, 20)
        ref = _solo_scores(1, reqs)
        labels = _labels_from(ref)
        with TenantRegistry(max_batch=32, max_wait_ms=2.0) as reg:
            reg.admit("champ", _bundle(1))
            controller = ShadowController(
                reg, "champ", "cand", _bundle(2),
                window_size=8, min_windows=5, cooldown_s=0.0,
            )
            try:
                _drive(reg, controller, reqs, labels)
                t0 = time.monotonic()
                verdict = controller.drain(timeout_s=60.0)
                waited = time.monotonic() - t0
                # 20 rows at window_size=8 -> exactly 2 full windows
                # digested; the 4-row remainder must not stall drain
                # until the deadline.
                assert verdict is None
                assert controller.status == "observing"
                assert controller.summary()["windows"] == 2
                assert waited < 50.0
            finally:
                controller.close()
            reg.close(release_bundles=True)

    def test_drain_returns_verdict_after_actuation(self):
        """When the backlog holds enough windows for a verdict, drain()
        returns it only after the actuation has landed: an identical-
        weights challenger comes back 'promote' with the generation
        already flipped."""
        reqs = _requests(62, 16)
        ref = _solo_scores(1, reqs)
        labels = _labels_from(ref)
        chall_bundle = _bundle(1)
        with TenantRegistry(max_batch=32, max_wait_ms=2.0) as reg:
            reg.admit("champ", _bundle(1))
            v0 = int(reg.tenant("champ").engine._state.version)
            controller = ShadowController(
                reg, "champ", "cand", chall_bundle,
                window_size=len(reqs), min_windows=1, cooldown_s=0.0,
            )
            try:
                _drive(reg, controller, reqs, labels)
                assert controller.drain(timeout_s=60.0) == "promote"
                assert controller.status == "promoted"
            finally:
                controller.close()
            engine = reg.tenant("champ").engine
            assert int(engine._state.version) == v0 + 1
            reg.close(release_bundles=True)


class TestRegistryRemove:
    def test_remove_drains_and_refuses_new_submits(self):
        reqs = _requests(51, 4)
        with TenantRegistry(max_batch=32, max_wait_ms=2.0) as reg:
            reg.admit("a", _bundle(1))
            for r in reqs:
                reg.submit("a", r, block=True).result(timeout=30)
            reg.remove("a", release_bundle=True)
            assert "a" not in reg.tenant_names
            with pytest.raises(KeyError):
                reg.submit("a", reqs[0])
            reg.close()

    def test_remove_unknown_tenant_raises(self):
        with TenantRegistry(max_batch=32, max_wait_ms=2.0) as reg:
            with pytest.raises(KeyError):
                reg.remove("ghost")
            reg.close()


class TestShadowGatedRefresh:
    def test_one_round_gated_loop_commits_on_clean_verdict(
        self, tmp_path, monkeypatch
    ):
        """End-to-end refresh gate (cli/refresh --shadow-gate): the
        round's delta lands as a shadow tenant, earns a promote verdict
        on labelled probe traffic, and only then commits through the
        normal apply_delta generation flip."""
        from photon_ml_tpu.cli.refresh import run_refresh_loop

        # The challenger is the champion plus one tiny delta batch; on
        # 8-row probe windows the verdict needs a tolerance wider than
        # small-sample AUC noise (the strict default belongs to
        # production-sized windows).
        monkeypatch.setenv("PHOTON_SHADOW_REGRESSION_TOL", "0.35")
        journal_path = str(tmp_path / "journal.jsonl")
        journal = telemetry.install_journal(
            telemetry.RunJournal(journal_path)
        )
        try:
            summary = run_refresh_loop(
                str(tmp_path),
                rounds=1,
                base_rows=96,
                batch_rows=48,
                entities=8,
                new_entities_per_round=1,
                churn_entities=2,
                task=TASK,
                seed=0,
                shadow_gate=True,
                probe_rows=16,
            )
        finally:
            telemetry.uninstall_journal()
            journal.close()
        (rec,) = summary["rounds"]
        assert rec["shadow_verdict"] == "promote"
        assert rec["committed"] is True
        block = rec["shadow"]
        assert block["champion"] == "live"
        assert block["challenger"] == "delta-r0"
        assert block["windows"] == 2
        assert block["mirror_failures"] == 0
        n_ok, errors = telemetry.validate_journal(journal_path)
        assert errors == []
        events = [json.loads(l) for l in open(journal_path)]
        types = [e["type"] for e in events]
        assert "shadow_start" in types
        assert "shadow_verdict" in types
        assert "delta_apply" in types
        assert "delta_rollback" not in types
