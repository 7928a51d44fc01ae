"""Precision-tier graceful degradation suite (ISSUE 20).

The load-bearing contracts of the f32 -> bf16 -> int8 -> host ladder:

  * quantized serving is CHARACTERIZED, not bitwise: bf16/int8 answers
    stay within the pinned TIER_TOLERANCES of the f32 reference, and
    every quantization's measured round-trip error lands in the
    per-tenant `tier_quant_error` histogram;
  * restore is BITWISE: every quantize step retains the original f32
    rows on the host, so walking back up to f32 (from any rung,
    including through the host tier with LRU-promoted hot rows)
    reproduces the pre-demotion answers exactly;
  * every ladder transition is a stage -> pre-warm -> commit -> drain
    generation flip: an injected `quantize_stage`/`tier_restore` fault
    (transient or terminal) never fails a request and a terminal one
    leaves the OLD generation serving bitwise — the in-process statement
    of the mid-quantize-SIGKILL contract (nothing commits before the
    flip);
  * the pressure valve and the autopilot's hbm rules are ladder-aware:
    quantize-in-place is tried before host-tier demotion, restore walks
    back up one rung at a time under the ceiling gate, and the
    post-action contract probe holds ladder actions to the pinned
    tolerances instead of bitwise.
"""

from __future__ import annotations

import json

import numpy as np
import jax.numpy as jnp
import pytest

from photon_ml_tpu.autopilot import Action, Autopilot, ControlRule
from photon_ml_tpu.autopilot.rules import hbm_demote_rule, hbm_restore_rule
from photon_ml_tpu.autopilot.sensors import SensorSnapshot, TenantSensors
from photon_ml_tpu.game.model import (
    Coefficients,
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_ml_tpu.serving import ScoreRequest, ServingBundle, TenantRegistry
from photon_ml_tpu.serving.bundle import (
    PRECISION_LADDER,
    quantize_bundle_rows,
    restore_bundle_precision,
)
from photon_ml_tpu.serving.tenancy import TierErrorCeilingExceeded
from photon_ml_tpu.transformers.game_transformer import CoordinateScoringSpec
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils import faults, telemetry
from photon_ml_tpu.utils.contracts import (
    JOURNAL_EVENT_SCHEMAS,
    TIER_BLOCK_KEYS,
    TIER_TOLERANCES,
)

pytestmark = pytest.mark.serving

TASK = TaskType.LOGISTIC_REGRESSION
D_FE, D_RE, E = 7, 5, 24


def _make_model(seed: int, n_entities: int = E, d_re: int = D_RE):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=D_FE).astype(np.float32)
    M = np.zeros((n_entities + 1, d_re), np.float32)
    M[:n_entities] = rng.normal(size=(n_entities, d_re))
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


def _bundle(
    seed: int, n_entities: int = E, d_re: int = D_RE
) -> ServingBundle:
    model, specs = _make_model(seed, n_entities, d_re)
    return ServingBundle.from_model(model, specs, TASK)


def _requests(seed: int, n: int, n_entities: int = E, d_re: int = D_RE):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, D_FE)).astype(np.float32)
    Xe = rng.normal(size=(n, d_re)).astype(np.float32)
    ids = rng.integers(0, n_entities + 6, size=n)  # trained + cold starts
    return [
        ScoreRequest(
            features={"g": X[i], "re": Xe[i]},
            entity_ids={"eid": str(int(ids[i]))},
            offset=float(i) * 0.125,
            uid=str(i),
        )
        for i in range(n)
    ]


def _scores(reg, name, reqs) -> np.ndarray:
    return np.asarray([reg.score(name, r).score for r in reqs], np.float64)


def _allclose(got, ref, tier) -> bool:
    tol = TIER_TOLERANCES[tier]
    return np.allclose(got, ref, rtol=tol["rtol"], atol=tol["atol"])


# =========================================================== quantize planes


class TestQuantizedPlanes:
    @pytest.mark.parametrize("tier", ["bf16", "int8"])
    def test_row_roundtrip_error_within_pinned_tolerance(self, tier):
        """Dequantizing the staged plane reproduces the original rows
        within the rung's pinned tolerance, and the builder's reported
        per-coordinate error is consistent with the measured one."""
        bundle = _bundle(1)
        re_cid = next(
            cid
            for cid, c in bundle.coordinates.items()
            if c.is_random_effect
        )
        original = np.asarray(bundle.coordinates[re_cid].params, np.float32)
        q, errors = quantize_bundle_rows(bundle, tier)
        c = q.coordinates[re_cid]
        assert c.tier == tier
        if tier == "int8":
            deq = np.asarray(c.params, np.float32) * np.asarray(
                c.scales, np.float32
            )[:, None]
        else:
            assert c.scales is None
            deq = np.asarray(c.params.astype(jnp.float32))
        assert _allclose(deq, original, tier)
        assert re_cid in errors and errors[re_cid] >= 0.0
        # The originals ride along on the host for the bitwise restore.
        assert np.array_equal(c.host_f32, original)
        r = restore_bundle_precision(q)
        assert np.array_equal(
            np.asarray(r.coordinates[re_cid].params), original
        )
        assert r.coordinates[re_cid].tier == "f32"
        r.release(close_stores=False)
        q.release(close_stores=False)
        bundle.release(close_stores=False)

    def test_quantized_plane_is_smaller(self):
        bundle = _bundle(2)
        re_cid = next(
            cid
            for cid, c in bundle.coordinates.items()
            if c.is_random_effect
        )
        f32 = bundle.coordinates[re_cid].device_nbytes()
        q16, _ = quantize_bundle_rows(bundle, "bf16")
        q8, _ = quantize_bundle_rows(bundle, "int8")
        assert q16.coordinates[re_cid].device_nbytes() < f32
        # int8 plane + f32 scale vector still beats the bf16 plane.
        assert (
            q8.coordinates[re_cid].device_nbytes()
            < q16.coordinates[re_cid].device_nbytes()
        )
        q8.release(close_stores=False)
        q16.release(close_stores=False)
        bundle.release(close_stores=False)

    def test_reshard_refuses_quantized_coordinate(self):
        """The reshard planner assumes f32 row planes; a quantized
        coordinate must be refused loudly, not silently moved."""
        from photon_ml_tpu.serving.reshard import plan_coordinate_reshard

        bundle = _bundle(3)
        q, _ = quantize_bundle_rows(bundle, "bf16")
        c = next(
            c for c in q.coordinates.values() if c.is_random_effect
        )
        with pytest.raises(ValueError, match="quantized"):
            plan_coordinate_reshard(c, None)
        q.release(close_stores=False)
        bundle.release(close_stores=False)


# ========================================================== serving parity


class TestServingParity:
    def test_ladder_down_characterized_and_restore_bitwise(self):
        """Walk a serving tenant down every rung and back: quantized
        answers within the pinned tolerances, restored answers bitwise
        (never-quantized FE rows and the quantized RE rows alike)."""
        reqs = _requests(7, 12)
        with TenantRegistry(max_batch=32, max_wait_ms=5.0) as reg:
            reg.admit("a", _bundle(1))
            t = reg.tenant("a")
            ref = _scores(reg, "a", reqs)
            for rung in PRECISION_LADDER[1:]:
                assert reg.demote_tier("a", reason="test") > 0
                assert t.tier == rung
                got = _scores(reg, "a", reqs)
                assert _allclose(got, ref, rung)
            # One more rung: the host tier (PR 15 demotion), built from
            # the retained originals — bitwise, with hot-row promotion.
            reg.demote_tier("a", reason="test")
            assert t.demoted
            assert np.array_equal(_scores(reg, "a", reqs), ref)
            # Back up: host -> f32 in one restore (the cold matrix IS
            # the original rows), answers bitwise vs pre-demotion self.
            assert reg.restore_tier("a", reason="test") > 0
            assert t.tier == "f32" and not t.demoted
            assert np.array_equal(_scores(reg, "a", reqs), ref)
            m = reg.metrics()
            block = m["tenants"]["a"]["tier"]
            assert set(block) == set(TIER_BLOCK_KEYS)
            assert block["demotions"] == 2  # bf16, int8 (host is PR 15's)
            assert block["quant_error_max"] is not None
            assert m["tenants"]["a"]["failed"] == 0
            reg.close(release_bundles=True)

    def test_direct_rung_restore_is_bitwise(self):
        """int8 -> f32 without passing the host tier: the restore builds
        from the retained originals, never by dequantizing the lossy
        plane."""
        reqs = _requests(9, 10)
        with TenantRegistry(max_batch=16, max_wait_ms=2.0) as reg:
            reg.admit("a", _bundle(4))
            ref = _scores(reg, "a", reqs)
            assert reg.demote_tier("a", to="int8", reason="test") > 0
            assert reg.tenant("a").tier == "int8"
            assert reg.restore_tier("a", reason="test") > 0
            assert reg.tenant("a").tier == "f32"
            assert np.array_equal(_scores(reg, "a", reqs), ref)
            assert faults.COUNTERS.get("tier_demotions") == 2
            assert faults.COUNTERS.get("tier_restores") >= 1
            reg.close(release_bundles=True)

    def test_int8_error_ceiling_refuses_the_rung(self, monkeypatch):
        """An int8 step whose measured round-trip error exceeds the
        knobbed ceiling raises BEFORE commit; the tenant keeps serving
        on its current rung."""
        monkeypatch.setenv("PHOTON_TIER_INT8_ERROR_CEILING", "1e-9")
        reqs = _requests(11, 8)
        with TenantRegistry(max_batch=16, max_wait_ms=2.0) as reg:
            reg.admit("a", _bundle(5))
            ref = _scores(reg, "a", reqs)
            reg.demote_tier("a", to="bf16", reason="test")
            with pytest.raises(TierErrorCeilingExceeded):
                reg.demote_tier("a", to="int8", reason="test")
            t = reg.tenant("a")
            assert t.tier == "bf16"
            assert t.tier_rollbacks == 1
            assert _allclose(_scores(reg, "a", reqs), ref, "bf16")
            # Walking PAST int8 to the host tier skips the refused rung:
            # pressure relief still lands on the bitwise host tier.
            reg.demote_tier("a", to="host", reason="test")
            assert t.demoted
            assert np.array_equal(_scores(reg, "a", reqs), ref)
            reg.close(release_bundles=True)

    def test_valve_quantizes_before_host_demotion(self, monkeypatch):
        """With the ladder opted in, HBM pressure at admission quantizes
        the coldest tenant in place instead of demoting it to the host
        tier."""
        monkeypatch.setenv("PHOTON_TIER_LADDER", "1")
        b0, b1, b2 = _bundle(10), _bundle(11), _bundle(12)
        per = b0.device_bytes_per_shard()
        with TenantRegistry(
            max_batch=16,
            max_wait_ms=2.0,
            hbm_budget_bytes=int(per * 3 - 100),
        ) as reg:
            reg.admit("cold", b0)
            reg.admit("warm", b1)
            reg.score("warm", _requests(62, 1)[0])  # cold is coldest
            reg.admit("new", b2)  # over budget -> quantize, don't demote
            m = reg.metrics()
            assert not m["tenants"]["cold"]["demoted"]
            assert m["tenants"]["cold"]["tier"]["tier"] != "f32"
            assert m["tenants"]["warm"]["tier"]["tier"] == "f32"
            assert m["tenants"]["new"]["tier"]["tier"] == "f32"
            reg.close(release_bundles=True)

    def test_ladder_keeps_three_times_the_f32_capacity_resident(
        self, monkeypatch
    ):
        """The HBM squeeze: seven wide tenants under a budget that fits one
        f32 tenant beside an int8 fleet. Without the ladder the valve
        host-demotes whole tenants and two stay resident; with it every
        tenant stays resident — at least 3x — each answering within its
        rung's pinned tolerance, with no failed request anywhere."""
        d_re, n_ent, names = 32, 64, [f"lad-{i}" for i in range(7)]
        reqs = {
            nm: _requests(900 + j, 8, n_ent, d_re)
            for j, nm in enumerate(names)
        }
        probe = _bundle(777, n_ent, d_re)
        per_f32 = probe.device_bytes_per_shard()
        q_probe, _ = quantize_bundle_rows(probe, "int8")
        per_i8 = q_probe.device_bytes_per_shard()
        q_probe.release(close_stores=False)
        probe.release(close_stores=False)
        budget = per_f32 + (len(names) - 1) * per_i8 + per_i8 // 2

        def squeeze(ladder_on):
            if ladder_on:
                monkeypatch.setenv("PHOTON_TIER_LADDER", "1")
            else:
                monkeypatch.delenv("PHOTON_TIER_LADDER", raising=False)
            with TenantRegistry(
                max_batch=16, max_wait_ms=2.0, hbm_budget_bytes=int(budget)
            ) as reg:
                refs = {}
                for j, nm in enumerate(names):
                    reg.admit(nm, _bundle(800 + j, n_ent, d_re))
                    refs[nm] = _scores(reg, nm, reqs[nm])  # f32: just admitted
                blocks = reg.metrics()["tenants"]
                for nm in names:
                    got = _scores(reg, nm, reqs[nm])
                    assert _allclose(got, refs[nm], blocks[nm]["tier"]["tier"])
                failed = sum(
                    b["failed"] for b in reg.metrics()["tenants"].values()
                )
                reg.close(release_bundles=True)
            resident = sum(1 for b in blocks.values() if not b["demoted"])
            return resident, failed

        f32_capacity, failed_off = squeeze(ladder_on=False)
        ladder_resident, failed_on = squeeze(ladder_on=True)
        assert 1 <= f32_capacity < len(names)  # the squeeze bites
        assert ladder_resident >= 3 * f32_capacity
        assert failed_off == 0 and failed_on == 0


# ======================================================== fault injection


@pytest.mark.chaos
class TestLadderFaults:
    def test_transient_quantize_fault_retries_and_commits(self):
        reqs = _requests(21, 8)
        with TenantRegistry(max_batch=16, max_wait_ms=2.0) as reg:
            reg.admit("a", _bundle(6))
            ref = _scores(reg, "a", reqs)
            with faults.inject("quantize_stage:1"):
                assert reg.demote_tier("a", reason="test") > 0
            t = reg.tenant("a")
            assert t.tier == "bf16"
            assert t.tier_rollbacks == 0
            assert _allclose(_scores(reg, "a", reqs), ref, "bf16")
            assert reg.metrics()["tenants"]["a"]["failed"] == 0
            reg.close(release_bundles=True)

    def test_terminal_quantize_fault_leaves_old_generation_bitwise(self):
        """Retry exhaustion mid-quantize: NOTHING commits before the
        generation flip, so the old f32 generation keeps serving bitwise
        with zero failed requests — the in-process statement of the
        mid-quantize-SIGKILL contract (a killed process never wrote a
        new generation either)."""
        reqs = _requests(23, 8)
        with TenantRegistry(max_batch=16, max_wait_ms=2.0) as reg:
            reg.admit("a", _bundle(7))
            t = reg.tenant("a")
            ref = _scores(reg, "a", reqs)
            version = t.engine._state.version
            with faults.inject("quantize_stage:99"):
                with pytest.raises(faults.InjectedFault):
                    reg.demote_tier("a", reason="test")
            assert t.tier == "f32"
            assert t.tier_rollbacks == 1
            assert t.engine._state.version == version  # no flip happened
            assert np.array_equal(_scores(reg, "a", reqs), ref)
            assert reg.metrics()["tenants"]["a"]["failed"] == 0
            assert faults.COUNTERS.get("tier_rollbacks") == 1
            assert faults.COUNTERS.get("tier_demotions") == 0
            reg.close(release_bundles=True)

    def test_terminal_restore_fault_keeps_quantized_generation(self):
        reqs = _requests(25, 8)
        with TenantRegistry(max_batch=16, max_wait_ms=2.0) as reg:
            reg.admit("a", _bundle(8))
            ref = _scores(reg, "a", reqs)
            reg.demote_tier("a", to="bf16", reason="test")
            with faults.inject("tier_restore:99"):
                with pytest.raises(faults.InjectedFault):
                    reg.restore_tier("a", reason="test")
            t = reg.tenant("a")
            assert t.tier == "bf16"  # the quantized generation survived
            assert _allclose(_scores(reg, "a", reqs), ref, "bf16")
            assert reg.metrics()["tenants"]["a"]["failed"] == 0
            # A later clean restore still lands bitwise.
            reg.restore_tier("a", reason="test")
            assert np.array_equal(_scores(reg, "a", reqs), ref)
            reg.close(release_bundles=True)

    def test_chaos_confined_to_the_transitioning_tenant(self):
        """A neighbor keeps answering bitwise, co-batched traffic and
        all, while another tenant's quantize step fails terminally."""
        req_a, req_b = _requests(27, 8), _requests(28, 8)
        with TenantRegistry(max_batch=16, max_wait_ms=2.0) as reg:
            reg.admit("chaos", _bundle(9))
            reg.admit("clean", _bundle(10))
            ref_clean = _scores(reg, "clean", req_b)
            with faults.inject("quantize_stage:99"):
                with pytest.raises(faults.InjectedFault):
                    reg.demote_tier("chaos", reason="test")
            assert np.array_equal(_scores(reg, "clean", req_b), ref_clean)
            assert np.array_equal(
                _scores(reg, "chaos", req_a),
                _scores(reg, "chaos", req_a),
            )
            m = reg.metrics()
            assert m["tenants"]["clean"]["failed"] == 0
            assert m["tenants"]["chaos"]["failed"] == 0
            reg.close(release_bundles=True)


# ==================================================== telemetry / journal


class TestLadderObservability:
    def test_transitions_journal_valid_and_histogram_labeled(self, tmp_path):
        path = str(tmp_path / "journal.jsonl")
        journal = telemetry.install_journal(telemetry.RunJournal(path))
        try:
            reqs = _requests(31, 6)
            with TenantRegistry(max_batch=16, max_wait_ms=2.0) as reg:
                reg.admit("a", _bundle(11))
                _scores(reg, "a", reqs)
                reg.demote_tier("a", to="int8", reason="test")
                reg.restore_tier("a", reason="test")
                reg.close(release_bundles=True)
        finally:
            telemetry.uninstall_journal()
            journal.close()
        n_ok, errors = telemetry.validate_journal(path)
        assert errors == []
        events = [json.loads(l) for l in open(path) if l.strip()]
        demotes = [e for e in events if e["type"] == "tier_demote"]
        restores = [e for e in events if e["type"] == "tier_restore"]
        assert [(e["from_tier"], e["to_tier"]) for e in demotes] == [
            ("f32", "bf16"),
            ("bf16", "int8"),
        ]
        assert restores and restores[-1]["to_tier"] == "f32"
        for e in demotes + restores:
            for key in JOURNAL_EVENT_SCHEMAS[e["type"]]:
                assert key in e, (e["type"], key)
        assert demotes[0]["evidence"]["quant_error_max"] >= 0.0
        # The per-tenant quantization-error histogram carries the
        # tenant label from the ambient metric scope.
        labeled = telemetry.METRICS.labeled_histograms("tier_quant_error")
        assert any(k == "tenant=a" for k in labeled)

    def test_obs_decisions_renders_tier_transitions(self, tmp_path, capsys):
        from photon_ml_tpu.cli.obs import cmd_decisions

        path = str(tmp_path / "journal.jsonl")
        journal = telemetry.install_journal(telemetry.RunJournal(path))
        try:
            with TenantRegistry(max_batch=16, max_wait_ms=2.0) as reg:
                reg.admit("a", _bundle(12))
                reg.demote_tier("a", to="bf16", reason="test")
                reg.restore_tier("a", reason="test")
                reg.close(release_bundles=True)
        finally:
            telemetry.uninstall_journal()
            journal.close()

        class _Args:
            pass

        args = _Args()
        args.path = path
        assert cmd_decisions(args) == 0
        out = capsys.readouterr().out
        assert "tier v" in out and "tier ^" in out
        assert "f32 -> bf16" in out and "bf16 -> f32" in out


# ========================================================== autopilot rules


def _tsensors(name, *, tier="f32", can_quantize=True, last_active=0.0,
              demoted=False, can_demote=True):
    return TenantSensors(
        name=name,
        demoted=demoted,
        can_demote=can_demote,
        last_active=last_active,
        completed=0,
        failed=0,
        in_flight=0,
        pending=0,
        device_bytes=1000,
        p95_ms=None,
        p99_ms=None,
        coords=(),
        tier=tier,
        can_quantize=can_quantize,
    )


def _snap(tenants, used=90, budget=100):
    return SensorSnapshot(
        tenants={t.name: t for t in tenants},
        hbm_budget=budget,
        hbm_used=used,
        latency_p95_ms=None,
        latency_p99_ms=None,
        queue_wait_p95_ms=None,
        batch_p50=None,
        failed_requests=0,
    )


class TestLadderRules:
    def test_demote_rule_prefers_quantize_when_ladder_on(self, monkeypatch):
        monkeypatch.setenv("PHOTON_TIER_LADDER", "1")
        rule = hbm_demote_rule()
        cur = _snap([_tsensors("a")], used=90)
        action = rule.decide(cur, None, 0.90)
        assert action.kind == "tier_demote"
        assert action.params["to"] == "bf16"
        assert action.evidence["from_tier"] == "f32"

    def test_demote_rule_int8_needs_the_higher_pressure(self, monkeypatch):
        monkeypatch.setenv("PHOTON_TIER_LADDER", "1")
        rule = hbm_demote_rule()
        cur = _snap([_tsensors("a", tier="bf16")], used=90)
        # Below the planned int8 pressure: the next rung is withheld and
        # the rule falls back to the host tier.
        action = rule.decide(cur, None, 0.90)
        assert action.kind == "demote"
        action = rule.decide(cur, None, 0.95)
        assert action.kind == "tier_demote"
        assert action.params["to"] == "int8"

    def test_demote_rule_host_tier_when_ladder_off(self):
        rule = hbm_demote_rule()
        cur = _snap([_tsensors("a")], used=90)
        action = rule.decide(cur, None, 0.90)
        assert action.kind == "demote"

    def test_restore_rule_walks_up_under_the_ceiling(self):
        rule = hbm_restore_rule()
        cur = _snap([_tsensors("a", tier="bf16")], used=40)
        action = rule.decide(cur, None, 0.6)
        assert action.kind == "tier_restore"
        assert action.params["to"] == "f32"
        cur = _snap([_tsensors("a", tier="int8")], used=40)
        assert rule.decide(cur, None, 0.6).params["to"] == "bf16"
        # Above the ceiling the restore is refused — walking straight
        # back into the demote band is the oscillation the gate avoids.
        over = _snap([_tsensors("a", tier="bf16")], used=85)
        assert rule.decide(over, None, 0.15) is None

    def test_restore_rule_signal_sees_quantized_tenants(self):
        rule = hbm_restore_rule()
        quantized = _snap([_tsensors("a", tier="int8")], used=40)
        assert rule.signal(quantized, None) == pytest.approx(0.6)
        healthy = _snap([_tsensors("a")], used=40)
        assert rule.signal(healthy, None) is None


class TestAutopilotLadderActuation:
    def _rule(self, kind, params, from_tier="f32"):
        return ControlRule(
            name=f"drive-{kind}",
            signal=lambda cur, prev: 12.0,
            fire_above=10.0,
            rearm_below=2.0,
            decide=lambda cur, prev, sig: Action(
                kind=kind,
                tenant="a",
                params=dict(params),
                # The built-in rules record the current rung; the probe
                # compares under the coarser of from/to.
                evidence={"from_tier": from_tier},
            ),
            cooldown_s=0.0,
        )

    def test_tier_actions_pass_the_characterized_probe(self):
        """A ladder step changes probe answers within tolerance — the
        loop must hold it to TIER_TOLERANCES, apply it, and the
        follow-up restore must land back on f32."""
        reqs = _requests(41, 4)
        with TenantRegistry(max_batch=16, max_wait_ms=2.0) as reg:
            reg.admit("a", _bundle(13))
            ref = _scores(reg, "a", reqs)
            down = Autopilot(
                reg,
                rules=[self._rule("tier_demote", {"to": "bf16"})],
                probe_requests={"a": reqs[0]},
                cooldown_s=0.0,
                max_actions=100,
                start=False,
            )
            down.tick()
            assert down.summary()["actions"] == 1
            assert down.summary()["rollbacks"] == 0
            assert reg.tenant("a").tier == "bf16"
            up = Autopilot(
                reg,
                rules=[
                    self._rule(
                        "tier_restore", {"to": "f32"}, from_tier="bf16"
                    )
                ],
                probe_requests={"a": reqs[0]},
                cooldown_s=0.0,
                max_actions=100,
                start=False,
            )
            up.tick()
            assert up.summary()["actions"] == 1
            assert reg.tenant("a").tier == "f32"
            assert np.array_equal(_scores(reg, "a", reqs), ref)
            assert reg.metrics()["tenants"]["a"]["failed"] == 0
            reg.close(release_bundles=True)

    @pytest.mark.chaos
    def test_actuation_fault_rolls_back_the_ladder_step(self):
        reqs = _requests(43, 4)
        with TenantRegistry(max_batch=16, max_wait_ms=2.0) as reg:
            reg.admit("a", _bundle(14))
            ref = _scores(reg, "a", reqs)
            pilot = Autopilot(
                reg,
                rules=[self._rule("tier_demote", {"to": "bf16"})],
                probe_requests={"a": reqs[0]},
                cooldown_s=0.0,
                max_actions=100,
                start=False,
            )
            with faults.inject("autopilot_act:1"):
                pilot.tick()
            s = pilot.summary()
            assert s["rollbacks"] == 1 and s["actions"] == 0
            assert reg.tenant("a").tier == "f32"
            assert np.array_equal(_scores(reg, "a", reqs), ref)
            assert reg.metrics()["tenants"]["a"]["failed"] == 0
            reg.close(release_bundles=True)
