"""Closed-loop autoscaling suite (ISSUE 19).

The load-bearing contracts of the autopilot:

  * HYSTERESIS — a sawtooth signal that crosses the fire band on every
    crest actuates ONCE per band crossing, not once per crest;
  * COOLDOWN and the ACTION BUDGET bound actuation frequency no matter
    how eager the policy set is;
  * one rollback QUARANTINES a rule, and only an operator `reset_rule`
    lifts it — the loop never self-forgives;
  * every actuator path rolls back under an injected `autopilot_act`
    fault with ZERO failed client requests and bitwise-unchanged
    answers;
  * every decision the loop takes validates against its journal schema.
"""

from __future__ import annotations

import time

import numpy as np
import jax.numpy as jnp
import pytest

from photon_ml_tpu.autopilot import (
    Action,
    Autopilot,
    ControlRule,
    SensorSnapshot,
    read_sensors,
    rebalance_rule,
    shard_grow_rule,
)
from photon_ml_tpu.game.model import (
    Coefficients,
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_ml_tpu.serving import ScoreRequest, ServingBundle, TenantRegistry
from photon_ml_tpu.transformers.game_transformer import CoordinateScoringSpec
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils import faults, telemetry

pytestmark = pytest.mark.serving

TASK = TaskType.LOGISTIC_REGRESSION
D_FE, D_RE, E = 7, 5, 24


# ------------------------------------------------------- synthetic sensors


def _snap(sig: float = 0.0) -> SensorSnapshot:
    """A synthetic snapshot; `failed_requests` doubles as the scripted
    signal channel the unit rules below read."""
    return SensorSnapshot(
        tenants={},
        hbm_budget=None,
        hbm_used=0,
        latency_p95_ms=None,
        latency_p99_ms=None,
        queue_wait_p95_ms=None,
        batch_p50=None,
        failed_requests=sig,
    )


def _scripted(values):
    """sensor_fn replaying one scripted signal value per tick."""
    it = iter(values)

    def fn(_registry):
        return _snap(next(it))

    return fn


def _unit_rule(
    name="unit-rule",
    *,
    fire_above=10.0,
    rearm_below=2.0,
    cooldown_s=None,
    fail=None,
    none_below=None,
):
    """A custom rule over the scripted signal channel. `fail` is a
    mutable [bool] — apply raises while it holds True. `none_below`
    makes the signal return None (no evidence) under that value."""
    applied = []
    undone = []

    def signal(cur, prev):
        v = float(cur.failed_requests)
        if none_below is not None and v < none_below:
            return None
        return v

    def decide(cur, prev, sig):
        def apply_fn():
            if fail is not None and fail[0]:
                raise RuntimeError("deliberately bad actuation")
            applied.append(sig)

        return Action(
            kind="custom",
            evidence={"sig": sig},
            apply_fn=apply_fn,
            undo_fn=lambda: undone.append(sig),
        )

    rule = ControlRule(
        name=name,
        signal=signal,
        fire_above=fire_above,
        rearm_below=rearm_below,
        decide=decide,
        cooldown_s=cooldown_s,
    )
    return rule, applied, undone


class _FakeTenant:
    def __init__(self):
        self.failed = 0


class _FakeRegistry:
    """Just enough registry for the probe: one tenant, a failed counter
    tests can bump from inside an actuation."""

    def __init__(self):
        self._t = _FakeTenant()

    @property
    def tenant_names(self):
        return ["a"]

    def tenant(self, name):
        return self._t


def _pilot(values, rules, **kw):
    kw.setdefault("cooldown_s", 0.0)
    kw.setdefault("max_actions", 100)
    return Autopilot(
        _FakeRegistry(),
        rules=rules,
        sensor_fn=_scripted(values),
        start=False,
        **kw,
    )


# ------------------------------------------------------------- real fleet


def _make_model(seed: int, n_entities: int = E):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=D_FE).astype(np.float32)
    M = np.zeros((n_entities + 1, D_RE), np.float32)
    M[:n_entities] = rng.normal(size=(n_entities, D_RE))
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


def _bundle(seed: int, n_entities: int = E) -> ServingBundle:
    model, specs = _make_model(seed, n_entities)
    return ServingBundle.from_model(model, specs, TASK)


def _requests(seed: int, n: int, n_entities: int = E):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, D_FE)).astype(np.float32)
    Xe = rng.normal(size=(n, D_RE)).astype(np.float32)
    ids = rng.integers(0, n_entities, size=n)
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


# ================================================================ hysteresis


class TestHysteresis:
    def test_sawtooth_actuates_once_per_band_crossing(self):
        """A sawtooth oscillating between 5 and 12 (band: fire>=10,
        rearm<=2) actuates on the FIRST crest only; crests while the
        trough never reaches the re-arm watermark are held. Dropping to
        1 re-arms, and the next crest fires again."""
        rule, applied, _ = _unit_rule()
        pilot = _pilot([12, 5, 12, 5, 12, 1, 12], [rule])
        for _ in range(7):
            pilot.tick()
        assert applied == [12.0, 12.0]
        s = pilot.summary()
        assert s["actions"] == 2
        assert s["rollbacks"] == 0
        assert s["ticks"] == 7
        # Holds are silent: only the two actuations were decisions.
        assert s["decisions"] == 2

    def test_none_signal_neither_fires_nor_rearms(self):
        """None = no evidence: it must not fire, and it must not re-arm
        a disarmed rule (absence of data is not a calm signal)."""
        rule, applied, _ = _unit_rule(none_below=2.0)
        # 0 -> None (below none_below): would re-arm if treated as low.
        pilot = _pilot([12, 0, 12, 3, 12], [rule])
        for _ in range(5):
            pilot.tick()
        # Fired once; the None tick did NOT re-arm (3 > rearm_below so
        # the later ticks never re-arm either).
        assert applied == [12.0]

    def test_inverted_band_is_rejected(self):
        with pytest.raises(ValueError, match="rearm_below"):
            ControlRule(
                name="inverted",
                signal=lambda cur, prev: 0.0,
                fire_above=1.0,
                rearm_below=5.0,
                decide=lambda cur, prev, sig: None,
            )

    def test_duplicate_rule_names_rejected(self):
        r1, _, _ = _unit_rule("dup")
        r2, _, _ = _unit_rule("dup")
        with pytest.raises(ValueError, match="duplicate"):
            _pilot([], [r1, r2])


# ========================================================= cooldown / budget


class TestCooldownAndBudget:
    def test_cooldown_suppresses_refire(self):
        """A re-armed rule inside its cooldown is SUPPRESSED (journaled,
        counted) rather than actuated."""
        rule, applied, _ = _unit_rule(cooldown_s=3600.0)
        pilot = _pilot([12, 1, 12], [rule], cooldown_s=3600.0)
        for _ in range(3):
            pilot.tick()
        assert applied == [12.0]
        s = pilot.summary()
        assert s["actions"] == 1
        assert s["suppressed"] == 1
        assert s["last_outcome"] == "suppressed_cooldown"
        assert telemetry.METRICS.get_counter("autopilot_suppressed") == 1

    def test_action_budget_bounds_the_whole_policy_set(self):
        """With a budget of 1 action per window, the second eager rule
        of the SAME tick is suppressed — a misbehaving policy set
        degrades to slow, never to thrashing."""
        r1, a1, _ = _unit_rule("eager-1", cooldown_s=0.0)
        r2, a2, _ = _unit_rule("eager-2", cooldown_s=0.0)
        pilot = _pilot([12], [r1, r2], cooldown_s=0.0, max_actions=1)
        pilot.tick()
        assert a1 == [12.0]
        assert a2 == []
        s = pilot.summary()
        assert s["actions"] == 1
        assert s["suppressed"] == 1
        assert s["last_outcome"] == "suppressed_budget"

    def test_knob_deferral_and_validation(self, monkeypatch):
        monkeypatch.setenv("PHOTON_AUTOPILOT_MS", "123")
        monkeypatch.setenv("PHOTON_AUTOPILOT_COOLDOWN_S", "7.5")
        monkeypatch.setenv("PHOTON_AUTOPILOT_MAX_ACTIONS", "9")
        pilot = Autopilot(_FakeRegistry(), rules=[], start=False)
        assert pilot.tick_ms == 123
        assert pilot.cooldown_s == 7.5
        assert pilot.max_actions == 9
        with pytest.raises(ValueError):
            Autopilot(_FakeRegistry(), rules=[], tick_ms=0, start=False)
        with pytest.raises(ValueError):
            Autopilot(
                _FakeRegistry(), rules=[], max_actions=0, start=False
            )


# ================================================================ quarantine


class TestQuarantine:
    def test_rollback_quarantines_until_operator_reset(self):
        """One failed actuation quarantines the rule; the quarantined
        rule stays OFF (suppressed, journaled) however loud its signal,
        until reset_rule — after which it may actuate again."""
        fail = [True]
        rule, applied, _ = _unit_rule(fail=fail)
        pilot = _pilot([12, 1, 12, 1, 12], [rule])
        pilot.tick()  # fires -> apply raises -> rollback + quarantine
        assert applied == []
        assert rule.quarantined
        assert rule.rollbacks == 1
        counters = faults.counters()
        assert counters.get("autopilot_rollbacks") == 1
        assert counters.get("autopilot_quarantines") == 1
        pilot.tick()  # 1: re-arms (quarantine does not block re-arming)
        pilot.tick()  # 12: armed but quarantined -> suppressed
        assert applied == []
        assert pilot.summary()["last_outcome"] == "suppressed_quarantined"
        assert pilot.summary()["quarantined"] == [rule.name]
        # Operator reset is the only way out.
        fail[0] = False
        pilot.reset_rule(rule.name)
        pilot.tick()  # 1: calm
        pilot.tick()  # 12: fires and applies this time
        assert applied == [12.0]
        assert not rule.quarantined

    def test_reset_unknown_rule_raises(self):
        pilot = _pilot([], [])
        with pytest.raises(KeyError):
            pilot.reset_rule("no-such-rule")

    def test_probe_regression_rolls_back_with_undo(self):
        """An actuation that makes a client request FAIL between the
        pre and post probes is undone (the undo closure runs) and the
        rule is quarantined."""
        reg = _FakeRegistry()
        applied = []
        undone = []

        def decide(cur, prev, sig):
            def apply_fn():
                applied.append(sig)
                reg.tenant("a").failed += 1  # the regression

            return Action(
                kind="custom",
                apply_fn=apply_fn,
                undo_fn=lambda: undone.append(sig),
            )

        rule = ControlRule(
            name="regressing",
            signal=lambda cur, prev: float(cur.failed_requests),
            fire_above=10.0,
            rearm_below=2.0,
            decide=decide,
        )
        pilot = Autopilot(
            reg,
            rules=[rule],
            sensor_fn=_scripted([12]),
            cooldown_s=0.0,
            max_actions=100,
            start=False,
        )
        pilot.tick()
        assert applied == [12.0]
        assert undone == [12.0]
        assert rule.quarantined
        s = pilot.summary()
        assert s["rollbacks"] == 1
        assert s["actions"] == 0
        assert s["last_outcome"] == "rolled_back"


# ====================================================== fault-injected paths


class TestActuatorRollbackUnderInjection:
    def test_every_actuator_path_rolls_back_with_zero_failed(self):
        """All five built-in actuator kinds, each armed by its own rule,
        hit an injected `autopilot_act` fault: every one rolls back, its
        rule is quarantined, and the fleet's answers stay bitwise with
        ZERO failed client requests."""
        reqs_a, reqs_b = _requests(31, 6), _requests(32, 6)
        kinds = (
            ("reshard", "a", {}),
            ("rebalance", "b", {"cid": "per-e"}),
            ("demote", "a", {}),
            ("restore", "b", {}),
            ("retune", None, {"serving_max_wait_ms": 1.0}),
        )
        rules = [
            ControlRule(
                name=f"inj-{kind}",
                signal=lambda cur, prev: 12.0,
                fire_above=10.0,
                rearm_below=2.0,
                decide=(
                    lambda cur, prev, sig, k=kind, t=tenant, p=params: Action(
                        kind=k, tenant=t, params=dict(p)
                    )
                ),
                cooldown_s=0.0,
            )
            for kind, tenant, params in kinds
        ]
        with TenantRegistry(max_batch=16, max_wait_ms=2.0) as reg:
            reg.admit("a", _bundle(1))
            reg.admit("b", _bundle(2))
            reg.demote("b", hot_rows=4)  # makes "restore" a live path
            ref_a = _scores(reg, "a", reqs_a)
            ref_b = _scores(reg, "b", reqs_b)
            pilot = Autopilot(
                reg,
                rules=rules,
                probe_requests={"a": reqs_a[0], "b": reqs_b[0]},
                cooldown_s=0.0,
                max_actions=100,
                start=False,
            )
            with faults.inject("autopilot_act:5"):
                pilot.tick()
            s = pilot.summary()
            assert s["rollbacks"] == 5
            assert s["actions"] == 0
            assert sorted(s["quarantined"]) == sorted(
                r.name for r in rules
            )
            counters = faults.counters()
            assert counters.get("autopilot_rollbacks") == 5
            assert counters.get("autopilot_quarantines") == 5
            # The contract: injection at the actuation site never
            # reaches a client. Answers bitwise, zero failed.
            assert np.array_equal(_scores(reg, "a", reqs_a), ref_a)
            assert np.array_equal(_scores(reg, "b", reqs_b), ref_b)
            m = reg.metrics()
            assert m["tenants"]["a"]["failed"] == 0
            assert m["tenants"]["b"]["failed"] == 0
            reg.close(release_bundles=True)


# ============================================================ real actuators


class TestRealActuators:
    def test_demote_restore_ladder_is_bitwise(self):
        """The new `restore` actuator is the exact inverse of demote:
        the tenant comes back single-tier and answers BITWISE what it
        answered before demotion."""
        reqs = _requests(41, 8)
        with TenantRegistry(max_batch=16, max_wait_ms=2.0) as reg:
            reg.admit("a", _bundle(1))
            ref = _scores(reg, "a", reqs)
            assert reg.demote("a", hot_rows=4) > 0
            assert reg.tenant("a").demoted
            assert np.array_equal(_scores(reg, "a", reqs), ref)
            assert reg.restore("a") > 0
            t = reg.tenant("a")
            assert not t.demoted
            # Single-tier again: no two-tier store on the RE coordinate.
            assert all(
                c.store is None
                for c in t.engine._state.bundle.coordinates.values()
            )
            assert np.array_equal(_scores(reg, "a", reqs), ref)
            assert faults.counters().get("tenant_restores") == 1
            # Restoring a tenant that is not demoted is a free no-op.
            assert reg.restore("a") == 0
            reg.close(release_bundles=True)

    def test_load_shift_reshards_and_rebalances(self, assert_sharded_close):
        """A request burst onto a replicated tenant and cold-row traffic
        onto a two-tier tenant: one tick of the built-in rules reshards
        the first across the mesh and re-places the second's hot set from
        its measured promotions — both actions applied, none rolled back,
        zero failed requests. The rebalanced tenant answers bitwise (the
        same rows, another tier); the resharded one is another program
        and holds the `serve` tolerance."""
        reqs_a = _requests(191, 16)
        reqs_b_cold = [
            r for r in _requests(193, 48) if int(r.entity_ids["eid"]) >= 8
        ]  # beyond b's 8 hot rows: every one a cold-tier hit
        with TenantRegistry(max_batch=32, max_wait_ms=2.0) as reg:
            reg.admit("a", _bundle(1))
            reg.admit("b", _bundle(2))
            ref_a = _scores(reg, "a", reqs_a)
            ref_b = _scores(reg, "b", reqs_b_cold)
            reg.demote("b", hot_rows=8, reason="test-setup")
            pilot = Autopilot(
                reg,
                rules=[
                    shard_grow_rule(fire_above=32.0, rearm_below=4.0),
                    rebalance_rule(fire_above=4.0, rearm_below=1.0),
                ],
                cooldown_s=30.0,
                max_actions=4,
                probe_requests={"a": reqs_a[0], "b": reqs_b_cold[0]},
                start=False,
            )
            pilot.tick()  # baseline snapshot: the rules read deltas
            for r in _requests(194, 96):
                reg.score("a", r)
            assert np.array_equal(_scores(reg, "b", reqs_b_cold), ref_b)

            def promotions():
                return sum(
                    sum(c.store.promotion_stats().values())
                    for c in reg.tenant("b")
                    .engine._state.bundle.coordinates.values()
                    if getattr(c, "store", None) is not None
                )

            deadline = time.monotonic() + 30.0  # the promote worker is async
            while promotions() < 4 and time.monotonic() < deadline:
                time.sleep(0.05)
            pilot.tick()  # the loop reacts
            s = pilot.summary()
            pilot.close()
            assert s["actions"] == 2 and s["rollbacks"] == 0, s
            assert s["quarantined"] == []
            assert any(
                c.mesh is not None
                for c in reg.tenant("a")
                .engine._state.bundle.coordinates.values()
            )
            assert_sharded_close(_scores(reg, "a", reqs_a), ref_a, "serve")
            assert np.array_equal(_scores(reg, "b", reqs_b_cold), ref_b)
            m = reg.metrics()
            assert m["tenants"]["a"]["failed"] == 0
            assert m["tenants"]["b"]["failed"] == 0
            assert faults.counters().get("autopilot_rollbacks", 0) == 0
            reg.close(release_bundles=True)

    def test_retune_updates_live_wait_and_round_trips(self):
        with TenantRegistry(max_batch=16, max_wait_ms=4.0) as reg:
            prev = reg.retune(max_wait_ms=1.0)
            assert prev == {"max_wait_ms": 4.0}
            assert reg.max_wait_s == pytest.approx(1e-3)
            reg.retune(max_wait_ms=prev["max_wait_ms"])
            assert reg.max_wait_s == pytest.approx(4e-3)
            with pytest.raises(ValueError):
                reg.retune(max_wait_ms=-1.0)

    def test_apply_online_decision_round_trips_fallback(self):
        from photon_ml_tpu import planner

        d1 = planner.apply_online_decision("serving_max_wait_ms", 1.0)
        assert d1 is not None
        assert d1.source == "autopilot"
        assert float(planner.planned_value("serving_max_wait_ms")) == 1.0
        d2 = planner.apply_online_decision("serving_max_wait_ms", 0.5)
        assert d2.fallback == 1.0  # rollback target = displaced value
        planner.apply_online_decision("serving_max_wait_ms", d2.fallback)
        assert float(planner.planned_value("serving_max_wait_ms")) == 1.0

    def test_read_sensors_over_live_fleet(self):
        """The sensor surface over a real registry: per-tenant labeled
        p95s, shard loads, HBM accounting, demotion flags."""
        reqs = _requests(51, 8)
        with TenantRegistry(max_batch=16, max_wait_ms=2.0) as reg:
            reg.admit("a", _bundle(1))
            reg.admit("b", _bundle(2))
            _scores(reg, "a", reqs)
            snap = read_sensors(reg)
            assert set(snap.tenants) == {"a", "b"}
            ta = snap.tenants["a"]
            assert ta.completed == len(reqs)
            assert ta.p95_ms is not None  # labeled histogram populated
            assert snap.tenants["b"].p95_ms is None  # no traffic yet
            assert ta.coords and ta.coords[0].total_load > 0
            assert snap.hbm_used > 0
            assert snap.failed_requests == 0
            reg.close(release_bundles=True)


# ================================================================== journal


class TestJournal:
    def test_every_decision_validates_against_its_schema(self, tmp_path):
        """Drive applied, suppressed, and rolled-back outcomes with an
        ambient journal: every line must validate, and the three
        autopilot event types must all appear."""
        path = str(tmp_path / "journal.jsonl")
        journal = telemetry.install_journal(telemetry.RunJournal(path))
        try:
            good, _, _ = _unit_rule("good", cooldown_s=3600.0)
            fail = [True]
            bad, _, _ = _unit_rule("bad", fail=fail, cooldown_s=0.0)
            pilot = _pilot(
                [12, 1, 12], [good, bad], cooldown_s=3600.0
            )
            for _ in range(3):
                pilot.tick()
        finally:
            telemetry.uninstall_journal()
            journal.close()
        n_ok, errors = telemetry.validate_journal(path)
        assert errors == []
        import json

        types = [
            json.loads(line)["type"]
            for line in open(path)
            if line.strip()
        ]
        assert "autopilot_decision" in types
        assert "autopilot_rollback" in types
        assert "rule_quarantined" in types
        outcomes = {
            json.loads(line).get("outcome")
            for line in open(path)
            if line.strip()
        }
        assert {"applied", "rolled_back", "suppressed_cooldown"} <= outcomes

    def test_worker_thread_lifecycle(self):
        """start=True spawns the photon-autopilot worker; close joins
        it (the conftest leak guard enforces this fleet-wide)."""
        import threading

        pilot = Autopilot(
            _FakeRegistry(),
            rules=[],
            tick_ms=10,
            sensor_fn=lambda reg: _snap(0),
            start=True,
        )
        try:
            assert any(
                t.name == "photon-autopilot" for t in threading.enumerate()
            )
        finally:
            pilot.close()
        assert not any(
            t.name == "photon-autopilot" and t.is_alive()
            for t in threading.enumerate()
        )
        assert pilot.summary()["status"] == "stopped"
        pilot.close()  # idempotent
