"""The supervised control loop (ISSUE 19): sensors -> rules -> actuators.

`Autopilot` closes the loop the planner left open: PR 14 decides once at
startup from a persisted profile; this controller re-decides every
`PHOTON_AUTOPILOT_MS` from LIVE telemetry, driving the actuators that
already exist — the reshard orchestrator (shard grow / hot-row
rebalance), the tenant registry's HBM ladder (demote / restore), and the
planner's online-decision path (batch/wait retune) — under control-theory
hygiene:

* per-rule HYSTERESIS: a fired rule stays disarmed until its signal
  drops below the re-arm watermark, so a sawtooth crossing the fire
  band on every crest actuates once, not per crest;
* per-rule COOLDOWN (`PHOTON_AUTOPILOT_COOLDOWN_S`): a rule that just
  actuated holds, letting the fleet settle before it may move again;
* a bounded ACTION BUDGET (`PHOTON_AUTOPILOT_MAX_ACTIONS` per cooldown
  window) across all rules — a misbehaving policy set degrades to slow,
  never to thrashing;
* ONE actuator mutex: actions serialize with each other here, and each
  actuator additionally serializes with hot-swaps/refresh on its
  engine's own swap mutex — a model push and an autopilot reshard
  order, never race;
* every decision JOURNALED (`autopilot_decision` carrying the rule's
  evidence and the outcome — applied and suppressed alike);
* a POST-ACTION CONTRACT PROBE (bitwise spot-check + latency factor +
  zero failed requests): a regressing action is undone
  (`autopilot_rollback`, counter `autopilot_rollbacks`) and its rule is
  QUARANTINED (`rule_quarantined`, counter `autopilot_quarantines`)
  until an operator `reset_rule` — the controller can be wrong once per
  rule, silently never.

The `autopilot_act` fault site arms between a decision and its effect,
so every actuator path exercises the rollback machinery under injection.
"""

from __future__ import annotations

import collections
import logging
import threading
import time
from typing import Callable, Deque, Dict, List, Mapping, Optional

import numpy as np

from photon_ml_tpu.autopilot.rules import Action, ControlRule, default_rules
from photon_ml_tpu.autopilot.sensors import SensorSnapshot, read_sensors
from photon_ml_tpu.utils import faults, telemetry
from photon_ml_tpu.utils.contracts import AUTOPILOT_BLOCK_KEYS, TIER_TOLERANCES
from photon_ml_tpu.utils.knobs import get_knob

logger = logging.getLogger(__name__)

__all__ = ["Autopilot"]

# Decision outcomes the journal carries. "applied" is the only one that
# actuated; everything else explains why the loop held its hand.
OUTCOMES = (
    "applied",
    "suppressed_quarantined",
    "suppressed_cooldown",
    "suppressed_budget",
    "rolled_back",
)


class Autopilot:
    """The closed-loop controller over one TenantRegistry fleet.

    Construction arms nothing by itself: `start=True` (default) spawns
    the `photon-autopilot` worker ticking every `tick_ms`; `start=False`
    leaves the loop inert for deterministic drive via `tick()` (the
    tests). Explicit ctor args win; None defers to the PHOTON_AUTOPILOT_*
    knobs — the same deferral every serving ctor uses.

    `probe_requests` maps tenant name -> a ScoreRequest whose answers
    must stay BITWISE across any action (all built-in actions except the
    precision ladder are bitwise-neutral by construction; ladder steps
    are held to the pinned TIER_TOLERANCES for the rung instead);
    without it the probe still checks failed-request and latency
    regressions.
    """

    def __init__(
        self,
        registry,
        *,
        rules: Optional[List[ControlRule]] = None,
        tick_ms: Optional[int] = None,
        cooldown_s: Optional[float] = None,
        max_actions: Optional[int] = None,
        probe_requests: Optional[Mapping[str, object]] = None,
        probe_factor: float = 5.0,
        probe_floor_ms: float = 50.0,
        sensor_fn: Optional[Callable[[object], SensorSnapshot]] = None,
        start: bool = True,
    ):
        self.registry = registry
        self.rules: List[ControlRule] = (
            list(rules) if rules is not None else default_rules()
        )
        names = [r.name for r in self.rules]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate rule names: {sorted(names)}")
        self.tick_ms = (
            int(get_knob("PHOTON_AUTOPILOT_MS"))
            if tick_ms is None
            else int(tick_ms)
        )
        self.cooldown_s = (
            float(get_knob("PHOTON_AUTOPILOT_COOLDOWN_S"))
            if cooldown_s is None
            else float(cooldown_s)
        )
        self.max_actions = (
            int(get_knob("PHOTON_AUTOPILOT_MAX_ACTIONS"))
            if max_actions is None
            else int(max_actions)
        )
        if self.tick_ms < 1:
            raise ValueError("tick_ms must be >= 1")
        if self.max_actions < 1:
            raise ValueError("max_actions must be >= 1")
        self._probe_requests = dict(probe_requests or {})
        self._probe_factor = float(probe_factor)
        self._probe_floor_ms = float(probe_floor_ms)
        self._sensor_fn = sensor_fn if sensor_fn is not None else read_sensors
        # ONE actuator mutex: decisions may evaluate concurrently with a
        # manual tick(), but actuations serialize here (and each actuator
        # serializes with hot-swaps on its engine's swap mutex inside).
        self._act_lock = threading.Lock()
        self._cv = threading.Condition()
        self._stop = False
        self._prev: Optional[SensorSnapshot] = None
        # The action-budget window: monotonic stamps of applied actions,
        # pruned to the budget window width on every check.
        self._window: Deque[float] = collections.deque()
        self._ticks = 0
        self._decisions = 0
        self._actions = 0
        self._suppressed = 0
        self._rollbacks = 0
        self._last_outcome: Optional[str] = None
        self._worker: Optional[threading.Thread] = None
        if start:
            self._worker = threading.Thread(
                target=self._run, name="photon-autopilot", daemon=True
            )
            self._worker.start()

    # ------------------------------------------------------------ lifecycle

    def _run(self) -> None:
        while True:
            with self._cv:
                if self._stop:
                    return
                self._cv.wait(timeout=self.tick_ms / 1e3)
                if self._stop:
                    return
            try:
                self.tick()
            except Exception:  # noqa: BLE001 - the loop must survive a tick
                logger.exception("autopilot tick failed; loop continues")

    def close(self) -> None:
        """Stop the loop and join the worker. Idempotent."""
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        w, self._worker = self._worker, None
        if w is not None:
            w.join(timeout=30.0)

    def __enter__(self) -> "Autopilot":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # ----------------------------------------------------------------- tick

    def tick(self) -> SensorSnapshot:
        """One synchronous control-loop pass: read sensors, evaluate
        every rule against (current, previous). Returns the snapshot it
        acted on — the deterministic drive for tests."""
        cur = self._sensor_fn(self.registry)
        prev, self._prev = self._prev, cur
        self._ticks += 1
        for rule in self.rules:
            try:
                self._evaluate(rule, cur, prev)
            except Exception:  # noqa: BLE001 - one rule must not kill the pass
                logger.exception("rule %r evaluation failed", rule.name)
        return cur

    def _evaluate(
        self,
        rule: ControlRule,
        cur: SensorSnapshot,
        prev: Optional[SensorSnapshot],
    ) -> None:
        sig = rule.signal(cur, prev)
        if sig is None:
            return
        sig = float(sig)
        if not rule.armed:
            # Hysteresis: below the re-arm watermark the rule re-arms
            # (silently — re-arming is not a decision); anywhere above
            # it, a disarmed rule holds without journaling, else every
            # tick of a persistently-high signal floods the journal.
            if sig <= rule.rearm_below:
                rule.armed = True
            return
        if sig < rule.fire_above:
            return
        evidence = {
            "signal": sig,
            "fire_above": rule.fire_above,
            "rearm_below": rule.rearm_below,
        }
        if rule.quarantined:
            self._record(rule, None, evidence, "suppressed_quarantined")
            return
        cooldown = (
            rule.cooldown_s if rule.cooldown_s is not None else self.cooldown_s
        )
        now = time.monotonic()
        if (
            cooldown > 0
            and rule.last_actuated is not None
            and now - rule.last_actuated < cooldown
        ):
            self._record(
                rule,
                None,
                {**evidence, "cooldown_s": cooldown},
                "suppressed_cooldown",
            )
            return
        window_s = self.cooldown_s if self.cooldown_s > 0 else 1.0
        while self._window and now - self._window[0] > window_s:
            self._window.popleft()
        if len(self._window) >= self.max_actions:
            self._record(
                rule,
                None,
                {**evidence, "budget": self.max_actions,
                 "window_s": window_s},
                "suppressed_budget",
            )
            return
        action = rule.decide(cur, prev, sig)
        if action is None:
            return  # declined: a hold, not a decision
        action = Action(
            kind=action.kind,
            tenant=action.tenant,
            params=action.params,
            evidence={**evidence, **action.evidence},
            apply_fn=action.apply_fn,
            undo_fn=action.undo_fn,
        )
        rule.armed = False  # fired — disarmed until the signal re-arms it
        self._actuate(rule, action)

    # ------------------------------------------------------------ actuation

    def _actuate(self, rule: ControlRule, action: Action) -> None:
        now = time.monotonic()
        undo: Optional[Callable[[], None]] = None
        with self._act_lock:
            pre = self._probe()
            try:
                faults.fault_point("autopilot_act")
                undo = self._apply(action)
            except BaseException as exc:  # noqa: BLE001 - rollback + quarantine
                self._rollback(
                    rule, action, f"actuation failed: {exc}", None
                )
                return
            post = self._probe()
            regression = self._probe_regressed(pre, post, action)
            if regression is not None:
                self._rollback(rule, action, regression, undo)
                return
        rule.last_actuated = now
        self._window.append(now)
        self._actions += 1
        telemetry.METRICS.increment("autopilot_actions")
        self._record(rule, action, action.evidence, "applied")

    def _apply(self, action: Action) -> Optional[Callable[[], None]]:
        """Dispatch one action to its actuator; returns the undo closure
        that restores the pre-action arrangement."""
        if action.apply_fn is not None:
            action.apply_fn()
            return action.undo_fn
        kind = action.kind
        if kind == "reshard":
            return self._apply_reshard(action)
        if kind == "rebalance":
            t = self.registry.tenant(action.tenant)
            t.engine.reshard_orchestrator.rebalance(action.params["cid"])
            # A rebalance is bitwise-neutral tier placement from observed
            # stats; "undoing" it would re-place from the same stats —
            # there is no prior arrangement to restore.
            return None
        if kind == "demote":
            name = action.tenant
            self.registry.demote(
                name,
                hot_rows=int(action.params.get("hot_rows", 0)),
                reason="autopilot",
            )
            return lambda: self.registry.restore(
                name, reason="autopilot-rollback"
            )
        if kind == "restore":
            name = action.tenant
            self.registry.restore(name, reason="autopilot")
            return lambda: self.registry.demote(
                name, reason="autopilot-rollback"
            )
        if kind == "tier_demote":
            return self._apply_tier_demote(action)
        if kind == "tier_restore":
            name = action.tenant
            prior = getattr(self.registry.tenant(name), "tier", "f32")
            self.registry.restore_tier(
                name, to=str(action.params.get("to", "f32")), reason="autopilot"
            )
            return lambda: self.registry.demote_tier(
                name, to=prior, reason="autopilot-rollback"
            )
        if kind == "retune":
            return self._apply_retune(action)
        raise ValueError(f"unknown action kind {kind!r}")

    def _apply_tier_demote(self, action: Action) -> Callable[[], None]:
        from photon_ml_tpu.serving.tenancy import TierErrorCeilingExceeded

        name = action.tenant
        prior = getattr(self.registry.tenant(name), "tier", "f32")
        try:
            self.registry.demote_tier(
                name, to=action.params.get("to"), reason="autopilot"
            )
        except TierErrorCeilingExceeded:
            # The quantize rung would breach the characterized error
            # ceiling — relieve the pressure through the bitwise host
            # tier instead, exactly what the valve does.
            self.registry.demote(name, reason="autopilot")
            return lambda: self.registry.restore(
                name, reason="autopilot-rollback"
            )
        return lambda: self.registry.restore_tier(
            name, to=prior, reason="autopilot-rollback"
        )

    def _apply_reshard(self, action: Action) -> Callable[[], None]:
        import jax

        from photon_ml_tpu.parallel.mesh import make_mesh

        t = self.registry.tenant(action.tenant)
        orch = t.engine.reshard_orchestrator
        old_sharded = any(
            c.mesh is not None
            for c in t.engine._state.bundle.coordinates.values()
        )
        n = action.params.get("devices")
        devs = jax.devices()
        n = len(devs) if n is None else max(1, min(int(n), len(devs)))
        new_mesh = make_mesh(devs[:n]) if n > 1 else None
        orch.reshard(new_mesh)

        def _undo() -> None:
            # Back to the pre-action layout: replicated unless the rows
            # were already mesh-sharded before this grow.
            orch.reshard(make_mesh(devs) if old_sharded else None)

        return _undo

    def _apply_retune(self, action: Action) -> Optional[Callable[[], None]]:
        from photon_ml_tpu import planner

        value = float(action.params["serving_max_wait_ms"])
        decision = planner.apply_online_decision(
            "serving_max_wait_ms",
            value,
            evidence=dict(action.evidence),
        )
        if decision is None:
            # An explicit knob pins the quantity — precedence says hold.
            return None
        prev = self.registry.retune(max_wait_ms=value)

        def _undo() -> None:
            planner.apply_online_decision(
                "serving_max_wait_ms",
                decision.fallback,
                evidence={"rollback_of": value},
            )
            self.registry.retune(max_wait_ms=prev["max_wait_ms"])

        return _undo

    # ---------------------------------------------------------------- probe

    def _probe(self) -> Dict[str, object]:
        """The contract probe: per-tenant failed-request counts, and for
        each probe request the bitwise scores + best-of-3 wall.

        Precision-ladder actions (`tier_demote`/`tier_restore`) are the
        one characterized exception: their scores are compared under the
        pinned ``TIER_TOLERANCES`` for the coarser rung involved instead
        of bitwise — quantization deliberately trades the bitwise
        contract for a characterized one."""
        failed = {}
        for name in self.registry.tenant_names:
            try:
                failed[name] = self.registry.tenant(name).failed
            except KeyError:
                continue
        probes: Dict[str, Dict[str, object]] = {}
        for name, req in self._probe_requests.items():
            if name not in failed:
                continue
            walls = []
            scores = None
            for _ in range(3):
                t0 = time.monotonic()
                res = self.registry.score(name, req)
                walls.append(time.monotonic() - t0)
                scores = np.asarray([res.score, res.mean], np.float64)
            probes[name] = {"scores": scores, "wall_s": min(walls)}
        return {"failed": failed, "probes": probes}

    def _probe_regressed(
        self,
        pre: Dict[str, object],
        post: Dict[str, object],
        action: Optional[Action] = None,
    ) -> Optional[str]:
        """None when the post-action probe holds the contract, else the
        human-readable regression reason."""
        tol = self._probe_tolerance(action)
        for name, n_pre in pre["failed"].items():
            n_post = post["failed"].get(name, n_pre)
            if n_post > n_pre:
                return (
                    f"failed requests regressed for tenant {name!r} "
                    f"({n_pre} -> {n_post})"
                )
        for name, p in pre["probes"].items():
            q = post["probes"].get(name)
            if q is None:
                continue
            if tol is not None:
                if not np.allclose(
                    q["scores"],
                    p["scores"],
                    rtol=tol["rtol"],
                    atol=tol["atol"],
                ):
                    return (
                        "characterized spot-check failed for tenant "
                        f"{name!r}"
                    )
            elif not np.array_equal(p["scores"], q["scores"]):
                return f"bitwise spot-check failed for tenant {name!r}"
            bound = max(
                p["wall_s"] * self._probe_factor,
                p["wall_s"] + self._probe_floor_ms / 1e3,
            )
            if q["wall_s"] > bound:
                return (
                    f"probe latency regressed for tenant {name!r} "
                    f"({p['wall_s'] * 1e3:.2f}ms -> "
                    f"{q['wall_s'] * 1e3:.2f}ms)"
                )
        return None

    @staticmethod
    def _probe_tolerance(
        action: Optional[Action],
    ) -> Optional[Dict[str, float]]:
        """The pinned tolerance a precision-ladder action's probe scores
        are held to, or None for the default bitwise contract. Uses the
        coarser of the from/to rungs — a restore's PRE probe answered on
        the quantized generation."""
        if action is None or action.kind not in (
            "tier_demote",
            "tier_restore",
        ):
            return None
        order = {"f32": 0, "bf16": 1, "int8": 2}
        rungs = [
            str(action.params.get("to", "f32")),
            str(action.evidence.get("from_tier", "f32")),
        ]
        rung = max(
            (r for r in rungs if r in order),
            key=lambda r: order[r],
            default="int8",
        )
        return TIER_TOLERANCES[rung]

    # ----------------------------------------------- rollback / quarantine

    def _rollback(
        self,
        rule: ControlRule,
        action: Action,
        reason: str,
        undo: Optional[Callable[[], None]],
    ) -> None:
        if undo is not None:
            try:
                undo()
            except Exception:  # noqa: BLE001 - journal it; never raise out
                logger.exception(
                    "rollback of %r (%s) itself failed", rule.name, action.kind
                )
        self._rollbacks += 1
        rule.rollbacks += 1
        faults.COUNTERS.increment("autopilot_rollbacks")
        telemetry.emit_event(
            "autopilot_rollback",
            rule=rule.name,
            action=action.describe(),
            reason=reason,
        )
        self._record(rule, action, action.evidence, "rolled_back")
        # One rollback quarantines the rule: the controller may be wrong
        # once per rule; a repeat needs an operator's reset_rule.
        if not rule.quarantined:
            rule.quarantined = True
            faults.COUNTERS.increment("autopilot_quarantines")
            telemetry.emit_event(
                "rule_quarantined",
                rule=rule.name,
                reason=reason,
                rollbacks=rule.rollbacks,
            )
            logger.warning(
                "autopilot rule %r quarantined after rollback: %s",
                rule.name,
                reason,
            )

    def reset_rule(self, name: str) -> None:
        """Operator reset: lift a rule's quarantine and re-arm it. The
        ONLY path out of quarantine — the loop never self-forgives."""
        for rule in self.rules:
            if rule.name == name:
                rule.quarantined = False
                rule.armed = True
                logger.info("autopilot rule %r reset by operator", name)
                return
        raise KeyError(
            f"unknown rule {name!r} (rules: {[r.name for r in self.rules]})"
        )

    # ------------------------------------------------------------ reporting

    def _record(
        self,
        rule: ControlRule,
        action: Optional[Action],
        evidence: Mapping[str, object],
        outcome: str,
    ) -> None:
        assert outcome in OUTCOMES, outcome
        self._decisions += 1
        self._last_outcome = outcome
        if outcome.startswith("suppressed"):
            self._suppressed += 1
            telemetry.METRICS.increment("autopilot_suppressed")
        telemetry.emit_event(
            "autopilot_decision",
            rule=rule.name,
            action=action.describe() if action is not None else None,
            evidence=dict(evidence),
            outcome=outcome,
        )

    def summary(self) -> Dict[str, object]:
        """The `autopilot` block (contracts.AUTOPILOT_BLOCK_KEYS, in
        order) serving-summary.json carries."""
        block = dict(
            zip(
                AUTOPILOT_BLOCK_KEYS,
                (
                    "stopped" if self._stop or self._worker is None
                    else "running",
                    self._ticks,
                    [r.name for r in self.rules],
                    self._decisions,
                    self._actions,
                    self._suppressed,
                    self._rollbacks,
                    [r.name for r in self.rules if r.quarantined],
                    self.tick_ms,
                    self.cooldown_s,
                    self.max_actions,
                    self._last_outcome,
                ),
            )
        )
        assert set(block) == set(AUTOPILOT_BLOCK_KEYS)
        return block
