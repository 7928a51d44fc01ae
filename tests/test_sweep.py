"""Pod-parallel hyperparameter sweep tests (ISSUE 12).

The batched trial executor's contract: trial-stacked and shard-group
evaluation are BITWISE-equal to the serial per-trial loop on the same
candidate matrix — cold rounds, warm-started rounds, and the explicit
warm-start-disabled parity mode — and the finalized winner is bitwise-equal
to a standalone fit of the winning configuration. Plus the executor's
operational surface: stack-plan splitting, mode choice via the sweep knobs,
and trial_start/trial_finish journal events.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from photon_ml_tpu.data.game_dataset import (
    FixedEffectDataConfig,
    GameDataset,
    RandomEffectDataConfig,
)
from photon_ml_tpu.estimators.game_estimator import GameEstimator
from photon_ml_tpu.hyperparameter import (
    HyperparameterConfig,
    HyperparameterTuningMode,
    SweepExecutor,
    get_tuner,
)
from photon_ml_tpu.optimize.config import (
    L2,
    CoordinateOptimizationConfig,
    OptimizerConfig,
)
from photon_ml_tpu.types import TaskType, VarianceComputationType
from photon_ml_tpu.utils import faults
from photon_ml_tpu.utils.contracts import ROBUSTNESS_CLEAN_ZERO_KEYS


def _make_data(n, n_entities, d_fixed=4, d_re=3, seed=0):
    r = np.random.default_rng(seed)
    entity = r.integers(0, n_entities, size=n)
    Xf = r.normal(size=(n, d_fixed)).astype(np.float32)
    Xe = r.normal(size=(n, d_re)).astype(np.float32)
    w = r.normal(size=d_fixed).astype(np.float32)
    u = r.normal(size=(n_entities, d_re)).astype(np.float32)
    margin = Xf @ w + np.einsum("nd,nd->n", Xe, u[entity])
    y = (r.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float32)
    return GameDataset.build(
        {"global": jnp.asarray(Xf), "per_entity": jnp.asarray(Xe)},
        y,
        id_tags={"entityId": entity},
    )


def _opt_config(max_iter=8, variance=VarianceComputationType.NONE):
    return CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=max_iter, tolerance=1e-7),
        regularization=L2,
        reg_weight=1.0,
    ) if variance == VarianceComputationType.NONE else (
        CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=max_iter, tolerance=1e-7),
            regularization=L2,
            reg_weight=1.0,
            variance_computation=variance,
        )
    )


_DATA_CFGS = {
    "fixed": FixedEffectDataConfig("global"),
    "re": RandomEffectDataConfig("entityId", "per_entity", min_bucket=4),
}


@pytest.fixture(scope="module")
def sweep_problem():
    return _make_data(96, 6, seed=1), _make_data(64, 6, seed=2)


def _executor(problem, mode, *, variance=VarianceComputationType.NONE,
              warm_start=True, max_stack=None, shard_groups=None,
              iterations=1, seed=4):
    train, val = problem
    est = GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        _DATA_CFGS,
        coordinate_descent_iterations=iterations,
        seed=seed,
    )
    base = {"fixed": _opt_config(variance=variance),
            "re": _opt_config(variance=variance)}
    return est, est.sweep_executor(
        train, val, base, mode=mode, warm_start=warm_start,
        max_stack=max_stack, shard_groups=shard_groups,
    )


def _assert_models_equal(a, b, what="", sharded_close=None):
    """Bitwise, unless `sharded_close` (conftest's `assert_sharded_close`)
    is handed in: then `b` ran a sharded program against `a`'s
    single-device one and the models agree to the `fit` tolerance."""
    assert len(a) == len(b)
    for i, (x, z) in enumerate(zip(a, b)):
        assert x.keys() == z.keys()
        for cid in x:
            for name in x[cid]:
                u, v = x[cid][name], z[cid][name]
                if u is None and v is None:
                    continue
                if sharded_close is not None:
                    sharded_close(v, u, "fit")
                    continue
                np.testing.assert_array_equal(
                    np.asarray(u),
                    np.asarray(v),
                    err_msg=f"{what} trial {i} {cid}/{name} not bitwise",
                )


_POINTS = np.array([[0.1, 0.5], [10.0, 0.02]])
_POINTS2 = np.array([[0.7, 1.5], [3.0, 0.2]])


class TestStackedParity:
    def test_stacked_matches_serial_bitwise_cold_and_warm(self, sweep_problem):
        _, ex_serial = _executor(sweep_problem, "serial")
        _, ex_stacked = _executor(sweep_problem, "stacked")
        vs1 = ex_serial.evaluate_batch(_POINTS)
        vt1 = ex_stacked.evaluate_batch(_POINTS)
        assert vs1 == vt1
        # warm-started round: the incumbent seeds every trial
        ms1, mt1 = ex_serial.last_trial_models, ex_stacked.last_trial_models
        _assert_models_equal(ms1, mt1, "cold round")
        vs2 = ex_serial.evaluate_batch(_POINTS2)
        vt2 = ex_stacked.evaluate_batch(_POINTS2)
        assert vs2 == vt2
        _assert_models_equal(
            ex_serial.last_trial_models,
            ex_stacked.last_trial_models,
            "warm round",
        )
        assert [t.mode for t in ex_stacked.trials] == ["stacked"] * 4

    def test_warm_start_disabled_parity(self, sweep_problem):
        """The explicit parity mode: every round cold, so round 2 results
        are independent of round 1's incumbent in BOTH modes."""
        _, ex_serial = _executor(sweep_problem, "serial", warm_start=False)
        _, ex_stacked = _executor(sweep_problem, "stacked", warm_start=False)
        ex_serial.evaluate_batch(_POINTS)
        ex_stacked.evaluate_batch(_POINTS)
        vs = ex_serial.evaluate_batch(_POINTS2)
        vt = ex_stacked.evaluate_batch(_POINTS2)
        assert vs == vt
        _assert_models_equal(
            ex_serial.last_trial_models, ex_stacked.last_trial_models,
            "warm-start-disabled",
        )
        # Cold rounds: a FRESH serial executor evaluating the same points
        # produces the same models — round 2 never saw round 1.
        _, ex_fresh = _executor(sweep_problem, "serial", warm_start=False)
        ex_fresh.evaluate_batch(_POINTS2)
        _assert_models_equal(
            ex_fresh.last_trial_models, ex_stacked.last_trial_models,
            "round independence",
        )

    def test_stacked_variance_parity(self, sweep_problem):
        """FE variances are recomputed post-dispatch through the serial
        `_variance_fn` program; RE variances ride the shared scan — both
        must be bitwise."""
        _, ex_serial = _executor(
            sweep_problem, "serial", variance=VarianceComputationType.SIMPLE
        )
        _, ex_stacked = _executor(
            sweep_problem, "stacked", variance=VarianceComputationType.SIMPLE
        )
        vs = ex_serial.evaluate_batch(_POINTS)
        vt = ex_stacked.evaluate_batch(_POINTS)
        assert vs == vt
        _assert_models_equal(
            ex_serial.last_trial_models, ex_stacked.last_trial_models,
            "variance",
        )
        for trial in ex_stacked.last_trial_models:
            assert trial["fixed"]["var"] is not None
            assert trial["re"]["v"] is not None

    def test_stack_plan_splits_rounds(self, sweep_problem):
        """k > max_stack splits into chunks; results identical to serial."""
        pts = np.array([[0.1, 0.5], [10.0, 0.02], [1.0, 1.0]])
        _, ex_serial = _executor(sweep_problem, "serial")
        _, ex_stacked = _executor(sweep_problem, "stacked", max_stack=2)
        vs = ex_serial.evaluate_batch(pts)
        vt = ex_stacked.evaluate_batch(pts)
        assert vs == vt
        _assert_models_equal(
            ex_serial.last_trial_models, ex_stacked.last_trial_models,
            "split round",
        )
        (dec,) = ex_stacked.stack_decisions
        assert dec["chunks"] == [2, 1]
        assert dec["k"] == 3 and dec["max_stack"] == 2
        assert dec["per_trial_bytes"] > 0


class TestShardGroupParity:
    def test_single_device_groups_bitwise(self, sweep_problem):
        """Default shard groups (one device each) run the serial loop's
        exact programs on other chips — bitwise, cold and warm rounds."""
        _, ex_serial = _executor(sweep_problem, "serial")
        _, ex_group = _executor(sweep_problem, "shard_group")
        assert ex_serial.evaluate_batch(_POINTS) == ex_group.evaluate_batch(_POINTS)
        _assert_models_equal(
            ex_serial.last_trial_models, ex_group.last_trial_models,
            "group cold",
        )
        assert ex_serial.evaluate_batch(_POINTS2) == ex_group.evaluate_batch(_POINTS2)
        _assert_models_equal(
            ex_serial.last_trial_models, ex_group.last_trial_models,
            "group warm",
        )
        assert [t.mode for t in ex_group.trials] == ["shard_group"] * 4

    def test_multi_device_groups_match_serial(
        self, sweep_problem, assert_sharded_close
    ):
        """Groups of >1 device: sample data replicated, RE store row-sharded
        (the PR 7 ring sweep inside the group) — another program than the
        serial loop's, so parity to the `fit` tolerance, cold and warm."""
        if len(jax.devices()) < 4:
            pytest.skip("needs >= 4 devices")
        _, ex_serial = _executor(sweep_problem, "serial")
        _, ex_group = _executor(sweep_problem, "shard_group", shard_groups=2)
        for points, what in ((_POINTS, "cold"), (_POINTS2, "warm")):
            assert_sharded_close(
                ex_group.evaluate_batch(points),
                ex_serial.evaluate_batch(points),
                "fit",
            )
            _assert_models_equal(
                ex_serial.last_trial_models, ex_group.last_trial_models,
                f"multi-dev {what}", sharded_close=assert_sharded_close,
            )
        assert [t.mode for t in ex_group.trials] == ["shard_group"] * 4


class TestExecutorSurface:
    def test_finalize_winner_bitwise_vs_standalone(self, sweep_problem):
        train, val = sweep_problem
        est, ex = _executor(sweep_problem, "stacked")
        ex.evaluate_batch(_POINTS)
        res = ex.finalize()
        assert res.best_trial in (0, 1)
        assert np.isfinite(res.winner_value)
        assert res.winner_refit_s >= 0
        # Standalone fit of the winning config through the estimator's own
        # serial path — the deliverable model must be bitwise-equal even
        # though the search itself warm-started and stacked trials.
        import dataclasses

        base = {"fixed": _opt_config(), "re": _opt_config()}
        win_cfg = {
            "fixed": dataclasses.replace(
                base["fixed"], reg_weight=float(res.best_point[0])
            ),
            "re": dataclasses.replace(
                base["re"], reg_weight=float(res.best_point[1])
            ),
        }
        standalone = est.fit(train, val, [win_cfg])[0]
        np.testing.assert_array_equal(
            np.asarray(res.winner_model["fixed"].coefficients.means),
            np.asarray(standalone.model["fixed"].coefficients.means),
        )
        np.testing.assert_array_equal(
            np.asarray(res.winner_model["re"].coefficients_matrix),
            np.asarray(standalone.model["re"].coefficients_matrix),
        )

    def test_mode_knob_forcing(self, sweep_problem, monkeypatch):
        _, ex = _executor(sweep_problem, None)
        # auto on a replicated store prefers stacking
        assert ex._choose_mode(2) == "stacked"
        monkeypatch.setenv("PHOTON_SWEEP_TRIAL_STACK", "0")
        assert ex._choose_mode(2) in ("shard_group", "serial")
        monkeypatch.setenv("PHOTON_SWEEP_TRIAL_STACK", "1")
        assert ex._choose_mode(2) == "stacked"

    def test_candidate_matrix_shape_validation(self, sweep_problem):
        _, ex = _executor(sweep_problem, "serial")
        with pytest.raises(ValueError, match="columns"):
            ex.evaluate_batch(np.ones((2, 3)))
        with pytest.raises(ValueError, match="unknown sweep mode"):
            _executor(sweep_problem, "bogus")

    def test_reset_keeps_programs(self, sweep_problem):
        _, ex = _executor(sweep_problem, "stacked")
        ex.evaluate_batch(_POINTS)
        programs = dict(ex._programs)
        assert programs
        ex.reset()
        assert ex.trials == [] and ex.rounds == 0 and ex._best is None
        assert ex._programs == programs

    def test_trial_journal_events(self, sweep_problem, tmp_path):
        from photon_ml_tpu.utils import telemetry

        journal = telemetry.RunJournal(str(tmp_path / "journal.jsonl"))
        telemetry.install_journal(journal)
        try:
            _, ex = _executor(sweep_problem, "serial")
            ex.evaluate_batch(_POINTS)
        finally:
            telemetry.uninstall_journal()
            journal.close()
        n_ok, errors = telemetry.validate_journal(str(tmp_path / "journal.jsonl"))
        assert errors == []
        import json

        lines = [
            json.loads(l)
            for l in open(tmp_path / "journal.jsonl")
            if l.strip()
        ]
        starts = [l for l in lines if l["type"] == "trial_start"]
        finishes = [l for l in lines if l["type"] == "trial_finish"]
        assert len(starts) == 2 and len(finishes) == 2
        assert {f["trial"] for f in finishes} == {0, 1}
        assert all(f["mode"] == "serial" for f in finishes)
        assert all(np.isfinite(f["value"]) for f in finishes)

    def test_all_rejected_trial_falls_back_to_zeros_in_every_mode(
        self, sweep_problem
    ):
        """A NaN reg weight drives every update of a coordinate non-finite:
        the divergence guard rejects them all, the serial loop keeps NO
        model for that coordinate, and the trial must report the zeros
        model (matching the stacked where-carry) instead of crashing."""
        bad = np.array([[np.nan, 1.0]])
        _, ex_serial = _executor(sweep_problem, "serial")
        _, ex_stacked = _executor(sweep_problem, "stacked")
        vs = ex_serial.evaluate_batch(bad)
        vt = ex_stacked.evaluate_batch(bad)
        assert vs == vt
        _assert_models_equal(
            ex_serial.last_trial_models, ex_stacked.last_trial_models,
            "all-rejected",
        )
        # Whether the degenerate solve is rejected (diverged) or resolves
        # to an accepted zeros step, the COUNT must be mode-invariant
        # (stacked charges 1 + PHOTON_SOLVE_RETRIES per rejection, the
        # serial attempt loop's own arithmetic).
        assert (
            ex_serial.trials[0].diverged_steps
            == ex_stacked.trials[0].diverged_steps
        )
        # The fallback itself, directly: a coordinate the serial loop kept
        # NO model for reports the zeros model instead of KeyError.
        from photon_ml_tpu.game.model import GameModel

        zeros = ex_serial._trial_arrays("fixed", GameModel({}))
        np.testing.assert_array_equal(np.asarray(zeros["w"]), 0.0)

    def test_tuner_sweep_drives_executor(self, sweep_problem):
        """HyperparameterTuner.sweep: batched Bayesian rounds through the
        executor, finalize() winner returned."""
        dims = [
            HyperparameterConfig("fixed", 1e-2, 1e2, transform="LOG"),
            HyperparameterConfig("re", 1e-2, 1e2, transform="LOG"),
        ]
        _, ex = _executor(sweep_problem, "stacked")
        tuner = get_tuner(HyperparameterTuningMode.BAYESIAN)
        out = tuner.sweep(
            4, dims, HyperparameterTuningMode.BAYESIAN, ex, seed=3,
            batch_size=2,
        )
        assert out is not None
        search_result, sweep_result = out
        assert len(search_result.observations) == 4
        assert len(sweep_result.trials) == 4
        assert ex.rounds == 2
        assert sweep_result.winner_model is not None
        # A clean sweep walks no robustness path (conftest zeroes the
        # counters before each test).
        assert not any(faults.COUNTERS.get(k) for k in ROBUSTNESS_CLEAN_ZERO_KEYS)
        assert sum(t.diverged_steps for t in sweep_result.trials) == 0
