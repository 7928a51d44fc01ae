"""The fit path's stages and counts (ISSUE 27): one boundary primitive with
three sinks, stages at every boundary of a fit, objective evaluations counted
where they happen, and the program's spans on the profiler's clock."""

import dataclasses
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data.game_dataset import (
    FixedEffectDataConfig,
    GameDataset,
    RandomEffectDataConfig,
    build_random_effect_dataset,
)
from photon_ml_tpu.estimators.game_estimator import GameEstimator
from photon_ml_tpu.evaluation.suite import EvaluatorType
from photon_ml_tpu.game.coordinate import (
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent
from photon_ml_tpu.optimize.config import (
    CoordinateOptimizationConfig,
    OptimizerConfig,
)
from photon_ml_tpu.types import OptimizerType, TaskType
from photon_ml_tpu.utils import faults, telemetry
from photon_ml_tpu.utils.contracts import (
    PREPARE_STAGES,
    SOLVE_STAGE_PARENT,
    SOLVE_STAGES,
)
from photon_ml_tpu.utils.observability import (
    CoordinateUpdateEvent,
    EventEmitter,
    TimingRegistry,
    stage_scope,
    stage_timer,
)

TASK = TaskType.LOGISTIC_REGRESSION


def _glmix(n=6000, n_val=1500, n_entities=120, d=8, seed=5):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n + n_val, d)).astype(np.float32)
    y = (rng.uniform(size=n + n_val) > 0.5).astype(np.float32)
    ents = rng.integers(0, n_entities, size=n + n_val).astype(str)
    build = lambda a, b: GameDataset.build(
        {"g": X[a:b]}, y[a:b], id_tags={"e": ents[a:b]}
    )
    return build(0, n), build(n, n + n_val)


def _estimator(emitter=None, cd_iterations=2, **kwargs):
    est = GameEstimator(
        TASK,
        {
            "global": FixedEffectDataConfig("g"),
            "per-e": RandomEffectDataConfig("e", "g", min_bucket=8),
        },
        coordinate_descent_iterations=cd_iterations,
        validation_evaluators=[EvaluatorType.parse("AUC")],
        event_emitter=emitter,
        pipeline=False,
        **kwargs,
    )
    cfg = {
        cid: CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=4)
        )
        for cid in ("global", "per-e")
    }
    return est, cfg


def _children(parent):
    return [s for s in SOLVE_STAGES if SOLVE_STAGE_PARENT[s] == parent]


class TestStagesOfAFit:
    def test_stages_tile_the_fit_and_nest(self):
        train, val = _glmix()
        est, cfg = _estimator()
        est.fit(train, val, [cfg])  # compiles
        est.fit(train, val, [cfg])
        stages = est.fit_timing["stages_s"]
        assert list(stages) == list(SOLVE_STAGES)
        assert all(isinstance(v, float) for v in stages.values())
        fit = stages["fit"]
        top = sum(stages[s] for s in _children("fit"))
        assert abs(fit - top) <= 0.05 * fit, (fit, top, stages)
        for parent in SOLVE_STAGES:
            inside = sum(stages[s] for s in _children(parent))
            assert inside <= stages[parent] * (1 + 1e-6) + 1e-9, (parent, stages)
        # Two sweeps of two coordinates, validated after each update.
        idle = {s for s in SOLVE_STAGES if stages[s] == 0.0}
        assert idle == {"cd/checkpoint"}  # no checkpoint_dir: it alone did not run

    def test_fit_timing_is_fed_from_the_stages(self):
        train, val = _glmix()
        est, cfg = _estimator()
        result = est.fit(train, val, [cfg])[0]
        ft = est.fit_timing
        stages = ft["stages_s"]
        assert ft["prepare_s"] == pytest.approx(
            stages["fit/revalidate"] + stages["fit/validation_prep"] + stages["fit/coordinates"]
        )
        assert ft["solve_s"] == pytest.approx(
            stages["fit/descent"] + stages["fit/final_evaluate"]
        )
        assert sum(result.timing.values()) == pytest.approx(stages["coordinate_update"])
        assert set(result.timing) == {
            f"{cid}/iter{it}" for cid in ("global", "per-e") for it in (0, 1)
        }
        # The prepare stages tile prepare_s as they did: the solve's stages
        # are in a registry of the fit's own, not the estimator's.
        tiled = sum(ft[k] for k in (*PREPARE_STAGES, "other"))
        assert abs(tiled - ft["prepare_s"]) <= 0.05 * ft["prepare_s"]
        assert sum(ft[k] for k in PREPARE_STAGES) <= ft["prepare_s"]
        assert not set(SOLVE_STAGES) & set(est.timing_registry.sections)
        assert not set(PREPARE_STAGES) & set(stages)

    def test_checkpoint_stage_runs_where_there_is_a_checkpoint(self, tmp_path):
        train, val = _glmix(n=1500, n_val=400)
        est, cfg = _estimator(cd_iterations=1, checkpoint_dir=str(tmp_path / "ckpt"))
        est.fit(train, val, [cfg])
        assert est.fit_timing["stages_s"]["cd/checkpoint"] > 0.0

    def test_every_fit_lands_in_the_process_registry(self):
        train, val = _glmix(n=1500, n_val=400)
        est, cfg = _estimator(cd_iterations=1)
        per_fit, evals = [], []
        for _ in range(3):
            est.fit(train, val, [cfg])
            per_fit.append(dict(est.fit_timing["stages_s"]))
            evals.append(dict(est.fit_timing["fn_evals"]))
        recorded = telemetry.METRICS.labeled_histograms("fit_stage_s")
        assert set(recorded) == {f"stage={s}" for s in SOLVE_STAGES}
        for stage in SOLVE_STAGES:
            snap = recorded[f"stage={stage}"]
            assert snap["count"] == 3
            assert snap["sum"] == pytest.approx(sum(f[stage] for f in per_fit))
        counted = telemetry.METRICS.labeled_counters("objective_evaluations")
        assert counted == {
            "coordinate=global,kind=fixed": sum(e["global"] for e in evals),
            "coordinate=per-e,kind=random": sum(e["per-e"] for e in evals),
        }
        assert evals[0] == evals[1] == evals[2]  # the same fit three times


@dataclasses.dataclass(frozen=True)
class _ValidatedFit:
    """One fit, and what its final evaluation must be made of."""

    coordinates: tuple = ("global",)
    passes: int = 1
    configurations: int = 1
    validated: bool = True
    locked: tuple = ()
    warm_start: bool = False  # an earlier fit's model handed in
    fault: str = ""  # a `faults.inject` plan armed round the fit
    resumed: bool = False  # the fit's checkpoint is an earlier, finished fit's
    evaluations: int = 1  # `evaluation_calls` the fit adds
    reused: tuple = (True,)  # a configuration: the descent's result, or the fallback's
    diverged_steps: int = 0


_VALIDATED_FITS = {
    "one_fixed_effect_one_pass": _ValidatedFit(),
    # An evaluation after each of the four updates, and no fifth.
    "fixed_and_random_two_passes": _ValidatedFit(
        coordinates=("global", "per-e"), passes=2, evaluations=4
    ),
    "locked_coordinate_and_warm_start": _ValidatedFit(
        coordinates=("global", "per-e"), locked=("global",), warm_start=True
    ),
    "two_configurations": _ValidatedFit(
        configurations=2, evaluations=2, reused=(True, True)
    ),
    # Both attempts of the only update are refused: the descent hands back
    # the warm start, which it never evaluated.
    "every_attempt_rejected": _ValidatedFit(
        warm_start=True, fault="solve@1+2", reused=(False,), diverged_steps=2
    ),
    # The second pass loses the mesh, rolls back and is then refused: the
    # first pass's validation is not handed on across the rollback.
    "mesh_lost_and_the_replay_rejected": _ValidatedFit(
        passes=2, fault="mesh_loss@2,solve@2+3", evaluations=2, reused=(False,),
        diverged_steps=2,
    ),
    "resumed_from_a_finished_checkpoint": _ValidatedFit(resumed=True, reused=(False,)),
    "no_validation_data": _ValidatedFit(validated=False, evaluations=0),
}


class TestAValidatedModelIsEvaluatedOnce:
    """ISSUE 41: `fit/final_evaluate` hands back the descent's last
    validation wherever that was made on the models the descent returns,
    and scores and evaluates only where there is none."""

    @staticmethod
    def _estimator(case, **kwargs):
        data_configs = {
            "global": FixedEffectDataConfig("g"),
            "per-e": RandomEffectDataConfig("e", "g", min_bucket=8),
        }
        return GameEstimator(
            TASK,
            {cid: data_configs[cid] for cid in case.coordinates},
            coordinate_descent_iterations=case.passes,
            validation_evaluators=[EvaluatorType.parse("AUC")],
            pipeline=False,
            **kwargs,
        )

    @pytest.mark.parametrize("name", list(_VALIDATED_FITS))
    def test_final_evaluation_is_the_descents_last_validation(
        self, name, monkeypatch, tmp_path
    ):
        from photon_ml_tpu.estimators import game_estimator

        case = _VALIDATED_FITS[name]
        train, val = _glmix(n=1500, n_val=400)

        def configuration(ci, coordinates):
            return {
                cid: CoordinateOptimizationConfig(
                    optimizer=OptimizerConfig(max_iterations=3), reg_weight=1.0 + ci
                )
                for cid in coordinates
            }

        kwargs = {}
        if case.resumed:
            kwargs["checkpoint_dir"] = str(tmp_path / "ckpt")
        earlier = None
        if case.warm_start or case.resumed:
            earlier = self._estimator(case, **kwargs).fit(
                train, val, [configuration(0, case.coordinates)]
            )[0]
        trained = [c for c in case.coordinates if c not in case.locked]
        cfgs = [configuration(ci, trained) for ci in range(case.configurations)]
        est = self._estimator(case, locked_coordinates=set(case.locked), **kwargs)

        descents = []
        descend = game_estimator.run_coordinate_descent
        monkeypatch.setattr(
            game_estimator,
            "run_coordinate_descent",
            lambda *a, **k: descents.append(descend(*a, **k)) or descents[-1],
        )
        tracer = telemetry.install_tracer(telemetry.Tracer())
        calls = telemetry.METRICS.get_counter("evaluation_calls")
        try:
            with faults.inject(case.fault):
                results = est.fit(
                    train,
                    val if case.validated else None,
                    cfgs,
                    initial_model=earlier.model if case.warm_start else None,
                )
        finally:
            telemetry.uninstall_tracer()
        calls = telemetry.METRICS.get_counter("evaluation_calls") - calls
        assert calls == case.evaluations
        assert len(results) == len(descents) == case.configurations
        stages = est.fit_timing["stages_s"]
        assert stages["fit/final_evaluate"] > 0.0  # the stage is still a stage
        assert est.fit_timing["solve_s"] == pytest.approx(
            stages["fit/descent"] + stages["fit/final_evaluate"]
        )
        if not case.validated:
            assert [r.evaluation for r in results] == [None]
            assert descents[0].evaluation is None
            assert descents[0].validation_history == []
            return

        final_spans = [s for s in tracer.spans() if s["name"] == "fit/final_evaluate"]
        assert [s["args"]["reused"] for s in final_spans] == list(case.reused)
        suite = est._validation_suite(val)
        for result, cd, reused in zip(results, descents, case.reused):
            assert list(result.evaluation.results) == ["AUC"]
            if reused:
                # The object best-model selection compared, not a copy of it.
                assert result.evaluation is cd.evaluation is cd.validation_history[-1][2]
            else:
                assert cd.evaluation is None
            # What `transformer.evaluate` reads on the returned model: the
            # same scores, offsets and program. With two coordinates the
            # descent sums the scores in its own order and `transform` in
            # the model's; on these rows the two AUCs came out equal to the
            # last bit all the same.
            again = est._make_transformer(result.model).evaluate(val, suite)
            assert again.results == result.evaluation.results
        assert est.fit_timing["diverged_steps"] == case.diverged_steps
        assert descents[0].mesh_losses == case.fault.count("mesh_loss")
        if earlier is not None and not any(case.reused):
            # Nothing was trained: the earlier fit's model, evaluated anew.
            assert results[0].evaluation.results == earlier.evaluation.results
        if case.resumed:
            assert descents[0].timing == {}  # every step was already done

    def test_a_model_of_no_coordinate_of_the_call_voids_the_result(self):
        """The validation sums the scores of the call's coordinates; a
        model handed in for another is in the returned `GameModel` and in
        no sum, so the last validation is not that model's evaluation."""
        from photon_ml_tpu.evaluation.suite import EvaluationSuite
        from photon_ml_tpu.game.model import GameModel

        train, _ = _glmix(n=1500, n_val=10)
        cfg = CoordinateOptimizationConfig(optimizer=OptimizerConfig(max_iterations=3))
        coord = FixedEffectCoordinate(train, "g", cfg, TASK)
        validated = dict(
            validation_scorer=lambda cid, model: coord.score(model),
            validation_suite=EvaluationSuite([EvaluatorType.parse("AUC")], train.labels),
        )
        own = run_coordinate_descent({"global": coord}, 1, **validated)
        assert own.evaluation is own.validation_history[-1][2]
        stranger = GameModel({"elsewhere": own.model["global"]})
        cd = run_coordinate_descent(
            {"global": coord}, 1, initial_models=stranger, **validated
        )
        assert set(cd.model.coordinate_ids) == {"global", "elsewhere"}
        assert len(cd.validation_history) == 1 and cd.evaluation is None


class TestEvaluationsCountedWhereTheyHappen:
    @pytest.mark.parametrize("optimizer", [OptimizerType.LBFGS, OptimizerType.TRON])
    def test_event_carries_the_optimizers_own_count(self, optimizer):
        train, _ = _glmix(n=2000, n_val=10)
        cfg = CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(optimizer_type=optimizer, max_iterations=6),
            reg_weight=1.0,
        )
        coord = FixedEffectCoordinate(train, "g", cfg, TASK)
        _, res = coord.train(train.offsets)
        events = []
        cd = run_coordinate_descent(
            {"global": coord}, 1, on_event=lambda etype, **f: events.append((etype, f))
        )
        (etype, fields), = events
        assert etype == "coordinate"
        assert fields["fn_evals"] == int(res.fn_evals) > int(res.iterations)
        assert cd.fn_evals == {"global": int(res.fn_evals)}
        event = CoordinateUpdateEvent(**fields)
        assert event.fn_evals == int(res.fn_evals)
        assert CoordinateUpdateEvent().fn_evals is None

    def test_estimator_events_and_fit_timing_agree(self):
        train, val = _glmix(n=1500, n_val=400)
        emitter, seen = EventEmitter(), []
        emitter.register(seen.append, CoordinateUpdateEvent)
        est, cfg = _estimator(emitter=emitter)
        est.fit(train, val, [cfg])
        by_coordinate = {}
        for e in seen:
            by_coordinate[e.coordinate] = by_coordinate.get(e.coordinate, 0) + e.fn_evals
        assert by_coordinate == est.fit_timing["fn_evals"]
        assert all(isinstance(v, int) for v in by_coordinate.values())


class TestRejectedTrialsRideTheOneFetch:
    """ISSUE 30: `line_search_rejected_trials` is the solve's evaluations less
    its first and one an iteration, read in the fetch the guard makes."""

    @pytest.mark.parametrize("optimizer", [OptimizerType.LBFGS, OptimizerType.TRON])
    def test_fixed_effect_count_and_a_single_fetch(self, monkeypatch, optimizer):
        from photon_ml_tpu.game import coordinate_descent

        train, _ = _glmix(n=2000, n_val=10)
        cfg = CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(optimizer_type=optimizer, max_iterations=6),
            reg_weight=1.0,
        )
        coord = FixedEffectCoordinate(train, "g", cfg, TASK)
        _, res = coord.train(train.offsets)
        fetched = []
        device_get = jax.device_get
        monkeypatch.setattr(
            coordinate_descent.jax,
            "device_get",
            lambda tree: fetched.append(len(tree)) or device_get(tree),
        )
        cd = run_coordinate_descent({"global": coord}, 1)
        if optimizer == OptimizerType.TRON:
            # ok, fn_evals, iterations, hv_evals: still one fetch an update
            assert fetched == [4]
            assert cd.line_search_rejected == {}
            hv = int(res.hv_evals)
            rejected = int(res.fn_evals) - 1 - int(res.iterations) - hv
            assert cd.tron == {
                "global": {
                    "accepted": int(res.iterations),
                    "rejected": rejected,
                    "hessian_vector_products": hv,
                    "kernel": "xla",
                }
            }
            assert hv >= int(res.iterations) > 0 and rejected >= 0
        else:
            assert fetched == [3]  # ok, fn_evals, iterations
            rejected = int(res.fn_evals) - 1 - int(res.iterations)
            assert cd.line_search_rejected == {"global": rejected}
            assert cd.tron == {} and res.hv_evals is None
            assert rejected >= 0

    def test_random_effect_count_is_by_solve(self):
        ds, red, coord = TestRandomEffectSolveStats()._coordinate(n_entities=40)
        _, stats = coord.train(ds.offsets)
        assert stats.solves == sum(b.num_entities for b in red.buckets)
        its, evals = coord.entity_counts(stats)
        by_entity = int((evals - its).sum()) - stats.solves
        cd = run_coordinate_descent({"per-entity": coord}, 1)
        assert cd.line_search_rejected == {"per-entity": by_entity}
        assert by_entity == stats.fn_evals - stats.solves - stats.iterations >= 0

    def test_a_fit_publishes_the_count(self):
        train, val = _glmix(n=1500, n_val=400)
        est, cfg = _estimator(cd_iterations=1)
        before = telemetry.METRICS.labeled_counters("line_search_rejected_trials")
        est.fit(train, val, [cfg])
        rejected = est.fit_timing["line_search_rejected"]
        assert set(rejected) == set(est.fit_timing["fn_evals"]) == {"global", "per-e"}
        after = telemetry.METRICS.labeled_counters("line_search_rejected_trials")
        for cid, kind in (("global", "fixed"), ("per-e", "random")):
            label = f"coordinate={cid},kind={kind}"
            assert after[label] - before.get(label, 0) == rejected[cid] >= 0
        profile = est.run_profile()
        assert profile["fit_timing"]["line_search_rejected"] == rejected
        assert "line_search_rejected_trials" in profile["metrics"]["labeled_counters"]


class TestRandomEffectSolveStats:
    def _coordinate(self, n=6000, n_entities=300, seed=3):
        rng = np.random.default_rng(seed)
        Xe = rng.normal(size=(n, 8)).astype(np.float32)
        entity = rng.integers(0, n_entities, size=n)
        y = (rng.uniform(size=n) > 0.5).astype(np.float32)
        ds = GameDataset.build({"pe": jnp.asarray(Xe)}, y, id_tags={"entityId": entity})
        # A small cell bound splits the 32-row entities into several blocks of
        # one shape, which the scan sweep fuses into one dispatch.
        red = build_random_effect_dataset(
            ds,
            RandomEffectDataConfig(
                "entityId", "pe", active_upper_bound=32, min_bucket=8, max_block_cells=2048
            ),
        )
        cfg = CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=6, tolerance=1e-7), reg_weight=2.0
        )
        return ds, red, RandomEffectCoordinate(ds, red, cfg, TASK)

    def test_per_entity_counts_equal_a_bucket_loop_recount(self, monkeypatch):
        ds, red, coord = self._coordinate()
        assert len(red.buckets) > 1
        _, scan = coord.train(ds.offsets)
        monkeypatch.setenv("PHOTON_SWEEP_SCAN", "0")
        _, loop = coord.train(ds.offsets)
        assert len(loop.per_entity) == len(red.buckets) > len(scan.per_entity)
        its_scan, evals_scan = coord.entity_counts(scan)
        its_loop, evals_loop = coord.entity_counts(loop)
        np.testing.assert_array_equal(its_scan, its_loop)
        np.testing.assert_array_equal(evals_scan, evals_loop)
        assert evals_scan.shape == (red.num_entities + 1,)
        assert evals_scan.sum() == scan.fn_evals == loop.fn_evals
        assert its_scan.sum() == scan.iterations == loop.iterations
        # Every real entity made its first evaluation and at least one step's.
        assert (evals_scan[: red.num_entities] > its_scan[: red.num_entities]).all()
        assert scan.buckets == loop.buckets
        shapes = {(b.capacity, b.num_entities) for b in red.buckets}
        assert {(r["capacity"], r["entities"]) for r in scan.buckets} == shapes
        assert sum(r["buckets"] for r in scan.buckets) == len(red.buckets)
        assert sum(r["fn_evals"] for r in scan.buckets) == scan.fn_evals

    @pytest.mark.parametrize("n_entities, scan", [(40, "1"), (300, "1"), (300, "0")])
    def test_finish_train_makes_one_fetch_whatever_the_bucket_count(
        self, monkeypatch, n_entities, scan
    ):
        """Counted where every blocking read of a device value passes
        (`float()`, `int()`, `bool()`, `jax.device_get`): the array's host
        copy being made."""
        from jax._src import array as jax_array

        monkeypatch.setenv("PHOTON_SWEEP_SCAN", scan)
        ds, red, coord = self._coordinate(n_entities=n_entities)
        coord.train(ds.offsets)  # compile outside the count
        made = []
        host_copy = jax_array.ArrayImpl._value

        def counting(self):
            if self._npy_value is None:
                made.append(self.shape)
            return host_copy.fget(self)

        monkeypatch.setattr(jax_array.ArrayImpl, "_value", property(counting))
        _, stats = coord.train(ds.offsets)
        monkeypatch.setattr(jax_array.ArrayImpl, "_value", host_copy)
        assert made == [(len(stats.per_entity), 2)], (made, len(red.buckets))
        assert all(isinstance(a, jax.Array) for _, a, b in stats.per_entity)

    def test_coordinate_descent_keeps_the_count(self):
        ds, _, coord = self._coordinate(n_entities=40)
        _, stats = coord.train(ds.offsets)
        events = []
        cd = run_coordinate_descent(
            {"per-entity": coord}, 1, on_event=lambda etype, **f: events.append(f)
        )
        assert events[0]["fn_evals"] == stats.fn_evals
        assert cd.fn_evals == {"per-entity": stats.fn_evals}


def _waits(cid):
    """(updates, blocking fetches) the process recorded for coordinate `cid`."""
    snap = telemetry.METRICS.labeled_histograms("cd_host_waits").get(f"coordinate={cid}")
    return (0, 0.0) if snap is None else (snap["count"], snap["sum"])


def _todays_path(coordinates, passes, scorer, suite, validation_offsets, reg_weights):
    """The validated descent as the host ran it before its validation rode
    in the verdict's fetch: the zero scores, start vector, regularisation
    weight and key made by the host, the verdict fetched, and then the
    validation scored, summed and evaluated with a fetch of its own."""
    from photon_ml_tpu.game.model import Coefficients, FixedEffectModel

    base = next(iter(coordinates.values())).dataset.offsets
    zeros = lambda: jnp.zeros(base.shape, base.dtype)
    summed, scores, models, val_scores, history = zeros(), {}, {}, {}, []
    for it in range(passes):
        for cid, coord in coordinates.items():
            residual = summed - scores.get(cid, zeros())
            offsets = base + residual
            if isinstance(coord, FixedEffectCoordinate):
                feats, data = coord.training_features, coord.dataset
                prev = models.get(cid)
                w0 = prev.coefficients.means if prev else jnp.zeros((feats.shape[-1],), data.labels.dtype)
                res = coord._train_fn(
                    feats, data.labels, offsets, data.weights, w0,
                    jnp.asarray(reg_weights[cid], data.labels.dtype), jax.random.PRNGKey(0),
                )
                model = FixedEffectModel(Coefficients(res.coefficients, None), TASK)
            else:
                model, _ = coord.train(offsets, models.get(cid), reg_weight=reg_weights[cid])
            scores[cid] = coord.score(model)
            summed = residual + scores[cid]
            models[cid] = model
            val_scores[cid] = scorer(cid, model)
            total = validation_offsets
            for v in val_scores.values():
                total = v if total is None else total + v
            history.append((it, cid, suite.evaluate(total).results))
    return models, history


class TestAValidatedUpdateWaitsOnce:
    """A validated update is enqueued whole — its inputs made in
    the programs or kept, the solve, the scoring, the commit and guard, the
    validation's scoring, sum and evaluation — and the host waits once, for
    one vector holding the guard's boolean, the solve's counts and the
    metrics."""

    @staticmethod
    def _fit(coordinates, passes=2, fault=""):
        from photon_ml_tpu.estimators import game_estimator

        data_configs = {
            "global": FixedEffectDataConfig("g"),
            "per-e": RandomEffectDataConfig("e", "g", min_bucket=8),
        }
        est = GameEstimator(
            TASK,
            {cid: data_configs[cid] for cid in coordinates},
            coordinate_descent_iterations=passes,
            validation_evaluators=[EvaluatorType.parse("AUC"), EvaluatorType.parse("LOGISTIC_LOSS")],
            pipeline=False,
        )
        cfg = {
            cid: CoordinateOptimizationConfig(
                optimizer=OptimizerConfig(max_iterations=3), reg_weight=1.5
            )
            for cid in coordinates
        }
        train, val = _glmix(n=1500, n_val=400, n_entities=40)
        calls = []
        descend = game_estimator.run_coordinate_descent

        def recording(*a, **k):
            calls.append((a, k, descend(*a, **k)))
            return calls[-1][2]

        tracer = telemetry.install_tracer(telemetry.Tracer())
        try:
            with pytest.MonkeyPatch.context() as mp, faults.inject(fault):
                mp.setattr(game_estimator, "run_coordinate_descent", recording)
                result = est.fit(train, val, [cfg])[0]
        finally:
            telemetry.uninstall_tracer()
        (args, kwargs, cd), = calls
        return result, cd, args, kwargs, tracer.spans()

    @pytest.mark.parametrize("coordinates", [("global",), ("global", "per-e")], ids=["dense", "glmix"])
    def test_one_wait_an_update_and_the_bits_of_the_path_before(self, coordinates):
        before = {cid: _waits(cid) for cid in coordinates}
        result, cd, args, kwargs, spans = self._fit(coordinates)
        for cid in coordinates:
            updates, waits = (a - b for a, b in zip(_waits(cid), before[cid]))
            assert updates == waits == 2, (cid, updates, waits)  # two passes, one wait each
        commits = [s for s in spans if s["name"] == "cd/commit"]
        assert len(commits) == 2 * len(coordinates)
        assert all(s["args"]["deferred_validation"] is True for s in commits)
        ids = {s["args"]["span_id"] for s in commits}
        assert len(_children("cd/commit")) == 2  # the validation's scoring and evaluation
        for name in _children("cd/commit"):
            inside = [s for s in spans if s["name"] == name]
            assert len(inside) == len(commits) and all(s["args"]["parent_id"] in ids for s in inside)

        coords, passes = args
        models, history = _todays_path(
            coords, passes, kwargs["validation_scorer"], kwargs["validation_suite"],
            kwargs["validation_offsets"], kwargs["reg_weights"],
        )
        for cid, model in models.items():
            got = result.model[cid]
            for a, b in (
                (got.coefficients.means, model.coefficients.means)
                if cid == "global"
                else (got.coefficients_matrix, model.coefficients_matrix),
            ):
                assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), cid
        assert [(it, cid, r.results) for it, cid, r in cd.validation_history] == history
        assert cd.evaluation is cd.validation_history[-1][2] is result.evaluation
        assert cd.evaluation.results == history[-1][2]

    def test_a_rejected_candidates_evaluation_is_dropped(self):
        clean, clean_cd, *_ = self._fit(("global",), passes=1)
        discarded = telemetry.METRICS.labeled_counters("validation_discarded")
        before = _waits("global")
        calls = telemetry.METRICS.get_counter("evaluation_calls")
        faulted, cd, *_ = self._fit(("global",), passes=1, fault="solve@1")
        assert cd.diverged_steps == 1
        after = telemetry.METRICS.labeled_counters("validation_discarded")
        assert after["coordinate=global"] - discarded.get("coordinate=global", 0) == 1
        assert tuple(a - b for a, b in zip(_waits("global"), before)) == (1, 2)  # one update, two attempts
        assert telemetry.METRICS.get_counter("evaluation_calls") - calls == 1
        a, b = (r.model["global"].coefficients.means for r in (clean, faulted))
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        assert [r.results for _, _, r in cd.validation_history] == [
            r.results for _, _, r in clean_cd.validation_history
        ]
        assert faulted.evaluation.results == clean.evaluation.results


def _dense_fit_data(optimizer):
    train, val = _glmix(n=1500, n_val=400)
    est = GameEstimator(
        TASK, {"global": FixedEffectDataConfig("g")},
        coordinate_descent_iterations=1,
        validation_evaluators=[EvaluatorType.parse("AUC")],
    )
    cfg = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(optimizer_type=optimizer, max_iterations=3), reg_weight=1.0
    )
    return est, train, val, {"global": cfg}


def _sample_sharded_fit_data(_):
    """The wide sparse fixed effect over four devices, a part a device, as
    the four-chip cell lays it (tests/test_sample_sharded_sparse_fit.py)."""
    from benchmarks.generators import criteo_shape_mesh
    from photon_ml_tpu.parallel.mesh import make_mesh, sample_sharded_dataset
    from tests import test_sample_sharded_sparse_fit as sharded

    mesh = make_mesh(jax.devices()[: sharded.DEVICES])
    problem = criteo_shape_mesh.generate(sharded.small_config(), 2_147_483_659, rows=4_001)
    train, val = (
        sample_sharded_dataset(sharded.device_parts(problem[part], real_rows_only=True), mesh)
        for part in ("train", "validation")
    )
    est, opt = sharded.estimator(sharded.small_config())
    return est, train, val, opt


class TestNothingEagerBeforeTheSolve:
    """Between a warm fit's start and its solve's dispatch the
    host runs no eager operation and moves nothing to the device: every
    array the solve reads is made in a program or kept from an earlier fit."""

    @pytest.mark.parametrize(
        "build, optimizer",
        [
            (_dense_fit_data, OptimizerType.LBFGS),
            (_dense_fit_data, OptimizerType.TRON),
            (_sample_sharded_fit_data, None),
        ],
        ids=["dense_lbfgs", "dense_tron", "sample_sharded_four_devices"],
    )
    def test_warm_fit_dispatches_the_solve_first(self, build, optimizer, monkeypatch):
        import contextlib

        from jax._src import dispatch

        est, train, val, cfg = build(optimizer)
        est.fit(train, val, [cfg])  # compiles, and makes what is kept
        (coord,) = est._coordinate_cache.values()
        eager, solves, window = [], [], contextlib.ExitStack()
        primitive_callable = dispatch.xla_primitive_callable

        def counting(prim, **params):
            if solves == []:
                eager.append(str(prim))
            return primitive_callable(prim, **params)

        train_fn = coord._train_fn

        def solve(*args, **kwargs):
            if solves == []:
                window.close()  # the host-to-device guard ends at the solve
            solves.append(1)
            return train_fn(*args, **kwargs)

        monkeypatch.setattr(dispatch, "xla_primitive_callable", counting)
        monkeypatch.setattr(coord, "_train_fn", solve)
        window.enter_context(jax.transfer_guard_host_to_device("disallow"))
        try:
            result = est.fit(train, val, [cfg])[0]
        finally:
            window.close()
        assert solves == [1] and eager == [], eager
        assert result.evaluation is not None


class TestThreeSinks:
    def test_one_boundary_three_sinks(self, tmp_path):
        """A stage's wall reaches the ambient registry, a telemetry span
        when a Tracer is installed, and the profiler's host plane."""
        registry = TimingRegistry()
        tracer = telemetry.install_tracer(telemetry.Tracer())
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        try:
            jax.profiler.start_trace(str(tmp_path), profiler_options=options)
            with stage_scope(registry), stage_timer("outer", tag="x") as outer:
                with stage_timer("outer/inner"):
                    time.sleep(0.002)
                with telemetry.span("direct"):  # a span opened directly mirrors itself
                    time.sleep(0.001)
                outer.set(done=True)
            jax.profiler.stop_trace()
        finally:
            telemetry.uninstall_tracer()
        assert registry.get("outer") == outer.seconds >= registry.get("outer/inner") > 0.0
        spans = {s["name"]: s for s in tracer.spans()}
        assert spans["outer"]["args"]["tag"] == "x" and spans["outer"]["args"]["done"] is True
        assert spans["outer/inner"]["args"]["parent_id"] == spans["outer"]["args"]["span_id"]
        found = _host_events(tmp_path)
        for name in ("photon/outer", "photon/outer/inner", "photon/direct"):
            assert len(found[name]) == 1, (name, sorted(found))  # mirrored once, not twice
        (o_lo, o_hi), = found["photon/outer"]
        for child in ("photon/outer/inner", "photon/direct"):
            (lo, hi), = found[child]
            assert o_lo <= lo and hi <= o_hi

    def test_fit_encloses_its_stages_on_the_profilers_host_plane(self, tmp_path):
        """No Tracer installed: the stages annotate the profiler themselves."""
        assert telemetry.current_tracer() is None
        train, val = _glmix(n=1500, n_val=400)
        est, cfg = _estimator(cd_iterations=1)
        est.fit(train, val, [cfg])  # compiles
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        with jax.profiler.TraceAnnotation("fit:0"):  # as the benchmark's driver does
            est.fit(train, val, [cfg])
        jax.profiler.stop_trace()
        found = _host_events(tmp_path)
        (b_lo, b_hi), = found["fit:0"]
        (f_lo, f_hi), = found["photon/fit"]
        assert b_lo <= f_lo and f_hi <= b_hi
        for stage in SOLVE_STAGES:
            if stage == "cd/checkpoint":
                assert "photon/cd/checkpoint" not in found
                continue
            parent = SOLVE_STAGE_PARENT[stage]
            for lo, hi in found[f"photon/{stage}"]:
                assert f_lo <= lo and hi <= f_hi
                if parent is not None:
                    assert any(p_lo <= lo and hi <= p_hi for p_lo, p_hi in found[f"photon/{parent}"])
        assert len(found["photon/cd/train"]) == 2  # one update a coordinate

    def test_untraced_span_is_still_the_shared_noop(self):
        assert telemetry.current_tracer() is None
        with stage_timer("anything") as stage:
            pass
        assert stage.seconds >= 0.0
        assert telemetry.span("a", x=1) is telemetry.span("b")

    def test_chrome_trace_carries_a_clock_anchor(self):
        tracer = telemetry.Tracer()
        anchor = tracer.to_chrome_trace()["otherData"]["clock_anchor"]
        assert set(anchor) == {"perf_counter_ns", "time_ns"}
        # The same offset between the two clocks as a reading taken now.
        now = time.time_ns() - time.perf_counter_ns()
        assert abs((anchor["time_ns"] - anchor["perf_counter_ns"]) - now) < 50_000_000


def _host_events(directory):
    """{event name: [(start_ns, end_ns)]} of the host planes of the one
    profile under `directory`."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(directory), "plugins", "profile", "*", "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("photon/", "fit:")):
                    found.setdefault(e.name, []).append((e.start_ns, e.start_ns + e.duration_ns))
    return found
