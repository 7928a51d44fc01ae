"""ISSUE 40: a dense fit solved by trust-region Newton, against the benchmark's
plain TRON reference, and what such a fit counts.

The program's solve (`optimize/tron.minimize_tron` over the fused Pallas
value+gradient and Hessian-vector kernels, interpret mode here) and
`benchmarks/references/tron.py` (Python loops over dense matmuls, no program
import) are two writings of one statement, `benchmarks/configs/
lr-epsilon-tron.json`'s: same rows in, the same steps taken, the same
Hessian-vector products made, the same CG count at every outer iteration,
the same coefficients to float32 rounding. A step limit that binds keeps both
sides off the last steps whose `actual / predicted` is decided by rounding,
as the configuration's does on the chip.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.generators import dense_unit_rows
from benchmarks.references import glm_dense_tron
from benchmarks.references import tron as reference_tron
from photon_ml_tpu.data.containers import LabeledData
from photon_ml_tpu.data.game_dataset import FixedEffectDataConfig, GameDataset
from photon_ml_tpu.estimators.game_estimator import GameEstimator
from photon_ml_tpu.evaluation.suite import EvaluatorType
from photon_ml_tpu.ops import objective, pallas_glm
from photon_ml_tpu.ops.losses import LOGISTIC, SQUARED
from photon_ml_tpu.optimize.config import (
    CoordinateOptimizationConfig,
    OptimizerConfig,
    RegularizationContext,
)
from photon_ml_tpu.optimize.tron import minimize_tron
from photon_ml_tpu.types import OptimizerType, RegularizationType, TaskType
from photon_ml_tpu.utils import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEP_LIMIT = 3


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _config(task, rows, d, storage):
    """lr-epsilon-tron's statement at a small shape."""
    cfg = _json("benchmarks", "configs", "lr-epsilon-tron.json")
    cfg.update(task=task, rows=rows, features=d, train_storage_dtype=storage)
    cfg["generator"]["row_block"] = rows
    cfg["coordinates"][0]["optimizer"].update(max_iterations=STEP_LIMIT, tolerance=1e-9)
    return cfg


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(pallas_glm, "FORCE_INTERPRET", True)
    monkeypatch.setattr(pallas_glm, "_HEALTHY", True)


# (rows, features): the row tile is 1,024 at both widths (`_tile_for`), so
# 4,096 rows are whole tiles and 4,100 leave a ragged last one.
@pytest.mark.parametrize(
    "task,rows,d,column_major,storage",
    [
        ("LOGISTIC_REGRESSION", 4096, 128, False, "float32"),
        ("LOGISTIC_REGRESSION", 4096, 128, True, "bfloat16"),
        ("LOGISTIC_REGRESSION", 4100, 136, False, "bfloat16"),
        ("LOGISTIC_REGRESSION", 4100, 136, True, "float32"),
        ("LOGISTIC_REGRESSION", 4100, 136, True, "bfloat16"),
        ("LINEAR_REGRESSION", 4100, 136, False, "float32"),
        ("LINEAR_REGRESSION", 4100, 136, True, "bfloat16"),
        ("LINEAR_REGRESSION", 4096, 128, False, "bfloat16"),
    ],
)
def test_the_programs_tron_solve_is_the_references(interpret, task, rows, d, column_major, storage):
    cfg = _config(task, rows, d, storage)
    problem = dense_unit_rows.generate(cfg, 3_000_000_019, rows)
    solved = glm_dense_tron.solve(cfg, problem)
    info = solved["info"]

    loss = LOGISTIC if task == "LOGISTIC_REGRESSION" else SQUARED
    x = problem["train"]["shards"]["g"].astype(jnp.dtype(storage))
    y = problem["train"]["labels"]
    data = LabeledData(x, y, jnp.zeros_like(y), jnp.ones_like(y), column_major=column_major)
    assert pallas_glm.dispatch(x, jnp.zeros((d,), jnp.float32)) is True
    res = minimize_tron(
        lambda w: objective.value_and_gradient(loss, w, data, None, 1.0, True),
        lambda w, v: objective.hessian_vector(loss, w, v, data, None, 1.0, True),
        jnp.zeros((d,), jnp.float32),
        max_iterations=STEP_LIMIT, tolerance=1e-9, tracking=True,
    )

    taken_cg = [cg for cg, taken in zip(info["cg_iterations"], info["taken"]) if taken]
    assert int(res.iterations) == info["iterations"] == len(taken_cg) > 0
    assert int(res.hv_evals) == info["hessian_vector_products"] == sum(info["cg_iterations"])
    assert int(res.fn_evals) == info["evaluations"] + info["hessian_vector_products"]
    assert int(res.fn_evals) - 1 - int(res.iterations) - int(res.hv_evals) == info["refused"]
    # The CG count of every outer iteration (slot 0 is the start).
    np.testing.assert_array_equal(np.asarray(res.cg_iterations_history)[1 : 1 + len(taken_cg)], taken_cg)
    np.testing.assert_allclose(np.asarray(res.loss_history)[: len(info["values"])], info["values"], rtol=2e-6)
    reference = solved["coefficients"]["global"]
    gap = np.linalg.norm(np.asarray(res.coefficients) - reference) / np.linalg.norm(reference)
    assert gap < 2e-5, gap


def test_the_reference_stops_as_the_statement_says():
    """A quadratic in four dimensions: every CG run ends on its residual
    (0.1 |g|) within four iterations and inside the region, the solve stops
    on the gradient; a radius too small to hold the step ends the CG on the
    region's surface."""
    a = jnp.asarray(np.diag([1.0, 2.0, 4.0, 8.0]), jnp.float32)
    b = jnp.asarray([1.0, -2.0, 3.0, -4.0], jnp.float32)
    objective_fn = lambda w: (0.5 * w @ a @ w - b @ w, a @ w - b, None)
    w, info = reference_tron.minimize(
        objective_fn, lambda _, v: a @ v, jnp.zeros(4, jnp.float32), max_iterations=15, tolerance=1e-5
    )
    np.testing.assert_allclose(w, np.linalg.solve(np.asarray(a), np.asarray(b)), rtol=1e-5)
    assert all(info["taken"]) and info["boundary_steps"] == 0
    assert all(1 <= cg <= 4 for cg in info["cg_iterations"]) and info["iterations"] < 15
    assert info["hessian_vector_products"] == sum(info["cg_iterations"])
    assert info["evaluations"] == 1 + len(info["taken"])
    cg, step, _, on_boundary = reference_tron._truncated_cg(lambda v: a @ v, -b, jnp.float32(0.5))
    assert on_boundary and cg == 1
    np.testing.assert_allclose(float(jnp.linalg.norm(step)), 0.5, rtol=1e-6)


class TestAFitCountsItsProducts:
    """The counters of ISSUE 40 against `OptResult`, through GameEstimator.fit."""

    def _fit(self, optimizer):
        cfg = _config("LOGISTIC_REGRESSION", 2000, 16, "float32")
        problem = dense_unit_rows.generate(cfg, 11, 2000)
        build = lambda part: GameDataset.build(part["shards"], part["labels"], id_tags={})
        train, validation = build(problem["train"]), build(problem["validation"])
        opt = CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(optimizer_type=optimizer, max_iterations=4, tolerance=1e-9),
            regularization=RegularizationContext(RegularizationType.L2),
            reg_weight=1.0,
        )
        est = GameEstimator(
            TaskType.LOGISTIC_REGRESSION,
            {"global": FixedEffectDataConfig("g")},
            coordinate_descent_iterations=1,
            validation_evaluators=[EvaluatorType.parse("AUC")],
        )
        before = {
            name: dict(telemetry.METRICS.labeled_counters(name))
            for name in ("hessian_vector_products", "objective_evaluations")
        }
        est.fit(train, validation, [{"global": opt}])
        moved = {
            name: {
                k: v - was.get(k, 0)
                for k, v in telemetry.METRICS.labeled_counters(name).items()
                if v != was.get(k, 0)
            }
            for name, was in before.items()
        }
        from photon_ml_tpu.game.coordinate import FixedEffectCoordinate

        coord = FixedEffectCoordinate(train, "g", opt, TaskType.LOGISTIC_REGRESSION)
        _, res = coord.train(train.offsets)  # the same solve again, its OptResult in hand
        return est, res, moved

    def test_a_tron_fit(self):
        est, res, moved = self._fit(OptimizerType.TRON)
        label = "coordinate=global,kind=fixed"
        hv, evals, steps = int(res.hv_evals), int(res.fn_evals), int(res.iterations)
        rejected = evals - 1 - steps - hv
        assert hv >= steps == 4 and rejected >= 0
        assert est.fit_timing["hv_evals"] == {"global": hv}
        assert "tron_rejected_steps" not in est.fit_timing  # the note alone says it
        assert est.fit_timing["fn_evals"] == {"global": evals}  # passes over the data, as before
        assert est.fit_timing["line_search_rejected"] == {}
        assert moved["hessian_vector_products"] == {label: hv}
        assert moved["objective_evaluations"] == {label: evals}
        assert est.run_profile()["dispatch"]["tron"] == {
            "accepted": steps, "rejected": rejected, "hessian_vector_products": hv,
            "kernel": "xla",  # the CPU runs no Pallas kernel
        }

    def test_a_line_search_fit_counts_none(self):
        est, res, moved = self._fit(OptimizerType.LBFGS)
        assert res.hv_evals is None
        assert est.fit_timing["hv_evals"] == moved["hessian_vector_products"] == {}
        assert est.run_profile()["dispatch"]["tron"] == "none"


def test_the_coordinate_says_which_kernel_computes_its_products(interpret):
    from photon_ml_tpu.game.coordinate import FixedEffectCoordinate

    rng = np.random.default_rng(5)
    opt = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(optimizer_type=OptimizerType.TRON, max_iterations=2), reg_weight=1.0
    )
    kernel = {}
    for rows, d in ((4096, 128), (512, 16)):  # the fused kernels engage, or not
        x = jnp.asarray(rng.normal(size=(rows, d)).astype(np.float32))
        y = (rng.uniform(size=rows) > 0.5).astype(np.float32)
        coord = FixedEffectCoordinate(GameDataset.build({"g": x}, y), "g", opt, TaskType.LOGISTIC_REGRESSION)
        kernel[rows] = coord.hessian_vector_kernel
    assert kernel == {4096: "pallas", 512: "xla"}


class TestTheConfigurationFile:
    """`lr-epsilon-tron` against the schema the other configurations satisfy
    and against `lr-epsilon`, whose everything-but-the-solver it is."""

    def test_it_is_lr_epsilon_with_another_solver(self):
        tron_cfg = _json("benchmarks", "configs", "lr-epsilon-tron.json")
        lbfgs_cfg = _json("benchmarks", "configs", "lr-epsilon.json")
        for key in ("task", "rows", "validation_rows", "features", "nnz_per_row", "generator", "shards",
                    "train_storage_dtype", "control_storage_dtype", "coordinate_descent_iterations",
                    "evaluators", "reduced"):
            assert tron_cfg[key] == lbfgs_cfg[key], key
        assert tron_cfg["reduced"] == [] and tron_cfg["architecture"] is None
        (coordinate,), (other,) = tron_cfg["coordinates"], lbfgs_cfg["coordinates"]
        assert {k: v for k, v in coordinate.items() if k != "optimizer"} == {
            k: v for k, v in other.items() if k != "optimizer"
        }
        assert coordinate["optimizer"]["type"] == "TRON" and OptimizerType[coordinate["optimizer"]["type"]]
        assert tron_cfg["reference"] == {"name": "glm_dense_tron"}
        assert tron_cfg["source"] != lbfgs_cfg["source"] and tron_cfg["source"].startswith(lbfgs_cfg["source"])

    def test_the_manifest_lists_it_as_the_others(self):
        manifest = _json("BENCHMARK.json")
        configs = {c["name"]: c for c in manifest["configs"]}
        cells = {w["name"]: w for w in manifest["workloads"]}
        entry = configs["lr-epsilon-tron"]
        cfg = _json(entry["file"])
        assert cfg["name"] == "lr-epsilon-tron" and cfg["source"] == entry["source"]
        assert len(entry["source"]) <= 200 and cfg["reduced"] == entry["reduced"] == []
        # Every key the three configurations before it share, it has too.
        earlier = [_json(configs[n]["file"]) for n in ("lr-epsilon", "lr-criteo", "lr-criteo-full")]
        assert set.intersection(*(set(c) for c in earlier)) <= set(cfg)
        assert set.intersection(*(set(c["assumed"]) for c in earlier)) <= set(cfg["assumed"])
        assert set(cfg["limits"]) == {"coef_gap.global", "metric_gap", "compiled_in_window"}
        assert cfg["limits"]["compiled_in_window"] == 0 and cfg["limits_from"]
        # Each limit from the cell's own readings (PERF.md section 2): above
        # every sound run's (29 seeds) and under float8 storage's, the
        # coefficient gap's on all 9 seeds, the AUC gap's on 8 of them.
        assert 2.96e-6 < cfg["limits"]["coef_gap.global"] < 3.55e-3
        assert 1.38e-7 < cfg["limits"]["metric_gap"] < 1.78e-6
        for key in ("generator", "reference"):
            assert os.path.exists(os.path.join(ROOT, "benchmarks", f"{key}s", cfg[key]["name"] + ".py"))
        # Two deployments of one data set name sources that differ.
        assert configs["lr-epsilon-tron"]["source"] != configs["lr-epsilon"]["source"]
        cell = cells["lr-epsilon-tron.fit"]
        workload = _json("benchmarks", "workloads", "lr-epsilon-tron.fit.json")
        assert (cell["config"], cell["chips"], cell["traffic"]) == ("lr-epsilon-tron", 1, "fit")
        assert (workload["config"], workload["driver"], workload["chips"]) == ("lr-epsilon-tron", "refit", 1)
        assert workload["traffic"]["trace_units"] == 3 and workload["traffic"]["compare_fits"] == 8
        listed = {m["name"] for m in manifest["per_layer"] if "lr-epsilon-tron.fit" in m.get("workloads", [])}
        assert "fit_mfu" not in listed  # it counts value+gradient executions alone
        # The closed loop of ~415 fits a window reports its tail as lr-epsilon.fit does.
        tail = next(m for m in manifest["end_to_end"] if m["name"] == "fit_p90_s")
        assert tail["workloads"] == ["lr-epsilon.fit", "lr-epsilon-tron.fit"]
        assert all(len(e["why"]) <= 200 for e in (entry, cell))
        assert listed >= {
            "prepare_s", "fe_update_share_pct", "dense_vg_roofline", "validation_share_pct",
            "fit_glue_share_pct", "fe_evals_per_fit", "compile_path_s", "programs_requested",
            "dense_hvp_roofline", "fit_mfu_tron", "fe_hvp_per_fit",
        }
        for name in listed:
            assert os.path.exists(os.path.join(ROOT, "benchmarks", "layers", name + ".py"))


class TestTheReadersOfATronCell:
    """A product's work from the shapes, and the three readers on a small
    hand-made trace; each reads None where its input is absent."""

    PEAKS = {"hbm_gb_per_s": 819.0, "bf16_tflop_per_s": 197.0}

    def _run(self, ops):
        cfg = _json("benchmarks", "configs", "lr-epsilon-tron.json")
        trace = {
            "window_s": 0.3,
            "op_self_s": {k: v[1] for k, v in ops.items()},
            "op_count": {k: v[0] for k, v in ops.items()},
            "op_line": {k: v[2] for k, v in ops.items()},
        }
        return {
            "config": cfg, "rows": cfg["rows"], "peaks": self.PEAKS, "trace": trace,
            "kinds": {"global": "fixed"}, "records": [{}] * 4, "warm_fit_timing": {"hv_evals": {"global": 7}},
        }

    def test_a_products_work_and_its_least_time(self):
        from benchmarks import work, work_hvp

        product = work_hvp.dense_hessian_vector(400_000, 2_000, 2)
        assert product == {"bytes": 1_600_000_000 + 4_800_000 + 24_000, "flops": 4_800_000_000}
        seconds, binds = work.least_seconds(product, self.PEAKS)
        assert binds == "hbm" and seconds == pytest.approx(1.9595e-3, rel=1e-4)
        cfg = _json("benchmarks", "configs", "lr-epsilon-tron.json")
        assert work_hvp.fixed_effect_product(cfg, 400_000) == product
        assert work_hvp.fixed_effect_product(_json("benchmarks", "configs", "lr-criteo.json"), 8) is None

    def test_the_trace_readers(self):
        from benchmarks.layers import dense_hvp_roofline, fit_mfu_tron

        vg_line = "%value_gradient_sums.3 = (f32[1,2]{1,0:T(1,128)}, f32[1,2000]) custom-call(...)"
        hv_line = "%hessian_vector_sums.1 = (f32[1,1]{1,0:T(1,128)}, f32[1,2000]) custom-call(...)"
        run = self._run({
            "value_gradient_sums.3": (12, 12 * 2.2e-3, vg_line),
            "hessian_vector_sums.1": (60, 60 * 2.3e-3, hv_line),
            "hessian_vector_sums": (30, 30 * 2.3e-3, hv_line),
            "fusion.7": (3, 0.01, "%fusion.7 = f32[400000] fusion(...)"),
        })
        least = 1_604_824_000 / 819e9
        assert dense_hvp_roofline.read(run) == pytest.approx(100 * least / 2.3e-3)
        vg_least = (1_600_000_000 + 4_800_000 + 16_000) / 819e9
        assert fit_mfu_tron.read(run) == pytest.approx(100 * (12 * vg_least + 90 * least) / 0.3)
        # No product in the trace (another solver, or the XLA fall-back): silent.
        run = self._run({"value_gradient_sums.3": (12, 0.03, vg_line)})
        assert dense_hvp_roofline.read(run) is None and fit_mfu_tron.read(run) is None
        run["trace"] = None
        assert dense_hvp_roofline.read(run) is None and fit_mfu_tron.read(run) is None

    def test_the_counter_reader(self, monkeypatch):
        from benchmarks.layers import fe_hvp_per_fit

        run = self._run({})
        counted = {"coordinate=global,kind=fixed": 7 + 4 * 23}
        monkeypatch.setattr(telemetry.METRICS, "labeled_counters", lambda name: counted if name == "hessian_vector_products" else {})
        assert fe_hvp_per_fit.read(run) == 23.0
        counted.clear()  # a program that counts no product: the parent commit, or L-BFGS
        assert fe_hvp_per_fit.read(run) is None
        run["warm_fit_timing"] = {}
        assert fe_hvp_per_fit.read(run) is None
