"""A whole run of run.py on the CPU at a rehearsal size, sound and broken.

The rehearsal from the command line (`--rows`) goes through every step and
ends `"correct": false`. The other tests skip the harness's look for a chip
(`chip_required=False`) and judge by limits fit for the small size: a sound
run is correct, the control (the reference one storage type down) is not, and
neither is a run with the timed path broken underneath.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmarks import compare, run
from benchmarks.drivers import refit

HERE = os.path.dirname(os.path.abspath(__file__))

# Limits for these sizes on the CPU, where the program keeps float32 rows:
# sound runs read 7e-4 / 3e-6 (dense) and 3e-7 / 3e-8 (GLMix), the controls
# 1.2e-2 (float8 rows) and 3e-4 to 1.5e-3 (bfloat16 values).
CELLS = {
    "lr-epsilon.fit": {
        "rows": "20000", "control": "float8_e4m3fn",
        "limits": {"coef_gap.global": 3e-3, "metric_gap": 3e-5, "compiled_in_window": 0},
    },
    "glmix-movielens.fit": {
        "rows": "40000", "control": "bfloat16",
        "limits": {"coef_gap.global": 2e-5, "coef_gap.per-user": 2e-5,
                   "coef_gap.per-movie": 2e-5, "metric_gap": 1e-6, "compiled_in_window": 0},
    },
}


def drive(capsys, cell, **kwargs):
    argv = ["--workload", cell, "--seed", "2147483659", "--seconds", "0.5", "--trace", "0",
            "--rows", CELLS[cell]["rows"]]
    assert run.main(argv, **kwargs) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_rehearsal_from_the_command_line_is_never_correct(capsys, cell):
    result = drive(capsys, cell)
    assert result["correct"] is False
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device", "compared"}
    assert list(result)[-1] == "compared"
    assert result["metrics"]["setup_s"]["value"] > 0
    assert result["metrics"]["train_rows_per_s"]["unit"] == "rows/s"


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_sound_run_is_correct_by_limits_fit_for_the_size(capsys, cell):
    result = drive(capsys, cell, chip_required=False, limits=CELLS[cell]["limits"])
    assert result["correct"] is True, result["compared"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_control_is_not_correct(cell):
    with open(os.path.join(HERE, "..", "workloads", f"{cell}.json")) as f:
        workload = json.load(f)
    with open(os.path.join(HERE, "..", "configs", f"{workload['config']}.json")) as f:
        config = json.load(f)
    generator = run.module("generators", config["generator"]["name"])
    reference = run.module("references", config["reference"]["name"])
    problem = generator.generate(config, 2147483659, int(CELLS[cell]["rows"]))
    sound = reference.solve(config, problem)
    control = reference.solve(config, problem, storage=CELLS[cell]["control"])
    assert config["control_storage_dtype"] == CELLS[cell]["control"]
    rows = compare.judge(compare.numbers([control], sound), CELLS[cell]["limits"])
    assert not all(r["ok"] for r in rows if r["name"] != "compiled_in_window")


def _zeroed(model):
    return type(model)({cid: jax.tree.map(jnp.zeros_like, m) for cid, m in model.items()})


def _nudged(model):
    first = model.coordinate_ids[0]
    return model.updated(first, jax.tree.map(lambda a: a * 1.01, model[first]))


def _break_fit(monkeypatch, alter):
    from photon_ml_tpu.estimators.game_estimator import GameEstimator

    sound = GameEstimator.fit

    def broken(self, *args, **kwargs):
        results = sound(self, *args, **kwargs)
        results[0].model = alter(results[0].model)
        return results

    monkeypatch.setattr(GameEstimator, "fit", broken)


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered", "half_batch"])
def test_a_broken_timed_path_is_not_correct(capsys, monkeypatch, cell, fault):
    if fault == "state_unchanged":  # the fit hands back the zero model it began from
        _break_fit(monkeypatch, _zeroed)
    elif fault == "answer_altered":  # one coordinate's answer is off by a hundredth
        _break_fit(monkeypatch, _nudged)
    else:  # the second half of the training rows never reaches the estimator
        sound = refit._dataset
        halves = iter([True, False])  # the train part is built first, then validation
        monkeypatch.setattr(
            refit, "_dataset", lambda part: sound(run.first_half(part) if next(halves) else part)
        )
    result = drive(capsys, cell, chip_required=False, limits=CELLS[cell]["limits"])
    assert result["correct"] is False
    assert any(v["value"] > v["limit"] for v in result["compared"].values())
