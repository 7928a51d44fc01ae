"""The whole of criteo over a mesh: the configuration's arithmetic, the mesh
generator against the one-chip generator's law, the mesh reference against the
one-chip reference on the same rows, the three readers of a cell on more than
one chip, and the cell through run.py at a rehearsal size.

The parts are one a device JAX has, up to the deployment's four: one here by
default, four under XLA_FLAGS=--xla_force_host_platform_device_count=4 (the
repo's tier-1 tests drive the same files over four of eight virtual devices).
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run, trace_reduce, work
from benchmarks.generators import criteo_shape, criteo_shape_mesh
from benchmarks.layers import collective_share_pct, collectives_per_eval, fit_mfu_counted, fit_mfu_mesh
from benchmarks.references import glm_sparse_lbfgs, glm_sparse_lbfgs_mesh
from photon_ml_tpu.utils import telemetry

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = {"hbm_gb_per_s": 819.0, "bf16_tflop_per_s": 197.0}
ROWS = 16_001  # odd: the last part ends in pad rows wherever the devices do not divide it


def config(name="lr-criteo-full"):
    with open(os.path.join(HERE, "..", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def problem():
    return criteo_shape_mesh.generate(config(), 3_000_000_019, rows=ROWS)


def gathered(per_chip):
    return np.concatenate([np.asarray(a) for a in per_chip.parts])


def test_the_configuration_is_lr_criteo_uncut_and_its_deployment_adds_up():
    full, cut = config(), config("lr-criteo")
    assert full["reduced"] == [] and full["architecture"] is None
    assert (full["rows"], full["validation_rows"]) == (cut["source_rows"], cut["source_validation_rows"])
    for key in ("source", "task", "features", "nnz_per_row", "shards", "coordinates", "evaluators",
                "coordinate_descent_iterations", "train_storage_dtype", "control_storage_dtype"):
        assert full[key] == cut[key], key
    for key in ("zipf_exponent", "margin_scale", "mean_margin", "field_sizes"):
        assert full["generator"][key] == cut["generator"][key], key
    d = full["deployment"]
    assert d["chips"] * d["rows_per_chip"] - d["pad_rows"] == full["rows"]
    assert d["chips"] * d["validation_rows_per_chip"] - d["validation_pad_rows"] == full["validation_rows"]
    assert d["rows_per_chip"] % full["generator"]["row_block"] == 0  # a chip's rows are whole blocks
    with open(os.path.join(HERE, "..", "workloads", "lr-criteo-full.fit.json")) as f:
        assert json.load(f)["chips"] == d["chips"]


def test_every_chips_rows_follow_the_one_chip_generators_law(problem):
    cfg = config()
    starts, sizes = criteo_shape.field_ranges(cfg["generator"])
    chips = len(problem["train"]["labels"].parts)
    assert chips == min(cfg["deployment"]["chips"], len(jax.devices()))
    for part, n in (("train", ROWS), ("validation", ROWS // 8)):
        shard, weights = problem[part]["shards"]["g"], gathered(problem[part]["weights"])
        assert {len(a) for a in shard["indices"].parts} == {-(-n // chips)}  # parts of one length
        real = weights > 0
        assert real.sum() == n and real[:n].all()  # the pads are the last rows of the last part
        idx, val = gathered(shard["indices"]), gathered(shard["values"])
        assert idx.shape == val.shape == (chips * -(-n // chips), 39) and shard["dim"] == 1_000_000
        assert ((idx[real] >= starts) & (idx[real] < starts + sizes)).all()
        assert (val[real] == np.float32(1 / math.sqrt(39))).all()
        assert not idx[~real].any() and not val[~real].any() and not gathered(problem[part]["labels"])[~real].any()
    labels = gathered(problem["train"]["labels"])[:ROWS]
    assert 0.2 < float(labels.mean()) < 0.32
    for a, device in zip(problem["train"]["shards"]["g"]["indices"].parts, jax.devices()):
        assert a.devices() == {device}  # each part where it was drawn


def test_chips_draw_different_rows_of_one_popularity_and_a_seed_repeats(problem):
    again = criteo_shape_mesh.generate(config(), 3_000_000_019, rows=ROWS)
    other = criteo_shape_mesh.generate(config(), 5, rows=ROWS)
    a, b, c = (gathered(p["train"]["shards"]["g"]["indices"]) for p in (problem, again, other))
    assert np.array_equal(a, b) and a.shape == c.shape and not np.array_equal(a, c)
    parts = problem["train"]["shards"]["g"]["indices"].parts
    tops = {int(np.bincount(np.asarray(p)[:-3, 13]).argmax()) for p in parts}
    assert len(tops) == 1  # one scramble for every chip: the narrowest field's top id is the same id
    if len(parts) > 1:
        assert not np.array_equal(np.asarray(parts[0]), np.asarray(parts[1]))


def test_a_cut_keeps_the_same_share_of_every_chips_rows(problem):
    labels = problem["train"]["labels"]
    half = run.first_half(problem["train"])
    assert "weights" not in half  # the first half of a part has no pad row
    for whole, cut in zip(labels.parts, half["labels"].parts):
        assert len(cut) == len(whole) * (len(labels) // 2) // len(labels)
        assert np.array_equal(np.asarray(cut), np.asarray(whole)[: len(cut)])
    assert half["shards"]["g"]["indices"].shape == (len(half["labels"]), 39)
    with pytest.raises(TypeError):
        labels[3]


def one_chip_problem(problem):
    """The same real rows as the one-chip generator would hand them over."""
    def part(p):
        real = gathered(p["weights"]) > 0
        shard = p["shards"]["g"]
        return {"shards": {"g": {"indices": jnp.asarray(gathered(shard["indices"])[real]),
                                 "values": jnp.asarray(gathered(shard["values"])[real]), "dim": shard["dim"]}},
                "labels": jnp.asarray(gathered(p["labels"])[real]), "id_tags": {}}

    return {"train": part(problem["train"]), "validation": part(problem["validation"])}


@pytest.mark.parametrize("storage", [None, "bfloat16"])
def test_the_mesh_reference_is_the_one_chip_reference_on_the_same_rows(problem, storage):
    cfg = config()
    cfg["reference"]["row_block"] = 1_500  # several blocks a part, the last one overlapping
    mesh = glm_sparse_lbfgs_mesh.solve(cfg, problem, storage=storage)
    one = glm_sparse_lbfgs.solve(dict(cfg, reference={"row_block": ROWS}), one_chip_problem(problem), storage=storage)
    w, ref = mesh["coefficients"]["global"], one["coefficients"]["global"]
    # Float32 sums in another order (blocks of 1,500 a part against one block).
    assert np.linalg.norm(w - ref) / np.linalg.norm(ref) < 2e-5
    # 2,000 validation rows after 16,001 training rows over a million features: an AUC near a half.
    assert abs(mesh["metric"] - one["metric"]) < 1e-5 and 0.4 < mesh["metric"] < 1.0
    assert mesh["info"] == one["info"] == {"iterations": 3, "evaluations": 4}


def test_the_controls_stand_clear_of_the_mesh_reference(problem):
    cfg = config()
    sound = glm_sparse_lbfgs_mesh.solve(cfg, problem)["coefficients"]["global"]
    gap = lambda w: np.linalg.norm(w - sound) / np.linalg.norm(sound)
    assert gap(glm_sparse_lbfgs_mesh.solve(cfg, problem, storage="bfloat16")["coefficients"]["global"]) > 5e-5
    halved = dict(problem, train=run.first_half(problem["train"]))
    assert gap(glm_sparse_lbfgs_mesh.solve(cfg, halved)["coefficients"]["global"]) > 0.05


def test_work_at_the_cells_shapes_and_the_mesh_share():
    cfg = config()
    w = work.fixed_effect_evaluation(cfg, cfg["rows"])
    assert w["bytes"] == 8 * 39 * 45_840_617 + 12 * 45_840_617 + 8 * 1_000_000
    seconds, binds = work.least_seconds(w, PEAKS)
    assert binds == "hbm" and seconds / 4 == pytest.approx(4.54e-3, rel=1e-2)  # a chip's quarter


# -- the three readers ---------------------------------------------------------


@pytest.fixture
def counted():
    """A process that made a warm fit of 4 evaluations and one window fit of 4."""
    telemetry.METRICS.reset()
    telemetry.METRICS.increment("objective_evaluations", 8, labels=(("coordinate", "global"), ("kind", "fixed")))
    yield {"records": [{"seconds": 1.0}], "kinds": {"global": "fixed"},
           "warm_fit_timing": {"fn_evals": {"global": 4}}}
    telemetry.METRICS.reset()


GRADIENT = "(f32[]{:T(128)}, f32[1000000]{0:T(1024)S(1)})"


def traced(ops_a_chip, counted, chips=4):
    planes = [{"name": "/host:CPU", "lines": [{"name": "python3", "events": [["fit:0", 0, 4000]]}]}]
    planes += [{"name": f"/device:TPU:{c}", "lines": [{"name": "XLA Ops", "events": ops_a_chip}]} for c in range(chips)]
    return dict(counted, config=config(), rows=1_000, peaks=PEAKS, trace=trace_reduce.reduce(planes, n_units=1))


def test_the_readers_on_a_trace_that_reduces_once_an_evaluation(counted):
    ops = [["%fusion.36 = f32[250]{0:T(1024)S(1)} fusion(f32[1000001]{0:T(1024)S(1)}, s32[512]{0})", 100, 2000]]
    ops += [[f"%all-reduce.5 = {GRADIENT} all-reduce(%fusion.34, %get-tuple-element.1)", 2200 + 100 * i, 50] for i in range(4)]
    ops += [["%all-reduce.9 = f32[6042136]{0:T(1024)S(1)} all-reduce(%dynamic-update-slice)", 3000, 100],  # the AUC's
            ["%all-gather-start.2 = (f32[250]{0}, f32[1000]{0}) all-gather-start(%x)", 3200, 30],
            ["%all-gather-done.2 = f32[1000]{0} all-gather-done(%all-gather-start.2)", 3300, 20]]
    run_ = traced(ops, counted)
    assert run_["trace"]["devices"] == 4
    assert collectives_per_eval.read(run_) == pytest.approx((4 + 1 + 1) / 4)  # a `-done` is its `-start`'s
    busy = 2000 + 4 * 50 + 100 + 30 + 20
    assert collective_share_pct.read(run_) == pytest.approx(100 * (4 * 50 + 100 + 30 + 20) / busy)
    least = (8 * 39_000 + 12 * 1_000 + 8 * 1_000_000) / 819e9
    assert fit_mfu_mesh.read(run_) == pytest.approx(100 * 4 * least / 4 / 4000e-9)
    assert fit_mfu_mesh.read(run_) == pytest.approx(fit_mfu_counted.read(run_) / 4)


def test_a_reduction_a_plane_reads_thirty_nine(counted):
    ops = [[f"%all-reduce.7 = f32[1000000]{{0:T(1024)S(1)}} all-reduce(%fusion.29)", 10 * i, 5] for i in range(4 * 39)]
    assert collectives_per_eval.read(traced(ops, counted)) == pytest.approx(39.0)  # 156 in 4 evaluations


def test_on_one_chip_or_without_a_trace_or_a_count_they_read_none(counted):
    one = traced([["%fusion.2 = f32[1000]{0} fusion(f32[1000]{0})", 100, 300]], counted, chips=1)
    assert collective_share_pct.read(one) is None and collectives_per_eval.read(one) is None
    assert fit_mfu_mesh.read(one) == pytest.approx(fit_mfu_counted.read(one))
    untraced = dict(counted, config=config(), rows=1_000, peaks=PEAKS, trace=None)
    for reader in (collective_share_pct, collectives_per_eval, fit_mfu_mesh):
        assert reader.read(untraced) is None
    telemetry.METRICS.reset()  # an earlier commit: nothing counted
    ops = [[f"%all-reduce.5 = {GRADIENT} all-reduce(%fusion.34)", 100, 50]]
    uncounted = traced(ops, dict(counted, warm_fit_timing={}))
    assert collectives_per_eval.read(uncounted) is None and fit_mfu_mesh.read(uncounted) is None
    assert collective_share_pct.read(uncounted) == pytest.approx(100.0)


# -- the cell through run.py, at a rehearsal size on the CPU ------------------

# test_criteo's limits for a rehearsal on the CPU, whose sequential float32
# scatter-add parts from the reference's by ~1e-4.
REHEARSAL_LIMITS = {"coef_gap.global": 2e-3, "metric_gap": 2e-4, "compiled_in_window": 0}


def test_the_cell_runs_every_step_and_a_sound_run_is_correct(capsys):
    argv = ["--workload", "lr-criteo-full.fit", "--seed", "2147483659", "--seconds", "0.2", "--trace", "0",
            "--rows", str(ROWS), "--control", "bfloat16,half_batch"]
    assert run.main(argv, chip_required=False, limits=REHEARSAL_LIMITS) == 0
    said = capsys.readouterr()
    result = json.loads(said.out.strip().splitlines()[-1])
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {"train_rows_per_s", "setup_s"}
    noted = json.loads(next(l for l in said.err.splitlines() if l.startswith("dispatch ")).split(" ", 1)[1])
    assert noted["sparse_objective"] == "ell_xla"
    controls = {l.split(" = ")[0]: float(l.split(" = ")[1]) for l in said.err.splitlines() if l.startswith("control[")}
    assert controls["control[half_batch] coef_gap.global"] > 0.05
    assert controls["control[bfloat16] coef_gap.global"] > 5e-5


def test_a_program_without_the_entry_is_refused_before_a_row_is_made(monkeypatch):
    from photon_ml_tpu.parallel import mesh

    monkeypatch.delattr(mesh, "sample_sharded_dataset")
    monkeypatch.setattr(criteo_shape_mesh, "_rows", None)  # would fail if a row were drawn
    with pytest.raises(SystemExit, match="sample_sharded_dataset"):
        criteo_shape_mesh.generate(config(), 7, rows=ROWS)
