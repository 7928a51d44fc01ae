"""The wide sparse configuration's own pieces: its generator, its reference
against a densified solve, its two readers, its work at the cell's shapes."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import trace_reduce, work
from benchmarks.generators import criteo_shape
from benchmarks.layers import fit_mfu_counted, sparse_vg_roofline
from benchmarks.references import glm_sparse_lbfgs, lbfgs, metrics
from photon_ml_tpu.utils import telemetry

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = {"hbm_gb_per_s": 819.0, "bf16_tflop_per_s": 197.0}


def config():
    with open(os.path.join(HERE, "..", "configs", "lr-criteo.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def problem():
    return criteo_shape.generate(config(), 3_000_000_019, rows=16_000)


def test_the_configuration_keeps_the_sources_widths():
    cfg = config()
    sizes = cfg["generator"]["field_sizes"]
    assert (cfg["features"], cfg["nnz_per_row"]) == (1_000_000, 39)
    assert len(sizes) == 39 and sum(sizes) == 1_000_000 and sizes[:13] == [40] * 13
    assert sorted(sizes[13:]) == sizes[13:] and sizes[13] < 10 and sizes[-1] > 300_000
    assert cfg["reduced"] == ["rows", "validation_rows"]


def test_rows_are_unit_norm_with_one_distinct_id_a_field(problem):
    cfg = config()
    starts, sizes = criteo_shape.field_ranges(cfg["generator"])
    for part, n in (("train", 16_000), ("validation", 2_000)):
        shard = problem[part]["shards"]["g"]
        idx, val = np.asarray(shard["indices"]), np.asarray(shard["values"])
        assert idx.shape == val.shape == (n, 39) and shard["dim"] == 1_000_000
        assert ((idx >= starts) & (idx < starts + sizes)).all()  # field f owns column f's range
        assert (val == np.float32(1 / math.sqrt(39))).all()
        assert np.allclose(np.linalg.norm(val, axis=1), 1.0, atol=1e-6)
    assert 0.2 < float(np.mean(np.asarray(problem["train"]["labels"]))) < 0.32


def test_the_seed_changes_pattern_and_numbers_and_never_the_shapes(problem):
    again = criteo_shape.generate(config(), 3_000_000_019, rows=16_000)
    other = criteo_shape.generate(config(), 5, rows=16_000)
    for part in ("train", "validation"):
        a, b, c = (p[part]["shards"]["g"]["indices"] for p in (problem, again, other))
        assert np.array_equal(a, b) and a.shape == c.shape and not np.array_equal(a, c)
        assert np.array_equal(problem[part]["labels"], again[part]["labels"])


def test_ids_are_heavy_tailed_within_a_field(problem):
    idx = np.asarray(problem["train"]["shards"]["g"]["indices"])
    wide = np.bincount(idx[:, -1])  # the widest field: 366,654 ids, 16,000 draws
    counts = np.sort(wide[wide > 0])[::-1]
    assert counts[0] > 0.05 * len(idx)  # one id sits in over a twentieth of the rows
    assert counts[:10].sum() > 0.2 * len(idx)
    assert (counts == 1).sum() > 0.5 * len(counts)  # and most ids seen are seen once
    assert np.bincount(idx[:, 0]).max() > 0.15 * len(idx)  # a numeric field, 40 ids: its top id in a fifth of the rows
    assert np.bincount(idx[:, 13]).max() > 0.35 * len(idx)  # the narrowest field, 4 ids: in over a third


def test_the_scramble_is_a_bijection_of_every_field():
    _, sizes = criteo_shape.field_ranges(config()["generator"])
    for size in sizes[[0, 13, 20, 30, 38]]:
        rank = jnp.arange(int(size), dtype=jnp.int32)
        for offset in (0, int(size) - 1):
            got = np.asarray(criteo_shape.scramble(rank, jnp.int32(offset), jnp.int32(size)))
            want = (criteo_shape.SCRAMBLE * np.arange(size, dtype=np.int64) + offset) % size
            assert np.array_equal(got, want)
            assert len(np.unique(got)) == size


def test_the_reference_agrees_with_a_densified_solve():
    """The same rows as a dense float32 matrix, the objective by matmul, the
    shared optimizer: another route to the same iterates."""
    cfg = config()
    cfg["features"], cfg["generator"]["field_sizes"] = 390, [10] * 39
    cfg["shards"]["g"]["dim"] = 390
    small = criteo_shape.generate(cfg, 11, rows=4_000)
    sparse = glm_sparse_lbfgs.solve(cfg, small)

    def dense(part):
        shard = small[part]["shards"]["g"]
        x = jnp.zeros((len(shard["indices"]), 390), jnp.float32)
        return x.at[jnp.arange(len(x))[:, None], shard["indices"]].add(shard["values"])

    x, y = dense("train"), small["train"]["labels"]

    def fun(W):
        w = W[0]
        z = jnp.dot(x, w, precision="highest")
        f = jnp.sum(jax.nn.softplus(z) - y * z) + 0.5 * jnp.dot(w, w)
        return f[None], (jnp.dot(jax.nn.sigmoid(z) - y, x, precision="highest") + w)[None]

    limit = cfg["coordinates"][0]["optimizer"]["max_iterations"]
    W, info = lbfgs.minimize(fun, jnp.zeros((1, 390), jnp.float32), max_iterations=limit, tolerance=1e-9)
    w = np.asarray(W[0])
    assert np.linalg.norm(sparse["coefficients"]["global"] - w) / np.linalg.norm(w) < 2e-5
    scores = np.asarray(jnp.dot(dense("validation"), W[0], precision="highest"))
    assert abs(sparse["metric"] - metrics.auc(scores, small["validation"]["labels"])) < 1e-5
    assert sparse["info"] == info and info["iterations"] == limit and 0.5 < sparse["metric"] < 1.0
    # And the control stands clear of that: bfloat16 values are 1.8e-4 off.
    control = glm_sparse_lbfgs.solve(cfg, small, storage="bfloat16")["coefficients"]["global"]
    assert np.linalg.norm(control - w) / np.linalg.norm(w) > 5e-5


def test_work_at_the_cells_shapes():
    cfg = config()
    w = work.fixed_effect_evaluation(cfg, cfg["rows"])
    assert w == work.sparse_value_gradient(8_000_000, 312_000_000, 1_000_000)
    assert w["bytes"] == 8 * 312_000_000 + 12 * 8_000_000 + 8 * 1_000_000
    seconds, binds = work.least_seconds(w, PEAKS)
    assert binds == "hbm" and seconds == pytest.approx(2.6e9 / 819e9)


# -- the two readers -------------------------------------------------------


@pytest.fixture
def counted():
    """A process that made a warm fit of 11 evaluations and two window fits of 11."""
    telemetry.METRICS.reset()
    telemetry.METRICS.increment(
        "objective_evaluations", 33, labels=(("coordinate", "global"), ("kind", "fixed"))
    )
    yield {"records": [{"seconds": 1.0}] * 2, "kinds": {"global": "fixed"},
           "warm_fit_timing": {"fn_evals": {"global": 11}}}
    telemetry.METRICS.reset()


def traced(ops, rows, counted):
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [["fit:0", 0, 4000]]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
    ]
    return dict(counted, config=config(), rows=rows, peaks=PEAKS,
                trace=trace_reduce.reduce(planes, n_units=1))


def test_the_readers_take_counted_evaluations_over_the_entry_streams_operations(counted):
    ops = [
        ["%while.3 = (s32[], f32[1000]) while(...)", 100, 3000],
        ["%fusion.7 = f32[39000]{0:T(1024)} fusion(f32[1000]{0}, s32[39512]{0})", 200, 500],  # gather, flat
        ["%fusion.9 = f32[1000]{0} fusion(f32[1000]{0}, s32[1000,39]{0,1}, f32[39,1000]{1,0})", 800, 700],  # scatter
        ["%fusion.2 = f32[1000]{0} fusion(f32[1000]{0}, f32[1000]{0})", 1600, 300],  # the optimizer's own
        ["%sparse_value_gradient.1 = f32[8]{0} custom-call(...)", 2000, 100],  # a kernel named for the stream
    ]
    run = traced(ops, 1_000, counted)
    least = (8 * 39_000 + 12 * 1_000 + 8 * 1_000_000) / 819e9
    # 11 evaluations in the one traced fit; 500 + 700 + 100 ns in the stream's operations.
    assert sparse_vg_roofline.read(run) == pytest.approx(100 * 11 * least / 1300e-9)
    assert fit_mfu_counted.read(run) == pytest.approx(100 * 11 * least / 4000e-9)


def test_no_matching_operation_reads_none_and_the_whole_fits_share_still_reads(counted):
    run = traced([["%fusion.2 = f32[1000]{0} fusion(f32[1000]{0})", 100, 300]], 1_000, counted)
    assert sparse_vg_roofline.read(run) is None
    assert fit_mfu_counted.read(run) is not None


def test_without_a_trace_or_a_count_the_readers_read_none(counted):
    run = dict(counted, config=config(), rows=1_000, peaks=PEAKS, trace=None)
    assert sparse_vg_roofline.read(run) is None and fit_mfu_counted.read(run) is None
    telemetry.METRICS.reset()  # an earlier commit: nothing counted
    ops = [["%fusion.7 = f32[39000]{0} fusion(...)", 200, 500]]
    run = traced(ops, 1_000, dict(counted, warm_fit_timing={}))
    assert sparse_vg_roofline.read(run) is None and fit_mfu_counted.read(run) is None


def test_a_dense_configuration_is_not_these_readers(counted):
    with open(os.path.join(HERE, "..", "configs", "lr-epsilon.json")) as f:
        dense = json.load(f)
    run = dict(traced([["%fusion.7 = f32[39000]{0} fusion(...)", 200, 500]], 1_000, counted), config=dense)
    assert sparse_vg_roofline.read(run) is None and fit_mfu_counted.read(run) is None


# -- the cell through run.py, at a rehearsal size on the CPU ------------------

# Limits fit for 16,000 rows on the CPU, whose sequential float32 scatter-add
# parts from the reference's by ~1e-4 (the chip's reads 4e-7 at the cell's
# size); half the rows read 0.2.
REHEARSAL_LIMITS = {"coef_gap.global": 2e-3, "metric_gap": 2e-4, "compiled_in_window": 0}


def drive(capsys, **kwargs):
    from benchmarks import run

    argv = ["--workload", "lr-criteo.fit", "--seed", "2147483659", "--seconds", "0.2", "--trace", "0", "--rows", "16000"]
    assert run.main(argv, **kwargs) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cell_runs_every_step_and_a_sound_run_is_correct(capsys):
    result = drive(capsys, chip_required=False, limits=REHEARSAL_LIMITS)
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {"train_rows_per_s", "setup_s"}  # no fit_p90_s: a fit a window


def test_half_the_rows_left_out_is_not_correct(capsys, monkeypatch):
    from benchmarks import run
    from benchmarks.drivers import refit

    sound = refit._dataset
    halves = iter([True, False])  # the train part is built first, then validation
    monkeypatch.setattr(refit, "_dataset", lambda part: sound(run.first_half(part) if next(halves) else part))
    result = drive(capsys, chip_required=False, limits=REHEARSAL_LIMITS)
    assert result["correct"] is False
    assert result["compared"]["coef_gap.global"]["value"] > 0.05
