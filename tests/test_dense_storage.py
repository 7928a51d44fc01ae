"""What a dense fixed effect keeps where the fused kernels engage, and how
the kernels are told it lies (`FixedEffectCoordinate.__init__`,
`pallas_glm.lies_row_major`, `LabeledData.column_major`).

The chip lays `[400000, 2000]` column-major by default and a kernel's
operand is constrained to row-major, so until PR 37 that matrix was relaid
in every execution of `train_fn`. Now the coordinate reads how its matrix
lies, once, from the concrete array, and the kernels read it so: (d, tile)
blocks of X^T where it lies column-major. The CPU backend's default is
row-major, but it holds a column-major array when told to
(`jax.device_put(x, Format(Layout((1, 0)), sharding))`), so the behaviour is
shown here: whole fits (kernels in interpret mode) from a column-major and a
row-major matrix, on one device and sample-sharded over the eight virtual
ones. `tests/test_pallas_glm.py` holds the kernels' side and
`tests/test_tpu_compile.py` the chip's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout

from photon_ml_tpu.data.game_dataset import FixedEffectDataConfig, GameDataset
from photon_ml_tpu.estimators.game_estimator import GameEstimator
from photon_ml_tpu.evaluation.suite import EvaluatorType
from photon_ml_tpu.game.coordinate import FixedEffectCoordinate
from photon_ml_tpu.ops import pallas_glm
from photon_ml_tpu.optimize.config import (
    CoordinateOptimizationConfig,
    OptimizerConfig,
    RegularizationContext,
)
from photon_ml_tpu.parallel.mesh import make_mesh, shard_game_dataset
from photon_ml_tpu.types import RegularizationType, TaskType

COLUMN_MAJOR, ROW_MAJOR = (1, 0), (0, 1)
DIM = 128
# Rows enough for the kernels to engage on one device, and on each of eight.
PLACEMENTS = {"one_device": 2 * pallas_glm._MIN_ROWS, "sample_sharded": 8 * pallas_glm._MIN_ROWS}


def lay(x, major_to_minor):
    return jax.device_put(x, Format(Layout(major_to_minor), x.sharding))


def order(x):
    return tuple(x.format.layout.major_to_minor)


def test_how_an_array_lies_is_read_from_the_array(rng):
    x = jnp.asarray(rng.normal(size=(640, DIM)).astype(np.float32))
    assert pallas_glm.lies_row_major(x) and not pallas_glm.lies_row_major(lay(x, COLUMN_MAJOR))
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh()
    rows = jax.device_put(x, NamedSharding(mesh, P(mesh.axis_names[0], None)))
    assert pallas_glm.lies_row_major(rows) and not pallas_glm.lies_row_major(lay(rows, COLUMN_MAJOR))


@pytest.fixture
def interpret_kernels(monkeypatch):
    monkeypatch.setattr(pallas_glm, "FORCE_INTERPRET", True)
    monkeypatch.setattr(pallas_glm, "_HEALTHY", False)


def dataset(rng, rows, placement, dtype=jnp.bfloat16):
    """Rows of a logistic model; the shard in `dtype` (a bfloat16 shard is
    kept by the coordinate as it is, so its layout is the test's to set:
    the CPU's `astype` would lay a cast of a float32 shard row-major)."""
    X = rng.normal(size=(rows, DIM)).astype(np.float32)
    X[:, -1] = 1.0
    margins = X @ (rng.normal(size=DIM) * 0.2)
    y = (rng.uniform(size=rows) < 1 / (1 + np.exp(-margins))).astype(np.float32)
    data = GameDataset.build({"g": jnp.asarray(X).astype(dtype)}, y)
    return shard_game_dataset(data, make_mesh()) if placement == "sample_sharded" else data


def fit(train, validation, shard_order):
    """One fit from a training shard laid `shard_order`: the estimator, the
    fixed effect's coordinate and its coefficients."""
    train.shards["g"] = lay(train.shards["g"], shard_order)
    train.bucketed_cache.clear()
    est = GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {"global": FixedEffectDataConfig("g")},
        coordinate_descent_iterations=1,
        validation_evaluators=[EvaluatorType.parse("AUC")],
    )
    opt = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=3, tolerance=1e-7),
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=1.0,
    )
    result = est.fit(train, validation, [{"global": opt}])[0]
    (coordinate,) = est._coordinate_cache.values()
    return est, coordinate, np.asarray(result.model["global"].coefficients.means)


@pytest.fixture(params=list(PLACEMENTS))
def two_fits(request, rng, interpret_kernels):
    """The same rows fitted from a column-major and from a row-major shard."""
    rows = PLACEMENTS[request.param]
    train = dataset(rng, rows, request.param)
    validation = dataset(rng, rows // 4, request.param)
    return request.param, train, [fit(train, validation, o) for o in (COLUMN_MAJOR, ROW_MAJOR)]


def test_both_matrices_train_to_the_same_coefficients_bit_for_bit(two_fits):
    placement, _, ((_, from_columns, w_columns), (_, from_rows, w_rows)) = two_fits
    sharded = isinstance(from_columns._use_pallas, pallas_glm.ShardedDispatch)
    assert sharded == (placement == "sample_sharded") and (sharded or from_columns._use_pallas is True)
    assert (from_columns._column_major, from_rows._column_major) == (True, False)
    assert np.abs(w_rows).max() > 0.01
    np.testing.assert_array_equal(w_columns, w_rows)


def test_the_coordinate_keeps_the_matrix_as_it_lies_and_the_fit_says_how(two_fits):
    _, train, fits = two_fits
    for (est, coordinate, _), laid in zip(fits, (COLUMN_MAJOR, ROW_MAJOR)):
        stored = coordinate.training_features
        assert order(stored) == laid and stored.dtype == jnp.bfloat16
        assert est.run_profile()["dispatch"]["dense_storage"] == {
            "layout": "column_major" if laid == COLUMN_MAJOR else "row_major", "dtype": "bfloat16", "bytes": 0,
        }
    # Nothing was made: the second fit's coordinate holds the shard itself.
    assert fits[1][1].training_features is train.shards["g"]


def test_the_compiled_solve_takes_a_column_major_matrix_as_it_lies(rng, interpret_kernels):
    """`train_fn`'s program for a column-major matrix: its parameter lies
    column-major and the kernel gets X^T, the (DIM, rows) array that such a
    matrix is."""
    train = dataset(rng, PLACEMENTS["one_device"], "one_device")
    _, coordinate, _ = fit(train, dataset(rng, 512, "one_device"), COLUMN_MAJOR)
    lowered = coordinate._train_fn.lower(
        coordinate.training_features, train.labels, train.offsets, train.weights,
        jnp.zeros((DIM,), jnp.float32), jnp.float32(1.0),
    )
    assert order(coordinate.training_features) == COLUMN_MAJOR
    assert tuple(lowered.compile().input_formats[0][0].layout.major_to_minor) == COLUMN_MAJOR
    assert f"bf16[{DIM},{train.num_samples}]" in lowered.as_text("hlo")


def test_a_float32_shard_is_stored_bfloat16_and_read_as_that_lies(rng, interpret_kernels):
    train = dataset(rng, PLACEMENTS["one_device"], "one_device", jnp.float32)
    validation = dataset(rng, 512, "one_device", jnp.float32)
    est, coordinate, _ = fit(train, validation, COLUMN_MAJOR)
    stored = coordinate.training_features
    # The cast is the coordinate's own array and lies as the backend lays
    # it (row-major here, whatever the shard); a second coordinate on the
    # same rows converts nothing.
    assert stored.dtype == jnp.bfloat16 and stored is not train.shards["g"]
    assert coordinate._column_major == (order(stored) == COLUMN_MAJOR)
    assert est.run_profile()["dispatch"]["dense_storage"] == {
        "layout": "row_major", "dtype": "bfloat16", "bytes": train.num_samples * DIM * 2,
    }
    again = FixedEffectCoordinate(train, "g", coordinate.config, TaskType.LOGISTIC_REGRESSION)
    assert again.training_features is stored
    assert order(train.shards["g"]) == COLUMN_MAJOR and train.shards["g"].dtype == jnp.float32


def test_a_matrix_the_kernels_read_as_float32_is_read_as_it_lies_too(rng, interpret_kernels, monkeypatch):
    monkeypatch.setenv("PHOTON_DENSE_BF16X", "0")
    train = dataset(rng, PLACEMENTS["one_device"], "one_device", jnp.float32)
    validation = dataset(rng, 512, "one_device", jnp.float32)
    fits = [fit(train, validation, o) for o in (COLUMN_MAJOR, ROW_MAJOR)]
    for (est, coordinate, _), laid in zip(fits, (COLUMN_MAJOR, ROW_MAJOR)):
        assert coordinate._use_pallas is True and order(coordinate.training_features) == laid
        assert est.run_profile()["dispatch"]["dense_storage"] == {
            "layout": "column_major" if laid == COLUMN_MAJOR else "row_major", "dtype": "float32", "bytes": 0,
        }
    np.testing.assert_array_equal(fits[0][2], fits[1][2])


def test_where_the_kernels_do_not_engage_nothing_is_noted(rng):
    train = dataset(rng, PLACEMENTS["one_device"], "one_device", jnp.float32)
    est, coordinate, _ = fit(train, dataset(rng, 512, "one_device", jnp.float32), COLUMN_MAJOR)
    assert coordinate._use_pallas is False and not coordinate._column_major
    assert coordinate.training_features is train.shards["g"]
    assert est.run_profile()["dispatch"]["dense_storage"] == "none"
