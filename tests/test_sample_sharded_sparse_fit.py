"""A wide sparse fixed effect whose rows lie on several devices, a part a
device (`parallel/mesh.sample_sharded_dataset`), through the main path.

The rows come from the benchmark's mesh generator (an odd count over four of
the eight virtual devices, so the last part ends in pad rows). Four things:
the fit equals the benchmark's plain reference and the one-device fit of the
same rows; pad rows weigh nothing in loss, gradient and AUC, whether the
caller or the entry made them; the compiled solve reduces value and gradient
over the devices once an evaluation and gathers no plane; the fit says so
(`sample_sharding`, `gradient_allreduce_bytes`) and a one-device fit does not.
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.generators import criteo_shape_mesh
from benchmarks.references import glm_sparse_lbfgs, glm_sparse_lbfgs_mesh
from photon_ml_tpu.data.containers import LabeledData, SparseFeatures
from photon_ml_tpu.data.game_dataset import GameDataset
from photon_ml_tpu.evaluation.suite import EvaluatorType, evaluate_metrics
from photon_ml_tpu.game.coordinate import FixedEffectCoordinate
from photon_ml_tpu.ops import objective, pallas_glm
from photon_ml_tpu.ops.losses import LOGISTIC
from photon_ml_tpu.parallel.mesh import (
    make_mesh,
    sample_sharded_dataset,
    shard_game_dataset,
)
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils import telemetry
from tests.test_wide_sparse_fixed_effect import DIM, FIELDS, estimator

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The benchmark's configuration, a fifth as wide (test_wide_sparse_fixed_effect's
# fields and estimator), 19,997 rows over four devices: 5,000 a device, 3 pad rows.
ROWS, DEVICES = 19_997, 4
PER_DEVICE, PAD_ROWS = 5_000, 3


def small_config():
    with open(os.path.join(ROOT, "benchmarks", "configs", "lr-criteo-full.json")) as f:
        config = json.load(f)
    assert sum(FIELDS) == DIM
    config["features"] = config["shards"]["g"]["dim"] = DIM
    config["generator"]["field_sizes"] = FIELDS
    return config


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(jax.devices()[:DEVICES])


@pytest.fixture(scope="module")
def problem():
    return criteo_shape_mesh.generate(small_config(), 2_147_483_659, rows=ROWS)


def device_parts(part, real_rows_only):
    """One GameDataset a device from a part of the problem; with
    `real_rows_only` the pad rows the generator made are cut off, so that the
    program's entry has to make them."""
    shard = part["shards"]["g"]
    out = []
    for idx, val, y, w in zip(shard["indices"].parts, shard["values"].parts, part["labels"].parts, part["weights"].parts):
        n = int(np.asarray(w).sum()) if real_rows_only else len(y)
        out.append(GameDataset.build({"g": SparseFeatures(idx[:n], val[:n], shard["dim"])}, y[:n], weights=w[:n]))
    return out


def one_device(part):
    """The same real rows as one data set on one device."""
    shard = part["shards"]["g"]
    whole = lambda per_chip: jnp.concatenate([jax.device_put(a, jax.devices()[0]) for a in per_chip.parts])
    real = np.flatnonzero(np.asarray(whole(part["weights"])))
    feats = SparseFeatures(whole(shard["indices"])[real], whole(shard["values"])[real], shard["dim"])
    return GameDataset.build({"g": feats}, whole(part["labels"])[real])


@pytest.fixture(scope="module")
def sharded_data(problem, mesh):
    return (
        sample_sharded_dataset(device_parts(problem["train"], real_rows_only=True), mesh),
        sample_sharded_dataset(device_parts(problem["validation"], real_rows_only=True), mesh),
    )


@pytest.fixture(scope="module")
def sharded_fit(sharded_data):
    telemetry.METRICS.reset()
    est, opt = estimator(small_config())
    result = est.fit(*sharded_data, [opt])[0]
    counted = telemetry.METRICS.labeled_counters("gradient_allreduce_bytes")
    return est, result, counted


@pytest.fixture(scope="module")
def single_fit(problem):
    telemetry.METRICS.reset()
    est, opt = estimator(small_config())
    result = est.fit(one_device(problem["train"]), one_device(problem["validation"]), [opt])[0]
    counted = telemetry.METRICS.labeled_counters("gradient_allreduce_bytes")
    return est, result, counted


def coefficients(result):
    return np.asarray(result.model["global"].coefficients.means, np.float64)


# -- the entry ---------------------------------------------------------------


def test_the_entry_pads_the_short_part_where_it_lies_and_copies_nothing_whole(sharded_data, mesh):
    train, validation = sharded_data
    assert (train.num_samples, train.pad_rows) == (DEVICES * PER_DEVICE, PAD_ROWS)
    assert (validation.num_samples, validation.pad_rows) == (2_500, 1)
    feats = train.shards["g"]
    for array in (feats.indices, feats.values, train.labels, train.offsets, train.weights):
        shards = array.addressable_shards
        assert [s.device for s in shards] == list(mesh.devices.flat)
        assert {s.data.shape[0] for s in shards} == {PER_DEVICE}
    weights = np.asarray(train.weights)
    assert weights[: ROWS].all() and not weights[ROWS:].any()
    assert not np.asarray(feats.indices)[ROWS:].any() and not np.asarray(feats.values)[ROWS:].any()


def test_build_places_default_offsets_and_weights_as_the_labels(mesh):
    labels = jax.device_put(jnp.arange(8.0), jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data")))
    sharded = GameDataset.build({}, labels)
    assert sharded.offsets.sharding == sharded.weights.sharding == labels.sharding
    elsewhere = GameDataset.build({}, jax.device_put(jnp.arange(8.0), jax.devices()[3]))
    assert elsewhere.offsets.devices() == elsewhere.weights.devices() == {jax.devices()[3]}


def test_a_data_set_from_one_device_goes_through_the_same_entry(problem, mesh):
    whole = one_device(problem["train"])
    sharded = shard_game_dataset(whole, mesh)
    assert (sharded.num_samples, sharded.pad_rows) == (DEVICES * PER_DEVICE, PAD_ROWS)
    assert np.array_equal(np.asarray(sharded.shards["g"].indices)[:ROWS], np.asarray(whole.shards["g"].indices))
    assert len(sharded.labels.sharding.device_set) == DEVICES


# -- the fit -----------------------------------------------------------------

# test_wide_sparse_fixed_effect's tolerances against the plain reference: float32
# sums of up to 9,000 addends a feature in another order on each side.
COEFFICIENT_TOLERANCE = 3e-4
AUC_TOLERANCE = 2e-5


@pytest.mark.parametrize("reference", ["mesh", "one_device"])
@pytest.mark.parametrize("what", ["coefficients", "auc"])
def test_the_sharded_fit_equals_the_plain_reference(problem, sharded_fit, reference, what):
    config = small_config()
    if reference == "mesh":
        solved = glm_sparse_lbfgs_mesh.solve(config, problem)
    else:  # the accepted one-chip reference on the same real rows, gathered
        def gathered(part):
            data = one_device(part)
            shard = {"indices": data.shards["g"].indices, "values": data.shards["g"].values, "dim": DIM}
            return {"shards": {"g": shard}, "labels": data.labels, "id_tags": {}}

        rows = {"train": gathered(problem["train"]), "validation": gathered(problem["validation"])}
        solved = glm_sparse_lbfgs.solve(dict(config, reference={"row_block": ROWS}), rows)
    _, result, _ = sharded_fit
    if what == "coefficients":
        ref = np.asarray(solved["coefficients"]["global"], np.float64)
        assert np.linalg.norm(ref) > 1.0
        assert np.linalg.norm(coefficients(result) - ref) / np.linalg.norm(ref) < COEFFICIENT_TOLERANCE
    else:
        assert 0.55 < solved["metric"] < 1.0
        assert abs(float(result.evaluation.primary_value) - solved["metric"]) < AUC_TOLERANCE


@pytest.mark.parametrize("what", ["coefficients", "auc", "evaluations"])
def test_the_sharded_fit_equals_the_one_device_fit(sharded_fit, single_fit, assert_sharded_close, what):
    (est4, sharded, _), (est1, single, _) = sharded_fit, single_fit
    if what == "coefficients":
        assert_sharded_close(coefficients(sharded), coefficients(single), "fit")
    elif what == "auc":
        assert_sharded_close(sharded.evaluation.primary_value, single.evaluation.primary_value, "fit")
    else:
        assert est4.fit_timing["fn_evals"] == est1.fit_timing["fn_evals"] == {"global": 4}


# -- the devices' shares and the pad rows -----------------------------------


def value_and_gradient(data, w, dispatch, annotated=False):
    feats = data.annotated_shard("g") if annotated else data.shards["g"]
    assert bool(feats.span_classes) == annotated
    rows = LabeledData(feats, data.labels, data.offsets, data.weights)
    return jax.jit(lambda w: objective.value_and_gradient(LOGISTIC, w, rows, None, 1.0, use_pallas=dispatch))(w)


@pytest.mark.parametrize("planes", ["gathered", "dense_span"])
@pytest.mark.parametrize("pads_by", ["the_entry", "the_caller"])
def test_the_devices_shares_add_up_to_the_one_device_objective(problem, sharded_data, mesh, pads_by, planes):
    """Value and gradient summed over the four devices' rows, pad rows among
    them, are the one-device objective over the real rows alone: with every
    plane gathered, and with the narrow planes' spans read from the global
    arrays (the pad rows' index 0 stretches no span: 30 of 39 are narrow) and
    their least ids riding replicated into every device's loops."""
    if pads_by == "the_entry":
        train = sharded_data[0]
    else:
        train = sample_sharded_dataset(device_parts(problem["train"], real_rows_only=False), mesh)
        assert train.num_samples == DEVICES * PER_DEVICE
    w = jax.random.normal(jax.random.PRNGKey(3), (DIM,), jnp.float32)
    f4, g4 = value_and_gradient(train, w, pallas_glm.ShardedDispatch(mesh, "data"), planes == "dense_span")
    if planes == "dense_span":
        feats = train.annotated_shard("g")
        assert sum(1 for c in feats.span_classes if c) == 30 and feats.span_classes[13] == 128
        assert feats.span_lo.sharding.is_fully_replicated and len(feats.span_lo.sharding.device_set) == DEVICES
    f1, g1 = value_and_gradient(one_device(problem["train"]), w, False)
    np.testing.assert_allclose(float(f4), float(f1), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(g4), np.asarray(g1), rtol=1e-4, atol=2e-5)
    # Each device's share alone: the same expression on its own rows.
    feats = train.shards["g"]
    shares = []
    for i in range(DEVICES):
        part = lambda a: jax.device_put(a.addressable_shards[i].data, jax.devices()[0])
        own = LabeledData(
            SparseFeatures(part(feats.indices), part(feats.values), DIM),
            part(train.labels), part(train.offsets), part(train.weights),
        )
        shares.append(objective.value_and_gradient(LOGISTIC, w, own, None, 0.0, use_pallas=False))
    np.testing.assert_allclose(float(sum(s[0] for s in shares) + 0.5 * jnp.dot(w, w)), float(f4), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(sum(s[1] for s in shares) + w), np.asarray(g4), rtol=1e-4, atol=2e-5)


def test_pad_rows_weigh_nothing_in_the_auc(problem, sharded_data):
    validation = sharded_data[1]
    single = one_device(problem["validation"])
    scores = jax.random.normal(jax.random.PRNGKey(5), (validation.num_samples,), jnp.float32)
    scores = jax.device_put(scores, validation.labels.sharding)
    auc = (EvaluatorType.parse("AUC"),)
    padded = evaluate_metrics(auc, scores, validation.labels, validation.weights, {})
    real = single.num_samples
    plain = evaluate_metrics(auc, np.asarray(scores)[:real], single.labels, single.weights, {})
    assert real == validation.num_samples - validation.pad_rows
    np.testing.assert_allclose(np.asarray(padded), np.asarray(plain), rtol=1e-6)


# -- the compiled solve ------------------------------------------------------

COLLECTIVE = re.compile(r" = (.+?) (all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)(?:-start)?\(")


def test_the_solve_reduces_once_an_evaluation_and_gathers_no_plane(sharded_data):
    """L-BFGS's program holds two evaluations, the first and the line search's:
    each has one all-reduce, of the value and the (d,) gradient together, and
    the program has no other collective: no plane, and nothing of the rows'
    size, crosses devices."""
    train = sharded_data[0]
    _, opt = estimator(small_config())
    coordinate = FixedEffectCoordinate(train, "g", opt["global"], TaskType.LOGISTIC_REGRESSION)
    assert isinstance(coordinate._use_pallas, pallas_glm.ShardedDispatch)
    assert sum(1 for c in coordinate.training_features.span_classes if c) == 30  # the dense-span loops are in it
    text = coordinate._train_fn.lower(
        coordinate.training_features, train.labels, train.offsets, train.weights,
        jnp.zeros((DIM,), jnp.float32), jnp.float32(1.0), jax.random.PRNGKey(0),
    ).compile().as_text()
    found = [(m.group(2), m.group(1), line) for line in text.splitlines() for m in [COLLECTIVE.search(line)] if m]
    assert [kind for kind, _, _ in found] == ["all-reduce"] * 2, [f[:2] for f in found]
    for _, shapes, line in found:
        assert f"f32[{DIM}]" in shapes and "f32[]" in shapes, shapes
        assert "objective/" in line and "allreduce/psum" in line, line
    depths = sorted(line.count("while/body") for _, _, line in found)
    assert depths[0] == 0 and depths[1] >= 2, depths  # before the loops; inside the line search


@pytest.mark.parametrize("planes", ["gathered", "dense_span"])
def test_scoring_sharded_rows_crosses_no_device(sharded_data, planes):
    from photon_ml_tpu.transformers.game_transformer import _fe_margins

    validation = sharded_data[1]
    feats = validation.annotated_shard("g") if planes == "dense_span" else validation.shards["g"]
    assert bool(feats.span_classes) == (planes == "dense_span")
    w = jnp.zeros((DIM,), jnp.float32)
    compiled = _fe_margins.lower(feats, w, None).compile()
    assert not COLLECTIVE.search(compiled.as_text())
    scores = _fe_margins(feats, w, None)
    assert scores.sharding.is_equivalent_to(validation.labels.sharding, 1)


# -- what the fit says of itself --------------------------------------------


def test_a_sharded_fit_notes_its_sharding_and_counts_its_reduction(sharded_fit):
    est, _, counted = sharded_fit
    dispatch = est.run_profile()["dispatch"]
    assert dispatch["sample_sharding"] == {"devices": DEVICES, "rows_per_device": PER_DEVICE, "pad_rows": PAD_ROWS}
    assert dispatch["sparse_objective"] == "ell_xla"
    nbytes = est.fit_timing["fn_evals"]["global"] * 4 * (DIM + 1)
    assert est.fit_timing["gradient_allreduce_bytes"] == {"global": nbytes}
    assert counted == {"coordinate=global": nbytes}
    assert "gradient_allreduce_bytes" in telemetry.METRIC_DESCRIPTIONS


def test_a_one_device_fit_says_nothing_of_sharding(single_fit):
    est, _, counted = single_fit
    assert est.run_profile()["dispatch"]["sample_sharding"] == "none"
    assert est.fit_timing["gradient_allreduce_bytes"] == {} and counted == {}
