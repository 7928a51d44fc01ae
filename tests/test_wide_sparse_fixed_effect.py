"""A wide sparse fixed effect (the Criteo shape: a million hashed features, 39
non-zeros a row) through the main path.

Three things: the fit through `GameEstimator` equals the benchmark's plain
reference on seeded data; the bucketed-pack decision for such a shard is taken
from its shapes alone, before any array is read, and the ELL objective it
leaves is recorded by name with the reason; shards that packed before still
pack, and into the planes they gave before.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.generators import criteo_shape
from benchmarks.references import glm_sparse_lbfgs
from photon_ml_tpu.data import bucketed
from photon_ml_tpu.data.containers import SparseFeatures
from photon_ml_tpu.data.game_dataset import FixedEffectDataConfig, GameDataset
from photon_ml_tpu.estimators.game_estimator import GameEstimator
from photon_ml_tpu.evaluation.suite import EvaluatorType
from photon_ml_tpu.ops import pallas_glm, pallas_sparse
from photon_ml_tpu.optimize.config import (
    CoordinateOptimizationConfig,
    OptimizerConfig,
    RegularizationContext,
)
from photon_ml_tpu.types import OptimizerType, RegularizationType, TaskType
from photon_ml_tpu.utils import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The benchmark's configuration, a fifth as wide: the same 39 fields, 13 of 40
# ids and a ladder of 26 that ends in a field of 100,000.
FIELDS = [40] * 13 + [4, 6, 9, 13, 19, 28, 41, 60, 88, 129, 189, 277, 406, 595, 872, 1278, 1873,
                      2745, 4023, 5896, 8641, 12664, 18560, 27201, 39865, 73998]
ROWS, DIM = 20_000, 200_000


def small_config():
    with open(os.path.join(ROOT, "benchmarks", "configs", "lr-criteo.json")) as f:
        config = json.load(f)
    assert sum(FIELDS) == DIM
    config["features"] = config["shards"]["g"]["dim"] = DIM
    config["generator"]["field_sizes"] = FIELDS
    return config


@pytest.fixture(scope="module")
def problem():
    return criteo_shape.generate(small_config(), 2_147_483_659, rows=ROWS)


def dataset(part):
    shard = part["shards"]["g"]
    feats = SparseFeatures(jnp.asarray(shard["indices"]), jnp.asarray(shard["values"]), shard["dim"])
    return GameDataset.build({"g": feats}, part["labels"])


def estimator(config):
    coordinate = config["coordinates"][0]
    opt = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(
            optimizer_type=OptimizerType.LBFGS,
            max_iterations=coordinate["optimizer"]["max_iterations"],
            tolerance=coordinate["optimizer"]["tolerance"],
        ),
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=coordinate["reg_weight"],
    )
    est = GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {"global": FixedEffectDataConfig("g")},
        coordinate_descent_iterations=1,
        validation_evaluators=[EvaluatorType.parse("AUC")],
    )
    return est, {"global": opt}


@pytest.fixture(scope="module")
def fitted(problem):
    est, opt = estimator(small_config())
    result = est.fit(dataset(problem["train"]), dataset(problem["validation"]), [opt])[0]
    return est, result


@pytest.fixture(scope="module")
def reference(problem):
    return glm_sparse_lbfgs.solve(small_config(), problem)


# Float32 sums of up to 9,000 addends a feature, in another order on each
# side, through the L-BFGS iterations: the two part by 1e-5 to 1e-4 here.
# bfloat16 values (the benchmark's control) part by 1.5e-4 from either.
COEFFICIENT_TOLERANCE = 3e-4
AUC_TOLERANCE = 2e-5


@pytest.mark.parametrize("what", ["coefficients", "auc", "iterations"])
def test_the_fit_equals_the_plain_reference(fitted, reference, what):
    est, result = fitted
    if what == "coefficients":
        w = np.asarray(result.model["global"].coefficients.means, np.float64)
        ref = np.asarray(reference["coefficients"]["global"], np.float64)
        assert w.shape == (DIM,) and np.linalg.norm(ref) > 1.0
        assert np.linalg.norm(w - ref) / np.linalg.norm(ref) < COEFFICIENT_TOLERANCE
    elif what == "auc":
        assert 0.55 < reference["metric"] < 1.0
        assert abs(float(result.evaluation.primary_value) - reference["metric"]) < AUC_TOLERANCE
    else:  # the iteration limit binds on both sides, every first trial is accepted:
        # the first evaluation, then one value+gradient evaluation a trial
        limit = small_config()["coordinates"][0]["optimizer"]["max_iterations"]
        assert reference["info"] == {"iterations": limit, "evaluations": 1 + limit}
        assert est.fit_timing["fn_evals"]["global"] == 1 + limit


def test_a_fit_on_the_cpu_names_the_ell_objective(fitted):
    est, _ = fitted
    dispatch = est.run_profile()["dispatch"]
    assert dispatch["sparse_objective"] == "ell_xla"
    assert dispatch["pack_declined"] == "none"  # no kernels here: no pack was considered


# -- the pack decision -----------------------------------------------------


class Unreadable:
    """A plane that knows its shape and fails on any attempt to read it."""

    def __init__(self, shape, dtype):
        self.shape, self.dtype, self.ndim = shape, np.dtype(dtype), len(shape)
        self.size = int(np.prod(shape))

    def __len__(self):
        return self.shape[0]

    def __array__(self, *args, **kwargs):
        raise AssertionError("the pack decision read the shard's arrays")

    def __getitem__(self, item):
        raise AssertionError("the pack decision read the shard's arrays")


@pytest.fixture
def kernels_eligible(monkeypatch):
    monkeypatch.setattr(pallas_glm, "FORCE_INTERPRET", True)
    assert pallas_sparse.kernels_eligible()
    telemetry.METRICS.reset()
    yield
    telemetry.METRICS.reset()


def declined():
    return telemetry.METRICS.labeled_counters("sparse_pack_declined")


@pytest.mark.parametrize(
    "n, k, dim, dtype, reason",
    [
        (8_000_000, 39, 1_000_000, np.float32, "pad_blowup"),  # the benchmark's cell
        (ROWS, 39, DIM, np.float32, "pad_blowup"),  # this file's fit
        (4_000, 39, 201, np.float32, "too_small"),
        (16_384, 9, 201, np.float64, "dtype"),
    ],
)
def test_the_pack_is_declined_from_shapes_without_reading_the_arrays(kernels_eligible, n, k, dim, dtype, reason):
    feats = SparseFeatures(Unreadable((n, k), np.int32), Unreadable((n, k), dtype), dim)
    assert pallas_sparse.maybe_pack(feats, n) is None
    assert declined() == {f"reason={reason}": 1}
    assert pallas_sparse.pack_decline_reason(n, n * k, dim, dtype) == reason


def test_an_ingest_stash_is_declined_before_its_coo_expansion(kernels_eligible):
    from photon_ml_tpu.data.game_dataset import HostCSR

    class Stash(HostCSR):
        def to_coo(self):
            raise AssertionError("the pack decision expanded the stash")

    n, k = 40_000, 39
    csr = Stash(np.arange(n + 1, dtype=np.int64) * k, Unreadable((n * k,), np.int64),
                Unreadable((n * k,), np.float32), 1_000_000)
    pallas_sparse.begin_pack_async(csr, n)
    assert csr.pack_future is None
    assert pallas_sparse.finish_pack(csr, n) is None
    assert declined() == {"reason=pad_blowup": 1}


def test_the_coordinate_records_the_ell_objective_and_the_reason(kernels_eligible, problem, monkeypatch):
    def never(*args, **kwargs):
        raise AssertionError("the pack ran")

    monkeypatch.setattr(bucketed, "pack_from_ell", never)
    monkeypatch.setattr(bucketed, "pack_bucketed", never)
    est, opt = estimator(small_config())
    est.fit(dataset(problem["train"]), None, [opt])
    dispatch = est.run_profile()["dispatch"]
    assert (dispatch["sparse_objective"], dispatch["pack_declined"]) == ("ell_xla", "pad_blowup")
    assert declined() == {"reason=pad_blowup": 1}
    assert "sparse_pack_declined" in telemetry.METRIC_DESCRIPTIONS


def seeded_shard(n, k, dim, seed):
    rng = np.random.default_rng(seed)
    # k distinct ids a row: a sorted draw with repeats from [0, dim - k] plus 0..k-1.
    idx = np.sort(rng.integers(0, dim - k + 1, size=(n, k)), axis=1) + np.arange(k)
    return SparseFeatures(jnp.asarray(idx, jnp.int32), jnp.asarray(rng.standard_normal((n, k)), jnp.float32), dim)


@pytest.mark.parametrize(
    "n, k, dim",
    [(8_192, 64, 16_384), (16_384, 9, 201)],
    ids=["d16384_nnz64", "movielens_shape"],
)
def test_shards_that_packed_before_pack_into_the_same_planes(kernels_eligible, n, k, dim):
    feats = seeded_shard(n, k, dim, seed=n + k)
    assert pallas_sparse.pack_can_pay(n * k, n, dim)
    got = pallas_sparse.maybe_pack(feats, n)
    want = bucketed.pack_from_ell(feats)  # what the decision used to run first, whatever came of it
    assert got is not None and declined() == {}
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))
    # And the planes still hold the shard: decoded, they are its entries.
    rows, cols, vals = bucketed.to_coo(got)
    order = np.lexsort((cols, rows))
    assert np.array_equal(cols[order].reshape(n, k), np.asarray(feats.indices))
    assert np.array_equal(vals[order].reshape(n, k), np.asarray(feats.values))


@pytest.mark.parametrize(
    "nnz, n, dim, pays",
    [
        (64_000_000, 1_000_000, 16_384, True),  # the kernels' own bench shape: blowup 1.0
        (36_000_000, 4_000_000, 201, True),  # MovieLens at the benchmark's rows
        (312_000_000, 8_000_000, 1_000_000, False),  # 31.3 G slots for 0.3 G entries
        (256, 64, 32, True),  # one segment of 1,024 slots for 256 entries: exactly 4
        (255, 64, 32, False),
    ],
)
def test_the_shape_predicate_is_the_floor_under_the_blowup(nnz, n, dim, pays):
    assert pallas_sparse.pack_can_pay(nnz, n, dim) is pays
    tiles, buckets = -(-n // bucketed.L1_TILE_ROWS), -(-dim // bucketed.BUCKET)
    assert bucketed.min_level1_slots(n, dim) == tiles * buckets * bucketed.MIN_SP


# -- the scopes a device trace names the ELL objective's operations by -------


def compiled_text(jitted, *args):
    return jitted.lower(*args).compile().as_text()


@pytest.mark.parametrize(
    "program, scopes, operation",
    [
        ("train", ("fe_solve", "objective"), "gather"),
        ("train", ("fe_solve", "objective"), "scatter-add"),
        ("score", ("score/fixed",), "gather"),
    ],
)
def test_the_scopes_cover_the_ell_gather_and_scatter(problem, program, scopes, operation):
    from photon_ml_tpu.game.coordinate import FixedEffectCoordinate
    from photon_ml_tpu.transformers.game_transformer import _fe_margins

    data = dataset(problem["validation"])  # 2,500 rows: the programs are the same, smaller
    _, opt = estimator(small_config())
    coordinate = FixedEffectCoordinate(data, "g", opt["global"], TaskType.LOGISTIC_REGRESSION)
    w = jnp.zeros((DIM,), jnp.float32)
    if program == "train":
        text = compiled_text(
            coordinate._train_fn, coordinate.training_features, data.labels, data.offsets,
            data.weights, w, jnp.float32(1.0), jax.random.PRNGKey(0),
        )
    else:
        text = compiled_text(_fe_margins, coordinate.training_features, w, None)
    names = [line.split('op_name="', 1)[1].split('"', 1)[0] for line in text.splitlines() if 'op_name="' in line]
    # Each is one plane's, in the loop over the planes that the innermost scope holds.
    plane_loop = f"/{scopes[-1]}/while/body/"
    found = [
        n for n in names
        if n.endswith(operation) and plane_loop in n and all(f"/{s}/" in n for s in scopes)
    ]
    assert found, f"no {operation} under {scopes} in a plane loop among {sorted(set(names))[:20]}"
