"""A wide sparse fixed effect (the Criteo shape: a million hashed features, 39
non-zeros a row) through the main path.

Three things: the fit through `GameEstimator` equals the benchmark's plain
reference on seeded data; the bucketed-pack decision for such a shard is taken
from its shapes alone, before any array is read, and the ELL objective it
leaves is recorded by name with the reason; shards that packed before still
pack, and into the planes they gave before.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.generators import criteo_shape
from benchmarks.references import glm_sparse_lbfgs
from photon_ml_tpu.data import bucketed, containers
from photon_ml_tpu.data.containers import SparseFeatures, annotate_spans, span_note
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


# -- a narrow plane is a dense span, not a gather ------------------------------

def field_major_shard(sizes, n, seed, lows=None, pad_share=0.0, dim=None):
    """(n, K) ids, a field a plane: plane k's ids uniform over
    [lows[k], lows[k] + sizes[k]) with both ends present, fields back to back
    unless `lows` says otherwise; a `pad_share` of the slots padding."""
    rng = np.random.default_rng(seed)
    lows = np.cumsum([0] + list(sizes[:-1])) if lows is None else np.asarray(lows)
    idx = np.stack([lo + rng.integers(0, size, n) for lo, size in zip(lows, sizes)], 1).astype(np.int32)
    idx[0], idx[1] = lows, lows + np.asarray(sizes) - 1
    val = rng.normal(size=idx.shape).astype(np.float32)
    pad = rng.random(idx.shape) < pad_share
    pad[:2] = False
    idx[pad], val[pad] = 0, 0.0
    dim = int(lows[-1] + sizes[-1]) if dim is None else dim
    return SparseFeatures(jnp.asarray(idx), jnp.asarray(val), dim)


# name -> (shard, the classes its planes must read)
SPAN_CASES = {
    # the intercept every Photon ML row carries: one id
    "intercept": lambda: (field_major_shard([1, 3000], 700, 1), (128, 0)),
    # 130 ids from id 100 on: over one row of lanes, and across ids 128 and 256
    "crosses_a_class_boundary": lambda: (field_major_shard([130, 4000], 700, 2, lows=[100, 230]), (256, 0)),
    # the last field ends at dim - 1 and its class reaches past the end of the coefficients
    "ends_at_dim_minus_1": lambda: (field_major_shard([5000, 70], 700, 3), (0, 128)),
    # padding slots (index 0, value 0.0) in planes whose spans do not hold id 0
    "padding_outside_the_span": lambda: (
        field_major_shard([40, 300, 5000], 900, 4, lows=[5, 45, 345], pad_share=0.2, dim=5400), (128, 512, 0)),
    # the ladder of classes, narrow planes between wide ones, a plane that is all padding
    "every_class_interleaved": lambda: (
        with_an_empty_last_plane(field_major_shard([3000, 100, 129, 5000, 500, 1000, 1025, 7], 600, 5, pad_share=0.1)),
        (0, 128, 256, 0, 512, 1024, 2048, 128)),
    # a field wider than the limit by one id keeps the gather
    "one_id_over_the_limit": lambda: (field_major_shard([2049, 2048, 3000], 700, 9), (0, 2048, 0)),
}


def with_an_empty_last_plane(feats):
    idx, val = np.array(feats.indices), np.array(feats.values)
    idx[:, -1], val[:, -1] = 0, 0.0
    return SparseFeatures(jnp.asarray(idx), jnp.asarray(val), feats.dim)


@pytest.mark.parametrize("product", ["matvec", "rmatvec", "sq_rmatvec"])
@pytest.mark.parametrize("case", sorted(SPAN_CASES))
def test_the_dense_span_products_equal_the_gathered_ones(case, product):
    plain, classes = SPAN_CASES[case]()
    annotated = annotate_spans(plain)
    assert annotated.span_classes == classes
    assert plain.span_classes == () and plain.span_lo is None
    rng = np.random.default_rng(11)
    n, dim = plain.shape
    operand = jnp.asarray(rng.normal(size=dim if product == "matvec" else n).astype(np.float32))
    run = jax.jit(lambda feats, x: getattr(feats, product)(x))
    want, got = np.asarray(run(plain, operand)), np.asarray(run(annotated, operand))
    if product == "matvec":
        # One term of a span's sum is non-zero, so a narrow plane's margins are
        # the gathered ones to the bit; the planes are added narrow first, so
        # the row sums may differ in the last place.
        np.testing.assert_allclose(got, want, rtol=0, atol=4e-6)
        one = jax.jit(lambda feats, x, k: containers._with_spans(
            dataclasses.replace(feats, indices=feats.indices[:, k:k + 1], values=feats.values[:, k:k + 1]),
            None if feats.span_lo is None else feats.span_lo[k:k + 1], feats.span_classes[k:k + 1],
        ).matvec(x), static_argnums=2)
        for k, c in enumerate(classes):
            if c:
                assert np.array_equal(np.asarray(one(annotated, operand, k)), np.asarray(one(plain, operand, k))), k
    else:
        # The transpose scatter-adds every plane, annotated or not (`rmatvec`).
        assert np.array_equal(got, want)


def test_field_major_planes_in_stored_order_give_the_margins_to_the_bit():
    """Where the narrow planes come first and in ascending class, as the
    benchmark's fields do, the planes are added in the stored order."""
    plain = field_major_shard([40, 40, 4, 100, 200, 600, 5000, 9000], 800, 6)
    annotated = annotate_spans(plain)
    assert annotated.span_classes == (128, 128, 128, 128, 256, 1024, 0, 0) and containers.DENSE_SPAN_LIMIT == 2048
    w = jnp.asarray(np.random.default_rng(12).normal(size=plain.dim).astype(np.float32))
    run = jax.jit(lambda feats, x: feats.matvec(x))
    assert np.array_equal(np.asarray(run(annotated, w)), np.asarray(run(plain, w)))


def hashed_shard(n, k, dim, seed):
    """Every field hashed into one space, as the LIBSVM copy of `criteo` is."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, dim, (n, k)).astype(np.int32)
    return SparseFeatures(jnp.asarray(idx), jnp.asarray(rng.normal(size=idx.shape).astype(np.float32)), dim)


@pytest.mark.parametrize("product", ["matvec", "rmatvec"])
def test_a_shard_with_no_narrow_plane_traces_the_program_it_traced(product):
    plain = hashed_shard(500, 6, 50_000, 7)
    annotated = annotate_spans(plain)
    assert annotated is plain and span_note(annotated) == {
        "planes": 6, "dense_span": 0, "classes": [], "limit": containers.DENSE_SPAN_LIMIT}
    # ... and is, operation for operation, the loop over all the planes that
    # an explicit "every plane wide" annotation would also have to be.
    operand = jnp.zeros((plain.dim if product == "matvec" else 500,), jnp.float32)
    text = str(jax.make_jaxpr(lambda f, x: getattr(f, product)(x))(plain, operand))
    assert text.count("scan") == 1 and "dynamic_slice" not in text
    narrow = annotate_spans(field_major_shard([40, 3000], 500, 8))
    narrow_text = str(jax.make_jaxpr(lambda f, x: getattr(f, product)(x))(
        narrow, jnp.zeros((narrow.dim if product == "matvec" else 500,), jnp.float32)))
    if product == "matvec":  # a loop a class, then the wide planes'
        assert narrow_text.count("scan") == 2 and "dynamic_slice" in narrow_text
    else:
        assert narrow_text.count("scan") == 1 and "dynamic_slice" not in narrow_text


def test_two_seeds_of_one_field_layout_compile_one_program():
    """The rarest ids of a field may or may not occur: the classes are the
    same, the least ids are data, and the second seed traces nothing."""
    sizes = [40, 97, 300, 900, 5000]
    traces = []

    @jax.jit
    def margins(feats, w):
        traces.append(1)
        return feats.matvec(w)

    shards = []
    for seed in (21, 22):
        idx = np.array(field_major_shard(sizes, 400, seed).indices)
        idx[:, 1] = np.clip(idx[:, 1], 40 + seed % 3, 40 + 90 + seed % 5)  # the ends of a field, by the seed
        shards.append(annotate_spans(SparseFeatures(jnp.asarray(idx), jnp.ones(idx.shape, jnp.float32), sum(sizes))))
    assert shards[0].span_classes == shards[1].span_classes == (128, 128, 512, 1024, 0)  # 5,000 ids: wide
    assert not np.array_equal(np.asarray(shards[0].span_lo), np.asarray(shards[1].span_lo))
    for shard in shards:
        margins(shard, jnp.ones((sum(sizes),), jnp.float32))
    assert len(traces) == 1


def test_only_a_flat_device_shard_is_annotated():
    block = SparseFeatures(jnp.zeros((3, 8, 4), jnp.int32), jnp.ones((3, 8, 4)), 16)  # entity blocks, under vmap
    transposed = SparseFeatures(jnp.zeros((4, 8), jnp.int32), jnp.ones((4, 8)), 16, ell_axis=-2)
    host = SparseFeatures(np.zeros((8, 4), np.int32), np.ones((8, 4), np.float32), 16)
    for feats in (block, transposed, host):
        assert annotate_spans(feats) is feats


def test_an_annotation_does_not_outlive_the_indices_it_was_read_from():
    """The annotation is no argument of the constructor: whatever builds a
    shard from other arrays builds it unannotated (stale least ids would make
    `matvec` drop the ids outside them), and only a pytree's way back from
    its own leaves carries it on."""
    annotated = annotate_spans(field_major_shard([40, 300, 5000], 400, 31))
    assert annotated.span_classes == (128, 512, 0)
    moved = dataclasses.replace(annotated, indices=annotated.indices + 1, dim=annotated.dim + 1)
    assert moved.span_classes == () and moved.span_lo is None
    assert dataclasses.replace(annotated, values=annotated.values * 2).span_classes == ()
    with pytest.raises(TypeError):
        SparseFeatures(annotated.indices, annotated.values, annotated.dim, span_lo=annotated.span_lo)
    with pytest.raises(ValueError):
        dataclasses.replace(annotated, span_classes=(0, 0, 0))
    paths, tree = jax.tree_util.tree_flatten_with_path(annotated)
    assert [jax.tree_util.keystr(path) for path, _ in paths] == [".indices", ".values", ".span_lo"]
    back = jax.tree_util.tree_unflatten(tree, [leaf for _, leaf in paths])
    assert back.span_classes == annotated.span_classes and back.span_lo is annotated.span_lo
    assert (back.dim, back.ell_axis) == (annotated.dim, annotated.ell_axis)
    plain = dataclasses.replace(annotated)
    assert [jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_flatten_with_path(plain)[0]] == [".indices", ".values"]
    assert jax.tree_util.tree_structure(plain) != tree


# Whose shards have narrow planes. (1) This repository's own: `IndexMap` sorts
# the `name\x01term` keys, so a name's terms own one contiguous id range, and
# the reader keeps a record's features in the record's order; records that list
# their columns in one order give a field a plane. (2) The public click
# pipelines that index a field at a time: `rixwew/pytorch-fm`
# (`torchfm/layer.py`, `FeaturesLinear`: the input is a `(batch, num_fields)`
# id matrix to which `offsets = (0, *cumsum(field_dims)[:-1])` is added, its
# `LogisticRegressionModel` is that layer and a sigmoid;
# `torchfm/dataset/criteo.py`: 39 fields, a count v > 2 becomes
# `int(log(v) ** 2)`) and `facebookresearch/dlrm` (`data_utils.py`: one
# contiguous id space a categorical column). The 26 categorical cardinalities
# below are the Criteo Display Advertising Challenge's as DLRM's preprocessing
# counts them on the Kaggle `train.txt` (its `--arch-embedding-size`); a
# 31-bit count gives a numeric field under 465 ids by pytorch-fm's rule.
CRITEO_KAGGLE_CATEGORICAL = (
    1460, 583, 10131227, 2202608, 305, 24, 12517, 633, 3, 93145, 5683, 8351593, 3194,
    27, 14992, 5461306, 10, 5652, 2173, 4, 7046547, 18, 15, 286181, 105, 142572,
)
CRITEO_NUMERIC_FIELD = int(np.log(2.0 ** 31) ** 2) + 3  # int(log(v)**2) for v > 2; -1, 0 and NULL


def test_the_published_criteo_fields_indexed_a_field_at_a_time_have_25_narrow_planes_of_39():
    """`lr-criteo`'s generator draws 27 narrow fields of 39 (its
    `assumed.fields`); the source's own columns, indexed as the public
    pipelines index them, give 25 (13 numeric at class 512, 12 categorical)."""
    sizes = [CRITEO_NUMERIC_FIELD] * 13 + list(CRITEO_KAGGLE_CATEGORICAL)
    assert len(sizes) == 39 and sum(CRITEO_KAGGLE_CATEGORICAL) == 33_762_577  # the data set's 33.76 M categorical values
    classes = annotate_spans(field_major_shard(sizes, 64, 41)).span_classes
    assert classes == tuple(containers.span_class(0, size - 1) for size in sizes)
    assert sum(1 for c in classes if c) == 25 and classes[:13] == (512,) * 13
    assert sum(1 for c in classes[13:] if 0 < c <= 128) == 8
    ours = real_fields_config()["generator"]["field_sizes"]
    assert sum(1 for size in ours if containers.span_class(0, size - 1)) == 27


@pytest.mark.parametrize("order", ["columns_in_one_order", "shuffled_in_every_record"])
def test_name_term_records_through_this_repositorys_reader_are_field_major(tmp_path, order):
    from photon_ml_tpu.data.index_map import feature_key
    from photon_ml_tpu.io.avro_data import FeatureShardConfig, read_game_dataset, write_training_examples

    fields = {"weekday": 7, "hour": 24, "device": 4, "country": 60, "position": 10, "advertiser": 3000, "user_bucket": 9000}
    rng = np.random.default_rng(51)
    rows = []
    for _ in range(3000):  # an index map holds the ids it saw: a wide field needs rows
        row = [(feature_key(name, str(rng.integers(0, size))), 1.0) for name, size in fields.items()]
        if order == "shuffled_in_every_record":
            rng.shuffle(row)
        rows.append(row)
    path = str(tmp_path / "clicks.avro")
    write_training_examples(path, rows, rng.integers(0, 2, len(rows)).astype(float))
    data, index_maps = read_game_dataset(path, {"g": FeatureShardConfig(("features",), True)})
    # A name's terms own one contiguous range of the sorted index map ...
    for name in fields:
        ids = sorted(i for key, i in index_maps["g"].items() if key.startswith(name + "\x01"))
        assert ids == list(range(ids[0], ids[0] + len(ids)))
    # ... and a plane holds one column of the records: a field, where the
    # records list their columns in one order. The intercept is the last plane.
    note = span_note(data.annotated_shard("g"))
    if order == "columns_in_one_order":
        assert data.annotated_shard("g").span_classes == (128, 128, 128, 128, 128, 2048, 0, 128)
        assert note["dense_span"] == 7
    else:
        assert note["dense_span"] == 1  # the intercept; the program of the other planes is the gathered one


# -- the fit reads the spans once a data set and says what it found ------------


def real_fields_config():
    """The benchmark's own 39 fields over its 1,000,000 ids: 21 span at most
    98 ids, 27 at most 1,522, the other 12 from 2,404 to 366,654."""
    with open(os.path.join(ROOT, "benchmarks", "configs", "lr-criteo.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def real_fields_problem():
    return criteo_shape.generate(real_fields_config(), 2_147_483_693, rows=16_000)


@pytest.fixture
def span_reads(monkeypatch):
    """Every reduction over a shard's arrays for its spans, as it is made."""
    reads = []
    reduce = containers._plane_spans

    def counted(indices, values):
        reads.append(indices.shape)
        return reduce(indices, values)

    monkeypatch.setattr(containers, "_plane_spans", counted)
    return reads


def test_a_fit_notes_its_dense_span_planes_and_reads_the_spans_once_a_data_set(real_fields_problem, span_reads):
    config = real_fields_config()
    est, opt = estimator(config)
    train, validation = dataset(real_fields_problem["train"]), dataset(real_fields_problem["validation"])
    est.fit(train, validation, [opt])
    dispatch = est.run_profile()["dispatch"]
    narrow = sum(1 for size in config["generator"]["field_sizes"] if size <= containers.DENSE_SPAN_LIMIT)
    assert narrow == 27
    classes = sorted({containers.span_class(0, size - 1) for size in config["generator"]["field_sizes"]} - {0})
    note = {"planes": 39, "dense_span": narrow, "classes": classes, "limit": containers.DENSE_SPAN_LIMIT}
    assert dispatch["sparse_objective"] == "ell_xla"
    assert dispatch["ell_planes"] == note and dispatch["ell_planes_scored"] == note
    assert sorted(span_reads) == [(2_000, 39), (16_000, 39)]  # the validation rows', the training rows'
    # The next fits on these data sets, another regularisation weight's
    # coordinate among them, and scoring the rows again fetch nothing.
    est.fit(train, validation, [opt])
    other = {"global": dataclasses.replace(opt["global"], reg_weight=3.0)}
    est.fit(train, validation, [other])
    assert len(span_reads) == 2
    assert est.run_profile()["dispatch"]["ell_planes_scored"] == note
    assert train.annotated_shard("g") is train.annotated_shard("g")
    assert train.annotated_shard("g").indices is train.shards["g"].indices  # no plane is stored anew


def test_a_hashed_shard_has_no_dense_span_plane(span_reads):
    rng = np.random.default_rng(5)
    data = GameDataset.build({"g": hashed_shard(3_000, 12, 60_000, 9)}, (rng.random(3_000) < 0.3).astype(np.float32))
    est, opt = estimator(small_config())
    est.fit(data, data, [opt])
    dispatch = est.run_profile()["dispatch"]
    none = {"planes": 12, "dense_span": 0, "classes": [], "limit": containers.DENSE_SPAN_LIMIT}
    assert dispatch["ell_planes"] == none and dispatch["ell_planes_scored"] == none
    assert span_reads == [(3_000, 12)]  # one data set
    assert data.annotated_shard("g") is data.shards["g"]


@pytest.mark.parametrize("what", ["coefficients", "auc"])
def test_the_fit_is_the_gathered_fit(problem, fitted, monkeypatch, what):
    """With the limit at nothing every plane's margins are gathered, as before;
    `fitted` multiplied 30 of its 39 planes as dense spans and is that fit to
    the bit: a narrow plane's margins are the gathered ones (one term of a
    span's sum is non-zero), these fields' narrow planes come first in the
    stored order, and the transpose is the scatter-add either way."""
    est, result = fitted
    assert est.run_profile()["dispatch"]["ell_planes"]["dense_span"] == 30
    monkeypatch.setattr(containers, "DENSE_SPAN_LIMIT", 0)
    plain_est, opt = estimator(small_config())
    plain = plain_est.fit(dataset(problem["train"]), dataset(problem["validation"]), [opt])[0]
    assert plain_est.run_profile()["dispatch"]["ell_planes"]["dense_span"] == 0
    if what == "coefficients":
        w, ref = (np.asarray(r.model["global"].coefficients.means) for r in (result, plain))
        assert np.linalg.norm(ref) > 1.0 and np.array_equal(w, ref)
    else:
        assert float(result.evaluation.primary_value) == float(plain.evaluation.primary_value)
