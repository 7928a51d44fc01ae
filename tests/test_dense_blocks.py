"""A random-effect bucket solved on dense rows.

Where a bucket's static shapes admit it (`containers.dense_block_admitted`),
`gather_block_arrays` lays the bucket's ELL block (E, K, S) out as X^T of
shape (E, D, S) once, and the vmapped solves contract that block by multiply
and reduce: no plane is gathered or scatter-added inside their loops. The
two layouts are two writings of one objective; these tests hold them to
each other, the rule to the shapes it reads, and the dense solve to float32.

A narrow fixed-effect shard is the same rule's case of one entity of every
row (`GameDataset.dense_block_shard`): one X^T block of shape (D, N), which
its coordinate trains and scores on in place of the bucketed pack or the
ELL planes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data import containers
from photon_ml_tpu.data.containers import SparseFeatures
from photon_ml_tpu.data.game_dataset import (
    FixedEffectDataConfig,
    GameDataset,
    RandomEffectDataConfig,
    block_layout,
)
from photon_ml_tpu.estimators.game_estimator import GameEstimator
from photon_ml_tpu.game import coordinate
from photon_ml_tpu.optimize.config import (
    CoordinateOptimizationConfig,
    OptimizerConfig,
    RegularizationContext,
)
from photon_ml_tpu.types import (
    NormalizationType,
    OptimizerType,
    RegularizationType,
    TaskType,
    VarianceComputationType,
)
from photon_ml_tpu.utils import telemetry

TASK = TaskType.LOGISTIC_REGRESSION
D = 24  # features of the shard, the intercept last
K = 5  # ELL planes: up to 4 ids and the intercept, the rest padding


def _ell_rows(rng, n):
    """(n, K) ELL planes: each row 1 to 4 distinct ids below D - 1, the
    intercept, then padding slots (id 0, value 0.0)."""
    idx = np.zeros((n, K), np.int32)
    val = np.zeros((n, K), np.float32)
    for r in range(n):
        m = int(rng.integers(1, K))
        idx[r, :m] = rng.choice(D - 1, size=m, replace=False)
        val[r, :m] = rng.normal(size=m) + 0.5
        idx[r, m], val[r, m] = D - 1, 1.0
    return idx, val


def _glmix(seed, n=900, users=12, movies=5):
    rng = np.random.default_rng(seed)
    idx, val = _ell_rows(rng, n)
    shard = SparseFeatures(jnp.asarray(idx), jnp.asarray(val), D)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    tags = {"user": rng.integers(0, users, size=n), "movie": rng.integers(0, movies, size=n)}
    return GameDataset.build({"g": shard}, y, id_tags=tags)


def _fit(monkeypatch, ell, optimizer, *, mask, normalize):
    """One fit of a fixed effect, per-user and per-movie; `ell` keeps every
    bucket's ELL block by setting the dense blocks' ceiling to 0."""
    monkeypatch.setattr(coordinate, "_RE_JIT_CACHE", {})
    monkeypatch.setattr(containers, "DENSE_BLOCK_MAX_BYTES", 0 if ell else 4 << 30)
    ratio = 0.1 if mask else None
    est = GameEstimator(
        TASK,
        {
            "global": FixedEffectDataConfig("g"),
            "per-user": RandomEffectDataConfig(
                "user", "g", min_bucket=8, num_features_to_samples_ratio_upper_bound=ratio
            ),
            "per-movie": RandomEffectDataConfig("movie", "g", min_bucket=8),
        },
        normalization=NormalizationType.STANDARDIZATION if normalize else NormalizationType.NONE,
        intercept_indices={"g": D - 1} if normalize else None,
        pipeline=False,
    )
    steps = 2 if optimizer == OptimizerType.TRON else 4
    solver = OptimizerConfig(optimizer, max_iterations=steps, tolerance=1e-9)
    l2 = RegularizationContext(RegularizationType.L2)
    cfg = {
        "global": CoordinateOptimizationConfig(optimizer=OptimizerConfig(max_iterations=3)),
        "per-user": CoordinateOptimizationConfig(optimizer=solver, regularization=l2, reg_weight=1.0),
        "per-movie": CoordinateOptimizationConfig(optimizer=solver, regularization=l2, reg_weight=1.0),
    }
    (result,) = est.fit(_glmix(5), None, [cfg])
    return est, result.model


@pytest.mark.parametrize("normalize", [False, True], ids=["raw", "normalized"])
@pytest.mark.parametrize("mask", [False, True], ids=["all_features", "feature_mask"])
@pytest.mark.parametrize("optimizer", [OptimizerType.TRON, OptimizerType.LBFGS], ids=["tron", "lbfgs"])
def test_dense_and_ell_blocks_solve_alike(monkeypatch, optimizer, mask, normalize):
    dense_est, dense = _fit(monkeypatch, False, optimizer, mask=mask, normalize=normalize)
    ell_est, ell = _fit(monkeypatch, True, optimizer, mask=mask, normalize=normalize)
    layouts = {"dense": dense_est, "sparse": ell_est}
    for layout, est in layouts.items():
        note = est.run_profile()["dispatch"]["re_block_layout"]
        assert set(note) == {"per-user", "per-movie"}
        for cid in note:
            buckets = len(est._prepared[cid].re_dataset.buckets)
            assert note[cid]["layout"] == {layout: buckets}
    for cid in ("per-user", "per-movie"):
        a = np.asarray(dense[cid].coefficients_matrix)
        b = np.asarray(ell[cid].coefficients_matrix)
        assert np.abs(b).max() > 0.05
        assert np.linalg.norm(a - b) <= 1e-5 * np.linalg.norm(b)
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    # Passes paid and used, evaluations and products: the same on both.
    assert dense_est.fit_timing["fn_evals"] == ell_est.fit_timing["fn_evals"]
    assert dense_est.fit_timing["hv_evals"] == ell_est.fit_timing["hv_evals"]
    assert dense_est.fit_timing["lane_passes"] == ell_est.fit_timing["lane_passes"]
    if optimizer == OptimizerType.TRON:
        assert set(dense_est.fit_timing["lane_passes"]) == {"per-user", "per-movie"}


@pytest.mark.parametrize("variance", [VarianceComputationType.SIMPLE, VarianceComputationType.FULL])
def test_dense_and_ell_blocks_give_the_same_variances(monkeypatch, variance):
    from photon_ml_tpu.data.game_dataset import build_random_effect_dataset
    from photon_ml_tpu.game.coordinate import RandomEffectCoordinate

    ds = _glmix(3)
    cfg = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(OptimizerType.TRON, max_iterations=2, tolerance=1e-9),
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=1.0,
        variance_computation=variance,
    )
    models = {}
    for ell in (False, True):
        monkeypatch.setattr(coordinate, "_RE_JIT_CACHE", {})
        monkeypatch.setattr(containers, "DENSE_BLOCK_MAX_BYTES", 0 if ell else 4 << 30)
        red = build_random_effect_dataset(ds, RandomEffectDataConfig("user", "g", min_bucket=8))
        models[ell], _ = RandomEffectCoordinate(ds, red, cfg, TASK).train(ds.offsets)
    dense, ell = (np.asarray(models[k].variances_matrix) for k in (False, True))
    assert np.isfinite(ell[:-1]).all() and (ell[:-1] > 0).all()
    np.testing.assert_allclose(dense, ell, rtol=1e-5)


@pytest.mark.parametrize("ell", [False, True], ids=["dense", "ell"])
def test_the_scan_sweep_and_the_bucket_loop_agree_bit_for_bit(monkeypatch, ell):
    """Both build a bucket's block in `gather_block_arrays`, so both take
    the same layout and the same operations."""
    from photon_ml_tpu.data.game_dataset import build_random_effect_dataset
    from photon_ml_tpu.game.coordinate import RandomEffectCoordinate

    monkeypatch.setattr(coordinate, "_RE_JIT_CACHE", {})
    monkeypatch.setattr(containers, "DENSE_BLOCK_MAX_BYTES", 0 if ell else 4 << 30)
    ds = _glmix(4, users=40)
    red = build_random_effect_dataset(
        ds, RandomEffectDataConfig("user", "g", min_bucket=8, max_block_cells=128)
    )
    assert len(red.buckets) > 1
    cfg = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(OptimizerType.TRON, max_iterations=2, tolerance=1e-9),
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=1.0,
        variance_computation=VarianceComputationType.SIMPLE,
    )
    coord = RandomEffectCoordinate(ds, red, cfg, TASK)
    assert set(coord.block_layout["layout"]) == {"sparse" if ell else "dense"}
    scan, scan_stats = coord.train(ds.offsets)
    monkeypatch.setenv("PHOTON_SWEEP_SCAN", "0")
    loop, loop_stats = coord.train(ds.offsets)
    np.testing.assert_array_equal(np.asarray(scan.coefficients_matrix), np.asarray(loop.coefficients_matrix))
    np.testing.assert_array_equal(np.asarray(scan.variances_matrix), np.asarray(loop.variances_matrix))
    assert scan_stats == loop_stats


# -- the rule, from shapes alone ------------------------------------------------


@pytest.mark.parametrize(
    "entities, planes, capacity, dim, dense",
    [
        (8192, 9, 256, 208, True),  # the per-user buckets of glmix-movielens-tron
        (4096, 9, 512, 208, True),  # its per-movie buckets
        (4096, 9, 128, 208, True),  # its last per-user bucket
        (8192, 39, 256, 1_000_000, False),  # an unprojected wide shard
        (8192, 9, 1024, 208, False),  # 6.98 GB: over the ceiling
        (64, 2, 128, 257, False),  # wider than 128 ids a plane
        (64, 2, 128, 256, True),
    ],
)
def test_the_layout_is_read_from_the_shapes(entities, planes, capacity, dim, dense):
    assert containers.dense_block_admitted(entities, planes, capacity, dim) is dense
    shard = SparseFeatures(np.zeros((planes, 8), np.int16), np.zeros((planes, 8), np.float32), dim, ell_axis=-2)
    assert block_layout(shard, entities, capacity) == ("dense" if dense else "sparse")
    assert block_layout(np.zeros((8, dim), np.float32), entities, capacity) == "dense"


def test_a_dense_block_is_laid_as_the_chip_pads_it():
    # D to 8 sublanes, S to 128 lanes: the cell's per-user block is 1.745 GB.
    assert containers.dense_block_bytes(8192, 208, 256) == 8192 * 208 * 256 * 4 == 1_744_830_464
    assert containers.dense_block_bytes(3, 209, 129) == 3 * 216 * 256 * 4
    assert containers.dense_block_bytes(8192, 208, 256) <= containers.DENSE_BLOCK_MAX_BYTES


def test_the_dense_block_is_the_float64_densification():
    rng = np.random.default_rng(1)
    entities, capacity = 3, 40
    idx = np.zeros((entities, K, capacity), np.int16)
    val = np.zeros((entities, K, capacity), np.float32)
    for e in range(entities):
        for s in range(capacity):
            m = int(rng.integers(0, K + 1))  # 0 to K ids, the rest padding slots
            idx[e, :m, s] = rng.choice(D, size=m, replace=False)
            val[e, :m, s] = rng.normal(size=m) * 10.0 ** rng.integers(-3, 4, size=m)
    block = containers.dense_block(SparseFeatures(jnp.asarray(idx), jnp.asarray(val), D, ell_axis=-2))
    by_hand = np.zeros((entities, D, capacity), np.float64)
    for e in range(entities):
        for k in range(K):
            np.add.at(by_hand[e], (idx[e, k], np.arange(capacity)), val[e, k].astype(np.float64))
    assert block.xt.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(block.xt), by_hand.astype(np.float32))
    assert block.dim == D


# -- float32, and no gather in the solve's loops --------------------------------


def _primitives(jaxpr, inside_loop=False, found=None):
    """(primitive name, equation, inside a while loop) of every equation,
    through every nested jaxpr."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append((eqn.primitive.name, eqn, inside_loop))
        loop = inside_loop or eqn.primitive.name == "while"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    _primitives(sub.jaxpr, loop, found)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    _primitives(sub, loop, found)
    return found


def _sweep_jaxpr(monkeypatch, ell):
    from photon_ml_tpu.data.game_dataset import build_random_effect_dataset
    from photon_ml_tpu.game.coordinate import RandomEffectCoordinate

    monkeypatch.setattr(coordinate, "_RE_JIT_CACHE", {})
    monkeypatch.setattr(containers, "DENSE_BLOCK_MAX_BYTES", 0 if ell else 4 << 30)
    ds = _glmix(2)
    red = build_random_effect_dataset(ds, RandomEffectDataConfig("user", "g", min_bucket=8))
    cfg = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(OptimizerType.TRON, max_iterations=2, tolerance=1e-9),
        regularization=RegularizationContext(RegularizationType.L2),
        reg_weight=1.0,
    )
    coord = RandomEffectCoordinate(ds, red, cfg, TASK)
    _idxs, gathers, masks, ents = coord._scan_group_list()[0]
    matrix = jnp.zeros((red.num_entities + 1, coord.dim))
    args = (
        ds.shards[red.feature_shard], ds.labels, ds.weights, ds.offsets, matrix, None,
        gathers, masks, ents, red.feature_mask, None, None, jnp.float32(1.0),
    )
    return coord, _primitives(jax.make_jaxpr(coord._train_scan)(*args).jaxpr)


def test_the_dense_solve_is_float32_and_gathers_nothing_in_its_loops(monkeypatch):
    coord, found = _sweep_jaxpr(monkeypatch, ell=False)
    assert coord.block_layout["layout"] == {"dense": len(coord.re_dataset.buckets)}
    # No contraction of the block (rank 3 under the scan, the entity axis
    # leading) at the default precision, which on the TPU rounds float32
    # operands to bfloat16; the solver's own vector dots are (E, D).
    blocks = [
        eqn for name, eqn, _ in found
        if name == "dot_general" and max(v.aval.ndim for v in eqn.invars) >= 3
    ]
    for eqn in blocks:
        assert eqn.params["precision"] is not None
        assert jax.lax.Precision.DEFAULT not in eqn.params["precision"]
    in_loops = {name for name, _, loop in found if loop}
    assert "while" in {name for name, _, _ in found} and "reduce_sum" in in_loops
    assert not in_loops & {"gather", "scatter-add", "scatter_add"}
    # The ELL layout of the same sweep gathers and scatter-adds in its loops:
    # what the assertion above would catch.
    _, ell_found = _sweep_jaxpr(monkeypatch, ell=True)
    assert {"gather", "scatter-add"} <= {name for name, _, loop in ell_found if loop}


def test_the_counter_reports_each_coordinates_buckets_by_layout(monkeypatch):
    before = telemetry.METRICS.labeled_counters("random_effect_blocks")
    est, _ = _fit(monkeypatch, False, OptimizerType.TRON, mask=False, normalize=False)
    after = telemetry.METRICS.labeled_counters("random_effect_blocks")
    for cid in ("per-user", "per-movie"):
        key = f"coordinate={cid},layout=dense"
        assert after[key] - before.get(key, 0) == len(est._prepared[cid].re_dataset.buckets)
        assert after.get(f"coordinate={cid},layout=sparse", 0) == before.get(f"coordinate={cid},layout=sparse", 0)
    note = est.run_profile()["dispatch"]["re_block_layout"]["per-user"]
    (coord,) = [c for (cid, _), c in est._coordinate_cache.items() if cid == "per-user"]
    assert note["dense_bytes"] == max(containers.dense_block_bytes(e, coord.dim, s) for e, s, _ in note["shapes"]) > 0


# -- a narrow fixed effect: one entity of every row -----------------------------


def _fixed_effect_shard(seed, n=3000, dim=D):
    """An ELL shard of `_ell_rows` over `dim` features, and its data set."""
    rng = np.random.default_rng(seed)
    idx, val = _ell_rows(rng, n)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    shard = SparseFeatures(jnp.asarray(idx), jnp.asarray(val), dim)
    return GameDataset.build({"g": shard}, y), rng


def test_a_narrow_fixed_effect_is_one_block_whose_sums_are_the_ell_routes():
    from photon_ml_tpu.data.containers import LabeledData
    from photon_ml_tpu.ops import objective
    from photon_ml_tpu.ops.losses import loss_for_task

    ds, rng = _fixed_effect_shard(7)
    n = ds.num_samples
    block, ell = ds.dense_block_shard("g"), ds.shards["g"]
    assert isinstance(block, containers.DenseBlock) and block.xt.dtype == jnp.float32
    assert block.xt.shape == (D, n) and block.shape == ell.shape == (n, D)
    assert ds.dense_block_shard("g") is block  # laid once a data set
    assert objective.route_for(block) == objective.XLA
    loss = loss_for_task(TASK)
    w, v = (jnp.asarray(rng.normal(size=D).astype(np.float32)) for _ in range(2))
    offsets = jnp.asarray(rng.normal(size=n).astype(np.float32))
    weights = jnp.asarray(rng.uniform(size=n).astype(np.float32))

    def data(features):
        return LabeledData(features, ds.labels, offsets, weights)

    (val_b, g_b), (val_e, g_e) = (objective.value_and_gradient(loss, w, data(f), l2=0.5) for f in (block, ell))
    np.testing.assert_allclose(float(val_b), float(val_e), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g_b), np.asarray(g_e), rtol=1e-6)
    hv_b, hv_e = (objective.hessian_vector(loss, w, v, data(f), l2=0.5) for f in (block, ell))
    np.testing.assert_allclose(np.asarray(hv_b), np.asarray(hv_e), rtol=1e-6)
    diag_b, diag_e = (objective.hessian_diagonal(loss, w, data(f), l2=0.5) for f in (block, ell))
    np.testing.assert_allclose(np.asarray(diag_b), np.asarray(diag_e), rtol=1e-6)
    # Margins: a few units in the last place of the row's terms.
    idx, val = np.asarray(ell.indices), np.asarray(ell.values)
    terms = np.abs(val * np.asarray(w)[idx]).sum(axis=1)
    z_b, z_e = (np.asarray(objective.compute_margins(w, data(f))) for f in (block, ell))
    assert np.all(np.abs(z_b - z_e) <= 4 * np.finfo(np.float32).eps * terms)


def test_the_scoring_program_scores_a_dense_block():
    from photon_ml_tpu.transformers.game_transformer import _fe_margins

    ds, rng = _fixed_effect_shard(8, n=500)
    w = jnp.asarray(rng.normal(size=D).astype(np.float32))
    block, ell = ds.dense_block_shard("g"), ds.shards["g"]
    z_b, z_e = np.asarray(_fe_margins(block, w, None)), np.asarray(_fe_margins(ell, w, None))
    terms = np.abs(np.asarray(ell.values) * np.asarray(w)[np.asarray(ell.indices)]).sum(axis=1)
    assert z_b.shape == (500,)
    assert np.all(np.abs(z_b - z_e) <= 4 * np.finfo(np.float32).eps * terms)


@pytest.mark.parametrize("admitted", [True, False], ids=["narrow", "wider_than_128_a_plane"])
def test_a_fixed_effect_coordinate_lays_its_shard_by_the_rule(monkeypatch, admitted):
    """With the sparse kernels eligible and ingest's pack started, an admitted
    shard is laid dense and no pack is joined or made; a declined one goes on
    to the pack's decision as before."""
    from photon_ml_tpu.data.game_dataset import HostCSR
    from photon_ml_tpu.ops import pallas_glm, pallas_sparse

    monkeypatch.setattr(pallas_glm, "FORCE_INTERPRET", True)
    monkeypatch.setenv("PHOTON_PIPELINE", "1")
    monkeypatch.setenv("PHOTON_DEVICE_PACK", "0")
    dim = D if admitted else containers.DENSE_BLOCK_IDS_PER_PLANE * K + 1
    ds, _ = _fixed_effect_shard(9, n=9000, dim=dim)
    idx, val = np.asarray(ds.shards["g"].indices), np.asarray(ds.shards["g"].values)
    live = val != 0
    csr = HostCSR(
        np.concatenate([[0], np.cumsum(live.sum(axis=1))]).astype(np.int64),
        idx[live].astype(np.int64), val[live], dim,
    )
    ds.host_csr = {"g": csr}
    pallas_sparse.begin_pack_async(csr, ds.num_samples)
    assert csr.pack_future is not None
    joined = []

    def finish_pack(stash, n):  # the join, declined: the ELL path follows
        joined.append(stash)
        return None

    monkeypatch.setattr(pallas_sparse, "finish_pack", finish_pack)
    monkeypatch.setattr(pallas_sparse, "maybe_pack", lambda *a, **k: pytest.fail("packed from the device ELL"))
    before = telemetry.METRICS.labeled_counters("fixed_effect_blocks")
    est = GameEstimator(TASK, {"global": FixedEffectDataConfig("g")})
    cfg = CoordinateOptimizationConfig(optimizer=OptimizerConfig(max_iterations=3))
    (result,) = est.fit(ds, None, [{"global": cfg}])
    after = telemetry.METRICS.labeled_counters("fixed_effect_blocks")
    (coord,) = est._coordinate_cache.values()
    layout = "dense" if admitted else "ell"
    assert coord.layout == layout
    assert after[f"coordinate=global,layout={layout}"] - before.get(f"coordinate=global,layout={layout}", 0) == 1
    assert sum(after.values()) - sum(before.values()) == 1
    dispatch = est.run_profile()["dispatch"]
    assert "g" not in ds.host_csr
    if admitted:
        assert isinstance(coord.training_features, containers.DenseBlock) and not joined
        assert dispatch["sparse_objective"] == "dense_block"
        assert dispatch["dense_storage"] == {
            "layout": "dense_block", "dtype": "float32",
            "bytes": containers.dense_block_bytes(1, D, 9000),
        }
    else:
        assert isinstance(coord.training_features, SparseFeatures) and joined == [csr]
        assert dispatch["sparse_objective"] == "ell_xla" and dispatch["dense_storage"] == "none"
    assert np.isfinite(np.asarray(result.model["global"].coefficients.means)).all()


@pytest.mark.parametrize(
    "planes, rows, dim, dense",
    [
        (9, 4_000_000, 201, True),  # glmix-movielens-tron's fixed effect: 3.33 GB
        (9, 5_000_000, 201, True),  # 4.16 GB
        (9, 5_200_000, 201, False),  # 4.33 GB: over the ceiling
        (9, 20_000_263, 201, False),  # the same shard at the source's rows, 16.6 GB
        (39, 8_000_000, 1_000_000, False),  # lr-criteo
        (39, 11_460_155, 1_000_000, False),  # a chip's part of lr-criteo-full
        (9, 30_000, 9 * 128 + 1, False),  # wider than 128 ids a plane
    ],
)
def test_a_fixed_effect_shard_is_admitted_from_its_shapes_alone(planes, rows, dim, dense):
    """Views of one zero, so nothing of the shard's size is made."""
    from photon_ml_tpu.data.game_dataset import ShardDict
    from photon_ml_tpu.ops import objective

    assert containers.dense_block_admitted(1, planes, rows, dim) is dense
    if dense:
        return
    shard = SparseFeatures(
        np.broadcast_to(np.int32(0), (rows, planes)), np.broadcast_to(np.float32(0), (rows, planes)), dim
    )
    one = np.zeros(1, np.float32)
    ds = GameDataset(ShardDict({"g": shard}), one, one, one, {})
    assert ds.dense_block_shard("g") is None
    assert dict.__getitem__(ds.shards, "g") is shard  # nothing was uploaded
    assert objective.route_for(shard) == objective.XLA  # today's route


def test_a_sample_sharded_shard_is_not_laid_dense():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from photon_ml_tpu.ops import objective

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    ds, _ = _fixed_effect_shard(10, n=64)
    ell = ds.shards["g"]
    rows = NamedSharding(mesh, PartitionSpec("data", None))
    sharded = SparseFeatures(jax.device_put(ell.indices, rows), jax.device_put(ell.values, rows), D)
    ds = GameDataset.build({"g": sharded}, np.asarray(ds.labels))
    assert ds.dense_block_shard("g") is None
    assert objective.route_for(sharded).kind == "ell_sharded"


def test_a_glmix_fit_on_the_dense_block_matches_the_declined_ell_route():
    """`generators/movielens_shape` at a CPU size through the benchmark's own
    driver, once at the configuration's width (the fixed effect laid dense)
    and once over 128 x 9 + 1 features (declined: the ELL route), the same
    rows and ids: the extra features are never hit and stay 0."""
    import copy
    import json
    import os

    from benchmarks.drivers import refit
    from benchmarks.generators import movielens_shape

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs", "glmix-movielens-tron.json")) as f:
        config = json.load(f)
    data = movielens_shape.generate(config, 2_147_483_659, 20_000)
    fits = {}
    declined = containers.DENSE_BLOCK_IDS_PER_PLANE * config["nnz_per_row"] + 1
    for dim in (config["shards"]["g"]["dim"], declined):
        wide = copy.deepcopy(data)
        for part in ("train", "validation"):
            wide[part]["shards"]["g"]["dim"] = dim
        state = refit.setup(config, {"traffic": {}}, wide)
        records, _ = refit.window(state, 0.0, lambda i: None)
        (coord,) = [c for (cid, _), c in state.estimator._coordinate_cache.items() if cid == "global"]
        fits[coord.layout] = refit.outputs(state, records[-1]), records[-1]["metric"]
        refit.release(state)
    (dense, auc), (ell, ell_auc) = fits["dense"], fits["ell"]
    w, w_ell = dense["global"], ell["global"]
    assert not w_ell[len(w):].any() and np.abs(w).max() > 0.05
    assert np.linalg.norm(w - w_ell[: len(w)]) <= 2e-6 * np.linalg.norm(w_ell)
    assert abs(auc - ell_auc) <= 1e-6
    for cid in ("per-user", "per-movie"):
        a, b = dense[cid], ell[cid][:, : len(w)]
        assert np.linalg.norm(a - b) <= 2e-6 * np.linalg.norm(b)
