"""Multi-device tests on the 8-device virtual CPU mesh.

Counterpart of the reference's Spark local-cluster integ tests
(SparkTestUtils.scala): the sharded code paths (GSPMD-partitioned optimizer
loops, entity-sharded vmapped solves, cross-shard residual gathers) run for
real with 8 devices, and must agree numerically with single-device runs.
"""

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from photon_ml_tpu.data.game_dataset import (
    GameDataset,
    RandomEffectDataConfig,
    build_random_effect_dataset,
)
from photon_ml_tpu.game.coordinate import FixedEffectCoordinate, RandomEffectCoordinate
from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent
from photon_ml_tpu.optimize.config import L2, CoordinateOptimizationConfig, OptimizerConfig
from photon_ml_tpu.parallel.mesh import (
    make_mesh,
    pad_game_dataset,
    shard_game_dataset,
    shard_random_effect_dataset,
)
from photon_ml_tpu.types import TaskType


def _dataset(rng, n=203, d=5, n_entities=11, d_re=3):
    Xf = rng.normal(size=(n, d)).astype(np.float32)
    Xf[:, -1] = 1.0
    Xe = rng.normal(size=(n, d_re)).astype(np.float32)
    entity = rng.integers(0, n_entities, size=n)
    w = rng.normal(size=d)
    u = rng.normal(size=(n_entities, d_re))
    m = Xf @ w + np.einsum("nd,nd->n", Xe, u[entity])
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-m))).astype(np.float32)
    return GameDataset.build(
        {"global": jnp.asarray(Xf), "per_entity": jnp.asarray(Xe)},
        y,
        id_tags={"entityId": entity},
    )


def _cfg(w=0.1):
    return CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=50, tolerance=1e-7),
        regularization=L2,
        reg_weight=w,
    )


def test_mesh_has_8_devices():
    mesh = make_mesh()
    assert mesh.devices.size == 8


def test_pad_dataset_row_count_and_inertness(rng):
    ds = _dataset(rng, n=203)
    padded = pad_game_dataset(ds, 8)
    assert padded.num_samples == 208
    assert float(padded.weights[203:].sum()) == 0.0
    np.testing.assert_array_equal(np.asarray(padded.labels[:203]), np.asarray(ds.labels))


def test_sharded_fixed_effect_matches_single_device(rng):
    ds = _dataset(rng)
    mesh = make_mesh()
    sharded = shard_game_dataset(ds, mesh)

    single = FixedEffectCoordinate(ds, "global", _cfg(), TaskType.LOGISTIC_REGRESSION)
    multi = FixedEffectCoordinate(sharded, "global", _cfg(), TaskType.LOGISTIC_REGRESSION)

    m1, r1 = single.train(ds.offsets)
    m2, r2 = multi.train(sharded.offsets)
    # f32 reduction order differs across shards; parity is to ~1e-4 absolute.
    np.testing.assert_allclose(
        m1.coefficients.means, m2.coefficients.means, rtol=5e-3, atol=2e-4
    )
    # The sharded input really is distributed over 8 devices.
    assert len(sharded.labels.sharding.device_set) == 8


class TestRingCollectives:
    """ring_gather_rows / ring_scatter_rows: exact row movement over the mesh
    (no arithmetic), so results must be bit-identical to local indexing."""

    def test_ring_gather_matches_local_gather(self, rng):
        from photon_ml_tpu.parallel.mesh import (
            batch_sharding,
            make_mesh,
            matrix_row_sharding,
            ring_gather_rows,
        )

        mesh = make_mesh()
        ndev = mesh.devices.size
        R, D, S = 4 * ndev, 6, 5 * ndev
        M = jnp.asarray(rng.normal(size=(R, D)).astype(np.float32))
        rows = jnp.asarray(rng.integers(0, R, size=S).astype(np.int32))
        Ms = jax.device_put(M, matrix_row_sharding(mesh))
        rows_s = jax.device_put(rows, batch_sharding(mesh, 1))
        got = np.asarray(ring_gather_rows(Ms, rows_s, mesh))
        assert np.array_equal(got, np.asarray(M)[np.asarray(rows)])

    def test_ring_gather_2d_rows(self, rng):
        from photon_ml_tpu.parallel.mesh import (
            batch_sharding,
            make_mesh,
            matrix_row_sharding,
            ring_gather_rows,
        )

        mesh = make_mesh()
        ndev = mesh.devices.size
        R, D = 2 * ndev, 4
        M = jnp.asarray(rng.normal(size=(R, D)).astype(np.float32))
        rows = jnp.asarray(rng.integers(0, R, size=(2 * ndev, 3)).astype(np.int32))
        got = np.asarray(
            ring_gather_rows(
                jax.device_put(M, matrix_row_sharding(mesh)),
                jax.device_put(rows, batch_sharding(mesh, 2)),
                mesh,
            )
        )
        assert np.array_equal(got, np.asarray(M)[np.asarray(rows)])

    def test_ring_scatter_matches_local_set(self, rng):
        from photon_ml_tpu.parallel.mesh import (
            batch_sharding,
            make_mesh,
            matrix_row_sharding,
            ring_scatter_rows,
        )

        mesh = make_mesh()
        ndev = mesh.devices.size
        R, D, S = 4 * ndev, 6, 2 * ndev
        M = jnp.asarray(rng.normal(size=(R, D)).astype(np.float32))
        # unique target rows (the coordinate's contract within a bucket)
        rows = jnp.asarray(
            rng.choice(R, size=S, replace=False).astype(np.int32)
        )
        vals = jnp.asarray(rng.normal(size=(S, D)).astype(np.float32))
        got = np.asarray(
            ring_scatter_rows(
                jax.device_put(M, matrix_row_sharding(mesh)),
                jax.device_put(rows, batch_sharding(mesh, 1)),
                jax.device_put(vals, batch_sharding(mesh, 2)),
                mesh,
            )
        )
        want = np.asarray(M).copy()
        want[np.asarray(rows)] = np.asarray(vals)
        assert np.array_equal(got, want)

    def test_trained_re_matrix_is_row_sharded(self, rng):
        ds = _dataset(rng)
        mesh = make_mesh()
        padded = pad_game_dataset(ds, mesh.devices.size)
        sharded = shard_game_dataset(padded, mesh)
        red = shard_random_effect_dataset(
            build_random_effect_dataset(
                sharded, RandomEffectDataConfig("entityId", "per_entity")
            ),
            mesh,
        )
        rand = RandomEffectCoordinate(sharded, red, _cfg(1.0), TaskType.LOGISTIC_REGRESSION)
        assert rand._entity_mesh is not None
        model, _ = rand.train(sharded.offsets)
        m = model.coefficients_matrix
        shard_bytes = [s.data.nbytes for s in m.addressable_shards]
        assert len(shard_bytes) == mesh.devices.size
        assert max(shard_bytes) <= m.nbytes // mesh.devices.size
        # sharded scoring matches the replicated gather
        s_sharded = np.asarray(rand.score(model))
        from photon_ml_tpu.game.model import random_effect_margins

        s_repl = np.asarray(
            random_effect_margins(
                sharded.shards["per_entity"],
                red.sample_entity_rows,
                jax.device_put(m, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())),
                None,
            )
        )
        np.testing.assert_allclose(s_sharded, s_repl, rtol=1e-6, atol=1e-6)

    def test_sharded_margins_match_replicated_with_norm(self, rng):
        """Guards the deliberate duplication between random_effect_margins and
        its sharded twin: norm algebra must stay numerically identical."""
        from photon_ml_tpu.game.model import (
            random_effect_margins,
            random_effect_margins_sharded,
        )
        from photon_ml_tpu.ops.normalization import NormalizationContext
        from photon_ml_tpu.parallel.mesh import (
            batch_sharding,
            make_mesh,
            matrix_row_sharding,
        )

        mesh = make_mesh()
        ndev = mesh.devices.size
        R, D, N = 4 * ndev, 6, 3 * ndev + 1  # N deliberately not divisible
        M = jnp.asarray(rng.normal(size=(R, D)).astype(np.float32))
        rows = jnp.asarray(rng.integers(0, R, size=N).astype(np.int32))
        X = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
        norm = NormalizationContext(
            factors=jnp.asarray(rng.uniform(0.5, 2.0, size=D).astype(np.float32)),
            shifts=jnp.asarray(rng.normal(size=D).astype(np.float32) * 0.1),
        )
        want = np.asarray(random_effect_margins(X, rows, M, norm))
        got = np.asarray(
            random_effect_margins_sharded(
                X, rows, jax.device_put(M, matrix_row_sharding(mesh)), norm, mesh
            )
        )
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_sharded_game_training_matches_single_device(rng):
    ds = _dataset(rng)
    cfg_re = RandomEffectDataConfig("entityId", "per_entity")

    # Single-device path.
    red_s = build_random_effect_dataset(ds, cfg_re)
    fixed_s = FixedEffectCoordinate(ds, "global", _cfg(), TaskType.LOGISTIC_REGRESSION)
    rand_s = RandomEffectCoordinate(ds, red_s, _cfg(1.0), TaskType.LOGISTIC_REGRESSION)
    res_s = run_coordinate_descent({"f": fixed_s, "r": rand_s}, 2)

    # Sharded path: pad + shard samples, shard entity blocks.
    mesh = make_mesh()
    padded = pad_game_dataset(ds, mesh.devices.size)
    sharded = shard_game_dataset(padded, mesh)
    red_m = shard_random_effect_dataset(build_random_effect_dataset(sharded, cfg_re), mesh)
    fixed_m = FixedEffectCoordinate(sharded, "global", _cfg(), TaskType.LOGISTIC_REGRESSION)
    rand_m = RandomEffectCoordinate(sharded, red_m, _cfg(1.0), TaskType.LOGISTIC_REGRESSION)
    res_m = run_coordinate_descent({"f": fixed_m, "r": rand_m}, 2)

    np.testing.assert_allclose(
        res_s.model["f"].coefficients.means,
        res_m.model["f"].coefficients.means,
        rtol=5e-3,
        atol=5e-4,
    )
    # Entity rows may be ordered differently only if id sets differ — they
    # don't here (same build logic); padded dataset adds one sentinel entity.
    W_s = np.asarray(res_s.model["r"].coefficients_matrix)
    W_m = np.asarray(res_m.model["r"].coefficients_matrix)
    for ent, row_s in red_s.entity_index.items():
        row_m = red_m.entity_index[ent]
        np.testing.assert_allclose(
            W_s[row_s], W_m[row_m], rtol=5e-3, atol=5e-4,
        )


def test_entity_blocks_sharded_over_devices(rng):
    ds = _dataset(rng)
    mesh = make_mesh()
    padded = pad_game_dataset(ds, mesh.devices.size)
    red = shard_random_effect_dataset(
        build_random_effect_dataset(padded, RandomEffectDataConfig("entityId", "per_entity")),
        mesh,
    )
    for b in red.buckets:
        assert b.gather.shape[0] % 8 == 0
        assert len(b.gather.sharding.device_set) == 8


class TestShardedFusedObjective:
    """The distributed fused Pallas objective: per-device kernel + psum
    (ValueAndGradientAggregator.scala:248-252 as one ICI all-reduce). The
    fused path must engage on batch-sharded data and match XLA numerics."""

    @pytest.fixture
    def interpret_kernels(self, monkeypatch):
        from photon_ml_tpu.ops import pallas_glm

        monkeypatch.setattr(pallas_glm, "FORCE_INTERPRET", True)
        monkeypatch.setattr(pallas_glm, "_HEALTHY", False)
        return pallas_glm

    @pytest.fixture
    def big_sharded(self, rng):
        # Sizes chosen to clear the per-device row threshold (2048) on 8 devs.
        from photon_ml_tpu.ops import pallas_glm

        n, d = 8 * pallas_glm._MIN_ROWS, 128
        Xf = rng.normal(size=(n, d)).astype(np.float32)
        Xf[:, -1] = 1.0
        w = rng.normal(size=d) * 0.2
        m = Xf @ w
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-m))).astype(np.float32)
        ds = GameDataset.build({"global": jnp.asarray(Xf)}, y)
        return shard_game_dataset(ds, make_mesh())

    def test_dispatch_returns_sharded_mode(self, interpret_kernels, big_sharded):
        pallas_glm = interpret_kernels
        feats = big_sharded.shards["global"]
        mode = pallas_glm.dispatch(
            feats, jnp.zeros((feats.shape[-1],), feats.dtype)
        )
        assert isinstance(mode, pallas_glm.ShardedDispatch)
        assert mode.mesh.devices.size == 8
        # Boolean view stays False for multi-device (it cannot carry a mesh).
        assert pallas_glm.should_use(feats, jnp.zeros((feats.shape[-1],))) is False

    def test_sharded_fused_sums_match_xla(self, interpret_kernels, big_sharded, rng):
        pallas_glm = interpret_kernels
        from photon_ml_tpu.ops.losses import LOGISTIC

        ds = big_sharded
        feats = ds.shards["global"]
        d = feats.shape[-1]
        w = jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.1)
        mode = pallas_glm.dispatch(feats, w)
        val, g, sum_u = pallas_glm.sharded_value_gradient_sums(
            LOGISTIC, w, jnp.zeros(()), feats, ds.labels, ds.offsets,
            ds.weights, mesh=mode.mesh, axis=mode.axis, interpret=True,
        )
        X = np.asarray(feats)
        z = X @ np.asarray(w) + np.asarray(ds.offsets)
        u = np.asarray(ds.weights) * np.asarray(LOGISTIC.d1(jnp.asarray(z), ds.labels))
        val_ref = float(np.sum(np.asarray(ds.weights) * np.asarray(LOGISTIC.loss(jnp.asarray(z), ds.labels))))
        np.testing.assert_allclose(float(val), val_ref, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g), u @ X, rtol=1e-3, atol=1e-2)
        np.testing.assert_allclose(float(sum_u), float(u.sum()), rtol=1e-3, atol=1e-3)

        hv, sum_r = pallas_glm.sharded_hessian_vector_sums(
            LOGISTIC, w, jnp.zeros(()), w, jnp.zeros(()), feats, ds.labels,
            ds.offsets, ds.weights, mesh=mode.mesh, axis=mode.axis,
            interpret=True,
        )
        r = np.asarray(ds.weights) * np.asarray(LOGISTIC.d2(jnp.asarray(z), ds.labels)) * (X @ np.asarray(w))
        np.testing.assert_allclose(np.asarray(hv), r @ X, rtol=1e-3, atol=1e-2)

    def test_fixed_effect_trains_through_sharded_fused_path(
        self, interpret_kernels, big_sharded
    ):
        """End-to-end: FixedEffectCoordinate on batch-sharded data engages
        the sharded fused objective and lands on the XLA path's optimum."""
        pallas_glm = interpret_kernels
        ds = big_sharded
        cfg = CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=5, tolerance=1e-7),
            regularization=L2,
            reg_weight=1.0,
        )
        fused = FixedEffectCoordinate(ds, "global", cfg, TaskType.LOGISTIC_REGRESSION)
        assert isinstance(fused._use_pallas, pallas_glm.ShardedDispatch)
        m_fused, _ = fused.train(ds.offsets)

        pallas_glm.set_enabled(False)
        try:
            xla = FixedEffectCoordinate(ds, "global", cfg, TaskType.LOGISTIC_REGRESSION)
            assert xla._use_pallas is False
            m_xla, _ = xla.train(ds.offsets)
        finally:
            pallas_glm.set_enabled(True)
        np.testing.assert_allclose(
            np.asarray(m_fused.coefficients.means),
            np.asarray(m_xla.coefficients.means),
            rtol=5e-3,
            atol=5e-4,
        )


@pytest.mark.slow
@pytest.mark.multihost
def test_multihost_two_process_dryrun():
    """TWO OS PROCESSES form a jax.distributed cluster (coordinator +
    worker) and train a sample-sharded GLM whose gradient all-reduces cross
    process boundaries, PLUS the entity-sharded random-effect variant
    (coefficient rows sharded over the cross-process mesh, ring collectives
    over DCN, per-process row parity) — the mesh.py multi-host claim,
    executed (parallel/multihost.py; reference analog: Spark local-cluster
    tests, SparkTestUtils.scala:61-75, one level stronger: real processes).
    Out of tier-1 (slow + multihost): OS-process jax.distributed needs a
    jaxlib with cross-process CPU collectives; the single-process 8-device
    sharded-sweep parity below is the tier-1 certificate."""
    from photon_ml_tpu.parallel.multihost import dryrun_multihost

    dryrun_multihost(2, 2, timeout_s=300)


def test_bcast_gather_rows_exact(rng):
    """The psum broadcast-gather (serving's sharded dispatch) is exact row
    movement: one shard contributes each requested row, the others exact
    zeros — bitwise equal to local indexing."""
    from photon_ml_tpu.parallel.mesh import (
        bcast_gather_rows,
        make_mesh,
        matrix_row_sharding,
    )

    mesh = make_mesh()
    ndev = mesh.devices.size
    R, D, S = 4 * ndev, 6, 13  # S deliberately not a mesh multiple
    M = jnp.asarray(rng.normal(size=(R, D)).astype(np.float32))
    rows = jnp.asarray(rng.integers(0, R, size=S).astype(np.int32))
    got = np.asarray(
        bcast_gather_rows(jax.device_put(M, matrix_row_sharding(mesh)), rows, mesh)
    )
    assert np.array_equal(got, np.asarray(M)[np.asarray(rows)])


def test_sharded_scan_sweep_matches_bucket_loop(
    rng, monkeypatch, assert_sharded_close
):
    """Tier-1 pod-scale certificate on the 8-virtual-device mesh: the
    entity-sharded scan sweep (ring gather -> vmapped shard-local solves ->
    ring scatter, all inside ONE lax.scan program per block shape) matches
    the sharded per-bucket loop — another program, so to the `fit`
    tolerance —, keeps the coefficient store row-sharded, and reports its
    collective bytes."""
    mesh = make_mesh()
    cfg_re = RandomEffectDataConfig("entityId", "per_entity", min_bucket=4)

    def build():
        # Fresh identical dataset per path: neither may warm the other's
        # device residency or pack caches.
        ds = shard_game_dataset(
            pad_game_dataset(_dataset(np.random.default_rng(7)), mesh.devices.size),
            mesh,
        )
        red = shard_random_effect_dataset(
            build_random_effect_dataset(ds, cfg_re), mesh
        )
        return ds, red

    ds_a, red_a = build()
    scan_coord = RandomEffectCoordinate(
        ds_a, red_a, _cfg(1.0), TaskType.LOGISTIC_REGRESSION
    )
    assert scan_coord._entity_mesh is not None
    assert scan_coord._train_scan_sharded is not None
    m_scan, _ = scan_coord.train(ds_a.offsets)

    monkeypatch.setenv("PHOTON_SWEEP_SCAN", "0")
    ds_b, red_b = build()
    loop_coord = RandomEffectCoordinate(
        ds_b, red_b, _cfg(1.0), TaskType.LOGISTIC_REGRESSION
    )
    m_loop, _ = loop_coord.train(ds_b.offsets)

    W_scan = np.asarray(m_scan.coefficients_matrix)
    W_loop = np.asarray(m_loop.coefficients_matrix)
    assert np.abs(W_loop).max() > 0.1  # a trained matrix, not the zero start
    assert_sharded_close(W_scan, W_loop, "fit")

    # The coefficient store stayed row-sharded through the scan.
    shard_bytes = [
        s.data.nbytes for s in m_scan.coefficients_matrix.addressable_shards
    ]
    assert len(shard_bytes) == mesh.devices.size
    assert max(shard_bytes) <= m_scan.coefficients_matrix.nbytes // mesh.devices.size

    # Sharding decision + analytic wire accounting surface as proper keys.
    info = scan_coord.sharding_info()
    assert info["entity_sharded"] is True
    assert info["axis_size"] == mesh.devices.size
    assert info["collective_bytes_per_sweep"] > 0
    assert scan_coord.last_train_collective_bytes == info[
        "collective_bytes_per_sweep"
    ]


def test_feature_sharded_wide_fe_matches_replicated(rng):
    """Wide-FE option (SURVEY §2.6 TP row): X columns + coefficient vector
    sharded over the mesh; GSPMD partitions the XLA objective (forward
    all-reduce, local gradient) and the unmodified L-BFGS solver runs on
    sharded vector state. Must land on the replicated path's optimum."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.containers import LabeledData
    from photon_ml_tpu.optimize import problem
    from photon_ml_tpu.optimize.config import (
        L2,
        CoordinateOptimizationConfig,
        OptimizerConfig,
    )
    from photon_ml_tpu.ops.losses import LOGISTIC
    from photon_ml_tpu.parallel.mesh import (
        feature_sharding,
        feature_vector_sharding,
        make_mesh,
    )

    mesh = make_mesh()
    n, d = 512, 1024  # wide: D >> N is the regime feature sharding exists for
    X_np = rng.normal(size=(n, d)).astype(np.float32)
    w_true = (rng.normal(size=d) * 0.2).astype(np.float32)
    y_np = (rng.uniform(size=n) < 1 / (1 + np.exp(-X_np @ w_true))).astype(
        np.float32
    )
    cfg = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=15, tolerance=1e-7),
        regularization=L2,
        reg_weight=1.0,
    )

    def solve(X, y, w0):
        return problem.solve(
            LOGISTIC,
            LabeledData(X, y, jnp.zeros(n), jnp.ones(n)),
            cfg,
            w0,
            None,
            use_pallas=False,
        )

    res_rep = jax.jit(solve)(
        jnp.asarray(X_np), jnp.asarray(y_np), jnp.zeros(d, jnp.float32)
    )

    Xs = jax.device_put(jnp.asarray(X_np), feature_sharding(mesh))
    w0s = jax.device_put(jnp.zeros(d, jnp.float32), feature_vector_sharding(mesh))
    res_sh = jax.jit(solve)(Xs, jnp.asarray(y_np), w0s)

    # Coefficient state stays feature-sharded through the whole solve.
    shards = res_sh.coefficients.addressable_shards
    assert len(shards) == mesh.devices.size
    assert max(s.data.size for s in shards) <= d // mesh.devices.size

    np.testing.assert_allclose(
        np.asarray(res_sh.coefficients),
        np.asarray(res_rep.coefficients),
        rtol=2e-3,
        atol=2e-4,
    )
    assert int(np.asarray(res_sh.iterations)) > 0
