"""Bucketed sparse layout + Pallas kernel tests.

Kernel bodies run in interpret mode on the CPU mesh (pallas_glm.FORCE_INTERPRET
pattern, as in test_pallas_glm.py); numerics are checked against float64
references built from the raw COO triplets.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from photon_ml_tpu.data import bucketed
from photon_ml_tpu.data.bucketed import (
    BucketedSparseFeatures,
    pack_bucketed,
    pack_from_ell,
    to_coo,
)
from photon_ml_tpu.data.containers import LabeledData, SparseFeatures
from photon_ml_tpu.ops import pallas_glm, pallas_sparse


def _random_coo(rng, n_rows, dim, nnz, hot_fraction=0.0):
    rows = rng.integers(0, n_rows, size=nnz).astype(np.int64)
    cols = rng.integers(0, dim, size=nnz).astype(np.int64)
    if hot_fraction:
        n_hot = int(nnz * hot_fraction)
        cols[:n_hot] = 3  # single hot feature -> hot bucket -> spill paths
    vals = rng.normal(size=nnz).astype(np.float32)
    return rows, cols, vals


def _dense(rows, cols, vals, n_rows, dim):
    M = np.zeros((n_rows, dim), np.float64)
    np.add.at(M, (rows, cols), vals.astype(np.float64))
    return M


class TestPacking:
    @pytest.mark.parametrize("row_aligned", [True, False])
    def test_roundtrip_preserves_every_entry(self, row_aligned):
        rng = np.random.default_rng(0)
        rows, cols, vals = _random_coo(rng, 5000, 300, 40000, hot_fraction=0.1)
        bf = pack_bucketed(rows, cols, vals, 5000, 300, row_aligned=row_aligned)
        assert bf.level1.row_aligned == row_aligned
        r2, c2, v2 = to_coo(bf)
        assert np.array_equal(
            _dense(rows, cols, vals, 5000, 300), _dense(r2, c2, v2, 5000, 300)
        )

    def test_hot_feature_spills_not_drops(self):
        rng = np.random.default_rng(1)
        rows, cols, vals = _random_coo(rng, 4096, 256, 30000, hot_fraction=0.5)
        bf = pack_bucketed(rows, cols, vals, 4096, 256)
        rep = bf.density_report()
        assert rep["level1_fraction"] < 1.0  # the hot bucket overflowed L1
        r2, c2, v2 = to_coo(bf)
        assert np.array_equal(
            _dense(rows, cols, vals, 4096, 256), _dense(r2, c2, v2, 4096, 256)
        )

    def test_pack_from_ell_drops_padding(self):
        sp = SparseFeatures(
            indices=jnp.asarray([[1, 2, 0], [4, 0, 0]], jnp.int32),
            values=jnp.asarray([[1.0, 2.0, 0.0], [3.0, 0.0, 0.0]], jnp.float32),
            dim=6,
        )
        bf = pack_from_ell(sp)
        r2, c2, v2 = to_coo(bf)
        M = _dense(r2, c2, v2, 2, 6)
        assert M[0, 1] == 1.0 and M[0, 2] == 2.0 and M[1, 4] == 3.0
        assert M.sum() == 6.0  # nothing extra (padding zeros dropped)

    def test_empty_matrix(self):
        bf = pack_bucketed(
            np.zeros(0, np.int64), np.zeros(0, np.int64), np.zeros(0, np.float32), 10, 7
        )
        z = pallas_sparse.matvec_xla(bf, jnp.ones(7))
        assert z.shape == (10,) and float(jnp.abs(z).max()) == 0.0


@pytest.fixture
def interpret_kernels():
    old = pallas_glm.FORCE_INTERPRET
    pallas_glm.FORCE_INTERPRET = True
    yield
    pallas_glm.FORCE_INTERPRET = old


class TestKernelParity:
    @pytest.mark.parametrize("row_aligned", [True, False])
    @pytest.mark.parametrize("shape", [(5000, 300, 35000), (9000, 700, 60000)])
    def test_matvec_rmatvec_match_f64(self, shape, row_aligned, interpret_kernels):
        n, d, nnz = shape
        rng = np.random.default_rng(2)
        rows, cols, vals = _random_coo(rng, n, d, nnz, hot_fraction=0.05)
        bf = pack_bucketed(rows, cols, vals, n, d, row_aligned=row_aligned)
        M = _dense(rows, cols, vals, n, d)
        w = rng.normal(size=d).astype(np.float32)
        u = rng.normal(size=n).astype(np.float32)

        z = np.asarray(pallas_sparse.matvec(bf, jnp.asarray(w), interpret=True))
        g = np.asarray(pallas_sparse.rmatvec(bf, jnp.asarray(u), interpret=True))
        gs = np.asarray(
            pallas_sparse.rmatvec(bf, jnp.asarray(u), interpret=True, square=True)
        )
        np.testing.assert_allclose(z, M @ w, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(g, M.T @ u, rtol=2e-5, atol=2e-5)
        gs_ref = np.zeros(d)
        np.add.at(gs_ref, cols, vals.astype(np.float64) ** 2 * u[rows])
        np.testing.assert_allclose(gs, gs_ref, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("row_aligned", [True, False])
    def test_xla_reference_matches_f64(self, row_aligned):
        rng = np.random.default_rng(3)
        rows, cols, vals = _random_coo(rng, 3000, 500, 20000)
        bf = pack_bucketed(rows, cols, vals, 3000, 500, row_aligned=row_aligned)
        M = _dense(rows, cols, vals, 3000, 500)
        w = rng.normal(size=500).astype(np.float32)
        u = rng.normal(size=3000).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(pallas_sparse.matvec_xla(bf, jnp.asarray(w))), M @ w, rtol=2e-5, atol=2e-5
        )
        np.testing.assert_allclose(
            np.asarray(pallas_sparse.rmatvec_xla(bf, jnp.asarray(u))), M.T @ u, rtol=2e-5, atol=2e-5
        )

    def test_to_dense_xla(self):
        rng = np.random.default_rng(4)
        rows, cols, vals = _random_coo(rng, 600, 130, 4000, hot_fraction=0.3)
        bf = pack_bucketed(rows, cols, vals, 600, 130)
        np.testing.assert_allclose(
            np.asarray(pallas_sparse.to_dense_xla(bf)),
            _dense(rows, cols, vals, 600, 130),
            rtol=1e-6,
            atol=1e-6,
        )


def _assert_same_layout(a, b):
    """Bitwise equality of two BucketedSparseFeatures layouts."""
    assert a.level1.row_aligned == b.level1.row_aligned
    assert a.level1.spv == b.level1.spv
    np.testing.assert_array_equal(
        np.asarray(a.level1.packed), np.asarray(b.level1.packed)
    )
    np.testing.assert_array_equal(
        np.asarray(a.level1.values), np.asarray(b.level1.values)
    )
    assert (a.level2 is None) == (b.level2 is None)
    if a.level2 is not None:
        np.testing.assert_array_equal(
            np.asarray(a.level2.packed), np.asarray(b.level2.packed)
        )
        np.testing.assert_array_equal(
            np.asarray(a.level2.values), np.asarray(b.level2.values)
        )
    np.testing.assert_array_equal(
        np.asarray(a.overflow_rows), np.asarray(b.overflow_rows)
    )
    np.testing.assert_array_equal(
        np.asarray(a.overflow_cols), np.asarray(b.overflow_cols)
    )
    np.testing.assert_array_equal(
        np.asarray(a.overflow_vals), np.asarray(b.overflow_vals)
    )


class TestDevicePack:
    """The XLA counting-sort pack must place every entry exactly where the
    host counting sort does — the device path swaps WHERE the pack runs,
    never what it produces (tentpole acceptance: bitwise layout parity)."""

    def _both(self, rows, cols, vals, n, d, monkeypatch, **kw):
        monkeypatch.setenv("PHOTON_DEVICE_PACK", "0")
        host = pack_bucketed(rows, cols, vals, n, d, host_only=True, **kw)
        monkeypatch.setenv("PHOTON_DEVICE_PACK", "1")
        dev = pack_bucketed(rows, cols, vals, n, d, **kw)
        return host, dev

    @pytest.mark.parametrize("row_aligned", [True, False])
    def test_device_pack_matches_host_pack_bitwise(self, row_aligned, monkeypatch):
        rng = np.random.default_rng(12)
        rows, cols, vals = _random_coo(rng, 5000, 300, 40000, hot_fraction=0.1)
        host, dev = self._both(
            rows, cols, vals, 5000, 300, monkeypatch, row_aligned=row_aligned
        )
        _assert_same_layout(host, dev)

    def test_duplicate_columns_and_empty_rows(self, monkeypatch):
        """The edge cases a rank-assignment bug would corrupt: repeated
        (row, col) entries must keep their input order (both land, summing
        on decode), and rows with no entries must stay empty."""
        n, d = 4200, 260
        rng = np.random.default_rng(13)
        rows, cols, vals = _random_coo(rng, n, d, 20000)
        # Duplicate-column block: the same (row, col) pair many times, with
        # distinct values so placement order is observable.
        dup_rows = np.full(500, 7, np.int64)
        dup_cols = np.full(500, 33, np.int64)
        dup_vals = (np.arange(500, dtype=np.float32) + 1.0) * 1e-3
        rows = np.concatenate([rows, dup_rows])
        cols = np.concatenate([cols, dup_cols])
        vals = np.concatenate([vals, dup_vals])
        # Empty rows: everything below row 2048 moved out of [100, 2048).
        keep = ~((rows >= 100) & (rows < 2048))
        rows, cols, vals = rows[keep], cols[keep], vals[keep]
        host, dev = self._both(rows, cols, vals, n, d, monkeypatch)
        _assert_same_layout(host, dev)
        r2, c2, v2 = to_coo(dev)
        assert not (((r2 >= 100) & (r2 < 2048)).any())
        np.testing.assert_allclose(
            _dense(r2, c2, v2, n, d), _dense(rows, cols, vals, n, d)
        )

    def test_empty_matrix_device(self, monkeypatch):
        monkeypatch.setenv("PHOTON_DEVICE_PACK", "1")
        bf = pack_bucketed(
            np.zeros(0, np.int64),
            np.zeros(0, np.int64),
            np.zeros(0, np.float32),
            10,
            7,
        )
        z = pallas_sparse.matvec_xla(bf, jnp.ones(7))
        assert z.shape == (10,) and float(jnp.abs(z).max()) == 0.0

    def test_enabled_gate(self, monkeypatch):
        from photon_ml_tpu.data import device_pack

        monkeypatch.setenv("PHOTON_DEVICE_PACK", "0")
        assert not device_pack.enabled()
        monkeypatch.setenv("PHOTON_DEVICE_PACK", "1")
        assert device_pack.enabled()
        monkeypatch.delenv("PHOTON_DEVICE_PACK")
        # auto: on only with an accelerator attached
        assert device_pack.enabled() == (
            jax.default_backend() in ("tpu", "gpu")
        )


class TestLayoutPlanner:
    def test_env_forces_layout(self, monkeypatch):
        from photon_ml_tpu.data.bucketed import choose_layout

        monkeypatch.setenv("PHOTON_SPARSE_LAYOUT", "rowalign")
        assert choose_layout(10**6, 10**5, 4096)[0] is True
        monkeypatch.setenv("PHOTON_SPARSE_LAYOUT", "grouped")
        assert choose_layout(10**6, 10**5, 4096)[0] is False

    def test_auto_declines_bench_shape(self, monkeypatch):
        """1M x 64 nnz into 16k dim: lane collisions force a ~2x aligned
        blowup (r05's measured 2.13), above the training threshold — auto
        must keep the grouped layout there."""
        from photon_ml_tpu.data.bucketed import choose_layout

        monkeypatch.delenv("PHOTON_SPARSE_LAYOUT", raising=False)
        aligned, _ = choose_layout(64 * 10**6, 10**6, 16384)
        assert aligned is False

    def test_auto_declines_when_lane_load_exceeds_capacity(self, monkeypatch):
        """Regression: lam >~ 746 underflowed exp(-lam) to 0 in the naive
        Poisson recurrence, so the planner saw ZERO spill on dense shapes
        whose per-lane load (~1562 here) dwarfs even MAX_SP capacity, and
        picked an aligned layout that spilled ~99% of entries to level 2.
        The log-space tail + the spill-fraction gate must decline."""
        from photon_ml_tpu.data.bucketed import (
            _poisson_excess_fraction,
            choose_layout,
        )

        monkeypatch.delenv("PHOTON_SPARSE_LAYOUT", raising=False)
        assert _poisson_excess_fraction(1562.5, 8) > 0.9
        aligned, _ = choose_layout(200_000, 2048, 128)
        assert aligned is False

    def test_auto_accepts_low_collision_shape(self, monkeypatch):
        """Dense-segment regime (high mean entries per lane): the adaptive
        width amortizes the 1024-slot granularity and alignment engages."""
        from photon_ml_tpu.data.bucketed import choose_layout

        monkeypatch.delenv("PHOTON_SPARSE_LAYOUT", raising=False)
        # mean1 = nnz / (T1 * B) = 64M / (16 * 1) = 4M>>MAX_SP; use a shape
        # with mean segment size ~6800: sp granularity is ~15% there.
        n_rows, dim = 32768, 128
        nnz = 16 * 1 * 6800
        aligned, sp1 = choose_layout(nnz, n_rows, dim)
        assert aligned is True and sp1 is not None and sp1 % 1024 == 0


class TestLayoutObjectiveParity:
    """Satellite: the fused sparse objective must agree across layouts —
    (value, gradient, sum_u) from the row-aligned pack vs the grouped pack
    of the SAME matrix, across level-1-only / level-2 / overflow mixes.
    (Exact bitwise equality across layouts is not defined — the two packs
    accumulate in different orders — so the contract is f32-tight
    agreement plus bitwise stability within each layout.)"""

    @pytest.mark.parametrize("hot_fraction", [0.0, 0.25, 0.6])
    def test_fused_objective_layout_parity(self, hot_fraction, interpret_kernels):
        from photon_ml_tpu.ops.losses import LOGISTIC

        rng = np.random.default_rng(21)
        n, d, nnz = 6000, 260, 48000
        rows, cols, vals = _random_coo(rng, n, d, nnz, hot_fraction=hot_fraction)
        bf_g = pack_bucketed(rows, cols, vals, n, d, row_aligned=False)
        bf_a = pack_bucketed(rows, cols, vals, n, d, row_aligned=True)
        if hot_fraction:
            # The hot bucket must actually exercise the spill levels.
            rep = bf_g.density_report()
            assert rep["level1_fraction"] < 1.0
        assert pallas_sparse.fused_feasible(bf_g)
        assert pallas_sparse.fused_feasible(bf_a)
        y = (rng.uniform(size=n) > 0.5).astype(np.float32)
        w = (rng.normal(size=d) * 0.1).astype(np.float32)
        offs = rng.normal(size=n).astype(np.float32) * 0.01
        wts = rng.uniform(0.5, 1.5, size=n).astype(np.float32)
        out = {}
        for name, bf in (("grouped", bf_g), ("aligned", bf_a)):
            val, grad, sum_u = pallas_sparse.fused_value_gradient_sums(
                LOGISTIC,
                jnp.asarray(w),
                jnp.zeros(()),
                bf,
                jnp.asarray(y),
                jnp.asarray(offs),
                jnp.asarray(wts),
                interpret=True,
            )
            out[name] = (float(val), np.asarray(grad), float(sum_u))
        np.testing.assert_allclose(out["grouped"][0], out["aligned"][0], rtol=1e-5)
        np.testing.assert_allclose(
            out["grouped"][1], out["aligned"][1], rtol=2e-4, atol=2e-4
        )
        np.testing.assert_allclose(out["grouped"][2], out["aligned"][2], rtol=1e-5)
        # f64 reference from the raw COO: both layouts must be RIGHT, not
        # merely mutually consistent.
        M = _dense(rows, cols, vals, n, d)
        z = M @ w.astype(np.float64) + offs
        p = 1.0 / (1.0 + np.exp(-z))
        val_ref = np.sum(
            wts * (np.log1p(np.exp(-np.abs(z))) + np.maximum(z, 0) - y * z)
        )
        u_ref = wts * (p - y)
        g_ref = M.T @ u_ref
        for name in ("grouped", "aligned"):
            np.testing.assert_allclose(out[name][0], val_ref, rtol=1e-4)
            np.testing.assert_allclose(
                out[name][1], g_ref, rtol=5e-4, atol=5e-4
            )
            np.testing.assert_allclose(out[name][2], u_ref.sum(), rtol=1e-4)


class TestMaybePack:
    def _ell(self, n, d, k, dtype=np.float32, seed=0):
        rng = np.random.default_rng(seed)
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(dtype)
        return SparseFeatures(jnp.asarray(idx), jnp.asarray(val), d)

    def test_engages_on_worthwhile_shard(self, interpret_kernels):
        sp = self._ell(9000, 200, 8)
        assert pallas_sparse.maybe_pack(sp, 9000) is not None

    def test_declines_low_density(self, interpret_kernels):
        # 1 nnz/row into a wide dim: segment floor of 1024 slots would blow
        # padding up far past the ELL bytes.
        sp = self._ell(100_000, 16384, 1)
        assert pallas_sparse.maybe_pack(sp, 100_000) is None

    # (the f64 decline branch is untestable here: without jax_enable_x64,
    # jnp.asarray coerces f64 input to f32 before the gate ever sees it)

    def test_declines_small_problem(self, interpret_kernels):
        sp = self._ell(1000, 200, 8)
        assert pallas_sparse.maybe_pack(sp, 1000) is None

    def test_declines_when_disabled(self, interpret_kernels):
        sp = self._ell(9000, 200, 8)
        pallas_glm.set_enabled(False)
        try:
            assert pallas_sparse.maybe_pack(sp, 9000) is None
        finally:
            pallas_glm.set_enabled(True)


class TestObjectiveIntegration:
    def test_objective_with_bucketed_features(self, interpret_kernels):
        """value_and_gradient / hessian paths agree between ELL and bucketed."""
        from photon_ml_tpu.ops import objective
        from photon_ml_tpu.ops.losses import LOGISTIC

        rng = np.random.default_rng(5)
        n, d, k = 4000, 260, 9
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        sp = SparseFeatures(jnp.asarray(idx), jnp.asarray(val), d)
        bf = pack_from_ell(sp)
        y = (rng.uniform(size=n) > 0.5).astype(np.float32)
        w = (rng.normal(size=d) * 0.1).astype(np.float32)
        mk = lambda feats: LabeledData(
            feats, jnp.asarray(y), jnp.zeros(n), jnp.ones(n)
        )
        route = objective.route_for(bf)
        assert route.sparse_kernels
        v1, g1 = objective.value_and_gradient(LOGISTIC, jnp.asarray(w), mk(sp), l2=0.5)
        v2, g2 = objective.value_and_gradient(LOGISTIC, jnp.asarray(w), mk(bf), l2=0.5, route=route)
        np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4, atol=1e-4)

        hv1 = objective.hessian_vector(LOGISTIC, jnp.asarray(w), jnp.asarray(w), mk(sp), l2=0.5)
        hv2 = objective.hessian_vector(LOGISTIC, jnp.asarray(w), jnp.asarray(w), mk(bf), l2=0.5, route=route)
        np.testing.assert_allclose(np.asarray(hv1), np.asarray(hv2), rtol=1e-4, atol=1e-4)

        d1 = objective.hessian_diagonal(LOGISTIC, jnp.asarray(w), mk(sp), l2=0.5)
        d2 = objective.hessian_diagonal(LOGISTIC, jnp.asarray(w), mk(bf), l2=0.5)
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-4, atol=1e-4)

    def test_fixed_effect_coordinate_packs_and_trains(self, interpret_kernels):
        """A big-enough sparse shard repacks to bucketed and converges to the
        same optimum as the ELL/XLA path."""
        from photon_ml_tpu.data.game_dataset import GameDataset
        from photon_ml_tpu.game.coordinate import FixedEffectCoordinate
        from photon_ml_tpu.optimize.config import (
            L2,
            CoordinateOptimizationConfig,
            OptimizerConfig,
        )
        from photon_ml_tpu.types import TaskType

        rng = np.random.default_rng(6)
        # Wider than 128 ids a plane: the dense block is declined
        # (containers.dense_block_admitted), so the pack is what is judged.
        n, d, k = 9000, 800, 6
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        sp = SparseFeatures(jnp.asarray(idx), jnp.asarray(val), d)
        w_true = rng.normal(size=d) * 0.3
        M = _dense(np.repeat(np.arange(n), k), idx.reshape(-1), val.reshape(-1), n, d)
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-M @ w_true))).astype(np.float32)
        ds = GameDataset.build({"s": sp}, y)
        cfg = CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=30, tolerance=1e-8),
            regularization=L2,
            reg_weight=1.0,
        )
        coord = FixedEffectCoordinate(ds, "s", cfg, TaskType.LOGISTIC_REGRESSION)
        assert isinstance(coord._features, BucketedSparseFeatures)
        model, res = coord.train(ds.offsets)

        pallas_glm.set_enabled(False)
        try:
            coord_ell = FixedEffectCoordinate(ds, "s", cfg, TaskType.LOGISTIC_REGRESSION)
            assert isinstance(coord_ell._features, SparseFeatures)
            model_ell, _ = coord_ell.train(ds.offsets)
        finally:
            pallas_glm.set_enabled(True)
        np.testing.assert_allclose(
            np.asarray(model.coefficients.means),
            np.asarray(model_ell.coefficients.means),
            rtol=5e-3,
            atol=5e-4,
        )
        # scoring path uses the bucketed features too
        s1 = np.asarray(coord.score(model))
        s2 = np.asarray(coord_ell.score(model))
        np.testing.assert_allclose(s1, s2, rtol=1e-4, atol=1e-4)

    def test_refused_kernel_is_an_error_at_coordinate_construction(
        self, interpret_kernels, monkeypatch
    ):
        """A sparse kernel the compiler refuses stops the job where the
        pack is accepted, with the compiler's message — not inside the
        solver's trace, and never by switching to the XLA reference."""
        from photon_ml_tpu.data.game_dataset import GameDataset
        from photon_ml_tpu.game.coordinate import FixedEffectCoordinate
        from photon_ml_tpu.optimize.config import L2, CoordinateOptimizationConfig
        from photon_ml_tpu.types import TaskType

        rng = np.random.default_rng(16)
        # Shapes no other test traces, wider than 128 ids a plane (no dense block).
        n, d, k = 9100, 790, 6
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        ds = GameDataset.build({"s": SparseFeatures(jnp.asarray(idx), jnp.asarray(val), d)}, y)
        cfg = CoordinateOptimizationConfig(regularization=L2, reg_weight=1.0)

        def refuse(*a, **k):
            raise AssertionError("indices_aval.shape == in_aval.shape + (1,)")

        monkeypatch.setattr(pallas_sparse, "_level_rmatvec", refuse)
        with pytest.raises(RuntimeError, match=r"do not compile.*indices_aval"):
            FixedEffectCoordinate(ds, "s", cfg, TaskType.LOGISTIC_REGRESSION)
        monkeypatch.undo()
        coord = FixedEffectCoordinate(ds, "s", cfg, TaskType.LOGISTIC_REGRESSION)
        assert isinstance(coord._features, BucketedSparseFeatures)


class TestHostCooPack:
    def test_coordinate_packs_from_host_csr(self, interpret_kernels, monkeypatch):
        """Ingest-stashed host CSR must feed the bucketed pack directly —
        the device-ELL pull-back (maybe_pack) must not run."""
        from photon_ml_tpu.data.game_dataset import GameDataset, HostCSR
        from photon_ml_tpu.game.coordinate import FixedEffectCoordinate
        from photon_ml_tpu.optimize.config import (
            L2,
            CoordinateOptimizationConfig,
            OptimizerConfig,
        )
        from photon_ml_tpu.types import TaskType

        rng = np.random.default_rng(9)
        n, d, k = 9000, 800, 6  # no dense block: d > 128 k
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        sp = SparseFeatures(jnp.asarray(idx), jnp.asarray(val), d)
        y = (rng.uniform(size=n) > 0.5).astype(np.float32)
        ds = GameDataset.build({"s": sp}, y)
        ds.host_csr = {
            "s": HostCSR(
                np.arange(n + 1, dtype=np.int64) * k,
                idx.reshape(-1).astype(np.int64),
                val.reshape(-1),
                d,
            )
        }
        monkeypatch.setattr(
            pallas_sparse,
            "maybe_pack",
            lambda *a, **k: pytest.fail("device-ELL pull-back ran"),
        )
        cfg = CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=5, tolerance=1e-6),
            regularization=L2,
            reg_weight=1.0,
        )
        coord = FixedEffectCoordinate(ds, "s", cfg, TaskType.LOGISTIC_REGRESSION)
        assert isinstance(coord._features, BucketedSparseFeatures)
        assert coord.route.kind == "bucketed_fused"

    def test_async_ingest_pack_joins_at_coordinate(
        self, interpret_kernels, monkeypatch
    ):
        """begin_pack_async at stash time -> the coordinate joins the
        background host pack (finish_pack) and the layout matches the
        synchronous pack exactly. The pipeline is forced on: the test is
        about join/pack parity, not the 1-core auto-off gate (which made
        it fail on single-core CI hosts), and the device pack is forced
        off so a background host thread exists to join at all."""
        monkeypatch.setenv("PHOTON_PIPELINE", "1")
        monkeypatch.setenv("PHOTON_DEVICE_PACK", "0")
        from photon_ml_tpu.data.game_dataset import GameDataset, HostCSR
        from photon_ml_tpu.game.coordinate import FixedEffectCoordinate
        from photon_ml_tpu.optimize.config import (
            L2,
            CoordinateOptimizationConfig,
            OptimizerConfig,
        )
        from photon_ml_tpu.types import TaskType

        rng = np.random.default_rng(10)
        n, d, k = 9000, 800, 6  # no dense block: d > 128 k
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        y = (rng.uniform(size=n) > 0.5).astype(np.float32)
        cols = idx.reshape(-1).astype(np.int64)
        vals = val.reshape(-1)
        indptr = np.arange(n + 1, dtype=np.int64) * k

        ds = GameDataset.build(
            {"s": SparseFeatures(jnp.asarray(idx), jnp.asarray(val), d)}, y
        )
        csr = HostCSR(indptr, cols, vals, d)
        ds.host_csr = {"s": csr}
        pallas_sparse.begin_pack_async(csr, n)
        assert csr.pack_future is not None
        cfg = CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=5, tolerance=1e-6),
            regularization=L2,
            reg_weight=1.0,
        )
        coord = FixedEffectCoordinate(ds, "s", cfg, TaskType.LOGISTIC_REGRESSION)
        assert isinstance(coord._features, BucketedSparseFeatures)
        # Same layout as the synchronous data-plane pack.
        sync = pallas_sparse.pack_coo_auto(
            np.repeat(np.arange(n, dtype=np.int64), k), cols, vals, n, d
        )
        np.testing.assert_array_equal(
            np.asarray(coord._features.level1.packed),
            np.asarray(sync.level1.packed),
        )
        np.testing.assert_array_equal(
            np.asarray(coord._features.level1.values),
            np.asarray(sync.level1.values),
        )
        model, res = coord.train(ds.offsets)
        assert np.isfinite(float(res.loss))
