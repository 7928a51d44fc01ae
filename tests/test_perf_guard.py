"""Perf-regression guards for the sparse hot path (r06 raw-speed sprint).

Tier-1 runs only the cheap structural checks; the `slow`+`perf` marked
guards pack bench-like shapes and assert the two r06 contracts that keep
the sprint's wins from silently regressing:

  * the fused sparse objective ENGAGES on the bench shape (r03 shipped a
    gate bug that silently kept it off for a whole round), and
  * the pack no longer dominates the sparse wall: on the device path the
    placement pass leaves the host CPU entirely (pack_host stage == 0),
    and the host fallback's native counting sort beats the numpy argsort
    oracle it replaced.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from photon_ml_tpu.data import bucketed
from photon_ml_tpu.ops import pallas_glm, pallas_sparse
from photon_ml_tpu.utils.observability import TimingRegistry, stage_scope


@pytest.fixture
def interpret_kernels():
    old = pallas_glm.FORCE_INTERPRET
    pallas_glm.FORCE_INTERPRET = True
    yield
    pallas_glm.FORCE_INTERPRET = old


def _bench_like_coo(n=131072, d=4096, k=32, seed=17):
    rng = np.random.default_rng(seed)
    rows = np.repeat(np.arange(n, dtype=np.int64), k)
    cols = rng.integers(0, d, size=n * k).astype(np.int64)
    vals = rng.normal(size=n * k).astype(np.float32)
    return rows, cols, vals, n, d


@pytest.mark.slow
@pytest.mark.perf
class TestSparsePerfGuards:
    def test_fused_path_engages_on_bench_shape(
        self, interpret_kernels, monkeypatch
    ):
        """kernel_engaged on the (scaled) bench shape: the pack gates must
        accept it AND the fused single-stream kernel must be the dispatch
        (should_use + fused_feasible) — the r03 regression shape."""
        monkeypatch.setenv("PHOTON_DEVICE_PACK", "1")
        rows, cols, vals, n, d = _bench_like_coo()
        bf = pallas_sparse.maybe_pack_coo(rows, cols, vals, n, d)
        assert bf is not None, "pack gates declined the bench shape"
        assert pallas_sparse.should_use(bf)
        assert pallas_sparse.fused_feasible(bf), (
            "bench shape fell off the fused kernel onto the composed path"
        )
        assert bf.density_report()["pad_blowup"] <= pallas_sparse.MAX_PAD_BLOWUP

    def test_device_pack_leaves_host_cpu(self, interpret_kernels, monkeypatch):
        """Pack non-dominance, device path: the placement pass must record
        NO host-placement wall — everything lands under pack_device (plus
        the small level-2 spill tail)."""
        monkeypatch.setenv("PHOTON_DEVICE_PACK", "1")
        rows, cols, vals, n, d = _bench_like_coo(n=65536, k=16)
        reg = TimingRegistry()
        with stage_scope(reg):
            bf = pallas_sparse.maybe_pack_coo(rows, cols, vals, n, d)
        assert bf is not None
        assert reg.get_note("pack_path") == "device"
        assert reg.get("pack_device") > 0.0
        # Level 1 — ~99% of entries on this uniform shape — must not have
        # paid a host placement pass; only the spill tail may.
        assert reg.get("pack_host") <= 0.25 * reg.get("pack_device") + 0.05

    def test_native_pack_beats_numpy_oracle(self, monkeypatch):
        """Pack non-dominance, host fallback: the native counting sort must
        beat the numpy argsort oracle it replaced (generous 1.5x slack —
        this is a regression tripwire, not a benchmark)."""
        import time

        from photon_ml_tpu.native.bucketed_pack import pack_level_native

        rows, cols, vals, n, d = _bench_like_coo(n=65536, k=32)
        monkeypatch.setenv("PHOTON_DEVICE_PACK", "0")
        monkeypatch.setenv("PHOTON_DISABLE_NATIVE", "1")
        t0 = time.perf_counter()
        bucketed.pack_bucketed(rows, cols, vals, n, d, host_only=True)
        numpy_wall = time.perf_counter() - t0
        monkeypatch.delenv("PHOTON_DISABLE_NATIVE")
        probe = pack_level_native(
            np.zeros(0, np.int32), np.zeros(0, np.int32),
            np.zeros(0, np.float32), 1, 1, 11, 1024,
        )
        if probe is None:
            pytest.skip("native library unavailable (no compiler)")
        t0 = time.perf_counter()
        bucketed.pack_bucketed(rows, cols, vals, n, d, host_only=True)
        native_wall = time.perf_counter() - t0
        assert native_wall < numpy_wall * 1.5, (
            f"native pack {native_wall:.3f}s vs numpy {numpy_wall:.3f}s — "
            "the counting sort regressed below the oracle it replaced"
        )


class TestDataPlaneGuards:
    """r09 streaming data plane: cheap structural gate checks run in
    tier-1; the scaled-down e2e guard (slow+perf) asserts the two walls
    the tentpole exists to move — device RE assembly engaged, prepare not
    dominating solve."""

    def test_device_assembly_auto_on_for_accelerators(self, monkeypatch):
        """The auto gate must engage on accelerator backends (the r03
        pack-gate bug class: a silently-off fast path for a whole round).
        Backend is monkeypatched — this checks the DECISION, not the
        hardware."""
        import jax

        from photon_ml_tpu.data import device_assemble

        monkeypatch.delenv("PHOTON_DEVICE_ASSEMBLY", raising=False)
        for backend, expect in (("tpu", True), ("gpu", True), ("cpu", False)):
            monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
            assert device_assemble.enabled() is expect, backend

    def test_stream_ingest_auto_gates_on_cores(self, monkeypatch):
        from photon_ml_tpu.io import avro_fast

        monkeypatch.delenv("PHOTON_STREAM_INGEST", raising=False)
        monkeypatch.setenv("PHOTON_HOST_THREADS", "1")
        assert avro_fast.stream_ingest_enabled() is False
        monkeypatch.setenv("PHOTON_HOST_THREADS", "4")
        assert avro_fast.stream_ingest_enabled() is True
        monkeypatch.setenv("PHOTON_STREAM_INGEST", "0")
        assert avro_fast.stream_ingest_enabled() is False


@pytest.mark.slow
@pytest.mark.perf
class TestPrepareNotDominantGuard:
    def test_scaled_e2e_prepare_below_solve(self, monkeypatch, tmp_path):
        """Scaled-down e2e_from_disk shape (the r05 469 s wall, shrunk):
        with the streaming data plane forced on, device RE assembly must
        ENGAGE and the prepare wall must come in under the solve wall —
        the acceptance shape of ISSUE 9, as a regression tripwire."""
        import photon_ml_tpu.io.avro_data as ad
        from photon_ml_tpu.data.game_dataset import (
            FixedEffectDataConfig,
            RandomEffectDataConfig,
        )
        from photon_ml_tpu.estimators.game_estimator import GameEstimator
        from photon_ml_tpu.native.avro_writer import (
            write_training_examples_columnar,
        )
        from photon_ml_tpu.optimize.config import (
            L2,
            CoordinateOptimizationConfig,
            OptimizerConfig,
        )
        from photon_ml_tpu.types import TaskType
        from photon_ml_tpu.utils.contracts import (
            INGEST_TIMING_REQUIRED_KEYS,
        )

        monkeypatch.setenv("PHOTON_DEVICE_ASSEMBLY", "1")
        monkeypatch.setenv("PHOTON_DEVICE_PACK", "1")
        monkeypatch.setenv("PHOTON_STREAM_INGEST", "1")
        monkeypatch.setenv("PHOTON_HOST_THREADS", "4")
        rows_n, d, k = 120_000, 200, 8
        n_users, n_movies = rows_n // 145, rows_n // 740
        rng = np.random.default_rng(11)
        users = rng.integers(0, n_users, size=rows_n)
        movies = rng.integers(0, n_movies, size=rows_n)
        indptr = np.arange(rows_n + 1, dtype=np.int64) * k
        ids = rng.integers(0, d, size=rows_n * k).astype(np.int32)
        vals = rng.normal(size=rows_n * k)
        labels = (rng.uniform(size=rows_n) > 0.5).astype(np.float64)
        names = [f"f{i}" for i in range(d)]
        half = rows_n // 2
        for fi, (lo, hi) in enumerate([(0, half), (half, rows_n)]):
            write_training_examples_columnar(
                str(tmp_path / f"part-{fi}.avro"),
                labels[lo:hi],
                indptr[lo : hi + 1] - indptr[lo],
                ids[indptr[lo] : indptr[hi]],
                vals[indptr[lo] : indptr[hi]],
                names,
                int_tags={"userId": users[lo:hi], "movieId": movies[lo:hi]},
            )
        ds, _ = ad.read_game_dataset(
            str(tmp_path),
            {"g": ad.FeatureShardConfig(("features",), True)},
            id_tag_fields=["userId", "movieId"],
        )
        missing = [
            k2 for k2 in INGEST_TIMING_REQUIRED_KEYS if k2 not in ds.ingest_timing
        ]
        assert not missing, missing
        est = GameEstimator(
            TaskType.LOGISTIC_REGRESSION,
            {
                "global": FixedEffectDataConfig("g"),
                "per-user": RandomEffectDataConfig(
                    "userId", "g", active_upper_bound=128
                ),
                "per-movie": RandomEffectDataConfig(
                    "movieId", "g", active_upper_bound=256
                ),
            },
        )
        cfgs = {
            "global": CoordinateOptimizationConfig(
                optimizer=OptimizerConfig(max_iterations=10, tolerance=1e-6),
                regularization=L2,
                reg_weight=1.0,
            ),
            "per-user": CoordinateOptimizationConfig(
                optimizer=OptimizerConfig(max_iterations=5, tolerance=1e-5),
                regularization=L2,
                reg_weight=10.0,
            ),
            "per-movie": CoordinateOptimizationConfig(
                optimizer=OptimizerConfig(max_iterations=5, tolerance=1e-5),
                regularization=L2,
                reg_weight=10.0,
            ),
        }
        est.fit(ds, None, [cfgs])
        ft = est.fit_timing
        assert ft["re_path"] == "device", (
            "device-side RE assembly did not engage on the e2e shape"
        )
        assert ft["re_host_s"] == 0.0
        assert ft["prepare_s"] < ft["solve_s"], (
            f"prepare {ft['prepare_s']:.1f}s dominates solve "
            f"{ft['solve_s']:.1f}s — the r05 wall is back"
        )
