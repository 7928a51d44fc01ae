"""Failure-domain hardening tests (the chaos harness).

The contracts, mirroring the reference's free recovery from Spark lineage
re-computation + driver retries (CoordinateDescent.scala:325-341):

* a training run under injected TRANSIENT faults (decode, upload, one
  diverged solve) completes and produces a model BITWISE-identical to the
  fault-free run — retries/fallbacks move when work happens, never what it
  computes;
* a SIGKILLed training process, resumed from its checkpoint, lands exactly
  where the uninterrupted run does;
* the async data plane degrades instead of dying: failed uploader jobs are
  evicted (retryable), failed prefetches fall back to synchronous uploads,
  failed background packs/builds fall back to in-thread rebuilds;
* a non-finite coordinate update is rejected, counted, and NEVER written to
  the durable checkpoint;
* a checkpoint with a truncated/missing model file is refused with an
  actionable integrity error, not loaded as garbage.
"""

import json
import os
import signal
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data import pipeline as pl
from photon_ml_tpu.data.containers import SparseFeatures
from photon_ml_tpu.data.game_dataset import (
    GameDataset,
    RandomEffectDataConfig,
    ShardDict,
    build_random_effect_dataset,
)
from photon_ml_tpu.game.checkpoint import (
    CheckpointIntegrityError,
    CoordinateDescentCheckpoint,
)
from photon_ml_tpu.game.coordinate import (
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent
from photon_ml_tpu.game.model import Coefficients, FixedEffectModel
from photon_ml_tpu.optimize.config import (
    L2,
    CoordinateOptimizationConfig,
    OptimizerConfig,
)
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------------- fixtures


def _chaos_dataset(n=180, d=4, n_entities=5, d_re=3, seed=0):
    rng = np.random.default_rng(seed)
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


def _chaos_coords(ds):
    cfg_f = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=25, tolerance=1e-8),
        regularization=L2,
        reg_weight=0.5,
    )
    cfg_r = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=20, tolerance=1e-8),
        regularization=L2,
        reg_weight=1.0,
    )
    red = build_random_effect_dataset(
        ds, RandomEffectDataConfig("entityId", "per_entity", min_bucket=4)
    )
    return {
        "fixed": FixedEffectCoordinate(
            ds, "global", cfg_f, TaskType.LOGISTIC_REGRESSION
        ),
        "per-entity": RandomEffectCoordinate(
            ds, red, cfg_r, TaskType.LOGISTIC_REGRESSION
        ),
    }


def _model_arrays(result):
    out = {}
    for cid, m in result.model.models.items():
        if hasattr(m, "coefficients_matrix"):
            out[cid] = np.asarray(m.coefficients_matrix)
        else:
            out[cid] = np.asarray(m.coefficients.means)
    return out


def _assert_bitwise_equal(a, b):
    assert set(a) == set(b)
    for cid in a:
        assert np.array_equal(a[cid], b[cid]), (
            f"coordinate {cid} diverged bitwise"
        )


# --------------------------------------------------------- fault primitives


class TestFaultPlan:
    def test_parse_forms(self):
        plan = faults.FaultPlan.parse("decode:2,upload@3+5,solve:p0.5", seed=9)
        assert plan.sites["decode"].first_n == 2
        assert plan.sites["upload"].indices == frozenset({3, 5})
        assert plan.sites["solve"].probability == 0.5
        bare = faults.FaultPlan.parse("pack")
        assert bare.sites["pack"].first_n == 1

    def test_unknown_site_rejected(self):
        with pytest.raises(ValueError, match="unknown fault site"):
            faults.FaultPlan.parse("uplaod:1")

    def test_deterministic_schedule(self):
        """The probabilistic schedule replays exactly for a given seed and
        differs across seeds (so chaos runs are reproducible)."""

        def schedule(seed):
            spec = faults.SiteSpec(probability=0.3)
            return [
                spec.should_fail("solve", i, seed) for i in range(1, 200)
            ]

        assert schedule(1) == schedule(1)
        assert any(schedule(1))
        assert not all(schedule(1))
        assert schedule(1) != schedule(2)

    def test_fault_point_counts_and_raises(self):
        with faults.inject("upload:2") as inj:
            with pytest.raises(faults.InjectedFault):
                faults.fault_point("upload")
            with pytest.raises(faults.InjectedFault):
                faults.fault_point("upload")
            faults.fault_point("upload")  # 3rd invocation passes
            faults.fault_point("decode")  # unarmed site: free
            assert inj.injected == {"upload": 2}
            assert inj.invocations == {"upload": 3, "decode": 1}
        faults.fault_point("upload")  # disarmed after the scope

    def test_env_plan(self, monkeypatch):
        monkeypatch.setenv("PHOTON_FAULTS", "decode:1")
        faults.clear()  # force env re-read
        with pytest.raises(faults.InjectedFault):
            faults.fault_point("decode")
        faults.fault_point("decode")


class TestRetry:
    def _policy(self, attempts=3):
        return faults.RetryPolicy(max_attempts=attempts, base_delay_s=0.0)

    def test_retries_transient_then_succeeds(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        assert faults.retry(flaky, self._policy()) == "ok"
        assert len(calls) == 3
        assert faults.counters()["retries"] == 2

    def test_exhaustion_reraises(self):
        def dead():
            raise TimeoutError("always")

        with pytest.raises(TimeoutError):
            faults.retry(dead, self._policy(attempts=2))
        assert faults.counters()["retries"] == 1

    def test_non_transient_raises_immediately(self):
        calls = []

        def buggy():
            calls.append(1)
            raise ValueError("a bug, not weather")

        with pytest.raises(ValueError):
            faults.retry(buggy, self._policy())
        assert len(calls) == 1
        assert faults.counters().get("retries", 0) == 0

    @pytest.mark.parametrize(
        "message, transient",
        [
            ("RESOURCE_EXHAUSTED: Attempting to allocate 17.2G on a 16G chip", False),
            ("INTERNAL: Mosaic failed to compile TPU kernel", False),
            ("INVALID_ARGUMENT: unsupported layout", False),
            ("UNAVAILABLE: peer task went away", True),
            ("DEADLINE_EXCEEDED: collective rendezvous timed out", True),
        ],
    )
    def test_xla_status_decides_what_is_retried(self, message, transient):
        """With the chip attached, a refused compile or an out-of-memory
        re-fails identically: it propagates at once, uncounted. Only the
        statuses that can clear by themselves get the retry policy."""
        import jax

        calls = []

        def failing():
            calls.append(1)
            raise jax.errors.JaxRuntimeError(message)

        exc = jax.errors.JaxRuntimeError(message)
        assert faults.is_device_error(exc) is transient
        with pytest.raises(jax.errors.JaxRuntimeError):
            faults.retry(failing, self._policy(attempts=2))
        assert len(calls) == (2 if transient else 1)
        assert faults.counters().get("retries", 0) == (1 if transient else 0)

    def test_backoff_is_bounded(self):
        p = faults.RetryPolicy(
            max_attempts=10, base_delay_s=0.5, max_delay_s=1.5, backoff=2.0
        )
        assert p.delay(1) == 0.5
        assert p.delay(2) == 1.0
        assert p.delay(5) == 1.5  # capped


# ------------------------------------------------------------ async uploads


class TestUploaderFailureDomain:
    def test_transient_job_failures_retry_in_worker(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("blip")
            return 42

        up = pl.AsyncUploader(
            retry_policy=faults.RetryPolicy(max_attempts=3, base_delay_s=0.0)
        )
        assert up.submit("k", flaky).result(timeout=30) == 42
        assert faults.counters()["retries"] == 2

    def test_failed_job_evicted_so_resubmit_works(self):
        """Satellite: a job whose fn raised must not pin a dead future under
        its key forever — after the failure surfaces, a fresh submit on the
        same key runs a fresh attempt."""

        def dead():
            raise ValueError("permanent")

        up = pl.AsyncUploader()
        fut = up.submit("k", dead)
        with pytest.raises(ValueError):
            fut.result(timeout=30)
        deadline = time.monotonic() + 10
        while up.peek("k") is not None and time.monotonic() < deadline:
            time.sleep(0.01)
        assert up.peek("k") is None, "failed job was not evicted"
        assert up.submit("k", lambda: "second try").result(timeout=30) == (
            "second try"
        )

    def _host_sparse(self):
        rng = np.random.default_rng(3)
        return SparseFeatures(
            rng.integers(0, 40, size=(30, 4)).astype(np.int32),
            rng.normal(size=(30, 4)).astype(np.float32),
            40,
        )

    def test_prefetch_degrades_to_sync_upload(self, monkeypatch):
        """Async attempts all fail -> the consumer degrades to a bounded-
        retry synchronous upload and still gets the device arrays."""
        monkeypatch.setenv("PHOTON_RETRY_BASE_DELAY_S", "0.001")
        sp = self._host_sparse()
        ref = ShardDict({"s": SparseFeatures(sp.indices, sp.values, sp.dim)})[
            "s"
        ]
        d = ShardDict({"s": sp})
        # Default policy = 3 attempts in the worker; arm 4 failures so the
        # async job dies, then the sync fallback burns #4 and succeeds at #5.
        with faults.inject("upload:4"):
            d.prefetch("s")
            got = d["s"]
        assert faults.counters()["fallback_sync_uploads"] == 1
        assert np.array_equal(np.asarray(got.indices), np.asarray(ref.indices))
        assert np.array_equal(np.asarray(got.values), np.asarray(ref.values))

    def test_sync_upload_retries_transient_fault(self, monkeypatch):
        monkeypatch.setenv("PHOTON_RETRY_BASE_DELAY_S", "0.001")
        sp = self._host_sparse()
        with faults.inject("upload:1"):
            got = ShardDict({"s": sp})["s"]
        assert faults.counters()["retries"] == 1
        import jax

        assert isinstance(got.indices, jax.Array)


# -------------------------------------------------------- divergence guard


class _NaNPoison:
    """Wraps a coordinate so selected train() calls return a NaN model —
    a deterministic stand-in for a diverged solve."""

    def __init__(self, inner, poison_calls):
        self.inner = inner
        self.poison_calls = set(poison_calls)
        self.calls = 0

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def train(self, *args, **kwargs):
        self.calls += 1
        model, stats = self.inner.train(*args, **kwargs)
        if self.calls in self.poison_calls:
            bad = jnp.full_like(model.coefficients.means, jnp.nan)
            model = FixedEffectModel(
                Coefficients(bad, model.coefficients.variances), model.task
            )
        return model, stats


class TestDivergenceGuard:
    def test_transient_nan_retried_to_bitwise_parity(self, rng):
        ds = _chaos_dataset()
        clean = run_coordinate_descent(_chaos_coords(ds), 2, seed=4)

        coords = _chaos_coords(ds)
        coords["fixed"] = _NaNPoison(coords["fixed"], poison_calls={2})
        guarded = run_coordinate_descent(coords, 2, seed=4)
        assert guarded.diverged_steps == 1
        _assert_bitwise_equal(_model_arrays(clean), _model_arrays(guarded))

    def test_injected_solve_fault_retried_to_bitwise_parity(self):
        ds = _chaos_dataset()
        clean = run_coordinate_descent(_chaos_coords(ds), 2, seed=4)
        with faults.inject("solve@2"):
            faulted = run_coordinate_descent(_chaos_coords(ds), 2, seed=4)
        assert faulted.diverged_steps == 1
        _assert_bitwise_equal(_model_arrays(clean), _model_arrays(faulted))

    def test_persistent_divergence_keeps_last_good_and_counts(self, tmp_path):
        ds = _chaos_dataset()
        ck = str(tmp_path / "ck")
        coords = _chaos_coords(ds)
        # Every fixed-effect solve diverges: 1 attempt + 1 retry per step,
        # 2 passes -> 4 rejections; the coordinate never gets a model.
        coords["fixed"] = _NaNPoison(coords["fixed"], poison_calls=range(1, 99))
        result = run_coordinate_descent(coords, 2, seed=4, checkpoint_dir=ck)
        assert result.diverged_steps == 4
        assert "fixed" not in result.model.models
        re_mat = np.asarray(result.model.models["per-entity"].coefficients_matrix)
        assert np.isfinite(re_mat).all()

        # The rejected updates were NEVER checkpointed: the durable state
        # reloads finite and has no fixed-effect file.
        state = CoordinateDescentCheckpoint(ck).load(
            TaskType.LOGISTIC_REGRESSION
        )
        assert state.completed_steps == 4  # cursor still advanced
        assert "fixed" not in state.models
        loaded = np.asarray(state.models["per-entity"].coefficients_matrix)
        np.testing.assert_array_equal(loaded, re_mat)

    def test_data_plane_fault_inside_train_surfaces(self):
        """An InjectedFault raised INSIDE train/score (e.g. an upload whose
        retries exhausted) is a data-plane failure, not a divergence: the
        guard must let it surface instead of shipping an untrained model
        behind a diverged counter."""
        ds = _chaos_dataset()
        coords = _chaos_coords(ds)

        class _DeadDataPlane:
            def __init__(self, inner):
                self.inner = inner

            def __getattr__(self, name):
                return getattr(self.inner, name)

            def train(self, *args, **kwargs):
                raise faults.InjectedFault("upload retries exhausted")

        coords["fixed"] = _DeadDataPlane(coords["fixed"])
        with pytest.raises(faults.InjectedFault, match="upload retries"):
            run_coordinate_descent(coords, 1, seed=4)

    def test_rejection_lands_in_stage_registry(self):
        from photon_ml_tpu.utils.observability import TimingRegistry, stage_scope

        ds = _chaos_dataset()
        coords = _chaos_coords(ds)
        coords["fixed"] = _NaNPoison(coords["fixed"], poison_calls={1})
        reg = TimingRegistry()
        with stage_scope(reg):
            run_coordinate_descent(coords, 1, seed=4)
        assert reg.get("diverged") == 1.0


class TestBestModelResumeParity:
    def test_rejected_pass_final_update_keeps_best_selection_on_resume(
        self, tmp_path
    ):
        """Interrupt after the pass's FIRST coordinate, then resume into a
        pass-final coordinate whose update is rejected: best-model
        selection must compare against the persisted validation results
        (reconstructed pass_results), exactly as the uninterrupted run
        compared against its in-memory ones."""
        import dataclasses

        from photon_ml_tpu.evaluation.suite import EvaluationSuite, EvaluatorType
        from photon_ml_tpu.game.model import random_effect_margins

        ds = _chaos_dataset()
        val = _chaos_dataset(seed=99)
        suite = EvaluationSuite([EvaluatorType("AUC")], val.labels, val.weights)

        class _REPoison:
            """Every per-entity solve returns a NaN matrix (persistent
            divergence of the pass-final coordinate)."""

            def __init__(self, inner):
                self.inner = inner

            def __getattr__(self, name):
                return getattr(self.inner, name)

            def train(self, *args, **kwargs):
                model, stats = self.inner.train(*args, **kwargs)
                return (
                    dataclasses.replace(
                        model,
                        coefficients_matrix=jnp.full_like(
                            model.coefficients_matrix, jnp.nan
                        ),
                    ),
                    stats,
                )

        class _Preempt:
            def __init__(self, inner, allowed):
                self.inner = inner
                self.allowed = allowed
                self.calls = 0

            def __getattr__(self, name):
                return getattr(self.inner, name)

            def train(self, *args, **kwargs):
                if self.calls >= self.allowed:
                    raise RuntimeError("simulated preemption")
                self.calls += 1
                return self.inner.train(*args, **kwargs)

        def make():
            coords = _chaos_coords(ds)
            coords["per-entity"] = _REPoison(coords["per-entity"])

            def scorer(cid, model):
                if cid == "fixed":
                    return val.shards["global"] @ model.coefficients.means
                red = coords["per-entity"].re_dataset
                return random_effect_margins(
                    val.shards["per_entity"],
                    red.sample_entity_rows,
                    model.coefficients_matrix,
                    None,
                )

            return coords, scorer

        kwargs = dict(
            validation_suite=suite, validation_offsets=val.offsets, seed=5
        )
        c, s = make()
        straight = run_coordinate_descent(c, 1, validation_scorer=s, **kwargs)

        # Interrupted run: fixed trains + commits (with its validation
        # entry), then the per-entity step is preempted before solving.
        ck = str(tmp_path / "ck")
        c, s = make()
        c["per-entity"] = _Preempt(c["per-entity"], 0)
        with pytest.raises(RuntimeError, match="simulated preemption"):
            run_coordinate_descent(
                c, 1, validation_scorer=s, checkpoint_dir=ck, **kwargs
            )
        c, s = make()
        resumed = run_coordinate_descent(
            c, 1, validation_scorer=s, checkpoint_dir=ck, **kwargs
        )

        def arrays(model):
            return {
                cid: np.asarray(m.coefficients_matrix)
                if hasattr(m, "coefficients_matrix")
                else np.asarray(m.coefficients.means)
                for cid, m in model.models.items()
            }

        # The rejected per-entity update means best was selected against
        # fixed's pass results in BOTH runs (per-entity has no model at all).
        assert "per-entity" not in straight.best_model.models
        _assert_bitwise_equal(
            arrays(straight.best_model), arrays(resumed.best_model)
        )


# ----------------------------------------------------- checkpoint integrity


class TestCheckpointIntegrity:
    def _checkpointed_run(self, tmp_path):
        ds = _chaos_dataset()
        ck = str(tmp_path / "ck")
        run_coordinate_descent(_chaos_coords(ds), 1, seed=2, checkpoint_dir=ck)
        state = json.load(open(os.path.join(ck, "state.json")))
        return ds, ck, state

    def test_checksums_recorded_for_every_model_file(self, tmp_path):
        _, ck, state = self._checkpointed_run(tmp_path)
        assert set(state["checksums"]) == set(state["model_files"].values())
        for c in state["checksums"].values():
            assert c.startswith("crc32:")

    def test_truncated_npz_refused(self, tmp_path):
        ds, ck, state = self._checkpointed_run(tmp_path)
        rel = state["model_files"]["fixed"]
        path = os.path.join(ck, rel)
        data = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(data[: max(1, len(data) // 2)])
        with pytest.raises(
            CheckpointIntegrityError, match="corrupt/torn checkpoint file"
        ):
            CoordinateDescentCheckpoint(ck).load(TaskType.LOGISTIC_REGRESSION)
        # The resume path surfaces the same actionable error.
        with pytest.raises(CheckpointIntegrityError, match="start fresh"):
            run_coordinate_descent(
                _chaos_coords(ds), 2, seed=2, checkpoint_dir=ck
            )

    def test_missing_npz_refused_with_actionable_error(self, tmp_path):
        _, ck, state = self._checkpointed_run(tmp_path)
        os.remove(os.path.join(ck, state["model_files"]["fixed"]))
        with pytest.raises(
            CheckpointIntegrityError, match="missing model file"
        ) as exc:
            CoordinateDescentCheckpoint(ck).load(TaskType.LOGISTIC_REGRESSION)
        assert "delete the checkpoint directory" in str(exc.value)

    def test_pre_checksum_state_still_loads(self, tmp_path):
        """Back-compat: a state.json without a checksums block (written
        before this layer) loads unverified rather than refusing."""
        _, ck, state = self._checkpointed_run(tmp_path)
        del state["checksums"]
        sp = os.path.join(ck, "state.json")
        json.dump(state, open(sp, "w"))
        loaded = CoordinateDescentCheckpoint(ck).load(
            TaskType.LOGISTIC_REGRESSION
        )
        assert set(loaded.models) == set(state["model_files"])

    def test_checkpoint_write_fault_retried(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PHOTON_RETRY_BASE_DELAY_S", "0.001")
        ds = _chaos_dataset()
        ck = str(tmp_path / "ck")
        clean = run_coordinate_descent(_chaos_coords(ds), 1, seed=2)
        with faults.inject("checkpoint_write:1"):
            ckpt_run = run_coordinate_descent(
                _chaos_coords(ds), 1, seed=2, checkpoint_dir=ck
            )
        assert faults.counters()["retries"] >= 1
        _assert_bitwise_equal(_model_arrays(clean), _model_arrays(ckpt_run))
        # The retried write committed intact state.
        loaded = CoordinateDescentCheckpoint(ck).load(
            TaskType.LOGISTIC_REGRESSION
        )
        assert loaded.completed_steps == 2


# ----------------------------------------------- fault-injected fit parity


class TestFaultInjectedParity:
    """The acceptance contract: transient decode/upload/solve faults change
    nothing about the trained model, bit for bit."""

    def _sparse_dataset(self, seed=0):
        rng = np.random.default_rng(seed)
        n, k, dim = 180, 4, 50
        sp = SparseFeatures(
            rng.integers(0, dim, size=(n, k)).astype(np.int32),
            rng.normal(size=(n, k)).astype(np.float32),
            dim,
        )
        y = (rng.uniform(size=n) > 0.5).astype(np.float32)
        return GameDataset.build({"s": sp}, y)

    def _fit(self, ds):
        cfg = CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=20, tolerance=1e-8),
            regularization=L2,
            reg_weight=1.0,
        )
        coord = FixedEffectCoordinate(
            ds, "s", cfg, TaskType.LOGISTIC_REGRESSION
        )
        return run_coordinate_descent({"s": coord}, 2, seed=6)

    @pytest.mark.chaos
    def test_upload_and_solve_faults_bitwise_parity(self, monkeypatch):
        monkeypatch.setenv("PHOTON_RETRY_BASE_DELAY_S", "0.001")
        clean = self._fit(self._sparse_dataset())
        with faults.inject("upload:1,solve@1") as inj:
            faulted = self._fit(self._sparse_dataset())
        assert inj.injected == {"upload": 1, "solve": 1}
        assert faulted.diverged_steps == 1
        assert faults.counters()["retries"] >= 1
        _assert_bitwise_equal(_model_arrays(clean), _model_arrays(faulted))


# ----------------------------------------------------------- ingest faults


def _native_available():
    try:
        from photon_ml_tpu.native.build import load_native

        return load_native() is not None
    except Exception:
        return False


@pytest.mark.skipif(
    not _native_available(), reason="native avro decoder unavailable"
)
class TestDecodeFaults:
    def _write(self, tmp_path, seed=0):
        from photon_ml_tpu.native.avro_writer import (
            write_training_examples_columnar,
        )

        rng = np.random.default_rng(seed)
        n, k, dim = 300, 3, 20
        path = os.path.join(str(tmp_path), "train.avro")
        write_training_examples_columnar(
            path,
            (rng.uniform(size=n) > 0.5).astype(np.float64),
            np.arange(n + 1, dtype=np.int64) * k,
            rng.integers(0, dim, size=n * k).astype(np.int32),
            rng.normal(size=n * k),
            [f"f{i}" for i in range(dim)],
            tag_key="entityId",
            tag_values=rng.integers(0, 9, size=n).astype(str),
        )
        return path

    def _read(self, path):
        import photon_ml_tpu.io.avro_data as ad

        ds, _ = ad.read_game_dataset(
            path,
            {"g": ad.FeatureShardConfig(("features",), True)},
            id_tag_fields=["entityId"],
        )
        return ds

    def _dense(self, ds):
        """Row-order-insensitive shard content: the native and Python
        codecs may order within-row ELL entries differently; the dense
        matrix is the semantic payload."""
        sp = ds.peek_shard("g")
        idx, val = np.asarray(sp.indices), np.asarray(sp.values)
        out = np.zeros((idx.shape[0], sp.dim), np.float32)
        np.add.at(out, (np.arange(idx.shape[0])[:, None], idx), val)
        return out

    @pytest.mark.chaos
    def test_transient_decode_fault_retried_to_parity(
        self, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("PHOTON_RETRY_BASE_DELAY_S", "0.001")
        path = self._write(tmp_path)
        clean = self._read(path)
        with faults.inject("decode:1"):
            faulted = self._read(path)
        assert faults.counters()["retries"] == 1
        np.testing.assert_array_equal(
            np.asarray(clean.labels), np.asarray(faulted.labels)
        )
        np.testing.assert_array_equal(self._dense(clean), self._dense(faulted))

    @pytest.mark.chaos
    def test_exhausted_decode_degrades_to_python_codec(
        self, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("PHOTON_RETRY_BASE_DELAY_S", "0.001")
        path = self._write(tmp_path)
        clean = self._read(path)
        with faults.inject("decode:99"):  # never native
            degraded = self._read(path)
        np.testing.assert_array_equal(
            np.asarray(clean.labels), np.asarray(degraded.labels)
        )
        np.testing.assert_array_equal(self._dense(clean), self._dense(degraded))


# ------------------------------------------------------------- kill-resume


_CHILD_SCRIPT = """
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {repo!r})
import time

from tests.test_faults import _chaos_coords, _chaos_dataset
from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent


class _Stall:
    # Slows each solve so the parent can SIGKILL mid-run; timing-only,
    # the math is untouched.
    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def train(self, *args, **kwargs):
        out = self.inner.train(*args, **kwargs)
        time.sleep(0.5)
        return out


ds = _chaos_dataset()
coords = {{cid: _Stall(c) for cid, c in _chaos_coords(ds).items()}}
run_coordinate_descent(coords, 3, seed=11, checkpoint_dir=sys.argv[1])
print("CHILD_DONE", flush=True)
"""


@pytest.mark.slow
@pytest.mark.chaos
class TestKillResume:
    def test_sigkill_mid_step_resume_bitwise_parity(self, tmp_path):
        ck = str(tmp_path / "ck")
        script = tmp_path / "child.py"
        script.write_text(_CHILD_SCRIPT.format(repo=REPO))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, str(script), ck],
            cwd=REPO,
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
        )
        try:
            # Kill -9 as soon as at least one step has durably committed
            # (state.json is replaced atomically, so a parse race just
            # means "poll again").
            state_path = os.path.join(ck, "state.json")
            deadline = time.monotonic() + 180
            killed = False
            while time.monotonic() < deadline and proc.poll() is None:
                try:
                    if json.load(open(state_path))["completed_steps"] >= 2:
                        proc.send_signal(signal.SIGKILL)
                        killed = True
                        break
                except (OSError, ValueError, KeyError):
                    pass
                time.sleep(0.02)
            proc.wait(timeout=120)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if killed:
            assert proc.returncode == -signal.SIGKILL
        assert os.path.isfile(state_path), "no step committed before timeout"

        ds = _chaos_dataset()
        straight = run_coordinate_descent(_chaos_coords(ds), 3, seed=11)
        resumed = run_coordinate_descent(
            _chaos_coords(ds), 3, seed=11, checkpoint_dir=ck
        )
        _assert_bitwise_equal(_model_arrays(straight), _model_arrays(resumed))


# ----------------------------------------- sharded kill-resume (ISSUE 10)


_SHARDED_CHILD_SCRIPT = """
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Device count is injected by the parent via XLA_FLAGS
# (--xla_force_host_platform_device_count): the SAME checkpoint resumes
# on 1, 2, and 8 virtual devices.
sys.path.insert(0, {repo!r})
import time
import numpy as np

from tests.test_mesh_faults import N_ENTITIES, _coords
from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent

ck = sys.argv[1]
mode = sys.argv[2]  # "train" (stalled, parent SIGKILLs mid-sweep) | "resume"


class _Stall:
    # Slows each sweep so the parent can SIGKILL mid-run; timing-only.
    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def train(self, *args, **kwargs):
        out = self.inner.train(*args, **kwargs)
        time.sleep(0.5)
        return out


coords = _coords(True)  # entity-sharded over however many devices exist
if mode == "train":
    coords = {{cid: _Stall(c) for cid, c in coords.items()}}
res = run_coordinate_descent(coords, 3, seed=11, checkpoint_dir=ck)
if mode == "resume":
    m = np.asarray(res.model.models["re"].coefficients_matrix)
    np.save(sys.argv[3], m[: N_ENTITIES + 1])
print("CHILD_DONE", flush=True)
"""


@pytest.mark.slow
@pytest.mark.chaos
class TestShardedKillResume:
    """The elastic-resume acceptance contract (ISSUE 10): SIGKILL an
    entity-sharded fit mid-sweep on the 8-virtual-device mesh, then resume
    its N-shard checkpoint on 1, 2, and 8 devices — every resumed run must
    land bitwise on the uninterrupted single-device fit."""

    def _env(self, ndev):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={ndev}"
        )
        return env

    def test_sigkill_mid_sweep_resumes_on_1_2_8_devices(self, tmp_path):
        from tests.test_mesh_faults import _coords as _mesh_coords, _matrix

        ck = str(tmp_path / "ck")
        script = tmp_path / "child.py"
        script.write_text(_SHARDED_CHILD_SCRIPT.format(repo=REPO))
        proc = subprocess.Popen(
            [sys.executable, str(script), ck, "train"],
            cwd=REPO,
            env=self._env(8),
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
        )
        try:
            state_path = os.path.join(ck, "state.json")
            deadline = time.monotonic() + 180
            killed = False
            while time.monotonic() < deadline and proc.poll() is None:
                try:
                    if json.load(open(state_path))["completed_steps"] >= 1:
                        proc.send_signal(signal.SIGKILL)
                        killed = True
                        break
                except (OSError, ValueError, KeyError):
                    pass
                time.sleep(0.02)
            proc.wait(timeout=120)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if killed:
            assert proc.returncode == -signal.SIGKILL
        assert os.path.isfile(state_path), "no step committed before timeout"
        # The interrupted checkpoint's sharded layout really landed (a
        # mid-fit state.json references per-shard files + checksums).
        state = json.load(open(state_path))
        rels = state["model_files"]["re"]
        assert isinstance(rels, list) and len(rels) == 8

        # Uninterrupted SINGLE-DEVICE reference (in-process, replicated —
        # bitwise-equal to the sharded fit per test_mesh_faults).
        straight = _matrix(
            run_coordinate_descent(_mesh_coords(False), 3, seed=11)
        )
        for ndev in (1, 2, 8):
            out = tmp_path / f"resume{ndev}.npy"
            r = subprocess.run(
                [sys.executable, str(script), ck, "resume", str(out)],
                cwd=REPO,
                env=self._env(ndev),
                capture_output=True,
                text=True,
                timeout=600,
            )
            assert "CHILD_DONE" in r.stdout, (
                f"resume on {ndev} device(s) failed: {r.stderr[-2000:]}"
            )
            resumed = np.load(out)
            np.testing.assert_array_equal(
                straight,
                resumed,
                err_msg=f"resume on {ndev} device(s) diverged bitwise",
            )


# -------------------------------------------- producer-thread degradation


class TestProducerFallbacks:
    def test_failed_background_pack_falls_back_to_sync(self, monkeypatch):
        from photon_ml_tpu.data.game_dataset import HostCSR
        from photon_ml_tpu.ops import pallas_sparse

        monkeypatch.setattr(
            pallas_sparse, "pack_worth_considering", lambda n: True
        )
        monkeypatch.setenv("PHOTON_HOST_THREADS", "4")
        rng = np.random.default_rng(5)
        n, k, dim = 64, 4, 32
        csr = HostCSR(
            np.arange(n + 1, dtype=np.int64) * k,
            rng.integers(0, dim, size=n * k).astype(np.int64),
            rng.normal(size=n * k).astype(np.float32),
            dim,
        )
        with faults.inject("pack:1"):
            pallas_sparse.begin_pack_async(csr, n)
            assert csr.pack_future is not None
            # finish_pack must absorb the producer failure and repack
            # synchronously (here the sync pack declines on CPU -> None,
            # which is the normal keep-the-ELL-path answer, NOT an error).
            pallas_sparse.finish_pack(csr, n)  # must not raise
        assert faults.counters()["fallback_sync_packs"] == 1
        assert csr.pack_future is None

    def test_failed_re_build_producer_falls_back(self, monkeypatch):
        """A prepare-pool producer whose build dies must not kill fit():
        the estimator rebuilds synchronously and the result is identical."""
        import photon_ml_tpu.estimators.game_estimator as ge
        from photon_ml_tpu.data.game_dataset import FixedEffectDataConfig
        from photon_ml_tpu.estimators.game_estimator import GameEstimator

        monkeypatch.setenv("PHOTON_HOST_THREADS", "4")

        def _make(seed=0):
            rng = np.random.default_rng(seed)
            n, d, ents = 160, 4, 4
            X = rng.normal(size=(n, d)).astype(np.float32)
            users = rng.permutation(np.repeat(np.arange(ents), n // ents))
            movies = rng.permutation(np.repeat(np.arange(ents), n // ents))
            y = (rng.uniform(size=n) > 0.5).astype(np.float32)
            return GameDataset.build(
                {"g": jnp.asarray(X)},
                y,
                id_tags={"userId": users, "movieId": movies},
            )

        data_cfgs = {
            "global": FixedEffectDataConfig("g"),
            "per-user": RandomEffectDataConfig("userId", "g", min_bucket=8),
            "per-movie": RandomEffectDataConfig("movieId", "g", min_bucket=8),
        }
        opt = {
            cid: CoordinateOptimizationConfig(
                optimizer=OptimizerConfig(max_iterations=10, tolerance=1e-7),
                regularization=L2,
                reg_weight=1.0,
            )
            for cid in data_cfgs
        }

        def _fit():
            est = GameEstimator(
                TaskType.LOGISTIC_REGRESSION,
                dict(data_cfgs),
                coordinate_descent_iterations=1,
                pipeline=True,
            )
            return est.fit(_make(), None, [opt])[0].model

        clean = _fit()

        real_build = ge.build_random_effect_dataset
        calls = {"n": 0}

        def _flaky_build(dataset, cfg):
            calls["n"] += 1
            if calls["n"] == 1:
                raise OSError("producer thread blew up")
            return real_build(dataset, cfg)

        monkeypatch.setattr(ge, "build_random_effect_dataset", _flaky_build)
        degraded = _fit()
        assert faults.counters()["fallback_sync_builds"] == 1

        out_c, out_d = {}, {}
        for cid in clean.models:
            mc, md = clean.models[cid], degraded.models[cid]
            a = getattr(mc, "coefficients_matrix", None)
            if a is not None:
                out_c[cid], out_d[cid] = np.asarray(a), np.asarray(
                    md.coefficients_matrix
                )
            else:
                out_c[cid] = np.asarray(mc.coefficients.means)
                out_d[cid] = np.asarray(md.coefficients.means)
        _assert_bitwise_equal(out_c, out_d)


# --------------------------------------------------------------- validators


class TestValidatorAggregation:
    def test_all_failed_checks_reported_in_one_error(self):
        from photon_ml_tpu.data.validators import (
            DataValidationError,
            validate_game_dataset,
        )
        from photon_ml_tpu.types import DataValidationType

        ds = GameDataset.build(
            {"s": jnp.asarray([[1.0], [np.nan], [2.0], [3.0]])},
            [1.0, 3.0, np.nan, 0.0],
            weights=[1.0, -1.0, 0.0, 1.0],
            offsets=[0.0, np.inf, 0.0, 0.0],
        )
        with pytest.raises(DataValidationError) as exc:
            validate_game_dataset(
                ds,
                TaskType.LOGISTIC_REGRESSION,
                DataValidationType.VALIDATE_FULL,
            )
        err = exc.value
        names = [f[0] for f in err.failures]
        # Every failed check present at once — not just the first.
        assert "finite label" in names
        assert "finite offset" in names
        assert "positive weight" in names
        assert "binary label" in names
        assert any("finite features" in n for n in names)
        assert err.rows_checked == 4
        # Counts + example indices per check.
        by_name = {f[0]: f for f in err.failures}
        assert by_name["positive weight"][1] == 2
        assert by_name["positive weight"][2] == [1, 2]
        msg = str(err)
        assert "failed check(s) over 4 rows" in msg
        assert "50.0%" in msg  # positive-weight fraction

    def test_max_examples_truncates_indices(self):
        from photon_ml_tpu.data.validators import (
            DataValidationError,
            validate_game_dataset,
        )
        from photon_ml_tpu.types import DataValidationType

        n = 40
        ds = GameDataset.build(
            {"s": jnp.ones((n, 1))},
            np.ones(n, np.float32),
            weights=np.full(n, -1.0, np.float32),
        )
        with pytest.raises(DataValidationError) as exc:
            validate_game_dataset(
                ds,
                TaskType.LOGISTIC_REGRESSION,
                DataValidationType.VALIDATE_FULL,
                max_examples=3,
            )
        (_, count, examples) = [
            f for f in exc.value.failures if f[0] == "positive weight"
        ][0]
        assert count == n
        assert examples == [0, 1, 2]
