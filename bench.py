"""Benchmark: GLMix training + the framework's main code paths, honestly.

The reference publishes no benchmark numbers (BASELINE.md: no benchmarks/
dir). The protocol here is therefore measured, not estimated:

Primary metric (stable across rounds): samples/s through ONE full
coordinate-descent pass of a synthetic GLMix logistic problem —
1,048,576 samples x 512 dense fixed-effect features + 8,192 entities x 16
random-effect features (vmapped entity solves).

`vs_baseline` is MEASURED on this host: the reference's hot loop is the
per-datum ValueAndGradientAggregator accumulation reduced by treeAggregate
(ValueAndGradientAggregator.scala:137-161, 248-252), whose single-process
equivalent is a float64 BLAS value+gradient pass (Breeze delegates to
netlib). The surrogate runs that pass in numpy float64 on a measured slice
of the same problem, scales linearly in rows (the pass is O(n*d)), and
multiplies by the same number of objective evaluations the accelerator run
executed. `baseline_basis` documents this; no constant is invented.

Per-variant diagnostics (the keys the r01 bench could not show):
  * iterations / fn_evals actually executed (from the optimizer carry),
  * kernel_engaged: whether the fused Pallas objective ran (and in which
    dispatch mode),
  * bytes_streamed + achieved GB/s: fn_evals x bytes-per-pass, where a pass
    is one X read for the fused kernel and two (matvec + rmatvec) for the
    XLA path.

Variants: dense LBFGS, dense TRON (Hessian-vector path), sparse-ELL LBFGS,
and scoring throughput — the four main compute paths.

Prints exactly one JSON line. Runs the measurement in a subprocess with a
time limit; a child that fails or times out makes this process exit non-zero
with the child's stderr — there is no second try on another backend.
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import time

_CHILD = "--run-child"
_MULTICHIP_CHILD = "--run-multichip"
_CHAOS_MULTICHIP_CHILD = "--run-chaos-multichip"
_ELASTIC_MESH_CHILD = "--run-elastic-mesh"
_MULTI_TENANT_CHILD = "--run-multi-tenant"
_CONTINUOUS_LOOP_CHILD = "--run-continuous-loop"
_MULTIHOST_CHAOS_CHILD = "--run-multihost-chaos"
_SHADOW_DEPLOY_CHILD = "--run-shadow-deploy"
_SHADOW_PROMOTE_WORKER = "--run-shadow-promote-worker"
_AUTOPILOT_CHILD = "--run-autopilot"

# Any achieved-bandwidth figure above the chip's HBM peak
# (telemetry.HBM_PEAK_GB_S, keyed by the device_kind the chip reports) is a
# measurement artifact (overhead subtraction, cache effects, or
# work-normalized bytes exceeding physical bytes) and MUST say so in the
# artifact — an impossible number shipping uncommented undermines the
# whole protocol.


def _bw_metrics(nbytes: int, wall: float, platform: str) -> dict:
    """Bandwidth fields with the roofline sanity annotation applied."""
    gbs = nbytes / wall / 1e9
    out = {"bytes_streamed": nbytes, "achieved_gb_per_s": round(gbs, 1)}
    import jax

    from photon_ml_tpu.utils import telemetry

    roof = telemetry.hbm_peak_gb_s(platform, jax.devices()[0].device_kind)
    if roof is not None:
        out["hbm_roofline_gb_per_s"] = roof
        if gbs > roof:
            out["exceeds_hbm_roofline"] = True
    return out


def _dispatch_json(mode):
    """Kernel dispatch decision as machine-comparable JSON (satellite fix:
    r05 serialized repr() strings, so dense_lbfgs carried "dispatch":
    "True" — a string — and the trajectory tooling could not compare it).
    True/False/None stay JSON booleans/null; a ShardedDispatch becomes an
    object naming the mesh axis and device count."""
    if mode is None or isinstance(mode, bool):
        return mode
    out = {"sharded": True}
    axis = getattr(mode, "axis", None)
    if axis is not None:
        out["axis"] = str(axis)
    mesh = getattr(mode, "mesh", None)
    if mesh is not None:
        try:
            out["devices"] = int(mesh.devices.size)
        except Exception:  # noqa: BLE001 - annotation only
            pass
    return out


def _measure_baseline_surrogate(n: int, d: int, fn_evals: int) -> dict:
    """Measured single-process float64 BLAS value+gradient passes — the
    reference's per-partition hot loop without Spark overhead (a strict
    lower bound on the reference's wall-clock for the same work)."""
    import numpy as np

    slice_n = min(n, 131072)
    rng = np.random.default_rng(0)
    X = rng.normal(size=(slice_n, d))  # float64, as Breeze
    y = (rng.uniform(size=slice_n) > 0.5).astype(np.float64)
    w = rng.normal(size=d) * 0.1

    def vg_pass():
        z = X @ w
        val = np.sum(np.log1p(np.exp(-np.abs(z))) + np.maximum(z, 0) - y * z)
        u = 1.0 / (1.0 + np.exp(-z)) - y
        g = u @ X
        return val, g

    vg_pass()  # warm BLAS
    # Best-of-reps: the surrogate shares the host with whatever else runs
    # (test suites, data loaders); min is the uncontended estimate.
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        vg_pass()
        times.append(time.perf_counter() - t0)
    per_pass = min(times)
    est_wall = per_pass * (n / slice_n) * fn_evals
    return {
        "surrogate_slice_rows": slice_n,
        "surrogate_pass_s": round(per_pass, 4),
        "estimated_wall_s": round(est_wall, 3),
    }


def _solve_stats(res) -> dict:
    import numpy as np

    return {
        "iterations": int(np.asarray(res.iterations)),
        "fn_evals": int(np.asarray(res.fn_evals)),
        "converged_reason": int(np.asarray(res.reason)),
    }


def _multichip_child() -> None:
    """Entity-sharded pod-scale measurement on an 8-virtual-device mesh.

    Launched as its own subprocess (JAX_PLATFORMS=cpu +
    xla_force_host_platform_device_count=8 — the same virtual mesh the
    test suite and MULTICHIP dryrun use) because the parent bench child
    has already initialized its backend. The certificate: a random-effect
    coefficient matrix DELIBERATELY sized past one virtual device's HBM
    budget trains through the sharded scan sweep and serves through the
    sharded bundle, with per-batch wall + analytic collective bytes
    reported, per-shard residency measured under the budget, and — on an
    overlap problem that fits one device — sharded serving bitwise-equal
    to the single-device path (training parity to f32 reduction order).
    Prints exactly one JSON line."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.game_dataset import (
        GameDataset,
        RandomEffectDataConfig,
        build_random_effect_dataset,
    )
    from photon_ml_tpu.game.coordinate import RandomEffectCoordinate
    from photon_ml_tpu.game.model import (
        Coefficients,
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_ml_tpu.optimize.config import (
        L2,
        CoordinateOptimizationConfig,
        OptimizerConfig,
    )
    from photon_ml_tpu.parallel.mesh import (
        make_mesh,
        pad_game_dataset,
        shard_game_dataset,
        shard_random_effect_dataset,
    )
    from photon_ml_tpu.serving import ScoreRequest, ServingBundle, ServingEngine
    from photon_ml_tpu.transformers.game_transformer import CoordinateScoringSpec
    from photon_ml_tpu.types import TaskType

    task = TaskType.LOGISTIC_REGRESSION
    mesh = make_mesh()
    ndev = int(mesh.devices.size)
    from photon_ml_tpu.utils.knobs import get_knob

    budget = int(get_knob("PHOTON_BENCH_VDEV_BUDGET"))
    d_re = 8
    # Matrix rows chosen so the full f32 matrix EXCEEDS the per-device
    # budget while one shard stays well under it.
    n_entities = (budget // (d_re * 4)) + 8 * ndev
    rows_per_entity = 2
    n = n_entities * rows_per_entity
    rng = np.random.default_rng(17)

    def build_re_problem(e, rows_each, seed):
        r = np.random.default_rng(seed)
        m = e * rows_each
        Xe = r.normal(size=(m, d_re)).astype(np.float32)
        entity = np.repeat(np.arange(e), rows_each)
        u = r.normal(size=(e, d_re)).astype(np.float32) * 0.5
        margin = np.einsum("nd,nd->n", Xe, u[entity])
        y = (r.uniform(size=m) < 1 / (1 + np.exp(-margin))).astype(np.float32)
        return Xe, entity, y

    Xe, entity, y = build_re_problem(n_entities, rows_per_entity, 29)
    cfg_r = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=3, tolerance=1e-6),
        regularization=L2,
        reg_weight=1.0,
    )
    # max_block_cells bounds each scan step's (E, S) block so the sweep is
    # a multi-step scan (several same-shape buckets -> ONE program).
    re_cfg = RandomEffectDataConfig(
        "entityId", "re", min_bucket=8, max_block_cells=1 << 16
    )
    ds = pad_game_dataset(
        GameDataset.build(
            {"re": jnp.asarray(Xe)}, y, id_tags={"entityId": entity}
        ),
        ndev,
    )
    sharded = shard_game_dataset(ds, mesh)
    red = shard_random_effect_dataset(
        build_random_effect_dataset(sharded, re_cfg), mesh
    )
    coord = RandomEffectCoordinate(sharded, red, cfg_r, task)
    assert coord._entity_mesh is not None, "entity mesh did not engage"
    # Warm-up compile, then the timed sweep (traced reg weight: same
    # programs, perturbed numerics so nothing is result-cached).
    model_big, _ = coord.train(sharded.offsets, reg_weight=1.001)
    jax.block_until_ready(model_big.coefficients_matrix)
    t0 = time.perf_counter()
    model_big, _ = coord.train(sharded.offsets)
    jax.block_until_ready(model_big.coefficients_matrix)
    sweep_wall = time.perf_counter() - t0
    n_buckets = len(red.buckets)
    matrix = model_big.coefficients_matrix
    shard_bytes = [s.data.nbytes for s in matrix.addressable_shards]
    collective = coord.sweep_collective_bytes()

    # ---- serve the over-budget model through the sharded bundle ----------
    d_fe = 16
    w_fe = rng.normal(size=d_fe).astype(np.float32)
    gm = GameModel(
        {
            "fixed": FixedEffectModel(Coefficients(jnp.asarray(w_fe)), task),
            "per-entity": model_big,
        }
    )
    specs = {
        "fixed": CoordinateScoringSpec(shard="g"),
        "per-entity": CoordinateScoringSpec(
            shard="re",
            random_effect_type="entityId",
            entity_index=dict(red.entity_index),
        ),
    }
    n_req = 256
    Xq_fe = rng.normal(size=(n_req, d_fe)).astype(np.float32)
    Xq_re = rng.normal(size=(n_req, d_re)).astype(np.float32)
    q_ent = rng.integers(0, n_entities, size=n_req)
    reqs = [
        ScoreRequest(
            features={"g": Xq_fe[i], "re": Xq_re[i]},
            entity_ids={"entityId": int(q_ent[i])},
            uid=str(i),
        )
        for i in range(n_req)
    ]
    bundle = ServingBundle.from_model(gm, specs, task)  # adopts the sharding
    assert bundle.coordinates["per-entity"].mesh is not None
    with ServingEngine(bundle, max_batch=64) as eng:
        eng.warmup()
        scores = np.asarray([r.score for r in eng.score_batch(reqs)])
        serving_sharding = eng.metrics()["sharding"]
    # Reference: THE single-device path — the same model staged as one
    # replicated matrix (the budget is virtual, so a full host copy is
    # computable here) served by its own engine. Exact row movement keeps
    # the sharded answers bitwise-equal to it.
    gm_repl = GameModel(
        {
            "fixed": FixedEffectModel(Coefficients(jnp.asarray(w_fe)), task),
            "per-entity": RandomEffectModel(
                jnp.asarray(np.asarray(matrix)),
                None,
                task,
                n_entities=model_big.num_entities,
            ),
        }
    )
    with ServingEngine(
        ServingBundle.from_model(gm_repl, specs, task), max_batch=64
    ) as eng_repl:
        ref = np.asarray(
            [r.score for r in eng_repl.score_batch(reqs)], np.float64
        )
    big_serve_bitwise = bool(np.array_equal(scores.astype(np.float64), ref))

    # ---- overlap problem (fits one device): parity certificates ----------
    e_small = 64 * ndev
    Xs, ents_s, ys = build_re_problem(e_small, 4, 31)
    ds_small = GameDataset.build(
        {"re": jnp.asarray(Xs)}, ys, id_tags={"entityId": ents_s}
    )
    red_small = build_random_effect_dataset(
        ds_small, RandomEffectDataConfig("entityId", "re", min_bucket=8)
    )
    c_single = RandomEffectCoordinate(ds_small, red_small, cfg_r, task)
    m_single, _ = c_single.train(ds_small.offsets)
    ds_small_sh = shard_game_dataset(pad_game_dataset(
        GameDataset.build(
            {"re": jnp.asarray(Xs)}, ys, id_tags={"entityId": ents_s}
        ),
        ndev,
    ), mesh)
    red_small_sh = shard_random_effect_dataset(
        build_random_effect_dataset(
            ds_small_sh, RandomEffectDataConfig("entityId", "re", min_bucket=8)
        ),
        mesh,
    )
    c_sh = RandomEffectCoordinate(ds_small_sh, red_small_sh, cfg_r, task)
    m_sh, _ = c_sh.train(ds_small_sh.offsets)
    W_a = np.asarray(m_single.coefficients_matrix)
    W_b = np.asarray(m_sh.coefficients_matrix)
    rows_a = [red_small.entity_index[e] for e in red_small.entity_index]
    rows_b = [red_small_sh.entity_index[e] for e in red_small.entity_index]
    dw = np.abs(W_a[rows_a] - W_b[rows_b]).max()
    scale_w = np.abs(W_a).max() + 1e-12
    overlap_rel_dw = float(dw / scale_w)

    # Serving the SAME single-device-trained model replicated vs
    # mesh-staged vs two-tier must be BITWISE identical (exact row
    # movement — the tentpole's parity discipline).
    gm_small = GameModel({"per-entity": m_single})
    specs_small = {
        "per-entity": CoordinateScoringSpec(
            shard="re",
            random_effect_type="entityId",
            entity_index=dict(red_small.entity_index),
        )
    }
    reqs_small = [
        ScoreRequest(
            features={"re": Xs[i]}, entity_ids={"entityId": int(ents_s[i])}
        )
        for i in range(128)
    ]

    def _serve(**kw):
        b = ServingBundle.from_model(gm_small, specs_small, task, **kw)
        try:
            with ServingEngine(b, max_batch=64) as e:
                return np.asarray([r.score for r in e.score_batch(reqs_small)])
        finally:
            # Join the two-tier promotion worker while the runtime is up:
            # a daemon thread dispatching during interpreter teardown
            # aborts the child and loses its buffered JSON line.
            b.release()

    s_repl = _serve()
    s_mesh = _serve(mesh=mesh)
    s_tier = _serve(hot_rows=e_small // 4)
    overlap_serve_sharded_bitwise = bool(np.array_equal(s_repl, s_mesh))
    overlap_serve_two_tier_bitwise = bool(np.array_equal(s_repl, s_tier))

    print(
        json.dumps(
            dict(
                n_devices=ndev,
                budget_bytes_per_device=budget,
                re_rows=int(matrix.shape[0]),
                re_dim=d_re,
                re_matrix_bytes=int(matrix.nbytes),
                max_shard_bytes=int(max(shard_bytes)),
                sweep_wall_s=round(sweep_wall, 3),
                buckets=n_buckets,
                per_batch_wall_ms=round(sweep_wall / max(1, n_buckets) * 1e3, 2),
                collective_bytes_per_sweep=int(collective),
                collective_bytes_per_batch=int(collective // max(1, n_buckets)),
                sharding=coord.sharding_info(),
                serving_sharding=serving_sharding,
                serve_bitwise_vs_replicated=big_serve_bitwise,
                overlap_train_max_rel_dw=overlap_rel_dw,
                overlap_serve_sharded_bitwise=overlap_serve_sharded_bitwise,
                overlap_serve_two_tier_bitwise=overlap_serve_two_tier_bitwise,
            )
        )
    )


def _chaos_multichip_child() -> None:
    """Pod-scale chaos certificate (ISSUE 10): an 8-virtual-device mesh
    with EVERY mesh fault site armed (PHOTON_FAULTS from the parent:
    collective/shard_upload/promote/resume_load, plus the hang watchdog)
    must degrade or retry without failing a fit or a request, and recover
    to bitwise serve parity. Phases:

      1. CLEAN: entity-sharded fit + replicated serve reference (faults
         explicitly disarmed with an empty installed plan).
      2. CHAOS FIT: same fit under the armed plan with a sharded
         checkpoint — the collective re-dispatch must land bitwise.
      3. CHAOS RESUME: re-run against the checkpoint — resume_load fires
         on the first shard read, retries, fast-forwards bitwise.
      4. CHAOS SERVE: sharded bundle (shard_upload fires at staging) and
         two-tier bundle (promote fires at the first promotion) answer a
         replay through the micro-batcher — zero failed, zero hangs,
         bitwise vs the clean reference.
      5. SHARD LOSS DRILL: mark one shard lost (exactly its entities go
         bitwise FE-only), restage ONLY that shard, recover bitwise.

    Prints exactly one JSON line."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.game_dataset import (
        GameDataset,
        RandomEffectDataConfig,
        build_random_effect_dataset,
    )
    from photon_ml_tpu.game.coordinate import RandomEffectCoordinate
    from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent
    from photon_ml_tpu.game.model import (
        Coefficients,
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_ml_tpu.optimize.config import (
        L2,
        CoordinateOptimizationConfig,
        OptimizerConfig,
    )
    from photon_ml_tpu.parallel.mesh import (
        make_mesh,
        pad_game_dataset,
        shard_game_dataset,
        shard_random_effect_dataset,
    )
    from photon_ml_tpu.serving import (
        ScoreRequest,
        ServingBundle,
        ServingEngine,
    )
    from photon_ml_tpu.transformers.game_transformer import (
        CoordinateScoringSpec,
    )
    from photon_ml_tpu.types import TaskType
    from photon_ml_tpu.utils import faults
    from photon_ml_tpu.utils.knobs import get_knob

    task = TaskType.LOGISTIC_REGRESSION
    mesh = make_mesh()
    ndev = int(mesh.devices.size)
    armed_spec = str(get_knob("PHOTON_FAULTS")).strip()
    import tempfile

    e, rows_each, d_re = 16 * ndev, 4, 8
    n = e * rows_each  # divisible by ndev: elastic resume fingerprints match
    rng = np.random.default_rng(41)
    Xe = rng.normal(size=(n, d_re)).astype(np.float32)
    ent = np.repeat(np.arange(e), rows_each)
    y = (rng.uniform(size=n) > 0.5).astype(np.float32)
    cfg = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=6, tolerance=1e-7),
        regularization=L2,
        reg_weight=1.0,
    )
    re_cfg = RandomEffectDataConfig("entityId", "re", min_bucket=8)

    def coords(sharded: bool):
        ds = GameDataset.build(
            {"re": jnp.asarray(Xe)}, y, id_tags={"entityId": ent}
        )
        if sharded:
            ds = shard_game_dataset(pad_game_dataset(ds, ndev), mesh)
            red = shard_random_effect_dataset(
                build_random_effect_dataset(ds, re_cfg), mesh
            )
        else:
            red = build_random_effect_dataset(ds, re_cfg)
        return {"re": RandomEffectCoordinate(ds, red, cfg, task)}, red

    def logical(result):
        m = np.asarray(result.model.models["re"].coefficients_matrix)
        return m[: e + 1]

    # ---- phase 1: CLEAN references (faults disarmed) ----------------------
    faults.install("")  # empty plan: nothing armed, env plan masked
    c, red_clean = coords(True)
    clean = logical(run_coordinate_descent(c, 2, seed=13))
    d_fe = 8
    w_fe = rng.normal(size=d_fe).astype(np.float32)
    entity_index = dict(red_clean.entity_index)
    specs = {
        "fixed": CoordinateScoringSpec(shard="g"),
        "per-entity": CoordinateScoringSpec(
            shard="re",
            random_effect_type="entityId",
            entity_index=entity_index,
        ),
    }

    def game_model(matrix):
        return GameModel(
            {
                "fixed": FixedEffectModel(
                    Coefficients(jnp.asarray(w_fe)), task
                ),
                "per-entity": RandomEffectModel(
                    jnp.asarray(matrix), None, task
                ),
            }
        )

    n_req = 128
    Xq_fe = rng.normal(size=(n_req, d_fe)).astype(np.float32)
    Xq_re = rng.normal(size=(n_req, d_re)).astype(np.float32)
    q_ent = rng.integers(0, e, size=n_req)
    reqs = [
        ScoreRequest(
            features={"g": Xq_fe[i], "re": Xq_re[i]},
            entity_ids={"entityId": int(q_ent[i])},
            uid=str(i),
        )
        for i in range(n_req)
    ]
    gm_clean = game_model(clean)
    with ServingEngine(
        ServingBundle.from_model(gm_clean, specs, task), max_batch=32
    ) as eng_ref:
        ref_scores = np.asarray(
            [r.score for r in eng_ref.score_batch(reqs)], np.float64
        )
        ref_fe = np.asarray(
            [r.score for r in eng_ref.score_batch_fe_only(reqs)], np.float64
        )

    # ---- phases 2-5: CHAOS (the env plan re-arms on clear) ----------------
    faults.reset_counters()
    faults.clear()
    with tempfile.TemporaryDirectory() as td:
        ck = os.path.join(td, "ck")
        c, _ = coords(True)
        chaos = logical(
            run_coordinate_descent(c, 2, seed=13, checkpoint_dir=ck)
        )
        train_bitwise = bool(np.array_equal(clean, chaos))
        c, _ = coords(True)
        resumed = logical(
            run_coordinate_descent(c, 2, seed=13, checkpoint_dir=ck)
        )
        resume_bitwise = bool(np.array_equal(chaos, resumed))

    failed_requests = 0
    hangs = 0

    from concurrent.futures import TimeoutError as _FutTimeout

    def replay(engine):
        nonlocal failed_requests, hangs
        out = [None] * n_req
        with engine.batcher(max_wait_ms=1.0) as b:  # photon-lint: disable=planner-constant — deliberate section config: fixed wait pins the measurement, not a runtime default
            futs = [b.submit(r, block=True) for r in reqs]
            for i, f in enumerate(futs):
                try:
                    out[i] = f.result(timeout=60)
                except (_FutTimeout, TimeoutError):
                    # Both: concurrent.futures.TimeoutError is NOT the
                    # builtin TimeoutError on 3.10 — catching only the
                    # builtin would count a hang as a request failure.
                    hangs += 1
                except Exception:  # noqa: BLE001 - counted, contract-fatal
                    failed_requests += 1
        return np.asarray(
            [np.nan if r is None else r.score for r in out], np.float64
        )

    gm_chaos = game_model(chaos)
    # Sharded bundle: shard_upload fires at staging (retried), then the
    # shard-loss drill exercises degradation + targeted recovery.
    bundle_sh = ServingBundle.from_model(gm_chaos, specs, task, mesh=mesh)
    restaged_bytes = 0
    with ServingEngine(bundle_sh, max_batch=32) as eng_sh:
        eng_sh.warmup()
        got_sh = replay(eng_sh)
        serve_bitwise = bool(np.array_equal(got_sh, ref_scores))
        lo, hi = eng_sh.mark_shard_lost("per-entity", 0)
        got_lost = replay(eng_sh)
        rows, _ = bundle_sh.coordinates["per-entity"].lookup_rows(
            [int(i) for i in q_ent]
        )
        lost_mask = (rows >= lo) & (rows < hi)
        expected = np.where(lost_mask, ref_fe, ref_scores)
        shard_loss_bitwise = bool(np.array_equal(got_lost, expected))
        restaged_bytes = eng_sh.restage_shard("per-entity", 0)
        got_rec = replay(eng_sh)
        recovery_bitwise = bool(np.array_equal(got_rec, ref_scores))
        loss_fallbacks = eng_sh.metrics()["sharding"][
            "shard_loss_fallbacks"
        ]
    # Two-tier bundle: promote fires at the first promotion batch (rows
    # stay cold, answers stay bitwise).
    bundle_tt = ServingBundle.from_model(
        gm_chaos, specs, task, hot_rows=e // 4
    )
    try:
        with ServingEngine(bundle_tt, max_batch=32) as eng_tt:
            eng_tt.warmup()
            got_tt = replay(eng_tt)
            bundle_tt.coordinates["per-entity"].store.drain()
            got_tt2 = replay(eng_tt)
            serve_bitwise = serve_bitwise and bool(
                np.array_equal(got_tt, ref_scores)
            ) and bool(np.array_equal(got_tt2, ref_scores))
    finally:
        bundle_tt.release()

    counters = faults.counters()
    print(
        json.dumps(
            dict(
                n_devices=ndev,
                faults_armed=armed_spec,
                injected_faults=int(counters.get("injected_faults", 0)),
                collective_retries=int(
                    counters.get("collective_retries", 0)
                ),
                shard_upload_retries=int(
                    counters.get("shard_upload_retries", 0)
                ),
                promote_failures=int(counters.get("promote_failures", 0)),
                watchdog_trips=int(counters.get("watchdog_trips", 0)),
                failed_requests=int(failed_requests),
                hangs=int(hangs),
                train_bitwise_vs_clean=train_bitwise,
                resume_bitwise_vs_train=resume_bitwise,
                serve_bitwise_vs_clean=serve_bitwise,
                shard_loss_fe_only_bitwise=shard_loss_bitwise,
                post_recovery_bitwise=recovery_bitwise,
                shard_loss_fallbacks=int(loss_fallbacks),
                restaged_bytes=int(restaged_bytes),
            )
        )
    )


def _elastic_mesh_child() -> None:
    """Live mesh-elasticity certificate (ISSUE 13) on an 8-virtual-device
    mesh. Phases:

      1. COLD REFERENCES: an engine cold-started at 8 shards and one at 4
         must already agree bitwise (the PR 7 foundation).
      2. LIVE SHRINK + REGROW: a closed-loop client scores continuously
         through the micro-batcher while the engine reshards 8 -> 4 and
         back 4 -> 8 — zero failed requests, every answer bitwise, and
         post-reshard probes bitwise-equal to the cold start at that
         shape. This phase is CLEAN: every reshard/mesh-loss robustness
         counter must read zero afterwards.
      3. HOT-ROW REBALANCE: a two-tier bundle replays a hot-tailed stream
         (cold-tier hits + promotions accrue), the observed promotion
         stats drive a rebalance through the same orchestrator, and the
         replayed stream afterwards pays ZERO cold-tier hits — bitwise
         throughout.
      4. MID-FIT SHRINK DRILL: a mesh_loss injected into sweep 2 of an
         entity-sharded fit re-forms onto 4 devices and resumes — bitwise
         equal to the uninterrupted fit, exactly one repeated sweep.

    Prints exactly one JSON line."""
    import threading as _threading

    import numpy as np
    import jax.numpy as jnp

    from photon_ml_tpu.data.game_dataset import (
        GameDataset,
        RandomEffectDataConfig,
        build_random_effect_dataset,
    )
    from photon_ml_tpu.game.coordinate import RandomEffectCoordinate
    from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent
    from photon_ml_tpu.game.model import (
        Coefficients,
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_ml_tpu.optimize.config import (
        L2,
        CoordinateOptimizationConfig,
        OptimizerConfig,
    )
    from photon_ml_tpu.parallel.mesh import (
        make_mesh,
        pad_game_dataset,
        shard_game_dataset,
        shard_random_effect_dataset,
        surviving_mesh,
    )
    from photon_ml_tpu.serving import (
        ScoreRequest,
        ServingBundle,
        ServingEngine,
        plan_reshard,
    )
    from photon_ml_tpu.transformers.game_transformer import (
        CoordinateScoringSpec,
    )
    from photon_ml_tpu.types import TaskType
    from photon_ml_tpu.utils import faults
    from photon_ml_tpu.utils.contracts import ROBUSTNESS_CLEAN_ZERO_KEYS

    task = TaskType.LOGISTIC_REGRESSION
    mesh8 = make_mesh()
    ndev = int(mesh8.devices.size)
    shrink_to = max(1, ndev // 2)
    mesh_small = surviving_mesh(shrink_to)
    faults.install("")  # nothing armed until the mid-fit drill
    faults.reset_counters()

    # ---- serving model + request stream -----------------------------------
    e_srv, d_fe, d_re = 24 * ndev, 16, 8
    rng = np.random.default_rng(53)
    w_fe = rng.normal(size=d_fe).astype(np.float32)
    M = np.zeros((e_srv + 1, d_re), np.float32)
    M[:e_srv] = rng.normal(size=(e_srv, d_re)).astype(np.float32) * 0.3
    model = GameModel(
        {
            "fixed": FixedEffectModel(Coefficients(jnp.asarray(w_fe)), task),
            "per-entity": RandomEffectModel(jnp.asarray(M), None, task),
        }
    )
    specs = {
        "fixed": CoordinateScoringSpec(shard="g"),
        "per-entity": CoordinateScoringSpec(
            shard="re",
            random_effect_type="entityId",
            entity_index={str(i): i for i in range(e_srv)},
        ),
    }
    n_req = 256
    Xf = rng.normal(size=(n_req, d_fe)).astype(np.float32)
    Xr = rng.normal(size=(n_req, d_re)).astype(np.float32)
    reqs = [
        ScoreRequest(
            features={"g": Xf[i], "re": Xr[i]},
            entity_ids={"entityId": str(int(v))},
            uid=str(i),
        )
        for i, v in enumerate(rng.integers(0, e_srv, size=n_req))
    ]

    def scores_of(results):
        return np.asarray([r.score for r in results], np.float64)

    # ---- phase 1: cold references at both shapes --------------------------
    with ServingEngine(
        ServingBundle.from_model(model, specs, task), max_batch=64
    ) as eng_ref:
        ref = scores_of(eng_ref.score_batch(reqs))
    with ServingEngine(
        ServingBundle.from_model(model, specs, task, mesh=mesh_small),
        max_batch=64,
    ) as eng_small:
        ref_small = scores_of(eng_small.score_batch(reqs))
    foundation_bitwise = bool(np.array_equal(ref, ref_small))

    # ---- phase 2: live shrink + regrow under replay traffic ---------------
    bundle = ServingBundle.from_model(model, specs, task, mesh=mesh8)
    eng = ServingEngine(bundle, max_batch=64)
    eng.warmup()
    plan = plan_reshard(eng.bundle, mesh_small)
    stop = _threading.Event()
    failed_requests = [0]
    answered = [0]
    answer_marks: list = []

    def _traffic(b):
        j = 0
        while not stop.is_set():
            try:
                res = b.score(reqs[j % n_req])
                if res.score != ref[j % n_req]:
                    failed_requests[0] += 1  # a wrong answer IS a failure
                else:
                    answered[0] += 1
            except Exception:  # noqa: BLE001 - the zero-failed contract
                failed_requests[0] += 1
            j += 1

    with eng, eng.batcher(max_wait_ms=1.0) as batcher:  # photon-lint: disable=planner-constant — deliberate section config: fixed wait pins the measurement, not a runtime default
        th = _threading.Thread(
            target=_traffic, args=(batcher,), name="photon-bench-elastic"
        )
        th.start()
        time.sleep(0.2)
        info_shrink = eng.reshard_orchestrator.reshard(mesh_small)
        answer_marks.append(answered[0])
        time.sleep(0.2)
        shrink_probe = scores_of(eng.score_batch(reqs))
        info_regrow = eng.reshard_orchestrator.reshard(make_mesh())
        answer_marks.append(answered[0])
        time.sleep(0.2)
        stop.set()
        th.join(timeout=60)
        hung = th.is_alive()
        regrow_probe = scores_of(eng.score_batch(reqs))
    shrink_bitwise = bool(np.array_equal(shrink_probe, ref_small))
    regrow_bitwise = bool(np.array_equal(regrow_probe, ref))

    # ---- phase 3: hot-row rebalance from observed promotions --------------
    hot_ids = [str(e_srv - 1 - (i % 8)) for i in range(n_req)]
    hot_reqs = [
        ScoreRequest(
            features={"g": Xf[i], "re": Xr[i]},
            entity_ids={"entityId": hot_ids[i]},
        )
        for i in range(n_req)
    ]
    with ServingEngine(
        ServingBundle.from_model(model, specs, task), max_batch=64
    ) as eng_hr:
        hot_ref = scores_of(eng_hr.score_batch(hot_reqs))
    bundle_tt = ServingBundle.from_model(model, specs, task, hot_rows=16)
    store_tt = bundle_tt.coordinates["per-entity"].store
    eng_tt = ServingEngine(bundle_tt, max_batch=64)
    with eng_tt:
        eng_tt.warmup()
        # Pass 1: the default preload (rows 0..hot-1) misses the hot tail
        # entirely — every hot lookup pays a cold-tier hit AND queues a
        # promotion (the observed-hotness signal the rebalance reads).
        rb_bitwise = bool(
            np.array_equal(scores_of(eng_tt.score_batch(hot_reqs)), hot_ref)
        )
        cold_hits_before = store_tt.cold_hits
        store_tt.drain()  # promotions recorded into promotion_stats
        info_rb = eng_tt.reshard_orchestrator.rebalance(
            "per-entity", min_promotions=1
        )
        # Pass 2 on the rebalanced generation: the observed-hot rows were
        # PRELOADED into the new store's hot tier, so the same stream now
        # pays zero cold-tier hits.
        new_store = eng_tt.bundle.coordinates["per-entity"].store
        cold_mark = new_store.cold_hits
        rb_bitwise = rb_bitwise and bool(
            np.array_equal(scores_of(eng_tt.score_batch(hot_reqs)), hot_ref)
        )
        cold_hits_after = new_store.cold_hits - cold_mark
    eng_tt.bundle.release()

    # Clean contract: phases 1-3 armed nothing, so every elastic (and mesh)
    # robustness counter must be zero BEFORE the injected drill below.
    counters_clean = faults.counters()
    clean_zero = {
        k: int(counters_clean.get(k, 0)) for k in ROBUSTNESS_CLEAN_ZERO_KEYS
    }
    clean_counters_zero = not any(clean_zero.values())

    # ---- phase 4: mid-fit shrink drill ------------------------------------
    e_fit, rows_each, d_fit = 16 * ndev, 4, 8
    n_fit = e_fit * rows_each
    rng_f = np.random.default_rng(67)
    Xe = rng_f.normal(size=(n_fit, d_fit)).astype(np.float32)
    ent = np.repeat(np.arange(e_fit), rows_each)
    y = (rng_f.uniform(size=n_fit) > 0.5).astype(np.float32)
    cfg = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=6, tolerance=1e-7),
        regularization=L2,
        reg_weight=1.0,
    )
    re_cfg = RandomEffectDataConfig("entityId", "re", min_bucket=8)

    def fit_coords(target_mesh):
        ds = GameDataset.build(
            {"re": jnp.asarray(Xe)}, y, id_tags={"entityId": ent}
        )
        if target_mesh is not None:
            ds = shard_game_dataset(
                pad_game_dataset(ds, target_mesh.devices.size), target_mesh
            )
            red = shard_random_effect_dataset(
                build_random_effect_dataset(ds, re_cfg), target_mesh
            )
        else:
            red = build_random_effect_dataset(ds, re_cfg)
        return {"re": RandomEffectCoordinate(ds, red, cfg, task)}

    def logical(result):
        m = np.asarray(result.model.models["re"].coefficients_matrix)
        return m[: e_fit + 1]

    uninterrupted = logical(
        run_coordinate_descent(fit_coords(make_mesh()), 2, seed=29)
    )
    faults.install("mesh_loss@2")  # dies mid-sweep-2, recovers, replays
    try:
        drilled = run_coordinate_descent(
            fit_coords(make_mesh()),
            2,
            seed=29,
            mesh_rebuilder=lambda: fit_coords(mesh_small),
        )
    finally:
        faults.install("")
    midfit_bitwise = bool(np.array_equal(logical(drilled), uninterrupted))

    print(
        json.dumps(
            dict(
                n_devices=ndev,
                shrink_to=shrink_to,
                foundation_bitwise=foundation_bitwise,
                moved_rows_shrink=int(plan.moved_rows),
                moved_bytes_shrink=int(plan.moved_bytes),
                answered_during_shrink=int(answer_marks[0]),
                answered_during_regrow=int(
                    answer_marks[1] - answer_marks[0]
                ),
                answered_total=int(answered[0]),
                failed_requests=int(failed_requests[0]),
                hangs=int(bool(hung)),
                shrink_bitwise_vs_cold=shrink_bitwise,
                regrow_bitwise_vs_cold=regrow_bitwise,
                reshard_stage_s=info_shrink["stage_s"],
                regrow_stage_s=info_regrow["stage_s"],
                rebalanced_rows=int(info_rb["rebalanced_rows"]),
                rebalance_bitwise=rb_bitwise,
                cold_tier_hits_before_rebalance=int(cold_hits_before),
                cold_tier_hits_after_rebalance=int(cold_hits_after),
                midfit_repeated_sweeps=int(drilled.repeated_sweeps),
                midfit_mesh_losses=int(drilled.mesh_losses),
                midfit_bitwise_vs_uninterrupted=midfit_bitwise,
                clean_counters=clean_zero,
                clean_counters_zero=clean_counters_zero,
            )
        )
    )


def _multi_tenant_child() -> None:
    """Multi-tenant serving-platform isolation certificate (ISSUE 15) on
    an 8-virtual-device fleet. Phases:

      1. TEN TENANTS, ONE FLEET: 10 named bundles (one entity-sharded
         over the mesh — the fleet is genuinely shared, and that tenant
         proves the solo-dispatch path rides alongside the co-batched
         one) admit into one TenantRegistry. Solo replicated engines
         cold-started per tenant are the bitwise references.
      2. CHAOS CONFINED TO ONE TENANT: the chaos tenant takes armed
         lookup/score/admit faults (its engine's injection gate), a
         10-microsecond watchdog (every fallback dispatch trips ->
         DeviceHang -> circuit -> FE-only ANSWERS) and a 6x-quota flood
         on a concurrent thread — while nine clean tenants replay
         closed-loop traffic. Contract: every clean tenant answers with
         ZERO failed requests, zero degradations (its LABELED robustness
         sub-counters stay zero), admitted p99 inside its deadline, and
         scores bitwise-equal to serving that tenant alone.
      3. HBM-PRESSURE EVICTION: an 11th tenant admits OVER the fleet
         budget — the coldest tenant demotes to the host tier (never
         fails), the newcomer admits, and the demoted tenant still
         answers bitwise through the TwoTierEntityStore overrides.
      4. PRECISION-LADDER HBM SQUEEZE (ISSUE 20): a second fleet under
         a budget that fits only a handful of f32 tenants; with
         PHOTON_TIER_LADDER opted in, quantize-in-place (f32 -> bf16 ->
         int8) keeps >= 3x as many tenants device-resident, every
         quantized tenant's replay stays within the pinned
         TIER_TOLERANCES, a terminal mid-quantize fault stays confined
         to its tenant with ZERO failed requests across every ladder
         transition, and a restored tenant answers bitwise vs its
         pre-demotion self.

    Prints exactly one JSON line."""
    import threading as _threading

    import numpy as np
    import jax.numpy as jnp

    from photon_ml_tpu.game.model import (
        Coefficients,
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_ml_tpu.parallel.mesh import make_mesh
    from photon_ml_tpu.serving import (
        Overloaded,
        ScoreRequest,
        ServingBundle,
        ServingEngine,
        TenantRegistry,
    )
    from photon_ml_tpu.transformers.game_transformer import (
        CoordinateScoringSpec,
    )
    from photon_ml_tpu.types import TaskType
    from photon_ml_tpu.utils import faults, telemetry

    task = TaskType.LOGISTIC_REGRESSION
    mesh = make_mesh()
    ndev = int(mesh.devices.size)
    d_fe, d_re = 12, 6
    n_clean_each = 24
    deadline_ms = 2000.0
    faults.install("")  # nothing armed until the chaos phase
    faults.reset_counters()

    def build(seed, n_entities):
        rng = np.random.default_rng(seed)
        w = rng.normal(size=d_fe).astype(np.float32)
        M = np.zeros((n_entities + 1, d_re), np.float32)
        M[:n_entities] = rng.normal(size=(n_entities, d_re)) * 0.4
        model = GameModel(
            {
                "fixed": FixedEffectModel(Coefficients(jnp.asarray(w)), task),
                "per-e": RandomEffectModel(jnp.asarray(M), None, task),
            }
        )
        specs = {
            "fixed": CoordinateScoringSpec(shard="g"),
            "per-e": CoordinateScoringSpec(
                shard="re",
                random_effect_type="eid",
                entity_index={str(i): i for i in range(n_entities)},
            ),
        }
        return model, specs

    def requests(seed, n, n_entities):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, d_fe)).astype(np.float32)
        Xe = rng.normal(size=(n, d_re)).astype(np.float32)
        ids = rng.integers(0, n_entities + 4, size=n)
        return [
            ScoreRequest(
                features={"g": X[i], "re": Xe[i]},
                entity_ids={"eid": str(int(ids[i]))},
                offset=float(i) * 0.0625,
                uid=str(i),
            )
            for i in range(n)
        ]

    def scores_of(results):
        return np.asarray([r.score for r in results], np.float64)

    # ---- phase 1: ten tenants, one fleet ----------------------------------
    # Entity counts vary per tenant (heterogeneous bundles co-batch); one
    # clean tenant stages entity-sharded over the mesh.
    clean_names = [f"clean-{i}" for i in range(1, 9)] + ["clean-sharded"]
    ent_of = {"chaos": 40}
    for i, nm in enumerate(clean_names):
        ent_of[nm] = 24 + 8 * i
    ent_of["clean-sharded"] = 16 * ndev
    models = {nm: build(100 + j, ent_of[nm]) for j, nm in enumerate(["chaos"] + clean_names)}
    reqs = {
        nm: requests(200 + j, n_clean_each, ent_of[nm])
        for j, nm in enumerate(["chaos"] + clean_names)
    }
    refs = {}
    for nm in ["chaos"] + clean_names:
        m, s = models[nm]
        with ServingEngine(
            ServingBundle.from_model(m, s, task), max_batch=16
        ) as eng:
            refs[nm] = scores_of(eng.score_batch(reqs[nm]))

    bundles = {}
    for nm in ["chaos"] + clean_names:
        m, s = models[nm]
        bundles[nm] = ServingBundle.from_model(
            m, s, task, mesh=mesh if nm == "clean-sharded" else None
        )
    latecomer_model = build(999, 32)
    latecomer_bundle = ServingBundle.from_model(*latecomer_model, task)
    resident = sum(b.device_bytes_per_shard() for b in bundles.values())
    # Budget fits the ten residents but NOT the latecomer: admission must
    # demote a cold tenant instead of failing anyone.
    budget = resident + latecomer_bundle.device_bytes_per_shard() // 2

    reg = TenantRegistry(
        max_batch=16,
        max_wait_ms=1.0,  # photon-lint: disable=planner-constant — deliberate section config: fixed wait pins the measurement, not a runtime default
        hbm_budget_bytes=int(budget),
    )
    reg.admit(
        "chaos",
        bundles["chaos"],
        max_pending=8,
        deadline_ms=deadline_ms,
        inject_faults=True,
        watchdog_ms_override=0.01,  # every chaos fallback dispatch trips
    )
    for nm in clean_names:
        reg.admit(
            nm,
            bundles[nm],
            deadline_ms=deadline_ms,
            inject_faults=False,
        )

    # ---- phase 2: chaos confined to one tenant ----------------------------
    faults.install("lookup:2,score:3,admit:2")
    chaos_shed = [0]
    chaos_answered = [0]
    chaos_reqs = requests(300, 48, ent_of["chaos"])

    def _chaos_flood():
        futs = []
        for r in chaos_reqs:
            try:
                futs.append(reg.submit("chaos", r))  # block=False: shed!
            except Overloaded:
                chaos_shed[0] += 1
            except Exception:  # noqa: BLE001 - typed rejections only
                pass
        for f in futs:
            try:
                f.result(timeout=120)
                chaos_answered[0] += 1
            except Exception:  # noqa: BLE001 - chaos tenant may reject
                pass

    flood = _threading.Thread(target=_chaos_flood, name="bench-mt-chaos")
    flood.start()
    clean_futs = {nm: [] for nm in clean_names}
    for i in range(n_clean_each):
        for nm in clean_names:
            clean_futs[nm].append(reg.submit(nm, reqs[nm][i], block=True))
    clean_scores = {
        nm: np.asarray([f.result(timeout=120).score for f in fs], np.float64)
        for nm, fs in clean_futs.items()
    }
    flood.join()
    faults.install("")

    m = reg.metrics()
    clean_bitwise = all(
        bool(np.array_equal(clean_scores[nm], refs[nm]))
        for nm in clean_names
    )
    clean_failed = sum(m["tenants"][nm]["failed"] for nm in clean_names)
    clean_deadline = sum(
        m["tenants"][nm]["deadline_missed"] for nm in clean_names
    )
    clean_degraded = sum(
        m["tenants"][nm]["degraded_batches"] for nm in clean_names
    )
    # The labeled sub-counters are the isolation proof at the metrics
    # layer: every clean tenant's slice of every serving robustness
    # counter must be zero even while the aggregate counts chaos events.
    for counter in (
        "serving_degraded_batches",
        "serving_shed_requests",
        "serving_deadline_misses",
        "serving_fe_only_requests",
    ):
        labeled = telemetry.METRICS.labeled_counters(counter)
        clean_degraded += sum(
            labeled.get(f"tenant={nm}", 0) for nm in clean_names
        )
    clean_p99_ok = all(
        m["tenants"][nm]["p99_ms"] is not None
        and m["tenants"][nm]["p99_ms"] < deadline_ms
        for nm in clean_names
    )
    chaos_hangs = int(
        telemetry.METRICS.labeled_counters("watchdog_trips").get(
            "tenant=chaos", 0
        )
    )

    # ---- phase 3: HBM-pressure eviction -----------------------------------
    # Touch everyone except clean-1 so it is the coldest; the latecomer's
    # admission must demote it (never fail it) and both keep answering.
    for nm in ["chaos"] + clean_names[1:]:
        try:
            reg.score(nm, reqs[nm][0])
        except Exception:  # noqa: BLE001 - chaos tenant may shed
            pass
    admitted_over_budget = False
    demoted_tenant = None
    try:
        reg.admit("latecomer", latecomer_bundle, deadline_ms=deadline_ms)
        admitted_over_budget = True
    except Exception:  # noqa: BLE001 - recorded in the artifact
        pass
    m3 = reg.metrics()
    for nm, block in m3["tenants"].items():
        if block["demoted"]:
            demoted_tenant = nm
    evicted_bitwise = False
    if demoted_tenant is not None:
        got = scores_of(
            [reg.score(demoted_tenant, r) for r in reqs[demoted_tenant]]
        )
        evicted_bitwise = bool(np.array_equal(got, refs[demoted_tenant]))

    final = reg.metrics()
    reg.close(release_bundles=True)

    # ---- phase 4: precision-ladder HBM squeeze (ISSUE 20) -----------------
    from photon_ml_tpu.serving.bundle import quantize_bundle_rows
    from photon_ml_tpu.utils.contracts import TIER_TOLERANCES

    lad_d_re = 32  # wide RE rows: the regime where int8 + scales pays
    lad_ents = 64
    n_lad = 13
    lad_names = [f"lad-{i}" for i in range(n_lad)]

    def build_wide(seed):
        rng = np.random.default_rng(seed)
        w = rng.normal(size=d_fe).astype(np.float32)
        M = np.zeros((lad_ents + 1, lad_d_re), np.float32)
        M[:lad_ents] = rng.normal(size=(lad_ents, lad_d_re)) * 0.4
        model = GameModel(
            {
                "fixed": FixedEffectModel(Coefficients(jnp.asarray(w)), task),
                "per-e": RandomEffectModel(jnp.asarray(M), None, task),
            }
        )
        specs = {
            "fixed": CoordinateScoringSpec(shard="g"),
            "per-e": CoordinateScoringSpec(
                shard="re",
                random_effect_type="eid",
                entity_index={str(i): i for i in range(lad_ents)},
            ),
        }
        return model, specs

    def requests_wide(seed, n):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, d_fe)).astype(np.float32)
        Xe = rng.normal(size=(n, lad_d_re)).astype(np.float32)
        ids = rng.integers(0, lad_ents + 4, size=n)
        return [
            ScoreRequest(
                features={"g": X[i], "re": Xe[i]},
                entity_ids={"eid": str(int(ids[i]))},
                offset=float(i) * 0.0625,
                uid=str(i),
            )
            for i in range(n)
        ]

    lad_models = {nm: build_wide(800 + j) for j, nm in enumerate(lad_names)}
    lad_reqs = {
        nm: requests_wide(900 + j, 16) for j, nm in enumerate(lad_names)
    }
    lad_refs = {}
    for nm in lad_names:
        mdl, spc = lad_models[nm]
        with ServingEngine(
            ServingBundle.from_model(mdl, spc, task), max_batch=16
        ) as eng:
            lad_refs[nm] = scores_of(eng.score_batch(lad_reqs[nm]))

    # Measure the per-tenant footprint at both ends of the ladder, then
    # set a budget that fits ONE f32 newcomer beside an int8 fleet.
    probe = ServingBundle.from_model(*build_wide(777), task)
    per_f32 = probe.device_bytes_per_shard()
    q_probe, _ = quantize_bundle_rows(probe, "int8")
    per_i8 = q_probe.device_bytes_per_shard()
    q_probe.release(close_stores=False)
    probe.release(close_stores=False)
    lad_budget = per_f32 + (n_lad - 1) * per_i8 + per_i8 // 2

    def _squeeze(ladder_on):
        """Admit the 13 wide tenants under the squeeze budget; return
        (resident count, registry metrics, registry)."""
        if ladder_on:
            os.environ["PHOTON_TIER_LADDER"] = "1"
        else:
            os.environ.pop("PHOTON_TIER_LADDER", None)
        r = TenantRegistry(
            max_batch=16,
            max_wait_ms=1.0,  # photon-lint: disable=planner-constant — deliberate section config: fixed wait pins the measurement, not a runtime default
            hbm_budget_bytes=int(lad_budget),
        )
        for nm in lad_names:
            mdl, spc = lad_models[nm]
            r.admit(
                nm,
                ServingBundle.from_model(mdl, spc, task),
                deadline_ms=deadline_ms,
                inject_faults=False,
            )
        mm = r.metrics()
        res = sum(
            1 for blk in mm["tenants"].values() if not blk["demoted"]
        )
        return res, mm, r

    # The f32-only baseline capacity, MEASURED: same budget, ladder off.
    f32_capacity, _, reg_f32 = _squeeze(ladder_on=False)
    reg_f32.close(release_bundles=True)

    injected_phase2 = int(faults.COUNTERS.get("injected_faults"))
    faults.reset_counters()  # isolate the ladder-phase transition counts
    ladder_resident, m4, reg4 = _squeeze(ladder_on=True)

    # Quantized replay: every resident tenant answers within its rung's
    # pinned tolerance. The tier sub-block keeps the rung even beside
    # demoted=True, so a quantized-then-evicted tenant compares under its
    # rung's tolerance and a never-quantized one under f32's exact zeros.
    quant_ok = True
    for nm in lad_names:
        tol = TIER_TOLERANCES[m4["tenants"][nm]["tier"]["tier"]]
        got = scores_of([reg4.score(nm, r) for r in lad_reqs[nm]])
        quant_ok = quant_ok and bool(
            np.allclose(got, lad_refs[nm], rtol=tol["rtol"], atol=tol["atol"])
        )

    # Chaos on a ladder transition: a terminal mid-quantize fault on the
    # newest (still-f32) tenant leaves its generation serving and stays
    # confined — neighbors keep answering, zero failed requests anywhere.
    chaos_confined = True
    faults.install("quantize_stage:99")
    try:
        reg4.demote_tier(lad_names[-1], reason="bench_chaos")
        chaos_confined = False  # the injected terminal fault vanished
    except Exception:  # noqa: BLE001 - the expected terminal injection
        pass
    faults.install("")
    got = scores_of(
        [reg4.score(lad_names[-1], r) for r in lad_reqs[lad_names[-1]]]
    )
    chaos_confined = chaos_confined and bool(
        np.array_equal(got, lad_refs[lad_names[-1]])
    )
    for nm in lad_names[:2]:
        tol = TIER_TOLERANCES[m4["tenants"][nm]["tier"]["tier"]]
        got = scores_of([reg4.score(nm, r) for r in lad_reqs[nm]])
        chaos_confined = chaos_confined and bool(
            np.allclose(got, lad_refs[nm], rtol=tol["rtol"], atol=tol["atol"])
        )

    # Restore: retire part of the fleet to make room, walk the coldest
    # (most-degraded) tenant back to f32 — bitwise vs its pre-demotion
    # self (the solo reference: it was admitted at f32). Failed-request
    # counts for the retired tenants are snapshotted first — remove()
    # drops their metrics blocks.
    m4c = reg4.metrics()
    retired = lad_names[5:10]
    for nm in retired:
        reg4.remove(nm, release_bundle=True)
    reg4.restore_tier(lad_names[0], reason="bench_restore")
    got0 = scores_of(
        [reg4.score(lad_names[0], r) for r in lad_reqs[lad_names[0]]]
    )
    restored_bitwise = bool(np.array_equal(got0, lad_refs[lad_names[0]]))
    restored_bitwise = restored_bitwise and (
        reg4.metrics()["tenants"][lad_names[0]]["tier"]["tier"] == "f32"
    )

    m4f = reg4.metrics()
    ladder_failed = sum(
        blk["failed"] for blk in m4f["tenants"].values()
    ) + sum(m4c["tenants"][nm]["failed"] for nm in retired)
    ladder_transitions = int(
        faults.COUNTERS.get("tier_demotions")
        + faults.COUNTERS.get("tier_restores")
        + faults.COUNTERS.get("tier_rollbacks")
        + faults.COUNTERS.get("tenant_demotions")
        + faults.COUNTERS.get("tenant_restores")
    )
    reg4.close(release_bundles=True)
    os.environ.pop("PHOTON_TIER_LADDER", None)

    print(
        json.dumps(
            dict(
                n_devices=ndev,
                n_tenants=10,
                chaos_tenant="chaos",
                injected_faults=injected_phase2,
                chaos_shed=int(chaos_shed[0]),
                chaos_answered=int(chaos_answered[0]),
                chaos_hangs=chaos_hangs,
                clean_requests=int(n_clean_each * len(clean_names)),
                clean_failed_requests=int(clean_failed),
                clean_deadline_misses=int(clean_deadline),
                clean_degraded_batches=int(clean_degraded),
                clean_p99_within_deadline=bool(clean_p99_ok),
                clean_bitwise_vs_solo=bool(clean_bitwise),
                cobatch_dispatches=int(final["cobatch_dispatches"]),
                demoted_tenant=demoted_tenant,
                admitted_over_budget=bool(admitted_over_budget),
                evicted_bitwise=bool(evicted_bitwise),
                ladder_resident_tenants=int(ladder_resident),
                f32_capacity_tenants=int(f32_capacity),
                ladder_capacity_ratio=float(
                    ladder_resident / max(1, f32_capacity)
                ),
                # Covers the post-chaos neighbor replays too: a confined
                # terminal quantize fault must leave every OTHER tenant
                # answering inside its rung's pinned tolerance.
                quantized_within_tolerance=bool(quant_ok and chaos_confined),
                ladder_failed_requests=int(ladder_failed),
                ladder_transitions=ladder_transitions,
                ladder_restored_bitwise=bool(restored_bitwise),
                tenants={
                    nm: dict(block)
                    for nm, block in final["tenants"].items()
                },
            )
        )
    )


def _continuous_loop_child() -> None:
    """Continuous-refresh certificate (ISSUE 16) on an 8-virtual-device
    mesh: full fit -> streamed delta batch -> warm-start incremental fit
    -> delta-bundle swap into a LIVE engine under replay. Measures the
    data->served freshness wall against the full-refit + full-restage
    baseline, asserts the unchanged-entity bitwise carry, and requires
    zero failed requests through the generation flip.

    Prints exactly one JSON line."""
    import threading as _threading

    import numpy as np
    import jax.numpy as jnp

    from photon_ml_tpu.data.game_dataset import (
        FixedEffectDataConfig,
        GameDataset,
        RandomEffectDataConfig,
        concat_datasets,
    )
    from photon_ml_tpu.game import incremental
    from photon_ml_tpu.optimize.config import (
        L2,
        CoordinateOptimizationConfig,
        OptimizerConfig,
    )
    from photon_ml_tpu.parallel.mesh import make_mesh
    from photon_ml_tpu.serving import (
        ScoreRequest,
        ServingBundle,
        ServingEngine,
    )
    from photon_ml_tpu.serving.delta import apply_delta, build_delta_bundle
    from photon_ml_tpu.types import TaskType
    from photon_ml_tpu.utils import faults

    task = TaskType.LOGISTIC_REGRESSION
    mesh8 = make_mesh()
    ndev = int(mesh8.devices.size)
    faults.install("")
    faults.reset_counters()

    rng = np.random.default_rng(61)
    d_fe, d_re = 8, 12
    # Entity-heavy on purpose: the delta win is re-solving 8 entities
    # instead of all of them, so the full refit must actually pay for the
    # entity sweep. 12 rows per entity, so min_bucket stays below it.
    n_ent = 2048 * ndev
    n_base = n_ent * 12
    data_configs = {
        "fixed": FixedEffectDataConfig("g"),
        "per-entity": RandomEffectDataConfig("eid", "re", min_bucket=8),
    }
    opt_configs = {
        "fixed": CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=10),
            regularization=L2,
            reg_weight=1.0,
        ),
        # The per-entity solves carry the iteration budget — the usual GAME
        # shape (photon-ml's per-member models dominate its training bill),
        # and exactly the work an incremental fit skips for clean entities.
        "per-entity": CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=40),
            regularization=L2,
            reg_weight=1.0,
        ),
    }

    def make_batch(n, ent_pool):
        ent = np.resize(np.asarray(ent_pool, np.int64), n)
        return GameDataset.build(
            {
                "g": jnp.asarray(
                    rng.normal(size=(n, d_fe)).astype(np.float32)
                ),
                "re": jnp.asarray(
                    rng.normal(size=(n, d_re)).astype(np.float32)
                ),
            },
            (rng.uniform(size=n) < 0.5).astype(np.float32),
            id_tags={"eid": ent},
        )

    base = make_batch(n_base, np.arange(n_ent))

    # ---- round 0: full fit + staged serving generation --------------------
    t0 = time.perf_counter()
    state = incremental.full_fit(base, data_configs, opt_configs, task)
    full_fit_s = time.perf_counter() - t0
    specs = incremental.scoring_specs(data_configs, state.entity_indices)
    engine = ServingEngine(
        ServingBundle.from_model(state.model, specs, task, mesh=mesh8),
        max_batch=64,
    )
    engine.warmup()

    n_req = 128
    Xf = rng.normal(size=(n_req, d_fe)).astype(np.float32)
    Xr = rng.normal(size=(n_req, d_re)).astype(np.float32)
    reqs = [
        ScoreRequest(
            features={"g": Xf[i], "re": Xr[i]},
            entity_ids={"eid": int(v)},
            uid=str(i),
        )
        for i, v in enumerate(rng.integers(0, n_ent, size=n_req))
    ]
    engine.score_batch(reqs)  # compile the serving path before the clock

    # ---- streamed delta batch: churn + brand-new entities -----------------
    churn = rng.choice(n_ent, size=6, replace=False)
    fresh = np.arange(n_ent, n_ent + 2)  # sort AFTER existing ids: append
    delta_batch = make_batch(128, np.concatenate([churn, fresh]))
    merged = concat_datasets(base, delta_batch)

    # Warm BOTH paths before the clocks start: a continuous refresh loop
    # runs every round with recurring shapes, so its steady-state cost is
    # compute, not XLA compiles — and in one process whichever path ran
    # second would inherit the other's executables anyway. The warm-up
    # results are discarded; both measured phases below replay the exact
    # same deterministic solves against warm caches.
    incremental.incremental_fit(
        merged, data_configs, opt_configs, task, prev=state
    )
    warm_state = incremental.full_fit(merged, data_configs, opt_configs, task)
    ServingBundle.from_model(
        warm_state.model,
        incremental.scoring_specs(data_configs, warm_state.entity_indices),
        task,
        mesh=mesh8,
    ).release()

    stop = _threading.Event()
    failures, answered = [], [0]

    def _traffic(batcher):
        # Steady replay, throttled so the GIL leaves room for the fit the
        # refresh is racing — the contract is zero FAILED requests, not an
        # open-loop load test (the dedicated serving sections measure that).
        j = 0
        while not stop.is_set():
            try:
                batcher.score(reqs[j % n_req])
                answered[0] += 1
            except Exception as exc:  # noqa: BLE001 - recorded
                failures.append(repr(exc))
            j += 1
            time.sleep(0.002)

    with engine, engine.batcher(max_wait_ms=0.5) as batcher:  # photon-lint: disable=planner-constant — deliberate section config: fixed wait pins the measurement, not a runtime default
        th = _threading.Thread(
            target=_traffic, args=(batcher,), name="photon-refresh-replay"
        )
        th.start()
        time.sleep(0.1)
        # The freshness clock: delta batch in hand -> new generation live.
        t_data = time.perf_counter()
        result = incremental.incremental_fit(
            merged, data_configs, opt_configs, task, prev=state
        )
        delta = build_delta_bundle(
            state, result.state, source="bench-delta", mode=result.plan.mode,
            delta_rows=result.plan.delta_rows,
            total_rows=result.plan.total_rows,
        )
        t_apply = time.perf_counter()
        info = apply_delta(engine, delta)
        delta_apply_s = time.perf_counter() - t_apply
        data_to_served_s = time.perf_counter() - t_data

        # ---- baseline: from-scratch refit + full restage, under the SAME
        # replay traffic (a production fleet keeps serving through a
        # retrain, and stopping the replay here would hand the baseline an
        # uncontended machine the delta path never got).
        t_base = time.perf_counter()
        cold_state = incremental.full_fit(
            merged, data_configs, opt_configs, task
        )
        cold_specs = incremental.scoring_specs(
            data_configs, cold_state.entity_indices
        )
        cold_bundle = ServingBundle.from_model(
            cold_state.model, cold_specs, task, mesh=mesh8
        )
        full_refresh_baseline_s = time.perf_counter() - t_base
        cold_bundle.release()
        stop.set()
        th.join(timeout=60)

    # ---- unchanged-entity bitwise carry ------------------------------------
    changed = set(result.plan.changed_entities.get("per-entity", ()))
    pm = np.asarray(state.model["per-entity"].coefficients_matrix)
    nm = np.asarray(result.state.model["per-entity"].coefficients_matrix)
    prev_idx = state.entity_indices["per-entity"]
    new_idx = result.state.entity_indices["per-entity"]
    unchanged_bitwise = all(
        np.array_equal(pm[prev_idx[k]], nm[new_idx[k]])
        for k in prev_idx
        if k not in changed
    )
    engine.bundle.release()

    print(
        json.dumps(
            dict(
                n_devices=ndev,
                total_rows=int(result.plan.total_rows),
                delta_rows=int(result.plan.delta_rows),
                delta_fraction=round(result.plan.delta_fraction, 4),
                changed_coordinates=list(result.plan.changed_coordinates),
                full_fit_s=round(full_fit_s, 4),
                incremental_fit_s=round(result.seconds, 4),
                delta_apply_s=round(delta_apply_s, 4),
                data_to_served_s=round(data_to_served_s, 4),
                full_refresh_baseline_s=round(full_refresh_baseline_s, 4),
                speedup_vs_full=round(
                    full_refresh_baseline_s / max(data_to_served_s, 1e-9), 2
                ),
                unchanged_entities_bitwise=bool(unchanged_bitwise),
                answered_during_refresh=int(answered[0]),
                failed_requests=len(failures),
                generation=int(info["version"]),
            )
        )
    )


def _shadow_sigkill_fixture():
    """Deterministic numpy-only fixture shared by the shadow_deploy child
    and its SIGKILL victim: both processes rebuild the SAME champion /
    challenger weights and probe traffic from fixed seeds, so the child
    can compute the champion's solo reference and compare it bitwise
    against scores the victim produced mid-promotion."""
    import numpy as np

    d_fe, d_re, n_ent, n_req = 8, 6, 32, 24
    rng = np.random.default_rng(7)
    w_champ = rng.normal(size=d_fe).astype(np.float32)
    M_champ = np.zeros((n_ent + 1, d_re), np.float32)
    M_champ[:n_ent] = rng.normal(size=(n_ent, d_re)).astype(np.float32)
    w_chall = rng.normal(size=d_fe).astype(np.float32)
    M_chall = np.zeros((n_ent + 1, d_re), np.float32)
    M_chall[:n_ent] = rng.normal(size=(n_ent, d_re)).astype(np.float32)
    Xg = rng.normal(size=(n_req, d_fe)).astype(np.float32)
    Xre = rng.normal(size=(n_req, d_re)).astype(np.float32)
    ids = rng.integers(0, n_ent, size=n_req)
    return (w_champ, M_champ), (w_chall, M_chall), (Xg, Xre, ids), n_ent


def _shadow_array_bundle(w, M, n_ent):
    import jax.numpy as jnp

    from photon_ml_tpu.game.model import (
        Coefficients,
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_ml_tpu.serving import ServingBundle
    from photon_ml_tpu.transformers.game_transformer import (
        CoordinateScoringSpec,
    )
    from photon_ml_tpu.types import TaskType

    task = TaskType.LOGISTIC_REGRESSION
    model = GameModel(
        {
            "fixed": FixedEffectModel(Coefficients(jnp.asarray(w)), task),
            "per-e": RandomEffectModel(jnp.asarray(M), None, task),
        }
    )
    specs = {
        "fixed": CoordinateScoringSpec(shard="g"),
        "per-e": CoordinateScoringSpec(
            shard="re",
            random_effect_type="eid",
            entity_index={str(i): i for i in range(n_ent)},
        ),
    }
    return ServingBundle.from_model(model, specs, task)


def _shadow_sigkill_requests(traffic):
    from photon_ml_tpu.serving import ScoreRequest

    Xg, Xre, ids = traffic
    return [
        ScoreRequest(
            features={"g": Xg[i], "re": Xre[i]},
            entity_ids={"eid": str(int(ids[i]))},
            uid=str(i),
        )
        for i in range(len(ids))
    ]


def _shadow_promote_worker() -> None:
    """SIGKILL-mid-promotion victim for the shadow_deploy section. Arms a
    stall at the BundleManager's `swap_commit` fault point (held under
    the swap lock only — the champion's serving path never stops),
    drives a promotion into that stall from a side thread, scores
    champion traffic THROUGH the registry mid-stall, durably writes the
    scores + a marker for the parent, then holds the promotion open
    until the parent SIGKILLs this process. Killed there, the flip never
    committed: the champion is still on its old generation, which is
    exactly what the parent's bitwise check certifies."""
    import threading as _threading

    from photon_ml_tpu.serving import TenantRegistry
    from photon_ml_tpu.serving.shadow import ShadowController
    from photon_ml_tpu.utils import faults

    scratch = sys.argv[sys.argv.index(_SHADOW_PROMOTE_WORKER) + 1]
    faults.install("")
    faults.reset_counters()
    champ, chall, traffic, n_ent = _shadow_sigkill_fixture()
    reqs = _shadow_sigkill_requests(traffic)

    stall_marker = os.path.join(scratch, "stalled")
    orig_fault_point = faults.fault_point

    def _stalling_fault_point(site):
        if site == "swap_commit":
            with open(stall_marker, "w") as fh:
                fh.write("stalled\n")
            time.sleep(600.0)  # the parent SIGKILLs long before this ends
        return orig_fault_point(site)

    faults.fault_point = _stalling_fault_point

    registry = TenantRegistry(max_batch=32)
    registry.admit("champ", _shadow_array_bundle(*champ, n_ent))
    controller = ShadowController(
        registry,
        "champ",
        "cand",
        _shadow_array_bundle(*chall, n_ent),
        window_size=64,
    )
    _threading.Thread(
        target=lambda: controller.promote(raise_on_failure=False),
        name="photon-shadow-promote-drive",
        daemon=True,
    ).start()
    deadline = time.monotonic() + 60.0
    while not os.path.exists(stall_marker):
        if time.monotonic() > deadline:
            raise RuntimeError("promotion never reached swap_commit")
        time.sleep(0.01)
    scores = [
        registry.submit("champ", r, block=True).result(timeout=30.0).score
        for r in reqs
    ]
    tmp = os.path.join(scratch, "scores.json.tmp")
    with open(tmp, "w") as fh:
        json.dump([float(s) for s in scores], fh)
    os.replace(tmp, os.path.join(scratch, "scores.json"))
    time.sleep(600.0)  # hold mid-promotion; the parent's SIGKILL ends us


def _shadow_deploy_child() -> None:
    """Shadow deployment & online evaluation certificate (ISSUE 18) on an
    8-virtual-device mesh. Four drills, one JSON line:

      A. a deliberately degraded challenger (refit with 40% of its labels
         flipped) admitted as a shadow tenant is detected from mirrored
         windowed metrics ALONE and torn down on its reject verdict —
         zero champion requests failed, champion answers bitwise vs the
         same weights served solo;
      B. armed shadow_mirror/label_join faults degrade mirroring to
         champion-only serving (counted), never a failed client request;
      C. a healthy challenger (same-data refit: identical weights by
         determinism, so the windowed regression is exactly 0.0 and the
         leg certifies the ACTUATION path on every backend — the
         quality-detection direction is drill A's job) rides the verdict
         loop to promotion through the atomic generation flip, with
         every robustness counter zero across the clean phase;
      D. a worker process SIGKILLed mid-promotion (stalled at
         swap_commit, pre-flip) leaves its champion serving the old
         generation bitwise — the flip is atomic under OS-level murder.
    """
    import shutil
    import tempfile

    import numpy as np
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.game_dataset import (
        FixedEffectDataConfig,
        GameDataset,
        RandomEffectDataConfig,
    )
    from photon_ml_tpu.game import incremental
    from photon_ml_tpu.optimize.config import (
        L2,
        CoordinateOptimizationConfig,
        OptimizerConfig,
    )
    from photon_ml_tpu.serving import (
        ScoreRequest,
        ServingBundle,
        ServingEngine,
        TenantRegistry,
    )
    from photon_ml_tpu.serving.shadow import ShadowController
    from photon_ml_tpu.types import TaskType
    from photon_ml_tpu.utils import faults
    from photon_ml_tpu.utils.contracts import ROBUSTNESS_CLEAN_ZERO_KEYS

    task = TaskType.LOGISTIC_REGRESSION
    ndev = len(jax.devices())
    faults.install("")
    faults.reset_counters()

    rng = np.random.default_rng(181)
    d_fe, d_re = 8, 6
    n_ent = 48
    n_base = n_ent * 16
    # Signal-bearing labels: a fixed linear rule + noise. The clean refit
    # learns it; the label-noised refit learns 40% garbage — that quality
    # gap is what the shadow windows must see from mirrored traffic.
    w_true = np.linspace(1.5, -1.5, d_fe).astype(np.float32)
    ent = np.resize(np.arange(n_ent, dtype=np.int64), n_base)
    Xg = rng.normal(size=(n_base, d_fe)).astype(np.float32)
    Xre = rng.normal(size=(n_base, d_re)).astype(np.float32)
    y = (Xg @ w_true + 0.25 * rng.normal(size=n_base) > 0).astype(np.float32)
    flip = rng.uniform(size=n_base) < 0.4
    y_bad = np.where(flip, 1.0 - y, y).astype(np.float32)

    data_configs = {
        "fixed": FixedEffectDataConfig("g"),
        "per-entity": RandomEffectDataConfig("eid", "re", min_bucket=8),
    }
    oc = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=15),
        regularization=L2,
        reg_weight=1.0,
    )
    opt_configs = {"fixed": oc, "per-entity": oc}

    def fit_bundle(labels):
        st = incremental.full_fit(
            GameDataset.build(
                {"g": jnp.asarray(Xg), "re": jnp.asarray(Xre)},
                jnp.asarray(labels),
                id_tags={"eid": ent},
            ),
            data_configs,
            opt_configs,
            task,
        )
        return ServingBundle.from_model(
            st.model,
            incremental.scoring_specs(data_configs, st.entity_indices),
            task,
        )

    champ_bundle = fit_bundle(y)
    degraded_bundle = fit_bundle(y_bad)
    healthy_bundle = fit_bundle(y)  # same data, same seed: same weights

    def probes(seed, n):
        prng = np.random.default_rng(seed)
        pe = np.resize(np.arange(n_ent, dtype=np.int64), n)
        Pg = prng.normal(size=(n, d_fe)).astype(np.float32)
        Pre = prng.normal(size=(n, d_re)).astype(np.float32)
        lab = (
            Pg @ w_true + 0.25 * prng.normal(size=n) > 0
        ).astype(np.float64)
        reqs = [
            ScoreRequest(
                features={"g": Pg[i], "re": Pre[i]},
                entity_ids={"eid": int(pe[i])},
                uid=f"p{seed}-{i}",
            )
            for i in range(n)
        ]
        return reqs, lab

    reqs_a, lab_a = probes(1, 32)
    reqs_b, lab_b = probes(2, 24)
    reqs_c, lab_c = probes(3, 32)
    reqs_post, _ = probes(4, 16)

    # Solo champion references: the SAME weights (same-data refit, exact
    # by determinism) alone on a plain engine — the bitwise anchor for
    # every drill's champion answers.
    ref = {}
    solo = ServingEngine(fit_bundle(y), max_batch=32)
    with solo:
        for key, rq in (
            ("a", reqs_a),
            ("b", reqs_b),
            ("c", reqs_c),
            ("post", reqs_post),
        ):
            ref[key] = np.asarray(
                [r.score for r in solo.score_batch(rq)], np.float64
            )
    solo.bundle.release()

    registry = TenantRegistry(max_batch=32)
    registry.admit("champ", champ_bundle)

    def drive(controller, reqs, labels):
        """The serving loop's shadow hookup: submit to the champion,
        mirror, join the label. Client answers come ONLY from the
        champion futures."""
        futs = []
        for rq, lb in zip(reqs, labels):
            fut = registry.submit("champ", rq, block=True)
            futs.append(fut)
            if controller.mirror(rq, fut):
                controller.record_label(rq.uid, float(lb))
        scores, failed = [], 0
        for f in futs:
            try:
                scores.append(float(f.result(timeout=60.0).score))
            except Exception:  # noqa: BLE001 - counted as a failed request
                failed += 1
        return np.asarray(scores, np.float64), failed

    # ---- drill A: degraded challenger detected + rolled back -------------
    ctl_a = ShadowController(
        registry,
        "champ",
        "degraded",
        degraded_bundle,
        window_size=16,
        min_windows=2,
        cooldown_s=0.0,
    )
    got_a, failed_a = drive(ctl_a, reqs_a, lab_a)
    verdict_a = ctl_a.wait_for_verdict(timeout_s=120.0)
    sum_a = ctl_a.summary()
    ctl_a.close()
    degraded_torn_down = False
    try:
        registry.tenant("degraded")
    except KeyError:
        degraded_torn_down = True
    degraded_detected = verdict_a == "reject"
    degraded_rolled_back = (
        degraded_torn_down
        and int(faults.COUNTERS.get("shadow_rollbacks")) == 1
    )
    degraded_champion_bitwise = bool(
        failed_a == 0 and np.array_equal(got_a, ref["a"])
    )

    # ---- drill B: mirror/label-join faults degrade to champion-only ------
    faults.reset_counters()
    ctl_b = ShadowController(
        registry,
        "champ",
        "cand-b",
        fit_bundle(y_bad),
        window_size=64,
        min_windows=2,
    )
    with faults.inject("shadow_mirror:3,label_join:2"):
        got_b, failed_b = drive(ctl_b, reqs_b, lab_b)
    sum_b = ctl_b.summary()
    ctl_b.close()  # no-opinion exit: shadow torn down, no rollback count
    mirror_faults_injected = int(
        faults.COUNTERS.get("shadow_mirror_failures")
    ) + int(faults.COUNTERS.get("label_join_failures"))
    mirror_fault_champion_clean = bool(
        failed_b == 0 and np.array_equal(got_b, ref["b"])
    )

    # ---- drill C: healthy challenger rides the loop to promotion ---------
    faults.reset_counters()
    ctl_c = ShadowController(
        registry,
        "champ",
        "healthy",
        healthy_bundle,
        window_size=16,
        min_windows=2,
        cooldown_s=0.0,
    )
    got_c, failed_c = drive(ctl_c, reqs_c, lab_c)
    verdict_c = ctl_c.wait_for_verdict(timeout_s=120.0)
    healthy_promoted = bool(
        verdict_c == "promote" and ctl_c.status == "promoted"
    )
    sum_c = ctl_c.summary()
    ctl_c.close()
    promoted_generation = int(registry.tenant("champ").engine._state.version)
    healthy_champion_bitwise = bool(
        failed_c == 0 and np.array_equal(got_c, ref["c"])
    )
    post_futs = [
        registry.submit("champ", rq, block=True) for rq in reqs_post
    ]
    post = np.asarray(
        [float(f.result(timeout=60.0).score) for f in post_futs], np.float64
    )
    post_promote_bitwise = bool(np.array_equal(post, ref["post"]))
    clean_counters_zero = all(
        int(faults.COUNTERS.get(k)) == 0 for k in ROBUSTNESS_CLEAN_ZERO_KEYS
    )
    cobatched = int(registry.metrics()["cobatch_dispatches"])
    mirrored_total = (
        int(sum_a["mirrored_requests"])
        + int(sum_b["mirrored_requests"])
        + int(sum_c["mirrored_requests"])
    )
    registry.close(release_bundles=True)

    # ---- drill D: SIGKILL mid-promotion leaves the old generation --------
    champ_d, _chall_d, traffic_d, n_ent_d = _shadow_sigkill_fixture()
    reqs_d = _shadow_sigkill_requests(traffic_d)
    solo_d = ServingEngine(
        _shadow_array_bundle(*champ_d, n_ent_d), max_batch=32
    )
    with solo_d:
        ref_d = np.asarray(
            [r.score for r in solo_d.score_batch(reqs_d)], np.float64
        )
    solo_d.bundle.release()
    scratch = tempfile.mkdtemp(prefix="photon-shadow-sigkill-")
    sigkill_champion_bitwise = False
    try:
        proc = subprocess.Popen(
            [
                sys.executable,
                os.path.abspath(__file__),
                _SHADOW_PROMOTE_WORKER,
                scratch,
            ],
            stdout=subprocess.DEVNULL,  # this child prints ONE JSON line
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        scores_path = os.path.join(scratch, "scores.json")
        deadline = time.monotonic() + 180.0
        while (
            not os.path.exists(scores_path)
            and proc.poll() is None
            and time.monotonic() < deadline
        ):
            time.sleep(0.05)
        if os.path.exists(scores_path):
            proc.kill()  # SIGKILL: the swap is still stalled pre-commit
            proc.wait(timeout=30.0)
            with open(scores_path) as fh:
                mid = np.asarray(json.load(fh), np.float64)
            sigkill_champion_bitwise = bool(np.array_equal(mid, ref_d))
        else:
            proc.kill()
            proc.wait(timeout=30.0)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)

    print(
        json.dumps(
            dict(
                n_devices=ndev,
                mirrored_requests=mirrored_total,
                shadow_cobatched=cobatched,
                degraded_detected=bool(degraded_detected),
                degraded_windows=int(sum_a["windows"]),
                degraded_rolled_back=bool(degraded_rolled_back),
                degraded_champion_failed=int(failed_a),
                degraded_champion_bitwise=degraded_champion_bitwise,
                healthy_promoted=healthy_promoted,
                promoted_generation=promoted_generation,
                post_promote_bitwise=post_promote_bitwise,
                mirror_faults_injected=mirror_faults_injected,
                mirror_fault_champion_clean=mirror_fault_champion_clean,
                sigkill_champion_bitwise=sigkill_champion_bitwise,
                clean_counters_zero=bool(clean_counters_zero),
                # Extra diagnostics (beyond the SHADOW_SECTION_KEYS floor).
                evaluator=str(sum_a["evaluator"]),
                degraded_champion_metric=sum_a["champion_metric"],
                degraded_challenger_metric=sum_a["challenger_metric"],
                healthy_champion_bitwise=healthy_champion_bitwise,
                mirror_fault_champion_failed=int(failed_b),
            )
        )
    )


def _autopilot_child() -> None:
    """Closed-loop autoscaling certificate (ISSUE 19) on an 8-virtual-
    device mesh. Three drills against live fleets, one JSON line:

      A. a load shift between two tenants — a request burst onto a
         replicated tenant plus cold-row pressure on a two-tier tenant —
         makes the autopilot reshard the hot tenant across the mesh AND
         re-place the two-tier hot set from measured promotion stats,
         with zero failed client requests, bitwise-unchanged answers,
         and a post-reshard p99 inside the probe's regression bound;
      B. an induced HBM squeeze (the fleet budget clamped so pinned
         bytes sit at 0.9 of it) walks the capacity ladder: the coldest
         tenant is demoted to the host tier, and on the next tick the
         reclaimed headroom restores it — answers bitwise through both
         legs, ladder ceiling respected;
      C. a deliberately bad rule (retunes the flush wait to an absurd
         250 ms) is caught by the post-action contract probe, rolled
         back (planner value and registry wait restored), and
         QUARANTINED — its still-screaming signal is suppressed on the
         next tick, and clients never see a changed answer.

    Every decision is journaled; the journal must validate against the
    contracts schemas, and every robustness counter must be zero across
    the clean drills (A and B).
    """
    import shutil
    import tempfile

    import numpy as np
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu import planner
    from photon_ml_tpu.autopilot import (
        Action,
        Autopilot,
        ControlRule,
        hbm_demote_rule,
        hbm_restore_rule,
        rebalance_rule,
        shard_grow_rule,
    )
    from photon_ml_tpu.game.model import (
        Coefficients,
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_ml_tpu.serving import (
        ScoreRequest,
        ServingBundle,
        ServingEngine,
        TenantRegistry,
    )
    from photon_ml_tpu.transformers.game_transformer import (
        CoordinateScoringSpec,
    )
    from photon_ml_tpu.types import TaskType
    from photon_ml_tpu.utils import faults, telemetry
    from photon_ml_tpu.utils.contracts import ROBUSTNESS_CLEAN_ZERO_KEYS

    task = TaskType.LOGISTIC_REGRESSION
    ndev = len(jax.devices())
    faults.install("")
    faults.reset_counters()

    scratch = tempfile.mkdtemp(prefix="photon-autopilot-")
    journal_path = os.path.join(scratch, "journal.jsonl")
    journal = telemetry.RunJournal(journal_path)
    telemetry.install_journal(journal)

    d_fe, d_re, n_ent = 8, 6, 48

    def build_bundle(seed):
        rng = np.random.default_rng(seed)
        w = rng.normal(size=d_fe).astype(np.float32)
        M = np.zeros((n_ent + 1, d_re), np.float32)
        M[:n_ent] = rng.normal(size=(n_ent, d_re))
        model = GameModel(
            {
                "fixed": FixedEffectModel(Coefficients(jnp.asarray(w)), task),
                "per-e": RandomEffectModel(jnp.asarray(M), None, task),
            }
        )
        specs = {
            "fixed": CoordinateScoringSpec(shard="g"),
            "per-e": CoordinateScoringSpec(
                shard="re",
                random_effect_type="eid",
                entity_index={str(i): i for i in range(n_ent)},
            ),
        }
        return ServingBundle.from_model(model, specs, task)

    def requests(seed, n, lo=0, hi=n_ent):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, d_fe)).astype(np.float32)
        Xe = rng.normal(size=(n, d_re)).astype(np.float32)
        ids = rng.integers(lo, hi, size=n)
        return [
            ScoreRequest(
                features={"g": X[i], "re": Xe[i]},
                entity_ids={"eid": str(int(ids[i]))},
                offset=float(i) * 0.125,
                uid=f"r{seed}-{i}",
            )
            for i in range(n)
        ]

    def solo(seed, reqs):
        """Reference answers: the same weights served alone."""
        eng = ServingEngine(build_bundle(seed), max_batch=32)
        with eng:
            out = np.asarray(
                [r.score for r in eng.score_batch(reqs)], np.float64
            )
        return out

    def scores(reg, name, reqs):
        return np.asarray(
            [reg.score(name, r).score for r in reqs], np.float64
        )

    def walls(reg, name, reqs):
        out = []
        for r in reqs:
            t0 = time.monotonic()
            reg.score(name, r)
            out.append(time.monotonic() - t0)
        return np.asarray(out, np.float64)

    def counters_now(name):
        return telemetry.METRICS.snapshot()["counters"].get(name, 0)

    reqs_a = requests(191, 16)
    reqs_b_cold = requests(193, 24, lo=8)  # beyond b's hot set: pressure
    ref_a = solo(1, reqs_a)
    ref_b_cold = solo(2, reqs_b_cold)

    # ---- drill A: load shift -> shard grow + hot-row rebalance ----------
    registry = TenantRegistry(max_batch=32, max_wait_ms=2.0)  # photon-lint: disable=planner-constant — deliberate section config: fixed wait pins the measurement, not a runtime default
    registry.admit("a", build_bundle(1))
    registry.admit("b", build_bundle(2))
    registry.demote("b", hot_rows=8, reason="bench-setup")

    pilot = Autopilot(
        registry,
        rules=[
            shard_grow_rule(fire_above=32.0, rearm_below=4.0),
            rebalance_rule(fire_above=4.0, rearm_below=1.0),
        ],
        cooldown_s=30.0,
        max_actions=4,
        probe_requests={"a": reqs_a[0], "b": reqs_b_cold[0]},
        start=False,
    )

    pilot.tick()  # baseline snapshot: deltas need a `prev`
    base_walls = walls(registry, "a", reqs_a)

    # The shift: a burst onto tenant a, cold-row traffic onto tenant b.
    for r in requests(194, 96):
        registry.score("a", r)
    got_b = scores(registry, "b", reqs_b_cold)

    def promotions():
        t = registry.tenant("b")
        return sum(
            sum(c.store.promotion_stats().values())
            for c in t.engine._state.bundle.coordinates.values()
            if getattr(c, "store", None) is not None
        )

    deadline = time.monotonic() + 30.0  # promote worker is async
    while promotions() < 4 and time.monotonic() < deadline:
        time.sleep(0.05)

    pilot.tick()  # the loop reacts: reshard a, rebalance b
    promotions_seen = int(promotions())

    t_a = registry.tenant("a")
    resharded = any(
        c.mesh is not None
        for c in t_a.engine._state.bundle.coordinates.values()
    )
    post_walls = walls(registry, "a", reqs_a)
    got_a = scores(registry, "a", reqs_a)
    got_b2 = scores(registry, "b", reqs_b_cold)
    pre_p99 = float(np.quantile(base_walls, 0.99))
    post_p99 = float(np.quantile(post_walls, 0.99))
    # Same bound the in-loop contract probe enforces.
    p99_recovered = bool(post_p99 <= max(pre_p99 * 5.0, pre_p99 + 0.05))
    load_shift_bitwise = bool(
        np.array_equal(got_a, ref_a)
        and np.array_equal(got_b, ref_b_cold)
        and np.array_equal(got_b2, ref_b_cold)
    )
    sum_a = pilot.summary()
    pilot.close()

    # ---- drill B: HBM squeeze -> demote, then headroom -> restore -------
    reqs_b2 = requests(195, 8)
    reg2 = TenantRegistry(max_batch=32, max_wait_ms=2.0)  # photon-lint: disable=planner-constant — deliberate section config: fixed wait pins the measurement, not a runtime default
    reg2.admit("a2", build_bundle(3))
    reg2.admit("b2", build_bundle(4))
    ref_b2 = scores(reg2, "b2", reqs_b2)
    _ = scores(reg2, "a2", requests(196, 8))  # a2 most recent: b2 coldest
    used = sum(reg2.tenant(n).device_bytes() for n in ("a2", "b2"))
    # Induce the squeeze: clamp the fleet budget so pinned bytes sit at
    # 0.9 of it — above the demote rule's 0.85 fire band.
    reg2._hbm_budget_override = int(used / 0.9)
    pilot2 = Autopilot(
        reg2,
        rules=[hbm_demote_rule(), hbm_restore_rule()],
        cooldown_s=0.0,
        max_actions=4,
        probe_requests={"b2": reqs_b2[0]},
        start=False,
    )
    pilot2.tick()  # pressure 0.9 -> demote the coldest tenant
    t_b2 = reg2.tenant("b2")
    hbm_demoted = bool(t_b2.demoted)
    mid_b2 = scores(reg2, "b2", reqs_b2)  # host-tier answers, mid-squeeze
    pilot2.tick()  # headroom ~0.55 -> restore under the 0.8 ceiling
    t_b2 = reg2.tenant("b2")
    restored_single_tier = not t_b2.demoted and all(
        getattr(c, "store", None) is None
        for c in t_b2.engine._state.bundle.coordinates.values()
    )
    post_b2 = scores(reg2, "b2", reqs_b2)
    hbm_restored_bitwise = bool(
        restored_single_tier
        and np.array_equal(mid_b2, ref_b2)
        and np.array_equal(post_b2, ref_b2)
    )
    sum_b = pilot2.summary()
    pilot2.close()

    # Clean phase ends here: A and B must not have tripped a single
    # robustness counter (demote/restore ladder actions are *policy*,
    # not failures — they are deliberately not clean-zero keys).
    counters = telemetry.METRICS.snapshot()["counters"]
    clean_counters_zero = all(
        int(counters.get(k, 0)) == 0 for k in ROBUSTNESS_CLEAN_ZERO_KEYS
    )

    # ---- drill C: a bad rule is rolled back and quarantined -------------
    # On the drill-B fleet: its tenants end the ladder single-tier and
    # un-resharded, so they still ride the co-batch path the wait
    # retune governs (a mesh-sharded or demoted tenant dispatches solo
    # through its own batcher and would never feel the bad wait).
    wait_before_ms = reg2.max_wait_s * 1e3
    plan_before = planner.planned_value("serving_max_wait_ms")

    def bad_decide(cur, prev, sig):
        return Action(
            kind="retune",
            params={"serving_max_wait_ms": 250.0},
            evidence={"note": "deliberately absurd flush wait", "sig": sig},
        )

    # Scripted signal: scream, dip below the re-arm band, scream again —
    # the dip re-arms the rule so the third tick exercises the
    # quarantine SUPPRESSION path (a quarantined rule never actuates,
    # however loud its signal).
    sig_script = iter([999.0, 0.0, 999.0])
    bad = ControlRule(
        name="bad-wait-spike",
        signal=lambda cur, prev: next(sig_script),
        fire_above=1.0,
        rearm_below=0.0,
        decide=bad_decide,
    )
    pilot3 = Autopilot(
        reg2,
        rules=[bad],
        cooldown_s=0.0,
        max_actions=4,
        probe_requests={"b2": reqs_b2[0]},
        start=False,
    )
    pilot3.tick()  # applies the 250 ms wait -> probe latency blows up
    plan_after = planner.planned_value("serving_max_wait_ms")
    bad_rule_rolled_back = bool(
        int(counters_now("autopilot_rollbacks")) == 1
        and abs(reg2.max_wait_s * 1e3 - wait_before_ms) < 1e-9
        and plan_after == plan_before
    )
    pilot3.tick()  # calm signal re-arms the (still-quarantined) rule
    pilot3.tick()  # screaming again: quarantine suppresses it
    sum_c = pilot3.summary()
    bad_rule_quarantined = bool(
        bad.quarantined
        and sum_c["last_outcome"] == "suppressed_quarantined"
        and int(counters_now("autopilot_quarantines")) == 1
    )
    post_c = scores(reg2, "b2", reqs_b2)
    bad_rule_client_bitwise = bool(np.array_equal(post_c, ref_b2))
    pilot3.close()

    failed_requests = 0
    for reg in (registry, reg2):
        for tb in reg.metrics()["tenants"].values():
            failed_requests += int(tb["failed"])

    registry.close(release_bundles=True)
    reg2.close(release_bundles=True)

    telemetry.uninstall_journal()
    journal.close()
    _n_ok, errors = telemetry.validate_journal(journal_path)
    with open(journal_path, "r", encoding="utf-8") as fh:
        events = [json.loads(l) for l in fh if l.strip()]
    decisions = [e for e in events if e["type"] == "autopilot_decision"]
    applied = [e for e in decisions if e["outcome"] == "applied"]

    def applied_kind(kind):
        return sum(
            1
            for e in applied
            if (e.get("action") or {}).get("kind") == kind
        )

    reshard_actions = applied_kind("reshard")
    rebalance_actions = applied_kind("rebalance")
    evidenced = all(
        isinstance(e.get("evidence"), dict) and e["evidence"]
        for e in decisions
    )
    shutil.rmtree(scratch, ignore_errors=True)

    print(
        json.dumps(
            dict(
                n_devices=ndev,
                ticks=int(sum_a["ticks"] + sum_b["ticks"] + sum_c["ticks"]),
                load_shift_detected=bool(
                    resharded and reshard_actions >= 1
                ),
                reshard_actions=reshard_actions,
                rebalance_actions=rebalance_actions,
                failed_requests=failed_requests,
                p99_recovered=p99_recovered,
                hbm_demoted=hbm_demoted,
                hbm_restored_bitwise=hbm_restored_bitwise,
                bad_rule_rolled_back=bad_rule_rolled_back,
                bad_rule_quarantined=bad_rule_quarantined,
                decisions_journaled=len(decisions),
                decisions_valid=bool(not errors and evidenced),
                clean_counters_zero=bool(clean_counters_zero),
                # Extra diagnostics (beyond the AUTOPILOT_SECTION_KEYS
                # floor).
                load_shift_bitwise=load_shift_bitwise,
                bad_rule_client_bitwise=bad_rule_client_bitwise,
                pre_p99_ms=pre_p99 * 1e3,
                post_p99_ms=post_p99 * 1e3,
                promotions_seen=promotions_seen,
                journal_errors=errors[:3],
            )
        )
    )


def _child() -> None:
    from photon_ml_tpu.utils import compile_cache

    compile_cache.enable()
    import numpy as np
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.containers import LabeledData, SparseFeatures
    from photon_ml_tpu.data.game_dataset import (
        GameDataset,
        RandomEffectDataConfig,
        build_random_effect_dataset,
    )
    from photon_ml_tpu.game.coordinate import (
        FixedEffectCoordinate,
        RandomEffectCoordinate,
    )
    from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent
    from photon_ml_tpu.ops import pallas_glm
    from photon_ml_tpu.optimize.config import (
        L2,
        CoordinateOptimizationConfig,
        OptimizerConfig,
    )
    from photon_ml_tpu.types import OptimizerType, TaskType

    t_start = time.perf_counter()

    def _mark(msg):
        sys.stderr.write(f"bench: +{time.perf_counter() - t_start:.1f}s {msg}\n")
        sys.stderr.flush()

    platform = jax.devices()[0].platform
    _mark(f"backend up ({platform})")
    # Adaptive runtime planner (ISSUE 14): a repeat round with
    # PHOTON_PLAN_PROFILE pointing at the last round's persisted profile
    # plans this round from it (the scoring section starts calibrated,
    # routing/layout decisions adopt the measured run); topology
    # mismatches refuse loudly rather than mis-plan the round.
    from photon_ml_tpu import planner as _planner_boot

    _ambient_plan = _planner_boot.ensure_ambient_plan()
    if _ambient_plan is not None:
        _mark(
            f"runtime plan installed ({_ambient_plan.source}: "
            f"{len(_ambient_plan.decisions)} decision(s))"
        )
    scale = float(os.environ.get("BENCH_SCALE", "1.0"))
    n = int((1 << 20) * scale)
    d_fixed, d_re = 512, 16
    n_entities = max(64, int(8192 * scale))
    f32 = jnp.float32

    key = jax.random.PRNGKey(0)
    kx, ke, kw, ku, kl = jax.random.split(key, 5)
    Xf = jax.random.normal(kx, (n, d_fixed), f32)
    Xe = jax.random.normal(ke, (n, d_re), f32)
    entity = np.asarray(jax.random.randint(kl, (n,), 0, n_entities))
    w = jax.random.normal(kw, (d_fixed,)) * 0.1
    u = jax.random.normal(ku, (n_entities, d_re)) * 0.5
    margin = Xf @ w + jnp.einsum("nd,nd->n", Xe, u[jnp.asarray(entity)])
    y = (jax.random.uniform(key, (n,)) < jax.nn.sigmoid(margin)).astype(f32)
    jax.block_until_ready(y)
    _mark("synthetic arrays materialized")

    ds = GameDataset.build(
        {"global": Xf, "per_entity": Xe}, y, id_tags={"entityId": entity}
    )
    _mark("GameDataset built")
    red = build_random_effect_dataset(
        ds,
        RandomEffectDataConfig(
            "entityId", "per_entity", active_upper_bound=128, min_bucket=32
        ),
    )
    _mark("RandomEffectDataset built")
    cfg_f = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=40, tolerance=1e-8),
        regularization=L2,
        reg_weight=1.0,
    )
    cfg_r = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=20, tolerance=1e-7),
        regularization=L2,
        reg_weight=10.0,
    )
    fixed = FixedEffectCoordinate(ds, "global", cfg_f, TaskType.LOGISTIC_REGRESSION)
    _mark(f"FixedEffectCoordinate built (dispatch={fixed._use_pallas!r})")
    rand = RandomEffectCoordinate(ds, red, cfg_r, TaskType.LOGISTIC_REGRESSION)
    _mark("RandomEffectCoordinate built")
    coords = {"fixed": fixed, "per-entity": rand}
    variants = {}

    def _force(out) -> float:
        """Round-trip a combining scalar to the host: completion is proven
        by fetching a value computed from every output leaf."""
        leaves = [x for x in jax.tree_util.tree_leaves(out) if hasattr(x, "dtype")]
        if not leaves:
            return 0.0
        return float(_force_sum(tuple(jnp.sum(x) for x in leaves)))

    @jax.jit
    def _force_sum(parts):
        return sum(parts[1:], parts[0])

    # The force step costs one tiny dispatch + one scalar fetch; measure that
    # overhead on a trivial program and subtract it from every wall.
    def _measure_rtt() -> float:
        ts = []
        for i in range(5):
            t0 = time.perf_counter()
            _force(jnp.ones(4) * float(i + 1))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    _force(jnp.ones(2))  # compile the force path before measuring it
    rtt = _measure_rtt()
    _mark(f"scalar round-trip overhead {rtt*1e3:.0f} ms (subtracted from walls)")

    def timed(fn, label="", warm=None):
        # Warm-up runs a PERTURBED-input call: the execution layer may cache
        # results for bit-identical repeat invocations, which would flatter
        # a timed-equals-warm-up protocol.
        t_c = time.perf_counter()
        _force((warm or fn)())  # warm-up/compile
        sys.stderr.write(f"bench: {label} warm-up {time.perf_counter() - t_c:.1f}s\n")
        sys.stderr.flush()
        t0 = time.perf_counter()
        out = fn()
        _force(out)
        return max(time.perf_counter() - t0 - rtt, 1e-9), out

    offsets_warm = ds.offsets + jnp.float32(1e-3)

    sys.stderr.write(f"bench: data built n={n}\n")
    sys.stderr.flush()

    # ---- primary: full GLMix coordinate-descent pass ----------------------
    # Warm-up uses perturbed reg weights (traced scalars: same compiled
    # programs, different numerics) so the timed pass is not bit-identical.
    glmix_wall, _ = timed(
        lambda: run_coordinate_descent(coords, 1).model["fixed"].coefficients.means,
        "glmix",
        warm=lambda: run_coordinate_descent(
            coords, 1, reg_weights={"fixed": 1.001, "per-entity": 10.001}
        ).model["fixed"].coefficients.means,
    )

    # ---- dense fixed-effect LBFGS (the aggregator hot loop) ---------------
    kernel_mode = fixed._use_pallas
    dense_wall, res_lbfgs = timed(lambda: fixed.train(ds.offsets)[1], "dense_lbfgs", warm=lambda: fixed.train(offsets_warm)[1])
    stats = _solve_stats(res_lbfgs)
    passes_per_eval = 1 if kernel_mode is not False else 2
    dense_bytes = stats["fn_evals"] * n * d_fixed * 4 * passes_per_eval
    variants["dense_lbfgs"] = dict(
        stats,
        wall_s=round(dense_wall, 3),
        kernel_engaged=kernel_mode is not False,
        dispatch=_dispatch_json(kernel_mode),
        **_bw_metrics(dense_bytes, dense_wall, platform),
    )

    # ---- dense TRON (Hessian-vector path) ---------------------------------
    cfg_t = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(OptimizerType.TRON, 15, 1e-6),
        regularization=L2,
        reg_weight=1.0,
    )
    tron_coord = FixedEffectCoordinate(ds, "global", cfg_t, TaskType.LOGISTIC_REGRESSION)
    tron_wall, res_tron = timed(lambda: tron_coord.train(ds.offsets)[1], "dense_tron", warm=lambda: tron_coord.train(offsets_warm)[1])
    tstats = _solve_stats(res_tron)
    tron_bytes = tstats["fn_evals"] * n * d_fixed * 4 * passes_per_eval
    variants["dense_tron"] = dict(
        tstats,
        wall_s=round(tron_wall, 3),
        kernel_engaged=tron_coord._use_pallas is not False,
        **_bw_metrics(tron_bytes, tron_wall, platform),
    )

    # ---- sparse-ELL LBFGS (the wide-sparse ingest shape) ------------------
    # Production-shaped pipeline: the data lives on HOST (as after Avro
    # ingest), the dataset carries the host-COO stash, and the coordinate
    # packs the bucketed layout straight from it — host counting-sort pack
    # plus ONE upload of the packed arrays; no device ELL round trip (the
    # r03 bench measured that pull-back at 64-124 s and the verdict flagged
    # it; the fix is pipeline placement, not a faster pack).
    from photon_ml_tpu.data.bucketed import BucketedSparseFeatures

    k_nnz, d_sparse = 64, 16384
    rng_sp = np.random.default_rng(11)
    sp_idx_np = rng_sp.integers(0, d_sparse, size=(n, k_nnz)).astype(np.int32)
    sp_val_np = rng_sp.normal(size=(n, k_nnz)).astype(np.float32)
    # Host-resident ELL container: the sparse coordinate trains on the
    # bucketed layout, so the ELL arrays are never uploaded here.
    sp = SparseFeatures(sp_idx_np, sp_val_np, d_sparse)
    ds_sp = GameDataset.build({"s": sp}, y)
    from photon_ml_tpu.data.game_dataset import HostCSR

    coo_cols = sp_idx_np.reshape(-1).astype(np.int64)
    coo_vals = sp_val_np.reshape(-1)
    ds_sp.host_csr["s"] = HostCSR(
        np.arange(n + 1, dtype=np.int64) * k_nnz, coo_cols, coo_vals, d_sparse
    )

    # Data-plane pack, as ingest runs it: begin_pack_async starts the host
    # counting sort on a background thread at stash time; here nothing
    # overlaps it (production ingest overlaps the remaining assembly), so
    # join it under the ingest-side accounting. Coordinate construction
    # below then pays only the device upload (pack_s).
    from photon_ml_tpu.data import device_pack as device_pack_mod
    from photon_ml_tpu.ops import pallas_sparse as pallas_sparse_mod
    from photon_ml_tpu.utils.observability import (
        TimingRegistry as _TReg,
        stage_scope as _sscope,
    )

    pack_reg = _TReg()
    t_pack = time.perf_counter()
    with _sscope(pack_reg):
        pallas_sparse_mod.begin_pack_async(ds_sp.host_csr["s"], n)
    fut = getattr(ds_sp.host_csr["s"], "pack_future", None)
    # No future has more than one cause — distinguish them in the artifact
    # (a deferral and a declined pack are different stories):
    # "background" = bg thread ran and was joined here; "device" = the
    # device pack runs inside coordinate construction below (no host
    # thread exists to hide); "deferred_*" = the host pack runs
    # synchronously inside coordinate construction below and lands in
    # pack_s; "not_engaged" = the size/backend gates declined before the
    # pipeline gate.
    if fut is not None:
        fut.result()
        pack_mode = "background"
    elif not pallas_sparse_mod.pack_worth_considering(n):
        pack_mode = "not_engaged"
    elif device_pack_mod.enabled():
        pack_mode = "device"
    else:
        from photon_ml_tpu.data.pipeline import effective_host_parallelism

        pack_mode = (
            "deferred_1core"
            if effective_host_parallelism() <= 1
            else "deferred_pipeline_off"
        )
    pack_ingest_s = time.perf_counter() - t_pack
    _mark(f"ingest-side pack {pack_ingest_s:.2f}s ({pack_mode})")

    t_pack = time.perf_counter()
    with _sscope(pack_reg):
        sp_coord = FixedEffectCoordinate(
            ds_sp,
            "s",
            CoordinateOptimizationConfig(
                optimizer=OptimizerConfig(max_iterations=20, tolerance=1e-7),
                regularization=L2,
                reg_weight=1.0,
            ),
            TaskType.LOGISTIC_REGRESSION,
        )
    pack_s = time.perf_counter() - t_pack
    sparse_kernel = isinstance(sp_coord._features, BucketedSparseFeatures)
    # Placement split + path: pack_device/pack_host stage walls recorded by
    # the pack itself (data/bucketed._pack_level); the ingest-side join wall
    # counts as host placement when a background host thread ran (the
    # worker thread records into no scope).
    pack_path = pack_reg.get_note("pack_path") or "none"
    pack_device_s = pack_reg.get("pack_device")
    pack_host_s = pack_reg.get("pack_host")
    if pack_mode == "background":
        pack_host_s = max(pack_host_s, pack_ingest_s)
    _mark(f"sparse coordinate built (bucketed={sparse_kernel}, {pack_s:.1f}s, path={pack_path})")
    sp_wall, res_sp = timed(lambda: sp_coord.train(ds_sp.offsets)[1], "sparse_ell", warm=lambda: sp_coord.train(offsets_warm)[1])
    sstats = _solve_stats(res_sp)
    # Work-normalized bytes per objective evaluation: the ELL entry bytes
    # (indices+values) counted once per direction — the same formula r02
    # used for the XLA path, kept so achieved_gb_per_s is comparable across
    # rounds regardless of which kernel (fused single-stream, composed
    # two-stream, or XLA gather/scatter) actually ran.
    pack_report = (
        sp_coord._features.density_report() if sparse_kernel else None
    )
    # Per-path roofline annotations: which objective kernel actually runs
    # (fused single-stream / composed matvec+rmatvec / XLA gather-scatter),
    # which layout each level carries, and — when the device pack ran — the
    # pack's own achieved bandwidth against the same HBM roofline (the
    # device pack streams ~12 B/entry of COO planes + the packed writes).
    objective_path = "xla"
    layout = None
    if sparse_kernel:
        bf = sp_coord._features
        if pallas_sparse_mod.should_use(bf):
            objective_path = (
                "fused"
                if pallas_sparse_mod.fused_feasible(bf)
                else "composed"
            )
        layout = dict(
            level1="row_aligned" if bf.level1.row_aligned else "grouped",
            level2=(
                None
                if bf.level2 is None
                else ("row_aligned" if bf.level2.row_aligned else "grouped")
            ),
        )
    pack_metrics = dict(
        pack_s=round(pack_s, 1),
        pack_ingest_s=round(pack_ingest_s, 2),
        pack_device_s=round(pack_device_s, 3),
        pack_host_s=round(pack_host_s, 2),
        pack_path=pack_path,
        pack_mode=pack_mode,
    )
    if pack_device_s > 0:
        pack_metrics["device_pack_bw"] = _bw_metrics(
            n * k_nnz * 12, max(pack_device_s, 1e-9), platform
        )
    sp_bytes = sstats["fn_evals"] * n * k_nnz * 8 * 2
    variants["sparse_ell_lbfgs"] = dict(
        sstats,
        nnz_per_row=k_nnz,
        dim=d_sparse,
        wall_s=round(sp_wall, 3),
        kernel_engaged=sparse_kernel,
        objective_path=objective_path,
        layout=layout,
        pack_report=pack_report,
        **pack_metrics,
        **_bw_metrics(sp_bytes, sp_wall, platform),
    )

    # ---- scoring throughput (GameTransformer margins + link) --------------
    # X passed as an ARGUMENT (a closure capture would lower the 2 GB design
    # matrix as a program constant and ship it with the executable). The
    # pass repeats SCORE_REPS times inside one jit via lax.scan so a single
    # host dispatch round-trip does not dominate a milliseconds-scale
    # computation; each repetition perturbs the coefficients so no pass is
    # foldable into another.
    # The rep count ADAPTS until the rtt correction is <5% of the measured
    # wall (VERDICT r05 weak #6: at 64 reps / 2.4 ms-per-pass the rtt
    # subtraction dominated and the artifact printed 911 GB/s — above the
    # chip's HBM peak). The START count is a planned quantity (ISSUE 14):
    # a prior round's profile carries its calibrated rep count
    # (dispatch["bench_score_reps"], written by the e2e section below), so
    # a repeat round with PHOTON_PLAN_PROFILE set begins calibrated and
    # skips the doubling ladder; cold rounds start at the default (host
    # dispatch jitter can exceed an 8-rep wall). Cap at 1024 so a slow
    # backend bounds compile count; the <5% contract is re-verified
    # either way — a stale planned count that no longer meets it resumes
    # adapting instead of shipping a bad artifact.
    from photon_ml_tpu import planner as _planner

    # Clamp to [1, 1024]: a degenerate planned count must not stall the
    # doubling ladder (0 * 2 == 0 loops forever) and a corrupt profile's
    # huge count must not dispatch an unbounded scan — 1024 is the same
    # cap the adaptation loop below enforces.
    score_reps = min(max(1, int(_planner.planned_value("bench_score_reps"))), 1024)
    _plan_now = _planner.current_plan()
    reps_from_plan = (
        _plan_now is not None and "bench_score_reps" in _plan_now.decisions
    )
    while True:

        @functools.partial(jax.jit, static_argnames=("reps",))
        def score(features, offsets, wv, reps):
            def one(carry, i):
                s = jax.nn.sigmoid(features @ (wv + i * 1e-6) + offsets)
                # Full reduction keeps every row live — a single-element
                # reduce would let XLA slice-sink the pass down to one row.
                return carry + jnp.sum(s), None

            total, _ = jax.lax.scan(
                one, jnp.zeros((), jnp.float32), jnp.arange(reps, dtype=jnp.float32)
            )
            return total

        score_wall_total, _ = timed(
            lambda: score(Xf, ds.offsets, res_lbfgs.coefficients, score_reps),
            f"scoring x{score_reps}",
            warm=lambda: score(Xf, offsets_warm, res_lbfgs.coefficients, score_reps),
        )
        rtt_fraction = rtt / max(score_wall_total + rtt, 1e-9)
        if rtt_fraction < 0.05 or score_reps >= 1024:
            break
        score_reps *= 2
    score_wall = score_wall_total / score_reps
    score_bytes = n * d_fixed * 4
    variants["scoring"] = dict(
        wall_s=round(score_wall, 4),
        samples_per_s=round(n / score_wall, 1),
        reps=score_reps,
        reps_from_plan=reps_from_plan,
        rtt_fraction=round(rtt_fraction, 4),
        **_bw_metrics(score_bytes, score_wall, platform),
    )

    # ---- sweep: pod-parallel hyperparameter search (ISSUE 12) -------------
    # A 16-trial Bayesian sweep through the batched trial executor
    # (trial-stacked: each proposal round is ONE XLA dispatch) against the
    # serial per-trial baseline — the GameTrainingDriver-inherited loop
    # cli/train.py still runs for tuning: one full estimator.fit per
    # observation. The shape is the dispatch-bound AutoML regime the
    # executor targets (many small fits swept over configs); it is fixed,
    # not BENCH_SCALE-scaled, because the measurement is overhead
    # amortization, not throughput. Proposal (GP fit + qEI picks) is
    # identical host work in both drivers and is reported separately;
    # `speedup_vs_serial` compares TRIAL-EVALUATION walls on the same 16
    # candidate points (speedup_basis names this). Same loud missing-key
    # contract as every other section, plus the clean-run zero robustness
    # counters.
    try:
        import dataclasses as _dc

        from photon_ml_tpu.data.game_dataset import FixedEffectDataConfig
        from photon_ml_tpu.estimators.game_estimator import GameEstimator
        from photon_ml_tpu.hyperparameter import (
            HyperparameterConfig,
            HyperparameterTuningMode,
            get_tuner,
        )
        from photon_ml_tpu.utils import faults as _faults_sw
        from photon_ml_tpu.utils.contracts import (
            ROBUSTNESS_CLEAN_ZERO_KEYS,
            SWEEP_SECTION_KEYS,
            SWEEP_TRIAL_KEYS,
        )

        n_sw, e_sw, nval_sw = 768, 64, 256
        d_fsw, d_resw = 12, 4

        def _sweep_data(n_rows, seed):
            r = np.random.default_rng(seed)
            ent = r.integers(0, e_sw, size=n_rows)
            Xfs = r.normal(size=(n_rows, d_fsw)).astype(np.float32)
            Xes = r.normal(size=(n_rows, d_resw)).astype(np.float32)
            wt = r.normal(size=d_fsw).astype(np.float32)
            ut = r.normal(size=(e_sw, d_resw)).astype(np.float32)
            mg = Xfs @ wt + np.einsum("nd,nd->n", Xes, ut[ent])
            ys = (r.uniform(size=n_rows) < 1 / (1 + np.exp(-mg))).astype(
                np.float32
            )
            return GameDataset.build(
                {"g": jnp.asarray(Xfs), "e": jnp.asarray(Xes)},
                ys,
                id_tags={"entityId": ent},
            )

        ds_sw = _sweep_data(n_sw, 31)
        val_sw = _sweep_data(nval_sw, 37)
        base_sw = {
            "fixed": CoordinateOptimizationConfig(
                optimizer=OptimizerConfig(max_iterations=12, tolerance=1e-7),
                regularization=L2,
                reg_weight=1.0,
            ),
            "per-entity": CoordinateOptimizationConfig(
                optimizer=OptimizerConfig(max_iterations=8, tolerance=1e-7),
                regularization=L2,
                reg_weight=1.0,
            ),
        }
        est_sw = GameEstimator(
            TaskType.LOGISTIC_REGRESSION,
            {
                "fixed": FixedEffectDataConfig("g"),
                "per-entity": RandomEffectDataConfig(
                    "entityId", "e", min_bucket=16
                ),
            },
            seed=7,
        )
        executor = est_sw.sweep_executor(
            ds_sw, val_sw, base_sw, mode="stacked", max_stack=8
        )
        dims_sw = [
            HyperparameterConfig("fixed", 1e-3, 1e3, transform="LOG"),
            HyperparameterConfig("per-entity", 1e-3, 1e3, transform="LOG"),
        ]
        # Warm-up: compile the cold + warm-started round programs on
        # throwaway candidates, then reset trial state (programs survive).
        rng_sw = np.random.default_rng(41)
        warm_sw = 10 ** rng_sw.uniform(-3, 3, size=(8, 2))
        executor.evaluate_batch(warm_sw)
        executor.evaluate_batch(warm_sw)
        executor.reset()
        _mark("sweep executor warm (round programs compiled)")

        rob_base_sw = {
            k: _faults_sw.COUNTERS.get(k) for k in ROBUSTNESS_CLEAN_ZERO_KEYS
        }
        tuner_sw = get_tuner(HyperparameterTuningMode.BAYESIAN)
        t_sw = time.perf_counter()
        _search_sw, sweep_res = tuner_sw.sweep(
            16,
            dims_sw,
            HyperparameterTuningMode.BAYESIAN,
            executor,
            seed=11,
            batch_size=8,
        )
        sweep_wall = time.perf_counter() - t_sw
        eval_wall = sum(t.seconds for t in sweep_res.trials)
        _mark(
            f"sweep: 16 trials in {sweep_wall:.2f}s "
            f"(trial-eval {eval_wall:.3f}s)"
        )

        # Serial baseline: the same 16 candidate points, each one full
        # estimator.fit (coordinate descent + validation evaluation) — the
        # pre-ISSUE-12 tuning path. Warmed by the executor's serial-shaped
        # programs above; first fit additionally warms the transformer
        # evaluation path before timing.
        def _fit_trial(point):
            cfgs_t = {
                "fixed": _dc.replace(
                    base_sw["fixed"], reg_weight=float(point[0])
                ),
                "per-entity": _dc.replace(
                    base_sw["per-entity"], reg_weight=float(point[1])
                ),
            }
            return est_sw.fit(ds_sw, val_sw, [cfgs_t])[0]

        _fit_trial(warm_sw[0])
        t_serial = time.perf_counter()
        for rec in sweep_res.trials:
            _fit_trial(rec.point)
        serial_wall = time.perf_counter() - t_serial

        # Winner parity: the sweep's cold-refit winner model must be
        # bitwise-equal to a standalone fit of the winning configuration.
        winner_cfg = {
            "fixed": _dc.replace(
                base_sw["fixed"], reg_weight=float(sweep_res.best_point[0])
            ),
            "per-entity": _dc.replace(
                base_sw["per-entity"],
                reg_weight=float(sweep_res.best_point[1]),
            ),
        }
        standalone = est_sw.fit(ds_sw, val_sw, [winner_cfg])[0]
        winner_bitwise = bool(
            np.array_equal(
                np.asarray(
                    sweep_res.winner_model["fixed"].coefficients.means
                ),
                np.asarray(standalone.model["fixed"].coefficients.means),
            )
            and np.array_equal(
                np.asarray(
                    sweep_res.winner_model["per-entity"].coefficients_matrix
                ),
                np.asarray(
                    standalone.model["per-entity"].coefficients_matrix
                ),
            )
        )
        rob_sw = {
            k: _faults_sw.COUNTERS.get(k) - rob_base_sw[k]
            for k in ROBUSTNESS_CLEAN_ZERO_KEYS
        }
        rob_sw["diverged_steps"] = sum(
            t.diverged_steps for t in sweep_res.trials
        )
        sweep_section = dict(
            shape=dict(
                n_samples=n_sw,
                n_validation=nval_sw,
                n_entities=e_sw,
                d_fixed=d_fsw,
                d_re=d_resw,
            ),
            trials=len(sweep_res.trials),
            rounds=executor.rounds,
            batch_size=8,
            modes=sorted({t.mode for t in sweep_res.trials}),
            stack_decisions=sweep_res.stack_decisions,
            trial_timings=[t.timing_entry() for t in sweep_res.trials],
            sweep_wall_s=round(sweep_wall, 3),
            trial_eval_wall_s=round(eval_wall, 4),
            proposal_wall_s=round(
                max(0.0, sweep_wall - eval_wall - sweep_res.winner_refit_s),
                3,
            ),
            winner_refit_s=round(sweep_res.winner_refit_s, 3),
            serial_baseline_wall_s=round(serial_wall, 3),
            speedup_vs_serial=round(serial_wall / max(eval_wall, 1e-9), 1),
            speedup_basis=(
                "trial-evaluation walls on the SAME 16 candidate points: "
                "stacked executor rounds vs one full estimator.fit per "
                "point (the GameTrainingDriver-inherited serial loop); "
                "proposal (GP fit + qEI picks) is identical host work in "
                "both drivers and reported as proposal_wall_s"
            ),
            best_point=[float(v) for v in sweep_res.best_point],
            winner_value=float(sweep_res.winner_value),
            winner_bitwise_vs_standalone=winner_bitwise,
            robustness=rob_sw,
        )
        missing_sw = [
            k for k in SWEEP_SECTION_KEYS if sweep_section.get(k) is None
        ]
        missing_sw += [
            f"trial:{k}"
            for k in SWEEP_TRIAL_KEYS
            for t in sweep_section["trial_timings"]
            if k not in t
        ]
        if missing_sw:
            raise RuntimeError(
                f"sweep section is missing keys {missing_sw} — the "
                "pod-parallel sweep contract regressed"
            )
        if not winner_bitwise:
            raise RuntimeError(
                "sweep winner refit is not bitwise-equal to the standalone "
                "fit of the winning config — parity regression"
            )
        if any(v != 0 for v in rob_sw.values()):
            raise RuntimeError(
                f"clean sweep run reported nonzero robustness events "
                f"{rob_sw} — robustness regression"
            )
        variants["sweep"] = sweep_section
        if sweep_section["speedup_vs_serial"] < 10.0:
            _mark(
                "sweep WARNING: trial-stacked speedup "
                f"{sweep_section['speedup_vs_serial']}x is below the 10x "
                "target (dispatch-bound backends amortize far more; on a "
                "contended CPU host this is a measurement-noise signal)"
            )
        _mark(
            f"sweep measured ({sweep_section['speedup_vs_serial']}x vs "
            f"serial trials, winner bitwise={winner_bitwise})"
        )
    except Exception as e:  # noqa: BLE001 - the artifact reports the failure
        variants["sweep"] = dict(error=repr(e))
        _mark(f"sweep section FAILED: {e!r}")

    # ---- planner: profile-driven adaptive-runtime certificate (ISSUE 14) --
    # A pilot GLMix fit's persisted profile plans a second, planner-on fit
    # of the same job. Contract: the planned fit is no slower end-to-end
    # than the hand-tuned default (every decision either adopts what the
    # pilot measured or moves a bitwise-neutral quantity), the two models
    # are bitwise-equal, the plan block round-trips through
    # write_profile/read_profile unchanged, and a topology-mutated profile
    # refuses loudly naming the field. Walls are min-of-2 on warmed
    # programs so a contended host's jitter cannot fail a true ≤.
    try:
        import tempfile

        from photon_ml_tpu import planner as _pl
        from photon_ml_tpu.data.game_dataset import (
            FixedEffectDataConfig as _FEC_pl,
            RandomEffectDataConfig as _REC_pl,
        )
        from photon_ml_tpu.estimators.game_estimator import (
            GameEstimator as _Est_pl,
        )
        from photon_ml_tpu.utils import telemetry as _tel_pl
        from photon_ml_tpu.utils.contracts import PLANNER_SECTION_KEYS

        n_pl, e_pl = 32768, 256
        d_fpl, d_repl = 16, 4

        def _pl_data(seed):
            r = np.random.default_rng(seed)
            ent = r.integers(0, e_pl, size=n_pl)
            Xf_ = r.normal(size=(n_pl, d_fpl)).astype(np.float32)
            Xe_ = r.normal(size=(n_pl, d_repl)).astype(np.float32)
            wt = r.normal(size=d_fpl).astype(np.float32)
            ut = r.normal(size=(e_pl, d_repl)).astype(np.float32)
            mg = Xf_ @ wt + np.einsum("nd,nd->n", Xe_, ut[ent])
            ys = (r.uniform(size=n_pl) < 1 / (1 + np.exp(-mg))).astype(
                np.float32
            )
            return GameDataset.build(
                {"g": jnp.asarray(Xf_), "e": jnp.asarray(Xe_)},
                ys,
                id_tags={"entityId": ent},
            )

        cfgs_pl = {
            "fixed": CoordinateOptimizationConfig(
                optimizer=OptimizerConfig(max_iterations=12, tolerance=1e-7),
                regularization=L2,
                reg_weight=1.0,
            ),
            "per-entity": CoordinateOptimizationConfig(
                optimizer=OptimizerConfig(max_iterations=8, tolerance=1e-7),
                regularization=L2,
                reg_weight=10.0,
            ),
        }

        def _pl_fit():
            est_pl = _Est_pl(
                TaskType.LOGISTIC_REGRESSION,
                {
                    "fixed": _FEC_pl("g"),
                    "per-entity": _REC_pl("entityId", "e", min_bucket=16),
                },
                seed=7,
            )
            ds_pl = _pl_data(51)
            t0_pl = time.perf_counter()
            res_pl = est_pl.fit(ds_pl, None, [cfgs_pl])
            return est_pl, res_pl[0], time.perf_counter() - t0_pl

        # The pilot must measure the hand-tuned DEFAULT config: stash any
        # round-ambient plan (PHOTON_PLAN_PROFILE) and restore it after,
        # and run the pilot fits under plan_suppressed() — without it the
        # estimator's own ensure_ambient_plan would quietly re-install a
        # plan from the still-set env and the certificate would compare
        # planned-vs-planned.
        _had_plan = _pl.current_plan()
        if _had_plan is not None:
            _pl.uninstall_plan()
        try:
            with _pl.plan_suppressed():
                _pl_fit()  # warm: compile every program both runs dispatch
                est_a, res_a, wall_a1 = _pl_fit()
                _, _, wall_a2 = _pl_fit()
            wall_a = min(wall_a1, wall_a2)
            prof_pl = est_a.run_profile()
            with tempfile.TemporaryDirectory() as td_pl:
                path_pl = os.path.join(td_pl, "profile.json")
                plan_pl = _pl.plan_from_profile(
                    _tel_pl.read_profile(
                        _tel_pl.write_profile(path_pl, prof_pl), kind="fit"
                    ),
                    path_pl,
                )
                _pl.install_plan(plan_pl)
                try:
                    est_b, res_b, wall_b1 = _pl_fit()
                    _, _, wall_b2 = _pl_fit()
                    wall_b = min(wall_b1, wall_b2)
                    plan_block_b = dict(est_b.fit_timing["plan"])
                    # Round trip: the planned run's profile carries its
                    # plan block and re-reads through the loud contract
                    # unchanged.
                    back_b = _tel_pl.read_profile(
                        _tel_pl.write_profile(
                            os.path.join(td_pl, "planned.json"),
                            est_b.run_profile(),
                        ),
                        kind="fit",
                    )
                    roundtrip_ok = back_b.get("plan") == plan_block_b
                finally:
                    _pl.uninstall_plan()
            # Topology guard: the same profile claiming a different
            # device count must refuse, naming the field.
            bad_topo = dict(prof_pl)
            bad_topo["device_topology"] = dict(prof_pl["device_topology"])
            bad_topo["device_topology"]["device_count"] = (
                int(prof_pl["device_topology"]["device_count"]) + 7
            )
            try:
                _pl.plan_from_profile(bad_topo)
                topo_ok = False
            except _pl.PlanTopologyError as te_pl:
                topo_ok = "device_count" in str(te_pl)
        finally:
            if _had_plan is not None:
                _pl.install_plan(_had_plan)

        pl_bitwise = bool(
            np.array_equal(
                np.asarray(res_a.model["fixed"].coefficients.means),
                np.asarray(res_b.model["fixed"].coefficients.means),
            )
            and np.array_equal(
                np.asarray(res_a.model["per-entity"].coefficients_matrix),
                np.asarray(res_b.model["per-entity"].coefficients_matrix),
            )
        )
        planner_section = dict(
            shape=dict(
                n_samples=n_pl, n_entities=e_pl, d_fixed=d_fpl, d_re=d_repl
            ),
            default_wall_s=round(wall_a, 3),
            planned_wall_s=round(wall_b, 3),
            wall_ratio=round(wall_b / max(wall_a, 1e-9), 3),
            decisions={
                k: d.value for k, d in sorted(plan_pl.decisions.items())
            },
            sources={
                k: d.source for k, d in sorted(plan_pl.decisions.items())
            },
            plan_vs_default_bitwise=pl_bitwise,
            profile_roundtrip_ok=bool(roundtrip_ok),
            topology_guard_ok=bool(topo_ok),
        )
        missing_pl = [
            k for k in PLANNER_SECTION_KEYS if planner_section.get(k) is None
        ]
        if missing_pl:
            raise RuntimeError(
                f"planner section is missing keys {missing_pl} — the "
                "adaptive-planner contract regressed"
            )
        if not (pl_bitwise and roundtrip_ok and topo_ok):
            raise RuntimeError(
                "planner certificate failed: "
                f"bitwise={pl_bitwise} roundtrip={roundtrip_ok} "
                f"topology_guard={topo_ok}"
            )
        if planner_section["wall_ratio"] > 1.1:
            raise RuntimeError(
                "planner-chosen config is slower than the hand-tuned "
                f"default ({planner_section['wall_ratio']}x) — the plan "
                "must never lose to the constants it replaces"
            )
        variants["planner"] = planner_section
        _mark(
            f"planner measured (default {wall_a:.2f}s vs planned "
            f"{wall_b:.2f}s, bitwise={pl_bitwise}, "
            f"{len(plan_pl.decisions)} decision(s))"
        )
    except Exception as e:  # noqa: BLE001 - the artifact reports the failure
        variants["planner"] = dict(error=repr(e))
        _mark(f"planner section FAILED: {e!r}")

    # ---- multichip: entity-sharded pod-scale path -------------------------
    # Own subprocess on the 8-virtual-device CPU mesh (this child's backend
    # is already up, and the TPU path must not be disturbed): an RE matrix
    # sized past one virtual device's budget trains through the sharded
    # scan sweep and serves through the sharded bundle; per-batch wall +
    # analytic collective bytes reported, overlap parity asserted. Same
    # loud missing-key contract as every other section.
    try:
        env_mc = dict(os.environ)
        env_mc["JAX_PLATFORMS"] = "cpu"
        flags_mc = env_mc.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags_mc:
            env_mc["XLA_FLAGS"] = (
                flags_mc + " --xla_force_host_platform_device_count=8"
            ).strip()
        out_mc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), _MULTICHIP_CHILD],
            capture_output=True,
            text=True,
            timeout=600,
            env=env_mc,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        line_mc = next(
            (l for l in out_mc.stdout.splitlines() if l.startswith("{")), None
        )
        if line_mc is None:
            raise RuntimeError(
                f"multichip child produced no JSON: {out_mc.stderr[-1500:]}"
            )
        mc = json.loads(line_mc)
        from photon_ml_tpu.utils.contracts import MULTICHIP_SECTION_KEYS

        missing_mc = [k for k in MULTICHIP_SECTION_KEYS if mc.get(k) is None]
        if missing_mc:
            raise RuntimeError(
                f"multichip section is missing keys {missing_mc} — the "
                "pod-scale metrics contract is broken"
            )
        if mc["re_matrix_bytes"] <= mc["budget_bytes_per_device"]:
            raise RuntimeError(
                "multichip RE matrix fits one device's budget "
                f"({mc['re_matrix_bytes']} <= {mc['budget_bytes_per_device']}) "
                "— the over-HBM certificate measured nothing"
            )
        if mc["max_shard_bytes"] > mc["budget_bytes_per_device"]:
            raise RuntimeError(
                f"per-shard residency {mc['max_shard_bytes']} B exceeds the "
                f"{mc['budget_bytes_per_device']} B virtual budget — sharding "
                "is not bounding per-device memory"
            )
        if not (
            mc["serve_bitwise_vs_replicated"]
            and mc["overlap_serve_sharded_bitwise"]
            and mc["overlap_serve_two_tier_bitwise"]
        ):
            raise RuntimeError(
                "sharded/two-tier serving is not bitwise-equal to the "
                f"single-device path: {mc}"
            )
        if mc["overlap_train_max_rel_dw"] > 5e-3:
            raise RuntimeError(
                "sharded-vs-single-device training diverged beyond f32 "
                f"reduction-order tolerance: {mc['overlap_train_max_rel_dw']}"
            )
        variants["multichip"] = mc
        _mark(
            f"multichip measured ({mc['re_matrix_bytes']} B matrix over "
            f"{mc['n_devices']} devices, {mc['per_batch_wall_ms']} ms/batch, "
            f"{mc['collective_bytes_per_batch']} B/batch collective)"
        )
    except Exception as exc:  # noqa: BLE001 - bench must still print a line
        import traceback

        traceback.print_exc(file=sys.stderr)
        variants["multichip"] = dict(
            failed=True, reason=f"{type(exc).__name__}: {exc}"
        )

    # ---- chaos multichip: pod-scale failure domains under armed faults ----
    # Own 8-virtual-device subprocess with EVERY mesh fault site armed
    # (PHOTON_FAULTS) and the hang watchdog on: the contract asserts zero
    # failed requests, zero hangs, and bitwise train/resume/serve parity
    # through the degradations — the pod-scale analogue of the PR 5
    # serving_overload gate.
    try:
        env_cm = dict(os.environ)
        env_cm["JAX_PLATFORMS"] = "cpu"
        flags_cm = env_cm.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags_cm:
            env_cm["XLA_FLAGS"] = (
                flags_cm + " --xla_force_host_platform_device_count=8"
            ).strip()
        env_cm["PHOTON_FAULTS"] = (
            "collective:1,shard_upload:1,promote:1,resume_load:1"
        )
        env_cm["PHOTON_WATCHDOG_MS"] = "30000"
        env_cm["PHOTON_RETRY_BASE_DELAY_S"] = "0.01"
        out_cm = subprocess.run(
            [sys.executable, os.path.abspath(__file__), _CHAOS_MULTICHIP_CHILD],
            capture_output=True,
            text=True,
            timeout=600,
            env=env_cm,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        line_cm = next(
            (l for l in out_cm.stdout.splitlines() if l.startswith("{")), None
        )
        if line_cm is None:
            raise RuntimeError(
                f"chaos_multichip child produced no JSON: {out_cm.stderr[-1500:]}"
            )
        cm = json.loads(line_cm)
        from photon_ml_tpu.utils.contracts import (
            CHAOS_MULTICHIP_SECTION_KEYS,
        )

        missing_cm = [
            k for k in CHAOS_MULTICHIP_SECTION_KEYS if cm.get(k) is None
        ]
        if missing_cm:
            raise RuntimeError(
                f"chaos_multichip section is missing keys {missing_cm} — "
                "the pod-scale chaos contract is broken"
            )
        if cm["injected_faults"] == 0:
            raise RuntimeError(
                "chaos_multichip injected nothing — the armed plan "
                f"({cm['faults_armed']!r}) tested nothing"
            )
        if cm["failed_requests"] or cm["hangs"]:
            raise RuntimeError(
                f"chaos_multichip dropped traffic: {cm['failed_requests']} "
                f"failed, {cm['hangs']} hung — every armed mesh fault must "
                "degrade or retry, never fail a request"
            )
        # Every bitwise-parity flag in the schema must hold (derived from
        # the imported contract so a renamed key cannot drift past here).
        parity_keys = [
            k for k in CHAOS_MULTICHIP_SECTION_KEYS if "bitwise" in k
        ]
        bad_parity = [k for k in parity_keys if not cm[k]]
        if bad_parity:
            raise RuntimeError(
                f"chaos_multichip parity broken: {bad_parity} — a "
                "degradation changed answers"
            )
        variants["chaos_multichip"] = cm
        _mark(
            f"chaos_multichip survived ({cm['injected_faults']} faults: "
            f"{cm['collective_retries']} collective retries, "
            f"{cm['shard_upload_retries']} shard-upload retries, "
            f"{cm['promote_failures']} promote failures; 0 failed, 0 hung)"
        )
    except Exception as exc:  # noqa: BLE001 - bench must still print a line
        import traceback

        traceback.print_exc(file=sys.stderr)
        variants["chaos_multichip"] = dict(
            failed=True, reason=f"{type(exc).__name__}: {exc}"
        )

    # ---- elastic mesh: live reshard + mid-fit mesh-loss resume ------------
    # Own 8-virtual-device subprocess (ISSUE 13): an 8->4 shrink and 4->8
    # regrow under live replay with zero failed requests and post-reshard
    # scores bitwise-equal to a cold start at the new shape, a hot-row
    # rebalance driven by observed promotion stats, and a mid-fit shrink
    # drill that resumes bitwise at the cost of exactly one repeated
    # sweep. The clean (un-injected) phases must leave every
    # reshard/mesh-loss counter at zero.
    try:
        env_em = dict(os.environ)
        env_em["JAX_PLATFORMS"] = "cpu"
        flags_em = env_em.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags_em:
            env_em["XLA_FLAGS"] = (
                flags_em + " --xla_force_host_platform_device_count=8"
            ).strip()
        env_em.pop("PHOTON_FAULTS", None)  # the child arms its own drill
        out_em = subprocess.run(
            [sys.executable, os.path.abspath(__file__), _ELASTIC_MESH_CHILD],
            capture_output=True,
            text=True,
            timeout=600,
            env=env_em,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        line_em = next(
            (l for l in out_em.stdout.splitlines() if l.startswith("{")), None
        )
        if line_em is None:
            raise RuntimeError(
                f"elastic_mesh child produced no JSON: {out_em.stderr[-1500:]}"
            )
        em = json.loads(line_em)
        from photon_ml_tpu.utils.contracts import ELASTIC_MESH_SECTION_KEYS

        missing_em = [
            k for k in ELASTIC_MESH_SECTION_KEYS if em.get(k) is None
        ]
        if missing_em:
            raise RuntimeError(
                f"elastic_mesh section is missing keys {missing_em} — the "
                "live-elasticity contract is broken"
            )
        if em["failed_requests"] or em.get("hangs"):
            raise RuntimeError(
                f"elastic_mesh dropped traffic: {em['failed_requests']} "
                f"failed, {em.get('hangs')} hung — a live reshard must "
                "never fail a request"
            )
        parity_em = [
            k for k in ELASTIC_MESH_SECTION_KEYS if "bitwise" in k
        ]
        bad_em = [k for k in parity_em if not em[k]]
        if bad_em:
            raise RuntimeError(
                f"elastic_mesh parity broken: {bad_em} — a reshard or "
                "mesh-loss resume changed answers"
            )
        if em["midfit_repeated_sweeps"] != 1:
            raise RuntimeError(
                "mid-fit mesh loss repeated "
                f"{em['midfit_repeated_sweeps']} sweeps — the contract is "
                "exactly one"
            )
        if not em["clean_counters_zero"]:
            raise RuntimeError(
                "clean elastic_mesh phases left nonzero robustness "
                f"counters ({em.get('clean_counters')}) — elasticity "
                "regression"
            )
        if em["moved_rows_shrink"] <= 0:
            raise RuntimeError(
                "elastic_mesh shrink plan moved no rows — the reshard "
                "certificate measured nothing"
            )
        variants["elastic_mesh"] = em
        _mark(
            f"elastic_mesh survived ({em['n_devices']}->{em['shrink_to']}"
            f"->{em['n_devices']} under replay: "
            f"{em['answered_during_shrink'] + em['answered_during_regrow']}"
            " answered, 0 failed; rebalance "
            f"{em['cold_tier_hits_before_rebalance']}->"
            f"{em['cold_tier_hits_after_rebalance']} cold hits; mid-fit "
            "resume bitwise in 1 repeated sweep)"
        )
    except Exception as exc:  # noqa: BLE001 - bench must still print a line
        import traceback

        traceback.print_exc(file=sys.stderr)
        variants["elastic_mesh"] = dict(
            failed=True, reason=f"{type(exc).__name__}: {exc}"
        )

    # ---- multi-tenant serving: N isolated bundles on one mesh -------------
    # Own 8-virtual-device subprocess (ISSUE 15): 10 tenant bundles on one
    # fleet, injected faults/hangs/overload confined to ONE chaos tenant —
    # every clean tenant must answer with zero failed requests, admitted
    # p99 inside its deadline, and scores bitwise-equal to serving it
    # alone; an over-budget 11th admission must demote (never fail) the
    # coldest tenant, which keeps answering bitwise from the host tier.
    try:
        env_mt = dict(os.environ)
        env_mt["JAX_PLATFORMS"] = "cpu"
        flags_mt = env_mt.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags_mt:
            env_mt["XLA_FLAGS"] = (
                flags_mt + " --xla_force_host_platform_device_count=8"
            ).strip()
        env_mt.pop("PHOTON_FAULTS", None)  # the child arms its own drill
        env_mt.pop("PHOTON_WATCHDOG_MS", None)
        # The ladder drill measures the f32 baseline with the ladder OFF;
        # an ambient opt-in would fake the capacity ratio.
        env_mt.pop("PHOTON_TIER_LADDER", None)
        out_mt = subprocess.run(
            [sys.executable, os.path.abspath(__file__), _MULTI_TENANT_CHILD],
            capture_output=True,
            text=True,
            timeout=600,
            env=env_mt,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        line_mt = next(
            (l for l in out_mt.stdout.splitlines() if l.startswith("{")), None
        )
        if line_mt is None:
            raise RuntimeError(
                f"multi_tenant child produced no JSON: {out_mt.stderr[-1500:]}"
            )
        mt = json.loads(line_mt)
        from photon_ml_tpu.utils.contracts import MULTI_TENANT_SECTION_KEYS

        missing_mt = [
            k for k in MULTI_TENANT_SECTION_KEYS if mt.get(k) is None
        ]
        # demoted_tenant is a name (or None on a broken drill) — its
        # absence is covered by the admitted/evicted flags below.
        missing_mt = [k for k in missing_mt if k != "demoted_tenant"]
        if missing_mt:
            raise RuntimeError(
                f"multi_tenant section is missing keys {missing_mt} — the "
                "serving-platform contract is broken"
            )
        if mt["injected_faults"] <= 0 or mt["chaos_shed"] <= 0:
            raise RuntimeError(
                "multi_tenant chaos phase injected nothing "
                f"(faults={mt['injected_faults']}, shed={mt['chaos_shed']})"
                " — the isolation drill tested nothing"
            )
        if mt["clean_failed_requests"] or mt["clean_degraded_batches"]:
            raise RuntimeError(
                f"chaos leaked across tenants: {mt['clean_failed_requests']}"
                f" clean failures, {mt['clean_degraded_batches']} clean "
                "degradations — the isolation contract is broken"
            )
        if not mt["clean_bitwise_vs_solo"]:
            raise RuntimeError(
                "co-batched clean-tenant scores diverged from solo serving"
                " — the cross-tenant bitwise contract is broken"
            )
        if not mt["clean_p99_within_deadline"]:
            raise RuntimeError(
                "a clean tenant's admitted p99 blew its deadline under a "
                "neighbor's chaos — the latency isolation contract is "
                "broken"
            )
        if mt["cobatch_dispatches"] <= 0:
            raise RuntimeError(
                "no cross-tenant co-batched dispatch ran — the section "
                "measured solo serving only"
            )
        if not mt["admitted_over_budget"] or not mt["evicted_bitwise"]:
            raise RuntimeError(
                "HBM-pressure eviction drill failed: over-budget admission"
                f" {mt['admitted_over_budget']}, evicted tenant bitwise "
                f"{mt['evicted_bitwise']}"
            )
        # Precision-ladder squeeze (ISSUE 20): the quantize-in-place
        # ladder must beat whole-tenant host eviction by >= 3x residency
        # on the same fleet, with the characterized-parity and
        # zero-failed-request contracts holding through every transition.
        if mt["ladder_capacity_ratio"] < 3.0:
            raise RuntimeError(
                "precision ladder fit only "
                f"{mt['ladder_resident_tenants']} resident tenants vs "
                f"{mt['f32_capacity_tenants']} at f32 (ratio "
                f"{mt['ladder_capacity_ratio']:.2f} < 3.0) — quantize-in-"
                "place bought almost nothing over host eviction"
            )
        if not mt["quantized_within_tolerance"]:
            raise RuntimeError(
                "a quantized tenant's replay left its rung's pinned "
                "TIER_TOLERANCES (or a mid-quantize fault leaked to a "
                "neighbor) — the characterized-parity contract is broken"
            )
        if mt["ladder_failed_requests"]:
            raise RuntimeError(
                f"{mt['ladder_failed_requests']} requests failed across "
                f"{mt['ladder_transitions']} ladder transitions — a "
                "quantize/restore flip dropped traffic"
            )
        if not mt["ladder_restored_bitwise"]:
            raise RuntimeError(
                "a tenant restored from the ladder diverged from its "
                "pre-demotion self — the restore-bitwise contract is broken"
            )
        variants["multi_tenant"] = mt
        _mark(
            f"multi_tenant survived (10 tenants on {mt['n_devices']} vdev:"
            f" {mt['injected_faults']} faults + {mt['chaos_shed']} sheds + "
            f"{mt['chaos_hangs']} hangs confined to '{mt['chaos_tenant']}',"
            f" {mt['clean_requests']} clean requests 0 failed bitwise, "
            f"{mt['cobatch_dispatches']} co-batched dispatches, "
            f"'{mt['demoted_tenant']}' evicted to host tier bitwise; "
            f"ladder: {mt['ladder_resident_tenants']} resident vs "
            f"{mt['f32_capacity_tenants']} f32-only "
            f"({mt['ladder_capacity_ratio']:.2f}x) across "
            f"{mt['ladder_transitions']} transitions, 0 failed, restored "
            "bitwise)"
        )
    except Exception as exc:  # noqa: BLE001 - bench must still print a line
        import traceback

        traceback.print_exc(file=sys.stderr)
        variants["multi_tenant"] = dict(
            failed=True, reason=f"{type(exc).__name__}: {exc}"
        )

    # ---- continuous refresh: incremental fit + delta-bundle swap ----------
    # Own 8-virtual-device subprocess (ISSUE 16): full fit, then a streamed
    # delta batch re-solved with a warm-start incremental fit and swapped
    # into the LIVE engine as a delta bundle under replay traffic. The
    # contract is the freshness wall: data->served latency must beat the
    # full-refit + full-restage baseline, unchanged entities ride bitwise,
    # and the generation flip answers every in-flight request.
    try:
        env_cl = dict(os.environ)
        env_cl["JAX_PLATFORMS"] = "cpu"
        flags_cl = env_cl.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags_cl:
            env_cl["XLA_FLAGS"] = (
                flags_cl + " --xla_force_host_platform_device_count=8"
            ).strip()
        env_cl.pop("PHOTON_FAULTS", None)  # a clean-path freshness measure
        out_cl = subprocess.run(
            [
                sys.executable,
                os.path.abspath(__file__),
                _CONTINUOUS_LOOP_CHILD,
            ],
            capture_output=True,
            text=True,
            timeout=600,
            env=env_cl,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        line_cl = next(
            (l for l in out_cl.stdout.splitlines() if l.startswith("{")), None
        )
        if line_cl is None:
            raise RuntimeError(
                "continuous_loop child produced no JSON: "
                f"{out_cl.stderr[-1500:]}"
            )
        cl = json.loads(line_cl)
        from photon_ml_tpu.utils.contracts import CONTINUOUS_SECTION_KEYS

        missing_cl = [
            k for k in CONTINUOUS_SECTION_KEYS if cl.get(k) is None
        ]
        if missing_cl:
            raise RuntimeError(
                f"continuous_loop section is missing keys {missing_cl} — "
                "the freshness contract is broken"
            )
        if cl["failed_requests"]:
            raise RuntimeError(
                f"{cl['failed_requests']} request(s) failed during the "
                "delta swap — the zero-failed-request contract is broken"
            )
        if not cl["unchanged_entities_bitwise"]:
            raise RuntimeError(
                "unchanged entities diverged across the incremental fit — "
                "the bitwise carry contract is broken"
            )
        if cl["answered_during_refresh"] <= 0:
            raise RuntimeError(
                "no live traffic was answered during the refresh — the "
                "swap-under-load measurement tested nothing"
            )
        if not 0 < cl["delta_rows"] < cl["total_rows"]:
            raise RuntimeError(
                f"delta batch was not a strict subset ({cl['delta_rows']}/"
                f"{cl['total_rows']} rows) — the incremental path was not "
                "exercised"
            )
        variants["continuous_loop"] = cl
        _mark(
            f"continuous_loop survived ({cl['n_devices']} vdev: delta "
            f"{cl['delta_rows']}/{cl['total_rows']} rows, data->served "
            f"{cl['data_to_served_s']}s vs full refresh "
            f"{cl['full_refresh_baseline_s']}s = {cl['speedup_vs_full']}x,"
            f" {cl['answered_during_refresh']} answered 0 failed, "
            "unchanged entities bitwise)"
        )
    except Exception as exc:  # noqa: BLE001 - bench must still print a line
        import traceback

        traceback.print_exc(file=sys.stderr)
        variants["continuous_loop"] = dict(
            failed=True, reason=f"{type(exc).__name__}: {exc}"
        )

    # ---- shadow deployment: the platform stops being quality-blind --------
    # Own 8-virtual-device subprocess (ISSUE 18): a challenger admitted as
    # a shadow tenant sees mirrored live traffic co-batched with the
    # champion, windowed label joins feed the EXACT offline metric
    # programs, and the verdict loop actuates the existing machinery —
    # reject tears the shadow down, promote rides the atomic generation
    # flip. The contract: a label-noised refit is detected and rolled
    # back from shadow metrics alone, a healthy challenger is promoted,
    # and the champion never fails (or changes) a single client answer —
    # not even when a worker is SIGKILLed mid-promotion.
    try:
        env_sd = dict(os.environ)
        env_sd["JAX_PLATFORMS"] = "cpu"
        flags_sd = env_sd.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags_sd:
            env_sd["XLA_FLAGS"] = (
                flags_sd + " --xla_force_host_platform_device_count=8"
            ).strip()
        env_sd.pop("PHOTON_FAULTS", None)  # drills arm their own faults
        out_sd = subprocess.run(
            [
                sys.executable,
                os.path.abspath(__file__),
                _SHADOW_DEPLOY_CHILD,
            ],
            capture_output=True,
            text=True,
            timeout=600,
            env=env_sd,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        line_sd = next(
            (l for l in out_sd.stdout.splitlines() if l.startswith("{")), None
        )
        if line_sd is None:
            raise RuntimeError(
                f"shadow_deploy child produced no JSON: "
                f"{out_sd.stderr[-1500:]}"
            )
        sd = json.loads(line_sd)
        from photon_ml_tpu.utils.contracts import SHADOW_SECTION_KEYS

        missing_sd = [k for k in SHADOW_SECTION_KEYS if sd.get(k) is None]
        if missing_sd:
            raise RuntimeError(
                f"shadow_deploy section is missing keys {missing_sd} — the "
                "online-evaluation contract is broken"
            )
        if not sd["degraded_detected"]:
            raise RuntimeError(
                "the label-noised challenger was NOT detected from shadow "
                "metrics — the platform is still quality-blind"
            )
        if not sd["degraded_rolled_back"]:
            raise RuntimeError(
                "the degraded challenger was not torn down on its reject "
                "verdict — the rollback actuator is broken"
            )
        if sd["degraded_champion_failed"] or not sd[
            "degraded_champion_bitwise"
        ]:
            raise RuntimeError(
                "champion traffic was damaged while shadowing a degraded "
                "challenger — mirroring is not isolated"
            )
        if not sd["mirror_fault_champion_clean"]:
            raise RuntimeError(
                "mirror/label-join faults leaked into champion answers — "
                "degradation to champion-only serving is broken"
            )
        if sd["mirror_faults_injected"] < 5:
            raise RuntimeError(
                f"only {sd['mirror_faults_injected']} mirror-path faults "
                "fired — the isolation drill tested nothing"
            )
        if not sd["healthy_promoted"] or sd["promoted_generation"] <= 0:
            raise RuntimeError(
                "the healthy challenger was not promoted through the "
                "generation flip — the promote actuator is broken"
            )
        if not sd["post_promote_bitwise"]:
            raise RuntimeError(
                "post-promotion answers diverged from the promoted bundle "
                "served solo — the flip did not install it bitwise"
            )
        if not sd["sigkill_champion_bitwise"]:
            raise RuntimeError(
                "a SIGKILL mid-promotion changed champion answers — the "
                "generation flip is not atomic under process murder"
            )
        if not sd["clean_counters_zero"]:
            raise RuntimeError(
                "robustness counters were nonzero on the clean promotion "
                "phase — the shadow path hides failures in a healthy run"
            )
        if sd["shadow_cobatched"] <= 0:
            raise RuntimeError(
                "no mirrored request was ever co-batched with champion "
                "traffic — the shadow rode a private dispatch path"
            )
        variants["shadow_deploy"] = sd
        _mark(
            f"shadow_deploy survived ({sd['n_devices']} vdev: degraded "
            f"challenger rejected after {sd['degraded_windows']} windows "
            f"and rolled back, healthy challenger promoted to generation "
            f"{sd['promoted_generation']}, {sd['mirrored_requests']} "
            f"mirrored / {sd['shadow_cobatched']} co-batched dispatches, "
            f"{sd['mirror_faults_injected']} mirror faults champion-clean, "
            "SIGKILL mid-promotion left the old generation bitwise)"
        )
    except Exception as exc:  # noqa: BLE001 - bench must still print a line
        import traceback

        traceback.print_exc(file=sys.stderr)
        variants["shadow_deploy"] = dict(
            failed=True, reason=f"{type(exc).__name__}: {exc}"
        )

    # ---- autopilot: closed-loop autoscaling — the planner goes online -----
    # Own 8-virtual-device subprocess (ISSUE 19): the supervised control
    # loop reads live telemetry, evaluates declarative rules behind
    # hysteresis bands, and drives the EXISTING actuators — reshard,
    # hot-row rebalance, the HBM demote/restore ladder, the planner's
    # online retune. The contract: a load shift triggers automatic
    # reshard + rebalance with zero failed requests and a recovered p99,
    # an HBM squeeze demotes and later restores the cold tenant bitwise,
    # a deliberately bad rule is rolled back by the post-action contract
    # probe and quarantined, every decision is journaled with evidence,
    # and the clean phases trip no robustness counter.
    try:
        env_ap = dict(os.environ)
        env_ap["JAX_PLATFORMS"] = "cpu"
        flags_ap = env_ap.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags_ap:
            env_ap["XLA_FLAGS"] = (
                flags_ap + " --xla_force_host_platform_device_count=8"
            ).strip()
        env_ap.pop("PHOTON_FAULTS", None)  # drills arm their own faults
        out_ap = subprocess.run(
            [
                sys.executable,
                os.path.abspath(__file__),
                _AUTOPILOT_CHILD,
            ],
            capture_output=True,
            text=True,
            timeout=600,
            env=env_ap,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        line_ap = next(
            (l for l in out_ap.stdout.splitlines() if l.startswith("{")), None
        )
        if line_ap is None:
            raise RuntimeError(
                f"autopilot child produced no JSON: {out_ap.stderr[-1500:]}"
            )
        ap = json.loads(line_ap)
        from photon_ml_tpu.utils.contracts import AUTOPILOT_SECTION_KEYS

        missing_ap = [k for k in AUTOPILOT_SECTION_KEYS if ap.get(k) is None]
        if missing_ap:
            raise RuntimeError(
                f"autopilot section is missing keys {missing_ap} — the "
                "closed-loop contract is broken"
            )
        if not ap["load_shift_detected"] or ap["reshard_actions"] < 1:
            raise RuntimeError(
                "the load shift did NOT trigger an automatic reshard — "
                "the planner never went online"
            )
        if ap["rebalance_actions"] < 1:
            raise RuntimeError(
                "promotion pressure did not trigger a hot-row rebalance — "
                "the two-tier placement loop is open"
            )
        if ap["failed_requests"]:
            raise RuntimeError(
                f"{ap['failed_requests']} client requests failed while the "
                "autopilot actuated — actuation is not transparent"
            )
        if not ap["p99_recovered"]:
            raise RuntimeError(
                f"post-reshard p99 ({ap['post_p99_ms']:.1f} ms) blew the "
                f"probe bound over the baseline ({ap['pre_p99_ms']:.1f} ms)"
            )
        if not ap["hbm_demoted"]:
            raise RuntimeError(
                "the induced HBM squeeze did not demote the cold tenant — "
                "the capacity ladder's downward leg is broken"
            )
        if not ap["hbm_restored_bitwise"]:
            raise RuntimeError(
                "the demoted tenant was not restored bitwise when headroom "
                "returned — the capacity ladder's upward leg is broken"
            )
        if not ap["bad_rule_rolled_back"]:
            raise RuntimeError(
                "the bad rule's retune survived the post-action contract "
                "probe — rollback is broken"
            )
        if not ap["bad_rule_quarantined"]:
            raise RuntimeError(
                "the bad rule was not quarantined after its rollback — "
                "the loop will keep re-firing a known-bad policy"
            )
        if ap["decisions_journaled"] <= 0 or not ap["decisions_valid"]:
            raise RuntimeError(
                "autopilot decisions missing from the journal or invalid "
                "against the contracts schemas — the loop is unauditable"
            )
        if not ap["clean_counters_zero"]:
            raise RuntimeError(
                "robustness counters were nonzero across the clean drills — "
                "the autopilot hides failures in a healthy run"
            )
        variants["autopilot"] = ap
        _mark(
            f"autopilot survived ({ap['n_devices']} vdev, {ap['ticks']} "
            f"ticks: load shift -> {ap['reshard_actions']} reshard + "
            f"{ap['rebalance_actions']} rebalance with 0 failed requests "
            f"and p99 {ap['pre_p99_ms']:.1f}->{ap['post_p99_ms']:.1f} ms, "
            "HBM squeeze demoted and restored the cold tenant bitwise, "
            "bad rule rolled back and quarantined, "
            f"{ap['decisions_journaled']} decisions journaled valid)"
        )
    except Exception as exc:  # noqa: BLE001 - bench must still print a line
        import traceback

        traceback.print_exc(file=sys.stderr)
        variants["autopilot"] = dict(
            failed=True, reason=f"{type(exc).__name__}: {exc}"
        )

    # ---- multihost chaos: whole OS processes as the failure domain --------
    # The ISSUE 17 production certificate, driven through the real CLI
    # supervisors: 2-process fit bitwise vs single-process with disjoint
    # per-host ingest, a host SIGKILLed mid-fit costing exactly one
    # repeated sweep, and a serving host SIGKILLed mid-replay failing
    # zero requests (lost rows FE-only through the survivor, resident
    # rows bitwise). Own subprocess; the child spawns the supervisors.
    try:
        env_mh = dict(os.environ)
        env_mh["JAX_PLATFORMS"] = "cpu"
        # The child's supervisors construct worker envs themselves
        # (hostmesh.worker_env scrubs fault/plan/trace knobs); the child
        # itself must not inherit an armed plan from a previous section.
        for leaked in ("PHOTON_FAULTS", "PHOTON_FAULTS_SEED",
                       "PHOTON_PLAN", "PHOTON_PLAN_PROFILE",
                       "PHOTON_TRACE", "PHOTON_HOST_LOSS_RETRIES"):
            env_mh.pop(leaked, None)
        out_mh = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             _MULTIHOST_CHAOS_CHILD],
            capture_output=True,
            text=True,
            timeout=900,
            env=env_mh,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        line_mh = next(
            (l for l in out_mh.stdout.splitlines() if l.startswith("{")),
            None,
        )
        if line_mh is None:
            raise RuntimeError(
                "multihost_chaos child produced no JSON: "
                f"{out_mh.stderr[-1500:]}"
            )
        mhc = json.loads(line_mh)
        from photon_ml_tpu.utils.contracts import MULTIHOST_SECTION_KEYS

        missing_mh = [
            k for k in MULTIHOST_SECTION_KEYS if mhc.get(k) is None
        ]
        if missing_mh:
            raise RuntimeError(
                f"multihost_chaos section is missing keys {missing_mh} — "
                "the DCN production contract is broken"
            )
        if not mhc["fit_bitwise_vs_single_process"]:
            raise RuntimeError(
                "2-process fit diverged from the single-process fit — "
                "the multi-host bitwise-parity contract is broken"
            )
        if not mhc["ingest_disjoint_ok"]:
            raise RuntimeError(
                f"per-host ingest was not disjoint ({mhc['files_per_host']}"
                " files per host) — one host decoded the whole corpus"
            )
        if mhc["host_losses"] != 1 or mhc["repeated_sweeps"] != 1:
            raise RuntimeError(
                f"host loss cost {mhc['repeated_sweeps']} repeated "
                f"sweep(s) over {mhc['host_losses']} loss(es) — the "
                "one-sweep contract is broken"
            )
        if mhc["failed_requests"]:
            raise RuntimeError(
                f"{mhc['failed_requests']} request(s) failed with a "
                "serving host down — the zero-failed-request contract "
                "is broken"
            )
        if mhc["fe_only_answers"] <= 0:
            raise RuntimeError(
                "no answers degraded with a serving host down — the "
                "SIGKILL landed after the replay and tested nothing"
            )
        if not mhc["serve_bitwise_resident"]:
            raise RuntimeError(
                "a resident row's answer diverged from the single-process "
                "serve — host loss must only ever degrade the LOST rows"
            )
        variants["multihost_chaos"] = mhc
        _mark(
            f"multihost_chaos survived ({mhc['n_hosts']}x"
            f"{mhc['devices_per_host']} hosts, {mhc['files_per_host']} "
            f"files/host: fit bitwise, 1 host loss = 1 repeated sweep, "
            f"{mhc['fe_only_answers']} FE-only of 0 failed, resident "
            f"bitwise, {mhc['dcn_collective_bytes']} DCN bytes)"
        )
    except Exception as exc:  # noqa: BLE001 - bench must still print a line
        import traceback

        traceback.print_exc(file=sys.stderr)
        variants["multihost_chaos"] = dict(
            failed=True, reason=f"{type(exc).__name__}: {exc}"
        )

    # ---- online serving (pinned bundle + deadline micro-batcher) ----------
    # The north star serves live traffic; this measures the online path the
    # offline scoring number cannot show: per-request latency through the
    # micro-batcher against a >=100k-entity bundle pinned in device memory,
    # with the bounded-compile-set contract checked (zero recompiles after
    # warmup) and the clean-run robustness contract (zero injected faults
    # => zero degraded batches), same loud-failure protocol as
    # prepare_breakdown.
    try:
        from photon_ml_tpu.game.model import (
            Coefficients as _SCoefs,
            FixedEffectModel as _SFE,
            GameModel as _SGM,
            RandomEffectModel as _SRE,
        )
        from photon_ml_tpu.serving import (
            ScoreRequest as _SReq,
            ServingBundle as _SBundle,
            ServingEngine as _SEngine,
        )
        from photon_ml_tpu.transformers.game_transformer import (
            CoordinateScoringSpec as _SSpec,
        )
        from photon_ml_tpu.utils import faults as _sfaults

        _sfaults.reset_counters()
        e_srv, d_srv_fe, d_srv_re = 120_000, 64, 16
        n_req, srv_batch = 16384, 256
        rng_s = np.random.default_rng(31)
        w_srv = rng_s.normal(size=d_srv_fe).astype(np.float32)
        m_srv = np.zeros((e_srv + 1, d_srv_re), np.float32)
        m_srv[:e_srv] = rng_s.normal(size=(e_srv, d_srv_re)).astype(np.float32) * 0.3
        task_srv = TaskType.LOGISTIC_REGRESSION
        bundle_srv = _SBundle.from_model(
            _SGM(
                {
                    "fixed": _SFE(_SCoefs(jnp.asarray(w_srv)), task_srv),
                    "per-entity": _SRE(jnp.asarray(m_srv), None, task_srv),
                }
            ),
            {
                "fixed": _SSpec(shard="g"),
                "per-entity": _SSpec(
                    shard="re",
                    random_effect_type="entityId",
                    entity_index={str(i): i for i in range(e_srv)},
                ),
            },
            task_srv,
        )
        _mark(
            f"serving bundle pinned ({e_srv} entities, "
            f"{bundle_srv.upload_bytes/1e6:.1f} MB in {bundle_srv.upload_s:.3f}s)"
        )
        Xs_fe = rng_s.normal(size=(n_req, d_srv_fe)).astype(np.float32)
        Xs_re = rng_s.normal(size=(n_req, d_srv_re)).astype(np.float32)
        # 1 in 64 requests carries an id outside the bundle -> cold start; the
        # measured fraction must match this stream exactly.
        ent_srv = rng_s.integers(0, e_srv, size=n_req)
        cold_mask = rng_s.uniform(size=n_req) < (1 / 64)
        reqs_srv = [
            _SReq(
                features={"g": Xs_fe[i], "re": Xs_re[i]},
                entity_ids={
                    "entityId": f"unknown-{i}" if cold_mask[i] else str(ent_srv[i])
                },
                uid=str(i),
            )
            for i in range(n_req)
        ]
        engine_srv = _SEngine(bundle_srv, max_batch=srv_batch)
        t0 = time.perf_counter()
        engine_srv.warmup()
        _mark(
            f"serving engine warm ({engine_srv.compiles} bucket programs, "
            f"{time.perf_counter() - t0:.1f}s)"
        )
        with engine_srv, engine_srv.batcher(max_wait_ms=1.0) as batcher_srv:  # photon-lint: disable=planner-constant — deliberate section config: fixed wait pins the measurement, not a runtime default
            batcher_srv.score_all(reqs_srv)
            m_srv_metrics = batcher_srv.metrics()
        from photon_ml_tpu.utils.contracts import (
            SERVING_METRIC_KEYS,
            SERVING_SHARDING_KEYS,
        )

        missing_srv = [
            k for k in SERVING_METRIC_KEYS if m_srv_metrics.get(k) is None
        ]
        # Sharding-decision contract (ISSUE 7): the summary must carry the
        # axis size / rows-per-shard / hot-set-fraction / collective-bytes
        # keys even on a single-tier replicated bundle (False/1/.../0), so
        # their absence is a loud metrics regression, not a silent gap.
        sharding_srv = m_srv_metrics.get("sharding") or {}
        missing_srv += [
            f"sharding.{k}"
            for k in SERVING_SHARDING_KEYS
            if sharding_srv.get(k) is None
        ]
        if missing_srv:
            raise RuntimeError(
                f"serving_online is missing metric keys {missing_srv} "
                f"(got {sorted(k for k, v in m_srv_metrics.items() if v is not None)}) "
                "— the serving metrics contract is broken"
            )
        expected_cold = float(cold_mask.sum()) / n_req
        if abs(m_srv_metrics["cold_start_fraction"] - expected_cold) > 1e-9:
            raise RuntimeError(
                f"cold_start_fraction {m_srv_metrics['cold_start_fraction']} does "
                f"not match the replayed stream's {expected_cold}"
            )
        if (
            _sfaults.COUNTERS.get("injected_faults") == 0
            and m_srv_metrics["degraded_batches"] != 0
        ):
            raise RuntimeError(
                "clean serving run reported degraded batches "
                f"({m_srv_metrics['degraded_batches']}) — robustness regression"
            )
        # Clean-run zero contract (ISSUE 5 + ISSUE 10): an un-faulted,
        # un-overloaded replay must shed nothing, miss no deadline, never
        # open the circuit, quarantine no Avro block — and fire none of
        # the pod-scale mesh events (collective retries, shard-upload
        # retries, promote failures, watchdog trips).
        from photon_ml_tpu.utils.contracts import (
            ROBUSTNESS_CLEAN_ZERO_KEYS,
            SERVING_CLEAN_ZERO_KEYS,
        )

        clean_zero = {k: m_srv_metrics[k] for k in SERVING_CLEAN_ZERO_KEYS}
        clean_zero["quarantined_blocks"] = _sfaults.COUNTERS.get(
            "quarantined_blocks"
        )
        for k in ROBUSTNESS_CLEAN_ZERO_KEYS:
            clean_zero[k] = _sfaults.COUNTERS.get(k)
        dirty = {k: v for k, v in clean_zero.items() if v}
        if dirty:
            raise RuntimeError(
                f"clean serving run reported nonzero robustness events "
                f"{dirty} — serving failure-semantics regression"
            )
        variants["serving_online"] = dict(
            n_entities=e_srv,
            requests=n_req,
            max_batch=srv_batch,
            p50_ms=m_srv_metrics["p50_ms"],
            p95_ms=m_srv_metrics["p95_ms"],
            p99_ms=m_srv_metrics["p99_ms"],
            qps=m_srv_metrics["qps"],
            cold_start_fraction=round(m_srv_metrics["cold_start_fraction"], 5),
            padding_waste=round(m_srv_metrics["padding_waste"], 4),
            recompiles_after_warmup=m_srv_metrics["recompiles_after_warmup"],
            degraded_batches=m_srv_metrics["degraded_batches"],
            bundle_upload_mb=round(bundle_srv.upload_bytes / 1e6, 1),
            bundle_upload_s=round(bundle_srv.upload_s, 3),
            sharding=sharding_srv,
            hot_tier_hits=m_srv_metrics["hot_tier_hits"],
            cold_tier_hits=m_srv_metrics["cold_tier_hits"],
            promotions=m_srv_metrics["promotions"],
            evictions=m_srv_metrics["evictions"],
        )
        _mark(f"serving_online measured ({m_srv_metrics['qps']} qps)")
    except Exception as exc:  # noqa: BLE001 - bench must still print a line
        import traceback

        traceback.print_exc(file=sys.stderr)
        variants["serving_online"] = dict(
            failed=True, reason=f"{type(exc).__name__}: {exc}"
        )

    # ---- serving under overload (admission control + deadlines) -----------
    # Offered load >= 2x the measured clean capacity against a bounded
    # queue: shed requests must get TYPED Overloaded rejections (never a
    # backlog), admitted-request p99 must stay under the configured
    # deadline, and nothing may hang — every submitted future resolves.
    try:
        from photon_ml_tpu.serving import (
            DeadlineExceeded as _SDeadline,
            Overloaded as _SOverload,
        )

        import threading as _ol_threading

        # The overload tier uses a SMALL batch ceiling: host submitters
        # must genuinely out-offer the engine (offered >= 2x capacity),
        # and a 256-wide bucket on this bundle out-runs any Python
        # submit loop — admission control would never engage.
        ol_batch = 8
        ol_pending = 16 * ol_batch
        eng_ol = _SEngine(bundle_srv, max_batch=ol_batch)
        eng_ol.warmup()
        with eng_ol:
            # Calibrate THIS configuration's clean capacity.
            with eng_ol.batcher(max_wait_ms=1.0) as b_cal:  # photon-lint: disable=planner-constant — deliberate section config: fixed wait pins the measurement, not a runtime default
                b_cal.score_all(reqs_srv[:4096])
                cap_qps = float(b_cal.metrics()["qps"] or 0.0)
            if cap_qps <= 0:
                raise RuntimeError("overload capacity calibration failed")
            # Deadline = several full-queue drain times (a realistic
            # operator budget: well above one batch's service time, small
            # enough that only ENFORCEMENT keeps the tail under it when
            # capacity dips mid-burst). Floor keeps fast hosts honest.
            deadline_ms = max(150.0, 5.0 * ol_pending / cap_qps * 1e3)
            duration_s = 1.0
            n_submitters = 2
            shed_by = [0] * n_submitters
            offered_by = [0] * n_submitters
            futures_by = [[] for _ in range(n_submitters)]

            with eng_ol.batcher(
                max_wait_ms=1.0,  # photon-lint: disable=planner-constant — deliberate section config: fixed wait pins the measurement, not a runtime default
                max_pending=ol_pending,
                default_deadline_ms=deadline_ms,
            ) as b_ol:
                t_start = time.perf_counter()
                t_end = t_start + duration_s

                def _offer(sid):
                    i = sid  # interleave the request stream across submitters
                    while time.perf_counter() < t_end:
                        try:
                            futures_by[sid].append(
                                b_ol.submit(reqs_srv[i % n_req])
                            )
                        except _SOverload:
                            shed_by[sid] += 1
                        offered_by[sid] += 1
                        i += n_submitters

                threads_ol = [
                    _ol_threading.Thread(
                        target=_offer,
                        args=(s,),
                        name=f"photon-bench-overload-{s}",
                    )
                    for s in range(n_submitters)
                ]
                for t in threads_ol:
                    t.start()
                for t in threads_ol:
                    t.join()
                offered_wall = time.perf_counter() - t_start
                offered = sum(offered_by)
                shed = sum(shed_by)
                futures_ol = [f for fs in futures_by for f in fs]
                from concurrent.futures import TimeoutError as _FutTimeout

                hangs = misses = failed_ol = 0
                for f in futures_ol:
                    try:
                        f.result(timeout=60)
                    except _SDeadline:
                        misses += 1
                    except (_FutTimeout, TimeoutError):
                        hangs += 1  # result() timed out: the hang the contract bans
                    except Exception:  # noqa: BLE001 - counted, not fatal here
                        failed_ol += 1
                m_ol = b_ol.metrics()
        offered_qps = offered / offered_wall
        if offered_qps < 2.0 * cap_qps:
            raise RuntimeError(
                f"overload offered only {offered_qps:.0f} qps against a "
                f"{cap_qps:.0f} qps tier — below the contract's 2x; the "
                "measurement says nothing about admission control"
            )
        if shed == 0:
            raise RuntimeError(
                f"offered {offered} requests at >=2x capacity and shed none "
                "— admission control is not bounding the queue"
            )
        if hangs:
            raise RuntimeError(
                f"{hangs} admitted request(s) hung past the harvest timeout — "
                "zero-hang contract broken"
            )
        if m_ol["p99_ms"] is not None and m_ol["p99_ms"] > deadline_ms:
            raise RuntimeError(
                f"admitted p99 {m_ol['p99_ms']}ms exceeds the {deadline_ms}ms "
                "deadline — deadline enforcement is not bounding queue delay"
            )
        variants["serving_overload"] = dict(
            max_batch=ol_batch,
            max_pending=ol_pending,
            capacity_qps=round(cap_qps, 1),
            offered_qps=round(offered_qps, 1),
            overload_ratio=round(offered_qps / cap_qps, 2),
            deadline_ms=round(deadline_ms, 1),
            offered=offered,
            admitted=len(futures_ol),
            shed=shed,
            shed_fraction=round(shed / max(offered, 1), 4),
            deadline_misses=misses,
            # NOT `failed` — every bench section reserves that key as the
            # boolean section-crashed flag (with a `reason` beside it).
            failed_requests=failed_ol,
            hangs=hangs,
            admitted_p50_ms=m_ol["p50_ms"],
            admitted_p99_ms=m_ol["p99_ms"],
            circuit_opens=m_ol["circuit_opens"],
        )
        _mark(
            f"serving_overload measured ({offered_qps:.0f} qps offered vs "
            f"{cap_qps:.0f} capacity: shed {shed}/{offered}, admitted p99 "
            f"{m_ol['p99_ms']}ms vs {deadline_ms:.0f}ms deadline)"
        )
    except Exception as exc:  # noqa: BLE001 - bench must still print a line
        import traceback

        traceback.print_exc(file=sys.stderr)
        variants["serving_overload"] = dict(
            failed=True, reason=f"{type(exc).__name__}: {exc}"
        )

    # ---- bundle hot-swap under live traffic -------------------------------
    # A model push must not drop traffic: swap to a same-shape successor
    # bundle while a closed-loop client scores continuously; zero failed
    # requests, and post-swap answers bitwise-equal to a cold-started
    # engine on the new bundle. (This section retires bundle_srv — it must
    # stay last among the serving sections.)
    try:
        import threading as _threading

        w_srv2 = rng_s.normal(size=d_srv_fe).astype(np.float32)
        m_srv2 = np.zeros((e_srv + 1, d_srv_re), np.float32)
        m_srv2[:e_srv] = (
            rng_s.normal(size=(e_srv, d_srv_re)).astype(np.float32) * 0.3
        )
        specs_srv = {
            "fixed": _SSpec(shard="g"),
            "per-entity": _SSpec(
                shard="re",
                random_effect_type="entityId",
                entity_index={str(i): i for i in range(e_srv)},
            ),
        }
        gm2 = _SGM(
            {
                "fixed": _SFE(_SCoefs(jnp.asarray(w_srv2)), task_srv),
                "per-entity": _SRE(jnp.asarray(m_srv2), None, task_srv),
            }
        )
        eng_hs = _SEngine(bundle_srv, max_batch=srv_batch)
        eng_hs.warmup()
        stop_hs = _threading.Event()
        hs_failures: list = []
        hs_answered = [0]

        def _traffic(b):
            j = 0
            while not stop_hs.is_set():
                try:
                    b.score(reqs_srv[j % n_req])
                    hs_answered[0] += 1
                except Exception as t_exc:  # noqa: BLE001 - recorded
                    hs_failures.append(repr(t_exc))
                j += 1

        t_swap0 = time.perf_counter()
        with eng_hs, eng_hs.batcher(max_wait_ms=1.0) as b_hs:  # photon-lint: disable=planner-constant — deliberate section config: fixed wait pins the measurement, not a runtime default
            th = _threading.Thread(
                target=_traffic,
                args=(b_hs,),
                name="photon-bench-hotswap-traffic",
            )
            th.start()
            time.sleep(0.1)  # traffic flowing against version 0
            info_hs = eng_hs.bundle_manager.swap(
                lambda: _SBundle.from_model(gm2, specs_srv, task_srv),
                expected_bytes=bundle_srv.upload_bytes,
            )
            time.sleep(0.1)  # traffic flowing against version 1
            stop_hs.set()
            th.join(timeout=60)
            if th.is_alive():
                raise RuntimeError("hot-swap traffic thread wedged")
            # Post-swap bitwise parity vs a cold start on the new bundle.
            probe = reqs_srv[:2048]
            swapped_scores = np.asarray(
                [r.score for r in eng_hs.score_batch(probe)], np.float64
            )
            recompiles_hs = eng_hs.recompiles_after_warmup
        with _SEngine(
            _SBundle.from_model(gm2, specs_srv, task_srv), max_batch=srv_batch
        ) as eng_cold:
            cold_scores = np.asarray(
                [r.score for r in eng_cold.score_batch(probe)], np.float64
            )
        swap_total_s = time.perf_counter() - t_swap0
        if hs_failures:
            raise RuntimeError(
                f"{len(hs_failures)} request(s) failed during the hot swap "
                f"(first: {hs_failures[0]}) — zero-drop contract broken"
            )
        if not (swapped_scores == cold_scores).all():
            raise RuntimeError(
                "post-swap scores are not bitwise-equal to a cold-started "
                "engine on the new bundle"
            )
        variants["serving_hot_swap"] = dict(
            version=info_hs["version"],
            stage_s=info_hs["stage_s"],
            old_released=info_hs["old_released"],
            swap_section_s=round(swap_total_s, 3),
            answered_during=hs_answered[0],
            failed_requests=0,
            recompiles_after_warmup=recompiles_hs,
            post_swap_bitwise_equal=True,
        )
        _mark(
            f"serving_hot_swap committed v{info_hs['version']} under live "
            f"traffic ({hs_answered[0]} answered, 0 failed)"
        )
    except Exception as exc:  # noqa: BLE001 - bench must still print a line
        import traceback

        traceback.print_exc(file=sys.stderr)
        variants["serving_hot_swap"] = dict(
            failed=True, reason=f"{type(exc).__name__}: {exc}"
        )

    # ---- Avro ingest (native block decoder vs pure-Python codec) ----------
    # File generated by the native columnar writer (null codec — the
    # reference's fixture codec) at ~150 MB so decode throughput is
    # measured, not per-call overhead. Stages reported separately: decode
    # (native block decode to columnar host arrays) and the full
    # read_game_dataset (decode + index maps + ELL assembly + device
    # arrays). The decode threads over container blocks
    # (PHOTON_INGEST_THREADS / hw concurrency); the host's cpu count is
    # reported so single-core results read as what they are.
    import tempfile

    import photon_ml_tpu.io.avro_data as ad
    from photon_ml_tpu.io import avro as avro_io
    from photon_ml_tpu.data.index_map import DELIMITER
    from photon_ml_tpu.native import avro_reader as avro_reader_native
    from photon_ml_tpu.native.avro_writer import write_training_examples_columnar
    from photon_ml_tpu.native.build import load_native

    rng_np = np.random.default_rng(7)
    n_ing, d_ing, k_ing = 400_000, 4000, 24
    indptr_ing = np.arange(n_ing + 1, dtype=np.int64) * k_ing
    ids_ing = rng_np.integers(0, d_ing, size=n_ing * k_ing).astype(np.int32)
    vals_ing = rng_np.normal(size=n_ing * k_ing)
    names_ing = [f"f{i}" for i in range(d_ing)]
    with tempfile.TemporaryDirectory() as td:
        pth = os.path.join(td, "bench.avro")
        t0 = time.perf_counter()
        write_training_examples_columnar(
            pth,
            (rng_np.uniform(size=n_ing) > 0.5).astype(np.float64),
            indptr_ing,
            ids_ing,
            vals_ing,
            names_ing,
            tag_key="entityId",
            tag_values=rng_np.integers(0, 1000, size=n_ing).astype(str),
        )
        t_write = time.perf_counter() - t0
        mb = os.path.getsize(pth) / 1e6
        _mark(f"ingest file written ({mb:.0f} MB in {t_write:.1f}s)")
        cfg_ing = {"g": ad.FeatureShardConfig(("features",), True)}
        cols_ing = ad.InputColumnNames()

        # Stage 1: native block decode only.
        with open(pth, "rb") as fh:
            raw = fh.read()
        schema_i, codec_i, sync_i, body_i = avro_io.read_header(raw, pth)
        prog_i = avro_reader_native.compile_program(
            schema_i, response=cols_ing.response, fallback_label=ad.LABEL,
            offset=cols_ing.offset, weight=cols_ing.weight, uid=cols_ing.uid,
            metadata_map=cols_ing.metadata_map, bag_names=["features"],
            tag_fields=("entityId",),
        )
        t0 = time.perf_counter()
        decoded_i = avro_reader_native.decode_file_native(
            raw, body_i, codec_i, sync_i, prog_i, DELIMITER
        )
        t_decode = time.perf_counter() - t0
        del raw
        decode_ok = decoded_i is not None
        del decoded_i

        # Stage 2: full read (decode + assembly + device arrays).
        t0 = time.perf_counter()
        ad.read_game_dataset(pth, cfg_ing, id_tag_fields=["entityId"])
        t_native = time.perf_counter() - t0

        # Pure-Python codec on a 10x smaller slice (it is ~50x slower; a
        # full-file run would dominate the bench wall for no information).
        py_rows = n_ing // 10
        pth_py = os.path.join(td, "bench_py.avro")
        write_training_examples_columnar(
            pth_py,
            np.zeros(py_rows),
            indptr_ing[: py_rows + 1],
            ids_ing[: py_rows * k_ing],
            vals_ing[: py_rows * k_ing],
            names_ing,
            tag_key="entityId",
            tag_values=rng_np.integers(0, 1000, size=py_rows).astype(str),
        )
        mb_py = os.path.getsize(pth_py) / 1e6
        os.environ["PHOTON_DISABLE_NATIVE"] = "1"
        try:
            t0 = time.perf_counter()
            ad.read_game_dataset(pth_py, cfg_ing, id_tag_fields=["entityId"])
            t_python = time.perf_counter() - t0
        finally:
            del os.environ["PHOTON_DISABLE_NATIVE"]
    variants["avro_ingest"] = dict(
        file_mb=round(mb, 1),
        codec="null",
        native_available=load_native() is not None,
        host_cpus=os.cpu_count(),
        decode_ok=decode_ok,
        decode_s=round(t_decode, 2),
        decode_mb_per_s=round(mb / t_decode, 1),
        native_s=round(t_native, 2),
        native_mb_per_s=round(mb / t_native, 1),
        write_mb_per_s=round(mb / t_write, 1),
        python_mb_per_s=round(mb_py / t_python, 1),
        speedup=round((mb / t_native) / (mb_py / t_python), 1),
    )
    _mark(
        f"ingest measured ({mb:.0f} MB: decode {mb/t_decode:.0f} MB/s, "
        f"full {mb/t_native:.0f} MB/s)"
    )

    # ---- end-to-end GLMix from disk (MovieLens-shaped) --------------------
    # VERDICT r03 item 5 / r04 item 4: the number BASELINE.md's north star
    # needs — full cli-equivalent pipeline from Avro files on disk to a
    # trained model, stage walls reported separately. Shape mirrors
    # MovieLens-20M's GLMix factorization (fixed effect + per-user +
    # per-movie random effects; user:movie ratio ~5:1) at MovieLens-20M
    # scale: 20M rows / ~138k users / ~27k movies by default
    # (PHOTON_BENCH_E2E_ROWS overrides; the CPU fallback uses 100k).
    e2e = {}
    try:
        from photon_ml_tpu.utils.knobs import get_knob as _get_knob

        e2e_rows = int(_get_knob("PHOTON_BENCH_E2E_ROWS"))
        elapsed_so_far = time.perf_counter() - t_start
        if elapsed_so_far > 1100:
            raise RuntimeError(f"bench already at {elapsed_so_far:.0f}s")
        from photon_ml_tpu.data.game_dataset import FixedEffectDataConfig
        from photon_ml_tpu.estimators.game_estimator import GameEstimator
        from photon_ml_tpu.evaluation.suite import EvaluationSuite, EvaluatorType
        from photon_ml_tpu.utils import faults

        # Robustness counters cover ONLY the e2e pipeline: a clean run
        # emits zeros; a nonzero retries/diverged_steps/
        # fallback_sync_uploads in a bench artifact is a loud robustness
        # regression signal (a data plane or solver quietly limping).
        faults.reset_counters()

        n_users = max(200, e2e_rows // 145)
        n_movies = max(50, e2e_rows // 740)
        k_e2e = 8
        d_e2e = 200
        rng_e = np.random.default_rng(23)
        with tempfile.TemporaryDirectory() as td:
            t0 = time.perf_counter()
            users_col = rng_e.integers(0, n_users, size=e2e_rows)
            movies_col = rng_e.integers(0, n_movies, size=e2e_rows)
            indptr_e = np.arange(e2e_rows + 1, dtype=np.int64) * k_e2e
            ids_e = rng_e.integers(0, d_e2e, size=e2e_rows * k_e2e).astype(
                np.int32
            )
            vals_e = rng_e.normal(size=e2e_rows * k_e2e)
            # Labels carry real fixed + per-user + per-movie structure so
            # the reported AUC means something.
            w_true = rng_e.normal(size=d_e2e) * 0.3
            margin_e = (
                (vals_e * w_true[ids_e]).reshape(e2e_rows, k_e2e).sum(axis=1)
                + rng_e.normal(size=n_users)[users_col] * 0.7
                + rng_e.normal(size=n_movies)[movies_col] * 0.7
            )
            labels_e = (
                rng_e.uniform(size=e2e_rows) < 1 / (1 + np.exp(-margin_e))
            ).astype(np.float64)
            names_e = [f"f{i}" for i in range(d_e2e)]
            # Two files (the multi-file fan-out path); userId and movieId
            # written as native INTEGER tags — the writer formats the ids
            # in C and the reader hands back factorized columns
            # (tag_codes), so no 10^7-row Python string handling anywhere.
            half = e2e_rows // 2
            for fi, (lo, hi) in enumerate([(0, half), (half, e2e_rows)]):
                write_training_examples_columnar(
                    os.path.join(td, f"part-{fi}.avro"),
                    labels_e[lo:hi],
                    indptr_e[lo : hi + 1] - indptr_e[lo],
                    ids_e[indptr_e[lo] : indptr_e[hi]],
                    vals_e[indptr_e[lo] : indptr_e[hi]],
                    names_e,
                    int_tags={
                        "userId": users_col[lo:hi],
                        "movieId": movies_col[lo:hi],
                    },
                )
            gen_s = time.perf_counter() - t0
            total_mb = sum(
                os.path.getsize(os.path.join(td, f)) / 1e6
                for f in os.listdir(td)
            )
            _mark(f"e2e data written ({e2e_rows} rows, {total_mb:.0f} MB, {gen_s:.0f}s)")

            t0 = time.perf_counter()
            ds_e, _maps_e = ad.read_game_dataset(
                td,
                {"g": ad.FeatureShardConfig(("features",), True)},
                id_tag_fields=["userId", "movieId"],
            )
            ingest_s = time.perf_counter() - t0
            # Ingest stage breakdown (r09 streaming data plane): the same
            # loud missing-key contract the fit_timing stages carry — an
            # artifact that silently lost its ingest attribution is a
            # measurement bug, so fail the section rather than ship it.
            from photon_ml_tpu.utils.contracts import (
                INGEST_STAGES,
                INGEST_TIMING_REQUIRED_KEYS,
            )

            ingest_timing = dict(getattr(ds_e, "ingest_timing", {}))
            missing_ing = [
                k for k in INGEST_TIMING_REQUIRED_KEYS if k not in ingest_timing
            ]
            if missing_ing:
                raise RuntimeError(
                    f"ingest_timing is missing stage keys {missing_ing} "
                    f"(got {sorted(ingest_timing)}) — the e2e ingest "
                    "breakdown contract is broken"
                )
            ingest_breakdown = {
                k: round(float(ingest_timing[k]), 2)
                for k in (*INGEST_STAGES, "other")
            }
            _mark(
                f"e2e ingest {ingest_s:.1f}s ({total_mb/ingest_s:.0f} MB/s, "
                f"{ingest_timing['ingest_path']}, "
                f"streaming={ingest_timing['streaming']})"
            )

            t0 = time.perf_counter()
            est = GameEstimator(
                TaskType.LOGISTIC_REGRESSION,
                {
                    "global": FixedEffectDataConfig("g"),
                    # Active-data caps bound the padded per-entity blocks in HBM
                    # (the reference's reservoir cap for oversized entities,
                    # RandomEffectDataset.scala:339): ML-shaped movies average
                    # ~740 rows each, so an uncapped per-movie block blows a
                    # single chip at >=2M rows.
                    # Above ~4M rows the caps tighten further: the per-bucket
                    # (E, S, K) training blocks are persistent device state,
                    # and 20M rows x 2 RE coordinates at 256/512 caps would
                    # put total HBM within noise of the 16 GB chip budget.
                    "per-user": RandomEffectDataConfig(
                        "userId",
                        "g",
                        active_upper_bound=256 if e2e_rows <= 4_000_000 else 128,
                        min_bucket=8,
                    ),
                    "per-movie": RandomEffectDataConfig(
                        "movieId",
                        "g",
                        active_upper_bound=512 if e2e_rows <= 4_000_000 else 256,
                        min_bucket=8,
                    ),
                },
                coordinate_descent_iterations=1,
            )
            cfgs_e = {
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
            results_e = est.fit(ds_e, None, [cfgs_e])
            train_s = time.perf_counter() - t0
            fit_timing = dict(est.fit_timing)
            # Per-stage prepare breakdown (VERDICT r05 "Next round" #1): the
            # trajectory needs it to attribute the host wall, so a missing
            # stage key is a BENCH BUG and must fail the e2e section loudly,
            # not ship an artifact that silently lost its breakdown.
            # The full schema (stages + residual + pack placement split
            # (r06) + the entity-sharding decision (r07)) lives in
            # utils/contracts.py — one source of truth, drift-checked.
            from photon_ml_tpu.utils.contracts import (
                FIT_TIMING_REQUIRED_KEYS,
                PREPARE_STAGES,
            )

            missing_stages = [
                k for k in FIT_TIMING_REQUIRED_KEYS if k not in fit_timing
            ]
            if missing_stages:
                raise RuntimeError(
                    f"fit_timing is missing prepare stage keys {missing_stages} "
                    f"(got {sorted(fit_timing)}) — the e2e breakdown contract "
                    "is broken"
                )
            prepare_breakdown = {
                k: round(fit_timing[k], 2) for k in (*PREPARE_STAGES, "other")
            }
            _mark(f"e2e train {train_s:.1f}s ({fit_timing})")

            # Run-profile round trip (ISSUE 11): persist the fit's
            # profile.json and RE-READ it through telemetry.read_profile —
            # the same loud missing-key contract the planner will consume
            # it with. A profile that silently lost a section fails the
            # e2e section here, not at plan time.
            from photon_ml_tpu.utils import telemetry as _tel

            prof_e2e = est.run_profile()
            # The scoring section's calibrated rep count rides the
            # profile as plan evidence (ISSUE 14 satellite): a repeat
            # round planning from this profile starts calibrated and
            # skips the rtt-adaptation ladder.
            prof_e2e["dispatch"]["bench_score_reps"] = score_reps
            profile_back = _tel.read_profile(
                _tel.write_profile(
                    os.path.join(td, "profile.json"), prof_e2e
                ),
                kind="fit",
            )
            _mark(
                "e2e profile round-tripped "
                f"({len(profile_back['bucket_shapes'])} coordinate "
                "bucket-shape set(s))"
            )
            # Persist outside the tempdir for the NEXT round when the
            # operator named a plan-profile path.
            _plan_profile_path = str(_get_knob("PHOTON_PLAN_PROFILE")).strip()
            if _plan_profile_path:
                _tel.write_profile(_plan_profile_path, prof_e2e)
                _mark(f"e2e profile persisted to {_plan_profile_path}")

            t0 = time.perf_counter()
            from photon_ml_tpu.transformers.game_transformer import (
                GameTransformer,
            )

            # Scoring the TRAINING dataset reuses fit()'s prepared arrays
            # (projected shards + entity rows) — the transform must not
            # re-run the projector over 2M rows it already resolved.
            scores_e = GameTransformer(
                results_e[0].model, est.scoring_specs(), est.task
            ).transform(ds_e, prepared=est.training_prepared())
            suite_e = EvaluationSuite(
                [EvaluatorType("AUC")],
                jnp.asarray(labels_e.astype(np.float32)),
            )
            eval_res = suite_e.evaluate(scores_e.scores)
            eval_s = time.perf_counter() - t0
            fault_counts = faults.counters()
            e2e = dict(
                rows=e2e_rows,
                n_users=n_users,
                n_movies=n_movies,
                file_mb=round(total_mb, 0),
                gen_s=round(gen_s, 1),
                ingest_s=round(ingest_s, 1),
                ingest_mb_per_s=round(total_mb / ingest_s, 1),
                ingest_breakdown=ingest_breakdown,
                ingest_path=ingest_timing["ingest_path"],
                ingest_streaming=bool(ingest_timing["streaming"]),
                ingest_chunks=int(ingest_timing["chunks"]),
                train_s=round(train_s, 1),
                prepare_s=round(fit_timing["prepare_s"], 1),
                prepare_breakdown=prepare_breakdown,
                pack_device_s=round(fit_timing["pack_device_s"], 3),
                pack_host_s=round(fit_timing["pack_host_s"], 2),
                pack_path=fit_timing["pack_path"],
                re_device_s=round(fit_timing["re_device_s"], 2),
                re_host_s=round(fit_timing["re_host_s"], 2),
                re_path=fit_timing["re_path"],
                solve_s=round(fit_timing["solve_s"], 1),
                sharding=fit_timing["sharding"],
                train_rows_per_s=round(e2e_rows / train_s, 0),
                eval_s=round(eval_s, 1),
                auc=round(float(eval_res.primary_value), 4),
                total_excl_gen_s=round(ingest_s + train_s + eval_s, 1),
                retries=int(fault_counts.get("retries", 0)),
                diverged_steps=int(fit_timing.get("diverged_steps", 0)),
                fallback_sync_uploads=int(
                    fault_counts.get("fallback_sync_uploads", 0)
                ),
                # The pod-scale mesh counters for THIS fit (all-zero on a
                # clean run; schema = ROBUSTNESS_CLEAN_ZERO_KEYS).
                robustness=dict(fit_timing["robustness"]),
                # Proof the persisted planner profile re-read through its
                # loud contract (telemetry.read_profile above).
                profile_roundtrip_ok=True,
                profile_dispatch=dict(profile_back["dispatch"]),
            )
            _mark(f"e2e done: {e2e}")
    except Exception as exc:  # noqa: BLE001 - bench must still print a line
        import traceback

        traceback.print_exc(file=sys.stderr)
        e2e = dict(skipped=True, reason=f"{type(exc).__name__}: {exc}")
    variants["e2e_from_disk"] = e2e

    # ---- measured baseline surrogate --------------------------------------
    surrogate = _measure_baseline_surrogate(n, d_fixed, stats["fn_evals"])
    vs_baseline = round(surrogate["estimated_wall_s"] / dense_wall, 2)

    print(
        json.dumps(
            dict(
                metric="glmix_train_samples_per_s",
                value=round(n / glmix_wall, 1),
                unit="samples/s",
                vs_baseline=vs_baseline,
                baseline_basis=(
                    "measured f64 numpy-BLAS value+gradient passes (the "
                    "reference aggregator hot loop without Spark overhead) "
                    "on this host, scaled linearly in rows x same fn_evals; "
                    "ratio is for the dense_lbfgs variant"
                ),
                baseline=surrogate,
                wall_s=round(glmix_wall, 3),
                platform=platform,
                n_samples=n,
                d_fixed=d_fixed,
                n_entities=n_entities,
                variants=variants,
            )
        )
    )


def _multihost_chaos_child() -> None:
    """DCN-scale production certificate (ISSUE 17): whole OS processes as
    the failure domain, driven through the REAL cli entrypoints (the
    supervisors spawn their own worker processes). Phases:

      1. PARITY: `cli/train --multihost 1` vs `--multihost 2` on the same
         4-file corpus at the same global device count (1x8 vs 2x4) —
         the model artifacts must match record for record, with each
         2-host worker Avro-decoding only its own disjoint file slice.
      2. CHAOS FIT: a 2-host fit, host 1 SIGKILLed after the first
         checkpoint commit — the supervisor must journal the host loss,
         relaunch on the survivor set, and finish having repeated
         exactly ONE sweep.
      3. CHAOS SERVE: a 2-host serve fleet (host-local stores: each host
         stages only its own row partition), host 1 SIGKILLed mid-replay
         with zero retry budget — every request must still answer (the
         lost rows FE-only through the survivor, bitwise-checked per
         answer against a single-process serve reference).

    DCN traffic is measured as the bytes moved through the rendezvous
    exchange (ingest row planes, barriers, heartbeats, commit markers) —
    the filesystem stands in for DCN on CPU hosts, so its file sizes ARE
    the cross-host bytes. Prints exactly one JSON line."""
    import shutil
    import signal
    import tempfile

    import numpy as np

    from photon_ml_tpu.cli import build_index
    from photon_ml_tpu.io import avro as avro_io
    from photon_ml_tpu.io.avro_data import write_training_examples

    repo = os.path.dirname(os.path.abspath(__file__))
    shard_dsl = "name=globalShard,feature.bags=features,intercept=true"
    coord_dsls = [
        "name=global,feature.shard=globalShard,optimizer=LBFGS,"
        "tolerance=1e-7,max.iter=25,regularization=L2,reg.weights=0.1",
        "name=per-member,random.effect.type=memberId,"
        "feature.shard=globalShard,optimizer=LBFGS,max.iter=15,"
        "regularization=L2,reg.weights=1,min.bucket=4,projector=IDENTITY",
    ]

    def _env(**extra):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        env.update(extra)
        return env

    root = tempfile.mkdtemp(prefix="photon-mh-bench-")
    try:
        data = os.path.join(root, "data")
        os.makedirs(data)
        w_true = np.random.default_rng(99).normal(size=4)
        b_true = np.random.default_rng(98).normal(size=(10, 2))
        for seed, n in enumerate((120, 80, 100, 60)):
            rng = np.random.default_rng(seed)
            X = rng.normal(size=(n, 4))
            entity = rng.integers(0, 10, size=n)
            margins = X @ w_true + np.einsum(
                "nd,nd->n", X[:, :2], b_true[entity]
            )
            y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margins))).astype(
                np.float32
            )
            write_training_examples(
                os.path.join(data, f"part-{seed}.avro"),
                [
                    [(f"f{j}", float(X[i, j])) for j in range(4)]
                    for i in range(n)
                ],
                y.tolist(),
                uids=[f"uid{seed}_{i}" for i in range(n)],
                id_tags={"memberId": [f"m{e}" for e in entity]},
            )
        idx = os.path.join(root, "index")
        build_index.main([
            "--input-data-directories", data,
            "--feature-shard-configurations", shard_dsl,
            "--output-dir", idx,
        ])

        def train_argv(out, n_hosts, iters):
            return [
                sys.executable, "-m", "photon_ml_tpu.cli.train",
                "--training-task", "LOGISTIC_REGRESSION",
                "--input-data-directories", data,
                "--root-output-directory", out,
                "--feature-shard-configurations", shard_dsl,
                "--coordinate-configurations", *coord_dsls,
                "--coordinate-descent-iterations", str(iters),
                "--offheap-indexmap-dir", idx,
                "--checkpoint-directory", os.path.join(out, "ckpt"),
                "--multihost", str(n_hosts),
                "--multihost-devices-per-host", str(8 // n_hosts),
                "--random-seed", "7",
            ]

        def run_fit(out, n_hosts, iters):
            r = subprocess.run(
                train_argv(out, n_hosts, iters),
                env=_env(), capture_output=True, text=True, timeout=600,
            )
            if r.returncode != 0:
                raise RuntimeError(
                    f"--multihost {n_hosts} fit failed: {r.stderr[-1500:]}"
                )
            with open(os.path.join(out, "training-summary.json")) as f:
                return json.load(f)

        def model_records(out):
            blobs = {}
            mdir = os.path.join(out, "models", "best")
            for dirpath, _, files in os.walk(mdir):
                for fn in sorted(files):
                    p = os.path.join(dirpath, fn)
                    rel = os.path.relpath(p, mdir)
                    if fn.endswith(".avro"):
                        blobs[rel] = repr(avro_io.read_container(p)[1])
                    else:
                        with open(p, "rb") as f:
                            blobs[rel] = f.read()
            return blobs

        # -- 1: parity + disjoint ingest ---------------------------------
        out1 = os.path.join(root, "fit1")
        out2 = os.path.join(root, "fit2")
        s1 = run_fit(out1, 1, 2)
        s2 = run_fit(out2, 2, 2)
        b1, b2 = model_records(out1), model_records(out2)
        fit_bitwise = set(b1) == set(b2) and all(
            b1[k] == b2[k] for k in b1
        )
        files_host0 = int(s2["files_this_host"])
        n_files = int(s2["num_files"])
        files_per_host = [files_host0, n_files - files_host0]
        ingest_disjoint_ok = 0 < files_host0 < n_files
        dcn_bytes = 0
        for dirpath, _, files in os.walk(os.path.join(out2, "rendezvous")):
            for fn in files:
                try:
                    dcn_bytes += os.path.getsize(os.path.join(dirpath, fn))
                except OSError:
                    pass
        del s1

        # -- 2: SIGKILL a whole host mid-fit -----------------------------
        outc = os.path.join(root, "fit_chaos")
        sup = subprocess.Popen(
            train_argv(outc, 2, 8),
            env=_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )
        state = os.path.join(outc, "ckpt", "state.json")
        pid_file = os.path.join(outc, "hosts", "attempt0-host1", "pid")
        deadline = time.time() + 300
        while time.time() < deadline and not os.path.exists(state):
            if sup.poll() is not None:
                raise RuntimeError(
                    "chaos fit supervisor exited before first commit: "
                    + sup.communicate()[1][-1500:]
                )
            time.sleep(0.05)
        os.kill(int(open(pid_file).read()), signal.SIGKILL)
        _, err = sup.communicate(timeout=600)
        if sup.returncode != 0:
            raise RuntimeError(f"chaos fit failed: {err[-1500:]}")
        with open(os.path.join(outc, "training-summary.json")) as f:
            mh_fit = json.load(f)["multihost"]

        # -- 3: SIGKILL a serving host mid-replay ------------------------
        model_dir = os.path.join(out1, "models", "best")

        def serve_argv(out):
            return [
                sys.executable, "-m", "photon_ml_tpu.cli.serve",
                "--model-input-directory", model_dir,
                "--requests", data,
                "--root-output-directory", out,
                "--feature-shard-configurations", shard_dsl,
                "--offheap-indexmap-dir", idx,
                "--model-id", "bench",
            ]

        def read_scores(out):
            recs = {}
            for p in sorted(
                avro_io.list_container_files(os.path.join(out, "scores"))
            ):
                for r in avro_io.read_container(p)[1]:
                    recs[r["uid"]] = r["predictionScore"]
            return recs

        ref_out = os.path.join(root, "serve_ref")
        r = subprocess.run(
            serve_argv(ref_out),
            env=_env(
                XLA_FLAGS="--xla_force_host_platform_device_count=4",
                PHOTON_SERVING_ENTITY_SHARD="1",
            ),
            capture_output=True, text=True, timeout=600,
        )
        if r.returncode != 0:
            raise RuntimeError(f"reference serve failed: {r.stderr[-1500:]}")
        ref = read_scores(ref_out)

        mh_out = os.path.join(root, "serve_mh")
        sup = subprocess.Popen(
            serve_argv(mh_out) + ["--multihost", "2"],
            env=_env(PHOTON_HOST_LOSS_RETRIES="0"),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        pid_file = os.path.join(mh_out, "hosts", "attempt0-host1", "pid")
        deadline = time.time() + 300
        while time.time() < deadline and not os.path.exists(pid_file):
            if sup.poll() is not None:
                raise RuntimeError(
                    "serve supervisor exited before workers came up: "
                    + sup.communicate()[1][-1500:]
                )
            time.sleep(0.02)
        os.kill(int(open(pid_file).read()), signal.SIGKILL)
        _, err = sup.communicate(timeout=600)
        if sup.returncode != 0:
            raise RuntimeError(f"chaos serve failed: {err[-1500:]}")
        with open(os.path.join(mh_out, "serving-summary.json")) as f:
            serve_summary = json.load(f)
        mh_serve = serve_summary["multihost"]
        # Per-answer residency check against the reference: the survivor's
        # result lines carry n_lost, so every answer WITHOUT a shard-loss
        # fallback must be bitwise-identical to the single-process serve.
        resident_ok = True
        res_dir = os.path.join(mh_out, "hosts", "attempt0-host0", "results")
        for fn in sorted(os.listdir(res_dir)):
            if not fn.endswith(".jsonl"):
                continue
            with open(os.path.join(res_dir, fn)) as f:
                for line in f:
                    ln = json.loads(line)
                    if ln["n_lost"] == 0 and ref.get(ln["uid"]) != ln["score"]:
                        resident_ok = False

        print(json.dumps({
            "n_hosts": 2,
            "devices_per_host": 4,
            "files_per_host": files_per_host,
            "fit_bitwise_vs_single_process": bool(fit_bitwise),
            "ingest_disjoint_ok": bool(ingest_disjoint_ok),
            "host_losses": int(mh_fit["host_losses"]),
            "repeated_sweeps": int(mh_fit["repeated_sweeps"]),
            "survivor_hosts": int(mh_serve["survivor_hosts"]),
            "failed_requests": int(serve_summary["failed_requests"]),
            "fe_only_answers": int(mh_serve["fe_only_answers"]),
            "serve_bitwise_resident": bool(resident_ok),
            "dcn_collective_bytes": int(dcn_bytes),
        }))
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main() -> None:
    if _MULTICHIP_CHILD in sys.argv:
        _multichip_child()
        return
    if _CHAOS_MULTICHIP_CHILD in sys.argv:
        _chaos_multichip_child()
        return
    if _ELASTIC_MESH_CHILD in sys.argv:
        _elastic_mesh_child()
        return
    if _MULTI_TENANT_CHILD in sys.argv:
        _multi_tenant_child()
        return
    if _CONTINUOUS_LOOP_CHILD in sys.argv:
        _continuous_loop_child()
        return
    if _MULTIHOST_CHAOS_CHILD in sys.argv:
        _multihost_chaos_child()
        return
    if _SHADOW_DEPLOY_CHILD in sys.argv:
        _shadow_deploy_child()
        return
    if _SHADOW_PROMOTE_WORKER in sys.argv:
        _shadow_promote_worker()
        return
    if _AUTOPILOT_CHILD in sys.argv:
        _autopilot_child()
        return
    if _CHILD in sys.argv:
        _child()
        return

    # One attempt, on whatever backend JAX finds. A child that fails or
    # times out makes this process exit non-zero with the child's stderr:
    # there is no second try on another backend and no placeholder result,
    # because either would print under the names of device metrics.
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), _CHILD],
            capture_output=True,
            text=True,
            timeout=1800,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired as exc:
        sys.stderr.write(str(exc.stderr or "")[-4000:] + "\n")
        raise SystemExit("bench: the measurement child timed out") from None
    if out.returncode == 0:
        for line in out.stdout.splitlines():
            if line.startswith("{"):
                print(line)
                return
    sys.stderr.write(out.stderr[-4000:] + "\n")
    raise SystemExit(
        f"bench: the measurement child failed (exit code {out.returncode})"
    )


if __name__ == "__main__":
    main()
