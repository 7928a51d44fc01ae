"""chip_smoke.py — the quickest proof that GLMix train -> serve starts on the chip.

    python chip_smoke.py                 # one chip: data -> cli.train -> cli.serve
    python chip_smoke.py --four-chips    # sharded fit + sharded serving on four

One chip (what the driver runs). From `--seed`, write MovieLens-shaped GLMix
training Avro (d = 200 named features, 8 non-zeros per row, integer userId /
movieId tags, user:movie about 5:1), fit fixed effect + per-user + per-movie
random effects with `python -m photon_ml_tpu.cli.train`, replay JSONL requests
through `python -m photon_ml_tpu.cli.serve`, recompute the answers in plain
numpy float32 from the written model, and run the dense Pallas probe. Then
check what the runs themselves wrote (training-summary.json, profile.json,
serving-summary.json): AUC, zero retry/degrade counters, the bucketed pack and
sparse Pallas kernels engaged, the pack and assembly routes, native ingest,
serving parity, compile-cache traffic.

One process per chip: this parent never imports JAX. Every stage is a child
process that exits before the next starts, and the device facts in the last
line come from what the training child recorded (profile.json,
`device_topology`). Every check is evaluated and printed; the verdict comes
after all stages ran. A stage that raises or exits non-zero ends the run
non-zero at once. Under JAX_PLATFORMS=cpu every stage still runs and the
correctness checks must pass, but the engagement checks fail by design
(kernels and device routes are TPU-only), so the last line says "ok": false
and the exit code is 1.

The last line of stdout is exactly
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# The published shape (MovieLens-like, the one BENCH_r05.json's from-disk job
# ran and benchmarks/configs/glmix-movielens.json states): never cut.
D_FEATURES = 200
NNZ_PER_ROW = 8
FULL_ROWS = 20_000_000  # BENCH_r05's e2e scale; the smoke cuts rows only
DEFAULT_ROWS = 2_000_000
FOUR_CHIP_ROWS = 200_000  # the four-chip option trains its model smaller

# Validation AUC floor. Labels are Bernoulli(sigmoid(margin)) with margin =
# fixed (8 of 200 N(0, 0.3^2) weights x N(0,1) values) + per-user N(0, 0.7^2)
# + per-movie N(0, 0.7^2). On such labels the true margin itself scores 0.784
# AUC, the true fixed effect alone 0.683 and the true random effects alone
# 0.710 (numpy, 220k rows of this generator), so a fit must have learned BOTH
# the fixed and the random effects to clear 0.72; one that learned neither or
# only one (zero coefficients, crossed entity ids, a broken solver) cannot.
AUC_FLOOR = 0.72

STAGE_TIMEOUT_S = 1000


def say(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


class Checks:
    """Every check is evaluated and printed; the verdict is their AND."""

    def __init__(self) -> None:
        self.failed = []

    def check(self, name: str, ok: bool, kind: str, **evidence) -> None:
        say(check=name, passed=bool(ok), kind=kind, **evidence)
        if not ok:
            self.failed.append(name)

    def verdict(self, platform: str, kind: str, count: int, chips: int) -> int:
        """Print the last line — `ok` only if every check held, on at least
        `chips` TPU devices — and return the exit code."""
        self.check("platform_is_tpu", platform == "tpu", "engagement", platform=platform)
        self.check("enough_devices", count >= chips, "engagement", count=count, needed=chips)
        if self.failed:
            say(failed_checks=self.failed)
        print(json.dumps({
            "ok": not self.failed,
            "device": {"platform": platform, "kind": kind, "count": count},
        }), flush=True)
        return 1 if self.failed else 0


def run_child(stage: str, argv, workdir: str, *, env=None) -> "tuple[float, str]":
    """Run one stage as a child process to completion; returns (wall, its
    stdout). Its stderr is kept in <workdir>/<stage>.stderr.txt. A non-zero
    exit (or the time limit) ends the smoke at once with the end of it."""
    err_path = os.path.join(workdir, f"{stage}.stderr.txt")
    t0 = time.perf_counter()
    with open(err_path, "w") as err:
        try:
            out = subprocess.run(
                argv, cwd=HERE, env=env, timeout=STAGE_TIMEOUT_S,
                stdout=subprocess.PIPE, stderr=err, text=True,
            )
            failure = (
                None if out.returncode == 0
                else f"exited with code {out.returncode}"
            )
        except subprocess.TimeoutExpired:  # run() has killed the child
            failure = f"passed its {STAGE_TIMEOUT_S}s limit"
    if failure:
        with open(err_path) as err:
            sys.stderr.write(err.read()[-6000:] + "\n")
        raise SystemExit(f"chip_smoke: stage {stage} {failure}")
    return time.perf_counter() - t0, out.stdout


def self_stage(stage: str, args, *, cpu_only: bool) -> "tuple[float, dict]":
    """Re-invoke this script for one in-repo stage; its last stdout line is
    the stage's JSON result. `cpu_only` stages (numpy work that imports the
    repo) are held off the chip so they can never take it from a later
    stage."""
    env = dict(os.environ)
    if cpu_only:
        env["JAX_PLATFORMS"] = "cpu"
    argv = [
        sys.executable, os.path.abspath(__file__), "--stage", stage,
        "--rows", str(args.rows), "--seed", str(args.seed),
        "--requests", str(args.requests), "--workdir", args.workdir,
    ]
    wall, stdout = run_child(stage, argv, args.workdir, env=env)
    return wall, json.loads(stdout.strip().splitlines()[-1])


# --------------------------------------------------------------- the data


def entity_counts(rows: int) -> "tuple[int, int]":
    return max(200, rows // 145), max(50, rows // 740)


def stage_data(args) -> dict:
    """Training + validation Avro and the JSONL request stream, all from
    --seed, through the native columnar writer."""
    import numpy as np

    from photon_ml_tpu.native import build as native_build
    from photon_ml_tpu.native.avro_writer import write_training_examples_columnar

    if native_build.load_native() is None:
        raise SystemExit(
            "chip_smoke: the native library is absent: "
            f"{native_build.build_error() or 'PHOTON_DISABLE_NATIVE is set'}"
        )
    rng = np.random.default_rng(args.seed)
    rows = args.rows
    n_val = min(max(rows // 10, 2_000), 200_000)
    n_users, n_movies = entity_counts(rows)
    n_all = rows + n_val
    users = rng.integers(0, n_users, size=n_all)
    movies = rng.integers(0, n_movies, size=n_all)
    ids = rng.integers(0, D_FEATURES, size=n_all * NNZ_PER_ROW).astype(np.int32)
    vals = rng.normal(size=n_all * NNZ_PER_ROW)
    w_true = rng.normal(size=D_FEATURES) * 0.3
    u_true = rng.normal(size=n_users) * 0.7
    m_true = rng.normal(size=n_movies) * 0.7
    margin = (
        (vals * w_true[ids]).reshape(n_all, NNZ_PER_ROW).sum(axis=1)
        + u_true[users] + m_true[movies]
    )
    labels = (rng.uniform(size=n_all) < 1 / (1 + np.exp(-margin))).astype(np.float64)
    names = [f"f{i}" for i in range(D_FEATURES)]
    indptr = np.arange(n_all + 1, dtype=np.int64) * NNZ_PER_ROW

    def write(path, lo, hi):
        write_training_examples_columnar(
            path, labels[lo:hi], indptr[lo : hi + 1] - indptr[lo],
            ids[indptr[lo] : indptr[hi]], vals[indptr[lo] : indptr[hi]], names,
            int_tags={"userId": users[lo:hi], "movieId": movies[lo:hi]},
        )

    train_dir = os.path.join(args.workdir, "train")
    val_dir = os.path.join(args.workdir, "validation")
    os.makedirs(train_dir, exist_ok=True)
    os.makedirs(val_dir, exist_ok=True)
    half = rows // 2  # two files: the multi-file fan-out path
    write(os.path.join(train_dir, "part-0.avro"), 0, half)
    write(os.path.join(train_dir, "part-1.avro"), half, rows)
    write(os.path.join(val_dir, "part-0.avro"), rows, n_all)

    # Requests: three in four name entities the model has seen, one in four
    # a cold-start id it has not (both for the user and the movie).
    n_req = args.requests
    with open(os.path.join(args.workdir, "requests.jsonl"), "w") as f:
        for i in range(n_req):
            cold = i % 4 == 3
            feat_ids = rng.choice(D_FEATURES, size=NNZ_PER_ROW, replace=False)
            feats = {f"f{j}": float(np.float32(rng.normal())) for j in feat_ids}
            feats["(INTERCEPT)"] = 1.0
            f.write(json.dumps({
                "uid": f"r{i}",
                "offset": float(np.float32(rng.normal() * 0.1)),
                "ids": {
                    "userId": str(n_users + i if cold else int(users[i])),
                    "movieId": str(n_movies + i if cold else int(movies[i])),
                },
                "features": {"g": feats},
            }) + "\n")

    def auc(score, y):
        order = np.argsort(score, kind="stable")
        ranks = np.empty(len(score))
        ranks[order] = np.arange(1, len(score) + 1)
        pos = y > 0.5
        return float(
            (ranks[pos].sum() - pos.sum() * (pos.sum() + 1) / 2)
            / max(pos.sum() * (~pos).sum(), 1)
        )

    mb = sum(
        os.path.getsize(os.path.join(d, f))
        for d in (train_dir, val_dir) for f in os.listdir(d)
    ) / 1e6
    return {
        "rows": rows, "validation_rows": n_val, "users": n_users,
        "movies": n_movies, "d": D_FEATURES, "nnz_per_row": NNZ_PER_ROW,
        "avro_mb": round(mb, 1), "requests": n_req,
        "true_margin_auc": round(auc(margin[rows:], labels[rows:]), 4),
    }


# ------------------------------------------------------- train and serve


def train_argv(args, out_dir: str) -> list:
    # The configuration of BENCH_r05.json's from-disk job: the reservoir
    # caps bound the padded per-entity blocks in HBM.
    cap_user, cap_movie = (256, 512) if args.rows <= 4_000_000 else (128, 256)
    return [
        sys.executable, "-m", "photon_ml_tpu.cli.train",
        "--training-task", "LOGISTIC_REGRESSION",
        "--input-data-directories", os.path.join(args.workdir, "train"),
        "--validation-data-directories", os.path.join(args.workdir, "validation"),
        "--root-output-directory", out_dir,
        "--override-output-directory",
        "--feature-shard-configurations",
        "name=g,feature.bags=features,intercept=true",
        "--coordinate-configurations",
        "name=global,feature.shard=g,optimizer=LBFGS,tolerance=1.0E-6,"
        "max.iter=10,regularization=L2,reg.weights=1",
        "name=per-user,feature.shard=g,random.effect.type=userId,"
        f"active.data.upper.bound={cap_user},min.bucket=8,optimizer=LBFGS,"
        "tolerance=1.0E-5,max.iter=5,regularization=L2,reg.weights=10",
        "name=per-movie,feature.shard=g,random.effect.type=movieId,"
        f"active.data.upper.bound={cap_movie},min.bucket=8,optimizer=LBFGS,"
        "tolerance=1.0E-5,max.iter=5,regularization=L2,reg.weights=10",
        "--coordinate-descent-iterations", "2",
        "--validation-evaluators", "AUC",
        "--random-seed", str(args.seed),
        "--logging-level", "WARNING",
    ]


def serve_argv(args, model_dir: str, out_dir: str) -> list:
    return [
        sys.executable, "-m", "photon_ml_tpu.cli.serve",
        "--model-input-directory", model_dir,
        "--requests", os.path.join(args.workdir, "requests.jsonl"),
        "--root-output-directory", out_dir,
        "--max-batch", "64",
        "--logging-level", "WARNING",
    ]


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


# Counters of the metrics snapshot that count a retry, a fallback or a
# degraded answer: every one is 0 on a run that used the chip as written.
_UNCLEAN = (
    "retr", "fallback", "rollback", "degraded", "failure", "fe_only", "shed",
    "miss", "loss", "trip", "quarantin", "injected", "demotion",
)


def unclean_counters(metrics: dict) -> dict:
    return {
        k: v for k, v in (metrics.get("counters") or {}).items()
        if v and any(tag in k for tag in _UNCLEAN)
    }


def cache_traffic(metrics: dict) -> "tuple[int, int]":
    """(programs compiled, programs read back) by one stage's process."""
    c = metrics.get("counters") or {}
    hits = int(c.get("compile_cache_hits", 0))
    return int(c.get("compile_cache_requests", 0)) - hits, hits


def stage_verify_serve(args) -> dict:
    """The plain reference: numpy float32, from the WRITTEN model files —
    fixed-effect dot + gathered random-effect rows (zero for an entity the
    model never saw) + offset, and the sigmoid of that."""
    import glob

    import numpy as np

    from photon_ml_tpu.data.index_map import IndexMap
    from photon_ml_tpu.io import avro as avro_io
    from photon_ml_tpu.io import model_store
    from photon_ml_tpu.utils.contracts import CHIP_SMOKE_SERVING_TOLERANCE

    model_dir = os.path.join(args.workdir, "fit", "models", "best")
    imaps = {
        os.path.splitext(os.path.basename(p))[0]: IndexMap.load(p)
        for p in glob.glob(os.path.join(model_dir, "feature-indexes", "*.json"))
    }
    art = model_store.load_game_model(model_dir, imaps)
    fixed = {c: a for c, a in art.coordinates.items() if not hasattr(a, "entity_ids")}
    rand = {c: a for c, a in art.coordinates.items() if hasattr(a, "entity_ids")}
    row_of = {c: {e: i for i, e in enumerate(a.entity_ids)} for c, a in rand.items()}

    want = {}
    n_cold = 0
    with open(os.path.join(args.workdir, "requests.jsonl")) as f:
        for line in f:
            doc = json.loads(line)
            x = {}
            for shard, feats in doc["features"].items():
                v = np.zeros(imaps[shard].size, np.float32)
                for key, val in feats.items():
                    j = imaps[shard].get_index(key)
                    if j >= 0:
                        v[j] += np.float32(val)
                x[shard] = v
            z = np.float32(doc["offset"])
            for a in fixed.values():
                z = z + np.sum(x[a.feature_shard] * a.means.astype(np.float32), dtype=np.float32)
            for c, a in rand.items():
                r = row_of[c].get(str(doc["ids"][a.random_effect_type]))
                if r is None:
                    n_cold += 1
                    continue
                z = z + np.sum(x[a.feature_shard] * a.means[r].astype(np.float32), dtype=np.float32)
            want[doc["uid"]] = np.float32(z)

    got = {}
    scores_dir = os.path.join(args.workdir, "served", "scores")
    for part in avro_io.list_container_files(scores_dir):
        for _, rec in avro_io.iter_container(part):
            got[str(rec["uid"])] = np.float32(rec["predictionScore"])
    uids = sorted(want)
    missing = [u for u in uids if u not in got]
    w = np.asarray([want[u] for u in uids if u in got], np.float32)
    g = np.asarray([got[u] for u in uids if u in got], np.float32)
    sig = lambda a: 1 / (1 + np.exp(-a.astype(np.float32)))
    tol = CHIP_SMOKE_SERVING_TOLERANCE
    return {
        "answers": len(g), "missing": len(missing), "cold_lookups": n_cold,
        "finite": bool(np.isfinite(g).all()),
        "max_abs_margin_error": float(np.max(np.abs(g - w))) if len(g) else None,
        "max_abs_mean_error": float(np.max(np.abs(sig(g) - sig(w)))) if len(g) else None,
        "margin_spread": float(np.std(w)) if len(g) else None,
        "equal": bool(len(g) and not missing and np.allclose(g, w, **tol)),
        "tolerance": tol,
    }


def stage_dense_probe(args) -> dict:
    """One call of the compiled dense Pallas probe on whatever backend JAX
    finds (cli.train from Avro never builds a dense shard, so this is the
    only place the smoke sees those kernels execute), plus the device as
    this process sees it."""
    from photon_ml_tpu.utils import compile_cache

    compile_cache.enable()
    import jax

    from photon_ml_tpu.ops import pallas_glm

    dev = jax.devices()[0]
    out = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    if dev.platform == "tpu":
        out["dense_kernels_healthy"] = pallas_glm.kernels_healthy()
    else:
        # Off the chip the kernels only exist in interpret mode; the probe
        # of the COMPILED kernels is what this stage is for.
        out["dense_kernels_healthy"] = None
    return out


# ------------------------------------------------------------ four chips


def stage_four_chips(args) -> dict:
    """One process on all four chips: (a) the sharded GLMix fit of
    __graft_entry__.dryrun_multichip against the same fit on one chip,
    (b) the trained model served entity-sharded against replicated.
    Returns evidence only; the parent judges it."""
    from photon_ml_tpu.utils import compile_cache

    compile_cache.enable()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from photon_ml_tpu.data.containers import SparseFeatures
    from photon_ml_tpu.data.game_dataset import (
        GameDataset, RandomEffectDataConfig, build_random_effect_dataset,
    )
    from photon_ml_tpu.game.coordinate import (
        FixedEffectCoordinate, RandomEffectCoordinate,
    )
    from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent
    from photon_ml_tpu.optimize.config import (
        L2, CoordinateOptimizationConfig, OptimizerConfig,
    )
    from photon_ml_tpu.parallel.mesh import (
        make_mesh, pad_game_dataset, shard_game_dataset,
        shard_random_effect_dataset,
    )
    from photon_ml_tpu.types import OptimizerType, TaskType
    from photon_ml_tpu.utils import faults
    from photon_ml_tpu.utils.contracts import SHARDED_VS_SINGLE_TOLERANCES

    devices = jax.devices()
    if len(devices) < 4:
        raise SystemExit(f"chip_smoke --four-chips needs 4 devices, JAX has {len(devices)}")
    devices = devices[:4]
    mesh = make_mesh(devices)
    out = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices()),
    }

    def shards_of(a) -> dict:
        """Where one array lives: devices, largest shard, total bytes."""
        return {
            "devices": len(a.sharding.device_set),
            "max_shard_bytes": max(s.data.nbytes for s in a.addressable_shards),
            "bytes": int(a.nbytes),
        }

    def device_bytes() -> list:
        per = {d.id: 0 for d in devices}
        for a in jax.live_arrays():
            for sh in a.addressable_shards:
                if sh.device.id in per:
                    per[sh.device.id] += sh.data.nbytes
        return [per[d.id] for d in devices]

    def gap(a, b) -> "tuple[float, float]":
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.max(np.abs(a - b))), float(np.max(np.abs(b)))

    # ---- (a) the fit: sample-sharded fixed effects, entity-sharded RE ----
    rng = np.random.default_rng(args.seed)
    n, d_fixed, d_re, d_sparse, n_entities, k_nnz = 16384 * 4, 128, 8, 512, 2048, 8
    Xf = rng.normal(size=(n, d_fixed)).astype(np.float32)
    Xe = rng.normal(size=(n, d_re)).astype(np.float32)
    sp_idx = rng.integers(0, d_sparse, size=(n, k_nnz)).astype(np.int32)
    sp_val = rng.normal(size=(n, k_nnz)).astype(np.float32)
    entity = rng.integers(0, n_entities, size=n)
    w_f = rng.normal(size=d_fixed) * 0.2
    margin = Xf @ w_f + (rng.normal(size=n_entities) * 0.7)[entity]
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float32)

    def build_ds():
        return pad_game_dataset(GameDataset.build(
            {
                "global": jnp.asarray(Xf),
                "sparse_global": SparseFeatures(
                    jnp.asarray(sp_idx), jnp.asarray(sp_val), d_sparse
                ),
                "per_entity": jnp.asarray(Xe),
            },
            y, id_tags={"entityId": entity},
        ), 4)

    re_cfg = RandomEffectDataConfig("entityId", "per_entity", min_bucket=4)
    cfg_f = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=5), regularization=L2, reg_weight=0.1,
    )
    cfg_r = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(optimizer_type=OptimizerType.TRON, max_iterations=3),
        regularization=L2, reg_weight=1.0,
    )
    task = TaskType.LOGISTIC_REGRESSION

    def fit(ds, red):
        coords = {
            "fixed": FixedEffectCoordinate(ds, "global", cfg_f, task),
            "sparse": FixedEffectCoordinate(ds, "sparse_global", cfg_f, task),
            "per-entity": RandomEffectCoordinate(ds, red, cfg_r, task),
        }
        model = run_coordinate_descent(coords, 1).model
        scores = sum(c.score(model[cid]) for cid, c in coords.items())
        return coords, model, jax.block_until_ready(scores)

    faults.reset_counters()
    t0 = time.perf_counter()
    sharded = shard_game_dataset(build_ds(), mesh)
    red = shard_random_effect_dataset(
        build_random_effect_dataset(sharded, re_cfg), mesh
    )
    coords_s, model_s, scores_s = fit(sharded, red)
    re_matrix = model_s["per-entity"].coefficients_matrix
    out["fit"] = {
        "samples": int(sharded.num_samples), "entities": int(red.num_entities),
        "sharded_wall_s": round(time.perf_counter() - t0, 1),
        "dense_fixed_dispatch": type(coords_s["fixed"]._use_pallas).__name__,
        "sharded_scan_built": coords_s["per-entity"]._train_scan_sharded is not None,
        "placement": {
            "labels": shards_of(sharded.labels),
            "dense_features": shards_of(coords_s["fixed"]._features),
            "sparse_values": shards_of(sharded.shards["sparse_global"].values),
            "re_features": shards_of(sharded.shards["per_entity"]),
            "re_block_rows": shards_of(red.buckets[0].entity_rows),
            "re_matrix": shards_of(re_matrix),
            "scores": shards_of(scores_s),
        },
        "device_bytes": device_bytes(),
    }
    # The same fit on ONE of the four chips (arrays land on devices[0]).
    t0 = time.perf_counter()
    ds_one = build_ds()
    coords_1, model_1, scores_1 = fit(
        ds_one, build_random_effect_dataset(ds_one, re_cfg)
    )
    out["fit"]["one_chip_wall_s"] = round(time.perf_counter() - t0, 1)
    out["fit"]["one_chip_sparse_features"] = type(coords_1["sparse"]._features).__name__
    out["fit"]["gaps"] = {
        "fixed": gap(model_s["fixed"].coefficients.means, model_1["fixed"].coefficients.means),
        "sparse": gap(model_s["sparse"].coefficients.means, model_1["sparse"].coefficients.means),
        "per_entity": gap(
            np.asarray(re_matrix)[: red.num_entities + 1],
            np.asarray(model_1["per-entity"].coefficients_matrix)[: red.num_entities + 1],
        ),
        "scores": gap(scores_s, scores_1),
    }
    out["fit"]["finite"] = bool(np.isfinite(np.asarray(scores_s)).all())
    out["fit"]["counters"] = unclean_counters({"counters": faults.counters()})
    del coords_s, coords_1, model_s, model_1, sharded, red, ds_one, re_matrix

    # ---- (b) serving: the trained model, entity-sharded vs replicated ----
    from photon_ml_tpu.cli.serve import _iter_json_requests
    from photon_ml_tpu.serving.bundle import load_bundle
    from photon_ml_tpu.serving.engine import ServingEngine

    model_dir = os.path.join(args.workdir, "fit", "models", "best")
    req_path = os.path.join(args.workdir, "requests.jsonl")

    def serve(entity_shard: bool):
        # The knob cli.serve's users set; load_bundle reads it at each load.
        os.environ["PHOTON_SERVING_ENTITY_SHARD"] = "1" if entity_shard else "0"
        faults.reset_counters()
        bundle = load_bundle(model_dir)
        try:
            placement = {
                cid: dict(shards_of(c.params), random_effect=c.is_random_effect)
                for cid, c in bundle.coordinates.items()
            }
            with ServingEngine(bundle, max_batch=64) as engine:
                engine.warmup()
                reqs = list(_iter_json_requests(req_path, bundle, [0]))
                res = engine.score_batch(reqs)
                metrics = engine.metrics()
            return (
                np.asarray([r.score for r in res], np.float32), placement,
                {
                    "sharding": metrics.get("sharding"),
                    "recompiles_after_warmup": metrics.get("recompiles_after_warmup"),
                    "fe_only": sum(r.fe_only for r in res),
                    "lost": sum(r.n_lost for r in res),
                    "counters": unclean_counters({"counters": faults.counters()}),
                },
            )
        finally:
            bundle.release()

    t0 = time.perf_counter()
    s_sh, place_sh, m_sh = serve(True)
    s_one, place_one, m_one = serve(False)
    out["serve"] = {
        "requests": int(len(s_sh)), "wall_s": round(time.perf_counter() - t0, 1),
        "finite": bool(np.isfinite(s_sh).all()),
        "gap": gap(s_sh, s_one),
        "sharded": dict(m_sh, placement=place_sh),
        "replicated": dict(m_one, placement=place_one),
    }
    out["tolerances"] = SHARDED_VS_SINGLE_TOLERANCES
    return out


def within(gap_scale, tol) -> bool:
    """max|a - b| <= atol + rtol * max|b|: the gap against the LARGEST
    magnitude (coefficients near zero carry absolute, not relative, error)."""
    gap, scale = gap_scale
    return gap <= tol["atol"] + tol["rtol"] * scale


def four_chips(args) -> int:
    checks = Checks()
    say(
        smoke="GLMix on four chips: sharded fit and sharded serving",
        seed=args.seed,
        set_up={
            "served_model": f"cli.train on {args.rows} MovieLens-shaped rows "
            "(step 2's configuration, rows cut: four chips cost four times "
            "the seconds), random-effect assembly on the host "
            "(PHOTON_DEVICE_ASSEMBLY=0: its device programs are one-chip "
            "programs the one-chip smoke already proves, and compiling them "
            "cold again is the longest part of that run)",
            "fit": "dryrun_multichip's problem: 65,536 samples, dense d=128 + "
            "sparse d=512 fixed effects, 2,048-entity random effect d=8",
        },
    )
    wall, data = self_stage("data", args, cpu_only=True)
    say(stage="data", wall_s=round(wall, 1), **data)
    fit_dir = os.path.join(args.workdir, "fit")
    wall, _ = run_child(
        "train", train_argv(args, fit_dir), args.workdir,
        env=dict(os.environ, PHOTON_DEVICE_ASSEMBLY="0"),
    )
    say(stage="train (set-up)", wall_s=round(wall, 1),
        best_evaluation=read_json(os.path.join(fit_dir, "training-summary.json"))["best_evaluation"])

    wall, ev = self_stage("four-chips", args, cpu_only=False)
    say(stage="four-chips", wall_s=round(wall, 1), **ev)
    tol = ev["tolerances"]
    fit, serve = ev["fit"], ev["serve"]

    for name, g in fit["gaps"].items():
        checks.check(
            f"sharded_fit_agrees_{name}", within(g, tol["fit"]), "correctness",
            max_abs_gap=g[0], scale=g[1], tolerance=tol["fit"],
        )
    checks.check("sharded_fit_finite_and_clean", fit["finite"] and not fit["counters"],
                 "correctness", nonzero=fit["counters"])
    for name, pl in fit["placement"].items():
        checks.check(
            f"fit_{name}_spread_over_4",
            pl["devices"] == 4 and pl["max_shard_bytes"] * 4 <= pl["bytes"],
            "placement", **pl,
        )
    per_dev = fit["device_bytes"]
    checks.check(
        "fit_no_device_holds_more_than_its_share",
        max(per_dev) <= 1.25 * min(per_dev) + (1 << 20), "placement",
        device_bytes=per_dev,
    )
    checks.check("sharded_scan_sweep_built", fit["sharded_scan_built"], "engagement")

    checks.check(
        "sharded_serving_agrees", serve["finite"] and within(serve["gap"], tol["serve"]),
        "correctness", max_abs_gap=serve["gap"][0], scale=serve["gap"][1],
        tolerance=tol["serve"],
    )
    for mode in ("sharded", "replicated"):
        m = serve[mode]
        checks.check(
            f"serve_{mode}_clean",
            m["recompiles_after_warmup"] == 0 and not m["fe_only"] and not m["lost"]
            and not m["counters"] and serve["requests"] == args.requests,
            "correctness", recompiles=m["recompiles_after_warmup"],
            fe_only=m["fe_only"], lost=m["lost"], nonzero=m["counters"],
        )
    for cid, pl in serve["sharded"]["placement"].items():
        if pl["random_effect"]:
            checks.check(
                f"serve_{cid}_rows_spread_over_4",
                pl["devices"] == 4 and pl["max_shard_bytes"] * 4 <= pl["bytes"],
                "placement", **pl,
            )
    for cid, pl in serve["replicated"]["placement"].items():
        checks.check(f"serve_replicated_{cid}_on_one", pl["devices"] == 1, "placement", **pl)
    checks.check(
        "serving_reports_entity_sharded",
        bool((serve["sharded"]["sharding"] or {}).get("entity_sharded"))
        and (serve["sharded"]["sharding"] or {}).get("axis_size") == 4,
        "engagement", sharding=serve["sharded"]["sharding"],
    )
    return checks.verdict(ev["platform"], ev["kind"], ev["count"], chips=4)


# ------------------------------------------------------------- one chip


def one_chip(args) -> int:
    checks = Checks()
    say(
        smoke="GLMix train -> serve", rows=args.rows, seed=args.seed,
        reduced={
            "rows": f"{args.rows} of the {FULL_ROWS} of BENCH_r05's e2e run "
            "(the contract's time limit); entity counts follow rows "
            "(rows//145 users, rows//740 movies)",
            "coordinate_descent_iterations": 2,
            "widths_cut": "none: d=200 named features, 8 non-zeros per row",
        },
        compile_cache=os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or os.path.join(HERE, ".jax_cache"),
    )

    wall, data = self_stage("data", args, cpu_only=True)
    say(stage="data", wall_s=round(wall, 1), **data)

    fit_dir = os.path.join(args.workdir, "fit")
    wall, _ = run_child("train", train_argv(args, fit_dir), args.workdir)
    summary = read_json(os.path.join(fit_dir, "training-summary.json"))
    profile = read_json(os.path.join(fit_dir, "profile.json"))
    ft, dispatch = profile["fit_timing"], profile["dispatch"]
    topo, ingest = profile["device_topology"], profile["ingest"]
    compiled, read_back = cache_traffic(profile["metrics"])
    say(
        stage="train", wall_s=round(wall, 1), samples=summary["num_samples"],
        best_evaluation=summary["best_evaluation"],
        prepare_s=ft["prepare_s"], solve_s=ft["solve_s"],
        pack_path=ft["pack_path"], re_path=ft["re_path"],
        layout=dispatch["layout"], sparse_objective=dispatch["sparse_objective"],
        ingest_path=ingest.get("ingest_path"), streaming=ingest.get("streaming"),
        device=topo, programs_compiled=compiled, programs_read_back=read_back,
    )

    auc = float((summary["best_evaluation"] or {}).get("AUC", float("nan")))
    checks.check(
        "auc_above_floor", auc > AUC_FLOOR, "correctness", auc=auc,
        floor=AUC_FLOOR, true_margin_auc=data["true_margin_auc"],
    )
    from_contracts = load_contracts()
    robustness = ft["robustness"]
    checks.check(
        "fit_robustness_all_zero",
        all(robustness.get(k, None) == 0 for k in from_contracts["ROBUSTNESS_CLEAN_ZERO_KEYS"]),
        "correctness",
        nonzero={k: v for k, v in robustness.items() if v},
    )
    unclean = unclean_counters(profile["metrics"])
    checks.check("fit_no_retry_or_fallback", not unclean, "correctness", nonzero=unclean)
    checks.check(
        "native_ingest", str(ingest.get("ingest_path", "")).startswith("native"),
        "correctness", ingest_path=ingest.get("ingest_path"),
    )
    checks.check(
        "bucketed_pack_happened", ft["pack_path"] != "none", "engagement",
        pack_path=ft["pack_path"], layout=dispatch["layout"],
    )
    checks.check(
        "sparse_pallas_objective_engaged",
        str(dispatch["sparse_objective"]).startswith("pallas_"), "engagement",
        sparse_objective=dispatch["sparse_objective"],
    )
    # The random-effect assembly route is reported, not required: an auto
    # rule may keep it on the host on a TPU, and then the line names it.
    say(
        note="random-effect assembly route", re_path=ft["re_path"],
        rule=(
            "device assembly engaged" if ft["re_path"] == "device" else
            "data/device_assemble.enabled() kept assembly on the host: "
            "PHOTON_DEVICE_ASSEMBLY or an installed plan's assembly_routing "
            "said 'host', or the backend is not tpu/gpu (its auto rule)"
        ),
    )

    served = os.path.join(args.workdir, "served")
    wall, _ = run_child(
        "serve",
        serve_argv(args, os.path.join(fit_dir, "models", "best"), served),
        args.workdir,
    )
    ssum = read_json(os.path.join(served, "serving-summary.json"))
    sprof = read_json(os.path.join(served, "profile.json"))
    sm = ssum["serving"]
    compiled, read_back = cache_traffic(sprof["metrics"])
    say(
        stage="serve", wall_s=round(wall, 1), requests=ssum["num_requests"],
        failed=ssum["failed_requests"], malformed=ssum["malformed_records"],
        p50_ms=sm.get("p50_ms"), p99_ms=sm.get("p99_ms"),
        cold_start_fraction=sm.get("cold_start_fraction"),
        recompiles_after_warmup=sm.get("recompiles_after_warmup"),
        warmup_s=sprof["stages"]["warmup_s"], buckets=sprof["bucket_shapes"],
        device=sprof["device_topology"],
        programs_compiled=compiled, programs_read_back=read_back,
    )
    checks.check(
        "serve_all_answered",
        ssum["num_requests"] == args.requests and ssum["failed_requests"] == 0
        and ssum["malformed_records"] == 0, "correctness",
        requests=ssum["num_requests"], failed=ssum["failed_requests"],
    )
    zero_keys = from_contracts["SERVING_CLEAN_ZERO_KEYS"]
    unclean = unclean_counters(sprof["metrics"])
    checks.check(
        "serve_no_degraded_answers",
        all(sm.get(k) == 0 for k in zero_keys) and sm.get("degraded_batches") == 0
        and not unclean
        and all(
            ssum["robustness_counters"].get(k) == 0
            for k in from_contracts["ROBUSTNESS_CLEAN_ZERO_KEYS"]
        ),
        "correctness",
        serving={k: sm.get(k) for k in (*zero_keys, "degraded_batches")},
        nonzero=unclean,
    )
    checks.check(
        "serve_no_recompiles_after_warmup", sm.get("recompiles_after_warmup") == 0,
        "correctness", recompiles=sm.get("recompiles_after_warmup"),
    )
    checks.check(
        "serve_saw_known_and_cold_entities",
        0.0 < float(sm.get("cold_start_fraction") or 0.0) < 1.0, "correctness",
        cold_start_fraction=sm.get("cold_start_fraction"),
    )
    checks.check(
        "serve_same_device_as_train",
        sprof["device_topology"]["platform"] == topo["platform"], "correctness",
    )

    wall, parity = self_stage("verify-serve", args, cpu_only=True)
    say(stage="verify-serve", wall_s=round(wall, 1), **parity)
    checks.check(
        "serving_equals_numpy_reference",
        parity["equal"] and parity["finite"] and parity["answers"] == args.requests,
        "correctness", max_abs_margin_error=parity["max_abs_margin_error"],
        tolerance=parity["tolerance"],
    )

    wall, probe = self_stage("dense-probe", args, cpu_only=False)
    say(stage="dense-probe", wall_s=round(wall, 1), **probe)
    checks.check(
        "dense_pallas_probe", probe["dense_kernels_healthy"] is True, "engagement",
        healthy=probe["dense_kernels_healthy"],
    )
    checks.check(
        "one_device_everywhere",
        (probe["platform"], probe["kind"], probe["count"])
        == (topo["platform"], topo["device_kind"], topo["device_count"]),
        "correctness",
    )

    return checks.verdict(
        topo["platform"], topo["device_kind"], topo["device_count"], chips=1
    )


def load_contracts() -> dict:
    """The zero-counter key lists, read from utils/contracts.py without
    importing the package (whose __init__ imports JAX): the module is
    constants only."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_photon_contracts",
        os.path.join(HERE, "photon_ml_tpu", "utils", "contracts.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return {
        k: getattr(mod, k)
        for k in ("ROBUSTNESS_CLEAN_ZERO_KEYS", "SERVING_CLEAN_ZERO_KEYS")
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rows", type=int, default=None,
                   help=f"training rows (default {DEFAULT_ROWS}; "
                        f"{FOUR_CHIP_ROWS} with --four-chips)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--requests", type=int, default=400)
    p.add_argument("--four-chips", action="store_true",
                   help="run ONLY the four-chip path and what it is compared "
                        "with: sharded fit vs one chip, sharded serving vs "
                        "replicated")
    p.add_argument("--workdir", default=None,
                   help="where data, models and scores go (default: a fresh "
                        "temporary directory, removed at the end)")
    p.add_argument("--stage", default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.rows is None:
        args.rows = FOUR_CHIP_ROWS if args.four_chips else DEFAULT_ROWS

    if args.stage is not None:  # a child: one in-repo stage, JSON on stdout
        stages = {
            "data": stage_data, "verify-serve": stage_verify_serve,
            "dense-probe": stage_dense_probe, "four-chips": stage_four_chips,
        }
        print(json.dumps(stages[args.stage](args), default=str), flush=True)
        return 0

    own_workdir = args.workdir is None
    if own_workdir:
        args.workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    os.makedirs(args.workdir, exist_ok=True)
    try:
        return four_chips(args) if args.four_chips else one_chip(args)
    finally:
        if own_workdir:
            shutil.rmtree(args.workdir, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
