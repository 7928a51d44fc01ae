"""CLI tests: config DSL round-trips, sweep expansion, end-to-end
train -> score drivers (reference: ScoptParserHelpers / GameTrainingDriver /
GameScoringDriver behavior)."""

import json
import os

import numpy as np
import pytest

from photon_ml_tpu.cli import score as score_cli
from photon_ml_tpu.cli import train as train_cli
from photon_ml_tpu.cli.config import (
    coordinate_config_to_string,
    expand_game_opt_configs,
    parse_coordinate_config,
    parse_feature_shard_config,
)
from photon_ml_tpu.data.game_dataset import RandomEffectDataConfig
from photon_ml_tpu.io.avro_data import write_training_examples
from photon_ml_tpu.types import OptimizerType, ProjectorType, RegularizationType


class TestConfigDSL:
    def test_feature_shard_parse(self):
        name, cfg = parse_feature_shard_config(
            "name=globalShard,feature.bags=features|context,intercept=true"
        )
        assert name == "globalShard"
        assert cfg.feature_bags == ("features", "context")
        assert cfg.has_intercept

    def test_feature_shard_defaults_and_errors(self):
        name, cfg = parse_feature_shard_config("name=s")
        assert cfg.feature_bags == ("features",) and cfg.has_intercept
        with pytest.raises(ValueError):
            parse_feature_shard_config("feature.bags=f1")
        with pytest.raises(ValueError):
            parse_feature_shard_config("name=s,bogus.key=1")

    def test_coordinate_parse_readme_example(self):
        # The README.md:283-292 example string parses verbatim.
        cfg = parse_coordinate_config(
            "name=global,feature.shard=globalShard,min.partitions=4,"
            "optimizer=LBFGS,tolerance=1.0E-6,max.iter=50,"
            "regularization=L2,reg.weights=0.1|1|10|100"
        )
        assert cfg.name == "global"
        assert cfg.data_config.feature_shard == "globalShard"
        assert cfg.opt_config.optimizer.optimizer_type == OptimizerType.LBFGS
        assert cfg.opt_config.optimizer.tolerance == 1e-6
        assert cfg.opt_config.optimizer.max_iterations == 50
        assert cfg.opt_config.regularization.reg_type == RegularizationType.L2
        assert set(cfg.reg_weights) == {0.1, 1.0, 10.0, 100.0}
        # Descending expansion (CoordinateConfiguration.scala:71-77).
        assert [c.reg_weight for c in cfg.expand()] == [100.0, 10.0, 1.0, 0.1]

    def test_random_effect_coordinate_parse(self):
        cfg = parse_coordinate_config(
            "name=per-member,random.effect.type=memberId,feature.shard=memberShard,"
            "active.data.lower.bound=2,active.data.upper.bound=100,"
            "optimizer=TRON,regularization=L2,reg.weights=1,projector=RANDOM,"
            "projected.dim=16,min.bucket=4"
        )
        dc = cfg.data_config
        assert isinstance(dc, RandomEffectDataConfig)
        assert dc.random_effect_type == "memberId"
        assert dc.active_lower_bound == 2 and dc.active_upper_bound == 100
        assert dc.projector_type == ProjectorType.RANDOM and dc.projected_dim == 16
        assert dc.min_bucket == 4

    def test_round_trip(self):
        for s in [
            "name=global,feature.shard=g,optimizer=OWLQN,tolerance=0.001,"
            "max.iter=20,regularization=L1,reg.weights=0.5|2.0",
            "name=re,random.effect.type=uid,feature.shard=s,optimizer=LBFGS,"
            "tolerance=1e-07,max.iter=100,regularization=NONE",
        ]:
            cfg = parse_coordinate_config(s)
            printed = coordinate_config_to_string(cfg)
            cfg2 = parse_coordinate_config(printed)
            assert cfg2.name == cfg.name
            assert cfg2.reg_weights == cfg.reg_weights
            assert cfg2.opt_config == cfg.opt_config
            assert cfg2.data_config == cfg.data_config

    def test_expand_cross_product(self):
        a = parse_coordinate_config(
            "name=a,feature.shard=s,regularization=L2,reg.weights=1|10"
        )
        b = parse_coordinate_config(
            "name=b,feature.shard=s,regularization=L2,reg.weights=0.5"
        )
        combos = expand_game_opt_configs({"a": a, "b": b})
        assert len(combos) == 2
        assert [c["a"].reg_weight for c in combos] == [10.0, 1.0]
        assert all(c["b"].reg_weight == 0.5 for c in combos)

    def test_errors(self):
        with pytest.raises(ValueError):
            parse_coordinate_config("feature.shard=s")  # no name
        with pytest.raises(ValueError):
            parse_coordinate_config("name=a,feature.shard=s,regularization=L2")
        with pytest.raises(ValueError):
            parse_coordinate_config("name=a,feature.shard=s,nope=1")


def _write_glmix_avro(path, seed, n, n_entities=8):
    rng = np.random.default_rng(seed)
    w_true = np.random.default_rng(99).normal(size=4)
    b_true = np.random.default_rng(98).normal(size=(20, 2))
    X = rng.normal(size=(n, 4))
    entity = rng.integers(0, n_entities, size=n)
    margins = X @ w_true + np.einsum("nd,nd->n", X[:, :2], b_true[entity])
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margins))).astype(np.float32)
    feats = [
        [(f"f{j}", float(X[i, j])) for j in range(4)] for i in range(n)
    ]
    write_training_examples(
        path,
        feats,
        y.tolist(),
        uids=[f"uid{i}" for i in range(n)],
        id_tags={"memberId": [f"m{e}" for e in entity]},
    )


class TestDriversEndToEnd:
    def test_train_then_score(self, tmp_path, monkeypatch):
        train_avro = str(tmp_path / "train.avro")
        val_avro = str(tmp_path / "val.avro")
        _write_glmix_avro(train_avro, 0, 400)
        _write_glmix_avro(val_avro, 1, 200)
        out = str(tmp_path / "out")

        train_cli.main([
            "--training-task", "LOGISTIC_REGRESSION",
            "--input-data-directories", train_avro,
            "--validation-data-directories", val_avro,
            "--root-output-directory", out,
            "--feature-shard-configurations",
            "name=globalShard,feature.bags=features,intercept=true",
            "--coordinate-configurations",
            "name=global,feature.shard=globalShard,optimizer=LBFGS,"
            "tolerance=1e-7,max.iter=40,regularization=L2,reg.weights=0.1|10",
            "name=per-member,random.effect.type=memberId,feature.shard=globalShard,"
            "optimizer=LBFGS,max.iter=30,regularization=L2,reg.weights=1,min.bucket=4",
            "--validation-evaluators", "AUC",
            "--output-mode", "ALL",
            "--data-summary-directory", str(tmp_path / "summary"),
        ])

        # Feature-shard summary Avro (writeBasicStatistics hook,
        # GameTrainingDriver.scala:582).
        from photon_ml_tpu.io import avro as avro_io
        _, srecs = avro_io.read_container(
            str(tmp_path / "summary" / "globalShard" / "part-00000.avro")
        )
        assert {r["featureName"] for r in srecs} == {"f0", "f1", "f2", "f3"}
        assert set(srecs[0]["metrics"]) == {
            "max", "min", "mean", "normL1", "normL2", "numNonzeros", "variance"
        }

        # Model layout (ModelProcessingUtils.scala:77-141).
        best = os.path.join(out, "models", "best")
        assert os.path.isfile(os.path.join(best, "model-metadata.json"))
        assert os.path.isdir(os.path.join(best, "fixed-effect", "global"))
        assert os.path.isdir(os.path.join(best, "random-effect", "per-member"))
        assert os.path.isdir(os.path.join(out, "models", "explicit-1"))
        summary = json.load(open(os.path.join(out, "training-summary.json")))
        assert summary["num_explicit"] == 2
        assert summary["best_evaluation"]["AUC"] > 0.6
        # Job log file (PhotonLogger) written under the output root.
        job_log = open(os.path.join(out, "photon-ml-tpu.log")).read()
        assert "training 2 explicit configuration(s)" in job_log
        assert "read data" in job_log  # Timed sections

        # Score with the trained model.
        score_out = str(tmp_path / "scores")
        score_cli.main([
            "--input-data-directories", val_avro,
            "--model-input-directory", best,
            "--root-output-directory", score_out,
            "--feature-shard-configurations",
            "name=globalShard,feature.bags=features,intercept=true",
            "--evaluators", "AUC",
        ])
        ssum = json.load(open(os.path.join(score_out, "scoring-summary.json")))
        assert ssum["num_scored"] == 200
        # Scoring-side AUC must match the training driver's validation AUC
        # (same model, same data, original-space scoring path).
        assert abs(ssum["evaluation"]["AUC"] - summary["best_evaluation"]["AUC"]) < 5e-3

        from photon_ml_tpu.io.score_store import load_scores
        items = load_scores(os.path.join(score_out, "scores"))
        assert len(items) == 200 and items[0].uid.startswith("uid")

        # Replay the same records through the ONLINE serving driver: same
        # model, same feature DSL — per-uid scores must agree with the
        # offline driver (approx, not bitwise: offline ingest scores the
        # ELL sparse layout, the engine densifies request rows, so the
        # per-row reduction ranges differ).
        from photon_ml_tpu.cli import serve as serve_cli
        serve_out = str(tmp_path / "served")
        # Small replay windows force the MULTI-window path, so the
        # --reshard-to drill below runs on its background worker WHILE
        # later windows stream — the generation flips mid-replay and the
        # lazily-encoding request iterator must keep working across it
        # (the retired bundle handle stays a live view of the new
        # generation).
        monkeypatch.setattr(serve_cli, "REPLAY_WINDOW", 32)
        serve_cli.main([
            "--model-input-directory", best,
            "--requests", val_avro,
            "--root-output-directory", serve_out,
            "--feature-shard-configurations",
            "name=globalShard,feature.bags=features,intercept=true",
            "--max-batch", "32",
            "--max-wait-ms", "1",
            "--reshard-to", "4",  # live elasticity drill mid-replay
        ])
        served = {
            it.uid: it.prediction_score
            for it in load_scores(os.path.join(serve_out, "scores"))
        }
        offline = {it.uid: it.prediction_score for it in items}
        assert set(served) == set(offline)
        for uid, s in served.items():
            assert s == pytest.approx(offline[uid], rel=1e-4, abs=1e-5)
        ssummary = json.load(
            open(os.path.join(serve_out, "serving-summary.json"))
        )
        assert ssummary["num_requests"] == 200
        m = ssummary["serving"]
        assert m["completed"] == 200
        assert m["recompiles_after_warmup"] == 0
        assert m["degraded_batches"] == 0
        # Validation entities were all seen at training time: no cold starts.
        assert m["cold_start_fraction"] == 0.0
        # The --reshard-to drill committed (replicated -> 4 entity shards)
        # with zero failed requests — every per-uid score above already
        # matched the offline driver across the generation flip.
        assert ssummary["reshard"]["committed"] is True
        assert ssummary["reshard"]["new_shards"] == 4
        assert ssummary["failed_requests"] == 0
        # The whole stream was encoded and scored ACROSS the flip — no
        # record silently dropped as malformed by a gutted encoder handle.
        assert ssummary["malformed_records"] == 0

        # JSON-lines replay: named features resolved through the model's
        # index maps.
        jsonl = str(tmp_path / "requests.jsonl")
        with open(jsonl, "w") as f:
            f.write(json.dumps({
                "uid": "j0",
                "ids": {"memberId": "m1"},
                "features": {"globalShard": {"f0": 1.0, "(INTERCEPT)": 1.0}},
            }) + "\n")
            f.write(json.dumps({
                "uid": "j1",
                "ids": {"memberId": "never-seen"},
                "features": {"globalShard": {"f1": -1.0, "(INTERCEPT)": 1.0}},
            }) + "\n")
        serve_out2 = str(tmp_path / "served-jsonl")
        serve_cli.main([
            "--model-input-directory", best,
            "--requests", jsonl,
            "--root-output-directory", serve_out2,
            "--max-batch", "4",
        ])
        jm = json.load(open(os.path.join(serve_out2, "serving-summary.json")))
        assert jm["num_requests"] == 2
        assert jm["serving"]["cold_start_lookups"] == 1
        # Unplanned replays always carry an INACTIVE plan block (the
        # SERVING_SUMMARY_KEYS contract: absence must be loud, "planner
        # off" must be explicit).
        assert jm["plan"]["active"] is False

        # Planned replay (ISSUE 14): the first replay's persisted serve
        # profile plans this one — bucket ceiling and micro-batch wait
        # resolve from the plan, the summary's plan block is active and
        # carries the full decision audit, and every summary contract
        # key is present.
        from photon_ml_tpu.utils.contracts import (
            PLAN_BLOCK_KEYS,
            SERVING_SUMMARY_KEYS,
        )

        serve_out3 = str(tmp_path / "served-planned")
        serve_cli.main([
            "--model-input-directory", best,
            "--requests", jsonl,
            "--root-output-directory", serve_out3,
            "--profile", os.path.join(serve_out, "profile.json"),
        ])
        pm = json.load(open(os.path.join(serve_out3, "serving-summary.json")))
        missing = [k for k in SERVING_SUMMARY_KEYS if k not in pm]
        assert not missing, missing
        block = pm["plan"]
        assert tuple(block) == PLAN_BLOCK_KEYS
        assert block["active"] is True
        assert block["source"] == "profile"
        assert {d["decision"] for d in block["decisions"]} == {
            "serving_max_batch",
            "serving_max_wait_ms",
        }
        assert pm["failed_requests"] == 0 and pm["num_requests"] == 2
        # The planned run's own profile re-reads loudly WITH its block.
        from photon_ml_tpu.utils import telemetry as _tel

        back = _tel.read_profile(
            os.path.join(serve_out3, "profile.json"), kind="serve"
        )
        assert back["plan"] == block

        # Multi-tenant replay (ISSUE 15): the same model serves as two
        # named tenants on one fleet through the TenantRegistry; replay
        # records assign round-robin, scores land per tenant, and the
        # summary carries one TENANT_BLOCK_KEYS dict per tenant.
        from photon_ml_tpu.utils.contracts import TENANT_BLOCK_KEYS

        serve_out4 = str(tmp_path / "served-tenants")
        serve_cli.main([
            "--tenant", f"alpha={best}",
            "--tenant", f"beta={best}",
            "--requests", jsonl,
            "--root-output-directory", serve_out4,
            "--max-batch", "4",
        ])
        tm = json.load(open(os.path.join(serve_out4, "serving-summary.json")))
        missing_t = [k for k in SERVING_SUMMARY_KEYS if k not in tm]
        assert not missing_t, missing_t
        assert tm["num_requests"] == 2 and tm["failed_requests"] == 0
        assert set(tm["tenants"]) == {"alpha", "beta"}
        for name, tblock in tm["tenants"].items():
            assert set(tblock) == set(TENANT_BLOCK_KEYS), name
            assert tblock["completed"] == 1 and tblock["failed"] == 0
        # Round-robin wrote each tenant's scores under its own subdir.
        alpha_scores = load_scores(
            os.path.join(serve_out4, "scores", "alpha")
        )
        beta_scores = load_scores(os.path.join(serve_out4, "scores", "beta"))
        assert {it.uid for it in alpha_scores} == {"j0"}
        assert {it.uid for it in beta_scores} == {"j1"}
        # Same model, same records: the tenant-path scores agree with the
        # single-tenant replay of the same stream bitwise.
        single = {
            it.uid: it.prediction_score
            for it in load_scores(os.path.join(serve_out2, "scores"))
        }
        for it in list(alpha_scores) + list(beta_scores):
            assert it.prediction_score == single[it.uid]

    def test_warm_start_and_partial_retrain(self, tmp_path):
        train_avro = str(tmp_path / "train.avro")
        _write_glmix_avro(train_avro, 0, 300)
        out1 = str(tmp_path / "out1")
        common = [
            "--training-task", "LOGISTIC_REGRESSION",
            "--input-data-directories", train_avro,
            "--feature-shard-configurations",
            "name=globalShard,feature.bags=features,intercept=true",
        ]
        train_cli.main(common + [
            "--root-output-directory", out1,
            "--coordinate-configurations",
            "name=global,feature.shard=globalShard,max.iter=30,"
            "regularization=L2,reg.weights=1",
            "name=per-member,random.effect.type=memberId,feature.shard=globalShard,"
            "max.iter=20,regularization=L2,reg.weights=1,min.bucket=4",
        ])
        # Partial retrain: lock the fixed effect, retrain only the RE.
        out2 = str(tmp_path / "out2")
        train_cli.main(common + [
            "--root-output-directory", out2,
            "--model-input-directory", os.path.join(out1, "models", "best"),
            "--partial-retrain-locked-coordinates", "global",
            "--coordinate-configurations",
            "name=global,feature.shard=globalShard,max.iter=30,"
            "regularization=L2,reg.weights=1",
            "name=per-member,random.effect.type=memberId,feature.shard=globalShard,"
            "max.iter=20,regularization=L2,reg.weights=0.1,min.bucket=4",
        ])
        assert os.path.isdir(os.path.join(out2, "models", "best", "fixed-effect"))


class TestValidators:
    def test_validation_catches_bad_rows(self, tmp_path):
        import jax.numpy as jnp

        from photon_ml_tpu.data.game_dataset import GameDataset
        from photon_ml_tpu.data.validators import (
            DataValidationError,
            validate_game_dataset,
        )
        from photon_ml_tpu.types import DataValidationType, TaskType

        ds = GameDataset.build(
            {"s": jnp.asarray([[1.0], [np.nan]])},
            [1.0, 3.0],
            weights=[1.0, -1.0],
        )
        with pytest.raises(DataValidationError) as exc:
            validate_game_dataset(ds, TaskType.LOGISTIC_REGRESSION, DataValidationType.VALIDATE_FULL)
        names = [f[0] for f in exc.value.failures]
        assert "positive weight" in names
        assert "binary label" in names
        assert any("finite features" in n for n in names)
        # Disabled mode never raises.
        validate_game_dataset(ds, TaskType.LOGISTIC_REGRESSION, DataValidationType.VALIDATE_DISABLED)


def test_features_to_samples_ratio_dsl_roundtrip():
    from photon_ml_tpu.cli.config import (
        coordinate_config_to_string,
        parse_coordinate_config,
    )

    cfg = parse_coordinate_config(
        "name=per-user,random.effect.type=userId,feature.shard=s,"
        "features.to.samples.ratio=0.5,optimizer=LBFGS,reg.weights=1"
    )
    assert cfg.data_config.num_features_to_samples_ratio_upper_bound == 0.5
    rendered = coordinate_config_to_string(cfg)
    assert "features.to.samples.ratio=0.5" in rendered
    assert (
        parse_coordinate_config(rendered).data_config.num_features_to_samples_ratio_upper_bound
        == 0.5
    )


class TestDateRangeAndMultiDirInput:
    def test_train_on_daily_dirs_and_multiple_inputs(self, tmp_path):
        """N input directories + date-range expansion feed one training run
        (GameDriver.pathsForDateRange:248; AvroDataReader.readMerged paths)."""
        # Daily layout: base/2016/01/{01,02}/part.avro + a second plain dir.
        base = tmp_path / "daily"
        d1 = base / "2016" / "01" / "01"
        d2 = base / "2016" / "01" / "02"
        d1.mkdir(parents=True)
        d2.mkdir(parents=True)
        extra = tmp_path / "extra"
        extra.mkdir()
        _write_glmix_avro(str(d1 / "part-00000.avro"), 0, 150)
        _write_glmix_avro(str(d2 / "part-00000.avro"), 1, 150)
        _write_glmix_avro(str(extra / "part-00000.avro"), 2, 100)
        out = str(tmp_path / "out")

        # Date-ranged read of the daily tree only.
        train_cli.main([
            "--training-task", "LOGISTIC_REGRESSION",
            "--input-data-directories", str(base),
            "--input-data-date-range", "20160101-20160131",
            "--root-output-directory", out,
            "--feature-shard-configurations",
            "name=globalShard,feature.bags=features,intercept=true",
            "--coordinate-configurations",
            "name=global,feature.shard=globalShard,optimizer=LBFGS,"
            "tolerance=1e-7,max.iter=20,regularization=L2,reg.weights=1",
        ])
        summary = json.load(open(os.path.join(out, "training-summary.json")))
        assert summary["num_samples"] == 300  # both daily dirs, not extra

        # Multiple plain input directories concatenate.
        out2 = str(tmp_path / "out2")
        train_cli.main([
            "--training-task", "LOGISTIC_REGRESSION",
            "--input-data-directories", str(d1), str(extra),
            "--root-output-directory", out2,
            "--feature-shard-configurations",
            "name=globalShard,feature.bags=features,intercept=true",
            "--coordinate-configurations",
            "name=global,feature.shard=globalShard,optimizer=LBFGS,"
            "tolerance=1e-7,max.iter=20,regularization=L2,reg.weights=1",
        ])
        summary2 = json.load(open(os.path.join(out2, "training-summary.json")))
        assert summary2["num_samples"] == 250

        # Scoring accepts multiple dirs + ranges too (cli/score.py).
        score_out = str(tmp_path / "scores")
        score_cli.main([
            "--input-data-directories", str(base),
            "--input-data-date-range", "20160101-20160102",
            "--model-input-directory", os.path.join(out, "models", "best"),
            "--root-output-directory", score_out,
            "--feature-shard-configurations",
            "name=globalShard,feature.bags=features,intercept=true",
        ])
        ssum = json.load(open(os.path.join(score_out, "scoring-summary.json")))
        assert ssum["num_scored"] == 300


class TestHyperparameterTuningCLI:
    def test_bayesian_tuning_end_to_end(self, tmp_path):
        """--hyper-parameter-tuning BAYESIAN runs GP trials after the
        explicit sweep, writes tuned-<i> model dirs, and the selected best
        model comes from the union (GameTrainingDriver.runHyperparameterTuning
        -> AtlasTuner -> GaussianProcessSearch)."""
        train_avro = str(tmp_path / "train.avro")
        val_avro = str(tmp_path / "val.avro")
        _write_glmix_avro(train_avro, 0, 300)
        _write_glmix_avro(val_avro, 1, 150)
        out = str(tmp_path / "out")

        train_cli.main([
            "--training-task", "LOGISTIC_REGRESSION",
            "--input-data-directories", train_avro,
            "--validation-data-directories", val_avro,
            "--root-output-directory", out,
            "--feature-shard-configurations",
            "name=globalShard,feature.bags=features,intercept=true",
            "--coordinate-configurations",
            "name=global,feature.shard=globalShard,optimizer=LBFGS,"
            "tolerance=1e-7,max.iter=25,regularization=L2,reg.weights=1",
            "--validation-evaluators", "AUC",
            "--hyper-parameter-tuning", "BAYESIAN",
            "--hyper-parameter-tuning-iter", "4",
            "--output-mode", "ALL",
        ])
        summary = json.load(open(os.path.join(out, "training-summary.json")))
        assert summary["num_tuned"] == 4
        # Tuned model dirs persisted alongside explicit ones.
        for i in range(4):
            assert os.path.isfile(
                os.path.join(out, "models", f"tuned-{i}", "model-metadata.json")
            )
        assert summary["best_evaluation"]["AUC"] > 0.6
        # Each trial carries its own sampled reg weight in the metadata.
        weights = set()
        for i in range(4):
            meta = json.load(open(os.path.join(out, "models", f"tuned-{i}", "model-metadata.json")))
            weights.add(json.dumps(meta.get("optimizationConfigurations", {}), sort_keys=True))
        assert len(weights) > 1  # the search explored, not repeated, configs


class TestTuneDriver:
    def test_tune_end_to_end(self, tmp_path):
        """cli/tune.py: the pod-parallel sweep driver — batched Bayesian
        rounds through the stacked executor, winner model saved in the
        standard layout, tuning-summary written, and trial_start/
        trial_finish journal lines validating against their schemas."""
        from photon_ml_tpu.cli import tune as tune_cli
        from photon_ml_tpu.utils import telemetry

        train_avro = str(tmp_path / "train.avro")
        val_avro = str(tmp_path / "val.avro")
        _write_glmix_avro(train_avro, 0, 300)
        _write_glmix_avro(val_avro, 1, 150)
        out = str(tmp_path / "out")
        tune_cli.main([
            "--training-task", "LOGISTIC_REGRESSION",
            "--input-data-directories", train_avro,
            "--validation-data-directories", val_avro,
            "--root-output-directory", out,
            "--feature-shard-configurations",
            "name=globalShard,feature.bags=features,intercept=true",
            "--coordinate-configurations",
            "name=global,feature.shard=globalShard,optimizer=LBFGS,"
            "tolerance=1e-7,max.iter=15,regularization=L2,reg.weights=1",
            "name=per-member,random.effect.type=memberId,"
            "feature.shard=globalShard,optimizer=LBFGS,max.iter=10,"
            "regularization=L2,reg.weights=1,min.bucket=4",
            "--validation-evaluators", "AUC",
            "--tuning-iter", "4",
            "--tuning-batch-size", "2",
            "--logging-level", "WARNING",
        ])
        summary = json.load(open(os.path.join(out, "tuning-summary.json")))
        assert len(summary["trials"]) == 4 and summary["rounds"] == 2
        assert summary["modes"] == ["stacked"]
        assert summary["tuned_coordinates"] == ["global", "per-member"]
        assert np.isfinite(summary["winner_value"])
        assert len(summary["best_point"]) == 2
        # Winner model in the standard layout, loadable with its indexes.
        best = os.path.join(out, "models", "tuned-best")
        assert os.path.isfile(os.path.join(best, "model-metadata.json"))
        assert os.path.isdir(os.path.join(best, "fixed-effect", "global"))
        assert os.path.isdir(os.path.join(best, "random-effect", "per-member"))
        assert os.path.isfile(
            os.path.join(best, "feature-indexes", "globalShard.json")
        )
        meta = json.load(open(os.path.join(best, "model-metadata.json")))
        tuned_rw = meta["optimizationConfigurations"]["global"]["reg_weight"]
        assert tuned_rw == summary["best_point"][0]
        # Journal: every line valid, one start + one finish per trial (and a
        # `program_compiled` line for every program the job compiled).
        n_ok, errors = telemetry.validate_journal(
            os.path.join(out, "journal.jsonl")
        )
        with open(os.path.join(out, "journal.jsonl")) as f:
            types = [json.loads(line)["type"] for line in f]
        trials = [t for t in types if t.startswith("trial_")]
        assert errors == [] and n_ok == len(types) and len(trials) == 8
        assert set(types) <= {"trial_start", "trial_finish", "program_compiled"}
