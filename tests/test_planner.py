"""Adaptive runtime planner tests (ISSUE 14, photon_ml_tpu/planner/).

The load-bearing contracts:

* NO plan installed (or PHOTON_PLAN=0) == the pre-planner tree, bit for
  bit: every consulting site returns its built-in default.
* Precedence: explicit PHOTON_* knob > plan decision > default, with the
  knob override recorded as `source: "knob"` in the plan block.
* A profile from a mismatched device topology refuses LOUDLY, naming the
  field (a profile written on an 8-vdev mesh must not plan a 1-device
  run); an r06-era profile (no `plan` block) still loads for the
  planner's cold-start path.
* A planner-on fit from a matching-topology profile is bitwise-equal to
  the default fit, and its plan block round-trips through
  write_profile/read_profile.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import planner
from photon_ml_tpu.data.game_dataset import (
    FixedEffectDataConfig,
    GameDataset,
    RandomEffectDataConfig,
)
from photon_ml_tpu.estimators.game_estimator import GameEstimator
from photon_ml_tpu.optimize.config import (
    L2,
    CoordinateOptimizationConfig,
    OptimizerConfig,
)
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils import telemetry
from photon_ml_tpu.utils.contracts import (
    PLAN_BLOCK_KEYS,
    PLAN_DECISION_KEYS,
)


# ---------------------------------------------------------------- fixtures


def _fit_profile(**overrides):
    """A synthetic fit profile shaped exactly like est.run_profile()'s
    output on THIS machine's topology (so plan_from_profile accepts it)."""
    profile = {
        "kind": "fit",
        "wall_s": 10.0,
        "stages": {
            "re_build": 1.0,
            "projector": 0.5,
            "stats": 0.1,
            "pack": 0.5,
            "upload": 0.2,
            "compile": 0.5,
            "other": 0.2,
            "prepare_s": 3.0,
            "solve_s": 7.0,
        },
        "dispatch": {
            "pack_path": "native",
            "re_path": "host",
            "sharding": {"entity_sharded": False, "axis_size": 1},
            "pipeline": False,
            "layout": "grouped",
        },
        "bucket_shapes": {"per-member": [[4, 8], [2, 16]]},
        "device_topology": telemetry.device_topology(),
        "roofline": {"hbm_gb_per_s": None},
        "metrics": {},
        "fit_timing": {
            "pack_device_s": 0.0,
            "pack_host_s": 0.5,
            "pack_path": "native",
            "re_device_s": 0.0,
            "re_host_s": 1.0,
            "re_path": "host",
            "robustness": {"collective_retries": 0, "watchdog_trips": 0},
        },
        "ingest": {},
    }
    profile.update(overrides)
    return profile


def _serve_profile(**overrides):
    profile = {
        "kind": "serve",
        "wall_s": 5.0,
        "stages": {"warmup_s": 1.0, "replay_s": 4.0},
        "dispatch": {"max_batch": 256, "max_wait_ms": 2.0, "sharding": None},
        "bucket_shapes": {"engine_buckets": [1, 2, 4, 8]},
        "device_topology": telemetry.device_topology(),
        "roofline": {"hbm_gb_per_s": None},
        "metrics": {},
        "serving": {"p50_ms": 4.0, "batch_size_p95": 24},
    }
    profile.update(overrides)
    return profile


_TRUTH = np.random.default_rng(7)
_W = _TRUTH.normal(size=4)
_B = _TRUTH.normal(size=(12, 3))


def _data(seed, n=300):
    rng = np.random.default_rng(seed)
    Xf = rng.normal(size=(n, 4)).astype(np.float32)
    Xe = rng.normal(size=(n, 3)).astype(np.float32)
    ent = rng.integers(0, 12, size=n)
    margins = Xf @ _W + np.einsum("nd,nd->n", Xe, _B[ent])
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margins))).astype(np.float32)
    return GameDataset.build(
        {"g": jnp.asarray(Xf), "e": jnp.asarray(Xe)},
        y,
        id_tags={"memberId": ent},
    )


def _estimator():
    return GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {
            "fixed": FixedEffectDataConfig("g"),
            "per-member": RandomEffectDataConfig("memberId", "e", min_bucket=4),
        },
        seed=3,
    )


_CFG = {
    "fixed": CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=15),
        regularization=L2,
        reg_weight=1.0,
    ),
    "per-member": CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=10),
        regularization=L2,
        reg_weight=10.0,
    ),
}


# ---------------------------------------------------------------- defaults


class TestDefaults:
    def test_no_plan_returns_pre_planner_defaults(self):
        assert planner.current_plan() is None
        assert planner.planned_value("prefetch_depth") == 1
        assert planner.planned_value("scan_fusion_max") == 0
        assert planner.planned_value("ingest_chunk_rows") == 262_144
        assert planner.planned_value("serving_max_batch") == 256
        assert planner.planned_value("serving_max_wait_ms") == 2.0
        assert planner.planned_value("pack_routing") == "auto"
        assert planner.planned_value("sparse_layout") == "auto"

    def test_unknown_quantity_raises(self):
        with pytest.raises(KeyError):
            planner.planned_value("no_such_quantity")

    def test_inactive_block_shape(self):
        block = planner.plan_block()
        assert tuple(block) == PLAN_BLOCK_KEYS
        assert block["active"] is False
        assert block["source"] == "off"
        assert block["decisions"] == []

    def test_photon_plan_off_blocks_everything(self, monkeypatch, tmp_path):
        path = str(tmp_path / "profile.json")
        telemetry.write_profile(path, _fit_profile())
        monkeypatch.setenv("PHOTON_PLAN", "0")
        monkeypatch.setenv("PHOTON_PLAN_PROFILE", path)
        assert planner.ensure_ambient_plan() is None
        assert planner.current_plan() is None


# ------------------------------------------------------------------- rules


class TestProfileRules:
    def test_fit_rules_adopt_measured_run(self):
        plan = planner.plan_from_profile(_fit_profile())
        d = plan.decisions
        assert d["pack_routing"].value == "host"
        assert d["pack_routing"].source == "profile"
        assert d["assembly_routing"].value == "host"
        assert d["sparse_layout"].value == "grouped"
        assert d["prefetch_depth"].value == 1  # pipeline off in evidence
        assert d["ingest_chunk_rows"].value == 262_144  # no streaming data
        assert d["scan_fusion_max"].value == 0  # clean robustness
        assert d["re_bucket_shapes"].value == {"per-member": [[4, 8], [2, 16]]}
        # Every decision is a full audit record.
        for dec in d.values():
            rec = dec.as_dict()
            assert tuple(rec) == PLAN_DECISION_KEYS
            assert isinstance(rec["evidence"], dict)

    def test_unknown_dispatch_key_is_ignored_not_refused(self, tmp_path):
        # A profile written by an older tree may carry a dispatch key no
        # rule reads any more: it loads through the contract and plans
        # exactly what the same profile plans without it.
        stale = _fit_profile()
        stale["dispatch"]["retired_rule_input"] = 64
        path = str(tmp_path / "stale.json")
        telemetry.write_profile(path, stale)
        plan = planner.plan_from_profile(telemetry.read_profile(path), path)
        clean = planner.plan_from_profile(_fit_profile())
        assert "retired_rule_input" not in plan.decisions
        assert {k: d.value for k, d in plan.decisions.items()} == {
            k: d.value for k, d in clean.decisions.items()
        }

    def test_prefetch_deepens_on_pipelined_fit_with_host_cores(
        self, monkeypatch
    ):
        """Depth 2 needs BOTH a pipelined fit and live host cores to feed
        concurrent uploads (the upload-stage wall is deliberately not the
        evidence: it cannot distinguish hidden from un-hidden work)."""
        from photon_ml_tpu.data import pipeline as pipeline_mod

        profile = _fit_profile()
        profile["dispatch"]["pipeline"] = True
        monkeypatch.setattr(
            pipeline_mod, "effective_host_parallelism", lambda: 8
        )
        plan = planner.plan_from_profile(profile)
        dec = plan.decisions["prefetch_depth"]
        assert dec.value == 2
        assert dec.evidence == {"pipeline": True, "host_parallelism": 8}
        # Unpipelined fits stay 1-deep regardless of cores.
        profile["dispatch"]["pipeline"] = False
        assert (
            planner.plan_from_profile(profile)
            .decisions["prefetch_depth"].value
            == 1
        )

    def test_ingest_skew_moves_chunk_rows_bounded(self):
        decode_bound = _fit_profile(
            ingest={"streaming": True, "decode": 8.0, "assemble": 1.0}
        )
        assert (
            planner.plan_from_profile(decode_bound)
            .decisions["ingest_chunk_rows"].value
            == 131_072
        )
        assemble_bound = _fit_profile(
            ingest={"streaming": True, "decode": 1.0, "assemble": 8.0}
        )
        assert (
            planner.plan_from_profile(assemble_bound)
            .decisions["ingest_chunk_rows"].value
            == 524_288
        )

    def test_flaky_collectives_cap_scan_fusion(self):
        profile = _fit_profile()
        profile["fit_timing"]["robustness"] = {
            "collective_retries": 2,
            "watchdog_trips": 0,
        }
        plan = planner.plan_from_profile(profile)
        assert plan.decisions["scan_fusion_max"].value == 8

    def test_serve_rules_shrink_bucket_and_wait(self):
        plan = planner.plan_from_profile(_serve_profile())
        assert plan.decisions["serving_max_batch"].value == 32  # p95=24 -> 32
        assert plan.decisions["serving_max_wait_ms"].value == 2.0  # p50/2=2.0
        fast = _serve_profile(
            serving={"p50_ms": 1.0, "batch_size_p95": 300}
        )
        plan2 = planner.plan_from_profile(fast)
        assert plan2.decisions["serving_max_batch"].value == 256  # capped
        assert plan2.decisions["serving_max_wait_ms"].value == 0.5

    def test_serve_rules_are_not_a_downward_ratchet(self):
        """Re-planning from a PLANNED run's profile must be able to
        recover: saturated batch evidence (p95 at the prior shrunk
        ceiling) plans back up to the default, and the wait derives from
        each round's fresh p50, not min'd against the prior wait."""
        shrunk = _serve_profile(
            dispatch={"max_batch": 16, "max_wait_ms": 0.5, "sharding": None},
            serving={"p50_ms": 6.0, "batch_size_p95": 16},  # saturated
        )
        plan = planner.plan_from_profile(shrunk)
        assert plan.decisions["serving_max_batch"].value == 256  # recovered
        assert plan.decisions["serving_max_wait_ms"].value == 2.0  # p50/2=3
        # Unsaturated evidence on a shrunk run still plans the evidence.
        light = _serve_profile(
            dispatch={"max_batch": 64, "max_wait_ms": 0.5, "sharding": None},
            serving={"p50_ms": 6.0, "batch_size_p95": 9},
        )
        assert (
            planner.plan_from_profile(light)
            .decisions["serving_max_batch"].value
            == 16
        )
        # An operator-validated tiny ceiling with genuinely tiny traffic
        # is NOT saturation (saturation compares p95 itself, not the
        # 8-floored ladder value): the plan keeps the small bucket set.
        tiny = _serve_profile(
            dispatch={"max_batch": 8, "max_wait_ms": 1.0, "sharding": None},
            serving={"p50_ms": 6.0, "batch_size_p95": 2},
        )
        assert (
            planner.plan_from_profile(tiny)
            .decisions["serving_max_batch"].value
            == 8
        )
        # A LARGER operator-validated ceiling with unsaturated p95 above
        # the built-in default must not clamp DOWN below demonstrated
        # traffic: p95=300 under a 512 ceiling plans 512, not 256.
        big = _serve_profile(
            dispatch={"max_batch": 512, "max_wait_ms": 2.0, "sharding": None},
            serving={"p50_ms": 6.0, "batch_size_p95": 300},
        )
        assert (
            planner.plan_from_profile(big)
            .decisions["serving_max_batch"].value
            == 512
        )

    def test_larger_validated_wait_raises_the_clamp_ceiling(self):
        """A recorded wait ABOVE the built-in default raises the
        evidence clamp's ceiling (the bucket-ceiling discipline): p50
        evidence can tighten within it but never ignores the bigger
        budget the profiled run validated."""
        big_wait = _serve_profile(
            dispatch={"max_batch": 256, "max_wait_ms": 10.0, "sharding": None},
            serving={"p50_ms": 30.0, "batch_size_p95": 24},
        )
        assert (
            planner.plan_from_profile(big_wait)
            .decisions["serving_max_wait_ms"].value
            == 10.0  # min(upper=10, p50/2=15)
        )
        tighter = _serve_profile(
            dispatch={"max_batch": 256, "max_wait_ms": 10.0, "sharding": None},
            serving={"p50_ms": 8.0, "batch_size_p95": 24},
        )
        assert (
            planner.plan_from_profile(tighter)
            .decisions["serving_max_wait_ms"].value
            == 4.0  # evidence tightens inside the validated ceiling
        )

    def test_zero_wait_config_survives_replanning(self):
        """A recorded max_wait_ms of 0.0 (immediate flush) is adopted,
        not silently replanned to the default by a falsy-zero `or`."""
        zero_wait = _serve_profile(
            dispatch={"max_batch": 256, "max_wait_ms": 0.0, "sharding": None},
            serving={},  # no p50 evidence -> adopt the recorded wait
        )
        assert (
            planner.plan_from_profile(zero_wait)
            .decisions["serving_max_wait_ms"].value
            == 0.0
        )

    def test_plan_block_overrides_resource_as_knob(self, tmp_path):
        """Explicit CLI flags re-source their decisions to 'knob' in the
        recorded block — the audit must show what actually served."""
        planner.install_plan(planner.plan_from_profile(_serve_profile()))
        block = planner.plan_block(
            overrides={"serving_max_wait_ms": 5.0}
        )
        by_name = {d["decision"]: d for d in block["decisions"]}
        assert by_name["serving_max_wait_ms"]["value"] == 5.0
        assert by_name["serving_max_wait_ms"]["source"] == "knob"
        assert by_name["serving_max_wait_ms"]["evidence"]["explicit_override"]
        assert by_name["serving_max_batch"]["source"] == "profile"
        # A flag that HAPPENS to equal the plan's choice is still pinned
        # by the operator — the audit must say "knob" regardless.
        planned = by_name["serving_max_batch"]["value"]
        same = planner.plan_block(overrides={"serving_max_batch": planned})
        by_name2 = {d["decision"]: d for d in same["decisions"]}
        assert by_name2["serving_max_batch"]["source"] == "knob"
        assert by_name2["serving_max_batch"]["value"] == planned
        # The installed plan itself is untouched (the overlay is a copy).
        assert (
            planner.current_plan()
            .decisions["serving_max_wait_ms"].source
            == "profile"
        )

    def test_calibration_plan_matches_auto_on_this_backend(self):
        plan = planner.plan_from_calibration()
        assert plan.source == "calibration"
        # On the CPU test backend the routing rules must equal the auto
        # policies (bitwise parity of the calibration cold start).
        assert plan.decisions["pack_routing"].value == "host"
        assert plan.decisions["assembly_routing"].value == "host"


# -------------------------------------------------------------- precedence


class TestPrecedence:
    def test_knob_beats_plan_at_consult_time(self, monkeypatch):
        planner.install_plan(planner.plan_from_profile(_fit_profile()))
        monkeypatch.setenv("PHOTON_STREAM_CHUNK_ROWS", "777")
        assert planner.planned_value("ingest_chunk_rows") == 777
        monkeypatch.setenv("PHOTON_DEVICE_PACK", "1")
        assert planner.planned_value("pack_routing") == "device"

    def test_knob_recorded_as_source_knob_at_build_time(self, monkeypatch):
        monkeypatch.setenv("PHOTON_STREAM_CHUNK_ROWS", "777")
        plan = planner.plan_from_profile(_fit_profile())
        dec = plan.decisions["ingest_chunk_rows"]
        assert dec.value == 777
        assert dec.source == "knob"
        assert dec.evidence["knob"] == "PHOTON_STREAM_CHUNK_ROWS"
        assert dec.fallback == 262_144

    def test_plan_beats_default(self):
        planner.install_plan(planner.plan_from_profile(_fit_profile()))
        assert planner.planned_value("sparse_layout") == "grouped"
        assert planner.planned_value("pack_routing") == "host"


# ------------------------------------------------------------- portability


class TestProfilePortability:
    def test_mismatched_device_count_refuses_naming_field(self):
        """A profile written on a bigger mesh (e.g. 8 vdevs) loudly
        refuses when planned onto a run with fewer devices — naming the
        mismatching topology field. The test harness itself runs 8
        forced host devices, so the mismatch is driven the other way:
        the profile claims a mesh this run does not have."""
        profile = _fit_profile()
        profile["device_topology"] = dict(profile["device_topology"])
        claimed = int(profile["device_topology"]["device_count"]) * 8
        profile["device_topology"]["device_count"] = claimed
        with pytest.raises(planner.PlanTopologyError) as exc:
            planner.plan_from_profile(profile)
        assert "device_count" in str(exc.value)
        assert str(claimed) in str(exc.value)

    def test_one_device_profile_refuses_on_this_mesh(self):
        """The satellite direction proper: an explicit current-topology
        override proves a 1-device run refuses an 8-vdev profile."""
        profile = _fit_profile()
        profile["device_topology"] = dict(
            profile["device_topology"], device_count=8
        )
        one_dev = dict(profile["device_topology"], device_count=1)
        with pytest.raises(planner.PlanTopologyError) as exc:
            planner.check_topology(
                profile["device_topology"], current=one_dev
            )
        assert "device_count" in str(exc.value)

    def test_platform_mismatch_names_platform(self):
        profile = _fit_profile()
        profile["device_topology"] = dict(profile["device_topology"])
        profile["device_topology"]["platform"] = "tpu-v999"
        with pytest.raises(planner.PlanTopologyError) as exc:
            planner.plan_from_profile(profile)
        assert "platform" in str(exc.value)

    def test_r06_era_profile_without_plan_block_loads(self, tmp_path):
        """read_profile of a pre-planner profile (no `plan` key) still
        loads, and the planner cold-starts from it."""
        profile = _fit_profile()
        assert "plan" not in profile  # the r06-era shape
        path = str(tmp_path / "r06.json")
        telemetry.write_profile(path, profile)
        back = telemetry.read_profile(path, kind="fit")
        assert "plan" not in back
        plan = planner.plan_from_profile(back, path)
        assert plan.profile_path == path
        assert plan.decisions  # cold start produced a real plan

    def test_ensure_ambient_plan_from_env_profile(self, monkeypatch, tmp_path):
        path = str(tmp_path / "profile.json")
        telemetry.write_profile(path, _fit_profile())
        monkeypatch.setenv("PHOTON_PLAN_PROFILE", path)
        plan = planner.ensure_ambient_plan()
        assert plan is not None and plan.profile_path == path
        # Idempotent: a second call returns the installed plan.
        assert planner.ensure_ambient_plan() is plan

    def test_env_profile_path_bootstraps_when_missing(
        self, monkeypatch, tmp_path
    ):
        """PHOTON_PLAN_PROFILE is a cache handle: pointing it at a
        not-yet-written path (a first round) runs unplanned
        instead of crashing — but an explicit --profile stays loud."""
        missing = str(tmp_path / "not_written_yet.json")
        monkeypatch.setenv("PHOTON_PLAN_PROFILE", missing)
        assert planner.ensure_ambient_plan() is None
        assert planner.current_plan() is None
        with pytest.raises(FileNotFoundError):
            planner.ensure_ambient_plan(missing)  # the explicit argument

    def test_estimator_owns_its_env_installed_plan(
        self, monkeypatch, tmp_path
    ):
        """A plan the FIT installed from the env is uninstalled when the
        fit returns — a later fit under a changed env must never reuse
        it — while the fit's own plan block still records it active."""
        est_a = _estimator()
        est_a.fit(_data(5), None, [_CFG])
        path = str(tmp_path / "profile.json")
        telemetry.write_profile(path, est_a.run_profile())
        monkeypatch.setenv("PHOTON_PLAN_PROFILE", path)
        est_b = _estimator()
        est_b.fit(_data(5), None, [_CFG])
        assert est_b.fit_timing["plan"]["active"] is True
        assert planner.current_plan() is None  # released on exit


# ------------------------------------------------------- end-to-end parity


class TestFitParity:
    def test_planned_fit_bitwise_equals_default_and_records_block(
        self, tmp_path
    ):
        est_a = _estimator()
        res_a = est_a.fit(_data(0), None, [_CFG])[0]
        block_a = est_a.fit_timing["plan"]
        assert block_a["active"] is False

        path = str(tmp_path / "profile.json")
        telemetry.write_profile(path, est_a.run_profile())
        plan = planner.plan_from_profile(
            telemetry.read_profile(path, kind="fit"), path
        )
        planner.install_plan(plan)
        est_b = _estimator()
        res_b = est_b.fit(_data(0), None, [_CFG])[0]
        block_b = est_b.fit_timing["plan"]
        assert block_b["active"] is True
        assert block_b["source"] == "profile"
        assert block_b["profile"] == path
        assert {d["decision"] for d in block_b["decisions"]} >= {
            "assembly_routing",
            "prefetch_depth",
            "re_bucket_shapes",
            "scan_fusion_max",
        }
        np.testing.assert_array_equal(
            np.asarray(res_a.model["fixed"].coefficients.means),
            np.asarray(res_b.model["fixed"].coefficients.means),
        )
        np.testing.assert_array_equal(
            np.asarray(res_a.model["per-member"].coefficients_matrix),
            np.asarray(res_b.model["per-member"].coefficients_matrix),
        )
        # The planned run's profile carries its plan block and
        # round-trips through the loud contract unchanged.
        path_b = str(tmp_path / "planned.json")
        telemetry.write_profile(path_b, est_b.run_profile())
        assert telemetry.read_profile(path_b, kind="fit")["plan"] == block_b

    def test_scan_fusion_cap_is_bitwise(self, tmp_path):
        """Chunked scan groups (fusion cap 1: one bucket per program)
        reproduce the unbounded-fusion model bit for bit."""
        est_a = _estimator()
        res_a = est_a.fit(_data(2), None, [_CFG])[0]
        profile = est_a.run_profile()
        profile["fit_timing"]["robustness"] = {
            "collective_retries": 1,  # trips the fusion-cap rule
            "watchdog_trips": 0,
        }
        profile["bucket_shapes"] = {}  # every shape is "novel" too
        plan = planner.plan_from_profile(profile)
        assert plan.decisions["scan_fusion_max"].value == 8
        planner.install_plan(plan)
        est_b = _estimator()
        res_b = est_b.fit(_data(2), None, [_CFG])[0]
        np.testing.assert_array_equal(
            np.asarray(res_a.model["per-member"].coefficients_matrix),
            np.asarray(res_b.model["per-member"].coefficients_matrix),
        )

    def test_fusion_chunks_unit(self):
        from photon_ml_tpu.game.coordinate import _fusion_chunks

        idxs = [0, 1, 2, 3, 4]
        # No plan: unbounded.
        assert _fusion_chunks(idxs, (4, 8), None) == [idxs]
        # Proven shape: unbounded even with shape evidence present.
        assert _fusion_chunks(idxs, (4, 8), {(4, 8)}) == [idxs]
        # Novel shape: conservative chunks of NOVEL_SHAPE_FUSE.
        many = list(range(20))
        chunks = _fusion_chunks(many, (4, 8), {(2, 16)})
        assert chunks == [many[0:8], many[8:16], many[16:20]]
        assert [i for c in chunks for i in c] == many  # order preserved


# ---------------------------------------------------------------- serving


class TestLayoutEvidence:
    def test_merge_note_collapses_disagreement_to_mixed(self):
        from photon_ml_tpu.utils.observability import TimingRegistry

        reg = TimingRegistry()
        reg.merge_note("sparse_layout", "rowalign", "mixed")
        assert reg.get_note("sparse_layout") == "rowalign"
        reg.merge_note("sparse_layout", "rowalign", "mixed")
        assert reg.get_note("sparse_layout") == "rowalign"
        reg.merge_note("sparse_layout", "grouped", "mixed")
        assert reg.get_note("sparse_layout") == "mixed"
        # Sticky: later agreement cannot un-mix a mixed fit.
        reg.merge_note("sparse_layout", "grouped", "mixed")
        assert reg.get_note("sparse_layout") == "mixed"

    def test_mixed_layout_plans_nothing(self):
        profile = _fit_profile()
        profile["dispatch"]["layout"] = "mixed"
        plan = planner.plan_from_profile(profile)
        assert "sparse_layout" not in plan.decisions

    def test_layout_evidence_is_per_fit_not_per_estimator(self):
        """A later fit on the same estimator must not inherit a previous
        fit's layout note as its own profile evidence — the notes clear
        at fit start (a fit that packed nothing honestly reports
        'none', and a one-time 'mixed' cannot pin future profiles)."""
        est = _estimator()
        ds = _data(11)
        est.fit(ds, None, [_CFG])
        # A stale note from a hypothetical earlier sparse fit:
        est.timing_registry.merge_note("sparse_layout", "rowalign", "mixed")
        est.fit(ds, None, [_CFG])  # dense refit: packs nothing
        assert est.run_profile()["dispatch"]["layout"] == "none"


class TestServingConsultation:
    def test_engine_and_batcher_resolve_from_plan(self):
        plan = planner.plan_from_profile(_serve_profile())
        planner.install_plan(plan)
        assert planner.planned_value("serving_max_batch") == 32
        assert planner.planned_value("serving_max_wait_ms") == 2.0
        from photon_ml_tpu.serving.engine import _bucket_sizes

        assert _bucket_sizes(int(planner.planned_value("serving_max_batch"))) \
            == (1, 2, 4, 8, 16, 32)


# ----------------------------------------------------------------- journal


class TestJournalAndDiff:
    def test_install_plan_journals_valid_plan_decisions(self, tmp_path):
        path = str(tmp_path / "journal.jsonl")
        journal = telemetry.RunJournal(path)
        telemetry.install_journal(journal)
        try:
            planner.install_plan(planner.plan_from_profile(_fit_profile()))
        finally:
            telemetry.uninstall_journal()
            journal.close()
        n_ok, errors = telemetry.validate_journal(path)
        assert errors == []
        types = [
            json.loads(line)["type"] for line in open(path) if line.strip()
        ]
        assert types.count("plan_decision") == len(
            planner.current_plan().decisions
        )
        assert n_ok == len(types)

    def test_profile_diff_cli(self, tmp_path, capsys):
        from photon_ml_tpu.cli import obs

        a = str(tmp_path / "a.json")
        b = str(tmp_path / "b.json")
        prof_a = _fit_profile()
        telemetry.write_profile(a, prof_a)
        prof_b = _fit_profile()
        prof_b["stages"] = dict(prof_a["stages"], solve_s=5.0)
        prof_b["dispatch"] = dict(prof_a["dispatch"], layout="rowalign")
        prof_b["plan"] = planner.plan_from_profile(prof_a).block()
        telemetry.write_profile(b, prof_b)

        assert obs.main(["profile", "diff", a, b]) == 0
        out = capsys.readouterr().out
        assert "solve_s" in out and "-2.000s" in out  # stage delta
        assert "layout" in out and "rowalign" in out  # dispatch change
        assert "+ pack_routing" in out  # plan-block decision added

    def test_profile_diff_contract_violation_exits_nonzero(
        self, tmp_path, capsys
    ):
        from photon_ml_tpu.cli import obs

        a = str(tmp_path / "a.json")
        telemetry.write_profile(a, _fit_profile())
        broken = str(tmp_path / "broken.json")
        doc = _fit_profile()
        del doc["stages"]
        with open(broken, "w") as f:
            json.dump(doc, f)  # bypass write_profile's validation
        assert obs.main(["profile", "diff", a, broken]) == 1
        assert "CONTRACT VIOLATION" in capsys.readouterr().out

    def test_profile_diff_kind_mismatch_exits_nonzero(self, tmp_path, capsys):
        from photon_ml_tpu.cli import obs

        a = str(tmp_path / "a.json")
        b = str(tmp_path / "b.json")
        telemetry.write_profile(a, _fit_profile())
        telemetry.write_profile(b, _serve_profile())
        assert obs.main(["profile", "diff", a, b]) == 1
        assert "kinds differ" in capsys.readouterr().out
