"""photon-trace telemetry (utils/telemetry.py, ISSUE 11).

Four contracts:
  * spans from the named worker fleet land under the correct parent via
    the span_handoff/adopt_span discipline, with no orphans;
  * histogram merges are associative and order-independent, across
    threads and across subprocesses (the bench child merge path);
  * every journal event type round-trips its contracts.py schema;
  * with no tracer installed (PHOTON_TRACE=0), span() emits nothing and
    costs one global read — no measurable overhead on a tier-1 fit.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data.game_dataset import (
    FixedEffectDataConfig,
    GameDataset,
    RandomEffectDataConfig,
)
from photon_ml_tpu.estimators.game_estimator import GameEstimator
from photon_ml_tpu.optimize.config import (
    CoordinateOptimizationConfig,
    OptimizerConfig,
)
from photon_ml_tpu.types import TaskType
from photon_ml_tpu.utils import telemetry
from photon_ml_tpu.utils.contracts import (
    JOURNAL_EVENT_SCHEMAS,
    JOURNAL_LINE_KEYS,
    PROFILE_FIT_KEYS,
)
from photon_ml_tpu.utils.observability import EventEmitter, journal_listener

# One geometric bucket width: the histogram quantile accuracy bound.
_BUCKET_RATIO = 10.0 ** (1.0 / 16.0)


def _assert_snapshots_equal(a, b):
    """Snapshot equality modulo float-summation order: buckets, count,
    min and max are exactly associative; `sum` is a float accumulation,
    equal only to rounding."""
    assert {k: v for k, v in a.items() if k != "sum"} == {
        k: v for k, v in b.items() if k != "sum"
    }
    assert a["sum"] == pytest.approx(b["sum"])


@pytest.fixture
def tracer():
    t = telemetry.install_tracer(telemetry.Tracer())
    yield t
    telemetry.uninstall_tracer()


def _game_fixture(rng, n=192, n_entities=8):
    X = rng.normal(size=(n, 6)).astype(np.float32)
    y = (rng.uniform(size=n) > 0.5).astype(np.float32)
    ents = rng.integers(0, n_entities, size=n).astype(str)
    return GameDataset.build(
        {"g": X}, y, id_tags={"e1": ents, "e2": ents[::-1].copy()}
    )


def _fit_estimator(ds, tmp_path=None, emitter=None, pipeline=None):
    est = GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {
            "global": FixedEffectDataConfig("g"),
            "per-e1": RandomEffectDataConfig("e1", "g"),
            "per-e2": RandomEffectDataConfig("e2", "g"),
        },
        event_emitter=emitter,
        pipeline=pipeline,
        checkpoint_dir=None if tmp_path is None else str(tmp_path / "ckpt"),
    )
    cfg = {
        cid: CoordinateOptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=3)
        )
        for cid in ("global", "per-e1", "per-e2")
    }
    return est, est.fit(ds, None, [cfg])


# ------------------------------------------------------------------- spans


class TestSpans:
    def test_handoff_parents_worker_spans(self, tracer):
        """The AsyncUploader pattern: a worker thread adopting the
        submitter's handoff parents its spans under the submitter's span."""
        results = []

        def worker(handoff):
            with telemetry.adopt_span(handoff), telemetry.span("child"):
                pass
            results.append(True)

        with telemetry.span("parent"):
            h = telemetry.span_handoff()
            t = threading.Thread(target=worker, args=(h,), name="photon-test")
            t.start()
            t.join()
        spans = {s["args"]["span_id"]: s for s in tracer.spans()}
        child = next(s for s in tracer.spans() if s["name"] == "child")
        parent = next(s for s in tracer.spans() if s["name"] == "parent")
        assert child["args"]["parent_id"] == parent["args"]["span_id"]
        assert child["tid"] != parent["tid"]
        assert all(
            s["args"].get("parent_id") is None
            or s["args"]["parent_id"] in spans
            for s in tracer.spans()
        )

    def test_fit_worker_fleet_spans_parent_correctly(self, rng, tracer):
        """A pipelined fit fans work onto the photon-prepare pool and the
        async upload/pack workers; every span from a named worker thread
        must resolve to an in-trace parent — no orphans."""
        ds = _game_fixture(rng)
        _fit_estimator(ds, pipeline=True)
        spans = tracer.spans()
        by_id = {s["args"]["span_id"]: s for s in spans}
        assert any(s["name"] == "fit" for s in spans)
        assert any(s["name"] == "re_build" for s in spans)
        # No orphans anywhere: every parent reference resolves.
        for s in spans:
            pid = s["args"].get("parent_id")
            assert pid is None or pid in by_id, f"orphan span {s['name']}"
        # Spans recorded OFF the main thread (the worker fleet) must have
        # adopted a parent — a parentless worker span is a lost handoff.
        trace = tracer.to_chrome_trace()
        names = {
            e["tid"]: e["args"]["name"]
            for e in trace["traceEvents"]
            if e.get("ph") == "M"
        }
        main_tid = threading.get_ident()
        worker_spans = [s for s in spans if s["tid"] != main_tid]
        assert worker_spans, "pipelined fit recorded no worker-thread spans"
        for s in worker_spans:
            assert s["args"].get("parent_id") is not None, (
                f"span {s['name']} on thread {names.get(s['tid'])} "
                "has no parent"
            )
            assert names.get(s["tid"], "").startswith("photon-")

    @pytest.mark.serving
    def test_serving_batch_spans(self, rng, tracer):
        """The batcher's flush thread records serving_batch spans with
        queue-wait attribution; the engine's pack/lookup/score stage
        spans nest under them on the same thread."""
        from tests.test_serving import TASK, _fixture

        from photon_ml_tpu.serving import ServingBundle, ServingEngine

        model, specs, _, reqs = _fixture(rng)
        engine = ServingEngine(
            ServingBundle.from_model(model, specs, TASK), max_batch=8
        )
        with engine, engine.batcher(max_wait_ms=1.0) as batcher:
            batcher.score_all(reqs)
        spans = tracer.spans()
        batches = [s for s in spans if s["name"] == "serving_batch"]
        assert batches
        assert all("queue_wait_ms_max" in b["args"] for b in batches)
        batch_ids = {b["args"]["span_id"] for b in batches}
        packs = [s for s in spans if s["name"] == "serve_pack"]
        assert packs and all(
            p["args"]["parent_id"] in batch_ids for p in packs
        )

    def test_export_is_chrome_loadable_json(self, tracer, tmp_path):
        with telemetry.span("a", tag="x"):
            with telemetry.span("b"):
                pass
        path = tracer.export(str(tmp_path / "trace.json"))
        doc = json.load(open(path))
        assert isinstance(doc["traceEvents"], list)
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in xs} == {"a", "b"}
        for e in xs:  # Perfetto-required fields
            assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        b = next(e for e in xs if e["name"] == "b")
        a = next(e for e in xs if e["name"] == "a")
        assert b["args"]["parent_id"] == a["args"]["span_id"]


# --------------------------------------------------------------- histograms


class TestHistogramMerge:
    def test_quantiles_within_one_bucket(self, rng):
        vals = np.exp(rng.normal(size=20_000) * 2.0)
        h = telemetry.Histogram()
        for v in vals:
            h.record(float(v))
        for q in (0.5, 0.95, 0.99):
            exact = float(np.quantile(vals, q))
            est = h.quantile(q)
            assert est / exact < _BUCKET_RATIO * 1.01
            assert exact / est < _BUCKET_RATIO * 1.01

    def test_merge_associative_and_order_independent(self, rng):
        vals = [float(v) for v in np.exp(rng.normal(size=3000))]
        parts = [telemetry.Histogram() for _ in range(4)]
        for i, v in enumerate(vals):
            parts[i % 4].record(v)
        snaps = [p.snapshot() for p in parts]
        m = telemetry.merge_histogram_snapshots
        left = m(m(m(snaps[0], snaps[1]), snaps[2]), snaps[3])
        right = m(snaps[0], m(snaps[1], m(snaps[2], snaps[3])))
        shuffled = m(snaps[3], snaps[1], snaps[0], snaps[2])
        _assert_snapshots_equal(left, right)
        _assert_snapshots_equal(left, shuffled)
        whole = telemetry.Histogram()
        for v in vals:
            whole.record(v)
        _assert_snapshots_equal(left, whole.snapshot())

    def test_thread_level_merge(self, rng):
        """Concurrent recorders into ONE histogram lose nothing, and
        per-thread histograms merge to the same snapshot — the two ways
        threads share the registry."""
        vals = [float(v) for v in np.exp(rng.normal(size=2000))]
        shared = telemetry.Histogram()
        locals_ = [telemetry.Histogram() for _ in range(4)]

        def work(k):
            for v in vals[k::4]:
                shared.record(v)
                locals_[k].record(v)

        threads = [
            threading.Thread(target=work, args=(k,), name=f"photon-test-{k}")
            for k in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        merged = telemetry.merge_histogram_snapshots(
            *[h.snapshot() for h in locals_]
        )
        _assert_snapshots_equal(merged, shared.snapshot())
        assert merged["count"] == len(vals)

    @pytest.mark.slow
    def test_subprocess_merge(self, tmp_path):
        """The bench-child path: a snapshot serialized from another
        process merges with a local one exactly (fixed shared bounds)."""
        code = (
            "from photon_ml_tpu.utils import telemetry\n"
            "import json\n"
            "h = telemetry.Histogram()\n"
            "for i in range(1, 1001):\n"
            "    h.record(i * 0.5)\n"
            "print(json.dumps(h.snapshot()))\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=120,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert out.returncode == 0, out.stderr
        remote = json.loads(out.stdout.strip().splitlines()[-1])
        local = telemetry.Histogram()
        for i in range(1, 1001):
            local.record(i * 0.5)
        assert remote == local.snapshot()
        merged = telemetry.merge_histogram_snapshots(remote, local.snapshot())
        assert merged["count"] == 2000
        assert merged["min"] == 0.5 and merged["max"] == 500.0


class TestLatencyStats:
    def test_small_run_exact(self, rng):
        stats = telemetry.LatencyStats(reservoir=256)
        vals = [float(v) for v in np.exp(rng.normal(size=100))]
        for v in vals:
            stats.record(v)
        for q in (50.0, 95.0, 99.0):
            assert stats.percentile(q) == pytest.approx(
                float(np.percentile(vals, q))
            )

    def test_sustained_traffic_bounded_and_close(self, rng):
        stats = telemetry.LatencyStats(reservoir=128)
        vals = [float(v) for v in np.exp(rng.normal(size=10_000))]
        for v in vals:
            stats.record(v)
        # Memory bound: reservoir never grows past its cap.
        assert len(stats._reservoir) == 128
        for q in (50.0, 95.0, 99.0):
            exact = float(np.percentile(vals, q))
            est = stats.percentile(q)
            assert est / exact < _BUCKET_RATIO * 1.01
            assert exact / est < _BUCKET_RATIO * 1.01


# ----------------------------------------------------------- labeled metrics


class TestLabeledMetrics:
    """ISSUE 19: gauges and histograms carry the same per-label
    attribution counters grew in ISSUE 15 — aggregates intact, labeled
    sub-series over the SAME fixed bucket bounds (so they merge exactly
    as order-independently as the aggregates)."""

    def test_labeled_observe_keeps_aggregate_intact(self, rng):
        vals_a = [float(v) for v in np.exp(rng.normal(size=400))]
        vals_b = [float(v) for v in np.exp(rng.normal(size=300) + 1.0)]
        for v in vals_a:
            telemetry.METRICS.observe(
                "serving_latency_ms", v, labels=(("tenant", "a"),)
            )
        for v in vals_b:
            telemetry.METRICS.observe(
                "serving_latency_ms", v, labels=(("tenant", "b"),)
            )
        telemetry.METRICS.observe("serving_latency_ms", 1.0)  # unlabeled
        agg = telemetry.METRICS.histogram("serving_latency_ms")
        assert agg.snapshot()["count"] == len(vals_a) + len(vals_b) + 1
        labeled = telemetry.METRICS.labeled_histograms("serving_latency_ms")
        assert set(labeled) == {"tenant=a", "tenant=b"}
        assert labeled["tenant=a"]["count"] == len(vals_a)
        assert labeled["tenant=b"]["count"] == len(vals_b)
        # Per-label quantiles differ the way the data does.
        qa = telemetry.snapshot_quantile(labeled["tenant=a"], 0.95)
        qb = telemetry.snapshot_quantile(labeled["tenant=b"], 0.95)
        assert qb > qa
        # The live per-label handle agrees with the snapshot.
        h = telemetry.METRICS.labeled_histogram(
            "serving_latency_ms", (("tenant", "a"),)
        )
        assert h is not None and h.snapshot()["count"] == len(vals_a)

    def test_labeled_merge_is_order_independent(self, rng):
        """Labeled sub-snapshots share the aggregate's fixed bucket
        bounds: merging them in ANY order reproduces the aggregate
        (when every observe was labeled)."""
        vals = [float(v) for v in np.exp(rng.normal(size=2000))]
        tenants = ("a", "b", "c", "d")
        for i, v in enumerate(vals):
            telemetry.METRICS.observe(
                "serving_queue_wait_ms",
                v,
                labels=(("tenant", tenants[i % 4]),),
            )
        labeled = telemetry.METRICS.labeled_histograms(
            "serving_queue_wait_ms"
        )
        snaps = [labeled[f"tenant={t}"] for t in tenants]
        m = telemetry.merge_histogram_snapshots
        fwd = m(snaps[0], snaps[1], snaps[2], snaps[3])
        rev = m(snaps[3], snaps[2], snaps[1], snaps[0])
        nested = m(m(snaps[2], snaps[0]), m(snaps[1], snaps[3]))
        _assert_snapshots_equal(fwd, rev)
        _assert_snapshots_equal(fwd, nested)
        agg = telemetry.METRICS.histogram("serving_queue_wait_ms")
        _assert_snapshots_equal(fwd, agg.snapshot())

    def test_label_scope_routes_gauges_and_histograms(self):
        with telemetry.metric_label_scope(tenant="a"):
            telemetry.METRICS.set_gauge("serving_pending_depth", 3.0)
            telemetry.METRICS.observe("serving_batch_size", 8.0)
        telemetry.METRICS.set_gauge("serving_pending_depth", 5.0)
        gauges = telemetry.METRICS.labeled_gauges("serving_pending_depth")
        assert gauges == {"tenant=a": 3.0}
        labeled = telemetry.METRICS.labeled_histograms("serving_batch_size")
        assert labeled["tenant=a"]["count"] == 1
        snap = telemetry.METRICS.snapshot()
        assert snap["gauges"]["serving_pending_depth"] == 5.0
        assert (
            snap["labeled_gauges"]["serving_pending_depth"]["tenant=a"]
            == 3.0
        )
        assert (
            snap["labeled_histograms"]["serving_batch_size"]["tenant=a"][
                "count"
            ]
            == 1
        )

    def test_undeclared_names_refused_and_reset_clears_labels(self):
        with pytest.raises(KeyError):
            telemetry.METRICS.observe("no_such_metric", 1.0)
        with pytest.raises(KeyError):
            telemetry.METRICS.set_gauge("no_such_metric", 1.0)
        telemetry.METRICS.observe(
            "serving_batch_size", 4.0, labels=(("tenant", "a"),)
        )
        telemetry.METRICS.reset_counters()  # counters only: labels stay
        assert telemetry.METRICS.labeled_histograms("serving_batch_size")
        telemetry.METRICS.reset()
        assert (
            telemetry.METRICS.labeled_histograms("serving_batch_size") == {}
        )
        assert telemetry.METRICS.labeled_gauges("serving_pending_depth") == {}


# ------------------------------------------------------------------ journal


class TestJournal:
    _SAMPLE = {
        "args": "ns",
        "num_samples": 7,
        "index": 0,
        "total": 2,
        "iteration": 1,
        "coordinate": "per-e1",
        "seconds": 0.25,
        "accepted": True,
        "fn_evals": 11,
        "step": 3,
        "num_configs": 2,
        "best_metric": 0.91,
        "error": "RuntimeError('x')",
        "program": "jit(train_fn)",
        "stage": "cd/train",
        "from_state": "READY",
        "to_state": "DEGRADED",
        "reasons": ["circuit_open"],
        "version": 2,
        "outcome": "committed",
        "label": "serving dispatch",
        "counter": "retries",
        "attempt": 1,
        "site": "decode",
        "invocation": 4,
        "shard_index": 1,
        "bytes": 4096,
        # -- hyperparameter sweep lifecycle (ISSUE 12) --
        "round": 0,
        "trial": 5,
        "mode": "stacked",
        "value": 0.72,
        "diverged_steps": 0,
        # -- live mesh elasticity (ISSUE 13) --
        "old_shards": 8,
        "new_shards": 4,
        "moved_rows": 167,
        "moved_bytes": 5344,
        "restaged_bytes": 5344,
        "reason": "InjectedFault('reshard_stage')",
        "surviving_devices": 4,
        "source": "memory",
        # -- adaptive runtime planner (ISSUE 14) --
        "decision": "prefetch_depth",
        "fallback": 1,
        # -- multi-tenant serving (ISSUE 15) --
        "tenant": "t-a",
        "device_bytes": 4096,
        "demoted_tenants": ["t-cold"],
        "freed_bytes": 2048,
        "hot_rows": 0,
        # -- continuous refresh (ISSUE 16) --
        "changed_coordinates": ["per-e1"],
        "carried_coordinates": ["fixed"],
        "delta_rows": 96,
        "total_rows": 512,
        "max_rel_diff": 0.31,
        "coordinates": ["per-e1"],
        "rows": 96,
        # -- multi-host production mode (ISSUE 17) --
        "host": 1,
        "missed_beats": 20,
        "name": "ckpt-commit",
        "num_hosts": 2,
        "restaged_rows": 11,
        # -- shadow deployment & online evaluation (ISSUE 18) --
        "champion": "live",
        "challenger": "cand",
        "window_size": 64,
        "min_windows": 3,
        "mirror_fraction": 1.0,
        "window": 2,
        "champion_metric": 0.93,
        "challenger_metric": 0.88,
        "evaluator": "AUC",
        "healthy": False,
        "windows": 3,
        # -- closed-loop autoscaling (ISSUE 19) --
        "rule": "hbm-demote",
        "action": {"kind": "demote", "tenant": "t-cold", "params": {}},
        "evidence": {"signal": 0.91, "fire_above": 0.85},
        "rollbacks": 1,
        # -- precision ladder (ISSUE 20) --
        "from_tier": "f32",
        "to_tier": "bf16",
        "repinned_bytes": 2048,
    }

    def test_every_event_type_round_trips_its_schema(self, tmp_path):
        path = str(tmp_path / "journal.jsonl")
        with telemetry.RunJournal(path) as journal:
            for etype, schema in JOURNAL_EVENT_SCHEMAS.items():
                journal.emit(etype, **{k: self._SAMPLE[k] for k in schema})
        n_ok, errors = telemetry.validate_journal(path)
        assert errors == []
        assert n_ok == len(JOURNAL_EVENT_SCHEMAS)
        for raw in open(path):
            doc = json.loads(raw)
            schema = JOURNAL_EVENT_SCHEMAS[doc["type"]]
            body = {k for k in doc if k not in JOURNAL_LINE_KEYS}
            assert body == set(schema)
            for k in schema:  # values survive the trip
                assert doc[k] == self._SAMPLE[k]

    def test_schema_violations_raise_and_never_write(self, tmp_path):
        path = str(tmp_path / "journal.jsonl")
        with telemetry.RunJournal(path) as journal:
            with pytest.raises(KeyError):
                journal.emit("not_a_type", x=1)
            with pytest.raises(ValueError):
                journal.emit("watchdog_trip")  # missing `label`
            with pytest.raises(ValueError):
                journal.emit("watchdog_trip", label="x", extra=1)
        assert open(path).read() == ""

    def test_validate_flags_bad_lines(self, tmp_path):
        path = str(tmp_path / "journal.jsonl")
        with open(path, "w") as f:
            f.write('{"ts": 1.0, "type": "watchdog_trip", "label": "ok"}\n')
            f.write("not json\n")
            f.write('{"ts": 1.0, "type": "mystery"}\n')
            f.write('{"ts": 1.0, "type": "watchdog_trip"}\n')
        n_ok, errors = telemetry.validate_journal(path)
        assert n_ok == 1 and len(errors) == 3

    def test_estimator_lifecycle_lands_in_journal(self, rng, tmp_path):
        """The ISSUE 11 satellite: a LIBRARY fit (no CLI) with an emitter
        produces the same typed journal record as cli/train jobs —
        start, sweep, per-coordinate updates, checkpoints, finish."""
        path = str(tmp_path / "journal.jsonl")
        journal = telemetry.RunJournal(path)
        emitter = EventEmitter()
        emitter.register(journal_listener(journal))
        ds = _game_fixture(rng)
        _fit_estimator(ds, tmp_path=tmp_path, emitter=emitter)
        journal.close()
        n_ok, errors = telemetry.validate_journal(path)
        assert errors == []
        types = [json.loads(l)["type"] for l in open(path) if l.strip()]
        assert types[0] == "fit_start" and types[-1] == "fit_finish"
        assert types.count("sweep_config") == 1
        assert types.count("coordinate_update") == 3  # one per coordinate
        assert types.count("checkpoint") == 3  # checkpoint_dir was set
        updates = [
            json.loads(l)
            for l in open(path)
            if json.loads(l)["type"] == "coordinate_update"
        ]
        assert [u["coordinate"] for u in updates] == [
            "global",
            "per-e1",
            "per-e2",
        ]
        assert all(u["accepted"] for u in updates)


# ------------------------------------------------------------------ profile


class TestProfile:
    def test_fit_profile_round_trip_and_loud_contract(self, rng, tmp_path):
        ds = _game_fixture(rng)
        est, _ = _fit_estimator(ds)
        profile = est.run_profile()
        path = telemetry.write_profile(str(tmp_path / "profile.json"), profile)
        back = telemetry.read_profile(path, kind="fit")
        for key in PROFILE_FIT_KEYS:
            assert key in back
        assert back["dispatch"]["re_path"] in ("host", "device")
        assert back["bucket_shapes"]["per-e1"]
        # Loud contract: a dropped section must refuse to load.
        del back["dispatch"]
        broken = str(tmp_path / "broken.json")
        with open(broken, "w") as f:
            json.dump(back, f)
        with pytest.raises(ValueError, match="dispatch"):
            telemetry.read_profile(broken)
        with pytest.raises(ValueError, match="kind"):
            telemetry.read_profile(path, kind="serve")

    @pytest.mark.parametrize(
        "platform, kind, peak",
        [
            ("tpu", "TPU v5 lite", 819.0),  # what a v5e reports itself as
            ("cpu", "cpu", None),  # off the chip: no roofline annotation
            ("tpu", "TPU v9 imaginary", KeyError),  # never another chip's peak
        ],
    )
    def test_hbm_peak_is_keyed_by_device_kind(self, platform, kind, peak):
        topo = dict(telemetry.device_topology(), platform=platform, device_kind=kind)
        build = lambda: telemetry.build_profile(
            "serve", wall_s=0.0, stages={}, dispatch={}, bucket_shapes={},
            serving={}, topology=topo,
        )
        if peak is KeyError:
            with pytest.raises(KeyError, match="TPU v9 imaginary"):
                build()
        else:
            assert build()["roofline"] == {"hbm_gb_per_s": peak}


# ------------------------------------------------------- tracing-off no-ops


class TestTracingOff:
    def test_span_is_shared_noop_and_records_nothing(self):
        assert telemetry.current_tracer() is None
        s1 = telemetry.span("anything", x=1)
        s2 = telemetry.span("else")
        assert s1 is s2  # the shared singleton: no allocation per call
        with s1:
            pass
        assert telemetry.span_handoff() is None

    def test_untraced_fit_records_nothing_and_costs_nothing(self, rng):
        """PHOTON_TRACE=0 contract: no tracer -> a tier-1-sized fit emits
        zero spans, and the span() fast path is orders of magnitude below
        anything a fit could measure."""
        assert telemetry.current_tracer() is None
        ds = _game_fixture(rng)
        _fit_estimator(ds)
        assert telemetry.current_tracer() is None  # nothing installed
        n = 100_000
        t0 = time.perf_counter()
        for _ in range(n):
            with telemetry.span("x"):
                pass
        per_call_us = (time.perf_counter() - t0) / n * 1e6
        assert per_call_us < 25.0  # generous CI bound; typically ~0.3us
