"""The record of every program the process makes ready
(utils/compile_cache.py): which program, under which stage, hit or miss, and
the self seconds of each phase, from JAX's own monitoring events."""

import contextlib
import json
import logging
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import pytest

from photon_ml_tpu.analysis import run_checks
from photon_ml_tpu.cli import obs
from photon_ml_tpu.utils import compile_cache, telemetry
from photon_ml_tpu.utils.compile_cache import PHASES
from photon_ml_tpu.utils.observability import current_stage, stage_timer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def registry(monkeypatch):
    """A registry of this test's own, and the listeners on."""
    fresh = telemetry.MetricsRegistry()
    monkeypatch.setattr(telemetry, "METRICS", fresh)
    compile_cache.listen()
    return fresh


def made_since(n0):
    return compile_cache.programs()[n0:]


def fresh_jit(body=lambda x: jnp.sin(x) * 2.0):
    """A jitted function no other test has called: its first call compiles."""

    def a_program_of_this_test(x):
        return body(x)

    return jax.jit(a_program_of_this_test)


def test_first_call_under_a_stage_leaves_one_record_and_the_second_none():
    f, x = fresh_jit(), jnp.ones(7)
    n0 = len(compile_cache.programs())
    with stage_timer("cd/train"):
        f(x)
    (record,) = made_since(n0)
    assert record["program"] == "jit(a_program_of_this_test)"
    assert (record["stage"], record["hit"]) == ("cd/train", False)
    assert record["trace"] > 0 and record["lower"] > 0 and record["compile"] > 0
    assert record["cache_read"] == 0.0
    assert abs(record["start"] - time.time()) < 60
    with stage_timer("cd/train"):
        f(x)
    assert len(made_since(n0)) == 1


def test_a_nested_trace_counts_once_and_phases_fit_in_the_wall(registry):
    nap = 0.2

    @jax.jit
    def inner(x):
        time.sleep(nap)  # runs while JAX traces `inner`, inside the trace of the outer
        return jnp.cos(x)

    f, x = fresh_jit(lambda x: inner(x) + 1.0), jnp.ones(5)
    n0 = len(compile_cache.programs())
    with stage_timer("cd/score") as block:
        f(x)
    (record,) = made_since(n0)
    seconds = registry.labeled_histograms("program_ready_s")
    traced = seconds["phase=trace,stage=cd/score"]
    assert traced["count"] >= 2 and traced["max"] >= nap  # the inner span, whole
    assert traced["sum"] < 2 * nap  # and not a second time in the outer's
    assert record["trace"] == pytest.approx(traced["sum"])
    staged = sum(s["sum"] for key, s in seconds.items() if key.endswith("stage=cd/score"))
    assert staged == pytest.approx(sum(record[p] for p in PHASES))
    assert staged <= block.seconds


def test_an_eager_dispatch_inside_a_trace_is_not_counted_in_the_trace(registry):
    """A traced body that dispatches a program of its own: the inner
    program's lowering and compilation are its record's, not trace seconds
    of the outer."""

    def body(x):
        with jax.ensure_compile_time_eval():  # dispatched now, while tracing
            jnp.arange(11.0) * 3.0
        return x + 1.0

    f = fresh_jit(body)
    x = jnp.ones(3)
    n0 = len(compile_cache.programs())
    with stage_timer("cd/commit") as block:
        f(x)
    records = made_since(n0)
    assert len(records) >= 2 and records[-1]["program"] == "jit(a_program_of_this_test)"
    seconds = registry.labeled_histograms("program_ready_s")
    staged = sum(s["sum"] for key, s in seconds.items() if key.endswith("stage=cd/commit"))
    assert staged == pytest.approx(sum(r[p] for r in records for p in PHASES))
    assert staged <= block.seconds


def test_a_call_outside_every_stage_reads_none():
    assert current_stage() == "none"
    x = jnp.ones(9)  # an eager program of its own
    n0 = len(compile_cache.programs())
    fresh_jit()(x)
    (record,) = made_since(n0)
    assert record["stage"] == "none"
    with stage_timer("fit"), stage_timer("fit/descent"):
        assert current_stage() == "fit/descent"
    assert current_stage() == "none"


_CHILD = textwrap.dedent(
    """
    import json, sys
    import jax, jax.numpy as jnp
    from photon_ml_tpu.utils import compile_cache, telemetry
    from photon_ml_tpu.utils.observability import stage_timer

    compile_cache.enable()
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    g = jax.jit(lambda x: jnp.cumsum(x) * 2.0)
    a, b = jnp.ones((6, 6)), jnp.ones(6)
    with stage_timer("cd/train"):
        f(a)
    g(b) + 1.0
    get = telemetry.METRICS.get_counter
    print(json.dumps({
        "programs": compile_cache.programs(),
        "requests": get("compile_cache_requests"),
        "hits": get("compile_cache_hits"),
        "by_stage": telemetry.METRICS.labeled_counters("compile_cache_requests"),
    }))
    """
)


def test_two_processes_on_one_cache_miss_then_hit(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path), PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)
    runs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _CHILD], env=env, capture_output=True, text=True, timeout=300
        )
        assert out.returncode == 0, out.stderr[-2000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    cold, warm = runs
    assert len(cold["programs"]) == len(warm["programs"]) >= 3
    assert [r["program"] for r in cold["programs"]] == [r["program"] for r in warm["programs"]]
    for r in cold["programs"]:
        assert not r["hit"] and r["compile"] > 0 and r["cache_read"] == 0
    for r in warm["programs"]:
        assert r["hit"] and r["cache_read"] > 0 and r["compile"] == 0
    for run in runs:
        misses = sum(not r["hit"] for r in run["programs"])
        assert misses == run["requests"] - run["hits"]
        assert run["requests"] == len(run["programs"]) == sum(run["by_stage"].values())
        assert run["by_stage"]["stage=cd/train"] == 1
    assert (cold["hits"], warm["hits"]) == (0, warm["requests"])


def test_a_miss_inside_a_fit_after_a_completed_fit_is_logged_by_name(registry, caplog):
    f, g, x = fresh_jit(), fresh_jit(lambda x: x * 3.0), jnp.ones(4)
    with caplog.at_level(logging.WARNING, logger=compile_cache.logger.name):
        with stage_timer("fit"), stage_timer("cd/train"):
            f(x)  # no fit has completed yet: the warm fit compiles, silently
        assert not caplog.records
        registry.observe("fit_stage_s", 0.5, labels=(("stage", "fit"),))  # what a finished fit publishes
        g(x)  # outside a fit: a caller's own program
        assert not caplog.records
        with stage_timer("fit"), stage_timer("cd/validation_evaluate"):
            fresh_jit(lambda x: x - 5.0)(x)
    (warning,) = caplog.records
    assert "jit(a_program_of_this_test)" in warning.getMessage()
    assert "cd/validation_evaluate" in warning.getMessage()


def test_the_journal_line_of_a_miss_passes_validation(tmp_path):
    path = str(tmp_path / "journal.jsonl")
    x = jnp.ones(13)
    journal = telemetry.install_journal(telemetry.RunJournal(path))
    try:
        with stage_timer("cd/train"):
            fresh_jit()(x)
    finally:
        telemetry.uninstall_journal()
        journal.close()
    assert telemetry.validate_journal(path) == (1, [])
    with open(path) as f:
        (line,) = [json.loads(raw) for raw in f]
    assert (line["type"], line["program"], line["stage"]) == (
        "program_compiled", "jit(a_program_of_this_test)", "cd/train",
    )
    (record,) = [r for r in compile_cache.programs() if r["start"] >= line["ts"] - 60][-1:]
    assert line["seconds"] == pytest.approx(sum(record[p] for p in PHASES), abs=1e-6)


def a_profile(path, records):
    profile = telemetry.build_profile(
        "fit", wall_s=1.0, stages={"compile": 0.2, "prepare_s": 0.4}, dispatch={}, bucket_shapes={},
        fit_timing={}, topology={"platform": "cpu", "device_count": 1, "device_kind": "cpu"},
    )
    profile["programs"] = compile_cache.summary(records)
    return telemetry.write_profile(str(path), profile)


RECORDS = [
    {"program": "jit(train_fn)", "stage": "cd/train", "hit": False, "start": 1.0,
     "trace": 0.25, "lower": 0.5, "cache_read": 0.0, "compile": 4.0},
    {"program": "jit(evaluate_metrics)", "stage": "cd/validation_evaluate", "hit": True, "start": 2.0,
     "trace": 0.125, "lower": 0.25, "cache_read": 0.5, "compile": 0.0},
    {"program": "jit(iota)", "stage": "cd/train", "hit": True, "start": 3.0,
     "trace": 0.0, "lower": 0.125, "cache_read": 0.125, "compile": 0.0},
]


def test_read_profile_accepts_the_programs_block_and_obs_prints_it(tmp_path, capsys):
    path = a_profile(tmp_path / "profile.json", RECORDS)
    block = telemetry.read_profile(path, kind="fit")["programs"]
    assert block["stages"]["cd/train"] == {
        "programs": 2, "hits": 1,
        "seconds": {"trace": 0.25, "lower": 0.625, "cache_read": 0.125, "compile": 4.0},
    }
    assert [m["program"] for m in block["misses"]] == ["jit(train_fn)"]
    assert obs.main(["profile", path]) == 0
    out = capsys.readouterr().out
    assert "programs made ready" in out and "cd/validation_evaluate" in out
    assert "compiled: jit(train_fn) under cd/train" in out and "compile 4.000s" in out


def test_obs_profile_diff_compares_the_programs_of_two_runs(tmp_path, capsys):
    cold = a_profile(tmp_path / "cold.json", RECORDS)
    warm = a_profile(tmp_path / "warm.json", [dict(r, hit=True, compile=0.0) for r in RECORDS[1:]])
    assert obs.main(["profile", "diff", cold, warm]) == 0
    out = capsys.readouterr().out
    assert "cd/train: 2 -> 1 programs, 1 -> 1 hits, 5.000s -> 0.250s" in out
    assert "compiled in a: jit(train_fn) under cd/train" in out and "compiled in b" not in out


def test_a_counter_is_the_sum_of_its_stages(registry):
    """What run.py and chip_smoke.py read through `get_counter` is what it
    was: a labelled increment adds to the aggregate too."""
    for stage, requests, hits in (("cd/train", 3, 2), ("fit/descent", 1, 1), (None, 4, 0)):
        for event, n in ((compile_cache._REQUEST, requests), (compile_cache._HIT, hits)):
            for _ in range(n):
                with stage_timer(stage) if stage else contextlib.nullcontext():
                    compile_cache._on_event(event)
    for name, total in (("compile_cache_requests", 8), ("compile_cache_hits", 3)):
        parts = registry.labeled_counters(name)
        assert registry.get_counter(name) == sum(parts.values()) == total
    assert registry.labeled_counters("compile_cache_requests") == {
        "stage=cd/train": 3, "stage=fit/descent": 1, "stage=none": 4,
    }


def test_listening_twice_registers_once():
    from jax._src import monitoring  # the public module has no way to ask

    before = len(monitoring.get_event_time_span_listeners())
    compile_cache.listen()
    assert len(monitoring.get_event_time_span_listeners()) == before
    assert monitoring.get_event_time_span_listeners().count(compile_cache._on_span) == 1


def test_the_analyzer_finds_every_metric_name_declared_and_incremented():
    assert "program_ready_s" in telemetry.METRIC_DESCRIPTIONS
    assert "evaluation_traces" not in telemetry.METRIC_DESCRIPTIONS
    assert run_checks(checks=["metric-name-sync"]) == []
