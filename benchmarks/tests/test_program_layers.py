"""The readers of the program's record of making its programs ready: the two
metrics sum over the stages of the program and leave `none` out, a program
that keeps no record reads None, and a rehearsal of a whole traced run
reports both."""

import json

import pytest

from benchmarks import run
from benchmarks.layers import compile_path_s, programs, programs_requested
from photon_ml_tpu.utils import compile_cache, telemetry


@pytest.fixture
def registry(monkeypatch):
    fresh = telemetry.MetricsRegistry()
    monkeypatch.setattr(telemetry, "METRICS", fresh)
    monkeypatch.setattr(programs, "_printed", False)
    return fresh


def made_ready(registry, stage, requests, hits, **seconds):
    """What `utils/compile_cache` leaves of `requests` programs under `stage`."""
    for phase, each in seconds.items():
        for s in each:
            registry.observe("program_ready_s", s, labels=(("phase", phase), ("stage", stage)))
    registry.increment("compile_cache_requests", requests, labels=(("stage", stage),))
    if hits:
        registry.increment("compile_cache_hits", hits, labels=(("stage", stage),))


def test_the_readers_sum_the_stages_of_the_program_and_leave_none_out(registry, capsys):
    made_ready(registry, "cd/train", 2, 1, trace=[0.25, 0.125], lower=[0.5, 0.25])
    made_ready(registry, "fit/final_evaluate", 1, 1, trace=[0.0625], lower=[0.125])
    made_ready(registry, "none", 40, 40, trace=[3.0], lower=[5.0])
    assert compile_path_s.read({}) == pytest.approx(0.25 + 0.125 + 0.5 + 0.25 + 0.0625 + 0.125)
    assert programs_requested.read({}) == 3
    table = programs.by_stage()
    assert table["none"]["requests"] == table["none"]["hits"] == 40 and table["none"]["lower"] == 5.0
    assert table["cd/train"] == {
        "requests": 2, "hits": 1, "trace": 0.375, "lower": 0.75, "cache_read": 0.0, "compile": 0.0,
    }
    printed = [l for l in capsys.readouterr().err.splitlines() if l.startswith("programs ")]
    stages = [l.split()[1] for l in printed if "costliest" not in l]
    assert stages == ["stage", "none", "cd/train", "fit/final_evaluate"]  # once, costliest first


def test_a_program_that_keeps_no_record_reads_none_not_zero(registry):
    """An earlier commit: the two counters unlabelled, no histogram."""
    registry.increment("compile_cache_requests", 86)
    registry.increment("compile_cache_hits", 86)
    assert programs.by_stage() is None
    assert compile_path_s.read({}) is None and programs_requested.read({}) is None


def test_a_rehearsal_reports_both(capsys):
    n0 = len(compile_cache.programs())
    argv = ["--workload", "lr-epsilon.fit", "--seed", "2147483693", "--seconds", "0.5",
            "--trace", "1", "--rows", "4000"]
    assert run.main(argv) == 0
    captured = capsys.readouterr()
    result = json.loads(captured.out.strip().splitlines()[-1])
    metrics = result["metrics"]
    assert metrics["compile_path_s"]["unit"] == "s" and metrics["programs_requested"]["unit"] == "count"
    assert 0 < metrics["compile_path_s"]["value"] < result["phases_s"]["setup"]  # a traced run's `setup_s`
    staged = [r for r in compile_cache.programs()[n0:] if r["stage"] != "none"]
    assert staged and {r["stage"] for r in staged} >= {"cd/train", "cd/validation_evaluate"}
    misses = sum(not r["hit"] for r in staged)
    assert metrics["programs_requested"]["value"] >= max(misses, 1)
    assert "programs costliest " in captured.err
