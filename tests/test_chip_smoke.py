"""chip_smoke.py off the chip, and the compile cache's placement.

The smoke's verdict needs a TPU, so here it must end `"ok": false` and
non-zero — after running every stage and passing every correctness check
(AUC, zero retry/degrade counters, serving parity with the numpy
reference). Only the engagement checks may fail: the kernels and device
routes they look for are TPU-only.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_runs_every_stage_on_cpu_and_says_not_ok(tmp_path):
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "PHOTON_FAULTS")
    }
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    out = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "chip_smoke.py"),
            "--rows", "20000", "--requests", "48",
            "--workdir", str(tmp_path / "work"),
        ],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()]
    assert out.returncode == 1, out.stderr[-3000:]
    last = lines[-1]
    assert set(last) == {"ok", "device"} and last["ok"] is False
    assert last["device"]["platform"] == "cpu"

    stages = [l["stage"] for l in lines if "stage" in l]
    assert stages == ["data", "train", "serve", "verify-serve", "dense-probe"]
    checks = {l["check"]: l for l in lines if "check" in l}
    wrong = [
        name for name, c in checks.items()
        if c["kind"] == "correctness" and not c["passed"]
    ]
    assert not wrong, [checks[n] for n in wrong]
    for name in (
        "auc_above_floor", "fit_robustness_all_zero", "fit_no_retry_or_fallback",
        "native_ingest", "serve_all_answered", "serve_no_degraded_answers",
        "serve_no_recompiles_after_warmup", "serving_equals_numpy_reference",
    ):
        assert checks[name]["passed"], checks[name]
    # Off the chip the engagement checks fail by design — and say so.
    failed = next(l["failed_checks"] for l in lines if "failed_checks" in l)
    assert "platform_is_tpu" in failed
    assert all(checks[name]["kind"] == "engagement" for name in failed)
    # The widths are the model's own and are printed with the cut.
    head = lines[0]
    assert head["rows"] == 20000 and "widths_cut" in head["reduced"]
    data = next(l for l in lines if l.get("stage") == "data")
    assert (data["d"], data["nnz_per_row"]) == (200, 8)
    # The stages shared the cache placed from outside.
    assert head["compile_cache"] == env["JAX_COMPILATION_CACHE_DIR"]
    assert os.listdir(env["JAX_COMPILATION_CACHE_DIR"])


@pytest.mark.parametrize("placed_from_outside", [True, False])
def test_compile_cache_directory(monkeypatch, tmp_path, placed_from_outside):
    """Env set -> no directory is set in code; unset -> one fixed path
    inside the checkout (never a temporary, pid- or time-named one)."""
    import jax

    from photon_ml_tpu.utils import compile_cache

    updates = {}
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setattr(compile_cache, "listen", lambda: None)
    if placed_from_outside:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    path = compile_cache.enable()
    if placed_from_outside:
        assert path == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates
    else:
        assert path == os.path.join(REPO, ".jax_cache")
        assert updates["jax_compilation_cache_dir"] == path
        assert compile_cache.enable() == path  # fixed: the same on every call
