"""photon-lint (photon_ml_tpu/analysis/): the tier-1 invariant gate.

Three layers:

1. Fixture corpus: every check FIRES on its known-bad snippet under
   tests/analysis_fixtures/<check>/bad/ and stays SILENT on the
   known-good sibling — so a refactor that quietly lobotomizes a checker
   fails here, not months later when the invariant rots.
2. Pragma engine: reasoned pragmas suppress exactly their line; a
   reasonless or unknown-check pragma is itself a finding.
3. The live tree: zero findings across the package and tests/ — the
   machine-checked statement that every invariant photon-lint encodes
   actually HOLDS right now (and that no disable pragma exists
   without a reason, since pragma hygiene is unsuppressable).
"""

import os
import subprocess
import sys

import pytest

from photon_ml_tpu.analysis import CHECKS, run_checks
from photon_ml_tpu.analysis.__main__ import main as lint_main

FIXTURES = os.path.join(os.path.dirname(__file__), "analysis_fixtures")

# check name -> fixture directory (underscored)
CHECK_DIRS = {
    "knob-registry": "knob_registry",
    "fault-site-sync": "fault_site_sync",
    "jit-purity": "jit_purity",
    "thread-lifecycle": "thread_lifecycle",
    "donation-aliasing": "donation_aliasing",
    "contract-key-drift": "contract_key_drift",
    "metric-name-sync": "metric_name_sync",
    "planner-constant": "planner_constant",
    "tolerance-pin": "tolerance_pin",
}


def _fixture(check: str, kind: str) -> str:
    return os.path.join(FIXTURES, CHECK_DIRS[check], kind)


def test_every_check_has_fixtures():
    assert set(CHECK_DIRS) == set(CHECKS), (
        "every registered check needs a bad/good fixture pair "
        "(tests/analysis_fixtures/<check>/{bad,good}) and an entry here"
    )
    for check, d in CHECK_DIRS.items():
        for kind in ("bad", "good"):
            path = os.path.join(FIXTURES, d, kind)
            assert os.path.isdir(path), f"missing fixture dir {path}"


@pytest.mark.parametrize("check", sorted(CHECK_DIRS))
def test_check_fires_on_bad_fixture(check):
    findings = run_checks(paths=[_fixture(check, "bad")], checks=[check])
    own = [f for f in findings if f.check == check]
    assert own, f"{check} reported nothing on its known-bad fixture"
    for f in own:
        # knob-registry's stale-table-row direction anchors at README.md;
        # everything else anchors at python source.
        assert f.line > 0 and f.path.endswith((".py", "README.md"))


@pytest.mark.parametrize("check", sorted(CHECK_DIRS))
def test_check_silent_on_good_fixture(check):
    findings = run_checks(paths=[_fixture(check, "good")], checks=[check])
    assert not findings, (
        f"{check} false-positived on its known-good fixture:\n"
        + "\n".join(f.render() for f in findings)
    )


def test_bad_fixtures_cover_every_direction():
    """Spot-check the multi-direction checks: the bad fixtures must
    exercise each rule, not just the easiest one."""
    fs = run_checks(
        paths=[_fixture("fault-site-sync", "bad")], checks=["fault-site-sync"]
    )
    msgs = "\n".join(f.message for f in fs)
    assert "not registered" in msgs  # unknown plant
    assert "no fault_point() plants it" in msgs  # unplanted description
    assert "string literal" in msgs  # computed site

    ks = run_checks(
        paths=[_fixture("knob-registry", "bad")], checks=["knob-registry"]
    )
    msgs = "\n".join(f.message for f in ks)
    assert "raw environment read" in msgs
    assert "unregistered knob" in msgs
    # Table sync is row-based in BOTH directions: a prose mention is not
    # a row, and a stale row is flagged too.
    assert "has no row in the README knob table" in msgs
    assert "stale row" in msgs
    # The indirect (module-constant) read resolves too: 4 raw reads.
    assert sum("raw environment read" in f.message for f in ks) == 4

    ts = run_checks(
        paths=[_fixture("thread-lifecycle", "bad")],
        checks=["thread-lifecycle"],
    )
    msgs = "\n".join(f.message for f in ts)
    assert "without name=" in msgs
    # sep.join(parts) in the fixture must not count as the module's join.
    assert "never joined" in msgs

    js = run_checks(paths=[_fixture("jit-purity", "bad")], checks=["jit-purity"])
    msgs = "\n".join(f.message for f in js)
    for needle in ("time.", "np.random", ".item()", "os.getenv", "global",
                   "one call deep"):
        assert needle in msgs, f"jit-purity bad fixture missed {needle!r}"

    ds = run_checks(
        paths=[_fixture("donation-aliasing", "bad")],
        checks=["donation-aliasing"],
    )
    assert len(ds) == 2  # named-callable AND immediately-invoked forms

    ms = run_checks(
        paths=[_fixture("metric-name-sync", "bad")],
        checks=["metric-name-sync"],
    )
    msgs = "\n".join(f.message for f in ms)
    assert "not declared" in msgs  # undeclared increment
    assert "nothing increments it" in msgs  # declared-but-unincremented
    assert "statically resolvable" in msgs  # computed name
    assert "counter= argument" in msgs  # unresolvable retry counter

    ps = run_checks(
        paths=[_fixture("planner-constant", "bad")],
        checks=["planner-constant"],
    )
    msgs = "\n".join(f.message for f in ps)
    # All four binding forms must fire: parameter default, call keyword,
    # plain assignment, and the bucket-shape tuple literal.
    assert "max_wait_ms=2.0" in msgs
    assert "max_wait_ms=1.0" in msgs
    assert "chunk_rows=262144" in msgs
    assert "prefetch_depth=2" in msgs
    assert "bucket_shapes=(64, 128, 256)" in msgs


# ------------------------------------------------------------------ pragmas


def test_reasonless_and_unknown_pragmas_are_findings():
    bad = os.path.join(FIXTURES, "pragma", "bad")
    findings = run_checks(paths=[bad], checks=["thread-lifecycle"])
    pragma = [f for f in findings if f.check == "pragma"]
    assert any("without a reason" in f.message for f in pragma)
    assert any("unknown check" in f.message for f in pragma)
    # A reasonless pragma suppresses nothing: the thread finding survives.
    assert any(f.check == "thread-lifecycle" for f in findings)


def test_reasoned_pragma_suppresses_trailing_and_comment_line():
    good = os.path.join(FIXTURES, "pragma", "good")
    findings = run_checks(paths=[good], checks=["thread-lifecycle"])
    assert not findings, "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------- live tree


def test_live_tree_is_clean():
    """THE gate: zero findings over the package and tests/.
    Also proves no disable pragma anywhere lacks a reason (pragma
    hygiene cannot be suppressed)."""
    findings = run_checks()
    assert not findings, "photon-lint findings on the live tree:\n" + "\n".join(
        f.render() for f in findings
    )


def test_contracts_match_live_producers():
    """The schemas the drift check defends must match what the code
    actually emits — a wrong schema with no duplicates is still wrong."""
    from photon_ml_tpu.utils import contracts

    # Key order is part of the zipped producer schema.
    assert contracts.SERVING_SHARDING_KEYS[0] == "entity_sharded"
    for name, keys in contracts.ALL_CONTRACTS.items():
        assert len(keys) == len(set(keys)), f"{name} has duplicate keys"
        assert keys, f"{name} is empty"


# ---------------------------------------------------------------------- CLI


def test_cli_list_checks_and_exit_codes(capsys):
    assert lint_main(["--list-checks"]) == 0
    out = capsys.readouterr().out
    for name in CHECKS:
        assert name in out

    bad = _fixture("thread-lifecycle", "bad")
    assert lint_main([bad]) == 1  # findings -> nonzero (CI/pre-commit hook)
    assert "thread-lifecycle" in capsys.readouterr().out

    good = _fixture("thread-lifecycle", "good")
    assert lint_main([good]) == 0
    assert lint_main(["--check", "no-such-check"]) == 2


@pytest.mark.slow
def test_cli_subprocess_matches_faults_list_sites_convention():
    """`python -m photon_ml_tpu.analysis --list-checks` works as a real
    subprocess, mirroring `python -m photon_ml_tpu.utils.faults
    --list-sites` (slow: pays a fresh interpreter+import)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "photon_ml_tpu.analysis", "--list-checks"],
        capture_output=True,
        text=True,
        timeout=120,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr
    assert "knob-registry" in out.stdout
