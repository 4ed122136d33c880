"""Tier-1 coverage for the static-analysis subsystem (repro.analysis).

Negative controls prove the passes detect what they claim to detect
(a planted f64 cast, a planted widened carry, a planted static-argument
recompile leak, one lint fixture per rule); positive controls prove HEAD
is clean.  The full 13-entry matrix runs in the CI ``static-analysis``
job via ``python -m repro.analysis`` — tests here use small subsets to
keep tier-1 fast.
"""

import json
import os
import subprocess
import sys

from repro.analysis import jaxpr_audit, lint_rules, recompile_guard
from repro.core.solver_fused import VERIFY_ROUNDS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cli(*args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, cwd=REPO, env=env)


# ---------------------------------------------------------------------------
# jaxpr auditor
# ---------------------------------------------------------------------------

def test_planted_f64_cast_is_caught():
    finds = jaxpr_audit.plant_f64()
    assert finds, "planted float64 cast slipped through the dtype audit"
    assert all(f.check.startswith("dtype") for f in finds)


def test_planted_widened_carry_is_caught():
    finds = jaxpr_audit.plant_widened_carry()
    assert any(f.check == "struct-carry" for f in finds), \
        "telemetry ring widens the while carry; the structural " \
        "comparison must see it"


def test_dtype_audit_clean_on_head_subset():
    # one fused + one classic entry as the tier-1 canary; the CI job
    # audits the whole matrix
    finds = jaxpr_audit.audit_all_dtypes(["plain_jnp", "classic_smo"])
    assert finds == [], "\n".join(f.render() for f in finds)


def test_structural_golden_covers_pinned_entries():
    with open(jaxpr_audit.default_golden_path()) as fh:
        golden = json.load(fh)
    assert set(jaxpr_audit.PINNED) <= set(golden["entries"])


def test_census_artifact_schema(tmp_path):
    paths = jaxpr_audit.emit_census(str(tmp_path), names=["plain_jnp"])
    assert len(paths) == 1
    with open(paths[0]) as fh:
        payload = json.load(fh)
    assert payload["entry"] == "plain_jnp"
    # each round of the engine: its trip loop, and the exit check's row
    # pick and blocked row sum
    assert payload["primitives"].get("while") == 3 * VERIFY_ROUNDS
    assert payload["carries"] and payload["dtypes"]


# ---------------------------------------------------------------------------
# recompile guard
# ---------------------------------------------------------------------------

def test_recompile_guard_exact_on_2x2_sweep():
    findings = []
    recompile_guard.probe_fused_c_gamma(findings)   # 2 C x 2 gamma
    assert findings == [], "\n".join(f.render() for f in findings)


def test_recompile_guard_grid_counts_exact():
    findings = []
    recompile_guard.probe_grid_values(findings)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_recompile_guard_catches_static_leak():
    finds = recompile_guard.plant_excess_recompile()
    assert [f.check for f in finds] == ["recompile-count"]


# ---------------------------------------------------------------------------
# repo-invariant linter
# ---------------------------------------------------------------------------

def test_lint_clean_on_repo():
    finds = lint_rules.run_lint()
    assert finds == [], "\n".join(f.render() for f in finds)


def test_lint_fixtures_trigger_each_rule_once():
    finds = lint_rules.run_fixtures()
    assert sorted(f.check for f in finds) == \
        ["RA001", "RA002", "RA003", "RA004"], \
        "\n".join(f.render() for f in finds)


def test_result_pins_match_source():
    # the pinned field tuples must track the real structs, else RA003
    # would fire on (or worse, miss) every run
    from repro.core.solver import SolveResult
    from repro.core.solver_fused import FusedResult
    import dataclasses

    assert tuple(f.name for f in dataclasses.fields(SolveResult)) == \
        lint_rules.RESULT_PINS["SolveResult"]
    assert tuple(f.name for f in dataclasses.fields(FusedResult)) == \
        lint_rules.RESULT_PINS["FusedResult"]


# ---------------------------------------------------------------------------
# CLI exit codes (lint paths only: no jax startup cost in a subprocess)
# ---------------------------------------------------------------------------

def test_cli_lint_exits_zero_on_head():
    proc = _cli("--lint")
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_plant_lint_exits_nonzero():
    proc = _cli("--plant", "lint")
    assert proc.returncode != 0
    assert "RA00" in proc.stdout
