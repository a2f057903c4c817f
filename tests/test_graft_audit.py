"""Tier-1 gate + golden-violation fixtures for graft-audit (analysis/).

Three layers:

  1. The repo itself must audit clean — AST lint over the python surface and
     the jaxpr auditor over every registered contract. This is the gate that
     keeps the hot paths certified as the codebase grows.
  2. Golden AST fixtures (tests/fixtures/graft_audit/): one deliberately-bad
     module and one clean twin per GA-A rule. Fixtures are PARSED, never
     imported, so the bad ones can contain would-crash code.
  3. Golden jaxpr fixtures, traced in-test: miniature entrypoints shaped like
     the real fixpoints that provably trip each GA-J rule — including the
     acceptance fixture, a vmapped-cond while-loop of the disseminate-repair
     shape that the auditor must flag as select_n-elided (GA-J003).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from dst_libp2p_test_node_tpu.analysis import (
    EntrypointContract,
    LadderRung,
    TraceSpec,
    audit_contract,
    audit_contracts,
    lint_paths,
    lint_source,
    render_report,
)
from dst_libp2p_test_node_tpu.analysis.registry import default_contracts

REPO = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).parent / "fixtures" / "graft_audit"
AST_RULES = ("GA-A001", "GA-A002", "GA-A003", "GA-A004", "GA-A005")


def _rules_of(violations):
    return sorted({v.rule for v in violations})


# ---------------------------------------------------------------- layer 1:
# the repo audits clean

def test_repo_ast_surface_is_clean():
    targets = [str(REPO / "dst_libp2p_test_node_tpu"),
               str(REPO / "bench_configs.py"),
               str(REPO / "scripts")]
    violations, checked = lint_paths(targets, str(REPO))
    assert checked > 30, "lint walked suspiciously few files"
    assert violations == [], render_report(violations, checked_files=checked)


def test_registered_entrypoints_audit_clean():
    contracts = default_contracts()
    names = {c.name for c in contracts}
    # the hot paths the issue requires certified must all be registered
    for required in ("disseminate/cold", "disseminate/warm",
                     "disseminate/bounded", "heartbeat_step",
                     "run_heartbeats", "run_attacked_heartbeats",
                     "kad/find_node", "multitopic/disseminate"):
        assert required in names, f"{required} missing from the registry"
    violations = audit_contracts(contracts)
    assert violations == [], render_report(
        violations, checked_entrypoints=len(contracts))


# ---------------------------------------------------------------- layer 2:
# golden AST fixtures

@pytest.mark.parametrize("rule", AST_RULES)
def test_golden_ast_bad_fixture_trips_exactly_its_rule(rule):
    path = FIXTURES / f"ga_{rule[3:].lower()}_bad.py"
    violations = lint_source(path.read_text(), str(path))
    assert _rules_of(violations) == [rule]
    for v in violations:
        assert v.file == str(path)
        assert v.line > 0


@pytest.mark.parametrize("rule", AST_RULES)
def test_golden_ast_clean_twin_passes(rule):
    path = FIXTURES / f"ga_{rule[3:].lower()}_clean.py"
    assert lint_source(path.read_text(), str(path)) == []


def test_lint_cli_nonzero_with_findings_on_bad_fixtures():
    """`python -m dst_libp2p_test_node_tpu lint` must exit nonzero and name
    every golden-violation fixture with file:line in strict JSON."""
    bad = sorted(str(p) for p in FIXTURES.glob("*_bad.py"))
    proc = subprocess.run(
        [sys.executable, "-m", "dst_libp2p_test_node_tpu",
         "lint", "--no-jaxpr", *bad],
        capture_output=True, text=True, cwd=str(REPO),
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 1, proc.stderr
    report = json.loads(proc.stdout)  # must be strict, parseable JSON
    assert report["clean"] is False
    flagged = {(v["file"], v["rule"]) for v in report["violations"]}
    assert len(report["violations"]) == len(bad)
    for p in bad:
        rel = os.path.relpath(p, REPO)
        rule = "GA-" + Path(p).stem.split("_")[1].upper()
        assert (rel, rule) in flagged
        assert all(v["line"] > 0 for v in report["violations"])


def test_lint_cli_clean_on_clean_twins():
    clean = sorted(str(p) for p in FIXTURES.glob("*_clean.py"))
    proc = subprocess.run(
        [sys.executable, "-m", "dst_libp2p_test_node_tpu",
         "lint", "--no-jaxpr", *clean],
        capture_output=True, text=True, cwd=str(REPO),
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(proc.stdout)["clean"] is True


# ---------------------------------------------------------------- layer 3:
# golden jaxpr fixtures (traced in-test; shapes mirror the real entrypoints)

def _contract(name, fn, args, **kw):
    return EntrypointContract(
        name=name, build=lambda: TraceSpec(fn, args), **kw)


def test_j003_vmapped_cond_fixpoint_is_flagged():
    """The acceptance fixture: a while-loop fixpoint whose per-peer repair
    cond got vmapped. The cond vanishes into select_n and both branches run
    every sweep — the auditor must catch the elision."""
    def fixpoint_vmapped(x):
        def body(c):
            i, v = c
            v = jax.vmap(lambda e: lax.cond(
                e > 0, lambda t: t * 2.0, lambda t: t + 1.0, e))(v)
            return i + 1, v
        return lax.while_loop(lambda c: c[0] < 3, body, (jnp.int32(0), x))

    c = _contract("fixture/vmapped-cond", fixpoint_vmapped,
                  (jnp.arange(8.0),), expected_conds=1)
    violations = audit_contract(c)
    assert _rules_of(violations) == ["GA-J003"]
    assert "select_n" in violations[0].message


def test_j003_scalar_cond_twin_survives():
    def fixpoint_scalar(x):
        def body(c):
            i, v = c
            v = lax.cond(i % 2 == 0, lambda t: t * 2.0, lambda t: t + 1.0, v)
            return i + 1, v
        return lax.while_loop(lambda c: c[0] < 3, body, (jnp.int32(0), x))

    c = _contract("fixture/scalar-cond", fixpoint_scalar,
                  (jnp.arange(8.0),), expected_conds=1)
    assert audit_contract(c) == []


def test_j001_debug_callback_in_scan_body():
    def noisy_scan(x):
        def body(c, _):
            jax.debug.print("c={c}", c=c)
            return c + 1.0, c
        return lax.scan(body, x, None, length=4)

    c = _contract("fixture/noisy-scan", noisy_scan, (jnp.float32(0.0),))
    violations = audit_contract(c)
    assert _rules_of(violations) == ["GA-J001"]


def test_j002_weak_python_scalar_carry():
    def weak_carry(x):
        return lax.while_loop(
            lambda c: c[0] < 3, lambda c: (c[0] + 1, c[1] * 0.5), (0, x))

    c = _contract("fixture/weak-carry", weak_carry, (jnp.arange(8.0),))
    violations = audit_contract(c)
    assert _rules_of(violations) == ["GA-J002"]
    assert "weak" in violations[0].message

    def strong_carry(x):
        return lax.while_loop(
            lambda c: c[0] < 3,
            lambda c: (c[0] + 1, c[1] * 0.5), (jnp.int32(0), x))

    assert audit_contract(
        _contract("fixture/strong-carry", strong_carry,
                  (jnp.arange(8.0),))) == []


def test_j004_non_aliasable_donation():
    def strided(x):
        return x[::2] * 2.0  # half-size output cannot alias the donor

    c = _contract("fixture/strided", strided, (jnp.arange(8.0),), donate=(0,))
    violations = audit_contract(c)
    assert _rules_of(violations) == ["GA-J004"]

    def inplace(x):
        return x + 1.0

    assert audit_contract(
        _contract("fixture/inplace", inplace,
                  (jnp.arange(8.0),), donate=(0,))) == []


def test_j005_compile_key_drift_and_feedback_drift():
    def inplace(x):
        return x + 1.0

    # weak-type drift between two rungs that should share one compile key
    drift = _contract(
        "fixture/key-drift", inplace, (jnp.arange(8.0),),
        ladder=lambda: [LadderRung("strong", "p", jnp.float32(1.0)),
                        LadderRung("weak", "p", 1.0)],
        expected_compile_keys=1)
    violations = audit_contract(drift)
    assert _rules_of(violations) == ["GA-J005"]

    # output fed back into the arg slot with a different shape
    def grower(x):
        return jnp.concatenate([x, x])

    fb = _contract(
        "fixture/feedback-drift", grower, (jnp.arange(8.0),),
        feedback=[(lambda out: out, lambda spec: spec.args[0])])
    violations = audit_contract(fb)
    assert _rules_of(violations) == ["GA-J005"]
    assert "feedback" in violations[0].message

    ok = _contract(
        "fixture/feedback-ok", inplace, (jnp.arange(8.0),),
        feedback=[(lambda out: out, lambda spec: spec.args[0])])
    assert audit_contract(ok) == []


def test_report_is_strict_json():
    from dst_libp2p_test_node_tpu.analysis import Violation

    v = Violation(rule="GA-A001", file="x.py", line=3, message="m")
    report = render_report([v], checked_files=1)
    parsed = json.loads(report)
    assert parsed["violations"][0]["slug"] == "np-math-on-tracer"
    # the encoder itself must refuse non-finite payloads
    with pytest.raises(ValueError):
        json.dumps({"x": float("nan")}, allow_nan=False)
