"""Every switch has someone who can turn it.

One case for each `bool`- or `str`-typed field of `SimParams` and
`ExperimentConfig`: some call in `cli.py`, `config/` or a `runtime/` module
sets it by keyword (found by AST walk), or the field is in UNSET below with
the reason it has no such setter and the test that uses it. A switch that
only tests can flip is a second program nobody runs; the table names those
that are left, and can only shrink: an entry whose field has gained a setter,
or is gone, fails too.
"""

import ast
import dataclasses
import functools
from pathlib import Path

import pytest

from dst_libp2p_test_node_tpu.ops.state import SimParams
from dst_libp2p_test_node_tpu.runtime.simulator import ExperimentConfig

PKG = Path(__file__).resolve().parent.parent / "dst_libp2p_test_node_tpu"

# (class, field) -> why nothing outside tests sets it, and who uses it.
# Named debts (ROADMAP design): each becomes the behaviour or goes.
UNSET = {
    ("SimParams", "slow_start"):
        "the TCP slow-start term is the link model of record and no CLI or "
        "config turns it off; tests/test_des_crosscheck.py sets it False to "
        "isolate the rx clamp",
    ("SimParams", "exclude_first_sender"):
        "never set anywhere: the reference never forwards a message back to "
        "the peer that delivered it; tests/test_des_crosscheck.py asserts "
        "the default its event queue replays",
}


def _switches(cls):
    # `from __future__ import annotations`: a field's type is its source text
    return [f.name for f in dataclasses.fields(cls)
            if f.type in ("bool", "str")]


@functools.lru_cache(maxsize=None)
def _keyword_setters() -> dict[str, set[str]]:
    """keyword name -> the setter modules that pass it in some call."""
    files = [PKG / "cli.py", *sorted((PKG / "config").glob("*.py")),
             *sorted((PKG / "runtime").glob("*.py"))]
    found: dict[str, set[str]] = {}
    for f in files:
        for node in ast.walk(ast.parse(f.read_text())):
            if isinstance(node, ast.Call):
                for kw in node.keywords:
                    if kw.arg:
                        found.setdefault(kw.arg, set()).add(
                            str(f.relative_to(PKG)))
    return found


CASES = [(cls.__name__, name) for cls in (SimParams, ExperimentConfig)
         for name in _switches(cls)]


@pytest.mark.parametrize("cls,field", CASES,
                         ids=[f"{c}.{f}" for c, f in CASES])
def test_switch_has_a_setter_or_a_stated_reason(cls, field):
    setters = _keyword_setters()
    if (cls, field) in UNSET:
        assert field not in setters, (
            f"{cls}.{field} is set by {sorted(setters[field])}: take it out "
            "of UNSET")
    else:
        assert field in setters, (
            f"nothing in cli.py, config/ or runtime/ sets {cls}.{field} by "
            "keyword: wire it, delete it, or give UNSET the reason")


def test_the_table_names_only_switches_that_exist():
    assert set(UNSET) <= set(CASES), sorted(set(UNSET) - set(CASES))
    # the counts after PR 32 (46 and 21 before): a new field is a new
    # configuration to cover, so it raises these numbers in the open.
    # PR 43: ExperimentConfig.proc_delay_ms (None: the muxer's; the
    # regression node's path states the 2.0 it runs with)
    assert len(dataclasses.fields(SimParams)) <= 44
    assert len(dataclasses.fields(ExperimentConfig)) <= 21
