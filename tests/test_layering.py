"""Which way the package's imports point, by AST walk.

Every import statement counts, those inside functions too: a lazy import
"to break a cycle" is still an arrow upward. Each rule names the modules it
holds and the modules they may not import.
"""

import ast
from pathlib import Path

import pytest

PKG = "dst_libp2p_test_node_tpu"
ROOT = Path(__file__).resolve().parent.parent / PKG


def _imports(source: str, package: list[str]) -> set[str]:
    """Absolute dotted names of everything `source`, a module of `package`,
    imports from this repo's package: `from ..ops.state import X` gives
    `<pkg>.ops.state` and `<pkg>.ops.state.X` (X may be a module)."""
    found = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            found.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level else []
            base = base + (node.module.split(".") if node.module else [])
            found.add(".".join(base))
            found.update(".".join(base + [a.name]) for a in node.names)
    return {m for m in found if m == PKG or m.startswith(PKG + ".")}


def _imports_of(path: Path) -> set[str]:
    package = [PKG, *path.relative_to(ROOT).parts[:-1]]
    return _imports(path.read_text(), package)


def _files(*globs: str) -> list[Path]:
    files = sorted(f for g in globs for f in ROOT.glob(g))
    assert files, globs
    return files


def _under(name: str, layer: str) -> bool:
    full = f"{PKG}.{layer}" if layer else PKG
    return name == full or name.startswith(full + ".")


UPPER = ("runtime", "analysis", "cli", "__main__")
STAGES = ("heartbeat", "adversary", "faults", "telemetry", "repair",
          "episub", "protocol")

RULES = {
    "config imports nothing above it": (
        ("config/*.py",),
        ("ops", "parallel", *UPPER)),
    "ops imports nothing from runtime, analysis or cli": (
        ("ops/*.py",), UPPER),
    "parallel imports nothing from runtime, analysis or cli": (
        ("parallel/*.py",), UPPER),
    "native imports nothing from the package": (
        ("native/*.py",), ("",)),
    "ops/disseminate imports no sibling stage": (
        ("ops/disseminate.py",), tuple(f"ops.{s}" for s in STAGES)),
    "ops/pull and parallel/exchange import nothing from ops/disseminate": (
        ("ops/pull.py", "parallel/exchange.py"), ("ops.disseminate",)),
}


@pytest.mark.parametrize("rule", sorted(RULES))
def test_imports_point_one_way(rule):
    globs, forbidden = RULES[rule]
    bad = sorted(
        f"{f.relative_to(ROOT)} imports {m}"
        for f in _files(*globs) for m in _imports_of(f)
        if any(_under(m, layer) for layer in forbidden))
    assert not bad, bad


def test_the_walk_sees_function_level_and_relative_imports():
    # the walker itself: a lazy relative import inside a function resolves
    # to the module it names (what ops/disseminate.py's fused scan did with
    # heartbeat, adversary, faults and telemetry)
    src = "def g():\n    from .heartbeat import run\n    from .. import cli\n"
    got = _imports(src, [PKG, "ops"])
    assert f"{PKG}.ops.heartbeat" in got and f"{PKG}.cli" in got
