"""Registry drift gate: every jitted entrypoint module is audited.

The static engines (GA-J/GA-S) only see what analysis/registry.py
registers. A new `@partial(jax.jit, ...)` module added to ops/ or
runtime/ without a contract silently escapes ALL of them — this test
turns that drift into a tier-1 failure: each module carrying the repo's
jit idiom must either be reachable from a registered contract's traced
fn or sit on the explicit allowlist below with a rationale.

The allowlist is exact-match and self-cleaning: an entry whose module is
no longer jitted (or gains a contract) fails the test until removed, so
waivers cannot rot.
"""

from __future__ import annotations

import functools
import re
from pathlib import Path

from dst_libp2p_test_node_tpu.analysis.registry import default_contracts

PKG = Path(__file__).resolve().parents[1] / "dst_libp2p_test_node_tpu"

# the repo's jit idioms (grep ops/: `@partial(jax.jit, static_argnames=...)`
# dominates); shard_map counts — it compiles a partitioned program too
_JIT_RE = re.compile(r"partial\(jax\.jit|@jax\.jit|jax\.jit\(|shard_map\(")

# modules that compile programs but are deliberately NOT registered as
# standalone entrypoint contracts — each with the reason the auditors
# still see (or need not see) them
ALLOWLIST = {
    "ops/connmanager": (
        "connmanager stress scan is a standalone workload CLI (`connmgr`), "
        "not on the campaign hot path; tests/test_connmanager.py pins its "
        "semantics directly"),
    "ops/mix": (
        "mix relay transform only runs composed inside the disseminate "
        "entrypoints (disseminate/* contracts trace it transitively when "
        "MOUNTSMIX configs build it in)"),
    "ops/servicedisco": (
        "service-discovery advertise/lookup is a standalone workload CLI "
        "(`servicedisco`), not on the campaign hot path; "
        "tests/test_servicedisco.py pins it"),
    "ops/dht_adversary": (
        "DHT adversary masks are compiled only inside the campaign window "
        "— campaign/dht_attack_window traces them transitively"),
    "runtime/profiling": (
        "lower_spec's jit wrapper is the audit machinery itself — it "
        "compiles OTHER contracts, it is not an entrypoint"),
}


def _jitted_modules() -> set[str]:
    found = set()
    for sub in ("ops", "runtime"):
        for f in sorted((PKG / sub).glob("*.py")):
            if f.name == "__init__.py":
                continue
            if _JIT_RE.search(f.read_text()):
                found.add(f"{sub}/{f.stem}")
    return found


def _covered_modules() -> set[str]:
    """Modules a registered contract's traced fn lives in (partial-
    unwrapped), mapped to the same sub/name keys as _jitted_modules."""
    prefix = "dst_libp2p_test_node_tpu."
    covered = set()
    for c in default_contracts():
        fn = c.build().fn
        while isinstance(fn, functools.partial):
            fn = fn.func
        mod = getattr(fn, "__module__", "") or ""
        if mod.startswith(prefix):
            covered.add(mod[len(prefix):].replace(".", "/"))
    return covered


def test_every_jitted_module_has_a_contract_or_waiver():
    jitted = _jitted_modules()
    covered = _covered_modules()
    uncovered = sorted(jitted - covered - set(ALLOWLIST))
    assert not uncovered, (
        f"jitted modules with no EntrypointContract and no allowlist "
        f"entry: {uncovered} — register them in analysis/registry.py so "
        f"the GA-J/GA-S engines audit them, or allowlist with a reason")


def test_allowlist_entries_are_live_and_necessary():
    jitted = _jitted_modules()
    covered = _covered_modules()
    stale = sorted(m for m in ALLOWLIST if m not in jitted)
    assert not stale, f"allowlisted modules no longer jitted: {stale}"
    redundant = sorted(m for m in ALLOWLIST if m in covered)
    assert not redundant, (
        f"allowlisted modules now covered by a contract — drop the "
        f"waiver: {redundant}")
    assert all(ALLOWLIST.values()), "every allowlist entry needs a reason"


def test_arena_subsystem_is_registered_not_allowlisted():
    """ISSUE 19: ops/episub.py carries the jit idiom and must be covered
    by a real contract (episub/heartbeat_step), never waived; the arena
    window rides runtime/campaign via protocol/arena_window."""
    names = {c.name for c in default_contracts()}
    assert "episub/heartbeat_step" in names
    assert "protocol/arena_window" in names
    assert "ops/episub" in _jitted_modules()
    assert "ops/episub" in _covered_modules()
    assert "ops/episub" not in ALLOWLIST


def test_protocol_registry_is_jit_free():
    """ops/protocol.py is pure dispatch — the ProtocolSpec fields ARE the
    already-audited runner objects, so the registry itself must never
    grow a compiled surface (that would dodge the drift gate: protocol/
    is outside the ops//runtime/ scan roots)."""
    src = (PKG / "ops" / "protocol.py").read_text()
    assert not _JIT_RE.search(src), (
        "ops/protocol.py gained a jit idiom — register a contract for it "
        "and extend _jitted_modules' scan if dispatch now compiles")


def test_jit_idiom_regex_matches_repo_convention():
    # the dominant idiom is @partial(jax.jit, static_argnames=...); if the
    # repo ever migrates off it, the scan regex must follow
    heartbeat = (PKG / "ops" / "heartbeat.py").read_text()
    assert _JIT_RE.search(heartbeat)
    assert "partial(jax.jit" in heartbeat
