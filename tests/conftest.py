"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

Multi-chip TPU hardware is not available in CI; sharding correctness is
validated on a virtual device mesh (SURVEY.md §7 / driver contract)."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Persistent XLA compilation cache: the suite is compile-bound, so repeated
# pytest runs reuse compiled executables from disk. First run pays full
# compile; reruns are fast.
_CACHE_DIR = os.path.join(os.path.dirname(__file__), ".jax_cache")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", _CACHE_DIR)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

# The env vars above can come too late: jax reads them when it is first
# imported, and something may have imported it before this file ran.
# config.update after import always holds — without it the suite could
# compile on an accelerator instead of the 8-device virtual CPU mesh the
# sharding tests need.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# feed the (possibly externally-set) env values through config so both paths
# honor a developer's JAX_COMPILATION_CACHE_DIR / threshold overrides
jax.config.update(
    "jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
jax.config.update(
    "jax_persistent_cache_min_compile_time_secs",
    float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]))
jax.config.update(
    "jax_persistent_cache_min_entry_size_bytes",
    int(os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"]))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# the package's env-var surface: cleared before every test so a developer's
# shell exports (PEERS=..., GOSSIPSUB_D=...) can't leak into assertions
_ENV_SURFACE_PREFIXES = ("GOSSIPSUB_",)
_ENV_SURFACE = (
    "PEERS", "CONNECTTO", "MUXER", "FRAGMENTS", "SHADOWENV", "SERVICE",
    "MAXCONNECTIONS", "SELFTRIGGER", "PEER_ID_OFFSET", "FILEPATH",
    "PUBLISHERS", "NODE_ROLE", "MOUNTSMIX", "USESMIX", "NUMMIX", "MIXD",
    "PORT", "SIMBACKEND", "GRAFT_AUDIT_TRIAL_GROUPS",
)


@pytest.fixture(autouse=True)
def _hermetic_env(monkeypatch):
    for var in list(os.environ):
        if var in _ENV_SURFACE or var.startswith(_ENV_SURFACE_PREFIXES):
            monkeypatch.delenv(var, raising=False)


# Per-test wall-clock ceiling: CI installs pytest-timeout and passes
# --timeout, so a hung scan FAILS tier-1 instead of stalling it until the
# job-level timeout. Containers without the plugin get a SIGALRM fallback
# with the same contract (main-thread only — it can't interrupt a stuck C
# extension on a worker thread, which is exactly pytest-timeout's caveat
# for its signal method too). 0 disables.
_PER_TEST_TIMEOUT_S = int(os.environ.get("PYTEST_PER_TEST_TIMEOUT_S", "300"))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    import signal

    armed = (_PER_TEST_TIMEOUT_S > 0
             and hasattr(signal, "SIGALRM")
             and not item.config.pluginmanager.hasplugin("timeout"))
    if armed:
        def _expired(signum, frame):
            raise TimeoutError(
                f"test exceeded the {_PER_TEST_TIMEOUT_S}s per-test "
                "ceiling (conftest SIGALRM fallback; install "
                "pytest-timeout for stack dumps)")

        prev = signal.signal(signal.SIGALRM, _expired)
        signal.alarm(_PER_TEST_TIMEOUT_S)
    try:
        yield
    finally:
        if armed:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, prev)


@pytest.fixture
def rng():
    return np.random.default_rng(0)
