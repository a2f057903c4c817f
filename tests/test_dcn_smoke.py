"""CI-runnable wrapper for the two-process DCN smoke (scripts/dcn_smoke.py):
initialize_multihost joins two local processes into one jax.distributed
group, the global mesh spans both, and a shard_map psum crosses the process
boundary over gloo — the multi-host story of parallel/sharding.py proven on
the only fabric this environment has."""

import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow  # ~26 s: spawns two gloo processes on an oversubscribed
# host; tier-1 wall budget is tight and CI covers the two-process path with
# a dedicated DCN campaign smoke step
@pytest.mark.skipif(
    "jax_cpu_collectives_implementation" not in getattr(jax.config,
                                                        "values", {}),
    reason="jax build has no CPU gloo collectives")
def test_two_process_dcn_smoke():
    env = dict(os.environ)
    # a test-specific port so parallel runs don't collide (the children
    # pin the CPU backend themselves)
    env["DCN_SMOKE_PORT"] = "51913"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "dcn_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "dcn_smoke: PASS" in r.stdout
