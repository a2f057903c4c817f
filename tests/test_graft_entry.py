"""Driver-contract regression tests for __graft_entry__.

Round 1's driver multi-chip proof failed (MULTICHIP_r01.json rc=1) because
`dryrun_multichip` built arrays on the default accelerator backend before the
CPU mesh existed, and that backend was broken. These tests run the dryrun the way the driver does
— a fresh interpreter, no conftest platform pinning, the environment's
default backend (including an adversarial JAX_PLATFORMS pointing at the
accelerator) — and assert both that it passes and that the caller's process
never initializes the accelerator backend.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, extra_env: dict | None = None) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "-c", code],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )


def test_dryrun_multichip_fresh_process_never_touches_accelerator():
    # the driver scenario: fresh interpreter, environment default backend
    # (possibly a broken accelerator plugin) — the dryrun runs in a
    # CPU-pinned subprocess and leaves the caller's backends untouched
    proc = _run(
        "import __graft_entry__\n"
        "__graft_entry__.dryrun_multichip(8)\n"
        "from jax._src import xla_bridge\n"
        "initialized = sorted(xla_bridge._backends)\n"
        "assert initialized == [], f'caller touched backends: {initialized}'\n"
        "print('BACKENDS_OK')\n"
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "dryrun_multichip ok" in proc.stdout
    assert "BACKENDS_OK" in proc.stdout


def test_dryrun_multichip_adversarial_jax_platforms_env():
    # an environment that points JAX_PLATFORMS at some accelerator platform
    # (here a made-up name): the dryrun subprocess's config.update pin must
    # take precedence over it
    proc = _run(
        "import __graft_entry__\n"
        "__graft_entry__.dryrun_multichip(8)\n",
        extra_env={"JAX_PLATFORMS": "nochip"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "dryrun_multichip ok" in proc.stdout


def test_dryrun_multichip_survives_preinitialized_backends():
    # the late-call scenario: the caller already ran jax work (its backends
    # are frozen) — the subprocess re-exec makes the dryrun still pass, and
    # the caller's platform config / device view stays intact afterwards
    proc = _run(
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import jax.numpy as jnp\n"
        "(jnp.ones(4) + 1).block_until_ready()\n"
        "assert len(jax.devices('cpu')) == 1\n"
        "import __graft_entry__\n"
        "__graft_entry__.dryrun_multichip(8)\n"
        "assert len(jax.devices('cpu')) == 1  # caller view untouched\n"
        "print('LATE_OK')\n"
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "LATE_OK" in proc.stdout
