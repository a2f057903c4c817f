"""chip_smoke.py off the chip: its phases at 200 peers on the CPU backend
(the phase functions, not the device gate), the gate itself refusing a
CPU-only backend, and the compile-cache rule every entry point shares."""

import os
import shutil
import subprocess
import sys

import pytest

import jax

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cli_phase_checks_its_own_artifacts(tmp_path):
    rec = chip_smoke.run_cli_phase("tiny", 200, str(tmp_path))
    assert rec["peers"] == 200 and rec["coverage"] == 1.0
    assert rec["latencies_lines"] == 3 * 200
    assert rec["fixpoint_formulation"] == "row_pull"
    assert rec["first_call_s"] > 0 and rec["steady_s"] > 0
    # below format_block's native threshold the Python formatter runs
    assert rec["native_logemit_used"] is False
    # the harness sets JAX_COMPILATION_CACHE_DIR: the rule sets nothing
    assert rec["compile_cache_dir"] == os.environ["JAX_COMPILATION_CACHE_DIR"]
    for call in ("first", "steady"):
        for name in ("latencies1", "stats1.json", "shadowlog1", "stdout.txt"):
            assert (tmp_path / "tiny" / call / name).exists()


def test_cli_phase_fails_on_a_wrong_reference(tmp_path):
    # a phase failure raises: no logging-and-carrying-on
    with pytest.raises(AssertionError, match="more than 1 ms apart"):
        chip_smoke.run_cli_phase(
            "tiny", 200, str(tmp_path),
            cpu_reference={"avg_latency_ms": 1.0, "max_latency_ms": 1.0})


def test_latency_checks_reject_malformed_artifacts():
    line = (b"shadow.data/hosts/peer%d/main.1000.stdout:1:77 "
            b"milliseconds: %d\n")
    good = b"".join(line % (p, 0 if p == 4 else 50 + p)
                    for _ in range(3) for p in range(8))
    chip_smoke._check_latencies(good, 24, 8)
    with pytest.raises(AssertionError, match="lines"):
        chip_smoke._check_latencies(good, 23, 8)
    with pytest.raises(AssertionError, match="outside the"):
        chip_smoke._check_latencies(
            good.replace(b"milliseconds: 55\n", b"milliseconds: inf\n"), 24, 8)
    with pytest.raises(AssertionError, match="smallest link latency"):
        chip_smoke._check_latencies(
            good.replace(b"milliseconds: 55\n", b"milliseconds: 12\n"), 24, 8)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 (virtual) devices")
def test_sharded_phase_matches_single_device_and_spreads_rows():
    rec = chip_smoke.run_sharded_phase(200, 4)
    assert rec["rows_per_device"] == 50 and rec["row_leaves_checked"] > 10
    assert rec["converged"] is True and rec["coverage"] == 1.0
    assert rec["fixpoint_formulation_sharded"].startswith("recv_sharded")
    assert rec["fixpoint_formulation_single"] == "row_pull"


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in_checkout", "alone_in_a_directory"])
def test_device_gate_exits_nonzero_without_a_tpu(tmp_path, alone):
    script = os.path.join(REPO, "chip_smoke.py")
    cwd = REPO
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
        cwd = str(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert proc.stdout == ""        # no phase ran, no result line


def test_compile_cache_rule(monkeypatch):
    from dst_libp2p_test_node_tpu.runtime import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    # set in the environment: JAX reads it itself, nothing is set in code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert calls == []
    # unset: the fixed directory in the checkout, conftest's thresholds
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert dict(calls) == {
        "jax_compilation_cache_dir": path,
        "jax_persistent_cache_min_compile_time_secs": 1.0,
        "jax_persistent_cache_min_entry_size_bytes": 0,
    }
