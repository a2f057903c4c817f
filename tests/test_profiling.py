"""Profiling harness (runtime/profiling.py) — ISSUE-10 contracts:

  - the tier-1 retrace gate: EVERY registered entrypoint's representative
    call, made twice with same-aval inputs, recompiles at most its
    contract-declared retrace_budget (default 0). The PR 1 / PR 3 carry
    bugs were exactly silent per-iteration retraces; this pins the whole
    registry against that class.
  - count_retraces observes a genuinely fresh compile and nothing on a
    warm cache hit.
  - entrypoint_cost returns the {flops, hbm_bytes, peak_memory_bytes}
    block with each field either None (surface absent on this backend) or
    a positive number — never a crash.
  - chrome_trace() emits a strict-JSON-safe structure.
"""

import json

import numpy as np
import pytest

from dst_libp2p_test_node_tpu.analysis.registry import default_contracts
from dst_libp2p_test_node_tpu.runtime.profiling import (
    chrome_trace, count_retraces, entrypoint_cost, measure_retraces,
)

_CONTRACTS = {c.name: c for c in default_contracts()}


@pytest.mark.parametrize("name", sorted(_CONTRACTS), ids=sorted(_CONTRACTS))
def test_retrace_budget(name):
    c = _CONTRACTS[name]
    got = measure_retraces(c)
    assert got <= c.retrace_budget, (
        f"{name}: {got} retraces on a same-aval second call "
        f"(budget {c.retrace_budget}) — aval drift at a call boundary")


def test_count_retraces_sees_a_fresh_compile():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return x * 2 + 1

    x = jnp.arange(7.0)
    with count_retraces() as c1:
        jax.block_until_ready(f(x))
    assert c1.count >= 1
    with count_retraces() as c2:  # warm call: zero cache misses
        jax.block_until_ready(f(x))
    assert c2.count == 0


def test_entrypoint_cost_fields():
    cost = entrypoint_cost(_CONTRACTS["heartbeat_step"])
    assert set(cost) == {"flops", "hbm_bytes", "peak_memory_bytes"}
    for k, v in cost.items():
        assert v is None or (isinstance(v, (int, float)) and v > 0), (k, v)


def test_chrome_trace_structure_and_strict_json():
    curves = {
        "tel_mesh_coverage": np.array([0.5, 0.9, 1.0]),
        "tel_score_q": np.array([[0.0, 1.0], [0.1, 1.1], [0.2, 1.2]]),
    }
    doc = chrome_trace(curves, heartbeat_ms=700.0, t0_ms=1400.0, name="t0")
    ev = doc["traceEvents"]
    meta = [e for e in ev if e["ph"] == "M"]
    slices = [e for e in ev if e["ph"] == "X"]
    counters = [e for e in ev if e["ph"] == "C"]
    assert {m["name"] for m in meta} == {"process_name", "thread_name"}
    assert len(slices) == 3 and len(counters) == 3  # scalar channel only
    assert slices[0]["ts"] == 1400.0 * 1000.0
    assert slices[1]["ts"] - slices[0]["ts"] == 700.0 * 1000.0
    assert slices[0]["dur"] == 700.0 * 1000.0
    assert slices[2]["args"]["hb"] == 2
    assert slices[2]["args"]["tel_score_q"] == [0.2, 1.2]
    json.dumps(doc, allow_nan=False)


def test_lower_spec_keeps_arrays_dynamic():
    # zero-argument lowering would constant-fold the whole state into the
    # program; the split must keep array pytrees as jit parameters
    from dst_libp2p_test_node_tpu.runtime.profiling import lower_spec

    spec = _CONTRACTS["heartbeat_step"].build()
    lowered = lower_spec(spec)
    text = lowered.as_text()
    assert "%arg" in text  # at least one real program parameter survived
