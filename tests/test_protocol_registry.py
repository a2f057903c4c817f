"""Protocol registry tests (ops/protocol.py, ISSUE 19 tentpole layer 1).

The arena refactor's acceptance gate: registry-dispatched GossipSub IS
the pre-registry call. The spec's runner fields must be the module-level
function OBJECTS (`is` identity, not equal wrappers), dispatch through
the registry must hit the same jit cache entries (zero retraces after
the direct call warmed them), and the outputs must be bit-identical
across the benign / attacked / adaptive / faulted windows. The campaign
resolver must reject ctrl-carrying protocols (episub) rather than
silently dropping their carry.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from dst_libp2p_test_node_tpu.ops import adversary as adv_mod
from dst_libp2p_test_node_tpu.ops import faults as faults_mod
from dst_libp2p_test_node_tpu.ops import heartbeat as hb_mod
from dst_libp2p_test_node_tpu.ops.adversary import (
    AdaptivePolicy,
    AdversaryParams,
    attacker_cohort,
)
from dst_libp2p_test_node_tpu.ops.faults import FaultParams, fault_masks
from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph
from dst_libp2p_test_node_tpu.ops.protocol import (
    get_protocol,
    protocol_names,
    register_protocol,
)
from dst_libp2p_test_node_tpu.ops.state import (
    SimParams,
    graph_arrays,
    init_state,
)
from dst_libp2p_test_node_tpu.runtime.campaign import _protocol_window_runner
from dst_libp2p_test_node_tpu.runtime.profiling import count_retraces

N = 32
STEPS = 4


def _setup(**over):
    g = build_connection_graph(N, 6, seed=0)
    params = SimParams(n=N, capacity=g.capacity, **over)
    state = init_state(params, seed=0)
    a = graph_arrays(g)
    att = jnp.asarray(attacker_cohort(N, 0.25, seed=1))
    return params, state, a, att


def _leaves_equal(x, y):
    import jax

    xs = jax.tree_util.tree_leaves(x)
    ys = jax.tree_util.tree_leaves(y)
    assert len(xs) == len(ys)
    for i, (xa, ya) in enumerate(zip(xs, ys)):
        np.testing.assert_array_equal(
            np.asarray(xa), np.asarray(ya), err_msg=f"leaf {i}")


def test_gossipsub_spec_fields_are_the_module_runner_objects():
    spec = get_protocol("gossipsub")
    assert spec.run_heartbeats is hb_mod.run_heartbeats
    assert spec.run_attacked_heartbeats is adv_mod.run_attacked_heartbeats
    assert spec.run_adaptive_heartbeats is adv_mod.run_adaptive_heartbeats
    assert spec.run_faulted_heartbeats is faults_mod.run_faulted_heartbeats
    assert spec.init_ctrl is None and spec.protocol_params is None


def test_episub_spec_is_registered_with_ctrl_and_observables():
    from dst_libp2p_test_node_tpu.ops.episub import (
        EpisubParams, init_episub_ctrl, run_episub_heartbeats)

    spec = get_protocol("episub")
    assert spec.run_heartbeats is run_episub_heartbeats
    assert spec.init_ctrl is init_episub_ctrl
    assert spec.protocol_params is EpisubParams
    assert "tree_reach_frac" in spec.observables
    assert protocol_names() == ["episub", "gossipsub"]


def test_registry_names_and_duplicates():
    with pytest.raises(KeyError, match="unknown protocol"):
        get_protocol("plumtree")
    with pytest.raises(ValueError, match="already registered"):
        register_protocol(dataclasses.replace(get_protocol("gossipsub")))


def test_window_runner_resolves_gossipsub_and_rejects_ctrl_protocols():
    assert _protocol_window_runner("gossipsub", "run_adaptive_heartbeats") \
        is adv_mod.run_adaptive_heartbeats
    assert _protocol_window_runner("gossipsub", "run_faulted_heartbeats") \
        is faults_mod.run_faulted_heartbeats
    with pytest.raises(ValueError, match="ctrl"):
        _protocol_window_runner("episub", "run_adaptive_heartbeats")


@pytest.mark.parametrize("window", ["benign", "attacked", "adaptive",
                                    "faulted"])
def test_registry_dispatch_is_bit_identical_and_retrace_free(window):
    """Direct module call warms the jit cache; the registry dispatch must
    then compile NOTHING (same cache entry) and return the same bits."""
    params, state, a, att = _setup()
    adv = AdversaryParams(scenario="sybil_graft_flood")
    spec = get_protocol("gossipsub")
    if window == "benign":
        args = (state, a["conns"], a["rev"], a["out_mask"], params, STEPS)
        direct, registry = hb_mod.run_heartbeats, spec.run_heartbeats
    elif window == "attacked":
        args = (state, a["conns"], a["rev"], a["out_mask"], att, params,
                adv, STEPS)
        direct = adv_mod.run_attacked_heartbeats
        registry = spec.run_attacked_heartbeats
    elif window == "adaptive":
        args = (state, a["conns"], a["rev"], a["out_mask"], att, params,
                dataclasses.replace(adv, adaptive=AdaptivePolicy(
                    enabled=True)), STEPS)
        direct = adv_mod.run_adaptive_heartbeats
        registry = spec.run_adaptive_heartbeats
    else:
        faults = FaultParams(crash_frac=0.2, crash_window=(1, 3))
        fm = fault_masks(N, faults, seed=2, publisher=4)
        args = (state, a["conns"], a["rev"], a["out_mask"], att, params,
                adv, faults, jnp.asarray(fm["crash"]),
                jnp.asarray(fm["side"]), jnp.asarray(fm["spike"]), STEPS)
        direct = faults_mod.run_faulted_heartbeats
        registry = spec.run_faulted_heartbeats
    assert registry is direct
    out_direct = direct(*args)
    with count_retraces() as counter:
        out_registry = registry(*args)
    assert counter.count == 0, (
        f"registry dispatch retraced {counter.count}x: {counter.events}")
    _leaves_equal(out_direct, out_registry)
