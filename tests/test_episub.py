"""Episub tree backend tests (ops/episub.py, ISSUE 19 tentpole layer 2).

The contracts pinned here:

  - the eager-push spanning tree actually forms: after a warm window the
    root reaches (almost) every subscribed peer and the parent pointers
    are a well-founded tree (hops strictly decrease toward the root).
  - determinism: the attacked window is a pure function of its inputs —
    two identical calls return the same bits.
  - delegation: the disabled adaptive wrapper IS the attacked runner
    (same bits), per the house delegation discipline.
  - sharded == vmapped: the nested trials x peers grid reproduces the
    per-trial results on BOTH grid orientations (2x4 and 4x2 under
    conftest's 8 virtual devices) — placement never moves numerics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dst_libp2p_test_node_tpu.ops.adversary import (
    AdaptivePolicy,
    AdversaryParams,
    attacker_cohort,
)
from dst_libp2p_test_node_tpu.ops.episub import (
    EpisubParams,
    init_episub_ctrl,
    run_episub_adaptive_heartbeats,
    run_episub_attacked_heartbeats,
    run_episub_heartbeats,
)
from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph
from dst_libp2p_test_node_tpu.ops.state import (
    SimParams,
    graph_arrays,
    init_state,
)
from dst_libp2p_test_node_tpu.parallel.sharding import (
    make_trial_mesh,
    place_trial_batch,
)
from dst_libp2p_test_node_tpu.runtime.campaign import sharded_episub_window

N = 32
ROOT = 4
WARM = 12
ARMED = dict(slow_weight=-10.0, slow_decay=0.9, gossip_threshold=-10.0,
             publish_threshold=-20.0, graylist_threshold=-50.0)


def _setup(**over):
    g = build_connection_graph(N, 6, seed=0)
    params = SimParams(n=N, capacity=g.capacity, **{**ARMED, **over})
    state = init_state(params, seed=0)
    a = graph_arrays(g)
    return params, state, a


def _leaves_equal(x, y, msg=""):
    xs, ys = jax.tree_util.tree_leaves(x), jax.tree_util.tree_leaves(y)
    assert len(xs) == len(ys)
    for i, (xa, ya) in enumerate(zip(xs, ys)):
        np.testing.assert_array_equal(
            np.asarray(xa), np.asarray(ya), err_msg=f"{msg} leaf {i}")


def test_tree_forms_and_hops_are_well_founded():
    params, state, a = _setup()
    ep = EpisubParams(root=ROOT)
    ctrl = init_episub_ctrl(N)
    state, ctrl = run_episub_heartbeats(
        state, ctrl, a["conns"], a["rev"], a["out_mask"], params, ep, WARM)
    hops = np.asarray(ctrl.hops)
    parent_slot = np.asarray(ctrl.parent)  # connection SLOT, not peer id
    conns = np.asarray(a["conns"])
    reached = np.isfinite(hops) & (hops < 1e30)
    assert hops[ROOT] == 0.0 and parent_slot[ROOT] < 0
    assert reached.mean() >= 0.9, (
        f"tree reached only {reached.mean():.2f} of peers after {WARM} "
        "rounds")
    # well-founded at the fixpoint: WARM rounds >> graph diameter, so the
    # async Bellman-Ford relaxation has converged and every non-root
    # reached peer sits exactly one hop below its parent peer (no cycles,
    # no stale estimates)
    for i in np.nonzero(reached)[0]:
        if i == ROOT:
            continue
        slot = parent_slot[i]
        assert 0 <= slot < conns.shape[1], f"peer {i} has no parent slot"
        p = conns[i, slot]
        assert 0 <= p < N and reached[p], f"peer {i} parent {p} unreachable"
        assert hops[p] == hops[i] - 1, (
            f"hops not converged at {i} (h={hops[i]}) -> {p} (h={hops[p]})")


def test_attacked_window_is_deterministic():
    params, state, a = _setup()
    ep = EpisubParams(root=ROOT)
    ctrl = init_episub_ctrl(N)
    att = jnp.asarray(attacker_cohort(N, 0.25, seed=1))
    adv = AdversaryParams(scenario="sybil_graft_flood")
    args = (state, ctrl, a["conns"], a["rev"], a["out_mask"], att, params,
            ep, adv, 6)
    (s1, c1), o1 = run_episub_attacked_heartbeats(*args)
    (s2, c2), o2 = run_episub_attacked_heartbeats(*args)
    _leaves_equal(s1, s2, "state")
    _leaves_equal(c1, c2, "ctrl")
    _leaves_equal(o1, o2, "obs")
    assert "tree_reach_frac" in o1 and "tree_depth_mean" in o1


def test_disabled_adaptive_delegates_to_attacked_bit_identically():
    params, state, a = _setup()
    ep = EpisubParams(root=ROOT)
    ctrl = init_episub_ctrl(N)
    att = jnp.asarray(attacker_cohort(N, 0.25, seed=1))
    adv = AdversaryParams(scenario="sybil_graft_flood")
    base = run_episub_attacked_heartbeats(
        state, ctrl, a["conns"], a["rev"], a["out_mask"], att, params, ep,
        adv, 6)
    deleg = run_episub_adaptive_heartbeats(
        state, ctrl, a["conns"], a["rev"], a["out_mask"], att, params, ep,
        adv, 6)
    _leaves_equal(base, deleg, "delegation")


@pytest.mark.parametrize("groups", [2, 4])
def test_sharded_window_equals_per_trial_runs(groups):
    """sharded_episub_window on the trials x peers grid vs the same four
    trials run one-by-one through the public runner: the shard boundary
    moves placement, never numerics."""
    params, state, a = _setup()
    ep = EpisubParams(root=ROOT)
    adv = AdversaryParams(scenario="sybil_graft_flood",
                          adaptive=AdaptivePolicy(enabled=True))
    trials = 4
    local = trials // groups
    steps = 5
    states = [init_state(params, seed=s) for s in range(trials)]
    ctrls = [init_episub_ctrl(N) for _ in range(trials)]
    atts = [jnp.asarray(attacker_cohort(N, 0.25, seed=s))
            for s in range(trials)]

    ref = [run_episub_adaptive_heartbeats(
        st, ct, a["conns"], a["rev"], a["out_mask"], at, params, ep, adv,
        steps) for st, ct, at in zip(states, ctrls, atts)]

    mesh = make_trial_mesh(groups)
    tree = jax.tree_util.tree_map
    stacked = tree(lambda *xs: jnp.stack(xs), *states)
    ctk = tree(lambda *xs: jnp.stack(xs), *ctrls)
    att = jnp.stack(atts)
    (stacked, ctk, att), shared = place_trial_batch(
        (stacked, ctk, att), a, mesh, n_rows=N)
    (o_states, o_ctrls, _actrl), obs = sharded_episub_window(
        stacked, ctk, shared, att, params, ep, adv, steps, mesh, local)

    for j in range(trials):
        (rs, rc, _ra), ro = ref[j]
        sj = tree(lambda x, j=j: np.asarray(x[j]), o_states)
        cj = tree(lambda x, j=j: np.asarray(x[j]), o_ctrls)
        for (la, lb) in zip(jax.tree_util.tree_leaves(rs),
                            jax.tree_util.tree_leaves(sj)):
            np.testing.assert_allclose(
                np.asarray(la), np.asarray(lb), rtol=1e-5, atol=1e-6,
                err_msg=f"state trial {j}")
        for (la, lb) in zip(jax.tree_util.tree_leaves(rc),
                            jax.tree_util.tree_leaves(cj)):
            np.testing.assert_allclose(
                np.asarray(la), np.asarray(lb), rtol=1e-5, atol=1e-6,
                err_msg=f"ctrl trial {j}")
        for k, v in ro.items():
            np.testing.assert_allclose(
                np.asarray(v), np.asarray(obs[k])[j], rtol=1e-5,
                atol=1e-6, err_msg=f"obs {k} trial {j}")
