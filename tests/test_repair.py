"""Mesh-repair subsystem tests (ops/repair.py + the opt-in heartbeat
branches + the campaign recovery window).

Pins the PR acceptance properties:
  - with the repair knobs at their defaults the heartbeat is BIT-identical
    to the repair-free engine (and an armed-but-never-firing eviction
    branch is bit-identical too — the lax.cond skip really skips);
  - the closed-form heartbeats_to_graylist budget is INVARIANT under
    eviction (the violation predicate swaps mesh for backoff without
    changing its truth value — ops/adversary.py), checked by bit-comparing
    the simulated graylisted_frac curves eviction on vs off;
  - an eclipsed publisher RECOVERS: attacker cohort >= publisher degree,
    repair on -> honest coverage back to >= 0.9 of the benign baseline and
    mesh_recovery_hb != -1; repair off -> it stays dark;
  - the dial path preserves the reverse-slot involution and the sharded
    recovery window equals the single-device one bit-exactly.
"""

import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dst_libp2p_test_node_tpu.config.topology import TopoParams
from dst_libp2p_test_node_tpu.ops.adversary import (
    AdversaryParams,
    attacker_cohort,
    heartbeats_to_graylist,
    run_attacked_heartbeats,
)
from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph
from dst_libp2p_test_node_tpu.ops.heartbeat import run_heartbeats
from dst_libp2p_test_node_tpu.ops.repair import (
    RepairParams,
    repair_round,
    run_recovery_heartbeats,
)
from dst_libp2p_test_node_tpu.ops.state import (
    PX_POOL_WIDTH,
    REPAIR_LEAVES,
    SimParams,
    arm_repair,
    graph_arrays,
    init_state,
)
from dst_libp2p_test_node_tpu.runtime.campaign import (
    CampaignConfig,
    attack_gossipsub,
    run_campaign,
)
from dst_libp2p_test_node_tpu.runtime.simulator import ExperimentConfig

ARMED = dict(slow_weight=-10.0, slow_decay=0.9, gossip_threshold=-10.0,
             publish_threshold=-20.0, graylist_threshold=-50.0)


def _net(n=32, connect_to=4, **over):
    g = build_connection_graph(n, connect_to, seed=0)
    params = SimParams(n=n, capacity=g.capacity, **over)
    state = init_state(params, seed=1)
    state = state.replace(subscribed=jnp.ones((n,), bool))
    return params, state, graph_arrays(g)


def _leaves_equal(s1, s2, skip=()):
    import flax.serialization as ser

    d1, d2 = ser.to_state_dict(s1), ser.to_state_dict(s2)
    assert d1.keys() == d2.keys()
    for k in d1:
        if k in skip:
            continue
        np.testing.assert_array_equal(
            np.asarray(d1[k]), np.asarray(d2[k]), err_msg=k)


# ------------------------------------------------------------- bit identity


def test_repair_params_defaults_are_inert():
    p = SimParams(n=16, capacity=8, **ARMED)
    assert RepairParams().apply(p) == p
    assert not RepairParams().enabled
    assert RepairParams(evict=True).enabled


def test_armed_but_unfired_eviction_is_bit_identical():
    # benign run: every score stays >= 0, so the eviction cond NEVER fires
    # and the armed step must produce the exact same state as the default
    # one — the lax.cond false branch is the proof the default path pays
    # nothing for the feature (the golden for "bit-identical when off")
    p_base, state, a = _net(**ARMED)
    p_ev = dataclasses.replace(p_base, evict=True, eviction_threshold=-50.0)
    s_base = run_heartbeats(state, a["conns"], a["rev"], a["out_mask"],
                            p_base, 10)
    s_ev = run_heartbeats(arm_repair(state), a["conns"], a["rev"],
                          a["out_mask"], p_ev, 10)
    _leaves_equal(s_base, s_ev, skip=REPAIR_LEAVES)
    # and the leaves the armed scan threaded are as arm_repair made them
    _leaves_equal(arm_repair(s_base), s_ev)


def test_default_run_leaves_repair_state_untouched():
    p, state, a = _net(**ARMED)
    s = run_heartbeats(state, a["conns"], a["rev"], a["out_mask"], p, 10)
    # a default run has no repair state to touch: armed afterwards, it is
    # a fresh run's (pool never written, nothing counted)
    assert all(getattr(s, leaf) is None for leaf in REPAIR_LEAVES)
    s = arm_repair(s)
    assert np.asarray(s.px_pool).shape == (p.n, PX_POOL_WIDTH)
    assert np.asarray(s.px_pool).max() == -1
    for leaf in ("starve_hb", "evictions", "px_grafts", "redials"):
        assert np.asarray(getattr(s, leaf)).sum() == 0, leaf


# ----------------------------------------- budget invariance under eviction


def _attacked(p, state, a, steps=12, fraction=0.25):
    att = jnp.asarray(attacker_cohort(p.n, fraction, seed=1))
    s = run_heartbeats(state, a["conns"], a["rev"], a["out_mask"], p, 8)
    s2, obs = run_attacked_heartbeats(
        s, a["conns"], a["rev"], a["out_mask"], att, p,
        AdversaryParams(), steps)
    return att, s2, jax.tree_util.tree_map(np.asarray, obs)


def test_graylist_curve_bit_equal_eviction_on_and_off():
    p_off, state, a = _net(**ARMED)
    p_on = dataclasses.replace(p_off, evict=True, eviction_threshold=-50.0)
    att, s_off, obs_off = _attacked(p_off, state, a)
    _att, s_on, obs_on = _attacked(p_on, arm_repair(state), a)
    # the accrual cadence is identical (backoff replaces mesh in the
    # violation predicate) -> same penalties, same scores, bit-equal curves
    np.testing.assert_array_equal(
        obs_off["graylisted_frac"], obs_on["graylisted_frac"])
    np.testing.assert_array_equal(
        obs_off["attacker_score_mean"], obs_on["attacker_score_mean"])
    np.testing.assert_array_equal(
        np.asarray(s_off.slow_penalty), np.asarray(s_on.slow_penalty))
    # but eviction actually acted: attackers lost honest mesh presence
    assert np.asarray(s_on.evictions).sum() > 0
    assert (obs_on["attacker_mesh_share"][-1]
            < obs_off["attacker_mesh_share"][-1])


def test_simulated_engagement_matches_budget_both_modes():
    p_off, state, a = _net(**ARMED)
    p_on = dataclasses.replace(p_off, evict=True, eviction_threshold=-50.0)
    budget = heartbeats_to_graylist(AdversaryParams(), p_off)
    assert budget == heartbeats_to_graylist(AdversaryParams(), p_on)
    assert math.isfinite(budget)
    for p, st in ((p_off, state), (p_on, arm_repair(state))):
        _att, _s, obs = _attacked(p, st, a)
        gf = obs["graylisted_frac"]
        hits = np.nonzero(gf >= 1.0)[0]
        assert hits.size, "defense never fully engaged"
        assert hits[0] + 1 <= budget


@pytest.mark.parametrize("w,d,G,p", [
    (-10.0, 0.9, -50.0, 1.0),
    (-5.0, 0.8, -40.0, 2.0),
])
def test_iwant_spam_budget_matches_recurrence(w, d, G, p):
    adv = AdversaryParams(scenario="iwant_spam", violation_penalty=p)
    params = SimParams(n=16, capacity=8, slow_weight=w, slow_decay=d,
                       graylist_threshold=G)
    budget = heartbeats_to_graylist(adv, params)
    c, measured = 0.0, math.inf
    for k in range(1, 500):
        c = c * d + (p if k >= 1 else 0.0)   # lead-in 1: spam hits round 1
        if w * c <= G:
            measured = k
            break
    assert budget == measured


def test_iwant_spam_exhausts_answer_queue_until_graylisted():
    p, state, a = _net(**ARMED)
    adv = AdversaryParams(scenario="iwant_spam")
    att = jnp.asarray(attacker_cohort(p.n, 0.25, seed=1))
    s = run_heartbeats(state, a["conns"], a["rev"], a["out_mask"], p, 8)
    assert float(np.asarray(s.uplink_free_ms).max()) == 0.0  # no publishes
    s2, obs = run_attacked_heartbeats(
        s, a["conns"], a["rev"], a["out_mask"], att, p, adv, 12)
    obs = jax.tree_util.tree_map(np.asarray, obs)
    # honest victims served spam answers: their uplink drain time moved
    att_np = np.asarray(att)
    cn = np.asarray(a["conns"])
    victim = (~att_np) & ((cn >= 0) & att_np[np.clip(cn, 0, None)]).any(-1)
    up = np.asarray(s2.uplink_free_ms)
    assert (up[victim] > 0.0).any()
    assert up[~victim & ~att_np].max() == 0.0     # bystanders untouched
    # and scoring caps it: the spammers are fully graylisted within budget
    budget = heartbeats_to_graylist(adv, p)
    hits = np.nonzero(obs["graylisted_frac"] >= 1.0)[0]
    assert hits.size and hits[0] + 1 <= budget


# ------------------------------------------------------ repair_round algebra


def _involution_ok(cn, rv):
    cn, rv = np.asarray(cn), np.asarray(rv)
    me = np.arange(cn.shape[0])[:, None]
    back = cn[np.clip(cn, 0, None), rv]
    return bool(np.where(cn >= 0, back == me, True).all())


def test_repair_round_dial_preserves_involution_and_zeroes_edge_state():
    p, state, a = _net(**{**ARMED, "evict": True, "px": True,
                          "redial": True, "redial_patience": 1})
    s = run_heartbeats(state, a["conns"], a["rev"], a["out_mask"], p, 8)
    # starve a victim: empty its mesh so the re-dial trigger arms
    victim = 3
    mesh = np.array(s.mesh_mask)
    mesh[victim] = False
    s = s.replace(mesh_mask=jnp.asarray(mesh),
                  starve_hb=s.starve_hb.at[victim].set(5),
                  px_pool=jnp.full_like(s.px_pool, -1))
    s2, cn, rv, om = repair_round(
        s, a["conns"], a["rev"], a["out_mask"], p,
        actor=jnp.ones((p.n,), bool))
    assert _involution_ok(cn, rv)
    assert int(np.asarray(s2.redials).sum()) >= 1
    # every newly filled slot carries pristine per-edge state and is meshed
    new = (np.asarray(cn) >= 0) & (np.asarray(a["conns"]) < 0)
    assert new.any()
    assert np.asarray(s2.mesh_mask)[new].all()
    assert (np.asarray(s2.backoff_until)[new] == 0.0).all()
    assert (np.asarray(s2.slow_penalty)[new] == 0.0).all()
    # a committed dial invalidates the warm-start carry wholesale
    assert np.asarray(s2.warm_offset_ms).min() > 1e38


def test_repair_round_respects_actor_mask():
    p, state, a = _net(**{**ARMED, "evict": True, "px": True,
                          "redial": True, "redial_patience": 1})
    s = run_heartbeats(state, a["conns"], a["rev"], a["out_mask"], p, 8)
    att = jnp.asarray(attacker_cohort(p.n, 0.25, seed=1))
    # starve everyone so any actor would dial
    s = s.replace(mesh_mask=jnp.zeros_like(s.mesh_mask),
                  starve_hb=jnp.full((p.n,), 5, dtype=jnp.int32))
    s2, cn, rv, om = repair_round(
        s, a["conns"], a["rev"], a["out_mask"], p, actor=~att)
    # non-actors (the attackers) committed no dials
    assert int(np.asarray(s2.redials)[np.asarray(att)].sum()) == 0


# ------------------------------------------------- eclipse recovery (E2E)


def _eclipse_cfg(recovery_heartbeats, repair):
    exp = ExperimentConfig(
        topo=TopoParams(network_size=64, anchor_stages=2, min_bandwidth=50,
                        max_bandwidth=150, min_latency=40, max_latency=130,
                        msg_size_bytes=2000, messages=3, delay_seconds=1.0),
        connect_to=4,   # publisher degree ~8 < the 13-peer cohort below
        gossipsub=attack_gossipsub(flood_publish=False),
        warmup_s=10.0, seed=0)
    return CampaignConfig(
        scenario="eclipse_publisher", fractions=(0.2,), seeds=(0,),
        experiment=exp, attack_heartbeats=20,
        recovery_heartbeats=recovery_heartbeats, repair=repair)


def test_eclipsed_publisher_recovers_with_repair_on():
    res = run_campaign(_eclipse_cfg(
        30, RepairParams(evict=True, px=True, redial=True)))
    t = res.trials[0]
    assert t.attackers >= 8          # cohort >= publisher degree: full eclipse
    # the acceptance bar: coverage back to >= 0.9 of the benign baseline
    assert t.benign_coverage > 0.9
    assert t.honest_coverage >= 0.9 * t.benign_coverage
    assert t.mesh_recovery_hb != -1
    assert t.recovery_time_ms > 0.0
    assert t.mesh_evictions_total > 0
    assert t.redials_total >= 1
    # strict-JSON round trip of the repair metrics
    json.dumps(res.to_dict(), allow_nan=False)


def test_eclipsed_publisher_stays_dark_without_repair():
    res = run_campaign(_eclipse_cfg(0, RepairParams()))
    t = res.trials[0]
    assert t.honest_coverage < 0.5 * max(t.benign_coverage, 1e-9)
    assert t.recovery_time_ms == -1.0
    assert t.mesh_evictions_total == 0 and t.redials_total == 0


# --------------------------------------------------------------- sharding


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 (virtual) devices")
def test_sharded_recovery_window_equals_single_device():
    from dst_libp2p_test_node_tpu.parallel.sharding import (
        make_peer_mesh, shard_simulation)

    p, state, a = _net(n=64, connect_to=4,
                       **{**ARMED, "evict": True, "px": True,
                          "redial": True, "redial_patience": 2})
    att = jnp.asarray(attacker_cohort(p.n, 0.25, seed=1))
    s = run_heartbeats(state, a["conns"], a["rev"], a["out_mask"], p, 8)
    s, obs0 = run_attacked_heartbeats(
        s, a["conns"], a["rev"], a["out_mask"], att, p,
        AdversaryParams(), 10)

    (s1, cn1, rv1, om1), obs1 = run_recovery_heartbeats(
        s, a["conns"], a["rev"], a["out_mask"], att, p, 10, publisher=3)

    mesh = make_peer_mesh(8)
    s_sh, arr_sh, _ = shard_simulation(
        s, {"conns": a["conns"], "rev": a["rev"], "out_mask": a["out_mask"],
            "att": att}, {}, mesh)
    (s2, cn2, rv2, om2), obs2 = run_recovery_heartbeats(
        s_sh, arr_sh["conns"], arr_sh["rev"], arr_sh["out_mask"],
        arr_sh["att"], p, 10, publisher=3)

    np.testing.assert_array_equal(np.asarray(cn1), np.asarray(cn2))
    np.testing.assert_array_equal(np.asarray(rv1), np.asarray(rv2))
    _leaves_equal(s1, s2)
    for k in obs1:
        # the scalar observables are cross-shard mean reductions — float
        # summation order differs, the state itself is bit-equal above
        np.testing.assert_allclose(
            np.asarray(obs1[k]), np.asarray(obs2[k]), rtol=1e-5,
            atol=1e-6, err_msg=k)
    assert _involution_ok(cn1, rv1)


# ------------------------------------------------------------- validation


def test_repair_validation():
    with pytest.raises(ValueError, match="eviction_threshold"):
        RepairParams(eviction_threshold=1.0).validate()
    with pytest.raises(ValueError, match="px_count"):
        RepairParams(px_count=0).validate()
    with pytest.raises(ValueError, match="px_count"):
        SimParams(n=16, capacity=8, px_count=PX_POOL_WIDTH + 1).validate()
    with pytest.raises(ValueError, match="redial_patience"):
        RepairParams(redial_patience=0).validate()
    with pytest.raises(ValueError, match="recovery_heartbeats"):
        CampaignConfig(recovery_heartbeats=-1).validate()


# ------------------------------------------------------------- checkpoint


def test_checkpoint_v7_loads_with_fresh_repair_state(tmp_path):
    from dst_libp2p_test_node_tpu.runtime.checkpoint import (
        load_checkpoint, save_checkpoint)
    from dst_libp2p_test_node_tpu.runtime.simulator import Simulator

    exp = ExperimentConfig(
        topo=TopoParams(network_size=32, anchor_stages=1, messages=1),
        connect_to=4, gossipsub=attack_gossipsub(), warmup_s=2.0, seed=0)
    sim = Simulator(exp)
    sim.warmup()
    path = tmp_path / "ck.npz"
    save_checkpoint(sim, str(path))

    # doctor the snapshot into a pre-repair v7 one: a run with repair off
    # holds no repair leaf, as a v7 snapshot held none
    z = dict(np.load(str(path), allow_pickle=False))
    meta = json.loads(bytes(z["meta_json"]).decode())
    meta["version"] = 7
    z["meta_json"] = np.frombuffer(
        json.dumps(meta, allow_nan=False).encode(), dtype=np.uint8)
    assert not any(f"state/{k}" in z for k in REPAIR_LEAVES)
    v7 = tmp_path / "ck_v7.npz"
    with open(v7, "wb") as f:
        np.savez_compressed(f, **z)

    sim2 = load_checkpoint(str(v7))
    assert all(getattr(sim2.state, k) is None for k in REPAIR_LEAVES)
    armed = arm_repair(sim2.state)
    assert np.asarray(armed.px_pool).shape == (32, PX_POOL_WIDTH)
    assert np.asarray(armed.px_pool).max() == -1
    for leaf in ("starve_hb", "evictions", "px_grafts", "redials"):
        assert np.asarray(getattr(armed, leaf)).sum() == 0, leaf
    # the restored run still continues bit-exactly
    np.testing.assert_array_equal(
        np.asarray(sim.state.mesh_mask), np.asarray(sim2.state.mesh_mask))
