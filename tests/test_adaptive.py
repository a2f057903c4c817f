"""Adaptive adversary controller + defense Pareto sweep (ISSUE 15).

The acceptance contracts pinned here:

  - the DISABLED policy path is literally run_attacked_heartbeats — the
    same jit cache entry (zero retraces after warming the base runner),
    bit-identical leaves, and no controller carry is ever materialized;
  - the ARMED window composes with the nested trials x peers sharding:
    nested == replicated-submesh on 2x4 and 4x2 grids (rtol 1e-5);
  - the armed duty cycle pushes heartbeats_to_graylist to inf and the
    Monte-Carlo run indeed never engages the graylist in-window;
  - pareto_front matches the literal O(P^2) pairwise dominance loop;
  - run_defense_sweep emits a strict-JSON artifact whose front survives
    brute-force host recomputation and whose beats_default set is
    non-empty on the default-vs-tightened-mesh grid;
  - the adaptive attacker is STRICTLY harder to recover from than the
    static cohort, per-seed and in aggregate (slow).
"""

import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dst_libp2p_test_node_tpu.cli import validate_attack_flags
from dst_libp2p_test_node_tpu.config.topology import TopoParams
from dst_libp2p_test_node_tpu.ops.adversary import (
    ADAPTIVE_SCENARIOS,
    AdaptivePolicy,
    AdversaryParams,
    attacker_cohort,
    heartbeats_to_graylist,
    run_adaptive_heartbeats,
    run_attacked_heartbeats,
)
from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph
from dst_libp2p_test_node_tpu.ops.heartbeat import run_heartbeats
from dst_libp2p_test_node_tpu.ops.repair import RepairParams
from dst_libp2p_test_node_tpu.ops.state import (
    SimParams,
    graph_arrays,
    init_adaptive_ctrl,
    init_state,
)
from dst_libp2p_test_node_tpu.parallel.sharding import make_trial_mesh
from dst_libp2p_test_node_tpu.runtime.campaign import (
    GRAYLIST_ENGAGED_FRAC,
    CampaignConfig,
    attack_gossipsub,
    pareto_front,
    run_campaign,
    run_defense_sweep,
    sharded_attack_window,
)
from dst_libp2p_test_node_tpu.runtime.profiling import count_retraces
from dst_libp2p_test_node_tpu.runtime.simulator import ExperimentConfig

_ARMED = dict(slow_weight=-10.0, slow_decay=0.9, gossip_threshold=-10.0,
              publish_threshold=-20.0, graylist_threshold=-50.0)


def _op_fixture(n=64, connect_to=8, seed=0, **over):
    g = build_connection_graph(n, connect_to, seed=seed)
    params = SimParams(n=n, capacity=g.capacity, **{**_ARMED, **over})
    return params, init_state(params, seed=seed), graph_arrays(g)


def _warm(params, state, a, hb=6):
    return run_heartbeats(
        state, a["conns"], a["rev"], a["out_mask"], params, hb)


def _armed_adv(scenario="sybil_graft_flood", **pol):
    return AdversaryParams(
        scenario=scenario, adaptive=AdaptivePolicy(enabled=True, **pol))


def _exp(n=64, seed=0, messages=2, **gs):
    return ExperimentConfig(
        topo=TopoParams(network_size=n, anchor_stages=2, min_bandwidth=50,
                        max_bandwidth=150, min_latency=40, max_latency=130,
                        msg_size_bytes=2000, messages=messages,
                        delay_seconds=1.0),
        connect_to=8, gossipsub=attack_gossipsub(**gs), warmup_s=8.0,
        seed=seed)


# ---------------------------------------------------------------------------
# disabled path: literal delegation, same cache entry, no controller


def test_disabled_policy_is_the_same_jit_cache_entry():
    params, state, a = _op_fixture()
    state = _warm(params, state, a)
    att = jnp.asarray(attacker_cohort(params.n, 0.2, seed=1))
    adv = AdversaryParams(scenario="sybil_graft_flood")
    assert not adv.adaptive.enabled

    plain = run_attacked_heartbeats(
        state, a["conns"], a["rev"], a["out_mask"], att, params, adv, 4)
    jax.block_until_ready(plain[0].key)
    # the adaptive wrapper must hit the cache entry the base runner just
    # compiled: zero retraces, bit-identical output leaves
    with count_retraces() as counter:
        gated = run_adaptive_heartbeats(
            state, a["conns"], a["rev"], a["out_mask"], att, params, adv, 4)
        jax.block_until_ready(gated[0].key)
    assert counter.count == 0, counter.events
    for lp, lg in zip(jax.tree_util.tree_leaves(plain),
                      jax.tree_util.tree_leaves(gated)):
        np.testing.assert_array_equal(np.asarray(lp), np.asarray(lg))


def test_disabled_policy_rejects_a_ctrl_carry():
    params, state, a = _op_fixture()
    adv = AdversaryParams(scenario="sybil_graft_flood")
    with pytest.raises(ValueError, match="disabled"):
        run_adaptive_heartbeats(
            state, a["conns"], a["rev"], a["out_mask"],
            jnp.zeros(params.n, bool), params, adv, 2,
            ctrl=init_adaptive_ctrl(params.n))


# ---------------------------------------------------------------------------
# armed path: duty cycle defeats the closed-form budget


def test_armed_duty_cycle_budget_is_inf_and_never_graylisted():
    params, state, a = _op_fixture()
    state = _warm(params, state, a)
    att = jnp.asarray(attacker_cohort(params.n, 0.2, seed=1))

    static = AdversaryParams(scenario="sybil_graft_flood")
    budget = heartbeats_to_graylist(static, params)
    assert math.isfinite(budget)
    adaptive = _armed_adv()
    assert math.isinf(heartbeats_to_graylist(adaptive, params))

    # Monte-Carlo: run well past the static budget; the throttled cohort
    # must stay under the engagement threshold the whole window
    window = int(2 * budget + 4)
    (_, ctrl), obs = run_adaptive_heartbeats(
        state, a["conns"], a["rev"], a["out_mask"], att, params, adaptive,
        window)
    curve = np.asarray(obs["graylisted_frac"])
    assert curve.shape == (window,)
    assert curve.max() < GRAYLIST_ENGAGED_FRAC
    # the controller actually throttled (the evasion is the duty cycle,
    # not a weak attack)
    assert int(np.asarray(ctrl.throttled_hb).sum()) > 0


def test_armed_controller_counters_engage_and_stay_on_the_cohort():
    # repair leaves LIVE so the PX poisoner has a pool to write
    params, state, a = _op_fixture()
    params = RepairParams(evict=True, px=True, redial=True).apply(params)
    state = init_state(params, seed=0)
    state = _warm(params, state, a)
    att_np = attacker_cohort(params.n, 0.2, seed=1)
    att = jnp.asarray(att_np)

    (out, ctrl), obs = run_adaptive_heartbeats(
        state, a["conns"], a["rev"], a["out_mask"], att, params,
        _armed_adv(), 10)
    regrafts = np.asarray(ctrl.regrafts)
    px = np.asarray(ctrl.px_injected)
    throttled = np.asarray(ctrl.throttled_hb)
    assert regrafts.sum() > 0 and px.sum() > 0 and throttled.sum() > 0
    assert float(np.asarray(ctrl.viol_est).max()) > 0.0
    # attacker-side leaves stay on the cohort; px_injected is indexed by
    # the POISONED pool row (honest victims), so its support is inverted
    for leaf in (regrafts, throttled, np.asarray(ctrl.viol_est)):
        assert (leaf[~att_np] == 0).all()
    assert (px[att_np] == 0).all() and px[~att_np].sum() > 0
    # the adv_* controller channels ride the obs curves, one value a round
    for k in ("adv_violation_rate", "adv_throttled_frac",
              "adv_regraft_attempts", "adv_px_sybil_frac"):
        assert np.asarray(obs[k]).shape == (10,), k


# ---------------------------------------------------------------------------
# armed path composes with the nested trials x peers sharding


def _stacked_fixture(trials=4, fraction=0.2):
    params, _, a = _op_fixture()
    states = [init_state(params, seed=s) for s in range(trials)]
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *states)
    att = jnp.stack([
        jnp.asarray(attacker_cohort(params.n, fraction, seed=s))
        for s in range(trials)])
    shared = {k: a[k] for k in ("conns", "rev", "out_mask")}
    return params, stacked, att, shared


@pytest.mark.parametrize("groups", [2, 4])
def test_armed_nested_window_matches_replicated_submesh(groups):
    params, stacked, att, shared = _stacked_fixture()
    adv = _armed_adv()
    mesh = make_trial_mesh(groups)  # 2x4 / 4x2 under conftest's 8 devices
    local = 4 // groups
    out_n = sharded_attack_window(stacked, shared, att, params, adv, 4,
                                  trial_mesh=mesh, local_trials=local,
                                  nested=True)
    out_r = sharded_attack_window(stacked, shared, att, params, adv, 4,
                                  trial_mesh=mesh, local_trials=local,
                                  nested=False)
    (st_n, ctrl_n), obs_n = out_n
    (st_r, ctrl_r), obs_r = out_r
    jax.tree_util.tree_map(np.testing.assert_array_equal, st_n, st_r)
    jax.tree_util.tree_map(np.testing.assert_array_equal, ctrl_n, ctrl_r)
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_allclose(x, y, rtol=1e-5),
        obs_n, obs_r)
    # the armed window really ran: ctrl is per-trial (T, N) and engaged
    assert np.asarray(ctrl_n.regrafts).shape == (4, params.n)
    assert np.asarray(ctrl_n.regrafts).sum() > 0


# ---------------------------------------------------------------------------
# pareto_front vs the literal pairwise loop


def _brute_force_front(vals, dirs):
    v = np.asarray(vals, dtype=np.float64).copy()
    for k, d in enumerate(dirs):
        if d == "min":
            v[:, k] = -v[:, k]
    keep = np.ones(len(v), dtype=bool)
    for j in range(len(v)):
        for i in range(len(v)):
            if i != j and (v[i] >= v[j]).all() and (v[i] > v[j]).any():
                keep[j] = False
                break
    return keep


def test_pareto_front_matches_bruteforce():
    rng = np.random.default_rng(0)
    vals = rng.uniform(0.0, 1.0, size=(60, 3))
    vals = np.vstack([vals, vals[:5]])  # exact ties must NOT dominate
    dirs = ("max", "min", "min")
    np.testing.assert_array_equal(
        pareto_front(vals, dirs), _brute_force_front(vals, dirs))
    assert pareto_front(vals, dirs).any()
    with pytest.raises(ValueError, match="direction"):
        pareto_front(vals, ("max", "min", "avg"))
    with pytest.raises(ValueError, match="values"):
        pareto_front(vals[:, :2], dirs)


# ---------------------------------------------------------------------------
# defense sweep: validation, artifact shape, front recomputation


def _sweep_cfg(**over):
    kw = dict(
        scenario="eclipse_publisher", fractions=(0.2,), seeds=(0, 1),
        experiment=_exp(flood_publish=False), attack_heartbeats=6,
        recovery_heartbeats=8,
        repair=RepairParams(evict=True, px=True, redial=True),
        adversary=_armed_adv("eclipse_publisher"))
    kw.update(over)
    return CampaignConfig(**kw)


def test_defense_sweep_rejects_degenerate_configs():
    with pytest.raises(ValueError, match="ADAPTIVE"):
        run_defense_sweep(_sweep_cfg(
            adversary=AdversaryParams(scenario="eclipse_publisher")))
    with pytest.raises(ValueError, match="recovery_heartbeats"):
        run_defense_sweep(_sweep_cfg(recovery_heartbeats=0))
    with pytest.raises(ValueError, match="attacked fraction"):
        run_defense_sweep(_sweep_cfg(fractions=(0.0,)))


@pytest.mark.slow
def test_defense_sweep_artifact_and_front():
    sweep = run_defense_sweep(
        _sweep_cfg(), degree_grid=((4, 6, 8), (4, 4, 6)),
        weight_grid=(-10.0,))

    # strict-JSON safe: inf/nan would raise here
    rt = json.loads(json.dumps(sweep, allow_nan=False))
    assert rt["configs"] == sweep["configs"]

    rows = sweep["configs"]
    assert len(rows) == 2  # default (4,6,8,-10) is already in the grid
    assert rows[sweep["default_index"]]["is_default"]
    obj = sweep["objectives"]
    vals = np.array([[r[k] for k in obj] for r in rows])
    front = _brute_force_front(vals, tuple(obj.values()))
    assert sweep["pareto"] == [i for i in range(len(rows)) if front[i]]
    assert sweep["pareto"], "empty Pareto front"

    # the acceptance finding: some non-default grid point dominates the
    # default knobs (the tightened mesh pays less bandwidth for the same
    # coverage/recovery against the adaptive attacker)
    assert sweep["beats_default"]
    sign = np.array([-1.0 if d == "min" else 1.0 for d in obj.values()])
    dv = (vals * sign)[sweep["default_index"]]
    for i in sweep["beats_default"]:
        sv = (vals * sign)[i]
        assert (sv >= dv).all() and (sv > dv).any()


# ---------------------------------------------------------------------------
# the adaptive attacker is strictly harder to recover from (slow)


@pytest.mark.slow
def test_adaptive_recovery_strictly_worse_than_static():
    seeds = (0, 1, 2)
    static_cfg = _sweep_cfg(seeds=seeds, attack_heartbeats=10,
                            recovery_heartbeats=16,
                            adversary=AdversaryParams(
                                scenario="eclipse_publisher"))
    adaptive_cfg = _sweep_cfg(seeds=seeds, attack_heartbeats=10,
                              recovery_heartbeats=16)
    r_s = run_campaign(static_cfg)
    r_a = run_campaign(adaptive_cfg)
    st = {t.seed: t.recovery_time_ms for t in r_s.trials if t.fraction > 0}
    ad = {t.seed: t.recovery_time_ms for t in r_a.trials if t.fraction > 0}
    assert set(st) == set(ad) == set(seeds)
    cap = (adaptive_cfg.recovery_heartbeats + 1) \
        * adaptive_cfg.experiment.gossipsub.heartbeat_ms
    fix = {s: (v if v >= 0 else cap) for s, v in st.items()}, \
          {s: (v if v >= 0 else cap) for s, v in ad.items()}
    st_f, ad_f = fix
    for s in seeds:
        assert ad_f[s] > st_f[s], (
            f"seed {s}: adaptive {ad_f[s]} not worse than static {st_f[s]}")
    assert np.mean(list(ad_f.values())) > np.mean(list(st_f.values()))


# ---------------------------------------------------------------------------
# policy + CLI flag validation


def test_adaptive_policy_validation():
    with pytest.raises(ValueError, match="throttle_margin"):
        AdaptivePolicy(throttle_margin=1.0).validate()
    with pytest.raises(ValueError, match="px_poison_per_hb"):
        AdaptivePolicy(px_poison_per_hb=0).validate()
    with pytest.raises(ValueError, match="no-op"):
        AdaptivePolicy(enabled=True, regraft=False, px_poison=False,
                       slot_race=False, duty_cycle=False).validate()
    with pytest.raises(ValueError, match="composes with"):
        _armed_adv("ihave_spam").validate()
    for scen in ADAPTIVE_SCENARIOS:
        _armed_adv(scen).validate()  # the whole graft-flood family arms


def test_validate_attack_flags():
    # incompatible combos fail UP FRONT with a clear message, before any
    # compilation starts
    bad = [
        (dict(scenario="sybil_graft_flood", mimic_margin=0.5),
         "mimic"),
        (dict(scenario="sybil_graft_flood", rotation_period_hb=4),
         "rotation"),
        (dict(scenario="cold_boot_join", dht_attack=True),
         "cold_boot_join"),
        (dict(scenario="sybil_graft_flood", dht_heal_hb=3),
         "heal"),
        (dict(scenario="ihave_spam", adaptive=True),
         "adaptive"),
        (dict(scenario="sybil_graft_flood", throttle_margin=0.5),
         "adaptive"),
        (dict(scenario="sybil_graft_flood", px_poison_per_hb=2),
         "adaptive"),
    ]
    for kw, frag in bad:
        scen = kw.pop("scenario")
        with pytest.raises(ValueError, match=frag):
            validate_attack_flags(scen, **kw)
    # and the intended combos pass
    validate_attack_flags("slow_peer_mimicry", mimic_margin=0.5)
    validate_attack_flags("identity_rotation", rotation_period_hb=4)
    validate_attack_flags("eclipse_publisher", adaptive=True,
                          throttle_margin=0.5, px_poison_per_hb=2)
    validate_attack_flags("sybil_graft_flood", dht_attack=True,
                          dht_heal_hb=3)


# ---------------------------------------------------------------------------
# report rendering: milestone sentinels and the defense-sweep table


def _fake_trial(**over):
    t = dict(fraction=0.2, seed=0, attackers=12, honest_coverage=0.97,
             latency_p50_ms=120.0, latency_p99_ms=300.0,
             latency_inflation=1.1, hb_to_graylist=4, mesh_recovery_hb=3,
             attacker_score_final=-60.0, mesh_evictions_total=2,
             px_grafts_total=1, redials_total=0, recovery_time_ms=2000.0,
             heal_time_ms=-1.0, post_churn_reconvergence_hb=-1,
             coverage_under_partition=-1.0, coverage90_hb=-1,
             score_cross_hb=-1, rtable_poison_frac=-1.0)
    t.update(over)
    return t


def test_report_campaign_renders_sentinels_as_dash():
    from dst_libp2p_test_node_tpu.runtime.summarize import report_campaign

    camp = dict(
        scenario="eclipse_publisher", network_size=64, hb_budget=None,
        trials=[
            _fake_trial(seed=0),
            _fake_trial(seed=1, hb_to_graylist=-1, mesh_recovery_hb=-1,
                        recovery_time_ms=-1.0),
        ],
        trials_per_s=1.0, wall_s=2.0)
    text = report_campaign(camp)
    lines = text.splitlines()
    row1 = [c.strip() for c in lines[3].split("\t")]
    # seed-1 trial: every unreached milestone is an em dash, never -1
    assert row1[1] == "1"
    assert "—" in row1 and "-1" not in row1
    # the aggregate row averages ONLY the non-sentinel milestones: the
    # seed-0 trial's values come through undiluted
    agg = [c.strip() for c in lines[4].split("\t")]
    assert agg[0] == "mean 0.2" and agg[1] == "n=2"
    assert agg[6] == "4.0" and agg[12] == "2000.0"
    # all-sentinel columns (fault family never armed) aggregate to a dash
    assert agg[13] == "—" and agg[15] == "—"


def test_report_defense_sweep_marks_front_and_default():
    from dst_libp2p_test_node_tpu.runtime.summarize import (
        report_defense_sweep)

    def row(**over):
        r = dict(d_low=4, d=6, d_high=8, slow_peer_penalty_weight=-10.0,
                 is_default=False, coverage=0.99, bandwidth_bytes=9e5,
                 recovery_time_ms=1000.0, recovered_frac=1.0, trials=2,
                 degraded=False)
        r.update(over)
        return r

    sweep = dict(
        scenario="eclipse_publisher", network_size=64,
        objectives={"coverage": "max", "bandwidth_bytes": "min",
                    "recovery_time_ms": "min"},
        configs=[row(is_default=True),
                 row(d=4, d_high=6, bandwidth_bytes=6e5),
                 row(recovery_time_ms=-1.0, recovered_frac=0.0)],
        pareto=[1], default_index=0, beats_default=[1], wall_s=1.5)
    text = report_defense_sweep(sweep)
    lines = text.splitlines()
    assert lines[2].startswith("0*")          # the default row is starred
    assert lines[3].split("\t")[-2].strip() == "yes"   # front membership
    assert lines[3].split("\t")[-1].strip() == "yes"   # beats default
    # an unrecovered config's capped-but-sentineled ms renders as the dash
    row2 = [c.strip() for c in lines[4].split("\t")]
    assert row2[7] == "—"
    assert "front :  [1]" in lines[-1] and "beats default :  [1]" in lines[-1]
