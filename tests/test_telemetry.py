"""Flight-recorder subsystem (ops/telemetry.py) — ISSUE-10 contracts:

  - recorder OFF is a pure delegation: `run_recorded_heartbeats` with no
    telemetry produces bit-identical buffers to `run_heartbeats` AND hits
    the same jit cache entry (zero retraces after the untraced runner is
    warm) — the disabled path must not even exist as a separate program.
  - recorder ON never perturbs the trajectory: the final state is
    bit-identical to the untraced runner; only the scan OUTPUT grows the
    tel_* channels. Same for the attack window's obs dict.
  - the channels are well-formed: coverage/fractions in [0, 1], the degree
    histogram is a normalized distribution over live peers, quantiles are
    sorted, cumulative counters are non-decreasing.
  - sharded == vmapped: the recorded channels off the nested trials x peers
    grid (2x4 and 4x2 under conftest's 8 virtual devices) match the plain
    vmapped stack to rtol 1e-5 (reductions reassociate across peer shards;
    nothing else moves).
  - campaign integration: an armed CampaignConfig.telemetry populates the
    coverage90_hb / score_cross_hb milestone columns identically under
    vmapped and nested-sharded execution; the default leaves them -1.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dst_libp2p_test_node_tpu.config.topology import TopoParams
from dst_libp2p_test_node_tpu.ops.adversary import (
    AdversaryParams, attacker_cohort, run_attacked_heartbeats,
)
from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph
from dst_libp2p_test_node_tpu.ops.heartbeat import run_heartbeats
from dst_libp2p_test_node_tpu.ops.state import (
    SimParams, graph_arrays, init_state,
)
from dst_libp2p_test_node_tpu.ops.telemetry import (
    TelemetryParams, run_recorded_heartbeats,
)
from dst_libp2p_test_node_tpu.parallel.sharding import make_trial_mesh
from dst_libp2p_test_node_tpu.runtime.campaign import (
    CampaignConfig, attack_gossipsub, run_campaign, sharded_attack_window,
)
from dst_libp2p_test_node_tpu.runtime.simulator import ExperimentConfig

# every column of the flight-recorder window, with trailing channel shape
CHANNELS = {
    "tel_mesh_coverage": (), "tel_mean_degree": (), "tel_degree_hist": (12,),
    "tel_score_q": (3,), "tel_graylisted_frac": (), "tel_bytes_tx": (),
    "tel_bytes_rx": (), "tel_ihave": (), "tel_iwant": (),
    "tel_queue_depth_ms": (),
}


def _fixture(n=64, connect_to=8, seed=0, **over):
    g = build_connection_graph(n, connect_to, seed=seed)
    params = SimParams(n=n, capacity=g.capacity, slow_weight=-10.0,
                       slow_decay=0.9, graylist_threshold=-50.0, **over)
    return params, init_state(params, seed=seed), graph_arrays(g)


def _exp(n=64, seed=0, messages=2):
    return ExperimentConfig(
        topo=TopoParams(network_size=n, anchor_stages=2, min_bandwidth=50,
                        max_bandwidth=150, min_latency=40, max_latency=130,
                        msg_size_bytes=2000, messages=messages,
                        delay_seconds=1.0),
        connect_to=8, gossipsub=attack_gossipsub(), warmup_s=8.0, seed=seed)


# --------------------------------------------------------- the off contract


def test_disabled_recorder_delegates_bit_identically():
    params, state, a = _fixture()
    plain = run_heartbeats(state, a["conns"], a["rev"], a["out_mask"],
                           params, 6)
    for tel in (None, TelemetryParams()):
        out, trace = run_recorded_heartbeats(
            state, a["conns"], a["rev"], a["out_mask"], params, 6,
            telemetry=tel)
        assert trace == {}
        for lp, lo in zip(jax.tree_util.tree_leaves(plain),
                          jax.tree_util.tree_leaves(out)):
            np.testing.assert_array_equal(np.asarray(lp), np.asarray(lo))


def test_disabled_recorder_shares_the_jit_cache_entry():
    # the strongest form of "recorder off costs nothing": after the
    # untraced runner is warm, the disabled recorded runner must not
    # trigger a single trace+compile — it IS the same cache entry
    from dst_libp2p_test_node_tpu.runtime.profiling import count_retraces

    params, state, a = _fixture()
    jax.block_until_ready(
        run_heartbeats(state, a["conns"], a["rev"], a["out_mask"],
                       params, 5).bytes_tx)
    with count_retraces() as counter:
        out, _ = run_recorded_heartbeats(
            state, a["conns"], a["rev"], a["out_mask"], params, 5,
            telemetry=TelemetryParams(record=False))
        jax.block_until_ready(out.bytes_tx)
    assert counter.count == 0, counter.events


# ---------------------------------------------------------- the on contract


def test_armed_recorder_keeps_the_trajectory_bit_identical():
    params, state, a = _fixture()
    plain = run_heartbeats(state, a["conns"], a["rev"], a["out_mask"],
                           params, 6)
    out, trace = run_recorded_heartbeats(
        state, a["conns"], a["rev"], a["out_mask"], params, 6,
        telemetry=TelemetryParams(record=True))
    for lp, lo in zip(jax.tree_util.tree_leaves(plain),
                      jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(lp), np.asarray(lo))
    assert set(trace) == set(CHANNELS)
    for k, tail in CHANNELS.items():
        assert np.asarray(trace[k]).shape == (6,) + tail, k


def test_armed_recorder_under_churn_path():
    # churn disables the hoisted-validity/carried-degree protocols; the
    # recorder's un-hoisted scan body must stay bit-identical there too
    params, state, a = _fixture(churn_down_per_hb=0.02, churn_up_per_hb=0.02)
    plain = run_heartbeats(state, a["conns"], a["rev"], a["out_mask"],
                           params, 5)
    out, trace = run_recorded_heartbeats(
        state, a["conns"], a["rev"], a["out_mask"], params, 5,
        telemetry=TelemetryParams(record=True))
    for lp, lo in zip(jax.tree_util.tree_leaves(plain),
                      jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(lp), np.asarray(lo))
    assert np.asarray(trace["tel_mesh_coverage"]).shape == (5,)


def test_channel_sanity():
    params, state, a = _fixture()
    _, trace = run_recorded_heartbeats(
        state, a["conns"], a["rev"], a["out_mask"], params, 8,
        telemetry=TelemetryParams(record=True))
    t = {k: np.asarray(v) for k, v in trace.items()}
    assert ((t["tel_mesh_coverage"] >= 0) & (t["tel_mesh_coverage"] <= 1)).all()
    assert ((t["tel_graylisted_frac"] >= 0)
            & (t["tel_graylisted_frac"] <= 1)).all()
    # every peer starts alive & subscribed, so the normalized degree
    # histogram is a distribution: rows sum to 1
    np.testing.assert_allclose(t["tel_degree_hist"].sum(axis=1), 1.0,
                               rtol=1e-5)
    assert (t["tel_mean_degree"] >= 0).all()
    # quantiles sorted along the quantile axis (0.1 <= 0.5 <= 0.9)
    q = t["tel_score_q"]
    assert (np.diff(q, axis=1) >= -1e-6).all()
    # cumulative counters never decrease across rounds
    for k in ("tel_bytes_tx", "tel_bytes_rx", "tel_ihave", "tel_iwant"):
        assert (np.diff(t[k]) >= 0).all(), k
    assert (t["tel_queue_depth_ms"] >= 0).all()


def test_telemetry_params_validate():
    with pytest.raises(ValueError):
        TelemetryParams(record=True, degree_bins=1).validate()
    with pytest.raises(ValueError):
        TelemetryParams(record=True, quantiles=()).validate()
    with pytest.raises(ValueError):
        TelemetryParams(record=True, quantiles=(0.5, 1.5)).validate()


def test_attack_window_telemetry_only_grows_the_obs_dict():
    params, state, a = _fixture(gossip_threshold=-10.0,
                                publish_threshold=-20.0)
    att = jnp.asarray(attacker_cohort(params.n, 0.25, seed=1))
    adv = AdversaryParams(scenario="sybil_graft_flood")
    plain, obs_p = run_attacked_heartbeats(
        state, a["conns"], a["rev"], a["out_mask"], att, params, adv, 6)
    rec, obs_r = run_attacked_heartbeats(
        state, a["conns"], a["rev"], a["out_mask"], att, params, adv, 6,
        telemetry=TelemetryParams(record=True))
    for lp, lr in zip(jax.tree_util.tree_leaves(plain),
                      jax.tree_util.tree_leaves(rec)):
        np.testing.assert_array_equal(np.asarray(lp), np.asarray(lr))
    assert set(obs_r) == set(obs_p) | set(CHANNELS)
    for k in obs_p:  # the pre-telemetry observables are untouched
        np.testing.assert_array_equal(np.asarray(obs_p[k]),
                                      np.asarray(obs_r[k]))


# ------------------------------------------------------------- sharded == vmapped


def _stacked_fixture(trials=4, fraction=0.2):
    params, _, a = _fixture(gossip_threshold=-10.0, publish_threshold=-20.0)
    states = [init_state(params, seed=s) for s in range(trials)]
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *states)
    att = jnp.stack([
        jnp.asarray(attacker_cohort(params.n, fraction, seed=s))
        for s in range(trials)])
    shared = {k: a[k] for k in ("conns", "rev", "out_mask")}
    return params, stacked, att, shared


@pytest.mark.parametrize("groups", [2, 4])
def test_sharded_telemetry_matches_vmapped(groups):
    # 2x4 and 4x2 grids under conftest's 8 virtual devices: the recorded
    # channels off the nested program must match the plain vmapped stack —
    # state bit-identical, channel reductions rtol 1e-5
    params, stacked, att, shared = _stacked_fixture()
    adv = AdversaryParams(scenario="sybil_graft_flood")
    tp = TelemetryParams(record=True)

    def one(s, at):
        return run_attacked_heartbeats(
            s, shared["conns"], shared["rev"], shared["out_mask"], at,
            params, adv, 4, batch_factor=4, telemetry=tp)

    st_v, obs_v = jax.vmap(one)(stacked, att)
    mesh = make_trial_mesh(groups)
    st_s, obs_s = sharded_attack_window(
        stacked, shared, att, params, adv, 4, trial_mesh=mesh,
        local_trials=4 // groups, nested=True, telemetry=tp)
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)), st_v, st_s)
    assert set(obs_v) == set(obs_s)
    for k in CHANNELS:
        np.testing.assert_allclose(
            np.asarray(obs_v[k]), np.asarray(obs_s[k]), rtol=1e-5,
            err_msg=f"{k} diverged on the {groups}-group grid")


# ------------------------------------------------------------- campaign level


def _cfg(**over):
    kw = dict(fractions=(0.2,), seeds=(0, 1), experiment=_exp(),
              attack_heartbeats=6)
    kw.update(over)
    return CampaignConfig(**kw)


def test_campaign_milestones_populate_when_armed():
    armed = run_campaign(_cfg(telemetry=TelemetryParams(record=True)))
    for t in armed.trials:
        # warmup already formed the mesh, so coverage >= 0.9 from round 1
        assert t.coverage90_hb == 1
        assert isinstance(t.score_cross_hb, int)
    # the default config records nothing and leaves the sentinel columns
    off = run_campaign(_cfg())
    for t in off.trials:
        assert t.coverage90_hb == -1
        assert t.score_cross_hb == -1


def test_campaign_milestones_identical_under_sharding():
    cfg = _cfg(telemetry=TelemetryParams(record=True))
    r_v = run_campaign(cfg)
    r_s = run_campaign(cfg, trial_mesh=make_trial_mesh(2))
    for tv, ts in zip(r_v.trials, r_s.trials):
        assert tv.coverage90_hb == ts.coverage90_hb, tv.seed
        assert tv.score_cross_hb == ts.score_cross_hb, tv.seed


def test_report_campaign_renders_milestone_columns():
    from dst_libp2p_test_node_tpu.runtime.summarize import report_campaign

    r = run_campaign(_cfg(telemetry=TelemetryParams(record=True)))
    text = report_campaign(r.to_dict())
    assert "cov90_hb" in text and "score_x_hb" in text


# -------------------------------------------------- simulator + /metrics export


def test_simulator_flight_recorder_and_metrics_export():
    from dst_libp2p_test_node_tpu.runtime.metrics import NodeMetrics
    from dst_libp2p_test_node_tpu.runtime.simulator import Simulator

    cfg = ExperimentConfig(
        topo=TopoParams(network_size=16, msg_size_bytes=500, messages=1),
        connect_to=4, warmup_s=5.0, seed=3)
    sim = Simulator(cfg)
    sim.warmup()
    assert sim.last_telemetry == {}
    hb = float(sim.params.heartbeat_ms)
    sim.record_telemetry(TelemetryParams(record=True))
    sim.advance(3 * hb)
    assert set(sim.last_telemetry) == set(CHANNELS)
    assert sim.last_telemetry["tel_mesh_coverage"].shape == (3,)
    m = NodeMetrics()
    m.fill_from_telemetry(sim.last_telemetry)
    text = m.render()
    assert 'dst_sim_round_mesh_coverage{hb="0"}' in text
    assert 'dst_sim_round_degree_hist{hb="2",idx="0"}' in text
    # a disabled params object disarms the recorder again
    sim.record_telemetry(TelemetryParams(record=False))
    sim.reset()
    sim.advance(2 * hb)
    assert sim.last_telemetry == {}
