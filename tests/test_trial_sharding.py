"""Two-level device parallelism: the trial-axis sharded campaign.

`run_campaign(trial_mesh=...)` partitions a fraction's seed column across
device groups (parallel/sharding.make_trial_mesh) instead of stacking the
whole column onto one vmapped device program. The contracts pinned here:

  - sharded == vmapped: the same grid produces the same trial metrics
    (rtol 1e-5) on >= 2 device groups — the shard boundary moves placement,
    never numerics (batch_factor is a memory-dispatch hint; both gather
    forms are exact).
  - zero-attacker trials stay on the benign path bit-identically, sharded
    or not.
  - per-trial checkpoint + obs-sidecar resume works ACROSS group
    boundaries: a sweep checkpointed under one trial grid resumes under a
    different one (the checkpoint identity is the epoch-graph hash, which
    is grid-independent).
  - the r05 dead-weight fix: with the repair subsystem off (the default)
    a state holds none of the five repair leaves (ops/state.py), so the
    heartbeat and adversary scans have none to carry; a state made for
    armed params threads them, and an armed trace over a state without
    them fails by name.

conftest.py forces 8 virtual CPU devices, so the 2- and 4-group meshes are
real multi-device placements here.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dst_libp2p_test_node_tpu.analysis.jaxpr_audit import iter_eqns
from dst_libp2p_test_node_tpu.config.topology import TopoParams
from dst_libp2p_test_node_tpu.ops.adversary import (
    AdversaryParams, attacker_cohort, run_attacked_heartbeats,
)
from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph
from dst_libp2p_test_node_tpu.ops.heartbeat import (
    _run_heartbeats, run_heartbeats,
)
from dst_libp2p_test_node_tpu.ops.repair import RepairParams
from dst_libp2p_test_node_tpu.ops.faults import FaultParams
from dst_libp2p_test_node_tpu.ops.state import (
    PX_POOL_WIDTH, REPAIR_LEAVES, SimParams, arm_repair, graph_arrays,
    init_state, repair_inert,
)
from dst_libp2p_test_node_tpu.parallel.sharding import (
    TRIAL_AXIS, make_trial_mesh, peers_per_group,
)
from dst_libp2p_test_node_tpu.runtime.campaign import (
    CampaignConfig, attack_gossipsub, run_campaign, sharded_attack_window,
)
from dst_libp2p_test_node_tpu.runtime.simulator import ExperimentConfig


def _exp(n=64, seed=0, messages=2):
    return ExperimentConfig(
        topo=TopoParams(network_size=n, anchor_stages=2, min_bandwidth=50,
                        max_bandwidth=150, min_latency=40, max_latency=130,
                        msg_size_bytes=2000, messages=messages,
                        delay_seconds=1.0),
        connect_to=8, gossipsub=attack_gossipsub(), warmup_s=8.0, seed=seed)


def _cfg(**over):
    kw = dict(fractions=(0.0, 0.2), seeds=(0, 1, 2, 3), experiment=_exp(),
              attack_heartbeats=6)
    kw.update(over)
    return CampaignConfig(**kw)


# numeric TrialResult fields compared between the sharded and vmapped runs
_COMPARE = ("honest_coverage", "benign_coverage", "latency_p50_ms",
            "latency_p99_ms", "latency_inflation", "graylisted_frac_final",
            "attacker_mesh_share_final", "attacker_score_final",
            "recovery_time_ms")
_EXACT = ("attackers", "hb_to_graylist", "mesh_recovery_hb",
          "mesh_evictions_total", "px_grafts_total", "redials_total")


def _assert_trials_close(a, b, rtol=1e-5):
    assert len(a) == len(b)
    for ta, tb in zip(a, b):
        assert (ta.fraction, ta.seed) == (tb.fraction, tb.seed)
        for k in _EXACT:
            assert getattr(ta, k) == getattr(tb, k), (k, ta.seed)
        for k in _COMPARE:
            np.testing.assert_allclose(
                getattr(ta, k), getattr(tb, k), rtol=rtol,
                err_msg=f"{k} diverged at seed {ta.seed}")


def test_trial_mesh_shape_and_divisibility():
    m = make_trial_mesh(2, n_devices=4)
    assert m.shape == {TRIAL_AXIS: 2, "peers": 2}
    assert make_trial_mesh(n_devices=4).shape[TRIAL_AXIS] == 4
    with pytest.raises(ValueError):
        make_trial_mesh(3, n_devices=4)


@pytest.mark.parametrize("groups", [2, 4])
def test_sharded_campaign_equals_vmapped(groups):
    r_v = run_campaign(_cfg())
    tm = make_trial_mesh(groups, n_devices=groups)
    r_s = run_campaign(_cfg(), trial_mesh=tm)
    _assert_trials_close(r_v.trials, r_s.trials)


def test_zero_attacker_trials_identical_under_sharding():
    # fraction-0.0 cells take the benign Simulator path whether or not a
    # trial mesh is live; their metrics must be EXACTLY equal, not rtol
    r_v = run_campaign(_cfg())
    r_s = run_campaign(_cfg(), trial_mesh=make_trial_mesh(4, n_devices=4))
    for tv, ts in zip(r_v.trials, r_s.trials):
        if tv.fraction == 0.0:
            assert tv.honest_coverage == ts.honest_coverage
            assert tv.latency_p50_ms == ts.latency_p50_ms
            assert tv.latency_p99_ms == ts.latency_p99_ms


def test_sharded_recovery_window_equals_sequential():
    rep = RepairParams(evict=True, px=True, redial=True)
    r_v = run_campaign(_cfg(fractions=(0.2,), recovery_heartbeats=4,
                            repair=rep))
    r_s = run_campaign(_cfg(fractions=(0.2,), recovery_heartbeats=4,
                            repair=rep),
                       trial_mesh=make_trial_mesh(2, n_devices=2))
    _assert_trials_close(r_v.trials, r_s.trials)


def test_checkpoint_resume_across_group_boundaries(tmp_path):
    d = str(tmp_path / "ck")
    c1 = _cfg(fractions=(0.2,), checkpoint_dir=d)
    r1 = run_campaign(c1, trial_mesh=make_trial_mesh(4, n_devices=4))
    written = sorted(os.listdir(d))
    assert len(written) == 8  # 4 trial checkpoints + 4 obs sidecars
    mtimes = {f: os.path.getmtime(os.path.join(d, f)) for f in written}
    # resume the SAME sweep under a different trial grid: the checkpoint
    # identity (epoch-graph hash) is grid-independent, so every trial must
    # resume — no snapshot may be rewritten — and the metrics must match
    c2 = _cfg(fractions=(0.2,), checkpoint_dir=d)
    r2 = run_campaign(c2, trial_mesh=make_trial_mesh(2, n_devices=2))
    assert {f: os.path.getmtime(os.path.join(d, f))
            for f in sorted(os.listdir(d))} == mtimes
    _assert_trials_close(r1.trials, r2.trials)


def test_stale_checkpoint_is_recomputed_not_trusted(tmp_path):
    d = str(tmp_path / "ck")
    r1 = run_campaign(_cfg(fractions=(0.2,), checkpoint_dir=d),
                      trial_mesh=make_trial_mesh(2, n_devices=2))
    # truncate one snapshot: the resume scan must silently recompute that
    # trial instead of crashing or loading garbage
    victim = sorted(f for f in os.listdir(d) if not f.endswith(".obs.npz"))[0]
    with open(os.path.join(d, victim), "wb") as fh:
        fh.write(b"\x00" * 16)
    r2 = run_campaign(_cfg(fractions=(0.2,), checkpoint_dir=d),
                      trial_mesh=make_trial_mesh(2, n_devices=2))
    _assert_trials_close(r1.trials, r2.trials)


def _corrupt_meta(path, mutate):
    """Round-trip a trial checkpoint .npz with its meta_json mutated —
    keeps the archive itself loadable so only the identity check trips."""
    import io
    import json

    z = np.load(path)
    arrs = {k: z[k] for k in z.files}
    meta = json.loads(bytes(arrs["meta_json"]).decode())
    raw = mutate(meta)
    arrs["meta_json"] = np.frombuffer(raw, dtype=np.uint8)
    buf = io.BytesIO()
    np.savez(buf, **arrs)
    with open(path, "wb") as fh:
        fh.write(buf.getvalue())


@pytest.mark.parametrize("corruption", ["truncated_sidecar", "bad_json_meta",
                                        "wrong_epoch_hash"])
def test_corrupt_checkpoint_is_recomputed_not_trusted(tmp_path, corruption):
    # PR-5 claims a stale snapshot is "silently recomputed, never trusted";
    # pin each failure class the resume path must absorb: a truncated obs
    # sidecar, snapshot metadata that no longer parses as JSON, and a
    # snapshot written against a DIFFERENT epoch graph
    d = str(tmp_path / "ck")
    r1 = run_campaign(_cfg(fractions=(0.2,), checkpoint_dir=d),
                      trial_mesh=make_trial_mesh(2, n_devices=2))
    snaps = sorted(f for f in os.listdir(d) if not f.endswith(".obs.npz"))
    if corruption == "truncated_sidecar":
        victim = os.path.join(d, snaps[0][:-len(".npz")] + ".obs.npz")
        raw = open(victim, "rb").read()
        with open(victim, "wb") as fh:
            fh.write(raw[: len(raw) // 3])
    elif corruption == "bad_json_meta":
        _corrupt_meta(os.path.join(d, snaps[0]),
                      lambda meta: b'{"version": not json')
    else:
        _corrupt_meta(
            os.path.join(d, snaps[0]),
            lambda meta: json.dumps(
                dict(meta, graph_sha256="0" * 64)).encode())
    r2 = run_campaign(_cfg(fractions=(0.2,), checkpoint_dir=d),
                      trial_mesh=make_trial_mesh(2, n_devices=2))
    _assert_trials_close(r1.trials, r2.trials)


def _make_op_fixture(n=64, connect_to=8, seed=0, **over):
    g = build_connection_graph(n, connect_to, seed=seed)
    params = SimParams(n=n, capacity=g.capacity, **over)
    return params, init_state(params, seed=seed), graph_arrays(g)


def test_inert_repair_leaves_ride_around_the_scan():
    # the r05 regression: the five repair leaves ((N,8) px_pool and four
    # (N,) counters) rode every default scan carry as dead weight. With
    # repair off the state has none of them, so no scan can carry one
    params, state, a = _make_op_fixture()
    assert repair_inert(params)
    assert all(getattr(state, k) is None for k in REPAIR_LEAVES)
    armed = RepairParams(evict=True).apply(params)
    assert not repair_inert(armed)
    armed_state = init_state(armed, seed=0)
    assert (len(jax.tree.leaves(armed_state))
            == len(jax.tree.leaves(state)) + len(REPAIR_LEAVES))

    def carried(st, p):
        """(count of (N, 8) int32, count of (N,) int32) in the carry of the
        scan `_run_heartbeats` traces to."""
        jaxpr = jax.make_jaxpr(
            lambda s: _run_heartbeats(s, a["conns"], a["rev"],
                                      a["out_mask"], p, 3))(st)
        scans = [e for e, _ in iter_eqns(jaxpr.jaxpr)
                 if e.primitive.name == "scan"]
        assert len(scans) == 1
        e = scans[0]
        nc = e.params["num_consts"]
        carry = e.invars[nc:nc + e.params["num_carry"]]
        avals = [(v.aval.shape, str(v.aval.dtype)) for v in carry]
        return (avals.count(((params.n, PX_POOL_WIDTH), "int32")),
                avals.count(((params.n,), "int32")))

    pools, counters = carried(state, params)
    assert pools == 0
    # an ARMED state threads them through the scan: the pool and the four
    # counters more
    assert carried(armed_state, armed) == (1, counters + 4)
    out = run_heartbeats(armed_state, a["conns"], a["rev"], a["out_mask"],
                         armed, 3)
    assert all(getattr(out, k) is not None for k in REPAIR_LEAVES)
    # an armed trace over a state made inert fails where it is traced, and
    # names the way out, which gives init_state's own leaves
    with pytest.raises(ValueError, match="arm_repair"):
        run_heartbeats(state, a["conns"], a["rev"], a["out_mask"], armed, 3)
    for x, y in zip(jax.tree.leaves(arm_repair(state)),
                    jax.tree.leaves(armed_state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert arm_repair(armed_state) is armed_state


def test_trial_mesh_full_grid_and_edge_cases():
    # the FULL grid under conftest's 8 virtual devices: trial_groups picks
    # the first axis and every remaining device becomes each group's peer
    # submesh — both axes live
    m = make_trial_mesh(2)
    assert m.shape == {TRIAL_AXIS: 2, "peers": 4}
    assert peers_per_group(m) == 4
    # 1-device degenerate grid: still a real 2-axis mesh (1 x 1), so the
    # nested window program compiles unchanged on a laptop
    m1 = make_trial_mesh(1, n_devices=1)
    assert m1.shape == {TRIAL_AXIS: 1, "peers": 1}
    assert peers_per_group(m1) == 1
    # validation: group count must be positive and divide the device count
    with pytest.raises(ValueError):
        make_trial_mesh(0, n_devices=4)
    with pytest.raises(ValueError):
        make_trial_mesh(3)  # 8 devices, non-divisible full grid
    with pytest.raises(ValueError):
        make_trial_mesh(5, n_devices=8)


def _stacked_attack_fixture(trials=4, fraction=0.2):
    params, _, a = _make_op_fixture(
        slow_weight=-10.0, slow_decay=0.9, graylist_threshold=-50.0,
        gossip_threshold=-10.0, publish_threshold=-20.0)
    states = [init_state(params, seed=s) for s in range(trials)]
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *states)
    att = jnp.stack([
        jnp.asarray(attacker_cohort(params.n, fraction, seed=s))
        for s in range(trials)])
    shared = {k: a[k] for k in ("conns", "rev", "out_mask")}
    return params, stacked, att, shared


@pytest.mark.parametrize("fraction", [0.2, 0.0])
def test_nested_window_matches_replicated_submesh(fraction):
    # the tentpole contract at the op level: the nested pjit program
    # (peer axis partitioned inside each trial group) against the legacy
    # trial-only shard_map that REPLICATES each group's peer submesh.
    # State leaves must come back bit-identical — the shard boundary moves
    # placement, never per-peer numerics; only the observable scalar
    # REDUCTIONS may reassociate across peer shards (rtol 1e-5). At zero
    # attackers the attacker-mean reductions sum exact zeros, so even the
    # observables are bit-equal
    import jax

    params, stacked, att, shared = _stacked_attack_fixture(fraction=fraction)
    adv = AdversaryParams(scenario="sybil_graft_flood")
    mesh = make_trial_mesh(2)  # 2 x 4 under conftest's 8 devices
    out_n = sharded_attack_window(stacked, shared, att, params, adv, 4,
                                  trial_mesh=mesh, local_trials=2,
                                  nested=True)
    out_r = sharded_attack_window(stacked, shared, att, params, adv, 4,
                                  trial_mesh=mesh, local_trials=2,
                                  nested=False)
    st_n, obs_n = out_n
    st_r, obs_r = out_r
    jax.tree_util.tree_map(np.testing.assert_array_equal, st_n, st_r)
    if fraction == 0.0:
        jax.tree_util.tree_map(np.testing.assert_array_equal, obs_n, obs_r)
    else:
        jax.tree_util.tree_map(
            lambda x, y: np.testing.assert_allclose(x, y, rtol=1e-5),
            obs_n, obs_r)


@pytest.mark.parametrize("groups", [2, 4])
def test_nested_campaign_equals_vmapped(groups):
    # end-to-end over the FULL 8-device grid: 2x4 and 4x2 nested meshes
    # must reproduce the single-device vmapped sweep trial for trial
    r_v = run_campaign(_cfg())
    r_s = run_campaign(_cfg(), trial_mesh=make_trial_mesh(groups))
    _assert_trials_close(r_v.trials, r_s.trials)


_FAULT_FIELDS = ("heal_time_ms", "coverage_under_partition",
                 "post_churn_reconvergence_hb")


def test_faulted_sharded_campaign_equals_vmapped():
    # the PR-6 regression this PR closes: a faulted sweep used to DROP the
    # trial mesh and silently fall back to the vmapped stack. Now the
    # crash/side/spike cohort masks shard with the trial batch and the
    # fault-armed window runs on the nested grid — same numbers, fault
    # observables included
    faults = FaultParams(partition_frac=0.5, partition_window=(1, 4),
                         crash_frac=0.1, crash_window=(1, 3))
    r_v = run_campaign(_cfg(faults=faults))
    r_s = run_campaign(_cfg(faults=faults), trial_mesh=make_trial_mesh(2))
    _assert_trials_close(r_v.trials, r_s.trials)
    for tv, ts in zip(r_v.trials, r_s.trials):
        for k in _FAULT_FIELDS:
            np.testing.assert_allclose(
                getattr(tv, k), getattr(ts, k), rtol=1e-5,
                err_msg=f"{k} diverged at seed {tv.seed}")


def _dht_cfg(**over):
    # lookup eclipse + rtable poisoning with a mid-window heal: exercises
    # both recovery legs (attacked pool, then healed pool resuming the same
    # per-trial dialed graphs) on top of the repair subsystem
    from dst_libp2p_test_node_tpu.ops.dht_adversary import DhtAdversaryParams

    kw = dict(
        fractions=(0.0, 0.2), seeds=(0, 1, 2, 3), experiment=_exp(),
        attack_heartbeats=4, recovery_heartbeats=4,
        repair=RepairParams(evict=True, redial=True),
        dht=DhtAdversaryParams(lookup_eclipse=True, rtable_poison=True,
                               heal_hb=2, warmup_waves=1, lookup_rounds=2))
    kw.update(over)
    return CampaignConfig(**kw)


@pytest.mark.parametrize("groups", [2, 4])
def test_dht_attacked_sharded_campaign_equals_vmapped(groups):
    # the cross-protocol window on the nested grid: per-seed poisoned DHT
    # pools shard with the trial batch, both recovery legs (eclipsed pool,
    # healed pool) run under shard_map — same trial metrics as the
    # single-device vmapped sweep, poison fraction included
    r_v = run_campaign(_dht_cfg())
    r_s = run_campaign(_dht_cfg(), trial_mesh=make_trial_mesh(groups))
    _assert_trials_close(r_v.trials, r_s.trials)
    for tv, ts in zip(r_v.trials, r_s.trials):
        np.testing.assert_allclose(
            tv.rtable_poison_frac, ts.rtable_poison_frac, rtol=1e-5,
            err_msg=f"rtable_poison_frac diverged at seed {tv.seed}")
        if tv.fraction > 0.0:
            # the DHT was built and measured for every attacked trial
            assert tv.rtable_poison_frac >= 0.0


def test_dht_zero_attacker_trials_exact_under_sharding():
    # fraction-0.0 cells take the benign path even with the DHT adversary
    # armed: metrics EXACTLY equal sharded-vs-not and the poison channel
    # stays at its -1 sentinel (no cohort -> no sybils -> nothing to build)
    r_v = run_campaign(_dht_cfg(fractions=(0.0,)))
    r_s = run_campaign(_dht_cfg(fractions=(0.0,)),
                       trial_mesh=make_trial_mesh(2))
    for tv, ts in zip(r_v.trials, r_s.trials):
        assert tv.honest_coverage == ts.honest_coverage
        assert tv.latency_p50_ms == ts.latency_p50_ms
        assert tv.rtable_poison_frac == ts.rtable_poison_frac == -1.0


def test_inert_repair_leaves_stripped_from_attack_window():
    params, state, a = _make_op_fixture(
        slow_weight=-10.0, slow_decay=0.9, graylist_threshold=-50.0,
        gossip_threshold=-10.0, publish_threshold=-20.0)
    assert repair_inert(params)
    att = jnp.asarray(attacker_cohort(params.n, 0.1, seed=0))
    adv = AdversaryParams(scenario="sybil_graft_flood")
    out, _obs = run_attacked_heartbeats(
        state, a["conns"], a["rev"], a["out_mask"], att, params, adv, 3)
    assert len(jax.tree.leaves(out)) == len(jax.tree.leaves(state))
    for k in REPAIR_LEAVES:
        assert getattr(out, k) is None, (
            f"{k} was carried through the attack-window scan")
