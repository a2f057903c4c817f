"""The hot-entrypoint contract registry.

Canonical small configs (N=32 single topic, T=2 x N=16 multitopic, 3-rung
aval-family miniature of the bench ladder) are built once per process and
shared across contracts — building them is pure numpy/host work plus a few
tiny device constants; the audit itself never executes a registered
entrypoint concretely (checkify mode excepted).

The registered surface:

  disseminate/cold        serialized-answer publish (2 surviving conds: the
                          exact-mode repair branch plus the nested
                          prefix-certificate fallback to the legacy serial
                          refiner)
  disseminate/warm        warm-started publish (3 surviving conds: repair +
                          certificate fallback + the cold-rerun guard)
  disseminate/exact_serial
                          the legacy serial refiner forced via
                          answer_queue_mode="serial" (1 surviving cond: the
                          repair branch only — no nested fallback to trace)
  disseminate/bounded     bounded-accounting publish (cond-free by design)
  publisher/batch_scan    the batched service dispatch (ISSUE 14): a scan
                          over stacked seed columns, disseminate/cold's 2
                          conds surviving in the body plus the padding
                          active-mask cond (3 total)
  runs/disseminate        one message in every run of a batch (ISSUE 52,
                          ops/runs.py): lax.map of disseminate/cold over
                          the runs' axis, its 2 conds alive in the body
  runs/run_heartbeats     the batch's scan: lax.map of run_heartbeats, the
                          step's 4 skips alive in the inner scan's body
  heartbeat_step          one mesh-maintenance round (4 steady-state skips)
  run_heartbeats          the simulator scan step (conds must survive the
                          scan body)
  run_attacked_heartbeats the campaign attack window, UNBATCHED trial form
                          (the vmapped multi-seed form in runtime/campaign.py
                          intentionally trades these conds for select_n —
                          that form is deliberately NOT registered with a
                          cond contract; see docs/ARCHITECTURE.md §9)
  adversary/adaptive_window
                          the adaptive attacker controller in the scan:
                          the carry widens to (state, ctrl), both feeding
                          the next window aval-stable; collective-free
  heartbeat_step/evict    the opt-in mesh-repair heartbeat (eviction +
                          PX-capture branches armed: 6 surviving conds)
  repair/recovery_window  the post-attack repair scan (ops/repair.py) with
                          the connection graph in the carry; checkified to
                          preserve the reverse-slot involution over the
                          mutated graph
  faults/churn_window     the fault window, crash + partition + spike armed
                          over an attacked mesh (UNBATCHED, collective-free)
  kad/find_node           the DHT lookup scan
  multitopic/disseminate  the T*N block-diagonal publish
  telemetry/recorded_heartbeats
                          the armed flight-recorder scan (ops/telemetry.py):
                          the heartbeat program plus the per-round channel
                          reductions riding the obs stack — the 4
                          steady-state conds must survive the added
                          instrumentation
  telemetry/recorded_attack_window
                          the attack window with the recorder armed via the
                          static telemetry kwarg — the UNBATCHED form, same
                          cond census as run_attacked_heartbeats
  campaign/attack_window_sharded
                          the LEGACY trial-only shard_map wrapper around
                          the vmapped attack window (nested=False): traced
                          on a device-count-adaptive 2-group trial mesh
                          with the repair leaves STRIPPED — retained as the
                          replicated-peer-submesh equality baseline (cond
                          census intentionally unset — the vmapped body
                          trades the heartbeat conds for select_n, see
                          run_attacked_heartbeats' note)
  campaign/attack_window_nested
                          the nested two-level pjit program the sharded
                          sweep dispatches by default: explicit
                          in/out_shardings over the full trials x peers
                          grid (2 groups x remaining devices per group),
                          peer rows partitioned inside each trial group
  campaign/faulted_window_nested
                          the fault-armed nested window: per-trial
                          crash/side/spike cohorts shard over both grid
                          axes like the attacker masks
  campaign/attack_window_dcn
                          the nested attack window on the three-level
                          dcn x trials x peers mesh: GA-S006 proves zero
                          collective bytes cross the dcn axis
  campaign/dht_attack_window
                          the cross-protocol recovery window
                          (ops/dht_adversary.py): repair armed, per-trial
                          poisoned discovery shortlists sharded over the
                          same nested grid and consumed by the redial path
  conformance/differential_round
                          the compiled side of the spec-differential gate
                          (analysis/conformance.py): heartbeat_step ->
                          adversary_round, the 4 heartbeat conds surviving
  episub/heartbeat_step   one episub tree round (ISSUE 19, ops/episub.py):
                          eager tree push + lazy IHAVE repair + graylisted
                          re-parenting, thresholds armed — exactly 1
                          surviving cond (the shared fmd/slow decay gate)
  protocol/arena_window   the arena's sharded episub attack window
                          (sharded_episub_window): nested trials x peers
                          grid like campaign/attack_window_nested, state
                          and ctrl feeding back aval-stable
"""

from __future__ import annotations

import functools

from .contracts import EntrypointContract, LadderRung, TraceSpec


@functools.lru_cache(maxsize=None)
def _single_topic(n: int = 32, connect_to: int = 4, **over):
    import jax.numpy as jnp

    from ..config.topology import Topology, TopoParams
    from ..ops.graph import build_connection_graph
    from ..ops.state import SimParams, graph_arrays, init_state

    g = build_connection_graph(n, connect_to, seed=0)
    params = SimParams(n=n, capacity=g.capacity, **dict(over))
    state = init_state(params, seed=0)
    a = graph_arrays(g)
    t = Topology.build(TopoParams(
        network_size=n, anchor_stages=5, min_bandwidth=50, max_bandwidth=150,
        min_latency=40, max_latency=130))
    topo = (jnp.asarray(t.stage_of_peer), jnp.asarray(t.latency_ms),
            jnp.asarray(t.bw_up_mbit))
    return g, params, state, a, topo


def _disseminate_spec(**params_over) -> TraceSpec:
    from ..ops.disseminate import disseminate

    g, params, state, a, (stage, lat, bw) = _single_topic(
        **{k: v for k, v in params_over.items()})
    return TraceSpec(
        fn=disseminate,
        args=(state, a["conns"], a["rev"], stage, lat, bw),
        kwargs=dict(publisher=3, t0_ms=0.0, params=params,
                    payload_bytes=15000))


def _publish_batch_spec() -> TraceSpec:
    import numpy as np

    from ..runtime.publisher import publish_batch_scan

    g, params, state, a, (stage, lat, bw) = _single_topic()
    rows = np.full(4, 3, dtype=np.int32)
    active = np.ones(4, dtype=bool)
    return TraceSpec(
        fn=publish_batch_scan,
        args=(state, a["conns"], a["rev"], stage, lat, bw, rows, active),
        kwargs=dict(t0_ms=0.0, params=params, payload_bytes=15000,
                    fragments=1, with_gossip=True, loss_stage=None,
                    loss_mode="tcp", lat_edge=None, loss_edge=None,
                    ans_tables=None, valid_edge=None, with_fanout=False))


def _runs_spec(program: str) -> TraceSpec:
    """ops/runs.py's batched programs on two stacked runs of the canonical
    network: every leaf a run owns with the runs' axis in front."""
    import jax
    import jax.numpy as jnp

    from ..ops import runs

    g, params, state, a, (stage, lat, bw) = _single_topic()
    states, a = jax.tree_util.tree_map(
        lambda x: jnp.stack([x, x]), (state, a))
    if program == "_run_heartbeats":
        return TraceSpec(
            fn=runs._run_heartbeats,
            args=(states, a["conns"], a["rev"], a["out_mask"]),
            kwargs=dict(params=params, steps=4))
    return TraceSpec(
        fn=runs.disseminate,
        args=(states, a["conns"], a["rev"], stage, lat, bw),
        kwargs=dict(publisher=3, t0_ms=0.0, params=params,
                    payload_bytes=15000))


def _heartbeat_spec(fn_name: str, **params_over) -> TraceSpec:
    from ..ops import heartbeat

    g, params, state, a, _ = _single_topic(**params_over)
    fn = getattr(heartbeat, fn_name)
    kwargs = {"params": params}
    if fn_name == "run_heartbeats":
        kwargs["steps"] = 4
    return TraceSpec(
        fn=fn, args=(state, a["conns"], a["rev"], a["out_mask"]),
        kwargs=kwargs)


# the armed-defense overrides every repair entrypoint traces under: the
# repair branches gate on scores, so auditing them against the default
# (thresholds compiled out) config would certify a path nobody runs
_ARMED = dict(slow_weight=-10.0, slow_decay=0.9, gossip_threshold=-10.0,
              publish_threshold=-20.0, graylist_threshold=-50.0)
_REPAIR = dict(evict=True, px=True, redial=True, **_ARMED)


def _repair_spec() -> TraceSpec:
    import jax.numpy as jnp

    from ..ops.adversary import attacker_cohort
    from ..ops.repair import run_recovery_heartbeats

    g, params, state, a, _ = _single_topic(**_REPAIR)
    att = jnp.asarray(attacker_cohort(params.n, 0.25, seed=1))
    return TraceSpec(
        fn=run_recovery_heartbeats,
        args=(state, a["conns"], a["rev"], a["out_mask"], att),
        kwargs=dict(params=params, steps=4, publisher=3))


def _attack_spec() -> TraceSpec:
    import jax.numpy as jnp

    from ..ops.adversary import (AdversaryParams, attacker_cohort,
                                 run_attacked_heartbeats)

    g, params, state, a, _ = _single_topic()
    att = jnp.asarray(attacker_cohort(params.n, 0.25, seed=1))
    return TraceSpec(
        fn=run_attacked_heartbeats,
        args=(state, a["conns"], a["rev"], a["out_mask"], att),
        kwargs=dict(params=params, adv=AdversaryParams(), steps=4))


def _adaptive_attack_spec() -> TraceSpec:
    import jax.numpy as jnp

    from ..ops.adversary import (AdaptivePolicy, AdversaryParams,
                                 attacker_cohort, run_adaptive_heartbeats)
    from ..ops.state import init_adaptive_ctrl

    # repair leaves live: the PX-poison behavior writes px_pool rows and the
    # audit should see that program, not the one without a pool
    g, params, state, a, _ = _single_topic(**_REPAIR)
    att = jnp.asarray(attacker_cohort(params.n, 0.25, seed=1))
    adv = AdversaryParams(adaptive=AdaptivePolicy(enabled=True))
    return TraceSpec(
        fn=run_adaptive_heartbeats,
        args=(state, a["conns"], a["rev"], a["out_mask"], att),
        kwargs=dict(params=params, adv=adv, steps=4,
                    ctrl=init_adaptive_ctrl(params.n)))


def _conform_spec() -> TraceSpec:
    import jax.numpy as jnp

    from ..ops.adversary import AdversaryParams, attacker_cohort
    from .conformance import differential_round

    # the conformance harness's own fixture arming: thresholds live, repair
    # off — the program the differential walks per heartbeat
    g, params, state, a, _ = _single_topic(**_ARMED)
    att = jnp.asarray(attacker_cohort(params.n, 0.25, seed=1))
    return TraceSpec(
        fn=differential_round,
        args=(state, a["conns"], a["rev"], a["out_mask"], att),
        kwargs=dict(params=params, adv=AdversaryParams(),
                    hb_idx=jnp.int32(0)))


def _faults_spec() -> TraceSpec:
    import jax.numpy as jnp

    from ..ops.adversary import AdversaryParams, attacker_cohort
    from ..ops.faults import FaultParams, fault_masks, run_faulted_heartbeats

    g, params, state, a, _ = _single_topic(**_ARMED)
    att = jnp.asarray(attacker_cohort(params.n, 0.25, seed=1))
    # every fault family armed at once: crash + partition + spike windows
    # overlapping, composed with an active adversary cohort — the maximal
    # program, so a cond lost in ANY family fails the audit
    faults = FaultParams(
        crash_frac=0.2, crash_window=(0, 2),
        partition_frac=0.3, partition_window=(1, 3),
        spike_frac=0.2, spike_window=(0, 4), spike_ms=250.0)
    fm = fault_masks(params.n, faults, seed=1, publisher=3)
    return TraceSpec(
        fn=run_faulted_heartbeats,
        args=(state, a["conns"], a["rev"], a["out_mask"], att),
        kwargs=dict(params=params, adv=AdversaryParams(), faults=faults,
                    crash=jnp.asarray(fm["crash"]),
                    side=jnp.asarray(fm["side"]),
                    spike=jnp.asarray(fm["spike"]), steps=4))


def _sharded_attack_spec() -> TraceSpec:
    import jax
    import jax.numpy as jnp

    from ..ops.adversary import AdversaryParams, attacker_cohort
    from ..parallel.sharding import audit_trial_groups, make_trial_mesh
    from ..runtime.campaign import sharded_attack_window

    g, params, state, a, _ = _single_topic()
    groups = audit_trial_groups()
    mesh = make_trial_mesh(groups, n_devices=groups)
    local = 2
    trials = groups * local
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.stack([jnp.asarray(x)] * trials), state)
    att = jnp.stack([
        jnp.asarray(attacker_cohort(params.n, 0.25, seed=s))
        for s in range(trials)])
    shared = {k: a[k] for k in ("conns", "rev", "out_mask")}
    return TraceSpec(
        fn=sharded_attack_window,
        args=(stacked, shared, att),
        kwargs=dict(params=params, adv=AdversaryParams(), steps=3,
                    trial_mesh=mesh, local_trials=local, nested=False))


def _nested_attack_spec() -> TraceSpec:
    import jax
    import jax.numpy as jnp

    from ..ops.adversary import AdversaryParams, attacker_cohort
    from ..parallel.sharding import audit_trial_groups, make_trial_mesh
    from ..runtime.campaign import sharded_attack_window

    g, params, state, a, _ = _single_topic()
    # the FULL grid: trial groups x every remaining device as each group's
    # peer submesh (2x2 under the CI lint gate's 4 virtual devices),
    # degenerating gracefully to 1x1 on a single device — the contract
    # always traces the nested pjit program the campaign dispatches,
    # whatever the host's device count. GRAFT_AUDIT_TRIAL_GROUPS flips the
    # grid aspect (2x4 vs 4x2 under CI's 8 devices) without a code change.
    groups = audit_trial_groups()
    mesh = make_trial_mesh(groups)
    local = 2
    trials = groups * local
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.stack([jnp.asarray(x)] * trials), state)
    att = jnp.stack([
        jnp.asarray(attacker_cohort(params.n, 0.25, seed=s))
        for s in range(trials)])
    shared = {k: a[k] for k in ("conns", "rev", "out_mask")}
    return TraceSpec(
        fn=sharded_attack_window,
        args=(stacked, shared, att),
        kwargs=dict(params=params, adv=AdversaryParams(), steps=3,
                    trial_mesh=mesh, local_trials=local))


def _dht_attack_window_spec() -> TraceSpec:
    import jax
    import jax.numpy as jnp

    from ..ops.adversary import attacker_cohort
    from ..ops.dht_adversary import (DhtAdversaryParams, build_attacked_dht,
                                     dht_repair_pool)
    from ..parallel.sharding import audit_trial_groups, make_trial_mesh
    from ..runtime.campaign import sharded_dht_recovery_window

    # repair ARMED: the DHT window exists to feed the redial path a
    # poisoned shortlist, so the audited program is the one with the repair
    # leaves live in the carry
    g, params, state, a, (stage, lat, bw) = _single_topic(**_REPAIR)
    groups = audit_trial_groups()
    mesh = make_trial_mesh(groups)
    local = 2
    trials = groups * local
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.stack([jnp.asarray(x)] * trials), state)
    dht = DhtAdversaryParams(lookup_eclipse=True, warmup_waves=1,
                             lookup_rounds=2)
    atts, pools = [], []
    for s in range(trials):
        att_np = attacker_cohort(params.n, 0.25, seed=s)
        kstate, directory = build_attacked_dht(
            params.n, seed=s, dht=dht, attacker=att_np, victim=3,
            stage=stage, lat_ms=lat)
        pool, _ = dht_repair_pool(
            kstate, dht, stage, lat, attacker=jnp.asarray(att_np),
            directory=directory)
        atts.append(jnp.asarray(att_np))
        pools.append(pool)
    shared = {k: a[k] for k in ("conns", "rev", "out_mask")}
    return TraceSpec(
        fn=sharded_dht_recovery_window,
        args=(stacked, shared, None, jnp.stack(atts), jnp.stack(pools)),
        kwargs=dict(rparams=params, steps=3, publisher=3, trial_mesh=mesh,
                    local_trials=local))


def _faulted_nested_spec() -> TraceSpec:
    import jax
    import jax.numpy as jnp

    from ..ops.adversary import AdversaryParams, attacker_cohort
    from ..ops.faults import FaultParams, fault_masks
    from ..parallel.sharding import audit_trial_groups, make_trial_mesh
    from ..runtime.campaign import sharded_faulted_window

    g, params, state, a, _ = _single_topic(**_ARMED)
    groups = audit_trial_groups()
    mesh = make_trial_mesh(groups)
    local = 2
    trials = groups * local
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.stack([jnp.asarray(x)] * trials), state)
    faults = FaultParams(
        crash_frac=0.2, crash_window=(0, 2),
        partition_frac=0.3, partition_window=(1, 3),
        spike_frac=0.2, spike_window=(0, 4), spike_ms=250.0)
    atts, crs, sds, sps = [], [], [], []
    for s in range(trials):
        atts.append(jnp.asarray(attacker_cohort(params.n, 0.25, seed=s)))
        fm = fault_masks(params.n, faults, seed=s, publisher=3)
        crs.append(jnp.asarray(fm["crash"]))
        sds.append(jnp.asarray(fm["side"]))
        sps.append(jnp.asarray(fm["spike"]))
    shared = {k: a[k] for k in ("conns", "rev", "out_mask")}
    return TraceSpec(
        fn=sharded_faulted_window,
        args=(stacked, shared, jnp.stack(atts), jnp.stack(crs),
              jnp.stack(sds), jnp.stack(sps)),
        kwargs=dict(params=params, adv=AdversaryParams(), faults=faults,
                    steps=3, trial_mesh=mesh, local_trials=local))


def _episub_step_spec() -> TraceSpec:
    from ..ops.episub import (EpisubParams, episub_heartbeat_step,
                              init_episub_ctrl)

    # graylist thresholds live: the score-gated parent-eligibility edge
    # mask is a static compile-out under the reference defaults, and the
    # audited program must be the armed one the arena runs
    g, params, state, a, _ = _single_topic(**_ARMED)
    return TraceSpec(
        fn=episub_heartbeat_step,
        args=(state, init_episub_ctrl(params.n), a["conns"], a["rev"],
              a["out_mask"]),
        kwargs=dict(params=params, ep=EpisubParams(root=3)))


def _arena_window_spec() -> TraceSpec:
    import jax
    import jax.numpy as jnp

    from ..ops.adversary import (AdaptivePolicy, AdversaryParams,
                                 attacker_cohort)
    from ..ops.episub import EpisubParams, init_episub_ctrl
    from ..parallel.sharding import audit_trial_groups, make_trial_mesh
    from ..runtime.campaign import sharded_episub_window

    g, params, state, a, _ = _single_topic(**_ARMED)
    groups = audit_trial_groups()
    mesh = make_trial_mesh(groups)
    local = 2
    trials = groups * local
    stack = lambda x: jnp.stack([jnp.asarray(x)] * trials)  # noqa: E731
    stacked = jax.tree_util.tree_map(stack, state)
    ctrls = jax.tree_util.tree_map(stack, init_episub_ctrl(params.n))
    att = jnp.stack([
        jnp.asarray(attacker_cohort(params.n, 0.25, seed=s))
        for s in range(trials)])
    shared = {k: a[k] for k in ("conns", "rev", "out_mask")}
    adv = AdversaryParams(adaptive=AdaptivePolicy(enabled=True))
    return TraceSpec(
        fn=sharded_episub_window,
        args=(stacked, ctrls, shared, att),
        kwargs=dict(params=params, ep=EpisubParams(root=3), adv=adv,
                    steps=3, trial_mesh=mesh, local_trials=local))


def attack_rung_spec(n: int, *, steps: int = 20, connect_to: int = 10,
                     local_trials: int = 2,
                     trial_groups: int | None = None) -> TraceSpec:
    """The 1M-rung ladder program at an arbitrary peer count: the nested
    attack window exactly as bench_configs config 8 dispatches it
    (scenario sybil_graft_flood, connect_to=10, fractions (0, 0.1) x seeds
    (0, 1) -> 2 trial groups x 2 local trials). The sharding auditor's
    rung predictor lowers THIS spec at 3-4 peer counts and extrapolates
    the per-leaf footprints to ATTACK_RUNG_PEERS on a modeled v5e-8."""
    import jax
    import jax.numpy as jnp

    from ..ops.adversary import AdversaryParams, attacker_cohort
    from ..parallel.sharding import make_trial_mesh
    from ..runtime.campaign import sharded_attack_window

    g, params, state, a, _ = _single_topic(n=n, connect_to=connect_to)
    groups = 2 if trial_groups is None else trial_groups
    mesh = make_trial_mesh(groups)
    trials = groups * local_trials
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.stack([jnp.asarray(x)] * trials), state)
    # config 8's attacked fraction (the 0.0 baseline trials share the same
    # program — the mask content never changes the compiled footprint)
    att = jnp.stack([
        jnp.asarray(attacker_cohort(params.n, 0.1, seed=s))
        for s in range(trials)])
    shared = {k: a[k] for k in ("conns", "rev", "out_mask")}
    return TraceSpec(
        fn=sharded_attack_window,
        args=(stacked, shared, att),
        kwargs=dict(params=params,
                    adv=AdversaryParams(scenario="sybil_graft_flood"),
                    steps=steps, trial_mesh=mesh,
                    local_trials=local_trials))


def _dcn_audit_shape() -> tuple[int, int]:
    """(dcn blocks, per-block trial groups) for the 3-level audit mesh,
    degrading with the host's device count the way audit_trial_groups
    does: 2x2x2 under the CI 8-device grid, 2x2x1 under the 4-device lint
    gate, 2x1x1 at two devices, 1x1x1 on a single device."""
    import jax

    nd = len(jax.devices())
    dcn = 2 if nd >= 2 else 1
    groups = 2 if nd // dcn >= 2 else 1
    return dcn, groups


def _dcn_block_devices() -> int:
    """Per-process device count on the canonical 3-level audit mesh — the
    GA-S006 blocking the contract declares (process-major device order
    makes partition_id // block the dcn index)."""
    import jax

    dcn, _groups = _dcn_audit_shape()
    return len(jax.devices()) // dcn


def _dcn_attack_window_spec() -> TraceSpec:
    import jax
    import jax.numpy as jnp

    from ..ops.adversary import AdversaryParams, attacker_cohort
    from ..parallel.sharding import make_dcn_mesh
    from ..runtime.campaign import sharded_attack_window

    # the three-level placement contract: the SAME nested window program the
    # campaign dispatches per process, traced single-process on the full
    # dcn x trials x peers mesh so GA-S006 can statically prove no
    # peer-axis collective ever crosses a dcn block boundary
    g, params, state, a, _ = _single_topic()
    dcn, groups = _dcn_audit_shape()
    mesh = make_dcn_mesh(dcn=dcn, trial_groups=groups)
    local = 2
    trials = dcn * groups * local
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.stack([jnp.asarray(x)] * trials), state)
    att = jnp.stack([
        jnp.asarray(attacker_cohort(params.n, 0.25, seed=s))
        for s in range(trials)])
    shared = {k: a[k] for k in ("conns", "rev", "out_mask")}
    return TraceSpec(
        fn=sharded_attack_window,
        args=(stacked, shared, att),
        kwargs=dict(params=params, adv=AdversaryParams(), steps=3,
                    trial_mesh=mesh, local_trials=local))


def arena_rung_spec(n: int, *, steps: int = 20, connect_to: int = 10,
                    local_trials: int = 2,
                    trial_groups: int | None = None) -> TraceSpec:
    """The arena ladder program at an arbitrary peer count: the sharded
    episub attack window (protocol/arena_window) on the config-8 grid
    shape, with the EpisubCtrl carry stacked alongside SimState. The rung
    predictor lowers THIS spec the same way it lowers attack_rung_spec, so
    the per-leaf power-law fits learn the `[...].hops/parent/reparents`
    leaves and the ROADMAP's arena-at-1M question gets the same
    compile-time fits / does-not-fit answer as the GossipSub window."""
    import jax
    import jax.numpy as jnp

    from ..ops.adversary import (AdaptivePolicy, AdversaryParams,
                                 attacker_cohort)
    from ..ops.episub import EpisubParams, init_episub_ctrl
    from ..parallel.sharding import make_trial_mesh
    from ..runtime.campaign import sharded_episub_window

    g, params, state, a, _ = _single_topic(n=n, connect_to=connect_to,
                                           **_ARMED)
    groups = 2 if trial_groups is None else trial_groups
    mesh = make_trial_mesh(groups)
    trials = groups * local_trials
    stack = lambda x: jnp.stack([jnp.asarray(x)] * trials)  # noqa: E731
    stacked = jax.tree_util.tree_map(stack, state)
    ctrls = jax.tree_util.tree_map(stack, init_episub_ctrl(params.n))
    att = jnp.stack([
        jnp.asarray(attacker_cohort(params.n, 0.1, seed=s))
        for s in range(trials)])
    shared = {k: a[k] for k in ("conns", "rev", "out_mask")}
    adv = AdversaryParams(scenario="sybil_graft_flood",
                          adaptive=AdaptivePolicy(enabled=True))
    return TraceSpec(
        fn=sharded_episub_window,
        args=(stacked, ctrls, shared, att),
        kwargs=dict(params=params, ep=EpisubParams(root=3), adv=adv,
                    steps=steps, trial_mesh=mesh,
                    local_trials=local_trials))


def _telemetry_spec() -> TraceSpec:
    from ..ops.telemetry import TelemetryParams, run_recorded_heartbeats

    # armed score params so tel_graylisted_frac / tel_score_q exercise the
    # deferred-decay reconstruction against live thresholds
    g, params, state, a, _ = _single_topic(**_ARMED)
    return TraceSpec(
        fn=run_recorded_heartbeats,
        args=(state, a["conns"], a["rev"], a["out_mask"]),
        kwargs=dict(params=params, steps=4,
                    telemetry=TelemetryParams(record=True)))


def _telemetry_attack_spec() -> TraceSpec:
    import jax.numpy as jnp

    from ..ops.adversary import (AdversaryParams, attacker_cohort,
                                 run_attacked_heartbeats)
    from ..ops.telemetry import TelemetryParams

    g, params, state, a, _ = _single_topic(**_ARMED)
    att = jnp.asarray(attacker_cohort(params.n, 0.25, seed=1))
    return TraceSpec(
        fn=run_attacked_heartbeats,
        args=(state, a["conns"], a["rev"], a["out_mask"], att),
        kwargs=dict(params=params, adv=AdversaryParams(), steps=4,
                    telemetry=TelemetryParams(record=True)))


def _kad_spec() -> TraceSpec:
    import jax.numpy as jnp

    from ..ops import kad

    g, params, state, a, (stage, lat, bw) = _single_topic()
    st = kad.init_kad_state(params.n, seed=0)
    origins = jnp.arange(4, dtype=jnp.int32)
    return TraceSpec(
        fn=kad.find_node,
        args=(st, origins, st.keys[origins], stage, lat),
        kwargs=dict(rounds=3))


@functools.lru_cache(maxsize=None)
def _multitopic_sim():
    from ..config.topology import TopoParams
    from ..runtime.multitopic import MultiTopicConfig, MultiTopicSimulator

    cfg = MultiTopicConfig(
        topo=TopoParams(network_size=16, anchor_stages=1),
        topics=("a", "b"), connect_to=3)
    return MultiTopicSimulator(cfg)


def _multitopic_spec() -> TraceSpec:
    from ..ops.disseminate import disseminate

    sim = _multitopic_sim()
    return TraceSpec(
        fn=disseminate,
        args=(sim.state, sim.arrays["conns"], sim.arrays["rev"], sim._stage,
              sim._lat, sim._bw),
        kwargs=dict(publisher=16 + 3, t0_ms=0.0, params=sim.params,
                    payload_bytes=500, lat_edge=sim._lat_edge,
                    ans_tables=sim._ans_tables))


def _disseminate_ladder() -> list[LadderRung]:
    """Miniature of the bench ladder's aval families: three network sizes
    plus a REPEAT of the first — 4 rungs must produce exactly 3 compile
    keys (distinct sizes split, identical configs collapse)."""
    rungs = []
    for name, n, ct in (("rung-16", 16, 3), ("rung-32", 32, 4),
                        ("rung-64", 64, 5), ("rung-16-again", 16, 3)):
        g, params, state, a, (stage, lat, bw) = _single_topic(
            n=n, connect_to=ct)
        rungs.append(LadderRung(
            name=name, statics=(params, 15000),
            dynamic=(state, a["conns"], a["rev"], stage, lat, bw, 3, 0.0)))
    return rungs


def _new_state_of(out):
    return out[1]


def _state_arg_of(spec):
    return spec.args[0]


def _first_out(out):
    return out[0]


def _checkify_heartbeat() -> None:
    """Runtime half of the heartbeat contract: from the canonical warm mesh,
    one scan keeps D_lo <= |mesh| <= D_hi for every live peer."""
    import jax.numpy as jnp
    from jax.experimental import checkify

    from ..ops.heartbeat import run_heartbeats

    g, params, state, a, _ = _single_topic()

    def prog(state):
        s = run_heartbeats(state, a["conns"], a["rev"], a["out_mask"],
                           params, 8)
        deg = s.mesh_mask.sum(axis=-1)
        checkify.check(
            jnp.all((deg >= params.d_low) & (deg <= params.d_high)),
            "mesh degree left [D_lo, D_hi]")
        checkify.check(
            jnp.all(s.fmd >= 0.0), "score decay went negative")
        return s

    err, _ = checkify.checkify(prog)(state)
    err.throw()


def _checkify_repair() -> None:
    """Runtime half of the recovery contract: after a repair window the
    reverse-slot involution still holds over the MUTATED graph — every
    committed dial extended conns/rev consistently on both sides — and the
    repair counters are consistent (a PX graft is a graft)."""
    import jax.numpy as jnp
    from jax.experimental import checkify

    from ..ops.adversary import attacker_cohort
    from ..ops.heartbeat import run_heartbeats
    from ..ops.repair import run_recovery_heartbeats

    g, params, state, a, _ = _single_topic(**_REPAIR)
    state = run_heartbeats(state, a["conns"], a["rev"], a["out_mask"],
                           params, 8)
    att = jnp.asarray(attacker_cohort(params.n, 0.25, seed=1))
    # force repair activity: pre-starve by evicting the attacker edges via
    # a hostile penalty so the dial path actually runs under the check
    state = state.replace(slow_penalty=jnp.where(
        att[jnp.clip(a["conns"], 0)] & (a["conns"] >= 0),
        jnp.float32(100.0), state.slow_penalty))
    (s2, cn, rv, om), _obs = run_recovery_heartbeats(
        state, a["conns"], a["rev"], a["out_mask"], att, params,
        steps=8, publisher=3)

    def prog(cn, rv, px_grafts, redials, grafts0, grafts1):
        me = jnp.arange(cn.shape[0], dtype=cn.dtype)[:, None]
        back = cn[jnp.clip(cn, 0), rv]
        checkify.check(
            jnp.all(jnp.where(cn >= 0, back == me, True)),
            "reverse-slot involution broken after repair window")
        checkify.check(
            jnp.all(rv >= 0) & jnp.all(rv < cn.shape[1]),
            "rev slot out of range after repair window")
        checkify.check(
            (px_grafts + redials).sum() <= (grafts1 - grafts0).sum() * 2 + 1,
            "repair counters inconsistent with graft accounting")
        return cn

    err, _ = checkify.checkify(prog)(
        cn, rv, s2.px_grafts, s2.redials, state.grafts, s2.grafts)
    err.throw()


def _checkify_disseminate() -> None:
    """Runtime half of the publish contract: delays are non-negative where
    received, and the bounded-mode wait bar is finite (json-safe)."""
    import jax.numpy as jnp
    from jax.experimental import checkify

    from ..ops.disseminate import disseminate
    from ..ops.heartbeat import run_heartbeats

    g, params, state, a, (stage, lat, bw) = _single_topic()
    state = run_heartbeats(state, a["conns"], a["rev"], a["out_mask"],
                           params, 8)

    # checkify cannot trace the fixpoint's batched while-loop
    # (checkify-of-vmap-of-while is unsupported), so run the publish
    # concretely and checkify only the assertions over its outputs.
    res, _s2 = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=3,
        t0_ms=0.0, params=params, payload_bytes=15000)

    def prog(received, delay_ms, answer_wait_max_ms):
        checkify.check(
            jnp.all(jnp.where(received, delay_ms, 0.0) >= 0.0),
            "negative dissemination delay")
        checkify.check(
            jnp.isfinite(answer_wait_max_ms),
            "non-finite answer wait bar (would poison strict JSON)")
        return received

    err, _ = checkify.checkify(prog)(
        res.received, res.delay_ms, res.answer_wait_max_ms)
    err.throw()


def default_contracts() -> list[EntrypointContract]:
    return [
        EntrypointContract(
            name="disseminate/cold",
            build=lambda: _disseminate_spec(),
            expected_conds=2,
            donate=(0,),
            ladder=_disseminate_ladder,
            expected_compile_keys=3,
            feedback=[(_new_state_of, _state_arg_of)],
            runtime_check=_checkify_disseminate,
            notes="serialized-answer repair branch must stay a real cond, "
                  "and the prefix-certificate fallback to the legacy serial "
                  "refiner must stay a NESTED cond inside it (the untaken "
                  "serial branch costs compile only — converting either to "
                  "select_n would run the serial refiner on every publish)"),
        EntrypointContract(
            name="disseminate/warm",
            build=lambda: _disseminate_spec(warm_start=True),
            expected_conds=3,
            feedback=[(_new_state_of, _state_arg_of)],
            notes="repair + certificate fallback + cold-rerun guard all "
                  "survive"),
        EntrypointContract(
            name="disseminate/exact_serial",
            build=lambda: _disseminate_spec(answer_queue_mode="serial"),
            expected_conds=1,
            feedback=[(_new_state_of, _state_arg_of)],
            notes="the legacy serial refiner forced by static param — the "
                  "bit-equality reference the prefix engine is pinned "
                  "against (tests/test_exact_prefix.py); only the repair "
                  "branch survives, there is no nested fallback to trace"),
        EntrypointContract(
            name="disseminate/bounded",
            build=lambda: _disseminate_spec(serialize_answers=False),
            expected_conds=None,
            feedback=[(_new_state_of, _state_arg_of)],
            notes="cond-free by design; loop/carry rules still apply"),
        EntrypointContract(
            name="publisher/batch_scan",
            build=_publish_batch_spec,
            expected_conds=3,
            feedback=[(_new_state_of, _state_arg_of)],
            notes="the batched service dispatch (ISSUE 14): one scan over "
                  "stacked seed columns whose body is disseminate/cold — "
                  "its 2 conds must survive inside the scan body, plus the "
                  "per-column active-mask cond that makes padding to a "
                  "static batch width free (a select_n there would publish "
                  "the padding columns); the carried SimState must feed "
                  "back aval-stable so every pump round is a cache hit"),
        EntrypointContract(
            name="runs/disseminate",
            build=lambda: _runs_spec("disseminate"),
            expected_conds=2,
            feedback=[(_new_state_of, _state_arg_of)],
            notes="one message in every run of a batch (ISSUE 52): lax.map "
                  "of disseminate/cold over the runs' axis — its 2 conds "
                  "must survive inside the map's body (a vmap over runs "
                  "would turn them into select_n and run the serial "
                  "refiner in every run of every publish); the stacked "
                  "states must feed back aval-stable, a message after a "
                  "message"),
        EntrypointContract(
            name="runs/run_heartbeats",
            build=lambda: _runs_spec("_run_heartbeats"),
            expected_conds=4,
            feedback=[(_first_out, _state_arg_of)],
            notes="the batch's scan: lax.map of run_heartbeats over the "
                  "runs' axis, the step's 4 skips alive in the inner scan's "
                  "body"),
        EntrypointContract(
            name="heartbeat_step",
            build=lambda: _heartbeat_spec("heartbeat_step"),
            expected_conds=4,
            donate=(0,),
            notes="graft/prune/fanout/deg skips are the steady-state perf"),
        EntrypointContract(
            name="run_heartbeats",
            build=lambda: _heartbeat_spec("run_heartbeats"),
            expected_conds=4,
            donate=(0,),
            feedback=[(lambda out: out, _state_arg_of)],
            runtime_check=_checkify_heartbeat,
            notes="the simulator scan step; conds live inside the scan body"),
        EntrypointContract(
            name="run_attacked_heartbeats",
            build=_attack_spec,
            expected_conds=4,
            feedback=[(_first_out, _state_arg_of)],
            notes="UNBATCHED campaign window; the vmapped trial batch "
                  "intentionally elides these conds and is not registered"),
        EntrypointContract(
            name="adversary/adaptive_window",
            build=_adaptive_attack_spec,
            expected_conds=None,
            # the armed window widens the carry to (state, ctrl): BOTH feed
            # the next window — the controller estimate crosses the
            # attack -> recovery edge, so aval drift in either leaf
            # recompiles every campaign window
            feedback=[(lambda out: out[0][0], _state_arg_of),
                      (lambda out: out[0][1],
                       lambda spec: spec.kwargs["ctrl"])],
            # single-device program: any collective appearing in its
            # compiled HLO means a mesh leaked into the unbatched window
            collectives=frozenset(),
            hbm_budget_bytes=2 * 1024 * 1024,
            notes="the adaptive attacker controller in the scan (ISSUE 15): "
                  "repair leaves live so PX poison writes real px_pool "
                  "rows; disabled configs are intentionally NOT registered "
                  "here — they ARE run_attacked_heartbeats (same cache "
                  "entry), already audited above"),
        EntrypointContract(
            name="heartbeat_step/evict",
            build=lambda: _heartbeat_spec("heartbeat_step", **_REPAIR),
            expected_conds=6,
            donate=(0,),
            notes="opt-in repair branches: the 4 default skips plus the "
                  "eviction and PX-capture conds must SURVIVE (a select_n "
                  "here would pay both branches in the steady state)"),
        EntrypointContract(
            name="repair/recovery_window",
            build=_repair_spec,
            expected_conds=7,
            # the WHOLE carry feeds back: (state, conns, rev, out_mask) —
            # the dynamic graph is a loop-carried value, not a constant
            feedback=[(_first_out, lambda spec: spec.args[:4])],
            runtime_check=_checkify_repair,
            notes="recovery scan: 6 armed-heartbeat conds + the repair "
                  "controller's single action cond, all inside the scan "
                  "body; the graph arrays ride the carry"),
        EntrypointContract(
            name="faults/churn_window",
            build=_faults_spec,
            expected_conds=None,
            feedback=[(_first_out, _state_arg_of)],
            # the UNBATCHED single-device window: collective-free by
            # construction
            collectives=frozenset(),
            hbm_budget_bytes=2 * 1024 * 1024,
            notes="fault window with crash + partition + spike all armed "
                  "over an attacked mesh: the go-dark/restart and "
                  "freeze/thaw branches are window-scheduled lax.conds "
                  "inside the scan; state must feed back aval-stable so "
                  "retried trials resume from a checkpoint without a "
                  "recompile"),
        EntrypointContract(
            name="campaign/faulted_window_nested",
            build=_faulted_nested_spec,
            expected_conds=None,
            feedback=[(_first_out, _state_arg_of)],
            # explicit in/out_shardings force a fresh jit closure per
            # window: one compile per call by construction
            retrace_budget=1,
            # ~18 KiB/device measured at the audit shape: the fault masks
            # ride the same gathers as the attacker masks
            collectives=frozenset(
                {"all-gather", "all-reduce", "collective-permute"}),
            collective_bytes_budget=72 * 1024,
            hbm_budget_bytes=2 * 1024 * 1024,
            notes="the fault-armed nested window (sharded_faulted_window): "
                  "per-trial crash/side/spike cohorts shard over both grid "
                  "axes exactly like the attacker masks, so fault sweeps "
                  "ride the trials x peers grid instead of falling back to "
                  "the vmapped single-device stack; no repair leaf in the "
                  "state (the _ARMED params are repair-inert, as the "
                  "campaign's are), and the sharding auditor "
                  "pins the same collective-kind set as the attack window"),
        EntrypointContract(
            name="campaign/attack_window_sharded",
            build=_sharded_attack_spec,
            expected_conds=None,
            feedback=[(_first_out, _state_arg_of)],
            # the wrapper jits a fresh shard_map closure per call — one
            # compile per window by construction, never more
            retrace_budget=1,
            # trials are independent on the trial-only grid: no cross-
            # device traffic is ever legitimate in this program
            collectives=frozenset(),
            hbm_budget_bytes=2 * 1024 * 1024,
            # GA-S001 fires by design here: the legacy layout REPLICATES
            # the epoch graph across the trial groups (that is what makes
            # it the replicated-peer-submesh baseline the nested program
            # is measured against) — pinned, not fixed
            waivers=(("GA-S001",
                      "legacy nested=False layout replicates the shared "
                      "epoch graph (conns/rev) across trial groups by "
                      "design — it exists as the replicated-peer-submesh "
                      "equality baseline for the nested program "
                      "(docs/ARCHITECTURE.md §13)"),),
            notes="legacy trial-only shard_map (nested=False), no repair "
                  "leaf in the state — the replicated-peer-submesh baseline "
                  "the nested program is pinned against; the stacked state "
                  "must feed back aval-stable across windows, and "
                  "loop/carry rules catch dead weight the r05 way"),
        EntrypointContract(
            name="campaign/attack_window_nested",
            build=_nested_attack_spec,
            expected_conds=None,
            feedback=[(_first_out, _state_arg_of)],
            # explicit in/out_shardings force a fresh jit closure per
            # window: one compile per call by construction
            retrace_budget=1,
            # measured at the canonical audit shape (N=32, 8 devices):
            # ~16 KiB/device of collective output across the three kinds
            # the neighbor gathers + trial reductions legitimately insert;
            # budgets are ~4x ratchets, not estimates
            collectives=frozenset(
                {"all-gather", "all-reduce", "collective-permute"}),
            collective_bytes_budget=64 * 1024,
            hbm_budget_bytes=2 * 1024 * 1024,
            notes="the nested two-level pjit program the sharded sweep "
                  "actually dispatches: trials split over groups, peer "
                  "rows split over each group's submesh via explicit "
                  "in/out_shardings; same aval-stability and loop/carry "
                  "bars as the legacy baseline; the sharding auditor "
                  "additionally pins its collective kinds and byte/HBM "
                  "budgets (GA-S002..4) — a reduce-scatter or all-to-all "
                  "appearing here means the partitioner stopped seeing "
                  "the layout the grid was designed around"),
        EntrypointContract(
            name="campaign/attack_window_dcn",
            build=_dcn_attack_window_spec,
            expected_conds=None,
            feedback=[(_first_out, _state_arg_of)],
            # explicit in/out_shardings force a fresh jit closure per
            # window: one compile per call by construction
            retrace_budget=1,
            collectives=frozenset(
                {"all-gather", "all-reduce", "collective-permute"}),
            collective_bytes_budget=64 * 1024,
            hbm_budget_bytes=2 * 1024 * 1024,
            # GA-S006: on the 3-level mesh a dcn block is one process's
            # devices — device_count / dcn with make_dcn_mesh's defaults —
            # and the cross-DCN byte budget is literally zero: trials are
            # embarrassingly parallel across processes, every peer-axis
            # collective must stay inside one ICI block
            dcn_block_devices=_dcn_block_devices(),
            dcn_collective_bytes_budget=0,
            notes="the multi-host placement contract (ISSUE 20): the same "
                  "nested attack window traced on the three-level "
                  "dcn x trials x peers mesh, stacked trials split "
                  "(dcn, trials)-major and peer rows over each block's "
                  "submesh. GA-S006 parses every collective's replica "
                  "groups and proves zero bytes cross the dcn axis — the "
                  "static license for run_campaign(dcn=...) to execute "
                  "per-process on local submeshes (supervisor retries, "
                  "checkpoints, recovery all process-local) without "
                  "losing anything the global formulation would compute"),
        EntrypointContract(
            name="campaign/dht_attack_window",
            build=_dht_attack_window_spec,
            expected_conds=None,
            # the carry is (state, conns, rev, out_mask, pool): the state
            # feeds the next window's state slot and the consumed pool the
            # pool slot (the heal leg over stacked graphs is a separate
            # call form, not this entrypoint's feedback)
            feedback=[(lambda out: out[0][0], _state_arg_of),
                      (lambda out: out[0][4], lambda spec: spec.args[4])],
            # explicit in/out_shardings force a fresh jit closure per
            # window: one compile per call by construction (the second
            # heal leg traces its OWN closure over stacked graphs — a
            # separate entrypoint, not a retrace of this one)
            retrace_budget=1,
            # ~23 KiB/device measured at the audit shape: the redial path
            # gathers the poisoned (T, N, K) shortlists on top of the
            # attack window's own collectives
            collectives=frozenset(
                {"all-gather", "all-reduce", "collective-permute"}),
            collective_bytes_budget=96 * 1024,
            hbm_budget_bytes=2 * 1024 * 1024,
            notes="the cross-protocol recovery window: repair leaves LIVE "
                  "(the poisoned shortlist feeds the redial path), the "
                  "(T, N, K) discovery pools shard over both grid axes and "
                  "ride the scan carry; aval-stability across windows is "
                  "the bar — the heal leg must reuse the same program "
                  "shape with only the pool contents changed"),
        EntrypointContract(
            name="telemetry/recorded_heartbeats",
            build=_telemetry_spec,
            expected_conds=4,
            feedback=[(_first_out, _state_arg_of)],
            notes="flight recorder armed: the channel reductions ride the "
                  "obs stack without converting any steady-state skip to "
                  "select_n; state feeds back aval-stable so windowed "
                  "recording never recompiles"),
        EntrypointContract(
            name="telemetry/recorded_attack_window",
            build=_telemetry_attack_spec,
            expected_conds=4,
            feedback=[(_first_out, _state_arg_of)],
            notes="attack window with the recorder armed via the static "
                  "telemetry kwarg — same cond census as the bare window; "
                  "the tel_* channels are pure reductions"),
        EntrypointContract(
            name="kad/find_node",
            build=_kad_spec,
            feedback=[(lambda out: out[1], _state_arg_of)],
            notes="lookup scan: loop/carry rules only"),
        EntrypointContract(
            name="multitopic/disseminate",
            build=_multitopic_spec,
            expected_conds=2,
            feedback=[(_new_state_of, _state_arg_of)],
            notes="T*N block-diagonal stack keeps the single-topic conds"),
        EntrypointContract(
            name="conformance/differential_round",
            build=_conform_spec,
            expected_conds=4,
            feedback=[(lambda out: out, _state_arg_of)],
            notes="the compiled side of the spec-differential gate "
                  "(analysis/conformance.py): one heartbeat_step -> "
                  "adversary_round composition per round, audited here so "
                  "the program the conformance oracle certifies is the "
                  "same steady-state-skip program the runners scan (the 4 "
                  "heartbeat conds must survive; the returned state feeds "
                  "the next round aval-stable)"),
        EntrypointContract(
            name="episub/heartbeat_step",
            build=_episub_step_spec,
            expected_conds=1,
            feedback=[(lambda out: out[0], lambda spec: spec.args[0]),
                      (lambda out: out[1], lambda spec: spec.args[1])],
            collectives=frozenset(),
            hbm_budget_bytes=2 * 1024 * 1024,
            notes="the episub tree round (ops/episub.py, ARCHITECTURE §21): "
                  "eager push down the spanning tree + lazy IHAVE repair on "
                  "non-tree edges + graylist-gated re-parenting, all dense "
                  "masked ops — exactly one cond survives (the fmd/slow "
                  "decay gate shared with gossipsub's scorer); state and "
                  "ctrl both feed back aval-stable, and single-device "
                  "tracing must stay collective-free"),
        EntrypointContract(
            name="protocol/arena_window",
            build=_arena_window_spec,
            expected_conds=None,
            feedback=[(lambda out: out[0][0], lambda spec: spec.args[0]),
                      (lambda out: out[0][1], lambda spec: spec.args[1])],
            retrace_budget=1,
            collectives=frozenset({"all-gather", "all-reduce",
                                   "collective-permute"}),
            collective_bytes_budget=64 * 1024,
            hbm_budget_bytes=2 * 1024 * 1024,
            notes="the arena's sharded episub attack window "
                  "(runtime/campaign.py sharded_episub_window), nested "
                  "trial x group sharding like campaign/attack_window_"
                  "nested; ISSUE 19's 'retrace budget 0' reads as zero "
                  "EXTRA retraces — explicit in/out_shardings force one "
                  "fresh jit closure per window, the house budget for "
                  "every nested window (retrace_budget=1); state and ctrl "
                  "feed back aval-stable (actrl is window-internal, no "
                  "input slot), and per-trial collective traffic stays "
                  "under the attack-window byte budget"),
    ]
