"""Cross-publish warm-started fixpoints: results must be BIT-IDENTICAL to
cold starts, always.

The warm path seeds each publish's earliest-arrival relaxation from the
previous message's arrival offsets (SimState.warm_offset_ms, re-based to the
new publish time). A min-plus fixpoint iterated from above accepts ANY seed
that is >= the true solution — but a heuristic seed can undershoot (stale
carry after topology/subscription drift), and an undershot point is a stuck
point of the relaxation. The implementation therefore certifies the result:
at loop exit every non-publisher time must be SUPPORTED by its own incoming
offer matrix (t == max(inc.min, rx_const), with finite-but-unsupported
times counted as violations), and any violation triggers one whole-message
cold rerun (ops/disseminate.py `bad` / `_run_fast`).

The contract under test here is exactly that: warm_start=True is a pure
performance knob — delays, received masks, counters and gossip accounting
match the warm_start=False run bitwise, including after the carry is
invalidated (churn, resubscription) and even when the carry is adversarially
poisoned with an impossibly optimistic seed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dst_libp2p_test_node_tpu.config.topology import TopoParams, Topology
from dst_libp2p_test_node_tpu.runtime.simulator import ExperimentConfig, Simulator

WARM_INVALID = 1e30  # anything above this means "no usable carry"


def _cfg(warm, **kw):
    topo = TopoParams(
        network_size=200, anchor_stages=3, min_bandwidth=50, max_bandwidth=150,
        min_latency=40, max_latency=130, msg_size_bytes=2000, messages=2,
        delay_seconds=1.0,
    )
    base = dict(topo=topo, connect_to=6, warmup_s=5.0, seed=3, warm_start=warm)
    base.update(kw)
    return ExperimentConfig(**base)


def _run(cfg, publishers=(4, 5)):
    sim = Simulator(cfg)
    sim.warmup()
    recs = []
    for i, p in enumerate(publishers):
        if i:
            sim.advance(1500.0)
        recs.append(sim.publish(p, msg_size=2000))
    return sim, recs


def _assert_same(recs_a, recs_b):
    for a, b in zip(recs_a, recs_b):
        np.testing.assert_array_equal(a.received, b.received)
        # bitwise, not allclose: the certified warm run either keeps a seed
        # it PROVED is the fixpoint or reruns cold — there is no tolerance
        np.testing.assert_array_equal(a.delays_ms, b.delays_ms)
        np.testing.assert_array_equal(a.sends, b.sends)
        np.testing.assert_array_equal(a.copies_rx, b.copies_rx)


def test_warm_equals_cold_bounded():
    _, warm = _run(_cfg(True, serialize_answers=False))
    _, cold = _run(_cfg(False, serialize_answers=False))
    _assert_same(warm, cold)


def test_warm_equals_cold_exact():
    # the serialized-answer mode layers its repair on the same fixpoints;
    # the warm seed must not perturb the repair trigger either
    _, warm = _run(_cfg(True, serialize_answers=True))
    _, cold = _run(_cfg(False, serialize_answers=True))
    _assert_same(warm, cold)


def test_publish_writes_carry_and_second_message_still_matches():
    sim, _ = _run(_cfg(True), publishers=(4,))
    w = np.asarray(sim.state.warm_offset_ms)
    # the first publish reached everyone, so every peer has a finite carry
    assert (w < WARM_INVALID).all()
    # and the carry is an offset from the publish time, not an absolute clock
    assert w.max() < 1e5


def test_churn_invalidates_carry_and_results_stay_equal():
    # under churn the peer set drifts between publishes: the carry is
    # invalidated wholesale each heartbeat (ops/heartbeat.py) and the next
    # publish runs cold through the seed gate — no certificate gymnastics
    kw = dict(churn_down_per_hb=0.05, churn_up_per_hb=0.025,
              serialize_answers=False)
    # both messages through peer 4, the configuration's publisher_id: the
    # draw spares it; peer 5 is dead by the second publish on this seed,
    # and a publish through a dead peer raises
    simw, warm = _run(_cfg(True, **kw), publishers=(4, 4))
    simc, cold = _run(_cfg(False, **kw), publishers=(4, 4))
    _assert_same(warm, cold)
    # the last publish wrote a fresh carry; one churny heartbeat batch
    # later it must be back at the INF sentinel
    assert float(np.asarray(simw.state.warm_offset_ms).min()) < WARM_INVALID
    simw.advance(1500.0)
    assert float(np.asarray(simw.state.warm_offset_ms).min()) > WARM_INVALID


def test_set_subscribed_invalidates_carry():
    sim, _ = _run(_cfg(True), publishers=(4,))
    assert float(np.asarray(sim.state.warm_offset_ms).min()) < WARM_INVALID
    sub = np.asarray(sim.state.subscribed).copy()
    sub[7] = ~sub[7]
    sim.set_subscribed(sub)
    assert float(np.asarray(sim.state.warm_offset_ms).min()) > WARM_INVALID
    # a fresh cold sim driven through the same subscription change must
    # produce the identical next publish
    simc, _ = _run(_cfg(False), publishers=(4,))
    simc.set_subscribed(sub)
    a = sim.publish(4, msg_size=2000)
    b = simc.publish(4, msg_size=2000)
    _assert_same([a], [b])


def test_poisoned_carry_is_caught_by_the_certificate():
    # adversarial seed: a zero offset claims every peer hears the message
    # the instant it is published — impossibly optimistic, and exactly the
    # stuck-point shape a naive warm start would silently keep. The
    # certificate must reject it and the cold rerun must restore equality.
    from dst_libp2p_test_node_tpu.ops.disseminate import disseminate
    from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph
    from dst_libp2p_test_node_tpu.ops.heartbeat import run_heartbeats
    from dst_libp2p_test_node_tpu.ops.state import (
        SimParams, graph_arrays, init_state,
    )
    import dataclasses

    g = build_connection_graph(120, 6, seed=2)
    params = SimParams(n=120, capacity=g.capacity, serialize_answers=False,
                       warm_start=True)
    a = graph_arrays(g)
    t = Topology.build(TopoParams(network_size=120, anchor_stages=2))
    topo = (jnp.asarray(t.stage_of_peer), jnp.asarray(t.latency_ms),
            jnp.asarray(t.bw_up_mbit))
    s = init_state(params, seed=2)
    s = run_heartbeats(s, a["conns"], a["rev"], a["out_mask"], params, 8)
    s = s.replace(warm_offset_ms=jnp.zeros((120,), jnp.float32))

    res_w, _ = disseminate(s, a["conns"], a["rev"], *topo, publisher=0,
                           t0_ms=float(s.t_ms), params=params,
                           payload_bytes=15000, with_gossip=True)
    res_c, _ = disseminate(
        s, a["conns"], a["rev"], *topo, publisher=0, t0_ms=float(s.t_ms),
        params=dataclasses.replace(params, warm_start=False),
        payload_bytes=15000, with_gossip=True)
    np.testing.assert_array_equal(np.asarray(res_w.delay_ms),
                                  np.asarray(res_c.delay_ms))
    np.testing.assert_array_equal(np.asarray(res_w.received),
                                  np.asarray(res_c.received))
    assert bool(np.asarray(res_w.converged))


def test_result_exposes_convergence_and_interleave_fields():
    from dst_libp2p_test_node_tpu.ops.disseminate import disseminate
    from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph
    from dst_libp2p_test_node_tpu.ops.heartbeat import run_heartbeats
    from dst_libp2p_test_node_tpu.ops.state import (
        SimParams, graph_arrays, init_state,
    )

    g = build_connection_graph(120, 6, seed=2)
    params = SimParams(n=120, capacity=g.capacity, serialize_answers=False)
    a = graph_arrays(g)
    t = Topology.build(TopoParams(network_size=120, anchor_stages=2))
    topo = (jnp.asarray(t.stage_of_peer), jnp.asarray(t.latency_ms),
            jnp.asarray(t.bw_up_mbit))
    s = init_state(params, seed=2)
    s = run_heartbeats(s, a["conns"], a["rev"], a["out_mask"], params, 8)
    res, _ = disseminate(s, a["conns"], a["rev"], *topo, publisher=0,
                         t0_ms=float(s.t_ms), params=params,
                         payload_bytes=15000, with_gossip=True)
    # converged: every fragment's fixpoint reached self-consistency under
    # the iteration cap (the old code threw this bit away inside the loop)
    assert bool(np.asarray(res.converged))
    # bounded-mode error bar is ALWAYS finite; the interleaved-rounds
    # corner is a separate count, not an INF poison on the bar
    wait = float(np.asarray(res.answer_wait_max_ms))
    assert np.isfinite(wait) and wait >= 0.0
    assert int(np.asarray(res.answer_interleaved)) >= 0


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
@pytest.mark.skipif(not hasattr(jax, "shard_map"),
                    reason="jax.shard_map unavailable in this environment")
def test_sharded_warm_equals_cold_and_single_device():
    # the carry must survive the shard_map path: the sharded warm run, the
    # sharded cold run and the single-device run all agree
    from dst_libp2p_test_node_tpu.parallel.sharding import make_peer_mesh

    def run(warm, mesh):
        sim = Simulator(_cfg(warm, serialize_answers=False), mesh=mesh)
        sim.warmup()
        r1 = sim.publish(4, msg_size=2000)
        sim.advance(1500.0)
        r2 = sim.publish(5, msg_size=2000)
        return [r1, r2]

    warm_sh = run(True, make_peer_mesh(8))
    cold_sh = run(False, make_peer_mesh(8))
    cold_1d = run(False, None)
    _assert_same(warm_sh, cold_sh)
    for a, b in zip(warm_sh, cold_1d):
        np.testing.assert_array_equal(a.received, b.received)
        np.testing.assert_allclose(a.delays_ms, b.delays_ms, rtol=1e-5)
