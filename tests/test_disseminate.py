import hashlib
import json
import os

import numpy as np
import pytest
import jax.numpy as jnp

from dst_libp2p_test_node_tpu.config.topology import Topology, TopoParams
from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph
from dst_libp2p_test_node_tpu.ops.heartbeat import run_heartbeats
from dst_libp2p_test_node_tpu.ops.disseminate import disseminate
from dst_libp2p_test_node_tpu.ops.state import SimParams, init_state, graph_arrays


def path_graph(n):
    """0-1-2-...-(n-1) line: peer i dials i+1; the tail re-dials its
    predecessor (dedup keeps a single edge)."""
    dials = np.arange(1, n + 1).reshape(n, 1)
    dials[-1, 0] = n - 2
    return build_connection_graph(n, 1, seed=0, dials=dials, max_degree=4)


def single_stage_topo(n, payload=15000):
    t = Topology.build(TopoParams(network_size=n, anchor_stages=1))
    return (
        jnp.asarray(t.stage_of_peer),
        jnp.asarray(t.latency_ms),
        jnp.asarray(t.bw_up_mbit),
    )


def test_path_graph_exact_latency():
    n, payload = 5, 15000
    g = path_graph(n)
    stage, lat, bw = single_stage_topo(n)
    params = SimParams(n=n, capacity=g.capacity, d=2, d_low=1, d_high=3,
                       max_relax_iters=16)
    state = init_state(params, seed=1)
    state = state.replace(mesh_mask=jnp.asarray(g.conns >= 0))
    res, _ = disseminate(
        state, jnp.asarray(g.conns), jnp.asarray(g.rev), stage, lat, bw,
        publisher=0, t0_ms=0.0, params=params, payload_bytes=payload,
        with_gossip=False,
    )
    # single stage: L = self-loop latency = 100 ms; tx = 15000*8/50e6*1e3 = 2.4
    L, tx, proc = 100.0, 2.4, params.proc_delay_ms
    # each intermediate hop forwards only onward (back-edge excluded -> rank 0);
    # 15 KB exceeds the ~14.6 KB initial window: 2 slow-start flights, so the
    # data traversal costs L * (1 + 2*(flights-1)) = 3L
    hop = proc + tx + 3.0 * L
    delays = np.asarray(res.delay_ms)
    expect = np.array([0.0] + [hop * h for h in range(1, n)])
    np.testing.assert_allclose(delays, expect, rtol=1e-5)
    assert bool(res.received.all())


def test_star_uplink_serialization():
    # publisher 0 dials 1..k: receiver ranks serialize on 0's uplink, so the
    # sorted delays are exactly proc + L + tx*{1..k}
    n, k = 9, 8
    dials = np.zeros((n, 1), dtype=np.int64)
    dials[0, 0] = 1  # deduped against 1->0
    g = build_connection_graph(n, 1, seed=0,
                               dials=np.vstack([np.full((1, 1), 1), np.zeros((n - 1, 1), dtype=np.int64)]),
                               max_degree=n)
    stage, lat, bw = single_stage_topo(n)
    params = SimParams(n=n, capacity=g.capacity)
    state = init_state(params, seed=2)
    state = state.replace(mesh_mask=jnp.asarray(g.conns >= 0))
    res, _ = disseminate(
        state, jnp.asarray(g.conns), jnp.asarray(g.rev), stage, lat, bw,
        publisher=0, t0_ms=0.0, params=params, payload_bytes=15000,
        with_gossip=False,
    )
    delays = np.sort(np.asarray(res.delay_ms)[1:])
    # 3*L: the 15 KB copy needs 2 slow-start flights (+1 RTT on the wire)
    expect = params.proc_delay_ms + 300.0 + 2.4 * np.arange(1, k + 1)
    np.testing.assert_allclose(delays, expect, rtol=1e-5)


def test_gossip_answer_serialization_exact():
    # star: publisher 0 connected to 1..k; EMPTY mesh and no flood, so the
    # only path is gossip round 0: every receiver lacks at the IHAVE, all k
    # IWANT back, and the answers must serialize BACK-TO-BACK on 0's uplink
    # (sum, not max): sorted delays = tick + 2L (control) + (i+1)*tx
    # + 3L (answer data: 2 cold slow-start flights), i = 0..k-1.
    n, k = 9, 8
    g = build_connection_graph(
        n, 1, seed=0,
        dials=np.vstack([np.full((1, 1), 1),
                         np.zeros((n - 1, 1), dtype=np.int64)]),
        max_degree=n)
    stage, lat, bw = single_stage_topo(n)
    params = SimParams(n=n, capacity=g.capacity, d_lazy=16,
                       flood_publish=False, max_relax_iters=16)
    state = init_state(params, seed=3)
    state = state.replace(
        mesh_mask=jnp.zeros_like(state.mesh_mask),
        hb_phase=jnp.full((n,), 250.0, jnp.float32),
    )
    res, s2 = disseminate(
        state, jnp.asarray(g.conns), jnp.asarray(g.rev), stage, lat, bw,
        publisher=0, t0_ms=0.0, params=params, payload_bytes=15000,
        with_gossip=True,
    )
    assert bool(np.asarray(res.received).all())
    delays = np.sort(np.asarray(res.delay_ms)[1:])
    L, tx = 100.0, 2.4
    expect = 250.0 + 2.0 * L + tx * np.arange(1, k + 1) + 3.0 * L
    np.testing.assert_allclose(delays, expect, rtol=1e-5)
    # one answered IWANT per receiver, all served by the publisher
    assert int(np.asarray(res.iwant_sent).sum()) == k
    # the uplink write-back carries the serialized drain: tick + 2L + k*tx
    up = np.asarray(s2.uplink_free_ms)
    np.testing.assert_allclose(up[0], 250.0 + 200.0 + k * tx, rtol=1e-5)


def mesh_setup(*, n=100, connect_to=10, seed=0, hb=10, **over):
    g = build_connection_graph(n, connect_to, seed=seed)
    params = SimParams(n=n, capacity=g.capacity, **over)
    state = init_state(params, seed=seed)
    a = graph_arrays(g)
    state = run_heartbeats(state, a["conns"], a["rev"], a["out_mask"], params, hb)
    t = Topology.build(
        TopoParams(network_size=n, anchor_stages=5, min_bandwidth=50,
                   max_bandwidth=150, min_latency=40, max_latency=130)
    )
    topo = (jnp.asarray(t.stage_of_peer), jnp.asarray(t.latency_ms),
            jnp.asarray(t.bw_up_mbit))
    return g, params, state, a, topo


def test_edge_tables_precompute_equals_in_call_fallback():
    # the Simulator precomputes the stage-pair tables once per experiment
    # (r4 perf); a direct call computes them in-call — same sampled plan
    # (identical key consumption), so results must be IDENTICAL, with and
    # without loss
    from dst_libp2p_test_node_tpu.ops.disseminate import edge_tables

    g, params, state, a, (stage, lat, bw) = mesh_setup(seed=6)
    loss = jnp.full((6, 6), 0.2, jnp.float32)
    lat_edge, loss_edge = edge_tables(stage, lat, a["conns"], a["rev"], loss)
    for ls, le in ((None, None), (loss, loss_edge)):
        r_fall, s_fall = disseminate(
            state, a["conns"], a["rev"], stage, lat, bw, publisher=3,
            t0_ms=float(state.t_ms), params=params, payload_bytes=15000,
            with_gossip=True, loss_stage=ls)
        r_pre, s_pre = disseminate(
            state, a["conns"], a["rev"], stage, lat, bw, publisher=3,
            t0_ms=float(state.t_ms), params=params, payload_bytes=15000,
            with_gossip=True, loss_stage=ls, lat_edge=lat_edge,
            loss_edge=(le if ls is not None else None))
        np.testing.assert_array_equal(
            np.asarray(r_fall.received), np.asarray(r_pre.received))
        np.testing.assert_array_equal(
            np.asarray(r_fall.delay_ms), np.asarray(r_pre.delay_ms))
        np.testing.assert_array_equal(
            np.asarray(s_fall.uplink_free_ms), np.asarray(s_pre.uplink_free_ms))


def test_full_coverage_100_peers():
    g, params, state, a, (stage, lat, bw) = mesh_setup()
    res, s2 = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw,
        publisher=4, t0_ms=float(state.t_ms), params=params,
        payload_bytes=15000,
    )
    assert bool(res.received.all()), f"coverage {int(res.received.sum())}/100"
    delays = np.asarray(res.delay_ms)
    assert delays[4] == 0.0
    others = np.delete(delays, 4)
    assert (others > 0).all()
    # sane for 40-130 ms links with +1 slow-start RTT per 15 KB data hop
    assert others.max() < 4000.0, others.max()
    assert others.min() >= 40.0  # can't beat the fastest link latency


def test_bytes_conserved_and_duplicates():
    g, params, state, a, (stage, lat, bw) = mesh_setup()
    res, s2 = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw,
        publisher=0, t0_ms=float(state.t_ms), params=params,
        payload_bytes=15000,
    )
    # every copy sent is a copy received somewhere
    assert int(res.sends.sum()) == int(res.copies_rx.sum())
    # receivers (minus publisher) got >= 1 copy; duplicates are the overhead
    copies = np.asarray(res.copies_rx)
    assert (copies[1:] >= 1).all()
    assert float(s2.bytes_tx.sum()) == float(s2.bytes_rx.sum())
    assert int(s2.dup_rx.sum()) >= 0


def test_gossip_only_dissemination():
    # empty mesh + no flood: only IHAVE/IWANT at heartbeat ticks can carry the
    # message. Coverage must still happen, at heartbeat-scale delays.
    g, params, state, a, (stage, lat, bw) = mesh_setup(
        flood_publish=False, max_relax_iters=64,
    )
    state = state.replace(mesh_mask=jnp.zeros_like(state.mesh_mask))
    res, s2 = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw,
        publisher=0, t0_ms=float(state.t_ms), params=params,
        payload_bytes=15000, with_gossip=True,
    )
    cov = int(res.received.sum())
    assert cov > 90, cov
    others = np.asarray(res.delay_ms)[np.asarray(res.received)]
    others = others[others > 0]
    # gossip is quantized to heartbeats: visibly slower than mesh forwarding
    assert np.median(others) > 500.0
    assert int(np.asarray(res.ihave_sent).sum()) > 0
    assert int(np.asarray(res.iwant_sent).sum()) > 0
    # conservation across the involution: every IWANT somebody sent was
    # received by the peer that gossiped (per-peer counters, both directions)
    assert int(np.asarray(s2.iwant_tx).sum()) == int(np.asarray(s2.iwant_rx).sum())
    assert int(np.asarray(s2.ihave_tx).sum()) == int(np.asarray(s2.ihave_rx).sum())


def test_full_mcache_window_ihave_totals_hand_computed():
    # The reference keeps IHAVEing a message at EVERY heartbeat of the
    # mcache gossip window (history_gossip ticks, nim-libp2p defaults via
    # main.nim; counted per entry by metrics.go RecvRPC). Mesh coverage
    # completes in well under one heartbeat, so nearly all of that control
    # traffic happens AFTER dissemination is complete — the engine must
    # still count the full window. Hand-computed expectation: every holder
    # emits min(|candidates|, ceil(max(D_lazy, factor*|candidates|)))
    # IHAVEs per window round, candidates = connected non-mesh topic peers.
    g, params, state, a, (stage, lat, bw) = mesh_setup()
    res, s2 = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw,
        publisher=0, t0_ms=float(state.t_ms), params=params,
        payload_bytes=15000, with_gossip=True,
    )
    assert bool(np.asarray(res.received).all())   # every peer is a holder
    conns = np.asarray(a["conns"])
    mesh = np.asarray(state.mesh_mask)
    valid = conns >= 0                 # everyone alive & subscribed here
    tgt = mesh & valid
    tgt[0] = valid[0]                  # flood publisher targets all peers
    n_cand = (valid & ~tgt).sum(axis=-1)
    g_count = np.maximum(float(params.d_lazy), params.gossip_factor * n_cand)
    sel = np.minimum(n_cand, np.ceil(g_count - 1e-6).astype(np.int64))
    expected = params.history_gossip * int(sel.sum())
    got = int(np.asarray(s2.ihave_tx).sum())
    assert got == expected, (got, expected)
    # and the involution conserves them
    assert got == int(np.asarray(s2.ihave_rx).sum())


def test_idontwant_counters():
    g, params, state, a, (stage, lat, bw) = mesh_setup()
    # large message: every RECEIVER announces IDONTWANT to its mesh members
    # except the one it received from; the publisher announces nothing
    res, s2 = disseminate(state, a["conns"], a["rev"], stage, lat, bw,
                          publisher=0, t0_ms=float(state.t_ms),
                          params=params, payload_bytes=15000)
    tx = np.asarray(s2.idontwant_tx)
    rx = np.asarray(s2.idontwant_rx)
    assert tx.sum() > 0 and tx.sum() == rx.sum()   # conservation
    assert tx[0] == 0                              # publisher receives nothing
    mesh_deg = np.asarray(state.mesh_mask).sum(-1)
    # each receiver: mesh degree, minus 1 when its first sender is one of
    # its mesh members (the flood publisher may deliver over a non-mesh edge)
    diff = mesh_deg[1:] - tx[1:]
    assert ((diff == 0) | (diff == 1)).all()
    assert (diff == 1).any()
    # small message: below the v1.2 threshold no IDONTWANT is sent
    _, s3 = disseminate(state, a["conns"], a["rev"], stage, lat, bw,
                        publisher=0, t0_ms=float(state.t_ms),
                        params=params, payload_bytes=500)
    assert int(np.asarray(s3.idontwant_tx).sum()) == 0


def test_multi_round_gossip_recovers_lossy_edges():
    # 20% per-edge message loss, gossip-only transport (empty mesh, no
    # flood): the mcache window re-samples IHAVE targets every heartbeat
    # (history_gossip rounds), so edges missed or lost in round 1 get fresh
    # chances — coverage must beat the single-round model.
    loss = jnp.full((6, 6), 0.2, jnp.float32)
    cov = {}
    for w in (1, 3):
        tot = 0
        for seed in range(3):
            g, params, state, a, (stage, lat, bw) = mesh_setup(
                seed=seed, flood_publish=False, max_relax_iters=64,
                history_gossip=w,
            )
            state = state.replace(mesh_mask=jnp.zeros_like(state.mesh_mask))
            res, _ = disseminate(
                state, a["conns"], a["rev"], stage, lat, bw,
                publisher=0, t0_ms=float(state.t_ms), params=params,
                payload_bytes=15000, with_gossip=True, loss_stage=loss,
                loss_mode="message",
            )
            tot += int(res.received.sum())
        cov[w] = tot
    assert cov[3] > cov[1], cov


def test_loss_draws_are_per_fragment():
    # each fragment is a distinct GossipSub message upstream (the fragment
    # byte flips the msgId hash, main.nim:177-179), so loss must be drawn
    # independently per (fragment, edge) — correlated draws would black
    # out every fragment of a message on an unlucky edge at once
    g, params, state, a, (stage, lat, bw) = mesh_setup(seed=9)
    loss = jnp.full((6, 6), 0.3, jnp.float32)
    _, _, plan = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=0,
        t0_ms=float(state.t_ms), params=params, payload_bytes=15000,
        fragments=3, with_gossip=True, loss_stage=loss,
        loss_mode="message", return_plan=True)
    surv = np.asarray(plan["survive"])
    assert surv.shape[0] == 3
    # the three fragments' draws differ on real edges
    real = np.asarray(a["conns"]) >= 0
    assert (surv[0][real] != surv[1][real]).any()
    assert (surv[1][real] != surv[2][real]).any()

    # tcp mode: the retransmission stalls are per fragment too (distinct
    # static loss_mode => its own jit cache entry, no eviction needed)
    _, _, plan_t = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=0,
        t0_ms=float(state.t_ms), params=params, payload_bytes=15000,
        fragments=3, with_gossip=True, loss_stage=loss,
        loss_mode="tcp", return_plan=True)
    retx = np.asarray(plan_t["retx_ms"])
    assert retx.shape[0] == 3
    assert ((retx[0] > 0) != (retx[1] > 0)).any()


def test_lost_tx_counter_verifies_negligibility_claim():
    # r4 advisor: the tcp-mode "abandonment is negligible" claim must be
    # verifiable from a counter, not trusted. At per-edge loss p, a tcp
    # copy is abandoned with prob p^(MAX_RETRIES+1); message mode loses
    # the copy outright with prob p — the counter must show both.
    from dst_libp2p_test_node_tpu.ops.disseminate import MAX_RETRIES

    loss = 0.5
    g, params, state, a, (stage, lat, bw) = mesh_setup(seed=11)
    ls = jnp.full((6, 6), loss, jnp.float32)
    out = {}
    for mode in ("tcp", "message"):
        res, _ = disseminate(
            state, a["conns"], a["rev"], stage, lat, bw, publisher=0,
            t0_ms=float(state.t_ms), params=params, payload_bytes=15000,
            with_gossip=True, loss_stage=ls, loss_mode=mode)
        out[mode] = (int(np.asarray(res.lost_tx).sum()),
                     int(np.asarray(res.sends).sum()))
    lost_t, sent_t = out["tcp"]
    lost_m, sent_m = out["message"]
    # message mode: about p of all transmitted copies are lost
    assert 0.35 <= lost_m / sent_m <= 0.65, (lost_m, sent_m)
    # tcp mode: only deep-backoff abandonment (p^7 ~ 0.8% at p=0.5) —
    # a generous band around the expectation, but far below message mode
    exp = loss ** (MAX_RETRIES + 1)
    assert lost_t / sent_t <= 6 * exp, (lost_t, sent_t, exp)
    assert lost_t / sent_t < 0.1 * lost_m / sent_m
    # lossless runs report zero
    res0, _ = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=0,
        t0_ms=float(state.t_ms), params=params, payload_bytes=15000,
        with_gossip=True)
    assert int(np.asarray(res0.lost_tx).sum()) == 0


def test_fragments_complete_on_last():
    g, params, state, a, (stage, lat, bw) = mesh_setup()
    r1, _ = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw,
        publisher=0, t0_ms=float(state.t_ms), params=params,
        payload_bytes=15000, fragments=1,
    )
    r4, _ = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw,
        publisher=0, t0_ms=float(state.t_ms), params=params,
        payload_bytes=15000, fragments=4,
    )
    assert bool(r4.received.all())
    d1 = np.asarray(r1.delay_ms)[1:]
    d4 = np.asarray(r4.delay_ms)[1:]
    # 4 fragments of 3750B: per-hop tx is smaller but the 4th fragment queues
    # behind the first three, so completion is later than the single-fragment
    # message on average
    assert d4.mean() > d1.mean()


def test_dead_publisher_reaches_nobody():
    g, params, state, a, (stage, lat, bw) = mesh_setup()
    alive = np.ones(100, bool)
    alive[0] = False
    state = state.replace(alive=jnp.asarray(alive))
    res, _ = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw,
        publisher=0, t0_ms=float(state.t_ms), params=params,
        payload_bytes=15000,
    )
    received = np.asarray(res.received)
    assert received[0]  # publisher "has" its own message
    assert not received[1:].any()


def test_persistent_phase_controls_gossip_timing():
    # 2 peers, empty mesh, no flood: the ONLY path is gossip, which fires at
    # the emitter's next heartbeat tick — a per-node phase set in SimState.
    g = build_connection_graph(2, 1, seed=0, max_degree=4)
    stage, lat, bw = single_stage_topo(2)
    params = SimParams(n=2, capacity=g.capacity, d=1, d_low=1, d_high=2,
                       flood_publish=False, max_relax_iters=8)
    state = init_state(params, seed=3)
    state = state.replace(
        mesh_mask=jnp.zeros_like(state.mesh_mask),
        hb_phase=jnp.asarray([250.0, 777.0], jnp.float32),
    )
    args = (jnp.asarray(g.conns), jnp.asarray(g.rev), stage, lat, bw)
    res1, s1 = disseminate(state, *args, publisher=0, t0_ms=0.0, params=params,
                           payload_bytes=15000, with_gossip=True)
    # analytic: gossip fires at 0's first tick after t0+proc (phase 250 ms),
    # then IHAVE -> IWANT (2 clean control traversals) -> the answering data
    # send (one serialization + 2 cold slow-start flights = 3 traversals)
    expect = 250.0 + (3 + 2) * 100.0 + 2.4
    np.testing.assert_allclose(float(res1.delay_ms[1]), expect, rtol=1e-5)
    # the phase is a run property: disseminate must not redraw it
    np.testing.assert_array_equal(
        np.asarray(s1.hb_phase), np.asarray(state.hb_phase))
    # a later message (advanced RNG key) sees the SAME phases -> identical
    # gossip-arrival timing, the way a real node's timer persists. Erase the
    # occupancy carry first: message 1's answered IWANT legitimately occupies
    # 0's uplink (and 1's downlink), which would queue message 2 behind it —
    # this test isolates phase persistence, not bandwidth contention.
    s1 = s1.replace(
        uplink_free_ms=jnp.zeros_like(s1.uplink_free_ms),
        rx_free_ms=jnp.zeros_like(s1.rx_free_ms),
    )
    res2, _ = disseminate(s1, *args, publisher=0, t0_ms=0.0, params=params,
                          payload_bytes=15000, with_gossip=True)
    np.testing.assert_array_equal(
        np.asarray(res1.delay_ms), np.asarray(res2.delay_ms))


def test_uplink_occupancy_couples_concurrent_messages():
    # the reference's per-connection queues serialize ALL in-flight traffic
    # (main.nim:264-299): a message published while the previous one is still
    # forwarding queues behind it. Gossip off so timings are purely mesh
    # paths (heartbeat quantization would couple delays to absolute t0).
    g, params, state, a, (stage, lat, bw) = mesh_setup()
    t0 = float(state.t_ms)
    kw = dict(params=params, payload_bytes=15000, with_gossip=False)
    _, s1 = disseminate(state, a["conns"], a["rev"], stage, lat, bw,
                        publisher=4, t0_ms=t0, **kw)
    assert float(np.asarray(s1.uplink_free_ms).max()) > t0  # occupancy recorded
    # same post-msg-1 state, only the spacing differs
    r_close, _ = disseminate(s1, a["conns"], a["rev"], stage, lat, bw,
                             publisher=4, t0_ms=t0, **kw)
    r_far, _ = disseminate(s1, a["conns"], a["rev"], stage, lat, bw,
                           publisher=4, t0_ms=t0 + 4000.0, **kw)
    d_close = np.asarray(r_close.delay_ms)[np.asarray(r_close.received)]
    d_far = np.asarray(r_far.delay_ms)[np.asarray(r_far.received)]
    # 0 ms spacing: the second message queues behind the first -> strictly
    # higher p50/p99 than at 4 s spacing (uplinks long drained)
    assert np.percentile(d_close, 50) > np.percentile(d_far, 50)
    assert np.percentile(d_close, 99) > np.percentile(d_far, 99)
    # at reference spacing (>= drain time) results are spacing-invariant
    r_far2, _ = disseminate(s1, a["conns"], a["rev"], stage, lat, bw,
                            publisher=4, t0_ms=t0 + 8000.0, **kw)
    # float32 absolute-time arithmetic wobbles in the ~0.01 ms range between
    # different t0 magnitudes; spacing-invariance is exact modulo that
    np.testing.assert_allclose(
        np.asarray(r_far.delay_ms), np.asarray(r_far2.delay_ms),
        rtol=1e-4, atol=0.05)


def test_receiver_side_large_n_path_matches(monkeypatch):
    # above the row-gather memory budget the single-device fixpoint switches
    # to the receiver-side constant formulation (the 1M-peer path); it must
    # produce the same arrival times as the sender-major path. Use a fresh
    # N so no cached trace of the other branch is reused, and shrink the
    # budget so the same shapes compile through the large-N branch.
    import dst_libp2p_test_node_tpu.ops.pull as pull_mod

    n = 101
    g, params, state, a, (stage, lat, bw) = mesh_setup(n=n)
    kw = dict(publisher=7, t0_ms=float(state.t_ms), params=params,
              payload_bytes=15000, with_gossip=True)
    res_ref, _ = disseminate(state, a["conns"], a["rev"], stage, lat, bw, **kw)
    monkeypatch.setattr(pull_mod, "_MAX_INTERMEDIATE_BYTES", 1)
    disseminate.clear_cache()
    try:
        res_big, _ = disseminate(
            state, a["conns"], a["rev"], stage, lat, bw, **kw)
    finally:
        monkeypatch.undo()
        disseminate.clear_cache()
    np.testing.assert_array_equal(
        np.asarray(res_ref.received), np.asarray(res_big.received))
    np.testing.assert_allclose(
        np.asarray(res_ref.delay_ms), np.asarray(res_big.delay_ms),
        rtol=1e-4, atol=0.05)


def test_determinism_same_key():
    g, params, state, a, (stage, lat, bw) = mesh_setup()
    r1, _ = disseminate(state, a["conns"], a["rev"], stage, lat, bw,
                        publisher=7, t0_ms=0.0, params=params, payload_bytes=15000)
    r2, _ = disseminate(state, a["conns"], a["rev"], stage, lat, bw,
                        publisher=7, t0_ms=0.0, params=params, payload_bytes=15000)
    np.testing.assert_array_equal(np.asarray(r1.delay_ms), np.asarray(r2.delay_ms))


def test_lost_tx_counts_network_losses_only_not_graylist_drops():
    # lost_tx must be drawn against the LOSS-ONLY survive mask: a
    # receiver-side graylist ignore is not a network loss (the bytes
    # arrived and were discarded above the transport). Folding the
    # graylist gate into the counter inflated "network-lost" copies
    # whenever score thresholds were armed.
    g, params, state, a, (stage, lat, bw) = mesh_setup(
        seed=11, slow_weight=-1.0, graylist_threshold=-50.0)
    # a third of the peers graylist peer 0 (the publisher)
    rng = np.random.default_rng(7)
    conns = np.asarray(a["conns"])
    slow = np.zeros(state.slow_penalty.shape, np.float32)
    for r in rng.choice(100, size=33, replace=False):
        slow[r, conns[r] == 0] = 100.0
    gray = state.replace(slow_penalty=jnp.asarray(slow))

    def run(s, ls):
        res, _, plan = disseminate(
            s, a["conns"], a["rev"], stage, lat, bw, publisher=0,
            t0_ms=float(s.t_ms), params=params, payload_bytes=15000,
            with_gossip=True, loss_stage=ls, loss_mode="message",
            return_plan=True)
        return res, plan

    # no network loss at all: the graylist drops delivery on a third of
    # the publisher's edges (the combined survive mask has holes), yet
    # ZERO copies were network-lost
    res, plan = run(gray, None)
    assert plan["survive"] is not None and not bool(plan["survive"].all())
    assert int(np.asarray(res.lost_tx).sum()) == 0

    # with loss active AND the graylist firing, the lost ratio must track
    # the network loss probability alone (~p of transmitted copies) — the
    # old counter folded the graylisted edges in on top of p
    ls = jnp.full((6, 6), 0.3, jnp.float32)
    res_l, _ = run(gray, ls)
    lost = int(np.asarray(res_l.lost_tx).sum())
    sent = int(np.asarray(res_l.sends).sum())
    assert 0.2 <= lost / sent <= 0.4, (lost, sent)


# ------------------------------------ the two bands of a publish's row pulls

def _banded_net(**over):
    """A 2,000-peer network with its hoisted tables and the bands of its
    pulls, forced under the size test through the maker's own arguments
    (`band_shape` at (2000, 40): C1 = 24, room for 256 heavy rows)."""
    from dst_libp2p_test_node_tpu.ops.disseminate import (
        answer_tables, edge_tables)
    from dst_libp2p_test_node_tpu.ops.pull import make_pull_bands

    g, params, state, a, (stage, lat, bw) = mesh_setup(
        n=2000, seed=5, **over)
    conns, rev = a["conns"], a["rev"]
    lat_edge, _ = edge_tables(stage, lat, conns, rev, None)
    ans = answer_tables(lat_edge, conns, rev)
    assert make_pull_bands(conns, rev) is None        # under the size test
    bands = make_pull_bands(
        conns, rev, ans.conns_sorted, ans.rev_sorted, min_bytes=0)
    heavy = int((np.asarray(bands.back) < bands.tails["conns"].shape[0]).sum())
    assert 100 < heavy <= 256 and bands.heads["rev_sorted"].shape == (2000, 24)
    mesh_only = make_pull_bands(conns, rev, min_bytes=0)
    return (params, state, conns, rev, stage, lat, bw, lat_edge, ans,
            bands, mesh_only)


_BANDED_PUBLISHES = {
    # name: (network's SimParams overrides, disseminate's keywords, refines?)
    "gossip_f1": ({}, dict(payload_bytes=15000), None),
    "gossip_f4_refined": ({}, dict(payload_bytes=131072, fragments=4), True),
    "meshonly_f1": ({}, dict(payload_bytes=15000, with_gossip=False), False),
    "meshonly_f4": ({}, dict(payload_bytes=15000, fragments=4,
                             with_gossip=False), False),
    "loss_tcp_f4": ({}, dict(payload_bytes=15000, fragments=4,
                             loss_mode="tcp", lossy=True), None),
    "churn_dead_neighbours": (
        dict(churn_down_per_hb=1e-3, churn_up_per_hb=5e-4),
        dict(payload_bytes=15000, fragments=4, dead_neighbours=True), None),
}


def _publish_case(name):
    """(arguments, keywords with the network's bands, refines?) of a
    `_BANDED_PUBLISHES` case."""
    over, kw, refines = _BANDED_PUBLISHES[name]
    kw = dict(kw)
    (params, state, conns, rev, stage, lat, bw, lat_edge, ans, bands,
     mesh_only) = _banded_net(**over)
    gossip = kw.get("with_gossip", True)
    if kw.pop("lossy", False):
        kw["loss_stage"] = jnp.full((6, 6), 0.2, jnp.float32)
    publisher = 3
    if kw.pop("dead_neighbours", False):
        alive = np.asarray(state.alive).copy()
        assert not alive.all()                  # the churned scan killed some
        nbrs = np.asarray(conns)[publisher]
        alive[nbrs[nbrs >= 0][::2]] = False
        alive[publisher] = True
        state = state.replace(alive=jnp.asarray(alive))
    common = dict(publisher=publisher, t0_ms=float(state.t_ms), params=params,
                  ans_tables=ans if gossip else None,
                  pull_bands=bands if gossip else mesh_only, **kw)
    if "loss_stage" not in kw:
        common["lat_edge"] = lat_edge
    return (state, conns, rev, stage, lat, bw), common, refines


def _same_leaves(want, got, but=None):
    """Every leaf of two results equal bit for bit (`but(leaf)`: what to
    compare of a leaf of either side)."""
    import jax

    leaves_w, tree_w = jax.tree_util.tree_flatten(want)
    leaves_g, tree_g = jax.tree_util.tree_flatten(got)
    assert tree_w == tree_g
    for w, g in zip(leaves_w, leaves_g):
        if but is not None:
            w, g = but(w), but(g)
        assert np.asarray(w).tobytes() == np.asarray(g).tobytes()


@pytest.mark.parametrize("name", sorted(_BANDED_PUBLISHES))
def test_publish_through_the_bands_is_the_publish(name):
    """ISSUE 50: `disseminate` with `pull_bands` returns every leaf of the
    result and of the next state that it returns without, bit for bit:
    gossip on and off, one fragment and four joint lanes, tcp loss draws, a
    publisher whose neighbours are dead under churn (its own validity
    pull), and the refined branch."""
    args, common, refines = _publish_case(name)
    want = disseminate(*args, **{**common, "pull_bands": None})
    got = disseminate(*args, **common)
    _same_leaves(want, got)
    res = want[0]
    assert bool(np.asarray(res.received).sum() > 1000)
    if refines is not None:
        assert bool(res.refined) is refines
    if name == "churn_dead_neighbours":
        assert int(res.alive) < 2000


def test_pull_bands_are_for_the_row_pull_formulation(monkeypatch):
    """Past the gather budget no row is pulled ("recv"): bands there are a
    caller's error, said at trace time."""
    import dst_libp2p_test_node_tpu.ops.pull as pull

    (params, state, conns, rev, stage, lat, bw, lat_edge, ans, bands,
     _) = _banded_net()
    monkeypatch.setattr(pull, "_MAX_INTERMEDIATE_BYTES", 1)
    with pytest.raises(ValueError, match="row_pull"):
        disseminate(state, conns, rev, stage, lat, bw, publisher=3,
                    t0_ms=float(state.t_ms), params=params,
                    payload_bytes=14000, pull_bands=bands)


# ------------------- the fast fixpoint relaxes the rows whose senders moved

@pytest.fixture
def route_by_rows(monkeypatch):
    """`route(min_bytes)`: the size constant that decides whether the fast
    fixpoint carries the moved rows (ops/pull.relax_route), set for the
    traces that follow, with K = 64 rows at these 2,000 peers. The program
    has no option for it: the jit's cache is cleared around each choice, so
    no other test meets a program traced under this one's constants."""
    import dst_libp2p_test_node_tpu.ops.pull as pull

    monkeypatch.setattr(pull, "_RELAX_ROWS", 64)

    def route(min_bytes):
        monkeypatch.setattr(pull, "_SPARSE_MIN_DENSE_BYTES", min_bytes)
        disseminate.clear_cache()

    yield route
    disseminate.clear_cache()


@pytest.mark.parametrize("name", sorted(_BANDED_PUBLISHES))
def test_publish_relaxing_the_moved_rows_is_the_publish(name, route_by_rows):
    """ISSUE 51: a whole `disseminate` whose fast fixpoints deliver the
    offers of the rows that moved (the route forced through the size
    constant) returns every leaf of the result and of the next state that
    the dense program returns, bit for bit, but the counter that says it
    engaged: gossip on and off, one fragment and four joint lanes, loss
    draws a lane, churn, and the refined branch (whose loops stay dense)."""
    args, common, refines = _publish_case(name)
    route_by_rows(128 * 1024**2)
    want = disseminate(*args, **common)
    route_by_rows(0)
    got = disseminate(*args, **common)
    dense, sparse = want[0], got[0]
    assert int(dense.fast_sparse_iters) == 0
    # a cold phase 1 starts from the publisher alone and every phase ends
    # on a pass that moves nothing
    assert 4 <= int(sparse.fast_sparse_iters) < int(sparse.fast_iters)
    assert int(sparse.fast_iters) == int(dense.fast_iters)
    width = dense.counters.shape
    # (and the refinement's own counter of the same route, PR 53)
    _same_leaves(want, got, but=lambda x: (
        x.at[9:11].set(0) if x.shape == width and x.dtype == jnp.int32
        else x))
    assert int(dense.refine_sparse_passes) == 0
    assert bool(np.asarray(dense.received).sum() > 1000)
    if refines is not None:
        assert bool(dense.refined) is refines
    if name == "churn_dead_neighbours":
        assert int(sparse.alive) == int(dense.alive) < 2000
        assert sparse.counters.shape == (13,)


def test_the_vmapped_fast_pipeline_conds_on_a_scalar(route_by_rows):
    """Four fragment lanes under `_per_fragment`'s vmap: each fast fixpoint
    holds ONE `cond` between the delivery and the pull, on a scalar (every
    lane's moved rows fit, or none is delivered); no scatter into the
    (F, N, C) offers outside it, which a select of the two sides would
    leave in the loop's body."""
    import jax
    from test_pull import _eqns

    args, common, _ = _publish_case("gossip_f4_refined")
    route_by_rows(0)
    jaxpr = jax.make_jaxpr(
        lambda *a: disseminate(*a, **common))(*args).jaxpr
    eqns = list(_eqns(jaxpr))

    def between(eqn):
        sides = [{name for name, _, _ in _eqns(b.jaxpr)}
                 for b in eqn.params["branches"]]
        return sorted("scatter" in s for s in sides) == [False, True]

    conds = [e for name, e, inside in eqns
             if name == "cond" and "while" in inside and between(e)]
    # phase 1 and phase 2 (and once more each in the warm seed's cold
    # rerun), and the two refinement loops' lookups (PR 53)
    assert len(conds) == (4 if common["params"].warm_start else 2) + 2
    assert all(e.invars[0].aval.shape == () for e in conds)
    n, c = args[1].shape
    loose = [e for name, e, inside in eqns
             if name == "scatter" and "while" in inside
             and "cond" not in inside[inside.index("while"):]
             and e.outvars[0].aval.shape[-2:] == (n, c)]
    assert not loose


def test_small_shapes_keep_the_dense_program(route_by_rows):
    """At (1000, 40) the dense pull is under the size test: the lowered
    text of `disseminate` is the text with the route forced off, loops,
    carries and all (and not the text with it forced on)."""
    g, params, state, a, (stage, lat, bw) = mesh_setup(n=1000, seed=5)
    assert a["conns"].shape == (1000, 40)

    def text(fragments):
        return disseminate.lower(
            state, a["conns"], a["rev"], stage, lat, bw, publisher=3,
            t0_ms=float(state.t_ms), params=params, payload_bytes=15000,
            fragments=fragments).as_text()

    asis = [text(f) for f in (1, 4)]
    route_by_rows(2**62)
    assert [text(f) for f in (1, 4)] == asis
    route_by_rows(0)
    forced = [text(f) for f in (1, 4)]
    assert all(a != b for a, b in zip(asis, forced))
    assert all("stablehlo.case" in t or "stablehlo.if" in t for t in forced)


# ------------- a refinement pass's receivers' times, by the rows that moved

HERE = os.path.dirname(os.path.abspath(__file__))


def _leaf_digests(tree):
    """{path: sha256} of every leaf (PRNG keys by their key data)."""
    from test_exact_prefix import _leaf_bytes

    return {path: hashlib.sha256(leaf.tobytes()).hexdigest()
            for path, leaf in _leaf_bytes(tree).items()}


def _parents(name):
    """The parent's counters and leaf digests of the publish `name`
    (tests/fixtures/refine_parent_leaves.json), under the jax they were
    taken on."""
    import jax

    with open(os.path.join(HERE, "fixtures",
                           "refine_parent_leaves.json")) as f:
        pinned = json.load(f)
    if pinned["jax"] != jax.__version__:
        pytest.skip(f"digests taken on jax {pinned['jax']}")
    return pinned["publishes"][name]


def _against_the_parent(got, parent):
    """Every leaf of (result, next state) is the parent's but the packed
    counters, and those are the parent's ten (and a churned publish's two)
    around the new one; returns the counters."""
    digests = _leaf_digests(got)
    assert digests.keys() == parent["leaves"].keys()
    assert {k for k in digests if digests[k] != parent["leaves"][k]} == {
        "[0].counters"}
    was, now = parent["counters"], np.asarray(got[0].counters).tolist()
    assert now[:9] + now[11:] == was[:9] + was[10:]
    assert now[10] == int(got[0].refine_sparse_passes)
    return now


@pytest.mark.parametrize("routed", [False, True], ids=["dense", "by_rows"])
@pytest.mark.parametrize("name", sorted(_BANDED_PUBLISHES))
def test_refinement_by_the_moved_rows_is_the_parents_publish(
        name, routed, route_by_rows):
    """ISSUE 53: a refinement pass carries its fold's receivers' times and,
    where few peers moved in the pass before, delivers their new times
    into the carry in place of the lookup of every row's
    (ops/pull.neighbor_update_min). Every leaf of the result and of the
    next state, `refine_passes` among them, is the leaf of the parent's
    publish (one lookup a pass; digests taken on its tree), bit for bit,
    under the size test (the plain program) and with the route forced
    through the size constant: one fragment and four joint lanes, loss
    draws a lane, churn, and the publishes that never refine."""
    parent = _parents(name)
    args, common, _ = _publish_case(name)
    route_by_rows(0 if routed else 128 * 1024**2)
    got = disseminate(*args, **common)
    now = _against_the_parent(got, parent)
    if routed and parent["counters"][2]:
        # a loop ends on a pass that moves nothing, after one that moved a
        # handful: at least the last pass of each of the two loops
        assert 2 <= now[10] < now[1]
    else:
        assert now[10] == 0
    if not routed:
        assert now[9] == parent["counters"][9] == 0


@pytest.mark.parametrize("name", sorted(
    k for k, v in _BANDED_PUBLISHES.items() if v[1].get("with_gossip", True)))
def test_refined_times_are_the_global_sort_engines(name, route_by_rows):
    """The times the carried receivers' times lead to are the fixpoint the
    global-sort engine (answer_queue_mode="serial") finds, which evaluates
    every answer queue from the times themselves and carries nothing: on
    the four pinned publishes that refine, with the route forced, its
    receipts and counts are the prefix engine's bit for bit and its arrival
    times to the last bit or two (the engines associate a queue's sums
    differently, as in the parent)."""
    import dataclasses

    args, common, _ = _publish_case(name)
    route_by_rows(0)
    res_p, st_p = disseminate(*args, **common)
    serial = dataclasses.replace(common["params"],
                                 answer_queue_mode="serial")
    res_s, st_s = disseminate(*args, **{**common, "params": serial})
    assert bool(res_p.refined) and bool(res_s.refined)
    assert not bool(res_p.refined_serial) and bool(res_s.refined_serial)
    assert int(res_p.refine_sparse_passes) > 0
    assert int(res_s.refine_sparse_passes) == 0
    assert bool(res_p.converged) and bool(res_s.converged)
    for leaf in ("received", "sends", "copies_rx", "ihave_sent",
                 "iwant_sent", "lost_tx"):
        assert np.asarray(getattr(res_p, leaf)).tobytes() == np.asarray(
            getattr(res_s, leaf)).tobytes(), leaf
    got = np.asarray(res_p.received)
    assert got.sum() > 1000
    np.testing.assert_allclose(np.asarray(res_p.t_rx_ms)[got],
                               np.asarray(res_s.t_rx_ms)[got], rtol=3e-7)
    np.testing.assert_allclose(st_p.uplink_free_ms, st_s.uplink_free_ms,
                               rtol=1e-6)


def _capped_case(cap):
    """A three-fragment 131,072-byte publish of a gossip-heavy 100-peer
    network whose every loop is capped at `cap` iterations: (arguments,
    keywords)."""
    g, params, state, a, (stage, lat, bw) = mesh_setup(
        flood_publish=False, d_lazy=12, max_relax_iters=cap)
    return ((state, a["conns"], a["rev"], stage, lat, bw),
            dict(publisher=7, t0_ms=float(state.t_ms), params=params,
                 payload_bytes=131072, fragments=3))


@pytest.mark.parametrize("routed", [False, True], ids=["dense", "by_rows"])
@pytest.mark.parametrize("cap,fell_back", [(3, True), (6, False)],
                         ids=["cut", "certified"])
def test_a_capped_refinement_is_the_parents(cap, fell_back, routed,
                                            route_by_rows):
    """A loop the cap cuts is uncertified and takes the global-sort rerun
    (refine/legacy), a loop that certifies under it does not, and either
    way the publish is the parent's, leaf for leaf, the carried times
    delivered or looked up."""
    parent = _parents(f"capped_{cap}")
    args, kw = _capped_case(cap)
    route_by_rows(0 if routed else 128 * 1024**2)
    got = disseminate(*args, **kw)
    now = _against_the_parent(got, parent)
    assert bool(got[0].fell_back) is fell_back is bool(
        parent["counters"][3])
    assert (now[10] > 0) is routed


def _refine_loops(jaxpr, scope=""):
    """The body of every `while` of the prefix refinement (scope
    refine/.../fixpoint, not the global-sort rerun's), sub-jaxprs (cond
    branches, pjit, a custom vmap's rule) included."""
    import jax

    for eqn in jaxpr.eqns:
        here = f"{scope}/{eqn.source_info.name_stack}"
        if (eqn.primitive.name == "while" and "refine" in here
                and "fixpoint" in here and "legacy" not in here):
            yield eqn.params["body_jaxpr"].jaxpr
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _refine_loops(sub, here)


@pytest.mark.parametrize("name", ["gossip_f1", "gossip_f4_refined"])
def test_a_refinement_pass_conds_its_lookup_on_a_scalar(name, route_by_rows):
    """The body of each of the two refinement loops, one lane and four
    joint lanes: ONE `cond` on a scalar between the delivery of the moved
    peers' times (a scatter into the carried (N, C) matrix) and the lookup
    (the row gathers of ops/pull.neighbor_rows_min through the bands); the
    offers' pull beside it, outside; no scatter outside the cond."""
    import jax
    from test_pull import _eqns

    args, common, _ = _publish_case(name)
    route_by_rows(0)
    jaxpr = jax.make_jaxpr(lambda *a: disseminate(*a, **common))(*args).jaxpr
    bodies = list(_refine_loops(jaxpr))
    assert len(bodies) == 2
    n, c = args[1].shape
    for body in bodies:
        eqns = list(_eqns(body))
        conds = [e for prim, e, _ in eqns if prim == "cond"]
        assert len(conds) == 1 and conds[0].invars[0].aval.shape == ()
        sides = [{prim for prim, _, _ in _eqns(b.jaxpr)}
                 for b in conds[0].params["branches"]]
        assert sorted("scatter" in s for s in sides) == [False, True]
        assert sorted("gather" in s and "scatter" not in s
                      for s in sides) == [False, True]
        assert not [e for prim, e, inside in eqns
                    if prim == "scatter" and "cond" not in inside]
        # the offers' pull: band A's, band B's and the way back
        rows = [e for prim, e, inside in eqns
                if prim == "gather" and "cond" not in inside
                and np.prod(e.outvars[0].aval.shape) >= n * (c - 24)]
        assert len(rows) == 3
