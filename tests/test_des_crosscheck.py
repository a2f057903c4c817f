"""Independent discrete-event cross-check of the dissemination fixpoint.

The environment cannot run Shadow, so the strongest available stand-in for
the reference's "within 5% of the Shadow run" gate (BASELINE.md) is a
from-scratch event-queue simulator of the exact link model:

    send start   = max(t_rx + proc, uplink_free)
    mesh offer   = start + (rank+1 + frag*k) * tx
                   + lat * slow-start flights + retx
    gossip       = IHAVE at max(nextHB(t_rx + proc) + round*HB, uplink),
                   receiver IWANTs iff still lacking at its arrival, the
                   answers SERIALIZE on the answering peer's single uplink
                   server in IWANT-arrival order (one tx each), then
                   deliver after lat * cold flights + retx
    delivery     = max(offer, rx_free[q] + rx_ms[q])   (downlink clamp)
    two phases   : re-rank with each receiver's first-delivery back-edge
                   removed from the sender's queue

This file implements that model as a host-side CHRONOLOGICAL event-queue
simulation (deliver / IHAVE / IWANT events on one heap — no fixpoints, no
pulls, no JAX) and asserts it produces the same arrival times as
ops/disseminate.disseminate on random graphs spanning fragments x loss x
flood/gossip-only, including a second back-to-back message so the
uplink-occupancy carry is exercised. The answer serialization emerges here
from event ordering, while the engine computes it as a sorted-prefix queue
fold — two independent derivations, so the differential discriminates that
term. The engine's sampled randomness (send sets, rank priorities,
per-round gossip targets, loss survivals) is exported through
disseminate(..., return_plan=True) so both implementations see identical
model inputs; everything downstream of the sampling is computed
independently.
"""

import heapq
import math

import jax.numpy as jnp
import numpy as np
import pytest

from dst_libp2p_test_node_tpu.config.topology import Topology, TopoParams
from dst_libp2p_test_node_tpu.ops.disseminate import disseminate
from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph
from dst_libp2p_test_node_tpu.ops.heartbeat import run_heartbeats
from dst_libp2p_test_node_tpu.ops.state import SimParams, graph_arrays, init_state

INF_CUT = 1e30


def _ranks(prio: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """rank[p, i] = position of slot i in p's ascending order of prio among
    masked slots (matches the engine's double-argsort on INF-filled rows)."""
    filled = np.where(mask, prio, np.inf)
    order = np.argsort(filled, axis=-1, kind="stable")
    ranks = np.empty_like(order)
    rows = np.arange(prio.shape[0])[:, None]
    ranks[rows, order] = np.arange(prio.shape[1])[None, :]
    return ranks.astype(np.float64)


def _flights_loop(nbytes: int, params) -> int:
    """TCP slow-start flight count, derived INDEPENDENTLY of the engine's
    closed form (ops/disseminate.tcp_flights): simulate the window growth
    byte-by-flight — IW out in flight 1, doubling each RTT — and count
    flights until the transfer fits."""
    if not params.slow_start:
        return 1
    iw = params.mss_bytes * params.initcwnd_segments
    sent, flights, cwnd = 0, 0, iw
    while sent < nbytes:
        sent += cwnd
        cwnd *= 2
        flights += 1
    return max(flights, 1)


class _Model:
    """The link model evaluated edge-by-edge (shared by both DES phases)."""

    def __init__(self, conns, rev, plan, params, payload_bytes=15000,
                 fragments=1):
        self.conns = np.asarray(conns)
        self.rev = np.asarray(rev)
        self.tx = np.asarray(plan["tx_ms"], np.float64)
        self.lat = np.asarray(plan["lat_edge"], np.float64)
        self.ph = np.asarray(plan["hb_phase"], np.float64)
        self.up = np.asarray(plan["uplink"], np.float64)
        self.rxf = np.asarray(plan["rx_free"], np.float64)
        self.rxm = np.asarray(plan["rx_ms"], np.float64)
        self.rxc = self.rxf + self.rxm   # downlink clamp per receiver
        self.can = np.asarray(plan["can_send"])
        self.gw = np.asarray(plan["g_tgt_w"])
        # loss draws are per (fragment, edge) — (F, N, C); a graylist-only
        # survive mask is (N, C), shared across fragments. Normalize both
        # to 3-D indexed by [frag, p, i].
        def _to_3d(x, fill):
            if x is None:
                return np.broadcast_to(fill, (1,) + self.conns.shape)
            x = np.asarray(x)
            return x[None] if x.ndim == 2 else x

        self.surv = _to_3d(plan["survive"], np.ones((), bool))
        # tcp loss mode: per-edge retransmission stall of the data-carrying
        # traversal (added once per delivery, not to control round trips)
        self.retx = _to_3d(plan.get("retx_ms"),
                           np.zeros((), np.float64)).astype(np.float64)
        self.proc = params.proc_delay_ms
        self.hb = params.heartbeat_ms
        self.n, self.c = self.conns.shape
        # TCP slow-start: extra RTTs of the data transfer beyond the pure
        # serialization model. Mesh fragment f rides a stream warmed by the
        # f earlier fragments; a gossip answer restarts cold.
        fb = max(payload_bytes // fragments, 16)
        self.ss_mesh = [
            float(_flights_loop((f + 1) * fb, params) - 1)
            for f in range(fragments)]
        self.ss_ans = float(_flights_loop(fb, params) - 1)

    def sv(self, frag):
        """This fragment's survive mask (modulo handles the shared 2-D
        graylist-only / lossless case normalized to one leading row)."""
        return self.surv[frag % self.surv.shape[0]]

    def rx_stall(self, frag):
        return self.retx[frag % self.retx.shape[0]]

    def mesh_offer(self, p, i, t_p, send_mask, rank, k, frag):
        """Arrival of p's MESH copy on slot i given t_rx[p] (inf if the
        copy is never sent or the network loses it)."""
        if not self.can[p] or t_p >= INF_CUT or not self.sv(frag)[p, i] \
                or not send_mask[p, i]:
            return math.inf
        start = max(t_p + self.proc, self.up[p])
        return (start + (rank[p, i] + 1.0 + frag * k[p]) * self.tx[p]
                + self.lat[p, i] * (1.0 + 2.0 * self.ss_mesh[frag])
                + self.rx_stall(frag)[p, i])


# event kinds, in tie-break order at equal times: deliveries fix t[q]
# BEFORE a same-instant IHAVE tests it (the engine's strict q_t > arrival),
# and same-instant IWANTs at one server serialize by (round, slot) — the
# exact tie order of the engine's stable sort over h*C + i columns.
_DELIVER, _IHAVE, _IWANT = 0, 1, 2


def _event_sim(m: _Model, publisher, t_pub, send_mask, rank, k, frag):
    """Chronological event-queue simulation of one fragment — the natural
    serialization the reference's runtime produces: a peer's IHAVE announce
    goes out at its heartbeat tick; a receiver still lacking at the
    announce's arrival IWANTs back; the answers queue on the answering
    peer's SINGLE uplink server in IWANT-arrival order, each occupying it
    for one tx time. Written independently of the engine's sorted-prefix
    fold (ops/disseminate.gossip_fold / gossip_serial_exact) so the differential suite
    discriminates exactly the serialization term.

    Returns (t, gossip_arr, server_busy, answered):
      t           (N,)    arrival times (rx-clamped)
      gossip_arr  (N, C)  earliest unclamped answer arrival per incoming
                          slot (inf where no answer was transmitted)
      server_busy (N,)    each peer's answer-queue drain (init m.up)
      answered    (N, C)  p answered >= 1 IWANT on its slot i
    """
    H = m.gw.shape[0]
    t = np.full(m.n, math.inf)
    server = m.up.copy()
    gossip_arr = np.full((m.n, m.c), math.inf)
    answered = np.zeros((m.n, m.c), bool)
    heap = [(t_pub, _DELIVER, 0, 0, publisher)]
    while heap:
        time, kind, h, i, p = heapq.heappop(heap)
        if kind == _DELIVER:
            q = p
            if t[q] <= time:
                continue
            t[q] = time
            if not m.can[q]:
                continue
            base = time + m.proc
            # mesh forwards (rank order static; delivery rx-clamped)
            for s in range(m.c):
                r = m.conns[q, s]
                if r < 0:
                    continue
                off = m.mesh_offer(q, s, time, send_mask, rank, k, frag)
                if off < math.inf:
                    dl = max(off, m.rxc[r])
                    if dl < t[r]:
                        heapq.heappush(heap, (dl, _DELIVER, 0, 0, r))
            # IHAVE announces per sampled mcache round (a lossy edge loses
            # the IHAVE with the copy: one survive draw per fragment-edge)
            tick = (math.floor((base - m.ph[q]) / m.hb) + 1.0) * m.hb \
                + m.ph[q]
            for hh in range(H):
                a = max(tick + hh * m.hb, m.up[q])
                for s in range(m.c):
                    if m.gw[hh, q, s] and m.sv(frag)[q, s] \
                            and m.conns[q, s] >= 0:
                        heapq.heappush(
                            heap, (a + m.lat[q, s], _IHAVE, hh, s, q))
        elif kind == _IHAVE:
            q = m.conns[p, i]
            if t[q] <= time:
                continue          # receiver already has it: no IWANT back
            heapq.heappush(heap, (time + m.lat[p, i], _IWANT, h, i, p))
        else:  # _IWANT arrives at the answering peer p
            q = m.conns[p, i]
            serve_start = max(time, server[p])
            server[p] = serve_start + m.tx[p]
            answered[p, i] = True
            arr = (server[p] + m.lat[p, i] * (1.0 + 2.0 * m.ss_ans)
                   + m.rx_stall(frag)[p, i])
            j = m.rev[p, i]
            gossip_arr[q, j] = min(gossip_arr[q, j], arr)
            dl = max(arr, m.rxc[q])
            if dl < t[q]:
                heapq.heappush(heap, (dl, _DELIVER, 0, 0, q))
    return t, gossip_arr, server, answered


def _remove_first_sender(m: _Model, t1, publisher, send_mask, rank, k, frag,
                         gossip_arr):
    """Each receiver's first-delivery back-edge leaves the sender's queue
    (the reference never forwards a message back to its deliverer). The
    candidate per incoming slot is the mesh copy's arrival or the actually-
    transmitted gossip answer's (recorded by the event sim) — whichever
    came first."""
    removed = np.zeros((m.n, m.c), bool)
    for q in range(m.n):
        best, best_j = math.inf, None
        for j in range(m.c):
            p = m.conns[q, j]
            if p < 0:
                continue
            o = min(m.mesh_offer(p, m.rev[q, j], t1[p], send_mask, rank,
                                 k, frag),
                    gossip_arr[q, j])
            if o < best:
                best, best_j = o, j
        if best_j is not None and best <= t1[q] + 0.01 + 1e-5 * t1[q] \
                and q != publisher:
            # q's OWN slot toward its first sender leaves q's send order
            removed[q, best_j] = True
    return removed


def des_delays(conns, rev, plan, params, publisher, t0_ms, fragments,
               return_occupancy=False, payload_bytes=15000):
    """Full DES: per fragment, two event-sim phases; message completes at a
    receiver when its last fragment lands. With `return_occupancy`, also
    computes each peer's post-message uplink drain time (last mesh slot
    actually transmitted — IDONTWANT suppression shortens trailing slots —
    plus the serialized answer queue's drain from the event sim) and its
    downlink drain time (every delivered copy folded through the receiver's
    single-server downlink queue in arrival order), independently of the
    engine's write-backs."""
    m = _Model(conns, rev, plan, params, payload_bytes=payload_bytes,
               fragments=fragments)
    tgt = np.asarray(plan["tgt"])
    rprio = np.asarray(plan["rprio"], np.float64)
    t_pubs = np.asarray(plan["t_pubs"], np.float64)
    idw_on = payload_bytes >= params.idontwant_threshold_bytes
    t_frags = []
    uplink_new = m.up.copy()
    rx_arrivals = [[] for _ in range(m.n)]   # delivered-copy wire arrivals
    for f in range(fragments):
        tgt_f = tgt.copy()
        if params.send_queue_cap < fragments and f + 1 > params.send_queue_cap:
            tgt_f[publisher] = False     # queue-drop: newest fragments beyond
            #                              the cap never leave the publisher
        rank1 = _ranks(rprio, tgt_f)
        k1 = tgt_f.sum(axis=-1).astype(np.float64)
        t1, g_arr, srv, ans = _event_sim(
            m, publisher, t_pubs[f], tgt_f, rank1, k1, f)
        send_f, rank_f, k_f = tgt_f, rank1, k1
        if params.exclude_first_sender:
            removed = _remove_first_sender(
                m, t1, publisher, tgt_f, rank1, k1, f, g_arr)
            send_f = tgt_f & ~removed
            rank_f = _ranks(rprio, send_f)
            k_f = send_f.sum(axis=-1).astype(np.float64)
            t1, g_arr, srv, ans = _event_sim(
                m, publisher, t_pubs[f], send_f, rank_f, k_f, f)
        if return_occupancy:
            # gossip side: the event sim's answer-queue drain IS the uplink
            # occupancy of this fragment's serialized answers
            uplink_new = np.maximum(uplink_new, srv)
            for p in range(m.n):
                if not m.can[p] or t1[p] >= INF_CUT:
                    continue
                start = max(t1[p] + m.proc, m.up[p])
                last_pos = 0.0
                for i in range(m.c):
                    q = m.conns[p, i]
                    if q < 0:
                        continue
                    # the engine counts ONE delivered copy per directed
                    # edge; its wire arrival is the min of the mesh copy
                    # (unless suppressed/lost) and the transmitted answer
                    arr = math.inf
                    if send_f[p, i]:
                        slot_start = start \
                            + (rank_f[p, i] + f * k_f[p]) * m.tx[p]
                        # mesh send: suppressed if the target's IDONTWANT
                        # (announced at its own delivery) lands before this
                        # slot's transmission begins
                        suppressed = (idw_on and t1[q] < INF_CUT
                                      and t1[q] + m.lat[p, i] < slot_start)
                        if not suppressed:
                            last_pos = max(last_pos, rank_f[p, i] + 1.0)
                            if m.sv(f)[p, i]:
                                arr = m.mesh_offer(p, i, t1[p], send_f,
                                                   rank_f, k_f, f)
                    if ans[p, i]:
                        arr = min(arr, g_arr[q, m.rev[p, i]])
                    if arr < math.inf:
                        rx_arrivals[q].append(arr)
                if last_pos > 0.0:
                    uplink_new[p] = max(
                        uplink_new[p],
                        start + (f * k_f[p] + last_pos) * m.tx[p])
        t_frags.append(t1)
    t_all = np.stack(t_frags)
    received = (t_all < INF_CUT).all(axis=0)
    t_rx = np.where(received, t_all.max(axis=0), math.inf)
    delays = np.where(received, t_rx - t0_ms, math.inf)
    if return_occupancy:
        rx_new = m.rxf.copy()
        for q in range(m.n):
            busy = m.rxf[q]
            for o in sorted(rx_arrivals[q]):
                busy = max(o, busy + m.rxm[q])
            rx_new[q] = busy
        return delays, received, uplink_new, rx_new
    return delays, received


def _setup(n, connect_to, seed, stages, hb_steps=8, **over):
    g = build_connection_graph(n, connect_to, seed=seed)
    params = SimParams(n=n, capacity=g.capacity, max_relax_iters=64, **over)
    state = init_state(params, seed=seed)
    a = graph_arrays(g)
    state = run_heartbeats(
        state, a["conns"], a["rev"], a["out_mask"], params, hb_steps)
    t = Topology.build(TopoParams(
        network_size=n, anchor_stages=stages, min_bandwidth=40,
        max_bandwidth=150, min_latency=30, max_latency=130))
    return g, params, state, a, (
        jnp.asarray(t.stage_of_peer), jnp.asarray(t.latency_ms),
        jnp.asarray(t.bw_up_mbit))


def _compare(res, plan, conns, rev, params, publisher, t0, frags,
             payload_bytes=15000):
    got_d = np.asarray(res.delay_ms, np.float64)
    got_r = np.asarray(res.received)
    want_d, want_r = des_delays(
        np.asarray(conns), np.asarray(rev), plan, params, publisher, t0,
        frags, payload_bytes=payload_bytes)
    np.testing.assert_array_equal(got_r, want_r)
    # the publisher's own receipt is the publish call, delay 0 whatever its
    # fragments' send origins are; the DES keeps it at the last origin,
    # t_pubs[F-1] (as its frozen copy does, benchmark/reference/des.py), so
    # its entry is taken out of the comparison
    if want_r[publisher]:
        assert got_d[publisher] == 0.0
    others = want_r & (np.arange(len(want_r)) != publisher)
    # engine runs float32 at absolute times up to ~1e4 ms: ~1e-3 ms wobble
    np.testing.assert_allclose(
        got_d[others], want_d[others], rtol=1e-4, atol=0.5)


CASES = [
    # (n, connect_to, seed, stages, fragments, loss, flood, gossip_only)
    (64, 5, 0, 1, 1, 0.0, True, False),
    (64, 5, 1, 3, 1, 0.0, True, False),
    (64, 5, 2, 3, 1, 0.2, True, False),
    (64, 5, 3, 2, 3, 0.0, True, False),
    (64, 5, 4, 2, 3, 0.2, True, False),
    (64, 5, 5, 3, 1, 0.0, False, False),
    (64, 5, 6, 2, 1, 0.2, False, True),
    (128, 8, 7, 5, 1, 0.0, True, False),
    (128, 8, 8, 5, 1, 0.2, True, False),
    (128, 8, 9, 4, 3, 0.2, True, False),
    (128, 8, 10, 4, 1, 0.0, False, True),
    (128, 8, 11, 2, 3, 0.0, False, False),
    (300, 10, 12, 5, 1, 0.0, True, False),
    (300, 10, 13, 5, 1, 0.2, True, False),
    (300, 10, 14, 5, 3, 0.0, True, False),
    (300, 10, 15, 3, 3, 0.2, True, False),
    (300, 10, 16, 3, 1, 0.0, False, True),
    (300, 10, 17, 2, 1, 0.2, False, False),
    (64, 5, 18, 1, 3, 0.2, False, True),
    (128, 8, 19, 1, 1, 0.2, True, False),
]


@pytest.mark.parametrize(
    "n,ct,seed,stages,frags,loss,flood,gossip_only", CASES)
def test_fixpoint_matches_des(n, ct, seed, stages, frags, loss, flood,
                              gossip_only):
    g, params, state, a, (stage, lat, bw) = _setup(
        n, ct, seed, stages, flood_publish=flood)
    if gossip_only:
        state = state.replace(mesh_mask=jnp.zeros_like(state.mesh_mask))
    loss_stage = (jnp.full((stages + 1, stages + 1), loss, jnp.float32)
                  if loss > 0 else None)
    pub = seed % n
    t0 = float(state.t_ms)
    res, _, plan = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=pub,
        t0_ms=t0, params=params, payload_bytes=15000, fragments=frags,
        with_gossip=True, loss_stage=loss_stage, loss_mode="message",
        return_plan=True)
    _compare(res, plan, a["conns"], a["rev"], params, pub, t0, frags)


TCP_CASES = [
    # (n, connect_to, seed, stages, fragments, loss, flood)
    (64, 5, 40, 3, 1, 0.1, True),
    (64, 5, 41, 2, 3, 0.3, True),
    (128, 8, 42, 5, 1, 0.05, True),
    (128, 8, 43, 4, 1, 0.3, False),
    (300, 10, 44, 5, 3, 0.1, True),
]


@pytest.mark.parametrize("n,ct,seed,stages,frags,loss,flood", TCP_CASES)
def test_fixpoint_matches_des_tcp_retransmit(n, ct, seed, stages, frags,
                                             loss, flood):
    # loss_mode="tcp": the sampled retransmission stalls (plan["retx_ms"])
    # must reproduce through the independent event queue exactly — and at
    # these loss rates every copy eventually lands (coverage ~1.0)
    g, params, state, a, (stage, lat, bw) = _setup(
        n, ct, seed, stages, flood_publish=flood)
    loss_stage = jnp.full((stages + 1, stages + 1), loss, jnp.float32)
    pub = seed % n
    t0 = float(state.t_ms)
    res, _, plan = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=pub,
        t0_ms=t0, params=params, payload_bytes=15000, fragments=frags,
        with_gossip=True, loss_stage=loss_stage, loss_mode="tcp",
        return_plan=True)
    assert plan["retx_ms"] is not None
    retx = np.asarray(plan["retx_ms"])
    assert (retx > 0).any(), "no retransmission sampled at this loss rate"
    assert np.asarray(res.received).mean() > 0.99
    _compare(res, plan, a["conns"], a["rev"], params, pub, t0, frags)


@pytest.mark.parametrize("frags", [1, 3])
def test_fixpoint_matches_des_with_occupancy_carry(frags):
    # message 1's uplink AND downlink occupancy WRITE-BACKS are recomputed
    # independently by the DES and must equal the engine's; message 2 then
    # reads both — both sides of the cross-message coupling cross-checked,
    # incl. multi-fragment
    g, params, state, a, (stage, lat, bw) = _setup(128, 8, 21, 4)
    t0 = float(state.t_ms)
    r1, s1, plan1 = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=3,
        t0_ms=t0, params=params, payload_bytes=15000, fragments=frags,
        with_gossip=True, return_plan=True)
    _, _, want_up, want_rx = des_delays(
        np.asarray(a["conns"]), np.asarray(a["rev"]), plan1, params, 3, t0,
        frags, return_occupancy=True)
    got_up = np.asarray(s1.uplink_free_ms, np.float64)
    assert float(got_up.max()) > t0
    np.testing.assert_allclose(got_up, want_up, rtol=1e-4, atol=0.5)
    got_rx = np.asarray(s1.rx_free_ms, np.float64)
    assert float(got_rx.max()) > t0   # every receiver drained some copies
    np.testing.assert_allclose(got_rx, want_rx, rtol=1e-4, atol=0.5)
    res, _, plan = disseminate(
        s1, a["conns"], a["rev"], stage, lat, bw, publisher=9,
        t0_ms=t0, params=params, payload_bytes=15000, with_gossip=True,
        return_plan=True)
    assert float(np.asarray(plan["uplink"]).max()) > t0
    assert float(np.asarray(plan["rx_free"]).max()) > t0
    _compare(res, plan, a["conns"], a["rev"], params, 9, t0, 1)


def test_rx_contention_binds_and_moves_p99():
    # Back-to-back publishes of large messages: the second message's
    # deliveries queue behind the first's downlink drain. The DES must agree
    # edge-for-edge, and the rx clamp must move the second message's tail —
    # the effect summary_latency_large.awk:20-24 exists to measure.
    # slow_start=False isolates the rx-clamp mechanism under test: with the
    # default slow-start model a 200 KB transfer pays +3 RTTs per hop, which
    # dominates the tail and hides the (still present) downlink queueing.
    big = 200_000   # 200 KB => rx_ms ~ 10-40 ms per copy on 40-150 Mbit hosts
    g, params, state, a, (stage, lat, bw) = _setup(96, 7, 31, 3,
                                                   slow_start=False)
    t0 = float(state.t_ms)
    r1, s1, plan1 = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=2,
        t0_ms=t0, params=params, payload_bytes=big, with_gossip=True,
        return_plan=True)
    _compare(r1, plan1, a["conns"], a["rev"], params, 2, t0, 1,
             payload_bytes=big)
    # second message at the same t0: full contention with message 1's drain
    r2, _, plan2 = disseminate(
        s1, a["conns"], a["rev"], stage, lat, bw, publisher=7,
        t0_ms=t0, params=params, payload_bytes=big, with_gossip=True,
        return_plan=True)
    _compare(r2, plan2, a["conns"], a["rev"], params, 7, t0, 1,
             payload_bytes=big)
    # same second message from the same sampled plan, but with the downlink
    # history erased: the rx clamp must be what moved the tail
    import jax.numpy as jnp

    s1_free = s1.replace(key=s1.key, rx_free_ms=jnp.zeros_like(s1.rx_free_ms))
    r2_free, _ = disseminate(
        s1_free, a["conns"], a["rev"], stage, lat, bw, publisher=7,
        t0_ms=t0, params=params, payload_bytes=big, with_gossip=True)
    d_with = np.asarray(r2.delay_ms, np.float64)
    d_free = np.asarray(r2_free.delay_ms, np.float64)
    both = np.asarray(r2.received) & np.asarray(r2_free.received)
    assert both.sum() > 60
    p99_with = np.percentile(d_with[both], 99)
    p99_free = np.percentile(d_free[both], 99)
    assert (d_with[both] >= d_free[both] - 0.5).all()   # clamp only delays
    assert p99_with > p99_free + 1.0, (
        f"rx contention did not move p99: {p99_with} vs {p99_free}")


def test_fixpoint_matches_des_fanout_publisher_tcp_loss():
    # the untested cross-product: an unsubscribed publisher on the v1.1
    # fanout path while every edge carries tcp-mode retransmission stalls
    g, params, state, a, (stage, lat, bw) = _setup(
        96, 7, 47, 3, flood_publish=False)
    sub = np.ones(96, bool)
    sub[11] = False
    state = state.replace(subscribed=jnp.asarray(sub))
    loss_stage = jnp.full((4, 4), 0.2, jnp.float32)
    t0 = float(state.t_ms)
    res, _, plan = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=11,
        t0_ms=t0, params=params, payload_bytes=15000, with_gossip=True,
        with_fanout=True, loss_stage=loss_stage, loss_mode="tcp",
        return_plan=True)
    assert np.asarray(plan["retx_ms"]).max() > 0
    assert int(np.asarray(res.received).sum()) > 80
    _compare(res, plan, a["conns"], a["rev"], params, 11, t0, 1)


def test_fixpoint_matches_des_fanout_publisher():
    # unsubscribed publisher -> gossipsub v1.1 fanout path; the plan's tgt
    # already resolves the fanout set, so the DES needs no special handling.
    # flood_publish OFF so the publisher's targets really come from the
    # fanout selection, not the flood set
    g, params, state, a, (stage, lat, bw) = _setup(
        128, 8, 23, 3, flood_publish=False)
    sub = np.ones(128, bool)
    sub[5] = False
    state = state.replace(subscribed=jnp.asarray(sub))
    t0 = float(state.t_ms)
    res, _, plan = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=5,
        t0_ms=t0, params=params, payload_bytes=15000, with_gossip=True,
        with_fanout=True, return_plan=True)
    assert int(np.asarray(res.received).sum()) > 100
    _compare(res, plan, a["conns"], a["rev"], params, 5, t0, 1)


SS_CASES = [
    # (n, connect_to, seed, stages, fragments, payload): payloads beyond the
    # ~14.6 KB initial window so the slow-start flight counts bind — the
    # 128 KB case is the validity-anchor block size (4 cold flights)
    (64, 5, 50, 3, 1, 131072),
    (96, 7, 51, 4, 3, 131072),
    (128, 8, 52, 5, 1, 65536),
    (64, 5, 53, 2, 4, 60000),
]


@pytest.mark.parametrize("n,ct,seed,stages,frags,payload", SS_CASES)
def test_fixpoint_matches_des_slow_start(n, ct, seed, stages, frags, payload):
    # multi-flight transfers: the per-fragment warm-stream flight counts and
    # the cold gossip-answer flights must reproduce through the independent
    # DES (which derives the counts with its own loop formulation)
    g, params, state, a, (stage, lat, bw) = _setup(n, ct, seed, stages)
    pub = seed % n
    t0 = float(state.t_ms)
    res, _, plan = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=pub,
        t0_ms=t0, params=params, payload_bytes=payload, fragments=frags,
        with_gossip=True, return_plan=True)
    _compare(res, plan, a["conns"], a["rev"], params, pub, t0, frags,
             payload_bytes=payload)


FRAG4_CASES = [
    # (n, connect_to, seed, stages, gossip-heavy): the publish of the
    # benchmark's runsh-100k-frag4 at a test's size: 15,000 B in FRAGMENTS=4
    # of 3,750 B sent back to back, loss 0, slow start on (fragment f rides
    # the stream its f predecessors warmed: 1, 1, 1, 2 flights), a relay
    # never sends back to its first sender, receipt on the LAST fragment
    (128, 8, 60, 5, False),
    (300, 10, 61, 5, False),
    (300, 10, 62, 5, True),
]


@pytest.mark.parametrize("n,ct,seed,stages,heavy", FRAG4_CASES)
def test_fixpoint_matches_des_four_fragments(n, ct, seed, stages, heavy):
    over = {"flood_publish": False, "d_lazy": 12} if heavy else {}
    g, params, state, a, (stage, lat, bw) = _setup(
        n, ct, seed, stages, **over)
    # the cell's link model (benchmark/configs/runsh-100k-frag4.json)
    assert params.slow_start and params.exclude_first_sender
    assert params.send_queue_cap >= 4
    pub = seed % n
    t0 = float(state.t_ms)
    res, _, plan = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=pub,
        t0_ms=t0, params=params, payload_bytes=15000, fragments=4,
        with_gossip=True, return_plan=True)
    assert np.asarray(res.received).all()
    # the last fragment's stream has outgrown the first window: it pays
    # one more round trip a hop than the first three
    from dst_libp2p_test_node_tpu.ops.disseminate import tcp_flights
    assert [tcp_flights((f + 1) * 3750, params) for f in range(4)] \
        == [1, 1, 1, 2]
    _compare(res, plan, a["conns"], a["rev"], params, pub, t0, 4)


CHURN_CASES = [
    # (n, connect_to, seed, stages, fragments, gossip): the publish of the
    # benchmark's runsh-100k-churn at a test's size: 40 churned heartbeats
    # (2 % down, 1 % up a heartbeat) leave a third of the peers dead, their
    # edges out of every mesh and some of the living short of D_low
    (128, 8, 70, 5, 1, True),
    (128, 8, 71, 5, 1, False),
    (300, 10, 72, 5, 4, True),
    (300, 10, 73, 5, 4, False),
]


@pytest.mark.parametrize("n,ct,seed,stages,frags,gossip", CHURN_CASES)
def test_fixpoint_matches_des_under_churn(n, ct, seed, stages, frags, gossip):
    g, params, state, a, (stage, lat, bw) = _setup(
        n, ct, seed, stages, hb_steps=40, churn_down_per_hb=0.02,
        churn_up_per_hb=0.01)
    alive = np.asarray(state.alive)
    assert 0.4 * n < alive.sum() < 0.9 * n
    pub = int(np.nonzero(alive)[0][seed % 7])
    t0 = float(state.t_ms)
    res, _, plan = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=pub,
        t0_ms=t0, params=params, payload_bytes=15000, fragments=frags,
        with_gossip=gossip, return_plan=True)
    plan = dict(plan)
    np.testing.assert_array_equal(np.asarray(plan["can_send"]), alive)
    if not gossip:
        # the engine exports gossip targets even with with_gossip=False; a
        # mesh-only publish announces nothing
        plan["g_tgt_w"] = np.zeros_like(np.asarray(plan["g_tgt_w"]))
    got_r = np.asarray(res.received)
    # no dead peer is reached, and the living are, but for those the dead
    # cut off (reached sets equal: _compare)
    assert not (got_r & ~alive).any()
    assert got_r.sum() > 0.9 * alive.sum()
    assert int(res.alive) == alive.sum()
    _compare(res, plan, a["conns"], a["rev"], params, pub, t0, frags)


def test_slow_start_flight_counts():
    from dst_libp2p_test_node_tpu.ops.disseminate import tcp_flights

    p = SimParams(n=2, capacity=4)
    iw = p.mss_bytes * p.initcwnd_segments      # 14600
    assert tcp_flights(1, p) == 1
    assert tcp_flights(iw, p) == 1              # exactly one window
    assert tcp_flights(iw + 1, p) == 2          # one byte over
    assert tcp_flights(15_000, p) == 2          # the flagship message
    assert tcp_flights(3 * iw, p) == 2          # IW*(2^2-1) boundary
    assert tcp_flights(3 * iw + 1, p) == 3
    assert tcp_flights(131_072, p) == 4         # the 128 KB anchor block
    # the DES's independent loop derivation agrees everywhere it matters
    for b in (1, 100, iw - 1, iw, iw + 1, 15_000, 3 * iw, 3 * iw + 1,
              65_536, 131_072, 10_000_000):
        assert _flights_loop(b, p) == tcp_flights(b, p), b
    off = SimParams(n=2, capacity=4, slow_start=False)
    assert tcp_flights(10_000_000, off) == 1


def test_slow_start_adds_rtts_not_bandwidth():
    # A/B at identical sampled plans (same state key, slow_start is a static
    # param): every delay with slow-start on is >= the delay with it off,
    # and first-hop receivers pay EXACTLY (flights-1) extra RTTs.
    from dst_libp2p_test_node_tpu.ops.disseminate import tcp_flights

    import dataclasses

    payload = 131_072
    g, params, state, a, (stage, lat, bw) = _setup(96, 7, 60, 3)
    params_off = dataclasses.replace(params, slow_start=False)
    pub = 9
    t0 = float(state.t_ms)
    res_on, _, plan = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=pub,
        t0_ms=t0, params=params, payload_bytes=payload, with_gossip=True,
        return_plan=True)
    res_off, _ = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=pub,
        t0_ms=t0, params=params_off, payload_bytes=payload, with_gossip=True)
    d_on = np.asarray(res_on.delay_ms, np.float64)
    d_off = np.asarray(res_off.delay_ms, np.float64)
    both = np.asarray(res_on.received) & np.asarray(res_off.received)
    assert both.sum() > 90
    assert (d_on[both] >= d_off[both] - 0.5).all()
    extra_rtts = float(tcp_flights(payload, params) - 1)
    assert extra_rtts == 3.0
    # first-hop check: peers whose first delivery came straight from the
    # publisher's mesh sends shifted by exactly extra_rtts * RTT(edge)
    lat_edge = np.asarray(plan["lat_edge"], np.float64)
    conns = np.asarray(a["conns"])
    tgt = np.asarray(plan["tgt"])
    moved = checked = 0
    for i in range(conns.shape[1]):
        q = conns[pub, i]
        if q < 0 or not tgt[pub, i]:
            continue
        want = extra_rtts * 2.0 * lat_edge[pub, i]
        got = d_on[q] - d_off[q]
        # only first-hop-delivered peers obey the exact shift; peers that
        # got it faster elsewhere shift differently — count exact matches
        checked += 1
        if abs(got - want) < 1.0:
            moved += 1
    assert checked >= 5 and moved >= 1, (checked, moved)


def test_bounded_mode_one_sided_within_reported_wait():
    # serialize_answers=False (the bounded delivery mode the 100k/1M
    # throughput configs run): accounting/attribution stay exact, but
    # arrival times keep the unserialized value where a queued answer
    # binds. Contract checked here against the chronological DES (= the
    # exact model): the bounded times are (a) NEVER LATER than the exact
    # ones (one-sided: dropping queue waits can only advance arrivals),
    # (b) no earlier than a small multiple of the REPORTED max answer
    # wait (queue waits can compound along a delivery path, but the path
    # has few gossip hops), and (c) the report itself is positive exactly
    # when queues formed.
    import dataclasses

    # gossip-only + loss: answers carry the traffic and queues form
    g, params, state, a, (stage, lat, bw) = _setup(
        128, 8, 70, 3, flood_publish=False)
    state = state.replace(mesh_mask=jnp.zeros_like(state.mesh_mask))
    loss_stage = jnp.full((4, 4), 0.15, jnp.float32)
    pub = 9
    t0 = float(state.t_ms)
    pb = dataclasses.replace(params, serialize_answers=False)
    res_b, _, plan = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=pub,
        t0_ms=t0, params=pb, payload_bytes=15000, with_gossip=True,
        loss_stage=loss_stage, loss_mode="message", return_plan=True)
    wait = float(np.asarray(res_b.answer_wait_max_ms))
    assert wait > 0.0, "expected answer queues to form at this seed"
    want_d, want_r = des_delays(
        np.asarray(a["conns"]), np.asarray(a["rev"]), plan, params, pub,
        t0, 1)
    got_d = np.asarray(res_b.delay_ms, np.float64)
    both = np.asarray(res_b.received) & want_r
    assert both.sum() > 100
    diff = want_d[both] - got_d[both]      # exact(DES) - bounded
    assert (diff >= -0.5).all(), "bounded mode must never be LATER than exact"
    assert diff.max() <= 10.0 * wait + 0.5, (diff.max(), wait)
    # the exact default reports zero wait (the repair removes the error)
    res_e, _ = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=pub,
        t0_ms=t0, params=params, payload_bytes=15000, with_gossip=True,
        loss_stage=loss_stage, loss_mode="message")
    assert float(np.asarray(res_e.answer_wait_max_ms)) == 0.0


def test_fixpoint_matches_des_with_graylist():
    # armed score thresholds: graylisted edges fold into the survive mask,
    # which the plan exports — receiver-side drops must match exactly
    g, params, state, a, (stage, lat, bw) = _setup(
        96, 7, 24, 2, slow_weight=-1.0, graylist_threshold=-50.0)
    # a third of the peers score peer 9 below the graylist threshold
    rng = np.random.default_rng(5)
    slow = np.zeros(state.slow_penalty.shape, np.float32)
    conns = np.asarray(a["conns"])
    rows = rng.choice(96, size=32, replace=False)
    for r in rows:
        slow[r, conns[r] == 9] = 100.0
    state = state.replace(slow_penalty=jnp.asarray(slow))
    t0 = float(state.t_ms)
    res, _, plan = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=9,
        t0_ms=t0, params=params, payload_bytes=15000, with_gossip=True,
        return_plan=True)
    assert plan["survive"] is not None and not bool(plan["survive"].all())
    _compare(res, plan, a["conns"], a["rev"], params, 9, t0, 1)
