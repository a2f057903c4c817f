"""The plain reference: a chronological discrete-event simulation of one
GossipSub publish under the link model, in float64, with no JAX.

A copy of the event-queue simulator in tests/test_des_crosscheck.py
(`_Model`, `_event_sim`, `_remove_first_sender`, `des_delays`), kept here so
that no later PR can change the yardstick. It imports nothing of the program.
It is fed the message's sampled plan (send sets, rank priorities, gossip
targets, phases, occupancy — what `disseminate(..., return_plan=True)`
exports) and the link-model constants of the configuration's own file, and
works out every arrival time by itself:

    send start   = max(t_rx + proc, uplink_free)
    mesh offer   = start + (rank+1 + frag*k) * tx + lat * flights + retx
    gossip       = IHAVE at max(nextHB(t_rx + proc) + round*HB, uplink);
                   the receiver IWANTs iff it still lacks the message when
                   the IHAVE arrives; answers queue on the answering peer's
                   single uplink in IWANT-arrival order, one tx each
    delivery     = max(offer, rx_free[q] + rx_ms[q])
    two phases   : the second with each receiver's first-delivery back-edge
                   removed from the sender's queue

Differences from the tests' copy, none of which changes a result bit: the
uplink/downlink occupancy write-backs are left out (nothing here reads
them), per-peer slot loops run over Python lists built once per phase
instead of indexing numpy arrays per event, events that could change nothing
are not queued (see `_event_sim`), and the back-edge removal is one numpy
pass. benchmark/tests/test_des_copy.py holds the two against each
other on three of the tests' CASES.

`quantize` is for the control only (benchmark/control.py): every model
table and every event time is rounded through it, which puts the reference
"in the program's place, one precision lower" (bfloat16 below the engine's
float32 clock). The reference itself never passes it.
"""

from __future__ import annotations

import heapq
import math
import struct
from types import SimpleNamespace

import numpy as np

INF_CUT = 1e30

# the link-model constants the DES reads; a configuration's file states them
LINK_MODEL_KEYS = (
    "proc_delay_ms", "heartbeat_ms", "slow_start", "mss_bytes",
    "initcwnd_segments", "exclude_first_sender", "send_queue_cap")


def link_model(values: dict) -> SimpleNamespace:
    missing = [k for k in LINK_MODEL_KEYS if k not in values]
    if missing:
        raise KeyError(f"link model lacks {missing}")
    return SimpleNamespace(**{k: values[k] for k in LINK_MODEL_KEYS})


def bfloat16_round(x):
    """Round a float (or an array) to the nearest bfloat16, ties to even."""
    if isinstance(x, np.ndarray):
        u = x.astype(np.float32).view(np.uint32).astype(np.uint64)
        finite = np.isfinite(x)
        u = np.where(finite, (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000, u)
        return u.astype(np.uint32).view(np.float32).astype(np.float64)
    if not math.isfinite(x):
        return x
    (u,) = struct.unpack("<I", struct.pack("<f", x))
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return struct.unpack("<f", struct.pack("<I", u))[0]


def _ranks(prio: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """rank[p, i] = position of slot i in p's ascending order of prio among
    masked slots."""
    filled = np.where(mask, prio, np.inf)
    order = np.argsort(filled, axis=-1, kind="stable")
    ranks = np.empty_like(order)
    rows = np.arange(prio.shape[0])[:, None]
    ranks[rows, order] = np.arange(prio.shape[1])[None, :]
    return ranks.astype(np.float64)


def _flights_loop(nbytes: int, params) -> int:
    """TCP slow-start flight count: the initial window out in flight 1,
    doubling each round trip, until the transfer fits."""
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
    """The link model's tables for one message."""

    def __init__(self, conns, rev, plan, params, payload_bytes, fragments,
                 quantize):
        q = quantize if quantize is not None else (lambda x: x)
        self.q = quantize
        self.conns = np.asarray(conns)
        self.rev = np.asarray(rev)
        self.tx = q(np.asarray(plan["tx_ms"], np.float64))
        self.lat = q(np.asarray(plan["lat_edge"], np.float64))
        self.ph = q(np.asarray(plan["hb_phase"], np.float64))
        self.up = q(np.asarray(plan["uplink"], np.float64))
        rxf = np.asarray(plan["rx_free"], np.float64)
        rxm = np.asarray(plan["rx_ms"], np.float64)
        self.rxc = q(rxf + rxm)          # downlink clamp per receiver
        self.can = np.asarray(plan["can_send"])
        self.gw = np.asarray(plan["g_tgt_w"])

        # loss draws are per (fragment, edge), (F, N, C); a graylist-only
        # survive mask is (N, C), shared across fragments; None is lossless
        def _to_3d(x, fill):
            if x is None:
                return np.broadcast_to(fill, (1,) + self.conns.shape)
            x = np.asarray(x)
            return x[None] if x.ndim == 2 else x

        self.surv = _to_3d(plan["survive"], np.ones((), bool))
        self.retx = q(_to_3d(plan.get("retx_ms"),
                             np.zeros((), np.float64)).astype(np.float64))
        self.proc = params.proc_delay_ms
        self.hb = params.heartbeat_ms
        self.n, self.c = self.conns.shape
        fb = max(payload_bytes // fragments, 16)
        self.ss_mesh = [float(_flights_loop((f + 1) * fb, params) - 1)
                        for f in range(fragments)]
        self.ss_ans = float(_flights_loop(fb, params) - 1)

    def sv(self, frag):
        return self.surv[frag % self.surv.shape[0]]

    def rx_stall(self, frag):
        return self.retx[frag % self.retx.shape[0]]

    def offer_terms(self, send_mask, rank, k, frag):
        """The three addends of a mesh offer after `start`, per (p, i), and
        the mask of copies that are sent and survive."""
        a = (rank + 1.0 + frag * k[:, None]) * self.tx[:, None]
        b = self.lat * (1.0 + 2.0 * self.ss_mesh[frag])
        c = np.broadcast_to(self.rx_stall(frag), self.conns.shape)
        ok = (self.can[:, None] & self.sv(frag) & send_mask
              & (self.conns >= 0))
        return a, b, c, ok


# event kinds, in tie-break order at equal times: a delivery fixes t[q]
# before a same-instant IHAVE tests it, and same-instant IWANTs at one
# server serialize by (round, slot). An announce is a peer's heartbeat tick
# of one gossip round; it sorts last, and only queues IHAVEs that lie later.
_DELIVER, _IHAVE, _IWANT, _ANNOUNCE = 0, 1, 2, 3


def _event_sim(m: _Model, publisher, t_pub, send_mask, rank, k, frag):
    """One fragment, chronologically. Returns (t, gossip_arr): arrival
    times (rx-clamped), and per incoming slot the earliest unclamped answer
    arrival (inf where no answer was transmitted).

    Two kinds of event that could change nothing are never queued: a
    delivery no earlier than one already queued for that receiver, and an
    IHAVE to a receiver that holds the message when the announce goes out
    (it would find t[q] <= its arrival and be dropped)."""
    q_ = m.q
    a, b, c, ok = m.offer_terms(send_mask, rank, k, frag)
    conns = m.conns.tolist()
    rev = m.rev.tolist()
    lat = m.lat.tolist()
    tx = m.tx.tolist()
    up = m.up.tolist()
    ph = m.ph.tolist()
    rxc = m.rxc.tolist()
    can = m.can.tolist()
    stall = np.broadcast_to(m.rx_stall(frag), m.conns.shape).tolist()
    # per peer: the slots it forwards on, with the offer's addends
    sends = [[] for _ in range(m.n)]
    for p, s in zip(*np.nonzero(ok)):
        sends[p].append((conns[p][s], a[p, s], b[p, s], c[p, s]))
    # per (round, peer): the slots it announces on
    rounds = m.gw.shape[0]
    announce = [[[] for _ in range(m.n)] for _ in range(rounds)]
    gw = m.gw & m.sv(frag)[None] & (m.conns >= 0)[None]
    for hh, p, s in zip(*np.nonzero(gw)):
        announce[hh][p].append(s)
    ans_lat = 1.0 + 2.0 * m.ss_ans

    t = [math.inf] * m.n
    queued = [math.inf] * m.n     # earliest delivery queued per receiver
    server = list(up)
    gossip_arr = np.full((m.n, m.c), math.inf)
    heap = [(t_pub, _DELIVER, 0, 0, publisher)]
    push, pop = heapq.heappush, heapq.heappop

    def deliver_at(dl, r):
        if dl < t[r] and dl < queued[r]:
            queued[r] = dl
            push(heap, (dl, _DELIVER, 0, 0, r))

    while heap:
        time, kind, h, i, p = pop(heap)
        if kind == _DELIVER:
            if t[p] <= time:
                continue
            t[p] = time
            if not can[p]:
                continue
            base = time + m.proc
            start = max(base, up[p])
            for r, a_, b_, c_ in sends[p]:
                off = start + a_ + b_ + c_
                if q_ is not None:
                    off = q_(off)
                deliver_at(max(off, rxc[r]), r)
            tick = (math.floor((base - ph[p]) / m.hb) + 1.0) * m.hb + ph[p]
            for hh in range(rounds):
                if announce[hh][p]:
                    push(heap, (max(tick + hh * m.hb, up[p]), _ANNOUNCE,
                                hh, 0, p))
        elif kind == _ANNOUNCE:
            for s in announce[h][p]:
                if t[conns[p][s]] <= time:
                    continue
                ih = time + lat[p][s]
                if q_ is not None:
                    ih = q_(ih)
                push(heap, (ih, _IHAVE, h, s, p))
        elif kind == _IHAVE:
            if t[conns[p][i]] <= time:
                continue          # receiver already has it: no IWANT back
            iw = time + lat[p][i]
            if q_ is not None:
                iw = q_(iw)
            push(heap, (iw, _IWANT, h, i, p))
        else:  # _IWANT arrives at the answering peer p
            r = conns[p][i]
            server[p] = max(time, server[p]) + tx[p]
            arr = server[p] + lat[p][i] * ans_lat + stall[p][i]
            if q_ is not None:
                server[p] = q_(server[p])
                arr = q_(arr)
            j = rev[p][i]
            if arr < gossip_arr[r, j]:
                gossip_arr[r, j] = arr
            deliver_at(max(arr, rxc[r]), r)
    return np.asarray(t), gossip_arr


def _remove_first_sender(m: _Model, t1, publisher, send_mask, rank, k, frag,
                         gossip_arr):
    """Each receiver's first-delivery back-edge leaves its own send order
    (a node never forwards a message back to its deliverer). The candidate
    per incoming slot is the mesh copy's arrival or the transmitted gossip
    answer's, whichever came first."""
    a, b, c, ok = m.offer_terms(send_mask, rank, k, frag)
    start = np.maximum(t1 + m.proc, m.up)
    offer = np.where(ok & (t1 < INF_CUT)[:, None],
                     start[:, None] + a + b + c, np.inf)
    if m.q is not None:
        offer = m.q(offer)
    src = np.where(m.conns >= 0, m.conns, 0)
    cand = np.where(m.conns >= 0,
                    np.minimum(offer[src, m.rev], gossip_arr), np.inf)
    best_j = np.argmin(cand, axis=1)          # first minimal slot
    best = cand[np.arange(m.n), best_j]
    hit = (best < np.inf) & (best <= t1 + 0.01 + 1e-5 * t1)
    hit[publisher] = False
    removed = np.zeros((m.n, m.c), bool)
    removed[np.nonzero(hit)[0], best_j[hit]] = True
    return removed


def des_delays(conns, rev, plan, params, publisher, t0_ms, fragments,
               payload_bytes, quantize=None):
    """Per fragment, two event-sim phases; the message completes at a
    receiver when its last fragment lands. Returns (delays_ms, received)."""
    m = _Model(conns, rev, plan, params, payload_bytes, fragments, quantize)
    tgt = np.asarray(plan["tgt"])
    rprio = np.asarray(plan["rprio"], np.float64)
    t_pubs = np.asarray(plan["t_pubs"], np.float64)
    if quantize is not None:
        t_pubs = quantize(t_pubs)
    t_frags = []
    for f in range(fragments):
        tgt_f = tgt.copy()
        if params.send_queue_cap < fragments and f + 1 > params.send_queue_cap:
            tgt_f[publisher] = False     # queue-drop: fragments beyond the
            #                              cap never leave the publisher
        rank1 = _ranks(rprio, tgt_f)
        k1 = tgt_f.sum(axis=-1).astype(np.float64)
        t1, g_arr = _event_sim(m, publisher, float(t_pubs[f]), tgt_f, rank1,
                               k1, f)
        if params.exclude_first_sender:
            removed = _remove_first_sender(
                m, t1, publisher, tgt_f, rank1, k1, f, g_arr)
            send_f = tgt_f & ~removed
            rank_f = _ranks(rprio, send_f)
            k_f = send_f.sum(axis=-1).astype(np.float64)
            t1, g_arr = _event_sim(m, publisher, float(t_pubs[f]), send_f,
                                   rank_f, k_f, f)
        t_frags.append(t1)
    t_all = np.stack(t_frags)
    received = (t_all < INF_CUT).all(axis=0)
    t_rx = np.where(received, t_all.max(axis=0), math.inf)
    delays = np.where(received, t_rx - t0_ms, math.inf)
    return delays, received
