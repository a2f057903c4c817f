"""Cross-shard exchange tests: the receiver-side fixpoint must match a dense
host-side reference exactly, and the shard_map variant must match the
single-shard variant bit-for-bit across an 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph
from dst_libp2p_test_node_tpu.parallel.exchange import (
    INF,
    build_recv_constants,
    converge_recv,
    converge_sharded,
    place_sharded,
)
from dst_libp2p_test_node_tpu.parallel.sharding import make_peer_mesh

N = 64
PROC = 2.0
HB = 1000.0


def _scenario(seed=0, with_gossip=True):
    rng = np.random.default_rng(seed)
    graph = build_connection_graph(N, 6, seed=seed)
    conns = jnp.asarray(graph.conns)
    rev = jnp.asarray(graph.rev)
    c = graph.capacity
    lat_edge = jnp.asarray(
        rng.uniform(40.0, 130.0, size=(N, c)).astype(np.float32))
    tx_ms = jnp.asarray(rng.uniform(0.5, 2.0, size=N).astype(np.float32))
    has = graph.conns >= 0
    send_mask = jnp.asarray(has & (rng.random((N, c)) < 0.7))
    rank = jnp.asarray(
        np.argsort(np.argsort(rng.random((N, c)), axis=-1), axis=-1)
        .astype(np.float32))
    k_p = jnp.asarray(np.asarray(send_mask).sum(axis=-1).astype(np.float32))
    can_send = jnp.ones((N,), bool)
    g_tgt = jnp.asarray(has & ~np.asarray(send_mask)
                        & (rng.random((N, c)) < 0.3)) \
        if with_gossip else jnp.zeros((N, c), bool)
    hb_phase = jnp.asarray(rng.uniform(0, HB, size=N).astype(np.float32))
    # per-edge gossip-round offsets (mcache window rounds 0..2)
    g_off = jnp.asarray(
        (rng.integers(0, 3, size=(N, c)) * HB).astype(np.float32))
    # nonzero uplink occupancy on some peers (cross-message contention term)
    uplink = jnp.asarray(
        (rng.uniform(0, 400, size=N) * (rng.random(N) < 0.5))
        .astype(np.float32))
    # nonzero downlink clamp on some peers (receiver-side contention term)
    rx_const = jnp.asarray(
        (rng.uniform(0, 500, size=N) * (rng.random(N) < 0.5))
        .astype(np.float32))
    consts = build_recv_constants(
        conns, rev, lat_edge, tx_ms, rank, k_p, 0.0, send_mask, can_send,
        g_tgt, g_off, hb_phase, uplink, rx_const, PROC, HB, with_gossip,
    )
    return (graph, lat_edge, tx_ms, send_mask, rank, k_p, g_tgt, g_off,
            hb_phase, uplink, rx_const, consts)


def _dense_reference(graph, lat_edge, tx_ms, send_mask, rank, k_p,
                     g_tgt, g_off, hb_phase, uplink, rx_const, t0, iters=64):
    """Host-side sender-perspective fixpoint (mirrors ops/disseminate's
    offers+pull semantics, written independently in numpy)."""
    conns = graph.conns
    t = t0.copy()
    lat = np.asarray(lat_edge)
    txm = np.asarray(tx_ms)
    sm = np.asarray(send_mask)
    rk = np.asarray(rank)
    gt = np.asarray(g_tgt)
    gf = np.asarray(g_off)
    ph = np.asarray(hb_phase)
    up = np.asarray(uplink)
    rxc = np.asarray(rx_const)
    for _ in range(iters):
        new = t.copy()
        for p in range(N):
            if t[p] >= 1e37:
                continue
            base = t[p] + PROC
            start = max(base, up[p])
            for i, q in enumerate(conns[p]):
                if q < 0:
                    continue
                # delivery completes no earlier than the receiver's downlink
                # clamp (rx_free + rx_ms) — applied per candidate
                if sm[p, i]:
                    cand = start + (rk[p, i] + 1.0) * txm[p] + lat[p, i]
                    new[q] = min(new[q], max(cand, rxc[q]))
                if gt[p, i]:
                    hb = (np.floor((base - ph[p]) / HB) + 1.0) * HB + ph[p]
                    cand = max(hb + gf[p, i], up[p]) + 3.0 * lat[p, i] + txm[p]
                    new[q] = min(new[q], max(cand, rxc[q]))
        if (new == t).all():
            break
        t = new
    return t


@pytest.mark.parametrize("with_gossip", [False, True])
def test_recv_fixpoint_matches_dense_reference(with_gossip):
    (graph, lat_edge, tx_ms, send_mask, rank, k_p, g_tgt, g_off, hb_phase,
     uplink, rx_const, consts) = _scenario(seed=1, with_gossip=with_gossip)
    t0 = jnp.full((N,), INF).at[0].set(123.0)
    t_fix, inc, ok, _ = converge_recv(t0, consts, 64)
    got = np.asarray(t_fix, dtype=np.float64)
    assert bool(ok)
    t0_np = np.full(N, np.float64(np.asarray(INF)))
    t0_np[0] = 123.0
    want = _dense_reference(graph, lat_edge, tx_ms, send_mask, rank, k_p,
                            g_tgt, g_off, hb_phase, uplink, rx_const, t0_np)
    reached = want < 1e37
    assert reached.sum() > N // 2     # scenario actually disseminates
    np.testing.assert_allclose(got[reached], want[reached], rtol=1e-5)
    assert (got[~reached] >= 1e37).all()


def test_sharded_matches_single_shard_exactly():
    consts = _scenario(seed=2, with_gossip=True)[-1]
    t0 = jnp.full((N,), INF).at[3].set(0.0)
    t_single, inc_single, ok_single, it_single = converge_recv(t0, consts, 64)
    single = np.asarray(t_single)

    mesh = make_peer_mesh(8)
    t0_s = place_sharded(mesh, t0)
    t_sh, inc_sh, ok_sh, it_sh = converge_sharded(t0_s, consts, 64, mesh)
    sharded = np.asarray(t_sh)
    np.testing.assert_array_equal(single, sharded)
    # the carried confirmation-pass offer matrices agree too (the
    # bounded-mode attribution consumes them)
    np.testing.assert_array_equal(np.asarray(inc_single),
                                  np.asarray(inc_sh))
    assert bool(ok_single) and bool(ok_sh)
    # and the iteration count means the same on a mesh
    assert int(it_single) == int(it_sh) > 0


def test_sharded_under_jit_compiles_collectives():
    consts = _scenario(seed=3, with_gossip=False)[-1]
    mesh = make_peer_mesh(8)

    @jax.jit
    def go(t0):
        return converge_sharded(t0, consts, 48, mesh)

    t0 = place_sharded(mesh, jnp.full((N,), INF).at[7].set(0.0))
    out = np.asarray(go(t0)[0])
    assert (out < 1e37).sum() > N // 2
    # publisher keeps its own time
    assert out[7] == 0.0
