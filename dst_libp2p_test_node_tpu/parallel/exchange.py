"""Hand-tuned cross-shard exchange for the dissemination fixpoint.

The reference's cross-peer traffic is TCP/QUIC sockets between processes;
sharded across TPU chips, a mesh edge whose endpoints live on different
shards must move data over ICI (SURVEY.md §2 parallelism table). The naive
formulation (ops/disseminate.py's sender-side `offers` + `pull`) reads the
full (N, C) candidate matrix across shards every fixpoint iteration; under
XLA auto-partitioning that becomes repeated all-gathers of C floats per peer.

This module reformulates the fixpoint receiver-side so the ONLY cross-shard
value is the (N,) arrival-time vector — 4 bytes/peer/iteration over ICI:

    inc[q, j] = t_rx[p] + A[q, j]                          (mesh edges)
    inc[q, j] = nextHB(t_rx[p] + proc, phase[p]) + G[q, j] (gossip edges)
    t_rx'[q]  = min(t_rx[q], min_j inc[q, j])     with p = conns[q, j]

where A and G are per-edge constants (uplink-serialization rank, stage
latency, tx time) gathered ONCE through the reverse-slot map before the
loop. Both the everything-on-one-shard path and the `shard_map` path run the
same expression; the sharded variant all-gathers t_rx and psums the
convergence flag, so XLA emits exactly one small collective pair per
iteration — the ICI-riding design the scaling recipe calls for (mesh ->
shardings -> let XLA insert collectives).

Equivalence to the sender-side formulation is exact: offers are affine in
the sender's arrival time for mesh edges, and the gossip term only needs
t_rx[p] and the sender's heartbeat phase (see test_exchange.py).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .sharding import shard_map as _shard_map

INF = jnp.float32(3.4e38)

PEER_AXIS = "peers"


@struct.dataclass
class RecvConstants:
    """Per-receiver-slot constants of one fixpoint (fragment x phase).

    The fixpoint carry is memory-bound (ARCHITECTURE §6): every iteration
    streams these tables from HBM, so their byte width IS the iteration
    cost at the 1M-peer shapes this formulation exists for. So the two
    validity masks are packed into one int8 `flags` word per slot (bit 0
    mesh, bit 1 gossip) — half the bool traffic, bit-identical results.
    Every time and cost table is float32: the sim clock runs to ~1e6 ms."""

    src: jnp.ndarray        # (N, C) int32 sender peer id (conns), -1 pad
    a_ms: jnp.ndarray       # (N, C) float32 mesh-edge additive constant
    #                         (queue slot + latency; proc applies to the start)
    g_ms: jnp.ndarray       # (N, C) float32 gossip additive constant
    g_off: jnp.ndarray      # (N, C) float32 gossip-round heartbeat offset:
    #                         the mcache window re-samples IHAVE targets each
    #                         heartbeat; this is (first round sampled) * hb_ms
    phase: jnp.ndarray      # (N, C) float32 sender heartbeat phase
    u_ms: jnp.ndarray       # (N, C) float32 sender uplink-free time: sends
    #                         start no earlier than this (cross-message
    #                         bandwidth contention, ops/state.py uplink_free_ms)
    flags: jnp.ndarray      # (N, C) int8 validity word: bit 0 = mesh edge
    #                         active, bit 1 = gossip edge active
    rx_c: jnp.ndarray       # (N,) float32 receiver downlink clamp: delivery
    #                         completes no earlier than this (rx_free + rx_ms,
    #                         ops/state.py rx_free_ms) — receiver-local, so it
    #                         shards with the rows
    proc_ms: jnp.ndarray    # () float32
    hb_ms: jnp.ndarray      # () float32


def _edge_gather(sender_val: jnp.ndarray, conns: jnp.ndarray,
                 rev: jnp.ndarray) -> jnp.ndarray:
    """recv[q, j] = sender_val[conns[q,j], rev[q,j]] (one-time gather)."""
    return sender_val[jnp.clip(conns, 0), jnp.clip(rev, 0)]


def build_recv_constants(
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    lat_edge: jnp.ndarray,      # (N, C) sender-side per-slot latency
    tx_ms: jnp.ndarray,         # (N,) sender uplink ms per fragment
    rank: jnp.ndarray,          # (N, C) sender-side send order
    k_p: jnp.ndarray,           # (N,) sender fanout size
    frag_idx,
    send_mask: jnp.ndarray,     # (N, C) sender-side forwarding mask
    can_send: jnp.ndarray,      # (N,) alive & subscribed
    g_tgt: jnp.ndarray,         # (N, C) sender-side gossip targets (any round)
    g_off_s: jnp.ndarray,       # (N, C) sender-side gossip-round offset (ms)
    hb_phase: jnp.ndarray,      # (N,) heartbeat phase
    uplink_free: jnp.ndarray,   # (N,) sender uplink-free time (absolute ms)
    rx_const: jnp.ndarray,      # (N,) receiver downlink clamp (rx_free + rx_ms)
    proc_ms: float,
    hb_ms: float,
    with_gossip: bool,
    lat_deliver=None,
    ld_gossip=None,
) -> RecvConstants:
    """Gather every sender-side term of ops/disseminate.offers through the
    reverse-slot map once, leaving a fixpoint that touches only t_rx.

    `lat_deliver` / `ld_gossip`: optional (N, C) effective DELIVERY latency
    of the data-carrying traversal for mesh sends / gossip answers — wire
    latency scaled by the TCP slow-start flight count plus the sampled
    retransmission stall (ops/disseminate loss_mode="tcp"). Additive edge
    constants, so they fold into a_ms/g_ms here and cost the fixpoint
    nothing per iteration. Default to the bare lat_edge."""
    valid = (conns >= 0) & (rev >= 0)
    queue = (rank + 1.0 + frag_idx * k_p[:, None]) * tx_ms[:, None]
    if lat_deliver is None:
        lat_deliver = lat_edge
    if ld_gossip is None:
        ld_gossip = lat_deliver
    a_sender = queue + lat_deliver  # offers minus the send start
    a_ms = jnp.where(valid, _edge_gather(a_sender, conns, rev), INF)
    mesh_ok = valid & _edge_gather(
        send_mask & can_send[:, None], conns, rev)

    if with_gossip:
        g_sender = 2.0 * lat_edge + ld_gossip + tx_ms[:, None]
        g_ms = jnp.where(valid, _edge_gather(g_sender, conns, rev), INF)
        g_ok = valid & _edge_gather(g_tgt & can_send[:, None], conns, rev)
        g_off = _edge_gather(g_off_s, conns, rev)
    else:
        g_ms = jnp.full_like(a_ms, INF)
        g_ok = jnp.zeros_like(mesh_ok)
        g_off = jnp.zeros_like(a_ms)
    phase = _edge_gather(
        jnp.broadcast_to(hb_phase[:, None], conns.shape), conns, rev)
    u_ms = _edge_gather(
        jnp.broadcast_to(uplink_free[:, None], conns.shape), conns, rev)
    return RecvConstants(
        src=jnp.where(valid, conns, -1),
        a_ms=a_ms,
        g_ms=g_ms,
        g_off=g_off,
        phase=phase,
        u_ms=u_ms,
        flags=(mesh_ok.astype(jnp.int8)
               | (g_ok.astype(jnp.int8) << 1)),
        rx_c=jnp.asarray(rx_const, jnp.float32),
        proc_ms=jnp.float32(proc_ms),
        hb_ms=jnp.float32(hb_ms),
    )


# What `_src_gather` lowers to, stated once (chip_smoke.py prints it): the
# plain XLA gather, on every backend.
SRC_GATHER = "xla"


def _src_gather(t_all: jnp.ndarray, src: jnp.ndarray) -> jnp.ndarray:
    """The fixpoint's hot gather: t of every slot's sender. Negative src
    marks pad slots; they clip to row 0, whose value is dead behind the
    flag masks."""
    return t_all[jnp.clip(src, 0)]


def _inc_from(t_all: jnp.ndarray, c: RecvConstants) -> jnp.ndarray:
    """Incoming offers of every receiver slot given the global t_rx."""
    t_src = _src_gather(t_all, c.src)
    live = (c.src >= 0) & (t_src < INF)
    mesh_ok = (c.flags & 1) > 0
    g_ok = (c.flags & 2) > 0
    base = t_src + c.proc_ms
    # a sender's queue can't start before its uplink drains earlier traffic
    start = jnp.maximum(base, c.u_ms)
    inc = jnp.where(mesh_ok & live, start + c.a_ms, INF)
    hb = (jnp.floor((base - c.phase) / c.hb_ms) + 1.0) * c.hb_ms + c.phase
    inc_g = jnp.where(
        g_ok & live, jnp.maximum(hb + c.g_off, c.u_ms) + c.g_ms, INF)
    # min with the sentinel: a live offer whose sum overflowed (a sender
    # time near INF plus its costs) must not leak past the f32 sentinel the
    # fixpoint (and strict-JSON export) reasons in
    return jnp.minimum(jnp.minimum(inc, inc_g), INF)


def converge_recv(
    t0: jnp.ndarray, c: RecvConstants, max_iters: int, g_floor=None
):
    """Single-shard receiver-side fixpoint (reference for the sharded one).

    `g_floor`: optional (N,) per-receiver FROZEN gossip candidate — the
    serialized answer offers of one outer pass of the serialized-answer
    model (ops/disseminate gossip_serial), already row-minimized. Receiver-
    local, so it joins the row min at zero per-iteration cost.

    Returns (t_rx, inc, converged, iters): the fixpoint, the (N, C)
    incoming-offer matrix of the loop's LAST pass (the no-change
    confirmation pass evaluates it at the final times, so it rides out for
    free — callers reuse it for first-sender attribution and for the
    warm-start undershoot certificate instead of paying another full
    pull), the final change bit inverted (False only when the iteration cap
    cut the loop, in which case `inc` is one pass stale), and the loop's
    iteration count (DisseminationResult.fast_iters)."""

    def cond(carry):
        _, _, changed, it = carry
        return changed & (it < max_iters)

    def body(carry):
        t_rx, _, _, it = carry
        # downlink clamp: delivery completes no earlier than the receiver's
        # downlink drains prior traffic plus this copy (max distributes over
        # the row min, so clamping the min equals clamping every candidate)
        inc = _inc_from(t_rx, c)
        inc_min = inc.min(axis=-1)
        if g_floor is not None:
            inc_min = jnp.minimum(inc_min, g_floor)
        t_new = jnp.minimum(t_rx, jnp.maximum(inc_min, c.rx_c))
        return t_new, inc, jnp.any(t_new < t_rx), it + 1

    inc0 = jnp.full(c.src.shape, INF)
    # strong int32 counter: a Python-int carry is weak-typed (GA-J002)
    t_rx, inc, changed, it = jax.lax.while_loop(
        cond, body, (t0, inc0, jnp.bool_(True), jnp.int32(0)))
    return t_rx, inc, ~changed, it


def converge_sharded(
    t0: jnp.ndarray, c: RecvConstants, max_iters: int, mesh: Mesh,
    g_floor=None, axis_name: str = PEER_AXIS,
):
    """shard_map fixpoint over the peer axis: rows of the constants live on
    their shard; each iteration all-gathers the (N,) time vector over ICI
    and psums one convergence bit. Identical results to converge_recv
    (including the optional frozen `g_floor`, which shards with the rows,
    and the carried-out (inc, converged, iters) — inc rows shard like the
    constants; converged is replicated by the psum, and so is the iteration
    count, which every shard steps on that bit).

    `axis_name`: which mesh axis the rows partition over — PEER_AXIS on the
    1-D simulation mesh, or the peer axis of a nested trials x peers grid
    (parallel/sharding.make_trial_mesh), where the same body runs inside
    each trial group's submesh. `mesh` may carry other axes; only
    `axis_name` is mapped here, so any extra axes replicate."""
    rows = P(axis_name)
    use_floor = g_floor is not None
    if g_floor is None:
        g_floor = jnp.full_like(t0, INF)

    def local_fix(t0_l, src, a_ms, g_ms, g_off, phase, u_ms, flags,
                  rx_c, gf_l):
        c_l = RecvConstants(
            src=src, a_ms=a_ms, g_ms=g_ms, g_off=g_off, phase=phase,
            u_ms=u_ms, flags=flags, rx_c=rx_c,
            proc_ms=c.proc_ms, hb_ms=c.hb_ms,
        )

        def cond(carry):
            _, _, changed, it = carry
            return changed & (it < max_iters)

        def body(carry):
            t_l, _, _, it = carry
            t_all = jax.lax.all_gather(t_l, axis_name, tiled=True)
            inc = _inc_from(t_all, c_l)
            inc_min = inc.min(axis=-1)
            if use_floor:
                inc_min = jnp.minimum(inc_min, gf_l)
            t_new = jnp.minimum(t_l, jnp.maximum(inc_min, rx_c))
            changed = jax.lax.psum(
                jnp.any(t_new < t_l).astype(jnp.int32), axis_name) > 0
            return t_new, inc, changed, it + 1

        # the body returns inc varying over the peer axis, so the carry
        # has to start that way (shard_map checks varying manual axes)
        inc0 = jax.lax.pcast(
            jnp.full(src.shape, INF), (axis_name,), to="varying")
        t_l, inc_l, changed, it = jax.lax.while_loop(
            cond, body, (t0_l, inc0, jnp.bool_(True), jnp.int32(0)))
        return t_l, inc_l, ~changed, it

    fn = _shard_map(
        local_fix,
        mesh=mesh,
        in_specs=(rows,) * 10,
        out_specs=(rows, rows, P(), P()),
    )
    return fn(t0, c.src, c.a_ms, c.g_ms, c.g_off, c.phase, c.u_ms,
              c.flags, c.rx_c, g_floor)


def place_sharded(mesh: Mesh, *arrays):
    """Put (N, ...) arrays row-sharded on the peer mesh (test harness +
    ad-hoc placement helper; the Simulator path uses sharding.shard_simulation)."""
    sh = NamedSharding(mesh, P(PEER_AXIS))
    out = tuple(jax.device_put(a, sh) for a in arrays)
    return out if len(out) > 1 else out[0]
