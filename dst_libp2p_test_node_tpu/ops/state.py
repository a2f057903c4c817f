"""Simulation parameter and state containers.

SimParams is a frozen (hashable) dataclass passed as a *static* jit argument —
every field participates in trace specialization, mirroring how the reference
bakes GossipSub params at startup (configureGossipsubParams,
gossipsub-queues/main.nim:252-332).

SimState is the peer-major device pytree: one row per simulated peer where the
reference runs one OS process per peer (shadow/topogen.py:102-122).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np
from flax import struct

from ..config.env import GossipSubParams

# Width of the per-peer PX candidate pool (SimState.px_pool). A CONSTANT, not
# a SimParams field: the pool is a state leaf, and keying its shape on a
# tunable would make checkpoints / stacked trial pytrees incompatible across
# repair configs. params.px_count (<= this) bounds how many entries a PRUNE
# actually fills; the rest stay -1.
PX_POOL_WIDTH = 8


@dataclass(frozen=True)
class SimParams:
    """Static simulation parameters (hashable -> jit static arg)."""

    n: int                      # PEERS
    capacity: int               # neighbor-list capacity C
    d: int = 6
    d_low: int = 4
    d_high: int = 8
    d_score: int = 4
    d_out: int = 3
    d_lazy: int = 6
    heartbeat_ms: float = 1000.0
    prune_backoff_ms: float = 60_000.0
    gossip_factor: float = 0.25
    history_gossip: int = 3     # mcache gossip window in heartbeats
    flood_publish: bool = True
    fmd_weight: float = 1.0     # firstMessageDeliveries topic params (main.nim:335-340)
    fmd_cap: float = 30.0
    fmd_decay: float = 0.9
    decay_to_zero: float = 0.01
    # slow-peer penalty + priority-queue drop model (main.nim:264-299).
    # libp2p scoring convention: penalty WEIGHTS are negative and multiply a
    # non-negative counter into the score; state.slow_penalty holds the
    # counter, score() applies the weight.
    slow_weight: float = 0.0          # GOSSIPSUB_SLOW_PEER_PENALTY_WEIGHT (<0)
    slow_threshold_ms: float = 2000.0  # ..._THRESHOLD (seconds in the env)
    slow_decay: float = 0.2            # ..._DECAY
    send_queue_cap: int = 1024         # MAX_LOW_PRIORITY_QUEUE_LEN: data msgs
    # v1.1 opportunistic grafting (main.nim:292); -10000 = disabled
    opportunistic_graft_threshold: float = -10000.0
    # v1.1 score thresholds. The reference COMMENTS these out
    # (main.nim:276-278,306-308), deferring to nim-libp2p's defaults — which
    # are these values. With the default non-negative score weights they can
    # never bind and the gating is statically removed from the compiled step.
    gossip_threshold: float = -100.0     # no IHAVE to peers scored below
    publish_threshold: float = -1000.0   # flood/fanout skips peers below
    graylist_threshold: float = -10000.0  # receiver ignores peers below
    proc_delay_ms: float = 2.0  # per-hop validation/processing latency
    # TCP slow-start transfer dynamics (ops/disseminate.py tcp_flights):
    # under Shadow the nodes run REAL TCP stacks
    # (regression/Dockerfile_amd64_shadow:3-11), so a transfer larger than
    # the initial congestion window needs multiple RTT-gated flights —
    # the first flight carries at most initcwnd_segments * mss_bytes
    # (Linux IW10, RFC 6928) and the window doubles each RTT. Messages are
    # seconds apart, so every transfer starts from a slow-start-restarted
    # (cold) window. slow_start=False removes the term (datagram-style
    # transports with no window, and A/B isolation in tests).
    slow_start: bool = True
    mss_bytes: int = 1460
    initcwnd_segments: int = 10
    # Exact answered-IWANT serialization in the DELIVERY fixpoint (r5).
    # Always exact in the accounting (answer-queue drains, answered sets,
    # attribution offers ride the serialized fold regardless); this flag
    # additionally REPAIRS the arrival times when a queued answer would
    # have been somebody's first delivery — which at heartbeat <
    # dissemination-span shapes (the 100k bench) is every message, at the
    # honest cost of extra fixpoint passes. False = keep the unserialized
    # arrival times in exactly those binding cases (the r4-and-earlier
    # approximation, error <= the answer queue wait, a few tx_ms) — an
    # A/B attribution knob for the bench, NOT the model of record.
    serialize_answers: bool = True
    fanout_ttl_ms: float = 60_000.0  # v1.1 fanoutTTL (libp2p default 60 s)
    max_relax_iters: int = 48   # bound on the earliest-arrival fixpoint
    # Warm-started fixpoints: seed each publish's phase-1 relaxation from
    # the previous message's arrival offsets re-based to the new publish
    # time (state.warm_offset_ms; INF = no usable carry). The seed is a
    # heuristic upper bound only, so the fixpoint carries a self-
    # consistency certificate: any peer left strictly below its supported
    # value triggers ONE cold from-INF rerun (a scalar lax.cond), making
    # the result bit-identical to a cold start unconditionally. False
    # (the default) removes the seed, the certificate and the cond from
    # the trace — the cond's untaken branch still costs a second compile
    # of the whole fast pipeline, which long publish loops amortize but
    # one-shot calls should not pay.
    warm_start: bool = False
    # Exact-repair engine selection (only read when serialize_answers=True):
    # "parallel_prefix" (default) runs the scan-free Jacobi refinement —
    # one answer-queue fold + one candidate pull per iteration, with the
    # serialized global-sort pipeline kept as an in-trace fallback cond for
    # the cases the fold cannot certify (interleaved announce rounds, cap
    # cut). "serial" forces the legacy global-sort outer iteration
    # everywhere — the reference implementation the prefix path is
    # bit/rtol-pinned against (tests/test_exact_prefix.py).
    answer_queue_mode: str = "parallel_prefix"
    exclude_first_sender: bool = True   # don't forward back to the delivering peer
    idontwant_threshold_bytes: int = 1000  # go-test-node/main.go:165 (v1.2)
    churn_down_per_hb: float = 0.0  # P(alive peer dies) per heartbeat
    churn_up_per_hb: float = 0.0    # P(dead peer revives) per heartbeat
    # Mesh-repair subsystem (ops/repair.py + the opt-in heartbeat branches).
    # All OFF by default: the compiled default step contains none of the
    # repair ops and is bit-identical to the repair-free engine (pinned by
    # tests/test_repair.py).
    evict: bool = False                 # score-based mesh eviction branch
    eviction_threshold: float = -50.0   # PRUNE mesh members scoring below this
    px: bool = False                    # peer exchange on PRUNE
    px_count: int = 6                   # candidate ids per PRUNE (<= PX_POOL_WIDTH)
    redial: bool = False                # re-dial controller for starved peers
    redial_patience: int = 3            # heartbeats below d_low before dialing

    def validate(self) -> None:
        if not (0 < self.d_low <= self.d <= self.d_high <= self.capacity):
            raise ValueError(
                "require 0 < d_low <= d <= d_high <= capacity, got "
                f"{self.d_low} <= {self.d} <= {self.d_high} <= {self.capacity}"
            )
        if self.n < 2:
            raise ValueError("need at least 2 peers")
        if self.heartbeat_ms <= 0:
            raise ValueError("heartbeat_ms must be positive")
        if self.history_gossip < 1:
            raise ValueError(
                f"history_gossip must be >= 1, got {self.history_gossip}")
        if self.mss_bytes < 1 or self.initcwnd_segments < 1:
            raise ValueError("mss_bytes and initcwnd_segments must be >= 1")
        # the spec requires non-positive thresholds; enforcing it keeps the
        # static can-thresholds-bind compile decision sound (scores are
        # non-negative unless a negative weight is configured)
        for name in ("gossip_threshold", "publish_threshold",
                     "graylist_threshold"):
            if getattr(self, name) > 0:
                raise ValueError(f"{name} must be <= 0")
        if self.eviction_threshold > 0:
            # eviction is a score defense: a positive threshold would evict
            # well-behaved zero-scored peers every heartbeat
            raise ValueError("eviction_threshold must be <= 0")
        if not (1 <= self.px_count <= PX_POOL_WIDTH):
            raise ValueError(
                f"px_count must be in [1, {PX_POOL_WIDTH}], got {self.px_count}")
        if self.redial_patience < 1:
            raise ValueError("redial_patience must be >= 1")
        if self.answer_queue_mode not in ("parallel_prefix", "serial"):
            raise ValueError(
                "answer_queue_mode must be 'parallel_prefix' or 'serial', "
                f"got {self.answer_queue_mode!r}")

    @classmethod
    def from_gossipsub(
        cls, n: int, capacity: int, g: GossipSubParams, **overrides
    ) -> "SimParams":
        return cls(
            n=n,
            capacity=capacity,
            d=g.d,
            d_low=g.d_low,
            d_high=g.d_high,
            d_score=g.d_score,
            d_out=g.d_out,
            d_lazy=g.d_lazy,
            heartbeat_ms=float(g.heartbeat_ms),
            prune_backoff_ms=float(g.prune_backoff_sec) * 1000.0,
            gossip_factor=g.gossip_factor,
            history_gossip=g.history_gossip,
            flood_publish=g.flood_publish,
            fmd_weight=g.first_message_deliveries_weight,
            fmd_cap=g.first_message_deliveries_cap,
            fmd_decay=g.first_message_deliveries_decay,
            decay_to_zero=g.decay_to_zero,
            idontwant_threshold_bytes=g.idontwant_message_threshold,
            slow_weight=g.slow_peer_penalty_weight,
            slow_threshold_ms=g.slow_peer_penalty_threshold * 1000.0,
            slow_decay=g.slow_peer_penalty_decay,
            send_queue_cap=g.max_low_priority_queue_len,
            opportunistic_graft_threshold=g.opportunistic_graft_threshold,
            gossip_threshold=g.gossip_threshold,
            publish_threshold=g.publish_threshold,
            graylist_threshold=g.graylist_threshold,
            **overrides,
        )


@struct.dataclass
class SimState:
    """Device-side per-peer protocol state (a jax pytree)."""

    mesh_mask: jnp.ndarray      # (N, C) bool — GossipSub mesh ⊆ connections
    fanout_mask: jnp.ndarray    # (N, C) bool — fanout set for unsubscribed publishers
    fanout_expire: jnp.ndarray  # (N,) float32 ms — when each fanout set expires
    #                             (last fanout publish + fanout_ttl_ms; 0 = none)
    backoff_until: jnp.ndarray  # (N, C) float32 ms — PRUNE backoff per directed edge
    fmd: jnp.ndarray            # (N, C) float32 — firstMessageDeliveries counter
    slow_penalty: jnp.ndarray   # (N, C) float32 — slowPeerPenalty COUNTER
    #                             (non-negative; weighted only in score())
    alive: jnp.ndarray          # (N,) bool — churn mask
    subscribed: jnp.ndarray     # (N,) bool — topic membership
    hb_phase: jnp.ndarray       # (N,) float32 ms — per-peer heartbeat phase.
    #                             Nodes start at different wall times, so ticks
    #                             are unaligned; the phase is a property of the
    #                             NODE (drawn once per run), not of a message —
    #                             gossip-arrival timing is consistent across
    #                             messages the way a real node's timer is.
    uplink_free_ms: jnp.ndarray  # (N,) float32 ms — absolute time each peer's
    #                             uplink drains. The reference's per-connection
    #                             queues serialize ALL in-flight traffic
    #                             (main.nim:264-299): a second message published
    #                             while the first is still forwarding queues
    #                             behind it. disseminate() starts each sender at
    #                             max(t_rx + proc, uplink_free) and writes back
    #                             the final occupancy, coupling concurrent
    #                             messages the way shared uplinks do.
    rx_free_ms: jnp.ndarray     # (N,) float32 ms — absolute time each peer's
    #                             DOWNLINK drains. Shadow enforces
    #                             host_bandwidth_down on every host
    #                             (shadow/topogen.py:50-51): every received
    #                             copy — wanted or duplicate — drains the
    #                             receiver's downlink for rx_ms, so a message
    #                             arriving while earlier traffic still drains
    #                             completes no earlier than
    #                             max(wire_arrival, rx_free + rx_ms).
    #                             disseminate() applies that clamp in the
    #                             fixpoint and writes back the exact
    #                             single-server drain time of all copies this
    #                             message delivered (sorted-arrival fold).
    warm_offset_ms: jnp.ndarray  # (N,) float32 ms — arrival OFFSET
    #                             (t_rx - t0) of the most recent fully-
    #                             received message at each peer, INF where
    #                             it never arrived or the carry is invalid.
    #                             disseminate() re-bases these to the next
    #                             publish time as the warm seed of its
    #                             phase-1 relaxation (params.warm_start);
    #                             churn and subscription changes invalidate
    #                             the whole carry to INF (the topology the
    #                             offsets were measured on is gone).
    t_ms: jnp.ndarray           # () float32 — sim clock
    key: jnp.ndarray            # jax PRNG key
    # cumulative observability counters (reference L5). GRAFT/PRUNE are
    # control messages with a sender and a receiver; the Go tracer counts
    # both directions per node (metrics.go:328-336), so all four are (N,)
    grafts: jnp.ndarray         # (N,) int32 GRAFTs sent by each peer
    grafts_rx: jnp.ndarray      # (N,) int32 GRAFTs received
    prunes: jnp.ndarray         # (N,) int32 PRUNEs sent
    prunes_rx: jnp.ndarray      # (N,) int32 PRUNEs received
    bytes_tx: jnp.ndarray       # (N,) float32
    bytes_rx: jnp.ndarray       # (N,) float32
    dup_rx: jnp.ndarray         # (N,) int32
    # per-peer gossip control-message counters, both directions — the
    # shadowlog's per-node ctrl fields are real per-node counters
    # (summary_shadowlog.awk:3-8), so these are (N,)-shaped, not globals
    ihave_tx: jnp.ndarray      # (N,) int32 IHAVE announcements sent
    iwant_tx: jnp.ndarray      # (N,) int32 IWANT requests sent
    ihave_rx: jnp.ndarray      # (N,) int32 IHAVE announcements received
    iwant_rx: jnp.ndarray      # (N,) int32 IWANT requests received
    idontwant_tx: jnp.ndarray  # (N,) int32 IDONTWANTs sent (v1.2: on first
    #                            receipt of a large message, to mesh peers)
    idontwant_rx: jnp.ndarray  # (N,) int32 IDONTWANTs received
    # mesh-repair bookkeeping (ops/repair.py): held exactly where the params
    # the state was made for arm repair (init_state), or after arm_repair;
    # None, an empty subtree, otherwise: no jit of an inert run carries them
    px_pool: jnp.ndarray | None = None    # (N, PX_POOL_WIDTH) int32 — PX
    #                            candidate ids carried by the most recent
    #                            PRUNE received; -1 = empty slot
    starve_hb: jnp.ndarray | None = None  # (N,) int32 — consecutive
    #                            heartbeats the peer spent below d_low
    #                            (re-dial trigger)
    evictions: jnp.ndarray | None = None  # (N,) int32 — score-evictions sent
    #                            (a subset of `prunes`, counted separately)
    px_grafts: jnp.ndarray | None = None  # (N,) int32 — mesh edges gained
    #                            through a PX candidate (grafted or
    #                            dialed+grafted)
    redials: jnp.ndarray | None = None    # (N,) int32 — new connections
    #                            dialed by the re-dial controller

    def score(self, params: SimParams) -> jnp.ndarray:
        """Peer score as seen across each directed edge (v1.1 subset:
        P2 firstMessageDeliveries plus the slow-peer penalty counter, each
        scaled by its weight — penalty weights are negative by libp2p
        convention, so the term subtracts)."""
        fmd = jnp.minimum(self.fmd, params.fmd_cap)
        return params.fmd_weight * fmd + params.slow_weight * self.slow_penalty


def init_state(params: SimParams, seed: int = 0) -> SimState:
    import jax

    return state_from_key(params, jax.random.PRNGKey(seed))


def state_from_key(params: SimParams, key) -> SimState:
    """`init_state` of a run whose `PRNGKey(seed)` is `key`: the one leaf a
    seed decides besides the key is `hb_phase` (traceable: ops/runs.py maps
    it over the keys of a batch's runs)."""
    import jax

    params.validate()
    n, c = params.n, params.capacity
    key, k_phase = jax.random.split(key)
    state = SimState(
        mesh_mask=jnp.zeros((n, c), dtype=bool),
        fanout_mask=jnp.zeros((n, c), dtype=bool),
        fanout_expire=jnp.zeros((n,), dtype=jnp.float32),
        backoff_until=jnp.zeros((n, c), dtype=jnp.float32),
        fmd=jnp.zeros((n, c), dtype=jnp.float32),
        slow_penalty=jnp.zeros((n, c), dtype=jnp.float32),
        alive=jnp.ones((n,), dtype=bool),
        subscribed=jnp.ones((n,), dtype=bool),
        hb_phase=jax.random.uniform(k_phase, (n,)) * params.heartbeat_ms,
        uplink_free_ms=jnp.zeros((n,), dtype=jnp.float32),
        rx_free_ms=jnp.zeros((n,), dtype=jnp.float32),
        warm_offset_ms=jnp.full((n,), 3.4e38, dtype=jnp.float32),
        t_ms=jnp.asarray(0.0, dtype=jnp.float32),
        key=key,
        grafts=jnp.zeros((n,), dtype=jnp.int32),
        grafts_rx=jnp.zeros((n,), dtype=jnp.int32),
        prunes=jnp.zeros((n,), dtype=jnp.int32),
        prunes_rx=jnp.zeros((n,), dtype=jnp.int32),
        bytes_tx=jnp.zeros((n,), dtype=jnp.float32),
        bytes_rx=jnp.zeros((n,), dtype=jnp.float32),
        dup_rx=jnp.zeros((n,), dtype=jnp.int32),
        ihave_tx=jnp.zeros((n,), dtype=jnp.int32),
        iwant_tx=jnp.zeros((n,), dtype=jnp.int32),
        ihave_rx=jnp.zeros((n,), dtype=jnp.int32),
        iwant_rx=jnp.zeros((n,), dtype=jnp.int32),
        idontwant_tx=jnp.zeros((n,), dtype=jnp.int32),
        idontwant_rx=jnp.zeros((n,), dtype=jnp.int32),
    )
    return state if repair_inert(params) else arm_repair(state)


# The five mesh-repair leaves. A state holds them only where repair is armed
# (a None field is an empty pytree subtree): an inert run's programs neither
# read nor write them, and a jit that carried them would pay five
# pass-through buffers (the r05 BENCH regression was exactly these riding
# the publish and heartbeat jits). They are made in arm_repair and nowhere
# else.
REPAIR_LEAVES = ("px_pool", "starve_hb", "evictions", "px_grafts", "redials")


def repair_inert(params: SimParams) -> bool:
    """True iff no compiled path can read or write the repair leaves —
    eviction, PX-on-PRUNE, and re-dial are all off (they gate every repair
    branch behind Python-static `if params.<knob>:` conds)."""
    return not (params.evict or params.px or params.redial)


def arm_repair(state: SimState) -> SimState:
    """`state` with the repair leaves: its own where it holds them, fresh
    ones (empty PX pool, zero counters) where it was made inert. init_state
    arms through here; the other caller is the one real transition, a state
    an inert window carried into a window that arms repair (a campaign
    trial's recovery). Works on a stacked (trials, N) state too."""
    if state.px_pool is not None:
        return state
    like = state.grafts  # (..., N) int32
    return state.replace(
        px_pool=jnp.full(like.shape + (PX_POOL_WIDTH,), -1, jnp.int32),
        # a buffer each, not one shared: a donating jit may take them
        **{k: jnp.zeros_like(like) for k in REPAIR_LEAVES if k != "px_pool"})


def disarm_repair(state: SimState) -> SimState:
    """The way back: `state` without the repair leaves, for one that leaves
    the window that armed repair and goes on under inert params (read its
    counters first: repair_totals)."""
    return state.replace(**dict.fromkeys(REPAIR_LEAVES))


def require_repair(state: SimState) -> None:
    """Trace-time guard of every stage that reads a repair leaf."""
    if state.px_pool is None:
        raise ValueError(
            "this program reads the mesh-repair leaves (evict / px / redial "
            "or a recovery window) and the state holds none: it was made "
            "for params that arm no repair. Pass it through "
            "ops.state.arm_repair(state) first.")


def repair_totals(state: SimState) -> dict[str, int]:
    """Host-side totals of the three repair activity counters; zeros for a
    state that holds no repair leaves (nothing could have counted)."""
    return {k: 0 if getattr(state, k) is None
            else int(np.asarray(getattr(state, k)).sum())
            for k in ("evictions", "px_grafts", "redials")}


# Per-attacker controller leaves for the ADAPTIVE adversary (ops/adversary.py
# AdaptivePolicy). The same rule as the repair leaves, as a struct of its
# own: instead of riding SimState where the policy is off, the controller
# is a SEPARATE pytree threaded through the armed scan carry
# (run_adaptive_heartbeats / run_adaptive_recovery_heartbeats) and never
# materialized at all on the disabled path — the delegating wrappers call the
# base runners with the exact argument list, so the default trace cannot grow
# a dead carry leaf by construction (the r05 regression class).
ADAPTIVE_LEAVES = ("viol_est", "regrafts", "px_injected", "throttled_hb")


@struct.dataclass
class AdaptiveCtrl:
    """On-device adaptive-attacker controller state, (N,) per peer (honest
    rows stay zero). `viol_est` is the attacker's own running estimate of
    the worst honest-side slow_penalty counter any of its edges carries —
    updated from its OWN tx view each round (backoff is symmetric on both
    endpoints of an edge; the attacker's mesh bit over-approximates the
    honest one, so the estimate is conservative: est >= max_j counter_j and
    the duty cycle never overshoots the graylist floor). The other leaves
    are attacker-side telemetry counters (ops/telemetry.py channels)."""

    viol_est: jnp.ndarray      # (N,) f32: self-estimated violation counter
    regrafts: jnp.ndarray      # (N,) i32: backoff-expiry re-graft attempts
    px_injected: jnp.ndarray   # (N,) i32: sybil ids planted in px_pool rows
    throttled_hb: jnp.ndarray  # (N,) i32: rounds spent duty-cycled OFF


def init_adaptive_ctrl(n: int, like=None) -> AdaptiveCtrl:
    """Zeroed controller carry for a fresh trial window. `like`: an (n,)
    array of the trial the carry joins; inside a shard_map body the zeros
    then vary over the same manual axes it does, which a scan carry needs."""
    if like is None:
        like = jnp.zeros((n,), dtype=jnp.int32)
    return AdaptiveCtrl(
        viol_est=jnp.zeros_like(like, dtype=jnp.float32),
        regrafts=jnp.zeros_like(like, dtype=jnp.int32),
        px_injected=jnp.zeros_like(like, dtype=jnp.int32),
        throttled_hb=jnp.zeros_like(like, dtype=jnp.int32),
    )


def graph_arrays(graph) -> dict:
    """Move a ConnGraph's arrays to device once (jnp constants per epoch)."""
    return {
        "conns": jnp.asarray(graph.conns),
        "rev": jnp.asarray(graph.rev),
        "out_mask": jnp.asarray(graph.out_mask),
    }


def topo_arrays(topology, payload_bytes: int) -> dict:
    return {
        "stage": jnp.asarray(topology.stage_of_peer),
        "lat_ms": jnp.asarray(topology.latency_ms),
        "tx_ms": jnp.asarray(
            topology.tx_ms_per_peer(payload_bytes).astype(np.float32)
        ),
    }
