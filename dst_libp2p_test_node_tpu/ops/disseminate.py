"""Message dissemination as an earliest-arrival-time fixpoint (the hot path).

The reference measures one thing above all: per-message dissemination latency
— a publisher embeds a nanosecond timestamp, every receiver logs
`<msgId> milliseconds: <delay>` (gossipsub-queues/main.nim:126-154), and awk
aggregates (shadow/summary_latency*.awk). Shadow produces those delays with a
full per-packet discrete-event simulation; we produce them as the fixpoint of

    t_rx[q] = max( min over senders p of
                     max(t_rx[p] + proc, uplink_free[p])
                     + (rank_p(q)+1) * tx_p + LAT[stage_p, stage_q],
                   rx_free[q] + rx_ms[q] )

where rank_p(q) is q's position in p's randomized send order (uplink
serialization: a peer forwarding B bytes to k mesh members occupies its own
uplink k times in sequence — Shadow's dominant queueing effect for 15 KB
messages, acknowledged by summary_latency_large.awk:20-24), LAT is the
stage-pair latency matrix from the topology, and uplink_free carries the
drain time of EARLIER messages (SimState): concurrent publishes queue
behind each other the way the reference's per-connection queues serialize
all in-flight traffic.

The data-carrying link traversal additionally pays TCP slow-start flight
dynamics (tcp_flights below): under Shadow the nodes run REAL TCP stacks
(regression/Dockerfile_amd64_shadow:3-11), so a transfer larger than the
~14.6 KB initial congestion window needs multiple RTT-gated flights and the
per-edge delivery latency becomes lat * (1 + 2*(flights-1)) — the flagship
15 KB message pays +1 RTT per hop, a 128 KB block +3. Publishes are seconds
apart, so windows slow-start-restart after idling (RFC 2861) and cold is
the default state; mesh fragments of one message ride a warmed back-to-back
stream, gossip answers restart cold. Control packets (IHAVE/IWANT/
IDONTWANT) fit the first window and keep the bare latency.

The outer max is the RECEIVER side of the same bandwidth story: Shadow
enforces host_bandwidth_down on every host (shadow/topogen.py:50-51), so a
copy of rx_ms[q] = bytes/bw_down drain time arriving while q's downlink is
still busy with earlier traffic completes only when that backlog clears
plus its own drain — the single-server queue completion
max(wire_arrival, busy_until + rx_ms). When the downlink is idle the copy
streams through concurrently with the sender's serialization (bw_down ==
bw_up per stage in the reference topology) and completes at its wire
arrival: no double-counted serialization. rx_free is carried in SimState
(write-back below folds ALL delivered copies — duplicates and gossip
answers included — through the queue in arrival order, exactly).
Cross-fragment rx contention inside one message is not modeled: same-sender
fragments are spaced k*tx >= rx_ms apart by the uplink queue, so only
interleaved different-sender duplicates could bind, a second-order effect.
Answered IWANTs SERIALIZE on the answering uplink (gossip_fold below): a
peer answering k IWANTs in one gossip round transmits the answers
back-to-back in IWANT-arrival order — sum, not max — and a round's backlog
spills into the next round's queue, the way the reference's per-connection
queues all drain through one host_bandwidth_up (main.nim:264-299). The DES
cross-check reproduces this through a chronological event heap (IHAVE
arrival -> IWANT -> single-server answer queue), written independently of
the fixpoint's sorted-prefix fold, so the differential suite discriminates
exactly this term. (Cross-fragment answer serialization within one message
remains uncoupled — fragment lanes are vmapped — matching the per-fragment
independence of everything else inside a message.)
The whole model is differentially validated against that independent
host-side event-queue simulator (tests/test_des_crosscheck.py).

The iteration is a *pull*: each peer gathers its neighbors' sender-side
candidate times through the reverse-slot map (ops/graph.py) — two gathers and
a row-min, no scatter, no dynamic shapes. Because arrival times decrease
monotonically, the fixpoint equals the discrete-event result for this link
model. The fixpoint runs twice per fragment: once to discover each peer's
first sender, then again with the back-edge removed from the send order (the
reference never forwards a message back to the peer that delivered it, so
that uplink slot is never occupied).

IHAVE/IWANT gossip joins the same fixpoint as extra candidate edges
quantized to the emitter's heartbeat ticks (IHAVE -> IWANT -> message =
3 link traversals + one serialization). Targets re-sample EVERY heartbeat
over the mcache history window (history_gossip rounds, main.nim:259,283);
since each round's offer grows by one heartbeat, the window collapses to a
per-edge first-sampled-round offset inside the fixpoint. Heartbeat phases
are persistent per-node state. Post-fixpoint, a single accounting pass
yields duplicate deliveries, per-peer tx/rx bytes, per-peer bidirectional
IHAVE/IWANT/IDONTWANT counts, IDONTWANT suppression
(go-test-node/main.go:165), v1.1 score-threshold gating, and
firstMessageDeliveries score credit.

Fragmentation (FRAGMENTS > 1, main.nim:177-179) runs everything once per
fragment lane: vmapped where one row gather with every lane's table in the
gathered row fits the gather budget (ops/pull.py: the lanes iterate
together, a joint pull about one lane's price), one lane at a time in a
rolled loop where only a single lane's does (fragments_in_sequence); a relay's uplink additionally carries the f earlier
fragments (f * k_p extra serialization slots) and a message completes at a
receiver when its LAST fragment lands (main.nim:147-148).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from flax import struct

from ..parallel.exchange import (
    build_recv_constants,
    converge_recv,
    converge_sharded,
)
from .pull import (
    exceeds_budget,
    neighbor_pull_bool,
    neighbor_pull_min,
    neighbor_rows_min,
    neighbor_update_min,
    permute_rows,
    pull_moved_min,
    reciprocal_pull_bool,
    reciprocal_pull_min,
    relax_route,
)
from .state import SimParams, SimState

INF = jnp.float32(3.4e38)
# any warm_offset_ms at or above this is "no valid carry" (init / churned /
# never-arrived peers store INF); real arrival offsets are orders of
# magnitude smaller
WARM_VALID = jnp.float32(1e30)

# TCP retransmission model (loss_mode="tcp"). Under Shadow, nodes run real
# TCP stacks over the lossy GML edges (regression/Dockerfile_amd64_shadow:
# 3-11 — LD_PRELOAD interposition of real sockets), so per-packet loss
# mostly becomes ADDED LATENCY, not lost coverage: the segment is
# retransmitted after an RTO, doubling per RFC 6298 on repeat failures.
#   RTO_edge      = max(RTO_MIN_MS, 1.5 * RTT)   (SRTT + 4*RTTVAR with
#                   RTTVAR ~ RTT/8 at steady state; Linux clamps at
#                   tcp_rto_min = 200 ms)
#   retx delay(j) = sum_{k<j} RTO * 2^k = RTO * (2^j - 1)   after j failures
#   j ~ Geometric(p): P(j >= k) = p^k, sampled once per FRAGMENT per
#                   directed edge (each fragment is a distinct GossipSub
#                   message upstream; per-packet re-draws are below the
#                   model's granularity)
#   j > MAX_RETRIES -> the copy is abandoned (prob p^(MAX_RETRIES+1);
#                   at topogen-scale loss rates this is negligible, so
#                   coverage stays ~1.0 and the loss knob moves p99 —
#                   the Shadow-faithful behavior). Retransmitted bytes are
#                   not re-billed to the uplink queue (second-order next
#                   to the >= 200 ms RTO stall; documented approximation).
RTO_MIN_MS = 200.0
MAX_RETRIES = 6


def tcp_flights(nbytes: int, params) -> int:
    """Number of RTT-gated TCP flights a cold-started transfer of `nbytes`
    needs. Under Shadow the nodes run real TCP stacks
    (regression/Dockerfile_amd64_shadow:3-11): the first flight carries at
    most initcwnd_segments * mss_bytes (Linux IW10, RFC 6928) and the
    congestion window doubles each RTT while slow-starting, so after F
    flights IW * (2^F - 1) bytes are out. Messages are published seconds
    apart (topogen delay_seconds), so connections slow-start-restart after
    idling (RFC 2861) and EVERY data transfer starts cold — this is the
    default state, not a corner case. The large-message statistic the
    reference acknowledges as TxTime-confounded (summary_latency_large.awk:
    20-24) is exactly this multi-flight effect.

    Closed form: smallest F >= 1 with IW * (2^F - 1) >= nbytes.
    (The DES cross-check derives the same count with an independent loop
    formulation — tests/test_des_crosscheck.py.)"""
    import math

    if not params.slow_start:
        return 1
    iw = params.mss_bytes * params.initcwnd_segments
    if nbytes <= iw:
        return 1
    f = max(1, math.ceil(math.log2(nbytes / iw + 1.0)))
    # integer-exact boundary correction (the float log can land a hair off
    # when nbytes sits exactly on a window-sum boundary)
    while f > 1 and iw * (2 ** (f - 1) - 1) >= nbytes:
        f -= 1
    while iw * (2 ** f - 1) < nbytes:
        f += 1
    return f


@struct.dataclass
class DisseminationResult:
    t_rx_ms: jnp.ndarray       # (N,) absolute full-receipt time, INF if never
    delay_ms: jnp.ndarray      # (N,) t_rx - t0, INF if never
    received: jnp.ndarray      # (N,) bool (all fragments)
    sends: jnp.ndarray         # (N,) int32 message copies sent by each peer
    copies_rx: jnp.ndarray     # (N,) int32 copies received (>=1 => received)
    ihave_sent: jnp.ndarray    # (N,) int32 IHAVEs sent per peer
    iwant_sent: jnp.ndarray    # (N,) int32 IWANTs sent per peer
    lost_tx: jnp.ndarray       # (N,) int32 transmitted copies the network
    #                            never delivered: loss_mode="tcp" abandons a
    #                            copy after MAX_RETRIES RTOs (prob
    #                            p^(MAX_RETRIES+1) per fragment-edge), the
    #                            "message" mode loses it outright. Lossy runs
    #                            verify the tcp-mode negligibility claim
    #                            against this counter instead of trusting it.
    answer_wait_max_ms: jnp.ndarray  # () float32 — bounded delivery mode
    #                            (params.serialize_answers=False) ONLY: the
    #                            max time any requested gossip answer waited
    #                            queued behind another at the final times —
    #                            the per-hop bound on how far an arrival
    #                            time may sit below the exact serialized
    #                            model's. 0.0 in the exact default mode
    #                            (the repair makes the times exact) and
    #                            whenever no answer ever queued. ALWAYS
    #                            finite: when announce rounds interleave the
    #                            per-round fold's bound does not cover the
    #                            interleaved corner — that condition is
    #                            reported separately in answer_interleaved
    #                            instead of the former INF poison (which
    #                            leaked invalid-JSON `Infinity` into bench
    #                            artifacts).
    answer_interleaved: jnp.ndarray  # () int32 — bounded mode: number of
    #                            fragment lanes whose gossip-answer rounds
    #                            INTERLEAVED at the final times (a round's
    #                            earliest requested IWANT arriving before
    #                            the previous round's latest), where the
    #                            fold's wait bar under-reports. 0 in exact
    #                            mode (interleaving routes to the global-
    #                            sort slow path and is repaired).
    converged: jnp.ndarray     # () bool — every fixpoint this result rode
    #                            (the per-fragment phase relaxations; in
    #                            exact mode also the serialized outer
    #                            iteration) reached self-consistency before
    #                            its iteration cap. False means some loop
    #                            was CUT at params.max_relax_iters and the
    #                            times/error bar may be off — previously
    #                            this was silently reported as exact.
    refine_passes: jnp.ndarray  # () int32 — exact mode only: refinement
    #                            iterations the serialized-answer repair
    #                            spent, max over fragment lanes (prefix
    #                            mode: Jacobi iterations of both phases;
    #                            after a fallback to the global-sort path,
    #                            the prefix iterations already spent plus
    #                            the serial outer passes). 0 whenever the
    #                            fast pipeline was kept (no queued answer
    #                            could have been a first delivery) and in
    #                            bounded / no-gossip mode. The tier-1
    #                            pass-count budget of the exactness
    #                            certificate pins this on canonical
    #                            topologies (tests/test_exact_prefix.py).
    counters: jnp.ndarray      # (11,) int32 — [fast_iters, refine_passes,
    #                            refined, fell_back, converged,
    #                            refined_serial, refine_lane_passes,
    #                            lanes_hinted, lanes_uncertified,
    #                            fast_sparse_iters, refine_sparse_passes],
    #                            and under churn (13,): [..., alive,
    #                            under_dlow]: how much
    #                            work the publish's fixpoints did and which
    #                            branches ran, packed so that the host
    #                            takes them in ONE device->host read
    #                            (runtime/simulator.record_from_result) and
    #                            the jit returns one leaf more, not ten.
    #                            The nine that are no field of their own
    #                            are the properties below.

    @property
    def fast_iters(self):
        """() int32 — loop iterations of the fast (unserialized) pipeline's
        fixpoints, summed over its phases, max over fragment lanes; the same
        count from all three engines (row_pull, converge_recv,
        converge_sharded)."""
        return self.counters[..., 0]

    @property
    def refined(self):
        """() bool — the serialized-answer repair branch ran (a queued
        answer could have been a first delivery, or rounds interleaved)."""
        return self.counters[..., 2] != 0

    @property
    def fell_back(self):
        """() bool — inside the repair, the prefix engine's certificate
        failed and the global-sort pipeline reran all fragments."""
        return self.counters[..., 3] != 0

    @property
    def refined_serial(self):
        """() bool — which engine the kept refinement came from: True the
        global-sort one (phases_serial: chosen off "row_pull" or under
        answer_queue_mode="serial", or the rerun after fell_back), False
        with `refined` the parallel-prefix one, False without it none."""
        return self.counters[..., 5] != 0

    @property
    def refine_lane_passes(self):
        """() int32 — the kept refinement's passes SUMMED over the fragment
        lanes (`refine_passes` is the deepest lane's): what the scope
        refine/per_fragment ran. Equal to `refine_passes` at one
        fragment."""
        return self.counters[..., 6]

    @property
    def lanes_hinted(self):
        """() int32 — fragment lanes whose own fast pipeline asked for the
        repair; every lane refines when one does, so `fragments` less this
        is the lanes refined for another's sake. 1..F whenever `refined`."""
        return self.counters[..., 7]

    @property
    def lanes_uncertified(self):
        """() int32 — lanes the parallel-prefix engine refined and could
        not certify; 0 unless `fell_back`."""
        return self.counters[..., 8]

    @property
    def fast_sparse_iters(self):
        """() int32 — of `fast_iters`, the iterations that delivered the
        offers of the rows that moved into the carried offer matrix
        (ops/pull.pull_moved_min) instead of pulling every row's: max over
        the fragment lanes, which take that side together. 0 off "row_pull"
        and under ops/pull.relax_route's size."""
        return self.counters[..., 9]

    @property
    def refine_sparse_passes(self):
        """() int32 — of `refine_passes`, the parallel-prefix passes that
        delivered their fold's receivers' times from the peers that moved
        in the pass before (ops/pull.neighbor_update_min) and gathered no
        row for them: max over the fragment lanes, which take that side
        together. 0 under ops/pull.relax_route's size and where the
        global-sort engine is the one chosen."""
        return self.counters[..., 10]

    @property
    def alive(self):
        """() int32 — peers that could send at this publish (alive and
        subscribed, and the fanout publisher). Under churn only
        (`SimParams.churn_*_per_hb`); None without."""
        return (self.counters[..., 11] if self.counters.shape[-1] > 11
                else None)

    @property
    def under_dlow(self):
        """() int32 — of those, the peers whose valid mesh degree was under
        D_low at this publish: what the heartbeats' repair had not yet
        mended. Under churn only; None without."""
        return (self.counters[..., 12] if self.counters.shape[-1] > 11
                else None)


def _stage_select(stage: jnp.ndarray, n_stages: int, conns: jnp.ndarray,
                  rev: jnp.ndarray) -> jnp.ndarray:
    """(N, C, S+1) one-hot of each neighbor slot's stage id. The naive
    2-index gather lat[stage[p], stage[conns[p,i]]] costs ~60 ms at 100k
    (scalar gathers); instead: pull each neighbor's stage id through the
    reverse map (ops/pull.py) and build a fused one-hot over the S+1-wide
    stage axis — all vectorized."""
    stage_iota = jnp.arange(n_stages, dtype=jnp.float32)
    stage_q = neighbor_pull_min(stage.astype(jnp.float32), conns, rev)
    return stage_q[..., None] == stage_iota


def edge_tables(stage, lat_ms, conns, rev, loss_stage=None):
    """Precompute the per-slot stage-pair tables disseminate() needs:
    lat_edge[p, i] = lat_ms[stage[p], stage[conns[p, i]]] (0 on pads) and,
    when loss_stage is given, the same contraction of the loss matrix.

    These are LOOP-INVARIANT ACROSS PUBLISHES (graph and topology are
    experiment constants) but were being rebuilt inside every disseminate
    call — 71.8 ms/publish at 100k peers, measured r4. The Simulator
    computes them once per experiment and passes them through
    disseminate(lat_edge=..., loss_edge=...); direct callers that skip
    them get the identical in-call fallback."""
    sel = _stage_select(stage, lat_ms.shape[0], conns, rev)
    lat_edge = jnp.where(sel, lat_ms[stage][:, None, :], 0.0).sum(axis=-1)
    loss_edge = None
    if loss_stage is not None:
        loss_edge = jnp.where(
            sel, loss_stage[stage][:, None, :], 0.0).sum(axis=-1)
    return lat_edge, loss_edge


@struct.dataclass
class AnswerTables:
    """Lat-sorted views of the connection slots — the static service order
    of the serialized answer-queue fold (gossip_fold). Like edge_tables,
    these depend only on (lat_edge, conns, rev): experiment constants rebuilt
    inside every publish until r6 — two stable (N, C) argsorts plus two
    take_alongs per message at the 100k bench shape, a measured slice of
    the accounting_s regression. Build once with answer_tables() and pass
    through disseminate(ans_tables=...); row-aligned, so a sharded run
    reshards them with the other edge constants."""

    perm_lat: jnp.ndarray     # (N, C) int32 lat-ascending slot permutation
    inv_lat: jnp.ndarray      # (N, C) int32 its inverse
    lat_sorted: jnp.ndarray   # (N, C) f32 slot latency in that order, INF pads
    conns_sorted: jnp.ndarray  # (N, C) int32 neighbor ids in that order
    rev_sorted: jnp.ndarray   # (N, C) int32 the reverse slot's POSITION in
    #                           the neighbor's lat order, inv_lat[conns, rev]
    #                           (-1 on pads): a table kept in lat order is
    #                           pulled to the receiver's slot layout as
    #                           reciprocal_pull_min(x_sorted, conns,
    #                           rev_sorted), with no un-permutation first
    #                           (_converge_prefix's pass)


@jax.jit
def answer_tables(lat_edge, conns, rev) -> AnswerTables:
    """Precompute the lat-sort tables of the answer fold (see AnswerTables).
    One dispatch: a Simulator builds them per experiment, and op by op the
    two permutations alone would be 2 x C dispatches."""
    slot_lat = jnp.where(conns >= 0, lat_edge, INF)
    perm_lat = jnp.argsort(slot_lat, axis=-1, stable=True)
    inv_lat = jnp.argsort(perm_lat, axis=-1, stable=True)
    # slot positions are < C, exact in f32: the float pull serves, its INF
    # on pads becomes -1
    pos = reciprocal_pull_min(inv_lat.astype(jnp.float32), conns, rev)
    return AnswerTables(
        perm_lat=perm_lat,
        inv_lat=inv_lat,
        lat_sorted=permute_rows(slot_lat, perm_lat),
        conns_sorted=permute_rows(conns, perm_lat),
        rev_sorted=jnp.where(pos < INF, pos, -1.0).astype(jnp.int32),
    )


def valid_edge_of(alive, subscribed, conns, rev):
    """`disseminate`'s `valid_edge`: per edge, connected AND the neighbor
    alive & subscribed (one row-gather pass). A caller whose liveness and
    membership stand still makes it once (runtime/simulator.py)."""
    return (conns >= 0) & neighbor_pull_bool(alive & subscribed, conns, rev)


@jax.jit
def valid_edge_at_publish(alive, subscribed, conns, rev, publisher):
    """`valid_edge_of` for one publish of a churned network, fused into one
    dispatch, with the publisher's liveness beside it (`publisher` is
    traced: one executable whoever publishes)."""
    return valid_edge_of(alive, subscribed, conns, rev), alive[publisher]


def _ranks_f32(priority: jnp.ndarray) -> jnp.ndarray:
    return jnp.argsort(jnp.argsort(priority, axis=-1), axis=-1).astype(jnp.float32)


def _mask_count_smallest(prio: jnp.ndarray, count: jnp.ndarray) -> jnp.ndarray:
    """Row mask of the `count[i]` smallest entries: rank(prio) < count
    without materializing ranks — one VALUE sort plus a per-row threshold
    gather instead of _ranks_f32's double key+payload argsort (the gossip
    sampler runs this once per mcache round, so the bench shape paid six
    argsorts per publish here). Fractional counts select ceil(count)
    entries, matching integer-rank < count. Strict < at the threshold
    drops boundary ties — for continuous uniform priorities a measure-zero
    deviation from the rank formulation (at worst one fewer sample drawn
    in an f32-collision row)."""
    c_ = prio.shape[-1]
    kk = jnp.ceil(count).astype(jnp.int32)
    s = jnp.sort(prio, axis=-1)
    thresh = jnp.take_along_axis(
        s, jnp.clip(kk, 0, c_ - 1)[:, None], axis=-1)
    thresh = jnp.where(kk[:, None] >= c_, INF, thresh)
    return prio < thresh


def _next_heartbeat(t, phase, hb_ms):
    """First heartbeat tick of a peer strictly after time t (per-peer phase —
    nodes start at different wall times, so ticks are unaligned)."""
    return (jnp.floor((t - phase) / hb_ms) + 1.0) * hb_ms + phase


def fixpoint_formulation(conns_shape, mesh=None) -> str:
    """Which formulation of the arrival-time fixpoint `disseminate` traces
    for this shape — decided at trace time from the mesh and the row-gather
    memory budget of ONE pull (ops/pull.exceeds_budget), nothing else; the
    fragment count decides only whether the lanes run together
    (fragments_in_sequence):

      "recv_sharded"  parallel/exchange.converge_sharded: receiver-side
                      constants under shard_map, one all-gather of t per
                      iteration, then exchange._src_gather
      "recv"          parallel/exchange.converge_recv: the same expression
                      on one device (the row-gather intermediate would not
                      fit the budget — the 1M-peer class)
      "row_pull"      sender-side offers + ops/pull.reciprocal_pull_min's
                      whole-row gather per iteration (one device, in budget)
    """
    if mesh is not None:
        return "recv_sharded"
    if exceeds_budget(jnp.float32, conns_shape):
        return "recv"
    return "row_pull"


def fragments_in_sequence(conns_shape, fragments: int, mesh=None) -> bool:
    """Whether a one-device publish takes its fragment lanes one at a time
    (one rolled loop over the fragment axis) instead of vmapping them: where
    the row pull of all `fragments` lanes at once would pass the gather
    budget and one lane's would not. Vmapped lanes share ONE row gather
    with every lane's table in the gathered row (ops/pull._lanes_in_the_row),
    so what is weighed is that packed row, N * C * roundup(F * C, 128) * 4
    bytes: at 100,000 x 40, 2.05 GB a lane, 4.1 GB for four or five lanes,
    6.1 GB for nine, all inside 6 GiB, so every `topogen -f` choice vmaps
    there (until PR 41 the lanes gathered one by one, 8.19 GB for four, and
    ran in sequence). Past the budget each lane keeps the "row_pull"
    formulation and the engines built on it, one after another; a shape
    whose single pull is past the budget is "recv" whatever its fragments,
    and a mesh unrolls its lanes as it always did."""
    return (mesh is None
            and exceeds_budget(jnp.float32, conns_shape, fragments)
            and not exceeds_budget(jnp.float32, conns_shape))


def lanes_in_pull(conns_shape, fragments: int, mesh=None) -> int:
    """How many fragment lanes one row gather of the publish's fixpoints
    carries: `fragments` where the lanes are vmapped on "row_pull" (their
    tables side by side in the gathered row), 1 at one fragment, in
    sequence, on a mesh, and where no row is gathered ("recv")."""
    packed = (fixpoint_formulation(conns_shape, mesh) == "row_pull"
              and not fragments_in_sequence(conns_shape, fragments, mesh))
    return fragments if packed else 1


@partial(
    jax.jit,
    static_argnames=("params", "payload_bytes", "fragments", "with_gossip",
                     "mesh", "with_fanout", "return_plan", "loss_mode"),
)
def disseminate(
    state: SimState,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    stage: jnp.ndarray,
    lat_ms: jnp.ndarray,
    bw_up_mbit_per_stage: jnp.ndarray,
    publisher,
    t0_ms,
    params: SimParams,
    payload_bytes: int,
    fragments: int = 1,
    with_gossip: bool = True,
    mesh=None,
    loss_stage=None,
    with_fanout: bool = False,
    return_plan: bool = False,
    bw_down_mbit_per_stage=None,
    loss_mode: str = "tcp",
    lat_edge=None,
    loss_edge=None,
    ans_tables=None,
    valid_edge=None,
    censor_edge=None,
    pull_bands=None,
):
    """Propagate one application message (all fragments) through the mesh.

    Returns (DisseminationResult, new_state). new_state carries advanced RNG,
    firstMessageDeliveries credit, and byte/duplicate counters.

    The fixpoint itself runs receiver-side (parallel/exchange.py): per-edge
    constants are gathered once, then each iteration touches only the (N,)
    arrival-time vector. With `mesh` (a 1-D jax.sharding.Mesh over the peer
    axis) the iteration runs under shard_map — one t_rx all-gather + one
    convergence-bit psum per iteration over ICI; without it, the same
    expression on one device.

    `loss_stage`: optional (S+1, S+1) per-stage-pair packet-loss rate
    (topogen's packet_loss edges, shadow/topogen.py:21,56). Pass None
    (not an all-zero matrix) for the lossless fast path. Two models,
    selected by `loss_mode`:

      "tcp" (default, Shadow-faithful): nodes under Shadow run real TCP
      stacks over
      the lossy edges (regression/Dockerfile_amd64_shadow:3-11), so loss
      becomes LATENCY — the copy is redelivered after a geometric number
      of RTO-doubling retransmissions (constants above). Coverage stays
      ~1.0 and p99 inflates, which is what a lossy topogen `-l` run of the
      reference measures.

      "message" (QUIC-unreliable-style): each directed edge
      independently fails to carry the whole message with its loss
      probability; mesh redundancy then degrades coverage gracefully.
      Kept for studying datagram-transport behavior and as the coverage
      stressor the gossip-recovery tests use.

    Either way a lost/delayed copy keeps its uplink queue slot and its
    tx-byte accounting — the transmission happened.

    `return_plan`: additionally return the message's sampled "plan" — the
    send sets, rank priorities, per-round gossip targets, loss survivals,
    phases and uplink occupancy this call drew — as a third output. This is
    the seam for the independent discrete-event cross-check
    (tests/test_des_crosscheck.py): the DES replays the exact same model
    inputs through an event queue written independently of the fixpoint.

    `with_fanout`: the publisher is NOT subscribed to the topic (gossipsub
    v1.1 fanout publish). It sends to its persistent fanout set — up to D
    connected topic peers, reused across publishes and topped back up to D
    at each publish (replenishFanout's effect at the moment it matters),
    expiring fanout_ttl_ms after the last fanout publish (heartbeat_step
    drops expired sets). With flood_publish the publisher floods all topic
    peers as usual, but the fanout set is still maintained, matching
    nim-libp2p's publish() which updates fanout in the unsubscribed branch
    regardless of floodPublish. The caller decides with_fanout from the
    publisher's subscription (host-side; subscription is publish-path
    static), keeping the subscribed-publisher compile unchanged.

    `pull_bands`: the hoisted `ops/pull.PullBands` of this graph
    (make_pull_bands; a table like `ans_tables`, under the same contract,
    and with gossip made with that table's `conns_sorted` and `rev_sorted`).
    Given, every row pull of the fixpoints, the folds, the attribution and
    the accounting fetches slots [0, C1) of every row and the rest of the
    heavy rows only, and returns what it returns without, bit for bit; None
    (the default) is the whole-width program.
    """
    # device scopes (jax.named_scope: metadata only, no operation is added):
    # `sample` is everything drawn or hoisted before the fixpoints run
    with jax.named_scope("sample"):
        n, c = conns.shape
        extra = (1 if loss_stage is not None else 0) + (1 if with_fanout else 0)
        keys = jax.random.split(state.key, 3 + extra)
        key, k_rank, k_gossip = keys[0], keys[1], keys[2]
        nxt = 3
        if loss_stage is not None:
            k_loss = keys[nxt]
            nxt += 1
        if with_fanout:
            k_fan = keys[nxt]

        frag_bytes = max(payload_bytes // fragments, 16)
        tx_ms = (frag_bytes * 8.0) / (bw_up_mbit_per_stage[stage] * 1e6) * 1e3  # (N,)
        # receiver-side drain time of one copy on each peer's downlink. The
        # reference topology sets host_bandwidth_down == host_bandwidth_up per
        # stage (shadow/topogen.py:50-51); pass bw_down_mbit_per_stage to model
        # asymmetric links.
        bw_down = (bw_up_mbit_per_stage if bw_down_mbit_per_stage is None
                   else bw_down_mbit_per_stage)
        rx_ms = (frag_bytes * 8.0) / (bw_down[stage] * 1e6) * 1e3          # (N,)
        # downlink clamp for THIS message's first delivery: nothing completes at
        # q before q's downlink drains earlier messages plus this copy
        rx_const = state.rx_free_ms + rx_ms                                # (N,)

        # per-slot link latency lat[stage[p], stage[conns[p,i]]] (and the loss
        # contraction when needed): experiment constants — callers that loop
        # over publishes precompute them via edge_tables(); the fallback here
        # keeps one-shot calls self-contained. NOTE: the stage pull runs once
        # at top level, OUTSIDE the fragment lanes — batch_factor stays 1 (the
        # per-lane pulls below pass `lanes`).
        if lat_edge is None or (loss_stage is not None and loss_edge is None):
            lat_edge_c, loss_edge_c = edge_tables(
                stage, lat_ms, conns, rev, loss_stage)
            if lat_edge is None:
                lat_edge = lat_edge_c                         # (N, C); 0 on pads
            if loss_edge is None:
                loss_edge = loss_edge_c

        # forwarding targets: mesh members; the publisher flood-publishes to every
        # connected topic peer (main.nim:279). The neighbor alive&subscribed
        # pull is publish-invariant between membership changes — callers that
        # loop over publishes precompute it (Simulator/bench maintain it and
        # invalidate on churn or subscription flips), saving one full
        # row-gather pass per publish. DYNAMIC-GRAPH CONTRACT: a hoisted
        # valid_edge (and lat_edge/loss_edge/ans_tables/pull_bands) is a pure
        # function of conns/rev — if the repair controller's dial path
        # extended the graph (ops/repair.py), the caller must re-derive all
        # of them against the mutated arrays (Simulator.rebind_graph) and the
        # warm-start carry in state.warm_offset_ms must already be INF
        # (repair_round writes it on any committed dial); passing stale
        # tables here silently publishes over the pre-repair edge set.
        has = conns >= 0
        if valid_edge is not None:
            valid = valid_edge
        else:
            valid = has & neighbor_pull_bool(
                state.alive & state.subscribed, conns, rev)
        # v1.1 score thresholds (nim-libp2p defaults; the reference comments the
        # overrides out, main.nim:276-278). With the default non-negative score
        # weights no peer can score below any threshold, so the whole block is
        # statically absent from the compiled step.
        thresholds_can_bind = params.slow_weight < 0.0 or params.fmd_weight < 0.0
        if thresholds_can_bind:
            sc = state.score(params)                       # my score of each nbr
            pub_ok = sc >= params.publish_threshold        # flood/fanout gate
            # graylist: the RECEIVER ignores traffic from peers it scores below
            # the threshold — pulled to the sender side it gates DELIVERY only
            # (the send still happens and is accounted), which is exactly the
            # `survive` semantics shared with packet loss below
            gray_ok = reciprocal_pull_bool(
                sc >= params.graylist_threshold, conns, rev)
        if loss_mode not in ("message", "tcp"):
            raise ValueError(f"unknown loss_mode {loss_mode!r}")
        retx_ms = None
        if loss_stage is not None:
            # one independent draw per (FRAGMENT, directed edge): each fragment
            # is a distinct GossipSub message upstream (main.nim:177-179 flips
            # the fragment byte precisely so the msgId hash differs), so its
            # packets face the lossy link independently — correlated
            # per-message draws would black out every fragment of a message on
            # an unlucky edge at once, which no packet-loss process does.
            # Memory note: the draws (and the derived retx/lat_deliver) are
            # (F, N, C) and live through the whole fragment vmap — generating
            # them inside the per-fragment body would not lower the peak,
            # since vmap batches all lanes anyway (and lanes taken in
            # sequence index them). At 1M peers this is
            # ~0.4 GB per f32 array per fragment; lossy runs at extreme N
            # should keep FRAGMENTS modest (the five BASELINE configs that
            # reach 1M are lossless and never allocate any of this).
            if loss_mode == "tcp":
                # geometric retransmission count per edge (see the model
                # constants above): P(j >= k) = p^k via the inverse-CDF
                # j = floor(log u / log p); j > MAX_RETRIES abandons the copy
                u = jnp.clip(jax.random.uniform(k_loss, (fragments, n, c)),
                             1e-12)
                safe_p = jnp.clip(loss_edge, 1e-9, 1.0 - 1e-9)
                j = jnp.where(
                    loss_edge > 0.0,
                    jnp.floor(jnp.log(u) / jnp.log(safe_p)),
                    0.0,
                )
                j = jnp.minimum(j, float(MAX_RETRIES + 1))
                survive = j <= float(MAX_RETRIES)
                rto = jnp.maximum(RTO_MIN_MS, 1.5 * 2.0 * lat_edge)
                retx_ms = jnp.where(
                    survive & (j > 0.0), rto * (jnp.exp2(j) - 1.0), 0.0)
            else:
                # whole-copy loss (see docstring): `survive` gates DELIVERY
                # only — a lost copy was still transmitted, so it keeps its
                # uplink queue slot and its tx-byte accounting; it just never
                # arrives
                survive = (jax.random.uniform(k_loss, (fragments, n, c))
                           >= loss_edge)
        else:
            survive = None
        # keep the loss-only draw separate from the graylist gate: lost_tx
        # counts copies the NETWORK dropped, and a receiver-side graylist
        # ignore is not a network loss (the bytes arrived and were discarded
        # above the transport) — folding gray_ok into the counter inflated
        # "network-lost" copies whenever the graylist was active
        survive_loss = survive
        if thresholds_can_bind:
            survive = gray_ok if survive is None else survive & gray_ok
        if censor_edge is not None:
            # adversarial per-edge DROP mask (ops/adversary.py): an in-mesh
            # censor silently withholds the copy. Same delivery-only semantics
            # as the graylist gate — and same exclusion from survive_loss, so
            # lost_tx keeps counting copies the NETWORK dropped. None (the
            # default pytree structure) keeps benign traces bit-identical.
            survive = (~censor_edge if survive is None
                       else survive & ~censor_edge)
        is_pub = jnp.arange(n) == publisher
        if with_fanout:
            # fanout set: still-valid unexpired members, topped back up to D
            # with fresh draws from the remaining connected topic peers. Computed
            # for every row (shape-static) but only the publisher's row is used
            # or written back.
            fan_active = (state.fanout_mask & valid
                          & (state.fanout_expire[:, None] > t0_ms))
            if thresholds_can_bind:
                # the v1.1 heartbeat drops fanout members scoring below
                # publishThreshold; checking at publish time is equivalent at
                # the moment it matters (same treatment as replenishment)
                fan_active = fan_active & pub_ok
            need_fan = jnp.maximum(
                float(params.d) - fan_active.sum(axis=-1).astype(jnp.float32), 0.0)
            fan_cand = valid & ~fan_active
            if thresholds_can_bind:
                fan_cand = fan_cand & pub_ok  # fanout selection skips low scorers
            fprio = jnp.where(fan_cand, jax.random.uniform(k_fan, (n, c)), INF)
            fan_row = fan_active | (
                fan_cand & (_ranks_f32(fprio) < need_fan[:, None]))

        tgt = state.mesh_mask & valid
        flood_set = valid
        if thresholds_can_bind:
            # publish (flood and fanout selection) skips peers the publisher
            # scores below publishThreshold
            flood_set = valid & pub_ok
        if with_fanout:
            pub_tgt = flood_set if params.flood_publish else fan_row
            tgt = jnp.where(is_pub[:, None], pub_tgt, tgt)
        elif params.flood_publish:
            tgt = jnp.where(is_pub[:, None], flood_set, tgt)

        # randomized send order per peer (one draw per message, standing in for
        # the reference's per-peer queue service order)
        rprio = jnp.where(tgt, jax.random.uniform(k_rank, (n, c)), INF)

        # gossip edge sampling: non-mesh connected topic peers; count =
        # max(D_lazy, gossip_factor * |candidates|)  (v1.1 heartbeat gossip).
        # The reference gossips EVERY heartbeat over the mcache history window
        # (history_gossip rounds, main.nim:259,283): each tick draws a FRESH
        # sample, so a peer missed in round h can be reached in round h+1 —
        # that re-sampling is what drives gossip recovery under loss/churn.
        g_cand = valid & ~tgt
        if thresholds_can_bind:
            # no IHAVE to peers scored below gossipThreshold
            g_cand = g_cand & (sc >= params.gossip_threshold)
        n_gc = g_cand.sum(axis=-1).astype(jnp.float32)
        g_count = jnp.maximum(float(params.d_lazy), params.gossip_factor * n_gc)
        n_rounds = params.history_gossip if with_gossip else 1
        gkeys = jax.random.split(k_gossip, n_rounds)
        g_tgt_w = jnp.stack([
            g_cand & _mask_count_smallest(
                jnp.where(g_cand, jax.random.uniform(gkeys[h], (n, c)), INF),
                g_count)
            for h in range(n_rounds)
        ])                                                  # (W, N, C)
        g_tgt = g_tgt_w.any(axis=0)
        # round offsets grow by a heartbeat each, so only the FIRST round an edge
        # is sampled can be its min offer — the multi-round term collapses to a
        # single (N, C) per-edge heartbeat offset inside the fixpoint (the full
        # per-round sets are still used for IHAVE/IWANT accounting below)
        g_off = jnp.min(
            jnp.where(g_tgt_w,
                      jnp.arange(n_rounds, dtype=jnp.float32)[:, None, None],
                      jnp.float32(n_rounds)),
            axis=0) * params.heartbeat_ms
        # heartbeat phase is a persistent per-NODE property (drawn once per run in
        # init_state), so gossip-arrival timing is consistent across messages
        hb_phase = state.hb_phase

        can_send = state.alive & state.subscribed
        if with_fanout:
            # the unsubscribed publisher originates (and gossips about) the
            # message even though it is not a topic member
            can_send = can_send | (is_pub & state.alive)

        # cross-message bandwidth contention: a sender's queue for THIS message
        # starts no earlier than the time its uplink drains traffic of earlier
        # messages (state write-back below; reference per-connection queues
        # serialize all in-flight traffic, main.nim:264-299)
        uplink = state.uplink_free_ms

        # effective per-edge delivery latency: the wire latency, times the TCP
        # slow-start flight count of the data transfer (tcp_flights above: a
        # transfer needing F cold-start flights pays F-1 extra RTTs = 2*lat
        # each), plus (tcp loss mode) the sampled retransmission stall.
        # Control messages (IHAVE/IWANT/IDONTWANT timing checks) keep the bare
        # lat_edge — they are single small packets inside the first window.
        # Mesh fragment f rides a connection the f earlier fragments of the
        # same back-to-back stream already warmed: its last byte departs in
        # flight F((f+1)*frag_bytes) of the cold-started stream. A gossip
        # answer is a single cold transfer — the non-mesh edge idled since the
        # previous message, so its window restarted. (Retransmission stalls
        # and flight counts compose additively; a real RTO inside slow start
        # would also halve the window — a second-order interaction left out.)
        ss_mesh = tuple(
            float(tcp_flights((f + 1) * frag_bytes, params) - 1)
            for f in range(fragments))
        ss_ans = float(tcp_flights(frag_bytes, params) - 1)
        ss_scale = jnp.asarray([1.0 + 2.0 * e for e in ss_mesh], jnp.float32)
        ans_scale = jnp.float32(1.0 + 2.0 * ss_ans)

    def _frag_slice(x, frag_idx):
        """Per-fragment view of a possibly-(F, N, C) array. Loss/retx draws
        are per fragment (leading axis); graylist-only survive masks are
        (N, C), shared across fragments."""
        if x is None or x.ndim == 2:
            return x
        return x[frag_idx.astype(jnp.int32)]

    def _ld_mesh(frag_idx):
        """Mesh-edge delivery latency of this fragment (slow-start flights
        x wire latency + sampled retransmission stall)."""
        ld = lat_edge * ss_scale[frag_idx.astype(jnp.int32)]
        r = _frag_slice(retx_ms, frag_idx)
        return ld if r is None else ld + r

    def _ld_ans(frag_idx):
        """Gossip-answer delivery latency (cold-start flights; same
        per-edge retransmission draw as the mesh copy — one draw per
        (fragment, edge), a documented approximation: the answer is a
        rare duplicate of data the mesh already moved, so an independent
        re-draw would change only the tail of a tail)."""
        ld = lat_edge * ans_scale
        r = _frag_slice(retx_ms, frag_idx)
        return ld if r is None else ld + r

    formulation = fixpoint_formulation(conns.shape, mesh)
    in_sequence = fragments_in_sequence(conns.shape, fragments, mesh)
    if pull_bands is not None and formulation != "row_pull":
        raise ValueError(
            f"pull_bands serve the row_pull formulation, not {formulation}")

    def _banded(name, index):
        """The index array a lane's row pull goes through: `index`, or its
        two bands where the caller hoisted them (ops/pull.Banded)."""
        return index if pull_bands is None else pull_bands.of(name)

    p_conns, p_rev = _banded("conns", conns), _banded("rev", rev)
    # how many lanes' pulls are live at once: what every pull's budget
    # dispatch must see (ops/pull.exceeds_budget's batch_factor)
    lanes = 1 if in_sequence else fragments

    def _per_fragment(fn, *xs, batched):
        """fn(*lane) on every fragment lane of the (F, ...) arrays `xs`,
        its outputs stacked on a leading fragment axis. In sequence
        (fragments_in_sequence): ONE rolled loop, so that one lane's
        intermediates are live and one copy of fn is compiled. Else the
        vmap where `batched`: the lanes' loops run jointly, as long as the
        slowest lane's, a finished lane's carry held, and every joint pull
        is one row gather with all the lanes in the row (ops/pull.py), about
        one lane's price. Else F unrolled copies (shard_map does not nest
        under vmap)."""
        with jax.named_scope("per_fragment"):
            if in_sequence:
                return jax.lax.map(lambda lane: fn(*lane), xs)
            if batched:
                return jax.vmap(fn)(*xs)
            outs = [fn(*(x[i] for x in xs)) for i in range(fragments)]
            return tuple(jnp.stack(x) for x in zip(*outs))

    # ---- serialized gossip-answer machinery --------------------------------
    # Static service order for the per-round queue fold: within a round all
    # of a sender's IWANTs arrive at A_h + 2*lat (A_h shared per sender-
    # round), so arrival order IS lat order — a permutation of each row
    # that never changes across fragments, phases or estimates. Sorting
    # once here turns every fold into elementwise work plus within-row
    # permutations (the r5 bench catch: per-estimate global argsorts
    # cost more than the whole r4 publish). As take_along_axis those
    # permutations were XLA's general gather, 39.7 ms apiece at 100k x 40
    # and 6.9 of a publish's 8.3 s with the fold's t[conns_sorted]
    # (26.8 ms); as ops/pull.permute_rows they are selects, 0.28 ms, and
    # that lookup a row pull, 8.6 ms (PR 28, TPU v5 lite; PERF.md). The
    # refinement loop does none of them: it stays in the lat order
    # (_converge_prefix). The sort itself is an
    # EXPERIMENT constant (lat_edge, conns and rev only): callers that loop over
    # publishes precompute it via answer_tables() — the in-call fallback
    # keeps one-shot calls self-contained (same contract as edge_tables).
    if with_gossip:
        with jax.named_scope("sample"):
            if ans_tables is None:
                ans_tables = answer_tables(lat_edge, conns, rev)
            perm_lat = ans_tables.perm_lat                       # (N, C)
            inv_lat = ans_tables.inv_lat
            lat_sorted = ans_tables.lat_sorted
            p_conns_sorted = _banded("conns_sorted", ans_tables.conns_sorted)
            p_rev_sorted = _banded("rev_sorted", ans_tables.rev_sorted)
            gw_sorted = [
                permute_rows(g_tgt_w[h], perm_lat) for h in range(n_rounds)
            ]

    def _sorted_frag(x, frag_idx):
        """Per-fragment slice of a (F/None, N, C) array, in lat order."""
        xs = _frag_slice(x, frag_idx)
        return None if xs is None else permute_rows(xs, perm_lat)

    def _round_req(h, tick, live, q_t, lat, gw_h, sv):
        """THE request/announce semantics of the serialized answer model,
        shared verbatim by the lat-sorted fold and the global-sort exact
        path (one copy, per the r5 review): round h's IHAVE leaves at
        A_h = max(tick + h*hb, uplink); a sampled live edge is REQUESTED
        iff the receiver still lacks the message when the IHAVE lands
        (strictly q_t > A_h + lat), and a lossy edge loses the IHAVE with
        the copy (survive-gated), so no IWANT ever comes back on it.
        Returns (a_h (N,1), sampled, requested) in the caller's layout."""
        a_h = jnp.maximum(
            tick + h * params.heartbeat_ms, uplink)[:, None]
        samp = gw_h & live[:, None]
        req = samp & (q_t > a_h + lat)
        if sv is not None:
            req = req & sv
        return a_h, samp, req

    def _fold_consts(frag_idx):
        """What the fold reads of a fragment's loss draws, in lat order:
        (sv_s, lda_s) — the survive mask (None when lossless) and the
        answers' delivery latency. Constant across estimates, so a loop
        over estimates (_converge_prefix) builds them once, outside it."""
        sv_s = _sorted_frag(survive, frag_idx)
        retx_s = _sorted_frag(retx_ms, frag_idx)
        lda_s = lat_sorted * ans_scale
        if retx_s is not None:
            lda_s = lda_s + retx_s
        return sv_s, lda_s

    def gossip_fold(t_rx, frag_idx):
        """gossip_fold_sorted brought back to the slot layout: the form
        every caller outside _converge_prefix's loop reads (see there for
        the tuple)."""
        g_sorted, req_any_s, drain, mixed, wait_max = gossip_fold_sorted(
            t_rx, *_fold_consts(frag_idx))
        return (permute_rows(g_sorted, inv_lat),
                permute_rows(req_any_s, inv_lat), drain, mixed, wait_max)

    def _receiver_times(t_rx):
        """t_rx[conns_sorted]: every slot's receiver's time, lat order
        (pads are never sampled, so what a pad reads is never used): a
        per-peer lookup, which a row pull does for a third of the scalar
        gather's price (ops/pull.py) where that pull is in budget and the
        table on one device."""
        if formulation == "row_pull":
            return neighbor_rows_min(
                t_rx, p_conns_sorted, batch_factor=lanes)
        return t_rx[jnp.clip(p_conns_sorted, 0)]

    def gossip_fold_sorted(t_rx, sv_s, lda_s, q_t_s=None):
        """Exact serialized gossip-answer offers via the per-round fold.

        A peer answering several IWANTs serializes the answers on its
        uplink — the reference's per-connection queues all feed the host's
        single host_bandwidth_up under Shadow (main.nim:264-299,
        shadow/topogen.py:50-51) — a single-server queue in IWANT-arrival
        order, rounds chaining through the carried busy time. Processing
        round-by-round in the static lat order is EXACT as long as rounds
        don't interleave (a round's last requested arrival precedes the
        next round's first — true whenever the heartbeat exceeds the
        round-trip spread, i.e. always at reference heartbeats); the fold
        detects the interleaved corner and reports it in `mixed`, which
        routes the message to the global-sort slow path. Only requested
        jobs (receiver still lacking at the IHAVE, survive-gated) occupy
        the queue; every sampled edge still gets an offer — the time its
        answer WOULD arrive if requested — which is self-consistent
        because an offer can only bind for a still-lacking receiver.

        `sv_s`, `lda_s`: _fold_consts of the fragment. `q_t_s`: the
        receivers' times in lat order, from a caller that holds them
        (_converge_prefix's loop, which carries them); every other
        caller's fold fetches its own. The whole fold
        works in the LAT-SORTED layout and returns it: (g_sorted,
        req_any_s, drain, mixed, wait_max) — per-edge absolute offers (INF
        where no sampled live edge) and answered flags, both by position
        in the sender's lat order; per-peer answer queue drain (0 if
        none), the scalar interleave flag, and the scalar MAX WAIT any
        requested answer spent queued behind another (serve - arrival) —
        the per-hop error bound of the bounded delivery mode
        (serialize_answers=False)."""
        base = t_rx + params.proc_delay_ms
        tick = _next_heartbeat(base, hb_phase, params.heartbeat_ms)  # (N,)
        live = can_send & (t_rx < INF)
        if q_t_s is None:
            q_t_s = _receiver_times(t_rx)
        txp = tx_ms[:, None]
        busy = uplink                               # (N,) queue busy carry
        g_sorted = jnp.full((n, c), INF)
        req_any_s = jnp.zeros((n, c), bool)
        had_req = jnp.zeros((n,), bool)
        mixed = jnp.bool_(False)
        wait_max = jnp.float32(0.0)
        prev_max_w = jnp.full((n,), -INF)
        for h in range(n_rounds):
            a_h, samp, req = _round_req(
                h, tick, live, q_t_s, lat_sorted, gw_sorted[h], sv_s)
            w = a_h + 2.0 * lat_sorted              # INF on pads/late slots
            # interleave check: this round's earliest requested arrival vs
            # the previous round's latest
            min_w = jnp.where(req, w, INF).min(axis=-1)
            mixed = mixed | jnp.any(min_w < prev_max_w - 1e-4)
            prev_max_w = jnp.maximum(
                prev_max_w, jnp.where(req, w, -INF).max(axis=-1))
            rf = req.astype(jnp.float32)
            R = jnp.cumsum(rf, axis=-1)
            m_term = jnp.where(req, w - (R - 1.0) * txp, -INF)
            M = jax.lax.cummax(m_term, axis=m_term.ndim - 1)
            M_prev = jnp.concatenate(
                [jnp.full_like(M[:, :1], -INF), M[:, :-1]], axis=-1)
            R_prev = jnp.concatenate(
                [jnp.zeros_like(R[:, :1]), R[:, :-1]], axis=-1)
            serve = jnp.maximum(
                w, jnp.maximum(busy[:, None], M_prev) + R_prev * txp)
            offer = serve + txp + lda_s
            g_sorted = jnp.minimum(g_sorted, jnp.where(samp, offer, INF))
            wait_max = jnp.maximum(
                wait_max, jnp.where(req, serve - w, 0.0).max())
            req_any_s = req_any_s | req
            r_last = R[:, -1]
            busy = jnp.where(
                r_last > 0.0,
                jnp.maximum(busy, M[:, -1]) + r_last * tx_ms, busy)
            had_req = had_req | (r_last > 0.0)
        g_sorted = jnp.where(g_sorted < INF, g_sorted, INF)  # overflow -> sentinel
        drain = jnp.where(had_req, busy, 0.0)
        return g_sorted, req_any_s, drain, mixed, wait_max

    def _gossip_jobs(t_rx, frag_idx):
        """Shared job builder of the serialized answer model: per sampled
        (round h, slot i) job, its IWANT arrival W = announce departure +
        2 link traversals, and whether it is REQUESTED — the receiver
        still lacks the message when that round's IHAVE lands (a lossy
        edge loses the IHAVE with the copy: one survive draw per
        fragment-edge, so no IWANT ever comes back on it)."""
        base = t_rx + params.proc_delay_ms
        tick = _next_heartbeat(base, hb_phase, params.heartbeat_ms)  # (N,)
        live = can_send & (t_rx < INF)
        sv = _frag_slice(survive, frag_idx)
        q_t = t_rx[jnp.clip(conns, 0)]           # (N, C) receiver times
        Ws, reqs = [], []
        for h in range(n_rounds):
            a_h, samp, r_h = _round_req(
                h, tick, live, q_t, lat_edge, g_tgt_w[h], sv)
            Ws.append(jnp.where(samp, a_h + 2.0 * lat_edge, INF))
            reqs.append(r_h)
        Wf = jnp.concatenate(Ws, axis=-1)        # (N, H*C), col = h*C + i
        rf = jnp.concatenate(reqs, axis=-1)
        return Wf, rf

    def _offers_from_serve(serve_u, frag_idx):
        """Per-edge delivery offer from per-job serve starts: + one tx
        serialization + the answer's cold-flight delivery latency; min
        over the edge's sampled rounds."""
        lda = _ld_ans(frag_idx)
        serve_hni = serve_u.reshape(n, n_rounds, c)
        g_abs = jnp.min(
            serve_hni + tx_ms[:, None, None] + lda[:, None, :], axis=1)
        # overflowed INF+finite arithmetic back to the sentinel
        return jnp.where(g_abs < INF, g_abs, INF)

    def gossip_serial_exact(t_rx, frag_idx):
        """Exact serialized gossip-answer offers at the estimate t_rx.

        A peer answering several IWANTs serializes the answers on its
        uplink — the reference's per-connection queues all feed the
        host's single host_bandwidth_up under Shadow (main.nim:264-299,
        shadow/topogen.py:50-51) — so the answers form a single-server
        queue in IWANT-arrival order (ties broken by (round, slot),
        matching the DES heap). Only requested jobs occupy the queue, but
        every sampled edge gets an offer = the time its answer WOULD
        arrive if requested (self-consistent: an offer can only bind for
        a receiver that was still lacking, i.e. requesting).

        Single-server queue fold in global W order (rounds chain
        naturally: a round's backlog spills into the next through the
        running busy time). For sorted arrivals the busy time after
        position j is B_j = M_j + R_j*tx with R the requested prefix
        count and M_j = cummax(W - (R-1)*tx over requested prefix); the
        job at position j starts at max(W_j, B_{j-1}).

        Returns (g_abs, req_any, drain). Runs the sorts unconditionally —
        callers reach it only on the hint-gated slow branch."""
        Wf, rf_b = _gossip_jobs(t_rx, frag_idx)
        req_any = rf_b.reshape(n, n_rounds, c).any(axis=1)
        rf = rf_b.astype(jnp.float32)
        txp = tx_ms[:, None]
        perm = jnp.argsort(Wf, axis=-1, stable=True)
        ws = jnp.take_along_axis(Wf, perm, axis=-1)
        rs = jnp.take_along_axis(rf, perm, axis=-1)
        R = jnp.cumsum(rs, axis=-1)
        m_term = jnp.where(rs > 0.0, ws - (R - 1.0) * txp, -INF)
        M = jax.lax.cummax(m_term, axis=m_term.ndim - 1)
        M_prev = jnp.concatenate(
            [jnp.full_like(M[:, :1], -INF), M[:, :-1]], axis=-1)
        R_prev = jnp.concatenate(
            [jnp.zeros_like(R[:, :1]), R[:, :-1]], axis=-1)
        serve = jnp.maximum(ws, M_prev + R_prev * txp)
        inv = jnp.argsort(perm, axis=-1, stable=True)
        serve_u = jnp.take_along_axis(serve, inv, axis=-1)
        drain = jnp.where(
            R[:, -1] > 0.0, M[:, -1] + R[:, -1] * tx_ms, 0.0)
        return _offers_from_serve(serve_u, frag_idx), req_any, drain

    def offers(t_rx, rank, k_p, frag_idx, send_mask, deliver_only=False,
               g_abs=None):
        """Arrival-time offers made by every peer on every neighbor slot.
        `deliver_only`: additionally mask copies the network loses — use for
        anything receiver-side (first-sender detection, delivery pulls);
        leave False for transmit-side accounting (sends, tx bytes).
        `g_abs`: the serialized gossip-answer offers of gossip_fold /
        gossip_serial_exact evaluated at the SAME t_rx (required when with_gossip)."""
        base = t_rx + params.proc_delay_ms
        start = jnp.maximum(base, uplink)
        ld = _ld_mesh(frag_idx)
        # uplink serialization: (rank+1) sends of this fragment, plus the
        # frag_idx earlier fragments each occupying k_p uplink slots
        queue = (rank + 1.0 + frag_idx * k_p[:, None]) * tx_ms[:, None]
        cand = start[:, None] + queue + ld
        live = can_send[:, None] & (t_rx[:, None] < INF)
        sm = send_mask
        if deliver_only and survive is not None:
            sv = _frag_slice(survive, frag_idx)
            sm = sm & sv
        cand = jnp.where(sm & live, cand, INF)
        if with_gossip:
            ga = g_abs
            if deliver_only and survive is not None:
                ga = jnp.where(sv, ga, INF)
            cand = jnp.minimum(cand, ga)
        return cand

    def pull(cand):
        """incoming[q, j] = offer made to q by the neighbor in its slot j
        (row-gather + fused slot select; see ops/pull.py for why). Runs
        inside the fragment vmap, so the memory dispatch must see how many
        lanes are live at once."""
        return reciprocal_pull_min(cand, p_conns, p_rev, batch_factor=lanes)

    def pull_sorted(cand_s):
        """pull() of a table whose rows are in the SENDER's lat order: the
        select takes the reverse slot's sorted position (rev_sorted). The
        result is in the receiver's slot layout, as pull()'s."""
        return reciprocal_pull_min(
            cand_s, p_conns, p_rev_sorted, batch_factor=lanes)

    def _converge_dyn(rank, k_p, frag_idx, t_pub, send_mask, t_init=None):
        """UNSERIALIZED fixpoint (every gossip answer rides its own uplink
        slot — exact whenever no answer queue forms; converge() below
        detects and repairs the rare serialized case). `t_init`: optional
        warm start — a pointwise upper bound on the true arrival times
        converges to the same unique fixpoint (Bellman-Ford from above,
        non-negative edge costs). A HEURISTIC seed (the cross-publish warm
        carry) may undershoot and stick; callers verify the returned
        self-consistency certificate (see phases_fast) and fall back cold.

        Returns (t, inc, ok, iters, few): the fixpoint, the deliver-only
        incoming-offer matrix of the loop's LAST pass — the no-change
        confirmation pass evaluates it at the final times, so the matrix
        the first-sender attribution and the certificate need rides out of
        the loop for FREE instead of costing another offers()+pull — the
        convergence bit (False = the iteration cap cut the loop and `inc`
        is one pass stale), the loop's iteration count (all three
        engines report it: DisseminationResult.fast_iters), and how many
        of those iterations delivered the moved rows' offers
        (DisseminationResult.fast_sparse_iters; 0 off "row_pull").

        The moved rows ("row_pull", where ops/pull.relax_route says the
        dense pull is worth avoiding). An entry inc[q, j] is a function of
        ONE sender's time, t[conns[q, j]], and of loop-invariant tables, and
        the relaxation is monotone, so an iteration changes the entries of
        the senders whose time moved in the iteration before and no other.
        The carry holds that mask, `moved`, and the body asks
        ops/pull.pull_moved_min for the pull: with at most K moved rows it
        evaluates the offers of those K rows and writes them into the
        carried `inc` at (conns[p, i], rev[p, i]); with more it pulls every
        row's, as ever. `inc` stays the dense body's at every iteration, by
        induction: before iteration 0 it is all INF and `moved` is t0 < INF
        (the publisher alone when cold; everybody under a `t_init`, so a
        warm or phase-2 loop starts dense), which is the dense pull at t0
        restricted to the rows that can offer (a sender at INF offers INF);
        if `inc` is the pull of the offers at the times before an
        iteration's update and `moved` the rows that update lowered, then
        overwriting the moved rows' entries with their offers at the new
        times gives the pull at the new times, the other entries being
        functions of times that did not change. So `t`, the returned `inc`,
        the convergence bit and the count are the dense loop's bit for bit,
        the cap's "one pass stale" included."""
        t0 = (jnp.full((n,), INF) if t_init is None else t_init
              ).at[publisher].set(t_pub)
        # arrival times are about DELIVERY: lost copies never relax an edge
        # (their queue slots still count — rank/k_p came from the unmasked
        # send set)
        sv = _frag_slice(survive, frag_idx)
        ld = _ld_mesh(frag_idx)
        deliver = send_mask if sv is None else send_mask & sv
        g_deliver = g_tgt if sv is None else g_tgt & sv
        if formulation == "recv_sharded":
            # sharded: receiver-local constants, one (N,) all-gather + one
            # psum per iteration over ICI (parallel/exchange.py)
            c = build_recv_constants(
                conns, rev, lat_edge, tx_ms, rank, k_p, frag_idx, deliver,
                can_send, g_deliver, g_off, hb_phase, uplink, rx_const,
                params.proc_delay_ms, params.heartbeat_ms, with_gossip,
                lat_deliver=ld, ld_gossip=_ld_ans(frag_idx),
            )
            with jax.named_scope("fixpoint"):
                return converge_sharded(
                    t0, c, params.max_relax_iters, mesh) + (jnp.int32(0),)
        if formulation == "recv":
            # large N (1M-peer class): the row-gather pull would blow the
            # memory budget and its 2-index fallback costs ~0.7 s/iteration —
            # switch to the receiver-side constant formulation: per-edge
            # constants gathered ONCE, then each iteration is (N, C)
            # elementwise plus one gather of the (N,) time vector (a 4 MB
            # table at 1M peers vs a 160 MB one), the same expression the
            # sharded path runs.
            c = build_recv_constants(
                conns, rev, lat_edge, tx_ms, rank, k_p, frag_idx, deliver,
                can_send, g_deliver, g_off, hb_phase, uplink, rx_const,
                params.proc_delay_ms, params.heartbeat_ms, with_gossip,
                lat_deliver=ld, ld_gossip=_ld_ans(frag_idx),
            )
            with jax.named_scope("fixpoint"):
                return converge_recv(
                    t0, c, params.max_relax_iters) + (jnp.int32(0),)
        # single device below the budget: sender-major offers (loop-invariant
        # parts hoisted here), row-gather pull per iteration — ~2.5x the
        # per-iteration speed of a receiver-side index gather (ops/pull.py)
        queue = (rank + 1.0 + frag_idx * k_p[:, None]) * tx_ms[:, None]
        a_base = jnp.where(
            deliver & can_send[:, None], queue + ld, INF)
        g_base = jnp.where(
            g_deliver & can_send[:, None],
            2.0 * lat_edge + _ld_ans(frag_idx) + tx_ms[:, None], INF)
        senders = (uplink, a_base) + (
            (hb_phase, g_off, g_base) if with_gossip else ())

        def cand_of(t_rx, uplink, a_base, hb_phase=None, g_off=None,
                    g_base=None):
            """The offers of the rows given: row p from `t_rx[p]` and row p
            of each table alone."""
            live = (t_rx < INF)[:, None]
            base = t_rx + params.proc_delay_ms
            start = jnp.maximum(base, uplink)
            cand = jnp.where(live, start[:, None] + a_base, INF)
            if with_gossip:
                hb = _next_heartbeat(base, hb_phase, params.heartbeat_ms)
                cand = jnp.minimum(
                    cand,
                    jnp.where(live,
                              jnp.maximum(hb[:, None] + g_off,
                                          uplink[:, None]) + g_base, INF))
            return cand

        # where the dense pull is worth avoiding (ops/pull.relax_route) the
        # carry holds the rows that moved, and how often they were few
        by_rows = relax_route(conns.shape)

        def cond(carry):
            _, _, changed, it = carry[:4]
            return changed & (it < params.max_relax_iters)

        def body(carry):
            t_rx, inc, _, it = carry[:4]
            if by_rows:
                moved, few = carry[4:]
                inc, sparse = pull_moved_min(
                    cand_of, t_rx, inc, moved, senders, conns, rev,
                    p_conns, p_rev, batch_factor=lanes)
            else:
                inc = pull(cand_of(t_rx, *senders))
            # downlink clamp (max distributes over the row min, so clamping
            # the min equals clamping every candidate)
            t_new = jnp.minimum(
                t_rx, jnp.maximum(inc.min(axis=-1), rx_const))
            moved = t_new < t_rx
            out = t_new, inc, jnp.any(moved), it + 1
            return out + ((moved, few + sparse) if by_rows else ())

        # (a mesh-only pre-relaxation before the full loop was measured
        # NET-WORSE here r4: the per-iteration cost is pull-dominated, so
        # skipping the gossip candidate arithmetic saves little while the
        # extra warm-up iterations add whole pulls)
        # iteration counter carries a STRONG int32: a Python-int carry is
        # weak-typed and re-promotes on feed-back (graft-audit GA-J002)
        with jax.named_scope("fixpoint"):
            t_rx, inc, changed, it, *rows = jax.lax.while_loop(
                cond, body,
                (t0, jnp.full(conns.shape, INF), jnp.bool_(True),
                 jnp.int32(0))
                + ((t0 < INF, jnp.int32(0)) if by_rows else ()))
        return t_rx, inc, ~changed, it, rows[1] if by_rows else jnp.int32(0)

    def _converge_floor(rank, k_p, frag_idx, t_pub, send_mask, g_floor,
                        t_init):
        """Mesh-only fixpoint against a FROZEN per-receiver gossip floor
        (the serialized answer offers of one outer pass, already pulled to
        the receiver side and row-minimized). Same three path dispatches as
        _converge_dyn, with the gossip arithmetic out of the loop body."""
        t0 = t_init.at[publisher].set(t_pub)
        sv = _frag_slice(survive, frag_idx)
        ld = _ld_mesh(frag_idx)
        deliver = send_mask if sv is None else send_mask & sv
        if formulation != "row_pull":
            c = build_recv_constants(
                conns, rev, lat_edge, tx_ms, rank, k_p, frag_idx, deliver,
                can_send, g_tgt, g_off, hb_phase, uplink, rx_const,
                params.proc_delay_ms, params.heartbeat_ms, False,
                lat_deliver=ld,
            )
            if formulation == "recv_sharded":
                t_rx, _, _, _ = converge_sharded(
                    t0, c, params.max_relax_iters, mesh, g_floor=g_floor)
            else:
                t_rx, _, _, _ = converge_recv(
                    t0, c, params.max_relax_iters, g_floor=g_floor)
            return t_rx
        queue = (rank + 1.0 + frag_idx * k_p[:, None]) * tx_ms[:, None]
        a_base = jnp.where(
            deliver & can_send[:, None], queue + ld, INF)

        def cond(carry):
            _, changed, it = carry
            return changed & (it < params.max_relax_iters)

        def body(carry):
            t_rx, _, it = carry
            live = (t_rx < INF)[:, None]
            start = jnp.maximum(t_rx + params.proc_delay_ms, uplink)
            cand = jnp.where(live, start[:, None] + a_base, INF)
            t_new = jnp.minimum(
                t_rx,
                jnp.maximum(
                    jnp.minimum(pull(cand).min(axis=-1), g_floor), rx_const))
            return t_new, jnp.any(t_new < t_rx), it + 1

        t_rx, _, _ = jax.lax.while_loop(
            cond, body, (t0, jnp.bool_(True), jnp.int32(0)))
        return t_rx

    def _converge_serialized(rank, k_p, frag_idx, t_pub, send_mask,
                             t_seed=None):
        """Exact fixpoint of the SERIALIZED answer model, as an outer
        iteration on the gossip ESTIMATE: each pass freezes the serialized
        answer offers at the current estimate t_g, then re-relaxes the
        whole network FROM SCRATCH against that floor. The from-INF
        restart is load-bearing (r5 review catch): the serialized system
        is NOT monotone in t — raising an announcer's estimate delays its
        IHAVE, which can REMOVE a requested job and make other answers
        earlier — so a warm-started min-only relaxation could undershoot
        and stick. A from-INF pass instead always lands exactly on
        min(candidates | frozen g), so when a pass reproduces its own
        estimate (t_new == t_g) the result is SELF-CONSISTENT:
        t = min(candidates(t)) with every gossip term evaluated at t.
        Any self-consistent point equals the DES's chronological fixpoint
        — a hypothetically-early solution would need its earliest wrong
        peer's candidate to be justified by strictly-earlier inputs, which
        are all correct by minimality, reproducing the true (later) value;
        contradiction. `t_seed`: optional starting estimate for the gossip
        terms (e.g. the phase-1 result), purely a convergence accelerator.

        Returns (t, converged, passes): `converged` is the final no-change
        bit of the outer loop — False means the iteration cap cut the
        refinement and t is NOT certified self-consistent (the caller
        surfaces this on DisseminationResult.converged instead of silently
        reporting a 0.0 error bar); `passes` the outer passes spent
        (DisseminationResult.refine_passes)."""
        sv = _frag_slice(survive, frag_idx)

        def cond(carry):
            _, _, changed, it = carry
            return changed & (it < params.max_relax_iters)

        def body(carry):
            t_g, _, _, it = carry
            g_abs, _, _ = gossip_serial_exact(t_g, frag_idx)
            g_d = g_abs if sv is None else jnp.where(sv, g_abs, INF)
            g_in = pull(g_d)
            g_floor = g_in.min(axis=-1)
            t_new = _converge_floor(
                rank, k_p, frag_idx, t_pub, send_mask, g_floor,
                jnp.full((n,), INF))
            return t_new, t_new, jnp.any(t_new != t_g), it + 1

        t0 = (jnp.full((n,), INF) if t_seed is None else t_seed
              ).at[publisher].set(t_pub)
        _, t, changed, it = jax.lax.while_loop(
            cond, body, (t0, t0, jnp.bool_(True), jnp.int32(0)))
        return t, ~changed, it

    def _converge_prefix(rank, k_p, frag_idx, t_pub, send_mask, t_seed):
        """Exact fixpoint of the SERIALIZED answer model by scan-free
        Jacobi iteration — the parallel-prefix replacement for the
        _converge_serialized outer loop. One iteration evaluates the full
        candidate map F at the current estimate and takes it wholesale:
        the lat-sorted answer-queue fold (gossip_fold — itself a
        parallel-prefix cumsum/cummax over the static service order, no
        global argsort) gives every edge's serialized answer offer, the
        hoisted mesh bases give the uplink-queue offers, and ONE merged
        pull yields t_{k+1} = max(min incoming offer, downlink clamp) with
        the publisher pinned. The fold's receivers' times,
        t_k[conns_sorted], are a second exchange through the same index
        (ops/pull.neighbor_rows_min: 5.5 of a pass's 16 ms at 100,000
        peers, PERF.md, PR 53). They ride in the carry: a pass changes them
        exactly at the slots that point at a peer whose time moved in the
        pass before, so where at most ops/pull._RELAX_ROWS moved (the last
        passes of a loop: 155, 3 and 0 rows of 100,000) the new times of
        those peers are delivered into the carried matrix
        (ops/pull.neighbor_update_min) and no row is gathered for them;
        the times the fold reads are the lookup's bit for bit, every pass.
        Because each estimate is recomputed FRESH
        (not min-folded into the previous one), the iteration handles the
        system's non-monotonicity in both directions — raising an
        announcer's estimate delays its IHAVE and may REMOVE a requested
        job, making other answers earlier — where a warm min-only
        relaxation would undershoot and stick (the r5 review catch that
        forced _converge_serialized's from-INF restarts).

        The exactness certificate is unchanged: the loop exits on a
        bitwise no-change pass, i.e. F(t) == t — the result is
        SELF-CONSISTENT (t = min(candidates(t)) with every gossip term
        evaluated at t), and any self-consistent point equals the DES's
        chronological fixpoint by the earliest-wrong-peer argument in
        _converge_serialized's docstring. What changes is the per-pass
        price: one fold + one pull, vs the serial path's global (N, H*C)
        argsort + a full from-INF mesh relaxation (~graph-diameter pulls)
        per outer pass.

        Returns (t, g_abs, req, drain, mixed, converged, passes, few) —
        `few` the passes that delivered their receivers' times; the
        gossip triple and `mixed` are the FINAL evaluation's (the
        no-change pass ran the fold at the fixpoint, so they ride out for
        free); `mixed` or ~converged sends the caller to the global-sort
        fallback, whose round-interleaving-proof sort covers the corner
        the per-round fold cannot certify."""
        sv = _frag_slice(survive, frag_idx)
        ld = _ld_mesh(frag_idx)
        deliver = send_mask if sv is None else send_mask & sv
        queue = (rank + 1.0 + frag_idx * k_p[:, None]) * tx_ms[:, None]
        a_base = jnp.where(
            deliver & can_send[:, None], queue + ld, INF)
        # The pass stays in the fold's LAT-SORTED layout end to end: the
        # mesh bases and the fold's constants go there once, here, and the
        # one consumer of the merged candidates, the pull, selects the
        # reverse slot's sorted position (rev_sorted) as easily as the
        # slot itself, so `inc` comes out in the receiver's slot layout
        # as it always did. Bringing g and req back through inv_lat every
        # pass was 79 of a pass's 117 ms at 100k peers (PERF.md, PR 27).
        a_base_s = permute_rows(a_base, perm_lat)
        sv_s, lda_s = _fold_consts(frag_idx)
        t0 = t_seed.at[publisher].set(t_pub)
        not_pub = jnp.arange(n) != publisher

        # where the dense lookup is worth avoiding (ops/pull.relax_route)
        # the carry holds the receivers' times, the rows that moved in the
        # pass before, and how often they were few
        by_rows = relax_route(conns.shape)

        def cond(carry):
            changed, it = carry[5], carry[6]
            return changed & (it < params.max_relax_iters)

        def body(carry):
            t_g, _, _, _, _, _, it = carry[:7]
            if by_rows:
                q_s, moved, few = carry[7:]
                q_s, sparse = neighbor_update_min(
                    q_s, t_g, moved, conns, ans_tables.rev_sorted,
                    p_conns_sorted, batch_factor=lanes)
            else:
                q_s = None
            g_sorted, req_s, drain, mixed, _ = gossip_fold_sorted(
                t_g, sv_s, lda_s, q_s)
            # merged candidates: mesh offers + SV-masked serialized answer
            # offers (every sampled surviving edge offers, matching the
            # serial path — an offer only binds for a still-lacking, hence
            # requesting, receiver)
            g_d = g_sorted if sv_s is None else jnp.where(
                sv_s, g_sorted, INF)
            live = (t_g < INF)[:, None]
            start = jnp.maximum(t_g + params.proc_delay_ms, uplink)
            cand_s = jnp.where(live, start[:, None] + a_base_s, INF)
            cand_s = jnp.minimum(cand_s, jnp.where(live, g_d, INF))
            inc = pull_sorted(cand_s)
            t_new = jnp.where(
                not_pub,
                jnp.maximum(inc.min(axis=-1), rx_const), t_pub)
            moved = t_new != t_g
            out = (t_new, g_sorted, req_s, drain, mixed, jnp.any(moved),
                   it + 1)
            return out + ((q_s, moved, few + sparse) if by_rows else ())

        with jax.named_scope("fixpoint"):
            t, g_sorted, req_s, drain, mixed, changed, it, *rows = (
                jax.lax.while_loop(
                    cond, body,
                    (t0, jnp.full((n, c), INF), jnp.zeros((n, c), bool),
                     jnp.zeros((n,), jnp.float32), jnp.bool_(False),
                     jnp.bool_(True), jnp.int32(0))
                    + ((jnp.full((n, c), INF), jnp.ones((n,), bool),
                        jnp.int32(0)) if by_rows else ())))
        # the caller's tuple is in the slot layout: ONE un-permutation,
        # after the loop
        return (t, permute_rows(g_sorted, inv_lat),
                permute_rows(req_s, inv_lat), drain, mixed, ~changed, it,
                rows[2] if by_rows else jnp.int32(0))

    def queue_drop(tgt_mask, frag_idx):
        """Priority-queue drop model (main.nim:264-299). The reference's
        queues are per-CONNECTION and hold MESSAGES: the publisher enqueues
        all fragments back-to-back on every connection (main.nim:177-179),
        so its per-connection depth for fragment f is f+1 and the newest
        fragments beyond the cap are dropped — identically on every
        connection, so a publisher cap < FRAGMENTS blacks the message out
        network-wide (nobody can assemble it), which is what the reference
        does too. Relay inter-fragment arrival gaps are >= one link latency
        (tens of ms >> tx), so relay queues drain between fragments and
        never overflow. Statically a no-op when the cap cannot bind."""
        if params.send_queue_cap >= fragments:
            return tgt_mask
        is_pub = (jnp.arange(n) == publisher)[:, None]
        dropped = frag_idx + 1.0 > params.send_queue_cap
        return tgt_mask & ~(is_pub & dropped)

    def _phase2_masks_from_inc(inc1, t1, rank1, k1, tgt_f):
        """Back-edge removal: drop each peer's slot toward its first sender
        from the send order — the slot is simply never occupied. The first
        sender is whoever DELIVERED: `inc1` is the pulled deliver-only
        offer matrix at t1 (lost copies masked; gossip offers only on
        ANSWERED edges — an unanswered edge's hypothetical offer never
        binds and must not steal the attribution argmin)."""
        first_slot = jnp.argmin(inc1, axis=-1)
        # the min offer equals t1 BY CONSTRUCTION at the fixpoint (every
        # reached non-publisher peer's time IS some offer), but offers() and
        # the converge body associate the same sum differently in f32, so the
        # equality needs a tolerance or a 1-ulp wobble leaves a receiver's
        # back-edge in place (caught by the DES cross-check). The relative
        # term keeps the tolerance above the f32 ulp at large sim times; a
        # generous value is safe — the only peers whose min offer truly
        # exceeds t1 are unreached ones (INF on both sides)
        # (t1 < INF) makes the reached-peer precondition explicit: for
        # unreached peers INF <= INF + eps is vacuously true and would strip
        # a phantom back-edge at slot 0
        got_remote = (inc1.min(axis=-1) <= t1 + 0.01 + 1e-5 * t1) \
            & (t1 < INF) & (jnp.arange(n) != publisher)
        # row-wise one-hot via fused iota compare (scatters serialize on TPU)
        back = (jnp.arange(c) == first_slot[:, None]) & got_remote[:, None]
        send_mask = tgt_f & ~back
        # re-rank WITHOUT re-sorting: at most one slot left each row's send
        # order (the back-edge, IF it was a send target at all — a first
        # sender needn't be one of ours), so ranks after the removed slot's
        # rank shift down by one; rows with no active removal shift nothing
        # (r0 is +INF there). Replaces a double argsort with fused passes.
        rm = got_remote & jnp.take_along_axis(
            tgt_f, first_slot[:, None], axis=-1)[:, 0]
        r0 = jnp.where(rm,
                       jnp.take_along_axis(
                           rank1, first_slot[:, None], axis=-1)[:, 0], INF)
        rank2 = rank1 - (rank1 > r0[:, None])
        k2 = k1 - rm.astype(jnp.float32)
        return rank2, k2, send_mask

    def _diverged(t, inc, mixed):
        """Self-consistency trigger of the fast path (zero extra cost: it
        reuses the already-pulled serialized candidates). The unserialized
        fixpoint t satisfies t = min(unserialized candidates) <= the
        serialized min; if t also >= the serialized candidate min (within
        float tolerance), the two coincide and t IS the serialized
        fixpoint by uniqueness (a hypothetically-earlier self-consistent
        solution would need its earliest wrong peer justified by
        strictly-earlier — hence correct — inputs, contradiction). A peer
        strictly below every serialized candidate means a queued answer
        it relied on would really arrive later: rerun serialized. `mixed`
        (interleaved announce rounds, beyond the per-round fold) also
        forces the exact path."""
        inc_min = inc.min(axis=-1)
        tol = 0.05 + 1e-5 * jnp.where(t < INF, t, 0.0)
        bad = (t < inc_min - tol) & (t < INF) \
            & (jnp.arange(n) != publisher)
        return jnp.any(bad) | mixed

    def phases_fast(frag_idx, t_pub, warm):
        """UNSERIALIZED two-phase pipeline. Contains no lax.cond, so it is
        safe under the fragment vmap.

        EXACT mode (serialize_answers=True): the serialized answer queues
        are resolved at both phase results by the cheap per-round fold
        (gossip_fold): the queue delays ride in the attribution pulls and
        the accounting triple, while the delivery fixpoint stays
        unserialized. The _diverged triggers (checked at both phases)
        certify when that is exact — a queued answer only matters if it
        would have been somebody's FIRST delivery — and route the message
        to the serialized slow branch otherwise.

        BOUNDED mode (serialize_answers=False) and the no-gossip model:
        the fold's output never moves a delivery time — it only feeds the
        answer_wait_max_ms error bar and the accounting triple — so it has
        no business riding every phase (the r5 regression: two folds plus
        two attribution offers()+pull per fragment on the path whose whole
        point is speed). The first-sender attribution reuses the fixpoint
        loop's confirmation-pass offer matrix (free, bit-consistent with
        the times it attributes — see _converge_dyn), and ONE fold at the
        final times supplies the triple and the wait bar. The gossip
        entries of that matrix are the UNSERIALIZED offers, consistent
        with bounded delivery semantics; they deviate from the serialized
        values only when an answer queued, which is exactly what the
        exported wait bar brackets.

        `warm` (static): seed phase 1 from the cross-publish arrival-
        offset carry (state.warm_offset_ms), re-based to this publish via
        t_pub + offset[q] + offset[publisher] + one heartbeat of margin —
        the publisher term covers publishing from a peer that was LATE in
        the previous spread, the heartbeat margin covers gossip-round
        phase shifts. The seed is a HEURISTIC upper-bound estimate, so the
        result is certified: at a correct fixpoint every reached
        non-publisher peer satisfies t == max(min incoming offer, downlink
        clamp) BITWISE (the loop's no-change pass computed t from this
        very inc), while a stuck undershot seed sits strictly BELOW its
        supported value (min-only relaxation never raises it) — `bad`
        flags any such peer and the message level reruns cold on a scalar
        cond (a vmapped cond here would execute both branches every
        publish).

        Returns (t, rank, k, send_mask, g_abs, req_any, drain, inc, wait,
        hint, mixed, ok, bad, iters, few) — `wait` is the fold's max answer-queue
        wait at the final times (always FINITE; `mixed` separately flags the
        interleaved-rounds corner where the fold's per-round exactness
        precondition fails), `ok` the fixpoint-convergence bit, `bad` the
        warm-seed certificate violation, `iters` the loop iterations of the
        phases' fixpoints, summed, `few` those of them that delivered the
        moved rows' offers (_converge_dyn)."""
        tgt_f = queue_drop(tgt, frag_idx)
        rank1 = _ranks_f32(jnp.where(tgt_f, rprio, INF))
        k1 = tgt_f.sum(axis=-1).astype(jnp.float32)
        if warm:
            w = state.warm_offset_ms
            seed = jnp.where(
                (w < WARM_VALID) & (w[publisher] < WARM_VALID),
                t_pub + w + w[publisher] + params.heartbeat_ms, INF)
            t1, inc1, ok1, it1, few1 = _converge_dyn(
                rank1, k1, frag_idx, t_pub, tgt_f, t_init=seed)
            supported = jnp.maximum(inc1.min(axis=-1), rx_const)
            # t1 <= supported holds at any loop exit; strict < means the
            # seed undershot and stuck (or a phantom: a finite seed on a
            # peer no offer reaches keeps supported at INF). An
            # iteration-capped run leaves inc one pass stale, so it cannot
            # certify either.
            bad = jnp.any((t1 < supported) & (t1 < INF) & ~is_pub) | ~ok1
        else:
            t1, inc1, ok1, it1, few1 = _converge_dyn(
                rank1, k1, frag_idx, t_pub, tgt_f)
            bad = jnp.bool_(False)
        if with_gossip and params.serialize_answers:
            with jax.named_scope("fold"):
                g1, req1, drain1, mixed1, wait1 = gossip_fold(t1, frag_idx)
            ga1 = jnp.where(req1, g1, INF)
            if not params.exclude_first_sender:
                inc2 = pull(offers(t1, rank1, k1, frag_idx, tgt_f,
                                   deliver_only=True, g_abs=ga1))
                hint = _diverged(t1, inc2, mixed1)
                return (t1, rank1, k1, tgt_f, g1, req1, drain1, inc2,
                        wait1, hint, mixed1, ok1, bad, it1, few1)
            inc1p = pull(offers(t1, rank1, k1, frag_idx, tgt_f,
                                deliver_only=True, g_abs=ga1))
            rank2, k2, send_mask = _phase2_masks_from_inc(
                inc1p, t1, rank1, k1, tgt_f)
            # phase-2 costs are pointwise <= phase-1 (a send slot was
            # removed from every queue), so t1 is a valid warm start
            t2, _, ok2, it2, few2 = _converge_dyn(
                rank2, k2, frag_idx, t_pub, send_mask, t_init=t1)
            with jax.named_scope("fold"):
                g2, req2, drain2, mixed2, wait2 = gossip_fold(t2, frag_idx)
            inc2 = pull(offers(t2, rank2, k2, frag_idx, send_mask,
                               deliver_only=True,
                               g_abs=jnp.where(req2, g2, INF)))
            hint = (_diverged(t1, inc1p, mixed1)
                    | _diverged(t2, inc2, mixed2))
            # error bar covers BOTH folds the fast result relied on (the
            # t1 fold fed the first-sender attribution)
            return (t2, rank2, k2, send_mask, g2, req2, drain2, inc2,
                    jnp.maximum(wait1, wait2), hint, mixed1 | mixed2,
                    ok1 & ok2, bad, it1 + it2, few1 + few2)
        # bounded / no-gossip: attribution from the loop's own matrix
        if not params.exclude_first_sender:
            t_fin, inc_fin, ok, iters, few = t1, inc1, ok1, it1, few1
            rank_o, k_o, mask_o = rank1, k1, tgt_f
        else:
            rank2, k2, send_mask = _phase2_masks_from_inc(
                inc1, t1, rank1, k1, tgt_f)
            # t1 is a valid (guaranteed) upper bound for phase 2 — no
            # certificate needed
            t2, inc2, ok2, it2, few2 = _converge_dyn(
                rank2, k2, frag_idx, t_pub, send_mask, t_init=t1)
            t_fin, inc_fin, ok, iters = t2, inc2, ok1 & ok2, it1 + it2
            few = few1 + few2
            rank_o, k_o, mask_o = rank2, k2, send_mask
        if with_gossip:
            with jax.named_scope("fold"):
                g_f, req_f, drain_f, mixed_o, wait_o = gossip_fold(
                    t_fin, frag_idx)
        else:
            g_f = jnp.zeros((n, c), jnp.float32)
            req_f = jnp.zeros((n, c), bool)
            drain_f = jnp.zeros((n,), jnp.float32)
            mixed_o, wait_o = jnp.bool_(False), jnp.float32(0.0)
        return (t_fin, rank_o, k_o, mask_o, g_f, req_f, drain_f, inc_fin,
                wait_o, jnp.bool_(False), mixed_o, ok, bad, iters, few)

    def phases_serial(frag_idx, t_pub, t_seed):
        """SERIALIZED pipeline: exact answer queues inside the delivery
        fixpoint itself (from-INF outer iteration) and in the accounting
        triple. Reached only from the trigger-gated slow branch (a
        scalar-predicate lax.cond at message level — a real XLA branch,
        never a batched select), so its global sorts and outer passes cost
        nothing unless a QUEUED answer was actually somebody's first
        delivery (or announce rounds interleaved). `t_seed`: the fast
        pipeline's final times — a near-correct gossip estimate that cuts
        the outer passes from reach-expansion count (~10) to tick/request
        refinement count (~2-3)."""
        tgt_f = queue_drop(tgt, frag_idx)
        rank1 = _ranks_f32(jnp.where(tgt_f, rprio, INF))
        k1 = tgt_f.sum(axis=-1).astype(jnp.float32)
        t1, conv1, it1 = _converge_serialized(rank1, k1, frag_idx, t_pub,
                                              tgt_f, t_seed=t_seed)
        if not params.exclude_first_sender:
            g2, req2, drain2 = gossip_serial_exact(t1, frag_idx)
            inc2 = pull(offers(t1, rank1, k1, frag_idx, tgt_f,
                               deliver_only=True,
                               g_abs=jnp.where(req2, g2, INF)))
            return t1, rank1, k1, tgt_f, g2, req2, drain2, inc2, conv1, it1
        g1, req1, _ = gossip_serial_exact(t1, frag_idx)
        inc1 = pull(offers(t1, rank1, k1, frag_idx, tgt_f,
                           deliver_only=True,
                           g_abs=jnp.where(req1, g1, INF)))
        rank2, k2, send_mask = _phase2_masks_from_inc(
            inc1, t1, rank1, k1, tgt_f)
        t2, conv2, it2 = _converge_serialized(rank2, k2, frag_idx, t_pub,
                                              send_mask, t_seed=t1)
        g2, req2, drain2 = gossip_serial_exact(t2, frag_idx)
        inc2 = pull(offers(t2, rank2, k2, frag_idx, send_mask,
                           deliver_only=True,
                           g_abs=jnp.where(req2, g2, INF)))
        return (t2, rank2, k2, send_mask, g2, req2, drain2, inc2,
                conv1 & conv2, it1 + it2)

    def phases_prefix(frag_idx, t_pub, t_seed):
        """PARALLEL-PREFIX serialized pipeline (the exact-mode default,
        params.answer_queue_mode="parallel_prefix"): the same two-phase
        structure as phases_serial with _converge_prefix supplying both
        fixpoints — exact answer queues inside the delivery times at one
        fold + one pull per refinement iteration, no global sorts, no
        from-INF restarts. Reached only from the trigger-gated slow
        branch; `t_seed` is the fast pipeline's final times, so the Jacobi
        iteration starts from a near-correct estimate and spends
        tick/request-refinement iterations, not reach-expansion ones.

        Returns the phases_serial 10-tuple with element 8 = the COMBINED
        certificate (both phases reached a bitwise F(t)==t pass AND
        neither's final fold saw interleaved announce rounds), and an
        eleventh element: of the passes (element 9), those that delivered
        their fold's receivers' times from the rows that moved
        (_converge_prefix). A False
        certificate means the prefix times are NOT certified exact —
        the caller's nested cond reruns the global-sort pipeline, whose
        sort-order exactness covers the interleaved corner."""
        tgt_f = queue_drop(tgt, frag_idx)
        rank1 = _ranks_f32(jnp.where(tgt_f, rprio, INF))
        k1 = tgt_f.sum(axis=-1).astype(jnp.float32)
        t1, g1, req1, drain1, mixed1, conv1, it1, few1 = _converge_prefix(
            rank1, k1, frag_idx, t_pub, tgt_f, t_seed)

        def pull_lat(cand):
            # this pipeline's attribution pulls go through the lat order
            # like its loops' pulls: XLA hoists a loop's select mask
            # (iota == rev_sorted) out of the loop and keeps it, 0.5 GB at
            # (100k, 40), and a pull through `rev` here would keep a second
            # one beside it. The permutation costs 0.3 ms.
            return pull_sorted(permute_rows(cand, perm_lat))

        # attribution pull: gossip offers masked to ANSWERED edges — an
        # unanswered edge's hypothetical offer must not steal the
        # first-sender argmin (same masking as phases_serial)
        inc1 = pull_lat(offers(t1, rank1, k1, frag_idx, tgt_f,
                               deliver_only=True,
                               g_abs=jnp.where(req1, g1, INF)))
        if not params.exclude_first_sender:
            return (t1, rank1, k1, tgt_f, g1, req1, drain1, inc1,
                    conv1 & ~mixed1, it1, few1)
        rank2, k2, send_mask = _phase2_masks_from_inc(
            inc1, t1, rank1, k1, tgt_f)
        t2, g2, req2, drain2, mixed2, conv2, it2, few2 = _converge_prefix(
            rank2, k2, frag_idx, t_pub, send_mask, t1)
        inc2 = pull_lat(offers(t2, rank2, k2, frag_idx, send_mask,
                               deliver_only=True,
                               g_abs=jnp.where(req2, g2, INF)))
        return (t2, rank2, k2, send_mask, g2, req2, drain2, inc2,
                conv1 & conv2 & ~mixed1 & ~mixed2, it1 + it2, few1 + few2)

    # publisher emits fragments back-to-back (main.nim:177-179)
    with jax.named_scope("sample"):
        frag_ids = jnp.arange(fragments, dtype=jnp.float32)
        t_pubs = t0_ms + frag_ids * tx_ms[publisher]

    def _run_fast(warm):
        # shard_map doesn't nest under vmap; fragments is static and <= 9
        # (topogen -f choices), so a mesh unrolls the fragment axis instead
        return _per_fragment(lambda f, t: phases_fast(f, t, warm),
                             frag_ids, t_pubs, batched=mesh is None)

    # scope `fast`: the unserialized two-phase pipeline (its loops under
    # fast/fixpoint, the answer-queue folds under fast/fold), all of it per
    # fragment lane (fast/per_fragment) but the warm-seed rerun's predicate
    with jax.named_scope("fast"):
        fast = _run_fast(params.warm_start)
        if params.warm_start:
            # the warm seed is heuristic: if ANY fragment's certificate
            # flags an undershoot (or a capped loop), restart the whole fast
            # pipeline cold. Scalar-predicate cond = a real XLA branch; never
            # taken when the seed margin holds, so the cold trace costs
            # compile time only.
            fast = jax.lax.cond(
                jnp.any(fast[12]), lambda _: _run_fast(False),
                lambda f: f, fast)
    (fast_results, wait_f, hint_f, mixed_f, ok_f) = (
        fast[:8], fast[8], fast[9], fast[10], fast[11])
    # loop iterations of the kept fast pipeline: summed over its phases
    # (phases_fast), max over fragment lanes
    fast_iters = jnp.max(fast[13])
    # and those of them that delivered the moved rows' offers (the fixpoint
    # says where)
    fast_sparse_iters = jnp.max(fast[14])
    # bounded-mode error bar: the max time any requested answer waited
    # queued at the final estimates — in exact mode the repair (below)
    # drives the actual delivery error to zero and this reports 0.
    # ALWAYS finite (json-safe): the interleaved-rounds corner, where the
    # per-round fold's bar is unreliable, is exported as a separate COUNT
    # instead of the old INF poison (which leaked invalid-JSON Infinity
    # into bench artifacts).
    answer_wait = jnp.max(wait_f)
    answer_interleaved = jnp.sum(mixed_f.astype(jnp.int32))
    converged = jnp.all(ok_f)
    refine_passes = refine_lane_passes = refine_sparse_passes = jnp.int32(0)
    lanes_hinted = lanes_uncertified = jnp.int32(0)
    refined = fell_back = refined_serial = jnp.bool_(False)
    if with_gossip and params.serialize_answers:
        # serialized-answer repair, decided ONCE per message on a SCALAR
        # predicate (_diverged): the fast pipeline is kept whenever no
        # queued answer could have been a first delivery and no announce
        # rounds interleaved — then the unserialized times are themselves
        # the serialized fixpoint and the triple/inc are already exact.
        # The scalar cond is a real branch on TPU — a vmapped cond would
        # lower to select_n and execute both branches every publish (the
        # r5 review + bench catch). The fast results ride in as the
        # operand: the slow pipeline seeds its gossip estimates from them.
        #
        # Engine selection (static): the parallel-prefix pipeline needs
        # the single-device row-gather pull its Jacobi body is built
        # around, so it runs exactly where _converge_dyn picks that
        # dispatch — mesh-free and under the memory budget (the nested
        # device grids call disseminate with mesh=None inside pjit, so
        # they ride it too). Elsewhere, and under answer_queue_mode=
        # "serial" (the reference engine the prefix path is pinned
        # against), the global-sort pipeline runs as before.
        use_prefix = (params.answer_queue_mode == "parallel_prefix"
                      and formulation == "row_pull")

        def _serial_all(seed):
            # the global-sort engine: a rerun that no benchmark cell has
            # ever taken (refine/legacy) or the engine off "row_pull". Its
            # from-INF outer loops gain nothing from joint lanes, and F
            # copies of it would multiply the publish's compile time: one
            # rolled copy, its pulls one lane wide (a mesh unrolls, as
            # shard_map under a loop never ran; one lane has no axis)
            if mesh is None and fragments > 1:
                with jax.named_scope("per_fragment"):
                    return jax.lax.map(lambda lane: phases_serial(*lane),
                                       (frag_ids, t_pubs, seed))
            return _per_fragment(phases_serial, frag_ids, t_pubs, seed,
                                 batched=False)

        # what a branch the prefix engine did not run appends to its
        # 10-tuple, as phases_prefix's eleventh element: no lane's pass
        # delivered its receivers' times; and what one that did not fall
        # back appends after it: the fell-back bit and the count of
        # uncertified lanes
        no_sparse = (jnp.zeros((fragments,), jnp.int32),)
        no_fallback = (jnp.bool_(False), jnp.int32(0))

        def _slow(fr):
            """The taken branch: fr[:10] refined, every lane's passes that
            delivered their receivers' times (phases_prefix), then the
            fell-back bit and how many lanes the prefix engine left
            uncertified."""
            t_fast = fr[0]
            if not use_prefix:
                # the global-sort engine is the one chosen: no fallback
                return _serial_all(t_fast) + no_sparse + no_fallback
            # phases_prefix holds no lax.cond, so its lanes vmap like the
            # fast pipeline's: F(t) == t is a fixed point of further passes
            # and the batched while_loop holds a finished lane's carry, so
            # every leaf is the lane's own loop's, its pass count included
            pref = _per_fragment(phases_prefix, frag_ids, t_pubs, t_fast,
                                 batched=fragments > 1)

            # certificate-gated fallback (nested scalar cond): any
            # fragment the prefix engine could not certify — interleaved
            # announce rounds or an iteration-capped Jacobi loop — reruns
            # ALL fragments through the global-sort pipeline, seeded from
            # the prefix times. Untaken, the legacy branch costs compile
            # time only (the repo's warm-rerun idiom); its pass count adds
            # to the prefix iterations already spent.
            def _legacy(p):
                with jax.named_scope("legacy"):
                    leg = _serial_all(p[0])
                return leg[:9] + (p[9] + leg[9], p[10], jnp.bool_(True),
                                  jnp.sum(~p[8], dtype=jnp.int32))

            return jax.lax.cond(
                jnp.all(pref[8]), lambda p: p + no_fallback, _legacy, pref)

        # the convergence bit rides the cond operand so the kept branch's
        # verdict (fast ok / serialized refinement certificate) wins; the
        # pass counter rides alongside (0 when the fast pipeline is kept)
        # scope `refine`: the conditional and both of its branches (the
        # global-sort rerun of an uncertified prefix result: refine/legacy)
        refined = jnp.any(hint_f)
        with jax.named_scope("refine"):
            kept = jax.lax.cond(
                refined, _slow, lambda fr: fr + no_sparse + no_fallback,
                fast_results + (ok_f, jnp.zeros((fragments,), jnp.int32)))
        fast_results, conv_f, passes_f = kept[:8], kept[8], kept[9]
        refine_sparse_passes = jnp.max(kept[10])
        fell_back, lanes_uncertified = kept[11], kept[12]
        # which engine the kept refinement is from: the global-sort one
        # where it was the one chosen, or after the fallback to it
        refined_serial = (refined & fell_back) if use_prefix else refined
        converged = jnp.all(conv_f)
        refine_passes = jnp.max(passes_f)
        # the scope refine/per_fragment runs every lane's passes, and every
        # lane refines when one hints
        refine_lane_passes = jnp.sum(passes_f)
        lanes_hinted = jnp.sum(hint_f, dtype=jnp.int32)
        # exact mode: the repair drives the delivery error to zero
        answer_wait = jnp.float32(0.0)
        answer_interleaved = jnp.int32(0)
    (t_rx_f, rank_f, k_f, smask_f, g_abs_acct, req_acct,
     drain_acct, inc_acct) = fast_results

    # scope `accounting`: everything after the fixpoints, to the return
    with jax.named_scope("accounting"):
        received = jnp.all(t_rx_f < INF, axis=0)
        # last fragment completes
        t_last = jnp.where(received, t_rx_f.max(axis=0), INF)
        # but the publisher's own receipt is the publish call: it stamps
        # `now` once before the fragment loop (main.nim:169) and its own
        # handler counts every fragment inside `publish` (SELFTRIGGER,
        # main.nim:245), so it logs t0_ms; its lanes' t_pubs[f] are send
        # origins, and t_last there is the START of its last send
        t_rx = jnp.where(is_pub & received, t0_ms, t_last)
        delay = jnp.where(received, t_rx - t0_ms, INF)

    # ---- post-fixpoint accounting (bytes, duplicates, gossip, score) -------
    def frag_accounting(frag_idx, t_rx_one, rank, k_p, send_mask,
                        g_abs_f, req_any_f, drain_f, inc):
        # this fragment's loss draw; the gossip triple (answer offers,
        # answered sets, serialized queue drain) and the deliver-only
        # offer matrix `inc` were resolved at the final times by the phase
        # pipeline (fold or exact per the trigger branch; in bounded mode
        # `inc` is the fixpoint loop's own confirmation-pass matrix, whose
        # gossip entries are the unserialized offers — the deviation from
        # the serialized values is bracketed by answer_wait_max_ms)
        sv = _frag_slice(survive, frag_idx)
        # loss-only draw (pre-graylist) for the lost_tx counter: a
        # receiver-side graylist ignore is not a network loss
        sv_loss = _frag_slice(survive_loss, frag_idx)
        if not with_gossip:
            g_abs_f = None
        # tx side (sends, bytes): everything transmitted, lost or not
        cand = offers(t_rx_one, rank, k_p, frag_idx, send_mask,
                      g_abs=g_abs_f)
        made_offer = cand < INF
        # rx side (first-delivery attribution): delivered copies only
        first_slot = jnp.argmin(inc, axis=-1)
        q_t = neighbor_pull_min(  # neighbor arrival times (fragment-vmapped)
            t_rx_one, p_conns, p_rev, batch_factor=lanes)
        start_tx = jnp.maximum(t_rx_one + params.proc_delay_ms, uplink)
        # IDONTWANT (v1.2): target announced receipt before our send began
        if payload_bytes >= params.idontwant_threshold_bytes:
            send_start = start_tx[:, None] \
                + (rank + frag_idx * k_p[:, None]) * tx_ms[:, None]
            idw_arrived = q_t + lat_edge < send_start
            made_offer = made_offer & ~(idw_arrived & send_mask)
        eff_send = made_offer & send_mask
        sends = eff_send.sum(axis=-1)
        # uplink occupancy of this fragment's mesh sends: the queue drains at
        # the end of the LAST slot actually transmitted. Slot positions stay
        # fixed when an IDONTWANT suppresses an earlier send (the delivery
        # model keeps static ranks), so only trailing suppressed slots
        # shorten the drain.
        last_pos = jnp.max(jnp.where(eff_send, rank + 1.0, 0.0), axis=-1)
        up_end = jnp.where(
            last_pos > 0.0,
            start_tx + (frag_idx * k_p + last_pos) * tx_ms, 0.0)
        if with_gossip:
            havers = (t_rx_one < INF) & can_send
            # per-round accounting over the mcache window: every heartbeat
            # tick h the emitter IHAVEs its fresh sample; the receiver
            # IWANTs only if it still lacks the message when the announce
            # lands — the phase pipeline already resolved the answered sets
            # (req_any_f) and the serialized drain of each peer's answer
            # queue (drain_f: announce tick, IWANT round trip, then the
            # answers transmitted BACK-TO-BACK on the answering uplink in
            # IWANT-arrival order — sum, not max; rounds chain through the
            # running busy time). The DES recomputes both through its
            # chronological event heap.
            ihave_ct = jnp.zeros((n, c), jnp.float32)   # per-edge IHAVEs
            for h in range(n_rounds):
                ihave_ct = ihave_ct + (g_tgt_w[h] & havers[:, None])
            gossip_sent = req_any_f                     # edge answered >=1 IWANT
            up_end = jnp.maximum(up_end, drain_f)
            ihave_pp = ihave_ct.sum(axis=-1)            # (N,) IHAVEs sent
            # the IWANT flows opposite the IHAVE: the lacking RECEIVER sends
            # it, the gossiping peer receives it
            iwant_rx_pp = gossip_sent.sum(axis=-1).astype(jnp.float32)
            sends = sends + (gossip_sent & made_offer).sum(axis=-1)
            sent_any = eff_send | (gossip_sent & made_offer)
            arrived = sent_any if sv is None else sent_any & sv
            lost_pp = (jnp.zeros((n,), jnp.float32) if sv_loss is None
                       else (sent_any & ~sv_loss).sum(axis=-1)
                       .astype(jnp.float32))
            # ONE pull for all three involution-crossing quantities: the
            # per-edge IHAVE count (<= history_gossip), the IWANT flag and
            # the delivered-copy flag pack exactly into one small float —
            # every extra pull is a full row-gather pass (ops/pull.py), so
            # 3 -> 1 saves two passes per fragment
            pack = (ihave_ct * 4.0 + gossip_sent.astype(jnp.float32) * 2.0
                    + arrived.astype(jnp.float32))
            slot_ok = (conns >= 0) & (rev >= 0)
            pulled = jnp.where(
                slot_ok,
                pull(pack),
                0.0)
            q_ihave = jnp.floor(pulled / 4.0)
            rem = pulled - q_ihave * 4.0
            q_gs = jnp.floor(rem / 2.0)
            ihave_rx_pp = q_ihave.sum(axis=-1)
            iwant_pp = q_gs.sum(axis=-1)
            arrived_rx = rem - q_gs * 2.0 > 0.5         # (N, C) copy landed
            copies = arrived_rx.sum(axis=-1).astype(jnp.float32)
        else:
            ihave_pp = jnp.zeros((n,), jnp.float32)
            iwant_pp = jnp.zeros((n,), jnp.float32)
            ihave_rx_pp = jnp.zeros((n,), jnp.float32)
            iwant_rx_pp = jnp.zeros((n,), jnp.float32)
            sent_any = eff_send
            # receivers only count copies the network actually delivered
            arrived = sent_any if sv is None else sent_any & sv
            lost_pp = (jnp.zeros((n,), jnp.float32) if sv_loss is None
                       else (sent_any & ~sv_loss).sum(axis=-1)
                       .astype(jnp.float32))
            arrived_rx = reciprocal_pull_bool(
                arrived, p_conns, p_rev, batch_factor=lanes)
            copies = arrived_rx.sum(axis=-1).astype(jnp.float32)
        # wire-arrival time of every copy that landed at each receiver slot
        # (for the downlink-occupancy fold below); -INF marks no-copy slots
        arr_t = jnp.where(arrived_rx, inc, -INF)
        # slow-peer penalty (main.nim:264-299): deliveries that spent longer
        # than the threshold in the SENDER's queue mark the sender as slow
        # in the RECEIVER's score of it (the reciprocal slot) — scoring and
        # opportunistic grafting then route around low-bandwidth peers.
        # Weight 0 (the default) statically removes the computation.
        if params.slow_weight != 0.0:
            # queue delay as the receiver experiences it: the wait for the
            # sender's uplink to drain earlier traffic counts too
            qdelay = jnp.maximum(
                uplink - (t_rx_one + params.proc_delay_ms), 0.0
            )[:, None] + (rank + frag_idx * k_p[:, None]) * tx_ms[:, None]
            slow_send = send_mask & made_offer & (
                qdelay > params.slow_threshold_ms)
            slow_inc = reciprocal_pull_bool(
                slow_send, p_conns, p_rev, batch_factor=lanes
            ).astype(jnp.float32)
        else:
            slow_inc = jnp.zeros((n, c), jnp.float32)
        return (sends, copies, ihave_pp, iwant_pp, ihave_rx_pp, iwant_rx_pp,
                first_slot, slow_inc, arr_t, up_end, lost_pp)

    with jax.named_scope("accounting"):
        (sends_f, copies_f, ihave_f, iwant_f, ihave_rx_f, iwant_rx_f,
         first_slot_f, slow_f, arr_f, up_end_f, lost_f) = _per_fragment(
            frag_accounting, frag_ids, t_rx_f, rank_f, k_f, smask_f,
            g_abs_acct, req_acct, drain_acct, inc_acct, batched=True)
        sends = sends_f.sum(axis=0).astype(jnp.int32)
        lost_tx = lost_f.sum(axis=0).astype(jnp.int32)
        copies = copies_f.sum(axis=0).astype(jnp.int32)
        ihave_pp = ihave_f.sum(axis=0).astype(jnp.int32)
        iwant_pp = iwant_f.sum(axis=0).astype(jnp.int32)
        ihave_rx_pp = ihave_rx_f.sum(axis=0).astype(jnp.int32)
        iwant_rx_pp = iwant_rx_f.sum(axis=0).astype(jnp.int32)

        # firstMessageDeliveries: credit the edge that delivered fragment 0 first
        fs = first_slot_f[0]
        got = received & (jnp.arange(n) != publisher)
        # one credit at each receiver's first-delivery slot: a row-wise one-hot
        # add (fused elementwise) — scatters serialize on TPU
        credit = (jnp.arange(c) == fs[:, None]) & got[:, None]
        fmd = jnp.minimum(state.fmd + credit.astype(jnp.float32), params.fmd_cap)

        # IDONTWANT control-message counters (v1.2, go-test-node/main.go:165):
        # on first RECEIPT of a large message a peer announces IDONTWANT to its
        # mesh members except the one that delivered it — once per MESSAGE, not
        # per fragment; the publisher announces nothing (it received nothing).
        # The suppression effect rides inside frag_accounting; this is the
        # announce traffic. `credit` is exactly the first-delivery back-edge.
        if payload_bytes >= params.idontwant_threshold_bytes:
            idw_edge = (state.mesh_mask & valid & ~credit
                        & (got & can_send)[:, None])
            idw_tx_pp = idw_edge.sum(axis=-1).astype(jnp.int32)
            idw_rx_pp = reciprocal_pull_bool(
                idw_edge, p_conns, p_rev).sum(axis=-1).astype(jnp.int32)
        else:
            idw_tx_pp = jnp.zeros((n,), jnp.int32)
            idw_rx_pp = jnp.zeros((n,), jnp.int32)

        packed = [
            fast_iters, refine_passes, refined.astype(jnp.int32),
            fell_back.astype(jnp.int32), converged.astype(jnp.int32),
            refined_serial.astype(jnp.int32), refine_lane_passes,
            lanes_hinted, lanes_uncertified, fast_sparse_iters,
            refine_sparse_passes]
        if params.churn_down_per_hb > 0.0 or params.churn_up_per_hb > 0.0:
            # under churn only (a churn-free publish stays the program it
            # was): who could send at this publish, and how many of them
            # the mesh repair has not yet brought back to D_low valid
            # mesh members
            mesh_deg = (state.mesh_mask & valid
                        & can_send[:, None]).sum(axis=-1)
            packed += [
                can_send.sum(dtype=jnp.int32),
                (can_send & (mesh_deg < params.d_low)).sum(dtype=jnp.int32)]
        result = DisseminationResult(
            t_rx_ms=t_rx,
            delay_ms=delay,
            received=received,
            sends=sends,
            copies_rx=copies,
            ihave_sent=ihave_pp,
            iwant_sent=iwant_pp,
            lost_tx=lost_tx,
            answer_wait_max_ms=answer_wait,
            answer_interleaved=answer_interleaved,
            converged=converged,
            refine_passes=refine_passes,
            counters=jnp.stack(packed),
        )
        dup = jnp.maximum(copies - fragments, 0)
        # uplink occupancy write-back: per fragment, frag_accounting computed the
        # effective drain end — the last mesh slot actually transmitted (IDONTWANT
        # suppression shortens trailing slots) plus answered-IWANT serializations.
        # Carried in SimState so the NEXT message's sends queue behind this one.
        uplink_new = jnp.maximum(uplink, up_end_f.max(axis=0))
        # downlink occupancy write-back: fold ALL delivered copies (mesh
        # duplicates + gossip answers, post-suppression) through each receiver's
        # single-server downlink queue in arrival order. For ascending arrivals
        # o_1..o_m the completion recurrence busy_j = max(o_j, busy_{j-1} + rx)
        # unrolls to busy_m = max(rx_free + m*rx, max_j o_j + (m-j)*rx); with d_i
        # the i-th LARGEST arrival that is max(rx_free + m*rx, max_i d_i + i*rx)
        # — one sort plus elementwise, order-exact (tied arrivals commute).
        arr_all = jnp.moveaxis(arr_f, 0, 1).reshape(n, fragments * c)
        d_sorted = -jnp.sort(-arr_all, axis=-1)
        m_copies = copies.astype(jnp.float32)
        pos = jnp.arange(fragments * c, dtype=jnp.float32)
        fold = jnp.where(pos[None, :] < m_copies[:, None],
                         d_sorted + pos[None, :] * rx_ms[:, None], -INF)
        rx_free_new = jnp.maximum(state.rx_free_ms + m_copies * rx_ms,
                                  fold.max(axis=-1))
        # the counter accrues unweighted; score() applies the (negative) weight
        slow_penalty = state.slow_penalty + slow_f.sum(axis=0)
        # cross-publish warm-start carry: this message's arrival OFFSETS seed
        # the next publish's relaxation (phases_fast re-bases them to the new
        # publish time). INF where the message never fully arrived; churn and
        # subscription changes invalidate the carry (heartbeat/simulator).
        warm_new = jnp.where(received, t_last - t0_ms, INF)
        new_state = state.replace(
            key=key,
            warm_offset_ms=warm_new,
            uplink_free_ms=uplink_new,
            rx_free_ms=rx_free_new,
            fmd=fmd,
            slow_penalty=slow_penalty,
            bytes_tx=state.bytes_tx + sends.astype(jnp.float32) * frag_bytes,
            bytes_rx=state.bytes_rx + copies.astype(jnp.float32) * frag_bytes,
            dup_rx=state.dup_rx + dup.astype(jnp.int32),
            ihave_tx=state.ihave_tx + ihave_pp,
            iwant_tx=state.iwant_tx + iwant_pp,
            ihave_rx=state.ihave_rx + ihave_rx_pp,
            iwant_rx=state.iwant_rx + iwant_rx_pp,
            idontwant_tx=state.idontwant_tx + idw_tx_pp,
            idontwant_rx=state.idontwant_rx + idw_rx_pp,
        )
        if with_fanout:
            # persist the publisher's (possibly replenished) fanout set and
            # restart its TTL from this publish
            new_state = new_state.replace(
                fanout_mask=jnp.where(is_pub[:, None], fan_row, state.fanout_mask),
                fanout_expire=jnp.where(
                    is_pub,
                    jnp.asarray(t0_ms + params.fanout_ttl_ms, jnp.float32),
                    state.fanout_expire,
                ),
            )
    if return_plan:
        plan = {
            "tgt": tgt,                 # (N, C) data send set (pre queue-drop)
            "rprio": rprio,             # (N, C) send-order priorities
            "g_tgt_w": g_tgt_w,         # (W, N, C) per-round gossip targets
            "survive": survive,         # (F, N, C) per-fragment loss draws,
            #                             (N, C) graylist-only, or None
            "retx_ms": retx_ms,         # (F, N, C) tcp-mode retransmit
            #                             stall per delivered copy, or None
            "hb_phase": hb_phase,       # (N,)
            "uplink": uplink,           # (N,) pre-message uplink occupancy
            "rx_free": state.rx_free_ms,  # (N,) pre-message downlink occupancy
            "rx_ms": rx_ms,             # (N,) per-copy downlink drain time
            "can_send": can_send,       # (N,)
            "tx_ms": tx_ms,             # (N,) per-fragment uplink ms
            "lat_edge": lat_edge,       # (N, C) per-slot latency
            "t_pubs": t_pubs,           # (F,) per-fragment publish times
        }
        return result, new_state, plan
    return result, new_state
