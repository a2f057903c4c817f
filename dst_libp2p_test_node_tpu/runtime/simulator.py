"""Experiment runtime: what `shadow shadow.yaml` does for the reference.

Shadow spawns one libp2p process per host, lets them boot (nodes start t=5 s),
dial, and stabilize their meshes, then a publisher controller injects messages
from t=500 s at a fixed inter-message delay (shadow/topogen.py:79-136,
run.sh:58-64). The Simulator replays that timeline against the JAX engine:

  boot     -> connection graph build (ops/graph.py)
  warm-up  -> `warmup_s` heartbeats of mesh maintenance (lax.scan)
  inject   -> one disseminate() fixpoint per message, heartbeats advancing
              between messages at the configured spacing
  output   -> awk-compatible latencies lines (runtime/logemit.py) + summary
              (runtime/summarize.py)

Publisher selection mirrors run.sh's publisher_id / publisher_rotation
(run.sh:34-35); SELFTRIGGER controls whether the publisher logs its own
delivery (main.nim:245: triggerSelf). The muxer choice collapses to a
per-hop processing-delay constant (SURVEY.md §5: yamux vs quic differ in
handshake/stream overhead, not steady-state routing).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import numpy as np

from ..config.env import GossipSubParams
from ..config.topology import Topology, TopoParams
from ..ops.disseminate import disseminate as _disseminate_program
from ..ops.disseminate import (fixpoint_formulation, fragments_in_sequence,
                               lanes_in_pull, valid_edge_at_publish,
                               valid_edge_of)
from ..ops.graph import ConnGraph, build_connection_graph
from ..ops.heartbeat import PULL_COUNTS, PULL_STAGES, run_heartbeats
from ..ops.pull import make_pull_bands, pull_rows_share
from ..ops.runs import disseminate as _disseminate_runs_program
from ..ops.state import SimParams, graph_arrays, init_state
from .logemit import LatenciesWriter
from .profiling import count_device_read, counters, device_read, span
from .summarize import LatencySummary, report, summarize_records

# Steady-state per-hop processing cost by muxer, DERIVED from the transport
# stack each choice composes (main.nim:433-441, main.go:361-366,
# main.rs:418-440) rather than asserted:
#
# The reference runs verifySignature=false (main.nim:247) and Noise over TCP
# for the muxed stacks (main.nim:425-427), so per-hop cost is NOT crypto or
# framing bytes (both are tens of µs for 15 KB) — it is ASYNC EVENT-LOOP
# CROSSINGS: each hop traverses the scheduler once per layer that re-queues
# the bytes (chronos/tokio/go-runtime dispatch on a single-core host).
#
# The per-crossing anchor is MEASURED, not asserted (VERDICT r3 missing
# #3): scripts/calibrate_event_loop.py ping-pongs a token through an
# asyncio scheduler while CONNECTTO=10 stream-handler tasks each hash a
# 15 KB payload per wake (the msgId provider's dominant per-message work,
# main.nim:123-124) — the same single-threaded-loop-under-load scene a
# reference node's scheduler services. Median on this host class:
# 0.2 ms/crossing (docs/event_loop_calibration.json, pinned by
# tests/test_simulator.py).
#
#   TCP+yamux  (withTcpTransport.withYamux): kernel TCP read -> Noise
#              decrypt loop -> yamux frame demux/window accounting ->
#              gossipsub RPC handler            = 4 crossings -> 0.8 ms
#   TCP+mplex  (withTcpTransport.withMplex): same 4 layers, but mplex's
#              varint header forces a header-then-payload double read per
#              frame (one extra partial wakeup)  ~ 4.4 crossings -> 0.88 ms
#   QUIC       (withQuicTransport): streams and crypto are native to the
#              transport — kernel UDP read -> QUIC packet/stream assembly
#              -> gossipsub RPC handler          = 3 crossings -> 0.6 ms
EVENT_LOOP_MS = 0.2          # measured: one scheduler crossing under load
_MUXER_CROSSINGS = {"yamux": 4.0, "mplex": 4.4, "quic": 3.0}
MUXER_PROC_MS = {m: EVENT_LOOP_MS * x for m, x in _MUXER_CROSSINGS.items()}

_INF_CUTOFF = 1e30


class PublisherDownError(RuntimeError):
    """A publish was asked of a peer that is dead at that moment. Under
    churn the peers `Simulator.run` publishes through are spared by the
    draw, so this is a caller's own choice of publisher (or state); a dead
    node sends nothing, and a record of it would read as a message nobody
    received."""


class MixDegradedError(RuntimeError):
    """The mix network has fewer eligible nodes than MIXD (a publish-time
    condition, not an engine failure — the service layer counts it as a
    failed publish request and keeps serving)."""


@dataclass
class ExperimentConfig:
    topo: TopoParams = field(default_factory=TopoParams)
    connect_to: int = 10              # CONNECTTO (run.sh:38 fixes 10)
    gossipsub: GossipSubParams = field(default_factory=GossipSubParams)
    publisher_id: int = 4             # run.sh:34
    publisher_rotation: bool = False  # run.sh:35
    warmup_s: float = 500.0           # injector start_time (topogen.py:130)
    self_trigger: bool = True         # SELFTRIGGER (main.nim:245)
    max_connections: int = 250        # MAXCONNECTIONS (main.nim:429)
    seed: int = 0
    with_gossip: bool = True
    churn_down_per_hb: float = 0.0
    churn_up_per_hb: float = 0.0
    # Mix-routing surface (README.md:42-46; BASELINE config 5). When
    # uses_mix is set, every publish relays through mix_d of the num_mix
    # mix-mounting peers before entering GossipSub (ops/mix.py).
    uses_mix: bool = False
    num_mix: int = 0
    mix_d: int = 4
    # Packet-loss model for lossy topologies (topogen -l): "tcp" turns loss
    # into RTO-retransmission latency the way Shadow's real TCP stacks do;
    # "message" drops whole copies (QUIC-unreliable-style). See
    # ops/disseminate.py loss model constants.
    loss_mode: str = "tcp"
    # Delivery-fidelity mode (SimParams.serialize_answers): True (default)
    # = exact answered-IWANT serialization including the delivery repair;
    # False = bounded mode for the large throughput configs (accounting/
    # attribution exact, arrival times keep the unserialized value where
    # queued answers bind, DisseminationResult.answer_wait_max_ms is the
    # per-hop error bar).
    serialize_answers: bool = True
    # Exact-repair engine (SimParams.answer_queue_mode, read only when
    # serialize_answers=True): "parallel_prefix" (default) = the scan-free
    # Jacobi refinement with an in-trace global-sort fallback;
    # "serial" = force the legacy global-sort outer iteration (the
    # reference engine the prefix path is bit/rtol-pinned against).
    answer_queue_mode: str = "parallel_prefix"
    # Cross-publish warm-started fixpoints (SimParams.warm_start): seed
    # each publish's relaxation from the previous message's arrival
    # offsets, certified + cold-rerun-guarded so results stay bit-identical
    # to cold starts. Off by default — the guard's untaken branch doubles
    # the publish compile, which only long publish loops amortize.
    warm_start: bool = False
    # Per-hop processing delay (SimParams.proc_delay_ms). None (default) =
    # the muxer's, MUXER_PROC_MS[topo.muxer]; an entry point whose node
    # runs with another states it here (the regression node: 2.0)
    proc_delay_ms: float | None = None
    # Message-id layout compat (SURVEY §7 quirks). "nim": a random 64-bit id
    # embedded at payload bytes 8-16 (gossipsub-queues/main.nim:169); "go":
    # the publish timestamp is the dedup key — Go/Rust embed no random id
    # (go main.go:63-81, rust main.rs:101-143), so their log lines key by
    # the LE64 nanosecond timestamp.
    msgid_mode: str = "nim"


def message_publisher(cfg: ExperimentConfig, i: int) -> int:
    """The peer message i of the schedule is published through:
    publisher_id, or with rotation one peer on for every message
    (run.sh:16-17, 34-35)."""
    return ((cfg.publisher_id + i * cfg.publisher_rotation)
            % cfg.topo.network_size)


def scheduled_publishers(cfg: ExperimentConfig) -> list[int]:
    """The peers `Simulator.run` publishes through, in order of first
    use."""
    messages = cfg.topo.messages if cfg.publisher_rotation else 1
    return list(dict.fromkeys(
        message_publisher(cfg, i) for i in range(messages)))


def graph_capacity(cfg: ExperimentConfig) -> int:
    """Neighbor-table width C of the (N, C) arrays a Simulator builds for
    `cfg`: MAXCONNECTIONS, or the dial-count slack that keeps rejections
    rare, whichever is smaller."""
    return min(cfg.max_connections, max(4 * cfg.connect_to, 16))


def disseminate(*args, return_plan: bool = False, **kw):
    """ops/disseminate.disseminate as a Simulator dispatches it: ALWAYS the
    program that also returns the publish's sampled plan, which is dropped
    here unless asked for. `return_plan` is a static argument of the jit, so
    a publish with its plan and one without are two executables: a process
    that takes one publish's plan (the benchmark's reference check does, and
    any differential replay would) compiled the whole publish a second
    time, 53-90 s at 100,000 peers on a v5e, and checked another executable
    than the one it had timed. The plan is what `sample` drew anyway (send
    sets, priorities, gossip targets, loss draws) plus five per-peer
    vectors: returning it keeps about 50 MB of intermediates alive to the
    end of a publish at (100000, 40) and costs no operation.

    An index of rank 3, (R, N, C), is the R runs of a batch
    (runtime/run_batch.py): the call goes to ops/runs.disseminate, every
    leaf a run owns with the runs' axis in front, under the same contract
    and this same name, so that whoever wraps the name for the length of a
    call (the benchmark's reference check) sees a batch's publishes as it
    sees a Simulator's."""
    if np.ndim(args[1]) == 3:
        res, state, plan = _disseminate_runs_program(*args, **kw)
    else:
        res, state, plan = _disseminate_program(*args, return_plan=True, **kw)
    return (res, state, plan) if return_plan else (res, state)


def drain_heartbeat_carry(carry_ms: float, ms: float, hb_ms: float):
    """Advance a fractional-heartbeat accumulator: returns (whole heartbeat
    steps due, new carry). Shared by every runtime that steps simulated time
    (Simulator, MultiTopicSimulator)."""
    carry = carry_ms + ms
    steps = int(carry // hb_ms)
    return steps, carry - steps * hb_ms


def _host(x, dtype=None):
    """`np.asarray(x)`, counted as the device->host read it is where `x`
    lives on the device (`profiling.device_reads`); a leaf a result view
    holds as numpy already, or a Python scalar, is no read."""
    if isinstance(x, jax.Array):
        count_device_read()
    return np.asarray(x, dtype=dtype)


def record_from_result(
    res, *, msg_id: int, publisher: int, t0_ms: float,
    extra_delay_ms: float = 0.0, drop_self=None, lanes_in_pull: int = 1,
    pull_rows_share: float = 100.0,
) -> "MessageRecord":
    """Build a MessageRecord from a DisseminationResult (shared by the
    single-topic and multi-topic publish paths). `drop_self`: peer id (or
    list of ids) whose own delivery is suppressed (SELFTRIGGER off,
    main.nim:245; unsubscribed originators/exit nodes with no handler)."""
    delays = _host(res.delay_ms, dtype=np.float64) + extra_delay_ms
    received = _host(res.received).copy()
    if drop_self is not None:
        received[np.asarray(drop_self)] = False
    delays = np.where(received, delays, np.inf)
    # the result's scalars come off the device in ONE read (`counters`, see
    # DisseminationResult). Result views that slice a block out of a bigger
    # run (multitopic's per-topic projection, a publish_batch column) carry
    # none: their records read converged and no refinement
    packed = getattr(res, "counters", None)
    values = ([0, 0, 0, 0, 1, 0, 0, 0, 0, 0, 0] if packed is None
              else [int(v) for v in _host(packed)])
    (fast_iters, refine_passes, refined, fell_back, converged,
     refined_serial, refine_lane_passes, lanes_hinted,
     lanes_uncertified, fast_sparse_iters,
     refine_sparse_passes) = values[:11]
    # under churn two more: who could send, and who of them sat under D_low
    alive, under_dlow = values[11:] or (None, None)
    return MessageRecord(
        msg_id=msg_id,
        publisher=publisher,
        t0_ms=t0_ms,
        delays_ms=delays,
        received=received,
        sends=_host(res.sends),
        copies_rx=_host(res.copies_rx),
        ihave=int(_host(res.ihave_sent).sum()),
        iwant=int(_host(res.iwant_sent).sum()),
        # the views above may not carry the scalar; exact mode's bar is 0.0
        # anyway
        answer_wait_max_ms=float(_host(
            getattr(res, "answer_wait_max_ms", 0.0))),
        converged=bool(converged),
        fast_iters=fast_iters,
        fast_sparse_iters=fast_sparse_iters,
        refine_passes=refine_passes,
        refine_sparse_passes=refine_sparse_passes,
        refined=bool(refined),
        fell_back=bool(fell_back),
        refined_serial=bool(refined_serial),
        refine_lane_passes=refine_lane_passes,
        lanes_hinted=lanes_hinted,
        lanes_uncertified=lanes_uncertified,
        lanes_in_pull=lanes_in_pull,
        pull_rows_share=pull_rows_share,
        alive=alive,
        under_dlow=under_dlow,
    )


class _BatchColumn:
    """Per-column view over a stacked publish_batch result: duck-typed like
    DisseminationResult so record_from_result unstacks one request's record
    from the batch ys without copying the whole stack."""

    __slots__ = ("delay_ms", "received", "sends", "copies_rx",
                 "ihave_sent", "iwant_sent", "answer_wait_max_ms")

    def __init__(self, ys_np: dict, i: int):
        self.delay_ms = ys_np["delay_ms"][i]
        self.received = ys_np["received"][i]
        self.sends = ys_np["sends"][i]
        self.copies_rx = ys_np["copies_rx"][i]
        self.ihave_sent = ys_np["ihave_sent"][i]
        self.iwant_sent = ys_np["iwant_sent"][i]
        self.answer_wait_max_ms = ys_np["answer_wait_max_ms"][i]


@dataclass
class MessageRecord:
    msg_id: int
    publisher: int
    t0_ms: float
    delays_ms: np.ndarray         # (N,) float, inf = never received
    received: np.ndarray          # (N,) bool
    sends: np.ndarray
    copies_rx: np.ndarray
    ihave: int
    iwant: int
    # bounded delivery mode only (SimParams.serialize_answers=False): the
    # per-hop arrival-time error bar — max time any requested gossip
    # answer waited queued. 0.0 in the exact default mode.
    answer_wait_max_ms: float = 0.0
    # DisseminationResult.converged: False when an iteration cap cut one of
    # the fixpoints this record rode (not checkpointed; views that carry no
    # bit read True)
    converged: bool = True
    # DisseminationResult.fast_iters / fast_sparse_iters / refine_passes /
    # refine_sparse_passes / refined / fell_back / refined_serial /
    # refine_lane_passes / lanes_hinted / lanes_uncertified:
    # how much work the publish's fixpoints did, which branches ran, which
    # engine refined and what the fragment lanes added (`stats<i>.json`
    # "publishes"; not checkpointed, views read 0 / False)
    fast_iters: int = 0
    fast_sparse_iters: int = 0
    refine_passes: int = 0
    refine_sparse_passes: int = 0
    refined: bool = False
    fell_back: bool = False
    refined_serial: bool = False
    refine_lane_passes: int = 0
    lanes_hinted: int = 0
    lanes_uncertified: int = 0
    # ops/disseminate.lanes_in_pull: the fragment lanes one row gather of
    # this publish's fixpoints carried (a trace-time constant of its shape,
    # no device read)
    lanes_in_pull: int = 1
    # ops/pull.pull_rows_share: 100 x the rows one pull of this publish
    # gathered / (peers x slots): 100 without the bands (make_pull_bands)
    pull_rows_share: float = 100.0
    # DisseminationResult.alive / under_dlow: under churn, the peers that
    # could send at this publish and those of them under D_low valid mesh
    # members (`stats<i>.json` "churn"); None without churn
    alive: int | None = None
    under_dlow: int | None = None

    @property
    def receivers(self) -> np.ndarray:
        return np.nonzero(self.received)[0]

    @property
    def delays_ms_int(self) -> np.ndarray:
        """Integer milliseconds as the reference logs them
        (inMilliseconds truncates, main.nim:150)."""
        return self.delays_ms[self.received].astype(np.int64)


class Simulator:
    def __init__(
        self,
        cfg: ExperimentConfig,
        topology: Topology | None = None,
        mesh=None,
        graph: ConnGraph | None = None,
    ):
        """`mesh`: optional 1-D jax.sharding.Mesh over the peer axis. When
        given, state/graph arrays are placed row-sharded across its devices
        and the dissemination fixpoint runs the explicit shard_map + ICI
        collective path (parallel/exchange.py). network_size must divide
        evenly by the device count.

        `graph`: the connection graph of a caller that formed its own (the
        regression node's, from kad-dht discovery) in place of the shuffle
        dials of `build_connection_graph`. Params, state, device arrays and
        every hoisted per-edge table are made from it here, in one place."""
        self._build_host(cfg, topology, mesh, graph)
        with span("build/tables"):
            self._build_device()

    @classmethod
    def host_side(cls, cfg: ExperimentConfig, topology: Topology):
        """One run of a batch (runtime/run_batch.py): everything a Simulator
        keeps on the host (its seed's graph, the params, the message ids,
        the records and what is emitted from them) and nothing on the
        device. `RunBatch` holds the R runs' states, index arrays and tables
        stacked, fills `records` as it publishes and leaves `state` when the
        schedule ends; whatever steps or publishes is not this object's."""
        run = cls.__new__(cls)
        run._build_host(cfg, topology, None, None)
        run.state = None
        return run

    def _build_host(self, cfg, topology, mesh, graph) -> None:
        cfg.topo.validate()
        cfg.gossipsub.validate()
        if cfg.msgid_mode not in ("nim", "go"):
            raise ValueError(f"unknown msgid_mode {cfg.msgid_mode!r}")
        if cfg.loss_mode not in ("message", "tcp"):
            raise ValueError(f"unknown loss_mode {cfg.loss_mode!r}")
        self.cfg = cfg
        self.mesh = mesh
        self.topology = topology or Topology.build(cfg.topo)
        n = cfg.topo.network_size
        # the two halves of the build, as the program's own spans (noted
        # only inside a `turn`): the graph is host numpy and nothing on the
        # device can start before it, the rest is state, device copies and
        # the tables made from the graph
        if graph is not None and graph.n != n:
            raise ValueError(
                f"graph of {graph.n} peers for a network of {n}")
        with span("build/graph"):
            if graph is None:
                graph = build_connection_graph(
                    n,
                    cfg.connect_to,
                    seed=cfg.seed,
                    max_degree=graph_capacity(cfg),
                )
            self.graph = graph
        proc_ms = (cfg.proc_delay_ms if cfg.proc_delay_ms is not None
                   else MUXER_PROC_MS.get(cfg.topo.muxer.lower(), 2.0))
        self.params = SimParams.from_gossipsub(
            n,
            self.graph.capacity,
            cfg.gossipsub,
            proc_delay_ms=proc_ms,
            churn_down_per_hb=cfg.churn_down_per_hb,
            churn_up_per_hb=cfg.churn_up_per_hb,
            serialize_answers=cfg.serialize_answers,
            answer_queue_mode=cfg.answer_queue_mode,
            warm_start=cfg.warm_start,
        )
        self._churny = (cfg.churn_down_per_hb > 0.0
                        or cfg.churn_up_per_hb > 0.0)
        # the node the injector publishes through does not churn (the
        # reference's injector POSTs to a named pod; a message from a
        # dead node measures nothing): the churn draw spares the peers
        # run() publishes through
        self.spared_peers: list[int] = (
            scheduled_publishers(cfg) if self._churny else [])
        # host mirror of state.subscribed: publish() picks the fanout code
        # path (static arg) without a device sync; keep in sync via
        # set_subscribed()
        self._subscribed_np = np.ones(n, dtype=bool)
        # cumulative SUBSCRIBE/UNSUBSCRIBE control-message counts per peer
        # (the Go tracer counts MESSAGES, metrics.go RecvRPC — a projection
        # from current state would diverge under mid-run churn): every node
        # joins at startup, every later flip broadcasts one more message
        self._sub_events_np = np.ones(n, dtype=np.int64)
        self._unsub_events_np = np.zeros(n, dtype=np.int64)
        self._msg_rng = np.random.default_rng(cfg.seed ^ 0x6D736749)  # msgId stream
        self._last_msg_id = -1  # go-mode monotonic timestamp tie-break
        self._hb_carry_ms = 0.0
        self.records: list[MessageRecord] = []
        # what the last write_latencies / write_shadowlog emitted: lines, and
        # blocks the native formatter took (`stats<i>.json` "emit")
        self.emit_counts: dict[str, int] = {}
        self._reset_heartbeat_pulls()
        # flight recorder (ops/telemetry.py): disarmed by default — advance()
        # then runs the exact pre-telemetry heartbeat program. Armed via
        # record_telemetry(); last_telemetry holds the most recent window's
        # host-side tel_* curves (node_service exports them as the
        # dst_sim_round_* family)
        self._telemetry = None
        self.last_telemetry: dict = {}
        self.mix_params = None
        if cfg.uses_mix:
            from ..ops.mix import MixParams

            self.mix_params = MixParams(num_mix=cfg.num_mix, mix_d=cfg.mix_d)
            self.mix_params.validate()

    def _build_device(self) -> None:
        """State, device copies of the graph and every table hoisted out of
        the publishes (the span `build/tables`)."""
        import jax.numpy as jnp

        cfg, mesh, n = self.cfg, self.mesh, self.params.n
        self.state = init_state(self.params, seed=cfg.seed)
        self.arrays = graph_arrays(self.graph)
        self._stage = jnp.asarray(self.topology.stage_of_peer)
        self._lat = jnp.asarray(self.topology.latency_ms)
        self._bw = jnp.asarray(self.topology.bw_up_mbit)
        # per-stage-pair packet loss (topogen -l); None keeps the lossless
        # fast path out of the compiled step entirely
        self._loss = (
            jnp.asarray(self.topology.packet_loss)
            if float(np.max(self.topology.packet_loss)) > 0.0 else None
        )
        # stage-pair edge tables are experiment constants: build them once
        # here instead of 70 ms/publish inside disseminate (ops edge_tables)
        from ..ops.disseminate import answer_tables, edge_tables

        self._lat_edge, self._loss_edge = edge_tables(
            self._stage, self._lat, self.arrays["conns"], self.arrays["rev"],
            self._loss)
        # so are the lat-sorted answer-queue service tables (two stable
        # argsorts per publish otherwise — the r5 bench's accounting bill)
        self._ans_tables = (
            answer_tables(self._lat_edge, self.arrays["conns"],
                          self.arrays["rev"])
            if cfg.with_gossip else None)
        if mesh is not None:
            from ..parallel.sharding import place_simulation, reshard_rows

            (self.state, self.arrays, self._stage, self._lat, self._bw,
             self._loss) = place_simulation(
                self.state, self.arrays, self._stage, self._lat, self._bw,
                self._loss, mesh)
            self._lat_edge = reshard_rows(self._lat_edge, mesh)
            if self._loss_edge is not None:
                self._loss_edge = reshard_rows(self._loss_edge, mesh)
            if self._ans_tables is not None:
                self._ans_tables = jax.tree_util.tree_map(
                    lambda x: reshard_rows(x, mesh), self._ans_tables)
        # so are the two bands of a publish's row pulls, where this
        # graph and shape admit them (ops/pull.make_pull_bands)
        self._pull_bands = self._compute_pull_bands()
        # neighbor alive&subscribed validity is publish-invariant between
        # membership changes: maintained here (set_subscribed recomputes,
        # churn disables the hoist — heartbeats mutate alive on device)
        self._valid_edge = None if self._churny else self._compute_valid_edge()
        # the spared peers as the churn draw reads them. Absent, not
        # all-false, with churn off: no churn-free program sees an
        # argument more
        self._spared = None
        if self._churny:
            spared = np.zeros(n, dtype=bool)
            spared[self.spared_peers] = True
            self._spared = jnp.asarray(spared)
            if mesh is not None:
                from ..parallel.sharding import reshard_rows

                self._spared = reshard_rows(self._spared, mesh)

    def _compute_pull_bands(self):
        """The hoisted bands of a publish's row pulls over the graph in
        `self.arrays` (slots [0, C1) of every row, the rest of the heavy
        rows), or None where the whole-width pull stays: a small or skewed
        graph, a mesh, past the gather budget (ops/pull.make_pull_bands)."""
        t = self._ans_tables
        return make_pull_bands(
            self.arrays["conns"], self.arrays["rev"],
            None if t is None else t.conns_sorted,
            None if t is None else t.rev_sorted, mesh=self.mesh)

    def _compute_valid_edge(self):
        """Hoisted per-edge delivery validity (connected AND the neighbor
        alive & subscribed): one row-gather pass here instead of one per
        publish. Only valid while liveness/membership is static — churny
        runs keep it None and every publish makes its own
        (`valid_edge_at_publish`)."""
        return valid_edge_of(self.state.alive, self.state.subscribed,
                             self.arrays["conns"], self.arrays["rev"])

    # ------------------------------------------- the scans' delivery counters

    # unread scans a Simulator holds at most before it reads them itself (a
    # caller that advances and never publishes must not hoard device arrays)
    _HB_UNREAD_MAX = 256

    def _reset_heartbeat_pulls(self) -> None:
        # per scan its packed counters (ops/heartbeat `pulls`), on the device
        # and not waited for, until a publish reads them with `t_ms`
        self._hb_unread: list = []
        self._hb_scans = 0
        self._hb_pulls = np.zeros((len(PULL_STAGES), len(PULL_COUNTS)),
                                  dtype=np.int64)

    @staticmethod
    def _folded(total: np.ndarray, scans: np.ndarray) -> np.ndarray:
        # ops/heartbeat._tallied over whole scans: counts add, maxima stay
        return np.concatenate(
            [total[:, :2] + scans[:, :, :2].sum(axis=0),
             np.maximum(total[:, 2:], scans[:, :, 2:].max(axis=0))], axis=1)

    def _note_heartbeat_pulls(self, read: list) -> None:
        """Fold the counters of the scans since the last read into the
        experiment's totals and put them on a zero-length annotation."""
        self._hb_unread = []
        if not read:
            return
        scans = np.stack(read).astype(np.int64)
        since = self._folded(np.zeros_like(self._hb_pulls), scans)
        self._hb_pulls = self._folded(self._hb_pulls, scans)
        self._hb_scans += len(scans)
        counters(
            "heartbeat/counters", scans=len(scans),
            pulls_sparse=int(since[:, PULL_COUNTS.index("sparse")].sum()),
            pulls_dense=int(since[:, PULL_COUNTS.index("dense")].sum()),
            **{f"{stage}_{count}": int(since[i, j])
               for i, stage in enumerate(PULL_STAGES)
               for j, count in enumerate(PULL_COUNTS)})

    @property
    def heartbeat_counts(self) -> dict:
        """`stats<i>.json` "heartbeat": over the experiment's scans, a stage
        how many steps delivered its reciprocity from the rows that sent,
        how many pulled it dense (the pull in front of a scan is a dense
        `validity`), and the most sending rows a step saw."""
        if self._hb_unread:
            self._note_heartbeat_pulls(device_read(self._hb_unread))
        by_stage = {
            stage: {count: int(self._hb_pulls[i, j])
                    for j, count in enumerate(PULL_COUNTS)}
            for i, stage in enumerate(PULL_STAGES)}
        return {
            "scans": self._hb_scans,
            "pulls_sparse": sum(v["sparse"] for v in by_stage.values()),
            "pulls_dense": sum(v["dense"] for v in by_stage.values()),
            **by_stage}

    # ---------------------------------------------------------------- phases

    def reset(self) -> None:
        """Rewind to the pre-warmup initial state, KEEPING the built graph,
        topology and compiled executables. The reference separates topology
        generation (topogen.py, run before Shadow starts) from the timed
        shadow run (run.sh); reset() gives benchmarks the same split — the
        host-side graph construction is prep, the warmup + injection
        schedule is the run."""
        import jax.numpy as jnp

        n = self.params.n
        self.state = init_state(self.params, seed=self.cfg.seed)
        if self.mesh is not None:
            from ..parallel.sharding import place_simulation

            (self.state, _, _, _, _, _) = place_simulation(
                self.state, dict(self.arrays), self._stage, self._lat,
                self._bw, self._loss, self.mesh)
        self._subscribed_np = np.ones(n, dtype=bool)
        self._sub_events_np = np.ones(n, dtype=np.int64)
        self._unsub_events_np = np.zeros(n, dtype=np.int64)
        self._msg_rng = np.random.default_rng(self.cfg.seed ^ 0x6D736749)
        self._last_msg_id = -1
        self._hb_carry_ms = 0.0
        self.records = []
        self._reset_heartbeat_pulls()
        self.last_telemetry = {}  # the recorder stays armed across resets
        if not self._churny:
            self._valid_edge = self._compute_valid_edge()

    def set_subscribed(self, mask) -> None:
        """Set per-peer topic membership. An unsubscribed peer can still
        publish — it goes through the gossipsub v1.1 fanout path
        (disseminate with_fanout)."""
        import jax.numpy as jnp

        mask = np.asarray(mask, dtype=bool)
        if mask.shape != (self.params.n,):
            raise ValueError(f"subscribed mask must be ({self.params.n},)")
        if float(self.state.t_ms) == 0.0 and not self.records:
            # pre-warmup: this DEFINES the startup membership — the one
            # SUBSCRIBE each joined node broadcasts at boot, nothing for
            # peers that never joined
            self._sub_events_np = mask.astype(np.int64)
            self._unsub_events_np = np.zeros_like(self._sub_events_np)
        else:
            # mid-run churn: every flip broadcasts one more control message
            self._sub_events_np = (
                self._sub_events_np + (mask & ~self._subscribed_np))
            self._unsub_events_np = (
                self._unsub_events_np + (~mask & self._subscribed_np))
        self._subscribed_np = mask
        sub = jnp.asarray(mask)
        # membership changed: the warm-start carry measured arrival offsets
        # on the old membership — invalidate it wholesale (INF = no carry)
        warm = jnp.full((self.params.n,), 3.4e38, dtype=jnp.float32)
        if self.mesh is not None:
            # keep the leaves row-sharded like the rest of the state pytree
            from ..parallel.sharding import reshard_rows

            sub = reshard_rows(sub, self.mesh)
            warm = reshard_rows(warm, self.mesh)
        self.state = self.state.replace(subscribed=sub, warm_offset_ms=warm)
        # refresh the hoisted validity mask against the new membership
        if not self._churny:
            self._valid_edge = self._compute_valid_edge()

    def rebind_graph(self, conns, rev, out_mask) -> None:
        """Adopt a mutated connection graph (the repair controller's dial
        path, ops/repair.py) as the simulator's current one.

        The dial path extends the involution into previously-free padding
        slots, which staleness-invalidates EVERY hoisted per-edge table:
        lat_edge/loss_edge and the answer-queue service tables index
        conns/rev directly, and valid_edge is a function of the edge set.
        All are re-derived here; the warm-start carry is invalidated
        wholesale (repair_round already wrote INF on the round a dial
        committed — this re-asserts it for callers that rebind from a
        checkpointed state). `self.graph` (the host-side ConnGraph) keeps
        the EPOCH graph: checkpoint identity hashes the built topology, so
        save_checkpoint must run before rebind_graph (runtime/campaign.py
        orders it that way)."""
        import jax.numpy as jnp

        from ..ops.disseminate import answer_tables, edge_tables

        self.arrays = {
            "conns": jnp.asarray(conns),
            "rev": jnp.asarray(rev),
            "out_mask": jnp.asarray(out_mask),
        }
        self._lat_edge, self._loss_edge = edge_tables(
            self._stage, self._lat, self.arrays["conns"], self.arrays["rev"],
            self._loss)
        self._ans_tables = (
            answer_tables(self._lat_edge, self.arrays["conns"],
                          self.arrays["rev"])
            if self.cfg.with_gossip else None)
        warm = jnp.full((self.params.n,), 3.4e38, dtype=jnp.float32)
        if self.mesh is not None:
            from ..parallel.sharding import reshard_rows

            self.arrays = {k: reshard_rows(v, self.mesh)
                           for k, v in self.arrays.items()}
            self._lat_edge = reshard_rows(self._lat_edge, self.mesh)
            if self._loss_edge is not None:
                self._loss_edge = reshard_rows(self._loss_edge, self.mesh)
            if self._ans_tables is not None:
                self._ans_tables = jax.tree_util.tree_map(
                    lambda x: reshard_rows(x, self.mesh), self._ans_tables)
            warm = reshard_rows(warm, self.mesh)
        self._pull_bands = self._compute_pull_bands()
        self.state = self.state.replace(warm_offset_ms=warm)
        if not self._churny:
            self._valid_edge = self._compute_valid_edge()

    def record_telemetry(self, params=None) -> None:
        """Arm the flight recorder: subsequent advance() calls return their
        per-heartbeat tel_* curves in `last_telemetry` (host numpy). Pass
        None or a record=False TelemetryParams to disarm — the disarmed
        advance() literally delegates to the untraced runner, so arming
        and disarming never perturbs the benign trajectory."""
        if params is not None:
            params.validate()
            if not params.enabled:
                params = None
        self._telemetry = params

    def advance(self, ms: float) -> None:
        """Advance simulated time by `ms`, running the heartbeats due."""
        steps, self._hb_carry_ms = drain_heartbeat_carry(
            self._hb_carry_ms, ms, self.params.heartbeat_ms)
        if steps > 0:
            a = self.arrays
            if self._telemetry is not None:
                from ..ops.telemetry import run_recorded_heartbeats

                self.state, trace = run_recorded_heartbeats(
                    self.state, a["conns"], a["rev"], a["out_mask"],
                    self.params, steps, telemetry=self._telemetry,
                    spared=self._spared)
                self.last_telemetry = {
                    k: np.asarray(v) for k, v in trace.items()}
            else:
                self.state, pulls = run_heartbeats(
                    self.state, a["conns"], a["rev"], a["out_mask"],
                    self.params, steps, spared=self._spared, with_pulls=True)
                if len(self._hb_unread) >= self._HB_UNREAD_MAX:
                    self._note_heartbeat_pulls(
                        device_read(self._hb_unread))
                self._hb_unread.append(pulls)

    def warmup(self) -> None:
        self.advance(self.cfg.warmup_s * 1000.0)

    def publish(
        self,
        publisher: int,
        msg_size: int | None = None,
        censor_edge=None,
    ) -> MessageRecord:
        """Inject one message at the current sim time (the /publish path).

        `censor_edge`: optional (N, C) adversarial per-edge delivery drop
        mask (ops/adversary.py censor_mask) threaded to disseminate; None
        (the default) keeps the benign publish trace bit-identical — the
        zero-attacker campaign contract (runtime/campaign.py)."""
        with span("publish", message=len(self.records)):
            # the host's first wait for the device is the read of t_ms here
            with span("publish/prepare"):
                cfg = self.cfg
                size = msg_size if msg_size is not None else cfg.topo.msg_size_bytes
                a = self.arrays
                valid_edge = self._valid_edge
                if self._churny:
                    # liveness moved since the last publish: the per-edge
                    # validity is this publish's own (one fused row pull,
                    # one dispatch), and the publisher's liveness comes
                    # with the read of t_ms
                    with span("publish/valid_edge"):
                        valid_edge, up = valid_edge_at_publish(
                            self.state.alive, self.state.subscribed,
                            a["conns"], a["rev"], publisher)
                    t_ms, up, pulls = device_read(
                        (self.state.t_ms, up, self._hb_unread))
                    self._note_heartbeat_pulls(pulls)
                    if not up:
                        raise PublisherDownError(
                            f"peer {publisher} is dead at t={float(t_ms)} ms "
                            "and cannot publish (the churn draw spares "
                            f"{self.spared_peers}: the peers run() "
                            "publishes through)")
                else:
                    # the scans' counters come with the read that waits for
                    # them anyway
                    t_ms, pulls = device_read(
                        (self.state.t_ms, self._hb_unread))
                    self._note_heartbeat_pulls(pulls)
                t0_ms = float(t_ms) + self._hb_carry_ms
                origin = publisher
                mix_delay = 0.0
                if self.mix_params is not None:
                    # relay through the mix network first; the exit node publishes
                    # on the origin's behalf (ops/mix.py, README.md:42-46)
                    import jax.numpy as jnp

                    from ..ops.mix import eligible_mix_count, mix_route, mix_wire_bytes

                    eligible = eligible_mix_count(
                        np.asarray(self.state.alive), publisher,
                        self.params.n, self.mix_params.num_mix,
                    )
                    if eligible < self.mix_params.mix_d:
                        raise MixDegradedError(
                            f"mix network degraded: {eligible} eligible mix nodes "
                            f"(alive, mounted, != publisher) < MIXD={self.mix_params.mix_d}"
                        )
                    key, k_mix = jax.random.split(self.state.key)
                    # occupancy-coupled: each hop's Sphinx serialization queues
                    # behind the sender's in-flight mesh/gossip traffic and is
                    # written back, so a relay's NEXT mesh forwarding queues behind
                    # the mix transmission it just made (shared real links)
                    path, exit_node, path_delay, uplink_new, rx_new = mix_route(
                        k_mix,
                        publisher,
                        self.state.alive,
                        self._stage,
                        self._lat,
                        self._bw,
                        params=self.mix_params,
                        n=self.params.n,
                        payload_bytes=size,
                        uplink_free_ms=self.state.uplink_free_ms,
                        rx_free_ms=self.state.rx_free_ms,
                        t0_ms=t0_ms,
                    )
                    mix_delay = float(path_delay)
                    wire = float(mix_wire_bytes(self.mix_params, size))
                    # per-hop attribution, both directions (Shadow's counters see
                    # both ends of every packet): senders are origin + first
                    # mix_d-1 relays, receivers are the mix_d relays
                    senders = jnp.concatenate(
                        [jnp.asarray([origin]), path[:-1]]
                    )
                    bytes_tx = self.state.bytes_tx.at[senders].add(wire)
                    bytes_rx = self.state.bytes_rx.at[path].add(wire)
                    self.state = self.state.replace(
                        key=key, bytes_tx=bytes_tx, bytes_rx=bytes_rx,
                        uplink_free_ms=uplink_new, rx_free_ms=rx_new,
                    )
                    publisher = int(exit_node)
            # enqueue only (trace + compile on a first call)
            with span("publish/dispatch"):
                res, self.state = disseminate(
                    self.state,
                    a["conns"],
                    a["rev"],
                    self._stage,
                    self._lat,
                    self._bw,
                    publisher=publisher,
                    t0_ms=t0_ms + mix_delay,
                    params=self.params,
                    payload_bytes=size,
                    fragments=cfg.topo.num_frags,
                    with_gossip=cfg.with_gossip,
                    mesh=self.mesh,
                    loss_stage=self._loss,
                    loss_mode=cfg.loss_mode,
                    lat_edge=self._lat_edge,
                    loss_edge=self._loss_edge,
                    ans_tables=self._ans_tables,
                    valid_edge=valid_edge,
                    censor_edge=censor_edge,
                    pull_bands=self._pull_bands,
                    # unsubscribed publisher -> gossipsub v1.1 fanout publish
                    with_fanout=not bool(self._subscribed_np[publisher]),
                )
            # every device->host read: the host waits for the publish here
            with span("publish/read"):
                rec = record_from_result(
                    res,
                    msg_id=self._next_msg_id(t0_ms),
                    publisher=origin,
                    t0_ms=t0_ms,
                    extra_delay_ms=mix_delay,
                    # a peer doesn't log its own message when SELFTRIGGER is off, and
                    # never when unsubscribed (no topic handler to fire): the origin
                    # on the fanout path, and a mix exit node publishing on the
                    # origin's behalf while itself unsubscribed
                    drop_self=[
                        p for p in {origin, publisher}
                        if (p == origin and not cfg.self_trigger)
                        or not self._subscribed_np[p]
                    ] or None,
                    lanes_in_pull=lanes_in_pull(
                        a["conns"].shape, cfg.topo.num_frags, self.mesh),
                    pull_rows_share=pull_rows_share(self._pull_bands),
                )
                self.records.append(rec)
            self._note_publish(rec)
        return rec

    def _next_msg_id(self, t0_ms: float) -> int:
        """The id of the message published at `t0_ms`, next in this run's
        stream."""
        if self.cfg.msgid_mode == "go":
            # Go/Rust key messages by the embedded LE64 ns timestamp. The
            # sim clock is float32-coarse, so back-to-back publishes could
            # collide where real nodes' nanosecond clocks would not —
            # enforce strict monotonicity the way distinct real publishes
            # always have distinct timestamps.
            msg_id = max(int(t0_ms * 1e6), self._last_msg_id + 1)
            self._last_msg_id = msg_id
            return msg_id
        return int(self._msg_rng.integers(0, 2**63, dtype=np.int64))

    def _note_publish(self, rec: MessageRecord) -> None:
        """The counters of the publish that `rec`, the newest record, is
        of, with the shape of its fixpoint loops (what a reader of the
        profile needs to turn iterations into bytes), on a zero-length
        annotation."""
        cfg, shape = self.cfg, (self.params.n, self.params.capacity)
        counters(
            "publish/counters", message=len(self.records) - 1,
            fast_iters=rec.fast_iters,
            fast_sparse_iters=rec.fast_sparse_iters,
            refine_passes=rec.refine_passes,
            refine_sparse_passes=rec.refine_sparse_passes,
            refined=int(rec.refined), fell_back=int(rec.fell_back),
            converged=int(rec.converged),
            refined_serial=int(rec.refined_serial),
            refine_lane_passes=rec.refine_lane_passes,
            lanes_hinted=rec.lanes_hinted,
            lanes_uncertified=rec.lanes_uncertified,
            peers=self.params.n, slots=self.params.capacity,
            fragments=cfg.topo.num_frags,
            rounds=self.params.history_gossip if cfg.with_gossip else 0,
            formulation=fixpoint_formulation(shape, self.mesh),
            in_sequence=int(fragments_in_sequence(
                shape, cfg.topo.num_frags, self.mesh)),
            lanes_in_pull=rec.lanes_in_pull,
            pull_rows_share=rec.pull_rows_share,
            **({} if rec.alive is None else
               {"alive": rec.alive, "under_dlow": rec.under_dlow}))

    def publish_batch(
        self,
        publishers,
        msg_size: int | None = None,
        pad_to: int | None = None,
    ) -> list[MessageRecord]:
        """Inject len(publishers) messages at the current sim time as ONE
        compiled device dispatch (ISSUE 14, ARCHITECTURE §16).

        The batch runs as a lax.scan over stacked seed columns whose carry
        is the SimState, so it is bit-identical to calling publish() once
        per entry in order — same PRNG splits, same uplink/rx occupancy
        serialization between same-t0 publishes, same warm-start carry
        (tests/test_batched_dispatch.py pins this) — while paying one
        dispatch instead of B. All entries share one static shape bucket:
        one msg_size and one fanout flag (mixed subscribed/unsubscribed
        publishers raise; callers group first — NodeService does).

        `pad_to` fixes the scan width: columns beyond len(publishers) run a
        state-passthrough cond branch, so every batch up to that width
        reuses one compiled program (the service passes its max_batch;
        None compiles per distinct width). Mix routing and peer-sharded
        grids keep the per-publish path: mix draws host-coupled routes per
        message, and the mesh dispatches disseminate under shard_map.
        """
        pubs = [int(p) for p in publishers]
        if not pubs:
            return []
        cfg = self.cfg
        if self.mix_params is not None or self.mesh is not None:
            return [self.publish(p, msg_size=msg_size) for p in pubs]
        subbed = {bool(self._subscribed_np[p]) for p in pubs}
        if len(subbed) != 1:
            raise ValueError(
                "publish_batch requires a uniform fanout bucket: mixed "
                "subscribed/unsubscribed publishers in one batch — group "
                "them first (NodeService._group_batch does)")
        with_fanout = not subbed.pop()
        size = msg_size if msg_size is not None else cfg.topo.msg_size_bytes
        a = self.arrays
        t0_ms = float(self.state.t_ms) + self._hb_carry_ms
        b = len(pubs)
        width = b if pad_to is None else max(int(pad_to), b)
        rows = np.zeros(width, dtype=np.int32)
        rows[:b] = pubs
        active = np.zeros(width, dtype=bool)
        active[:b] = True

        from .publisher import publish_batch_scan

        ys, self.state = publish_batch_scan(
            self.state, a["conns"], a["rev"], self._stage, self._lat,
            self._bw, rows, active, t0_ms, self.params, size,
            cfg.topo.num_frags, cfg.with_gossip, self._loss, cfg.loss_mode,
            self._lat_edge, self._loss_edge, self._ans_tables,
            self._valid_edge, with_fanout)

        ys_np = {k: np.asarray(v) for k, v in ys.items()}
        recs = []
        for i, pub in enumerate(pubs):
            recs.append(record_from_result(
                _BatchColumn(ys_np, i),
                msg_id=self._next_msg_id(t0_ms),
                publisher=pub,
                t0_ms=t0_ms,
                drop_self=(
                    [pub] if (not cfg.self_trigger)
                    or not self._subscribed_np[pub] else None),
            ))
        self.records.extend(recs)
        return recs

    def run(
        self,
        checkpoint_path: str | None = None,
        checkpoint_every: int = 1,
    ) -> list[MessageRecord]:
        """Full experiment: warm-up, then the injection schedule.

        `checkpoint_path`: snapshot the experiment there after every
        `checkpoint_every`-th message (runtime/checkpoint.py; each snapshot
        re-serializes all state + records, so raise the interval for long
        schedules at large N); a run resumed from that file via
        `load_checkpoint(path).run()` continues the remaining schedule
        bit-exactly."""
        cfg = self.cfg
        done = len(self.records)  # >0 when resumed from a checkpoint
        if done == 0:
            with span("warmup"):    # dispatch of the warm-up scan
                self.warmup()
        delay_ms = cfg.topo.delay_seconds * 1000.0
        for i in range(done, cfg.topo.messages):
            if i > 0:
                with span("advance"):
                    self.advance(delay_ms)
            self.publish(message_publisher(cfg, i))
            if checkpoint_path is not None and (
                (i + 1) % max(checkpoint_every, 1) == 0
                or i == cfg.topo.messages - 1
            ):
                from .checkpoint import save_checkpoint

                save_checkpoint(self, checkpoint_path)
        return self.records

    # --------------------------------------------------------------- outputs

    def latencies_writer(self) -> LatenciesWriter:
        w = LatenciesWriter()
        for rec in self.records:
            w.add_message(rec.msg_id, rec.receivers, rec.delays_ms_int)
        return w

    def write_latencies(self, path: str) -> int:
        from . import native_logemit

        before = native_logemit.native_blocks
        lines = self.latencies_writer().write(path)
        self.emit_counts["latencies_lines"] = lines
        self.emit_counts["latencies_native_blocks"] = (
            native_logemit.native_blocks - before)
        return lines

    def summary(self, large: bool | None = None) -> LatencySummary:
        """The latency summary, from the records' arrays: what `summarize`
        gives on the lines of `latencies<i>`, with no line formatted."""
        if large is None:
            large = self.cfg.topo.msg_size_bytes >= 1000  # run.sh:68 switch
        return summarize_records(
            ((rec.msg_id, rec.receivers, rec.delays_ms_int)
             for rec in self.records), large=large)

    def summary_report(self) -> str:
        large = self.cfg.topo.msg_size_bytes >= 1000
        return report(self.summary(large), large=large)

    def traffic(self):
        """Cumulative per-peer traffic counters (runtime/bandwidth.py)."""
        from .bandwidth import PeerTraffic

        return PeerTraffic.from_state(self.state)

    def write_shadowlog(self, path: str) -> int:
        """Write Shadow-heartbeat-shaped '[node]' lines: the input of
        summary_shadowlog.awk (run.sh:70-74)."""
        from . import native_logemit
        from .bandwidth import shadowlog_text

        traffic = self.traffic()
        before = native_logemit.native_shadowlog_blocks
        block = shadowlog_text(traffic)
        with open(path, "w") as f:
            f.write(block)
        lines = traffic.rx_bytes.shape[0]
        self.emit_counts["shadowlog_lines"] = lines
        self.emit_counts["shadowlog_native_blocks"] = (
            native_logemit.native_shadowlog_blocks - before)
        return lines

    def bandwidth_report(self) -> str:
        from .bandwidth import report as bw_report
        from .bandwidth import summarize_bandwidth

        return bw_report(summarize_bandwidth(self.traffic()))

    # ------------------------------------------------------------ statistics

    def peer_rounds_per_sec(self, wall_seconds: float) -> float:
        """The metric of record: simulated peers x heartbeat-rounds / wall s."""
        sim_rounds = (float(self.state.t_ms)) / self.params.heartbeat_ms
        return self.cfg.topo.network_size * sim_rounds / max(wall_seconds, 1e-9)
