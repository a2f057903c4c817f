"""Multi-topic GossipSub simulation (BASELINE config 3: "10k-peer
multi-topic, IHAVE/IWANT heartbeat + peer scoring").

The reference nodes run a single topic ("test", gossipsub-queues
main.nim:450), but the protocol and the Go/Rust metric surfaces are
per-topic: the tracer keeps mesh size, peer counts, and a topic-health
classifier per topic string (go-test-node/metrics.go:348-380,
rust-test-node/src/metrics.rs:158-176).

TPU-first design — topics as VIRTUAL PEERS, not a vmap axis: topic t's copy
of peer p is row t*N + p of one block-diagonal connection graph (the same
physical connections repeated per topic with a t*N offset, so no edge
crosses a topic block — exactly one libp2p host multiplexing independent
per-topic meshes over one connection set). The ordinary single-topic engine
then runs unchanged over T*N rows:

  - ONE heartbeat scan advances every topic with no vmap. This matters for
    speed: the engine's steady-state lax.cond skips (graft/prune/decay are
    no-ops on stable meshes) vmap-lower to `select`, which executes BOTH
    branches — a vmapped-topics formulation pays the full rebalance cost
    every step, the stacked formulation skips it globally.
  - publish() targets row t*N + p; dissemination cannot leave the topic
    block (there are no cross-block edges), so per-topic isolation is a
    property of the graph, not of bookkeeping.
  - per-topic metrics are reshapes of the flat (T*N, ...) state.

Subscription model: `subscribe_fraction` < 1 subscribes each peer to each
topic independently with that probability (seeded, reproducible); 1.0 =
everyone on every topic.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from ..config.env import GossipSubParams
from ..config.topology import Topology, TopoParams
from ..ops.disseminate import disseminate
from ..ops.graph import build_connection_graph
from ..ops.heartbeat import run_heartbeats
from ..ops.state import SimParams, init_state
from .simulator import (
    MUXER_PROC_MS,
    MessageRecord,
    drain_heartbeat_carry,
    record_from_result,
)


@dataclass
class MultiTopicConfig:
    topo: TopoParams = field(default_factory=TopoParams)
    topics: tuple = ("test",)
    connect_to: int = 10
    gossipsub: GossipSubParams = field(default_factory=GossipSubParams)
    subscribe_fraction: float = 1.0
    warmup_s: float = 60.0
    seed: int = 0
    with_gossip: bool = True
    max_connections: int = 250       # MAXCONNECTIONS (main.nim:429)
    self_trigger: bool = True        # SELFTRIGGER (main.nim:245)
    loss_mode: str = "tcp"           # see ExperimentConfig.loss_mode

    def validate(self) -> None:
        self.topo.validate()
        self.gossipsub.validate()
        if self.loss_mode not in ("message", "tcp"):
            raise ValueError(f"unknown loss_mode {self.loss_mode!r}")
        if not self.topics:
            raise ValueError("need at least one topic")
        if len(set(self.topics)) != len(self.topics):
            raise ValueError("duplicate topic names")
        if not (0.0 < self.subscribe_fraction <= 1.0):
            raise ValueError("subscribe_fraction must be in (0, 1]")


class _TopicStateView:
    """Per-topic view of the flat (T*N, ...) state: every peer-major leaf
    reshapes to (T, N, ...); scalars pass through. Read-only convenience for
    metrics/tests."""

    def __init__(self, state, n_topics: int, n_peers: int):
        self._state = state
        self._t = n_topics
        self._n = n_peers

    def __getattr__(self, name):
        leaf = getattr(self._state, name)
        if hasattr(leaf, "ndim") and leaf.ndim >= 1 \
                and leaf.shape[0] == self._t * self._n:
            return leaf.reshape((self._t, self._n) + leaf.shape[1:])
        return leaf


class MultiTopicSimulator:
    """T topics over one shared connection graph, stacked as virtual peers."""

    def __init__(self, cfg: MultiTopicConfig, topology: Topology | None = None,
                 mesh=None):
        """`mesh`: optional 1-D jax.sharding.Mesh over the (virtual) peer
        axis — the T*N stacked rows shard across its devices exactly like
        the single-topic Simulator's rows, and every publish runs the
        explicit shard_map + ICI collective fixpoint. T*network_size must
        divide evenly by the device count."""
        cfg.validate()
        self.cfg = cfg
        self.mesh = mesh
        self.topology = topology or Topology.build(cfg.topo)
        n = cfg.topo.network_size
        tcount = len(cfg.topics)
        self.n_peers = n
        self.graph = build_connection_graph(
            n, cfg.connect_to, seed=cfg.seed,
            max_degree=min(cfg.max_connections, max(4 * cfg.connect_to, 16)),
        )
        proc_ms = MUXER_PROC_MS.get(cfg.topo.muxer.lower(), 2.0)
        self.params = SimParams.from_gossipsub(
            tcount * n, self.graph.capacity, cfg.gossipsub,
            proc_delay_ms=proc_ms,
        )
        # block-diagonal stack: per-topic copies of the same physical edges,
        # shifted by t*N; padding (-1) stays padding. rev/out_mask are
        # slot-local, so a plain tile suffices.
        off = (np.arange(tcount) * n)[:, None, None]
        conns = np.where(
            self.graph.conns[None] >= 0, self.graph.conns[None] + off, -1
        ).reshape(tcount * n, -1)
        self.arrays = {
            "conns": jnp.asarray(conns),
            "rev": jnp.asarray(np.tile(self.graph.rev, (tcount, 1))),
            "out_mask": jnp.asarray(np.tile(self.graph.out_mask, (tcount, 1))),
        }
        self._stage = jnp.asarray(np.tile(self.topology.stage_of_peer, tcount))
        self._lat = jnp.asarray(self.topology.latency_ms)
        self._bw = jnp.asarray(self.topology.bw_up_mbit)
        # per-stage-pair packet loss (topogen -l): the tiled stage array
        # already indexes the (S+1, S+1) matrix, so no tiling is needed;
        # None keeps the lossless fast path out of the compiled step
        self._loss = (
            jnp.asarray(self.topology.packet_loss)
            if float(np.max(self.topology.packet_loss)) > 0.0 else None
        )
        # stage-pair edge tables: experiment constants, built once (the
        # tiled stage/conns arrays make them valid across topic blocks)
        from ..ops.disseminate import answer_tables, edge_tables

        self._lat_edge, self._loss_edge = edge_tables(
            self._stage, self._lat, self.arrays["conns"], self.arrays["rev"],
            self._loss)
        # lat-sorted answer-queue service tables: also experiment constants
        # (lat_edge, conns and rev only), hoisted off the per-publish path
        self._ans_tables = (
            answer_tables(self._lat_edge, self.arrays["conns"],
                          self.arrays["rev"])
            if cfg.with_gossip else None)

        rng = np.random.default_rng(np.random.SeedSequence([cfg.seed, 0x709]))
        self.subscribed_np = np.ones((tcount, n), dtype=bool)
        if cfg.subscribe_fraction < 1.0:
            # a topic with no subscribers is legal; an empty mesh just
            # classifies as "no peers" in the health metric
            self.subscribed_np = rng.random((tcount, n)) < cfg.subscribe_fraction
        self.state = init_state(self.params, seed=cfg.seed)
        # a physical node's heartbeat timer is shared by all its topics: tile
        # one per-NODE phase draw across the topic blocks (same for the
        # uplink below — the T*N rows are one host's T protocol views, not
        # T*N hosts)
        phase_node = np.asarray(self.state.hb_phase)[:n]
        self.state = self.state.replace(
            subscribed=jnp.asarray(self.subscribed_np.reshape(-1)),
            hb_phase=jnp.asarray(np.tile(phase_node, tcount)))
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
                import jax

                self._ans_tables = jax.tree_util.tree_map(
                    lambda x: reshard_rows(x, mesh), self._ans_tables)
        self._hb_carry_ms = 0.0
        self.records: list[tuple[str, MessageRecord]] = []
        self._msg_rng = np.random.default_rng(cfg.seed ^ 0x6D736749)

    def reset(self) -> None:
        """Rewind to the pre-warmup initial state, keeping the built stacked
        graph, topology, subscription draw and compiled executables (same
        prep/run split as Simulator.reset)."""
        tcount = len(self.cfg.topics)
        n = self.n_peers
        self.state = init_state(self.params, seed=self.cfg.seed)
        phase_node = np.asarray(self.state.hb_phase)[:n]
        self.state = self.state.replace(
            subscribed=jnp.asarray(self.subscribed_np.reshape(-1)),
            hb_phase=jnp.asarray(np.tile(phase_node, tcount)))
        if self.mesh is not None:
            from ..parallel.sharding import place_simulation

            (self.state, _, _, _, _, _) = place_simulation(
                self.state, dict(self.arrays), self._stage, self._lat,
                self._bw, self._loss, self.mesh)
        self._hb_carry_ms = 0.0
        self.records = []
        self._msg_rng = np.random.default_rng(self.cfg.seed ^ 0x6D736749)

    # ---------------------------------------------------------------- stepping

    @property
    def states(self) -> _TopicStateView:
        """(T, N, ...) reshaped view of the flat per-topic state."""
        return _TopicStateView(self.state, len(self.cfg.topics), self.n_peers)

    def advance(self, ms: float) -> None:
        """Advance all topics' meshes together (one unbatched scan — see the
        module docstring for why this beats a vmap over topics)."""
        steps, self._hb_carry_ms = drain_heartbeat_carry(
            self._hb_carry_ms, ms, self.params.heartbeat_ms)
        if steps <= 0:
            return
        a = self.arrays
        self.state = run_heartbeats(
            self.state, a["conns"], a["rev"], a["out_mask"], self.params, steps
        )

    def warmup(self) -> None:
        self.advance(self.cfg.warmup_s * 1000.0)

    # --------------------------------------------------------------- publish

    def topic_index(self, topic: str) -> int:
        try:
            return self.cfg.topics.index(topic)
        except ValueError:
            raise KeyError(f"topic not joined: {topic!r}") from None

    def publish(self, topic: str, publisher: int,
                msg_size: int | None = None) -> MessageRecord:
        """One message on one topic; dissemination stays inside the topic's
        block of the stacked graph by construction.

        A publisher not subscribed to the topic goes through the gossipsub
        v1.1 fanout path (disseminate with_fanout): it sends to a persistent
        fanout set of up to D topic peers with fanout-TTL expiry."""
        ti = self.topic_index(topic)
        size = msg_size if msg_size is not None else self.cfg.topo.msg_size_bytes
        a = self.arrays
        n = self.n_peers
        t0_ms = float(self.state.t_ms) + self._hb_carry_ms
        res, self.state = disseminate(
            self.state, a["conns"], a["rev"], self._stage, self._lat,
            self._bw, publisher=ti * n + publisher, t0_ms=t0_ms,
            params=self.params, payload_bytes=size,
            fragments=self.cfg.topo.num_frags,
            with_gossip=self.cfg.with_gossip,
            mesh=self.mesh,
            loss_stage=self._loss,
            loss_mode=self.cfg.loss_mode,
            lat_edge=self._lat_edge,
            loss_edge=self._loss_edge,
            ans_tables=self._ans_tables,
            with_fanout=not bool(self.subscribed_np[ti][publisher]),
        )
        # one uplink per physical NODE: fold the per-row occupancy across
        # topic blocks so a publish on topic B queues behind topic A's
        # in-flight traffic (the reference's per-connection queues carry all
        # topics of a host; cross-topic coupling happens at publish
        # granularity, which is exact for this host-sequential publish loop)
        t_ct = len(self.cfg.topics)
        if t_ct > 1:
            u_node = self.state.uplink_free_ms.reshape(t_ct, n).max(axis=0)
            u_all = jnp.tile(u_node, t_ct)
            # the downlink is per physical NODE too: fold receiver occupancy
            # across topic blocks so copies of topic B drain behind topic A's
            r_node = self.state.rx_free_ms.reshape(t_ct, n).max(axis=0)
            r_all = jnp.tile(r_node, t_ct)
            if self.mesh is not None:
                # keep the leaves row-sharded like the rest of the state
                from ..parallel.sharding import reshard_rows

                u_all = reshard_rows(u_all, self.mesh)
                r_all = reshard_rows(r_all, self.mesh)
            self.state = self.state.replace(
                uplink_free_ms=u_all, rx_free_ms=r_all)
        blk = slice(ti * n, (ti + 1) * n)

        class _Blk:  # the topic's N-row window of the stacked result
            delay_ms = res.delay_ms[blk]
            received = res.received[blk]
            sends = res.sends[blk]
            copies_rx = res.copies_rx[blk]
            ihave_sent = res.ihave_sent[blk]
            iwant_sent = res.iwant_sent[blk]
            # SCALARS, not block-sliced: the bounded-mode error bar covers
            # the whole stacked publish — without this projection
            # record_from_result's tolerant getattr silently zeroed the bar
            # for every multitopic record
            answer_wait_max_ms = res.answer_wait_max_ms
            counters = res.counters    # whole-publish scalars too

        rec = record_from_result(
            _Blk,
            msg_id=int(self._msg_rng.integers(0, 2**63, dtype=np.int64)),
            publisher=publisher,
            t0_ms=t0_ms,
            # the publisher doesn't log its own message when SELFTRIGGER is
            # off, and never when unsubscribed (no topic handler to fire —
            # the fanout-publish case)
            drop_self=publisher
            if (not self.cfg.self_trigger
                or not self.subscribed_np[ti][publisher])
            else None,
        )
        self.records.append((topic, rec))
        return rec

    def publish_batch(self, items, msg_size: int | None = None,
                      pad_to: int | None = None) -> list[MessageRecord]:
        """Batched device dispatch across topics (ISSUE 14): `items` is a
        sequence of (topic, publisher) pairs injected at the current sim
        time as ONE compiled scan over stacked seed columns.

        The topic is a ROW INDEX (ti * n + publisher), not a static, so one
        batch freely mixes topics — the eth2 att-subnet lane batches across
        its subnets. Only msg_size and the fanout flag are static bucket
        keys (mixed fanout raises; callers group). The scan body replays
        the cross-topic uplink/rx occupancy fold between columns, making
        the batch bit-identical to the sequential publish loop
        (tests/test_batched_dispatch.py pins the mixed-topic case).
        `pad_to` fixes the compiled scan width as in Simulator.publish_batch.
        """
        pairs = [(str(t), int(p)) for t, p in items]
        if not pairs:
            return []
        if self.mesh is not None:
            return [self.publish(t, p, msg_size=msg_size) for t, p in pairs]
        n = self.n_peers
        t_ct = len(self.cfg.topics)
        tis = [self.topic_index(t) for t, _ in pairs]
        subbed = {bool(self.subscribed_np[ti][p])
                  for ti, (_, p) in zip(tis, pairs)}
        if len(subbed) != 1:
            raise ValueError(
                "publish_batch requires a uniform fanout bucket: mixed "
                "subscribed/unsubscribed (topic, publisher) pairs in one "
                "batch — group them first (NodeService._group_batch does)")
        with_fanout = not subbed.pop()
        size = msg_size if msg_size is not None else self.cfg.topo.msg_size_bytes
        a = self.arrays
        t0_ms = float(self.state.t_ms) + self._hb_carry_ms
        b = len(pairs)
        width = b if pad_to is None else max(int(pad_to), b)
        rows = np.zeros(width, dtype=np.int32)
        rows[:b] = [ti * n + p for ti, (_, p) in zip(tis, pairs)]
        active = np.zeros(width, dtype=bool)
        active[:b] = True

        from .publisher import publish_batch_scan

        ys, self.state = publish_batch_scan(
            self.state, a["conns"], a["rev"], self._stage, self._lat,
            self._bw, rows, active, t0_ms, self.params, size,
            self.cfg.topo.num_frags, self.cfg.with_gossip, self._loss,
            self.cfg.loss_mode, self._lat_edge, self._loss_edge,
            self._ans_tables, None, with_fanout, topic_blocks=t_ct)

        ys_np = {k: np.asarray(v) for k, v in ys.items()}

        class _BlkCol:  # one request's topic-block window of the batch ys
            __slots__ = ("delay_ms", "received", "sends", "copies_rx",
                         "ihave_sent", "iwant_sent", "answer_wait_max_ms")

            def __init__(self, i, blk):
                self.delay_ms = ys_np["delay_ms"][i][blk]
                self.received = ys_np["received"][i][blk]
                self.sends = ys_np["sends"][i][blk]
                self.copies_rx = ys_np["copies_rx"][i][blk]
                self.ihave_sent = ys_np["ihave_sent"][i][blk]
                self.iwant_sent = ys_np["iwant_sent"][i][blk]
                # scalar, covers the whole stacked publish (see _Blk above)
                self.answer_wait_max_ms = ys_np["answer_wait_max_ms"][i]

        recs = []
        for i, (ti, (topic, pub)) in enumerate(zip(tis, pairs)):
            rec = record_from_result(
                _BlkCol(i, slice(ti * n, (ti + 1) * n)),
                msg_id=int(self._msg_rng.integers(0, 2**63, dtype=np.int64)),
                publisher=pub,
                t0_ms=t0_ms,
                drop_self=pub
                if (not self.cfg.self_trigger
                    or not self.subscribed_np[ti][pub])
                else None,
            )
            self.records.append((topic, rec))
            recs.append(rec)
        return recs

    # --------------------------------------------------------------- metrics

    def mesh_sizes(self) -> dict:
        """Per-topic mean mesh degree over subscribed+alive peers — the
        libp2p_gossipsub_peers_per_topic_mesh family, one label per topic."""
        out = {}
        mesh = np.asarray(self.states.mesh_mask)       # (T, N, C)
        alive = np.asarray(self.states.alive)          # (T, N)
        for ti, name in enumerate(self.cfg.topics):
            member = self.subscribed_np[ti] & alive[ti]
            deg = mesh[ti].sum(axis=-1)[member]
            out[name] = float(deg.mean()) if deg.size else 0.0
        return out

    def topic_health(self) -> dict:
        """The Go tracer's 3-way classifier (metrics.go:348-380): a topic is
        'no' with zero mesh peers, 'low' under D_lo, else 'healthy' — here
        judged from the publisher-side mean mesh degree."""
        sizes = self.mesh_sizes()
        d_lo = self.params.d_low
        return {
            name: ("no" if s == 0 else "low" if s < d_lo else "healthy")
            for name, s in sizes.items()
        }
