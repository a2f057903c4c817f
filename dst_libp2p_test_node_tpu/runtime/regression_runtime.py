"""Regression-node runtime: GossipSub over kad-dht discovery + mesh pings.

The reference regression node (nim-test-node/regression/{main,env,ping_utils,
kad_utils}.nim) runs the same GossipSub publish/receive core as the flagship
node but forms its mesh through Kademlia bootstrap instead of static dials:

  RoleBootstrap   kad-dht anchor only — no GossipSub (main.nim:219-223)
  RoleNormal      mount GossipSub(+ping)+kad -> STARTSLEEP (180 s default,
                  env.nim:15) -> dial bootstrap -> seedBootstraps: updatePeers
                  + kad.bootstrap(forceRefresh) (kad_utils.nim:88-94) ->
                  mesh grafts from DHT-discovered connections ->
                  pingMeshLoop: every 45 s ping each mesh peer, logging
                  dial/ping ms (ping_utils.nim:8-15, 23-87)

GossipSub params differ slightly from the flagship (main.nim:141-152:
dScore=6, dOut=3, no env overrides) — captured here as defaults.

TPU mapping: the discovery phase runs batched FIND_NODE waves (ops/kad) —
one self-lookup "bootstrap round" (forceRefresh) plus warmup randoms — and
the connection graph for GossipSub is then sampled from each node's ROUTING
TABLE (the reference grafts from DHT-discovered conns, kad_utils.nim:8-11)
instead of the flagship's uniform shuffle-dials. Dissemination and heartbeat
then reuse the standard engine: the Simulator is built ON that graph
(`Simulator(cfg, graph=...)`), so its params, state, device arrays and
hoisted per-edge tables all come from it. Mesh pings are array ops: RTT per
mesh edge from the stage latency matrix + muxer processing, logged in the
reference's "mesh ping" key=value shape.

The phases note the program's own spans (runtime/profiling.span; inside
`cli.cmd_regression`'s turn they reach `--stats-json`): `run/topology`,
`run/discover` (a `discover/wave` a FIND_NODE wave), `run/discovery_graph`,
`run/simulator_init`, `run/simulate`, `run/pings`.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..config.env import GossipSubParams, env_int, env_str
from ..config.topology import Topology, TopoParams
from ..ops import kad
from ..ops.graph import ConnGraph, build_connection_graph
from .kad_runtime import dispatch_waves, latency_percentiles, wave_numbers
from .profiling import counters, span
from .simulator import (ExperimentConfig, MessageRecord, Simulator,
                        graph_capacity)

MESH_PING_INTERVAL_S = 45.0     # ping_utils.nim:9
MESH_PING_TIMEOUT_MS = 4000.0   # ping_utils.nim:10
PING_PROC_MS = 2.0              # dial/processing overhead of one ping
PING_LINES_LOGGED = 20          # of a round (the reference logs every ping)
# the per-hop processing delay the regression path has always run with
# (SimParams' default), stated so that the Simulator is built with it
REGRESSION_PROC_DELAY_MS = 2.0


def regression_gossipsub_params() -> GossipSubParams:
    """The regression node's fixed GossipSub tuning (main.nim:141-152)."""
    return GossipSubParams(d=6, d_low=4, d_high=8, d_score=6, d_out=3,
                           d_lazy=6)


@dataclass
class RegressionConfig:
    network_size: int = 100
    n_bootstrap: int = 1
    connect_to: int = 10
    start_sleep_s: float = 180.0      # STARTSLEEP (env.nim:15)
    discovery_rounds: int = 3         # bootstrap + warmup lookup waves
    muxer: str = "yamux"
    fragments: int = 1                # FRAGMENTS
    msg_size: int = 1000
    messages: int = 10
    delay_seconds: float = 4.0
    ping_rounds: int = 2              # pingMeshLoop iterations to simulate
    seed: int = 0
    topo: TopoParams | None = None

    def validate(self) -> None:
        if self.n_bootstrap < 1:
            raise ValueError("need at least one bootstrap")
        if self.n_bootstrap + self.connect_to >= self.network_size:
            raise ValueError("connect_to too large for network size")


@dataclass
class MeshPings:
    """One pingMeshLoop pass, an entry a mesh edge, in (peer, slot) order."""
    peer: np.ndarray      # (M,) int64 who pings
    target: np.ndarray    # (M,) int32 the mesh peer pinged
    ping_ms: np.ndarray   # (M,) float64 round-trip time


@dataclass
class RegressionSummary:
    census_mean: float
    mesh_degree_mean: float
    coverage: float
    ping_count: int
    ping_ms_p50: float
    ping_ms_p99: float
    ping_timeouts: int

    def report(self) -> str:
        return "\n".join([
            "Regression summary",
            f"Routing table census: mean {self.census_mean:.1f}",
            f"Mesh degree: mean {self.mesh_degree_mean:.1f}",
            f"Coverage: {self.coverage * 100.0:.1f}%",
            f"Mesh pings: {self.ping_count} "
            f"({self.ping_timeouts} over the {MESH_PING_TIMEOUT_MS:.0f} ms "
            "timeout)",
            f"Ping RTT ms: p50 {self.ping_ms_p50:.0f} "
            f"p99 {self.ping_ms_p99:.0f}",
        ])


def _padded_dials(dials: np.ndarray, rows: np.ndarray,
                  bootstraps: np.ndarray, n: int) -> np.ndarray:
    """Short tables: what `rows` drew from their tables (-1 where the table
    ran out), then the anchors, then ring neighbours p+1, p+2, ... — the
    first connect_to distinct of them, never the peer itself."""
    m, k = dials.shape
    cand = np.concatenate([
        dials,
        np.broadcast_to(np.sort(bootstraps), (m, len(bootstraps))),
        (rows[:, None] + 1 + np.arange(k)) % n,
    ], axis=1)
    w = cand.shape[1]
    earlier = np.tri(w, w, -1, dtype=bool)       # [a, b]: b before a
    bad = ((cand < 0) | (cand == rows[:, None])
           | ((cand[:, :, None] == cand[:, None, :]) & earlier).any(axis=2))
    rank = np.cumsum(~bad, axis=1) - 1
    at, col = np.nonzero(~bad & (rank < k))
    out = np.full((m, k), -1, dtype=np.int64)
    out[at, rank[at, col]] = cand[at, col]
    return out


def discovery_dials(rtable: np.ndarray, connect_to: int,
                    bootstraps: np.ndarray, seed: int) -> np.ndarray:
    """dials[p]: connect_to distinct peers of p's ROUTING TABLE, uniformly
    (DHT-discovered peers, kad_utils.nim:8-11) instead of the flagship's
    global shuffle. The rule, which benchmark/reference/kad_plain.py states
    again: `default_rng(seed ^ 0x4E6).random((N, B*K))` gives every slot of
    every flat table a number; p dials the connect_to valid entries of its
    table with the smallest, in ascending order of them. A table with fewer
    entries dials them all, then the anchors, then ring neighbours (the
    reference's conns are likewise bootstrap-heavy early on)."""
    n = rtable.shape[0]
    rt = rtable.reshape(n, -1)
    u = np.random.default_rng(seed ^ 0x4E6).random(rt.shape)
    u[(rt < 0) | (rt == np.arange(n)[:, None])] = np.inf
    # the connect_to smallest a row, then those in order
    cols = np.argpartition(u, connect_to - 1, axis=1)[:, :connect_to]
    drawn = np.take_along_axis(u, cols, axis=1)
    order = np.argsort(drawn, axis=1)
    cols = np.take_along_axis(cols, order, axis=1)
    dials = np.take_along_axis(rt, cols, axis=1).astype(np.int64)
    dials[np.take_along_axis(drawn, order, axis=1) == np.inf] = -1
    short = np.nonzero((dials < 0).any(axis=1))[0]
    if len(short):
        dials[short] = _padded_dials(dials[short], short,
                                     np.asarray(bootstraps), n)
    return dials


def discovery_graph(
    kstate: kad.KadState, connect_to: int, bootstraps: np.ndarray,
    seed: int, max_degree: int | None = None,
) -> ConnGraph:
    """The connection graph of `discovery_dials` on the state's tables."""
    rt = np.asarray(kstate.rtable)
    return build_connection_graph(
        rt.shape[0], connect_to, seed=seed, max_degree=max_degree,
        dials=discovery_dials(rt, connect_to, bootstraps, seed))


class RegressionSimulator:
    """Discovery-then-dissemination composition: ops/kad forms the graph,
    the standard Simulator runs GossipSub over it, plus mesh ping probes."""

    def __init__(self, cfg: RegressionConfig):
        import jax.numpy as jnp

        cfg.validate()
        self.cfg = cfg
        n = cfg.network_size
        topo = cfg.topo or TopoParams(
            network_size=n, muxer=cfg.muxer, msg_size_bytes=cfg.msg_size,
            num_frags=cfg.fragments, messages=cfg.messages,
            delay_seconds=cfg.delay_seconds,
        )
        self.topo_params = topo
        with span("run/topology"):
            self.topology = Topology.build(topo)
        self._stage = jnp.asarray(self.topology.stage_of_peer)
        self._lat = jnp.asarray(self.topology.latency_ms)
        self.kstate = kad.init_kad_state(n, seed=cfg.seed)
        self.bootstraps = jnp.arange(cfg.n_bootstrap, dtype=jnp.int32)
        self.lines: list[str] = []
        self.pings: list[MeshPings] = []
        # `--stats-json` "kad": the lookups' counters, from the one
        # device->host read after the last wave (discover)
        self.kad_stats: dict = {}
        self.sim: Simulator | None = None

    def _log(self, line: str) -> None:
        self.lines.append(line)

    def experiment_config(self) -> ExperimentConfig:
        """What the regression node differs in from `run`'s node: its
        GossipSub degrees, the publisher (the first normal node), the
        warm-up (meshes stabilize post-dial) and the processing delay."""
        cfg = self.cfg
        return ExperimentConfig(
            topo=self.topo_params,
            connect_to=cfg.connect_to,
            gossipsub=regression_gossipsub_params(),
            publisher_id=cfg.n_bootstrap,
            warmup_s=cfg.start_sleep_s / 4.0,
            seed=cfg.seed,
            proc_delay_ms=REGRESSION_PROC_DELAY_MS,
        )

    # ---------------------------------------------------------------- phases

    def discover(self) -> None:
        """STARTSLEEP -> connectToBootstrap -> seedBootstraps (updatePeers +
        forceRefresh bootstrap round = one self-lookup wave) -> warmup
        randoms (main.nim:223-232)."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        n = cfg.network_size
        with span("run/discover"):
            self.kstate = kad.seed_bootstraps(self.kstate, self.bootstraps)
            self._log(f"kad-dht discovery active bootstraps={cfg.n_bootstrap}")
            origins = jnp.arange(cfg.n_bootstrap, n, dtype=jnp.int32)
            # the forceRefresh bootstrap round is FIND_NODE(self), the
            # warm-up waves look up random targets. A queried peer learns
            # EVERYONE who asked it (learn_cap None), as KadDHT adds every
            # requester. ops/kad's default of 8 a wave leaves, at 10,000
            # peers, 240 peers that anybody's table holds: the capacity
            # then turns 95 % of the dials away and 70 % of the network has
            # no connection
            self.kstate, _, waves = dispatch_waves(
                self.kstate, origins,
                (["bootstrap"] + ["random"] * cfg.discovery_rounds)[
                    :cfg.discovery_rounds],
                jax.random.PRNGKey(cfg.seed ^ 0x4E62), self._stage,
                self._lat, learn_cap=None, wave_span="discover/wave")
            # one device->host read for every counter of the discovery
            waves, census, tx, rx = jax.device_get(
                (wave_numbers(waves), kad.rtable_census(self.kstate),
                 self.kstate.queries_tx.sum(), self.kstate.queries_rx.sum()))
        self.kad_stats = _kad_stats(waves, float(census.mean()), int(tx),
                                    int(rx))

    def discovery_graph(self) -> ConnGraph:
        cfg = self.cfg
        with span("run/discovery_graph"):
            return discovery_graph(
                self.kstate, cfg.connect_to, np.arange(cfg.n_bootstrap),
                cfg.seed, max_degree=graph_capacity(self.experiment_config()))

    def build_sim(self) -> Simulator:
        graph = self.discovery_graph()
        with span("run/simulator_init"):
            self.sim = Simulator(self.experiment_config(),
                                 topology=self.topology, graph=graph)
        return self.sim

    def ping_round(self) -> None:
        """One pingMeshLoop pass: ping every mesh peer (ping_utils.nim:84-87).
        RTT = 2 x stage latency + dial/processing overhead."""
        assert self.sim is not None
        mesh = np.asarray(self.sim.state.mesh_mask)
        conns = self.sim.graph.conns
        stage = self.topology.stage_of_peer
        p_idx, s_idx = np.nonzero(mesh & (conns >= 0))
        targets = conns[p_idx, s_idx]
        rtt = (2.0 * self.topology.latency_ms[stage[p_idx], stage[targets]]
               + PING_PROC_MS)
        self.pings.append(MeshPings(p_idx, targets, rtt))
        # log a sample (the reference logs every ping; keep lines bounded)
        self.lines.extend(
            f"mesh ping peerId={q} pingMs={ms:.0f}"
            for q, ms in zip(targets[:PING_LINES_LOGGED].tolist(),
                             rtt[:PING_LINES_LOGGED].tolist()))

    def run(self) -> RegressionSummary:
        cfg = self.cfg
        self.discover()
        sim = self.build_sim()
        with span("run/simulate"):
            sim.warmup()
            mesh_deg = float(np.asarray(
                sim.state.mesh_mask.sum(axis=-1)).mean())
            self._log(f"Mesh details meshSize={mesh_deg:.1f}")
            for i in range(cfg.messages):
                if i > 0:
                    sim.advance(cfg.delay_seconds * 1000.0)
                sim.publish(cfg.n_bootstrap)
        with span("run/pings"):
            for _ in range(cfg.ping_rounds):
                self.ping_round()
                sim.advance(MESH_PING_INTERVAL_S * 1000.0)
        counters("kad/counters", **self.counters())
        return self.summary()

    # --------------------------------------------------------------- outputs

    def counters(self) -> dict:
        """What the `kad/counters` annotation carries, one an experiment:
        the lookups' (discover's one read), and from the graph build how
        many edges the capacity turned away and how many pings went."""
        assert self.sim is not None
        k = self.kad_stats
        return {
            "lookups": k["lookups"], "hops_mean": k["hops_mean"],
            "queries_per_lookup": k["queries_per_lookup"],
            "rtable_census_mean": k["rtable_census_mean"],
            "packed_share": k["packed_share"],
            "cap_filtered_edges": self.sim.graph.build["cap_filtered_edges"],
            "mesh_pings": sum(len(p.ping_ms) for p in self.pings),
        }

    def ping_stats(self) -> dict:
        """`--stats-json` "pings"."""
        ping_ms = (np.concatenate([p.ping_ms for p in self.pings])
                   if self.pings else np.zeros(0))
        measured = ping_ms if len(ping_ms) else np.zeros(1)
        return {
            "count": int(len(ping_ms)), "rounds": len(self.pings),
            "p50_ms": float(np.percentile(measured, 50)),
            "p99_ms": float(np.percentile(measured, 99)),
            "timeouts": int((ping_ms > MESH_PING_TIMEOUT_MS).sum()),
        }

    def summary(self) -> RegressionSummary:
        assert self.sim is not None
        deg = np.asarray(self.sim.state.mesh_mask.sum(axis=-1))
        recs = self.sim.records
        n = self.cfg.network_size
        cov = (np.mean([r.received.sum() / n for r in recs])
               if recs else 0.0)
        pings = self.ping_stats()
        return RegressionSummary(
            census_mean=self.kad_stats.get("rtable_census_mean", 0.0),
            mesh_degree_mean=float(deg.mean()),
            coverage=float(cov),
            ping_count=pings["count"],
            ping_ms_p50=pings["p50_ms"],
            ping_ms_p99=pings["p99_ms"],
            ping_timeouts=pings["timeouts"],
        )

    def records(self) -> list[MessageRecord]:
        return self.sim.records if self.sim else []


def _kad_stats(waves: list, census_mean: float, tx: int, rx: int) -> dict:
    """`--stats-json` "kad" from the read of `wave_numbers`."""
    hops = np.concatenate([w[0] for w in waves])
    queries = np.concatenate([w[1] for w in waves])
    return {
        "waves": len(waves),
        "lookups": int(len(hops)),
        "hops_mean": float(hops.mean()),
        "queries_per_lookup": float(queries.mean()),
        "rtable_census_mean": census_mean,
        "packed_share": float(np.mean([w[4] for w in waves])),
        "queries_tx": tx,
        "queries_rx": rx,
        "lookup_latency_ms": [latency_percentiles(w[2]) for w in waves],
    }


def config_from_env() -> RegressionConfig:
    """STARTSLEEP/FRAGMENTS/MUXER/NODE_ROLE surface (regression/env.nim)."""
    return RegressionConfig(
        network_size=env_int("PEERS", 100),
        n_bootstrap=env_int("REGRESSION_BOOTSTRAPS", 1),
        connect_to=env_int("CONNECTTO", 10),
        start_sleep_s=float(env_int("STARTSLEEP", 180)),
        muxer=env_str("MUXER", "yamux"),
        fragments=env_int("FRAGMENTS", 1),
        seed=env_int("SEED", 0),
    )
