"""Regression-node tests (reference behavior: nim-test-node/regression —
GossipSub mesh formed via kad-dht discovery, mesh-peer ping probes).

One shared simulation run (module fixture) keeps the jit compile chain to a
single network size; the assertions slice it from different angles."""

import json
import os

import jax
import numpy as np
import pytest

from dst_libp2p_test_node_tpu.config.topology import TopoParams
from dst_libp2p_test_node_tpu.ops import kad
from dst_libp2p_test_node_tpu.ops.disseminate import (
    answer_tables, edge_tables, valid_edge_of)
from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph
from dst_libp2p_test_node_tpu.runtime.regression_runtime import (
    MESH_PING_TIMEOUT_MS,
    REGRESSION_PROC_DELAY_MS,
    RegressionConfig,
    RegressionSimulator,
    config_from_env,
    discovery_dials,
    discovery_graph,
    regression_gossipsub_params,
)
from dst_libp2p_test_node_tpu.runtime.simulator import (
    MUXER_PROC_MS, ExperimentConfig, Simulator)

N = 48


@pytest.fixture(scope="module")
def run():
    cfg = RegressionConfig(network_size=N, n_bootstrap=1, connect_to=6,
                           messages=2, msg_size=500, ping_rounds=1,
                           discovery_rounds=2, seed=0)
    sim = RegressionSimulator(cfg)
    summary = sim.run()
    return sim, summary


def test_regression_gossipsub_params():
    """The regression node pins dScore=6, dOut=3 (main.nim:141-152), unlike
    the flagship's env-tunable dScore=4."""
    g = regression_gossipsub_params()
    assert (g.d, g.d_low, g.d_high) == (6, 4, 8)
    assert g.d_score == 6 and g.d_out == 3


def test_discovery_graph_uses_routing_tables(run):
    sim, _ = run
    graph = discovery_graph(sim.kstate, 6, np.array([0]), seed=0)
    graph.validate()
    conns = graph.conns
    for p in range(N):
        nbrs = conns[p][conns[p] >= 0]
        assert p not in nbrs
        assert len(set(nbrs.tolist())) == len(nbrs)
    # the anchor is massively popular (everyone learns it at seeding)
    assert (conns == 0).sum() >= 6


def test_regression_end_to_end(run):
    sim, s = run
    assert s.coverage > 0.95            # DHT-discovered mesh disseminates
    assert s.census_mean > 5.0
    assert 3.0 <= s.mesh_degree_mean <= 8.5   # D bounds (dLow..dHigh)
    assert s.ping_count > 0
    assert s.ping_ms_p50 > 0
    assert s.ping_timeouts == 0
    text = "\n".join(sim.lines)
    assert "kad-dht discovery active" in text
    assert "Mesh details" in text
    assert "mesh ping peerId=" in text
    # latency lines flow through the standard record path
    recs = sim.records()
    assert len(recs) == 2
    assert all(r.delays_ms_int.size > 0 for r in recs)
    assert "Regression summary" in s.report()


def test_ping_rtt_matches_topology(run):
    """A round's pings are arrays, one entry a mesh edge."""
    sim, s = run
    lat = sim.topology.latency_ms
    stage = sim.topology.stage_of_peer
    assert len(sim.pings) == 1
    pings = sim.pings[0]
    mesh = np.asarray(sim.sim.state.mesh_mask) & (sim.sim.graph.conns >= 0)
    assert len(pings.ping_ms) == s.ping_count > 0
    assert len(pings.ping_ms) == len(pings.peer) == len(pings.target)
    want = 2.0 * lat[stage[pings.peer], stage[pings.target]] + 2.0
    np.testing.assert_allclose(pings.ping_ms, want)
    assert (pings.ping_ms < MESH_PING_TIMEOUT_MS).all()
    assert (pings.peer != pings.target).all()
    # every ping goes over a connection
    conns = sim.sim.graph.conns
    assert all(t in conns[p] for p, t in
               zip(pings.peer[:50].tolist(), pings.target[:50].tolist()))
    assert mesh.shape == conns.shape
    # the log keeps the first 20 lines of a round
    assert sum("mesh ping peerId=" in line for line in sim.lines) == 20
    assert sim.ping_stats()["rounds"] == 1


@pytest.mark.parametrize("seed", [0, 7, 2147483999])
def test_discovery_dials_keep_the_loops_properties(run, seed):
    """connect_to distinct peers of the routing table, never the peer
    itself, drawn uniformly: what the Python loop gave, as array
    operations."""
    sim, _ = run
    rt = np.asarray(sim.kstate.rtable)
    dials = discovery_dials(rt, 6, np.array([0]), seed)
    assert dials.shape == (N, 6) and (dials >= 0).all()
    for p in range(N):
        known = set(rt[p][rt[p] >= 0].tolist())
        assert p not in dials[p]
        assert len(set(dials[p].tolist())) == 6
        assert len(known) < 6 or set(dials[p].tolist()) <= known
    # another seed, other draws; the same seed, the same
    assert (dials == discovery_dials(rt, 6, np.array([0]), seed)).all()
    assert (dials != discovery_dials(rt, 6, np.array([0]), seed + 1)).any()


def test_discovery_dials_pad_short_tables():
    """A table with fewer than connect_to entries dials them all, then the
    anchors, then ring neighbours: distinct, none the peer itself."""
    n, k = 12, 5
    state = kad.seed_bootstraps(kad.init_kad_state(n, seed=1),
                                np.array([0, 1], np.int32))
    rt = np.asarray(state.rtable)      # a normal peer knows the two anchors
    dials = discovery_dials(rt, k, np.array([0, 1]), seed=4)
    for p in range(2, n):
        assert set(dials[p, :2].tolist()) == {0, 1}
        ring = [(p + 1 + i) % n for i in range(k)]
        assert dials[p, 2:].tolist() == [x for x in ring
                                         if x not in (0, 1)][:k - 2]
    for p in range(n):
        assert p not in dials[p] and len(set(dials[p].tolist())) == k
    # an empty table: anchors, then the ring
    empty = np.full_like(rt, -1)
    assert discovery_dials(empty, k, np.array([0]), 0)[n - 1].tolist() == [
        0, 1, 2, 3, 4]


def test_simulator_on_a_callers_graph_hoists_that_graphs_tables(run):
    """`Simulator(cfg, graph=...)`: params, device arrays and every hoisted
    per-edge table come from the caller's graph; nothing is the shuffle
    graph's. (The regression path used to overwrite graph, params, state and
    arrays and leave the four hoisted tables stale: the publish then pulled
    through another graph's neighbour order.)"""
    reg, _ = run
    sim = reg.sim
    graph = sim.graph
    assert graph.build["dedupe"] == "unique"      # a caller's dials
    shuffle = build_connection_graph(N, 6, seed=0, max_degree=graph.capacity)
    assert (graph.conns != shuffle.conns).any()
    conns, rev = sim.arrays["conns"], sim.arrays["rev"]
    assert (np.asarray(conns) == graph.conns).all()
    assert (np.asarray(rev) == graph.rev).all()
    assert sim.params.capacity == graph.capacity
    lat_edge, loss_edge = edge_tables(sim._stage, sim._lat, conns, rev, None)
    assert loss_edge is None and sim._loss_edge is None
    np.testing.assert_array_equal(sim._lat_edge, lat_edge)
    want = answer_tables(lat_edge, conns, rev)
    got = sim._ans_tables
    assert type(got) is type(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(
        sim._valid_edge,
        valid_edge_of(sim.state.alive, sim.state.subscribed, conns, rev))
    # what the regression node differs in is in its ExperimentConfig
    assert sim.cfg.proc_delay_ms == sim.params.proc_delay_ms == \
        REGRESSION_PROC_DELAY_MS
    assert sim.cfg.publisher_id == 1 and sim.cfg.warmup_s == 45.0
    assert sim.cfg.gossipsub == regression_gossipsub_params()


def test_simulator_graph_argument_and_proc_delay_default():
    """Without `graph` the Simulator dials as before and takes the muxer's
    processing delay; a graph of another size is refused."""
    cfg = ExperimentConfig(topo=TopoParams(network_size=32), connect_to=4,
                           seed=2)
    sim = Simulator(cfg)
    assert sim.graph.build["dedupe"] == "mutual"
    assert sim.params.proc_delay_ms == MUXER_PROC_MS["yamux"]
    with pytest.raises(ValueError, match="graph of 16 peers"):
        Simulator(cfg, graph=build_connection_graph(16, 4, seed=2))


def test_regression_cli_stats_json(tmp_path, monkeypatch, capsys):
    """`regression --stats-json PATH`: what `run`'s stats file has that
    applies, "kad" and "pings"; the summary's stdout lines stay; the
    `kad/counters` annotation, one an experiment, is "kad"'s own numbers."""
    from dst_libp2p_test_node_tpu import cli
    from dst_libp2p_test_node_tpu.runtime import regression_runtime

    noted = []
    monkeypatch.setattr(regression_runtime, "counters",
                        lambda name, **values: noted.append((name, values)))
    for name, value in (("PEERS", N), ("CONNECTTO", 6), ("SEED", 5),
                        ("STARTSLEEP", 180)):
        monkeypatch.setenv(name, str(value))
    stats_path, lat_path = tmp_path / "stats.json", tmp_path / "latencies"
    assert cli.main(["regression", "--messages", "2", "--msg-size", "500",
                     "--latencies", str(lat_path),
                     "--stats-json", str(stats_path)]) == 0
    said = capsys.readouterr().out
    for line in ("Regression summary", "Routing table census: mean ",
                 "Mesh degree: mean ", "Coverage: 100.0%", "Mesh pings: ",
                 "Ping RTT ms: p50 202 p99 202", "[tpu backend] wall="):
        assert line in said, line
    stats = json.loads(stats_path.read_text())
    assert {"network_size", "coverage", "coverage_by_message", "spans",
            "publishes", "heartbeat", "build", "emit", "compile", "kad",
            "pings", "wall_s", "mesh_degree_mean"} <= set(stats)
    assert stats["coverage_by_message"] == [N, N] and stats["coverage"] == N
    assert lat_path.read_text().count("\n") == 2 * N
    assert stats["emit"]["latencies_lines"] == 2 * N
    for name in ("run", "run/topology", "run/discover", "discover/wave",
                 "run/discovery_graph", "run/simulator_init", "build/graph",
                 "build/tables", "run/simulate", "publish", "run/pings",
                 "run/write_latencies", "run/summary", "run/report",
                 "run/stats_json"):
        assert name in stats["spans"], name
    assert stats["spans"]["discover/wave"]["count"] == 3
    assert stats["spans"]["publish"]["count"] == len(stats["publishes"]) == 2
    kad_stats = stats["kad"]
    assert {"waves", "lookups", "hops_mean", "queries_per_lookup",
            "rtable_census_mean", "queries_tx", "queries_rx",
            "lookup_latency_ms"} <= set(kad_stats)
    assert kad_stats["waves"] == 3 and kad_stats["lookups"] == 3 * (N - 1)
    # every table fitted the columns a response sorts, in all three waves
    assert kad_stats["packed_share"] == 1.0
    assert [name for name, _ in noted] == ["kad/counters"]
    assert set(noted[0][1]) == {
        "lookups", "hops_mean", "queries_per_lookup", "rtable_census_mean",
        "packed_share", "cap_filtered_edges", "mesh_pings"}
    assert all(kad_stats[k] == v for k, v in noted[0][1].items()
               if k in kad_stats)
    with open(os.path.join(os.path.dirname(os.path.dirname(__file__)),
                           "benchmark", "layer_metrics",
                           "kad.packed_share.json")) as f:
        assert json.load(f)["params"] == {
            "annotation": "kad/counters", "counter": "packed_share"}
    assert kad_stats["queries_tx"] == kad_stats["queries_rx"] > 0
    assert [set(w) for w in kad_stats["lookup_latency_ms"]] == [
        {"p50", "p99"}] * 3
    assert {"count", "rounds", "p50_ms", "p99_ms", "timeouts"} <= set(
        stats["pings"])
    assert stats["pings"]["rounds"] == 2 and stats["pings"]["timeouts"] == 0
    assert stats["build"]["dedupe"] == "unique"


def test_config_from_env(monkeypatch):
    monkeypatch.setenv("PEERS", "80")
    monkeypatch.setenv("STARTSLEEP", "60")
    monkeypatch.setenv("FRAGMENTS", "2")
    monkeypatch.setenv("CONNECTTO", "7")
    cfg = config_from_env()
    assert cfg.network_size == 80
    assert cfg.start_sleep_s == 60.0
    assert cfg.fragments == 2
    assert cfg.connect_to == 7
    with pytest.raises(ValueError):
        RegressionConfig(network_size=10, connect_to=10).validate()
