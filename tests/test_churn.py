"""Churn as the benchmark's runsh-100k-churn runs it (ISSUE 36): the peers an
injector publishes through are spared by the draw and nobody else's liveness
moves with that; the alive share follows the Markov transient; a churned
publish counts who could send and who sat under D_low; a publish through a
dead peer raises; `run ... --churn` end to end; and the churn-free programs
are the ones they were, but for scope names."""

import contextlib
import hashlib
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_configs
import pull_route
from dst_libp2p_test_node_tpu import cli
from dst_libp2p_test_node_tpu.config.topology import TopoParams
from dst_libp2p_test_node_tpu.ops.disseminate import disseminate
from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph
from dst_libp2p_test_node_tpu.ops.heartbeat import (
    _run_heartbeats, run_heartbeats)
from dst_libp2p_test_node_tpu.ops.state import (
    SimParams, graph_arrays, init_state)
from dst_libp2p_test_node_tpu.runtime.simulator import (
    ExperimentConfig, PublisherDownError, Simulator, scheduled_publishers)

HERE = os.path.dirname(os.path.abspath(__file__))


def _network(n=300, connect_to=10, seed=3, **over):
    g = build_connection_graph(n, connect_to, seed=seed)
    params = SimParams(n=n, capacity=g.capacity, **over)
    return params, init_state(params, seed=seed), graph_arrays(g)


def _walk(params, state, a, steps, chunks, spared=None):
    """`chunks` scans of `steps` heartbeats; liveness after each."""
    seen = []
    for _ in range(chunks):
        state = run_heartbeats(state, a["conns"], a["rev"], a["out_mask"],
                               params, steps, spared=spared)
        seen.append(np.asarray(state.alive))
    return np.stack(seen)


# ------------------------------------------------------- the spared publisher


@pytest.mark.parametrize("rotation", [False, True])
def test_scheduled_publishers_are_the_peers_run_publishes_through(rotation):
    topo = TopoParams(network_size=50, messages=4, delay_seconds=1.0)
    cfg = ExperimentConfig(topo=topo, publisher_id=48,
                           publisher_rotation=rotation, warmup_s=2.0,
                           churn_down_per_hb=0.01, churn_up_per_hb=0.005)
    want = [48, 49, 0, 1] if rotation else [48]
    assert scheduled_publishers(cfg) == want
    sim = Simulator(cfg)
    assert sim.spared_peers == want
    assert [r.publisher for r in sim.run()] == (
        want if rotation else [48] * 4)
    # churn off: nothing spared, and no argument more for any program
    quiet = Simulator(ExperimentConfig(topo=topo, warmup_s=2.0))
    assert quiet.spared_peers == [] and quiet._spared is None


@pytest.mark.parametrize("rotation", [False, True])
def test_spared_peers_never_die_and_nobody_else_moves(rotation):
    """--churn 0.01 over 300 steps (30 scans of 10): the unspared run kills
    the peers; spared they live through every scan, and every other peer's
    liveness is the unspared run's for the same key, scan for scan."""
    params, state, a = _network(churn_down_per_hb=0.01, churn_up_per_hb=0.005)
    plain = _walk(params, state, a, 10, 30)
    # the peers of a publisher with rotation on and off, taken among those
    # the plain draw kills, so that sparing them is seen to matter
    died = np.nonzero(~plain.all(axis=0))[0]
    assert len(died) > 100
    peers = died[:3] if rotation else died[:1]
    mask = np.zeros(params.n, bool)
    mask[peers] = True
    spared = _walk(params, state, a, 10, 30, spared=jnp.asarray(mask))
    assert spared[:, peers].all()
    assert not plain[:, peers].all(axis=0).any()
    np.testing.assert_array_equal(spared[:, ~mask], plain[:, ~mask])


def test_a_simulator_under_churn_keeps_its_publisher_alive():
    topo = TopoParams(network_size=200, messages=3, delay_seconds=100.0)
    cfg = ExperimentConfig(topo=topo, publisher_id=7, warmup_s=300.0, seed=11,
                           churn_down_per_hb=0.01, churn_up_per_hb=0.005)
    sim = Simulator(cfg)
    sim.warmup()
    warmed = np.asarray(sim.state.alive)
    # the same key without the mask kills peer 7 within the 300 heartbeats
    # and gives every other peer the liveness the simulator has
    key_twin = _walk(sim.params, init_state(sim.params, seed=11), sim.arrays,
                     10, 30)
    assert warmed[7] and not key_twin[:, 7].all()
    others = np.arange(200) != 7
    np.testing.assert_array_equal(warmed[others], key_twin[-1][others])
    for rec in sim.run():
        assert rec.received[7] and rec.delays_ms[7] == 0.0
        # no dead peer logs, and everyone who logs could send
        assert rec.received.sum() <= rec.alive < 200
    assert bool(np.asarray(sim.state.alive)[7])


@pytest.mark.parametrize("down,n", [(1e-3, 2000), (1e-2, 1000)])
def test_alive_share_follows_the_markov_transient(down, n):
    params, state, a = _network(n=n, seed=5, churn_down_per_hb=down,
                                churn_up_per_hb=down / 2)
    alive = _walk(params, state, a, 500, 1)[0]
    want = bench_configs.expected_alive_fraction(down, down / 2, 500)
    sd = math.sqrt(want * (1 - want) / n)
    assert abs(alive.mean() - want) < 6 * sd, (alive.mean(), want, sd)


# ----------------------------------------------- the publish under churn


def test_a_publish_through_a_dead_peer_raises():
    topo = TopoParams(network_size=120, messages=1)
    cfg = ExperimentConfig(topo=topo, warmup_s=200.0, seed=2,
                           churn_down_per_hb=0.01, churn_up_per_hb=0.005)
    sim = Simulator(cfg)
    sim.warmup()
    alive = np.asarray(sim.state.alive)
    dead = int(np.nonzero(~alive)[0][0])
    before = sim.state
    with pytest.raises(PublisherDownError, match=f"peer {dead} is dead"):
        sim.publish(dead)
    # nothing was recorded and the state did not move
    assert sim.records == [] and sim.state is before
    rec = sim.publish(int(np.nonzero(alive)[0][0]))
    assert rec.received.sum() > 1


@pytest.mark.parametrize("fragments", [1, 4])
def test_the_packed_counters_count_alive_and_under_dlow(fragments):
    params, state, a = _network(n=200, churn_down_per_hb=0.02,
                                churn_up_per_hb=0.01)
    state = run_heartbeats(state, a["conns"], a["rev"], a["out_mask"],
                           params, 30)
    alive = np.asarray(state.alive)
    pub = int(np.nonzero(alive)[0][0])
    stage = jnp.zeros((params.n,), jnp.int32)
    res, _ = disseminate(
        state, a["conns"], a["rev"], stage, jnp.full((1, 1), 50.0),
        jnp.full((1,), 100.0), publisher=pub, t0_ms=float(state.t_ms),
        params=params, payload_bytes=15000, fragments=fragments,
        with_gossip=True)
    assert res.counters.shape == (13,)
    assert int(res.alive) == alive.sum() < params.n
    conns = np.asarray(a["conns"])
    valid = ((conns >= 0) & alive[:, None] & alive[np.clip(conns, 0, None)])
    deg = (np.asarray(state.mesh_mask) & valid).sum(axis=-1)
    assert int(res.under_dlow) == (alive & (deg < params.d_low)).sum()
    # no dead peer receives
    assert not (np.asarray(res.received) & ~alive).any()
    # churn off: the eleven counters of every publish
    quiet, qstate, _ = _network(n=200)
    res, _ = disseminate(
        qstate, a["conns"], a["rev"], stage, jnp.full((1, 1), 50.0),
        jnp.full((1,), 100.0), publisher=pub, t0_ms=0.0, params=quiet,
        payload_bytes=15000, fragments=fragments, with_gossip=True)
    assert res.counters.shape == (11,)
    assert res.alive is None and res.under_dlow is None


def test_run_with_churn_end_to_end(tmp_path, capsys):
    """`run ... --churn 0.0001 --stats-json` at 1,000 peers: rc 0, one line
    of latencies1 per receipt, the "churn" block, same seed same bytes."""
    argv = ["run", "1", "1000", "15000", "4", "3", "50", "150", "40", "130",
            "5", "0.0", "4", "0", "4000", "--churn", "0.0001", "--seed", "9",
            "--stats-json"]
    files = []
    for name in ("a", "b"):
        out = tmp_path / name
        out.mkdir()
        assert cli.main(argv + ["--out-prefix", str(out) + os.sep]) == 0
        files.append((out / "latencies1").read_bytes())
        with open(out / "stats1.json") as f:
            stats = json.load(f)
    capsys.readouterr()
    assert files[0] == files[1]
    churn = stats["churn"]
    assert churn["down_per_hb"] == 1e-4 and churn["up_per_hb"] == 5e-5
    assert churn["spared_peers"] == [4]
    assert len(churn["alive"]) == len(churn["under_dlow"]) == 3
    want = bench_configs.expected_alive_fraction(1e-4, 5e-5, 504)
    sd = math.sqrt(want * (1 - want) / 1000)
    for alive in churn["alive"]:
        assert abs(alive / 1000 - want) < 6 * sd
    lines = files[0].count(b"\n")
    assert lines == round(stats["coverage"] * 3) <= sum(churn["alive"])
    assert lines > 0.9 * 3000
    # the publisher's own 0 in every message, and it is peer 4's
    zero = re.findall(rb"peer(\d+)/main\.1000\.stdout:\d+:\d+ milliseconds: 0\n",
                      files[0])
    assert zero == [b"4"] * 3
    assert stats["spans"]["publish/valid_edge"]["count"] == 3


@pytest.mark.parametrize("text,want", [
    ("0.0001", (1e-4, 5e-5)), ("0.0001:0.00005", (1e-4, 5e-5)),
    ("0.01:0.1", (0.01, 0.1)), ("1e-3:0", (1e-3, 0.0)), ("0", (0.0, 0.0))])
def test_churn_takes_down_or_down_and_up(text, want):
    """`--churn DOWN[:UP]`: UP is DOWN / 2 where it is left out, to the
    bit, so that the two forms of one rate are one experiment."""
    assert cli._churn_rates(text) == want


@pytest.mark.parametrize("text", ["", "a", "0.1:", ":0.1", "0.1:0.2:0.3",
                                  "1.5", "-0.1", "0.1:2", "nan"])
def test_churn_refuses_what_is_no_rate(text, capsys):
    """argparse's exit 2 before anything runs: what a program that knows
    only `--churn DOWN` (the parent of ISSUE 36) answers to DOWN:UP."""
    with pytest.raises(SystemExit) as e:
        cli.main(["run", "1", "100", "15000", "1", "2", "50", "150", "40",
                  "130", "5", "0.0", "4", "0", "1000", "--churn", text])
    assert e.value.code == 2
    assert "--churn" in capsys.readouterr().err


def test_both_forms_of_one_rate_write_the_same_bytes(tmp_path, capsys):
    files = []
    for name, churn in (("a", "0.001"), ("b", "0.001:0.0005")):
        out = tmp_path / name
        out.mkdir()
        assert cli.main(["run", "1", "300", "15000", "4", "2", "50", "150",
                         "40", "130", "5", "0.0", "4", "0", "4000", "--churn",
                         churn, "--seed", "11", "--stats-json", "--out-prefix",
                         str(out) + os.sep]) == 0
        with open(out / "stats1.json") as f:
            churned = json.load(f)["churn"]
        files.append(((out / "latencies1").read_bytes(), churned))
    capsys.readouterr()
    assert files[0] == files[1]
    assert files[0][1]["up_per_hb"] == 5e-4
    assert min(files[0][1]["alive"]) < 300      # the network did churn


def test_run_without_churn_has_no_churn_block(tmp_path, capsys):
    out = str(tmp_path) + os.sep
    assert cli.main(["run", "1", "100", "15000", "1", "2", "50", "150", "40",
                     "130", "5", "0.0", "4", "0", "1000", "--stats-json",
                     "--warmup-s", "5", "--out-prefix", out]) == 0
    capsys.readouterr()
    with open(out + "stats1.json") as f:
        stats = json.load(f)
    assert "churn" not in stats
    assert "publish/valid_edge" not in stats["spans"]


# ------------------------------------------- the programs, scopes and text


def _lowered(churn, debug_info=False):
    params, state, a = _network(n=300, seed=1, churn_down_per_hb=churn,
                                churn_up_per_hb=churn / 2)
    scan = _run_heartbeats.lower(
        state, a["conns"], a["rev"], a["out_mask"], params, 7)
    stage = jnp.zeros((params.n,), jnp.int32)
    pubs = {
        f: disseminate.lower(
            state, a["conns"], a["rev"], stage, jnp.full((5, 5), 50.0),
            jnp.full((5,), 100.0), publisher=4, t0_ms=1000.0, params=params,
            payload_bytes=15000, fragments=f, with_gossip=True,
            return_plan=True)
        for f in (1, 4)}
    texts = {"_run_heartbeats": scan, "disseminate.f1": pubs[1],
             "disseminate.f4": pubs[4]}
    return {k: v.as_text(debug_info=debug_info) for k, v in texts.items()}


@pytest.mark.parametrize("program", ["_run_heartbeats", "disseminate.f1",
                                     "disseminate.f4"])
def test_churn_free_programs_are_the_parents_but_for_scope_names(program):
    """The StableHLO text without debug info (where the scope names live)
    of the churn-free scan and publish, against what was pinned at the same
    shapes (tests/fixtures/lowered_churn_free.json, with the jax named
    there): the one-fragment publish as PR 36's parent lowered it, the scan
    as PR 37 left it (its delivery counters ride in the carry; at this shape
    the step keeps the dense pull): PR 41's lanes in the gathered row leave
    both texts as they were. The four-fragment publish is PR 41's, whose
    lanes share their gathers. Both publishes carry PR 51's tenth counter
    (nine lines: a zero at this shape, its max over the lanes, the wider
    concatenate) and PR 53's eleventh (another nine); their loops are the
    pinned parents'."""
    with open(os.path.join(HERE, "fixtures", "lowered_churn_free.json")) as f:
        pinned = json.load(f)
    if pinned["jax"] != jax.__version__:
        pytest.skip(f"pinned on jax {pinned['jax']}, this is {jax.__version__}")
    text = _lowered(0.0)[program]
    assert hashlib.sha256(text.encode()).hexdigest() == pinned[program]


@pytest.mark.parametrize("sparse", [False, True])
@pytest.mark.parametrize("churn", [0.0, 0.01])
def test_lowered_scan_carries_the_scopes(churn, sparse):
    # sparse: the route a 100,000-peer scan takes, at this test's 300 peers
    with pull_route.forced(0) if sparse else contextlib.nullcontext():
        text = _lowered(churn, debug_info=True)["_run_heartbeats"]
    # the scan's own ops carry their names from `jit(_run_heartbeats)` on;
    # the step is a jit of its own inside the body, and its ops' names
    # start at its stages (the profile of a chip run joins the two)
    names = re.findall(r'loc\("([^"]*)"', text)
    scan = [n.split("/", 1)[1] for n in names
            if n.startswith("jit(_run_heartbeats)/")]
    stages = {"validity", "graft", "prune", "decay", "fanout", "state"}
    if churn:
        stages.add("churn")
    step = [n for n in names if n.split("/")[0] in stages]
    assert {n.split("/")[0] for n in step} == stages
    assert {"graft/cond", "prune/cond", "fanout/cond"} <= {
        "/".join(n.split("/")[:2]) for n in step}
    # what the scan does around the steps: the deferred decay, and the one
    # dense neighbour pull in front (without churn with the whole validity
    # conjunction; under churn the seed of the carried view, which a scan
    # too small for the sparse route never reads: dead code, not lowered);
    # the rest is the loop itself
    around = {n.split("/")[0] for n in scan}
    assert around == {"decay", "scan", "while"} | (
        {"validity"} if sparse or not churn else set())
    assert {n for n in scan if n.startswith("while/")} <= {
        "while/body/add", "while/cond/lt", "while/body/closed_call",
        "while/body/decay/mul"}
    assert any(n.startswith("validity/dense/gather") for n in scan) == (
        sparse or not churn)
    # under churn the draws and the validity conjunction run every step
    assert any(n.startswith("validity/and") for n in step) == bool(churn)
    assert any(n.startswith("validity/and") for n in scan) != bool(churn)
    # which delivery ran is a sub-scope of the stage: the dense pull alone
    # at a small shape; past the static bound a switch over none / sparse /
    # dense in graft and prune, and under churn a cond in validity
    def ways(stage):
        return {part for n in step if n.startswith(stage + "/")
                for part in n.split("/")[1:] if part in ("sparse", "dense")}
    both = {"sparse", "dense"} if sparse else {"dense"}
    assert ways("graft") == both and ways("prune") == both
    assert ways("validity") == (both if churn else set())
    assert any("sparse/scatter" in n for n in step) == sparse
    # how the selection ranked is a sub-scope too (`rank`, inside the
    # stage's cond): past the static bound a switch over none / few / all
    # of the rows that select; at a small shape `all` alone and no switch,
    # the parent's program
    for stage in ("graft", "prune"):
        rank = [n.split("/rank/", 1)[1] for n in step
                if n.startswith(stage + "/cond/") and "/rank/" in n]
        assert {r.split("/")[0] for r in rank if "/" in r} == (
            {"cond"} if sparse else {"all"})
        assert {r.split("/")[2] for r in rank if r.startswith("cond/")} == (
            {"none", "few", "all"} if sparse else set())
        assert any(r.startswith("cond/branch_1_fun/few/scatter")
                   for r in rank) == sparse
        # every sort is `all`'s: `few` counts its ranks
        assert all("/all/" in "/" + r for r in rank if "argsort" in r)
        assert any("argsort" in r for r in rank)


@pytest.mark.parametrize("sparse", [False, True])
@pytest.mark.parametrize("churn", [0.0, 0.01])
def test_scan_holds_the_parents_sorts(churn, sparse):
    """`_ranks`' two argsorts lower to one function each (float32 priorities,
    int32 orders), called eight times a step, before PR 46 and since: the
    selection by rows ranks its few rows by counting, so the routed scan
    gives XLA:TPU, which is slow to compile a sort, no sort it had not."""
    with pull_route.forced(0) if sparse else contextlib.nullcontext():
        text = _lowered(churn)["_run_heartbeats"]
    assert text.count("stablehlo.sort") == 2
    assert len(re.findall(r"call @argsort", text)) == 8
