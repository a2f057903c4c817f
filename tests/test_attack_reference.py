"""The attack campaign against its plain reference (XLA:CPU, small):

  the whole path, `cli.main(["attack", ..., "--json", ..., "--stats-json",
  ...])` at 64 and 128 peers, fractions 0 / 0.2, two trial seeds, on three
  campaign seeds: every attacked trial's window heartbeat by heartbeat, every
  publish's delivery mask and delays, and every row of `"attack"` against
  benchmark/reference/attack_plain.py (numpy, nothing of the program) and
  benchmark/reference/des.py;

  the contracts of the campaign: a fraction-0 trial is the benign
  `Simulator`'s bytes, `--no-vmap` gives the vmapped campaign's JSON less its
  clock fields, the same seed writes the same bytes;

  what the turn leaves: the spans, the two device scopes of the attacked
  scan's step, the `attack/counters` annotation, the `--stats-json` keys,
  and `device_reads` equal to the `jax.device_get` calls and the
  `np.asarray`s of a device array that a patch counts.

Float leaves of a window compare within rtol 1e-5 and atol 1e-4, as
analysis/conformance.py compares them and for its reason: the reference
performs the engine's float32 operations in the engine's order (the deltas
read 0 on XLA:CPU); the tolerance is room for a fused multiply-add on
another backend, not a semantic allowance.
"""

import copy
import dataclasses
import json
import os
import re
import sys

import numpy as np
import pytest

from dst_libp2p_test_node_tpu import cli
from dst_libp2p_test_node_tpu.config.topology import TopoParams
from dst_libp2p_test_node_tpu.ops import adversary
from dst_libp2p_test_node_tpu.ops.state import SimParams
from dst_libp2p_test_node_tpu.runtime import campaign
from dst_libp2p_test_node_tpu.runtime.simulator import (
    ExperimentConfig, Simulator, graph_capacity)

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

from benchmark.entries import attack as entry  # noqa: E402
from benchmark.harness import manifest  # noqa: E402
from benchmark.harness.experiment import call_cli  # noqa: E402
from benchmark.reference import attack_plain  # noqa: E402

TEST_MANIFEST = os.path.join(CHECKOUT, "benchmark", "tests",
                             "BENCHMARK.attack.test.json")
CASES = [(64, 3), (64, 2147483651), (64, 4294967299),
         (128, 5), (128, 2147483999), (128, 11)]


def _cell(peers: int):
    """benchmark/tests' tiny-attack (fractions 0 / 0.2, two trial seeds) at
    `peers`."""
    cell = manifest.load_cell("tiny-attack.sybil-tiny", TEST_MANIFEST)
    cell = copy.copy(cell)
    cell.config = copy.deepcopy(cell.config)
    cell.config["attack"]["peers"] = peers
    return cell


def _run_cli(cell, seed: int, out_dir: str, *more: str):
    """One campaign as the benchmark runs it; (stats1.json, campaign1.json,
    the entry's invariants of both)."""
    argv, env = entry.invocation(cell, seed, out_dir)
    rc, _ = call_cli(argv + list(more), env, out_dir)
    assert rc == 0
    with open(os.path.join(out_dir, "stats1.json")) as f:
        stats = json.load(f)
    with open(os.path.join(out_dir, "campaign1.json")) as f:
        ran = json.load(f)
    return stats, ran, entry.invariants(cell, out_dir)


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """One captured campaign a case, every attacked trial replayed; a size
    compiles its programs once a module."""
    cache = {}

    def get(peers: int, seed: int):
        if (peers, seed) not in cache:
            cell = _cell(peers)
            outcome, items = entry.captured(
                cell, seed, str(tmp_path_factory.mktemp("captured")),
                every=True)
            assert outcome.ok, outcome.faults
            cache[peers, seed] = (cell, outcome, items)
        return cache[peers, seed]

    return get


def _records(cell, items, kind: str):
    return [entry.against_reference(cell, item) for item in items
            if item["kind"] == kind]


# -------------------------------------------------- the reference's file


def test_attack_plain_imports_nothing_of_the_program():
    with open(attack_plain.__file__) as f:
        source = f.read()
    imported = {line.split()[1] for line in source.splitlines()
                if line.lstrip().startswith(("import ", "from "))}
    # jax inside `draws` alone: the selection's draws are data
    assert imported <= {"__future__", "math", "numpy", "jax",
                        "benchmark.reference.des"}, imported
    assert source.count("import jax") == 1
    assert "dst_libp2p_test_node_tpu" not in source.replace(
        "`dst_libp2p_test_node_tpu`", "")


@pytest.mark.parametrize("name", ["configs/attack-2k.json",
                                  "tests/configs/tiny-attack.json"])
def test_the_configurations_defence_and_links_are_the_programs(name):
    """What the reference reads of a configuration is what `cmd_attack`
    runs with: `attack_gossipsub()`'s weights and thresholds, the campaign's
    engagement and recovery shares, yamux's processing delay, three equal
    link stages."""
    with open(os.path.join(CHECKOUT, "benchmark", name)) as f:
        config = json.load(f)
    at = config["attack"]
    exp = ExperimentConfig(
        topo=TopoParams(network_size=at["peers"], anchor_stages=3),
        connect_to=at["connect_to"], gossipsub=campaign.attack_gossipsub())
    params = dataclasses.asdict(SimParams.from_gossipsub(
        at["peers"], graph_capacity(exp), exp.gossipsub, proc_delay_ms=0.8))
    ours = {**params,
            "violation_penalty": adversary.AdversaryParams().violation_penalty,
            "censor_penalty": adversary.AdversaryParams().censor_penalty,
            "graylist_engaged_frac": campaign.GRAYLIST_ENGAGED_FRAC,
            "mesh_recovery_share": campaign.CampaignConfig(
            ).mesh_recovery_share}
    assert set(attack_plain.PARAMS) <= set(config["defence"])
    assert {k: ours[k] for k in config["defence"]} == config["defence"]
    assert {k: params[k] for k in config["link_model"]} == config["link_model"]
    topo = exp.topo
    assert at["links"] == {
        "anchor_stages": topo.anchor_stages,
        "min_bandwidth": topo.min_bandwidth,
        "max_bandwidth": topo.max_bandwidth,
        "min_latency": topo.min_latency, "max_latency": topo.max_latency}
    assert attack_plain.budget(config["defence"]) == \
        adversary.heartbeats_to_graylist(
            adversary.AdversaryParams(), SimParams(**params))


# ------------------------------------------- program against reference


@pytest.mark.parametrize("peers,seed", CASES)
def test_every_window_is_the_plain_transition_heartbeat_by_heartbeat(
        captured, peers, seed):
    cell, _, items = captured(peers, seed)
    records = _records(cell, items, "heartbeat")
    # two attacked trials of twenty heartbeats, each walked beside the scan
    assert len(records) == 2 * 20
    assert [r["heartbeat"] for r in records] == 2 * list(range(1, 21))
    for r in records:
        assert r["by_leaf"] == {leaf: 0 for leaf in r["by_leaf"]}, r
        assert (r["exact_differing"], r["float_beyond"],
                r["untouched_differing"]) == (0, 0, 0) and r["passed"]
        # the engine's operations in the engine's order: not a bit apart
        assert r["float_max_abs_diff"] == 0.0
    # the attack did something to compare: meshes grew under the flood
    assert max(r["mesh_edges"] for r in records) > min(
        r["mesh_edges"] for r in records)


@pytest.mark.parametrize("peers,seed", CASES)
def test_every_publish_has_the_plain_delivery_mask_and_the_des_delays(
        captured, peers, seed):
    cell, _, items = captured(peers, seed)
    records = _records(cell, items, "publish")
    assert len(records) == 2 * 3
    for r in records:
        assert r["survive_differing"] == 0 and r["reached_differing"] == 0
        assert r["passed"], r
        # attackers forward nothing and the graylist holds: fewer edges
        # deliver than the graph has
        assert 0 < r["delivering_edges"]
    # an attacker's own slots deliver nowhere (it forwards nothing); an
    # honest peer never floods, so between honest peers every edge delivers
    for item in (i for i in items if i["kind"] == "publish"):
        pub, cohort = item["publish"], item["shared"]["attacker"]
        survive, conns = pub["plan"]["survive"], pub["conns"]
        assert not survive[cohort].any()
        honest_edge = ((conns >= 0) & ~cohort[:, None]
                       & ~cohort[np.clip(conns, 0, None)])
        assert survive[honest_edge].all()


@pytest.mark.parametrize("peers,seed", CASES)
def test_every_publish_starts_from_the_references_own_carried_state(
        captured, peers, seed):
    """The reference walks its OWN state from the window's end through the
    schedule (a heartbeat between publishes, what a publish writes, the
    censorship penalty): the program's state at each publish's start, its
    counters after each penalty and the shape of each publish's writes are
    that walk's, entry for entry."""
    cell, _, items = captured(peers, seed)
    records = _records(cell, items, "publish")
    for r in records:
        assert (r["start_exact_differing"], r["start_float_beyond"],
                r["credit_rows_differing"], r["penalty_receivers_differing"],
                r["penalty_differing"]) == (0, 0, 0, 0, 0), r
    # a heartbeat runs between two publishes, none before the first
    assert [entry._heartbeats_before(cell, i) for i in range(3)] == [0, 1, 1]
    for item in (i for i in items if i["kind"] == "publish"):
        pub = item["publish"]
        mine = entry._carried(cell, item, False)[item["index"]]
        # the counters moved between the publishes (a credit a receiver,
        # decayed by the heartbeat), so the walk had something to carry
        if item["index"]:
            assert mine["start"]["fmd"].max() > 0.0
            assert not np.array_equal(
                mine["start"]["fmd"], item["schedule"][0]["start"]["fmd"])
        assert np.array_equal(mine["start"]["fmd"], pub["start"]["fmd"])
        assert np.array_equal(mine["penalised"]["slow_penalty"],
                              pub["penalised"])


@pytest.mark.parametrize("peers,seed,heartbeat", [
    (64, 3, 1), (64, 3, 2), (64, 2147483651, 1), (128, 5, 1), (128, 5, 3),
    (128, 11, 2)])
def test_the_censorship_penalty_is_the_plain_rule_where_attackers_are_meshed(
        captured, peers, seed, heartbeat):
    """By the publishes no honest peer keeps an attacker in its mesh, so the
    campaign's own penalties are zeros; here the program's update and the
    plain rule meet on a state early in the window, where the first GRAFTs
    were accepted and attackers sit in honest meshes."""
    import jax.numpy as jnp

    from dst_libp2p_test_node_tpu.ops.state import init_state

    cell, _, items = captured(peers, seed)
    trial = next(i for i in items if i["kind"] == "heartbeat")["shared"]
    host = trial["walk"][heartbeat - 1]
    at = cell.config["attack"]
    exp = ExperimentConfig(
        topo=TopoParams(network_size=peers, anchor_stages=3),
        connect_to=at["connect_to"], gossipsub=campaign.attack_gossipsub())
    params = SimParams.from_gossipsub(peers, graph_capacity(exp),
                                      exp.gossipsub, proc_delay_ms=0.8)
    state = init_state(params, seed=1).replace(
        **{k: jnp.asarray(v) for k, v in host.items()})
    received = np.random.default_rng(seed).random(peers) < 0.7
    got = np.asarray(adversary.censorship_penalty_update(
        state, jnp.asarray(trial["conns"]), jnp.asarray(trial["rev"]),
        jnp.asarray(trial["attacker"]), jnp.asarray(received), params,
        adversary.AdversaryParams()).slow_penalty)
    want = attack_plain.censorship_penalty(
        host, trial["conns"], trial["rev"], trial["attacker"], received,
        cell.config["defence"])
    assert np.array_equal(got, want)
    owed = want != host["slow_penalty"]
    assert owed.sum() > 0
    # only honest receivers' counters of attackers moved, by one unit
    rows, slots = np.nonzero(owed)
    assert received[rows].all() and not trial["attacker"][rows].any()
    assert trial["attacker"][trial["conns"][rows, slots]].all()
    assert np.array_equal(want[owed], host["slow_penalty"][owed] + 1.0)


def test_a_wrong_penalty_update_fails_the_publish_items(tmp_path,
                                                        monkeypatch):
    """An update that charges every mesh member of a receiver, not the
    silent ones, is caught where it is made (the counters after the first
    publish) and where it tells (the state the second starts from)."""
    import jax.numpy as jnp

    def wrong(state, conns, rev, attacker, received, params, adv):
        charged = state.mesh_mask & received[:, None]
        return state.replace(slow_penalty=state.slow_penalty + jnp.where(
            charged, jnp.float32(adv.censor_penalty), 0.0))

    monkeypatch.setattr(campaign, "censorship_penalty_update", wrong)
    cell = _cell(64)
    outcome, items = entry.captured(cell, 3, str(tmp_path))
    records = _records(cell, items, "publish")
    assert [r["message"] for r in records] == [101, 102, 103]
    assert not any(r["passed"] for r in records)
    assert records[0]["start_float_beyond"] == 0
    assert records[0]["penalty_differing"] > 0
    assert records[1]["start_float_beyond"] > 0


@pytest.mark.parametrize("peers,seed", CASES)
def test_every_row_of_stats_json_is_the_plain_metrics(captured, peers, seed):
    cell, outcome, items = captured(peers, seed)
    records = _records(cell, items, "row")
    assert len(records) == 2
    for r in records:
        assert r["differing"] == [] and r["passed"], r
    rows = outcome.stats["attack"]["rows"]
    assert [(row["fraction"], row["seed"]) for row in rows] == [
        (0.0, seed), (0.0, seed + 1), (0.2, seed), (0.2, seed + 1)]
    assert set(rows[0]) == {
        "fraction", "seed", "attackers", "honest_coverage",
        "latency_p50_ms", "latency_p99_ms", "benign_p50_ms",
        "latency_inflation", "hb_to_graylist", "mesh_recovery_hb"}
    # the campaign's counters are over what the rows say
    counted = outcome.stats["attack"]
    attacked = rows[2:]
    assert counted["hb_to_graylist_max"] == max(
        r["hb_to_graylist"] for r in attacked)
    assert counted["honest_coverage_min"] == min(
        r["honest_coverage"] for r in attacked)
    assert counted["latency_inflation_max"] == max(
        r["latency_inflation"] for r in attacked)
    assert counted["attacker_mesh_share_peak"] == pytest.approx(max(
        r["attacker_mesh_share_peak"] for r in records), abs=1e-7)
    assert counted["hb_budget"] == attack_plain.budget(cell.config["defence"])


@pytest.mark.parametrize("peers,seed", [(64, 3), (128, 5)])
def test_the_control_differs_in_every_item(captured, peers, seed):
    cell, _, items = captured(peers, seed)
    for item in items:
        control = entry.against_reference(cell, item, control=True)
        assert not control["passed"], control
    summary = entry.summarised(
        [entry.against_reference(cell, i, control=True) for i in items],
        control=True)
    assert summary["control_heartbeat_differing_min"] > 0
    assert summary["control_share_beyond_min"] > 0.9
    assert summary["control_row_differing_min"] > 0


# ----------------------------------------------------------- contracts


@pytest.mark.parametrize("peers,seed", CASES)
def test_a_fraction_0_trial_is_the_benign_simulators_bytes(
        captured, peers, seed):
    """The zero-attacker contract on the whole path: the baseline publishes
    of the captured campaign are `Simulator`'s on the trial's seed, bit for
    bit, delays and receipts."""
    cell, _, items = captured(peers, seed)
    at = entry.settings(cell)
    row = next(i for i in items if i["kind"] == "row"
               and i["trial_seed"] == seed)
    exp = ExperimentConfig(
        topo=TopoParams(network_size=peers, anchor_stages=3,
                        msg_size_bytes=at["msg_size"],
                        messages=at["messages"], delay_seconds=at["delay_s"]),
        connect_to=at["connect_to"], gossipsub=campaign.attack_gossipsub(),
        publisher_id=at["publisher_id"], warmup_s=at["warmup_s"], seed=seed)
    sim = Simulator(exp)
    records = sim.run()
    assert len(records) == len(row["baseline"]) == at["messages"]
    # (a record drops the publisher's own receipt; the result has it)
    others = np.arange(peers) != at["publisher_id"]
    for rec, (delays, received) in zip(records, row["baseline"]):
        assert np.array_equal(rec.received[others], received[others])
        got = rec.received & others
        assert got.sum() == peers - 1
        assert np.array_equal(rec.delays_ms[got], delays[got])


@pytest.mark.parametrize("peers,seed", [(64, 3), (128, 5)])
def test_same_seed_same_bytes_no_vmap_the_same_and_the_reads_counted(
        captured, peers, seed, tmp_path, monkeypatch):
    import jax

    cell, outcome, _ = captured(peers, seed)
    reads, leaves = [], []
    device_get, asarray = jax.device_get, np.asarray
    monkeypatch.setattr(jax, "device_get", lambda tree: (
        reads.append(len(jax.tree_util.tree_leaves(tree))), device_get(tree)
    )[1])

    def leaf(x, *args, **kw):
        if isinstance(x, jax.Array):
            leaves.append(x.shape)
        return asarray(x, *args, **kw)

    monkeypatch.setattr(np, "asarray", leaf)
    stats, ran, checked = _run_cli(cell, seed, str(tmp_path / "a"))
    monkeypatch.undo()
    assert checked["faults"] == []
    # the same seed: the captured campaign's bytes less the clock
    assert checked["digest"] == outcome.digest
    # every device->host read of the campaign is counted where it is made:
    # a `device_read` (one `jax.device_get`: a window's curves, 4 leaves, in
    # one; a publish's clock with the scans' counters; three an attacked
    # trial's metrics and one a benign one's) or one of the eight leaves of
    # a publish's result that `record_from_result` takes by `np.asarray`,
    # as the parent did; no device array is turned into numpy anywhere else
    counted = stats["attack"]
    assert len(reads) == 1 + 12 + 2 * 3 + 2 and reads.count(4) == 1
    assert len(leaves) == 12 * 8
    assert counted["device_reads"] == len(reads) + len(leaves) == 117
    assert counted["publishes"] == 12 and counted["vmapped_windows"] == 1
    assert counted["window_heartbeats"] == 20
    # sequential windows: the same campaign, two dispatches
    slow, ran_slow, _ = _run_cli(cell, seed, str(tmp_path / "b"), "--no-vmap")
    assert entry.less_clock(ran_slow) == entry.less_clock(ran)
    assert slow["attack"]["vmapped_windows"] == 0
    assert slow["attack"]["window_heartbeats"] == 2 * 20
    assert slow["attack"]["device_reads"] == counted["device_reads"] + 1
    other, ran_other, _ = _run_cli(cell, seed + 7, str(tmp_path / "c"))
    assert entry.less_clock(ran_other) != entry.less_clock(ran)


# ------------------------------------------------------------- the turn


def test_the_turn_leaves_its_spans_counters_and_stats_keys(
        captured, tmp_path, monkeypatch):
    cell, _, _ = captured(64, 3)
    noted = []
    monkeypatch.setattr(campaign, "counters",
                        lambda name, **values: noted.append((name, values)))
    stats, ran, checked = _run_cli(cell, 3, str(tmp_path / "a"))
    assert checked["faults"] == []
    assert {"network_size", "wall_s", "spans", "compile", "attack"} <= set(
        stats)
    assert stats["network_size"] == 64
    spans = stats["spans"]
    for name, count in (
            ("run", 1), ("run/topology", 1), ("run/simulator_init", 1),
            ("build/graph", 1), ("build/tables", 1), ("run/campaign", 1),
            ("campaign/baseline", 2),
            # a baseline's reset, an attacked trial's cohort draw and its
            # two resets (before the warm-up, before the publishes)
            ("trial/setup", 2 + 2 * 3), ("trial/warmup", 4),
            ("trial/window", 1), ("trial/publish", 4),
            ("publish", 12), ("publish/prepare", 12),
            ("publish/dispatch", 12), ("publish/read", 12),
            # the baseline's delivery metrics and its bytes; an attacked
            # trial's
            ("trial/metrics", 2 * 2 + 2), ("run/summary", 1),
            ("run/report", 1), ("run/write_json", 1),
            ("run/stats_json", 1)):
        assert spans[name]["count"] == count, name
    assert set(spans) == {
        "run", "run/topology", "run/simulator_init", "build/graph",
        "build/tables", "run/campaign", "campaign/baseline", "trial/setup",
        "trial/warmup", "trial/window", "trial/publish", "publish",
        "publish/prepare", "publish/dispatch", "publish/read",
        "trial/metrics", "run/summary", "run/report", "run/write_json",
        "run/stats_json"}
    assert stats["wall_s"] == pytest.approx(sum(
        spans[name]["total_s"] for name in (
            "run/topology", "run/simulator_init", "run/campaign")))
    # one annotation a campaign, with what --stats-json says
    assert [name for name, _ in noted] == ["attack/counters"]
    counted = noted[0][1]
    assert set(counted) == {
        "trials", "attacked_trials", "vmapped_windows", "window_heartbeats",
        "publishes", "device_reads", "honest_coverage_min",
        "latency_inflation_max", "hb_to_graylist_max", "hb_budget",
        "graylisted_frac_final_min", "attacker_mesh_share_peak",
        "attacker_score_final_mean"}
    assert {k: stats["attack"][k] for k in counted} == counted
    assert set(stats["attack"]) == set(counted) | {"rows"}
    # the campaign's JSON is what it was before the turn: no counters in it
    assert "counters" not in ran and "rows" not in ran
    # the last line of stdout takes its wall from the spans
    with open(tmp_path / "a" / "stdout.txt") as f:
        last = f.read().splitlines()[-1]
    assert last.startswith(f"[tpu backend] wall={stats['wall_s']:.2f}s "
                           "trials=4 ")


def test_spans_carry_a_trials_fraction_seed_and_vmapped(monkeypatch,
                                                        tmp_path):
    from dst_libp2p_test_node_tpu.runtime import profiling

    seen = []
    span = profiling.span

    def noting(name, **attrs):
        seen.append((name, attrs))
        return span(name, **attrs)

    monkeypatch.setattr(campaign, "span", noting)
    _run_cli(_cell(64), 3, str(tmp_path / "a"))
    phases = [(n, a) for n, a in seen if n.startswith(("trial/",
                                                       "campaign/"))]
    assert phases and all(
        {"fraction", "seed", "vmapped"} <= set(a) for _, a in phases)
    windows = [a for n, a in phases if n == "trial/window"]
    assert windows == [{"fraction": 0.2, "seed": 3, "vmapped": True,
                        "trials": 2}]
    assert {a["vmapped"] for n, a in phases if a["fraction"] == 0.0} == {
        False}


def test_the_attacked_scans_step_carries_its_two_device_scopes():
    import jax.numpy as jnp

    from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph
    from dst_libp2p_test_node_tpu.ops.state import graph_arrays, init_state

    graph = build_connection_graph(32, 4, seed=1)
    params = SimParams.from_gossipsub(32, graph.capacity,
                                      campaign.attack_gossipsub())
    a = graph_arrays(graph)
    # the compiled program's op names: what a profile's op events carry
    text = adversary._run_attacked_heartbeats.lower(
        init_state(params, seed=1), a["conns"], a["rev"], a["out_mask"],
        jnp.arange(32) < 6, params, adversary.AdversaryParams(), 2,
    ).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    step = "jit(_run_attacked_heartbeats)/while/body/closed_call/"
    assert any(n.startswith(step + "attack/adversary/jit(adversary_round)/")
               for n in names)
    # heartbeat_step's own scopes nest under the first
    under = {n[len(step + "attack/heartbeat/jit(heartbeat_step)/"):]
             .split("/")[0] for n in names
             if n.startswith(step + "attack/heartbeat/jit(heartbeat_step)/")}
    assert {"validity", "graft", "prune", "decay", "state"} <= under, under
    # and nothing of a step lies outside the two
    assert all(n.startswith((step + "attack/heartbeat/",
                             step + "attack/adversary/"))
               for n in names if n.startswith(step))


def test_attack_help_says_how_to_read_stats_json(capsys):
    with pytest.raises(SystemExit):
        cli.main(["attack", "--help"])
    said = " ".join(capsys.readouterr().out.split())
    for word in ("--stats-json", "device_reads", "vmapped_windows",
                 "window_heartbeats", "hb_to_graylist_max",
                 "attacker_mesh_share_peak", "latency_inflation_max",
                 "mesh_recovery_hb", "rows"):
        assert word in said, word
