"""The entry `regression`: the reference's regression node,
`cli.main(["regression", "--messages", m, "--msg-size", b, "--latencies",
<out_dir>/latencies1, "--stats-json", <out_dir>/stats1.json])` under the
node's environment (PEERS, CONNECTTO, STARTSLEEP, FRAGMENTS, MUXER,
REGRESSION_BOOTSTRAPS, and SEED from `--seed`:
runtime/regression_runtime.config_from_env).

The node forms its GossipSub mesh by kad-dht bootstrap (FIND_NODE waves,
then dials drawn from the routing tables) and publishes through the
`disseminate` that `run` publishes through. So `correct` has two references:

  a publish    benchmark/reference/des.py, as `run` compares it
               (entries/run.py's capture and comparison; the links are the
               configuration's, the node's one stage), limits `eps`,
               `eps_hop` of the configuration's `reference`;
  discovery    benchmark/reference/kad_plain.py: every wave's lookups
               (`closest`, `hops`, `n_queries` exactly, `latency_ms` within
               `kad_latency_atol_ms`) and the tables after it, from the
               wave's start tables and targets; the first wave's start
               tables from the seed alone; and the dials' connections from
               the final tables and the seed. Exact: the limit is 0
               differing entries.

Which waves whole: all of them (kad_plain takes 3-4 s a wave at 10,000 peers
on the chip's host, `reference_seconds` says). Items are numbered for the
`correct_part3` lines: a publish by its number in the experiment, wave i as
100 + i, the connections as 110.

The control of the exact comparisons (benchmark/control.py) is kad_plain
itself computed one precision lower and put in the program's place: XOR
distances, times and the dials' draws rounded to bfloat16, so that what
agrees in its first eight bits is ordered by where it stood. It has to
differ, in thousands of entries; a control that passed would say the
comparison cannot see an ordering fault.

Part 1 reads the program's own `--stats-json`: the receivers of EVERY
message as a count (a percentage to one decimal cannot tell 9,999 from
10,000), the waves and ping rounds the configuration states, requests sent
equal to requests served.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os

import numpy as np

from benchmark.entries import run as run_entry
from benchmark.harness.experiment import Outcome
from benchmark.reference import des, kad_plain, link_tables

WAVE_ITEM = 100     # wave i is item 100 + i
CONNS_ITEM = 110


def settings(cell) -> dict:
    """The configuration's `regression` (env, messages, msg_size, publisher,
    links, waves and ping rounds), with the environment the traffic mix
    overrides."""
    reg = dict(cell.config["regression"])
    reg["env"] = {**reg["env"], **cell.traffic.get("env", {})}
    return reg


def invocation(cell, seed: int, out_dir: str) -> tuple[list[str], dict]:
    reg = settings(cell)
    env = {**{k: str(v) for k, v in reg["env"].items()}, "SEED": str(seed)}
    return ["regression", "--messages", str(reg["messages"]), "--msg-size",
            str(reg["msg_size"]),
            "--latencies", os.path.join(out_dir, "latencies1"),
            "--stats-json", os.path.join(out_dir, "stats1.json")], env


# ------------------------------------------------- part 1 and the digest


def invariants(cell, out_dir: str) -> dict:
    reg, guarantees = settings(cell), cell.config["guarantees"]
    try:
        with open(os.path.join(out_dir, "stats1.json")) as f:
            stats = json.load(f)
        with open(os.path.join(out_dir, "latencies1"), "rb") as f:
            latencies = f.read()
    except OSError as e:
        return {"faults": [f"artifact missing: {e}"]}
    peers, messages = int(reg["env"]["PEERS"]), int(reg["messages"])
    floor = float(guarantees["coverage_share_min"]) * peers
    counts = stats.get("coverage_by_message")
    faults = []
    if (not isinstance(counts, list) or len(counts) != messages
            or not all(floor <= c <= peers for c in counts)):
        faults.append(f"receivers a message {counts} of {peers} peers and "
                      f"{messages} messages, guaranteed at least {floor}")
        counts = [peers] * messages
    faults += run_entry.latency_lines_faults(
        latencies, messages, sum(counts) / messages,
        guarantees["no_delay_under_ms"], int(reg["publisher"]))
    kad, pings = stats.get("kad", {}), stats.get("pings", {})
    if kad.get("waves") != reg["discovery_rounds"]:
        faults.append(f"{kad.get('waves')} find_node waves, the "
                      f"configuration states {reg['discovery_rounds']}")
    if kad.get("queries_tx") != kad.get("queries_rx"):
        faults.append(f"{kad.get('queries_tx')} FIND_NODE requests sent, "
                      f"{kad.get('queries_rx')} served")
    if pings.get("rounds") != reg["ping_rounds"] or pings.get("timeouts"):
        faults.append(f"pings {pings}, the configuration states "
                      f"{reg['ping_rounds']} rounds and no timeout")
    return {"faults": faults, "digest": hashlib.sha256(latencies).hexdigest(),
            "digest_of": "latencies1", "stats": stats}


def digest_line(outcome: Outcome) -> dict:
    stats = outcome.stats
    return {"latencies_sha256": outcome.digest,
            "avg_latency_ms": stats.get("avg_latency_ms"),
            "max_latency_ms": stats.get("max_latency_ms"),
            "coverage_by_message": stats.get("coverage_by_message"),
            "mesh_degree_mean": stats.get("mesh_degree_mean"),
            "rtable_census_mean": stats.get("kad", {}).get(
                "rtable_census_mean"),
            "cap_filtered_edges": stats.get("build", {}).get(
                "cap_filtered_edges")}


# ------------------------------------------------------------------ part 3


@contextlib.contextmanager
def capture_discovery():
    """Wrap `ops.kad.find_node` and `regression_runtime.discovery_graph` as
    the regression path calls them; yields the list that fills with every
    wave (start tables, origins, targets, the lookups, end tables) and the
    graph made from the final tables."""
    from dst_libp2p_test_node_tpu.ops import kad
    from dst_libp2p_test_node_tpu.runtime import regression_runtime as rr

    find_node, discovery_graph = kad.find_node, rr.discovery_graph
    taken: list[dict] = []

    def wave(state, origins, targets, stage, lat_ms, **kw):
        res, after = find_node(state, origins, targets, stage, lat_ms, **kw)
        taken.append({
            "message": WAVE_ITEM + sum(t["kind"] == "wave" for t in taken),
            "kind": "wave",
            "start_rtable": np.asarray(state.rtable),
            "origins": np.asarray(origins), "targets": np.asarray(targets),
            "closest": np.asarray(res.closest), "hops": np.asarray(res.hops),
            "n_queries": np.asarray(res.n_queries),
            "latency_ms": np.asarray(res.latency_ms, np.float64),
            "end_rtable": np.asarray(after.rtable)})
        return res, after

    def graph(kstate, connect_to, bootstraps, seed, **kw):
        made = discovery_graph(kstate, connect_to, bootstraps, seed, **kw)
        taken.append({
            "message": CONNS_ITEM, "kind": "conns",
            "rtable": np.asarray(kstate.rtable), "connect_to": connect_to,
            "bootstraps": [int(b) for b in bootstraps],
            "conns": made.conns.copy(),
            "cap_filtered_edges": made.build["cap_filtered_edges"]})
        return made

    kad.find_node, rr.discovery_graph = wave, graph
    try:
        yield taken
    finally:
        kad.find_node, rr.discovery_graph = find_node, discovery_graph


def captured(cell, seed: int, out_dir: str,
             every: bool = False) -> tuple[Outcome, list[dict]]:
    reg = settings(cell)
    with capture_discovery() as discovery:
        outcome, publishes = run_entry.captured_experiment(
            cell, seed, int(reg["messages"]), out_dir, every)
    waves = [d["message"] for d in discovery if d["kind"] == "wave"]
    if outcome.ok and (len(waves) != reg["discovery_rounds"]
                       or len(discovery) != len(waves) + 1):
        outcome.faults.append(
            f"captured discovery items {[d['message'] for d in discovery]}, "
            f"wanted {reg['discovery_rounds']} waves and the connections")
    for item in discovery:
        item["seed"], item["drawn"] = seed, True
    return outcome, discovery + publishes


def _network(cell, peers: int):
    """The reference's own (stage of a peer, latency of a stage pair)."""
    links = settings(cell)["links"]
    _, latency = link_tables.stage_tables(links)
    return np.arange(peers) % int(links["anchor_stages"]), latency


def _differing(a, b) -> int:
    return int((np.asarray(a) != np.asarray(b)).sum())


def _padded(rows, width: int) -> np.ndarray:
    out = np.full((len(rows), width), -1, np.int64)
    for i, row in enumerate(rows):
        out[i, :len(row)] = row
    return out


def _wave_reading(cell, item: dict, quantize) -> dict:
    """kad_plain on the wave's start tables and targets, as arrays shaped
    like the program's; the first wave's start tables from the seed."""
    peers = item["start_rtable"].shape[0]
    keys = kad_plain.make_keys(peers, item["seed"])
    stage, latency = _network(cell, peers)
    out = {}
    if item["message"] == WAVE_ITEM:
        seeded = kad_plain.empty_tables(peers)
        kad_plain.seed_bootstraps(
            seeded, keys,
            range(int(settings(cell)["env"]["REGRESSION_BOOTSTRAPS"])))
        out["start_rtable"] = kad_plain.tables_to_array(seeded)
    lookups, after = kad_plain.wave(
        kad_plain.tables_from_array(item["start_rtable"]), keys,
        item["origins"], item["targets"], stage, latency, quantize,
        learn_cap=settings(cell)["learn_cap"])
    out.update(
        closest=_padded([found["closest"] for found in lookups],
                        kad_plain.K_RESP),
        hops=np.array([found["hops"] for found in lookups]),
        n_queries=np.array([found["n_queries"] for found in lookups]),
        latency_ms=np.array([found["latency_ms"] for found in lookups]),
        end_rtable=kad_plain.tables_to_array(after))
    return out


def _conns_reading(item: dict, capacity: int, quantize) -> dict:
    tables = kad_plain.tables_from_array(item["rtable"])
    dialled = kad_plain.dials(tables, item["connect_to"], item["bootstraps"],
                              item["seed"], quantize)
    return {"conns": kad_plain.connections(dialled, item["seed"], capacity)}


def _discovery_record(cell, item: dict, control: bool) -> dict:
    ref = cell.config["reference"]
    wave = item["kind"] == "wave"

    def reading(quantize):
        return (_wave_reading(cell, item, quantize) if wave
                else _conns_reading(item, item["conns"].shape[1], quantize))

    # the sound reading is kept on the item: the control beside it pays it
    # once
    if "reference" not in item:
        item["reference"] = reading(None)
    want = item["reference"]
    got = reading(des.bfloat16_round) if control else item
    exact = (("closest", "hops", "n_queries", "end_rtable") if wave
             else ("conns",))
    numbers = {f"{k}_differing": _differing(got[k], want[k]) for k in exact}
    if "start_rtable" in want:
        numbers["start_rtable_differing"] = _differing(
            got["start_rtable"], want["start_rtable"])
    record = {"what": ("find_node wave" if wave else "the dials' "
                       "connections") + " against the plain Kademlia "
              "reference", "seed": item["seed"], "message": item["message"]}
    if wave:
        diff = np.abs(got["latency_ms"] - want["latency_ms"])
        numbers["latency_beyond"] = int(
            (diff > ref["kad_latency_atol_ms"]).sum())
        record.update(
            lookups=len(diff), hops_mean=float(np.mean(want["hops"])),
            latency_max_abs_diff_ms=float(diff.max()),
            tolerance=f"{ref['kad_latency_atol_ms']} ms a lookup")
    else:
        record.update(edges=int((want["conns"] >= 0).sum()) // 2,
                      cap_filtered_edges=item["cap_filtered_edges"])
    return {**record, **numbers,
            **{f"limit_{k}": 0 for k in numbers},
            "passed": not any(numbers.values())}


def against_reference(cell, item: dict, control: bool = False) -> dict:
    if "kind" in item:
        return _discovery_record(cell, item, control)
    return run_entry.against_reference(cell, item, control,
                                       links=settings(cell)["links"])


def summarised(records: list[dict], control: bool = False) -> dict:
    """The publishes as `run` summarises them; of the discovery items the
    sound runs' largest count of differing entries, the control's smallest."""
    publishes = [r for r in records if r["message"] < WAVE_ITEM]
    discovery = [r for r in records if r["message"] >= WAVE_ITEM]
    counts = [sum(v for k, v in r.items()
                  if k.endswith("_differing") and not k.startswith("limit_"))
              + r.get("latency_beyond", 0) for r in discovery]
    name, of = (("control_discovery_differing_min", min) if control
                else ("sound_discovery_differing_max", max))
    return {**run_entry.summarised(publishes, control),
            name: of(counts, default=None)}
