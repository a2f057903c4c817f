"""The entry `kad`: the reference's kad-dht node, `cli.main(["kad", "--log",
<out_dir>/kadlog1, "--stats-json", <out_dir>/stats1.json])` under the node's
environment (PEERS, KAD_BOOTSTRAPS, KAD_PROBES, DISCOVERY, MUXER,
KAD_LEARN_CAP, and SEED from `--seed`: runtime/kad_runtime.config_from_env).
A traffic mix overrides the environment by name (`env`); `headline`
overrides nothing.

The node publishes nothing: an experiment is the boot, twenty FIND_NODE
waves of every normal peer (5 on its own key, 15 on random targets) and a
probe loop of twelve ticks, and what it leaves is the node's log and the
program's `--stats-json`. `correct`:

  part 1   from stats1.json and the log (`invariants`): the waves, ticks and
           lookups the configuration states, every request sent was served,
           every probe succeeded and the log says so line by line, and
           `closest1_share` at least `closest1_share_min`;
  part 2   the digest is the log's sha256;
  part 3   the experiment once more with `ops.kad.find_node` wrapped so that
           every wave and tick keeps its targets and results, and the ones
           replayed their start and end tables too. Against
           benchmark/reference/kad_node_plain.py (Python integers, no JAX),
           exactly (limit 0 differing entries; a lookup's latency within
           `kad_latency_atol_ms`): wave 1 from the seed alone (the boot's
           tables are the reference's own); `reference.waves` more drawn
           from the seed among waves 2-20 and every probe tick, each from
           the program's own start tables: `closest`, `hops`, `n_queries`,
           the requests every peer served and the tables after it; and the
           summary: every number of stats1.json "kad" recomputed from the
           captured arrays, `closest1_share` by brute force over all keys.

Items are numbered for the `correct_part3` lines: wave i is 100 + i, tick j
is 200 + j, the summary 300. `every=True` (rehearse_seeds.py) replays all
twenty waves.

The control (benchmark/control.py) is kad_plain's own: the reference
computed one precision lower and put in the program's place, XOR distances
and times rounded to bfloat16, so that what agrees in its first eight bits
is ordered by where it stood. It has to differ in every item: in thousands
of entries a wave, in tens a tick of ten lookups (in a tick whose ten
orderings bfloat16 happens to keep, by its ten latencies alone), and in the
summary's latency percentiles (1,212 ms is no bfloat16; the brute force of
the probes' targets under bfloat16 mostly finds the same peer, the nearest
and the second nearest of 10,000 uniform keys seldom lying within 0.4 %).
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import random

import numpy as np

from benchmark.entries.regression import _differing, _padded
from benchmark.harness.experiment import Outcome, run_experiment
from benchmark.reference import des, kad_node_plain, kad_plain, link_tables

WAVE_ITEM = 100     # wave i is item 100 + i
TICK_ITEM = 200     # tick j is item 200 + j
SUMMARY_ITEM = 300
WAVES = kad_node_plain.SELF_WAVES + kad_node_plain.RANDOM_WAVES


def settings(cell) -> dict:
    """The configuration's `kad` (env, probe ticks, links, learn_cap), with
    the environment the traffic mix overrides."""
    kad = dict(cell.config["kad"])
    kad["env"] = {**kad["env"], **cell.traffic.get("env", {})}
    return kad


def invocation(cell, seed: int, out_dir: str) -> tuple[list[str], dict]:
    env = {**{k: str(v) for k, v in settings(cell)["env"].items()},
           "SEED": str(seed)}
    return ["kad", "--log", os.path.join(out_dir, "kadlog1"),
            "--stats-json", os.path.join(out_dir, "stats1.json")], env


def roles(cell) -> tuple[int, int, int]:
    """(peers, bootstraps, probes)."""
    env = settings(cell)["env"]
    return (int(env["PEERS"]), int(env["KAD_BOOTSTRAPS"]),
            int(env["KAD_PROBES"]))


# ------------------------------------------------- part 1 and the digest


def invariants(cell, out_dir: str) -> dict:
    node, guarantees = settings(cell), cell.config["guarantees"]
    try:
        with open(os.path.join(out_dir, "stats1.json")) as f:
            stats = json.load(f)
        with open(os.path.join(out_dir, "kadlog1"), "rb") as f:
            log = f.read()
    except OSError as e:
        return {"faults": [f"artifact missing: {e}"]}
    peers, bootstraps, probes = roles(cell)
    ticks = int(node["probe_ticks"])
    normal = peers - bootstraps - probes
    kad = stats.get("kad", {})
    kinds = [w.get("kind") for w in kad.get("lookup_latency_ms", [])]
    want_kinds = (["self"] * kad_node_plain.SELF_WAVES
                  + ["random"] * kad_node_plain.RANDOM_WAVES
                  + ["probe"] * ticks)
    faults = []
    if kinds != want_kinds:
        faults.append(f"waves and ticks {kinds}, the configuration states "
                      f"{want_kinds}")
    want = {"warmup_waves": WAVES, "probe_ticks": ticks,
            "lookups": WAVES * normal + ticks * probes,
            "probe_lookups": ticks * probes, "probe_success": ticks * probes}
    for name, value in want.items():
        if kad.get(name) != value:
            faults.append(f"{name} {kad.get(name)}, the configuration "
                          f"states {value}")
    if kad.get("queries_tx") != kad.get("queries_rx"):
        faults.append(f"{kad.get('queries_tx')} FIND_NODE requests sent, "
                      f"{kad.get('queries_rx')} served")
    lines = log.decode().splitlines()
    found = sum(line.startswith("Probe: Finding node target=")
                for line in lines)
    if found != ticks * probes or any(
            line.startswith("Probe Failed") for line in lines):
        faults.append(f"{found} 'Probe: Finding node' lines of "
                      f"{ticks * probes}, or a 'Probe Failed'")
    floor = float(guarantees["closest1_share_min"])
    share = kad.get("closest1_share")
    if not isinstance(share, float) or not floor <= share <= 1.0:
        faults.append(f"closest1_share {share}, guaranteed at least {floor}")
    return {"faults": faults, "digest": hashlib.sha256(log).hexdigest(),
            "digest_of": "kadlog1", "stats": stats}


def digest_line(outcome: Outcome) -> dict:
    kad = outcome.stats.get("kad", {})
    return {"log_sha256": outcome.digest,
            **{k: kad.get(k) for k in (
                "hops_mean", "queries_per_lookup", "census_mean",
                "census_min", "bucket_full_share", "closest1_share",
                "queries_tx")}}


# ------------------------------------------------------------------ part 3


def drawn(cell, seed: int) -> list[int]:
    """The waves run.py replays: wave 1, and `reference.waves` of waves
    2-20 drawn from the seed."""
    count = min(int(cell.config["reference"]["waves"]), WAVES - 1)
    return [1] + sorted(random.Random(seed).sample(range(2, WAVES + 1),
                                                   count))


@contextlib.contextmanager
def capture_waves(with_tables):
    """Wrap `ops.kad.find_node` as runtime/kad_runtime.dispatch_waves calls
    it; yields the list that fills with every call's origins, targets and
    lookups, and where `with_tables(index)` (calls count from 1) the tables
    before and after it."""
    from dst_libp2p_test_node_tpu.ops import kad

    find_node = kad.find_node
    taken: list[dict] = []

    def wave(state, origins, targets, stage, lat_ms, **kw):
        res, after = find_node(state, origins, targets, stage, lat_ms, **kw)
        item = {
            "origins": np.asarray(origins), "targets": np.asarray(targets),
            "closest": np.asarray(res.closest), "hops": np.asarray(res.hops),
            "n_queries": np.asarray(res.n_queries),
            "latency_ms": np.asarray(res.latency_ms, np.float64),
            "queried": np.asarray(res.queried),
            "learn_counts": np.asarray(res.learn_counts)}
        if with_tables(len(taken) + 1):
            item["start_rtable"] = np.asarray(state.rtable)
            item["end_rtable"] = np.asarray(after.rtable)
        taken.append(item)
        return res, after

    kad.find_node = wave
    try:
        yield taken
    finally:
        kad.find_node = find_node


def captured(cell, seed: int, out_dir: str,
             every: bool = False) -> tuple[Outcome, list[dict]]:
    ticks = int(settings(cell)["probe_ticks"])
    checked = drawn(cell, seed)
    replayed = range(1, WAVES + 1) if every else checked
    with capture_waves(lambda i: i in replayed or i > WAVES) as taken:
        outcome = run_experiment(cell, seed, out_dir)
    if outcome.ok and len(taken) != WAVES + ticks:
        outcome.faults.append(f"captured {len(taken)} find_node calls, "
                              f"wanted {WAVES} waves and {ticks} ticks")
    if not outcome.ok:
        return outcome, []
    for i, item in enumerate(taken, start=1):
        item["kind"] = "wave" if i <= WAVES else "tick"
        item["message"] = WAVE_ITEM + i if i <= WAVES else \
            TICK_ITEM + i - WAVES
        item["seed"] = seed
        item["drawn"] = i in checked or i > WAVES
    summary = {"kind": "summary", "message": SUMMARY_ITEM, "seed": seed,
               "drawn": True, "stats": outcome.stats["kad"],
               "end_rtable": taken[-1]["end_rtable"],
               "calls": [{k: v for k, v in item.items()
                          if not k.endswith("_rtable")} for item in taken]}
    return outcome, [item for item in taken
                     if "start_rtable" in item] + [summary]


def _network(cell, peers: int):
    """The reference's own (stage of a peer, latency of a stage pair)."""
    links = settings(cell)["links"]
    _, latency = link_tables.stage_tables(links)
    return np.arange(peers) % int(links["anchor_stages"]), latency


def _served(peers: int, asked) -> np.ndarray:
    """Requests each peer served, from the peers asked (-1: none)."""
    asked = np.asarray(asked).reshape(-1)
    return np.bincount(asked[asked >= 0], minlength=peers)


def _wave_reading(cell, item: dict, quantize) -> dict:
    """kad_node_plain on the call's start tables and targets, as arrays
    shaped like the program's; wave 1's start tables from the seed."""
    peers, bootstraps, _ = roles(cell)
    keys = kad_plain.make_keys(peers, item["seed"])
    stage, latency = _network(cell, peers)
    out = {}
    if item["message"] == WAVE_ITEM + 1:
        seeded = kad_plain.empty_tables(peers)
        kad_plain.seed_bootstraps(seeded, keys, range(bootstraps))
        out["start_rtable"] = kad_plain.tables_to_array(seeded)
    lookups, after = kad_node_plain.wave(
        kad_node_plain.tables_from_array(item["start_rtable"]), keys,
        item["origins"], item["targets"], stage, latency, quantize,
        learn_cap=settings(cell)["learn_cap"])
    out.update(
        closest=_padded([f["closest"] for f in lookups], kad_plain.K_RESP),
        hops=np.array([f["hops"] for f in lookups]),
        n_queries=np.array([f["n_queries"] for f in lookups]),
        latency_ms=np.array([f["latency_ms"] for f in lookups]),
        served=_served(peers, [p for f in lookups for p in f["asked"]]),
        end_rtable=kad_plain.tables_to_array(after))
    return out


def _wave_record(cell, item: dict, control: bool) -> dict:
    ref = cell.config["reference"]
    # the sound reading is kept on the item: the control beside it pays it
    # once
    if "reference" not in item:
        item["reference"] = _wave_reading(cell, item, None)
    want = item["reference"]
    if control:
        got = _wave_reading(cell, item, des.bfloat16_round)
    else:
        got = {**item, "served": _served(len(item["start_rtable"]),
                                         item["queried"])}
    numbers = {f"{k}_differing": _differing(got[k], want[k])
               for k in ("closest", "hops", "n_queries", "served",
                         "end_rtable")}
    if "start_rtable" in want:
        numbers["start_rtable_differing"] = _differing(
            got["start_rtable"], want["start_rtable"])
    diff = np.abs(got["latency_ms"] - want["latency_ms"])
    numbers["latency_beyond"] = int((diff > ref["kad_latency_atol_ms"]).sum())
    return {"what": f"find_node {item['kind']} against the plain kad-dht "
            "node", "seed": item["seed"], "message": item["message"],
            "lookups": len(diff), "hops_mean": float(np.mean(want["hops"])),
            "latency_max_abs_diff_ms": float(diff.max()),
            "tolerance": f"{ref['kad_latency_atol_ms']} ms a lookup",
            **numbers, **{f"limit_{k}": 0 for k in numbers},
            "passed": not any(numbers.values())}


def _plain_calls(calls) -> tuple[list[dict], list[dict]]:
    """The captured arrays as kad_node_plain's waves and ticks."""
    items = []
    for n, call in enumerate(calls):
        kind = ("self" if n < kad_node_plain.SELF_WAVES else
                "random" if n < WAVES else "probe")
        items.append({"kind": kind, "targets": call["targets"], "lookups": [
            {"closest": [x for x in row if x >= 0], "hops": int(h),
             "n_queries": int(q), "latency_ms": float(ms),
             "asked": [x for x in asked if x >= 0]}
            for row, h, q, ms, asked in zip(
                call["closest"].tolist(), call["hops"], call["n_queries"],
                call["latency_ms"], call["queried"].tolist())]})
    return items[:WAVES], items[WAVES:]


def _summary_record(cell, item: dict, control: bool) -> dict:
    """stats1.json "kad" against the same numbers recomputed from the
    captured arrays; closest1 by brute force (the control's: the probes'
    targets under bfloat16 distances, every time rounded to bfloat16)."""
    ref = cell.config["reference"]
    peers, bootstraps, _ = roles(cell)
    keys = kad_plain.make_keys(peers, item["seed"])
    waves, ticks = _plain_calls(item["calls"])
    if "reference" not in item:
        item["reference"] = (kad_node_plain.closest1(waves[-1:], keys),
                             kad_node_plain.closest1(ticks, keys))
    last_wave, probes = item["reference"]
    want = kad_node_plain.summary(
        waves, ticks, kad_node_plain.tables_from_array(item["end_rtable"]),
        bootstraps, tuple(a + b for a, b in zip(last_wave, probes)))
    got = item["stats"]
    if control:
        low = kad_node_plain.closest1(ticks, keys, des.bfloat16_round)
        got = {**got, "closest1_share":
               (last_wave[1] + low[1]) / want["closest1_checked"],
               "lookup_latency_ms": [
                   {p: des.bfloat16_round(w[p]) for p in ("p50", "p99")}
                   for w in want["lookup_latency_ms"]]}
    exact = ("lookups", "warmup_waves", "probe_ticks", "probe_lookups",
             "probe_success", "census_min", "queries_tx", "queries_rx")
    close = ("census_mean", "hops_mean", "queries_per_lookup",
             "queries_per_bootstrap", "closest1_share")
    off = [k for k in exact if got.get(k) != want[k]]
    off += [k for k in close
            if not abs(got.get(k, float("nan")) - want[k]) <= 1e-9]
    took = [abs(g[p] - w[p]) for g, w in zip(got["lookup_latency_ms"],
                                             want["lookup_latency_ms"])
            for p in ("p50", "p99")]
    numbers = {
        "summary_numbers_differing": len(off),
        "latency_percentiles_beyond": int(sum(
            d > ref["kad_latency_atol_ms"] for d in took))}
    return {"what": "stats1.json's kad numbers against the captured "
            "arrays, closest1_share against the brute force over all keys",
            "seed": item["seed"], "message": item["message"],
            "differing": off, "closest1_checked": want["closest1_checked"],
            "closest1_share": got.get("closest1_share"),
            "closest1_share_reference": want["closest1_share"],
            **numbers, **{f"limit_{k}": 0 for k in numbers},
            "passed": not any(numbers.values())}


def against_reference(cell, item: dict, control: bool = False) -> dict:
    if item["kind"] == "summary":
        return _summary_record(cell, item, control)
    return _wave_record(cell, item, control)


def summarised(records: list[dict], control: bool = False) -> dict:
    """Of all items the sound runs' largest count of differing entries, the
    control's smallest (by kind too: a tick has ten lookups, a wave ten
    thousand)."""
    def count(r):
        return sum(v for k, v in r.items()
                   if not k.startswith("limit_") and isinstance(v, int)
                   and k.endswith(("_differing", "_beyond")))

    name, of = (("control_differing_min", min) if control
                else ("sound_differing_max", max))
    kinds = {"wave": lambda m: m < TICK_ITEM,
             "tick": lambda m: TICK_ITEM <= m < SUMMARY_ITEM,
             "summary": lambda m: m == SUMMARY_ITEM}
    return {f"{name}_{kind}": of((count(r) for r in records
                                  if holds(r["message"])), default=None)
            for kind, holds in kinds.items()}
