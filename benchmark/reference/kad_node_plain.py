"""The plain kad-dht node: the reference's role program (vacp2p/
dst-libp2p-test-node nim-test-node/kad-dht/{main,core}.nim) over the plain
Kademlia of `kad_plain.py`.

Python integers and lists, numpy only where `kad_plain.py` has it (the
seeded keys) and for a table's conversion; no JAX and nothing of the
program. `kad_plain.py` is imported as it stands: keys, buckets, learning,
seeding, a FIND_NODE response and the iterative lookup are its rules.

The role program (`node`), at N peers, B bootstraps (ids 0..B-1) and P
probes (the P highest ids); the others are the normal peers:

  boot      every peer learns the bootstraps, every bootstrap every peer
            (`kad_plain.seed_bootstraps`; main.nim:34-47, helpers.nim:62);
  warm-up   every normal peer runs 5 x FIND_NODE(its own key), then 15 x
            FIND_NODE(a random target) (`runWarmup`, core.nim:12-35), and
            after each of the first five the routing tables are counted
            (core.nim:17-22);
  probe     every probe peer runs FIND_NODE(a random target) every 5 s, as
            many ticks as the probe phase has (`runProbe`, core.nim:38-55);
            a lookup that takes over 30,000 ms is a "Probe Failed".

The random targets are the caller's, in the order they are used (a deployment
draws them with `getRandomPeerId`, helpers.nim:10-12; the program's come
out of its seed, and the comparison gives the reference the same ones).

**Departs** from the node, as the program does (`KadSimulator.boot`):
- batched waves in place of the `myId * 200 ms` start jitter: every normal
  peer runs iteration i on the tables as they stand after iteration i - 1,
  and no lookup of a wave sees what another of the same wave taught
  (`kad_plain.wave`'s own departure);
- a round costs its slowest query (`kad_plain.lookup`), and nothing fails:
  the network is fault-free, so no dial is retried and no probe times out
  unless six rounds take over 30 s;
- the probes look up from the tables the warm-up left; they seed no table of
  their own beyond the boot's.

`wave` here is `kad_plain.wave` that also says whom each lookup asked, from
`kad_plain.lookup` and `kad_plain.learn`, so that the requests a peer served
can be counted (`tests/test_kad_node_reference.py` holds the two to the same
lookups and tables). `true_closest` is the brute force: the peer whose key
is closest to a target among ALL keys, which is what a lookup that
converged returns first.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import kad_plain

SELF_WAVES = 5          # core.nim:13-23
RANDOM_WAVES = 15       # core.nim:25-33
PROBE_TIMEOUT_MS = 30000.0   # core.nim:47


def key_words(key: int) -> list[int]:
    """A key as four uint32 words, most significant first (what
    `kad_plain.key_of` reads)."""
    return [(key >> shift) & 0xFFFFFFFF for shift in (96, 64, 32, 0)]


def tables_from_array(rtable) -> list[list[list[int]]]:
    """`kad_plain.tables_from_array`, through one `tolist` (at 10,000 peers
    the element-wise walk takes seconds a call)."""
    rtable = np.asarray(rtable)
    held = (rtable >= 0).sum(axis=-1).tolist()
    return [[bucket[:count] for bucket, count in zip(table, counts)]
            for table, counts in zip(rtable.tolist(), held)]


def wave(tables, keys, origins, targets, stage, latency_ms, quantize=None,
         learn_cap: int | None = None):
    """`kad_plain.wave`, each lookup also with `asked`: the peers it
    queried, in order."""
    entries = [[x for bucket in table for x in bucket] for table in tables]

    def rtt_ms(a, b):
        return 2.0 * float(latency_ms[stage[a]][stage[b]]) + kad_plain.PROC_MS

    lookups = []
    learners: dict[int, list[int]] = {}
    for origin, target in zip(origins, targets):
        origin = int(origin)
        shortlist, hops, sent, took, asked = kad_plain.lookup(
            origin, kad_plain.key_of(target), entries, keys, rtt_ms, quantize)
        lookups.append({"origin": origin,
                        "closest": shortlist[:kad_plain.K_RESP],
                        "shortlist": shortlist, "hops": hops,
                        "n_queries": sent, "latency_ms": took,
                        "asked": asked})
        for p in asked:
            learners.setdefault(p, []).append(origin)
    after = [[list(bucket) for bucket in table] for table in tables]
    for found in lookups:
        kad_plain.learn(after[found["origin"]], keys, found["origin"],
                        found["shortlist"])
    for p, who in learners.items():
        kad_plain.learn(after[p], keys, p, who[:learn_cap])
    return lookups, after


def census(tables) -> list[int]:
    """Entries a peer's routing table holds (core.nim:17-22)."""
    return [sum(len(bucket) for bucket in table) for table in tables]


def true_closest(keys, target: int, quantize=None) -> int:
    """The peer closest to `target` among all keys; of peers at one
    (quantized) distance the lowest id."""
    if quantize is None:
        return min(range(len(keys)), key=lambda p: keys[p] ^ target)
    return min(range(len(keys)), key=lambda p: quantize(
        float(keys[p] ^ target) / 2.0 ** 64))


def percentile(values, q: float) -> float:
    """The q-th percentile by linear interpolation between the two nearest
    ranks (numpy's default rule)."""
    ordered = sorted(values)
    at = (len(ordered) - 1) * q / 100.0
    low = int(at)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (at - low)


def summary(waves, ticks, tables, n_bootstrap: int, closest1=None) -> dict:
    """The node's summary from the lookups of all waves and ticks and the
    final tables: the numbers `KadSummary` and `--stats-json` "kad" give."""
    lookups = [f for item in waves + ticks for f in item["lookups"]]
    probes = [f for item in ticks for f in item["lookups"]]
    latencies = [f["latency_ms"] for f in lookups]
    counts = census(tables)
    served = [0] * len(tables)
    for found in lookups:
        for p in found["asked"]:
            served[p] += 1
    sent = sum(f["n_queries"] for f in lookups)
    out = {
        "lookups": len(lookups),
        "warmup_waves": len(waves),
        "probe_ticks": len(ticks),
        "warmup_lookups": len(lookups) - len(probes),
        "probe_lookups": len(probes),
        "probe_success": sum(f["latency_ms"] <= PROBE_TIMEOUT_MS
                             for f in probes),
        "census_mean": sum(counts) / len(counts),
        "census_min": min(counts),
        "census_max": max(counts),
        "lookup_latency_ms_p50": percentile(latencies, 50),
        "lookup_latency_ms_p99": percentile(latencies, 99),
        "hops_mean": sum(f["hops"] for f in lookups) / len(lookups),
        "queries_per_lookup": sent / len(lookups),
        "queries_tx": sent,
        "queries_rx": sum(served),
        "queries_per_bootstrap": sum(served[:n_bootstrap]) / n_bootstrap,
        "lookup_latency_ms": [
            {"kind": item["kind"],
             "p50": percentile([f["latency_ms"] for f in item["lookups"]], 50),
             "p99": percentile([f["latency_ms"] for f in item["lookups"]], 99)}
            for item in waves + ticks],
    }
    if closest1 is not None:
        out["closest1_checked"], out["closest1_hits"] = closest1
        out["closest1_share"] = closest1[1] / closest1[0]
    return out


def closest1(items, keys, quantize=None) -> tuple[int, int]:
    """(lookups checked, lookups that returned the truly closest peer
    first) over these waves' or ticks' lookups, by brute force."""
    checked = hits = 0
    for item in items:
        for found, target in zip(item["lookups"], item["targets"]):
            checked += 1
            hits += (bool(found["closest"]) and found["closest"][0]
                     == true_closest(keys, kad_plain.key_of(target),
                                     quantize))
    return checked, hits


def node(peers: int, n_bootstrap: int, n_probe: int, seed: int,
         random_targets, stage, latency_ms,
         learn_cap: int | None = kad_plain.LEARN_CAP, quantize=None) -> dict:
    """The whole role program from the seed. `random_targets`: the target
    rows of each random wave and then of each probe tick, in order (15
    warm-up waves of one row a normal peer; the rest are ticks of one row a
    probe). Returns the waves and ticks (kind, origins, targets, lookups,
    the tables after it and their census), and the summary."""
    keys = kad_plain.make_keys(peers, seed)
    tables = kad_plain.empty_tables(peers)
    kad_plain.seed_bootstraps(tables, keys, range(n_bootstrap))
    seeded = tables
    normals = list(range(n_bootstrap, peers - n_probe))
    probes = list(range(peers - n_probe, peers))
    own = [key_words(keys[p]) for p in normals]
    random_targets = list(random_targets)
    plan = ([("self", normals, own)] * SELF_WAVES
            + [("random", normals, t) for t in random_targets[:RANDOM_WAVES]]
            + [("probe", probes, t) for t in random_targets[RANDOM_WAVES:]])
    items = []
    for kind, origins, targets in plan:
        lookups, tables = wave(tables, keys, origins, targets, stage,
                               latency_ms, quantize, learn_cap)
        items.append({"kind": kind, "origins": origins, "targets": targets,
                      "lookups": lookups, "tables": tables,
                      "census": census(tables)})
    waves = [i for i in items if i["kind"] != "probe"]
    ticks = [i for i in items if i["kind"] == "probe"]
    checked = ([w for w in waves if w["kind"] == "random"][-1:] + ticks)
    return {"keys": keys, "seeded": seeded, "waves": waves, "ticks": ticks,
            "tables": tables,
            "summary": summary(waves, ticks, tables, n_bootstrap,
                               closest1(checked, keys, quantize))}
