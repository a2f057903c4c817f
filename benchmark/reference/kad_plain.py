"""The plain Kademlia reference: the regression node's kad-dht discovery,
wave by wave, and the dials and connections made from its routing tables.

numpy (for the seeded draws, which are part of the deployment) and Python
integers; no JAX and nothing of the program. Keys are 128-bit Python
integers, XOR distance is `a ^ b`, a routing table is a list of buckets and
a bucket a list of peer ids in the order they were learned. Every rule
below is written down from `dst_libp2p_test_node_tpu/ops/kad.py`,
`runtime/regression_runtime.discovery_dials` and
`ops/graph.build_connection_graph`, which the program runs as fixed-shape
array operations; where a rule departs from the Kademlia paper (Maymounkov
and Mazieres 2002) the departure is noted as **departs**.

The rules.

Keys (`make_keys`). Peer p's key is four uint32 words, most significant
first, row p of `default_rng(SeedSequence([seed, 0x6AD])).integers(0, 2**32,
(N, 4), uint32)`.

Buckets (`bucket_of`). A table has 24 buckets of 16. A peer at XOR distance
d from the owner goes to bucket `128 - bit_length(d)`: bucket 0 is the far
half of the key space. **Departs:** distances under 2**105 share the last
bucket (the paper has one bucket a bit; at any size that is simulated the
near buckets are empty).

Learning (`learn`). An owner learns candidates in the order given: not
itself, not a peer it has already been given in this batch, not a peer
already in the bucket; appended if the bucket has room, dropped if it is
full. **Departs:** a full bucket keeps what it has and never pings its
least-recently-seen entry, and a peer seen again is not moved to the tail.

Seeding (`seed_bootstraps`). Every peer learns the bootstraps; then every
bootstrap learns every peer, 0 to N-1 in order (so a bootstrap's bucket
holds the 16 lowest ids that fall into it).

A FIND_NODE response (`closest`): the 16 entries of the responder's table
closest to the target, closest first. Tables are read as they stood at the
START of the wave: **departs**, all lookups of a wave run side by side and
none sees what another taught.

The iterative lookup (`lookup`), ALPHA 3, shortlist 32, at most 6 rounds:
the shortlist starts as the 32 closest entries of the origin's own table. A
round picks the 3 closest entries that are neither queried nor the origin
itself, but only while one of the 16 closest is unqueried (the classic
termination); queries them; merges their responses; keeps the 32 closest,
each peer once, a queried peer staying queried. A peer that falls off the
shortlist and is returned again counts as new. The round takes as long as
its slowest query, `2 * latency + 2 ms` (PROC_MS), and counts as a hop if
it changed the shortlist. **Departs:** six rounds at most, no query fails
or times out, and the lookup waits for all of a round's queries.

What a wave teaches (`wave`), after all its lookups: each origin learns its
final shortlist, closest first (**departs:** not every entry of every
response). Then every queried peer learns who queried it, in the order of
origin, round and pick: all of a wave (`learn_cap` None, the regression
node's path, and the paper's rule), or at most the first `learn_cap`
(**departs:** 8 by default, on the program's other paths).

Dials (`dials`). `default_rng(seed ^ 0x4E6).random((N, 384))` gives every
slot of every table, bucket-major, a number. A peer dials the `connect_to`
entries of its table with the smallest numbers, in ascending order of them;
with fewer entries it dials them all, then the bootstraps, then ring
neighbours p+1, p+2, ..., each peer once and never itself.

Connections (`connections`). The dials in (peer, dial) order, each unordered
pair once (the first), shuffled by `default_rng(seed + 0x5EED).
permutation`. A peer's slots are its edges as dialer in that order, then its
edges as dialled. An edge both of whose slots lie under the capacity stands,
and the others are turned away (a full peer rejects a dial); `conns[p]` is
what stands, in slot order.

The control (benchmark/control.py) is this reference computed one precision
lower, put in the program's place: `quantize` rounds every XOR distance (as
a float) and every time to bfloat16, so that peers whose distances agree in
their first eight bits are ordered by where they stood and not by distance.
"""

from __future__ import annotations

import numpy as np

KEY_BITS = 128
N_BUCKETS = 24
K_BUCKET = 16
ALPHA = 3
K_RESP = 16
SHORTLIST = 32
ROUNDS = 6
PROC_MS = 2.0
LEARN_CAP = 8


def key_of(words) -> int:
    """Four uint32 words, most significant first, as one integer."""
    out = 0
    for w in words:
        out = (out << 32) | int(w)
    return out


def make_keys(n: int, seed: int) -> list[int]:
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x6AD]))
    words = rng.integers(0, 1 << 32, size=(n, 4), dtype=np.uint32)
    return [key_of(row) for row in words]


def bucket_of(distance: int) -> int:
    return min(KEY_BITS - distance.bit_length(), N_BUCKETS - 1)


def empty_tables(n: int) -> list[list[list[int]]]:
    return [[[] for _ in range(N_BUCKETS)] for _ in range(n)]


def tables_from_array(rtable) -> list[list[list[int]]]:
    """(N, 24, 16) with -1 in empty slots -> lists (entries are packed to
    the left of a bucket, since learning only appends)."""
    return [[[int(x) for x in bucket if x >= 0] for bucket in table]
            for table in np.asarray(rtable)]


def tables_to_array(tables) -> np.ndarray:
    out = np.full((len(tables), N_BUCKETS, K_BUCKET), -1, np.int32)
    for p, table in enumerate(tables):
        for b, bucket in enumerate(table):
            out[p, b, :len(bucket)] = bucket
    return out


def learn(table, keys, owner: int, candidates) -> None:
    given = set()
    for c in candidates:
        c = int(c)
        if c < 0 or c == owner or c in given:
            continue
        given.add(c)
        bucket = table[bucket_of(keys[c] ^ keys[owner])]
        if c not in bucket and len(bucket) < K_BUCKET:
            bucket.append(c)


def seed_bootstraps(tables, keys, bootstraps) -> None:
    for p, table in enumerate(tables):
        learn(table, keys, p, bootstraps)
    for b in bootstraps:
        learn(tables[b], keys, b, range(len(tables)))


def _by_distance(peers, keys, target: int, quantize):
    """`peers` closest first; peers at one (quantized) distance keep the
    order they came in."""
    if quantize is None:
        return sorted(peers, key=lambda x: keys[x] ^ target)
    # scaled under float32's largest, which a distance of 2**128 - 1 passes
    return sorted(peers, key=lambda x: quantize(
        float(keys[x] ^ target) / 2.0 ** 64))


def closest(entries, keys, target: int, count: int, quantize=None):
    return _by_distance(entries, keys, target, quantize)[:count]


def lookup(origin: int, target: int, entries, keys, rtt_ms, quantize=None):
    """One iterative FIND_NODE. `entries[p]`: p's table at the start of the
    wave, flat (bucket-major); `rtt_ms(a, b)`: one query's round trip.
    Returns (final shortlist, hops, queries sent, latency in ms, the peers
    queried in order)."""
    q = quantize if quantize is not None else (lambda x: x)
    shortlist = closest(entries[origin], keys, target, SHORTLIST, quantize)
    queried: set[int] = set()
    hops = sent = 0
    took = 0.0
    asked = []
    for _ in range(ROUNDS):
        open_at = [i for i, p in enumerate(shortlist)
                   if p not in queried and p != origin]
        if not any(i < K_RESP for i in open_at):
            break       # every peer of the head is queried: nothing to ask
        picks = [shortlist[i] for i in open_at[:ALPHA]]
        merged = list(shortlist)
        for p in picks:
            merged += [x for x in closest(entries[p], keys, target, K_RESP,
                                          quantize) if x not in merged]
        queried.update(picks)
        after = _by_distance(merged, keys, target, quantize)[:SHORTLIST]
        queried &= set(after)
        took = q(took + max(q(rtt_ms(origin, p)) for p in picks))
        hops += after != shortlist
        sent += len(picks)
        asked += picks
        shortlist = after
    return shortlist, hops, sent, took, asked


def wave(tables, keys, origins, targets, stage, latency_ms, quantize=None,
         learn_cap: int | None = LEARN_CAP):
    """One wave of lookups, origin i for target i, on `tables` as they
    stand; returns the lookups and the tables after what the wave taught.
    `tables` is not changed."""
    entries = [[x for bucket in table for x in bucket] for table in tables]

    def rtt_ms(a, b):
        return 2.0 * float(latency_ms[stage[a]][stage[b]]) + PROC_MS

    lookups = []
    learners: dict[int, list[int]] = {}
    for origin, target in zip(origins, targets):
        origin = int(origin)
        shortlist, hops, sent, took, asked = lookup(
            origin, key_of(target), entries, keys, rtt_ms, quantize)
        lookups.append({"origin": origin, "closest": shortlist[:K_RESP],
                        "shortlist": shortlist, "hops": hops,
                        "n_queries": sent, "latency_ms": took})
        for p in asked:
            learners.setdefault(p, []).append(origin)
    after = [[list(bucket) for bucket in table] for table in tables]
    for found in lookups:
        learn(after[found["origin"]], keys, found["origin"],
              found["shortlist"])
    for p, who in learners.items():
        learn(after[p], keys, p, who[:learn_cap])
    return lookups, after


def dials(tables, connect_to: int, bootstraps, seed: int,
          quantize=None) -> list[list[int]]:
    n = len(tables)
    u = np.random.default_rng(seed ^ 0x4E6).random(
        (n, N_BUCKETS * K_BUCKET))
    q = quantize if quantize is not None else (lambda x: x)
    out = []
    for p, table in enumerate(tables):
        numbered = [(q(float(u[p, b * K_BUCKET + i])), x)
                    for b, bucket in enumerate(table)
                    for i, x in enumerate(bucket) if x != p]
        numbered.sort(key=lambda pair: pair[0])
        row = [x for _, x in numbered[:connect_to]]
        ring = ((p + 1 + i) % n for i in range(connect_to))
        for x in [*sorted(int(b) for b in bootstraps), *ring]:
            if len(row) < connect_to and x != p and x not in row:
                row.append(x)
        out.append(row)
    return out


def connections(dialled, seed: int, capacity: int) -> np.ndarray:
    """conns (N, capacity), -1 in empty slots."""
    n = len(dialled)
    seen, edges = set(), []
    for p, row in enumerate(dialled):
        for x in row:
            pair = (min(p, x), max(p, x))
            if pair not in seen:
                seen.add(pair)
                edges.append((p, x))
    order = np.random.default_rng(seed + 0x5EED).permutation(len(edges))
    edges = [edges[i] for i in order]
    # a peer's slots: its edges as dialer in this order, then as dialled
    as_dialer = [0] * n
    for src, _ in edges:
        as_dialer[src] += 1
    slot_src, slot_dst = [0] * n, list(as_dialer)
    stands = []
    for src, dst in edges:
        stands.append(slot_src[src] < capacity and slot_dst[dst] < capacity)
        slot_src[src] += 1
        slot_dst[dst] += 1
    rows: list[list[int]] = [[] for _ in range(n)]
    later: list[list[int]] = [[] for _ in range(n)]
    for (src, dst), ok in zip(edges, stands):
        if ok:
            rows[src].append(dst)
            later[dst].append(src)
    conns = np.full((n, capacity), -1, np.int32)
    for p in range(n):
        row = rows[p] + later[p]
        conns[p, :len(row)] = row
    return conns
