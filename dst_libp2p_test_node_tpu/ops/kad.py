"""Kademlia DHT substrate: XOR-metric routing tables and FIND_NODE lookups
as fixed-shape batched array ops.

The reference's kad-dht node (nim-test-node/kad-dht/{main,core,helpers}.nim)
delegates the protocol to nim-libp2p's KadDHT: a per-node routing table of
XOR-distance buckets, iterative FIND_NODE lookups (query the alpha closest
known peers, merge their k closest entries, repeat), and three roles —
RoleBootstrap (passive anchor), RoleNormal (warmup: 5x FIND_NODE(self) +
15x FIND_NODE(random), kad-dht/core.nim:12-35), RoleProbe (FIND_NODE(random)
every 5 s forever, core.nim:38-55). The regression node reuses the same
machinery for mesh discovery (regression/kad_utils.nim:81-94).

TPU-native design (not a port):
  keys[p]           (N, W) uint32 — 128-bit node key, host-generated per seed
  rtable[p]         (N, B, K) int32 — bucket b holds peers whose XOR distance
                    to p has bit-length KEY_BITS - b; -1 = empty slot
  find_node         vmapped iterative lookup: a lax.scan over lookup rounds,
                    each round queries ALPHA closest unqueried shortlist
                    entries in parallel (round time = max RTT, per the
                    iterative-lookup wait-for-all semantics), merges their
                    K_RESP closest entries by one keyed sort (`lex_sort`).

Everything is a masked fixed-shape op: shortlists are padded to S entries,
bucket inserts route dropped entries out of bounds (`mode="drop"`), and a
big-integer XOR comparison is one `lax.sort` whose keys are the W distance
words and whose payload is what the caller wants in that order (ids, queried
flags, or an iota for the permutation) — no Python bigints, no dynamic
shapes, so the whole lookup batch jits and shards over the peer axis like
the GossipSub engine. A wave reads `rtable` and `keys` only (what it teaches
is written after its last round), so `find_node` packs every table once
(occupied slots in front, `packed_width` of them kept as the head), gathers
the head's key words once (`_slot_keys`), and a response is the row pulls of
a peer's head ids and key words, an XOR and that sort; what a table holds
past the head is sorted only where some table reaches that far.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

KEY_WORDS = 4                    # 128-bit keys; collisions ~ N^2 / 2^129
KEY_BITS = 32 * KEY_WORDS
ALPHA = 3                        # parallel queries per lookup round
K_RESP = 16                      # closest entries returned per FIND_NODE
PROC_MS = 2.0                    # per-query handler latency
LEARN_CAP = 8                    # origins a queried peer learns of one wave


def make_keys(n: int, seed: int = 0) -> np.ndarray:
    """Uniform 128-bit node keys, host-generated once per experiment (the
    reference derives keys from peer identities; only uniformity matters)."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x6AD]))
    keys = rng.integers(0, 1 << 32, size=(n, KEY_WORDS), dtype=np.uint32)
    # two peers with one key would be at one distance from every target:
    # `_closest_from_table`'s sort leaves the order of such a pair open.
    # Keys that differ in their high 64 bits differ (one sort of n words)
    high = (keys[:, 0].astype(np.uint64) << np.uint64(32)) | keys[:, 1]
    if (np.unique(high).size != n
            and np.unique(keys, axis=0).shape[0] != n):
        raise ValueError(f"make_keys({n}, seed={seed}): two peers drew the "
                         "same key; take another seed")
    return keys


def _bitlen32(x: jnp.ndarray) -> jnp.ndarray:
    """Bit length of each uint32 lane (0 for 0), via 5-step binary search."""
    x = x.astype(jnp.uint32)
    bl = jnp.zeros(x.shape, jnp.int32)
    for shift in (16, 8, 4, 2, 1):
        gt = x >= (jnp.uint32(1) << shift)
        bl = jnp.where(gt, bl + shift, bl)
        x = jnp.where(gt, x >> shift, x)
    return bl + (x > 0).astype(jnp.int32)


def xor_bitlen(d: jnp.ndarray) -> jnp.ndarray:
    """Bit length of the big-int whose words (most significant first) are the
    trailing axis. The first nonzero word strictly dominates, so a max over
    per-word contributions is exact."""
    w = jnp.arange(KEY_WORDS)
    contrib = (KEY_WORDS - 1 - w) * 32 + _bitlen32(d)
    return jnp.max(jnp.where(d > 0, contrib, 0), axis=-1).astype(jnp.int32)


def bucket_slot(d: jnp.ndarray, n_buckets: int) -> jnp.ndarray:
    """Bucket index for an XOR distance: 0 = farthest half of the keyspace.
    Distances closer than 2^(KEY_BITS - n_buckets) clamp into the last bucket
    (astronomically rare for uniform keys at any simulated N)."""
    return jnp.clip(KEY_BITS - xor_bitlen(d), 0, n_buckets - 1)


def lex_sort(words: jnp.ndarray, *payload: jnp.ndarray,
             is_stable: bool = True) -> tuple[jnp.ndarray, ...]:
    """The (..., M) `payload` arrays in ascending big-int order of `words`,
    (W, ..., M) with the most significant word first; entries that tie keep
    their order. The package's one implementation of the XOR-metric order:
    a single `lax.sort` along the last axis, the W words its keys, the
    payload carried through the same exchanges (no gather afterwards).

    `is_stable=False` leaves the order of entries that tie open and saves
    the operand XLA adds to keep it (an iota as wide as the rest, the last
    key): exact only where entries that tie in every word carry one payload,
    so that either order is the same bits (`_closest_from_table`)."""
    out = jax.lax.sort(tuple(words) + payload, dimension=-1,
                       is_stable=is_stable, num_keys=words.shape[0])
    return out[words.shape[0]:]


def lex_argsort(d: jnp.ndarray) -> jnp.ndarray:
    """Ascending stable big-int argsort over the trailing word axis of
    (..., M, W): `lex_sort` with an iota as its payload."""
    iota = jax.lax.broadcasted_iota(jnp.int32, d.shape[:-1], d.ndim - 2)
    return lex_sort(jnp.moveaxis(d, -1, 0), iota)[0]


def _dist(keys: jnp.ndarray, entries: jnp.ndarray, target_key: jnp.ndarray):
    """XOR distance of each entry to target; invalid entries (-1) -> max."""
    ek = keys[jnp.clip(entries, 0)]
    d = jnp.bitwise_xor(ek, target_key[..., None, :])
    return jnp.where((entries >= 0)[..., None], d, jnp.uint32(0xFFFFFFFF))


@struct.dataclass
class KadState:
    """Device-side DHT state (a jax pytree). keys are per-epoch constants but
    ride along so every op is self-contained.

    rt_fails/rt_retry_ms shadow the routing table slot-for-slot: the
    per-entry dial-failure count and the sim-ms deadline before the entry
    may be re-dialed (exponential backoff). Both stay all-zero unless
    `evict_failed` runs with a retry budget (max_fails > 1), so the default
    eviction path is unchanged."""

    rtable: jnp.ndarray      # (N, B, K) int32, -1 empty
    keys: jnp.ndarray        # (N, W) uint32
    alive: jnp.ndarray       # (N,) bool
    t_ms: jnp.ndarray        # () float32
    key: jnp.ndarray         # PRNG key
    queries_tx: jnp.ndarray  # (N,) int32 FIND_NODE requests sent
    queries_rx: jnp.ndarray  # (N,) int32 FIND_NODE requests served
    rt_fails: jnp.ndarray    # (N, B, K) int32 failed dials per table entry
    rt_retry_ms: jnp.ndarray  # (N, B, K) float32 backoff deadline per entry


def init_kad_state(
    n: int, n_buckets: int = 24, k_bucket: int = 16, seed: int = 0
) -> KadState:
    return KadState(
        rtable=jnp.full((n, n_buckets, k_bucket), -1, dtype=jnp.int32),
        keys=jnp.asarray(make_keys(n, seed)),
        alive=jnp.ones((n,), dtype=bool),
        t_ms=jnp.asarray(0.0, jnp.float32),
        key=jax.random.PRNGKey(seed ^ 0x6AD),
        queries_tx=jnp.zeros((n,), jnp.int32),
        queries_rx=jnp.zeros((n,), jnp.int32),
        rt_fails=jnp.zeros((n, n_buckets, k_bucket), jnp.int32),
        rt_retry_ms=jnp.zeros((n, n_buckets, k_bucket), jnp.float32),
    )


def _segment_rank(sort_key: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """rank[i] = occurrence index of sort_key[i] among equal keys (array
    order); jit-friendly analog of graph._cumcount. Returns (rank, order)."""
    m = sort_key.shape[0]
    order = jnp.argsort(sort_key, stable=True)
    sk = sort_key[order]
    is_start = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, jnp.arange(m), 0)
    )
    rank_sorted = jnp.arange(m) - start
    rank = jnp.zeros((m,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    return rank, order


def _insert_one(table: jnp.ndarray, keys: jnp.ndarray, owner: jnp.ndarray,
                cands: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Insert candidate peer ids into one owner's (B, K) table; returns the
    table and (offered, full): how many candidates were new to the table
    (valid, each once, not held) and how many of those found their bucket
    full.

    Kademlia bucket policy: keep existing entries (the reference's LRU
    preference without the ping-eviction probe), append new distinct entries
    into free slots, drop the rest. Pure fixed-shape: compute each candidate's
    target (bucket, position) and scatter with out-of-bounds drop."""
    b, k = table.shape
    valid = (cands >= 0) & (cands != owner)
    d = _dist(keys, cands, keys[owner])
    slot = bucket_slot(d, b)

    # drop candidates already present in their target bucket
    in_bucket = table[slot]                      # (E, K)
    dup_existing = (in_bucket == cands[:, None]).any(axis=-1)
    # drop repeats within the batch (keep first occurrence)
    eq = cands[:, None] == cands[None, :]
    dup_within = (jnp.tril(eq, k=-1)).any(axis=-1)
    keep = valid & ~dup_existing & ~dup_within

    occupancy = (table >= 0).sum(axis=-1)        # (B,)
    rank, _ = _segment_rank(jnp.where(keep, slot, b).astype(jnp.int32))
    pos = occupancy[slot] + rank
    ok = keep & (pos < k)
    new = table.at[
        jnp.where(ok, slot, b), jnp.where(ok, pos, 0)
    ].set(jnp.where(ok, cands, -1).astype(table.dtype), mode="drop")
    return new, jnp.stack([keep.sum(), (keep & ~ok).sum()])


def _insert_rows(state: KadState, owners: jnp.ndarray, cands: jnp.ndarray
                 ) -> tuple[KadState, jnp.ndarray]:
    """`rtable_insert` and its (offered, full) summed over the owners."""
    new_rows, counts = jax.vmap(_insert_one, in_axes=(0, None, 0, 0))(
        state.rtable[owners], state.keys, owners, cands
    )
    return (state.replace(rtable=state.rtable.at[owners].set(new_rows)),
            counts.sum(axis=0))


@jax.jit
def rtable_insert(state: KadState, owners: jnp.ndarray, cands: jnp.ndarray
                  ) -> KadState:
    """Batch insert: owners (M,) each learn cands (M, E). Owner rows must be
    distinct within a batch (callers vmap over distinct lookup origins)."""
    return _insert_rows(state, owners, cands)[0]


def packed_width(n: int, n_buckets: int, k_bucket: int) -> int:
    """How many slots of a packed table (occupied slots in front) a FIND_NODE
    response sorts unconditionally: K * (ceil(log2(n / K)) + 3) rounded up to
    a multiple of 128 (a sort's tile), or B*K where that is no narrower (no
    packing then). From the shapes alone.

    Why it holds what a table can hold: bucket b of a peer takes only peers
    that share exactly b leading key bits with it, n / 2^(b+1) of n uniform
    keys, and at most K of them. The log2(n / K) buckets in which that
    expectation exceeds K fill to K; the deeper ones expect K/2, K/4, ...,
    under K together. So a table that knows everybody holds about
    K * (log2(n / K) + 1) peers (10,000 uniform keys, everybody known: mean
    163, max 175 of 384), the rule keeps two buckets of K beyond that and the
    rounding more: 256 at 10,000 and at 100,000 peers (expected 164 and 217),
    128 from 64 to 512. It is a width, not a promise: `find_node` reads on
    the tables it is handed whether every one fits (`LookupResult.packed`)
    and sorts the rest where one does not."""
    full = n_buckets * k_bucket
    levels = math.ceil(math.log2(max(n, k_bucket) / k_bucket)) + 3
    width = -(-k_bucket * levels // 128) * 128
    return width if width < full else full


def _slot_keys(keys: jnp.ndarray, slots: jnp.ndarray) -> jnp.ndarray:
    """The key words of the (N, M) id slots, (W, N, M) with the word axis
    first (a peer's words are W contiguous rows); what an empty slot reads
    is masked where it is used."""
    return jnp.moveaxis(keys[jnp.clip(slots, 0)], -1, 0)


def _closest_from_table(table: jnp.ndarray, keys: jnp.ndarray,
                        target_key: jnp.ndarray, k_out: int,
                        table_keys: jnp.ndarray | None = None) -> jnp.ndarray:
    """The k_out closest entries of one (B, K) table (or any id list: it is
    flattened) to target, closest first, -1 padded — a FIND_NODE response
    (the reference returns the k nearest from the routing table). One
    `lex_sort` over the slots' XOR distances that carries the ids; an empty
    slot's distance is all ones, so it sorts last. `table_keys`: the (W, M)
    key words of the slots where the caller has gathered them already
    (`_slot_keys`), else they are gathered from `keys` here.

    The sort is not stable, and exact all the same: entries at one distance
    are empty slots (all -1) or one id held twice (a sybil directory may),
    the same bits in either order, because two peers never have one key
    (`make_keys` raises) and a peer at distance 2^128 - 1, where an empty
    slot sorts, is one draw in 2^128."""
    flat = table.reshape(-1)
    if table_keys is None:
        table_keys = keys[jnp.clip(flat, 0)].T
    d = jnp.where(flat >= 0, jnp.bitwise_xor(table_keys, target_key[:, None]),
                  jnp.uint32(0xFFFFFFFF))
    return lex_sort(d, flat, is_stable=False)[0][:k_out]


def _teach_events(state: KadState, flat_peers: jnp.ndarray,
                  flat_origin: jnp.ndarray, extra_ok=None,
                  e_cap: int | None = LEARN_CAP
                  ) -> tuple[KadState, jnp.ndarray]:
    """Every learner `flat_peers[e]` learns the candidate `flat_origin[e]`,
    in the order of the events and under `_insert_one`'s bucket policy (not
    itself, each candidate once, not one its bucket holds, appended while
    the bucket has room) — the shared scatter behind find_node's
    query-learning pass and connect_found's dial-backs. `e_cap`: only a
    learner's first e_cap events count (None: all of them).

    Flat over the M events, with no per-learner table of candidates: a
    vmapped `_insert_one` compares every pair of a row's candidates, and at
    a row of 384 the program of one 10,000-peer wave took twenty minutes to
    compile for a v5e where this one takes under one; what it costs here
    is three sorts of M keys."""
    n, b, k = state.rtable.shape
    m = flat_peers.shape[0]
    rank, _ = _segment_rank(jnp.where(flat_peers >= 0, flat_peers, n))
    ok = flat_peers >= 0
    if e_cap is not None:
        ok = ok & (rank < e_cap)
    if extra_ok is not None:
        ok = ok & extra_ok
    valid = ok & (flat_origin >= 0) & (flat_origin != flat_peers)
    learner = jnp.where(valid, flat_peers, 0)
    cand = jnp.where(valid, flat_origin, 0)
    slot = bucket_slot(
        jnp.bitwise_xor(state.keys[cand], state.keys[learner]), b)
    held = (state.rtable[learner, slot] == cand[:, None]).any(axis=-1)
    # a (learner, candidate) pair that came before: its first event stands
    group = jnp.where(valid, learner, n)
    first = jnp.lexsort((jnp.arange(m), cand, group))
    sl, sc = group[first], cand[first]
    again = jnp.zeros((m,), bool).at[first].set(jnp.concatenate(
        [jnp.zeros((1,), bool), (sl[1:] == sl[:-1]) & (sc[1:] == sc[:-1])]))
    keep = valid & ~held & ~again
    # position in the bucket: what it holds, then the kept events in order
    in_bucket, _ = _segment_rank(
        jnp.where(keep, learner * b + slot, n * b).astype(jnp.int32))
    occupancy = (state.rtable >= 0).sum(axis=-1)
    pos = occupancy[learner, slot] + in_bucket
    put = keep & (pos < k)
    return state.replace(rtable=state.rtable.at[
        jnp.where(put, learner, n), jnp.where(put, slot, 0),
        jnp.where(put, pos, 0)
    ].set(cand.astype(state.rtable.dtype), mode="drop")), jnp.stack(
        [keep.sum(), (keep & ~put).sum()])


def _teach_learners(state: KadState, flat_peers: jnp.ndarray,
                    flat_origin: jnp.ndarray, extra_ok=None,
                    e_cap: int | None = LEARN_CAP) -> KadState:
    """`_teach_events` without its counts."""
    return _teach_events(state, flat_peers, flat_origin, extra_ok, e_cap)[0]


def _pick_alpha(sl: jnp.ndarray, rank: jnp.ndarray, cand: jnp.ndarray,
                s: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Select the ALPHA closest candidate shortlist entries by distance rank
    and gather their ids into a dense (Q, ALPHA) block (-1 padded). Shared
    by find_node and servicedisco.lookup so the two walks cannot diverge."""
    pick_prio = jnp.where(cand, rank, s + 1)
    pick = (jnp.argsort(jnp.argsort(pick_prio, axis=-1), axis=-1)
            < ALPHA) & cand
    p_order = jnp.argsort(~pick, axis=-1, stable=True)[:, :ALPHA]
    p_ids = jnp.take_along_axis(jnp.where(pick, sl, -1), p_order, axis=-1)
    return pick, p_ids


def _merge_shortlist(keys: jnp.ndarray, sl: jnp.ndarray, queried: jnp.ndarray,
                     pick: jnp.ndarray, resp: jnp.ndarray,
                     targets: jnp.ndarray, s: int
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Merge FIND_NODE responses into the shortlist: concat, dedup keeping
    the queried copy of an id (one sort of the key id*2 + freshness, from
    which id and flag are read back; ids < 2^30 so int32 is safe), then one
    `lex_sort` by XOR distance that carries the ids and the queried flags;
    keep the closest S. Shared by find_node and servicedisco.lookup."""
    q = sl.shape[0]
    merged = jnp.concatenate([sl, resp.reshape(q, -1)], axis=-1)
    mq = jnp.concatenate(
        [queried | pick, jnp.zeros((q, merged.shape[1] - s), bool)], axis=-1
    )
    # the key holds both what it sorts: id = key >> 1, queried = even
    mkey = jnp.sort(merged * 2 + jnp.where(mq, 0, 1), axis=-1, stable=False)
    msort, qsort = mkey >> 1, (mkey & 1) == 0
    dup = jnp.concatenate(
        [jnp.zeros((q, 1), bool), msort[:, 1:] == msort[:, :-1]], axis=-1
    )
    msort = jnp.where(dup | (msort < 0), -1, msort)
    md = _dist(keys, msort, targets)
    sl_new, q_new = lex_sort(jnp.moveaxis(md, -1, 0), msort, qsort & ~dup)
    return sl_new[:, :s], q_new[:, :s]


@struct.dataclass
class LookupResult:
    closest: jnp.ndarray     # (Q, K_RESP) int32 final shortlist heads
    hops: jnp.ndarray        # (Q,) int32 rounds until convergence
    latency_ms: jnp.ndarray  # (Q,) float32 wall time of the lookup
    queried: jnp.ndarray     # (Q, rounds*ALPHA) int32 query log (-1 padded)
    n_queries: jnp.ndarray   # (Q,) int32 total FIND_NODE requests
    # (2,) int32: the candidates the wave offered to the tables (the final
    # shortlists to the origins, the origins to the peers they queried:
    # valid, each once, not held already) and how many found their bucket
    # full
    learn_counts: jnp.ndarray
    # () bool: every table held at most `packed_width` peers, so every
    # response of this call was sorted from the packed head alone
    packed: jnp.ndarray


def _find_node_impl(
    state: KadState,
    origins: jnp.ndarray,
    targets: jnp.ndarray,
    stage: jnp.ndarray,
    lat_ms: jnp.ndarray,
    rounds: int,
    shortlist: int,
    attacker: jnp.ndarray | None = None,
    poison0: jnp.ndarray | None = None,
    learn_cap: int | None = LEARN_CAP,
) -> tuple[LookupResult, KadState]:
    """Shared lookup body behind find_node and the DHT adversary's attacked
    lookup (ops/dht_adversary.find_node_attacked). The poison hook is
    python-level: with attacker/poison0 None, the traced program is
    IDENTICAL to the original find_node — the benign path never pays for
    the attack machinery. Armed, every response from an attacker-controlled
    peer is replaced wholesale by `poison0` (the (Q, K_RESP) sybil-directory
    response per target): a lookup eclipse denies honest entries entirely
    instead of merely biasing the merge."""
    n, n_buckets, k_bucket = state.rtable.shape
    q = origins.shape[0]
    s = shortlist

    o_stage = stage[origins]
    flat_tables = state.rtable.reshape(n, -1)
    width = packed_width(n, n_buckets, k_bucket)

    with jax.named_scope("seed"):
        # the tables and the keys are read-only until the learning pass, so
        # once a wave, not a response: every table packed (occupied slots in
        # front, in any order: their distances differ), the key words of the
        # head's slots gathered, and whether every table fits its head read
        # off the first column past it
        if width < flat_tables.shape[1]:
            packed = -jnp.sort(-flat_tables, axis=-1, stable=False)
            head, tail = packed[:, :width], packed[:, width:]
            fits = (tail[:, 0] < 0).all()
        else:
            head, tail, fits = flat_tables, None, jnp.ones((), bool)
        head_keys = _slot_keys(state.keys, head)

    def table_closest(peer, target_key, k_out):
        """The k_out closest of `peer`'s table: its head's ids and each of
        their W key-word rows are one contiguous row pull. Where some table
        of the wave reaches past its head, the head's closest and the tail's
        slots are sorted once more together: the same entries, whatever a
        table holds. That branch gathers its key words a response (a tail
        hoisted beside the head's would be an operand of the conditional,
        and the branch that is taken then paid 1.9 ms a call for zeros in
        its place, on the chip)."""
        near = _closest_from_table(
            head[peer], state.keys, target_key, k_out,
            table_keys=jnp.stack([words[peer] for words in head_keys]))
        if tail is None:
            return near

        def with_tail():
            both = jnp.concatenate([near, tail[peer]])
            # a word at a time: rows of W words gathered here would pad
            # 32-fold, 2.2 GB of the program's temporaries at 10,000 peers
            return _closest_from_table(
                both, state.keys, target_key, k_out,
                table_keys=jnp.stack(
                    [word[jnp.clip(both, 0)] for word in state.keys.T]))

        return jax.lax.cond(fits, lambda: near, with_tail)

    with jax.named_scope("seed"):
        # seed shortlist from the origin's own table
        sl0 = jax.vmap(lambda o, t: table_closest(o, t, s))(origins, targets)
    queried0 = jnp.zeros((q, s), bool)

    def response(peer, target_key):
        """FIND_NODE response of `peer` (masked if dead)."""
        return jnp.where(state.alive[peer],
                         table_closest(peer, target_key, K_RESP), -1)

    def round_body(carry, _):
        sl, queried, t_acc, hops, nq = carry
        with jax.named_scope("order"):
            # a node never FIND_NODEs itself over the network, so the
            # origin's own id (distance 0 on self-lookups) is not a query
            # candidate
            cand = ((sl >= 0) & ~queried & state.alive[jnp.clip(sl, 0)]
                    & (sl != origins[:, None]))
            # classic termination: the lookup is done once every entry in
            # the top-K_RESP head of the shortlist has been queried. The
            # shortlist is in distance order as _closest_from_table and
            # _merge_shortlist return it (only empty slots tie, and they
            # come last), so an entry's distance rank is its position
            head_unqueried = cand[:, :K_RESP].any(axis=-1)
            cand = cand & head_unqueried[:, None]
            # pick the ALPHA closest unqueried
            pick, p_ids = _pick_alpha(sl, jnp.arange(s), cand, s)
            any_pick = pick.any(axis=-1)

        with jax.named_scope("response"):
            # one flat batch of Q*ALPHA tables: the sort runs over rows of
            # a (Q*ALPHA, B*K) array, whose tiles are full
            resp = jax.vmap(response)(
                jnp.clip(p_ids, 0).reshape(-1),
                jnp.repeat(targets, ALPHA, axis=0),
            ).reshape(q, ALPHA, K_RESP)
            resp = jnp.where((p_ids >= 0)[..., None], resp, -1)
            if attacker is not None:
                # lookup eclipse: a live attacker responder answers with the
                # sybil directory's closest entries instead of its table
                is_att = ((p_ids >= 0) & attacker[jnp.clip(p_ids, 0)]
                          & state.alive[jnp.clip(p_ids, 0)])
                resp = jnp.where(is_att[..., None], poison0[:, None, :], resp)

        # round RTT = max over the parallel queries (iterative lookup waits)
        rtt = 2.0 * lat_ms[o_stage[:, None], stage[jnp.clip(p_ids, 0)]] + PROC_MS
        rtt = jnp.where(p_ids >= 0, rtt, 0.0)
        round_ms = rtt.max(axis=-1)

        with jax.named_scope("merge"):
            sl_new, q_new = _merge_shortlist(
                state.keys, sl, queried, pick, resp, targets, s)

        improved = jnp.any(sl_new != sl, axis=-1) & any_pick
        t_acc = t_acc + jnp.where(any_pick, round_ms, 0.0)
        hops = hops + jnp.where(improved, 1, 0)
        nq = nq + (p_ids >= 0).sum(axis=-1)
        return (sl_new, q_new, t_acc, hops, nq), p_ids

    zeros_q = jnp.zeros((q,), jnp.float32)
    (sl, queried, t_acc, hops, nq), picked_seq = jax.lax.scan(
        round_body,
        (sl0, queried0, zeros_q, jnp.zeros((q,), jnp.int32),
         jnp.zeros((q,), jnp.int32)),
        None,
        length=rounds,
    )
    picked_seq = jnp.moveaxis(picked_seq, 0, 1).reshape(q, -1)  # (Q, R*ALPHA)

    # ---- learning + accounting -------------------------------------------
    with jax.named_scope("learn"):
        # origin learns its final shortlist (every response it accepted)
        state, own_counts = _insert_rows(state, origins, sl)
        # each queried peer learns the origins that queried it: group the
        # (learner, origin) events by learner (segment ranks,
        # capacity-bounded) so parallel lookups hitting the same responder
        # all land
        flat_peers = picked_seq.reshape(-1)
        flat_origin = jnp.broadcast_to(
            origins[:, None], picked_seq.shape).reshape(-1)
        state, taught_counts = _teach_events(state, flat_peers, flat_origin,
                                             e_cap=learn_cap)
        learn_counts = own_counts + taught_counts

        served = jnp.zeros((n,), jnp.int32).at[
            jnp.where(flat_peers >= 0, flat_peers, n)
        ].add(1, mode="drop")
        state = state.replace(
            queries_tx=state.queries_tx.at[origins].add(nq),
            queries_rx=state.queries_rx + served,
        )

    result = LookupResult(
        closest=sl[:, :K_RESP], hops=hops, latency_ms=t_acc,
        queried=picked_seq, n_queries=nq,
        learn_counts=learn_counts, packed=fits,
    )
    return result, state


@partial(jax.jit, static_argnames=("rounds", "shortlist", "learn_cap"))
def find_node(
    state: KadState,
    origins: jnp.ndarray,     # (Q,) int32 distinct querying peers
    targets: jnp.ndarray,     # (Q, W) uint32 target keys
    stage: jnp.ndarray,       # (N,) int32 topology stage per peer
    lat_ms: jnp.ndarray,      # (S+1, S+1) float32 stage-pair latency
    rounds: int = 6,
    shortlist: int = 32,
    learn_cap: int | None = LEARN_CAP,
) -> tuple[LookupResult, KadState]:
    """Batched iterative FIND_NODE (kad-dht/core.nim warmup/probe primitive).

    Each origin walks the XOR metric toward its target: query the ALPHA
    closest unqueried shortlist peers, merge their K_RESP closest entries,
    repeat `rounds` times (enough for uniform keys at any simulated N: each
    round roughly halves the remaining distance). Per-round wall time is the
    max RTT of the parallel queries, accumulated only while the shortlist
    still improves — matching the iterative lookup's termination ("no peer
    closer than the best seen" => stop counting).

    Returns per-origin results plus state with updated tables (the origin
    learns its final shortlist; a queried peer learns the origins that asked
    it, the first `learn_cap` of the wave in the order of origin, round and
    pick, or with None all of them) and counters.
    """
    return _find_node_impl(state, origins, targets, stage, lat_ms,
                           rounds, shortlist, learn_cap=learn_cap)


@partial(jax.jit, static_argnames=("max_fails", "backoff_base_ms"))
def evict_failed(state: KadState, origins: jnp.ndarray,
                 found: jnp.ndarray, max_fails: int = 1,
                 backoff_base_ms: float = 0.0) -> KadState:
    """DISCOVERY=extended (KademliaDiscovery) eviction: the discovery layer
    exists to hand the application CONNECTABLE peers, so after the
    end-of-lookup dial-out to the FOUND peers, every dial that fails (a
    dead shortlist entry — queried peers are alive by construction, the
    lookup's candidate filter sees to that) drops the entry from the
    dialer's routing table. Plain KadDHT mode keeps the stale entry (the
    LRU-keep-without-ping-eviction policy of rtable_insert). Buckets are
    re-packed left so the append-position arithmetic of _insert_one stays
    valid.

    Retry budget (the supervisor's backoff idiom, runtime/campaign.py):
    with `max_fails` > 1 a failed dial does not evict immediately — the
    entry's per-slot failure counter increments and the entry goes under
    exponential backoff (`backoff_base_ms * 2**(fails-1)` past state.t_ms);
    while under backoff a repeated failure is NOT re-counted (the dial was
    never retried). Eviction fires only once the counter reaches
    `max_fails`. A successful dial resets the counter and the deadline.
    The default (max_fails=1) reproduces the original immediate-eviction
    tables exactly — an attack cannot get free evictions from one lossy
    round unless the operator opted out of retries.

    `found`: (Q, K) shortlist heads each origin dials
    (LookupResult.closest)."""
    dead = ~state.alive
    t = state.t_ms

    def evict_one(table, fails, retry, f_ids):
        bad_ids = jnp.where((f_ids >= 0) & dead[jnp.clip(f_ids, 0)],
                            f_ids, -2)
        is_bad = (table[..., None] == bad_ids).any(axis=-1)
        good_ids = jnp.where((f_ids >= 0) & ~dead[jnp.clip(f_ids, 0)],
                             f_ids, -2)
        is_good = (table[..., None] == good_ids).any(axis=-1)
        # entries under backoff were not re-dialed this wave: no new count
        fail_event = is_bad & ~(retry > t)
        fails = jnp.where(fail_event, fails + 1, fails)
        fails = jnp.where(is_good, 0, fails)
        evict = fail_event & (fails >= max_fails)
        retry = jnp.where(
            fail_event & ~evict,
            t + backoff_base_ms * jnp.exp2((fails - 1).astype(jnp.float32)),
            retry)
        retry = jnp.where(is_good, 0.0, retry)
        marked = jnp.where(evict, -1, table)
        fails = jnp.where(evict, 0, fails)
        retry = jnp.where(evict, 0.0, retry)
        # compact each bucket: keep entries left-packed, holes to the right
        # (the shadow arrays repack with the table so slots stay aligned)
        order = jnp.argsort(marked < 0, axis=-1, stable=True)
        return (jnp.take_along_axis(marked, order, axis=-1),
                jnp.take_along_axis(fails, order, axis=-1),
                jnp.take_along_axis(retry, order, axis=-1))

    new_rows, new_fails, new_retry = jax.vmap(evict_one)(
        state.rtable[origins], state.rt_fails[origins],
        state.rt_retry_ms[origins], found)
    return state.replace(
        rtable=state.rtable.at[origins].set(new_rows),
        rt_fails=state.rt_fails.at[origins].set(new_fails),
        rt_retry_ms=state.rt_retry_ms.at[origins].set(new_retry),
    )


@jax.jit
def connect_found(state: KadState, origins: jnp.ndarray,
                  found: jnp.ndarray) -> KadState:
    """DISCOVERY=extended (KademliaDiscovery, kad-dht/helpers.nim:48-57)
    dial-backs: after a lookup the origin connects to the peers it found,
    so every live entry of the final shortlist learns the origin. Plain
    KadDHT mode only teaches the origin to the peers it QUERIED
    (find_node's learning pass).

    `found`: (Q, K) shortlist heads per origin (LookupResult.closest)."""
    flat_peers = found.reshape(-1)
    flat_origin = jnp.broadcast_to(
        origins[:, None], found.shape).reshape(-1)
    # dead peers answer no dial; self-dials don't happen
    extra_ok = ((flat_peers != flat_origin)
                & state.alive[jnp.clip(flat_peers, 0)])
    return _teach_learners(state, flat_peers, flat_origin, extra_ok)


@jax.jit
def seed_bootstraps(state: KadState, bootstraps: jnp.ndarray) -> KadState:
    """Every peer seeds its table with the bootstrap anchors and every
    bootstrap learns every peer — the array form of connectToBootstraps +
    the bootstrap's passive accumulation (kad-dht/helpers.nim:62-91,
    regression/kad_utils.nim:88-94)."""
    n = state.rtable.shape[0]
    all_peers = jnp.arange(n, dtype=jnp.int32)
    cands = jnp.broadcast_to(bootstraps[None, :], (n, bootstraps.shape[0]))
    state = rtable_insert(state, all_peers, cands)
    # bootstraps learn everyone (batched over bootstraps; N candidates each)
    nb = bootstraps.shape[0]
    state = rtable_insert(
        state, bootstraps, jnp.broadcast_to(all_peers[None, :], (nb, n))
    )
    return state


def rtable_census(state: KadState) -> jnp.ndarray:
    """Per-peer routing-table population — the reference's warmup census
    (kad-dht/core.nim:17-22 'Kad routing table, peers = rtPeers')."""
    return (state.rtable >= 0).sum(axis=(-1, -2)).astype(jnp.int32)


def random_targets(key: jnp.ndarray, q: int) -> jnp.ndarray:
    """Random lookup targets — getRandomPeerId (kad-dht/helpers.nim:10-12):
    uniform keys that (almost surely) match no live node."""
    return jax.random.bits(key, (q, KEY_WORDS), dtype=jnp.uint32)


@jax.jit
def closest_peer(keys: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """(Q,) int32: for each target the peer whose key is closest to it under
    the XOR metric among ALL N keys, by brute force and no routing table:
    the lexicographic minimum taken a word at a time, each a masked
    min-reduction over (Q, N). Peers with one key tie; the lowest id wins.
    What a lookup should return first (the kad-dht node's `closest1_share`)."""
    live = jnp.ones((targets.shape[0], keys.shape[0]), bool)
    for w in range(KEY_WORDS):
        d = jnp.where(live, keys[None, :, w] ^ targets[:, None, w],
                      jnp.uint32(0xFFFFFFFF))
        live = live & (d == d.min(axis=1, keepdims=True))
    return jnp.argmax(live, axis=1).astype(jnp.int32)


def true_closest(keys: np.ndarray, target: np.ndarray, k: int = 1) -> np.ndarray:
    """Host-side brute-force ground truth for tests: the k globally closest
    node indices to target under the XOR metric."""
    ints = np.zeros(keys.shape[0], dtype=object)
    t_int = 0
    for w in range(KEY_WORDS):
        ints = ints * (1 << 32) + keys[:, w].astype(object)
        t_int = t_int * (1 << 32) + int(target[w])
    d = np.array([x ^ t_int for x in ints], dtype=object)
    return np.argsort(d, kind="stable")[:k]
