"""Kademlia substrate tests: XOR-metric math, routing-table invariants,
lookup correctness, and the role-program runtime (reference behavior:
nim-test-node/kad-dht/{core,main,helpers}.nim)."""

import json
import os
import re
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dst_libp2p_test_node_tpu.ops import kad
from dst_libp2p_test_node_tpu.runtime.kad_runtime import KadConfig, KadSimulator

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:        # the benchmark's reference and readers
    sys.path.insert(0, CHECKOUT)


def _key_ints(keys: np.ndarray) -> list[int]:
    out = []
    for row in keys:
        v = 0
        for w in row:
            v = (v << 32) | int(w)
        out.append(v)
    return out


def test_xor_bitlen_matches_python_ints():
    rng = np.random.default_rng(0)
    d = rng.integers(0, 1 << 32, size=(64, kad.KEY_WORDS), dtype=np.uint32)
    # exercise leading-zero words and exact powers of two
    d[:16, 0] = 0
    d[:8, 1] = 0
    d[0] = 0
    d[1] = [0, 0, 0, 1]
    d[2] = [0, 0, 1 << 31, 0]
    got = np.asarray(kad.xor_bitlen(jnp.asarray(d)))
    want = [v.bit_length() for v in _key_ints(d)]
    assert got.tolist() == want


def test_lex_argsort_matches_bigint_sort():
    rng = np.random.default_rng(1)
    d = rng.integers(0, 1 << 32, size=(40, kad.KEY_WORDS), dtype=np.uint32)
    d[5] = d[9]  # duplicates must not break stability
    order = np.asarray(kad.lex_argsort(jnp.asarray(d)))
    ints = _key_ints(d)
    sorted_ints = [ints[i] for i in order]
    assert sorted_ints == sorted(ints)
    # entries that tie keep their order: servicedisco's ranks read it
    assert order.tolist().index(5) + 1 == order.tolist().index(9)


# ---- the oracle: the order as stable radix argsorts, least to most
# significant word, and the closest-K selection and merge built on it (what
# ops/kad.py ran before lex_sort; kept here only, as the reference the one
# keyed sort is held to)


def _radix_lex_argsort(d):
    idx = jnp.argsort(d[..., -1], axis=-1, stable=True)
    for w in range(kad.KEY_WORDS - 2, -1, -1):
        key = jnp.take_along_axis(d[..., w], idx, axis=-1)
        refine = jnp.argsort(key, axis=-1, stable=True)
        idx = jnp.take_along_axis(idx, refine, axis=-1)
    return idx


def _radix_closest_from_table(table, keys, target_key, k_out,
                              table_keys=None):
    flat = table.reshape(-1)
    order = _radix_lex_argsort(kad._dist(keys, flat, target_key))
    return flat[order[:k_out]]


def _radix_merge_shortlist(keys, sl, queried, pick, resp, targets, s):
    q = sl.shape[0]
    merged = jnp.concatenate([sl, resp.reshape(q, -1)], axis=-1)
    mq = jnp.concatenate(
        [queried | pick, jnp.zeros((q, merged.shape[1] - s), bool)], axis=-1)
    mkey = merged * 2 + jnp.where(mq, 0, 1)
    dorder = jnp.argsort(mkey, axis=-1, stable=True)
    msort = jnp.take_along_axis(merged, dorder, axis=-1)
    qsort = jnp.take_along_axis(mq, dorder, axis=-1)
    dup = jnp.concatenate(
        [jnp.zeros((q, 1), bool), msort[:, 1:] == msort[:, :-1]], axis=-1)
    msort = jnp.where(dup | (msort < 0), -1, msort)
    morder = _radix_lex_argsort(kad._dist(keys, msort, targets))[:, :s]
    return (jnp.take_along_axis(msort, morder, axis=-1),
            jnp.take_along_axis(qsort & ~dup, morder, axis=-1))


def _tied_case(tied_words: int, one_key: bool = True):
    """40 peers whose distances to the target agree in their first
    `tied_words` words (so a later word decides), in a table with empty
    slots. `one_key`: two of them have one key (a full tie: `lex_argsort`
    keeps the earlier slot first; the closest-K selection, which holds only
    peers of distinct keys in the program, promises their distances and not
    which of the two comes first)."""
    rng = np.random.default_rng(10 + tied_words)
    target = rng.integers(0, 1 << 32, kad.KEY_WORDS, dtype=np.uint32)
    d = rng.integers(0, 1 << 32, (40, kad.KEY_WORDS), dtype=np.uint32)
    d[:, :tied_words] = d[0, :tied_words]
    if one_key or tied_words + 1 < kad.KEY_WORDS:
        d[20:30, tied_words:tied_words + 1] = d[5, tied_words]  # one deeper
    keys = d ^ target
    if one_key:
        keys[17] = keys[3]
    table = np.full((6, 16), -1, np.int32)
    slots = rng.choice(96, 40, replace=False)
    table.reshape(-1)[slots] = rng.permutation(40)
    return keys, table, target


def _plain_case(n_valid: int, shape=(6, 16), repeat: bool = False):
    rng = np.random.default_rng(100 + n_valid)
    keys = rng.integers(0, 1 << 32, (64, kad.KEY_WORDS), dtype=np.uint32)
    target = rng.integers(0, 1 << 32, kad.KEY_WORDS, dtype=np.uint32)
    table = np.full(shape, -1, np.int32)
    ids = rng.choice(64, n_valid, replace=False)
    if repeat:          # one id in several slots, as a directory may hold it
        ids[1::3] = ids[0]
    table.reshape(-1)[rng.choice(table.size, n_valid, replace=False)] = ids
    return keys, table, target


def _holes_case():
    """A (24, 16) table whose buckets hold their entries with holes between
    them (an eviction that did not compact, a hand-written table): where
    an entry stands in its bucket decides nothing."""
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 1 << 32, (64, kad.KEY_WORDS), dtype=np.uint32)
    target = rng.integers(0, 1 << 32, kad.KEY_WORDS, dtype=np.uint32)
    table = np.full((24, 16), -1, np.int32)
    ids = iter(rng.permutation(64))
    for bucket in table[:9]:
        at = rng.choice(16, 5, replace=False)
        bucket[at] = [next(ids) for _ in at]
    assert (table[:9, 0] < 0).any() and (table[:9, 15] >= 0).any()
    return keys, table, target


ORDER_CASES = {
    "tie_in_first_word": lambda: _tied_case(1),
    "tie_in_two_words": lambda: _tied_case(2),
    "tie_in_three_words": lambda: _tied_case(3),
    "tie_in_first_word_distinct_keys": lambda: _tied_case(1, one_key=False),
    "tie_in_two_words_distinct_keys": lambda: _tied_case(2, one_key=False),
    "tie_in_three_words_distinct_keys": lambda: _tied_case(3, one_key=False),
    "duplicate_ids": lambda: _plain_case(30, repeat=True),
    "all_empty": lambda: _plain_case(0),
    "all_empty_table": lambda: _plain_case(0, shape=(24, 16)),
    "one_entry": lambda: _plain_case(1),
    "fewer_than_k": lambda: _plain_case(9),
    "holes_in_buckets": _holes_case,
    # ops/dht_adversary passes its (D,) sybil directory as a (1, D) view
    "flat_directory": lambda: _plain_case(50, shape=(1, 50)),
    "flat_directory_repeated_id": lambda: _plain_case(
        50, shape=(1, 50), repeat=True),
}


@pytest.mark.parametrize("k_out", [kad.K_RESP, 32])
@pytest.mark.parametrize("case", sorted(ORDER_CASES))
def test_keyed_sort_is_the_radix_oracle_and_bigint_order(case, k_out):
    """`lex_argsort` and the closest-K selection against the radix oracle
    and against Python integers (`true_closest`'s arithmetic), entry for
    entry; an empty slot is the farthest. `lex_argsort` is stable (ties keep
    slot order); the selection's sort is not, and gives the stable one's
    bits wherever entries at one distance are one id or empty slots, which
    is every case but the three that give two peers one key."""
    keys, table, target = ORDER_CASES[case]()
    flat = table.reshape(-1)
    ints = _key_ints(keys)
    held = set(flat[flat >= 0].tolist())
    one_key = len({ints[e] for e in held}) < len(held)
    assert one_key == (case in ("tie_in_first_word", "tie_in_two_words",
                                "tie_in_three_words"))
    far = (1 << kad.KEY_BITS) - 1
    t_int = _key_ints(target[None, :])[0]
    dist = [ints[e] ^ t_int if e >= 0 else far for e in flat]
    by_int = sorted(range(flat.size), key=dist.__getitem__)   # stable

    jkeys, jtable, jtarget = map(jnp.asarray, (keys, table, target))
    d = kad._dist(jkeys, jnp.asarray(flat), jtarget)
    assert _key_ints(np.asarray(d)) == dist
    order = np.asarray(kad.lex_argsort(d))
    assert order.tolist() == by_int
    assert order.tolist() == np.asarray(_radix_lex_argsort(d)).tolist()
    # batched, as the round and servicedisco.lookup call it
    both = jnp.stack([d, d[::-1]])
    assert (np.asarray(kad.lex_argsort(both))
            == np.asarray(_radix_lex_argsort(both))).all()

    got = np.asarray(kad._closest_from_table(jtable, jkeys, jtarget, k_out))
    want = flat[by_int][:k_out]
    assert ([ints[e] ^ t_int if e >= 0 else far for e in got]
            == [dist[i] for i in by_int[:k_out]])
    if one_key:         # which of two peers with one key: not promised
        return
    assert got.tolist() == want.tolist()
    assert got.tolist() == np.asarray(_radix_closest_from_table(
        jtable, jkeys, jtarget, k_out)).tolist()
    # the slots' key words gathered beforehand, as find_node passes them
    pulled = jkeys[jnp.clip(jnp.asarray(flat), 0)].T
    assert got.tolist() == np.asarray(kad._closest_from_table(
        jtable, jkeys, jtarget, k_out, table_keys=pulled)).tolist()
    # what comes back is in distance order already: sorting it again is
    # the identity (what find_node's round takes a shortlist's rank from)
    again = kad.lex_argsort(kad._dist(jkeys, jnp.asarray(got), jtarget))
    assert np.asarray(again).tolist() == list(range(got.size))


def test_merged_shortlist_is_the_oracles_and_in_distance_order():
    """`_merge_shortlist` against the oracle's, ids and queried flags, on
    responses that repeat the shortlist's ids, each other's, and hold empty
    slots; and what it returns is in distance order, which is why
    find_node's round reads an entry's rank off its position."""
    rng = np.random.default_rng(4)
    n, q, s = 120, 50, 32
    keys = jnp.asarray(rng.integers(0, 1 << 32, (n, kad.KEY_WORDS),
                                    dtype=np.uint32))
    targets = jnp.asarray(rng.integers(0, 1 << 32, (q, kad.KEY_WORDS),
                                       dtype=np.uint32))
    tables = np.full((q, 60), -1, np.int32)
    for row in tables:
        m = rng.integers(0, 40)
        row[rng.choice(60, m, replace=False)] = rng.choice(n, m, replace=False)
    sl = jax.vmap(lambda tb, t: kad._closest_from_table(tb, keys, t, s))(
        jnp.asarray(tables), targets)
    queried = jnp.asarray(rng.random((q, s)) < 0.4) & (sl >= 0)
    pick = jnp.asarray(rng.random((q, s)) < 0.2) & (sl >= 0) & ~queried
    resp = rng.integers(-1, n, (q, kad.ALPHA, kad.K_RESP)).astype(np.int32)
    resp[:, 0, :4] = np.asarray(sl)[:, :4]          # ids the shortlist holds
    resp[:, 1, :6] = resp[:, 2, :6]                 # and each other's
    resp = jnp.asarray(resp)
    got = kad._merge_shortlist(keys, sl, queried, pick, resp, targets, s)
    want = _radix_merge_shortlist(keys, sl, queried, pick, resp, targets, s)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert np.asarray(got[1]).any() and (np.asarray(got[0]) < 0).any()
    for shortlist in (sl, got[0]):
        again = kad.lex_argsort(kad._dist(keys, shortlist, targets))
        assert (np.asarray(again) == np.arange(s)).all()


def _assert_same_wave(res, after, o_res, o_after):
    """Two programs' results of one wave, leaf for leaf (but `packed`, which
    says how the program answered, not what)."""
    for name in ("closest", "hops", "n_queries", "latency_ms", "queried",
                 "learn_counts"):
        np.testing.assert_array_equal(
            np.asarray(getattr(res, name)), np.asarray(getattr(o_res, name)),
            err_msg=name)
    np.testing.assert_array_equal(np.asarray(after.rtable),
                                  np.asarray(o_after.rtable))


def _assert_wave_is_kad_plains(state, origins, targets, stage, lat,
                               learn_cap, res, after):
    """The wave `res, after = find_node(state, origins, targets, ...)`
    against benchmark/reference/kad_plain.py from the same start tables."""
    from benchmark.reference import kad_plain

    lookups, tables = kad_plain.wave(
        kad_plain.tables_from_array(np.asarray(state.rtable)),
        [kad_plain.key_of(row) for row in np.asarray(state.keys)],
        np.asarray(origins), np.asarray(targets), np.asarray(stage),
        np.asarray(lat, np.float64), learn_cap=learn_cap)
    closest = np.full((len(lookups), kad_plain.K_RESP), -1)
    for i, found in enumerate(lookups):
        closest[i, :len(found["closest"])] = found["closest"]
    assert (closest == np.asarray(res.closest)).all()
    assert [f["hops"] for f in lookups] == np.asarray(res.hops).tolist()
    assert ([f["n_queries"] for f in lookups]
            == np.asarray(res.n_queries).tolist())
    np.testing.assert_allclose([f["latency_ms"] for f in lookups],
                               np.asarray(res.latency_ms), atol=1e-3, rtol=0)
    assert (kad_plain.tables_to_array(tables)
            == np.asarray(after.rtable)).all()


@pytest.mark.parametrize("learn_cap", [kad.LEARN_CAP, None])
def test_find_node_wave_is_the_oracle_programs_and_kad_plains(
        learn_cap, monkeypatch):
    """One wave at 200 peers on tables a first wave filled: the program
    (one keyed sort, the wave's table keys gathered once) against the same
    wave built on the radix oracle and un-hoisted, bit for bit, and against
    benchmark/reference/kad_plain.py."""
    n, seed = 200, 11
    state = kad.seed_bootstraps(kad.init_kad_state(n, seed=seed),
                                jnp.asarray([0], jnp.int32))
    stage = jnp.arange(n, dtype=jnp.int32) % 2
    lat = jnp.asarray([[100.0, 130.0], [130.0, 40.0]], jnp.float32)
    origins = jnp.arange(1, n, dtype=jnp.int32)
    _, state = kad.find_node(state, origins, state.keys[origins], stage, lat,
                             learn_cap=learn_cap)
    targets = kad.random_targets(jax.random.PRNGKey(seed), n - 1)
    res, after = kad.find_node(state, origins, targets, stage, lat,
                               learn_cap=learn_cap)
    assert int(np.asarray(res.hops).max()) > 0

    monkeypatch.setattr(kad, "_closest_from_table", _radix_closest_from_table)
    monkeypatch.setattr(kad, "_merge_shortlist", _radix_merge_shortlist)
    o_res, o_after = jax.jit(
        lambda st, o, t: kad._find_node_impl(
            st, o, t, stage, lat, 6, 32, learn_cap=learn_cap)
    )(state, origins, targets)
    monkeypatch.undo()
    _assert_same_wave(res, after, o_res, o_after)
    _assert_wave_is_kad_plains(state, origins, targets, stage, lat,
                               learn_cap, res, after)


# ---- the packed head: its width from the shapes, and a wave on tables that
# fit it or do not

def test_packed_width_rule():
    """K * (ceil(log2(n / K)) + 3) rounded up to 128 columns, B*K where
    that is no narrower: a multiple of 128 or the whole table, never wider,
    never narrower at a larger n."""
    assert kad.packed_width(10_000, 24, 16) == 256
    assert kad.packed_width(100_000, 24, 16) == 256
    assert [kad.packed_width(n, 24, 16) for n in (64, 200, 512)] == [128] * 3
    # where it does not pay: a table no wider than the rule's width
    assert kad.packed_width(64, 6, 16) == 96
    assert kad.packed_width(64, 8, 16) == 128
    assert kad.packed_width(10_000_000, 24, 16) == 384
    assert kad.packed_width(4, 24, 16) == 128          # fewer peers than K
    widths = [kad.packed_width(n, 24, 16) for n in (1, 16, 10**3, 10**4,
                                                    10**5, 10**6, 10**7)]
    assert widths == sorted(widths)
    assert all(w % 128 == 0 or w == 384 for w in widths)
    # two buckets of K over what a table that knows everybody expects
    for n in (512, 10_000, 100_000):
        assert kad.packed_width(n, 24, 16) >= 16 * (np.log2(n / 16) + 3)


def test_unpacked_table_traces_no_pack_and_no_cond():
    """Where the width is the whole table (B*K under the rule's), find_node
    is the program without a pack and without a conditional, and says
    `packed`."""
    n = 64
    state = kad.seed_bootstraps(kad.init_kad_state(n, n_buckets=6, seed=1),
                                jnp.asarray([0], jnp.int32))
    origins = jnp.arange(1, n, dtype=jnp.int32)
    args = (state, origins, state.keys[origins], jnp.zeros((n,), jnp.int32),
            jnp.full((2, 2), 100.0, jnp.float32))
    text = str(jax.make_jaxpr(lambda *a: kad.find_node(*a))(*args))
    assert "cond[" not in text
    wide = kad.seed_bootstraps(kad.init_kad_state(n, seed=1),
                               jnp.asarray([0], jnp.int32))
    text = str(jax.make_jaxpr(lambda *a: kad.find_node(*a))(
        wide, *args[1:]))
    assert text.count("cond[") == 2       # the seed's and the response's
    res, _ = kad.find_node(*args)
    assert bool(res.packed)


def test_init_kad_state_refuses_two_peers_with_one_key(monkeypatch):
    """What makes the unstable sort exact is checked where the keys are
    made: two equal rows raise."""
    class OneKey:
        def integers(self, low, high, size, dtype):
            keys = np.arange(size[0] * size[1], dtype=dtype).reshape(size)
            keys[-1] = keys[2]
            return keys

    assert kad.make_keys(300, seed=5).shape == (300, kad.KEY_WORDS)
    monkeypatch.setattr(kad.np.random, "default_rng", lambda *_: OneKey())
    with pytest.raises(ValueError, match="same key"):
        kad.make_keys(300, seed=5)
    with pytest.raises(ValueError, match="same key"):
        kad.init_kad_state(300, seed=5)


def _written_tables(keys: np.ndarray, entries) -> np.ndarray:
    """Hand-written (N, 24, 16) tables: peer p holds `entries[p]` peers,
    drawn in a random order from all the others and put where the bucket
    policy puts them (the bucket of their XOR distance, appended while it
    has room), in Python integers."""
    n = keys.shape[0]
    ints = _key_ints(keys)
    rng = np.random.default_rng(3)
    rtable = np.full((n, 24, 16), -1, np.int32)
    for p in range(n):
        held = [0] * 24
        for c in rng.permutation(n):
            if c == p or sum(held) >= entries[p]:
                continue
            b = min(kad.KEY_BITS - (ints[c] ^ ints[p]).bit_length(), 23)
            if held[b] < 16:
                rtable[p, b, held[b]] = c
                held[b] += 1
    return rtable


@pytest.mark.parametrize("fits", [True, False])
def test_find_node_on_written_tables_packed_or_not(fits, monkeypatch):
    """One wave at 512 peers (128 of 384 columns packed) on hand-written
    tables: every table under 128 entries (`packed` true), or forty of them
    at 200 (`packed` false: the tail is sorted too and merged). Either way
    the lookups and the tables the wave leaves are those of the program
    built on the radix oracle over all 384 slots, and of kad_plain, leaf for
    leaf. Sixteen groups of 32 peers, group g sharing exactly g leading key
    bits with one base key, so that a peer of a late group has a full
    bucket for every earlier group: uniform keys give 512 peers no table
    past 96."""
    n, seed = 512, 21
    assert kad.packed_width(n, 24, 16) == 128
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 1 << 32, (n, kad.KEY_WORDS), dtype=np.uint32)
    base = int(rng.integers(0, 1 << 16))
    group = np.arange(n) // 32
    # the first 16 bits: the base's first g, the next one flipped, then own
    own = keys[:, 0] >> 16
    mask = (0xFFFF << (16 - group)) & 0xFFFF
    head = (base & mask) | ((~base & 0xFFFF) & (0x8000 >> group)) | (
        own & ~mask & ~(0x8000 >> group) & 0xFFFF)
    keys[:, 0] = (head.astype(np.uint32) << 16) | (keys[:, 0] & 0xFFFF)
    assert np.unique(keys, axis=0).shape[0] == n
    entries = np.full(n, 100)
    if not fits:
        entries[-40:] = 200
    rtable = _written_tables(keys, entries)
    census = (rtable >= 0).sum(axis=(1, 2))
    assert census.max() == (100 if fits else 200), census.max()

    state = kad.init_kad_state(n, seed=seed).replace(
        keys=jnp.asarray(keys), rtable=jnp.asarray(rtable))
    stage = jnp.arange(n, dtype=jnp.int32) % 2
    lat = jnp.asarray([[100.0, 130.0], [130.0, 40.0]], jnp.float32)
    origins = jnp.arange(1, n, dtype=jnp.int32)
    targets = kad.random_targets(jax.random.PRNGKey(seed), n - 1)
    res, after = kad.find_node(state, origins, targets, stage, lat,
                               learn_cap=None)
    assert bool(res.packed) == fits
    assert int(np.asarray(res.hops).max()) > 0

    # the oracle program: radix argsorts over all 384 slots, nothing packed
    monkeypatch.setattr(kad, "_closest_from_table", _radix_closest_from_table)
    monkeypatch.setattr(kad, "_merge_shortlist", _radix_merge_shortlist)
    monkeypatch.setattr(kad, "packed_width", lambda n, b, k: b * k)
    o_res, o_after = jax.jit(
        lambda st, o, t: kad._find_node_impl(
            st, o, t, stage, lat, 6, 32, learn_cap=None)
    )(state, origins, targets)
    monkeypatch.undo()
    assert bool(o_res.packed)
    _assert_same_wave(res, after, o_res, o_after)
    _assert_wave_is_kad_plains(state, origins, targets, stage, lat, None,
                               res, after)


# ---- the scopes of jit_find_node and the four metrics that read them

FIND_NODE_SCOPES = ["seed", "order", "response", "merge", "learn"]


@pytest.fixture(scope="module")
def find_node_scope_paths():
    """The scope path (`op_name`, what a profile's op event carries) of
    every instruction of `find_node` compiled as the tiny-regression cell
    runs it (64 peers, one bootstrap, no learn cap)."""
    n = 64
    state = kad.seed_bootstraps(kad.init_kad_state(n, seed=1),
                                jnp.asarray([0], jnp.int32))
    origins = jnp.arange(1, n, dtype=jnp.int32)
    text = kad.find_node.lower(
        state, origins, state.keys[origins], jnp.zeros((n,), jnp.int32),
        jnp.full((2, 2), 100.0, jnp.float32), learn_cap=None,
    ).compile().as_text()
    return re.findall(r'op_name="(jit\(find_node\)/[^"]*)"', text)


def test_compiled_find_node_carries_the_scopes(find_node_scope_paths):
    from benchmark.harness import program_profile

    paths = find_node_scope_paths
    under = {name: [p for p in paths if program_profile.follows(
        p, [name], FIND_NODE_SCOPES)] for name in FIND_NODE_SCOPES}
    assert all(under.values()), {k: len(v) for k, v in under.items()}
    # the rounds' three keep their scope inside the scan's body, the
    # responses through the vmap over the queried peers too
    for name in ("order", "response", "merge"):
        assert all("while/body" in p for p in under[name]), name
    assert any(p.endswith("/sort") for p in under["response"])
    assert not any("while/body" in p for p in under["seed"] + under["learn"])
    # the hoisted key gather is the seed's
    assert any(p.endswith("/gather") for p in under["seed"])
    # no sixth: the pack is the seed's, the tail's sort under its
    # conditional the seed's or the response's
    for word, homes in (("jit(sort)", ("seed", "merge")),
                        ("cond/branch_", ("seed", "response"))):
        inside = [p for p in paths if word in p]
        assert inside and all(
            any(p in under[home] for home in homes) for p in inside), word
    assert any("cond/branch_" in p for p in under["response"])
    # outside the five: the round's RTT and counters, a few scalars
    loose = len(paths) - sum(map(len, under.values()))
    assert loose <= 0.1 * len(paths), loose


@pytest.mark.parametrize("cell, scope", [
    ("regression-10k", "seed"), ("regression-10k", "response"),
    ("regression-10k", "merge"), ("regression-10k", "learn"),
    ("kad-10k", "seed"), ("kad-10k", "response"), ("kad-10k", "learn")])
def test_kad_scope_metric_reads_a_traced_find_node(
        cell, scope, find_node_scope_paths, monkeypatch):
    """benchmark/layer_metrics/kad.<scope>.device_s.json (the regression
    cell's) and kadnode.<scope>.device_s.json (the kad cell's) through the
    reader they name, on a profile with one microsecond of device time for
    every instruction of the compiled tiny-regression `find_node` (XLA:CPU's
    own profile has no device plane to read): not None, and with `order` and
    the unscoped rest the scopes add up to the module."""
    from benchmark.harness import manifest, program_profile, trace

    plane = trace.DEVICE_PLANE_PREFIX + "0"
    ops = [{"plane": plane, "name": f"op.{i}", "start_ns": 1e3 * i,
            "dur_ns": 1e3, "scope": p}
           for i, p in enumerate(find_node_scope_paths)]
    whole = 1e3 * len(ops)
    profile = {"ops": ops, "host": [], "modules": [
        {"plane": plane, "name": "jit_find_node(7)", "start_ns": 0.0,
         "dur_ns": whole}]}
    monkeypatch.setattr(program_profile, "load", lambda: profile)
    ctx = SimpleNamespace(
        trace_windows=[(0.0, whole)], recorder=None, experiments=[],
        memory_stats=[], trace_rows=(
            [{**r, "line": trace.MODULE_LINE} for r in profile["modules"]]
            + [{**r, "line": trace.OP_LINE} for r in ops]))

    def read(name):
        with open(os.path.join(CHECKOUT, "benchmark", "layer_metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        assert spec["name"] == name
        return spec, manifest.reader(spec["reader"])(ctx, **spec["params"])

    prefix = {"regression-10k": "kad", "kad-10k": "kadnode"}[cell]
    spec, seconds = read(f"{prefix}.{scope}.device_s")
    assert seconds is not None and seconds > 0.0
    assert spec["params"]["scopes"] == FIND_NODE_SCOPES
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    entry = entries[spec["name"]]
    whole_entry = entries[f"{prefix}.find_node.device_s"]
    assert (entry["layer"], entry["moves"], entry["workloads"]) == (
        spec["layer"], "experiment_s", [f"{cell}.headline"])
    assert entry["layer"] == whole_entry["layer"]
    parts = program_profile.scope_seconds(
        profile, ctx.trace_windows, "jit_find_node",
        [[n] for n in FIND_NODE_SCOPES], FIND_NODE_SCOPES)
    assert seconds == parts[FIND_NODE_SCOPES.index(scope)]
    module = read(f"{prefix}.find_node.device_s")[1]
    assert module == pytest.approx(whole / 1e9)
    assert sum(parts) <= module and sum(parts) >= 0.9 * module


def test_bucket_slot_ranges():
    d = np.zeros((3, kad.KEY_WORDS), dtype=np.uint32)
    d[0, 0] = 1 << 31          # max distance -> bucket 0
    d[1, kad.KEY_WORDS - 1] = 1  # tiny distance -> clamps to last bucket
    got = np.asarray(kad.bucket_slot(jnp.asarray(d), 24))
    assert got[0] == 0
    assert got[1] == 23
    assert got[2] == 23  # zero distance also clamps


def test_insert_invariants():
    n = 32
    st = kad.init_kad_state(n, n_buckets=8, k_bucket=4, seed=2)
    owners = jnp.arange(n, dtype=jnp.int32)
    allp = jnp.broadcast_to(owners[None, :], (n, n))
    st = kad.rtable_insert(st, owners, allp)
    rt = np.asarray(st.rtable)
    for p in range(n):
        entries = rt[p][rt[p] >= 0]
        # no self, no duplicates
        assert p not in entries
        assert len(set(entries.tolist())) == len(entries)
        # every entry sits in its correct bucket
        for b in range(rt.shape[1]):
            for q in rt[p, b]:
                if q < 0:
                    continue
                d = jnp.bitwise_xor(st.keys[p], st.keys[q])[None, :]
                want = int(np.asarray(kad.bucket_slot(d, rt.shape[1]))[0])
                assert want == b
    # double insert is a no-op
    st2 = kad.rtable_insert(st, owners, allp)
    np.testing.assert_array_equal(np.asarray(st2.rtable), rt)


def test_lookup_finds_global_closest_when_fully_informed():
    n = 64
    st = kad.init_kad_state(n, seed=3)
    allp = jnp.arange(n, dtype=jnp.int32)
    st = kad.rtable_insert(st, allp, jnp.broadcast_to(allp[None, :], (n, n)))
    stage = jnp.zeros((n,), jnp.int32)
    lat = jnp.full((2, 2), 50.0, jnp.float32)
    targets = kad.random_targets(jax.random.PRNGKey(0), n)
    res, st = kad.find_node(st, allp, targets, stage, lat, rounds=6)
    keys_np = np.asarray(st.keys)
    closest = np.asarray(res.closest)
    for i in range(n):
        truth = kad.true_closest(keys_np, np.asarray(targets[i]), 1)[0]
        assert closest[i, 0] == truth
    # parallel queries cost max-RTT per round: positive, bounded latency
    lats = np.asarray(res.latency_ms)
    assert (lats > 0).all() and (lats < 30_000).all()


def test_bootstrap_and_warmup_populate_tables():
    n = 96
    st = kad.init_kad_state(n, seed=1)
    boots = jnp.asarray([0, 1], jnp.int32)
    st = kad.seed_bootstraps(st, boots)
    census0 = np.asarray(kad.rtable_census(st))
    assert (census0[2:] >= 2).all()      # everyone knows the anchors
    assert census0[0] > 10               # anchors learned the network

    stage = jnp.zeros((n,), jnp.int32)
    lat = jnp.full((2, 2), 50.0, jnp.float32)
    origins = jnp.arange(2, n, dtype=jnp.int32)
    for _ in range(5):
        _, st = kad.find_node(st, origins, st.keys[origins], stage, lat)
    key = jax.random.PRNGKey(7)
    for _ in range(10):
        key, k = jax.random.split(key)
        _, st = kad.find_node(
            st, origins, kad.random_targets(k, origins.shape[0]), stage, lat
        )
    census1 = np.asarray(kad.rtable_census(st))
    assert census1.mean() > census0.mean() + 5

    # most lookups now terminate at the true global closest
    key, k = jax.random.split(key)
    targets = kad.random_targets(k, origins.shape[0])
    res, st = kad.find_node(st, origins, targets, stage, lat)
    keys_np = np.asarray(st.keys)
    hits = sum(
        int(np.asarray(res.closest)[i, 0]
            == kad.true_closest(keys_np, np.asarray(targets[i]), 1)[0])
        for i in range(origins.shape[0])
    )
    assert hits >= 0.7 * origins.shape[0]


def test_dead_peers_are_not_queried():
    n = 48
    st = kad.init_kad_state(n, seed=5)
    allp = jnp.arange(n, dtype=jnp.int32)
    st = kad.rtable_insert(st, allp, jnp.broadcast_to(allp[None, :], (n, n)))
    dead = jnp.zeros((n,), bool).at[10].set(True).at[11].set(True)
    st = st.replace(alive=~dead)
    stage = jnp.zeros((n,), jnp.int32)
    lat = jnp.full((2, 2), 50.0, jnp.float32)
    origins = jnp.asarray([0, 1, 2, 3], jnp.int32)
    targets = kad.random_targets(jax.random.PRNGKey(2), 4)
    res, _ = kad.find_node(st, origins, targets, stage, lat)
    queried = np.asarray(res.queried)
    assert not np.isin(queried[queried >= 0], [10, 11]).any()


def test_kad_simulator_end_to_end():
    cfg = KadConfig(network_size=64, n_bootstrap=2, n_probe=6,
                    probe_duration_s=15.0, seed=0)
    sim = KadSimulator(cfg)
    summary = sim.run()
    # reference log-line surface (core.nim notice/debug lines)
    text = "\n".join(sim.lines)
    assert "Starting warmup phase" in text
    assert "Warmup complete" in text
    assert "Kad routing table peers=" in text
    assert "Probe: Finding node" in text
    # 5 self + 15 random per normal node; 3 probe ticks per probe node
    n_normal = 64 - 2 - 6
    assert summary.warmup_lookups == 20 * n_normal
    assert summary.probe_lookups == 3 * 6
    # probes succeed within the 30 s timeout and tables are populated
    assert summary.probe_success == summary.probe_lookups
    assert summary.census_mean > 10
    assert summary.queries_per_bootstrap > 0
    report = summary.report()
    assert "Routing table census" in report


def test_config_from_env_roundtrip(monkeypatch):
    monkeypatch.setenv("PEERS", "40")
    monkeypatch.setenv("KAD_BOOTSTRAPS", "2")
    monkeypatch.setenv("KAD_PROBES", "4")
    monkeypatch.setenv("DISCOVERY", "extended")
    from dst_libp2p_test_node_tpu.runtime.kad_runtime import config_from_env

    cfg = config_from_env()
    assert (cfg.network_size, cfg.n_bootstrap, cfg.n_probe) == (40, 2, 4)
    assert cfg.discovery == "extended"
    bad = KadConfig(discovery="nope")
    with pytest.raises(ValueError):
        bad.validate()
    with pytest.raises(ValueError):
        KadConfig(n_probe=-5).validate()


def test_extended_discovery_self_cleans_under_churn():
    # DISCOVERY=extended mounts KademliaDiscovery (kad-dht/helpers.nim:48-57):
    # discovery hands the application CONNECTABLE peers, so a failed dial
    # evicts the stale entry — under churn its routing tables shed dead
    # peers, while plain KadDHT keeps them (LRU-keep, no ping eviction).
    import numpy as np
    import jax.numpy as jnp

    def dead_entries(sim, alive):
        rt = np.asarray(sim.state.rtable)
        dead = 0
        for p in range(rt.shape[0]):
            e = rt[p].reshape(-1)
            e = e[e >= 0]
            dead += int((~alive[e]).sum())
        return dead

    counts = {}
    for disc in ("kad-dht", "extended"):
        cfg = KadConfig(network_size=96, n_bootstrap=2, n_probe=20,
                        probe_duration_s=30.0, seed=3, discovery=disc)
        sim = KadSimulator(cfg)
        sim.boot()
        sim.warmup()
        # 25% of the normal population dies before the probe phase
        alive = np.ones(96, bool)
        rng = np.random.default_rng(9)
        dead_ids = rng.choice(np.arange(2, 76), size=18, replace=False)
        alive[dead_ids] = False
        sim.state = sim.state.replace(alive=jnp.asarray(alive))
        sim.probe()
        counts[disc] = dead_entries(sim, alive)
    assert counts["extended"] < counts["kad-dht"], counts


def test_evict_failed_removes_dead_found_entries():
    import jax.numpy as jnp
    import numpy as np

    from dst_libp2p_test_node_tpu.ops import kad

    state = kad.init_kad_state(32, seed=0)
    state = kad.rtable_insert(
        state, jnp.asarray([1]), jnp.asarray([[2, 3, 4]]))
    alive = np.ones(32, bool)
    alive[3] = False
    state = state.replace(alive=jnp.asarray(alive))
    assert (np.asarray(state.rtable[1]) == 3).any()
    # origin 1 dials its found set {3, 2}: the dial to dead 3 fails -> evict
    s2 = kad.evict_failed(state, jnp.asarray([1]), jnp.asarray([[3, 2]]))
    after = np.asarray(s2.rtable[1])
    assert not (after == 3).any()
    assert (after == 2).any() and (after == 4).any()
    # buckets stay left-packed (the insert position arithmetic relies on it)
    for row in after:
        hole = False
        for v in row:
            if v < 0:
                hole = True
            else:
                assert not hole, row


def test_evict_failed_retry_budget_and_backoff():
    # the retry budget: with max_fails=2 one lossy dial wave charges the
    # entry but keeps it; re-failing while the exponential-backoff deadline
    # is live is NOT re-counted (the dial was never retried); once the
    # clock passes the deadline the second genuine failure evicts; a
    # successful dial resets both counters. Defaults (max_fails=1)
    # reproduce the original immediate eviction bit-for-bit.
    state = kad.init_kad_state(32, seed=0)
    state = kad.rtable_insert(
        state, jnp.asarray([1]), jnp.asarray([[2, 3, 4]]))
    alive = np.ones(32, bool)
    alive[3] = False
    state = state.replace(alive=jnp.asarray(alive))
    origins = jnp.asarray([1])
    found = jnp.asarray([[3, 2]])

    def slot_of(s, entry):
        pos = np.nonzero(np.asarray(s.rtable[1]) == entry)
        assert len(pos[0]) == 1
        return pos[0][0], pos[1][0]

    # wave 1: first failure charges the counter, arms the backoff, keeps
    # the entry
    s1 = kad.evict_failed(state, origins, found, max_fails=2,
                          backoff_base_ms=100.0)
    b, k = slot_of(s1, 3)
    assert int(s1.rt_fails[1, b, k]) == 1
    np.testing.assert_allclose(float(s1.rt_retry_ms[1, b, k]), 100.0)

    # wave 2 inside the backoff window (t_ms unchanged): no re-count, no
    # eviction — the entry was never re-dialed
    s2 = kad.evict_failed(s1, origins, found, max_fails=2,
                          backoff_base_ms=100.0)
    b, k = slot_of(s2, 3)
    assert int(s2.rt_fails[1, b, k]) == 1

    # wave 3 past the deadline: the second genuine failure reaches the
    # budget and evicts (bucket stays left-packed)
    s3 = kad.evict_failed(
        s2.replace(t_ms=s2.t_ms + 1000.0), origins, found, max_fails=2,
        backoff_base_ms=100.0)
    after = np.asarray(s3.rtable[1])
    assert not (after == 3).any()
    assert (after == 2).any() and (after == 4).any()

    # a successful dial resets the charged counter and the deadline
    revived = s1.replace(alive=jnp.ones(32, bool))
    s4 = kad.evict_failed(revived, origins, found, max_fails=2,
                          backoff_base_ms=100.0)
    b, k = slot_of(s4, 3)
    assert int(s4.rt_fails[1, b, k]) == 0
    assert float(s4.rt_retry_ms[1, b, k]) == 0.0

    # defaults reproduce the original immediate-eviction tables exactly
    s_now = kad.evict_failed(state, origins, found)
    s_budget1 = kad.evict_failed(state, origins, found, max_fails=1,
                                 backoff_base_ms=0.0)
    np.testing.assert_array_equal(np.asarray(s_now.rtable),
                                  np.asarray(s_budget1.rtable))
    assert not (np.asarray(s_now.rtable[1]) == 3).any()
