import functools

import numpy as np
import pytest

import graph_reference
from dst_libp2p_test_node_tpu.ops.graph import (
    build_connection_graph,
    sample_dials,
    _cumcount,
)
from dst_libp2p_test_node_tpu.runtime.checkpoint import _graph_hash


def test_cumcount():
    keys = np.array([3, 1, 3, 3, 1, 2])
    assert _cumcount(keys).tolist() == [0, 0, 1, 2, 1, 0]


def test_sample_dials_small():
    d = sample_dials(100, 10, seed=1)
    assert d.shape == (100, 10)
    for p in range(100):
        row = d[p]
        assert p not in row
        assert len(set(row.tolist())) == 10


def test_sample_dials_large_path():
    d = sample_dials(5000, 10, seed=2)
    assert d.shape == (5000, 10)
    me = np.arange(5000)[:, None]
    assert not (d == me).any()
    # all distinct per row
    srt = np.sort(d, axis=1)
    assert not (srt[:, 1:] == srt[:, :-1]).any()


def test_graph_reverse_map_and_symmetry():
    g = build_connection_graph(200, 10, seed=3)
    g.validate()
    # symmetric: q in conns[p] <=> p in conns[q]
    p, i = np.nonzero(g.conns >= 0)
    q = g.conns[p, i]
    for pp, qq in list(zip(p, q))[:500]:
        assert pp in g.conns[qq]


def test_degree_distribution():
    g = build_connection_graph(1000, 10, seed=4)
    # every peer dialed 10; expected degree ~ 20
    assert g.degree.min() >= 10
    assert abs(g.degree.mean() - 20.0) < 1.0


def test_outbound_count():
    g = build_connection_graph(300, 10, seed=5)
    # each peer's outbound edges == its dials (minus dedup'd mutual dials)
    out_deg = g.out_mask.sum(axis=1)
    assert (out_deg <= 10).all()
    assert out_deg.mean() > 9.0


def test_max_degree_cap():
    g = build_connection_graph(500, 10, seed=6, max_degree=16)
    assert g.capacity == 16
    assert g.degree.max() <= 16
    g.validate()


def test_determinism():
    a = build_connection_graph(100, 5, seed=7)
    b = build_connection_graph(100, 5, seed=7)
    assert np.array_equal(a.conns, b.conns)
    assert np.array_equal(a.rev, b.rev)


# ------------------------------------------------ the build against its
# reference (tests/graph_reference.py: the parent's functions, verbatim)

ARRAYS = ("conns", "rev", "out_mask", "degree")


def _same(a: np.ndarray, b: np.ndarray) -> bool:
    return a.dtype == b.dtype and a.shape == b.shape and np.array_equal(a, b)


def _assert_same_graph(got, want):
    for name in ARRAYS:
        assert _same(getattr(got, name), getattr(want, name)), name
    got.validate()


@functools.lru_cache(maxsize=2)
def _both_dials(n, connect_to, seed):
    return (graph_reference.sample_dials(n, connect_to, seed),
            sample_dials(n, connect_to, seed))


# max_degree innermost, so that a (n, connect_to, seed)'s dials are sampled
# once a side for its four capacities (the reference takes its own as
# `dials=`, which changes nothing there; the build under test samples again)
GRID = [(n, k, seed, cap)
        for n in (8, 60, 300, 4096, 4097, 5000, 20000, 100000)
        for k in (1, 4, 10)
        for seed in (0, 11, 2147505906)
        for cap in (None, 4, 8, 40)
        if k < n and (cap is None or cap < n)]


@pytest.mark.parametrize("n,connect_to,seed,max_degree", GRID)
def test_build_is_the_references_arrays(n, connect_to, seed, max_degree):
    dials, sampled = _both_dials(n, connect_to, seed)
    assert _same(sampled, dials)
    got = build_connection_graph(n, connect_to, seed, max_degree)
    _assert_same_graph(got, graph_reference.build_connection_graph(
        n, connect_to, seed, max_degree, dials=dials))
    # these dials were sampled here, whole: the dedupe of mutual dials
    build = got.build
    assert build["dedupe"] == "mutual"
    if n <= 4096:
        assert build["dial_rows_resampled"] == 0
    edges = n * connect_to - build["mutual_dials_dropped"]
    me = np.arange(n)[:, None]
    pairs = np.minimum(me, dials) * n + np.maximum(me, dials)
    assert edges == len(np.unique(pairs))
    assert build["cap_filtered_edges"] == edges - int(got.degree.sum()) // 2


def _ring_dials(n, k):
    """Every peer dials its next k neighbours on a ring: rows of distinct
    peers, none its own."""
    return (np.arange(n)[:, None] + 1 + np.arange(k)[None, :]) % n


def _passed_dials(kind):
    n = 50
    if kind == "repeat-in-a-row":
        dials = _ring_dials(n, 4)
        dials[7, 3] = dials[7, 0]
        dials[9, 1:] = dials[9, 0]
    elif kind == "self-dial":
        dials = _ring_dials(n, 4)
        dials[5, 2] = 5
        dials[0, 0] = 0
    elif kind == "one-column":
        dials = _ring_dials(n, 1)
        dials[n - 1, 0] = n - 2     # and n-2 dials n-1: one mutual pair
    elif kind == "mutual-everywhere":
        dials = np.concatenate([_ring_dials(n, 2),
                                (np.arange(n)[:, None] - 1 - np.arange(2)) % n],
                               axis=1)
    return n, dials.astype(np.int64)


@pytest.mark.parametrize("max_degree", [None, 3, 8])
@pytest.mark.parametrize("kind", ["repeat-in-a-row", "self-dial",
                                  "one-column", "mutual-everywhere"])
def test_passed_dials_take_the_unique_path(kind, max_degree):
    """A caller's dials promise nothing (a repeat in a row, a self-dial):
    the first copy of every pair key, as before."""
    n, dials = _passed_dials(kind)
    got = build_connection_graph(n, dials.shape[1], 2, max_degree, dials=dials)
    _assert_same_graph(got, graph_reference.build_connection_graph(
        n, dials.shape[1], 2, max_degree, dials=dials))
    assert got.build["dedupe"] == "unique"
    assert got.build["dial_rows_resampled"] == 0
    if kind == "mutual-everywhere":
        assert got.build["mutual_dials_dropped"] == 2 * n


class _CraftedDraws:
    """numpy's generator with `edit` applied to the table `integers` draws."""

    def __init__(self, rng, edit):
        self._rng, self._edit = rng, edit

    def integers(self, *args, **kwargs):
        table = self._rng.integers(*args, **kwargs)
        self._edit(table)
        return table

    def __getattr__(self, name):
        return getattr(self._rng, name)


def _repeat_in_the_head(table):
    table[7, 3] = table[7, 1]               # the 4th dial is the 2nd again
    table[4999, 9] = table[4999, 0]
    table[11, 20] = table[11, 12]           # past the head: nobody looks


def _too_few_distinct(table):
    _repeat_in_the_head(table)
    table[9, :] = table[9, np.arange(table.shape[1]) % 4]   # 4 peers: 6 holes
    table[13, :] = table[13, 0]                             # 1 peer: 9 holes


@pytest.mark.parametrize("edit,resampled,dedupe", [
    (_repeat_in_the_head, 2, "mutual"), (_too_few_distinct, 4, "unique")],
    ids=["repeat-in-the-head", "holes-to-fill"])
def test_crafted_draws_resample_only_the_rows_that_repeat(
        monkeypatch, edit, resampled, dedupe):
    """Rows whose first k draws repeat go through the general algorithm,
    and only those; a row that runs out of distinct draws has its holes
    filled, after which nothing is promised about the rows and the dedupe
    is the `np.unique` one."""
    n, k, seed = 5000, 10, 1
    sound = np.random.default_rng
    natural = build_connection_graph(n, k, seed).build["dial_rows_resampled"]
    monkeypatch.setattr(np.random, "default_rng",
                        lambda s: _CraftedDraws(sound(s), edit))
    want_dials = graph_reference.sample_dials(n, k, seed)
    assert _same(sample_dials(n, k, seed), want_dials)
    if edit is _too_few_distinct:
        assert want_dials[9, 4:].tolist() == [(9 + 1 + c) % n
                                              for c in range(4, k)]
    for max_degree in (None, 12):
        got = build_connection_graph(n, k, seed, max_degree)
        _assert_same_graph(got, graph_reference.build_connection_graph(
            n, k, seed, max_degree))
        assert got.build["dedupe"] == dedupe
        assert natural < got.build["dial_rows_resampled"] <= natural + resampled


@pytest.mark.parametrize("n,connect_to,seed,max_degree", [
    (300, 10, 1, 20), (5000, 10, 2, 21), (20000, 4, 3, 9)])
def test_the_cap_bites_on_some_peers_and_not_on_others(
        n, connect_to, seed, max_degree):
    got = build_connection_graph(n, connect_to, seed, max_degree)
    _assert_same_graph(got, graph_reference.build_connection_graph(
        n, connect_to, seed, max_degree))
    assert 0 < got.build["cap_filtered_edges"]
    assert (got.degree == max_degree).any() and (got.degree < max_degree).any()
    assert got.degree.max() == max_degree


def test_no_peer_overflows_skips_the_filter_and_says_so():
    got = build_connection_graph(5000, 10, 4, 40)
    assert got.build["cap_filtered_edges"] == 0
    assert got.degree.max() < 40
    assert got.degree.sum() == 2 * (5000 * 10 - got.build["mutual_dials_dropped"])


@pytest.mark.parametrize("n,seed,want", [
    (60, 5, "2eb570a1b800336dec4b2a46cb59c38b4649c61fe4cb67cf9626df7e7ece01b2"),
    (200, 3, "f7e6d9eb12e5c44bbd39d5b2cc1ef6b5e4e0dafb29d0a65d868775001dd757f8"),
    (100000, 0,
     "4dc7b779729424c19424d91c234281884ef596038b954afd3bfd79661a62e0ae"),
    (100000, 2147505906,
     "d7dd291846bfd0ecc67dbf0124116d20a691c2532325a3245eeb8be4a5f443ec"),
])
def test_graph_fingerprint_is_the_one_the_parent_commit_gave(n, seed, want):
    """`graph_sha256` of run.sh's graph (connect-to 10, capacity 40), read at
    the commit before the build lost its sorts: a checkpoint written there
    resumes here."""
    assert _graph_hash(build_connection_graph(n, 10, seed, 40)) == want
