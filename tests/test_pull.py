"""Unit tests for the reciprocal-pull primitive (ops/pull.py) — the hot
memory op of the engine: row-gather + fused slot select, with the 2-index
fallback above the memory budget. Both paths must agree exactly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dst_libp2p_test_node_tpu.ops.pull as pull
from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph
from dst_libp2p_test_node_tpu.ops.state import graph_arrays


@pytest.fixture(scope="module")
def edges():
    g = build_connection_graph(300, 6, seed=7)
    a = graph_arrays(g)
    return a["conns"], a["rev"]


def _ref_pull(vals, conns, rev, fill):
    cn = np.clip(np.asarray(conns), 0, None)
    rv = np.clip(np.asarray(rev), 0, None)
    v = np.asarray(vals)[cn, rv]
    return np.where((np.asarray(conns) >= 0) & (np.asarray(rev) >= 0), v, fill)


def test_bool_pull_matches_reference(edges):
    conns, rev = edges
    m = jax.random.uniform(jax.random.PRNGKey(0), conns.shape) < 0.3
    got = np.asarray(pull.reciprocal_pull_bool(m, conns, rev))
    np.testing.assert_array_equal(got, _ref_pull(m, conns, rev, False))


def test_min_pull_matches_reference(edges):
    conns, rev = edges
    v = jax.random.uniform(jax.random.PRNGKey(1), conns.shape) * 50
    got = np.asarray(pull.reciprocal_pull_min(v, conns, rev))
    ref = _ref_pull(v, conns, rev, float(pull.INF))
    np.testing.assert_allclose(got, ref)


def test_neighbor_pull_is_per_peer_value(edges):
    conns, rev = edges
    per_peer = jnp.arange(conns.shape[0], dtype=jnp.float32)
    got = np.asarray(pull.neighbor_pull_min(per_peer, conns, rev))
    cn = np.asarray(conns)
    want = np.where(cn >= 0, cn.astype(np.float32), float(pull.INF))
    np.testing.assert_allclose(got, want)


def test_neighbor_rows_min_is_the_per_peer_gather(edges, monkeypatch):
    """The selector-free per-peer lookup (any index, no reverse map): the
    row pull, and the scalar gather it falls back to past the budget, both
    read per_peer[conns] bit for bit — INF included — and INF on pads."""
    conns, _ = edges
    n = conns.shape[0]
    u = jax.random.uniform(jax.random.PRNGKey(8), (n,))
    per_peer = jnp.where(u < 0.7, u * 1e6, pull.INF)
    shuffled = jax.random.permutation(
        jax.random.PRNGKey(9), conns, axis=1, independent=True)
    cn = np.asarray(shuffled)
    want = np.where(cn >= 0, np.asarray(per_peer)[np.clip(cn, 0, None)],
                    np.float32(pull.INF))
    rows = pull.neighbor_rows_min(per_peer, shuffled)
    assert np.asarray(rows).tobytes() == want.tobytes()
    assert "gather" in str(jax.make_jaxpr(pull.neighbor_rows_min)(
        per_peer, shuffled))
    monkeypatch.setattr(pull, "_MAX_INTERMEDIATE_BYTES", 1)
    scalar = pull.neighbor_rows_min(per_peer, shuffled)
    assert np.asarray(scalar).tobytes() == want.tobytes()


def test_fallback_path_identical(edges, monkeypatch):
    """Force the 2-index fallback (as at 1M-peer scale) and require exact
    agreement with the row-gather path."""
    conns, rev = edges
    v = jax.random.uniform(jax.random.PRNGKey(2), conns.shape) * 50
    m = v > 25
    fast_min = np.asarray(pull.reciprocal_pull_min(v, conns, rev))
    fast_bool = np.asarray(pull.reciprocal_pull_bool(m, conns, rev))
    monkeypatch.setattr(pull, "_MAX_INTERMEDIATE_BYTES", 1)
    slow_min = np.asarray(pull.reciprocal_pull_min(v, conns, rev))
    slow_bool = np.asarray(pull.reciprocal_pull_bool(m, conns, rev))
    np.testing.assert_allclose(fast_min, slow_min)
    np.testing.assert_array_equal(fast_bool, slow_bool)


def test_batch_factor_triggers_fallback(edges, monkeypatch):
    """A large enclosing-vmap width must push the dispatch over budget even
    when the per-instance intermediate would fit — asserted on the dispatch
    decision itself (both paths return identical values by design, so a
    value comparison could not catch a broken batch_factor)."""
    conns, rev = edges
    n, c = conns.shape
    budget = n * c * 128 * 4 * 4  # fits 4 instances
    monkeypatch.setattr(pull, "_MAX_INTERMEDIATE_BYTES", budget)
    assert not pull.exceeds_budget(jnp.float32, conns.shape, batch_factor=1)
    assert not pull.exceeds_budget(jnp.float32, conns.shape, batch_factor=4)
    assert pull.exceeds_budget(jnp.float32, conns.shape, batch_factor=64)
    # bool packs 4x smaller before padding
    assert not pull.exceeds_budget(jnp.bool_, conns.shape, batch_factor=16)
    # and the fallback path still computes the same values
    v = jax.random.uniform(jax.random.PRNGKey(3), conns.shape)
    a = np.asarray(pull.reciprocal_pull_min(v, conns, rev, batch_factor=1))
    b = np.asarray(pull.reciprocal_pull_min(v, conns, rev, batch_factor=64))
    np.testing.assert_allclose(a, b)


def test_involution_roundtrip(edges):
    """Pulling twice through the involution returns the original edge values
    (on valid slots) — the defining property of the reverse-slot map."""
    conns, rev = edges
    v = jax.random.uniform(jax.random.PRNGKey(4), conns.shape) * 10
    valid = np.asarray((conns >= 0) & (rev >= 0))
    once = pull.reciprocal_pull_min(v, conns, rev)
    twice = np.asarray(pull.reciprocal_pull_min(once, conns, rev))
    np.testing.assert_allclose(twice[valid], np.asarray(v)[valid])


# ------------------------------------------------- within-row permutations --

def _row_perms(key, n, c):
    """One random permutation of range(c) per row."""
    return jnp.argsort(jax.random.uniform(key, (n, c)), axis=-1)


def _rows_of(kind, key, n, c):
    u = jax.random.uniform(key, (n, c))
    if kind == "f32":       # arrival-time rows: finite values and the sentinel
        return jnp.where(u < 0.6, u * 1e6, pull.INF).astype(jnp.float32)
    if kind == "bool":
        return u < 0.4
    return jnp.where(u < 0.8, (u * 1e5).astype(jnp.int32), -1)   # ids, -1 pads


@pytest.mark.parametrize("c", [8, 40, 128, 200])
@pytest.mark.parametrize("kind", ["f32", "bool", "int32"])
def test_permute_rows_is_take_along_axis(kind, c):
    """The select (up to one lane tile) and the take_along_axis branch (past
    it) give take_along_axis's bits, dtype and shape."""
    n = 64
    x = _rows_of(kind, jax.random.PRNGKey(c), n, c)
    idx = _row_perms(jax.random.PRNGKey(c + 1), n, c)
    got = pull.permute_rows(x, idx)
    want = jnp.take_along_axis(x, idx, axis=-1)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    # a permutation and its inverse undo each other
    inv = jnp.argsort(idx, axis=-1)
    back = pull.permute_rows(got, inv)
    assert np.asarray(back).tobytes() == np.asarray(x).tobytes()


def test_permute_rows_dispatches_on_row_width():
    """Up to the lane tile no gather is traced; past it, the one of
    take_along_axis."""
    def gathers(c):
        x = jnp.zeros((16, c), jnp.float32)
        idx = jnp.zeros((16, c), jnp.int32)
        jaxpr = jax.make_jaxpr(pull.permute_rows)(x, idx)
        return str(jaxpr).count("gather")
    assert gathers(40) == 0 and gathers(128) == 0
    assert gathers(129) > 0


def test_permute_rows_under_a_batch_axis_and_narrow_picks():
    """x may carry leading axes the index lacks (the fragment vmap), and the
    index may be narrower than the row (a per-row pick)."""
    n, c = 32, 40
    x = _rows_of("f32", jax.random.PRNGKey(0), 3 * n, c).reshape(3, n, c)
    idx = _row_perms(jax.random.PRNGKey(1), n, c)
    got = jax.vmap(lambda xf: pull.permute_rows(xf, idx))(x)
    want = jnp.take_along_axis(x, jnp.broadcast_to(idx, x.shape), axis=-1)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    pick = idx[:, :1]
    assert np.asarray(pull.permute_rows(x[0], pick)).tobytes() == \
        np.asarray(jnp.take_along_axis(x[0], pick, axis=-1)).tobytes()


def test_rev_sorted_pulls_a_sorted_table_to_the_slot_layout(edges):
    """AnswerTables.rev_sorted: a table kept in lat order, pulled through
    (conns, rev_sorted), is the table in slot order pulled through (conns,
    rev) — on a random involution with pad slots."""
    from dst_libp2p_test_node_tpu.ops.disseminate import answer_tables

    conns, rev = edges
    assert bool((conns < 0).any())          # the graph has pad slots
    lat_edge = jnp.where(
        conns >= 0,
        40.0 + 90.0 * jax.random.uniform(jax.random.PRNGKey(5), conns.shape),
        0.0)
    tabs = answer_tables(lat_edge, conns, rev)
    valid = np.asarray(conns >= 0)
    rs = np.asarray(tabs.rev_sorted)
    assert (rs[~valid] == -1).all()
    assert ((rs[valid] >= 0) & (rs[valid] < conns.shape[1])).all()
    # perm_lat / inv_lat are each other's inverse, lat_sorted ascends
    c = conns.shape[1]
    assert (np.asarray(pull.permute_rows(tabs.perm_lat, tabs.inv_lat))
            == np.arange(c)).all()
    assert (np.diff(np.asarray(tabs.lat_sorted), axis=-1) >= 0).all()
    g_sorted = _rows_of("f32", jax.random.PRNGKey(6), *conns.shape)
    g_slot = jnp.take_along_axis(g_sorted, tabs.inv_lat, axis=-1)
    got = pull.reciprocal_pull_min(g_sorted, conns, tabs.rev_sorted)
    want = pull.reciprocal_pull_min(g_slot, conns, rev)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    # and spelt out as the two-index gather of the issue
    cn, rv = np.clip(np.asarray(conns), 0, None), np.clip(np.asarray(rev), 0, None)
    np.testing.assert_array_equal(
        np.asarray(g_sorted)[cn, np.clip(rs, 0, None)][valid],
        np.asarray(g_slot)[cn, rv][valid])


# ------------------------------------------------------ sparse reciprocity --

K = 8      # `_SPARSE_ROWS` for these tests: both sides of the cond at n=300


@pytest.fixture
def sparse_at_k(monkeypatch):
    """The sparse route at this file's small shapes, K sending rows wide
    (nothing here is jitted, so the constants are read at every call)."""
    monkeypatch.setattr(pull, "_SPARSE_MIN_DENSE_BYTES", 0)
    monkeypatch.setattr(pull, "_SPARSE_ROWS", K)


def _sending(conns, rows, seed, on_pads=False):
    """An edge mask with `rows` non-empty rows (None: every row): one to
    three marked slots each, on pad slots too if asked."""
    n, c = conns.shape
    rng = np.random.default_rng(seed)
    m = np.zeros((n, c), bool)
    senders = np.arange(n) if rows is None else rng.choice(n, rows, False)
    cn = np.asarray(conns)
    for p in senders:
        slots = np.arange(c) if on_pads else np.flatnonzero(cn[p] >= 0)
        m[p, rng.choice(slots, rng.integers(1, 4), replace=False)] = True
    return jnp.asarray(m)


@pytest.mark.parametrize("on_pads", [False, True])
@pytest.mark.parametrize("rows", [0, 1, K - 1, K, K + 1, None])
def test_sparse_send_is_the_dense_pull(edges, sparse_at_k, rows, on_pads):
    """reciprocal_send_bool against reciprocal_pull_bool and the loop
    reference, on both sides of the cond, marked pad slots included; the
    tally says which side ran and how many rows sent."""
    conns, rev = edges
    m = _sending(conns, rows, seed=11 + (rows or 0), on_pads=on_pads)
    sent = int(np.asarray(m).any(axis=-1).sum())
    got, tally = pull.reciprocal_send_bool(m, conns, rev)
    want = np.asarray(pull.reciprocal_pull_bool(m, conns, rev))
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(want, _ref_pull(m, conns, rev, False))
    assert np.asarray(tally).tolist() == [int(sent <= K), int(sent > K), sent]
    if rows and not on_pads:    # marks on pads alone deliver nothing
        assert want.any()
    jitted, _ = jax.jit(pull.reciprocal_send_bool)(m, conns, rev)
    np.testing.assert_array_equal(np.asarray(jitted), want)


def test_sparse_route_is_a_trace_time_choice(edges, monkeypatch):
    """Small shapes and batched callers keep the one dense program: no cond
    is traced; past the static bound there is one, and a scatter in it."""
    conns, rev = edges
    m = _sending(conns, 3, seed=1)

    def text(**kw):
        return str(jax.make_jaxpr(
            lambda m: pull.reciprocal_send_bool(m, conns, rev, **kw))(m))
    assert "cond" not in text() and "scatter" not in text()
    assert not pull.sparse_route((100_000, 40), batch_factor=4)
    assert pull.sparse_route((100_000, 40)) and not pull.sparse_route((1000, 40))
    monkeypatch.setattr(pull, "_SPARSE_MIN_DENSE_BYTES", 0)
    assert "cond" in text() and "scatter" in text()
    assert "cond" not in text(batch_factor=2)
    _, tally = pull.reciprocal_send_bool(m, conns, rev, batch_factor=2)
    assert np.asarray(tally).tolist() == [0, 1, 3]


@pytest.mark.parametrize("k", [1, 5, 64])
def test_sending_rows_are_the_first_k_senders(k):
    mask = np.zeros(200, bool)
    mask[[3, 17, 18, 150, 199]] = True
    got = np.asarray(pull.sending_rows(jnp.asarray(mask), k))
    want = np.full(k, 200)
    want[:min(k, 5)] = np.flatnonzero(mask)[:k]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("changed", [0, 1, K, K + 1, 120])
def test_neighbor_update_is_a_fresh_neighbor_pull(edges, sparse_at_k, changed):
    """The carried neighbour view after `changed` peers flipped, against
    neighbor_pull_bool of the new vector: on both sides of the cond, the
    unchanged peers' slots untouched."""
    conns, rev = edges
    n = conns.shape[0]
    rng = np.random.default_rng(changed)
    old = rng.random(n) < 0.8
    flips = np.zeros(n, bool)
    flips[rng.choice(n, changed, replace=False)] = True
    new = jnp.asarray(old ^ flips)
    carried = pull.neighbor_pull_bool(jnp.asarray(old), conns, rev)
    got, tally = pull.neighbor_update_bool(
        carried, new, jnp.asarray(flips), conns, rev)
    want = np.asarray(pull.neighbor_pull_bool(new, conns, rev))
    np.testing.assert_array_equal(np.asarray(got), want)
    assert np.asarray(tally).tolist() == [
        int(changed <= K), int(changed > K), changed]
    if changed:
        assert (want != np.asarray(carried)).any()


# --------------------------------------------- lanes in the gathered row --

LANES = [1, 2, 4, 9]


def _lane_tables(kind, f, conns, seed):
    """(F, N, C) tables with INF (f32) / False-heavy (bool) entries."""
    n, c = conns.shape
    return jnp.stack([_rows_of(kind, jax.random.PRNGKey(seed + k), n, c)
                      for k in range(f)])


def _jaxpr_gathers(fn, *args):
    """(slice_sizes, output shape) of every gather `fn` traces."""
    from test_exact_prefix import _gathers

    return [(ss, shape)
            for _, ss, shape in _gathers(jax.make_jaxpr(fn)(*args).jaxpr)]


@pytest.mark.parametrize("f", LANES)
@pytest.mark.parametrize("name,kind", [
    ("reciprocal_pull_min", "f32"), ("reciprocal_pull_bool", "bool")])
def test_vmapped_lanes_are_the_per_lane_pulls(edges, name, kind, f):
    """ISSUE 41: under the vmap a caller declares (`batch_factor` lanes
    wide), batching the table alone, the lanes take ONE gather with every
    lane's C columns in the gathered row. Bit for bit the pull of each lane
    alone, -1 slots and INF values included."""
    conns, rev = edges
    assert bool((conns < 0).any())
    fn = getattr(pull, name)
    vals = _lane_tables(kind, f, conns, seed=20)
    got = jax.vmap(lambda v: fn(v, conns, rev, batch_factor=f))(vals)
    want = jnp.stack([fn(vals[k], conns, rev) for k in range(f)])
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    jitted = jax.jit(jax.vmap(
        lambda v: fn(v, conns, rev, batch_factor=f)))(vals)
    assert np.asarray(jitted).tobytes() == np.asarray(want).tobytes()
    # and in a loop's body, which is batched as a jaxpr, not as it is traced
    looped = jax.vmap(lambda v: jax.lax.fori_loop(
        0, 2, lambda _, x: fn(x, conns, rev, batch_factor=f), v))(vals)
    twice = jnp.stack([fn(want[k], conns, rev) for k in range(f)])
    assert np.asarray(looped).tobytes() == np.asarray(twice).tobytes()


@pytest.mark.parametrize("f", LANES)
def test_vmapped_neighbor_rows_min_is_the_per_lane_lookup(edges, f):
    """The per-peer lookup of F lanes: one gather of an (N, F) table, lane f
    its column f; the lookups one by one, bit for bit, INF included."""
    conns, _ = edges
    n = conns.shape[0]
    u = jax.random.uniform(jax.random.PRNGKey(30), (f, n))
    per_peer = jnp.where(u < 0.7, u * 1e6, pull.INF)
    shuffled = jax.random.permutation(
        jax.random.PRNGKey(31), conns, axis=1, independent=True)
    got = jax.vmap(lambda t: pull.neighbor_rows_min(
        t, shuffled, batch_factor=f))(per_peer)
    want = jnp.stack([pull.neighbor_rows_min(per_peer[k], shuffled)
                      for k in range(f)])
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


@pytest.mark.parametrize("name", ["neighbor_pull_min", "neighbor_pull_bool"])
def test_vmapped_neighbor_pulls_are_the_per_lane_pulls(edges, name):
    conns, rev = edges
    n = conns.shape[0]
    u = jax.random.uniform(jax.random.PRNGKey(32), (4, n))
    per_peer = u < 0.5 if name.endswith("bool") else u * 1e3
    fn = getattr(pull, name)
    got = jax.vmap(lambda t: fn(t, conns, rev, batch_factor=4))(per_peer)
    want = jnp.stack([fn(per_peer[k], conns, rev) for k in range(4)])
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


def test_the_gathers_a_pull_traces(edges):
    """Unbatched, a pull holds the one gather it always had: rows of C
    values, (N, C, C) gathered. Under the declared vmap over the table alone
    it holds ONE gather of rows F*C wide; a lookup's gather is F wide. A vmap
    nobody declared, or of another width than declared (the nested device
    grids: lanes spread over devices), keeps the batched gather of rows of
    C."""
    conns, rev = edges
    n, c = conns.shape
    v = _rows_of("f32", jax.random.PRNGKey(40), n, c)
    assert _jaxpr_gathers(
        lambda v: pull.reciprocal_pull_min(v, conns, rev), v) \
        == [((1, c), (n, c, c))]
    assert _jaxpr_gathers(
        lambda t: pull.neighbor_rows_min(t, conns), v[:, 0]) \
        == [((1, c), (n, c, c))]
    for f in (2, 4, 9):
        vals = _lane_tables("f32", f, conns, seed=41)
        assert _jaxpr_gathers(
            jax.vmap(lambda v: pull.reciprocal_pull_min(
                v, conns, rev, batch_factor=f)),
            vals) == [((1, f * c), (n, c, f * c))]
        assert _jaxpr_gathers(
            jax.vmap(lambda m: pull.reciprocal_pull_bool(
                m, conns, rev, batch_factor=f)),
            vals < 5e5) == [((1, f * c), (n, c, f * c))]
        assert _jaxpr_gathers(
            jax.vmap(lambda t: pull.neighbor_rows_min(
                t, conns, batch_factor=f)),
            vals[:, :, 0]) == [((1, f), (n, c, f))]
        for declared in (1, f + 1):
            assert _jaxpr_gathers(
                jax.vmap(lambda v: pull.reciprocal_pull_min(
                    v, conns, rev, batch_factor=declared)),
                vals) == [((f, 1, c), (f, n, c, c))]


def test_a_batched_index_keeps_the_batched_gather(edges):
    """Trials with a graph each (the index carries the batch axis too) and a
    packed row past the budget take the gather they always took: F rows of C
    values, never a row F*C wide; same bits either way."""
    conns, rev = edges
    n, c = conns.shape
    vals = _lane_tables("f32", 3, conns, seed=50)
    perms = jnp.stack([jax.random.permutation(jax.random.PRNGKey(k), n)
                       for k in range(3)])
    # three relabelled copies of the graph: conns'[p] = perm[conns[inv[p]]]
    inv = jnp.argsort(perms, axis=-1)
    cn = jnp.stack([jnp.where(conns[inv[k]] >= 0,
                              perms[k][jnp.clip(conns[inv[k]], 0)], -1)
                    for k in range(3)])
    rv = jnp.stack([rev[inv[k]] for k in range(3)])
    batched = jax.vmap(
        lambda v, q, r: pull.reciprocal_pull_min(v, q, r, batch_factor=3))
    widths = {ss[-1] for ss, _ in _jaxpr_gathers(batched, vals, cn, rv)}
    assert widths == {c}
    got = batched(vals, cn, rv)
    want = jnp.stack([pull.reciprocal_pull_min(vals[k], cn[k], rv[k])
                      for k in range(3)])
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


def test_a_packed_row_past_the_budget_gathers_single_elements(
        edges, monkeypatch):
    """Where the declared lanes' packed row is past the budget they take
    the 2-index gather, as declared lanes past it always did; so do lanes
    whose rows, gathered one by one (the index batched too), pass it
    together. Same bits."""
    conns, rev = edges
    n, c = conns.shape
    vals = _lane_tables("f32", 9, conns, seed=60)

    def shared(bf):
        return jax.vmap(lambda v: pull.reciprocal_pull_min(
            v, conns, rev, batch_factor=bf))

    want = np.asarray(shared(9)(vals))
    assert {ss[-1] for ss, _ in _jaxpr_gathers(shared(9), vals)} == {9 * c}
    # room for one lane's row, one tile wide; nine lanes' columns fill two
    monkeypatch.setattr(pull, "_MAX_INTERMEDIATE_BYTES",
                        pull.intermediate_bytes(jnp.float32, (n, c)))
    assert not pull.exceeds_budget(jnp.float32, (n, c), 128 // c)
    assert pull.exceeds_budget(jnp.float32, (n, c), 9)
    assert {ss[-1] for ss, _ in _jaxpr_gathers(shared(9), vals)} == {1}
    assert np.asarray(shared(9)(vals)).tobytes() == want.tobytes()
    # five lanes' columns fit the one tile; with an index a lane their five
    # rows do not
    each = jax.vmap(lambda v, q, r: pull.reciprocal_pull_min(
        v, q, r, batch_factor=5))
    five = (vals[:5], jnp.stack([conns] * 5), jnp.stack([rev] * 5))
    assert {ss[-1] for ss, _ in _jaxpr_gathers(each, *five)} == {1}
    assert np.asarray(each(*five)).tobytes() == want[:5].tobytes()
    assert {ss[-1] for ss, _ in _jaxpr_gathers(shared(5), vals[:5])} \
        == {5 * c}


def test_vmapped_trials_of_one_graph_keep_their_bits(edges):
    """The campaign's position: heartbeats vmapped over trials that share
    one graph. Their reciprocity and neighbour pulls share one gathered row
    from PR 41 on; every leaf is the trial's own run's."""
    from dst_libp2p_test_node_tpu.ops.heartbeat import heartbeat_step
    from dst_libp2p_test_node_tpu.ops.state import SimParams, init_state

    g = build_connection_graph(120, 6, seed=3)
    a = graph_arrays(g)
    params = SimParams(n=120, capacity=g.capacity)
    args = (a["conns"], a["rev"], a["out_mask"], params)
    alone = [init_state(params, seed=s) for s in (1, 2, 3)]
    together = jax.tree.map(lambda *xs: jnp.stack(xs), *alone)
    step = jax.vmap(lambda s: heartbeat_step(s, *args, batch_factor=3))
    assert {ss[-1] for ss, _ in _jaxpr_gathers(step, together)
            if len(ss) == 2} == {3 * g.capacity}
    for _ in range(4):
        together = step(together)
        alone = [heartbeat_step(s, *args) for s in alone]
    assert bool(together.mesh_mask.any())
    for k, s in enumerate(alone):
        for x, y in zip(jax.tree.leaves(together), jax.tree.leaves(s)):
            if jnp.issubdtype(y.dtype, jax.dtypes.prng_key):
                x, y = jax.random.key_data(x), jax.random.key_data(y)
            assert np.asarray(x[k]).tobytes() == np.asarray(y).tobytes()


# ------------------------------------------- the two bands of a publish's pull

BC, BC1 = 16, 8     # slots and the cut of the hand-made graphs below


def _graph_of(n, c, pairs):
    """conns / rev of the undirected `pairs`, a row's slots filled from the
    front in the order given (build_connection_graph's layout)."""
    conns = np.full((n, c), -1, np.int32)
    rev = np.full((n, c), -1, np.int32)
    deg = np.zeros(n, np.int64)
    for p, q in pairs:
        i, j = deg[p], deg[q]
        conns[p, i], rev[p, i], conns[q, j], rev[q, j] = q, j, p, i
        deg[p] += 1
        deg[q] += 1
    return conns, rev


def _cut(conns, rev, p, i):
    """Clear slot i of row p and its reverse slot: a hole where it lies in
    front of a filled slot (what ops/connmanager and ops/repair leave)."""
    q, j = conns[p, i], rev[p, i]
    conns[p, i] = rev[p, i] = conns[q, j] = rev[q, j] = -1


@pytest.fixture(scope="module", params=["degrees", "holes"])
def banded(request):
    """A 40-peer graph whose rows have degree C (peer 0), exactly C1 (1),
    C1 + 1 (2) and 0 (39); `holes`: with cleared slots in front of filled
    ones, one of which leaves peer 2 with C1 connections and one of them
    still in slot C1 (heavy by its slots, not by its degree). With it the
    lat-sorted tables and the bands of all four index arrays."""
    from dst_libp2p_test_node_tpu.ops.disseminate import answer_tables

    pairs = ([(0, q) for q in range(1, BC + 1)]
             + [(1, q) for q in range(17, 17 + BC1 - 1)]
             + [(2, q) for q in range(17, 17 + BC1)]
             + [(30, 31), (31, 32), (5, 33)])
    conns, rev = _graph_of(40, BC, pairs)
    deg = (conns >= 0).sum(axis=-1)
    assert [deg[0], deg[1], deg[2], deg[39]] == [BC, BC1, BC1 + 1, 0]
    if request.param == "holes":
        _cut(conns, rev, 0, 2)
        _cut(conns, rev, 2, 0)
        _cut(conns, rev, 18, 0)
        assert (conns[2] >= 0).sum() == BC1 and conns[2, BC1] >= 0
    conns, rev = jnp.asarray(conns), jnp.asarray(rev)
    lat_edge = jnp.where(
        conns >= 0,
        40.0 + 90.0 * jax.random.uniform(jax.random.PRNGKey(5), conns.shape),
        0.0)
    tabs = answer_tables(lat_edge, conns, rev)
    bands = pull.make_pull_bands(
        conns, rev, tabs.conns_sorted, tabs.rev_sorted,
        min_bytes=0, c1=BC1, rows=4)
    assert bands is not None
    return conns, rev, tabs, bands


# what is pulled, through which two index arrays (None: no reverse map)
_BANDED_PULLS = {
    "min": (pull.reciprocal_pull_min, "f32", "conns", "rev"),
    "bool": (pull.reciprocal_pull_bool, "bool", "conns", "rev"),
    "min_lat": (pull.reciprocal_pull_min, "f32", "conns", "rev_sorted"),
    "rows_min": (pull.neighbor_rows_min, "peer", "conns", None),
    "rows_min_lat": (pull.neighbor_rows_min, "peer", "conns_sorted", None),
    "neighbor_min": (pull.neighbor_pull_min, "peer", "conns", "rev"),
    "neighbor_bool": (pull.neighbor_pull_bool, "peer_bool", "conns", "rev"),
}


def _banded_case(name, banded, lanes):
    """(fn, vals, whole index, banded index) of a `_BANDED_PULLS` case,
    `vals` with a leading axis of `lanes` where lanes > 1."""
    conns, rev, tabs, bands = banded
    fn, kind, *names = _BANDED_PULLS[name]
    arrays = {"conns": conns, "rev": rev, "conns_sorted": tabs.conns_sorted,
              "rev_sorted": tabs.rev_sorted}
    n, c = conns.shape
    lead = (lanes,) if lanes > 1 else ()
    key = jax.random.PRNGKey(len(name) + lanes)
    if kind in ("f32", "bool"):
        vals = _rows_of(kind, key, int(np.prod(lead + (n,))), c).reshape(
            lead + (n, c))
    else:
        u = jax.random.uniform(key, lead + (n,))
        vals = (u < 0.5 if kind == "peer_bool"
                else jnp.where(u < 0.7, u * 1e6, pull.INF))
    names = [x for x in names if x is not None]
    return (fn, vals, [arrays[x] for x in names],
            [bands.of(x) for x in names])


@pytest.mark.parametrize("lanes", [1, 4])
@pytest.mark.parametrize("name", sorted(_BANDED_PULLS))
def test_banded_pull_is_the_whole_pull(banded, name, lanes):
    """ISSUE 50: every pull of the publish through the two bands of its
    index (slots [0, C1) of every row, the rest of the heavy rows) returns
    the whole-width pull's bits: rows of degree 0, C1, C1 + 1 and C, rows
    with holes, the slot and the lat-sorted layout, one lane and four
    declared lanes under vmap, eager and jitted."""
    fn, vals, whole, bands = _banded_case(name, banded, lanes)

    def run(index):
        if lanes == 1:
            return fn(vals, *index)
        return jax.vmap(lambda v: fn(v, *index, batch_factor=lanes))(vals)

    want = np.asarray(run(whole))
    got = run(bands)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.asarray(got).tobytes() == want.tobytes()
    assert np.asarray(jax.jit(run)(bands)).tobytes() == want.tobytes()
    # the heavy rows are where the test says they are: band B is not idle
    conns = np.asarray(banded[0])
    assert (conns[:, BC1:] >= 0).any(axis=-1).sum() in (2, 3)


def _gathers(jaxpr):
    """(output shape, batched?) of every gather in a printed jaxpr."""
    import re

    return [(tuple(int(d) for d in m.group(1).split(",")),
             "operand_batching_dims=()" not in m.group(2))
            for m in re.finditer(
                r"\w+\[([\d,]+)\] = gather\[(.*?)\n\s+\] ", str(jaxpr), re.S)]


@pytest.mark.parametrize("name", ["min", "bool", "rows_min"])
def test_four_lanes_share_one_gather_a_band(banded, name):
    """Four declared lanes under vmap: ONE gather a band with every lane in
    the gathered row and one for band B's way back, none of them XLA's own
    batching of a one-lane gather (388 ms against 25.7 at 100k peers); the
    index shapes are (N, C1) and (M, C - C1), then (N,)."""
    fn, vals, _, bands = _banded_case(name, banded, 4)
    n, c, m = 40, BC, 4
    jaxpr = jax.make_jaxpr(
        jax.vmap(lambda v: fn(v, *bands, batch_factor=4)))(vals)
    width = 4 if name == "rows_min" else 4 * c
    assert _gathers(jaxpr) == [
        ((n, BC1, width), False), ((m, c - BC1, width), False),
        ((n, 4 * (c - BC1)), False)]
    # and one lane: the same three, one table wide
    one = jax.make_jaxpr(lambda v: fn(v, *bands))(vals[0])
    width = BC1 if name == "rows_min" else c
    assert [g[0] for g in _gathers(one)][:2] == [
        (n, BC1, width), (m, c - BC1, c - BC1 if name == "rows_min" else c)]


@pytest.mark.parametrize("why", ["size", "heavy", "mesh", "budget", "cut"])
def test_no_bands_where_the_whole_pull_stays(banded, monkeypatch, why):
    """The maker returns None under the size test (the small shapes keep
    the one program they had), with more than M heavy rows (a skewed or
    capped graph), on a mesh, past the gather budget and where the cut
    leaves no second band."""
    conns, rev, tabs, _ = banded
    kw = dict(min_bytes=0, c1=BC1, rows=4)
    if why == "size":
        kw.pop("min_bytes")
    elif why == "heavy":
        kw["rows"] = 1
    elif why == "mesh":
        kw["mesh"] = object()
    elif why == "budget":
        monkeypatch.setattr(pull, "_MAX_INTERMEDIATE_BYTES", 1)
    else:
        kw["c1"] = BC
    assert pull.make_pull_bands(conns, rev, **kw) is None
    assert pull.pull_rows_share(None) == 100.0


def test_band_shape_is_static_and_the_reference_graph_fits_it():
    """(C1, M) comes from the shape alone, (24, 12504) at (100000, 40), 65 %
    of the rows; the graph of the 100,000-peer cells is front-compacted,
    half pads, and 8.4 % of its rows are heavy, so the bands exist there."""
    assert pull.band_shape((100000, 40)) == (24, 12504)
    assert pull.band_shape((2000, 40)) == (24, 256)
    g = build_connection_graph(100000, 10, seed=3, max_degree=40)
    filled = g.conns >= 0
    assert 0.49 < filled.mean() < 0.51
    assert (filled[:, :-1] >= filled[:, 1:]).all()      # front-compacted
    heavy = int(filled[:, 24:].any(axis=-1).sum())
    assert 7000 < heavy < 10000
    bands = pull.make_pull_bands(jnp.asarray(g.conns), jnp.asarray(g.rev))
    assert bands.heads["conns"].shape == (100000, 24)
    assert bands.tails["rev"].shape == (12504, 16)
    assert int((np.asarray(bands.back) < 12504).sum()) == heavy
    assert pull.pull_rows_share(bands) == pytest.approx(65.0016)


# ------------------------------------- the moved rows of a float fixpoint --

RK = 8      # `_RELAX_ROWS` for these tests


@pytest.fixture(scope="module")
def relax_at_k():
    """K = 8 moved rows (module-wide: the jitted loops below bake it in)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pull, "_RELAX_ROWS", RK)
        yield


@pytest.fixture(scope="module", params=["pads", "holes"])
def relaxed(request, relax_at_k):
    """A random 300-peer graph (half of its slots pads); `holes`: with 60
    connections cut, some in front of filled slots. With it the bands of
    its index and per-edge costs for four lanes."""
    g = build_connection_graph(300, 6, seed=9)
    a = graph_arrays(g)
    conns, rev = np.array(a["conns"]), np.array(a["rev"])
    if request.param == "holes":
        rng = np.random.default_rng(4)
        for p in rng.choice(300, 60, replace=False):
            slots = np.flatnonzero(conns[p] >= 0)
            if slots.size:
                _cut(conns, rev, p, rng.choice(slots))
        filled = conns >= 0
        assert (filled[:, :-1] < filled[:, 1:]).any()   # a hole in front
    conns, rev = jnp.asarray(conns), jnp.asarray(rev)
    c = conns.shape[1]
    bands = pull.make_pull_bands(conns, rev, min_bytes=0, c1=c // 2, rows=300)
    assert bands is not None
    key = jax.random.PRNGKey(2)
    cost = jnp.where(conns >= 0, 1.0 + 9.0 * jax.random.uniform(
        key, (4,) + conns.shape), pull.INF)
    busy = 5.0 * jax.random.uniform(jax.random.fold_in(key, 1), (300,))
    return conns, rev, bands, cost, busy


def _offer(t, busy, cost):
    """Row p from t[p] and row p of the tables alone, INF from a row that
    has not been reached: the shape of ops/disseminate's offers."""
    return jnp.where((t < pull.INF)[:, None],
                     jnp.maximum(t + 0.5, busy)[:, None] + cost, pull.INF)


def _relax(net, t0, cost, cap, lanes, by_rows, banded=True):
    """ops/disseminate._converge_dyn's loop over `_offer`: (t, inc, ok,
    iterations, iterations that delivered the moved rows)."""
    conns, rev, bands, _, busy = net
    via = (bands.of("conns"), bands.of("rev")) if banded else (conns, rev)

    def body(carry):
        t, inc, _, it, moved, few = carry
        if by_rows:
            inc, sparse = pull.pull_moved_min(
                _offer, t, inc, moved, (busy, cost), conns, rev, *via,
                batch_factor=lanes)
        else:
            inc = pull.reciprocal_pull_min(_offer(t, busy, cost), *via, lanes)
            sparse = 0
        t_new = jnp.minimum(t, inc.min(axis=-1))
        moved = t_new < t
        return t_new, inc, jnp.any(moved), it + 1, moved, few + sparse

    t, inc, changed, it, _, few = jax.lax.while_loop(
        lambda carry: carry[2] & (carry[3] < cap), body,
        (t0, jnp.full(conns.shape, pull.INF), jnp.bool_(True), jnp.int32(0),
         t0 < pull.INF, jnp.int32(0)))
    return t, inc, ~changed, it, few


def _relax_lanes(net, t0, cap, lanes, by_rows, banded=True):
    cost = net[3]
    if lanes == 1:
        return _relax(net, t0[0], cost[0], cap, 1, by_rows, banded)
    return jax.vmap(lambda t, a: _relax(net, t, a, cap, lanes, by_rows,
                                        banded))(t0, cost)


def _same_bits(want, got):
    for w, g in zip(want, got):
        w, g = np.asarray(w), np.asarray(g)
        assert w.shape == g.shape and w.dtype == g.dtype
        assert w.tobytes() == g.tobytes()


@pytest.mark.parametrize("lanes", [1, 4])
@pytest.mark.parametrize("rows", [0, 1, RK, RK + 1])
def test_moved_rows_delivered_are_the_dense_pull(relaxed, rows, lanes):
    """ISSUE 51, one step: given the pull of the offers at `t` and `rows`
    rows that then moved, pull_moved_min returns the pull of the offers at
    the new times, bit for bit, and says which side ran: sparse with up to
    K rows, in EVERY lane where there are four (lane 1 moves no row, lane 2
    one; a lane with K + 1 sends all four to the dense side). Pads and
    holes, the whole index and its bands."""
    conns, rev, bands, cost, busy = relaxed
    n = conns.shape[0]
    rng = np.random.default_rng(rows + lanes)
    t = jnp.asarray(np.where(rng.random((4, n)) < 0.8,
                             50.0 * rng.random((4, n)), np.inf)
                    .astype(np.float32)).clip(max=pull.INF)
    moved = np.zeros((4, n), bool)
    for lane, count in enumerate((rows, 0, 1, rows)):
        moved[lane, rng.choice(n, count, replace=False)] = True
    # a moved row may come from INF (a row reached for the first time)
    t_new = jnp.where(moved, 40.0 * rng.random((4, n)).astype(np.float32), t)
    moved = jnp.asarray(moved)

    def step(t, t_new, moved, cost, via):
        inc = pull.reciprocal_pull_min(_offer(t, busy, cost), conns, rev,
                                       lanes)
        return pull.pull_moved_min(_offer, t_new, inc, moved, (busy, cost),
                                   conns, rev, *via, batch_factor=lanes)

    def fresh(t_new, cost):
        return pull.reciprocal_pull_min(_offer(t_new, busy, cost), conns,
                                        rev, lanes)

    for via in ((), (bands.of("conns"), bands.of("rev"))):
        if lanes == 1:
            got, sparse = jax.jit(lambda *a: step(*a, via))(
                t[0], t_new[0], moved[0], cost[0])
            want = fresh(t_new[0], cost[0])
        else:
            got, sparse = jax.jit(jax.vmap(lambda *a: step(*a, via)))(
                t, t_new, moved, cost)
            want = jax.vmap(fresh)(t_new, cost)
        _same_bits([want], [got])
        assert np.asarray(sparse).tolist() == (
            int(rows <= RK) if lanes == 1 else [int(rows <= RK)] * 4)
    if rows:
        assert (np.asarray(want) < np.asarray(pull.INF)).any()


@pytest.mark.parametrize("cap", [3, 64])
@pytest.mark.parametrize("start", ["cold", "warm"])
@pytest.mark.parametrize("lanes", [1, 4])
def test_relaxing_the_moved_rows_is_the_fixpoint(relaxed, lanes, start, cap):
    """ISSUE 51, the loop: `t`, the carried offer matrix, the convergence
    bit and the iteration count of a Bellman-Ford relaxation that delivers
    the moved rows' offers are the dense loop's bit for bit: from a cold
    start (one finite row, a publisher a lane) and from a `t_init` (an
    upper bound on every row: the first iteration is dense), converged and
    cut by the iteration cap ("one pass stale" included)."""
    n = relaxed[0].shape[0]
    t0 = jnp.full((4, n), pull.INF).at[jnp.arange(4), jnp.array(
        [3, 77, 150, 299])].set(jnp.arange(4.0))
    if start == "warm":
        fix = _relax_lanes(relaxed, t0, 64, 4, False)[0]
        t0 = jnp.where(fix < pull.INF, fix * 1.5 + 3.0, pull.INF).at[
            jnp.arange(4), jnp.array([3, 77, 150, 299])].set(jnp.arange(4.0))
    want = jax.jit(lambda t: _relax_lanes(relaxed, t, cap, lanes, False))(t0)
    got = jax.jit(lambda t: _relax_lanes(relaxed, t, cap, lanes, True))(t0)
    _same_bits(want[:4], got[:4])
    its, few = np.asarray(got[3]), np.asarray(got[4])
    assert bool(np.all(np.asarray(got[2]))) is (cap == 64)
    assert np.all(few <= its)
    if cap == 64:
        # the first iterations (cold) and the last ones deliver rows
        assert np.all(few >= (2 if start == "cold" else 1)), (few, its)
        assert np.all(few < its)
    assert np.all(np.asarray(want[4]) == 0)
    # the whole index in place of the bands: the same loop
    plain = jax.jit(lambda t: _relax_lanes(
        relaxed, t, cap, lanes, True, banded=False))(t0)
    _same_bits(want[:4], plain[:4])


def _eqns(jaxpr, inside=()):
    """(primitive name, eqn, names of the enclosing eqns) of every equation
    of a jaxpr and of the jaxprs in its equations' params."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, eqn, inside
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, inside + (eqn.primitive.name,))


def test_lanes_relax_on_one_scalar_predicate(relaxed):
    """Four declared lanes take ONE `cond`, its predicate a scalar (every
    lane's rows fit, or none is delivered), the scatter in one of its
    branches and the gathers of the pull in the other: no select of the two
    sides. A vmap nobody declared, and a vmap around the lanes', keep the
    dense body: no cond, no scatter."""
    n = relaxed[0].shape[0]
    t0 = jnp.full((4, n), pull.INF).at[:, 5].set(0.0)

    def conds(fn, *args):
        eqns = list(_eqns(jax.make_jaxpr(fn)(*args).jaxpr))
        found = [e for name, e, _ in eqns if name == "cond"]
        loose = [e for name, e, inside in eqns
                 if name == "scatter" and "cond" not in inside]
        return found, loose

    found, loose = conds(lambda t: _relax_lanes(relaxed, t, 64, 4, True), t0)
    assert len(found) == 1 and not loose
    (cond,) = found
    assert cond.invars[0].aval.shape == ()
    sides = [{name for name, _, _ in _eqns(b.jaxpr)}
             for b in cond.params["branches"]]
    assert sorted("scatter" in s for s in sides) == [False, True]
    assert sorted("gather" in s and "scatter" not in s for s in sides) == [
        False, True]
    # lanes nobody declared (three of a declared four), and trials around
    # the four lanes: dense
    for fn, arg in (
            (lambda t: jax.vmap(lambda t, a: _relax(
                relaxed, t, a, 64, 4, True))(t, relaxed[3][:3]), t0[:3]),
            (jax.vmap(lambda t: _relax_lanes(relaxed, t, 64, 4, True)),
             jnp.stack([t0, t0]))):
        found, loose = conds(fn, arg)
        assert not found and not loose
    want = _relax_lanes(relaxed, t0, 64, 4, False)
    got = jax.vmap(lambda t: _relax_lanes(relaxed, t, 64, 4, True))(
        jnp.stack([t0, t0]))
    _same_bits(want[:4], [x[1] for x in got[:4]])
    assert not np.asarray(got[4]).any()


def test_relax_route_is_the_size_test_of_the_bands():
    """The trace-time half: the f32 test `make_pull_bands` makes, so 1,000
    and 2,048 peers keep the dense program and 10,000 pass."""
    assert not pull.relax_route((1000, 40))
    assert not pull.relax_route((2048, 40))
    assert pull.relax_route((10000, 40))
    assert pull.relax_route((100000, 40))


# ------------------- the receivers' times of a refinement pass, by the rows

def _lat_tables(net):
    """The lat-sorted index of a `relaxed` network and the bands of it
    (ops/disseminate.answer_tables on random latencies)."""
    from dst_libp2p_test_node_tpu.ops.disseminate import answer_tables

    conns, rev = net[0], net[1]
    lat = jnp.where(conns >= 0, 40.0 + 90.0 * jax.random.uniform(
        jax.random.PRNGKey(8), conns.shape), 0.0)
    tabs = answer_tables(lat, conns, rev)
    c = conns.shape[1]
    bands = pull.make_pull_bands(conns, rev, tabs.conns_sorted,
                                 tabs.rev_sorted, min_bytes=0, c1=c // 2,
                                 rows=300)
    return tabs, bands


@pytest.mark.parametrize("layout", ["slot", "lat"])
@pytest.mark.parametrize("lanes", [1, 4])
@pytest.mark.parametrize("rows", [0, 1, RK, RK + 1])
def test_moved_peers_delivered_are_the_per_peer_lookup(
        relaxed, rows, lanes, layout):
    """ISSUE 53: given `neighbor_rows_min` of a per-peer vector and `rows`
    peers whose value then moved (up or down), neighbor_update_min returns
    the lookup of the new vector, bit for bit, and says which side ran:
    the delivery with up to K moved peers in EVERY lane, the lookup with
    more; through the slot layout (`conns`, `rev`) and through the lat
    order (`conns_sorted`, looked up whole or banded, delivered through
    `conns` and `rev_sorted`); pads and holes."""
    conns, rev = relaxed[0], relaxed[1]
    tabs, bands = _lat_tables(relaxed)
    if layout == "slot":
        via, by, back = conns, conns, rev
        p_via = bands.of("conns")
    else:
        via, by, back = tabs.conns_sorted, conns, tabs.rev_sorted
        p_via = bands.of("conns_sorted")
    n = conns.shape[0]
    key = jax.random.PRNGKey(rows + lanes)
    u = jax.random.uniform(key, (4, n))
    old = jnp.where(u < 0.8, u * 1e5, pull.INF)[:lanes]
    counts = [rows, 0, 1, min(rows, 2)][:lanes]
    moved = jnp.stack([
        jnp.zeros((n,), bool).at[jax.random.choice(
            jax.random.fold_in(key, k), n, (cnt,), replace=False)].set(True)
        for k, cnt in enumerate(counts)])
    # a moved peer rises, falls, or becomes reached
    new = jnp.where(moved, jnp.where(old < pull.INF, old * 0.5 + 7.0, 3.0),
                    old)

    def step(nbr, t, m):
        return pull.neighbor_update_min(nbr, t, m, by, back, p_via, lanes)

    def lookup(t):
        return pull.neighbor_rows_min(t, via)

    if lanes == 1:
        got, side = jax.jit(step)(lookup(old[0]), new[0], moved[0])
        want = lookup(new[0])
    else:
        got, side = jax.jit(jax.vmap(step))(
            jnp.stack([lookup(t) for t in old]), new, moved)
        want = jnp.stack([lookup(t) for t in new])
    _same_bits([want], [got])
    assert np.all(np.asarray(side) == (1 if rows <= RK else 0))
    # and the plain index in place of the bands on the dense side
    plain, _ = pull.neighbor_update_min(
        lookup(old[0]), new[0], moved[0], by, back, via)
    _same_bits([lookup(new[0])], [plain])


def test_lanes_update_on_one_scalar_predicate(relaxed):
    """Four declared lanes take ONE `cond` on a scalar between the scatter
    of the moved peers' values and the lookup's gathers; a vmap nobody
    declared keeps the lookup."""
    conns = relaxed[0]
    tabs, bands = _lat_tables(relaxed)
    n = conns.shape[0]
    t = jnp.zeros((4, n))
    nbr = jnp.zeros((4,) + conns.shape)
    moved = jnp.zeros((4, n), bool)

    def step(lanes):
        return jax.vmap(lambda a, b, m: pull.neighbor_update_min(
            a, b, m, conns, tabs.rev_sorted, bands.of("conns_sorted"),
            lanes))

    eqns = list(_eqns(jax.make_jaxpr(step(4))(nbr, t, moved).jaxpr))
    conds = [e for name, e, _ in eqns if name == "cond"]
    assert len(conds) == 1 and conds[0].invars[0].aval.shape == ()
    sides = [{name for name, _, _ in _eqns(b.jaxpr)}
             for b in conds[0].params["branches"]]
    assert sorted("scatter" in s for s in sides) == [False, True]
    assert not [e for name, e, inside in eqns
                if name == "scatter" and "cond" not in inside]
    names = {name for name, _, _ in _eqns(
        jax.make_jaxpr(step(3))(nbr, t, moved).jaxpr)}
    assert "cond" not in names and "scatter" not in names
