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
