"""Exact-mode prefix engine pins (ISSUE 11 tentpole).

The parallel-prefix answer-queue refinement (SimParams.answer_queue_mode
= "parallel_prefix", the default) must reproduce the legacy serial engine
("serial", the pre-prefix model of record) on every result surface: bitwise
on the integer counters and delivery masks, to float tolerance on arrival
times, with the exactness certificate (converged=True) and a bounded pass
count. The receiver-side constants' layout and the fixpoint's hot gather
(parallel/exchange.py) are pinned here too.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dst_libp2p_test_node_tpu.config.topology import Topology, TopoParams
from dst_libp2p_test_node_tpu.ops.disseminate import disseminate
from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph
from dst_libp2p_test_node_tpu.ops.heartbeat import run_heartbeats
from dst_libp2p_test_node_tpu.ops.state import (
    SimParams, graph_arrays, init_state,
)

# the prefix engine's pass ceiling at these shapes: observed 6-8 Jacobi
# iterations where the serial engine pays 4 from-INF outer passes (each of
# which is itself a full nested fixpoint, ~15-20 inner sweeps at bench
# shapes) — a pass count past this bound means the Jacobi iteration lost
# its contraction and the certificate fallback is carrying the result
PASS_BUDGET = 32


def mesh_setup(*, n=100, connect_to=10, seed=0, hb=10, **over):
    g = build_connection_graph(n, connect_to, seed=seed)
    params = SimParams(n=n, capacity=g.capacity, **over)
    state = init_state(params, seed=seed)
    a = graph_arrays(g)
    state = run_heartbeats(state, a["conns"], a["rev"], a["out_mask"],
                           params, hb)
    t = Topology.build(
        TopoParams(network_size=n, anchor_stages=5, min_bandwidth=50,
                   max_bandwidth=150, min_latency=40, max_latency=130))
    topo = (jnp.asarray(t.stage_of_peer), jnp.asarray(t.latency_ms),
            jnp.asarray(t.bw_up_mbit))
    return g, params, state, a, topo


def _publish(state, a, topo, params, **kw):
    stage, lat, bw = topo
    kw.setdefault("publisher", 7)
    if "t0_ms" not in kw:
        kw["t0_ms"] = float(state.t_ms)
    return disseminate(
        state, a["conns"], a["rev"], stage, lat, bw,
        params=params, payload_bytes=15000, with_gossip=True, **kw)


def _pin_engines_equal(res_p, res_s, *, delay_rtol=1e-6):
    """The full equality contract between the two engines' results."""
    # integer surfaces and delivery masks: BITWISE
    np.testing.assert_array_equal(
        np.asarray(res_p.received), np.asarray(res_s.received))
    np.testing.assert_array_equal(
        np.asarray(res_p.lost_tx), np.asarray(res_s.lost_tx))
    assert int(np.asarray(res_p.answer_interleaved)) \
        == int(np.asarray(res_s.answer_interleaved))
    # arrival times: rtol (bitwise-equal on the CI CPU backend today, but
    # the contract is the model's, not the instruction scheduler's)
    ok = np.asarray(res_p.received)
    np.testing.assert_allclose(
        np.asarray(res_p.delay_ms)[ok], np.asarray(res_s.delay_ms)[ok],
        rtol=delay_rtol, atol=1e-2)
    # both certificates must hold — neither engine may ship a capped
    # fixpoint as exact
    assert bool(np.asarray(res_p.converged))
    assert bool(np.asarray(res_s.converged))


@pytest.mark.parametrize("kw,over", [
    ({}, {}),
    ({"fragments": 4}, {}),
    # publisher 3: on jax 0.9.0's random streams the default publisher's
    # draw forms no answer queue in this scenario (refine_passes == 0)
    ({"publisher": 3}, {"flood_publish": False, "d_lazy": 12}),
    ({"fragments": 3}, {"flood_publish": False, "d_lazy": 12}),
], ids=["mesh", "mesh-frag4", "gossip-heavy", "gossip-heavy-frag3"])
def test_prefix_matches_serial_engine(kw, over):
    g, params, state, a, topo = mesh_setup(**over)
    res_p, _ = _publish(state, a, topo, params, **kw)
    res_s, _ = _publish(
        state, a, topo,
        dataclasses.replace(params, answer_queue_mode="serial"), **kw)
    # the scenario must actually TRIGGER the refinement path on both
    # engines, else this test pins the shared fast pipeline against itself
    assert int(np.asarray(res_p.refine_passes)) > 0
    assert int(np.asarray(res_s.refine_passes)) > 0
    assert int(np.asarray(res_p.refine_passes)) <= PASS_BUDGET
    _pin_engines_equal(res_p, res_s)


@pytest.fixture
def one_lane_budget(monkeypatch):
    """Shrink ops/pull's gather budget to exactly one lane's row pull at the
    shape given: the lanes whose columns fill one 128-lane tile with one
    fragment's are in budget (three at C = 40), more together are not: the
    position the benchmark's runsh-100k-frag4 had until PR 41 put four lanes
    in one gathered row, kept alive at a test's size. The budget is read while
    `disseminate` is traced, so the jit cache is emptied on both sides."""
    import dst_libp2p_test_node_tpu.ops.pull as pull_mod

    def shrink(conns_shape):
        monkeypatch.setattr(
            pull_mod, "_MAX_INTERMEDIATE_BYTES",
            pull_mod.intermediate_bytes(jnp.float32, conns_shape))
        disseminate.clear_cache()

    yield shrink
    monkeypatch.undo()
    disseminate.clear_cache()


# four or five lanes of 40 columns are two 128-lane tiles of gathered row:
# past a budget of one lane's pull (three lanes are one tile, and fit it)
IN_SEQUENCE_CASES = [
    ({"fragments": 4}, {}),
    ({"fragments": 5}, {"flood_publish": False, "d_lazy": 12}),
]


def _loop_pulls(jaxpr, shape):
    """(slice_sizes, output shape) of the row gathers inside the prefix
    refinement's loops."""
    return [(ss, shp) for where, ss, shp in _gathers(jaxpr)
            if "refine" in where and "fixpoint" in where
            and "legacy" not in where and np.prod(shp) >= np.prod(shape)]


@pytest.mark.parametrize("kw,over", IN_SEQUENCE_CASES,
                         ids=["mesh-frag4", "gossip-heavy-frag5"])
def test_fragments_in_sequence_stay_on_row_pull_with_the_vmapped_bits(
        kw, over, one_lane_budget):
    """ISSUE 30: where the fragments' row pull passes the gather budget
    and one lane's alone does not, the publish takes the lanes one at a
    time in a rolled loop and keeps the row_pull formulation and the prefix
    engine (until PR 30 it went to "recv" and the global-sort engine). It
    is the in-budget vmapped publish, bit for bit, in every leaf of the
    result and of the new state, the counters' leaf (fast_iters,
    refine_passes, refine_lane_passes) among them. ISSUE 41: vmapped, the
    lanes share ONE gather with every lane's table in the gathered row."""
    from dst_libp2p_test_node_tpu.ops.disseminate import (
        fixpoint_formulation, fragments_in_sequence, lanes_in_pull)

    g, params, state, a, topo = mesh_setup(**over)
    shape = a["conns"].shape
    f, (n, c) = kw["fragments"], shape
    assert not fragments_in_sequence(shape, f)
    assert lanes_in_pull(shape, f) == f
    res_v, st_v = _publish(state, a, topo, params, **kw)
    packed = _loop_pulls(jax.make_jaxpr(
        lambda st: _publish(st, a, topo, params, t0_ms=0.0, **kw))(
            state).jaxpr, shape)
    # two phases, each one pull of receiver times (a column a lane) and one
    # of candidates (C columns a lane): four gathers for all the lanes
    assert sorted(packed) == sorted(
        2 * [((1, f), (n, c, f)), ((1, f * c), (n, c, f * c))])
    one_lane_budget(shape)
    assert lanes_in_pull(shape, f) == 1
    assert fragments_in_sequence(shape, kw["fragments"])
    assert not fragments_in_sequence(shape, 1)
    assert fixpoint_formulation(shape) == "row_pull"
    # a mesh unrolls its lanes as it did
    assert not fragments_in_sequence(shape, kw["fragments"], mesh=object())
    jaxpr = jax.make_jaxpr(
        lambda st: _publish(st, a, topo, params, t0_ms=0.0, **kw))(
            state).jaxpr
    big = [(where, ss) for where, ss, shp in _gathers(jaxpr)
           if np.prod(shp) >= np.prod(shape) and "legacy" not in where]
    # row pulls only (slice (1, C)) outside the global-sort rerun: no lane
    # fell back to the scalar gather of "recv" or of an over-budget pull,
    # and each engine is traced once, in the rolled loop, not once a
    # fragment
    assert big and all(ss[-1] == shape[1] for _, ss in big)
    in_loop = [ss for where, ss in big
               if "refine" in where and "fixpoint" in where
               and "legacy" not in where]
    assert len(in_loop) == 4
    res_q, st_q = _publish(state, a, topo, params, **kw)
    assert bool(res_q.refined) and not bool(res_q.refined_serial)
    got, want = _leaf_bytes((res_q, st_q)), _leaf_bytes((res_v, st_v))
    assert got.keys() == want.keys()
    for name in got:
        assert got[name].tobytes() == want[name].tobytes(), name


def test_three_lanes_share_the_tile_one_lane_fills(one_lane_budget):
    """Three lanes of 40 columns are 120 of a tile's 128: the packed row is
    the size of one lane's, so a budget that holds one lane holds them and
    they stay vmapped, one gather for the three."""
    from dst_libp2p_test_node_tpu.ops.disseminate import (
        fragments_in_sequence, lanes_in_pull)

    g, params, state, a, topo = mesh_setup(flood_publish=False, d_lazy=12)
    shape = a["conns"].shape
    one_lane_budget(shape)
    assert not fragments_in_sequence(shape, 3)
    assert lanes_in_pull(shape, 3) == 3
    assert fragments_in_sequence(shape, 4)
    pulls = _loop_pulls(jax.make_jaxpr(
        lambda st: _publish(st, a, topo, params, t0_ms=0.0, fragments=3))(
            state).jaxpr, shape)
    assert {ss[-1] for ss, _ in pulls} == {3, 3 * shape[1]}


@pytest.mark.parametrize("kw,over", IN_SEQUENCE_CASES,
                         ids=["mesh-frag4", "gossip-heavy-frag5"])
def test_prefix_matches_serial_engine_in_sequence(kw, over, one_lane_budget):
    """test_prefix_matches_serial_engine's fragment cases once more with
    the lanes in sequence: the rolled loop over phases_prefix against the
    rolled loop over phases_serial."""
    g, params, state, a, topo = mesh_setup(**over)
    one_lane_budget(a["conns"].shape)
    res_p, _ = _publish(state, a, topo, params, **kw)
    res_s, _ = _publish(
        state, a, topo,
        dataclasses.replace(params, answer_queue_mode="serial"), **kw)
    assert 0 < int(np.asarray(res_p.refine_passes)) <= PASS_BUDGET
    assert int(np.asarray(res_s.refine_passes)) > 0
    assert not bool(res_p.refined_serial) and bool(res_s.refined_serial)
    _pin_engines_equal(res_p, res_s)


def answer_star_setup(stages=1, latency=(100, 100)):
    """Empty mesh, no flood: the publisher's answers serialize back-to-back
    on its uplink (the hand-computed corner of test_disseminate
    .test_gossip_answer_serialization_exact). Peer 0 is the hub; with
    several `stages` and a `latency` range its eight links differ in
    latency, so its lat order is a real permutation of its slots."""
    n = 9
    g = build_connection_graph(
        n, 1, seed=0,
        dials=np.vstack([np.full((1, 1), 1),
                         np.zeros((n - 1, 1), dtype=np.int64)]),
        max_degree=n)
    t = Topology.build(TopoParams(
        network_size=n, anchor_stages=stages,
        min_latency=latency[0], max_latency=latency[1]))
    topo = (jnp.asarray(t.stage_of_peer), jnp.asarray(t.latency_ms),
            jnp.asarray(t.bw_up_mbit))
    params = SimParams(n=n, capacity=g.capacity, d_lazy=16,
                       flood_publish=False, max_relax_iters=16)
    state = init_state(params, seed=3)
    state = state.replace(
        mesh_mask=jnp.zeros_like(state.mesh_mask),
        hb_phase=jnp.full((n,), 250.0, jnp.float32))
    return g, params, state, graph_arrays(g), topo


def test_prefix_matches_serial_on_answer_star():
    # the hand-computed exact-serialization corner (test_disseminate
    # .test_gossip_answer_serialization_exact pins the prefix default
    # against closed-form delays); here the two engines are pinned against
    # each other on the same topology: empty mesh, no flood, answers
    # serialize back-to-back on the publisher's uplink
    g, params, state, a, topo = answer_star_setup()
    res_p, _ = _publish(state, a, topo, params)
    res_s, _ = _publish(
        state, a, topo,
        dataclasses.replace(params, answer_queue_mode="serial"))
    assert bool(np.asarray(res_p.received).all())
    assert int(np.asarray(res_p.refine_passes)) > 0
    _pin_engines_equal(res_p, res_s)


def _leaf_bytes(tree):
    """{path: bytes} of every leaf (PRNG keys by their key data)."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key):
            leaf = jax.random.key_data(leaf)
        out[jax.tree_util.keystr(path)] = np.asarray(leaf)
    return out


@pytest.mark.parametrize("fragments", [1, 2])
@pytest.mark.parametrize("loss", [None, 0.2], ids=["lossless", "lossy"])
@pytest.mark.parametrize("case", ["answer-star", "gossip-heavy"])
def test_sorted_layout_refinement_is_the_slot_layout_bits(
        case, loss, fragments):
    """ISSUE 28: the prefix refinement keeps the answer fold's lat-sorted
    layout through the whole Jacobi loop (the pull selects the reverse
    slot's sorted position; g and req come back through inv_lat once, after
    it) and every permutation is ops/pull.permute_rows. The slot-layout
    reference is the serial engine, which sorts globally and never sees
    perm_lat / inv_lat / rev_sorted: on a publish whose refinement runs,
    EVERY leaf of the result and of the new state — arrival times, and what
    the g_abs / req / drain triple and the attribution matrix `inc` feed:
    sends, copies, IHAVE / IWANT counts, lost copies, first-delivery credit,
    uplink and downlink occupancy — is the reference's, bit for bit, with
    and without loss draws (survive, retx_ms), at one and two fragments."""
    if case == "answer-star":
        # the hub publishes: eight answers queue on its uplink, in the
        # order of eight different latencies
        g, params, state, a, topo = answer_star_setup(
            stages=3, latency=(40, 130))
        assert len(set(np.asarray(topo[1])[np.asarray(topo[0])[0]][
            np.asarray(topo[0])[1:]].tolist())) > 1
        kw = {"publisher": 0}
    else:
        g, params, state, a, topo = mesh_setup(
            flood_publish=False, d_lazy=12)
        kw = {"publisher": 3}
    if loss is not None:
        kw["loss_stage"] = jnp.full(topo[1].shape, loss, jnp.float32)
    res_p, st_p = _publish(state, a, topo, params, fragments=fragments, **kw)
    res_s, st_s = _publish(
        state, a, topo,
        dataclasses.replace(params, answer_queue_mode="serial"),
        fragments=fragments, **kw)
    (fast_iters, passes, refined, fell_back, converged, by_serial,
     lane_passes, hinted, uncertified, few, few_passes) = (
        int(x) for x in np.asarray(res_p.counters))
    assert refined == 1 and passes > 0 and fell_back == 0 and converged == 1
    assert passes <= PASS_BUDGET
    assert passes <= lane_passes <= fragments * passes
    assert 1 <= hinted <= fragments and uncertified == 0
    # under ops/pull.relax_route's size: no iteration, no pass by the rows
    assert few == 0 and few_passes == 0
    # the engines count their own passes and say which of them refined; the
    # other four are shared
    ref = np.asarray(res_s.counters)
    assert [fast_iters, refined, fell_back, converged] == \
        [int(ref[0]), int(ref[2]), int(ref[3]), int(ref[4])]
    assert (by_serial, int(ref[5])) == (0, 1)
    got, want = _leaf_bytes((res_p, st_p)), _leaf_bytes((res_s, st_s))
    assert got.keys() == want.keys()
    for name in got:
        if name.endswith((".refine_passes", ".counters")):
            continue
        if name.endswith(".uplink_free_ms"):
            # the queue drain: the two engines associate busy + r*tx
            # differently (1 ulp at 2 fragments under loss, as in the parent)
            np.testing.assert_allclose(got[name], want[name], rtol=1e-6)
            continue
        assert got[name].tobytes() == want[name].tobytes(), name


def _gathers(jaxpr, scope=""):
    """(scope path, slice_sizes, output shape) of every gather traced,
    sub-jaxprs (while bodies, cond branches, pjit) included."""
    for eqn in jaxpr.eqns:
        here = f"{scope}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "gather":
            yield here, eqn.params["slice_sizes"], eqn.outvars[0].aval.shape
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _gathers(sub, here)


def test_refinement_pass_and_folds_trace_row_gathers_only():
    """ISSUE 28's mechanism, read off the trace: on the row_pull formulation
    no gather of N*C scalars is left in the refinement loop or in a fold —
    each per-pass lookup is a whole-row pull (slice (1, C), output (N, C, C)
    before the fused select) and each within-row permutation a select with
    no gather at all. XLA priced the scalar forms at 26-40 ms apiece at
    (100k, 40) on a v5e, the row pull at 5-9 ms, a select at 0.25 ms."""
    g, params, state, a, topo = mesh_setup(hb=2)
    n, c = a["conns"].shape
    jaxpr = jax.make_jaxpr(
        lambda st: _publish(st, a, topo, params, t0_ms=0.0))(state).jaxpr
    seen = list(_gathers(jaxpr))
    loop = [(ss, shape) for where, ss, shape in seen
            if "refine" in where and "fixpoint" in where
            and "legacy" not in where]
    # two phases, each one pull of receiver times and one of candidates
    assert len(loop) == 4
    fold = [(ss, shape) for where, ss, shape in seen
            if "fold" in where and np.prod(shape) >= n * c]
    assert len(fold) == 2               # one receiver-time pull a fold
    for ss, shape in loop + fold:
        assert ss[-1] == c and shape[-2:] == (c, c), (ss, shape)
    # the nine gw_sorted permutations of `sample` are selects: what is
    # left there is per-peer
    assert not [1 for where, ss, shape in seen
                if where.endswith("/sample") and ss[-1] == 1
                and np.prod(shape) >= n * c]


@pytest.mark.parametrize("submesh", [2, 4])
def test_prefix_matches_sharded_serial_across_nested_widths(submesh):
    # the nested campaign grids (2x4 / 4x2 trial meshes) run each trial
    # group's publishes over a peer submesh of width 4 / 2; with a mesh
    # the exact path keeps the LEGACY serial engine (use_prefix requires
    # mesh None), so prefix-on-one-device vs serial-on-the-submesh is the
    # cross-formulation equality the mode flip rests on
    from dst_libp2p_test_node_tpu.parallel.sharding import make_peer_mesh

    g, params, state, a, topo = mesh_setup(n=64, connect_to=6)
    res_p, _ = _publish(state, a, topo, params)
    stage, lat, bw = topo
    res_m, _ = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=7,
        t0_ms=float(state.t_ms), params=params, payload_bytes=15000,
        with_gossip=True, mesh=make_peer_mesh(submesh, platform="cpu"))
    np.testing.assert_array_equal(
        np.asarray(res_p.received), np.asarray(res_m.received))
    ok = np.asarray(res_p.received)
    np.testing.assert_allclose(
        np.asarray(res_p.delay_ms)[ok], np.asarray(res_m.delay_ms)[ok],
        rtol=1e-4, atol=0.05)
    assert bool(np.asarray(res_p.converged))
    assert bool(np.asarray(res_m.converged))


def test_refine_passes_zero_when_untriggered():
    # flood over a full mesh with gossip off: the fast pipeline is exact,
    # the repair never arms, and the pass counter must report 0 (the
    # counter is the bench's refine_passes detail field — a nonzero here
    # would bill refinement that never ran)
    g, params, state, a, topo = mesh_setup()
    stage, lat, bw = topo
    res, _ = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=7,
        t0_ms=float(state.t_ms), params=params, payload_bytes=15000,
        with_gossip=False)
    assert int(np.asarray(res.refine_passes)) == 0
    assert bool(np.asarray(res.converged))


# ---------------------------------------------------------- recv constants --


def _recv_scenario(seed=0):
    from dst_libp2p_test_node_tpu.parallel.exchange import (
        build_recv_constants,
    )

    n = 64
    rng = np.random.default_rng(seed)
    graph = build_connection_graph(n, 6, seed=seed)
    conns = jnp.asarray(graph.conns)
    rev = jnp.asarray(graph.rev)
    c = graph.capacity
    lat_edge = jnp.asarray(
        rng.uniform(40.0, 130.0, size=(n, c)).astype(np.float32))
    tx_ms = jnp.asarray(rng.uniform(0.5, 2.0, size=n).astype(np.float32))
    has = graph.conns >= 0
    send_mask = jnp.asarray(has & (rng.random((n, c)) < 0.7))
    rank = jnp.asarray(
        np.argsort(np.argsort(rng.random((n, c)), axis=-1), axis=-1)
        .astype(np.float32))
    k_p = jnp.asarray(np.asarray(send_mask).sum(axis=-1).astype(np.float32))
    g_tgt = jnp.asarray(has & ~np.asarray(send_mask)
                        & (rng.random((n, c)) < 0.3))
    hb_phase = jnp.asarray(rng.uniform(0, 1000.0, size=n).astype(np.float32))
    g_off = jnp.asarray(
        (rng.integers(0, 3, size=(n, c)) * 1000.0).astype(np.float32))
    uplink = jnp.zeros((n,), jnp.float32)
    rx_const = jnp.zeros((n,), jnp.float32)

    c = build_recv_constants(
        conns, rev, lat_edge, tx_ms, rank, k_p, 0.0, send_mask,
        jnp.ones((n,), bool), g_tgt, g_off, hb_phase, uplink, rx_const,
        2.0, 1000.0, True)
    t0 = jnp.full((n,), 3.4e38, jnp.float32).at[0].set(0.0)
    return c, t0


def test_recv_constants_layout():
    from dst_libp2p_test_node_tpu.parallel.exchange import converge_recv

    c, t0 = _recv_scenario()
    # layout contract (ARCHITECTURE §6): the two validity booleans pack
    # into one int8 flags word and every time and cost table is f32 (the
    # exact mode's bit guarantees, and the sharded/single-shard bitwise
    # pins in test_exchange, are stated over this layout)
    for f in ("a_ms", "g_ms", "g_off", "phase", "u_ms", "rx_c"):
        assert getattr(c, f).dtype == jnp.float32
    assert c.flags.dtype == jnp.int8
    assert set(np.unique(np.asarray(c.flags))) <= {0, 1, 2}
    t_rx, _, converged, _ = converge_recv(t0, c, 64)
    assert bool(converged)
    assert (np.asarray(t_rx) < 1e30).all()


# ---------------------------------------------------------------- gather --


def test_src_gather_is_statically_the_xla_gather(monkeypatch):
    # the gather is decided by what exchange.py states, not by a probe or
    # an environment switch: no pallas_call in its trace, whatever the
    # retired DST_PALLAS_GATHER says, and no capability function left
    from dst_libp2p_test_node_tpu.parallel import exchange

    assert exchange.SRC_GATHER == "xla"
    t = jnp.zeros((128,), jnp.float32)
    src = jnp.zeros((128, 6), jnp.int32)
    for env in ("0", "1"):
        monkeypatch.setenv("DST_PALLAS_GATHER", env)
        text = str(jax.make_jaxpr(exchange._src_gather)(t, src))
        assert "pallas_call" not in text and "gather" in text


def test_src_gather_matches_clipped_gather():
    # the exchange fixpoint's hot gather: same values as the plain clipped
    # numpy gather, inside a jit
    from dst_libp2p_test_node_tpu.parallel.exchange import _src_gather

    rng = np.random.default_rng(1)
    t = jnp.asarray(rng.uniform(0.0, 1e6, size=128).astype(np.float32))
    src = jnp.asarray(rng.integers(-1, 128, size=(128, 6)).astype(np.int32))
    got = jax.jit(_src_gather)(t, src)
    want = np.asarray(t)[np.clip(np.asarray(src), 0, None)]
    np.testing.assert_array_equal(np.asarray(got), want)
