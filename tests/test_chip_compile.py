"""The main path's device programs, compiled for a described TPU v5e.

The chip's compiler is installed here and compiles for a chip that is
described, not attached (no device executes anything): what Mosaic or XLA:TPU
would refuse on the machine with the chip, it refuses in this file, at no
chip time. Shapes are the 100,000-peer headline config's
(`chip_smoke.py` phase 2). Cases:

  - the gather `parallel/exchange._src_gather` lowers to (the plain XLA
    gather: `exchange.SRC_GATHER`), also in the vmapped fragment form;
  - the sharded fixpoint `converge_sharded` on a 4-chip peer mesh: the
    collective the design rests on is in the HLO and the per-device memory
    fits a v5e;
  - both halves of the heartbeat scan, and `ops/kad.find_node` at the
    kad-10k cell's probe tick (10,000 peers): what its response sorts;
  - a publish's row pull through its two bands (ops/pull.make_pull_bands),
    one lane and four: the gathered rows the compiler keeps are the bands';
  - a step of the fast fixpoint that delivers the moved rows' offers
    (ops/pull.pull_moved_min), one lane and four: one conditional, a
    scatter on one side of it, the banded pull on the other;
  - a step that brings a refinement pass's carried receivers' times up to
    date (ops/pull.neighbor_update_min), one lane and four: one
    conditional, a scatter on one side, the banded lookup on the other.

Everything that touches the topology lives in fixtures of THIS file (one
xdist worker loads the TPU library, only after a test here has started);
the persistent compilation cache is off around the compiles, since an entry
written for a described chip cannot be read back without one.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

N = 100_000
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture(scope="module")
def capacity():
    from dst_libp2p_test_node_tpu.runtime.simulator import (
        ExperimentConfig, graph_capacity)

    return graph_capacity(ExperimentConfig())   # connect-to 10, as the CLI


@pytest.fixture(scope="module")
def one_chip(topo):
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def peer_mesh(topo):
    return Mesh(np.array(topo.devices[:4]), ("peers",))


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


@pytest.mark.parametrize("fragments", [None, 4])
def test_src_gather_formulation_compiles_for_v5e(one_chip, capacity,
                                                 fragments):
    from dst_libp2p_test_node_tpu.parallel import exchange

    assert exchange.SRC_GATHER == "xla"
    src = one_chip((N, capacity), jnp.int32)
    if fragments is None:
        fn, t = exchange._src_gather, one_chip((N,), jnp.float32)
    else:   # the fragment axis vmaps the fixpoint over a shared src table
        fn = jax.vmap(exchange._src_gather, in_axes=(0, None))
        t = one_chip((fragments, N), jnp.float32)
    text = jax.jit(fn).lower(t, src).compile().as_text()
    assert "gather" in text and "tpu_custom_call" not in text


def test_sharded_fixpoint_compiles_for_four_chips(peer_mesh, capacity):
    from dst_libp2p_test_node_tpu.parallel.exchange import (
        RecvConstants, converge_sharded)

    rows = NamedSharding(peer_mesh, P("peers"))
    everywhere = NamedSharding(peer_mesh, P())

    def edge(dtype):
        return jax.ShapeDtypeStruct((N, capacity), dtype, sharding=rows)

    def peer(dtype):
        return jax.ShapeDtypeStruct((N,), dtype, sharding=rows)

    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=everywhere)
    c = RecvConstants(
        src=edge(jnp.int32), a_ms=edge(jnp.float32), g_ms=edge(jnp.float32),
        g_off=edge(jnp.float32), phase=edge(jnp.float32),
        u_ms=edge(jnp.float32), flags=edge(jnp.int8),
        rx_c=peer(jnp.float32), proc_ms=scalar, hb_ms=scalar)
    lowered = jax.jit(
        lambda t0, c: converge_sharded(t0, c, 64, peer_mesh)
    ).lower(peer(jnp.float32), c)
    # the design's one per-iteration exchange: the (N,) t vector
    assert "all-gather" in lowered.as_text(dialect="hlo")
    compiled = lowered.compile()
    # ... which the v5e compiler may keep, or rewrite as an all-reduce over
    # a padded buffer (it does at this size); either way it crosses chips
    text = compiled.as_text()
    assert "all-gather" in text or "all-reduce" in text
    assert "f32[25000]" in text     # rows really are N/4 per device
    assert _device_bytes(compiled) < V5E_HBM_BYTES
    t_out, inc_out, _, _ = compiled.output_shardings
    assert t_out.spec == P("peers") and inc_out.spec == P("peers")


def _band_structs(one_chip, capacity):
    """`Banded` conns and rev of the 100,000-peer shape, as shapes."""
    from dst_libp2p_test_node_tpu.ops import pull

    c1, m = pull.band_shape((N, capacity))
    assert (c1, m) == (24, 12504)
    back = one_chip((N,), jnp.int32)
    return tuple(
        pull.Banded(one_chip((N, c1), jnp.int32),
                    one_chip((m, capacity - c1), jnp.int32), back)
        for _ in range(2))


@pytest.mark.parametrize("lanes", [1, 4])
def test_banded_pull_compiles_for_v5e_and_keeps_fewer_rows(
        one_chip, capacity, lanes):
    """ISSUE 50: the pull through slots [0, 24) of every row and slots
    [24, 40) of 12,504 heavy rows compiles for the chip with three gathers
    and no kernel, and its temporaries (the padded gathered rows: 2.05 GB a
    whole pull, 4.1 GB for four lanes) shrink with the rows, 65 % of them."""
    from dst_libp2p_test_node_tpu.ops import pull

    vals = one_chip(((lanes,) if lanes > 1 else ()) + (N, capacity),
                    jnp.float32)
    whole = (one_chip((N, capacity), jnp.int32),) * 2
    bands = _band_structs(one_chip, capacity)

    def fn(v, conns, rev):
        if lanes == 1:
            return pull.reciprocal_pull_min(v, conns, rev)
        return jax.vmap(lambda x: pull.reciprocal_pull_min(
            x, conns, rev, batch_factor=lanes))(v)

    temp = {}
    for name, index in (("whole", whole), ("bands", bands)):
        compiled = jax.jit(fn).lower(vals, *index).compile()
        assert "tpu_custom_call" not in compiled.as_text()
        temp[name] = compiled.memory_analysis().temp_size_in_bytes
    assert temp["whole"] > 2.0e9 * (2 if lanes > 1 else 1)
    assert temp["bands"] < 0.72 * temp["whole"], temp


@pytest.mark.parametrize("lanes", [1, 4])
def test_moved_rows_step_compiles_for_v5e_with_both_sides(
        one_chip, capacity, lanes):
    """ISSUE 51: a step of a fast fixpoint at the 100,000-peer shape, one
    lane and four vmapped lanes through the bands: ONE conditional holds
    the delivery (a scatter into the carried offers) on one side and the
    banded pull's gathers on the other, no kernel, and the step keeps no
    more than the dense pull's temporaries beside a copy of the offers."""
    from dst_libp2p_test_node_tpu.ops import pull

    lead = (lanes,) if lanes > 1 else ()
    edge = one_chip((N, capacity), jnp.int32)
    bands = _band_structs(one_chip, capacity)

    def offer(t, busy, cost):
        return jnp.where((t < pull.INF)[:, None],
                         jnp.maximum(t, busy)[:, None] + cost, pull.INF)

    def step(t, inc, moved, busy, cost, conns, rev, via, via_rev):
        def lane(t, inc, moved, cost):
            return pull.pull_moved_min(
                offer, t, inc, moved, (busy, cost), conns, rev, via, via_rev,
                batch_factor=lanes)
        return (lane if lanes == 1 else jax.vmap(lane))(t, inc, moved, cost)

    def dense(t, busy, cost, via, via_rev):
        def lane(t, cost):
            return pull.reciprocal_pull_min(offer(t, busy, cost), via,
                                            via_rev, lanes)
        return (lane if lanes == 1 else jax.vmap(lane))(t, cost)

    t = one_chip(lead + (N,), jnp.float32)
    table = one_chip(lead + (N, capacity), jnp.float32)
    busy = one_chip((N,), jnp.float32)
    compiled = jax.jit(step).lower(
        t, table, one_chip(lead + (N,), jnp.bool_), busy, table, edge, edge,
        *bands).compile()
    text = compiled.as_text()
    assert text.count(" conditional(") == 1
    assert " scatter(" in text and "tpu_custom_call" not in text
    whole = jax.jit(dense).lower(t, busy, table, *bands).compile()
    assert (compiled.memory_analysis().temp_size_in_bytes
            < whole.memory_analysis().temp_size_in_bytes
            + 2.5 * lanes * N * capacity * 4)


@pytest.mark.parametrize("lanes", [1, 4])
def test_receiver_times_step_compiles_for_v5e_with_both_sides(
        one_chip, capacity, lanes):
    """ISSUE 53: a step that brings a refinement pass's carried receivers'
    times up to date (ops/pull.neighbor_update_min) at the 100,000-peer
    shape, one lane and four vmapped lanes: ONE conditional between a
    scatter into the carried (N, C) matrix and the banded lookup's
    gathers, no kernel, and no more temporaries than the lookup's beside a
    copy of the matrix. One lane's scatter keeps the caller's scope in its
    op_name (ops/pull._deliver writes it flat: the compiler's own rewrite
    of a scatter at (row, column) pairs drops the name, and a device trace
    then counts the delivery under no scope); a vmapped one is rewritten
    and loses it."""
    from dst_libp2p_test_node_tpu.ops import pull

    lead = (lanes,) if lanes > 1 else ()
    edge = one_chip((N, capacity), jnp.int32)
    via = _band_structs(one_chip, capacity)[0]

    def step(nbr, t, moved, conns, rev, via):
        def lane(nbr, t, moved):
            return pull.neighbor_update_min(nbr, t, moved, conns, rev, via,
                                            batch_factor=lanes)
        with jax.named_scope("refine"):
            return (lane if lanes == 1 else jax.vmap(lane))(nbr, t, moved)

    def dense(t, via):
        def lane(t):
            return pull.neighbor_rows_min(t, via, lanes)
        return (lane if lanes == 1 else jax.vmap(lane))(t)

    t = one_chip(lead + (N,), jnp.float32)
    compiled = jax.jit(step).lower(
        one_chip(lead + (N, capacity), jnp.float32), t,
        one_chip(lead + (N,), jnp.bool_), edge, edge, via).compile()
    text = compiled.as_text()
    assert text.count(" conditional(") == 1
    assert " scatter(" in text and "tpu_custom_call" not in text
    scatters = [line for line in text.splitlines() if " scatter(" in line]
    assert all(('op_name="jit(step)/refine/' in line) == (lanes == 1)
               for line in scatters)
    whole = jax.jit(dense).lower(t, via).compile()
    assert (compiled.memory_analysis().temp_size_in_bytes
            < whole.memory_analysis().temp_size_in_bytes
            + 2.5 * lanes * N * capacity * 4)


@pytest.mark.parametrize("churn", [1e-4, 0.0])
def test_heartbeat_scan_compiles_for_v5e(one_chip, capacity, churn):
    """Both halves of `_run_heartbeats` at the shape of the benchmark's
    100,000-peer cells: under churn (runsh-100k-churn) the draws, the carried
    neighbour view and the validity conjunction every step, the spared mask
    as an argument; without, the hoisted validity. At this shape the step's
    reciprocity takes the sparse route: the scatter from the rows that send
    and the dense pull it falls back to are both in the loop's body."""
    from dst_libp2p_test_node_tpu.ops import pull
    from dst_libp2p_test_node_tpu.ops.heartbeat import _run_heartbeats
    from dst_libp2p_test_node_tpu.ops.state import SimParams, init_state

    assert pull.sparse_route((N, capacity))
    params = SimParams(n=N, capacity=capacity, churn_down_per_hb=churn,
                       churn_up_per_hb=churn / 2)
    state = jax.tree_util.tree_map(
        lambda s: one_chip(s.shape, s.dtype),
        jax.eval_shape(lambda: init_state(params, seed=0)))
    compiled = _run_heartbeats.lower(
        state, one_chip((N, capacity), jnp.int32),
        one_chip((N, capacity), jnp.int32), one_chip((N, capacity), jnp.bool_),
        params, 500, one_chip((N,), jnp.bool_) if churn else None).compile()
    text = compiled.as_text()
    assert "while" in text and "gather" in text and "scatter" in text
    assert _device_bytes(compiled) < 0.1 * V5E_HBM_BYTES
    # the sorts XLA:TPU is slow to compile: as many as before GRAFT and
    # PRUNE selected by rows (PR 46: the few rows' ranks are counted)
    assert len(re.findall(r" sort\(", text)) <= (11 if churn else 10)


def test_find_node_probe_tick_compiles_for_v5e(one_chip):
    """`find_node` at the kad-10k cell's probe tick (10,000 peers, ten
    origins): a response sorts the packed head, 256 of a table's 384
    columns, in five operands (four distance words and the ids, no
    stability iota), and what a table holds past the head sits behind a
    conditional."""
    from dst_libp2p_test_node_tpu.ops import kad

    n, q = 10_000, 10
    assert kad.packed_width(n, 24, 16) == 256
    state = jax.tree_util.tree_map(
        lambda s: one_chip(s.shape, s.dtype),
        jax.eval_shape(lambda: kad.init_kad_state(n, seed=1)))
    compiled = kad.find_node.lower(
        state, one_chip((q,), jnp.int32),
        one_chip((q, kad.KEY_WORDS), jnp.uint32), one_chip((n,), jnp.int32),
        one_chip((2, 2), jnp.float32), learn_cap=None).compile()
    sorts = [line for line in compiled.as_text().splitlines()
             if re.search(r" sort\(", line) and "/response/" in line
             and "cond/branch" not in line]
    assert len(sorts) == 1, sorts
    result = sorts[0].split(" sort(")[0]
    operands = re.findall(r"(?:pred|[a-z]+\d+)\[[\d,]*\]", result)
    assert operands == ["u32[30,256]"] * 4 + ["s32[30,256]"], operands
    assert "is_stable=true" not in sorts[0]
    assert re.search(r" conditional\(.*/response/", compiled.as_text())
    assert _device_bytes(compiled) < V5E_HBM_BYTES
