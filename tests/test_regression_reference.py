"""The regression node against its two plain references (XLA:CPU, small):

  ops/kad.find_node and what a wave teaches, the dials drawn from the
  routing tables and the connections made from them, against
  benchmark/reference/kad_plain.py (Python integers, no JAX), exactly;

  the whole regression path, `cli.main(["regression", ...])` under the
  benchmark's own entry (benchmark/entries/regression.py: part 1's
  invariants, the captured discovery and publishes, the comparison with
  kad_plain.py and with the float64 DES), at the two sizes where PR 42 found
  71.4 % and 38.8 % coverage and at the size its rehearsal holds.
"""

import copy
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dst_libp2p_test_node_tpu.ops import kad
from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph
from dst_libp2p_test_node_tpu.runtime import regression_runtime as rr

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

from benchmark.entries import regression as entry  # noqa: E402
from benchmark.harness import manifest  # noqa: E402
from benchmark.reference import kad_plain  # noqa: E402


def test_kad_plain_imports_nothing_of_the_program():
    with open(kad_plain.__file__) as f:
        source = f.read()
    imported = [line.split()[1].split(".")[0]
                for line in source.splitlines()
                if line.startswith(("import ", "from "))]
    assert set(imported) <= {"__future__", "numpy"}, imported


@pytest.mark.parametrize("n,seed,learn_cap", [
    (64, 3, kad.LEARN_CAP), (200, 5, kad.LEARN_CAP), (200, 2147483999, None)])
def test_find_node_waves_tables_dials_and_conns_are_kad_plains(
        n, seed, learn_cap):
    """Three waves as the regression node runs them (self-lookup, two
    random), each compared whole: closest, hops, requests, latency, and the
    tables after what the wave taught; then dials and connections."""
    state = kad.init_kad_state(n, seed=seed)
    keys = kad_plain.make_keys(n, seed)
    assert keys == [kad_plain.key_of(row) for row in np.asarray(state.keys)]
    state = kad.seed_bootstraps(state, jnp.asarray([0], jnp.int32))
    tables = kad_plain.empty_tables(n)
    kad_plain.seed_bootstraps(tables, keys, [0])
    assert (kad_plain.tables_to_array(tables)
            == np.asarray(state.rtable)).all()

    stage, latency = np.arange(n) % 2, np.array([[100.0, 130.0],
                                                 [130.0, 40.0]])
    origins = jnp.arange(1, n, dtype=jnp.int32)
    key = jax.random.PRNGKey(seed ^ 0x4E62)
    for wave in range(3):
        if wave == 0:
            targets = state.keys[origins]
        else:
            key, k = jax.random.split(key)
            targets = kad.random_targets(k, n - 1)
        res, state = kad.find_node(
            state, origins, targets, jnp.asarray(stage),
            jnp.asarray(latency, jnp.float32), learn_cap=learn_cap)
        lookups, tables = kad_plain.wave(
            tables, keys, np.asarray(origins), np.asarray(targets), stage,
            latency, learn_cap=learn_cap)
        closest = np.full((n - 1, kad_plain.K_RESP), -1)
        for i, found in enumerate(lookups):
            closest[i, :len(found["closest"])] = found["closest"]
        assert (closest == np.asarray(res.closest)).all(), wave
        assert ([f["hops"] for f in lookups]
                == np.asarray(res.hops).tolist()), wave
        assert ([f["n_queries"] for f in lookups]
                == np.asarray(res.n_queries).tolist()), wave
        np.testing.assert_allclose(
            [f["latency_ms"] for f in lookups], np.asarray(res.latency_ms),
            atol=1e-3, rtol=0)
        assert (kad_plain.tables_to_array(tables)
                == np.asarray(state.rtable)).all(), wave
    assert max(f["hops"] for f in lookups) > 0

    dials = rr.discovery_dials(np.asarray(state.rtable), 10, np.array([0]),
                               seed)
    plain = kad_plain.dials(tables, 10, [0], seed)
    assert dials.tolist() == plain
    graph = build_connection_graph(n, 10, seed=seed, max_degree=40,
                                   dials=dials)
    assert (graph.conns == kad_plain.connections(plain, seed, 40)).all()
    if n == 200:    # the kept-prefix capacity pass ran, and agrees
        assert graph.build["cap_filtered_edges"] > 0


def _cell(peers: int, msg_size: int, messages: int = 3) -> manifest.Cell:
    """The benchmark's regression-10k configuration at a test's size, every
    peer held to log every message."""
    with open(os.path.join(CHECKOUT, "benchmark", "configs",
                           "regression-10k.json")) as f:
        config = json.load(f)
    config = copy.deepcopy(config)
    config["regression"]["env"]["PEERS"] = peers
    config["regression"]["messages"] = messages
    config["regression"]["msg_size"] = msg_size
    config["reference"]["messages"] = messages
    config["guarantees"]["coverage_share_min"] = 1.0
    return manifest.Cell(
        name="regression-test.headline", chips=1,
        config_name="regression-test", config=config,
        traffic_name="headline", traffic={}, entry_name="regression",
        entry=entry, end_to_end=[], per_layer=[])


@pytest.mark.parametrize("peers,msg_size", [
    (64, 1000), (64, 15000), (200, 1000)])
def test_regression_path_against_the_des_and_kad_plain(
        peers, msg_size, tmp_path):
    cell = _cell(peers, msg_size)
    outcome, items = entry.captured(cell, 3, str(tmp_path / "out"))
    # part 1: every peer logs every message, the lines' form, no delay
    # under the link's 100 ms but the publisher's own 0, waves and pings
    assert outcome.ok, outcome.faults
    assert outcome.stats["coverage_by_message"] == [peers] * 3
    assert [i["message"] for i in items] == [100, 101, 102, 110, 0, 1, 2]
    for item in items:
        record = entry.against_reference(cell, item)
        assert record["passed"], record
        limited = {k: v for k, v in record.items() if k.startswith("limit_")}
        assert limited and all(k[len("limit_"):] in record for k in limited)
    # the control of the exact comparisons differs, in many entries
    for item in items[:4]:
        control = entry.against_reference(cell, item, control=True)
        assert not control["passed"], control
    summary = entry.summarised(
        [entry.against_reference(cell, i) for i in items])
    assert summary["sound_discovery_differing_max"] == 0
    assert summary["sound_reached_differing_max"] == 0
