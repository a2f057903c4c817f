"""The decision table of `fixpoint_formulation` and `fragments_in_sequence`.

Both are pure functions of the (N, C) shape, the fragment count and whether
there is a mesh: they pick the engine a publish compiles. The expected
values below are worked out by hand from ops/pull.py's budget (one row pull
gathers N x C rows padded to the 128-lane tile, 4 bytes an element, against
6 GiB), at the benchmark cells' own shapes and at the queued sizes, so that a
change which silently moves a cell to another engine fails here before it
reaches the chip. Nothing is traced or compiled.
"""

import json
from pathlib import Path

import jax.numpy as jnp
import pytest

from dst_libp2p_test_node_tpu.ops import pull
from dst_libp2p_test_node_tpu.ops.disseminate import (
    fixpoint_formulation, fragments_in_sequence)
from dst_libp2p_test_node_tpu.runtime.simulator import (
    ExperimentConfig, graph_capacity)

REPO = Path(__file__).resolve().parent.parent
GIB = 1024 ** 3
MESH = object()     # any mesh: the functions only ask whether there is one


def _cell_shape(config: str) -> tuple[int, int, int]:
    """(N, C, fragments) as `run` builds them from the configuration's argv."""
    run = json.loads(
        (REPO / "benchmark" / "configs" / f"{config}.json").read_text())["run"]
    flags = run.get("flags", [])
    cfg = ExperimentConfig()
    if "--connect-to" in flags:
        cfg.connect_to = int(flags[flags.index("--connect-to") + 1])
    pos = run["positionals"]
    return int(pos["nodes"]), graph_capacity(cfg), int(pos["num_frag"])


def test_the_budget_the_table_is_written_from():
    assert pull._MAX_INTERMEDIATE_BYTES == 6 * GIB and pull._LANE == 128
    # connect-to 10 (run.sh:38) gives 40 slots a peer in every cell
    assert graph_capacity(ExperimentConfig()) == 40


# (config, one pull's bytes = N * 40 * 128 * 4, formulation, in sequence)
CELLS = [
    ("runsh-1k", 20_480_000, "row_pull", False),
    ("runsh-100k", 2_048_000_000, "row_pull", False),
    # four lanes at once are 8.192e9 B > 6 GiB (6.442e9), one is not
    ("runsh-100k-frag4", 2_048_000_000, "row_pull", True),
]


@pytest.mark.parametrize("config,pull_bytes,formulation,in_sequence", CELLS,
                         ids=[c[0] for c in CELLS])
def test_benchmark_cells_keep_their_engine(config, pull_bytes, formulation,
                                           in_sequence):
    n, c, fragments = _cell_shape(config)
    assert pull.intermediate_bytes(jnp.float32, (n, c)) == pull_bytes
    assert fixpoint_formulation((n, c)) == formulation
    assert fragments_in_sequence((n, c), fragments) is in_sequence


# (N, fragments, mesh, formulation, in sequence), all at C = 40
QUEUED = [
    # 1M peers: one pull is 20.48e9 B, past the budget whatever the lanes
    (1_000_000, 1, None, "recv", False),
    (1_000_000, 4, None, "recv", False),
    # a mesh takes the sharded engine and unrolls its lanes, at any size
    (1_000_000, 1, MESH, "recv_sharded", False),
    (100_000, 4, MESH, "recv_sharded", False),
    # three lanes of 100k are 6.144e9 B, still under 6 GiB: vmapped
    (100_000, 3, None, "row_pull", False),
    # the last N whose pull fits: 6 GiB / (40 * 128 * 4 B) = 314,572.8
    (314_572, 1, None, "row_pull", False),
    (314_573, 1, None, "recv", False),
    (314_572, 2, None, "row_pull", True),
]


@pytest.mark.parametrize(
    "n,fragments,mesh,formulation,in_sequence", QUEUED,
    ids=[f"{n}x40-F{f}{'-mesh' if m else ''}" for n, f, m, _, _ in QUEUED])
def test_queued_sizes_and_the_budget_edge(n, fragments, mesh, formulation,
                                          in_sequence):
    assert fixpoint_formulation((n, 40), mesh) == formulation
    assert fragments_in_sequence((n, 40), fragments, mesh) is in_sequence
