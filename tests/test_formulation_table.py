"""The decision table of `fixpoint_formulation` and `fragments_in_sequence`.

Both are pure functions of the (N, C) shape, the fragment count and whether
there is a mesh: they pick the engine a publish compiles. The expected
values below are worked out by hand from ops/pull.py's budget (one row pull
gathers N x C rows, the F lanes of a vmap side by side in the row, F x C
columns padded to whole 128-lane tiles, 4 bytes an element, against 6 GiB),
at the benchmark cells' own shapes and at the queued sizes, so that a
change which silently moves a cell to another engine fails here before it
reaches the chip. Nothing is traced or compiled.
"""

import json
from pathlib import Path

import jax.numpy as jnp
import pytest

from dst_libp2p_test_node_tpu.ops import pull
from dst_libp2p_test_node_tpu.ops.disseminate import (
    fixpoint_formulation, fragments_in_sequence, lanes_in_pull)
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


# (config, one lane's bytes = N * 40 * 128 * 4, all lanes' bytes in one
# gathered row = N * 40 * roundup(F * 40, 128) * 4, formulation, in sequence)
CELLS = [
    ("runsh-1k", 20_480_000, 20_480_000, "row_pull", False),
    ("runsh-100k", 2_048_000_000, 2_048_000_000, "row_pull", False),
    # four lanes in one row are 160 columns, two tiles: 4.096e9 B, inside
    # 6 GiB (6.442e9). Until PR 41 the lanes gathered one by one, 8.192e9 B,
    # and these three cells ran them in sequence
    ("runsh-100k-frag4", 2_048_000_000, 4_096_000_000, "row_pull", False),
    ("runsh-100k-128k-frag4", 2_048_000_000, 4_096_000_000, "row_pull",
     False),
    ("runsh-100k-churn", 2_048_000_000, 4_096_000_000, "row_pull", False),
]


@pytest.mark.parametrize(
    "config,lane_bytes,row_bytes,formulation,in_sequence", CELLS,
    ids=[c[0] for c in CELLS])
def test_benchmark_cells_keep_their_engine(config, lane_bytes, row_bytes,
                                           formulation, in_sequence):
    n, c, fragments = _cell_shape(config)
    assert pull.intermediate_bytes(jnp.float32, (n, c)) == lane_bytes
    assert pull.intermediate_bytes(jnp.float32, (n, c), fragments) == row_bytes
    assert fixpoint_formulation((n, c)) == formulation
    assert fragments_in_sequence((n, c), fragments) is in_sequence
    assert lanes_in_pull((n, c), fragments) == fragments


# (N, fragments, mesh, formulation, in sequence), all at C = 40
QUEUED = [
    # 1M peers: one pull is 20.48e9 B, past the budget whatever the lanes
    (1_000_000, 1, None, "recv", False),
    (1_000_000, 4, None, "recv", False),
    # a mesh takes the sharded engine and unrolls its lanes, at any size
    (1_000_000, 1, MESH, "recv_sharded", False),
    (100_000, 4, MESH, "recv_sharded", False),
    # three lanes are 120 columns, one tile, the bytes of one lane: vmapped
    (100_000, 3, None, "row_pull", False),
    # every `topogen -f` choice fits at 100k: five lanes 200 columns, two
    # tiles, 4.096e9 B; nine 360 columns, three tiles, 6.144e9 B (5.72 GiB)
    (100_000, 5, None, "row_pull", False),
    (100_000, 9, None, "row_pull", False),
    # ten lanes are 400 columns, four tiles, 8.192e9 B: in sequence
    (100_000, 10, None, "row_pull", True),
    # the last N whose pull fits: 6 GiB / (40 * 128 * 4 B) = 314,572.8;
    # two lanes are 80 columns, still one tile, so they fit wherever one does
    (314_572, 1, None, "row_pull", False),
    (314_573, 1, None, "recv", False),
    (314_572, 2, None, "row_pull", False),
    (314_572, 4, None, "row_pull", True),
    # the last N whose four lanes fit one row: 6 GiB / (40 * 256 * 4 B)
    (157_286, 4, None, "row_pull", False),
    (157_287, 4, None, "row_pull", True),
]


@pytest.mark.parametrize(
    "n,fragments,mesh,formulation,in_sequence", QUEUED,
    ids=[f"{n}x40-F{f}{'-mesh' if m else ''}" for n, f, m, _, _ in QUEUED])
def test_queued_sizes_and_the_budget_edge(n, fragments, mesh, formulation,
                                          in_sequence):
    assert fixpoint_formulation((n, 40), mesh) == formulation
    assert fragments_in_sequence((n, 40), fragments, mesh) is in_sequence
    # lanes share a gathered row only vmapped on "row_pull"
    packed = formulation == "row_pull" and not in_sequence
    assert lanes_in_pull((n, 40), fragments, mesh) == (
        fragments if packed else 1)


# (lanes, columns padded to whole tiles, bytes at (100000, 40) f32)
ROWS = [(1, 128, 2_048_000_000), (2, 128, 2_048_000_000),
        (3, 128, 2_048_000_000), (4, 256, 4_096_000_000),
        (5, 256, 4_096_000_000), (9, 384, 6_144_000_000),
        (10, 512, 8_192_000_000)]


@pytest.mark.parametrize("lanes,width,nbytes", ROWS,
                         ids=[f"F{r[0]}" for r in ROWS])
def test_the_packed_rows_bytes(lanes, width, nbytes):
    """What a pull allocates with `lanes` tables in the gathered row, and on
    which side of 6 GiB it falls; a bool row is a quarter of it."""
    assert -(-lanes * 40 // 128) * 128 == width
    assert pull.intermediate_bytes(jnp.float32, (100_000, 40), lanes) == nbytes
    assert pull.intermediate_bytes(jnp.bool_, (100_000, 40), lanes) \
        == nbytes // 4
    assert pull.exceeds_budget(jnp.float32, (100_000, 40), lanes) \
        is (nbytes > 6 * GIB)
