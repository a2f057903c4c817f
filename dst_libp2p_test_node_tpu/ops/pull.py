"""The reciprocal-permutation pull — THE hot memory primitive of the engine.

Every protocol exchange in the simulator moves data across the static
directed-edge involution (p, i) <-> (q = conns[p,i], j = rev[p,i]): GRAFT /
PRUNE reciprocity in the heartbeat, the per-iteration offer pull of the
dissemination fixpoint, and the post-fixpoint accounting. Semantically each
is `out[q, j] = vals[conns[q,j], rev[q,j]]` — a gather through two (N, C)
index vectors.

TPU performance note (measured at N=100k, C=40 on v5e):
  - two-index-vector gather `vals[conns, rev]`:        ~45 ms (4M random
    scalar loads; XLA's general gather path)
  - flattened one-index gather over the (N*C,) table:  ~34 ms
  - whole-ROW gather `vals[conns]` + fused iota-select: ~11 ms

Row gathers are embedding-style lookups (contiguous C-element reads) that
the TPU pipeline handles well; the slot-select then happens in registers via
an iota comparison that XLA fuses into the gather consumer. We trade C x
read amplification for contiguity and win ~4x. The iota mask is built
inline (never materialized as an (N, C, C) constant) so peak memory stays
O(N*C*C) only inside the fused loop body.

Measured again, each as a jit of its own, same shape (PR 28, TPU v5 lite):
  - two-index gather 39.2 ms; the row pull 9.1 ms, gathered rows included
    (10.6-10.9 ms a pull inside the publish's loops: `publish.fast.device_s`
    over the pulls it counts, ledger, PR 40, which is 2 x 2.05 GB of padded
    rows in 10.8 ms, 46 % of 819 GB/s; the 5.3 ms once read from PR 27's
    trace is borne out by no ledger line)
  - a per-peer lookup `t[idx]` over an (N, C) index: 26.8 ms as XLA's scalar
    gather, 8.6 ms as a row pull of the broadcast table (neighbor_rows_min)
  - a WITHIN-ROW permutation (`take_along_axis(x, idx, axis=-1)`, nothing
    crosses rows): 39.7 ms as XLA lowers it — the same 4M scalar loads — and
    0.28 ms as selects alone, no gather (permute_rows; f32, bool and int32
    alike; the (N, C, C) one-hot form 0.21-0.25 ms, see there why not that)

Sparse reciprocity (reciprocal_send_bool; PR 37, TPU v5 lite, same shape,
each candidate a jit of its own, median of the timed calls; such a call costs
0.5-0.6 ms whatever it does: an (N, C) `any` + `sum` alone 0.62 ms, an (N,)
cumsum 0.64 ms, so every line under a millisecond stands on that floor):
  - the dense bool pull: reciprocal_pull_bool 11.2 ms, neighbor_pull_bool
    11.3 ms; 10.3 ms a step inside a 50-step scan
  - compacting the sending rows into K = 128 (256) indices: jnp.nonzero(
    size=K) 1.55 (1.53) ms; cumsum + searchsorted 0.62 (0.64), `scan` and
    `compare_all` alike; lax.top_k over a masked iota 0.67 (0.69); a
    two-level reduce 0.61 (0.63) — all but nonzero are at the floor
  - delivering K = 128 rows, 0 / 3 / 30 / 127 of them sending: the 2-D
    scatter with unique_indices, mode="drop" 0.67 / 0.68 / 0.70 / 0.67 ms
    (0.82 at K = 256); without unique_indices 0.67-0.72; into int32 0.64-
    0.70; a flattened 1-D scatter 0.77-0.81; a fori_loop of
    dynamic_update_slice 0.65 / 0.64 / 0.84 / 1.46; the scatter-free
    membership compare against the edge ids conns*C + rev 0.65 for 16 marked
    edges, 0.83 for 64. XLA:TPU's scatter is not slow at this size
  - the whole of reciprocal_send_bool (count, switch over none / sparse /
    dense): 0.67 ms with no sending row, 0.80-0.82 with 3, 30 and 127,
    11.1 with 200 (the dense side); neighbor_update_bool with 10 changed
    peers 0.84 ms
  - where it counts, inside a 50-step scan with no dispatch between steps:
    0.25 ms a step with 3 or with 30 sending rows, against 10.3 ms dense

Row width against time (PR 41, TPU v5 lite, same shape and index, each
candidate a jit of its own, median of 8 timed calls): one gather of the
rows of an (N, W) table through the (N, C) index and one fused select of
column `rev` over the whole row.
      W        40      128     160     256     360
      f32     8.96    9.02   16.59   16.77   47.90 ms
      bool   11.13   11.22   17.04   16.94   17.48 ms
A pull is bound by the ROWS it fetches and the 128-lane tiles they fill, not
by their bytes: flat across the first tile, 1.85 x for the second (four
times the columns of W = 40), bool no cheaper than f32 until f32 falls off
at a third tile. So F tables that share `conns` and `rev` lie side by side in
one gathered row (_lanes_in_the_row). Four lanes (F, N, C), f32 / bool:
  - one after another (lax.map of the pull above): 33.7 / 42.1 ms
  - ONE gather of the (N, 160) table, then a select a lane on its own 40
    columns: as F reduces 25.7 / 31.5 ms (24.8 a step inside a 20-step
    loop, against 33.7 one after another and 8.1 for one lane);
    the same with every lane in ONE variadic reduce, one fusion and one
    pass over the rows, 27.4 / 28.6, and over F masks 25.4 / 25.8 (24.7 a
    step): no faster, the selects are elementwise work, not reads;
    F masks over the flat row 33.2 / 29.3; one masked row and a
    reduce_window of C columns 24.6 / 17.9 (6.2 GiB of temporaries as the
    TPU compiler counts them, against 3.8)
  - a reshape of the gathered rows to (N, C, F, C) 105.8, a slot-major table
    (column i*F + f) 106.1: relayouts of 4 GB
  - XLA's own batching of the one-lane pull, what a vmap over `vals` alone
    got until PR 41: 388.3 ms (and 11.7 GiB of temporaries)
  - nine lanes (three tiles: f32's fall-off): 74.9 one after another, 72.3
    packed; no loss, and the per-peer lookup below still gains
neighbor_rows_min for F lanes, an (N, F) table gathered once, lane f its
column f: 8.9 ms for four lanes against 33.8 one after another (9.6 against
74.5 for nine); the (N, F*C) table of broadcast lanes 25.1.

Rows against time (PR 50, TPU v5 lite, chiprun call 125: `python
scripts/pull_bands_bench.py`; same shape, f32, the index of
`build_connection_graph(100000, 10, 1, max_degree=40)`, each candidate a jit
of its own, median of 8 timed calls; this machine read the whole pull at
10.0 ms where PR 41's read 9.0). Half of an (N, C) index at 4 x connect_to
is pads: mean degree 20.0 of 40 slots, filled share 0.49998, max degree
36-37, every row filled from the front; rows with more than 16 / 20 / 24 /
28 / 32 connections: 87,059 / 41,525 / 8,384 / 669 / 32. A pad's -1 clips to
row 0 and is gathered like any other, so a pull that fetches slots [0, C1)
of every row (band A) and slots [C1, C) of the M rows that hold a
connection there (band B) returns the same array from fewer rows
(make_pull_bands; C1 = 24, M = 12,504: 2,600,064 of 4,000,000 rows, 65 %):
      rows gathered             one lane   four packed lanes
      whole (N, 40)              10.01       26.08 ms
      band A alone, C1 = 16       5.28
      band A alone, C1 = 24       6.53
      band A alone, C1 = 32       9.59
      A + B, C1 = 24, M = 12,504, band B back through an (N,) inverse row
        gather of the (M + 1, C - C1) block (_spread: what runs)
                                  7.42       17.47
      the same, band B scattered into a filled (N, C - C1)
                                  6.70       18.94 (one scatter of (F, T)
                                             windows; a scatter of (M, F*T)
                                             rows: not measured)
      A + B, C1 = 24, M = 50,000  8.94
      A + B, C1 = 32, M = 12,504 10.40
      A + B, C1 = 20, M = 50,000 13.75
      inside a 20-step loop, a step: whole 8.27 / 24.88; A + B 5.89 / 16.75
      bool, one lane: whole 11.28; A + B 9.37
      neighbor_rows_min: whole 9.20 / 9.05; A + B 6.81 / 6.47
Cost follows the rows, less than in proportion: 65 % of the rows cost 74 %
standalone and 67-71 % inside a loop, and a second band is not free (band
B's 200,000 rows and their way back cost 0.9 ms as an inverse gather of
100,000 16-wide rows, 0.2 ms as a scatter of one lane). In the publish's
loops (ledger and traced runs, PR 50): a fast iteration 10.98 -> 7.89 ms at
F = 1 and 30.5 -> 21.5 ms for four joint lanes, a refinement pass (two
pulls) 21.5 -> 16.0 and 46.1 -> 35.4 ms. Band A is still 17.4 % pads and the
heavy rows' tails 86 %: what fetches filled slots alone would gather 50 %.

Moved rows against time (PR 51; `python scripts/relax_moved_bench.py`: same
shape and index, f32, a step of a 20-step `fori_loop` round the body of
ops/disseminate._converge_dyn with gossip, the bands above on the dense
side, one lane and four vmapped lanes). An iteration of a monotone fixpoint
changes the offers of the senders whose time moved in the iteration before
and of no other, and the offers are in the loop's carry, so with at most K
moved senders a step compacts their ids (sending_rows), evaluates the
offers of those K rows and scatters them into the carried matrix
(pull_moved_min); no row is gathered through the (N, C) index. The census
(XLA:CPU, whose results are the chip's bit for bit; `cli run 1 100000 15000
<F> 1 50 150 40 130 5 0.0 4 0 4000 --seed 7`): rows moved by iteration,
phase 1 from the publisher alone 18, 303, 4,896, 54,012, 83,764, 67,253,
39,900, 12,424, 1,488, 69, 1, 0 and phase 2 from phase 1's times 44,517,
42,136, 40,562, 35,906, 24,439, 10,369, 2,086, 157, 3, 0 at F = 1 (the four
lanes of F = 4 move in step and finish within one iteration of each other),
so of 22 iterations a publish 5 / 7 / 7 / 8 / 9 / 10 fit K = 128 / 512 /
1,024 / 2,048 / 4,096 / 8,192 at F = 1 and 4 / 6 / 7 / 7 / 8 / 10 in every
lane at F = 4.
A step, ms (TPU v5 lite, chiprun call 214; median of 4 timed calls; the
sparse side with 3 / K/2 / K moved rows):
      step                      one lane            four vmapped lanes
      the dense body             6.01                17.09
      the loop's tail alone      0.06                 0.07
      `inc` handed back through
        a cond                   0.09                 0.18
      K =   128              0.32 / 0.32 / 0.32   1.20 / 1.18 / 1.16
      K =   512              0.44 / 0.42 / 0.42   1.83 / 1.75 / 1.67
      K = 1,024              0.58 / 0.56 / 0.55   2.69 / 2.54 / 2.37
      K = 2,048              0.96 / 0.93 / 0.88   4.55 / 4.30 / 4.04
      K = 4,096              1.71 / 1.64 / 1.56   7.91 / 7.68 / 7.44
      every row moved (the dense side through the cond, any K)
                             6.10-6.12            17.25-17.38
      the parts, one lane, K = 128 / 512 / 1,024 / 2,048 / 4,096 (each
      with the tail's 0.06):
        sending_rows, compare_all   0.17 / 0.19 / 0.26 / 0.40 / 0.72
        sending_rows, scan          0.13 / 0.19 / 0.24 / 0.36 / 0.61
        sending_rows, sort          0.82 / 0.83 / 0.83 / 0.85 / 0.87
        the K-row offers (seven gathers and the arithmetic)
                                    0.11 / 0.12 / 0.14 / 0.20 / 0.28
        the scatter of K x C        0.15 / 0.23 / 0.35 / 0.56 / 1.00
      four lanes: the scatter 0.77 / 1.12 / 1.63 / 2.70 / 4.73; the other
      two parts write one element of the vmapped carry to stay alive and
      stand on the 3.4 ms that costs (3.63-5.12 and 3.57-4.66): they say
      how a part grows with K, not what it costs
The count of senders moves nothing (fewer real updates cost slightly MORE:
a dropped update is not cheaper); cost follows K, through the scatter
first (0.2 ms a thousand rows of one lane, 1.0 ms of four: four lanes'
scatter is one custom fusion over the flat 16M offers and costs four
lanes' updates and then some), the compaction second (`compare_all` and
`scan` alike, `sort` flat at 0.8). The cond is free on the dense side
(+0.1 ms at F = 1, +0.2 at F = 4) and passing `inc` through it costs
0.03 and 0.12 ms. `_RELAX_ROWS` is the largest K whose step costs under a
quarter of the dense body's at F = 1 (1.50 ms) AND at F = 4 (4.27 ms):
1,024 (2.69 ms; 2,048 reads 4.55 at F = 4). In the publish (traced pair,
`runsh-100k-frag4.headline`, PR 51): 5.67 of 21.0 joint iterations a
publish took the sparse side and `fast` fell 1.381 -> 1.116 s with 0.039 s
more outside every scope (PERF.md section 6 has what that leaves open).

A rider in the gathered row, measured and not taken (PR 53, TPU v5 lite,
chiprun call 320; same shape, index and bands, f32, median of 8 timed
calls, 4 for a loop; the primitive, its tests and its bench rows are in git
history, taken out at that PR's review). A pass of the prefix refinement
(ops/disseminate._converge_prefix) makes two exchanges through the same
index: its receivers' times t[conns] before its fold, the offers after it.
The rider fetched the per-peer vector as one column more of the table whose
rows a pull gathers anyway ((N, C + 1); four packed lanes (N, 4 * (C + 1)) =
164 columns, still two tiles), so that a pass made ONE gather, folded with
the times a pass late and certified on a pass that read its own (one pass
more a loop, every byte the same).
A step of a 20-step loop through the bands, ms (one lane / four packed):
      the pull alone                          5.88 / 17.07
      the pull and the lookup it replaces    11.43 / 22.38
      the pull with the rider                 8.13 / 25.07
        and permute_rows of the rider        10.13 / 26.21
      the rider read over the whole lane      7.70 / 26.77
      the rider as a plain pick               8.42 / 41.00 (a second copy of
                                              the gathered rows: 7.6 GB)
      whole index: pull 8.30 / 24.90, two 16.65 / 33.16, rider 11.22 / 36.70
      standalone: rider 9.29 / 25.72 (whole 12.46 / 37.48), two 12.42 / 23.27
The rider costs 2.25 ms at F = 1 and 8.0 ms for four lanes, not a column:
a row of 41 (164) columns is dearer to gather than one of 40 (160), whatever
reads it (every width PR 41 priced was a multiple of 8); the permutation
that brings it to the lat order costs 2.0 ms inside the loop, not the 0.28 of
a standalone call. One lane would save 1.3 ms a pass and pay two confirming
passes of 16 for it; four lanes lose 3.8 ms a pass. In the publish (pairs on
shared seeds, every byte equal): runsh-100k.headline 2.072 -> 2.207 s and
2.037 -> 2.188, a pass 15.89 -> 16.74 ms (22 for 20 of them); the blob cell
3.965 -> 4.379 s, a joint pass 35.60 -> 38.21; regression-10k 1.179 -> 1.286.
Not tried: a lane padded to 48 columns, the riders packed behind the lanes, a
pull through the lat-sorted index (no permutation after it).
What runs instead (neighbor_update_min): the receivers' times are a carry
of the loop, and a pass changes them exactly at the slots that point at a
peer whose time moved in the pass before: with few such peers their new
times are scattered into the carry and no row is gathered for them.
A step of a 20-step loop (chiprun call 323; one lane / four vmapped
lanes, K = 1,024 for both): 3 moved peers 0.52 / 2.05 ms, 1,024 moved
0.51 / 1.97, every peer moved (the banded lookup through the cond) 5.72 /
5.83 (call 341, _deliver's flat scatter: 0.51 / 2.06, 0.50 / 1.98, 5.73 /
5.83; at (10000, 40) 0.29 / 1.00). In the publish the gain is UNRESOLVED:
six pairs on shared seeds (every byte equal) read runsh-100k.headline 2.091
-> 2.035, 2.054 -> 2.032, 2.074 -> 2.030 s (call 323) and 2.040 -> 2.030,
2.057 -> 2.003, 2.041 -> 1.991 (call 341): better in all six, by 0.5 to
2.7 %, median 2.3 %, where the parent's own runs spread by 0.8 to 1.8 %; the
driver's pairs decide (PERF.md section 6, PR 53). 12 to 14 of an
experiment's 60 passes go by the rows (the last 2 of a loop's 10: 155 and 3
peers moved before them).
A larger K for one lane was measured and not taken (chiprun call 329): at
K = 4,096 the step costs 1.39-1.46 ms whatever moved (5.94 for four lanes),
and regression-10k read 1.174 -> 1.294 s in one run, of a kind that reads
5 % slow whatever the tree (a process that loads some programs from the
compile cache and compiles others: PERF.md section 7): unresolved. K and
the size under which the route is off (_SPARSE_MIN_DENSE_BYTES) are PR
51's, priced for pull_moved_min and not measured again for this lookup.

The sharded fixpoint (parallel/exchange.py converge_sharded) deliberately
does NOT use this: its per-iteration cross-shard traffic is the (N,) time
vector alone, and the pull there is against receiver-local constants.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.custom_batching import custom_vmap

INF = jnp.float32(3.4e38)

# Peak-memory budget for the row-gather intermediate: (N, C, C) for one
# table, (N, C, F*C) where F tables that share `conns` lie side by side in
# the gathered row (the lanes of a vmap: _lanes_in_the_row). The last axis
# pads to the 128-lane TPU tile, so the real footprint is
# N*C*roundup(F*C, 128)*itemsize bytes. Within budget the row gather is the
# fastest formulation (11 ms f32 / at 100k,C=40 vs 45 ms for the naive
# 2-index gather). Beyond it — e.g. f32 at 1M peers would be a 20 GiB
# intermediate — the memory-light 2-index gather WINS outright (732 ms/pull
# at 1M vs ~2.7 s for a sequentially-chunked row gather: chunk serialization
# costs more than the random scalar loads), so large pulls simply fall back.
_MAX_INTERMEDIATE_BYTES = 6 * 1024**3
_LANE = 128

# Sparse reciprocity (see reciprocal_send_bool). `_SPARSE_ROWS` is K, the
# most sending rows a step delivers from the rows themselves: the churned
# scan at 100,000 peers sends from a few tens of rows a stage after its
# first steps, and compaction plus delivery cost the same for every count
# up to K (the numbers are in the module docstring). `_SPARSE_MIN_DENSE_BYTES`
# is the static half of the dispatch: below it the dense pull is cheaper
# than the sparse path's fixed cost (at (1000, 40) the pull is microseconds),
# so small shapes keep the one program they had.
_SPARSE_ROWS = 128
_SPARSE_MIN_DENSE_BYTES = 128 * 1024**2
# K of `pull_moved_min`: the most moved rows an iteration of a float fixpoint
# delivers from the rows themselves (the table in the module docstring)
_RELAX_ROWS = 1024


def intermediate_bytes(dtype, conns_shape, batch_factor: int = 1) -> int:
    """Bytes of the padded row-gather intermediate of one pull whose gathered
    row carries `batch_factor` tables side by side (see exceeds_budget):
    N * C * roundup(batch_factor * C, 128) * itemsize."""
    n, c = conns_shape[-2], conns_shape[-1]
    itemsize = 1 if dtype == jnp.bool_ else jnp.dtype(dtype).itemsize
    width = -(-max(batch_factor, 1) * c // _LANE) * _LANE
    return n * c * width * itemsize


def exceeds_budget(dtype, conns_shape, batch_factor: int = 1) -> bool:
    """The dispatch decision, exposed for tests: would the padded row-gather
    intermediate for this pull exceed the memory budget?

    `batch_factor`: the width of the enclosing vmap over `vals` alone that
    the caller declares (fragments, topics, trials of one graph).
    Trace-time shapes are per-instance, and the lanes of such a vmap share
    ONE gather with all of them in the row (_lanes_in_the_row), so the REAL
    allocation is the packed row's: at (100000, 40) f32, 2.05 GB for one
    lane, 4.1 GB for four or five, 6.1 GB for nine, where one gather a lane
    would have been 2.05 GB times the lanes. The dispatch must account for
    it or a wide vmap would blow an in-budget pull past the device."""
    return (intermediate_bytes(dtype, conns_shape, batch_factor)
            > _MAX_INTERMEDIATE_BYTES)


def _select_min(rows, sel):
    return jnp.where(sel, rows, INF).min(axis=-1)


def _select_any(rows, sel):
    return (rows & sel).any(axis=-1)


def _gather_select(select):
    """out[..., q, j] = vals[..., conns[q,j], rev[q,j]] as the whole-row
    gather and `select(rows, sel)`, the fused pick of column `rev` (clipped:
    callers mask the invalid slots)."""
    def pull(vals, conns, rev):
        c = vals.shape[-1]
        rows = vals[..., jnp.clip(conns, 0), :]   # (..., N, C, C) contiguous
        sel = jnp.arange(c) == jnp.clip(rev, 0)[..., None]
        return select(rows, sel)
    return pull


def _gather_select_packed(select):
    """`_gather_select` for `vals` (F, N, C) through ONE (N, C) `conns` and
    `rev`: the F tables lie side by side in one (N, F*C) table, lane f in
    columns [f*C, (f+1)*C), so ONE gather of N*C rows serves every lane
    (a pull is bound by the rows it fetches, not by their width: the
    module docstring's table), and each lane picks column `rev` of its own
    C columns with the one (N, C, C) mask a single lane builds."""
    def pull(vals, conns, rev):
        f, n, c = vals.shape
        table = jnp.moveaxis(vals, 0, 1).reshape(n, f * c)
        rows = table[jnp.clip(conns, 0), :]       # (N, C, F*C)
        sel = jnp.arange(c) == jnp.clip(rev, 0)[..., None]
        return jnp.stack([select(rows[..., k * c:(k + 1) * c], sel)
                          for k in range(f)])
    return pull


def _two_index(vals, conns, rev):
    return vals[jnp.clip(conns, 0), jnp.clip(rev, 0)]


@functools.lru_cache(maxsize=None)
def _lanes_in_the_row(one, packed, scalar, lanes: int, rank: int = 3):
    """`one(vals, *index)` for a caller that declares an enclosing vmap
    `lanes` wide, with a batching rule of its own: where that vmap batches
    `vals` alone (fragment lanes, trials of one graph: every lane goes
    through the SAME index) the lanes take `packed`, one gather with all of
    them in the gathered row (`rank`: of the batched `vals` it takes). What
    the rule sees decides, nothing else:
    a vmap that batches the index too (trials with a graph each) or is not
    `lanes` wide (the nested device grids declare what one device holds of
    a vmap spread over several: a shared row would gather the lanes onto
    each) keeps what it had, the batched row gather or, where the declared
    lanes' rows together pass the budget, `scalar`, the gather of single
    elements.
    Unbatched (a rolled loop over the lanes) it is `one`."""
    core = custom_vmap(one)

    @core.def_vmap
    def rule(axis_size, in_batched, vals, *index):
        if (axis_size == lanes and vals.ndim == rank and in_batched[0]
                and not any(in_batched[1:])):
            return packed(vals, *index), True
        lane = scalar if (lanes * intermediate_bytes(
            vals.dtype, index[0].shape) > _MAX_INTERMEDIATE_BYTES) else one
        axes = tuple(0 if b else None for b in in_batched)
        return jax.vmap(lane, in_axes=axes)(vals, *index), True

    return core


# (one lane, declared lanes in one row, past the budget) of a select
_PULL_MIN = (_gather_select(_select_min), _gather_select_packed(_select_min),
             _two_index)
_PULL_ANY = (_gather_select(_select_any), _gather_select_packed(_select_any),
             _two_index)


def _row_pull(vals, index, forms, shape, batch_factor: int, rank: int = 3):
    """Size-dispatched core over a select's `forms` (_PULL_MIN, _PULL_ANY,
    _ROWS_MIN, or one of them `_in_bands`) and the `index` arrays they take
    after `vals`: the whole-row gather and the select, or past the budget
    (see exceeds_budget; `shape`, the whole (N, C) index whatever its bands)
    the gather of single elements. One lane is the plain program; declared
    lanes may share a row (_lanes_in_the_row, `rank` as there)."""
    one, packed, scalar = forms
    if exceeds_budget(vals.dtype, shape, batch_factor):
        return scalar(vals, *index)
    if batch_factor <= 1:
        return one(vals, *index)
    return _lanes_in_the_row(one, packed, scalar, batch_factor, rank)(
        vals, *index)


class Banded(NamedTuple):
    """An (N, C) index array as the two bands a publish's pulls fetch
    (make_pull_bands): `head` (N, C1), slots [0, C1) of every row; `tail`
    (M, C - C1), slots [C1, C) of the heavy rows, the rows that hold a
    connection there (all -1 past their count); `back` (N,), a row's place
    in `tail`, M for a row that is not heavy. The pulls take it in place of
    the array and return the (N, C) array they return for the array."""

    head: jnp.ndarray
    tail: jnp.ndarray
    back: jnp.ndarray

    @property
    def shape(self):
        return (self.head.shape[0], self.head.shape[1] + self.tail.shape[1])


def _spread(tail, back, fill):
    """Band B back into the rows of the index: row p of the result is row
    `back[p]` of `tail` (M, T), `fill` where `back` says M. One gather of N
    rows through the (N,) inverse index; F lanes (F, M, T) lie side by side
    in its rows, as the pulled tables do (_gather_select_packed)."""
    if tail.ndim == 2:
        row = jnp.full((1, tail.shape[-1]), fill, tail.dtype)
        return jnp.concatenate([tail, row])[back]
    f, m, t = tail.shape
    table = jnp.moveaxis(tail, 0, 1).reshape(m, f * t)
    rows = jnp.concatenate(
        [table, jnp.full((1, f * t), fill, tail.dtype)])[back]
    return jnp.moveaxis(rows.reshape(-1, f, t), 1, 0)


def _in_bands(form, mask, fill):
    """`form(vals, *index)`, one of a select's three, through a `Banded`
    index: band A, slots [0, C1) of every row, and band B, slots [C1, C) of
    the heavy rows, each `mask`ed by its own slots as the whole pull's
    result is, and B spread back over the rows. The index arrives flat, the
    heads, the tails, then `back`, so that the batching rule of
    `_lanes_in_the_row` sees arrays."""
    def banded(vals, *index):
        *index, back = index
        heads, tails = index[:len(index) // 2], index[len(index) // 2:]
        head = mask(form(vals, *heads), *heads)
        tail = mask(form(vals, *tails), *tails)
        return jnp.concatenate([head, _spread(tail, back, fill)], axis=-1)
    return banded


def _flat(*index):
    """What `_in_bands` takes of `Banded` arrays that share a `back`."""
    return (*(x.head for x in index), *(x.tail for x in index),
            index[0].back)


def _mask_min(out, conns, rev):
    return jnp.where((conns >= 0) & (rev >= 0), out, INF)


def _mask_rows(out, conns):
    return jnp.where(conns >= 0, out, INF)


def _mask_any(out, conns, rev):
    return out & (conns >= 0) & (rev >= 0)


class PullBands(NamedTuple):
    """The hoisted index tables of a publish's banded pulls: a pure function
    of the index arrays (make_pull_bands), like `AnswerTables` of theirs.
    `heads[name]` (N, C1) and `tails[name]` (M, C - C1) for every index
    array given by `name` ("conns", "rev", "conns_sorted", "rev_sorted");
    `back` (N,) as `Banded` has it, one for all of them."""

    back: jnp.ndarray
    heads: dict
    tails: dict

    def of(self, name: str) -> Banded:
        return Banded(self.heads[name], self.tails[name], self.back)


def band_shape(conns_shape) -> tuple[int, int]:
    """(C1, M) of the two bands of an (N, C) index, from the static shape
    alone, so that every graph of a shape compiles one program: the cut at
    three fifths of the slots and room for an eighth of the rows past it,
    both rounded up to 8 (24 and 12,504 at (100000, 40): at C = 4 x
    connect_to the degrees centre on C / 2, and 8.4 % of the rows hold more
    than 24 connections; the module docstring has the census)."""
    n, c = conns_shape
    return -(-3 * c // 5 // 8) * 8, -(-n // 8 // 8) * 8


def pull_rows_share(bands: PullBands | None) -> float:
    """100 x the rows one pull of the publish gathers / (peers x slots):
    100 without bands."""
    if bands is None:
        return 100.0
    conns = bands.of("conns")
    n, c = conns.shape
    return 100.0 * (conns.head.size + conns.tail.size) / (n * c)


def make_pull_bands(conns, rev, conns_sorted=None, rev_sorted=None, *,
                    mesh=None, c1=None, rows=None,
                    min_bytes=_SPARSE_MIN_DENSE_BYTES) -> PullBands | None:
    """The bands of a publish's pulls over this graph, or None where the
    whole-width pull stays: half of an (N, C) index at C = 4 x connect_to is
    pads, a graph fills a row's slots from the front, and a pull is bound by
    the rows it fetches, so the pulls fetch slots [0, C1) of every row and
    slots [C1, C) of the heavy rows only. A row is heavy when any slot at or
    past C1 holds a connection, read from `conns` itself (holes in a row
    keep it exact); a row of `conns_sorted` that holds one there has more
    than C1 connections and is heavy already, so one set serves both
    layouts (`conns_sorted` and `rev_sorted` of `AnswerTables`; without
    them, the slot layout alone).

    (C1, M) is `band_shape`'s, static; what the graph in hand decides is
    only bands or none. None: on a `mesh`; where the dense pull is under
    `min_bytes` of gathered rows (microseconds: the small shapes keep the
    one program they had) or past the gather budget (no row is pulled);
    where more than M rows are heavy (a skewed or capped graph). `c1`,
    `rows`, `min_bytes`: for tests."""
    n, c = conns.shape
    cut, most = band_shape((n, c))
    c1 = cut if c1 is None else c1
    rows = most if rows is None else rows
    if (mesh is not None or not 0 < c1 < c
            or intermediate_bytes(jnp.float32, (n, c)) < min_bytes
            or exceeds_budget(jnp.float32, (n, c))):
        return None
    # the census, reduced where the array lives: N flags come to the host
    heavy = np.flatnonzero(np.asarray((conns[:, c1:] >= 0).any(axis=-1)))
    if heavy.size > rows:
        return None
    ids = np.full(rows, n, np.int32)
    ids[:heavy.size] = heavy
    back = np.full(n, rows, np.int32)
    back[heavy] = np.arange(heavy.size, dtype=np.int32)
    index = {"conns": conns, "rev": rev, "conns_sorted": conns_sorted,
             "rev_sorted": rev_sorted}
    index = {k: jnp.asarray(x) for k, x in index.items() if x is not None}
    ids = jnp.asarray(ids)
    return PullBands(
        back=jnp.asarray(back),
        heads={k: x[:, :c1] for k, x in index.items()},
        tails={k: x.at[ids].get(mode="fill", fill_value=-1)[:, c1:]
               for k, x in index.items()})


_PULL_MIN_BANDED = tuple(_in_bands(f, _mask_min, INF) for f in _PULL_MIN)
_PULL_ANY_BANDED = tuple(_in_bands(f, _mask_any, False) for f in _PULL_ANY)


def permute_rows(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """out[..., p, j] = x[..., p, idx[p, j]] — a within-row pick (every
    permutation of the lat-sorted answer fold is one). Nothing crosses rows,
    so no gather is needed, only selects: column k of x goes wherever idx
    says k, one (N, J) select per column, all of them one fusion. Exact for
    every value (a pick, not a masked min); bit for bit
    `take_along_axis(x, idx, axis=-1)` for in-range `idx`. Deliberately NOT
    the (N, J, C) one-hot of `_row_pull`: a one-hot shared by several
    permutations through the same index XLA:TPU materialises (0.5 GB as
    pred at 100k x 40) instead of fusing. Dispatch is on what is visible at
    trace time, the static row width: up to one lane tile takes the
    selects; wider rows keep XLA's gather, whose cost no longer hides
    behind C selects."""
    c = x.shape[-1]
    if c > _LANE:
        return jnp.take_along_axis(x, idx, axis=-1)
    shape = jnp.broadcast_shapes(x.shape[:-1], idx.shape[:-1])
    out = jnp.broadcast_to(x[..., :1], shape + idx.shape[-1:])
    for k in range(1, c):
        out = jnp.where(idx == k, x[..., k:k + 1], out)
    return out


def reciprocal_pull_bool(
    edge_mask: jnp.ndarray, conns: jnp.ndarray, rev: jnp.ndarray,
    batch_factor: int = 1,
) -> jnp.ndarray:
    """out[q, j] = edge_mask[conns[q,j], rev[q,j]]; False on invalid slots.
    `conns` and `rev` may be `Banded`."""
    if isinstance(conns, Banded):
        return _row_pull(edge_mask, _flat(conns, rev), _PULL_ANY_BANDED,
                         conns.shape, batch_factor)
    out = _row_pull(edge_mask, (conns, rev), _PULL_ANY, conns.shape,
                    batch_factor)
    return _mask_any(out, conns, rev)


def sparse_route(conns_shape, batch_factor: int = 1) -> bool:
    """The trace-time half of the sparse dispatch: one instance (under an
    enclosing vmap a `cond` lowers to a select and both branches would run)
    of a static shape whose dense bool pull costs more than the sparse
    path's fixed cost."""
    return (batch_factor == 1 and len(conns_shape) == 2
            and intermediate_bytes(jnp.bool_, conns_shape)
            >= _SPARSE_MIN_DENSE_BYTES)


def rows_route(count) -> jnp.ndarray:
    """The run-time half of the sparse dispatch, as a `lax.switch` index
    over (none, few, all) of the rows a step marks: 0 where `count` is 0, 1
    up to `_SPARSE_ROWS`, 2 beyond."""
    return (count > 0).astype(jnp.int32) + (count > _SPARSE_ROWS)


def sending_rows(row_mask: jnp.ndarray, k: int | None = None,
                 method: str = "compare_all") -> jnp.ndarray:
    """The indices of the first k (`_SPARSE_ROWS` where not given) True
    entries of an (N,) mask, ascending, N (one past the end) beyond their
    count: the k-th sender is the first row whose running count reaches k
    (`method`: `jnp.searchsorted`'s)."""
    k = _SPARSE_ROWS if k is None else k
    running = jnp.cumsum(row_mask.astype(jnp.int32))
    return jnp.searchsorted(
        running, jnp.arange(1, k + 1, dtype=jnp.int32), side="left",
        method=method).astype(jnp.int32)


def _deliver(into, senders, marks, values, conns, rev):
    """into[conns[p,i], rev[p,i]] = values[k,i] for every marked slot i of
    sender p = senders[k]. The involution makes the targets of distinct
    (p, i) distinct; slots that are unmarked, invalid or a sentinel row's
    are sent past the end, each to an index of its own, and dropped.

    The scatter is written over the matrix flattened column by column, the
    one XLA:TPU makes of a scatter at (row, column) pairs itself (an (N, C)
    matrix lies column-major there, so the transposes are no copies): the
    compiler's own rewrite leaves the scatter and its index arithmetic
    without the op_name that carries the caller's `jax.named_scope`, and a
    device trace then counts them under no scope."""
    n, c = conns.shape
    k = senders.shape[0]
    cn = conns.at[senders].get(mode="fill", fill_value=-1)
    rv = rev.at[senders].get(mode="fill", fill_value=-1)
    ok = marks & (cn >= 0) & (rv >= 0)
    at = jnp.where(ok, rv * n + cn,
                   n * c + jnp.arange(k * c, dtype=jnp.int32).reshape(k, c))
    flat = into.T.reshape(-1).at[at.reshape(-1)].set(
        jnp.broadcast_to(values, (k, c)).reshape(-1), mode="drop",
        unique_indices=True)
    return flat.reshape(c, n).T


def relax_route(conns_shape) -> bool:
    """The trace-time half of `pull_moved_min`'s dispatch, for a caller
    whose pulls are row pulls: a static shape whose dense float pull (the
    f32 test `make_pull_bands` makes) costs more than the sparse side's
    fixed cost. Under it a fixpoint keeps the one program it had."""
    return (len(conns_shape) == 2
            and intermediate_bytes(jnp.float32, conns_shape)
            >= _SPARSE_MIN_DENSE_BYTES)


def _moved_step(sparse, dense, lanes: int, index: int):
    """`sparse(*args)` where the moved rows (args[2], a mask over the rows)
    fit `_RELAX_ROWS`, else `dense(*args)`: a `lax.cond` on that scalar, with
    a batching rule of its own, because under a vmap a `cond` on a lane's
    own count lowers to a select and both sides run. A vmap `lanes` wide
    that batches none of the last `index` arguments (the fragment lanes of
    a publish: one graph) takes the same step over all its lanes, sparse
    where EVERY lane's rows fit, on one scalar predicate, each lane
    delivering its own rows into its own matrix. Any other vmap (a graph a
    lane, a width nobody declared, a vmap around the lanes') keeps
    `dense`."""
    step = custom_vmap(lambda *args: jax.lax.cond(
        jnp.all(args[2].sum(axis=-1, dtype=jnp.int32) <= _RELAX_ROWS),
        sparse, dense, *args))

    @step.def_vmap
    def rule(axis_size, in_batched, *args):
        axes = tuple(0 if b else None for b in in_batched)
        over = [jax.vmap(f, in_axes=axes) for f in (sparse, dense)]
        if axis_size == lanes and not any(in_batched[len(args) - index:]):
            return _moved_step(*over, 0, index)(*args), (True, True)
        return over[1](*args), (True, True)

    return step


def pull_moved_min(offer, t, inc, moved, operands, conns, rev,
                   p_conns=None, p_rev=None, batch_factor: int = 1):
    """`reciprocal_pull_min(offer(t, *operands), ...)`, bit for bit, given
    `inc`, that pull as it stood before the rows in `moved` (N,) took their
    new `t`, where row p of `offer` is a function of `t[p]` and of row p of
    each of `operands` ((N,) or (N, C)) alone: `inc` changes exactly at the
    slots that point at a moved row, (conns[p, i], rev[p, i]) for every
    valid slot i of a moved p. With at most `_RELAX_ROWS` moved rows (the
    first and the last iterations of a monotone fixpoint) that is a
    compaction of their ids, `offer` on those K rows of `t` and of the
    operands, and one K x C scatter into `inc`: no row is gathered through
    the (N, C) index. With more it is the dense pull, its offers computed
    inside that branch (an operand of a `cond` is not free in the branch
    that ignores it). `p_conns`, `p_rev`: what the dense pull goes through
    where that is `Banded`. `batch_factor`: the enclosing vmap over
    everything but the index that the caller declares (`_moved_step` says
    what such lanes get).

    Returns (inc, sparse): `sparse` int32, 1 where the rows were delivered,
    0 where they were pulled."""
    leaves, tree = jax.tree_util.tree_flatten(
        (conns, rev, conns if p_conns is None else p_conns,
         rev if p_rev is None else p_rev))
    n_ops = len(operands)

    def dense(t, inc, moved, *rest):
        _, _, via, via_rev = jax.tree_util.tree_unflatten(tree, rest[n_ops:])
        with jax.named_scope("dense"):
            return (reciprocal_pull_min(offer(t, *rest[:n_ops]), via,
                                        via_rev, batch_factor),
                    jnp.int32(0))

    def sparse(t, inc, moved, *rest):
        conns, rev, _, _ = jax.tree_util.tree_unflatten(tree, rest[n_ops:])
        with jax.named_scope("sparse"):
            senders = sending_rows(moved, _RELAX_ROWS)
            # a sentinel (one past the end) reads the last row: _deliver
            # drops what it would send
            at = jnp.minimum(senders, moved.shape[0] - 1)
            values = offer(t[at], *(x[at] for x in rest[:n_ops]))
            return (_deliver(inc, senders, True, values, conns, rev),
                    jnp.int32(1))

    return _moved_step(sparse, dense, batch_factor, len(leaves))(
        t, inc, moved, *operands, *leaves)


def neighbor_update_min(nbr, per_peer, moved, conns, rev, via=None,
                        batch_factor: int = 1):
    """`neighbor_rows_min(per_peer, via)`, bit for bit, given `nbr`, that
    lookup as it stood before the peers in `moved` (N,) took their new
    values: `nbr` changes exactly at the slots that point at a moved peer,
    so with at most `_RELAX_ROWS` of them (the last passes of a refinement)
    it is a compaction of their ids and one K x C scatter of their values
    into `nbr`, no row gathered; with more, the lookup. `conns`, `rev`:
    where peer p's value lands, row conns[p, i] at position rev[p, i] for
    every valid slot i (the reverse slot; for a lookup through the lat
    order `conns_sorted`, the reverse slot's position in it, `rev_sorted`).
    `via`: the index of the lookup, whole or `Banded` (None: `conns`).
    `batch_factor`, and what lanes get: as `pull_moved_min`.

    Returns (nbr, sparse): `sparse` int32, 1 where the values were
    delivered, 0 where they were looked up."""
    leaves, tree = jax.tree_util.tree_flatten(
        (conns, rev, conns if via is None else via))

    def dense(per_peer, nbr, moved, *rest):
        index = jax.tree_util.tree_unflatten(tree, rest)[2]
        with jax.named_scope("dense"):
            return (neighbor_rows_min(per_peer, index, batch_factor),
                    jnp.int32(0))

    def sparse(per_peer, nbr, moved, *rest):
        conns, rev, _ = jax.tree_util.tree_unflatten(tree, rest)
        with jax.named_scope("sparse"):
            senders = sending_rows(moved, _RELAX_ROWS)
            # a sentinel (one past the end) reads the last peer: _deliver
            # drops what it would send
            values = per_peer[jnp.minimum(senders, moved.shape[0] - 1)]
            return (_deliver(
                nbr, senders, True,
                jnp.broadcast_to(values[:, None],
                                 (_RELAX_ROWS, conns.shape[-1])),
                conns, rev), jnp.int32(1))

    return _moved_step(sparse, dense, batch_factor, len(leaves))(
        per_peer, nbr, moved, *leaves)


def _tally(count, routed: bool) -> jnp.ndarray:
    """int32 (3,) = (1 if delivered sparse, 1 if pulled dense, `count`, the
    sending rows): sparse where the shape is `routed` there and the rows fit
    `_SPARSE_ROWS`."""
    sparse = ((count <= _SPARSE_ROWS).astype(jnp.int32) if routed
              else jnp.int32(0))
    return jnp.stack([sparse, 1 - sparse, count])


def reciprocal_send_bool(
    edge_mask: jnp.ndarray, conns: jnp.ndarray, rev: jnp.ndarray,
    batch_factor: int = 1,
):
    """`reciprocal_pull_bool`, bit for bit, delivered from the rows that
    send where they are few. The reverse-slot map is an involution, so
    "for every marked (p, i), mark (conns[p,i], rev[p,i])" IS the gather
    out[q, j] = edge_mask[conns[q,j], rev[q,j]]: with at most `_SPARSE_ROWS`
    non-empty rows it is a compaction of their indices, three K-row gathers
    and one K*C-update scatter into an all-False (N, C); with more (step 0
    of a scan from an empty mesh: every row sends) it is the dense pull;
    with none it is the all-False array. Chosen by `lax.switch` on the count
    the call observes, where `sparse_route` allows it at trace time.

    Returns (out, tally): tally as `_tally` packs it."""
    rows = edge_mask.any(axis=-1)
    count = rows.sum(dtype=jnp.int32)
    routed = sparse_route(conns.shape, batch_factor)

    def dense(m):
        with jax.named_scope("dense"):
            return reciprocal_pull_bool(m, conns, rev, batch_factor)

    def sparse(m):
        with jax.named_scope("sparse"):
            senders = sending_rows(rows)
            marks = m.at[senders].get(mode="fill", fill_value=False)
            return _deliver(jnp.zeros_like(m), senders, marks, True,
                            conns, rev)

    def none(m):
        with jax.named_scope("sparse"):
            return jnp.zeros_like(m)

    if routed:
        out = jax.lax.switch(
            rows_route(count), [none, sparse, dense], edge_mask)
    else:
        out = dense(edge_mask)
    return out, _tally(count, routed)


def neighbor_update_bool(
    nbr: jnp.ndarray, per_peer: jnp.ndarray, changed: jnp.ndarray,
    conns: jnp.ndarray, rev: jnp.ndarray, batch_factor: int = 1,
):
    """`neighbor_pull_bool(per_peer, ...)`, bit for bit, given `nbr`, the
    pull of the vector as it was before the peers in `changed` (N,) took
    their new values: `nbr` changes exactly at the slots that point at a
    changed peer, (conns[p,i], rev[p,i]) for every valid slot i of a changed
    p, which is `reciprocal_send_bool`'s delivery of "every slot of a
    changed row" with the peer's new value instead of True. More than
    `_SPARSE_ROWS` changed peers, or a shape `sparse_route` refuses: the
    dense pull. Returns (out, tally) as `reciprocal_send_bool` does."""
    count = changed.sum(dtype=jnp.int32)
    routed = sparse_route(conns.shape, batch_factor)

    def dense(nbr):
        with jax.named_scope("dense"):
            return neighbor_pull_bool(per_peer, conns, rev, batch_factor)

    def sparse(nbr):
        with jax.named_scope("sparse"):
            senders = sending_rows(changed)
            values = per_peer.at[senders].get(mode="fill", fill_value=False)
            return _deliver(
                nbr, senders, jnp.ones((_SPARSE_ROWS, 1), dtype=bool),
                jnp.broadcast_to(values[:, None],
                                 (_SPARSE_ROWS, conns.shape[-1])),
                conns, rev)

    if routed:
        out = jax.lax.cond(count <= _SPARSE_ROWS, sparse, dense, nbr)
    else:
        out = dense(nbr)
    return out, _tally(count, routed)


def neighbor_pull_bool(
    per_peer: jnp.ndarray, conns: jnp.ndarray, rev: jnp.ndarray,
    batch_factor: int = 1,
) -> jnp.ndarray:
    """out[q, j] = per_peer[conns[q,j]] (False on invalid slots) — a per-PEER
    table lookup through the neighbor index. Same row-contiguity trick: the
    (N,) vector broadcasts to a (N, C) table that is constant along slots,
    so pulling any slot of the neighbor's row (we use the reverse slot, which
    is always in range) yields the per-peer value."""
    table = jnp.broadcast_to(per_peer[:, None], conns.shape)
    return reciprocal_pull_bool(table, conns, rev, batch_factor)


def neighbor_pull_min(
    per_peer: jnp.ndarray, conns: jnp.ndarray, rev: jnp.ndarray,
    batch_factor: int = 1,
) -> jnp.ndarray:
    """out[q, j] = per_peer[conns[q,j]] for floats; INF on invalid slots."""
    table = jnp.broadcast_to(per_peer[:, None], conns.shape)
    return reciprocal_pull_min(table, conns, rev, batch_factor)


def _rows_min_one(per_peer, conns):
    q = jnp.clip(conns, 0)
    table = jnp.broadcast_to(
        per_peer[:, None], per_peer.shape + conns.shape[-1:])
    return table[q, :].min(axis=-1)


def _rows_min_packed(per_peer, conns):
    """`_rows_min_one` for F lanes (F, N) through one index: the table is
    constant along a lane's row, so a lane needs one COLUMN of the gathered
    row, not C of them: (N, F), one gather, lane f reads column f."""
    return jnp.moveaxis(per_peer.T[jnp.clip(conns, 0), :], -1, 0)


def _rows_scalar(per_peer, conns):
    return per_peer[jnp.clip(conns, 0)]


_ROWS_MIN = (_rows_min_one, _rows_min_packed, _rows_scalar)
_ROWS_MIN_BANDED = tuple(_in_bands(f, _mask_rows, INF) for f in _ROWS_MIN)


def neighbor_rows_min(
    per_peer: jnp.ndarray, conns: jnp.ndarray, batch_factor: int = 1,
) -> jnp.ndarray:
    """out[q, j] = per_peer[conns[q,j]] for floats; INF on invalid slots —
    neighbor_pull_min for an index that has no reverse map (the lat-sorted
    conns_sorted of the answer fold). Every slot of the neighbor's row of
    the broadcast table holds the value, so the row's min IS the value and
    no slot is selected: no (N, C, C) iota mask exists, which inside a
    while_loop XLA would hoist as a loop invariant and keep in HBM (0.5 GB
    as pred at 100k x 40; ops/disseminate._converge_prefix). Same budget
    dispatch as `_row_pull`; over it, XLA's scalar gather. `conns` may be
    `Banded`."""
    if isinstance(conns, Banded):
        return _row_pull(per_peer, _flat(conns), _ROWS_MIN_BANDED,
                         conns.shape, batch_factor, rank=2)
    out = _row_pull(per_peer, (conns,), _ROWS_MIN, conns.shape, batch_factor,
                    rank=2)
    return _mask_rows(out, conns)


def reciprocal_pull_min(
    vals: jnp.ndarray, conns: jnp.ndarray, rev: jnp.ndarray,
    batch_factor: int = 1,
) -> jnp.ndarray:
    """out[q, j] = vals[conns[q,j], rev[q,j]] for float vals; INF on invalid
    slots. Exactly-one-hot select via masked min (INF-safe: the fill value
    is the identity of min and also the 'absent' sentinel). `conns` and
    `rev` may be `Banded`."""
    if isinstance(conns, Banded):
        return _row_pull(vals, _flat(conns, rev), _PULL_MIN_BANDED,
                         conns.shape, batch_factor)
    out = _row_pull(vals, (conns, rev), _PULL_MIN, conns.shape, batch_factor)
    return _mask_min(out, conns, rev)
