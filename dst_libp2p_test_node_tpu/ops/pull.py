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
    (5.3 ms inside the publish's loops: PR 27's trace)
  - a per-peer lookup `t[idx]` over an (N, C) index: 26.8 ms as XLA's scalar
    gather, 8.6 ms as a row pull of the broadcast table (neighbor_rows_min)
  - a WITHIN-ROW permutation (`take_along_axis(x, idx, axis=-1)`, nothing
    crosses rows): 39.7 ms as XLA lowers it — the same 4M scalar loads — and
    0.28 ms as selects alone, no gather (permute_rows; f32, bool and int32
    alike; the (N, C, C) one-hot form 0.21-0.25 ms, see there why not that)

The sharded fixpoint (parallel/exchange.py converge_sharded) deliberately
does NOT use this: its per-iteration cross-shard traffic is the (N,) time
vector alone, and the pull there is against receiver-local constants.
"""

from __future__ import annotations

import jax.numpy as jnp

INF = jnp.float32(3.4e38)

# Peak-memory budget for the (N, C, C) row-gather intermediate. The last
# axis pads to the 128-lane TPU tile, so the real footprint is
# N*C*max(128,C)*itemsize bytes. Within budget the row gather is the fastest
# formulation (11 ms f32 / at 100k,C=40 vs 45 ms for the naive 2-index
# gather). Beyond it — e.g. f32 at 1M peers would be a 20 GiB intermediate —
# the memory-light 2-index gather WINS outright (732 ms/pull at 1M vs
# ~2.7 s for a sequentially-chunked row gather: chunk serialization costs
# more than the random scalar loads), so large pulls simply fall back.
_MAX_INTERMEDIATE_BYTES = 6 * 1024**3
_LANE = 128


def intermediate_bytes(dtype, conns_shape, batch_factor: int = 1) -> int:
    """Bytes of the padded (N, C, C) row-gather intermediate of one pull,
    times `batch_factor` pulls live at once (see exceeds_budget)."""
    n, c = conns_shape[-2], conns_shape[-1]
    itemsize = 1 if dtype == jnp.bool_ else jnp.dtype(dtype).itemsize
    return n * c * max(_LANE, c) * itemsize * max(batch_factor, 1)


def exceeds_budget(dtype, conns_shape, batch_factor: int = 1) -> bool:
    """The dispatch decision, exposed for tests: would the padded row-gather
    intermediate for this pull exceed the memory budget?

    `batch_factor`: outer vmap width (fragments, topics). Trace-time shapes
    are per-instance — the REAL allocation is batch_factor times the
    per-instance intermediate, so the dispatch must account for it or a
    9-fragment publish would blow an in-budget 2 GiB pull up to 18 GiB."""
    return (intermediate_bytes(dtype, conns_shape, batch_factor)
            > _MAX_INTERMEDIATE_BYTES)


def _row_pull(vals, conns, rev, select, fallback, batch_factor):
    """Size-dispatched core. `select(rows, sel)` reduces the gathered rows;
    `fallback(q, r)` is the direct 2-index gather used when the row-gather
    intermediate would not fit the budget (see exceeds_budget)."""
    c = conns.shape[-1]
    if exceeds_budget(vals.dtype, conns.shape, batch_factor):
        return fallback(jnp.clip(conns, 0), jnp.clip(rev, 0))
    rows = vals[..., jnp.clip(conns, 0), :]   # (..., N, C, C) contiguous
    sel = jnp.arange(c) == jnp.clip(rev, 0)[..., None]
    return select(rows, sel)


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
    """out[q, j] = edge_mask[conns[q,j], rev[q,j]]; False on invalid slots."""
    out = _row_pull(
        edge_mask, conns, rev,
        lambda rows, sel: (rows & sel).any(axis=-1),
        lambda q, r: edge_mask[q, r], batch_factor)
    return out & (conns >= 0) & (rev >= 0)


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
    dispatch as `_row_pull`; over it, XLA's scalar gather."""
    q = jnp.clip(conns, 0)
    if exceeds_budget(per_peer.dtype, conns.shape, batch_factor):
        out = per_peer[q]
    else:
        table = jnp.broadcast_to(per_peer[:, None], conns.shape)
        out = table[q, :].min(axis=-1)
    return jnp.where(conns >= 0, out, INF)


def reciprocal_pull_min(
    vals: jnp.ndarray, conns: jnp.ndarray, rev: jnp.ndarray,
    batch_factor: int = 1,
) -> jnp.ndarray:
    """out[q, j] = vals[conns[q,j], rev[q,j]] for float vals; INF on invalid
    slots. Exactly-one-hot select via masked min (INF-safe: the fill value
    is the identity of min and also the 'absent' sentinel)."""
    out = _row_pull(
        vals, conns, rev,
        lambda rows, sel: jnp.where(sel, rows, INF).min(axis=-1),
        lambda q, r: vals[q, r], batch_factor)
    return jnp.where((conns >= 0) & (rev >= 0), out, INF)
