"""GossipSub heartbeat as a jit-compiled array step (reference L0 behavior).

One call = one heartbeat of the protocol the reference delegates to
nim-libp2p/go-libp2p-pubsub/rust-libp2p (configured in
gossipsub-queues/main.nim:252-332): mesh rebalance (graft when |mesh| < D_low
up to D, prune when |mesh| > D_high down to D keeping the D_score
highest-scored members and at least D_out outbound members), PRUNE backoff
bookkeeping, and peer-score decay.

Everything is a masked fixed-shape op over the (N, C) neighbor-slot arrays;
reciprocity (GRAFT/PRUNE control messages) is one delivery through the
precomputed reverse-slot involution (ops/graph.py, ops/pull.py): a scatter
from the few rows that send or, where most rows send (step 0 from an empty
mesh), a single row-gather pull. The rebalance work runs under lax.cond so
a stable mesh skips it entirely. Dead neighbors (churn) simply fall out of
the validity mask and are replaced on the next rebalance — the
elastic-recovery analog of the reference's dial-retry loops (SURVEY.md §5).

Which slots a row grafts or prunes is a rank of its slots under a random
priority. The selection is by rows (`_select_rows`): the rows that can
select are the rows that then send, a few tens of 100,000 after a scan's
first step, so where the delivery goes from the few rows that send the rank
is taken in those rows alone, by counting, and sorts nothing; a step in
which every row selects, and every shape under ops/pull's static bound,
keeps the double argsort of every row (`_ranks`). The draws behind the
priorities are NOT by rows: `jax.random.uniform(key, (N, C))` gives a slot
its value by its position in the whole array, and the key schedule and every
bit of the draw are part of the result, so both draws stay whole whichever
rows read them.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .pull import (neighbor_pull_bool, neighbor_update_bool,
                   reciprocal_send_bool, rows_route, sending_rows,
                   sparse_route)
from .state import (PX_POOL_WIDTH, SimParams, SimState, repair_inert,
                    require_repair)

BIG = jnp.float32(1e30)


def _ranks(priority: jnp.ndarray) -> jnp.ndarray:
    """Per-row rank of each slot under ascending priority (double argsort):
    two sorts of every row, which is what a selection over ALL the rows
    costs (`_select_rows` says where the rows are few instead)."""
    return jnp.argsort(jnp.argsort(priority, axis=-1), axis=-1)


def _ranks_counted(priority: jnp.ndarray) -> jnp.ndarray:
    """`_ranks`, bit for bit, without a sort: jax's argsort is stable, so a
    slot's rank is the number of slots of its row that sort before it,
    rank[k, i] = #{j : p[k, j] < p[k, i] or (p[k, j] == p[k, i] and j < i)}.
    C compares a slot, so for a few rows only (priorities are never NaN)."""
    mine, other = priority[..., :, None], priority[..., None, :]
    earlier = jnp.tri(priority.shape[-1], k=-1, dtype=bool)   # [i, j]: j < i
    before = (other < mine) | ((other == mine) & earlier)
    return before.sum(axis=-1, dtype=jnp.int32)


def _select_rows(select, rows, operands):
    """`select(_ranks, *operands)`: an (N, C) bool selection of slots, of a
    `select(rank, *operands)` that works row by row on operands of N rows
    ((N, C) or (N,)) and is all False outside the rows marked in `rows`
    (N,). GRAFT and PRUNE both are: after its first step a scan selects in a
    few tens of rows of 100,000, and every other row would be sorted only to
    compare its rank with 0. So where the shape takes the sparse route
    (ops/pull.sparse_route: the caller passes `rows`; None keeps the one
    program a small, vmapped or sharded step had) a `lax.switch` on the
    marked rows' count, the count against the K that the delivery of the
    same selection switches on (ops/pull.reciprocal_send_bool), chooses:
    `none`: the all-False array, no rank at all (dead peers need D and have
    no eligible slot); `few` (at most K): the K rows of every operand
    gathered, the same `select` on (K, C) with the rank counted
    (`_ranks_counted`: no sort), its rows scattered into an all-False
    (N, C); `all` (step 0 from an empty mesh): `_ranks` over every row.
    What `select` reads must be built whole before the call: the draws are
    (N, C) under one key whichever rows read them, and float arithmetic
    stays in the program every route shares."""
    def all_rows(ops):
        with jax.named_scope("all"):
            return select(_ranks, *ops)

    def none(ops):
        with jax.named_scope("none"):
            return jnp.zeros_like(ops[0], dtype=bool)

    def few(ops):
        with jax.named_scope("few"):
            n = rows.shape[0]
            senders = sending_rows(rows)
            chosen = select(_ranks_counted, *(
                x.at[senders].get(mode="clip") for x in ops))
            # rows past the count go past the end, each to an index of its
            # own, and are dropped (as ops/pull._deliver sends them)
            k = senders.shape[0]
            at = jnp.where(senders < n, senders,
                           n + jnp.arange(k, dtype=jnp.int32))
            return jnp.zeros_like(ops[0], dtype=bool).at[at].set(
                chosen, mode="drop", unique_indices=True,
                indices_are_sorted=True)

    with jax.named_scope("rank"):
        if rows is None:
            return all_rows(operands)
        return jax.lax.switch(
            rows_route(rows.sum(dtype=jnp.int32)), [none, few, all_rows],
            operands)


def _apply_decay(arr: jnp.ndarray, scale, params: SimParams) -> jnp.ndarray:
    """Geometric decay with the zero-cutoff: where(arr*scale < z, 0, ...).
    The one formula behind per-step decay, deferred-scale score reads, and
    the end-of-scan materialization — keep them identical."""
    eff = arr * scale
    return jnp.where(eff < params.decay_to_zero, 0.0, eff)


# The scan's delivery counters (`pulls`, int32 (3, 3)): a row a stage that
# crosses the involution every churned step, a column a count.
PULL_STAGES = ("validity", "graft", "prune")
PULL_COUNTS = ("sparse", "dense", "max_rows")


def _reciprocal_view(
    edge_mask: jnp.ndarray, conns: jnp.ndarray, rev: jnp.ndarray,
    batch_factor: int = 1,
):
    """view[q, j] = edge_mask[conns[q,j], rev[q,j]] — the counterpart edge's
    flag seen from my slot space. Because the reverse-slot map is an
    involution ((p,i) <-> (q,j)), a reciprocal *scatter* ("for every selected
    (p,i), mark (conns[p,i], rev[p,i])") is exactly this *gather*. One
    delivery replaces the reference's GRAFT/PRUNE RPC round trips.

    Which of the two runs is the call's own choice (ops/pull.py
    reciprocal_send_bool): on step 0 from an empty mesh every row sends and
    the gather of whole neighbor ROWS with a fused iota-compare select is
    the fastest form (~4x the naive 2-index gather, ~45 ms at N=100k); on
    every later step a handful of rows send, and the scatter from those
    rows costs a tenth of it. Returns (view, tally): tally int32 (3,) =
    (delivered sparse, pulled dense, sending rows)."""
    return reciprocal_send_bool(edge_mask, conns, rev, batch_factor)


def _tallied(pulls: jnp.ndarray, tallies) -> jnp.ndarray:
    """`pulls` after one step: the counts added, the row maxima kept."""
    t = jnp.stack(tallies)
    return jnp.concatenate(
        [pulls[:, :2] + t[:, :2], jnp.maximum(pulls[:, 2:], t[:, 2:])],
        axis=1)


@partial(jax.jit, static_argnames=("params", "batch_factor"))
def heartbeat_step(
    state: SimState,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    params: SimParams,
    batch_factor: int = 1,
    nbr_ok: jnp.ndarray | None = None,
    valid_pre: jnp.ndarray | None = None,
    decay_scales=None,
    deg_in: jnp.ndarray | None = None,
    edge_ok: jnp.ndarray | None = None,
    spared: jnp.ndarray | None = None,
    pulls: jnp.ndarray | None = None,
):
    """`batch_factor`: width of any enclosing vmap (e.g. the topic axis of
    runtime/multitopic.py) so the pull memory dispatch sees the true
    allocation size (ops/pull.py). `nbr_ok`: optional precomputed neighbor
    alive&subscribed pull — pass it when alive/subscribed cannot change
    between steps (churn off) to hoist the pull out of a scan
    (run_heartbeats); XLA cannot prove loop-carried state invariant itself.
    `valid_pre`: the fully-assembled edge validity mask, hoisting the
    remaining per-step (N, C) conjunction too — the steady-state round is
    then one reduce plus cond probes.

    `decay_scales`: optional (fmd_scale, slow_scale) f32 scalars — the
    DEFERRED-decay protocol run_heartbeats uses. Score decay is a pure
    geometric shrink with a zero-cutoff, so across a scan it factors into
    one scalar per array: this step then touches NO (N, C) decay arrays
    (the caller materializes arr * scale with the cutoff once, after the
    scan), and any score read inside the cond branches applies the scale +
    cutoff on the fly — exactly the per-step-decayed value, because decay
    is monotone (once below decay_to_zero, always below).

    `deg_in`: optional carried (N,) mesh degree — the second scan-level
    protocol (requires `valid_pre`). The caller must have established the
    invariant mesh_mask ⊆ valid_pre (one AND before the scan); every
    branch write here re-ANDs with `valid`, so the invariant is
    preserved, the per-step (N, C) mesh-AND and degree reduce both
    disappear, and the degree is re-reduced only inside a cond when a
    branch actually changed the mesh. When given, the step returns
    (state, deg_out) instead of state.

    `edge_ok`: optional (N, C) per-edge availability mask ANDed into the
    validity conjunction — the fault-injection hook (ops/faults.py): a
    partitioned edge is connected but unusable, so it falls out of `valid`
    exactly like an edge to a dead peer. None keeps the default trace
    untouched (the same optional-arg contract as nbr_ok/valid_pre).

    `spared`: optional (N,) mask of peers the churn draw does not kill (the
    nodes an injector publishes through, runtime/simulator.py): applied
    AFTER the draw, so every other peer's liveness is what the same key
    gives without it. Read only under churn; None (always, with churn off)
    keeps the trace untouched.

    `pulls`: optional int32 (3, 3) delivery counters — the third scan-level
    protocol (run_heartbeats'): a row each for `PULL_STAGES`, the steps the
    stage delivered sparse, the steps it pulled dense, and the largest
    number of sending rows it saw (`PULL_COUNTS`; a stage whose cond did not
    fire delivered nothing and counts nowhere). When given, the step
    returns the updated counters last. Under churn it also makes `nbr_ok`
    a CARRIED view instead of a stale one: the caller passes the neighbour
    pull of the incoming `alive & subscribed`, the step flips it at the
    slots that point at the peers this step's draw changed
    (ops/pull.neighbor_update_bool: a handful of rows, not a pull) and
    returns the new view before the counters: (state, nbr_ok, pulls).

    Device scopes (jax.named_scope: metadata only, no operation is added)
    name the step's stages for a profile: `churn`, `validity`, `graft`,
    `prune`, `evict`, `px`, `opportunistic`, `decay`, `fanout`, `state`."""
    if deg_in is not None and (
        valid_pre is None
        or edge_ok is not None
        or params.churn_down_per_hb > 0.0
        or params.churn_up_per_hb > 0.0
    ):
        # the carried-degree protocol only makes sense on top of the
        # hoisted validity mask with churn off; reject misuse loudly (the
        # degrees would silently count edges to dead/unsubscribed peers,
        # or the return arity would silently change under churn)
        raise ValueError("deg_in requires valid_pre, no edge_ok, and churn "
                         "off (run_heartbeats' churn-free scan protocol)")
    if not repair_inert(params):
        require_repair(state)
    n, c = conns.shape
    # whether GRAFT and PRUNE select by rows (_select_rows), as their
    # deliveries do: the trace-time half of ops/pull's sparse dispatch
    routed = sparse_route(conns.shape, batch_factor)
    with jax.named_scope("state"):
        key, k_graft, k_keep, k_churn_d, k_churn_u = jax.random.split(
            state.key, 5)
    t = state.t_ms

    # -- churn (failure injection; BASELINE config 4) ------------------------
    alive = state.alive
    if params.churn_down_per_hb > 0.0 or params.churn_up_per_hb > 0.0:
        with jax.named_scope("churn"):
            dies = (jax.random.uniform(k_churn_d, (n,))
                    < params.churn_down_per_hb)
            revives = (jax.random.uniform(k_churn_u, (n,))
                       < params.churn_up_per_hb)
            if spared is not None:
                # after the draw: nobody else's liveness moves with the mask
                dies = dies & ~spared
            alive = jnp.where(alive, ~dies, revives)
            # alive just changed: precomputed masks are stale, but for the
            # scan's carried view, which the validity stage brings up to date
            nbr_carried = nbr_ok if pulls is not None else None
            nbr_ok = None
            valid_pre = None
            # the warm-start carry measured arrival offsets on the OLD
            # liveness set — a revived peer's stale offset (or a died relay's
            # reachability) makes the re-based seed meaningless, so
            # invalidate the whole carry (disseminate's certificate would
            # catch a bad seed anyway; this keeps the next publish on the
            # cheap no-rerun path)
            warm = jnp.full_like(state.warm_offset_ms, 3.4e38)
    else:
        warm = state.warm_offset_ms
        nbr_carried = None

    validity_tally = None   # what this stage delivered this step (`pulls`)
    with jax.named_scope("validity"):
        if valid_pre is not None:
            valid = valid_pre
        else:
            has_conn = conns >= 0
            if nbr_carried is not None:
                # about ten peers of 100,000 changed: flip the slots that
                # point at them instead of pulling every neighbour anew
                nbr_ok, validity_tally = neighbor_update_bool(
                    nbr_carried, alive & state.subscribed,
                    (alive ^ state.alive) & state.subscribed,
                    conns, rev, batch_factor)
            elif nbr_ok is None:
                # one pull for the conjunction (alive AND subscribed) — each
                # pull is a full row-gather pass, so fusing the two masks
                # halves the cost
                nbr_ok = neighbor_pull_bool(
                    alive & state.subscribed, conns, rev, batch_factor)
            valid = (has_conn & alive[:, None] & nbr_ok
                     & state.subscribed[:, None])
        if edge_ok is not None:
            # fault injection: a partitioned edge is invalid for the round
            # even though both endpoints are alive; applied after valid_pre
            # too, so the fault scan can hoist the liveness conjunction and
            # still mask
            valid = valid & edge_ok

        if deg_in is not None:
            # carried-degree protocol: mesh_mask ⊆ valid already (caller's
            # pre-scan AND + every branch write re-ANDing), so the per-step
            # mesh-AND and degree reduce are skipped outright
            mesh = state.mesh_mask
            deg = deg_in
        else:
            mesh = state.mesh_mask & valid  # drop edges to dead/unsubscribed
            deg = mesh.sum(axis=-1)

    def _score_now():
        if decay_scales is None:
            return state.score(params)
        # deferred decay: reconstruct this step's exact decayed view and
        # delegate the score formula to the one place it lives
        f_sc, s_sc = decay_scales
        return state.replace(
            fmd=_apply_decay(state.fmd, f_sc, params),
            slow_penalty=_apply_decay(state.slow_penalty, s_sc, params),
        ).score(params)

    # score() is only consumed inside the cond-gated graft/prune/og branches;
    # computing it lazily there keeps the steady-state step score-free. With
    # opportunistic grafting enabled the og block needs scores every step
    # anyway — compute once and share instead of once per branch.
    _og_enabled = params.opportunistic_graft_threshold > -9999.0
    _scores = _score_now() if _og_enabled else None

    def get_scores():
        return _scores if _scores is not None else _score_now()

    # -- GRAFT: |mesh| < D_low -> add random eligible peers up to D ----------
    # The whole selection (uniform draw + rank + reciprocal delivery) runs
    # under a cond: at steady state every row sits in [D_low, D_high] and
    # the step skips straight through. Key consumption stays identical
    # either way (k_graft was split above).
    with jax.named_scope("graft"):
        need = jnp.where(deg < params.d_low, params.d - deg, 0)
        # built from deg so it varies over whatever manual axes deg does: a
        # cond under shard_map needs both branches to agree on them
        zeros_n = jnp.zeros_like(deg, dtype=jnp.int32)
        no_pull = zeros_n[:3]

    def do_graft(mesh):
        eligible = (valid & ~mesh & (state.backoff_until <= t)
                    & (get_scores() >= 0.0))
        g_prio = jnp.where(eligible, jax.random.uniform(k_graft, (n, c)), BIG)

        def select(rank, g_prio, need, eligible):
            return (rank(g_prio) < need[:, None]) & eligible

        # a row grafts iff it needs a member and has an eligible slot
        grafted = _select_rows(
            select, (need > 0) & eligible.any(axis=-1) if routed else None,
            (g_prio, need, eligible))
        # GRAFT control msg: counterpart adds us to its mesh (handleGraft
        # accepts unless backed off; overflow is corrected at its own next
        # heartbeat). The reciprocal view IS the receive side — both
        # directions are counted per peer. The counter increments and the
        # refreshed degree are reduced INSIDE the branch: at steady state
        # the round pays no (N, C) reduce for them at all.
        graft_rx, tally = _reciprocal_view(grafted, conns, rev, batch_factor)
        mesh = (mesh | grafted | graft_rx) & valid
        return (mesh, mesh.sum(axis=-1),
                grafted.sum(axis=-1, dtype=jnp.int32),
                graft_rx.sum(axis=-1, dtype=jnp.int32), tally)

    with jax.named_scope("graft"):
        mesh, deg2, graft_tx_inc, graft_rx_inc, graft_tally = jax.lax.cond(
            (need > 0).any(),
            do_graft,
            lambda m: (m, deg, zeros_n, zeros_n, no_pull),
            mesh,
        )

    # -- PRUNE: |mesh| > D_high -> keep D (D_score best, >= D_out outbound) --
    # The whole selection (three ranks) plus the reciprocal delivery runs
    # under a cond: at steady state no row exceeds D_high and the step skips
    # it.
    with jax.named_scope("prune"):
        over = deg2 > params.d_high

    def _prune_sel(mesh):
        rand_keep = jax.random.uniform(k_keep, (n, c))
        scores = get_scores()
        # rank by descending score (random tiebreak) among mesh members
        s_prio = jnp.where(mesh, -scores + 1e-3 * rand_keep, BIG)

        def select(rank, s_prio, mesh, rand_keep, out_mask, over):
            top_score = (rank(s_prio) < params.d_score) & mesh
            # at least D_out outbound among the kept set
            out_in_top = (top_score & out_mask).sum(axis=-1)
            need_out = jnp.clip(params.d_out - out_in_top, 0, params.d)
            o_prio = jnp.where(mesh & out_mask & ~top_score, rand_keep, BIG)
            keep_out = ((rank(o_prio) < need_out[:, None])
                        & mesh & out_mask & ~top_score)
            # random fill to exactly D
            base = top_score | keep_out
            need_fill = jnp.clip(params.d - base.sum(axis=-1), 0, params.d)
            f_prio = jnp.where(mesh & ~base, rand_keep, BIG)
            keep = base | ((rank(f_prio) < need_fill[:, None]) & mesh & ~base)
            return mesh & ~keep & over[:, None]

        pruned = _select_rows(
            select, over if routed else None,
            (s_prio, mesh, rand_keep, out_mask, over))
        mesh = mesh & ~pruned
        # PRUNE control msg: counterpart drops us; backoff on both sides
        pruned_by_peer, tally = _reciprocal_view(
            pruned, conns, rev, batch_factor)
        backoff = jnp.where(
            pruned | pruned_by_peer,
            t + params.prune_backoff_ms, state.backoff_until)
        return (mesh & ~pruned_by_peer, backoff,
                pruned.sum(axis=-1, dtype=jnp.int32),
                pruned_by_peer.sum(axis=-1, dtype=jnp.int32), tally,
                pruned_by_peer)

    pruned_rx = None
    with jax.named_scope("prune"):
        if params.px:
            # PX needs the received-PRUNE edge set out of the branch; the
            # extra output exists only on the opt-in trace (ops/repair.py)
            (mesh, backoff, prune_tx_inc, prune_rx_inc, prune_tally,
             pruned_rx) = jax.lax.cond(
                over.any(),
                _prune_sel,
                lambda m: (m, state.backoff_until, zeros_n, zeros_n, no_pull,
                           jnp.zeros((n, c), dtype=bool)),
                mesh,
            )
        else:
            (mesh, backoff, prune_tx_inc, prune_rx_inc,
             prune_tally) = jax.lax.cond(
                over.any(),
                lambda m: _prune_sel(m)[:5],
                lambda m: (m, state.backoff_until, zeros_n, zeros_n, no_pull),
                mesh,
            )

    # -- score eviction (mesh repair; opt-in via params.evict) ---------------
    # v1.1 mesh maintenance also drops members whose score sank below a
    # floor, with PRUNE + backoff on both sides (go-libp2p-pubsub prunes
    # negative-score peers before rebalancing). Statically gated so the
    # default step carries none of it; inside the gate a separate lax.cond
    # keeps the healthy steady state (nobody under the floor) probe-cheap.
    # Reciprocity reuses _reciprocal_view — identical PRUNE semantics to
    # _prune_sel. The predicate pays one score materialization per step;
    # that is the documented cost of arming eviction.
    ev_tx_inc = ev_rx_inc = None
    evict_fired = None
    ev_rx_edges = None
    with jax.named_scope("evict"):
        if params.evict:
            ev_cand = mesh & (get_scores() < params.eviction_threshold)
            evict_fired = ev_cand.any()

            def do_evict(mesh, backoff):
                ev_rx, _ = _reciprocal_view(ev_cand, conns, rev, batch_factor)
                new_backoff = jnp.where(
                    ev_cand | ev_rx, t + params.prune_backoff_ms, backoff)
                return (mesh & ~ev_cand & ~ev_rx, new_backoff,
                        ev_cand.sum(axis=-1, dtype=jnp.int32),
                        ev_rx.sum(axis=-1, dtype=jnp.int32),
                        ev_rx)

            mesh, backoff, ev_tx_inc, ev_rx_inc, ev_rx_edges = jax.lax.cond(
                evict_fired,
                do_evict,
                lambda m, b: (m, b, zeros_n, zeros_n,
                              jnp.zeros((n, c), dtype=bool)),
                mesh, backoff,
            )

    # -- PX on PRUNE (mesh repair; opt-in via params.px) ---------------------
    # Every PRUNE (degree rebalance or eviction) carries up to px_count
    # candidate peer ids: the pruner's best-scored valid neighbors ("honest"
    # proxied by score >= 0 — penalized/graylisted peers are never
    # advertised). The prunee stores them in its px_pool; acting on them
    # (graft / dial) is the repair controller's job next heartbeat
    # (ops/repair.py repair_round). Deterministic slot-index tiebreak: no
    # PRNG is consumed, keeping the default key schedule untouched.
    px_pool = None
    with jax.named_scope("px"):
        if params.px:
            got_pruned = pruned_rx
            if ev_rx_edges is not None:
                got_pruned = got_pruned | ev_rx_edges

            def do_px(pool):
                scores = get_scores()
                elig = valid & (scores >= 0.0)
                prio = (jnp.where(elig, -scores, BIG)
                        + 1e-4 * jnp.arange(c, dtype=jnp.float32))
                w = min(PX_POOL_WIDTH, c)
                order = jnp.argsort(prio, axis=-1)[:, :w]
                take_ok = (jnp.take_along_axis(elig, order, axis=-1)
                           & (jnp.arange(w) < params.px_count))
                cand = jnp.where(
                    take_ok, jnp.take_along_axis(conns, order, axis=-1), -1)
                if w < PX_POOL_WIDTH:
                    cand = jnp.pad(cand, ((0, 0), (0, PX_POOL_WIDTH - w)),
                                   constant_values=-1)
                # the prunee reads the advert off ONE pruning edge (the lowest
                # pruning slot) — one row-gather through the involution, same
                # shape economics as _reciprocal_view
                got = got_pruned.any(axis=-1)
                i0 = jnp.argmax(got_pruned, axis=-1)
                pruner = jnp.take_along_axis(conns, i0[:, None], axis=1)[:, 0]
                advert = cand[jnp.clip(pruner, 0)]
                advert = jnp.where(
                    advert == jnp.arange(n, dtype=jnp.int32)[:, None], -1, advert)
                return jnp.where(got[:, None], advert, pool)

            px_pool = jax.lax.cond(
                got_pruned.any(), do_px, lambda p: p, state.px_pool)

    # -- opportunistic grafting (v1.1, main.nim:292): when the MEDIAN mesh
    # score sinks below the threshold, graft up to 2 peers scoring above the
    # median (escape hatch from a low-quality mesh). Static-gated: at the
    # disabled default (-10000) the sort never enters the compiled step.
    og_tx_inc = zeros_n
    og_rx_inc = zeros_n
    with jax.named_scope("opportunistic"):
        if params.opportunistic_graft_threshold > -9999.0:
            scores = get_scores()
            deg3 = mesh.sum(axis=-1)
            msort = jnp.sort(jnp.where(mesh, scores, BIG), axis=-1)
            # upper median (sorted[len/2]) — matches the libp2p implementations
            k_med = jnp.clip(deg3 // 2, 0, c - 1)
            median = jnp.take_along_axis(msort, k_med[:, None], axis=-1)[:, 0]
            low = (median < params.opportunistic_graft_threshold) & (deg3 > 0)
            og_elig = (valid & ~mesh & (backoff <= t)
                       & (scores > median[:, None]) & low[:, None])
            og_prio = jnp.where(og_elig, -scores, BIG)  # best scores first
            og = (_ranks(og_prio) < 2) & og_elig
            # same steady-state economics as graft/prune: the reciprocal pull
            # and the counter reduces only run when something actually grafted
            def do_og(m):
                rx, _ = _reciprocal_view(og, conns, rev, batch_factor)
                return ((m | og | rx) & valid,
                        og.sum(axis=-1, dtype=jnp.int32),
                        rx.sum(axis=-1, dtype=jnp.int32))

            mesh, og_tx_inc, og_rx_inc = jax.lax.cond(
                og.any(),
                do_og,
                lambda m: (m, zeros_n, zeros_n),
                mesh,
            )

    # -- score decay (decayInterval == heartbeat here; main.nim:272-273) -----
    with jax.named_scope("decay"):
        if decay_scales is not None:
            # deferred: the scan carries the scalar scales; arrays untouched
            fmd, slow = state.fmd, state.slow_penalty
        else:
            # gated: once everything decayed to zero (no recent messages) the
            # two (N, C) rewrite passes per step are skipped
            def do_decay(fmd, slow):
                return (_apply_decay(fmd, params.fmd_decay, params),
                        _apply_decay(slow, params.slow_decay, params))

            fmd, slow = jax.lax.cond(
                # one fused (N, C) reduce for the predicate, not one per array
                ((state.fmd > 0) | (state.slow_penalty > 0)).any(),
                do_decay,
                lambda f, s: (f, s),
                state.fmd, state.slow_penalty,
            )

    # -- fanout expiry (v1.1 fanoutTTL): a fanout set whose owner hasn't
    # fanout-published within the TTL is dropped wholesale (nim-libp2p
    # dropFanoutPeers). Gated on the (N,) expiry stamps — nonzero only for
    # peers that ever fanout-published — so runs with no fanout publishers
    # pay an (N,) reduce, not an (N, C) one.
    with jax.named_scope("fanout"):
        fanout = jax.lax.cond(
            (state.fanout_expire > 0.0).any(),
            lambda fm: fm & (t < state.fanout_expire)[:, None],
            lambda fm: fm,
            state.fanout_mask,
        )

    with jax.named_scope("state"):
        prunes_new = state.prunes + prune_tx_inc
        prunes_rx_new = state.prunes_rx + prune_rx_inc
        repair_extra = {}
        if params.evict:
            # an eviction IS a PRUNE control message; count it in both ledgers
            prunes_new = prunes_new + ev_tx_inc
            prunes_rx_new = prunes_rx_new + ev_rx_inc
            repair_extra["evictions"] = state.evictions + ev_tx_inc
        if params.px:
            repair_extra["px_pool"] = px_pool
        new_state = state.replace(
            mesh_mask=mesh,
            fanout_mask=fanout,
            backoff_until=backoff,
            fmd=fmd,
            slow_penalty=slow,
            alive=alive,
            warm_offset_ms=warm,
            t_ms=t + params.heartbeat_ms,
            key=key,
            grafts=state.grafts + graft_tx_inc + og_tx_inc,
            grafts_rx=state.grafts_rx + graft_rx_inc + og_rx_inc,
            prunes=prunes_new,
            prunes_rx=prunes_rx_new,
            **repair_extra,
        )
    out = [new_state]
    if deg_in is not None:
        # carried degree: re-reduce only if some branch actually touched the
        # mesh this step — the steady-state round stays free of (N, C)
        # reduces
        with jax.named_scope("validity"):
            fired = (need > 0).any() | over.any()
            if params.opportunistic_graft_threshold > -9999.0:
                fired = fired | og.any()
            if params.evict:
                fired = fired | evict_fired
            out.append(jax.lax.cond(
                fired, lambda m: m.sum(axis=-1), lambda m: deg_in, mesh))
    if nbr_carried is not None:
        out.append(nbr_ok)
    if pulls is not None:
        with jax.named_scope("state"):
            out.append(_tallied(pulls, [
                no_pull if validity_tally is None else validity_tally,
                graft_tally, prune_tally]))
    return out[0] if len(out) == 1 else tuple(out)


def run_heartbeats(
    state: SimState,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    params: SimParams,
    steps: int,
    spared: jnp.ndarray | None = None,
    with_pulls: bool = False,
):
    """lax.scan over heartbeat rounds — simulated time scales in rounds with
    no host sync (the reference's 'long simulated time' axis, SURVEY.md §5).

    The jitted scan is `_run_heartbeats`, which carries the state as it
    is: one made for params that arm no repair holds no repair leaf
    (ops/state.py), so the scan has none to pass through. NOT donated:
    callers (tests) re-run segments from a kept state. Jitted with static
    `steps` so repeated same-length segments (the simulator's inter-message
    gaps) hit the compile cache.

    `spared`: heartbeat_step's — (N,) peers the churn draw does not kill;
    None with churn off, where nothing would read it.

    `with_pulls`: also return the scan's delivery counters (heartbeat_step's
    `pulls`, the pull in front of the scan counted as a dense `validity`),
    a device array nobody has waited for: (state, pulls). The program is
    the same either way."""
    out, pulls = _run_heartbeats(
        state, conns, rev, out_mask, params, steps, spared)
    return (out, pulls) if with_pulls else out


@partial(jax.jit, static_argnames=("params", "steps"))
def _run_heartbeats(
    state: SimState,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    params: SimParams,
    steps: int,
    spared: jnp.ndarray | None = None,
):
    """(state, pulls): the state after `steps` rounds and the scan's delivery
    counters (heartbeat_step's `pulls`)."""
    # the one dense pull in front of either scan: without churn it is the
    # only one (alive/subscribed are invariant, so the pull — a full
    # row-gather pass — hoists out of the loop, and so does the whole
    # edge-validity conjunction); under churn it seeds the view the steps
    # keep up to date from the handful of peers each draw changes. (A
    # churned scan of a shape the sparse route refuses pulls anew every
    # step and never reads the view: the pull in front is dead code there,
    # and not counted.)
    churn_free = (params.churn_down_per_hb == 0.0
                  and params.churn_up_per_hb == 0.0)
    with jax.named_scope("validity"), jax.named_scope("dense"):
        nbr_ok = neighbor_pull_bool(
            state.alive & state.subscribed, conns, rev)
        # PULL_STAGES x PULL_COUNTS: it counts as one dense `validity`
        in_front = int(churn_free or sparse_route(conns.shape))
        pulls = jnp.asarray(
            [[0, in_front, 0], [0, 0, 0], [0, 0, 0]], jnp.int32)
    valid_pre = None
    if churn_free:
        with jax.named_scope("validity"):
            valid_pre = ((conns >= 0) & state.alive[:, None] & nbr_ok
                         & state.subscribed[:, None])

    one = jnp.float32(1.0)
    if valid_pre is not None:
        # carried-degree protocol: establish mesh_mask ⊆ valid ONCE (the
        # AND every step used to apply), then the steady-state round pays
        # no (N, C) mesh-AND or degree reduce at all
        with jax.named_scope("validity"):
            mesh0 = state.mesh_mask & valid_pre
            deg0 = mesh0.sum(axis=-1)
        state = state.replace(mesh_mask=mesh0)

        def body(carry, _):
            s, deg, pulls, f_sc, s_sc = carry
            s, deg, pulls = heartbeat_step(
                s, conns, rev, out_mask, params, nbr_ok=nbr_ok,
                valid_pre=valid_pre, decay_scales=(f_sc, s_sc), deg_in=deg,
                pulls=pulls)
            with jax.named_scope("decay"):
                f_sc, s_sc = f_sc * params.fmd_decay, s_sc * params.slow_decay
            return (s, deg, pulls, f_sc, s_sc), None

        (state, _, pulls, f_sc, s_sc), _ = jax.lax.scan(
            body, (state, deg0, pulls, one, one), None, length=steps)
    else:
        # the neighbour view rides in the carry (4 MB at 100,000 x 40) and
        # nowhere else: no SimState leaf, nothing for a checkpoint to hold
        def body(carry, _):
            s, nbr, pulls, f_sc, s_sc = carry
            s, nbr, pulls = heartbeat_step(
                s, conns, rev, out_mask, params, nbr_ok=nbr,
                decay_scales=(f_sc, s_sc), spared=spared, pulls=pulls)
            # end-of-round decay, factored to two scalar multiplies
            with jax.named_scope("decay"):
                f_sc, s_sc = f_sc * params.fmd_decay, s_sc * params.slow_decay
            return (s, nbr, pulls, f_sc, s_sc), None

        (state, _, pulls, f_sc, s_sc), _ = jax.lax.scan(
            body, (state, nbr_ok, pulls, one, one), None, length=steps)
    # materialize the deferred decay ONCE per scan (vs two (N, C) passes
    # plus a predicate reduce per round): exact, because geometric decay
    # with a monotone zero-cutoff commutes with deferral
    with jax.named_scope("decay"):
        state = state.replace(
            fmd=_apply_decay(state.fmd, f_sc, params),
            slow_penalty=_apply_decay(state.slow_penalty, s_sc, params),
        )
    return state, pulls
