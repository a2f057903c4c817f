"""Episub: a Topiary-style eager-push tree backend (arXiv:2312.06800).

The second protocol in the arena (ops/protocol.py). Where GossipSub
maintains a redundant D-regular mesh, episub maintains a spanning TREE
rooted at the publisher: each peer adopts its minimum-hop valid neighbor
as parent (distributed Bellman-Ford relaxation, one neighbor-pull per
heartbeat), eager-pushes only along parent/child edges, and advertises
lazily (IHAVE-style) along up to d_lazy non-tree edges so a broken
branch can be repaired through the message-grain gossip machinery. The
trade the arena measures is exactly Topiary's: ~N-1 eager edges instead
of ~N*D/2, so far lower amplification, bought with a single point of
structural failure per subtree.

Everything reuses the house machinery:

  * SimState is shared unchanged — the tree IS mesh_mask (the eager-push
    edge set disseminate forwards along), so publish/delivery, telemetry
    channels, faults, and the adversary all compose without a new code
    path. Non-mesh edges are episub's lazy channel, which is precisely
    what disseminate's gossip emission already samples.
  * Per-protocol carry (hop estimates, parent slots) follows the
    AdaptiveCtrl discipline (ops/state.py): a separate EpisubCtrl pytree
    threaded through the armed scans, never a SimState leaf, so the
    GossipSub traces cannot grow a dead carry by construction.
  * Scoring compatibility: an edge whose score sank below
    params.graylist_threshold is neither an acceptable parent nor an
    accepted child — the attacker faces the same graylist defense on
    both backends (static-gated like the engine: with non-negative
    weights the comparison compiles out).
  * Re-parenting on churn/eviction is implicit: a dead/partitioned/
    graylisted parent falls out of the validity mask, its children's
    candidate hops go to INF, and the next relaxation adopts the best
    surviving neighbor. A detached subtree's stale hop estimates can
    only count UP (classic Bellman-Ford), so candidates are clamped at
    N hops — a component with no finite-hop path to the root drains to
    unreached within N rounds instead of counting to infinity.

Determinism: ties in the parent choice resolve to the LOWEST NEIGHBOR
SLOT (jnp.argmin's first-occurrence rule) — the same deterministic
slot-order policy the spec's opportunistic-grafting tie break documents
(ops/spec.py opportunistic_graft_candidates). The step consumes PRNG
only for churn (3 splits, unconditionally, mirroring heartbeat_step's
fixed key schedule so a fixed seed gives a reproducible trajectory).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from .adversary import AdversaryParams, adaptive_round, adversary_round
from .faults import FaultParams, partition_edge_mask
from .heartbeat import _apply_decay
from .pull import neighbor_pull_bool, neighbor_pull_min, reciprocal_pull_bool
from .state import SimParams, SimState, init_adaptive_ctrl

# numpy, NOT jnp: the protocol registry imports this module lazily, and
# the first import can happen INSIDE an active jit trace (a campaign
# window resolving get_protocol under lowering) — a module-level
# jnp.float32 would bind a tracer from that trace to the global and leak
# it into every later compile as a phantom hoisted parameter
INF = np.float32(3.4e38)


@dataclass(frozen=True)
class EpisubParams:
    """Static episub configuration (hashable -> jit static arg).

    `root`: the tree root's peer id — the arena pins it to the trial's
    publisher so the eager tree points the way the traffic flows.
    `lazy_degree`: per-round IHAVE advertisement budget along non-tree
    edges; None defers to params.d_lazy (the GossipSub lazy floor, the
    fair default for head-to-head runs)."""

    root: int = 0
    lazy_degree: int | None = None

    def validate(self, n: int) -> None:
        if not (0 <= self.root < n):
            raise ValueError(f"root must be in [0, {n}), got {self.root}")
        if self.lazy_degree is not None and self.lazy_degree < 0:
            raise ValueError("lazy_degree must be >= 0")


@struct.dataclass
class EpisubCtrl:
    """On-device per-peer tree state, (N,). `hops` is the peer's current
    estimate of its hop distance to the root (INF = unreached); `parent`
    is the NEIGHBOR SLOT of its parent edge (-1 = none — the root, or a
    detached peer); `reparents` counts parent changes (the episub analog
    of the graft/prune control churn)."""

    hops: jnp.ndarray       # (N,) f32 hop estimate to root; INF unreached
    parent: jnp.ndarray     # (N,) i32 parent neighbor slot; -1 = none
    reparents: jnp.ndarray  # (N,) i32 cumulative parent changes


def init_episub_ctrl(n: int) -> EpisubCtrl:
    """Fresh (fully detached) tree carry for one trial window."""
    return EpisubCtrl(
        hops=jnp.full((n,), 3.4e38, dtype=jnp.float32),
        parent=jnp.full((n,), -1, dtype=jnp.int32),
        reparents=jnp.zeros((n,), dtype=jnp.int32),
    )


def episub_observables(ctrl: EpisubCtrl, alive: jnp.ndarray,
                       subscribed: jnp.ndarray) -> dict:
    """The per-round episub obs channels (ProtocolSpec.observables):
    tree_reach_frac — fraction of live subscribed peers with a finite
    hop estimate (the tree's coverage of the peer set); tree_depth_mean
    — mean hop distance over reached peers (the eager path length)."""
    n = ctrl.hops.shape[0]
    live = alive & subscribed
    reached = live & (ctrl.hops <= jnp.float32(n))
    n_r = jnp.maximum(reached.sum(), 1)
    return {
        "tree_reach_frac": (reached.sum()
                            / jnp.float32(jnp.maximum(live.sum(), 1))),
        "tree_depth_mean": (jnp.where(reached, ctrl.hops, 0.0).sum()
                            / jnp.float32(n_r)),
    }


@partial(jax.jit, static_argnames=("params", "ep", "batch_factor"))
def episub_heartbeat_step(
    state: SimState,
    ctrl: EpisubCtrl,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    params: SimParams,
    ep: EpisubParams,
    batch_factor: int = 1,
    nbr_ok: jnp.ndarray | None = None,
    edge_ok: jnp.ndarray | None = None,
):
    """One episub heartbeat: hop relaxation -> parent adoption -> tree
    edge set -> lazy IHAVE budget -> score decay. Same optional-arg
    contract as heartbeat_step: `nbr_ok` hoists the liveness pull out of
    churn-free scans, `edge_ok` is the fault-injection hook. Returns
    (state, ctrl); mesh_mask on return IS the tree (parent edge plus
    accepted child edges), which disseminate eager-pushes along."""
    n, c = conns.shape
    key, k_churn_d, k_churn_u = jax.random.split(state.key, 3)
    t = state.t_ms

    # -- churn (same schedule semantics as heartbeat_step) -------------------
    alive = state.alive
    if params.churn_down_per_hb > 0.0 or params.churn_up_per_hb > 0.0:
        dies = jax.random.uniform(k_churn_d, (n,)) < params.churn_down_per_hb
        revives = jax.random.uniform(k_churn_u, (n,)) < params.churn_up_per_hb
        alive = jnp.where(alive, ~dies, revives)
        nbr_ok = None   # alive just changed; precomputed masks are stale
        warm = jnp.full_like(state.warm_offset_ms, 3.4e38)
    else:
        warm = state.warm_offset_ms

    if nbr_ok is None:
        nbr_ok = neighbor_pull_bool(
            alive & state.subscribed, conns, rev, batch_factor)
    valid = ((conns >= 0) & alive[:, None] & nbr_ok
             & state.subscribed[:, None])
    if edge_ok is not None:
        valid = valid & edge_ok

    # scoring-compatible graylist: a graylisted edge is neither a parent
    # candidate nor an accepted child. Static-gated exactly like the
    # engine's threshold machinery — with non-negative score weights the
    # floor can never bind and the compare compiles out.
    _gray = params.slow_weight < 0.0 or params.fmd_weight < 0.0
    if _gray:
        ok_edge = valid & (state.score(params) >= params.graylist_threshold)
    else:
        ok_edge = valid

    # -- hop relaxation + parent adoption ------------------------------------
    # pull every neighbor's hop estimate (INF on invalid slots), relax by
    # one hop, clamp runaway estimates at N (a detached subtree's stale
    # values count up, never down — the clamp drains it to unreached in
    # at most N rounds instead of forever)
    is_root = jnp.arange(n) == ep.root
    nbr_hops = neighbor_pull_min(ctrl.hops, conns, rev, batch_factor)
    cand = jnp.where(ok_edge & (nbr_hops < jnp.float32(n)),
                     nbr_hops + 1.0, INF)
    best = cand.min(axis=-1)
    best_slot = jnp.argmin(cand, axis=-1).astype(jnp.int32)  # lowest slot
    # parent damping: keep the incumbent while it still achieves the
    # minimum — re-parenting only on strict improvement or parent loss
    # keeps the tree stable under score noise
    old = ctrl.parent
    old_cand = jnp.take_along_axis(
        cand, jnp.clip(old, 0)[:, None], axis=-1)[:, 0]
    keep_old = (old >= 0) & (old_cand <= best)
    slot = jnp.where(keep_old, jnp.clip(old, 0), best_slot)
    reachable = best <= jnp.float32(n)
    has_parent = reachable & ~is_root & alive & state.subscribed
    root_live = is_root & alive & state.subscribed
    hops = jnp.where(root_live, 0.0,
                     jnp.where(has_parent,
                               jnp.take_along_axis(
                                   cand, slot[:, None], axis=-1)[:, 0],
                               INF))
    parent = jnp.where(has_parent, slot, jnp.int32(-1))

    # -- tree edge set: my parent edge + accepted child edges ----------------
    parent_edge = ((jnp.arange(c, dtype=jnp.int32)[None, :]
                    == parent[:, None]) & has_parent[:, None])
    child_edge = reciprocal_pull_bool(parent_edge, conns, rev, batch_factor)
    if _gray:
        child_edge = child_edge & ok_edge  # refuse graylisted children
    tree = (parent_edge | child_edge) & valid

    # re-parent accounting: a parent change is a GRAFT to the new parent
    # and (when an old parent existed) a PRUNE of the old edge — counted
    # in the shared control ledgers so the telemetry channels compare
    # across protocols
    moved = parent != old
    i32 = jnp.int32
    reparents = ctrl.reparents + (moved & (old >= 0)).astype(i32)
    grafts = state.grafts + (moved & has_parent).astype(i32)
    prunes = state.prunes + (moved & (old >= 0)).astype(i32)

    # -- lazy IHAVE channel: advertise along up to lazy_degree non-tree
    # edges per round (lowest slots first — deterministic, PRNG-free).
    # This is the heartbeat-grain tree-repair advertisement; message-grain
    # repair rides disseminate's gossip over the same non-mesh edges.
    lazy_budget = params.d_lazy if ep.lazy_degree is None else ep.lazy_degree
    lazy = valid & ~tree
    sel = lazy & (jnp.cumsum(lazy, axis=-1) <= lazy_budget)
    ihave_tx = state.ihave_tx + sel.sum(axis=-1, dtype=i32)
    ihave_rx = state.ihave_rx + reciprocal_pull_bool(
        sel, conns, rev, batch_factor).sum(axis=-1, dtype=i32)

    # -- score decay (identical gated formula to heartbeat_step) -------------
    def do_decay(fmd, slow):
        return (_apply_decay(fmd, params.fmd_decay, params),
                _apply_decay(slow, params.slow_decay, params))

    fmd, slow = jax.lax.cond(
        ((state.fmd > 0) | (state.slow_penalty > 0)).any(),
        do_decay,
        lambda f, s: (f, s),
        state.fmd, state.slow_penalty,
    )

    new_state = state.replace(
        mesh_mask=tree,
        fmd=fmd,
        slow_penalty=slow,
        alive=alive,
        warm_offset_ms=warm,
        t_ms=t + params.heartbeat_ms,
        key=key,
        grafts=grafts,
        prunes=prunes,
        ihave_tx=ihave_tx,
        ihave_rx=ihave_rx,
    )
    new_ctrl = EpisubCtrl(hops=hops, parent=parent, reparents=reparents)
    return new_state, new_ctrl


@partial(jax.jit, static_argnames=("params", "ep", "steps", "batch_factor"))
def run_episub_heartbeats(
    state: SimState,
    ctrl: EpisubCtrl,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    params: SimParams,
    ep: EpisubParams,
    steps: int,
    batch_factor: int = 1,
):
    """lax.scan of episub_heartbeat_step x steps -> (state, ctrl). The
    runner contract mirrors run_heartbeats (static steps for segment cache
    hits) with the ctrl carry prepended per the ProtocolSpec convention.
    `ep` is static: it is validated once, where it is traced."""
    ep.validate(params.n)
    nbr_ok = None
    if params.churn_down_per_hb == 0.0 and params.churn_up_per_hb == 0.0:
        nbr_ok = neighbor_pull_bool(
            state.alive & state.subscribed, conns, rev, batch_factor)

    def body(carry, _):
        s, c = carry
        s, c = episub_heartbeat_step(
            s, c, conns, rev, out_mask, params, ep,
            batch_factor=batch_factor, nbr_ok=nbr_ok)
        return (s, c), None

    (state, ctrl), _ = jax.lax.scan(body, (state, ctrl), None, length=steps)
    return state, ctrl


def run_episub_attacked_heartbeats(
    state: SimState,
    ctrl: EpisubCtrl,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    attacker: jnp.ndarray,
    params: SimParams,
    ep: EpisubParams,
    adv: AdversaryParams,
    steps: int,
    batch_factor: int = 1,
    telemetry=None,
):
    """lax.scan of [episub_heartbeat_step -> adversary_round] x steps ->
    ((state, ctrl), obs). The SAME adversary_round as GossipSub's window
    — the arena's whole point: the attacker's graft flood lands in
    mesh_mask after the tree write, so attack edges carry eager traffic
    until the next relaxation recomputes the tree (and the graylist
    blocks a penalized attacker from ever becoming a parent). Obs adds
    the episub channels (tree_reach_frac, tree_depth_mean) to the shared
    attack_observables set."""
    ep.validate(params.n)
    if telemetry is not None and not telemetry.enabled:
        telemetry = None
    return _run_episub_attacked_heartbeats(
        state, ctrl, conns, rev, out_mask, attacker, params, ep, adv, steps,
        batch_factor, telemetry)


@partial(jax.jit, static_argnames=("params", "ep", "adv", "steps",
                                   "batch_factor", "telemetry"))
def _run_episub_attacked_heartbeats(
    state: SimState,
    ctrl: EpisubCtrl,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    attacker: jnp.ndarray,
    params: SimParams,
    ep: EpisubParams,
    adv: AdversaryParams,
    steps: int,
    batch_factor: int = 1,
    telemetry=None,
):
    nbr_ok = None
    if params.churn_down_per_hb == 0.0 and params.churn_up_per_hb == 0.0:
        nbr_ok = neighbor_pull_bool(
            state.alive & state.subscribed, conns, rev, batch_factor)

    xs = jnp.arange(steps) if adv.identity_rotation else None

    def body(carry, hb):
        s, c = carry
        s, c = episub_heartbeat_step(
            s, c, conns, rev, out_mask, params, ep,
            batch_factor=batch_factor, nbr_ok=nbr_ok)
        s, obs = adversary_round(s, conns, rev, attacker, params, adv,
                                 batch_factor=batch_factor, nbr_ok=nbr_ok,
                                 hb_idx=hb)
        obs.update(episub_observables(c, s.alive, s.subscribed))
        if telemetry is not None:
            from .telemetry import telemetry_observables

            obs.update(telemetry_observables(
                s, conns, rev, params, telemetry, batch_factor=batch_factor))
        return (s, c), obs

    return jax.lax.scan(body, (state, ctrl), xs, length=steps)


def run_episub_adaptive_heartbeats(
    state: SimState,
    ctrl: EpisubCtrl,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    attacker: jnp.ndarray,
    params: SimParams,
    ep: EpisubParams,
    adv: AdversaryParams,
    steps: int,
    actrl=None,
    batch_factor: int = 1,
    telemetry=None,
):
    """The adaptive attack window against the tree. Disabled
    (`not adv.adaptive.enabled`) this IS run_episub_attacked_heartbeats
    — the same call, the same jit cache entry, the house delegation
    invariant — and `actrl` must be None. Armed, the adaptive controller
    carry threads alongside the tree carry and the return widens to
    ((state, ctrl, actrl), obs)."""
    if not adv.adaptive.enabled:
        if actrl is not None:
            raise ValueError("actrl given but adv.adaptive is disabled — "
                             "the disabled path delegates to "
                             "run_episub_attacked_heartbeats and carries "
                             "none")
        return run_episub_attacked_heartbeats(
            state, ctrl, conns, rev, out_mask, attacker, params, ep, adv,
            steps, batch_factor, telemetry)
    ep.validate(params.n)
    if telemetry is not None and not telemetry.enabled:
        telemetry = None
    if actrl is None:
        actrl = init_adaptive_ctrl(params.n)
    return _run_episub_adaptive_heartbeats(
        state, ctrl, actrl, conns, rev, out_mask, attacker, params, ep, adv,
        steps, batch_factor, telemetry)


@partial(jax.jit, static_argnames=("params", "ep", "adv", "steps",
                                   "batch_factor", "telemetry"))
def _run_episub_adaptive_heartbeats(
    state: SimState,
    ctrl: EpisubCtrl,
    actrl,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    attacker: jnp.ndarray,
    params: SimParams,
    ep: EpisubParams,
    adv: AdversaryParams,
    steps: int,
    batch_factor: int = 1,
    telemetry=None,
):
    nbr_ok = None
    if params.churn_down_per_hb == 0.0 and params.churn_up_per_hb == 0.0:
        nbr_ok = neighbor_pull_bool(
            state.alive & state.subscribed, conns, rev, batch_factor)

    # the PX poisoner's sybil-id schedule is scan-invariant: hoist it
    n = conns.shape[0]
    att_sorted = jnp.sort(jnp.where(
        attacker, jnp.arange(n, dtype=jnp.int32), jnp.int32(n)))
    n_att = attacker.sum()

    def body(carry, hb):
        s, c, a = carry
        s, c = episub_heartbeat_step(
            s, c, conns, rev, out_mask, params, ep,
            batch_factor=batch_factor, nbr_ok=nbr_ok)
        (s, a), obs = adaptive_round(
            s, a, conns, rev, attacker, params, adv,
            batch_factor=batch_factor, nbr_ok=nbr_ok, hb_idx=hb,
            att_sorted=att_sorted, n_att=n_att)
        obs.update(episub_observables(c, s.alive, s.subscribed))
        if telemetry is not None:
            from .telemetry import telemetry_observables

            obs.update(telemetry_observables(
                s, conns, rev, params, telemetry, batch_factor=batch_factor))
        return (s, c, a), obs

    return jax.lax.scan(body, (state, ctrl, actrl), jnp.arange(steps),
                        length=steps)


def run_episub_faulted_heartbeats(
    state: SimState,
    ctrl: EpisubCtrl,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    attacker: jnp.ndarray,
    params: SimParams,
    ep: EpisubParams,
    adv: AdversaryParams,
    faults: FaultParams,
    crash: jnp.ndarray,
    side: jnp.ndarray,
    spike: jnp.ndarray,
    steps: int,
    batch_factor: int = 1,
    telemetry=None,
    actrl=None,
):
    """The fault-armed episub window (crash / partition / spike cohorts,
    ops/faults.py window semantics). Disabled this IS the adaptive (or
    attacked) episub runner — the same delegation chain as
    run_faulted_heartbeats. Armed, the fault schedule differs from the
    GossipSub window in ONE deliberate way: there is no freeze/thaw mesh
    bank, because the tree re-derives from the hop relaxation every
    round — a partition simply re-parents both sides (the cut side with
    no root drains to unreached), and healing re-merges the tree without
    banked state. A crashed peer goes dark by cohort edge-mask (its hop
    estimate drains to INF, its children re-parent) and returns cold
    (parent=-1 semantics emerge from the relaxation, no state surgery
    needed)."""
    ep.validate(params.n)
    if telemetry is not None and not telemetry.enabled:
        telemetry = None
    if not faults.enabled:
        if adv.adaptive.enabled:
            return run_episub_adaptive_heartbeats(
                state, ctrl, conns, rev, out_mask, attacker, params, ep,
                adv, steps, actrl=actrl, batch_factor=batch_factor,
                telemetry=telemetry)
        if actrl is not None:
            raise ValueError("actrl given but the adaptive policy is "
                             "disabled — the delegating path carries none")
        return run_episub_attacked_heartbeats(
            state, ctrl, conns, rev, out_mask, attacker, params, ep, adv,
            steps, batch_factor, telemetry)
    if adv.adaptive.enabled and actrl is None:
        actrl = init_adaptive_ctrl(params.n)
    if not adv.adaptive.enabled and actrl is not None:
        raise ValueError("actrl given but the adaptive policy is disabled")
    return _run_episub_faulted_heartbeats(
        state, ctrl, actrl, conns, rev, out_mask, attacker, crash, side,
        spike, params, ep, adv, faults, steps, batch_factor, telemetry)


@partial(jax.jit, static_argnames=("params", "ep", "adv", "faults", "steps",
                                   "batch_factor", "telemetry"))
def _run_episub_faulted_heartbeats(
    state: SimState,
    ctrl: EpisubCtrl,
    actrl,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    attacker: jnp.ndarray,
    crash: jnp.ndarray,
    side: jnp.ndarray,
    spike: jnp.ndarray,
    params: SimParams,
    ep: EpisubParams,
    adv: AdversaryParams,
    faults: FaultParams,
    steps: int,
    batch_factor: int = 1,
    telemetry=None,
):
    adaptive = adv.adaptive.enabled
    if adaptive:
        n_rows = conns.shape[0]
        att_sorted = jnp.sort(jnp.where(
            attacker, jnp.arange(n_rows, dtype=jnp.int32), jnp.int32(n_rows)))
        n_att = attacker.sum()
    nbr_ok = None
    if (params.churn_down_per_hb == 0.0
            and params.churn_up_per_hb == 0.0):
        # crash goes through edge_ok here (no alive surgery), so liveness
        # stays scan-invariant without churn and the pull hoists
        nbr_ok = neighbor_pull_bool(
            state.alive & state.subscribed, conns, rev, batch_factor)

    cross = partition_edge_mask(side, conns) if faults.partition else None
    if faults.crash:
        crash_nbr = neighbor_pull_bool(crash, conns, rev, batch_factor)
        crash_edges = ((crash[:, None] | crash_nbr) & (conns >= 0))

    def body(carry, hb):
        if adaptive:
            s, c, a = carry
        else:
            s, c = carry
        edge_ok = None
        if faults.crash:
            cs, ce = faults.crash_window
            dark = (hb >= cs) & (hb < ce)
            edge_ok = jnp.where(dark, ~crash_edges, True)
        if faults.partition:
            ps, pe = faults.partition_window
            cut = jnp.where((hb >= ps) & (hb < pe), ~cross, True)
            edge_ok = cut if edge_ok is None else (edge_ok & cut)
        s, c = episub_heartbeat_step(
            s, c, conns, rev, out_mask, params, ep,
            batch_factor=batch_factor, nbr_ok=nbr_ok, edge_ok=edge_ok)
        if adaptive:
            (s, a), obs = adaptive_round(
                s, a, conns, rev, attacker, params, adv,
                batch_factor=batch_factor, nbr_ok=nbr_ok, edge_ok=edge_ok,
                hb_idx=hb, att_sorted=att_sorted, n_att=n_att)
        else:
            s, obs = adversary_round(
                s, conns, rev, attacker, params, adv,
                batch_factor=batch_factor, nbr_ok=nbr_ok, edge_ok=edge_ok,
                hb_idx=hb)
        if faults.spike:
            ss, se = faults.spike_window
            live = (hb >= ss) & (hb < se)
            s = s.replace(uplink_free_ms=jnp.where(
                spike & live,
                jnp.maximum(s.uplink_free_ms, s.t_ms)
                + jnp.float32(faults.spike_ms),
                s.uplink_free_ms))
        obs.update(episub_observables(c, s.alive, s.subscribed))
        f32 = jnp.float32
        if faults.partition:
            obs["cross_mesh_edges"] = (s.mesh_mask & cross).sum().astype(f32)
        if faults.crash:
            obs["restarted_mean_degree"] = (
                (s.mesh_mask & crash[:, None]).sum()
                / f32(jnp.maximum(crash.sum(), 1)))
        if telemetry is not None:
            from .telemetry import telemetry_observables

            obs.update(telemetry_observables(
                s, conns, rev, params, telemetry, batch_factor=batch_factor))
        if adaptive:
            return (s, c, a), obs
        return (s, c), obs

    xs = jnp.arange(steps)
    if adaptive:
        (state, ctrl, actrl), obs = jax.lax.scan(
            body, (state, ctrl, actrl), xs, length=steps)
        return (state, ctrl, actrl), obs
    (state, ctrl), obs = jax.lax.scan(body, (state, ctrl), xs, length=steps)
    return (state, ctrl), obs
