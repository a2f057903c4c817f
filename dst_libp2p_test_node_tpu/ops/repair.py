"""Mesh repair: score eviction, PX-on-PRUNE, and re-dial recovery.

GossipSub v1.1's resilience story is not just that badly-scored peers stop
being *accepted* — the mesh actively heals (arXiv:2007.02754 §2; the ACL2s
formalization arXiv:2311.08859 treats the PRUNE/PX/backoff machine as the
correctness-critical core):

  eviction   mesh maintenance PRUNEs members whose score sank below a floor,
             with backoff on both sides (the opt-in `params.evict` lax.cond
             branch in ops/heartbeat.py).
  PX         a PRUNE carries peer-exchange candidates — the pruner's
             best-scored neighbors — which the prunee may graft or dial
             (the opt-in `params.px` capture branch in ops/heartbeat.py
             writes SimState.px_pool; `repair_round` here acts on it).
  re-dial    a peer starved below D_low for `redial_patience` heartbeats
             dials its way back in: PX pool first, then the ambient
             known-peer table (modeled as a uniform random peer — every
             reference node keeps a peer store / bootstrap list).

The dial controller makes the CONNECTION GRAPH dynamic — the one thing the
engine's involution substrate (ops/graph.py) treats as an epoch constant.
The contract that keeps this sound:

  * new edges only ever fill never-used padding slots (conns == -1); the
    reverse-slot involution is extended functionally in the same round
    (conns/rev/out_mask travel in the scan carry, never mutated in place);
  * at most ONE dial per peer per heartbeat, and an acceptor takes at most
    one inbound dial per round (lowest dialer id wins; a dialing peer does
    not accept) — collision-free fixed-shape scatters, no retry loops;
  * any committed dial invalidates the warm-start carry wholesale
    (SimState.warm_offset_ms := INF — the same invalidation contract as
    churn: the offsets were measured on the old reachability graph), and
    the host must re-derive every hoisted per-edge table before the next
    publish (Simulator.rebind_graph: valid_edge, lat_edge/loss_edge,
    answer tables all index the mutated conns/rev).

Adversary models. The STATIC runners (`run_recovery_heartbeats`,
`run_dht_recovery_heartbeats`) pass actor=~attacker: attackers do NOT run
the repair controller to worm back into the mesh after eviction, and on
the DHT leg their identities refuse inbound dials (refuse=attacker) — the
weakest opponent. `run_adaptive_recovery_heartbeats` is the arms-race
runner (ops/adversary.AdaptivePolicy): with slot_race armed the attacker
cohort runs the dial controller too AND accepts inbound dials (a sybil
that wants your slot completes the handshake), its controller re-grafts
at backoff expiry and re-poisons the PX pool after every repair pass, so
the candidate lattice honest repair draws from is contested every round.
Disabled, it literally delegates to the static runner (same jit cache
entry, bit-identical, zero extra PRNG).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from .adversary import (AdversaryParams, adaptive_round, attack_observables)
from .heartbeat import heartbeat_step
from .state import (AdaptiveCtrl, SimParams, SimState, init_adaptive_ctrl,
                    require_repair)

INF = jnp.float32(3.4e38)


@dataclass(frozen=True)
class RepairParams:
    """The repair knobs as a standalone (hashable) config surface.

    These mirror the SimParams fields one-to-one; `apply` folds them into a
    SimParams so the campaign/CLI can arm repair on an existing experiment
    without re-deriving the whole parameter set. Defaults are all OFF —
    RepairParams().apply(p) == p and the compiled paths stay bit-identical
    to the repair-free engine."""

    evict: bool = False
    eviction_threshold: float = -50.0
    px: bool = False
    px_count: int = 6
    redial: bool = False
    redial_patience: int = 3

    @property
    def enabled(self) -> bool:
        return self.evict or self.px or self.redial

    def validate(self) -> None:
        if self.eviction_threshold > 0:
            raise ValueError("eviction_threshold must be <= 0")
        if self.px_count < 1:
            raise ValueError("px_count must be >= 1")
        if self.redial_patience < 1:
            raise ValueError("redial_patience must be >= 1")

    def apply(self, params: SimParams) -> SimParams:
        out = dataclasses.replace(
            params,
            evict=self.evict,
            eviction_threshold=self.eviction_threshold,
            px=self.px,
            px_count=self.px_count,
            redial=self.redial,
            redial_patience=self.redial_patience,
        )
        out.validate()
        return out


def repair_round(
    state: SimState,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    params: SimParams,
    actor: jnp.ndarray | None = None,
    batch_factor: int = 1,
    dht_pool: jnp.ndarray | None = None,
    refuse: jnp.ndarray | None = None,
):
    """One round of the repair controller, applied AFTER heartbeat_step.

    Returns (state, conns, rev, out_mask) — the graph arrays are part of the
    result because committed dials extend the involution. `actor`: (N,) bool
    mask of peers that RUN the controller (default all); non-actors still
    accept inbound dials (acceptance is passive — a socket, not a policy).

    Per acting peer and round, at most one action:
      graft  the first plausible PX candidate that is already connected
             (subject to both sides' backoff, degree need, and score >= 0 —
             exactly handleGraft's acceptance), or
      dial   an unconnected candidate — PX pool first, else (re-dial
             trigger) a uniform random known peer — filling one free slot
             on each side and grafting the fresh edge (score 0, no backoff).

    `dht_pool`: optional (N, K) discovery shortlist (a FIND_NODE self-lookup,
    ops/dht_adversary.dht_repair_pool) that REPLACES the uniform-random
    fallback as the re-dial candidate source — the candidate-source lattice
    becomes PX pool -> DHT shortlist -> nothing. The examined DHT entry is
    consumed success-or-fail (like the PX pool) so a dead or refusing
    candidate cannot wedge the controller, and the updated pool is returned
    as a fifth result. `refuse`: optional (N,) bool of peers that never
    accept an inbound dial (sybil identities are not connectable
    endpoints); a starved peer whose every candidate refuses keeps its
    starve_hb counter growing instead of wedging. Both are python-level
    (None compiles the original program — bit-identical, same key
    schedule).

    The whole action machinery runs under one lax.cond: a healthy network
    (nobody starved, no PX pending) pays only the trigger probes."""
    require_repair(state)
    n, c = conns.shape
    me = jnp.arange(n, dtype=jnp.int32)
    iota_c = jnp.arange(c, dtype=jnp.int32)
    t = state.t_ms
    alive_sub = state.alive & state.subscribed
    act = alive_sub if actor is None else (actor & alive_sub)

    deg = state.mesh_mask.sum(axis=-1)

    # -- starvation counter (re-dial trigger) --------------------------------
    if params.redial:
        starve = jnp.where(act & (deg < params.d_low), state.starve_hb + 1, 0)
    else:
        starve = state.starve_hb

    key, k_dial = jax.random.split(state.key)

    # -- candidate selection (cheap, outside the cond: it IS the trigger) ----
    pool = state.px_pool
    pool_c = jnp.clip(pool, 0)
    cand_ok = (pool >= 0) & (pool != me[:, None]) & alive_sub[pool_c]
    has_cand = cand_ok.any(axis=-1)
    k0 = jnp.argmax(cand_ok, axis=-1)
    cand = jnp.take_along_axis(pool, k0[:, None], axis=1)[:, 0]

    # ambient known-peer table: one uniform draw over [0, n) \ {me}
    r = jax.random.randint(k_dial, (n,), 0, n - 1, dtype=jnp.int32)
    r = jnp.where(r >= me, r + 1, r)

    px_want = jnp.zeros((n,), dtype=bool)
    redial_want = jnp.zeros((n,), dtype=bool)
    if params.px:
        px_want = act & (deg < params.d) & has_cand
    if params.redial:
        redial_want = act & (starve >= params.redial_patience)
    use_px = px_want | (redial_want & has_cand)
    if dht_pool is None:
        use_rand = redial_want & ~has_cand & alive_sub[r]
        want = use_px | use_rand
        tgt = jnp.where(use_px, cand, jnp.where(use_rand, r, -1))
    else:
        # discovery-backed re-dial: the DHT shortlist replaces the uniform
        # random fallback entirely — a poisoned lookup measurably starves
        # the controller instead of being papered over by ambient luck
        d_ok = ((dht_pool >= 0) & (dht_pool != me[:, None])
                & alive_sub[jnp.clip(dht_pool, 0)])
        has_dcand = d_ok.any(axis=-1)
        dk0 = jnp.argmax(d_ok, axis=-1)
        dcand = jnp.take_along_axis(dht_pool, dk0[:, None], axis=1)[:, 0]
        use_dht = redial_want & ~has_cand & has_dcand
        want = use_px | use_dht
        tgt = jnp.where(use_px, cand, jnp.where(use_dht, dcand, -1))
    tgt_c = jnp.clip(tgt, 0)

    def _fire(_):
        hit = (conns == tgt_c[:, None]) & want[:, None]
        connected = hit.any(axis=-1)
        slot_a = jnp.argmax(hit, axis=-1)

        # ---- path A: candidate already connected -> plain GRAFT ----------
        sc = state.score(params)
        take = lambda a: jnp.take_along_axis(a, slot_a[:, None], axis=1)[:, 0]
        j_a = take(rev)
        my_ok = ((take(state.backoff_until) <= t)
                 & (take(sc) >= 0.0) & ~take(state.mesh_mask))
        graft_a = (want & connected & my_ok
                   & (state.backoff_until[tgt_c, j_a] <= t)
                   & (sc[tgt_c, j_a] >= 0.0))
        mesh = state.mesh_mask | (
            graft_a[:, None] & (iota_c[None, :] == slot_a[:, None]))
        mesh = mesh.at[tgt_c, j_a].max(graft_a)

        # ---- path B: unconnected -> dial into a free padding slot --------
        has_free = (conns < 0).any(axis=-1)
        free_slot = jnp.argmax(conns < 0, axis=-1).astype(jnp.int32)
        dial_try = want & ~connected & has_free
        # target-side screening: free slot, alive, not itself dialing (a
        # dialer never accepts in the same round — breaks the mutual-dial
        # double-edge race deterministically)
        attempt = dial_try & has_free[tgt_c] & alive_sub[tgt_c] & ~dial_try[tgt_c]
        if refuse is not None:
            # sybil identities never complete a handshake: the dial is
            # attempted (and the candidate consumed) but cannot commit
            attempt = attempt & ~refuse[tgt_c]
        # one inbound dial per acceptor per round: lowest dialer id wins
        winner = jnp.full((n,), n, dtype=jnp.int32).at[
            jnp.where(attempt, tgt_c, 0)].min(jnp.where(attempt, me, n))
        committed = attempt & (winner[tgt_c] == me)
        accepted = winner < n
        dialer = jnp.where(accepted, winner, 0)

        my_hot = committed[:, None] & (iota_c[None, :] == free_slot[:, None])
        acc_hot = accepted[:, None] & (iota_c[None, :] == free_slot[:, None])
        j_t = free_slot[tgt_c]       # my rev entry = the target's free slot
        i_d = free_slot[dialer]      # acceptor's rev entry = dialer's slot
        new_conns = jnp.where(my_hot, tgt_c[:, None], conns)
        new_conns = jnp.where(acc_hot, dialer[:, None], new_conns)
        new_rev = jnp.where(my_hot, j_t[:, None], rev)
        new_rev = jnp.where(acc_hot, i_d[:, None], new_rev)
        new_out = out_mask | my_hot  # the dialer side is the outbound one

        # fresh edge: scrub per-edge state (padding slots are zero already —
        # defense in depth) and graft both sides (score 0, no backoff: this
        # is exactly the PX-graft the prunee was promised)
        hot = my_hot | acc_hot
        mesh = mesh | hot
        backoff = jnp.where(hot, 0.0, state.backoff_until)
        fmd = jnp.where(hot, 0.0, state.fmd)
        slow = jnp.where(hot, 0.0, state.slow_penalty)

        # a committed dial changes the reachability graph the warm-start
        # offsets were measured on: invalidate the whole carry (the same
        # contract as churn, ops/heartbeat.py)
        warm = jnp.where(committed.any(),
                         jnp.full_like(state.warm_offset_ms, 3.4e38),
                         state.warm_offset_ms)

        i32 = jnp.int32
        grafts = state.grafts + (graft_a | committed).astype(i32)
        grafts_rx = state.grafts_rx.at[
            jnp.where(graft_a, tgt_c, 0)].add(graft_a.astype(i32))
        grafts_rx = grafts_rx + accepted.astype(i32)
        px_grafts = state.px_grafts + (
            graft_a | (committed & use_px)).astype(i32)
        redials = state.redials + committed.astype(i32)

        # consume the examined pool entry (success or fail) so a dead
        # candidate cannot wedge the controller
        pw = pool.shape[1]
        pool2 = jnp.where(
            use_px[:, None] & (jnp.arange(pw)[None, :] == k0[:, None]),
            -1, pool)
        out = (mesh, backoff, fmd, slow, warm, new_conns, new_rev, new_out,
               pool2, grafts, grafts_rx, px_grafts, redials)
        if dht_pool is not None:
            # same consume-on-examine rule for the DHT shortlist
            dw = dht_pool.shape[1]
            dpool2 = jnp.where(
                use_dht[:, None] & (jnp.arange(dw)[None, :] == dk0[:, None]),
                -1, dht_pool)
            out = out + (dpool2,)
        return out

    def _skip(_):
        out = (state.mesh_mask, state.backoff_until, state.fmd,
               state.slow_penalty, state.warm_offset_ms, conns, rev,
               out_mask, pool, state.grafts, state.grafts_rx,
               state.px_grafts, state.redials)
        if dht_pool is not None:
            out = out + (dht_pool,)
        return out

    fired = jax.lax.cond(want.any(), _fire, _skip, jnp.int32(0))
    (mesh, backoff, fmd, slow, warm, conns2, rev2, out2, pool2,
     grafts, grafts_rx, px_grafts, redials) = fired[:13]

    new_state = state.replace(
        mesh_mask=mesh, backoff_until=backoff, fmd=fmd, slow_penalty=slow,
        warm_offset_ms=warm, px_pool=pool2, starve_hb=starve, key=key,
        grafts=grafts, grafts_rx=grafts_rx,
        px_grafts=px_grafts, redials=redials,
    )
    if dht_pool is not None:
        return new_state, conns2, rev2, out2, fired[13]
    return new_state, conns2, rev2, out2


@partial(jax.jit,
         static_argnames=("params", "steps", "publisher", "batch_factor",
                          "telemetry"))
def run_recovery_heartbeats(
    state: SimState,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    attacker: jnp.ndarray,
    params: SimParams,
    steps: int,
    publisher: int = 0,
    batch_factor: int = 1,
    telemetry=None,
):
    """The post-attack recovery window: lax.scan of
    [heartbeat_step (evict/px branches armed) -> repair_round] x steps with
    the CONNECTION GRAPH in the carry — committed dials thread forward into
    every subsequent round's pulls, exactly like state.

    Unlike run_heartbeats/run_attacked_heartbeats, NOTHING hoists out of the
    scan: conns itself is loop-carried, so the per-step neighbor pull is
    load-bearing. Returns ((state, conns, rev, out_mask), obs) with obs
    leaves shaped (steps,) — the attack observables (shared with
    adversary_round, so campaign curves concatenate) plus per-round repair
    activity and the publisher's honest mesh degree (the eclipse-recovery
    signal).

    `telemetry`: optional armed ops/telemetry.TelemetryParams — the flight
    recorder's tel_* channels join the obs dict (disabled normalizes to
    None before the jit via the campaign caller; a disabled params passed
    here directly is treated as None so the trace stays identical)."""
    if telemetry is not None and not telemetry.enabled:
        telemetry = None
    require_repair(state)

    def body(carry, _):
        s, cn, rv, om = carry
        ev0 = s.evictions.sum()
        px0 = s.px_grafts.sum()
        rd0 = s.redials.sum()
        s = heartbeat_step(s, cn, rv, om, params, batch_factor=batch_factor)
        s, cn, rv, om = repair_round(
            s, cn, rv, om, params, actor=~attacker,
            batch_factor=batch_factor)
        obs = attack_observables(s, cn, rv, attacker, params,
                                 batch_factor=batch_factor)
        f32 = jnp.float32
        nbr = cn[publisher]
        att_n = (nbr >= 0) & attacker[jnp.clip(nbr, 0)]
        obs["pub_honest_degree"] = (
            s.mesh_mask[publisher] & (nbr >= 0) & ~att_n).sum().astype(f32)
        obs["evictions"] = (s.evictions.sum() - ev0).astype(f32)
        obs["px_grafts"] = (s.px_grafts.sum() - px0).astype(f32)
        obs["redials"] = (s.redials.sum() - rd0).astype(f32)
        if telemetry is not None:
            from .telemetry import telemetry_observables

            obs.update(telemetry_observables(
                s, cn, rv, params, telemetry, batch_factor=batch_factor))
        return (s, cn, rv, om), obs

    return jax.lax.scan(
        body, (state, conns, rev, out_mask), None, length=steps)


@partial(jax.jit,
         static_argnames=("params", "steps", "publisher", "batch_factor",
                          "telemetry"))
def _run_dht_recovery_heartbeats(state, conns, rev, out_mask, attacker,
                                 dht_pool, params, steps, publisher,
                                 batch_factor, telemetry):
    require_repair(state)

    def body(carry, _):
        s, cn, rv, om, pool = carry
        ev0 = s.evictions.sum()
        px0 = s.px_grafts.sum()
        rd0 = s.redials.sum()
        s = heartbeat_step(s, cn, rv, om, params, batch_factor=batch_factor)
        s, cn, rv, om, pool = repair_round(
            s, cn, rv, om, params, actor=~attacker,
            batch_factor=batch_factor, dht_pool=pool, refuse=attacker)
        obs = attack_observables(s, cn, rv, attacker, params,
                                 batch_factor=batch_factor)
        f32 = jnp.float32
        nbr = cn[publisher]
        att_n = (nbr >= 0) & attacker[jnp.clip(nbr, 0)]
        obs["pub_honest_degree"] = (
            s.mesh_mask[publisher] & (nbr >= 0) & ~att_n).sum().astype(f32)
        obs["evictions"] = (s.evictions.sum() - ev0).astype(f32)
        obs["px_grafts"] = (s.px_grafts.sum() - px0).astype(f32)
        obs["redials"] = (s.redials.sum() - rd0).astype(f32)
        obs["dht_pool_left"] = (pool >= 0).sum().astype(f32)
        # the starvation-degradation signal: a peer whose every candidate
        # refuses keeps counting up — the curve must climb, never wedge
        obs["starve_max"] = s.starve_hb.max().astype(f32)
        if telemetry is not None:
            from .telemetry import telemetry_observables

            obs.update(telemetry_observables(
                s, cn, rv, params, telemetry, batch_factor=batch_factor))
        return (s, cn, rv, om, pool), obs

    return jax.lax.scan(
        body, (state, conns, rev, out_mask, dht_pool), None, length=steps)


def run_dht_recovery_heartbeats(
    state: SimState,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    attacker: jnp.ndarray,
    params: SimParams,
    steps: int,
    dht_pool: jnp.ndarray | None = None,
    publisher: int = 0,
    batch_factor: int = 1,
    telemetry=None,
):
    """run_recovery_heartbeats with the discovery-backed candidate source:
    the (N, K) DHT shortlist rides the scan carry and feeds repair_round's
    re-dial path (refuse=attacker — sybil identities never accept), so a
    poisoned lookup measurably delays recovery and an exhausted pool
    degrades to monotone starvation instead of wedging. Returns
    ((state, conns, rev, out_mask, dht_pool), obs) with the extra
    `dht_pool_left` per-round channel.

    `dht_pool=None` LITERALLY delegates to run_recovery_heartbeats — same
    function object, same jit cache entry, bit-identical output shape and
    values, zero extra PRNG (tests/test_dht_adversary.py pins this)."""
    if dht_pool is None:
        return run_recovery_heartbeats(
            state, conns, rev, out_mask, attacker, params, steps,
            publisher=publisher, batch_factor=batch_factor,
            telemetry=telemetry)
    if telemetry is not None and not telemetry.enabled:
        telemetry = None
    return _run_dht_recovery_heartbeats(
        state, conns, rev, out_mask, attacker, dht_pool, params, steps,
        publisher, batch_factor, telemetry)


@partial(jax.jit,
         static_argnames=("params", "adv", "steps", "publisher",
                          "batch_factor", "telemetry"))
def _run_adaptive_recovery_heartbeats(state, ctrl, conns, rev, out_mask,
                                      attacker, dht_pool, params, adv,
                                      steps, publisher, batch_factor,
                                      telemetry):
    require_repair(state)
    pol = adv.adaptive
    # slot_race: the cohort runs the dial controller too, and its sybil
    # identities COMPLETE inbound handshakes (it wants the slot) — the
    # static model's refuse=attacker flips off
    actor = None if pol.slot_race else ~attacker
    refuse = None if pol.slot_race else (
        attacker if dht_pool is not None else None)
    # the PX poisoner's sybil-id schedule is scan-invariant even though the
    # graph is not: hoist it (nbr_ok must NOT hoist — conns is carried)
    n = conns.shape[0]
    att_sorted = jnp.sort(jnp.where(
        attacker, jnp.arange(n, dtype=jnp.int32), jnp.int32(n)))
    n_att = attacker.sum()

    def body(carry, hb):
        if dht_pool is not None:
            s, c, cn, rv, om, pool = carry
        else:
            s, c, cn, rv, om = carry
            pool = None
        ev0 = s.evictions.sum()
        px0 = s.px_grafts.sum()
        rd0 = s.redials.sum()
        s = heartbeat_step(s, cn, rv, om, params, batch_factor=batch_factor)
        fired = repair_round(
            s, cn, rv, om, params, actor=actor, batch_factor=batch_factor,
            dht_pool=pool, refuse=refuse)
        if dht_pool is not None:
            s, cn, rv, om, pool = fired
        else:
            s, cn, rv, om = fired
        # the controller reacts AFTER the repair pass: re-grafts the slots
        # eviction just freed, re-poisons the pool repair just consumed
        (s, c), obs = adaptive_round(
            s, c, cn, rv, attacker, params, adv,
            batch_factor=batch_factor, hb_idx=hb,
            att_sorted=att_sorted, n_att=n_att)
        f32 = jnp.float32
        nbr = cn[publisher]
        att_n = (nbr >= 0) & attacker[jnp.clip(nbr, 0)]
        obs["pub_honest_degree"] = (
            s.mesh_mask[publisher] & (nbr >= 0) & ~att_n).sum().astype(f32)
        obs["evictions"] = (s.evictions.sum() - ev0).astype(f32)
        obs["px_grafts"] = (s.px_grafts.sum() - px0).astype(f32)
        obs["redials"] = (s.redials.sum() - rd0).astype(f32)
        if dht_pool is not None:
            obs["dht_pool_left"] = (pool >= 0).sum().astype(f32)
            obs["starve_max"] = s.starve_hb.max().astype(f32)
        if telemetry is not None:
            from .telemetry import telemetry_observables

            obs.update(telemetry_observables(
                s, cn, rv, params, telemetry, batch_factor=batch_factor))
        carry = ((s, c, cn, rv, om, pool) if dht_pool is not None
                 else (s, c, cn, rv, om))
        return carry, obs

    carry0 = ((state, ctrl, conns, rev, out_mask, dht_pool)
              if dht_pool is not None
              else (state, ctrl, conns, rev, out_mask))
    return jax.lax.scan(body, carry0, jnp.arange(steps), length=steps)


def run_adaptive_recovery_heartbeats(
    state: SimState,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    attacker: jnp.ndarray,
    params: SimParams,
    steps: int,
    adv: AdversaryParams | None = None,
    ctrl: AdaptiveCtrl | None = None,
    dht_pool: jnp.ndarray | None = None,
    publisher: int = 0,
    batch_factor: int = 1,
    telemetry=None,
):
    """The ARMS-RACE recovery window: the repair controller heals the mesh
    while the adaptive adversary controller (ops/adversary.adaptive_round)
    contests every round of it — racing honest dialers for freed slots
    (actor=everyone, refuse=None: sybils dial AND accept), re-grafting
    edges the moment their backoff expires, re-poisoning the PX candidate
    pool right after repair consumes from it, and duty-cycling its own
    violation rate so the graylist never disarms it.

    Disabled (`adv` None or adv.adaptive.enabled False) this IS
    run_dht_recovery_heartbeats — the same call, the same jit cache entry,
    bit-identical, zero extra PRNG — which itself delegates to
    run_recovery_heartbeats when `dht_pool` is None; `ctrl` must be None
    then. Armed, the controller carry threads through the scan and the
    return widens to ((state, ctrl, conns, rev, out_mask[, dht_pool]),
    obs) with the adv_* channels joining the recovery obs."""
    if adv is None or not adv.adaptive.enabled:
        if ctrl is not None:
            raise ValueError("ctrl given but the adaptive policy is "
                             "disabled — the delegating path carries none")
        return run_dht_recovery_heartbeats(
            state, conns, rev, out_mask, attacker, params, steps,
            dht_pool=dht_pool, publisher=publisher,
            batch_factor=batch_factor, telemetry=telemetry)
    if telemetry is not None and not telemetry.enabled:
        telemetry = None
    if ctrl is None:
        ctrl = init_adaptive_ctrl(params.n)
    return _run_adaptive_recovery_heartbeats(
        state, ctrl, conns, rev, out_mask, attacker, dht_pool, params, adv,
        steps, publisher, batch_factor, telemetry)
