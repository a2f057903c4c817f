"""Adversarial perturbations: the v1.1 attack scenarios as on-device masks.

"GossipSub: Attack-Resilient Message Propagation in the Filecoin and ETH2.0
Networks" (arXiv:2007.02754) evaluates the v1.1 score function against a
small canon of attacks. This module expresses that canon inside the engine's
existing fixed-shape algebra — every attacker behavior is a masked (N,)/(N, C)
op riding the same reciprocal-pull involution and the same dissemination
fixpoint as benign traffic, so a 100k-peer attack round costs the same order
as a benign heartbeat and NOTHING here loops over peers in Python:

  sybil_graft_flood   attacker rows force-graft every valid edge each
                      heartbeat (plus the censorship behavior below — sybils
                      contribute nothing). Honest peers answer with the v1.1
                      defense: a re-GRAFT of an edge that is backed off or
                      already meshed is a protocol violation that accrues the
                      behaviour-penalty counter.
  ihave_spam          attacker rows announce `spam_ihaves_per_hb` bogus ids
                      to every valid edge each heartbeat; honest peers IWANT
                      the unseen ids and the answers never come (broken
                      IWANT promises -> the same penalty counter).
  iwant_spam          the amplification dual: attacker rows REQUEST
                      `spam_iwants_per_hb` ids per valid edge each
                      heartbeat. Honest peers answer requests from
                      not-yet-graylisted edges, and each answer occupies
                      the shared uplink for `iwant_answer_ms` — the
                      answer-queue exhaustion lands in
                      SimState.uplink_free_ms, the SAME carry the
                      dissemination fixpoint serializes publishes through,
                      so spam directly delays the next publish. Unsolicited
                      IWANTs accrue the penalty counter once per spammed
                      edge per heartbeat, so scoring eventually stops the
                      bleeding (a graylisted requester is refused).
  censorship          in-mesh attackers silently refuse to forward: a
                      per-edge DELIVERY drop mask (censor_mask) folded into
                      disseminate's `survive` exactly like the graylist
                      gate — distinct from `survive_loss`, so lost_tx keeps
                      counting network losses only.
  eclipse_publisher   the attacker cohort is drawn from the publisher's
                      connected neighbors and the publisher's mesh row is
                      overwritten with attacker edges only (eclipse_setup);
                      with flood_publish off, the first publishes die inside
                      the cohort until scoring evicts it.
  cold_boot_join      the graft-flood scenario started from the un-warmed
                      t=0 state: the mesh must FORM while under attack.

Penalty plumbing. The engine's score model is the v1.1 subset the reference
actually configures (P2 firstMessageDeliveries + the slow-peer penalty
counter, ops/state.py score()). The slow-peer counter is libp2p's negative-
weighted "non-negative counter x weight < 0" shape — exactly the shape of
v1.1's P7 behaviour penalty — so attack violations accrue into
`state.slow_penalty` and the full defense chain downstream is the EXISTING
one: score() -> gossip/publish thresholds -> graylist delivery gating in
disseminate -> score-ranked prune + score>=0 graft eligibility in
heartbeat_step. Campaign configs must set slow_peer_penalty_weight < 0 or
the static `thresholds_can_bind` gate compiles every defense out
(ops/disseminate.py) — attack_gossipsub() in runtime/campaign.py does.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .heartbeat import heartbeat_step
from .pull import neighbor_pull_bool, reciprocal_pull_bool
from .state import (PX_POOL_WIDTH, AdaptiveCtrl, SimParams, SimState,
                    init_adaptive_ctrl)

SCENARIOS = (
    "sybil_graft_flood",
    "ihave_spam",
    "iwant_spam",
    "censorship",
    "eclipse_publisher",
    "cold_boot_join",
    # the two static-canon stragglers from arXiv:2007.02754 (ROADMAP):
    #   slow_peer_mimicry    the attacker meters its own misbehavior so its
    #                        score in every honest peer's view sits at
    #                        mimic_margin * (G/w) — just ABOVE the graylist
    #                        floor, below the gossip/publish thresholds: it
    #                        contributes nothing, censors everything, and
    #                        the threshold defenses never quite fire.
    #   identity_rotation    graft-flood whose sybils rotate identities
    #                        every rotation_period_hb heartbeats: the honest
    #                        side's per-edge counters (fmd, penalty,
    #                        backoff) reset — a "new peer" on the same
    #                        socket slots — so the accrual race restarts
    #                        before the graylist budget is spent.
    "slow_peer_mimicry",
    "identity_rotation",
)


# Scenarios the adaptive controller composes with: the graft-flood family,
# where the attacker's round behavior is mesh pressure the controller can
# modulate. The spam scenarios have no backoff/mesh feedback loop to adapt
# to, mimicry IS already a (perfect-information) adaptive policy, and
# rotation's scrub cadence would erase the controller's own estimate.
ADAPTIVE_SCENARIOS = ("sybil_graft_flood", "eclipse_publisher",
                      "cold_boot_join")


@dataclass(frozen=True)
class AdaptivePolicy:
    """Static (hashable -> jit static arg) per-round attacker controller
    policy — the adaptive arms race from arXiv:2007.02754 §5 compiled into
    the heartbeat scan. Disabled (the default) the wrappers LITERALLY
    delegate to the static runners: same jit cache entry, bit-identical,
    zero extra PRNG. Armed, a per-attacker controller state (AdaptiveCtrl,
    ops/state.py) rides the scan carry and the attacker reacts to its own
    observables each round:

      regraft      re-graft every edge the moment its backoff expires (and
                   the edge left the mesh) — legal grafts that rebuild
                   attacker mesh share without accruing the behaviour
                   penalty.
      px_poison    answer PX demand with sybil ids: plant attacker ids into
                   the px_pool rows of honest peers adjacent to the cohort
                   (px_poison_per_hb plants per victim per round, rotating
                   through the sorted cohort) — mesh repair's candidate
                   lattice (PX -> DHT -> random) then dials sybils first.
      slot_race    during recovery windows, the attacker cohort runs the
                   repair controller too (run_adaptive_recovery_heartbeats
                   passes actor=everyone) and ACCEPTS inbound dials — it
                   races honest dialers for every slot eviction frees.
      duty_cycle   score-aware throttling: each attacker tracks its own
                   conservative estimate of the worst honest-side penalty
                   counter any of its edges carries and stops flooding
                   whenever one more violation would push its score past
                   throttle_margin * graylist_threshold. The closed-form
                   heartbeats_to_graylist budget becomes inf — the
                   graylist never engages, which is the scenario's finding
                   (the mimicry precedent), not a config error.
    """

    enabled: bool = False
    regraft: bool = True
    px_poison: bool = True
    slot_race: bool = True
    duty_cycle: bool = True
    # duty-cycle setpoint: throttle when the predicted counter would exceed
    # throttle_margin * c_req (c_req = graylist_threshold / slow_weight).
    # Margins close to 1 flood harder but risk graylisting through estimate
    # error; the default leaves 20% headroom.
    throttle_margin: float = 0.8
    # sybil ids planted per victim px_pool row per heartbeat
    px_poison_per_hb: int = 2

    def validate(self, scenario: str | None = None) -> None:
        if not (0.0 < self.throttle_margin < 1.0):
            raise ValueError("throttle_margin must be in (0, 1) — at >= 1 "
                             "the controller graylists itself, defeating "
                             "the duty cycle")
        if not (1 <= self.px_poison_per_hb <= PX_POOL_WIDTH):
            raise ValueError(
                f"px_poison_per_hb must be in [1, {PX_POOL_WIDTH}] "
                f"(the px_pool width), got {self.px_poison_per_hb}")
        if self.enabled and not (self.regraft or self.px_poison
                                 or self.slot_race or self.duty_cycle):
            raise ValueError("adaptive policy is enabled but every behavior "
                             "is off — use enabled=False (the delegating "
                             "path) instead of an armed no-op")
        if self.enabled and scenario is not None \
                and scenario not in ADAPTIVE_SCENARIOS:
            raise ValueError(
                f"adaptive policy composes with {ADAPTIVE_SCENARIOS} only "
                f"(the graft-flood family), not scenario {scenario!r}: the "
                "spam scenarios have no backoff/mesh loop to adapt to, "
                "mimicry is already an adaptive policy, and rotation's "
                "identity scrubs erase the controller's own estimate")


@dataclass(frozen=True)
class AdversaryParams:
    """Static (hashable -> jit static arg) attack-scenario parameters."""

    scenario: str = "sybil_graft_flood"
    # behaviour-penalty counter increment per protocol violation per
    # heartbeat (re-GRAFT of a backed-off/meshed edge; unanswered IWANT)
    violation_penalty: float = 1.0
    # P3-analog: counter increment per publish on a mesh edge whose member
    # silently delivered nothing (censorship_penalty_update)
    censor_penalty: float = 1.0
    # bogus IHAVE ids announced per valid edge per heartbeat (ihave_spam)
    spam_ihaves_per_hb: int = 8
    # unsolicited IWANT ids requested per valid edge per heartbeat
    # (iwant_spam); each answered id occupies the victim's uplink for
    # iwant_answer_ms (the amplification factor)
    spam_iwants_per_hb: int = 16
    iwant_answer_ms: float = 2.0
    # slow_peer_mimicry: pin the attacker's per-edge penalty counter at
    # mimic_margin * c_req (c_req = graylist_threshold / slow_weight), i.e.
    # the score sits at mimic_margin * graylist_threshold — just above the
    # floor for any margin < 1
    mimic_margin: float = 0.9
    # identity_rotation: heartbeats between identity scrubs
    rotation_period_hb: int = 4
    # per-round adaptive controller policy (frozen, so the shared default
    # instance keeps the dataclass a pure static key: every disabled config
    # hashes/compares equal and lands on the same jit cache entry)
    adaptive: AdaptivePolicy = AdaptivePolicy()

    def validate(self) -> None:
        self.adaptive.validate(self.scenario)
        if self.scenario not in SCENARIOS:
            raise ValueError(
                f"unknown scenario {self.scenario!r}; expected one of {SCENARIOS}")
        if self.violation_penalty <= 0.0 or self.censor_penalty < 0.0:
            raise ValueError("violation_penalty must be > 0, censor_penalty >= 0")
        if self.spam_ihaves_per_hb < 1:
            raise ValueError("spam_ihaves_per_hb must be >= 1")
        if self.spam_iwants_per_hb < 1:
            raise ValueError("spam_iwants_per_hb must be >= 1")
        if self.iwant_answer_ms < 0.0:
            raise ValueError("iwant_answer_ms must be >= 0")
        if not (0.0 < self.mimic_margin < 1.0):
            raise ValueError("mimic_margin must be in (0, 1) — at >= 1 the "
                             "mimic graylists itself, defeating the scenario")
        if self.rotation_period_hb < 2:
            raise ValueError("rotation_period_hb must be >= 2 (a period of 1 "
                             "scrubs every round: no accrual ever survives)")

    # scenario -> active behaviors (all derived, keeping the dataclass a
    # pure static key: one flag per scenario would multiply trace keys)
    @property
    def graft_flood(self) -> bool:
        return self.scenario in ("sybil_graft_flood", "eclipse_publisher",
                                 "cold_boot_join", "identity_rotation")

    @property
    def ihave_spam(self) -> bool:
        return self.scenario == "ihave_spam"

    @property
    def iwant_spam(self) -> bool:
        return self.scenario == "iwant_spam"

    @property
    def eclipse(self) -> bool:
        return self.scenario == "eclipse_publisher"

    @property
    def cold_boot(self) -> bool:
        return self.scenario == "cold_boot_join"

    @property
    def slow_mimicry(self) -> bool:
        return self.scenario == "slow_peer_mimicry"

    @property
    def identity_rotation(self) -> bool:
        return self.scenario == "identity_rotation"


def attacker_cohort(
    n: int,
    fraction: float,
    seed: int,
    conns: np.ndarray | None = None,
    publisher: int | None = None,
    eclipse: bool = False,
) -> np.ndarray:
    """(N,) bool attacker membership — host-side TRIAL SETUP (one draw per
    trial, not per peer per round). Deterministic in (seed, fraction).

    `eclipse`: fill the cohort from the publisher's connected neighbors
    first (the attacker placed its sybils on the victim's connection slots),
    then at random; the publisher itself is never an attacker."""
    if not (0.0 <= fraction < 1.0):
        raise ValueError(f"attacker fraction must be in [0, 1), got {fraction}")
    k = int(round(fraction * n))
    mask = np.zeros(n, dtype=bool)
    if k == 0:
        return mask
    rng = np.random.default_rng(
        np.random.SeedSequence([seed, int(fraction * 1e6), 0xAD5E]))
    candidates = np.arange(n)
    if publisher is not None:
        candidates = candidates[candidates != publisher]
    if eclipse:
        if conns is None or publisher is None:
            raise ValueError("eclipse cohort needs conns and publisher")
        nbrs = np.asarray(conns)[publisher]
        nbrs = np.unique(nbrs[nbrs >= 0])
        nbrs = nbrs[nbrs != publisher]
        take = nbrs[:k] if len(nbrs) > k else nbrs
        mask[take] = True
        k -= len(take)
        candidates = candidates[~mask[candidates]]
    if k > 0:
        mask[rng.choice(candidates, size=k, replace=False)] = True
    return mask


def heartbeats_to_graylist(adv: AdversaryParams, params: SimParams) -> float:
    """The DOCUMENTED engagement budget: heartbeats from attack start until
    every violated honest->attacker edge scores below graylist_threshold.

    The penalty counter on a violated edge follows c_k = d*c_{k-1} + p
    (heartbeat decay, then the round's accrual), so after k accrual rounds
    c_k = p(1-d^k)/(1-d). The edge is graylisted when
    slow_weight*c_k <= graylist_threshold, i.e. c_k >= G/w (both negative).
    Violations start on round 2 for graft-flood (round 1's grafts are
    accepted into empty backoff/mesh; every re-graft after violates) and
    round 1 for ihave_spam / iwant_spam. Returns inf when the steady-state
    counter p/(1-d) can never reach the requirement — the campaign should
    treat that as a config error, not wait forever.

    INVARIANT UNDER EVICTION (params.evict). The budget does not move when
    the eviction branch is armed, because eviction swaps WHICH disjunct of
    the violation predicate fires without changing its truth value. Take
    graft-flood: pre-eviction, a flooded edge violates through
    `rx & mesh` (the re-GRAFT of a meshed edge). The eviction PRUNE removes
    the edge from the mesh but — through `_reciprocal_view`, both sides —
    writes `backoff_until = t + prune_backoff_ms`, so from the next round
    the SAME edge violates through `rx & (backoff_until > t)` instead
    (re-GRAFT of a backed-off edge). Since prune_backoff_ms (60 s default)
    spans hundreds of heartbeats and a fresh flood re-arms it, the accrual
    cadence — one violation_penalty per flooded edge per heartbeat — is
    identical, and the c_k = d*c_{k-1} + p recurrence (hence this closed
    form) holds with eviction on or off. tests/test_repair.py pins this by
    bit-comparing the graylisted_frac curves across both modes. The spam
    scenarios never consult mesh/backoff in their violation predicate, so
    they are trivially invariant.

    SLOW-PEER MIMICRY returns inf by construction: the attacker pins its own
    counter at mimic_margin * c_req every round, so the graylist can never
    engage — inf is the scenario's finding, not a config error (run_campaign
    exempts it from the inf-budget guard).

    IDENTITY ROTATION scrubs the honest side's per-edge counters every
    rotation_period_hb rounds. A scrub at round m*period leaves violations
    accruing only in rounds m*period+1 .. (m+1)*period-1, so the graylist
    engages iff the un-rotated budget fits strictly inside one rotation
    cycle; the boundary budget == period is conservatively reported inf
    (engagement there depends on cycle alignment).

    ADAPTIVE DUTY CYCLING (AdaptivePolicy.duty_cycle) returns inf by the
    mimicry precedent: the controller throttles its own flood whenever one
    more violation would push its predicted counter past throttle_margin *
    c_req, and its estimate over-approximates the honest-side counter, so
    the counter is clamped strictly below c_req forever — the budget is
    adaptive in exactly the sense the arms race predicts: infinite. inf is
    the finding, not a config error (run_campaign exempts it from the
    inf-budget guard, like mimicry and rotation)."""
    if adv.slow_mimicry:
        return math.inf
    if adv.adaptive.enabled and adv.adaptive.duty_cycle \
            and params.slow_weight < 0.0:
        return math.inf  # the controller never spends the budget
    if params.slow_weight >= 0.0:
        return math.inf  # thresholds_can_bind is False: defenses compiled out
    c_req = params.graylist_threshold / params.slow_weight
    p = adv.violation_penalty
    d = params.slow_decay
    lead_in = 1.0 if (adv.ihave_spam or adv.iwant_spam) else 2.0
    if c_req <= p:
        base = lead_in  # first accrual already crosses
    else:
        rhs = 1.0 - c_req * (1.0 - d) / p
        if rhs <= 0.0:
            return math.inf
        base = lead_in - 1.0 + math.ceil(math.log(rhs) / math.log(d))
    if adv.identity_rotation and base >= adv.rotation_period_hb:
        return math.inf
    return base


def censor_mask(attacker: jnp.ndarray, conns: jnp.ndarray) -> jnp.ndarray:
    """(N, C) per-edge delivery drop mask: every out-edge of an attacker row.
    Folded into disseminate's `survive` (delivery only — the graylist
    semantics), NOT into `survive_loss`: a withheld copy is not a network
    loss. The censor's own tx accounting keeps the queue slot, modeling a
    lying node that claims to forward."""
    return attacker[:, None] & (conns >= 0)


def eclipse_setup(
    state: SimState, conns: jnp.ndarray, attacker: jnp.ndarray, publisher: int
) -> SimState:
    """Overwrite the publisher's mesh row with its attacker edges only —
    the moment the eclipse closes (every slot the victim meshes through is
    a sybil). The attacker rows keep/gain the reciprocal edges through the
    graft-flood behavior; honest recovery happens through the normal
    heartbeat (graft fills the row back when scoring empties it)."""
    # only the publisher's row is touched: gather its neighbors directly
    # (nbr_is_attacker[i] = attacker[conns[pub, i]]) instead of a full pull
    row = jnp.where(conns[publisher] >= 0,
                    attacker[jnp.clip(conns[publisher], 0)], False)
    mesh = state.mesh_mask.at[publisher].set(row)
    return state.replace(mesh_mask=mesh)


@partial(jax.jit, static_argnames=("params", "adv", "batch_factor"))
def adversary_round(
    state: SimState,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    attacker: jnp.ndarray,
    params: SimParams,
    adv: AdversaryParams,
    batch_factor: int = 1,
    nbr_ok: jnp.ndarray | None = None,
    edge_ok: jnp.ndarray | None = None,
    hb_idx: jnp.ndarray | None = None,
):
    """One heartbeat of attacker behavior + honest defense accounting,
    applied AFTER heartbeat_step. Returns (new_state, obs) where obs holds
    the per-round scalar observables the campaign's engagement/recovery
    metrics are built from. All ops are fixed-shape masked array passes.

    `edge_ok`: the same per-edge availability mask heartbeat_step takes
    (ops/faults.py) — a partitioned edge carries no attack traffic either.
    `hb_idx`: the scan's 0-based round index; required (traced, from the
    scan xs) when adv.identity_rotation so the scrub cadence is part of the
    compiled program, ignored otherwise."""
    if adv.identity_rotation and hb_idx is None:
        raise ValueError("identity_rotation needs the scan round index "
                         "(hb_idx) to schedule the identity scrubs")
    t = state.t_ms
    if nbr_ok is None:
        nbr_ok = neighbor_pull_bool(
            state.alive & state.subscribed, conns, rev, batch_factor)
    valid = ((conns >= 0) & state.alive[:, None] & nbr_ok
             & state.subscribed[:, None])
    if edge_ok is not None:
        valid = valid & edge_ok
    att_row = attacker[:, None] & valid   # attacker out-edges
    honest = ~attacker & state.alive & state.subscribed

    mesh = state.mesh_mask
    slow_penalty = state.slow_penalty
    uplink_free_ms = state.uplink_free_ms
    backoff_until = state.backoff_until
    fmd = state.fmd
    grafts, grafts_rx = state.grafts, state.grafts_rx
    ihave_tx, ihave_rx = state.ihave_tx, state.ihave_rx
    iwant_tx, iwant_rx = state.iwant_tx, state.iwant_rx

    if adv.identity_rotation:
        # rotation round: every edge incident to an attacker carries "a new
        # peer on the same socket slot" — the honest side's per-edge memory
        # of the old identity (mesh membership, delivery credit, penalty
        # counter, backoff) resets, and so does the attacker's own row.
        # Under a lax.cond: off-cadence rounds pay a scalar probe only.
        def _scrub(m, sl, f, b):
            inc = (attacker[:, None] | neighbor_pull_bool(
                attacker, conns, rev, batch_factor)) & (conns >= 0)
            return (m & ~inc, jnp.where(inc, 0.0, sl),
                    jnp.where(inc, 0.0, f), jnp.where(inc, 0.0, b))

        rot = (hb_idx % adv.rotation_period_hb) == (adv.rotation_period_hb - 1)
        mesh, slow_penalty, fmd, backoff_until = jax.lax.cond(
            rot, _scrub, lambda m, sl, f, b: (m, sl, f, b),
            mesh, slow_penalty, fmd, backoff_until)

    if adv.graft_flood:
        # the attacker GRAFTs every valid edge, every heartbeat, ignoring
        # backoff. The receive side is one reciprocal pull; v1.1 handleGraft
        # accepts a first graft (no backoff, grafter not negatively scored)
        # and treats a re-GRAFT of a backed-off or already-meshed edge as
        # the graft-flood violation (go-libp2p-pubsub adds a behaviour
        # penalty for exactly this).
        flood = att_row
        rx = reciprocal_pull_bool(flood, conns, rev, batch_factor)
        violation = rx & ((backoff_until > t) | mesh)
        # rotation reads the POST-scrub counters (a fresh identity is
        # accepted); every other scenario reads state.* untouched, keeping
        # those traces bit-identical to the pre-rotation engine
        sc = (state.replace(fmd=fmd, slow_penalty=slow_penalty).score(params)
              if adv.identity_rotation else state.score(params))
        accept = rx & ~violation & (sc >= 0.0)
        mesh = (mesh | flood | accept) & valid
        slow_penalty = slow_penalty + jnp.where(
            violation, jnp.float32(adv.violation_penalty), 0.0)
        grafts = grafts + flood.sum(axis=-1, dtype=jnp.int32)
        grafts_rx = grafts_rx + rx.sum(axis=-1, dtype=jnp.int32)

    if adv.ihave_spam:
        # bogus IHAVEs on every valid attacker edge; honest receivers IWANT
        # each unseen id and the answer never comes — the broken-promise
        # violation accrues once per spammed edge per heartbeat (the v1.1
        # IWANT-timeout behaviour penalty, applied at the round grain)
        ann = att_row
        rx_ann = reciprocal_pull_bool(ann, conns, rev, batch_factor)
        k = jnp.int32(adv.spam_ihaves_per_hb)
        ihave_tx = ihave_tx + ann.sum(axis=-1, dtype=jnp.int32) * k
        ihave_rx = ihave_rx + rx_ann.sum(axis=-1, dtype=jnp.int32) * k
        # IWANT flows back along the same involution: honest tx, attacker rx
        iwant_tx = iwant_tx + rx_ann.sum(axis=-1, dtype=jnp.int32) * k
        iwant_rx = iwant_rx + ann.sum(axis=-1, dtype=jnp.int32) * k
        slow_penalty = slow_penalty + jnp.where(
            rx_ann, jnp.float32(adv.violation_penalty), 0.0)

    if adv.iwant_spam:
        # unsolicited IWANT requests on every valid attacker edge. The
        # honest side answers requests from edges it has not graylisted yet
        # (scored on the PRE-round counter: the refusal reacts one round
        # late, like a real score cache), and every answered id serializes
        # `iwant_answer_ms` onto the victim's shared uplink — the
        # amplification: requests are tiny, answers are messages. The
        # unsolicited request itself is the violation (penalty per spammed
        # edge per heartbeat), so scoring caps the damage.
        req = att_row
        rx_req = reciprocal_pull_bool(req, conns, rev, batch_factor)
        k = jnp.int32(adv.spam_iwants_per_hb)
        sc0 = state.score(params)
        serve = rx_req & (sc0 >= params.graylist_threshold)
        served = serve.sum(axis=-1, dtype=jnp.int32) * k   # answers sent
        iwant_tx = iwant_tx + req.sum(axis=-1, dtype=jnp.int32) * k
        iwant_rx = iwant_rx + rx_req.sum(axis=-1, dtype=jnp.int32) * k
        uplink_free_ms = jnp.where(
            served > 0,
            jnp.maximum(uplink_free_ms, t)
            + served.astype(jnp.float32) * jnp.float32(adv.iwant_answer_ms),
            uplink_free_ms)
        slow_penalty = slow_penalty + jnp.where(
            rx_req, jnp.float32(adv.violation_penalty), 0.0)

    if adv.slow_mimicry and params.slow_weight < 0.0:
        # the attacker meters its own misbehavior so the penalty counter on
        # every edge viewing an attacker sits at mimic_margin * c_req: the
        # attacker's score in the honest peer's view is mimic_margin *
        # graylist_threshold — below the gossip/publish thresholds (it is
        # never gossiped to and is skipped at publish) yet above the
        # graylist and eviction floors, so it is never refused, never
        # evicted. Re-pinned every heartbeat: decay and the post-publish
        # censorship penalty are both clamped back onto the pin.
        c_req = params.graylist_threshold / params.slow_weight
        att_view = neighbor_pull_bool(attacker, conns, rev, batch_factor)
        slow_penalty = jnp.where(
            valid & att_view,
            jnp.float32(adv.mimic_margin * c_req), slow_penalty)

    rotation_extra = {}
    if adv.identity_rotation:
        # the scrub is the only writer of these two leaves; keeping them
        # out of the replace on every other scenario keeps those traces
        # bit-identical to the pre-rotation engine
        rotation_extra = dict(fmd=fmd, backoff_until=backoff_until)
    new_state = state.replace(
        mesh_mask=mesh, slow_penalty=slow_penalty,
        uplink_free_ms=uplink_free_ms,
        grafts=grafts, grafts_rx=grafts_rx,
        ihave_tx=ihave_tx, ihave_rx=ihave_rx,
        iwant_tx=iwant_tx, iwant_rx=iwant_rx,
        **rotation_extra,
    )

    obs = attack_observables(new_state, conns, rev, attacker, params,
                             batch_factor=batch_factor, valid=valid)
    return new_state, obs


def attack_observables(
    state: SimState,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    attacker: jnp.ndarray,
    params: SimParams,
    batch_factor: int = 1,
    valid: jnp.ndarray | None = None,
):
    """The per-round scalar observables the campaign's engagement/recovery
    metrics are built from (the scan stacks them into (steps,) curves).
    Shared by adversary_round and the recovery runner (ops/repair.py) so
    attack-window and recovery-window curves concatenate seamlessly."""
    if valid is None:
        nbr_ok = neighbor_pull_bool(
            state.alive & state.subscribed, conns, rev, batch_factor)
        valid = ((conns >= 0) & state.alive[:, None] & nbr_ok
                 & state.subscribed[:, None])
    honest = ~attacker & state.alive & state.subscribed
    mesh = state.mesh_mask
    sc = state.score(params)
    att_nbr = neighbor_pull_bool(attacker, conns, rev, batch_factor)
    h_att_edge = valid & att_nbr & honest[:, None]   # honest view of attackers
    n_e = jnp.maximum(h_att_edge.sum(), 1)
    f32 = jnp.float32
    return {
        # fraction of honest->attacker edges the receiver graylists
        "graylisted_frac": (h_att_edge
                            & (sc < params.graylist_threshold)).sum() / f32(n_e),
        "attacker_score_mean": jnp.where(h_att_edge, sc, 0.0).sum() / f32(n_e),
        # attacker share of honest peers' mesh edges (mesh recovery metric)
        "attacker_mesh_share": (
            (mesh & att_nbr & honest[:, None]).sum()
            / f32(jnp.maximum((mesh & honest[:, None]).sum(), 1))),
        "honest_mean_degree": (
            (mesh & honest[:, None]).sum()
            / f32(jnp.maximum(honest.sum(), 1))),
    }


@partial(jax.jit, static_argnames=("params", "adv", "batch_factor"))
def adaptive_round(
    state: SimState,
    ctrl: AdaptiveCtrl,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    attacker: jnp.ndarray,
    params: SimParams,
    adv: AdversaryParams,
    batch_factor: int = 1,
    nbr_ok: jnp.ndarray | None = None,
    edge_ok: jnp.ndarray | None = None,
    hb_idx: jnp.ndarray | None = None,
    att_sorted: jnp.ndarray | None = None,
    n_att: jnp.ndarray | None = None,
):
    """One heartbeat of the ADAPTIVE attacker controller + honest defense
    accounting, applied AFTER heartbeat_step (and after repair_round in the
    recovery runner). The armed sibling of adversary_round: same masked
    fixed-shape algebra, zero PRNG, but the attacker's round behavior is a
    function of the controller carry `ctrl` instead of a constant mask.
    Returns ((new_state, new_ctrl), obs); obs carries attack_observables
    plus the adv_* controller channels (ops/telemetry.py).

    `hb_idx`: the scan's 0-based round index (rotates the sybil-id schedule
    of the PX poisoner); `att_sorted`/`n_att` are the scan-invariant sorted
    cohort ids / cohort size the runners hoist (recomputed here when absent
    so the round stays callable standalone).

    State-machine per attacker row, per round:

      1. PREDICT: next-round counter estimate = viol_est * slow_decay +
         violation_penalty (what one more flood round would cost).
      2. ACT or THROTTLE (duty_cycle): flood every valid edge iff the
         prediction stays under throttle_margin * c_req; otherwise send
         only LEGAL grafts this round (backoff expired, edge not meshed —
         the regraft behavior, which accrues nothing).
      3. OBSERVE: update viol_est from the attacker's OWN tx view — an
         edge it grafted while its own backoff/mesh bits were set violated
         on the honest side too (backoff writes are reciprocal everywhere
         in the engine; the attacker's mesh bit over-approximates the
         honest one since the flood sets it unilaterally, so the estimate
         is conservative and the margin covers residual asymmetry).
      4. POISON (px_poison, pool leaves live): plant px_poison_per_hb sybil
         ids into the px_pool row of every honest peer adjacent to the
         cohort, filling empty (-1) slots only — the same write discipline
         as heartbeat's PX capture, consumed by repair_round's candidate
         lattice. A state made for inert repair holds no pool and this
         block compiles out (pool is None)."""
    pol = adv.adaptive
    if not pol.enabled:
        raise ValueError("adaptive_round requires an armed AdaptivePolicy; "
                         "the disabled path is run_attacked_heartbeats")
    f32, i32 = jnp.float32, jnp.int32
    t = state.t_ms
    if nbr_ok is None:
        nbr_ok = neighbor_pull_bool(
            state.alive & state.subscribed, conns, rev, batch_factor)
    valid = ((conns >= 0) & state.alive[:, None] & nbr_ok
             & state.subscribed[:, None])
    if edge_ok is not None:
        valid = valid & edge_ok
    att_row = attacker[:, None] & valid
    n = conns.shape[0]
    me = jnp.arange(n, dtype=i32)

    # -- 1/2: score-aware duty cycle ------------------------------------
    if pol.duty_cycle and params.slow_weight < 0.0:
        c_req = f32(params.graylist_threshold / params.slow_weight)
        predicted = ctrl.viol_est * f32(params.slow_decay) \
            + f32(adv.violation_penalty)
        act = attacker & (predicted < f32(pol.throttle_margin) * c_req)
    else:
        act = attacker

    # -- graft set: full flood when acting, legal-only when throttled ----
    legal = att_row & (state.backoff_until <= t) & ~state.mesh_mask
    graft = att_row & act[:, None]
    if pol.regraft:
        graft = graft | legal
    rx = reciprocal_pull_bool(graft, conns, rev, batch_factor)
    violation = rx & ((state.backoff_until > t) | state.mesh_mask)
    sc = state.score(params)
    accept = rx & ~violation & (sc >= 0.0)
    mesh = (state.mesh_mask | graft | accept) & valid
    slow_penalty = state.slow_penalty + jnp.where(
        violation, f32(adv.violation_penalty), 0.0)
    grafts = state.grafts + graft.sum(axis=-1, dtype=i32)
    grafts_rx = state.grafts_rx + rx.sum(axis=-1, dtype=i32)

    # -- 3: controller estimate update (the attacker's own tx view) -----
    self_viol = (graft & ((state.backoff_until > t)
                          | state.mesh_mask)).any(axis=-1)
    viol_est = ctrl.viol_est * f32(params.slow_decay) + jnp.where(
        attacker & self_viol, f32(adv.violation_penalty), 0.0)
    regrafts = ctrl.regrafts
    if pol.regraft:
        regrafts = regrafts + jnp.where(
            attacker, legal.sum(axis=-1, dtype=i32), 0)
    throttled_hb = ctrl.throttled_hb + (attacker & ~act).astype(i32)

    # -- 4: PX poisoning (sybil answers to PX demand) --------------------
    px_injected = ctrl.px_injected
    pool = state.px_pool
    extra = {}
    if pol.px_poison and pool is not None:
        if att_sorted is None:
            att_sorted = jnp.sort(jnp.where(attacker, me, i32(n)))
        if n_att is None:
            n_att = attacker.sum()
        att_nbr = neighbor_pull_bool(attacker, conns, rev, batch_factor)
        victim = (~attacker & state.alive & state.subscribed
                  & (att_nbr & valid).any(axis=-1))
        hb = hb_idx if hb_idx is not None else 0
        base = me + hb * i32(pol.px_poison_per_hb)
        denom = jnp.maximum(n_att, 1)
        for k in range(pol.px_poison_per_hb):
            cand = att_sorted[(base + k) % denom]
            empty = pool < 0
            slot = jnp.argmax(empty, axis=-1)
            do = victim & (n_att > 0) & (cand < n) & empty.any(axis=-1)
            pool = pool.at[me, slot].set(
                jnp.where(do, cand, pool[me, slot]))
            px_injected = px_injected + do.astype(i32)
        extra["px_pool"] = pool

    new_state = state.replace(
        mesh_mask=mesh, slow_penalty=slow_penalty,
        grafts=grafts, grafts_rx=grafts_rx, **extra)
    new_ctrl = AdaptiveCtrl(viol_est=viol_est, regrafts=regrafts,
                            px_injected=px_injected,
                            throttled_hb=throttled_hb)

    from .telemetry import adaptive_observables

    obs = attack_observables(new_state, conns, rev, attacker, params,
                             batch_factor=batch_factor, valid=valid)
    obs.update(adaptive_observables(
        new_state, new_ctrl, attacker,
        acting=act, violations=violation.sum(dtype=i32)))
    return (new_state, new_ctrl), obs


def run_attacked_heartbeats(
    state: SimState,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    attacker: jnp.ndarray,
    params: SimParams,
    adv: AdversaryParams,
    steps: int,
    batch_factor: int = 1,
    telemetry=None,
):
    """lax.scan of [heartbeat_step -> adversary_round] x steps.

    Unlike run_heartbeats, decay is NOT deferred to scan end and the
    carried-degree protocol is off: adversary_round writes the penalty
    counter and the mesh mid-scan, so per-round decay interleaving and the
    per-step mesh&valid AND are both load-bearing. The alive/subscribed
    neighbor pull still hoists when churn is off (the attack mutates
    neither). Returns (state, obs) with obs leaves shaped (steps,). Device
    scopes of a step: `attack/heartbeat` (heartbeat_step, whose own scopes
    nest under it) and `attack/adversary` (adversary_round with the
    observables).

    No attack behavior touches the mesh-repair leaves, and an attack window
    with repair off (the common campaign case — repair arms only the
    RECOVERY window) scans a state that holds none (ops/state.py).

    `telemetry`: optional armed ops/telemetry.TelemetryParams — the flight
    recorder's per-round tel_* channels join the obs dict. None or a
    disabled params normalizes to None and takes the IDENTICAL python
    trace path (same jaxpr, same jit cache entry as the pre-recorder
    engine); armed telemetry consumes no PRNG and writes no state leaf,
    so the protocol trajectory is bit-identical either way."""
    if telemetry is not None and not telemetry.enabled:
        telemetry = None
    return _run_attacked_heartbeats(
        state, conns, rev, out_mask, attacker, params, adv, steps,
        batch_factor, telemetry)


@partial(jax.jit, static_argnames=("params", "adv", "steps", "batch_factor",
                                   "telemetry"))
def _run_attacked_heartbeats(
    state: SimState,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    attacker: jnp.ndarray,
    params: SimParams,
    adv: AdversaryParams,
    steps: int,
    batch_factor: int = 1,
    telemetry=None,
):
    nbr_ok = None
    if params.churn_down_per_hb == 0.0 and params.churn_up_per_hb == 0.0:
        nbr_ok = neighbor_pull_bool(
            state.alive & state.subscribed, conns, rev, batch_factor)

    # identity rotation needs the round index inside the compiled body (the
    # scrub cadence); every other scenario scans over nothing, as before
    xs = jnp.arange(steps) if adv.identity_rotation else None

    # device scopes (jax.named_scope: metadata only, no operation is added):
    # a step's two halves as `attack/heartbeat`, under which heartbeat_step's
    # own scopes nest, and `attack/adversary`
    def body(s, hb):
        with jax.named_scope("attack"), jax.named_scope("heartbeat"):
            s = heartbeat_step(s, conns, rev, out_mask, params,
                               batch_factor=batch_factor, nbr_ok=nbr_ok)
        with jax.named_scope("attack"), jax.named_scope("adversary"):
            s, obs = adversary_round(
                s, conns, rev, attacker, params, adv,
                batch_factor=batch_factor, nbr_ok=nbr_ok, hb_idx=hb)
        if telemetry is not None:
            from .telemetry import telemetry_observables

            obs.update(telemetry_observables(
                s, conns, rev, params, telemetry, batch_factor=batch_factor))
        return s, obs

    return jax.lax.scan(body, state, xs, length=steps)


def run_adaptive_heartbeats(
    state: SimState,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    attacker: jnp.ndarray,
    params: SimParams,
    adv: AdversaryParams,
    steps: int,
    ctrl: AdaptiveCtrl | None = None,
    batch_factor: int = 1,
    telemetry=None,
):
    """The adaptive attack window: lax.scan of [heartbeat_step ->
    adaptive_round] x steps with the per-attacker controller carry.

    Disabled (`not adv.adaptive.enabled`) this IS run_attacked_heartbeats —
    the same call, the same jit cache entry, bit-identical, zero extra PRNG
    (the faults/telemetry/DHT delegation pattern); `ctrl` must be None and
    the return is the base runner's (state, obs). Armed, `ctrl` defaults to
    a fresh init_adaptive_ctrl(params.n) and the return widens to
    ((state, ctrl), obs) — the run_dht_recovery_heartbeats carry
    convention. Armed obs adds the adv_* controller channels; over a state
    without repair leaves the PX poisoner compiles out (nothing could read
    the pool)."""
    if not adv.adaptive.enabled:
        if ctrl is not None:
            raise ValueError("ctrl given but adv.adaptive is disabled — the "
                             "disabled path delegates to "
                             "run_attacked_heartbeats and carries none")
        return run_attacked_heartbeats(
            state, conns, rev, out_mask, attacker, params, adv, steps,
            batch_factor, telemetry)
    if telemetry is not None and not telemetry.enabled:
        telemetry = None
    if ctrl is None:
        ctrl = init_adaptive_ctrl(params.n, like=attacker)
    return _run_adaptive_heartbeats(
        state, ctrl, conns, rev, out_mask, attacker, params, adv, steps,
        batch_factor, telemetry)


@partial(jax.jit, static_argnames=("params", "adv", "steps", "batch_factor",
                                   "telemetry"))
def _run_adaptive_heartbeats(
    state: SimState,
    ctrl: AdaptiveCtrl,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    attacker: jnp.ndarray,
    params: SimParams,
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
        s, c = carry
        s = heartbeat_step(s, conns, rev, out_mask, params,
                           batch_factor=batch_factor, nbr_ok=nbr_ok)
        (s, c), obs = adaptive_round(
            s, c, conns, rev, attacker, params, adv,
            batch_factor=batch_factor, nbr_ok=nbr_ok, hb_idx=hb,
            att_sorted=att_sorted, n_att=n_att)
        if telemetry is not None:
            from .telemetry import telemetry_observables

            obs.update(telemetry_observables(
                s, conns, rev, params, telemetry, batch_factor=batch_factor))
        return (s, c), obs

    return jax.lax.scan(body, (state, ctrl), jnp.arange(steps), length=steps)


def censorship_penalty_update(
    state: SimState,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    attacker: jnp.ndarray,
    received: jnp.ndarray,
    params: SimParams,
    adv: AdversaryParams,
) -> SimState:
    """Post-publish P3 analog (mesh message delivery failures): a receiver
    that obtained the message penalizes mesh members that silently delivered
    none of it. The engine's score subset has no per-edge delivery-window
    bookkeeping, so the deficit edge set is computed from the adversary
    ground truth (mesh edges toward censoring attackers) — the EFFECT of P3
    at the round grain, documented as such in docs/ARCHITECTURE.md."""
    if float(adv.censor_penalty) == 0.0:
        return state
    att_nbr = neighbor_pull_bool(attacker, conns, rev)
    deficit = (state.mesh_mask & att_nbr
               & (received & ~attacker)[:, None])
    return state.replace(slow_penalty=state.slow_penalty + jnp.where(
        deficit, jnp.float32(adv.censor_penalty), 0.0))
