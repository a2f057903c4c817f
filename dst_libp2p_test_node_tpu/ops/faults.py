"""Fault injection: node churn, partitions, and latency spikes as masks.

The reference harness measures GossipSub under adversity the network
inflicts, not just adversaries: Shadow injects latency/loss, nodes crash and
return, links die in bulk (SURVEY §5; the v1.1 evaluation arXiv:2007.02754
treats churn and partition-heal as first-class resilience scenarios). This
module compiles that fault model into the SAME scan the attack campaigns
already run — every fault is a scheduled mask over the existing fixed-shape
algebra, so "eclipse during a partition" is one config, not a new engine.

Three fault families, each a [start, end) window in heartbeat rounds
relative to the fault-armed scan:

  crash/restart   the cohort goes dark at crash_window[0] (alive=False: its
                  rows and its neighbors' views fall out of the validity
                  mask, exactly like BASELINE-config-4 churn) and returns at
                  crash_window[1] COLD — mesh membership, per-edge delivery
                  credit, penalty counters and backoffs are scrubbed on
                  every edge incident to a restarted peer, both directions
                  (a process restart forgets protocol state; its neighbors
                  re-handshake a fresh peer). The returned peer re-enters
                  through the normal graft path — and, when armed, the PR-4
                  repair path (PX/re-dial) — which is what
                  `post_churn_reconvergence_hb` measures.
  partition/heal  a node cut: `side` 2-colors the peers and every
                  cross-color edge is masked out of validity
                  (partition_edge_mask -> heartbeat_step/adversary_round
                  `edge_ok`) for the window. MESH MEMORY survives the
                  window: a partition is network-layer unreachability, not
                  a DISCONNECT — real GossipSub has no liveness-based mesh
                  eviction, so both endpoints still list the edge when the
                  link returns. The scan freezes the cross mesh edges at
                  partition start (heartbeat's mesh&valid would scrub them)
                  and thaws the still-valid ones at heal; the post-heal
                  rebalance (degrees exceed D_high: each side grafted
                  replacements during the cut) is the measured heal
                  transient (`heal_time_ms`, cross_mesh_edges curve).
  latency spike   the spiked cohort's uplink clock (SimState.uplink_free_ms
                  — the carry the dissemination fixpoint serializes
                  publishes through) is pushed `spike_ms` forward each
                  window round: the Shadow latency-injection analog, felt
                  as delivery delay by everything downstream.

Determinism contract (what is off is not in the program, applied at the
config level): `FaultParams()` is all-off, `run_faulted_heartbeats` then
literally delegates to run_attacked_heartbeats — same function object, same
jit cache entry, bit-identical outputs, zero PRNG consumed by any fault
(cohorts are drawn host-side in fault_masks; the armed scan adds no
jax.random call, so the key schedule equals the un-faulted run's).
tests/test_faults.py pins all three claims.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .adversary import (AdversaryParams, adaptive_round, adversary_round,
                        run_adaptive_heartbeats, run_attacked_heartbeats)
from .heartbeat import heartbeat_step
from .pull import neighbor_pull_bool
from .state import (SimParams, SimState, init_adaptive_ctrl, repair_inert,
                    require_repair)

INF = jnp.float32(3.4e38)


@dataclass(frozen=True)
class FaultParams:
    """Static (hashable -> jit static arg) fault schedule. All windows are
    [start, end) in heartbeat rounds of the fault-armed scan; a family is
    armed iff its fraction is > 0 AND its window is non-empty. Defaults are
    all OFF — the disabled path is a pure delegation to the un-faulted
    runner (RepairParams' contract, ops/repair.py)."""

    crash_frac: float = 0.0
    crash_window: tuple[int, int] = (0, 0)
    partition_frac: float = 0.0
    partition_window: tuple[int, int] = (0, 0)
    spike_frac: float = 0.0
    spike_window: tuple[int, int] = (0, 0)
    spike_ms: float = 0.0

    @property
    def crash(self) -> bool:
        return self.crash_frac > 0.0 and self.crash_window[1] > self.crash_window[0]

    @property
    def partition(self) -> bool:
        return (self.partition_frac > 0.0
                and self.partition_window[1] > self.partition_window[0])

    @property
    def spike(self) -> bool:
        return (self.spike_frac > 0.0 and self.spike_ms > 0.0
                and self.spike_window[1] > self.spike_window[0])

    @property
    def enabled(self) -> bool:
        return self.crash or self.partition or self.spike

    def validate(self) -> None:
        for name, frac in (("crash_frac", self.crash_frac),
                           ("partition_frac", self.partition_frac),
                           ("spike_frac", self.spike_frac)):
            if not (0.0 <= frac < 1.0):
                raise ValueError(f"{name} must be in [0, 1), got {frac}")
        for name, win in (("crash_window", self.crash_window),
                          ("partition_window", self.partition_window),
                          ("spike_window", self.spike_window)):
            a, b = win
            if a < 0 or b < a:
                raise ValueError(
                    f"{name} must be [start, end) with 0 <= start <= end, "
                    f"got {win}")
        if self.spike_ms < 0.0:
            raise ValueError("spike_ms must be >= 0")


def fault_masks(
    n: int,
    faults: FaultParams,
    seed: int,
    publisher: int | None = None,
) -> dict[str, np.ndarray]:
    """Host-side TRIAL SETUP (attacker_cohort's contract): the per-trial
    fault cohorts as (N,) bool numpy arrays, deterministic in (seed,
    faults). Keys: 'crash' (restarting cohort — never the publisher, whose
    delivery the trial measures), 'side' (partition 2-coloring: True =
    side A, |A| = round(partition_frac * n)), 'spike' (latency-spiked
    cohort). Disabled families return all-False/zeros so the device
    signature never changes shape. NO device PRNG is consumed — this is
    the only randomness the fault subsystem ever draws."""
    crash = np.zeros(n, dtype=bool)
    side = np.zeros(n, dtype=bool)
    spike = np.zeros(n, dtype=bool)
    if faults.crash:
        k = int(round(faults.crash_frac * n))
        rng = np.random.default_rng(
            np.random.SeedSequence([seed, 0xFA17, 0]))
        cand = np.arange(n)
        if publisher is not None:
            cand = cand[cand != publisher]
        k = min(k, len(cand))
        if k > 0:
            crash[rng.choice(cand, size=k, replace=False)] = True
    if faults.partition:
        k = int(round(faults.partition_frac * n))
        rng = np.random.default_rng(
            np.random.SeedSequence([seed, 0xFA17, 1]))
        if k > 0:
            side[rng.choice(n, size=min(k, n), replace=False)] = True
    if faults.spike:
        k = int(round(faults.spike_frac * n))
        rng = np.random.default_rng(
            np.random.SeedSequence([seed, 0xFA17, 2]))
        if k > 0:
            spike[rng.choice(n, size=min(k, n), replace=False)] = True
    return {"crash": crash, "side": side, "spike": spike}


def partition_edge_mask(side: jnp.ndarray, conns: jnp.ndarray) -> jnp.ndarray:
    """(N, C) bool: True on every connected edge that CROSSES the cut. The
    gather is row-owner -> neighbor color (side[conns[i, j]]), the same
    index economics as the involution pulls — side is (N,), so this is one
    embedding-style row gather, not a 2-index scatter."""
    return (conns >= 0) & (side[:, None] ^ side[jnp.clip(conns, 0)])


def run_faulted_heartbeats(
    state: SimState,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    attacker: jnp.ndarray,
    params: SimParams,
    adv: AdversaryParams,
    faults: FaultParams,
    crash: jnp.ndarray,
    side: jnp.ndarray,
    spike: jnp.ndarray,
    steps: int,
    batch_factor: int = 1,
    telemetry=None,
    ctrl=None,
):
    """The fault-armed attack window: run_attacked_heartbeats with the
    fault schedule compiled into the scan body. `crash`/`side`/`spike` are
    the (N,) fault_masks cohorts as device arrays.

    Disabled (`not faults.enabled`) this IS run_attacked_heartbeats — the
    same call, the same jit cache entry — so the default path cannot drift
    from the un-faulted engine by construction (with an armed
    adv.adaptive the delegation target is run_adaptive_heartbeats, whose
    own disabled path closes the chain back to the base runner). Armed
    adaptive composes inside the faulted scan: the controller carry
    (`ctrl`, defaulting to a fresh init_adaptive_ctrl) threads through
    alongside the partition's frozen-edge bank, adaptive_round replaces
    adversary_round, and the return widens to ((state, ctrl), obs) — a
    crashed attacker's controller keeps its own estimate (the honest-side
    counters its restart scrubbed are forgotten by the HONEST peers, so
    the estimate stays conservative). Armed, the scan adds the
    per-family fault observables to the obs dict (present only when the
    family is armed; downstream reads use .get):

      cross_mesh_edges        (partition) mesh edges crossing the cut — 0
                              during the window, the heal signal after
      restarted_mean_degree   (crash) mean mesh degree over the restarting
                              cohort — 0 while dark, the reconvergence
                              signal after restart

    `telemetry`: optional armed ops/telemetry.TelemetryParams — the flight
    recorder's tel_* channels join the obs dict, same contract as
    run_attacked_heartbeats (disabled normalizes to None; identical trace).
    """
    if telemetry is not None and not telemetry.enabled:
        telemetry = None
    if not faults.enabled:
        if adv.adaptive.enabled:
            return run_adaptive_heartbeats(
                state, conns, rev, out_mask, attacker, params, adv, steps,
                ctrl=ctrl, batch_factor=batch_factor, telemetry=telemetry)
        if ctrl is not None:
            raise ValueError("ctrl given but the adaptive policy is "
                             "disabled — the delegating path carries none")
        return run_attacked_heartbeats(
            state, conns, rev, out_mask, attacker, params, adv, steps,
            batch_factor, telemetry)
    if adv.adaptive.enabled and ctrl is None:
        ctrl = init_adaptive_ctrl(params.n)
    if not adv.adaptive.enabled and ctrl is not None:
        raise ValueError("ctrl given but the adaptive policy is disabled")
    return _run_faulted_heartbeats(
        state, conns, rev, out_mask, attacker, crash, side, spike,
        params, adv, faults, steps, batch_factor, telemetry, ctrl)


@partial(jax.jit,
         static_argnames=("params", "adv", "faults", "steps", "batch_factor",
                          "telemetry"))
def _run_faulted_heartbeats(
    state: SimState,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    attacker: jnp.ndarray,
    crash: jnp.ndarray,
    side: jnp.ndarray,
    spike: jnp.ndarray,
    params: SimParams,
    adv: AdversaryParams,
    faults: FaultParams,
    steps: int,
    batch_factor: int = 1,
    telemetry=None,
    ctrl=None,
):
    adaptive = adv.adaptive.enabled
    if adaptive:
        # the PX poisoner's sybil-id schedule is scan-invariant: hoist it
        n_rows = conns.shape[0]
        att_sorted = jnp.sort(jnp.where(
            attacker, jnp.arange(n_rows, dtype=jnp.int32), jnp.int32(n_rows)))
        n_att = attacker.sum()
    nbr_ok = None
    if (not faults.crash and params.churn_down_per_hb == 0.0
            and params.churn_up_per_hb == 0.0):
        # liveness is scan-invariant without crash/churn: hoist the pull
        # (partition/spike never touch alive/subscribed — they mask edges
        # and clocks, so the hoist stays sound)
        nbr_ok = neighbor_pull_bool(
            state.alive & state.subscribed, conns, rev, batch_factor)

    cross = partition_edge_mask(side, conns) if faults.partition else None
    crash_nbr = (neighbor_pull_bool(crash, conns, rev, batch_factor)
                 if faults.crash else None)

    def _go_dark(s):
        # the cohort's warm-start offsets were measured on the full liveness
        # set — invalidate the whole carry (heartbeat_step's churn contract)
        return s.replace(alive=s.alive & ~crash,
                         warm_offset_ms=jnp.full_like(s.warm_offset_ms, INF))

    def _restart(s):
        # cold return: every edge incident to a restarted peer forgets the
        # old session on BOTH sides — the peer must re-graft from nothing
        inc = (crash[:, None] | crash_nbr) & (conns >= 0)
        repl = dict(
            alive=s.alive | crash,
            mesh_mask=s.mesh_mask & ~inc,
            fmd=jnp.where(inc, 0.0, s.fmd),
            slow_penalty=jnp.where(inc, 0.0, s.slow_penalty),
            backoff_until=jnp.where(inc, 0.0, s.backoff_until),
            warm_offset_ms=jnp.full_like(s.warm_offset_ms, INF),
        )
        if not repair_inert(params):
            # the state holds the repair leaves only when a knob is armed;
            # a restarted peer's PX pool and starvation clock reset with it
            require_repair(s)
            repl["px_pool"] = jnp.where(crash[:, None], -1, s.px_pool)
            repl["starve_hb"] = jnp.where(crash, 0, s.starve_hb)
        return s.replace(**repl)

    def _freeze(s, frozen):
        # partition start: pull the cross mesh edges out of the live mesh
        # (heartbeat's mesh&valid would scrub them permanently) and bank
        # them — mesh memory survives a network-layer cut
        return (s.replace(mesh_mask=s.mesh_mask & ~cross),
                s.mesh_mask & cross)

    def _thaw(s, frozen):
        # heal: restore the banked edges whose endpoints both still stand
        ok = s.alive & s.subscribed
        keep = frozen & ok[:, None] & ok[jnp.clip(conns, 0)]
        return (s.replace(mesh_mask=s.mesh_mask | keep),
                jnp.zeros_like(frozen))

    def body(carry, hb):
        frozen = c = None
        if faults.partition and adaptive:
            s, c, frozen = carry
        elif faults.partition:
            s, frozen = carry
        elif adaptive:
            s, c = carry
        else:
            s = carry
        if faults.crash:
            cs, ce = faults.crash_window
            s = jax.lax.cond(hb == cs, _go_dark, lambda x: x, s)
            s = jax.lax.cond(hb == ce, _restart, lambda x: x, s)
        edge_ok = None
        if faults.partition:
            ps, pe = faults.partition_window
            s, frozen = jax.lax.cond(
                hb == ps, _freeze, lambda a, b: (a, b), s, frozen)
            s, frozen = jax.lax.cond(
                hb == pe, _thaw, lambda a, b: (a, b), s, frozen)
            edge_ok = jnp.where((hb >= ps) & (hb < pe), ~cross, True)
        s = heartbeat_step(s, conns, rev, out_mask, params,
                           batch_factor=batch_factor, nbr_ok=nbr_ok,
                           edge_ok=edge_ok)
        if adaptive:
            (s, c), obs = adaptive_round(
                s, c, conns, rev, attacker, params, adv,
                batch_factor=batch_factor, nbr_ok=nbr_ok, edge_ok=edge_ok,
                hb_idx=hb, att_sorted=att_sorted, n_att=n_att)
        else:
            s, obs = adversary_round(s, conns, rev, attacker, params, adv,
                                     batch_factor=batch_factor, nbr_ok=nbr_ok,
                                     edge_ok=edge_ok, hb_idx=hb)
        if faults.spike:
            # push the spiked cohort's uplink clock forward: the next
            # publish serializes behind the spike, exactly like an
            # iwant-spam answer queue (ops/adversary.py)
            ss, se = faults.spike_window
            live = (hb >= ss) & (hb < se)
            s = s.replace(uplink_free_ms=jnp.where(
                spike & live,
                jnp.maximum(s.uplink_free_ms, s.t_ms)
                + jnp.float32(faults.spike_ms),
                s.uplink_free_ms))
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
        if faults.partition and adaptive:
            return (s, c, frozen), obs
        if faults.partition:
            return (s, frozen), obs
        if adaptive:
            return (s, c), obs
        return s, obs

    xs = jnp.arange(steps)
    if faults.partition and adaptive:
        carry0 = (state, ctrl, jnp.zeros_like(state.mesh_mask))
        (state, ctrl, _), obs = jax.lax.scan(body, carry0, xs, length=steps)
    elif faults.partition:
        carry0 = (state, jnp.zeros_like(state.mesh_mask))
        (state, _), obs = jax.lax.scan(body, carry0, xs, length=steps)
    elif adaptive:
        (state, ctrl), obs = jax.lax.scan(body, (state, ctrl), xs,
                                          length=steps)
    else:
        state, obs = jax.lax.scan(body, state, xs, length=steps)
    return ((state, ctrl) if adaptive else state), obs
