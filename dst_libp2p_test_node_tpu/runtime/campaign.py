"""Monte-Carlo adversarial campaigns: the attack workload family.

`run_campaign` sweeps attacker fraction x seed over ONE built network and
reports resilience metrics per trial. The protocol under test is the v1.1
score defense the reference ships but no benign workload ever engages
("GossipSub: Attack-Resilient Message Propagation in the Filecoin and
ETH2.0 Networks", arXiv:2007.02754); the attacker behaviors live in
ops/adversary.py as pure on-device masks.

Trial anatomy (one trial = one (fraction, seed) cell):

  setup     attacker cohort drawn host-side (ops/adversary.attacker_cohort),
            trial PRNG/state re-seeded from the trial seed. The CONNECTION
            GRAPH is shared across every trial (built once from the
            experiment seed): the Monte-Carlo axis is protocol randomness +
            cohort placement, which is what lets the attack window batch.
  warmup    benign mesh stabilization — except cold_boot_join, where the
            mesh must FORM during the attack window instead.
  window    `attack_heartbeats` rounds of [heartbeat_step -> adversary_round]
            (ops/adversary.run_attacked_heartbeats). When several seeds run
            the same fraction un-sharded, their windows execute as ONE
            jax.vmap'd scan over the stacked trial states — the trial batch
            rides the device, not a Python loop.
  publish   the experiment's injection schedule. Attackers never usefully
            forward in ANY scenario (censor_mask folded into disseminate's
            delivery mask); received-but-undelivered mesh edges accrue the
            P3-analog penalty (censorship_penalty_update) after each
            publish, so censors get scored out across the schedule.
  recovery  optional (recovery_heartbeats > 0): after the attack window —
            and after the trial checkpoint, which hashes the EPOCH graph —
            the mesh-repair subsystem runs `recovery_heartbeats` rounds of
            [heartbeat_step (evict/px armed via cfg.repair) -> repair_round]
            (ops/repair.run_recovery_heartbeats). The dial controller can
            MUTATE the connection graph, so the simulator rebinds every
            hoisted per-edge table afterwards (Simulator.rebind_graph) and
            the publish schedule measures delivery over the HEALED graph;
            the epoch graph is restored before the next trial. Under the
            STATIC adversary models attackers do not run the controller
            (see ops/repair.py); arming AdversaryParams.adaptive threads
            the per-attacker controller carry (ops/state.AdaptiveCtrl)
            from the attack window into the recovery legs, where the
            cohort contests every repair round
            (ops/repair.run_adaptive_recovery_heartbeats). The attack
            window itself stays on the standard params, so attack-window
            traces are bit-identical whether or not a recovery window
            follows.

Zero-attacker contract: a fraction-0.0 trial takes EXACTLY the benign
Simulator path — no adversary call, no censor mask (None keeps the publish
trace's pytree structure), no attack window — so its latencies, byte
accounting and scores are bit-identical to `Simulator` on the same seed
(tests/test_adversary.py pins this).

Resilience metrics per trial:
  honest_coverage      mean delivery fraction over honest peers
  latency_inflation    honest p50 delay / same-seed benign-baseline p50
  hb_to_graylist       first window round where >= GRAYLIST_ENGAGED_FRAC of
                       honest->attacker edges score below graylist_threshold
                       (compare against the closed-form budget
                       ops/adversary.heartbeats_to_graylist)
  mesh_recovery_hb     first round after peak where the attacker share of
                       honest mesh edges falls back under
                       `mesh_recovery_share` (attack + recovery windows
                       concatenated — the shared attack_observables make
                       the curves continuous)
  recovery_time_ms     first recovery-window round where the attacker mesh
                       share is back under the floor AND the publisher has
                       at least one honest mesh edge, in sim ms; -1 = not
                       recovered (only meaningful with recovery_heartbeats)

Warm-start/checkpoint reuse: the experiment's `warm_start` flag threads
through unchanged (the publish schedule warm-starts its fixpoints), and
`checkpoint_dir` snapshots each trial post-window via runtime/checkpoint.py
plus an `.obs.npz` sidecar with the window's observable curves — a crashed
sweep resumes per-trial (`_try_resume`, keyed on the epoch-graph hash)
instead of restarting the campaign, including across trial-group
boundaries of a sharded run.

Spans and counters (runtime/profiling.py; noted inside a `turn`, which
`cli.cmd_attack` opens): `run/topology` and `run/simulator_init` (the ONE
network every trial shares), `run/campaign`, and under it a span a trial and
phase with attributes `fraction`, `seed` and `vmapped`: `campaign/baseline`
(a benign run of `_ensure_baseline`, whose phases nest in it),
`trial/setup` (cohort draw, `_reset_trial`), `trial/warmup`, `trial/window`
(ONE span a stack of trials, attribute `trials`), `trial/publish` (the
schedule; `Simulator.publish`'s own spans nest in it), `trial/metrics`. A
campaign ends with one zero-length annotation `sim:attack/counters`
(`CampaignResult.counters`), which also says how many device->host reads the
campaign made (`device_reads`: every read of the normal path is counted
where it is made, the campaign's own and `Simulator.publish`'s through
`profiling.device_read`, the eight leaves `record_from_result` takes of a
publish's result one by one; the repair, DHT and checkpoint paths read
without counting).

Two-level device parallelism: `run_campaign(trial_mesh=...)` takes a 2-D
(trials x peers) grid from parallel/sharding.make_trial_mesh and runs the
STACKED TRIAL BATCH as one nested-sharded program — the trial axis splits
over the grid's trial groups AND each trial's peer rows split over the
group's peer submesh (explicit in/out_shardings, GSPMD inserts the
cross-peer collectives), for attack, fault-armed, and recovery windows
alike. The alternative `mesh=` (1-D peer mesh) shards each trial's peer
rows instead and keeps trials sequential; the two compose at the
device-grid level, not per-run.
"""

from __future__ import annotations

import json
import math
import os
import time
from dataclasses import dataclass, field, replace

import numpy as np

from ..config.env import GossipSubParams
from ..ops.adversary import (
    AdversaryParams,
    attacker_cohort,
    censor_mask,
    censorship_penalty_update,
    eclipse_setup,
    heartbeats_to_graylist,
    run_adaptive_heartbeats,
    run_attacked_heartbeats,
)
from ..ops.dht_adversary import (
    DhtAdversaryParams,
    build_attacked_dht,
    dht_repair_pool,
    rtable_poison_frac,
)
from ..ops.faults import (
    FaultParams,
    fault_masks,
    partition_edge_mask,
    run_faulted_heartbeats,
)
from ..ops.repair import (
    RepairParams,
    run_adaptive_recovery_heartbeats,
    run_dht_recovery_heartbeats,
    run_recovery_heartbeats,
)
from ..ops.state import (arm_repair, disarm_repair, repair_inert,
                         repair_totals)
from ..ops.telemetry import TelemetryParams
from .profiling import counters, device_read, device_reads, span
from .simulator import ExperimentConfig, MessageRecord, Simulator
from .summarize import sanitize_nonfinite

# an attack "engaged" when this fraction of honest->attacker edges is
# graylisted (1.0 is the steady state; <1.0 tolerates stragglers whose
# cohort edge died to churn mid-window)
GRAYLIST_ENGAGED_FRAC = 0.95


def attack_gossipsub(**overrides) -> GossipSubParams:
    """GossipSub params with the score defense ARMED. The reference default
    (slow_peer_penalty_weight=0.0) statically compiles every threshold out
    of the step (`thresholds_can_bind`, ops/state.py) — an attack campaign
    against that config would measure nothing. These weights give the
    documented engagement budget of ~7 accrual rounds for unit violations
    (heartbeats_to_graylist: c_req=5, decay 0.9)."""
    base = dict(
        slow_peer_penalty_weight=-10.0,
        slow_peer_penalty_decay=0.9,
        gossip_threshold=-10.0,
        publish_threshold=-20.0,
        graylist_threshold=-50.0,
    )
    base.update(overrides)
    return GossipSubParams(**base)


@dataclass(frozen=True)
class SupervisorConfig:
    """Host-side trial supervision: timeout + bounded retry with exponential
    backoff + quarantine. The reference tooling "re-runs crashed
    experiments" (SURVEY §5); this closes that row — one poisoned trial
    (device OOM, NaN, checkify trip, hung scan) degrades the sweep instead
    of aborting it, and retries resume from the per-trial checkpoints when
    `checkpoint_dir` is set, so a re-run pays only the failed cell.

    Retry k (1-based) sleeps retry_backoff_s * 2**(k-1) first, so the total
    backoff budget for a cell is retry_backoff_s * (2**max_retries - 1).

    `trial_timeout_s` > 0 runs each attempt on a worker thread and abandons
    it at the deadline. Python cannot cancel in-flight XLA work: the
    abandoned attempt may still be finishing its device call while the
    retry starts, which is safe for results (every attempt re-derives all
    trial state from _reset_trial, and checkpoint writes are atomic
    tmp->replace with an epoch-hash identity check) but means a truly hung
    backend still holds its thread. 0 disables the timeout (default).

    `inject_failures`: deterministic failure hook — the first K supervised
    attempts raise before touching the device. This is the CI/test knob
    that makes "campaign with K crashes completes degraded" a reproducible
    assertion, not a hope."""

    trial_timeout_s: float = 0.0
    max_retries: int = 2
    retry_backoff_s: float = 0.5
    inject_failures: int = 0

    def validate(self) -> None:
        if self.trial_timeout_s < 0.0:
            raise ValueError("trial_timeout_s must be >= 0 (0 disables)")
        if self.max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        if self.retry_backoff_s < 0.0:
            raise ValueError("retry_backoff_s must be >= 0")
        if self.inject_failures < 0:
            raise ValueError("inject_failures must be >= 0")


class _FailureInjector:
    """Counts down SupervisorConfig.inject_failures across supervised
    attempts (campaign-global, not per-cell: K injected failures total)."""

    def __init__(self, k: int):
        self.left = int(k)

    def maybe_fail(self) -> None:
        if self.left > 0:
            self.left -= 1
            raise RuntimeError(
                "injected trial failure (SupervisorConfig.inject_failures)")


def _call_with_timeout(fn, timeout_s: float):
    if timeout_s <= 0.0:
        return fn()
    import concurrent.futures as cf

    ex = cf.ThreadPoolExecutor(max_workers=1)
    try:
        return ex.submit(fn).result(timeout=timeout_s)
    finally:
        # never join the worker: a hung attempt must not hang the sweep
        ex.shutdown(wait=False)


def _supervise(sup: SupervisorConfig, injector: _FailureInjector, run,
               on_fail=None, sleep=time.sleep):
    """Run one trial cell under the supervisor. Returns
    (result | None, retries_used, last_error | None) — None result means
    every attempt failed and the caller should quarantine the cell."""
    last_err = None
    for attempt in range(sup.max_retries + 1):
        if attempt > 0:
            sleep(sup.retry_backoff_s * (2 ** (attempt - 1)))
        try:
            injector.maybe_fail()
            return _call_with_timeout(run, sup.trial_timeout_s), attempt, None
        except Exception as e:  # noqa: BLE001 — the supervisor IS the handler
            last_err = e
            if on_fail is not None:
                on_fail()
    return None, sup.max_retries, last_err


@dataclass
class CampaignConfig:
    scenario: str = "sybil_graft_flood"
    fractions: tuple = (0.0, 0.1, 0.2)
    seeds: tuple = (0,)
    experiment: ExperimentConfig = field(
        default_factory=lambda: ExperimentConfig(gossipsub=attack_gossipsub()))
    adversary: AdversaryParams | None = None  # None -> built from scenario
    # attacked mesh-maintenance rounds between warmup and the first publish
    attack_heartbeats: int = 20
    # attacker mesh-share floor that counts as "recovered"
    mesh_recovery_share: float = 0.05
    # post-attack repair rounds (0 = no recovery window; the pre-repair
    # campaign shape, bit-identical trial outputs)
    recovery_heartbeats: int = 0
    # mesh-repair knobs for the recovery window (ops/repair.py); defaults
    # are all OFF, i.e. a recovery window that only runs benign heartbeats
    repair: RepairParams = field(default_factory=RepairParams)
    # batch same-fraction trials into one vmapped attack window (un-sharded
    # runs only; sharded runs go sequential so placement stays row-wise)
    vmap_trials: bool = True
    # snapshot each trial's post-window state here (runtime/checkpoint.py)
    checkpoint_dir: str | None = None
    # fault schedule compiled into the attack window (ops/faults.py);
    # defaults all-off — the window then IS run_attacked_heartbeats
    faults: FaultParams = field(default_factory=FaultParams)
    # host-side trial supervision (timeout/retry/backoff/quarantine)
    supervisor: SupervisorConfig = field(default_factory=SupervisorConfig)
    # opt-in flight recorder (ops/telemetry.py): record=True adds the tel_*
    # per-heartbeat channels to every window's obs curves (attack, fault,
    # recovery — vmapped and nested-sharded alike) and the per-round
    # milestone columns to TrialResult; the default (record=False) leaves
    # every window on the exact pre-telemetry program
    telemetry: TelemetryParams = field(default_factory=TelemetryParams)
    # DHT adversary + discovery wiring (ops/dht_adversary.py): armed, every
    # trial builds a per-seed Kademlia state (shared attacker cohort —
    # cross-protocol), the recovery window's re-dial path draws candidates
    # from the possibly-attacked FIND_NODE shortlist, and dht.heal_hb
    # splits the window into an attacked leg and a healed leg. The default
    # (all-off) leaves every trial on the exact pre-DHT program.
    dht: DhtAdversaryParams = field(default_factory=DhtAdversaryParams)
    # attach a small-N conformance certificate for this campaign's scenario
    # (analysis/conformance.py) to CampaignResult.conformance — the sweep's
    # artifact then carries its own faithfulness check alongside the budget
    conformance: bool = False

    def adversary_params(self) -> AdversaryParams:
        return self.adversary or AdversaryParams(scenario=self.scenario)

    def validate(self) -> None:
        adv = self.adversary_params()
        adv.validate()
        if adv.scenario != self.scenario:
            raise ValueError(
                f"adversary.scenario {adv.scenario!r} != campaign scenario "
                f"{self.scenario!r}")
        if not self.fractions or not self.seeds:
            raise ValueError("need at least one fraction and one seed")
        for f in self.fractions:
            if not (0.0 <= f < 1.0):
                raise ValueError(f"attacker fraction {f} outside [0, 1)")
        if self.attack_heartbeats < 1:
            raise ValueError("attack_heartbeats must be >= 1")
        if self.recovery_heartbeats < 0:
            raise ValueError("recovery_heartbeats must be >= 0")
        self.repair.validate()
        self.faults.validate()
        self.supervisor.validate()
        self.telemetry.validate()
        self.dht.validate()
        if self.dht.enabled:
            if self.recovery_heartbeats < 1:
                raise ValueError(
                    "dht arming needs recovery_heartbeats >= 1: the DHT "
                    "candidate source only feeds the recovery window")
            if not self.repair.redial:
                raise ValueError(
                    "dht arming needs repair.redial=True: the DHT shortlist "
                    "is the re-dial path's candidate source")
            if self.dht.heal_hb >= self.recovery_heartbeats:
                raise ValueError(
                    f"dht.heal_hb {self.dht.heal_hb} must fall inside the "
                    f"recovery window ({self.recovery_heartbeats} rounds)")
        if self.faults.crash and (
                self.faults.crash_window[1] > self.attack_heartbeats):
            # the restart edge must land inside the window or the cohort
            # never comes back and reconvergence is unmeasurable by
            # construction (the partition/spike windows MAY spill past the
            # window end — a still-open partition composes into the publish
            # schedule's delivery mask instead)
            raise ValueError(
                f"crash_window end {self.faults.crash_window[1]} exceeds "
                f"attack_heartbeats {self.attack_heartbeats}: the restart "
                "would never fire")
        if adv.eclipse:
            if self.experiment.gossipsub.flood_publish:
                # flood_publish sends to EVERY connected peer regardless of
                # mesh: the eclipse would be a no-op and the trial would
                # silently measure nothing
                raise ValueError(
                    "eclipse_publisher requires flood_publish=False "
                    "(flood publish bypasses the eclipsed mesh)")
            if self.experiment.publisher_rotation:
                raise ValueError(
                    "eclipse_publisher targets one publisher; disable "
                    "publisher_rotation")


@dataclass
class TrialResult:
    scenario: str
    fraction: float
    seed: int
    attackers: int
    honest_coverage: float
    benign_coverage: float
    latency_p50_ms: float
    latency_p99_ms: float
    benign_p50_ms: float
    latency_inflation: float
    hb_to_graylist: int          # window round (1-based); -1 = never engaged
    hb_budget: float             # closed-form documented budget (may be inf)
    graylisted_frac_final: float
    mesh_recovery_hb: int        # -1 = not recovered inside the window
    attacker_mesh_share_final: float
    attacker_score_final: float
    wall_s: float
    # mesh-repair subsystem outputs (defaults keep pre-repair trial dicts
    # valid: zero activity, no recovery window)
    mesh_evictions_total: int = 0
    px_grafts_total: int = 0
    redials_total: int = 0
    recovery_time_ms: float = -1.0
    # network-wide bytes transmitted over the trial's full timeline
    # (attack + recovery + publish schedule) — the bandwidth axis of the
    # defense Pareto sweep; -1 = written by an older sweep without it
    bytes_tx_total: float = -1.0
    # fault-injection observables (ops/faults.py); -1 = family not armed
    # or never reached the milestone
    heal_time_ms: float = -1.0           # rounds after heal until the first
    #                                      cross-cut mesh edge, in sim ms
    post_churn_reconvergence_hb: int = -1  # rounds after restart until the
    #                                        cohort's mean degree >= D_low
    coverage_under_partition: float = -1.0  # honest share on the
    #                                         publisher's side of the cut
    # flight-recorder curve milestones (ops/telemetry.py); -1 = recorder
    # off or the curve never crossed inside the recorded windows
    coverage90_hb: int = -1      # first round with tel_mesh_coverage >= 0.9
    score_cross_hb: int = -1     # first round the median live score drops
    #                              below graylist_threshold
    # DHT adversary observables (ops/dht_adversary.py); -1 = DHT not armed
    rtable_poison_frac: float = -1.0  # attacker share of occupied honest
    #                                   routing-table slots, post-build

    def to_dict(self) -> dict:
        # strict-JSON consumers run allow_nan=False; the shared sanitizer
        # nulls the legitimately-infinite fields (e.g. hb_budget)
        return sanitize_nonfinite(dict(self.__dict__))


@dataclass
class CampaignResult:
    scenario: str
    network_size: int
    trials: list[TrialResult]
    hb_budget: float
    wall_s: float
    # supervisor outcome: a degraded sweep completed with retries and/or
    # quarantined cells instead of raising — strict-JSON consumers see the
    # full record (which cells are missing and why) in quarantined_trials
    degraded: bool = False
    quarantined_trials: list = field(default_factory=list)
    retries_total: int = 0
    # conformance certificate for this scenario (CampaignConfig.conformance;
    # analysis/conformance.py) — None when the gate wasn't requested
    conformance: dict | None = None
    # what the campaign counted of itself (`_campaign_counters`; the
    # `sim:attack/counters` annotation and `attack --stats-json`); not part
    # of `to_dict`, so the campaign's JSON is what it was
    counters: dict = field(default_factory=dict)

    @property
    def trials_per_s(self) -> float:
        return len(self.trials) / max(self.wall_s, 1e-9)

    def to_dict(self) -> dict:
        return sanitize_nonfinite({
            "scenario": self.scenario,
            "network_size": self.network_size,
            "hb_budget": self.hb_budget,
            "wall_s": self.wall_s,
            "trials_per_s": self.trials_per_s,
            "degraded": self.degraded,
            "retries_total": self.retries_total,
            "quarantined_trials": list(self.quarantined_trials),
            "conformance": self.conformance,
            "trials": [t.to_dict() for t in self.trials],
        })


# --------------------------------------------------------------------- trials


@dataclass
class _Tally:
    """What a campaign counts of itself while it runs, for
    `_campaign_counters`: window dispatches and their heartbeats, publishes,
    and each attacked trial's peak of the `attacker_mesh_share` curve. It
    counts what was run, as `device_reads` does: an attempt the supervisor
    retried is in it beside the retry."""
    vmapped_windows: int = 0
    window_heartbeats: int = 0
    publishes: int = 0
    mesh_share_peaks: list = field(default_factory=list)


def _campaign_counters(trials: list, tally: _Tally, budget: float,
                       reads: int) -> dict:
    """The campaign's counters, from `TrialResult`'s fields and the window's
    observable curves: nothing here reads the device. The resilience numbers
    are over the attacked trials (over all where none is attacked);
    `hb_to_graylist_max` is -1 where one of them never engaged."""
    attacked = [t for t in trials if t.fraction > 0.0]
    over = attacked or trials
    engaged = [t.hb_to_graylist for t in attacked]

    def of(fn, key):
        return fn((getattr(t, key) for t in over), default=0.0)

    return {
        "trials": len(trials),
        "attacked_trials": len(attacked),
        "vmapped_windows": tally.vmapped_windows,
        "window_heartbeats": tally.window_heartbeats,
        "publishes": tally.publishes,
        "device_reads": reads,
        "honest_coverage_min": of(min, "honest_coverage"),
        "latency_inflation_max": of(max, "latency_inflation"),
        "hb_to_graylist_max": (-1 if not engaged or min(engaged) < 0
                               else max(engaged)),
        "hb_budget": budget,
        "graylisted_frac_final_min": of(min, "graylisted_frac_final"),
        "attacker_mesh_share_peak": max(tally.mesh_share_peaks, default=0.0),
        "attacker_score_final_mean": float(np.mean(
            [t.attacker_score_final for t in over] or [0.0])),
    }


def _reset_trial(sim: Simulator, seed: int) -> None:
    """Rewind the shared Simulator onto a trial's seed: state PRNG and msgId
    stream re-derive from `seed`, the built graph/topology stay the
    campaign's (Simulator.reset keeps both by design)."""
    base = sim.cfg.seed
    sim.cfg.seed = seed
    try:
        sim.reset()
    finally:
        sim.cfg.seed = base


def _publish_schedule(
    sim: Simulator,
    censor=None,
    attacker=None,
    adv: AdversaryParams | None = None,
    cross=None,
    partition_ms=None,
) -> list[MessageRecord]:
    """The experiment's injection schedule (Simulator.run's loop), with the
    adversarial delivery mask threaded into every publish and the P3-analog
    censorship penalty applied after each one.

    `cross`/`partition_ms`: a still-open partition (ops/faults.py window
    extending past the attack window) folds its cross-cut edge mask into
    the delivery mask of every publish falling inside [lo, hi) sim-ms —
    "eclipse during a partition" is censor|cross on the same publish."""
    exp = sim.cfg
    n = exp.topo.network_size
    delay_ms = exp.topo.delay_seconds * 1000.0
    pub = exp.publisher_id % n
    a = sim.arrays
    for i in range(exp.topo.messages):
        if i > 0:
            sim.advance(delay_ms)
        eff = censor
        if cross is not None and partition_ms is not None:
            t_now = float(device_read(sim.state.t_ms))
            if partition_ms[0] <= t_now < partition_ms[1]:
                eff = cross if censor is None else (censor | cross)
        rec = sim.publish(pub, censor_edge=eff)
        if censor is not None:
            import jax.numpy as jnp

            sim.state = censorship_penalty_update(
                sim.state, a["conns"], a["rev"], attacker,
                jnp.asarray(rec.received), sim.params, adv)
        if exp.publisher_rotation:
            pub = (pub + 1) % n
    return sim.records


def _delivery_metrics(records: list[MessageRecord], honest: np.ndarray):
    """(coverage, p50_ms, p99_ms) over honest peers, pooled across the
    schedule. Empty delivery pools report inf latencies (to_dict nulls
    them for strict-JSON consumers)."""
    if not records:
        return 0.0, math.inf, math.inf
    cov = float(np.mean([r.received[honest].mean() for r in records]))
    pool = np.concatenate(
        [r.delays_ms[honest & r.received] for r in records])
    if pool.size == 0:
        return cov, math.inf, math.inf
    return (cov, float(np.percentile(pool, 50)), float(np.percentile(pool, 99)))


def _ensure_baseline(sim: Simulator, cache: dict, seed: int,
                     tally: _Tally) -> dict:
    """Benign metrics for `seed` (the fraction-0.0 path), computed at most
    once per seed per campaign."""
    if seed not in cache:
        at = dict(fraction=0.0, seed=seed, vmapped=False)
        with span("campaign/baseline", **at):
            with span("trial/setup", **at):
                _reset_trial(sim, seed)
            with span("trial/warmup", **at):
                sim.warmup()
            with span("trial/publish", **at):
                records = _publish_schedule(sim)
            with span("trial/metrics", **at):
                honest = np.ones(sim.params.n, dtype=bool)
                cov, p50, p99 = _delivery_metrics(records, honest)
        tally.publishes += len(records)
        cache[seed] = {"coverage": cov, "p50": p50, "p99": p99}
    return cache[seed]


def _benign_trial(sim: Simulator, cfg: CampaignConfig, seed: int,
                  cache: dict, budget: float, tally: _Tally) -> TrialResult:
    t0 = time.time()
    cache.pop(seed, None)  # force the run (the trial IS the baseline)
    base = _ensure_baseline(sim, cache, seed, tally)
    with span("trial/metrics", fraction=0.0, seed=seed, vmapped=False):
        # the forced _ensure_baseline run above leaves the benign trial's
        # post-publish state bound — its byte counters ARE this trial's
        bytes_tx = float(device_read(sim.state.bytes_tx).sum())
    return TrialResult(
        scenario=cfg.scenario, fraction=0.0, seed=seed, attackers=0,
        honest_coverage=base["coverage"], benign_coverage=base["coverage"],
        latency_p50_ms=base["p50"], latency_p99_ms=base["p99"],
        benign_p50_ms=base["p50"], latency_inflation=1.0,
        hb_to_graylist=-1, hb_budget=budget,
        graylisted_frac_final=0.0, mesh_recovery_hb=-1,
        attacker_mesh_share_final=0.0, attacker_score_final=0.0,
        wall_s=time.time() - t0,
        bytes_tx_total=bytes_tx,
    )


def _first_round(curve: np.ndarray, pred) -> int:
    """1-based index of the first round satisfying pred, -1 if none."""
    hits = np.nonzero(pred(curve))[0]
    return int(hits[0]) + 1 if hits.size else -1


def _obs_metrics(obs: dict, share_floor: float):
    gf = np.asarray(obs["graylisted_frac"], dtype=np.float64)
    share = np.asarray(obs["attacker_mesh_share"], dtype=np.float64)
    engaged = _first_round(gf, lambda c: c >= GRAYLIST_ENGAGED_FRAC)
    peak = int(np.argmax(share))
    if share.max() <= share_floor:
        recovery = 1  # never meaningfully compromised
    else:
        after = share[peak:]
        rel = _first_round(after, lambda c: c <= share_floor)
        recovery = peak + rel if rel > 0 else -1
    return engaged, float(gf[-1]), recovery, float(share[-1])


def _nested_batch_factor(trial_mesh, local_trials: int) -> int:
    """Static memory-dispatch hint for the pull row-gather inside a nested
    window (ops/pull.exceeds_budget): per device the batch is `local_trials`
    trials x 1/per_group of the row space, so the full-N trace shape
    over-counts by the peer submesh width. Both gather forms are exact —
    this only tunes WHICH one large pulls take."""
    from ..parallel.sharding import peers_per_group

    return max(1, -(-local_trials // peers_per_group(trial_mesh)))


def _run_nested_window(body, trial_mesh, n_rows: int, stacked_args: tuple,
                       shared: dict):
    """Compile `body(*stacked_args, conns, rev, out_mask)` as ONE program
    over the full 2-D trials x peers grid: explicit in/out_shardings hand
    GSPMD the placement — stacked peer-major leaves split over BOTH axes
    (parallel/sharding.nested_batch_shardings), the epoch graph arrays
    row-shard over each group's peer submesh — and XLA inserts the
    cross-peer collectives (all-gathers of the (N,)/(N, C) values the
    involution pulls read, reductions for the observable scalars). Output
    shardings come from eval_shape + the same shape rule, so results land
    nested too and the host-side per-trial unstack reads one group's
    shards."""
    import jax

    from ..parallel.sharding import (
        nested_batch_shardings,
        peer_submesh_sharding,
    )

    prow = peer_submesh_sharding(trial_mesh)
    in_sh = tuple(
        nested_batch_shardings(a, trial_mesh, n_rows) for a in stacked_args
    ) + (prow, prow, prow)
    args = stacked_args + (shared["conns"], shared["rev"], shared["out_mask"])
    out_sh = nested_batch_shardings(
        jax.eval_shape(body, *args), trial_mesh, n_rows)
    return jax.jit(body, in_shardings=in_sh, out_shardings=out_sh)(*args)


def _protocol_window_runner(protocol: str, runner: str):
    """Resolve a campaign window's heartbeat runner through the protocol
    registry (ops/protocol.py). For "gossipsub" — the default every
    pre-arena caller gets — the resolved field IS the module-level runner
    object the windows used to name directly: same function object, same
    jit cache entry, zero retraces, bit-identical
    (tests/test_protocol_registry.py pins the `is` identity). Protocols
    with a per-protocol ctrl carry (episub) thread it explicitly through
    their own windows (sharded_episub_window / _episub_windows); the
    SimState-only windows reject them rather than silently dropping the
    carry."""
    from ..ops.protocol import get_protocol

    spec = get_protocol(protocol)
    if spec.init_ctrl is not None:
        raise ValueError(
            f"protocol {protocol!r} carries a per-protocol ctrl; route it "
            "through its ctrl-threading windows (sharded_episub_window), "
            "not the SimState-only attack/fault windows")
    return getattr(spec, runner)


def _vary_over_trials(*shared_arrays):
    """Inside the trial-only shard_map body: give the replicated epoch-graph
    arrays the trial axis before the window reads them. vmap turns a `cond`
    on a per-trial predicate into a select over both branches and re-binds
    their equations with every operand trial-varying; constants the branch
    compared against an unvarying graph array then no longer match
    (shard_map's varying-axes check). Varying from the start, the trace
    records the casts it needs."""
    import jax

    from ..parallel.sharding import TRIAL_AXIS

    return tuple(jax.lax.pcast(x, (TRIAL_AXIS,), to="varying")
                 for x in shared_arrays)


def sharded_attack_window(stacked, shared: dict, attackers, params, adv,
                          steps: int, trial_mesh, local_trials: int,
                          nested: bool = True, telemetry=None,
                          protocol: str = "gossipsub"):
    """One device program over the 2-D trials x peers grid: the stacked
    batch's trial axis splits across trial groups AND each trial's peer
    rows split across the group's peer submesh. `stacked` leaves and
    `attackers` carry a leading trial axis divisible by the mesh's group
    count; `shared` is the epoch graph dict (peer-row-sharded within every
    group).

    `nested=True` (default) is the pjit formulation: explicit
    in/out_shardings over the full grid, both axes live. `nested=False`
    retains the PR-5 trial-only shard_map whose body names just "trials"
    in its specs and therefore REPLICATES each group's peer submesh — the
    equality baseline the nested program is pinned against
    (tests/test_trial_sharding.py) and the degenerate-grid fallback's
    semantics (with 1 peer device per group the two emit the same
    partitioning).

    Both branches call run_adaptive_heartbeats: disabled policies
    literally delegate to run_attacked_heartbeats inside the trace (the
    identical program, no extra leaves), while an armed
    adv.adaptive widens the window output to ((states, ctrls), obs) — the
    per-trial AdaptiveCtrl leaves are (T, N) peer-major like the attacker
    masks, so they nested-shard through the same in/out rules."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..parallel.sharding import TRIAL_AXIS, shard_map

    run_win = _protocol_window_runner(protocol, "run_adaptive_heartbeats")
    if nested:
        bf = _nested_batch_factor(trial_mesh, local_trials)

        def body(st, at, cn, rv, om):
            def one(s, a):
                return run_win(
                    s, cn, rv, om, a, params, adv, steps, batch_factor=bf,
                    telemetry=telemetry)

            return jax.vmap(one)(st, at)

        n_rows = shared["conns"].shape[0]
        return _run_nested_window(body, trial_mesh, n_rows,
                                  (stacked, attackers), shared)

    t, r = P(TRIAL_AXIS), P()

    def group(st, at, cn, rv, om):
        cn, rv, om = _vary_over_trials(cn, rv, om)

        def one(s, a):
            return run_win(
                s, cn, rv, om, a, params, adv, steps,
                batch_factor=local_trials, telemetry=telemetry)

        return jax.vmap(one)(st, at)

    # jit around the shard_map: eagerly-applied shard_map dispatches the
    # window primitive-by-primitive (~67 compiles per call measured by
    # runtime/profiling.count_retraces); under jit the whole window is one
    # program and a second same-aval call costs one closure rebuild
    return jax.jit(shard_map(
        group, mesh=trial_mesh, in_specs=(t, t, r, r, r), out_specs=(t, t),
    ))(stacked, attackers, shared["conns"], shared["rev"], shared["out_mask"])


def sharded_faulted_window(stacked, shared: dict, attackers, crash, side,
                           spike, params, adv, faults, steps: int,
                           trial_mesh, local_trials: int, telemetry=None,
                           protocol: str = "gossipsub"):
    """The fault-armed nested window: per-trial crash/side/spike cohort
    masks are (T, N) peer-major exactly like the attacker masks, so they
    shard over both grid axes and the fault-scheduled scan
    (ops/faults.run_faulted_heartbeats) runs peer-partitioned inside each
    trial group — fault sweeps ride the grid instead of falling back to
    the vmapped single-device stack."""
    import jax

    run_win = _protocol_window_runner(protocol, "run_faulted_heartbeats")
    bf = _nested_batch_factor(trial_mesh, local_trials)

    def body(st, at, cr, sd, sp, cn, rv, om):
        def one(s, a, c2, d2, p2):
            return run_win(
                s, cn, rv, om, a, params, adv, faults, c2, d2, p2, steps,
                batch_factor=bf, telemetry=telemetry)

        return jax.vmap(one)(st, at, cr, sd, sp)

    n_rows = shared["conns"].shape[0]
    return _run_nested_window(body, trial_mesh, n_rows,
                              (stacked, attackers, crash, side, spike),
                              shared)


def sharded_recovery_window(stacked, shared: dict, attackers, rparams,
                            steps: int, publisher: int, trial_mesh,
                            local_trials: int, nested: bool = True,
                            telemetry=None):
    """The recovery analog of sharded_attack_window: every trial's repair
    window runs from the shared EPOCH graph (recoveries are independent per
    trial), and each trial's possibly-dialed graph arrays come back with a
    leading trial axis — nested-sharded like the state — for the host to
    rebind per trial. Same nested/legacy split as the attack window."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..parallel.sharding import TRIAL_AXIS, shard_map

    if nested:
        bf = _nested_batch_factor(trial_mesh, local_trials)

        def body(st, at, cn, rv, om):
            def one(s, a):
                return run_recovery_heartbeats(
                    s, cn, rv, om, a, rparams, steps, publisher=publisher,
                    batch_factor=bf, telemetry=telemetry)

            return jax.vmap(one)(st, at)

        n_rows = shared["conns"].shape[0]
        return _run_nested_window(body, trial_mesh, n_rows,
                                  (stacked, attackers), shared)

    t, r = P(TRIAL_AXIS), P()

    def group(st, at, cn, rv, om):
        cn, rv, om = _vary_over_trials(cn, rv, om)

        def one(s, a):
            return run_recovery_heartbeats(
                s, cn, rv, om, a, rparams, steps, publisher=publisher,
                batch_factor=local_trials, telemetry=telemetry)

        return jax.vmap(one)(st, at)

    # jit for the same reason as sharded_attack_window's legacy branch:
    # one program per window instead of eager per-primitive dispatch
    return jax.jit(shard_map(
        group, mesh=trial_mesh, in_specs=(t, t, r, r, r), out_specs=(t, t),
    ))(stacked, attackers, shared["conns"], shared["rev"], shared["out_mask"])


def _run_nested_window_stacked(body, trial_mesh, n_rows: int,
                               stacked_args: tuple):
    """_run_nested_window for a body whose EVERY input carries a leading
    trial axis — the second DHT recovery leg, where each trial continues
    from its own (possibly dialed) graph arrays instead of the shared
    epoch graph."""
    import jax

    from ..parallel.sharding import nested_batch_shardings

    in_sh = tuple(
        nested_batch_shardings(a, trial_mesh, n_rows) for a in stacked_args)
    out_sh = nested_batch_shardings(
        jax.eval_shape(body, *stacked_args), trial_mesh, n_rows)
    return jax.jit(body, in_shardings=in_sh,
                   out_shardings=out_sh)(*stacked_args)


def sharded_dht_recovery_window(stacked, shared: dict | None, graphs,
                                attackers, pools, rparams, steps: int,
                                publisher: int, trial_mesh,
                                local_trials: int, telemetry=None):
    """The DHT-armed recovery window on the 2-D trials x peers grid: the
    per-trial (N, K) discovery shortlists are peer-major like the attacker
    masks, so they shard over both axes and ride the repair scan carry
    inside each trial group. Pass `shared` (the epoch graph dict) for a
    window starting from the shared graph, or `graphs` (stacked per-trial
    (T, N, C) conns/rev/out_mask) for a continuation leg that resumes each
    trial's own dialed graph — the heal-after-eclipse second leg."""
    import jax

    bf = _nested_batch_factor(trial_mesh, local_trials)

    if graphs is None:
        def body(st, at, pl, cn, rv, om):
            def one(s, a, p):
                return run_dht_recovery_heartbeats(
                    s, cn, rv, om, a, rparams, steps, dht_pool=p,
                    publisher=publisher, batch_factor=bf,
                    telemetry=telemetry)

            return jax.vmap(one)(st, at, pl)

        n_rows = shared["conns"].shape[0]
        return _run_nested_window(body, trial_mesh, n_rows,
                                  (stacked, attackers, pools), shared)

    def body2(st, at, pl, cn, rv, om):
        def one(s, a, p, c2, r2, o2):
            return run_dht_recovery_heartbeats(
                s, c2, r2, o2, a, rparams, steps, dht_pool=p,
                publisher=publisher, batch_factor=bf, telemetry=telemetry)

        return jax.vmap(one)(st, at, pl, cn, rv, om)

    n_rows = graphs[0].shape[1]
    return _run_nested_window_stacked(
        body2, trial_mesh, n_rows,
        (stacked, attackers, pools) + tuple(graphs))


def _unstack_trial(tree_fn, stacked_out, j: int):
    """Slice trial j out of a sharded window's stacked output and NORMALIZE
    its placement to the default device. A nested-sharded output leaf keeps
    its peer-axis sharding through the slice; leaving that residue on the
    state would re-partition every downstream host-driven program (the
    publish schedule, per-trial checkpoints) under GSPMD — whose tie-breaks
    (sort-based queue ranks) need not match the single-device program the
    unsharded path runs. One device_put per leaf restores the exact
    unsharded placement, which is what the PR-5 equality pins compare
    against."""
    import jax

    # local_devices, not devices: under a multi-process (DCN) run the global
    # devices[0] belongs to rank 0 and a device_put onto it from any other
    # rank would fail — the default device is always the first ADDRESSABLE one
    dev0 = jax.local_devices()[0]
    return tree_fn(lambda x: jax.device_put(x[j], dev0), stacked_out)


def _pad_to_groups(states: list, attackers: list, trial_mesh, extras=None):
    """Pad a trial batch to a multiple of the trial-group count by repeating
    the last trial (extras are dropped after the window). Returns
    (states, attackers, local_trials), or with `extras` (a parallel
    per-trial list, e.g. fault-mask dicts) padded alongside:
    (states, attackers, extras, local_trials)."""
    from ..parallel.sharding import TRIAL_AXIS

    groups = trial_mesh.shape[TRIAL_AXIS]
    pad = (-len(states)) % groups
    states = list(states) + [states[-1]] * pad
    attackers = list(attackers) + [attackers[-1]] * pad
    if extras is not None:
        extras = list(extras) + [extras[-1]] * pad
        return states, attackers, extras, len(states) // groups
    return states, attackers, len(states) // groups


def _attack_windows(sim: Simulator, attackers, states, adv, steps: int,
                    trial_mesh=None, faults=None, fmasks=None,
                    telemetry=None, protocol: str = "gossipsub"):
    """Run the attack window for a batch of trials. With `trial_mesh` (a 2-D
    make_trial_mesh grid) the stacked batch runs as one nested-sharded
    program — trials split over the grid's trial groups, each trial's peer
    rows split over the group's peer submesh. Un-sharded multi-trial
    batches stack onto one vmapped scan (the fraction's whole seed column
    in one device program); single trials run the plain jit.

    `faults`/`fmasks`: an armed FaultParams plus the per-trial fault_masks
    cohorts (list of dicts of device arrays) route the window through
    run_faulted_heartbeats. The cohort masks are peer-major (T, N) exactly
    like the attacker masks, so fault sweeps shard over the same grid
    (sharded_faulted_window) instead of dropping the trial_mesh.

    Returns (states, obs_dicts, ctrls): `ctrls` is the per-trial
    AdaptiveCtrl list when adv.adaptive is armed (every window runner
    widens its state output to (state, ctrl) then) and None otherwise —
    the caller threads each trial's controller into its recovery legs."""
    import jax
    import jax.numpy as jnp

    tree = jax.tree_util.tree_map
    a = sim.arrays
    run_adaptive = _protocol_window_runner(protocol,
                                           "run_adaptive_heartbeats")
    run_faulted = _protocol_window_runner(protocol, "run_faulted_heartbeats")
    adaptive = adv.adaptive.enabled
    faulted = faults is not None and faults.enabled
    if faulted and trial_mesh is not None and len(states) > 1:
        from ..parallel.sharding import place_trial_batch

        n_rows = sim.params.n
        s_count = len(states)
        states, attackers, fmasks, local = _pad_to_groups(
            states, attackers, trial_mesh, extras=fmasks)
        stacked = tree(lambda *xs: jnp.stack(xs), *states)
        att = jnp.stack(attackers)
        crs = jnp.stack([m["crash"] for m in fmasks])
        sds = jnp.stack([m["side"] for m in fmasks])
        sps = jnp.stack([m["spike"] for m in fmasks])
        (stacked, att, crs, sds, sps), shared = place_trial_batch(
            (stacked, att, crs, sds, sps), a, trial_mesh, n_rows=n_rows)
        out_states, obs = sharded_faulted_window(
            stacked, shared, att, crs, sds, sps, sim.params, adv, faults,
            steps, trial_mesh, local, telemetry=telemetry,
            protocol=protocol)
        obs_np = device_read(obs)
        outs, ctrls = [], ([] if adaptive else None)
        for j in range(s_count):
            st = _unstack_trial(tree, out_states, j)
            if adaptive:
                st, c = st
                ctrls.append(c)
            outs.append(st)
        return outs, [{k: v[j] for k, v in obs_np.items()}
                      for j in range(s_count)], ctrls
    if faulted and len(states) == 1:
        m = fmasks[0]
        st, obs = run_faulted(
            states[0], a["conns"], a["rev"], a["out_mask"], attackers[0],
            sim.params, adv, faults, m["crash"], m["side"], m["spike"],
            steps, telemetry=telemetry)
        ctrls = None
        if adaptive:
            st, c = st
            ctrls = [c]
        return [st], [device_read(obs)], ctrls
    if faulted:
        s_count = len(states)
        stacked = tree(lambda *xs: jnp.stack(xs), *states)
        att = jnp.stack(attackers)
        crs = jnp.stack([m["crash"] for m in fmasks])
        sds = jnp.stack([m["side"] for m in fmasks])
        sps = jnp.stack([m["spike"] for m in fmasks])

        def one_f(st, at, cr, sd, sp):
            return run_faulted(
                st, a["conns"], a["rev"], a["out_mask"], at, sim.params,
                adv, faults, cr, sd, sp, steps, batch_factor=s_count,
                telemetry=telemetry)

        out_states, obs = jax.vmap(one_f)(stacked, att, crs, sds, sps)
        ctrl_stack = None
        if adaptive:
            out_states, ctrl_stack = out_states
        obs_np = device_read(obs)
        return (
            [tree(lambda x, j=j: x[j], out_states) for j in range(s_count)],
            [{k: v[j] for k, v in obs_np.items()} for j in range(s_count)],
            ([tree(lambda x, j=j: x[j], ctrl_stack) for j in range(s_count)]
             if adaptive else None),
        )
    if trial_mesh is not None and len(states) > 1:
        from ..parallel.sharding import place_trial_batch

        s_count = len(states)
        states, attackers, local = _pad_to_groups(states, attackers,
                                                  trial_mesh)
        stacked = tree(lambda *xs: jnp.stack(xs), *states)
        att = jnp.stack(attackers)
        (stacked, att), shared = place_trial_batch(
            (stacked, att), a, trial_mesh, n_rows=sim.params.n)
        out_states, obs = sharded_attack_window(
            stacked, shared, att, sim.params, adv, steps, trial_mesh, local,
            telemetry=telemetry, protocol=protocol)
        obs_np = device_read(obs)
        outs, ctrls = [], ([] if adaptive else None)
        for j in range(s_count):
            st = _unstack_trial(tree, out_states, j)
            if adaptive:
                st, c = st
                ctrls.append(c)
            outs.append(st)
        return outs, [{k: v[j] for k, v in obs_np.items()}
                      for j in range(s_count)], ctrls
    if len(states) == 1:
        st, obs = run_adaptive(
            states[0], a["conns"], a["rev"], a["out_mask"], attackers[0],
            sim.params, adv, steps, telemetry=telemetry)
        ctrls = None
        if adaptive:
            st, c = st
            ctrls = [c]
        return [st], [device_read(obs)], ctrls
    s_count = len(states)
    stacked = tree(lambda *xs: jnp.stack(xs), *states)
    att = jnp.stack(attackers)

    def one(st, at):
        return run_adaptive(
            st, a["conns"], a["rev"], a["out_mask"], at, sim.params, adv,
            steps, batch_factor=s_count, telemetry=telemetry)

    out_states, obs = jax.vmap(one)(stacked, att)
    ctrl_stack = None
    if adaptive:
        out_states, ctrl_stack = out_states
    obs_np = device_read(obs)
    return (
        [tree(lambda x, j=j: x[j], out_states) for j in range(s_count)],
        [{k: v[j] for k, v in obs_np.items()} for j in range(s_count)],
        ([tree(lambda x, j=j: x[j], ctrl_stack) for j in range(s_count)]
         if adaptive else None),
    )


def _trial_ckpt(cfg: CampaignConfig, fraction: float, seed: int):
    """(checkpoint, obs-sidecar) paths for one (fraction, seed) cell."""
    base = os.path.join(cfg.checkpoint_dir,
                        f"{cfg.scenario}_f{fraction:g}_s{seed}")
    return base + ".npz", base + ".obs.npz"


def _try_resume(sim: Simulator, cfg: CampaignConfig, fraction: float,
                seed: int):
    """(post-window state, attack-window obs) recovered from a prior run's
    per-trial checkpoint + obs sidecar, or None. Identity is the EPOCH
    graph hash the checkpoint was written against plus the current state
    layout version — a stale snapshot is silently recomputed, never
    trusted."""
    import json

    from .checkpoint import FORMAT_VERSION, _graph_hash, restore_state

    ck, sc = _trial_ckpt(cfg, fraction, seed)
    if not (os.path.exists(ck) and os.path.exists(sc)):
        return None
    try:
        z = np.load(ck)
        meta = json.loads(bytes(z["meta_json"]).decode())
        if meta["version"] != FORMAT_VERSION:
            return None
        if meta.get("graph_sha256") != _graph_hash(sim.graph):
            return None
        state = restore_state(sim.state, z, meta["version"])
        zo = np.load(sc)
        obs = {k: np.asarray(zo[k]) for k in zo.files}
    except Exception:
        return None  # unreadable/truncated snapshot: recompute the trial
    return state, obs


def _recovery_windows_sharded(sim: Simulator, cfg: CampaignConfig,
                              states: list, attackers: list, pub: int,
                              trial_mesh, telemetry=None):
    """Batch every trial's recovery window into one shard_map program over
    the trial groups; returns per-trial ((state, conns, rev, out_mask),
    obs) in input order. Each trial recovers from the shared EPOCH graph,
    exactly like the sequential path restores it between trials."""
    import jax
    import jax.numpy as jnp

    tree = jax.tree_util.tree_map
    t_count = len(states)
    states, attackers, local = _pad_to_groups(states, attackers, trial_mesh)
    stacked = arm_repair(tree(lambda *xs: jnp.stack(xs), *states))
    att = jnp.stack(attackers)
    rparams = cfg.repair.apply(sim.params)
    outs, obs = sharded_recovery_window(
        stacked, sim.arrays, att, rparams, cfg.recovery_heartbeats, pub,
        trial_mesh, local, telemetry=telemetry)
    obs_np = tree(np.asarray, obs)
    return [
        (_unstack_trial(tree, outs, j),
         {k: v[j] for k, v in obs_np.items()})
        for j in range(t_count)
    ]


def _dht_legs(dht: DhtAdversaryParams, steps: int) -> tuple[int, int]:
    """(attacked rounds, healed rounds) of a recovery window: dht.heal_hb
    splits the window at the heal edge; -1 = the DHT never heals."""
    if dht.heal_hb < 0:
        return steps, 0
    return dht.heal_hb, steps - dht.heal_hb


def _dht_recovery_windows_sharded(sim: Simulator, cfg: CampaignConfig,
                                  states: list, attackers: list,
                                  pools_a: list, pools_b: list, pub: int,
                                  trial_mesh, telemetry=None):
    """The DHT-armed analog of _recovery_windows_sharded: one nested window
    per leg (attacked, then healed), the second leg resuming each trial's
    own dialed graph arrays; obs legs concatenate along the round axis so
    recovery_time_ms is measured over the whole window."""
    import jax
    import jax.numpy as jnp

    from ..parallel.sharding import place_trial_batch

    tree = jax.tree_util.tree_map
    t_count = len(states)
    steps1, steps2 = _dht_legs(cfg.dht, cfg.recovery_heartbeats)
    pairs = list(zip(pools_a, pools_b))
    states, attackers, pairs, local = _pad_to_groups(
        states, attackers, trial_mesh, extras=pairs)
    stacked = arm_repair(tree(lambda *xs: jnp.stack(xs), *states))
    att = jnp.stack(attackers)
    (stacked, att), shared = place_trial_batch(
        (stacked, att), sim.arrays, trial_mesh, n_rows=sim.params.n)
    rparams = cfg.repair.apply(sim.params)
    obs_legs = []
    cur_state, cur_graphs = stacked, None
    if steps1 > 0:
        pa = jnp.stack([p[0] for p in pairs])
        (st, cn, rv, om, _pool), obs1 = sharded_dht_recovery_window(
            cur_state, shared, None, att, pa, rparams, steps1, pub,
            trial_mesh, local, telemetry=telemetry)
        cur_state, cur_graphs = st, (cn, rv, om)
        obs_legs.append(obs1)
    if steps2 > 0:
        pb = jnp.stack([p[1] for p in pairs])
        (st, cn, rv, om, _pool), obs2 = sharded_dht_recovery_window(
            cur_state, shared if cur_graphs is None else None, cur_graphs,
            att, pb, rparams, steps2, pub, trial_mesh, local,
            telemetry=telemetry)
        cur_state, cur_graphs = st, (cn, rv, om)
        obs_legs.append(obs2)
    obs_np = (tree(np.asarray, obs_legs[0]) if len(obs_legs) == 1 else
              tree(lambda *xs: np.concatenate(
                  [np.asarray(x) for x in xs], axis=1), *obs_legs))
    outs = (cur_state,) + cur_graphs
    return [
        (_unstack_trial(tree, outs, j),
         {k: v[j] for k, v in obs_np.items()})
        for j in range(t_count)
    ]


def _attacked_trials(
    sim: Simulator,
    cfg: CampaignConfig,
    fraction: float,
    seeds: list[int],
    cache: dict,
    budget: float,
    tally: _Tally,
    trial_mesh=None,
) -> list[TrialResult]:
    import jax.numpy as jnp

    adv = cfg.adversary_params()
    exp = cfg.experiment
    n = sim.params.n
    conns_np = np.asarray(sim.graph.conns)
    pub = exp.publisher_id % n
    hb_ms = sim.params.heartbeat_ms
    warm_steps = int(exp.warmup_s * 1000.0 // hb_ms)
    # cold boot joins the network mid-attack: the warmup rounds RUN INSIDE
    # the window (mesh formation under fire), not before it
    steps = cfg.attack_heartbeats + (warm_steps if adv.cold_boot else 0)
    # no dial can ever commit unless PX or re-dial is armed (repair_round's
    # dial path is reachable from BOTH, ops/repair.py `use_px`): with both
    # off the recovery window provably leaves the graph arrays untouched,
    # so the per-trial rebind_graph — a full edge/answer-table rebuild plus
    # a wholesale warm-start invalidation, pure r05-regression-class dead
    # weight here — and the epoch-graph restore are both skipped
    graph_static = not (cfg.repair.px or cfg.repair.redial)
    # normalize ONCE: a disabled recorder must hand the windows the exact
    # pre-telemetry static key (None), not a distinct-but-inert params value
    tel = cfg.telemetry if cfg.telemetry.enabled else None

    t0 = time.time()
    adaptive = adv.adaptive.enabled
    cohorts: dict[int, tuple] = {}
    state_by_seed: dict[int, object] = {}
    obs_by_seed: dict[int, dict] = {}
    # per-trial adversary controller carry (adaptive armed only); a trial
    # resumed from a checkpoint has no snapshot of it and restarts the
    # controller from init_adaptive_ctrl — the conservative warm restart
    # (the attacker re-learns its violation estimate from zero)
    ctrl_by_seed: dict[int, object] = {}
    resumed: set[int] = set()
    # the spans' attributes: a stack of several trials runs ONE window
    vmapped = len(seeds) > 1
    at = {s: dict(fraction=fraction, seed=s, vmapped=vmapped) for s in seeds}
    for s in seeds:
        with span("trial/setup", **at[s]):
            att = attacker_cohort(n, fraction, seed=s, conns=conns_np,
                                  publisher=pub, eclipse=adv.eclipse)
            cohorts[s] = (att, jnp.asarray(att))
    faulted = cfg.faults.enabled
    fmasks_np: dict[int, dict] = {}
    fmasks_dev: dict[int, dict] = {}
    if faulted:
        for s in seeds:
            fm = fault_masks(n, cfg.faults, seed=s, publisher=pub)
            fmasks_np[s] = fm
            fmasks_dev[s] = {k: jnp.asarray(v) for k, v in fm.items()}
    if cfg.checkpoint_dir:
        for s in seeds:
            got = _try_resume(sim, cfg, fraction, s)
            if got is not None:
                state_by_seed[s], obs_by_seed[s] = got
                resumed.add(s)
    run_seeds = [s for s in seeds if s not in resumed]
    run_states = []
    for s in run_seeds:
        with span("trial/setup", **at[s]):
            _reset_trial(sim, s)
        if not adv.cold_boot:
            with span("trial/warmup", **at[s]):
                sim.warmup()
        if adv.eclipse:
            sim.state = eclipse_setup(sim.state, sim.arrays["conns"],
                                      cohorts[s][1], pub)
        run_states.append(sim.state)

    if run_seeds:
        # ONE span a stack: the dispatch of the window and the read of its
        # observable curves, in which the host waits for the warm-ups too
        with span("trial/window", fraction=fraction, seed=run_seeds[0],
                  vmapped=len(run_seeds) > 1, trials=len(run_seeds)):
            w_states, w_obs, w_ctrls = _attack_windows(
                sim, [cohorts[s][1] for s in run_seeds], run_states, adv,
                steps, trial_mesh=trial_mesh,
                faults=cfg.faults if faulted else None,
                fmasks=([fmasks_dev[s] for s in run_seeds]
                        if faulted else None),
                telemetry=tel)
        tally.vmapped_windows += len(run_seeds) > 1
        tally.window_heartbeats += steps
        for j, s in enumerate(run_seeds):
            state_by_seed[s] = w_states[j]
            obs_by_seed[s] = w_obs[j]
            if w_ctrls is not None:
                ctrl_by_seed[s] = w_ctrls[j]

    # the dial controller can mutate the graph arrays per trial; keep the
    # epoch graph to restore before the next trial's reset
    epoch_arrays = dict(sim.arrays)
    # cross-protocol setup: one per-seed Kademlia state built under the
    # SHARED attacker cohort (the same node ids attack both layers), plus
    # the pre-computed repair-pool shortlists for each window leg. Host
    # work + a few device lookups — deterministic per (seed, dht), so
    # checkpoint resume re-derives instead of snapshotting.
    dht_on = cfg.dht.enabled and cfg.recovery_heartbeats > 0
    steps1, steps2 = _dht_legs(cfg.dht, cfg.recovery_heartbeats)
    kad_ctx: dict[int, tuple] = {}
    if dht_on:
        for s in seeds:
            att_np, att_dev = cohorts[s]
            kstate, directory = build_attacked_dht(
                n, seed=s, dht=cfg.dht, attacker=att_np, victim=pub,
                stage=sim._stage, lat_ms=sim._lat)
            pfrac = rtable_poison_frac(kstate, att_np)
            pool_a = pool_b = None
            if steps1 > 0:
                pool_a, kstate = dht_repair_pool(
                    kstate, cfg.dht, sim._stage, sim._lat,
                    attacker=att_dev, directory=directory)
            if steps2 > 0:
                pool_b, kstate = dht_repair_pool(
                    kstate, cfg.dht, sim._stage, sim._lat,
                    attacker=att_dev, directory=directory, healed=True)
            kad_ctx[s] = (kstate, pool_a, pool_b, pfrac)
    recov = None
    # adaptive recoveries keep the per-seed path even under a trial_mesh:
    # the controller carry is per-trial state the sharded recovery
    # builders don't thread. Sharded and vmapped campaigns still agree —
    # both route armed recoveries through the same per-seed runner below.
    if (cfg.recovery_heartbeats > 0 and trial_mesh is not None
            and len(seeds) > 1 and not adaptive):
        if dht_on:
            recov = _dht_recovery_windows_sharded(
                sim, cfg, [state_by_seed[s] for s in seeds],
                [cohorts[s][1] for s in seeds],
                [kad_ctx[s][1] for s in seeds],
                [kad_ctx[s][2] for s in seeds], pub, trial_mesh,
                telemetry=tel)
        else:
            recov = _recovery_windows_sharded(
                sim, cfg, [state_by_seed[s] for s in seeds],
                [cohorts[s][1] for s in seeds], pub, trial_mesh,
                telemetry=tel)
    out = []
    for j, s in enumerate(seeds):
        att, att_j = cohorts[s]
        base = _ensure_baseline(sim, cache, s, tally)
        with span("trial/setup", **at[s]):
            _reset_trial(sim, s)
            sim.state = state_by_seed[s]
        part_ms = None
        if cfg.faults.partition:
            # sim-ms bounds of the partition window, anchored on the
            # post-window clock (works for resumed trials too): a window
            # extending past the attack window stays open for the publish
            # schedule below
            t_win0 = float(device_read(sim.state.t_ms)) - steps * hb_ms
            pws, pwe = cfg.faults.partition_window
            part_ms = (t_win0 + pws * hb_ms, t_win0 + pwe * hb_ms)
        if cfg.checkpoint_dir and s not in resumed:
            from .checkpoint import save_checkpoint

            os.makedirs(cfg.checkpoint_dir, exist_ok=True)
            ck, sc = _trial_ckpt(cfg, fraction, s)
            save_checkpoint(
                sim, ck, kad_state=kad_ctx[s][0] if dht_on else None)
            # obs sidecar: the engagement/recovery curves span the attack
            # window the checkpoint already paid for — without them a
            # resumed trial could restore the state but not its metrics
            tmp = sc + ".tmp"
            with open(tmp, "wb") as fh:
                np.savez_compressed(fh, **{
                    k: np.asarray(v) for k, v in obs_by_seed[s].items()})
            os.replace(tmp, sc)
        obs_j = obs_by_seed[s]
        recovery_time_ms = -1.0
        repaired = repair_totals(sim.state)
        if cfg.recovery_heartbeats > 0:
            # post-attack repair window. The checkpoint above snapshots the
            # post-window/pre-repair state against the EPOCH graph (whose
            # hash is the checkpoint identity) — recovery must come after.
            import jax

            if recov is not None:
                (st2, cn2, rv2, om2), robs = recov[j]
            else:
                # the one transition of a state's layout: the attack window
                # ran inert params over a state without repair leaves, the
                # recovery window arms repair over it
                rparams = cfg.repair.apply(sim.params)
                a = sim.arrays
                st2, cn2, rv2, om2 = (arm_repair(sim.state), a["conns"],
                                      a["rev"], a["out_mask"])
                if dht_on:
                    # two-leg window: attacked pool, then (optionally) healed
                    # pool resuming the same trial's dialed graph
                    _, pool_a, pool_b, _ = kad_ctx[s]
                    leg_obs = []
                    ctrl2 = ctrl_by_seed.get(s)
                    for leg_steps, pool in ((steps1, pool_a),
                                            (steps2, pool_b)):
                        if leg_steps <= 0:
                            continue
                        if adaptive:
                            # the controller carry crosses the heal edge: the
                            # attacker keeps its violation estimate while the
                            # DHT under it heals
                            carry, lobs = run_adaptive_recovery_heartbeats(
                                st2, cn2, rv2, om2, att_j, rparams, leg_steps,
                                adv=adv, ctrl=ctrl2, dht_pool=pool,
                                publisher=pub, telemetry=tel)
                            st2, ctrl2, cn2, rv2, om2 = carry[:5]
                        else:
                            carry, lobs = run_dht_recovery_heartbeats(
                                st2, cn2, rv2, om2, att_j, rparams, leg_steps,
                                dht_pool=pool, publisher=pub, telemetry=tel)
                            st2, cn2, rv2, om2 = carry[:4]
                        leg_obs.append(lobs)
                    robs = jax.tree_util.tree_map(
                        lambda *xs: np.concatenate(
                            [np.asarray(x) for x in xs], axis=0), *leg_obs)
                elif adaptive:
                    carry, robs = run_adaptive_recovery_heartbeats(
                        st2, cn2, rv2, om2, att_j, rparams,
                        cfg.recovery_heartbeats, adv=adv,
                        ctrl=ctrl_by_seed.get(s), publisher=pub, telemetry=tel)
                    st2, _, cn2, rv2, om2 = carry
                else:
                    (st2, cn2, rv2, om2), robs = run_recovery_heartbeats(
                        st2, cn2, rv2, om2, att_j, rparams,
                        cfg.recovery_heartbeats, publisher=pub, telemetry=tel)
            robs = device_read(robs)
            # and back: the publish schedule below runs sim.params, and on
            # a state of their layout it runs the program the baseline
            # trial compiled
            repaired = repair_totals(st2)
            sim.state = (disarm_repair(st2) if repair_inert(sim.params)
                         else st2)
            if not graph_static:
                sim.rebind_graph(cn2, rv2, om2)
            # concatenate the shared observables: engagement/recovery
            # rounds are counted over the whole attack+recovery timeline
            # (fault-only curves have no recovery leg — they keep their
            # attack-window length and indexing)
            obs_j = {k: (np.concatenate(
                [np.asarray(obs_j[k]), np.asarray(robs[k])])
                if k in robs else np.asarray(obs_j[k])) for k in obs_j}
            if dht_on:
                # host-side channel: constant over the window, but shaped
                # like a curve so sidecars/reports treat it uniformly
                obs_j["rtable_poison_frac"] = np.full(
                    cfg.recovery_heartbeats, kad_ctx[s][3], np.float32)
            rec_ok = ((robs["attacker_mesh_share"]
                       <= cfg.mesh_recovery_share)
                      & (robs["pub_honest_degree"] >= 1.0))
            hit = np.nonzero(rec_ok)[0]
            if hit.size:
                recovery_time_ms = float((hit[0] + 1) * hb_ms)
        with span("trial/publish", **at[s]):
            censor = censor_mask(att_j, sim.arrays["conns"])
            part_cross = None
            if part_ms is not None:
                # cross-cut mask over the CURRENT conns (the repair window
                # may have extended the graph)
                part_cross = partition_edge_mask(
                    fmasks_dev[s]["side"], sim.arrays["conns"])
            records = _publish_schedule(sim, censor=censor, attacker=att_j,
                                        adv=adv, cross=part_cross,
                                        partition_ms=part_ms)
        with span("trial/metrics", **at[s]):
            honest = ~att
            cov, p50, p99 = _delivery_metrics(records, honest)
            heal_time_ms = -1.0
            reconv_hb = -1
            cov_part = -1.0
            if cfg.faults.partition:
                pws, pwe = cfg.faults.partition_window
                curve = np.asarray(obs_j.get("cross_mesh_edges", ()))
                if curve.size > pwe:
                    hit = np.nonzero(curve[pwe:] > 0)[0]
                    if hit.size:
                        heal_time_ms = float((hit[0] + 1) * hb_ms)
                side_np = fmasks_np[s]["side"]
                same_side = side_np == side_np[pub]
                cov_part = float((same_side & honest).sum()
                                 / max(int(honest.sum()), 1))
            if cfg.faults.crash:
                cwe = cfg.faults.crash_window[1]
                curve = np.asarray(obs_j.get("restarted_mean_degree", ()))
                if curve.size > cwe:
                    hit = np.nonzero(curve[cwe:] >= sim.params.d_low)[0]
                    if hit.size:
                        reconv_hb = int(hit[0] + 1)
            engaged, gf_final, recovery, share_final = _obs_metrics(
                obs_j, cfg.mesh_recovery_share)
            tally.publishes += len(records)
            tally.mesh_share_peaks.append(
                float(np.max(obs_j["attacker_mesh_share"])))
            # flight-recorder curve milestones over the concatenated
            # attack+recovery timeline (the tel_* channels ride both windows)
            cov90_hb = -1
            score_cross_hb = -1
            tel_cov = np.asarray(obs_j.get("tel_mesh_coverage", ()))
            if tel_cov.size:
                cov90_hb = _first_round(tel_cov, lambda c: c >= 0.9)
            tel_q = np.asarray(obs_j.get("tel_score_q", ()))
            if tel_q.size:
                med = tel_q[:, tel_q.shape[1] // 2]
                thr = float(sim.params.graylist_threshold)
                score_cross_hb = _first_round(med, lambda c: c < thr)
            # final honest-side view of attacker edges (post-publish: includes
            # the censorship penalties the window could not see). Read the
            # CURRENT conns — the repair window may have extended the graph.
            cn_now = device_read(sim.arrays["conns"])
            sc = np.asarray(device_read(sim.state.score(sim.params)),
                            dtype=np.float64)
            att_edge = (cn_now >= 0) & att[np.clip(cn_now, 0, None)]
            h_att = att_edge & honest[:, None]
            score_final = float(sc[h_att].mean()) if h_att.any() else 0.0
            out.append(TrialResult(
                scenario=cfg.scenario, fraction=fraction, seed=s,
                attackers=int(att.sum()),
                honest_coverage=cov, benign_coverage=base["coverage"],
                latency_p50_ms=p50, latency_p99_ms=p99,
                benign_p50_ms=base["p50"],
                latency_inflation=(p50 / base["p50"]
                                   if base["p50"] > 0 and math.isfinite(p50)
                                   else math.inf),
                hb_to_graylist=engaged, hb_budget=budget,
                graylisted_frac_final=gf_final, mesh_recovery_hb=recovery,
                attacker_mesh_share_final=share_final,
                attacker_score_final=score_final,
                wall_s=(time.time() - t0) / len(seeds),
                mesh_evictions_total=repaired["evictions"],
                px_grafts_total=repaired["px_grafts"],
                redials_total=repaired["redials"],
                recovery_time_ms=recovery_time_ms,
                bytes_tx_total=float(
                    device_read(sim.state.bytes_tx).sum()),
                heal_time_ms=heal_time_ms,
                post_churn_reconvergence_hb=reconv_hb,
                coverage_under_partition=cov_part,
                coverage90_hb=cov90_hb,
                score_cross_hb=score_cross_hb,
                rtable_poison_frac=(kad_ctx[s][3] if dht_on else -1.0),
            ))
        if cfg.recovery_heartbeats > 0 and not graph_static:
            # restore the epoch graph: the next trial (and _reset_trial's
            # valid_edge refresh) must start from the built topology
            sim.rebind_graph(epoch_arrays["conns"], epoch_arrays["rev"],
                             epoch_arrays["out_mask"])
    return out


def run_campaign(cfg: CampaignConfig, mesh=None,
                 trial_mesh=None, dcn=None) -> CampaignResult:
    """Execute the sweep: every (fraction, seed) cell of the campaign grid.

    `mesh`: optional 1-D jax.sharding.Mesh over the PEER axis, threaded to
    the Simulator (row-sharded state + shard_map dissemination); peer-sharded
    runs keep trials sequential so placement stays row-wise.

    `trial_mesh`: optional 2-D parallel/sharding.make_trial_mesh grid —
    each device group runs its slice of a fraction's seed column
    concurrently AND partitions each trial's peer rows over its peer
    submesh (sharded_attack_window / sharded_faulted_window /
    sharded_recovery_window), replacing the vmapped single-device stack.
    Mutually exclusive with `mesh`: the trial grid already owns every
    device, including the peer axis inside each group.

    `dcn`: optional 3-D parallel/sharding.make_dcn_mesh grid (or True to
    build the default one) — multi-process orchestration. Each process runs
    this same function on its seed slice over its OWN 2-D ICI submesh, then
    the ranks merge into one canonical CampaignResult (see
    _run_campaign_dcn). Owns the whole device grid: mutually exclusive with
    both `mesh` and `trial_mesh`."""
    if dcn is not None:
        if mesh is not None or trial_mesh is not None:
            raise ValueError(
                "dcn owns the full dcn x trials x peers grid; "
                "drop mesh/trial_mesh")
        return _run_campaign_dcn(cfg, dcn)
    if mesh is not None and trial_mesh is not None:
        raise ValueError(
            "pass either mesh (peer-axis sharding) or trial_mesh "
            "(trial-axis sharding), not both")
    cfg.validate()
    adv = cfg.adversary_params()
    t0 = time.time()
    reads0 = device_reads()
    # the ONE network every trial shares, under the names `cmd_run` gives
    # the two halves of its build
    with span("run/topology"):
        from ..config.topology import Topology

        topology = Topology.build(cfg.experiment.topo)
    with span("run/simulator_init"):
        sim = Simulator(cfg.experiment, topology=topology, mesh=mesh)
    budget = heartbeats_to_graylist(adv, sim.params)
    if ((adv.graft_flood or adv.ihave_spam or adv.iwant_spam)
            and not adv.identity_rotation
            and not adv.adaptive.enabled
            and any(f > 0 for f in cfg.fractions) and math.isinf(budget)):
        # identity_rotation (and slow_peer_mimicry, which never sets these
        # flags) is exempt: an inf budget there IS the scenario's finding —
        # the rotation period defeats the accrual — not a config error.
        # The adaptive duty cycle joins that list: its inf budget says the
        # throttled attacker never crosses the graylist threshold, which
        # is exactly what the campaign is armed to measure
        raise ValueError(
            "score defense cannot engage under this config "
            "(heartbeats_to_graylist is inf): raise |slow_peer_penalty_weight|"
            ", lower |graylist_threshold|, or raise the penalty/decay — "
            "attack_gossipsub() is the armed default")
    cache: dict[int, dict] = {}
    trials: list[TrialResult] = []
    tally = _Tally()
    sup = cfg.supervisor
    injector = _FailureInjector(sup.inject_failures)
    quarantined: list[dict] = []
    retries_total = 0
    # a failed attempt may die mid-recovery with a dialed graph bound;
    # restore the epoch graph before the retry re-resets the trial
    graph_can_mutate = (cfg.recovery_heartbeats > 0
                        and (cfg.repair.px or cfg.repair.redial))
    epoch = dict(sim.arrays) if graph_can_mutate else None

    def _on_fail():
        if epoch is not None:
            sim.rebind_graph(epoch["conns"], epoch["rev"], epoch["out_mask"])

    def _cell(f: float, ss: list[int]) -> list[TrialResult]:
        if f == 0.0:
            return [_benign_trial(sim, cfg, s, cache, budget, tally)
                    for s in ss]
        if trial_mesh is not None and cfg.vmap_trials and len(ss) > 1:
            return _attacked_trials(sim, cfg, f, ss, cache, budget, tally,
                                    trial_mesh=trial_mesh)
        if cfg.vmap_trials and len(ss) > 1 and mesh is None:
            return _attacked_trials(sim, cfg, f, ss, cache, budget, tally)
        out: list[TrialResult] = []
        for s in ss:
            out.extend(_attacked_trials(sim, cfg, f, [s], cache, budget,
                                        tally))
        return out

    def _quarantine(f: float, ss: list[int], err) -> None:
        quarantined.append({
            "fraction": f, "seeds": list(ss),
            "failures": sup.max_retries + 1,
            "error": repr(err)[:500] if err is not None else "unknown",
        })

    with span("run/campaign"):
        for f in cfg.fractions:
            seeds = list(cfg.seeds)
            res, used, err = _supervise(
                sup, injector, lambda f=f, ss=seeds: _cell(f, ss), _on_fail)
            retries_total += used
            if res is not None:
                trials.extend(res)
                continue
            if len(seeds) == 1:
                _quarantine(f, seeds, err)
                continue
            # the batch is poisoned — isolate per seed so siblings survive
            # (checkpointed seeds resume instead of recomputing their
            # windows)
            for s in seeds:
                res1, used1, err1 = _supervise(
                    sup, injector, lambda f=f, s=s: _cell(f, [s]), _on_fail)
                retries_total += used1
                if res1 is not None:
                    trials.extend(res1)
                else:
                    _quarantine(f, [s], err1)
    conformance = _campaign_conformance(cfg, adv) if cfg.conformance else None
    counted = _campaign_counters(trials, tally, budget,
                                 device_reads() - reads0)
    # one zero-length annotation a campaign, for a reader of the profile
    # (a number that is not finite, an infinite budget, reads -1 there)
    counters("attack/counters", **{
        k: -1.0 if v is None else v
        for k, v in sanitize_nonfinite(counted).items()})
    return CampaignResult(
        scenario=cfg.scenario,
        network_size=sim.params.n,
        trials=trials,
        hb_budget=budget,
        wall_s=time.time() - t0,
        degraded=bool(quarantined) or retries_total > 0,
        quarantined_trials=quarantined,
        retries_total=retries_total,
        conformance=conformance,
        counters=counted,
    )


# ----------------------------------------------------------------- DCN engine


DCN_RANK_FORMAT = 1
DCN_MERGED_BASENAME = "dcn_merged.json"
# ceiling on how long one rank waits for its siblings' result files before
# declaring the group dead (generous: covers a sibling paying full compile
# while this rank rode the persistent cache)
_DCN_MERGE_TIMEOUT_S = float(os.environ.get("DCN_MERGE_TIMEOUT_S", "3600"))


def _dcn_rank_path(cfg: CampaignConfig, rank: int) -> str:
    return os.path.join(cfg.checkpoint_dir, f"dcn_rank{rank}.trials.json")


def merge_dcn_rank_results(cfg: CampaignConfig, payloads: list[dict],
                           wall_s: float | None = None) -> CampaignResult:
    """Fold per-rank DCN payloads into ONE canonical CampaignResult.

    Trials are re-ordered into the single-process sweep order — fractions
    in cfg.fractions order, seeds in cfg.seeds order inside each fraction —
    so the merged observables are comparable field-for-field with a
    single-process nested campaign on the same grid. Validates the rank
    set is contiguous from 0 and that every seed in cfg.seeds is claimed by
    exactly one rank (the round-robin slice invariant); a violated claim
    means two ranks ran the same cell or a rank file is stale, and a merge
    over it would silently double- or drop-count trials."""
    ranks = sorted(int(p["rank"]) for p in payloads)
    if ranks != list(range(len(payloads))):
        raise ValueError(f"rank set {ranks} is not contiguous from 0")
    by_rank = {int(p["rank"]): p for p in payloads}
    claimed: dict[int, int] = {}
    for p in payloads:
        for s in p["seeds"]:
            if int(s) in claimed:
                raise ValueError(
                    f"seed {s} claimed by ranks {claimed[int(s)]} "
                    f"and {p['rank']} — stale or overlapping rank files")
            claimed[int(s)] = int(p["rank"])
    missing = [int(s) for s in cfg.seeds if int(s) not in claimed]
    if missing:
        raise ValueError(f"seeds {missing} claimed by no rank")
    by_cell: dict[tuple[float, int], dict] = {}
    for p in payloads:
        for t in p["trials"]:
            by_cell[(float(t["fraction"]), int(t["seed"]))] = t
    trials = [TrialResult(**by_cell[(float(f), int(s))])
              for f in cfg.fractions for s in cfg.seeds
              if (float(f), int(s)) in by_cell]
    r0 = by_rank[0]
    hb = r0["hb_budget"]
    return CampaignResult(
        scenario=r0["scenario"],
        network_size=int(r0["network_size"]),
        trials=trials,
        # the sanitizer nulled a legitimately-infinite budget on write;
        # restore it so the merged artifact round-trips identically
        hb_budget=math.inf if hb is None else float(hb),
        wall_s=float(wall_s) if wall_s is not None
        else max(float(p["wall_s"]) for p in payloads),
        degraded=any(p["degraded"] for p in payloads),
        quarantined_trials=[q for p in payloads
                            for q in p["quarantined_trials"]],
        retries_total=sum(int(p["retries_total"]) for p in payloads),
        conformance=r0.get("conformance"),
    )


def _run_campaign_dcn(cfg: CampaignConfig, dcn_mesh) -> CampaignResult:
    """Multi-process campaign over a dcn x trials x peers grid.

    Every process executes the SAME code path: slice the seed column
    round-robin (seeds[rank::nproc]), run the ordinary single-process
    campaign on this process's 2-D ICI submesh (supervisor retries,
    checkpoints and quarantine all stay process-local — no SPMD lockstep
    to deadlock when one rank retries), publish the slice's results as a
    strict-JSON rank file, then meet at a single DCN all-reduce. The
    collective carries the few global aggregates (trial/retry counts,
    max wall-clock) AND doubles as the barrier that makes every rank's
    file visible before any rank merges. All ranks return the same merged
    CampaignResult; rank 0 additionally writes the merged strict-JSON
    artifact next to the rank files. Requires cfg.checkpoint_dir on a
    filesystem shared by all processes (trivially true for the
    single-host multi-process launches the engine targets)."""
    import jax

    from ..parallel.sharding import (
        DCN_AXIS,
        dcn_allreduce,
        local_trial_submesh,
        make_dcn_mesh,
    )

    if dcn_mesh is True:
        dcn_mesh = make_dcn_mesh()
    if DCN_AXIS not in dcn_mesh.axis_names:
        raise ValueError(
            "dcn expects a 3-level make_dcn_mesh grid (leading 'dcn' axis)")
    if not cfg.checkpoint_dir:
        raise ValueError(
            "DCN campaigns need cfg.checkpoint_dir: the rank-0 merge rides "
            "per-process rank files (and trial resume is the whole point "
            "of process-local supervision)")
    nproc = int(dcn_mesh.shape[DCN_AXIS])
    if nproc != jax.process_count():
        raise ValueError(
            f"dcn axis size {nproc} != process_count {jax.process_count()} "
            "— one DCN block per process is the placement contract")
    rank = jax.process_index()
    if len(cfg.seeds) < nproc:
        raise ValueError(
            f"{len(cfg.seeds)} seeds over {nproc} processes leaves a rank "
            "idle; give every process at least one seed")
    os.makedirs(cfg.checkpoint_dir, exist_ok=True)
    # start fence: every rank clears ITS OWN stale rank file, then meets at
    # a throwaway all-reduce. After it, no file from a previous run exists,
    # which is what licenses the cheap existence-poll below
    try:
        os.remove(_dcn_rank_path(cfg, rank))
    except FileNotFoundError:
        pass
    dcn_allreduce(np.zeros(1, dtype=np.float32), op="sum")
    t0 = time.time()
    local_mesh = local_trial_submesh(dcn_mesh)
    local_seeds = tuple(cfg.seeds)[rank::nproc]
    # conformance is a small-N CPU certificate independent of the seed
    # slice — run it once, on rank 0, not nproc times
    local_cfg = replace(cfg, seeds=local_seeds,
                        conformance=cfg.conformance and rank == 0)
    local = run_campaign(local_cfg, trial_mesh=local_mesh)

    payload = {
        "format_version": DCN_RANK_FORMAT,
        "rank": int(rank),
        "nproc": int(nproc),
        "seeds": [int(s) for s in local_seeds],
        "scenario": local.scenario,
        "network_size": int(local.network_size),
        "hb_budget": local.hb_budget,
        "wall_s": local.wall_s,
        "degraded": bool(local.degraded),
        "retries_total": int(local.retries_total),
        "quarantined_trials": list(local.quarantined_trials),
        "conformance": local.conformance,
        "trials": [t.to_dict() for t in local.trials],
    }
    path = _dcn_rank_path(cfg, rank)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(sanitize_nonfinite(payload), f, allow_nan=False,
                  sort_keys=True)
    os.replace(tmp, path)

    # sleep-poll until every sibling's rank file exists BEFORE entering the
    # collective: a gloo all-reduce spin-waits for stragglers, which on an
    # oversubscribed host steals the very cores the straggler needs (and a
    # sweep longer than the collective timeout would kill the group). File
    # existence is completion — os.replace is atomic and the start fence
    # removed every stale file
    deadline = time.time() + _DCN_MERGE_TIMEOUT_S
    while not all(os.path.exists(_dcn_rank_path(cfg, r))
                  for r in range(nproc)):
        if time.time() > deadline:
            missing = [r for r in range(nproc)
                       if not os.path.exists(_dcn_rank_path(cfg, r))]
            raise RuntimeError(
                f"rank {rank}: ranks {missing} produced no result within "
                f"{_DCN_MERGE_TIMEOUT_S:.0f}s — sibling process dead?")
        time.sleep(0.05)

    # the ONLY cross-process collective of the whole campaign: sum the
    # global aggregates, max the wall-clock — and, as a side effect, fence
    # every rank's os.replace above behind every rank's reads below
    agg = dcn_allreduce(
        np.array([len(local.trials), local.retries_total], dtype=np.float32),
        op="sum")
    wall = float(dcn_allreduce(
        np.array([time.time() - t0], dtype=np.float32), op="max")[0])

    payloads = []
    for r in range(nproc):
        with open(_dcn_rank_path(cfg, r)) as f:
            payloads.append(json.load(f))
    merged = merge_dcn_rank_results(cfg, payloads, wall_s=wall)
    # cross-check the file-based merge against the collective's counters:
    # a mismatch means a rank file from a previous run leaked in
    if (len(merged.trials), merged.retries_total) != (int(agg[0]),
                                                      int(agg[1])):
        raise RuntimeError(
            f"merge saw {len(merged.trials)} trials / "
            f"{merged.retries_total} retries but the DCN all-reduce "
            f"counted {int(agg[0])} / {int(agg[1])} — stale rank files?")
    if rank == 0:
        out = os.path.join(cfg.checkpoint_dir, DCN_MERGED_BASENAME)
        tmp = f"{out}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(merged.to_dict(), f, allow_nan=False, sort_keys=True,
                      indent=2)
        os.replace(tmp, out)
    return merged


def _campaign_conformance(cfg: CampaignConfig, adv: AdversaryParams) -> dict:
    """Small-N conformance certificate for the campaign's scenario
    (CampaignConfig.conformance): the scenario differential, plus the
    adaptive-controller and fault-family differentials when the campaign
    arms them. Cost is one N=48 instance per entry — noise next to any
    sweep — and the result rides the summary artifact via to_dict()."""
    from ..analysis.conformance import (certificate_entry, load_waivers,
                                        run_adaptive_differential,
                                        run_faults_differential,
                                        run_scenario_differential)

    waivers = load_waivers()
    meta = dict(seeds=[0], n=48, steps=8)
    entries = [certificate_entry(
        cfg.scenario, run_scenario_differential(cfg.scenario), waivers,
        **meta)]
    if adv.adaptive.enabled:
        entries.append(certificate_entry(
            "adaptive", run_adaptive_differential(cfg.scenario), waivers,
            **meta))
    if cfg.faults.enabled:
        entries.append(certificate_entry(
            "faults", run_faults_differential(), waivers, **meta))
    sim_bugs = sum(e["sim_bugs"] for e in entries)
    return {"entries": entries, "sim_bugs": sim_bugs,
            "clean": sim_bugs == 0}


# ---------------------------------------------------- defense Pareto sweep

# objective -> optimization direction, in artifact column order. Coverage
# is what the defense exists to protect; bandwidth is what raising the
# mesh degree spends to protect it; recovery time is how long the adaptive
# attacker keeps the mesh compromised. No scalarization — the sweep
# reports the non-dominated set and lets the operator pick the trade.
DEFENSE_OBJECTIVES = {
    "coverage": "max",
    "bandwidth_bytes": "min",
    "recovery_time_ms": "min",
}


def pareto_front(values, directions) -> np.ndarray:
    """Boolean non-domination mask over the rows of a (P, K) objective
    matrix. `directions` gives one "max"/"min" per column. Row j is
    dominated when some row i is at least as good on every objective and
    strictly better on at least one. Vectorized O(P^2 K) — the test suite
    pins it against the literal pairwise loop."""
    v = np.asarray(values, dtype=np.float64)
    if v.ndim != 2 or v.shape[1] != len(directions):
        raise ValueError(
            f"values must be (P, {len(directions)}), got {v.shape}")
    v = v.copy()
    for k, d in enumerate(directions):
        if d == "min":
            v[:, k] = -v[:, k]
        elif d != "max":
            raise ValueError(f"direction {d!r} not in ('max', 'min')")
    ge = (v[:, None, :] >= v[None, :, :]).all(-1)  # ge[i, j]: i >= j all-k
    gt = (v[:, None, :] > v[None, :, :]).any(-1)   # gt[i, j]: i > j some-k
    return ~(ge & gt).any(axis=0)


def _sweep_knobs(gs: GossipSubParams) -> tuple:
    return (gs.d_low, gs.d, gs.d_high, gs.slow_peer_penalty_weight)


def run_defense_sweep(
    cfg: CampaignConfig,
    degree_grid: tuple = ((4, 6, 8), (6, 8, 12), (8, 12, 16)),
    weight_grid: tuple = (-5.0, -10.0, -20.0),
    trial_mesh=None,
) -> dict:
    """Race a grid of defense configurations against the ADAPTIVE attacker
    and report the coverage / bandwidth / recovery-time Pareto front.

    Each grid point is `cfg` with the mesh-degree triple (d_low, d,
    d_high) and the slow-peer penalty weight swapped in (d_score/d_out/
    d_lazy re-derive from their bases); the point runs a full
    run_campaign and aggregates its ATTACKED trials:

      coverage          mean honest delivery fraction
      bandwidth_bytes   mean network-wide bytes transmitted per trial —
                        the cost axis a fatter mesh pays even when benign
      recovery_time_ms  mean time until the repaired mesh sheds the
                        cohort, with unrecovered trials charged the full
                        window ((recovery_heartbeats + 1) * hb_ms) so a
                        config that never recovers cannot look cheap

    The base config's own knobs always join the grid (is_default /
    default_index), so `beats_default` — grid points that dominate the
    default — is well-defined. Returns a strict-JSON-safe artifact dict:
    `configs` rows, `pareto` (non-dominated row indices), and the
    objective directions; per-point checkpointing is disabled because
    every point would collide on the same (scenario, fraction, seed)
    keys."""
    adv = cfg.adversary_params()
    if not adv.adaptive.enabled:
        raise ValueError(
            "run_defense_sweep races the ADAPTIVE attacker: arm "
            "cfg.adversary.adaptive (a static-cohort Pareto sweep would "
            "understate every defense)")
    if cfg.recovery_heartbeats < 1:
        raise ValueError(
            "run_defense_sweep needs recovery_heartbeats >= 1: "
            "recovery_time_ms is a sweep objective")
    if not any(f > 0 for f in cfg.fractions):
        raise ValueError("run_defense_sweep needs an attacked fraction")
    base_gs = cfg.experiment.gossipsub
    points = [(dl, d, dh, w)
              for (dl, d, dh) in degree_grid for w in weight_grid]
    default_knobs = _sweep_knobs(base_gs)
    if default_knobs not in points:
        points.insert(0, default_knobs)
    default_index = points.index(default_knobs)
    t0 = time.time()
    rows = []
    for dl, d, dh, w in points:
        gs = replace(base_gs, d_low=dl, d=d, d_high=dh,
                     slow_peer_penalty_weight=w,
                     d_score=None, d_out=None, d_lazy=None)
        cfg_p = replace(
            cfg,
            experiment=replace(cfg.experiment, gossipsub=gs),
            checkpoint_dir=None,
        )
        res = run_campaign(cfg_p, trial_mesh=trial_mesh)
        atk = [t for t in res.trials if t.fraction > 0.0]
        hb_ms = gs.heartbeat_ms
        cap_ms = float((cfg.recovery_heartbeats + 1) * hb_ms)
        rec = [t.recovery_time_ms if t.recovery_time_ms >= 0.0 else cap_ms
               for t in atk]
        rows.append({
            "d_low": dl, "d": d, "d_high": dh,
            "slow_peer_penalty_weight": w,
            "is_default": (dl, d, dh, w) == default_knobs,
            "coverage": float(np.mean([t.honest_coverage for t in atk])),
            "bandwidth_bytes": float(np.mean(
                [t.bytes_tx_total for t in atk])),
            "recovery_time_ms": float(np.mean(rec)),
            "recovered_frac": float(np.mean(
                [t.recovery_time_ms >= 0.0 for t in atk])),
            "trials": len(atk),
            "degraded": res.degraded,
        })
    dirs = tuple(DEFENSE_OBJECTIVES.values())
    vals = np.array([[r[k] for k in DEFENSE_OBJECTIVES] for r in rows])
    front = pareto_front(vals, dirs)
    # beats_default: at least as good on every objective, better on one —
    # the acceptance finding is that this set is non-empty on real sweeps
    sign = np.array([-1.0 if d == "min" else 1.0 for d in dirs])
    sv = vals * sign
    dv = sv[default_index]
    beats = [i for i in range(len(rows))
             if i != default_index
             and bool((sv[i] >= dv).all() and (sv[i] > dv).any())]
    return sanitize_nonfinite({
        "scenario": cfg.scenario,
        "network_size": cfg.experiment.topo.network_size,
        "fractions": [f for f in cfg.fractions if f > 0.0],
        "seeds": list(cfg.seeds),
        "recovery_heartbeats": cfg.recovery_heartbeats,
        "objectives": dict(DEFENSE_OBJECTIVES),
        "configs": rows,
        "pareto": [i for i in range(len(rows)) if bool(front[i])],
        "default_index": default_index,
        "beats_default": beats,
        "wall_s": time.time() - t0,
    })


# ------------------------------------------------------- protocol arena

# objective -> direction, in artifact column order. Coverage and the two
# latency quantiles are what a dissemination protocol exists to deliver;
# bandwidth_bytes is what GossipSub's mesh redundancy spends to deliver
# them (the axis Topiary-style trees exist to shrink, arXiv:2312.06800);
# recovery_time_ms is how fast the protocol sheds the adaptive cohort
# once compromised. The win matrix scores every objective per scenario —
# no scalarization: the artifact reports who wins WHAT, not who "wins".
ARENA_OBJECTIVES = {
    "coverage": "max",
    "bandwidth_bytes": "min",
    "latency_p50_ms": "min",
    "latency_p99_ms": "min",
    "recovery_time_ms": "min",
}

# relative tolerance under which an objective cell scores a tie: means
# this close are sampling noise at arena seed counts, not a win
ARENA_REL_TOL = 1e-3


def sharded_episub_window(stacked, ctrls, shared: dict, attackers, params,
                          ep, adv, steps: int, trial_mesh,
                          local_trials: int, telemetry=None):
    """The episub arena window on the 2-D trials x peers grid: the
    EpisubCtrl carry's leaves are (T, N) peer-major exactly like the
    attacker masks, so the tree controller nested-shards through the same
    shape rule as the state (parallel/sharding.nested_batch_shardings)
    and hop relaxation / re-parenting run peer-partitioned inside each
    trial group. Mirrors sharded_attack_window's nested branch; there is
    no legacy trial-only branch because this window postdates the PR-5
    formulation (tests/test_episub.py pins sharded == vmapped on both
    grid orientations instead)."""
    import jax

    from ..ops.episub import run_episub_adaptive_heartbeats

    bf = _nested_batch_factor(trial_mesh, local_trials)

    def body(st, ct, at, cn, rv, om):
        def one(s, c, a):
            return run_episub_adaptive_heartbeats(
                s, c, cn, rv, om, a, params, ep, adv, steps,
                batch_factor=bf, telemetry=telemetry)

        return jax.vmap(one)(st, ct, at)

    n_rows = shared["conns"].shape[0]
    return _run_nested_window(body, trial_mesh, n_rows,
                              (stacked, ctrls, attackers), shared)


def _episub_windows(sim: Simulator, ep, attackers, states, ctrls, adv,
                    steps: int, trial_mesh=None, faults=None, fmasks=None,
                    telemetry=None):
    """Run the episub attack window for a batch of trials: the
    ctrl-threading mirror of _attack_windows. Returns (states, ctrls,
    obs_dicts) in input order; an armed adv.adaptive widens the runner
    carry with the attacker controller, which the arena drops — it reads
    protocol state only, and unlike run_campaign it has no recovery legs
    to thread the controller into. Fault-armed cells run vmapped (no
    sharded fault variant: arena fault cells are smoke-scale); plain
    windows ride the nested grid when trial_mesh is given."""
    import jax
    import jax.numpy as jnp

    from ..ops.episub import (run_episub_adaptive_heartbeats,
                              run_episub_faulted_heartbeats)

    tree = jax.tree_util.tree_map
    a = sim.arrays
    adaptive = adv.adaptive.enabled
    faulted = faults is not None and faults.enabled
    s_count = len(states)

    def _unpack(out):
        # (state, ctrl[, actrl]) -> (state, ctrl): the arena drops actrl
        return (out[0], out[1]) if adaptive else out

    if faulted:
        stacked = tree(lambda *xs: jnp.stack(xs), *states)
        ctk = tree(lambda *xs: jnp.stack(xs), *ctrls)
        att = jnp.stack(attackers)
        crs = jnp.stack([m["crash"] for m in fmasks])
        sds = jnp.stack([m["side"] for m in fmasks])
        sps = jnp.stack([m["spike"] for m in fmasks])

        def one_f(st, ct, at, cr, sd, sp):
            return run_episub_faulted_heartbeats(
                st, ct, a["conns"], a["rev"], a["out_mask"], at,
                sim.params, ep, adv, faults, cr, sd, sp, steps,
                batch_factor=s_count, telemetry=telemetry)

        out, obs = jax.vmap(one_f)(stacked, ctk, att, crs, sds, sps)
        o_states, o_ctrls = _unpack(out)
        obs_np = tree(np.asarray, obs)
        return (
            [tree(lambda x, j=j: x[j], o_states) for j in range(s_count)],
            [tree(lambda x, j=j: x[j], o_ctrls) for j in range(s_count)],
            [{k: v[j] for k, v in obs_np.items()} for j in range(s_count)],
        )
    if trial_mesh is not None and s_count > 1:
        from ..parallel.sharding import place_trial_batch

        states, attackers, ctrls, local = _pad_to_groups(
            states, attackers, trial_mesh, extras=ctrls)
        stacked = tree(lambda *xs: jnp.stack(xs), *states)
        ctk = tree(lambda *xs: jnp.stack(xs), *ctrls)
        att = jnp.stack(attackers)
        (stacked, ctk, att), shared = place_trial_batch(
            (stacked, ctk, att), a, trial_mesh, n_rows=sim.params.n)
        out, obs = sharded_episub_window(
            stacked, ctk, shared, att, sim.params, ep, adv, steps,
            trial_mesh, local, telemetry=telemetry)
        o_states, o_ctrls = _unpack(out)
        obs_np = tree(np.asarray, obs)
        return ([_unstack_trial(tree, o_states, j) for j in range(s_count)],
                [_unstack_trial(tree, o_ctrls, j) for j in range(s_count)],
                [{k: v[j] for k, v in obs_np.items()}
                 for j in range(s_count)])
    if s_count == 1:
        out, obs = run_episub_adaptive_heartbeats(
            states[0], ctrls[0], a["conns"], a["rev"], a["out_mask"],
            attackers[0], sim.params, ep, adv, steps, telemetry=telemetry)
        st, ct = _unpack(out)
        return [st], [ct], [tree(np.asarray, obs)]
    stacked = tree(lambda *xs: jnp.stack(xs), *states)
    ctk = tree(lambda *xs: jnp.stack(xs), *ctrls)
    att = jnp.stack(attackers)

    def one(st, ct, at):
        return run_episub_adaptive_heartbeats(
            st, ct, a["conns"], a["rev"], a["out_mask"], at, sim.params,
            ep, adv, steps, batch_factor=s_count, telemetry=telemetry)

    out, obs = jax.vmap(one)(stacked, ctk, att)
    o_states, o_ctrls = _unpack(out)
    obs_np = tree(np.asarray, obs)
    return (
        [tree(lambda x, j=j: x[j], o_states) for j in range(s_count)],
        [tree(lambda x, j=j: x[j], o_ctrls) for j in range(s_count)],
        [{k: v[j] for k, v in obs_np.items()} for j in range(s_count)],
    )


def _episub_publish(sim: Simulator, ctrl, ep, censor=None, attacker=None,
                    adv=None, cross=None, partition_ms=None):
    """_publish_schedule with the inter-message advance stepping EPISUB
    heartbeats: Simulator.advance would re-form the GossipSub mesh
    between publishes, silently swapping protocols mid-trial. The local
    carry keeps Simulator.advance's drain semantics (partial heartbeats
    accumulate across messages); sim.publish itself is protocol-neutral —
    dissemination, censorship masking, and byte accounting all ride
    whatever mesh_mask the protocol wrote. Returns (records, ctrl)."""
    from ..ops.episub import run_episub_heartbeats
    from .simulator import drain_heartbeat_carry

    exp = sim.cfg
    n = exp.topo.network_size
    delay_ms = exp.topo.delay_seconds * 1000.0
    pub = exp.publisher_id % n
    a = sim.arrays
    carry_ms = 0.0
    for i in range(exp.topo.messages):
        if i > 0:
            hb_steps, carry_ms = drain_heartbeat_carry(
                carry_ms, delay_ms, sim.params.heartbeat_ms)
            if hb_steps > 0:
                sim.state, ctrl = run_episub_heartbeats(
                    sim.state, ctrl, a["conns"], a["rev"], a["out_mask"],
                    sim.params, ep, hb_steps)
        eff = censor
        if cross is not None and partition_ms is not None:
            t_now = float(device_read(sim.state.t_ms))
            if partition_ms[0] <= t_now < partition_ms[1]:
                eff = cross if censor is None else (censor | cross)
        rec = sim.publish(pub, censor_edge=eff)
        if censor is not None:
            import jax.numpy as jnp

            sim.state = censorship_penalty_update(
                sim.state, a["conns"], a["rev"], attacker,
                jnp.asarray(rec.received), sim.params, adv)
        if exp.publisher_rotation:
            pub = (pub + 1) % n
    return sim.records, ctrl


def _cohort_sha(att: np.ndarray) -> str:
    """sha256 of the packed attacker-cohort bitmask — the per-cell
    identity the arena artifact records so a reader (and the paired-trial
    test) can verify both protocols faced the same node ids."""
    import hashlib

    return hashlib.sha256(
        np.packbits(np.asarray(att, dtype=bool)).tobytes()).hexdigest()


def _arena_recovery_ms(obs: dict, floor: float, hb_ms: float,
                       cap_ms: float) -> float:
    """Recovery time read off the attack-window attacker_mesh_share curve:
    0.0 when the share never exceeds the floor (never meaningfully
    compromised), first-return-below-floor after the peak otherwise, with
    unrecovered windows charged `cap_ms` so a protocol that never sheds
    the cohort cannot look cheap (run_defense_sweep's convention)."""
    share = np.asarray(obs["attacker_mesh_share"], dtype=np.float64)
    if share.size == 0 or share.max() <= floor:
        return 0.0
    peak = int(np.argmax(share))
    rel = _first_round(share[peak:], lambda c: c <= floor)
    return float((peak + rel) * hb_ms) if rel > 0 else cap_ms


def _arena_obs_extras(spec_observables, obs_j) -> dict:
    """Final-round values of the shared attack channels plus the
    protocol's declared extra observables (ProtocolSpec.observables) —
    the per-protocol color on each arena trial row."""
    out: dict = {}
    if obs_j is None:
        return out
    for k in ("graylisted_frac", "attacker_mesh_share") + tuple(
            spec_observables):
        if k in obs_j:
            v = np.asarray(obs_j[k], dtype=np.float64)
            if v.size:
                out[k + "_final"] = float(v[-1])
    return out


def run_arena_campaign(cfg: CampaignConfig, scenarios=None, ep=None,
                       trial_mesh=None) -> dict:
    """Head-to-head protocol arena: GossipSub and episub race on IDENTICAL
    inputs and the artifact scores who wins each objective per scenario.

    Pairing discipline per (scenario, seed) cell — the whole point:

      graph    ONE Simulator built once from the experiment seed; both
               protocols inherit the same conns/rev/out_mask (the
               artifact records the same graph sha256 the checkpoint
               subsystem hashes)
      cohort   attacker_cohort draws from (n, fraction, seed, graph)
               only — per-cell sha256 recorded; tests/test_arena.py pins
               cross-protocol equality
      faults   fault_masks(seed): the same crash/partition/spike cohorts
               thread both windows
      traffic  the experiment's injection schedule with flood_publish
               REQUIRED off — every publish rides mesh_mask, which is
               exactly the surface under test (GossipSub's mesh vs
               episub's tree), and the episub publish phase advances
               EPISUB heartbeats between messages (_episub_publish)

    "benign" is a reserved scenario name: fraction 0.0, plain heartbeat
    windows, no adversary — the bandwidth-floor row the arena bench gate
    reads. Attack scenarios REQUIRE the adaptive policy armed: the PR-13
    attacker is the referee both protocols face; a static-cohort race
    would understate both.

    The arena measures INTRINSIC resilience: no repair subsystem, no
    recovery window. recovery_time_ms is read off the attack-window
    attacker_mesh_share curve (GossipSub recovers by score-gated
    prune/evict, episub by graylisted re-parenting), with unrecovered
    windows charged the full window. Returns a strict-JSON-safe dict:
    per-trial rows, per-(scenario, protocol) aggregate rows, the win
    matrix, and the identity block."""
    import jax.numpy as jnp

    from ..ops.episub import (EpisubParams, init_episub_ctrl,
                              run_episub_heartbeats)
    from ..ops.protocol import get_protocol
    from .checkpoint import _graph_hash

    cfg.validate()
    adv0 = cfg.adversary_params()
    if cfg.experiment.gossipsub.flood_publish:
        raise ValueError(
            "the arena requires flood_publish=False: flood publish routes "
            "traffic around mesh_mask, the one surface the two protocols "
            "differ on — the race would measure nothing")
    fracs = [f for f in cfg.fractions if f > 0.0]
    if not fracs:
        raise ValueError(
            "the arena needs an attacked fraction (> 0); the benign row "
            "is the reserved 'benign' scenario, not a 0.0 fraction")
    fraction = fracs[0]
    if scenarios is None:
        scenarios = ("benign", cfg.scenario)
    scenarios = tuple(scenarios)
    if any(s != "benign" for s in scenarios) and not adv0.adaptive.enabled:
        raise ValueError(
            "arena attack scenarios require cfg.adversary.adaptive armed: "
            "the adaptive attacker is the referee both protocols face")
    protos = ("gossipsub", "episub")
    gspec, espec = get_protocol(protos[0]), get_protocol(protos[1])
    sim = Simulator(cfg.experiment)
    n = sim.params.n
    hb_ms = sim.params.heartbeat_ms
    pub = cfg.experiment.publisher_id % n
    conns_np = np.asarray(sim.graph.conns)
    warm_steps = int(cfg.experiment.warmup_s * 1000.0 // hb_ms)
    steps = cfg.attack_heartbeats
    cap_ms = float((steps + 1) * hb_ms)
    if ep is None:
        # the tree roots at the publisher: eager push follows the
        # dissemination direction the traffic schedule measures
        ep = EpisubParams(root=pub)
    tel = cfg.telemetry if cfg.telemetry.enabled else None
    seeds = list(cfg.seeds)
    faulted = cfg.faults.enabled
    t0 = time.time()
    trials: list[dict] = []
    cohort_shas: dict = {}

    for sc in scenarios:
        benign = sc == "benign"
        adv = (adv0 if benign or sc == cfg.scenario
               else replace(adv0, scenario=sc))
        cohorts = {}
        for s in seeds:
            att = (np.zeros(n, dtype=bool) if benign else attacker_cohort(
                n, fraction, seed=s, conns=conns_np, publisher=pub,
                eclipse=adv.eclipse))
            cohorts[s] = (att, jnp.asarray(att))
            cohort_shas.setdefault(sc, {})[str(s)] = _cohort_sha(att)
        fmasks = None
        if faulted and not benign:
            fmasks = {s: {k: jnp.asarray(v) for k, v in fault_masks(
                n, cfg.faults, seed=s, publisher=pub).items()}
                for s in seeds}
        a = sim.arrays

        def _finish(s, j, obs_j, spec_obs, records):
            att, _ = cohorts[s]
            honest = ~att
            cov, p50, p99 = _delivery_metrics(records, honest)
            rec_ms = (0.0 if obs_j is None else _arena_recovery_ms(
                obs_j, cfg.mesh_recovery_share, hb_ms, cap_ms))
            return {
                "seed": s, "attackers": int(att.sum()),
                "coverage": cov,
                "bandwidth_bytes": float(
                    np.asarray(sim.state.bytes_tx).sum()),
                "latency_p50_ms": p50, "latency_p99_ms": p99,
                "recovery_time_ms": rec_ms,
                "cohort_sha256": cohort_shas[sc][str(s)],
                **_arena_obs_extras(spec_obs, obs_j),
            }

        def _part_ctx(s):
            # still-open partition window folded into the publish masks,
            # same anchoring as _attacked_trials
            if not (faulted and not benign and cfg.faults.partition):
                return None, None
            t_win0 = float(np.asarray(sim.state.t_ms)) - steps * hb_ms
            pws, pwe = cfg.faults.partition_window
            part_ms = (t_win0 + pws * hb_ms, t_win0 + pwe * hb_ms)
            return partition_edge_mask(fmasks[s]["side"],
                                       a["conns"]), part_ms

        # ---- gossipsub side: registry-dispatched house runners
        g_states = []
        for s in seeds:
            _reset_trial(sim, s)
            sim.warmup()
            if not benign and adv.eclipse:
                sim.state = eclipse_setup(sim.state, a["conns"],
                                          cohorts[s][1], pub)
            g_states.append(sim.state)
        if benign:
            g_out = [gspec.run_heartbeats(
                st, a["conns"], a["rev"], a["out_mask"], sim.params, steps)
                for st in g_states]
            g_obs = [None] * len(seeds)
        else:
            g_out, g_obs, _ = _attack_windows(
                sim, [cohorts[s][1] for s in seeds], g_states, adv, steps,
                trial_mesh=trial_mesh,
                faults=cfg.faults if faulted else None,
                fmasks=[fmasks[s] for s in seeds] if faulted else None,
                telemetry=tel, protocol=protos[0])
        for j, s in enumerate(seeds):
            _reset_trial(sim, s)
            sim.state = g_out[j]
            cross, part_ms = _part_ctx(s)
            censor = (None if benign
                      else censor_mask(cohorts[s][1], a["conns"]))
            records = _publish_schedule(
                sim, censor=censor,
                attacker=None if benign else cohorts[s][1],
                adv=None if benign else adv, cross=cross,
                partition_ms=part_ms)
            trials.append({"scenario": sc, "protocol": protos[0],
                           **_finish(s, j, g_obs[j], gspec.observables,
                                     records)})

        # ---- episub side: same cells, same cohorts, same fault masks
        e_states, e_ctrls = [], []
        for s in seeds:
            _reset_trial(sim, s)
            ctrl = init_episub_ctrl(n)
            if warm_steps > 0:
                sim.state, ctrl = run_episub_heartbeats(
                    sim.state, ctrl, a["conns"], a["rev"], a["out_mask"],
                    sim.params, ep, warm_steps)
            if not benign and adv.eclipse:
                sim.state = eclipse_setup(sim.state, a["conns"],
                                          cohorts[s][1], pub)
            e_states.append(sim.state)
            e_ctrls.append(ctrl)
        if benign:
            e_out, e_cout, e_obs = [], [], [None] * len(seeds)
            for st, ct in zip(e_states, e_ctrls):
                st2, ct2 = run_episub_heartbeats(
                    st, ct, a["conns"], a["rev"], a["out_mask"],
                    sim.params, ep, steps)
                e_out.append(st2)
                e_cout.append(ct2)
        else:
            e_out, e_cout, e_obs = _episub_windows(
                sim, ep, [cohorts[s][1] for s in seeds], e_states, e_ctrls,
                adv, steps, trial_mesh=trial_mesh,
                faults=cfg.faults if faulted else None,
                fmasks=[fmasks[s] for s in seeds] if faulted else None,
                telemetry=tel)
        for j, s in enumerate(seeds):
            _reset_trial(sim, s)
            sim.state = e_out[j]
            cross, part_ms = _part_ctx(s)
            censor = (None if benign
                      else censor_mask(cohorts[s][1], a["conns"]))
            records, _ = _episub_publish(
                sim, e_cout[j], ep, censor=censor,
                attacker=None if benign else cohorts[s][1],
                adv=None if benign else adv, cross=cross,
                partition_ms=part_ms)
            trials.append({"scenario": sc, "protocol": protos[1],
                           **_finish(s, j, e_obs[j], espec.observables,
                                     records)})

    # ---- aggregates + win matrix
    rows = []
    for sc in scenarios:
        for p in protos:
            cell = [t for t in trials
                    if t["scenario"] == sc and t["protocol"] == p]
            rows.append({
                "scenario": sc, "protocol": p, "trials": len(cell),
                **{k: float(np.mean([t[k] for t in cell]))
                   for k in ARENA_OBJECTIVES},
            })
    wins: dict = {}
    win_counts = {p: 0 for p in protos}
    ties = 0
    for sc in scenarios:
        by_p = {r["protocol"]: r for r in rows if r["scenario"] == sc}
        wsc = {}
        for k, d in ARENA_OBJECTIVES.items():
            va, vb = by_p[protos[0]][k], by_p[protos[1]][k]
            if ((math.isinf(va) and math.isinf(vb))
                    or bool(np.isclose(va, vb, rtol=ARENA_REL_TOL,
                                       atol=0.0))):
                wsc[k] = "tie"
                ties += 1
                continue
            w = protos[0] if ((va > vb) if d == "max" else (va < vb)) \
                else protos[1]
            wsc[k] = w
            win_counts[w] += 1
        wins[sc] = wsc

    return sanitize_nonfinite({
        "protocols": list(protos),
        "scenarios": list(scenarios),
        "network_size": n,
        "fraction": fraction,
        "seeds": seeds,
        "attack_heartbeats": steps,
        "objectives": dict(ARENA_OBJECTIVES),
        "identity": {
            "graph_sha256": _graph_hash(sim.graph),
            "publisher": pub,
            "cohort_sha256": cohort_shas,
            "flood_publish": False,
            "episub_root": ep.root,
        },
        "trials": trials,
        "rows": rows,
        "wins": wins,
        "win_counts": win_counts,
        "ties": ties,
        "wall_s": time.time() - t0,
    })
