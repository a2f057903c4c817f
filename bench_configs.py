"""The five BASELINE.json scaling configs as a reproducible runner.

  1. Shadow-parity:   100 peers, CONNECTTO=10, yamux, single publisher
  2. 1k peers, D=8 mesh, flood-publish only (gossip off)
  3. 10k peers, MULTI-TOPIC, IHAVE/IWANT heartbeat + peer scoring
  4. 100k peers, fragmented publish (FRAGMENTS=4), churn + mesh pruning,
     EXACT delivery (parallel-prefix answer-queue engine)
  5. 1M peers, mix-routed (MOUNTSMIX/MIXD=4), bounded delivery
     [--all only; ~minutes]
  6. 2k peers, adversarial campaign (sybil graft-flood sweep)
     [--attack / --only 6; never written to BENCH_CONFIGS.json]
  7. 2k peers x peers_per_group, NESTED-sharded adversarial campaign:
     the fraction x seed grid partitioned over trial groups AND the peer
     axis partitioned over each group's device submesh
     (parallel/sharding.make_trial_mesh over the full grid); the peer
     count scales with the submesh width, so wider hosts climb the rung;
     single-device hosts fall back to the vmapped stack  [--all only;
     COMMITTED — the ROADMAP "attack ladder entry"]
  8. Attacked rung toward 1M peers: 2 trial groups x all remaining
     devices as the peer submesh, peers = ATTACK_RUNG_PEERS or
     8192 x peers_per_group  [--only 8; never written to
     BENCH_CONFIGS.json]

Rows 6-8 are this file's own sweeps on whatever devices it finds, with
compiles inside the wall: not a chip's speed. The chip's numbers for the
sybil sweep at 2,048 peers are the cell `attack-2k.sybil`'s (BENCHMARK.json;
PERF.md sections 4 and 5, and the driver's lines in PERF_LEDGER.jsonl), not
these rows'.

Each config prints ONE JSON line: config id, peers, wall seconds,
peers*rounds/sec, coverage, p50/p99 dissemination latency (ms). Run:

  python bench_configs.py            # configs 1-4
  python bench_configs.py --all      # include the 1M mix config
  python bench_configs.py --only 3
  python bench_configs.py --check    # gate: derived coverage expectations,
                                     # latency sanity bands, wall-time
                                     # regression budget vs the committed
                                     # BENCH_CONFIGS.json; exit 1 on failure
  python bench_configs.py --all --check --write BENCH_CONFIGS.json
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np


def _percentiles(delays: np.ndarray):
    ok = np.isfinite(delays)
    if not ok.any():
        return 0.0, float("nan"), float("nan")
    return (
        float(ok.mean()),
        float(np.percentile(delays[ok], 50)),
        float(np.percentile(delays[ok], 99)),
    )


def _emit(config: int, n: int, wall: float, rounds: float, delays, extra=None):
    cov, p50, p99 = _percentiles(np.asarray(delays))
    out = {
        "config": config,
        "peers": n,
        "wall_s": round(wall, 2),
        "peer_rounds_per_sec": round(n * rounds / max(wall, 1e-9), 1),
        "coverage": round(cov, 4),
        "p50_ms": round(p50, 1),
        "p99_ms": round(p99, 1),
    }
    if extra:
        out.update(extra)
    print(json.dumps(out, allow_nan=False), flush=True)
    return out


def _topo(n, msg_size, frags=1):
    from dst_libp2p_test_node_tpu.config.topology import TopoParams

    return TopoParams(
        network_size=n, anchor_stages=5, min_bandwidth=50, max_bandwidth=150,
        min_latency=40, max_latency=130, msg_size_bytes=msg_size,
        num_frags=frags, messages=3, delay_seconds=2.0,
    )


def _run_simple(config, n, *, gossipsub=None, with_gossip=True, msg_size=15000,
                frags=1, churn=0.0, uses_mix=False, num_mix=0, messages=3,
                warmup_s=60.0, serialize_answers=True):
    import jax

    from dst_libp2p_test_node_tpu.config.env import GossipSubParams
    from dst_libp2p_test_node_tpu.runtime.simulator import (
        ExperimentConfig, Simulator)

    cfg = ExperimentConfig(
        topo=_topo(n, msg_size, frags),
        connect_to=10,
        gossipsub=gossipsub or GossipSubParams(),
        publisher_id=4 + (num_mix if uses_mix else 0),
        warmup_s=warmup_s,
        with_gossip=with_gossip,
        churn_down_per_hb=churn,
        churn_up_per_hb=churn / 2,
        uses_mix=uses_mix,
        num_mix=num_mix,
        mix_d=4,
        seed=0,
        serialize_answers=serialize_answers,
    )
    # Build ONCE outside the timed region: topology + graph construction is
    # prep the reference also runs before the timed Shadow run (topogen.py
    # precedes run.sh's shadow invocation). The timed experiment is the
    # warmup + injection schedule on a reset() state.
    sim = Simulator(cfg)

    def experiment():
        sim.reset()
        sim.warmup()
        for i in range(messages):
            if i:
                sim.advance(2000.0)
            sim.publish(cfg.publisher_id, msg_size=msg_size)
        jax.block_until_ready(sim.state.mesh_mask)

    # throwaway pass compiles every trace the timed experiment uses (the
    # XLA cache is process-global and keyed on shapes; the reference
    # likewise excludes image build time from run time); then min over
    # `reps` timed passes — host noise on this box is +-20%, and min is
    # the standard contention-robust estimator
    experiment()
    reps = 1 if n >= 1_000_000 else 3
    wall = math.inf
    for _ in range(reps):
        t0 = time.time()
        experiment()
        wall = min(wall, time.time() - t0)
    delays = np.concatenate([r.delays_ms for r in sim.records])
    rounds = float(sim.state.t_ms) / sim.params.heartbeat_ms
    # delivery_mode is emitted in BOTH modes (downstream keys on the field,
    # not on key-presence heuristics); the wait bar is bounded-only — it is
    # a structural 0.0 in exact mode and is omitted rather than emitted as
    # a meaningless zero
    extra = {"delivery_mode": "exact" if serialize_answers else "bounded"}
    if not serialize_answers:
        # bounded delivery mode (SimParams.serialize_answers): record the
        # per-hop arrival-time error bar alongside the latencies it
        # qualifies — max over the run's messages
        # the bar is always finite now (the interleaved corner is a count,
        # not an INF poison); the min() guard keeps the artifact
        # strict-JSON even against a future regression
        extra["answer_wait_max_ms"] = round(
            min(max(r.answer_wait_max_ms for r in sim.records),
                3.0e38), 3)
    return _emit(config, n, wall, rounds, delays, extra=extra)


def config_1():
    return _run_simple(1, 100, msg_size=15000, warmup_s=300.0)


def config_2():
    from dst_libp2p_test_node_tpu.config.env import GossipSubParams

    gs = GossipSubParams(d=8, d_low=6, d_high=12, flood_publish=True)
    return _run_simple(2, 1000, gossipsub=gs, with_gossip=False, warmup_s=120.0)


def config_3():
    import jax

    from dst_libp2p_test_node_tpu.runtime.multitopic import (
        MultiTopicConfig, MultiTopicSimulator)

    cfg = MultiTopicConfig(
        topo=_topo(10_000, 2000),
        topics=("blocks", "attestations", "aggregates", "sync"),
        connect_to=10,
        subscribe_fraction=0.75,
        warmup_s=60.0,
        seed=0,
    )
    sim = MultiTopicSimulator(cfg)  # built once: prep, not run (see _run_simple)

    def experiment():
        sim.reset()
        sim.warmup()
        delays = []
        for ti, topic in enumerate(cfg.topics):
            pub = int(np.nonzero(sim.subscribed_np[ti])[0][4])
            rec = sim.publish(topic, pub)
            delays.append(rec.delays_ms[np.asarray(sim.subscribed_np[ti])])
            sim.advance(2000.0)
        jax.block_until_ready(sim.states.mesh_mask)
        return delays

    experiment()  # compile-warm pass (see _run_simple)
    wall, delays = math.inf, None
    for _ in range(3):
        t0 = time.time()
        d = experiment()
        dt = time.time() - t0
        if dt < wall:
            wall, delays = dt, d
    rounds = float(sim.state.t_ms) / sim.params.heartbeat_ms
    return _emit(3, 10_000, wall, rounds * len(cfg.topics), np.concatenate(delays),
          extra={"topics": len(cfg.topics),
                 "health": sim.topic_health()})


def config_4():
    # 100k rung: EXACT delivery mode (the default — serialize_answers=True
    # rides _run_simple's default). This rung ran bounded until the
    # parallel-prefix answer-queue engine (SimParams.answer_queue_mode)
    # replaced the serial from-INF refinement sweeps, whose ~15-20 extra
    # fixpoint passes per publish made exact ~7x the bounded publish at
    # this shape; the prefix engine's Jacobi refinement keeps the
    # exactness certificate (falling back to the serial refiner in-graph
    # if it ever fails) at a cost close enough to bounded to make the
    # model of record the committed rung. The mode flip opens a fresh
    # check_results comparison bucket — the wall gate only compares
    # same-delivery_mode rows, so this run is not gated against the old
    # committed bounded wall.
    return _run_simple(4, 100_000, msg_size=15000, frags=4, churn=0.001,
                warmup_s=60.0)


def config_5():
    # 1M rung stays BOUNDED: at this scale the budgeted receiver-side
    # formulation carries the fixpoint and the bounded accounting is the
    # committed trade (error <= the exported answer_wait_max_ms bar); the
    # exact default is the 100k-and-below story (config_4)
    return _run_simple(5, 1_000_000, msg_size=15000, uses_mix=True, num_mix=128,
                messages=2, warmup_s=30.0, serialize_answers=False)


def config_6():
    """Adversarial campaign (runtime/campaign.py): sybil graft-flood sweep,
    fractions {0, 0.1} x seeds {0, 1}. OPT-IN (--attack or --only 6) and
    deliberately NOT part of the committed BENCH_CONFIGS.json ladder — the
    README config table is pinned to that artifact (test_doc_tripwire); the
    tracked series here is attack_trials_per_s."""
    from dst_libp2p_test_node_tpu.runtime.campaign import (
        CampaignConfig, attack_gossipsub, run_campaign)
    from dst_libp2p_test_node_tpu.runtime.simulator import ExperimentConfig

    n = 2048
    cfg = CampaignConfig(
        scenario="sybil_graft_flood",
        fractions=(0.0, 0.1),
        seeds=(0, 1),
        experiment=ExperimentConfig(
            topo=_topo(n, 2000), connect_to=10,
            gossipsub=attack_gossipsub(), warmup_s=30.0, seed=0),
        attack_heartbeats=20,
    )
    res = run_campaign(cfg)
    attacked = [t for t in res.trials if t.fraction > 0]
    # worst-case honest view across the attacked cells: the resilience gate
    cov = min(t.honest_coverage for t in attacked)
    p50 = max(t.latency_p50_ms for t in attacked)
    p99 = max(t.latency_p99_ms for t in attacked)
    engaged = max(t.hb_to_graylist for t in attacked)
    hb_ms = cfg.experiment.gossipsub.heartbeat_ms
    per_trial = (cfg.experiment.warmup_s * 1000.0 // hb_ms
                 + (cfg.experiment.topo.messages - 1)
                 * cfg.experiment.topo.delay_seconds * 1000.0 // hb_ms)
    rounds = per_trial * len(res.trials) + cfg.attack_heartbeats * len(attacked)
    out = {
        "config": 6,
        "peers": n,
        "wall_s": round(res.wall_s, 2),
        "peer_rounds_per_sec": round(n * rounds / max(res.wall_s, 1e-9), 1),
        "coverage": round(cov, 4),
        "p50_ms": round(p50, 1),
        "p99_ms": round(p99, 1),
        "scenario": res.scenario,
        "attack_trials_per_s": round(res.trials_per_s, 4),
        "hb_to_graylist": engaged if math.isfinite(engaged) else None,
        "hb_budget": res.hb_budget,
    }
    print(json.dumps(out, allow_nan=False), flush=True)
    return out


def _attacked_sweep(config: int, n: int, trial_mesh, seeds, grid: dict,
                    attack_heartbeats: int = 20):
    """Shared body of the grid-sharded attack configs (7 and 8): run the
    sybil sweep on the given grid and emit the row with the grid recorded."""
    from dst_libp2p_test_node_tpu.runtime.campaign import (
        CampaignConfig, attack_gossipsub, run_campaign)
    from dst_libp2p_test_node_tpu.runtime.simulator import ExperimentConfig

    cfg = CampaignConfig(
        scenario="sybil_graft_flood",
        fractions=(0.0, 0.1),
        seeds=tuple(seeds),
        experiment=ExperimentConfig(
            topo=_topo(n, 2000), connect_to=10,
            gossipsub=attack_gossipsub(), warmup_s=30.0, seed=0),
        attack_heartbeats=attack_heartbeats,
    )
    res = run_campaign(cfg, trial_mesh=trial_mesh)
    attacked = [t for t in res.trials if t.fraction > 0]
    cov = min(t.honest_coverage for t in attacked)
    p50 = max(t.latency_p50_ms for t in attacked)
    p99 = max(t.latency_p99_ms for t in attacked)
    engaged = max(t.hb_to_graylist for t in attacked)
    hb_ms = cfg.experiment.gossipsub.heartbeat_ms
    per_trial = (cfg.experiment.warmup_s * 1000.0 // hb_ms
                 + (cfg.experiment.topo.messages - 1)
                 * cfg.experiment.topo.delay_seconds * 1000.0 // hb_ms)
    rounds = per_trial * len(res.trials) + cfg.attack_heartbeats * len(attacked)
    out = {
        "config": config,
        "peers": n,
        "wall_s": round(res.wall_s, 2),
        "peer_rounds_per_sec": round(n * rounds / max(res.wall_s, 1e-9), 1),
        "coverage": round(cov, 4),
        "p50_ms": round(p50, 1),
        "p99_ms": round(p99, 1),
        "scenario": res.scenario,
        **grid,
        "attack_trials_per_s": round(res.trials_per_s, 4),
        "hb_to_graylist": engaged if math.isfinite(engaged) else None,
        "hb_budget": res.hb_budget,
    }
    print(json.dumps(out, allow_nan=False), flush=True)
    return out


def config_7():
    """Committed sharded adversarial sweep (the ROADMAP "1M-peer attack
    ladder" line's first rung): sybil graft-flood, fractions {0, 0.1} x
    seeds {0..3}, on the FULL nested device grid — trial groups capped at
    4, every remaining device widens each group's peer submesh
    (runtime/campaign.run_campaign(trial_mesh=...) with both axes live).
    The peer count scales with the peer submesh: 2048 x peers_per_group,
    so the committed 4-device row stays 2048 on a 4x1 grid while an
    8-device host runs 4096 peers on 4x2 — a larger rung at the same
    per-device row load. Single-device hosts fall back to the vmapped
    stack: identical numbers (tests/test_trial_sharding pins sharded ==
    vmapped), different wall. Unlike config 6 this row IS part of the
    committed BENCH_CONFIGS.json ladder; the resilience gates match
    config 6 and the tracked series is attack_trials_per_s over the
    two-level-parallel path."""
    import jax

    from dst_libp2p_test_node_tpu.parallel.sharding import make_trial_mesh

    n_dev = len(jax.devices())
    groups = min(n_dev, 4)
    per_group = max(n_dev // groups, 1)
    trial_mesh = make_trial_mesh(groups) if n_dev > 1 else None
    grid = {"trial_groups": groups, "peers_per_group": per_group,
            "devices": n_dev}
    return _attacked_sweep(7, 2048 * per_group, trial_mesh, (0, 1, 2, 3),
                           grid)


def config_8():
    """Nested-grid attacked rung toward the 1M-peer target (--only 8;
    OPT-IN, never committed): 2 trial groups x every remaining device as
    each group's peer submesh — the peer-axis-heavy grid shape. The peer
    count defaults to 8192 x peers_per_group and is overridable via
    ATTACK_RUNG_PEERS (a real v5e-8 run sets ATTACK_RUNG_PEERS=1048576 on
    the 2x4 grid; CPU smoke stays tractable at the default). Fewer seeds
    than config 7 — the rung measures peer-axis scale, not Monte-Carlo
    width."""
    import jax

    from dst_libp2p_test_node_tpu.parallel.sharding import make_trial_mesh

    n_dev = len(jax.devices())
    groups = 2 if n_dev >= 2 else 1
    per_group = max(n_dev // groups, 1)
    trial_mesh = make_trial_mesh(groups) if n_dev > 1 else None
    n = int(os.environ.get("ATTACK_RUNG_PEERS", 0)) or 8192 * per_group
    grid = {"trial_groups": groups, "peers_per_group": per_group,
            "devices": n_dev}
    return _attacked_sweep(8, n, trial_mesh, (0, 1), grid)


CONFIGS = {1: config_1, 2: config_2, 3: config_3, 4: config_4, 5: config_5,
           6: config_6, 7: config_7, 8: config_8}

ARTIFACT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_CONFIGS.json")

# Regression budget vs the committed artifact: wall time may drift up to
# this factor before the gate fails (dispatch/compile noise at small N is
# a few hundred ms on multi-second runs).
WALL_BUDGET = 1.20


def expected_alive_fraction(down: float, up: float, t_hb: float) -> float:
    """Two-state Markov churn transient: P(alive) after t_hb heartbeats from
    all-alive, with per-heartbeat death rate `down` and revival rate `up` —
    a(t) = a_inf + (1 - a_inf) * exp(-(down+up) t), a_inf = up/(up+down).
    This is the DERIVED coverage expectation for the churn config: dead
    peers cannot receive, and mesh redundancy keeps coverage of the living
    near 1 at these rates."""
    a_inf = up / (up + down)
    return a_inf + (1.0 - a_inf) * math.exp(-(down + up) * t_hb)


def check_results(results: list[dict], artifact_path: str = ARTIFACT) -> list[str]:
    """Per-config assertions. Returns failure strings (empty = gate passes)."""
    committed = {}
    if os.path.exists(artifact_path):
        with open(artifact_path) as f:
            for line in f:
                line = line.strip()
                if line:
                    d = json.loads(line)
                    committed[d["config"]] = d
    failures = []

    def fail(cfg, msg):
        failures.append(f"config {cfg}: {msg}")

    for r in results:
        c = r["config"]
        cov, p50, p99 = r["coverage"], r["p50_ms"], r["p99_ms"]
        # coverage floors: lossless/churn-free configs must blanket the
        # network; the churn config must match the derived Markov transient
        if c == 4:
            # publish times (heartbeats): warmup 60 s + 3 messages 2 s apart
            want = expected_alive_fraction(0.001, 0.0005, 62.0)
            if not (want - 0.04 <= cov <= want + 0.02):
                fail(c, f"coverage {cov} outside derived churn expectation "
                        f"{want:.4f} (+0.02/-0.04)")
        elif c in (7, 8):
            # worst-case HONEST coverage under the sybil sweep: censors
            # cannot stop delivery (attackers forward nothing but honest
            # mesh redundancy routes around them), but the floor is looser
            # than the churn-free 0.999 — cohort placement can strand a
            # low-degree honest straggler behind an all-attacker cut
            if cov < 0.99:
                fail(c, f"honest coverage {cov} < 0.99 under the sweep")
        elif cov < 0.999:
            fail(c, f"coverage {cov} < 0.999 on a churn-free config")
        # latency sanity bands: delays must sit between one link latency
        # and the mcache gossip horizon
        if not (40.0 <= p50 <= p99):
            fail(c, f"p50 {p50} outside [40, p99={p99}]")
        if p99 > 20_000.0:
            fail(c, f"p99 {p99} ms beyond any sane dissemination horizon")
        # attack configs: the tracked throughput series must be live and
        # the defense must engage within the closed-form heartbeat budget
        if c in (6, 7, 8):
            if not r.get("attack_trials_per_s", 0.0) > 0.0:
                fail(c, "attack_trials_per_s not positive")
            if r.get("hb_to_graylist") is None:
                fail(c, "graylist never engaged under sybil graft-flood")
            elif r["hb_to_graylist"] > r["hb_budget"]:
                fail(c, f"graylist engagement {r['hb_to_graylist']} hb "
                        f"beyond the closed-form budget {r['hb_budget']}")
        # wall-time regression budget vs the committed artifact — only
        # comparable when the run matches the committed row's scale AND
        # delivery mode: a wider device grid scales the peer count with it
        # (config 7), comparing an n=4096 8-device run against the
        # committed n=2048 4-device row would gate on the wrong baseline,
        # and an exact-mode run against a committed bounded row (the
        # config-4 mode flip) would gate a different model's wall
        base = committed.get(c)
        comparable = (base is not None
                      and base.get("peers") == r.get("peers")
                      and base.get("devices", r.get("devices"))
                      == r.get("devices")
                      and base.get("delivery_mode", r.get("delivery_mode"))
                      == r.get("delivery_mode"))
        if comparable and r["wall_s"] > base["wall_s"] * WALL_BUDGET:
            fail(c, f"wall {r['wall_s']} s exceeds budget "
                    f"{base['wall_s']} s x {WALL_BUDGET}")
    return failures


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--all", action="store_true",
                   help="include the 1M (5) and sharded-attack (7) configs")
    p.add_argument("--attack", action="store_true",
                   help="append the adversarial-campaign config (6); never "
                        "part of the committed BENCH_CONFIGS.json ladder")
    p.add_argument("--only", type=int, choices=sorted(CONFIGS), default=None)
    p.add_argument("--check", action="store_true",
                   help="apply per-config gates; exit 1 on any failure")
    p.add_argument("--write", metavar="PATH", default=None,
                   help="write the results as the new artifact (JSON lines)")
    a = p.parse_args()
    from dst_libp2p_test_node_tpu.runtime.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    runs = [a.only] if a.only else (
        [1, 2, 3, 4, 5, 7] if a.all else [1, 2, 3, 4])
    if a.attack and not a.only:
        runs.append(6)
    results = [CONFIGS[c]() for c in runs]
    failures = check_results(results) if a.check else []
    for f in failures:
        print(f"GATE FAIL: {f}", file=sys.stderr)
    if a.write and not failures:
        with open(a.write, "w") as fh:
            # the opt-in attack configs never enter the committed ladder:
            # the README config table is pinned to the artifact's rows
            for r in results:
                if r["config"] not in (6, 8):
                    fh.write(json.dumps(r, allow_nan=False) + "\n")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
