"""Benchmark: simulated peers x heartbeat-rounds per second (metric of record).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"vs_best_committed"}. The two ratios mean different things:

  vs_baseline        value / the reference harness's effective throughput
                     (BASELINE_PEER_ROUNDS_PER_SEC, a fixed constant — see
                     the baseline note below). "How much faster than Shadow."
  vs_best_committed  value / the best metric-of-record value across the
                     committed repo-root BENCH_r*.json artifacts. "How does
                     this run compare to the best this repo has shipped."

Regression tripwire: when vs_best_committed falls below
1 - REGRESSION_TOLERANCE (i.e. a >20% regression against the best committed
artifact — the r05 failure mode, where dead repair state in the default scan
carries silently cost 2.2x), the artifact gains a strict-JSON "error" field
and the process exits nonzero, so the driver records the regression instead
of committing it as the new normal. The wire only arms on accelerator
backends (the committed artifacts are device runs; a CPU smoke is orders of
magnitude off for reasons that are not regressions); BENCH_TRIPWIRE=1 forces
it on, BENCH_TRIPWIRE=0 forces it off.

Baseline note (BASELINE.md): the reference publishes no numbers. The
comparison constant below is the reference harness's *effective* simulation
throughput: Shadow runs the canonical 100-peer GossipSub experiment (15 min of
simulated time = 900 heartbeat rounds, shadow/topogen.py:82) in on the order
of 100 s of wall time on one amd64 host — about 1e3 peer-rounds/s, and Shadow
scales roughly linearly in process count. We benchmark the same workload
shape (heartbeat mesh maintenance + periodic 15 KB message dissemination with
IHAVE/IWANT gossip) at 100k peers on one chip.

Run: on the machine with the chip, where JAX's default backend is the TPU;
BENCH_SMOKE=1 is the small CPU smoke and its numbers are not device numbers.
Compile time is excluded (one warm-up call per traced
shape), matching how the reference excludes image build time from run time.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

# Shadow's effective throughput on the canonical config (see module docstring)
BASELINE_PEER_ROUNDS_PER_SEC = 1000.0

# BENCH_SMOKE=1 shrinks the workload to a CI-sized CPU run. The config key
# below encodes the shrunken shape, so the tripwire finds no committed
# artifact to compare against and a smoke can never fake a device number.
_SMOKE = os.environ.get("BENCH_SMOKE", "") == "1"
N_PEERS = 2_000 if _SMOKE else 100_000
HB_ROUNDS = 30 if _SMOKE else 300   # timed heartbeat rounds
MESSAGES = 3             # timed dissemination fixpoints (one per ~100 rounds)

# fraction of the best committed value a run may fall short by before the
# tripwire fires (module docstring "Regression tripwire")
REGRESSION_TOLERANCE = 0.20

# the timed loop's delivery mode. EXACT is the model of record and — since
# the parallel-prefix answer-queue engine — also the default bench mode; the
# bounded mode stays measured as a probe (publish_bounded_s). The mode rides
# the config key, so the tripwire never compares an exact-mode run against a
# committed bounded artifact (or vice versa): flipping the default opens a
# fresh comparison bucket instead of tripping a false regression.
DELIVERY_MODE = "exact"

# the workload identity this bench run measures: the tripwire only compares
# against committed artifacts of the SAME config, so a heavier rung (the r05
# 15 KB-payload bounded run) neither masks nor falsely trips a regression
# against the light pre-r05 configs, and a mode flip (bounded -> exact)
# starts a fresh bucket
# the "-dht" suffix keys the cross-protocol probe into the per-config
# tripwire: a run that also builds the poisoned DHT and times the
# DHT-backed recovery window opens its own comparison bucket instead of
# comparing against pre-DHT artifacts of the same workload shape
# the "-svc" suffix does the same for the resident-service probe: a run
# that also drives the admission/dispatch overload rung opens its own
# bucket instead of comparing against pre-service artifacts.
# the service DISPATCH MODE rides the suffix the same way DELIVERY_MODE
# rides the main key (PR 9's pattern): flipping batched <-> sequential
# opens a fresh comparison bucket instead of tripping against the other
# mode's best — the two modes are bit-identical in RESULTS but not in
# requests/s, which is the whole point of the batched engine
SERVICE_DISPATCH_MODE = "batched"
# the "-adaptive" suffix keys the adaptive-attacker probe (ISSUE 15) the
# same way: a run that also times the armed controller window opens a fresh
# tripwire bucket instead of comparing against pre-adaptive artifacts
# fused mega-round scan (ISSUE 16, ops/disseminate.run_fused_rounds): the
# timed loop runs each rep's whole heartbeat-burst + publish chain as ONE
# lax.scan over rounds — one device dispatch per rep instead of one per
# phase per round. Default ON (the raw-speed mode of record; results are
# bit-identical to the phase-split chain on delivery outcomes);
# BENCH_FUSED=0 times the phase-split chain instead. The flag rides the
# config key like DELIVERY_MODE does: per-phase attribution changes shape
# across the flip (fused_round_s vs hb_s/disseminate_s), so a mode flip
# opens a fresh tripwire bucket instead of comparing across regimes.
FUSED_ROUNDS = os.environ.get("BENCH_FUSED", "1") == "1"
# the "-arena" suffix keys the protocol-arena probe (ISSUE 19) the same
# way: a run that also races GossipSub against the episub tree backend
# (runtime/campaign.run_arena_campaign) opens a fresh tripwire bucket
# instead of comparing against pre-arena artifacts
# the "-dcn" suffix keys the multi-host campaign probe (ISSUE 20,
# runtime/campaign.run_campaign(dcn=...)): a run that also launches the
# two-process gloo campaign and times its merged throughput against the
# single-process 8-device grid opens a fresh tripwire bucket instead of
# comparing against pre-DCN artifacts
BENCH_CONFIG = (f"n{N_PEERS}-r{HB_ROUNDS}-m{MESSAGES}-{DELIVERY_MODE}"
                f"-dht-svc-{SERVICE_DISPATCH_MODE}-adaptive"
                + ("-fused" if FUSED_ROUNDS else "") + "-arena-dcn")


def attribution_split(
    wall_s: float, hb_sync_s: float, dis_sync_s: float,
) -> tuple[float, float]:
    """Disjoint per-phase attribution of the metric-of-record wall.

    The instrumented pass that produces hb_sync_s/dis_sync_s syncs after
    every phase, which removes the dispatch overlap the timed loop enjoys —
    so the raw synced times can legitimately sum ABOVE the overlapped wall
    (the r05 artifact shipped disseminate_s 2.322 > wall_s 2.131 this way,
    which read as an accounting bug). This helper scales the synced SHARES
    onto the real wall instead: the returned components are disjoint by
    construction (they sum to wall_s exactly, so the
    `hb_s + disseminate_s <= wall_s` sanity gate in tests/test_bench_gates
    holds), and the raw synced values ship alongside as *_sync_s for anyone
    who wants the overlap-free numbers."""
    total = hb_sync_s + dis_sync_s
    if total <= 0.0:
        return 0.0, 0.0
    return wall_s * hb_sync_s / total, wall_s * dis_sync_s / total


def _config_key_of(rec: dict) -> str:
    """Config key of a committed metric record. Precedence: the explicit
    detail.bench_config field (artifacts from this revision on), else a key
    derived from the workload-shape fields (the r05 artifact predates the
    explicit field but carries delivery_mode), else the legacy pre-r05
    light-config bucket (those artifacts all ran the 2 KB-payload
    exact-delivery workload and are only comparable to each other)."""
    d = rec.get("detail") or {}
    explicit = d.get("bench_config")
    if explicit:
        return str(explicit)
    mode = d.get("delivery_mode")
    if mode and all(d.get(k) is not None
                    for k in ("n_peers", "rounds", "timed_messages")):
        return (f"n{d['n_peers']}-r{d['rounds']}-m{d['timed_messages']}"
                f"-{mode}")
    return "pre-r5-light"


def best_committed_peer_rounds(
    repo_root: str | None = None, config_key: str | None = None,
) -> float | None:
    """Best metric-of-record value across the committed BENCH_r*.json
    artifacts, or None when none parse. Each artifact is the driver's wrapper
    {"n", "cmd", "rc", "tail"} — the bench's own JSON line lives INSIDE the
    "tail" string (after any warnings), so this scans tail lines for the
    {"metric": "simulated_peer_rounds_per_sec", ...} record. With config_key
    set, only records whose _config_key_of matches count — the per-config
    tripwire keying; None keeps the global best (analysis tooling)."""
    import glob
    import os

    root = repo_root or os.path.dirname(os.path.abspath(__file__))
    best = None
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        try:
            with open(path) as fh:
                art = json.load(fh)
        except (OSError, json.JSONDecodeError):
            continue
        for line in str(art.get("tail", "")).splitlines():
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("metric") != "simulated_peer_rounds_per_sec":
                continue
            if config_key is not None and _config_key_of(rec) != config_key:
                continue
            v = rec.get("value")
            if isinstance(v, (int, float)) and (best is None or v > best):
                best = float(v)
    return best


def main() -> None:
    import jax

    from dst_libp2p_test_node_tpu.runtime.compile_cache import (
        enable_compile_cache,
    )

    compile_cache_dir = enable_compile_cache()

    from dst_libp2p_test_node_tpu.config.topology import Topology, TopoParams
    from dst_libp2p_test_node_tpu.ops.disseminate import disseminate
    from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph
    from dst_libp2p_test_node_tpu.ops.heartbeat import run_heartbeats
    from dst_libp2p_test_node_tpu.ops.state import (
        SimParams, graph_arrays, init_state,
    )

    topo = Topology.build(
        TopoParams(
            network_size=N_PEERS, anchor_stages=5, min_bandwidth=50,
            max_bandwidth=150, min_latency=40, max_latency=130,
            msg_size_bytes=15000,
        )
    )
    graph = build_connection_graph(N_PEERS, 10, seed=0)
    # Throughput is measured in the EXACT delivery mode (DELIVERY_MODE
    # above): serialized answer queues are the model of record, and since
    # the parallel-prefix answer-queue engine (SimParams.answer_queue_mode,
    # the default) replaced the serial from-INF refinement sweeps, its
    # per-publish cost sits close enough to the bounded pipeline to be the
    # default at this shape. The bounded mode and the legacy serial engine
    # are both still measured below as probes (publish_bounded_s,
    # publish_exact_serial_s) so the artifact carries the mode gap and the
    # engine speedup on every run.
    import dataclasses

    # warm_start: cross-publish warm-started fixpoints (certified +
    # cold-rerun-guarded, so results are bit-identical to cold starts);
    # the guard's untaken branch costs compile time only, which the bench
    # excludes. A cold-publish timing below attributes the actual benefit.
    params = SimParams(n=N_PEERS, capacity=graph.capacity,
                       serialize_answers=True, warm_start=True)
    params_cold = dataclasses.replace(params, warm_start=False)
    # the bounded-accounting probe mirrors the timed mode's warm carry so
    # publish_bounded_s stays comparable to the pre-flip artifacts' timed
    # publishes; the engine A/B holds everything BUT the engine fixed
    # (exact, cold) so the ratio isolates prefix vs serial refinement
    params_bounded = dataclasses.replace(params, serialize_answers=False)
    params_serial = dataclasses.replace(params_cold,
                                        answer_queue_mode="serial")
    state = init_state(params, seed=0)
    a = graph_arrays(graph)
    import jax.numpy as jnp

    stage = jnp.asarray(topo.stage_of_peer)
    lat = jnp.asarray(topo.latency_ms)
    bw = jnp.asarray(topo.bw_up_mbit)

    def hb(s, k):
        return run_heartbeats(s, a["conns"], a["rev"], a["out_mask"], params, k)

    # experiment-constant edge tables, built once (the Simulator does the
    # same; rebuilding inside the op cost 71.8 ms/publish at this N)
    from dst_libp2p_test_node_tpu.ops.disseminate import (
        answer_tables, edge_tables,
    )
    from dst_libp2p_test_node_tpu.ops.pull import neighbor_pull_bool

    lat_edge, _ = edge_tables(stage, lat, a["conns"], a["rev"])
    # also experiment constants: the lat-sorted answer-queue service tables
    # (two stable argsorts/publish otherwise — the r5 accounting bill) and
    # the neighbor alive&subscribed validity pull (one row-gather/publish)
    ans_tables = answer_tables(lat_edge, a["conns"], a["rev"])
    valid_edge = (a["conns"] >= 0) & neighbor_pull_bool(
        state.alive & state.subscribed, a["conns"], a["rev"])

    def publish(s, pub, p=None):
        res, s = disseminate(
            s, a["conns"], a["rev"], stage, lat, bw, publisher=pub,
            t0_ms=s.t_ms, params=p if p is not None else params,
            payload_bytes=15000, lat_edge=lat_edge,
            ans_tables=ans_tables, valid_edge=valid_edge,
        )
        return res, s

    # warm-up: trace/compile both kernels (same shapes as the timed loop) and
    # form the mesh
    per_burst = HB_ROUNDS // MESSAGES
    state = hb(state, per_burst)
    res, state = publish(state, 4)
    jax.block_until_ready(state.mesh_mask)
    coverage_warmup = float(np.asarray(res.received).mean())

    # fused mega-round scan (FUSED_ROUNDS above): the whole timed rep —
    # MESSAGES x (heartbeat burst + exact publish) — as one jitted scan
    # over rounds. Same publisher schedule as the phase-split loop (4+i
    # from the post-warm-up state), so the two modes replay the identical
    # workload and their delivery outcomes are bitwise equal.
    from dst_libp2p_test_node_tpu.ops.disseminate import run_fused_rounds

    params_fused = dataclasses.replace(params, fused_rounds=True)
    fused_publishers = list(range(4, 4 + MESSAGES))

    def fused_loop(s):
        head, stacked, _obs = run_fused_rounds(
            s, a["conns"], a["rev"], stage, lat, bw, a["out_mask"],
            fused_publishers, params_fused, 15000, per_burst,
            lat_edge=lat_edge, ans_tables=ans_tables, valid_edge=valid_edge)
        return head, stacked

    if FUSED_ROUNDS:
        s_w, _ = fused_loop(state)                  # compile the fused scan
        jax.block_until_ready(s_w.mesh_mask)

    import contextlib
    import os

    profile_dir = os.environ.get("BENCH_PROFILE_DIR", "")
    prof = (jax.profiler.trace(profile_dir) if profile_dir
            else contextlib.nullcontext())  # op-level traces on demand
    # min over reps from the SAME post-warm-up state (the pytree is
    # immutable, so each rep replays the identical workload): host noise
    # on this box is ±20% and min is the contention-robust estimator —
    # the same methodology the config ladder uses. Only rep 0 runs under
    # the optional profiler trace: one clean capture of the workload, and
    # the profiling overhead stays out of the reps the min is taken over.
    state0 = state
    wall = float("inf")
    # device-dispatch census of the timed loop: every top-level jitted
    # entry call is one host->device dispatch point (the retrace counters
    # in runtime/profiling.py certify each is also exactly one cache
    # entry). Phase-split pays 2 per message (heartbeat burst + publish);
    # the fused scan pays 1 per REP covering all MESSAGES rounds.
    dispatches = 0
    for rep in range(3):
        state = state0
        dispatches = 0
        t0 = time.time()
        with prof if rep == 0 else contextlib.nullcontext():
            if FUSED_ROUNDS:
                state, stacked = fused_loop(state0)
                dispatches = 1
                jax.block_until_ready(state.mesh_mask)
            else:
                # keep every timed message's result (device arrays —
                # holding them adds no syncs, so dispatch overlap inside
                # the loop is unchanged)
                results = []
                for i in range(MESSAGES):
                    state = hb(state, per_burst)
                    res, state = publish(state, 4 + i)
                    results.append(res)
                    dispatches += 2
                jax.block_until_ready(state.mesh_mask)
        wall = min(wall, time.time() - t0)
    if FUSED_ROUNDS:
        # unstack the scan's (MESSAGES, ...) result pytree into the
        # per-message records every downstream gate expects — host-side
        # views, after timing
        results = [jax.tree_util.tree_map(lambda x, i=i: x[i], stacked)
                   for i in range(MESSAGES)]
    # per-phase split from a SEPARATE instrumented pass: the inner syncs it
    # needs would change dispatch overlap inside the metric-of-record loop,
    # so they must not ride there. The raw synced sums can exceed the
    # overlapped wall (that's what the syncs remove); attribution_split
    # rescales them into disjoint components of the real wall for the
    # artifact, and the raw values ship as *_sync_s
    hb_sync_s = 0.0
    dis_sync_s = 0.0
    for i in range(MESSAGES):
        t1 = time.time()
        state = hb(state, per_burst)
        jax.block_until_ready(state.t_ms)
        hb_sync_s += time.time() - t1
        t1 = time.time()
        _, state = publish(state, 7 + i)
        jax.block_until_ready(state.bytes_tx)
        dis_sync_s += time.time() - t1
    # fused mode admits no per-phase boundary inside the timed wall (the
    # whole rep is one dispatch): the wall is attributed to fused_round_s
    # whole, and hb_s/disseminate_s are structural zeros — so the emitted
    # phase components ALWAYS sum exactly to wall_s, whichever mode ran
    # (asserted here on the unrounded values; the synced per-phase times
    # above still ship as *_sync_s overlap-free context in both modes)
    if FUSED_ROUNDS:
        fused_round_s = wall
        hb_s = dis_s = 0.0
    else:
        fused_round_s = 0.0
        hb_s, dis_s = attribution_split(wall, hb_sync_s, dis_sync_s)
    assert abs((hb_s + dis_s + fused_round_s) - wall) < 1e-9, (
        "bench attribution broke: hb_s + disseminate_s + fused_round_s "
        "must sum exactly to wall_s")

    # attribution pass: fixpoint-only vs full publish on a FIXED state.
    # The wrapper jit returns ONLY delay_ms, so XLA dead-code-eliminates
    # the post-fixpoint accounting (pulls, rx fold, counters, write-backs)
    # from the inlined disseminate — the difference against the full call
    # is the accounting cost (VERDICT r3 ask #4's per-pull attribution).
    def _probe(keep, p):
        def go(s, pub):
            res, _ = disseminate(
                s, a["conns"], a["rev"], stage, lat, bw, publisher=pub,
                t0_ms=s.t_ms, params=p, payload_bytes=15000,
                lat_edge=lat_edge, ans_tables=ans_tables,
                valid_edge=valid_edge,
            )
            return tuple(getattr(res, k) for k in keep)
        return jax.jit(go)

    # number-by-number floor: delay_ms alone keeps only the fixpoints (in
    # the exact timed mode that includes the prefix refinement — delays
    # depend on it); the fold probe runs on the BOUNDED params, where
    # adding answer_wait keeps the final-times answer-queue fold live too
    # — the difference against the bounded fixpoint isolates the fold (in
    # exact mode the wait bar is a structural 0.0 and would DCE to nothing)
    fix_fn = _probe(("delay_ms",), params)
    bfix_fn = _probe(("delay_ms",), params_bounded)
    fold_fn = _probe(("delay_ms", "answer_wait_max_ms"), params_bounded)
    jax.block_until_ready(fix_fn(state, 11))        # compile
    jax.block_until_ready(bfix_fn(state, 11))
    jax.block_until_ready(fold_fn(state, 11))
    fix_s = np.inf
    bfix_s = np.inf
    fold_s = np.inf
    full_s = np.inf
    cold_s = np.inf
    r, s2 = publish(state, 12, params_cold)
    jax.block_until_ready(s2.bytes_tx)              # compile cold variant
    for i in range(3):
        t1 = time.time()
        jax.block_until_ready(fix_fn(state, 12 + i))
        fix_s = min(fix_s, time.time() - t1)
        t1 = time.time()
        jax.block_until_ready(bfix_fn(state, 12 + i))
        bfix_s = min(bfix_s, time.time() - t1)
        t1 = time.time()
        jax.block_until_ready(fold_fn(state, 12 + i))
        fold_s = min(fold_s, time.time() - t1)
        t1 = time.time()
        r, s2 = publish(state, 12 + i)
        jax.block_until_ready(s2.bytes_tx)
        full_s = min(full_s, time.time() - t1)
        t1 = time.time()
        r, s2 = publish(state, 12 + i, params_cold)
        jax.block_until_ready(s2.bytes_tx)
        cold_s = min(cold_s, time.time() - t1)

    # mode + engine attribution (r5 ask, flipped): the timed loop IS the
    # exact mode now, so the probes measure (a) the same publish with the
    # bounded accounting — the remaining mode gap — and (b) the exact
    # publish refined by the LEGACY serial engine
    # (answer_queue_mode="serial", the pre-prefix model of record), both
    # min-of-3 on the fixed state. serial/cold is the engine speedup the
    # prefix refinement buys at this shape with everything else held fixed.
    def _mode_probe(p):
        def go(s, pub):
            res, s = disseminate(
                s, a["conns"], a["rev"], stage, lat, bw, publisher=pub,
                t0_ms=s.t_ms, params=p, payload_bytes=15000,
                lat_edge=lat_edge, ans_tables=ans_tables,
                valid_edge=valid_edge,
            )
            return res, s
        return go

    bounded_s = np.inf
    serial_s = np.inf
    _bounded = _mode_probe(params_bounded)
    _serial = _mode_probe(params_serial)
    _, s0 = _bounded(state, 21)
    jax.block_until_ready(s0.bytes_tx)              # compile
    _, s0 = _serial(state, 21)
    jax.block_until_ready(s0.bytes_tx)              # compile
    for i in range(3):
        t1 = time.time()
        _, s2 = _bounded(state, 22 + i)
        jax.block_until_ready(s2.bytes_tx)
        bounded_s = min(bounded_s, time.time() - t1)
        t1 = time.time()
        _, s2 = _serial(state, 22 + i)
        jax.block_until_ready(s2.bytes_tx)
        serial_s = min(serial_s, time.time() - t1)

    # sanity gates on the mode/engine attribution (VERDICT r5 "What's
    # weak" #2, reworked for the exact-default flip): a zero timing means
    # the probe measured nothing (a cached/DCE'd call) and the artifact
    # must not ship it. The old `exact >= bounded-full` ordering gate is
    # gone by design — the prefix engine's whole point is closing that gap,
    # so the gap is REPORTED (publish_bounded_s vs publish_exact_s), not
    # asserted on.
    assert full_s > 0.0, "publish_exact_s == 0.0: probe measured nothing"
    assert bounded_s > 0.0, (
        "publish_bounded_s == 0.0: bounded probe measured nothing")
    assert serial_s > 0.0, (
        "publish_exact_serial_s == 0.0: serial-engine probe measured nothing")
    # the exactness certificate of the timed loop: in the exact mode every
    # timed publish must reach self-consistency (prefix certificate, or
    # the serial certificate after the nested fallback) — a capped
    # fixpoint would silently ship approximate times under an exact label
    if DELIVERY_MODE == "exact":
        assert all(bool(np.asarray(r.converged)) for r in results), (
            "exact-mode timed publish did not converge under the "
            "iteration cap; the artifact would mislabel approximate times "
            "as exact")

    # adversarial-campaign probe (ops/adversary.py): one sybil graft-flood
    # window + one censored publish at the bench shape, timed as a single
    # attack trial — BENCH tracks attack_trials_per_s alongside the metric
    # of record. The bench params leave score defenses statically compiled
    # out (slow_weight == 0), so the probe arms the attack score surface;
    # warm_start off because the attacked state diverges from the warm
    # carry's certificate.
    from dst_libp2p_test_node_tpu.ops.adversary import (
        AdversaryParams, attacker_cohort, censor_mask,
        run_attacked_heartbeats,
    )

    adv = AdversaryParams(scenario="sybil_graft_flood")
    params_attack = dataclasses.replace(
        params, slow_weight=-10.0, slow_decay=0.9, graylist_threshold=-50.0,
        gossip_threshold=-10.0, publish_threshold=-20.0, warm_start=False)
    att = attacker_cohort(N_PEERS, 0.1, seed=0)
    att_j = jnp.asarray(att)
    censor = censor_mask(att_j, a["conns"])
    ATTACK_HB = 10

    def _attack_trial(s):
        s, obs = run_attacked_heartbeats(
            s, a["conns"], a["rev"], a["out_mask"], att_j, params_attack,
            adv, ATTACK_HB)
        res, s = disseminate(
            s, a["conns"], a["rev"], stage, lat, bw, publisher=4,
            t0_ms=s.t_ms, params=params_attack, payload_bytes=15000,
            lat_edge=lat_edge, ans_tables=ans_tables, valid_edge=valid_edge,
            censor_edge=censor,
        )
        return res, obs, s

    res_a, obs_a, s_a = _attack_trial(state0)
    jax.block_until_ready(s_a.bytes_tx)             # compile
    attack_s = np.inf
    for _ in range(3):
        t1 = time.time()
        res_a, obs_a, s_a = _attack_trial(state0)
        jax.block_until_ready(s_a.bytes_tx)
        attack_s = min(attack_s, time.time() - t1)
    att_score = float(np.asarray(obs_a["attacker_score_mean"])[-1])
    gray_frac = float(np.asarray(obs_a["graylisted_frac"])[-1])
    honest = ~att
    cov_attack = float(
        (np.asarray(res_a.delay_ms)[honest] < 1e30).mean())
    attack_trials_per_s = 1.0 / attack_s
    # sanity gates, same style as the exact-mode gates above: an unarmed
    # score surface or a DCE'd window shows up as a non-negative attacker
    # score / zero graylisting, and then the probe measured nothing
    assert att_score < 0.0, (
        f"attacker_score {att_score} >= 0: the attack window left no "
        "score signal; the probe params are not armed")
    assert gray_frac > 0.0, (
        "graylisted_frac == 0 after the attack window: defense never "
        "engaged; the probe measured nothing")
    assert cov_attack >= 0.95, (
        f"honest coverage {cov_attack} under sybil graft-flood: the "
        "censored publish broke honest delivery")
    assert np.isfinite(attack_trials_per_s) and attack_trials_per_s > 0.0

    # mesh-repair probe (ops/repair.py): one recovery window — eviction +
    # PX + re-dial armed — run from the post-attack state, timed min-of-3
    # as a single repair trial. BENCH tracks repair_trials_per_s alongside
    # attack_trials_per_s: the recovery scan carries the CONNECTION GRAPH
    # (nothing hoists), so its round cost bounds the dynamic-graph path.
    from dst_libp2p_test_node_tpu.ops.repair import (
        RepairParams, run_recovery_heartbeats,
    )

    params_repair = RepairParams(
        evict=True, px=True, redial=True).apply(params_attack)
    REPAIR_HB = 10

    def _repair_trial():
        return run_recovery_heartbeats(
            s_a, a["conns"], a["rev"], a["out_mask"], att_j, params_repair,
            REPAIR_HB, publisher=4)

    (s_r, cn_r, _rv_r, _om_r), obs_r = _repair_trial()
    jax.block_until_ready(cn_r)                     # compile
    repair_s = np.inf
    for _ in range(3):
        t1 = time.time()
        (s_r, cn_r, _rv_r, _om_r), obs_r = _repair_trial()
        jax.block_until_ready(cn_r)
        repair_s = min(repair_s, time.time() - t1)
    repair_trials_per_s = 1.0 / repair_s
    evictions_total = int(np.asarray(s_r.evictions).sum())
    redials_total = int(np.asarray(s_r.redials).sum())
    att_share_attack = float(np.asarray(obs_a["attacker_mesh_share"])[-1])
    att_share_repair = float(np.asarray(obs_r["attacker_mesh_share"])[-1])
    # sanity gates, same style as above: a repair window that evicts
    # nothing (the post-attack scores sit far below the threshold) or
    # leaves the attacker mesh share where the attack left it measured a
    # DCE'd or disarmed path
    assert evictions_total > 0, (
        "mesh_evictions_total == 0 after the repair window: the eviction "
        "branch never fired on a state full of graylisted attackers")
    assert att_share_repair <= att_share_attack, (
        f"attacker mesh share rose {att_share_attack} -> "
        f"{att_share_repair} across the repair window")
    assert np.isfinite(repair_trials_per_s) and repair_trials_per_s > 0.0

    # cross-protocol DHT probe (ops/dht_adversary.py): build the poisoned
    # DHT under the SAME sybil cohort (lookup eclipse + one rtable insert
    # wave), derive the discovery shortlist pool, and time one DHT-backed
    # recovery window from the post-attack state — dht_attack_trials_per_s.
    # Pre-emit gates mirror the attack/repair probes: a probe that measured
    # a disarmed or broken substrate must not ship a number.
    from dst_libp2p_test_node_tpu.ops.dht_adversary import (
        DhtAdversaryParams, build_attacked_dht, dht_repair_pool,
        rtable_poison_budget, rtable_poison_frac,
    )
    from dst_libp2p_test_node_tpu.ops.repair import run_dht_recovery_heartbeats

    dht = DhtAdversaryParams(lookup_eclipse=True, rtable_poison=True,
                             warmup_waves=1, lookup_rounds=2)
    kstate, directory = build_attacked_dht(
        N_PEERS, seed=0, dht=dht, attacker=att, victim=4, stage=stage,
        lat_ms=lat)
    # reference build: same seed and eclipse, poison wave OFF. Attackers
    # are real peers (organic table share) and the eclipsed warmup itself
    # infects tables, so the gate bounds only the EXCESS the insert wave
    # added — the one thing the closed-form occupancy budget prices
    kstate_b, _ = build_attacked_dht(
        N_PEERS, seed=0,
        dht=DhtAdversaryParams(lookup_eclipse=True, warmup_waves=1,
                               lookup_rounds=2),
        attacker=att, victim=4, stage=stage, lat_ms=lat)
    pfrac = rtable_poison_frac(kstate, att)

    def _att_entries(ks):
        rt = np.asarray(ks.rtable)[~att]
        occ = rt >= 0
        return int(att[np.clip(rt, 0, None)][occ].sum())

    # the budget denominates over FULL table capacity (B*K slots), so the
    # gate compares the capacity-normalized excess entry count — the
    # occupied-share pfrac above is the reported campaign channel, not the
    # budget's unit (sparse tables would inflate it)
    n_honest = int((~att).sum())
    poison_excess = ((_att_entries(kstate) - _att_entries(kstate_b))
                     / (n_honest * dht.n_buckets * dht.k_bucket))
    poison_budget = rtable_poison_budget(
        dht.poison_per_peer, dht.n_buckets, dht.k_bucket)
    assert 0.0 < poison_excess <= poison_budget, (
        f"rtable poison excess {poison_excess:.4f} outside (0, "
        f"{poison_budget:.4f}]: the insert wave is disarmed or exceeded "
        "its closed-form occupancy ceiling; the probe params are wrong")
    pool_d, _ = dht_repair_pool(kstate, dht, stage, lat, attacker=att_j,
                                directory=directory)
    # honest-lookup success floor: the HEALED self-lookup (the repair
    # controller's honest walk over the same evolved tables) must hand
    # nearly every honest peer at least one dial candidate — a substrate
    # whose lookups come back empty would time a no-op redial path
    pool_h, _ = dht_repair_pool(kstate, dht, stage, lat, attacker=att_j,
                                directory=directory, healed=True)
    honest = ~att
    lookup_hits = float(
        (np.asarray(pool_h)[honest] >= 0).any(axis=1).mean())
    assert lookup_hits >= 0.9, (
        f"honest lookup success {lookup_hits:.2f} < 0.9: the healed "
        "self-lookup left honest peers without dial candidates; "
        "dht_attack_trials_per_s would time a broken walk")

    def _dht_trial():
        return run_dht_recovery_heartbeats(
            s_a, a["conns"], a["rev"], a["out_mask"], att_j, params_repair,
            REPAIR_HB, dht_pool=pool_d, publisher=4)

    (_, cn_d, *_), obs_d = _dht_trial()
    jax.block_until_ready(cn_d)                     # compile
    dht_s = np.inf
    for _ in range(3):
        t1 = time.time()
        (_, cn_d, *_), obs_d = _dht_trial()
        jax.block_until_ready(cn_d)
        dht_s = min(dht_s, time.time() - t1)
    dht_attack_trials_per_s = 1.0 / dht_s
    pool_left = np.asarray(obs_d["dht_pool_left"])
    assert pool_left[-1] <= pool_left[0], (
        "dht_pool_left grew across the recovery window: the consume-on-"
        "examine contract broke and the probe timed a no-op pool")
    assert np.isfinite(dht_attack_trials_per_s) and dht_attack_trials_per_s > 0.0

    # adaptive-attacker probe (ops/adversary.py AdaptivePolicy, ISSUE 15):
    # one ARMED controller window (same ATTACK_HB and cohort as the static
    # attack probe) from the post-warm-up state, min-of-3 —
    # adaptive_attack_trials_per_s. The repair params keep px_pool live so
    # the PX-poison behavior writes real candidate rows instead of tracing
    # against the stripped state. Pre-emit gates mirror the other probes: a
    # controller that never regrafts, never plants a sybil id, or never
    # throttles measured a disarmed policy, not the adaptive arms race.
    from dst_libp2p_test_node_tpu.ops.adversary import (
        AdaptivePolicy, run_adaptive_heartbeats,
    )

    adv_adaptive = dataclasses.replace(
        adv, adaptive=AdaptivePolicy(enabled=True))

    def _adaptive_trial():
        return run_adaptive_heartbeats(
            state0, a["conns"], a["rev"], a["out_mask"], att_j,
            params_repair, adv_adaptive, ATTACK_HB)

    (s_ad, ctrl_ad), obs_ad = _adaptive_trial()
    jax.block_until_ready(s_ad.bytes_tx)            # compile
    adaptive_s = np.inf
    for _ in range(3):
        t1 = time.time()
        (s_ad, ctrl_ad), obs_ad = _adaptive_trial()
        jax.block_until_ready(s_ad.bytes_tx)
        adaptive_s = min(adaptive_s, time.time() - t1)
    adaptive_attack_trials_per_s = 1.0 / adaptive_s
    regrafts_total = int(np.asarray(ctrl_ad.regrafts).sum())
    px_injected_total = int(np.asarray(ctrl_ad.px_injected).sum())
    throttled_total = int(np.asarray(ctrl_ad.throttled_hb).sum())
    viol_est_max = float(np.asarray(ctrl_ad.viol_est).max())
    adaptive_score = float(np.asarray(obs_ad["attacker_score_mean"])[-1])
    assert regrafts_total > 0, (
        "adaptive regrafts == 0 after the armed window: the backoff-expiry "
        "regraft behavior never fired; the probe measured a disarmed "
        "controller")
    assert px_injected_total > 0, (
        "adaptive px_injected == 0 after the armed window: the PX-poison "
        "behavior planted nothing; the probe measured a disarmed controller")
    assert throttled_total > 0 and viol_est_max > 0.0, (
        f"adaptive duty cycle inert (throttled {throttled_total}, "
        f"viol_est max {viol_est_max}): the score-aware throttle never "
        "engaged on an armed score surface")
    assert np.isfinite(adaptive_attack_trials_per_s) \
        and adaptive_attack_trials_per_s > 0.0

    # protocol-arena probe (ISSUE 19, runtime/campaign.run_arena_campaign):
    # one small DEDICATED paired campaign — GossipSub vs the episub tree
    # backend on identical epoch graphs, traffic, and the armed adaptive
    # attacker — timed end-to-end (compile + trials + publish); the shape
    # is fixed (not N_PEERS-scaled) so the probe costs the same on every
    # rung. Pre-emit gates pin the trade the arena exists to measure:
    # both protocols must actually deliver on the benign row, and the
    # tree's eager push must undercut the mesh's duplicate-heavy benign
    # bandwidth — an arena where either fails timed a broken backend,
    # not a protocol race.
    from dst_libp2p_test_node_tpu.config.topology import (
        TopoParams as _ArenaTopo)
    from dst_libp2p_test_node_tpu.ops.adversary import (
        AdversaryParams as _ArenaAdversary)
    from dst_libp2p_test_node_tpu.runtime.campaign import (
        CampaignConfig, attack_gossipsub, run_arena_campaign)
    from dst_libp2p_test_node_tpu.runtime.simulator import ExperimentConfig

    arena_cfg = CampaignConfig(
        scenario="sybil_graft_flood",
        fractions=(0.25,),
        seeds=(0,),
        experiment=ExperimentConfig(
            topo=_ArenaTopo(network_size=64, anchor_stages=3,
                            msg_size_bytes=2000, messages=2,
                            delay_seconds=0.5),
            connect_to=8,
            gossipsub=attack_gossipsub(flood_publish=False),
            publisher_id=4,
            warmup_s=8.0,
            seed=0,
        ),
        adversary=_ArenaAdversary(
            scenario="sybil_graft_flood",
            adaptive=AdaptivePolicy(enabled=True)),
        attack_heartbeats=6,
    )
    t1 = time.time()
    arena = run_arena_campaign(
        arena_cfg, scenarios=("benign", "sybil_graft_flood"))
    arena_wall_s = time.time() - t1
    arena_trials_per_s = len(arena["trials"]) / arena_wall_s
    arena_rows = {(r["scenario"], r["protocol"]): r for r in arena["rows"]}
    bw_gossip = arena_rows[("benign", "gossipsub")]["bandwidth_bytes"]
    bw_episub = arena_rows[("benign", "episub")]["bandwidth_bytes"]
    for proto in arena["protocols"]:
        cov = arena_rows[("benign", proto)]["coverage"]
        assert cov >= 0.95, (
            f"arena benign coverage {cov:.3f} < 0.95 for {proto}: the "
            "backend never converged on the no-attacker row; the probe "
            "timed a broken protocol, not a race")
    assert bw_episub < bw_gossip, (
        f"arena benign bandwidth episub {bw_episub:.0f} >= gossipsub "
        f"{bw_gossip:.0f}: the tree's eager push stopped undercutting the "
        "mesh's duplicate traffic — the Topiary trade the arena measures "
        "is gone")
    assert np.isfinite(arena_trials_per_s) and arena_trials_per_s > 0.0

    # resident-service probe (ARCHITECTURE §16): drive the in-process
    # admission/dispatch path at 2x the dispatcher's per-round capacity on
    # a small dedicated multitopic sim. requests_per_s is the service-mode
    # rung; p99_ms the admitted-latency bound under overload; shed_rate
    # proves the offered load actually exceeded capacity (a probe that
    # never sheds timed an idle queue, not an overloaded one)
    from dst_libp2p_test_node_tpu.runtime.traffic import run_service_load

    # one probe per dispatch mode on the SAME shape: sequential is the
    # pinned reference, batched (ISSUE 14) the mode of record — the ratio
    # is the headline batched-dispatch claim and the records_sha equality
    # is the live bit-identity gate. Each mode runs once untimed over the
    # FULL tick count (the ETH2 schedule introduces tenants over time, so
    # a shorter warm leg would leave a ~3s XLA compile of a late tenant's
    # msg_size inside the timed window), so the timed leg measures
    # dispatch, not XLA compile. The shape is deliberately small
    # (16 peers): per-request dispatch overhead is what batching
    # amortizes, and on a large network the per-column fixpoint device
    # time drowns it — the ratio measures the engine, not the sim.
    svc_shape = dict(
        n_peers=16, subnets=4, connect_to=6, warmup_s=5.0, seed=0,
        per_tick=32, tick_ms=50.0,
        max_queue_depth=32, max_batch=16, via_http=False)
    run_service_load(dispatch_mode="sequential", ticks=10, **svc_shape)
    svc_seq = run_service_load(
        dispatch_mode="sequential", ticks=10, **svc_shape)
    run_service_load(dispatch_mode=SERVICE_DISPATCH_MODE, ticks=10,
                     **svc_shape)
    svc_rep = run_service_load(
        dispatch_mode=SERVICE_DISPATCH_MODE, ticks=10, **svc_shape)
    svc_rps = svc_rep["requests_per_s"]
    svc_p99 = svc_rep["p99_ms"]
    assert svc_rep["queue_bound_held"] and svc_seq["queue_bound_held"], (
        f"service queue depth {svc_rep['max_depth_seen']} exceeded the "
        "admission cap: backpressure is not bounding the resident queue")
    assert svc_rps is not None and np.isfinite(svc_rps) and svc_rps > 0.0, (
        f"service_requests_per_s {svc_rps!r}: the overload probe "
        "dispatched nothing — the service rung measured an idle loop")
    assert svc_p99 is not None and np.isfinite(svc_p99), (
        f"service p99 {svc_p99!r} not finite under overload: admitted "
        "requests are not completing within the run")
    assert 0.0 < svc_rep["shed_rate"] < 1.0, (
        f"service shed_rate {svc_rep['shed_rate']:.3f} outside (0,1): the "
        "2x-capacity probe either never overloaded or admitted nothing")
    assert svc_rep["records_sha"] == svc_seq["records_sha"], (
        "batched and sequential dispatch produced DIFFERENT record "
        "streams on the same schedule — the stacked scan broke the "
        "bit-equality contract (tests/test_batched_dispatch.py localizes)")
    svc_ratio = (svc_rps / svc_seq["requests_per_s"]
                 if svc_seq["requests_per_s"] else float("inf"))
    assert svc_ratio > 1.0, (
        f"batched/sequential requests_per_s ratio {svc_ratio:.3f} <= 1: "
        "the batched engine is slower than the per-request loop on the "
        "smoke shape — one scan dispatch per group should beat one "
        "dispatch per request")

    # multi-host DCN campaign probe (ISSUE 20): launch the two-process
    # engine end-to-end — 2 gloo ranks x 4 virtual CPU devices vs the
    # single-process 8-device grid on the SAME total work — min-of-3
    # subprocess invocations against one shared compilation cache, each
    # with an untimed warm-up sweep, so the throughput gated here is the
    # engine's steady state (scripts/dcn_campaign.py). Pre-emit gates:
    # every invocation must merge BIT-IDENTICAL observables (a fast run
    # with wrong numbers is a broken engine, not a fast one), the
    # core-normalized scaling efficiency must clear 0.6 (normalization:
    # a 1-core smoke host physically serializes the two ranks — the gate
    # judges the engine against what the host can deliver, same meaning
    # on a many-core runner), and the attacked trials must keep the
    # honest-coverage floor (throughput with a collapsed sim is not
    # throughput).
    import subprocess as _sp
    import sys
    import tempfile as _tf

    _dcn_script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts", "dcn_campaign.py")
    dcn_best = None
    # work dirs are scratch; the compilation cache is not — the ranks share
    # the one this process uses (runtime/compile_cache.py), a fixed path
    with _tf.TemporaryDirectory(prefix="bench_dcn_") as _dcn_tmp:
        # a rank killed by distributed-runtime infrastructure (gloo pair
        # teardown, coordination-service heartbeat starvation on an
        # oversubscribed host) is an environment flake, not a perf or
        # correctness signal: grant the 3 measured reps a small retry
        # budget for THAT class only. A rep that runs to completion is
        # never retried — its gates (bit-identity, coverage) stay hard
        _dcn_flake_budget = 2
        rep = 0
        attempt = 0
        while rep < 3:
            _wd = os.path.join(_dcn_tmp, f"rep{rep}.{attempt}")
            _res_path = os.path.join(_wd, "result.json")
            os.makedirs(_wd)
            _proc = _sp.run(
                [sys.executable, _dcn_script, "--out", _res_path,
                 "--workdir", os.path.join(_wd, "work"),
                 "--cache-dir", compile_cache_dir, "--warmup",
                 "--seeds", "8", "--heartbeats", "12"],
                capture_output=True, text=True, timeout=1200)
            if (_proc.returncode != 0 and _dcn_flake_budget > 0
                    and not os.path.exists(_res_path)):
                _dcn_flake_budget -= 1
                attempt += 1
                print(f"bench: dcn probe rep {rep} hit an infra flake "
                      f"(rc={_proc.returncode}), retrying "
                      f"({_dcn_flake_budget} retries left)",
                      file=sys.stderr, flush=True)
                continue
            assert _proc.returncode == 0, (
                f"dcn probe rep {rep} failed "
                f"(rc={_proc.returncode}):\n{_proc.stdout[-2000:]}")
            rep += 1
            attempt = 0
            with open(_res_path) as _f:
                _rep_res = json.load(_f)
            assert _rep_res["bit_identical"], (
                f"dcn probe rep {rep}: two-process merged observables "
                "differ from the single-process grid — the DCN boundary "
                "changed numerics")
            assert _rep_res["honest_coverage_min"] >= 0.9, (
                f"dcn probe rep {rep}: honest coverage floor broken "
                f"({_rep_res['honest_coverage_min']:.3f} < 0.9) — the "
                "probe timed a collapsed sim")
            if (dcn_best is None or _rep_res["dcn_trials_per_s"]
                    > dcn_best["dcn_trials_per_s"]):
                dcn_best = _rep_res
    dcn_trials_per_s = dcn_best["dcn_trials_per_s"]
    assert dcn_best["scaling_efficiency_normalized"] >= 0.6, (
        f"dcn scaling efficiency {dcn_best['scaling_efficiency']:.3f} "
        f"(normalized {dcn_best['scaling_efficiency_normalized']:.3f} on "
        f"{dcn_best['host_cores']} cores) below the 0.6 floor: the "
        "two-process engine is losing more than 40% of the throughput "
        "this host can physically deliver to orchestration overhead")

    rounds = MESSAGES * per_burst
    value = N_PEERS * rounds / wall
    # coverage and percentiles over ALL timed messages, not the last one's
    # tail — one message at 100k peers is a noisy stand-in for the
    # distribution across the timed publishes
    delays = np.stack([np.asarray(r.delay_ms) for r in results])
    ok = delays < 1e30
    coverage = float(ok.mean())
    # the device grid this host runs campaigns on (config-7's scheme:
    # trial groups capped at 4, every remaining device widens each
    # group's peer submesh). Recorded in the artifact so every committed
    # number names the grid that produced it, and folded into the config
    # key on multi-device hosts so the tripwire never compares a 1-chip
    # artifact against an 8-chip run (single-device runs keep the bare
    # key — committed artifacts predate the suffix)
    n_dev = jax.device_count()
    grid_groups = min(n_dev, 4)
    grid_per_group = n_dev // grid_groups
    bench_config = (BENCH_CONFIG if n_dev == 1
                    else f"{BENCH_CONFIG}-d{n_dev}")
    # regression tripwire vs the best committed artifact OF THIS CONFIG
    # (module docstring; _config_key_of keys the committed records)
    best = best_committed_peer_rounds(config_key=bench_config)
    import os as _os

    trip_env = _os.environ.get("BENCH_TRIPWIRE", "")
    trip_armed = (trip_env == "1"
                  or (trip_env != "0" and jax.default_backend() != "cpu"))
    regressed = (best is not None
                 and value < (1.0 - REGRESSION_TOLERANCE) * best)
    out = {
        "metric": "simulated_peer_rounds_per_sec",
        "value": round(value, 1),
        "unit": "peers*rounds/s",
        # value / the fixed reference-harness constant ("vs Shadow")
        "vs_baseline": round(value / BASELINE_PEER_ROUNDS_PER_SEC, 2),
        # value / the best committed BENCH_r*.json ("vs our own best")
        "vs_best_committed": (round(value / best, 3)
                              if best is not None else None),
        "detail": {
            # explicit workload identity for the per-config tripwire keying
            # (grid-suffixed on multi-device hosts, see above)
            "bench_config": bench_config,
            # the campaign device grid on this host: which trials x peers
            # shape produced (or would have produced) the sharded numbers
            "device_grid": {
                "backend_devices": n_dev,
                "trial_groups": grid_groups,
                "peers_per_group": grid_per_group,
            },
            "n_peers": N_PEERS,
            "rounds": rounds,
            "wall_s": round(wall, 3),
            # per-phase split so heartbeat vs dissemination regressions are
            # attributable across rounds. hb_s/disseminate_s/fused_round_s
            # are DISJOINT components of wall_s and sum to it exactly
            # (asserted above): phase-split attributes via
            # attribution_split (rescaled synced shares — the r05
            # artifact's disseminate_s > wall_s confusion is structurally
            # gone) and leaves fused_round_s 0.0; the fused scan has no
            # per-phase boundary inside the wall, so it attributes the
            # whole wall to fused_round_s and zeros the per-phase pair.
            # The raw synced times ship as *_sync_s in both modes and may
            # legitimately sum above wall_s (they are overlap-free).
            "fused_rounds": FUSED_ROUNDS,
            "fused_round_s": round(fused_round_s, 3),
            "hb_s": round(hb_s, 3),
            "disseminate_s": round(dis_s, 3),
            "hb_sync_s": round(hb_sync_s, 3),
            "disseminate_sync_s": round(dis_sync_s, 3),
            # the timed loop's top-level jitted entry calls (= host->device
            # dispatch points) per rep, and the same normalized per publish
            # round: 2.0 phase-split, 1/MESSAGES fused — the mega-round
            # scan's whole point
            "timed_loop_dispatches": dispatches,
            "dispatches_per_publish_round": round(dispatches / MESSAGES, 3),
            # one-publish attribution on a fixed state (min of 3):
            # fixpoint_s = the two-phase arrival fixpoint alone (accounting
            # DCE'd; includes the prefix refinement in the exact timed
            # mode); accounting_s = what the post-fixpoint pulls, rx fold,
            # counters and write-backs add on top
            "fixpoint_s": round(fix_s, 3),
            "accounting_s": round(max(full_s - fix_s, 0.0), 3),
            # fold_s isolates the final-times answer-queue fold (the
            # bounded mode's wait bar) from the rest of the accounting,
            # measured on the bounded probe where the bar is live: keep
            # delay_ms + answer_wait_max_ms, DCE everything else, subtract
            # the bounded fixpoint floor
            "fold_s": round(max(fold_s - bfix_s, 0.0), 3),
            "publish_full_s": round(full_s, 3),
            # the same exact publish with the cross-publish warm carry
            # disabled: the measured (wavefront-limited) warm-start benefit
            "publish_cold_s": round(cold_s, 3),
            # delivery-fidelity attribution (see SimParams
            # .serialize_answers and README "Delivery-fidelity modes"):
            # the timed loop runs the EXACT mode (model of record) on the
            # parallel-prefix engine; publish_exact_s is its measured
            # publish (== publish_full_s in this mode), publish_bounded_s
            # the bounded-accounting publish on the same state (the
            # remaining mode gap), publish_exact_serial_s the exact
            # publish refined by the legacy serial engine — over
            # publish_cold_s (same cold exact publish, prefix engine) it
            # is the engine speedup the prefix refinement buys
            "delivery_mode": DELIVERY_MODE,
            "publish_exact_s": round(full_s, 3),
            "publish_bounded_s": round(bounded_s, 3),
            "publish_exact_serial_s": round(serial_s, 3),
            "exact_serial_over_prefix": round(serial_s / max(cold_s, 1e-9),
                                              2),
            # max refinement passes any timed publish paid (prefix Jacobi
            # iterations; prefix + serial outer passes if the certificate
            # ever fell back): the retrace-free analogue of the serial
            # engine's ~15-20 from-INF sweeps
            "refine_passes": int(max(
                int(np.asarray(r.refine_passes)) for r in results)),
            # every timed fixpoint reached self-consistency under the
            # iteration cap (in exact mode this is the exactness
            # certificate — asserted above, reported here)
            "converged": bool(all(
                bool(np.asarray(r.converged)) for r in results)),
            "backend": jax.default_backend(),
            "coverage": coverage,               # all timed messages
            "coverage_warmup": coverage_warmup,
            "timed_messages": MESSAGES,
            # adversarial-campaign probe: one armed sybil graft-flood
            # window (ATTACK_HB heartbeats) + one censored publish,
            # min-of-3 trials on the fixed post-warm-up state
            "attack_trials_per_s": round(attack_trials_per_s, 3),
            "attack": {
                "scenario": "sybil_graft_flood",
                "attacker_fraction": 0.1,
                "attack_heartbeats": ATTACK_HB,
                "trial_s": round(attack_s, 3),
                "honest_coverage": round(cov_attack, 4),
                "attacker_score": round(att_score, 2),
                "graylisted_frac": round(gray_frac, 4),
            },
            # mesh-repair probe: one recovery window (eviction + PX +
            # re-dial, REPAIR_HB heartbeats with the graph in the scan
            # carry) from the post-attack state, min-of-3 trials
            "repair_trials_per_s": round(repair_trials_per_s, 3),
            "repair": {
                "recovery_heartbeats": REPAIR_HB,
                "trial_s": round(repair_s, 3),
                "mesh_evictions_total": evictions_total,
                "redials_total": redials_total,
                "attacker_mesh_share_after": round(att_share_repair, 4),
            },
            # cross-protocol DHT probe: one DHT-backed recovery window
            # (poisoned discovery shortlist feeding the re-dial path) from
            # the post-attack state, min-of-3 trials; the poison numbers
            # are the pre-emit gate inputs (excess over the benign build,
            # bounded by the closed-form occupancy budget)
            "dht_attack_trials_per_s": round(dht_attack_trials_per_s, 3),
            "dht": {
                "recovery_heartbeats": REPAIR_HB,
                "trial_s": round(dht_s, 3),
                "rtable_poison_frac": round(pfrac, 4),
                "rtable_poison_excess": round(poison_excess, 4),
                "rtable_poison_budget": round(poison_budget, 4),
                "honest_lookup_success": round(lookup_hits, 4),
                "pool_left_final": float(pool_left[-1]),
            },
            # adaptive-attacker probe: one armed controller window (same
            # shape as the attack probe, repair leaves live), min-of-3; the
            # counters are the pre-emit gate inputs and attacker_score is
            # the duty cycle's whole point — it must sit ABOVE the static
            # probe's post-window score (throttling trades violations for
            # score headroom)
            "adaptive_attack_trials_per_s": round(
                adaptive_attack_trials_per_s, 3),
            "adaptive": {
                "attack_heartbeats": ATTACK_HB,
                "trial_s": round(adaptive_s, 3),
                "regrafts_total": regrafts_total,
                "px_injected_total": px_injected_total,
                "throttled_hb_total": throttled_total,
                "viol_est_max": round(viol_est_max, 3),
                "attacker_score": round(adaptive_score, 2),
            },
            # protocol-arena probe: one paired GossipSub-vs-episub
            # campaign on a fixed small shape (benign + armed adaptive
            # graft-flood), timed end-to-end; the benign bandwidth pair
            # is the pre-emit-gated Topiary trade and the win counts are
            # the artifact's headline
            "arena_trials_per_s": round(arena_trials_per_s, 3),
            "arena": {
                "peers": arena["network_size"],
                "scenarios": list(arena["scenarios"]),
                "seeds": list(arena["seeds"]),
                "attack_heartbeats": arena["attack_heartbeats"],
                "trials": len(arena["trials"]),
                "wall_s": round(arena_wall_s, 3),
                "benign_bandwidth_bytes": {
                    "gossipsub": round(bw_gossip, 1),
                    "episub": round(bw_episub, 1),
                },
                "win_counts": arena["win_counts"],
                "ties": arena["ties"],
            },
            # resident-service probe: in-process submit()/pump() at 2x
            # dispatcher capacity (runtime/traffic.py ETH2-style mix); the
            # gates above pin shed_rate in (0,1) and a finite p99 before
            # any artifact is emitted
            "service_requests_per_s": round(svc_rps, 3),
            "service_p99_ms": round(svc_p99, 3),
            "service": {
                "dispatch_mode": SERVICE_DISPATCH_MODE,
                "overload_factor": svc_rep["config"]["overload_factor"],
                "offered": svc_rep["offered"],
                "admitted": svc_rep["admitted"],
                "rejected": svc_rep["rejected"],
                "dispatched": svc_rep["dispatched"],
                "device_dispatches": svc_rep["device_dispatches"],
                "shed_rate": round(svc_rep["shed_rate"], 4),
                "p50_ms": round(svc_rep["p50_ms"], 3),
                "max_depth_seen": svc_rep["max_depth_seen"],
                # the batched-dispatch headline: same schedule, same
                # record stream (sha-checked above), fewer dispatches
                "sequential_requests_per_s":
                    round(svc_seq["requests_per_s"], 3),
                "batched_over_sequential": round(svc_ratio, 3),
                "batch_factor": round(
                    svc_rep["dispatched"]
                    / max(svc_rep["device_dispatches"], 1), 3),
            },
            # multi-host DCN campaign probe: two gloo processes x 4
            # virtual CPU devices vs the single-process 8-device grid on
            # the same total work, min-of-3 + warm-up (steady state); the
            # pre-emit gates above pinned bit-identity, the normalized
            # scaling floor and the honest-coverage floor before this
            # block could be emitted
            "dcn_trials_per_s": round(dcn_trials_per_s, 3),
            "dcn": {
                "nproc": dcn_best["nproc"],
                "devs_per_proc": dcn_best["devs_per_proc"],
                "network_size": dcn_best["network_size"],
                "trials": dcn_best["trials"],
                "host_cores": dcn_best["host_cores"],
                "ideal_scaling": dcn_best["ideal_scaling"],
                "dcn_wall_s": round(dcn_best["dcn_wall_s"], 3),
                "single_wall_s": round(dcn_best["single_wall_s"], 3),
                "single_trials_per_s": round(
                    dcn_best["single_trials_per_s"], 3),
                "scaling_efficiency": round(
                    dcn_best["scaling_efficiency"], 4),
                "scaling_efficiency_normalized": round(
                    dcn_best["scaling_efficiency_normalized"], 4),
                "bit_identical": dcn_best["bit_identical"],
                "honest_coverage_min": round(
                    dcn_best["honest_coverage_min"], 4),
            },
            "p50_ms": float(np.percentile(delays[ok], 50)),
            "p99_ms": float(np.percentile(delays[ok], 99)),
        },
    }
    # bounded-only keys, keyed by the mode field (satellite contract: a
    # consumer checks delivery_mode, not key presence heuristics): the
    # wait bar and the interleaved-lane count are the bounded mode's error
    # accounting — in exact mode both are structural zeros and are OMITTED
    # rather than emitted as meaningless 0.0s. The min() guard keeps the
    # bar strict-JSON even if a regression reintroduces an infinite value
    # (sanitize_nonfinite + allow_nan=False below are the hard backstops).
    if DELIVERY_MODE == "bounded":
        out["detail"]["answer_wait_max_ms"] = round(
            min(max(float(np.asarray(r.answer_wait_max_ms))
                    for r in results), 3.0e38), 3)
        out["detail"]["answer_interleaved"] = int(sum(
            int(np.asarray(r.answer_interleaved)) for r in results))
    # roofline block (runtime/profiling.py): per-entrypoint XLA cost
    # analysis + retrace counts over the contract registry. Env-gated —
    # lowering every registered entrypoint at bench shapes costs real
    # compile time, so the default bench artifact stays lean
    if _os.environ.get("BENCH_ROOFLINE", "") == "1":
        from dst_libp2p_test_node_tpu.runtime.profiling import roofline

        out["detail"]["roofline"] = roofline()
    # sharding block (analysis/sharding_audit.py): GSPMD facts — collective
    # kinds/volumes, per-device peak, replicated operands — for the window
    # contracts the campaign configs dispatch, so a bench artifact records
    # the partitioning it ran under next to the throughput it measured.
    # Env-gated like the roofline (one XLA compile per audited contract);
    # BENCH_SHARDING_ONLY narrows the contract-name prefix (default the
    # campaign/ window family)
    if _os.environ.get("BENCH_SHARDING", "") == "1":
        from dst_libp2p_test_node_tpu.analysis.registry import (
            default_contracts)
        from dst_libp2p_test_node_tpu.analysis.sharding_audit import (
            audit_sharding_contracts)

        prefix = _os.environ.get("BENCH_SHARDING_ONLY", "campaign/")
        sh_v, sh_w, sh_facts = audit_sharding_contracts(
            [c for c in default_contracts() if c.name.startswith(prefix)])
        out["detail"]["sharding"] = {
            "facts": sh_facts,
            "violations": [v.to_dict() for v in sh_v],
            "waived": sh_w,
        }
    # flight-recorder overhead probe: the disabled recorder delegates to
    # the SAME jitted run_heartbeats (ops/telemetry.py), so this measures
    # the recorder-off dispatch overhead on the real bench state — the
    # acceptance line is < 2%
    from dst_libp2p_test_node_tpu.ops.telemetry import run_recorded_heartbeats

    def _rec_off(s):
        s2, _ = run_recorded_heartbeats(
            s, a["conns"], a["rev"], a["out_mask"], params, per_burst,
            telemetry=None)
        return s2

    jax.block_until_ready(_rec_off(state).bytes_tx)  # warm (shared cache)
    rec_off_s = np.inf
    plain_s = np.inf
    for _ in range(5):
        t1 = time.time()
        jax.block_until_ready(_rec_off(state).bytes_tx)
        rec_off_s = min(rec_off_s, time.time() - t1)
        t1 = time.time()
        jax.block_until_ready(hb(state, per_burst).bytes_tx)
        plain_s = min(plain_s, time.time() - t1)
    out["detail"]["telemetry_off_overhead"] = round(
        max(rec_off_s / plain_s - 1.0, 0.0), 4)
    # strict JSON: the shared sanitizer nulls any non-finite float that
    # slipped past the sanity gates above, and allow_nan=False stays on as
    # the hard backstop (json.dump would otherwise emit the invalid-JSON
    # literal Infinity and downstream parsers choke)
    from dst_libp2p_test_node_tpu.runtime.summarize import sanitize_nonfinite

    if regressed and trip_armed:
        out["error"] = (
            f"bench regression: {value:.1f} peer-rounds/s is more than "
            f"{REGRESSION_TOLERANCE:.0%} below the best committed "
            f"{best:.1f} (BENCH_r*.json)")
    out = sanitize_nonfinite(out)
    print(json.dumps(out, allow_nan=False))
    if regressed and trip_armed:
        # nonzero exit AFTER the strict-JSON artifact: the driver still
        # captures the full detail block, but records the run as failed
        # instead of committing the regression as the new normal
        raise SystemExit(1)


if __name__ == "__main__":
    main()
