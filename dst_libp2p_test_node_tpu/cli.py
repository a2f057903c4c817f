"""CLI driver: the `SIMBACKEND=tpu` replacement for shadow/run.sh + topogen.py.

Subcommands:

  topogen    — emit network_topology.gml + shadow.yaml. Accepts BOTH the
               reference topogen's argparse flags (-n/-bl/-bh/...) and the 13
               positional args shadow/run.sh actually passes (the reference's
               two halves are out of sync — run.sh:49-50 sends positionals to
               a flags-only parser; we accept either, SURVEY.md §7 quirks).
  run        — the 14-positional-arg experiment driver mirroring
               shadow/run.sh:23-38: generates the topology, runs the JAX
               simulation N times, writes awk-compatible latencies<i> files
               and prints the per-run summaries (small/large switch at
               msg_size < 1000, run.sh:68-72).
  summarize  — re-run the summary over an existing latencies file.
  serve      — long-lived node service (HTTP /publish + /health, Prometheus).
  inject     — publisher controller: POST /publish to node services at a
               fixed inter-message delay (pod-api-requester / traffic_sync.py
               analog, shadow/Dockerfile:45-53, topogen.py:124-136).
  attack     — adversarial Monte-Carlo campaign (runtime/campaign.py): sweep
               attacker fraction x seed for one of the v1.1 attack scenarios
               (ops/adversary.py, arXiv:2007.02754) and report resilience
               metrics against the score defense. --adaptive arms the
               per-round attacker controller inside the heartbeat scan.
  pareto     — defense Pareto sweep (runtime/campaign.run_defense_sweep):
               grid over mesh-degree/scoring knobs vs the adaptive attacker,
               report the coverage/bandwidth/recovery-time front and which
               configurations dominate the defaults.
  arena      — protocol arena (runtime/campaign.run_arena_campaign):
               GossipSub vs episub (ops/episub.py, Topiary-style tree) on
               identical graphs/traffic/fault cohorts under the same
               adaptive attacker; strict-JSON head-to-head artifact with
               the per-scenario win matrix.
  kad        — role-based kad-dht workload (bootstrap/normal/probe).
  connmanager — hub-and-spoke watermark/reconnect stress workload.
  servicedisco — advertise/lookup service discovery over the DHT.
  regression — GossipSub-over-kad-dht discovery workload with mesh pings.
  lint       — graft-audit static certification: AST lint over the python
               surface + jaxpr audit of every registered hot entrypoint
               (analysis/). Strict-JSON report on stdout, exit 0 iff clean.
  conform    — conformance oracle (analysis/conformance.py): differential-
               test the compiled heartbeat/adversary step against the
               pure-numpy GossipSub v1.1 reference model (ops/spec.py,
               ACL2s transcription) over the attack canon and emit a
               strict-JSON certificate. Unwaivered divergence = exit 1
               (waiver table: docs/CONFORMANCE.md).
  trace      — flight-recorder export (ops/telemetry.py): run a warmup plus
               a recorded heartbeat window and emit a Chrome-trace/perfetto
               JSON timeline, a per-round .npz and a CSV of every tel_*
               channel; --profile-dir additionally captures a jax.profiler
               trace around the run.

Usage:
  python -m dst_libp2p_test_node_tpu run 1 1000 15000 1 10 50 150 40 130 5 0.0 4 0 4000
  python -m dst_libp2p_test_node_tpu topogen -n 100 -st 5 -bl 50 -bh 150
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

from .config.env import env_str, gossipsub_params_from_env
from .config.topology import Topology, TopoParams

# run.sh positional order (run.sh:23-38)
RUN_SH_PARAMS = [
    "runs", "nodes", "msg_size", "num_frag", "num_publishers",
    "min_bandwidth", "max_bandwidth", "min_latency", "max_latency",
    "anchor_stages", "packet_loss", "publisher_id", "publisher_rotation",
    "inter_message_delay_ms",
]
# the 13 positionals run.sh hands to topogen (run.sh:49-50), in its order
TOPOGEN_POSITIONALS = [
    "nodes", "min_bandwidth", "max_bandwidth", "min_latency", "max_latency",
    "anchor_stages", "packet_loss", "msg_size", "num_frag", "num_publishers",
    "publisher_id", "publisher_rotation", "inter_message_delay_ms",
]


def _topo_flags(p: argparse.ArgumentParser) -> None:
    """The reference topogen's flag surface (topogen.py:13-36)."""
    p.add_argument("-n", "--network-size", type=int, default=100)
    p.add_argument("-bl", "--min-bandwidth", type=int, default=50)
    p.add_argument("-bh", "--max-bandwidth", type=int, default=50)
    p.add_argument("-ll", "--min-latency", type=int, default=100)
    p.add_argument("-lh", "--max-latency", type=int, default=100)
    p.add_argument("-st", "--anchor-stages", type=int, default=1)
    p.add_argument("-l", "--packet-loss", type=float, default=0.0)
    p.add_argument("-s", "--msg-size-bytes", type=int, default=1500)
    p.add_argument("-f", "--num-frags", type=int, choices=range(1, 10), default=1)
    p.add_argument("-m", "--messages", type=int, default=10)
    p.add_argument("-d", "--delay-seconds", type=float, default=0.1)
    p.add_argument(
        "-mx", "--muxer", choices=["mplex", "yamux", "quic"], default="yamux"
    )


def _params_from_flags(a) -> TopoParams:
    return TopoParams(
        network_size=a.network_size,
        min_bandwidth=a.min_bandwidth,
        max_bandwidth=a.max_bandwidth,
        min_latency=a.min_latency,
        max_latency=a.max_latency,
        anchor_stages=a.anchor_stages,
        packet_loss=a.packet_loss,
        msg_size_bytes=a.msg_size_bytes,
        num_frags=a.num_frags,
        messages=a.messages,
        delay_seconds=a.delay_seconds,
        muxer=a.muxer,
    )


def _topo_from_fields(m: dict, muxer: str = "yamux") -> TopoParams:
    """One place owns the run.sh-field -> TopoParams contract (both the
    `topogen` positional form and the `run` driver feed through here)."""
    return TopoParams(
        network_size=int(m["nodes"]),
        min_bandwidth=int(m["min_bandwidth"]),
        max_bandwidth=int(m["max_bandwidth"]),
        min_latency=int(m["min_latency"]),
        max_latency=int(m["max_latency"]),
        anchor_stages=int(m["anchor_stages"]),
        packet_loss=float(m["packet_loss"]),
        msg_size_bytes=int(m["msg_size"]),
        num_frags=int(m["num_frag"]),
        messages=int(m["num_publishers"]),
        delay_seconds=float(m["inter_message_delay_ms"]) / 1000.0,
        muxer=muxer,
    )


def _params_from_positionals(vals: list[str]) -> tuple[TopoParams, dict]:
    m = dict(zip(TOPOGEN_POSITIONALS, vals))
    extra = {
        "publisher_id": int(m["publisher_id"]),
        "publisher_rotation": bool(int(m["publisher_rotation"])),
    }
    return _topo_from_fields(m), extra


def cmd_topogen(argv: list[str]) -> int:
    if argv and not argv[0].startswith("-"):
        if len(argv) != 13:
            print(
                f"topogen: expected 13 positional args ({' '.join(TOPOGEN_POSITIONALS)}) "
                f"or flag form, got {len(argv)}",
                file=sys.stderr,
            )
            return 2
        topo, _ = _params_from_positionals(argv)
    else:
        p = argparse.ArgumentParser(prog="topogen")
        _topo_flags(p)
        topo = _params_from_flags(p.parse_args(argv))
    t = Topology.build(topo)
    t.write_gml()
    t.write_shadow_yaml()
    print(f"wrote network_topology.gml + shadow.yaml ({topo.network_size} peers, "
          f"{topo.anchor_stages} stages)")
    return 0


def _churn_rates(text: str) -> tuple[float, float]:
    """`--churn DOWN[:UP]`: the probabilities a heartbeat; UP is DOWN / 2
    where it is left out."""
    down, colon, up = text.partition(":")
    try:
        down = float(down)
        up = float(up) if colon else down / 2
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"wants DOWN or DOWN:UP, two probabilities, got {text!r}")
    if not (0.0 <= down <= 1.0 and 0.0 <= up <= 1.0):
        raise argparse.ArgumentTypeError(
            f"a probability lies in [0, 1], got {text!r}")
    return down, up


def _publish_counts(records) -> list[dict]:
    """`stats<i>.json` "publishes": a message how much work its fixpoints
    did and which branches ran (MessageRecord)."""
    return [{"fast_iters": r.fast_iters,
             "fast_sparse_iters": r.fast_sparse_iters,
             "refine_passes": r.refine_passes,
             "refine_sparse_passes": r.refine_sparse_passes,
             "refined": r.refined,
             "fell_back": r.fell_back,
             "refined_serial": r.refined_serial,
             "refine_lane_passes": r.refine_lane_passes,
             "lanes_hinted": r.lanes_hinted,
             "lanes_uncertified": r.lanes_uncertified,
             "lanes_in_pull": r.lanes_in_pull,
             "pull_rows_share": r.pull_rows_share,
             "converged": r.converged}
            for r in records]


def _batch_refusal(a) -> str | None:
    """Why `run <runs> ...` with runs > 1 makes these runs one by one and
    not as one batch (runtime/run_batch.py), or None where it batches them:
    the mix routes a message on the host between two device calls, and a
    churned publish reads its publisher's liveness before it dispatches.
    (`--checkpoint` and `--resume` are refused at runs > 1 before this.)"""
    if a.use_mix:
        return "--use-mix routes every message on the host, a run at a time"
    if a.churn != (0.0, 0.0):
        return ("--churn reads the publisher's liveness before a publish, "
                "a run at a time")
    return None


def cmd_run(argv: list[str]) -> int:
    # flags appended after the 14 positionals tune the TPU backend
    p = argparse.ArgumentParser(
        prog="run",
        usage="run <runs> <nodes> <message_size> <num_fragment> <num_publishers> "
        "<min_bandwidth> <max_bandwidth> <min_latency> <max_latency> "
        "<anchor_stages> <packet_loss> <publisher_id> <publisher_rotation> "
        "<inter_message_delay> [--seed N] [--warmup-s S] ...",
        epilog="runs > 1 is simulated as ONE batch (runtime/run_batch.py): "
        "the runs' networks on the device together, one warm-up scan and "
        "one dispatch a message for all of them, and run i (--seed s+i-1) "
        "writes the latencies<i> and shadowlog<i> it writes alone, byte "
        "for byte; --use-mix and --churn keep the loop, a run at a time, "
        "and stats<i>.json \"batch\" says which it was.",
    )
    for name in RUN_SH_PARAMS:
        p.add_argument(name)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--warmup-s", type=float, default=500.0)
    p.add_argument("--connect-to", type=int, default=10)  # run.sh:38
    p.add_argument("--muxer", choices=["mplex", "yamux", "quic"], default="yamux")
    p.add_argument("--no-gossip", action="store_true")
    p.add_argument("--churn", type=_churn_rates, default=(0.0, 0.0),
                   metavar="DOWN[:UP]",
                   help="failure injection: each heartbeat a living peer "
                   "goes down with probability DOWN and a dead one comes "
                   "back with UP (DOWN / 2 where it is left out); the peers "
                   "the run publishes through are spared")
    p.add_argument("--use-mix", action="store_true",
                   help="route publishes through the mix network (USESMIX)")
    p.add_argument("--num-mix", type=int, default=0, help="NUMMIX")
    p.add_argument("--mix-d", type=int, default=4, help="MIXD")
    p.add_argument("--out-prefix", default="")
    p.add_argument("--stats-json", action="store_true",
                   help="also write stats<i>.json next to latencies<i>: the "
                   "summary's numbers, the turn's spans and counters, and "
                   "under \"compile\" what jax traced, lowered, compiled or "
                   "loaded from the persistent cache in that turn. Reading "
                   "it: compiled > 0 with loaded == 0 and stored > 0 is a "
                   "cold cache (the next process loads instead); programs "
                   "> 0 in a later turn of the same arguments is a steady "
                   "state that recompiles (slowest names the program, "
                   "by_span where); compiled_under_threshold close to "
                   "compiled with stored == 0, process after process, is a "
                   "store threshold (stored_threshold_s) that keeps "
                   "nothing: compile_s of it is paid by every process. "
                   "Turn 1 of a process adds \"process\": seconds from the "
                   "package's import to cli.main and to the turn, and the "
                   "device backend's start (span setup/backend)")
    p.add_argument("--checkpoint", default=None,
                   help="snapshot the experiment to this .npz during the run "
                   "(crash-resumable; see --resume; requires runs == 1)")
    p.add_argument("--checkpoint-every", type=int, default=1,
                   help="messages between snapshots (raise for long "
                   "schedules at large N)")
    p.add_argument("--resume", default=None,
                   help="resume from a --checkpoint file and finish its "
                   "remaining schedule (requires runs == 1, same config)")
    p.add_argument("--gml", default=None,
                   help="ingest an existing network_topology.gml (e.g. one "
                   "the reference topogen generated) instead of rebuilding "
                   "the topology from the positional parameters")
    p.add_argument("--msgid-mode", choices=["nim", "go"], default="nim",
                   help="message-id layout: nim = random id embedded in the "
                   "payload (main.nim:169), go = timestamp-keyed "
                   "(go/rust nodes embed no id)")
    p.add_argument("--loss-mode", choices=["tcp", "message"], default="tcp",
                   help="packet-loss model for lossy topologies (-l): tcp = "
                   "RTO retransmission latency (Shadow runs real TCP "
                   "stacks), message = whole-copy drops (QUIC-unreliable "
                   "style)")
    p.add_argument("--delivery-mode", choices=["exact", "bounded"],
                   default="exact",
                   help="answered-IWANT serialization fidelity: exact = "
                   "the model of record (queued answers repaired into the "
                   "arrival times); bounded = the 100k+/1M throughput "
                   "mode (accounting/attribution exact, arrival times "
                   "keep the unserialized value where a queued answer "
                   "binds; the max queue wait is the recorded error bar)")
    a = p.parse_args(argv)
    if (a.checkpoint or a.resume) and int(a.runs) != 1:
        # per-run states would overwrite one checkpoint file and a resume
        # could not tell which run it belongs to
        p.error("--checkpoint/--resume require runs == 1")
    if a.resume and a.gml:
        # a resumed run continues on the checkpoint's embedded topology
        # matrices; silently parsing a (possibly different) GML would
        # mislead about which links are in effect
        p.error("--resume restores the checkpoint's topology; drop --gml")
    if a.use_mix:
        # a publisher that is itself a mix node is excluded from its own
        # relay path, so rotation (any ordinal publishes) or a mix-range
        # publisher_id needs one spare node
        need = a.mix_d + (
            1 if (int(a.publisher_rotation) or int(a.publisher_id) < a.num_mix)
            else 0
        )
        if a.num_mix < need:
            p.error(f"--use-mix requires --num-mix >= {need} here "
                    f"(mix-d={a.mix_d}, publisher inside mix range or "
                    f"rotation on), got {a.num_mix}")

    from .runtime.profiling import process_summary, span, turn
    from .runtime.simulator import ExperimentConfig, Simulator
    from .runtime.summarize import report

    topo = _topo_from_fields(vars(a), muxer=a.muxer)
    t = None
    runs = int(a.runs)
    # runs > 1: ONE batched experiment (runtime/run_batch.py), made in turn
    # 1, unless the call is one the batch does not take; `kept_loop` says
    # why the runs are made one by one, in `stats<i>.json` "batch"
    kept_loop = _batch_refusal(a) if runs > 1 else None
    batch = None

    def config_of(i: int):
        return ExperimentConfig(
            topo=topo,
            connect_to=a.connect_to,
            # the reference nodes read GOSSIPSUB_* inside the simulation,
            # so the driver honors the same env surface
            # (main.nim:252-306)
            gossipsub=gossipsub_params_from_env(),
            publisher_id=int(a.publisher_id),
            publisher_rotation=bool(int(a.publisher_rotation)),
            warmup_s=a.warmup_s,
            seed=a.seed + i - 1,
            with_gossip=not a.no_gossip,
            churn_down_per_hb=a.churn[0],
            churn_up_per_hb=a.churn[1],
            uses_mix=a.use_mix,
            num_mix=a.num_mix,
            mix_d=a.mix_d,
            msgid_mode=a.msgid_mode,
            loss_mode=a.loss_mode,
            serialize_answers=(a.delivery_mode == "exact"),
        )

    for i in range(1, runs + 1):
        with turn(seed=a.seed + i - 1, turn=i) as spans, \
                contextlib.ExitStack() as emit:
            # what this turn's `shadow.yaml` took (no file written: zeros)
            artifacts = {"yaml_hosts_dumped": 0, "yaml_alias_lines": 0}
            if i == 1 and a.gml:
                # run an existing experiment dir: link properties come from
                # the GML (stage latencies/bandwidths), peers/messages from
                # the positionals
                with span("run/topology"):
                    t = Topology.from_gml(
                        a.gml, network_size=topo.network_size, params=topo)
                topo = t.params
            elif i == 1 and not a.resume:
                # (a resumed run: the checkpoint embeds its topology; do NOT
                # overwrite the experiment dir's artifacts before, or after,
                # validating it)
                with span("run/topology"):
                    t = Topology.build(topo)
                with span("run/write_gml"):
                    t.write_gml(a.out_prefix + "network_topology.gml")
                with span("run/write_yaml"):
                    artifacts = t.write_shadow_yaml(a.out_prefix + "shadow.yaml")
            large = topo.msg_size_bytes >= 1000
            print(f"Running for turn {i}")
            cfg = config_of(i)
            if i == 1 and runs > 1 and kept_loop is None:
                from .runtime.run_batch import NotBatchable, RunBatch

                try:
                    with span("run/simulator_init"):
                        batch = RunBatch(
                            [config_of(j) for j in range(1, runs + 1)], t)
                except NotBatchable as e:
                    kept_loop = str(e)
                else:
                    with span("run/simulate"):
                        batch.run()
                    # the batch's one clock, which every run's report
                    # and `stats<i>.json` give: build + run of all R
                    batch_wall = (spans.seconds("run/simulator_init")
                                  + spans.seconds("run/simulate"))
            if batch is not None:
                sim, wall = batch.runs[i - 1], batch_wall
            else:
                with span("run/simulator_init"):
                    if a.resume:
                        from .runtime.checkpoint import load_checkpoint

                        sim = load_checkpoint(a.resume)
                        if sim.cfg != cfg:
                            p.error(
                                "--resume checkpoint was created with a "
                                "different configuration than these "
                                "arguments; re-run with the original "
                                "parameters"
                            )
                    else:
                        sim = Simulator(cfg, topology=t)
                with span("run/simulate"):
                    sim.run(checkpoint_path=a.checkpoint,
                            checkpoint_every=a.checkpoint_every)
                # the program's one clock: build + run, from the spans
                wall = (spans.seconds("run/simulator_init")
                        + spans.seconds("run/simulate"))
            if batch is not None:
                # a run of a batch: its emit under one span more (to the
                # turn's end), so that a profile tells the R runs' emit
                # from the batch before it
                emit.enter_context(span("batch/emit"))
            with span("run/write_latencies"):
                n_lines = sim.write_latencies(f"{a.out_prefix}latencies{i}")
            with span("run/write_shadowlog"):
                # run.sh:60 artifact
                sim.write_shadowlog(f"{a.out_prefix}shadowlog{i}")
            with span("run/summary"):
                s = sim.summary(large)
            with span("run/report"):
                print(f"Summary for turn {i}")
                print(report(s, large=large), end="")
                # summary_shadowlog.awk (run.sh:70-74)
                print(sim.bandwidth_report(), end="")
                print(
                    f"[tpu backend] wall={wall:.2f}s "
                    f"peers*rounds/s={sim.peer_rounds_per_sec(wall):.0f} "
                    f"lines={n_lines}"
                )
            if a.stats_json:
                from .runtime.summarize import sanitize_nonfinite

                with span("run/stats_json"), \
                        open(f"{a.out_prefix}stats{i}.json", "w") as f:
                    json.dump(
                        sanitize_nonfinite({
                            "network_size": s.network_size,
                            "coverage": s.coverage(),
                            "max_latency_ms": s.max_latency_ms,
                            "avg_latency_ms": s.avg_latency_ms,
                            "avg_max_latency_ms": s.avg_max_latency_ms,
                            "wall_s": wall,
                            "peer_rounds_per_sec":
                                sim.peer_rounds_per_sec(wall),
                            # per span name: count and total seconds of this
                            # turn (`run` and `run/stats_json` are still
                            # open: up to here)
                            "spans": spans.totals(),
                            # what jax traced, lowered, compiled or loaded
                            # from the persistent cache in this turn
                            # (profiling.CompileTotals); a steady-state turn
                            # says zeros
                            "compile": spans.compile.as_dict(),
                            # the process's first turn only: seconds from
                            # the package's import to cli.main and to this
                            # turn, and the device backend's start
                            **({"process": process_summary()}
                               if spans.number == 1 else {}),
                            # runs > 1 only: whether this run shared its
                            # dispatches with the others (then `wall_s`,
                            # `spans` and `compile` are the batch's, all
                            # of it in turn 1's, and `batch` counts the
                            # experiment's publish dispatches and
                            # device->host reads), or why the runs were
                            # made one by one
                            **({"batch": {
                                "runs": runs, "index": i,
                                "batched": batch is not None,
                                **(batch.counts if batch is not None
                                   else {"kept_loop": kept_loop})}}
                               if runs > 1 else {}),
                            # lines of latencies<i> and shadowlog<i>, and how
                            # many blocks of each the native formatter took
                            # (0: the Python one wrote them)
                            "emit": sim.emit_counts,
                            # hosts PyYAML wrote into `shadow.yaml` and
                            # alias lines joined beside them
                            "artifacts": artifacts,
                            # which path of the graph build engaged how
                            # often (ops/graph.ConnGraph.build): a run that
                            # fell back to a slow one says so here
                            "build": sim.graph.build,
                            # the heartbeat scans' reciprocity, a stage:
                            # steps delivered from the rows that sent, steps
                            # pulled dense, the most sending rows of a step
                            "heartbeat": sim.heartbeat_counts,
                            "publishes": _publish_counts(sim.records),
                            # under --churn only: the rates a heartbeat,
                            # the peers the draw spares (those the run
                            # publishes through), and a message how many
                            # peers could send and how many of them sat
                            # under D_low valid mesh members
                            **({"churn": {
                                "down_per_hb": cfg.churn_down_per_hb,
                                "up_per_hb": cfg.churn_up_per_hb,
                                "spared_peers": sim.spared_peers,
                                "alive": [r.alive for r in sim.records],
                                "under_dlow": [r.under_dlow
                                               for r in sim.records],
                            }} if sim.spared_peers else {}),
                        }),
                        f,
                        indent=2,
                        allow_nan=False,
                    )
    return 0


def validate_attack_flags(
        scenario: str,
        *,
        mimic_margin: float | None = None,
        rotation_period_hb: int | None = None,
        dht_attack: bool = False,
        dht_heal_hb: int = -1,
        adaptive: bool = False,
        throttle_margin: float | None = None,
        px_poison_per_hb: int | None = None,
) -> None:
    """Reject incompatible `attack` scenario/flag combinations up front,
    before any topology is built or jit trace starts — a bad combo should
    cost milliseconds, not a silent no-op campaign. Raises ValueError with
    the offending flag named; cmd_attack maps it onto argparse's error path.
    """
    from .ops.adversary import ADAPTIVE_SCENARIOS

    if mimic_margin is not None and scenario != "slow_peer_mimicry":
        raise ValueError(
            f"--mimic-margin tunes the slow_peer_mimicry score setpoint; "
            f"scenario {scenario!r} never reads it — drop the flag or use "
            "--scenario slow_peer_mimicry")
    if rotation_period_hb is not None and scenario != "identity_rotation":
        raise ValueError(
            f"--rotation-period-hb sets the identity_rotation scrub cadence; "
            f"scenario {scenario!r} never reads it — drop the flag or use "
            "--scenario identity_rotation")
    if dht_attack and scenario == "cold_boot_join":
        raise ValueError(
            "--dht-eclipse/--dht-poison/--dht-cluster poison discovery "
            "state built during the attack window, but cold_boot_join "
            "replays the join race on a fresh topology with no pre-attack "
            "DHT to poison — drop the --dht-* flags or pick a scenario "
            "with an established mesh")
    if dht_heal_hb >= 0 and not dht_attack:
        raise ValueError(
            "--dht-heal-hb schedules the recovery round a DHT attack heals "
            "at, but no DHT attack is armed — add one of --dht-eclipse/"
            "--dht-poison/--dht-cluster")
    if adaptive and scenario not in ADAPTIVE_SCENARIOS:
        raise ValueError(
            f"--adaptive composes with the graft-flood family "
            f"{ADAPTIVE_SCENARIOS}, not scenario {scenario!r}: the spam "
            "scenarios have no backoff/mesh loop to adapt to, mimicry is "
            "already an adaptive policy, and rotation's identity scrubs "
            "erase the controller's own estimate")
    if throttle_margin is not None and not adaptive:
        raise ValueError("--throttle-margin tunes the adaptive duty cycle; "
                         "it needs --adaptive")
    if px_poison_per_hb is not None and not adaptive:
        raise ValueError("--px-poison-per-hb tunes the adaptive PX poison "
                         "rate; it needs --adaptive")


def cmd_attack(argv: list[str]) -> int:
    """Adversarial campaign driver: one scenario, a fraction x seed grid,
    resilience report + optional JSON/Prometheus artifacts."""
    p = argparse.ArgumentParser(
        prog="attack",
        description="One adversarial campaign: ONE network, a sweep of "
        "attacker fractions x trial seeds. A trial at fraction 0 is the "
        "benign experiment (and its seed's baseline); an attacked trial is "
        "a cohort draw, the warm-up, --attack-heartbeats rounds of "
        "[heartbeat, adversary] (the trials of one fraction as ONE vmapped "
        "window), then the publish schedule with the attackers censoring.")
    from .ops.adversary import SCENARIOS

    p.add_argument("--scenario", choices=SCENARIOS,
                   default="sybil_graft_flood")
    p.add_argument("-n", "--peers", type=int, default=256)
    p.add_argument("--fractions", default="0,0.1,0.2",
                   help="comma-separated attacker fractions in [0, 1); "
                   "include 0 for the in-sweep benign baseline")
    p.add_argument("--seeds", default="0",
                   help="comma-separated trial seeds (the Monte-Carlo axis)")
    p.add_argument("--messages", type=int, default=3)
    p.add_argument("--msg-size", type=int, default=2000)
    p.add_argument("--delay-s", type=float, default=1.0,
                   help="inter-message delay in the publish schedule")
    p.add_argument("--warmup-s", type=float, default=30.0)
    p.add_argument("--attack-heartbeats", type=int, default=20,
                   help="attacked mesh-maintenance rounds before publishing")
    p.add_argument("--connect-to", type=int, default=10)
    p.add_argument("--seed", type=int, default=0,
                   help="campaign seed: builds the shared connection graph")
    p.add_argument("--publisher-id", type=int, default=4)
    p.add_argument("--violation-penalty", type=float, default=1.0)
    p.add_argument("--mimic-margin", type=float, default=None,
                   help="slow_peer_mimicry only: pin the attacker score at "
                   "this fraction of the graylist threshold (0 < m < 1)")
    p.add_argument("--rotation-period-hb", type=int, default=None,
                   help="identity_rotation only: heartbeats between "
                   "identity scrubs (>= 2)")
    # adaptive attacker controller (ops/adversary.AdaptivePolicy): the
    # per-round arms race compiled into the heartbeat scan
    p.add_argument("--adaptive", action="store_true",
                   help="arm the per-round adaptive attacker controller "
                   "(backoff-expiry regraft + PX sybil poison + recovery "
                   "slot race + score-aware duty cycle); graft-flood "
                   "scenarios only")
    p.add_argument("--throttle-margin", type=float, default=None,
                   help="adaptive duty-cycle setpoint as a fraction of the "
                   "graylist threshold (0 < m < 1); requires --adaptive")
    p.add_argument("--px-poison-per-hb", type=int, default=None,
                   help="sybil ids the adaptive attacker plants per victim "
                   "px_pool row per heartbeat; requires --adaptive")
    p.add_argument("--no-vmap", action="store_true",
                   help="run same-fraction trials sequentially instead of "
                   "one vmapped attack window")
    p.add_argument("--warm-start", action="store_true",
                   help="cross-publish warm-started fixpoints (long "
                   "schedules)")
    p.add_argument("--mesh", action="store_true",
                   help="shard the peer axis over all visible devices "
                   "(peers must divide evenly by the device count)")
    p.add_argument("--trial-groups", type=int, default=None, metavar="N",
                   help="run the campaign on the nested trial x peer grid: "
                   "N trial groups, every remaining device widening each "
                   "group's peer submesh (parallel/sharding.make_trial_mesh; "
                   "N must divide the device count). Mutually exclusive "
                   "with --mesh; 0 = one group per visible device")
    p.add_argument("--checkpoint-dir", default=None,
                   help="snapshot each trial's post-window state here")
    # mesh-repair subsystem (ops/repair.py): the recovery window + knobs
    p.add_argument("--recovery-heartbeats", type=int, default=0,
                   help="post-attack repair rounds before the publish "
                   "schedule (0 = no recovery window)")
    p.add_argument("--evict", action="store_true",
                   help="arm score-based mesh eviction in the recovery "
                   "window's heartbeats")
    p.add_argument("--eviction-threshold", type=float, default=-50.0,
                   help="PRUNE mesh members scoring below this (<= 0)")
    p.add_argument("--px", action="store_true",
                   help="peer exchange on PRUNE: pruned peers learn "
                   "score-ranked candidates and may GRAFT/dial them")
    p.add_argument("--px-count", type=int, default=6,
                   help="candidate ids carried per PRUNE")
    p.add_argument("--redial", action="store_true",
                   help="starved peers (mesh degree < D_lo for "
                   "--redial-patience heartbeats) dial new connections")
    p.add_argument("--redial-patience", type=int, default=3)
    # fault-injection subsystem (ops/faults.py): scheduled windows are in
    # heartbeat-round indices A:B relative to the attack window, half-open
    p.add_argument("--crash-frac", type=float, default=0.0,
                   help="fraction of non-publisher peers that crash for "
                   "--crash-window and restart with cold mesh/score state")
    p.add_argument("--crash-window", default="0:0", metavar="A:B",
                   help="heartbeat rounds [A, B) the crash cohort is dark")
    p.add_argument("--partition-frac", type=float, default=0.0,
                   help="fraction of peers cut onto the far side of a "
                   "two-component graph partition")
    p.add_argument("--partition-window", default="0:0", metavar="A:B",
                   help="heartbeat rounds [A, B) the partition is up")
    p.add_argument("--spike-frac", type=float, default=0.0,
                   help="fraction of peers whose uplink clocks take a "
                   "latency spike during --spike-window")
    p.add_argument("--spike-window", default="0:0", metavar="A:B",
                   help="heartbeat rounds [A, B) of the latency spike")
    p.add_argument("--spike-ms", type=float, default=0.0,
                   help="extra uplink serialization delay per spiked peer")
    # cross-protocol DHT adversary (ops/dht_adversary.py): poison the
    # discovery layer, let the repair controller's redial path draw its
    # candidates from the (possibly attacked) DHT instead of random peers
    p.add_argument("--dht-eclipse", action="store_true",
                   help="lookup eclipse: attacker responders answer "
                   "FIND_NODE with sybil-only shortlists")
    p.add_argument("--dht-poison", action="store_true",
                   help="routing-table poisoning: sybil insert waves squat "
                   "honest bucket slots")
    p.add_argument("--dht-cluster", action="store_true",
                   help="sybil key clustering: mint attacker keys inside "
                   "the victim's keyspace prefix")
    p.add_argument("--dht-heal-hb", type=int, default=-1, metavar="HB",
                   help="recovery heartbeat at which the DHT heals (the "
                   "redial pool switches to honest lookups); -1 = never")
    p.add_argument("--dht-poison-per-peer", type=int, default=8,
                   help="sybil insert attempts per honest routing table")
    p.add_argument("--dht-cluster-prefix-bits", type=int, default=16,
                   help="shared victim-prefix bits of minted sybil keys")
    p.add_argument("--dht-evict-max-fails", type=int, default=1,
                   help="failed lookups a routing-table entry survives "
                   "before eviction (retry budget)")
    p.add_argument("--dht-evict-backoff-ms", type=float, default=0.0,
                   help="exponential backoff base between retries of a "
                   "failing routing-table entry")
    # trial supervisor (SupervisorConfig): timeout + bounded retry/backoff
    p.add_argument("--trial-timeout-s", type=float, default=0.0,
                   help="wall-clock ceiling per trial batch attempt "
                   "(0 = no timeout)")
    p.add_argument("--max-retries", type=int, default=2,
                   help="retry budget per trial cell before quarantine")
    p.add_argument("--retry-backoff-s", type=float, default=0.5,
                   help="base of the exponential retry backoff")
    p.add_argument("--inject-failures", type=int, default=0,
                   help="force the first N trial attempts to fail "
                   "(supervisor smoke-test hook)")
    p.add_argument("--json", default=None,
                   help="write the campaign result as strict JSON here")
    p.add_argument("--metrics-out", default=None,
                   help="write Prometheus text exposition of the "
                   "dst_testnode_attack_* series here")
    p.add_argument("--stats-json", default=None, metavar="PATH",
                   help="write the campaign's numbers here: network_size, "
                   "wall_s (the network's build and the campaign, from the "
                   "spans), spans, compile and process as `run --stats-json` "
                   "names them, and \"attack\": the campaign's counters and "
                   "\"rows\", a row a trial. Counters: trials, attacked_trials; "
                   "vmapped_windows (attack windows that ran a stack of "
                   "trials as one program) and window_heartbeats (the "
                   "heartbeats of every window dispatched, a stack's "
                   "counted once); publishes (each read back); device_reads "
                   "(device->host reads of the campaign: one a window for "
                   "its observable curves, nine a publish (the clock, then "
                   "eight leaves of the result one by one), three an "
                   "attacked trial and one a benign one for the metrics; a "
                   "supervised retry's are in it; the repair, "
                   "DHT and checkpoint options read without counting); over "
                   "the attacked trials honest_coverage_min, "
                   "latency_inflation_max (honest p50 over the same seed's "
                   "benign p50), hb_to_graylist_max (the window round by "
                   "which 95 %% of honest->attacker edges were graylisted, "
                   "-1 if a trial never got there: compare hb_budget, the "
                   "closed form), graylisted_frac_final_min, "
                   "attacker_mesh_share_peak (the largest attacker share "
                   "of honest mesh edges any round of any window saw), "
                   "attacker_score_final_mean. A row: fraction, seed, "
                   "attackers, honest_coverage, latency_p50_ms, "
                   "latency_p99_ms, benign_p50_ms, latency_inflation, "
                   "hb_to_graylist, mesh_recovery_hb (first round after "
                   "its peak at which the attacker mesh share is back "
                   "under 5 %%)")
    a = p.parse_args(argv)

    def _window(spec: str, flag: str) -> tuple[int, int]:
        try:
            lo, hi = spec.split(":")
            return int(lo), int(hi)
        except ValueError:
            p.error(f"{flag} must be A:B heartbeat indices, got {spec!r}")

    from .ops.adversary import AdaptivePolicy, AdversaryParams
    from .ops.dht_adversary import DhtAdversaryParams
    from .ops.faults import FaultParams
    from .ops.repair import RepairParams
    from .runtime.campaign import (
        CampaignConfig, SupervisorConfig, attack_gossipsub, run_campaign)
    from .runtime.profiling import process_summary, span, turn
    from .runtime.simulator import ExperimentConfig
    from .runtime.summarize import report_campaign, sanitize_nonfinite

    try:
        validate_attack_flags(
            a.scenario,
            mimic_margin=a.mimic_margin,
            rotation_period_hb=a.rotation_period_hb,
            dht_attack=(a.dht_eclipse or a.dht_poison or a.dht_cluster),
            dht_heal_hb=a.dht_heal_hb,
            adaptive=a.adaptive,
            throttle_margin=a.throttle_margin,
            px_poison_per_hb=a.px_poison_per_hb,
        )
    except ValueError as e:
        p.error(str(e))

    fractions = tuple(float(s) for s in a.fractions.split(",") if s.strip())
    seeds = tuple(int(s) for s in a.seeds.split(",") if s.strip())
    adv_kw: dict = {}
    if a.mimic_margin is not None:
        adv_kw["mimic_margin"] = a.mimic_margin
    if a.rotation_period_hb is not None:
        adv_kw["rotation_period_hb"] = a.rotation_period_hb
    if a.adaptive:
        pol_kw: dict = {"enabled": True}
        if a.throttle_margin is not None:
            pol_kw["throttle_margin"] = a.throttle_margin
        if a.px_poison_per_hb is not None:
            pol_kw["px_poison_per_hb"] = a.px_poison_per_hb
        adv_kw["adaptive"] = AdaptivePolicy(**pol_kw)
    # eclipse needs a mesh-bound publish to have anything to eclipse
    gs = attack_gossipsub(
        flood_publish=(a.scenario != "eclipse_publisher"))
    cfg = CampaignConfig(
        scenario=a.scenario,
        fractions=fractions,
        seeds=seeds,
        experiment=ExperimentConfig(
            topo=TopoParams(
                network_size=a.peers, anchor_stages=3,
                msg_size_bytes=a.msg_size, messages=a.messages,
                delay_seconds=a.delay_s),
            connect_to=a.connect_to,
            gossipsub=gs,
            publisher_id=a.publisher_id,
            warmup_s=a.warmup_s,
            seed=a.seed,
            warm_start=a.warm_start,
        ),
        adversary=AdversaryParams(
            scenario=a.scenario, violation_penalty=a.violation_penalty,
            **adv_kw),
        attack_heartbeats=a.attack_heartbeats,
        vmap_trials=not a.no_vmap,
        checkpoint_dir=a.checkpoint_dir,
        recovery_heartbeats=a.recovery_heartbeats,
        repair=RepairParams(
            evict=a.evict, eviction_threshold=a.eviction_threshold,
            px=a.px, px_count=a.px_count,
            redial=a.redial, redial_patience=a.redial_patience),
        faults=FaultParams(
            crash_frac=a.crash_frac,
            crash_window=_window(a.crash_window, "--crash-window"),
            partition_frac=a.partition_frac,
            partition_window=_window(a.partition_window,
                                     "--partition-window"),
            spike_frac=a.spike_frac,
            spike_window=_window(a.spike_window, "--spike-window"),
            spike_ms=a.spike_ms),
        dht=DhtAdversaryParams(
            lookup_eclipse=a.dht_eclipse,
            rtable_poison=a.dht_poison,
            sybil_cluster=a.dht_cluster,
            heal_hb=a.dht_heal_hb,
            poison_per_peer=a.dht_poison_per_peer,
            cluster_prefix_bits=a.dht_cluster_prefix_bits,
            evict_max_fails=a.dht_evict_max_fails,
            evict_backoff_ms=a.dht_evict_backoff_ms),
        supervisor=SupervisorConfig(
            trial_timeout_s=a.trial_timeout_s,
            max_retries=a.max_retries,
            retry_backoff_s=a.retry_backoff_s,
            inject_failures=a.inject_failures),
    )
    mesh = None
    if a.mesh:
        from .parallel.sharding import make_peer_mesh

        mesh = make_peer_mesh()
        if a.peers % len(mesh.devices.flat) != 0:
            p.error(f"--mesh needs peers ({a.peers}) divisible by the "
                    f"device count ({len(mesh.devices.flat)})")
    trial_mesh = None
    if a.trial_groups is not None:
        if a.mesh:
            p.error("--trial-groups and --mesh are mutually exclusive "
                    "(the trial grid already owns every device)")
        from .parallel.sharding import make_trial_mesh

        try:
            trial_mesh = make_trial_mesh(a.trial_groups or None)
        except ValueError as e:
            p.error(str(e))
    # the turn's identifier is the campaign's seed; `seed` on a span is the
    # trial's
    with turn(campaign_seed=a.seed) as spans:
        res = run_campaign(cfg, mesh=mesh, trial_mesh=trial_mesh)
        # the program's one clock, from the spans: the build and the sweep
        wall = sum(spans.seconds(name) for name in (
            "run/topology", "run/simulator_init", "run/campaign"))
        with span("run/summary"):
            d = res.to_dict()
        with span("run/report"):
            print(report_campaign(d), end="")
        if a.json:
            with span("run/write_json"), open(a.json, "w") as f:
                # strict JSON: non-finite metrics are already nulled by
                # to_dict
                json.dump(d, f, indent=2, allow_nan=False)
        if a.metrics_out:
            from .runtime.metrics import CampaignMetrics

            m = CampaignMetrics()
            m.fill_from_campaign(d)
            with open(a.metrics_out, "w") as f:
                f.write(m.render())
        if a.stats_json:
            rows = ("fraction", "seed", "attackers", "honest_coverage",
                    "latency_p50_ms", "latency_p99_ms", "benign_p50_ms",
                    "latency_inflation", "hb_to_graylist",
                    "mesh_recovery_hb")
            with span("run/stats_json"), open(a.stats_json, "w") as f:
                json.dump(
                    sanitize_nonfinite({
                        "network_size": res.network_size,
                        "wall_s": wall,
                        "spans": spans.totals(),
                        "compile": spans.compile.as_dict(),
                        **({"process": process_summary()}
                           if spans.number == 1 else {}),
                        "attack": {
                            **res.counters,
                            "rows": [
                                {k: t[k] for k in rows}
                                for t in d["trials"]]},
                    }),
                    f, indent=2, allow_nan=False)
        print(f"[tpu backend] wall={wall:.2f}s trials={len(res.trials)} "
              f"trials/s={len(res.trials) / max(wall, 1e-9):.3f}")
    return 0


def cmd_pareto(argv: list[str]) -> int:
    """Defense Pareto sweep: grid the score-defense knobs (mesh degree band,
    slow-peer penalty weight) against the ADAPTIVE attacker and report the
    coverage-vs-bandwidth-vs-recovery-time front (runtime/campaign.
    run_defense_sweep). Every grid point is a full campaign under a fresh
    GossipSubParams — i.e. a fresh jit cache entry — so keep grids small."""
    p = argparse.ArgumentParser(prog="pareto")
    from .ops.adversary import ADAPTIVE_SCENARIOS

    p.add_argument("--scenario", choices=ADAPTIVE_SCENARIOS,
                   default="eclipse_publisher",
                   help="adaptive-capable scenario the sweep defends "
                   "against (eclipse_publisher gives the sharpest "
                   "recovery_time_ms separation)")
    p.add_argument("-n", "--peers", type=int, default=64)
    p.add_argument("--fractions", default="0.2",
                   help="comma-separated ATTACKED fractions (> 0); the "
                   "sweep aggregates over all of them")
    p.add_argument("--seeds", default="0,1")
    p.add_argument("--messages", type=int, default=2)
    p.add_argument("--msg-size", type=int, default=2000)
    p.add_argument("--delay-s", type=float, default=0.5)
    p.add_argument("--warmup-s", type=float, default=8.0)
    p.add_argument("--attack-heartbeats", type=int, default=6)
    p.add_argument("--recovery-heartbeats", type=int, default=8)
    p.add_argument("--connect-to", type=int, default=8)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--publisher-id", type=int, default=4)
    p.add_argument("--throttle-margin", type=float, default=None,
                   help="adaptive duty-cycle setpoint (0 < m < 1)")
    p.add_argument("--degree-grid", default="4:6:8,4:4:6",
                   metavar="DL:D:DH[,...]",
                   help="comma-separated d_low:d:d_high degree bands to "
                   "sweep (the defaults are inserted if absent)")
    p.add_argument("--weight-grid", default="-10",
                   metavar="W[,...]",
                   help="comma-separated slow_peer_penalty_weight values "
                   "(<= 0) to sweep")
    p.add_argument("--trial-groups", type=int, default=None, metavar="N",
                   help="nested trial x peer sharding for every campaign "
                   "in the sweep (parallel/sharding.make_trial_mesh)")
    p.add_argument("--json", default=None,
                   help="write the sweep artifact as strict JSON here")
    a = p.parse_args(argv)

    from .ops.adversary import AdaptivePolicy, AdversaryParams
    from .ops.repair import RepairParams
    from .runtime.campaign import (
        CampaignConfig, attack_gossipsub, run_defense_sweep)
    from .runtime.simulator import ExperimentConfig
    from .runtime.summarize import report_defense_sweep

    try:
        degree_grid = tuple(
            tuple(int(x) for x in band.split(":"))
            for band in a.degree_grid.split(",") if band.strip())
        if any(len(b) != 3 for b in degree_grid):
            raise ValueError
    except ValueError:
        p.error(f"--degree-grid must be DL:D:DH[,DL:D:DH...], got "
                f"{a.degree_grid!r}")
    weight_grid = tuple(
        float(s) for s in a.weight_grid.split(",") if s.strip())
    fractions = tuple(float(s) for s in a.fractions.split(",") if s.strip())
    if not fractions or any(f <= 0.0 for f in fractions):
        p.error("--fractions must list attacked fractions > 0 (the sweep "
                "measures the defense against the armed attacker; benign "
                "baselines belong to the attack subcommand)")
    seeds = tuple(int(s) for s in a.seeds.split(",") if s.strip())
    pol_kw: dict = {"enabled": True}
    if a.throttle_margin is not None:
        pol_kw["throttle_margin"] = a.throttle_margin
    cfg = CampaignConfig(
        scenario=a.scenario,
        fractions=fractions,
        seeds=seeds,
        experiment=ExperimentConfig(
            topo=TopoParams(
                network_size=a.peers, anchor_stages=3,
                msg_size_bytes=a.msg_size, messages=a.messages,
                delay_seconds=a.delay_s),
            connect_to=a.connect_to,
            gossipsub=attack_gossipsub(
                flood_publish=(a.scenario != "eclipse_publisher")),
            publisher_id=a.publisher_id,
            warmup_s=a.warmup_s,
            seed=a.seed,
        ),
        adversary=AdversaryParams(
            scenario=a.scenario, adaptive=AdaptivePolicy(**pol_kw)),
        attack_heartbeats=a.attack_heartbeats,
        recovery_heartbeats=a.recovery_heartbeats,
        repair=RepairParams(evict=True, px=True, redial=True),
    )
    trial_mesh = None
    if a.trial_groups is not None:
        from .parallel.sharding import make_trial_mesh

        try:
            trial_mesh = make_trial_mesh(a.trial_groups or None)
        except ValueError as e:
            p.error(str(e))
    sweep = run_defense_sweep(cfg, degree_grid=degree_grid,
                              weight_grid=weight_grid,
                              trial_mesh=trial_mesh)
    print(report_defense_sweep(sweep), end="")
    if a.json:
        with open(a.json, "w") as f:
            # strict JSON: run_defense_sweep sanitizes non-finite values
            json.dump(sweep, f, indent=2, allow_nan=False)
    return 0


def cmd_arena(argv: list[str]) -> int:
    """Protocol arena: race GossipSub against the episub tree backend on
    identical epoch graphs, traffic schedules, fault cohorts, and the
    adaptive attacker (runtime/campaign.run_arena_campaign), and report
    the per-scenario win matrix. The benign scenario rides along by
    default — it is the bandwidth-floor row the arena bench gate reads."""
    p = argparse.ArgumentParser(prog="arena")
    from .ops.adversary import ADAPTIVE_SCENARIOS

    p.add_argument("--scenarios", default="benign,sybil_graft_flood",
                   help="comma-separated scenario list; 'benign' is the "
                   "reserved no-attacker row, the rest must be "
                   f"adaptive-capable ({', '.join(ADAPTIVE_SCENARIOS)})")
    p.add_argument("-n", "--peers", type=int, default=64)
    p.add_argument("--fraction", type=float, default=0.25,
                   help="attacker fraction for every attack scenario")
    p.add_argument("--seeds", default="0,1")
    p.add_argument("--messages", type=int, default=2)
    p.add_argument("--msg-size", type=int, default=2000)
    p.add_argument("--delay-s", type=float, default=0.5)
    p.add_argument("--warmup-s", type=float, default=8.0)
    p.add_argument("--attack-heartbeats", type=int, default=8)
    p.add_argument("--connect-to", type=int, default=8)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--publisher-id", type=int, default=4)
    p.add_argument("--throttle-margin", type=float, default=None,
                   help="adaptive duty-cycle setpoint (0 < m < 1)")
    p.add_argument("--lazy-degree", type=int, default=None,
                   help="episub lazy-IHAVE budget per round (default: "
                   "the GossipSub d_lazy derivation)")
    p.add_argument("--trial-groups", type=int, default=None, metavar="N",
                   help="nested trial x peer sharding for both windows "
                   "(parallel/sharding.make_trial_mesh)")
    p.add_argument("--json", default=None,
                   help="write the arena artifact as strict JSON here")
    a = p.parse_args(argv)

    from .ops.adversary import AdaptivePolicy, AdversaryParams
    from .ops.episub import EpisubParams
    from .runtime.campaign import (
        CampaignConfig, attack_gossipsub, run_arena_campaign)
    from .runtime.simulator import ExperimentConfig
    from .runtime.summarize import report_arena

    scenarios = tuple(s.strip() for s in a.scenarios.split(",") if s.strip())
    attack_scs = [s for s in scenarios if s != "benign"]
    bad = [s for s in attack_scs if s not in ADAPTIVE_SCENARIOS]
    if bad:
        p.error(f"scenarios {bad} are not adaptive-capable; choose from "
                f"'benign', {', '.join(ADAPTIVE_SCENARIOS)}")
    if not attack_scs:
        p.error("--scenarios needs at least one attack scenario beside "
                "'benign' (the arena's referee is the adaptive attacker)")
    if not 0.0 < a.fraction < 1.0:
        p.error("--fraction must be in (0, 1)")
    seeds = tuple(int(s) for s in a.seeds.split(",") if s.strip())
    pol_kw: dict = {"enabled": True}
    if a.throttle_margin is not None:
        pol_kw["throttle_margin"] = a.throttle_margin
    cfg = CampaignConfig(
        scenario=attack_scs[0],
        fractions=(a.fraction,),
        seeds=seeds,
        experiment=ExperimentConfig(
            topo=TopoParams(
                network_size=a.peers, anchor_stages=3,
                msg_size_bytes=a.msg_size, messages=a.messages,
                delay_seconds=a.delay_s),
            connect_to=a.connect_to,
            # flood_publish off: arena traffic must ride mesh_mask, the
            # surface the two protocols differ on
            gossipsub=attack_gossipsub(flood_publish=False),
            publisher_id=a.publisher_id,
            warmup_s=a.warmup_s,
            seed=a.seed,
        ),
        adversary=AdversaryParams(
            scenario=attack_scs[0], adaptive=AdaptivePolicy(**pol_kw)),
        attack_heartbeats=a.attack_heartbeats,
    )
    ep = None
    if a.lazy_degree is not None:
        ep = EpisubParams(root=a.publisher_id % a.peers,
                          lazy_degree=a.lazy_degree)
    trial_mesh = None
    if a.trial_groups is not None:
        from .parallel.sharding import make_trial_mesh

        try:
            trial_mesh = make_trial_mesh(a.trial_groups or None)
        except ValueError as e:
            p.error(str(e))
    arena = run_arena_campaign(cfg, scenarios=scenarios, ep=ep,
                               trial_mesh=trial_mesh)
    print(report_arena(arena), end="")
    if a.json:
        with open(a.json, "w") as f:
            # strict JSON: run_arena_campaign sanitizes non-finite values
            json.dump(arena, f, indent=2, allow_nan=False)
    return 0


def cmd_serve(argv: list[str]) -> int:
    """Run as a long-lived node service (the reference's steady-state node:
    HTTP /publish + /health + /ready on :8645, Prometheus on :8008), hosting
    the whole simulated network in-process and exposing the env-selected
    peer's view (getPeerDetails, env.nim:13-36)."""
    p = argparse.ArgumentParser(prog="serve")
    p.add_argument("--control-port", type=int, default=None)
    p.add_argument("--metrics-port", type=int, default=None)
    p.add_argument("--time-scale", type=float, default=1.0,
                   help="simulated seconds advanced per wall second")
    p.add_argument("--tick-s", type=float, default=1.0)
    p.add_argument("--duration-s", type=float, default=None)
    p.add_argument("--warmup-s", type=float, default=15.0,
                   help="heartbeats run before serving (mesh stabilization, "
                   "main.nim:466-477)")
    p.add_argument("--store-metrics-dir", default=None)
    # resident-runtime surface (ARCHITECTURE §16): admission control,
    # batching dispatch, supervision, crash-safe warm restart
    p.add_argument("--queue-depth", type=int, default=1024,
                   help="bounded admission queue; overflow answers 429")
    p.add_argument("--device-ms-budget", type=float, default=0.0,
                   help="reject once est. queued device ms exceeds this")
    p.add_argument("--deadline-ms", type=float, default=0.0,
                   help="default per-request sim-time deadline (0 = none)")
    p.add_argument("--max-batch", type=int, default=64,
                   help="requests per service round (tenant round-robin)")
    p.add_argument("--dispatch-mode", default="batched",
                   choices=("batched", "sequential"),
                   help="batched = one stacked device dispatch per "
                   "same-shape group of the round (ISSUE 14); sequential = "
                   "the pinned per-request reference path")
    p.add_argument("--dispatch-timeout-s", type=float, default=0.0)
    p.add_argument("--max-retries", type=int, default=1)
    p.add_argument("--retry-backoff-s", type=float, default=0.05)
    p.add_argument("--inject-failures", type=int, default=0,
                   help="force the first K dispatch attempts to fail (CI)")
    p.add_argument("--checkpoint", default=None,
                   help="service checkpoint path (periodic + final flush)")
    p.add_argument("--checkpoint-every", type=int, default=0,
                   help="flush every K service rounds (0 = final only)")
    p.add_argument("--drain-deadline-s", type=float, default=5.0)
    p.add_argument("--resume", action="store_true",
                   help="warm-restart from --checkpoint if it exists")
    a = p.parse_args(argv)

    from .config.env import (
        HTTP_CONTROL_PORT,
        PROMETHEUS_PORT,
        env_float,
        get_peer_details,
    )
    from .runtime.node_service import ServiceConfig, serve_forever
    from .runtime.simulator import ExperimentConfig, Simulator

    node = get_peer_details()
    node.validate()  # reject unknown muxer / connect_to >= peers at startup
    svc_cfg = ServiceConfig(
        max_queue_depth=a.queue_depth,
        device_ms_budget=a.device_ms_budget,
        default_deadline_ms=a.deadline_ms,
        max_batch=a.max_batch,
        dispatch_mode=a.dispatch_mode,
        dispatch_timeout_s=a.dispatch_timeout_s,
        max_retries=a.max_retries,
        retry_backoff_s=a.retry_backoff_s,
        inject_failures=a.inject_failures,
        checkpoint_path=a.checkpoint,
        checkpoint_every=a.checkpoint_every,
        drain_deadline_s=a.drain_deadline_s,
    )
    svc_cfg.validate()
    if a.resume and not a.checkpoint:
        p.error("--resume requires --checkpoint")
    resume_from = a.checkpoint if (a.resume and a.checkpoint
                                   and os.path.exists(a.checkpoint)) else None
    if resume_from is not None:
        # warm restart: the checkpoint carries sim + service state, so skip
        # building and warming a simulator that restore() would discard
        store_dir = a.store_metrics_dir
        if store_dir is None and node.in_shadow:
            store_dir = "."
        control = (a.control_port if a.control_port is not None
                   else HTTP_CONTROL_PORT)
        metrics = (a.metrics_port if a.metrics_port is not None
                   else PROMETHEUS_PORT)
        print(f"node service warm-restarting from {resume_from}, "
              f"control :{control} metrics :{metrics}")
        serve_forever(
            None, node,
            control_port=control, metrics_port=metrics,
            time_scale=a.time_scale, tick_s=a.tick_s,
            duration_s=a.duration_s,
            store_metrics_dir=store_dir, out=sys.stdout,
            service=svc_cfg, resume_from=resume_from,
        )
        return 0
    topo = TopoParams(
        network_size=node.network_size,
        muxer=node.muxer,
        num_frags=node.fragments,
    )
    topics = tuple(
        s.strip() for s in env_str("TOPICS", "").split(",") if s.strip())
    if len(topics) == 1:
        node.topic = topics[0]  # single custom topic, single-topic engine
    if len(topics) > 1:
        # multi-topic node: /publish routes by topic name (BASELINE config 3
        # surface); SUBSCRIBE_FRACTION < 1 subscribes each peer per topic
        if node.uses_mix or node.mounts_mix:
            p.error("mix routing (USESMIX/MOUNTSMIX) is single-topic only; "
                    "drop TOPICS or the mix surface")
        from .runtime.multitopic import MultiTopicConfig, MultiTopicSimulator

        sim = MultiTopicSimulator(MultiTopicConfig(
            topo=topo,
            topics=topics,
            connect_to=node.connect_to,
            gossipsub=node.gossipsub,
            warmup_s=a.warmup_s,
            subscribe_fraction=env_float("SUBSCRIBE_FRACTION", 1.0),
            max_connections=node.max_connections,
            self_trigger=node.self_trigger,
        ))
    else:
        cfg = ExperimentConfig(
            topo=topo,
            connect_to=node.connect_to,
            gossipsub=node.gossipsub,
            warmup_s=a.warmup_s,
            self_trigger=node.self_trigger,
            max_connections=node.max_connections,
            uses_mix=node.uses_mix,
            num_mix=node.num_mix,
            mix_d=node.mix_d,
        )
        sim = Simulator(cfg)
    sim.warmup()
    store_dir = a.store_metrics_dir
    if store_dir is None and node.in_shadow:
        store_dir = "."  # in-Shadow persistence default (env.nim:58-73)
    control = a.control_port if a.control_port is not None else HTTP_CONTROL_PORT
    metrics = a.metrics_port if a.metrics_port is not None else PROMETHEUS_PORT
    print(
        f"node service up: {node.network_size} peers simulated, node view "
        f"peer {node.my_id}, control :{control} metrics :{metrics}"
    )
    serve_forever(
        sim, node,
        control_port=control, metrics_port=metrics,
        time_scale=a.time_scale, tick_s=a.tick_s, duration_s=a.duration_s,
        store_metrics_dir=store_dir, out=sys.stdout,
        service=svc_cfg,
    )
    return 0


def cmd_kad(argv: list[str]) -> int:
    """Role-based kad-dht workload (kad-dht/main.nim:15-72): bootstrap
    anchors + RoleNormal warmup + RoleProbe lookup loop, batched."""
    p = argparse.ArgumentParser(
        prog="kad",
        description="The reference's kad-dht node, every role at once: the "
        "bootstraps are seeded into every table, every RoleNormal peer runs "
        "5 FIND_NODE(self) waves a second apart and 15 on random targets two "
        "seconds apart, every RoleProbe peer then looks up a random target "
        "every 5 s under a 30 s time-out.",
        epilog="Environment, as the node reads it (kad-dht/env.nim): PEERS "
        "(100), KAD_BOOTSTRAPS (3: peers 0.. are the anchors), KAD_PROBES "
        "(10: the highest ids), DISCOVERY (kad-dht | extended), MUXER "
        "(yamux), SEED (0), and KAD_LEARN_CAP (8: the origins a queried "
        "peer learns of one wave; `all` for every requester, as KadDHT "
        "adds them).")
    p.add_argument("-n", "--nodes", type=int, default=None,
                   help="defaults to PEERS env")
    p.add_argument("--bootstraps", type=int, default=None)
    p.add_argument("--probes", type=int, default=None)
    p.add_argument("--discovery", choices=["kad-dht", "extended"], default=None)
    p.add_argument("--duration-s", type=float, default=60.0,
                   help="length of the probe loop (a tick every 5 s)")
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--log", default=None, help="write node log lines here")
    p.add_argument("--stats-json", default=None, metavar="PATH",
                   help="write the experiment's numbers here: network_size, "
                   "wall_s (boot, warm-up, probe loop and their reads, from "
                   "the spans), spans, compile and process as `run "
                   "--stats-json` names them, and \"kad\": lookups, "
                   "warmup_waves, probe_ticks; hops_mean (rounds in which a "
                   "shortlist still improved) and queries_per_lookup "
                   "(FIND_NODE requests sent, at most 18) over all lookups; "
                   "census_mean, census_min (entries a routing table holds "
                   "at the end: a minimum near the bootstraps' count says "
                   "some peer learned nothing); bucket_full_share (of the "
                   "peers a wave offered to a table as new, the share whose "
                   "bucket was full and which were dropped: it rises as the "
                   "tables fill); packed_share (of the FIND_NODE calls, the "
                   "share in which every routing table fitted the width the "
                   "responses sort, ops/kad.packed_width: under 1 the tables "
                   "hold more than uniform keys allow and a response sorts "
                   "twice); probe_lookups, probe_success, "
                   "probe_success_share (under the 30 s time-out); "
                   "closest1_share (of the last random warm-up wave's and "
                   "the probes' lookups, the share that returned first the "
                   "peer a brute force over every key finds closest: "
                   "lookups that converge read near 1); queries_tx / "
                   "queries_rx summed over the peers (equal: every request "
                   "is served), queries_per_bootstrap; lookup_latency_ms: a "
                   "wave or tick its kind, p50 and p99, in order, a round "
                   "costing its slowest query")
    a = p.parse_args(argv)

    from .runtime.kad_runtime import KadSimulator, config_from_env
    from .runtime.profiling import process_summary, span, turn

    cfg = config_from_env()
    if a.nodes is not None:
        cfg.network_size = a.nodes
    if a.bootstraps is not None:
        cfg.n_bootstrap = a.bootstraps
    if a.probes is not None:
        cfg.n_probe = a.probes
    if a.discovery is not None:
        cfg.discovery = a.discovery
    if a.seed is not None:
        cfg.seed = a.seed
    cfg.probe_duration_s = a.duration_s
    cfg.validate()
    with turn(seed=cfg.seed) as spans:
        sim = KadSimulator(cfg)
        summary = sim.run()
        # the program's one clock, from the spans: build, phases, reads
        wall = sum(spans.seconds(name) for name in (
            "run/topology", "run/boot", "run/warmup", "run/probe",
            "run/record"))
        if a.log:
            with span("run/write_log"), open(a.log, "w") as f:
                f.write("\n".join(sim.lines) + "\n")
        kad_stats = sim.stats(summary)
        with span("run/report"):
            print(summary.report())
            print(f"[tpu backend] wall={wall:.2f}s "
                  f"lookups={kad_stats['lookups']}")
        if a.stats_json:
            from .runtime.summarize import sanitize_nonfinite

            with span("run/stats_json"), open(a.stats_json, "w") as f:
                json.dump(
                    sanitize_nonfinite({
                        "network_size": cfg.network_size,
                        "wall_s": wall,
                        "spans": spans.totals(),
                        "compile": spans.compile.as_dict(),
                        **({"process": process_summary()}
                           if spans.number == 1 else {}),
                        "kad": kad_stats,
                    }),
                    f, indent=2, allow_nan=False)
    return 0


def cmd_connmanager(argv: list[str]) -> int:
    """Hub-and-spoke connection-manager stress (connmanager/main.nim):
    watermark trimming + reconnect strategies, driven by the WATERMARK_*/
    RECONNECT env surface with flag overrides."""
    p = argparse.ArgumentParser(prog="connmanager")
    p.add_argument("--duration-s", type=int, default=None)
    p.add_argument("--trace", default=None,
                   help="write the per-tick hub connection counts (CSV)")
    a = p.parse_args(argv)

    from .ops.connmanager import config_from_env, run_connmanager

    cfg = config_from_env()
    if a.duration_s is not None:
        cfg.duration_s = a.duration_s
    t0 = time.time()
    summary, _ = run_connmanager(cfg)
    wall = time.time() - t0
    if a.trace:
        import numpy as np

        np.savetxt(a.trace, summary.trace, fmt="%d", delimiter=",")
    print(summary.report())
    print(f"[tpu backend] wall={wall:.2f}s ticks={len(summary.trace)}")
    return 0


def cmd_regression(argv: list[str]) -> int:
    """Regression workload (regression/main.nim): GossipSub mesh formed via
    kad-dht bootstrap + mesh ping probes + standard latency output."""
    p = argparse.ArgumentParser(
        prog="regression",
        description="The reference's regression node: every peer seeds its "
        "kad-dht table with the bootstrap, runs a FIND_NODE(self) wave and "
        "warm-up waves on random targets, dials CONNECTTO peers of its "
        "routing table, GossipSub (D 6, D_low 4, D_high 8) warms up for "
        "STARTSLEEP / 4 seconds, the first normal peer publishes, and "
        "every mesh peer is pinged each 45 s.",
        epilog="Environment, as the node reads it (regression/env.nim): "
        "PEERS (100), CONNECTTO (10), STARTSLEEP (180, seconds), FRAGMENTS "
        "(1), MUXER (yamux), SEED (0), and REGRESSION_BOOTSTRAPS (1: peers "
        "0.. are the anchors; the first peer after them publishes).")
    p.add_argument("-n", "--nodes", type=int, default=None,
                   help="overrides PEERS")
    p.add_argument("--messages", type=int, default=None)
    p.add_argument("--msg-size", type=int, default=None)
    p.add_argument("--log", default=None)
    p.add_argument("--latencies", default=None,
                   help="write awk-compatible latencies file here")
    p.add_argument("--stats-json", default=None, metavar="PATH",
                   help="write the experiment's numbers here, as `run "
                   "--stats-json` names them where they apply (coverage: "
                   "mean receivers a message, with the count of every "
                   "message under coverage_by_message; spans, compile, "
                   "process, emit, build, heartbeat, publishes), and two "
                   "keys of this entry. \"kad\": the lookups of all waves "
                   "(lookups, hops_mean: rounds in which a shortlist still "
                   "improved; queries_per_lookup: FIND_NODE requests sent, "
                   "at most 18; rtable_census_mean: entries a routing "
                   "table holds after the last wave; packed_share: of the "
                   "waves, the share in which every routing table fitted "
                   "the width the responses sort, ops/kad.packed_width), "
                   "queries_tx / "
                   "queries_rx summed over the peers (equal: every request "
                   "is served), lookup_latency_ms: a wave its p50 and p99, "
                   "a round costing its slowest query; a p99 near 6 rounds "
                   "or a census under CONNECTTO says the tables are too "
                   "thin for the mesh. \"pings\": count (one a mesh edge "
                   "and round), p50_ms, p99_ms, timeouts (over 4000 ms)")
    a = p.parse_args(argv)

    from .runtime.profiling import process_summary, span, turn
    from .runtime.regression_runtime import (
        RegressionSimulator,
        config_from_env as regression_config,
    )

    cfg = regression_config()
    if a.nodes is not None:
        cfg.network_size = a.nodes
    if a.messages is not None:
        cfg.messages = a.messages
    if a.msg_size is not None:
        cfg.msg_size = a.msg_size
    cfg.validate()
    with turn(seed=cfg.seed) as spans:
        reg = RegressionSimulator(cfg)
        summary = reg.run()
        sim = reg.sim
        # the program's one clock, from the spans: discovery, build and run
        wall = sum(spans.seconds(name) for name in (
            "run/topology", "run/discover", "run/discovery_graph",
            "run/simulator_init", "run/simulate", "run/pings"))
        if a.log:
            with open(a.log, "w") as f:
                f.write("\n".join(reg.lines) + "\n")
        if a.latencies:
            with span("run/write_latencies"):
                sim.write_latencies(a.latencies)
        with span("run/summary"):
            s = sim.summary()
        with span("run/report"):
            print(summary.report())
            print(f"[tpu backend] wall={wall:.2f}s")
        if a.stats_json:
            from .runtime.summarize import sanitize_nonfinite

            with span("run/stats_json"), open(a.stats_json, "w") as f:
                json.dump(
                    sanitize_nonfinite({
                        "network_size": s.network_size,
                        # mean receivers a message, as `run` has it, and
                        # the count of each: a percentage to one decimal
                        # cannot say whether 9,999 or 10,000 peers logged
                        "coverage": s.coverage(),
                        "coverage_by_message": [
                            int(r.received.sum()) for r in sim.records],
                        "max_latency_ms": s.max_latency_ms,
                        "avg_latency_ms": s.avg_latency_ms,
                        "avg_max_latency_ms": s.avg_max_latency_ms,
                        "mesh_degree_mean": summary.mesh_degree_mean,
                        "wall_s": wall,
                        "spans": spans.totals(),
                        "compile": spans.compile.as_dict(),
                        **({"process": process_summary()}
                           if spans.number == 1 else {}),
                        "emit": sim.emit_counts,
                        "build": sim.graph.build,
                        "heartbeat": sim.heartbeat_counts,
                        "publishes": _publish_counts(sim.records),
                        "kad": reg.kad_stats,
                        "pings": reg.ping_stats(),
                    }),
                    f, indent=2, allow_nan=False)
    return 0


def cmd_servicedisco(argv: list[str]) -> int:
    """Service-discovery workload (service-discovery/main.nim): advertisers
    + discoverers + hybrid over the DHT, env-driven with flag overrides."""
    p = argparse.ArgumentParser(prog="servicedisco")
    p.add_argument("-n", "--nodes", type=int, default=None)
    p.add_argument("--duration-s", type=int, default=None)
    p.add_argument("--services", default=None,
                   help="comma-separated (ADVERTISE_SERVICES)")
    p.add_argument("--log", default=None)
    a = p.parse_args(argv)

    from .runtime.sd_runtime import SDSimulator, config_from_env

    cfg = config_from_env()
    if a.nodes is not None:
        cfg.network_size = a.nodes
    if a.duration_s is not None:
        cfg.duration_s = a.duration_s
    if a.services:
        cfg.services = [s.strip() for s in a.services.split(",") if s.strip()]
    cfg.validate()
    t0 = time.time()
    sim = SDSimulator(cfg)
    summary = sim.run()
    wall = time.time() - t0
    if a.log:
        with open(a.log, "w") as f:
            f.write("\n".join(sim.lines) + "\n")
    print(summary.report())
    print(f"[tpu backend] wall={wall:.2f}s")
    return 0


def cmd_inject(argv: list[str]) -> int:
    """Publisher controller against running `serve` nodes — the traffic_sync
    surface (-s size, -m messages, -d delay, --peer-selection id|rotation)."""
    p = argparse.ArgumentParser(prog="inject")
    p.add_argument("targets", nargs="+",
                   help="node control endpoints (host[:port] or URL)")
    p.add_argument("-s", "--msg-size", type=int, default=1500)
    p.add_argument("-m", "--messages", type=int, default=10)
    p.add_argument("-d", "--delay-s", type=float, default=1.0)
    p.add_argument("--topic", default="test")
    p.add_argument("--peer-selection", choices=["id", "rotation"], default="id")
    p.add_argument("--publisher-id", type=int, default=0)
    p.add_argument("--burst", type=int, default=1,
                   help="messages posted back-to-back before each delay — "
                   "gives a batched-dispatch service multi-request rounds")
    a = p.parse_args(argv)

    from .runtime.publisher import inject

    res = inject(
        a.targets, a.msg_size, a.messages, a.delay_s, topic=a.topic,
        peer_selection=a.peer_selection, publisher_id=a.publisher_id,
        burst=a.burst,
    )
    for r in res.replies:
        print(json.dumps(r, allow_nan=False))
    print(f"published ok={res.ok} failed={res.failed}")
    return 0 if res.failed == 0 else 1


def cmd_lint(argv: list[str]) -> int:
    """graft-audit: static certification of the hot paths (analysis/).

    Runs the AST lint over the package + bench/scripts sources and the
    jaxpr auditor over every registered entrypoint contract, then emits a
    strict-JSON violation report on stdout. Exit 0 iff clean.
    """
    p = argparse.ArgumentParser(prog="lint")
    p.add_argument("paths", nargs="*",
                   help="files/dirs for the AST engine (default: the repo's "
                        "python surface: package, bench*.py, scripts/)")
    p.add_argument("--no-ast", action="store_true",
                   help="skip the AST lint engine")
    p.add_argument("--no-jaxpr", action="store_true",
                   help="skip the jaxpr auditor (fast, no jax tracing)")
    p.add_argument("--checkify", action="store_true",
                   help="also run the opt-in runtime half of the contracts "
                        "(executes small configs under jax.experimental."
                        "checkify; slower)")
    p.add_argument("--sharding", action="store_true",
                   help="also run the sharding auditor (GA-S rules): "
                        "compile every registered contract and walk the "
                        "GSPMD output for collectives / replication / "
                        "per-device memory (slower — real XLA compiles)")
    p.add_argument("--only", default=None, metavar="PREFIX",
                   help="restrict the jaxpr + sharding engines to "
                        "contracts whose name starts with PREFIX (e.g. "
                        "campaign/)")
    p.add_argument("--predict-rung", nargs="?", const=1048576, type=int,
                   default=None, metavar="PEERS",
                   help="also fit the attack-window footprint curves and "
                        "emit the rung feasibility certificate for PEERS "
                        "(default 1048576) on a modeled v5e-8")
    p.add_argument("--rung-dcn", type=int, default=1, metavar="HOSTS",
                   help="model the rung on a HOSTS-strong pod of v5e-8 "
                        "slices joined over DCN (make_dcn_mesh placement: "
                        "each host holds its own stacked-trial slice; "
                        "default 1 = the single-slice rung)")
    p.add_argument("--rung-scenario", choices=("attack", "arena"),
                   default="attack",
                   help="which window family to fit: the GossipSub attack "
                        "window (default) or the protocol-arena window "
                        "with its EpisubCtrl leaves")
    p.add_argument("--rung-out", default=None, metavar="PATH",
                   help="also write the rung certificate alone to PATH "
                        "(strict JSON; the report embeds it either way)")
    p.add_argument("--out", default=None, metavar="PATH",
                   help="write the strict-JSON report to PATH instead of "
                        "stdout (github annotations still print to stdout)")
    p.add_argument("--format", choices=("json", "github"), default="json",
                   help="'github' additionally emits ::error/::notice "
                        "workflow-command lines so GA-* findings render "
                        "inline on PRs")
    a = p.parse_args(argv)

    from .analysis import audit_contracts, lint_paths, render_report, run_checkify
    from .analysis.registry import default_contracts
    from .analysis.report import github_annotations

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    violations = []
    waived: list[dict] = []
    sharding_facts = None
    rung_cert = None
    checked_files = 0
    checked_entrypoints = 0

    if not a.no_ast:
        if a.paths:
            targets = a.paths
        else:
            pkg = os.path.dirname(os.path.abspath(__file__))
            targets = [pkg]
            for extra in ("bench_configs.py", "scripts"):
                cand = os.path.join(repo_root, extra)
                if os.path.exists(cand):
                    targets.append(cand)
        ast_violations, checked_files = lint_paths(targets, repo_root)
        violations.extend(ast_violations)

    contracts = default_contracts()
    if a.only:
        contracts = [c for c in contracts if c.name.startswith(a.only)]
    if not a.no_jaxpr:
        checked_entrypoints = len(contracts)
        violations.extend(audit_contracts(contracts))
        if a.checkify:
            violations.extend(run_checkify(contracts))

    if a.sharding:
        from .analysis.sharding_audit import audit_sharding_contracts

        checked_entrypoints = max(checked_entrypoints, len(contracts))
        sh_violations, waived, sharding_facts = audit_sharding_contracts(
            contracts)
        violations.extend(sh_violations)

    if a.predict_rung is not None:
        from .analysis.sharding_audit import predict_rung_certificate

        spec_builder = None
        scenario = "sybil_graft_flood"
        if a.rung_scenario == "arena":
            from .analysis.registry import arena_rung_spec

            def spec_builder(n):
                return arena_rung_spec(n)

            scenario = "protocol_arena/episub"
        rung_cert = predict_rung_certificate(
            rung_peers=a.predict_rung, dcn=a.rung_dcn,
            spec_builder=spec_builder, scenario=scenario)
        if a.rung_out:
            with open(a.rung_out, "w") as fh:
                json.dump(rung_cert, fh, indent=2, sort_keys=True,
                          allow_nan=False)
                fh.write("\n")

    if a.format == "github":
        for line in github_annotations(violations, waived):
            print(line)
    report = render_report(
        violations, checked_files=checked_files,
        checked_entrypoints=checked_entrypoints,
        sharding=sharding_facts, waived=waived if a.sharding else None,
        rung=rung_cert)
    if a.out:
        with open(a.out, "w") as fh:
            fh.write(report + "\n")
    else:
        print(report)
    return 1 if violations else 0


def cmd_conform(argv: list[str]) -> int:
    """Conformance oracle: spec-differential certification of the compiled
    step against the pure-numpy GossipSub v1.1 reference model.

    Emits the strict-JSON certificate (stdout or --out). Exit 0 iff every
    divergence is absent or carries a documented_choice waiver
    (docs/CONFORMANCE.md); any sim_bug is a hard failure.
    """
    p = argparse.ArgumentParser(prog="conform")
    p.add_argument("--all-scenarios", action="store_true",
                   help="run the full canon: all 8 attack scenarios plus "
                        "the adaptive, faults, churn and cross-fragment "
                        "entries (default when no --scenario is given)")
    p.add_argument("--scenario", action="append", default=None,
                   help="restrict to specific attack scenario(s); "
                        "repeatable. Skips the adaptive/faults/churn/"
                        "gossip entries unless --all-scenarios is also set")
    p.add_argument("--n", type=int, default=48,
                   help="peers per differential instance (default 48)")
    p.add_argument("--connect-to", type=int, default=8)
    p.add_argument("--steps", type=int, default=8,
                   help="attack heartbeats walked per instance")
    p.add_argument("--warm-steps", type=int, default=4)
    p.add_argument("--seeds", type=int, nargs="+", default=[0],
                   help="fuzz seeds; each reseeds graph, state and cohort")
    p.add_argument("--fuzz", type=int, default=0, metavar="N",
                   help="append N random-parameter-grid entries: each "
                        "samples degree bounds (0 < d_low <= d <= d_high "
                        "<= capacity), gossip factor and score weights, "
                        "then runs the differential under that grid, "
                        "cycling through the attack canon. One jit compile "
                        "per sample")
    p.add_argument("--fuzz-seed", type=int, default=0,
                   help="PRNG stream for --fuzz grid sampling (default 0)")
    p.add_argument("--out", default=None,
                   help="certificate path (default: stdout)")
    a = p.parse_args(argv)

    from .analysis.conformance import (conformance_certificate,
                                       write_certificate)
    from .runtime.summarize import sanitize_nonfinite

    full = a.all_scenarios or a.scenario is None
    cert = conformance_certificate(
        scenarios=a.scenario, n=a.n, connect_to=a.connect_to,
        seeds=tuple(a.seeds), steps=a.steps, warm_steps=a.warm_steps,
        include_adaptive=full, include_faults=full, include_churn=full,
        include_gossip=full, fuzz=a.fuzz, fuzz_seed=a.fuzz_seed)
    if a.out:
        write_certificate(cert, a.out)
    else:
        print(json.dumps(sanitize_nonfinite(cert), indent=2,
                         allow_nan=False))
    for e in cert["entries"]:
        line = f"conform: {e['scenario']:<22} {e['status']}"
        if e["divergences"]:
            line += f" ({len(e['divergences'])} divergence(s), " \
                    f"{e['sim_bugs']} sim_bug(s))"
        print(line, file=sys.stderr)
    return 0 if cert["clean"] else 1


def cmd_trace(argv: list[str]) -> int:
    """Flight-recorder trace export: a self-contained mini-run (warmup
    untraced, then a recorded window) whose per-heartbeat tel_* curves are
    written as a perfetto-loadable Chrome-trace JSON plus .npz/CSV sidecars.
    Strict-JSON summary on stdout, exit 0 on success."""
    p = argparse.ArgumentParser(prog="trace")
    p.add_argument("-n", "--network-size", type=int, default=64)
    p.add_argument("--connect-to", type=int, default=6)
    p.add_argument("--heartbeats", type=int, default=20,
                   help="recorded window length in heartbeats")
    p.add_argument("--warmup-hb", type=int, default=10,
                   help="untraced mesh-stabilization rounds before recording")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--degree-bins", type=int, default=12)
    p.add_argument("--out", default="trace_out",
                   help="output directory for the trace artifacts")
    p.add_argument("--profile-dir", default=None,
                   help="also capture a jax.profiler trace into this dir")
    a = p.parse_args(argv)

    import numpy as np

    from .ops.telemetry import TelemetryParams
    from .runtime.campaign import attack_gossipsub
    from .runtime.profiling import chrome_trace, profiler_trace
    from .runtime.simulator import ExperimentConfig, Simulator
    from .runtime.summarize import sanitize_nonfinite

    cfg = ExperimentConfig(
        topo=TopoParams(network_size=a.network_size, anchor_stages=5,
                        min_bandwidth=50, max_bandwidth=150,
                        min_latency=40, max_latency=130),
        connect_to=a.connect_to,
        # armed score params: the recorder's score quantiles / graylist
        # fraction measure nothing against the compiled-out default weights
        gossipsub=attack_gossipsub(),
        warmup_s=0.0,
        seed=a.seed,
    )
    sim = Simulator(cfg)
    hb_ms = float(sim.params.heartbeat_ms)
    tp = TelemetryParams(record=True, degree_bins=a.degree_bins)
    tp.validate()
    with profiler_trace(a.profile_dir):
        sim.advance(a.warmup_hb * hb_ms)      # untraced warmup
        sim.record_telemetry(tp)
        t0_ms = float(np.asarray(sim.state.t_ms))
        sim.advance(a.heartbeats * hb_ms)     # the recorded window
    tel = sim.last_telemetry
    if not tel:
        print("flight recorder produced no rounds "
              "(heartbeats too small for the heartbeat interval?)",
              file=sys.stderr)
        return 1

    os.makedirs(a.out, exist_ok=True)
    ct = chrome_trace(tel, hb_ms, t0_ms=t0_ms,
                      name=f"gossipsub n={a.network_size} seed={a.seed}")
    trace_path = os.path.join(a.out, "trace.perfetto.json")
    with open(trace_path, "w") as fh:
        json.dump(sanitize_nonfinite(ct), fh, allow_nan=False)
    npz_path = os.path.join(a.out, "rounds.npz")
    with open(npz_path, "wb") as fh:
        np.savez_compressed(fh, **{k: np.asarray(v) for k, v in tel.items()})
    # CSV: one row per heartbeat, vector channels expanded per index
    cols = []
    for k in sorted(tel):
        arr = np.asarray(tel[k])
        if arr.ndim == 1:
            cols.append((k, arr))
        else:
            cols.extend((f"{k}_{j}", arr[:, j]) for j in range(arr.shape[1]))
    steps = int(cols[0][1].shape[0])
    csv_path = os.path.join(a.out, "rounds.csv")
    with open(csv_path, "w") as fh:
        fh.write("hb," + ",".join(k for k, _ in cols) + "\n")
        for i in range(steps):
            fh.write(f"{i}," + ",".join(
                format(float(v[i]), "g") for _, v in cols) + "\n")

    cov = np.asarray(tel["tel_mesh_coverage"])
    hits = np.nonzero(cov >= 0.9)[0]
    summary = {
        "network_size": a.network_size,
        "heartbeats": steps,
        "heartbeat_ms": hb_ms,
        "channels": sorted(tel),
        "coverage90_hb": int(hits[0]) + 1 if hits.size else -1,
        "final_mean_degree": float(np.asarray(tel["tel_mean_degree"])[-1]),
        "trace_json": trace_path,
        "rounds_npz": npz_path,
        "rounds_csv": csv_path,
        "profile_dir": a.profile_dir,
    }
    print(json.dumps(sanitize_nonfinite(summary), indent=2, allow_nan=False))
    return 0


def cmd_summarize(argv: list[str]) -> int:
    p = argparse.ArgumentParser(prog="summarize")
    p.add_argument("path")
    p.add_argument("--large", action="store_true")
    a = p.parse_args(argv)
    from .runtime.summarize import report, summarize_file

    print(report(summarize_file(a.path, large=a.large), large=a.large), end="")
    return 0


def main(argv: list[str] | None = None) -> int:
    entered = time.perf_counter()
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv:
        print(__doc__)
        return 2
    cmd, rest = argv[0], argv[1:]
    backend = env_str("SIMBACKEND", "tpu")
    platform = env_str("SIMPLATFORM", "")
    if platform and cmd not in ("topogen", "summarize"):
        # pin the JAX platform before any backend initializes (e.g.
        # SIMPLATFORM=cpu for small role-based runs where an accelerator's
        # first-compile latency dominates). config.update holds even where
        # jax was imported before this ran and read the environment then.
        # topogen/summarize are pure numpy — don't pay the jax import for
        # them.
        import jax

        jax.config.update("jax_platforms", platform)
    if cmd not in ("topogen", "summarize"):
        from .runtime import profiling
        from .runtime.compile_cache import enable_compile_cache

        profiling.mark("main", at=entered)
        enable_compile_cache()
        if not profiling.marked("backend_ready"):
            # the device runtime's start as a span of its own: otherwise
            # the import of `ops/*` (module constants) would make it
            import jax

            with profiling.span("setup/backend"):
                jax.devices()
            profiling.mark("backend_ready")
    if cmd == "topogen":
        return cmd_topogen(rest)
    if cmd == "run":
        if backend.lower() not in ("tpu", "jax"):
            print(
                f"SIMBACKEND={backend} is not provided by this package "
                "(use the reference's shadow/ harness for the shadow backend)",
                file=sys.stderr,
            )
            return 2
        return cmd_run(rest)
    if cmd == "summarize":
        return cmd_summarize(rest)
    if cmd == "serve":
        return cmd_serve(rest)
    if cmd == "attack":
        return cmd_attack(rest)
    if cmd == "pareto":
        return cmd_pareto(rest)
    if cmd == "arena":
        return cmd_arena(rest)
    if cmd == "inject":
        return cmd_inject(rest)
    if cmd == "kad":
        return cmd_kad(rest)
    if cmd == "connmanager":
        return cmd_connmanager(rest)
    if cmd == "servicedisco":
        return cmd_servicedisco(rest)
    if cmd == "regression":
        return cmd_regression(rest)
    if cmd == "lint":
        return cmd_lint(rest)
    if cmd == "conform":
        return cmd_conform(rest)
    if cmd == "trace":
        return cmd_trace(rest)
    print(f"unknown command: {cmd}\n{__doc__}", file=sys.stderr)
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
