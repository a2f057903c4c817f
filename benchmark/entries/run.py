"""The entry `run`: the run.sh experiment, `cli.main(["run", <fourteen
positionals>, <flags>, "--seed", s, "--stats-json", "--out-prefix", dir])`.

What is this entry's own of an experiment and of `correct`: the argv from
the configuration's `run` and the traffic mix's overrides; part 1, the
invariants of `stats1.json` and `latencies1` (a copy of chip_smoke.py's
`_check_latencies`, made general over the configuration's `guarantees`); the
digest part 2 compares, `latencies1`'s sha256; and part 3, the timed
experiment's publishes against benchmark/reference/des.py.

Part 3: the cell's own experiment, the argv and `--seed` of the window's
iteration 0, runs once more. For the length of that call the name
`disseminate` in `runtime.simulator` is wrapped so that the publishes
checked also return their sampled plan; the argument list stays the
program's own. The DES then replays each plan's draws on link tables of its
own (benchmark/reference/link_tables.py) with the link-model constants of
the configuration's file. The control is the same reference with every
table and event time rounded to bfloat16, put in the program's place.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import random
import re
from dataclasses import dataclass

import numpy as np

from benchmark.harness.experiment import Outcome, run_experiment
from benchmark.reference import des, link_tables

# run.sh's positional order (shadow/run.sh:23-38), as `run` takes them
POSITIONALS = (
    "runs", "nodes", "msg_size", "num_frag", "num_publishers",
    "min_bandwidth", "max_bandwidth", "min_latency", "max_latency",
    "anchor_stages", "packet_loss", "publisher_id", "publisher_rotation",
    "inter_message_delay_ms")

_LINE = re.compile(
    rb"shadow\.data/hosts/peer(\d+)/main\.1000\.stdout:\d+:\d+ "
    rb"milliseconds: (\d+)\n")


# ---------------------------------------------------------- the invocation


def arguments(cell) -> dict:
    """The `run` positionals and flags: the configuration's, with what the
    traffic mix overrides (positionals by name, flags appended)."""
    run = cell.config["run"]
    positionals = dict(run["positionals"])
    positionals.update(cell.traffic.get("positionals", {}))
    flags = list(run.get("flags", [])) + list(cell.traffic.get("flags", []))
    return {"positionals": positionals, "flags": flags}


def run_argv(argv: dict, seed: int, out_dir: str) -> list[str]:
    """The argv of one experiment."""
    pos = argv["positionals"]
    missing = [k for k in POSITIONALS if k not in pos]
    if missing:
        raise SystemExit(f"benchmark: the configuration's run lacks {missing}")
    return ["run", *(str(pos[k]) for k in POSITIONALS), *argv["flags"],
            "--seed", str(seed), "--stats-json",
            "--out-prefix", out_dir + os.sep]


def invocation(cell, seed: int, out_dir: str) -> tuple[list[str], dict]:
    """(argv, env) of one experiment; `run` reads no environment."""
    return run_argv(arguments(cell), seed, out_dir), {}


# ------------------------------------------------- part 1 and the digest


def check_artifacts(out_dir: str, argv: dict,
                    guarantees: dict) -> tuple[list[str], str, dict]:
    """The exact invariants of one finished experiment (part 1 of `correct`),
    as the configuration's `guarantees` state them: `coverage_share_min` of
    the peers receive every message; `latencies1` has one line of the
    `<msgId> milliseconds: <ms>` form per receipt; no delay under
    `no_delay_under_ms` (null: not held) but each message's publisher's own
    0. Returns (faults, sha256 of latencies1, stats1.json)."""
    pos = argv["positionals"]
    peers, messages = int(pos["nodes"]), int(pos["num_publishers"])
    publisher = int(pos["publisher_id"])
    rotation = bool(int(pos["publisher_rotation"]))
    faults = []
    try:
        with open(os.path.join(out_dir, "stats1.json")) as f:
            stats = json.load(f)
        with open(os.path.join(out_dir, "latencies1"), "rb") as f:
            latencies = f.read()
    except OSError as e:
        return [f"artifact missing: {e}"], "", {}
    # stats coverage is mean receivers per message (runtime/summarize.py)
    coverage = stats.get("coverage")
    floor = float(guarantees["coverage_share_min"]) * peers
    if not isinstance(coverage, (int, float)) or not (
            floor <= coverage <= peers):
        faults.append(f"coverage {coverage} of {peers} peers, guaranteed "
                      f"at least {floor}")
        coverage = peers
    faults += latency_lines_faults(
        latencies, messages, coverage, guarantees["no_delay_under_ms"],
        None if rotation else publisher)
    return faults, hashlib.sha256(latencies).hexdigest(), stats


def latency_lines_faults(latencies: bytes, messages: int, coverage: float,
                         min_ms, publisher) -> list[str]:
    """What the latencies file of `messages` messages, each received by
    `coverage` peers, keeps: the line form, the line count, and no delay
    under `min_ms` (None: not held) but each message's publisher's own 0
    (`publisher` None: whoever published)."""
    faults = []
    rows, matched = [], 0
    for m in _LINE.finditer(latencies):
        rows.append((int(m.group(1)), int(m.group(2))))
        matched += m.end() - m.start()
    n_lines = latencies.count(b"\n")
    if n_lines != round(coverage * messages):
        faults.append(f"latencies1 has {n_lines} lines, expected {messages} "
                      f"messages x {coverage} receivers")
    if len(rows) != n_lines or matched != len(latencies):
        faults.append("latencies1 has lines outside the "
                      "'<msgId> milliseconds: <ms>' form")
    if min_ms is not None:
        early = [(p, d) for p, d in rows if d < min_ms]
        sound = (len(early) == messages and all(d == 0 for _, d in early)
                 and (publisher is None
                      or all(p == publisher for p, _ in early)))
        if not sound:
            faults.append(f"delays under {min_ms} ms other than the "
                          f"publisher's own 0: {early[:5]} ({len(early)} in "
                          "all)")
    return faults


def invariants(cell, out_dir: str) -> dict:
    """Part 1 of one finished experiment, as `Outcome`'s fields."""
    faults, digest, stats = check_artifacts(out_dir, arguments(cell),
                                            cell.config["guarantees"])
    return {"faults": faults, "digest": digest, "digest_of": "latencies1",
            "stats": stats}


def digest_line(outcome: Outcome) -> dict:
    """The `statistics_digest` line of the warm-up experiment."""
    return {"latencies_sha256": outcome.digest,
            "avg_latency_ms": outcome.stats.get("avg_latency_ms"),
            "max_latency_ms": outcome.stats.get("max_latency_ms")}


# ------------------------------------------------------------------ part 3


def drawn(cell, seed: int, total: int) -> list[int]:
    """`reference.messages` of an experiment's `total` publishes (all of
    them where that is as many), drawn from the seed."""
    count = min(int(cell.config["reference"]["messages"]), total)
    return sorted(random.Random(seed).sample(range(total), count))


@contextlib.contextmanager
def capture_publishes(which: list[int]):
    """Wrap runtime.simulator's `disseminate`; yields the list that fills
    with (result, plan, call arguments) of the publishes numbered in
    `which`."""
    from dst_libp2p_test_node_tpu.runtime import simulator as simmod

    original = simmod.disseminate
    taken: list[dict] = []
    calls = iter(range(1 << 30))

    def with_plan(state, conns, rev, *args, **kw):
        index = next(calls)
        if index not in which:
            return original(state, conns, rev, *args, **kw)
        res, new_state, plan = original(state, conns, rev, *args, **kw,
                                        return_plan=True)
        taken.append({
            "message": index,
            "conns": np.asarray(conns), "rev": np.asarray(rev),
            "plan": {k: None if v is None else np.asarray(v)
                     for k, v in plan.items()},
            "delay_ms": np.asarray(res.delay_ms, np.float64),
            "received": np.asarray(res.received),
            "publisher": int(kw["publisher"]), "t0_ms": float(kw["t0_ms"]),
            "payload_bytes": int(kw["payload_bytes"]),
            "fragments": int(kw["fragments"]),
            "with_gossip": bool(kw["with_gossip"]),
        })
        return res, new_state

    simmod.disseminate = with_plan
    try:
        yield taken
    finally:
        simmod.disseminate = original


def captured_experiment(cell, seed: int, total: int, out_dir: str,
                        every: bool = False) -> tuple[Outcome, list[dict]]:
    """The cell's experiment of `total` publishes on `seed`, with the
    publishes the reference replays captured (`drawn`; `every`: all of
    them, each saying whether it is `drawn`); its artifacts are checked as
    every experiment's."""
    checked = drawn(cell, seed, total)
    which = list(range(total)) if every else checked
    with capture_publishes(which) as taken:
        outcome = run_experiment(cell, seed, out_dir)
    if outcome.ok and [p["message"] for p in taken] != which:
        outcome.faults.append(
            f"captured publishes {[p['message'] for p in taken]}, "
            f"wanted {which}")
    for pub in taken:
        pub["seed"], pub["drawn"] = seed, pub["message"] in checked
    return outcome, taken


def captured(cell, seed: int, out_dir: str,
             every: bool = False) -> tuple[Outcome, list[dict]]:
    return captured_experiment(
        cell, seed, int(arguments(cell)["positionals"]["num_publishers"]),
        out_dir, every)


@dataclass
class Comparison:
    message: int
    t0_ms: float
    receivers: int
    reached_differing: int  # receivers in one reached set and not the other
    share_beyond: float     # receivers beyond atol + rtol * delay
    share_beyond_hop: float  # receivers beyond one hop (hop_ms)
    abs_diff_p50_ms: float
    abs_diff_p99_ms: float
    max_abs_diff_ms: float

    def line(self) -> dict:
        return dict(vars(self))


def reference_delays(pub: dict, cell, quantize=None,
                     links: dict | None = None):
    """The DES on one captured publish: the plan's draws, the reference's
    own link tables (from `links`: the network's stages and ranges, by
    default the `run` positionals)."""
    links = arguments(cell)["positionals"] if links is None else links
    plan = {**pub["plan"], **link_tables.edge_tables(
        pub["conns"], links, pub["payload_bytes"], pub["fragments"])}
    if cell.config["reference"]["idle_links_at_publish"]:
        # the deployment spaces its messages further apart than one takes
        # to drain, so the reference starts each on idle links and takes no
        # occupancy that the program carried from the last
        plan["uplink"] = np.zeros_like(plan["uplink"])
        plan["rx_free"] = np.zeros_like(plan["rx_free"])
    if not pub["with_gossip"]:
        # the engine exports gossip targets even with with_gossip=False;
        # a mesh-only publish announces nothing
        plan["g_tgt_w"] = np.zeros_like(plan["g_tgt_w"])
    return des.des_delays(
        pub["conns"], pub["rev"], plan, des.link_model(
            cell.config["link_model"]),
        pub["publisher"], pub["t0_ms"], pub["fragments"],
        pub["payload_bytes"], quantize=quantize)


def compare(got_d, got_r, want_d, want_r, ref: dict, message: int,
            t0_ms: float) -> Comparison:
    both = got_r & want_r
    if not both.any():
        return Comparison(message, t0_ms, int(want_r.sum()),
                          int((got_r != want_r).sum()), 1.0, 1.0,
                          float("inf"), float("inf"), float("inf"))
    diff = np.abs(got_d[both] - want_d[both])
    beyond = diff > ref["atol_ms"] + ref["rtol"] * np.abs(want_d[both])
    return Comparison(
        message=message, t0_ms=t0_ms, receivers=int(want_r.sum()),
        reached_differing=int((got_r != want_r).sum()),
        share_beyond=float(beyond.mean()),
        share_beyond_hop=float((diff > ref["hop_ms"]).mean()),
        abs_diff_p50_ms=float(np.percentile(diff, 50)),
        abs_diff_p99_ms=float(np.percentile(diff, 99)),
        max_abs_diff_ms=float(diff.max()))


def limits(ref: dict) -> dict:
    return {"limit_reached_differing": 0, "limit_share_beyond": ref["eps"],
            "limit_share_beyond_hop": ref["eps_hop"]}


def passes(c: Comparison, ref: dict) -> bool:
    return (c.reached_differing == 0 and c.share_beyond <= ref["eps"]
            and c.share_beyond_hop <= ref["eps_hop"])


def against_reference(cell, pub: dict, control: bool = False,
                      links: dict | None = None) -> dict:
    """One captured publish against the float64 reference, as the
    `correct_part3` line prints it: each number beside its limit, and
    `passed`. `control`: not the program's delays but the reference's own,
    computed in bfloat16, in the program's place. The float64 replay is
    kept on the publish, so that the control beside a sound reading pays
    it once."""
    ref = cell.config["reference"]
    if "reference" not in pub:
        pub["reference"] = reference_delays(pub, cell, links=links)
    want_d, want_r = pub["reference"]
    got_d, got_r = (reference_delays(pub, cell, des.bfloat16_round, links)
                    if control else (pub["delay_ms"], pub["received"]))
    c = compare(got_d, got_r, want_d, want_r, ref, pub["message"],
                pub["t0_ms"])
    return {"what": "publish against the float64 reference",
            "seed": pub["seed"], **c.line(),
            "tolerance": f"{ref['atol_ms']} ms + {ref['rtol']} * delay",
            "hop_ms": ref["hop_ms"], **limits(ref), "passed": passes(c, ref)}


def summarised(records: list[dict], control: bool = False) -> dict:
    """What a summary of many seeds lists of part 3's records (control.py,
    rehearse_seeds.py): the sound runs' largest, the control's smallest."""
    if control:
        return {f"control_{k}_min": min((r[k] for r in records), default=None)
                for k in ("share_beyond", "share_beyond_hop")}
    return {f"sound_{k}_max": max((r[k] for r in records), default=None)
            for k in ("reached_differing", "share_beyond",
                      "share_beyond_hop", "max_abs_diff_ms")}
