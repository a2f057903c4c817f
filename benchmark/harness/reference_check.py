"""Part 3 of `correct`: the timed experiment's publishes against the plain
reference (benchmark/reference/des.py).

The cell's own experiment, the argv, `--seed` and warm-up of the window's
iteration 0, is run once more through `cli.main(["run", ...])`. For the
length of that call the name `disseminate` in `runtime.simulator` is wrapped
so that the publishes the configuration asks for also return their sampled
plan; the argument list stays the program's own. That run's `latencies1`
has to be the timed runs' own, byte for byte, which ties what is compared to
what the window drove. The DES then replays each plan's draws on link tables
of its own (benchmark/reference/link_tables.py) with the link-model constants
of the configuration's file.
"""

from __future__ import annotations

import contextlib
import random
import time
from dataclasses import dataclass

import numpy as np

from benchmark.harness.experiment import Outcome, run_experiment
from benchmark.reference import des, link_tables


def messages_checked(cell, seed: int) -> list[int]:
    """Which publishes of an experiment the reference replays: all of them,
    or as many as `reference.messages` says, drawn from the seed."""
    total = int(cell.argv["positionals"]["num_publishers"])
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


def reference_delays(pub: dict, cell, quantize=None):
    """The DES on one captured publish: the plan's draws, the reference's
    own link tables."""
    plan = {**pub["plan"], **link_tables.edge_tables(
        pub["conns"], cell.argv["positionals"], pub["payload_bytes"],
        pub["fragments"])}
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


def captured_experiment(cell, seed: int, which: list[int],
                        out_dir: str) -> tuple[Outcome, list[dict]]:
    """The cell's experiment on `seed`, with the publishes numbered in
    `which` captured; its artifacts are checked as every experiment's."""
    with capture_publishes(which) as taken:
        outcome = run_experiment(cell, seed, out_dir)
    if outcome.ok and [p["message"] for p in taken] != which:
        outcome.faults.append(
            f"captured publishes {[p['message'] for p in taken]}, "
            f"wanted {which}")
    return outcome, taken


def check(cell, seed: int, out_dir: str, which: list[int] | None = None
          ) -> tuple[Outcome, list[Comparison], dict]:
    """The captured experiment, the comparisons of its checked messages, and
    where the seconds went (the program's run, the DES)."""
    which = messages_checked(cell, seed) if which is None else which
    t0 = time.perf_counter()
    outcome, taken = captured_experiment(cell, seed, which, out_dir)
    t1 = time.perf_counter()
    out = []
    for pub in taken if outcome.ok else []:
        want_d, want_r = reference_delays(pub, cell)
        out.append(compare(pub["delay_ms"], pub["received"], want_d, want_r,
                           cell.config["reference"], pub["message"],
                           pub["t0_ms"]))
    return outcome, out, {"program_s": t1 - t0,
                          "des_s": time.perf_counter() - t1}


def limits(ref: dict) -> dict:
    return {"limit_reached_differing": 0, "limit_share_beyond": ref["eps"],
            "limit_share_beyond_hop": ref["eps_hop"]}


def passes(c: Comparison, ref: dict) -> bool:
    return (c.reached_differing == 0 and c.share_beyond <= ref["eps"]
            and c.share_beyond_hop <= ref["eps_hop"])
