"""The entry `runs`: the run.sh experiment repeated over seeds by run.sh's
own first positional, `cli.main(["run", <runs>, <thirteen positionals>,
<flags>, "--seed", s, "--stats-json", "--out-prefix", dir])` with runs > 1
(shadow/run.sh:58-64: a Shadow run, a `latencies<i>` and a summary a turn;
turn i is seed s+i-1).

The argv is `entries/run.run_argv`'s with the traffic mix's `runs`. What is
this entry's own:

part 1  EVERY run i of the experiment is held to what `entries/run.py`
        holds one run to: coverage from `stats<i>.json`, `latencies<i>`'s
        line count and form, no delay under `no_delay_under_ms` but each
        message's publisher's own 0. The digest part 2 compares is the
        sha256 of all the `latencies<i>` in order.
part 3  the experiment once more with `runtime.simulator.disseminate`
        wrapped, as `entries/run.capture_publishes` wraps it, so that the
        publishes drawn also return their sampled plan. `reference.runs` of
        the runs are drawn from the seed and `reference.messages` of each
        one's messages; an item is one (run, message). A program that makes
        its runs one by one calls `disseminate` a run and a message with an
        (N, C) index, in run order; one that batches them calls it once a
        message with an (R, N, C) index and every leaf stacked: the capture
        tells them apart by the index's rank and takes the drawn run's rows.
        Each item goes to benchmark/reference/des.py exactly as a solo
        publish does (`entries/run.against_reference`); and each drawn run
        is made ALONE, `run 1 ... --seed s+i-1`, outside any timed span: its
        `latencies1` and `shadowlog1` have to be the experiment's
        `latencies<i>` and `shadowlog<i>` byte for byte (`files_differing`,
        limit 0), which is the guarantee the configuration states.

It asks the program for nothing but its argv, its files and that one name.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import random
import shutil

import numpy as np

from benchmark.entries import run as solo
from benchmark.harness.experiment import Outcome, call_cli, run_experiment

# what a run writes that the run made alone has to write too
RUN_FILES = ("latencies", "shadowlog")

arguments = solo.arguments


def _shape(cell) -> tuple[int, int]:
    """(runs, messages a run) of the cell's experiment."""
    pos = arguments(cell)["positionals"]
    return int(pos["runs"]), int(pos["num_publishers"])


def invocation(cell, seed: int, out_dir: str) -> tuple[list[str], dict]:
    """(argv, env) of one experiment of `runs` runs; `run` reads no
    environment."""
    return solo.run_argv(arguments(cell), seed, out_dir), {}


# ------------------------------------------------- part 1 and the digest


def _sha256(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def check_run(out_dir: str, i: int, argv: dict,
              guarantees: dict) -> tuple[list[str], bytes, dict]:
    """`entries/run.check_artifacts` for run i of an experiment: (faults,
    latencies<i>, stats<i>.json)."""
    pos = argv["positionals"]
    peers, messages = int(pos["nodes"]), int(pos["num_publishers"])
    rotation = bool(int(pos["publisher_rotation"]))
    try:
        with open(os.path.join(out_dir, f"stats{i}.json")) as f:
            stats = json.load(f)
        with open(os.path.join(out_dir, f"latencies{i}"), "rb") as f:
            latencies = f.read()
    except OSError as e:
        return [f"run {i}: artifact missing: {e}"], b"", {}
    faults = []
    coverage = stats.get("coverage")
    floor = float(guarantees["coverage_share_min"]) * peers
    if not isinstance(coverage, (int, float)) or not (
            floor <= coverage <= peers):
        faults.append(f"coverage {coverage} of {peers} peers, guaranteed "
                      f"at least {floor}")
        coverage = peers
    faults += solo.latency_lines_faults(
        latencies, messages, coverage, guarantees["no_delay_under_ms"],
        None if rotation else int(pos["publisher_id"]))
    return ([f"run {i}: " + fault.replace("latencies1", f"latencies{i}")
             for fault in faults], latencies, stats)


def invariants(cell, out_dir: str) -> dict:
    """Part 1 of one finished experiment, every run of it, as `Outcome`'s
    fields. `stats["runs"]` keeps a run's own digests and statistics, for
    the comparison with the run made alone and the digest line."""
    argv, (runs, _) = arguments(cell), _shape(cell)
    faults, whole, kept = [], hashlib.sha256(), []
    for i in range(1, runs + 1):
        run_faults, latencies, stats = check_run(
            out_dir, i, argv, cell.config["guarantees"])
        faults += run_faults
        whole.update(latencies)
        kept.append({
            "avg_latency_ms": stats.get("avg_latency_ms"),
            "max_latency_ms": stats.get("max_latency_ms"),
            **{name: _sha256(os.path.join(out_dir, f"{name}{i}"))
               for name in RUN_FILES
               if os.path.isfile(os.path.join(out_dir, f"{name}{i}"))}})
    return {"faults": faults,
            "digest": "" if faults else whole.hexdigest(),
            "digest_of": f"latencies1 ... latencies{runs}, in order",
            "stats": {"runs": kept}}


def digest_line(outcome: Outcome) -> dict:
    """The `statistics_digest` line of the warm-up experiment: the runs'
    mean average latency and their largest maximum."""
    runs = outcome.stats.get("runs", [])
    avg = [r["avg_latency_ms"] for r in runs
           if r.get("avg_latency_ms") is not None]
    most = [r["max_latency_ms"] for r in runs
            if r.get("max_latency_ms") is not None]
    return {"latencies_sha256": outcome.digest,
            "avg_latency_ms": sum(avg) / len(avg) if avg else None,
            "max_latency_ms": max(most, default=None)}


# ------------------------------------------------------------------ part 3


def drawn(cell, seed: int) -> list[tuple[int, int]]:
    """The (run, message) pairs `run.py` replays: `reference.runs` of the
    runs and `reference.messages` of each one's messages, drawn from the
    seed (both counted from 0)."""
    runs, messages = _shape(cell)
    ref, rng = cell.config["reference"], random.Random(seed)
    return [(r, m)
            for r in sorted(rng.sample(range(runs),
                                       min(int(ref["runs"]), runs)))
            for m in sorted(rng.sample(range(messages),
                                       min(int(ref["messages"]), messages)))]


def _rows(tree, r: int):
    """Run r's rows of a stacked result or plan, on the host."""
    if isinstance(tree, dict):
        return {k: None if v is None else np.asarray(v[r])
                for k, v in tree.items()}
    return np.asarray(tree[r])


@contextlib.contextmanager
def capture_publishes(which: list[tuple[int, int]], messages: int):
    """Wrap runtime.simulator's `disseminate`; yields the list that fills,
    in (run, message) order, with the publishes in `which` as
    `entries/run.capture_publishes` keeps one: a call with an (N, C) index
    is one run's publish, calls in run order, `messages` a run; a call with
    an (R, N, C) index is one message of every run."""
    from dst_libp2p_test_node_tpu.runtime import simulator as simmod

    original = simmod.disseminate
    taken: list[dict] = []
    calls = iter(range(1 << 30))

    def kept(run, message, conns, rev, plan, res, kw):
        return {
            "run": run, "message": run * messages + message,
            "conns": conns, "rev": rev, "plan": plan,
            "delay_ms": np.asarray(res["delay_ms"], np.float64),
            "received": res["received"],
            "publisher": int(kw["publisher"]), "t0_ms": float(kw["t0_ms"]),
            "payload_bytes": int(kw["payload_bytes"]),
            "fragments": int(kw["fragments"]),
            "with_gossip": bool(kw["with_gossip"])}

    def with_plan(state, conns, rev, *args, **kw):
        index = next(calls)
        batched = np.ndim(conns) == 3
        wanted = ([(r, m) for r, m in which if m == index] if batched
                  else [pair for pair in which
                        if pair == divmod(index, messages)])
        if not wanted:
            return original(state, conns, rev, *args, **kw)
        res, new_state, plan = original(state, conns, rev, *args, **kw,
                                        return_plan=True)
        got = {"delay_ms": res.delay_ms, "received": res.received}
        for r, m in wanted:
            taken.append(
                kept(r, m, _rows(conns, r), _rows(rev, r), _rows(plan, r),
                     _rows(got, r), kw) if batched else
                kept(r, m, np.asarray(conns), np.asarray(rev),
                     {k: None if v is None else np.asarray(v)
                      for k, v in plan.items()},
                     {k: np.asarray(v) for k, v in got.items()}, kw))
        return res, new_state

    simmod.disseminate = with_plan
    try:
        yield taken
    finally:
        simmod.disseminate = original
        taken.sort(key=lambda item: item["message"])


def made_alone(cell, seed: int, run: int, out_dir: str) -> dict:
    """Run `run` (from 0) of the experiment on `seed`, made alone: `run 1
    ... --seed seed+run`, and the sha256 of what it wrote, by name."""
    argv = arguments(cell)
    argv = {**argv, "positionals": {**argv["positionals"], "runs": 1}}
    shutil.rmtree(out_dir, ignore_errors=True)
    rc, _ = call_cli(solo.run_argv(argv, seed + run, out_dir), {}, out_dir)
    files = {name: _sha256(os.path.join(out_dir, f"{name}1"))
             for name in RUN_FILES
             if rc == 0 and os.path.isfile(os.path.join(out_dir,
                                                        f"{name}1"))}
    shutil.rmtree(out_dir, ignore_errors=True)
    return files


def captured(cell, seed: int, out_dir: str,
             every: bool = False) -> tuple[Outcome, list[dict]]:
    """The cell's experiment on `seed` with the drawn (run, message)
    publishes captured (`every`: all of them, each saying whether it is
    `drawn`), its artifacts checked as every experiment's; then each drawn
    run made alone, and on every item of that run how many of its files
    differ from the experiment's."""
    runs, messages = _shape(cell)
    checked = drawn(cell, seed)
    which = ([(r, m) for r in range(runs) for m in range(messages)]
             if every else checked)
    with capture_publishes(which, messages) as taken:
        outcome = run_experiment(cell, seed, out_dir)
    got = [divmod(item["message"], messages) for item in taken]
    if outcome.ok and got != which:
        outcome.faults.append(f"captured publishes {got}, wanted {which}")
    differing = {}
    for r in sorted({r for r, _ in checked} if outcome.ok else ()):
        alone = made_alone(cell, seed, r, out_dir + f".alone{r}")
        batch = outcome.stats["runs"][r]
        differing[r] = sum(
            alone.get(name) is None or alone[name] != batch.get(name)
            for name in RUN_FILES)
    for item in taken:
        pair = divmod(item["message"], messages)
        item["seed"], item["drawn"] = seed, pair in checked
        # a run that is not drawn is not made alone: nothing to compare
        item["files_differing"] = differing.get(item["run"])
    return outcome, taken


def against_reference(cell, item: dict, control: bool = False) -> dict:
    """One captured (run, message) as the `correct_part3` line prints it:
    the publish against the float64 reference with the `run` entry's
    numbers and limits, and `files_differing` (limit 0) of that run made
    alone. `control`: the reference in bfloat16 in the program's place; the
    files are the program's either way."""
    record = solo.against_reference(cell, item, control)
    files = item["files_differing"]
    _, messages = _shape(cell)
    return {
        **record,
        "what": "a run's publish against the float64 reference; the run "
                "against the run made alone",
        "run": item["run"], "message_of_run": item["message"] % messages,
        **({} if files is None else
           {"files_differing": files, "limit_files_differing": 0}),
        "passed": record["passed"] and not files}


def summarised(records: list[dict], control: bool = False) -> dict:
    out = solo.summarised(records, control)
    if not control:
        out["sound_files_differing_max"] = max(
            (r["files_differing"] for r in records
             if "files_differing" in r), default=None)
    return out
