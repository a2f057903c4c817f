"""One experiment: the command a user types, through `cli.main(["run", ...])`
in this process, and the checks of what it left on disk.

The call and the artifact checks are a copy of chip_smoke.py's
(`_run_cli_once`, `_check_latencies`), made general over the configuration.
"""

from __future__ import annotations

import contextlib
import gc
import hashlib
import json
import os
import re
import shutil
import time
from dataclasses import dataclass, field

# run.sh's positional order (shadow/run.sh:23-38), as `run` takes them
POSITIONALS = (
    "runs", "nodes", "msg_size", "num_frag", "num_publishers",
    "min_bandwidth", "max_bandwidth", "min_latency", "max_latency",
    "anchor_stages", "packet_loss", "publisher_id", "publisher_rotation",
    "inter_message_delay_ms")

_LINE = re.compile(
    rb"shadow\.data/hosts/peer(\d+)/main\.1000\.stdout:\d+:\d+ "
    rb"milliseconds: (\d+)\n")


def run_argv(argv: dict, seed: int, out_dir: str) -> list[str]:
    """The argv of one experiment."""
    pos = argv["positionals"]
    missing = [k for k in POSITIONALS if k not in pos]
    if missing:
        raise SystemExit(f"benchmark: the configuration's run lacks {missing}")
    return ["run", *(str(pos[k]) for k in POSITIONALS), *argv["flags"],
            "--seed", str(seed), "--stats-json",
            "--out-prefix", out_dir + os.sep]


@dataclass
class Outcome:
    seed: int
    seconds: float
    rc: int
    faults: list[str] = field(default_factory=list)  # invariants missed
    latencies_sha256: str = ""
    stats: dict = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return self.rc == 0 and not self.faults


def call_cli(argv: list[str], out_dir: str,
             around=contextlib.nullcontext) -> tuple[int, float]:
    """cli.main(argv) with its stdout kept in <out_dir>/stdout.txt; returns
    (rc, host seconds of the call). The call ends with every artifact on
    disk, so the device has finished. `around()` is entered just around the
    call (the traced run's span)."""
    from dst_libp2p_test_node_tpu import cli

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "stdout.txt"), "w") as f, \
            contextlib.redirect_stdout(f), around():
        t0 = time.perf_counter()
        rc = cli.main(argv)
        seconds = time.perf_counter() - t0
    return rc, seconds


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
    min_ms = guarantees["no_delay_under_ms"]
    if min_ms is not None:
        early = [(p, d) for p, d in rows if d < min_ms]
        sound = (len(early) == messages and all(d == 0 for _, d in early)
                 and (rotation or all(p == publisher for p, _ in early)))
        if not sound:
            faults.append(f"delays under {min_ms} ms other than the "
                          f"publisher's own 0: {early[:5]} ({len(early)} in "
                          "all)")
    return faults, hashlib.sha256(latencies).hexdigest(), stats


def run_experiment(cell, seed: int, out_dir: str,
                   around=contextlib.nullcontext) -> Outcome:
    """Run, then (outside the timed span) check and delete the artifacts,
    and collect the garbage the checks made, so that no collection of it
    falls into the next experiment's span."""
    shutil.rmtree(out_dir, ignore_errors=True)
    argv = cell.argv
    rc, seconds = call_cli(run_argv(argv, seed, out_dir), out_dir, around)
    out = Outcome(seed=seed, seconds=seconds, rc=rc)
    if rc != 0:
        out.faults.append(f"cli.main returned {rc}")
    else:
        out.faults, out.latencies_sha256, out.stats = check_artifacts(
            out_dir, argv, cell.config["guarantees"])
    shutil.rmtree(out_dir, ignore_errors=True)
    gc.collect()
    return out
