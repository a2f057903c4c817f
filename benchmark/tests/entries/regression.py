"""The entry `regression`, rehearsed: a second user of the entry contract
(benchmark/README.md, "An entry point: new files only"), under
benchmark/tests only and in no manifest the driver reads.

`cli.main(["regression", "--messages", m, "--msg-size", b, "--latencies",
<out_dir>/latencies1])` takes its deployment from the reference node's
environment (PEERS, CONNECTTO, STARTSLEEP, FRAGMENTS, MUXER, SEED:
runtime/regression_runtime.config_from_env), forms the mesh by kad-dht
bootstrap and publishes through the `disseminate` that `run` publishes
through, so the capture and the DES of benchmark/entries/run.py apply as
they are. What differs from `run` is what the configuration's file has to
state and this module has to read: an environment, the publisher (the first
peer that is no bootstrap), the network's links (the node's own defaults:
one stage, 50 Mbit/s, 100 ms), and a summary on stdout in the place of
`stats1.json`.
"""

from __future__ import annotations

import hashlib
import os
import re

from benchmark.entries import run as run_entry
from benchmark.harness.experiment import Outcome

_COVERAGE = re.compile(r"^Coverage: ([0-9.]+)%$", re.M)
_MESH = re.compile(r"^Mesh degree: mean ([0-9.]+)$", re.M)


def settings(cell) -> dict:
    """The configuration's `regression` (env, messages, msg_size, links),
    with the environment the traffic mix overrides."""
    reg = dict(cell.config["regression"])
    reg["env"] = {**reg["env"], **cell.traffic.get("env", {})}
    return reg


def invocation(cell, seed: int, out_dir: str) -> tuple[list[str], dict]:
    reg = settings(cell)
    env = {**{k: str(v) for k, v in reg["env"].items()}, "SEED": str(seed)}
    return ["regression", "--messages", str(reg["messages"]), "--msg-size",
            str(reg["msg_size"]), "--latencies",
            os.path.join(out_dir, "latencies1")], env


def invariants(cell, out_dir: str) -> dict:
    """Part 1: the summary's coverage is at least `coverage_share_min`, and
    `latencies1` holds one line per receipt, none under `no_delay_under_ms`
    but the publisher's own 0."""
    reg, guarantees = settings(cell), cell.config["guarantees"]
    try:
        with open(os.path.join(out_dir, "stdout.txt")) as f:
            said = f.read()
        with open(os.path.join(out_dir, "latencies1"), "rb") as f:
            latencies = f.read()
    except OSError as e:
        return {"faults": [f"artifact missing: {e}"]}
    coverage, mesh = _COVERAGE.search(said), _MESH.search(said)
    if coverage is None or mesh is None:
        return {"faults": ["the summary on stdout has no 'Coverage:' or no "
                           "'Mesh degree:' line"]}
    peers = int(reg["env"]["PEERS"])
    share = float(coverage.group(1)) / 100.0
    stats = {"coverage": share * peers,
             "mesh_degree_mean": float(mesh.group(1))}
    faults = []
    if not float(guarantees["coverage_share_min"]) <= share <= 1.0:
        faults.append(f"coverage {share} of {peers} peers, guaranteed at "
                      f"least {guarantees['coverage_share_min']}")
    faults += run_entry.latency_lines_faults(
        latencies, int(reg["messages"]), stats["coverage"],
        guarantees["no_delay_under_ms"], int(reg["publisher"]))
    return {"faults": faults, "digest": hashlib.sha256(latencies).hexdigest(),
            "digest_of": "latencies1", "stats": stats}


def digest_line(outcome: Outcome) -> dict:
    return {"latencies_sha256": outcome.digest, **outcome.stats}


def captured(cell, seed: int, out_dir: str,
             every: bool = False) -> tuple[Outcome, list[dict]]:
    return run_entry.captured_experiment(
        cell, seed, int(settings(cell)["messages"]), out_dir, every)


def against_reference(cell, pub: dict, control: bool = False) -> dict:
    return run_entry.against_reference(cell, pub, control,
                                       links=settings(cell)["links"])


summarised = run_entry.summarised
