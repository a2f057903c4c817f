"""One experiment: the command a user types, through `cli.main([<entry>,
...])` in this process, and the check of what it left on disk. The argv, the
environment and the check are the cell's entry's (benchmark/entries/).

The call is a copy of chip_smoke.py's `_run_cli_once`.
"""

from __future__ import annotations

import contextlib
import gc
import os
import shutil
import time
from dataclasses import dataclass, field


@dataclass
class Outcome:
    seed: int
    seconds: float
    rc: int
    faults: list[str] = field(default_factory=list)  # invariants missed
    digest: str = ""    # sha256 of the artifact that `digest_of` names
    digest_of: str = ""
    stats: dict = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return self.rc == 0 and not self.faults


@contextlib.contextmanager
def environment(env: dict[str, str]):
    """`env` in os.environ for the length of the block; what was there
    before is there after, whatever the block raised."""
    before = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        yield
    finally:
        for k, v in before.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def call_cli(argv: list[str], env: dict[str, str], out_dir: str,
             around=contextlib.nullcontext) -> tuple[int, float]:
    """cli.main(argv) under `env`, with its stdout kept in
    <out_dir>/stdout.txt; returns (rc, host seconds of the call). The call
    ends with every artifact on disk, so the device has finished. `around()`
    is entered just around the call (the traced run's span); the
    environment is set outside it."""
    from dst_libp2p_test_node_tpu import cli

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "stdout.txt"), "w") as f, \
            contextlib.redirect_stdout(f), environment(env), around():
        t0 = time.perf_counter()
        rc = cli.main(argv)
        seconds = time.perf_counter() - t0
    return rc, seconds


def run_experiment(cell, seed: int, out_dir: str,
                   around=contextlib.nullcontext) -> Outcome:
    """Run, then (outside the timed span) check and delete the artifacts,
    and collect the garbage the checks made, so that no collection of it
    falls into the next experiment's span."""
    shutil.rmtree(out_dir, ignore_errors=True)
    argv, env = cell.entry.invocation(cell, seed, out_dir)
    rc, seconds = call_cli(argv, env, out_dir, around)
    if rc != 0:
        out = Outcome(seed, seconds, rc, [f"cli.main returned {rc}"])
    else:
        out = Outcome(seed, seconds, rc, **cell.entry.invariants(cell,
                                                                 out_dir))
    shutil.rmtree(out_dir, ignore_errors=True)
    gc.collect()
    return out
