#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the main path runs on a TPU.

Drives the Quick-start experiment (README.md, scripts/run_tpu.sh) through
the entry point a user types — `dst_libp2p_test_node_tpu.cli.main(["run",
...])` -> cmd_run -> Simulator -> LatenciesWriter — in this one process,
first at 1,000 peers and then at the headline width of 100,000 peers
(connect-to 10, one 15,000-byte fragment, 3 publishes, bandwidth 50-150,
latency 40-130, 5 stages, loss 0.0, exact delivery, gossip on), and checks
what each run's own artifacts show. Every phase runs its command twice: the
first wall includes compiling, the second is the steady one.

    python chip_smoke.py [--out DIR]      one chip (what the driver runs)
    python chip_smoke.py --chips 4        the sharded phase only: the same
                                          100,000-peer config on a 4-chip
                                          peer mesh, then on one device,
                                          and the two compared

It needs a TPU: the first thing main() does is ask JAX for its devices, and
anything but a TPU exits non-zero before any phase. It sets no platform and
never continues on the CPU. A failed check raises; nothing is retried. One
process, no child that touches JAX (a chip belongs to one process).

Each phase prints one JSON object (observations for CHANGES.md, not
metrics); the last line of stdout is the contract's
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_OUT = os.path.join(HERE, "chip_smoke_out")
CPU_FIXTURE = os.path.join(HERE, "tests", "fixtures", "chip_smoke_cpu_1000.json")

# the run.sh positionals after <runs> <nodes> (scripts/run_tpu.sh):
# size frag pubs bwlo bwhi latlo lathi stages loss publisher rotation delay
MSG_SIZE, PUBLISHES, MIN_LATENCY_MS, PUBLISHER = 15000, 3, 40, 4
TAIL = [str(MSG_SIZE), "1", str(PUBLISHES), "50", "150", str(MIN_LATENCY_MS),
        "130", "5", "0.0", str(PUBLISHER), "0", "4000"]

_LINE = re.compile(
    rb"shadow\.data/hosts/peer(\d+)/main\.1000\.stdout:\d+:\d+ "
    rb"milliseconds: (\d+)\n")


def require_tpu():
    """The device gate. Non-zero exit unless JAX's default backend is a TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found "
                 f"{devices[0].platform!r} ({len(devices)} device(s)); "
                 "not continuing on it")
    return devices


def _peak_bytes(device):
    """peak_bytes_in_use as the device reports it (the CPU reports none)."""
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke: {what}")


def _run_cli_once(peers: int, call_dir: str) -> dict:
    """One `run` through cli.main, stdout kept in <call_dir>/stdout.txt."""
    from dst_libp2p_test_node_tpu import cli

    os.makedirs(call_dir, exist_ok=True)
    argv = ["run", "1", str(peers), *TAIL, "--stats-json",
            "--out-prefix", call_dir + os.sep]
    log = os.path.join(call_dir, "stdout.txt")
    t0 = time.perf_counter()
    with open(log, "w") as f, contextlib.redirect_stdout(f):
        rc = cli.main(argv)
    seconds = time.perf_counter() - t0
    check(rc == 0, f"`{' '.join(argv)}` returned {rc}")
    with open(log) as f:
        printed = re.search(r"\[tpu backend\].* lines=(\d+)", f.read())
    check(printed is not None, f"no '[tpu backend] ... lines=' in {log}")
    with open(os.path.join(call_dir, "stats1.json")) as f:
        stats = json.load(f)
    with open(os.path.join(call_dir, "latencies1"), "rb") as f:
        latencies = f.read()
    return {"seconds": seconds, "printed_lines": int(printed.group(1)),
            "stats": stats, "latencies": latencies}


def _check_latencies(latencies: bytes, printed_lines: int, peers: int) -> None:
    rows, matched = [], 0
    for m in _LINE.finditer(latencies):
        rows.append((int(m.group(1)), int(m.group(2))))
        matched += m.end() - m.start()
    n_lines = latencies.count(b"\n")
    check(n_lines == printed_lines == PUBLISHES * peers,
          f"latencies1 has {n_lines} lines, cmd_run printed {printed_lines}, "
          f"expected {PUBLISHES * peers}")
    # the matches tile the file: every line is in the
    # "<msgId> milliseconds: <ms>" form, with nothing between them
    check(len(rows) == n_lines and matched == len(latencies),
          "latencies1 has lines outside the '<msgId> milliseconds: <ms>' form")
    # integer delays are finite by construction of the match; none may beat
    # the smallest link latency except the publisher's own (logged at 0 ms)
    early = [(p, d) for p, d in rows if d < MIN_LATENCY_MS]
    check(early == [(PUBLISHER, 0)] * PUBLISHES,
          f"delays below the smallest link latency ({MIN_LATENCY_MS} ms) "
          f"other than the publisher's own: {early[:5]}")


def run_cli_phase(name: str, peers: int, out_dir: str,
                  cpu_reference: dict | None = None) -> dict:
    """Run the Quick-start command twice at `peers` and check its artifacts.
    Returns the phase's observation record (also printed, one JSON line)."""
    import jax

    from dst_libp2p_test_node_tpu.ops.disseminate import fixpoint_formulation
    from dst_libp2p_test_node_tpu.runtime import native_logemit
    from dst_libp2p_test_node_tpu.runtime.compile_cache import (
        enable_compile_cache,
    )
    from dst_libp2p_test_node_tpu.runtime.simulator import graph_capacity

    cache_dir = enable_compile_cache()
    blocks_before = native_logemit.native_blocks
    first = _run_cli_once(peers, os.path.join(out_dir, name, "first"))
    steady = _run_cli_once(peers, os.path.join(out_dir, name, "steady"))

    for call in (first, steady):
        _check_latencies(call["latencies"], call["printed_lines"], peers)
        # stats coverage is mean receivers per message (summarize.py)
        check(call["stats"]["coverage"] / peers == 1.0,
              f"coverage {call['stats']['coverage']} of {peers} peers at "
              "loss 0")
    check(first["latencies"] == steady["latencies"],
          "the two calls wrote different latencies1")
    stats = steady["stats"]
    if cpu_reference is not None:
        for key in ("avg_latency_ms", "max_latency_ms"):
            check(abs(stats[key] - cpu_reference[key]) <= 1.0,
                  f"{key} {stats[key]} vs {cpu_reference[key]} on the CPU "
                  f"(tests/fixtures), more than 1 ms apart")
    native_used = native_logemit.native_blocks > blocks_before
    if peers >= native_logemit.NATIVE_MIN_LINES:
        check(native_used, "the native log emitter was not used at "
              f"{peers} peers (build failed? see stderr)")

    device = jax.devices()[0]
    capacity = graph_capacity(_experiment(peers))
    record = {
        "phase": name,
        "device_kind": device.device_kind,
        "peers": peers,
        "first_call_s": first["seconds"],
        "steady_s": steady["seconds"],
        "first_call_sim_wall_s": first["stats"]["wall_s"],
        "steady_sim_wall_s": stats["wall_s"],
        "coverage": stats["coverage"] / peers,
        "avg_latency_ms": stats["avg_latency_ms"],
        "max_latency_ms": stats["max_latency_ms"],
        "latencies_lines": steady["printed_lines"],
        "latencies_sha256": hashlib.sha256(steady["latencies"]).hexdigest(),
        "peak_bytes_in_use": _peak_bytes(device),
        "fixpoint_formulation": fixpoint_formulation((peers, capacity)),
        "native_logemit_built": native_logemit.ensure_built(),
        "native_logemit_used": native_used,
        "compile_cache_dir": cache_dir,
    }
    print(json.dumps(record), flush=True)
    return record


def _experiment(peers: int):
    """The ExperimentConfig cmd_run builds for the command above."""
    from dst_libp2p_test_node_tpu import cli
    from dst_libp2p_test_node_tpu.runtime.simulator import ExperimentConfig

    fields = dict(zip(cli.RUN_SH_PARAMS, ["1", str(peers), *TAIL]))
    return ExperimentConfig(
        topo=cli._topo_from_fields(fields),
        gossipsub=cli.gossipsub_params_from_env(), publisher_id=PUBLISHER)


def _row_leaves(sim, peers: int):
    import jax

    return [x for x in jax.tree.leaves((sim.state, sim.arrays))
            if getattr(x, "ndim", 0) >= 1 and x.shape[0] == peers]


def run_sharded_phase(peers: int, n_devices: int) -> dict:
    """The same experiment on an n-device peer mesh, then on one device, in
    this process; the comparison tests/test_sharded_sim.py makes at toy
    size, plus a check that the state really is spread over the devices."""
    import jax
    import numpy as np

    from dst_libp2p_test_node_tpu.ops.disseminate import fixpoint_formulation
    from dst_libp2p_test_node_tpu.parallel import exchange
    from dst_libp2p_test_node_tpu.parallel.sharding import make_peer_mesh
    from dst_libp2p_test_node_tpu.runtime.compile_cache import (
        enable_compile_cache,
    )
    from dst_libp2p_test_node_tpu.runtime.simulator import Simulator

    cache_dir = enable_compile_cache()
    devices = jax.devices()[:n_devices]
    check(len(devices) == n_devices,
          f"need {n_devices} devices, JAX has {len(jax.devices())}")
    mesh = make_peer_mesh(n_devices)

    def peaks():
        return [_peak_bytes(d) for d in devices]

    # sharded first: peaks never fall, so device 0's figure after this run
    # and before the single-device one is the sharded program's own
    t0 = time.perf_counter()
    sharded = Simulator(_experiment(peers), mesh=mesh)
    rec_s = sharded.run()
    sharded_s = time.perf_counter() - t0
    rows = peers // n_devices
    leaves = _row_leaves(sharded, peers)
    check(len(leaves) > 0, "no (N, ...) leaves found on the sharded state")
    for x in leaves:
        per_device = {s.device: s.data.shape[0] for s in x.addressable_shards}
        check(set(per_device) == set(devices)
              and set(per_device.values()) == {rows},
              f"an (N, ...) leaf of shape {x.shape} is not spread "
              f"{rows} rows to each of {n_devices} devices: "
              f"{sorted((d.id, r) for d, r in per_device.items())}")
    peak_sharded = peaks()

    t0 = time.perf_counter()
    single = Simulator(_experiment(peers))
    rec_1 = single.run()
    single_s = time.perf_counter() - t0
    peak_single = peaks()[0]

    check(len(rec_s) == len(rec_1) == PUBLISHES, "publish counts differ")
    for a, b in zip(rec_1, rec_s):
        np.testing.assert_array_equal(a.received, b.received)
        np.testing.assert_allclose(a.delays_ms, b.delays_ms, rtol=1e-5)
        np.testing.assert_array_equal(a.sends, b.sends)
        check(a.converged == b.converged, "converged differs")
        check(a.converged and bool(a.received.all()),
              "single-device run did not converge to full coverage")

    record = {
        "phase": f"sharded_{n_devices}_vs_single",
        "device_kind": devices[0].device_kind,
        "peers": peers,
        "devices": n_devices,
        "rows_per_device": rows,
        "row_leaves_checked": len(leaves),
        "sharded_run_s_with_compile": sharded_s,
        "single_run_s_with_compile": single_s,
        "max_abs_delay_diff_ms": float(max(
            np.max(np.abs(a.delays_ms - b.delays_ms))
            for a, b in zip(rec_1, rec_s))),
        "converged": all(r.converged for r in rec_s),
        "coverage": float(np.mean([r.received.mean() for r in rec_s])),
        "peak_bytes_in_use_sharded_per_device": peak_sharded,
        "peak_bytes_in_use_single_device": peak_single,
        "fixpoint_formulation_sharded": (
            fixpoint_formulation((peers, sharded.graph.capacity), mesh=mesh)
            + f" ({exchange.SRC_GATHER} gather)"),
        "fixpoint_formulation_single": fixpoint_formulation(
            (peers, single.graph.capacity)),
        "compile_cache_dir": cache_dir,
    }
    print(json.dumps(record), flush=True)
    return record


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", default=DEFAULT_OUT,
                   help="output directory for the runs' artifacts")
    p.add_argument("--chips", type=int, choices=[1, 4], default=1,
                   help="4 = only the sharded phase and its single-device "
                   "comparison, on four chips")
    a = p.parse_args(argv)

    devices = require_tpu()
    check(a.chips == 1 or len(devices) == a.chips,
          f"--chips {a.chips} but JAX has {len(devices)} device(s)")

    if a.chips == 4:
        rec = run_sharded_phase(100_000, 4)
        single = rec["peak_bytes_in_use_single_device"]
        check(single is not None and all(
            pk is not None and pk < 0.6 * single
            for pk in rec["peak_bytes_in_use_sharded_per_device"]),
            "sharded per-device peak memory is not well under the "
            f"single-device peak: {rec}")
    else:
        with open(CPU_FIXTURE) as f:
            cpu_reference = json.load(f)
        for name, peers, ref in (("quickstart_1k", 1_000, cpu_reference),
                                 ("headline_100k", 100_000, None)):
            rec = run_cli_phase(name, peers, a.out, ref)
            check(rec["peak_bytes_in_use"] is not None,
                  "the device reports no peak_bytes_in_use")

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
