#!/usr/bin/env python3
"""The control of `correct`, part 3: readings on the chip at a cell's size.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3

For each seed it runs the cell's own experiment with its publishes captured
as benchmark/run.py's part 3 does (same functions, same messages), and
prints two readings of the numbers that part 3 limits (receivers in one
reached set only, the share of receivers beyond the tolerance, the share
beyond one hop):

  sound    the program's delays against the float64 reference;
  control  the reference put in the program's place and computed one
           precision lower (every table and event time rounded to bfloat16,
           the step below the engine's float32 clock), against the float64
           reference.

Every sound reading has to pass the limits of the configuration's file and
every control reading has to fail one; the last line says whether they do.
benchmark/run.py never runs this. `--rehearse` lets it run off a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)


def readings(cell, seed: int, work: str) -> list[dict]:
    from benchmark.harness import reference_check as rc
    from benchmark.reference import des

    ref = cell.config["reference"]
    out = []
    outcome, taken = rc.captured_experiment(
        cell, seed, rc.messages_checked(cell, seed), work)
    if not outcome.ok:
        raise RuntimeError(f"seed {seed}: {outcome.faults}")
    for pub in taken:
        want_d, want_r = rc.reference_delays(pub, cell)
        low_d, low_r = rc.reference_delays(pub, cell,
                                           quantize=des.bfloat16_round)
        sound = rc.compare(pub["delay_ms"], pub["received"], want_d, want_r,
                           ref, pub["message"], pub["t0_ms"])
        control = rc.compare(low_d, low_r, want_d, want_r, ref,
                             pub["message"], pub["t0_ms"])
        out.append({"seed": seed, "message": pub["message"],
                    "sound": sound.line(), "control": control.line(),
                    "sound_passes": rc.passes(sound, ref),
                    "control_passes": rc.passes(control, ref)})
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--manifest", default=None)
    a = p.parse_args(argv)
    from benchmark.harness import manifest
    from benchmark.run import require_devices

    cell = manifest.load_cell(a.workload, a.manifest)
    devices = require_devices(cell.chips, a.rehearse)
    from dst_libp2p_test_node_tpu.runtime.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    work = os.path.join(CHECKOUT, ".bench_work", cell.name + ".control")
    rows = []
    for seed in (int(s) for s in a.seeds.split(",")):
        for row in readings(cell, seed, work):
            rows.append(row)
            print(json.dumps(row), flush=True)
    ok = (all(r["sound_passes"] for r in rows)
          and not any(r["control_passes"] for r in rows))
    print(json.dumps({
        "workload": cell.name, "platform": devices[0].platform,
        "device_kind": devices[0].device_kind, "messages": len(rows),
        "limits": cell.config["reference"],
        **{f"sound_{k}_max": max(r["sound"][k] for r in rows)
           for k in ("reached_differing", "share_beyond",
                     "share_beyond_hop", "max_abs_diff_ms")},
        **{f"control_{k}_min": min(r["control"][k] for r in rows)
           for k in ("share_beyond", "share_beyond_hop")},
        "sound_all_pass_and_control_all_fail": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
