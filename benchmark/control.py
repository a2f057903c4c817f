#!/usr/bin/env python3
"""The control of `correct`, part 3: readings on the chip at a cell's size.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3

For each seed it runs the cell's own experiment with what part 3 checks of
it captured, as benchmark/run.py's part 3 does (the same functions of the
cell's entry, benchmark/entries/<entry>.py, the same messages), and prints
two readings of the numbers that part 3 limits (for the entry `run`:
receivers in one reached set only, the share of receivers beyond the
tolerance, the share beyond one hop):

  sound    what the program produced against the entry's plain reference;
  control  the reference put in the program's place and computed one
           precision lower (`run`: every table and event time rounded to
           bfloat16, the step below the engine's float32 clock), against the
           same reference.

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
    outcome, taken = cell.entry.captured(cell, seed, work)
    if not outcome.ok:
        raise RuntimeError(f"seed {seed}: {outcome.faults}")
    out = []
    for item in taken:
        sound = cell.entry.against_reference(cell, item)
        control = cell.entry.against_reference(cell, item, control=True)
        out.append({"seed": seed, "message": sound["message"],
                    "sound": sound, "control": control,
                    "sound_passes": sound["passed"],
                    "control_passes": control["passed"]})
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
        **cell.entry.summarised([r["sound"] for r in rows]),
        **cell.entry.summarised([r["control"] for r in rows], control=True),
        "sound_all_pass_and_control_all_fail": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
