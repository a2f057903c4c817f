#!/usr/bin/env python3
"""Rehearse `correct` on many seeds, on the CPU, before any chip-minute.

    python3 benchmark/rehearse_seeds.py --workload runsh-1k.headline \
        --seeds 0-899,2147483623-2147483722 --jobs 6 \
        --out benchmark/rehearsal/seeds_1k.json

For every seed it runs the parts of `correct` exactly as benchmark/run.py
does (same functions): part 1, the invariants of one whole experiment;
part 2, the same seed once more writes the same latencies file (here the
second experiment is part 3's captured one, which ties the two as run.py
does); part 3, every message of the captured experiment against the float64
reference, and the control (the reference in bfloat16) on its leading
`--control-messages`. The limits come from the configuration's file; the
summary gives the readings that they were set from. The simulated
statistics are the same bits on XLA:CPU and on the chip (PERF.md section
6), so a seed that passes here has not yet been shown to pass there, but one
that fails here will fail there.

Workers are spawned processes pinned to the CPU backend; the parent never
imports JAX. Prints no metric: these are not measurements of speed.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def parse_seeds(spec: str) -> list[int]:
    seeds: list[int] = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        seeds.extend(range(int(lo), int(hi or lo) + 1))
    return seeds


def rehearse_seed(job: tuple[str, int, int, bool]) -> dict:
    workload, seed, control_messages, part3_only = job
    os.environ["JAX_PLATFORMS"] = "cpu"
    if CHECKOUT not in sys.path:
        sys.path.insert(0, CHECKOUT)
    from benchmark.harness import manifest
    from benchmark.harness.experiment import run_experiment

    cell = manifest.load_cell(workload)
    entry = cell.entry
    work = os.path.join(CHECKOUT, ".bench_work", "rehearse", str(os.getpid()))
    t0 = time.time()
    again, taken = entry.captured(cell, seed, os.path.join(work, "b"),
                                  every=True)
    first = again if part3_only else run_experiment(
        cell, seed, os.path.join(work, "a"))
    t_exp = (time.time() - t0) / (1 if part3_only else 2)
    t0 = time.time()
    messages = [entry.against_reference(cell, item) for item in taken]
    control = [entry.against_reference(cell, item, control=True)
               for item in taken if item["message"] < control_messages]
    t_des = time.time() - t0
    # the digest line's fields: the digest goes to part 2, cut to 16
    # characters, and what else the entry says of an experiment to part 1
    said = entry.digest_line(first)
    row = {
        "seed": seed,
        "part1": {"pass": first.ok and again.ok,
                  "faults": first.faults + again.faults,
                  **{k: v for k, v in said.items() if v != first.digest}},
        "part2": {"pass": first.digest == again.digest
                  and first.digest != "", "run": not part3_only,
                  **{k: v[:16] for k, v in said.items()
                     if v == first.digest}},
        "part3": {"pass": bool(messages)
                  and all(m["passed"] for m in messages),
                  "run_py_checks": [item["message"] for item in taken
                                    if item["drawn"]],
                  "messages": messages},
        "control": {"fails": all(not c["passed"] for c in control),
                    "messages": control},
        "seconds": {"experiment": round(t_exp, 2),
                    "des_and_control": round(t_des, 2)},
    }
    row["pass"] = all(row[p]["pass"] for p in ("part1", "part2", "part3"))
    return row


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True,
                   help="comma-separated seeds and lo-hi ranges")
    p.add_argument("--jobs", type=int, default=4)
    p.add_argument("--control-messages", type=int, default=1,
                   help="leading messages on which the control is read too")
    p.add_argument("--part3-only", action="store_true",
                   help="one experiment a seed, the captured one: part 2 is "
                   "not run (for part 3's readings on many seeds at a size "
                   "where an experiment takes minutes)")
    p.add_argument("--out", required=True)
    a = p.parse_args(argv)
    seeds = parse_seeds(a.seeds)
    ctx = multiprocessing.get_context("spawn")
    rows = []
    with ctx.Pool(a.jobs) as pool:
        jobs = [(a.workload, s, a.control_messages, a.part3_only)
                for s in seeds]
        for row in pool.imap_unordered(
                rehearse_seed, jobs,
                chunksize=max(1, min(4, len(jobs) // (4 * a.jobs)))):
            rows.append(row)
            if not row["pass"] or len(rows) % 50 == 0:
                print(f"{len(rows)}/{len(seeds)} seed {row['seed']} "
                      f"pass={row['pass']}", flush=True)
    rows.sort(key=lambda r: r["seed"])
    sys.path.insert(0, CHECKOUT)
    from benchmark.harness import manifest   # no JAX in the parent

    cell = manifest.load_cell(a.workload)
    sound = [m for r in rows for m in r["part3"]["messages"]]
    low = [m for r in rows for m in r["control"]["messages"]]
    summary = {
        "workload": a.workload, "backend": "XLA:CPU (no device number here)",
        "seeds": len(rows), "passed": sum(r["pass"] for r in rows),
        "part2_run": not a.part3_only,
        "failed_seeds": [r["seed"] for r in rows if not r["pass"]],
        "control_passed_seeds": [r["seed"] for r in rows
                                 if not r["control"]["fails"]],
        "reference": cell.config["reference"],
        "messages_compared": len(sound),
        **cell.entry.summarised(sound),
        "control_messages_compared": len(low),
        **cell.entry.summarised(low, control=True),
    }
    with open(a.out, "w") as f:
        f.write('{"summary": ' + json.dumps(summary) + ',\n "rows": [\n')
        f.write(",\n".join(json.dumps(r) for r in rows))
        f.write("\n]}\n")
    print(json.dumps(summary))
    return 0 if summary["passed"] == len(rows) else 1


if __name__ == "__main__":
    sys.exit(main())
