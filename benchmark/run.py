#!/usr/bin/env python3
"""The benchmark: one cell, one run.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell is a closed loop of whole experiments through
`dst_libp2p_test_node_tpu.cli.main([<entry>, ...])` in this process, by the
configuration's `entry` (see benchmark/README.md; the argv and environment
of an experiment, its invariants and its reference check are
benchmark/entries/<entry>.py's, and nothing here knows which entry it is).
Set-up is imports, the device, and one warm-up experiment on `--seed` (it
compiles on the first run in a checkout and reads <checkout>/.jax_cache
after). The window then runs experiment after experiment, iteration i on
`--seed + i`, until `--seconds` have passed and the experiment in flight has
returned. `correct` is decided after the window (parts 1 to 3 below).
Earlier lines are JSON objects with a "line" key; the last line of stdout is
the result.

It measures on a TPU only. `--rehearse` drives the same path on whatever
backend JAX has, to rehearse `correct` and the control flow; it prints no
metric.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse          # noqa: E402
import contextlib        # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
from types import SimpleNamespace  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

EXPERIMENT_SPAN = "experiment"


def say(line: str, /, **fields) -> None:
    print(json.dumps({"line": line, **fields}), flush=True)


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="run on whatever backend JAX has; print no metric")
    p.add_argument("--manifest", default=None,
                   help="another manifest than <checkout>/BENCHMARK.json "
                   "(benchmark/tests)")
    a = p.parse_args(argv)
    if a.seed < 0:
        # np.random.default_rng(seed ^ 0x6D736749) in Simulator raises on one
        p.error(f"--seed must not be negative, got {a.seed}")
    return a


def require_devices(chips: int, rehearse: bool):
    """The device gate: a TPU with the chips the cell asks for, or exit."""
    import jax

    devices = jax.devices()
    if not rehearse and (devices[0].platform != "tpu"
                         or len(devices) < chips):
        sys.exit(f"benchmark: the cell needs {chips} TPU chip(s), JAX found "
                 f"{len(devices)} {devices[0].platform!r} device(s); no "
                 "numbers from anything else (--rehearse prints none)")
    return devices


def window(cell, seed, seconds, work, recorder, trace_dir):
    """The measured window. Returns (outcomes, traced), where `traced` is
    how many leading experiments ran under the profiler."""
    import jax

    from benchmark.harness.experiment import run_experiment

    traced = 0
    tracing = recorder is not None
    around = ((lambda: recorder.span(EXPERIMENT_SPAN)) if tracing
              else contextlib.nullcontext)
    outcomes = []
    if tracing:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host annotations only
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            i = len(outcomes)
            outcomes.append(run_experiment(
                cell, seed + i, os.path.join(work, f"iter{i}"), around))
            if tracing and i + 1 == int(cell.config["trace_experiments"]):
                jax.profiler.stop_trace()
                tracing, traced = False, i + 1
    finally:
        if tracing:
            jax.profiler.stop_trace()
            traced = len(outcomes)
    return outcomes, traced


def end_to_end(cell, times, setup_s) -> dict:
    from benchmark.harness import manifest

    samples = {"experiment_seconds": times, "setup_seconds": [setup_s]}
    return {m["name"]: {"value": manifest.statistic(m["spec"], samples),
                        "unit": m["unit"]}
            for m in cell.end_to_end}


def per_layer(cell, ctx) -> dict:
    from benchmark.harness import manifest

    out = {}
    for m in cell.per_layer:
        spec = m["spec"]
        value = manifest.reader(spec["reader"])(ctx, **spec.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    a = parse(argv)
    from benchmark.harness import manifest, reference_check, spans, trace
    from benchmark.harness.experiment import run_experiment

    cell = manifest.load_cell(a.workload, a.manifest)
    try:
        from dst_libp2p_test_node_tpu.runtime.compile_cache import (
            enable_compile_cache,
        )
        from dst_libp2p_test_node_tpu.runtime.profiling import count_retraces
    except ImportError as e:
        sys.exit(f"benchmark: the program is not in this checkout: {e}")
    devices = require_devices(cell.chips, a.rehearse)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if not a.rehearse:
        manifest.peaks(device["kind"])      # an unknown device is an error
    cache_dir = enable_compile_cache()
    say("device", **device, compile_cache_dir=cache_dir,
        workload=cell.name, seed=a.seed, seconds=a.seconds, trace=a.trace,
        rehearse=a.rehearse)

    work = os.path.join(CHECKOUT, ".bench_work", cell.name)
    shutil.rmtree(work, ignore_errors=True)
    trace_dir = os.path.join(work, "trace")
    recorder = spans.Recorder() if a.trace else None
    with (spans.installed(recorder, cell.spans) if a.trace
          else contextlib.nullcontext()):
        warm = run_experiment(cell, a.seed, os.path.join(work, "warmup"))
        setup_s = time.perf_counter() - T_PROCESS_START
        with count_retraces() as retraces:
            outcomes, traced = window(cell, a.seed, a.seconds, work,
                                      recorder, trace_dir)
    memory = [d.memory_stats() or {} for d in devices]
    times = [o.seconds for o in outcomes]
    say("window", experiments=len(outcomes), traced=traced,
        compilations_in_window=retraces.count, setup_s=setup_s,
        warmup_experiment_s=warm.seconds,
        first_s=times[0], last_s=times[-1], min_s=min(times), max_s=max(times))
    say("statistics_digest", seed=a.seed, **cell.entry.digest_line(warm))

    # part 1: the exact invariants, in the warm-up and in every experiment
    failed = [o for o in outcomes if not o.ok]
    for o in ([] if warm.ok else [warm]) + failed:
        say("correct_part1_fault", seed=o.seed, rc=o.rc, faults=o.faults)
    part1 = warm.ok and not failed
    missed = len(failed) + (not warm.ok)
    say("correct_part1", what="invariants of every experiment, exact",
        experiments=len(outcomes) + 1, missed=missed, limit=0, passed=part1)
    # part 2: iteration 0 repeats the warm-up experiment, byte for byte
    part2 = warm.digest != "" and outcomes[0].digest == warm.digest
    say("correct_part2", what=f"same seed, same {warm.digest_of}",
        seed=a.seed, warmup=warm.digest, iteration0=outcomes[0].digest,
        differing_files=int(not part2), limit=0, passed=part2)
    # part 3: the same experiment once more, what the entry checks of it
    # captured, against the entry's plain reference
    t0 = time.perf_counter()
    captured, compared, ref_seconds = reference_check.check(
        cell, a.seed, os.path.join(work, "reference"))
    tied = captured.ok and captured.digest == warm.digest
    say("correct_part3_tie", what="the captured experiment writes the timed "
        f"experiments' {warm.digest_of}", seed=a.seed, rc=captured.rc,
        faults=captured.faults, captured=captured.digest,
        timed=warm.digest, differing_files=int(not tied), limit=0,
        passed=tied)
    for record in compared:
        say("correct_part3", **record)
    part3 = tied and bool(compared) and all(r["passed"] for r in compared)
    say("reference_seconds", **ref_seconds,
        after_window_s=time.perf_counter() - t0)
    numbers = {
        "part1.missed": (missed, 0),
        "part2.differing_files": (int(not part2), 0),
        "part3.tie.differing_files": (int(not tied), 0),
        "part3.compared_none": (int(not compared), 0),
        **{f"part3.m{r['message']}.{k}": pair for r in compared
           for k, pair in reference_check.limited(r).items()}}

    result = {"correct": bool(part1 and part2 and part3),
              "attempted": len(outcomes), "failed": len(failed)}
    device["memory_peak_bytes"] = max(
        (m.get("peak_bytes_in_use", 0) for m in memory), default=0)
    if not a.trace:
        result["metrics"] = end_to_end(cell, times, setup_s)
    else:
        rows = trace.load_events(trace_dir, spans.ANNOTATION_PREFIX)
        wins = trace.windows(rows, spans.ANNOTATION_PREFIX + EXPERIMENT_SPAN)
        ctx = SimpleNamespace(
            recorder=recorder, memory_stats=memory,
            experiments=[(s.start, s.end) for s in recorder.spans
                         if s.name == EXPERIMENT_SPAN][:traced],
            trace_rows=rows, trace_windows=wins)
        result["metrics"] = per_layer(cell, ctx)
        device["busy_s"], device["window_s"] = trace.busy_and_window_s(
            rows, wins)
        result["breakdown"] = trace.breakdown(rows, wins,
                                              spans.ANNOTATION_PREFIX)
    if a.rehearse:
        # the path was driven; no number of it is a measurement
        say("rehearse", would_report=sorted(result["metrics"]))
        result = {**result, "metrics": {}, "rehearse": True}
        result.pop("breakdown", None)
        for key in ("busy_s", "window_s"):
            device.pop(key, None)
    result["device"] = device
    # every number compared beside its limit: last in the result, and the
    # last lines of stderr
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, (value, limit) in numbers.items()}
    shutil.rmtree(work, ignore_errors=True)
    for name, (value, limit) in numbers.items():
        print(f"compared {name} {value} limit {limit}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
