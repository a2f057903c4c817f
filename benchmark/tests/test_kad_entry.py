"""The entry `kad` (ISSUE 45), rehearsed: the reference's kad-dht node at 64
peers (configs/tiny-kad.json, BENCHMARK.kad.test.json; in no manifest the
driver reads) goes through benchmark/run.py --rehearse to `correct` true on
XLA:CPU, set-up, window and parts 1 to 3, with benchmark/entries/kad.py found
by the configuration's `entry` and no harness file knowing of it; the traced
run reports what the node's path has to read; the control fails."""

import json
import os
import subprocess
import sys

from benchmark import control, run
from benchmark.entries import kad as kad_entry
from benchmark.harness import manifest, program_profile, reference_check

HERE = os.path.dirname(os.path.abspath(__file__))
KAD = os.path.join(HERE, "BENCHMARK.kad.test.json")
CELL = "tiny-kad.headline"


def _lines(out: str) -> list[dict]:
    return [json.loads(ln) for ln in out.strip().splitlines()]


def test_the_entry_is_benchmark_entries_kad_with_every_part():
    cell = manifest.load_cell(CELL, KAD)
    assert cell.entry_name == "kad" and cell.entry is kad_entry
    for part in manifest.ENTRY_PARTS:
        assert hasattr(cell.entry, part)
    argv, env = cell.entry.invocation(cell, 2147483999, "/tmp/out")
    assert argv == ["kad", "--log", "/tmp/out/kadlog1",
                    "--stats-json", "/tmp/out/stats1.json"]
    assert env == {"PEERS": "64", "KAD_BOOTSTRAPS": "3", "KAD_PROBES": "10",
                   "DISCOVERY": "kad-dht", "MUXER": "yamux",
                   "KAD_LEARN_CAP": "all", "SEED": "2147483999"}
    # the deployment's own cell runs the same entry, the same way
    real = manifest.load_cell("kad-10k.headline")
    assert real.entry is kad_entry and real.chips == 1
    assert real.entry.invocation(real, 1, "o")[1]["PEERS"] == "10000"
    assert {m["name"] for m in real.per_layer} >= {
        "kadnode.find_node.device_s", "kadnode.response.device_s",
        "kadnode.learn.device_s", "kadnode.warmup.host_s",
        "kadnode.probe.host_s", "kadnode.record.host_s", "kadnode.hops_mean",
        "kadnode.queries_per_lookup", "kadnode.census_mean",
        "kadnode.bucket_full_share", "kadnode.probe_success_share",
        "kadnode.closest1_share"}


def test_kad_goes_through_run_py_to_correct():
    """As the driver would start it, but for --rehearse and --manifest."""
    p = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--rehearse", "--manifest", KAD, "--workload", CELL,
         "--seed", "2147483999", "--seconds", "0.5", "--trace", "0"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PEERS": "9",
             "KAD_LEARN_CAP": "8"})
    assert p.returncode == 0, p.stderr[-2000:]
    lines = _lines(p.stdout)
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1 and last["metrics"] == {}
    part = {ln["line"]: ln for ln in lines[:-1]}
    for kind in ("correct_part1", "correct_part2", "correct_part3_tie",
                 "correct_part3"):
        assert part[kind]["passed"] is True, part[kind]
    assert part["window"]["compilations_in_window"] == 0
    assert part["correct_part2"]["what"] == "same seed, same kadlog1"
    assert len(part["statistics_digest"]["log_sha256"]) == 64
    # the configuration's 64 peers and no cap, not the caller's environment:
    # 20 waves of 51 lookups, 12 ticks of 10, 17 or 18 requests a lookup
    assert part["statistics_digest"]["queries_tx"] > 16 * (20 * 51 + 120)
    # wave 1, three drawn waves, twelve ticks, the summary
    records = [ln for ln in lines if ln.get("line") == "correct_part3"]
    messages = [r["message"] for r in records]
    assert messages[0] == 101 and len(messages) == 4 + 12 + 1
    assert messages[4:] == list(range(201, 213)) + [300]
    assert all(101 < m <= 120 for m in messages[1:4])
    assert [r["lookups"] for r in records[:-1]] == [51] * 4 + [10] * 12
    assert records[-1]["closest1_checked"] == 51 + 120
    compared = last["compared"]
    assert list(last)[-1] == "compared"
    assert {"part1.missed", "part2.differing_files",
            "part3.tie.differing_files", "part3.m101.start_rtable_differing",
            "part3.m101.end_rtable_differing", "part3.m212.served_differing",
            "part3.m300.summary_numbers_differing"} <= set(compared)
    assert all(c["value"] == c["limit"] == 0 for c in compared.values())
    said = p.stderr.strip().splitlines()[-len(compared):]
    assert said == [f"compared {k} {c['value']} limit {c['limit']}"
                    for k, c in compared.items()]


def test_kad_traced_reports_what_its_path_has_to_read(capsys):
    """Under the twelve `kadnode.*` entries and every per-layer entry of
    BENCHMARK.json that lists no cell: the spans and counters are read on
    any backend, the device's only where the trace has a device plane, and
    a reader that finds nothing leaves its metric out without raising."""
    cell = manifest.load_cell(CELL, KAD)
    with open(os.path.join(manifest.CHECKOUT, "BENCHMARK.json")) as f:
        unlisted = [m["name"] for m in json.load(f)["per_layer"]
                    if "workloads" not in m]
    assert [m["name"] for m in cell.per_layer][:len(unlisted)] == unlisted
    program_profile.load.cache_clear()
    try:
        rc = run.main(["--manifest", KAD, "--workload", CELL, "--seed", "11",
                       "--seconds", "0.5", "--trace", "1", "--rehearse"])
    finally:
        program_profile.load.cache_clear()
    lines = _lines(capsys.readouterr().out)
    assert rc == 0 and lines[-1]["correct"] is True
    would = set(next(ln for ln in lines
                     if ln.get("line") == "rehearse")["would_report"])
    assert would >= {
        "kadnode.warmup.host_s", "kadnode.probe.host_s",
        "kadnode.record.host_s", "kadnode.hops_mean",
        "kadnode.queries_per_lookup", "kadnode.census_mean",
        "kadnode.bucket_full_share", "kadnode.probe_success_share",
        "kadnode.closest1_share",
        # what the node's turn shares with `run`'s under the same names
        "build.topology.host_s", "emit.summary.host_s",
        "entry.report.host_s", "entry.self_s", "build.host_s"}
    # XLA:CPU's trace has no device plane: the device's metrics are left out
    assert not would & {"kadnode.find_node.device_s", "device.idle_share"}


def test_kad_control_fails_part3_on_three_seeds():
    """control.py through the entry's own functions: every sound reading
    passes with 0 differing entries, every control reading fails."""
    cell = manifest.load_cell(CELL, KAD)
    work = os.path.join(manifest.CHECKOUT, ".bench_work", "test.kad")
    for seed in (3, 2147483651, 4294967299):
        rows = control.readings(cell, seed, work)
        assert len(rows) == 4 + 12 + 1
        for row in rows:
            assert row["sound_passes"] and not row["control_passes"], row
            assert not any(v for v, _ in reference_check.limited(
                row["sound"]).values())
        sound = kad_entry.summarised([r["sound"] for r in rows])
        low = kad_entry.summarised([r["control"] for r in rows],
                                   control=True)
        assert set(sound.values()) == {0}
        assert all(v > 0 for v in low.values()), low
        assert low["control_differing_min_wave"] > 50
