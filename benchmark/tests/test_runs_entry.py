"""The entry `runs` (ISSUE 52), rehearsed: the runsh-2k experiment at 64 peers
and 4 messages, three runs in the mix traffic/runs3.json
(configs/tiny-runs.json, BENCHMARK.runs.test.json; in no manifest the driver
reads: the deployment's cell, `runsh-2k.runs16`, is BENCHMARK.json's) goes
through benchmark/run.py --rehearse to `correct` true on XLA:CPU, set-up,
window and parts 1 to 3, with benchmark/entries/runs.py found by the
configuration's `entry` and no harness file knowing of it; the capture reads
a program that batches its runs and one that makes them one by one; the
traced run reports what the batch's path has to read; the control fails."""

import json
import os
import subprocess
import sys

import numpy as np

from benchmark import control, run
from benchmark.entries import runs as runs_entry
from benchmark.harness import manifest, program_profile

HERE = os.path.dirname(os.path.abspath(__file__))
RUNS = os.path.join(HERE, "BENCHMARK.runs.test.json")
CELL = "tiny-runs.runs3"


def _lines(out: str) -> list[dict]:
    return [json.loads(ln) for ln in out.strip().splitlines()]


def test_the_entry_is_benchmark_entries_runs_with_every_part():
    cell = manifest.load_cell(CELL, RUNS)
    assert cell.entry_name == "runs" and cell.entry is runs_entry
    for part in manifest.ENTRY_PARTS:
        assert hasattr(cell.entry, part)
    argv, env = cell.entry.invocation(cell, 2147483999, "/tmp/out")
    assert env == {}
    assert argv == [
        "run", "3", "64", "15000", "1", "4", "50", "150", "40", "130", "5",
        "0.0", "4", "1", "4000", "--seed", "2147483999", "--stats-json",
        "--out-prefix", "/tmp/out/"]
    # the deployment's own cell: the same entry, the README's experiment,
    # sixteen runs from the mix
    real = manifest.load_cell("runsh-2k.runs16")
    assert real.config_name == "runsh-2k" and real.traffic_name == "runs16"
    assert real.entry is runs_entry and real.chips == 1
    argv, _ = real.entry.invocation(real, 7, "o")
    assert argv == [
        "run", "16", "2000", "15000", "1", "10", "50", "150", "40", "130",
        "5", "0.0", "4", "1", "4000", "--seed", "7", "--stats-json",
        "--out-prefix", "o/"]
    assert real.config["reduced"] == []
    assert real.config["run"]["positionals"]["runs"] == 1
    assert [m["name"] for m in real.end_to_end] == ["experiment_s",
                                                    "setup_s"]
    # the rehearsal reads what the deployment's cell reads, entry for entry
    # but for the cell's name
    with open(os.path.join(manifest.CHECKOUT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert [m["name"] for m in cell.per_layer] == [
        m["name"] for m in real.per_layer]
    for m in cell.per_layer:
        assert {k: v for k, v in m.items()
                if k not in ("workloads", "spec")} == {
            k: v for k, v in entries[m["name"]].items() if k != "workloads"}
    assert {m["name"] for m in real.per_layer} >= {
        "runs.batched", "runs.publish_dispatches", "runs.device_reads",
        "runs.build.host_s", "runs.split.host_s", "runs.emit.host_s",
        "runs.device_s_per_run", "publish.device_s", "heartbeat.device_s",
        "publish.fast.device_s", "publish.dispatch.host_s",
        "publish.read.host_s", "build.graph.host_s", "build.tables.host_s",
        "emit.latencies.host_s", "emit.host_s", "device.idle_share"}
    # host spans around `Simulator.publish` / `.warmup` / `.advance`, which
    # a batch never calls, would read 0.0 there: the cell is not on their
    # lists
    for name in ("publish.host_s", "heartbeat.host_s"):
        assert "runsh-2k.runs16" not in entries[name]["workloads"]
    # the drawn (run, message) pairs: two runs, two messages of each
    pairs = runs_entry.drawn(real, 2147483999)
    assert len(pairs) == 4 and len({r for r, _ in pairs}) == 2
    assert {r for s in range(60) for r, _ in runs_entry.drawn(real, s)} == (
        set(range(16)))


def test_the_link_model_is_the_programs():
    """What reference/des.py reads of the configuration's file is what the
    program runs that argv with (as test_manifest.py holds runsh-1k)."""
    from dst_libp2p_test_node_tpu.config.env import GossipSubParams
    from dst_libp2p_test_node_tpu.ops.state import SimParams
    from dst_libp2p_test_node_tpu.runtime.simulator import MUXER_PROC_MS

    for name, path in (("runsh-2k.runs16", None), (CELL, RUNS)):
        cell = manifest.load_cell(name, path)
        nodes = int(cell.config["run"]["positionals"]["nodes"])
        params = SimParams.from_gossipsub(
            nodes, 40, GossipSubParams(),
            proc_delay_ms=MUXER_PROC_MS["yamux"])
        for key, value in cell.config["link_model"].items():
            assert getattr(params, key) == value, (name, key)


def test_runs_goes_through_run_py_to_correct():
    """As the driver would start it, but for --rehearse and --manifest."""
    p = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--rehearse", "--manifest", RUNS, "--workload", CELL,
         "--seed", "2147483999", "--seconds", "0.5", "--trace", "0"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
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
    assert part["correct_part2"]["what"] == \
        "same seed, same latencies1 ... latencies3, in order"
    assert len(part["statistics_digest"]["latencies_sha256"]) == 64
    records = [ln for ln in lines if ln.get("line") == "correct_part3"]
    # two of the three runs, two of each one's four messages
    assert len(records) == 4 and len({r["run"] for r in records}) == 2
    for r in records:
        assert r["message"] == 4 * r["run"] + r["message_of_run"]
        assert r["files_differing"] == 0 and r["reached_differing"] == 0
        assert r["receivers"] == 64
    compared = last["compared"]
    assert list(last)[-1] == "compared"
    m = records[0]["message"]
    assert {"part1.missed", "part2.differing_files",
            "part3.tie.differing_files", f"part3.m{m}.files_differing",
            f"part3.m{m}.reached_differing", f"part3.m{m}.share_beyond",
            f"part3.m{m}.share_beyond_hop"} <= set(compared)
    assert all(c["value"] <= c["limit"] for c in compared.values())


def _loop_runs(monkeypatch):
    """Make `run <runs> ...` make its runs one by one, as the parent of the
    PR that added the batch does."""
    from dst_libp2p_test_node_tpu import cli

    monkeypatch.setattr(cli, "_batch_refusal",
                        lambda a: "the test keeps the loop")


def test_the_capture_reads_batched_and_looped_programs_alike(monkeypatch):
    """A program that makes the runs one by one calls `disseminate` a run
    and a message, one that batches them once a message with every run in
    it: the entry takes the same (run, message) items of both, bit for
    bit."""
    cell = manifest.load_cell(CELL, RUNS)
    work = os.path.join(manifest.CHECKOUT, ".bench_work", "test.runs")
    batched, items = runs_entry.captured(cell, 5, work)
    _loop_runs(monkeypatch)
    looped, again = runs_entry.captured(cell, 5, work)
    assert batched.ok and looped.ok and batched.digest == looped.digest
    assert [i["message"] for i in items] == [i["message"] for i in again]
    assert len(items) == 4
    for a, b in zip(items, again):
        assert a["run"] == b["run"] and a["files_differing"] == 0 == (
            b["files_differing"])
        assert a["conns"].shape == b["conns"].shape == (64, 40)
        for key in ("conns", "rev", "delay_ms", "received"):
            np.testing.assert_array_equal(a[key], b[key])
        for key, value in a["plan"].items():
            if value is not None:
                np.testing.assert_array_equal(value, b["plan"][key])
        assert a["t0_ms"] == b["t0_ms"] and a["publisher"] == b["publisher"]


def test_runs_traced_reports_what_its_path_has_to_read(capsys, monkeypatch):
    """The seven `runs.*` entries, the accepted entries the cell is appended
    to and every per-layer entry that lists no cell: spans and counters are
    read on any backend, the device's only where the trace has a device
    plane. A program that makes its runs one by one writes no `batch/*`:
    the `runs.*` readers return nothing and nothing raises."""
    def would_report():
        program_profile.load.cache_clear()
        try:
            rc = run.main(["--manifest", RUNS, "--workload", CELL, "--seed",
                           "11", "--seconds", "0.5", "--trace", "1",
                           "--rehearse"])
        finally:
            program_profile.load.cache_clear()
        lines = _lines(capsys.readouterr().out)
        assert rc == 0 and lines[-1]["correct"] is True
        return set(next(ln for ln in lines
                        if ln.get("line") == "rehearse")["would_report"])

    shared = {
        "publish.prepare.host_s", "publish.dispatch.host_s",
        "publish.read.host_s", "publish.fast_iters", "publish.refined_share",
        "build.topology.host_s", "build.simulator.host_s",
        "build.graph.host_s", "build.tables.host_s", "entry.report.host_s",
        "entry.artifacts.host_s", "emit.summary.host_s", "emit.host_s",
        "emit.latencies.host_s", "emit.shadowlog.host_s", "entry.self_s",
        "build.host_s"}
    own = {"runs.batched", "runs.publish_dispatches", "runs.device_reads",
           "runs.build.host_s", "runs.split.host_s", "runs.emit.host_s"}
    would = would_report()
    assert would >= shared | own
    # XLA:CPU's trace has no device plane: the device's metrics are left out
    assert not would & {"publish.device_s", "heartbeat.device_s",
                        "device.idle_share", "runs.device_s_per_run"}
    _loop_runs(monkeypatch)
    would = would_report()
    assert would >= shared and not would & own


def test_runs_control_fails_part3_on_three_seeds():
    """control.py through the entry's own functions: every sound reading
    passes with no file differing, every control reading fails."""
    cell = manifest.load_cell(CELL, RUNS)
    work = os.path.join(manifest.CHECKOUT, ".bench_work", "test.runs")
    for seed in (3, 2147483651, 4294967299):
        rows = control.readings(cell, seed, work)
        assert len(rows) == 4
        for row in rows:
            assert row["sound_passes"] and not row["control_passes"], row
            assert row["sound"]["files_differing"] == 0
        sound = runs_entry.summarised([r["sound"] for r in rows])
        low = runs_entry.summarised([r["control"] for r in rows],
                                    control=True)
        assert sound["sound_reached_differing_max"] == 0
        assert sound["sound_files_differing_max"] == 0
        assert low["control_share_beyond_min"] > 0.5
