"""The entry `attack` (ISSUE 49), rehearsed: the attack campaign at 64 peers,
fractions 0 / 0.2 and two trial seeds (configs/tiny-attack.json under the
mix traffic/sybil-tiny.json, BENCHMARK.attack.test.json; in no manifest the
driver reads: the deployment's cell, `attack-2k.sybil`, is BENCHMARK.json's)
goes through
benchmark/run.py --rehearse to `correct` true on XLA:CPU, set-up, window and
parts 1 to 3, with benchmark/entries/attack.py found by the configuration's
`entry` and no harness file knowing of it; the traced run reports what the
campaign's path has to read; the control fails; and the plain reference
imports nothing of the program, as test_des_copy.py holds the DES."""

import json
import os
import subprocess
import sys

from benchmark import control, run
from benchmark.entries import attack as attack_entry
from benchmark.harness import manifest, program_profile, reference_check
from benchmark.reference import attack_plain

HERE = os.path.dirname(os.path.abspath(__file__))
ATTACK = os.path.join(HERE, "BENCHMARK.attack.test.json")
CELL = "tiny-attack.sybil-tiny"


def _lines(out: str) -> list[dict]:
    return [json.loads(ln) for ln in out.strip().splitlines()]


def test_attack_plain_imports_nothing_of_the_program():
    with open(attack_plain.__file__) as f:
        source = f.read()
    imported = {line.split()[1] for line in source.splitlines()
                if line.lstrip().startswith(("import ", "from "))}
    # jax once, inside `draws`: the selection's draws are data to it
    assert imported <= {"__future__", "math", "numpy", "jax",
                        "benchmark.reference.des"}, imported
    assert source.count("import jax") == 1
    assert "from dst_libp2p_test_node_tpu" not in source
    assert "import dst_libp2p_test_node_tpu" not in source


def test_the_entry_is_benchmark_entries_attack_with_every_part():
    cell = manifest.load_cell(CELL, ATTACK)
    assert cell.entry_name == "attack" and cell.entry is attack_entry
    for part in manifest.ENTRY_PARTS:
        assert hasattr(cell.entry, part)
    argv, env = cell.entry.invocation(cell, 2147483999, "/tmp/out")
    assert env == {}
    assert argv == [
        "attack", "--scenario", "sybil_graft_flood", "-n", "64",
        "--fractions", "0,0.2", "--seeds", "2147483999,2147484000",
        "--seed", "2147483999", "--messages", "3", "--msg-size", "2000",
        "--delay-s", "1.0", "--warmup-s", "30", "--attack-heartbeats", "20",
        "--connect-to", "10", "--publisher-id", "4",
        "--json", "/tmp/out/campaign1.json",
        "--stats-json", "/tmp/out/stats1.json"]
    # the deployment's own cell runs the same entry, the sweep its mix gives
    with open(os.path.join(manifest.CHECKOUT, "BENCHMARK.json")) as f:
        accepted = json.load(f)
    cell = manifest.load_cell(CELL, ATTACK)
    real = manifest.load_cell("attack-2k.sybil")
    assert real.config_name == "attack-2k" and real.traffic_name == "sybil"
    assert real.entry is attack_entry and real.chips == 1
    argv, _ = real.entry.invocation(real, 7, "o")
    assert argv[:11] == [
        "attack", "--scenario", "sybil_graft_flood", "-n", "2048",
        "--fractions", "0,0.1,0.2", "--seeds", "7,8,9,10", "--seed", "7"]
    assert argv[11:25] == [
        "--messages", "3", "--msg-size", "2000", "--delay-s", "1.0",
        "--warmup-s", "30", "--attack-heartbeats", "20", "--connect-to",
        "10", "--publisher-id", "4"]
    assert "sweep" not in real.config["attack"]
    assert "sweep" not in cell.config["attack"]
    # the rehearsal reads what the deployment's cell reads, entry for entry
    # but for the cell's name: the publish and heartbeat layers of this path
    # by the accepted metrics that read them everywhere else (the cell is
    # appended to their lists), the campaign's own by the thirteen
    # `attack.*`; and every metric that lists no cell finds something here
    entries = {m["name"]: m for m in accepted["per_layer"]}
    assert [m["name"] for m in cell.per_layer] == [
        m["name"] for m in real.per_layer]
    for m in cell.per_layer:
        assert {k: v for k, v in m.items()
                if k not in ("workloads", "spec")} == {
            k: v for k, v in entries[m["name"]].items() if k != "workloads"}
    assert {m["name"] for m in real.per_layer} >= {
        "publish.prepare.host_s", "publish.dispatch.host_s",
        "publish.read.host_s", "publish.host_s", "publish.device_s",
        "publish.fast.device_s", "publish.refine.device_s",
        "publish.accounting.device_s", "publish.fast_iters",
        "publish.refined_share", "heartbeat.device_s",
        "attack.window.device_s", "attack.window.heartbeat.device_s",
        "attack.window.adversary.device_s", "attack.baseline.host_s",
        "attack.setup.host_s", "attack.window.host_s",
        "attack.publish.host_s", "attack.metrics.host_s",
        "attack.device_reads", "attack.hb_to_graylist_max",
        "attack.honest_coverage_min", "attack.graylisted_frac_final_min",
        "attack.attacker_mesh_share_peak"}
    # `jit_disseminate` and `jit__run_heartbeats` are read once, under the
    # accepted names (review of PR 49, finding 3)
    assert not {"attack.publish.device_s", "attack.warmup.device_s"} & set(
        entries)
    # eleven accepted entries read on this path and keep their lists, which
    # test_setup_metrics.py, test_churn.py and test_frag4.py pin
    for name in ("setup.compile_s", "heartbeat.graft.device_s",
                 "publish.fixpoint.hbm_share"):
        assert "attack-2k.sybil" not in entries[name]["workloads"]
    assert [m["name"] for m in real.end_to_end] == ["experiment_s",
                                                    "setup_s"]
    # the one trial run.py replays is drawn from the seed
    assert {attack_entry.drawn(real, s) for s in range(40)} == set(range(8))


def test_attack_goes_through_run_py_to_correct():
    """As the driver would start it, but for --rehearse and --manifest."""
    p = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--rehearse", "--manifest", ATTACK, "--workload", CELL,
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
        "same seed, same campaign1.json less its clock fields"
    digest = part["statistics_digest"]
    assert len(digest["campaign_sha256"]) == 64
    # a window, nine a publish (its clock; eight leaves of the result, as
    # the parent read them), three an attacked trial, one a benign one
    assert digest["device_reads"] == 1 + 12 * 9 + 2 * 3 + 2
    assert digest["hb_to_graylist_max"] <= digest["hb_budget"] + 2
    # one attacked trial: twenty heartbeats, three publishes, its row
    records = [ln for ln in lines if ln.get("line") == "correct_part3"]
    assert [r["message"] for r in records] == list(range(1, 21)) + [
        101, 102, 103, 200]
    assert len({(r["trial"], r["fraction"], r["trial_seed"])
                for r in records}) == 1
    compared = last["compared"]
    assert list(last)[-1] == "compared"
    assert {"part1.missed", "part2.differing_files",
            "part3.tie.differing_files", "part3.m1.exact_differing",
            "part3.m20.float_beyond", "part3.m20.untouched_differing",
            "part3.m101.survive_differing", "part3.m103.share_beyond",
            "part3.m101.penalty_differing",
            "part3.m102.start_exact_differing",
            "part3.m103.start_float_beyond",
            "part3.m102.credit_rows_differing",
            "part3.m200.row_numbers_differing"} <= set(compared)
    assert all(c["value"] == 0 for c in compared.values())
    said = p.stderr.strip().splitlines()[-len(compared):]
    assert said == [f"compared {k} {c['value']} limit {c['limit']}"
                    for k, c in compared.items()]


def test_attack_traced_reports_what_its_path_has_to_read(capsys):
    """Under the thirteen `attack.*` entries, the accepted entries the cell
    is appended to and every per-layer entry of BENCHMARK.json that lists no
    cell: the spans and counters are read on any backend, the device's only
    where the trace has a device plane, and a reader that finds nothing
    leaves its metric out without raising."""
    cell = manifest.load_cell(CELL, ATTACK)
    with open(os.path.join(manifest.CHECKOUT, "BENCHMARK.json")) as f:
        unlisted = [m["name"] for m in json.load(f)["per_layer"]
                    if "workloads" not in m]
    assert len(unlisted) == 9
    assert [m["name"] for m in cell.per_layer
            if "workloads" not in m] == unlisted
    program_profile.load.cache_clear()
    try:
        rc = run.main(["--manifest", ATTACK, "--workload", CELL, "--seed",
                       "11", "--seconds", "0.5", "--trace", "1",
                       "--rehearse"])
    finally:
        program_profile.load.cache_clear()
    lines = _lines(capsys.readouterr().out)
    assert rc == 0 and lines[-1]["correct"] is True
    would = set(next(ln for ln in lines
                     if ln.get("line") == "rehearse")["would_report"])
    assert would >= {
        # `Simulator.publish`'s own spans and counters, nested in the
        # campaign's `trial/publish`
        "publish.prepare.host_s", "publish.dispatch.host_s",
        "publish.read.host_s", "publish.host_s", "publish.fast_iters",
        "publish.refined_share", "heartbeat.host_s",
        # the campaign's own spans and its one counters annotation
        "attack.baseline.host_s", "attack.setup.host_s",
        "attack.window.host_s", "attack.publish.host_s",
        "attack.metrics.host_s", "attack.device_reads",
        "attack.hb_to_graylist_max", "attack.honest_coverage_min",
        "attack.graylisted_frac_final_min",
        "attack.attacker_mesh_share_peak",
        # what the campaign's turn shares with `run`'s under the same names:
        # every one of the entries that list no cell whose reader needs no
        # device plane
        "build.topology.host_s", "build.simulator.host_s",
        "build.graph.host_s", "build.tables.host_s", "entry.report.host_s",
        "emit.summary.host_s", "entry.self_s", "build.host_s"}
    # XLA:CPU's trace has no device plane: the device's metrics are left out
    assert not would & {"publish.device_s", "heartbeat.device_s",
                        "device.idle_share", "attack.window.device_s",
                        "attack.window.heartbeat.device_s"}


def test_attack_control_fails_part3_on_three_seeds():
    """control.py through the entry's own functions: every sound reading
    passes with 0 differing entries, every control reading fails."""
    cell = manifest.load_cell(CELL, ATTACK)
    work = os.path.join(manifest.CHECKOUT, ".bench_work", "test.attack")
    for seed in (3, 2147483651, 4294967299):
        rows = control.readings(cell, seed, work)
        assert len(rows) == 20 + 3 + 1
        for row in rows:
            assert row["sound_passes"] and not row["control_passes"], row
        for row in rows[:20] + rows[23:]:
            assert not any(v for v, _ in reference_check.limited(
                row["sound"]).values())
        sound = attack_entry.summarised([r["sound"] for r in rows])
        low = attack_entry.summarised([r["control"] for r in rows],
                                      control=True)
        assert sound["sound_heartbeat_differing_max"] == 0
        assert sound["sound_row_differing_max"] == 0
        assert sound["sound_survive_differing_max"] == 0
        assert low["control_heartbeat_differing_min"] > 0
        assert low["control_row_differing_min"] > 0
        assert low["control_share_beyond_min"] > 0.9
