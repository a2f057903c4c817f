"""A configuration names its entry point (ISSUE 42): the dispatch by name
(the default; an unknown entry and a module that lacks a part, each a
sentence at load; the environment of an experiment, restored whatever
`cli.main` did); the entry `run` gives the argv the harness gave before it;
and a second entry, `regression` at 64 peers (benchmark/tests/entries/, in no
manifest the driver reads), goes through benchmark/run.py --rehearse to
`correct` true with no harness file knowing of it."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from benchmark import control, entries, run
from benchmark.entries import run as run_entry
from benchmark.harness import (
    experiment, manifest, program_profile, reference_check)

HERE = os.path.dirname(os.path.abspath(__file__))
REGRESSION = os.path.join(HERE, "BENCHMARK.regression.test.json")
CELL = "tiny-regression.headline"

# ------------------------------------------------------------ the dispatch


def test_a_configuration_that_names_no_entry_runs_the_default():
    with open(os.path.join(manifest.CHECKOUT, "BENCHMARK.json")) as f:
        man = json.load(f)
    assert entries.DEFAULT == "run"
    for w in man["workloads"]:
        cell = manifest.load_cell(w["name"])
        assert "entry" not in cell.config
        assert cell.entry_name == "run" and cell.entry is run_entry
    tiny = manifest.load_cell("tiny.headline",
                              os.path.join(HERE, "BENCHMARK.test.json"))
    assert tiny.entry is run_entry      # no entries/run.py beside the tests'


def test_the_rehearsed_entry_is_found_beside_its_configuration():
    cell = manifest.load_cell(CELL, REGRESSION)
    assert cell.entry_name == "regression"
    assert cell.entry.__name__ == "benchmark.tests.entries.regression"
    assert not os.path.exists(os.path.join(
        manifest.BENCH_DIR, "entries", "regression.py"))
    for part in manifest.ENTRY_PARTS:
        assert hasattr(cell.entry, part) and hasattr(run_entry, part)


@pytest.mark.parametrize("name,says", [
    ("nosuch", "there is no benchmark/entries/nosuch.py (has: ['run'])"),
    ("../run", "there is no benchmark/entries/../run.py"),
    ("lacking", "lacks ['invariants', 'digest_line', 'captured', "
     "'against_reference', 'summarised']"),
])
def test_an_unknown_or_partial_entry_is_a_sentence_at_load(name, says):
    config = os.path.join(HERE, "configs", "tiny-regression.json")
    with pytest.raises(SystemExit) as e:
        manifest.load_entry(name, config)
    assert str(e.value).startswith("benchmark: ") and says in str(e.value)


def test_an_entry_beside_a_configuration_outside_the_checkout_is_not_run(
        tmp_path):
    """Only this checkout's files are the benchmark's: a module beside a
    configuration somewhere else is no place to look, and the sentence says
    so where an import of a `..` package would have been a traceback."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "entries").mkdir()
    (tmp_path / "entries" / "elsewhere.py").write_text("raise RuntimeError")
    config = tmp_path / "configs" / "c.json"
    config.write_text("{}")
    with pytest.raises(SystemExit) as e:
        manifest.load_entry("elsewhere", str(config))
    assert "there is no benchmark/entries/elsewhere.py" in str(e.value)
    # and the default is found from there as from anywhere
    assert manifest.load_entry("run", str(config)) is run_entry


def test_an_unknown_entry_stops_run_py_before_any_experiment(
        tmp_path, capsys):
    with open(REGRESSION) as f:
        man = json.load(f)
    with open(os.path.join(manifest.CHECKOUT, man["configs"][0]["file"])) as f:
        config = json.load(f)
    config["entry"] = "nosuch"
    (tmp_path / "configs").mkdir()
    path = tmp_path / "configs" / "c.json"
    path.write_text(json.dumps(config))
    man["configs"][0]["file"] = str(path)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    with pytest.raises(SystemExit) as e:
        run.main(["--manifest", str(tmp_path / "BENCHMARK.json"),
                  "--workload", CELL, "--seed", "1", "--seconds", "0.5",
                  "--rehearse"])
    assert "names the entry 'nosuch'" in str(e.value)
    assert capsys.readouterr().out == ""


# ------------------------------------------------------- the environment


@pytest.mark.parametrize("raises", [False, True])
def test_the_environment_is_set_around_cli_main_and_restored(
        monkeypatch, tmp_path, raises):
    from dst_libp2p_test_node_tpu import cli

    monkeypatch.setenv("PEERS", "7")
    monkeypatch.delenv("SEED", raising=False)
    seen, order = {}, []

    def main(argv):
        seen.update(argv=argv, peers=os.environ["PEERS"],
                    seed=os.environ["SEED"])
        order.append("main")
        if raises:
            raise RuntimeError("the program fell over")
        return 0

    class Around:
        def __enter__(self):
            # the span is entered inside the environment, just around main
            order.append(("span", os.environ["PEERS"]))

        def __exit__(self, *exc):
            order.append("span closed")

    monkeypatch.setattr(cli, "main", main)
    call = lambda: experiment.call_cli(        # noqa: E731
        ["regression"], {"PEERS": "64", "SEED": "5"}, str(tmp_path), Around)
    if raises:
        with pytest.raises(RuntimeError):
            call()
    else:
        rc, seconds = call()
        assert rc == 0 and seconds >= 0
    assert seen == {"argv": ["regression"], "peers": "64", "seed": "5"}
    assert order == [("span", "64"), "main", "span closed"]
    assert os.environ["PEERS"] == "7" and "SEED" not in os.environ


def test_run_reads_no_environment():
    cell = manifest.load_cell("runsh-100k-churn.headline")
    assert cell.entry.invocation(cell, 3, "out")[1] == {}


# ------------------------------------- `run`: the argv the harness gave

ARGV = {   # benchmark/harness/experiment.run_argv at the parent of ISSUE 42
    "runsh-1k.headline": "1 1000 15000 1 10 50 150 40 130 5 0.0 4 0 4000",
    "runsh-100k.headline": "1 100000 15000 1 3 50 150 40 130 5 0.0 4 0 4000",
    "runsh-100k.meshonly":
        "1 100000 15000 1 3 50 150 40 130 5 0.0 4 0 4000 --no-gossip",
    "runsh-100k-frag4.headline":
        "1 100000 15000 4 3 50 150 40 130 5 0.0 4 0 4000",
    "runsh-100k-128k-frag4.headline":
        "1 100000 131072 4 3 50 150 40 130 5 0.0 4 0 12000",
    "runsh-100k-churn.headline":
        "1 100000 15000 4 3 50 150 40 130 5 0.0 4 0 4000 "
        "--churn 0.0001:0.00005",
}


@pytest.mark.parametrize("cell_name", sorted(ARGV))
def test_run_gives_the_argv_the_harness_gave(cell_name):
    cell = manifest.load_cell(cell_name)
    argv, env = cell.entry.invocation(cell, 2147483777, "out")
    assert argv == ["run", *ARGV[cell_name].split(), "--seed", "2147483777",
                    "--stats-json", "--out-prefix", "out" + os.sep]
    assert env == {}


def test_every_cell_of_the_manifest_is_in_that_table():
    with open(os.path.join(manifest.CHECKOUT, "BENCHMARK.json")) as f:
        man = json.load(f)
    on_run = [w["name"] for w in man["workloads"]
              if manifest.load_cell(w["name"]).entry is run_entry]
    assert set(on_run) == set(ARGV)


# ------------------------------------------- the second entry, rehearsed


def _lines(out: str) -> list[dict]:
    return [json.loads(ln) for ln in out.strip().splitlines()]


def test_regression_goes_through_run_py_to_correct():
    """As the driver would start it, but for --rehearse and --manifest."""
    p = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--rehearse", "--manifest", REGRESSION, "--workload", CELL,
         "--seed", "2147483999", "--seconds", "0.5", "--trace", "0"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PEERS": "9"})
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
    # the configuration's 64 peers, not the 9 of the caller's environment
    assert part["statistics_digest"]["coverage"] == 64.0
    assert len(part["statistics_digest"]["latencies_sha256"]) == 64
    assert sum(ln.get("line") == "correct_part3" for ln in lines) == 3
    assert part["correct_part3"]["t0_ms"] >= 45000.0   # STARTSLEEP / 4
    # every number compared beside its limit: the result's last key, and
    # the last lines of stderr
    assert list(last)[-1] == "compared"
    compared = last["compared"]
    assert {"part1.missed", "part2.differing_files",
            "part3.tie.differing_files", "part3.m0.reached_differing",
            "part3.m2.share_beyond_hop"} <= set(compared)
    assert all(c["value"] <= c["limit"] for c in compared.values())
    said = p.stderr.strip().splitlines()[-len(compared):]
    assert said == [f"compared {k} {c['value']} limit {c['limit']}"
                    for k, c in compared.items()]


@pytest.mark.parametrize("per_layer", ["its own", "BENCHMARK.json's"])
def test_regression_traced_reports_what_its_path_has_to_read(
        capsys, tmp_path, per_layer):
    """A cell on another entry under every per-layer metric of
    BENCHMARK.json that lists no cell (as a cell a later PR adds is): each
    reader is asked, one that finds nothing is left out, nothing raises.
    What `regression` shares with `run` (the Simulator, `disseminate`) has
    something to read; `run`'s own spans have not."""
    path = REGRESSION
    if per_layer != "its own":
        with open(REGRESSION) as f:
            man = json.load(f)
        with open(os.path.join(manifest.CHECKOUT, "BENCHMARK.json")) as f:
            man["per_layer"] = [m for m in json.load(f)["per_layer"]
                                if "workloads" not in m]
        assert len(man["per_layer"]) == 31
        path = str(tmp_path / "BENCHMARK.json")
        with open(path, "w") as f:
            json.dump(man, f)
    # one traced window a process, so the readers keep its profile: not
    # this one for the next test's
    program_profile.load.cache_clear()
    try:
        rc = run.main(["--manifest", path, "--workload", CELL, "--seed", "11",
                       "--seconds", "0.5", "--trace", "1", "--rehearse"])
    finally:
        program_profile.load.cache_clear()
    lines = _lines(capsys.readouterr().out)
    assert rc == 0 and lines[-1]["correct"] is True
    would = set(next(ln for ln in lines
                     if ln.get("line") == "rehearse")["would_report"])
    # XLA:CPU has no device plane: no device_trace metric finds anything
    if per_layer == "its own":
        assert would <= {"device.idle_share", "device.peak_hbm_gib"}
        return
    sources = {m["name"]: m["source"] for m in man["per_layer"]}
    assert not [m for m in would - {"device.idle_share"}
                if sources[m] == "device_trace"]
    assert {"publish.host_s", "publish.fast_iters", "publish.read.host_s",
            "heartbeat.host_s", "build.host_s"} <= would
    assert not would & {"entry.artifacts.host_s", "entry.report.host_s",
                        "build.topology.host_s", "emit.latencies.host_s"}


def test_regression_broken_where_it_publishes_is_not_correct(
        capsys, monkeypatch):
    import jax.numpy as jnp

    from dst_libp2p_test_node_tpu.runtime import simulator as simmod

    sound = simmod.disseminate

    def broken(state, *args, **kw):
        out = sound(state, *args, **kw)
        res = out[0]
        shift = jnp.where(jnp.arange(res.delay_ms.shape[0]) % 2 == 0, 3.0,
                          0.0)
        return (dataclasses.replace(res, delay_ms=res.delay_ms + shift),
                *out[1:])

    monkeypatch.setattr(simmod, "disseminate", broken)
    rc = run.main(["--manifest", REGRESSION, "--workload", CELL, "--seed",
                   "7", "--seconds", "0.5", "--trace", "0", "--rehearse"])
    lines = _lines(capsys.readouterr().out)
    assert rc == 0 and lines[-1]["correct"] is False
    part = {ln["line"]: ln for ln in lines[:-1]}
    assert part["correct_part1"]["passed"] and part["correct_part2"]["passed"]
    assert part["correct_part3_tie"]["passed"]
    p3 = part["correct_part3"]
    assert p3["passed"] is False and p3["reached_differing"] == 0
    assert p3["share_beyond"] > p3["limit_share_beyond"]
    assert lines[-1]["compared"]["part3.m0.share_beyond"]["value"] \
        == next(ln for ln in lines
                if ln.get("line") == "correct_part3")["share_beyond"]


def test_regression_control_fails_part3_on_three_seeds():
    """control.py through the entry's own functions, `run` unnamed."""
    cell = manifest.load_cell(CELL, REGRESSION)
    work = os.path.join(manifest.CHECKOUT, ".bench_work", "test.regression")
    for seed in (3, 2147483651, 4294967299):
        rows = control.readings(cell, seed, work)
        assert [r["message"] for r in rows] == [0, 1, 2]
        for row in rows:
            assert row["sound_passes"] and not row["control_passes"]
            assert row["sound"]["reached_differing"] == 0
            assert list(reference_check.limited(row["sound"])) == [
                "reached_differing", "share_beyond", "share_beyond_hop"]
            assert row["control"]["share_beyond"] >= 3 * cell.config[
                "reference"]["eps"]


def test_the_summaries_keep_the_keys_the_committed_rehearsals_have():
    """control.py's and rehearse_seeds.py's summaries list what the entry's
    `summarised` gives: for `run`, the keys of benchmark/rehearsal/*.json,
    in their order."""
    record = {"reached_differing": 0, "share_beyond": 0.1,
              "share_beyond_hop": 0.0, "max_abs_diff_ms": 3.5}
    other = {**record, "share_beyond": 0.2, "max_abs_diff_ms": 1.0}
    sound = run_entry.summarised([record, other])
    low = run_entry.summarised([record, other], control=True)
    assert sound == {"sound_reached_differing_max": 0,
                     "sound_share_beyond_max": 0.2,
                     "sound_share_beyond_hop_max": 0.0,
                     "sound_max_abs_diff_ms_max": 3.5}
    assert low == {"control_share_beyond_min": 0.1,
                   "control_share_beyond_hop_min": 0.0}
    assert run_entry.summarised([], control=True) == {
        "control_share_beyond_min": None, "control_share_beyond_hop_min": None}
    with open(os.path.join(manifest.BENCH_DIR, "rehearsal",
                           "part3_100k_churn.json")) as f:
        committed = list(json.loads(f.readline().rstrip(",\n") + "}")
                         ["summary"])
    at = committed.index("messages_compared")
    assert committed[at:] == ["messages_compared", *sound,
                              "control_messages_compared", *low]


def test_rehearse_seeds_writes_the_row_the_committed_file_has():
    """Seed 0 of runsh-1k.headline through rehearse_seeds.py's worker, one
    experiment (--part3-only): the row of benchmark/rehearsal/seeds_1k.json,
    its digest, statistics and readings (the simulated statistics are the
    same bits on every backend)."""
    from benchmark import rehearse_seeds

    with open(os.path.join(manifest.BENCH_DIR, "rehearsal",
                           "seeds_1k.json")) as f:
        f.readline(), f.readline()
        was = json.loads(f.readline().rstrip(",\n"))
    row = rehearse_seeds.rehearse_seed(("runsh-1k.headline", 0, 1, True))
    assert was["seed"] == row["seed"] == 0 and row["pass"]
    assert row["part1"] == was["part1"]
    assert row["part2"] == {**was["part2"], "run": False}
    assert row["part3"]["run_py_checks"] == was["part3"]["run_py_checks"]
    assert row["control"]["fails"]
    for now, then in zip(row["part3"]["messages"], was["part3"]["messages"],
                         strict=True):
        assert {k: now[k] for k in then} == then
    assert list(row["seconds"]) == ["experiment", "des_and_control"]


def test_a_dropped_summary_line_is_a_fault_of_part1(tmp_path):
    cell = manifest.load_cell(CELL, REGRESSION)
    line = "shadow.data/hosts/peer%d/main.1000.stdout:1:7 milliseconds: %d\n"
    (tmp_path / "latencies1").write_text(
        "".join(line % (p, 0 if p == 1 else 150) for p in range(64)) * 3)
    (tmp_path / "stdout.txt").write_text(
        "Regression summary\nMesh degree: mean 5.9\nCoverage: 100.0%\n")
    found = cell.entry.invariants(cell, str(tmp_path))
    assert found["faults"] == [] and len(found["digest"]) == 64
    assert found["stats"]["coverage"] == 64.0
    assert experiment.Outcome(1, 0.1, 0, **found).ok    # its fields
    (tmp_path / "stdout.txt").write_text("Mesh degree: mean 5.9\n")
    found = cell.entry.invariants(cell, str(tmp_path))
    assert "no 'Coverage:'" in found["faults"][0]
    assert not experiment.Outcome(1, 0.1, 0, **found).ok
    (tmp_path / "stdout.txt").write_text(
        "Mesh degree: mean 5.9\nCoverage: 40.0%\n")
    faults = cell.entry.invariants(cell, str(tmp_path))["faults"]
    assert "coverage 0.4" in faults[0] and "192 lines" in faults[1]
