"""The cell runsh-100k-churn.headline (ISSUE 36): the manifest finds it and
its `run` argv carries `--churn 0.0001:0.00005` (both rates, in the form
the parent of ISSUE 36 cannot parse, so that it fails cleanly there); benchmark/run.py --rehearse drives
all three parts of `correct` on a churned network on the CPU at 2,000 peers
(the DES replays the plan's dead peers and pruned meshes); a run whose
publisher was let die, as the parent of ISSUE 36 lets it, is not `correct`,
and neither is one in which a dead peer is given a receipt."""

import dataclasses
import json
import os

import pytest

from benchmark import run
from benchmark.entries.run import POSITIONALS, arguments, run_argv
from benchmark.harness import manifest, program_profile

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "BENCHMARK.churn.test.json")
CELL = "runsh-100k-churn.headline"
TINY = "tiny-churn.headline"
NEW = ("heartbeat.churn.device_s", "heartbeat.validity.device_s",
       "heartbeat.graft.device_s", "heartbeat.prune.device_s",
       "publish.alive", "publish.under_dlow", "publish.valid_edge.host_s")
# at 2,000 peers the unspared draw has peer 4 dead at all three publishes
DEAD_PUBLISHER_SEED = 2147483051


def test_the_cell_loads_and_runs_under_churn():
    cell = manifest.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "runsh-100k-churn", "headline")
    argv = run_argv(arguments(cell), 2147483777, "out")
    assert argv[15:17] == ["--churn", "0.0001:0.00005"]
    # runsh-100k-frag4 with the churn PR 30 left out, and nothing else
    other = manifest.load_cell("runsh-100k-frag4.headline")
    assert arguments(cell)["positionals"] == arguments(other)["positionals"]
    assert dict(zip(POSITIONALS, argv[1:]))["num_frag"] == "4"
    assert cell.config["link_model"] == other.config["link_model"]
    assert cell.config["reduced"] == ["num_publishers"]
    assert cell.config["architecture"] is None
    floor = cell.config["guarantees"]["coverage_share_min"]
    assert 0.93 <= floor < 0.9518
    assert "publisher" in cell.config["assumed"]


def test_the_new_metrics_are_the_new_cells_alone():
    with open(os.path.join(manifest.CHECKOUT, "BENCHMARK.json")) as f:
        man = json.load(f)
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "experiment_s"
    assert set(NEW) <= {m["name"] for m in manifest.load_cell(CELL).per_layer}
    for old in ("runsh-1k.headline", "runsh-100k-frag4.headline"):
        names = {m["name"] for m in manifest.load_cell(old).per_layer}
        assert not set(NEW) & names


# ------------------------------------------------------------ the rehearsal


def _rehearse(capsys, trace_flag, seed):
    # one profile a process is what the harness caches; a test process
    # that traced another cell before this one would read that one's
    program_profile.load.cache_clear()
    rc = run.main(["--manifest", MANIFEST, "--seconds", "0.5", "--workload",
                   TINY, "--seed", str(seed), "--trace", trace_flag,
                   "--rehearse"])
    lines = [json.loads(ln)
             for ln in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0
    part = {ln["line"]: ln for ln in lines[:-1]}
    return lines[-1], part, lines


@pytest.mark.parametrize("trace_flag", ["0", "1"])
def test_rehearse_under_churn(capsys, trace_flag):
    # on this seed the parent's draw kills the publisher: spared, it runs
    last, part, lines = _rehearse(capsys, trace_flag, DEAD_PUBLISHER_SEED)
    assert last["correct"] is True and last["metrics"] == {}
    assert last["failed"] == 0 and last["attempted"] >= 1
    for kind in ("correct_part1", "correct_part2", "correct_part3_tie"):
        assert part[kind]["passed"] is True, part[kind]
    assert part["window"]["compilations_in_window"] == 0
    compared = [ln for ln in lines if ln.get("line") == "correct_part3"]
    assert len(compared) == 3
    for c in compared:
        # the DES reaches the peers the program reached: the living that
        # the dead did not cut off
        assert c["passed"] and c["reached_differing"] == 0
        assert 1800 < c["receivers"] < 1950
    if trace_flag == "1":
        # the counters and the host span are read off XLA:CPU's profile too;
        # the device scopes need a device plane, which it has not
        assert {"publish.alive", "publish.under_dlow",
                "publish.valid_edge.host_s"} <= set(
            part["rehearse"]["would_report"])


def test_a_program_that_knows_one_rate_leaves_the_cell_at_once(
        capsys, monkeypatch):
    """The parent of ISSUE 36 parses `--churn` as one float. Given the
    cell's DOWN:UP it ends with argparse's exit 2 inside the warm-up's
    `cli.main`, before any experiment and with no result line: the driver
    then measures the cell on the change alone, and not a dead publisher
    on the parent on one seed in twenty."""
    from dst_libp2p_test_node_tpu import cli

    monkeypatch.setattr(cli, "_churn_rates", float)
    with pytest.raises(SystemExit) as e:
        _rehearse(capsys, "0", DEAD_PUBLISHER_SEED)
    assert e.value.code == 2
    out, err = capsys.readouterr()
    assert "argument --churn: invalid float value" in err
    assert [json.loads(ln)["line"] for ln in out.strip().splitlines()] == [
        "device"]


def test_a_publisher_let_die_is_not_correct(capsys, monkeypatch):
    """The parent of ISSUE 36 spares nobody and asks nothing of the
    publisher: on a seed whose draw kills peer 4 every message is its own
    receipt alone, `cli.main` returns 0, and part 1 refuses the run by the
    configuration's coverage floor."""
    from dst_libp2p_test_node_tpu.runtime import simulator as simmod

    sound = simmod.valid_edge_at_publish

    def parents(*args):
        valid, _ = sound(*args)
        return valid, True

    monkeypatch.setattr(simmod, "valid_edge_at_publish", parents)
    monkeypatch.setattr(simmod, "scheduled_publishers", lambda cfg: [])
    last, part, _ = _rehearse(capsys, "0", DEAD_PUBLISHER_SEED)
    assert last["correct"] is False
    assert last["failed"] == last["attempted"]
    assert part["correct_part1"]["passed"] is False
    fault = part["correct_part1_fault"]["faults"][0]
    assert fault.startswith("coverage 1.0 of 2000 peers")


def test_a_dead_publisher_raises_where_nobody_is_spared(capsys, monkeypatch):
    """Without the sparing alone, the program says so instead of recording
    a message nobody received."""
    from dst_libp2p_test_node_tpu.runtime import simulator as simmod

    monkeypatch.setattr(simmod, "scheduled_publishers", lambda cfg: [])
    with pytest.raises(simmod.PublisherDownError, match="peer 4 is dead"):
        _rehearse(capsys, "0", DEAD_PUBLISHER_SEED)
    capsys.readouterr()


def test_a_dead_peer_given_a_receipt_is_not_correct(capsys, monkeypatch):
    """One dead peer logs every message, a hop after the publish: coverage,
    form and determinism hold; the reference, which sends nothing to a peer
    the plan has dead, reaches one receiver fewer."""
    import numpy as np

    from dst_libp2p_test_node_tpu.runtime import simulator as simmod

    sound = simmod.disseminate

    def leaking(state, conns, rev, *args, **kw):
        out = sound(state, conns, rev, *args, **{**kw, "return_plan": True})
        res, plan = out[0], out[2]
        dead = int(np.nonzero(~np.asarray(plan["can_send"]))[0][0])
        res = dataclasses.replace(
            res, received=res.received.at[dead].set(True),
            delay_ms=res.delay_ms.at[dead].set(60.0))
        return (res, out[1], plan) if kw.get("return_plan") else (res, out[1])

    monkeypatch.setattr(simmod, "disseminate", leaking)
    last, part, lines = _rehearse(capsys, "0", 2147483999)
    assert last["correct"] is False
    assert part["correct_part1"]["passed"] and part["correct_part2"]["passed"]
    assert part["correct_part3_tie"]["passed"]
    for c in (ln for ln in lines if ln.get("line") == "correct_part3"):
        assert c["passed"] is False and c["reached_differing"] == 1
