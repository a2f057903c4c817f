"""benchmark/run.py driven end to end on the CPU at 200 peers: --rehearse
prints no metric; without it, off a TPU, nothing runs; with the timed path
broken underneath, `correct` comes out false; the control fails part 3; the
invariants of part 1 are the configuration's guarantees."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from benchmark import control, run
from benchmark.harness import manifest

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "BENCHMARK.test.json")
BASE = ["--manifest", MANIFEST, "--seconds", "0.5"]


def _lines(out: str) -> list[dict]:
    return [json.loads(ln) for ln in out.strip().splitlines()]


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearse_prints_no_metric(capsys, trace):
    rc = run.main([*BASE, "--workload", "tiny.headline", "--seed",
                   "2147483999", "--trace", trace, "--rehearse"])
    lines = _lines(capsys.readouterr().out)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True and last["metrics"] == {}
    assert last["failed"] == 0 and last["attempted"] >= 1
    assert "busy_s" not in last["device"] and "breakdown" not in last
    kinds = [ln.get("line") for ln in lines[:-1]]
    for kind in ("device", "window", "statistics_digest", "correct_part1",
                 "correct_part2", "correct_part3_tie", "correct_part3"):
        assert kind in kinds
    win = lines[kinds.index("window")]
    assert win["compilations_in_window"] == 0
    if trace == "1":
        would = lines[kinds.index("rehearse")]["would_report"]
        assert {"entry.self_s", "build.host_s", "publish.host_s",
                "emit.host_s"} <= set(would)


def test_meshonly_passes_with_gossip_targets_zeroed(capsys):
    rc = run.main([*BASE, "--workload", "tiny.meshonly", "--seed", "5",
                   "--trace", "0", "--rehearse"])
    last = _lines(capsys.readouterr().out)[-1]
    assert rc == 0 and last["correct"] is True


def test_no_tpu_no_result():
    """Off a TPU and without --rehearse: non-zero, and no result line."""
    p = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"), *BASE,
         "--workload", "tiny.headline", "--seed", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_negative_seed_is_rejected():
    with pytest.raises(SystemExit) as e:
        run.parse(["--workload", "x", "--seed", "-1", "--seconds", "1"])
    assert e.value.code != 0


def _shifted(sound, every: int, by_ms: float, only_without_plan=False):
    """`disseminate` with every `every`-th peer's arrival moved by `by_ms`
    where it is produced."""
    import jax.numpy as jnp

    def broken(state, *args, **kw):
        out = sound(state, *args, **kw)
        if only_without_plan and kw.get("return_plan"):
            return out
        res = out[0]
        shift = jnp.where(jnp.arange(res.delay_ms.shape[0]) % every == 1,
                          by_ms, 0.0)
        res = dataclasses.replace(res, delay_ms=res.delay_ms + shift)
        return (res, *out[1:])

    return broken


@pytest.mark.parametrize("every,by_ms,number", [
    (2, 3.0, "share_beyond"),          # half the peers, by a few ms
    (11, 45.0, "share_beyond_hop"),    # 9 % of the peers, by a hop
])
def test_broken_timed_path_is_not_correct(capsys, monkeypatch, every, by_ms,
                                          number):
    """Arrivals moved where they are produced, in every run alike:
    coverage, form, determinism and the tie still hold; the reference
    catches it, by the number that is there for that fault."""
    from dst_libp2p_test_node_tpu.runtime import simulator as simmod

    monkeypatch.setattr(simmod, "disseminate",
                        _shifted(simmod.disseminate, every, by_ms))
    rc = run.main([*BASE, "--workload", "tiny.headline", "--seed", "7",
                   "--trace", "0", "--rehearse"])
    lines = _lines(capsys.readouterr().out)
    assert rc == 0 and lines[-1]["correct"] is False
    part = {ln["line"]: ln for ln in lines[:-1]}
    assert part["correct_part1"]["passed"] and part["correct_part2"]["passed"]
    assert part["correct_part3_tie"]["passed"]
    p3 = part["correct_part3"]
    assert p3["passed"] is False and p3["seed"] == 7
    assert p3[number] > p3["limit_" + number]
    assert p3["reached_differing"] == 0


def test_fault_in_the_timed_program_alone_breaks_the_tie(capsys, monkeypatch):
    """A fault in the program the window drives (no plan returned) that the
    captured run's program does not share: the two write different files."""
    from dst_libp2p_test_node_tpu.runtime import simulator as simmod

    monkeypatch.setattr(simmod, "disseminate", _shifted(
        simmod.disseminate, 11, 45.0, only_without_plan=True))
    rc = run.main([*BASE, "--workload", "tiny.headline", "--seed", "9",
                   "--trace", "0", "--rehearse"])
    lines = _lines(capsys.readouterr().out)
    assert rc == 0 and lines[-1]["correct"] is False
    part = {ln["line"]: ln for ln in lines[:-1]}
    assert part["correct_part1"]["passed"] and part["correct_part2"]["passed"]
    assert part["correct_part3_tie"]["passed"] is False
    assert part["correct_part3"]["passed"]      # the captured run is sound


def test_dropped_receiver_is_not_correct(capsys, monkeypatch):
    """A part of the answers left out: one peer never logs the message."""
    from dst_libp2p_test_node_tpu.runtime import simulator as simmod

    sound = simmod.record_from_result

    def dropping(res, **kw):
        kw["drop_self"] = [17]
        return sound(res, **kw)

    monkeypatch.setattr(simmod, "record_from_result", dropping)
    rc = run.main([*BASE, "--workload", "tiny.headline", "--seed", "8",
                   "--trace", "0", "--rehearse"])
    lines = _lines(capsys.readouterr().out)
    assert rc == 0 and lines[-1]["correct"] is False
    assert lines[-1]["failed"] == lines[-1]["attempted"]
    part1 = next(ln for ln in lines if ln.get("line") == "correct_part1")
    assert part1["passed"] is False


def test_control_fails_part3_on_three_seeds(capsys):
    cell = manifest.load_cell("tiny.headline", MANIFEST)
    ref = cell.config["reference"]
    work = os.path.join(manifest.CHECKOUT, ".bench_work", "test.control")
    for seed in (3, 2147483651, 4294967299):
        rows = control.readings(cell, seed, work)
        assert [r["message"] for r in rows] == [0, 1, 2]
        for row in rows:
            assert row["sound_passes"] and not row["control_passes"]
            assert row["sound"]["reached_differing"] == 0
            assert row["control"]["share_beyond"] >= 3 * ref["eps"]


def test_messages_checked_are_drawn_from_the_seed():
    from benchmark.entries import run as run_entry

    cell = manifest.load_cell("tiny.headline", MANIFEST)

    def checked(cell, seed):
        return run_entry.drawn(cell, seed, 3)

    assert checked(cell, 5) == [0, 1, 2]
    cell.config["reference"]["messages"] = 1
    drawn = {tuple(checked(cell, s)) for s in range(2147483648, 2147483688)}
    assert drawn == {(0,), (1,), (2,)}
    assert checked(cell, 77) == checked(cell, 77)


def test_invariants_are_the_configurations_guarantees(tmp_path):
    """check_artifacts holds what the configuration's file guarantees: a
    lossy deployment states a coverage floor and no early-delay rule, and
    the same files then pass."""
    from benchmark.entries.run import check_artifacts

    argv = {"positionals": {"nodes": 4, "num_publishers": 1,
                            "publisher_id": 0, "publisher_rotation": 0}}
    line = "shadow.data/hosts/peer%d/main.1000.stdout:1:7 milliseconds: %d\n"
    (tmp_path / "latencies1").write_text(
        line % (0, 0) + line % (1, 60) + line % (2, 30))
    (tmp_path / "stats1.json").write_text(json.dumps({"coverage": 3}))
    exact = {"coverage_share_min": 1.0, "no_delay_under_ms": 40}
    faults, sha, _ = check_artifacts(str(tmp_path), argv, exact)
    assert len(faults) == 3 and "coverage" in faults[0] and sha
    assert "3 lines" in faults[1] and "under 40 ms" in faults[2]
    lossy = {"coverage_share_min": 0.7, "no_delay_under_ms": None}
    assert check_artifacts(str(tmp_path), argv, lossy)[0] == []
    (tmp_path / "stats1.json").write_text(json.dumps({"coverage": 4}))
    assert "3 lines" in check_artifacts(str(tmp_path), argv, lossy)[0][0]
