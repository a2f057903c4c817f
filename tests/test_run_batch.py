"""`run <runs> ...` with runs > 1 is ONE batched experiment
(runtime/run_batch.py, ops/runs.py), and run i of it is the run made alone
on `--seed s+i-1`: the same `latencies<i>` and `shadowlog<i>` byte for byte,
the same simulated statistics in `stats<i>.json`. runs == 1 keeps the solo
programs, and a call the batch does not take keeps the loop and says so."""

import contextlib
import hashlib
import io
import json
import os

import jax
import numpy as np
import pytest

from dst_libp2p_test_node_tpu import cli
from dst_libp2p_test_node_tpu.runtime import run_batch, simulator
from dst_libp2p_test_node_tpu.runtime.simulator import (ExperimentConfig,
                                                        Simulator)

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2147483659       # the driver's seeds are large: past 32 signed bits
# the clocks of a `stats<i>.json`: the batch's where the runs are batched
CLOCKS = ("wall_s", "peer_rounds_per_sec", "spans", "compile", "process")


def _argv(runs, seed, prefix, *, nodes=64, frags=1, messages=4, rotation=1,
          loss="0.0", flags=()):
    return ["run", str(runs), str(nodes), "1500", str(frags), str(messages),
            "50", "150", "40", "130", "5", loss, "4", str(rotation), "4000",
            "--warmup-s", "20", *flags, "--seed", str(seed), "--stats-json",
            "--out-prefix", prefix]


def _run(argv) -> str:
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert cli.main(argv) == 0
    return out.getvalue()


def _read(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def _stats(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


CASES = {
    "rotation": {},
    "one_publisher": {"rotation": 0},
    "no_gossip": {"flags": ("--no-gossip",)},
    "frag4": {"frags": 4},
    "loss": {"loss": "0.05"},
}


@pytest.mark.parametrize("case", CASES)
def test_run_i_of_a_batch_is_the_run_made_alone(case, tmp_path):
    kw, runs = CASES[case], 3
    batch = str(tmp_path / "batch_")
    said = _run(_argv(runs, SEED, batch, **kw))
    for i in range(1, runs + 1):
        alone = str(tmp_path / f"alone{i}_")
        _run(_argv(1, SEED + i - 1, alone, **kw))
        for name in ("latencies", "shadowlog"):
            assert _read(f"{batch}{name}{i}") == _read(f"{alone}{name}1"), (
                f"{name}{i} of the batch is not --seed {SEED + i - 1} alone")
        got, want = _stats(f"{batch}stats{i}.json"), _stats(
            f"{alone}stats1.json")
        # `process` is in the first turn of a process, whichever that is;
        # `artifacts` counts what turn 1 wrote into shadow.yaml (the
        # loop's later turns write none either)
        assert set(got) ^ set(want) <= {"batch", "process"}
        for key in set(want) - set(CLOCKS) - {"artifacts"}:
            assert got[key] == want[key], (i, key)
        assert got["batch"] == {
            "runs": runs, "index": i, "batched": True,
            "publish_dispatches": 4, "device_reads": 9}
        assert f"Running for turn {i}\nSummary for turn {i}\n" in said
    first = _stats(f"{batch}stats1.json")["spans"]
    # one warm-up scan, one dispatch and one split a message, for all runs
    assert first["warmup"]["count"] == 1
    assert first["publish/dispatch"]["count"] == 4
    assert first["batch/build"]["count"] == 1
    assert first["build/graph"]["count"] == runs
    assert first["batch/split"]["count"] == 5
    later = _stats(f"{batch}stats2.json")["spans"]
    assert "publish" not in later and later["batch/emit"]["count"] == 1


def test_same_seed_same_bytes(tmp_path):
    for name in ("a_", "b_"):
        _run(_argv(2, SEED, str(tmp_path / name), messages=2))
    for i in (1, 2):
        assert (_read(str(tmp_path / f"a_latencies{i}"))
                == _read(str(tmp_path / f"b_latencies{i}")))


@pytest.mark.parametrize("flags,why", [
    (("--use-mix", "--num-mix", "8"), "--use-mix"),
    (("--churn", "0.001"), "--churn"),
])
def test_a_call_the_batch_does_not_take_keeps_the_loop(flags, why, tmp_path,
                                                       monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("the batch took a call it does not take")

    monkeypatch.setattr(run_batch.RunBatch, "__init__", refuse)
    prefix = str(tmp_path / "loop_")
    _run(_argv(2, SEED, prefix, messages=2, flags=flags))
    for i in (1, 2):
        stats = _stats(f"{prefix}stats{i}.json")
        assert stats["batch"]["batched"] is False
        assert stats["batch"]["runs"] == 2 and stats["batch"]["index"] == i
        assert stats["batch"]["kept_loop"].startswith(why)
        assert stats["spans"]["publish"]["count"] == 2    # its own publishes


def test_runs_that_share_no_program_fall_to_the_loop(tmp_path, monkeypatch):
    """A refusal found while building (graphs of which only some admit the
    pull bands) leaves the runs to the loop, which says why."""
    def skewed(self):
        raise run_batch.NotBatchable("1 of 2 graphs admit the pull bands")

    monkeypatch.setattr(run_batch.RunBatch, "_stacked_pull_bands", skewed)
    prefix = str(tmp_path / "loop_")
    _run(_argv(2, SEED, prefix, messages=2))
    for i in (1, 2):
        stats = _stats(f"{prefix}stats{i}.json")["batch"]
        assert stats == {"runs": 2, "index": i, "batched": False,
                         "kept_loop": "1 of 2 graphs admit the pull bands"}
        alone = str(tmp_path / f"alone{i}_")
        _run(_argv(1, SEED + i - 1, alone, messages=2))
        assert (_read(f"{prefix}latencies{i}")
                == _read(f"{alone}latencies1"))


# ------------------------------------------------ runs == 1: the solo route


def _solo_programs() -> dict:
    """The StableHLO text, without debug info, of the two programs a `run 1`
    experiment of this file's shape runs on the device, lowered with the
    arguments `Simulator.publish` and `Simulator.advance` pass."""
    from dst_libp2p_test_node_tpu.config.topology import TopoParams
    from dst_libp2p_test_node_tpu.ops.disseminate import disseminate
    from dst_libp2p_test_node_tpu.ops.heartbeat import _run_heartbeats

    cfg = ExperimentConfig(
        topo=TopoParams(network_size=64, msg_size_bytes=1500, messages=4,
                        anchor_stages=5, min_bandwidth=50, max_bandwidth=150,
                        min_latency=40, max_latency=130),
        publisher_rotation=True, warmup_s=20.0, seed=7)
    sim = Simulator(cfg)
    a = sim.arrays
    publish = disseminate.lower(
        sim.state, a["conns"], a["rev"], sim._stage, sim._lat, sim._bw,
        publisher=4, t0_ms=20000.0, params=sim.params, payload_bytes=1500,
        fragments=1, with_gossip=True, mesh=None, loss_stage=None,
        loss_mode="tcp", lat_edge=sim._lat_edge, loss_edge=None,
        ans_tables=sim._ans_tables, valid_edge=sim._valid_edge,
        censor_edge=None, pull_bands=sim._pull_bands, with_fanout=False,
        return_plan=True)
    scan = _run_heartbeats.lower(
        sim.state, a["conns"], a["rev"], a["out_mask"], sim.params, 20, None)
    return {"jit_disseminate": publish.as_text(),
            "jit__run_heartbeats": scan.as_text()}


@pytest.mark.parametrize("program", ["jit_disseminate",
                                     "jit__run_heartbeats"])
def test_one_run_lowers_the_parents_programs(program):
    """tests/fixtures/lowered_run1.json: `_solo_programs` on the parent of
    the PR that added the batch (f4a2339), with the jax named there; the
    publish re-pinned at PR 53 for its eleventh counter (the fixture says
    which lines)."""
    with open(os.path.join(HERE, "fixtures", "lowered_run1.json")) as f:
        pinned = json.load(f)
    if pinned["jax"] != jax.__version__:
        pytest.skip(f"pinned on jax {pinned['jax']}, this is {jax.__version__}")
    text = _solo_programs()[program]
    assert hashlib.sha256(text.encode()).hexdigest() == pinned[program]


def test_one_run_takes_the_solo_route(tmp_path, monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("runs == 1 is no batch")

    monkeypatch.setattr(run_batch.RunBatch, "__init__", refuse)
    ranks = []
    solo = simulator.disseminate

    def seen(*args, **kw):
        ranks.append(np.ndim(args[1]))
        return solo(*args, **kw)

    monkeypatch.setattr(simulator, "disseminate", seen)
    prefix = str(tmp_path / "one_")
    _run(_argv(1, SEED, prefix))
    assert ranks == [2, 2, 2, 2]
    assert "batch" not in _stats(f"{prefix}stats1.json")


# ----------------------------------------------- a run of a batch, the DES


def test_a_run_of_a_batch_against_the_des(monkeypatch):
    """One run's rows of a batch's first publish, its sampled plan replayed
    by the event-queue reference of tests/test_des_crosscheck.py under that
    file's tolerances."""
    from test_des_crosscheck import _compare

    from dst_libp2p_test_node_tpu.config.topology import Topology, TopoParams

    topo = TopoParams(network_size=64, msg_size_bytes=1500, messages=1,
                      anchor_stages=5, min_bandwidth=50, max_bandwidth=150,
                      min_latency=40, max_latency=130)
    cfgs = [ExperimentConfig(topo=topo, publisher_rotation=True,
                             warmup_s=8.0, seed=seed) for seed in (5, 6, 7)]
    batch = run_batch.RunBatch(cfgs, Topology.build(topo))
    taken = []
    program = simulator.disseminate

    def with_plans(*args, **kw):
        res, states, plans = program(*args, **kw, return_plan=True)
        taken.append((res, plans, kw["t0_ms"]))
        return res, states

    monkeypatch.setattr(simulator, "disseminate", with_plans)
    batch.run()
    (res, plans, t0_ms), = taken
    for r in (0, 2):
        one = jax.tree_util.tree_map(lambda x: x[r], (res, plans))
        _compare(*one, batch.arrays["conns"][r], batch.arrays["rev"][r],
                 batch.params, 4, t0_ms, 1, payload_bytes=1500)
        np.testing.assert_array_equal(
            np.asarray(one[0].delay_ms)[batch.runs[r].records[0].received],
            batch.runs[r].records[0].delays_ms[
                batch.runs[r].records[0].received])
