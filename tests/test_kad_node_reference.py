"""The kad-dht node against its plain reference (XLA:CPU, small):

  the whole path, `cli.main(["kad", "--log", ..., "--stats-json", ...])`
  under the node's environment, wave by wave and in every summary number
  against benchmark/reference/kad_node_plain.py (Python integers, no JAX),
  exactly;

  what the turn leaves: the spans, the `kadnode/counters` annotation, the
  `--stats-json` keys, a log that the same seed writes again byte for byte;

  the array records against the numbers the per-lookup records of the
  parent gave (pinned from the commit before `LookupRecord` went), and the
  regression node's bytes as they were before its wave loop was shared with
  this one.
"""

import copy
import hashlib
import json
import os
import sys

import numpy as np
import pytest

from dst_libp2p_test_node_tpu import cli
from dst_libp2p_test_node_tpu.ops import kad
from dst_libp2p_test_node_tpu.runtime import kad_runtime
from dst_libp2p_test_node_tpu.runtime.kad_runtime import (
    KadConfig, KadSimulator)

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

from benchmark.entries import kad as entry  # noqa: E402
from benchmark.harness import manifest  # noqa: E402
from benchmark.harness.experiment import environment  # noqa: E402
from benchmark.reference import kad_node_plain, kad_plain  # noqa: E402

# one stage, 100 ms: TopoParams' defaults, which the kad entry runs with
STAGE_LATENCY = [[100.0]]


def test_kad_node_plain_imports_no_jax_and_nothing_of_the_program():
    with open(kad_node_plain.__file__) as f:
        source = f.read()
    imported = [line.split()[1] for line in source.splitlines()
                if line.startswith(("import ", "from "))]
    assert set(imported) <= {"__future__", "numpy",
                             "benchmark.reference"}, imported


def test_kad_node_plains_wave_is_kad_plains_and_says_who_was_asked():
    n, seed = 64, 5
    keys = kad_plain.make_keys(n, seed)
    tables = kad_plain.empty_tables(n)
    kad_plain.seed_bootstraps(tables, keys, [0, 1])
    origins = list(range(2, n))
    targets = [kad_node_plain.key_words(keys[p]) for p in origins]
    assert [kad_plain.key_of(t) for t in targets] == [keys[p]
                                                      for p in origins]
    stage = [0] * n
    for cap in (None, 8):
        want, want_after = kad_plain.wave(
            tables, keys, origins, targets, stage, STAGE_LATENCY,
            learn_cap=cap)
        got, after = kad_node_plain.wave(
            tables, keys, origins, targets, stage, STAGE_LATENCY,
            learn_cap=cap)
        assert after == want_after
        assert [{k: v for k, v in f.items() if k != "asked"}
                for f in got] == want
        assert [len(f["asked"]) for f in got] == [f["n_queries"]
                                                 for f in got]
        assert kad_node_plain.tables_from_array(
            kad_plain.tables_to_array(after)) == after
        tables = after


def _cell(peers: int, bootstraps: int = 3, probes: int = 10,
          learn_cap="all") -> manifest.Cell:
    """The benchmark's kad-10k configuration at a test's size."""
    with open(os.path.join(CHECKOUT, "benchmark", "configs",
                           "kad-10k.json")) as f:
        config = copy.deepcopy(json.load(f))
    config["kad"]["env"].update(PEERS=peers, KAD_BOOTSTRAPS=bootstraps,
                                KAD_PROBES=probes, KAD_LEARN_CAP=learn_cap)
    config["kad"]["learn_cap"] = None if learn_cap == "all" else learn_cap
    config["guarantees"]["closest1_share_min"] = 0.5
    return manifest.Cell(
        name="kad-test.headline", chips=1, config_name="kad-test",
        config=config, traffic_name="headline", traffic={},
        entry_name="kad", entry=entry, end_to_end=[], per_layer=[])


def _run_cli(cell, seed: int, out_dir: str):
    """`cli.main(["kad", ...])` as the benchmark's entry invokes it, every
    find_node call captured with its tables; returns (rc, stats, log bytes,
    the captured calls)."""
    os.makedirs(out_dir, exist_ok=True)
    argv, env = entry.invocation(cell, seed, out_dir)
    with entry.capture_waves(lambda i: True) as calls, environment(env):
        rc = cli.main(argv)
    with open(os.path.join(out_dir, "stats1.json")) as f:
        stats = json.load(f)
    with open(os.path.join(out_dir, "kadlog1"), "rb") as f:
        log = f.read()
    return rc, stats, log, calls


@pytest.mark.parametrize("peers,seed", [
    (64, 3), (64, 2147483999), (64, 4294967299),
    (200, 5), (200, 2147484401), (200, 2147487034)])
def test_cli_kad_is_the_plain_node_wave_by_wave_and_in_every_summary_number(
        peers, seed, tmp_path, capsys):
    cell = _cell(peers)
    rc, stats, log, calls = _run_cli(cell, seed, str(tmp_path / "a"))
    assert rc == 0
    assert len(calls) == 20 + 12
    # the plain node from the seed alone; only the random targets are the
    # program's (a deployment draws them, the program from its seed)
    plain = kad_node_plain.node(
        peers, 3, 10, seed, [c["targets"] for c in calls[5:]],
        [0] * peers, STAGE_LATENCY, learn_cap=None)
    assert (kad_plain.tables_to_array(plain["seeded"])
            == calls[0]["start_rtable"]).all()
    for n, (call, item) in enumerate(zip(calls,
                                         plain["waves"] + plain["ticks"])):
        lookups = item["lookups"]
        assert call["origins"].tolist() == item["origins"], n
        if item["kind"] == "self":
            assert ([kad_plain.key_of(t) for t in call["targets"]]
                    == [plain["keys"][p] for p in item["origins"]]), n
        assert (entry._padded([f["closest"] for f in lookups], kad_plain.K_RESP)
                == call["closest"]).all(), n
        assert [f["hops"] for f in lookups] == call["hops"].tolist(), n
        assert ([f["n_queries"] for f in lookups]
                == call["n_queries"].tolist()), n
        np.testing.assert_allclose([f["latency_ms"] for f in lookups],
                                   call["latency_ms"], atol=1e-3, rtol=0)
        assert (kad_plain.tables_to_array(item["tables"])
                == call["end_rtable"]).all(), n
        # what the wave offered to the tables: what it appended is what it
        # offered less what found a full bucket
        offered, full = call["learn_counts"].tolist()
        appended = int((call["end_rtable"] >= 0).sum()
                       - (call["start_rtable"] >= 0).sum())
        assert 0 <= full <= offered and offered - full == appended, n
    assert max(f["hops"] for f in plain["waves"][-1]["lookups"]) > 0

    # every summary number: --stats-json "kad", the report, the log
    want, got = plain["summary"], stats["kad"]
    for name in ("lookups", "warmup_waves", "probe_ticks", "probe_lookups",
                 "probe_success", "census_min", "queries_tx", "queries_rx"):
        assert got[name] == want[name], name
    for name in ("census_mean", "hops_mean", "queries_per_lookup",
                 "queries_per_bootstrap", "closest1_share"):
        assert got[name] == pytest.approx(want[name], abs=1e-12), name
    assert got["probe_success_share"] == 1.0
    offered, full = np.sum([c["learn_counts"] for c in calls], axis=0)
    assert got["bucket_full_share"] == pytest.approx(full / offered)
    assert 0.0 < got["bucket_full_share"] < 1.0
    assert [w["kind"] for w in got["lookup_latency_ms"]] == (
        ["self"] * 5 + ["random"] * 15 + ["probe"] * 12)
    for g, w in zip(got["lookup_latency_ms"], want["lookup_latency_ms"]):
        assert g["p50"] == pytest.approx(w["p50"], abs=1e-3)
        assert g["p99"] == pytest.approx(w["p99"], abs=1e-3)
    said = capsys.readouterr().out
    for line in (
            "Kad-DHT summary",
            f"Routing table census: mean {want['census_mean']:.1f} "
            f"(min {want['census_min']}, max {want['census_max']})",
            f"Warmup lookups: {want['warmup_lookups']}",
            "Probe lookups: 120 (120 ok, 0 timed out)",
            "Probe success share: 100.0%",
            f"Lookup latency ms: p50 {want['lookup_latency_ms_p50']:.0f} "
            f"p99 {want['lookup_latency_ms_p99']:.0f}",
            f"Lookup hops: mean {want['hops_mean']:.2f}",
            f"Closest peer returned first: "
            f"{want['closest1_share'] * 100.0:.1f}%",
            f"FIND_NODE served per bootstrap: "
            f"{want['queries_per_bootstrap']:.0f}",
            "[tpu backend] wall="):
        assert line in said, line
    lines = log.decode().splitlines()
    census_lines = [line for line in lines
                    if line.startswith("Kad routing table peers=")]
    assert census_lines == [
        f"Kad routing table peers={np.mean(w['census']):.1f} buckets=24"
        for w in plain["waves"][:5]]
    probe_lines = [line for line in lines if line.startswith("Probe")]
    assert probe_lines == [
        "Probe: Finding node target="
        + "".join(f"{int(w):08x}" for w in target[:2])
        for tick in plain["ticks"] for target in tick["targets"]]


def test_cli_kad_turn_spans_counters_stats_keys_and_same_seed_same_log(
        tmp_path, monkeypatch):
    """The turn: the spans `kad --help` names, one `kadnode/counters`
    annotation an experiment, the `--stats-json` keys; part 1 of the
    benchmark's entry holds; the same seed writes the same log bytes."""
    import jax

    noted, reads = [], []
    monkeypatch.setattr(kad_runtime, "counters",
                        lambda name, **values: noted.append((name, values)))
    device_get = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda tree: (
        reads.append(len(jax.tree_util.tree_leaves(tree))), device_get(tree)
    )[1])
    cell = _cell(64)
    rc, stats, log, _ = _run_cli(cell, 7, str(tmp_path / "a"))
    assert rc == 0
    # two reads an experiment, each of everything its phase left: the
    # twenty waves' five leaves (hops, queries, latency, learn counts,
    # packed), five censuses and the hits; the twelve ticks' five leaves
    # and targets, the hits and the final state's four
    assert reads == [20 * 5 + 5 + 1, 12 * 6 + 1 + 4]
    assert {"network_size", "wall_s", "spans", "compile", "kad"} <= set(stats)
    assert stats["network_size"] == 64
    spans = stats["spans"]
    for name, count in (("run", 1), ("run/topology", 1), ("run/boot", 1),
                        ("run/warmup", 1), ("warmup/wave", 20),
                        ("run/probe", 1), ("probe/tick", 12),
                        ("run/record", 2), ("run/summary", 1),
                        ("run/write_log", 1), ("run/report", 1),
                        ("run/stats_json", 1)):
        assert spans[name]["count"] == count, name
    assert stats["wall_s"] == pytest.approx(sum(
        spans[name]["total_s"] for name in (
            "run/topology", "run/boot", "run/warmup", "run/probe",
            "run/record")))
    assert [name for name, _ in noted] == ["kadnode/counters"]
    counted = noted[0][1]
    assert set(counted) == {
        "lookups", "warmup_waves", "probe_ticks", "hops_mean",
        "queries_per_lookup", "census_mean", "census_min",
        "probe_success_share", "closest1_share", "bucket_full_share",
        "packed_share"}
    # 64 peers hold at most 63 of the 128 columns a response sorts
    assert counted["packed_share"] == 1.0
    with open(os.path.join(CHECKOUT, "benchmark", "layer_metrics",
                           "kadnode.packed_share.json")) as f:
        assert json.load(f)["params"] == {
            "annotation": "kadnode/counters", "counter": "packed_share"}
    assert {k: stats["kad"][k] for k in counted} == counted
    assert set(stats["kad"]) == set(counted) | {
        "queries_tx", "queries_rx", "queries_per_bootstrap",
        "lookup_latency_ms", "probe_lookups", "probe_success"}
    checked = entry.invariants(cell, str(tmp_path / "a"))
    assert checked["faults"] == []
    assert checked["digest"] == hashlib.sha256(log).hexdigest()
    rc, again, log2, _ = _run_cli(cell, 7, str(tmp_path / "b"))
    assert log2 == log and again["kad"] == stats["kad"]
    # a turn after the first says nothing of the process
    assert "process" not in again
    _, other, log3, _ = _run_cli(cell, 8, str(tmp_path / "c"))
    assert log3 != log


def test_kad_help_says_how_to_read_stats_json(capsys):
    with pytest.raises(SystemExit):
        cli.main(["kad", "--help"])
    said = " ".join(capsys.readouterr().out.split())
    for word in ("--stats-json", "closest1_share", "bucket_full_share",
                 "packed_share", "probe_success_share", "census_min",
                 "queries_per_bootstrap", "lookup_latency_ms",
                 "KAD_LEARN_CAP"):
        assert word in said, word


def test_learn_cap_is_wired_from_the_environment_to_the_tables(monkeypatch):
    monkeypatch.setenv("PEERS", "64")
    monkeypatch.setenv("SEED", "3")
    tables = {}
    for cap in ("all", "8"):
        monkeypatch.setenv("KAD_LEARN_CAP", cap)
        cfg = kad_runtime.config_from_env()
        assert cfg.learn_cap == (None if cap == "all" else 8)
        sim = KadSimulator(cfg)
        sim.boot()
        sim.warmup()
        tables[cap] = np.asarray(sim.state.rtable)
    assert (tables["all"] != tables["8"]).any()
    assert (tables["all"] >= 0).sum() > (tables["8"] >= 0).sum()
    monkeypatch.delenv("KAD_LEARN_CAP")
    assert kad_runtime.config_from_env().learn_cap == kad.LEARN_CAP
    with pytest.raises(ValueError):
        KadConfig(learn_cap=0).validate()


# What `KadSimulator(cfg).run()` gave at the parent commit (PR 44), where a
# lookup was a `LookupRecord`: the summary, and sums over the records.
PARENT_RECORDS = [
    ({"network_size": 64, "n_bootstrap": 2, "n_probe": 6,
      "probe_duration_s": 15.0, "seed": 0, "discovery": "kad-dht"},
     {"census_mean": 41.0, "census_min": 26, "census_max": 48,
      "warmup_lookups": 1120, "probe_lookups": 18, "probe_success": 18,
      "lookup_latency_ms_p50": 1212.0, "lookup_latency_ms_p99": 1212.0,
      "hops_mean": 1.318980667838313, "queries_per_bootstrap": 416.5},
     {"lookups": 1138, "hops": 1501, "queries": 19249, "timed_out": 0,
      "latency_ms": 1300072.0,
      "census_lines": ["17.7", "20.4", "20.4", "20.4", "20.4"],
      "probe_lines_sha256": "3657cc297091486d635b0392dfbdbb0e2b6d196b2f0a0922"
      "2584a4299aa78dd2",
      "rtable_sha256": "051da7ad9dd122f1b403f02c2f80a14e5056fcacd7dbe918736e"
      "ab261423c055"}),
    ({"network_size": 64, "n_bootstrap": 3, "n_probe": 10,
      "probe_duration_s": 15.0, "seed": 2147483999, "discovery": "kad-dht"},
     {"census_mean": 44.140625, "census_min": 33, "census_max": 53,
      "warmup_lookups": 1020, "probe_lookups": 30, "probe_success": 30,
      "lookup_latency_ms_p50": 1212.0, "lookup_latency_ms_p99": 1212.0,
      "hops_mean": 1.4266666666666667,
      "queries_per_bootstrap": 335.3333333333333},
     {"lookups": 1050, "hops": 1498, "queries": 17659, "timed_out": 0,
      "latency_ms": 1189174.0,
      "census_lines": ["19.4", "22.0", "22.0", "22.0", "22.0"],
      "probe_lines_sha256": "4065a8e270a90551bf99b2a3d6464df19c49fb1b6842d39e"
      "3e7a3390eb30cd4a",
      "rtable_sha256": "ab4c82062a84b8b8da3472cf751a438a7ebe4aff2f57249dc284"
      "a29e7e85b18b"}),
    # DISCOVERY=extended: dial-backs and evictions after every wave
    ({"network_size": 96, "n_bootstrap": 2, "n_probe": 20,
      "probe_duration_s": 15.0, "seed": 3, "discovery": "extended"},
     {"census_mean": 52.833333333333336, "census_min": 17, "census_max": 62,
      "warmup_lookups": 1480, "probe_lookups": 60, "probe_success": 60,
      "lookup_latency_ms_p50": 1212.0, "lookup_latency_ms_p99": 1212.0,
      "hops_mean": 1.5188311688311689, "queries_per_bootstrap": 408.0},
     {"lookups": 1540, "hops": 2339, "queries": 26155, "timed_out": 0,
      "latency_ms": 1766692.0,
      "census_lines": ["23.0", "24.4", "24.4", "24.4", "24.4"],
      "probe_lines_sha256": "45dc579bb78db952b50b445374fdac6f2152e2e1b286269d"
      "ed7ccf52c1619467",
      "rtable_sha256": "206b2cdcb446bfff45db0730fa4ed998837310e0b45c4cdf9c03"
      "027bd146b6e6"}),
]


@pytest.mark.parametrize("config,summary,records",
                         PARENT_RECORDS,
                         ids=["64-kad-dht", "64-three-bootstraps",
                              "96-extended"])
def test_array_records_give_what_the_per_lookup_records_gave(
        config, summary, records):
    sim = KadSimulator(KadConfig(**config))
    got = sim.run()
    for name, value in summary.items():
        assert getattr(got, name) == value, name
    phases = [sim.warm, sim.probed]
    assert sum(p.count for p in phases) == records["lookups"]
    assert sum(int(p.hops.sum()) for p in phases) == records["hops"]
    assert sum(int(p.n_queries.sum()) for p in phases) == records["queries"]
    assert sum(int(p.timed_out.sum()) for p in phases) == records["timed_out"]
    assert sum(float(p.latency_ms.sum(dtype=np.float64))
               for p in phases) == records["latency_ms"]
    assert sim.warm.kinds == ["self"] * 5 + ["random"] * 15
    assert (sim.warm.origins == np.arange(
        config["n_bootstrap"],
        config["network_size"] - config["n_probe"])).all()
    assert [line.split("=")[1].split()[0] for line in sim.lines
            if line.startswith("Kad routing table")] == records[
                "census_lines"]
    probe_lines = "\n".join(line for line in sim.lines
                            if line.startswith("Probe"))
    assert (hashlib.sha256(probe_lines.encode()).hexdigest()
            == records["probe_lines_sha256"])
    assert (hashlib.sha256(np.asarray(sim.state.rtable).tobytes())
            .hexdigest() == records["rtable_sha256"])


# `regression --messages 3 --msg-size 1000` at 64 peers at the parent commit
# (PR 44), before `discover`'s wave loop became kad_runtime.dispatch_waves
PARENT_REGRESSION = [
    (3, "9de22059a7839b062af9b3201a6bb34bcc50ef593bf3d50b1c90dcc6fab7dda8",
     "307717c79dddb074f45b7e23f21963bdb450fe5730a4b01535c0d767ec6ca67d"),
    (2147483999,
     "dfd7790c2c1e669e8d426bdced6bdf9506ea83d1058c9a0f8c420f61ec76d463",
     "8c7b5b2244c11119dfa52859d29cf1a9d1d647513bb62dc4218c76e86ca5fc7b"),
]


@pytest.mark.parametrize("seed,latencies_sha256,log_sha256",
                         PARENT_REGRESSION)
def test_regression_writes_the_bytes_it_wrote_before_the_loop_was_shared(
        seed, latencies_sha256, log_sha256, tmp_path, monkeypatch):
    monkeypatch.setenv("PEERS", "64")
    monkeypatch.setenv("SEED", str(seed))
    lat, log = tmp_path / "lat", tmp_path / "log"
    stats = tmp_path / "stats.json"
    assert cli.main(["regression", "--messages", "3", "--msg-size", "1000",
                     "--latencies", str(lat), "--log", str(log),
                     "--stats-json", str(stats)]) == 0
    assert hashlib.sha256(lat.read_bytes()).hexdigest() == latencies_sha256
    assert hashlib.sha256(log.read_bytes()).hexdigest() == log_sha256
    said = json.loads(stats.read_text())
    assert said["spans"]["discover/wave"]["count"] == 3
    assert said["kad"]["waves"] == 3


@pytest.mark.parametrize("peers,seed", [(64, 11), (200, 2147486012)])
def test_kad_entry_parts_against_the_plain_node_and_the_control(
        peers, seed, tmp_path):
    """The benchmark's entry as run.py and control.py drive it: part 1,
    the drawn items against the plain node (0 differing), the control
    differing in every item."""
    cell = _cell(peers)
    outcome, items = entry.captured(cell, seed, str(tmp_path / "out"))
    assert outcome.ok, outcome.faults
    drawn = entry.drawn(cell, seed)
    assert drawn[0] == 1 and len(drawn) == 4 == len(set(drawn))
    assert [i["message"] for i in items] == (
        [100 + w for w in drawn] + [200 + t for t in range(1, 13)] + [300])
    for item in items:
        record = entry.against_reference(cell, item)
        assert record["passed"], record
        limited = {k: v for k, v in record.items() if k.startswith("limit_")}
        assert limited and all(k[len("limit_"):] in record for k in limited)
        control = entry.against_reference(cell, item, control=True)
        assert not control["passed"], control
    sound = entry.summarised(
        [entry.against_reference(cell, i) for i in items])
    assert sound == {"sound_differing_max_wave": 0,
                     "sound_differing_max_tick": 0,
                     "sound_differing_max_summary": 0}
    assert entry.digest_line(outcome)["log_sha256"] == outcome.digest
    every = entry.captured(cell, seed, str(tmp_path / "every"),
                           every=True)[1]
    assert [i["message"] for i in every if i["message"] < 200] == list(
        range(101, 121))
    assert [i["drawn"] for i in every if i["message"] < 200] == [
        w in drawn for w in range(1, 21)]
