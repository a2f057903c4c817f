import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

from dst_libp2p_test_node_tpu.config.topology import TopoParams
from dst_libp2p_test_node_tpu.runtime.simulator import ExperimentConfig, Simulator

BASE = TopoParams(
    network_size=100, min_bandwidth=50, max_bandwidth=150,
    min_latency=40, max_latency=130, anchor_stages=5,
    msg_size_bytes=15000, messages=3, delay_seconds=4.0,
)


def small_cfg(**over):
    kw = dict(topo=BASE, warmup_s=30.0, seed=0)
    kw.update(over)
    return ExperimentConfig(**kw)


def test_muxer_constants_derive_from_stack_crossings():
    # the per-hop costs are EVENT_LOOP_MS x layer-crossing counts of each
    # composed stack (main.nim:433-441), not free-floating numbers: QUIC
    # (3 layers, muxer+crypto native) < TCP+Noise+yamux (4) < TCP+Noise+
    # mplex (4 + double-read framing)
    from dst_libp2p_test_node_tpu.runtime.simulator import (
        EVENT_LOOP_MS, MUXER_PROC_MS, _MUXER_CROSSINGS,
    )

    assert MUXER_PROC_MS["quic"] < MUXER_PROC_MS["yamux"] < MUXER_PROC_MS["mplex"]
    for m, v in MUXER_PROC_MS.items():
        assert v == EVENT_LOOP_MS * _MUXER_CROSSINGS[m]
    assert _MUXER_CROSSINGS["quic"] == 3.0      # UDP -> QUIC -> pubsub
    assert _MUXER_CROSSINGS["yamux"] == 4.0     # TCP -> Noise -> yamux -> pubsub


def test_event_loop_anchor_matches_committed_measurement():
    # EVENT_LOOP_MS is MEASURED (scripts/calibrate_event_loop.py: asyncio
    # scheduler crossing under CONNECTTO=10 sha256(15KB)-per-wake stream
    # handler load), and the committed measurement artifact is its basis —
    # this pins the constant to the measurement, not to an assertion
    import json

    from dst_libp2p_test_node_tpu.runtime.simulator import EVENT_LOOP_MS

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "docs", "event_loop_calibration.json")) as f:
        cal = json.load(f)
    assert cal["payload_bytes"] == 15000 and cal["n_conns"] == 10
    assert EVENT_LOOP_MS == pytest.approx(cal["event_loop_ms_median"], rel=0.01)
    # and the measurement itself is stable enough to anchor on: the repeat
    # spread stays within a factor ~2 band around the median
    assert cal["event_loop_ms_max"] <= 2.0 * cal["event_loop_ms_median"]
    assert cal["event_loop_ms_min"] >= 0.5 * cal["event_loop_ms_median"]


def test_full_experiment_coverage_and_summary():
    sim = Simulator(small_cfg())
    recs = sim.run()
    assert len(recs) == 3
    for r in recs:
        assert r.received.sum() == 100
        assert r.delays_ms[r.publisher] == 0.0
    s = sim.summary()
    assert s.total_messages == 3
    assert s.coverage() == 100.0
    assert s.network_size == 99
    assert 40 <= s.avg_max_latency_ms <= 2000


def test_publisher_rotation():
    sim = Simulator(small_cfg(publisher_rotation=True, publisher_id=4))
    recs = sim.run()
    assert [r.publisher for r in recs] == [4, 5, 6]


def test_self_trigger_off_excludes_publisher():
    sim = Simulator(small_cfg(self_trigger=False))
    recs = sim.run()
    for r in recs:
        assert not r.received[r.publisher]
        assert r.received.sum() == 99


def test_time_advances_with_schedule():
    sim = Simulator(small_cfg())
    sim.run()
    # 30 s warmup + 2 * 4 s gaps = 38 s of heartbeats
    assert float(sim.state.t_ms) == pytest.approx(38_000.0, abs=1001)


def test_msg_ids_unique_and_deterministic():
    a = Simulator(small_cfg())
    b = Simulator(small_cfg())
    ids_a = [r.msg_id for r in a.run()]
    ids_b = [r.msg_id for r in b.run()]
    assert ids_a == ids_b
    assert len(set(ids_a)) == 3


def test_latencies_file_roundtrip(tmp_path):
    sim = Simulator(small_cfg())
    sim.run()
    path = str(tmp_path / "latencies1")
    n = sim.write_latencies(path)
    assert n == 300
    from dst_libp2p_test_node_tpu.runtime.summarize import summarize_file

    s = summarize_file(path, large=True)
    assert s.coverage() == 100.0


def test_cli_run_end_to_end(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="/root/repo")
    out = subprocess.run(
        [sys.executable, "-m", "dst_libp2p_test_node_tpu", "run",
         "1", "60", "500", "1", "2", "50", "50", "40", "40", "1", "0.0",
         "4", "0", "1000", "--warmup-s", "20", "--stats-json"],
        capture_output=True, text=True, cwd=str(tmp_path), env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "Running for turn 1" in out.stdout
    assert "Total Nodes :  59" in out.stdout
    # msg_size < 1000 -> small-message summary (7 spread buckets)
    assert (tmp_path / "latencies1").exists()
    assert (tmp_path / "stats1.json").exists()
    assert (tmp_path / "shadow.yaml").exists()
    assert (tmp_path / "network_topology.gml").exists()


def test_cli_run_emits_what_the_reference_loops_and_the_text_path_give(
        tmp_path, capsys, monkeypatch):
    """One `run` in this process: the emit layer formats from arrays and
    summarises from the records, and every artifact is still what the loops
    a peer and a receipt, and the summary parsed back from `latencies1`,
    give on the same run."""
    import json

    import shadow_yaml_reference
    import shadowlog_reference
    from dst_libp2p_test_node_tpu import cli
    from dst_libp2p_test_node_tpu.runtime.bandwidth import (
        report as bandwidth_report, summarize_bandwidth)
    from dst_libp2p_test_node_tpu.runtime.logemit import grep_lines
    from dst_libp2p_test_node_tpu.runtime.summarize import (
        report, summarize_file)

    sims = []
    real = Simulator.write_shadowlog

    def remember(self, path):
        sims.append(self)
        return real(self, path)

    monkeypatch.setattr(Simulator, "write_shadowlog", remember)
    prefix = str(tmp_path) + os.sep
    assert cli.main([
        "run", "1", "60", "15000", "1", "3", "50", "150", "40", "130", "5",
        "0.0", "4", "0", "1000", "--warmup-s", "20", "--seed", "5",
        "--stats-json", "--out-prefix", prefix]) == 0
    printed = capsys.readouterr().out
    (sim,) = sims

    # latencies1: a line a receipt, numbered by a count a peer
    seen: dict[int, int] = {}
    want = []
    for rec in sim.records:
        linenos = []
        for p in rec.receivers.tolist():
            seen[p] = seen.get(p, 0) + 1
            linenos.append(seen[p])
        want += grep_lines(rec.receivers, rec.msg_id, rec.delays_ms_int,
                           np.array(linenos))
    assert (tmp_path / "latencies1").read_text() == "".join(
        ln + "\n" for ln in want)
    assert (tmp_path / "shadowlog1").read_text() == (
        shadowlog_reference.shadowlog_text(sim.traffic()))

    s = summarize_file(prefix + "latencies1", large=True)
    assert s.total_messages == 3 and s.coverage() == 60.0
    assert sim.summary(True) == s
    assert ("Summary for turn 1\n" + report(s, large=True)
            + bandwidth_report(summarize_bandwidth(sim.traffic()))
            + "[tpu backend] wall=") in printed
    with open(prefix + "stats1.json") as f:
        stats = json.load(f)
    assert {k: stats[k] for k in (
        "network_size", "coverage", "max_latency_ms", "avg_latency_ms",
        "avg_max_latency_ms")} == {
        "network_size": s.network_size, "coverage": s.coverage(),
        "max_latency_ms": s.max_latency_ms,
        "avg_latency_ms": s.avg_latency_ms,
        "avg_max_latency_ms": s.avg_max_latency_ms}
    # shadow.yaml: the whole-document dump's bytes, 50 of its hosts joined
    # as alias lines
    shadow_yaml_reference.write_shadow_yaml(sim.topology, prefix + "ref.yaml")
    assert (tmp_path / "shadow.yaml").read_bytes() == (
        tmp_path / "ref.yaml").read_bytes()
    assert stats["artifacts"] == {
        "yaml_hosts_dumped": 11, "yaml_alias_lines": 50}
    # the graph is the one the commit before the build lost its sorts made
    # (`graph_sha256` read there), and the build says which paths it took
    from dst_libp2p_test_node_tpu.runtime.checkpoint import _graph_hash

    assert _graph_hash(sim.graph) == (
        "2eb570a1b800336dec4b2a46cb59c38b4649c61fe4cb67cf9626df7e7ece01b2")
    assert stats["build"] == {
        "dial_rows_resampled": 0, "mutual_dials_dropped": 55,
        "dedupe": "mutual", "cap_filtered_edges": 0}
    # 180 and 60 lines are under NATIVE_MIN_LINES: the Python formatters
    assert stats["emit"] == {
        "latencies_lines": 180, "latencies_native_blocks": 0,
        "shadowlog_lines": 60, "shadowlog_native_blocks": 0}


def test_cli_run_lossy_loss_modes(tmp_path):
    # the run driver exposes the two loss models; at topogen -l 0.5 the
    # tcp default must keep full coverage (retransmission, not drops) and
    # the two modes must be OBSERVABLY different through the CLI — the
    # message mode's only recovery is next-heartbeat gossip, slower than
    # a TCP RTO, so its worst receiver is later
    def run_mode(args, prefix):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="/root/repo")
        out = subprocess.run(
            [sys.executable, "-m", "dst_libp2p_test_node_tpu", "run",
             "1", "80", "500", "1", "1", "50", "50", "30", "60", "2", "0.5",
             "4", "0", "1000", "--warmup-s", "10", "--connect-to", "6",
             "--out-prefix", prefix] + args,
            capture_output=True, text=True, cwd=str(tmp_path), env=env,
            timeout=600,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        lines = (tmp_path / f"{prefix}latencies1").read_text().splitlines()
        delays = [int(ln.rsplit(":", 1)[1]) for ln in lines
                  if "milliseconds" in ln]
        return delays

    tcp = run_mode([], "tcp-")
    msg = run_mode(["--loss-mode", "message"], "msg-")
    # tcp mode delivered to the whole network despite 50% edge loss
    assert len(tcp) >= 79
    # the flag is live: message mode's recovery tail is strictly later
    # (same seed, common random numbers across the modes)
    assert max(msg) > max(tcp), (max(msg), max(tcp))
    # --delivery-mode bounded is live through the CLI: same run, arrival
    # times never LATER than exact (dropping answer-queue waits can only
    # advance arrivals), same coverage
    bnd = run_mode(["--delivery-mode", "bounded"], "bnd-")
    assert len(bnd) == len(tcp)
    assert max(bnd) <= max(tcp)


def test_cli_topogen_positional_and_flag_forms(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="/root/repo")
    # the exact positional vector run.sh:49-50 passes
    out = subprocess.run(
        [sys.executable, "-m", "dst_libp2p_test_node_tpu", "topogen",
         "100", "50", "150", "40", "130", "5", "0.0", "15000", "1", "10",
         "4", "0", "4000"],
        capture_output=True, text=True, cwd=str(tmp_path), env=env, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert (tmp_path / "network_topology.gml").exists()
    out2 = subprocess.run(
        [sys.executable, "-m", "dst_libp2p_test_node_tpu", "topogen",
         "-n", "100", "-st", "5", "-bl", "50", "-bh", "150"],
        capture_output=True, text=True, cwd=str(tmp_path), env=env, timeout=120,
    )
    assert out2.returncode == 0, out2.stderr[-2000:]


def test_churn_configured_run():
    cfg = small_cfg(churn_down_per_hb=0.002, churn_up_per_hb=0.001)
    sim = Simulator(cfg)
    recs = sim.run()
    alive = np.asarray(sim.state.alive)
    for r in recs:
        # dead peers never log receipt
        assert r.received.sum() <= 100
    assert alive.sum() < 100  # some churn actually happened over 30+ hb


def _lossy_publish(loss, loss_mode, seed=3):
    topo = TopoParams(network_size=80, anchor_stages=2, min_bandwidth=50,
                      max_bandwidth=100, min_latency=30, max_latency=60,
                      msg_size_bytes=500, packet_loss=loss, messages=1)
    cfg = ExperimentConfig(topo=topo, connect_to=6, warmup_s=5.0, seed=seed,
                           loss_mode=loss_mode)
    sim = Simulator(cfg)
    sim.warmup()
    return sim.publish(4)


def test_packet_loss_degrades_coverage_in_message_mode():
    """topogen's -l packet loss in loss_mode="message" (QUIC-unreliable
    style): heavy loss must strictly reduce delivered copies vs the same
    seeded lossless run, and moderate loss leaves coverage graceful (mesh
    redundancy)."""
    clean = _lossy_publish(0.0, "message")
    heavy = _lossy_publish(0.9, "message")
    assert clean.received.mean() == 1.0
    assert heavy.received.sum() < clean.received.sum()
    mild = _lossy_publish(0.05, "message")
    assert mild.received.mean() > 0.9  # redundancy keeps coverage graceful


def test_packet_loss_becomes_latency_in_tcp_mode():
    """loss_mode="tcp" (the default, Shadow-faithful): under Shadow the
    nodes run real TCP stacks, so per-packet loss is retransmitted after an
    RTO — coverage stays ~1.0 and the latency tail inflates instead
    (VERDICT r3 ask #3). Compare the same seeded run across the modes."""
    clean = _lossy_publish(0.0, "tcp")
    tcp = _lossy_publish(0.5, "tcp")
    msg = _lossy_publish(0.5, "message")

    # tcp mode never loses coverage at any loss rate short of abandonment
    assert tcp.received.mean() == 1.0
    # ... it pays in latency instead: the tail inflates by RTO-scale stalls
    p99_tcp = np.percentile(tcp.delays_ms[tcp.received], 99)
    p99_clean = np.percentile(clean.delays_ms[clean.received], 99)
    max_tcp = tcp.delays_ms[tcp.received].max()
    max_clean = clean.delays_ms[clean.received].max()
    assert p99_tcp > p99_clean + 50.0, (p99_tcp, p99_clean)
    assert max_tcp > max_clean + 150.0, (max_tcp, max_clean)
    # the modes are distinguishable in the physically-right direction: a
    # TCP retransmit (>= 200 ms RTO) recovers FASTER than message mode's
    # only fallback — waiting for next-heartbeat IHAVE/IWANT gossip — so
    # at a rate where both lean on recovery, tcp's tail is the shorter one
    # (message mode's coverage cliff at 0.9 is covered above)
    p99_msg = np.percentile(msg.delays_ms[msg.received], 99)
    assert p99_tcp < p99_msg, (p99_tcp, p99_msg)
    # median stays in the same regime: most copies still arrive first try
    p50_tcp = np.percentile(tcp.delays_ms[tcp.received], 50)
    p50_clean = np.percentile(clean.delays_ms[clean.received], 50)
    assert p50_tcp < p50_clean + 250.0


def test_taking_a_publishs_plan_compiles_nothing_more(monkeypatch):
    """A Simulator dispatches ONE publish program, the one that also returns
    the sampled plan (runtime/simulator.disseminate drops it unless asked):
    a caller that takes one publish's plan, as the benchmark's reference
    check does by wrapping the module's `disseminate`, reuses the executable
    the other publishes ran, and gets their results."""
    from dst_libp2p_test_node_tpu.runtime import simulator as simmod
    from dst_libp2p_test_node_tpu.runtime.profiling import count_retraces

    def delays(cfg):
        sim = Simulator(cfg)
        sim.run()
        return [r.delays_ms.copy() for r in sim.records]

    # a shape of its own: no other test's executable is in the jit cache
    cfg = small_cfg(topo=dataclasses.replace(BASE, network_size=97))
    plain = delays(cfg)
    original, plans = simmod.disseminate, []

    def with_plan(*args, **kw):
        res, state, plan = original(*args, **kw, return_plan=True)
        plans.append(plan)
        return res, state

    monkeypatch.setattr(simmod, "disseminate", with_plan)
    with count_retraces() as retraces:
        captured = delays(cfg)
    assert len(plans) == BASE.messages and "rprio" in plans[0]
    assert not [e for e in retraces.events if "disseminate" in e], \
        retraces.events
    for got, want in zip(captured, plain):
        np.testing.assert_array_equal(got, want)


# ------------------------------------ the two bands of a publish's row pulls

def _spy_on_disseminate(monkeypatch):
    """Record the `pull_bands` every publish of a Simulator passes."""
    from dst_libp2p_test_node_tpu.runtime import simulator as simmod

    original, seen = simmod.disseminate, []

    def spy(*args, **kw):
        seen.append(kw.get("pull_bands"))
        return original(*args, **kw)

    monkeypatch.setattr(simmod, "disseminate", spy)
    return seen


def test_a_small_simulator_publishes_through_the_whole_index(monkeypatch):
    """Under the size test (a pull is microseconds) a Simulator makes no
    bands, passes None, states 100 % on its records, and None IS the
    program without the argument: the lowered text is the same."""
    from dst_libp2p_test_node_tpu.ops.disseminate import disseminate

    seen = _spy_on_disseminate(monkeypatch)
    sim = Simulator(small_cfg())
    assert sim._pull_bands is None
    sim.warmup()
    rec = sim.publish(4)
    assert seen == [None] and rec.pull_rows_share == 100.0
    a = sim.arrays
    args = (sim.state, a["conns"], a["rev"], sim._stage, sim._lat, sim._bw)
    kw = dict(publisher=4, t0_ms=1.0, params=sim.params, payload_bytes=15000,
              lat_edge=sim._lat_edge, ans_tables=sim._ans_tables)
    assert (disseminate.lower(*args, **kw).as_text()
            == disseminate.lower(*args, **kw, pull_bands=None).as_text())


@pytest.mark.parametrize("with_gossip", [True, False])
def test_a_simulator_hoists_the_bands_and_rebind_graph_makes_them_again(
        monkeypatch, with_gossip):
    """Where the maker admits them (forced here at 2,000 peers) a Simulator
    hoists the bands in `build/tables`, every publish passes them, and its
    records are those of the whole-width publishes; `rebind_graph` derives
    them from the graph it adopts, so a stale table cannot survive it."""
    import functools

    from dst_libp2p_test_node_tpu.ops.pull import make_pull_bands
    from dst_libp2p_test_node_tpu.runtime import simulator as simmod

    cfg = small_cfg(topo=dataclasses.replace(BASE, network_size=2000,
                                             messages=2),
                    seed=5, with_gossip=with_gossip)
    plain = Simulator(cfg)
    plain.run()
    assert plain._pull_bands is None
    monkeypatch.setattr(simmod, "make_pull_bands",
                        functools.partial(make_pull_bands, min_bytes=0))
    seen = _spy_on_disseminate(monkeypatch)
    sim = Simulator(cfg)
    bands = sim._pull_bands
    assert bands is not None
    assert set(bands.heads) == ({"conns", "rev", "conns_sorted", "rev_sorted"}
                                if with_gossip else {"conns", "rev"})
    sim.run()
    assert len(seen) == 2 and all(b is bands for b in seen)
    for got, want in zip(sim.records, plain.records):
        assert got.pull_rows_share == pytest.approx(100 * (24 * 2000 + 16 * 256)
                                                    / (40 * 2000))
        assert got.delays_ms.tobytes() == want.delays_ms.tobytes()
        assert got.sends.tobytes() == want.sends.tobytes()
        assert (got.fast_iters, got.refine_passes) == (
            want.fast_iters, want.refine_passes)
    # a graph that lost an edge: row p's slot i and its reverse are holes
    conns = np.asarray(sim.arrays["conns"]).copy()
    rev = np.asarray(sim.arrays["rev"]).copy()
    p = int(np.flatnonzero((conns[:, 24:] >= 0).any(axis=-1))[0])
    q, j = conns[p, 0], rev[p, 0]
    conns[p, 0] = rev[p, 0] = conns[q, j] = rev[q, j] = -1
    sim.rebind_graph(conns, rev, np.asarray(sim.arrays["out_mask"]))
    again = sim._pull_bands
    assert again is not bands
    np.testing.assert_array_equal(np.asarray(again.heads["conns"]),
                                  conns[:, :24])
    assert int(np.asarray(again.back)[p]) < again.tails["conns"].shape[0]
    np.testing.assert_array_equal(
        np.asarray(again.tails["rev"])[int(np.asarray(again.back)[p])],
        rev[p, 24:])
    sim.publish(4)
    assert seen[-1] is again
