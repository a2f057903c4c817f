"""Measurement-parity tests: latency lines, awk compatibility, summarizer."""

import io
import os
import shutil
import subprocess

import numpy as np
import pytest

from dst_libp2p_test_node_tpu.runtime.logemit import LatenciesWriter, stdout_line
from dst_libp2p_test_node_tpu.runtime.native_logemit import format_block
from dst_libp2p_test_node_tpu.runtime.summarize import (
    LatencySummary,
    parse_latencies,
    summarize,
    summarize_records,
)

REF_AWK_SMALL = "/root/reference/shadow/summary_latency.awk"
REF_AWK_LARGE = "/root/reference/shadow/summary_latency_large.awk"


def test_stdout_line_format():
    # main.nim:150: echo msgId, " milliseconds: ", delay
    assert stdout_line(12345, 250) == "12345 milliseconds: 250"


def test_grep_line_awk_split_contract():
    w = LatenciesWriter()
    w.add_message(777, np.array([3, 12]), np.array([100, 250]))
    buf = io.StringIO()
    w.write_to(buf)
    lines = buf.getvalue().splitlines()
    assert lines[0] == "shadow.data/hosts/peer3/main.1000.stdout:1:777 milliseconds: 100"
    # the awk split "peer|/main|:.*:" must yield arr[2]=peer, arr[4]=msgId
    import re

    parts = re.split(r"peer|/main|:.*:", lines[1].split(" ")[0])
    assert parts[1] == "12"
    assert parts[3] == "777"


def test_linenos_increment_per_peer():
    w = LatenciesWriter()
    w.add_message(1, np.array([5]), np.array([10]))
    w.add_message(2, np.array([5]), np.array([20]))
    buf = io.StringIO()
    w.write_to(buf)
    lines = buf.getvalue().splitlines()
    assert ":1:1 milliseconds: 10" in lines[0]
    assert ":2:2 milliseconds: 20" in lines[1]


def test_linenos_are_a_count_a_peer_whatever_the_ids():
    """The counter array indexed by peer id numbers lines as a dict bumped
    once a receipt did: ids that grow from call to call, receivers in no
    order, and an id given twice in one call."""
    rng = np.random.default_rng(5)
    calls = [(1, np.array([5, 2, 9])), (2, np.arange(6000)),
             (3, rng.permutation(20_000)[:7000]), (4, np.array([3, 3, 1, 3])),
             (5, np.array([], dtype=np.int64)), (6, np.array([19_999, 0]))]
    w = LatenciesWriter()
    seen: dict[int, int] = {}
    want = []
    for msg_id, peers in calls:
        w.add_message(msg_id, peers, np.full(peers.size, 40 + msg_id))
        for p in sorted(peers.tolist()):
            seen[p] = seen.get(p, 0) + 1
            want.append(f"shadow.data/hosts/peer{p}/main.1000.stdout:"
                        f"{seen[p]}:{msg_id} milliseconds: {40 + msg_id}\n")
    buf = io.StringIO()
    assert w.write_to(buf) == len(want)
    assert buf.getvalue() == "".join(want)


def test_parse_accepts_peer_and_pod_naming():
    rows, total = parse_latencies([
        "shadow.data/hosts/peer7/main.1000.stdout:3:99 milliseconds: 140",
        "shadow.data/hosts/pod-8/main.1000.stdout:1:99 milliseconds: 150",
        "garbage line",
        "shadow.data/hosts/peer1/main.1000.stdout:1:99 milliseconds: notanum",
    ])
    assert rows == [(7, 99, 140), (8, 99, 150)]
    assert total == 4  # awk's NR counts every line (its Average divides by NR)


def test_summarize_small():
    w = LatenciesWriter()
    w.add_message(42, np.array([1, 2, 3]), np.array([50, 150, 250]))
    w.add_message(43, np.array([1, 2]), np.array([100, 300]))
    buf = io.StringIO()
    w.write_to(buf)
    s = summarize(buf.getvalue().splitlines(), large=False)
    assert s.network_size == 3
    assert s.total_messages == 2
    assert s.max_latency_ms == 300
    assert s.avg_latency_ms == pytest.approx((50 + 150 + 250 + 100 + 300) / 5)
    m42 = next(m for m in s.messages if m.msg_id == 42)
    assert m42.received == 3
    assert m42.avg_latency_ms == pytest.approx(150.0)
    assert m42.spread == {0: 1, 1: 1, 2: 1}


def test_summarize_large_rounds_to_hop():
    lines = [
        f"shadow.data/hosts/peer{p}/main.1000.stdout:1:9 milliseconds: {d}"
        for p, d in [(1, 149), (2, 151), (3, 250)]
    ]
    s = summarize(lines, large=True)
    m = s.messages[0]
    # 149 -> 100, 151 -> 200, 250 -> 300 (nearest-100 rounding, awk:24)
    assert m.avg_latency_ms == pytest.approx((100 + 200 + 300) / 3)
    assert m.spread == {1: 1, 2: 1, 3: 1}
    assert m.max_latency_ms == 250
    assert s.avg_max_latency_ms == 250


def _records(case):
    """(msg_id, receivers, delays_ms_int) a message, as Simulator.summary
    hands them over."""
    rng = np.random.default_rng(11)
    everyone = np.arange(200)
    if case == "every_peer_receives":
        return [(7_000_000_000 + m, everyone, rng.integers(0, 2600, 200))
                for m in range(3)]
    if case == "a_message_not_every_peer_received":
        some = np.nonzero(rng.random(200) < 0.6)[0]
        return [(11, everyone, rng.integers(40, 900, 200)),
                (12, some, rng.integers(40, 900, some.size)),
                (13, np.array([199]), np.array([77]))]
    if case == "receivers_in_no_order":
        return [(21, rng.permutation(200), rng.integers(0, 5400, 200)),
                (22, rng.permutation(150), rng.integers(0, 5400, 150))]
    if case == "one_message":
        return [(31, everyone[:50], rng.integers(40, 700, 50))]
    if case == "no_records":
        return []
    if case == "a_record_nobody_received":
        return [(41, everyone[:0], np.zeros(0, dtype=np.int64)),
                (42, everyone[:9], rng.integers(40, 700, 9))]
    if case == "nobody_received_anything":
        return [(51, everyone[:0], np.zeros(0, dtype=np.int64))]
    if case == "both_sides_of_a_50_ms_rounding_edge":
        # the large variant rounds to the nearest 100 ms, halves up, and
        # int() truncates toward zero
        edges = np.array([0, 1, 49, 50, 51, 99, 100, 101, 149, 150, 151,
                          249, 250, 251, 1049, 1050, 1051, 5349, 5350, 5351])
        return [(61, np.arange(edges.size), edges),
                (62, np.arange(edges.size), edges[::-1] + 50)]
    if case == "one_msg_id_in_two_records":
        return [(71, everyone[:40], rng.integers(40, 700, 40)),
                (72, everyone[:30], rng.integers(40, 700, 30)),
                (71, everyone[40:90], rng.integers(40, 2700, 50))]
    raise AssertionError(case)


@pytest.mark.parametrize("large", [False, True], ids=["small", "large"])
@pytest.mark.parametrize("case", [
    "every_peer_receives", "a_message_not_every_peer_received",
    "receivers_in_no_order", "one_message", "no_records",
    "a_record_nobody_received", "nobody_received_anything",
    "both_sides_of_a_50_ms_rounding_edge", "one_msg_id_in_two_records"])
def test_summarize_records_is_summarize_on_the_writers_lines(case, large):
    """Simulator.summary's path (arrays in, no text) against the text path
    on the very lines `latencies<i>` holds: equal as dataclasses, so every
    float to the last bit, every spread key and count, the messages' order."""
    records = _records(case)
    w = LatenciesWriter()
    for msg_id, receivers, delays in records:
        w.add_message(msg_id, receivers, delays)
    buf = io.StringIO()
    w.write_to(buf)
    want = summarize(buf.getvalue().splitlines(), large=large)
    got = summarize_records(records, large=large)
    assert got == want
    assert [m.msg_id for m in got.messages] == [
        m.msg_id for m in want.messages]
    for g in [got] + got.messages:
        for name, value in vars(g).items():
            if name not in ("messages", "spread"):
                assert type(value) in (int, float), (name, type(value))
    if not records or case == "nobody_received_anything":
        assert got == LatencySummary(0, 0, 0, 0.0, [], 0.0)


def test_simulator_summary_reads_the_records_not_the_text(monkeypatch):
    """Simulator.summary formats no line and parses none: with the writer
    and the parser set to raise it still gives what the text path gives on
    `latencies<i>`."""
    from dst_libp2p_test_node_tpu.config.topology import TopoParams
    import importlib

    from dst_libp2p_test_node_tpu.runtime import logemit, simulator
    from dst_libp2p_test_node_tpu.runtime.simulator import (
        ExperimentConfig, Simulator)

    # (the package exports the function `summarize` under the module's name)
    summarize_mod = importlib.import_module(
        "dst_libp2p_test_node_tpu.runtime.summarize")
    sim = Simulator(ExperimentConfig(
        topo=TopoParams(network_size=40, msg_size_bytes=1500, messages=3,
                        delay_seconds=1.0),
        connect_to=5, warmup_s=5.0, seed=2))
    sim.run()
    buf = io.StringIO()
    sim.latencies_writer().write_to(buf)
    want = {large: summarize(buf.getvalue().splitlines(), large=large)
            for large in (False, True)}

    def tripped(*a, **kw):
        raise AssertionError("Simulator.summary went through the text")

    monkeypatch.setattr(summarize_mod, "parse_latencies", tripped)
    monkeypatch.setattr(summarize_mod, "summarize", tripped)
    monkeypatch.setattr(logemit, "LatenciesWriter", tripped)
    monkeypatch.setattr(simulator, "LatenciesWriter", tripped)
    monkeypatch.setattr(io, "StringIO", tripped)
    assert sim.summary(False) == want[False]
    assert sim.summary(True) == want[True]
    assert sim.summary() == want[True]      # 1,500 B: run.sh:68's switch
    assert want[True].total_messages == 3 and want[True].coverage() == 40.0


@pytest.mark.skipif(
    not (shutil.which("awk") and os.path.exists(REF_AWK_SMALL)),
    reason="reference awk scripts not available",
)
def test_reference_awk_runs_unchanged_on_our_output(tmp_path):
    """The compatibility gate: the REFERENCE summary awk scripts consume our
    latencies file and agree with our summarizer's numbers."""
    rng = np.random.default_rng(0)
    w = LatenciesWriter()
    ids = [111111, 222222]
    for mid in ids:
        peers = np.arange(1, 50)
        delays = rng.integers(40, 700, size=49)
        w.add_message(mid, peers, delays)
    path = str(tmp_path / "latencies1")
    w.write(path)

    with open(path) as f:
        ours = summarize(f, large=True)

    out = subprocess.run(
        ["awk", "-f", REF_AWK_LARGE, path], capture_output=True, text=True
    ).stdout
    assert f"Total Nodes :  {ours.network_size}" in out
    assert f"Total Messages Published :  {ours.total_messages}" in out
    assert f"MAX :  {ours.max_latency_ms}" in out
    for m in ours.messages:
        assert f"MAX delay for  {m.msg_id} is \t {m.max_latency_ms}" in out
    # avg-of-max headline stat matches to awk's %g printing
    assert f"Average Max Message Dissemination Latency :  {ours.avg_max_latency_ms:g}" in out

    out_small = subprocess.run(
        ["awk", "-f", REF_AWK_SMALL, path], capture_output=True, text=True
    ).stdout
    small = summarize(open(path), large=False)
    for m in small.messages:
        # awk prints "value \t avg \t   count spread is ..."
        assert f"{m.msg_id} \t {m.avg_latency_ms:g} \t   {m.received} spread is" in out_small


def test_native_and_python_formatters_agree():
    peers = np.arange(1, 6000)
    linenos = np.ones(5999, dtype=np.int64)
    delays = np.arange(5999, dtype=np.int64) % 999
    py = format_block(424242, peers, linenos, delays, force_python=True)
    native = format_block(424242, peers, linenos, delays)
    assert py == native


def test_go_msgid_mode_keys_by_timestamp():
    """Go/Rust embed no random message id; the dedup/log key is the LE64
    publish timestamp (go main.go:63-81, rust main.rs:101-143) — SURVEY §7's
    'keep a compat flag' for the payload-layout split."""
    from dst_libp2p_test_node_tpu.config.topology import TopoParams
    from dst_libp2p_test_node_tpu.runtime.simulator import (
        ExperimentConfig, Simulator)

    cfg = ExperimentConfig(
        topo=TopoParams(network_size=30, msg_size_bytes=400, messages=2,
                        delay_seconds=1.0),
        connect_to=5, warmup_s=5.0, seed=1, msgid_mode="go",
    )
    sim = Simulator(cfg)
    sim.run()
    for rec in sim.records:
        assert rec.msg_id == int(rec.t0_ms * 1e6)  # ns timestamp key
    assert sim.records[0].msg_id != sim.records[1].msg_id

    import pytest

    with pytest.raises(ValueError, match="msgid_mode"):
        Simulator(ExperimentConfig(
            topo=TopoParams(network_size=30), connect_to=5,
            msgid_mode="rust"))
