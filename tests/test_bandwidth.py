"""Bandwidth channel parity with shadow/summary_shadowlog.awk.

The gold test: emit our '[node]' heartbeat lines, run the REFERENCE awk
script on them unchanged, and check its printed aggregates equal our
Python summarizer's (same approach as the latency parity tests)."""

import os
import re
import shutil
import subprocess

import numpy as np
import pytest

import shadowlog_reference
from dst_libp2p_test_node_tpu.runtime import native_logemit
from dst_libp2p_test_node_tpu.runtime.bandwidth import (
    MSS_BYTES,
    PeerTraffic,
    report,
    shadowlog_fields,
    shadowlog_head,
    shadowlog_text,
    summarize_bandwidth,
)

AWK = shutil.which("awk")
REF_AWK = "/root/reference/shadow/summary_shadowlog.awk"


def _traffic(n=16, seed=0):
    rng = np.random.default_rng(seed)
    rx = np.floor(rng.uniform(1e4, 5e6, n))
    tx = np.floor(rng.uniform(1e4, 5e6, n))
    ctrl = np.floor(rng.uniform(0, 40, n))
    return PeerTraffic(rx_bytes=rx, tx_bytes=tx, ctrl_rx=ctrl.copy(), ctrl_tx=ctrl)


def shadowlog_lines(t):
    return shadowlog_text(t).splitlines()


def test_line_field_layout():
    t = _traffic(4)
    lines = shadowlog_lines(t)
    assert len(lines) == 4
    for i, ln in enumerate(lines):
        f = ln.split()
        assert f[4] == f"pod-{i}"      # $5 peer (awk:14)
        assert f[8] == "[node]"        # $9 filter (awk:12)
        arr = re.split("[,;]", f[9])   # $10 split on ",|;" (awk:16)
        assert len(arr) == 6 + 4 * 12  # tag + 5 + four 12-flag blocks
        # arr[2]/arr[3] are awk 1-indexed => python [1]/[2]
        assert int(arr[1]) >= t.rx_bytes[i]
        assert int(arr[2]) >= t.tx_bytes[i]


@pytest.mark.skipif(AWK is None or not os.path.exists(REF_AWK),
                    reason="awk or reference script unavailable")
def test_reference_awk_parity(tmp_path):
    t = _traffic(12, seed=3)
    log = tmp_path / "shadowlog1"
    log.write_text("\n".join(shadowlog_lines(t)) + "\n")
    out = subprocess.run(
        [AWK, "-f", REF_AWK, str(log)], capture_output=True, text=True, check=True
    ).stdout
    s = summarize_bandwidth(t)

    m = re.search(r"Total Bytes Received :\s+(\S+)\s+Total Bytes Transferred :\s+(\S+)", out)
    assert m, out
    assert float(m.group(1)) == pytest.approx(s.total_rx)
    assert float(m.group(2)) == pytest.approx(s.total_tx)

    m = re.search(
        r"Per Node Pkt Receives : min, max, avg, stddev =\s+(\S+)\s+(\S+)\s+(\S+)\s+(\S+)",
        out,
    )
    assert float(m.group(1)) == pytest.approx(s.min_rx)
    assert float(m.group(2)) == pytest.approx(s.max_rx)
    assert float(m.group(3)) == pytest.approx(s.avg_rx, rel=1e-5)
    assert float(m.group(4)) == pytest.approx(s.std_rx, rel=1e-5)

    m = re.search(
        r"Remote IN pkt:\s+(\S+) Bytes :\s+(\S+) ctrlPkt:\s+(\S+) ctrlHdrBytes:\s+(\S+) "
        r"DataPkt:\s+(\S+) DataHdrBytes:\s+(\S+) DataBytes\s+(\S+)",
        out,
    )
    assert m, out
    assert int(float(m.group(1))) == s.remote_in_pkt
    assert int(float(m.group(3))) == s.remote_in_ctrl_pkt
    assert int(float(m.group(5))) == s.remote_in_data_pkt
    assert int(float(m.group(7))) == s.remote_in_data_bytes

    m = re.search(
        r"Remote OUT pkt:\s+(\S+) Bytes :.*ctrlPkt:\s+(\S+) ctrlHdrBytes:\s+(\S+) "
        r"DataPkt:\s+(\S+) DataHdrBytes:\s+(\S+) DataBytes\s+(\S+)",
        out,
    )
    assert m, out
    assert int(float(m.group(1))) == s.remote_out_pkt
    assert int(float(m.group(4))) == s.remote_out_data_pkt
    assert int(float(m.group(6))) == s.remote_out_data_bytes


def _same(n, rx, tx, crx, ctx):
    return PeerTraffic(rx_bytes=np.full(n, rx), tx_bytes=np.full(n, tx),
                       ctrl_rx=np.full(n, crx), ctrl_tx=np.full(n, ctx))


def _wide(n, top, seed, dtype=np.float64):
    """Byte counts up to `top` with fractions, as a float32 device counter
    that has rounded leaves them; control counts whole."""
    rng = np.random.default_rng(seed)
    return PeerTraffic(
        rx_bytes=rng.uniform(0, top, n).astype(dtype),
        tx_bytes=rng.uniform(0, top, n).astype(dtype),
        ctrl_rx=np.floor(rng.uniform(0, 1e4, n)).astype(dtype),
        ctrl_tx=np.floor(rng.uniform(0, 1e4, n)).astype(dtype))


# name -> traffic; the peer counts lie on both sides of NATIVE_MIN_LINES
SHADOWLOG_CASES = {
    "all_zero": lambda: _same(16, 0.0, 0.0, 0.0, 0.0),
    # int() truncates: 1447.9 bytes are 1447, and still one packet
    "fractional_bytes": lambda: _same(7, 1447.9, 1448.000001, 2.5, 0.999),
    "beyond_2_31": lambda: _wide(33, 2.0 ** 33, seed=1),
    "beyond_1e12": lambda: _wide(33, 5e13, seed=2),
    "float32_counters": lambda: _wide(50, 3e7, seed=3, dtype=np.float32),
    "1_peer": lambda: _traffic(1, seed=4),
    "4095_peers": lambda: _traffic(native_logemit.NATIVE_MIN_LINES - 1, seed=5),
    "4096_peers": lambda: _traffic(native_logemit.NATIVE_MIN_LINES, seed=6),
    "20000_peers": lambda: _wide(20_000, 5e7, seed=7),
}


@pytest.mark.parametrize("formatter", ["chosen_by_size", "python"])
@pytest.mark.parametrize("case", list(SHADOWLOG_CASES))
def test_shadowlog_bytes_are_the_reference_loops(case, formatter):
    t = SHADOWLOG_CASES[case]()
    n = t.rx_bytes.shape[0]
    want = shadowlog_reference.shadowlog_text(t)
    assert want.count("\n") == n
    before = native_logemit.native_shadowlog_blocks
    if formatter == "python":
        got = native_logemit.format_shadowlog(
            shadowlog_head("00:15:00"), shadowlog_fields(t), force_python=True)
        native = 0
    else:
        got = shadowlog_text(t)
        native = int(n >= native_logemit.NATIVE_MIN_LINES
                     and native_logemit.ensure_built())
    assert got == want
    assert native_logemit.native_shadowlog_blocks == before + native


def test_shadowlog_sim_time_is_the_reference_loops():
    t = _traffic(5000, seed=8)
    for sim_time in ("00:00:01", "12:34:56.789"):
        assert shadowlog_text(t, sim_time) == (
            shadowlog_reference.shadowlog_text(t, sim_time))


def test_summary_math():
    t = PeerTraffic(
        rx_bytes=np.array([1000.0, 3000.0]),
        tx_bytes=np.array([2000.0, 2000.0]),
        ctrl_rx=np.zeros(2),
        ctrl_tx=np.zeros(2),
    )
    s = summarize_bandwidth(t)
    assert s.total_rx == 4000 and s.total_tx == 4000
    assert s.min_rx == 1000 and s.max_rx == 3000 and s.avg_rx == 2000
    assert s.std_rx == pytest.approx(1000.0)  # population stddev (awk:128)
    assert s.remote_in_data_pkt == int(np.ceil(1000 / MSS_BYTES) + np.ceil(3000 / MSS_BYTES))
    assert s.remote_in_data_bytes == 4000
    assert s.remote_in_ctrl_hdr_bytes == 0
    txt = report(s)
    assert "Total Bytes Received" in txt and "Details..." in txt


def test_from_state_per_peer_ctrl():
    class FakeState:
        bytes_rx = np.array([10.0, 20.0, 30.0])
        bytes_tx = np.array([1.0, 2.0, 3.0])
        ihave_tx = np.array([4, 0, 0])
        iwant_tx = np.array([0, 3, 0])
        ihave_rx = np.array([0, 2, 2])
        iwant_rx = np.array([3, 0, 1])
        idontwant_tx = np.array([0, 0, 5])
        idontwant_rx = np.array([2, 2, 1])

    t = PeerTraffic.from_state(FakeState)
    # ctrl counters are REAL per-peer values, not an even spread
    assert (t.ctrl_tx == np.array([4.0, 3.0, 5.0])).all()
    assert (t.ctrl_rx == np.array([5.0, 4.0, 4.0])).all()
    assert (t.rx_bytes == FakeState.bytes_rx).all()


def test_simulator_integration(tmp_path):
    from dst_libp2p_test_node_tpu.config.topology import TopoParams
    from dst_libp2p_test_node_tpu.runtime.simulator import (
        ExperimentConfig,
        Simulator,
    )

    cfg = ExperimentConfig(
        topo=TopoParams(network_size=16, msg_size_bytes=600, messages=2),
        connect_to=5, warmup_s=3.0, seed=0,
    )
    sim = Simulator(cfg)
    sim.run()
    p = tmp_path / "shadowlog1"
    assert sim.write_shadowlog(str(p)) == 16
    assert p.read_text() == shadowlog_reference.shadowlog_text(sim.traffic())
    assert sim.emit_counts == {"shadowlog_lines": 16,
                               "shadowlog_native_blocks": 0}
    rep = sim.bandwidth_report()
    assert "Total Bytes Received" in rep
    s = summarize_bandwidth(sim.traffic())
    assert s.total_tx > 0 and s.total_rx > 0


@pytest.mark.parametrize("peers", [300, 5000])
def test_write_shadowlog_writes_the_reference_bytes(
        tmp_path, monkeypatch, peers):
    """Simulator.write_shadowlog on both sides of NATIVE_MIN_LINES, on
    counters set from here (a 5,000-peer run costs seconds on the CPU)."""
    from dst_libp2p_test_node_tpu.config.topology import TopoParams
    from dst_libp2p_test_node_tpu.runtime.simulator import (
        ExperimentConfig,
        Simulator,
    )

    sim = Simulator(ExperimentConfig(
        topo=TopoParams(network_size=16, msg_size_bytes=600, messages=1),
        connect_to=5, warmup_s=3.0, seed=0))
    t = _wide(peers, 4e8, seed=peers)
    monkeypatch.setattr(sim, "traffic", lambda: t)
    assert sim.write_shadowlog(str(tmp_path / "shadowlog1")) == peers
    assert (tmp_path / "shadowlog1").read_text() == (
        shadowlog_reference.shadowlog_text(t))
    native = int(peers >= native_logemit.NATIVE_MIN_LINES
                 and native_logemit.ensure_built())
    assert sim.emit_counts == {"shadowlog_lines": peers,
                               "shadowlog_native_blocks": native}
