import dataclasses
import hashlib

import numpy as np
import pytest

import shadow_yaml_reference
from dst_libp2p_test_node_tpu.config.topology import Topology, TopoParams


BASELINE = TopoParams(
    network_size=100,
    min_bandwidth=50,
    max_bandwidth=150,
    min_latency=40,
    max_latency=130,
    anchor_stages=5,
    msg_size_bytes=15000,
)


def test_stage_bandwidth_ramp():
    t = Topology.build(BASELINE)
    # bw_jump = int(100/5) = 20 -> stages 50,70,90,110,130; injector 100.
    assert t.bw_up_mbit.tolist() == [50, 70, 90, 110, 130, 100]


def test_edge_latency_rule():
    t = Topology.build(BASELINE)
    # lat_jump = int(90/5) = 18; pair (i,j), j>i: min(ceil((5-j)*18+40), 130)
    assert t.latency_ms[0, 1] == min((5 - 1) * 18 + 40, 130)  # 112
    assert t.latency_ms[0, 4] == min((5 - 4) * 18 + 40, 130)  # 58
    assert t.latency_ms[3, 4] == 58
    # symmetric
    assert np.allclose(t.latency_ms, t.latency_ms.T)
    # self-loop rule: max((5-i)*18, 40)
    assert t.latency_ms[0, 0] == max(5 * 18, 40)  # 90
    assert t.latency_ms[4, 4] == max(1 * 18, 40)  # 40
    # injector fast node: 1 ms everywhere
    assert np.all(t.latency_ms[5, :] == 1.0)


def test_stage_assignment_round_robin():
    t = Topology.build(BASELINE)
    assert t.stage_of_peer[0] == 0
    assert t.stage_of_peer[7] == 2
    assert t.stage_of_peer[99] == 99 % 5


def test_tx_time():
    t = Topology.build(BASELINE)
    tx = t.tx_ms_per_peer(15000)
    # stage0 peer: 15000*8 bits / 50 Mbit/s = 2.4 ms
    assert tx[0] == pytest.approx(2.4)
    assert tx[4] == pytest.approx(15000 * 8 / 130e6 * 1e3)


def test_gml_roundtrip(tmp_path):
    t = Topology.build(BASELINE)
    gml = str(tmp_path / "network_topology.gml")
    t.write_gml(gml)
    t2 = Topology.from_gml(gml, network_size=100)
    assert t2.n_stages == 5
    assert np.allclose(t.latency_ms, t2.latency_ms)
    assert np.allclose(t.bw_up_mbit, t2.bw_up_mbit)
    assert np.array_equal(t.stage_of_peer, t2.stage_of_peer)


def test_shadow_yaml_schema(tmp_path):
    import yaml

    t = Topology.build(BASELINE)
    path = str(tmp_path / "shadow.yaml")
    t.write_shadow_yaml(path)
    with open(path) as f:
        cfg = yaml.safe_load(f)
    assert cfg["general"]["stop_time"] == "15m"
    assert cfg["general"]["bootstrap_end_time"] == "10s"
    assert cfg["network"]["graph"]["type"] == "gml"
    hosts = cfg["hosts"]
    # pods 0..99 plus the pod-100 publish controller
    assert len(hosts) == 101
    pod0 = hosts["pod-0"]["processes"][0]
    assert pod0["environment"]["PEERS"] == "100"
    assert pod0["environment"]["CONNECTTO"] == "10"
    assert pod0["environment"]["MUXER"] == "yamux"
    assert pod0["start_time"] == "5s"
    ctrl = hosts["pod-100"]["processes"][0]
    assert ctrl["start_time"] == "500s"
    assert "traffic_sync.py" in ctrl["args"]
    # round-robin network node assignment
    assert hosts["pod-7"]["network_node_id"] == 2


# (N, S): fewer peers than stages (no anchor at all), N = S, S < N < 2S (only
# the first N - S stages get one), N = 2S and one past it, one stage
SIZES = [(1, 1), (3, 5), (5, 5), (7, 5), (10, 5), (11, 5), (12, 5),
         (1000, 5), (2000, 3), (2048, 1)]
# what else reaches the file: the stage hosts' environment and the
# injector's `args` line (the last two fold it past 80 columns at any N)
FIELDS = {
    "defaults": {},
    "frag4-mplex": dict(num_frags=4, muxer="mplex", messages=3,
                        msg_size_bytes=15000, delay_seconds=4.0),
    "frag9-quic": dict(num_frags=9, muxer="quic", messages=1,
                       msg_size_bytes=100, delay_seconds=0.5),
    "yamux-long-args": dict(num_frags=2, muxer="yamux", messages=1000,
                            msg_size_bytes=1500000, delay_seconds=0.125),
    "quic-longer-args": dict(num_frags=9, muxer="quic", messages=123456,
                             msg_size_bytes=2 ** 31, delay_seconds=1e-05,
                             packet_loss=0.25),
}


def _both_files(tmp_path, params):
    t = Topology.build(params)
    ours, ref = tmp_path / "shadow.yaml", tmp_path / "reference.yaml"
    counts = t.write_shadow_yaml(str(ours))
    shadow_yaml_reference.write_shadow_yaml(t, str(ref))
    return ours.read_bytes(), ref.read_bytes(), counts


@pytest.mark.parametrize("fields_id", FIELDS)
@pytest.mark.parametrize("n,s", SIZES)
def test_shadow_yaml_bytes_are_the_whole_document_dumps(
        tmp_path, n, s, fields_id):
    params = dataclasses.replace(BASELINE, network_size=n, anchor_stages=s,
                                 **FIELDS[fields_id])
    ours, ref, counts = _both_files(tmp_path, params)
    assert ours == ref
    dumped = min(n, 2 * s)
    assert counts == {"yaml_hosts_dumped": dumped + 1,
                      "yaml_alias_lines": n - dumped}
    if "args" in fields_id:
        # past 80 columns the injector's `args` is PyYAML's to fold
        lines = ref.decode().splitlines()
        assert lines[lines.index("      start_time: 500s") - 1].startswith(
            "        ")


def test_shadow_yaml_bytes_at_100k_peers(tmp_path):
    ours, ref, counts = _both_files(
        tmp_path, dataclasses.replace(BASELINE, network_size=100_000,
                                      messages=3))
    assert ours == ref
    assert hashlib.sha256(ours).hexdigest() \
        == hashlib.sha256(ref).hexdigest()
    assert counts == {"yaml_hosts_dumped": 11, "yaml_alias_lines": 99_990}


@pytest.mark.parametrize("n,s", [(100_000, 5), (2048, 1), (10, 5), (3, 5),
                                 (40, 7)])
def test_shadow_yaml_hands_pyyaml_a_document_of_constant_size(
        tmp_path, monkeypatch, n, s):
    """The mechanism engaged, and there is no other path to fall back to:
    whatever the size, `yaml.dump` sees the first min(N, 2S) hosts and the
    injector, once."""
    import yaml

    handed = []
    real = yaml.dump

    def counting(data, *args, **kwargs):
        handed.append(len(data["hosts"]))
        return real(data, *args, **kwargs)

    monkeypatch.setattr(yaml, "dump", counting)
    t = Topology.build(dataclasses.replace(
        BASELINE, network_size=n, anchor_stages=s))
    counts = t.write_shadow_yaml(str(tmp_path / "shadow.yaml"))
    # 11 of 100,001 hosts at (100,000, 5); never more than 2S + 1
    assert handed == [min(n, 2 * s) + 1]
    assert counts == {"yaml_hosts_dumped": handed[0],
                      "yaml_alias_lines": n + 1 - handed[0]}


def test_validation():
    with pytest.raises(ValueError):
        Topology.build(TopoParams(min_bandwidth=100, max_bandwidth=50))
    with pytest.raises(ValueError):
        Topology.build(TopoParams(min_latency=100, max_latency=50))
    with pytest.raises(ValueError):
        Topology.build(TopoParams(num_frags=0))


def test_single_stage_degenerate():
    t = Topology.build(TopoParams(network_size=10, anchor_stages=1))
    assert t.latency_ms[0, 0] == 100.0  # max((1-0)*0, 100)
    assert np.all(t.stage_of_peer == 0)
