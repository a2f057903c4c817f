"""The large fragmented message (ISSUE 34): `run 1 <n> 131072 4 3 ... 12000`,
one blob-sized message in 4 fragments of 32,768 B a 12 s slot, at sizes a
test run can hold. Fragments this large queue IWANT answers, so every
publish takes the answer-queue refinement once a fragment lane (the 15,000
byte message in 4 fragments never does at 100,000 peers); the publisher's
own receipt is the publish call whatever its lanes' send origins are; and
the counters say what the lanes added."""

import contextlib
import dataclasses
import hashlib
import io
import json
import os

import numpy as np
import pytest

import shadowlog_reference
from dst_libp2p_test_node_tpu import cli
from dst_libp2p_test_node_tpu.ops.disseminate import (
    disseminate, fragments_in_sequence)
from dst_libp2p_test_node_tpu.runtime import simulator as simmod
from dst_libp2p_test_node_tpu.runtime.bandwidth import (
    PeerTraffic, summarize_bandwidth)
from test_des_crosscheck import des_delays
from test_exact_prefix import (  # noqa: F401  (one_lane_budget: a fixture)
    _leaf_bytes, mesh_setup, one_lane_budget)

BLOB = 131072
LINKS = ["50", "150", "40", "130", "5", "0.0", "4", "0"]


def _run(tmp, *, nodes, size, fragments, messages, gap_ms, seed, flags=()):
    """`run 1 <nodes> <size> <fragments> <messages> ... <gap_ms>` with the
    benchmark cells' links, publisher 4; returns `latencies1`'s bytes."""
    with contextlib.redirect_stdout(io.StringIO()):
        rc = cli.main(["run", "1", str(nodes), str(size), str(fragments),
                       str(messages), *LINKS, str(gap_ms), "--seed",
                       str(seed), "--stats-json", "--out-prefix",
                       str(tmp) + os.sep, *flags])
    assert rc == 0
    return (tmp / "latencies1").read_bytes()


# ------------------------------------------- the deployment against the DES


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    """The cell's line at 300 peers through cli.main, every publish's plan
    captured the way benchmark/harness/reference_check.py does: the name
    `disseminate` in runtime.simulator wrapped for the length of the call."""
    tmp = tmp_path_factory.mktemp("blob")
    original, taken = simmod.disseminate, []

    def with_plan(state, conns, rev, *args, **kw):
        res, new_state, plan = original(state, conns, rev, *args, **kw,
                                        return_plan=True)
        taken.append({"res": res, "plan": plan, "conns": np.asarray(conns),
                      "rev": np.asarray(rev), "kw": kw})
        return res, new_state

    simmod.disseminate = with_plan
    try:
        latencies = _run(tmp, nodes=300, size=BLOB, fragments=4, messages=3,
                         gap_ms=12000, seed=7)
    finally:
        simmod.disseminate = original
    with open(tmp / "stats1.json") as f:
        stats = json.load(f)
    return latencies, stats, taken


def test_every_publish_refines_on_the_prefix_engine(deployment):
    _, stats, taken = deployment
    assert stats["coverage"] == 300 and len(stats["publishes"]) == 3
    for p, pub in zip(stats["publishes"], taken):
        assert p["refined"] and not p["fell_back"]
        assert not p["refined_serial"] and p["converged"]
        # the deepest lane's passes, all lanes' passes, and how many lanes
        # asked: every lane refines when one hints
        assert 0 < p["refine_passes"] <= p["refine_lane_passes"] \
            <= 4 * p["refine_passes"]
        assert 1 <= p["lanes_hinted"] <= 4 and p["lanes_uncertified"] == 0
        assert pub["kw"]["fragments"] == 4
        assert pub["kw"]["payload_bytes"] == BLOB
        # a 12 s slot: the last message has drained, no link is still held
        t0 = pub["kw"]["t0_ms"]
        assert float(np.asarray(pub["plan"]["uplink"]).max()) <= t0
        assert float(np.asarray(pub["plan"]["rx_free"]).max()) <= t0


def test_the_deployment_matches_the_des(deployment):
    """Reached sets equal and every receiver's delay inside the tests'
    tolerance of the event-queue DES at the cell's link model; the
    publisher's own entry is 0 where the DES keeps its last send origin."""
    _, _, taken = deployment
    for pub in taken:
        kw = pub["kw"]
        plan = {k: None if v is None else np.asarray(v)
                for k, v in pub["plan"].items()}
        want_d, want_r = des_delays(
            pub["conns"], pub["rev"], plan, kw["params"], kw["publisher"],
            kw["t0_ms"], 4, payload_bytes=BLOB)
        got_d = np.asarray(pub["res"].delay_ms, np.float64)
        got_r = np.asarray(pub["res"].received)
        np.testing.assert_array_equal(got_r, want_r)
        assert got_r.all()
        own = kw["publisher"]
        assert got_d[own] == 0.0
        # 3 x 32,768 B at the publisher's uplink: the start of its last send
        t_pubs = plan["t_pubs"].astype(np.float64)
        assert want_d[own] == pytest.approx(t_pubs[3] - kw["t0_ms"])
        assert want_d[own] > 5.0
        others = np.arange(300) != own
        np.testing.assert_allclose(got_d[others], want_d[others],
                                   rtol=1e-4, atol=0.5)


# ------------------------------------------------ lanes in sequence, refined


def _publish_blob(state, a, topo, params, **kw):
    stage, lat, bw = topo
    return disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=7,
        t0_ms=float(state.t_ms), params=params, payload_bytes=BLOB,
        with_gossip=True, **kw)


def test_large_fragments_in_sequence_are_the_vmapped_bits(one_lane_budget):
    """The cell's position at a test's size: four lanes' pulls pass the
    gather budget, one lane's does not, so the lanes run in sequence on
    row_pull and the refinement is taken once a lane in the rolled loop.
    Bit for bit the vmapped publish, the three lane counters included."""
    g, params, state, a, topo = mesh_setup()
    shape = a["conns"].shape
    assert not fragments_in_sequence(shape, 4)
    res_v, st_v = _publish_blob(state, a, topo, params, fragments=4)
    one_lane_budget(shape)
    assert fragments_in_sequence(shape, 4)
    res_q, st_q = _publish_blob(state, a, topo, params, fragments=4)
    assert bool(res_q.refined) and not bool(res_q.fell_back)
    assert not bool(res_q.refined_serial)
    assert int(res_q.refine_lane_passes) > int(res_q.refine_passes) > 0
    got, want = _leaf_bytes((res_q, st_q)), _leaf_bytes((res_v, st_v))
    assert got.keys() == want.keys()
    for name in got:
        assert got[name].tobytes() == want[name].tobytes(), name


# ------------------------------------------------------------- the counters


@pytest.mark.parametrize("fragments", [1, 3, 4])
def test_lane_counters(fragments):
    g, params, state, a, topo = mesh_setup()
    res, _ = _publish_blob(state, a, topo, params, fragments=fragments)
    assert bool(res.refined) and not bool(res.fell_back)
    lane, deepest = int(res.refine_lane_passes), int(res.refine_passes)
    assert deepest <= lane <= fragments * deepest
    if fragments == 1:
        assert lane == deepest and int(res.lanes_hinted) == 1
    assert 1 <= int(res.lanes_hinted) <= fragments
    assert int(res.lanes_uncertified) == 0
    assert np.asarray(res.counters).tolist()[6:9] == [
        lane, int(res.lanes_hinted), 0]


@pytest.mark.parametrize("cap,uncertified,converged", [
    (2, 3, False), (3, 2, True)], ids=["every-lane", "two-of-three"])
def test_lanes_uncertified_when_the_prefix_engine_is_capped(
        cap, uncertified, converged):
    """The fallback names its size: with the Jacobi loops capped under what
    they need (as tests/test_tracing.py forces the fallback) the lanes the
    prefix engine could not certify are counted, 3 of 3 under a cap of 2
    (which cuts the fast pipeline's loops too) and 2 of 3 under 3; all
    three rerun through the global-sort engine either way, and a lane's
    passes are its prefix iterations spent plus its serial ones."""
    g, params, state, a, topo = mesh_setup(flood_publish=False, d_lazy=12)
    res, _ = _publish_blob(
        state, a, topo, dataclasses.replace(params, max_relax_iters=cap),
        fragments=3)
    assert bool(res.refined) and bool(res.fell_back)
    assert bool(res.refined_serial) and bool(res.converged) is converged
    assert int(res.lanes_uncertified) == uncertified
    assert int(res.lanes_hinted) == 3
    assert int(res.refine_passes) < int(res.refine_lane_passes) \
        <= 3 * int(res.refine_passes)


def test_no_refinement_counts_no_lane():
    g, params, state, a, topo = mesh_setup()
    stage, lat, bw = topo
    res, _ = disseminate(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=7,
        t0_ms=float(state.t_ms), params=params, payload_bytes=BLOB,
        with_gossip=False, fragments=4)
    assert not bool(res.refined)
    assert np.asarray(res.counters).tolist()[6:9] == [0, 0, 0]


# ------------------------------------------------ the publisher's own receipt

# `run 1 200 <size> <fragments> <messages> 50 150 40 130 5 0.0 4 0 <gap>
# --seed 11` on the parent commit (bb1c2e5, XLA:CPU): sha256[:16] of
# latencies1, of latencies1 without the publisher's own lines, and what
# those lines read there. The first four are the accepted cells' argv at
# 200 peers: the publisher printed 0 (exactly at one fragment, by truncation
# of 3 x 3,750 B at its uplink at four) and the file does not move by a
# byte. At 60,000 and 131,072 B in 4 fragments the parent's publisher logged
# the start of its last send, 2 and 6 ms; no other receiver's line moves.
PARENT = {
    "runsh-1k": (15000, 1, 10, 4000, (), "eeccba80e68ffadd",
                 "ccdcf51f745d0359", 0),
    "runsh-100k": (15000, 1, 3, 4000, (), "12052e7b273bfce2",
                   "95e831c51ed72ba9", 0),
    "runsh-100k.meshonly": (15000, 1, 3, 4000, ("--no-gossip",),
                            "be9262374eaf387c", "b7bd6be1bd87a16a", 0),
    "runsh-100k-frag4": (15000, 4, 3, 4000, (), "fb85bd35273cfa0f",
                         "9855f4e8415f418b", 0),
    "60k-frag4": (60000, 4, 3, 12000, (), "37cc2359bea05efc",
                  "dc006920b2927871", 2),
    "runsh-100k-128k-frag4": (BLOB, 4, 3, 12000, (), "778f94c8a77afd3b",
                              "409ef1e47550bf5a", 6),
}


@pytest.mark.parametrize("case", list(PARENT))
def test_the_publisher_logs_zero_and_nobody_else_moves(case, tmp_path):
    size, fragments, messages, gap, flags, whole, others, own_ms = PARENT[case]
    latencies = _run(tmp_path, nodes=200, size=size, fragments=fragments,
                     messages=messages, gap_ms=gap, seed=11, flags=flags)
    lines = latencies.splitlines(True)
    own = [ln for ln in lines if b"/peer4/" in ln]
    assert len(own) == messages
    assert all(ln.endswith(b" milliseconds: 0\n") for ln in own)
    rest = b"".join(ln for ln in lines if b"/peer4/" not in ln)
    assert hashlib.sha256(rest).hexdigest()[:16] == others
    assert (hashlib.sha256(latencies).hexdigest()[:16] == whole) \
        == (own_ms == 0)


# ------------------------------------------------- the bandwidth report's sums


def test_bandwidth_totals_are_exact_past_32_bits():
    """100,000 peers x 3 messages x 131,072 B x about 8 copies is 3 x 10^11
    bytes in the `Remote IN/OUT` report, 70 x what any run summed before
    (15,000 B: 4.5 x 10^9). The totals are the per-peer loop's integers."""
    n = 100_000
    rng = np.random.default_rng(34)
    copies = rng.integers(4, 13, n)
    # what the engine counts: whole fragments of 32,768 B, in float32
    rx = (3 * 4 * copies * (BLOB // 4)).astype(np.float32)
    tx = rng.permutation(rx)
    ctrl = rng.integers(0, 4000, n).astype(np.float64)
    traffic = PeerTraffic(rx_bytes=rx.astype(np.float64),
                          tx_bytes=tx.astype(np.float64),
                          ctrl_rx=ctrl, ctrl_tx=ctrl[::-1].copy())
    assert float(rx.max()) < 2 ** 24      # a peer's own counter is exact
    s = summarize_bandwidth(traffic)
    # the loop a peer: Python integers off every line's two remote blocks
    sums = [0] * 24
    for line in shadowlog_reference.shadowlog_lines(traffic):
        blocks = line.rsplit(";", 1)[1].split(",")[24:]
        for i, v in enumerate(blocks):
            sums[i] += int(v)
    assert sums[1] > 2 ** 32 and sums[13] > 2 ** 32
    got = [s.remote_in_pkt, s.remote_in_bytes, s.remote_in_ctrl_pkt,
           s.remote_in_ctrl_hdr_bytes, s.remote_in_data_pkt,
           s.remote_in_data_hdr_bytes, s.remote_in_data_bytes,
           s.remote_out_pkt, s.remote_out_bytes, s.remote_out_ctrl_pkt,
           s.remote_out_ctrl_hdr_bytes, s.remote_out_data_pkt,
           s.remote_out_data_hdr_bytes, s.remote_out_data_bytes]
    want = [sums[i] for i in (0, 1, 2, 3, 6, 7, 8, 12, 13, 14, 15, 18, 19, 20)]
    assert got == want
    assert (s.total_rx, s.total_tx) == (float(sums[1]), float(sums[13]))
