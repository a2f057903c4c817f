"""End-to-end sharded execution: a Simulator on an 8-device peer mesh
produces the same experiment results as the single-device run.

This is the multi-chip contract (SURVEY.md §2 parallelism table): peers
row-sharded over a 1-D Mesh, heartbeats auto-partitioned by XLA, the
dissemination fixpoint on the explicit shard_map + all-gather/psum path
(parallel/exchange.py via ops/disseminate.py `mesh=`)."""

import jax
import numpy as np
import pytest

from dst_libp2p_test_node_tpu.config.topology import TopoParams
from dst_libp2p_test_node_tpu.parallel.sharding import make_peer_mesh
from dst_libp2p_test_node_tpu.runtime.simulator import ExperimentConfig, Simulator


def _cfg(**kw):
    topo = TopoParams(
        network_size=64, anchor_stages=2, min_bandwidth=50, max_bandwidth=100,
        min_latency=40, max_latency=80, msg_size_bytes=2000, **kw
    )
    return ExperimentConfig(topo=topo, connect_to=6, warmup_s=3.0, seed=11)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
def test_sharded_simulator_matches_single_device():
    a = Simulator(_cfg())
    a.warmup()
    ra = a.publish(4)

    b = Simulator(_cfg(), mesh=make_peer_mesh(8))
    b.warmup()
    rb = b.publish(4)

    np.testing.assert_array_equal(ra.received, rb.received)
    np.testing.assert_allclose(ra.delays_ms, rb.delays_ms, rtol=1e-5)
    np.testing.assert_array_equal(ra.sends, rb.sends)


@pytest.mark.parametrize("churn", [0.0, 0.02])
@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
def test_sharded_scan_on_the_sparse_route_matches_single_device(
        churn):
    """The heartbeat scan's sparse reciprocity (the scatter from the rows
    that send, the carried neighbour view under churn) through the SPMD
    partitioner: the route a large mesh-sharded Simulator takes, forced at
    this size, against one device on the dense pull."""
    import dataclasses

    import pull_route

    cfg = dataclasses.replace(_cfg(), churn_down_per_hb=churn,
                              churn_up_per_hb=churn / 2)
    a = Simulator(cfg)
    a.warmup()
    ra = a.publish(4)
    assert a.heartbeat_counts["pulls_sparse"] == 0

    with pull_route.forced(0, rows=8):
        b = Simulator(cfg, mesh=make_peer_mesh(8))
        b.warmup()
        rb = b.publish(4)
    counts = b.heartbeat_counts
    assert counts["pulls_sparse"] > 0 and counts["graft"]["dense"] >= 1
    if churn:
        assert counts["validity"]["sparse"] > 0
    np.testing.assert_array_equal(
        np.asarray(a.state.mesh_mask), np.asarray(b.state.mesh_mask))
    np.testing.assert_array_equal(
        np.asarray(a.state.alive), np.asarray(b.state.alive))
    np.testing.assert_array_equal(ra.received, rb.received)
    np.testing.assert_allclose(ra.delays_ms, rb.delays_ms, rtol=1e-5)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
def test_sharded_fragments_unrolled():
    a = Simulator(_cfg(num_frags=2))
    a.warmup()
    ra = a.publish(4)

    b = Simulator(_cfg(num_frags=2), mesh=make_peer_mesh(8))
    b.warmup()
    rb = b.publish(4)

    np.testing.assert_array_equal(ra.received, rb.received)
    np.testing.assert_allclose(ra.delays_ms, rb.delays_ms, rtol=1e-5)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
def test_sharded_tcp_loss_matches_single_device():
    # loss_mode="tcp" folds the sampled retransmission stalls into the
    # per-edge constants (parallel/exchange.py retx_ms) — the shard_map
    # path must reproduce the single-device arrival times exactly
    def cfg():
        c = _cfg(packet_loss=0.3)
        c.loss_mode = "tcp"
        return c

    a = Simulator(cfg())
    a.warmup()
    ra = a.publish(4)

    b = Simulator(cfg(), mesh=make_peer_mesh(8))
    b.warmup()
    rb = b.publish(4)

    assert ra.received.all()  # tcp loss never costs coverage
    np.testing.assert_array_equal(ra.received, rb.received)
    np.testing.assert_allclose(ra.delays_ms, rb.delays_ms, rtol=1e-5)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
def test_sharded_bounded_mode_matches_single_device():
    # the bounded delivery mode (serialize_answers=False — what the 1M
    # sharded platform runs) must also be sharded==single-device, and its
    # exported error bar must agree across the two executions
    def cfg():
        c = _cfg(packet_loss=0.3)
        c.loss_mode = "message"       # queues form via gossip recovery
        c.serialize_answers = False
        return c

    a = Simulator(cfg())
    a.warmup()
    ra = a.publish(4)

    b = Simulator(cfg(), mesh=make_peer_mesh(8))
    b.warmup()
    rb = b.publish(4)

    np.testing.assert_array_equal(ra.received, rb.received)
    np.testing.assert_allclose(ra.delays_ms, rb.delays_ms, rtol=1e-5)
    np.testing.assert_allclose(ra.answer_wait_max_ms, rb.answer_wait_max_ms,
                               rtol=1e-5)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
def test_uneven_shard_rejected():
    with pytest.raises(ValueError):
        Simulator(
            ExperimentConfig(
                topo=TopoParams(network_size=60), connect_to=6
            ),
            mesh=make_peer_mesh(8),
        )


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
def test_sharded_multitopic_matches_single_device():
    # the EP analog sharded: T*N virtual-peer rows across the mesh; two
    # topics published back-to-back so the cross-topic uplink fold also
    # runs on sharded state
    from dst_libp2p_test_node_tpu.runtime.multitopic import (
        MultiTopicConfig, MultiTopicSimulator,
    )

    def cfg():
        return MultiTopicConfig(
            topo=TopoParams(network_size=48, anchor_stages=2,
                            min_bandwidth=50, max_bandwidth=100,
                            min_latency=40, max_latency=80,
                            msg_size_bytes=15000),
            topics=("blocks", "attestations"), connect_to=6,
            subscribe_fraction=0.8, warmup_s=3.0, seed=11,
        )

    a = MultiTopicSimulator(cfg())
    a.warmup()
    ra1 = a.publish("blocks", 7)
    ra2 = a.publish("attestations", 7)

    b = MultiTopicSimulator(cfg(), mesh=make_peer_mesh(8))
    b.warmup()
    rb1 = b.publish("blocks", 7)
    rb2 = b.publish("attestations", 7)

    for ra, rb in ((ra1, rb1), (ra2, rb2)):
        np.testing.assert_array_equal(ra.received, rb.received)
        np.testing.assert_allclose(ra.delays_ms, rb.delays_ms, rtol=1e-5)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
def test_sharded_multitopic_uneven_rejected():
    from dst_libp2p_test_node_tpu.runtime.multitopic import (
        MultiTopicConfig, MultiTopicSimulator,
    )

    with pytest.raises(ValueError):
        MultiTopicSimulator(
            MultiTopicConfig(topo=TopoParams(network_size=30),
                             topics=("a", "b", "c"), connect_to=6),
            mesh=make_peer_mesh(8),
        )
