"""Checkpoint/resume: a restored experiment continues bit-exactly.

The reference never checkpoints (SURVEY.md §5); this subsystem is an
improvement the 1M-peer configs need. The contract under test: save at an
arbitrary point mid-experiment, load in a fresh Simulator, continue both —
identical heartbeat outcomes, message ids, and delay arrays.
"""

import numpy as np
import pytest

from dst_libp2p_test_node_tpu.config.topology import TopoParams
from dst_libp2p_test_node_tpu.runtime.checkpoint import (
    load_checkpoint,
    save_checkpoint,
)
from dst_libp2p_test_node_tpu.runtime.simulator import ExperimentConfig, Simulator


def _cfg(**kw):
    topo = TopoParams(
        network_size=60, anchor_stages=3, min_bandwidth=50, max_bandwidth=150,
        min_latency=40, max_latency=130, msg_size_bytes=500, messages=2,
        delay_seconds=1.0,
    )
    return ExperimentConfig(topo=topo, connect_to=6, warmup_s=5.0, seed=3, **kw)


@pytest.fixture(scope="module")
def midpoint(tmp_path_factory):
    """One experiment advanced past warm-up + first publish, checkpointed.
    `snap` freezes the at-save values (tests mutate the live sim)."""
    sim = Simulator(_cfg())
    sim.warmup()
    sim.publish(4)
    path = tmp_path_factory.mktemp("ckpt") / "mid.npz"
    save_checkpoint(sim, str(path))
    snap = {
        "n_records": len(sim.records),
        "rec0_delays": sim.records[0].delays_ms.copy(),
        "rec0_msg_id": sim.records[0].msg_id,
        "bytes_tx": np.asarray(sim.state.bytes_tx).copy(),
        "hb_carry_ms": sim._hb_carry_ms,
    }
    return sim, str(path), snap


def _finish(sim):
    sim.advance(3000.0)
    rec = sim.publish(7, msg_size=500)
    return rec


def test_resume_is_bit_exact(midpoint):
    sim, path, _ = midpoint
    restored = load_checkpoint(path)

    a = _finish(sim)
    b = _finish(restored)

    assert a.msg_id == b.msg_id  # host msgId RNG stream resumed
    np.testing.assert_array_equal(a.received, b.received)
    np.testing.assert_allclose(a.delays_ms, b.delays_ms)
    np.testing.assert_array_equal(
        np.asarray(sim.state.mesh_mask), np.asarray(restored.state.mesh_mask)
    )
    assert float(sim.state.t_ms) == float(restored.state.t_ms)


def test_records_and_counters_survive(midpoint):
    _, path, snap = midpoint
    restored = load_checkpoint(path)

    assert len(restored.records) == snap["n_records"] == 1
    np.testing.assert_allclose(restored.records[0].delays_ms, snap["rec0_delays"])
    assert restored.records[0].msg_id == snap["rec0_msg_id"]
    np.testing.assert_allclose(
        np.asarray(restored.state.bytes_tx), snap["bytes_tx"]
    )
    assert restored._hb_carry_ms == snap["hb_carry_ms"]


def test_config_roundtrip(midpoint):
    sim, path, _ = midpoint
    restored = load_checkpoint(path)
    assert restored.cfg == sim.cfg
    assert restored.params == sim.params
    np.testing.assert_array_equal(
        restored.topology.latency_ms, sim.topology.latency_ms
    )


def test_run_resume_matches_uninterrupted(tmp_path):
    """A run interrupted after message k and resumed from its checkpoint
    produces the same remaining records as the uninterrupted run."""
    cfg_a = _cfg()
    full = Simulator(cfg_a)
    full.run()

    ck = str(tmp_path / "run.npz")
    part = Simulator(_cfg())
    part.warmup()
    part.publish(part.cfg.publisher_id % part.params.n)  # message 1 of 2
    save_checkpoint(part, ck)

    resumed = load_checkpoint(ck)
    resumed.run()

    assert len(resumed.records) == len(full.records) == 2
    for ra, rb in zip(full.records, resumed.records):
        np.testing.assert_allclose(ra.delays_ms, rb.delays_ms)
        assert ra.msg_id == rb.msg_id


def test_subscribe_event_counters_survive(tmp_path):
    """ADVICE r3: the cumulative SUBSCRIBE/UNSUBSCRIBE event counters are
    host-side state (a projection from current membership diverges under
    churn) — a restore must not silently reset them to constructor
    defaults."""
    sim = Simulator(_cfg())
    # startup membership: peers 0-39 join, 40-59 never do
    mask = np.arange(60) < 40
    sim.set_subscribed(mask)
    sim.warmup()
    sim.publish(4)
    # mid-run churn before the save: 5 leave, 10 (re)join
    flip = mask.copy()
    flip[:5] = False
    flip[40:50] = True
    sim.set_subscribed(flip)

    path = str(tmp_path / "subev.npz")
    save_checkpoint(sim, path)
    restored = load_checkpoint(path)

    np.testing.assert_array_equal(restored._sub_events_np, sim._sub_events_np)
    np.testing.assert_array_equal(
        restored._unsub_events_np, sim._unsub_events_np)
    # and the metrics derived from them agree (not the all-ones default)
    assert restored._sub_events_np.sum() == 40 + 10
    assert restored._unsub_events_np.sum() == 5


def test_graph_mismatch_fails_loudly(tmp_path):
    # ADVICE r1: the graph is rebuilt from code on load; if graph
    # construction changed between save and load, the edge-slot state would
    # silently remap — the stored fingerprint must catch it
    import json

    import numpy as np
    import pytest

    from dst_libp2p_test_node_tpu.config.topology import TopoParams
    from dst_libp2p_test_node_tpu.runtime.checkpoint import (
        load_checkpoint, save_checkpoint,
    )
    from dst_libp2p_test_node_tpu.runtime.simulator import (
        ExperimentConfig, Simulator,
    )

    cfg = ExperimentConfig(
        topo=TopoParams(network_size=16, msg_size_bytes=500, messages=1),
        connect_to=4, warmup_s=2.0, seed=0,
    )
    sim = Simulator(cfg)
    sim.warmup()
    path = str(tmp_path / "ck.npz")
    save_checkpoint(sim, path)
    assert load_checkpoint(path) is not None  # clean round trip

    # simulate changed graph-construction code: tamper the fingerprint
    z = dict(np.load(path).items())
    meta = json.loads(bytes(z["meta_json"]).decode())
    meta["graph_sha256"] = "0" * 64
    z["meta_json"] = np.frombuffer(json.dumps(meta).encode(), dtype=np.uint8)
    np.savez_compressed(path, **z)
    with pytest.raises(ValueError, match="graph mismatch"):
        load_checkpoint(path)


def test_warm_carry_survives_checkpoint(tmp_path):
    # v7: the cross-publish warm-start carry is a SimState leaf now; a
    # resumed warm run must continue from the same carry and stay
    # bit-identical to the uninterrupted one
    import numpy as np

    sim = Simulator(_cfg(warm_start=True))
    sim.warmup()
    sim.publish(4)
    path = str(tmp_path / "warm.npz")
    save_checkpoint(sim, path)
    restored = load_checkpoint(path)
    np.testing.assert_array_equal(
        np.asarray(sim.state.warm_offset_ms),
        np.asarray(restored.state.warm_offset_ms))
    a = _finish(sim)
    b = _finish(restored)
    np.testing.assert_array_equal(a.received, b.received)
    np.testing.assert_array_equal(a.delays_ms, b.delays_ms)


def test_pre_v7_checkpoint_loads_with_inf_carry(tmp_path):
    # a v6 snapshot has no warm_offset_ms leaf: loading must default the
    # carry to the INF sentinel ("no usable carry" — the state a fresh run
    # starts in) and resume identically to a cold continuation
    import json

    import numpy as np

    sim = Simulator(_cfg())
    sim.warmup()
    sim.publish(4)
    path = str(tmp_path / "v7.npz")
    save_checkpoint(sim, path)
    # rewrite as a v6 snapshot: drop the carry leaf, stamp the old version
    z = np.load(path)
    meta = json.loads(bytes(z["meta_json"]).decode())
    meta["version"] = 6
    arrays = {k: z[k] for k in z.files
              if k not in ("meta_json", "state/warm_offset_ms")}
    arrays["meta_json"] = np.frombuffer(
        json.dumps(meta).encode(), dtype=np.uint8)
    old = str(tmp_path / "v6.npz")
    np.savez_compressed(old, **arrays)

    restored = load_checkpoint(old)
    assert float(np.asarray(restored.state.warm_offset_ms).min()) > 1e30
    a = _finish(sim)
    b = _finish(restored)
    np.testing.assert_array_equal(a.received, b.received)
    np.testing.assert_array_equal(a.delays_ms, b.delays_ms)


@pytest.mark.parametrize("case", ["v10_inert", "v11_armed"])
def test_repair_leaves_across_a_checkpoint(midpoint, tmp_path, case):
    # a state holds the mesh-repair leaves only where repair is armed
    # (ops/state.py), and a snapshot what the state held. v10_inert: until
    # v10 every snapshot held the five, as init_state made them wherever
    # repair was off; such a file still loads, into a state without them,
    # and resumes bit-exactly. v11_armed: a state that counted repairs keeps
    # its pool and counters through save and load
    import json

    from dst_libp2p_test_node_tpu.ops.state import (
        REPAIR_LEAVES, arm_repair, repair_totals)

    _sim, path, _ = midpoint
    z = np.load(path)
    assert not any(f"state/{k}" in z.files for k in REPAIR_LEAVES)
    restored = load_checkpoint(path)
    assert all(getattr(restored.state, k) is None for k in REPAIR_LEAVES)
    armed = arm_repair(restored.state)
    out = str(tmp_path / f"{case}.npz")
    if case == "v10_inert":
        meta = json.loads(bytes(z["meta_json"]).decode())
        meta["version"] = 10
        arrays = {k: z[k] for k in z.files if k != "meta_json"}
        arrays.update({f"state/{k}": np.asarray(getattr(armed, k))
                       for k in REPAIR_LEAVES})
        arrays["meta_json"] = np.frombuffer(
            json.dumps(meta).encode(), dtype=np.uint8)
        np.savez_compressed(out, **arrays)
        again = load_checkpoint(out)
        assert all(getattr(again.state, k) is None for k in REPAIR_LEAVES)
        assert repair_totals(again.state) == {
            "evictions": 0, "px_grafts": 0, "redials": 0}
        a, b = _finish(restored), _finish(again)
        np.testing.assert_array_equal(a.received, b.received)
        np.testing.assert_array_equal(a.delays_ms, b.delays_ms)
    else:
        restored.state = armed.replace(
            px_pool=armed.px_pool.at[3, 0].set(7),
            starve_hb=armed.starve_hb.at[5].set(2),
            evictions=armed.evictions.at[3].set(4),
            px_grafts=armed.px_grafts.at[9].set(1),
            redials=armed.redials.at[11].set(6))
        save_checkpoint(restored, out)
        again = load_checkpoint(out)
        for k in REPAIR_LEAVES:
            np.testing.assert_array_equal(
                np.asarray(getattr(again.state, k)),
                np.asarray(getattr(restored.state, k)), err_msg=k)
        assert repair_totals(again.state) == {
            "evictions": 4, "px_grafts": 1, "redials": 6}


def test_restored_valid_edge_tracks_restored_subscriptions(tmp_path):
    # the publish path hoists a validity mask from alive&subscribed at
    # construction; load_checkpoint replaces the state AFTER construction,
    # so the mask must be recomputed against the RESTORED vectors — or a
    # peer the checkpoint had unsubscribed would silently keep receiving
    import numpy as np

    sim = Simulator(_cfg())
    sim.warmup()
    sub = np.asarray(sim.state.subscribed).copy()
    sub[7] = False
    sim.set_subscribed(sub)
    path = str(tmp_path / "unsub.npz")
    save_checkpoint(sim, path)
    restored = load_checkpoint(path)
    a = _finish(sim)
    b = _finish(restored)
    assert not a.received[7] and not b.received[7]
    np.testing.assert_array_equal(a.received, b.received)
    np.testing.assert_array_equal(a.delays_ms, b.delays_ms)
