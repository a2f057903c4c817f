"""Checkpoint/resume of a running experiment (a deliberate improvement).

The reference has no checkpointing at all — experiments are minutes long and
crashed runs are simply re-run (SURVEY.md §5 "Checkpoint / resume: absent
entirely"). At the 1M-peer scale this framework targets, a run is hours of
device time, so the simulator snapshots everything an experiment needs to
resume bit-exactly:

  - the device-side SimState pytree (mesh, scores, counters, sim clock, and
    the JAX PRNG key — restoring it resumes the *same* random stream),
  - the host-side experiment position (heartbeat carry, msgId RNG state,
    completed MessageRecords),
  - the full ExperimentConfig and the dense topology matrices (so a
    GML-ingested topology restores exactly even without the GML file).

Format: one .npz (arrays, including every SimState leaf via
flax.serialization) + an embedded JSON string (config/scalars). No
framework-specific on-disk layout to version-skew against; `numpy.load`
can open a checkpoint anywhere.

Resume equivalence is exact: continuing a restored simulator produces the
same heartbeat decisions, the same message ids, and the same delay arrays
as the uninterrupted run (tests/test_checkpoint.py).
"""

from __future__ import annotations

import hashlib
import json
import os
from dataclasses import asdict

import numpy as np

from ..config.env import GossipSubParams
from ..config.topology import Topology, TopoParams
from .simulator import ExperimentConfig, MessageRecord, Simulator

FORMAT_VERSION = 11  # bump on any SimState layout change (v11: the
#                     mesh-repair leaves are in a snapshot exactly where the
#                     state held them, a run with repair armed; a v8..v10
#                     snapshot always holds them, as init_state made them
#                     wherever repair was inert, and the loader leaves them
#                     out (restore_state); v10: resident
#                     service mode — snapshots may carry a `service_json`
#                     sidecar (pending publish queue + counters, read only
#                     by NodeService.restore) and a meta "kind" that extends
#                     the format to MultiTopicSimulator (host/subscribed_np
#                     + per-record records/topic_idx); single-topic v9
#                     snapshots load unchanged and plain load_checkpoint
#                     ignores the sidecar; v9: optional
#                     kad/* leaves — a campaign snapshot taken with the DHT
#                     adversary armed embeds the per-trial KadState so the
#                     poisoned routing tables are auditable offline; the
#                     loader IGNORES them (campaign resume re-derives the
#                     DHT deterministically from (seed, dht config)), so
#                     v8 snapshots load unchanged; v8: mesh-repair
#                     leaves px_pool/starve_hb/evictions/px_grafts/redials;
#                     v7: warm_offset_ms cross-publish warm-start carry,
#                     defaulted to INF = "no usable carry"; v6 added
#                     per-record answer_wait_max_ms, read tolerantly)


def _graph_hash(graph) -> str:
    """Fingerprint of the connection graph the state arrays index into.
    The graph is rebuilt from (n, connect_to, seed) on load, so resume is
    bit-exact only while graph construction is code-identical — mesh_mask/
    backoff/fmd columns refer to neighbor SLOTS, and a silently different
    graph would remap every edge. The hash makes that failure loud."""
    h = hashlib.sha256()
    for arr in (graph.conns, graph.rev, graph.out_mask):
        a = np.ascontiguousarray(np.asarray(arr))
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()

_TOPO_KEYS = ("latency_ms", "bw_up_mbit", "packet_loss", "stage_of_peer")


def _records_arrays(records: list[MessageRecord]) -> dict:
    if not records:
        return {}
    return {
        "records/msg_id": np.asarray([r.msg_id for r in records], dtype=np.int64),
        "records/publisher": np.asarray([r.publisher for r in records], dtype=np.int64),
        "records/t0_ms": np.asarray([r.t0_ms for r in records], dtype=np.float64),
        "records/ihave": np.asarray([r.ihave for r in records], dtype=np.int64),
        "records/iwant": np.asarray([r.iwant for r in records], dtype=np.int64),
        "records/answer_wait_max_ms": np.asarray(
            [r.answer_wait_max_ms for r in records], dtype=np.float64),
        "records/delays_ms": np.stack([r.delays_ms for r in records]),
        "records/received": np.stack([r.received for r in records]),
        "records/sends": np.stack([r.sends for r in records]),
        "records/copies_rx": np.stack([r.copies_rx for r in records]),
    }


def _records_from_arrays(z) -> list[MessageRecord]:
    if "records/msg_id" not in z:
        return []
    n = z["records/msg_id"].shape[0]
    return [
        MessageRecord(
            msg_id=int(z["records/msg_id"][i]),
            publisher=int(z["records/publisher"][i]),
            t0_ms=float(z["records/t0_ms"][i]),
            delays_ms=z["records/delays_ms"][i],
            received=z["records/received"][i],
            sends=z["records/sends"][i],
            copies_rx=z["records/copies_rx"][i],
            ihave=int(z["records/ihave"][i]),
            iwant=int(z["records/iwant"][i]),
            # absent in pre-r5 checkpoints: exact mode's bar is 0.0
            answer_wait_max_ms=(
                float(z["records/answer_wait_max_ms"][i])
                if "records/answer_wait_max_ms" in z else 0.0),
        )
        for i in range(n)
    ]


def save_checkpoint(sim, path: str, kad_state=None,
                    service_meta: dict | None = None) -> None:
    """Snapshot a Simulator or MultiTopicSimulator to `path` (.npz).

    `kad_state`: optional ops.kad.KadState. Campaign trials running with
    the DHT adversary armed pass their per-trial Kademlia state so the
    poisoned routing tables travel with the snapshot (offline audit,
    `rtable_poison_frac` recomputation). Resume does NOT read these
    leaves — the campaign re-derives the DHT from (seed, dht config).

    `service_meta`: optional strict-JSON dict from the resident NodeService
    (pending publish queue, counters, fairness cursor). Stored as a sidecar
    read only by NodeService.restore; load_checkpoint ignores it."""
    from flax import serialization

    multitopic = hasattr(sim, "topic_index")
    meta = {
        "version": FORMAT_VERSION,
        "kind": "multitopic" if multitopic else "single",
        "graph_sha256": _graph_hash(sim.graph),
        "cfg": asdict(sim.cfg),
        "hb_carry_ms": sim._hb_carry_ms,
        "msg_rng_state": sim._msg_rng.bit_generator.state,
        "t_ms": float(sim.state.t_ms),
    }
    arrays: dict = {}
    if multitopic:
        # the stacked sim has no publisher-rotation cursor or SUBSCRIBE
        # event counters; its host extras are the subscription draw and the
        # per-record topic routing
        arrays["host/subscribed_np"] = sim.subscribed_np
        topic_of = {t: i for i, t in enumerate(sim.cfg.topics)}
        arrays.update(_records_arrays([rec for _, rec in sim.records]))
        if sim.records:
            arrays["records/topic_idx"] = np.asarray(
                [topic_of[t] for t, _ in sim.records], dtype=np.int64)
    else:
        meta["last_msg_id"] = sim._last_msg_id
        # host-side counters that are NOT SimState leaves: cumulative
        # SUBSCRIBE/UNSUBSCRIBE control-message events (a projection from
        # current state diverges under churn — simulator.py set_subscribed)
        arrays["host/sub_events"] = sim._sub_events_np
        arrays["host/unsub_events"] = sim._unsub_events_np
        arrays.update(_records_arrays(sim.records))
    arrays["meta_json"] = np.frombuffer(
        json.dumps(meta, allow_nan=False).encode(), dtype=np.uint8)
    for k, v in serialization.to_state_dict(sim.state).items():
        if v is not None:  # a repair leaf of a state that holds none
            arrays[f"state/{k}"] = np.asarray(v)
    topo = sim.topology
    for k in _TOPO_KEYS:
        arrays[f"topo/{k}"] = np.asarray(getattr(topo, k))
    if kad_state is not None:
        for k, v in serialization.to_state_dict(kad_state).items():
            arrays[f"kad/{k}"] = np.asarray(v)
    if service_meta is not None:
        arrays["service_json"] = np.frombuffer(
            json.dumps(service_meta, allow_nan=False).encode(),
            dtype=np.uint8)
    # atomic replace: a crash mid-write (the exact event checkpoints exist
    # to survive) must not truncate the previous good snapshot
    tmp = f"{path}.tmp"
    with open(tmp, "wb") as f:
        np.savez_compressed(f, **arrays)
    os.replace(tmp, path)


def restore_state(state, z, version: int):
    """The `state/` leaves of the open snapshot `z` restored into `state`,
    the fresh one of the rebuilt simulator (made for inert repair: no
    config arms it). A v11 snapshot holds the repair leaves exactly where
    the run had armed repair: they are restored, over arm_repair's. Up to
    v10 every snapshot held them and almost every one as init_state made
    them: left out."""
    from flax import serialization

    from ..ops.state import REPAIR_LEAVES, arm_repair

    sd = {k.split("/", 1)[1]: z[k] for k in z.files if k.startswith("state/")}
    if version >= 11 and "px_pool" in sd:
        state = arm_repair(state)
    else:
        sd.update(dict.fromkeys(REPAIR_LEAVES))
    if "warm_offset_ms" not in sd:
        # pre-v7 snapshot: no warm-start carry was recorded. INF = "no
        # usable carry" — the next publish simply runs cold, identical to
        # a fresh run's first message.
        sd["warm_offset_ms"] = np.full(
            state.warm_offset_ms.shape, 3.4e38, dtype=np.float32)
    return serialization.from_state_dict(state, sd)


def load_service_meta(path: str) -> dict:
    """Read the resident-service sidecar out of a checkpoint; {} when the
    snapshot was written without one (plain sim checkpoints)."""
    z = np.load(path)
    if "service_json" not in z:
        return {}
    return json.loads(bytes(z["service_json"]).decode())


def load_checkpoint(path: str, mesh=None) -> Simulator:
    """Rebuild a Simulator (or MultiTopicSimulator, for snapshots stamped
    kind="multitopic") that continues exactly where `path` left off.

    `mesh`: re-shard the restored state over this device mesh (a sharded
    run does NOT remember its mesh — device topology is a property of the
    resuming host, not of the experiment)."""
    z = np.load(path)
    meta = json.loads(bytes(z["meta_json"]).decode())
    if meta["version"] not in (5, 6, 7, 8, 9, 10, FORMAT_VERSION):
        # v5..v10 differ only by leaves with safe fresh-run defaults:
        # per-record answer_wait (record reader), the warm-start carry and
        # the mesh-repair leaves (restore_state), v9's write-only kad/*
        # extras, and v10's service sidecar / multitopic kind — accept all
        raise ValueError(
            f"checkpoint format {meta['version']} != supported {FORMAT_VERSION}"
        )
    if meta.get("kind", "single") == "multitopic":
        return _load_multitopic(z, meta, mesh)
    cfg_d = dict(meta["cfg"])
    topo_p = TopoParams(**cfg_d.pop("topo"))
    gs = GossipSubParams(**cfg_d.pop("gossipsub"))
    cfg = ExperimentConfig(topo=topo_p, gossipsub=gs, **cfg_d)
    topology = Topology(
        topo_p, *(z[f"topo/{k}"] for k in _TOPO_KEYS)
    )
    sim = Simulator(cfg, topology=topology, mesh=mesh)
    got = _graph_hash(sim.graph)
    want = meta.get("graph_sha256", "")
    if want and got != want:
        raise ValueError(
            "checkpoint graph mismatch: the rebuilt connection graph "
            f"(sha256 {got[:12]}…) differs from the one the checkpoint was "
            f"written against ({want[:12]}…). Graph-construction code "
            "changed between save and load; the restored edge-slot state "
            "would silently refer to different edges."
        )
    sim.state = restore_state(sim.state, z, meta["version"])
    # the publish-path fanout decision reads a host mirror of subscription
    sim._subscribed_np = np.asarray(sim.state.subscribed).copy()
    sim._sub_events_np = np.asarray(z["host/sub_events"]).copy()
    sim._unsub_events_np = np.asarray(z["host/unsub_events"]).copy()
    if mesh is not None:
        # from_state_dict replaced the constructor's sharded leaves with host
        # arrays; re-place them row-sharded (graph/topology arrays were
        # already placed by the constructor)
        from ..parallel.sharding import shard_simulation

        sim.state, _, _ = shard_simulation(sim.state, {}, {}, mesh)
    # the constructor hoisted _valid_edge from its FRESH state; recompute it
    # against the restored alive/subscribed vectors or the publish path would
    # route through peers the checkpoint had unsubscribed
    if sim._valid_edge is not None:
        sim._valid_edge = sim._compute_valid_edge()
    sim._hb_carry_ms = float(meta["hb_carry_ms"])
    sim._msg_rng.bit_generator.state = meta["msg_rng_state"]
    sim._last_msg_id = int(meta.get("last_msg_id", -1))
    sim.records = _records_from_arrays(z)
    return sim


def _load_multitopic(z, meta: dict, mesh):
    """kind="multitopic" restore path: same contract as the single-topic
    branch — rebuild from config, verify the physical graph hash, replace
    the stacked state leaves, restore the host extras."""
    from .multitopic import MultiTopicConfig, MultiTopicSimulator

    cfg_d = dict(meta["cfg"])
    topo_p = TopoParams(**cfg_d.pop("topo"))
    gs = GossipSubParams(**cfg_d.pop("gossipsub"))
    cfg_d["topics"] = tuple(cfg_d["topics"])
    cfg = MultiTopicConfig(topo=topo_p, gossipsub=gs, **cfg_d)
    topology = Topology(topo_p, *(z[f"topo/{k}"] for k in _TOPO_KEYS))
    sim = MultiTopicSimulator(cfg, topology=topology, mesh=mesh)
    got = _graph_hash(sim.graph)
    want = meta.get("graph_sha256", "")
    if want and got != want:
        raise ValueError(
            "checkpoint graph mismatch: the rebuilt connection graph "
            f"(sha256 {got[:12]}…) differs from the one the checkpoint was "
            f"written against ({want[:12]}…)."
        )
    sim.state = restore_state(sim.state, z, meta["version"])
    sim.subscribed_np = np.asarray(z["host/subscribed_np"]).copy()
    if mesh is not None:
        from ..parallel.sharding import shard_simulation

        sim.state, _, _ = shard_simulation(sim.state, {}, {}, mesh)
    sim._hb_carry_ms = float(meta["hb_carry_ms"])
    sim._msg_rng.bit_generator.state = meta["msg_rng_state"]
    recs = _records_from_arrays(z)
    if recs:
        idx = z["records/topic_idx"]
        sim.records = [(cfg.topics[int(idx[i])], r)
                       for i, r in enumerate(recs)]
    else:
        sim.records = []
    return sim
