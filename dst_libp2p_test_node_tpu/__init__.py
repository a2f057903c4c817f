"""dst-libp2p-test-node-tpu: a TPU-native DST (distributed systems testing) framework.

Re-implements the capabilities of vacp2p/dst-libp2p-test-node — a libp2p
GossipSub / Kademlia / connection-manager / service-discovery test harness
driven by the Shadow network simulator — as a single JAX program:

- every simulated peer is a row of peer-major state arrays (the reference
  spawns one OS process per peer: /root/reference/shadow/topogen.py:102-122);
- the static connection graph is a fixed-capacity padded neighbor list and
  the GossipSub mesh is a boolean mask over those edges;
- heartbeat mesh maintenance (graft/prune/score-decay) is a `lax.scan` step;
- message dissemination is an earliest-arrival-time min-relaxation fixpoint
  (scatter-min over mesh edges with uplink serialization and per-stage link
  latency) instead of Shadow's per-packet discrete event queue;
- peers shard across TPU chips via `jax.sharding.Mesh` + `shard_map`; cross
  shard mesh edges resolve with XLA collectives over ICI.

The *surfaces* of the reference are preserved exactly: the env-var config
(PEERS/CONNECTTO/FRAGMENTS/MUXER/GOSSIPSUB_*...), the topogen CLI and its
GML + shadow.yaml outputs, the HTTP /publish control endpoint, the
Prometheus metric names, and the `"<msgId> milliseconds: <ms>"` stdout line
format consumed by the reference's awk summaries.
"""

import time

# the first mark of runtime/profiling.process_record(); nothing heavier is
# imported here, so it stands before jax's import
IMPORTED_AT = time.perf_counter()

__version__ = "0.1.0"
