"""Peer-axis sharding: the framework's scale-out story.

The reference scales by spawning more OS processes (one per peer) across
Shadow workers or K8s nodes; its cross-peer traffic rides TCP/QUIC sockets
(SURVEY.md §2 parallelism table). Here the peer axis IS the parallel axis:
every (N, ...) state array shards across TPU chips over a 1-D
`jax.sharding.Mesh` ("peers"), cross-shard mesh edges become XLA collectives
over ICI (gathers through the neighbor index arrays), and multi-host scales
the same mesh over DCN. This is the context-parallel analog the north star
asks for: the 1M-peer adjacency node-sharded across a v5e-8.

Latency/stage constants stay replicated (they are (S+1)^2-tiny); per-peer
rows shard on axis 0. XLA inserts the all-gathers for neighbor lookups; the
explicit shard_map + all_to_all bucketing lives in parallel/exchange.py for
the hand-tuned path.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

shard_map = jax.shard_map


def initialize_multihost(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> int:
    """Join a multi-host JAX run (DCN scale-out; SURVEY.md §2 'multi-pod via
    DCN'). Wraps jax.distributed.initialize: afterwards jax.devices() spans
    every host's chips and make_peer_mesh() builds the global peer mesh —
    per-iteration fixpoint collectives ride ICI within a slice and DCN
    across hosts, with no change to any engine code. Arguments default to
    the standard JAX env vars (JAX_COORDINATOR_ADDRESS etc.) / TPU metadata.
    Returns the process index."""
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return jax.process_index()


def make_peer_mesh(n_devices: int | None = None, platform: str | None = None) -> Mesh:
    """1-D peer mesh over the default backend's devices, or over a specific
    platform's (e.g. "cpu" to get the XLA_FLAGS-forced virtual host devices
    even when an accelerator plugin owns the default backend)."""
    devs = jax.devices(platform)
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), ("peers",))


def peer_sharding(mesh: Mesh) -> NamedSharding:
    """Rows of any (N, ...) peer-major array shard across the mesh."""
    return NamedSharding(mesh, P("peers"))


TRIAL_AXIS = "trials"


def audit_trial_groups(n_devices: int | None = None) -> int:
    """Trial-group count the audit/registry mesh builders use.

    GRAFT_AUDIT_TRIAL_GROUPS overrides it so CI can trace every registered
    window contract on BOTH full-grid aspect ratios (2x4 and 4x2 under 8
    virtual devices) without touching the registry; the default is the
    2-group grid (2 x remaining-devices-per-group), degenerating to 1 on a
    single device. Must divide the device count evenly — same constraint
    make_trial_mesh enforces."""
    import os

    nd = len(jax.devices()) if n_devices is None else n_devices
    env = os.environ.get("GRAFT_AUDIT_TRIAL_GROUPS", "")
    if env:
        groups = int(env)
        if groups < 1 or nd % groups != 0:
            raise ValueError(
                f"GRAFT_AUDIT_TRIAL_GROUPS={groups} must divide the device "
                f"count {nd} evenly")
        return groups
    return 2 if nd >= 2 else 1


def make_trial_mesh(trial_groups: int | None = None,
                    n_devices: int | None = None,
                    platform: str | None = None) -> Mesh:
    """2-D trial x peer device grid for Monte-Carlo campaigns
    (runtime/campaign.py): axis 0 ("trials") partitions the (fraction, seed)
    sweep into independent device groups, axis 1 ("peers") partitions each
    group's peer row space. Both axes are live: the nested window programs
    (campaign.sharded_attack_window and friends) shard stacked trial state
    as P("trials", "peers") and the shared epoch-graph arrays as P("peers"),
    so with >1 peers per group each window body runs peer-partitioned under
    GSPMD instead of replicating the group's submesh. The default is still
    one device per group (trial_groups = all visible devices) — the right
    grid when trials outnumber devices; widen the peer axis (fewer groups)
    when the peer count, not the trial count, is the scale axis."""
    devs = jax.devices(platform)
    if n_devices is not None:
        devs = devs[:n_devices]
    if trial_groups is None:
        trial_groups = len(devs)
    if trial_groups < 1 or len(devs) % trial_groups != 0:
        raise ValueError(
            f"trial_groups {trial_groups} must divide the device count "
            f"{len(devs)} evenly")
    per_group = len(devs) // trial_groups
    grid = np.array(devs).reshape(trial_groups, per_group)
    return Mesh(grid, (TRIAL_AXIS, "peers"))


DCN_AXIS = "dcn"


def make_dcn_mesh(dcn: int | None = None,
                  trial_groups: int | None = None,
                  n_devices: int | None = None,
                  platform: str | None = None) -> Mesh:
    """Three-level dcn x trials x peers grid over the GLOBAL device set.

    The multi-host extension of make_trial_mesh (ROADMAP "go past one
    host"): axis 0 ("dcn") is PROCESS granularity — each dcn block is one
    host's addressable devices, so every "peers"-axis collective the nested
    window programs emit stays strictly inside a host's ICI submesh and
    only trial-axis work (which is embarrassingly parallel) ever spans the
    DCN boundary. Devices are ordered process-major (sorted by
    process_index) so dcn block b == process b's chips — the invariant the
    GA-S006 auditor's block classification and local_trial_submesh both
    rely on. `dcn` defaults to jax.process_count(); `trial_groups` is the
    PER-BLOCK trial-group count (defaults to 2 when the block has >= 2
    devices, mirroring audit_trial_groups)."""
    devs = jax.devices(platform)
    if n_devices is not None:
        devs = devs[:n_devices]
    devs = sorted(devs, key=lambda d: (d.process_index, d.id))
    if dcn is None:
        dcn = jax.process_count()
    if dcn < 1 or len(devs) % dcn != 0:
        raise ValueError(
            f"dcn {dcn} must divide the device count {len(devs)} evenly")
    per_block = len(devs) // dcn
    if trial_groups is None:
        trial_groups = 2 if per_block >= 2 else 1
    if trial_groups < 1 or per_block % trial_groups != 0:
        raise ValueError(
            f"trial_groups {trial_groups} must divide the per-block device "
            f"count {per_block} evenly")
    grid = np.array(devs).reshape(dcn, trial_groups, per_block // trial_groups)
    if dcn == jax.process_count() > 1:
        for b in range(dcn):
            procs = {d.process_index for d in grid[b].flat}
            if len(procs) != 1:
                raise ValueError(
                    f"dcn block {b} spans processes {sorted(procs)}; the "
                    f"DCN axis must be process granularity (peer collectives "
                    f"would cross the DCN boundary)")
    return Mesh(grid, (DCN_AXIS, TRIAL_AXIS, "peers"))


def local_trial_submesh(mesh: Mesh) -> Mesh:
    """This process's 2-D trials x peers submesh of a make_dcn_mesh grid.

    The runtime half of the DCN split: the campaign executes the SAME
    jitted nested window per process on its addressable block (supervisor
    retries, checkpoints, and recovery legs stay process-local), while the
    3-level mesh exists for placement reasoning and the static GA-S006
    audit. On a mesh without a dcn axis this is the identity."""
    if DCN_AXIS not in mesh.axis_names:
        return mesh
    rank = jax.process_index()
    grid = mesh.devices
    for b in range(grid.shape[0]):
        if all(d.process_index == rank for d in grid[b].flat):
            return Mesh(grid[b], (TRIAL_AXIS, "peers"))
    raise ValueError(
        f"no dcn block of {mesh} is wholly addressable by process {rank}")


def trial_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis (stacked-trial) sharding over a make_trial_mesh grid;
    on a 3-level make_dcn_mesh grid the stacked axis splits over dcn AND
    trial groups (dcn-major, matching the seed round-robin)."""
    if DCN_AXIS in mesh.axis_names:
        return NamedSharding(mesh, P((DCN_AXIS, TRIAL_AXIS)))
    return NamedSharding(mesh, P(TRIAL_AXIS))


def nested_sharding(mesh: Mesh) -> NamedSharding:
    """Both-axes sharding for stacked peer-major leaves (T, N, ...): trials
    over the "trials" axis (and the "dcn" axis on a 3-level grid), peer
    rows over each group's "peers" submesh — peer-axis collectives stay
    inside one ICI block by construction."""
    if DCN_AXIS in mesh.axis_names:
        return NamedSharding(mesh, P((DCN_AXIS, TRIAL_AXIS), "peers"))
    return NamedSharding(mesh, P(TRIAL_AXIS, "peers"))


def peer_submesh_sharding(mesh: Mesh) -> NamedSharding:
    """Peer-row sharding of a trial-invariant (N, ...) array on the 2-D
    grid: rows split over the "peers" axis, replicated across trial groups
    (the epoch graph arrays every trial shares)."""
    return NamedSharding(mesh, P("peers"))


def peers_per_group(mesh: Mesh) -> int:
    """Width of the peer submesh inside each trial group (1 on the
    degenerate trials-only grid)."""
    return int(mesh.shape.get("peers", 1))


def nested_batch_shardings(tree, mesh: Mesh, n_rows: int):
    """Sharding pytree for a stacked trial batch (or its eval_shape avals)
    on the nested grid. Rule, by leaf shape: axis 1 == the peer row count
    -> P("trials", "peers") (peer-major state, attacker masks, per-trial
    graph copies); everything else with a leading trial axis -> P("trials")
    (the per-trial scalar clock, PRNG keys, per-round observables). The
    rule is a layout choice, not a semantics choice — GSPMD computes the
    same values under any of these placements."""
    nested = nested_sharding(mesh)
    rows = trial_sharding(mesh)

    def rule(x):
        if getattr(x, "ndim", 0) >= 2 and x.shape[1] == n_rows:
            return nested
        return rows

    return jax.tree_util.tree_map(rule, tree)


def place_trial_batch(stacked, shared: dict, mesh: Mesh,
                      n_rows: int | None = None):
    """Place one stacked trial batch for the sharded campaign window.

    With `n_rows` (the peer row count) the placement is NESTED: stacked
    peer-major leaves shard over both grid axes per nested_batch_shardings
    and the `shared` dict (epoch graph arrays, identical for every trial)
    row-shards over each group's peer submesh. Without it — the legacy
    trial-only layout — stacked leaves shard over "trials" alone and the
    shared arrays replicate. Returns (stacked, shared)."""
    if n_rows is None:
        rows = trial_sharding(mesh)
        rep = replicated(mesh)
        stacked = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, rows), stacked)
        shared = {k: jax.device_put(v, rep) for k, v in shared.items()}
        return stacked, shared
    shardings = nested_batch_shardings(stacked, mesh, n_rows)
    stacked = jax.tree_util.tree_map(jax.device_put, stacked, shardings)
    prow = peer_submesh_sharding(mesh)
    shared = {k: jax.device_put(v, prow) for k, v in shared.items()}
    return stacked, shared


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def reshard_rows(x, mesh: Mesh):
    """Place one (N, ...) leaf row-sharded (host-side state swaps like
    set_subscribed / the multi-topic uplink fold keep leaves aligned with
    the rest of the pytree through this)."""
    return jax.device_put(x, peer_sharding(mesh))


def place_simulation(state, arrays: dict, stage, lat, bw, loss, mesh: Mesh):
    """Constructor-side placement shared by the single- and multi-topic
    simulators: row-axis divisibility check, then shard state/graph/topology
    (rows sharded, the tiny stage matrices replicated). Returns
    (state, arrays, stage, lat, bw, loss)."""
    n_rows = state.mesh_mask.shape[0]
    if n_rows % mesh.devices.size != 0:
        raise ValueError(
            f"peer rows {n_rows} must divide evenly over "
            f"{mesh.devices.size} devices"
        )
    topo = {"stage": stage, "lat": lat, "bw": bw}
    if loss is not None:
        topo["loss"] = loss
    state, arrays, topo = shard_simulation(state, arrays, topo, mesh)
    return (state, arrays, topo["stage"], topo["lat"], topo["bw"],
            topo.get("loss"))


def shard_simulation(state, arrays: dict, topo: dict, mesh: Mesh):
    """Place SimState + graph/topology arrays: peer-major rows sharded,
    scalars/clock/key and the tiny stage matrices replicated."""
    rows = peer_sharding(mesh)
    rep = replicated(mesh)

    def place_state(path, x):
        x = jax.numpy.asarray(x)
        if x.ndim >= 1 and x.shape[0] == state.mesh_mask.shape[0]:
            return jax.device_put(x, rows)
        return jax.device_put(x, rep)

    state = jax.tree_util.tree_map_with_path(place_state, state)
    arrays = {k: jax.device_put(v, rows) for k, v in arrays.items()}
    topo_placed = {}
    for k, v in topo.items():
        sh = rows if (v.ndim >= 1 and v.shape[0] == state.mesh_mask.shape[0]) else rep
        topo_placed[k] = jax.device_put(v, sh)
    return state, arrays, topo_placed


# Fixed lane width for every dcn_allreduce payload. Uniform message sizes
# are load-bearing, not cosmetic: the campaign issues back-to-back reduces
# of different logical widths (fence 1, aggregates 2, wall 1), and on an
# oversubscribed host one rank can enter reduce N+1 while its peer still
# drains reduce N — gloo buffers the early bytes as "unexpected" messages,
# which only works when the posted recv is at least as large as the inbound
# preamble (op.preamble.length <= op.nbytes fails otherwise, killing the
# process group). Padding every call to one width removes the mismatched-
# size class entirely; _dcn_reducer reuse below removes the per-call
# re-jit so all reduces of one op share a single executable/communicator.
_DCN_LANES = 4

_dcn_reducers: dict = {}


def _dcn_reducer(op: str, mesh: Mesh, width: int):
    """One cached jitted reduction per (op, device clique, width)."""
    import jax.numpy as jnp

    key = (op, tuple(d.id for d in mesh.devices.flat), width)
    fn = _dcn_reducers.get(key)
    if fn is None:
        body = (lambda a: jnp.sum(a, axis=0)) if op == "sum" \
            else (lambda a: jnp.max(a, axis=0))
        fn = jax.jit(body, out_shardings=NamedSharding(mesh, P()))
        _dcn_reducers[key] = fn
    return fn


def dcn_allreduce(vec, op: str = "sum") -> np.ndarray:
    """All-reduce a small per-process host vector across every process.

    The campaign's cross-process channel for the few global aggregates
    (trial counts, retry totals, wall max) — everything else merges through
    per-rank artifact files. Each process contributes its vector on its
    first addressable device (identity elements elsewhere); one jitted
    reduction over a 1-D all-devices mesh turns into a single DCN
    all-reduce, and because every process must reach it before any can
    leave, the call doubles as the barrier the rank-file merge needs.
    Payloads are padded to _DCN_LANES-float lanes (see above). Returns the
    reduced vector as float32 numpy; `op` is "sum" or "max"."""
    if op not in ("sum", "max"):
        raise ValueError(f"op must be 'sum' or 'max', got {op!r}")
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    vec = np.asarray(vec, np.float32).reshape(-1)
    size = vec.size
    width = max(_DCN_LANES, -(-size // _DCN_LANES) * _DCN_LANES)
    # identity element per op so the padding lanes never perturb the result
    fill = np.float32(0.0 if op == "sum" else -np.inf)
    padded = np.full(width, fill, np.float32)
    padded[:size] = vec
    idle = np.full_like(padded, fill)
    mesh = Mesh(np.array(devs), ("all",))
    sh = NamedSharding(mesh, P("all"))
    first = jax.local_devices()[0]
    shards = [
        jax.device_put((padded if d == first else idle)[None, :], d)
        for d in jax.local_devices()
    ]
    arr = jax.make_array_from_single_device_arrays(
        (len(devs), width), sh, shards)
    reduced = _dcn_reducer(op, mesh, width)(arr)
    return np.asarray(reduced)[:size]
