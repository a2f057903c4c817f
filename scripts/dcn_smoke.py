"""Two-process DCN smoke for parallel/sharding.initialize_multihost.

The reference scales across hosts by pointing more Shadow workers / K8s nodes
at the same experiment; the TPU framework's equivalent is a jax.distributed
process group whose global device mesh spans hosts, with the same engine code
running unchanged (SURVEY.md §2 "multi-pod via DCN"). Real multi-host TPU
hardware is not available in this environment, so this smoke proves the
multi-host path end-to-end on the only fabric that exists here: two local
processes, CPU devices, gloo collectives over localhost — the same
jax.distributed machinery a v5e pod slice uses, minus the ICI.

Each process:
  1. joins the group via initialize_multihost (the wrapper under test),
  2. checks the GLOBAL device view spans both processes,
  3. builds the 1-D peer mesh over all global devices (make_peer_mesh),
  4. runs a shard_map psum over the mesh and checks the result — a real
     cross-process collective, the primitive every fixpoint iteration of
     the sharded engine rides on,
  5. runs the REAL fixpoint across the boundary: one full simulation step
     (heartbeat + disseminate(mesh=…) -> converge_sharded) on the global
     mesh, asserting each process's addressable rows equal the
     single-process run at rtol 1e-5 — the cross-process mirror of
     __graft_entry__.dryrun_multichip's equality oracle.

Run:  python scripts/dcn_smoke.py            (spawns both workers, checks both)
      python scripts/dcn_smoke.py --worker I (internal: one group member)
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys

DEVS_PER_PROC = 4
NUM_PROCS = 2

# stderr fragments that mean the coordinator lost the bind race — the only
# failure class worth an automatic relaunch on a fresh port
_BIND_RACE = ("EADDRINUSE", "Address already in use",
              "address already in use")


def free_port() -> int:
    """Bind-probe: let the kernel assign an ephemeral localhost port, read
    it back, release. The window between release and jax.distributed's own
    bind is real but tiny; main() retries the whole launch on EADDRINUSE
    instead of pretending the race away."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def worker(process_id: int) -> None:
    # env must be set before jax import: per-process virtual CPU devices +
    # gloo cross-process collectives
    os.environ["JAX_PLATFORMS"] = "cpu"
    # replace (not prepend) any inherited device-count flag — XLA honors the
    # last occurrence, and test environments commonly pin their own count
    kept = [f for f in os.environ.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")]
    os.environ["XLA_FLAGS"] = " ".join(
        kept + [f"--xla_force_host_platform_device_count={DEVS_PER_PROC}"])
    os.environ["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] = "gloo"

    import jax

    # the environment variables are read when the jax config module
    # defines its flags, which already happened if anything imported jax
    # before this ran; the config pins always land as long as no backend
    # has been created yet
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from dst_libp2p_test_node_tpu.parallel.sharding import (
        initialize_multihost, make_peer_mesh, peer_sharding,
    )

    # the coordinator port is chosen by the launcher's bind probe and
    # threaded through the environment — never hardcoded, so parallel CI
    # shards / stray earlier runs cannot collide on it
    port = int(os.environ["DCN_SMOKE_PORT"])
    pid = initialize_multihost(
        coordinator_address=f"localhost:{port}",
        num_processes=NUM_PROCS,
        process_id=process_id,
    )
    assert pid == process_id, (pid, process_id)
    n_global = len(jax.devices())
    assert n_global == NUM_PROCS * DEVS_PER_PROC, n_global
    assert len(jax.local_devices()) == DEVS_PER_PROC

    mesh = make_peer_mesh()
    n = 64
    sh = peer_sharding(mesh)
    # build the globally-sharded array from per-process local shards
    local_rows = n // NUM_PROCS
    local = np.arange(n, dtype=np.float32)[
        process_id * local_rows:(process_id + 1) * local_rows]
    arr = jax.make_array_from_process_local_data(sh, local, (n,))

    def body(x):
        return jax.lax.psum(x.sum(), "peers") * jnp.ones_like(x)

    from dst_libp2p_test_node_tpu.parallel.sharding import shard_map

    out = jax.jit(shard_map(
        body, mesh=mesh, in_specs=P("peers"), out_specs=P("peers")))(arr)
    # every element is the GLOBAL sum — proof the collective crossed the
    # process boundary (reading this process's local shard suffices)
    expect = float(np.arange(n).sum())
    got = float(np.asarray(out.addressable_shards[0].data)[0])
    assert got == expect, (got, expect)

    # ---- the REAL fixpoint across the process boundary ------------------
    # One full simulation step (heartbeat + disseminate -> converge_sharded)
    # over the global mesh; each process checks its own rows against the
    # single-process run — same seed, same computation, no mesh.
    from __graft_entry__ import _build, _step_fn
    from dst_libp2p_test_node_tpu.parallel.sharding import shard_simulation

    n_peers = 64
    params, state, arrays, topo = _build(n_peers)
    ref_delays, _ = jax.jit(_step_fn(params))(
        state, arrays["conns"], arrays["rev"], arrays["out_mask"],
        topo["stage"], topo["lat_ms"], topo["bw"],
    )
    ref = np.asarray(ref_delays)                     # local, addressable
    ref_recv = np.isfinite(ref) & (ref < 1e30)
    assert ref_recv.sum() > n_peers * 0.9

    state_s, arrays_s, topo_s = shard_simulation(state, arrays, topo, mesh)
    delays, _ = jax.jit(_step_fn(params, mesh=mesh))(
        state_s, arrays_s["conns"], arrays_s["rev"], arrays_s["out_mask"],
        topo_s["stage"], topo_s["lat_ms"], topo_s["bw"],
    )
    delays.block_until_ready()
    checked = 0
    for shard in delays.addressable_shards:
        got_rows = np.asarray(shard.data)
        want_rows = ref[shard.index[0]]
        recv = np.isfinite(want_rows) & (want_rows < 1e30)
        got_recv = np.isfinite(got_rows) & (got_rows < 1e30)
        np.testing.assert_array_equal(got_recv, recv)
        np.testing.assert_allclose(
            got_rows[recv], want_rows[recv], rtol=1e-5)
        checked += got_rows.shape[0]
    assert checked == n_peers // NUM_PROCS, checked

    print(
        f"worker {process_id}: global_devices={n_global} psum={got} "
        f"fixpoint rows={checked} sharded==single-process OK",
        flush=True,
    )


def _launch(port: int) -> tuple[bool, str]:
    """One two-worker launch attempt on `port`; (ok, combined transcript)."""
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(here)
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["DCN_SMOKE_PORT"] = str(port)
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker", str(i)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(NUM_PROCS)
    ]
    ok = True
    transcript = ""
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            transcript += out
            if p.returncode != 0 or "OK" not in out:
                ok = False
    except subprocess.TimeoutExpired:
        # a hung worker must not orphan its sibling (the coordinator port
        # stays bound otherwise and the next run cannot bind it)
        ok = False
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return ok, transcript


def main() -> int:
    pinned = os.environ.get("DCN_SMOKE_PORT")
    attempts = int(os.environ.get("DCN_SMOKE_BIND_RETRIES", "3"))
    ok, transcript = False, ""
    for attempt in range(attempts):
        port = int(pinned) if pinned else free_port()
        ok, transcript = _launch(port)
        sys.stdout.write(transcript)
        if ok:
            break
        raced = any(tok in transcript for tok in _BIND_RACE)
        if pinned or not raced or attempt + 1 == attempts:
            break
        print(f"dcn_smoke: port {port} raced (EADDRINUSE), "
              f"re-probing [{attempt + 1}/{attempts}]", flush=True)
    print("dcn_smoke:", "PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    if "--worker" in sys.argv:
        worker(int(sys.argv[sys.argv.index("--worker") + 1]))
    else:
        sys.exit(main())
