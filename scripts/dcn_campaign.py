"""Two-process DCN campaign launcher: the engine's end-to-end proof.

Drives runtime/campaign.run_campaign(dcn=...) the way a multi-host pod
would — two local jax.distributed processes, 4 virtual CPU devices each,
gloo collectives over a bind-probed localhost port — and holds the result
against the single-process nested campaign on the SAME total work
(1 process x 8 devices, 2x4 trial grid):

  - merged observables must be IDENTICAL field-for-field (wall-clock
    excluded): the DCN boundary moves placement, never numerics;
  - scaling efficiency = dcn_trials_per_s / single_trials_per_s is
    reported (same device count on
    both sides, so 1.0 is the ideal and the process split + rank merge is
    the only overhead being measured).

The launcher writes one strict-JSON result file (--out) consumed by
tests/test_dcn_smoke.py and the CI smoke job.

Run:  python scripts/dcn_campaign.py --out /tmp/dcn.json
      python scripts/dcn_campaign.py --worker I ... (internal: one rank)
      python scripts/dcn_campaign.py --single ...   (internal: reference)
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

from dcn_smoke import _BIND_RACE, free_port  # scripts/ sibling

DEVS_PER_PROC = 4
NUM_PROCS = 2

# the merged artifact and the single-process reference must agree on every
# field EXCEPT the timing ones (and the derived throughput)
_TIMING_KEYS = ("wall_s", "trials_per_s")


def _pin_backend(n_devices: int, gloo: bool,
                 cache_dir: str | None = None) -> None:
    """CPU backend with `n_devices` virtual devices (+ gloo collectives for
    the multi-process ranks). Must run before the first backend use; the
    config pins hold even where jax was imported, and read the env vars,
    before this ran (see scripts/dcn_smoke.py). `cache_dir` arms the persistent XLA
    compilation cache — the bench probe runs min-of-3 against one shared
    cache so the throughput it gates is steady-state, not cold-compile
    (the tiny CPU-smoke grid is otherwise compile-bound and the two ranks
    contend for compile threads)."""
    kept = [f for f in os.environ.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")]
    os.environ["XLA_FLAGS"] = " ".join(
        kept + [f"--xla_force_host_platform_device_count={n_devices}"])

    import jax

    jax.config.update("jax_platforms", "cpu")
    if gloo:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    if cache_dir:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _campaign_cfg(args, checkpoint_dir: str | None):
    from dst_libp2p_test_node_tpu.config.topology import TopoParams
    from dst_libp2p_test_node_tpu.runtime.campaign import (
        CampaignConfig,
        attack_gossipsub,
    )
    from dst_libp2p_test_node_tpu.runtime.simulator import ExperimentConfig

    exp = ExperimentConfig(
        topo=TopoParams(network_size=args.n, anchor_stages=2,
                        min_bandwidth=50, max_bandwidth=150, min_latency=40,
                        max_latency=130, msg_size_bytes=2000, messages=2,
                        delay_seconds=1.0),
        connect_to=8, gossipsub=attack_gossipsub(), warmup_s=8.0, seed=0)
    return CampaignConfig(
        fractions=tuple(float(f) for f in args.fractions.split(",")),
        seeds=tuple(range(args.seeds)),
        experiment=exp,
        attack_heartbeats=args.heartbeats,
        checkpoint_dir=checkpoint_dir,
    )


def worker(args) -> None:
    _pin_backend(DEVS_PER_PROC, gloo=True, cache_dir=args.cache_dir)

    import jax

    from dst_libp2p_test_node_tpu.parallel.sharding import (
        initialize_multihost,
        make_dcn_mesh,
    )

    # join the process group BEFORE anything touches the backend: a gloo
    # CPU client needs the distributed runtime client at creation time,
    # and importing the engine (module-level jnp constants) creates it
    port = int(os.environ["DCN_CAMPAIGN_PORT"])
    pid = initialize_multihost(
        coordinator_address=f"localhost:{port}",
        num_processes=NUM_PROCS,
        process_id=args.worker,
    )
    assert pid == args.worker, (pid, args.worker)
    assert len(jax.devices()) == NUM_PROCS * DEVS_PER_PROC

    from dst_libp2p_test_node_tpu.runtime.campaign import run_campaign

    mesh = make_dcn_mesh()
    if args.warmup:
        # untimed warm-up sweep into a throwaway checkpoint dir: fills the
        # in-process jit cache so the timed pass below measures STEADY-STATE
        # engine throughput (execution + barriers + merge), not XLA
        # compile/cache-deserialization — the quantity the bench tripwire
        # and its min-of-3 are defined over
        run_campaign(_campaign_cfg(args, os.path.join(args.workdir,
                                                      "dcn_warm")),
                     dcn=mesh)
    cfg = _campaign_cfg(args, os.path.join(args.workdir, "dcn"))
    res = run_campaign(cfg, dcn=mesh)
    print(f"worker {args.worker}: trials={len(res.trials)} "
          f"wall={res.wall_s:.2f}s merged OK", flush=True)


def single(args) -> None:
    _pin_backend(NUM_PROCS * DEVS_PER_PROC, gloo=False,
                 cache_dir=args.cache_dir)

    from dst_libp2p_test_node_tpu.parallel.sharding import make_trial_mesh
    from dst_libp2p_test_node_tpu.runtime.campaign import run_campaign

    mesh = make_trial_mesh(2)
    if args.warmup:
        warm = os.path.join(args.workdir, "single_ckpt_warm")
        os.makedirs(warm, exist_ok=True)
        run_campaign(_campaign_cfg(args, warm), trial_mesh=mesh)
    ckpt = os.path.join(args.workdir, "single_ckpt")
    os.makedirs(ckpt, exist_ok=True)
    cfg = _campaign_cfg(args, ckpt)
    res = run_campaign(cfg, trial_mesh=mesh)
    out = os.path.join(args.workdir, "single.json")
    with open(f"{out}.tmp", "w") as f:
        json.dump(res.to_dict(), f, allow_nan=False, sort_keys=True, indent=2)
    os.replace(f"{out}.tmp", out)
    print(f"single: trials={len(res.trials)} wall={res.wall_s:.2f}s OK",
          flush=True)


def _spawn(cmd_args: list[str], env: dict) -> subprocess.Popen:
    here = os.path.dirname(os.path.abspath(__file__))
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)] + cmd_args,
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=here)


def _passthrough(args) -> list[str]:
    out = ["--workdir", args.workdir, "--n", str(args.n),
           "--seeds", str(args.seeds), "--fractions", args.fractions,
           "--heartbeats", str(args.heartbeats)]
    if args.warmup:
        out += ["--warmup"]
    if args.cache_dir:
        out += ["--cache-dir", args.cache_dir]
    return out


def _launch_ranks(args, env: dict, port: int) -> tuple[bool, str]:
    env = dict(env)
    env["DCN_CAMPAIGN_PORT"] = str(port)
    procs = [_spawn(["--worker", str(i)] + _passthrough(args), env)
             for i in range(NUM_PROCS)]
    ok, transcript = True, ""
    try:
        for p in procs:
            out, _ = p.communicate(timeout=args.timeout)
            transcript += out
            if p.returncode != 0 or "OK" not in out:
                ok = False
    except subprocess.TimeoutExpired:
        ok = False
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return ok, transcript


def _strip_timing(artifact: dict) -> dict:
    out = {k: v for k, v in artifact.items() if k not in _TIMING_KEYS}
    out["trials"] = [{k: v for k, v in t.items() if k != "wall_s"}
                    for t in artifact["trials"]]
    return out


def main() -> int:
    args = _parse(require_out=True)
    workdir = args.workdir or tempfile.mkdtemp(prefix="dcn_campaign_")
    args.workdir = workdir
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(here)
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

    # ---- two-process DCN run (bind-probed port, EADDRINUSE retry) -------
    attempts = int(os.environ.get("DCN_SMOKE_BIND_RETRIES", "3"))
    ok, transcript = False, ""
    for attempt in range(attempts):
        port = free_port()
        ok, transcript = _launch_ranks(args, env, port)
        sys.stdout.write(transcript)
        if ok or not any(tok in transcript for tok in _BIND_RACE):
            break
        print(f"dcn_campaign: port {port} raced, re-probing "
              f"[{attempt + 1}/{attempts}]", flush=True)
    if not ok:
        print("dcn_campaign: FAIL (workers)")
        return 1

    # ---- single-process reference on the same total work ----------------
    p = _spawn(["--single"] + _passthrough(args), env)
    out, _ = p.communicate(timeout=args.timeout)
    sys.stdout.write(out)
    if p.returncode != 0 or "OK" not in out:
        print("dcn_campaign: FAIL (single-process reference)")
        return 1

    with open(os.path.join(workdir, "dcn", "dcn_merged.json")) as f:
        dcn = json.load(f)
    with open(os.path.join(workdir, "single.json")) as f:
        ref = json.load(f)

    identical = _strip_timing(dcn) == _strip_timing(ref)
    dcn_tps = float(dcn["trials_per_s"])
    single_tps = float(ref["trials_per_s"])
    # the raw ratio is capped by HOST parallelism, not by the engine: two
    # ranks on one core serialize no matter how good the orchestration is.
    # ideal_scaling is that cap (1.0 on any >=2-core host); the normalized
    # efficiency judges the engine against what the host can physically
    # deliver, so the bench gate means the same thing on a 1-core smoke
    # container and a many-core CI runner
    cores = os.cpu_count() or 1
    ideal = min(cores, NUM_PROCS) / NUM_PROCS
    result = {
        "bit_identical": identical,
        "trials": len(dcn["trials"]),
        "nproc": NUM_PROCS,
        "devs_per_proc": DEVS_PER_PROC,
        "network_size": dcn["network_size"],
        "host_cores": cores,
        "ideal_scaling": ideal,
        "dcn_wall_s": dcn["wall_s"],
        "single_wall_s": ref["wall_s"],
        "dcn_trials_per_s": dcn_tps,
        "single_trials_per_s": single_tps,
        "scaling_efficiency": dcn_tps / single_tps,
        "scaling_efficiency_normalized": dcn_tps / single_tps / ideal,
        "honest_coverage_min": min(
            t["honest_coverage"] for t in dcn["trials"]),
    }
    with open(f"{args.out}.tmp", "w") as f:
        json.dump(result, f, allow_nan=False, sort_keys=True, indent=2)
    os.replace(f"{args.out}.tmp", args.out)
    print(f"dcn_campaign: identical={identical} "
          f"efficiency={result['scaling_efficiency']:.3f} "
          f"(normalized {result['scaling_efficiency_normalized']:.3f} "
          f"on {cores} cores) "
          f"dcn={dcn_tps:.3f}/s single={single_tps:.3f}/s")
    print("dcn_campaign:", "PASS" if identical else "FAIL")
    return 0 if identical else 1


def _parse(require_out: bool = False):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--worker", type=int, default=None)
    ap.add_argument("--single", action="store_true")
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--out", default=None, required=require_out)
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--fractions", default="0.0,0.2")
    ap.add_argument("--heartbeats", type=int, default=4)
    ap.add_argument("--cache-dir", default=None)
    ap.add_argument("--warmup", action="store_true",
                    help="one untimed sweep first; the reported walls then "
                         "measure steady-state execution, not compile")
    ap.add_argument("--timeout", type=float, default=420.0)
    return ap.parse_args()


if __name__ == "__main__":
    _args = _parse()
    if _args.worker is not None:
        worker(_args)
    elif _args.single:
        single(_args)
    else:
        sys.exit(main())
