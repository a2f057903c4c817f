"""Microbenchmark + kernel autotune harness (ISSUE 16).

arXiv:1912.03413's methodology, applied to this repo's registered hot
entrypoints: measure where each compiled program sits on the roofline
BEFORE optimizing it, and pick kernel block sizes from measurement rather
than folklore. Three sections, each emitting strict JSON:

  rooflines      per-EntrypointContract {flops, hbm_bytes,
                 peak_memory_bytes, retraces} from runtime/profiling.py,
                 EXTENDED with a measured min-of-k wall and the derived
                 achieved GFLOP/s, HBM GB/s and arithmetic intensity —
                 the two coordinates that place the program on the
                 roofline plot.
  kernel_sweep   explicit row-block sweep over the Pallas kernels
                 (native/vmem_gather.py, native/score_update.py): every
                 power-of-two block that tiles the rung is timed via the
                 kernels' `block_rows` override, and the winners become a
                 `tuned` block-size table. `--install` writes it to
                 native/tuned.json (see native/tuned.py), which the
                 kernels' block choosers consult before their heuristic.
                 On CPU the sweep runs `interpret=True` — a functional
                 sweep (CI exercises the full path and the artifact
                 schema), not a performance claim; only a TPU run's
                 table is worth installing.
  packed_state_ab
                 the SimParams.packed_state A/B (bf16 per-edge cost
                 tables on the receiver-side fixpoint): one timed publish
                 per setting at the requested rung, plus a lowered-HLO
                 comparison that reports whether the flag changed the
                 compiled program AT ALL (below the row-gather budget on
                 a single device the receiver-side formulation is not
                 dispatched and the flag is dead). The recorded verdict
                 keeps the default off: exact delivery is the model of
                 record and bf16 packing breaks its bit guarantee, so a
                 wall-clock win alone can never flip the default.

CLI: `python -m dst_libp2p_test_node_tpu microbench [--out FILE]
[--install] [--only PREFIX] [--no-retraces] [--no-rooflines]
[--no-sweep] [--no-packed] [--sweep-rows N] [--sweep-cap C]
[--packed-n N] [--reps K]`.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import time

# the sweep's block ceiling mirrors the kernels' own VMEM ceiling
_MAX_BLOCK = 512


def _min_wall(thunk, reps: int) -> float:
    """Min-of-reps wall of an already-warm thunk (the bench's
    contention-robust estimator)."""
    import jax

    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = time.time()
        jax.block_until_ready(thunk())
        best = min(best, time.time() - t0)
    return best


def registry_rooflines(name_prefix: str | None = None,
                       with_retraces: bool = True, reps: int = 3) -> dict:
    """The profiling.roofline block per contract, extended with a measured
    wall and the derived roofline coordinates. A contract that cannot
    build/run on this backend degrades to an `error` entry (same contract
    as roofline() itself — the harness must keep emitting)."""
    import jax

    from ..analysis.registry import default_contracts
    from .profiling import roofline

    contracts = default_contracts()
    if name_prefix:
        contracts = [c for c in contracts if c.name.startswith(name_prefix)]
    block = roofline(contracts, with_retraces=with_retraces,
                     name_prefix=name_prefix or "")
    for c in contracts:
        entry = block.get(c.name)
        if entry is None or "error" in entry:
            continue
        try:
            thunk = c.build().thunk()
            jax.block_until_ready(thunk())            # warm (compile)
            wall = _min_wall(thunk, reps)
            entry["wall_s"] = round(wall, 6)
            flops = entry.get("flops")
            hbm = entry.get("hbm_bytes")
            if flops and wall > 0:
                entry["gflops_per_s"] = round(flops / wall / 1e9, 3)
            if hbm and wall > 0:
                entry["hbm_gbytes_per_s"] = round(hbm / wall / 1e9, 3)
            if flops and hbm:
                entry["arith_intensity"] = round(flops / hbm, 4)
        except Exception as e:  # noqa: BLE001 — per-entry degradation
            entry["error"] = repr(e)[:200]
    return block


def _candidate_blocks(n_rows: int, interpret: bool) -> list[int]:
    """Every power-of-two row block <= _MAX_BLOCK that tiles n_rows
    exactly; the real kernel additionally needs >= 8 rows to meet the
    (8, 128) f32 tiling floor (interpret mode has no such floor)."""
    out = []
    b = 1
    while b <= _MAX_BLOCK:
        if n_rows % b == 0 and (interpret or b >= 8):
            out.append(b)
        b *= 2
    return out


def sweep_kernels(n_rows: int = 4096, cap: int = 16, reps: int = 5,
                  interpret: bool | None = None) -> dict:
    """Time every candidate row block of both Pallas kernels at one
    (n_rows, cap) rung via their `block_rows` override; the per-kernel
    winner is the tuned table entry."""
    import jax
    import jax.numpy as jnp

    from ..native.score_update import score_update
    from ..native.vmem_gather import vmem_gather
    from ..ops.state import SimParams

    if interpret is None:
        # off-TPU the real kernel cannot compile; the interpreter run is
        # a functional sweep, flagged as such in the artifact
        interpret = jax.default_backend() != "tpu"

    t = jnp.arange(n_rows, dtype=jnp.float32) * 0.5
    src = (jnp.arange(n_rows * cap, dtype=jnp.int32)
           .reshape(n_rows, cap) * 7) % n_rows
    params = SimParams(n=n_rows, capacity=cap, slow_weight=-10.0)
    fmd = (jnp.arange(n_rows * cap, dtype=jnp.float32)
           .reshape(n_rows, cap) % 13) * 0.3
    slow = (jnp.arange(n_rows * cap, dtype=jnp.float32)
            .reshape(n_rows, cap) % 7) * 0.2

    calls = {
        "vmem_gather": lambda b: vmem_gather(
            t, src, interpret=interpret, block_rows=b),
        "score_update": lambda b: score_update(
            fmd, slow, 0.9, 0.8, params, interpret=interpret, block_rows=b),
    }
    out: dict = {"n_rows": n_rows, "cap": cap, "interpret": interpret,
                 "kernels": {}}
    for name, call in calls.items():
        cands: dict = {}
        best_b, best_w = None, float("inf")
        for b in _candidate_blocks(n_rows, interpret):
            try:
                jax.block_until_ready(call(b))        # warm (compile)
                wall = _min_wall(lambda: call(b), reps)  # noqa: B023
            except Exception as e:  # noqa: BLE001 — candidate degrades
                cands[str(b)] = {"error": repr(e)[:120]}
                continue
            cands[str(b)] = round(wall, 6)
            if wall < best_w:
                best_b, best_w = b, wall
        out["kernels"][name] = {
            "candidates": cands,
            "best_block_rows": best_b,
            "best_wall_s": (round(best_w, 6) if best_b is not None
                            else None),
        }
    return out


def packed_state_ab(n: int = 100_000, connect_to: int = 10, reps: int = 3,
                    payload_bytes: int = 15_000, warm_hb: int = 10) -> dict:
    """SimParams.packed_state A/B at one rung: timed publish walls for
    off/on plus a lowered-program comparison, and the recorded verdict.

    The verdict NEVER flips the default from measurement alone: the bench
    timed loop is the exact delivery mode (model of record) and the bf16
    per-edge tables break its bit guarantee by construction (ops/state.py
    packed_state note), so packed can only ever be a bounded-mode knob.
    The A/B records whether it even changes the program at this rung —
    below the row-gather budget on one device the receiver-side
    formulation that reads the flag is not dispatched at all."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ..config.topology import Topology, TopoParams
    from ..ops.disseminate import answer_tables, disseminate, edge_tables
    from ..ops.graph import build_connection_graph
    from ..ops.heartbeat import run_heartbeats
    from ..ops.state import SimParams, graph_arrays, init_state

    topo = Topology.build(TopoParams(
        network_size=n, anchor_stages=5, min_bandwidth=50,
        max_bandwidth=150, min_latency=40, max_latency=130,
        msg_size_bytes=payload_bytes))
    graph = build_connection_graph(n, connect_to, seed=0)
    params = SimParams(n=n, capacity=graph.capacity, serialize_answers=True)
    a = graph_arrays(graph)
    stage = jnp.asarray(topo.stage_of_peer)
    lat = jnp.asarray(topo.latency_ms)
    bw = jnp.asarray(topo.bw_up_mbit)
    lat_edge, _ = edge_tables(stage, lat, a["conns"], a["rev"])
    ans_tables = answer_tables(lat_edge, a["conns"], a["rev"])
    state = init_state(params, seed=0)
    state = run_heartbeats(state, a["conns"], a["rev"], a["out_mask"],
                           params, warm_hb)           # form the mesh

    def _pub(p):
        def go(s):
            res, _ = disseminate(
                s, a["conns"], a["rev"], stage, lat, bw, publisher=4,
                t0_ms=s.t_ms, params=p, payload_bytes=payload_bytes,
                lat_edge=lat_edge, ans_tables=ans_tables)
            return res.delay_ms
        return go

    out: dict = {"n_peers": n, "delivery_mode": "exact"}
    digests = {}
    for key, p in (("off", params),
                   ("on", dataclasses.replace(params, packed_state=True))):
        go = _pub(p)
        digests[key] = hashlib.sha256(
            jax.jit(go).lower(state).as_text().encode()).hexdigest()
        jax.block_until_ready(go(state))              # warm (compile)
        out[f"publish_{key}_s"] = round(_min_wall(lambda: go(state), reps),
                                        6)
    identical = digests["off"] == digests["on"]
    out["program_identical"] = identical
    out["packed_over_unpacked"] = round(
        out["publish_off_s"] / max(out["publish_on_s"], 1e-12), 4)
    out["verdict"] = (
        "keep-default-off: exact mode is the model of record and the bf16 "
        "per-edge tables break its bit guarantee, so packed_state can only "
        "be a bounded-mode knob; "
        + ("the flag is DEAD at this rung (receiver-side formulation not "
           "dispatched below the row-gather budget on one device) — the "
           "walls differ only by host noise"
           if identical else
           "the flag is live at this rung (receiver-side formulation "
           "dispatched); the measured ratio above is the bounded-path "
           "trade, not grounds to flip the exact-mode default"))
    return out


def run(argv=None) -> dict:
    """CLI body (`microbench` subcommand): assemble the strict-JSON
    artifact, optionally install the tuned block table."""
    import jax

    from .summarize import sanitize_nonfinite

    ap = argparse.ArgumentParser(
        prog="microbench",
        description="per-kernel rooflines + Pallas block-size autotune")
    ap.add_argument("--out", default="", help="write the artifact here "
                    "(default: print one JSON line)")
    ap.add_argument("--only", default="", metavar="PREFIX",
                    help="restrict rooflines to contracts with this name "
                    "prefix (the full registry costs minutes of compiles)")
    ap.add_argument("--no-retraces", action="store_true",
                    help="skip the per-contract retrace measurement")
    ap.add_argument("--no-rooflines", action="store_true")
    ap.add_argument("--no-sweep", action="store_true")
    ap.add_argument("--no-packed", action="store_true")
    ap.add_argument("--install", action="store_true",
                    help="write the sweep winners to native/tuned.json "
                    "(DST_TUNED_JSON overrides the path)")
    ap.add_argument("--sweep-rows", type=int, default=4096)
    ap.add_argument("--sweep-cap", type=int, default=16)
    ap.add_argument("--packed-n", type=int, default=100_000)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args(argv)

    out: dict = {"metric": "microbench", "backend": jax.default_backend()}
    if not args.no_rooflines:
        out["rooflines"] = registry_rooflines(
            args.only or None, with_retraces=not args.no_retraces,
            reps=args.reps)
    if not args.no_sweep:
        sweep = sweep_kernels(args.sweep_rows, args.sweep_cap, args.reps)
        out["kernel_sweep"] = sweep
        tuned = {k: {"block_rows": v["best_block_rows"]}
                 for k, v in sweep["kernels"].items()
                 if v.get("best_block_rows") is not None}
        out["tuned"] = tuned
        if args.install and tuned:
            from ..native import score_update as _sk
            from ..native import tuned as _tuned
            from ..native import vmem_gather as _vg

            with open(_tuned.tuned_path(), "w") as fh:
                json.dump(tuned, fh, indent=1, sort_keys=True,
                          allow_nan=False)
                fh.write("\n")
            # drop every cache that baked in the pre-install block choice
            _tuned.invalidate_cache()
            _vg._compiled.cache_clear()
            _sk._compiled.cache_clear()
            out["tuned_installed"] = _tuned.tuned_path()
    if not args.no_packed:
        out["packed_state_ab"] = packed_state_ab(args.packed_n,
                                                 reps=args.reps)
    out = sanitize_nonfinite(out)
    text = json.dumps(out, allow_nan=False)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(text + "\n")
    else:
        print(text)
    return out
