"""The moved-rows table of ops/pull.py's docstring (PR 51): a step of a
float fixpoint that pulls every sender's offers against one that delivers
the offers of the K senders that moved (ops/pull.pull_moved_min), each
candidate a jit of its own, a step of a 20-step `fori_loop`, at
(100000, 40), f32, the index of `build_connection_graph(100000, 10, seed,
max_degree=40)` and the bands of PR 50 on the dense side; one lane and four
vmapped lanes; median of 4 timed calls after two warm ones.

    chiprun --chips 1 -- python scripts/relax_moved_bench.py

Refuses to run off a TPU (a CPU timing is no device number); `--tiny` runs
the candidates at 2,000 peers on any backend, for the control flow alone.
Writes chiprun_out/relax_moved_bench.json and prints it."""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dst_libp2p_test_node_tpu.ops import pull  # noqa: E402
from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph  # noqa: E402

INF = pull.INF
STEPS = 20
PROC_MS, HB_MS = 1.0, 1000.0


def timed(fn, *args, calls=4):
    f = jax.jit(fn)
    for _ in range(2):
        jax.block_until_ready(f(*args))
    out = []
    for _ in range(calls):
        t = time.perf_counter()
        jax.block_until_ready(f(*args))
        out.append((time.perf_counter() - t) * 1e3)
    return statistics.median(out) / STEPS


def offer(t, uplink, a_base, hb_phase, g_off, g_base):
    """ops/disseminate._converge_dyn's offers with gossip, row for row."""
    live = (t < INF)[:, None]
    base = t + PROC_MS
    start = jnp.maximum(base, uplink)
    cand = jnp.where(live, start[:, None] + a_base, INF)
    hb = (jnp.floor((base - hb_phase) / HB_MS) + 1.0) * HB_MS + hb_phase
    return jnp.minimum(cand, jnp.where(
        live, jnp.maximum(hb[:, None] + g_off, uplink[:, None]) + g_base,
        INF))


def main():
    tiny = "--tiny" in sys.argv
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not tiny:
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    n, c = (2000, 40) if tiny else (100000, 40)
    ks = (16, 64) if tiny else (128, 512, 1024, 2048, 4096)
    seed = 1
    g = build_connection_graph(n, 10, seed=seed, max_degree=c)
    conns, rev = jnp.asarray(g.conns), jnp.asarray(g.rev)
    bands = pull.make_pull_bands(conns, rev, min_bytes=0)
    bc, br = bands.of("conns"), bands.of("rev")
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    has = conns >= 0
    a4 = jnp.where(has, 50.0 * jax.random.uniform(keys[0], (4, n, c)), INF)
    g_base = jnp.where(has & (jax.random.uniform(keys[1], (n, c)) < 0.3),
                       200.0 * jax.random.uniform(keys[2], (n, c)), INF)
    g_off = HB_MS * jnp.floor(3.0 * jax.random.uniform(keys[3], (n, c)))
    uplink = 10.0 * jax.random.uniform(keys[4], (n,))
    hb_phase = HB_MS * jax.random.uniform(keys[5], (n,))
    rx_const = jax.random.uniform(keys[6], (n,))
    t4 = 1e3 + 1e3 * jax.random.uniform(keys[7], (4, n))
    inc4 = jnp.full((4, n, c), INF)

    def mask(m):
        """m rows marked, spread over the index."""
        at = (jnp.arange(m) * (n // max(m, 1))) % n
        one = jnp.zeros((n,), bool).at[at].set(True)
        return jnp.stack([jnp.roll(one, 7 * lane) for lane in range(4)])

    def loop(step, lanes):
        """A step of the fixpoint's body around `step(t, inc, moved, a)`;
        the mask shifts a row a step, so no compaction is loop-invariant."""
        def one(t, inc, moved, a):
            def body(_, carry):
                t, inc, moved = carry
                inc = step(t, inc, moved, a)
                t = jnp.minimum(
                    t, jnp.maximum(inc.min(axis=-1), rx_const))
                return t, inc, jnp.roll(moved, 1)
            return jax.lax.fori_loop(0, STEPS, body, (t, inc, moved))
        if lanes == 1:
            return lambda t, inc, moved, a: one(
                t[0], inc[0], moved[0], a[0])
        return jax.vmap(one)

    def operands(a):
        return (uplink, a, hb_phase, g_off, g_base)

    def dense(lanes):
        return lambda t, inc, moved, a: pull.reciprocal_pull_min(
            offer(t, *operands(a)), bc, br, lanes)

    def moved_min(lanes):
        return lambda t, inc, moved, a: pull.pull_moved_min(
            offer, t, inc, moved, operands(a), conns, rev, bc, br, lanes)[0]

    ms = {}
    for lanes in (1, 4):
        ms[f"dense.{lanes}"] = timed(
            loop(dense(lanes), lanes), t4, inc4, mask(n), a4)
        # the body around the step alone, and `inc` through a cond whose
        # taken side hands it back
        ms[f"tail.{lanes}"] = timed(
            loop(lambda t, inc, moved, a: inc, lanes), t4, inc4, mask(3), a4)
        hand_back = pull._moved_step(
            lambda t, inc, moved, a: (inc, jnp.int32(1)),
            lambda t, inc, moved, a, lanes=lanes: (
                dense(lanes)(t, inc, moved, a), jnp.int32(0)), lanes, 0)
        ms[f"pass.{lanes}"] = timed(
            loop(lambda *args: hand_back(*args)[0], lanes),
            t4, inc4, mask(3), a4)
    for k in ks:
        pull._RELAX_ROWS = k
        for lanes in (1, 4):
            for m in (3, k // 2, k):
                ms[f"sparse.{k}.{m}.{lanes}"] = timed(
                    loop(moved_min(lanes), lanes), t4, inc4, mask(m), a4)
            # the dense side reached through the cond
            ms[f"sparse.{k}.all.{lanes}"] = timed(
                loop(moved_min(lanes), lanes), t4, inc4, mask(n), a4)
            # the parts: compaction, the K-row offers, the scatter
            methods = ("compare_all", "scan", "sort") if lanes == 1 else (
                "compare_all", "scan")
            for method in methods:
                ms[f"ids.{method}.{k}.{lanes}"] = timed(loop(
                    lambda t, inc, moved, a: inc.at[0, 0].add(
                        pull.sending_rows(moved, k, method).sum().astype(
                            jnp.float32)), lanes), t4, inc4, mask(k), a4)
            ids = jnp.minimum(pull.sending_rows(mask(k)[0], k), n - 1)

            def rows(t, inc, moved, a):
                at = (ids + moved.argmax()) % n
                vals = offer(t[at], *(x[at] for x in operands(a)))
                return inc.at[0, 0].add(vals.min())

            def scatter(t, inc, moved, a):
                at = (ids + moved.argmax()) % n
                return pull._deliver(
                    inc, at, True, jnp.broadcast_to(t[:k, None], (k, c)),
                    conns, rev)

            ms[f"rows.{k}.{lanes}"] = timed(
                loop(rows, lanes), t4, inc4, mask(1), a4)
            ms[f"scatter.{k}.{lanes}"] = timed(
                loop(scatter, lanes), t4, inc4, mask(1), a4)
    rows = {"device": f"{dev.platform} {dev.device_kind}", "shape": [n, c],
            "seed": seed, "calls": 4, "steps": STEPS,
            "pull_rows_share": pull.pull_rows_share(bands), "ms_a_step": ms}
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/relax_moved_bench.json", "w") as f:
        json.dump(rows, f, indent=1, allow_nan=False)
    print(json.dumps(rows, indent=1, allow_nan=False))


if __name__ == "__main__":
    main()
