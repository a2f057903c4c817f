"""The row-count table of ops/pull.py's docstring (PR 50): a pull's price
against the rows it fetches, each candidate a jit of its own at
(100000, 40), f32, the index of `build_connection_graph(100000, 10, seed,
max_degree=40)`, median of 8 timed calls after two warm ones.

    chiprun --chips 1 -- python scripts/pull_bands_bench.py

`--update-only` (PR 53): the rows of the carried per-peer lookup brought
up to date from the peers that moved (ops/pull.neighbor_update_min), a step
of a 20-step loop, one lane and four, alone; to
chiprun_out/pull_update_bench.json.

Refuses to run off a TPU (a CPU timing is no device number); `--tiny` runs
the candidates at 2,000 peers on any backend, for the control flow alone.
Writes chiprun_out/pull_bands_bench.json and prints it."""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dst_libp2p_test_node_tpu.ops import pull  # noqa: E402
from dst_libp2p_test_node_tpu.ops.graph import build_connection_graph  # noqa: E402

INF = pull.INF


def timed(fn, *args, calls=8):
    f = jax.jit(fn)
    for _ in range(2):
        jax.block_until_ready(f(*args))
    out = []
    for _ in range(calls):
        t = time.perf_counter()
        jax.block_until_ready(f(*args))
        out.append((time.perf_counter() - t) * 1e3)
    return statistics.median(out)


def scatter_spread(tail, ids, n, fill):
    """The alternative to pull._spread: band B's rows scattered into a
    filled (N, T) (F lanes: (F, N, T), one scatter of M rows of (F, T))."""
    if tail.ndim == 2:
        return jnp.full((n, tail.shape[-1]), fill, tail.dtype).at[ids].set(
            tail, mode="drop", unique_indices=True)
    f, m, t = tail.shape
    return jnp.full((f, n, t), fill, tail.dtype).at[:, ids].set(
        tail, mode="drop", unique_indices=True)


def update_rows(vals, vals4, t1, t4, index):
    """ms a step of a 20-step loop of the carried lookup brought up to date
    from the peers that moved (pull.neighbor_update_min: a refinement pass's
    receivers' times), 3 / K / every peer moved (the last is the banded
    lookup through the cond)."""
    ms = {}
    n = vals.shape[0]
    rev = index["full"][1]
    cn = index["AB"][0]

    def update_loop(lanes):
        def lane(nbr, t, moved):
            return pull.neighbor_update_min(nbr, t, moved, index["full"][0],
                                            rev, cn, lanes)[0]

        def run(nbr, t, moved):
            def body(_, x):
                nbr, t = x
                nbr = (lane if lanes == 1 else jax.vmap(lane))(nbr, t, moved)
                return nbr, jnp.where(moved, t + 1.0, t)
            return jax.lax.fori_loop(0, 20, body, (nbr, t))
        return run

    for name, count in (("3", 3), ("K", pull._RELAX_ROWS), ("all", n)):
        moved = jnp.arange(n) % (n // count) == 0 if count < n else (
            jnp.ones((n,), bool))
        moved = moved & (jnp.cumsum(moved) <= count)
        ms[f"loop20.update.{name}.AB.1"] = timed(
            update_loop(1), vals, t1, moved, calls=4) / 20
        ms[f"loop20.update.{name}.AB.4"] = timed(
            update_loop(4), vals4, t4, jnp.stack([moved] * 4), calls=4) / 20
    return ms


def main():
    tiny = "--tiny" in sys.argv
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not tiny:
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    n, c = (2000, 40) if tiny else (100000, 40)
    seed = 1
    g = build_connection_graph(n, 10, seed=seed, max_degree=c)
    conns, rev = jnp.asarray(g.conns), jnp.asarray(g.rev)
    key = jax.random.PRNGKey(seed)
    vals = jax.random.uniform(key, (n, c))
    vals4 = jax.random.uniform(key, (4, n, c))
    t1 = jax.random.uniform(key, (n,))
    t4 = jax.random.uniform(key, (4, n))
    rows = {"device": f"{dev.platform} {dev.device_kind}", "shape": [n, c],
            "seed": seed, "calls": 8,
            "filled_share": float((g.conns >= 0).mean()),
            "max_degree": int(g.degree.max())}
    census = {}
    for c1 in (16, 20, 24, 28, 32):
        census[c1] = int((g.degree > c1).sum())
    rows["rows_with_more_than"] = census
    if "--update-only" in sys.argv:
        bands = pull.make_pull_bands(conns, rev, min_bytes=0)
        rows["bands"] = [int(bands.heads["conns"].shape[1]),
                         int(bands.tails["conns"].shape[0])]
        index = {"full": (conns, rev),
                 "AB": (bands.of("conns"), bands.of("rev"))}
        rows["ms"] = update_rows(vals, vals4, t1, t4, index)
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/pull_update_bench.json", "w") as f:
            json.dump(rows, f, indent=1, allow_nan=False)
        print(json.dumps(rows, indent=1, allow_nan=False))
        return

    ms = {}
    # the full pull, one lane and four
    ms["full.1"] = timed(pull.reciprocal_pull_min, vals, conns, rev)
    ms["full.4"] = timed(
        jax.vmap(lambda v, cn, rv: pull.reciprocal_pull_min(v, cn, rv, 4),
                 in_axes=(0, None, None)), vals4, conns, rev)
    ms["full.bool.1"] = timed(pull.reciprocal_pull_bool, vals > 0.5, conns,
                              rev)
    ms["rows_min.full.1"] = timed(pull.neighbor_rows_min, t1, conns)
    ms["rows_min.full.4"] = timed(
        jax.vmap(lambda v, cn: pull.neighbor_rows_min(v, cn, 4),
                 in_axes=(0, None)), t4, conns)
    # band A alone
    for c1 in (16, 24, 32):
        ms[f"bandA.{c1}.1"] = timed(
            lambda v, cn, rv: pull._mask_min(
                pull._PULL_MIN[0](v, cn, rv), cn, rv),
            vals, conns[:, :c1], rev[:, :c1])
    # bands A + B assembled, through the inverse row gather (pull._spread)
    for c1, m in ((24, None), (24, n // 2), (32, None), (20, n // 2)):
        bands = pull.make_pull_bands(conns, rev, min_bytes=0, c1=c1, rows=m)
        if bands is None:
            ms[f"AB.gather.{c1}.{m}"] = None
            continue
        bc, br = bands.of("conns"), bands.of("rev")
        tag = f"{c1}.{bc.tail.shape[0]}"
        ms[f"AB.gather.{tag}.1"] = timed(pull.reciprocal_pull_min, vals, bc,
                                         br)
        if c1 != 24 or m is not None:
            continue
        ms[f"AB.gather.{tag}.4"] = timed(
            jax.vmap(lambda v, cn, rv: pull.reciprocal_pull_min(v, cn, rv, 4),
                     in_axes=(0, None, None)), vals4, bc, br)
        ms[f"AB.gather.{tag}.bool.1"] = timed(
            pull.reciprocal_pull_bool, vals > 0.5, bc, br)
        ms[f"rows_min.AB.gather.{tag}.1"] = timed(
            pull.neighbor_rows_min, t1, bc)
        ms[f"rows_min.AB.gather.{tag}.4"] = timed(
            jax.vmap(lambda v, cn: pull.neighbor_rows_min(v, cn, 4),
                     in_axes=(0, None)), t4, bc)
        # the same with band B scattered back
        ids = jnp.asarray(np.flatnonzero(np.asarray(bc.back) < bc.tail.shape[0]
                                         ).astype(np.int32))
        ids = jnp.concatenate(
            [ids, jnp.full((bc.tail.shape[0] - ids.shape[0],), n, jnp.int32)])

        def by_scatter(form, v):
            head = pull._mask_min(form(v, bc.head, br.head), bc.head, br.head)
            tail = pull._mask_min(form(v, bc.tail, br.tail), bc.tail, br.tail)
            return jnp.concatenate(
                [head, scatter_spread(tail, ids, n, INF)], axis=-1)

        ms[f"AB.scatter.{tag}.1"] = timed(
            lambda v: by_scatter(pull._PULL_MIN[0], v), vals)
        ms[f"AB.scatter.{tag}.4"] = timed(
            lambda v: by_scatter(pull._PULL_MIN[1], v), vals4)
        # inside a 20-step loop (what the publish's fixpoints pay a step)
        def loop(pull_fn, v):
            def body(_, x):
                return jnp.minimum(x, pull_fn(x) + 1.0)
            return jax.lax.fori_loop(0, 20, body, v)

        ms["loop20.full.1"] = timed(
            lambda v: loop(lambda x: pull.reciprocal_pull_min(x, conns, rev),
                           v), vals, calls=4) / 20
        ms[f"loop20.AB.gather.{tag}.1"] = timed(
            lambda v: loop(lambda x: pull.reciprocal_pull_min(x, bc, br), v),
            vals, calls=4) / 20
        pull4 = jax.vmap(lambda v, cn, rv: pull.reciprocal_pull_min(
            v, cn, rv, 4), in_axes=(0, None, None))
        ms["loop20.full.4"] = timed(
            lambda v: loop(lambda x: pull4(x, conns, rev), v), vals4,
            calls=4) / 20
        ms[f"loop20.AB.gather.{tag}.4"] = timed(
            lambda v: loop(lambda x: pull4(x, bc, br), v), vals4,
            calls=4) / 20
    rows["ms"] = ms
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/pull_bands_bench.json", "w") as f:
        json.dump(rows, f, indent=1, allow_nan=False)
    print(json.dumps(rows, indent=1, allow_nan=False))


if __name__ == "__main__":
    main()
