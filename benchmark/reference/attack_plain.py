"""The plain reference of an attacked trial: numpy on the host, nothing of the
program (no import from `dst_libp2p_test_node_tpu`), parameters as a plain
dict (the configuration's `defence`, `PARAMS`' keys).

  (a) `attacked_heartbeat`: the transition of one attacked heartbeat of the
      scenario `sybil_graft_flood` with the v1.1 score defence armed, the
      honest `heartbeat` (GRAFT under D_low and PRUNE over D_high with
      backoff, decay with the zero cut-off, fanout expiry) and then the
      `adversary_round` (every attacker GRAFTs every valid edge; a GRAFT of
      an edge that is backed off or already meshed is the violation that
      accrues the behaviour penalty; a first GRAFT is accepted only from a
      peer that is not negatively scored). The honest rules are written as
      guards on explicit state, every transition a total function of
      (state, topology, parameters), after the ACL2s formalisation of
      GossipSub (arXiv:2311.08859) and the libp2p specification
      pubsub/gossipsub/gossipsub-v1.1.md ("Spam protection measures").
      `window` walks a trial's heartbeats and returns the state after each.
  (b) `delivery_mask`: which copies of an attacked publish can be a
      delivery, from the cohort and the scores alone: a sender that is an
      attacker forwards nothing, and an edge whose receiver scores the
      sender under the graylist threshold delivers nothing.
      `censorship_penalty`: what an attacked publish costs the attackers
      that sat in a mesh and forwarded nothing of it, and `carried`: a
      trial's state from the end of its window through its publish
      schedule, so that every publish's mask comes from THIS file's scores.
  (c) `trial_row`: a trial's metrics from arrays (honest coverage, honest
      p50 and p99, the inflation against the same seed's baseline, the round
      the graylist engaged, the round the mesh recovered) and `budget`, the
      closed form of the rounds the graylist may take.

THE ONE DEPARTURE, and the file's one use of JAX: where the formal model
leaves a SELECTION open (which eligible peers a row grafts, which mesh
members survive a prune), the draws are data, as the plan's draws are data to
benchmark/reference/des.py: regenerated from the state's carried key with
`jax.random.split` / `jax.random.uniform` on the CPU backend (threefry is
counter-based and bit-deterministic on any backend), and ties resolved in
slot order by a stable sort. That turns the transition relation into a
function that can be compared leaf by leaf.

Numerics: every float leaf is float32 and every constant is wrapped in
np.float32, so the host performs the engine's IEEE-754 single operations in
the engine's order. The control (`quantize=bfloat16`) is this reference
computed one precision lower and put in the program's place: every float
leaf of the state (the clock and the backoffs with the counters: after the
first attacked heartbeat every penalty is 1.0, which any precision holds),
the decay factors, the weights and the scores rounded to bfloat16 after
every operation that writes them.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.reference.des import bfloat16_round

BIG = np.float32(1e30)

# what the reference reads of a configuration's `defence`
PARAMS = (
    "d", "d_low", "d_high", "d_score", "d_out", "heartbeat_ms",
    "prune_backoff_ms", "fmd_weight", "fmd_cap", "fmd_decay", "slow_weight",
    "slow_decay", "decay_to_zero", "graylist_threshold", "violation_penalty",
    "censor_penalty", "graylist_engaged_frac", "mesh_recovery_share")

# the leaves of a state an attacked heartbeat may write, by how they compare
EXACT_LEAVES = ("mesh_mask", "fanout_mask", "alive", "subscribed", "grafts",
                "grafts_rx", "prunes", "prunes_rx")
FLOAT_LEAVES = ("backoff_until", "fmd", "slow_penalty", "t_ms")


def bfloat16(x):
    """`x` (a float or an array) to the nearest bfloat16, as float32."""
    return np.asarray(bfloat16_round(np.asarray(x, np.float32)), np.float32)


def exact(x):
    return x


# ----------------------------------------------------------- the selection


def draws(key: np.ndarray, n: int, c: int):
    """(next key, graft draws, prune draws): the engine's key schedule of
    one heartbeat on the carried key (uint32[2]): a split in five (the last
    two are the churn's, which this deployment has off), then one uniform
    (n, c) array for each selection. The one use of JAX."""
    import jax

    try:
        host = jax.devices("cpu")[0]
    except RuntimeError:        # a process held to one platform: any does
        host = jax.devices()[0]
    with jax.default_device(host):
        keys = jax.random.split(jax.numpy.asarray(key, jax.numpy.uint32), 5)
        return (np.asarray(keys[0]),
                np.asarray(jax.random.uniform(keys[1], (n, c))),
                np.asarray(jax.random.uniform(keys[2], (n, c))))


def _ranks(priority: np.ndarray) -> np.ndarray:
    """A slot's rank in its row under ascending priority; equal priorities
    rank in slot order."""
    return np.argsort(np.argsort(priority, axis=-1, kind="stable"),
                      axis=-1, kind="stable")


# -------------------------------------------------------------- the graph


def pulled(edge_mask, conns, rev):
    """What each slot's peer says of the edge back: out[q, j] =
    edge_mask[conns[q, j], rev[q, j]] (the involution of the edges)."""
    out = edge_mask[np.clip(conns, 0, None), np.clip(rev, 0, None)]
    return out & (conns >= 0) & (rev >= 0)


def of_neighbour(per_peer, conns, rev):
    """out[q, j] = per_peer[conns[q, j]]."""
    return per_peer[np.clip(conns, 0, None)] & (conns >= 0) & (rev >= 0)


def valid_edges(st, conns, rev):
    up = st["alive"] & st["subscribed"]
    return (conns >= 0) & up[:, None] & of_neighbour(up, conns, rev)


def score(fmd, slow_penalty, p, q=exact):
    """The score a peer keeps of each neighbour: the subset the reference's
    node configures, P2 (first message deliveries, capped) and one
    non-negative counter under a negative weight, the shape of P7."""
    capped = np.minimum(fmd, np.float32(p["fmd_cap"]))
    return q(q(np.float32(p["fmd_weight"])) * capped
             + q(np.float32(p["slow_weight"])) * slow_penalty)


# --------------------------------------------------------- (a) transitions


def _decayed(counter, factor, p, q):
    eff = q((counter * q(np.float32(factor))).astype(np.float32))
    return np.where(eff < np.float32(p["decay_to_zero"]), np.float32(0.0),
                    eff)


def heartbeat(st: dict, conns, rev, out_mask, p: dict, q=exact) -> dict:
    """One honest heartbeat: the mesh is cut to the valid edges; a row under
    D_low grafts up to D among its eligible edges (valid, not meshed, not
    backed off, not negatively scored) and the grafted peer meshes back; a
    row over D_high keeps D_score by score, D_out outbound and fills to D at
    random, prunes the rest and both ends back off; the counters decay, with
    the zero cut-off; a fanout set past its TTL goes."""
    st = dict(st)
    n, c = conns.shape
    key, u_graft, u_keep = draws(st["key"], n, c)
    t = np.float32(st["t_ms"])
    valid = valid_edges(st, conns, rev)
    mesh = st["mesh_mask"] & valid
    degree = mesh.sum(axis=-1)
    scores = score(st["fmd"], st["slow_penalty"], p, q)
    none = np.zeros((n,), np.int32)

    # GRAFT, guarded by: some row is under D_low
    need = np.where(degree < p["d_low"], p["d"] - degree, 0)
    grafted_n = grafted_rx_n = none
    if (need > 0).any():
        eligible = (valid & ~mesh & (st["backoff_until"] <= t)
                    & (scores >= np.float32(0.0)))
        priority = np.where(eligible, u_graft, BIG)
        grafted = (_ranks(priority) < need[:, None]) & eligible
        grafted_rx = pulled(grafted, conns, rev)
        mesh = (mesh | grafted | grafted_rx) & valid
        degree = mesh.sum(axis=-1)
        grafted_n = grafted.sum(axis=-1, dtype=np.int32)
        grafted_rx_n = grafted_rx.sum(axis=-1, dtype=np.int32)

    # PRUNE, guarded by: some row is over D_high
    over = degree > p["d_high"]
    backoff = st["backoff_until"]
    pruned_n = pruned_rx_n = none
    if over.any():
        by_score = np.where(mesh, -scores + np.float32(1e-3) * u_keep, BIG)
        top = (_ranks(by_score) < p["d_score"]) & mesh
        out_in_top = (top & out_mask).sum(axis=-1)
        need_out = np.clip(p["d_out"] - out_in_top, 0, p["d"])
        outbound = mesh & out_mask & ~top
        keep_out = (_ranks(np.where(outbound, u_keep, BIG))
                    < need_out[:, None]) & outbound
        kept = top | keep_out
        need_fill = np.clip(p["d"] - kept.sum(axis=-1), 0, p["d"])
        rest = mesh & ~kept
        keep = kept | ((_ranks(np.where(rest, u_keep, BIG))
                        < need_fill[:, None]) & rest)
        pruned = mesh & ~keep & over[:, None]
        pruned_rx = pulled(pruned, conns, rev)
        mesh = mesh & ~pruned & ~pruned_rx
        backoff = np.where(pruned | pruned_rx,
                           q(t + np.float32(p["prune_backoff_ms"])), backoff)
        pruned_n = pruned.sum(axis=-1, dtype=np.int32)
        pruned_rx_n = pruned_rx.sum(axis=-1, dtype=np.int32)

    # decay, guarded by: some counter is positive
    fmd, slow = st["fmd"], st["slow_penalty"]
    if ((fmd > 0) | (slow > 0)).any():
        fmd = _decayed(fmd, p["fmd_decay"], p, q)
        slow = _decayed(slow, p["slow_decay"], p, q)

    fanout = st["fanout_mask"]
    if (st["fanout_expire"] > 0.0).any():
        fanout = fanout & (t < st["fanout_expire"])[:, None]

    st.update(
        mesh_mask=mesh, fanout_mask=fanout, backoff_until=backoff, fmd=fmd,
        slow_penalty=slow, key=key,
        t_ms=q(np.float32(t + np.float32(p["heartbeat_ms"]))),
        grafts=st["grafts"] + grafted_n,
        grafts_rx=st["grafts_rx"] + grafted_rx_n,
        prunes=st["prunes"] + pruned_n,
        prunes_rx=st["prunes_rx"] + pruned_rx_n)
    return st


def adversary_round(st: dict, conns, rev, attacker, p: dict,
                    q=exact) -> dict:
    """The attackers' round and the honest accounting of it. Every attacker
    GRAFTs every valid edge, backoff or not. The receiver's guards: a GRAFT
    of an edge that is backed off or already meshed is a VIOLATION (the
    behaviour penalty accrues, the edge is not newly accepted); any other
    GRAFT is ACCEPTED iff the receiver's score of the sender is not
    negative. The attacker meshes every edge it flooded."""
    st = dict(st)
    t = np.float32(st["t_ms"])
    valid = valid_edges(st, conns, rev)
    flood = attacker[:, None] & valid
    received = pulled(flood, conns, rev)
    violation = received & ((st["backoff_until"] > t) | st["mesh_mask"])
    scores = score(st["fmd"], st["slow_penalty"], p, q)
    accepted = received & ~violation & (scores >= np.float32(0.0))
    st.update(
        mesh_mask=(st["mesh_mask"] | flood | accepted) & valid,
        slow_penalty=q(st["slow_penalty"] + np.where(
            violation, np.float32(p["violation_penalty"]), np.float32(0.0))),
        grafts=st["grafts"] + flood.sum(axis=-1, dtype=np.int32),
        grafts_rx=st["grafts_rx"] + received.sum(axis=-1, dtype=np.int32))
    return st


def attacked_heartbeat(st, conns, rev, out_mask, attacker, p, q=exact):
    return adversary_round(heartbeat(st, conns, rev, out_mask, p, q),
                           conns, rev, attacker, p, q)


def window(st: dict, conns, rev, out_mask, attacker, p: dict, steps: int,
           quantize=None) -> list[dict]:
    """The states after each of `steps` attacked heartbeats from `st` (a
    dict of numpy leaves: EXACT_LEAVES, FLOAT_LEAVES, `fanout_expire` and
    the carried `key`). `quantize` is the control's."""
    missing = [k for k in PARAMS if k not in p]
    if missing:
        raise KeyError(f"the defence's parameters lack {missing}")
    q = exact if quantize is None else quantize
    st = dict(st)
    if quantize is not None:
        for leaf in FLOAT_LEAVES:
            st[leaf] = q(st[leaf])
    out = []
    for _ in range(steps):
        st = attacked_heartbeat(st, conns, rev, out_mask, attacker, p, q)
        out.append(st)
    return out


def curves(states: list[dict], conns, rev, attacker, p: dict,
           q=exact) -> dict:
    """What a window shows of the defence, a number a heartbeat: the share
    of the honest peers' valid edges to attackers that the honest end
    graylists, and the attackers' share of the honest peers' mesh edges."""
    graylisted, share = [], []
    for st in states:
        honest = ~attacker & st["alive"] & st["subscribed"]
        to_attacker = (valid_edges(st, conns, rev)
                       & of_neighbour(attacker, conns, rev)
                       & honest[:, None])
        scores = score(st["fmd"], st["slow_penalty"], p, q)
        under = to_attacker & (scores < np.float32(p["graylist_threshold"]))
        honest_mesh = st["mesh_mask"] & honest[:, None]
        graylisted.append(np.float32(under.sum())
                          / np.float32(max(to_attacker.sum(), 1)))
        share.append(np.float32((honest_mesh & of_neighbour(
            attacker, conns, rev)).sum())
            / np.float32(max(honest_mesh.sum(), 1)))
    return {"graylisted_frac": np.array(graylisted, np.float32),
            "attacker_mesh_share": np.array(share, np.float32)}


# ------------------------------------------------------ (b) delivery mask


def delivery_mask(fmd, slow_penalty, conns, rev, attacker, p: dict,
                  q=exact) -> np.ndarray:
    """(N, C), by the sender's slot: whether the copy sent over the edge can
    be a delivery. Not where the sender is an attacker (it forwards
    nothing), and not where the RECEIVER scores the sender under the
    graylist threshold (it ignores the sender's traffic)."""
    scores = score(np.asarray(fmd, np.float32),
                   np.asarray(slow_penalty, np.float32), p, q)
    listens = pulled(scores >= np.float32(p["graylist_threshold"]), conns,
                     rev)
    return listens & ~(attacker[:, None] & (conns >= 0))


def censorship_penalty(st: dict, conns, rev, attacker, received, p: dict,
                       q=exact) -> np.ndarray:
    """The counters after an attacked publish (the shape of P3, mesh
    message delivery failures, at the grain of a message): an honest peer
    that got the message adds `censor_penalty` to its counter of every mesh
    member that is an attacker, which forwarded it nothing. `received` is
    (N,), who got the message. Returns the new `slow_penalty`."""
    silent = st["mesh_mask"] & of_neighbour(attacker, conns, rev)
    owed = silent & (np.asarray(received, bool) & ~attacker)[:, None]
    return q(st["slow_penalty"] + np.where(
        owed, np.float32(p["censor_penalty"]), np.float32(0.0)))


# What a publish itself writes of the leaves a heartbeat reads is the timing
# model's result: WHERE each receiver's first-delivery credit went
# (`credited`, bool (N, C)), how many marks a slow sender's queue earned
# (`slow_marks`, whole numbers), the publisher's fanout set and its expiry,
# the split `key`. They are DATA to `carried`, as the plan's draws are data to
# des.py (a publish's timing is des.py's to hold); the amounts are the
# rule's, and `credit_off` holds the data's shape to it.


def publish_writes(before: dict, after: dict) -> dict:
    """The data above from a state before and after a publish (numpy
    leaves)."""
    marks = np.asarray(after["slow_penalty"], np.float64) \
        - before["slow_penalty"]
    return {"key": after["key"], "fanout_mask": after["fanout_mask"],
            "fanout_expire": after["fanout_expire"],
            "credited": after["fmd"] != before["fmd"],
            "slow_marks": np.rint(marks).astype(np.float32),
            "marks_off": int((np.abs(marks - np.rint(marks)) > 1e-4).sum()
                             + (marks < -1e-4).sum())}


def credit_off(writes: dict, fmd_before, received, publisher: int,
               p: dict) -> int:
    """How many rows of a publish's writes are NOT the rule's shape: one
    credit at one slot for a peer that got the message and did not publish
    it (none where that leaves every counter of the row as it was: at the
    cap), none for anybody else; and a mark is a whole number, not
    negative."""
    owed = np.asarray(received, bool).copy()
    owed[publisher] = False
    credits = writes["credited"].sum(axis=-1)
    capped = (np.asarray(fmd_before) >= np.float32(p["fmd_cap"])).any(axis=-1)
    ok = np.where(owed, (credits == 1) | ((credits == 0) & capped),
                  credits == 0)
    return int((~ok).sum()) + writes["marks_off"]


def carried(st: dict, conns, rev, out_mask, attacker, p: dict,
            publishes: list[dict], quantize=None) -> list[dict]:
    """A trial's state from the end of its window through its publish
    schedule. `st` is the state after the window (this file's own walk);
    each of `publishes` gives `heartbeats` (the honest heartbeats the
    schedule runs before it), `received` ((N,): who got the message, by the
    reference of the publish) and `writes` (see above). Returns, a publish,
    `start` (the state it starts from: its delivery mask is `delivery_mask`
    of this) and `penalised` (the state after `censorship_penalty`)."""
    q = exact if quantize is None else quantize
    out = []
    for pub in publishes:
        for _ in range(pub["heartbeats"]):
            st = heartbeat(st, conns, rev, out_mask, p, q)
        start, w = st, pub["writes"]
        st = {**st, "key": w["key"], "fanout_mask": w["fanout_mask"],
              "fanout_expire": w["fanout_expire"],
              "fmd": q(np.minimum(
                  st["fmd"] + np.where(w["credited"], np.float32(1.0),
                                       np.float32(0.0)),
                  np.float32(p["fmd_cap"]))),
              "slow_penalty": q(st["slow_penalty"] + w["slow_marks"])}
        st["slow_penalty"] = censorship_penalty(
            st, conns, rev, attacker, pub["received"], p, q)
        out.append({"start": start, "penalised": st})
    return out


# ------------------------------------------------------------ (c) metrics


def budget(p: dict) -> float:
    """The closed form: heartbeats from the start of a GRAFT flood until a
    flooded edge is graylisted. The counter follows c_k = decay * c_(k-1) +
    penalty from the second round on (the first round's GRAFTs are accepted
    or already meshed); the edge is graylisted once weight * c_k is at or
    under the threshold. Infinite where the steady state never gets there."""
    if p["slow_weight"] >= 0.0:
        return math.inf
    need = p["graylist_threshold"] / p["slow_weight"]
    penalty, decay = p["violation_penalty"], p["slow_decay"]
    if need <= penalty:
        return 2.0
    rest = 1.0 - need * (1.0 - decay) / penalty
    if rest <= 0.0:
        return math.inf
    return 1.0 + math.ceil(math.log(rest) / math.log(decay))


def _first(curve, holds) -> int:
    """The first round (from 1) at which `holds`, -1 if none."""
    hits = np.nonzero(holds(np.asarray(curve, np.float64)))[0]
    return int(hits[0]) + 1 if hits.size else -1


def trial_row(delays_ms, received, attacker, baseline_p50_ms: float,
              window_curves: dict, p: dict) -> dict:
    """A trial's row from arrays: `delays_ms` and `received` are (messages,
    N) of its publishes, `window_curves` the two curves of its window."""
    honest = ~attacker
    delays_ms = np.asarray(delays_ms, np.float64)
    received = np.asarray(received, bool)
    coverage = float(np.mean([r[honest].mean() for r in received]))
    pool = np.concatenate([d[honest & r]
                           for d, r in zip(delays_ms, received)])
    p50 = float(np.percentile(pool, 50)) if pool.size else math.inf
    p99 = float(np.percentile(pool, 99)) if pool.size else math.inf
    share = np.asarray(window_curves["attacker_mesh_share"], np.float64)
    floor = p["mesh_recovery_share"]
    if share.max() <= floor:
        recovered = 1       # never compromised to speak of
    else:
        peak = int(np.argmax(share))
        after = _first(share[peak:], lambda s: s <= floor)
        recovered = peak + after if after > 0 else -1
    return {
        "attackers": int(attacker.sum()),
        "honest_coverage": coverage,
        "latency_p50_ms": p50, "latency_p99_ms": p99,
        "benign_p50_ms": baseline_p50_ms,
        "latency_inflation": (p50 / baseline_p50_ms
                              if baseline_p50_ms > 0 and math.isfinite(p50)
                              else math.inf),
        "hb_to_graylist": _first(
            window_curves["graylisted_frac"],
            lambda g: g >= p["graylist_engaged_frac"]),
        "mesh_recovery_hb": recovered,
        "graylisted_frac_final": float(
            window_curves["graylisted_frac"][-1]),
        "attacker_mesh_share_peak": float(share.max()),
        "hb_budget": budget(p),
    }
