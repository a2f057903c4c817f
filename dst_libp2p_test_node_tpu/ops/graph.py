"""Connection-graph substrate: the "dial phase" as array construction.

The reference forms its network by every peer shuffling [0..PEERS)\\{me} with a
per-process RNG and dialing the first CONNECTTO peers
(gossipsub-queues/main.nim:367-409; go-test-node/main.go:276-348;
rust-test-node/src/main.rs:303-345). Connections are symmetric and capped by
MAXCONNECTIONS (main.nim:429). This module reproduces that *distribution*
deterministically (seeded per run, SURVEY.md §7 RNG note) and lays the result
out TPU-first:

  conns[p, i]  int32  — i-th neighbor of peer p, -1 padding (capacity C)
  rev[p, i]    int32  — slot j such that conns[conns[p, i], j] == p
  out_mask[p,i] bool  — True iff p dialed that neighbor (outbound, for D_out)
  degree[p]    int32

The reverse-slot map makes every graft/prune *reciprocal* update a single
fixed-shape scatter (mesh_mask[q, rev] = v) with no collision handling — the
key trick that lets the whole GossipSub control plane run under jit.

Built host-side in numpy once per experiment epoch (the reference dials once
at startup, main.nim:466-471); everything steady-state runs on device. The
device waits for it (the warm-up scan's first operand is `conns`), so which
pass runs is chosen by what the call observes, never by a parameter:

  dials      n <= 4096: an exact row permutation. Above: the first k
             distinct of 2k+8 draws a row; the rows whose first k draws
             are distinct (all but ~k^2/2n of them) take those, only the
             others go through the general algorithm (_first_distinct)
  dedupe     dials sampled by this call (rows of distinct peers, none its
             own): p->q goes exactly when q < p and q dialed p ("mutual").
             A caller's `dials=` promise nothing: np.unique of the pair
             keys, the first copy kept ("unique")
  slot ranks the stable order of the 2E endpoint ids by radix passes over
             their 16-bit halves (_stable_order), group starts by bincount
  capacity   the kept-prefix pass runs only if some rank reaches the cap

`ConnGraph.build` says which engaged how often. The stream of BOTH
generators (`default_rng(seed)`: the dials; `default_rng(seed + 0x5EED)`:
the edge order) is part of the checkpoint contract: a resumed run rebuilds
the graph from (n, connect_to, seed) and checks its fingerprint
(runtime/checkpoint.py graph_sha256), so the two calls keep their dtype,
shape and order, and tests/graph_reference.py (the sorts this build
replaced, verbatim) holds every array to the same bytes.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


def _stable_order(keys: np.ndarray, n: int) -> np.ndarray:
    """np.argsort(keys, kind="stable") for ids in [0, n), as radix passes.

    numpy's stable sort of 16-bit keys is a radix sort, its stable sort of
    32-bit keys a comparison sort three times slower at two million ids: so
    sort on the low half, then stably on the high half of that order (one
    pass where the ids fit 16 bits). Same permutation."""
    if n <= 1 << 16:
        return np.argsort(keys.astype(np.uint16), kind="stable")
    low = np.argsort((keys & 0xFFFF).astype(np.uint16), kind="stable")
    high = np.argsort((keys >> 16).astype(np.uint16)[low], kind="stable")
    return low[high]


def _group_ranks(keys: np.ndarray, n: int):
    """(order, counts, starts, ranks) of ids in [0, n): the stable sort
    order, every id's number of occurrences and the start of its run in
    sorted order, and each element's occurrence rank among equal keys in
    ARRAY order (int32) — the shared core of the two ranking entry points
    below."""
    m = len(keys)
    order = _stable_order(keys, n)
    counts = np.bincount(keys, minlength=n)
    starts = np.cumsum(counts) - counts
    ranks = np.empty(m, dtype=np.int32)
    ranks[order] = (np.arange(m, dtype=np.int32)
                    - np.repeat(starts.astype(np.int32), counts))
    return order, counts, starts, ranks


def _cumcount(keys: np.ndarray) -> np.ndarray:
    """Occurrence rank of each element among equal keys, in array order."""
    return _group_ranks(keys, int(keys.max()) + 1 if len(keys) else 0)[3]


def _cumcount_and_filtered(keys: np.ndarray, n: int, cap: int, half: int):
    """One-sort version of the build's two ranking passes.

    Returns (ok, slot_full, counts): ok marks edges whose BOTH endpoint
    occurrences rank below `cap` (keys holds the src half then the dst
    half, `half` elements each), slot_full[i] is the occurrence rank of
    keys[i] among the KEPT occurrences — bit-identical to running _cumcount
    again on the filtered arrays, without a second sort (the kept elements
    keep their relative order, so their kept-prefix count within each key
    group IS their filtered cumcount) — and counts[p] how often p occurs
    in keys. When no rank reaches `cap` (capacity 40 at mean degree 20: no
    peer of 100,000) every edge is kept, the ranks ARE the slots, and the
    kept-prefix pass does not run: ok is None then."""
    m = len(keys)
    order, counts, starts, ranks = _group_ranks(keys, n)
    if m == 0 or int(ranks.max()) < cap:
        return None, ranks, counts
    ok = (ranks[:half] < cap) & (ranks[half:] < cap)

    kept_sorted = np.concatenate([ok, ok])[order]
    before = np.cumsum(kept_sorted) - kept_sorted   # kept strictly before, global
    # ... at its group's start (an empty group's start may be m: clamped,
    # and repeated zero times)
    base = np.repeat(before[np.minimum(starts, m - 1)], counts)
    slot_full = np.empty(m, dtype=np.int32)
    slot_full[order] = before - base                # kept-prefix within the group
    return ok, slot_full, counts


def _first_distinct(cand: np.ndarray, k: int, n: int, rows: np.ndarray):
    """(out, holes): the first k distinct entries of each row of `cand`
    (already over [0..n) less each row's own peer `rows[i]`), in draw order,
    and how many slots no draw filled. The general algorithm, for any row."""
    r = len(rows)
    # "Duplicate" = an equal value appeared EARLIER in the row; a stable row
    # sort puts the earliest occurrence first within each equal run, so
    # flagging equal-to-predecessor in sorted order and scattering back
    # marks exactly the later occurrences.
    ordr = np.argsort(cand, axis=1, kind="stable")
    srt = np.take_along_axis(cand, ordr, axis=1)
    dup_sorted = np.concatenate(
        [np.zeros((r, 1), bool), srt[:, 1:] == srt[:, :-1]], axis=1)
    dup = np.empty_like(dup_sorted)
    np.put_along_axis(dup, ordr, dup_sorted, axis=1)
    keep_rank = np.cumsum(~dup, axis=1) - 1
    out = np.full((r, k), -1, dtype=np.int64)
    at, cols = np.nonzero(~dup & (keep_rank < k))
    out[at, keep_rank[at, cols]] = cand[at, cols]
    # rows that still have holes (astronomically rare): fill with (p+1+i) mod n
    hr, hc = np.nonzero(out < 0)
    out[hr, hc] = (rows[hr] + 1 + hc) % n
    return out, len(hr)


def _sample_dials(n: int, connect_to: int, seed: int):
    """(dials, resampled, clean): sample_dials' table, how many rows went
    through the general first-k-distinct algorithm, and whether every row
    is known to hold k distinct peers other than its own (false once a hole
    was filled, or when connect_to reaches n)."""
    rng = np.random.default_rng(seed)
    if n <= 4096:
        r = rng.random((n, n))
        np.fill_diagonal(r, np.inf)
        dials = np.argsort(r, axis=1)[:, :connect_to].astype(np.int64)
        return dials, 0, connect_to < n

    k = connect_to
    draw = max(2 * k + 8, k + 16)
    # NOTE: the draw must stay int64 and (n, draw) — the generator's output
    # stream depends on the requested dtype and count, and graph
    # construction is fingerprinted (runtime/checkpoint.py); narrow AFTER
    # drawing
    cand = rng.integers(0, n - 1, size=(n, draw))
    # a row's dials are its first k distinct draws. In all but a few rows
    # (k^2/2n of them: about 50 of 100,000 at k = 10) the first k draws ARE
    # distinct, and those rows need neither the other draw - k columns nor a
    # sort of the whole row: shift the head past `me` (uniform over
    # [0..n)\{me}), find the rows whose head holds a repeat by a sort of the
    # narrow head, and send only those through the general algorithm
    head = cand[:, :k]
    out = head + (head >= np.arange(n)[:, None])
    srt = np.sort(out.astype(np.int32), axis=1)
    rows = np.nonzero((srt[:, 1:] == srt[:, :-1]).any(axis=1))[0]
    holes = 0
    if len(rows):
        again = cand[rows]
        again = (again + (again >= rows[:, None])).astype(np.int32)
        out[rows], holes = _first_distinct(again, k, n, rows)
    return out, len(rows), holes == 0


def sample_dials(n: int, connect_to: int, seed: int) -> np.ndarray:
    """dials[p] = the connect_to distinct peers (!= p) that p dials.

    Matches the reference's per-peer independent shuffle-and-take
    (main.nim:376-381). Exact row permutation for small n; rejection sampling
    for large n (collision probability ~ connect_to^2/n)."""
    return _sample_dials(n, connect_to, seed)[0]


@dataclass
class ConnGraph:
    conns: np.ndarray      # (N, C) int32, -1 padded
    rev: np.ndarray        # (N, C) int32, -1 padded
    out_mask: np.ndarray   # (N, C) bool
    degree: np.ndarray     # (N,) int32
    # which path of the build engaged how often (`stats<i>.json` "build"):
    # dial_rows_resampled, mutual_dials_dropped, dedupe, cap_filtered_edges
    build: dict = field(default_factory=dict)

    @property
    def n(self) -> int:
        return int(self.conns.shape[0])

    @property
    def capacity(self) -> int:
        return int(self.conns.shape[1])

    def validate(self) -> None:
        """Reverse-map invariant: conns[conns[p,i], rev[p,i]] == p."""
        p, i = np.nonzero(self.conns >= 0)
        q = self.conns[p, i]
        j = self.rev[p, i]
        assert (self.conns[q, j] == p).all(), "reverse-slot map broken"


def build_connection_graph(
    n: int,
    connect_to: int,
    seed: int = 0,
    max_degree: int | None = None,
    dials: np.ndarray | None = None,
) -> ConnGraph:
    """Symmetrize per-peer dials into padded neighbor lists + reverse map.

    max_degree plays MAXCONNECTIONS (main.nim:429): an edge is kept only if
    both endpoints still have a free slot, in random edge order — mirroring
    dial-time rejection by a full peer."""
    resampled, clean = 0, False   # a caller's dials promise nothing
    if dials is None:
        dials, resampled, clean = _sample_dials(n, connect_to, seed)
    k = dials.shape[1]
    if max_degree is None:
        # expected degree = 2*connect_to; generous slack keeps rejections rare
        max_degree = min(max(4 * k, 16), max(n - 1, 1))
    cap = max_degree

    # int32 endpoint ids (peer ids fit easily): half the bytes in every pass
    dials = dials.astype(np.int32)
    src = np.repeat(np.arange(n, dtype=np.int32), k)
    dst = dials.reshape(-1)
    # dedupe undirected pairs, keeping the first dialer as the outbound side
    if clean:
        # rows of distinct peers, none its own: a pair occurs at most twice,
        # as p->q and q->p, and the later copy in flat order is the larger
        # peer's. So p->q goes exactly when q < p and q dialed p
        back = np.nonzero(dst < src)[0]
        q = dst[back]
        mutual = (dials[q] == src[back][:, None]).any(axis=1)
        keep = np.ones(len(src), dtype=bool)
        keep[back[mutual]] = False
        e_src, e_dst = src[keep], dst[keep]
    else:
        # any dials (repeats in a row, self-dials): the first of every equal
        # pair key (which needs the full int64 range: n^2 ids)
        lo, hi = np.minimum(src, dst), np.maximum(src, dst)
        pair_key = lo.astype(np.int64) * n + hi
        _, first_idx = np.unique(pair_key, return_index=True)
        first_idx.sort()
        e_src, e_dst = src[first_idx], dst[first_idx]
    m = len(e_src)
    build = {"dial_rows_resampled": resampled,
             "mutual_dials_dropped": len(src) - m,
             "dedupe": "mutual" if clean else "unique",
             "cap_filtered_edges": 0}

    # random edge order, then capacity filter (both endpoints must have room)
    rng = np.random.default_rng(seed + 0x5EED)
    order = rng.permutation(m)
    e_src, e_dst = e_src[order], e_dst[order]
    # a node occupies one slot per incident edge regardless of direction, so
    # slot ranks count appearances across BOTH endpoint arrays; the src copy
    # of edge e sits at position e, the dst copy at position E + e, keeping
    # slot order aligned with edge order
    ok, slot_full, degree = _cumcount_and_filtered(
        np.concatenate([e_src, e_dst]), n, cap, m)
    slot_src, slot_dst = slot_full[:m], slot_full[m:]
    if ok is not None:
        slot_src, slot_dst = slot_src[ok], slot_dst[ok]
        e_src, e_dst = e_src[ok], e_dst[ok]
        build["cap_filtered_edges"] = m - len(e_src)
        degree = np.bincount(np.concatenate([e_src, e_dst]), minlength=n)

    # every (peer, slot) is written once: flat indices, computed once
    at_src = e_src.astype(np.intp) * cap + slot_src
    at_dst = e_dst.astype(np.intp) * cap + slot_dst
    conns = np.full(n * cap, -1, dtype=np.int32)
    rev = np.full(n * cap, -1, dtype=np.int32)
    out = np.zeros(n * cap, dtype=bool)
    conns[at_src] = e_dst
    conns[at_dst] = e_src
    rev[at_src] = slot_dst
    rev[at_dst] = slot_src
    out[at_src] = True  # dialer side is the outbound connection
    return ConnGraph(
        conns=conns.reshape(n, cap), rev=rev.reshape(n, cap),
        out_mask=out.reshape(n, cap), degree=degree.astype(np.int32),
        build=build)
