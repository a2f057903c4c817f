"""The link tables of one experiment, worked out by the reference itself.

The source's topology rule (vacp2p/dst-libp2p-test-node shadow/topogen.py:
`anchor_stages` network nodes, bandwidth and latency stepped between the
run's minima and maxima, peer p on node p % stages, downlink = uplink), from
the `run` positionals of the configuration's file and nothing of the
program: float64, no JAX. The DES reads these instead of the tables the
program's plan carries.
"""

from __future__ import annotations

import math

import numpy as np


def stage_tables(pos: dict) -> tuple[np.ndarray, np.ndarray]:
    """(bandwidth in Mbit/s per network node, latency in ms per node pair)."""
    s = int(pos["anchor_stages"])
    lo_bw, hi_bw = int(pos["min_bandwidth"]), int(pos["max_bandwidth"])
    lo_lat, hi_lat = int(pos["min_latency"]), int(pos["max_latency"])
    bw_step = int((hi_bw - lo_bw) / s)
    lat_step = int((hi_lat - lo_lat) / s)
    bw = np.array([math.ceil(i * bw_step + lo_bw) for i in range(s)],
                  np.float64)
    lat = np.empty((s, s), np.float64)
    for i in range(s):
        lat[i, i] = max((s - i) * lat_step, lo_lat)
        for j in range(i + 1, s):
            lat[i, j] = lat[j, i] = min(
                math.ceil((s - j) * lat_step + lo_lat), hi_lat)
    return bw, lat


def edge_tables(conns: np.ndarray, pos: dict, payload_bytes: int,
                fragments: int) -> dict:
    """tx_ms (N,), rx_ms (N,) and lat_edge (N, C; 0 on empty slots), under
    the names the DES reads them by."""
    bw, lat = stage_tables(pos)
    n = conns.shape[0]
    stage = np.arange(n) % int(pos["anchor_stages"])
    frag_bytes = max(payload_bytes // fragments, 16)
    tx_ms = (frag_bytes * 8.0) / (bw[stage] * 1e6) * 1e3
    peer = np.where(conns >= 0, conns, 0)
    lat_edge = np.where(conns >= 0, lat[stage[:, None], stage[peer]], 0.0)
    return {"tx_ms": tx_ms, "rx_ms": tx_ms.copy(), "lat_edge": lat_edge}
