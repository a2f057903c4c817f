"""The reference for `shadowlog<i>`: the loop a peer that wrote the file
until the emit layer took to formatting it from arrays in one call
(runtime/bandwidth.shadowlog_text). It builds every line from Python
numbers, one `int()` and one `str()` a field, and is kept here, word for
word, so that the tests can hold the bulk formatters to its bytes."""

import numpy as np

from dst_libp2p_test_node_tpu.runtime.bandwidth import (
    CTRL_PKT_BYTES,
    HDR_BYTES,
    MSS_BYTES,
    PeerTraffic,
)

FLAG_BLOCK = 12        # summary_shadowlog.awk:4


def data_pkts(data_bytes):
    return np.ceil(data_bytes / MSS_BYTES)


def shadowlog_lines(traffic: PeerTraffic, sim_time: str = "00:15:00") -> list[str]:
    """One cumulative '[node]' heartbeat line per peer, field-compatible with
    summary_shadowlog.awk ($5 peer, $9 '[node]', $10 counters)."""
    out = []
    n = traffic.rx_bytes.shape[0]
    for i in range(n):
        rx = traffic.rx_bytes[i]
        tx = traffic.tx_bytes[i]
        crx, ctx = traffic.ctrl_rx[i], traffic.ctrl_tx[i]
        d_in_pkt = data_pkts(rx)
        d_out_pkt = data_pkts(tx)
        blocks = []
        blocks.append([0] * FLAG_BLOCK)  # inbound-localhost
        blocks.append([0] * FLAG_BLOCK)  # outbound-localhost
        for pkt, byt, ctrl in ((d_in_pkt, rx, crx), (d_out_pkt, tx, ctx)):
            b = [0] * FLAG_BLOCK
            b[0] = int(pkt + ctrl)                      # pkt
            b[1] = int(byt + ctrl * CTRL_PKT_BYTES)     # bytes
            b[2] = int(ctrl)                            # ctrl_pkt
            b[3] = int(ctrl * HDR_BYTES)                # ctrl_hdr_bytes
            b[6] = int(pkt)                             # data_pkt
            b[7] = int(pkt * HDR_BYTES)                 # data_hdr_bytes
            b[8] = int(byt)                             # data_bytes
            blocks.append(b)
        flags = ",".join(str(v) for b in blocks for v in b)
        rx_tot = int(rx + crx * CTRL_PKT_BYTES)
        tx_tot = int(tx + ctx * CTRL_PKT_BYTES)
        # $10 split on ",|;": arr[1]=tag, arr[2]=rx, arr[3]=tx,
        # arr[4..6] pad, arr[7..54] the four flag blocks
        stats = f"heartbeat;{rx_tot},{tx_tot},0,0,0;{flags}"
        out.append(
            f"{sim_time} [shadow] {sim_time} [INFO] pod-{i} n/a shadow "
            f"heartbeat [node] {stats}"
        )
    return out


def shadowlog_text(traffic: PeerTraffic, sim_time: str = "00:15:00") -> str:
    return "".join(ln + "\n" for ln in shadowlog_lines(traffic, sim_time))
