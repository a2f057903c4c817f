"""Publisher controller + the batched device-dispatch engine.

The reference drives publishing from outside the nodes: Shadow bakes
vacp2p/pod-api-requester into the runner image (shadow/Dockerfile:45-53) and
the generated shadow.yaml starts `traffic_sync.py -s <size> -m <messages>
-d <delay> -n <n> --peer-selection id` on the injector fast-node at t=500 s
(shadow/topogen.py:124-136); under K8s the 10ksim publisher does the same
(README.md:21). Either way the controller POSTs
`{"topic","msgSize","version"}` to the chosen node's :8645 /publish at a
fixed inter-message delay.

Two halves live here:

  - the HTTP injector for the `serve` mode (pure stdlib, below): peer
    selection mirrors the reference surface — `id` pins one publisher
    (run.sh publisher_id, run.sh:34), `rotation` advances to the next
    target after every message (run.sh:35, publisher_rotation), and
    `burst` posts back-to-back request groups so the resident service's
    batched dispatcher actually sees multi-request pump rounds.

  - the BATCHED DEVICE DISPATCH engine (ISSUE 14, ARCHITECTURE §16):
    `publish_batch_scan` stacks a pump round's same-shape publish requests
    into seed columns — per-request publisher rows, the chained PRNG and
    warm-offset columns riding in the carried SimState — and executes the
    whole batch as ONE compiled device dispatch (a lax.scan whose body is
    the ordinary disseminate program, padded to a static batch width with
    a per-column active cond). The scan carry IS the sequential publish
    chain — same key splits, same uplink/rx occupancy write-backs, same
    warm-start carry — so the stacked batch is bit-identical to the
    equivalent publish() loop while paying one dispatch instead of B
    (tests/test_batched_dispatch.py pins this bitwise). Simulator and
    MultiTopicSimulator expose it as `publish_batch`; the resident
    service's `dispatch_mode="batched"` rides on top.
"""

from __future__ import annotations

import json
import time
import urllib.error
import urllib.request
from dataclasses import dataclass

from ..config.env import HTTP_CONTROL_PORT


# ---------------------------------------------------------------------------
# Batched device dispatch (ISSUE 14): one compiled scan over seed columns.
# ---------------------------------------------------------------------------

def _batch_scan_impl(state, conns, rev, stage, lat_ms, bw, rows, active,
                     t0_ms, params, payload_bytes, fragments, with_gossip,
                     loss_stage, loss_mode, lat_edge, loss_edge, ans_tables,
                     valid_edge, with_fanout, topic_blocks):
    import jax
    import jax.numpy as jnp

    from ..ops.disseminate import disseminate

    def publish_one(st, row):
        res, new_st = disseminate(
            st, conns, rev, stage, lat_ms, bw,
            publisher=row, t0_ms=t0_ms, params=params,
            payload_bytes=payload_bytes, fragments=fragments,
            with_gossip=with_gossip, mesh=None,
            loss_stage=loss_stage, loss_mode=loss_mode,
            lat_edge=lat_edge, loss_edge=loss_edge,
            ans_tables=ans_tables, valid_edge=valid_edge,
            with_fanout=with_fanout)
        if topic_blocks > 1:
            # Cross-topic occupancy fold: uplink/rx are per NODE, not per
            # (topic, node) row, so fold the blocks before the next column
            # publishes — exactly what MultiTopicSimulator.publish does
            # between sequential dispatches.
            n = new_st.uplink_free_ms.shape[0] // topic_blocks
            u_node = new_st.uplink_free_ms.reshape(topic_blocks, n).max(axis=0)
            r_node = new_st.rx_free_ms.reshape(topic_blocks, n).max(axis=0)
            new_st = new_st.replace(
                uplink_free_ms=jnp.tile(u_node, topic_blocks),
                rx_free_ms=jnp.tile(r_node, topic_blocks))
        ys = {
            "delay_ms": res.delay_ms,
            "received": res.received,
            "sends": res.sends,
            "copies_rx": res.copies_rx,
            "ihave_sent": res.ihave_sent,
            "iwant_sent": res.iwant_sent,
            "answer_wait_max_ms": jnp.asarray(res.answer_wait_max_ms),
            "converged": jnp.asarray(res.converged),
        }
        return new_st, ys

    def body(st, x):
        row, live = x

        def on(st):
            return publish_one(st, row)

        def off(st):
            # Padding column: state passes through untouched (no key split,
            # no occupancy write-back) and the ys slot is all-zero.
            shapes = jax.eval_shape(lambda s: publish_one(s, row)[1], st)
            return st, jax.tree_util.tree_map(
                lambda sd: jnp.zeros(sd.shape, sd.dtype), shapes)

        return jax.lax.cond(live, on, off, st)

    new_state, ys = jax.lax.scan(body, state, (rows, active))
    return ys, new_state


_batch_scan_jit = None


def publish_batch_scan(state, conns, rev, stage, lat_ms, bw, rows, active,
                       t0_ms, params, payload_bytes, fragments, with_gossip,
                       loss_stage, loss_mode, lat_edge, loss_edge, ans_tables,
                       valid_edge, with_fanout, topic_blocks=1):
    """Execute a padded column batch of publishes as ONE device dispatch.

    `rows` is the (B,) int32 publisher-row column (for multi-topic sims the
    row is topic_index * n + publisher), `active` the (B,) bool padding mask;
    both are traced so every batch width up to the pad length shares one
    compiled program. The scan carry is the SimState, which makes the batch
    bit-identical to publishing the active columns sequentially: each column
    sees the previous column's key split, warm-offset advance, and uplink/rx
    occupancy exactly as publish() would. Returns (ys, new_state) where each
    ys leaf is stacked along the batch axis.
    """
    global _batch_scan_jit
    if _batch_scan_jit is None:
        import jax
        _batch_scan_jit = jax.jit(
            _batch_scan_impl,
            static_argnames=("params", "payload_bytes", "fragments",
                            "with_gossip", "loss_mode", "with_fanout",
                            "topic_blocks"))
    return _batch_scan_jit(
        state, conns, rev, stage, lat_ms, bw, rows, active, t0_ms, params,
        payload_bytes, fragments, with_gossip, loss_stage, loss_mode,
        lat_edge, loss_edge, ans_tables, valid_edge, with_fanout,
        topic_blocks)


@dataclass
class InjectResult:
    ok: int = 0
    failed: int = 0
    replies: list = None

    def __post_init__(self):
        if self.replies is None:
            self.replies = []


def publish_once(
    target: str, msg_size: int, topic: str = "test", version: int = 1,
    timeout_s: float = 10.0,
) -> dict:
    """POST one /publish to `target` (host[:port] or full URL)."""
    if not target.startswith("http"):
        if ":" not in target:
            target = f"{target}:{HTTP_CONTROL_PORT}"
        target = f"http://{target}"
    req = urllib.request.Request(
        f"{target}/publish",
        data=json.dumps(
            {"topic": topic, "msgSize": msg_size, "version": version},
            allow_nan=False,
        ).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout_s) as resp:
        return json.loads(resp.read().decode())


def inject(
    targets: list[str],
    msg_size: int,
    messages: int,
    delay_s: float,
    topic: str = "test",
    peer_selection: str = "id",
    publisher_id: int = 0,
    timeout_s: float = 10.0,
    burst: int = 1,
    sleep=time.sleep,
) -> InjectResult:
    """Drive `messages` publishes at `delay_s` spacing against `targets`.

    peer_selection: 'id' always hits targets[publisher_id % len];
    'rotation' advances one target per message (traffic_sync --peer-selection
    / run.sh publisher_rotation). `burst` > 1 posts that many messages
    back-to-back before sleeping, so a resident service's pump round sees a
    multi-request fair batch and the batched dispatcher has columns to
    stack."""
    if peer_selection not in ("id", "rotation"):
        raise ValueError(f"unknown peer_selection {peer_selection!r}")
    if burst < 1:
        raise ValueError(f"burst must be >= 1, got {burst}")
    res = InjectResult()
    idx = publisher_id % len(targets)
    for i in range(messages):
        if i > 0 and i % burst == 0 and delay_s > 0:
            sleep(delay_s)
        try:
            reply = publish_once(
                targets[idx], msg_size, topic=topic, timeout_s=timeout_s)
            res.ok += 1
            res.replies.append(reply)
        except (urllib.error.URLError, OSError, ValueError) as e:
            res.failed += 1
            res.replies.append({"status": "error", "message": str(e)})
        if peer_selection == "rotation":
            idx = (idx + 1) % len(targets)
    return res
