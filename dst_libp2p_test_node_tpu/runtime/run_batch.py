"""`run <runs> ...` with runs > 1 as ONE batched experiment: what run.sh's
loop over its first positional does (shadow/run.sh:58-64, a Shadow run, a
`latencies<i>` and a summary a turn), with the R networks on the device
together.

R runs of one shape, seeds s ... s+R-1, one graph a seed: the states, the
index arrays and the hoisted tables stacked on a leading axis (ops/runs.py),
one warm-up scan, one dispatch a message for all of them (the same
publisher in every run: rotation is a schedule, not a draw; the clock all
share), one device->host read a message, the records split back out a run.
Each run keeps a `Simulator.host_side` for what is the host's: its graph,
its message ids, its records, and everything emitted from them, by the code
that emits a run made alone. Run i of a batch writes the bytes that
`run 1 ... --seed s+i-1` writes (tests/test_run_batch.py holds it to that).

The batch takes what `cli.cmd_run` gives it (`batch_refusal` there says
which calls keep the loop); `NotBatchable` is a graph-dependent refusal
found only while building.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax
import numpy as np

from ..ops import runs as runs_ops
from ..ops.disseminate import lanes_in_pull
from ..ops.pull import make_pull_bands, pull_rows_share
from . import simulator
from .profiling import counters, device_read, device_reads, span
from .simulator import (ExperimentConfig, Simulator, drain_heartbeat_carry,
                        message_publisher, record_from_result)

# the leaves of a publish's results a record is made from, read in one go
_RECORD_LEAVES = ("delay_ms", "received", "sends", "copies_rx", "ihave_sent",
                  "iwant_sent", "answer_wait_max_ms", "counters")
# the (N, C) leaves of a state: nothing emitted reads them, so the one read
# of the final states leaves them on the device
_EDGE_LEAVES = ("mesh_mask", "fanout_mask", "backoff_until", "fmd",
                "slow_penalty", "px_pool")


class NotBatchable(ValueError):
    """These runs cannot share a program: the caller makes them one by
    one."""


class RunBatch:
    def __init__(self, cfgs: list[ExperimentConfig], topology):
        """`cfgs`: the runs' configurations, equal but for `seed`."""
        import jax.numpy as jnp

        self.reads_before = device_reads()
        self.publish_dispatches = 0
        with span("batch/build", runs=len(cfgs)):
            self.runs = [Simulator.host_side(c, topology) for c in cfgs]
            first = self.runs[0]
            self.cfg, self.params = first.cfg, first.params
            with span("build/tables"):
                top = first.topology
                self._stage = jnp.asarray(top.stage_of_peer)
                self._lat = jnp.asarray(top.latency_ms)
                self._bw = jnp.asarray(top.bw_up_mbit)
                self._loss = (jnp.asarray(top.packet_loss)
                              if float(np.max(top.packet_loss)) > 0.0
                              else None)
                self.arrays = {
                    name: jnp.asarray(np.stack(
                        [getattr(r.graph, name) for r in self.runs]))
                    for name in ("conns", "rev", "out_mask")}
                self.states = runs_ops.init_states(
                    jnp.stack([jax.random.PRNGKey(c.seed) for c in cfgs]),
                    self.params)
                a = self.arrays
                (self._lat_edge, self._loss_edge, self._ans_tables,
                 self._valid_edge) = runs_ops.tables(
                    self.states.alive, self.states.subscribed, a["conns"],
                    a["rev"], self._stage, self._lat, self._loss,
                    self.cfg.with_gossip)
                self._pull_bands, self._pull_rows_share = (
                    self._stacked_pull_bands())
        self._hb_carry_ms = 0.0
        self._hb_unread: list = []

    def _stacked_pull_bands(self):
        """(bands, share): the runs' pull bands stacked (their shape is the
        index's alone, ops/pull.band_shape), or None where no run has them,
        and `pull_rows_share` of either; runs of which only some have them
        (a skewed graph among them) share no program."""
        t, a = self._ans_tables, self.arrays
        bands = [make_pull_bands(
            a["conns"][r], a["rev"][r],
            None if t is None else t.conns_sorted[r],
            None if t is None else t.rev_sorted[r])
            for r in range(len(self.runs))]
        have = sum(b is not None for b in bands)
        if have not in (0, len(bands)):
            raise NotBatchable(
                f"{have} of {len(bands)} graphs admit the pull bands")
        if not have:
            return None, pull_rows_share(None)
        import jax.numpy as jnp

        return (jax.tree_util.tree_map(lambda *x: jnp.stack(x), *bands),
                pull_rows_share(bands[0]))

    # ---------------------------------------------------------------- phases

    def advance(self, ms: float) -> None:
        """Advance every run's clock by `ms`: the heartbeats due, one scan
        for all."""
        steps, self._hb_carry_ms = drain_heartbeat_carry(
            self._hb_carry_ms, ms, self.params.heartbeat_ms)
        if steps > 0:
            a = self.arrays
            self.states, pulls = runs_ops._run_heartbeats(
                self.states, a["conns"], a["rev"], a["out_mask"],
                self.params, steps)
            self._hb_unread.append(pulls)

    def publish(self, publisher: int) -> None:
        """One message from `publisher` in every run, at the clock all
        share: one dispatch, one read, a record a run."""
        cfg, a = self.cfg, self.arrays
        message = len(self.runs[0].records)
        with span("publish", message=message, runs=len(self.runs)):
            with span("publish/prepare"):
                t_ms, pulls = device_read((self.states.t_ms, self._hb_unread))
                self._hb_unread = []
                for r, run in enumerate(self.runs):
                    run._note_heartbeat_pulls([p[r] for p in pulls])
                t0_ms = float(t_ms[0]) + self._hb_carry_ms
            with span("publish/dispatch"):
                res, self.states = simulator.disseminate(
                    self.states, a["conns"], a["rev"], self._stage,
                    self._lat, self._bw, publisher=publisher, t0_ms=t0_ms,
                    params=self.params,
                    payload_bytes=cfg.topo.msg_size_bytes,
                    fragments=cfg.topo.num_frags,
                    with_gossip=cfg.with_gossip, loss_stage=self._loss,
                    loss_mode=cfg.loss_mode, lat_edge=self._lat_edge,
                    loss_edge=self._loss_edge, ans_tables=self._ans_tables,
                    valid_edge=self._valid_edge,
                    pull_bands=self._pull_bands)
                self.publish_dispatches += 1
            with span("publish/read"):
                leaves = device_read(
                    {k: getattr(res, k) for k in _RECORD_LEAVES})
            with span("batch/split"):
                shape = a["conns"].shape[1:]
                for r, run in enumerate(self.runs):
                    rec = record_from_result(
                        SimpleNamespace(
                            **{k: v[r] for k, v in leaves.items()}),
                        msg_id=run._next_msg_id(t0_ms), publisher=publisher,
                        t0_ms=t0_ms,
                        drop_self=None if cfg.self_trigger else [publisher],
                        lanes_in_pull=lanes_in_pull(
                            shape, cfg.topo.num_frags),
                        pull_rows_share=self._pull_rows_share)
                    run.records.append(rec)
                    run._note_publish(rec)

    def run(self) -> None:
        """The experiment of every run, as `Simulator.run` schedules it:
        warm-up, then a message every `delay_seconds`, each from the
        schedule's publisher."""
        cfg = self.cfg
        with span("warmup"):
            self.advance(cfg.warmup_s * 1000.0)
        for i in range(cfg.topo.messages):
            if i > 0:
                with span("advance"):
                    self.advance(cfg.topo.delay_seconds * 1000.0)
            self.publish(message_publisher(cfg, i))
        with span("batch/split"):
            # what a run's shadowlog, bandwidth report and rate read of
            # its final state, and the scans nobody has read yet
            final, pulls = device_read((
                self.states.replace(**dict.fromkeys(_EDGE_LEAVES)),
                self._hb_unread))
            self._hb_unread = []
            for r, run in enumerate(self.runs):
                run.state = jax.tree_util.tree_map(lambda x: x[r], final)
                run._note_heartbeat_pulls([p[r] for p in pulls])
        self.counts = {
            "runs": len(self.runs),
            "publish_dispatches": self.publish_dispatches,
            "device_reads": device_reads() - self.reads_before}
        counters("batch/counters", **self.counts)
