"""R runs of one experiment as one program each: the batch axis over seed
and graph (run.sh's first positional, `runs`, runtime/run_batch.py).

Every leaf a run owns (its state, its graph's index arrays, the tables made
from them) is stacked on a leading axis of R; what the runs share (the
topology's stage tables, the publisher, the clock) is not. A program here is
`lax.map` of the solo program over that axis: the body is the solo
program's own trace at the solo shapes, so its `lax.cond` skips stay skips,
a `while_loop` stops where that run's fixpoint stops, every `(N, C)` draw
comes from that run's key, and the pull bands and sparse iterations that are
built for one `(N, C)` index keep it. Run r of a batch is therefore the run
made alone, bit for bit (tests/test_run_batch.py), and the device's work is
R times a solo run's in one dispatch. A `vmap` over runs would turn every
cond into a select that runs both branches (PERF.md section 4: ten times a
benign scan a trial and heartbeat in the attack windows).

`disseminate` and `_run_heartbeats` carry the solo programs' names on
purpose: the XLA modules are `jit_disseminate` and `jit__run_heartbeats`
whichever route ran, which is what a reader of the device profile looks
for, and the solo scopes (`sample`, `fast`, `refine`, `accounting`; the
scan's) lie under them unchanged.
"""

from __future__ import annotations

from functools import partial

import jax

from . import heartbeat as _heartbeat
from .disseminate import answer_tables, edge_tables, valid_edge_of
from .disseminate import disseminate as _disseminate
from .state import SimParams, state_from_key


@partial(jax.jit, static_argnames=("params",))
def init_states(keys, params: SimParams):
    """The R initial states: `init_state` of every run's `PRNGKey(seed)`
    (`keys`, (R, 2))."""
    return jax.lax.map(lambda key: state_from_key(params, key), keys)


@partial(jax.jit, static_argnames=("with_gossip",))
def tables(alive, subscribed, conns, rev, stage, lat_ms, loss_stage,
           with_gossip: bool):
    """What a Simulator hoists out of its publishes, for R graphs in one
    dispatch: (lat_edge, loss_edge, ans_tables, valid_edge), each leaf
    (R, N, C); `loss_edge` None at loss 0 and `ans_tables` None without
    gossip, as a Simulator has them."""
    def one(run):
        alive, subscribed, conns, rev = run
        lat_edge, loss_edge = edge_tables(stage, lat_ms, conns, rev,
                                          loss_stage)
        return (lat_edge, loss_edge,
                answer_tables(lat_edge, conns, rev) if with_gossip else None,
                valid_edge_of(alive, subscribed, conns, rev))

    return jax.lax.map(one, (alive, subscribed, conns, rev))


@partial(jax.jit, static_argnames=("params", "steps"))
def _run_heartbeats(states, conns, rev, out_mask, params: SimParams,
                    steps: int):
    """`steps` heartbeats of every run: (states, pulls (R, 3, 3))."""
    return jax.lax.map(
        lambda run: _heartbeat._run_heartbeats(*run, params, steps),
        (states, conns, rev, out_mask))


@partial(
    jax.jit,
    static_argnames=("params", "payload_bytes", "fragments", "with_gossip",
                     "loss_mode"),
)
def disseminate(states, conns, rev, stage, lat_ms, bw_up_mbit_per_stage,
                publisher, t0_ms, params: SimParams, payload_bytes: int,
                fragments: int = 1, with_gossip: bool = True,
                loss_stage=None, loss_mode: str = "tcp", lat_edge=None,
                loss_edge=None, ans_tables=None, valid_edge=None,
                pull_bands=None):
    """One message from `publisher` at `t0_ms` in every run: (results,
    states, plans), each leaf with the runs' axis in front. The plan is
    always returned, as `runtime/simulator.disseminate` says why."""
    def one(run):
        state, conns, rev, lat_edge, loss_edge, ans_tables, valid_edge, \
            pull_bands = run
        return _disseminate(
            state, conns, rev, stage, lat_ms, bw_up_mbit_per_stage,
            publisher=publisher, t0_ms=t0_ms, params=params,
            payload_bytes=payload_bytes, fragments=fragments,
            with_gossip=with_gossip, loss_stage=loss_stage,
            loss_mode=loss_mode, lat_edge=lat_edge, loss_edge=loss_edge,
            ans_tables=ans_tables, valid_edge=valid_edge,
            pull_bands=pull_bands, return_plan=True)

    return jax.lax.map(one, (states, conns, rev, lat_edge, loss_edge,
                             ans_tables, valid_edge, pull_bands))
