"""On-device flight recorder: opt-in per-heartbeat telemetry channels.

The reference harness's observability contract stops at CUMULATIVE counters
(latency lines + a Prometheus scrape of end-state totals, SURVEY §0); the
per-round dynamics — the coverage/score curves arXiv:2007.02754 uses to
characterize attacks — are invisible. This module records them ON DEVICE:
`telemetry_observables` reduces the live SimState to a fixed set of
per-round channels, and the scan runners stack them into a fixed-shape
(n_heartbeats, K) trace alongside their existing obs dicts.

The arming contract follows ops/faults.py exactly:

  * `TelemetryParams` is a frozen (hashable) dataclass passed as a STATIC
    jit argument. `record=False` (the default) means the recorder does not
    exist: `run_recorded_heartbeats` literally delegates to
    `run_heartbeats` — the same function, the same jit cache entry, the
    same output buffers — and the attack/fault/recovery runners take
    `telemetry=None` on exactly the pre-recorder trace. Bit-identity is
    pinned by tests/test_telemetry.py.
  * Armed, the channels are pure reductions over state the scan body
    already holds — no PRNG is consumed, no state leaf is written, so the
    protocol trajectory is bit-identical armed or not; only the scan's
    OUTPUT grows the tel_* keys.
  * Sharding is free: every channel is a full-array reduction (or a
    small-vector reduction) over the peer axis, so under the nested
    trials x peers grid (parallel/sharding.py) GSPMD inserts per-group
    partial reductions and the (steps,) curves land trial-sharded like
    the rest of the obs dict, gathered at unstack.

Channel catalog (K columns of the flight-recorder window; all float32):

  tel_mesh_coverage    fraction of live subscribed peers with >= 1 mesh edge
  tel_mean_degree      mean mesh degree over live subscribed peers
  tel_degree_hist      (degree_bins,) mesh-degree histogram, normalized;
                       last bin catches degree >= degree_bins - 1
  tel_score_q          (len(quantiles),) score quantiles over valid
                       directed edges (exact under the deferred-decay
                       protocol — the scales are applied on the fly)
  tel_graylisted_frac  fraction of valid edges scoring below the graylist
                       threshold (ALL edges — the attack obs key of the
                       same name is restricted to honest->attacker edges)
  tel_bytes_tx/rx      cumulative traffic totals (per-round deltas are a
                       host-side diff of the curve)
  tel_ihave/tel_iwant  cumulative IHAVE/IWANT control messages sent
  tel_queue_depth_ms   mean uplink backlog: max(uplink_free - t, 0) over
                       live subscribed peers (the answer-queue depth the
                       iwant_spam attack drives)
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from .heartbeat import _apply_decay, heartbeat_step, run_heartbeats
from .pull import neighbor_pull_bool
from .state import SimParams, SimState


@dataclass(frozen=True)
class TelemetryParams:
    """Static flight-recorder configuration (hashable -> jit static arg).

    `record=False` disables the recorder entirely: the runners delegate to
    their un-instrumented counterparts and no telemetry code is traced."""

    record: bool = False
    # mesh-degree histogram bins: [0, 1, .., degree_bins-2, >=degree_bins-1]
    degree_bins: int = 12
    # score quantiles over valid directed edges (fractions in [0, 1])
    quantiles: tuple = (0.1, 0.5, 0.9)

    @property
    def enabled(self) -> bool:
        return self.record

    def validate(self) -> None:
        if self.degree_bins < 2:
            raise ValueError(
                f"degree_bins must be >= 2, got {self.degree_bins}")
        if not self.quantiles:
            raise ValueError("need at least one score quantile")
        for q in self.quantiles:
            if not (0.0 <= q <= 1.0):
                raise ValueError(f"quantile {q} outside [0, 1]")


def telemetry_observables(
    state: SimState,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    params: SimParams,
    telemetry: TelemetryParams,
    batch_factor: int = 1,
    valid: jnp.ndarray | None = None,
    decay_scales=None,
    deg: jnp.ndarray | None = None,
) -> dict:
    """One round's telemetry channels as a dict of f32 scalars/vectors.

    `valid`: the (N, C) edge-validity conjunction when the caller already
    holds it (hoisted scans); recomputed otherwise. `decay_scales`: the
    deferred-decay (fmd_scale, slow_scale) pair — scores are reconstructed
    exactly as heartbeat_step's _score_now does, so recorded quantiles
    match the per-step-decayed values bit-for-bit. `deg`: the carried (N,)
    mesh degree when the carried-degree protocol holds (mesh ⊆ valid);
    requires `valid`."""
    live = state.alive & state.subscribed
    if valid is None:
        if deg is not None:
            raise ValueError("deg requires valid (the carried-degree "
                             "protocol's hoisted validity mask)")
        nbr_ok = neighbor_pull_bool(live, conns, rev, batch_factor)
        valid = ((conns >= 0) & state.alive[:, None] & nbr_ok
                 & state.subscribed[:, None])
    if deg is None:
        mesh = state.mesh_mask & valid
        deg = mesh.sum(axis=-1)
    else:
        mesh = state.mesh_mask  # caller guarantees mesh ⊆ valid
    f32 = jnp.float32
    n_live = jnp.maximum(live.sum(), 1).astype(f32)

    if decay_scales is not None:
        f_sc, s_sc = decay_scales
        sc = state.replace(
            fmd=_apply_decay(state.fmd, f_sc, params),
            slow_penalty=_apply_decay(state.slow_penalty, s_sc, params),
        ).score(params)
    else:
        sc = state.score(params)

    b = telemetry.degree_bins
    idx = jnp.clip(deg, 0, b - 1)
    # one-hot-compare histogram (no scatter: the (N, b) compare reduces
    # over the peer axis, which is what shards under the nested grid)
    hist = ((idx[:, None] == jnp.arange(b)) & live[:, None]).sum(axis=0)
    qs = jnp.asarray(telemetry.quantiles, dtype=f32)
    scv = jnp.where(valid, sc, jnp.nan)
    n_edges = jnp.maximum(valid.sum(), 1).astype(f32)
    backlog = jnp.maximum(state.uplink_free_ms - state.t_ms, 0.0)
    return {
        "tel_mesh_coverage": (live & (deg >= 1)).sum() / n_live,
        "tel_mean_degree": jnp.where(live, deg, 0).sum() / n_live,
        "tel_degree_hist": hist.astype(f32) / n_live,
        "tel_score_q": jnp.nanquantile(scv, qs).astype(f32),
        "tel_graylisted_frac": (
            (valid & (sc < params.graylist_threshold)).sum() / n_edges),
        "tel_bytes_tx": state.bytes_tx.sum().astype(f32),
        "tel_bytes_rx": state.bytes_rx.sum().astype(f32),
        "tel_ihave": state.ihave_tx.sum().astype(f32),
        "tel_iwant": state.iwant_tx.sum().astype(f32),
        "tel_queue_depth_ms": jnp.where(live, backlog, 0.0).sum() / n_live,
    }


def adaptive_observables(
    state: SimState,
    ctrl,
    attacker: jnp.ndarray,
    acting: jnp.ndarray,
    violations: jnp.ndarray,
) -> dict:
    """Attacker-side controller channels for the ADAPTIVE adversary
    (ops/adversary.py adaptive_round) — the recorder discipline applies:
    pure reductions over state the scan body already holds, no PRNG, no
    state write; only the armed scan's OUTPUT grows these keys. All f32
    scalars:

      adv_violation_rate    protocol violations accrued THIS round per
                            attacker (the live rate the duty cycle is
                            throttling; ~0 while the controller coasts)
      adv_throttled_frac    fraction of the cohort duty-cycled OFF this
                            round
      adv_regraft_attempts  cumulative backoff-expiry re-grafts sent
      adv_px_sybil_frac     fraction of OCCUPIED honest px_pool entries
                            holding attacker ids — how poisoned the repair
                            candidate lattice currently is (0.0 for a state
                            without repair leaves: nothing reads the pool
                            either)

    `ctrl` is the ops/state.AdaptiveCtrl carry; `acting` the (N,) bool
    flood mask the duty cycle chose; `violations` the round's scalar
    violation count."""
    f32 = jnp.float32
    n_att = jnp.maximum(attacker.sum(), 1).astype(f32)
    if state.px_pool is not None:
        honest = ~attacker & state.alive & state.subscribed
        occ = (state.px_pool >= 0) & honest[:, None]
        sybil = occ & attacker[jnp.clip(state.px_pool, 0)]
        px_sybil_frac = sybil.sum() / jnp.maximum(occ.sum(), 1).astype(f32)
    else:
        px_sybil_frac = f32(0.0)
    return {
        "adv_violation_rate": violations.astype(f32) / n_att,
        "adv_throttled_frac": (attacker & ~acting).sum() / n_att,
        "adv_regraft_attempts": ctrl.regrafts.sum().astype(f32),
        "adv_px_sybil_frac": px_sybil_frac,
    }


def run_recorded_heartbeats(
    state: SimState,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    params: SimParams,
    steps: int,
    telemetry: TelemetryParams | None = None,
    batch_factor: int = 1,
    spared: jnp.ndarray | None = None,
):
    """run_heartbeats with the flight recorder: returns (state, trace) where
    trace maps each tel_* channel to a (steps,) or (steps, k) curve.
    `spared` is run_heartbeats' (peers the churn draw does not kill).

    Disabled (`telemetry` None or record=False) this IS run_heartbeats —
    the same call, the same jit cache entry, the same output buffers — and
    the trace is {}. Armed, the scan preserves run_heartbeats' protocols
    exactly (hoisted validity, carried degree, deferred decay: the recorded
    scores apply the running scales on the fly), so the final state is
    bit-identical to the untraced runner; only the outputs grow."""
    if telemetry is None or not telemetry.enabled:
        return run_heartbeats(state, conns, rev, out_mask, params, steps,
                              spared=spared), {}
    telemetry.validate()
    return _run_recorded_heartbeats(
        state, conns, rev, out_mask, params, telemetry, steps, batch_factor,
        spared)


@partial(jax.jit,
         static_argnames=("params", "telemetry", "steps", "batch_factor"))
def _run_recorded_heartbeats(
    state: SimState,
    conns: jnp.ndarray,
    rev: jnp.ndarray,
    out_mask: jnp.ndarray,
    params: SimParams,
    telemetry: TelemetryParams,
    steps: int,
    batch_factor: int = 1,
    spared: jnp.ndarray | None = None,
):
    # mirror of ops/heartbeat._run_heartbeats with a per-round telemetry
    # emission — the hoist/carry/deferral decisions must stay in lockstep
    # (the bit-identity tests compare final states across the two)
    nbr_ok = None
    valid_pre = None
    if params.churn_down_per_hb == 0.0 and params.churn_up_per_hb == 0.0:
        nbr_ok = neighbor_pull_bool(
            state.alive & state.subscribed, conns, rev, batch_factor)
        valid_pre = ((conns >= 0) & state.alive[:, None] & nbr_ok
                     & state.subscribed[:, None])

    one = jnp.float32(1.0)
    if valid_pre is not None:
        mesh0 = state.mesh_mask & valid_pre
        state = state.replace(mesh_mask=mesh0)

        def body(carry, _):
            s, deg, f_sc, s_sc = carry
            s, deg = heartbeat_step(
                s, conns, rev, out_mask, params, batch_factor=batch_factor,
                nbr_ok=nbr_ok, valid_pre=valid_pre,
                decay_scales=(f_sc, s_sc), deg_in=deg)
            f2, s2 = f_sc * params.fmd_decay, s_sc * params.slow_decay
            # post-step the effective decay scale is the UPDATED carry (the
            # step defers its own end-of-round decay into it)
            obs = telemetry_observables(
                s, conns, rev, params, telemetry, batch_factor=batch_factor,
                valid=valid_pre, decay_scales=(f2, s2), deg=deg)
            return (s, deg, f2, s2), obs

        (state, _, f_sc, s_sc), trace = jax.lax.scan(
            body, (state, mesh0.sum(axis=-1), one, one), None, length=steps)
    else:
        def body(carry, _):
            s, f_sc, s_sc = carry
            s = heartbeat_step(
                s, conns, rev, out_mask, params, batch_factor=batch_factor,
                nbr_ok=nbr_ok, valid_pre=valid_pre,
                decay_scales=(f_sc, s_sc), spared=spared)
            f2, s2 = f_sc * params.fmd_decay, s_sc * params.slow_decay
            obs = telemetry_observables(
                s, conns, rev, params, telemetry, batch_factor=batch_factor,
                decay_scales=(f2, s2))
            return (s, f2, s2), obs

        (state, f_sc, s_sc), trace = jax.lax.scan(
            body, (state, one, one), None, length=steps)
    state = state.replace(
        fmd=_apply_decay(state.fmd, f_sc, params),
        slow_penalty=_apply_decay(state.slow_penalty, s_sc, params),
    )
    return state, trace
