"""kad-dht experiment runtime: the role-based DHT workload as sim phases.

Replays the reference kad-dht node's role program (kad-dht/main.nim:15-72)
against the batched Kademlia substrate (ops/kad.py):

  RoleBootstrap  passive anchors: seeded into every table, serve queries
                 (main.nim:34-38)
  RoleNormal     startup jitter myId*200 ms, connect to bootstraps, warmup =
                 5x FIND_NODE(self) @ 1 s + 15x FIND_NODE(random) @ 2 s
                 (core.nim:12-35), then idle steady state
  RoleProbe      jitter + bootstrap connect, then FIND_NODE(random) every 5 s
                 with a 30 s timeout, forever (core.nim:38-55)

One OS process per role in the reference becomes one batched lookup wave per
phase tick here: all normal nodes' warmup iteration i is a single find_node()
call over the normal-role origins, all probe ticks one call over the probe
origins. Log lines mirror the chronicles output (notice/debug key=value) so
the same eyeballs-and-grep workflow applies; the summary aggregates what the
reference leaves implicit in logs (census, hops, lookup latency, probe
success under the 30 s timeout).

The waves of a phase are dispatched back to back (`dispatch_waves`, which
the regression node's discovery runs too) and nothing is read back between
them: one device->host read after the last warm-up wave, one after the last
probe tick (`run/record`). The lookups are kept as arrays, a row a wave
(`Lookups`); the log's per-iteration lines and the summary come from those.
The phases note the program's own spans (runtime/profiling.span; inside
`cli.cmd_kad`'s turn they reach `--stats-json`): `run/topology`, `run/boot`,
`run/warmup` (a `warmup/wave` a FIND_NODE wave), `run/probe` (a `probe/tick`
a tick), `run/record`, `run/summary`.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..config.topology import Topology, TopoParams
from ..ops import kad
from .profiling import counters, span


@dataclass
class KadConfig:
    network_size: int = 100
    n_bootstrap: int = 3          # RoleBootstrap anchors (ids 0..n_bootstrap-1)
    n_probe: int = 10             # RoleProbe tail (highest ids)
    discovery: str = "kad-dht"    # DISCOVERY: kad-dht | extended (env.nim:29)
    muxer: str = "yamux"
    probe_duration_s: float = 60.0
    probe_period_s: float = 5.0   # core.nim:55
    probe_timeout_s: float = 30.0  # core.nim:47
    seed: int = 0
    topo: TopoParams | None = None
    n_buckets: int = 24
    k_bucket: int = 16
    # extended-mode dial-failure handling (ops/kad.evict_failed): a routing
    # entry survives `evict_max_fails - 1` failed dials, with exponential
    # backoff between retries, before it is evicted. The default (1, 0.0)
    # is the original immediate-eviction behavior.
    evict_max_fails: int = 1
    evict_backoff_ms: float = 0.0
    # origins a queried peer learns of one wave, the first so many in the
    # order of origin, round and pick; None: all of them, as KadDHT adds
    # every requester (ops/kad.find_node)
    learn_cap: int | None = kad.LEARN_CAP

    def validate(self) -> None:
        if self.discovery not in ("kad-dht", "extended"):
            raise ValueError(f"Unknown DISCOVERY: {self.discovery}")
        if self.n_bootstrap < 1:
            raise ValueError("need at least one bootstrap")
        if self.n_probe < 0:
            raise ValueError("n_probe must be >= 0")
        if self.n_bootstrap + self.n_probe > self.network_size:
            raise ValueError("roles exceed network size")
        if self.evict_max_fails < 1:
            raise ValueError("evict_max_fails must be >= 1")
        if self.evict_backoff_ms < 0.0:
            raise ValueError("evict_backoff_ms must be >= 0")
        if self.learn_cap is not None and self.learn_cap < 1:
            raise ValueError("learn_cap must be >= 1, or None for no cap")


def dispatch_waves(state, origins, kinds, key, stage, lat_ms, *, learn_cap,
                   wave_span: str, after_wave=None):
    """One `kad.find_node` wave of `origins` a kind, dispatched back to back;
    nothing is read back from the device. A "random" wave looks up fresh
    targets drawn from `key` (split once a wave); any other kind ("self",
    the regression node's "bootstrap") looks up the origins' own keys. Each
    dispatch is a span `wave_span` with the attribute `kind`.
    `after_wave(state, origins, result) -> state`: what the caller does on
    the tables as the wave left them, before the next one starts. Returns
    (state, key, [(kind, targets, LookupResult), ...]), all on the device."""
    import jax

    waves = []
    for kind in kinds:
        if kind == "random":
            key, k = jax.random.split(key)
            targets = kad.random_targets(k, origins.shape[0])
        else:
            targets = state.keys[origins]
        with span(wave_span, kind=kind):
            res, state = kad.find_node(state, origins, targets, stage,
                                       lat_ms, learn_cap=learn_cap)
            if after_wave is not None:
                state = after_wave(state, origins, res)
        waves.append((kind, targets, res))
    return state, key, waves


def wave_numbers(waves) -> list:
    """What the one read after a phase takes of its waves, still on the
    device: a wave's (hops, n_queries, latency_ms, learn_counts, packed)."""
    return [(res.hops, res.n_queries, res.latency_ms, res.learn_counts,
             res.packed) for _, _, res in waves]


def latency_percentiles(latency_ms: np.ndarray) -> dict:
    return {"p50": float(np.percentile(latency_ms, 50)),
            "p99": float(np.percentile(latency_ms, 99))}


@dataclass
class Lookups:
    """The lookups of a phase, a row a wave and a column an origin (the
    same origins in every wave)."""
    origins: np.ndarray       # (Q,) int32
    kinds: list[str]          # a wave: "self" | "random" | "probe"
    hops: np.ndarray          # (W, Q) int32
    n_queries: np.ndarray     # (W, Q) int32
    latency_ms: np.ndarray    # (W, Q) float32
    timed_out: np.ndarray     # (W, Q) bool: over the probe time-out
    learn_counts: np.ndarray  # (W, 2) int32: offered, found the bucket full
    packed: np.ndarray        # (W,) bool: answered from the packed heads alone

    @classmethod
    def of(cls, origins, kinds, numbers, timeout_ms: float) -> "Lookups":
        """From the read of `wave_numbers`."""
        hops, queries, latency, learned, packed = (
            np.stack(column) for column in zip(*numbers))
        return cls(origins=np.asarray(origins), kinds=list(kinds), hops=hops,
                   n_queries=queries, latency_ms=latency,
                   timed_out=latency > timeout_ms, learn_counts=learned,
                   packed=packed)

    @property
    def count(self) -> int:
        return int(self.hops.size)


@dataclass
class KadSummary:
    census_mean: float
    census_min: int
    census_max: int
    warmup_lookups: int
    probe_lookups: int
    probe_success: int
    lookup_latency_ms_p50: float
    lookup_latency_ms_p99: float
    hops_mean: float
    queries_per_bootstrap: float
    # of the random lookups checked against a brute force over every key
    # (the last random warm-up wave's and the probes'), the share whose first
    # returned peer is the closest there is; NaN when none was checked
    closest1_share: float = float("nan")

    @property
    def probe_success_share(self) -> float:
        return (self.probe_success / self.probe_lookups
                if self.probe_lookups else float("nan"))

    def report(self) -> str:
        to = self.probe_lookups - self.probe_success
        return "\n".join([
            "Kad-DHT summary",
            f"Routing table census: mean {self.census_mean:.1f} "
            f"(min {self.census_min}, max {self.census_max})",
            f"Warmup lookups: {self.warmup_lookups}",
            f"Probe lookups: {self.probe_lookups} "
            f"({self.probe_success} ok, {to} timed out)",
            f"Probe success share: {self.probe_success_share * 100.0:.1f}%",
            f"Lookup latency ms: p50 {self.lookup_latency_ms_p50:.0f} "
            f"p99 {self.lookup_latency_ms_p99:.0f}",
            f"Lookup hops: mean {self.hops_mean:.2f}",
            f"Closest peer returned first: "
            f"{self.closest1_share * 100.0:.1f}%",
            f"FIND_NODE served per bootstrap: {self.queries_per_bootstrap:.0f}",
        ])


class KadSimulator:
    """Batched role-program driver over ops/kad (one instance per run)."""

    def __init__(self, cfg: KadConfig):
        import jax
        import jax.numpy as jnp

        cfg.validate()
        self.cfg = cfg
        n = cfg.network_size
        topo = cfg.topo or TopoParams(
            network_size=n, muxer=cfg.muxer, msg_size_bytes=100
        )
        with span("run/topology"):
            self.topology = Topology.build(topo)
        self._stage = jnp.asarray(self.topology.stage_of_peer)
        self._lat = jnp.asarray(self.topology.latency_ms)
        self.state = kad.init_kad_state(
            n, n_buckets=cfg.n_buckets, k_bucket=cfg.k_bucket, seed=cfg.seed
        )
        self._probe_key = jax.random.PRNGKey(cfg.seed ^ 0x9406E)
        self.bootstraps = jnp.arange(cfg.n_bootstrap, dtype=jnp.int32)
        self.normals = jnp.arange(
            cfg.n_bootstrap, n - cfg.n_probe, dtype=jnp.int32
        )
        self.probes = jnp.arange(n - cfg.n_probe, n, dtype=jnp.int32)
        # DISCOVERY=extended mounts KademliaDiscovery instead of KadDHT
        # (kad-dht/helpers.nim:36-59): discovery connects to what it finds,
        # so each lookup wave ends with dial-backs from the found peers
        self.extended = cfg.discovery == "extended"
        self.t_ms = 0.0
        self.lines: list[str] = []
        # the lookups of each phase, from the phase's one read
        self.warm: Lookups | None = None
        self.probed: Lookups | None = None
        # random lookups held to the brute force, and how many returned the
        # closest peer first: the last random warm-up wave's, the probes'
        self._closest1 = np.zeros(2, np.int64)       # checked, hits
        # the tables' census, the requests sent and served, as the last
        # read found them
        self._final: dict | None = None

    # ------------------------------------------------------------------ util

    def _log(self, line: str) -> None:
        self.lines.append(line)

    def _phase(self, origins, kinds, wave_span: str, period_ms: float,
               with_census: bool = False):
        """The waves of one phase, a `period_ms` apart on the role
        program's clock, and with `with_census` the tables' census after
        each (the reference's warm-up census, core.nim:17-22), all still on
        the device; in extended (KademliaDiscovery) mode the origins
        then connect to the peers they found (kad.connect_found dial-backs)
        and evict entries whose dial failed (kad.evict_failed, under the
        configured retry budget + backoff), the mode's observable
        differences: symmetric knowledge and tables that self-clean under
        churn."""
        import jax.numpy as jnp

        cfg = self.cfg
        census = []

        def after_wave(state, origins, res):
            if self.extended:
                # sync the device clock to the role program's host clock
                # so the eviction backoff deadlines are measured in real
                # sim time; failed dials (dead entries) are counted against
                # the entry's retry budget and evicted once it is
                # exhausted, successful ones teach the found peer the
                # origin
                state = state.replace(
                    t_ms=jnp.asarray(self.t_ms, jnp.float32))
                state = kad.evict_failed(
                    state, origins, res.closest,
                    max_fails=cfg.evict_max_fails,
                    backoff_base_ms=cfg.evict_backoff_ms)
                state = kad.connect_found(state, origins, res.closest)
            self.t_ms += period_ms
            if with_census:
                census.append(kad.rtable_census(state))
            return state

        self.state, self._probe_key, waves = dispatch_waves(
            self.state, origins, kinds, self._probe_key, self._stage,
            self._lat, learn_cap=cfg.learn_cap, wave_span=wave_span,
            after_wave=after_wave)
        return waves, census

    def _closest1_hits(self, waves):
        """On the device: of these waves' lookups, how many returned first
        the peer a brute force over every key finds closest."""
        import jax.numpy as jnp

        targets = jnp.concatenate([t for _, t, _ in waves])
        first = jnp.concatenate([res.closest[:, 0] for _, _, res in waves])
        return (kad.closest_peer(self.state.keys, targets) == first).sum()

    def _final_numbers(self):
        """Still on the device: what `summary` wants of the final state."""
        st = self.state
        return {"census": kad.rtable_census(st),
                "queries_tx": st.queries_tx.sum(),
                "queries_rx": st.queries_rx.sum(),
                "served_by_bootstrap": st.queries_rx[:self.cfg.n_bootstrap]}

    # ---------------------------------------------------------------- phases

    def boot(self) -> None:
        """Node starts + jittered bootstrap connects (main.nim:28-47). The
        per-node jitter (myId*200 ms) staggers dials; batched seeding is its
        fixed point — every node ends with the anchors in its table."""
        cfg = self.cfg
        for b in range(cfg.n_bootstrap):
            self._log(f"Node started peer={b} role=RoleBootstrap "
                      f"discovery={cfg.discovery}")
        with span("run/boot"):
            self.state = kad.seed_bootstraps(self.state, self.bootstraps)
        max_jitter = (cfg.network_size - 1) * 200.0
        self.t_ms += max_jitter + 10_000.0  # jitter + dial/backoff envelope
        n_conn = cfg.network_size - cfg.n_bootstrap
        self._log(f"Connected to bootstrap nodes={n_conn} "
                  f"anchors={cfg.n_bootstrap}")

    def warmup(self) -> None:
        """5x FIND_NODE(self) @ 1 s + 15x FIND_NODE(random) @ 2 s over all
        RoleNormal nodes (core.nim:12-35)."""
        import jax

        origins = self.normals
        if origins.shape[0] == 0:
            return
        with span("run/warmup"):
            selfs, census = self._phase(origins, ["self"] * 5,
                                        "warmup/wave", 1000.0,
                                        with_census=True)
            randoms, _ = self._phase(origins, ["random"] * 15,
                                     "warmup/wave", 2000.0)
            hits = self._closest1_hits(randoms[-1:])
        with span("run/record"):
            # the one device->host read of the warm-up
            numbers, census, hits = jax.device_get(
                (wave_numbers(selfs + randoms), census, hits))
            self.warm = Lookups.of(
                origins, [kind for kind, _, _ in selfs + randoms], numbers,
                self.cfg.probe_timeout_s * 1000.0)
            self._closest1 += (len(origins), int(hits))
            self._log("Starting warmup phase")
            for i, kind in enumerate(self.warm.kinds):
                took = latency_percentiles(self.warm.latency_ms[i])
                if kind == "self":
                    self._log(f"Warmup: Finding self iteration={i + 1}")
                    self._log(
                        f"Kad routing table peers={census[i].mean():.1f} "
                        f"buckets={self.cfg.n_buckets}")
                else:
                    self._log(
                        f"Warmup: Finding random node iteration={i - 4}")
                self._log(
                    f"Warmup: Lookups done count={len(origins)} "
                    f"hops={self.warm.hops[i].mean():.2f} "
                    f"p50Ms={took['p50']:.0f} p99Ms={took['p99']:.0f}")
            self._log("Warmup complete")

    def probe(self, duration_s: float | None = None) -> None:
        """FIND_NODE(random) every probe_period_s over all RoleProbe nodes
        (core.nim:38-55); a lookup exceeding the 30 s timeout is a
        'Probe Failed'."""
        import jax

        cfg = self.cfg
        origins = self.probes
        dur = duration_s if duration_s is not None else cfg.probe_duration_s
        ticks = (max(int(dur / cfg.probe_period_s), 1)
                 if origins.shape[0] else 0)
        with span("run/probe"):
            waves, _ = self._phase(origins, ["random"] * ticks,
                                   "probe/tick", cfg.probe_period_s * 1000.0)
            hits = self._closest1_hits(waves) if waves else 0
        with span("run/record"):
            # the one device->host read of the probe loop, and of the state
            # it leaves
            numbers, targets, hits, self._final = jax.device_get(
                (wave_numbers(waves), [t[:, :2] for _, t, _ in waves], hits,
                 self._final_numbers()))
            if not waves:
                return
            self.probed = Lookups.of(origins, ["probe"] * ticks, numbers,
                                     cfg.probe_timeout_s * 1000.0)
            self._closest1 += (self.probed.count, int(hits))
            self._log("Starting probe loop")
            for tick, words in enumerate(targets):
                for (hi, lo), late in zip(words.tolist(),
                                          self.probed.timed_out[tick]):
                    if late:
                        self._log(f"Probe Failed target={hi:08x}{lo:08x} "
                                  "success=false")
                    else:
                        self._log(
                            f"Probe: Finding node target={hi:08x}{lo:08x}")

    def run(self) -> KadSummary:
        self.boot()
        self.warmup()
        self.probe()
        with span("run/summary"):
            summary = self.summary()
        counters("kadnode/counters", **self.counters(summary))
        return summary

    # --------------------------------------------------------------- outputs

    def _phases(self) -> list[Lookups]:
        return [p for p in (self.warm, self.probed) if p is not None]

    def summary(self) -> KadSummary:
        import jax

        if self._final is None:     # no probe loop ran: its read is made here
            self._final = jax.device_get(self._final_numbers())
        census = self._final["census"]
        phases = self._phases()
        lats = (np.concatenate([p.latency_ms.reshape(-1) for p in phases])
                if phases else np.zeros(1))
        hops = (np.concatenate([p.hops.reshape(-1) for p in phases])
                if phases else np.zeros(1))
        took = latency_percentiles(lats)
        checked, hits = self._closest1.tolist()
        return KadSummary(
            census_mean=float(census.mean()),
            census_min=int(census.min()),
            census_max=int(census.max()),
            warmup_lookups=self.warm.count if self.warm else 0,
            probe_lookups=self.probed.count if self.probed else 0,
            probe_success=int((~self.probed.timed_out).sum())
            if self.probed else 0,
            lookup_latency_ms_p50=took["p50"],
            lookup_latency_ms_p99=took["p99"],
            hops_mean=float(hops.mean()),
            queries_per_bootstrap=float(
                self._final["served_by_bootstrap"].mean()),
            closest1_share=hits / checked if checked else float("nan"),
        )

    def counters(self, s: KadSummary) -> dict:
        """What the `kadnode/counters` annotation carries, one an
        experiment, all from the phases' two reads (`s`: the summary)."""
        phases = self._phases()
        lookups = sum(p.count for p in phases)
        offered, full = (sum(p.learn_counts.sum(axis=0) for p in phases)
                         if phases else (0, 0))
        return {
            "lookups": lookups,
            "warmup_waves": len(self.warm.kinds) if self.warm else 0,
            "probe_ticks": len(self.probed.kinds) if self.probed else 0,
            "hops_mean": s.hops_mean,
            "queries_per_lookup": float(
                sum(int(p.n_queries.sum()) for p in phases)
                / max(lookups, 1)),
            "census_mean": s.census_mean,
            "census_min": s.census_min,
            "probe_success_share": s.probe_success_share,
            "closest1_share": s.closest1_share,
            "bucket_full_share": float(full) / max(float(offered), 1.0),
            "packed_share": float(
                sum(int(p.packed.sum()) for p in phases)
                / max(sum(len(p.kinds) for p in phases), 1)),
        }

    def stats(self, s: KadSummary) -> dict:
        """`--stats-json` "kad": the counters, the requests sent and served
        summed over the peers, and a wave's lookup latency, in order."""
        return {
            **self.counters(s),
            "queries_tx": int(self._final["queries_tx"]),
            "queries_rx": int(self._final["queries_rx"]),
            "queries_per_bootstrap": s.queries_per_bootstrap,
            "lookup_latency_ms": [
                {"kind": kind, **latency_percentiles(p.latency_ms[i])}
                for p in self._phases() for i, kind in enumerate(p.kinds)],
            "probe_lookups": s.probe_lookups,
            "probe_success": s.probe_success,
        }


def config_from_env() -> KadConfig:
    """NODE_ROLE/DISCOVERY/MUXER env surface (kad-dht/env.nim:8-35) mapped to
    a whole-experiment config (the per-process NODE_ROLE becomes role counts:
    the simulator owns every role at once)."""
    from ..config.env import env_int, env_str

    # KAD_LEARN_CAP: a count, or "all" for no cap (KadConfig.learn_cap)
    cap = env_str("KAD_LEARN_CAP", str(kad.LEARN_CAP))
    return KadConfig(
        network_size=env_int("PEERS", 100),
        n_bootstrap=env_int("KAD_BOOTSTRAPS", 3),
        n_probe=env_int("KAD_PROBES", 10),
        discovery=env_str("DISCOVERY", "kad-dht"),
        muxer=env_str("MUXER", "yamux"),
        seed=env_int("SEED", 0),
        learn_cap=None if cap == "all" else int(cap),
    )
