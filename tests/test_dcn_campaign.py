"""Multi-host DCN campaign engine: rank-0 merge + GA-S006 golden pairs +
the two-process end-to-end equality gate.

Layers:

  1. merge_dcn_rank_results on synthetic per-process payloads: canonical
     fractions x seeds re-ordering, aggregate folding (retries summed,
     degraded any, quarantine concatenated, conformance from rank 0),
     infinite-hb_budget round-trip through the strict-JSON null, and the
     claim validators (overlapping / missing seeds, non-contiguous ranks)
     that keep a stale rank file from silently double- or drop-counting.
  2. GA-S006 golden bad/clean pair traced in-test (test_sharding_audit.py
     style): an all-gather whose replica groups span two 4-device process
     blocks fires, the same gather confined to one block's ICI submesh
     stays clean with zero cross-DCN bytes.
  3. The launcher (scripts/dcn_campaign.py): two gloo processes over a
     dcn x trials x peers grid must produce observables bit-identical to
     the single-process nested campaign on the same grid. Slow-marked —
     the CI dcn-campaign job runs the launcher directly on every push;
     this test is the local reproduction of that gate.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dst_libp2p_test_node_tpu.analysis import (
    EntrypointContract,
    TraceSpec,
    audit_sharding_contract,
)
from dst_libp2p_test_node_tpu.runtime.campaign import (
    CampaignConfig,
    DCN_RANK_FORMAT,
    merge_dcn_rank_results,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- layer 1:
# the rank-0 merge on synthetic per-process payloads


def _trial(fraction, seed, **kw):
    """Minimal strict-JSON trial dict as a rank file carries it (the
    sanitizer has already mapped any non-finite float to None)."""
    base = dict(
        scenario="sybil_graft_flood", fraction=fraction, seed=seed,
        attackers=12, honest_coverage=1.0, benign_coverage=1.0,
        latency_p50_ms=120.0, latency_p99_ms=340.0, benign_p50_ms=118.0,
        latency_inflation=1.02, hb_to_graylist=-1, hb_budget=None,
        graylisted_frac_final=0.0, mesh_recovery_hb=-1,
        attacker_mesh_share_final=0.1, attacker_score_final=-3.0,
        wall_s=0.5)
    base.update(kw)
    return base


def _payload(rank, nproc, seeds, fractions, **kw):
    p = dict(
        format_version=DCN_RANK_FORMAT, rank=rank, nproc=nproc,
        seeds=list(seeds), scenario="sybil_graft_flood", network_size=64,
        hb_budget=None, wall_s=1.0 + rank, degraded=False,
        retries_total=rank, quarantined_trials=[],
        conformance={"clean": True} if rank == 0 else None,
        trials=[_trial(f, s) for f in fractions for s in seeds])
    p.update(kw)
    return p


def _cfg(seeds=(0, 1, 2, 3), fractions=(0.0, 0.2)):
    return CampaignConfig(seeds=tuple(seeds), fractions=tuple(fractions))


def test_merge_reorders_to_canonical_sweep_order():
    """Round-robin seed slices arrive rank-major; the merge must emit the
    single-process order (fractions outer, cfg.seeds inner) regardless of
    payload list order, and fold the aggregates."""
    cfg = _cfg()
    p1 = _payload(1, 2, (1, 3), cfg.fractions, retries_total=3,
                  degraded=True, quarantined_trials=[[0.2, 3]])
    p0 = _payload(0, 2, (0, 2), cfg.fractions, retries_total=2)
    merged = merge_dcn_rank_results(cfg, [p1, p0])  # reversed on purpose
    cells = [(t.fraction, t.seed) for t in merged.trials]
    assert cells == [(f, s) for f in cfg.fractions for s in cfg.seeds]
    assert merged.retries_total == 5
    assert merged.degraded is True
    assert merged.quarantined_trials == [[0.2, 3]]
    assert merged.conformance == {"clean": True}   # rank 0's certificate
    assert merged.wall_s == 2.0                    # max over ranks


def test_merge_wall_override_and_infinite_budget_restore():
    """The collective's max wall-clock wins over per-rank walls, and the
    strict-JSON null a legitimately-infinite hb_budget sanitized to is
    restored so the merged result round-trips a nested campaign's."""
    cfg = _cfg(seeds=(0, 1), fractions=(0.0,))
    payloads = [_payload(0, 2, (0,), (0.0,)), _payload(1, 2, (1,), (0.0,))]
    merged = merge_dcn_rank_results(cfg, payloads, wall_s=7.5)
    assert merged.wall_s == 7.5
    assert math.isinf(merged.hb_budget)


def test_merge_rejects_overlapping_seed_claims():
    cfg = _cfg(seeds=(0, 1), fractions=(0.0,))
    payloads = [_payload(0, 2, (0,), (0.0,)),
                _payload(1, 2, (0,), (0.0,))]   # rank 1 re-claims seed 0
    with pytest.raises(ValueError, match="claimed by ranks"):
        merge_dcn_rank_results(cfg, payloads)


def test_merge_rejects_unclaimed_seed():
    cfg = _cfg(seeds=(0, 1, 5), fractions=(0.0,))
    payloads = [_payload(0, 2, (0,), (0.0,)), _payload(1, 2, (1,), (0.0,))]
    with pytest.raises(ValueError, match=r"seeds \[5\] claimed by no rank"):
        merge_dcn_rank_results(cfg, payloads)


def test_merge_rejects_noncontiguous_rank_set():
    cfg = _cfg(seeds=(0, 1), fractions=(0.0,))
    payloads = [_payload(0, 3, (0,), (0.0,)), _payload(2, 3, (1,), (0.0,))]
    with pytest.raises(ValueError, match="not contiguous"):
        merge_dcn_rank_results(cfg, payloads)


# ---------------------------------------------------------------- layer 2:
# GA-S006 golden pair: cross-DCN gather fires, block-local gather is clean


def _dcn_gather_fixture(*, cross):
    """(fn, args) on the 8 virtual devices split as two 4-device process
    blocks. cross=True shards rows over ALL devices so gathering to
    replicated needs replica groups spanning both blocks (the GA-S006
    mutant); cross=False shards rows over the in-block peers axis only, so
    the same gather runs once per block on its own ICI submesh."""
    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("dcn", "peers"))
    spec = P(("dcn", "peers")) if cross else P("peers")
    x = jax.device_put(jnp.ones((64, 64), jnp.float32),
                       NamedSharding(mesh, spec))

    def fn(x):
        return jax.lax.with_sharding_constraint(
            x * 2.0, NamedSharding(mesh, P()))

    return fn, (x,)


def _dcn_contract(name, *, cross):
    fn, args = _dcn_gather_fixture(cross=cross)
    return EntrypointContract(
        name=name, build=lambda: TraceSpec(fn, args),
        collectives=frozenset({"all-gather"}),
        dcn_block_devices=4, dcn_collective_bytes_budget=0)


def test_ga_s006_cross_dcn_gather_fires():
    c = _dcn_contract("fixture/cross-dcn-gather", cross=True)
    violations, waived, facts = audit_sharding_contract(c)
    assert sorted({v.rule for v in violations}) == ["GA-S006"]
    assert waived == []
    assert facts["collective_bytes_by_scope"]["cross_dcn"] > 0
    assert "all-gather" in facts["cross_dcn_collectives"]


def test_ga_s006_clean_when_gather_stays_in_block():
    c = _dcn_contract("fixture/block-local-gather", cross=False)
    violations, _waived, facts = audit_sharding_contract(c)
    assert violations == [], [v.to_dict() for v in violations]
    assert facts["collective_bytes_by_scope"]["cross_dcn"] == 0
    # the gather still happened — on each block's own ICI submesh
    assert facts["collective_bytes_by_scope"]["intra_process"] > 0


# ---------------------------------------------------------------- layer 3:
# two-process campaign == single-process nested campaign, bit-identical


def _gloo_available():
    # the workers pin jax.config.update("jax_cpu_collectives_implementation",
    # "gloo"); a jax build without that config entry has no CPU gloo backend
    return "jax_cpu_collectives_implementation" in getattr(
        jax.config, "values", {})


@pytest.mark.slow
@pytest.mark.skipif(not _gloo_available(),
                    reason="jax build has no CPU gloo collectives")
def test_two_process_dcn_campaign_matches_single_process(tmp_path):
    """The launcher's own equality oracle: merged two-process observables
    must equal the single-process nested campaign bit-for-bit (timing
    fields excluded). Exit code 0 IS that assertion; re-check the artifact
    here anyway. The ci.yml dcn-campaign job runs this same launcher on
    every push — this test is the local reproduction."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = tmp_path / "dcn_probe.json"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "dcn_campaign.py"),
         "--out", str(out), "--workdir", str(tmp_path / "work"),
         "--seeds", "2", "--fractions", "0.0,0.2", "--heartbeats", "2"],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    probe = json.loads(out.read_text())
    assert probe["bit_identical"] is True
    assert probe["trials"] == 4
    assert probe["nproc"] == 2
    assert probe["honest_coverage_min"] >= 0.0
