"""The bench-ladder gate logic (bench_configs.py --check) as a unit.

The gates themselves must be trustworthy: a silent coverage collapse or a
wall-time regression has to flip the exit code, and the churn config's
expectation is DERIVED (two-state Markov transient), not a frozen number.
"""

import importlib.util
import json
import math
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load():
    spec = importlib.util.spec_from_file_location(
        "bench_configs", os.path.join(REPO, "bench_configs.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("bench_configs", mod)
    spec.loader.exec_module(mod)
    return mod


bc = _load()


def _r(config, cov=1.0, p50=200.0, p99=400.0, wall=5.0, peers=1000):
    return {"config": config, "peers": peers, "wall_s": wall,
            "peer_rounds_per_sec": 1.0, "coverage": cov,
            "p50_ms": p50, "p99_ms": p99}


def test_derived_churn_expectation_matches_committed_artifact():
    # the committed config-4 coverage must sit inside the derived Markov
    # band — the gate's expectation explains the artifact, it doesn't
    # memorize it
    want = bc.expected_alive_fraction(0.001, 0.0005, 62.0)
    assert 0.93 < want < 0.95
    with open(bc.ARTIFACT) as f:
        cov4 = [json.loads(x) for x in f if x.strip()
                if '"config": 4' in x][0]["coverage"]
    assert want - 0.04 <= cov4 <= want + 0.02


def test_gates_pass_on_sane_results(tmp_path):
    art = tmp_path / "art.json"
    art.write_text(json.dumps(_r(1, wall=5.0)) + "\n")
    assert bc.check_results([_r(1, wall=5.5)], str(art)) == []


def test_gate_fails_on_coverage_collapse(tmp_path):
    art = tmp_path / "art.json"
    art.write_text("")
    fails = bc.check_results([_r(2, cov=0.7)], str(art))
    assert any("coverage" in f for f in fails)


def test_gate_fails_on_wall_regression(tmp_path):
    art = tmp_path / "art.json"
    art.write_text(json.dumps(_r(3, wall=5.0)) + "\n")
    fails = bc.check_results([_r(3, wall=5.0 * bc.WALL_BUDGET + 1.0)],
                             str(art))
    assert any("wall" in f for f in fails)


def test_gate_fails_on_insane_latency(tmp_path):
    fails = bc.check_results([_r(1, p50=10.0)], str(tmp_path / "x"))
    assert any("p50" in f for f in fails)
    fails = bc.check_results([_r(1, p99=50_000.0)], str(tmp_path / "x"))
    assert any("p99" in f for f in fails)


def test_churn_gate_tracks_derivation(tmp_path):
    want = bc.expected_alive_fraction(0.001, 0.0005, 62.0)
    ok = bc.check_results([_r(4, cov=round(want - 0.02, 4))],
                          str(tmp_path / "x"))
    assert ok == []
    bad = bc.check_results([_r(4, cov=round(want - 0.10, 4))],
                           str(tmp_path / "x"))
    assert any("churn" in f for f in bad)
    # steady state sanity: the transient decays toward up/(up+down)
    assert math.isclose(
        bc.expected_alive_fraction(0.001, 0.0005, 1e9), 1.0 / 3.0,
        rel_tol=1e-6)


def test_bench_artifact_emission_is_strict_json():
    # the r5 artifact leaked the invalid-JSON literal Infinity through the
    # bounded-mode wait bar once; the emitter must now refuse NaN/Inf
    # outright and the committed artifacts must strict-parse
    src = open(os.path.join(REPO, "bench.py")).read()
    assert "allow_nan=False" in src, \
        "bench.py must emit with json.dumps(..., allow_nan=False)"

    def _refuse(const):
        raise ValueError(f"non-finite literal {const} in committed artifact")

    import glob
    arts = glob.glob(os.path.join(REPO, "docs", "BENCH_LOCAL_*.json"))
    assert arts
    for path in arts:
        with open(path) as f:
            json.loads(f.read(), parse_constant=_refuse)


def test_bench_guards_probe_attribution():
    # VERDICT r5 "What's weak" #2: publish_exact_s: 0.0 shipped once (the
    # probe measured a cached call). The bench must refuse to emit an
    # artifact where any mode/engine probe measured nothing. The old
    # `exact >= bounded` ordering gate is gone BY DESIGN with the
    # exact-default flip (the prefix engine closes that gap, so the gap is
    # reported, not asserted); what replaced it is the exactness
    # certificate — an exact-mode timed loop whose fixpoints did not
    # converge must not ship.
    src = open(os.path.join(REPO, "bench.py")).read()
    assert "assert full_s > 0.0" in src
    assert "assert bounded_s > 0.0" in src
    assert "assert serial_s > 0.0" in src
    assert 'if DELIVERY_MODE == "exact":' in src
    assert "r.converged" in src
    assert "assert exact_s >= full_s" not in src
    # and the emission happens after the gates: the asserts must precede
    # the json.dumps line in the source
    assert src.index("assert full_s > 0.0") < src.index("json.dumps(out")


def test_attribution_split_components_are_disjoint():
    # the r05 artifact shipped disseminate_s 2.322 > wall_s 2.131 because
    # the synced per-phase pass removes the overlap the timed loop enjoys;
    # the split helper must return DISJOINT components of the real wall
    # (sum == wall, shares preserved) and survive the all-zero corner
    bench = _load_bench()
    hb, dis = bench.attribution_split(2.131, 0.5, 2.322)
    assert hb >= 0.0 and dis >= 0.0
    assert math.isclose(hb + dis, 2.131, rel_tol=1e-9)
    assert hb + dis <= 2.131 * 1.01
    assert math.isclose(dis / hb, 2.322 / 0.5, rel_tol=1e-9)
    assert bench.attribution_split(1.0, 0.0, 0.0) == (0.0, 0.0)


def test_wall_gate_compares_like_delivery_modes_only(tmp_path):
    # the config-4 mode flip (bounded -> exact): an exact-mode run must
    # NOT be wall-gated against a committed bounded row — it is a
    # different model's wall — while a same-mode run still is
    art = tmp_path / "art.json"
    base = _r(1, wall=5.0)
    base["delivery_mode"] = "bounded"
    art.write_text(json.dumps(base) + "\n")
    cross = _r(1, wall=50.0)
    cross["delivery_mode"] = "exact"
    assert bc.check_results([cross], str(art)) == []
    same = _r(1, wall=50.0)
    same["delivery_mode"] = "bounded"
    assert any("wall" in f for f in bc.check_results([same], str(art)))


def test_bounded_ladder_wait_bar_stays_finite():
    # bench_configs guards the bounded rows' error bar the same way: the
    # min() clamp keeps the committed ladder strict-JSON even against a
    # regression reintroducing an infinite bar
    with open(bc.ARTIFACT) as f:
        rows = [json.loads(x) for x in f if x.strip()]
    for r in rows:
        if r.get("delivery_mode") == "bounded":
            assert math.isfinite(r["answer_wait_max_ms"])
            assert r["answer_wait_max_ms"] >= 0.0


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("bench", mod)
    spec.loader.exec_module(mod)
    return mod


def test_bench_tripwire_parses_committed_artifacts(tmp_path):
    # the metric-of-record JSON lives INSIDE each BENCH_r*.json wrapper's
    # "tail" string (after any runtime warnings); the tripwire's parser
    # must dig it out of the live artifacts and out of a synthetic wrapper,
    # and skip unparseable files instead of crashing
    bench = _load_bench()
    # live artifacts: r06/r07, the 2,000-peer CPU smokes (the r01-r05
    # records of the retired device stack were deleted with PR 24)
    assert bench.best_committed_peer_rounds() == 34479.0   # BENCH_r07
    assert bench.best_committed_peer_rounds(str(tmp_path)) is None
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "rc": 0, "tail": "WARNING: noise\n"
         '{"metric": "simulated_peer_rounds_per_sec", "value": 123.0}'}))
    (tmp_path / "BENCH_r02.json").write_text("not json at all")
    (tmp_path / "BENCH_r03.json").write_text(json.dumps(
        {"n": 3, "rc": 1, "tail": "crashed before the metric line"}))
    assert bench.best_committed_peer_rounds(str(tmp_path)) == 123.0


def test_bench_tripwire_is_keyed_per_config(tmp_path):
    # the r05 15 KB-payload bounded rung is ~2x slower than the light
    # pre-r05 configs BY DESIGN; the tripwire must compare like with like,
    # so the heavy config's best is the r05 record, not the global 31.4M
    # (which would perpetually trip >20% "regressions" on heavy runs)
    bench = _load_bench()

    def wrapper(value, detail=None):
        rec = {"metric": "simulated_peer_rounds_per_sec", "value": value}
        if detail is not None:
            rec["detail"] = detail
        return json.dumps({"n": 1, "rc": 0, "tail": json.dumps(rec)})

    # the shapes of the deleted r04 (no key fields: the legacy light bucket)
    # and r05 (delivery_mode + workload shape, no explicit key) records
    shapes = tmp_path / "shapes"
    shapes.mkdir()
    (shapes / "BENCH_r04.json").write_text(wrapper(31.43e6))
    (shapes / "BENCH_r05.json").write_text(wrapper(14.08e6, {
        "delivery_mode": "bounded", "n_peers": 100000, "rounds": 300,
        "timed_messages": 3}))
    heavy = bench.best_committed_peer_rounds(
        str(shapes), config_key="n100000-r300-m3-bounded")
    assert heavy == 14.08e6
    light = bench.best_committed_peer_rounds(
        str(shapes), config_key="pre-r5-light")
    assert light == 31.43e6  # the light bucket keeps its own best
    # live: each committed smoke sits in the bucket its explicit key names
    assert bench.best_committed_peer_rounds(
        config_key="n2000-r30-m3-exact-dht-svc-batched-adaptive-fused"
    ) == 31736.0   # BENCH_r06
    # the live bench emits its key explicitly, and explicit beats derived.
    # Workload-identity changes ride the key: the exact-default flip added
    # the mode suffix, the cross-protocol DHT probe the -dht suffix, and
    # the resident-service probe the -svc suffix, the batched-dispatch
    # flip the dispatch-mode suffix (ISSUE 14), the adaptive-attacker
    # probe the -adaptive suffix (ISSUE 15), and the mega-round scan flip
    # the -fused suffix (ISSUE 16), and the protocol-arena probe the
    # -arena suffix (ISSUE 19), and the multi-host DCN campaign probe the
    # -dcn suffix (ISSUE 20) — each opens a FRESH bucket, so the
    # first run of a new shape compares against nothing instead of
    # tripping a false regression against committed rows of the old shape
    assert bench.BENCH_CONFIG == \
        "n100000-r300-m3-exact-dht-svc-batched-adaptive-fused-arena-dcn"
    assert bench.best_committed_peer_rounds(
        config_key=bench.BENCH_CONFIG) is None
    assert bench._config_key_of(
        {"detail": {"bench_config": "custom", "delivery_mode": "bounded",
                    "n_peers": 1, "rounds": 2, "timed_messages": 3}},
    ) == "custom"
    # unknown-key lookups return None instead of falling back to global
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "rc": 0,
         "tail": '{"metric": "simulated_peer_rounds_per_sec", '
                 '"value": 9.0, "detail": {"bench_config": "k1"}}'}))
    assert bench.best_committed_peer_rounds(str(tmp_path), "k1") == 9.0
    assert bench.best_committed_peer_rounds(str(tmp_path), "k2") is None


def test_bench_tripwire_wiring_orders_error_before_exit():
    # the regression artifact must still be a complete strict-JSON line
    # (error field included) BEFORE the nonzero exit — the driver captures
    # the detail block either way
    src = open(os.path.join(REPO, "bench.py")).read()
    assert '"vs_best_committed"' in src
    assert "REGRESSION_TOLERANCE" in src
    assert 'out["error"]' in src
    emit = src.index("json.dumps(out")
    assert src.index('out["error"]') < emit
    assert emit < src.index("raise SystemExit(1)")


def test_attack_ladder_row_gates(tmp_path):
    # config 7 (the committed sharded attack row) has its own gates: a live
    # attack_trials_per_s series, engagement within the closed-form budget,
    # and an honest-coverage floor looser than the churn-free 0.999
    def row(**over):
        r = _r(7, peers=2048)
        r.update({"attack_trials_per_s": 0.15, "hb_to_graylist": 8,
                  "hb_budget": 8.0})
        r.update(over)
        return r

    x = str(tmp_path / "x")
    assert bc.check_results([row()], x) == []
    assert bc.check_results([row(coverage=0.995)], x) == []  # own floor
    assert any("coverage" in f
               for f in bc.check_results([row(coverage=0.98)], x))
    assert any("budget" in f
               for f in bc.check_results([row(hb_to_graylist=9)], x))
    assert any("engaged" in f
               for f in bc.check_results([row(hb_to_graylist=None)], x))
    assert any("trials_per_s" in f
               for f in bc.check_results([row(attack_trials_per_s=0.0)], x))


def test_committed_attack_row_inside_its_gates():
    # the committed config-7 row must itself pass the gate it ships with
    with open(bc.ARTIFACT) as f:
        rows = [json.loads(x) for x in f if x.strip()]
    r7 = [r for r in rows if r["config"] == 7]
    assert r7, "BENCH_CONFIGS.json must carry the attack ladder row"
    assert bc.check_results(r7) == []


def test_bench_guards_repair_probe():
    # the repair probe (ISSUE 4) must refuse to emit an artifact where the
    # recovery window did nothing: zero evictions or a GROWING attacker
    # mesh share means the repair jit silently compiled the disabled path.
    # Same ordering contract as the exact-mode gates: asserts precede emit.
    src = open(os.path.join(REPO, "bench.py")).read()
    assert "assert evictions_total > 0" in src
    assert "assert att_share_repair <= att_share_attack" in src
    assert '"repair_trials_per_s"' in src
    emit = src.index("json.dumps(out")
    assert src.index("assert evictions_total > 0") < emit
    assert src.index("assert att_share_repair <= att_share_attack") < emit


def test_bench_guards_service_probe():
    # the resident-service probe (ISSUE 13) must refuse to emit an
    # artifact where the overload run didn't overload: shed_rate pinned
    # inside (0,1) proves the offered load exceeded dispatch capacity AND
    # some requests were still admitted, and a non-finite p99 means
    # admitted work never completed. Same ordering contract as the other
    # probe gates: asserts precede emit.
    src = open(os.path.join(REPO, "bench.py")).read()
    assert '0.0 < svc_rep["shed_rate"] < 1.0' in src
    assert "np.isfinite(svc_p99)" in src
    assert 'svc_rep["queue_bound_held"]' in src
    assert '"service_requests_per_s"' in src
    assert '"service_p99_ms"' in src
    emit = src.index("json.dumps(out")
    assert src.index('0.0 < svc_rep["shed_rate"] < 1.0') < emit
    assert src.index("np.isfinite(svc_p99)") < emit
    assert src.index('svc_rep["queue_bound_held"]') < emit
