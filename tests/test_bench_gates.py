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
