"""The program's own tracing (ISSUE 27): host spans of one `run` turn
(runtime/profiling.span / turn), device scopes inside `disseminate`
(jax.named_scope) and the publish's device-side counters, all through
`cli.main(["run", ...])` or `disseminate` itself on the CPU backend."""

import contextlib
import dataclasses
import hashlib
import json
import os
import re

import numpy as np
import pytest

from dst_libp2p_test_node_tpu import cli
from dst_libp2p_test_node_tpu.ops.disseminate import disseminate
from dst_libp2p_test_node_tpu.runtime import profiling
from test_exact_prefix import _publish, mesh_setup

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")

# parent -> children, as ISSUE 27 draws the tree
TREE = {
    None: {"run"},
    "run": {"run/topology", "run/write_gml", "run/write_yaml",
            "run/simulator_init", "run/simulate", "run/write_latencies",
            "run/write_shadowlog", "run/summary", "run/report",
            "run/stats_json"},
    "run/simulator_init": {"build/graph", "build/tables"},
    "run/simulate": {"warmup", "advance", "publish"},
    "publish": {"publish/prepare", "publish/dispatch", "publish/read"},
}
MESSAGES = 3


def _run(tmp, *flags, runs=1, nodes=200, seed=3, capture=None):
    """`run <runs> <nodes> ... --stats-json` into `tmp`; with `capture`, the
    recorder of every turn is appended to it as the turn ends."""
    sound = profiling.turn

    @contextlib.contextmanager
    def capturing(**attrs):
        with sound(**attrs) as spans:
            yield spans
        capture.append(spans)

    if capture is not None:
        profiling.turn = capturing
    try:
        rc = cli.main(["run", str(runs), str(nodes), "15000", "1",
                       str(MESSAGES), "50", "150", "40", "130", "5", "0.0",
                       "4", "0", "4000", "--seed", str(seed), "--stats-json",
                       "--out-prefix", str(tmp) + os.sep, *flags])
    finally:
        profiling.turn = sound
    assert rc == 0


@pytest.fixture(scope="module")
def two_turns(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("two_turns")
    turns = []
    _run(tmp, runs=2, capture=turns)
    assert len(turns) == 2
    return tmp, turns


@pytest.fixture(scope="module")
def no_gossip(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("no_gossip")
    _run(tmp, "--no-gossip")
    with open(tmp / "stats1.json") as f:
        return json.load(f)


def _strict(path):
    def refuse(token):
        raise ValueError(f"non-finite literal {token} in {path}")

    with open(path) as f:
        return json.load(f, parse_constant=refuse)


# ------------------------------------------------------------- host spans


def test_span_tree_has_exactly_the_documented_names(two_turns):
    first = two_turns[1][0]
    want = set().union(*TREE.values())
    assert {s.name for s in first.spans} == want


def test_every_span_is_closed_and_nested_under_its_parent(two_turns):
    for turn in two_turns[1]:
        for s in turn.spans:
            assert s.end is not None and s.end >= s.start, s
            up = None if s.parent is None else turn.spans[s.parent]
            assert s.name in TREE[None if up is None else up.name], s
            if up is not None:
                assert up.start <= s.start and s.end <= up.end, (s, up)


def test_one_publish_with_three_children_per_message(two_turns):
    for turn in two_turns[1]:
        publishes = [i for i, s in enumerate(turn.spans)
                     if s.name == "publish"]
        assert [turn.spans[i].attrs["message"] for i in publishes] \
            == list(range(MESSAGES))
        for i in publishes:
            kids = [s.name for s in turn.spans if s.parent == i]
            assert kids == ["publish/prepare", "publish/dispatch",
                            "publish/read"]
        names = [s.name for s in turn.spans]
        assert names.count("warmup") == 1
        assert names.count("advance") == MESSAGES - 1


def test_a_turn_holds_only_its_own_spans(two_turns):
    first, second = two_turns[1]
    assert (first.attrs, second.attrs) == (
        {"seed": 3, "turn": 1}, {"seed": 4, "turn": 2})
    # the topology is built once, in the first turn; the second turn's
    # recorder starts empty and is no longer than the first's
    once = {"run/topology", "run/write_gml", "run/write_yaml"}
    assert {s.name for s in second.spans} \
        == set().union(*TREE.values()) - once
    assert len(second.spans) == len(first.spans) - len(once)
    assert [s.name for s in second.spans].count("run") == 1


def test_stats_json_spans_and_publishes_are_strict_json(two_turns):
    tmp, turns = two_turns
    for i, turn in enumerate(turns, start=1):
        stats = _strict(tmp / f"stats{i}.json")
        assert set(stats["spans"]) == {s.name for s in turn.spans}
        assert stats["spans"]["publish"]["count"] == MESSAGES
        for name, entry in stats["spans"].items():
            assert set(entry) == {"count", "total_s"}
            assert entry["count"] >= 1 and entry["total_s"] >= 0.0, name
        assert len(stats["publishes"]) == MESSAGES
        for p in stats["publishes"]:
            assert set(p) == {"fast_iters", "refine_passes", "refined",
                              "fell_back", "converged", "refined_serial",
                              "refine_lane_passes", "lanes_hinted",
                              "lanes_uncertified"}
            assert isinstance(p["fast_iters"], int) and p["fast_iters"] > 0
            assert p["converged"] is True and p["fell_back"] is False


def test_stats_json_artifacts_count_what_wrote_shadow_yaml(
        two_turns, tmp_path):
    """PyYAML wrote the ten hosts that fix the anchors and the injector, the
    other hosts are alias lines; a turn that wrote no file says zeros."""
    _run(tmp_path, "--warmup-s", "20", nodes=1000)
    assert _strict(tmp_path / "stats1.json")["artifacts"] == {
        "yaml_hosts_dumped": 11, "yaml_alias_lines": 990}
    assert (tmp_path / "shadow.yaml").read_text().count("\n  pod-") == 1001
    tmp, _ = two_turns
    assert _strict(tmp / "stats1.json")["artifacts"] == {
        "yaml_hosts_dumped": 11, "yaml_alias_lines": 190}
    assert _strict(tmp / "stats2.json")["artifacts"] == {
        "yaml_hosts_dumped": 0, "yaml_alias_lines": 0}


def test_wall_s_is_the_build_and_simulate_spans(two_turns):
    tmp, turns = two_turns
    stats = _strict(tmp / "stats1.json")
    want = (turns[0].seconds("run/simulator_init")
            + turns[0].seconds("run/simulate"))
    assert stats["wall_s"] == pytest.approx(want, rel=1e-9)
    # and the spans the experiment is made of fit inside the turn
    assert want <= turns[0].seconds("run")


def test_the_build_is_two_spans_that_make_up_simulator_init(two_turns):
    """`Simulator.__init__` is the graph (host numpy) and then everything
    made from it: once each a turn, and nothing of the build outside them."""
    tmp, turns = two_turns
    for i, turn in enumerate(turns, start=1):
        (init,) = [j for j, s in enumerate(turn.spans)
                   if s.name == "run/simulator_init"]
        kids = [s for s in turn.spans if s.parent == init]
        assert [s.name for s in kids] == ["build/graph", "build/tables"]
        whole = turn.seconds("run/simulator_init")
        halves = turn.seconds("build/graph") + turn.seconds("build/tables")
        assert 0.0 <= whole - halves <= max(1e-3, 0.05 * whole)
        assert _strict(tmp / f"stats{i}.json")["build"] == {
            "dial_rows_resampled": 0, "dedupe": "mutual",
            "mutual_dials_dropped": 47, "cap_filtered_edges": 0}


def test_span_outside_a_turn_records_nothing_and_does_not_raise():
    with profiling.span("publish", message=0):
        with profiling.span("publish/read"):
            pass
    profiling.counters("publish/counters", fast_iters=1)
    with profiling.turn(seed=0, turn=1) as spans:
        pass
    assert [s.name for s in spans.spans] == ["run"]


def test_a_raising_span_is_closed_and_the_turn_is_left():
    with pytest.raises(RuntimeError):
        with profiling.turn(seed=0, turn=1) as spans:
            with profiling.span("run/simulate"):
                raise RuntimeError("boom")
    assert all(s.end is not None for s in spans.spans)
    with profiling.span("advance"):     # no turn is current any more
        pass
    assert len(spans.spans) == 2


# ----------------------------------------------------------- device scopes


@pytest.mark.parametrize("fragments", [1, 3])
def test_lowered_disseminate_carries_the_scopes(fragments):
    g, params, state, a, topo = mesh_setup()
    stage, lat, bw = topo
    text = disseminate.lower(
        state, a["conns"], a["rev"], stage, lat, bw, publisher=7,
        t0_ms=float(state.t_ms), params=params, payload_bytes=15000,
        with_gossip=True, fragments=fragments).as_text(debug_info=True)
    names = re.findall(r'loc\("jit\(disseminate\)/([^"]*)"', text)
    outermost = {n.split("/")[0] for n in names}
    assert {"sample", "fast", "refine", "accounting"} <= outermost
    # the loops' bodies and the cond's branches keep the scope they were
    # traced under, through vmap over the fragment lanes too; what runs once
    # a lane lies under `per_fragment`
    assert any(re.match(r"fast/per_fragment/(vmap\()?fixpoint\)?/while/body/",
                        n) for n in names)
    assert any(re.match(r"fast/per_fragment/(vmap\()?fold\)?/", n)
               for n in names)
    assert any(n.startswith("refine/cond/") and "/fixpoint/while/body/" in n
               for n in names)
    assert any(n.startswith("refine/cond/") and "/legacy/" in n
               for n in names)
    assert any(n.startswith("accounting/per_fragment/vmap(") for n in names)
    # and the cross-fragment part of both (completion on the last fragment,
    # the downlink fold, the warm rerun's predicate) does not
    assert any(n.startswith("accounting/") and "per_fragment" not in n
               for n in names)
    # what is left outside the four is a handful of scalar reductions
    loose = [n for n in names if n.split("/")[0] not in
             ("sample", "fast", "refine", "accounting")]
    assert len(loose) <= 0.01 * len(names), loose


# ---------------------------------------------------------------- counters

# (publish kwargs, SimParams overrides, the parent commit's refine_passes
# of the prefix engine and of the serial one): tests/test_exact_prefix.py's
# cases
PREFIX_CASES = [
    ({}, {}, 6, 4),
    ({"fragments": 4}, {}, 6, 4),
    ({"publisher": 3}, {"flood_publish": False, "d_lazy": 12}, 8, 4),
    ({"fragments": 3}, {"flood_publish": False, "d_lazy": 12}, 10, 4),
]


@pytest.mark.parametrize(
    "kw,over,passes_prefix,passes_serial", PREFIX_CASES,
    ids=["mesh", "mesh-frag4", "gossip-heavy", "gossip-heavy-frag3"])
def test_counters_on_the_prefix_cases(kw, over, passes_prefix,
                                      passes_serial):
    g, params, state, a, topo = mesh_setup(**over)
    res_p, _ = _publish(state, a, topo, params, **kw)
    res_s, _ = _publish(
        state, a, topo,
        dataclasses.replace(params, answer_queue_mode="serial"), **kw)
    assert int(res_p.refine_passes) == passes_prefix
    assert int(res_s.refine_passes) == passes_serial
    for res in (res_p, res_s):
        assert int(res.fast_iters) > 0
        assert bool(res.refined) and not bool(res.fell_back)
        # the packed vector is the scalars, in the documented order
        assert np.asarray(res.counters).tolist() == [
            int(res.fast_iters), int(res.refine_passes), int(res.refined),
            int(res.fell_back), int(res.converged),
            int(res.refined_serial), int(res.refine_lane_passes),
            int(res.lanes_hinted), int(res.lanes_uncertified)]
    # which engine refined: the one chosen
    assert not bool(res_p.refined_serial) and bool(res_s.refined_serial)
    # the fast pipeline is the same program under both engines
    assert int(res_p.fast_iters) == int(res_s.fast_iters)


def test_fell_back_when_the_prefix_engine_is_capped():
    # no canonical topology makes the prefix engine fail its certificate; a
    # cap of 3 iterations under the 4 + 4 its two phases need here does
    kw, over, passes_prefix, passes_serial = PREFIX_CASES[2]
    g, params, state, a, topo = mesh_setup(**over)
    res, _ = _publish(
        state, a, topo, dataclasses.replace(params, max_relax_iters=3), **kw)
    assert bool(res.refined) and bool(res.fell_back)
    assert bool(res.refined_serial)     # the rerun's result is the one kept
    # the prefix iterations already spent, plus the serial outer passes
    assert int(res.refine_passes) == 2 * 3 + passes_serial
    assert bool(res.converged)


def test_no_gossip_never_refines(no_gossip):
    assert len(no_gossip["publishes"]) == MESSAGES
    for p in no_gossip["publishes"]:
        assert p["fast_iters"] > 0 and p["refine_passes"] == 0
        assert p["refined"] is False and p["fell_back"] is False
        assert p["refined_serial"] is False
        assert p["converged"] is True


def test_record_takes_the_counters_in_one_read(monkeypatch):
    from dst_libp2p_test_node_tpu.runtime import simulator as simmod

    g, params, state, a, topo = mesh_setup()
    res, _ = _publish(state, a, topo, params)
    reads = []
    sound = np.asarray

    def counting(x, *args, **kw):
        if not isinstance(x, (np.ndarray, list, int, float, bool)):
            reads.append(type(x).__name__)
        return sound(x, *args, **kw)

    monkeypatch.setattr(simmod.np, "asarray", counting)
    rec = simmod.record_from_result(res, msg_id=1, publisher=7, t0_ms=0.0)
    # eight device->host reads a publish, as before this PR: the six
    # per-peer arrays, the wait bar, and the packed counters (which took
    # the place of the `converged` read)
    assert len(reads) == 8, reads
    assert (rec.fast_iters, rec.refine_passes, rec.refined, rec.fell_back,
            rec.converged) == (7, 6, True, False, True)


def test_latencies_of_the_quick_start_are_the_parents_bytes(tmp_path):
    # the scopes and counters changed no simulated number: the documented
    # Quick start (1,000 peers, seed 0) writes the bytes the parent wrote
    with open(os.path.join(FIXTURES, "chip_smoke_cpu_1000.json")) as f:
        want = json.load(f)["latencies_sha256"]
    _run(tmp_path, nodes=1000, seed=0)
    with open(tmp_path / "latencies1", "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest() == want
