"""The program's own tracing (ISSUE 27): host spans of one `run` turn
(runtime/profiling.span / turn), device scopes inside `disseminate`
(jax.named_scope) and the publish's device-side counters, all through
`cli.main(["run", ...])` or `disseminate` itself on the CPU backend; and
(ISSUE 40) the process record and the compile ledger that
`jax.monitoring`'s events feed, by real compiles and by events fed through
`jax.monitoring.record_*`."""

import contextlib
import dataclasses
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
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
    # this file looks at the turns of the loop, one whole experiment a turn
    # (tests/test_run_batch.py looks at a batch's): runs > 1 keeps it here
    refusal = cli._batch_refusal
    cli._batch_refusal = lambda a: "tests/test_tracing.py keeps the loop"
    try:
        rc = cli.main(["run", str(runs), str(nodes), "15000", "1",
                       str(MESSAGES), "50", "150", "40", "130", "5", "0.0",
                       "4", "0", "4000", "--seed", str(seed), "--stats-json",
                       "--out-prefix", str(tmp) + os.sep, *flags])
    finally:
        profiling.turn = sound
        cli._batch_refusal = refusal
    assert rc == 0


@pytest.fixture(scope="module")
def two_turns(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("two_turns")
    turns = []
    _run(tmp, runs=2, capture=turns)
    assert len(turns) == 2
    return tmp, turns


@pytest.fixture(scope="module")
def first_turns(tmp_path_factory):
    """Two turns of one `run` as the first two of a process: the process
    record starts anew before them. At a peer count no other test runs, so
    that whatever this worker ran before, the turn's programs compile."""
    tmp = tmp_path_factory.mktemp("first_turns")
    was, profiling._PROCESS = profiling._PROCESS, profiling.ProcessRecord()
    try:
        _run(tmp, runs=2, nodes=193)
        record = profiling.process_record()
    finally:
        profiling._PROCESS = was
    return tmp, record


@pytest.fixture
def fresh_process(monkeypatch):
    """A process record of the test's own, fed by the listeners."""
    profiling.register_compile_listeners()
    process = profiling.ProcessRecord()
    monkeypatch.setattr(profiling, "_PROCESS", process)
    return process


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
            assert set(p) == {"fast_iters", "fast_sparse_iters",
                              "refine_passes", "refine_sparse_passes",
                              "refined",
                              "fell_back", "converged", "refined_serial",
                              "refine_lane_passes", "lanes_hinted",
                              "lanes_uncertified", "lanes_in_pull",
                              "pull_rows_share"}
            assert p["lanes_in_pull"] == 1      # one fragment: no lane axis
            assert p["pull_rows_share"] == 100.0    # under the size test
            assert isinstance(p["fast_iters"], int) and p["fast_iters"] > 0
            assert p["fast_sparse_iters"] == 0      # and under relax_route's
            assert p["refine_sparse_passes"] == 0
            assert p["converged"] is True and p["fell_back"] is False


@pytest.mark.parametrize("fragments", [1, 4])
def test_lanes_in_pull_on_the_counters_and_in_stats_json(
        tmp_path, monkeypatch, fragments):
    """ISSUE 41: how many fragment lanes one row gather of the publish's
    fixpoints carries, a trace-time constant of the shape, stated on the
    `sim:publish/counters` annotation and in `stats<i>.json` "publishes"."""
    from dst_libp2p_test_node_tpu.runtime import simulator as simmod

    seen = []
    monkeypatch.setattr(
        simmod, "counters", lambda name, **values: seen.append((name, values)))
    rc = cli.main(["run", "1", "200", "15000", str(fragments), "2", "50",
                   "150", "40", "130", "5", "0.0", "4", "0", "4000", "--seed",
                   "3", "--stats-json", "--out-prefix", str(tmp_path) + os.sep])
    assert rc == 0
    on_publish = [v for name, v in seen if name == "publish/counters"]
    assert len(on_publish) == 2
    for values in on_publish:
        assert values["fragments"] == fragments
        assert values["lanes_in_pull"] == fragments
        assert values["in_sequence"] == 0
        assert values["formulation"] == "row_pull"
    publishes = _strict(tmp_path / "stats1.json")["publishes"]
    assert [p["lanes_in_pull"] for p in publishes] == [fragments] * 2


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
    # (the prefix refinement's lanes are vmapped too where there are several)
    assert any(n.startswith("refine/cond/")
               and re.search(r"/per_fragment/(vmap\()?fixpoint\)?/while/body/", n)
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
            int(res.lanes_hinted), int(res.lanes_uncertified),
            int(res.fast_sparse_iters), int(res.refine_sparse_passes)]
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


# ------------------------------------ process record and compile ledger

TRACE, LOWER, BACKEND = profiling._TRACE_EVENTS + (profiling._BACKEND_EVENT,)
COMPILE_KEYS = {
    "programs", "compiled", "loaded", "stored", "trace_lower_s", "compile_s",
    "load_s", "stored_threshold_s",
    "compiled_under_threshold", "by_span", "slowest"}


def _feed_program(name, seconds, at=100.0, hit=False, stored=False):
    """One program's events as jax sends them: what the cache did, then the
    backend span."""
    if hit:
        jax.monitoring.record_event(profiling._CACHE_HIT_EVENT)
    if stored:
        jax.monitoring.record_event(profiling._CACHE_STORE_EVENT)
    jax.monitoring.record_event_time_span(
        BACKEND, at, at + seconds, fun_name=name)


def test_first_turn_of_a_process_carries_process_and_ordered_marks(
        first_turns):
    tmp, record = first_turns
    first, second = _strict(tmp / "stats1.json"), _strict(tmp / "stats2.json")
    assert set(first["process"]) == {
        "import_to_main_s", "backend_s", "import_to_first_turn_s"}
    assert "process" not in second
    p = first["process"]
    assert 0.0 <= p["import_to_main_s"] <= p["import_to_first_turn_s"]
    assert p["backend_s"] >= 0.0
    json.dumps(record, allow_nan=False)
    marks = record["marks"]
    order = ["imported", "main", "backend_ready", "turn1_start", "turn1_end"]
    assert list(marks) == order
    assert [marks[k] for k in order] == sorted(marks.values())
    assert record["turns"] == 2
    assert record["spans"]["setup/backend"]["count"] == 1
    assert record["process"] == p
    # the ledger holds the first turn's programs and closes with it
    assert set(first["compile"]) == COMPILE_KEYS == set(second["compile"])
    assert set(record["compile"]) == {"setup"}
    setup = record["compile"]["setup"]
    assert setup["programs"] == first["compile"]["programs"] > 0
    assert second["compile"]["programs"] == 0
    assert setup["compile_s"] + setup["load_s"] == pytest.approx(
        sum(first["compile"]["by_span"].values()))
    # each program under the span it was first called in
    by_fun = setup["by_fun"]
    assert by_fun["jit(disseminate)"]["span"] == "publish/dispatch"
    assert by_fun["jit(_run_heartbeats)"]["span"] == "warmup"
    assert "build/tables" in first["compile"]["by_span"]


def test_profiling_is_importable_before_jax_and_the_backend():
    """cli.main marks, enables the cache and times the backend's start
    before anything imports `ops/*`, whose module constants start it."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from dst_libp2p_test_node_tpu.runtime import profiling\n"
        "from dst_libp2p_test_node_tpu.runtime import compile_cache\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "p = profiling._PROCESS\n"
        "assert list(p.marks) == ['imported'] and p.turns == 0\n")
    subprocess.run([sys.executable, "-c", code], check=True,
                   cwd=os.path.dirname(os.path.dirname(__file__)))


def test_a_later_turn_keeps_no_marks(fresh_process):
    for i in range(1, 4):
        with profiling.turn(seed=0, turn=1) as spans:
            assert spans.number == i
    assert fresh_process.turns == 3
    assert [k for k in fresh_process.marks if k.startswith("turn")] == [
        "turn1_start", "turn1_end"]


def test_a_jit_first_called_in_a_span_shows_under_name_and_span(
        fresh_process):
    @jax.jit
    def fresh_program_of_test_tracing(x):
        return x * 5 - 2

    name = "jit(fresh_program_of_test_tracing)"
    x = jnp.arange(11.0)
    with profiling.turn(seed=0, turn=1) as spans:
        with profiling.span("run/simulator_init"), \
                profiling.span("build/tables"):
            jax.block_until_ready(fresh_program_of_test_tracing(x))
    got = spans.compile.as_dict()
    assert set(got) == COMPILE_KEYS
    # only the innermost open span is charged
    assert set(got["by_span"]) == {"build/tables"}
    rows = [r for r in got["slowest"] if r[0] == name]
    assert len(rows) == 1 and rows[0][1] in ("compiled", "loaded")
    assert rows[0][2] == pytest.approx(got["by_span"]["build/tables"])
    entry = profiling.process_record()["compile"]["setup"]["by_fun"][name]
    assert entry["programs"] == 1 and entry["span"] == "build/tables"
    assert entry["trace_lower_s"] > 0.0
    # the same call again: in memory, no event
    with profiling.turn(seed=0, turn=1) as again:
        jax.block_until_ready(fresh_program_of_test_tracing(x))
    assert again.compile.as_dict()["programs"] == 0


def test_second_run_of_the_same_arguments_compiles_nothing(
        two_turns, tmp_path, capsys):
    """The steady state the benchmark's window relies on; and what the turn
    wrote is what the parent wrote, byte for byte."""
    with open(os.path.join(FIXTURES, "tracing_run_200_seed3.json")) as f:
        want = json.load(f)
    capsys.readouterr()
    _run(tmp_path, runs=1, seed=3)
    out = capsys.readouterr().out
    stats = _strict(tmp_path / "stats1.json")
    assert stats["compile"] == {
        "programs": 0, "compiled": 0, "loaded": 0, "stored": 0,
        "trace_lower_s": 0.0, "compile_s": 0.0, "load_s": 0.0,
        "compiled_under_threshold": 0, "by_span": {}, "slowest": [],
        "stored_threshold_s": stats["compile"]["stored_threshold_s"]}
    for name in ("latencies1", "shadowlog1"):
        with open(tmp_path / name, "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == want[name], name
    out = re.sub(r"(?m)^\[tpu backend\].*$", "[tpu backend]", out)
    assert hashlib.sha256(out.encode()).hexdigest() == want["stdout"]


def test_a_jit_traced_inside_another_adds_its_interval_once(fresh_process):
    @jax.jit
    def inner_of_test_tracing(x):
        return jnp.sin(x) * 2

    @jax.jit
    def outer_of_test_tracing(x):
        return inner_of_test_tracing(x) + inner_of_test_tracing(x * 3)

    with profiling.turn(seed=0, turn=1) as spans:
        jax.block_until_ready(outer_of_test_tracing(jnp.arange(13.0)))
    got = spans.compile.as_dict()
    assert 0.0 < got["trace_lower_s"] <= spans.seconds("run")
    by_fun = fresh_process.setup.by_fun
    assert by_fun["jit(inner_of_test_tracing)"]["programs"] == 0
    assert by_fun["jit(outer_of_test_tracing)"]["programs"] == 1
    # and exactly, on fed events: the inner trace ends first, inside the
    # outer; the lowering follows
    totals = profiling.CompileTotals(by_fun=True)
    totals.add_trace("jit(inner)", 10.2, 10.4)
    totals.add_trace("jit(inner)", 10.5, 10.6)
    totals.add_trace("jit(outer)", 10.0, 11.0)
    totals.add_trace("jit(outer)", 11.0, 11.5)
    assert totals.trace_lower_s == pytest.approx(1.5)
    assert totals.by_fun["jit(inner)"]["trace_lower_s"] == pytest.approx(0.3)
    assert totals.by_fun["jit(outer)"]["trace_lower_s"] == pytest.approx(1.5)


def test_compiled_loaded_stored_and_under_the_threshold(fresh_process):
    threshold = profiling.store_threshold_s()
    assert threshold == 1.0             # tests/conftest.py
    with profiling.turn(seed=0, turn=1) as spans:
        with profiling.span("warmup"):
            _feed_program("jit(small)", 0.25)
            _feed_program("jit(big)", 2.5, stored=True)
        with profiling.span("publish"), profiling.span("publish/dispatch"):
            _feed_program("jit(cached)", 0.5, hit=True)
    got = spans.compile.as_dict()
    assert (got["programs"], got["compiled"], got["loaded"]) == (3, 2, 1)
    assert got["stored"] == 1 and got["compiled_under_threshold"] == 1
    assert got["compile_s"] == pytest.approx(2.75)
    assert got["load_s"] == pytest.approx(0.5)
    assert got["by_span"] == pytest.approx(
        {"warmup": 2.75, "publish/dispatch": 0.5})
    assert [r[:2] for r in got["slowest"]] == [
        ["jit(big)", "compiled"], ["jit(cached)", "loaded"],
        ["jit(small)", "compiled"]]
    # a hit is spent by the program it answered; and the first turn's end
    # closed the ledger: a later program counts in its own turn alone
    with profiling.turn(seed=0, turn=2) as later:
        _feed_program("jit(in_a_later_turn)", 0.125)
    got = later.compile.as_dict()
    assert (got["compiled"], got["loaded"]) == (1, 0)
    assert fresh_process.setup.compiled + fresh_process.setup.loaded == 3


def test_a_thousand_events_of_one_name_leave_one_entry(fresh_process):
    for i in range(1000):
        jax.monitoring.record_event_time_span(
            TRACE, 2.0 * i, 2.0 * i + 0.5, fun_name="again")
        jax.monitoring.record_event_time_span(
            LOWER, 2.0 * i + 0.5, 2.0 * i + 1.0, fun_name="jit(again)")
        _feed_program("jit(again)", 0.001 * (i + 1), at=2.0 * i + 1.0)
    setup = fresh_process.setup
    assert list(setup.by_fun) == ["jit(again)"]
    assert setup.by_fun["jit(again)"]["programs"] == 1000
    assert len(setup.slowest) == profiling.SLOWEST_KEPT
    assert len(setup._intervals) <= profiling._INTERVALS_KEPT
    got = profiling.process_record()["compile"]["setup"]
    assert got["trace_lower_s"] == pytest.approx(1000.0)
    assert got["compile_s"] == pytest.approx(0.001 * 1000 * 1001 / 2)
    assert [r[2] for r in got["slowest"]] == pytest.approx(
        [1.0, 0.999, 0.998, 0.997, 0.996])
    json.dumps(got, allow_nan=False)


def test_registering_twice_leaves_one_listener(fresh_process):
    from dst_libp2p_test_node_tpu.runtime.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    profiling.register_compile_listeners()
    enable_compile_cache()
    with profiling.count_retraces() as counter:
        _feed_program("jit(once)", 0.5)
    assert counter.count == 1 and counter.events == ["jit(once)"]
    assert fresh_process.setup.compiled == 1


def test_count_retraces_reads_a_fresh_jit_then_nothing():
    @jax.jit
    def fresh_for_count_retraces(x):
        return x * 7 + 3

    x = jnp.arange(5.0)
    with profiling.count_retraces() as first:
        jax.block_until_ready(fresh_for_count_retraces(x))
    assert first.count >= 1
    assert "jit(fresh_for_count_retraces)" in first.events
    with profiling.count_retraces() as second:
        jax.block_until_ready(fresh_for_count_retraces(x))
    assert (second.count, second.events) == (0, [])
    # a counter that was closed hears nothing more
    _feed_program("jit(later)", 0.5)
    assert first.count == len(first.events) and second.count == 0


def test_a_program_outside_a_turn_names_the_span_it_fell_in(fresh_process):
    with profiling.span("setup/backend"):
        _feed_program("jit(early)", 0.5)
    _feed_program("jit(nowhere)", 0.25)
    got = profiling.process_record()
    assert got["spans"]["setup/backend"]["count"] == 1
    assert got["compile"]["setup"]["by_span"] == pytest.approx(
        {"setup/backend": 0.5, "(no span)": 0.25})
    assert got["compile"]["setup"]["slowest"][0] == [
        "jit(early)", "compiled", pytest.approx(0.5), 0, "setup/backend"]
    assert got["process"] == {
        "import_to_main_s": None,
        "backend_s": got["spans"]["setup/backend"]["total_s"],
        "import_to_first_turn_s": None}


@pytest.mark.parametrize("banded", [False, True])
def test_pull_rows_share_on_the_counters_and_in_stats_json(
        tmp_path, monkeypatch, banded):
    """ISSUE 50: the share of an (N, C) index's rows one pull of the publish
    gathers, stated on the `sim:publish/counters` annotation (what the
    benchmark's `publish.pull_rows_share` reads) and in `stats<i>.json`
    "publishes": 100 where the maker refuses bands (200 peers: under the
    size test), the bands' share where it makes them (forced here)."""
    import functools

    from dst_libp2p_test_node_tpu.ops.pull import make_pull_bands
    from dst_libp2p_test_node_tpu.runtime import simulator as simmod

    seen = []
    monkeypatch.setattr(
        simmod, "counters", lambda name, **values: seen.append((name, values)))
    if banded:
        monkeypatch.setattr(
            simmod, "make_pull_bands",
            functools.partial(make_pull_bands, min_bytes=0, rows=64))
    rc = cli.main(["run", "1", "200", "15000", "1", "2", "50",
                   "150", "40", "130", "5", "0.0", "4", "0", "4000", "--seed",
                   "3", "--stats-json", "--out-prefix", str(tmp_path) + os.sep])
    assert rc == 0
    want = 100.0 * (200 * 24 + 64 * 16) / (200 * 40) if banded else 100.0
    on_publish = [v for name, v in seen if name == "publish/counters"]
    assert [v["pull_rows_share"] for v in on_publish] == [want] * 2
    publishes = _strict(tmp_path / "stats1.json")["publishes"]
    assert [p["pull_rows_share"] for p in publishes] == [want] * 2
