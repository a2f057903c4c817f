"""The cell runsh-100k-128k-frag4.headline (ISSUE 34): the manifest finds it
and its `run` argv carries one blob-sized message in 4 fragments a 12 s
slot; benchmark/run.py --rehearse drives all three parts of `correct` at 200
peers with fragments that refine, and part 1 catches the parent's
publisher, which logged the start of its last send; the readers of the lane
counters and of refine/per_fragment, on a recorded chip profile and on rows
made by hand."""

import dataclasses
import json
import os
from types import SimpleNamespace

import pytest

from benchmark import run
from benchmark.entries.run import POSITIONALS, arguments, run_argv
from benchmark.harness import manifest, program_profile, trace
from benchmark.harness.experiment import run_experiment

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "BENCHMARK.128k-frag4.test.json")
METRICS = os.path.join(os.path.dirname(HERE), "layer_metrics")
CELL = "runsh-100k-128k-frag4.headline"
TINY = "tiny-128k-frag4.headline"
NEW = ("publish.refine.per_fragment.device_s", "publish.refine.lane_pass_ms",
       "publish.lanes_hinted", "publish.lanes_uncertified")
PLANE = "/device:TPU:0"


def _read(name, ctx):
    with open(os.path.join(METRICS, name + ".json")) as f:
        spec = json.load(f)
    return manifest.reader(spec["reader"])(ctx, **spec["params"])


# ------------------------------------------------------------ the manifest


def test_the_cell_loads_and_runs_a_blob_in_four_fragments():
    cell = manifest.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "runsh-100k-128k-frag4", "headline")
    argv = run_argv(arguments(cell), 2147483777, "out")
    assert argv[:15] == ["run", "1", "100000", "131072", "4", "3", "50",
                         "150", "40", "130", "5", "0.0", "4", "0", "12000"]
    # runsh-100k-frag4's experiment but for the size and the slot
    other = manifest.load_cell("runsh-100k-frag4.headline")
    differing = {k for k in POSITIONALS if arguments(cell)["positionals"][k]
                 != arguments(other)["positionals"][k]}
    assert differing == {"msg_size", "inter_message_delay_ms"}
    assert arguments(cell)["flags"] == arguments(other)["flags"] == []
    assert cell.config["link_model"] == other.config["link_model"]
    assert cell.config["guarantees"] == other.config["guarantees"]
    # the publisher's own 0 is held in this cell, not waived
    assert cell.config["guarantees"]["no_delay_under_ms"] == 40
    assert cell.config["reduced"] == ["num_publishers"]
    assert cell.config["architecture"] is None
    assert set(cell.config["assumed"]) >= {
        "nodes", "num_frag", "link_ranges", "inter_message_delay_ms"}
    ref = cell.config["reference"]
    assert (ref["messages"], ref["idle_links_at_publish"], ref["atol_ms"],
            ref["rtol"], ref["hop_ms"]) == (1, True, 0.5, 1e-4, 40)
    assert set(cell.config["reference_readings"]) == {
        "eps", "eps_hop", "reached"}
    assert cell.config["trace_experiments"] == 1


def test_the_new_metrics_are_the_new_cells_alone():
    with open(os.path.join(manifest.CHECKOUT, "BENCHMARK.json")) as f:
        man = json.load(f)
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "experiment_s"
    reported = {m["name"] for m in manifest.load_cell(CELL).per_layer}
    assert set(NEW) <= reported
    assert {"publish.refine.device_s", "publish.refine.pass_ms",
            "publish.refined_share", "publish.fallback_share"} <= reported
    # PR 30's four keep their one cell, and the roofline share its three
    for name in ("publish.fragments", "publish.serial_refine_share",
                 "publish.fast.per_fragment.device_s",
                 "publish.accounting.per_fragment.device_s"):
        assert by_name[name]["workloads"] == ["runsh-100k-frag4.headline"]
    assert CELL not in by_name["publish.fixpoint.hbm_share"]["workloads"]
    for old in ("runsh-1k.headline", "runsh-100k.headline",
                "runsh-100k.meshonly", "runsh-100k-frag4.headline"):
        names = {m["name"] for m in manifest.load_cell(old).per_layer}
        assert not set(NEW) & names


# ------------------------------------------------------------ the rehearsal


def _lines(out: str) -> list[dict]:
    return [json.loads(ln) for ln in out.strip().splitlines()]


def _rehearse(capsys, trace_flag, seed):
    rc = run.main(["--manifest", MANIFEST, "--seconds", "0.5", "--workload",
                   TINY, "--seed", str(seed), "--trace", trace_flag,
                   "--rehearse"])
    lines = _lines(capsys.readouterr().out)
    assert rc == 0
    return lines[-1], {ln["line"]: ln for ln in lines[:-1]}, lines


@pytest.mark.parametrize("trace_flag", ["0", "1"])
def test_rehearse_a_blob_in_four_fragments(capsys, trace_flag):
    last, part, lines = _rehearse(capsys, trace_flag, 2147483999)
    assert last["correct"] is True and last["metrics"] == {}
    assert last["failed"] == 0 and last["attempted"] >= 1
    for kind in ("correct_part1", "correct_part2", "correct_part3_tie",
                 "correct_part3"):
        assert part[kind]["passed"] is True, part[kind]
    assert part["window"]["compilations_in_window"] == 0
    # all three messages replayed, each on four fragments' draws
    assert sum(ln.get("line") == "correct_part3" for ln in lines) == 3
    if trace_flag == "1":
        # the counters are read off XLA:CPU's profile too; the device scopes
        # need a device plane, which it has not
        assert {"publish.lanes_hinted", "publish.lanes_uncertified",
                "publish.refined_share"} <= set(
                    part["rehearse"]["would_report"])


def test_the_tiny_cells_fragments_refine(tmp_path):
    cell = manifest.load_cell(TINY, MANIFEST)
    out = run_experiment(cell, 2147483999, str(tmp_path / "x"))
    assert out.ok, out.faults
    assert len(out.stats["publishes"]) == 3
    for p in out.stats["publishes"]:
        assert p["refined"] and not p["fell_back"]
        assert not p["refined_serial"]
        assert p["refine_lane_passes"] >= p["refine_passes"] > 0
        assert 1 <= p["lanes_hinted"] <= 4 and p["lanes_uncertified"] == 0


def test_part1_catches_the_parents_publisher(capsys, monkeypatch):
    """The parent of ISSUE 34 gave the publisher the last lane's send
    origin as its own receipt: 6 ms at 4 x 32,768 B. Put back where the
    result is produced, part 1 names it in every experiment; the same seed
    still writes the same file."""
    from dst_libp2p_test_node_tpu.runtime import simulator as simmod

    sound = simmod.disseminate

    def parents(state, conns, rev, *args, **kw):
        out = sound(state, conns, rev, *args, **{**kw, "return_plan": True})
        res, plan = out[0], out[2]
        own = plan["t_pubs"][-1] - kw["t0_ms"]
        res = dataclasses.replace(
            res, delay_ms=res.delay_ms.at[kw["publisher"]].set(own))
        return (res, out[1], plan) if kw.get("return_plan") else (res, out[1])

    monkeypatch.setattr(simmod, "disseminate", parents)
    last, part, _ = _rehearse(capsys, "0", 7)
    assert last["correct"] is False
    assert part["correct_part1"]["passed"] is False
    fault = part["correct_part1_fault"]["faults"][0]
    assert "under 40 ms other than the publisher's own 0" in fault
    assert "(4, 6)" in fault
    assert part["correct_part2"]["passed"] is True
    # the captured experiment writes the same file and has the same fault,
    # so it is not compared: part 3 says nothing about a run part 1 refused
    tie = part["correct_part3_tie"]
    assert tie["captured"] == tie["timed"] and tie["faults"] == [fault]
    assert tie["passed"] is False and "correct_part3" not in part


# -------------------------------------------------------------- the readers


@pytest.fixture(scope="module")
def recorded():
    """recorded_profile_128k_frag4.json as program_profile's rows (its
    `note` says what it is: four lanes in sequence, every publish refined,
    on a v5e)."""
    with open(os.path.join(HERE, "recorded_profile_128k_frag4.json")) as f:
        rec = json.load(f)
    plane = rec["plane"]
    return {
        "modules": [{"plane": plane, "name": n, "start_ns": s, "dur_ns": d}
                    for n, s, d in rec["modules"]],
        "ops": [{"plane": plane, "name": n, "start_ns": s, "dur_ns": d,
                 "scope": rec["scopes"][i]} for n, s, d, i in rec["ops"]],
        "host": [dict(zip(("name", "start_ns", "dur_ns", "attrs"), r))
                 for r in rec["host"]],
    }


@pytest.fixture
def first_publish(recorded, monkeypatch):
    """The first publish alone: the one jit_disseminate module whose op
    events the recording keeps whole."""
    monkeypatch.setattr(program_profile, "load", lambda: recorded)
    span = next(h for h in recorded["host"] if h["name"] == "sim:publish")
    rows = ([{**r, "line": trace.MODULE_LINE} for r in recorded["modules"]]
            + [{**r, "line": trace.OP_LINE} for r in recorded["ops"]])
    return SimpleNamespace(
        trace_rows=rows, recorder=None, experiments=[], memory_stats=[],
        trace_windows=[(span["start_ns"],
                        span["start_ns"] + span["dur_ns"])])


def test_recorded_lanes_refined_in_sequence_on_the_prefix_engine(recorded):
    said = [h["attrs"] for h in recorded["host"]
            if h["name"] == "sim:publish/counters"]
    assert len(said) == 3
    for attrs in said:
        assert (attrs["fragments"], attrs["formulation"],
                attrs["in_sequence"]) == ("4", "row_pull", "1")
        assert (attrs["refined"], attrs["fell_back"],
                attrs["refined_serial"], attrs["lanes_uncertified"]) == (
                    "1", "0", "0", "0")
        assert int(attrs["refine_passes"]) < int(
            attrs["refine_lane_passes"]) <= 4 * int(attrs["refine_passes"])
        assert 1 <= int(attrs["lanes_hinted"]) <= 4
    # the prefix engine's loops inside the rolled loop over the lanes, under
    # the taken branch of the conditional; nothing ran under refine/legacy
    known = ["sample", "fast", "refine", "accounting"]
    assert any(program_profile.follows(
        r["scope"], ["refine", "per_fragment", "while", "fixpoint", "while"],
        known) for r in recorded["ops"])
    assert not any(program_profile.follows(
        r["scope"], ["refine", "legacy"], known) for r in recorded["ops"])


def test_lane_metrics_on_the_recorded_profile(first_publish):
    refine = _read("publish.refine.device_s", first_publish)
    lanes = _read("publish.refine.per_fragment.device_s", first_publish)
    # nearly all of `refine` is the lanes' own work: outside them are the
    # two conditionals and their operands
    assert 0.98 * refine < lanes <= refine
    # the first publish: 42 lane passes, 12 in the deepest lane
    lane_ms = _read("publish.refine.lane_pass_ms", first_publish)
    assert lane_ms == pytest.approx(1e3 * lanes / 42)
    assert _read("publish.refine.pass_ms", first_publish) \
        == pytest.approx(1e3 * refine / 12)
    assert _read("publish.lanes_hinted", first_publish) == 4.0
    assert _read("publish.lanes_uncertified", first_publish) == 0.0
    assert _read("publish.refined_share", first_publish) == 100.0
    assert _read("publish.fallback_share", first_publish) == 0.0
    # the five-way split of the module is what it was, and the refinement
    # leads it
    parts = {p: _read(f"publish.{p}.device_s", first_publish)
             for p in ("sample", "fast", "refine", "accounting", "unscoped")}
    whole = trace.module_seconds(
        first_publish.trace_rows, first_publish.trace_windows,
        "jit_disseminate")["jit_disseminate"]
    assert sum(parts.values()) == pytest.approx(whole, rel=1e-9)
    assert parts["refine"] == max(parts.values())


def _op(name, start, dur, scope):
    return {"plane": PLANE, "name": name, "start_ns": float(start),
            "dur_ns": float(dur), "scope": scope}


@pytest.fixture
def by_hand(monkeypatch):
    """Two publishes in one module of 1,000 ns: fast 300, refine 600 (its
    lanes 540, the conditional's operands 60 outside them), accounting 100.
    The lanes' passes are 40 + 50 where the deepest lane's are 12 + 13."""
    d = "jit(disseminate)/"
    lane = "refine/cond/branch_1_fun/per_fragment/while/body/"
    ops = [
        _op("fusion.1", 0, 300, d + "fast/per_fragment/while/body/a"),
        _op("while.1", 300, 540, ""),    # the rolled loop: no scope of its own
        _op("fusion.2", 300, 400, d + lane + "fixpoint/while/body/b"),
        _op("fusion.3", 700, 140, d + lane + "c"),
        _op("fusion.4", 840, 60, d + "refine/cond/branch_1_fun/select_n"),
        _op("fusion.5", 900, 100, d + "accounting/sort"),
    ]
    counters = {"fast_iters": "20", "refine_passes": "12", "refined": "1",
                "fell_back": "0", "converged": "1", "refined_serial": "0",
                "refine_lane_passes": "40", "lanes_hinted": "3",
                "lanes_uncertified": "0", "fragments": "4",
                "peers": "100000", "slots": "40", "rounds": "3",
                "formulation": "row_pull", "in_sequence": "1"}
    profile = {
        "modules": [{"plane": PLANE, "name": "jit_disseminate(1)",
                     "start_ns": 0.0, "dur_ns": 1000.0}],
        "ops": ops,
        "host": [{"name": "sim:publish/counters", "start_ns": 1000.0,
                  "dur_ns": 0.0, "attrs": counters},
                 {"name": "sim:publish/counters", "start_ns": 1001.0,
                  "dur_ns": 0.0,
                  "attrs": {**counters, "refine_passes": "13",
                            "refine_lane_passes": "50", "lanes_hinted": "4",
                            "fell_back": "1", "lanes_uncertified": "1"}}],
    }
    monkeypatch.setattr(program_profile, "load", lambda: profile)
    rows = ([{**r, "line": trace.MODULE_LINE} for r in profile["modules"]]
            + [{**r, "line": trace.OP_LINE} for r in ops])
    return SimpleNamespace(trace_rows=rows, trace_windows=[(0.0, 2000.0)],
                           recorder=None, experiments=[], memory_stats=[])


def test_lane_metrics_by_hand(by_hand):
    assert _read("publish.refine.device_s", by_hand) == pytest.approx(600e-9)
    assert _read("publish.refine.per_fragment.device_s", by_hand) \
        == pytest.approx(540e-9)
    # 540 ns over the lanes' 90 passes, in ms; the older metric divides all
    # of `refine` by the deepest lanes' 25
    assert _read("publish.refine.lane_pass_ms", by_hand) \
        == pytest.approx(540e-6 / 90)
    assert _read("publish.refine.pass_ms", by_hand) \
        == pytest.approx(600e-6 / 25)
    assert _read("publish.lanes_hinted", by_hand) == 3.5
    assert _read("publish.lanes_uncertified", by_hand) == 0.5
    assert _read("publish.fallback_share", by_hand) == 50.0


def test_no_lane_pass_reads_zero(by_hand, monkeypatch):
    flat = program_profile.load()
    flat = {**flat, "host": [
        {**r, "attrs": {**r["attrs"], "refine_lane_passes": "0"}}
        for r in flat["host"]]}
    monkeypatch.setattr(program_profile, "load", lambda: flat)
    assert _read("publish.refine.lane_pass_ms", by_hand) == 0.0


def test_a_program_without_them_gives_none(by_hand, monkeypatch):
    """The parent of ISSUE 34 has the scope (PR 30) and none of the three
    counters: the counter metrics and the per-count one return None and do
    not raise, the scope's seconds are read."""
    parent = program_profile.load()
    parent = {**parent, "host": [
        {**r, "attrs": {k: v for k, v in r["attrs"].items()
                        if k not in ("refine_lane_passes", "lanes_hinted",
                                     "lanes_uncertified")}}
        for r in parent["host"]]}
    monkeypatch.setattr(program_profile, "load", lambda: parent)
    assert _read("publish.refine.lane_pass_ms", by_hand) is None
    assert _read("publish.lanes_hinted", by_hand) is None
    assert _read("publish.lanes_uncertified", by_hand) is None
    assert _read("publish.refine.per_fragment.device_s", by_hand) \
        == pytest.approx(540e-9)
    assert _read("publish.refine.pass_ms", by_hand) \
        == pytest.approx(600e-6 / 25)
    monkeypatch.setattr(program_profile, "load", lambda: None)
    for name in NEW:
        assert _read(name, by_hand) is None
