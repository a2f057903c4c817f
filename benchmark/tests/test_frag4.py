"""The cell runsh-100k-frag4.headline (ISSUE 30): the manifest finds it and
its `run` argv carries FRAGMENTS=4; benchmark/run.py --rehearse drives all
three parts of `correct` with four fragments on the CPU at 200 peers, with
the lanes vmapped and, the gather budget shrunk to one lane's pull, in
sequence as at 100,000 peers; the readers of the fragment counters and of
the per_fragment scopes, on a recorded chip profile and on rows made by
hand."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark import run
from benchmark.entries.run import POSITIONALS, arguments, run_argv
from benchmark.harness import manifest, program_profile, trace

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "BENCHMARK.frag4.test.json")
METRICS = os.path.join(os.path.dirname(HERE), "layer_metrics")
CELL = "runsh-100k-frag4.headline"
NEW = ("publish.fragments", "publish.serial_refine_share",
       "publish.fast.per_fragment.device_s",
       "publish.accounting.per_fragment.device_s")
PLANE = "/device:TPU:0"


def _read(name, ctx):
    with open(os.path.join(METRICS, name + ".json")) as f:
        spec = json.load(f)
    return manifest.reader(spec["reader"])(ctx, **spec["params"])


# ------------------------------------------------------------ the manifest


def test_the_cell_loads_and_runs_four_fragments():
    cell = manifest.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "runsh-100k-frag4", "headline")
    argv = run_argv(arguments(cell), 2147483777, "out")
    positional = dict(zip(POSITIONALS, argv[1:]))
    assert positional["num_frag"] == "4" and positional["nodes"] == "100000"
    assert positional["msg_size"] == "15000"
    assert positional["num_publishers"] == "3"
    # the same experiment as runsh-100k but for the fragments
    other = manifest.load_cell("runsh-100k.headline")
    differing = {k for k in POSITIONALS if arguments(cell)["positionals"][k]
                 != arguments(other)["positionals"][k]}
    assert differing == {"num_frag"}
    assert arguments(cell)["flags"] == arguments(other)["flags"] == []
    assert cell.config["link_model"] == other.config["link_model"]
    assert set(cell.config["reduced"]) == {"num_publishers", "churn"}
    assert any("LAST fragment" in s
               for s in cell.config["guarantees"]["stated"])


def test_the_new_metrics_are_the_new_cells_alone():
    with open(os.path.join(manifest.CHECKOUT, "BENCHMARK.json")) as f:
        man = json.load(f)
    by_name = {m["name"]: m for m in man["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "experiment_s"
    reported = {m["name"] for m in manifest.load_cell(CELL).per_layer}
    assert set(NEW) <= reported
    # the roofline share's operand table counts a vmapped publish (lanes x
    # the slowest lane's iterations): not read where the lanes run in
    # sequence
    assert by_name["publish.fixpoint.hbm_share"]["workloads"] == [
        "runsh-1k.headline", "runsh-100k.headline", "runsh-100k.meshonly"]
    assert "publish.fixpoint.hbm_share" not in reported
    for old in ("runsh-1k.headline", "runsh-100k.headline",
                "runsh-100k.meshonly"):
        names = {m["name"] for m in manifest.load_cell(old).per_layer}
        assert "publish.fixpoint.hbm_share" in names
        assert not set(NEW) & names


# ------------------------------------------------------------ the rehearsal


def _lines(out: str) -> list[dict]:
    return [json.loads(ln) for ln in out.strip().splitlines()]


@pytest.fixture
def one_lane_budget(monkeypatch):
    """Call it to set the gather budget of ops/pull to one lane's pull at
    the tiny shape, so that four fragments run in sequence as they do at
    (100000, 40); the program's jit caches are emptied on both sides.
    Returns the (peers, slots) shape."""
    import jax

    import dst_libp2p_test_node_tpu.ops.pull as pull_mod
    from dst_libp2p_test_node_tpu.runtime.simulator import (
        ExperimentConfig, graph_capacity)

    def shrink():
        peers = int(arguments(manifest.load_cell(
            "tiny-frag4.headline", MANIFEST))["positionals"]["nodes"])
        shape = (peers, graph_capacity(ExperimentConfig()))
        monkeypatch.setattr(
            pull_mod, "_MAX_INTERMEDIATE_BYTES",
            pull_mod.intermediate_bytes(jax.numpy.float32, shape))
        jax.clear_caches()
        return shape

    yield shrink
    monkeypatch.undo()
    jax.clear_caches()


def _rehearse(capsys, trace_flag, seed):
    rc = run.main(["--manifest", MANIFEST, "--seconds", "0.5", "--workload",
                   "tiny-frag4.headline", "--seed", str(seed), "--trace",
                   trace_flag, "--rehearse"])
    lines = _lines(capsys.readouterr().out)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True and last["metrics"] == {}
    assert last["failed"] == 0 and last["attempted"] >= 1
    part = {ln["line"]: ln for ln in lines[:-1]}
    for kind in ("correct_part1", "correct_part2", "correct_part3_tie",
                 "correct_part3"):
        assert part[kind]["passed"] is True, part[kind]
    assert part["window"]["compilations_in_window"] == 0
    # all three messages replayed, each on four fragments' draws
    assert sum(ln.get("line") == "correct_part3" for ln in lines) == 3
    return part


@pytest.mark.parametrize("trace_flag", ["0", "1"])
def test_rehearse_four_fragments(capsys, trace_flag):
    part = _rehearse(capsys, trace_flag, 2147483999)
    if trace_flag == "1":
        # the counters are read off XLA:CPU's profile too; the device scopes
        # need a device plane, which it has not
        assert {"publish.fragments", "publish.serial_refine_share"} <= set(
            part["rehearse"]["would_report"])


def test_rehearse_four_fragments_in_sequence(capsys, one_lane_budget):
    """The same seed with the lanes vmapped and in sequence: `correct` in
    all three parts both times, and one latencies1."""
    from dst_libp2p_test_node_tpu.ops.disseminate import (
        fixpoint_formulation, fragments_in_sequence)

    vmapped = _rehearse(capsys, "0", 5)["statistics_digest"]
    shape = one_lane_budget()
    assert fragments_in_sequence(shape, 4)
    assert fixpoint_formulation(shape) == "row_pull"
    in_sequence = _rehearse(capsys, "0", 5)["statistics_digest"]
    assert len(vmapped["latencies_sha256"]) == 64
    assert in_sequence["latencies_sha256"] == vmapped["latencies_sha256"]


# -------------------------------------------------------------- the readers


@pytest.fixture(scope="module")
def recorded():
    """recorded_profile_frag4.json as program_profile's rows (its `note`
    says what it is: four fragment lanes in sequence, on a v5e)."""
    with open(os.path.join(HERE, "recorded_profile_frag4.json")) as f:
        rec = json.load(f)
    return {
        "modules": [dict(zip(("plane", "name", "start_ns", "dur_ns"), r))
                    for r in rec["modules"]],
        "ops": [{"plane": p, "name": n, "start_ns": s, "dur_ns": d,
                 "scope": rec["scopes"][i]} for p, n, s, d, i in rec["ops"]],
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


def test_recorded_lanes_ran_in_sequence_on_row_pull(recorded):
    said = [h["attrs"] for h in recorded["host"]
            if h["name"] == "sim:publish/counters"]
    assert len(said) == 3
    for attrs in said:
        assert (attrs["fragments"], attrs["formulation"],
                attrs["in_sequence"], attrs["refined_serial"]) == (
                    "4", "row_pull", "1", "0")
    # the rolled loop over the lanes is in the scope paths
    assert any(program_profile.follows(r["scope"], [
        "fast", "per_fragment", "while", "fixpoint", "while"],
        ["sample", "fast", "refine", "accounting"])
        for r in recorded["ops"])


def test_per_fragment_scopes_on_the_recorded_profile(first_publish):
    fast = _read("publish.fast.device_s", first_publish)
    fast_lanes = _read("publish.fast.per_fragment.device_s", first_publish)
    acct = _read("publish.accounting.device_s", first_publish)
    acct_lanes = _read("publish.accounting.per_fragment.device_s",
                       first_publish)
    # nearly all of `fast` is the lanes' own work; what the fragment axis
    # costs `accounting` outside them (the downlink fold over (N, F*C),
    # the sums, the state) is a visible share
    assert 0.9 * fast < fast_lanes <= fast
    assert 0.3 * acct < acct_lanes < 0.98 * acct
    # the five-way split of the module is what it was
    parts = [_read(f"publish.{p}.device_s", first_publish)
             for p in ("sample", "fast", "refine", "accounting", "unscoped")]
    whole = trace.module_seconds(
        first_publish.trace_rows, first_publish.trace_windows,
        "jit_disseminate")["jit_disseminate"]
    assert sum(parts) == pytest.approx(whole, rel=1e-9)
    assert _read("publish.fragments", first_publish) == 4.0
    assert _read("publish.serial_refine_share", first_publish) == 0.0


def _op(name, start, dur, scope):
    return {"plane": PLANE, "name": name, "start_ns": float(start),
            "dur_ns": float(dur), "scope": scope}


@pytest.fixture
def by_hand(monkeypatch):
    """One publish of 1,000 ns: fast 600 (per_fragment 560 of it, in a
    rolled loop), refine 200, accounting 150 (per_fragment 90), 50 loose."""
    d = "jit(disseminate)/"
    ops = [
        _op("while.1", 0, 560, ""),      # the rolled loop: no scope of its own
        _op("fusion.1", 0, 300,
            d + "fast/per_fragment/while/body/fixpoint/while/body/a"),
        _op("fusion.2", 300, 260, d + "fast/per_fragment/while/body/fold/b"),
        _op("fusion.3", 560, 40, d + "fast/reduce_or"),
        _op("fusion.4", 600, 200,
            d + "refine/cond/branch_1_fun/per_fragment/while/body/c"),
        _op("fusion.5", 800, 90, d + "accounting/per_fragment/while/body/d"),
        _op("fusion.6", 890, 60, d + "accounting/sort"),
        _op("fusion.7", 950, 50, d + "reduce_max"),
    ]
    counters = {"fast_iters": "40", "refine_passes": "12", "refined": "1",
                "fell_back": "0", "converged": "1", "refined_serial": "0",
                "fragments": "4", "peers": "100000", "slots": "40",
                "rounds": "3", "formulation": "row_pull", "in_sequence": "1"}
    profile = {
        "modules": [{"plane": PLANE, "name": "jit_disseminate(1)",
                     "start_ns": 0.0, "dur_ns": 1000.0}],
        "ops": ops,
        "host": [{"name": "sim:publish/counters", "start_ns": 1000.0,
                  "dur_ns": 0.0, "attrs": counters},
                 {"name": "sim:publish/counters", "start_ns": 1001.0,
                  "dur_ns": 0.0,
                  "attrs": {**counters, "refined_serial": "1"}}],
    }
    monkeypatch.setattr(program_profile, "load", lambda: profile)
    rows = ([{**r, "line": trace.MODULE_LINE} for r in profile["modules"]]
            + [{**r, "line": trace.OP_LINE} for r in ops])
    return SimpleNamespace(trace_rows=rows, trace_windows=[(0.0, 2000.0)],
                           recorder=None, experiments=[], memory_stats=[])


def test_per_fragment_scopes_by_hand(by_hand):
    # the loop's own time (560 - 300 - 260 = 0 here) has no scope; the
    # per_fragment of `refine` is not the one of `fast` or `accounting`
    assert _read("publish.fast.per_fragment.device_s", by_hand) \
        == pytest.approx(560e-9)
    assert _read("publish.fast.device_s", by_hand) == pytest.approx(600e-9)
    assert _read("publish.accounting.per_fragment.device_s", by_hand) \
        == pytest.approx(90e-9)
    assert _read("publish.accounting.device_s", by_hand) \
        == pytest.approx(150e-9)
    assert _read("publish.refine.device_s", by_hand) == pytest.approx(200e-9)


def test_fragment_counters_by_hand(by_hand):
    assert _read("publish.fragments", by_hand) == 4.0
    # one of the two publishes was refined by the global-sort engine
    assert _read("publish.serial_refine_share", by_hand) == 50.0
    assert _read("publish.refined_share", by_hand) == 100.0


def test_a_program_without_them_gives_none(by_hand, monkeypatch):
    """The parent of ISSUE 30: no per_fragment scope, no refined_serial on
    the annotation. The readers return None and do not raise."""
    parent = program_profile.load()
    parent = {
        **parent,
        "ops": [{**r, "scope": r["scope"].replace("per_fragment/", "")}
                for r in parent["ops"]],
        "host": [{**r, "attrs": {k: v for k, v in r["attrs"].items()
                                 if k not in ("refined_serial",
                                              "in_sequence")}}
                 for r in parent["host"]]}
    monkeypatch.setattr(program_profile, "load", lambda: parent)
    assert _read("publish.fast.per_fragment.device_s", by_hand) is None
    assert _read("publish.accounting.per_fragment.device_s", by_hand) is None
    assert _read("publish.serial_refine_share", by_hand) is None
    assert _read("publish.fragments", by_hand) == 4.0   # it stated that
    assert _read("publish.fast.device_s", by_hand) == pytest.approx(600e-9)
    monkeypatch.setattr(program_profile, "load", lambda: None)
    for name in NEW:
        assert _read(name, by_hand) is None
