"""The readers of what the program itself writes into the profile (ISSUE
27), on a small recorded chip profile (recorded_profile_1k.json: one
runsh-1k.headline experiment on a v5e, with the metadata the readers need,
thinned as its `note` says) and on rows made by hand."""

import glob
import json
import os
from types import SimpleNamespace

import pytest

from benchmark.harness import manifest, program_profile, trace

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "layer_metrics")
PLANE = "/device:TPU:0"
NEW_KINDS = ("trace_scope_time", "trace_scope_per_count", "program_span",
             "program_counter", "span_device_idle", "fixpoint_hbm_share")


def spec(name):
    with open(os.path.join(METRICS, name + ".json")) as f:
        return json.load(f)


def read(name, ctx):
    s = spec(name)
    return manifest.reader(s["reader"])(ctx, **s["params"])


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_profile_1k.json")) as f:
        rec = json.load(f)
    return {
        "modules": [dict(zip(("plane", "name", "start_ns", "dur_ns"), r))
                    for r in rec["modules"]],
        "ops": [{"plane": p, "name": n, "start_ns": s, "dur_ns": d,
                 "scope": rec["scopes"][i]} for p, n, s, d, i in rec["ops"]],
        "host": [dict(zip(("name", "start_ns", "dur_ns", "attrs"), r))
                 for r in rec["host"]],
    }


def _ctx(profile, wins):
    rows = ([{**r, "line": trace.MODULE_LINE} for r in profile["modules"]]
            + [{**r, "line": trace.OP_LINE} for r in profile["ops"]])
    return SimpleNamespace(trace_rows=rows, trace_windows=wins,
                           recorder=None, experiments=[], memory_stats=[])


def _interval(profile, name, nth=0):
    r = [h for h in profile["host"] if h["name"] == "sim:" + name][nth]
    return (r["start_ns"], r["start_ns"] + r["dur_ns"])


@pytest.fixture
def experiment(recorded, monkeypatch):
    """The whole traced experiment (its `sim:run` span) as the window."""
    monkeypatch.setattr(program_profile, "load", lambda: recorded)
    return _ctx(recorded, [_interval(recorded, "run")])


@pytest.fixture
def first_publish(recorded, monkeypatch):
    """The first publish alone: the one jit_disseminate module whose op
    events the recording keeps whole."""
    monkeypatch.setattr(program_profile, "load", lambda: recorded)
    return _ctx(recorded, [_interval(recorded, "publish")])


PARTS = ["publish.sample.device_s", "publish.fast.device_s",
         "publish.refine.device_s", "publish.accounting.device_s",
         "publish.unscoped.device_s"]


def test_the_scopes_add_up_to_the_module(first_publish):
    parts = {name: read(name, first_publish) for name in PARTS}
    whole = trace.module_seconds(
        first_publish.trace_rows, first_publish.trace_windows,
        "jit_disseminate")["jit_disseminate"]
    assert all(v is not None and v >= 0.0 for v in parts.values()), parts
    assert sum(parts.values()) == pytest.approx(whole, rel=1e-9)
    assert parts["publish.unscoped.device_s"] < 0.05 * whole
    # at 1,000 peers the fast pipeline is most of a publish that kept it
    assert parts["publish.fast.device_s"] > 0.5 * whole
    assert parts["publish.refine.device_s"] < 0.01 * whole


def test_every_instant_goes_to_one_op_event(recorded, first_publish):
    lo, hi = first_publish.trace_windows[0]
    ops = [r for r in recorded["ops"] if lo <= r["start_ns"] < hi]
    assert any("while" in r["name"] for r in ops)
    own = list(program_profile.self_intervals(ops))
    total = sum(b - a for _, a, b in own)
    union = sum(b - a for a, b in trace._union(
        [(r["start_ns"], r["start_ns"] + r["dur_ns"]) for r in ops]))
    assert total == pytest.approx(union, rel=1e-12)
    assert total < sum(r["dur_ns"] for r in ops)      # the loops nest
    pieces = sorted((a, b) for _, a, b in own)
    assert all(b1 <= a2 + 1e-6 for (_, b1), (a2, _) in zip(pieces,
                                                            pieces[1:]))


def test_a_while_keeps_only_what_its_body_leaves():
    def op(name, start, dur, scope):
        return {"plane": PLANE, "name": name, "start_ns": float(start),
                "dur_ns": float(dur), "scope": scope}

    ops = [op("while.1", 0, 100, ""),
           op("fusion.1", 10, 20, "jit(f)/fast/vmap(fixpoint)/while/body/a"),
           op("while.2", 40, 50, "jit(f)/fast/vmap(fixpoint)/while/body/b"),
           op("fusion.2", 45, 5, "jit(f)/refine/cond/fixpoint/while/body/c"),
           op("fusion.3", 60, 30, "jit(f)/accounting/d"),
           op("fusion.4", 100, 7, "jit(f)/sample/e")]
    own = {}
    for o, a, b in program_profile.self_intervals(ops):
        own[o["name"]] = own.get(o["name"], 0.0) + (b - a)
    assert own == {"while.1": 30.0, "fusion.1": 20.0, "while.2": 15.0,
                   "fusion.2": 5.0, "fusion.3": 30.0, "fusion.4": 7.0}
    profile = {"ops": ops, "host": [], "modules": [
        {"plane": PLANE, "name": "jit_f(1)", "start_ns": 0.0,
         "dur_ns": 110.0}]}
    known = ["sample", "fast", "refine", "accounting"]
    got = program_profile.scope_seconds(
        profile, [(0.0, 200.0)], "jit_f",
        [["fast"], ["refine"], ["accounting"], ["sample"],
         ["fast", "fixpoint"], ["refine", "fixpoint"], ["fast", "fold"]],
        known)
    assert [round(s * 1e9, 6) for s in got] == [
        35.0, 5.0, 30.0, 7.0, 35.0, 5.0, 0.0]
    # a window that cuts an op takes the part inside
    half = program_profile.scope_seconds(
        profile, [(0.0, 75.0)], "jit_f", [["accounting"]], known)
    assert half[0] * 1e9 == pytest.approx(15.0)


def test_scope_names_take_the_transforms_off():
    names = program_profile.scope_names(
        "jit(disseminate)/fast/vmap(fixpoint)/while/body/vmap(jit(clip))/max:")
    assert names[:5] == ("disseminate", "fast", "fixpoint", "while", "body")
    assert names[5] == "clip"
    known = ["sample", "fast", "refine", "accounting"]
    assert program_profile.follows(
        "jit(disseminate)/refine/cond/branch_1_fun/fixpoint/while",
        ["refine", "fixpoint"], known)
    # the outermost of the program's scopes decides, not a later one
    assert not program_profile.follows(
        "jit(disseminate)/refine/cond/fast/x", ["fast"], known)
    assert not program_profile.follows("", ["fast"], known)


def test_counters_are_the_recorded_ones(recorded, experiment):
    attrs = [h["attrs"] for h in recorded["host"]
             if h["name"] == "sim:publish/counters"]
    assert len(attrs) == 10
    mean = sum(int(a["fast_iters"]) for a in attrs) / 10
    assert 12.0 <= mean <= 13.0
    assert read("publish.fast_iters", experiment) == pytest.approx(mean)
    assert read("publish.refine_passes", experiment) == pytest.approx(
        sum(int(a["refine_passes"]) for a in attrs) / 10)
    assert read("publish.refined_share", experiment) == pytest.approx(
        100.0 * sum(a["refined"] != "0" for a in attrs) / 10)
    assert read("publish.fallback_share", experiment) == 0.0
    # no refinement pass in the recording: the pass time reads 0, not None
    assert read("publish.refine_passes", experiment) == 0.0
    assert read("publish.refine.pass_ms", experiment) == 0.0


def test_spans_are_the_recorded_ones(recorded, experiment):
    def seconds(name):
        return sum(h["dur_ns"] for h in recorded["host"]
                   if h["name"] == "sim:" + name) / 1e9

    parts = [read(f"publish.{p}.host_s", experiment)
             for p in ("prepare", "dispatch", "read")]
    assert parts == pytest.approx([seconds("publish/prepare"),
                                   seconds("publish/dispatch"),
                                   seconds("publish/read")])
    assert 0.95 * seconds("publish") < sum(parts) <= seconds("publish")
    assert read("entry.artifacts.host_s", experiment) == pytest.approx(
        seconds("run/write_gml") + seconds("run/write_yaml"))
    assert read("entry.report.host_s", experiment) == pytest.approx(
        seconds("run/report") + seconds("run/stats_json"))
    assert read("build.topology.host_s", experiment) == pytest.approx(
        seconds("run/topology"))
    assert read("build.simulator.host_s", experiment) == pytest.approx(
        seconds("run/simulator_init"))
    for part, name in (("latencies", "run/write_latencies"),
                       ("shadowlog", "run/write_shadowlog"),
                       ("summary", "run/summary")):
        assert read(f"emit.{part}.host_s", experiment) == pytest.approx(
            seconds(name))
    # two traced experiments halve what one of them gives
    lo, hi = experiment.trace_windows[0]
    experiment.trace_windows = [(lo, hi), (hi + 1.0, hi + 2.0)]
    assert read("publish.read.host_s", experiment) == pytest.approx(
        seconds("publish/read") / 2)


def test_idle_under_spans_against_a_grid(recorded, experiment):
    import numpy as np

    for name in ("device.idle_in_entry_s", "device.idle_in_emit_s"):
        spans = [_interval(recorded, n, i) for n in spec(name)["params"][
            "names"] for i in range(sum(h["name"] == "sim:" + n
                                        for h in recorded["host"]))]
        idle = 0.0
        for lo, hi in spans:
            grid = np.zeros(int((hi - lo) / 1e3) + 1, bool)
            for r in recorded["ops"]:
                a = max(r["start_ns"], lo)
                b = min(r["start_ns"] + r["dur_ns"], hi)
                if b > a:
                    grid[int((a - lo) / 1e3):
                         int(np.ceil((b - lo) / 1e3))] = True
            idle += (hi - lo) / 1e9 - grid.sum() * 1e3 / 1e9
        got = read(name, experiment)
        assert got == pytest.approx(idle, abs=1e-4)
        assert 0.0 < got <= sum(b - a for a, b in spans) / 1e9


def test_hbm_share_lies_between_0_and_100(recorded, experiment,
                                          first_publish, monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: [
        SimpleNamespace(device_kind="TPU v5 lite")])
    params = spec("publish.fixpoint.hbm_share")["params"]
    for ctx in (first_publish, experiment):
        share = read("publish.fixpoint.hbm_share", ctx)
        assert share is not None and 0.0 < share < 100.0
    # by hand, for the first publish: iterations x the body's operands over
    # the loops' seconds over the published peak
    from benchmark.readers import fixpoint_hbm_share

    attrs = next(h["attrs"] for h in recorded["host"]
                 if h["name"] == "sim:publish/counters")
    n, c = int(attrs["peers"]), int(attrs["slots"])
    assert (n, c, attrs["formulation"]) == (1000, 40, "row_pull")
    fast = params["loops"][0]
    per_iter = fixpoint_hbm_share.body_bytes(
        fast["operands"], {"peers": n, "slots": c, "rounds": 3})
    assert per_iter == 4 * (2 * n + 2 * n + n) + 4 * n * c * (1 + 2 + 2 + 2 + 1)
    no_gossip = fixpoint_hbm_share.body_bytes(
        fast["operands"], {"peers": n, "slots": c, "rounds": 0})
    assert no_gossip == 4 * (2 * n + 2 * n) + 4 * n * c * (1 + 2 + 2 + 1)
    seconds = program_profile.scope_seconds(
        recorded, first_publish.trace_windows, params["module"],
        [fast["path"]], params["scopes"])[0]
    want = (100.0 * int(attrs["fast_iters"]) * per_iter / seconds
            / manifest.peaks("TPU v5 lite")["hbm_bytes_per_s"])
    assert read("publish.fixpoint.hbm_share", first_publish) \
        == pytest.approx(want)
    # another formulation than the table's is not read
    for h in recorded["host"]:
        monkeypatch.setitem(h["attrs"], "formulation", "recv")
    assert read("publish.fixpoint.hbm_share", experiment) is None


def test_a_program_without_scopes_spans_or_counters_reads_nothing(
        recorded, monkeypatch):
    # the parent of the PR: its ops carry an op_name but none of the
    # program's scopes, and its host plane has no sim: annotation
    bare = {"modules": recorded["modules"], "host": [],
            "ops": [{**r, "scope": "/".join(
                p for p in r["scope"].split("/")
                if program_profile.scope_names(p)[0] not in
                ("sample", "fast", "refine", "accounting"))}
                for r in recorded["ops"]]}
    wins = [_interval(recorded, "run")]
    for profile in (bare, None):
        monkeypatch.setattr(program_profile, "load", lambda p=profile: p)
        ctx = _ctx(bare, wins)
        for path in sorted(glob.glob(os.path.join(METRICS, "*.json"))):
            with open(path) as f:
                s = json.load(f)
            if s["reader"] in NEW_KINDS:
                assert manifest.reader(s["reader"])(
                    ctx, **s["params"]) is None, s["name"]


# PR 26's nine, which time a layer from outside: their layers, and the
# kernels', are the layers a metric the program writes may belong to
FROM_OUTSIDE = ("entry.self_s", "build.host_s", "heartbeat.device_s",
                "heartbeat.host_s", "publish.device_s", "publish.host_s",
                "emit.host_s", "device.idle_share", "device.peak_hbm_gib")


def test_every_new_metric_is_in_the_manifest_and_names_a_reader():
    """Every metric file on a reader of what the program itself writes, by
    membership and its own fields: wherever a later PR appends its entry."""
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        man = json.load(f)
    entries = {m["name"]: m for m in man["per_layer"]}
    cells = {w["name"] for w in man["workloads"]}
    layers = {entries[name]["layer"] for name in FROM_OUTSIDE} | {"kernels"}
    new = [p for p in sorted(glob.glob(os.path.join(METRICS, "*.json")))
           if json.load(open(p))["reader"] in NEW_KINDS]
    assert len(new) >= 23       # PR 27's; later PRs brought more
    for path in new:
        s = json.load(open(path))
        entry = entries[s["name"]]
        assert os.path.basename(path) == s["name"] + ".json"
        assert (entry["layer"], entry["unit"], entry["moves"]) == (
            s["layer"], s["unit"], "experiment_s")
        assert entry["layer"] in layers
        # a list, where it has one, names cells that are there
        assert set(entry.get("workloads", cells)) <= cells
        assert callable(manifest.reader(s["reader"]))
        # no parameter of a program metric may be taken for a callable to
        # wrap (harness/manifest.Cell.spans reads `params.spans`)
        assert "spans" not in s["params"]


def test_the_newest_profile_under_the_work_directory_is_found(tmp_path):
    assert program_profile.find_xplane(str(tmp_path)) is None
    made = []
    for cell, stamp in (("a", "t1"), ("b", "t2")):
        d = tmp_path / cell / "trace" / "plugins" / "profile" / stamp
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"")
        made.append(str(d / "host.xplane.pb"))
    os.utime(made[0], (10, 10))
    os.utime(made[1], (20, 20))
    assert program_profile.find_xplane(str(tmp_path)) == made[1]
    # an empty profile reads as no rows, and does not raise
    assert program_profile.rows_from_xplane(made[1]) == {
        "ops": [], "modules": [], "host": []}
