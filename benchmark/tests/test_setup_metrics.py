"""The seven `setup.*` metrics (ISSUE 40) and their reader,
benchmark/readers/program_setup.py, on a process record the program wrote on
the chip (recorded_process_record.json, whose `note` says which run) and on
records made by hand."""

import glob
import json
import os

import pytest

from benchmark.harness import manifest
from benchmark.readers import program_setup

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "layer_metrics")
LAYER = "set-up (process start to the first finished experiment)"
NAMES = ["setup.before_first_turn_s", "setup.first_turn_s",
         "setup.trace_lower_s", "setup.compile_s", "setup.load_s",
         "setup.programs_compiled", "setup.programs_loaded"]
CELLS = ["runsh-1k.headline", "runsh-100k.headline", "runsh-100k.meshonly",
         "runsh-100k-frag4.headline", "runsh-100k-128k-frag4.headline",
         "runsh-100k-churn.headline"]


def spec(name):
    with open(os.path.join(METRICS, name + ".json")) as f:
        return json.load(f)


def read(name):
    s = spec(name)
    return manifest.reader(s["reader"])(None, **s["params"])


@pytest.fixture
def recorded(monkeypatch):
    with open(os.path.join(HERE, "recorded_process_record.json")) as f:
        rec = json.load(f)["record"]
    monkeypatch.setattr(program_setup, "record", lambda: rec)
    return rec


def test_the_reader_reads_marks_and_the_ledger(recorded):
    marks, setup = recorded["marks"], recorded["compile"]["setup"]
    got = {name: read(name) for name in NAMES}
    assert got == {
        "setup.before_first_turn_s": marks["turn1_start"] - marks["imported"],
        "setup.first_turn_s": marks["turn1_end"] - marks["turn1_start"],
        "setup.trace_lower_s": setup["trace_lower_s"],
        "setup.compile_s": setup["compile_s"],
        "setup.load_s": setup["load_s"],
        "setup.programs_compiled": setup["compiled"],
        "setup.programs_loaded": setup["loaded"],
    }
    assert all(v is not None and v >= 0 for v in got.values())
    # counts against seconds: what was counted took time, and what the
    # ledger names adds up to its sums
    assert (got["setup.programs_compiled"] > 0) == (got["setup.compile_s"] > 0)
    assert (got["setup.programs_loaded"] > 0) == (got["setup.load_s"] > 0)
    by_fun = setup["by_fun"].values()
    assert sum(e["compiled"] for e in by_fun) == got["setup.programs_compiled"]
    assert sum(e["loaded"] for e in by_fun) == got["setup.programs_loaded"]
    assert sum(e["compile_s"] for e in by_fun) == pytest.approx(
        got["setup.compile_s"])
    assert sum(e["load_s"] for e in by_fun) == pytest.approx(
        got["setup.load_s"])
    # one process: the stages of the first turn fit inside it
    assert (got["setup.trace_lower_s"] + got["setup.compile_s"]
            + got["setup.load_s"]) <= got["setup.first_turn_s"]


def test_no_record_and_no_mark_give_none(monkeypatch):
    monkeypatch.setattr(program_setup, "record", lambda: None)
    assert [read(name) for name in NAMES] == [None] * 7
    # a process that entered no turn: the ledger reads, the marks do not
    monkeypatch.setattr(program_setup, "record", lambda: {
        "marks": {"imported": 1.0},
        "compile": {"setup": {"compiled": 2, "loaded": 0, "compile_s": 0.5,
                              "load_s": 0.0, "trace_lower_s": 0.25}}})
    assert [read(name) for name in NAMES] == [None, None, 0.25, 0.5, 0.0, 2, 0]


def test_a_program_without_the_record_gives_none(monkeypatch):
    from dst_libp2p_test_node_tpu.runtime import profiling

    assert isinstance(program_setup.record(), dict)
    monkeypatch.delattr(profiling, "process_record")
    assert program_setup.record() is None       # the parent's program


@pytest.mark.parametrize("name", NAMES)
def test_the_metric_file_and_the_manifest_entry_agree(name):
    with open(os.path.join(manifest.CHECKOUT, "BENCHMARK.json")) as f:
        man = json.load(f)
    (entry,) = [m for m in man["per_layer"] if m["name"] == name]
    s = spec(name)
    assert s["name"] == name and s["reader"] == "program_setup"
    assert callable(manifest.reader(s["reader"]))
    assert (entry["layer"], entry["unit"], entry["moves"]) == (
        LAYER, s["unit"], "setup_s") == (s["layer"], s["unit"], s["moves"])
    assert entry["workloads"] == CELLS
    assert entry["better"] == "lower"
    assert entry["source"] == ("program_counter" if s["unit"] == "count"
                               else "program_span")
    assert "does not apply" in s["what"]
    # no parameter may be taken for a callable to wrap (Cell.spans)
    assert "spans" not in s["params"]


def test_only_these_seven_name_the_reader_and_every_cell_loads_them():
    named = [os.path.basename(p)[:-5]
             for p in sorted(glob.glob(os.path.join(METRICS, "*.json")))
             if json.load(open(p))["reader"] == "program_setup"]
    assert named == sorted(NAMES)
    for cell in CELLS:
        loaded = {m["name"] for m in manifest.load_cell(cell).per_layer}
        assert set(NAMES) <= loaded
