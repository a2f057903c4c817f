"""The reduction from trace rows to device metrics, on a small recorded
trace (recorded_trace_1k.json: one runsh-1k.headline experiment on a v5e,
thinned as its `note` says), against brute force on a 1 us grid."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import trace
from benchmark.harness.spans import ANNOTATION_PREFIX

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def rows():
    with open(os.path.join(HERE, "recorded_trace_1k.json")) as f:
        rec = json.load(f)
    return [dict(zip(rec["columns"], r)) for r in rec["rows"]]


@pytest.fixture(scope="module")
def wins(rows):
    return trace.windows(rows, ANNOTATION_PREFIX + "experiment")


def _grid_busy(rows, lo, hi):
    grid = np.zeros(int((hi - lo) / 1e3) + 1, bool)
    for r in rows:
        if r["line"] == trace.OP_LINE:
            a = max(r["start_ns"], lo)
            b = min(r["start_ns"] + r["dur_ns"], hi)
            if b > a:
                grid[int((a - lo) / 1e3):int(np.ceil((b - lo) / 1e3))] = True
    return grid.sum() * 1e3 / 1e9


def test_one_experiment_one_device(rows, wins):
    assert len(wins) == 1
    assert trace.device_planes(rows) == ["/device:TPU:0"]
    assert 0.30 < (wins[0][1] - wins[0][0]) / 1e9 < 0.36


def test_busy_is_the_union_of_op_intervals(rows, wins):
    busy, window = trace.busy_and_window_s(rows, wins)
    assert window == pytest.approx((wins[0][1] - wins[0][0]) / 1e9)
    # the grid rounds each of ~1100 ops outwards by up to 1 us
    assert busy == pytest.approx(_grid_busy(rows, *wins[0]), abs=2.5e-3)
    assert 0.0 < busy < window
    ops = [(r["start_ns"], r["start_ns"] + r["dur_ns"]) for r in rows
           if r["line"] == trace.OP_LINE]
    assert busy <= sum(b - a for a, b in ops) / 1e9 + 1e-12


def test_module_seconds(rows, wins):
    mods = trace.module_seconds(rows, wins)
    want = {}
    for r in rows:
        if r["line"] == trace.MODULE_LINE:
            name = r["name"].split("(")[0]
            want[name] = want.get(name, 0.0) + r["dur_ns"] / 1e9
    assert mods == pytest.approx(want)
    # 10 publishes and 10 heartbeat dispatches (500 s warm-up + 9 gaps)
    counts = {}
    for r in rows:
        if r["line"] == trace.MODULE_LINE:
            counts[r["name"].split("(")[0]] = counts.get(
                r["name"].split("(")[0], 0) + 1
    assert counts["jit_disseminate"] == 10
    assert counts["jit__run_heartbeats"] == 10
    assert mods["jit_disseminate"] > mods["jit__run_heartbeats"] > 0
    only = trace.module_seconds(rows, wins, "jit_disseminate")
    assert list(only) == ["jit_disseminate"]


def test_idle_gaps_are_named_and_add_up(rows, wins):
    busy, window = trace.busy_and_window_s(rows, wins)
    gaps = trace.idle_gaps(rows, wins, ANNOTATION_PREFIX, top=100)
    assert sum(s for _, s in gaps) == pytest.approx(window - busy)
    assert all(n.startswith(ANNOTATION_PREFIX) or n == "(no host span)"
               for n, _ in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    bd = trace.breakdown(rows, wins, ANNOTATION_PREFIX)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0] == "jit_disseminate"


def test_windows_clip(rows, wins):
    lo, hi = wins[0]
    half = [(lo, (lo + hi) / 2)]
    busy_half, window_half = trace.busy_and_window_s(rows, half)
    busy, window = trace.busy_and_window_s(rows, wins)
    assert window_half == pytest.approx(window / 2)
    assert busy_half < busy
