"""BENCHMARK.json against the contract's limits on names, units and files,
and every name resolved to its file."""

import importlib
import json
import os
import re

import pytest

from benchmark.harness import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    with open(os.path.join(manifest.CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_limits(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= man["run_seconds"] <= 51
    assert man["paths"] == ["benchmark"]
    assert all(w.startswith(("python3", "benchmark/", "--"))
               for w in man["command"])
    size = os.path.getsize(os.path.join(manifest.CHECKOUT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_names_units_and_one_line_texts(man):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in man[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            for key in ("why", "layer"):
                if key in e:
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                        and "\t" not in e[key], (e["name"], key)
    assert len(names) == len(set(names))
    for e in man["end_to_end"] + man["per_layer"]:
        assert UNIT.match(e["unit"]), e["unit"]
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
    for e in man["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    for e in man["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and c["file"].startswith("benchmark/")
        assert all(NAME.match(k) for k in c["reduced"])
    assert "setup_s" in [e["name"] for e in man["end_to_end"]]


def test_every_cell_resolves_and_reports(man):
    e2e = {e["name"] for e in man["end_to_end"]}
    used = set()
    for w in man["workloads"]:
        cell = manifest.load_cell(w["name"])
        used.add(cell.config_name)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        samples = {"experiment_seconds": [3.0, 1.0, 2.0],
                   "setup_seconds": [9.0]}
        for m in cell.end_to_end:
            assert m["spec"]["unit"] == m["unit"]
            assert manifest.statistic(m["spec"], samples) > 0
        for spec in cell.spans:
            module, _, name = spec.partition(":")
            cls, _, attr = name.partition(".")
            assert attr in vars(getattr(importlib.import_module(module), cls))
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in reported
            assert m["spec"]["layer"] == m["layer"]
            assert m["spec"]["unit"] == m["unit"]
            assert callable(manifest.reader(m["spec"]["reader"]))
        assert set(cell.config["reduced"]) == set(
            next(c for c in man["configs"]
                 if c["name"] == cell.config_name)["reduced"])
        # the entry is found and makes an experiment's argv (for `run`,
        # every positional it needs is there)
        argv, env = cell.entry.invocation(cell, 1, "x")
        assert argv[0] == cell.entry_name and isinstance(env, dict)
    assert used == {c["name"] for c in man["configs"]}
    assert all(m["moves"] in e2e for m in man["per_layer"])


def test_link_model_is_the_programs(man):
    """The constants the reference reads are stated in the configuration's
    file; they have to be the program's own for that run."""
    from dst_libp2p_test_node_tpu.config.env import gossipsub_params_from_env
    from dst_libp2p_test_node_tpu.ops.state import SimParams
    from dst_libp2p_test_node_tpu.runtime.simulator import MUXER_PROC_MS

    params = SimParams.from_gossipsub(
        100, 40, gossipsub_params_from_env(),
        proc_delay_ms=MUXER_PROC_MS["yamux"])
    for c in man["configs"]:
        with open(os.path.join(manifest.CHECKOUT, c["file"])) as f:
            link = json.load(f)["link_model"]
        for key, value in link.items():
            assert getattr(params, key) == pytest.approx(value), key


def test_unknown_device_is_an_error():
    assert manifest.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        manifest.peaks("TPU v99")


def test_statistics_of_all_samples():
    samples = {"experiment_seconds": [4.0, 1.0, 2.0, 3.0], "setup_seconds": [7.5]}
    stat = manifest.statistic
    assert stat({"of": "experiment_seconds", "statistic": "median"},
                samples) == 2.5
    assert stat({"of": "experiment_seconds", "statistic": "percentile",
                 "q": 90}, samples) == pytest.approx(3.7)
    assert stat({"of": "setup_seconds", "statistic": "value"}, samples) == 7.5
    with pytest.raises(SystemExit):
        stat({"name": "x", "of": "experiment_seconds", "statistic": "value"},
             samples)
