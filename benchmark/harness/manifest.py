"""Find everything that belongs to a cell by the names in BENCHMARK.json.

    cell     -> BENCHMARK.json workloads[name]
    config   -> the `file` of BENCHMARK.json configs[cell.config]
    entry    -> benchmark/entries/<the configuration's `entry`>.py: the
                argv and environment of one experiment, its invariants, its
                reference check (benchmark/entries/__init__.py names the
                entry of a file that says none)
    traffic  -> benchmark/traffic/<cell.traffic>.json
    metric   -> benchmark/layer_metrics/<metric name>.json, whose `reader`
                names benchmark/readers/<reader>.py (per-layer), or
                benchmark/end_to_end/<metric name>.json, which names the
                samples and the statistic taken of them (end-to-end)

A later PR adds entries and files; nothing here lists them.
"""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass
from types import ModuleType

from benchmark import entries

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _named(entries: list[dict], name: str, what: str) -> dict:
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise SystemExit(f"benchmark: {what} {name!r} is not in the manifest "
                         f"(has: {[e['name'] for e in entries]})")
    return found[0]


# what an entry module has to have (benchmark/README.md, "An entry point")
ENTRY_PARTS = ("invocation", "invariants", "digest_line", "captured",
               "against_reference", "summarised")


def load_entry(name: str, config_path: str) -> ModuleType:
    """The entry module `<name>.py`: of `entries/` beside the directory the
    configuration's file is in, where this checkout has one (a
    configuration of benchmark/tests brings the entry it rehearses), else
    of benchmark/entries/. Missing, or lacking a part, is a sentence here
    and not a traceback in the first experiment."""
    beside = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(config_path))), "entries")
    places = [d for d in dict.fromkeys((beside, entries.__path__[0]))
              if os.path.commonpath((d, CHECKOUT)) == CHECKOUT
              and os.path.isfile(os.path.join(d, name + ".py"))]
    if not name.isidentifier() or not places:
        has = sorted(f[:-3] for f in os.listdir(entries.__path__[0])
                     if f.endswith(".py") and f != "__init__.py")
        raise SystemExit(
            f"benchmark: the configuration {config_path} names the entry "
            f"{name!r}, and there is no benchmark/entries/{name}.py "
            f"(has: {has})")
    package = os.path.relpath(places[0], CHECKOUT).replace(os.sep, ".")
    module = importlib.import_module(f"{package}.{name}")
    missing = [part for part in ENTRY_PARTS if not hasattr(module, part)]
    if missing:
        raise SystemExit(
            f"benchmark: the entry {name!r} ({module.__file__}) lacks "
            f"{missing}; benchmark/README.md, 'An entry point', says what "
            "each is")
    return module


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration's file
    traffic_name: str
    traffic: dict           # the traffic mix's file
    entry_name: str         # the configuration's `entry`
    entry: ModuleType       # benchmark/entries/<entry_name>.py
    end_to_end: list[dict]  # manifest entries, each with its metric file
    per_layer: list[dict]   # manifest entries, each with its metric file

    @property
    def spans(self) -> list[str]:
        """Every callable that one of the cell's per-layer metrics reads a
        host span of, as "<module>:<Class>.<attribute>"."""
        return sorted({s for m in self.per_layer
                       for s in m["spec"].get("params", {}).get("spans", [])})


def _in_cell(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(workload: str, manifest_path: str | None = None) -> Cell:
    manifest_path = manifest_path or os.path.join(CHECKOUT, "BENCHMARK.json")
    man = _load(manifest_path)
    entry = _named(man["workloads"], workload, "workload")
    cfg_entry = _named(man["configs"], entry["config"], "configuration")
    traffic_path = os.path.join(BENCH_DIR, "traffic", entry["traffic"] + ".json")
    per_layer = []
    for m in man["per_layer"]:
        if _in_cell(m, workload):
            spec = _load(os.path.join(BENCH_DIR, "layer_metrics",
                                      m["name"] + ".json"))
            per_layer.append({**m, "spec": spec})
    config_path = os.path.join(CHECKOUT, cfg_entry["file"])
    config = _load(config_path)
    entry_name = config.get("entry", entries.DEFAULT)
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config_name=entry["config"], config=config,
        traffic_name=entry["traffic"], traffic=_load(traffic_path),
        entry_name=entry_name, entry=load_entry(entry_name, config_path),
        end_to_end=[
            {**m, "spec": _load(os.path.join(BENCH_DIR, "end_to_end",
                                             m["name"] + ".json"))}
            for m in man["end_to_end"] if _in_cell(m, workload)],
        per_layer=per_layer)


def reader(kind: str):
    """benchmark/readers/<kind>.py's `read(ctx, **params)`."""
    return importlib.import_module(f"benchmark.readers.{kind}").read


def statistic(spec: dict, samples: dict) -> float:
    """An end-to-end metric: the statistic its file names, of the samples
    its file names. Always of all the samples of the window."""
    import numpy as np

    values = samples[spec["of"]]
    if spec["statistic"] == "median":
        return float(np.median(values))
    if spec["statistic"] == "percentile":
        return float(np.percentile(values, spec["q"]))
    if spec["statistic"] == "value" and len(values) == 1:
        return float(values[0])
    raise SystemExit(f"benchmark: {spec['name']}: no statistic "
                     f"{spec['statistic']!r} of {len(values)} sample(s)")


def peaks(device_kind: str) -> dict:
    table = _load(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SystemExit(f"benchmark: device kind {device_kind!r} is not in "
                         "benchmark/peaks.json; add it with its source")
    return table["devices"][device_kind]
