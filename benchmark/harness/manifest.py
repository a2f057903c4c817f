"""Find everything that belongs to a cell by the names in BENCHMARK.json.

    cell     -> BENCHMARK.json workloads[name]
    config   -> the `file` of BENCHMARK.json configs[cell.config]
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


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration's file
    traffic_name: str
    traffic: dict           # the traffic mix's file
    end_to_end: list[dict]  # manifest entries, each with its metric file
    per_layer: list[dict]   # manifest entries, each with its metric file

    @property
    def argv(self) -> dict:
        """The `run` positionals and flags: the configuration's, with what
        the traffic mix overrides."""
        run = self.config["run"]
        positionals = dict(run["positionals"])
        positionals.update(self.traffic.get("positionals", {}))
        flags = list(run.get("flags", [])) + list(self.traffic.get("flags", []))
        return {"positionals": positionals, "flags": flags}

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
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config_name=entry["config"],
        config=_load(os.path.join(CHECKOUT, cfg_entry["file"])),
        traffic_name=entry["traffic"], traffic=_load(traffic_path),
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
