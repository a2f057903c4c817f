"""What the program itself wrote into the traced run's profile: its device
scopes, its host spans and its counters (ISSUE 27).

`harness/trace.load_events` keeps only the benchmark's own `bench:`
annotations and no event metadata, so the readers that need more
(`readers/trace_scope_time.py`, `program_span.py`, `program_counter.py`,
`trace_scope_per_count.py`, `fixpoint_hbm_share.py`,
`span_device_idle.py`) load the `.xplane.pb` once more through `load()`,
found the way `load_events` finds it and read once per process. Everything
after `load()` works on plain rows, so that benchmark/tests checks the
arithmetic on a small recorded profile kept as JSON beside it:

    ops      {"plane", "name", "start_ns", "dur_ns", "scope"}   XLA Ops line
    modules  {"plane", "name", "start_ns", "dur_ns"}            XLA Modules line
    host     {"name", "start_ns", "dur_ns", "attrs"}            "sim:" annotations

An op's `scope` is the program's scope path of the HLO instruction, the
`jax.named_scope` names joined by "/" as JAX wrote them into the
instruction's `op_name` (for example
"jit(disseminate)/fast/vmap(fixpoint)/while/body/..."); on a TPU v5e with
jax 0.9.0 the profile carries it in the op event's SCOPE_STATS stat (PERF.md
section 6 says how that was found). Where the stat is missing the scope is
"" and the op counts as unscoped.

A program that has none of this (the parent of the PR that added it) gives
rows without scopes and no `sim:` annotation: every reader then returns
None.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re

from benchmark.harness import trace

PROGRAM_PREFIX = "sim:"
# the stats of an XLA op event that may hold the instruction's op_name, in
# order of preference
SCOPE_STATS = ("tf_op",)
WORK_ROOT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".bench_work")


def find_xplane(work_root: str = WORK_ROOT) -> str | None:
    """The newest .xplane.pb of a traced window under the work directory
    (`run.py` keeps one cell's there while it reduces it)."""
    files = glob.glob(os.path.join(
        work_root, "*", "trace", "plugins", "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


@functools.lru_cache(maxsize=1)
def _xspace_class():
    """The XSpace message of the profiler's xplane.proto, described here as
    far as the rows need it (`jax.profiler.ProfileData` shows an event's own
    stats but not those of its metadata, where the scope is)."""
    from google.protobuf import (
        descriptor_pb2, descriptor_pool, message_factory)

    file = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    kinds = descriptor_pb2.FieldDescriptorProto
    int64, string = kinds.TYPE_INT64, kinds.TYPE_STRING
    messages = {
        "XStat": [("metadata_id", 1, int64), ("double_value", 2,
                  kinds.TYPE_DOUBLE), ("uint64_value", 3, kinds.TYPE_UINT64),
                  ("int64_value", 4, int64), ("str_value", 5, string),
                  ("bytes_value", 6, kinds.TYPE_BYTES),
                  ("ref_value", 7, kinds.TYPE_UINT64)],
        "XEvent": [("metadata_id", 1, int64), ("offset_ps", 2, int64),
                   ("duration_ps", 3, int64), ("stats", 4, "*XStat")],
        "XLine": [("name", 2, string), ("timestamp_ns", 3, int64),
                  ("events", 4, "*XEvent")],
        "XEventMetadata": [("id", 1, int64), ("name", 2, string),
                           ("stats", 5, "*XStat")],
        "XStatMetadata": [("id", 1, int64), ("name", 2, string)],
        "EventMetadataEntry": [("key", 1, int64),
                               ("value", 2, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, int64),
                              ("value", 2, "XStatMetadata")],
        "XPlane": [("name", 2, string), ("lines", 3, "*XLine"),
                   ("event_metadata", 4, "*EventMetadataEntry"),
                   ("stat_metadata", 5, "*StatMetadataEntry")],
        "XSpace": [("planes", 1, "*XPlane")],
    }
    for name, fields in messages.items():
        message = file.message_type.add(name=name)
        for field, number, kind in fields:
            of_message = isinstance(kind, str)
            added = message.field.add(
                name=field, number=number,
                type=kinds.TYPE_MESSAGE if of_message else kind,
                label=(kinds.LABEL_REPEATED if of_message
                       and kind.startswith("*") else kinds.LABEL_OPTIONAL))
            if of_message:
                added.type_name = ".bench_xplane." + kind.lstrip("*")
            elif field.endswith("_value"):     # XStat's `oneof value`
                if not message.oneof_decl:
                    message.oneof_decl.add(name="value")
                added.oneof_index = 0
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _stat_value(stat, stat_names: dict):
    which = stat.WhichOneof("value")
    if which == "ref_value":       # a string kept once, as a stat's name
        return stat_names.get(stat.ref_value, "")
    return getattr(stat, which) if which else ""


def rows_from_xplane(path: str) -> dict:
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    ops, modules, host = [], [], []
    for plane in space.planes:
        device = plane.name.startswith(trace.DEVICE_PLANE_PREFIX)
        if not device and plane.name != trace.HOST_PLANE:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        metadata = {e.key: e.value for e in plane.event_metadata}
        scope_of = {}       # event metadata id -> scope
        for line in plane.lines:
            if device and line.name not in (trace.MODULE_LINE, trace.OP_LINE):
                continue
            for e in line.events:
                meta = metadata[e.metadata_id]
                row = {"name": meta.name,
                       "start_ns": line.timestamp_ns + e.offset_ps / 1e3,
                       "dur_ns": e.duration_ps / 1e3}
                if not device:
                    if meta.name.startswith(PROGRAM_PREFIX):
                        row["attrs"] = {
                            stat_names[s.metadata_id]:
                                str(_stat_value(s, stat_names))
                            for s in e.stats}
                        host.append(row)
                    continue
                row["plane"] = plane.name
                if line.name == trace.MODULE_LINE:
                    modules.append(row)
                    continue
                if e.metadata_id not in scope_of:
                    stats = {stat_names[s.metadata_id]:
                             str(_stat_value(s, stat_names))
                             for s in meta.stats
                             if stat_names[s.metadata_id] in SCOPE_STATS}
                    scope_of[e.metadata_id] = next(
                        (stats[k] for k in SCOPE_STATS if k in stats), "")
                row["scope"] = scope_of[e.metadata_id]
                ops.append(row)
    return {"ops": ops, "modules": modules, "host": host}


@functools.lru_cache(maxsize=1)
def load() -> dict | None:
    """The rows of the process's traced window, or None where there is no
    profile to read."""
    path = find_xplane()
    return rows_from_xplane(path) if path else None


# ---------------------------------------------------------- device scopes

_WRAPPED = re.compile(r"^(?:\w+\()*([^()]*)\)*$")


@functools.lru_cache(maxsize=None)
def scope_names(scope: str) -> tuple[str, ...]:
    """The components of a scope path with the transforms JAX wraps around
    them taken off: "jit(f)/fast/vmap(fixpoint)/while" gives
    ("f", "fast", "fixpoint", "while")."""
    matches = ((part, _WRAPPED.match(part)) for part in scope.split("/"))
    return tuple(m.group(1) if m else part for part, m in matches)


def follows(scope: str, path: list[str], known: list[str]) -> bool:
    """Whether the op lies under `path`: its outermost component among
    `known` (the program's top-level scopes) is path[0], and path[1:]
    come after it in order."""
    names = scope_names(scope)
    first = next((i for i, n in enumerate(names) if n in known), None)
    if first is None or names[first] != path[0]:
        return False
    at = first
    for want in path[1:]:
        try:
            at = names.index(want, at + 1)
        except ValueError:
            return False
    return True


def self_intervals(ops: list[dict]):
    """Yield (op, lo, hi) so that every instant of a plane's device time
    goes to the innermost op event that covers it: a `while` keeps only
    what its body's events leave. `ops` are one plane's."""
    open_ops: list[list] = []      # [end, cursor, op], innermost last

    def close(entry):
        end, cursor, op = entry
        if end > cursor:
            yield op, cursor, end

    for op in sorted(ops, key=lambda r: (r["start_ns"], -r["dur_ns"])):
        start, end = op["start_ns"], op["start_ns"] + op["dur_ns"]
        while open_ops and open_ops[-1][0] <= start:
            yield from close(open_ops.pop())
        if open_ops:
            outer = open_ops[-1]
            if start > outer[1]:
                yield outer[2], outer[1], start
            outer[1] = max(outer[1], min(outer[0], end))
        open_ops.append([end, start, op])
    while open_ops:
        yield from close(open_ops.pop())


def _module_of(modules: list[tuple], t: float) -> str | None:
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return modules[i][2]
    return None


def owned(profile: dict) -> list[tuple]:
    """(op, lo, hi, module) for every piece of device time an op event owns
    (see `self_intervals`), with the XLA module it ran in. Computed once
    and kept in the profile under "owned"."""
    if "owned" not in profile:
        pieces = []
        for plane in sorted({r["plane"] for r in profile["modules"]}):
            mods = sorted((r["start_ns"], r["start_ns"] + r["dur_ns"],
                           r["name"].split("(")[0])
                          for r in profile["modules"] if r["plane"] == plane)
            ops = [r for r in profile["ops"] if r["plane"] == plane]
            pieces += [(op, lo, hi, _module_of(mods, op["start_ns"]))
                       for op, lo, hi in self_intervals(ops)]
        profile["owned"] = pieces
    return profile["owned"]


def scope_seconds(profile: dict, wins, module: str, paths: list[list[str]],
                  known: list[str]) -> list[float] | None:
    """Device seconds inside the windows of the ops of XLA module `module`
    under each of `paths` (see `follows`), averaged over the device planes.
    None where the module did not run."""
    planes = {r["plane"] for r in profile["modules"]
              if r["name"].split("(")[0] == module}
    if not planes:
        return None
    totals = [0.0] * len(paths)
    for op, lo, hi, ran_in in owned(profile):
        if ran_in != module or not op["scope"]:
            continue
        inside = sum(b - a for a, b in trace._clip([(lo, hi)], wins))
        if inside > 0.0:
            for i, path in enumerate(paths):
                if follows(op["scope"], path, known):
                    totals[i] += inside
    return [t / len(planes) / 1e9 for t in totals]


def module_seconds(profile: dict, wins, module: str) -> float | None:
    rows = [{**r, "line": trace.MODULE_LINE} for r in profile["modules"]]
    return trace.module_seconds(rows, wins, module).get(module)


# ------------------------------------------------- host spans and counters


def host_rows(profile: dict, wins, name: str) -> list[dict]:
    """The program's annotations of that name (without the prefix) that
    start inside a window, in order of time."""
    rows = [r for r in profile["host"]
            if r["name"] == PROGRAM_PREFIX + name
            and any(lo <= r["start_ns"] < hi for lo, hi in wins)]
    return sorted(rows, key=lambda r: r["start_ns"])


def span_intervals(profile: dict, wins, names: list[str]):
    return [(r["start_ns"], r["start_ns"] + r["dur_ns"])
            for name in names for r in host_rows(profile, wins, name)]


def counter_values(profile: dict, wins, annotation: str,
                   counter: str) -> list[float]:
    return [float(r["attrs"][counter])
            for r in host_rows(profile, wins, annotation)
            if counter in r["attrs"]]
