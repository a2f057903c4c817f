"""From the profiler's trace to device metrics: the reduction every PR
computes in the same way.

`load_events` flattens an .xplane.pb (read with jax.profiler.ProfileData)
into plain rows; everything after it works on rows, so that
benchmark/tests checks the arithmetic on a small recorded trace kept as
JSON beside it.

A row is {"plane", "line", "name", "start_ns", "dur_ns"}. On a TPU the
device planes are "/device:TPU:<i>"; their "XLA Modules" line holds one
event per executed program, named "<module>(<fingerprint>)", and their
"XLA Ops" line one event per operation. Host annotations
(jax.profiler.TraceAnnotation) lie on the "/host:CPU" plane under the names
the benchmark gave them.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def load_events(trace_dir: str, keep_host_prefix: str) -> list[dict]:
    """Device module and op events, and the host annotations that start with
    `keep_host_prefix`, of the newest trace under `trace_dir`."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    rows = []
    for plane in ProfileData.from_file(files[-1]).planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name not in (MODULE_LINE, OP_LINE):
                continue
            for e in line.events:
                if device or e.name.startswith(keep_host_prefix):
                    rows.append({"plane": plane.name, "line": line.name,
                                 "name": e.name,
                                 "start_ns": float(e.start_ns),
                                 "dur_ns": float(e.duration_ns)})
    return rows


def device_planes(rows) -> list[str]:
    return sorted({r["plane"] for r in rows
                   if r["plane"].startswith(DEVICE_PLANE_PREFIX)})


def _union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def _clip(intervals, windows):
    out = []
    for lo, hi in intervals:
        for wlo, whi in windows:
            a, b = max(lo, wlo), min(hi, whi)
            if b > a:
                out.append((a, b))
    return out


def windows(rows, annotation: str) -> list[tuple[float, float]]:
    """The spans of the traced experiments, on the trace's clock."""
    return sorted((r["start_ns"], r["start_ns"] + r["dur_ns"])
                  for r in rows
                  if r["plane"] == HOST_PLANE and r["name"] == annotation)


def busy_intervals(rows, plane: str, wins) -> list[tuple[float, float]]:
    """Union of the plane's op intervals (module intervals where the trace
    has no op line), clipped to the windows."""
    ops = [(r["start_ns"], r["start_ns"] + r["dur_ns"]) for r in rows
           if r["plane"] == plane and r["line"] == OP_LINE]
    if not ops:
        ops = [(r["start_ns"], r["start_ns"] + r["dur_ns"]) for r in rows
               if r["plane"] == plane and r["line"] == MODULE_LINE]
    return _union(_clip(ops, wins))


def busy_and_window_s(rows, wins) -> tuple[float, float]:
    """Seconds in which an operation ran on the device, averaged over the
    device planes, and the summed length of the windows."""
    planes = device_planes(rows)
    window = sum(hi - lo for lo, hi in wins)
    if not planes:
        return 0.0, window / 1e9
    busy = [sum(hi - lo for lo, hi in busy_intervals(rows, p, wins))
            for p in planes]
    return sum(busy) / len(busy) / 1e9, window / 1e9


def module_seconds(rows, wins, module: str | None = None) -> dict[str, float]:
    """Device seconds per XLA module inside the windows, averaged over the
    device planes; the module's name is what stands before "(". With
    `module`, only that one."""
    planes = device_planes(rows)
    out: dict[str, float] = {}
    for r in rows:
        if r["line"] != MODULE_LINE or r["plane"] not in planes:
            continue
        name = r["name"].split("(")[0]
        if module is not None and name != module:
            continue
        inside = _clip([(r["start_ns"], r["start_ns"] + r["dur_ns"])], wins)
        out[name] = out.get(name, 0.0) + sum(b - a for a, b in inside)
    return {k: v / len(planes) / 1e9 for k, v in out.items()}


def idle_gaps(rows, wins, host_prefix: str, top: int = 10):
    """The longest idle gaps of the first device plane inside the windows,
    each named by the innermost host annotation that covers its middle;
    gaps of one name are summed. Returns [[name, seconds], ...]."""
    planes = device_planes(rows)
    if not planes:
        return []
    busy = busy_intervals(rows, planes[0], wins)
    host = [(r["start_ns"], r["start_ns"] + r["dur_ns"], r["name"])
            for r in rows if r["plane"] == HOST_PLANE
            and r["name"].startswith(host_prefix)]
    gaps = []
    for wlo, whi in wins:
        edge = wlo
        for lo, hi in busy:
            if hi <= wlo or lo >= whi:
                continue
            if lo > edge:
                gaps.append((edge, lo))
            edge = max(edge, hi)
        if whi > edge:
            gaps.append((edge, whi))
    by_name: dict[str, float] = {}
    for lo, hi in gaps:
        mid = (lo + hi) / 2
        cover = [(h_hi - h_lo, name) for h_lo, h_hi, name in host
                 if h_lo <= mid <= h_hi]
        name = min(cover)[1] if cover else "(no host span)"
        by_name[name] = by_name.get(name, 0.0) + (hi - lo) / 1e9
    return [[n, s] for n, s in sorted(by_name.items(),
                                      key=lambda kv: -kv[1])[:top]]


def breakdown(rows, wins, host_prefix: str) -> dict:
    mods = module_seconds(rows, wins)
    return {"device_ops": [[n, s] for n, s in sorted(
                mods.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": idle_gaps(rows, wins, host_prefix)}
