"""Seconds in which no operation ran on the device (busy as
`harness/trace.busy_intervals` defines it) while the program was inside the
named spans, per traced experiment: device trace and program spans on the
profiler's one clock."""

from benchmark.harness import program_profile, trace


def read(ctx, names):
    profile = program_profile.load()
    planes = trace.device_planes(ctx.trace_rows or [])
    if not profile or not ctx.trace_windows or not planes:
        return None
    wins = ctx.trace_windows
    found = trace._union(trace._clip(
        program_profile.span_intervals(profile, wins, names), wins))
    if not found:
        return None
    busy = trace.busy_intervals(ctx.trace_rows, planes[0], found)
    idle = sum(b - a for a, b in found) - sum(b - a for a, b in busy)
    return idle / 1e9 / len(wins)
