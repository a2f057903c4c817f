"""Host seconds in the program's own spans (`sim:<name>` annotations of
runtime/profiling.span, on the profiler's clock), per traced experiment."""

from benchmark.harness import program_profile, trace


def read(ctx, names):
    profile = program_profile.load()
    if not profile or not ctx.trace_windows:
        return None
    wins = ctx.trace_windows
    found = program_profile.span_intervals(profile, wins, names)
    if not found:
        return None
    return sum(b - a for a, b in trace._clip(found, wins)) / 1e9 / len(wins)
