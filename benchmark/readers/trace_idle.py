"""Share of the traced experiments' span in which no operation ran on the
device, in percent."""

from benchmark.harness import trace


def read(ctx):
    if not ctx.trace_rows or not ctx.trace_windows:
        return None
    busy, window = trace.busy_and_window_s(ctx.trace_rows, ctx.trace_windows)
    if window <= 0.0 or busy <= 0.0:
        return None
    return 100.0 * (1.0 - busy / window)
