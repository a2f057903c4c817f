"""Device seconds of one XLA module, per traced experiment."""

from benchmark.harness import trace


def read(ctx, module):
    if not ctx.trace_rows or not ctx.trace_windows:
        return None
    seconds = trace.module_seconds(ctx.trace_rows, ctx.trace_windows, module)
    if module not in seconds:
        return None
    return seconds[module] / len(ctx.trace_windows)
