"""Seconds in which an operation ran on the device, per traced experiment,
divided by a counter the program attached to a `sim:<annotation>`
annotation (its mean over the traced experiments): the device's time per
unit of what the experiment batched. A program that writes no such
annotation gives None."""

from benchmark.harness import program_profile, trace


def read(ctx, annotation, counter):
    profile = program_profile.load()
    if not profile or not ctx.trace_rows or not ctx.trace_windows:
        return None
    counts = program_profile.counter_values(
        profile, ctx.trace_windows, annotation, counter)
    busy, _ = trace.busy_and_window_s(ctx.trace_rows, ctx.trace_windows)
    if not counts or not sum(counts) or busy <= 0.0:
        return None
    return busy / len(ctx.trace_windows) / (sum(counts) / len(counts))
