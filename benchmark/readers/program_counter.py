"""A counter the program attached to its `sim:<annotation>` annotations,
over the publishes of the traced experiments: its mean, or with
`statistic` "share" the percentage of them in which it is nonzero."""

from benchmark.harness import program_profile


def read(ctx, annotation, counter, statistic="mean"):
    profile = program_profile.load()
    if not profile or not ctx.trace_windows:
        return None
    values = program_profile.counter_values(
        profile, ctx.trace_windows, annotation, counter)
    if not values:
        return None
    if statistic == "share":
        return 100.0 * sum(v != 0 for v in values) / len(values)
    return sum(values) / len(values)
