"""Device time of one program scope over a program counter: `scale` x
seconds under `path` / the counter summed over the publishes of the traced
experiments (for example milliseconds per refinement pass). Where the
program counted nothing in any of them, `none_counted`."""

from benchmark.harness import program_profile


def read(ctx, module, scopes, path, annotation, counter, scale=1.0,
         none_counted=None):
    profile = program_profile.load()
    if not profile or not ctx.trace_windows:
        return None
    wins = ctx.trace_windows
    counts = program_profile.counter_values(
        profile, wins, annotation, counter)
    seconds = program_profile.scope_seconds(
        profile, wins, module, [path], scopes)
    if seconds is None or not counts:
        return None
    if sum(counts) <= 0:
        return none_counted
    return scale * seconds[0] / sum(counts)
