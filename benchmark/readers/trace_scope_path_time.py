"""Device seconds of one XLA module's ops under one program scope PATH
(`jax.named_scope` names, outermost first: `path[0]` one of the program's
top-level `scopes`, the rest sub-scopes in order, as
`program_profile.follows` reads them), per traced experiment. A program
that has no op under the path gives None."""

from benchmark.harness import program_profile


def read(ctx, module, scopes, path):
    profile = program_profile.load()
    if not profile or not ctx.trace_windows:
        return None
    wins = ctx.trace_windows
    seconds = program_profile.scope_seconds(
        profile, wins, module, [path], scopes)
    if seconds is None or seconds[0] <= 0.0:
        return None      # the module did not run, or it has no such scope
    return seconds[0] / len(wins)
