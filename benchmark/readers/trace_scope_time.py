"""Device seconds of one XLA module's ops under one program scope
(`jax.named_scope`), per traced experiment; with `rest_of`, the module's
seconds outside all of the scopes named. `scopes` are the program's
top-level scopes: a program that has none of them gives None."""

from benchmark.harness import program_profile


def read(ctx, module, scopes, scope=None, rest_of=None):
    profile = program_profile.load()
    if not profile or not ctx.trace_windows:
        return None
    wins = ctx.trace_windows
    seconds = program_profile.scope_seconds(
        profile, wins, module, [[n] for n in scopes], scopes)
    if seconds is None or not any(seconds):
        return None      # the module did not run, or it has no such scope
    by_scope = dict(zip(scopes, seconds))
    if scope:
        return by_scope[scope] / len(wins)
    whole = program_profile.module_seconds(profile, wins, module)
    return (whole - sum(by_scope[n] for n in rest_of)) / len(wins)
