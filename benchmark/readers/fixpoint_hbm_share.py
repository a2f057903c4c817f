"""Share of the chip's HBM bandwidth that the publish's fixpoint loops
achieve, in percent: the bytes their bodies must move over the device
seconds of the loops.

The metric file holds the operand table: per loop, the counter that counts
its iterations and the HBM operands of one iteration of its body, each once,
as {"dims": [...], "bytes": itemsize, "times": reads + writes} with dims
from `peers` (N), `slots` (C) and `rounds` (gossip rounds) of the publish;
an operand with "if": "rounds" exists only with gossip on. The arithmetic is
here: bytes = sum over publishes of fragments x iterations x sum over
operands of times x bytes x prod(dims); seconds = device time under the
loops' scope paths; share = 100 x bytes / seconds / the peak of
benchmark/peaks.json for the device the profile is from."""

from benchmark.harness import manifest, program_profile, trace


def body_bytes(operands, shape) -> float:
    total = 0.0
    for op in operands:
        if "if" in op and not shape[op["if"]]:
            continue
        size = op["bytes"] * op.get("times", 1)
        for dim in op["dims"]:
            size *= shape[dim]
        total += size
    return total


def read(ctx, module, scopes, annotation, formulation, loops):
    profile = program_profile.load()
    if (not profile or not ctx.trace_windows
            or not trace.device_planes(ctx.trace_rows or [])):
        return None
    wins = ctx.trace_windows
    publishes = program_profile.host_rows(profile, wins, annotation)
    publishes = [p["attrs"] for p in publishes
                 if p["attrs"].get("formulation") == formulation]
    if not publishes:
        return None
    moved = 0.0
    for attrs in publishes:
        shape = {k: int(attrs[k]) for k in ("peers", "slots", "rounds")}
        for loop in loops:
            moved += (int(attrs["fragments"]) * int(attrs[loop["counter"]])
                      * body_bytes(loop["operands"], shape))
    seconds = program_profile.scope_seconds(
        profile, wins, module, [loop["path"] for loop in loops], scopes)
    if seconds is None or sum(seconds) <= 0.0:
        return None
    import jax

    peak = manifest.peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * moved / sum(seconds) / peak
