"""Host seconds inside the named spans, per traced experiment."""

from benchmark.harness.spans import span_name


def read(ctx, spans):
    if not ctx.experiments:
        return None
    names = {span_name(s) for s in spans}
    total = sum(ctx.recorder.seconds(names, w) for w in ctx.experiments)
    return total / len(ctx.experiments)
