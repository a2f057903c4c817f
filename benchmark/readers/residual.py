"""Host seconds of a traced experiment outside the named spans."""

from benchmark.harness.spans import span_name


def read(ctx, spans):
    if not ctx.experiments:
        return None
    names = {span_name(s) for s in spans}
    total = sum((w[1] - w[0]) - ctx.recorder.seconds(names, w)
                for w in ctx.experiments)
    return total / len(ctx.experiments)
