"""One key of the fullest device's memory_stats(), as read when the window
closed."""


def read(ctx, key, divide_by=1):
    values = [stats.get(key) for stats in ctx.memory_stats]
    values = [v for v in values if v is not None]
    if not values:
        return None
    return max(values) / divide_by
