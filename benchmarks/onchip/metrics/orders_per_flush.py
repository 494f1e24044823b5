"""Orders per micro-batch flush over the window, from the service's own
counters (``ServiceStats.scored`` over ``ServiceStats.flushes``)."""


def read(ctx, metric):
    if not ctx.stats.get("flushes"):
        return None
    return ctx.stats["scored"] / ctx.stats["flushes"]
