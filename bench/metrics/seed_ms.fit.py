"""Device time under the ``kpynq/seed`` scope, k-means++'s draws, per
traced fit (every fit seeds once). None where the program names no such
scope."""


def read(ctx):
    t = ctx.trace.scope_seconds("kpynq/seed")
    if not t:
        return None
    return t * 1e3 / ctx.counters["fits"]
