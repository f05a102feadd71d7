"""Device time under the ``kpynq/centroid_sums`` scope, the per-cluster
scatter-add of the points and their counts inside
``engine.move_and_bounds``, per iteration of the traced fits. None where
the program names no such scope."""


def read(ctx):
    t = ctx.trace.scope_seconds("kpynq/centroid_sums")
    if not t:
        return None
    return t * 1e3 / ctx.counters["iterations"]
