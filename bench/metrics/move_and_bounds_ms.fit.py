"""Device time under the ``kpynq/move_and_bounds`` scope (its ``reduce``
and ``refresh`` scopes included) per iteration of the traced fits."""


def read(ctx):
    t = ctx.trace.scope_seconds("kpynq/move_and_bounds")
    if not t:
        return None
    return t * 1e3 / ctx.counters["iterations"]
