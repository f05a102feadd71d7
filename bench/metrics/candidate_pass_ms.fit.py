"""Device time under the ``kpynq/candidate_pass`` scope per iteration of
the traced fits (the epilogue's final pass included)."""


def read(ctx):
    t = ctx.trace.scope_seconds("kpynq/candidate_pass")
    if not t:
        return None
    return t * 1e3 / ctx.counters["iterations"]
