"""Host syncs per fit: ``KMeans.stats_.host_syncs`` over the window's
fits — each a device-to-host wait inside ``engine.fit`` (the group
table, bucket switches, the exit)."""


def read(ctx):
    return ctx.counters["host_syncs"] / ctx.counters["fits"]
