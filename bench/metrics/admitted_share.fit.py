"""Share of the point-centroid pairs the window's candidate passes
evaluated, against a dense pass's N*K each: a fit of ``n_iter``
iterations runs ``n_iter + 1`` passes, and its first, dense assignment
(``N*K`` of its ``distance_evals_``) runs before them."""


def read(ctx):
    c = ctx.counters
    dense = float(c["n_points"]) * c["n_clusters"]
    admitted = c["distance_evals"] - c["fits"] * dense
    return 100.0 * admitted / (dense * (c["iterations"] + c["fits"]))
