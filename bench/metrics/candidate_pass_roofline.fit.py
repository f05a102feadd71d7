"""Share of the roofline of the whole ``kpynq/candidate_pass`` scope:
the least time any implementation of the traced fits' candidate passes
could take on this chip (``roofline.py``: the larger of their bytes over
HBM bandwidth and their flops over the float32-at-HIGHEST rate) over
the device time the scope took. A fit of ``n_iter`` iterations runs
``n_iter + 1`` passes (the loop's and the epilogue's); its admitted
distance evaluations are ``distance_evals - N*K`` (the first, dense
assignment runs outside the scope)."""
import roofline


def read(ctx):
    t = ctx.trace.scope_seconds("kpynq/candidate_pass")
    if ctx.platform != "tpu" or not t:
        return None
    pk = roofline.peaks(ctx.device_kind)
    c = ctx.counters
    n, k, d, g = c["n_points"], c["n_clusters"], ctx.config["n_dims"], \
        ctx.config["n_groups"]
    passes = c["iterations"] + c["fits"]
    evals = c["distance_evals"] - c["fits"] * float(n) * k
    least, _ = roofline.least_seconds(
        roofline.candidate_pass_flops(d, evals),
        passes * roofline.candidate_pass_bytes(n, d, g), pk)
    return 100.0 * least / t
