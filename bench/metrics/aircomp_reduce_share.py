"""aircomp_reduce_share (%): the share of the traced window in which the
AirComp aggregation kernel ran, averaged over the chips: how much of a
round the aggregation costs, beside its roofline."""

KERNEL = "aircomp_reduce"


def read(ctx):
    seconds = ctx["trace"]["op_s"].get(KERNEL, 0.0)
    if seconds <= 0 or ctx["window_s"] <= 0:
        return None
    return 100.0 * seconds / ctx["chips"] / ctx["window_s"]
