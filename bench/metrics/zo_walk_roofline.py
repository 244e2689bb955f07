"""zo_walk_roofline (%): the time the zo_walk kernel's bytes need at the
chip's peak HBM bandwidth, over the kernel's device time in the trace (both
summed over the chips); see costs.kernel_bytes_per_round. The kernel's
Threefry direction generation counts as no bytes and no FLOPs, so a kernel
bound by it reads low."""

KERNEL = "zo_walk"


def read(ctx):
    per_round = ctx["kernel_bytes_per_round"].get(KERNEL)
    seconds = ctx["trace"]["op_s"].get(KERNEL, 0.0)
    if not per_round or seconds <= 0:
        return None
    need = per_round * ctx["rounds"] / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * need / seconds
