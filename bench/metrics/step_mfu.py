"""step_mfu (%): the loss-forward FLOPs the rounds of the traced window
require, per second of the window, over the chips' bf16 peak. The program
computes in float32 at "highest" precision, several bfloat16 passes per
product, so this share is bounded well below 100 %."""


def read(ctx):
    if ctx["window_s"] <= 0 or ctx["rounds"] <= 0:
        return None
    rate = ctx["flops_per_round"] * ctx["rounds"] / ctx["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peak"]["bf16_flops"])
