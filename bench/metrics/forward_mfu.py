"""forward_mfu (%): the local queries' forward FLOPs over the device time
under the program's ``fedzo.forward`` scope (summed over the chips) times
the chip's bf16 peak. The FLOPs are (b2 + 1) loss forwards of b1 samples
per iterate, H iterates, M clients a round, at the configuration's FLOPs
per sample, which ``flops_per_round`` holds together with the in-scan
eval's forwards (``costs.forward_flops_per_round``); the eval's share is
taken back out, since the eval runs outside the scope. The program
computes in float32 at "highest", several bfloat16 passes per product, so
this share is bounded well below 100 %. Empty for a program that
registers no such scope."""
from scope_reduce import program_scopes, scope_share

SCOPE = "fedzo.forward"


def read(ctx):
    if SCOPE not in program_scopes():
        return None
    share = scope_share(ctx, SCOPE)
    if not share or ctx["rounds"] <= 0:
        return None
    fz = ctx["fz"]
    local = (fz["b2"] + 1) * fz["local_iters"] * fz["n_participating"] \
        * fz["b1"]
    evals = 2 * fz["eval_rows"] / fz["eval_every"]
    flops = ctx["flops_per_round"] * local / (local + evals) * ctx["rounds"]
    seconds = share / 100.0 * ctx["chips"] * ctx["window_s"]
    return 100.0 * flops / (seconds * ctx["peak"]["bf16_flops"])
