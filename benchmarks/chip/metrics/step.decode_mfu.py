"""Model FLOPs of the decode tokens the traced window produced (2 x the
weights multiplied through per token, plus attention over each token's
true context) over the decode step's device time x chips x peak bf16
FLOP/s (device trace; FLOPs from costs.py)."""


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    t = ctx.trace["module_s"].get("_fused_decode")
    contexts, _ = ctx.work
    if not t or not contexts:
        return None
    flops = ctx.costs.decode_flops(ctx.m, contexts)
    return 100.0 * flops / (t * ctx.chips * ctx.peaks["bf16_flops"])
