"""Model FLOPs of the real (unpadded, not reused) prompt tokens the
traced window prefilled over the device time of the prefill and tail
prefill programs x chips x peak bf16 FLOP/s (device trace; FLOPs from
costs.py)."""

PROGRAMS = ("_fused_prefill", "_fused_extend")


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    t = sum(ctx.trace["module_s"].get(p, 0.0) for p in PROGRAMS)
    _, prefill = ctx.work
    if not t or not prefill:
        return None
    flops = sum(ctx.costs.prefill_flops(ctx.m, s, n) for s, n in prefill)
    return 100.0 * flops / (t * ctx.chips * ctx.peaks["bf16_flops"])
