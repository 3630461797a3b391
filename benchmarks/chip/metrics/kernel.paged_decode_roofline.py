"""The paged decode attention kernel's share of its roofline: the least
time the chip needs for the decode tokens the traced window produced
(every cached key and value of each token's true context read once, the
query read and the output written once; FLOPs likewise, from costs.py),
over the kernel's device time in the decode step (device trace).  What
the kernel fetches beyond the true contexts (free slots, pages past a
slot's length) counts against it.  Memory-bound at any context: 4 FLOPs
per cached byte over 8 KV heads."""

KERNEL = ("_fused_decode", "decode_attention_paged_pallas")


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    calls = ctx.trace["calls"].get(KERNEL)
    contexts, _ = ctx.work
    if not calls or not contexts:
        return None
    flops = nbytes = 0
    for c in contexts:
        f, b = ctx.costs.paged_decode_cost(ctx.m, c, shards=ctx.chips)
        flops += f
        nbytes += b
    least, _ = ctx.costs.least_time(flops, nbytes, ctx.peaks)
    return 100.0 * least / sum(c[0] for c in calls)
