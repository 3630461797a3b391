"""The two-pass softmax kernel's share of its roofline in the prefill and
tail-prefill programs: for each of its calls, the least time to read the
scores once and write the probabilities once (costs.py, from the shape
of the call's output in the device trace), over the device time of both
passes.  It also counts the one-row sampler softmax each admission
runs."""

PROGRAMS = ("_fused_prefill", "_fused_extend")
KERNEL = "twopass_softmax_2d"
BYTES = {"f32": 4, "bf16": 2}


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    least = secs = 0.0
    for prog in PROGRAMS:
        for dur, dtype, dims in ctx.trace["calls"].get((prog, KERNEL), []):
            secs += dur
            if len(dims) == 2 and dims[1] > 1:      # pass 2: [rows, cols]
                f, b = ctx.costs.softmax_cost(dims[0], dims[1],
                                              BYTES[dtype], BYTES[dtype])
                least += ctx.costs.least_time(f, b, ctx.peaks)[0]
    if not secs:
        return None
    return 100.0 * least / secs
