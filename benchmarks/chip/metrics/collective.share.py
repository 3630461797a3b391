"""Share of the decode step's device time that its collectives take on
device 0: the self time of the collective ops (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all) inside ``_fused_decode``
over that program's device time (device trace).  An asynchronous
collective is counted by the self time of its start and done ops, not
by the span between them, so an exchange hidden under compute reads
low.  None where the trace holds no decode program or no collective in
it (one chip)."""


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace["module_s"].get("_fused_decode")
    c = ctx.trace["collective_s"].get("_fused_decode")
    if not t or not c:
        return None
    return 100.0 * c / t
