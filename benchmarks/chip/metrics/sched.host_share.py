"""Share of the window in which the engine was neither prefilling nor
decoding, by its own clock around each device call that it waits for:
scheduling, admission bookkeeping, the client and waiting for work."""


def read(ctx):
    busy = ctx.stats["prefill_s"] + ctx.stats["decode_s"]
    return 100.0 * (1.0 - busy / ctx.window_s)
