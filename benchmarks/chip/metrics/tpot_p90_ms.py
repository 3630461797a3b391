"""90th percentile over every request with two or more tokens in the
window of (time of its last token - time of its first) / (tokens - 1),
as the client saw them (host clock)."""


def read(ctx):
    return ctx.e2e["tpot_p90_ms"]
