"""90th percentile over every request due in the window of the time
from when it was due to when the client saw its first token; one still
unserved when the window closes counts its wait so far (host clock)."""


def read(ctx):
    return ctx.e2e["ttft_p90_ms"]
