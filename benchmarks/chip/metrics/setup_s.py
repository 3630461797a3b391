"""Seconds from the process's start to the window's: device init,
weights made on the device, every program compiled or loaded from the
cache and run once, set-up traffic (host clock)."""


def read(ctx):
    return ctx.setup_s
