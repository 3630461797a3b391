"""Programs compiled or loaded inside the measured window: a shape that
set-up left cold shows here (jax.monitoring).  Should read 0."""


def read(ctx):
    return ctx.window_compiles
