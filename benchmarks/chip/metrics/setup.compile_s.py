"""Seconds of set-up spent tracing, lowering and compiling programs or
loading them from the persistent cache (jax.monitoring)."""


def read(ctx):
    return ctx.setup_compile_s
