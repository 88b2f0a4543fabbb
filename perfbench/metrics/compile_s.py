"""Seconds of backend compiles during set-up, persistent-cache loads
included (`jax.monitoring`'s backend-compile durations)."""


def read(ctx):
    return ctx.setup["compile_s"]
